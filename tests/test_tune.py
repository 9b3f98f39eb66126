"""Auto-tuner (deeplearning4j_tpu/tune): knob registry, tuning DB, search
determinism, online apply — and the enabling perf feature, gradient-
accumulation micro-batching (DL4J_TPU_GRAD_ACCUM), whose parity with the
un-accumulated step is the guarantee that makes it safe to tune.

No test here spawns a real trial subprocess (tier-1 stays fast); the
subprocess plumbing is exercised end-to-end by tools/tune_smoke.sh and the
bench tuner arm. Search logic is driven through an in-process stub runner.
"""

import json
import os
import warnings
import zipfile

import numpy as np
import pytest

from deeplearning4j_tpu import tune
from deeplearning4j_tpu.nn import aot
from deeplearning4j_tpu.nn.graph import (
    ComputationGraph,
    ComputationGraphConfiguration,
)
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
from deeplearning4j_tpu.nn.model import (
    MultiLayerConfiguration,
    MultiLayerNetwork,
)
from deeplearning4j_tpu.tune import db as tune_db
from deeplearning4j_tpu.tune import knobs as tune_knobs
from deeplearning4j_tpu.tune import search as tune_search
from deeplearning4j_tpu.tune import trial as tune_trial


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    # tune.maybe_apply writes its winners into os.environ for good, behind
    # monkeypatch's back: put the environment back as it was, or the next
    # test file in this worker trains with DL4J_TPU_GRAD_ACCUM=4
    before = dict(os.environ)
    for k in tune_knobs.KNOBS:
        monkeypatch.delenv(k.env, raising=False)
    monkeypatch.delenv("DL4J_TPU_TUNE", raising=False)
    monkeypatch.delenv("DL4J_TPU_TUNE_DB", raising=False)
    # parity must compare the same dispatch shape; chaining is its own knob
    monkeypatch.setenv("DL4J_TPU_CHAIN_STEPS", "0")
    yield
    for k in set(os.environ) - set(before):
        del os.environ[k]
    os.environ.update(before)


_TC = {"jax_version": "0.9", "jaxlib_version": "0.9", "backend": "cpu"}


def _mln(seed=3, updater=None):
    conf = MultiLayerConfiguration(
        layers=(Dense(n_out=16, activation="tanh"),
                OutputLayer(n_out=3, activation="softmax")),
        input_type=InputType.feed_forward(8),
        updater=updater or {"type": "adam", "lr": 0.01},
        seed=seed,
    )
    return MultiLayerNetwork(conf).init()


def _cg(seed=3):
    conf = (ComputationGraphConfiguration.builder()
            .add_inputs("in")
            .set_input_types(InputType.feed_forward(8))
            .add_layer("d", Dense(n_out=16, activation="tanh"), "in")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax"), "d")
            .set_outputs("out")
            .updater({"type": "sgd", "lr": 0.1})
            .build())
    g = ComputationGraph(conf)
    g.init()
    return g


def _data(n=32, seed=0, feat=8, classes=3):
    rs = np.random.RandomState(seed)
    x = rs.rand(n, feat).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rs.randint(0, classes, n)]
    return x, y


def _leaves(m):
    import jax

    return [np.asarray(l) for l in jax.tree_util.tree_leaves(m.params)]


# ---------------------------------------------------------------------------
# Knob registry
# ---------------------------------------------------------------------------


class TestKnobRegistry:
    def test_round_trip_through_json(self):
        for k in tune_knobs.KNOBS:
            clone = tune_knobs.Knob.from_dict(json.loads(json.dumps(k.to_dict())))
            assert clone == k

    def test_defaults_are_in_domain_and_envs_unique(self):
        envs = [k.env for k in tune_knobs.KNOBS]
        assert len(envs) == len(set(envs))
        for k in tune_knobs.KNOBS:
            assert k.default in k.domain
            # the env encoding must round-trip every domain value exactly
            for v in k.domain:
                assert k.parse(k.format(v)) == v

    def test_registry_covers_the_issue_knob_space(self):
        names = {k.name for k in tune_knobs.KNOBS}
        assert {"bucket_min", "bucket_growth", "chain_steps", "rnn_unroll",
                "compress_threshold", "grad_accum"} <= names

    def test_validate_rejects_out_of_domain(self):
        k = tune_knobs.get("grad_accum")
        with pytest.raises(ValueError):
            k.validate(3)

    def test_scope_filtering(self):
        fit = {k.name for k in tune_knobs.all_knobs("fit")}
        serve = {k.name for k in tune_knobs.all_knobs("serve")}
        assert "grad_accum" in fit and "grad_accum" not in serve
        assert "rnn_unroll" in fit and "rnn_unroll" in serve


# ---------------------------------------------------------------------------
# Tuning DB
# ---------------------------------------------------------------------------


class TestTuningDB:
    def test_record_persist_lookup(self, tmp_path):
        db = tune_db.TuningDB(tmp_path / "tunedb.zip")
        db.record("sig", {"grad_accum": 4}, {"steps_per_sec": 12.5}, 7,
                  toolchain=_TC)
        # a fresh instance reads the file, not memory
        entry = tune_db.TuningDB(tmp_path / "tunedb.zip").lookup(
            "sig", toolchain=_TC)
        assert entry["knobs"] == {"grad_accum": 4}
        assert entry["objective"]["steps_per_sec"] == 12.5
        assert entry["trials"] == 7

    def test_crc_mismatch_rejects_whole_db(self, tmp_path):
        path = tmp_path / "tunedb.zip"
        db = tune_db.TuningDB(path)
        db.record("sig", {"grad_accum": 2}, {}, 1, toolchain=_TC)
        # rewrite the JSON entry without updating the CRC sidecar
        with zipfile.ZipFile(path, "r") as zf:
            raw = zf.read("tunedb.json")
            crc = zf.read("tunedb.json.crc32")
        doc = json.loads(raw)
        doc["entries"]["sig|cpu"]["knobs"]["grad_accum"] = 8
        with zipfile.ZipFile(path, "w") as zf:
            zf.writestr("tunedb.json", json.dumps(doc, sort_keys=True))
            zf.writestr("tunedb.json.crc32", crc)
        assert db.load() == {}
        assert db.lookup("sig", toolchain=_TC) is None

    def test_truncated_file_rejected(self, tmp_path):
        path = tmp_path / "tunedb.zip"
        db = tune_db.TuningDB(path)
        db.record("sig", {"grad_accum": 2}, {}, 1, toolchain=_TC)
        data = path.read_bytes()
        path.write_bytes(data[: len(data) // 2])
        assert db.load() == {}

    def test_stale_toolchain_rejected(self, tmp_path):
        db = tune_db.TuningDB(tmp_path / "tunedb.zip")
        db.record("sig", {"grad_accum": 4}, {}, 3, toolchain=_TC)
        bumped = dict(_TC, jax_version="99.0")
        assert db.lookup("sig", toolchain=bumped) is None
        assert db.lookup("sig", toolchain=bumped, allow_stale=True) is not None
        # the matching toolchain still resolves
        assert db.lookup("sig", toolchain=_TC)["knobs"] == {"grad_accum": 4}

    def test_backend_is_part_of_the_key(self, tmp_path):
        db = tune_db.TuningDB(tmp_path / "tunedb.zip")
        db.record("sig", {"grad_accum": 4}, {}, 1, toolchain=_TC)
        other = dict(_TC, backend="tpu")
        assert db.lookup("sig", toolchain=other) is None

    def test_unknown_knob_name_rejected_at_record(self, tmp_path):
        db = tune_db.TuningDB(tmp_path / "tunedb.zip")
        with pytest.raises(KeyError):
            db.record("sig", {"warp_factor": 9}, {}, 1, toolchain=_TC)


# ---------------------------------------------------------------------------
# Search: determinism + successive halving
# ---------------------------------------------------------------------------


class TestSearch:
    def test_enumeration_deterministic_and_default_first(self):
        a = tune_search.enumerate_configs(("grad_accum", "chain_steps"))
        b = tune_search.enumerate_configs(("chain_steps", "grad_accum"))
        assert a == b
        assert a[0] == {"chain_steps": "auto", "grad_accum": 1}
        # full cross product, no duplicates
        assert len(a) == len({json.dumps(c, sort_keys=True) for c in a})
        assert len(a) == len(tune_knobs.get("grad_accum").domain) * len(
            tune_knobs.get("chain_steps").domain)

    def test_overrides_narrow_but_stay_domain_checked(self):
        cfgs = tune_search.enumerate_configs(
            ("grad_accum",), overrides={"grad_accum": [2, 1]})
        assert cfgs == [{"grad_accum": 1}, {"grad_accum": 2}]
        with pytest.raises(ValueError):
            tune_search.enumerate_configs(
                ("grad_accum",), overrides={"grad_accum": [3]})

    def test_halving_runs_trials_in_deterministic_order(self):
        calls = []

        def runner(spec, config, timeout_s=0.0):
            calls.append((spec["steps"], json.dumps(config, sort_keys=True)))
            obj = {1: 10.0, 2: 30.0, 4: 20.0, 8: 5.0}[config["grad_accum"]]
            return tune_search.TrialResult(config=dict(config), objective=obj,
                                           ok=True)

        cfgs = tune_search.enumerate_configs(("grad_accum",))
        winner, history = tune_search.successive_halving(
            {"steps": 0}, cfgs, base_steps=4, runner=runner)
        assert winner.config == {"grad_accum": 2}
        # round 1: all 4 at 4 steps in enumeration order; round 2: top-2 at 8
        assert calls[:4] == [
            (4, '{"grad_accum": 1}'), (4, '{"grad_accum": 2}'),
            (4, '{"grad_accum": 4}'), (4, '{"grad_accum": 8}')]
        assert [c[0] for c in calls[4:]] == [8, 8]
        assert len(history) == len(calls)
        # a re-run makes identical decisions in the identical order
        first_run = list(calls)
        calls.clear()
        w2, _ = tune_search.successive_halving(
            {"steps": 0}, cfgs, base_steps=4, runner=runner)
        assert w2.config == winner.config
        assert calls == first_run

    def test_ties_break_toward_the_default(self):
        def runner(spec, config, timeout_s=0.0):
            return tune_search.TrialResult(config=dict(config), objective=1.0,
                                           ok=True)

        cfgs = tune_search.enumerate_configs(("grad_accum",))
        winner, _ = tune_search.successive_halving(
            {"steps": 0}, cfgs, base_steps=1, runner=runner)
        assert winner.config == {"grad_accum": 1}

    def test_failed_trials_sink(self):
        def runner(spec, config, timeout_s=0.0):
            if config["grad_accum"] == 1:
                return tune_search.TrialResult(config=dict(config),
                                               error="boom")
            return tune_search.TrialResult(
                config=dict(config), ok=True,
                objective=float(config["grad_accum"]))

        cfgs = tune_search.enumerate_configs(("grad_accum",))
        winner, _ = tune_search.successive_halving(
            {"steps": 0}, cfgs, base_steps=1, runner=runner)
        assert winner.config == {"grad_accum": 8}

    def test_tune_model_records_winner_in_db(self, tmp_path):
        model = _mln()

        def runner(spec, config, timeout_s=0.0):
            return tune_search.TrialResult(
                config=dict(config), ok=True,
                objective=100.0 + config["grad_accum"])

        db = tune_db.TuningDB(tmp_path / "tunedb.zip")
        entry = tune.tune_model(model, *_data(), knob_names=("grad_accum",),
                                db=db, runner=runner)
        assert entry["knobs"] == {"grad_accum": 8}
        assert entry["history"]
        stored = db.lookup(aot.model_signature(model))
        assert stored["knobs"] == {"grad_accum": 8}
        assert stored["toolchain"] == aot.toolchain_fingerprint()


# ---------------------------------------------------------------------------
# Online apply (DL4J_TPU_TUNE=auto)
# ---------------------------------------------------------------------------


class TestMaybeApply:
    def _seed_db(self, tmp_path, model, knobs):
        db = tune_db.TuningDB(tmp_path / "tunedb.zip")
        db.record(aot.model_signature(model), knobs, {}, 1,
                  toolchain=aot.toolchain_fingerprint())
        return db

    def test_off_by_default(self, tmp_path, monkeypatch):
        model = _mln()
        monkeypatch.setenv("DL4J_TPU_TUNE_DB", str(tmp_path / "tunedb.zip"))
        self._seed_db(tmp_path, model, {"grad_accum": 4})
        assert tune.maybe_apply(model, "fit") is None
        assert "DL4J_TPU_GRAD_ACCUM" not in os.environ

    def test_auto_applies_and_is_idempotent(self, tmp_path, monkeypatch):
        model = _mln()
        monkeypatch.setenv("DL4J_TPU_TUNE_DB", str(tmp_path / "tunedb.zip"))
        monkeypatch.setenv("DL4J_TPU_TUNE", "auto")
        self._seed_db(tmp_path, model, {"grad_accum": 4})
        applied = tune.maybe_apply(model, "fit")
        assert applied == {"DL4J_TPU_GRAD_ACCUM": "4"}
        assert os.environ["DL4J_TPU_GRAD_ACCUM"] == "4"
        # second call: env already set, nothing re-applied
        assert tune.maybe_apply(model, "fit") is None

    def test_explicit_user_env_wins(self, tmp_path, monkeypatch):
        model = _mln()
        monkeypatch.setenv("DL4J_TPU_TUNE_DB", str(tmp_path / "tunedb.zip"))
        monkeypatch.setenv("DL4J_TPU_TUNE", "auto")
        monkeypatch.setenv("DL4J_TPU_GRAD_ACCUM", "2")
        self._seed_db(tmp_path, model, {"grad_accum": 4})
        assert tune.maybe_apply(model, "fit") is None
        assert os.environ["DL4J_TPU_GRAD_ACCUM"] == "2"

    def test_scope_mismatch_not_applied(self, tmp_path, monkeypatch):
        model = _mln()
        monkeypatch.setenv("DL4J_TPU_TUNE_DB", str(tmp_path / "tunedb.zip"))
        monkeypatch.setenv("DL4J_TPU_TUNE", "auto")
        self._seed_db(tmp_path, model, {"grad_accum": 4, "rnn_unroll": 4})
        applied = tune.maybe_apply(model, "serve")
        # grad_accum is fit-scoped; only the both-scoped knob lands
        assert applied == {"DL4J_TPU_RNN_UNROLL": "4"}

    def test_fit_consults_db_under_auto(self, tmp_path, monkeypatch):
        model = _mln()
        monkeypatch.setenv("DL4J_TPU_TUNE_DB", str(tmp_path / "tunedb.zip"))
        monkeypatch.setenv("DL4J_TPU_TUNE", "auto")
        self._seed_db(tmp_path, model, {"grad_accum": 2})
        model.fit([_data(n=8)], epochs=1)
        assert os.environ["DL4J_TPU_GRAD_ACCUM"] == "2"


# ---------------------------------------------------------------------------
# Trial spec plumbing (no subprocess)
# ---------------------------------------------------------------------------


class TestTrialSpec:
    def test_build_spec_and_in_process_run(self):
        model = _mln()
        x, y = _data(n=16)
        spec = tune_trial.build_spec(model, x, y, steps=2, warmup_steps=1)
        assert spec["model_class"] == "MultiLayerNetwork"
        assert spec["features_shape"] == [16, 8]
        spec["knobs"] = {"grad_accum": 2}
        result = tune_trial.run_trial(spec)
        assert result["ok"] and result["steps_per_sec"] > 0

    def test_apply_knobs_writes_validated_envs(self):
        env = {}
        delta = tune_trial.apply_knobs({"grad_accum": 4,
                                        "chain_steps": "8"}, env)
        assert env == delta == {"DL4J_TPU_GRAD_ACCUM": "4",
                                "DL4J_TPU_CHAIN_STEPS": "8"}
        with pytest.raises(ValueError):
            tune_trial.apply_knobs({"grad_accum": 7}, {})


# ---------------------------------------------------------------------------
# Gradient-accumulation parity (the knob the tuner leans on hardest)
# ---------------------------------------------------------------------------


class TestGradAccumParity:
    """Accumulated step ≡ full-batch step in fp32 (equal-size micro-batches,
    mean-of-micro-means == full mean exactly). Models here carry no
    batch-coupled layers: BatchNorm statistics over 8-row micro-batches
    genuinely differ from 32-row full-batch statistics — that is the
    documented semantic of accumulation, not a parity bug."""

    def _fit(self, model, data, steps=3):
        for _ in range(steps):
            model.fit([data], epochs=1)
        return _leaves(model)

    @pytest.mark.parametrize("updater", [
        {"type": "sgd", "lr": 0.1},
        {"type": "adam", "lr": 0.01},
    ])
    def test_mln_parity(self, updater, monkeypatch):
        data = _data(n=32)
        base = self._fit(_mln(seed=5, updater=updater), data)
        monkeypatch.setenv("DL4J_TPU_GRAD_ACCUM", "4")
        accum = self._fit(_mln(seed=5, updater=updater), data)
        for a, b in zip(base, accum):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    def test_cg_parity(self, monkeypatch):
        data = _data(n=32)
        base = self._fit(_cg(seed=5), data)
        monkeypatch.setenv("DL4J_TPU_GRAD_ACCUM", "4")
        accum = self._fit(_cg(seed=5), data)
        for a, b in zip(base, accum):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    def test_dp_compressed_parity(self, monkeypatch):
        """Accumulation inside the donated step composes with the DP
        explicit-exchange compressed arm: micro-grads are averaged BEFORE
        the exchange, so the threshold codec sees the same mean gradient."""
        from deeplearning4j_tpu.parallel import (MeshSpec, ParallelWrapper,
                                                 make_mesh)

        data = _data(n=64)
        m1 = _mln(seed=5, updater={"type": "sgd", "lr": 0.1})
        ParallelWrapper(m1, mesh=make_mesh(MeshSpec(data=8)),
                        grad_compress=True,
                        compress_threshold=1e-3).fit(data, epochs=3)
        monkeypatch.setenv("DL4J_TPU_GRAD_ACCUM", "2")
        m2 = _mln(seed=5, updater={"type": "sgd", "lr": 0.1})
        ParallelWrapper(m2, mesh=make_mesh(MeshSpec(data=8)),
                        grad_compress=True,
                        compress_threshold=1e-3).fit(data, epochs=3)
        for a, b in zip(_leaves(m1), _leaves(m2)):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5)

    def test_non_divisible_batch_falls_back_with_warning(self, monkeypatch):
        from deeplearning4j_tpu.nn import step_program

        # the warn-once flag lives in the unified step-program module now
        monkeypatch.setattr(step_program, "_GRAD_ACCUM_WARNED", False)
        monkeypatch.setenv("DL4J_TPU_GRAD_ACCUM", "5")
        data = _data(n=32)  # 32 % 5 != 0
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            accum = self._fit(_mln(seed=5), data, steps=1)
        assert any("DL4J_TPU_GRAD_ACCUM" in str(w.message) for w in caught)
        # the fallback is the plain un-accumulated step, bit for bit
        monkeypatch.delenv("DL4J_TPU_GRAD_ACCUM")
        base = self._fit(_mln(seed=5), data, steps=1)
        for a, b in zip(base, accum):
            np.testing.assert_array_equal(a, b)

    def test_accum_is_engaged_not_vacuous(self, monkeypatch):
        """The accum=4 arm must actually run the scan path: its BN-free
        params match, but a model WITH BatchNorm must differ — proving the
        micro-batch semantics (and thus the scan) are live."""
        from deeplearning4j_tpu.nn.layers import BatchNorm

        def bn_model(seed=5):
            conf = MultiLayerConfiguration(
                layers=(Dense(n_out=16, activation="tanh"),
                        BatchNorm(),
                        OutputLayer(n_out=3, activation="softmax")),
                input_type=InputType.feed_forward(8),
                updater={"type": "sgd", "lr": 0.1},
                seed=seed,
            )
            return MultiLayerNetwork(conf).init()

        data = _data(n=32)
        base = self._fit(bn_model(), data, steps=2)
        monkeypatch.setenv("DL4J_TPU_GRAD_ACCUM", "4")
        accum = self._fit(bn_model(), data, steps=2)
        deltas = [np.max(np.abs(a - b)) for a, b in zip(base, accum)]
        assert max(deltas) > 1e-7
