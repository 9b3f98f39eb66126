"""AOT bucket-ladder compilation + safe executable persistence (ISSUE 6).

Covers: ladder enumeration against the retrace-guard bound, AOT vs lazy-JIT
bit-exact step parity (incl. the compressed data-parallel arm), warm-path
zero-compile dispatch, bundle round-trips, corrupt/version/backend rejection
falling back to clean recompile, checkpoint resume restoring executables,
and validation-gated persistence (default OFF on XLA:CPU)."""

import json
import os
import pickle
import zipfile
import zlib

import numpy as np
import pytest

import jax

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.analysis import retrace_guard
from deeplearning4j_tpu.nn import aot
from deeplearning4j_tpu.nn.graph import (
    ComputationGraph,
    ComputationGraphConfiguration,
)
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
from deeplearning4j_tpu.nn.memory import memory_report
from deeplearning4j_tpu.nn.model import (
    MultiLayerConfiguration,
    MultiLayerNetwork,
)
from deeplearning4j_tpu.train import resilience
from deeplearning4j_tpu.utils import bucketing


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    for var in ("DL4J_TPU_AOT", "DL4J_TPU_AOT_BUNDLE", "DL4J_TPU_BUCKETING",
                "DL4J_TPU_BUCKETS", "DL4J_TPU_BUCKET_MIN",
                "DL4J_TPU_BUCKET_GROWTH", "DL4J_TPU_RETRACE_GUARD",
                "DL4J_TPU_STRICT_RETRACE"):
        monkeypatch.delenv(var, raising=False)
    # AOT warming is the subject here, not an ambient accelerant; the
    # chained-dispatch path opts out of per-step AOT by design
    monkeypatch.setenv("DL4J_TPU_CHAIN_STEPS", "0")
    bucketing.telemetry().reset()
    retrace_guard.reset_aot_warmed()
    retrace_guard.reset_warnings()
    yield
    retrace_guard.reset_aot_warmed()
    bucketing.telemetry().reset()


def _conf(seed=1):
    return MultiLayerConfiguration(
        layers=(Dense(n_out=8, activation="tanh"),
                OutputLayer(n_out=2, activation="softmax")),
        input_type=InputType.feed_forward(4),
        updater={"type": "sgd", "lr": 0.1},
        seed=seed,
    )


def _mln(seed=1):
    return MultiLayerNetwork(_conf(seed)).init()


def _gconf():
    return (ComputationGraphConfiguration.builder()
            .add_inputs("in")
            .set_input_types(InputType.feed_forward(4))
            .add_layer("d", Dense(n_out=8, activation="tanh"), "in")
            .add_layer("out", OutputLayer(n_out=2, activation="softmax"), "d")
            .set_outputs("out")
            .build())


def _data(n=20, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, 4).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rs.randint(0, 2, n)]
    return x, y


def _max_leaf_diff(a, b):
    return max(
        (float(np.abs(np.asarray(u) - np.asarray(v)).max())
         for u, v in zip(jax.tree_util.tree_leaves(a),
                         jax.tree_util.tree_leaves(b))),
        default=0.0)


def _allow_cpu_bundles(monkeypatch):
    """Persistence is opt-in on every backend (and the opt-in is trusted):
    the zip/manifest machinery runs under DL4J_TPU_AOT_BUNDLE=1."""
    monkeypatch.setenv("DL4J_TPU_AOT_BUNDLE", "1")


# ---------------------------------------------------------------------------
# Ladder enumeration <-> retrace-guard bound
# ---------------------------------------------------------------------------


class TestEnumeration:
    def test_reachable_buckets_exact(self):
        lad = bucketing.BucketLadder()
        assert aot.reachable_buckets(40, lad) == [1, 2, 4, 8, 16, 32, 64]
        # boundary walk == brute force over every n
        brute = sorted({lad.bucket(n) for n in range(1, 41)})
        assert aot.reachable_buckets(40, lad) == brute

    def test_reachable_buckets_custom_rungs(self):
        lad = bucketing.BucketLadder(rungs=(8, 16, 24))
        assert aot.reachable_buckets(24, lad) == [8, 16, 24]
        brute = sorted({lad.bucket(n) for n in range(1, 25)})
        assert aot.reachable_buckets(24, lad) == brute

    def test_warmed_buckets_extend_guard_bound(self, monkeypatch):
        """AOT warming with NO traffic must not trip the guard: warmed
        buckets are unioned into the predicted-compile bound."""
        monkeypatch.setenv("DL4J_TPU_AOT", "1")
        monkeypatch.setenv("DL4J_TPU_STRICT_RETRACE", "1")
        m = _mln()
        aot.warm_serving(m, 16)
        buckets = aot.reachable_buckets(16)
        assert retrace_guard.aot_warmed_buckets("mln.output") == frozenset(buckets)
        tel = bucketing.telemetry()
        assert tel.compiles("mln.output") == len(buckets)
        # the bound holds with zero recorded hits...
        assert retrace_guard.check("mln.output").ok
        # ...and a real dispatch through a warmed bucket stays within it
        m.output(np.zeros((3, 4), np.float32))
        assert tel.compiles("mln.output") == len(buckets)

    def test_guard_still_fires_beyond_warmed_set(self, monkeypatch):
        """Cross-check in the other direction: compiles beyond the warmed
        set + traffic stay a guard violation."""
        monkeypatch.setenv("DL4J_TPU_STRICT_RETRACE", "1")
        tel = bucketing.telemetry()
        retrace_guard.register_aot_warmed("site.x", [8])
        tel.record_trace("site.x", (8,))
        tel.record_trace("site.x", (8,))  # second compile, one bucket
        with pytest.raises(retrace_guard.RetraceError):
            retrace_guard.check("site.x")


# ---------------------------------------------------------------------------
# AOT vs lazy-JIT parity
# ---------------------------------------------------------------------------


class TestWarmParity:
    def test_fit_parity_mln(self, monkeypatch):
        data = _data()
        lazy = _mln()
        lazy.fit(data, epochs=2, batch_size=8)

        monkeypatch.setenv("DL4J_TPU_AOT", "1")
        warm = _mln()
        tel = bucketing.telemetry()
        tel.reset()
        warm.fit(data, epochs=2, batch_size=8)
        assert _max_leaf_diff(lazy.params, warm.params) == 0.0
        assert _max_leaf_diff(lazy.opt_state, warm.opt_state) == 0.0
        # one executable serves full AND padded-tail batches, warmed ahead
        assert tel.compiles("mln.step") == 1
        snap = obs.registry().snapshot()
        assert snap["dl4j_aot_warm_hits_total"]["site=mln.step"] >= 6

    def test_fit_parity_cg(self, monkeypatch):
        data = _data()
        lazy = ComputationGraph(_gconf()).init()
        lazy.fit(data, epochs=2, batch_size=8)

        monkeypatch.setenv("DL4J_TPU_AOT", "1")
        warm = ComputationGraph(_gconf()).init()
        tel = bucketing.telemetry()
        tel.reset()
        warm.fit(data, epochs=2, batch_size=8)
        assert _max_leaf_diff(lazy.params, warm.params) == 0.0
        assert tel.compiles("cg.step") == 1

    def test_dp_compressed_parity(self, monkeypatch):
        """The grad-exchange variant: warm_dp pre-compiles the shard_map
        step of a compressed DataParallelStep; dispatch hits it (zero
        further compiles) and matches the un-warmed runner bit-exactly."""
        from jax.sharding import Mesh

        from deeplearning4j_tpu.parallel.grads import DataParallelStep

        x, y = _data(16)
        mesh = Mesh(np.array(jax.devices()), ("data",))

        lazy = _mln()
        dp_lazy = DataParallelStep(lazy, mesh, compress=True)
        dp_lazy.begin()
        dp_lazy.fit_batch(x, y, None, None)
        dp_lazy.finish()

        monkeypatch.setenv("DL4J_TPU_AOT", "1")
        warm = _mln()
        dp_warm = DataParallelStep(warm, mesh, compress=True)
        tel = bucketing.telemetry()
        tel.reset()
        aot.warm_dp(dp_warm, x, y)
        assert tel.compiles("mln.step") == 1
        dp_warm.fit_batch(x, y, None, None)
        dp_warm.finish()
        assert tel.compiles("mln.step") == 1  # dispatch was a warm hit
        snap = obs.registry().snapshot()
        assert snap["dl4j_aot_warm_hits_total"]["site=dp.step"] >= 1
        assert _max_leaf_diff(lazy.params, warm.params) == 0.0

    def test_warm_serving_zero_compile_output(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_AOT", "1")
        m = _mln()
        tel = bucketing.telemetry()
        tel.reset()
        warmed = aot.warm_serving(m, 16)
        assert warmed == len(aot.reachable_buckets(16))
        c0 = tel.compiles("mln.output")
        for n in (1, 3, 7, 16):  # every bucket <= the warm target
            m.output(np.zeros((n, 4), np.float32))
        assert tel.compiles("mln.output") == c0

    def test_parallel_inference_warmup(self, monkeypatch):
        from deeplearning4j_tpu.parallel.inference import ParallelInference

        monkeypatch.setenv("DL4J_TPU_AOT", "1")
        m = _mln()
        tel = bucketing.telemetry()
        tel.reset()
        pi = ParallelInference(m, max_batch_size=8)
        try:
            c0 = tel.compiles("mln.output")
            assert c0 == len(aot.reachable_buckets(8))
            out = pi.output(np.zeros((3, 4), np.float32))
            assert out.shape == (3, 2)
            assert tel.compiles("mln.output") == c0
        finally:
            pi.shutdown()

    def test_aot_off_by_default(self):
        """No env knob -> fit takes the plain lazy path (no phantom bucket
        hits, no warm-hit counters)."""
        obs.reset()
        m = _mln()
        tel = bucketing.telemetry()
        tel.reset()
        m.fit(_data(16), epochs=1, batch_size=8)
        assert tel.compiles("mln.step") == 1
        snap = obs.registry().snapshot()
        assert not (snap.get("dl4j_aot_warm_hits_total") or {}).get(
            "site=mln.step")


# ---------------------------------------------------------------------------
# Bundles: round trip + rejection fallbacks
# ---------------------------------------------------------------------------


class TestBundles:
    def _warm_model_with_bundle(self, tmp_path, monkeypatch):
        _allow_cpu_bundles(monkeypatch)
        monkeypatch.setenv("DL4J_TPU_AOT", "1")
        m = _mln()
        m.fit(_data(), epochs=1, batch_size=8)
        path = str(tmp_path / "exec.aotbundle")
        info = aot.save_bundle(m, path)
        assert info is not None and info["entries"] >= 1
        assert os.path.exists(path)
        return m, path

    def test_round_trip_zero_compiles(self, tmp_path, monkeypatch):
        m, path = self._warm_model_with_bundle(tmp_path, monkeypatch)
        with zipfile.ZipFile(path) as zf:
            manifest = json.loads(zf.read("manifest.json"))
        assert manifest["format_version"] == aot.BUNDLE_FORMAT_VERSION
        assert manifest["backend"] == jax.default_backend()
        assert manifest["model_signature"] == aot.model_signature(m)

        fresh = _mln()
        assert aot.restore_bundle(fresh, path) >= 1
        tel = bucketing.telemetry()
        tel.reset()
        fresh.fit(_data(), epochs=1, batch_size=8)
        assert tel.compiles("mln.step") == 0  # restored executable served
        # and the restored executable's math matches a lazy-compiled one
        lazy = _mln()
        lazy.fit(_data(), epochs=1, batch_size=8)
        assert _max_leaf_diff(lazy.params, fresh.params) == 0.0

    def test_missing_bundle_is_silent_noop(self, tmp_path):
        obs.reset()
        assert aot.restore_bundle(_mln(), str(tmp_path / "nope.aotbundle")) == 0
        snap = obs.registry().snapshot()
        assert not snap.get("dl4j_aot_bundle_rejected_total")

    def test_corrupt_bundle_rejected_then_recompiles(self, tmp_path, monkeypatch):
        m, path = self._warm_model_with_bundle(tmp_path, monkeypatch)
        with open(path, "r+b") as f:  # flip a byte inside an entry payload
            f.seek(os.path.getsize(path) // 2)
            b = f.read(1)
            f.seek(-1, os.SEEK_CUR)
            f.write(bytes([b[0] ^ 0xFF]))
        obs.reset()
        fresh = _mln()
        assert aot.restore_bundle(fresh, path) == 0
        snap = obs.registry().snapshot()
        assert sum((snap["dl4j_aot_bundle_rejected_total"]).values()) == 1
        # clean fallback: training works, recompiling lazily
        tel = bucketing.telemetry()
        tel.reset()
        fresh.fit(_data(), epochs=1, batch_size=8)
        assert tel.compiles("mln.step") == 1

    def _rewrite_manifest(self, path, mutate):
        with zipfile.ZipFile(path) as zf:
            manifest = json.loads(zf.read("manifest.json"))
            entries = {n: zf.read(n) for n in zf.namelist()
                       if n != "manifest.json"}
        mutate(manifest)
        with zipfile.ZipFile(path, "w") as zf:
            zf.writestr("manifest.json", json.dumps(manifest))
            for n, blob in entries.items():
                zf.writestr(n, blob)

    @pytest.mark.parametrize("field,value,reason", [
        ("jaxlib_version", "0.0.0", "version_mismatch"),
        ("backend", "tpu", "backend_mismatch"),
        ("format_version", 999, "format_version"),
        ("model_signature", "deadbeef", "model_signature"),
    ])
    def test_manifest_mismatch_rejected(self, tmp_path, monkeypatch,
                                        field, value, reason):
        _, path = self._warm_model_with_bundle(tmp_path, monkeypatch)
        self._rewrite_manifest(path, lambda man: man.__setitem__(field, value))
        obs.reset()
        fresh = _mln()
        assert aot.restore_bundle(fresh, path) == 0
        snap = obs.registry().snapshot()
        assert snap["dl4j_aot_bundle_rejected_total"] == {f"reason={reason}": 1}
        # rejection is clean: the model still trains (lazy recompile)
        fresh.fit(_data(8), epochs=1)

    def test_entry_crc_mismatch_rejected(self, tmp_path, monkeypatch):
        m, path = self._warm_model_with_bundle(tmp_path, monkeypatch)
        with zipfile.ZipFile(path) as zf:
            manifest = json.loads(zf.read("manifest.json"))
            entries = {n: zf.read(n) for n in zf.namelist()
                       if n != "manifest.json"}
        name = manifest["entries"][0]["name"]
        rec = pickle.loads(entries[name])
        rec["payload"] = rec["payload"][:-1] + bytes(
            [rec["payload"][-1] ^ 1])
        entries[name] = pickle.dumps(rec)  # valid pickle, wrong CRC
        with zipfile.ZipFile(path, "w") as zf:
            zf.writestr("manifest.json", json.dumps(manifest))
            for n, blob in entries.items():
                zf.writestr(n, blob)
        obs.reset()
        assert aot.restore_bundle(_mln(), path) == 0
        snap = obs.registry().snapshot()
        assert snap["dl4j_aot_bundle_rejected_total"] == {
            "reason=crc_mismatch": 1}

    def test_saved_restored_counters_and_events(self, tmp_path, monkeypatch):
        obs.reset()
        ev0 = dict(obs.snapshot()["events"])  # event counts don't reset
        _, path = self._warm_model_with_bundle(tmp_path, monkeypatch)
        aot.restore_bundle(_mln(), path)
        snap = obs.registry().snapshot()
        assert snap["dl4j_aot_bundle_saved_total"] == {"": 1}
        assert snap["dl4j_aot_bundle_restored_total"] == {"": 1}
        ev = obs.snapshot()["events"]
        assert ev.get("aot_bundle_saved", 0) == ev0.get("aot_bundle_saved", 0) + 1
        assert ev.get("aot_bundle_restored", 0) == ev0.get("aot_bundle_restored", 0) + 1


# ---------------------------------------------------------------------------
# Persistence gating (the PR 4 XLA:CPU lesson)
# ---------------------------------------------------------------------------


class TestPersistenceGating:
    def test_default_is_off(self, monkeypatch):
        """auto (the default) never persists, on any backend."""
        monkeypatch.delenv("DL4J_TPU_AOT_BUNDLE", raising=False)
        assert not aot.persistence_allowed()
        monkeypatch.setenv("DL4J_TPU_AOT_BUNDLE", "auto")
        assert not aot.persistence_allowed()

    def test_mode_zero_never_persists(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_AOT_BUNDLE", "0")
        assert not aot.persistence_allowed()

    def test_gated_off_falls_back_to_recompile(self, tmp_path, monkeypatch):
        """Without the opt-in: save is a no-op, restore rejects, training
        recompiles; nothing crashes."""
        monkeypatch.setenv("DL4J_TPU_AOT", "1")
        m = _mln()
        m.fit(_data(), epochs=1, batch_size=8)
        path = str(tmp_path / "gated.aotbundle")
        assert aot.save_bundle(m, path) is None
        assert not os.path.exists(path)
        # a bundle produced under the opt-in is likewise refused without it
        monkeypatch.setenv("DL4J_TPU_AOT_BUNDLE", "1")
        assert aot.save_bundle(m, path) is not None
        monkeypatch.delenv("DL4J_TPU_AOT_BUNDLE")
        obs.reset()
        fresh = _mln()
        assert aot.restore_bundle(fresh, path) == 0
        snap = obs.registry().snapshot()
        assert snap["dl4j_aot_bundle_rejected_total"] == {
            "reason=persistence_disabled": 1}
        fresh.fit(_data(8), epochs=1)  # clean recompile, no crash

    def test_opt_in_is_trusted_and_starts_no_process(self, monkeypatch):
        """One process per chip: the gate validates nothing at run time and
        spawns nothing; it says so once in an aot_validation event."""
        import subprocess as sp

        def no_children(*a, **kw):
            raise AssertionError("persistence gate spawned a process")

        monkeypatch.setattr(sp, "run", no_children)
        monkeypatch.setattr(sp, "Popen", no_children)
        monkeypatch.setattr(aot, "_opt_in_announced", False)
        monkeypatch.setenv("DL4J_TPU_AOT_BUNDLE", "1")
        seen = []
        monkeypatch.setattr(
            obs, "event", lambda kind, **f: seen.append((kind, f)))
        assert aot.persistence_allowed() and aot.persistence_allowed()
        ev = [f for k, f in seen if k == "aot_validation"]
        assert len(ev) == 1 and ev[0]["mode"] == "opt_in_trusted"

    def test_parity_harness(self):
        """The standalone harness body (python -m deeplearning4j_tpu.nn.aot):
        serialize->deserialize->execute bitwise parity on this backend —
        with 8 local devices, so a one-device executable must load onto
        its own device, not all of them."""
        result = aot._selftest()
        assert result["ok"] and all(c["parity"] for c in result["cases"])


# ---------------------------------------------------------------------------
# Checkpoint integration: resume restores params AND executables
# ---------------------------------------------------------------------------


class TestCheckpointIntegration:
    def test_resume_restores_executables(self, tmp_path, monkeypatch):
        from deeplearning4j_tpu.train.checkpoint import CheckpointListener

        _allow_cpu_bundles(monkeypatch)
        monkeypatch.setenv("DL4J_TPU_AOT", "1")
        data = _data()
        m = _mln()
        m.set_listeners(CheckpointListener(
            tmp_path, save_every_n_epochs=1, delete_existing=True))
        m.fit(data, epochs=1, batch_size=8)
        cp = CheckpointListener.last_valid_checkpoint(tmp_path)
        assert cp is not None
        bundle = aot.bundle_path_for(os.path.join(str(tmp_path), cp.filename))
        assert os.path.exists(bundle)

        fresh = _mln(seed=99)
        tel = bucketing.telemetry()
        tel.reset()
        assert resilience.resume(fresh, tmp_path) is not None
        assert _max_leaf_diff(m.params, fresh.params) == 0.0
        # the first post-resume step dispatches a RESTORED executable
        fresh.fit(data, epochs=1, batch_size=8)
        assert tel.compiles("mln.step") == 0
        snap = obs.registry().snapshot()
        assert snap["dl4j_aot_warm_hits_total"]["site=mln.step"] >= 3

    def test_checkpoint_without_bundle_still_resumes(self, tmp_path):
        """Bundle persistence off (CPU default): checkpoints and resume
        behave exactly as before — the sidecar simply doesn't exist."""
        from deeplearning4j_tpu.train.checkpoint import CheckpointListener

        m = _mln()
        m.set_listeners(CheckpointListener(
            tmp_path, save_every_n_epochs=1, delete_existing=True))
        m.fit(_data(), epochs=1, batch_size=8)
        assert not [f for f in os.listdir(tmp_path)
                    if f.endswith(".aotbundle")]
        fresh = _mln(seed=99)
        assert resilience.resume(fresh, tmp_path) is not None
        assert _max_leaf_diff(m.params, fresh.params) == 0.0


# ---------------------------------------------------------------------------
# memory_report double-compile fix
# ---------------------------------------------------------------------------


class TestMemoryReportCache:
    def test_report_warms_not_recompiles_mln(self):
        m = _mln()
        tel = bucketing.telemetry()
        tel.reset()
        memory_report(m, batch_size=16)
        assert tel.compiles("mln.output") == 1
        assert tel.compiles("mln.step") == 1
        memory_report(m, batch_size=16)  # second report: pure cache hits
        assert tel.compiles("mln.output") == 1
        assert tel.compiles("mln.step") == 1
        # the analyzed executables ARE the serving ones
        m.output(np.zeros((16, 4), np.float32))
        m.fit(_data(16), epochs=1)
        assert tel.compiles("mln.output") == 1
        assert tel.compiles("mln.step") == 1

    def test_report_warms_not_recompiles_cg(self):
        g = ComputationGraph(_gconf()).init()
        tel = bucketing.telemetry()
        tel.reset()
        memory_report(g, batch_size=16)
        memory_report(g, batch_size=16)
        assert tel.compiles("cg.output") == 1
        assert tel.compiles("cg.step") == 1


# ---------------------------------------------------------------------------
# Dispatcher internals
# ---------------------------------------------------------------------------


class TestDispatcher:
    def test_signature_key_distinguishes_shapes_dtypes(self):
        k1 = aot.signature_key((np.zeros((4, 2), np.float32),), {})
        k2 = aot.signature_key((np.zeros((8, 2), np.float32),), {})
        k3 = aot.signature_key((np.zeros((4, 2), np.int32),), {})
        k4 = aot.signature_key((np.zeros((4, 2), np.float32),), {"a": None})
        assert len({k1, k2, k3, k4}) == 4
        assert k1 == aot.signature_key((np.zeros((4, 2), np.float32),), {})

    def test_clear_compiled_drops_step_not_output(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_AOT", "1")
        m = _mln()
        aot.warm_serving(m, 8)
        m.fit(_data(16), epochs=1, batch_size=8)
        assert "mln.step" in m._aot_fns and "mln.output" in m._aot_fns
        m._clear_compiled()
        assert "mln.step" not in m._aot_fns
        assert "mln.output" in m._aot_fns

    def test_unwarmed_wrapper_is_passthrough(self):
        from deeplearning4j_tpu.nn.step_program import StepProgram

        m = _mln()
        step = m._get_step_fn(False)
        assert isinstance(step, StepProgram)
        assert isinstance(step._fn, aot.AotFunction)
        assert step.compiled_count == 0
        m.fit(_data(8), epochs=1)  # dispatches through the lazy jit
        assert bucketing.telemetry().compiles("mln.step") == 1
