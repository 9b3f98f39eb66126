"""Unified step program + named-mesh (data × tensor × stage) trainer
(ISSUE 13): parity of the one StepProgram against every path that now
instantiates it, mesh-shape parity on the 8-device CPU mesh, sharded
optimizer state, mesh knobs, and the zero-steady-state-recompile contract."""

import os

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.nn import aot
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
from deeplearning4j_tpu.nn.model import (
    MultiLayerConfiguration, MultiLayerNetwork,
)
from deeplearning4j_tpu.nn.step_program import (
    StepProgram, mesh_shape_from_env,
)
from deeplearning4j_tpu.parallel import (
    DataParallelStep, MeshSpec, MeshTrainer, make_mesh, shard_update_spec,
)
from deeplearning4j_tpu.utils import bucketing

MESH_ENVS = ("DL4J_TPU_MESH_DATA", "DL4J_TPU_MESH_MODEL",
             "DL4J_TPU_MESH_PIPE")


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    for var in MESH_ENVS + (
            "DL4J_TPU_GRAD_ACCUM", "DL4J_TPU_CHAIN_STEPS",
            "DL4J_TPU_GRAD_COMPRESS", "DL4J_TPU_SHARDED_UPDATE"):
        monkeypatch.delenv(var, raising=False)
    bucketing.telemetry().reset()
    yield


def _model(seed=3, updater=None, n_in=4, hidden=16):
    conf = MultiLayerConfiguration(
        layers=(
            Dense(n_out=hidden, activation="tanh"),
            OutputLayer(n_out=2, activation="softmax"),
        ),
        input_type=InputType.feed_forward(n_in),
        updater=updater or {"type": "sgd", "lr": 0.1},
        seed=seed,
    )
    return MultiLayerNetwork(conf).init()


def _data(n=64, seed=0, n_in=4):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, n_in).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[(x.sum(axis=1) > 0).astype(int)]
    return x, y


def _params_close(m1, m2, rtol=1e-5, atol=1e-6):
    for a, b in zip(jax.tree_util.tree_leaves(m1.params),
                    jax.tree_util.tree_leaves(m2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=atol)


def _fit_steps(trainer_fit_batch, x, y, steps=4, batch=64):
    losses = []
    for i in range(steps):
        lo, hi = 0, batch  # same full batch every step: pure parity probe
        losses.append(float(trainer_fit_batch(x[lo:hi], y[lo:hi])))
    return losses


# ---------------------------------------------------------------------------
# StepProgram: the one abstraction every path instantiates
# ---------------------------------------------------------------------------


class TestStepProgram:
    def test_wraps_and_dispatches(self):
        def body(a, b):
            return a + b, a * b

        sp = StepProgram(body, "test.step", donate_argnums=(), aot_wrap=False)
        s, p = sp.dispatch(np.float32(3.0), np.float32(4.0))
        assert float(s) == 7.0 and float(p) == 12.0

    def test_delegates_to_wrapped_fn(self):
        m = _model()
        sp = m._get_step_fn(False)
        assert isinstance(sp, StepProgram)
        # AotFunction surface stays reachable through the program
        assert hasattr(sp, "warm")
        assert sp.compiled_count >= 0

    def test_wrap_body_hook(self):
        seen = {}

        def body(a):
            return a * 2

        def wrap(fn):
            def wrapped(a):
                seen["called"] = True
                return fn(a)
            return wrapped

        sp = StepProgram(body, "test.wrap", donate_argnums=(),
                         aot_wrap=False, wrap_body=wrap)
        assert float(sp(np.float32(2.0))) == 4.0
        assert seen["called"]


# ---------------------------------------------------------------------------
# Parity: unified step vs the pre-existing paths
# ---------------------------------------------------------------------------


class TestUnifiedStepParity:
    @pytest.mark.parametrize("updater", [
        {"type": "sgd", "lr": 0.1},
        {"type": "adam", "lr": 0.01},
    ], ids=["sgd", "adam"])
    def test_mesh_matches_single_device(self, updater):
        """Pure-data mesh (8,1,1) == plain MLN fit on the full batch: the
        StepProgram body is the SAME function, GSPMD only shards it."""
        x, y = _data(64)
        m1 = _model(seed=5, updater=dict(updater))
        m2 = _model(seed=5, updater=dict(updater))
        l1 = _fit_steps(lambda a, b: m1._fit_batch(a, b, None, None), x, y)
        tr = MeshTrainer(m2, MeshSpec(data=8))
        l2 = _fit_steps(tr.fit_batch, x, y)
        np.testing.assert_allclose(l1, l2, rtol=1e-6, atol=1e-6)
        tr.finish()
        _params_close(m1, m2)

    def test_mesh_matches_dp_step(self):
        """MeshTrainer on (8,1,1) == the explicit shard_map exchange."""
        x, y = _data(64)
        m1 = _model(seed=7)
        m2 = _model(seed=7)
        dp = DataParallelStep(m1, make_mesh(MeshSpec(data=8)))
        l1 = [float(dp.fit_batch(x, y, None, None)) for _ in range(4)]
        tr = MeshTrainer(m2, MeshSpec(data=8))
        l2 = _fit_steps(tr.fit_batch, x, y)
        np.testing.assert_allclose(l1, l2, rtol=1e-5, atol=1e-6)

    def test_grad_accum_composes(self, monkeypatch):
        """The grad-accum scan runs INSIDE the mesh step: equal micro-splits
        of one batch give the full-batch gradient (mean of micro-means)."""
        x, y = _data(64)
        m1 = _model(seed=11)
        m2 = _model(seed=11)
        l1 = _fit_steps(lambda a, b: m1._fit_batch(a, b, None, None), x, y)
        monkeypatch.setenv("DL4J_TPU_GRAD_ACCUM", "4")
        tr = MeshTrainer(m2, MeshSpec(data=8))
        l2 = _fit_steps(tr.fit_batch, x, y)
        np.testing.assert_allclose(l1, l2, rtol=1e-4, atol=1e-5)
        tr.finish()
        _params_close(m1, m2, rtol=1e-4, atol=1e-5)

    def test_compress_hook_composes(self):
        """compress=True routes through the PR 3 ternary exchange on the
        pure-data mesh; loss stays close to the dense path (thresholded
        encoding carries residuals, so a few steps stay near-exact)."""
        x, y = _data(64)
        m1 = _model(seed=13)
        m2 = _model(seed=13)
        l1 = _fit_steps(lambda a, b: m1._fit_batch(a, b, None, None),
                        x, y, steps=2)
        tr = MeshTrainer(m2, MeshSpec(data=8), compress=True)
        l2 = _fit_steps(tr.fit_batch, x, y, steps=2)
        # first step: residuals empty, exchange is exact
        np.testing.assert_allclose(l1[0], l2[0], rtol=1e-5, atol=1e-6)

    def test_compress_refuses_tensor_or_stage_axes(self):
        with pytest.raises(ValueError, match="pure data mesh"):
            MeshTrainer(_model(), MeshSpec(data=4, model=2), compress=True)


# ---------------------------------------------------------------------------
# Mesh-shape parity: (d), (d,t), (d,s), (d,t,s) all compute the same step
# ---------------------------------------------------------------------------


class TestMeshShapeParity:
    @pytest.mark.parametrize("spec", [
        MeshSpec(data=4, model=2),
        MeshSpec(data=4, pipe=2),
        MeshSpec(data=2, model=2, pipe=2),
    ], ids=["d4t2", "d4s2", "d2t2s2"])
    def test_shape_parity_vs_pure_dp(self, spec):
        x, y = _data(64)
        m1 = _model(seed=17, updater={"type": "adam", "lr": 0.01})
        m2 = _model(seed=17, updater={"type": "adam", "lr": 0.01})
        t1 = MeshTrainer(m1, MeshSpec(data=8))
        l1 = _fit_steps(t1.fit_batch, x, y)
        t2 = MeshTrainer(m2, spec)
        l2 = _fit_steps(t2.fit_batch, x, y)
        np.testing.assert_allclose(l1, l2, rtol=1e-5, atol=1e-6)
        t1.finish()
        t2.finish()
        _params_close(m1, m2, rtol=1e-5, atol=1e-6)

    def test_fit_loop_and_output(self):
        x, y = _data(64)
        m = _model(seed=19)
        tr = MeshTrainer(m, MeshSpec(data=2, model=2, pipe=2))
        s0 = float(tr.fit_batch(x, y))
        tr.fit([(x, y)], epochs=10)
        out = np.asarray(tr.output(x))
        assert out.shape == (64, 2)
        sN = float(tr.fit_batch(x, y))
        assert sN < s0


# ---------------------------------------------------------------------------
# Sharded optimizer state (arXiv 2004.13336) + steady-state compile contract
# ---------------------------------------------------------------------------


class TestShardedUpdate:
    def test_moments_shard_over_spare_axes(self):
        """Adam moments shard over (data, pipe): 1/(d·s) of each moment per
        device, while params keep their (replicated/TP) layout."""
        x, y = _data(64)
        m = _model(seed=23, updater={"type": "adam", "lr": 0.01}, hidden=64)
        tr = MeshTrainer(m, MeshSpec(data=2, model=2, pipe=2))
        tr.fit_batch(x, y)
        sharded = 0
        for layer in m.opt_state:
            if not isinstance(layer, dict):
                continue
            for tree in layer.values():
                for leaf in jax.tree_util.tree_leaves(tree):
                    spec = leaf.sharding.spec
                    axes = [a for d in spec if d is not None
                            for a in (d if isinstance(d, tuple) else (d,))]
                    if axes:
                        sharded += 1
                        n = int(np.prod([tr.mesh.shape[a] for a in axes]))
                        shard_rows = leaf.addressable_shards[0].data.shape
                        assert shard_rows[0] * n == leaf.shape[0]
        assert sharded > 0

    def test_shard_update_spec_prefers_joint_combo(self):
        mesh = make_mesh(MeshSpec(data=2, model=2, pipe=2))
        # first dim divisible by d*s=4 → joint tuple spec
        assert shard_update_spec(P(), (8, 3), mesh) == \
            P(("data", "pipe"), None)
        # TP already took dim 0: spare axes take the next free dim
        assert shard_update_spec(P("model", None), (2, 8), mesh) == \
            P("model", ("data", "pipe"))
        # nothing divides → leaf stays as the TP rules had it
        assert shard_update_spec(P(), (3, 5), mesh) == P()
        # scalar leaves never shard
        assert shard_update_spec(P(), (), mesh) == P()

    def test_shard_update_spec_falls_back_to_single_axis(self):
        mesh = make_mesh(MeshSpec(data=4, pipe=2))
        # 8 % (4*2) == 0 → joint; 4 % 8 != 0 but 4 % 4 == 0 → data alone
        assert shard_update_spec(P(), (4, 4), mesh) == P("data", None)

    def test_zero_steady_state_recompiles(self):
        """After one warm dispatch the mesh step never re-traces: the output
        sharding constraints pin the 2004.13336 layout, so donated buffers
        land back with identical shardings every step."""
        x, y = _data(64)
        m = _model(seed=29)
        tr = MeshTrainer(m, MeshSpec(data=2, model=2, pipe=2))
        tr.fit_batch(x, y)
        warm_traces = bucketing.telemetry().traces.get("mln.step", 0)
        assert warm_traces >= 1
        for _ in range(5):
            tr.fit_batch(x, y)
        assert bucketing.telemetry().traces.get("mln.step", 0) == warm_traces

    def test_finish_round_trips_to_single_device(self):
        x, y = _data(64)
        m = _model(seed=31)
        tr = MeshTrainer(m, MeshSpec(data=4, model=2))
        tr.fit_batch(x, y)
        tr.finish()
        for leaf in jax.tree_util.tree_leaves((m.params, m.opt_state)):
            assert leaf.sharding.spec == P()
        # plain single-device training continues from the gathered state
        m._fit_batch(x, y, None, None)
        assert np.asarray(m.output(x)).shape == (64, 2)

    def test_batch_must_divide_data_axis(self):
        m = _model(seed=37)
        tr = MeshTrainer(m, MeshSpec(data=8))
        x, y = _data(60)  # 60 % 8 != 0
        with pytest.raises(ValueError, match="divide the data axis"):
            tr.fit_batch(x, y)


# ---------------------------------------------------------------------------
# Mesh-shape knobs: env resolution, the default
# ---------------------------------------------------------------------------


class TestMeshKnobs:
    def test_mesh_shape_from_env_auto(self):
        assert mesh_shape_from_env(8) == (8, 1, 1)

    def test_mesh_shape_from_env_partial(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_MESH_MODEL", "2")
        assert mesh_shape_from_env(8) == (4, 2, 1)
        monkeypatch.setenv("DL4J_TPU_MESH_PIPE", "2")
        assert mesh_shape_from_env(8) == (2, 2, 2)

    def test_mesh_shape_from_env_rejects_non_covering(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_MESH_DATA", "2")
        monkeypatch.setenv("DL4J_TPU_MESH_MODEL", "2")
        with pytest.raises(ValueError):
            mesh_shape_from_env(8)  # 2*2*1 != 8

    def test_mesh_shape_from_env_rejects_non_dividing(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_MESH_MODEL", "3")
        with pytest.raises(ValueError):
            mesh_shape_from_env(8)

    def test_untuned_default_is_pure_dp(self):
        tr = MeshTrainer(_model(seed=43))
        assert (tr.shape[0], tr.shape[1], tr.shape[3]) == (8, 1, 1)
