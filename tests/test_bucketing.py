"""Shape-bucketed execution: ladder math, padded-fit equivalence, one
compile per bucket, device prefetch (ISSUE 1 tentpole)."""

import threading

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers import BatchNorm, Dense, OutputLayer
from deeplearning4j_tpu.nn.model import (
    MultiLayerConfiguration,
    MultiLayerNetwork,
)
from deeplearning4j_tpu.utils import bucketing


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    for var in ("DL4J_TPU_BUCKETING", "DL4J_TPU_BUCKETS",
                "DL4J_TPU_BUCKET_MIN", "DL4J_TPU_BUCKET_GROWTH",
                "DL4J_TPU_DEVICE_PREFETCH"):
        monkeypatch.delenv(var, raising=False)
    bucketing.telemetry().reset()
    yield


def _bn_model(seed=11):
    conf = MultiLayerConfiguration(
        layers=(
            Dense(n_out=16, activation="identity"),
            BatchNorm(),
            Dense(n_out=8, activation="tanh"),
            OutputLayer(n_out=2, activation="softmax"),
        ),
        input_type=InputType.feed_forward(4),
        updater={"type": "sgd", "lr": 0.1},
        seed=seed,
    )
    return MultiLayerNetwork(conf).init()


def _data(n=20, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, 4).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rs.randint(0, 2, n)]
    return x, y


def _max_leaf_diff(a, b):
    return max(
        float(np.abs(np.asarray(u) - np.asarray(v)).max())
        for u, v in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)))


class TestLadder:
    def test_geometric_default(self):
        lad = bucketing.BucketLadder()
        assert [lad.bucket(n) for n in (1, 2, 3, 5, 9, 17, 33)] == \
            [1, 2, 4, 8, 16, 32, 64]

    def test_explicit_rungs_extend_geometrically(self):
        lad = bucketing.BucketLadder(rungs=(8, 16, 24))
        assert lad.bucket(3) == 8
        assert lad.bucket(24) == 24
        assert lad.bucket(25) == 48    # past the top rung: geometric growth
        assert lad.bucket(49) == 96

    def test_validation(self):
        with pytest.raises(ValueError):
            bucketing.BucketLadder(rungs=(8, 8))
        with pytest.raises(ValueError):
            bucketing.BucketLadder(min_size=0)
        with pytest.raises(ValueError):
            bucketing.BucketLadder(growth=1.0)

    def test_env_knobs(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_BUCKETS", "8,16,32")
        assert bucketing.bucket_size(3) == 8
        assert bucketing.bucket_size(17) == 32
        monkeypatch.setenv("DL4J_TPU_BUCKETS", "not,numbers")
        with pytest.raises(ValueError, match="DL4J_TPU_BUCKETS"):
            bucketing.bucket_size(3)
        monkeypatch.delenv("DL4J_TPU_BUCKETS")
        monkeypatch.setenv("DL4J_TPU_BUCKET_MIN", "4")
        monkeypatch.setenv("DL4J_TPU_BUCKET_GROWTH", "3.0")
        assert bucketing.bucket_size(1) == 4
        assert bucketing.bucket_size(5) == 12
        monkeypatch.setenv("DL4J_TPU_BUCKET_GROWTH", "fast")
        with pytest.raises(ValueError, match="DL4J_TPU_BUCKET_GROWTH"):
            bucketing.bucket_size(1)

    def test_master_switch(self, monkeypatch):
        assert bucketing.bucketing_enabled()
        monkeypatch.setenv("DL4J_TPU_BUCKETING", "0")
        assert not bucketing.bucketing_enabled()


class TestTelemetry:
    def test_thread_safe_counts(self):
        tel = bucketing.BucketTelemetry()

        def hammer():
            for _ in range(200):
                tel.record_trace("s", (8, 4))
                tel.record_hit("s", 5, 8)

        ts = [threading.Thread(target=hammer) for _ in range(4)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert tel.compiles("s") == 800
        assert tel.bucket_hits[("s", 8)] == 800
        assert tel.padded_examples == 800 * 3
        snap = tel.snapshot()
        assert snap["bucket_hits"]["s:8"] == 800


class TestOutputBucketing:
    def test_bucketed_output_matches_unbucketed(self, monkeypatch):
        m = _bn_model()
        x, _ = _data(20)
        got = {n: np.asarray(m.output(x[:n])) for n in (3, 5, 7)}
        monkeypatch.setenv("DL4J_TPU_BUCKETING", "0")
        m2 = _bn_model()
        for n, o in got.items():
            ref = np.asarray(m2.output(x[:n]))
            assert np.abs(o - ref).max() < 1e-5

    def test_bn_zoo_model_output_equivalence(self, monkeypatch):
        # acceptance: bucketed == unbucketed within 1e-5 on a
        # BatchNorm-bearing zoo model
        from deeplearning4j_tpu.models.zoo import SimpleCNN

        def mk():
            return MultiLayerNetwork(SimpleCNN(
                height=8, width=8, channels=1, num_classes=3)).init()

        rs = np.random.RandomState(2)
        x = rs.rand(7, 8, 8, 1).astype(np.float32)  # 7 pads to bucket 8
        out = np.asarray(mk().output(x))
        assert out.shape[0] == 7
        monkeypatch.setenv("DL4J_TPU_BUCKETING", "0")
        ref = np.asarray(mk().output(x))
        assert np.abs(out - ref).max() < 1e-5

    def test_one_output_compile_per_bucket(self):
        m = _bn_model()
        x, _ = _data(40)
        tel = bucketing.telemetry()
        for n in (3, 4, 5, 6, 7, 8, 9, 12):
            m.output(x[:n])
        # sizes 3..8 hit buckets {4, 8}; 9 and 12 hit 16: 3 distinct buckets
        assert tel.compiles("mln.output") == 3
        assert {s[0] for s in tel.trace_shapes["mln.output"]} == {4, 8, 16}


class TestFitPadding:
    def test_partial_tail_single_executable_and_equal_results(self, monkeypatch):
        # acceptance: fit() with a partial final batch traces ONE training
        # executable, results equal to the unpadded path within 1e-5
        monkeypatch.setenv("DL4J_TPU_CHAIN_STEPS", "0")
        x, y = _data(20)  # 20 % 8 != 0 -> tail of 4
        tel = bucketing.telemetry()
        m1 = _bn_model()
        m1.fit((x, y), epochs=3, batch_size=8)
        assert tel.compiles("mln.step") == 1
        assert tel.trace_shapes["mln.step"] == {(8, 4)}
        monkeypatch.setenv("DL4J_TPU_BUCKETING", "0")
        tel.reset()
        m2 = _bn_model()
        m2.fit((x, y), epochs=3, batch_size=8)
        assert tel.compiles("mln.step") == 2  # full + tail shapes
        assert _max_leaf_diff(m1.params, m2.params) < 1e-5
        assert _max_leaf_diff(m1.state, m2.state) < 1e-5
        assert abs(m1.score(x, y) - m2.score(x, y)) < 1e-5

    def test_graph_partial_tail_single_executable(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_CHAIN_STEPS", "0")
        from deeplearning4j_tpu.nn.graph import (
            ComputationGraph, ComputationGraphConfiguration)

        def mk():
            conf = (ComputationGraphConfiguration.builder()
                    .add_inputs("in")
                    .set_input_types(InputType.feed_forward(4))
                    .add_layer("d", Dense(n_out=16, activation="tanh"), "in")
                    .add_layer("out", OutputLayer(n_out=3, activation="softmax"), "d")
                    .set_outputs("out").build())
            return ComputationGraph(conf).init()

        rs = np.random.RandomState(0)
        x = rs.randn(20, 4).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, 20)]
        tel = bucketing.telemetry()
        g1 = mk()
        g1.fit((x, y), epochs=2, batch_size=8)
        assert tel.compiles("cg.step") == 1
        monkeypatch.setenv("DL4J_TPU_BUCKETING", "0")
        g2 = mk()
        g2.fit((x, y), epochs=2, batch_size=8)
        assert _max_leaf_diff(g1.params, g2.params) < 1e-5

    def test_even_split_unchanged(self, monkeypatch):
        # no partial tail -> no padding machinery engaged at all
        monkeypatch.setenv("DL4J_TPU_CHAIN_STEPS", "0")
        x, y = _data(16)
        tel = bucketing.telemetry()
        _bn_model().fit((x, y), epochs=1, batch_size=8)
        assert ("mln.fit", 8) not in tel.bucket_hits

    def test_pad_fit_batch_masks(self):
        x, y = _data(5)
        px, py, pfm, plm, ew = bucketing.pad_fit_batch(x, y, None, None, 8)
        assert px.shape == (8, 4) and py.shape == (8, 2)
        assert list(ew) == [1.0] * 5 + [0.0] * 3
        # validity mask pre-scaled by B_pad/n so loss == mean over 5 rows
        np.testing.assert_allclose(plm[:5], 8.0 / 5.0)
        np.testing.assert_allclose(plm[5:], 0.0)
        # uniform calling convention: full batch still materializes channels
        fx, fy, ffm, flm, few = bucketing.pad_fit_batch(x, y, None, None, 5)
        np.testing.assert_allclose(flm, 1.0)
        assert list(few) == [1.0] * 5


class TestSolverBucketing:
    def test_solver_reuses_bucket_executable(self, monkeypatch):
        from deeplearning4j_tpu.train.solvers import Solver

        x, y = _data(20, seed=3)
        m = _bn_model()
        sol = Solver(m, "lbfgs")
        tel = bucketing.telemetry()
        sol.optimize((x[:7], y[:7]), iterations=2)
        first = tel.compiles("solver")   # _jf + _jvg traces for bucket 8
        sol.optimize((x[:6], y[:6]), iterations=2)  # same bucket: no retrace
        assert tel.compiles("solver") == first

    def test_solver_loss_matches_unbucketed(self, monkeypatch):
        from deeplearning4j_tpu.train.solvers import Solver

        x, y = _data(7, seed=4)
        l1 = Solver(_bn_model(), "line_gradient_descent").optimize(
            (x, y), iterations=3)
        monkeypatch.setenv("DL4J_TPU_BUCKETING", "0")
        l2 = Solver(_bn_model(), "line_gradient_descent").optimize(
            (x, y), iterations=3)
        assert abs(l1 - l2) < 1e-5


class TestParallelInferenceBucketing:
    def test_mixed_sizes_one_compile_per_bucket(self):
        # acceptance: >= 8 distinct request sizes, exactly one
        # trace/compile per bucket, verified via the telemetry counter
        from deeplearning4j_tpu.parallel.inference import ParallelInference

        m = _bn_model()
        rs = np.random.RandomState(1)
        sizes = [1, 2, 3, 5, 7, 9, 12, 17]
        assert len(set(sizes)) >= 8
        tel = bucketing.telemetry()
        pi = ParallelInference(m, mode="batched", max_batch_size=64)
        try:
            for s in sizes:
                xs = rs.randn(s, 4).astype(np.float32)
                out = pi.output(xs)
                assert out.shape == (s, 2)
                ref = np.asarray(m.output(xs))
                np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
        finally:
            pi.shutdown()
        buckets = tel.buckets_used("pi.batched")
        assert buckets == (1, 2, 4, 8, 16, 32)
        assert tel.compiles("mln.output") == len(buckets)

    def test_bucket_opt_out(self):
        from deeplearning4j_tpu.parallel.inference import ParallelInference

        m = _bn_model()
        tel = bucketing.telemetry()
        pi = ParallelInference(m, mode="batched", max_batch_size=8,
                               bucket=False)
        try:
            out = pi.output(np.zeros((3, 4), np.float32))
            assert out.shape == (3, 2)
        finally:
            pi.shutdown()
        assert ("pi.batched", 4) not in tel.bucket_hits


class TestDevicePrefetch:
    def test_preserves_order_and_values(self):
        from deeplearning4j_tpu.datasets.iterator import prefetch_to_device

        items = [(np.full((2, 3), i, np.float32), None) for i in range(25)]
        got = list(prefetch_to_device(iter(items), depth=2))
        assert len(got) == 25
        for i, (a, b) in enumerate(got):
            assert isinstance(a, jax.Array)  # actually moved to device
            assert b is None                 # None members survive
            assert float(a[0, 0]) == i

    def test_early_close_joins_producer(self):
        from deeplearning4j_tpu.datasets.iterator import prefetch_to_device

        n_threads = threading.active_count()
        gen = prefetch_to_device(iter([np.zeros(2)] * 100), depth=2)
        next(gen)
        gen.close()  # must stop + join the producer, not leak it
        for _ in range(50):
            if threading.active_count() <= n_threads:
                break
            import time
            time.sleep(0.05)
        assert threading.active_count() <= n_threads

    def test_producer_error_propagates(self):
        from deeplearning4j_tpu.datasets.iterator import prefetch_to_device

        def bad():
            yield np.zeros(2)
            raise RuntimeError("boom")

        with pytest.raises(RuntimeError, match="boom"):
            list(prefetch_to_device(bad()))

    def test_iterator_class_and_dataset_items(self):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.datasets.iterator import (
            DevicePrefetchIterator, ListDataSetIterator)

        x = np.arange(12, dtype=np.float32).reshape(6, 2)
        y = np.eye(2, dtype=np.float32)[np.arange(6) % 2]
        it = DevicePrefetchIterator(ListDataSetIterator(DataSet(x, y), 2))
        seen = list(it)
        assert len(seen) == 3
        assert all(isinstance(ds.features, jax.Array) for ds in seen)
        np.testing.assert_allclose(
            np.concatenate([np.asarray(ds.features) for ds in seen]), x)

    def test_invalid_depth(self):
        from deeplearning4j_tpu.datasets.iterator import prefetch_to_device

        with pytest.raises(ValueError):
            list(prefetch_to_device(iter([]), depth=0))


class TestSatellites:
    def test_tbptt_slice_gating(self):
        from deeplearning4j_tpu.nn.graph import _tbptt_slice_t

        T, sl = 6, slice(0, 3)
        td = np.zeros((4, T, 5), np.float32)
        static_3d = np.zeros((4, T, 5), np.float32)  # middle dim == T by luck
        assert _tbptt_slice_t(td, sl, T, "feat_td").shape == (4, 3, 5)
        # static 3-D side input must pass through WHOLE, not time-chunked
        assert _tbptt_slice_t(static_3d, sl, T, "feat").shape == (4, T, 5)
        assert _tbptt_slice_t(np.zeros((4, T, 2)), sl, T, "label").shape == (4, 3, 2)
        assert _tbptt_slice_t(np.zeros((4, T)), sl, T, "mask").shape == (4, 3)
        # sparse integer labels [B,T] chunk; float rank-2 labels pass whole
        assert _tbptt_slice_t(np.zeros((4, T), np.int32), sl, T, "label").shape == (4, 3)
        assert _tbptt_slice_t(np.zeros((4, T), np.float32), sl, T, "label").shape == (4, T)

    def test_chain_rng_warning(self, monkeypatch):
        import warnings

        from deeplearning4j_tpu.nn import model as model_mod
        from deeplearning4j_tpu.nn import step_program

        assert model_mod.CHAIN_AUTO_PARAM_LIMIT == 2_000_000
        monkeypatch.setenv("DL4J_TPU_CHAIN_STEPS", "4")
        # the warn-once flag lives in the unified step-program module now
        monkeypatch.setattr(step_program, "_CHAIN_RNG_WARNED", False)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            assert model_mod._chain_k_from_env(True, 1000) == 4
            assert any("DL4J_TPU_CHAIN_STEPS" in str(x.message) for x in w)
        # warn ONCE per process
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            model_mod._chain_k_from_env(True, 1000)
            assert not w

    def test_system_page_renders_without_resource(self, monkeypatch):
        import builtins

        from deeplearning4j_tpu.ui.server import UIServer

        real_import = builtins.__import__

        def no_resource(name, *a, **k):
            if name == "resource":
                raise ImportError("non-POSIX host")
            return real_import(name, *a, **k)

        monkeypatch.setattr(builtins, "__import__", no_resource)
        html = UIServer().render_system_html()
        assert "n/a" in html
