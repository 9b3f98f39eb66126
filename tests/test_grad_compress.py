"""Threshold gradient compression + explicit sharded exchange
(parallel/compress.py, parallel/grads.py) on 8 virtual CPU devices.

Two layers of guarantees:
- pure-function properties of the ternary codec (round-trip, error-feedback
  conservation, sub-threshold accumulation, packing for awkward lengths);
- end-to-end parity of the explicit exchange against the implicit dense
  path: sharded weight update must reproduce the replicated update
  parameter-for-parameter, and compressed mode must actually train.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
from deeplearning4j_tpu.nn.model import MultiLayerConfiguration, MultiLayerNetwork
from deeplearning4j_tpu.parallel import (
    MeshSpec,
    ParallelWrapper,
    decode_gathered,
    encode_packed,
    make_mesh,
    pack_ternary,
    packed_nbytes,
    threshold_encode,
    unpack_ternary,
)
from deeplearning4j_tpu.utils import bucketing


# ---------------------------------------------------------------------------
# Codec properties
# ---------------------------------------------------------------------------


class TestThresholdCodec:
    def test_encode_values_and_invariant(self):
        rs = np.random.RandomState(0)
        g = jnp.asarray(rs.randn(257).astype(np.float32)) * 0.01
        r0 = jnp.asarray(rs.randn(257).astype(np.float32)) * 0.001
        thr = 5e-3
        q, r1 = threshold_encode(g, r0, thr)
        vals = np.unique(np.asarray(q))
        allowed = {np.float32(-thr), np.float32(0.0), np.float32(thr)}
        assert set(vals) <= allowed
        # error-feedback invariant: q + r_new == g + r_old
        np.testing.assert_allclose(
            np.asarray(q + r1), np.asarray(g + r0), rtol=0, atol=1e-7)

    def test_residual_conservation_over_time(self):
        """Telescoping the invariant: sum(q_t) + r_T == sum(g_t) + r_0, so no
        gradient mass is ever lost — only delayed."""
        rs = np.random.RandomState(1)
        thr = 1e-2
        r = jnp.zeros(64)
        total_q = jnp.zeros(64)
        total_g = jnp.zeros(64)
        for t in range(50):
            g = jnp.asarray(rs.randn(64).astype(np.float32)) * 0.003
            q, r = threshold_encode(g, r, thr)
            total_q = total_q + q
            total_g = total_g + g
        np.testing.assert_allclose(
            np.asarray(total_q + r), np.asarray(total_g), rtol=0, atol=1e-5)

    def test_subthreshold_eventually_transmits(self):
        """A constant gradient at 0.4*thr crosses the threshold on step 3 —
        residual accumulation is what makes tiny components survive."""
        thr = 1e-2
        g = jnp.full((8,), 0.4 * thr)
        r = jnp.zeros(8)
        sent = []
        for _ in range(5):
            q, r = threshold_encode(g, r, thr)
            sent.append(float(np.asarray(q).sum()))
        assert sent[0] == 0.0 and sent[1] == 0.0
        assert sent[2] == pytest.approx(8 * thr)

    @pytest.mark.parametrize("n", [1, 3, 4, 7, 64, 257])
    def test_pack_unpack_roundtrip(self, n):
        rs = np.random.RandomState(n)
        signs = jnp.asarray(rs.choice([-1.0, 0.0, 1.0], size=n).astype(np.float32))
        packed = pack_ternary(signs)
        assert packed.shape == (packed_nbytes(n),)
        assert packed.dtype == jnp.uint8
        np.testing.assert_array_equal(
            np.asarray(unpack_ternary(packed, n)), np.asarray(signs))

    def test_unpack_batch_axis_and_decode(self):
        """decode_gathered sums the all-gathered [R, nbytes] payloads in a
        fixed order — the replica-exchange decode path."""
        thr = 2e-3
        rs = np.random.RandomState(3)
        gs = [jnp.asarray(rs.randn(21).astype(np.float32)) * 0.01
              for _ in range(4)]
        packs, qs = [], []
        for g in gs:
            q, _ = threshold_encode(g, jnp.zeros(21), thr)
            packs.append(pack_ternary(jnp.sign(q)))
            qs.append(np.asarray(q))
        gathered = jnp.stack(packs)                       # [R, nbytes]
        total = decode_gathered(gathered, 21, thr, jnp.float32)
        np.testing.assert_allclose(
            np.asarray(total), np.sum(qs, axis=0), rtol=0, atol=1e-7)

    def test_encode_packed_matches_components(self):
        g = jnp.asarray([0.02, -0.03, 1e-5, 0.0, 0.011])
        packed, r = encode_packed(g, jnp.zeros(5), 1e-2)
        np.testing.assert_array_equal(
            np.asarray(unpack_ternary(packed, 5)), [1, -1, 0, 0, 1])
        np.testing.assert_allclose(
            np.asarray(r), [0.01, -0.02, 1e-5, 0.0, 0.001], atol=1e-7)


# ---------------------------------------------------------------------------
# End-to-end exchange
# ---------------------------------------------------------------------------


def _model(seed=3, updater=None):
    conf = MultiLayerConfiguration(
        layers=(
            Dense(n_out=16, activation="tanh"),
            OutputLayer(n_out=2, activation="softmax"),
        ),
        input_type=InputType.feed_forward(4),
        updater=updater or {"type": "sgd", "lr": 0.1},
        seed=seed,
    )
    return MultiLayerNetwork(conf).init()


def _data(n=64, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, 4).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[(x.sum(axis=1) > 0).astype(int)]
    return x, y


def _leaves(m):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(m.params)]


class TestShardedUpdateParity:
    """The acceptance gate: reduce-scatter + 1/R-shard update + all-gather
    must equal the replicated update parameter-for-parameter."""

    @pytest.mark.parametrize("updater", [
        {"type": "sgd", "lr": 0.1},
        {"type": "adam", "lr": 0.01},
    ])
    def test_sharded_equals_replicated(self, updater):
        x, y = _data(64)
        m1 = _model(seed=5, updater=updater)
        ParallelWrapper(m1, mesh=make_mesh(MeshSpec(data=8))).fit(
            (x, y), epochs=3)
        m2 = _model(seed=5, updater=updater)
        ParallelWrapper(m2, mesh=make_mesh(MeshSpec(data=8)),
                        sharded_update=True).fit((x, y), epochs=3)
        for a, b in zip(_leaves(m1), _leaves(m2)):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)

    def test_uneven_batch_parity(self):
        """60 % 8 != 0: the padded/zero-weighted path through the explicit
        runner still matches the implicit path."""
        x, y = _data(60)
        m1 = _model(seed=5)
        ParallelWrapper(m1, mesh=make_mesh(MeshSpec(data=8))).fit(
            (x, y), epochs=3)
        m2 = _model(seed=5)
        ParallelWrapper(m2, mesh=make_mesh(MeshSpec(data=8)),
                        sharded_update=True).fit((x, y), epochs=3)
        for a, b in zip(_leaves(m1), _leaves(m2)):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)

    def test_opt_state_restored_after_fit(self):
        """finish() must hand the structured (replicated) optimizer state
        back to the model — same tree structure and leaf shapes as a model
        that never used the explicit exchange."""
        x, y = _data(64)
        upd = {"type": "adam", "lr": 0.01}
        m1 = _model(seed=5, updater=upd)
        m1.fit((x, y), epochs=1)
        m2 = _model(seed=5, updater=upd)
        ParallelWrapper(m2, mesh=make_mesh(MeshSpec(data=8)),
                        sharded_update=True).fit((x, y), epochs=1)
        s1 = jax.tree_util.tree_structure(m1.opt_state)
        s2 = jax.tree_util.tree_structure(m2.opt_state)
        assert s1 == s2
        for a, b in zip(jax.tree_util.tree_leaves(m1.opt_state),
                        jax.tree_util.tree_leaves(m2.opt_state)):
            assert np.shape(a) == np.shape(b)

    def test_graph_sharded_parity(self):
        from deeplearning4j_tpu.nn.graph import (
            ComputationGraph,
            ComputationGraphConfiguration,
        )

        def graph(seed):
            conf = (
                ComputationGraphConfiguration.builder()
                .add_inputs("in")
                .set_input_types(InputType.feed_forward(4))
                .add_layer("d1", Dense(n_out=8, activation="tanh"), "in")
                .add_layer("out", OutputLayer(n_out=2, activation="softmax"),
                           "d1")
                .set_outputs("out")
                .updater({"type": "adam", "lr": 0.05})
                .seed(seed)
                .build()
            )
            return ComputationGraph(conf).init()

        rs = np.random.RandomState(0)
        x = rs.randn(64, 4).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[(x.sum(1) > 0).astype(int)]
        g1 = graph(7)
        ParallelWrapper(g1, mesh=make_mesh(MeshSpec(data=8))).fit(
            ((x,), y), epochs=3)
        g2 = graph(7)
        ParallelWrapper(g2, mesh=make_mesh(MeshSpec(data=8)),
                        sharded_update=True).fit(((x,), y), epochs=3)
        for a, b in zip(_leaves(g1), _leaves(g2)):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)


class TestCompressedExchange:
    def test_compressed_mode_trains(self):
        """Ternary exchange with error feedback converges on the toy task
        (threshold matched to the gradient scale: per-step transmitted
        magnitude is capped at the threshold)."""
        x, y = _data(64)
        m = _model(seed=9)
        pw = ParallelWrapper(m, mesh=make_mesh(MeshSpec(data=8)),
                             grad_compress=True, compress_threshold=1e-2)
        s0 = float(m.score(x, y))
        pw.fit((x, y), epochs=20, batch_size=16)
        assert float(m.score(x, y)) < s0 * 0.8

    def test_compressed_sharded_matches_replicated_update(self):
        """Compression decodes the same fixed-order replica sum everywhere,
        so adding the sharded update must not change the trajectory."""
        x, y = _data(64)
        m1 = _model(seed=9)
        ParallelWrapper(m1, mesh=make_mesh(MeshSpec(data=8)),
                        grad_compress=True, compress_threshold=1e-2).fit(
            (x, y), epochs=5, batch_size=16)
        m2 = _model(seed=9)
        ParallelWrapper(m2, mesh=make_mesh(MeshSpec(data=8)),
                        grad_compress=True, sharded_update=True,
                        compress_threshold=1e-2).fit(
            (x, y), epochs=5, batch_size=16)
        for a, b in zip(_leaves(m1), _leaves(m2)):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)

    def test_compressed_deterministic_across_reruns(self):
        x, y = _data(64)

        def run():
            m = _model(seed=11)
            ParallelWrapper(m, mesh=make_mesh(MeshSpec(data=8)),
                            grad_compress=True, compress_threshold=1e-2).fit(
                (x, y), epochs=3, batch_size=16)
            return _leaves(m)

        for a, b in zip(run(), run()):
            np.testing.assert_array_equal(a, b)

    def test_comm_stats_and_telemetry(self):
        """Wire bytes must beat dense by >= 4x (ternary packing is 16x for
        f32 modulo shard padding) and land in the bucketing snapshot."""
        x, y = _data(64)
        m = _model(seed=9)
        pw = ParallelWrapper(m, mesh=make_mesh(MeshSpec(data=8)),
                             grad_compress=True, sharded_update=True,
                             compress_threshold=1e-2)
        pw.fit((x, y), epochs=1)
        stats = pw._runner.comm_stats()
        assert stats["compressed_entries"] == stats["n_entries"] > 0
        assert stats["dense_bytes"] >= 4 * stats["wire_bytes"]
        comm = bucketing.telemetry().snapshot()["comm"]
        assert comm["dp.grads"]["wire_bytes"] == stats["wire_bytes"]
        assert comm["dp.grads"]["dense_bytes"] == stats["dense_bytes"]


class TestDpLadderPadding:
    def test_dp_fit_pads_up_the_bucketing_ladder(self):
        """Ragged DP batch sizes must reuse the shared bucket ladder (one
        compile per bucket), not one compile per distinct size."""
        if not bucketing.bucketing_enabled():
            pytest.skip("bucketing disabled via env")
        x, y = _data(64)
        m = _model(seed=3)
        pw = ParallelWrapper(m, mesh=make_mesh(MeshSpec(data=8)))
        tel = bucketing.telemetry()
        before = {b: c for (s, b), c in tel.bucket_hits.items() if s == "dp.fit"}
        # ragged tail: 64 rows in batches of 24 -> 24, 24, 16
        pw.fit((x, y), epochs=1, batch_size=24)
        used = tel.buckets_used("dp.fit")
        assert used, "dp.fit recorded no bucket traffic"
        # every padded size is a ladder bucket rounded to the shard quantum
        for b in used:
            assert b % 8 == 0
        expected = {-(-bucketing.bucket_size(n) // 8) * 8 for n in (24, 16)}
        assert expected <= set(used)
