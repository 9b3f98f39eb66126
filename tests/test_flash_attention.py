"""Pallas flash-attention kernel (ops/flash_attention.py): interpret-mode
equivalence against the XLA reference (the dual-path pattern of
SURVEY.md §4), gradient parity through the custom VJP, and the layer-level
"auto"/force policy."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.flash_attention import (
    _reference, flash_attention)

# the module itself (the package exports the function under the same name)
fa = importlib.import_module("deeplearning4j_tpu.ops.flash_attention")


def _qkv(rs, B, T, H, D, scale=0.5):
    return tuple(jnp.asarray(rs.randn(B, T, H, D).astype(np.float32) * s)
                 for s in (scale, scale, 1.0))


class TestKernelEquivalence:
    @pytest.mark.parametrize("shape", [(2, 16, 2, 8), (1, 64, 4, 16),
                                       (2, 50, 3, 32), (1, 130, 2, 64),
                                       # heads paired up in 128-lane blocks
                                       (1, 50, 4, 32), (2, 40, 4, 64),
                                       (1, 40, 3, 128)])
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_xla_reference(self, shape, causal):
        rs = np.random.RandomState(0)
        q, k, v = _qkv(rs, *shape)
        out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                              interpret=True)
        ref = _reference(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=2e-5)

    def test_block_not_dividing_t(self):
        # T=50 with 32-blocks: padded keys must be excluded exactly
        rs = np.random.RandomState(1)
        q, k, v = _qkv(rs, 1, 50, 2, 16)
        out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                              interpret=True)
        ref = _reference(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=2e-5)

    def test_gradients_match_reference(self):
        rs = np.random.RandomState(2)
        q, k, v = _qkv(rs, 1, 24, 2, 8)

        gf = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=8, block_k=8, interpret=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(
            _reference(q, k, v, True) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=2e-4)


class TestPallasBackward:
    """The blockwise dq/dkv kernels vs the XLA-remat oracle (bwd='xla') and
    vs autodiff of the dense reference."""

    @pytest.mark.parametrize("shape", [(2, 16, 2, 8), (1, 64, 4, 16),
                                       (2, 50, 3, 32), (1, 130, 2, 64),
                                       (1, 50, 4, 32), (2, 40, 4, 64),
                                       (1, 40, 3, 128)])
    @pytest.mark.parametrize("causal", [False, True])
    def test_pallas_bwd_matches_xla_bwd(self, shape, causal):
        rs = np.random.RandomState(7)
        q, k, v = _qkv(rs, *shape)

        def loss(bwd):
            return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
                q, k, v, causal=causal, block_q=32, block_k=32,
                interpret=True, bwd=bwd) ** 2), argnums=(0, 1, 2))(q, k, v)

        gp = loss("pallas")
        gx = loss("xla")
        for a, b in zip(gp, gx):
            assert np.all(np.isfinite(np.asarray(a)))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=2e-4)

    def test_padded_rows_contribute_nothing(self):
        """T=50 with 32-blocks: zero-padded q rows must not poison dk/dv
        (the lse=0 + masked-p guard)."""
        rs = np.random.RandomState(8)
        q, k, v = _qkv(rs, 1, 50, 2, 16)
        g = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=32, block_k=32,
            interpret=True, bwd="pallas") ** 2), argnums=(0, 1, 2))(q, k, v)
        ref = jax.grad(lambda q, k, v: jnp.sum(
            _reference(q, k, v, True) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, ref):
            assert np.all(np.isfinite(np.asarray(a)))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=2e-4)

    def test_bf16_inputs(self):
        rs = np.random.RandomState(9)
        q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(rs, 1, 32, 2, 16))
        g = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16,
            interpret=True).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a in g:
            assert a.dtype == jnp.bfloat16
            assert np.all(np.isfinite(np.asarray(a, np.float32)))

    def test_bf16_numerics_close_to_f32_reference(self):
        """The native-dtype matmul path (p cast to bf16 before the
        accumulating dots) must stay within bf16 tolerance of the f32
        dense reference — guards against a future change accumulating in
        bf16."""
        rs = np.random.RandomState(11)
        qf, kf, vf = _qkv(rs, 2, 48, 2, 32)
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (qf, kf, vf))
        out_b = flash_attention(qb, kb, vb, causal=True, block_q=16,
                                block_k=16, interpret=True)
        ref = _reference(qf, kf, vf, True)
        np.testing.assert_allclose(
            np.asarray(out_b, np.float32), np.asarray(ref, np.float32),
            rtol=2e-2, atol=2e-2)
        gb = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16,
            interpret=True).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))(qb, kb, vb)
        gf = jax.grad(lambda q, k, v: jnp.sum(
            _reference(q, k, v, True) ** 2), argnums=(0, 1, 2))(qf, kf, vf)
        for a, b in zip(gb, gf):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=5e-2, atol=5e-2)

    def test_bad_bwd_flag_rejected(self):
        rs = np.random.RandomState(10)
        q, k, v = _qkv(rs, 1, 8, 1, 8)
        with pytest.raises(ValueError, match="bwd"):
            flash_attention(q, k, v, bwd="nope")


class TestLayerPolicy:
    def _layer_out(self, use_flash, x, mask=None):
        from deeplearning4j_tpu.nn.input_type import InputType
        from deeplearning4j_tpu.nn.layers import MultiHeadAttention

        C = x.shape[-1]
        mha = MultiHeadAttention(n_heads=2, causal=True, use_flash=use_flash)
        params = mha.init(jax.random.PRNGKey(0), InputType.recurrent(C, 12))
        y, _ = mha.apply(params, {}, x, mask=mask)
        return np.asarray(y)

    # C = 16: heads of 8, transposed around the kernels; C = 128: heads of
    # 64, the kernels read the fused projection in place
    @pytest.mark.parametrize("C", [16, 128])
    def test_forced_flash_equals_xla_path(self, C):
        rs = np.random.RandomState(3)
        x = jnp.asarray(rs.randn(2, 12, C).astype(np.float32))
        np.testing.assert_allclose(
            self._layer_out(True, x), self._layer_out(False, x),
            rtol=1e-5, atol=2e-5)

    @pytest.mark.parametrize("masked", [False, True], ids=["nomask", "kmask"])
    def test_fused_projection_gradients_equal_xla_path(self, masked):
        """The layer's parameter and input gradients through the fused
        entry point (heads of 64: two a lane block) against the XLA path."""
        from deeplearning4j_tpu.nn.input_type import InputType
        from deeplearning4j_tpu.nn.layers import MultiHeadAttention

        rs = np.random.RandomState(6)
        B, T, C = 2, 24, 256
        x = jnp.asarray(rs.randn(B, T, C).astype(np.float32))
        mask = jnp.asarray((np.arange(T)[None] < np.array([[24], [15]]))
                           .astype(np.float32)) if masked else None
        grads = []
        for use_flash in (True, False):
            mha = MultiHeadAttention(n_heads=4, causal=True,
                                     use_flash=use_flash)
            params = mha.init(jax.random.PRNGKey(0),
                              InputType.recurrent(C, T))
            grads.append(jax.grad(
                lambda p, x: jnp.sum(mha.apply(p, {}, x, mask=mask)[0] ** 2),
                argnums=(0, 1))(params, x))
        for a, b in zip(jax.tree_util.tree_leaves(grads[0]),
                        jax.tree_util.tree_leaves(grads[1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_auto_on_cpu_uses_xla_path(self):
        # same numbers (it IS the XLA path on CPU) — and no interpreter cost
        rs = np.random.RandomState(4)
        x = jnp.asarray(rs.randn(1, 8, 16).astype(np.float32))
        np.testing.assert_allclose(
            self._layer_out("auto", x), self._layer_out(False, x),
            rtol=0, atol=0)

    def test_masked_attention_uses_flash(self):
        # round 5: a key mask runs IN the kernel (forced flash) and matches
        # the masked XLA path to float tolerance
        rs = np.random.RandomState(5)
        x = jnp.asarray(rs.randn(2, 12, 16).astype(np.float32))
        mask = jnp.asarray(np.concatenate(
            [np.ones((2, 9)), np.zeros((2, 3))], 1).astype(np.float32))
        np.testing.assert_allclose(
            self._layer_out(True, x, mask), self._layer_out(False, x, mask),
            rtol=1e-5, atol=2e-5)

    def test_serde_round_trip_with_flag(self):
        from deeplearning4j_tpu.nn.config import LayerConfig
        from deeplearning4j_tpu.nn.layers import MultiHeadAttention

        cfg = MultiHeadAttention(n_heads=4, causal=True, use_flash=False)
        assert LayerConfig.from_json(cfg.to_json()) == cfg


class TestChunkedBackward:
    def test_chunked_reference_matches_dense(self):
        from deeplearning4j_tpu.ops.flash_attention import _reference_chunked

        rs = np.random.RandomState(6)
        q, k, v = _qkv(rs, 2, 50, 2, 16)
        for causal in (False, True):
            np.testing.assert_allclose(
                np.asarray(_reference_chunked(q, k, v, causal, chunk=16)),
                np.asarray(_reference(q, k, v, causal)),
                rtol=1e-5, atol=2e-5)

    def test_vjp_grads_match_dense_reference(self):
        rs = np.random.RandomState(7)
        q, k, v = _qkv(rs, 1, 40, 2, 8)
        gf = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16, interpret=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(
            _reference(q, k, v, True) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=2e-4)

    def test_transformer_block_forwards_flag(self):
        from deeplearning4j_tpu.nn.layers import TransformerBlock

        blk = TransformerBlock(n_heads=2, use_flash=False)
        assert blk._mha().use_flash is False

    def test_chunked_path_gradients(self):
        # the long-T branch of _flash_bwd differentiates _reference_chunked
        # through lax.map — cover that vjp machinery directly (the adaptive
        # threshold keeps small-T tests on the dense branch otherwise)
        from deeplearning4j_tpu.ops.flash_attention import _reference_chunked

        rs = np.random.RandomState(8)
        q, k, v = _qkv(rs, 1, 40, 2, 8)
        for causal in (False, True):
            gc = jax.grad(lambda q, k, v: jnp.sum(_reference_chunked(
                q, k, v, causal, chunk=16).astype(jnp.float32) ** 2),
                argnums=(0, 1, 2))(q, k, v)
            gd = jax.grad(lambda q, k, v: jnp.sum(
                _reference(q, k, v, causal).astype(jnp.float32) ** 2),
                argnums=(0, 1, 2))(q, k, v)
            for a, b in zip(gc, gd):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-4, atol=2e-4)


class TestBlockMerge:
    """flash_attention_block + merge_attention_blocks: the chunked/ring
    building block (forward-only, absolute position offsets)."""

    def test_two_chunk_merge_equals_full(self):
        from deeplearning4j_tpu.ops.flash_attention import (
            flash_attention_block, merge_attention_blocks)

        rs = np.random.RandomState(0)
        B, T, H, D = 2, 64, 2, 16
        q, k, v = _qkv(rs, B, T, H, D)
        half = T // 2
        p0 = flash_attention_block(q, k[:, :half], v[:, :half],
                                   q_offset=0, k_offset=0,
                                   block_q=16, block_k=16, interpret=True)
        p1 = flash_attention_block(q, k[:, half:], v[:, half:],
                                   q_offset=0, k_offset=half,
                                   block_q=16, block_k=16, interpret=True)
        merged = merge_attention_blocks([p0, p1])
        ref = _reference(q, k, v, False)
        np.testing.assert_allclose(np.asarray(merged), np.asarray(ref),
                                   rtol=1e-5, atol=2e-5)

    def test_causal_offsets_ring_style(self):
        """The second sequence shard's queries (absolute offset T0) attend
        chunk 0 fully and chunk 1 causally — merged result equals the
        corresponding rows of full causal attention."""
        from deeplearning4j_tpu.ops.flash_attention import (
            flash_attention_block, merge_attention_blocks)

        rs = np.random.RandomState(1)
        B, T, H, D = 2, 64, 2, 16
        q, k, v = _qkv(rs, B, T, H, D)
        half = T // 2
        q1 = q[:, half:]
        p0 = flash_attention_block(q1, k[:, :half], v[:, :half],
                                   q_offset=half, k_offset=0, causal=True,
                                   block_q=16, block_k=16, interpret=True)
        p1 = flash_attention_block(q1, k[:, half:], v[:, half:],
                                   q_offset=half, k_offset=half, causal=True,
                                   block_q=16, block_k=16, interpret=True)
        merged = merge_attention_blocks([p0, p1])
        ref = _reference(q, k, v, True)[:, half:]
        np.testing.assert_allclose(np.asarray(merged), np.asarray(ref),
                                   rtol=1e-5, atol=2e-5)

    def test_fully_masked_chunk_vanishes(self):
        """Causal q at offset 0 sees nothing of a future k chunk: its lse is
        ~-1e30 so the merge weight underflows to zero, no NaNs."""
        from deeplearning4j_tpu.ops.flash_attention import (
            flash_attention_block, merge_attention_blocks)

        rs = np.random.RandomState(2)
        B, T, H, D = 1, 32, 2, 16
        q, k, v = _qkv(rs, B, T, H, D)
        p_own = flash_attention_block(q, k, v, q_offset=0, k_offset=0,
                                      causal=True, block_q=16, block_k=16,
                                      interpret=True)
        p_future = flash_attention_block(q, k, v, q_offset=0, k_offset=T,
                                         causal=True, block_q=16, block_k=16,
                                         interpret=True)
        merged = merge_attention_blocks([p_own, p_future])
        ref = _reference(q, k, v, True)
        assert np.all(np.isfinite(np.asarray(merged, np.float32)))
        np.testing.assert_allclose(np.asarray(merged), np.asarray(ref),
                                   rtol=1e-5, atol=2e-5)


class TestDifferentiableBlocks:
    """flash_attention_block_grad: gradients flow through BOTH out and lse
    (the dlse -> delta shift), so chunk-merged attention trains exactly
    like full attention."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("T", [64, 40])  # 40: chunks of 20 pad to 32
    def test_merged_chunk_grads_equal_full(self, causal, T):
        from deeplearning4j_tpu.ops.flash_attention import (
            flash_attention_block_grad, merge_attention_blocks)

        rs = np.random.RandomState(0)
        B, H, D = 2, 2, 16
        q, k, v = _qkv(rs, B, T, H, D)
        half = T // 2

        def loss_chunked(q, k, v):
            p0 = flash_attention_block_grad(
                q, k[:, :half], v[:, :half], q_offset=0, k_offset=0,
                causal=causal, block_q=16, block_k=16, interpret=True)
            p1 = flash_attention_block_grad(
                q, k[:, half:], v[:, half:], q_offset=0, k_offset=half,
                causal=causal, block_q=16, block_k=16, interpret=True)
            return jnp.sum(merge_attention_blocks([p0, p1]) ** 2)

        def loss_full(q, k, v):
            return jnp.sum(_reference(q, k, v, causal) ** 2)

        gc = jax.grad(loss_chunked, argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gc, gf):
            assert np.all(np.isfinite(np.asarray(a)))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=5e-4)

    def test_ring_style_sharded_q_grads(self):
        """Both q shards' chunk-merged losses summed: total grads equal the
        full causal attention's — the ring-attention training identity."""
        from deeplearning4j_tpu.ops.flash_attention import (
            flash_attention_block_grad, merge_attention_blocks)

        rs = np.random.RandomState(1)
        B, T, H, D = 1, 48, 2, 16
        q, k, v = _qkv(rs, B, T, H, D)
        half = T // 2

        def loss_ring(q, k, v):
            total = 0.0
            for si, off in ((0, 0), (1, half)):
                qs = q[:, off:off + half]
                parts = []
                for ko in (0, half):
                    parts.append(flash_attention_block_grad(
                        qs, k[:, ko:ko + half], v[:, ko:ko + half],
                        q_offset=off, k_offset=ko, causal=True,
                        block_q=16, block_k=16, interpret=True))
                total = total + jnp.sum(merge_attention_blocks(parts) ** 2)
            return total

        def loss_full(q, k, v):
            return jnp.sum(_reference(q, k, v, True) ** 2)

        gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gr, gf):
            assert np.all(np.isfinite(np.asarray(a)))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=5e-4)


class TestKmask:
    """Round-5: key-validity masks inside the kernel (VERDICT r4 #4) —
    forward and both Pallas backwards match the masked XLA oracle."""

    @staticmethod
    def _mask(rs, B, T):
        # variable-length padding: every row keeps >=1 valid key
        lens = rs.randint(1, T + 1, B)
        m = (np.arange(T)[None, :] < lens[:, None]).astype(np.float32)
        return jnp.asarray(m)

    @pytest.mark.parametrize("shape", [(2, 16, 2, 8), (2, 50, 3, 32)])
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_masked_reference(self, shape, causal):
        rs = np.random.RandomState(7)
        q, k, v = _qkv(rs, *shape)
        km = self._mask(rs, shape[0], shape[1])
        out = flash_attention(q, k, v, kmask=km, causal=causal,
                              block_q=16, block_k=16, interpret=True)
        ref = _reference(q, k, v, causal, kmask=km)
        # compare only valid QUERY rows (padded-position queries are
        # meaningless and masked downstream by the layer stack)
        w = np.asarray(km)[:, :, None, None]
        np.testing.assert_allclose(np.asarray(out) * w, np.asarray(ref) * w,
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_pallas_backward_matches_masked_reference(self, causal):
        rs = np.random.RandomState(8)
        B, T, H, D = 2, 40, 2, 16
        q, k, v = _qkv(rs, B, T, H, D)
        km = self._mask(rs, B, T)
        w = jnp.asarray(np.asarray(km)[:, :, None, None])

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, kmask=km, causal=causal,
                                block_q=16, block_k=16, interpret=True,
                                bwd="pallas")
            return jnp.sum((o * w) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum((_reference(q, k, v, causal, kmask=km) * w) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

    def test_xla_bwd_flag_with_kmask(self):
        rs = np.random.RandomState(9)
        B, T, H, D = 1, 24, 2, 8
        q, k, v = _qkv(rs, B, T, H, D)
        km = self._mask(rs, B, T)
        w = jnp.asarray(np.asarray(km)[:, :, None, None])
        gp = jax.grad(lambda q: jnp.sum((flash_attention(
            q, k, v, kmask=km, causal=True, block_q=8, block_k=8,
            interpret=True, bwd="pallas") * w) ** 2))(q)
        gx = jax.grad(lambda q: jnp.sum((flash_attention(
            q, k, v, kmask=km, causal=True, block_q=8, block_k=8,
            interpret=True, bwd="xla") * w) ** 2))(q)
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gx),
                                   rtol=2e-4, atol=2e-5)

    def test_masked_keys_get_zero_kv_grads(self):
        """dk/dv at masked key positions must be exactly zero."""
        rs = np.random.RandomState(10)
        B, T, H, D = 2, 16, 2, 8
        q, k, v = _qkv(rs, B, T, H, D)
        km = jnp.asarray(np.concatenate(
            [np.ones((B, 10)), np.zeros((B, 6))], 1).astype(np.float32))
        gk, gv = jax.grad(lambda k, v: jnp.sum(flash_attention(
            q, k, v, kmask=km, block_q=8, block_k=8, interpret=True) ** 2),
            argnums=(0, 1))(k, v)
        np.testing.assert_allclose(np.asarray(gk)[:, 10:], 0.0, atol=0)
        np.testing.assert_allclose(np.asarray(gv)[:, 10:], 0.0, atol=0)

    def test_chunked_block_kmask_merge_equals_full(self):
        """Two key chunks with per-chunk kmask slices merge to the full
        masked attention (the ring path's building block)."""
        from deeplearning4j_tpu.ops.flash_attention import (
            flash_attention_block_grad, merge_attention_blocks)

        rs = np.random.RandomState(11)
        B, T, H, D = 2, 32, 2, 8
        q, k, v = _qkv(rs, B, T, H, D)
        km = self._mask(rs, B, T)
        half = T // 2
        parts = [
            flash_attention_block_grad(
                q, k[:, :half], v[:, :half], kmask=km[:, :half],
                q_offset=0, k_offset=0, block_q=8, block_k=8, interpret=True),
            flash_attention_block_grad(
                q, k[:, half:], v[:, half:], kmask=km[:, half:],
                q_offset=0, k_offset=half, block_q=8, block_k=8,
                interpret=True),
        ]
        out = merge_attention_blocks(parts)
        ref = _reference(q, k, v, False, kmask=km)
        w = np.asarray(km)[:, :, None, None]
        np.testing.assert_allclose(np.asarray(out) * w, np.asarray(ref) * w,
                                   rtol=1e-5, atol=1e-5)

    def test_left_padded_bwd_flags_agree(self):
        """Left-padded kmask + causal: rows with zero valid keys must get
        identical (zero) gradients from bwd='pallas' and bwd='xla'."""
        rs = np.random.RandomState(12)
        B, T, H, D = 2, 16, 2, 8
        q, k, v = _qkv(rs, B, T, H, D)
        km = jnp.asarray(np.concatenate(
            [np.zeros((B, 5)), np.ones((B, 11))], 1).astype(np.float32))

        def grads(bwd):
            return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
                q, k, v, kmask=km, causal=True, block_q=8, block_k=8,
                interpret=True, bwd=bwd) ** 2), argnums=(0, 1, 2))(q, k, v)

        gp, gx = grads("pallas"), grads("xla")
        for a, b in zip(gp, gx):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)
        # fully-masked query rows (0..4): dq exactly zero in both
        np.testing.assert_allclose(np.asarray(gp[0])[:, :5], 0.0, atol=0)
        np.testing.assert_allclose(np.asarray(gx[0])[:, :5], 0.0, atol=0)


class TestUnderAMesh:
    """GSPMD cannot partition a Mosaic kernel, so under an active
    multi-device mesh the attention layer runs the kernel inside a
    shard_map over (data, model) — nn/layers/attention.py _sharded_flash.
    Values and gradients must not depend on the mesh shape."""

    # heads of 16 are transposed around the kernels on every shard; heads of
    # 64 pair up where a shard holds an even count (4 heads, or 2 under
    # model=2) and are transposed where it holds 3 (6 heads under model=2):
    # the addressing follows the LOCAL head count
    @pytest.mark.parametrize("H,C", [(4, 64), (4, 256), (6, 384)],
                             ids=["d16", "d64", "d64-odd-local-heads"])
    @pytest.mark.parametrize("masked", [False, True], ids=["nomask", "kmask"])
    def test_sharded_kernel_matches_unsharded(self, masked, H, C):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from deeplearning4j_tpu.nn.input_type import InputType
        from deeplearning4j_tpu.nn.layers.attention import MultiHeadAttention
        from deeplearning4j_tpu.parallel import MeshSpec, make_mesh, use_mesh

        B, T = 4, 32
        layer = MultiHeadAttention(n_heads=H, causal=True, use_flash=True)
        params = layer.init(jax.random.PRNGKey(0),
                            InputType.recurrent(C, T), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (B, T, C))
        lens = jnp.array([32, 20, 9, 32])
        m = (jnp.arange(T)[None] < lens[:, None]).astype(jnp.float32) \
            if masked else None

        def loss(p, x, m):
            y, _ = layer.apply(p, {}, x, mask=m)
            return jnp.sum(y ** 2)

        ref = jax.jit(jax.value_and_grad(loss))(params, x, m)
        for spec in (MeshSpec(data=4), MeshSpec(data=2, model=2)):
            mesh = make_mesh(spec, jax.devices()[:4])
            rows = NamedSharding(mesh, P("data"))
            with use_mesh(mesh):
                got = jax.jit(jax.value_and_grad(loss))(
                    params, jax.device_put(x, rows),
                    None if m is None else jax.device_put(m, rows))
            for a, b in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(ref)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=2e-4, atol=2e-4)


class TestBlockChooser:
    """Blocks come from the shapes (ops/flash_attention.py choose_blocks):
    the tier-1 kernels run in the interpreter at explicit small blocks, so
    the sizes the chip runs at are checked here from the estimate alone."""

    @pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
    @pytest.mark.parametrize("T,D,item,mask,heads", [
        (50, 64, 4, False, 1),     # below one block: the whole length
        (128, 64, 2, True, 1),
        (200, 128, 2, False, 1),   # not a multiple of 128: padded to 256
        (256, 64, 4, False, 1),    # gpt2m-f32-train-b32-t256, transposed
        (1000, 64, 4, True, 1),
        (1024, 64, 4, False, 1),   # gpt2m-f32-train-b8-t1024, transposed
        (2048, 128, 2, True, 1),   # chip_smoke.py's kernels phase
        (8192, 64, 2, False, 1),   # the long-context envelope
        # two heads of 64 (four of 32) a lane block: the same blocks
        (256, 64, 4, False, 2),    # gpt2m-f32-train-b32-t256 as it runs
        (1024, 64, 4, False, 2),   # gpt2m-f32-train-b8-t1024 as it runs
        (1024, 64, 2, True, 2),
        (1536, 32, 4, False, 4),
        (8192, 64, 2, False, 2),
    ])
    def test_blocks_divide_and_fit(self, kernel, T, D, item, mask, heads):
        bq, bk = fa.choose_blocks(kernel, T, T, D, item, mask, heads)
        t_pad = fa._padded(T)
        assert t_pad >= T and (t_pad == T or t_pad % 128 == 0)
        for b in (bq, bk):
            assert t_pad % b == 0 and (b % 128 == 0 or b == T)
        # nothing here is near _VMEM_MAX, so: the loop side is the largest
        # divisor up to the cap, the grid side the whole sequence up to
        # _MAX_WHOLE (one program a head, no loop) and the same divisor past it
        cap = max(b for b in (t_pad, 128, 256, 384, 512)
                  if t_pad % b == 0 and b <= fa._MAX_BLOCK)
        grid, loop = (bq, bk) if kernel != "dkv" else (bk, bq)
        assert loop == cap
        assert grid == (t_pad if t_pad <= fa._MAX_WHOLE else cap)
        need = fa._working_set(kernel, bq, bk, t_pad, t_pad, D, item, mask,
                               heads)
        params = fa._compiler_params(kernel, bq, bk, t_pad, t_pad, D, item,
                                     mask, heads)
        if need <= fa._VMEM_SHARE * fa._VMEM_DEFAULT:
            assert params == {}
        else:       # T = 8192: the whole-sequence operands want more VMEM
            limit = params["compiler_params"].vmem_limit_bytes
            assert T >= 1000 and fa._VMEM_DEFAULT <= limit <= fa._VMEM_MAX
            assert need < limit

    @pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
    def test_blocks_shrink_only_past_the_vmem_ceiling(self, kernel):
        """T = 32768 at D = 128 float32 still runs 512 x 512 under a raised
        limit; at T = 65536 the whole-sequence operands alone pass what the
        chip has, the smallest tiling comes back and the limit is the
        ceiling (such a call belongs on the ring path)."""
        D, item = 128, 4
        assert fa.choose_blocks(kernel, 32768, 32768, D, item) == (512, 512)
        need = fa._working_set(kernel, 512, 512, 32768, 32768, D, item, False)
        limit = fa._compiler_params(kernel, 512, 512, 32768, 32768, D, item,
                                    False)["compiler_params"].vmem_limit_bytes
        assert need < limit <= fa._VMEM_MAX
        T = 65536
        assert fa.choose_blocks(kernel, T, T, D, item) == (128, 128)
        assert fa._compiler_params(kernel, 128, 128, T, T, D, item, False)[
            "compiler_params"].vmem_limit_bytes == fa._VMEM_MAX

    def test_lane_padding_of_a_narrow_head_is_counted(self):
        a = fa._working_set("fwd", 256, 256, 1024, 1024, 64, 4, False)
        b = fa._working_set("fwd", 256, 256, 1024, 1024, 128, 4, False)
        assert a == b           # D = 64 occupies 128 lanes all the same

    @pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
    def test_two_heads_a_block_count_their_rows_and_results(self, kernel):
        """Two heads of 64 in a lane block: the operands are the 128 lanes
        one head's were counted at, the float32 tiles are one head's (the
        heads run one after another); what is added is a second lse/delta
        row a block and the result the heads' columns are gathered into."""
        one = fa._working_set(kernel, 512, 512, 1024, 1024, 64, 4, False)
        two = fa._working_set(kernel, 512, 512, 1024, 1024, 64, 4, False, 2)
        rows = {"fwd": 1, "dq": 2, "dkv": 2}[kernel] * 8 * (
            1024 if kernel == "dkv" else 512) * 4
        results = {"fwd": 1, "dq": 1, "dkv": 2}[kernel] * 512 * 128 * 4
        assert two - one == 2 * rows + results

    @pytest.mark.parametrize("same_len", [False, True])
    def test_explicit_blocks_win(self, same_len):
        blocks, q_pad, k_pad = fa._plan(("dq", "dkv"), 1000, 600, 64, 4,
                                        False, 32, 16, same_len)
        assert blocks == {"dq": (32, 16), "dkv": (32, 16)}
        assert q_pad % 32 == 0 and k_pad % 16 == 0 and q_pad >= 1000
        # short sequences still clamp to T
        blocks, q_pad, k_pad = fa._plan(("fwd",), 20, 20, 64, 4, False,
                                        128, 128, same_len)
        assert blocks == {"fwd": (20, 20)} and (q_pad, k_pad) == (20, 20)
        with pytest.raises(ValueError, match="both"):
            fa._plan(("fwd",), 256, 256, 64, 4, False, 128, None)


class TestTileSchedule:
    """The split loops: tiles wholly on the valid side of the diagonal run
    a body without iota/compare/where, the diagonal's and the padded tail's
    run the masked one. Forward, dq and dk/dv against the dense reference
    and the XLA-remat oracle, at every shape of tiling the ranges have a
    case for."""

    CASES = [
        pytest.param(64, 16, 16, False, id="bq=bk"),
        pytest.param(64, 32, 16, False, id="bq=2bk"),
        pytest.param(64, 16, 32, False, id="bk=2bq"),
        pytest.param(50, 16, 16, False, id="padded-tail"),
        pytest.param(50, 32, 16, False, id="padded-bq=2bk"),
        pytest.param(50, 16, 32, False, id="padded-bk=2bq"),
        pytest.param(64, 32, 16, True, id="kmask-bq=2bk"),
        pytest.param(50, 16, 32, True, id="kmask-padded-bk=2bq"),
    ]

    @pytest.mark.parametrize("causal", [True, False],
                             ids=["causal", "full"])
    @pytest.mark.parametrize("T,bq,bk,masked", CASES)
    def test_fwd_dq_dkv_match_reference_and_xla(self, T, bq, bk, masked,
                                                causal):
        rs = np.random.RandomState(T + bq + 2 * bk)
        B, H, D = 2, 2, 16
        q, k, v = _qkv(rs, B, T, H, D)
        km = TestKmask._mask(rs, B, T) if masked else None
        w = 1.0 if km is None else jnp.asarray(
            np.asarray(km)[:, :, None, None])

        def val_and_grads(fn):
            return jax.value_and_grad(
                lambda q, k, v: jnp.sum((fn(q, k, v) * w) ** 2),
                argnums=(0, 1, 2))(q, k, v)

        def flash(bwd):
            return lambda q, k, v: flash_attention(
                q, k, v, kmask=km, causal=causal, block_q=bq, block_k=bk,
                interpret=True, bwd=bwd)

        lp, gp = val_and_grads(flash("pallas"))
        lx, gx = val_and_grads(flash("xla"))
        lr, gr = val_and_grads(
            lambda q, k, v: _reference(q, k, v, causal, kmask=km))
        np.testing.assert_allclose(float(lp), float(lr), rtol=1e-5)
        np.testing.assert_allclose(float(lp), float(lx), rtol=1e-6)
        for a, b, c in zip(gp, gx, gr):
            assert np.all(np.isfinite(np.asarray(a)))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("H,D", [(2, 16), (4, 64)],
                             ids=["transposed", "h2"])
    @pytest.mark.parametrize("bq,bk", [(16, 16), (32, 16), (16, 32)])
    def test_unequal_offsets_mask_every_tile(self, bq, bk, H, D):
        """q_offset != k_offset (ring and chunked blocks): no tile may take
        the plain body, whatever the blocks; the second q shard against
        both key chunks equals its rows of the full causal attention, in
        value and in all three gradients."""
        from deeplearning4j_tpu.ops.flash_attention import (
            flash_attention_block_grad, merge_attention_blocks)

        rs = np.random.RandomState(3)
        B, T = 1, 64
        q, k, v = _qkv(rs, B, T, H, D)
        half = T // 2

        def loss_chunks(q, k, v):
            parts = [flash_attention_block_grad(
                q[:, half:], k[:, ko:ko + half], v[:, ko:ko + half],
                q_offset=half, k_offset=ko, causal=True, block_q=bq,
                block_k=bk, interpret=True) for ko in (0, half)]
            return jnp.sum(merge_attention_blocks(parts) ** 2)

        def loss_full(q, k, v):
            return jnp.sum(_reference(q, k, v, True)[:, half:] ** 2)

        lc, gc = jax.value_and_grad(loss_chunks, argnums=(0, 1, 2))(q, k, v)
        lf, gf = jax.value_and_grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(float(lc), float(lf), rtol=1e-5)
        for a, b in zip(gc, gf):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=5e-4)

    @staticmethod
    def _kinds(ranges, n_tiles):
        """tile index -> the body's flags, for every tile the ranges visit
        (static counts and bounds only: the cases below keep them so)."""
        seen = {}
        for lo, count, kw in ranges:
            for i in range(int(lo), int(lo) + int(count)):
                assert i not in seen and 0 <= i < n_tiles
                seen[i] = kw
        return seen

    @pytest.mark.parametrize("bq,bk,t_real,t_pad", [
        (16, 16, 64, 64), (32, 16, 64, 64), (16, 32, 64, 64),
        (64, 64, 64, 64),                  # one tile: no loop at all
        (16, 16, 50, 64), (32, 16, 50, 64), (16, 32, 50, 64),
        (48, 32, 96, 96),                  # neither block divides the other
    ])
    @pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
    def test_ranges_against_the_dense_mask(self, bq, bk, t_real, t_pad,
                                           causal):
        """Every (q-block, k-block) pair against the dense validity mask,
        for the key loop (forward, dq) and the query loop (dk/dv): a tile
        that is not visited holds no valid entry, a tile that takes the
        plain body holds no invalid one, and when one block divides the
        other and nothing is padded the diagonal's tiles are a static
        max(1, bq/bk) (straight-line code, not a second loop)."""
        rows = np.arange(t_pad)[:, None]
        cols = np.arange(t_pad)[None, :]
        ok = (cols < t_real) & (rows < t_real)
        if causal:
            ok &= cols <= rows
        n_q, n_k = t_pad // bq, t_pad // bk
        split = causal and fa._nested(bq, bk) and t_real == t_pad and n_k > 1
        for qi in range(n_q):
            ranges = fa._key_ranges(qi, bq, bk, t_pad, t_pad, t_real, causal,
                                    True)
            if split:
                assert ranges[-1][1] == max(1, bq // bk)
                assert isinstance(ranges[-1][1], int)
            kinds = self._kinds(ranges, n_k)
            for kb in range(n_k):
                # padded q rows are the caller's to slice off: judge the
                # key loop on the real rows of the block
                tile = ok[qi * bq:(qi + 1) * bq, kb * bk:(kb + 1) * bk]
                real = tile[:max(0, min(bq, t_real - qi * bq))]
                if kb not in kinds:
                    assert not tile.any()
                elif not kinds[kb]["masked"]:
                    assert real.all()
        for ki in range(n_k):
            ranges = fa._query_ranges(ki, bq, bk, t_pad, t_pad, t_real,
                                      causal, True)
            if split and n_q > 1:
                assert ranges[0][1] == max(1, bk // bq)
            kinds = self._kinds(ranges, n_q)
            for qb in range(n_q):
                tile = ok[qb * bq:(qb + 1) * bq, ki * bk:(ki + 1) * bk]
                real = tile[:, :max(0, min(bk, t_real - ki * bk))]
                if qb not in kinds:
                    assert not tile.any()
                    continue
                if not kinds[qb]["diag"] and causal:
                    rr, cc = np.meshgrid(np.arange(qb * bq, (qb + 1) * bq),
                                         np.arange(ki * bk, (ki + 1) * bk),
                                         indexing="ij")
                    assert (cc <= rr).all()
                if not kinds[qb]["tail"]:
                    assert (qb + 1) * bq <= t_real

    def test_unequal_offsets_mask_every_tile_ranges(self):
        assert fa._key_ranges(1, 16, 16, 64, 64, 64, True, False) == [
            (0, 4, {"masked": True})]
        assert fa._query_ranges(1, 16, 16, 64, 64, 64, True, False) == [
            (0, 4, {"diag": True, "tail": False})]


class TestKernelNamesAndResults:
    """benchmark/metrics/flash_{fwd,bwd}_roofline.json find the kernels by
    the END of the Mosaic call's name, which is its result types: forward
    = (x[BH,t_pad,D], f32[BH,1,t_pad]); dk/dv = two equal results; dq = one
    three-dimensional result. The names carry the blocks that ran. A change
    that would silence a roofline fails here first."""

    @staticmethod
    def _pallas_eqns(jaxpr, out):
        out.extend(e for e in _eqns(jaxpr, [])
                   if e.primitive.name == "pallas_call")
        return out

    @pytest.mark.parametrize("H,D,tag", [(3, 8, ""), (4, 64, "_h2"),
                                         (4, 32, "_h4"), (3, 128, "_h1"),
                                         (3, 64, "")],
                             ids=["d8", "d64", "d32", "d128", "d64-odd"])
    @pytest.mark.parametrize("T,bq,bk,t_pad", [(64, 32, 16, 64),
                                               (50, 16, 32, 64)])
    def test_three_names_with_blocks_and_result_types(self, T, bq, bk, t_pad,
                                                      H, D, tag):
        """``tag``: the heads that share a lane block, in the name; none
        where the call transposes to [B*H, T, D] (an odd head count at
        D = 64 says so by its name). Head-addressed results are
        [B, t_pad, H*D]: still one rank-3 array for dq, two equal ones for
        dk/dv, and a float32 [B*H, 1, t_pad] beside the forward's."""
        B = 2
        q = jnp.ones((B, T, H, D), jnp.float32)
        jp = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True)),
            argnums=(0, 1, 2)))(q, q, q)
        got = {e.params["name"]: [(tuple(v.aval.shape), str(v.aval.dtype))
                                  for v in e.outvars]
               for e in self._pallas_eqns(jp.jaxpr, [])}
        BH, x = B * H, "float32"
        res = (BH, t_pad, D) if tag == "" else (B, t_pad, H * D)
        assert fa._layout(H, D).tag == tag
        assert got == {
            f"flash_fwd{tag}_q{bq}_k{bk}": [(res, x),
                                            ((BH, 1, t_pad), "float32")],
            f"flash_bwd_dq{tag}_q{bq}_k{bk}": [(res, x)],
            f"flash_bwd_dkv{tag}_q{bq}_k{bk}": [(res, x)] * 2,
        }

    def test_chosen_blocks_are_in_the_names(self):
        """No blocks given: each kernel's name carries its own choice (the
        trace is where a run says which tiling it took)."""
        B, T, H, D = 1, 256, 1, 8
        q = jnp.ones((B, T, H, D), jnp.bfloat16)
        jp = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, interpret=True).astype(jnp.float32)),
            argnums=(0, 1, 2)))(q, q, q)
        names = {e.params["name"] for e in self._pallas_eqns(jp.jaxpr, [])}
        want = {f"{fa._NAMES[kn]}_q%d_k%d" % fa.choose_blocks(kn, T, T, D, 2)
                for kn in ("fwd", "dq", "dkv")}
        assert names == want


def _eqns(jaxpr, out):
    """Every equation of a jaxpr and of the jaxprs inside it, a Pallas
    kernel's body apart."""
    for e in jaxpr.eqns:
        out.append(e)
        if e.primitive.name == "pallas_call":
            continue
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _eqns(inner, out)
    return out


class TestHeadAddressing:
    """The kernels read and write [B, T, H*D] (ops/flash_attention.py
    ``heads_per_block``): 128 // D heads a 128-lane block where D divides
    128 and that count divides H, one where D is a multiple of 128, the
    transposed [B*H, T, D] arrays otherwise."""

    @pytest.mark.parametrize("D,H,want", [
        (32, 1, None), (32, 3, None), (32, 4, 4), (32, 16, 4),
        (64, 1, None), (64, 3, None), (64, 4, 2), (64, 16, 2),
        (128, 1, 1), (128, 3, 1), (128, 4, 1), (128, 16, 1),
        (256, 3, 1), (80, 4, None), (96, 4, None), (16, 8, 8), (16, 4, None),
    ])
    def test_rule(self, D, H, want):
        assert fa.heads_per_block(H, D) == want
        lay = fa._layout(H, D, fused=True)
        assert lay.transposed == (want is None)
        if want is None:
            assert (lay.heads, lay.groups, lay.mask_rows, lay.tag) == (
                1, 1, H, "")
        else:
            assert (lay.heads, lay.groups, lay.mask_rows) == (want, H // want,
                                                              1)
            assert lay.at == (0, H // want, 2 * H // want)
            assert lay.tag == f"_h{want}"

    # T = 200: padded along T in the new layout; 256: one tile; 1024: two
    # static tiles, no loop; 1536: a real loop. Blocks None: the chooser's.
    CASES = [
        # B, T, H, D, causal, masked, dtype, blocks
        pytest.param(1, 200, 4, 32, True, True, "float32", None, id="d32-t200"),
        pytest.param(1, 256, 16, 32, False, False, "float32", None,
                     id="d32-h16-t256-full"),
        pytest.param(2, 200, 4, 64, True, False, "float32", None,
                     id="d64-t200"),
        pytest.param(1, 200, 4, 64, False, True, "float32", (64, 32),
                     id="d64-t200-kmask-full-blocks"),
        pytest.param(1, 256, 16, 64, True, False, "float32", None,
                     id="d64-h16-t256"),
        pytest.param(1, 256, 4, 64, True, True, "bfloat16", None,
                     id="d64-t256-kmask-bf16"),
        pytest.param(1, 1024, 4, 64, True, False, "float32", None,
                     id="d64-t1024"),
        pytest.param(1, 1536, 2, 64, True, True, "float32", None,
                     id="d64-t1536-kmask"),
        pytest.param(1, 1536, 4, 32, True, False, "bfloat16", None,
                     id="d32-t1536-bf16"),
        pytest.param(1, 200, 1, 128, True, False, "float32", None,
                     id="d128-h1-t200"),
        pytest.param(1, 256, 3, 128, False, True, "float32", (128, 64),
                     id="d128-h3-t256-kmask-full-blocks"),
        pytest.param(1, 1024, 4, 128, True, False, "bfloat16", None,
                     id="d128-t1024-bf16"),
        pytest.param(1, 1536, 1, 128, True, False, "float32", None,
                     id="d128-t1536"),
        # shapes that pair up nowhere say so and take the transposed path
        pytest.param(1, 256, 3, 64, True, True, "float32", None,
                     id="d64-h3-transposed"),
        pytest.param(1, 200, 1, 64, True, False, "float32", None,
                     id="d64-h1-transposed"),
        pytest.param(1, 256, 3, 32, False, False, "float32", None,
                     id="d32-h3-transposed"),
    ]

    @pytest.mark.parametrize("B,T,H,D,causal,masked,dtype,blocks", CASES)
    def test_fwd_and_three_gradients_match_reference(
            self, B, T, H, D, causal, masked, dtype, blocks):
        rs = np.random.RandomState(T + H + D)
        qf, kf, vf = _qkv(rs, B, T, H, D)
        q, k, v = (x.astype(dtype) for x in (qf, kf, vf))
        km = TestKmask._mask(rs, B, T) if masked else None
        w = 1.0 if km is None else jnp.asarray(
            np.asarray(km)[:, :, None, None])
        bq, bk = blocks or (None, None)
        assert (fa._layout(H, D).tag == "") == (
            (D, H) in {(64, 3), (64, 1), (32, 3)})

        def val_and_grads(fn, *xs):
            return jax.value_and_grad(
                lambda q, k, v: jnp.sum(
                    (fn(q, k, v).astype(jnp.float32) * w) ** 2),
                argnums=(0, 1, 2))(*xs)

        lp, gp = val_and_grads(lambda q, k, v: flash_attention(
            q, k, v, kmask=km, causal=causal, block_q=bq, block_k=bk,
            interpret=True), q, k, v)
        lr, gr = val_and_grads(
            lambda q, k, v: _reference(q, k, v, causal, kmask=km), qf, kf, vf)
        tol = dict(rtol=2e-4, atol=2e-4) if dtype == "float32" else dict(
            rtol=5e-2, atol=5e-2)
        np.testing.assert_allclose(float(lp), float(lr),
                                   rtol=1e-5 if dtype == "float32" else 2e-2)
        for a, b in zip(gp, gr):
            assert a.dtype == jnp.dtype(dtype)
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b), **tol)

    @pytest.mark.parametrize("bwd", ["pallas", "xla"])
    @pytest.mark.parametrize("H,D,masked", [(4, 64, False), (4, 64, True),
                                            (4, 32, False), (2, 128, True),
                                            (3, 64, True), (2, 16, False)])
    def test_fused_projection_equals_split(self, H, D, masked, bwd):
        """``flash_attention_qkv`` over [B, T, 3*H*D] against
        ``flash_attention`` over its split: the same numbers, value and
        cotangent, whether the kernels read the fused array in place or
        (an odd head count, heads of 16) it is split and transposed."""
        rs = np.random.RandomState(H * D)
        B, T = 2, 40
        q, k, v = _qkv(rs, B, T, H, D)
        qkv = fa.join_qkv(q, k, v)
        for a, b in zip(fa.split_qkv(qkv, H), (q, k, v)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        km = TestKmask._mask(rs, B, T) if masked else None
        kw = dict(kmask=km, causal=True, block_q=16, block_k=32,
                  interpret=True, bwd=bwd)
        w = jnp.asarray(rs.randn(B, T, H * D).astype(np.float32))

        lf, gf = jax.value_and_grad(lambda x: jnp.sum(
            fa.flash_attention_qkv(x, H, **kw) * w))(qkv)
        ls, gs = jax.value_and_grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, **kw).reshape(B, T, H * D) * w),
            argnums=(0, 1, 2))(q, k, v)
        assert gf.shape == qkv.shape
        np.testing.assert_allclose(float(lf), float(ls), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(gf),
                                   np.asarray(fa.join_qkv(*gs)),
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("fused", [False, True], ids=["qkv", "fused"])
    @pytest.mark.parametrize("shape", [(8, 1024, 16, 64), (32, 256, 16, 64),
                                       (1, 4096, 32, 128)])
    def test_no_q_sized_transpose_in_the_gradient(self, shape, fused):
        """The jaxpr of ``jax.grad`` through the kernels at the benchmark's
        three attention shapes: three Pallas calls and no ``transpose`` of
        an array as large as q (what ``_pad_bh``/``_from_bh`` were twelve
        times a layer); delta's [B, T, H] -> [B, H, T] is the only
        transposition left, a 64th of that."""
        B, T, H, D = shape
        if fused:
            args = (jax.ShapeDtypeStruct((B, T, 3 * H * D), jnp.float32),)
            fn = lambda x: jnp.sum(                     # noqa: E731
                fa.flash_attention_qkv(x, H, causal=True))
        else:
            args = (jax.ShapeDtypeStruct(shape, jnp.float32),) * 3
            fn = lambda q, k, v: jnp.sum(               # noqa: E731
                flash_attention(q, k, v, causal=True))
        jp = jax.make_jaxpr(jax.grad(fn, argnums=tuple(range(len(args)))))(
            *args)
        eqns = _eqns(jp.jaxpr, [])
        assert sum(e.primitive.name == "pallas_call" for e in eqns) == 3
        moved = [e for e in eqns if e.primitive.name == "transpose"
                 and e.invars[0].aval.size >= B * T * H * D]
        assert moved == []
        if fused:       # nor is the projection split or sliced
            assert not [e for e in eqns if e.primitive.name in (
                "split", "slice", "dynamic_slice")
                and e.invars[0].aval.size >= B * T * H * D]

    @pytest.mark.parametrize("D", [16, 64, 128])
    def test_grouped_query_attention_at_two_kv_heads(self, D):
        """``GroupedQueryAttention`` (4 query heads over 2 key-value heads)
        through the kernels against its XLA path, output and every
        gradient: heads of 16 transposed, of 64 two a lane block, of 128
        one."""
        from deeplearning4j_tpu.nn.input_type import InputType
        from deeplearning4j_tpu.nn.layers import GroupedQueryAttention

        rs = np.random.RandomState(D)
        B, T, C = 2, 24, 48
        x = jnp.asarray(rs.randn(B, T, C).astype(np.float32))
        got = []
        for use_flash in (True, False):
            layer = GroupedQueryAttention(n_heads=4, n_kv_heads=2, head_dim=D,
                                          use_flash=use_flash)
            params = layer.init(jax.random.PRNGKey(1),
                                InputType.recurrent(C, T))
            got.append(jax.value_and_grad(
                lambda p, x: jnp.sum(layer.apply(p, {}, x)[0] ** 2),
                argnums=(0, 1))(params, x))
        for a, b in zip(jax.tree_util.tree_leaves(got[0]),
                        jax.tree_util.tree_leaves(got[1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)
