"""Pallas flash-attention kernel (ops/flash_attention.py): interpret-mode
equivalence against the XLA reference (the dual-path pattern of
SURVEY.md §4), gradient parity through the custom VJP, and the layer-level
"auto"/force policy."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.flash_attention import (
    _reference, flash_attention)


def _qkv(rs, B, T, H, D, scale=0.5):
    return tuple(jnp.asarray(rs.randn(B, T, H, D).astype(np.float32) * s)
                 for s in (scale, scale, 1.0))


class TestKernelEquivalence:
    @pytest.mark.parametrize("shape", [(2, 16, 2, 8), (1, 64, 4, 16),
                                       (2, 50, 3, 32), (1, 130, 2, 64)])
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
                                       (2, 50, 3, 32), (1, 130, 2, 64)])
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

        mha = MultiHeadAttention(n_heads=2, causal=True, use_flash=use_flash)
        params = mha.init(jax.random.PRNGKey(0), InputType.recurrent(16, 12))
        y, _ = mha.apply(params, {}, x, mask=mask)
        return np.asarray(y)

    def test_forced_flash_equals_xla_path(self):
        rs = np.random.RandomState(3)
        x = jnp.asarray(rs.randn(2, 12, 16).astype(np.float32))
        np.testing.assert_allclose(
            self._layer_out(True, x), self._layer_out(False, x),
            rtol=1e-5, atol=2e-5)

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

    @pytest.mark.parametrize("masked", [False, True], ids=["nomask", "kmask"])
    def test_sharded_kernel_matches_unsharded(self, masked):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from deeplearning4j_tpu.nn.input_type import InputType
        from deeplearning4j_tpu.nn.layers.attention import MultiHeadAttention
        from deeplearning4j_tpu.parallel import MeshSpec, make_mesh, use_mesh

        B, T, C = 4, 32, 64
        layer = MultiHeadAttention(n_heads=4, causal=True, use_flash=True)
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
