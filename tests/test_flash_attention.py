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


class TestBlockChooser:
    """Blocks come from the shapes (ops/flash_attention.py choose_blocks):
    the tier-1 kernels run in the interpreter at explicit small blocks, so
    the sizes the chip runs at are checked here from the estimate alone."""

    @pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
    @pytest.mark.parametrize("T,D,item,mask", [
        (50, 64, 4, False),        # below one block: the whole length
        (128, 64, 2, True),
        (200, 128, 2, False),      # not a multiple of 128: padded to 256
        (256, 64, 4, False),       # gpt2m-f32-train-b32-t256
        (1000, 64, 4, True),
        (1024, 64, 4, False),      # gpt2m-f32-train-b8-t1024
        (2048, 128, 2, True),      # chip_smoke.py's kernels phase
        (8192, 64, 2, False),      # docs/PERF.md's long-context envelope
    ])
    def test_blocks_divide_and_fit(self, kernel, T, D, item, mask):
        bq, bk = fa.choose_blocks(kernel, T, T, D, item, mask)
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
        need = fa._working_set(kernel, bq, bk, t_pad, t_pad, D, item, mask)
        params = fa._compiler_params(kernel, bq, bk, t_pad, t_pad, D, item,
                                     mask)
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

    @pytest.mark.parametrize("bq,bk", [(16, 16), (32, 16), (16, 32)])
    def test_unequal_offsets_mask_every_tile(self, bq, bk):
        """q_offset != k_offset (ring and chunked blocks): no tile may take
        the plain body, whatever the blocks; the second q shard against
        both key chunks equals its rows of the full causal attention, in
        value and in all three gradients."""
        from deeplearning4j_tpu.ops.flash_attention import (
            flash_attention_block_grad, merge_attention_blocks)

        rs = np.random.RandomState(3)
        B, T, H, D = 1, 64, 2, 16
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
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                out.append(e)
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns") and e.primitive.name != "pallas_call":
                        TestKernelNamesAndResults._pallas_eqns(inner, out)
        return out

    @pytest.mark.parametrize("T,bq,bk,t_pad", [(64, 32, 16, 64),
                                               (50, 16, 32, 64)])
    def test_three_names_with_blocks_and_result_types(self, T, bq, bk, t_pad):
        B, H, D = 2, 3, 8
        q = jnp.ones((B, T, H, D), jnp.float32)
        jp = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True)),
            argnums=(0, 1, 2)))(q, q, q)
        got = {e.params["name"]: [(tuple(v.aval.shape), str(v.aval.dtype))
                                  for v in e.outvars]
               for e in self._pallas_eqns(jp.jaxpr, [])}
        BH, x = B * H, "float32"
        assert got == {
            f"flash_fwd_q{bq}_k{bk}": [((BH, t_pad, D), x),
                                       ((BH, 1, t_pad), "float32")],
            f"flash_bwd_dq_q{bq}_k{bk}": [((BH, t_pad, D), x)],
            f"flash_bwd_dkv_q{bq}_k{bk}": [((BH, t_pad, D), x)] * 2,
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
