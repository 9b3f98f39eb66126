"""Attention / transformer layers — the long-context stack.

Beyond-reference capability (the reference has NO attention layer anywhere —
SURVEY.md §2.5/§5.7; its only long-sequence device is truncated BPTT). Here
transformers are first-class and designed for the TPU:

- ``MultiHeadAttention``: fused qkv projection (one MXU matmul), optional
  causal masking, and optional **sequence parallelism**: when
  ``sequence_parallel=True`` and a mesh with a ``seq`` axis is active (see
  parallel/context.py), attention runs as ring attention over the mesh's
  ``seq`` axis (parallel/ring.py) — K/V blocks rotate over ICI, O(T²) memory
  never materializes on one chip.
- ``TransformerBlock``: pre-LN block (LN→MHA→residual, LN→MLP→residual),
  the standard compilation-friendly composition XLA fuses well.
- ``PositionalEmbedding``: learned positions added to token embeddings.

Tensor parallelism for these layers is sharding metadata, not code: see
parallel/tp.py for the PartitionSpec rules (qkv/mlp-in column-parallel,
out/mlp-out row-parallel).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn import initializers
from deeplearning4j_tpu.nn.config import LayerConfig, register_layer
from deeplearning4j_tpu.nn.input_type import InputType


def _mesh_has_axis(axis: str) -> bool:
    from deeplearning4j_tpu.parallel.context import current_mesh

    mesh = current_mesh()
    return mesh is not None and axis in mesh.shape and mesh.shape[axis] > 1


def _head_axis(mesh, n_heads: int) -> Optional[str]:
    """The mesh axis the head dimension shards over: ``model`` when heads
    are tensor-parallel (column-sharded Wqkv) and divide evenly — the
    kernel then runs on local heads instead of all-gathering activations
    over ``model``."""
    size = mesh.shape.get("model", 1)
    return "model" if size > 1 and n_heads % size == 0 else None


def _sharded_flash(flash, mesh, q, k, v, kmask):
    """The flash kernel under a multi-device mesh. Mosaic kernels are opaque
    to GSPMD ("cannot be automatically partitioned" — a lowering error, first
    seen on the four-chip host), so the call runs inside a shard_map over
    the axes attention is independent along: batch rows over ``data``,
    heads over ``model`` (see ``_head_axis``). Every other mesh axis sees
    replicated operands."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    data = "data" if q.shape[0] % mesh.shape.get("data", 1) == 0 else None
    spec = P(data, None, _head_axis(mesh, q.shape[2]), None)
    args, in_specs = (q, k, v), (spec, spec, spec)
    if kmask is not None:
        args, in_specs = args + (kmask,), in_specs + (P(data, None),)
    # pallas_call outputs carry no vma annotation: check_vma off
    return shard_map(
        lambda q_, k_, v_, m_=None: flash(q_, k_, v_, kmask=m_),
        mesh=mesh, in_specs=in_specs, out_specs=spec,
        check_vma=False)(*args)


@register_layer("positional_embedding")
@dataclass
class PositionalEmbedding(LayerConfig):
    """Learned positional embedding added to the input sequence [B,T,C]."""

    max_len: int = 512

    def init(self, key, input_type, dtype=jnp.float32):
        return {"pos": jax.random.normal(key, (self.max_len, input_type.size), dtype) * 0.02}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        T = x.shape[1]
        return x + params["pos"][:T][None, :, :], state

    def decode_apply(self, params, x, positions):
        """Decode-mode: positions are per-row absolute indices [B, Tc]
        (a chunk mid-stream starts wherever the row's cache ends), not the
        implicit 0..T-1 of the training path. Clipped, not wrapped: padded
        chunk slots may carry positions past the table; their activations
        are dead (masked by the caller's n_new) either way."""
        idx = jnp.clip(positions, 0, params["pos"].shape[0] - 1)
        return x + jnp.take(params["pos"], idx, axis=0)


@register_layer("multi_head_attention")
@dataclass
class MultiHeadAttention(LayerConfig):
    """Multi-head self-attention over [B, T, C].

    ``sequence_parallel``: run the attention core as ring attention over the
    active mesh's ``seq`` axis (requires T divisible by the axis size and the
    time axis sharded over it).
    """

    n_heads: int = 8
    causal: bool = False
    sequence_parallel: bool = False
    attn_dropout: float = 0.0
    weight_init: Any = "xavier"
    # Pallas flash-attention policy (ops/flash_attention.py): "auto" uses
    # the kernel on TPU — masked (kmask) or not; the [T,T] scores never
    # leave VMEM (at T=8192 the XLA path cannot even compile, PERF.md).
    # True forces it everywhere (Pallas interpreter on CPU — slow, for
    # tests); False always uses the XLA einsum path.
    use_flash: Any = "auto"

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def uses_rng(self) -> bool:
        return super().uses_rng() or self.attn_dropout > 0.0

    def init(self, key, input_type, dtype=jnp.float32):
        C = input_type.size
        if C % self.n_heads:
            raise ValueError(f"n_heads={self.n_heads} must divide model dim {C}")
        k1, k2 = jax.random.split(key)
        return {
            # fused qkv: one [C, 3C] matmul onto the MXU
            "Wqkv": initializers.initialize(self.weight_init, k1, (C, 3 * C), C, 3 * C, dtype),
            "bqkv": jnp.zeros((3 * C,), dtype),
            "Wo": initializers.initialize(self.weight_init, k2, (C, C), C, C, dtype),
            "bo": jnp.zeros((C,), dtype),
        }

    def _flash(self):
        """``(flash_attention's settings, active mesh)`` where this call
        takes the flash kernels, else None: "auto" takes them on the TPU,
        True anywhere (the Pallas interpreter off it)."""
        if self.use_flash not in ("auto", True):
            return None
        if self.sequence_parallel and _mesh_has_axis("seq"):
            return None
        on_tpu = jax.default_backend() == "tpu"
        if not (self.use_flash is True or on_tpu):
            return None
        from deeplearning4j_tpu.parallel.context import partitioning_mesh

        # off-TPU (interpreter) the compiled XLA-remat backward is far
        # faster than three interpreted Pallas kernels; kmask loads one
        # [1, block_k] validity row per key block in-kernel. Each kernel
        # picks its blocks from the shapes it is given
        # (ops/flash_attention.py choose_blocks).
        return (dict(causal=self.causal, interpret=not on_tpu,
                     bwd="pallas" if on_tpu else "xla"), partitioning_mesh())

    def _attend(self, q, k, v, kmask=None):
        from deeplearning4j_tpu.parallel.ring import local_attention, ring_self_attention

        if self.sequence_parallel and _mesh_has_axis("seq"):
            from deeplearning4j_tpu.parallel.context import current_mesh

            mesh = current_mesh()
            # tp+sp composition: keep tensor-parallel heads sharded through
            # the ring kernel
            head_axis = _head_axis(mesh, q.shape[2])
            # flash-backed ring (Pallas chunk kernels + exact lse merge) on
            # TPU, same policy as the single-chip flash gate; forced
            # use_flash=True engages it anywhere. kmask rides the ring
            # with its k/v block (round 5 — padded batches keep the flash
            # memory envelope).
            on_tpu = jax.default_backend() == "tpu"
            ring_flash = (
                self.use_flash is True or (self.use_flash == "auto" and on_tpu))
            return ring_self_attention(
                q, k, v, mesh, causal=self.causal, kmask=kmask,
                head_axis=head_axis, use_flash=ring_flash
            )
        settings, mesh = self._flash() or (None, None)
        if settings is not None:
            from deeplearning4j_tpu.ops.flash_attention import flash_attention

            flash = functools.partial(flash_attention, **settings)
            if mesh is None:
                return flash(q, k, v, kmask=kmask)
            return _sharded_flash(flash, mesh, q, k, v, kmask)
        return local_attention(q, k, v, causal=self.causal, kmask=kmask)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        rng_in, rng_attn = (jax.random.split(rng) if rng is not None else (None, None))
        x = self.maybe_dropout_input(x, train, rng_in)
        B, T, C = x.shape
        H = self.n_heads
        qkv = x @ params["Wqkv"] + params["bqkv"]
        kmask = None
        if mask is not None and mask.ndim >= 2:
            kmask = mask.reshape(B, T)  # [B,T] key validity from feature mask
        settings, mesh = self._flash() or (None, None)
        if settings is not None and mesh is None:
            # one chip: the kernels read q, k and v out of the projection
            # where it lies and write [B, T, C]; nothing is split or moved
            from deeplearning4j_tpu.ops.flash_attention import flash_attention_qkv

            out = flash_attention_qkv(qkv, H, kmask=kmask, **settings)
        else:
            q, k, v = jnp.split(qkv.reshape(B, T, 3 * H, C // H), 3, axis=2)
            out = self._attend(q, k, v, kmask).reshape(B, T, C)
        if train and self.attn_dropout > 0.0 and rng_attn is not None:
            keep = 1.0 - self.attn_dropout
            out = jnp.where(jax.random.bernoulli(rng_attn, keep, out.shape), out / keep, 0.0)
        return out @ params["Wo"] + params["bo"], state

    def decode_apply(self, params, x, *, cache, positions):
        """Single-query/chunk attention against a KV cache (serving decode
        path, nn/decode.py). ``x`` [B, Tc, C] is the new-token chunk;
        ``cache`` is a cache view (append + gathered, paged or contiguous —
        the layer never sees the paging); ``positions`` [B, Tc] are the
        chunk's absolute positions. Eval-mode by construction: no dropout,
        no rng. The chunk's own k/v are appended to the cache BEFORE the
        gather, so causal self-attention within the chunk and attention
        over the history are one masked span (ops.decode_attention)."""
        from deeplearning4j_tpu.ops.flash_attention import decode_attention

        B, Tc, C = x.shape
        H = self.n_heads
        qkv = x @ params["Wqkv"] + params["bqkv"]
        q, k, v = jnp.split(qkv.reshape(B, Tc, 3 * H, C // H), 3, axis=2)
        cache.append(k, v)
        k_all, v_all = cache.gathered()
        out = decode_attention(q, k_all, v_all, positions)   # [B,Tc,H,D]
        out = out.reshape(B, Tc, C)
        return out @ params["Wo"] + params["bo"]


@register_layer("transformer_block")
@dataclass
class TransformerBlock(LayerConfig):
    """Pre-LN transformer block: x + MHA(LN(x)); x + MLP(LN(x)).

    MLP is a fused [C,4C]→gelu→[4C,C] pair (``ffn_mult`` configurable).
    """

    n_heads: int = 8
    ffn_mult: int = 4
    causal: bool = True
    sequence_parallel: bool = False
    activation: Any = "gelu"
    weight_init: Any = "xavier"
    eps: float = 1e-5
    use_flash: Any = "auto"  # forwarded to the nested MultiHeadAttention

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def _mha(self) -> MultiHeadAttention:
        return MultiHeadAttention(
            n_heads=self.n_heads,
            causal=self.causal,
            sequence_parallel=self.sequence_parallel,
            weight_init=self.weight_init,
            use_flash=self.use_flash,
        )

    def nested_param_layers(self) -> dict:
        return {"attn": self._mha()}

    def init(self, key, input_type, dtype=jnp.float32):
        C = input_type.size
        F = self.ffn_mult * C
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "attn": self._mha().init(k1, input_type, dtype),
            "ln1": {"gamma": jnp.ones((C,), dtype), "beta": jnp.zeros((C,), dtype)},
            "ln2": {"gamma": jnp.ones((C,), dtype), "beta": jnp.zeros((C,), dtype)},
            "Wi": initializers.initialize(self.weight_init, k2, (C, F), C, F, dtype),
            "bi": jnp.zeros((F,), dtype),
            "Wo": initializers.initialize(self.weight_init, k3, (F, C), F, C, dtype),
            "bo": jnp.zeros((C,), dtype),
        }

    def _ln(self, p, x):
        from deeplearning4j_tpu.nn.layers.normalization import layer_norm

        return layer_norm(x, p["gamma"], p["beta"], self.eps)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self._apply_inner(params, x, train, rng, mask), state

    def _apply_inner(self, params, x, train, rng, mask):
        rng_in, rng_attn = (jax.random.split(rng) if rng is not None else (None, None))
        x = self.maybe_dropout_input(x, train, rng_in)
        with jax.named_scope("attn"):
            h = self._ln(params["ln1"], x)
            a, _ = self._mha().apply(params["attn"], {}, h, train=train, rng=rng_attn, mask=mask)
            x = x + a
        with jax.named_scope("mlp"):
            h = self._ln(params["ln2"], x)
            h = self.activation_fn()(h @ params["Wi"] + params["bi"])
            return x + (h @ params["Wo"] + params["bo"])

    def decode_apply(self, params, x, *, cache, positions):
        """The block's eval-mode forward for a new-token chunk against a KV
        cache: identical composition to :meth:`_apply_inner` with the MHA
        swapped for its cache-backed decode path (see
        MultiHeadAttention.decode_apply)."""
        with jax.named_scope("attn"):
            h = self._ln(params["ln1"], x)
            a = self._mha().decode_apply(params["attn"], h, cache=cache,
                                         positions=positions)
            x = x + a
        with jax.named_scope("mlp"):
            h = self._ln(params["ln2"], x)
            h = self.activation_fn()(h @ params["Wi"] + params["bi"])
            return x + (h @ params["Wo"] + params["bo"])


@register_layer("grouped_query_attention")
@dataclass
class GroupedQueryAttention(LayerConfig):
    """Causal self-attention over [B, T, C] with ``n_heads`` query heads of
    ``head_dim`` that share ``n_kv_heads`` key/value heads (each serves
    ``n_heads / n_kv_heads`` query heads), bias-free projections and a head
    width of its own (``n_heads * head_dim`` need not be ``C``).
    ``qk_norm`` puts an RMSNorm over each head's lanes of q and of k (one
    gain vector of ``head_dim`` each, shared by the heads; ``eps``);
    ``rope_theta`` above 0 then turns all lanes of every q and k head by
    their position (``rotary``, ``rope_pairing``). Both are off by default:
    no norm, no positional encoding. The attention core is
    ``MultiHeadAttention``'s (flash kernels on the TPU, the XLA path
    elsewhere); keys and values are repeated to the query heads for it, and
    autodiff sums their gradients back over the repeat."""

    n_heads: int = 8
    n_kv_heads: int = 1
    head_dim: int = 64
    causal: bool = True
    qk_norm: bool = False
    eps: float = 1e-5
    rope_theta: float = 0.0         # 0: no rotary positions
    rope_pairing: str = "half"
    weight_init: Any = "xavier"
    use_flash: Any = "auto"

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def init(self, key, input_type, dtype=jnp.float32):
        C = input_type.size
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"n_kv_heads={self.n_kv_heads} must divide "
                             f"n_heads={self.n_heads}")
        q, kv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        kq, kk, kv_, ko = jax.random.split(key, 4)
        init = lambda k, fi, fo: initializers.initialize(   # noqa: E731
            self.weight_init, k, (fi, fo), fi, fo, dtype)
        p = {"Wq": init(kq, C, q), "Wk": init(kk, C, kv),
             "Wv": init(kv_, C, kv), "Wo": init(ko, q, C)}
        if self.qk_norm:
            p["q_norm"] = jnp.ones((self.head_dim,), dtype)
            p["k_norm"] = jnp.ones((self.head_dim,), dtype)
        return p

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.nn.layers.normalization import rms_norm

        x = self.maybe_dropout_input(x, train, rng)
        B, T, _ = x.shape
        H, Hkv, D = self.n_heads, self.n_kv_heads, self.head_dim
        with jax.named_scope("attn"):
            q = (x @ params["Wq"]).reshape(B, T, H, D)
            k = (x @ params["Wk"]).reshape(B, T, Hkv, D)
            v = (x @ params["Wv"]).reshape(B, T, Hkv, D)
            if self.qk_norm:
                with jax.named_scope("qk_norm"):
                    q = rms_norm(q, params["q_norm"], self.eps)
                    k = rms_norm(k, params["k_norm"], self.eps)
            if self.rope_theta:
                with jax.named_scope("rope"):
                    turn = lambda t: rotary(                     # noqa: E731
                        t.reshape(B, T, -1), jnp.arange(T), width=D,
                        theta=self.rope_theta, pairing=self.rope_pairing
                    ).reshape(t.shape)
                    q, k = turn(q), turn(k)
            k, v = (jnp.repeat(t, H // Hkv, axis=2) for t in (k, v))
            kmask = mask.reshape(B, T) if mask is not None and mask.ndim >= 2 else None
            core = MultiHeadAttention(n_heads=H, causal=self.causal,
                                      use_flash=self.use_flash)
            out = core._attend(q, k, v, kmask)               # [B, T, H, D]
            return out.reshape(B, T, H * D) @ params["Wo"], state


def rotary(x, positions, *, width: int, theta: float = 10000.0,
           pairing: str = "adjacent"):
    """Rotary position embedding over the lanes of ``x`` [..., T, n*width]:
    each run of ``width`` lanes (a head) is rotated pair by pair, pair ``i``
    of position ``t`` by the angle ``t * theta^(-2i/width)``. ``pairing``
    says which lanes make pair ``i``: ``"adjacent"`` the lanes ``(2i, 2i+1)``
    (GPT-J, DeepSeek's ``rope_interleave``), ``"half"`` the lanes ``(i, i +
    width/2)`` (GPT-NeoX's "rotate half"). ``positions`` [T] or [..., T]. A
    partial rotation is the caller's slice: hand over the lanes that turn.

    Written over whole lanes: a lane's partner is a roll (by one lane, or by
    half a head) chosen by a mask, so no ``[.., width/2, 2]`` view with a
    minor dimension of 2 is made."""
    lane, heads = np.arange(width), x.shape[-1] // width
    inv = np.power(float(theta), -np.arange(0, width, 2) / width).astype(
        np.float32)
    if pairing == "adjacent":
        first, pair, step = lane % 2 == 0, lane // 2, 1
    elif pairing == "half":
        first, pair, step = lane < width // 2, lane % (width // 2), width // 2
    else:
        raise ValueError(f"pairing {pairing!r}: 'adjacent' or 'half'")
    # one head's angles [.., T, width], repeated over the heads' lanes
    ang = positions.astype(jnp.float32)[..., None] * inv[pair]
    sign = np.where(first, -1.0, 1.0).astype(np.float32)
    reps = (1,) * (ang.ndim - 1) + (heads,)
    cos, sin = jnp.tile(jnp.cos(ang), reps), jnp.tile(jnp.sin(ang) * sign, reps)
    xf = x.astype(jnp.float32)
    partner = jnp.where(np.tile(first, heads), jnp.roll(xf, -step, axis=-1),
                        jnp.roll(xf, step, axis=-1))
    return (xf * cos + partner * sin).astype(x.dtype)


@register_layer("latent_attention")
@dataclass
class MultiHeadLatentAttention(LayerConfig):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 section
    2.1) over [B, T, C], causal, bias-free; no residual and no pre-norm of
    its own: wrap it in a ``ResidualBlock``.

    Queries and key-values pass through low-rank latents, each with an
    RMSNorm: ``c_q = RMSNorm(x W_dq)`` (``q_rank``), ``[c_kv | k_r] = x W_dkv``
    (``kv_rank`` and one rotary key of ``rope_dim`` a token, shared by every
    head), ``c_kv <- RMSNorm(c_kv)``.
    A head has a key part of ``nope_dim`` without position and a rotary part
    of ``rope_dim``; values are ``v_dim`` wide; scores are ``(q_nope . k_nope
    + q_rope . k_rope) / sqrt(nope_dim + rope_dim)``.

    Parameters as the kernels read their products: ``Wuq_n`` [q_rank,
    H*nope_dim] and ``Wuq_r`` [q_rank, H*rope_dim] are the query
    up-projection's columns by part (a published ``[H, nope | rope]`` matrix
    is split by columns); ``Wukv`` [kv_rank, H*(nope_dim + v_dim)] keeps head
    ``h``'s ``[k_nope | v]`` together, as published. On the TPU the core is
    ops/flash_mla.py's kernels where the widths fill lane blocks
    (``heads_per_program``), else and elsewhere plain XLA over the full
    score square."""

    n_heads: int = 8
    q_rank: int = 96
    kv_rank: int = 64
    nope_dim: int = 32
    rope_dim: int = 16
    v_dim: int = 32
    rope_theta: float = 10000.0
    eps: float = 1e-6
    weight_init: Any = "xavier"
    use_flash: Any = "auto"

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def init(self, key, input_type, dtype=jnp.float32):
        C, H = input_type.size, self.n_heads
        ks = jax.random.split(key, 6)
        init = lambda k, fi, fo: initializers.initialize(   # noqa: E731
            self.weight_init, k, (fi, fo), fi, fo, dtype)
        return {
            "Wdq": init(ks[0], C, self.q_rank),
            "q_norm": jnp.ones((self.q_rank,), dtype),
            "Wuq_n": init(ks[1], self.q_rank, H * self.nope_dim),
            "Wuq_r": init(ks[2], self.q_rank, H * self.rope_dim),
            "Wdkv": init(ks[3], C, self.kv_rank + self.rope_dim),
            "kv_norm": jnp.ones((self.kv_rank,), dtype),
            "Wukv": init(ks[4], self.kv_rank, H * (self.nope_dim + self.v_dim)),
            "Wo": init(ks[5], H * self.v_dim, C)}

    def _core(self):
        """The attention core for the (qn, qr, kv, kr) operands of
        ops/flash_mla.py: the kernels on the TPU where the widths suit them
        and no mesh partitions the step (``use_flash=True``: anywhere, in
        the interpreter), else the XLA form."""
        from deeplearning4j_tpu.ops import flash_mla
        from deeplearning4j_tpu.parallel.context import partitioning_mesh

        kw = dict(n_heads=self.n_heads,
                  scale=1.0 / (self.nope_dim + self.rope_dim) ** 0.5)
        on_tpu = jax.default_backend() == "tpu"
        fits = flash_mla.heads_per_program(
            self.n_heads, self.nope_dim, self.rope_dim, self.v_dim)
        if (fits and partitioning_mesh() is None and (
                self.use_flash is True or (self.use_flash == "auto" and on_tpu))):
            return functools.partial(flash_mla.flash_mla, interpret=not on_tpu,
                                     **kw)
        return functools.partial(flash_mla.mla_attention_xla, **kw)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.nn.layers.normalization import rms_norm

        x = self.maybe_dropout_input(x, train, rng)
        B, T, _ = x.shape
        turn = functools.partial(
            rotary, positions=jnp.arange(T), width=self.rope_dim,
            theta=self.rope_theta)
        with jax.named_scope("mla"):
            with jax.named_scope("q"):
                c_q = rms_norm(x @ params["Wdq"], params["q_norm"], self.eps)
                qn, qr = c_q @ params["Wuq_n"], c_q @ params["Wuq_r"]
            with jax.named_scope("kv"):
                down = x @ params["Wdkv"]
                c_kv = rms_norm(down[..., :self.kv_rank], params["kv_norm"],
                                self.eps)
                kv = c_kv @ params["Wukv"]
            with jax.named_scope("rope"):
                qr, kr = turn(qr), turn(down[..., self.kv_rank:])
            with jax.named_scope("core"):
                kmask = (mask.reshape(B, T)
                         if mask is not None and mask.ndim >= 2 else None)
                o = self._core()(qn, qr, kv, kr, kmask=kmask)
            with jax.named_scope("out"):
                return o @ params["Wo"], state
