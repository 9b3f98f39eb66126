"""Ring attention: sequence/context parallelism over the ``seq`` mesh axis.

Long-context capability (first-class in this framework; the reference tops
out at truncated BPTT — SURVEY.md §5.7). Each device holds a block of the
sequence; K/V blocks rotate around the ring via ``ppermute`` over ICI while
every device accumulates its queries' attention online (numerically-stable
streaming softmax, the FlashAttention/RingAttention recurrence). Peak memory
per chip is O(T/seq · T/seq) instead of O(T²), and the K/V transfer for step
i+1 overlaps with the compute of step i (XLA schedules the ppermute DMA
concurrently with the einsums).

Composition: the per-shard kernel `_ring_attention_shard` runs inside
``shard_map``; `ring_self_attention` wraps it for direct use under a mesh
with dp on "data" and sp on "seq".
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

_NEG_BIG = -1e30


def _block_attend(q, k, v, scale, q_off, k_off, causal, m, l, acc, kmask=None):
    """One block of the streaming-softmax recurrence.

    q: [B,Tq,H,D] local queries; k/v: [B,Tk,H,D] current ring block.
    m/l/acc: running max [B,H,Tq], normalizer [B,H,Tq], output [B,Tq,H,D].
    kmask: [B,Tk] key validity (1=real, 0=padding) for this block.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        Tq, Tk = q.shape[1], k.shape[1]
        qpos = q_off + jnp.arange(Tq)
        kpos = k_off + jnp.arange(Tk)
        s = jnp.where(kpos[None, None, None, :] > qpos[None, None, :, None], -jnp.inf, s)
    if kmask is not None:
        s = jnp.where(kmask[:, None, None, :] > 0, s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    m_new = jnp.maximum(m_new, _NEG_BIG)  # keep finite when a block is fully masked
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    acc_new = acc * jnp.transpose(corr, (0, 2, 1))[..., None] + pv
    return m_new, l_new, acc_new


def _ring_attention_shard(q, k, v, kmask, *, axis_name: str, causal: bool):
    """Ring attention on per-device shards [B, T_local, H, D] (call inside
    shard_map with the sequence sharded over ``axis_name``). ``kmask`` is the
    per-shard key-validity mask [B, T_local] (or None)."""
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    scale = 1.0 / (D**0.5)
    # Accumulate in f32 even for bf16 activations: l sums thousands of exp
    # terms and acc is rescaled every ring step — bf16 compounds ~1e-2 error.
    out_dtype = q.dtype
    acc_dtype = jnp.float32 if q.dtype == jnp.bfloat16 else q.dtype
    m = jnp.full((B, H, Tq), _NEG_BIG, acc_dtype)
    l = jnp.zeros((B, H, Tq), acc_dtype)
    acc = jnp.zeros(q.shape, acc_dtype)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    q_off = my_idx * Tq

    def step(i, carry):
        k_cur, v_cur, km_cur, m, l, acc = carry
        src = (my_idx - i) % axis_size  # which rank's block we now hold
        m, l, acc = _block_attend(
            q, k_cur, v_cur, scale, q_off, src * Tq, causal, m, l, acc, km_cur
        )
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        km_nxt = lax.ppermute(km_cur, axis_name, perm) if km_cur is not None else None
        return k_nxt, v_nxt, km_nxt, m, l, acc

    # Static Python loop: axis_size is known at trace time, blocks stay
    # unrolled so XLA overlaps each step's ppermute with the next einsum.
    carry = (k, v, kmask, m, l, acc)
    for i in range(axis_size):
        carry = step(i, carry)
    _, _, _, m, l, acc = carry
    l = jnp.maximum(l, 1e-20)
    return (acc / jnp.transpose(l, (0, 2, 1))[..., None]).astype(out_dtype)


def _ring_flash_shard(q, k, v, kmask=None, *, axis_name: str, causal: bool,
                      interpret: bool):
    """Flash-backed ring attention shard (round 4): each arriving k/v block
    is attended with the Pallas chunked kernel and the partials merge by
    the streaming-softmax identity — fully differentiable (the blocks'
    custom VJP carries the lse cotangent), and no [Tq, Tk] score tensor
    ever exists.

    The causal structure needs NO absolute positions: the diagonal block is
    always ring step 0 (k is each shard's OWN block before any permute), so
    step 0 runs the local causal kernel; every later step is either fully
    allowed (source shard strictly before ours) or fully masked — a traced
    where() on the block's lse (weight -> 0) handles that, keeping block
    offsets static. ``kmask`` [B, T_local]: this shard's key validity; it
    rotates around the ring with its k/v block and feeds the chunk kernel's
    per-key-block mask (round 5)."""
    from deeplearning4j_tpu.ops.flash_attention import (
        flash_attention_block_grad, merge_attention_blocks)

    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    parts = []
    kc, vc, kmc = k, v, kmask
    for i in range(axis_size):          # static unroll, like the XLA ring
        o_i, lse_i = flash_attention_block_grad(
            q, kc, vc, kmask=kmc, causal=(causal and i == 0),
            interpret=interpret)
        if causal and i > 0:
            src = (my_idx - i) % axis_size       # which shard's block this is
            allowed = src < my_idx               # strictly-past blocks only
            lse_i = jnp.where(allowed, lse_i, _NEG_BIG)
        parts.append((o_i, lse_i))
        if i + 1 < axis_size:
            kc = lax.ppermute(kc, axis_name, perm)
            vc = lax.ppermute(vc, axis_name, perm)
            if kmc is not None:
                kmc = lax.ppermute(kmc, axis_name, perm)
    return merge_attention_blocks(parts)


def local_attention(q, k, v, *, causal: bool = False, kmask=None):
    """Single-device reference attention, same layout [B,T,H,D].
    ``kmask`` [B,T]: 1=real key, 0=padding (excluded from attention)."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        T = q.shape[1]
        msk = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(msk[None, None], s, -jnp.inf)
    if kmask is not None:
        s = jnp.where(kmask[:, None, None, :] > 0, s, -jnp.inf)
    # guard fully-masked rows (all -inf) against NaN softmax
    s = jnp.maximum(s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def ring_self_attention(
    q,
    k,
    v,
    mesh: Mesh,
    *,
    causal: bool = False,
    kmask=None,
    data_axis: Optional[str] = "data",
    seq_axis: str = "seq",
    head_axis: Optional[str] = None,
    use_flash: bool = False,
):
    """shard_map-wrapped ring attention: batch over ``data_axis``, sequence
    blocks over ``seq_axis``. Pass ``head_axis="model"`` when q/k/v are
    head-sharded by tensor parallelism (column-parallel Wqkv) so the kernel
    runs on local heads instead of forcing an all-gather over the model axis.
    ``use_flash=True`` runs each ring block through the Pallas chunked
    kernel with exact streaming-softmax merging — no per-block score
    tensor, fully differentiable; a kmask rides the ring alongside its
    k/v block. Inputs/outputs [B, T, H, D] global arrays; kmask [B, T]
    or None."""
    spec = P(data_axis, seq_axis, head_axis, None)
    mspec = P(data_axis, seq_axis)
    if use_flash:
        fn_flash = functools.partial(
            _ring_flash_shard, axis_name=seq_axis, causal=causal,
            interpret=jax.default_backend() != "tpu")
        in_specs = (spec, spec, spec) if kmask is None else (spec, spec, spec, mspec)
        args = (q, k, v) if kmask is None else (q, k, v, kmask)
        # pallas_call outputs carry no vma annotation; disable the
        # shard_map varying-axes check for this (correct) spec
        return shard_map(fn_flash, mesh=mesh, in_specs=in_specs,
                         out_specs=spec, check_vma=False)(*args)
    fn = functools.partial(_ring_attention_shard, axis_name=seq_axis, causal=causal)
    if kmask is None:
        def fn_nomask(q, k, v):
            return fn(q, k, v, None)

        return shard_map(fn_nomask, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)(q, k, v)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec, mspec), out_specs=spec)(
        q, k, v, kmask
    )
