"""Flash attention for latent-attention (MLA) layers: scores of two parts.

A DeepSeek-V2/V3 attention layer scores a query against a key in two parts
that add: ``q_nope_h . k_nope_h`` over a per-head width ``Dn`` and
``q_rope_h . k_rope`` over a rotary width ``Dr`` whose key is ONE vector a
token, shared by every head; the values have a width ``Dv`` of their own and
the scale is the caller's (``1 / sqrt(Dn + Dr)``). ops/flash_attention.py
takes one width for q, k and v and would need the rotary key repeated to every
head and the three operands padded to a common width in HBM; these kernels
read what the layer's projections produce, as they produce it:

- ``qn`` [B, T, H*Dn] and ``qr`` [B, T, H*Dr] (rotated): the two query
  projections;
- ``kv`` [B, T, H*(Dn+Dv)]: the one key-value up-projection, head ``h``'s
  ``[k_nope | v]`` in lanes ``h*(Dn+Dv) ..``, read in place;
- ``kr`` [B, T, Dr] (rotated): the shared rotary key, read once a program.

A grid program is (batch row, group of ``G`` heads, block): ``G = 128 // Dr``
heads, so that the rotary queries of a group fill whole 128-lane blocks (two
heads at Dr = 64); the heads of a group run one after another over the same
resident keys, each on its own lanes of the blocks. Same streaming softmax,
same split of the loop into unmasked tiles and the diagonal's masked ones,
same row-layout log-sum-exp and transposed dk/dv tiles as
ops/flash_attention.py, whose helpers these kernels share. The dk/dv kernel
writes the rotary key's gradient a head (``[B, T, H*Dr]``); the sum over heads
is one small XLA reduction outside.

Names: ``mla_flash_fwd``, ``mla_flash_bwd_dq``, ``mla_flash_bwd_dkv``, each
followed by the heads of a group and the blocks that ran
(``mla_flash_fwd_h2_q512_k512``). Off the TPU the layer takes
:func:`mla_attention_xla`; ``interpret=True`` runs the kernels in the Pallas
interpreter (tests).
"""

from __future__ import annotations

import functools
import types
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.flash_attention import (
    ATTN_LSE, ATTN_OUT, _NEG_BIG, _aligned, _and, _delta_rows, _dot_nt,
    _key_ranges, _lane, _pad_km, _pad_rows, _query_ranges, _tiles,
    _vmem_params)
from deeplearning4j_tpu.ops.flash_attention import _plan as _fa_plan

_NAMES = types.MappingProxyType(
    {"fwd": "mla_flash_fwd", "dq": "mla_flash_bwd_dq",
     "dkv": "mla_flash_bwd_dkv"})


class MlaDims(NamedTuple):
    """Heads ``H``; widths of the per-head key part ``Dn``, the rotary part
    ``Dr`` and the values ``Dv``; ``G`` heads a grid program."""
    H: int
    Dn: int
    Dr: int
    Dv: int
    G: int = 1

    @property
    def groups(self) -> int:
        return self.H // self.G

    @property
    def W(self) -> int:
        return self.Dn + self.Dv


def heads_per_program(H: int, Dn: int, Dr: int, Dv: int) -> Optional[int]:
    """Heads a grid program takes, so that every block is whole 128-lane
    blocks: ``128 // Dr`` where that divides H and fills the other operands'
    blocks too, 1 where every width is a multiple of 128; None where neither
    holds (the layer then takes the XLA path)."""
    G = 1 if Dr % 128 == 0 else (128 // Dr if 128 % Dr == 0 else 0)
    if not G or H % G or (G * Dn) % 128 or (G * Dv) % 128:
        return None
    return G


def _working_set(kernel: str, bq: int, bk: int, q_pad: int, k_pad: int,
                 m: MlaDims, item: int, has_kmask: bool) -> int:
    """Bytes of VMEM one grid program holds, counted as
    ops/flash_attention.py ``_working_set`` counts them."""
    row = lambda n: 8 * _lane(n) * 4                              # noqa: E731
    mat = lambda n, w: n * _lane(w) * item                        # noqa: E731
    G = m.G
    tile = _lane(bq) * _lane(bk) * 4
    if kernel == "fwd":
        io = (mat(bq, G * m.Dn) + mat(bq, G * m.Dr) + mat(k_pad, G * m.W)
              + mat(k_pad, m.Dr) + mat(bq, G * m.Dv) + G * row(bq))
        live = 4 * tile + bq * _lane(m.Dv) * 4 + 2 * bq * 128 * 4
    elif kernel == "dq":
        io = (2 * mat(bq, G * m.Dn) + 2 * mat(bq, G * m.Dr)
              + mat(k_pad, G * m.W) + mat(k_pad, m.Dr) + mat(bq, G * m.Dv)
              + 2 * G * row(bq))
        live = 6 * tile + bq * (_lane(m.Dn) + _lane(m.Dr)) * 4
    elif kernel == "dkv":
        io = (mat(q_pad, G * m.Dn) + mat(q_pad, G * m.Dr)
              + mat(q_pad, G * m.Dv) + 2 * G * row(q_pad)
              + 2 * mat(bk, G * m.W) + mat(bk, m.Dr) + mat(bk, G * m.Dr))
        live = 6 * tile + bk * (_lane(m.Dn) + _lane(m.Dr) + _lane(m.Dv)) * 4
    else:
        raise ValueError(f"unknown flash kernel {kernel!r}")
    if has_kmask:
        io += row(k_pad if kernel != "dkv" else bk)
    return 2 * io + live


def _plan(kernels, T, m, item, has_kmask, block_q, block_k):
    """``({kernel: (bq, bk)}, t_pad)`` by ops/flash_attention.py's rule
    (``choose_blocks`` there) over this module's working set."""
    blocks, t_pad, _ = _fa_plan(
        kernels, T, T, None, item, has_kmask, block_q, block_k, same_len=True,
        working_set=lambda kn, bq, bk, q_pad, k_pad: _working_set(
            kn, bq, bk, q_pad, k_pad, m, item, has_kmask))
    return blocks, t_pad


def _call_kw(kernel, interpret, m, bq, bk, t_pad, dtype, has_kmask):
    kw = {"interpret": interpret,
          "name": f"{_NAMES[kernel]}_h{m.G}_q{bq}_k{bk}"}
    if not interpret:
        kw.update(_vmem_params(_working_set(
            kernel, bq, bk, t_pad, t_pad, m, jnp.dtype(dtype).itemsize,
            has_kmask)))
    return kw


def _valid(masked, causal, t_pad, t_real, q_pos, km_ref, start, bk):
    """The [bq, bk] (or broadcastable) validity of a forward/dq score tile
    whose keys start at ``start``, None where every entry counts."""
    valid = None
    if masked:
        k_pos = start + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        if t_pad != t_real:
            valid = k_pos < t_real
        if causal:
            valid = _and(valid, k_pos <= q_pos)
    if km_ref is not None:
        valid = _and(valid, km_ref[0, :, pl.ds(start, bk)] > 0)
    return valid


def _fwd_kernel(qn_ref, qr_ref, kv_ref, kr_ref, *rest, m: MlaDims,
                block_q: int, block_k: int, t_real: int, t_pad: int,
                causal: bool, scale: float, has_kmask: bool):
    """One q-block of ``G`` heads against all key blocks. Refs: qn
    [1, bq, G*Dn]; qr [1, bq, G*Dr]; kv [1, t_pad, G*(Dn+Dv)]; kr
    [1, t_pad, Dr]; optional kmask [1, 1, t_pad]; o [1, bq, G*Dv]; lse
    [G, 1, bq] (row layout, as ops/flash_attention.py stores it)."""
    if has_kmask:
        km_ref, o_ref, lse_ref = rest
    else:
        (o_ref, lse_ref), km_ref = rest, None
    qi = 0 if t_pad == block_q else pl.program_id(2)
    q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    Dn, Dr, Dv, W = m.Dn, m.Dr, m.Dv, m.W
    m0 = jnp.full((block_q, 1), _NEG_BIG, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, Dv), jnp.float32)
    for h in range(m.G):
        qn = qn_ref[0, :, h * Dn:(h + 1) * Dn]
        qr = qr_ref[0, :, h * Dr:(h + 1) * Dr]

        def body(kb, carry, masked, h=h, qn=qn, qr=qr):
            mx, l, acc = carry
            start = _aligned(kb, block_k)
            kn = kv_ref[0, pl.ds(start, block_k), h * W:h * W + Dn]
            v = kv_ref[0, pl.ds(start, block_k), h * W + Dn:(h + 1) * W]
            kr = kr_ref[0, pl.ds(start, block_k), :]
            s = (_dot_nt(qn, kn) + _dot_nt(qr, kr)) * scale      # [bq, bk]
            valid = _valid(masked, causal, t_pad, t_real, q_pos,
                           km_ref, start, block_k)
            if valid is not None:
                s = jnp.where(valid, s, _NEG_BIG)
            m_new = jnp.maximum(mx, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(mx - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                        preferred_element_type=jnp.float32)
            return m_new, l, acc

        mx, l, acc = _tiles(
            _key_ranges(qi, block_q, block_k, t_pad, t_pad, t_real, causal,
                        True), body, (m0, l0, acc0))
        o_ref[0, :, h * Dv:(h + 1) * Dv] = (
            acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse_ref[h] = (mx + jnp.log(jnp.maximum(l, 1e-30))).reshape(1, block_q)


def _dq_kernel(qn_ref, qr_ref, kv_ref, kr_ref, do_ref, lse_ref, delta_ref,
               *rest, m: MlaDims, block_q: int, block_k: int, t_real: int,
               t_pad: int, causal: bool, scale: float, has_kmask: bool):
    """dq of both parts for one q-block of ``G`` heads: ``ds = p * (do v^T -
    delta)``, ``dqn = scale * ds kn``, ``dqr = scale * ds kr``."""
    if has_kmask:
        km_ref, dqn_ref, dqr_ref = rest
    else:
        (dqn_ref, dqr_ref), km_ref = rest, None
    qi = 0 if t_pad == block_q else pl.program_id(2)
    q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    Dn, Dr, Dv, W = m.Dn, m.Dr, m.Dv, m.W
    for h in range(m.G):
        qn = qn_ref[0, :, h * Dn:(h + 1) * Dn]
        qr = qr_ref[0, :, h * Dr:(h + 1) * Dr]
        do = do_ref[0, :, h * Dv:(h + 1) * Dv]
        lse = lse_ref[h].reshape(block_q, 1)
        delta = delta_ref[h].reshape(block_q, 1)

        def body(kb, carry, masked, h=h, qn=qn, qr=qr, do=do, lse=lse,
                 delta=delta):
            dqn, dqr = carry
            start = _aligned(kb, block_k)
            kn = kv_ref[0, pl.ds(start, block_k), h * W:h * W + Dn]
            v = kv_ref[0, pl.ds(start, block_k), h * W + Dn:(h + 1) * W]
            kr = kr_ref[0, pl.ds(start, block_k), :]
            p = jnp.exp((_dot_nt(qn, kn) + _dot_nt(qr, kr)) * scale - lse)
            valid = _valid(masked, causal, t_pad, t_real, q_pos,
                           km_ref, start, block_k)
            if valid is not None:
                p = jnp.where(valid, p, 0.0)
            ds = (p * (_dot_nt(do, v) - delta)).astype(kn.dtype)
            return (dqn + jnp.dot(ds, kn, preferred_element_type=jnp.float32),
                    dqr + jnp.dot(ds, kr, preferred_element_type=jnp.float32))

        dqn, dqr = _tiles(
            _key_ranges(qi, block_q, block_k, t_pad, t_pad, t_real, causal,
                        True), body,
            (jnp.zeros((block_q, Dn), jnp.float32),
             jnp.zeros((block_q, Dr), jnp.float32)))
        dqn_ref[0, :, h * Dn:(h + 1) * Dn] = (dqn * scale).astype(dqn_ref.dtype)
        dqr_ref[0, :, h * Dr:(h + 1) * Dr] = (dqr * scale).astype(dqr_ref.dtype)


def _dkv_kernel(qn_ref, qr_ref, kv_ref, kr_ref, do_ref, lse_ref, delta_ref,
                *rest, m: MlaDims, block_q: int, block_k: int, t_real: int,
                t_pad: int, causal: bool, scale: float, has_kmask: bool):
    """dk (both parts) and dv for one k-block of ``G`` heads, looping over
    q-blocks with the tiles transposed (keys down the sublanes), as
    ops/flash_attention.py ``_bwd_dkv_kernel``. The rotary key's gradient is
    written a head: the caller sums over heads."""
    if has_kmask:
        km_ref, dkv_ref, dkr_ref = rest
    else:
        (dkv_ref, dkr_ref), km_ref = rest, None
    ki = 0 if t_pad == block_k else pl.program_id(2)
    k_loc = ki * block_k + lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
    k_valid = (k_loc < t_real) if t_pad != t_real else None
    if km_ref is not None:
        k_valid = _and(k_valid, km_ref[0].reshape(block_k, 1) > 0)
    Dn, Dr, Dv, W = m.Dn, m.Dr, m.Dv, m.W
    kr = kr_ref[0]                                               # [bk, Dr]
    for h in range(m.G):
        kn = kv_ref[0, :, h * W:h * W + Dn]
        v = kv_ref[0, :, h * W + Dn:(h + 1) * W]

        def body(qb, carry, diag, tail, h=h, kn=kn, v=v):
            dkn, dkr, dv = carry
            start = _aligned(qb, block_q)
            qn = qn_ref[0, pl.ds(start, block_q), h * Dn:(h + 1) * Dn]
            qr = qr_ref[0, pl.ds(start, block_q), h * Dr:(h + 1) * Dr]
            do = do_ref[0, pl.ds(start, block_q), h * Dv:(h + 1) * Dv]
            lse = lse_ref[h, :, pl.ds(start, block_q)]           # [1, bq]
            delta = delta_ref[h, :, pl.ds(start, block_q)]
            pt = jnp.exp((_dot_nt(kn, qn) + _dot_nt(kr, qr)) * scale - lse)
            valid = k_valid
            if diag or tail:
                q_loc = start + lax.broadcasted_iota(
                    jnp.int32, (1, block_q), 1)
                if tail:
                    valid = _and(valid, q_loc < t_real)
                if diag:
                    valid = _and(valid, k_loc <= q_loc)
            if valid is not None:
                pt = jnp.where(valid, pt, 0.0)
            dv = dv + jnp.dot(pt.astype(do.dtype), do,
                              preferred_element_type=jnp.float32)
            dst = (pt * (_dot_nt(v, do) - delta)).astype(qn.dtype)
            return (dkn + jnp.dot(dst, qn, preferred_element_type=jnp.float32),
                    dkr + jnp.dot(dst, qr, preferred_element_type=jnp.float32),
                    dv)

        dkn, dkr, dv = _tiles(
            _query_ranges(ki, block_q, block_k, t_pad, t_pad, t_real, causal,
                          True), body,
            (jnp.zeros((block_k, Dn), jnp.float32),
             jnp.zeros((block_k, Dr), jnp.float32),
             jnp.zeros((block_k, Dv), jnp.float32)))
        dkv_ref[0, :, h * W:h * W + Dn] = (dkn * scale).astype(dkv_ref.dtype)
        dkv_ref[0, :, h * W + Dn:(h + 1) * W] = dv.astype(dkv_ref.dtype)
        dkr_ref[0, :, h * Dr:(h + 1) * Dr] = (dkr * scale).astype(dkr_ref.dtype)


def _specs(m: MlaDims, interpret: bool):
    """BlockSpec makers over the grid (batch row n, head group g, block i)."""
    kw = {} if interpret else {"memory_space": pltpu.VMEM}

    def lanes(rows, width, whole=False):
        """A group's ``width`` lanes of a [B, t_pad, groups*width] array."""
        return pl.BlockSpec(
            (1, rows, width),
            (lambda n, g, i: (n, 0, g)) if whole else
            (lambda n, g, i: (n, i, g)), **kw)

    def shared(rows, width, whole=False):
        """The rotary key [B, t_pad, Dr]: one for every group."""
        return pl.BlockSpec(
            (1, rows, width),
            (lambda n, g, i: (n, 0, 0)) if whole else
            (lambda n, g, i: (n, i, 0)), **kw)

    def rows(n_cols, whole=False):
        return pl.BlockSpec(
            (m.G, 1, n_cols),
            (lambda n, g, i: (n * m.groups + g, 0, 0)) if whole else
            (lambda n, g, i: (n * m.groups + g, 0, i)), **kw)

    def mask(n_cols, whole=False):
        return pl.BlockSpec(
            (1, 1, n_cols),
            (lambda n, g, i: (n, 0, 0)) if whole else
            (lambda n, g, i: (n, 0, i)), **kw)

    return lanes, shared, rows, mask


_STATIC = ("m", "t_pad", "t_real", "causal", "scale", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC + ("bq", "bk"))
def _fwd_call(qn, qr, kv, kr, *, m: MlaDims, bq, bk, t_pad, t_real, causal,
              scale, interpret, kmask=None):
    """Padded operands -> (o [B, t_pad, H*Dv], lse [B*H, 1, t_pad])."""
    B, dtype = qn.shape[0], qn.dtype
    lanes, shared, rows, mask = _specs(m, interpret)
    G, has_km = m.G, kmask is not None
    in_specs = [lanes(bq, G * m.Dn), lanes(bq, G * m.Dr),
                lanes(t_pad, G * m.W, True), shared(t_pad, m.Dr, True)]
    args = [qn, qr, kv, kr]
    if has_km:
        in_specs.append(mask(t_pad, True))
        args.append(kmask)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, m=m, block_q=bq, block_k=bk,
                          t_real=t_real, t_pad=t_pad, causal=causal,
                          scale=scale, has_kmask=has_km),
        grid=(B, m.groups, t_pad // bq),
        in_specs=in_specs,
        out_specs=[lanes(bq, G * m.Dv), rows(bq)],
        out_shape=[jax.ShapeDtypeStruct((B, t_pad, m.H * m.Dv), dtype),
                   jax.ShapeDtypeStruct((B * m.H, 1, t_pad), jnp.float32)],
        **_call_kw("fwd", interpret, m, bq, bk, t_pad, dtype, has_km),
    )(*args)


@functools.partial(jax.jit, static_argnames=_STATIC + ("dq_blocks",
                                                       "dkv_blocks"))
def _bwd_calls(qn, qr, kv, kr, do, lse, delta, *, m: MlaDims, dq_blocks,
               dkv_blocks, t_pad, t_real, causal, scale, interpret,
               kmask=None):
    """The two backward calls over padded operands -> (dqn, dqr, dkv, the
    rotary key's gradient a head [B, t_pad, H*Dr])."""
    B, dtype = qn.shape[0], qn.dtype
    lanes, shared, rows, mask = _specs(m, interpret)
    G, has_km = m.G, kmask is not None
    common = dict(m=m, t_real=t_real, t_pad=t_pad, causal=causal, scale=scale,
                  has_kmask=has_km)
    args = [qn, qr, kv, kr, do, lse, delta] + ([kmask] if has_km else [])
    like = lambda x: jax.ShapeDtypeStruct(x.shape, dtype)        # noqa: E731

    bq, bk = dq_blocks
    dqn, dqr = pl.pallas_call(
        functools.partial(_dq_kernel, block_q=bq, block_k=bk, **common),
        grid=(B, m.groups, t_pad // bq),
        in_specs=[lanes(bq, G * m.Dn), lanes(bq, G * m.Dr),
                  lanes(t_pad, G * m.W, True), shared(t_pad, m.Dr, True),
                  lanes(bq, G * m.Dv), rows(bq), rows(bq)]
        + ([mask(t_pad, True)] if has_km else []),
        out_specs=[lanes(bq, G * m.Dn), lanes(bq, G * m.Dr)],
        out_shape=[like(qn), like(qr)],
        **_call_kw("dq", interpret, m, bq, bk, t_pad, dtype, has_km),
    )(*args)

    bq, bk = dkv_blocks
    dkv, dkr = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=bq, block_k=bk, **common),
        grid=(B, m.groups, t_pad // bk),
        in_specs=[lanes(t_pad, G * m.Dn, True), lanes(t_pad, G * m.Dr, True),
                  lanes(bk, G * m.W), shared(bk, m.Dr),
                  lanes(t_pad, G * m.Dv, True), rows(t_pad, True),
                  rows(t_pad, True)] + ([mask(bk)] if has_km else []),
        out_specs=[lanes(bk, G * m.W), lanes(bk, G * m.Dr)],
        out_shape=[like(kv), like(qr)],
        **_call_kw("dkv", interpret, m, bq, bk, t_pad, dtype, has_km),
    )(*args)
    return dqn, dqr, dkv, dkr


def _fwd(qn, qr, kv, kr, kmask, m, causal, scale, block_q, block_k, interpret):
    T = qn.shape[1]
    blocks, t_pad = _plan(("fwd",), T, m, qn.dtype.itemsize,
                          kmask is not None, block_q, block_k)
    bq, bk = blocks["fwd"]
    km = _pad_km(kmask, t_pad) if kmask is not None else None
    o, lse = _fwd_call(*(_pad_rows(x, t_pad) for x in (qn, qr, kv, kr)),
                       m=m, bq=bq, bk=bk, t_pad=t_pad, t_real=T,
                       causal=causal, scale=scale, interpret=interpret,
                       kmask=km)
    return o[:, :T], lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_mla(qn, qr, kv, kr, kmask, m, causal, scale, block_q, block_k,
               interpret):
    return _fwd(qn, qr, kv, kr, kmask, m, causal, scale, block_q, block_k,
                interpret)[0]


def _flash_mla_fwd(qn, qr, kv, kr, kmask, m, causal, scale, block_q, block_k,
                   interpret):
    o, lse = _fwd(qn, qr, kv, kr, kmask, m, causal, scale, block_q, block_k,
                  interpret)
    o, lse = checkpoint_name(o, ATTN_OUT), checkpoint_name(lse, ATTN_LSE)
    return o, (qn, qr, kv, kr, kmask, o, lse)


def _flash_mla_bwd(m, causal, scale, block_q, block_k, interpret, res, g):
    qn, qr, kv, kr, kmask, o, lse = res
    B, T, _ = qn.shape
    blocks, t_pad = _plan(("dq", "dkv"), T, m, qn.dtype.itemsize,
                          kmask is not None, block_q, block_k)
    km = _pad_km(kmask, t_pad) if kmask is not None else None
    dqn, dqr, dkv, dkr = _bwd_calls(
        *(_pad_rows(x, t_pad) for x in (qn, qr, kv, kr, g)), lse,
        _delta_rows(g, o, (B, T, m.H, m.Dv), t_pad), m=m,
        dq_blocks=blocks["dq"], dkv_blocks=blocks["dkv"], t_pad=t_pad,
        t_real=T, causal=causal, scale=scale, interpret=interpret, kmask=km)
    dkr = jnp.sum(dkr[:, :T].reshape(B, T, m.H, m.Dr).astype(jnp.float32),
                  axis=2).astype(kr.dtype)
    dkm = jnp.zeros_like(kmask) if kmask is not None else None
    return dqn[:, :T], dqr[:, :T], dkv[:, :T], dkr, dkm


_flash_mla.defvjp(_flash_mla_fwd, _flash_mla_bwd)


def flash_mla(qn, qr, kv, kr, *, n_heads: int, scale: float, kmask=None,
              causal: bool = True, block_q: Optional[int] = None,
              block_k: Optional[int] = None, interpret: bool = False):
    """Attention of two-part scores, differentiable, blockwise in both
    directions: ``qn`` [B, T, H*Dn], ``qr`` [B, T, H*Dr] and ``kr``
    [B, T, Dr] already rotated, ``kv`` [B, T, H*(Dn+Dv)] with head ``h``'s
    ``[k_nope | v]`` together -> [B, T, H*Dv]. ``kmask`` [B, T]: key validity.
    The widths have to suit :func:`heads_per_program`."""
    B, T, _ = qn.shape
    H, Dr = n_heads, kr.shape[-1]
    Dn = qn.shape[-1] // H
    Dv = kv.shape[-1] // H - Dn
    G = heads_per_program(H, Dn, Dr, Dv)
    if G is None or qr.shape[-1] != H * Dr:
        raise ValueError(f"no lane blocks for H={H}, Dn={Dn}, Dr={Dr}, "
                         f"Dv={Dv}: use mla_attention_xla")
    km = None if kmask is None else jnp.asarray(kmask, jnp.float32)
    return _flash_mla(qn, qr, kv, kr, km, MlaDims(H, Dn, Dr, Dv, G), causal,
                      float(scale), block_q, block_k, interpret)


def mla_attention_xla(qn, qr, kv, kr, *, n_heads: int, scale: float,
                      kmask=None, causal: bool = True):
    """The same attention as plain XLA ops, the full score square in memory:
    the path off the TPU and the tests' oracle."""
    B, T, _ = qn.shape
    H, Dr = n_heads, kr.shape[-1]
    Dn = qn.shape[-1] // H
    kv = kv.reshape(B, T, H, -1)
    f32 = lambda x: x.astype(jnp.float32)                        # noqa: E731
    s = (jnp.einsum("bqhd,bkhd->bhqk", f32(qn.reshape(B, T, H, Dn)),
                    f32(kv[..., :Dn]))
         + jnp.einsum("bqhd,bkd->bhqk", f32(qr.reshape(B, T, H, Dr)),
                      f32(kr))) * scale
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, _NEG_BIG)
    if kmask is not None:
        s = jnp.where(kmask[:, None, None, :] > 0, s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, f32(kv[..., Dn:]))
    return o.reshape(B, T, -1).astype(qn.dtype)
