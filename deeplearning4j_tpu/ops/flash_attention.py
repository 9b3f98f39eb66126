"""Flash attention — Pallas TPU kernel with online (streaming) softmax.

The XLA attention path (`parallel/ring.py local_attention`) materialises
the [B, H, T, T] score matrix in HBM; at long T that traffic dominates
(the framework's ResNet-style roofline analysis, docs/PERF.md, shows HBM
bandwidth is the binding resource on this chip). This kernel computes
attention blockwise in VMEM — scores never leave the chip — using the
standard streaming-softmax recurrence (running max m, normaliser l,
rescaled accumulator), one (batch*head, q-block) program per grid cell
looping over key blocks.

Tiling: each of the three kernels picks its blocks from the shapes of the
call (``choose_blocks``: a sequence of up to 1024 rows is one grid block,
a longer one is cut into the largest multiples of 128, up to 512, that
divide its padded length), and its loop is split by what a tile needs:
tiles wholly on the valid side of the causal diagonal run in a loop whose
body has no iota, compare or select in it; the one or two tiles the diagonal
crosses, and the last one of a padded length, run the masked body as
straight-line code after it. The blocks that ran are part of each kernel's
name (``flash_fwd_q1024_k512``), so a profiler trace says which tiling it
shows.

Beyond-reference scope: the reference (DL4J 0.9.2) has no attention layer
at all (SURVEY.md §5.7); this accelerates the framework's TransformerLM
extension. Training uses a custom VJP whose backward is ALSO blockwise
Pallas (FlashAttention-2 style): the forward emits a per-row logsumexp
residual, the dq kernel grids over q-blocks and the dk/dv kernel over
k-blocks, each rebuilding p = exp(s - lse) in VMEM — no [T, T] tensor in
either direction. A rematerialising XLA backward (``bwd="xla"``) remains
as the correctness oracle and fallback.

CPU/tests: ``interpret=True`` runs the identical kernel in the Pallas
interpreter; the layer's default ("auto") uses the kernel only on TPU and
falls back to the XLA path elsewhere. Key-validity masks (padded batches)
run IN the kernel: a [B, T] kmask contributes one [1, block_k] row load
per key block, ANDed into the causal/length validity mask (round 5).
Attention dropout is applied to the attention OUTPUT (not the probability
matrix) in both paths — see MultiHeadAttention.apply in
nn/layers/attention.py — so dropout is flash-compatible and does not gate
the kernel.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -1e30
_NT = (((1,), (1,)), ((), ()))      # a @ b.T without a materialised transpose

# Mosaic gives a kernel 16 MiB of VMEM unless told otherwise. A call whose
# estimated working set stays under _VMEM_SHARE of that says nothing; one
# above it (long T: the whole-sequence operands) asks for the estimate with
# _VMEM_HEADROOM, up to _VMEM_MAX of the v5e's 128 MiB.
_VMEM_DEFAULT = 16 << 20
_VMEM_SHARE = 0.75
_VMEM_HEADROOM = 1.25
_VMEM_MAX = 100 << 20
_NAMES = {"fwd": "flash_fwd", "dq": "flash_bwd_dq", "dkv": "flash_bwd_dkv"}
_STRAIGHT = 2       # tiles of a statically known count emitted without a loop


def _cdiv(a, b):
    return -(-a // b)


def _min(a, b):
    """min that stays a Python int when both are (a loop bound that can be
    static should be)."""
    if isinstance(a, int) and isinstance(b, int):
        return min(a, b)
    return jnp.minimum(a, b)


def _and(a, b):
    return b if a is None else jnp.logical_and(a, b)


def _dot_nt(a, b):
    """a @ b.T, float32 result, operands in the type they arrive in."""
    return lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)


def _aligned(i, block):
    """The first row of tile ``i``, with the alignment its slice may use."""
    return i * block if isinstance(i, int) else pl.multiple_of(i * block, block)


def _tiles(ranges, body, carry):
    """Run ``body(i, carry, **kw)`` over ``count`` tiles from ``lo`` for each
    ``(lo, count, kw)`` in turn, so that a tile which needs no mask runs a
    body with none in it. A count known at trace time and no more than
    ``_STRAIGHT`` is emitted as straight-line code, anything else as a
    ``fori_loop``: the diagonal's one or two tiles scheduled together with
    what follows them, rather than as a second loop, is where the split pays
    (on the v5e a second dynamic loop cost more than the masks it saved,
    PERF.md section 5)."""
    for lo, count, kw in ranges:
        if isinstance(count, int) and count <= _STRAIGHT:
            for j in range(count):
                carry = body(lo + j, carry, **kw)
        else:
            carry = lax.fori_loop(lo, lo + count,
                                  functools.partial(body, **kw), carry)
    return carry


def _nested(block_q, block_k):
    return block_q % block_k == 0 or block_k % block_q == 0


def _key_ranges(qi, block_q, block_k, q_pad, k_pad, t_real_k, causal,
                aligned):
    """``(lo, count, {"masked": ...})`` over the key tiles one q-block visits
    (forward and dq). The masked body compares lengths only when the keys
    are padded and positions only when causal.

    Not causal: every tile before the first padded key is plain. Causal at
    equal offsets (``aligned``): tile kb is wholly valid iff its last column
    (kb+1)*bk - 1 <= the block's first row qi*bq, and contributes nothing
    iff its first column kb*bk > the block's last row (qi+1)*bq - 1; when
    one block divides the other and nothing is padded, the tiles the
    diagonal crosses are max(1, bq/bk) in number whatever qi is, and they
    follow a loop over the plain ones; otherwise one masked loop visits
    every tile up to the diagonal. Causal at unequal offsets (ring and
    chunked blocks): every tile is masked."""
    n_kb = k_pad // block_k
    padded = k_pad != t_real_k
    plain, masked = {"masked": False}, {"masked": True}
    if not causal:
        n_plain = t_real_k // block_k if padded else n_kb
        return [(0, n_plain, plain), (n_plain, n_kb - n_plain, masked)]
    if not aligned:
        return [(0, n_kb, masked)]
    if n_kb == 1:                   # whatever qi is: keep the count static
        return [(0, 1, masked)]
    if _nested(block_q, block_k) and not padded and q_pad <= k_pad:
        n_plain = (qi * block_q + 1) // block_k
        return [(0, n_plain, plain),
                (n_plain, max(1, block_q // block_k), masked)]
    return [(0, _min(n_kb, _cdiv((qi + 1) * block_q, block_k)), masked)]


def _query_ranges(ki, block_q, block_k, q_pad, k_pad, t_real_q, causal,
                  aligned):
    """``(lo, count, {"diag": ..., "tail": ...})`` over the q tiles one
    k-block visits (dk/dv): ``diag`` tiles compare positions, ``tail`` tiles
    hold zero-padded q rows and compare lengths. Causal at equal offsets:
    q tiles strictly above this k-block's first column (qb < ki*bk // bq)
    see none of it, and tile qb is wholly valid iff its first row qb*bq >=
    the block's last column (ki+1)*bk - 1; the same cases as
    :func:`_key_ranges`, with the diagonal's tiles first."""
    n_qb = q_pad // block_q
    padded = q_pad != t_real_q
    plain = {"diag": False, "tail": False}
    if not causal:
        n_plain = t_real_q // block_q if padded else n_qb
        return [(0, n_plain, plain),
                (n_plain, n_qb - n_plain, {"diag": False, "tail": True})]
    every = {"diag": True, "tail": padded}
    if not aligned:
        return [(0, n_qb, every)]
    if n_qb == 1:
        return [(0, 1, every)]
    lo = (ki * block_k) // block_q
    if _nested(block_q, block_k) and not padded and k_pad <= q_pad:
        n_diag = max(1, block_k // block_q)
        return [(lo, n_diag, every), (lo + n_diag, n_qb - lo - n_diag, plain)]
    return [(lo, n_qb - lo, every)]


def _kernel(q_ref, k_ref, v_ref, *rest, block_q: int, block_k: int,
            q_pad: int, t_real: int, t_pad: int, causal: bool, scale: float,
            q_off: int = 0, k_off: int = 0, has_kmask: bool = False):
    """One q-block vs all key blocks. Refs: q [1, block_q, D];
    k/v [1, t_pad, D]; optional kmask [1, 1, t_pad] (row layout, per
    BATCH — key validity, ANDed into ``valid``); o [1, block_q, D];
    lse [1, 1, block_q].

    lse is stored as a ROW over a [BH, 1, t_pad] array: the natural
    column layout ([.., t_pad, 1]) lane-pads 128x on TPU, which as a
    per-layer vjp residual OOMs large models; the row layout only
    sublane-pads 8x. NOTE: zero-padded q rows get a real finite lse (they
    still see valid keys); the dk/dv kernel's mask on its padded q tiles —
    not any lse sentinel — is what keeps padded rows out of dk/dv."""
    if has_kmask:
        km_ref, o_ref, lse_ref = rest
    else:
        (o_ref, lse_ref), km_ref = rest, None
    qi = 0 if q_pad == block_q else pl.program_id(1)
    # operands stay in their native dtype (bf16 keeps the MXU at full rate);
    # scores, softmax state and the accumulator are f32. q_off/k_off are
    # ABSOLUTE sequence offsets (ring/chunked attention blocks).
    q = q_ref[0]                                                 # [bq, D]
    d = q.shape[-1]
    q_pos = q_off + qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)                              # [bq, 1]

    m0 = jnp.full((block_q, 1), _NEG_BIG, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)

    def body(kb, carry, masked):
        m, l, acc = carry
        start = _aligned(kb, block_k)
        k = k_ref[0, pl.ds(start, block_k), :]
        v = v_ref[0, pl.ds(start, block_k), :]
        s = _dot_nt(q, k) * scale                                # [bq, bk]
        valid = None
        if masked:
            k_pos = k_off + start + lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)                      # [1, bk]
            if t_pad != t_real:
                valid = k_pos < k_off + t_real
            if causal:
                valid = _and(valid, k_pos <= q_pos)
        if km_ref is not None:
            valid = _and(valid, km_ref[0, :, pl.ds(start, block_k)] > 0)
        if valid is not None:
            s = jnp.where(valid, s, _NEG_BIG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                                   # [bq, bk] f32
        alpha = jnp.exp(m - m_new)                               # [bq, 1]
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    # key blocks strictly above the diagonal contribute nothing and are not
    # visited. Equal offsets (incl. the ring schedule's diagonal chunk)
    # reduce k_pos <= q_pos to the same local comparison as the unshifted
    # case; for unequal offsets masking every tile stays correct.
    m, l, acc = _tiles(
        _key_ranges(qi, block_q, block_k, q_pad, t_pad, t_real, causal,
                    q_off == k_off), body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse_ref[0] = (m + jnp.log(jnp.maximum(l, 1e-30))).reshape(1, block_q)


def _pad_bh(x, t_pad):
    """[B, T, H, D] -> [B*H, t_pad, D]."""
    B, T, H, D = x.shape
    x = jnp.swapaxes(x, 1, 2).reshape(B * H, T, D)
    if t_pad != T:
        x = jnp.pad(x, ((0, 0), (0, t_pad - T), (0, 0)))
    return x


def _from_bh(x, B, T, H):
    x = x[:, :T].reshape(B, H, T, x.shape[-1])
    return jnp.swapaxes(x, 1, 2)


def _lane(n: int) -> int:
    return _cdiv(n, 128) * 128


def _working_set(kernel: str, bq: int, bk: int, q_pad: int, k_pad: int,
                 D: int, item: int, has_kmask: bool) -> int:
    """Bytes of VMEM one grid program of ``kernel`` ("fwd", "dq", "dkv")
    holds at blocks (bq, bk): every BlockSpec operand twice (Pallas
    double-buffers inputs and outputs), a minor dimension of D counted at
    its lane padding to 128, a [1, n] float32 row at its sublane padding to
    8; then the float32 [bq, bk] tiles the body keeps live (s and p; the
    backward adds dp and ds) plus one for the compiler's select/cast
    temporaries, and the float32 accumulators."""
    wide = _lane(D)
    row = lambda n: 8 * _lane(n) * 4                 # noqa: E731
    mat = lambda n: n * wide * item                  # noqa: E731
    tile = _lane(bq) * _lane(bk) * 4
    if kernel == "fwd":
        # q tile, whole K and V, [kmask]; o tile, lse row
        io = mat(bq) + 2 * mat(k_pad) + mat(bq) + row(bq)
        live = 3 * tile + bq * wide * 4 + 2 * bq * 128 * 4   # acc, m, l
    elif kernel == "dq":
        # q and do tiles, whole K and V, lse and delta rows; dq tile
        io = 2 * mat(bq) + 2 * mat(k_pad) + 2 * row(bq) + mat(bq)
        live = 5 * tile + bq * wide * 4
    elif kernel == "dkv":
        # whole q and do with their lse and delta rows, k and v tiles;
        # dk and dv tiles
        io = 2 * mat(q_pad) + 2 * row(q_pad) + 2 * mat(bk) + 2 * mat(bk)
        live = 5 * tile + 2 * bk * wide * 4
    else:
        raise ValueError(f"unknown flash kernel {kernel!r}")
    if has_kmask:
        io += row(k_pad if kernel != "dkv" else bk)
    return 2 * io + live


# What the chooser stands on: a v5e run of the three kernels over bq, bk in
# {128, 256, 512, 1024} at five shapes (T 256 to 8192, D 64 and 128, float32
# and bfloat16; PERF.md section 5). The row reductions and the loop's own
# overhead are paid per tile, so 512 x 512 is two to four times under
# 128 x 128 and within a tenth of the best at every shape that needs a loop;
# past 512 the live float32 tiles gain nothing more. A sequence of up to
# 1024 rows is best taken as ONE grid-side block: its one or two loop-side
# tiles are then a static count, no loop is emitted at all, and the three
# kernels together ran 10% (bfloat16) and 17% (float32) under 512 x 512.
_MAX_BLOCK = 512
_MAX_WHOLE = 1024


def _padded(T: int) -> int:
    """The length the chooser tiles: T itself up to one 128-row block (a
    block equal to the whole dimension is always a legal tile), else T
    rounded up to a multiple of 128."""
    return max(T, 1) if T <= 128 else _lane(T)


def choose_blocks(kernel: str, Tq: int, Tk: int, D: int, item: int,
                  has_kmask: bool = False):
    """(bq, bk) for one of the three kernels, from what the call can see.
    The grid side (q for forward and dq, k for dk/dv) is the whole padded
    length up to ``_MAX_WHOLE``, else its largest divisor that is a multiple
    of 128 up to ``_MAX_BLOCK``; the loop side the same up to
    ``_MAX_BLOCK``. The same sweep showed a larger VMEM limit to be worth
    more than a smaller block (dk/dv at T = 8192: 7.4 ms at 512 x 512 under
    a raised limit, 11.7 ms at the 128 x 512 that fits the default), so
    blocks shrink only where the estimate passes ``_VMEM_MAX``, the loop
    side first, since the grid side sets the number of programs."""
    q_pad, k_pad = _padded(Tq), _padded(Tk)

    def sizes(n, whole):
        if n <= whole:
            return [n]
        return [b for b in range(min(_MAX_BLOCK, n), 127, -128) if n % b == 0]

    on_q = kernel != "dkv"                   # which side the grid runs over
    cands = [(bq, bk) for bq in sizes(q_pad, _MAX_WHOLE if on_q else 128)
             for bk in sizes(k_pad, 128 if on_q else _MAX_WHOLE)]
    cands.sort(key=lambda c: c if on_q else c[::-1], reverse=True)
    for bq, bk in cands:
        if _VMEM_HEADROOM * _working_set(kernel, bq, bk, q_pad, k_pad, D,
                                         item, has_kmask) <= _VMEM_MAX:
            return bq, bk
    return cands[-1]


def _plan(kernels, Tq, Tk, D, item, has_kmask, block_q, block_k,
          same_len=False):
    """``({kernel: (bq, bk)}, q_pad, k_pad)`` for the kernels of one call.
    With no blocks given each kernel's are chosen from the shapes and the
    lengths are padded to what the chooser tiled; explicit blocks (both, or
    neither) are honoured as given, clamped to the length, and shared by
    the kernels. ``same_len``: self-attention, one padded length that both
    blocks divide."""
    if (block_q is None) != (block_k is None):
        raise ValueError("give both block_q and block_k, or neither "
                         f"(got block_q={block_q!r}, block_k={block_k!r})")
    if block_q is None:
        blocks = {kn: choose_blocks(kn, Tq, Tk, D, item, has_kmask)
                  for kn in kernels}
        return blocks, _padded(Tq), _padded(Tk)
    bq, bk = min(block_q, max(Tq, 1)), min(block_k, max(Tk, 1))
    q_pad, k_pad = _cdiv(Tq, bq) * bq, _cdiv(Tk, bk) * bk
    if same_len:
        q_pad = k_pad = _cdiv(q_pad, bk) * bk
    return {kn: (bq, bk) for kn in kernels}, q_pad, k_pad


def _compiler_params(kernel, bq, bk, q_pad, k_pad, D, item, has_kmask):
    """Nothing while the estimate fits the default limit; else the estimate
    with its headroom as ``vmem_limit_bytes``. Beyond ``_VMEM_MAX``
    (whole-K residency of T around 90K at D = 64 bfloat16) shard the
    sequence instead (ring attention, parallel/ring.py)."""
    need = _working_set(kernel, bq, bk, q_pad, k_pad, D, item, has_kmask)
    if need <= _VMEM_SHARE * _VMEM_DEFAULT:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=int(min(_VMEM_MAX, max(
            _VMEM_DEFAULT, _VMEM_HEADROOM * need))))}


def _call_kw(kernel, interpret, bq, bk, q_pad, k_pad, D, dtype, has_kmask):
    """What the three pallas_calls share: the interpreter switch, the VMEM
    limit, and the name, which carries the blocks that ran into the
    profiler's trace (the roofline patterns of benchmark/metrics anchor at
    the end of the Mosaic call's name, on its result types)."""
    kw = {"interpret": interpret, "name": f"{_NAMES[kernel]}_q{bq}_k{bk}"}
    if not interpret:
        kw.update(_compiler_params(kernel, bq, bk, q_pad, k_pad, D,
                                   jnp.dtype(dtype).itemsize, has_kmask))
    return kw


def _fwd_pallas_call(qt, kt, vt, *, D, bq, bk, q_pad, k_pad, t_real_k,
                     causal, scale, q_off, k_off, interpret, dtype,
                     kmask=None, H=1):
    """The shared forward pallas_call (main path and chunked-block path):
    padded [BH, q_pad, D] q and [BH, k_pad, D] k/v -> ([BH, q_pad, D] out,
    [BH, 1, q_pad] row-layout lse). ``kmask``: optional [B, 1, k_pad] f32
    key-validity rows, shared by the H heads of each batch (the grid's bh
    axis maps to batch bh // H)."""
    BH = qt.shape[0]
    kernel = functools.partial(
        _kernel, block_q=bq, block_k=bk, q_pad=q_pad, t_real=t_real_k,
        t_pad=k_pad, causal=causal, scale=scale, q_off=q_off, k_off=k_off,
        has_kmask=kmask is not None)
    kw = {} if interpret else {"memory_space": pltpu.VMEM}
    in_specs = [
        pl.BlockSpec((1, bq, D), lambda bh, qi: (bh, qi, 0), **kw),
        pl.BlockSpec((1, k_pad, D), lambda bh, qi: (bh, 0, 0), **kw),
        pl.BlockSpec((1, k_pad, D), lambda bh, qi: (bh, 0, 0), **kw),
    ]
    args = [qt, kt, vt]
    if kmask is not None:
        in_specs.append(
            pl.BlockSpec((1, 1, k_pad), lambda bh, qi: (bh // H, 0, 0), **kw))
        args.append(kmask)
    return pl.pallas_call(
        kernel,
        grid=(BH, q_pad // bq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi: (bh, qi, 0), **kw),
            pl.BlockSpec((1, 1, bq), lambda bh, qi: (bh, 0, qi), **kw),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, q_pad, D), dtype),
            jax.ShapeDtypeStruct((BH, 1, q_pad), jnp.float32),
        ],
        **_call_kw("fwd", interpret, bq, bk, q_pad, k_pad, D, dtype,
                   kmask is not None),
    )(*args)


def _pad_km(kmask, k_pad):
    """[B, Tk] key-validity -> [B, 1, k_pad] f32 rows (padding keys 0)."""
    B, Tk = kmask.shape
    km = kmask.astype(jnp.float32).reshape(B, 1, Tk)
    if k_pad != Tk:
        km = jnp.pad(km, ((0, 0), (0, 0), (0, k_pad - Tk)))
    return km


def _flash_raw(q, k, v, kmask, causal: bool, block_q, block_k,
               interpret: bool, with_lse: bool = False):
    """q/k/v: [B, T, H, D] -> [B, T, H, D] (plus the [B*H, 1, t_pad] row
    logsumexp when ``with_lse``). Forward only. ``kmask``: [B, T] key
    validity or None."""
    B, T, H, D = q.shape
    scale = 1.0 / (D ** 0.5)
    blocks, t_pad, _ = _plan(("fwd",), T, T, D, q.dtype.itemsize,
                             kmask is not None, block_q, block_k, True)
    bq, bk = blocks["fwd"]
    qt, kt, vt = (_pad_bh(x, t_pad) for x in (q, k, v))
    km = _pad_km(kmask, t_pad) if kmask is not None else None
    out, lse = _fwd_pallas_call(
        qt, kt, vt, D=D, bq=bq, bk=bk, q_pad=t_pad, k_pad=t_pad, t_real_k=T,
        causal=causal, scale=scale, q_off=0, k_off=0, interpret=interpret,
        dtype=q.dtype, kmask=km, H=H)
    res = _from_bh(out, B, T, H)
    return (res, lse) if with_lse else res


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   block_q: int, block_k: int, q_pad: int, t_real_k: int,
                   k_pad: int, causal: bool, scale: float, q_off: int = 0,
                   k_off: int = 0, has_kmask: bool = False):
    """dq for one q-block: dq = scale * sum_k [p * (do@v^T - delta)] @ k,
    p = exp(q@k^T*scale - lse) (FlashAttention-2 backward, eq. dS).
    ``delta`` may already carry the -dlse shift (differentiable-lse path:
    ds = p * (dp - delta + dlse)). Key validity uses LOCAL positions vs
    t_real_k; the causal comparison uses ABSOLUTE positions (q_off/k_off —
    chunked/ring blocks). Optional kmask ref [1, 1, k_pad] per batch ANDs
    into validity, mirroring the forward. Zero-padded q rows are not
    masked: a dq row depends on its own q row alone, and the caller slices
    the padded rows off."""
    if has_kmask:
        km_ref, dq_ref = rest
    else:
        (dq_ref,), km_ref = rest, None
    qi = 0 if q_pad == block_q else pl.program_id(1)
    q = q_ref[0]                                                 # [bq, D]
    do = do_ref[0]                                               # [bq, D]
    lse = lse_ref[0].reshape(block_q, 1)                         # row -> col
    delta = delta_ref[0].reshape(block_q, 1)
    q_pos = q_off + qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)

    def body(kb, dq, masked):
        start = _aligned(kb, block_k)
        k = k_ref[0, pl.ds(start, block_k), :]
        v = v_ref[0, pl.ds(start, block_k), :]
        p = jnp.exp(_dot_nt(q, k) * scale - lse)                 # [bq, bk]
        valid = None
        if masked:
            k_loc = start + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
            if k_pad != t_real_k:
                valid = k_loc < t_real_k
            if causal:
                valid = _and(valid, k_off + k_loc <= q_pos)
        if km_ref is not None:
            valid = _and(valid, km_ref[0, :, pl.ds(start, block_k)] > 0)
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        ds = (p * (_dot_nt(do, v) - delta)).astype(k.dtype)
        return dq + jnp.dot(ds, k, preferred_element_type=jnp.float32)

    dq = _tiles(
        _key_ranges(qi, block_q, block_k, q_pad, k_pad, t_real_k, causal,
                    q_off == k_off), body,
        jnp.zeros((block_q, q.shape[-1]), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *rest, block_q: int, block_k: int,
                    t_real_q: int, t_real_k: int, q_pad: int, k_pad: int,
                    causal: bool, scale: float, q_off: int = 0,
                    k_off: int = 0, has_kmask: bool = False):
    """dk/dv for one k-block, looping over q-blocks:
    dv = sum_q p^T @ do;  dk = scale * sum_q [p*(do@v^T - delta)]^T @ q.
    Same delta/offset semantics as _bwd_dq_kernel. Optional kmask ref
    [1, 1, block_k] (THIS k-block's validity slice, per batch).

    The tiles are computed TRANSPOSED, keys down the sublanes and queries
    along the lanes (s^T = k @ q^T, [bk, bq]): the forward's row-layout lse
    and delta then broadcast down the tile as they are stored (no
    row -> column relayout per q-block), and p^T @ do and ds^T @ q are
    plain matmuls with nothing to transpose."""
    if has_kmask:
        km_ref, dk_ref, dv_ref = rest
    else:
        (dk_ref, dv_ref), km_ref = rest, None
    ki = 0 if k_pad == block_k else pl.program_id(1)
    k = k_ref[0]                                                 # [bk, D]
    v = v_ref[0]
    k_loc = ki * block_k + lax.broadcasted_iota(
        jnp.int32, (block_k, 1), 0)                              # [bk, 1]
    k_valid = (k_loc < t_real_k) if k_pad != t_real_k else None
    if km_ref is not None:
        k_valid = _and(k_valid, km_ref[0].reshape(block_k, 1) > 0)

    def body(qb, carry, diag, tail):
        dk, dv = carry
        start = _aligned(qb, block_q)
        q = q_ref[0, pl.ds(start, block_q), :]
        do = do_ref[0, pl.ds(start, block_q), :]
        lse = lse_ref[0, :, pl.ds(start, block_q)]               # [1, bq]
        delta = delta_ref[0, :, pl.ds(start, block_q)]
        pt = jnp.exp(_dot_nt(k, q) * scale - lse)                # [bk, bq]
        valid = k_valid
        if diag or tail:
            q_loc = start + lax.broadcasted_iota(jnp.int32, (1, block_q), 1)
            if tail:
                valid = _and(valid, q_loc < t_real_q)
            if diag:
                valid = _and(valid, k_off + k_loc <= q_off + q_loc)
        if valid is not None:
            pt = jnp.where(valid, pt, 0.0)
        dv = dv + jnp.dot(pt.astype(do.dtype), do,
                          preferred_element_type=jnp.float32)
        dst = (pt * (_dot_nt(v, do) - delta)).astype(q.dtype)
        dk = dk + jnp.dot(dst, q, preferred_element_type=jnp.float32)
        return dk, dv

    zeros = jnp.zeros((block_k, k.shape[-1]), jnp.float32)
    dk, dv = _tiles(
        _query_ranges(ki, block_q, block_k, q_pad, k_pad, t_real_q, causal,
                      q_off == k_off), body, (zeros, zeros))
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_pallas_calls(qt, kt, vt, dot, lse, delta, *, D, blocks, q_pad,
                      k_pad, t_real_q, t_real_k, causal, scale, q_off,
                      k_off, interpret, dtype, kmask=None, H=1):
    """The two backward pallas_calls over padded [BH, ., D] arrays; returns
    padded (dq, dk, dv). ``blocks``: ``{"dq": (bq, bk), "dkv": (bq, bk)}``,
    each kernel at its own. ``delta`` may already carry the -dlse shift.
    ``kmask``: optional [B, 1, k_pad] f32 rows (per batch; bh // H)."""
    BH = qt.shape[0]
    kw = {} if interpret else {"memory_space": pltpu.VMEM}
    full = lambda bh, i: (bh, 0, 0)          # noqa: E731
    blk = lambda bh, i: (bh, i, 0)           # noqa: E731
    row = lambda bh, i: (bh, 0, i)           # noqa: E731
    has_km = kmask is not None
    shared = dict(q_pad=q_pad, k_pad=k_pad, t_real_k=t_real_k, causal=causal,
                  scale=scale, q_off=q_off, k_off=k_off, has_kmask=has_km)

    bq, bk = blocks["dq"]
    dq_in_specs = [
        pl.BlockSpec((1, bq, D), blk, **kw),
        pl.BlockSpec((1, k_pad, D), full, **kw),
        pl.BlockSpec((1, k_pad, D), full, **kw),
        pl.BlockSpec((1, bq, D), blk, **kw),
        pl.BlockSpec((1, 1, bq), row, **kw),
        pl.BlockSpec((1, 1, bq), row, **kw),
    ]
    dq_args = [qt, kt, vt, dot, lse, delta]
    if has_km:
        dq_in_specs.append(
            pl.BlockSpec((1, 1, k_pad), lambda bh, i: (bh // H, 0, 0), **kw))
        dq_args.append(kmask)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=bq, block_k=bk, **shared),
        grid=(BH, q_pad // bq),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, bq, D), blk, **kw),
        out_shape=jax.ShapeDtypeStruct((BH, q_pad, D), dtype),
        **_call_kw("dq", interpret, bq, bk, q_pad, k_pad, D, dtype,
                   has_km),
    )(*dq_args)

    bq, bk = blocks["dkv"]
    dkv_in_specs = [
        pl.BlockSpec((1, q_pad, D), full, **kw),
        pl.BlockSpec((1, bk, D), blk, **kw),
        pl.BlockSpec((1, bk, D), blk, **kw),
        pl.BlockSpec((1, q_pad, D), full, **kw),
        pl.BlockSpec((1, 1, q_pad), full, **kw),
        pl.BlockSpec((1, 1, q_pad), full, **kw),
    ]
    dkv_args = [qt, kt, vt, dot, lse, delta]
    if has_km:
        dkv_in_specs.append(
            pl.BlockSpec((1, 1, bk), lambda bh, i: (bh // H, 0, i), **kw))
        dkv_args.append(kmask)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=bq, block_k=bk,
                          t_real_q=t_real_q, **shared),
        grid=(BH, k_pad // bk),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, bk, D), blk, **kw),
            pl.BlockSpec((1, bk, D), blk, **kw),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, k_pad, D), dtype),
            jax.ShapeDtypeStruct((BH, k_pad, D), dtype),
        ],
        **_call_kw("dkv", interpret, bq, bk, q_pad, k_pad, D, dtype,
                   has_km),
    )(*dkv_args)
    return dq, dk, dv


def _row_layout(x2d, B, H, T, t_pad):
    """[B, H, T] f32 -> padded [B*H, 1, t_pad] row layout."""
    r = x2d.reshape(B * H, 1, T).astype(jnp.float32)
    if t_pad != T:
        r = jnp.pad(r, ((0, 0), (0, 0), (0, t_pad - T)))
    return r


def _flash_bwd_pallas(q, k, v, kmask, o, lse, g, causal: bool, block_q,
                      block_k, interpret: bool):
    """Blockwise backward: scores are rebuilt in VMEM from q/k/v and the
    forward's row-layout logsumexp — no [T, T] tensor ever reaches HBM."""
    B, T, H, D = q.shape
    scale = 1.0 / (D ** 0.5)
    blocks, t_pad, _ = _plan(("dq", "dkv"), T, T, D, q.dtype.itemsize,
                             kmask is not None, block_q, block_k, True)

    qt, kt, vt, dot = (_pad_bh(x, t_pad) for x in (q, k, v, g))
    km = _pad_km(kmask, t_pad) if kmask is not None else None
    # delta_i = rowsum(do_i * o_i): cheap elementwise XLA, f32; same
    # [BH, 1, t_pad] row layout as lse
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = _row_layout(jnp.swapaxes(delta, 1, 2), B, H, T, t_pad)

    dq, dk, dv = _bwd_pallas_calls(
        qt, kt, vt, dot, lse, delta, D=D, blocks=blocks, q_pad=t_pad,
        k_pad=t_pad, t_real_q=T, t_real_k=T, causal=causal, scale=scale,
        q_off=0, k_off=0, interpret=interpret, dtype=q.dtype, kmask=km, H=H)
    return (_from_bh(dq, B, T, H), _from_bh(dk, B, T, H),
            _from_bh(dv, B, T, H))


def _reference(q, k, v, causal: bool, kmask=None):
    """The same math in plain XLA ops — used by the equivalence tests.
    Matches parallel/ring.py local_attention semantics incl. the
    fully-masked-row clamp."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk",
                   q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        T = q.shape[1]
        msk = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(msk[None, None], s, _NEG_BIG)
    if kmask is not None:
        s = jnp.where(kmask[:, None, None, :] > 0, s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


def _reference_chunked(q, k, v, causal: bool, chunk: int = 128, kmask=None):
    """Attention computed q-chunk-at-a-time with ``lax.map`` — identical
    math to :func:`_reference`, but only [B, H, chunk, T] scores exist at
    once. The custom VJP differentiates THIS function, so the backward is
    memory-bounded too (vjp of lax.map is a scan with per-chunk residuals)
    and training works at the long T the flash forward enables."""
    B, T, H, D = q.shape
    scale = 1.0 / (D ** 0.5)
    n = _cdiv(T, chunk)
    t_pad = n * chunk
    qp = jnp.pad(q, ((0, 0), (0, t_pad - T), (0, 0), (0, 0)))
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    k_pos = jnp.arange(T)

    def one_chunk(ci):
        qc = lax.dynamic_slice_in_dim(qp, ci * chunk, chunk, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qc.astype(jnp.float32), kf) * scale
        q_pos = ci * chunk + jnp.arange(chunk)
        valid = jnp.ones((chunk, T), bool)
        if causal:
            valid = k_pos[None, :] <= q_pos[:, None]
        s = jnp.where(valid[None, None], s, _NEG_BIG)
        if kmask is not None:
            s = jnp.where(kmask[:, None, None, :] > 0, s, _NEG_BIG)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vf)        # [B,chunk,H,D]

    out = lax.map(one_chunk, jnp.arange(n))                # [n,B,chunk,H,D]
    out = jnp.moveaxis(out, 0, 1).reshape(B, t_pad, H, D)
    return out[:, :T].astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, kmask, causal, block_q, block_k, interpret, bwd):
    return _flash_raw(q, k, v, kmask, causal, block_q, block_k, interpret)


def _flash_fwd(q, k, v, kmask, causal, block_q, block_k, interpret, bwd):
    if bwd == "pallas":
        out, lse = _flash_raw(q, k, v, kmask, causal, block_q, block_k,
                              interpret, with_lse=True)
        return out, (q, k, v, kmask, out, lse)
    # the xla fallback exists for memory-constrained cases: don't burden it
    # with the out/lse residuals it never reads
    out = _flash_raw(q, k, v, kmask, causal, block_q, block_k, interpret)
    return out, (q, k, v, kmask, None, None)


def _flash_bwd(causal, block_q, block_k, interpret, bwd, res, g):
    q, k, v, kmask, o, lse = res
    dkm = (jnp.zeros_like(kmask) if kmask is not None else None)
    if bwd == "pallas":
        dq, dk, dv = _flash_bwd_pallas(q, k, v, kmask, o, lse, g, causal,
                                       block_q, block_k, interpret)
        return dq, dk, dv, dkm
    # XLA rematerialisation fallback (also the correctness oracle in
    # tests). Chunking is a memory/throughput trade: lax.map serialises
    # chunks (~15% slower at T=2048), so use the dense [T,T] recompute
    # while the f32 score tensor is affordable and switch to q-chunks only
    # when it is not.
    B, T, H, _ = q.shape
    if kmask is not None:
        # agree with the Pallas backward on fully-masked query rows: the
        # kernel's validity mask makes their p (hence dq and their dk/dv
        # contributions) exactly zero, while _reference's softmax over an
        # all-_NEG_BIG row is uniform — zero those rows' cotangent here
        has_valid = (jnp.cumsum(kmask, axis=1) > 0) if causal else \
            (jnp.sum(kmask, axis=1, keepdims=True) > 0)          # [B, T]/[B,1]
        g = g * has_valid[:, :, None, None].astype(g.dtype)
    score_bytes = 4 * B * H * T * T
    # the dense vjp holds ~3 score-sized f32 tensors at once (softmax
    # residual p + dp/ds temporaries), so budget for 3x, not 1x
    if 3 * score_bytes <= 4 << 30:
        fn = lambda q_, k_, v_: _reference(q_, k_, v_, causal, kmask)
    else:
        fn = lambda q_, k_, v_: _reference_chunked(q_, k_, v_, causal,
                                                   kmask=kmask)
    _, vjp = jax.vjp(fn, q, k, v)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, dkm


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, kmask=None, causal: bool = False,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False, bwd: str = "pallas"):
    """Blockwise flash attention over [B, T, H, D] (differentiable).

    Forward runs the Pallas kernel (never materialises [T, T]); the
    backward is a blockwise Pallas kernel pair too (dq grid over q-blocks,
    dk/dv grid over k-blocks) consuming the forward's logsumexp residual —
    ``bwd="xla"`` selects the rematerialising XLA fallback (the tests'
    correctness oracle). ``interpret=True`` runs the kernels in the Pallas
    interpreter (CPU tests). ``kmask`` [B, T]: key validity (1=real,
    0=padding) shared across heads — the padded/variable-length batch case;
    the kernel loads one [1, block_k] row slice per key block and ANDs it
    into the validity mask, so masked training keeps the flash memory
    envelope."""
    if bwd not in ("pallas", "xla"):
        raise ValueError(f"bwd must be 'pallas' or 'xla', got {bwd!r}")
    if kmask is not None:
        # float at the custom_vjp boundary (integer args would need float0
        # cotangents); the bwd returns zeros for it
        kmask = jnp.asarray(kmask, jnp.float32)
    return _flash(q, k, v, kmask, causal, block_q, block_k, interpret, bwd)


def flash_attention_block(q, k, v, *, kmask=None, q_offset: int = 0,
                          k_offset: int = 0,
                          causal: bool = False,
                          block_q: Optional[int] = None,
                          block_k: Optional[int] = None,
                          interpret: bool = False):
    """FORWARD-ONLY building block for chunked/ring attention: attention of
    q (absolute positions starting at ``q_offset``) over ONE k/v chunk
    (positions starting at ``k_offset``), returning
    ``(out, lse [B, H, T])`` — the per-row logsumexp needed to merge
    partial results across chunks with :func:`merge_attention_blocks`.

    Rows whose keys are entirely masked (causal, q < k_offset; or a fully
    kmasked chunk) return a ~-1e30 lse whose merge weight underflows to
    exactly 0 — but their ``out`` is mean(v), NOT 0 (every masked score
    equals the running-max sentinel, so p=1 uniformly). ``out`` alone is
    therefore meaningless without the lse weighting: always combine via
    merge_attention_blocks. ``kmask`` [B, Tk]: THIS key chunk's validity."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = 1.0 / (D ** 0.5)
    blocks, q_pad, k_pad = _plan(("fwd",), Tq, Tk, D, q.dtype.itemsize,
                                 kmask is not None, block_q, block_k)
    bq, bk = blocks["fwd"]
    qt = _pad_bh(q, q_pad)
    kt, vt = _pad_bh(k, k_pad), _pad_bh(v, k_pad)
    km = _pad_km(kmask, k_pad) if kmask is not None else None
    # t_real_k gates KEY validity (Tk, not Tq — the chunk may be shorter);
    # padded q rows emit garbage that is sliced off below
    out, lse = _fwd_pallas_call(
        qt, kt, vt, D=D, bq=bq, bk=bk, q_pad=q_pad, k_pad=k_pad, t_real_k=Tk,
        causal=causal, scale=scale, q_off=q_offset, k_off=k_offset,
        interpret=interpret, dtype=q.dtype, kmask=km, H=H)
    # fully masked rows: m stays _NEG_BIG so lse = m + log(l) is ~-1e30
    # and the merge weight underflows to 0 (their out is mean(v), see
    # docstring — only the weighted combination is meaningful)
    lse_b = lse[:, 0, :Tq].reshape(B, H, Tq)
    return _from_bh(out, B, Tq, H), lse_b


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_block_diff(q, k, v, kmask, q_offset, k_offset, causal, block_q,
                      block_k, interpret):
    return flash_attention_block(
        q, k, v, kmask=kmask, q_offset=q_offset, k_offset=k_offset,
        causal=causal, block_q=block_q, block_k=block_k, interpret=interpret)


def _flash_block_diff_fwd(q, k, v, kmask, q_offset, k_offset, causal,
                          block_q, block_k, interpret):
    out, lse = flash_attention_block(
        q, k, v, kmask=kmask, q_offset=q_offset, k_offset=k_offset,
        causal=causal, block_q=block_q, block_k=block_k, interpret=interpret)
    return (out, lse), (q, k, v, kmask, out, lse)


def _flash_block_diff_bwd(q_offset, k_offset, causal, block_q, block_k,
                          interpret, res, cts):
    """Backward with BOTH cotangents (do, dlse). d lse_i/d s_ij = p_ij, so
    the dlse contribution folds into the delta shift:
    ds = p * (do@v^T - delta + dlse)  =>  delta_eff = delta - dlse
    (FlashAttention-2 eq. dS extended for a differentiable logsumexp —
    exactly what chunk-merged/ring attention training needs)."""
    q, k, v, kmask, o, lse = res
    do, dlse = cts
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = 1.0 / (D ** 0.5)
    blocks, q_pad, k_pad = _plan(("dq", "dkv"), Tq, Tk, D, q.dtype.itemsize,
                                 kmask is not None, block_q, block_k)
    qt, dot = _pad_bh(q, q_pad), _pad_bh(do, q_pad)
    kt, vt = _pad_bh(k, k_pad), _pad_bh(v, k_pad)
    km = _pad_km(kmask, k_pad) if kmask is not None else None
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.swapaxes(delta, 1, 2) - dlse.astype(jnp.float32)  # [B,H,Tq]
    delta = _row_layout(delta, B, H, Tq, q_pad)
    lse_r = _row_layout(lse, B, H, Tq, q_pad)
    dq, dk, dv = _bwd_pallas_calls(
        qt, kt, vt, dot, lse_r, delta, D=D, blocks=blocks, q_pad=q_pad,
        k_pad=k_pad, t_real_q=Tq, t_real_k=Tk, causal=causal, scale=scale,
        q_off=q_offset, k_off=k_offset, interpret=interpret, dtype=q.dtype,
        kmask=km, H=H)
    dkm = jnp.zeros_like(kmask) if kmask is not None else None
    return (_from_bh(dq, B, Tq, H), _from_bh(dk, B, Tk, H),
            _from_bh(dv, B, Tk, H), dkm)


_flash_block_diff.defvjp(_flash_block_diff_fwd, _flash_block_diff_bwd)


def flash_attention_block_grad(q, k, v, *, kmask=None, q_offset: int = 0,
                               k_offset: int = 0, causal: bool = False,
                               block_q: Optional[int] = None,
                               block_k: Optional[int] = None,
                               interpret: bool = False):
    """DIFFERENTIABLE chunked flash attention: like
    :func:`flash_attention_block` but (out, lse) both carry gradients —
    the merge (and anything downstream of it) backpropagates exactly
    through every chunk via blockwise Pallas kernels. This is the
    training-capable building block for chunk-sequential and ring
    attention schedules. ``kmask`` [B, Tk]: this key chunk's validity."""
    if kmask is not None:
        kmask = jnp.asarray(kmask, jnp.float32)
    return _flash_block_diff(q, k, v, kmask, q_offset, k_offset, causal,
                             block_q, block_k, interpret)


def merge_attention_blocks(parts):
    """Merge [(out_i [B,T,H,D], lse_i [B,H,T])] partial attentions over
    DISJOINT key chunks into the attention over their union:
    out = sum_i w_i * out_i with w_i = exp(lse_i - logsumexp_i(lse_i)).
    Streaming-softmax identity — exact up to float rounding."""
    outs = jnp.stack([o for o, _ in parts])                # [N, B, T, H, D]
    lses = jnp.stack([l for _, l in parts])                # [N, B, H, T]
    lse_tot = jax.nn.logsumexp(lses, axis=0)               # [B, H, T]
    w = jnp.exp(lses - lse_tot[None])                      # [N, B, H, T]
    w = jnp.moveaxis(w, 3, 2)[..., None]                   # [N, B, T, H, 1]
    return jnp.sum(outs.astype(jnp.float32) * w, axis=0).astype(outs.dtype)


# VMEM ceiling note: what one grid program holds (``_working_set``), every
# BlockSpec operand twice and a D = 64 minor dimension at 128 lanes. Forward
# and dq: the WHOLE [t_pad, D] K and V (4 * t_pad * 128 * itemsize bytes:
# 2 MiB at T = 1024 float32, 8 MiB at T = 8192 bfloat16) beside bq-row
# tiles of q/o (and do/dq) and three to five float32 [bq, bk] score tiles.
# dk/dv: the whole q and do with their lse/delta rows beside bk-row tiles of
# k/v/dk/dv. The chooser keeps that under 12 of the default 16 MiB by
# shrinking the blocks (T = 8192 bfloat16 still runs 512 x 256 forward);
# where the whole-sequence operands alone pass it (T = 16384 at D = 128
# bfloat16, T = 32768 at D = 64) the call runs 128 x 128 and asks Mosaic for
# ``vmem_limit_bytes`` = 1.25 x the estimate, up to 100 of the v5e's
# 128 MiB: T about 90K at D = 64 bfloat16. Beyond that, shard the sequence
# (ring attention, parallel/ring.py) — the ring's per-shard blocks land back
# under the ceiling. A second grid axis over key blocks would lift the limit
# in-kernel; not needed at the lengths the framework targets single-chip.


# ---------------------------------------------------------------------------
# Decode-mode attention (KV-cache serving path, nn/decode.py)
# ---------------------------------------------------------------------------


def decode_attention(q, k, v, q_positions):
    """Attention of a short new-token chunk against a gathered KV cache.

    ``q`` [B, Tc, H, D] — the chunk being decoded/prefilled (Tc is 1 in
    steady-state decode, a prefill-chunk bucket otherwise); ``k``/``v``
    [B, K, H, D] — the cache span gathered for each row, laid out so index
    ``g`` along K IS absolute sequence position ``g`` (nn/decode.py writes
    the chunk's own k/v into the cache before gathering, so no separate
    self-attention term exists); ``q_positions`` [B, Tc] int32 — each
    query's absolute position. Causality is positional: key ``g`` is valid
    iff ``g <= q_positions[b, t]``, which simultaneously enforces the
    causal mask and hides every cache slot past the row's written length
    (unwritten pool pages hold finite garbage, masked to an exact-zero
    softmax weight).

    Deliberately plain XLA, not Pallas: flash attention exists to keep the
    [T, T] score tensor out of HBM, but here the score tensor is
    [B, H, Tc, K] with Tc <= one prefill chunk — a few hundred KB at
    serving shapes. The flash kernel remains the training/full-prefill
    path. Numerics mirror ``parallel/ring.py local_attention`` (scores in
    the operand dtype, -inf mask clamped at ``_NEG_BIG``) so a
    cache-backed prefill agrees with the full forward on the XLA path.

    Bit-exactness under padding (the serving tier's batched==unbatched
    guarantee): padded batch rows are independent (row-block computation),
    and padded/masked cache tail positions contribute exp(-1e30 - m) = 0
    exactly to the softmax and 0 * v to the value sum — trailing zero
    terms that leave every real row's reduction bitwise unchanged.
    """
    K = k.shape[1]
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale      # [B, H, Tc, K]
    valid = jnp.arange(K)[None, None, None, :] <= \
        q_positions[:, None, :, None]                    # [B, 1, Tc, K]
    s = jnp.where(valid, s, -jnp.inf)
    # position 0 is always <= q_position, so no row is fully masked; the
    # clamp keeps the same guard local_attention carries regardless
    s = jnp.maximum(s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)           # [B, Tc, H, D]
