"""Flash attention — Pallas TPU kernel with online (streaming) softmax.

The XLA attention path (`parallel/ring.py local_attention`) materialises
the [B, H, T, T] score matrix in HBM; at long T that traffic dominates
(PERF.md section 5). This kernel computes
attention blockwise in VMEM — scores never leave the chip — using the
standard streaming-softmax recurrence (running max m, normaliser l,
rescaled accumulator), one (batch, head group, q-block) program per grid
cell looping over key blocks.

Addressing: the kernels read q, k, v and write their results in the layout
the projection matmuls produce, ``[B, T, H*D]`` (a free reshape of the public
``[B, T, H, D]``; the fused ``[B, T, 3*H*D]`` projection is read in place,
once per role, by three index maps). A block's minor dimension is 128 lanes:
one head at D = 128 (or D itself where D is a multiple of 128), ``128 // D``
heads where D divides 128 and that count divides H (``heads_per_block``).
A program runs its score tiles once a head: the other heads' lanes of q (or
of k and v in dk/dv) are zeroed, so the 128-deep contraction is the head's
own, and each head keeps its own columns of the 128-wide products. Shapes
that fit neither (D = 80, an odd head count at D = 64) are transposed to
``[B*H, T, D]`` around the calls, as every shape was before, and run the same
kernels one head a block.

Tiling: each kernel picks its blocks from the shapes of the
call (``choose_blocks``: a sequence of up to 1024 rows is one grid block,
a longer one is cut into the largest multiples of 128, up to 512, that
divide its padded length), and its loop is split by what a tile needs:
tiles wholly on the valid side of the causal diagonal run in a loop whose
body has no iota, compare or select in it; the one or two tiles the diagonal
crosses, and the last one of a padded length, run the masked body as
straight-line code after it. The heads in a lane block and the blocks that
ran are part of each kernel's name (``flash_fwd_h2_q1024_k512``; no ``h``
part on the transposed path), so a profiler trace says which addressing and
which tiling it shows.

Beyond-reference scope: the reference (DL4J 0.9.2) has no attention layer
at all (SURVEY.md §5.7); this accelerates the framework's TransformerLM
extension. Training uses a custom VJP whose backward is ALSO blockwise
Pallas (FlashAttention-2 style): the forward emits a per-row logsumexp
residual, and one kernel gridded over key blocks rebuilds each tile's
p = exp(s - lse) once in VMEM and takes dv, dk and dq from it, dq added
into a whole-length float32 accumulator that lives across the key blocks
(``flash_bwd_dqkv``) — no [T, T] tensor in either direction. Where the
offsets differ (ring and chunked blocks) or that dq does not fit VMEM, the
split pair runs instead: a dq kernel over q-blocks and a dk/dv kernel over
k-blocks, each computing every tile again (``_bwd_plan`` chooses from the
shapes and offsets). A rematerialising XLA backward (``bwd="xla"``)
remains as the correctness oracle and fallback.

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
import types
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -1e30
_NT = (((1,), (1,)), ((), ()))      # a @ b.T without a materialised transpose
_TN = (((0,), (0,)), ((), ()))      # a.T @ b

# Mosaic gives a kernel 16 MiB of VMEM unless told otherwise. A call whose
# estimated working set stays under _VMEM_SHARE of that says nothing; one
# above it (long T: the whole-sequence operands) asks for the estimate with
# _VMEM_HEADROOM, up to _VMEM_MAX of the v5e's 128 MiB.
_VMEM_DEFAULT = 16 << 20
_VMEM_SHARE = 0.75
_VMEM_HEADROOM = 1.25
_VMEM_MAX = 100 << 20
_NAMES = types.MappingProxyType(    # read under jit: not to be mutated
    {"fwd": "flash_fwd", "dq": "flash_bwd_dq", "dkv": "flash_bwd_dkv",
     "dqkv": "flash_bwd_dqkv"})
_BY_KEY_BLOCK = ("dkv", "dqkv")     # kernels whose grid runs over key blocks
_STRAIGHT = 2       # tiles of a statically known count emitted without a loop
# What a forward rule calls the two results its backward reads again, here and
# in ops/flash_mla.py: a ``jax.checkpoint`` whose policy saves these names
# (``ResidualBlock.remat``) keeps them and does not run the kernel a second
# time. Outside a checkpoint the name is the identity and lowers to nothing.
ATTN_OUT = "attn_out"
ATTN_LSE = "attn_lse"


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


def _dot_tn(a, b):
    """a.T @ b, float32 result: the one product of the one-kernel backward
    whose left operand is a transposed tile (dq from ds^T)."""
    return lax.dot_general(a, b, _TN, preferred_element_type=jnp.float32)


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


class _Layout(NamedTuple):
    """How the kernels find a head in the arrays they are handed.

    ``heads`` heads of width ``D`` share one lane block of ``heads * D``
    lanes; a role (q, k or v) spans ``groups`` such blocks, and its first one
    is ``at[role]`` (0 unless q, k and v are one fused array). Head-addressed
    arrays are ``[B, T, lanes]``; ``transposed`` ones are ``[B*H, T, D]``
    (one head a block, one group) and ``mask_rows`` = H of their rows share
    a key-mask row. The row-layout lse and delta are ``[B*H, 1, T]`` either
    way, head ``g * heads + h`` of batch ``n`` in row ``(n * groups + g) *
    heads + h``."""
    heads: int
    D: int
    groups: int = 1
    at: tuple = (0, 0, 0)
    transposed: bool = False
    mask_rows: int = 1

    @property
    def tag(self) -> str:
        return "" if self.transposed else f"_h{self.heads}"


def heads_per_block(H: int, D: int) -> Optional[int]:
    """Heads that share one lane block of a ``[B, T, H*D]`` array, from the
    shapes alone: 1 where D is a multiple of 128, ``128 // D`` where D
    divides 128 and that count divides H, None where neither holds (the
    call then transposes to ``[B*H, T, D]``)."""
    if D % 128 == 0:
        return 1
    if 128 % D == 0 and H % (128 // D) == 0:
        return 128 // D
    return None


def _layout(H: int, D: int, fused: bool = False) -> _Layout:
    G = heads_per_block(H, D)
    if G is None:
        return _Layout(1, D, transposed=True, mask_rows=H)
    groups = H // G
    return _Layout(G, D, groups,
                   (0, groups, 2 * groups) if fused else (0, 0, 0))


def _head_lanes(h: int, lay: _Layout):
    """[1, lanes] bool: the lanes of head ``h`` of a block; None where the
    block is one head."""
    if lay.heads == 1:
        return None
    lane = lax.broadcasted_iota(jnp.int32, (1, lay.heads * lay.D), 1)
    return jnp.logical_and(lane >= h * lay.D, lane < (h + 1) * lay.D)


def _only(lanes, x):
    """``x`` with the lanes of the other heads zeroed: a contraction over
    the block's 128 lanes is then this head's own (the same MXU pass a
    D-deep one takes)."""
    return x if lanes is None else jnp.where(lanes, x, jnp.zeros_like(x))


def _keep(lanes, new, old):
    """This head's columns of ``new`` into ``old`` (a product over the
    128-lane block holds every head's columns; one head's are its own)."""
    return new if lanes is None or old is None else jnp.where(lanes, new, old)


def _kernel(q_ref, k_ref, v_ref, *rest, lay: _Layout, block_q: int,
            block_k: int, q_pad: int, t_real: int, t_pad: int, causal: bool,
            scale: float, q_off: int = 0, k_off: int = 0,
            has_kmask: bool = False):
    """One q-block vs all key blocks, once a head of the lane block. Refs:
    q [1, block_q, lanes]; k/v [1, t_pad, lanes]; optional kmask
    [1, 1, t_pad] (row layout, per BATCH — key validity, ANDed into
    ``valid``); o [1, block_q, lanes]; lse [heads, 1, block_q].

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
    qi = 0 if q_pad == block_q else pl.program_id(2)
    # operands stay in their native dtype (bf16 keeps the MXU at full rate);
    # scores, softmax state and the accumulator are f32. q_off/k_off are
    # ABSOLUTE sequence offsets (ring/chunked attention blocks).
    q_all = q_ref[0]                                             # [bq, lanes]
    q_pos = q_off + qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)                              # [bq, 1]

    m0 = jnp.full((block_q, 1), _NEG_BIG, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros(q_all.shape, jnp.float32)
    out = None
    for h in range(lay.heads):
        lanes = _head_lanes(h, lay)
        q = _only(lanes, q_all)

        def body(kb, carry, masked):
            m, l, acc = carry
            start = _aligned(kb, block_k)
            k = k_ref[0, pl.ds(start, block_k), :]
            v = v_ref[0, pl.ds(start, block_k), :]
            s = _dot_nt(q, k) * scale                            # [bq, bk]
            valid = None
            if masked:
                k_pos = k_off + start + lax.broadcasted_iota(
                    jnp.int32, (1, block_k), 1)                  # [1, bk]
                if t_pad != t_real:
                    valid = k_pos < k_off + t_real
                if causal:
                    valid = _and(valid, k_pos <= q_pos)
            if km_ref is not None:
                valid = _and(valid, km_ref[0, :, pl.ds(start, block_k)] > 0)
            if valid is not None:
                s = jnp.where(valid, s, _NEG_BIG)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)                               # [bq, bk] f32
            alpha = jnp.exp(m - m_new)                           # [bq, 1]
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            # every head's columns of p @ v; this head's are kept below
            acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                        preferred_element_type=jnp.float32)
            return m_new, l, acc

        # key blocks strictly above the diagonal contribute nothing and are
        # not visited. Equal offsets (incl. the ring schedule's diagonal
        # chunk) reduce k_pos <= q_pos to the same local comparison as the
        # unshifted case; for unequal offsets masking every tile stays
        # correct.
        m, l, acc = _tiles(
            _key_ranges(qi, block_q, block_k, q_pad, t_pad, t_real, causal,
                        q_off == k_off), body, (m0, l0, acc0))
        out = _keep(lanes, acc / jnp.maximum(l, 1e-30), out)
        lse_ref[h] = (m + jnp.log(jnp.maximum(l, 1e-30))).reshape(1, block_q)
    o_ref[0] = out.astype(o_ref.dtype)


def _operand(x, t_pad, lay: _Layout):
    """[B, T, H, D] in the layout the kernels address, T padded with zero
    rows: [B, t_pad, H*D], a reshape; [B*H, t_pad, D] where ``lay`` is
    transposed."""
    B, T, H, D = x.shape
    if lay.transposed:
        x = jnp.swapaxes(x, 1, 2).reshape(B * H, T, D)
    else:
        x = x.reshape(B, T, H * D)
    return _pad_rows(x, t_pad)


def _pad_rows(x, t_pad):
    T = x.shape[1]
    return x if t_pad == T else jnp.pad(x, ((0, 0), (0, t_pad - T), (0, 0)))


def _result(x, B, T, H, lay: _Layout):
    """A kernel's padded result back to [B, T, H, D]."""
    if lay.transposed:
        return jnp.swapaxes(x[:, :T].reshape(B, H, T, lay.D), 1, 2)
    return x[:, :T].reshape(B, T, H, lay.D)


def _lane(n: int) -> int:
    return _cdiv(n, 128) * 128


def _working_set(kernel: str, bq: int, bk: int, q_pad: int, k_pad: int,
                 D: int, item: int, has_kmask: bool, heads: int = 1) -> int:
    """Bytes of VMEM one grid program of ``kernel`` ("fwd", "dq", "dkv",
    "dqkv": "dkv" with dq's whole-length result block, its float32
    accumulator and one more tile) holds at blocks (bq, bk): every BlockSpec
    operand twice (Pallas
    double-buffers inputs and outputs), a lane block of ``heads * D`` counted
    at its padding to 128 lanes, a [1, n] float32 row at its sublane padding
    to 8, one lse or delta row a head; then the float32 [bq, bk] tiles the
    body keeps live (s and p; the backward adds dp and ds: the heads of a
    block run one after another, so these are one head's) plus one for the
    compiler's select/cast temporaries, and the float32 accumulators, with
    the result the heads' columns are gathered into where a block holds
    more than one."""
    wide = _lane(heads * D)
    row = lambda n: 8 * _lane(n) * 4                 # noqa: E731
    mat = lambda n: n * wide * item                  # noqa: E731
    tile = _lane(bq) * _lane(bk) * 4
    gather = 2 if heads > 1 else 1                   # accumulator + result
    if kernel == "fwd":
        # q tile, whole K and V, [kmask]; o tile, lse rows
        io = mat(bq) + 2 * mat(k_pad) + mat(bq) + heads * row(bq)
        live = 3 * tile + gather * bq * wide * 4 + 2 * bq * 128 * 4  # m, l
    elif kernel == "dq":
        # q and do tiles, whole K and V, lse and delta rows; dq tile
        io = 2 * mat(bq) + 2 * mat(k_pad) + 2 * heads * row(bq) + mat(bq)
        live = 5 * tile + gather * bq * wide * 4
    elif kernel in _BY_KEY_BLOCK:
        # whole q and do with their lse and delta rows, k and v tiles;
        # dk and dv tiles
        io = (2 * mat(q_pad) + 2 * heads * row(q_pad) + 2 * mat(bk)
              + 2 * mat(bk))
        live = 5 * tile + gather * 2 * bk * wide * 4
        if kernel == "dqkv":
            io += mat(q_pad)
            live += tile + q_pad * wide * 4
    else:
        raise ValueError(f"unknown flash kernel {kernel!r}")
    if has_kmask:
        io += row(bk if kernel in _BY_KEY_BLOCK else k_pad)
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
                  has_kmask: bool = False, heads: int = 1, working_set=None):
    """(bq, bk) for one of the kernels, from what the call can see
    (``heads``: how many share its lane blocks, ``heads_per_block``).
    The grid side (q for forward and dq, k for dk/dv and dqkv) is the
    whole padded length up to ``_MAX_WHOLE``, else its largest divisor that
    is a multiple of 128 up to ``_MAX_BLOCK``; the loop side the same up to
    ``_MAX_BLOCK``. The same sweep showed a larger VMEM limit to be worth
    more than a smaller block (dk/dv at T = 8192: 7.4 ms at 512 x 512 under
    a raised limit, 11.7 ms at the 128 x 512 that fits the default), so
    blocks shrink only where the estimate passes ``_VMEM_MAX``, the loop
    side first, since the grid side sets the number of programs.
    ``working_set(kernel, bq, bk, q_pad, k_pad)``: another kernel family's
    estimate in place of :func:`_working_set` (ops/flash_mla.py)."""
    q_pad, k_pad = _padded(Tq), _padded(Tk)
    need = working_set or functools.partial(
        _working_set, D=D, item=item, has_kmask=has_kmask, heads=heads)

    def sizes(n, whole):
        if n <= whole:
            return [n]
        return [b for b in range(min(_MAX_BLOCK, n), 127, -128) if n % b == 0]

    on_q = kernel not in _BY_KEY_BLOCK       # which side the grid runs over
    cands = [(bq, bk) for bq in sizes(q_pad, _MAX_WHOLE if on_q else 128)
             for bk in sizes(k_pad, 128 if on_q else _MAX_WHOLE)]
    cands.sort(key=lambda c: c if on_q else c[::-1], reverse=True)
    for bq, bk in cands:
        if _VMEM_HEADROOM * need(kernel, bq, bk, q_pad, k_pad) <= _VMEM_MAX:
            return bq, bk
    return cands[-1]


def _plan(kernels, Tq, Tk, D, item, has_kmask, block_q, block_k,
          same_len=False, heads=1, working_set=None):
    """``({kernel: (bq, bk)}, q_pad, k_pad)`` for the kernels of one call.
    With no blocks given each kernel's are chosen from the shapes and the
    lengths are padded to what the chooser tiled; explicit blocks (both, or
    neither) are honoured as given, clamped to the length, and shared by
    the kernels. ``same_len``: self-attention, one padded length that both
    blocks divide. ``working_set``: as :func:`choose_blocks` takes it."""
    if (block_q is None) != (block_k is None):
        raise ValueError("give both block_q and block_k, or neither "
                         f"(got block_q={block_q!r}, block_k={block_k!r})")
    if block_q is None:
        blocks = {kn: choose_blocks(kn, Tq, Tk, D, item, has_kmask, heads,
                                    working_set)
                  for kn in kernels}
        return blocks, _padded(Tq), _padded(Tk)
    bq, bk = min(block_q, max(Tq, 1)), min(block_k, max(Tk, 1))
    q_pad, k_pad = _cdiv(Tq, bq) * bq, _cdiv(Tk, bk) * bk
    if same_len:
        q_pad = k_pad = _cdiv(q_pad, bk) * bk
    return {kn: (bq, bk) for kn in kernels}, q_pad, k_pad


def _bwd_plan(Tq, Tk, D, item, has_kmask, block_q, block_k, same_len,
              heads, aligned):
    """:func:`_plan` for the backward, which also picks its kernels: the one
    "dqkv" kernel where the offsets are ``aligned`` (self-attention; the
    ring's diagonal) and its working set, dq's whole length resident, passes
    ``_VMEM_MAX``'s test; else the split "dq" and "dkv" pair (chunked blocks
    at unequal offsets, a length whose dq does not fit), each of which
    computes every score tile again."""
    args = (Tq, Tk, D, item, has_kmask, block_q, block_k, same_len, heads)
    if aligned:
        blocks, q_pad, k_pad = _plan(("dqkv",), *args)
        need = _working_set("dqkv", *blocks["dqkv"], q_pad, k_pad, D, item,
                            has_kmask, heads)
        if _VMEM_HEADROOM * need <= _VMEM_MAX:
            return blocks, q_pad, k_pad
    return _plan(("dq", "dkv"), *args)


def _block_kw(blocks):
    """A backward plan's blocks as :func:`_bwd_pallas_calls` takes them."""
    return {f"{kn}_blocks": b for kn, b in blocks.items()}


def _compiler_params(kernel, bq, bk, q_pad, k_pad, D, item, has_kmask,
                     heads=1):
    """Nothing while the estimate fits the default limit; else the estimate
    with its headroom as ``vmem_limit_bytes``. Beyond ``_VMEM_MAX``
    (whole-K residency of T around 90K at D = 64 bfloat16) shard the
    sequence instead (ring attention, parallel/ring.py)."""
    return _vmem_params(_working_set(kernel, bq, bk, q_pad, k_pad, D, item,
                                     has_kmask, heads))


def _vmem_params(need: int) -> dict:
    """The ``pallas_call`` keyword that raises Mosaic's VMEM limit for a
    working set of ``need`` bytes; nothing while the default holds it."""
    if need <= _VMEM_SHARE * _VMEM_DEFAULT:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=int(min(_VMEM_MAX, max(
            _VMEM_DEFAULT, _VMEM_HEADROOM * need))))}


def _call_kw(kernel, interpret, lay, bq, bk, q_pad, k_pad, dtype, has_kmask):
    """What the pallas_calls share: the interpreter switch, the VMEM
    limit, and the name, which carries the heads of a lane block and the
    blocks that ran into the profiler's trace (the roofline patterns of
    benchmark/metrics anchor at the end of the Mosaic call's name, on its
    result types)."""
    kw = {"interpret": interpret,
          "name": f"{_NAMES[kernel]}{lay.tag}_q{bq}_k{bk}"}
    if not interpret:
        kw.update(_compiler_params(kernel, bq, bk, q_pad, k_pad, lay.D,
                                   jnp.dtype(dtype).itemsize, has_kmask,
                                   lay.heads))
    return kw


def _specs(lay: _Layout, interpret: bool):
    """BlockSpec makers over the grid (array row n, head group g, block i):
    ``lanes(rows, role, whole)`` a [1, rows, lanes] block of q (role 0), k
    (1), v (2) or an array of the call's own (None: the cotangent, a
    result), its block i along T or the ``whole`` padded length;
    ``rows(n_cols, whole)`` the group's lse/delta rows; ``mask(n_cols,
    whole)`` the batch's key-validity row."""
    kw = {} if interpret else {"memory_space": pltpu.VMEM}
    wide = lay.heads * lay.D

    def lanes(rows, role, whole=False):
        at = 0 if role is None else lay.at[role]
        return pl.BlockSpec(
            (1, rows, wide),
            (lambda n, g, i: (n, 0, at + g)) if whole else
            (lambda n, g, i: (n, i, at + g)), **kw)

    def rows(n_cols, whole=False):
        return pl.BlockSpec(
            (lay.heads, 1, n_cols),
            (lambda n, g, i: (n * lay.groups + g, 0, 0)) if whole else
            (lambda n, g, i: (n * lay.groups + g, 0, i)), **kw)

    def mask(n_cols, whole=False):
        return pl.BlockSpec(
            (1, 1, n_cols),
            (lambda n, g, i: (n // lay.mask_rows, 0, 0)) if whole else
            (lambda n, g, i: (n // lay.mask_rows, 0, i)), **kw)

    return lanes, rows, mask


# Everything of a call but its arrays. The two functions that make the
# pallas_calls are jitted on it: the 24 layers of a model then trace and lower
# each kernel once, not 72 kernel bodies of two heads each (a train step's
# set-up read 6 s longer without, PERF.md section 6); XLA inlines the calls.
_STATIC = ("lay", "q_pad", "k_pad", "t_real_k", "causal", "scale", "q_off",
           "k_off", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC + ("bq", "bk"))
def _fwd_pallas_call(qt, kt, vt, *, lay: _Layout, bq, bk, q_pad, k_pad,
                     t_real_k, causal, scale, q_off, k_off, interpret,
                     kmask=None):
    """The shared forward pallas_call (main path and chunked-block path):
    padded q [N, q_pad, .] and k/v [N, k_pad, .] as ``lay`` addresses them
    (one fused array may be all three) -> (out [N, q_pad, groups * lanes],
    [N * H, 1, q_pad] row-layout lse). ``kmask``: optional [B, 1, k_pad]
    f32 key-validity rows, shared by the heads of each batch."""
    N, dtype = qt.shape[0], qt.dtype
    kernel = functools.partial(
        _kernel, lay=lay, block_q=bq, block_k=bk, q_pad=q_pad,
        t_real=t_real_k, t_pad=k_pad, causal=causal, scale=scale,
        q_off=q_off, k_off=k_off, has_kmask=kmask is not None)
    lanes, rows, mask = _specs(lay, interpret)
    in_specs = [lanes(bq, 0), lanes(k_pad, 1, True), lanes(k_pad, 2, True)]
    args = [qt, kt, vt]
    if kmask is not None:
        in_specs.append(mask(k_pad, True))
        args.append(kmask)
    return pl.pallas_call(
        kernel,
        grid=(N, lay.groups, q_pad // bq),
        in_specs=in_specs,
        out_specs=[lanes(bq, None), rows(bq)],
        out_shape=[
            jax.ShapeDtypeStruct((N, q_pad, lay.groups * lay.heads * lay.D),
                                 dtype),
            jax.ShapeDtypeStruct((N * lay.groups * lay.heads, 1, q_pad),
                                 jnp.float32),
        ],
        **_call_kw("fwd", interpret, lay, bq, bk, q_pad, k_pad, dtype,
                   kmask is not None),
    )(*args)


def _pad_km(kmask, k_pad):
    """[B, Tk] key-validity -> [B, 1, k_pad] f32 rows (padding keys 0)."""
    B, Tk = kmask.shape
    km = kmask.astype(jnp.float32).reshape(B, 1, Tk)
    if k_pad != Tk:
        km = jnp.pad(km, ((0, 0), (0, 0), (0, k_pad - Tk)))
    return km


def _self_operands(q, k, v, H):
    """The operands of a self-attention call as the kernels address them:
    ``((qt, kt, vt), lay, (B, T, H, D))``, unpadded. ``q, k, v`` are
    [B, T, H, D]; or ``k`` and ``v`` are None and ``q`` is the fused
    projection [B, T, 3*H*D] (q's heads, then k's, then v's), which the
    three roles then read in place, or split where its heads do not pair up
    into lane blocks."""
    if k is None:
        B, T, W = q.shape
        D = W // (3 * H)
        lay = _layout(H, D, fused=True)
        if not lay.transposed:
            return (q, q, q), lay, (B, T, H, D)
        q, k, v = split_qkv(q, H)
    B, T, H, D = q.shape
    lay = _layout(H, D)
    return tuple(_operand(x, T, lay) for x in (q, k, v)), lay, (B, T, H, D)


def split_qkv(qkv, H):
    """The fused projection [B, T, 3*H*D] as q, k, v [B, T, H, D]."""
    B, T, W = qkv.shape
    return jnp.split(qkv.reshape(B, T, 3 * H, W // (3 * H)), 3, axis=2)


def join_qkv(q, k, v):
    """q, k, v [B, T, H, D] as the fused [B, T, 3*H*D]."""
    B, T = q.shape[:2]
    return jnp.concatenate((q, k, v), axis=2).reshape(B, T, -1)


def _pad_each(ops, t_pad):
    """Every operand padded to ``t_pad`` rows, a fused array once."""
    if ops[0] is ops[1]:
        return (_pad_rows(ops[0], t_pad),) * 3
    return tuple(_pad_rows(x, t_pad) for x in ops)


def _flash_raw(q, k, v, kmask, H, causal: bool, block_q, block_k,
               interpret: bool, with_lse: bool = False):
    """q/k/v: [B, T, H, D] -> [B, T, H, D]; or the fused [B, T, 3*H*D]
    with ``k = v = None`` -> [B, T, H*D] (plus the [B*H, 1, t_pad] row
    logsumexp when ``with_lse``). Forward only. ``kmask``: [B, T] key
    validity or None."""
    fused = k is None
    ops, lay, (B, T, H, D) = _self_operands(q, k, v, H)
    blocks, t_pad, _ = _plan(("fwd",), T, T, D, q.dtype.itemsize,
                             kmask is not None, block_q, block_k, True,
                             lay.heads)
    bq, bk = blocks["fwd"]
    km = _pad_km(kmask, t_pad) if kmask is not None else None
    out, lse = _fwd_pallas_call(
        *_pad_each(ops, t_pad), lay=lay, bq=bq, bk=bk, q_pad=t_pad,
        k_pad=t_pad, t_real_k=T, causal=causal, scale=1.0 / (D ** 0.5),
        q_off=0, k_off=0, interpret=interpret, kmask=km)
    res = _result(out, B, T, H, lay)
    if fused:
        res = res.reshape(B, T, H * D)
    return (res, lse) if with_lse else res


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   lay: _Layout, block_q: int, block_k: int, q_pad: int,
                   t_real_k: int, k_pad: int, causal: bool, scale: float,
                   q_off: int = 0, k_off: int = 0, has_kmask: bool = False):
    """dq for one q-block: dq = scale * sum_k [p * (do@v^T - delta)] @ k,
    p = exp(q@k^T*scale - lse) (FlashAttention-2 backward, eq. dS), once a
    head of the lane block. ``delta`` may already carry the -dlse shift
    (differentiable-lse path: ds = p * (dp - delta + dlse)). Key validity
    uses LOCAL positions vs t_real_k; the causal comparison uses ABSOLUTE
    positions (q_off/k_off — chunked/ring blocks). Optional kmask ref
    [1, 1, k_pad] per batch ANDs into validity, mirroring the forward.
    Zero-padded q rows are not masked: a dq row depends on its own q row
    alone, and the caller slices the padded rows off."""
    if has_kmask:
        km_ref, dq_ref = rest
    else:
        (dq_ref,), km_ref = rest, None
    qi = 0 if q_pad == block_q else pl.program_id(2)
    q_all = q_ref[0]                                             # [bq, lanes]
    do_all = do_ref[0]
    q_pos = q_off + qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)
    out = None
    for h in range(lay.heads):
        lanes = _head_lanes(h, lay)
        q, do = _only(lanes, q_all), _only(lanes, do_all)
        lse = lse_ref[h].reshape(block_q, 1)                     # row -> col
        delta = delta_ref[h].reshape(block_q, 1)

        def body(kb, dq, masked):
            start = _aligned(kb, block_k)
            k = k_ref[0, pl.ds(start, block_k), :]
            v = v_ref[0, pl.ds(start, block_k), :]
            p = jnp.exp(_dot_nt(q, k) * scale - lse)             # [bq, bk]
            valid = None
            if masked:
                k_loc = start + lax.broadcasted_iota(
                    jnp.int32, (1, block_k), 1)
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
            jnp.zeros(q_all.shape, jnp.float32))
        out = _keep(lanes, dq, out)
    dq_ref[0] = (out * scale).astype(dq_ref.dtype)


def _when(cond):
    """``pl.when`` that also takes a Python bool (a grid of one block along
    the axis): the body is then emitted, or not, at trace time."""
    if isinstance(cond, bool):
        return lambda body: body() if cond else None
    return pl.when(cond)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *rest, lay: _Layout, block_q: int, block_k: int,
                    t_real_q: int, t_real_k: int, q_pad: int, k_pad: int,
                    causal: bool, scale: float, q_off: int = 0,
                    k_off: int = 0, has_kmask: bool = False,
                    with_dq: bool = False):
    """dk/dv for one k-block, looping over q-blocks, once a head of the lane
    block: dv = sum_q p^T @ do;  dk = scale * sum_q [p*(do@v^T - delta)]^T @ q.
    Same delta/offset semantics as _bwd_dq_kernel. Optional kmask ref
    [1, 1, block_k] (THIS k-block's validity slice, per batch).

    The tiles are computed TRANSPOSED, keys down the sublanes and queries
    along the lanes (s^T = k @ q^T, [bk, bq]): the forward's row-layout lse
    and delta then broadcast down the tile as they are stored (no
    row -> column relayout per q-block), and p^T @ do and ds^T @ q are
    plain matmuls with nothing to transpose.

    ``with_dq`` (the one-kernel backward, "dqkv"): the same tiles also give
    dq = scale * sum_k ds @ k, added a q tile at a time into a float32
    [q_pad, lanes] accumulator that lives across the k-block grid axis
    (zeroed at the first k block, written at the last: that axis runs in
    order, Mosaic's default). k holds this head's lanes alone, so each
    head's product fills its own columns of dq and the heads simply add.
    Refs after the kmask: dq [1, q_pad, lanes] (whole length, resident),
    dk, dv, then the accumulator."""
    km_ref = rest[0] if has_kmask else None
    outs = rest[1:] if has_kmask else rest
    if with_dq:
        dq_ref, dk_ref, dv_ref, acc_ref = outs
    else:
        (dk_ref, dv_ref), acc_ref = outs, None
    n_kb = k_pad // block_k
    ki = 0 if n_kb == 1 else pl.program_id(2)
    if with_dq:
        @_when(ki == 0)
        def _():
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    k_all = k_ref[0]                                             # [bk, lanes]
    v_all = v_ref[0]
    k_loc = ki * block_k + lax.broadcasted_iota(
        jnp.int32, (block_k, 1), 0)                              # [bk, 1]
    k_valid = (k_loc < t_real_k) if k_pad != t_real_k else None
    if km_ref is not None:
        k_valid = _and(k_valid, km_ref[0].reshape(block_k, 1) > 0)
    zeros = jnp.zeros(k_all.shape, jnp.float32)
    dk_out = dv_out = None
    for h in range(lay.heads):
        lanes = _head_lanes(h, lay)
        k, v = _only(lanes, k_all), _only(lanes, v_all)

        def body(qb, carry, diag, tail):
            dk, dv = carry
            start = _aligned(qb, block_q)
            q = q_ref[0, pl.ds(start, block_q), :]
            do = do_ref[0, pl.ds(start, block_q), :]
            lse = lse_ref[h, :, pl.ds(start, block_q)]           # [1, bq]
            delta = delta_ref[h, :, pl.ds(start, block_q)]
            pt = jnp.exp(_dot_nt(k, q) * scale - lse)            # [bk, bq]
            valid = k_valid
            if diag or tail:
                q_loc = start + lax.broadcasted_iota(
                    jnp.int32, (1, block_q), 1)
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
            if with_dq:
                acc_ref[pl.ds(start, block_q), :] += _dot_tn(dst, k)
            return dk, dv

        dk, dv = _tiles(
            _query_ranges(ki, block_q, block_k, q_pad, k_pad, t_real_q,
                          causal, q_off == k_off), body, (zeros, zeros))
        dk_out, dv_out = _keep(lanes, dk, dk_out), _keep(lanes, dv, dv_out)
    dk_ref[0] = (dk_out * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv_out.astype(dv_ref.dtype)
    if with_dq:
        @_when(ki == n_kb - 1)
        def _():
            dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=_STATIC + (
    "dq_blocks", "dkv_blocks", "dqkv_blocks", "t_real_q"))
def _bwd_pallas_calls(qt, kt, vt, dot, lse, delta, *, lay: _Layout, q_pad,
                      k_pad, t_real_q, t_real_k, causal, scale, q_off, k_off,
                      interpret, kmask=None, dq_blocks=None, dkv_blocks=None,
                      dqkv_blocks=None):
    """The backward's pallas_calls over padded arrays as ``lay`` addresses
    them (``dot`` and the results are arrays of their own: [N, ., groups *
    lanes]); returns padded (dq, dk, dv). ``dqkv_blocks`` (bq, bk): the one
    kernel that gives all three; else ``dq_blocks`` and ``dkv_blocks``, the
    split pair, each kernel at its own (:func:`_bwd_plan` says which).
    ``delta`` may already carry the -dlse shift. ``kmask``: optional
    [B, 1, k_pad] f32 rows, per batch."""
    N, dtype = qt.shape[0], qt.dtype
    lanes, rows, mask = _specs(lay, interpret)
    has_km = kmask is not None
    shared = dict(lay=lay, q_pad=q_pad, k_pad=k_pad, t_real_k=t_real_k,
                  causal=causal, scale=scale, q_off=q_off, k_off=k_off,
                  has_kmask=has_km)
    args = [qt, kt, vt, dot, lse, delta] + ([kmask] if has_km else [])
    width = lay.groups * lay.heads * lay.D

    def by_key_block(bq, bk):
        """What the dk/dv and the one-kernel grids over key blocks read."""
        return [lanes(q_pad, 0, True), lanes(bk, 1), lanes(bk, 2),
                lanes(q_pad, None, True), rows(q_pad, True),
                rows(q_pad, True)] + ([mask(bk)] if has_km else [])

    if dqkv_blocks is not None:
        bq, bk = dqkv_blocks
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, block_q=bq, block_k=bk,
                              t_real_q=t_real_q, with_dq=True, **shared),
            grid=(N, lay.groups, k_pad // bk),
            in_specs=by_key_block(bq, bk),
            out_specs=[lanes(q_pad, None, True), lanes(bk, None),
                       lanes(bk, None)],
            out_shape=[jax.ShapeDtypeStruct((N, q_pad, width), dtype)]
            + [jax.ShapeDtypeStruct((N, k_pad, width), dtype)] * 2,
            scratch_shapes=[pltpu.VMEM((q_pad, lay.heads * lay.D),
                                       jnp.float32)],
            **_call_kw("dqkv", interpret, lay, bq, bk, q_pad, k_pad, dtype,
                       has_km),
        )(*args)
        return dq, dk, dv

    bq, bk = dq_blocks
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=bq, block_k=bk, **shared),
        grid=(N, lay.groups, q_pad // bq),
        in_specs=[lanes(bq, 0), lanes(k_pad, 1, True), lanes(k_pad, 2, True),
                  lanes(bq, None), rows(bq), rows(bq)]
        + ([mask(k_pad, True)] if has_km else []),
        out_specs=lanes(bq, None),
        out_shape=jax.ShapeDtypeStruct((N, q_pad, width), dtype),
        **_call_kw("dq", interpret, lay, bq, bk, q_pad, k_pad, dtype, has_km),
    )(*args)

    bq, bk = dkv_blocks
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=bq, block_k=bk,
                          t_real_q=t_real_q, **shared),
        grid=(N, lay.groups, k_pad // bk),
        in_specs=by_key_block(bq, bk),
        out_specs=[lanes(bk, None), lanes(bk, None)],
        out_shape=[jax.ShapeDtypeStruct((N, k_pad, width), dtype)] * 2,
        **_call_kw("dkv", interpret, lay, bq, bk, q_pad, k_pad, dtype,
                   has_km),
    )(*args)
    return dq, dk, dv


def _row_layout(x2d, B, H, T, t_pad):
    """[B, H, T] f32 -> padded [B*H, 1, t_pad] row layout."""
    r = x2d.reshape(B * H, 1, T).astype(jnp.float32)
    if t_pad != T:
        r = jnp.pad(r, ((0, 0), (0, 0), (0, t_pad - T)))
    return r


def _delta_rows(do, o, dims, t_pad, dlse=None):
    """delta_i = rowsum(do_i * o_i) a head: cheap elementwise XLA, f32, in
    the same [B*H, 1, t_pad] row layout as lse; less ``dlse`` [B, H, T]
    where the logsumexp carries a cotangent of its own. ``do`` and ``o`` are
    [B, T, H*D] or [B, T, H, D]; the sum runs over windows of D lanes of
    the former, so that no [.., H, D] view of a q-sized array is made (XLA
    lays a minor dimension of 64 out T-minor, which costs a copy of it)."""
    B, T, H, D = dims
    prod = (do.reshape(B, T, H * D).astype(jnp.float32)
            * o.reshape(B, T, H * D).astype(jnp.float32))
    heads = (jnp.arange(H * D)[:, None] // D == jnp.arange(H)[None, :])
    delta = jnp.einsum("btc,ch->bht", prod, heads.astype(jnp.float32),
                       precision=lax.Precision.HIGHEST)          # [B, H, T]
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    return _row_layout(delta, B, H, T, t_pad)


def _side_by_side(parts):
    """[B, T, C_i] arrays joined along their last axis, written as the sum of
    their zero-padded copies: XLA fuses that into what reads the join (the
    projection's weight, bias and input gradients), where it writes a
    concatenation of one call's three results out to HBM first (a
    [B, T, 3*H*D] copy a layer: on a v5e about 6 ms of a GPT-2 medium
    float32 training step)."""
    width = sum(x.shape[-1] for x in parts)
    out, at = 0, 0
    for x in parts:
        out = out + jnp.pad(
            x, ((0, 0), (0, 0), (at, width - at - x.shape[-1])))
        at += x.shape[-1]
    return out


def _flash_bwd_pallas(q, k, v, kmask, o, lse, g, H, causal: bool, block_q,
                      block_k, interpret: bool):
    """Blockwise backward: scores are rebuilt in VMEM from q/k/v and the
    forward's row-layout logsumexp — no [T, T] tensor ever reaches HBM.
    Operands as :func:`_flash_raw` takes them; the fused projection's
    cotangent is [dq | dk | dv] along its lanes."""
    fused = k is None
    ops, lay, dims = _self_operands(q, k, v, H)
    B, T, H, D = dims
    blocks, t_pad, _ = _bwd_plan(T, T, D, q.dtype.itemsize,
                                 kmask is not None, block_q, block_k, True,
                                 lay.heads, aligned=True)
    km = _pad_km(kmask, t_pad) if kmask is not None else None
    dq, dk, dv = _bwd_pallas_calls(
        *_pad_each(ops, t_pad), _operand(g.reshape(dims), t_pad, lay), lse,
        _delta_rows(g, o, dims, t_pad), lay=lay, q_pad=t_pad, k_pad=t_pad,
        t_real_q=T, t_real_k=T, causal=causal, scale=1.0 / (D ** 0.5),
        q_off=0, k_off=0, interpret=interpret, kmask=km,
        **_block_kw(blocks))
    if fused and not lay.transposed:
        # joined as [B, T, C] arrays: a [.., H, D] view of them with D = 64
        # would be laid out T-minor and cost a copy of each
        return _side_by_side((dq, dk, dv))[:, :T], None, None
    grads = tuple(_result(x, B, T, H, lay) for x in (dq, dk, dv))
    if fused:
        return join_qkv(*grads), None, None
    return grads


def _reference(q, k, v, causal: bool, kmask=None, scale=None):
    """The same math in plain XLA ops — used by the equivalence tests.
    Matches parallel/ring.py local_attention semantics incl. the
    fully-masked-row clamp. ``v`` may have a width of its own; ``scale``
    defaults to ``1 / sqrt(q's width)``."""
    scale = 1.0 / (q.shape[-1] ** 0.5) if scale is None else scale
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, kmask, H, causal, block_q, block_k, interpret, bwd):
    return _flash_raw(q, k, v, kmask, H, causal, block_q, block_k, interpret)


def _flash_fwd(q, k, v, kmask, H, causal, block_q, block_k, interpret, bwd):
    if bwd == "pallas":
        out, lse = _flash_raw(q, k, v, kmask, H, causal, block_q, block_k,
                              interpret, with_lse=True)
        out = checkpoint_name(out, ATTN_OUT)
        lse = checkpoint_name(lse, ATTN_LSE)
        return out, (q, k, v, kmask, out, lse)
    # the xla fallback exists for memory-constrained cases: don't burden it
    # with the out/lse residuals it never reads
    out = _flash_raw(q, k, v, kmask, H, causal, block_q, block_k, interpret)
    return out, (q, k, v, kmask, None, None)


def _flash_bwd(H, causal, block_q, block_k, interpret, bwd, res, g):
    q, k, v, kmask, o, lse = res
    dkm = (jnp.zeros_like(kmask) if kmask is not None else None)
    if bwd == "pallas":
        return _flash_bwd_pallas(q, k, v, kmask, o, lse, g, H, causal,
                                 block_q, block_k, interpret) + (dkm,)
    # XLA rematerialisation fallback (also the correctness oracle in
    # tests). Chunking is a memory/throughput trade: lax.map serialises
    # chunks (~15% slower at T=2048), so use the dense [T,T] recompute
    # while the f32 score tensor is affordable and switch to q-chunks only
    # when it is not.
    fused = k is None
    if fused:
        q, k, v = split_qkv(q, H)
        g = g.reshape(q.shape)
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
    if fused:
        return join_qkv(dq, dk, dv), None, None, dkm
    return dq, dk, dv, dkm


_flash.defvjp(_flash_fwd, _flash_bwd)


def _checked(kmask, bwd):
    if bwd not in ("pallas", "xla"):
        raise ValueError(f"bwd must be 'pallas' or 'xla', got {bwd!r}")
    # float at the custom_vjp boundary (integer args would need float0
    # cotangents); the bwd returns zeros for it
    return None if kmask is None else jnp.asarray(kmask, jnp.float32)


def flash_attention(q, k, v, *, kmask=None, causal: bool = False,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False, bwd: str = "pallas"):
    """Blockwise flash attention over [B, T, H, D] (differentiable).

    Forward runs the Pallas kernel (never materialises [T, T]); the
    backward is one blockwise Pallas kernel too (grid over k-blocks, dq
    accumulated across them) consuming the forward's logsumexp residual —
    ``bwd="xla"`` selects the rematerialising XLA fallback (the tests'
    correctness oracle). ``interpret=True`` runs the kernels in the Pallas
    interpreter (CPU tests). ``kmask`` [B, T]: key validity (1=real,
    0=padding) shared across heads — the padded/variable-length batch case;
    the kernel loads one [1, block_k] row slice per key block and ANDs it
    into the validity mask, so masked training keeps the flash memory
    envelope."""
    return _flash(q, k, v, _checked(kmask, bwd), q.shape[2], causal, block_q,
                  block_k, interpret, bwd)


def flash_attention_qkv(qkv, n_heads: int, *, kmask=None,
                        causal: bool = False,
                        block_q: Optional[int] = None,
                        block_k: Optional[int] = None,
                        interpret: bool = False, bwd: str = "pallas"):
    """:func:`flash_attention` over the fused projection ``qkv``
    [B, T, 3*H*D] (q's heads, then k's, then v's: what ``x @ Wqkv`` gives)
    -> [B, T, H*D], the layout the output projection takes. Where the heads
    pair up into lane blocks (``heads_per_block``) the kernels read the
    three roles out of the one array and nothing is split or copied; the
    cotangent comes back as one [B, T, 3*H*D] array."""
    return _flash(qkv, None, None, _checked(kmask, bwd), n_heads, causal,
                  block_q, block_k, interpret, bwd)


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
    lay = _layout(H, D)
    blocks, q_pad, k_pad = _plan(("fwd",), Tq, Tk, D, q.dtype.itemsize,
                                 kmask is not None, block_q, block_k,
                                 heads=lay.heads)
    bq, bk = blocks["fwd"]
    km = _pad_km(kmask, k_pad) if kmask is not None else None
    # t_real_k gates KEY validity (Tk, not Tq — the chunk may be shorter);
    # padded q rows emit garbage that is sliced off below
    out, lse = _fwd_pallas_call(
        _operand(q, q_pad, lay), _operand(k, k_pad, lay),
        _operand(v, k_pad, lay), lay=lay, bq=bq, bk=bk, q_pad=q_pad,
        k_pad=k_pad, t_real_k=Tk, causal=causal, scale=1.0 / (D ** 0.5),
        q_off=q_offset, k_off=k_offset, interpret=interpret, kmask=km)
    # fully masked rows: m stays _NEG_BIG so lse = m + log(l) is ~-1e30
    # and the merge weight underflows to 0 (their out is mean(v), see
    # docstring — only the weighted combination is meaningful)
    lse_b = lse[:, 0, :Tq].reshape(B, H, Tq)
    return _result(out, B, Tq, H, lay), lse_b


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
    lay = _layout(H, D)
    blocks, q_pad, k_pad = _bwd_plan(Tq, Tk, D, q.dtype.itemsize,
                                     kmask is not None, block_q, block_k,
                                     False, lay.heads, q_offset == k_offset)
    km = _pad_km(kmask, k_pad) if kmask is not None else None
    dq, dk, dv = _bwd_pallas_calls(
        _operand(q, q_pad, lay), _operand(k, k_pad, lay),
        _operand(v, k_pad, lay), _operand(do, q_pad, lay),
        _row_layout(lse, B, H, Tq, q_pad),
        _delta_rows(do, o, q.shape, q_pad, dlse), lay=lay,
        q_pad=q_pad, k_pad=k_pad, t_real_q=Tq, t_real_k=Tk, causal=causal,
        scale=1.0 / (D ** 0.5), q_off=q_offset, k_off=k_offset,
        interpret=interpret, kmask=km, **_block_kw(blocks))
    dkm = jnp.zeros_like(kmask) if kmask is not None else None
    return (_result(dq, B, Tq, H, lay), _result(dk, B, Tk, H, lay),
            _result(dv, B, Tk, H, lay), dkm)


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
# BlockSpec operand twice and a lane block at 128 lanes (two heads of 64, or
# one where the arrays are transposed). Forward and dq: the WHOLE
# [t_pad, lanes] K and V (4 * t_pad * 128 * itemsize bytes:
# 2 MiB at T = 1024 float32, 8 MiB at T = 8192 bfloat16) beside bq-row
# tiles of q/o (and do/dq) and three to five float32 [bq, bk] score tiles.
# dk/dv: the whole q and do with their lse/delta rows beside bk-row tiles of
# k/v/dk/dv; the one backward kernel adds dq's whole [t_pad, lanes] result
# block and its float32 accumulator (at T = 8192 and 128 float32 lanes,
# 4 MiB of accumulator and twice 4 MiB of result). The chooser keeps that
# under 12 of the default 16 MiB by
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
