"""Weight-stationary fused LSTM scan — the CudnnLSTMHelper analog.

The reference accelerates its LSTMs with a fused cuDNN time loop
(deeplearning4j-cuda/.../CudnnLSTMHelper.java, 612 LoC; shared math in
LSTMHelpers.java:69,393). The TPU-native equivalent here is a Pallas
kernel that runs the WHOLE recurrence in one kernel invocation:

- The input projection x @ Wx + b is hoisted OUTSIDE (one [B*T, F] MXU
  matmul, exactly like the XLA path in nn/layers/recurrent.py).
- The kernel grids over (batch blocks, time chunks). TPU grids execute
  sequentially on a core, so VMEM scratch persists across grid steps: the
  recurrent weights Wh [H, 4H] stay resident in VMEM for the entire
  sequence (index_map pins their block), and the h/c carries live in f32
  scratch — nothing recurrent touches HBM between timesteps. At the bench
  config (H=256 bf16) Wh is 0.5 MB — re-fetched from HBM every scan
  iteration by the XLA path, fetched ONCE here.
- Everything streamed is TIME-MAJOR inside the kernels: a chunk is a
  (tc, bb, 4H) block whose last two dimensions are a whole (8,128)-tiled
  [bb, 4H] slab, so the chunk length tc is free of the TPU tiling rule and
  step t is a leading-dimension index. The mask rides as [T, B, 1] (one
  lane-broadcast per step). The batch-block axis bounds the VMEM working
  set for any B; recurrences of different batch rows are independent.
- Per chunk it writes the h outputs plus the gate/cell residuals the
  backward needs.
- The backward is a second Pallas kernel over the REVERSED chunk grid:
  dh/dc ride in scratch, dWh accumulates in its (pinned, f32) output block
  across the whole grid, dzx streams out per chunk (the cotangent of the
  hoisted input projection — XLA autodiff handles Wx/b from there).

Masking follows the framework's recurrent contract exactly (masked steps
carry state through unchanged and output zeros — nn/layers/recurrent.py
``apply_seq``): the forward blends carries with the mask, the backward
routes carry-through cotangents around the gate path. Sequence padding
(T not a multiple of the chunk) is the same mechanism with mask rows 0.

Gate order is [i, f, g, o] (the framework's LSTM layout; DL4J's
[g, f, o, i] order is permuted at import time by modelimport/dl4j.py).
``interpret=True`` runs both kernels in the Pallas interpreter — the CPU
test path (tests/test_fused_lstm.py asserts equivalence against the
lax.scan oracle, forward and gradients, masked and unmasked);
tests/test_tpu_compile.py compiles both kernels for a v5e topology.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scoped-VMEM ceiling handed to Mosaic (its default is 16 MiB; a v5e core
# has 128 MiB). Resident here: Wh (+ the f32 dWh accumulator in the
# backward) and double-buffered per-chunk streams, sized by _pick_chunk.
_VMEM_LIMIT = 100 * 2 ** 20
_STREAM_BUDGET = 12 * 2 ** 20        # one buffer of per-chunk blocks
_MAX_BATCH_BLOCK = 256
_MAX_CHUNK = 16                      # the chunk loop is fully unrolled

_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)


def _sig(x):
    return jax.nn.sigmoid(x)


def _fwd_kernel(*refs, tc: int, H: int, has_mask: bool, has_peep: bool):
    """One (batch block, time chunk): zx [tc, bb, 4H]; Wh [H, 4H]
    (resident); h0/c0 [bb, H]; optional m [tc, bb, 1]; optional peephole
    [3, H] (GravesLSTM rows p_i, p_f, p_o: c_prev->i,f and c_new->o,
    LSTMHelpers.java:71); outputs hs/cs [tc, bb, H] (post-mask carries),
    gates [tc, bb, 4H] (pre-mask), final carries [bb, H]. h/c persist in
    f32 scratch across the sequential chunk axis."""
    refs = list(refs)
    zx_ref, wh_ref, h0_ref, c0_ref = refs[:4]
    del refs[:4]
    m_ref = refs.pop(0) if has_mask else None
    peep_ref = refs.pop(0) if has_peep else None
    hs_ref, gates_ref, cs_ref, hT_ref, cT_ref, h_scr, c_scr = refs
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)
        c_scr[...] = c0_ref[...].astype(jnp.float32)

    if has_peep:
        # each row read straight from the ref: a (1, H) operand Mosaic
        # broadcasts over sublanes (lane-slicing a loaded [1, 3H] value
        # leaves a layout vector.broadcast rejects)
        p_i = peep_ref[0:1, :].astype(jnp.float32)
        p_f = peep_ref[1:2, :].astype(jnp.float32)
        p_o = peep_ref[2:3, :].astype(jnp.float32)

    def step(t, _):
        h = h_scr[...]
        c = c_scr[...]
        z = zx_ref[t].astype(jnp.float32) + jnp.dot(
            h.astype(wh_ref.dtype), wh_ref[...],
            preferred_element_type=jnp.float32)                # [bb, 4H]
        if has_peep:
            i = _sig(z[:, 0 * H:1 * H] + c * p_i)
            f = _sig(z[:, 1 * H:2 * H] + c * p_f)
            g = jnp.tanh(z[:, 2 * H:3 * H])
            c_new = f * c + i * g
            o = _sig(z[:, 3 * H:4 * H] + c_new * p_o)
        else:
            i = _sig(z[:, 0 * H:1 * H])
            f = _sig(z[:, 1 * H:2 * H])
            g = jnp.tanh(z[:, 2 * H:3 * H])
            o = _sig(z[:, 3 * H:4 * H])
            c_new = f * c + i * g
        h_new = o * jnp.tanh(c_new)
        if has_mask:
            m = m_ref[t].astype(jnp.float32)                   # [bb, 1]
            h_new = m * h_new + (1.0 - m) * h
            c_new = m * c_new + (1.0 - m) * c
        h_scr[...] = h_new
        c_scr[...] = c_new
        hs_ref[t] = h_new.astype(hs_ref.dtype)
        cs_ref[t] = c_new.astype(cs_ref.dtype)
        gates_ref[t] = jnp.concatenate(
            [i, f, g, o], axis=-1).astype(gates_ref.dtype)
        return 0

    lax.fori_loop(0, tc, step, 0, unroll=True)

    @pl.when(ci == pl.num_programs(1) - 1)
    def _final():
        hT_ref[...] = h_scr[...].astype(hT_ref.dtype)
        cT_ref[...] = c_scr[...].astype(cT_ref.dtype)


def _bwd_kernel(*refs, tc: int, H: int, has_mask: bool, has_peep: bool):
    """Reverse-grid chunk: consumes the forward residuals and the output
    cotangent dhs; emits dzx per chunk, dh0/dc0 per batch block (on its
    last grid step = time chunk 0) and dWh (+ dpeephole) accumulated in
    their pinned f32 output blocks across the whole grid. dh/dc persist
    in f32 scratch; the final-carry cotangents seed them (dhT is folded
    into dhs[T-1] by the caller — h_T IS hs[T-1] — and dcT seeds the dc
    scratch here)."""
    refs = list(refs)
    (gates_ref, cs_ref, cprev_ref, hprev_ref, wh_ref,
     dhs_ref, dcT_ref) = refs[:7]
    del refs[:7]
    m_ref = refs.pop(0) if has_mask else None
    peep_ref = refs.pop(0) if has_peep else None
    dzx_ref, dwh_ref, dh0_ref, dc0_ref = refs[:4]
    del refs[:4]
    dpeep_ref = refs.pop(0) if has_peep else None
    dh_scr, dc_scr = refs
    bi = pl.program_id(0)
    ci = pl.program_id(1)

    @pl.when((bi == 0) & (ci == 0))
    def _init_acc():
        dwh_ref[...] = jnp.zeros_like(dwh_ref)
        if has_peep:
            dpeep_ref[...] = jnp.zeros_like(dpeep_ref)

    @pl.when(ci == 0)
    def _init():
        dh_scr[...] = jnp.zeros_like(dh_scr)
        dc_scr[...] = dcT_ref[...].astype(jnp.float32)

    if has_peep:
        p_i = peep_ref[0:1, :].astype(jnp.float32)
        p_f = peep_ref[1:2, :].astype(jnp.float32)
        p_o = peep_ref[2:3, :].astype(jnp.float32)

    def step(k, _):
        t = tc - 1 - k
        gates = gates_ref[t].astype(jnp.float32)
        i = gates[:, 0 * H:1 * H]
        f = gates[:, 1 * H:2 * H]
        g = gates[:, 2 * H:3 * H]
        o = gates[:, 3 * H:4 * H]
        c_t = cs_ref[t].astype(jnp.float32)
        c_prev = cprev_ref[t].astype(jnp.float32)

        # total cotangents on (h_t, c_t): carry + this step's output
        # (the layer's emitted output is hs * m, so its cotangent arrives
        # here already multiplied by m by the caller)
        A = dh_scr[...] + dhs_ref[t].astype(jnp.float32)
        C = dc_scr[...]
        if has_mask:
            m = m_ref[t].astype(jnp.float32)                   # [bb, 1]
            dh_g, dc_g = A * m, C * m      # gate-path share
        else:
            dh_g, dc_g = A, C

        tanh_c = jnp.tanh(c_t)
        do = dh_g * tanh_c * o * (1.0 - o)          # dz_o (a-level)
        dcg = dc_g + dh_g * o * (1.0 - tanh_c * tanh_c)
        if has_peep:
            # o = sig(z_o + c_new * p_o): its c_new dependence feeds dcg
            dcg = dcg + do * p_o
        di = dcg * g * i * (1.0 - i)
        dg = dcg * i * (1.0 - g * g)
        df = dcg * c_prev * f * (1.0 - f)
        dz = jnp.concatenate([di, df, dg, do], axis=-1)       # [bb, 4H]

        dzx_ref[t] = dz.astype(dzx_ref.dtype)
        dz_w = dz.astype(wh_ref.dtype)
        # dWh += h_prev^T dz ; dh_{t-1} = dz Wh^T — contractions over the
        # leading / trailing dims, no materialized transpose
        dwh_ref[...] += lax.dot_general(
            hprev_ref[t].astype(wh_ref.dtype), dz_w,
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dh_new = lax.dot_general(
            dz_w, wh_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dc_new = dcg * f
        if has_mask:
            dh_new = dh_new + A * (1.0 - m)
            dc_new = dc_new + C * (1.0 - m)
        if has_peep:
            # i/f peepholes read c_prev: route their a-level cotangents
            # into dc_{t-1}; accumulate the [3, H] peephole grads
            dc_new = dc_new + di * p_i + df * p_f
            dpeep_ref[0:1, :] += jnp.sum(di * c_prev, axis=0, keepdims=True)
            dpeep_ref[1:2, :] += jnp.sum(df * c_prev, axis=0, keepdims=True)
            dpeep_ref[2:3, :] += jnp.sum(do * c_t, axis=0, keepdims=True)
        dh_scr[...] = dh_new
        dc_scr[...] = dc_new
        return 0

    lax.fori_loop(0, tc, step, 0, unroll=True)

    @pl.when(ci == pl.num_programs(1) - 1)
    def _final():
        dh0_ref[...] = dh_scr[...].astype(dh0_ref.dtype)
        dc0_ref[...] = dc_scr[...].astype(dc0_ref.dtype)


def _pick_batch_block(B: int) -> int:
    """Rows per grid step: the whole batch when it is small, otherwise the
    largest tile-aligned divisor <= _MAX_BATCH_BLOCK (a batch with none
    stays whole)."""
    if B <= _MAX_BATCH_BLOCK:
        return B
    for bb in (256, 128, 64, 32, 16):
        if B % bb == 0:
            return bb
    return B


def _pick_chunk(T: int, bb: int, H: int, itemsize: int) -> int:
    """Time-chunk size: bounded by the VMEM stream budget AND an absolute
    ceiling (the kernels fully unroll the chunk — unbounded tc would blow
    up compile time). Prefers divisors of T (no padding); falls back to
    the padded path when T's divisors are all degenerate (prime T)."""
    # per-timestep block bytes, backward (the larger): gates 4H + cs H +
    # cprev H + hprev H + dzx 4H in the stream dtype, dhs H in f32
    per_t = bb * H * (11 * itemsize + 4)
    cap = max(1, min(_MAX_CHUNK, _STREAM_BUDGET // per_t))
    best = 1
    for tc in range(1, min(T, cap) + 1):
        if T % tc == 0:
            best = tc
    if best >= max(cap // 2, 1) or best == T:
        return best
    return cap  # non-divisor: callers pad T with mask-0 rows


def fits_vmem(B: int, H: int, itemsize: int) -> bool:
    """Whether an upper estimate of the backward kernel's VMEM working set
    at the smallest chunk (tc=1) — double-buffered Wh and f32 dWh, the
    per-step streams, the [bb, 4H] f32 temporaries of one unrolled step —
    is within _VMEM_LIMIT. The layer gate (nn/layers/recurrent.py) sends
    shapes that cannot fit down the lax.scan path."""
    bb = _pick_batch_block(B)
    pinned = 2 * H * 4 * H * (itemsize + 4)
    streams = 2 * bb * H * (11 * itemsize + 4)
    temps = 6 * bb * 4 * H * 4
    return pinned + streams + temps <= _VMEM_LIMIT


def _pad_time(x, T_pad):
    if x.shape[0] == T_pad:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[0] = (0, T_pad - x.shape[0])
    return jnp.pad(x, cfg)


def _blocking(T, B, H, dtype):
    bb = _pick_batch_block(B)
    tc = _pick_chunk(T, bb, H, jnp.dtype(dtype).itemsize)
    return bb, tc, ((T + tc - 1) // tc) * tc


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _fused(zx, wh, h0, c0, mask, peephole, interpret):
    """Time-major core: zx [T, B, 4H], mask [T, B] or None."""
    out, _res = _fused_fwd(zx, wh, h0, c0, mask, peephole, interpret)
    return out


def _fwd_call(zx, wh, h0, c0, m, peephole, interpret, bb, tc):
    T, B, Z = zx.shape
    H = Z // 4
    blk_t = lambda bi, ci: (ci, bi, 0)     # noqa: E731
    blk_b = lambda bi, ci: (bi, 0)         # noqa: E731
    pin = lambda bi, ci: (0, 0)            # noqa: E731
    kernel = functools.partial(_fwd_kernel, tc=tc, H=H,
                               has_mask=m is not None,
                               has_peep=peephole is not None)
    in_specs = [
        pl.BlockSpec((tc, bb, Z), blk_t),
        pl.BlockSpec((H, Z), pin),
        pl.BlockSpec((bb, H), blk_b),
        pl.BlockSpec((bb, H), blk_b),
    ]
    args = [zx, wh, h0, c0]
    if m is not None:
        in_specs.append(pl.BlockSpec((tc, bb, 1), blk_t))
        args.append(m[..., None])
    if peephole is not None:
        in_specs.append(pl.BlockSpec((3, H), pin))
        args.append(peephole.reshape(3, H))
    return pl.pallas_call(
        kernel,
        grid=(B // bb, T // tc),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((tc, bb, H), blk_t),
            pl.BlockSpec((tc, bb, Z), blk_t),
            pl.BlockSpec((tc, bb, H), blk_t),
            pl.BlockSpec((bb, H), blk_b),
            pl.BlockSpec((bb, H), blk_b),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, H), zx.dtype),       # hs (carries)
            # residuals in the INPUT precision: exact f32 when training
            # f32, half-bandwidth when the model is bf16
            jax.ShapeDtypeStruct((T, B, Z), zx.dtype),       # gate residuals
            jax.ShapeDtypeStruct((T, B, H), zx.dtype),       # cell residuals
            jax.ShapeDtypeStruct((B, H), zx.dtype),          # final h
            jax.ShapeDtypeStruct((B, H), zx.dtype),          # final c
        ],
        scratch_shapes=[
            pltpu.VMEM((bb, H), jnp.float32),
            pltpu.VMEM((bb, H), jnp.float32),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="fused_lstm_fwd",
    )(*args)


def _fused_fwd(zx, wh, h0, c0, mask, peephole, interpret):
    T, B, Z = zx.shape
    bb, tc, T_pad = _blocking(T, B, Z // 4, zx.dtype)
    m = None if mask is None else mask.astype(zx.dtype)
    if m is None and T_pad != T:
        m = jnp.ones((T, B), zx.dtype)
    if m is not None:
        m = _pad_time(m, T_pad)        # padded steps: mask 0 = carry freeze
    hs, gates, cs, hT, cT = _fwd_call(_pad_time(zx, T_pad), wh, h0, c0, m,
                                      peephole, interpret, bb, tc)
    hs = hs[:T]
    out = hs * mask.astype(hs.dtype)[..., None] if mask is not None else hs
    # zx itself is NOT a backward residual: the gates carry everything the
    # reverse sweep needs (keeping zx alive would hold an extra [T,B,4H]
    # HBM buffer across the step for nothing)
    return ((out, (hT, cT)),
            (gates[:T], wh, h0, c0, mask, peephole, hs, cs[:T]))


def _bwd_call(gates, cs, cprev, hprev, wh, dhs, dcT, m, peephole,
              interpret, bb, tc):
    T, B, Z = gates.shape
    H = Z // 4
    n_chunks = T // tc
    rev_t = lambda bi, ci: (n_chunks - 1 - ci, bi, 0)   # noqa: E731
    blk_b = lambda bi, ci: (bi, 0)                      # noqa: E731
    pin = lambda bi, ci: (0, 0)                         # noqa: E731
    has_peep = peephole is not None
    kernel = functools.partial(_bwd_kernel, tc=tc, H=H,
                               has_mask=m is not None, has_peep=has_peep)
    in_specs = [
        pl.BlockSpec((tc, bb, Z), rev_t),
        pl.BlockSpec((tc, bb, H), rev_t),
        pl.BlockSpec((tc, bb, H), rev_t),
        pl.BlockSpec((tc, bb, H), rev_t),
        pl.BlockSpec((H, Z), pin),
        pl.BlockSpec((tc, bb, H), rev_t),
        pl.BlockSpec((bb, H), blk_b),
    ]
    args = [gates, cs, cprev, hprev, wh, dhs, dcT]
    if m is not None:
        in_specs.append(pl.BlockSpec((tc, bb, 1), rev_t))
        args.append(m[..., None])
    if has_peep:
        in_specs.append(pl.BlockSpec((3, H), pin))
        args.append(peephole.reshape(3, H))
    out_specs = [
        pl.BlockSpec((tc, bb, Z), rev_t),
        pl.BlockSpec((H, Z), pin),
        pl.BlockSpec((bb, H), blk_b),
        pl.BlockSpec((bb, H), blk_b),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((T, B, Z), gates.dtype),    # dzx
        jax.ShapeDtypeStruct((H, Z), jnp.float32),       # dWh
        jax.ShapeDtypeStruct((B, H), jnp.float32),       # dh0
        jax.ShapeDtypeStruct((B, H), jnp.float32),       # dc0
    ]
    if has_peep:
        out_specs.append(pl.BlockSpec((3, H), pin))
        out_shape.append(jax.ShapeDtypeStruct((3, H), jnp.float32))
    return pl.pallas_call(
        kernel,
        grid=(B // bb, n_chunks),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bb, H), jnp.float32),
            pltpu.VMEM((bb, H), jnp.float32),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="fused_lstm_bwd",
    )(*args)


def _fused_bwd(interpret, res, cts):
    (dout, (dhT, dcT)) = cts
    gates, wh, h0, c0, mask, peephole, hs, cs = res
    T, B, Z = gates.shape
    H = Z // 4
    bb, tc, T_pad = _blocking(T, B, H, hs.dtype)   # hs is in zx's dtype

    m = None if mask is None else mask.astype(jnp.float32)
    # the layer output is hs * m: fold m into the output cotangent
    dhs = dout.astype(jnp.float32)
    if m is not None:
        dhs = dhs * m[..., None]
    # the final-carry cotangents enter the reverse sweep exactly: h_T IS
    # hs[T-1] (post-mask), so dhT folds into the last timestep's dhs
    # row (the kernel adds dhs[t] to the carry WITHOUT the mask factor);
    # dcT seeds the kernel's dc scratch at the first reverse chunk.
    dhs = dhs.at[T - 1].add(dhT.astype(jnp.float32))
    # shifted carries: value entering step t
    hprev = jnp.concatenate([h0.astype(hs.dtype)[None], hs[:-1]], 0)
    cprev = jnp.concatenate([c0.astype(cs.dtype)[None], cs[:-1]], 0)
    if m is None and T_pad != T:
        m = jnp.ones((T, B), jnp.float32)

    pad = lambda a: _pad_time(a, T_pad)   # noqa: E731
    outs = _bwd_call(
        pad(gates), pad(cs), pad(cprev), pad(hprev), wh, pad(dhs),
        dcT.astype(jnp.float32), None if m is None else pad(m), peephole,
        interpret, bb, tc)
    dzx, dwh, dh0, dc0 = outs[:4]
    dpeep = outs[4].reshape(3 * H).astype(peephole.dtype) \
        if peephole is not None else None
    return dzx[:T], dwh.astype(wh.dtype), \
        dh0.astype(h0.dtype), dc0.astype(c0.dtype), \
        (jnp.zeros_like(mask) if mask is not None else None), dpeep


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_lstm(zx, wh, h0, c0, mask=None, peephole=None, *,
               interpret: bool = False):
    """Weight-stationary LSTM recurrence over precomputed input rows.

    zx: [B, T, 4H] (= x @ Wx + b, gate order [i, f, g, o]);
    wh: [H, 4H]; h0/c0: [B, H]; mask: optional [B, T] (masked steps carry
    state through and output zeros — the framework's recurrent contract);
    peephole: optional [3H] = [p_i | p_f | p_o] (GravesLSTM: c_prev feeds
    i and f, c_new feeds o — LSTMHelpers.java:71).
    Returns (outputs [B, T, H], (h_T, c_T)). Differentiable (custom VJP,
    blockwise Pallas backward); BOTH final-carry cotangents are exact —
    dhT folds into the last timestep's output row, dcT seeds the reverse
    sweep's dc scratch (test_fused_lstm.py differentiates through both).
    The kernels are time-major; the two swapaxes here are the same ones
    the lax.scan path pays.
    """
    if mask is not None:
        mask = jnp.swapaxes(jnp.asarray(mask, jnp.float32), 0, 1)
    out, carry = _fused(jnp.swapaxes(zx, 0, 1), wh, h0, c0, mask, peephole,
                        interpret)
    return jnp.swapaxes(out, 0, 1), carry
