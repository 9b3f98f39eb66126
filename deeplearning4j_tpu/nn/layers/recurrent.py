"""Recurrent layers: LSTM, GravesLSTM (peepholes), SimpleRnn, Bidirectional,
LastTimeStep, MaskZero, RnnOutputLayer.

Reference parity: the shared fwd/bwd in
/root/reference/deeplearning4j-nn/src/main/java/org/deeplearning4j/nn/layers/recurrent/LSTMHelpers.java:69,393
(used by LSTM / GravesLSTM / GravesBidirectionalLSTM) and the cuDNN fused
path (CudnnLSTMHelper.java). TPU-native design: the time loop is a single
``lax.scan`` whose body is one fused [x,h] @ W matmul on the MXU; backward
comes from autodiff of the scan (XLA keeps the whole unrolled graph on
device — no per-timestep kernel dispatch).

Layout: [batch, time, features] (the reference uses [batch, features, time]).
Masking: mask [batch, time] — masked steps pass the carry through unchanged
and output zeros, matching the reference's masked RNN semantics.

Streaming/tBPTT: every recurrent layer exposes
``initial_carry(batch)`` and ``apply_seq(params, x, carry, mask) ->
(out, new_carry)`` so truncated BPTT is scan-over-chunks with carried state
(SURVEY.md §5.7) and ``rnnTimeStep`` is a one-step call with a stored carry.
"""

from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn import initializers, losses
from deeplearning4j_tpu.nn.config import FeedForwardLayerConfig, LayerConfig, register_layer
from deeplearning4j_tpu.nn.input_type import InputType


def _mask_step(mask_t, new, old):
    """Where mask_t==0, keep `old`; else `new`. mask_t: [batch]."""
    m = mask_t[:, None]
    return jnp.where(m > 0, new, old)


_FUSED_SUPPRESS_DEPTH = 0


def _fused_suppressed() -> bool:
    return _FUSED_SUPPRESS_DEPTH > 0


@contextmanager
def no_fused_lstm():
    """Trace-time guard: contexts whose SPMD machinery cannot host a
    pallas_call (GPipe's vma-checked rank switch) wrap their step tracing
    in this to force the lax.scan path regardless of policy."""
    global _FUSED_SUPPRESS_DEPTH
    _FUSED_SUPPRESS_DEPTH += 1
    try:
        yield
    finally:
        _FUSED_SUPPRESS_DEPTH -= 1


@dataclass
class BaseRecurrent(FeedForwardLayerConfig):
    """Common recurrent scaffolding."""

    # True for layers with a time-stepped carry (LSTM/SimpleRnn...): enables
    # tBPTT chunking and rnnTimeStep streaming through the model.
    SUPPORTS_CARRY = True

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def initial_carry(self, batch: int, dtype=jnp.float32):
        raise NotImplementedError

    def _cell(self, params, x_t, carry):
        """One timestep: (params, x_t [b,f], carry) -> new_carry. Default:
        project the single row and delegate to ``_cell_from_proj`` (cells
        that define ``_input_proj`` get this for free; others override)."""
        proj = self._input_proj(params, x_t)
        if proj is None:
            raise NotImplementedError(
                f"{type(self).__name__} must implement _cell or _input_proj")
        return self._cell_from_proj(params, proj, carry)

    def _input_proj(self, params, x):
        """Optional TPU fast path: project the WHOLE [b,t,f] input in one
        [b*t,f]x[f,Z] MXU matmul up front; the scan then consumes the
        precomputed rows via ``_cell_from_proj`` and only runs the recurrent
        [b,h]x[h,Z] matmul per step. Return None to scan raw inputs."""
        return None

    def _cell_from_proj(self, params, zx_t, carry):
        """One timestep from a precomputed input projection row."""
        raise NotImplementedError

    def _carry_output(self, carry):
        """Extract the per-step output h from the carry."""
        return carry

    def apply_seq(self, params, x, carry, mask=None):
        """Shared scan scaffolding: [b,t,f] -> ([b,t,h], final_carry).

        Masked steps pass the carry through unchanged and emit zeros — the
        single implementation of the reference's masked-RNN semantics, used
        by every recurrent cell via the ``_cell``/``_cell_from_proj`` hooks."""
        zx = self._input_proj(params, x)
        if zx is not None:
            stream = zx
            cell = lambda c, v: self._cell_from_proj(params, v, c)
        else:
            stream = x
            cell = lambda c, v: self._cell(params, v, c)

        # tie the carry's device-varying axes to x's: inside shard_map
        # (GPipe stages, ring shards) a constant-zeros carry is unvarying
        # while the scan body's outputs vary over the mesh axes — lax.scan
        # rejects the carry type change. The zero-valued add is free after
        # XLA folding but carries the vma annotation.
        vtie = jnp.sum(x[..., :1]) * 0
        carry = jax.tree_util.tree_map(
            lambda c: c + vtie.astype(c.dtype), carry)

        def step(c, inp):
            v_t, m_t = inp if mask is not None else (inp, None)
            new_c = cell(c, v_t)
            if m_t is not None:
                new_c = jax.tree_util.tree_map(
                    lambda n, o: _mask_step(m_t, n, o), new_c, c
                )
                out = self._carry_output(new_c) * m_t[:, None]
            else:
                out = self._carry_output(new_c)
            return new_c, out

        xs = jnp.swapaxes(stream, 0, 1)  # [time, batch, feat] for scan
        # Scan unroll, overridable via DL4J_TPU_RNN_UNROLL. Round-4 honest
        # re-measure (fresh-process A/B, value-fetch sync): unroll 1/8/50
        # all land within run-to-run noise (~1.8-2.0M tokens/s on the
        # char-RNN bench) — the round-3 "+46% at unroll=8" was a phantom of
        # the sync-elision measurement bug.
        # Default kept at 8: never measured worse, bounds compile time.
        import os as _os

        cap = int(_os.environ.get("DL4J_TPU_RNN_UNROLL", "8"))
        unroll = max(1, min(cap, xs.shape[0]))
        if mask is not None:
            ms = jnp.swapaxes(mask.astype(x.dtype), 0, 1)
            final, outs = lax.scan(step, carry, (xs, ms), unroll=unroll)
        else:
            final, outs = lax.scan(step, carry, xs, unroll=unroll)
        return jnp.swapaxes(outs, 0, 1), final

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        carry = self.initial_carry(x.shape[0], x.dtype)
        y, _ = self.apply_seq(params, x, carry, mask)
        return y, state


@register_layer("lstm")
@dataclass
class LSTM(BaseRecurrent):
    """Standard (non-peephole) LSTM — parity with nn/conf/layers/LSTM.java.

    Gate order in the fused kernel: [i, f, g, o] (Keras order, which makes
    Keras h5 import a pure reshape). DL4J's forgetGateBiasInit default of 1.0
    is kept.
    """

    activation: Any = "tanh"
    gate_activation: Any = "sigmoid"
    forget_gate_bias_init: float = 1.0

    def init(self, key, input_type, dtype=jnp.float32):
        n_in = self.n_in if self.n_in is not None else input_type.size
        H = self.n_out
        kx, kh = jax.random.split(key)
        Wx = initializers.initialize(self.weight_init, kx, (n_in, 4 * H), n_in, H, dtype)
        Wh = initializers.initialize(self.weight_init, kh, (H, 4 * H), H, H, dtype)
        b = jnp.zeros((4 * H,), dtype)
        # forget-gate block is the second quarter [H:2H]
        b = b.at[H : 2 * H].set(self.forget_gate_bias_init)
        return {"Wx": Wx, "Wh": Wh, "b": b}

    def initial_carry(self, batch: int, dtype=jnp.float32):
        H = self.n_out
        return (jnp.zeros((batch, H), dtype), jnp.zeros((batch, H), dtype))

    def _carry_output(self, carry):
        return carry[0]

    def _input_proj(self, params, x):
        return x @ params["Wx"] + params["b"]

    def _fused_eligible(self) -> bool:
        """The weight-stationary Pallas scan (ops/fused_lstm.py — the
        CudnnLSTMHelper analog) covers the standard and peephole cells
        with default activations and a lane-aligned hidden width."""
        return (self.activation == "tanh"
                and self.gate_activation == "sigmoid"
                and self.n_out % 128 == 0
                and type(self) in (LSTM, GravesLSTM))

    def apply_seq(self, params, x, carry, mask=None):
        import os as _os

        from deeplearning4j_tpu.ops.fused_lstm import fits_vmem, fused_lstm
        from deeplearning4j_tpu.parallel.context import partitioning_mesh

        policy = _os.environ.get("DL4J_TPU_FUSED_LSTM", "auto")
        on_tpu = jax.default_backend() == "tpu"
        # auto: on the TPU, for shapes whose resident set fits VMEM, and not
        # under a multi-device mesh — GSPMD cannot partition a Mosaic kernel
        # (the forced "1" skips these checks and surfaces the compiler's
        # own error)
        auto = policy == "auto" and on_tpu \
            and partitioning_mesh() is None and fits_vmem(
                x.shape[0], self.n_out, jnp.dtype(x.dtype).itemsize)
        use_fused = (policy == "1" or auto) \
            and self._fused_eligible() and not _fused_suppressed()
        if not use_fused:
            return super().apply_seq(params, x, carry, mask)

        zx = self._input_proj(params, x)
        h0, c0 = carry
        out, (hT, cT) = fused_lstm(zx, params["Wh"], h0, c0, mask,
                                   params.get("peephole"),
                                   interpret=not on_tpu)
        return out, (hT, cT)

    def _cell_from_proj(self, params, zx_t, carry):
        from deeplearning4j_tpu.nn import activations as A

        h, cell = carry
        H = self.n_out
        gate = A.get(self.gate_activation)
        act = A.get(self.activation)
        z = zx_t + h @ params["Wh"]
        i = gate(z[:, 0 * H : 1 * H])
        f = gate(z[:, 1 * H : 2 * H])
        g = act(z[:, 2 * H : 3 * H])
        o = gate(z[:, 3 * H : 4 * H])
        new_cell = f * cell + i * g
        new_h = o * act(new_cell)
        return (new_h, new_cell)



@register_layer("graves_lstm")
@dataclass
class GravesLSTM(LSTM):
    """LSTM with peephole connections — parity with GravesLSTM.java
    (LSTMHelpers.java applies peepholes from c_{t-1} to i,f and c_t to o)."""

    def init(self, key, input_type, dtype=jnp.float32):
        params = super().init(key, input_type, dtype)
        H = self.n_out
        params["peephole"] = jnp.zeros((3 * H,), dtype)  # [p_i, p_f, p_o]
        return params

    def _cell_from_proj(self, params, zx_t, carry):
        from deeplearning4j_tpu.nn import activations as A

        h, cell = carry
        H = self.n_out
        act = A.get(self.activation)
        gate = A.get(self.gate_activation)
        p = params["peephole"]
        p_i, p_f, p_o = p[:H], p[H : 2 * H], p[2 * H :]
        z = zx_t + h @ params["Wh"]
        i = gate(z[:, 0 * H : 1 * H] + cell * p_i)
        f = gate(z[:, 1 * H : 2 * H] + cell * p_f)
        g = act(z[:, 2 * H : 3 * H])
        new_cell = f * cell + i * g
        o = gate(z[:, 3 * H : 4 * H] + new_cell * p_o)
        new_h = o * act(new_cell)
        return (new_h, new_cell)



@register_layer("gru")
@dataclass
class GRU(BaseRecurrent):
    """Gated recurrent unit. Gate order [z, r, h] and the ``reset_after``
    switch follow Keras (cuDNN-compatible variant when True, the default) so
    h5 import is a direct weight copy; early DL4J shipped a (since-removed)
    GRU layer — this restores the capability TPU-first with the same
    hoisted-input-projection scan as LSTM."""

    activation: Any = "tanh"
    gate_activation: Any = "sigmoid"
    reset_after: bool = True

    def init(self, key, input_type, dtype=jnp.float32):
        n_in = self.n_in if self.n_in is not None else input_type.size
        H = self.n_out
        kx, kh = jax.random.split(key)
        p = {
            "Wx": initializers.initialize(self.weight_init, kx, (n_in, 3 * H),
                                          n_in, H, dtype),
            "Wh": initializers.initialize(self.weight_init, kh, (H, 3 * H),
                                          H, H, dtype),
            "b_in": jnp.zeros((3 * H,), dtype),
        }
        if self.reset_after:
            # separate recurrent bias exists ONLY in the reset_after variant
            # (Keras parity; without it b_rec would be redundant with b_in)
            p["b_rec"] = jnp.zeros((3 * H,), dtype)
        return p

    def initial_carry(self, batch: int, dtype=jnp.float32):
        return jnp.zeros((batch, self.n_out), dtype)

    def _input_proj(self, params, x):
        return x @ params["Wx"] + params["b_in"]

    def _cell_from_proj(self, params, zx_t, carry):
        from deeplearning4j_tpu.nn import activations as A

        h = carry
        H = self.n_out
        gate = A.get(self.gate_activation)
        act = A.get(self.activation)
        if self.reset_after:
            rec = h @ params["Wh"] + params["b_rec"]
            z = gate(zx_t[:, :H] + rec[:, :H])
            r = gate(zx_t[:, H:2 * H] + rec[:, H:2 * H])
            hh = act(zx_t[:, 2 * H:] + r * rec[:, 2 * H:])
        else:
            rec_zr = h @ params["Wh"][:, :2 * H]
            z = gate(zx_t[:, :H] + rec_zr[:, :H])
            r = gate(zx_t[:, H:2 * H] + rec_zr[:, H:])
            hh = act(zx_t[:, 2 * H:] + (r * h) @ params["Wh"][:, 2 * H:])
        return z * h + (1.0 - z) * hh


@register_layer("simple_rnn")
@dataclass
class SimpleRnn(BaseRecurrent):
    """Elman RNN: h_t = act(x_t Wx + h_{t-1} Wh + b) (SimpleRnn.java)."""

    activation: Any = "tanh"

    def init(self, key, input_type, dtype=jnp.float32):
        n_in = self.n_in if self.n_in is not None else input_type.size
        H = self.n_out
        kx, kh = jax.random.split(key)
        return {
            "Wx": initializers.initialize(self.weight_init, kx, (n_in, H), n_in, H, dtype),
            "Wh": initializers.initialize(self.weight_init, kh, (H, H), H, H, dtype),
            "b": jnp.full((H,), self.bias_init, dtype),
        }

    def initial_carry(self, batch: int, dtype=jnp.float32):
        return jnp.zeros((batch, self.n_out), dtype)

    def _input_proj(self, params, x):
        return x @ params["Wx"] + params["b"]

    def _cell_from_proj(self, params, zx_t, carry):
        return self.activation_fn()(zx_t + carry @ params["Wh"])



@register_layer("bidirectional")
@dataclass
class Bidirectional(LayerConfig):
    """Bidirectional wrapper (conf/layers/recurrent/Bidirectional.java +
    GravesBidirectionalLSTM): runs the wrapped RNN forward and over the
    time-reversed sequence, combining with CONCAT | ADD | MUL | AVERAGE."""

    rnn: Optional[LayerConfig] = None
    mode: str = "concat"

    def output_type(self, input_type: InputType) -> InputType:
        inner = self.rnn.output_type(input_type)
        if self.mode == "concat":
            return InputType.recurrent(inner.size * 2, inner.timesteps)
        return inner

    def init(self, key, input_type, dtype=jnp.float32):
        kf, kb = jax.random.split(key)
        return {
            "fwd": self.rnn.init(kf, input_type, dtype),
            "bwd": self.rnn.init(kb, input_type, dtype),
        }

    def nested_param_layers(self) -> dict:
        return {"fwd": self.rnn, "bwd": self.rnn}

    def regularization_penalty(self, params):
        pen = super().regularization_penalty(params)
        return pen + self.rnn.regularization_penalty(params["fwd"]) + \
            self.rnn.regularization_penalty(params["bwd"])

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        # Input dropout: honor both the wrapper's and the wrapped RNN's
        # configured dropout (apply_seq bypasses BaseRecurrent.apply) with
        # independent rng streams.
        if rng is not None:
            rng, rng2 = jax.random.split(rng)
        else:
            rng2 = None
        x = self.maybe_dropout_input(x, train, rng)
        if train and self.rnn.dropout > 0.0:
            x = self.rnn.maybe_dropout_input(x, train, rng2)
        carry_f = self.rnn.initial_carry(x.shape[0], x.dtype)
        carry_b = self.rnn.initial_carry(x.shape[0], x.dtype)
        yf, _ = self.rnn.apply_seq(params["fwd"], x, carry_f, mask)
        xr = jnp.flip(x, axis=1)
        mr = jnp.flip(mask, axis=1) if mask is not None else None
        yb, _ = self.rnn.apply_seq(params["bwd"], xr, carry_b, mr)
        yb = jnp.flip(yb, axis=1)
        if self.mode == "concat":
            return jnp.concatenate([yf, yb], axis=-1), state
        if self.mode == "add":
            return yf + yb, state
        if self.mode == "mul":
            return yf * yb, state
        if self.mode in ("average", "avg"):
            return 0.5 * (yf + yb), state
        raise ValueError(f"Unknown Bidirectional mode '{self.mode}'")


@register_layer("last_time_step")
@dataclass
class LastTimeStep(LayerConfig):
    """Wraps an RNN layer, returning only the last (unmasked) timestep
    (recurrent/LastTimeStepLayer.java): [b,t,f] -> [b,f]."""

    rnn: Optional[LayerConfig] = None

    def output_type(self, input_type: InputType) -> InputType:
        inner = self.rnn.output_type(input_type)
        return InputType.feed_forward(inner.size)

    def init(self, key, input_type, dtype=jnp.float32):
        return self.rnn.init(key, input_type, dtype)

    def regularization_penalty(self, params):
        return super().regularization_penalty(params) + self.rnn.regularization_penalty(params)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        y, _ = self.rnn.apply(params, {}, x, train=train, rng=rng, mask=mask)
        if mask is None:
            out = y[:, -1, :]
        else:
            # last index where mask==1 (handles left-padded/ALIGN_END masks,
            # not just contiguous-from-t0)
            T = y.shape[1]
            rev = jnp.flip(mask > 0, axis=1)
            idx = (T - 1 - jnp.argmax(rev, axis=1)).astype(jnp.int32)
            out = jnp.take_along_axis(y, idx[:, None, None], axis=1)[:, 0, :]
        return out, state

    def propagate_mask(self, mask, input_type):
        return None


@register_layer("bidir_last_time_step")
@dataclass
class BidirectionalLastTimeStep(LayerConfig):
    """Keras ``Bidirectional(rnn, return_sequences=False)`` semantics over a
    wrapped :class:`Bidirectional` (concat mode): the forward half's LAST
    step concatenated with the backward half's step 0 — which is the
    backward RNN's final state, since Bidirectional flips the backward
    output back to input time order. A plain LastTimeStep would wrongly
    take the backward half at t=T-1 (one step of context)."""

    rnn: Optional[LayerConfig] = None  # a Bidirectional, mode="concat"

    def output_type(self, input_type: InputType) -> InputType:
        inner = self.rnn.output_type(input_type)
        return InputType.feed_forward(inner.size)

    def init(self, key, input_type, dtype=jnp.float32):
        if getattr(self.rnn, "mode", "concat") != "concat":
            raise ValueError(
                "BidirectionalLastTimeStep requires mode='concat' (merged "
                "fwd/bwd halves are not separable for other modes)")
        return self.rnn.init(key, input_type, dtype)

    def regularization_penalty(self, params):
        return super().regularization_penalty(params) + self.rnn.regularization_penalty(params)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        y, _ = self.rnn.apply(params, {}, x, train=train, rng=rng, mask=mask)
        H = y.shape[-1] // 2
        if mask is None:
            return jnp.concatenate([y[:, -1, :H], y[:, 0, H:]], axis=-1), state
        # masked: fwd half at the LAST valid step, bwd half at the FIRST
        # valid step (= the backward RNN's final state after flip-back;
        # masked steps emit zeros, so the literal endpoints would be wrong
        # for padded sequences)
        T = y.shape[1]
        rev = jnp.flip(mask > 0, axis=1)
        last_idx = (T - 1 - jnp.argmax(rev, axis=1)).astype(jnp.int32)
        first_idx = jnp.argmax(mask > 0, axis=1).astype(jnp.int32)
        fwd = jnp.take_along_axis(
            y[..., :H], last_idx[:, None, None], axis=1)[:, 0, :]
        bwd = jnp.take_along_axis(
            y[..., H:], first_idx[:, None, None], axis=1)[:, 0, :]
        return jnp.concatenate([fwd, bwd], axis=-1), state

    def propagate_mask(self, mask, input_type):
        return None


@register_layer("mask_zero")
@dataclass
class MaskZero(LayerConfig):
    """Derives a mask from timesteps equal to `mask_value` and applies the
    wrapped RNN with it (recurrent/MaskZeroLayer.java)."""

    rnn: Optional[LayerConfig] = None
    mask_value: float = 0.0

    def output_type(self, input_type: InputType) -> InputType:
        return self.rnn.output_type(input_type)

    def init(self, key, input_type, dtype=jnp.float32):
        return self.rnn.init(key, input_type, dtype)

    def regularization_penalty(self, params):
        return super().regularization_penalty(params) + self.rnn.regularization_penalty(params)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        derived = jnp.any(x != self.mask_value, axis=-1).astype(x.dtype)
        if mask is not None:
            derived = derived * mask
        return self.rnn.apply(params, state, x, train=train, rng=rng, mask=derived)


@register_layer("rnn_output")
@dataclass
class RnnOutputLayer(BaseRecurrent):
    """Time-distributed output layer (RnnOutputLayer.java): dense+loss applied
    at every timestep of [batch, time, feat]."""

    SUPPORTS_CARRY = False  # no recurrence of its own

    activation: Any = "softmax"
    loss: Any = "mcxent"
    has_bias: bool = True

    def init(self, key, input_type, dtype=jnp.float32):
        n_in = self.n_in if self.n_in is not None else input_type.size
        kW, _ = jax.random.split(key)
        params = {
            "W": initializers.initialize(self.weight_init, kW, (n_in, self.n_out), n_in, self.n_out, dtype)
        }
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return params

    def preactivation(self, params, x):
        y = x @ params["W"]
        if self.has_bias:
            y = y + params["b"]
        return y

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        y = self.activation_fn()(self.preactivation(params, x))
        if mask is not None:
            y = y * mask[..., None]
        return y, state

    def score(self, params, x, labels, mask=None, average=True, weights=None):
        preact = self.preactivation(params, x)
        if average:
            return losses.average_score(self.loss, labels, preact, self.activation, mask, weights)
        return losses.per_example_scores(self.loss, labels, preact, self.activation, mask, weights)
