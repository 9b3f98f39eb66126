"""Normalization layers: BatchNorm, LRN.

Reference parity: nn/conf/layers/BatchNormalization.java +
nn/layers/normalization/{BatchNormalization,LocalResponseNormalization}.java
and their cuDNN helpers (CudnnBatchNormalizationHelper.java). On TPU these
are plain fused elementwise/reduction graphs; running statistics live in the
non-trainable ``state`` pytree (the flax ``batch_stats`` pattern) rather
than being updated in-place.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.config import LayerConfig, register_layer
from deeplearning4j_tpu.nn.input_type import InputType


@register_layer("batch_norm")
@dataclass
class BatchNorm(LayerConfig):
    """Batch normalization over the channel/feature axis (last axis, NHWC).

    DL4J defaults (BatchNormalization.java): decay=0.9 ('momentum' of the
    running stats EMA), eps=1e-5, lockGammaBeta=False.
    """

    CONSUMES_EXAMPLE_WEIGHT = True  # batch stats must exclude padded rows

    decay: float = 0.9
    eps: float = 1e-5
    use_gamma_beta: bool = True   # lockGammaBeta=True in DL4J means fixed 1/0
    gamma_init: float = 1.0
    beta_init: float = 0.0

    def _nfeat(self, input_type: InputType) -> int:
        return input_type.channels if input_type.kind == "conv" else input_type.flat_size()

    def init(self, key, input_type, dtype=jnp.float32):
        n = self._nfeat(input_type)
        if not self.use_gamma_beta:
            return {}
        return {
            "gamma": jnp.full((n,), self.gamma_init, dtype),
            "beta": jnp.full((n,), self.beta_init, dtype),
        }

    def init_state(self, input_type: InputType):
        n = self._nfeat(input_type)
        return {
            "mean": jnp.zeros((n,), jnp.float32),
            "var": jnp.ones((n,), jnp.float32),
        }

    def apply(self, params, state, x, *, train=False, rng=None, mask=None,
              ex_weight=None):
        # Statistics in f32 (bf16 means/variances lose mantissa over real
        # batch sizes), but the NORMALIZATION is a per-channel scale/shift
        # folded to two [C] vectors and applied in the input dtype — so for
        # bf16 models the full activation tensor is never upcast and the
        # residuals XLA saves for backward stay bf16 (half the HBM traffic
        # of normalizing in f32).
        dt = x.dtype
        f32 = jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float16) else x.dtype
        axes = tuple(range(x.ndim - 1))  # all but channel/feature axis
        if train:
            # Shifted two-pass statistics with f32 ACCUMULATION but no f32
            # copy of the tensor in the autodiff graph: the mean is an
            # f32-accumulated reduction of x, the variance an f32-accumulated
            # reduction of the model-dtype residual squared — backward stays
            # in the model dtype, and the shifted form avoids the E[x^2]
            # cancellation that breaks channels with |mean| >> std. Both
            # branches use the same form so the DP-padded weighted step
            # reproduces the unpadded single-device statistics exactly.
            if ex_weight is not None:
                # Example-weighted statistics: rows with weight 0 (the
                # ParallelWrapper padding rows) contribute nothing to
                # mean/var. 0/1 weights are exact in every dtype, so casting
                # w to the model dtype keeps the math bit-equal while
                # avoiding an f32 promotion of x.
                w = ex_weight.reshape((x.shape[0],) + (1,) * (x.ndim - 1)).astype(dt)
                spatial = 1
                for d in x.shape[1:-1]:
                    spatial *= d
                denom = jnp.maximum(
                    jnp.sum(w, dtype=f32) * spatial, jnp.asarray(1.0, f32))
                mean = jnp.sum(x * w, axis=axes, dtype=f32) / denom
                xc = (x - mean.astype(dt)) * w
                var = jnp.sum(xc * xc, axis=axes, dtype=f32) / denom
            else:
                mean = jnp.mean(x, axis=axes, dtype=f32)
                xc = x - mean.astype(dt)
                var = jnp.mean(xc * xc, axis=axes, dtype=f32)
            new_state = {
                "mean": self.decay * state["mean"] + (1.0 - self.decay) * mean,
                "var": self.decay * state["var"] + (1.0 - self.decay) * var,
            }
        else:
            mean, var = state["mean"].astype(f32), state["var"].astype(f32)
            new_state = state
        inv = lax.rsqrt(var + self.eps)
        if self.use_gamma_beta and params:
            a = params["gamma"].astype(f32) * inv
            b = params["beta"].astype(f32) - mean * a
        else:
            a = inv
            b = -mean * inv
        y = x * a.astype(dt) + b.astype(dt)
        return y, new_state


@register_layer("lrn")
@dataclass
class LocalResponseNormalization(LayerConfig):
    """Local response normalization across channels (LocalResponseNormalization.java).

    DL4J defaults: k=2, n=5, alpha=1e-4, beta=0.75.
    """

    k: float = 2.0
    n: int = 5
    alpha: float = 1e-4
    beta: float = 0.75

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        # Sum x^2 over a window of `n` adjacent channels (last axis, NHWC).
        half = self.n // 2
        sq = x * x
        # reduce_window over channel axis
        window = (1,) * (x.ndim - 1) + (self.n,)
        strides = (1,) * x.ndim
        pads = tuple(
            (0, 0) if i < x.ndim - 1 else (half, self.n - 1 - half) for i in range(x.ndim)
        )
        ssum = lax.reduce_window(sq, 0.0, lax.add, window, strides, pads)
        denom = (self.k + self.alpha * ssum) ** self.beta
        return x / denom, state


def layer_norm(x, gamma=None, beta=None, eps: float = 1e-5):
    """Functional layer norm over the last axis (shared by LayerNorm and
    TransformerBlock). Statistics in f32 for bf16 inputs (stability), result
    cast back to the input dtype."""
    dt = x.dtype
    xs = x.astype(jnp.float32) if dt == jnp.bfloat16 else x
    mean = jnp.mean(xs, axis=-1, keepdims=True)
    var = jnp.mean((xs - mean) ** 2, axis=-1, keepdims=True)
    y = (xs - mean) * lax.rsqrt(var + eps)
    y = y.astype(dt)
    if gamma is not None:
        y = y * gamma + beta
    return y


@register_layer("layer_norm")
@dataclass
class LayerNorm(LayerConfig):
    """Layer normalization over the last (feature) axis.

    Beyond-reference capability (the reference has no transformer stack);
    required by the attention/transformer layers (attention.py). One fused
    reduce+elementwise graph under XLA.
    """

    eps: float = 1e-5
    use_gamma_beta: bool = True

    def _nfeat(self, input_type: InputType) -> int:
        return input_type.channels if input_type.kind == "conv" else input_type.size

    def init(self, key, input_type, dtype=jnp.float32):
        if not self.use_gamma_beta:
            return {}
        n = self._nfeat(input_type)
        return {"gamma": jnp.ones((n,), dtype), "beta": jnp.zeros((n,), dtype)}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        g = params.get("gamma") if params else None
        b = params.get("beta") if params else None
        return layer_norm(x, g, b, self.eps), state


def rms_norm(x, gamma=None, eps: float = 1e-5):
    """Functional root-mean-square norm over the last axis:
    ``x * rsqrt(mean(x^2) + eps) * gamma`` (no mean subtracted, no bias).
    Statistics in f32 for bf16 inputs, result cast back to the input dtype."""
    dt = x.dtype
    xs = x.astype(jnp.float32) if dt == jnp.bfloat16 else x
    y = xs * lax.rsqrt(jnp.mean(xs * xs, axis=-1, keepdims=True) + eps)
    y = y.astype(dt)
    return y if gamma is None else y * gamma


@register_layer("rms_norm")
@dataclass
class RMSNorm(LayerConfig):
    """RMS normalization over the last (feature) axis, gain only."""

    eps: float = 1e-5

    def init(self, key, input_type, dtype=jnp.float32):
        return {"gamma": jnp.ones((input_type.size,), dtype)}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return rms_norm(x, params["gamma"], self.eps), state
