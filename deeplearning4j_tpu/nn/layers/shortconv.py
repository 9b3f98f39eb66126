"""The gated short-convolution mixer of the LFM2 family (Liquid AI): a
sequence mixer with no attention and no recurrent state beyond the
convolution's last positions.

One mixer over ``u`` [B, T, C]:

    [B | C | x] = u W_in                       (three streams of C channels)
    z   = B * x
    c_t = sum_j w[j] * z[t - (k-1-j)]          (causal, depthwise, k taps,
                                                zeros before the row's start)
    out = (C * c) W_out

No bias and no activation function anywhere in it: the two element-wise gates
are its only nonlinearity.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import initializers
from deeplearning4j_tpu.nn.config import LayerConfig, register_layer
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers.ssm import causal_depthwise_conv1d


@register_layer("short_conv_mixer")
@dataclass
class ShortConvMixer(LayerConfig):
    """The gated short convolution over [B, T, C] (no residual, no pre-norm:
    wrap it in a ``ResidualBlock``). ``conv_kernel`` taps a channel. Its
    middle (both gates and the taps, scope ``shortconv/gate``) multiplies no
    matrix: it is bound by memory bandwidth, the two projections around it by
    the MXU."""

    conv_kernel: int = 3
    weight_init: Any = "xavier"

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def init(self, key, input_type, dtype=jnp.float32):
        C, k = input_type.size, self.conv_kernel
        k_in, k_out, k_conv = jax.random.split(key, 3)
        init = lambda kk, fi, fo: initializers.initialize(   # noqa: E731
            self.weight_init, kk, (fi, fo), fi, fo, dtype)
        return {"W_in": init(k_in, C, 3 * C),
                "conv_w": (jax.random.uniform(k_conv, (k, C), jnp.float32,
                                              -1.0, 1.0) / k ** 0.5).astype(dtype),
                "W_out": init(k_out, C, C)}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        with jax.named_scope("shortconv"):
            with jax.named_scope("in"):
                b, c, xs = jnp.split(x @ params["W_in"], 3, axis=-1)
            with jax.named_scope("gate"):
                z = b * xs
                if mask is not None and mask.ndim >= 2:
                    # a padded position adds nothing to the positions after it
                    z = z * mask.reshape(z.shape[:2] + (1,)).astype(z.dtype)
                y = c * causal_depthwise_conv1d(z, params["conv_w"])
            with jax.named_scope("out"):
                return y @ params["W_out"], state
