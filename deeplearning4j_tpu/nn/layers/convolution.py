"""Convolution / pooling / padding / upsampling layers (NHWC).

Reference parity: nn/conf/layers/{ConvolutionLayer,Convolution1DLayer,
Deconvolution2D,SeparableConvolution2D,DepthwiseConvolution2D,
SubsamplingLayer,Subsampling1DLayer,Upsampling2D,ZeroPaddingLayer}.java and
the cuDNN helpers they dispatch to
(/root/reference/deeplearning4j-cuda/.../CudnnConvolutionHelper.java:54,
CudnnSubsamplingHelper.java). On TPU all of these lower to
``lax.conv_general_dilated`` / ``lax.reduce_window``, which XLA tiles onto
the MXU — the helper indirection disappears (one lowering path, always on).

Layout: **NHWC** + HWIO kernels (the reference is NCHW; NHWC is what XLA:TPU
prefers). ``convolution_mode`` mirrors DL4J's Same/Truncate/Strict.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn import initializers
from deeplearning4j_tpu.nn.config import FeedForwardLayerConfig, LayerConfig, register_layer
from deeplearning4j_tpu.nn.input_type import InputType

DIMNUMS = ("NHWC", "HWIO", "NHWC")


def _pair(v) -> Tuple[int, int]:
    if isinstance(v, (tuple, list)):
        return (int(v[0]), int(v[1]))
    return (int(v), int(v))


def _out_size(size: int, k: int, s: int, p: int, mode: str, d: int = 1) -> int:
    k_eff = (k - 1) * d + 1  # effective kernel extent under dilation
    if mode == "same":
        return -(-size // s)  # ceil
    if mode == "strict":
        if (size - k_eff + 2 * p) % s != 0:
            raise ValueError(
                f"Strict convolution mode: ({size} - {k_eff} + 2*{p}) not divisible by stride {s}"
            )
    return (size - k_eff + 2 * p) // s + 1


def _conv_padding(mode: str, pad: Tuple[int, int]):
    if mode == "same":
        return "SAME"
    return [(pad[0], pad[0]), (pad[1], pad[1])]


@register_layer("conv2d")
@dataclass
class Conv2D(FeedForwardLayerConfig):
    """2-D convolution. Parity: nn/conf/layers/ConvolutionLayer.java.

    n_out = output channels; n_in inferred from input channels.
    """

    kernel: Any = (3, 3)
    stride: Any = (1, 1)
    padding: Any = (0, 0)
    dilation: Any = (1, 1)
    convolution_mode: str = "truncate"  # same | truncate | strict
    has_bias: bool = True

    def infer_n_in(self, input_type):
        return input_type.channels  # n_in = input channels, not flat size

    def output_type(self, input_type: InputType) -> InputType:
        if input_type.kind != "conv":
            raise ValueError(f"Conv2D needs convolutional input, got {input_type}")
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        dh, dw = _pair(self.dilation)
        oh = _out_size(input_type.height, kh, sh, ph, self.convolution_mode, dh)
        ow = _out_size(input_type.width, kw, sw, pw, self.convolution_mode, dw)
        return InputType.convolutional(oh, ow, self.n_out)

    def init(self, key, input_type, dtype=jnp.float32):
        in_c = self.n_in if self.n_in is not None else input_type.channels
        kh, kw = _pair(self.kernel)
        fan_in = in_c * kh * kw
        fan_out = self.n_out * kh * kw
        kW, _ = jax.random.split(key)
        params = {
            "W": initializers.initialize(
                self.weight_init, kW, (kh, kw, in_c, self.n_out), fan_in, fan_out, dtype
            )
        }
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return params

    def _conv(self, x, W, groups: int = 1):
        sh, sw = _pair(self.stride)
        # NOTE: a slice-then-dense rewrite of strided 1x1 convs (the
        # ResNet-v1 bottleneck pattern) was a +12% win in round 3 but a
        # -12% LOSS on the round-4 toolchain — the strided-gather lowering
        # improved and the explicit slice now breaks producer fusion. Keep
        # the plain form.
        return lax.conv_general_dilated(
            x,
            W,
            window_strides=(sh, sw),
            padding=_conv_padding(self.convolution_mode, _pair(self.padding)),
            rhs_dilation=_pair(self.dilation),
            dimension_numbers=DIMNUMS,
            feature_group_count=groups,
        )

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        y = self._conv(x, params["W"])
        if self.has_bias:
            y = y + params["b"]
        return self.activation_fn()(y), state

    def propagate_mask(self, mask, input_type):
        return None  # masks don't flow through spatial convs


@register_layer("deconv2d")
@dataclass
class Deconv2D(Conv2D):
    """Transposed convolution (Deconvolution2D.java)."""

    def output_type(self, input_type: InputType) -> InputType:
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        if self.convolution_mode == "same":
            oh, ow = input_type.height * sh, input_type.width * sw
        else:
            oh = sh * (input_type.height - 1) + kh - 2 * ph
            ow = sw * (input_type.width - 1) + kw - 2 * pw
        return InputType.convolutional(oh, ow, self.n_out)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        kh, kw = _pair(self.kernel)
        ph, pw = _pair(self.padding)
        if self.convolution_mode == "same":
            padding = "SAME"
        else:
            # lax.conv_transpose applies explicit pads to the dilated input;
            # (k-1-p, k-1-p) yields the standard deconv output size
            # s*(h-1) + k - 2p that output_type advertises.
            padding = [(kh - 1 - ph, kh - 1 - ph), (kw - 1 - pw, kw - 1 - pw)]
        y = lax.conv_transpose(
            x,
            params["W"],
            strides=_pair(self.stride),
            padding=padding,
            dimension_numbers=DIMNUMS,
        )
        if self.has_bias:
            y = y + params["b"]
        return self.activation_fn()(y), state


@register_layer("depthwise_conv2d")
@dataclass
class DepthwiseConv2D(Conv2D):
    """Depthwise convolution (DepthwiseConvolution2D.java): each input channel
    convolved with `depth_multiplier` filters; n_out = in_c * depth_multiplier."""

    depth_multiplier: int = 1

    def output_type(self, input_type: InputType) -> InputType:
        base = super().output_type(
            input_type
        )
        return InputType.convolutional(base.height, base.width, input_type.channels * self.depth_multiplier)

    def init(self, key, input_type, dtype=jnp.float32):
        in_c = self.n_in if self.n_in is not None else input_type.channels
        kh, kw = _pair(self.kernel)
        out_c = in_c * self.depth_multiplier
        kW, _ = jax.random.split(key)
        params = {
            "W": initializers.initialize(
                self.weight_init, kW, (kh, kw, 1, out_c), kh * kw, kh * kw * self.depth_multiplier, dtype
            )
        }
        if self.has_bias:
            params["b"] = jnp.full((out_c,), self.bias_init, dtype)
        return params

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        y = self._conv(x, params["W"], groups=x.shape[-1])
        if self.has_bias:
            y = y + params["b"]
        return self.activation_fn()(y), state


@register_layer("separable_conv2d")
@dataclass
class SeparableConv2D(Conv2D):
    """Depthwise + pointwise (SeparableConvolution2D.java)."""

    depth_multiplier: int = 1

    def init(self, key, input_type, dtype=jnp.float32):
        in_c = self.n_in if self.n_in is not None else input_type.channels
        kh, kw = _pair(self.kernel)
        mid_c = in_c * self.depth_multiplier
        kD, kP = jax.random.split(key)
        params = {
            "dW": initializers.initialize(
                self.weight_init, kD, (kh, kw, 1, mid_c), kh * kw, kh * kw, dtype
            ),
            "pW": initializers.initialize(
                self.weight_init, kP, (1, 1, mid_c, self.n_out), mid_c, self.n_out, dtype
            ),
        }
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return params

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        y = self._conv(x, params["dW"], groups=x.shape[-1])
        y = lax.conv_general_dilated(
            y, params["pW"], window_strides=(1, 1), padding="VALID", dimension_numbers=DIMNUMS
        )
        if self.has_bias:
            y = y + params["b"]
        return self.activation_fn()(y), state


@register_layer("conv1d")
@dataclass
class Conv1D(FeedForwardLayerConfig):
    """1-D convolution over [batch, time, feat] (Convolution1DLayer.java)."""

    kernel: int = 3
    stride: int = 1
    padding: int = 0
    dilation: int = 1
    convolution_mode: str = "truncate"
    has_bias: bool = True

    def output_type(self, input_type: InputType) -> InputType:
        t = input_type.timesteps
        ot = None
        if t is not None:
            ot = _out_size(
                t,
                int(self.kernel),
                int(self.stride),
                int(self.padding),
                self.convolution_mode,
                int(self.dilation),
            )
        return InputType.recurrent(self.n_out, ot)

    def init(self, key, input_type, dtype=jnp.float32):
        in_c = self.n_in if self.n_in is not None else input_type.size
        k = int(self.kernel)
        kW, _ = jax.random.split(key)
        params = {
            "W": initializers.initialize(
                self.weight_init, kW, (k, in_c, self.n_out), k * in_c, k * self.n_out, dtype
            )
        }
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return params

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        pad = (
            "SAME"
            if self.convolution_mode == "same"
            else [(int(self.padding), int(self.padding))]
        )
        y = lax.conv_general_dilated(
            x,
            params["W"],
            window_strides=(int(self.stride),),
            padding=pad,
            rhs_dilation=(int(self.dilation),),
            dimension_numbers=("NHC", "HIO", "NHC"),
        )
        if self.has_bias:
            y = y + params["b"]
        return self.activation_fn()(y), state

    def propagate_mask(self, mask, input_type):
        return _subsample_mask_1d(
            mask, int(self.kernel), int(self.stride), int(self.padding),
            self.convolution_mode, int(self.dilation),
        )


def _subsample_mask_1d(mask, kernel, stride, padding, mode, dilation=1):
    """Downsample a [batch, T] mask to the pooled/conv output length: keep the
    mask value at each output window's start position (the reference's
    stride-based mask reduction for 1-D conv/subsampling layers)."""
    if mask is None:
        return None
    T = mask.shape[1]
    if mode == "same":
        ot = -(-T // stride)  # ceil
    else:
        ot = _out_size(T, kernel, stride, padding, mode, dilation)
    idx = jnp.clip(jnp.arange(ot) * stride, 0, T - 1)
    return jnp.take(mask, idx, axis=1)


@register_layer("subsampling2d")
@dataclass
class Subsampling2D(LayerConfig):
    """Spatial pooling (SubsamplingLayer.java): max | avg | sum | pnorm."""

    kernel: Any = (2, 2)
    stride: Any = (2, 2)
    padding: Any = (0, 0)
    pooling: str = "max"
    pnorm: int = 2
    convolution_mode: str = "truncate"

    def output_type(self, input_type: InputType) -> InputType:
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        oh = _out_size(input_type.height, kh, sh, ph, self.convolution_mode)
        ow = _out_size(input_type.width, kw, sw, pw, self.convolution_mode)
        return InputType.convolutional(oh, ow, input_type.channels)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        window = (1, kh, kw, 1)
        strides = (1, sh, sw, 1)
        if self.convolution_mode == "same":
            pads = "SAME"
        else:
            pads = ((0, 0), (ph, ph), (pw, pw), (0, 0))
        if self.pooling == "max":
            init = -jnp.inf
            y = lax.reduce_window(x, init, lax.max, window, strides, pads)
        elif self.pooling in ("avg", "mean"):
            s = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
            y = s / (kh * kw)
        elif self.pooling == "sum":
            y = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
        elif self.pooling == "pnorm":
            p = float(self.pnorm)
            s = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, window, strides, pads)
            y = s ** (1.0 / p)
        else:
            raise ValueError(f"Unknown pooling '{self.pooling}'")
        return y, state

    def propagate_mask(self, mask, input_type):
        return None


@register_layer("subsampling1d")
@dataclass
class Subsampling1D(LayerConfig):
    """Temporal pooling over [batch, time, feat] (Subsampling1DLayer.java)."""

    kernel: int = 2
    stride: int = 2
    padding: int = 0
    pooling: str = "max"
    pnorm: int = 2
    convolution_mode: str = "truncate"

    def output_type(self, input_type: InputType) -> InputType:
        t = input_type.timesteps
        ot = None
        if t is not None:
            ot = _out_size(t, int(self.kernel), int(self.stride), int(self.padding), self.convolution_mode)
        return InputType.recurrent(input_type.size, ot)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        k, s, p = int(self.kernel), int(self.stride), int(self.padding)
        window = (1, k, 1)
        strides = (1, s, 1)
        pads = "SAME" if self.convolution_mode == "same" else ((0, 0), (p, p), (0, 0))
        if self.pooling == "max":
            y = lax.reduce_window(x, -jnp.inf, lax.max, window, strides, pads)
        elif self.pooling in ("avg", "mean"):
            y = lax.reduce_window(x, 0.0, lax.add, window, strides, pads) / k
        elif self.pooling == "sum":
            y = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
        elif self.pooling == "pnorm":
            pn = float(getattr(self, "pnorm", 2))
            s_ = lax.reduce_window(jnp.abs(x) ** pn, 0.0, lax.add, window, strides, pads)
            y = s_ ** (1.0 / pn)
        else:
            raise ValueError(f"Unknown pooling '{self.pooling}'")
        return y, state

    def propagate_mask(self, mask, input_type):
        return _subsample_mask_1d(
            mask, int(self.kernel), int(self.stride), int(self.padding), self.convolution_mode
        )


@register_layer("upsampling2d")
@dataclass
class Upsampling2D(LayerConfig):
    """Nearest-neighbor upsampling (Upsampling2D.java)."""

    size: Any = (2, 2)

    def output_type(self, input_type: InputType) -> InputType:
        sh, sw = _pair(self.size)
        return InputType.convolutional(input_type.height * sh, input_type.width * sw, input_type.channels)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        sh, sw = _pair(self.size)
        y = jnp.repeat(jnp.repeat(x, sh, axis=1), sw, axis=2)
        return y, state


@register_layer("upsampling1d")
@dataclass
class Upsampling1D(LayerConfig):
    """Temporal nearest-neighbor upsampling over [B,T,F]
    (Upsampling1D.java)."""

    size: int = 2

    def output_type(self, input_type: InputType) -> InputType:
        t = input_type.timesteps
        return InputType.recurrent(
            input_type.size, t * int(self.size) if t is not None else None)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return jnp.repeat(x, int(self.size), axis=1), state

    def propagate_mask(self, mask, input_type):
        if mask is None:
            return None
        return jnp.repeat(mask, int(self.size), axis=1)


@register_layer("zero_padding1d")
@dataclass
class ZeroPadding1D(LayerConfig):
    """Temporal zero padding over [B,T,F] (ZeroPadding1DLayer.java).
    padding: (left, right) or symmetric int."""

    padding: Any = (1, 1)

    def _pads(self):
        p = self.padding
        if isinstance(p, (tuple, list)):
            return int(p[0]), int(p[1])
        return int(p), int(p)

    def output_type(self, input_type: InputType) -> InputType:
        l, r = self._pads()
        t = input_type.timesteps
        return InputType.recurrent(
            input_type.size, t + l + r if t is not None else None)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        l, r = self._pads()
        return jnp.pad(x, ((0, 0), (l, r), (0, 0))), state

    def propagate_mask(self, mask, input_type):
        if mask is None:
            return None
        l, r = self._pads()
        return jnp.pad(mask, ((0, 0), (l, r)), constant_values=1.0)


@register_layer("cropping1d")
@dataclass
class Cropping1D(LayerConfig):
    """Temporal cropping over [B,T,F] (Cropping1D.java).
    crop: (left, right) or symmetric int."""

    crop: Any = (0, 0)

    def _crops(self):
        c = self.crop
        if isinstance(c, (tuple, list)):
            return int(c[0]), int(c[1])
        return int(c), int(c)

    def output_type(self, input_type: InputType) -> InputType:
        l, r = self._crops()
        t = input_type.timesteps
        return InputType.recurrent(
            input_type.size, t - l - r if t is not None else None)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        l, r = self._crops()
        t = x.shape[1]
        return x[:, l: t - r, :], state

    def propagate_mask(self, mask, input_type):
        if mask is None:
            return None
        l, r = self._crops()
        return mask[:, l: mask.shape[1] - r]


@register_layer("zero_padding2d")
@dataclass
class ZeroPadding2D(LayerConfig):
    """Explicit spatial zero padding (ZeroPaddingLayer.java).

    padding: (top, bottom, left, right) or (h, w) symmetric.
    """

    padding: Any = (1, 1, 1, 1)

    def _pads(self):
        p = self.padding
        if isinstance(p, (tuple, list)) and len(p) == 4:
            return tuple(int(v) for v in p)
        ph, pw = _pair(p)
        return (ph, ph, pw, pw)

    def output_type(self, input_type: InputType) -> InputType:
        t, b, l, r = self._pads()
        return InputType.convolutional(
            input_type.height + t + b, input_type.width + l + r, input_type.channels
        )

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        t, b, l, r = self._pads()
        return jnp.pad(x, ((0, 0), (t, b), (l, r), (0, 0))), state


@register_layer("cropping2d")
@dataclass
class Cropping2D(LayerConfig):
    """Spatial cropping (Cropping2D.java). crop: (top, bottom, left, right)."""

    CONSUMES_CONV = True

    crop: Any = (0, 0, 0, 0)

    def _crops(self):
        c = self.crop
        if isinstance(c, (tuple, list)) and len(c) == 4:
            return tuple(int(v) for v in c)
        ch, cw = _pair(c)
        return (ch, ch, cw, cw)

    def output_type(self, input_type: InputType) -> InputType:
        t, b, l, r = self._crops()
        return InputType.convolutional(
            input_type.height - t - b, input_type.width - l - r, input_type.channels
        )

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        t, b, l, r = self._crops()
        h, w = x.shape[1], x.shape[2]
        return x[:, t : h - b, l : w - r, :], state


@register_layer("space_to_depth")
@dataclass
class SpaceToDepth(LayerConfig):
    """[B,H,W,C] -> [B,H/b,W/b,C*b^2] (SpaceToDepthLayer.java). On TPU this
    is also the MLPerf-style stem trick: it turns a thin-channel stem conv
    (C_in=3, which underfills the 128-lane MXU contraction) into a
    b^2-richer one."""

    CONSUMES_CONV = True

    block: int = 2

    def output_type(self, input_type: InputType) -> InputType:
        b = int(self.block)
        if input_type.height % b or input_type.width % b:
            raise ValueError(
                f"SpaceToDepth: spatial dims {input_type.height}x"
                f"{input_type.width} not divisible by block {b}")
        return InputType.convolutional(
            input_type.height // b, input_type.width // b,
            input_type.channels * b * b)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        b = int(self.block)
        B, H, W, C = x.shape
        y = x.reshape(B, H // b, b, W // b, b, C)
        y = y.transpose(0, 1, 3, 2, 4, 5).reshape(B, H // b, W // b, b * b * C)
        return y, state


@register_layer("depth_to_space")
@dataclass
class DepthToSpace(LayerConfig):
    """[B,H,W,C*b^2] -> [B,H*b,W*b,C] (the inverse; Upsampling alternative)."""

    CONSUMES_CONV = True

    block: int = 2

    def output_type(self, input_type: InputType) -> InputType:
        b = int(self.block)
        if input_type.channels % (b * b):
            raise ValueError(
                f"DepthToSpace: channels {input_type.channels} not divisible "
                f"by block^2 {b * b}")
        return InputType.convolutional(
            input_type.height * b, input_type.width * b,
            input_type.channels // (b * b))

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        b = int(self.block)
        B, H, W, C = x.shape
        y = x.reshape(B, H, W, b, b, C // (b * b))
        y = y.transpose(0, 1, 3, 2, 4, 5).reshape(B, H * b, W * b, C // (b * b))
        return y, state
