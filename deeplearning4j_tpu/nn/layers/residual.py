"""The pre-norm residual wrapper of the hybrid stacks:
``x <- x + mixer(RMSNorm(x))`` with any mixer layer (a state-space mixer,
attention, an expert layer), one mixer a layer."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.config import LayerConfig, register_layer
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers.normalization import rms_norm


@register_layer("residual_block")
@dataclass
class ResidualBlock(LayerConfig):
    """``x + mixer(RMSNorm(x))``. ``remat=True`` recomputes the whole layer
    in the backward pass (``jax.checkpoint``): only the layer's input is kept
    between the passes. The mixer's state (an expert layer's routing bias and
    load counters) is this layer's state."""

    mixer: Any = None           # a LayerConfig
    eps: float = 1e-5
    remat: bool = False

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def uses_rng(self) -> bool:
        return super().uses_rng() or self.mixer.uses_rng()

    def nested_param_layers(self) -> dict:
        return {"mixer": self.mixer}

    def init(self, key, input_type, dtype=jnp.float32):
        return {"norm": {"gamma": jnp.ones((input_type.size,), dtype)},
                "mixer": self.mixer.init(key, input_type, dtype)}

    def init_state(self, input_type: InputType):
        return self.mixer.init_state(input_type)

    def publish_stats(self, index: int, stats) -> None:
        self.mixer.publish_stats(index, stats)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)

        def body(p, st, xx, r, m):
            h = rms_norm(xx, p["norm"]["gamma"], self.eps)
            y, new_st = self.mixer.apply(p["mixer"], st, h, train=train, rng=r, mask=m)
            return xx + y, new_st

        if self.remat:
            body = jax.checkpoint(body)
        return body(params, state, x, rng, mask)
