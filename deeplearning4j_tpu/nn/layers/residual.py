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
from deeplearning4j_tpu.ops.flash_attention import ATTN_LSE, ATTN_OUT


@register_layer("residual_block")
@dataclass
class ResidualBlock(LayerConfig):
    """``x + mixer(RMSNorm(x))``. ``remat=True`` recomputes the layer in the
    backward pass (``jax.checkpoint``): between the passes it keeps the
    layer's input and, where the mixer runs a flash attention kernel
    (``ops/flash_attention.py``, ``ops/flash_mla.py``), the kernel's result
    and its row statistic, which the kernel's backward reads, so the kernel
    runs once a step and not twice. A mixer that calls neither kernel (a
    state-space mixer, an expert layer, a gated MLP, attention on its XLA
    path) has nothing named and keeps its input alone. Keeping the result
    costs no device time, only bytes between the passes: where ``H * Dv``
    equals the model width an attention block keeps twice its input where it
    kept once, and where ``H * Dv`` is twice the width (JoyAI-LLM-Flash)
    three times. The mixer's state (an expert layer's routing bias and load
    counters) is this layer's state."""

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
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.save_only_these_names(
                    ATTN_OUT, ATTN_LSE))
        return body(params, state, x, rng, mask)
