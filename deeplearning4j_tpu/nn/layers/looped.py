"""A stack of layers run several times with the same weights, and the output
layer that reads every pass: the looped language model of "Scaling Latent
Reasoning via Looped Language Models" (Ouro, arXiv:2510.25741).

``LoopedStack`` applies its blocks ``loops`` times; after each pass a final
RMSNorm gives that loop's state, which the next loop starts from. It hands
every loop's state on, folded into the batch axis (``[loops * B, T, C]``),
so that ``InputType`` stays that of one state; a mask stays ``[B, T]``.

``LoopExitOutputLayer`` reads them: one bias-free head (its own matrix) and
a one-output exit gate after every loop, and the loss is the expected
cross-entropy under the gate's exit distribution less ``beta`` times that
distribution's entropy.

Every block reads the same parameters in every loop, so ``jax.grad`` sums
the loops' contributions into one gradient and the updater keeps one state
for each parameter."""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn import initializers
from deeplearning4j_tpu.nn.config import LayerConfig, register_layer
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers.normalization import rms_norm
from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer

# a step's counters of a LoopedStack, in this order in its "stats" vector and
# added up by publish_stats as dl4j_loop_<key>_total{layer}
_STACK_STATS = {
    "steps": "steps whose counters the host fetched",
    "block_applications": "block applications the mathematics needs "
                          "(loops x blocks a step)",
    "block_passes": "block forward passes the compiled step runs, the "
                    "recomputed ones counted",
}


@register_layer("looped_stack")
@dataclass
class LoopedStack(LayerConfig):
    """``blocks`` (``ResidualBlock``s, or any layers that keep no state and
    map ``[B, T, C]`` to itself) applied in order ``loops`` times:
    ``h^t = RMSNorm_f(blocks(h^{t-1}))``, ``h^0`` the input, one gain for
    the final norm. The output is ``[h^1; ...; h^loops]`` along the batch
    axis. The device scope of loop ``t`` (from 1) is ``loop/<t>``.

    Loops are unrolled in the step (a Python loop), so each block appears
    ``loops`` times in the program. Each block keeps its own recomputation
    policy (``ResidualBlock.remat``) in every loop: the block passes the
    step runs are counted from it (``dl4j_loop_block_passes_total``)."""

    blocks: tuple = ()
    loops: int = 1
    eps: float = 1e-6

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def uses_rng(self) -> bool:
        return super().uses_rng() or any(b.uses_rng() for b in self.blocks)

    def nested_param_layers(self) -> dict:
        return {f"block{i}": b for i, b in enumerate(self.blocks)}

    def init(self, key, input_type, dtype=jnp.float32):
        if self.loops < 1 or not self.blocks:
            raise ValueError(f"LoopedStack: loops={self.loops} over "
                             f"{len(self.blocks)} blocks")
        keys = jax.random.split(key, len(self.blocks))
        p = {f"block{i}": b.init(k, input_type, dtype)
             for i, (b, k) in enumerate(zip(self.blocks, keys))}
        p["norm"] = {"gamma": jnp.ones((input_type.size,), dtype)}
        return p

    def init_state(self, input_type: InputType):
        if any(b.init_state(input_type) for b in self.blocks):
            raise ValueError("LoopedStack: a block that keeps state would "
                             "update it once a loop")
        return {"stats": jnp.zeros((len(_STACK_STATS),), jnp.float32)}

    def counts(self) -> tuple:
        """(block applications, block forward passes) of one step: a block
        recomputed in the backward pass runs its forward twice."""
        passes = sum(2 if getattr(b, "remat", False) else 1 for b in self.blocks)
        return self.loops * len(self.blocks), self.loops * passes

    def publish_stats(self, index: int, stats) -> None:
        from deeplearning4j_tpu import obs

        for (key, help_), value in zip(_STACK_STATS.items(), np.asarray(stats)):
            obs.counter(f"dl4j_loop_{key}_total", help_, ("layer",)).inc(
                float(value), layer=str(index))

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        h, states = x, []
        for t in range(self.loops):
            with jax.named_scope(f"loop/{t + 1}"):
                for i, b in enumerate(self.blocks):
                    r = (None if rng is None
                         else jax.random.fold_in(rng, t * len(self.blocks) + i))
                    h, _ = b.apply(params[f"block{i}"], {}, h, train=train,
                                   rng=r, mask=mask)
                h = rms_norm(h, params["norm"]["gamma"], self.eps)
            states.append(h)
        stats = jnp.asarray((1.0,) + self.counts(), jnp.float32)
        return jnp.concatenate(states, 0), {"stats": stats}


# a step's counters of a LoopExitOutputLayer after the per-loop exit mass and
# cross-entropy, in this order in its "stats" vector
_EXIT_STATS = {
    "tokens": "tokens scored",
    "exits": "loop exits scored (a head and a gate each)",
    "head_passes": "head logits passes the compiled step runs, first and "
                   "recomputed",
}


@register_layer("loop_exit_output")
@dataclass
class LoopExitOutputLayer(RnnOutputLayer):
    """The exits of a ``LoopedStack`` of ``loops`` loops, fed ``[h^1; ...;
    h^loops]`` along the batch axis (each state normed already). For every
    loop ``t``: logits ``z^t = h^t W`` (``W`` [C, vocabulary], bias-free,
    one matrix for all loops) and an exit gate ``lambda^t = sigmoid(h^t w_g
    + b_g)``. The exit distribution of a token is ``p_1 = lambda^1``,
    ``p_t = lambda^t prod_{j<t}(1 - lambda^j)``, ``p_loops = prod_{j<loops}
    (1 - lambda^j)`` (the last loop's gate is not read), and the loss is the
    mean over tokens of ``sum_t p_t CE(z^t, y) - beta H(p)``, ``H(p) =
    -sum_t p_t log p_t``: the entropy-regularised objective. Gradients flow
    through ``p`` into the gate and the trunk. Labels are sparse ids [B, T].

    ``remat`` recomputes each loop's logits in the backward pass: a head
    keeps its input and a token's cross-entropy, not its logits. The device
    scope of the heads, the gate and the loss is ``loop/exit``.

    The state is a ``"stats"`` vector: the exit mass ``sum p_t`` of each loop
    over the step's tokens, each loop's summed cross-entropy, then the
    tokens, the exits and the head passes; ``fit()`` fetches it with the
    loss and ``publish_stats`` adds it up. ``apply`` (inference) gives the
    last loop's probabilities: every loop runs (Ouro's
    ``early_exit_threshold`` 1)."""

    SCORES_STATE = True         # MultiLayerNetwork._loss calls score_shared

    has_bias: bool = False
    loops: int = 1
    beta: float = 0.1
    remat: bool = False

    def init(self, key, input_type, dtype=jnp.float32):
        if self.has_bias:
            raise ValueError("LoopExitOutputLayer: the head has no bias")
        if self.loss != "mcxent" or self.activation != "softmax":
            raise ValueError("LoopExitOutputLayer scores softmax cross-entropy")
        kh, kg = jax.random.split(key)
        p = super().init(kh, input_type, dtype)
        d = input_type.size if self.n_in is None else self.n_in
        p["gate"] = {"W": initializers.initialize(self.weight_init, kg, (d, 1),
                                                  d, 1, dtype),
                     "b": jnp.zeros((1,), dtype)}
        return p

    def init_state(self, input_type: InputType):
        return {"stats": jnp.zeros((2 * self.loops + len(_EXIT_STATS),),
                                   jnp.float32)}

    def publish_stats(self, index: int, stats) -> None:
        from deeplearning4j_tpu import obs

        layer, stats, T = str(index), np.asarray(stats), self.loops
        mass = obs.counter("dl4j_loop_exit_mass_total", "sum over tokens of a "
                           "loop's exit probability", ("layer", "loop"))
        ce = obs.counter("dl4j_loop_ce_total", "sum over tokens of a loop's "
                         "cross-entropy", ("layer", "loop"))
        for t in range(T):
            mass.inc(float(stats[t]), layer=layer, loop=str(t + 1))
            ce.inc(float(stats[T + t]), layer=layer, loop=str(t + 1))
        for (key, help_), value in zip(_EXIT_STATS.items(), stats[2 * T:]):
            obs.counter(f"dl4j_loop_{key}_total", help_, ("layer",)).inc(
                float(value), layer=layer)

    def _loops(self, h):
        """[loops * B, T, C] -> [loops, B, T, C]."""
        return h.reshape((self.loops, h.shape[0] // self.loops) + h.shape[1:])

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        h = self._loops(x)[-1]
        y = jax.nn.softmax(h @ params["W"], axis=-1)
        return (y if mask is None else y * mask[..., None]), state

    def score_shared(self, params, state, h, labels, *, shared, mask=None,
                     train=True, rng=None):
        """``(loss, new state)`` for the loop states ``h`` [loops * B, T, C]
        and sparse ``labels`` [B, T]."""
        hs = self._loops(h)
        if labels.ndim != 2:
            raise ValueError("LoopExitOutputLayer reads sparse labels [B, T]")
        ids = labels.astype(jnp.int32)

        def head(x, W):
            """A token's cross-entropy [B, T] of the logits ``x W``."""
            z = (x @ W).astype(jnp.float32)
            zy = jnp.take_along_axis(z, ids[..., None], -1)[..., 0]
            return jax.nn.logsumexp(z, -1) - zy

        if self.remat:          # a head's logits are not kept for its backward
            head = jax.checkpoint(head)
        T = self.loops
        with jax.named_scope("loop/exit"):
            ce = jnp.stack([head(hs[t], params["W"]) for t in range(T)])
            # the gate's logit after loops 1 .. T-1; log p_t in log-sigmoids
            a = (hs[:-1] @ params["gate"]["W"])[..., 0].astype(jnp.float32) \
                + params["gate"]["b"][0].astype(jnp.float32)
            stay = jnp.cumsum(jax.nn.log_sigmoid(-a), 0)         # log prod(1 - l)
            logp = jnp.concatenate([
                jax.nn.log_sigmoid(a)
                + jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]], 0),
                stay[-1:]], 0) if T > 1 else jnp.zeros_like(ce)
            p = jnp.exp(logp)                                     # [T, B, S]
            per_token = jnp.sum(p * ce, 0) + self.beta * jnp.sum(p * logp, 0)
            m = (jnp.ones(ids.shape, jnp.float32) if mask is None
                 else mask.reshape(ids.shape).astype(jnp.float32))
            n = jnp.sum(m)
            loss = jnp.sum(per_token * m) / jnp.maximum(n, 1.0)
            stats = jnp.concatenate([
                jnp.sum(p * m, (1, 2)), jnp.sum(ce * m, (1, 2)),
                jnp.stack([n, jnp.float32(T),
                           jnp.float32(T * (2 if self.remat else 1))])])
        return loss, {"stats": jax.lax.stop_gradient(stats)}
