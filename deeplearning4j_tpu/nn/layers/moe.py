"""Mixture-of-Experts layers.

``MixtureOfExperts``: switch-transformer-style top-1 routing with a
fixed per-expert capacity so every shape is static under jit: tokens are
dispatched to [E, capacity, C] expert buffers with one einsum, each expert
runs a batched FFN (one [E,·,·] batched matmul pair → MXU), and results
combine back weighted by the router gate. Overflow tokens (beyond capacity)
pass through the residual unchanged — the standard capacity-drop policy.

Expert parallelism = sharding the leading E axis of the expert weights over
the mesh's ``model`` axis (see parallel/tp.py); XLA turns the dispatch
einsums into all-to-alls over ICI.

``SparseMoE`` (further down): dropless top-k routing with sigmoid scores, a
shared expert, and an expert layer that holds a share of the experts.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn import initializers
from deeplearning4j_tpu.nn.config import LayerConfig, register_layer
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.ops.grouped_matmul import (
    ROW_TILE, grouped_matmul, no_cotangent, row_layout, tile_layout,
    tiles_needed)


@register_layer("mixture_of_experts")
@dataclass
class MixtureOfExperts(LayerConfig):
    """Top-1 (switch) MoE over [B, T, C] token streams, residual style:
    ``y = x + combine(expert_ffn(dispatch(x)))``."""

    n_experts: int = 8
    ffn_mult: int = 4
    capacity_factor: float = 1.25
    activation: Any = "gelu"
    weight_init: Any = "xavier"

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def init(self, key, input_type, dtype=jnp.float32):
        C = input_type.size
        F = self.ffn_mult * C
        E = self.n_experts
        kg, ki, ko = jax.random.split(key, 3)
        init = lambda k, shape, fi, fo: initializers.initialize(
            self.weight_init, k, shape, fi, fo, dtype
        )
        return {
            "Wg": init(kg, (C, E), C, E),
            "Wi": jnp.stack([init(k, (C, F), C, F) for k in jax.random.split(ki, E)]),
            "bi": jnp.zeros((E, F), dtype),
            "Wo": jnp.stack([init(k, (F, C), F, C) for k in jax.random.split(ko, E)]),
            "bo": jnp.zeros((E, C), dtype),
        }

    def _capacity(self, n_tokens: int) -> int:
        cap = int(self.capacity_factor * n_tokens / self.n_experts)
        return max(cap, 1)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        B, T, C = x.shape
        E = self.n_experts
        N = B * T
        cap = self._capacity(N)
        xt = x.reshape(N, C)

        # Routing math runs in f32/int32 regardless of activation dtype:
        # a bf16 cumsum loses integer precision past 256 and collides slots.
        logits = (xt @ params["Wg"]).astype(jnp.float32)            # [N,E]
        gates = jax.nn.softmax(logits, axis=-1)
        expert = jnp.argmax(gates, axis=-1)             # [N]
        gate = jnp.max(gates, axis=-1).astype(x.dtype)  # [N]
        onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)       # [N,E]
        if mask is not None and mask.ndim >= 2:
            # padding tokens don't route: they must not consume expert
            # capacity (slots are position-ordered) nor receive expert output
            onehot = onehot * mask.reshape(N).astype(jnp.float32)[:, None]
        pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0             # slot per token
        keep = (pos >= 0) & (pos < cap)
        slot = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32) * keep.astype(jnp.float32)[..., None]
        dispatch = (onehot[..., None] * slot).astype(x.dtype)       # [N,E,cap]

        xe = jnp.einsum("nec,nd->ecd", dispatch, xt)    # [E,cap,C]
        he = self.activation_fn()(jnp.einsum("ecd,edf->ecf", xe, params["Wi"]) + params["bi"][:, None])
        ye = jnp.einsum("ecf,efd->ecd", he, params["Wo"]) + params["bo"][:, None]
        combine = dispatch * gate[:, None, None]        # gate-weighted routes
        yt = jnp.einsum("nec,ecd->nd", combine, ye)
        return x + yt.reshape(B, T, C), state

    def load_balance_loss(self, params, x) -> jax.Array:
        """Auxiliary load-balancing loss (Switch §2.2): E · Σ_e f_e · P_e."""
        N = x.shape[0] * x.shape[1]
        logits = (x.reshape(N, -1) @ params["Wg"]).astype(jnp.float32)
        gates = jax.nn.softmax(logits, axis=-1)
        frac = jnp.mean(jax.nn.one_hot(jnp.argmax(gates, -1), self.n_experts), axis=0)
        prob = jnp.mean(gates, axis=0)
        return self.n_experts * jnp.sum(frac * prob)


def gated_silu(a):
    """``silu(gate) * up`` of ``a = [gate | up]``, halves of the last axis."""
    F = a.shape[-1] // 2
    return jax.nn.silu(a[..., :F]) * a[..., F:]


def _expert_act(a, gated: bool):
    """An expert's nonlinearity over its first product."""
    return gated_silu(a) if gated else jnp.square(jax.nn.relu(a))


@register_layer("gated_mlp")
@dataclass
class GatedMLP(LayerConfig):
    """The gated feed-forward of the Llama/DeepSeek kind over [B, T, C],
    bias-free: ``(silu(x W_g) * (x W_u)) W_o`` with ``Wi = [W_g | W_u]`` side
    by side, one product (no residual, no pre-norm: wrap it in a
    ``ResidualBlock``)."""

    width: int = 0
    weight_init: Any = "xavier"

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def init(self, key, input_type, dtype=jnp.float32):
        C, F = input_type.size, self.width
        ki, ko = jax.random.split(key)
        init = lambda k, fi, fo: initializers.initialize(    # noqa: E731
            self.weight_init, k, (fi, fo), fi, fo, dtype)
        return {"Wi": init(ki, C, 2 * F), "Wo": init(ko, F, C)}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        with jax.named_scope("mlp"):
            return gated_silu(x @ params["Wi"]) @ params["Wo"], state


# a step's counters of a SparseMoE layer, kept in its state under "stats" as
# one float32 vector in this order (one transfer a layer when the host
# fetches them) and added up by publish_stats as dl4j_moe_<key>_total{layer}
_MOE_STATS = {
    "pairs_held": "token-expert pairs sent to held experts",
    "load_max": "largest load of a held expert, summed over steps",
    "load_mean": "mean load of a held expert, summed over steps",
    "pairs_dropped": "token-expert pairs of held experts not computed (always 0)",
    "rows_computed": "rows of the row tiles the held experts' products ran",
    "rows_buffer": "rows of the pair buffer the step took",
}


def _branches(fn, variants):
    """``fn`` under each variant's keywords, as functions of ``(diff, ints)``."""
    return [lambda d, i, kw=dict(v): fn(*d, *i, **kw) for v in variants]


@functools.partial(jax.jit, static_argnames=("fn", "variants"))
def _switch(index, diff, ints, *, fn, variants):
    return jax.lax.switch(index, _branches(fn, variants), diff, ints)


@functools.partial(jax.jit, static_argnames=("fn", "variants"))
def _switch_vjp(index, diff, ints, g, *, fn, variants):
    return jax.lax.switch(index, [
        lambda d, i, g, b=b: jax.vjp(lambda *d: b(d, i), *d)[1](g)
        for b in _branches(fn, variants)], diff, ints, g)


def _cond_recomputed(index, fn, variants, diff, ints):
    """``lax.switch(index, branches, *diff, *ints)`` with branch ``j`` being
    ``fn(*diff, *ints, **dict(variants[j]))``, differentiable in ``diff``,
    that keeps nothing of a branch for the backward pass: the backward pass
    is a second conditional whose taken branch runs its own forward again
    and then its backward. Differentiated as it stands, a conditional hands
    the backward pass the residuals of all its branches, and the branch
    that runs fills those of the others with zeros of their shapes: with
    the buffer of every pair beside the usual one that was 0.3 ms of
    ``broadcast`` an array and a tenth of the expert layers' time (PERF.md
    section 6, PR 31). Here a branch that is not taken costs no device
    time, however many there are. Under a ``ResidualBlock`` that recomputes
    its layer the forward inside the backward is the recomputation itself:
    the recomputed layer's own call of the experts feeds nothing and goes.

    Both conditionals are jitted on ``fn`` and ``variants`` (a module-level
    function and a tuple of tuples of keyword pairs: hashable), so layers
    of one shape share one trace of every branch and of its backward: a
    process traces its step before the first one whether or not the
    compiled program is cached, and a branch is its layer's whole expert
    path to trace (PERF.md section 6, PR 35)."""
    @jax.custom_vjp
    def run(index, diff, ints):
        return _switch(index, diff, ints, fn=fn, variants=variants)

    def fwd(index, diff, ints):
        return run(index, diff, ints), (index, diff, ints)

    def bwd(res, g):
        index, diff, ints = res
        grads = _switch_vjp(index, diff, ints, g, fn=fn, variants=variants)
        return no_cotangent(index), grads, no_cotangent(ints)

    run.defvjp(fwd, bwd)
    return run(index, diff, ints)


def _experts(w1, w2, u, wflat, order, counts, *, cap: int, k: int,
             gated: bool, impl: str):
    """The held experts over a buffer that holds ``cap`` pairs in the
    tile-aligned layout (ops/grouped_matmul.py ``tile_layout``): buffer row
    ``starts[g] + p`` is the ``p``-th pair sorted to expert ``g``; the rows
    between a group's count and its next tile gather token 0 under weight 0,
    and the products give them exact zeros. Returns the tokens' sums, the
    pairs computed, the rows the products ran and the rows the buffer has."""
    n_tiles = tiles_needed(cap, counts.shape[0])
    lay = tile_layout(counts, n_tiles)
    gid, pos, valid = row_layout(lay)
    first = jnp.cumsum(counts) - counts         # a group's first sorted pair
    pair = jnp.take(order, jnp.where(valid, jnp.take(first, gid) + pos, 0))
    tok = jnp.where(valid, pair // k, 0)
    w = jnp.where(valid, jnp.take(wflat, pair), 0.0).astype(u.dtype)
    xs = jnp.take(u, tok, axis=0)
    h = _expert_act(grouped_matmul(xs, w1, counts, impl=impl), gated)
    y = grouped_matmul(h, w2, counts, impl=impl) * w[:, None]
    out = jnp.zeros_like(u).at[tok].add(y)
    return (out, jnp.sum(valid).astype(jnp.float32),
            (lay.n_occ[0] * ROW_TILE).astype(jnp.float32),
            jnp.float32(n_tiles * ROW_TILE))


@register_layer("sparse_moe")
@dataclass
class SparseMoE(LayerConfig):
    """Dropless top-k expert layer that knows its share (no residual, no
    pre-norm: wrap it in a ``ResidualBlock``).

    The router scores all ``n_experts`` in float32, ``s = sigmoid(u W_r)``,
    takes the ``top_k`` experts with the largest ``s + bias`` (``bias`` is a
    buffer in the layer's state that no gradient moves), and weighs each by
    ``s_e / (sum_chosen s + norm_topk_eps)`` (``norm_topk``) times
    ``routed_scaling``. An
    expert is ``relu(u W1_e)^2 W2_e``; the shared expert has the same form
    and sees every token. ``gated`` makes both ``(silu(u W_g) * (u W_u))
    W2``, with ``W1 = [W_g | W_u]`` side by side (twice ``expert_width``
    columns, one product).

    The layer holds experts ``held_start .. held_start + n_held - 1`` only
    (``n_held = 0`` means all of them): it routes over all ``n_experts`` and
    computes the part of the sum its own experts give, plus the shared
    expert; what the absent experts would have added is left out.

    No token-expert pair is dropped. The pairs are sorted by expert, held
    ones first, and gathered into one buffer in which every held expert's
    rows start at a multiple of a 128-row tile (``tile_layout``); the two
    expert products run over it as grouped matmuls
    (ops/grouped_matmul.py: Pallas kernels on the TPU, whose cost follows
    the occupied tiles, not the buffer), and the results are scatter-added
    back to their tokens. The buffer has one of up to four static sizes
    (``row_caps``): the even share of the pairs, twice it, the geometric
    mean of that and every pair, and every pair, which nothing can pass. A
    step takes the smallest that holds the pairs its own router sent here:
    the gathers, the activation and the scatter-add pay for the buffer's
    rows, and how the load is spread over the held experts changes no
    shape. The choice is a conditional that keeps nothing for the backward
    pass (``_cond_recomputed``), so a size that is not taken costs no device
    time. Under an active mesh the products are the kernels' plain XLA form
    (the layer has no mesh path of its own yet) over the same sizes.
    """

    n_experts: int = 8
    top_k: int = 2
    expert_width: int = 0
    shared_width: int = 0           # 0: no shared expert
    held_start: int = 0
    n_held: int = 0                 # 0: all of them
    routed_scaling: float = 1.0
    norm_topk: bool = True
    norm_topk_eps: float = 0.0      # added to the chosen scores' sum
    gated: bool = False
    weight_init: Any = "xavier"

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def _held(self) -> int:
        return self.n_held or self.n_experts

    def init(self, key, input_type, dtype=jnp.float32):
        C, F, E = input_type.size, self.expert_width, self._held()
        if self.held_start + E > self.n_experts:
            raise ValueError(
                f"experts {self.held_start}..{self.held_start + E - 1} are not "
                f"among the router's {self.n_experts}")
        kr, k1, k2, ks1, ks2 = jax.random.split(key, 5)
        init = lambda k, shape: initializers.initialize(    # noqa: E731
            self.weight_init, k, shape, shape[-2], shape[-1], dtype)
        up = 2 if self.gated else 1
        p = {"Wr": init(kr, (C, self.n_experts)),
             "W1": init(k1, (E, C, up * F)), "W2": init(k2, (E, F, C))}
        if self.shared_width:
            p["Ws1"] = init(ks1, (C, up * self.shared_width))
            p["Ws2"] = init(ks2, (self.shared_width, C))
        return p

    def init_state(self, input_type: InputType):
        return {"bias": jnp.zeros((self.n_experts,), jnp.float32),
                "stats": jnp.zeros((len(_MOE_STATS),), jnp.float32)}

    @staticmethod
    def stats_dict(stats) -> dict:
        """The state's ``"stats"`` vector under its counters' names."""
        return {k: float(v) for k, v in zip(_MOE_STATS, np.asarray(stats))}

    def publish_stats(self, index: int, stats) -> None:
        """One step's counters of this layer into the ``obs`` registry (the
        host got them with the step's loss, ``MultiLayerNetwork.fit``)."""
        from deeplearning4j_tpu import obs

        layer = str(index)
        obs.counter("dl4j_moe_steps_total", "steps whose counters the host "
                    "fetched", ("layer",)).inc(1, layer=layer)
        for (key, help_), value in zip(_MOE_STATS.items(), np.asarray(stats)):
            obs.counter(f"dl4j_moe_{key}_total", help_, ("layer",)).inc(
                float(value), layer=layer)

    def row_caps(self, n_tokens: int) -> tuple:
        """The sizes of the pair buffer (token-expert pairs one step can
        hold), ascending: what an even router sends here, twice that, the
        geometric mean of twice that and every pair, and every pair, each in
        whole row tiles and none over every pair (one size where the layer
        holds every expert). The products cost what the occupied row tiles
        cost in any of them; the gathers of the rows, the activation and
        the combine pay for the buffer, so a step takes the smallest that
        holds its pairs."""
        pairs, held = n_tokens * self.top_k, self._held()
        even = -(-pairs * held // self.n_experts)
        twice = -(-2 * pairs * held // self.n_experts)
        between = math.isqrt(max(twice * pairs - 1, 0)) + 1     # sqrt, up
        return tuple(sorted({
            min(pairs, -(-rows // ROW_TILE) * ROW_TILE)         # whole tiles
            for rows in (even, twice, between, pairs)}))

    def _route(self, params, bias, u):
        s = jax.nn.sigmoid(jnp.matmul(
            u.astype(jnp.float32), params["Wr"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, eid = jax.lax.top_k(s + bias, self.top_k)              # [N, k]
        w = jnp.take_along_axis(s, eid, axis=-1)
        if self.norm_topk:
            total = jnp.sum(w, axis=-1, keepdims=True)
            if self.norm_topk_eps:
                total = total + self.norm_topk_eps
            w = w / total
        return eid, w * self.routed_scaling

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.parallel.context import partitioning_mesh

        x = self.maybe_dropout_input(x, train, rng)
        B, T, C = x.shape
        N, k, E = B * T, self.top_k, self._held()
        u = x.reshape(N, C)
        with jax.named_scope("moe"):
            with jax.named_scope("router"):
                eid, w = self._route(params, state["bias"], u)
                local = (eid - self.held_start).reshape(N * k)
                held = (local >= 0) & (local < E)
                if mask is not None and mask.ndim >= 2:
                    held = held & jnp.repeat(mask.reshape(N) > 0, k)
                key = jnp.where(held, local, E)                   # absent pairs last
                order = jnp.argsort(key, stable=True).astype(jnp.int32)
                counts = jnp.sum(key[:, None] == jnp.arange(E)[None], axis=0,
                                 dtype=jnp.int32)
            with jax.named_scope("experts"):
                # no SparseMoE mesh path yet (ROADMAP R2): under an active
                # mesh the products are plain XLA, so that no Mosaic call
                # meets GSPMD's partitioner
                kernels = (partitioning_mesh() is None
                           and jax.default_backend() == "tpu")
                common = (("k", k), ("gated", self.gated),
                          ("impl", "pallas" if kernels else "plain"))
                caps = self.row_caps(N)
                sizes = tuple((("cap", c),) + common for c in caps)
                diff = (params["W1"], params["W2"], u, w.reshape(N * k))
                if len(caps) == 1:
                    y, done, rows, buffer = _experts(
                        *diff, order, counts, **dict(sizes[0]))
                else:
                    # the smallest buffer that holds the step's pairs: the
                    # sizes they exceed come before it
                    taken = jnp.sum(jnp.sum(counts) > jnp.array(caps[:-1]),
                                    dtype=jnp.int32)
                    y, done, rows, buffer = _cond_recomputed(
                        taken, _experts, sizes, diff, (order, counts))
            if self.shared_width:
                with jax.named_scope("shared"):
                    h = _expert_act(u @ params["Ws1"], self.gated)
                    y = y + h @ params["Ws2"]
            pairs = jnp.sum(counts).astype(jnp.float32)
            stats = jnp.stack([pairs, jnp.max(counts).astype(jnp.float32),
                               pairs / E, pairs - done, rows,
                               buffer])                    # _MOE_STATS' order
        return y.reshape(B, T, C), {"bias": state["bias"],
                                    "stats": jax.lax.stop_gradient(stats)}
