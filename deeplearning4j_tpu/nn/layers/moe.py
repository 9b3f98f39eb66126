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
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn import initializers
from deeplearning4j_tpu.nn.config import LayerConfig, register_layer
from deeplearning4j_tpu.nn.input_type import InputType


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


# a step's counters of a SparseMoE layer, kept in its state under "stats" as
# one float32 vector in this order (one transfer a layer when the host
# fetches them) and added up by publish_stats as dl4j_moe_<key>_total{layer}
_MOE_STATS = {
    "pairs_held": "token-expert pairs sent to held experts",
    "load_max": "largest load of a held expert, summed over steps",
    "load_mean": "mean load of a held expert, summed over steps",
    "pairs_dropped": "token-expert pairs of held experts not computed (always 0)",
}


def _round_up(n: int, m: int) -> int:
    return -(-int(n) // m) * m


def _layout(counts, M: int, win: int):
    """Where the groups lie in a buffer of ``M`` rows sorted by group, and
    their windows: ``rows`` [G, win] the buffer rows of each group's window
    (from its start), ``valid`` the window slots inside the group's count,
    ``gid`` [M] the group of each buffer row (``G`` past the counts' sum) and
    ``back`` [M] where a buffer row's result sits in the windows laid end to
    end."""
    G = counts.shape[0]
    ends = jnp.cumsum(counts)
    starts = ends - counts
    slot = jnp.arange(win)
    rows = jnp.minimum(starts[:, None] + slot[None], M - 1)
    valid = slot[None] < counts[:, None]
    r = jnp.arange(M)
    gid = jnp.sum(r[:, None] >= ends[None], axis=1)
    g = jnp.minimum(gid, G - 1)
    back = jnp.where(gid < G, g * win + r - jnp.take(starts, g), 0)
    return rows, valid, gid, back


def _grouped(x, w, counts, windowed: bool):
    """``y[r] = x[r] @ w[group of r]`` for the rows of a buffer sorted by
    group, zeros past the counts' sum. A group's rows are contiguous, so
    where no group has more than a third of the buffer (checked on the
    step's own counts) each group reads a window of that many rows from its
    start, masked past its count, the groups' products are one batched
    matmul over ``G * M / 3`` rows, and every buffer row picks its result out
    of its group's window: gathers and a batched matmul, no scatter.
    Otherwise (or where ``windowed`` is false: the buffer that holds every
    pair) each group takes the whole buffer under its mask, ``G * M`` rows:
    the price of an imbalance no router should show."""
    M, G = x.shape[0], counts.shape[0]
    win = _round_up(M / 3, 128)
    rows, valid, gid, back = _layout(counts, M, win)

    def window():
        xw = jnp.take(x, rows, axis=0) * valid[..., None].astype(x.dtype)
        yw = jnp.einsum("gwk,gkn->gwn", xw, w).reshape(G * win, w.shape[2])
        return jnp.where((gid < G)[:, None], jnp.take(yw, back, axis=0), 0.0)

    def whole():
        def add(y, gw):
            g, wg = gw
            return y + jnp.where((gid == g)[:, None], x @ wg, 0.0), None

        y0 = jnp.zeros((M, w.shape[2]), x.dtype)
        return jax.lax.scan(add, y0, (jnp.arange(G), w))[0]

    if not windowed or win >= M:
        return whole()
    return jax.lax.cond(jnp.max(counts) <= win, window, whole)


def _grouped_dw(x, dy, counts, windowed: bool):
    """``dw[g] = x_g^T dy_g`` over group ``g``'s rows, by the same windows as
    ``_grouped`` (or the whole buffer under each group's mask)."""
    M, G = x.shape[0], counts.shape[0]
    win = _round_up(M / 3, 128)
    rows, valid, gid, _ = _layout(counts, M, win)

    def window():
        xw = jnp.take(x, rows, axis=0) * valid[..., None].astype(x.dtype)
        return jnp.einsum("gwk,gwn->gkn", xw, jnp.take(dy, rows, axis=0))

    def whole():
        return jax.lax.map(
            lambda g: jnp.matmul((x * (gid == g)[:, None].astype(x.dtype)).T, dy),
            jnp.arange(G))

    if not windowed or win >= M:
        return whole()
    return jax.lax.cond(jnp.max(counts) <= win, window, whole)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(x, w, counts, windowed: bool):
    """``x`` [M, K] with its rows sorted by group, ``w`` [G, K, N],
    ``counts`` [G] (their sum at most ``M``): row ``r`` of group ``g`` gives
    ``x[r] @ w[g]``, a row past the counts' sum gives zeros. Forward, ``dx``
    and ``dw`` are all ``_grouped``'s windows: plain XLA matmuls.
    (``lax.ragged_dot`` was tried in their place on the chip: XLA's grouped
    kernel ran the float32 products at 22 TFLOP/s, left the rows past the
    counts' sum unwritten, and its weight-gradient form is a lone rank-3
    custom call, which the benchmark's accepted pattern for the flash dq
    kernel would read as one; PERF.md, PR 28.)"""
    return _grouped(x, w, counts, windowed)


def _grouped_matmul_fwd(x, w, counts, windowed):
    return _grouped(x, w, counts, windowed), (x, w, counts)


def _grouped_matmul_bwd(windowed, res, dy):
    x, w, counts = res
    return (_grouped(dy, jnp.swapaxes(w, 1, 2), counts, windowed),
            _grouped_dw(x, dy, counts, windowed).astype(w.dtype),
            np.zeros(counts.shape, jax.dtypes.float0))


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


@register_layer("sparse_moe")
@dataclass
class SparseMoE(LayerConfig):
    """Dropless top-k expert layer that knows its share (no residual, no
    pre-norm: wrap it in a ``ResidualBlock``).

    The router scores all ``n_experts`` in float32, ``s = sigmoid(u W_r)``,
    takes the ``top_k`` experts with the largest ``s + bias`` (``bias`` is a
    buffer in the layer's state that no gradient moves), and weighs each by
    ``s_e / sum_chosen s`` (``norm_topk``) times ``routed_scaling``. An
    expert is ``relu(u W1_e)^2 W2_e``; the shared expert has the same form
    and sees every token.

    The layer holds experts ``held_start .. held_start + n_held - 1`` only
    (``n_held = 0`` means all of them): it routes over all ``n_experts`` and
    computes the part of the sum its own experts give, plus the shared
    expert; what the absent experts would have added is left out.

    No token-expert pair is dropped. The pairs are sorted by expert, held
    ones first; the first ``cap`` of them are gathered into one buffer, the
    two expert products run over it as grouped matmuls (``grouped_matmul``),
    and the results are scatter-added back to their tokens. ``cap`` is taken
    per step from a short static ladder by the number of pairs that landed
    here (``row_caps``: twice, four and eight times the even share, then all
    ``top_k * N``, which nothing can pass): shapes stay static under ``jit``, the usual step pays for a window
    of a third of that buffer per held expert (``_grouped``), not for
    ``top_k * N`` rows, and how the load is spread over the held experts
    changes no shape and no cost.
    """

    n_experts: int = 8
    top_k: int = 2
    expert_width: int = 0
    shared_width: int = 0           # 0: no shared expert
    held_start: int = 0
    n_held: int = 0                 # 0: all of them
    routed_scaling: float = 1.0
    norm_topk: bool = True
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
        p = {"Wr": init(kr, (C, self.n_experts)),
             "W1": init(k1, (E, C, F)), "W2": init(k2, (E, F, C))}
        if self.shared_width:
            p["Ws1"] = init(ks1, (C, self.shared_width))
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
        """The ladder of buffer sizes (rows of token-expert pairs computed in
        one step): twice, four and eight times what an even router sends
        here, then every pair. A step whose pairs pass one size pays for the
        next, about twice as much in that layer, never for ``top_k * N`` at
        once."""
        pairs = n_tokens * self.top_k
        even = pairs * self._held() / self.n_experts
        return tuple(sorted({min(pairs, _round_up(m * even, 256))
                             for m in (2, 4, 8)} | {pairs}))

    def _route(self, params, bias, u):
        s = jax.nn.sigmoid(jnp.matmul(
            u.astype(jnp.float32), params["Wr"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, eid = jax.lax.top_k(s + bias, self.top_k)              # [N, k]
        w = jnp.take_along_axis(s, eid, axis=-1)
        if self.norm_topk:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        return eid, w * self.routed_scaling

    def _experts(self, params, u, order, counts, wflat, cap: int, windowed: bool):
        """The held experts over the first ``cap`` pairs of the sorted list;
        rows past the pairs that landed here are masked to nothing."""
        k = self.top_k
        total = jnp.sum(counts)
        pair = order[:cap]
        valid = jnp.arange(cap) < total
        tok = jnp.where(valid, pair // k, 0)
        w = jnp.where(valid, jnp.take(wflat, pair), 0.0).astype(u.dtype)
        xs = jnp.take(u, tok, axis=0)                             # [cap, C]
        h = jnp.square(jax.nn.relu(
            grouped_matmul(xs, params["W1"], counts, windowed)))
        y = grouped_matmul(h, params["W2"], counts, windowed) * w[:, None]
        out = jnp.zeros_like(u).at[tok].add(y)
        return out, jnp.minimum(total, cap).astype(jnp.float32)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
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
                caps = self.row_caps(N)
                step = sum((jnp.sum(counts) > c).astype(jnp.int32)
                           for c in caps[:-1])
                y, done = jax.lax.switch(
                    step, [functools.partial(self._experts, cap=c,
                                             windowed=c < N * k) for c in caps],
                    params, u, order, counts, w.reshape(N * k))
            if self.shared_width:
                with jax.named_scope("shared"):
                    h = jnp.square(jax.nn.relu(u @ params["Ws1"]))
                    y = y + h @ params["Ws2"]
            pairs = jnp.sum(counts).astype(jnp.float32)
            stats = jnp.stack([pairs, jnp.max(counts).astype(jnp.float32),
                               pairs / E, pairs - done])     # _MOE_STATS' order
        return y.reshape(B, T, C), {"bias": state["bias"],
                                    "stats": jax.lax.stop_gradient(stats)}
