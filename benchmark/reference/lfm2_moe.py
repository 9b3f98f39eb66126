"""Plain reference for the ``lfm2_moe`` family (Liquid AI's LFM2 expert
models: gated short convolutions and grouped-query attention, gated experts
with sigmoid scores, a head tied to the embedding): float32 ``jax.numpy`` at
``highest`` matmul precision, no kernels, no sorted dispatch. It imports
nothing of the program and takes nothing the program made: the weights come
from ``make_weights`` below, which the drivers also use to fill the program
(benchmark/families/lfm2_moe.py).

The equations (the keys of the source's ``config.json``; what they do not
give is listed in the configuration file under ``assumed``). ``n(x; g) = g *
x / sqrt(mean(x^2) + norm_eps)``. Layer ``l`` is pre-norm residual twice,
``h = x + Op_l(n(x; g_op))`` then ``y = h + FFN_l(n(h; g_ffn))``; after the
last layer ``n(.; g_f)`` and the logits ``h E^T`` with ``E`` the embedding
matrix (the head is tied to it). No bias anywhere. ``u`` is a half-layer's
normed input.

- ``Op`` where ``layer_types[l] == "conv"``: ``[B | C | x] = u W_in``
  (``W_in`` [d, 3d], the three streams in that order); ``z = B * x``; ``c_t =
  sum_j w_j * z_{t-(k-1-j)}`` with ``k = conv_L_cache`` taps a channel and
  ``z`` zero before the row's start; ``Op = (C * c) W_out``. No activation
  function.
- ``Op`` where ``layer_types[l] == "full_attention"``: ``q = u W_q``
  (``num_attention_heads`` of ``head_dim``), ``k = u W_k`` and ``v = u W_v``
  (``num_key_value_heads``); ``q <- n(q; g_q)``, ``k <- n(k; g_k)`` over each
  head's lanes with one gain vector for all heads; rotary positions on all
  lanes of q and k, lane ``i`` paired with lane ``i + head_dim/2`` ("rotate
  half"), angle ``t * rope_theta^(-2i/head_dim)``; causal ``softmax(q k^T /
  sqrt(head_dim)) v``, key-value head ``j`` serving query heads ``j * rep ..
  (j+1) * rep - 1``; ``Op = o W_o``. The scores are computed a head and a
  block of query rows at a time, as the full masked rows of that block.
- ``FFN`` for ``l < num_dense_layers``: ``(silu(u W_1) * (u W_3)) W_2`` of
  width ``intermediate_size``.
- ``FFN`` otherwise: ``s = sigmoid(u W_r)`` over all ``router_experts`` in
  float32; the ``num_experts_per_tok`` experts with the largest ``s + b``
  (``use_expert_bias``: ``b`` a buffer no gradient moves, zero here);
  weights ``s_e / (sum_chosen s + 1e-6)`` (``norm_topk_prob``) times
  ``routed_scaling_factor``; each expert the gated form at
  ``moe_intermediate_size``; no shared expert. This reference holds experts
  ``held_experts_start .. + num_experts - 1`` of the router's
  ``router_experts``, as the program does, loops over them with a mask, and
  leaves out what the absent ones would have added.

Departures, which follow the program the benchmark measures and are stated in
the configuration file: the loss is the mean over rows of the *sum* over
positions of the cross-entropy (``mcxent`` over ``[B, T, V]``), the labels
the ids rolled by one with the wrap-around position kept.

``lowp`` runs the same mathematics in a lower precision and is what the
controls of ``correct`` use, as in benchmark/reference/nemotron_h.py.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# the seed as a key's words, Adam's constants and step, the rounding of the
# controls: one definition for the references (a reference imports nothing of
# the program; another reference is not the program)
from benchmark.reference.gpt2 import ADAM, seed_words  # noqa: F401
# the gated feed-forward and the dense layer's run of rows at a time are the
# latent-attention reference's, whose leaves have the same names
from benchmark.reference.deepseek_mla import dense, gated
from benchmark.reference.nemotron_h import (  # noqa: F401
    _BF16, _HI, _acc, _adam, _largest_divisor, _mm, _rms, _round,
    _to_bf16_and_back, cfg_key as _scalars_key)

# a layer's leaves by its operator and by its feed-forward
OPS = {
    "conv": ("c_in", "c_conv", "c_out"),
    "full_attention": ("a_q", "a_k", "a_v", "a_o", "a_qnorm", "a_knorm"),
}
FFNS = {
    "dense": ("f_gate", "f_up", "f_down"),
    "expert": ("e_router", "e_gate", "e_up", "e_down"),
}
# query rows of one head scored at once: 1,024 float32 score rows over 8,192
# keys are 34 MB a row of the batch
Q_ROWS = 1024
# the denominator of the normalised routing weights (the family's modelling
# code; the config has no key for it)
ROUTE_EPS = 1e-6
# leaves outside the layers whose first gradient is kept whole
KEPT_WHOLE = ("normf",)


def dims(cfg: dict) -> dict:
    H = int(cfg["num_attention_heads"])
    d = int(cfg["hidden_size"])
    return dict(
        V=int(cfg["vocab_size"]), d=d, L=int(cfg["num_hidden_layers"]),
        dense=int(cfg["num_dense_layers"]), H=H,
        Hkv=int(cfg["num_key_value_heads"]),
        Dh=int(cfg.get("head_dim") or d // H), k=int(cfg["conv_L_cache"]),
        Fd=int(cfg["intermediate_size"]), F=int(cfg["moe_intermediate_size"]),
        E=int(cfg["num_experts"]),
        R=int(cfg.get("router_experts", cfg["num_experts"])),
        e0=int(cfg.get("held_experts_start", 0)),
        topk=int(cfg["num_experts_per_tok"]),
        theta=float(cfg["rope_theta"]), eps=float(cfg["norm_eps"]))


def cfg_key(cfg: dict):
    """The configuration as a static argument of a jitted function: its
    scalars, and the layer list as a tuple."""
    return _scalars_key(cfg) + (("layer_types", tuple(cfg["layer_types"])),)


def kinds(cfg: dict) -> tuple:
    """(operator, feed-forward) of each layer, in order."""
    D = dims(cfg)
    ops = tuple(cfg["layer_types"])
    if len(ops) != D["L"] or set(ops) - set(OPS):
        raise ValueError(f"layer_types {ops!r} for {D['L']} layers")
    return tuple((op, "dense" if i < D["dense"] else "expert")
                 for i, op in enumerate(ops))


def layer_leaves(kind) -> tuple:
    op, ffn = kind
    return ("norm1",) + OPS[op] + ("norm2",) + FFNS[ffn]


def weight_shapes(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, kind); a layer's leaves are ``<leaf>.<layer index>``.
    There is no head matrix: the head is ``wte``."""
    D = dims(cfg)
    d, H, Hkv, Dh = D["d"], D["H"], D["Hkv"], D["Dh"]
    out = {"wte": ((D["V"], d), "matrix"), "normf": ((d,), "gain")}
    per = {
        "norm1": ((d,), "gain"), "norm2": ((d,), "gain"),
        "c_in": ((d, 3 * d), "matrix"), "c_conv": ((D["k"], d), "conv"),
        "c_out": ((d, d), "matrix"),
        "a_q": ((d, H * Dh), "matrix"), "a_k": ((d, Hkv * Dh), "matrix"),
        "a_v": ((d, Hkv * Dh), "matrix"), "a_o": ((H * Dh, d), "matrix"),
        "a_qnorm": ((Dh,), "gain"), "a_knorm": ((Dh,), "gain"),
        "f_gate": ((d, D["Fd"]), "matrix"), "f_up": ((d, D["Fd"]), "matrix"),
        "f_down": ((D["Fd"], d), "matrix"),
        "e_router": ((d, D["R"]), "matrix"),
        "e_gate": ((D["E"], d, D["F"]), "matrix"),
        "e_up": ((D["E"], d, D["F"]), "matrix"),
        "e_down": ((D["E"], D["F"], d), "matrix"),
    }
    for i, kind in enumerate(kinds(cfg)):
        for leaf in layer_leaves(kind):
            out[f"{leaf}.{i}"] = per[leaf]
    return out


def num_params(cfg: dict) -> int:
    return sum(math.prod(s) for s, _ in weight_shapes(cfg).values())


def make_weights(cfg: dict, words, dtype) -> Dict[str, jax.Array]:
    """All weights from the seed, traceable as one program: matrices
    N(0, 0.02), gains 1 + N(0, 0.02), convolution taps N(0, 0.25) (the
    hybrid configuration's 0.5 / sqrt(4)). Made in float32, rounded once to
    ``dtype``."""
    key = jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32), impl="rbg")
    out = {}
    for i, (name, (shape, kind)) in enumerate(sorted(weight_shapes(cfg).items())):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        x = 0.25 * x if kind == "conv" else 0.02 * x
        out[name] = (1.0 + x if kind == "gain" else x).astype(dtype)
    return out


# ---------------------------------------------------------------------------
# The layers, each over u [B, T, d] (already normed)
# ---------------------------------------------------------------------------


def rope(x, theta: float):
    """Rotary positions over ``x`` [B, T, n, D], position ``t`` the index
    along T: lane ``i < D/2`` and lane ``i + D/2`` rotated as a pair by ``t *
    theta^(-2i/D)`` ("rotate half")."""
    T, D = x.shape[1], x.shape[-1]
    inv = np.power(float(theta), -np.arange(0, D, 2) / D).astype(np.float32)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]    # [T, D/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x0, x1 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x0 * cos - x1 * sin, x1 * cos + x0 * sin], -1)


def conv(cfg, lowp, u, p):
    """The gated short convolution."""
    T = u.shape[1]
    r = functools.partial(_round, lowp=lowp if lowp in _BF16 else None)
    b, c, x = jnp.split(r(_mm(u, p["c_in"], lowp)), 3, -1)
    z = r(b * x)
    k = p["c_conv"].shape[0]
    zp = jnp.pad(z, ((0, 0), (k - 1, 0), (0, 0)))
    taps = sum(zp[:, j:j + T] * p["c_conv"][j] for j in range(k))
    return _mm(r(c * r(taps)), p["c_out"], lowp)


def attention(cfg, lowp, u, p):
    """One head at a time, and within a head one run of query rows at a time
    against all keys. What a head's loop closes over is ``u``: were q, k and
    v of all heads made first, the compiler would make them again early for
    every layer's backward pass and hold them."""
    D = dims(cfg)
    Bsz, T, _ = u.shape
    H, Hkv, Dh, eps = D["H"], D["Hkv"], D["Dh"], D["eps"]
    r = functools.partial(_round, lowp=lowp if lowp in _BF16 else None)
    bq = _largest_divisor(T, Q_ROWS)
    k_pos = jnp.arange(T)

    def made(w, gain):                                  # [B, T, Dh], turned
        x = r(_mm(u, w, lowp))
        if gain is not None:
            x = r(rope(r(_rms(x, gain, eps))[:, :, None], D["theta"]))[:, :, 0]
        return x

    @jax.checkpoint
    def head(w):
        w_q, w_k, w_v = w                               # [d, Dh] each
        q, k, v = made(w_q, p["a_qnorm"]), made(w_k, p["a_knorm"]), made(w_v, None)

        @jax.checkpoint
        def rows(i):
            qi = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, 1)
            s = jnp.einsum("bqd,bkd->bqk", _round(qi, lowp), _round(k, lowp),
                           precision=_HI) / math.sqrt(Dh)
            seen = k_pos[None, :] <= (i * bq + jnp.arange(bq))[:, None]
            w_ = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
            return jnp.einsum("bqk,bkd->bqd", _round(w_, lowp), _round(v, lowp),
                              precision=_HI)

        o = jax.lax.map(rows, jnp.arange(T // bq))               # [n, B, bq, Dh]
        return jnp.moveaxis(o, 0, 1).reshape(Bsz, T, Dh)

    by_head = lambda m, n: jnp.moveaxis(                        # noqa: E731
        m.reshape(m.shape[0], n, Dh), 1, 0)
    rep = H // Hkv                                      # query heads a kv head
    o = jax.lax.map(head, (by_head(p["a_q"], H),
                           jnp.repeat(by_head(p["a_k"], Hkv), rep, 0),
                           jnp.repeat(by_head(p["a_v"], Hkv), rep, 0)))
    o = r(jnp.moveaxis(o, 0, 2).reshape(Bsz, T, H * Dh))
    return _mm(o, p["a_o"], lowp)


def route(cfg, u, w_router, bias=None):
    """Expert ids [N, k] and their weights for tokens ``u`` [N, d]."""
    s = jax.nn.sigmoid(jnp.matmul(u, w_router, precision=_HI))
    _, eid = jax.lax.top_k(s if bias is None else s + bias, dims(cfg)["topk"])
    w = jnp.take_along_axis(s, eid, -1)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + ROUTE_EPS)
    return eid, w * float(cfg.get("routed_scaling_factor", 1.0))


def experts(cfg, lowp, u, p):
    D = dims(cfg)
    Bsz, T, d = u.shape
    x = u.reshape(Bsz * T, d)
    # the router stays float32; its selection bias is zero unless given
    eid, w = route(cfg, x, p["e_router"], p.get("e_bias"))

    @jax.checkpoint
    def one(e, w_gate, w_up, w_down):
        gate = jnp.sum(jnp.where(eid == D["e0"] + e, w, 0.0), -1)
        return gate[:, None] * gated(lowp, x, w_gate, w_up, w_down)

    # the held experts, one by one over all tokens under their masks: a scan,
    # so that the backward pass holds one expert's activations at a time
    y, _ = jax.lax.scan(lambda acc, ew: (acc + one(*ew), None),
                        jnp.zeros_like(x),
                        (jnp.arange(D["E"]), p["e_gate"], p["e_up"], p["e_down"]))
    return y.reshape(Bsz, T, d)


HALVES = {"conv": conv, "full_attention": attention, "dense": dense,
          "expert": experts}


def _half(cfg, lowp, fn, norm, x, p):
    r = functools.partial(_round, lowp=lowp if lowp in _BF16 else None)
    return r(x + fn(cfg, lowp, r(_rms(x, p[norm], dims(cfg)["eps"])), p))


def layer_weights(w: dict, i: int, kind) -> dict:
    """Layer ``i``'s leaves under their bare names."""
    return {k: w[f"{k}.{i}"] for k in layer_leaves(kind)}


def block(cfg, lowp, kind, x, p):
    """One layer: both halves, each recomputed in the backward pass."""
    for name, norm in zip(kind, ("norm1", "norm2")):
        x = jax.checkpoint(functools.partial(
            _half, cfg, lowp, HALVES[name], norm))(x, p)
    return x


def trunk(cfg, w, ids, lowp=None):
    """The last layer's output [B, T, d], before the final norm."""
    x = _round(jnp.take(w["wte"], ids, axis=0), lowp if lowp in _BF16 else None)
    for i, kind in enumerate(kinds(cfg)):
        x = block(cfg, lowp, kind, x, layer_weights(w, i, kind))
    return x


def head_nll(cfg, w, x, labels, lowp=None):
    """Cross-entropy [B, T] of ``RMSNorm(x) wte^T`` against ``labels``, a
    run of positions at a time (the logits of 2 x 8,192 positions at once
    are a gigabyte, and their gradient as much again)."""
    r = functools.partial(_round, lowp=lowp if lowp in _BF16 else None)
    Bsz, T, d = x.shape
    run = _largest_divisor(T, 2048)

    @jax.checkpoint
    def some(xl):
        xs, ls = xl
        z = r(_mm(r(_rms(xs, w["normf"], dims(cfg)["eps"])), w["wte"].T, lowp))
        return -jnp.take_along_axis(jax.nn.log_softmax(z, -1), ls[..., None],
                                    -1)[..., 0]

    cut = lambda t: jnp.moveaxis(                               # noqa: E731
        t.reshape((Bsz, T // run, run) + t.shape[2:]), 1, 0)
    nll = jax.lax.map(some, (cut(x), cut(labels)))              # [n, B, run]
    return jnp.moveaxis(nll, 0, 1).reshape(Bsz, T)


def loss_rows(cfg, w, ids, labels, lowp=None, positions=None):
    """Sum over the given rows of the sum over positions (the first
    ``positions`` of them, if given) of the cross-entropy; the caller
    divides by the batch's rows."""
    nll = head_nll(cfg, w, trunk(cfg, w, ids, lowp), labels, lowp)
    return jnp.sum(nll[:, :positions])


# ---------------------------------------------------------------------------
# Training: Adam steps, row block by row block
# ---------------------------------------------------------------------------


def leaf_sq_norms(tree):
    """Squared norm per leaf, an expert stack as one leaf (a single expert's
    share of a gradient hangs on the few tokens a near-tie sends it or not);
    the convolution's taps one each, [k], so that most entries are small
    leaves and the comparison's floor, the median entry, is a small leaf's
    norm (PERF.md section 4)."""
    return {k: jnp.sum(jnp.square(v.astype(jnp.float32)),
                       axis=1 if k.startswith("c_conv.") else None)
            for k, v in tree.items()}


def kept_names(cfg: dict, layers) -> tuple:
    """The leaves whose first gradient is compared whole: the driver's layers
    (first, middle, last) and the first attention layer beside them, so that
    a layer of each kind is among them; the embedding, whose gradient is the
    sum of its two uses; the final gain."""
    ks = kinds(cfg)
    attn = {i for i, (op, _) in enumerate(ks) if op == "full_attention"}
    keep = {int(i) for i in layers} | set(sorted(attn)[:1])
    return KEPT_WHOLE + ("wte",) + tuple(
        f"{leaf}.{i}" for i in sorted(keep) for leaf in layer_leaves(ks[i]))


@functools.partial(jax.jit, static_argnames=("cfg_key", "lowp", "n", "positions"))
def _grad_block(cfg_key, lowp, n, positions, w, ids, labels):
    cfg = dict(cfg_key)
    return jax.value_and_grad(
        lambda p: loss_rows(cfg, p, ids, labels, lowp, positions) / n)(w)


def train_steps(cfg: dict, w0: Dict[str, jax.Array],
                batches: Sequence[Tuple[np.ndarray, np.ndarray]],
                lr: float, rows: int = 2, lowp: Optional[str] = None,
                faults: Sequence[str] = (), keep_layers: Sequence[int] = ()):
    """Follow the program's first steps, as ``reference/nemotron_h.py`` does
    (the same returns, the same ``half_batch`` fault, the start's device
    buffers given to the update)."""
    key = cfg_key(cfg)
    start = {k: np.asarray(v, np.float32) for k, v in w0.items()}
    w = {k: jnp.asarray(v, jnp.float32) for k, v in w0.items()}
    if lowp == "bfloat16":
        w = _to_bf16_and_back(w)
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, g1, kept = [], None, None
    store = "bfloat16" if lowp == "bfloat16" else None
    names = kept_names(cfg, keep_layers)
    for step, (x, y) in enumerate(batches):
        positions = None
        if "half_batch" in faults:
            if len(x) > 1:
                x, y = x[: len(x) // 2], y[: len(y) // 2]
            else:
                positions = x.shape[1] // 2
        n = len(x)
        total, grads = 0.0, None
        for i in range(0, n, rows):
            l, g = _grad_block(key, lowp, n, positions, w,
                               jnp.asarray(x[i:i + rows]),
                               jnp.asarray(y[i:i + rows]))
            total = total + l
            grads = g if grads is None else _acc(grads, g)
        losses.append(float(total))
        if step == 0:
            g1 = {k: np.sqrt(np.asarray(s))
                  for k, s in leaf_sq_norms(grads).items()}
            kept = {k: np.asarray(grads[k]) for k in names}
        w, m, v = _adam(w, m, v, grads, step, lr=float(lr), store=store)
        del grads
    del m, v
    change = {}
    for k in sorted(w):                 # leaf by leaf: no second copy of w
        (name, sq), = leaf_sq_norms({k: w[k] - start[k]}).items()
        change[name] = np.sqrt(np.asarray(sq))
    return {"losses": losses, "grad_norms": g1, "grad_leaves": kept,
            "change_norms": change}
