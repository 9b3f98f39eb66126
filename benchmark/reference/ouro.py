"""Plain reference for the ``ouro`` family (ByteDance's looped language
models, "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741): float32 ``jax.numpy`` at ``highest`` matmul precision, no
kernels. It imports nothing of the program and takes nothing the program
made: the weights come from ``make_weights`` below, which the drivers also
use to fill the program (benchmark/families/ouro.py).

The equations (the keys of the source's ``config.json``; what they do not
give is listed in the configuration file under ``assumed``). ``n(x; g) = g *
x / sqrt(mean(x^2) + rms_norm_eps)``, every ``n`` with a gain of its own.

- ``h^0 = E[ids]``.
- Loop ``t = 1 .. total_ut_steps``, from ``x = h^{t-1}``, every layer
  ``l`` in turn: ``x <- x + n(Attn_l(n(x; g1_l)); g2_l)``, then ``x <- x +
  n(MLP_l(n(x; g3_l)); g4_l)`` (the sandwich norm); then ``h^t = n(x;
  g_f)``, which the next loop starts from.
- ``Attn``: ``q = u W_q``, ``k = u W_k``, ``v = u W_v``
  (``num_attention_heads`` = ``num_key_value_heads`` heads of ``head_dim``),
  no bias, no norm of q or k; rotary positions 0 .. T-1 on all lanes of q and
  k, lane ``i`` paired with lane ``i + head_dim/2`` ("rotate half"), angle
  ``t * rope_theta^(-2i/head_dim)``, the same in every loop; causal
  ``softmax(q k^T / sqrt(head_dim)) v``; ``o W_o``. Scored a head and a
  block of query rows at a time.
- ``MLP``: ``(silu(u W_gate) * (u W_up)) W_down``, ``intermediate_size``
  wide.
- After every loop: logits ``z^t = h^t W_head`` (untied, no bias) and the
  exit gate ``lambda^t = sigmoid(h^t w_g + b_g)``.
- Exit distribution of a token: ``p_1 = lambda^1``, ``p_t = lambda^t
  prod_{j<t} (1 - lambda^j)``, ``p_T = prod_{j<T} (1 - lambda^j)``.
- Loss: the mean over tokens of ``sum_t p_t CE(z^t, y) - beta H(p)``,
  ``H(p) = -sum_t p_t log p_t``, ``beta`` = ``exit_entropy_beta``.

Every block application is recomputed in the backward pass
(``jax.checkpoint``), and each loop's head is taken a run of positions at a
time, so that the reference fits on the chip beside nothing. The loops read
one weight set; ``per_loop`` lets a test give each loop a copy of its own
and add their gradients.

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

# the seed as a key's words and Adam's constants, one definition for the
# references (a reference imports nothing of the program)
from benchmark.reference.gpt2 import ADAM, seed_words  # noqa: F401
# the gated feed-forward a run of rows at a time is the latent-attention
# reference's, whose leaves have the same names; rotate-half rotary is LFM2's
from benchmark.reference.deepseek_mla import dense
from benchmark.reference.lfm2_moe import rope
from benchmark.reference.nemotron_h import (
    _HI, _acc, _adam, _largest_divisor, _mm, _rms, _round,
    _to_bf16_and_back, cfg_key)

# a layer's leaves: the four gains of the sandwich norm, attention, MLP
LAYER = ("norm1", "a_q", "a_k", "a_v", "a_o", "norm2",
         "norm3", "f_gate", "f_up", "f_down", "norm4")
# leaves outside the layers; the first gradient of every one is kept whole
OUTSIDE = ("wte", "normf", "w_head", "gate_w", "gate_b")
# query rows of one head scored at once, and positions of one head's logits
Q_ROWS = 1024
HEAD_ROWS = 1024


def dims(cfg: dict) -> dict:
    d, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    L = int(cfg["num_hidden_layers"])
    if int(cfg["num_key_value_heads"]) != H:
        raise ValueError("ouro: num_key_value_heads is num_attention_heads")
    # the held layers are the first L entries of the published list; a
    # jitted function's static configuration holds no list, and no check
    if set(cfg.get("layer_types", ("full_attention",))[:L]) != {"full_attention"}:
        raise ValueError(f"ouro: layer_types {cfg['layer_types'][:L]!r}")
    return dict(V=int(cfg["vocab_size"]), d=d, L=L, H=H,
                Dh=int(cfg.get("head_dim") or d // H),
                F=int(cfg["intermediate_size"]),
                T=int(cfg["total_ut_steps"]), theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]),
                beta=float(cfg["exit_entropy_beta"]))


def weight_shapes(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, kind); a layer's leaves are ``<leaf>.<layer index>``."""
    D = dims(cfg)
    d, V, q = D["d"], D["V"], D["H"] * D["Dh"]
    out = {"wte": ((V, d), "matrix"), "normf": ((d,), "gain"),
           "w_head": ((d, V), "matrix"), "gate_w": ((d, 1), "matrix"),
           "gate_b": ((1,), "matrix")}
    per = {"a_q": (d, q), "a_k": (d, q), "a_v": (d, q), "a_o": (q, d),
           "f_gate": (d, D["F"]), "f_up": (d, D["F"]), "f_down": (D["F"], d)}
    for i in range(D["L"]):
        for leaf in LAYER:
            out[f"{leaf}.{i}"] = ((d,), "gain") if leaf.startswith("norm") \
                else (per[leaf], "matrix")
    return out


def num_params(cfg: dict) -> int:
    return sum(math.prod(s) for s, _ in weight_shapes(cfg).values())


def make_weights(cfg: dict, words, dtype) -> Dict[str, jax.Array]:
    """All weights from the seed, traceable as one program: matrices (and
    the gate's bias) N(0, 0.02), gains 1 + N(0, 0.02). Made in float32,
    rounded once to ``dtype``."""
    key = jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32), impl="rbg")
    out = {}
    for i, (name, (shape, kind)) in enumerate(sorted(weight_shapes(cfg).items())):
        x = 0.02 * jax.random.normal(jax.random.fold_in(key, i), shape,
                                     jnp.float32)
        out[name] = (1.0 + x if kind == "gain" else x).astype(dtype)
    return out


# ---------------------------------------------------------------------------
# The layer, over x [B, T, d]
# ---------------------------------------------------------------------------


def attention(cfg, lowp, u, p):
    """One head at a time, and within a head one run of query rows at a time
    against all keys (what a head's loop closes over is ``u``, as in
    benchmark/reference/lfm2_moe.py)."""
    D = dims(cfg)
    Bsz, T, _ = u.shape
    H, Dh = D["H"], D["Dh"]
    bq = _largest_divisor(T, Q_ROWS)
    k_pos = jnp.arange(T)

    def made(w, turn):                                  # [B, T, Dh]
        x = _round(_mm(u, w, lowp), lowp)
        return _round(rope(x[:, :, None], D["theta"])[:, :, 0], lowp) \
            if turn else x

    @jax.checkpoint
    def head(w):
        w_q, w_k, w_v = w                               # [d, Dh] each
        q, k, v = made(w_q, True), made(w_k, True), made(w_v, False)

        @jax.checkpoint
        def rows(i):
            qi = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, 1)
            s = jnp.einsum("bqd,bkd->bqk", qi, k, precision=_HI) / math.sqrt(Dh)
            seen = k_pos[None, :] <= (i * bq + jnp.arange(bq))[:, None]
            w_ = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
            return jnp.einsum("bqk,bkd->bqd", _round(w_, lowp), v,
                              precision=_HI)

        o = jax.lax.map(rows, jnp.arange(T // bq))               # [n, B, bq, Dh]
        return jnp.moveaxis(o, 0, 1).reshape(Bsz, T, Dh)

    by_head = lambda m: jnp.moveaxis(m.reshape(m.shape[0], H, Dh), 1, 0)  # noqa: E731
    o = jax.lax.map(head, (by_head(p["a_q"]), by_head(p["a_k"]),
                           by_head(p["a_v"])))
    o = _round(jnp.moveaxis(o, 0, 2).reshape(Bsz, T, H * Dh), lowp)
    return _mm(o, p["a_o"], lowp)


def _half(cfg, lowp, fn, pre, post, x, p):
    eps = dims(cfg)["eps"]
    y = fn(cfg, lowp, _round(_rms(x, p[pre], eps), lowp), p)
    return _round(x + _rms(_round(y, lowp), p[post], eps), lowp)


def layer(cfg, lowp, x, p):
    """One layer: both sandwich-normed halves, each recomputed in the
    backward pass."""
    for fn, pre, post in ((attention, "norm1", "norm2"),
                          (dense, "norm3", "norm4")):
        x = jax.checkpoint(functools.partial(
            _half, cfg, lowp, fn, pre, post))(x, p)
    return x


def layer_weights(w: dict, i: int) -> dict:
    return {k: w[f"{k}.{i}"] for k in LAYER}


def _own(w: dict, own) -> dict:
    return w if own is None else {**w, **own}


def loop_states(cfg, w, ids, lowp=None, per_loop=None):
    """``[h^1, ..., h^T]`` stacked, [T, B, S, d]: a scan over the loops, so
    that the backward pass adds every loop's gradient of a shared leaf into
    one accumulator (written as a Python loop, the compiler held each
    loop's part of the weights' gradients and 9 GiB of temporaries at the
    cell's size). ``per_loop`` (a test's) gives each loop its own copy of
    some leaves, stacked [T, ...], in place of ``w``'s."""
    D = dims(cfg)

    def loop(h, own):
        wt = _own(w, own)
        for i in range(D["L"]):
            h = layer(cfg, lowp, h, layer_weights(wt, i))
        h = _round(_rms(h, wt["normf"], D["eps"]), lowp)
        return h, h

    h0 = _round(jnp.take(w["wte"], ids, axis=0), lowp)
    return jax.lax.scan(jax.checkpoint(loop), h0, per_loop, length=D["T"])[1]


def head_ce(cfg, w_head, h, labels, lowp=None):
    """Cross-entropy [B, T] of ``h W_head`` against ``labels``, a run of
    positions at a time (one loop's 4,096 x 49,152 logits are 805 MB, and
    their gradient as much again)."""
    Bsz, T, d = h.shape
    run = _largest_divisor(T, HEAD_ROWS)

    @jax.checkpoint
    def some(xl):
        xs, ls = xl
        z = _round(_mm(xs, w_head, lowp), lowp)
        return -jnp.take_along_axis(jax.nn.log_softmax(z, -1), ls[..., None],
                                    -1)[..., 0]

    cut = lambda t: jnp.moveaxis(                               # noqa: E731
        t.reshape((Bsz, T // run, run) + t.shape[2:]), 1, 0)
    ce = jax.lax.map(some, (cut(h), cut(labels)))               # [n, B, run]
    return jnp.moveaxis(ce, 0, 1).reshape(Bsz, T)


def exits(cfg, w, states, labels, lowp=None, per_loop=None):
    """(per-token loss [B, T], exit distribution p [T, B, T], per-loop
    cross-entropy [T, B, T]) of the stacked loop states, written as the
    equations read; each loop's head and gate in a scan over the loops."""
    D = dims(cfg)

    def one(_, x):
        h, own = x
        wt = _own(w, own)
        gate = _mm(h, wt["gate_w"], lowp)[..., 0] + wt["gate_b"][0]
        return None, (head_ce(cfg, wt["w_head"], h, labels, lowp),
                      jax.nn.sigmoid(gate))

    _, (ce, lam) = jax.lax.scan(one, None, (states, per_loop))
    p, stay = [], jnp.ones_like(ce[0])
    for t in range(D["T"] - 1):
        p.append(lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    p = jnp.stack(p + [stay])
    entropy = -jnp.sum(p * jnp.log(p), 0)
    return jnp.sum(p * ce, 0) - D["beta"] * entropy, p, ce


def loss_rows(cfg, w, ids, labels, lowp=None, positions=None, per_loop=None):
    """Sum over the given rows of the sum over positions (the first
    ``positions`` of them, if given) of the per-token loss; the caller
    divides by the tokens."""
    states = loop_states(cfg, w, ids, lowp, per_loop)
    per_token, _, _ = exits(cfg, w, states, labels, lowp, per_loop)
    return jnp.sum(per_token[:, :positions])


# ---------------------------------------------------------------------------
# Training: Adam steps, row block by row block
# ---------------------------------------------------------------------------


def leaf_sq_norms(tree):
    return {k: jnp.sum(jnp.square(v.astype(jnp.float32)))
            for k, v in tree.items()}


def kept_names(cfg: dict, layers) -> tuple:
    """The leaves whose first gradient is compared whole: the driver's layers
    (first, middle, last), and every leaf outside the layers (the head, whose
    gradient is the sum of the loops', the gate, the embedding, the final
    gain)."""
    return OUTSIDE + tuple(f"{leaf}.{i}" for i in sorted({int(i) for i in layers})
                           for leaf in LAYER)


@functools.partial(jax.jit, static_argnames=("cfg_key", "lowp", "n", "positions"))
def _grad_block(cfg_key, lowp, n, positions, w, ids, labels):
    cfg = dict(cfg_key)
    return jax.value_and_grad(
        lambda p: loss_rows(cfg, p, ids, labels, lowp, positions) / n)(w)


def train_steps(cfg: dict, w0: Dict[str, jax.Array],
                batches: Sequence[Tuple[np.ndarray, np.ndarray]],
                lr: float, rows: int = 1, lowp: Optional[str] = None,
                faults: Sequence[str] = (), keep_layers: Sequence[int] = ()):
    """Follow the program's first steps, as ``reference/nemotron_h.py`` does
    (the same returns, the same ``half_batch`` fault: with one row a batch,
    the second half of its positions left out). The loss is a mean over the
    batch's tokens, so a block of rows is divided by all of them."""
    dims(cfg)                           # the layer list is checked here
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
        n = len(x) * (positions or x.shape[1])
        total, grads = 0.0, None
        for i in range(0, len(x), rows):
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
