"""Plain reference for the GPT-2 family: float32 ``jax.numpy``, no kernels,
no cache, no batching tricks. It imports nothing of the program and takes
nothing the program made: the weights come from ``make_weights`` below, which
the drivers also use to fill the program (benchmark/families/gpt2.py).

The equations are GPT-2's (Radford et al. 2019; ``modeling_gpt2.py``):
token + learned position embedding, pre-LN blocks (fused biased qkv, causal
softmax attention at 1/sqrt(head), biased output projection, tanh-GELU MLP of
``n_inner``), a final LayerNorm and a linear head. Departures, which follow
the program the benchmark measures and are stated in the configuration files:
the head is untied (its own ``[d, vocab]`` matrix and bias) and the loss is
the mean over rows of the *sum* over positions of the cross-entropy
(``RnnOutputLayer`` + ``mcxent``), so gradients are ``T`` times those of a
per-token mean. Adam is Kingma & Ba's with bias correction, float32 moments.

``lowp`` runs the same mathematics in a lower precision and is what the
controls of ``correct`` use: ``"bfloat16"`` rounds parameters, activations and
matmul inputs to bfloat16 (parameters stay bfloat16 across updates),
``"bfloat16_compute"`` rounds activations and matmul inputs alike but keeps
the parameters and their updates in float32, ``"fp8"`` rounds every matmul
input to float8_e4m3 under a per-tensor scale.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_KEYS = ("ln1_g", "ln1_b", "w_qkv", "b_qkv", "w_o", "b_o",
              "ln2_g", "ln2_b", "w_fc", "b_fc", "w_pr", "b_pr")
ADAM = {"beta1": 0.9, "beta2": 0.999, "eps": 1e-8}
_BF16 = ("bfloat16", "bfloat16_compute")


def dims(cfg: dict) -> Tuple[int, int, int, int, int, int]:
    d = int(cfg["n_embd"])
    inner = cfg.get("n_inner") or 4 * d
    return (int(cfg["vocab_size"]), int(cfg["n_positions"]), d,
            int(cfg["n_layer"]), int(cfg["n_head"]), int(inner))


def weight_shapes(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, kind). Block tensors carry the layer axis first."""
    V, P, d, L, _, F = dims(cfg)
    return {
        "wte": ((V, d), "matrix"), "wpe": ((P, d), "matrix"),
        "ln1_g": ((L, d), "gain"), "ln1_b": ((L, d), "bias"),
        "w_qkv": ((L, d, 3 * d), "matrix"), "b_qkv": ((L, 3 * d), "bias"),
        "w_o": ((L, d, d), "matrix"), "b_o": ((L, d), "bias"),
        "ln2_g": ((L, d), "gain"), "ln2_b": ((L, d), "bias"),
        "w_fc": ((L, d, F), "matrix"), "b_fc": ((L, F), "bias"),
        "w_pr": ((L, F, d), "matrix"), "b_pr": ((L, d), "bias"),
        "lnf_g": ((d,), "gain"), "lnf_b": ((d,), "bias"),
        "w_head": ((d, V), "matrix"), "b_head": ((V,), "bias"),
    }


def num_params(cfg: dict) -> int:
    return sum(math.prod(s) for s, _ in weight_shapes(cfg).values())


def seed_words(seed: int) -> np.ndarray:
    """A whole-number seed (it may pass 2**31) as the four uint32 words of an
    ``rbg`` key. Passed to the jitted maker as data, so a new seed compiles
    nothing."""
    s = int(seed) % (1 << 64)
    lo, hi = s & 0xFFFFFFFF, s >> 32
    return np.array([lo, hi, lo ^ 0x9E3779B9, hi ^ 0x85EBCA6B], np.uint32)


def make_weights(cfg: dict, words, dtype) -> Dict[str, jax.Array]:
    """All weights from the seed, traceable as one program: matrices
    N(0, 0.02) as GPT-2 initialises them, biases N(0, 0.02) and gains
    1 + N(0, 0.02) instead of 0 and 1, so that every term does work and no
    two rows are alike. Made in float32, rounded once to ``dtype``."""
    key = jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32), impl="rbg")
    out = {}
    for i, (name, (shape, kind)) in enumerate(sorted(weight_shapes(cfg).items())):
        x = 0.02 * jax.random.normal(jax.random.fold_in(key, i), shape,
                                     jnp.float32)
        if kind == "gain":
            x = 1.0 + x
        out[name] = x.astype(dtype)
    return out


# ---------------------------------------------------------------------------
# Precision: the reference itself is float32 at "highest"; the controls round
# ---------------------------------------------------------------------------


def _ste(x, q):
    """Rounded value forward, identity backward."""
    return x + jax.lax.stop_gradient(q - x)


def _round(x, lowp: Optional[str]):
    if lowp is None:
        return x
    if lowp in _BF16:
        # not astype there and back: XLA may keep the excess precision and
        # drop the pair (on the TPU it does), reduce_precision it keeps
        return _ste(x, jax.lax.reduce_precision(x, exponent_bits=8,
                                                mantissa_bits=7))
    if lowp == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
        return _ste(x, q)
    raise ValueError(f"unknown lower precision {lowp!r}")


def _mm(a, b, lowp):
    return jnp.matmul(_round(a, lowp), _round(b, lowp),
                      precision=jax.lax.Precision.HIGHEST)


def _act(x, lowp):
    # activations between operations: only the bfloat16 controls store them
    # rounded; fp8 is a matmul-input format
    return _round(x, lowp) if lowp in _BF16 else x


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(cfg, lowp, x, p):
    """One pre-LN block over x [B, T, d]; ``p`` holds one layer's tensors."""
    _, _, d, _, H, _ = dims(cfg)
    eps = float(cfg.get("layer_norm_epsilon", 1e-5))
    B, T, _ = x.shape
    h = _act(_ln(x, p["ln1_g"], p["ln1_b"], eps), lowp)
    qkv = _act(_mm(h, p["w_qkv"], lowp) + p["b_qkv"], lowp)
    q, k, v = (t.reshape(B, T, H, d // H) for t in jnp.split(qkv, 3, -1))
    s = jnp.einsum("bqhd,bkhd->bhqk", _round(q, lowp), _round(k, lowp),
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(d // H)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, -1)
    a = jnp.einsum("bhqk,bkhd->bqhd", _round(w, lowp), _round(v, lowp),
                   precision=jax.lax.Precision.HIGHEST).reshape(B, T, d)
    x = _act(x + _mm(_act(a, lowp), p["w_o"], lowp) + p["b_o"], lowp)
    h = _act(_ln(x, p["ln2_g"], p["ln2_b"], eps), lowp)
    h = _act(_gelu_tanh(_mm(h, p["w_fc"], lowp) + p["b_fc"]), lowp)
    return _act(x + _mm(h, p["w_pr"], lowp) + p["b_pr"], lowp)


def hidden(cfg, w, ids, lowp=None):
    """Final-LayerNorm output [B, T, d] for ids [B, T] (positions 0..T-1)."""
    T = ids.shape[1]
    x = _act(jnp.take(w["wte"], ids, axis=0) + w["wpe"][:T][None], lowp)
    blocks = {k: w[k] for k in BLOCK_KEYS}
    body = jax.checkpoint(functools.partial(_block, cfg, lowp))
    x, _ = jax.lax.scan(lambda c, p: (body(c, p), None), x, blocks)
    eps = float(cfg.get("layer_norm_epsilon", 1e-5))
    return _act(_ln(x, w["lnf_g"], w["lnf_b"], eps), lowp)


def logits_at(cfg, w, ids, positions, lowp=None):
    """Logits [B, n, V] at ``positions`` [B, n] of a full causal forward."""
    h = hidden(cfg, w, ids, lowp)
    h = jnp.take_along_axis(h, positions[..., None], axis=1)
    return _mm(h, w["w_head"], lowp) + w["b_head"]


@functools.partial(jax.jit, static_argnames=("cfg_key", "lowp"))
def logits_jit(cfg_key, lowp, w, ids, positions):
    """``logits_at`` as one program; ``cfg_key`` is ``cfg_key(cfg)``."""
    return logits_at(dict(cfg_key), w, jnp.asarray(ids),
                     jnp.asarray(positions), lowp)


def loss_rows(cfg, w, ids, labels, lowp=None):
    """Sum over the given rows of the sum over positions of the
    cross-entropy; the caller divides by the batch's rows."""
    h = hidden(cfg, w, ids, lowp)
    z = _act(_mm(h, w["w_head"], lowp) + w["b_head"], lowp)
    logp = jax.nn.log_softmax(z, -1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], -1))


# ---------------------------------------------------------------------------
# Training: three Adam steps, row block by row block
# ---------------------------------------------------------------------------


def split_qkv(name: str, x):
    """The fused qkv leaves as three, so that the key's bias, whose gradient
    is nought under softmax, is a leaf of its own."""
    if "qkv" not in name:
        return [(name, x)]
    return [(name.replace("qkv", part), t)
            for part, t in zip("qkv", jnp.split(x, 3, axis=-1))]


def leaf_sq_norms(tree):
    """Squared norm per leaf *per layer*: block tensors give [L], others []."""
    out = {}
    for k, v in tree.items():
        axes = tuple(range(1, v.ndim)) if k in BLOCK_KEYS else None
        for name, t in split_qkv(k, v.astype(jnp.float32)):
            out[name] = jnp.sum(jnp.square(t), axis=axes)
    return out


# the leaves outside the blocks whose first gradient is kept whole: all but
# the two [vocab, d] matrices
KEPT_WHOLE = ("wpe", "lnf_g", "lnf_b", "b_head")


@functools.partial(jax.jit, static_argnames=("layers",))
def _kept_leaves(tree, layers):
    out = {k: tree[k] for k in KEPT_WHOLE}
    for k in BLOCK_KEYS:
        for i in layers:
            for name, t in split_qkv(k, tree[k][i]):
                out[f"{name}.{i}"] = t
    return out


@functools.partial(jax.jit, static_argnames=("cfg_key", "lowp"))
def _grad_block(cfg_key, lowp, w, ids, labels):
    cfg = dict(cfg_key)
    return jax.value_and_grad(
        lambda p: loss_rows(cfg, p, ids, labels, lowp))(w)


@functools.partial(jax.jit, donate_argnums=(0,))
def _acc(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


@functools.partial(jax.jit, static_argnames=("lr", "store"),
                   donate_argnums=(0, 1, 2))
def _adam(w, m, v, g, step, lr, store):
    tt = jnp.asarray(step, jnp.float32) + 1.0
    b1, b2, eps = ADAM["beta1"], ADAM["beta2"], ADAM["eps"]
    bc1, bc2 = 1.0 - b1 ** tt, 1.0 - b2 ** tt
    tm = jax.tree_util.tree_map
    m = tm(lambda mi, gi: b1 * mi + (1 - b1) * gi, m, g)
    v = tm(lambda vi, gi: b2 * vi + (1 - b2) * gi * gi, v, g)

    def new(p, mi, vi):
        upd = lr * (mi / bc1) / (jnp.sqrt(vi / bc2) + eps)
        if store is None:
            return p - upd
        # the lower-precision control keeps its parameters in that type
        dt = jnp.dtype(store)
        return (p.astype(dt) - upd.astype(dt)).astype(jnp.float32)

    return tm(new, w, m, v), m, v


def cfg_key(cfg: dict):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


def train_steps(cfg: dict, w0: Dict[str, jax.Array],
                batches: Sequence[Tuple[np.ndarray, np.ndarray]],
                lr: float, rows: int = 2, lowp: Optional[str] = None,
                faults: Sequence[str] = (), keep_layers: Sequence[int] = ()):
    """Follow the program's first steps. Returns the loss of each step, the
    per-leaf gradient norms of the first, that gradient itself for the
    blocks ``keep_layers`` and the small leaves outside the blocks (on the
    host), and the per-leaf norms of the parameters' change after the last.
    ``faults`` plants what the tests and the fault readings need:
    ``"half_batch"`` leaves out the second half of every batch and takes the
    mean over the rest."""
    key = cfg_key(cfg)
    # a copy: the update donates ``w``, and ``w0`` is read again at the end
    w = {k: jnp.array(v, jnp.float32, copy=True) for k, v in w0.items()}
    if lowp == "bfloat16":
        w = {k: v.astype(jnp.bfloat16).astype(jnp.float32)
             for k, v in w.items()}
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, g1, kept = [], None, None
    store = "bfloat16" if lowp == "bfloat16" else None
    for step, (x, y) in enumerate(batches):
        if "half_batch" in faults:
            x, y = x[: len(x) // 2], y[: len(y) // 2]
        n = len(x)
        total, grads = 0.0, None
        for i in range(0, n, rows):
            l, g = _grad_block(key, lowp, w, jnp.asarray(x[i:i + rows]),
                               jnp.asarray(y[i:i + rows]))
            total = total + l
            grads = g if grads is None else _acc(grads, g)
        grads = jax.tree_util.tree_map(lambda t: t / n, grads)
        losses.append(float(total) / n)
        if step == 0:
            g1 = {k: np.sqrt(np.asarray(s))
                  for k, s in leaf_sq_norms(grads).items()}
            kept = {k: np.asarray(t) for k, t in _kept_leaves(
                grads, tuple(int(i) for i in keep_layers)).items()}
        w, m, v = _adam(w, m, v, grads, step, lr=float(lr), store=store)
    change = leaf_sq_norms({k: w[k] - w0[k].astype(jnp.float32) for k in w})
    return {"losses": losses, "grad_norms": g1, "grad_leaves": kept,
            "change_norms": {k: np.sqrt(np.asarray(s))
                             for k, s in change.items()}}
