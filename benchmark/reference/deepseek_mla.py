"""Plain reference for the DeepSeek-V2/V3 family of latent-attention expert
language models (``deepseek_v3``, ``joyai_llm_flash`` and the like): float32
``jax.numpy`` at ``highest`` matmul precision, no kernels, no sorted dispatch.
It imports nothing of the program and takes nothing the program made: the
weights come from ``make_weights`` below, which the drivers also use to fill
the program (benchmark/families/deepseek_mla.py).

The equations (DeepSeek-V2, arXiv:2405.04434 section 2.1; DeepSeek-V3,
arXiv:2412.19437 sections 2.1 and 2.2). Every layer ``i`` is pre-norm
residual twice, ``x <- x + Attn(RMSNorm(x))`` then ``x <- x +
FFN(RMSNorm(x))``; a final RMSNorm and an untied, bias-free head. No bias
anywhere. ``u`` is a half-layer's normed input.

- Latent attention: ``c_q = RMSNorm(u W_dq)``; ``[q_nope_h | q_rope_h] =
  (c_q W_uq)_h``; ``[c_kv | k_r] = u W_dkv``; ``c_kv <- RMSNorm(c_kv)``;
  ``[k_nope_h | v_h] = (c_kv W_ukv)_h``. ``q_rope_h <- R_t(q_rope_h)``,
  ``k_rope <- R_t(k_r)``: one rotary key a token, shared by all heads; ``R_t``
  rotates the adjacent pairs ``(2i, 2i+1)`` by ``t * theta^(-2i/d_rope)``
  (``rope_interleave``; no scaling). ``s_h = (q_nope_h . k_nope_h + q_rope_h .
  k_rope) / sqrt(d_nope + d_rope)``, causal softmax, ``o_h = P_h v_h``,
  ``y = [o_1 ... o_H] W_o``. The scores are computed a head and a block of
  queries at a time, as the full masked rows of that block.
- Feed-forward: the first ``first_k_dense_replace`` layers
  ``(silu(u W_g) * (u W_u)) W_d``. The others: ``s = sigmoid(u W_r)`` over all
  ``router_experts``; the ``num_experts_per_tok`` experts with the largest
  ``s + b`` (``b`` zero; one group, so ``noaux_tc`` is a plain top-k);
  weights ``s_e / sum_chosen s`` times ``routed_scaling_factor``; each expert
  and the shared expert the gated form. This reference holds experts
  ``held_experts_start .. + n_routed_experts - 1`` of the router's
  ``router_experts``, as the program does, loops over them with a mask, and
  leaves out what the absent ones would have added.
- Multi-token prediction, depth 1: with ``h_i`` the trunk's output at position
  ``i`` before the final norm and ``t_{i+1}`` the next token (the labels),
  ``h'_i = [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)] W_eh``, one layer of the
  expert kind over ``h'``, a final RMSNorm of its own, and the main head:
  logits for ``t_{i+2}`` (the labels rolled by one more). ``Emb`` and the head
  are the main model's arrays. ``L = L_main + mtp_loss_weight * L_mtp``.

Departures, which follow the program the benchmark measures and are stated in
the configuration file: each loss term is the mean over rows of the *sum* over
positions of the cross-entropy (``mcxent`` over ``[B, T, V]``), and the roll's
wrap-around positions are kept in both terms.

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
from benchmark.reference.nemotron_h import (  # noqa: F401
    _BF16, _HI, _acc, _adam, _largest_divisor, _mm, _rms, _round,
    _to_bf16_and_back, cfg_key)

# a layer's leaves by its kind: the attention half, then the feed-forward half
ATTN = ("norm1", "a_dq", "a_qnorm", "a_uq", "a_dkv", "a_kvnorm", "a_ukv", "a_o")
LEAVES = {
    "dense": ATTN + ("norm2", "f_gate", "f_up", "f_down"),
    "expert": ATTN + ("norm2", "e_router", "e_gate", "e_up", "e_down",
                      "e_sgate", "e_sup", "e_sdown"),
}
# query rows of one head scored at once: 1,024 float32 score rows over 8,192
# keys are 34 MB, and the backward pass holds several of them beside weights,
# gradient and both moments (10.9 GB of the chip's 16)
Q_ROWS = 1024
MTP = "mtp"                         # the MTP module's block, in a layer's place
MTP_OWN = ("mtp_enorm", "mtp_hnorm", "mtp_eh", "mtp_normf")
# leaves outside the layers whose first gradient is kept whole
KEPT_WHOLE = ("normf",) + MTP_OWN


def dims(cfg: dict) -> dict:
    return dict(
        V=int(cfg["vocab_size"]), d=int(cfg["hidden_size"]),
        L=int(cfg["num_hidden_layers"]),
        dense=int(cfg.get("first_k_dense_replace", 0)),
        H=int(cfg["num_attention_heads"]), Rq=int(cfg["q_lora_rank"]),
        Rkv=int(cfg["kv_lora_rank"]), Dn=int(cfg["qk_nope_head_dim"]),
        Dr=int(cfg["qk_rope_head_dim"]), Dv=int(cfg["v_head_dim"]),
        Fd=int(cfg["intermediate_size"]), F=int(cfg["moe_intermediate_size"]),
        Fs=int(cfg["moe_intermediate_size"]) * int(cfg.get("n_shared_experts", 0)),
        E=int(cfg["n_routed_experts"]),
        R=int(cfg.get("router_experts", cfg["n_routed_experts"])),
        e0=int(cfg.get("held_experts_start", 0)),
        topk=int(cfg["num_experts_per_tok"]),
        mtp=int(cfg.get("num_nextn_predict_layers", 0)),
        lam=float(cfg.get("mtp_loss_weight", 0.0)),
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))


def kinds(cfg: dict) -> tuple:
    """The kind of each of the trunk's layers, in order."""
    D = dims(cfg)
    return tuple("dense" if i < D["dense"] else "expert" for i in range(D["L"]))


def blocks(cfg: dict) -> tuple:
    """(index, kind) of every block: the trunk's layers, then the MTP
    module's (index ``"mtp"``) where the configuration has one."""
    out = tuple(enumerate(kinds(cfg)))
    return out + (((MTP, "expert"),) if dims(cfg)["mtp"] else ())


def weight_shapes(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, kind); a layer's leaves are ``<leaf>.<layer index>``,
    the MTP block's ``<leaf>.mtp``."""
    D = dims(cfg)
    d, H = D["d"], D["H"]
    if D["mtp"] > 1:
        raise ValueError("one MTP module is all this reference writes down")
    out = {"wte": ((D["V"], d), "matrix"), "normf": ((d,), "gain"),
           "w_head": ((d, D["V"]), "matrix")}
    per = {
        "norm1": ((d,), "gain"), "norm2": ((d,), "gain"),
        "a_dq": ((d, D["Rq"]), "matrix"), "a_qnorm": ((D["Rq"],), "gain"),
        "a_uq": ((D["Rq"], H * (D["Dn"] + D["Dr"])), "matrix"),
        "a_dkv": ((d, D["Rkv"] + D["Dr"]), "matrix"),
        "a_kvnorm": ((D["Rkv"],), "gain"),
        "a_ukv": ((D["Rkv"], H * (D["Dn"] + D["Dv"])), "matrix"),
        "a_o": ((H * D["Dv"], d), "matrix"),
        "f_gate": ((d, D["Fd"]), "matrix"), "f_up": ((d, D["Fd"]), "matrix"),
        "f_down": ((D["Fd"], d), "matrix"),
        "e_router": ((d, D["R"]), "matrix"),
        "e_gate": ((D["E"], d, D["F"]), "matrix"),
        "e_up": ((D["E"], d, D["F"]), "matrix"),
        "e_down": ((D["E"], D["F"], d), "matrix"),
        "e_sgate": ((d, D["Fs"]), "matrix"), "e_sup": ((d, D["Fs"]), "matrix"),
        "e_sdown": ((D["Fs"], d), "matrix"),
    }
    for i, kind in blocks(cfg):
        for leaf in LEAVES[kind]:
            out[f"{leaf}.{i}"] = per[leaf]
    if D["mtp"]:
        out.update({"mtp_enorm": ((d,), "gain"), "mtp_hnorm": ((d,), "gain"),
                    "mtp_eh": ((2 * d, d), "matrix"),
                    "mtp_normf": ((d,), "gain")})
    return out


def num_params(cfg: dict) -> int:
    return sum(math.prod(s) for s, _ in weight_shapes(cfg).values())


def make_weights(cfg: dict, words, dtype) -> Dict[str, jax.Array]:
    """All weights from the seed, traceable as one program: matrices
    N(0, 0.02), gains 1 + N(0, 0.02). Made in float32, rounded once to
    ``dtype``."""
    key = jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32), impl="rbg")
    out = {}
    for i, (name, (shape, kind)) in enumerate(sorted(weight_shapes(cfg).items())):
        x = 0.02 * jax.random.normal(jax.random.fold_in(key, i), shape,
                                     jnp.float32)
        out[name] = (1.0 + x if kind == "gain" else x).astype(dtype)
    return out


# ---------------------------------------------------------------------------
# The layers, each over u [B, T, d] (already normed)
# ---------------------------------------------------------------------------


def rope(x, theta: float):
    """``R_t`` over ``x`` [B, T, n, Dr], position ``t`` the index along T:
    the adjacent pairs ``(2i, 2i+1)`` rotated by ``t * theta^(-2i/Dr)``."""
    T, Dr = x.shape[1], x.shape[-1]
    inv = np.power(float(theta), -np.arange(0, Dr, 2) / Dr).astype(np.float32)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]   # [T, Dr/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     -1).reshape(x.shape)


def attention(cfg, lowp, u, p):
    """One head at a time, and within a head one run of query rows at a time
    against all keys. What a head's loop closes over is the two latents and
    the rotary key: were q, k and v of all heads made first, the compiler
    would make them again early for every layer's backward pass and hold
    them (0.8 GB a layer at 8,192 positions)."""
    D = dims(cfg)
    Bsz, T, _ = u.shape
    H, Dn, Dr, Dv, eps = D["H"], D["Dn"], D["Dr"], D["Dv"], D["eps"]
    r = functools.partial(_round, lowp=lowp if lowp in _BF16 else None)
    c_q = r(_rms(r(_mm(u, p["a_dq"], lowp)), p["a_qnorm"], eps))
    down = r(_mm(u, p["a_dkv"], lowp))
    c_kv = r(_rms(down[..., :D["Rkv"]], p["a_kvnorm"], eps))
    k_rope = r(rope(down[..., None, D["Rkv"]:], D["theta"]))[:, :, 0]  # [B, T, Dr]
    bq = _largest_divisor(T, Q_ROWS)
    k_pos = jnp.arange(T)

    @jax.checkpoint
    def head(w):
        w_uq, w_ukv = w                          # [Rq, Dn + Dr], [Rkv, Dn + Dv]
        q = r(_mm(c_q, w_uq, lowp))
        kv = r(_mm(c_kv, w_ukv, lowp))
        q_nope = q[..., :Dn]
        q_rope = r(rope(q[..., None, Dn:], D["theta"]))[:, :, 0]
        k_nope, v = kv[..., :Dn], kv[..., Dn:]

        @jax.checkpoint
        def rows(i):
            cut = lambda t: jax.lax.dynamic_slice_in_dim(t, i * bq, bq, 1)  # noqa: E731
            s = (jnp.einsum("bqd,bkd->bqk", _round(cut(q_nope), lowp),
                            _round(k_nope, lowp), precision=_HI)
                 + jnp.einsum("bqd,bkd->bqk", _round(cut(q_rope), lowp),
                              _round(k_rope, lowp), precision=_HI)
                 ) / math.sqrt(Dn + Dr)
            seen = k_pos[None, :] <= (i * bq + jnp.arange(bq))[:, None]
            w_ = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
            return jnp.einsum("bqk,bkd->bqd", _round(w_, lowp), _round(v, lowp),
                              precision=_HI)

        o = jax.lax.map(rows, jnp.arange(T // bq))               # [n, B, bq, Dv]
        return jnp.moveaxis(o, 0, 1).reshape(Bsz, T, Dv)

    by_head = lambda m, width: jnp.moveaxis(                     # noqa: E731
        m.reshape(m.shape[0], H, width), 1, 0)
    o = jax.lax.map(head, (by_head(p["a_uq"], Dn + Dr),
                           by_head(p["a_ukv"], Dn + Dv)))        # [H, B, T, Dv]
    o = r(jnp.moveaxis(o, 0, 2).reshape(Bsz, T, H * Dv))
    return _mm(o, p["a_o"], lowp)


def route(cfg, u, w_router, bias=None):
    """Expert ids [N, k] and their weights for tokens ``u`` [N, d]."""
    s = jax.nn.sigmoid(jnp.matmul(u, w_router, precision=_HI))
    _, eid = jax.lax.top_k(s if bias is None else s + bias, dims(cfg)["topk"])
    w = jnp.take_along_axis(s, eid, -1)
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, -1, keepdims=True)
    return eid, w * float(cfg.get("routed_scaling_factor", 1.0))


def gated(lowp, x, w_gate, w_up, w_down):
    """``(silu(x W_g) * (x W_u)) W_d``."""
    h = _round(jax.nn.silu(_mm(x, w_gate, lowp)) * _mm(x, w_up, lowp),
               lowp if lowp in _BF16 else None)
    return _mm(h, w_down, lowp)


def dense(cfg, lowp, u, p):
    """The dense layer's feed-forward, a run of rows at a time (its hidden
    activations are [T, intermediate_size], 235 MB each at 8,192 rows, and
    the backward pass would hold half a dozen of them)."""
    Bsz, T, d = u.shape
    n = T // _largest_divisor(T, 2048)
    rows = jax.checkpoint(lambda x: gated(lowp, x, p["f_gate"], p["f_up"],
                                          p["f_down"]))
    y = jax.lax.map(rows, jnp.moveaxis(u.reshape(Bsz, n, T // n, d), 1, 0))
    return jnp.moveaxis(y, 0, 1).reshape(Bsz, T, d)


def experts(cfg, lowp, u, p):
    D = dims(cfg)
    Bsz, T, d = u.shape
    x = u.reshape(Bsz * T, d)
    # the router stays float32; its correction bias is zero unless given
    eid, w = route(cfg, x, p["e_router"], p.get("e_bias"))
    y = gated(lowp, x, p["e_sgate"], p["e_sup"], p["e_sdown"]) if D["Fs"] \
        else jnp.zeros_like(x)

    @jax.checkpoint
    def one(e, w_gate, w_up, w_down):
        gate = jnp.sum(jnp.where(eid == D["e0"] + e, w, 0.0), -1)
        return gate[:, None] * gated(lowp, x, w_gate, w_up, w_down)

    # the held experts, one by one over all tokens under their masks: a scan,
    # so that the backward pass holds one expert's activations at a time
    y, _ = jax.lax.scan(lambda acc, ew: (acc + one(*ew), None), y,
                        (jnp.arange(D["E"]), p["e_gate"], p["e_up"], p["e_down"]))
    return y.reshape(Bsz, T, d)


FFN = {"dense": dense, "expert": experts}


def _half(cfg, lowp, fn, norm, x, p):
    r = functools.partial(_round, lowp=lowp if lowp in _BF16 else None)
    return r(x + fn(cfg, lowp, r(_rms(x, p[norm], dims(cfg)["eps"])), p))


def layer_weights(w: dict, i, kind: str) -> dict:
    """Block ``i``'s leaves under their bare names."""
    return {k: w[f"{k}.{i}"] for k in LEAVES[kind]}


def block(cfg, lowp, kind, x, p):
    """One layer: both halves, each recomputed in the backward pass."""
    x = jax.checkpoint(functools.partial(_half, cfg, lowp, attention, "norm1"))(x, p)
    return jax.checkpoint(functools.partial(_half, cfg, lowp, FFN[kind], "norm2"))(x, p)


def trunk(cfg, w, ids, lowp=None):
    """The last layer's output [B, T, d], before the final norm."""
    x = _round(jnp.take(w["wte"], ids, axis=0), lowp if lowp in _BF16 else None)
    for i, kind in enumerate(kinds(cfg)):
        x = block(cfg, lowp, kind, x, layer_weights(w, i, kind))
    return x


def mtp_hidden(cfg, w, h, next_ids, lowp=None):
    """The MTP module's block output for the trunk's ``h`` and the next
    tokens' ids, before its own final norm."""
    r = functools.partial(_round, lowp=lowp if lowp in _BF16 else None)
    eps = dims(cfg)["eps"]
    e = r(jnp.take(w["wte"], next_ids, axis=0))
    both = jnp.concatenate((r(_rms(e, w["mtp_enorm"], eps)),
                            r(_rms(h, w["mtp_hnorm"], eps))), -1)
    return block(cfg, lowp, "expert", r(_mm(both, w["mtp_eh"], lowp)),
                 layer_weights(w, MTP, "expert"))


def head_nll(cfg, w, x, gain, labels, lowp=None):
    """Cross-entropy [B, T] of ``RMSNorm(x) W_head`` against ``labels``, a
    run of positions at a time (the logits of 8,192 positions at once are
    half a gigabyte, and their gradient as much again)."""
    r = functools.partial(_round, lowp=lowp if lowp in _BF16 else None)
    Bsz, T, d = x.shape
    run = _largest_divisor(T, 2048)

    @jax.checkpoint
    def some(xl):
        xs, ls = xl
        z = r(_mm(r(_rms(xs, gain, dims(cfg)["eps"])), w["w_head"], lowp))
        return -jnp.take_along_axis(jax.nn.log_softmax(z, -1), ls[..., None],
                                    -1)[..., 0]

    cut = lambda t: jnp.moveaxis(                               # noqa: E731
        t.reshape((Bsz, T // run, run) + t.shape[2:]), 1, 0)
    nll = jax.lax.map(some, (cut(x), cut(labels)))              # [n, B, run]
    return jnp.moveaxis(nll, 0, 1).reshape(Bsz, T)


def loss_terms(cfg, w, ids, labels, lowp=None, positions=None):
    """(main, mtp): each the sum over the given rows of the sum over
    positions (the first ``positions`` of them, if given) of its
    cross-entropy; ``mtp`` is 0.0 where the configuration has no module."""
    h = trunk(cfg, w, ids, lowp)
    main = jnp.sum(head_nll(cfg, w, h, w["normf"], labels, lowp)[:, :positions])
    if not dims(cfg)["mtp"]:
        return main, jnp.zeros((), jnp.float32)
    hp = mtp_hidden(cfg, w, h, labels, lowp)
    nll = head_nll(cfg, w, hp, w["mtp_normf"], jnp.roll(labels, -1, axis=1), lowp)
    return main, jnp.sum(nll[:, :positions])


def loss_rows(cfg, w, ids, labels, lowp=None, positions=None):
    """``L_main + mtp_loss_weight * L_mtp`` summed over the given rows; the
    caller divides by the batch's rows."""
    main, mtp = loss_terms(cfg, w, ids, labels, lowp, positions)
    return main + dims(cfg)["lam"] * mtp


# ---------------------------------------------------------------------------
# Training: Adam steps, row block by row block
# ---------------------------------------------------------------------------


def leaf_sq_norms(tree):
    """Squared norm per leaf, an expert stack as one leaf (a single expert's
    share of a gradient hangs on the few tokens a near-tie sends it or
    not)."""
    return {k: jnp.sum(jnp.square(v.astype(jnp.float32)))
            for k, v in tree.items()}


def kept_names(cfg: dict, layers) -> tuple:
    """The leaves whose first gradient is compared whole: the driver's layers
    (first, middle, last: the dense layer and two expert layers), the MTP
    module (its block, its merge matrix and its gains) and the final gain."""
    own = tuple(k for k in KEPT_WHOLE if k in weight_shapes(cfg))
    return own + tuple(
        f"{leaf}.{i}" for i, kind in blocks(cfg)
        if i == MTP or i in {int(j) for j in layers} for leaf in LEAVES[kind])


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
