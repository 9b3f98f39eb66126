"""Plain reference for the ``nemotron_h`` family (Nemotron-H / Nemotron-Labs
hybrid stacks): float32 ``jax.numpy`` at ``highest`` matmul precision, no
kernels, no chunked scan, no sorted dispatch. It imports nothing of the
program and takes nothing the program made: the weights come from
``make_weights`` below, which the drivers also use to fill the program
(benchmark/families/nemotron_h.py).

The equations (``modeling_nemotron_h.py`` of the source; Dao & Gu 2024 for the
mixer). Every layer ``i`` of ``hybrid_override_pattern`` is
``x <- x + Mixer_i(RMSNorm_i(x))``, then a final RMSNorm and an untied,
bias-free head. No bias anywhere but the convolution's.

- ``M``, Mamba-2: ``[z | xBC | dt] = u W_in``; ``xBC <- silu(conv1d_causal_
  depthwise(xBC, k) + b)``; split ``x [T,H,P]``, ``B [T,G,N]``, ``C [T,G,N]``;
  ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; for head ``h`` of group
  ``g = h // (H/G)``: ``S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (outer) B_t^g``,
  ``y_t = S_t C_t^g + D_h x_t``; ``y <- RMSNorm_groups(y * silu(z)) * w``;
  out ``y W_out``. The recurrence is a ``lax.scan`` over positions, one step a
  position, in elementwise float32.
- ``*``, attention: ``q = u W_q`` (``num_attention_heads`` of ``head_dim``),
  ``k, v`` with ``num_key_value_heads`` heads, each shared by a run of query
  heads; causal ``softmax(q k^T / sqrt(head_dim)) v`` as the full masked
  square; out ``W_o``. No positional encoding (the source's attention layers
  apply none; ``rope_theta`` is not read).
- ``E``, experts: ``s = sigmoid(u W_r)`` over all ``router_experts``; the
  ``num_experts_per_tok`` experts with the largest ``s + b`` (``b`` zero);
  weights ``s_e / sum_chosen s`` (``norm_topk_prob``) times
  ``routed_scaling_factor``; expert ``relu(u W1_e)^2 W2_e``; the shared expert
  the same form, every token. This reference holds experts
  ``held_experts_start .. + n_routed_experts - 1`` of the router's
  ``router_experts``, as the program does, loops over them with a mask, and
  leaves out what the absent ones would have added.

Departures, which follow the program the benchmark measures and are stated in
the configuration file: the loss is the mean over rows of the *sum* over
positions of the cross-entropy (``RnnOutputLayer`` + ``mcxent``); the second
tower and the diffusion objective of the source's release are not built.

Memory: at the benchmark's size the weights, their gradient and Adam's two
moments are 10.7 GB of the chip's 16, so every layer is a ``jax.checkpoint``
(and the position scan and the attention heads are checkpointed in pieces):
that changes what is kept between the passes, never the arithmetic.

``lowp`` runs the same mathematics in a lower precision and is what the
controls of ``correct`` use: ``"bfloat16"`` rounds parameters, activations and
matmul inputs to bfloat16 (parameters stay bfloat16 across updates),
``"bfloat16_compute"`` rounds activations and matmul inputs alike but keeps
the parameters and their updates in float32.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# the seed as a key's words and Adam's constants: one definition for both
# references (a reference imports nothing of the program, another reference
# is not the program)
from benchmark.reference.gpt2 import ADAM, seed_words  # noqa: F401

_BF16 = ("bfloat16", "bfloat16_compute")
_HI = jax.lax.Precision.HIGHEST

# per-layer leaves by the layer's letter: name -> kind
LEAVES = {
    "M": ("m_in", "m_conv_w", "m_conv_b", "m_dt_bias", "m_A_log", "m_D",
          "m_norm", "m_out"),
    "*": ("a_q", "a_k", "a_v", "a_o"),
    "E": ("e_router", "e_w1", "e_w2", "e_s1", "e_s2"),
}
# leaves outside the layers whose first gradient is kept whole
KEPT_WHOLE = ("normf",)


def pattern(cfg: dict) -> str:
    return str(cfg["hybrid_override_pattern"])


def dims(cfg: dict) -> dict:
    H, P = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    G, N = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    inner = H * P
    return dict(
        V=int(cfg["vocab_size"]), d=int(cfg["hidden_size"]), H=H, P=P, G=G,
        N=N, inner=inner, conv_dim=inner + 2 * G * N, k=int(cfg["conv_kernel"]),
        Hq=int(cfg["num_attention_heads"]), Hkv=int(cfg["num_key_value_heads"]),
        Dh=int(cfg["head_dim"]), E=int(cfg["n_routed_experts"]),
        R=int(cfg.get("router_experts", cfg["n_routed_experts"])),
        e0=int(cfg.get("held_experts_start", 0)),
        topk=int(cfg["num_experts_per_tok"]),
        F=int(cfg["moe_intermediate_size"]),
        Fs=int(cfg["moe_shared_expert_intermediate_size"]),
        eps=float(cfg.get("norm_eps", cfg.get("layer_norm_epsilon", 1e-5))))


def weight_shapes(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, kind); a layer's leaves are ``<leaf>.<layer index>``."""
    D = dims(cfg)
    d = D["d"]
    out = {"wte": ((D["V"], d), "matrix"), "normf": ((d,), "gain"),
           "w_head": ((d, D["V"]), "matrix")}
    per = {
        "m_in": ((d, 2 * D["inner"] + 2 * D["G"] * D["N"] + D["H"]), "matrix"),
        "m_conv_w": ((D["k"], D["conv_dim"]), "conv"),
        "m_conv_b": ((D["conv_dim"],), "bias"),
        "m_dt_bias": ((D["H"],), "dt_bias"), "m_A_log": ((D["H"],), "a_log"),
        "m_D": ((D["H"],), "gain"), "m_norm": ((D["inner"],), "gain"),
        "m_out": ((D["inner"], d), "matrix"),
        "a_q": ((d, D["Hq"] * D["Dh"]), "matrix"),
        "a_k": ((d, D["Hkv"] * D["Dh"]), "matrix"),
        "a_v": ((d, D["Hkv"] * D["Dh"]), "matrix"),
        "a_o": ((D["Hq"] * D["Dh"], d), "matrix"),
        "e_router": ((d, D["R"]), "matrix"),
        "e_w1": ((D["E"], d, D["F"]), "matrix"),
        "e_w2": ((D["E"], D["F"], d), "matrix"),
        "e_s1": ((d, D["Fs"]), "matrix"), "e_s2": ((D["Fs"], d), "matrix"),
    }
    for i, c in enumerate(pattern(cfg)):
        out[f"norm.{i}"] = ((d,), "gain")
        for leaf in LEAVES[c]:
            out[f"{leaf}.{i}"] = per[leaf]
    return out


def num_params(cfg: dict) -> int:
    return sum(math.prod(s) for s, _ in weight_shapes(cfg).values())


def make_weights(cfg: dict, words, dtype) -> Dict[str, jax.Array]:
    """All weights from the seed, traceable as one program. Matrices and the
    convolution's bias N(0, 0.02); gains (the norms and the skip ``D``)
    1 + N(0, 0.02); convolution taps N(0, 0.5 / sqrt(k)); ``dt_bias`` so that
    ``softplus(dt_bias)`` is log-uniform in [time_step_min, time_step_max];
    ``A_log`` the log of a uniform draw from [1, 16], as Mamba-2 initialises
    them. Made in float32, rounded once to ``dtype``."""
    key = jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32), impl="rbg")
    lo = math.log(float(cfg.get("time_step_min", 1e-3)))
    hi = math.log(float(cfg.get("time_step_max", 0.1)))
    out = {}
    for i, (name, (shape, kind)) in enumerate(sorted(weight_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if kind == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo, hi))
            x = dt + jnp.log(-jnp.expm1(-dt))
        elif kind == "a_log":
            x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        else:
            scale = 0.5 / math.sqrt(shape[0]) if kind == "conv" else 0.02
            x = scale * jax.random.normal(k, shape, jnp.float32)
            if kind == "gain":
                x = 1.0 + x
        out[name] = x.astype(dtype)
    return out


# ---------------------------------------------------------------------------
# Precision: the reference itself is float32 at "highest"; the controls round
# ---------------------------------------------------------------------------


def _bf16(x):
    """The nearest bfloat16, kept in float32. Not ``astype`` there and back:
    inside one program the TPU's compiler drops that pair as excess
    precision (read on the chip: the control's gains moved as the
    reference's did), ``reduce_precision`` it keeps."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _ste(x, q):
    """Rounded value forward, identity backward."""
    return x + jax.lax.stop_gradient(q - x)


def _round(x, lowp: Optional[str]):
    if lowp is None:
        return x
    if lowp in _BF16:
        # not astype there and back: XLA may keep the excess precision and
        # drop the pair (on the TPU it does), reduce_precision it keeps
        return _ste(x, _bf16(x))
    raise ValueError(f"unknown lower precision {lowp!r}")


def _mm(a, b, lowp):
    return jnp.matmul(_round(a, lowp), _round(b, lowp), precision=_HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _largest_divisor(n: int, most: int) -> int:
    return max(k for k in range(1, most + 1) if n % k == 0)


# ---------------------------------------------------------------------------
# The three mixers, each over u [B, T, d] (already normed)
# ---------------------------------------------------------------------------


def recurrence(x, dt, A, Bm, Cm):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t``, ``y_t = S_t C_t``,
    one position a step. ``x`` [B,T,H,P], ``dt`` [B,T,H], ``A`` [H],
    ``Bm``/``Cm`` [B,T,G,N]; head ``h`` reads group ``h // (H/G)``. The scan
    is cut into runs whose inside is recomputed in the backward pass."""
    Bsz, T, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G

    def step(S, inp):
        x_t, dt_t, b_t, c_t = inp                  # [B,H,P] [B,H] [B,G,N] x2
        b_h, c_h = jnp.repeat(b_t, rep, 1), jnp.repeat(c_t, rep, 1)
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None, :])
        return S, jnp.sum(S * c_h[:, :, None, :], -1)

    run = _largest_divisor(T, 64)
    seq = tuple(jnp.moveaxis(t, 1, 0).reshape((T // run, run) + t.shape[:1]
                                              + t.shape[2:])
                for t in (x, dt, Bm, Cm))
    inner = jax.checkpoint(lambda S, r: jax.lax.scan(step, S, r))
    _, y = jax.lax.scan(inner, jnp.zeros((Bsz, H, P, N), jnp.float32), seq)
    return jnp.moveaxis(y.reshape((T,) + y.shape[2:]), 0, 1)


def mamba(cfg, lowp, u, p):
    D = dims(cfg)
    Bsz, T, _ = u.shape
    inner, conv_dim, H, P, G, N = (D[k] for k in
                                   ("inner", "conv_dim", "H", "P", "G", "N"))
    r = functools.partial(_round, lowp=lowp if lowp in _BF16 else None)
    zxbcdt = r(_mm(u, p["m_in"], lowp))
    z, xBC, dt = jnp.split(zxbcdt, [inner, inner + conv_dim], -1)
    k = p["m_conv_w"].shape[0]
    xp = jnp.pad(xBC, ((0, 0), (k - 1, 0), (0, 0)))
    conv = p["m_conv_b"] + sum(xp[:, j:j + T] * p["m_conv_w"][j]
                               for j in range(k))
    xBC = r(jax.nn.silu(conv))
    x, Bm, Cm = jnp.split(xBC, [inner, inner + G * N], -1)
    dt = jax.nn.softplus(dt + p["m_dt_bias"])
    x = x.reshape(Bsz, T, H, P)
    y = recurrence(x, dt, -jnp.exp(p["m_A_log"]), Bm.reshape(Bsz, T, G, N),
                   Cm.reshape(Bsz, T, G, N))
    y = r(y + x * p["m_D"][:, None])
    y = (y.reshape(Bsz, T, inner) * jax.nn.silu(z)).reshape(Bsz, T, G, inner // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + D["eps"])
    y = r(y.reshape(Bsz, T, inner) * p["m_norm"])
    return _mm(y, p["m_out"], lowp)


def attention(cfg, lowp, u, p):
    D = dims(cfg)
    Bsz, T, _ = u.shape
    Hq, Hkv, Dh = D["Hq"], D["Hkv"], D["Dh"]
    r = functools.partial(_round, lowp=lowp if lowp in _BF16 else None)
    q = r(_mm(u, p["a_q"], lowp)).reshape(Bsz, T, Hq, Dh)
    k = r(_mm(u, p["a_k"], lowp)).reshape(Bsz, T, Hkv, Dh)
    v = r(_mm(u, p["a_v"], lowp)).reshape(Bsz, T, Hkv, Dh)
    causal = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv                                       # [B, T, Dh]
        s = jnp.einsum("bqd,bkd->bqk", _round(qh, lowp), _round(kh, lowp),
                       precision=_HI) / math.sqrt(Dh)
        w = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
        return jnp.einsum("bqk,bkd->bqd", _round(w, lowp), _round(vh, lowp),
                          precision=_HI)

    rep = Hq // Hkv                                            # query heads a kv head
    a = jax.lax.map(head, (jnp.moveaxis(q, 2, 0),
                           jnp.repeat(jnp.moveaxis(k, 2, 0), rep, 0),
                           jnp.repeat(jnp.moveaxis(v, 2, 0), rep, 0)))
    a = r(jnp.moveaxis(a, 0, 2).reshape(Bsz, T, Hq * Dh))
    return _mm(a, p["a_o"], lowp)


def route(cfg, u, w_router, bias=None):
    """Expert ids [N, k] and their weights for tokens ``u`` [N, d]."""
    D = dims(cfg)
    s = jax.nn.sigmoid(jnp.matmul(u, w_router, precision=_HI))
    _, eid = jax.lax.top_k(s if bias is None else s + bias, D["topk"])
    w = jnp.take_along_axis(s, eid, -1)
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, -1, keepdims=True)
    return eid, w * float(cfg.get("routed_scaling_factor", 1.0))


def experts(cfg, lowp, u, p):
    D = dims(cfg)
    Bsz, T, d = u.shape
    x = u.reshape(Bsz * T, d)
    r = functools.partial(_round, lowp=lowp if lowp in _BF16 else None)
    # the router stays float32; its correction bias is zero unless given
    eid, w = route(cfg, x, p["e_router"], p.get("e_bias"))
    ffn = lambda w1, w2: _mm(r(jnp.square(jax.nn.relu(   # noqa: E731
        _mm(x, w1, lowp)))), w2, lowp)
    y = ffn(p["e_s1"], p["e_s2"])
    for e in range(D["E"]):                     # the held experts, one by one
        gate = jnp.sum(jnp.where(eid == D["e0"] + e, w, 0.0), -1)
        y = y + gate[:, None] * ffn(p["e_w1"][e], p["e_w2"][e])
    return y.reshape(Bsz, T, d)


MIXERS = {"M": mamba, "*": attention, "E": experts}


def _layer(cfg, lowp, c, x, p):
    r = functools.partial(_round, lowp=lowp if lowp in _BF16 else None)
    u = r(_rms(x, p["norm"], dims(cfg)["eps"]))
    return r(x + MIXERS[c](cfg, lowp, u, p))


def layer_weights(w: dict, i: int, c: str) -> dict:
    """Layer ``i``'s leaves under their bare names."""
    return {"norm": w[f"norm.{i}"], **{k: w[f"{k}.{i}"] for k in LEAVES[c]}}


def hidden(cfg, w, ids, lowp=None):
    """Final-RMSNorm output [B, T, d] for ids [B, T]."""
    x = _round(jnp.take(w["wte"], ids, axis=0), lowp if lowp in _BF16 else None)
    for i, c in enumerate(pattern(cfg)):
        x = jax.checkpoint(functools.partial(_layer, cfg, lowp, c))(
            x, layer_weights(w, i, c))
    return _round(_rms(x, w["normf"], dims(cfg)["eps"]),
                  lowp if lowp in _BF16 else None)


def loss_rows(cfg, w, ids, labels, lowp=None, positions=None):
    """Sum over the given rows of the sum over positions (the first
    ``positions`` of them, if given) of the cross-entropy; the caller
    divides by the batch's rows."""
    h = hidden(cfg, w, ids, lowp)
    z = _round(_mm(h, w["w_head"], lowp), lowp if lowp in _BF16 else None)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(z, -1), labels[..., None], -1)
    return jnp.sum(nll[:, :positions])


# ---------------------------------------------------------------------------
# Training: Adam steps, row block by row block
# ---------------------------------------------------------------------------


def leaf_sq_norms(tree):
    """Squared norm per leaf, an expert stack as one leaf (a single expert's
    share of a gradient hangs on the few tokens a near-tie sends it or not);
    the convolution's taps one each, [k]. With the taps apart most entries
    are small leaves, so the comparison's floor, the median entry, is a
    small leaf's norm and a gain that does not move is read against that and
    not against a matrix's."""
    return {k: jnp.sum(jnp.square(v.astype(jnp.float32)),
                       axis=1 if k.startswith("m_conv_w.") else None)
            for k, v in tree.items()}


def kept_layers(cfg: dict, layers) -> tuple:
    """The layers whose first gradient is compared whole: the driver's
    (first, middle, last) and the first attention layer beside them, so that
    one layer of each kind is among them."""
    pat = pattern(cfg)
    extra = {pat.index("*")} if "*" in pat else set()
    return tuple(sorted({int(i) for i in layers} | extra))


def kept_names(cfg: dict, layers) -> tuple:
    pat = pattern(cfg)
    return KEPT_WHOLE + tuple(
        f"{leaf}.{i}" for i in kept_layers(cfg, layers)
        for leaf in ("norm",) + LEAVES[pat[i]])


@functools.partial(jax.jit, static_argnames=("cfg_key", "lowp", "n", "positions"))
def _grad_block(cfg_key, lowp, n, positions, w, ids, labels):
    cfg = dict(cfg_key)
    return jax.value_and_grad(
        lambda p: loss_rows(cfg, p, ids, labels, lowp, positions) / n)(w)


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
        return _bf16(_bf16(p) - _bf16(upd))

    return tm(new, w, m, v), m, v


@functools.partial(jax.jit, donate_argnums=(0,))
def _to_bf16_and_back(w):
    return {k: _bf16(v) for k, v in w.items()}


def cfg_key(cfg: dict):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


def train_steps(cfg: dict, w0: Dict[str, jax.Array],
                batches: Sequence[Tuple[np.ndarray, np.ndarray]],
                lr: float, rows: int = 2, lowp: Optional[str] = None,
                faults: Sequence[str] = (), keep_layers: Sequence[int] = ()):
    """Follow the program's first steps. Returns the loss of each step, the
    per-leaf gradient norms of the first, that gradient itself for the layers
    ``kept_layers`` and ``KEPT_WHOLE`` (on the host), and the per-leaf norms
    of the parameters' change after the last. ``faults`` plants what the
    tests and the fault readings need: ``"half_batch"`` leaves out the second
    half of every batch (of its rows; of its one row's positions where it has
    one) and takes the mean over the rest.

    The start ``w0`` is copied to the host and its device buffers are given
    to the update (donated: ``w0`` is not to be read again): weights,
    gradient and both moments are all the chip has room for at the
    benchmark's size."""
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
