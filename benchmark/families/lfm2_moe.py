"""The ``lfm2_moe`` family as the program builds it: ``models.ShortConvLM``
(an embedding, two ``ResidualBlock``s a layer around a ``ShortConvMixer`` or
a ``GroupedQueryAttention`` with normed and rotated q and k, then a
``GatedMLP`` or a gated ``SparseMoE``, and an ``MTPOutputLayer`` that holds
the final norm and reads the embedding as its head) behind
``MultiLayerNetwork``. This module is the only place that knows both the
reference's weight names (benchmark/reference/lfm2_moe.py) and the program's
parameter tree: a gated feed-forward's gate and up matrices lie side by side
in one (``Wi``, ``W1``). The drivers go through it, and the reference never
sees it.

A configuration file names this module under ``family``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import lfm2_moe as ref

# the program's name, inside a block's "mixer", of each of the reference's
# leaves that is one array on both sides
_MIXER = {
    "c_in": "W_in", "c_conv": "conv_w", "c_out": "W_out",
    "a_q": "Wq", "a_k": "Wk", "a_v": "Wv", "a_o": "Wo",
    "a_qnorm": "q_norm", "a_knorm": "k_norm",
    "f_down": "Wo", "e_router": "Wr", "e_down": "W2",
}
# (gate, up) of the reference -> the program's one matrix, by feed-forward
_SIDE_BY_SIDE = {"dense": ("f_gate", "f_up", "Wi"),
                 "expert": ("e_gate", "e_up", "W1")}


def build_conf(cfg: dict):
    """The program's configuration object for a configuration file."""
    from deeplearning4j_tpu.models import ShortConvLM

    D = ref.dims(cfg)
    return ShortConvLM(
        tuple(cfg["layer_types"]), vocab_size=D["V"], d_model=D["d"],
        n_dense=D["dense"],
        attention=dict(n_heads=D["H"], n_kv_heads=D["Hkv"], head_dim=D["Dh"],
                       qk_norm=True, rope_theta=D["theta"],
                       rope_pairing="half"),
        conv=dict(conv_kernel=D["k"]), dense_width=D["Fd"],
        moe=dict(n_experts=D["R"], top_k=D["topk"], expert_width=D["F"],
                 held_start=D["e0"], n_held=D["E"],
                 routed_scaling=float(cfg["routed_scaling_factor"]),
                 norm_topk=bool(cfg["norm_topk_prob"]),
                 norm_topk_eps=ref.ROUTE_EPS),
        eps=D["eps"], remat=bool(cfg["recompute_layers"]),
        updater=dict(cfg["updater"]), dtype=cfg["dtype"])


def _half(w, i, norm, leaves, ffn=None) -> dict:
    mixer = {_MIXER[k]: w[f"{k}.{i}"] for k in leaves if k in _MIXER}
    if ffn:
        gate, up, name = _SIDE_BY_SIDE[ffn]
        mixer[name] = jnp.concatenate((w[f"{gate}.{i}"], w[f"{up}.{i}"]), -1)
    return {"norm": {"gamma": w[f"{norm}.{i}"]}, "mixer": mixer}


def to_program(cfg: dict, w: dict) -> tuple:
    """The reference's weights as the program's tuple of per-layer parameter
    dicts: embedding, two blocks a layer, the output layer (the final norm
    alone: its matrix is the embedding's)."""
    blocks = tuple(b for i, (op, ffn) in enumerate(ref.kinds(cfg)) for b in (
        _half(w, i, "norm1", ref.OPS[op]),
        _half(w, i, "norm2", ref.FFNS[ffn], ffn)))
    return ({"W": w["wte"]}, *blocks, {"norm": {"gamma": w["normf"]}})


def from_program(cfg: dict, tree: tuple) -> dict:
    """A parameter-shaped tree of the program under the reference's names."""
    out = {"wte": tree[0]["W"], "normf": tree[-1]["norm"]["gamma"]}
    for i, (op, ffn) in enumerate(ref.kinds(cfg)):
        first, second = tree[1 + 2 * i], tree[2 + 2 * i]
        out[f"norm1.{i}"] = first["norm"]["gamma"]
        out[f"norm2.{i}"] = second["norm"]["gamma"]
        for k in ref.OPS[op]:
            out[f"{k}.{i}"] = first["mixer"][_MIXER[k]]
        gate, up, name = _SIDE_BY_SIDE[ffn]
        out[f"{gate}.{i}"], out[f"{up}.{i}"] = jnp.split(
            second["mixer"][name], 2, axis=-1)
        for k in ref.FFNS[ffn]:
            if k in _MIXER:
                out[f"{k}.{i}"] = second["mixer"][_MIXER[k]]
    return out


def new_model(cfg: dict, words, optimizer: bool = True):
    """A ``MultiLayerNetwork`` holding the benchmark's weights for ``words``
    (the seed), made on the device in one jitted call in the type the
    configuration states, with a fresh optimizer state. ``init()`` is not
    called: it would draw the program's own weights leaf by leaf."""
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork

    model = MultiLayerNetwork(build_conf(cfg))
    want = jax.eval_shape(lambda: tuple(
        l.init(jax.random.PRNGKey(0), it, model.dtype)
        for l, it in zip(model.layers, model.layer_input_types)))
    make = jax.jit(lambda s: to_program(
        cfg, ref.make_weights(cfg, s, model.dtype)))
    got = jax.eval_shape(make, words)
    if (jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(got)
            or jax.tree_util.tree_leaves(want) != jax.tree_util.tree_leaves(got)):
        raise RuntimeError(
            "the program's parameter tree is not the one this family module "
            "fills: ShortConvLM's layers changed")
    model.params = make(words)
    model.state = tuple(l.init_state(it) for l, it in
                        zip(model.layers, model.layer_input_types))
    model._build_updaters()
    if not optimizer:
        return model
    model.opt_state = jax.jit(lambda p: tuple(
        u.init(pi) for u, pi in zip(model._updaters, p)))(model.params)
    return model


def sq_norms(cfg: dict, tree: tuple, minus: tuple = None) -> dict:
    """Squared norms per leaf of a parameter-shaped tree (or of its
    difference from ``minus``), under the reference's names: an expert stack
    one entry, each convolution tap one entry."""
    f32 = lambda x: x.astype(jnp.float32)      # noqa: E731
    if minus is not None:
        tree = jax.tree_util.tree_map(lambda a, b: f32(a) - f32(b), tree, minus)
    return ref.leaf_sq_norms(from_program(cfg, tree))


def kept_leaves(cfg: dict, tree: tuple, layers) -> dict:
    """The leaves the reference keeps whole (``ref.kept_names``) of a
    parameter-shaped tree, fetched to the host under the reference's names."""
    named = from_program(cfg, tree)
    return {k: np.asarray(named[k], np.float32)
            for k in ref.kept_names(cfg, layers)}


def change_sq_norms(cfg: dict, params: tuple, words, dtype) -> dict:
    """Squared norms of (params - the seed's weights); the start is made
    again from the seed rather than kept."""
    return sq_norms(cfg, params,
                    to_program(cfg, ref.make_weights(cfg, words, dtype)))
