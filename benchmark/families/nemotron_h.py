"""The ``nemotron_h`` family as the program builds it: ``models.HybridLM``
(an embedding, one ``ResidualBlock`` a letter of the pattern around a
``Mamba2Mixer``, a ``GroupedQueryAttention`` or a ``SparseMoE``, an RMSNorm,
a bias-free ``RnnOutputLayer``) behind ``MultiLayerNetwork``. This module is
the only place that knows both the reference's weight names
(benchmark/reference/nemotron_h.py) and the program's parameter tree; the
drivers go through it, and the reference never sees it.

A configuration file names this module under ``family``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import nemotron_h as ref

# the program's name, inside a block's "mixer", of each of the reference's
_MIXER = {
    "m_in": "W_in", "m_conv_w": "conv_w", "m_conv_b": "conv_b",
    "m_dt_bias": "dt_bias", "m_A_log": "A_log", "m_D": "D", "m_norm": "norm",
    "m_out": "W_out",
    "a_q": "Wq", "a_k": "Wk", "a_v": "Wv", "a_o": "Wo",
    "e_router": "Wr", "e_w1": "W1", "e_w2": "W2", "e_s1": "Ws1", "e_s2": "Ws2",
}


def build_conf(cfg: dict):
    """The program's configuration object for a configuration file."""
    from deeplearning4j_tpu.models import HybridLM

    D = ref.dims(cfg)
    return HybridLM(
        ref.pattern(cfg), vocab_size=D["V"], d_model=D["d"],
        max_len=int(cfg["max_position_embeddings"]),
        mamba=dict(n_heads=D["H"], head_dim=D["P"], n_groups=D["G"],
                   state_size=D["N"], conv_kernel=D["k"],
                   chunk=int(cfg["chunk_size"])),
        attention=dict(n_heads=D["Hq"], n_kv_heads=D["Hkv"], head_dim=D["Dh"]),
        moe=dict(n_experts=D["R"], top_k=D["topk"], expert_width=D["F"],
                 shared_width=D["Fs"], held_start=D["e0"], n_held=D["E"],
                 routed_scaling=float(cfg["routed_scaling_factor"]),
                 norm_topk=bool(cfg["norm_topk_prob"])),
        eps=D["eps"], remat=bool(cfg["recompute_layers"]),
        updater=dict(cfg["updater"]), dtype=cfg["dtype"])


def to_program(cfg: dict, w: dict) -> tuple:
    """The reference's weights as the program's tuple of per-layer parameter
    dicts: embedding, one block a letter, RMSNorm, head."""
    blocks = tuple(
        {"norm": {"gamma": w[f"norm.{i}"]},
         "mixer": {_MIXER[k]: w[f"{k}.{i}"] for k in ref.LEAVES[c]}}
        for i, c in enumerate(ref.pattern(cfg)))
    return ({"W": w["wte"]}, *blocks, {"gamma": w["normf"]},
            {"W": w["w_head"]})


def from_program(cfg: dict, tree: tuple) -> dict:
    """A parameter-shaped tree of the program under the reference's names."""
    L = len(ref.pattern(cfg))
    out = {"wte": tree[0]["W"], "normf": tree[1 + L]["gamma"],
           "w_head": tree[2 + L]["W"]}
    for i, c in enumerate(ref.pattern(cfg)):
        out[f"norm.{i}"] = tree[1 + i]["norm"]["gamma"]
        for k in ref.LEAVES[c]:
            out[f"{k}.{i}"] = tree[1 + i]["mixer"][_MIXER[k]]
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
            "fills: HybridLM's layers changed")
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
    difference from ``minus``), under the reference's names; the expert
    stacks give one per expert."""
    f32 = lambda x: x.astype(jnp.float32)      # noqa: E731
    if minus is not None:
        tree = jax.tree_util.tree_map(lambda a, b: f32(a) - f32(b), tree, minus)
    return ref.leaf_sq_norms(from_program(cfg, tree))


def kept_leaves(cfg: dict, tree: tuple, layers) -> dict:
    """The leaves the reference keeps whole (``ref.kept_names``: the layers
    ``layers``, the first attention layer beside them, the final norm) of a
    parameter-shaped tree, fetched to the host under the reference's names."""
    named = from_program(cfg, tree)
    return {k: np.asarray(named[k], np.float32)
            for k in ref.kept_names(cfg, layers)}


def change_sq_norms(cfg: dict, params: tuple, words, dtype) -> dict:
    """Squared norms of (params - the seed's weights). The start is made
    again from the seed rather than kept: a copy would sit in device memory
    through the window."""
    return sq_norms(cfg, params,
                    to_program(cfg, ref.make_weights(cfg, words, dtype)))
