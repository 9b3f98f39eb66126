"""The ``ouro`` family as the program builds it: ``models.LoopedLM`` (an
embedding; a ``LoopedStack`` of two sandwich-normed ``ResidualBlock``s a
layer, around a ``GroupedQueryAttention`` with rotate-half rotary and a
``GatedMLP``, run ``total_ut_steps`` times with a final norm after each; a
``LoopExitOutputLayer`` with the untied head and the exit gate) behind
``MultiLayerNetwork``. This module is the only place that knows both the
reference's weight names (benchmark/reference/ouro.py) and the program's
parameter tree: the MLP's gate and up matrices lie side by side in one
(``Wi``). The drivers go through it, and the reference never sees it.

A configuration file names this module under ``family``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import ouro as ref

# the program's name, inside a block's "mixer", of each of the reference's
# leaves that is one array on both sides; the attention block first
_ATTN = {"a_q": "Wq", "a_k": "Wk", "a_v": "Wv", "a_o": "Wo"}


def build_conf(cfg: dict):
    """The program's configuration object for a configuration file."""
    from deeplearning4j_tpu.models import LoopedLM

    D = ref.dims(cfg)
    return LoopedLM(
        vocab_size=D["V"], d_model=D["d"], n_layers=D["L"], loops=D["T"],
        attention=dict(n_heads=D["H"], n_kv_heads=D["H"], head_dim=D["Dh"],
                       rope_theta=D["theta"], rope_pairing="half"),
        dense_width=D["F"], eps=D["eps"], remat=bool(cfg["recompute_layers"]),
        beta=D["beta"], updater=dict(cfg["updater"]), dtype=cfg["dtype"])


def to_program(cfg: dict, w: dict) -> tuple:
    """The reference's weights as the program's tuple of per-layer parameter
    dicts: embedding, the looped stack (two blocks a layer, the final gain),
    the exits (head and gate)."""
    stack = {"norm": {"gamma": w["normf"]}}
    for i in range(ref.dims(cfg)["L"]):
        gain = lambda k: {"gamma": w[f"{k}.{i}"]}               # noqa: E731
        stack[f"block{2 * i}"] = {
            "norm": gain("norm1"), "post_norm": gain("norm2"),
            "mixer": {v: w[f"{k}.{i}"] for k, v in _ATTN.items()}}
        stack[f"block{2 * i + 1}"] = {
            "norm": gain("norm3"), "post_norm": gain("norm4"),
            "mixer": {"Wi": jnp.concatenate((w[f"f_gate.{i}"], w[f"f_up.{i}"]), -1),
                      "Wo": w[f"f_down.{i}"]}}
    return ({"W": w["wte"]}, stack,
            {"W": w["w_head"], "gate": {"W": w["gate_w"], "b": w["gate_b"]}})


def from_program(cfg: dict, tree: tuple) -> dict:
    """A parameter-shaped tree of the program under the reference's names."""
    emb, stack, out = tree
    named = {"wte": emb["W"], "normf": stack["norm"]["gamma"],
             "w_head": out["W"], "gate_w": out["gate"]["W"],
             "gate_b": out["gate"]["b"]}
    for i in range(ref.dims(cfg)["L"]):
        attn, mlp = stack[f"block{2 * i}"], stack[f"block{2 * i + 1}"]
        for k, (b, part) in {"norm1": (attn, "norm"), "norm2": (attn, "post_norm"),
                             "norm3": (mlp, "norm"),
                             "norm4": (mlp, "post_norm")}.items():
            named[f"{k}.{i}"] = b[part]["gamma"]
        for k, v in _ATTN.items():
            named[f"{k}.{i}"] = attn["mixer"][v]
        named[f"f_gate.{i}"], named[f"f_up.{i}"] = jnp.split(
            mlp["mixer"]["Wi"], 2, axis=-1)
        named[f"f_down.{i}"] = mlp["mixer"]["Wo"]
    return named


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
            "fills: LoopedLM's layers changed")
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
    difference from ``minus``), under the reference's names."""
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
