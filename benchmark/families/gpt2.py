"""The GPT-2 family as the program builds it: ``TransformerLM`` behind
``MultiLayerNetwork``. This module is the only place that knows both the
reference's weight names (benchmark/reference/gpt2.py) and the program's
parameter tree; the drivers go through it, and the reference never sees it.

A configuration file names this module under ``family``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import gpt2 as ref

# the program's per-block parameter path for each of the reference's names
_BLOCK = {
    "ln1_g": ("ln1", "gamma"), "ln1_b": ("ln1", "beta"),
    "w_qkv": ("attn", "Wqkv"), "b_qkv": ("attn", "bqkv"),
    "w_o": ("attn", "Wo"), "b_o": ("attn", "bo"),
    "ln2_g": ("ln2", "gamma"), "ln2_b": ("ln2", "beta"),
    "w_fc": ("Wi",), "b_fc": ("bi",), "w_pr": ("Wo",), "b_pr": ("bo",),
}


def build_conf(cfg: dict):
    """The program's configuration object for a configuration file."""
    from deeplearning4j_tpu.models import TransformerLM

    _, P, d, L, H, F = ref.dims(cfg)
    if F % d:
        raise ValueError("TransformerLM takes the MLP width as a multiple of "
                         f"the hidden size; n_inner {F} is not one of {d}")
    return TransformerLM(
        vocab_size=int(cfg["vocab_size"]), max_len=P, d_model=d, n_heads=H,
        n_blocks=L, ffn_mult=F // d, dtype=cfg["dtype"],
        updater=dict(cfg["updater"]))


def to_program(cfg: dict, w: dict) -> tuple:
    """The reference's stacked weights as the program's tuple of per-layer
    parameter dicts: embedding, positions, L blocks, LayerNorm, head."""
    L = int(cfg["n_layer"])

    def block(i):
        out: dict = {}
        for name, path in _BLOCK.items():
            node = out
            for k in path[:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = w[name][i]
        return out

    return ({"W": w["wte"]}, {"pos": w["wpe"]},
            *(block(i) for i in range(L)),
            {"gamma": w["lnf_g"], "beta": w["lnf_b"]},
            {"W": w["w_head"], "b": w["b_head"]})


def new_model(cfg: dict, words, optimizer: bool = True):
    """A ``MultiLayerNetwork`` holding the benchmark's weights for ``words``
    (the seed), made on the device in one jitted call in the type the
    configuration states, with a fresh optimizer state unless the model is
    only served. ``init()`` is not called: it would draw the program's own
    weights leaf by leaf."""
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
            "fills: TransformerLM's layers changed")
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
    """Squared norms per leaf and layer of a parameter-shaped tree (or of its
    difference from ``minus``), under the reference's names and its split of
    the fused qkv leaves: block leaves give [L], the others a scalar."""
    f32 = lambda x: x.astype(jnp.float32)      # noqa: E731
    if minus is not None:
        tree = jax.tree_util.tree_map(lambda a, b: f32(a) - f32(b), tree, minus)
    L = int(cfg["n_layer"])
    sq = lambda x: jnp.sum(jnp.square(f32(x)))  # noqa: E731
    out = {}
    for name, path in _BLOCK.items():
        per_layer = []
        for b in tree[2:2 + L]:
            for k in path:
                b = b[k]
            per_layer.append([sq(t) for _, t in ref.split_qkv(name, b)])
        for j, (part, _) in enumerate(ref.split_qkv(name, jnp.zeros((3,)))):
            out[part] = jnp.stack([row[j] for row in per_layer])
    out.update(wte=sq(tree[0]["W"]), wpe=sq(tree[1]["pos"]),
               lnf_g=sq(tree[2 + L]["gamma"]), lnf_b=sq(tree[2 + L]["beta"]),
               w_head=sq(tree[3 + L]["W"]), b_head=sq(tree[3 + L]["b"]))
    return out


def kept_leaves(cfg: dict, tree: tuple, layers) -> dict:
    """The leaves the reference keeps whole (``ref.KEPT_WHOLE`` and the
    blocks ``layers``) of a parameter-shaped tree, fetched to the host under
    the reference's names, the fused qkv leaves split in three there."""
    L = int(cfg["n_layer"])
    whole = {"wpe": tree[1]["pos"], "lnf_g": tree[2 + L]["gamma"],
             "lnf_b": tree[2 + L]["beta"], "b_head": tree[3 + L]["b"]}
    out = {k: np.asarray(whole[k], np.float32) for k in ref.KEPT_WHOLE}
    for name, path in _BLOCK.items():
        for i in layers:
            leaf = tree[2 + int(i)]
            for k in path:
                leaf = leaf[k]
            leaf = np.asarray(leaf, np.float32)
            if "qkv" in name:
                for part, t in zip("qkv", np.split(leaf, 3, axis=-1)):
                    out[f"{name.replace('qkv', part)}.{i}"] = t
            else:
                out[f"{name}.{i}"] = leaf
    return out


def change_sq_norms(cfg: dict, params: tuple, words, dtype) -> dict:
    """Squared norms of (params - the seed's weights). The start is made
    again from the seed rather than kept: a copy would sit in device memory
    through the window."""
    return sq_norms(cfg, params,
                    to_program(cfg, ref.make_weights(cfg, words, dtype)))
