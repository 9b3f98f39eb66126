"""The DeepSeek-V2/V3 family of latent-attention expert language models as
the program builds it: ``models.LatentAttentionLM`` (an embedding, two
``ResidualBlock``s a layer around a ``MultiHeadLatentAttention`` and a
``GatedMLP`` or a gated ``SparseMoE``, an ``MTPOutputLayer`` with the final
norm, the head and the MTP module) behind ``MultiLayerNetwork``. This module
is the only place that knows both the reference's weight names
(benchmark/reference/deepseek_mla.py) and the program's parameter tree: the
query up-projection's columns are split into the per-head and the rotary part
(``Wuq_n``, ``Wuq_r``), and a gated feed-forward's gate and up matrices lie
side by side in one (``Wi``, ``W1``, ``Ws1``). The drivers go through it, and
the reference never sees it.

A configuration file names this module under ``family``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import deepseek_mla as ref


def build_conf(cfg: dict):
    """The program's configuration object for a configuration file."""
    from deeplearning4j_tpu.models import LatentAttentionLM

    D = ref.dims(cfg)
    return LatentAttentionLM(
        vocab_size=D["V"], d_model=D["d"], n_layers=D["L"], n_dense=D["dense"],
        attention=dict(n_heads=D["H"], q_rank=D["Rq"], kv_rank=D["Rkv"],
                       nope_dim=D["Dn"], rope_dim=D["Dr"], v_dim=D["Dv"],
                       rope_theta=D["theta"]),
        dense_width=D["Fd"],
        moe=dict(n_experts=D["R"], top_k=D["topk"], expert_width=D["F"],
                 shared_width=D["Fs"], held_start=D["e0"], n_held=D["E"],
                 routed_scaling=float(cfg["routed_scaling_factor"]),
                 norm_topk=bool(cfg["norm_topk_prob"])),
        mtp_layers=D["mtp"], mtp_weight=D["lam"], eps=D["eps"],
        remat=bool(cfg["recompute_layers"]), updater=dict(cfg["updater"]),
        dtype=cfg["dtype"])


def _q_parts(cfg, w_uq):
    """The published ``[H, nope | rope]`` columns as (all heads' nope
    columns, all heads' rope columns)."""
    D = ref.dims(cfg)
    m = w_uq.reshape(w_uq.shape[0], D["H"], D["Dn"] + D["Dr"])
    return (m[..., :D["Dn"]].reshape(w_uq.shape[0], -1),
            m[..., D["Dn"]:].reshape(w_uq.shape[0], -1))


def _q_whole(cfg, nope, rope):
    D = ref.dims(cfg)
    r = nope.shape[0]
    return jnp.concatenate((nope.reshape(r, D["H"], D["Dn"]),
                            rope.reshape(r, D["H"], D["Dr"])), -1).reshape(r, -1)


def _attn(cfg, w, i) -> dict:
    nope, rope = _q_parts(cfg, w[f"a_uq.{i}"])
    return {"norm": {"gamma": w[f"norm1.{i}"]},
            "mixer": {"Wdq": w[f"a_dq.{i}"], "q_norm": w[f"a_qnorm.{i}"],
                      "Wuq_n": nope, "Wuq_r": rope, "Wdkv": w[f"a_dkv.{i}"],
                      "kv_norm": w[f"a_kvnorm.{i}"], "Wukv": w[f"a_ukv.{i}"],
                      "Wo": w[f"a_o.{i}"]}}


def _side(a, b):
    return jnp.concatenate((a, b), -1)


def _ffn(cfg, w, i, kind) -> dict:
    if kind == "dense":
        mixer = {"Wi": _side(w[f"f_gate.{i}"], w[f"f_up.{i}"]),
                 "Wo": w[f"f_down.{i}"]}
    else:
        mixer = {"Wr": w[f"e_router.{i}"],
                 "W1": _side(w[f"e_gate.{i}"], w[f"e_up.{i}"]),
                 "W2": w[f"e_down.{i}"],
                 "Ws1": _side(w[f"e_sgate.{i}"], w[f"e_sup.{i}"]),
                 "Ws2": w[f"e_sdown.{i}"]}
    return {"norm": {"gamma": w[f"norm2.{i}"]}, "mixer": mixer}


def to_program(cfg: dict, w: dict) -> tuple:
    """The reference's weights as the program's tuple of per-layer parameter
    dicts: embedding, two blocks a layer, the output layer."""
    blocks = tuple(b for i, kind in enumerate(ref.kinds(cfg))
                   for b in (_attn(cfg, w, i), _ffn(cfg, w, i, kind)))
    out = {"W": w["w_head"], "norm": {"gamma": w["normf"]}}
    if ref.dims(cfg)["mtp"]:
        gain = lambda k: {"gamma": w[k]}                         # noqa: E731
        out["mtp"] = {"enorm": gain("mtp_enorm"), "hnorm": gain("mtp_hnorm"),
                      "norm": gain("mtp_normf"), "Weh": w["mtp_eh"],
                      "attn": _attn(cfg, w, ref.MTP),
                      "ffn": _ffn(cfg, w, ref.MTP, "expert")}
    return ({"W": w["wte"]}, *blocks, out)


def _from_block(cfg, out, i, kind, attn, ffn):
    a, f = attn["mixer"], ffn["mixer"]
    out.update({
        f"norm1.{i}": attn["norm"]["gamma"], f"a_dq.{i}": a["Wdq"],
        f"a_qnorm.{i}": a["q_norm"],
        f"a_uq.{i}": _q_whole(cfg, a["Wuq_n"], a["Wuq_r"]),
        f"a_dkv.{i}": a["Wdkv"], f"a_kvnorm.{i}": a["kv_norm"],
        f"a_ukv.{i}": a["Wukv"], f"a_o.{i}": a["Wo"],
        f"norm2.{i}": ffn["norm"]["gamma"]})
    halves = lambda m: jnp.split(m, 2, axis=-1)                  # noqa: E731
    if kind == "dense":
        out[f"f_gate.{i}"], out[f"f_up.{i}"] = halves(f["Wi"])
        out[f"f_down.{i}"] = f["Wo"]
    else:
        out[f"e_router.{i}"] = f["Wr"]
        out[f"e_gate.{i}"], out[f"e_up.{i}"] = halves(f["W1"])
        out[f"e_down.{i}"] = f["W2"]
        out[f"e_sgate.{i}"], out[f"e_sup.{i}"] = halves(f["Ws1"])
        out[f"e_sdown.{i}"] = f["Ws2"]


def from_program(cfg: dict, tree: tuple) -> dict:
    """A parameter-shaped tree of the program under the reference's names."""
    last = tree[-1]
    out = {"wte": tree[0]["W"], "normf": last["norm"]["gamma"],
           "w_head": last["W"]}
    for i, kind in enumerate(ref.kinds(cfg)):
        _from_block(cfg, out, i, kind, tree[1 + 2 * i], tree[2 + 2 * i])
    if ref.dims(cfg)["mtp"]:
        m = last["mtp"]
        out.update({"mtp_enorm": m["enorm"]["gamma"],
                    "mtp_hnorm": m["hnorm"]["gamma"],
                    "mtp_normf": m["norm"]["gamma"], "mtp_eh": m["Weh"]})
        _from_block(cfg, out, ref.MTP, "expert", m["attn"], m["ffn"])
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
            "fills: LatentAttentionLM's layers changed")
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
