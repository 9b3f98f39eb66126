"""The latent-attention expert stack on the CPU at a toy size, each new piece
against the benchmark's plain reference (benchmark/reference/deepseek_mla.py)
on seeded weights: rotary positions against a complex-number form; the flash
kernels of two-part scores at unequal widths, in the interpreter, against
``_reference`` with the scale given; the latent-attention layer, the gated
feed-forward and the gated ``SparseMoE``, forward and gradients; the MTP
output layer's loss and the embedding's summed gradient; the expert shares
adding up to the uncut layer.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.families import deepseek_mla as fam  # noqa: E402
from benchmark.reference import deepseek_mla as ref  # noqa: E402
from deeplearning4j_tpu.nn.input_type import InputType  # noqa: E402
from deeplearning4j_tpu.nn.layers.attention import rotary  # noqa: E402
from deeplearning4j_tpu.nn.model import MultiLayerNetwork  # noqa: E402
from deeplearning4j_tpu.ops import flash_mla  # noqa: E402
from deeplearning4j_tpu.ops.flash_attention import _reference  # noqa: E402

CFG = {
    "num_hidden_layers": 2, "first_k_dense_replace": 1, "hidden_size": 32,
    "vocab_size": 50, "max_position_embeddings": 64,
    "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 12,
    "rope_theta": 10000.0, "rope_interleave": True, "intermediate_size": 40,
    "moe_intermediate_size": 12, "n_shared_experts": 1, "n_routed_experts": 4,
    "router_experts": 16, "held_experts_start": 8, "num_experts_per_tok": 3,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "num_nextn_predict_layers": 1, "mtp_loss_weight": 0.3,
    "rms_norm_eps": 1e-6, "dtype": "float32", "recompute_layers": True,
    "updater": {"type": "adam", "lr": 3e-4},
}
B, T = 2, 21
IT = InputType.recurrent(32, T)


def _weights(cfg=CFG, seed=7):
    return ref.make_weights(cfg, ref.seed_words(seed), jnp.float32)


def _u(seed=0, d=32):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, T, d), jnp.float32)


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(float(np.max(np.abs(b))), 1e-12)
    assert float(np.max(np.abs(a - b))) / scale < tol, \
        float(np.max(np.abs(a - b))) / scale


def _model(cfg=CFG):
    return MultiLayerNetwork(fam.build_conf(cfg))


# ---------------------------------------------------------------------------
# rotary positions
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("heads", [1, 3], ids=["one-head", "three-heads"])
def test_rotary_against_the_complex_form(heads):
    """The adjacent pair ``(2i, 2i+1)`` of a head at position ``t`` is
    multiplied, as a complex number, by ``exp(i t theta^(-2i/width))``; heads
    side by side in the lanes turn alike; position 0 is left as it is."""
    width, theta = 8, 32e6
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1),
                                     (2, 11, heads * width)), np.float64)
    pos = np.arange(11) * 37
    got = rotary(jnp.asarray(x, jnp.float32), jnp.asarray(pos), width=width,
                 theta=theta)
    xs = x.reshape(2, 11, heads, width)
    z = xs[..., 0::2] + 1j * xs[..., 1::2]
    ang = pos[:, None] * theta ** (-np.arange(0, width, 2) / width)
    z = z * np.exp(1j * ang)[None, :, None, :]
    want = np.empty_like(xs)
    want[..., 0::2], want[..., 1::2] = z.real, z.imag
    _close(got, want.reshape(x.shape), 1e-5)
    _close(got[:, 0], x[:, 0], 1e-7)
    # the reference's own rotation, a head at a time
    small = jnp.asarray(x, jnp.float32)
    _close(rotary(small, jnp.arange(11), width=width, theta=theta),
           ref.rope(small.reshape(2, 11, heads, width), theta).reshape(x.shape),
           1e-6)


# ---------------------------------------------------------------------------
# the kernels, in the interpreter
# ---------------------------------------------------------------------------


def _operands(Bk, Tk, H, Dn, Dr, Dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    f = lambda k, *s: jax.random.normal(k, s, jnp.float32)      # noqa: E731
    return ((f(ks[0], Bk, Tk, H * Dn), f(ks[1], Bk, Tk, H * Dr),
             f(ks[2], Bk, Tk, H * (Dn + Dv)), f(ks[3], Bk, Tk, Dr)),
            f(ks[4], Bk, Tk, H * Dv),
            (jax.random.uniform(ks[5], (Bk, Tk)) > 0.25).astype(
                jnp.float32).at[:, 0].set(1.0))


def _as_reference(qn, qr, kv, kr, H, Dn, Dr, kmask, scale):
    """The same attention through ``_reference``: q and k joined to the
    score width, the rotary key repeated to every head."""
    Bk, Tk, _ = qn.shape
    kv = kv.reshape(Bk, Tk, H, -1)
    q = jnp.concatenate((qn.reshape(Bk, Tk, H, Dn), qr.reshape(Bk, Tk, H, Dr)), -1)
    k = jnp.concatenate((kv[..., :Dn], jnp.broadcast_to(
        kr[:, :, None, :], (Bk, Tk, H, Dr))), -1)
    return _reference(q, k, kv[..., Dn:], True, kmask, scale).reshape(Bk, Tk, -1)


@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "kmask"])
@pytest.mark.parametrize("Tk,blocks", [(256, (128, 128)), (200, (64, 128)),
                                       (300, (None, None))],
                         ids=["aligned", "padded", "chosen"])
def test_flash_mla_kernels_against_the_reference(Tk, blocks, masked):
    """Forward, dq (both parts), dk/dv and the rotary key's summed gradient
    at the published widths (128 | 64 score lanes, 128 value lanes, two heads
    a program), causal, with and without a key mask, at lengths that do and
    do not fill the blocks."""
    H, Dn, Dr, Dv = 2, 128, 64, 128
    ops, w, km = _operands(1 if Tk > 256 else 2, Tk, H, Dn, Dr, Dv)
    km = km if masked else None
    scale = 1.0 / np.sqrt(Dn + Dr)
    kern = lambda *a: jnp.sum(flash_mla.flash_mla(               # noqa: E731
        *a, n_heads=H, scale=scale, kmask=km, block_q=blocks[0],
        block_k=blocks[1], interpret=True) * w)
    plain = lambda *a: jnp.sum(_as_reference(                    # noqa: E731
        *a, H, Dn, Dr, km, scale) * w)
    got = jax.value_and_grad(kern, argnums=(0, 1, 2, 3))(*ops)
    want = jax.value_and_grad(plain, argnums=(0, 1, 2, 3))(*ops)
    _close(got[0], want[0], 1e-5)
    for g, r in zip(got[1], want[1]):
        _close(g, r, 2e-5)
    # the XLA form the layer takes off the TPU is the same function
    _close(flash_mla.mla_attention_xla(*ops, n_heads=H, scale=scale, kmask=km),
           _as_reference(*ops, H, Dn, Dr, km, scale), 1e-5)


def test_flash_mla_widths_that_fill_no_lane_block_are_refused():
    assert flash_mla.heads_per_program(32, 128, 64, 128) == 2
    assert flash_mla.heads_per_program(16, 128, 128, 128) == 1
    assert flash_mla.heads_per_program(3, 128, 64, 128) is None     # odd heads
    assert flash_mla.heads_per_program(4, 16, 8, 12) is None
    ops, _, _ = _operands(1, 16, 4, 16, 8, 12)
    with pytest.raises(ValueError, match="lane blocks"):
        flash_mla.flash_mla(*ops, n_heads=4, scale=0.2, interpret=True)


# ---------------------------------------------------------------------------
# the layers against the reference's functions
# ---------------------------------------------------------------------------


def _block(i):
    """(program's attention block, its feed-forward block) of layer ``i``
    with their parameters filled from the reference's weights."""
    model, w = _model(), _weights()
    tree = fam.to_program(CFG, w)
    return (model.layers[1 + 2 * i], tree[1 + 2 * i],
            model.layers[2 + 2 * i], tree[2 + 2 * i], w)


def _grads_agree(prog_fn, prog_p, ref_fn, ref_p, names, u, tol=5e-5):
    """Forward, input gradient and every parameter's gradient: ``names``
    maps a reference leaf to a function of the program's gradient tree."""
    probe = _u(9, u.shape[-1])
    (yp, (gp, gup)) = (prog_fn(prog_p, u), jax.grad(
        lambda p, x: jnp.sum(prog_fn(p, x) * probe), argnums=(0, 1))(prog_p, u))
    (yr, (gr, gur)) = (ref_fn(ref_p, u), jax.grad(
        lambda p, x: jnp.sum(ref_fn(p, x) * probe), argnums=(0, 1))(ref_p, u))
    _close(yp, yr, tol)
    _close(gup, gur, tol)
    for name, pick in names.items():
        _close(pick(gp), gr[name], tol)


def test_latent_attention_layer_against_the_reference():
    attn, p, _, _, w = _block(0)
    mixer, pm = attn.mixer, p["mixer"]
    lw = ref.layer_weights(w, 0, "dense")
    whole = lambda g: fam._q_whole(CFG, g["Wuq_n"], g["Wuq_r"])  # noqa: E731
    _grads_agree(
        lambda q, x: mixer.apply(q, {}, x, train=True)[0], pm,
        lambda q, x: ref.attention(CFG, None, x, q), lw,
        {"a_dq": lambda g: g["Wdq"], "a_qnorm": lambda g: g["q_norm"],
         "a_uq": whole, "a_dkv": lambda g: g["Wdkv"],
         "a_kvnorm": lambda g: g["kv_norm"], "a_ukv": lambda g: g["Wukv"],
         "a_o": lambda g: g["Wo"]}, _u(1))


def test_latent_attention_layer_on_the_kernels_in_the_interpreter():
    """At widths that fill lane blocks ``use_flash=True`` takes the kernels
    (the interpreter off the TPU) and agrees with the layer's XLA form."""
    from deeplearning4j_tpu.nn.layers import MultiHeadLatentAttention

    kw = dict(n_heads=2, q_rank=24, kv_rank=16, nope_dim=128, rope_dim=64,
              v_dim=128, rope_theta=32e6)
    it = InputType.recurrent(32, 40)
    flash = MultiHeadLatentAttention(use_flash=True, **kw)
    plain = MultiHeadLatentAttention(use_flash=False, **kw)
    p = flash.init(jax.random.PRNGKey(0), it)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 40, 32), jnp.float32)
    f = lambda l: jax.value_and_grad(                            # noqa: E731
        lambda q: jnp.sum(jnp.square(l.apply(q, {}, x, train=True)[0])))(p)
    (a, ga), (b, gb) = f(flash), f(plain)
    _close(a, b, 1e-5)
    for k in gb:
        _close(ga[k], gb[k], 5e-5)


def test_gated_feed_forward_against_the_reference():
    _, _, ffn, p, w = _block(0)
    halves = lambda m: jnp.split(m, 2, axis=-1)                  # noqa: E731
    _grads_agree(
        lambda q, x: ffn.mixer.apply(q, {}, x, train=True)[0], p["mixer"],
        lambda q, x: ref.dense(CFG, None, x, q), ref.layer_weights(w, 0, "dense"),
        {"f_gate": lambda g: halves(g["Wi"])[0],
         "f_up": lambda g: halves(g["Wi"])[1], "f_down": lambda g: g["Wo"]},
        _u(2))


@pytest.mark.parametrize("kernels", [False, True], ids=["plain", "pallas"])
def test_gated_sparse_moe_against_the_reference(kernels, monkeypatch):
    _, _, ffn, p, w = _block(1)
    moe, state = ffn.mixer, ffn.mixer.init_state(IT)
    assert moe.gated and p["mixer"]["W1"].shape == (4, 32, 24)
    halves = lambda m: jnp.split(m, 2, axis=-1)                  # noqa: E731
    names = {"e_router": lambda g: g["Wr"],
             "e_gate": lambda g: halves(g["W1"])[0],
             "e_up": lambda g: halves(g["W1"])[1], "e_down": lambda g: g["W2"],
             "e_sgate": lambda g: halves(g["Ws1"])[0],
             "e_sup": lambda g: halves(g["Ws1"])[1],
             "e_sdown": lambda g: g["Ws2"]}

    import contextlib

    from jax.experimental.pallas import tpu as pltpu

    prog = lambda q, x: moe.apply(q, state, x, train=True)[0]   # noqa: E731
    if kernels:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the kernels round their operands to bfloat16, as a float32 product on
    # the TPU does: compared at that tolerance
    with (pltpu.force_tpu_interpret_mode() if kernels
          else contextlib.nullcontext()):
        _grads_agree(prog, p["mixer"],
                     lambda q, x: ref.experts(CFG, None, x, q),
                     ref.layer_weights(w, 1, "expert"), names, _u(3),
                     tol=3e-2 if kernels else 5e-5)
    monkeypatch.undo()
    _, st = moe.apply(p["mixer"], state, _u(3), train=True)
    stats = moe.stats_dict(st["stats"])
    assert stats["pairs_dropped"] == 0.0 and stats["pairs_held"] > 0


def test_two_matrix_experts_keep_their_tree():
    """``gated`` is a field: without it the layer is the hybrid
    configuration's (``relu^2``, ``W1`` of the expert width)."""
    from deeplearning4j_tpu.nn.layers import SparseMoE

    kw = dict(n_experts=8, top_k=2, expert_width=12, shared_width=20)
    plain = SparseMoE(**kw).init(jax.random.PRNGKey(0), IT)
    gated = SparseMoE(gated=True, **kw).init(jax.random.PRNGKey(0), IT)
    assert set(plain) == set(gated) == {"Wr", "W1", "W2", "Ws1", "Ws2"}
    assert plain["W1"].shape == (8, 32, 12) and gated["W1"].shape == (8, 32, 24)
    assert plain["Ws1"].shape == (32, 20) and gated["Ws1"].shape == (32, 40)


# ---------------------------------------------------------------------------
# the two heads
# ---------------------------------------------------------------------------


def _ids(seed=5):
    ids = np.random.default_rng(seed).integers(0, 50, (B, T), dtype=np.int32)
    return ids, np.roll(ids, -1, axis=1)


def test_mtp_loss_and_the_embeddings_summed_gradient_against_the_reference():
    """``_loss`` hands the output layer the embedding by reference: the loss
    is ``L_main + 0.3 L_mtp`` of the reference, each parameter has one
    gradient, and the embedding's is the sum of its two uses (dropping
    either moves it)."""
    model, w = _model(), _weights()
    params = fam.to_program(CFG, w)
    state = tuple(l.init_state(it) for l, it in
                  zip(model.layers, model.layer_input_types))
    ids, labels = _ids()

    def prog(p):
        loss, (new_state, _) = model._loss(p, state, ids, labels, None, None,
                                           None, train=True)
        return loss, new_state

    (loss, new_state), grads = jax.value_and_grad(prog, has_aux=True)(params)
    want, gref = jax.value_and_grad(
        lambda p: ref.loss_rows(CFG, p, ids, labels) / B)(w)
    _close(loss, want, 1e-6)
    named = fam.from_program(CFG, grads)
    assert set(named) == set(gref)
    for k in gref:
        _close(named[k], gref[k], 1e-4)
    main, mtp = (t / B for t in ref.loss_terms(CFG, w, ids, labels))
    stats = np.asarray(new_state[-1]["stats"])
    _close(stats[0], main, 1e-6)
    _close(stats[1], mtp, 1e-6)
    assert len(stats) == 2 + 6          # the MTP expert layer's counters follow
    # one use alone gives another gradient: the trunk's ids, or the MTP's
    trunk_only = jax.grad(lambda p: ref.loss_terms(CFG, p, ids, labels)[0] / B)(w)
    gap = float(jnp.linalg.norm(trunk_only["wte"] - gref["wte"])
                / jnp.linalg.norm(gref["wte"]))
    assert gap > 1e-2, gap


def test_fit_publishes_both_loss_terms_and_the_mtp_expert_counters():
    from deeplearning4j_tpu import obs

    class Quiet:
        def on_epoch_start(self, *a): pass
        def on_epoch_end(self, *a): pass
        def on_gradient_calculation(self, *a): pass
        def iteration_done(self, *a): pass

    def total(name, layer):
        for f in obs.registry().families():
            if f.name == name:
                return sum(v for k, v in f.as_dict().items() if layer in str(k))
        return 0.0

    model = fam.new_model(CFG, ref.seed_words(11))
    model.set_listeners(Quiet())
    last = str(len(model.layers) - 1)
    before = {n: total(n, last) for n in (
        "dl4j_main_loss_total", "dl4j_mtp_loss_total", "dl4j_moe_steps_total")}
    ids, labels = _ids()
    model.fit([(ids, labels)] * 2)
    got = {n: total(n, last) - v for n, v in before.items()}
    assert got["dl4j_moe_steps_total"] == 2.0
    # random weights: each term is about T ln V a row
    for n in ("dl4j_main_loss_total", "dl4j_mtp_loss_total"):
        assert 1.5 * T * np.log(50) < got[n] < 2.5 * T * np.log(50), got
    # one Adam state a parameter, a layer: what the train driver reads
    assert len(model.opt_state) == len(model.layers)
    assert jax.tree_util.tree_structure(model.opt_state[-1]["m"]) == \
        jax.tree_util.tree_structure(model.params[-1])


def test_an_output_layer_with_no_second_head_scores_as_before():
    cfg = dict(CFG, num_nextn_predict_layers=0)
    model, w = _model(cfg), _weights(cfg)
    assert model.layers[-1].shared_params() == {}
    params = fam.to_program(cfg, w)
    state = tuple(l.init_state(it) for l, it in
                  zip(model.layers, model.layer_input_types))
    assert state[-1] == {} and "mtp" not in params[-1]
    ids, labels = _ids()
    loss, _ = model._loss(params, state, ids, labels, None, None, None, train=True)
    _close(loss, ref.loss_rows(cfg, w, ids, labels) / B, 1e-6)


# ---------------------------------------------------------------------------
# the share
# ---------------------------------------------------------------------------


def test_the_shares_expert_sums_add_up_to_the_uncut_layer():
    """Four shares of four experts over the router's 16: the shares' outputs,
    the shared expert counted once, add up to what the uncut reference gives
    for the whole layer, in the program and in the reference alike."""
    whole = dict(CFG, n_routed_experts=16, held_experts_start=0)
    ww = _weights(whole)
    lw = ref.layer_weights(ww, 1, "expert")
    u = _u(4)
    uncut = ref.experts(whole, None, u, lw)
    no_shared = dict(lw, e_sgate=lw["e_sgate"] * 0, e_sup=lw["e_sup"] * 0)
    shared = uncut - ref.experts(whole, None, u, no_shared)
    total_ref, total_prog = shared, shared
    for s in range(4):
        cfg = dict(CFG, held_experts_start=4 * s)
        part = dict(lw, **{k: lw[k][4 * s:4 * s + 4]
                           for k in ("e_gate", "e_up", "e_down")})
        total_ref = total_ref + ref.experts(cfg, None, u, part) - shared
        moe = _model(cfg).layers[4].mixer
        assert (moe.held_start, moe.n_held, moe.n_experts) == (4 * s, 4, 16)
        named = {f"{k}.1": v for k, v in part.items()}
        pm = fam._ffn(cfg, named, 1, "expert")["mixer"]
        y, _ = moe.apply(pm, moe.init_state(IT), u, train=True)
        total_prog = total_prog + y - shared
    _close(total_ref, uncut, 1e-5)
    _close(total_prog, uncut, 1e-5)


def test_the_builders_configuration_survives_json():
    """The nested mixers of the blocks and of the output layer's MTP module
    come back as the layers they were."""
    from deeplearning4j_tpu.nn.model import MultiLayerConfiguration

    conf = fam.build_conf(CFG)
    again = MultiLayerConfiguration.from_json(conf.to_json())
    assert again == conf and again.to_json() == conf.to_json()
    out = again.layers[-1]
    assert (type(out.attention).__name__, type(out.ffn).__name__, out.ffn.gated,
            out.mtp_layers, out.remat) == (
        "MultiHeadLatentAttention", "SparseMoE", True, 1, True)


def test_the_expert_layers_pair_buffers_are_the_layers_default():
    """The cell sizes nothing of the pair buffer: the even share of the
    pairs, twice it, the geometric mean of that and every pair, then every
    pair, here as in the hybrid configuration; the two sizes the layer had
    before are among them, and a layer that holds every expert has one."""
    import dataclasses

    from deeplearning4j_tpu.nn.layers import SparseMoE

    kw = dict(n_experts=256, top_k=8, expert_width=768, n_held=16)
    caps = SparseMoE(gated=True, **kw).row_caps(8192)
    assert caps == (4096, 8192, 23296, 65536) and {8192, 65536} <= set(caps)
    assert SparseMoE(n_experts=128, top_k=6, expert_width=1856,
                     n_held=8).row_caps(4096) == (1536, 3072, 8704, 24576)
    assert dataclasses.replace(SparseMoE(gated=True, **kw),
                               n_held=0).row_caps(8192) == (65536,)
    conf = fam.build_conf(CFG)
    for moe in (conf.layers[4].mixer, conf.layers[-1].ffn):
        assert moe.row_caps(64) == SparseMoE(
            n_experts=moe.n_experts, top_k=moe.top_k,
            expert_width=moe.expert_width, n_held=moe.n_held).row_caps(64)
