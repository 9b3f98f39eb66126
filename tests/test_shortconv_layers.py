"""The layers of the gated-short-convolution expert stack on the CPU at a toy
size, each against the benchmark's plain reference
(benchmark/reference/lfm2_moe.py) on seeded weights: the convolution mixer
forward and gradients, with a row's first positions (the zero history) and
two rows (nothing leaks from one row's end into the next row's start);
grouped-query attention with normed and rotated q and k on the XLA path and
on the flash kernels in the interpreter; the rotation's two pairings; the
routing weights' denominator; the tied head's one matrix, one gradient, one
updater state; the four expert shares adding up to the uncut layer.
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

from benchmark.families import lfm2_moe as fam  # noqa: E402
from benchmark.reference import lfm2_moe as ref  # noqa: E402
from deeplearning4j_tpu.nn.input_type import InputType  # noqa: E402
from deeplearning4j_tpu.nn.layers import (  # noqa: E402
    GroupedQueryAttention, MTPOutputLayer, ShortConvMixer, SparseMoE)
from deeplearning4j_tpu.nn.layers.attention import rotary  # noqa: E402
from deeplearning4j_tpu.nn.model import MultiLayerNetwork  # noqa: E402

CFG = {
    "num_hidden_layers": 3, "layer_types": ["conv", "full_attention", "conv"],
    "num_dense_layers": 1, "hidden_size": 32, "vocab_size": 50,
    "num_attention_heads": 4, "num_key_value_heads": 2, "conv_L_cache": 3,
    "rope_theta": 10000.0, "intermediate_size": 40,
    "moe_intermediate_size": 12, "num_experts": 4, "router_experts": 16,
    "held_experts_start": 8, "num_experts_per_tok": 3,
    "routed_scaling_factor": 1.0, "norm_topk_prob": True, "norm_eps": 1e-5,
    "dtype": "float32", "recompute_layers": False,
    "updater": {"type": "adam", "lr": 3e-4},
}
B, T = 2, 21
IT = InputType.recurrent(32, T)

C_NAMES = {"c_in": "W_in", "c_conv": "conv_w", "c_out": "W_out"}
A_NAMES = {"a_q": "Wq", "a_k": "Wk", "a_v": "Wv", "a_o": "Wo",
           "a_qnorm": "q_norm", "a_knorm": "k_norm"}


def _weights(cfg=CFG, seed=7):
    return ref.make_weights(cfg, ref.seed_words(seed), jnp.float32)


def _u(seed=0, B=B, T=T, d=32):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, T, d), jnp.float32)


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(float(np.max(np.abs(b))), 1e-12)
    assert float(np.max(np.abs(a - b))) / scale < tol, \
        float(np.max(np.abs(a - b))) / scale


def _attn_layer(**kw):
    return GroupedQueryAttention(**dict(dict(
        n_heads=4, n_kv_heads=2, head_dim=8, qk_norm=True, eps=1e-5,
        rope_theta=10000.0, rope_pairing="half"), **kw))


def _fwd_and_grads(layer, names, mixer, i, cfg=CFG, u=None, tol=2e-5):
    """The layer's forward and its gradients (parameters and input) beside
    the reference operator's, under one scalar loss."""
    w, u = _weights(cfg), _u() if u is None else u
    p_prog = {mine: w[f"{theirs}.{i}"] for theirs, mine in names.items()}
    p_ref = {k: w[f"{k}.{i}"] for k in names}
    probe = jax.random.normal(jax.random.PRNGKey(3), u.shape)
    f_prog = lambda p, x: jnp.sum(layer.apply(p, {}, x)[0] * probe)   # noqa: E731
    f_ref = lambda p, x: jnp.sum(mixer(cfg, None, x, p) * probe)      # noqa: E731
    _close(layer.apply(p_prog, {}, u)[0], mixer(cfg, None, u, p_ref), tol)
    gp, gx = jax.grad(f_prog, (0, 1))(p_prog, u)
    rp, rx = jax.grad(f_ref, (0, 1))(p_ref, u)
    _close(gx, rx, tol)
    for theirs, mine in names.items():
        _close(gp[mine], rp[theirs], tol)


# ---------------------------------------------------------------------------
# The gated short convolution
# ---------------------------------------------------------------------------


def test_short_conv_mixer_forward_and_gradients():
    layer = ShortConvMixer(conv_kernel=3)
    _fwd_and_grads(layer, C_NAMES, ref.conv, 0)
    shapes = jax.tree_util.tree_map(
        lambda a: a.shape, layer.init(jax.random.PRNGKey(0), IT))
    assert shapes == {"W_in": (32, 96), "conv_w": (3, 32), "W_out": (32, 32)}


def test_short_conv_by_hand_at_a_rows_start_and_across_rows():
    """Position 0 sees one tap, position 1 two (the history before a row is
    zero), and the second row's first positions see nothing of the first
    row's last: written out position by position."""
    w = _weights()
    p = {mine: w[f"{theirs}.0"] for theirs, mine in C_NAMES.items()}
    u = _u(4)
    y, _ = ShortConvMixer(conv_kernel=3).apply(p, {}, u)
    s = np.asarray(u @ p["W_in"], np.float64)
    b, c, x = s[..., :32], s[..., 32:64], s[..., 64:]
    z, taps = b * x, np.asarray(p["conv_w"], np.float64)
    for row in range(B):
        for t in (0, 1, 2, T - 1):
            conv = sum(taps[2 - j] * z[row, t - j] for j in range(3) if t - j >= 0)
            _close(y[row, t], (c[row, t] * conv) @ np.asarray(p["W_out"], np.float64))
    # a row alone gives what it gives beside another
    alone, _ = ShortConvMixer(conv_kernel=3).apply(p, {}, u[1:])
    _close(alone[0], y[1], 1e-6)


def test_short_conv_looks_back_only_and_a_masked_position_adds_nothing():
    w = _weights()
    p = {mine: w[f"{theirs}.0"] for theirs, mine in C_NAMES.items()}
    layer, u = ShortConvMixer(conv_kernel=3), _u(6)
    y, _ = layer.apply(p, {}, u)
    later = u.at[:, 10:].set(0.0)
    _close(layer.apply(p, {}, later)[0][:, :10], y[:, :10], 1e-6)
    # position 5 masked: the positions after it read a zero there
    mask = jnp.ones((B, T)).at[:, 5].set(0.0)
    masked, _ = layer.apply(p, {}, u, mask=mask)
    s = u @ p["W_in"]
    z = (s[..., :32] * s[..., 64:]).at[:, 5].set(0.0)
    zp = jnp.pad(z, ((0, 0), (2, 0), (0, 0)))
    conv = sum(zp[:, j:j + T] * p["conv_w"][j] for j in range(3))
    _close(masked, (s[..., 32:64] * conv) @ p["W_out"], 1e-6)
    assert float(jnp.max(jnp.abs(masked[:, 6] - y[:, 6]))) > 1e-4


# ---------------------------------------------------------------------------
# Rotary positions and grouped-query attention with normed q and k
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("heads", [1, 3], ids=["one-head", "three-heads"])
def test_rotary_half_pairing_against_the_complex_form(heads):
    """Lane i and lane i + width/2 of a head turn as one complex number by
    t * theta^(-2i/width); the reference's own rotation agrees."""
    width, theta = 8, 100.0
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, heads * width))
    pos = jnp.arange(5)
    got = np.asarray(rotary(x, pos, width=width, theta=theta, pairing="half"))
    xs = np.asarray(x, np.float64).reshape(2, 5, heads, width)
    z = xs[..., :width // 2] + 1j * xs[..., width // 2:]
    ang = np.arange(5)[:, None] * theta ** (-np.arange(0, width, 2) / width)
    z = z * np.exp(1j * ang)[None, :, None]
    want = np.concatenate([z.real, z.imag], -1).reshape(2, 5, heads * width)
    _close(got, want, 1e-6)
    _close(ref.rope(x.reshape(2, 5, heads, width), theta).reshape(got.shape),
           want, 1e-6)
    # the adjacent pairing is another rotation, and the default
    adjacent = rotary(x, pos, width=width, theta=theta)
    assert float(jnp.max(jnp.abs(adjacent - got))) > 1e-2
    with pytest.raises(ValueError):
        rotary(x, pos, width=width, pairing="whole")


def test_grouped_query_attention_with_norms_and_rotation_on_the_xla_path():
    layer = _attn_layer()
    _fwd_and_grads(layer, A_NAMES, ref.attention, 1)
    shapes = jax.tree_util.tree_map(
        lambda a: a.shape, layer.init(jax.random.PRNGKey(0), IT))
    assert shapes == {"Wq": (32, 32), "Wk": (32, 16), "Wv": (32, 16),
                      "Wo": (32, 32), "q_norm": (8,), "k_norm": (8,)}


def test_grouped_query_attention_off_by_default_keeps_its_tree_and_result():
    """Without the two fields the layer has four matrices and applies no
    position: shifting the whole row changes nothing but the causal cut."""
    layer = GroupedQueryAttention(n_heads=4, n_kv_heads=2, head_dim=8)
    p = layer.init(jax.random.PRNGKey(0), IT)
    assert set(p) == {"Wq", "Wk", "Wv", "Wo"}
    u = _u(2)
    y, _ = layer.apply(p, {}, u)
    with_norm = _attn_layer(rope_theta=0.0)
    pn = dict(p, q_norm=jnp.ones((8,)), k_norm=jnp.ones((8,)))
    assert float(jnp.max(jnp.abs(with_norm.apply(pn, {}, u)[0] - y))) > 1e-3
    turned = _attn_layer(qk_norm=False)
    assert float(jnp.max(jnp.abs(turned.apply(p, {}, u)[0] - y))) > 1e-4
    _close(turned.apply(p, {}, u)[0][:, 0], y[:, 0], 1e-6)   # position 0 does not turn


def test_grouped_query_attention_on_the_flash_kernels_in_the_interpreter():
    """Heads of 64 lanes in pairs, a sequence of two blocks: the kernels'
    forward and (on the XLA backward off the TPU) gradients against the
    reference's attention layer."""
    cfg = dict(CFG, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=64)
    layer = _attn_layer(head_dim=64, use_flash=True)
    u = _u(9, B=2, T=256, d=64)
    _fwd_and_grads(layer, A_NAMES, ref.attention, 1, cfg=cfg, u=u, tol=2e-4)


# ---------------------------------------------------------------------------
# The expert layer: the weights' denominator and the four shares
# ---------------------------------------------------------------------------


def _moe_layer(**kw):
    return SparseMoE(**dict(dict(
        n_experts=16, top_k=3, expert_width=12, held_start=8, n_held=4,
        gated=True, norm_topk_eps=ref.ROUTE_EPS), **kw))


def _moe_params(p_ref, sl=slice(None)):
    return {"Wr": p_ref["e_router"],
            "W1": jnp.concatenate((p_ref["e_gate"][sl], p_ref["e_up"][sl]), -1),
            "W2": p_ref["e_down"][sl]}


def test_gated_experts_without_a_shared_expert_against_the_reference():
    w, u = _weights(), _u(3)
    p_ref = {k: w[f"{k}.1"] for k in ref.FFNS["expert"]}
    layer = _moe_layer()
    assert set(layer.init(jax.random.PRNGKey(0), IT)) == {"Wr", "W1", "W2"}
    y, st = layer.apply(_moe_params(p_ref), layer.init_state(IT), u)
    _close(y, ref.experts(CFG, None, u, p_ref))
    assert SparseMoE.stats_dict(st["stats"])["pairs_dropped"] == 0.0


def test_the_routing_weights_denominator_is_a_field_that_defaults_to_nought():
    """With the field at 0 the weights are the other two expert
    configurations' to the bit; at 1e-6 they are the reference's."""
    w, u = _weights(), _u(3).reshape(-1, 32)
    p = {"Wr": w["e_router.1"]}
    bias = jnp.zeros((16,))
    eid0, w0 = _moe_layer(norm_topk_eps=0.0)._route(p, bias, u)
    s = jax.nn.sigmoid(jnp.matmul(u, p["Wr"], precision=jax.lax.Precision.HIGHEST))
    chosen = jnp.take_along_axis(s, eid0, -1)
    assert bool((w0 == chosen / jnp.sum(chosen, -1, keepdims=True)).all())
    eid1, w1 = _moe_layer()._route(p, bias, u)
    want_id, want_w = ref.route(CFG, u, p["Wr"])
    assert bool((eid1 == want_id).all()) and bool((eid0 == want_id).all())
    _close(w1, want_w, 1e-6)
    assert bool((jnp.sum(w1, -1) < 1.0).all())


def test_the_four_shares_expert_layers_add_up_to_the_uncut_layer():
    """16 experts as 4 shares of 4, the router whole in each: the four
    shares' results are the uncut reference's layer (there is no shared
    expert to count once), and every pair has one home. The same holds among
    the reference's own shares."""
    whole_cfg = dict(CFG, num_experts=16, held_experts_start=0)
    w = _weights(whole_cfg, seed=11)
    u = _u(5)
    p_whole = {k: w[f"{k}.1"] for k in ref.FFNS["expert"]}
    want = ref.experts(whole_cfg, None, u, p_whole)
    got, got_ref, pairs = 0.0, 0.0, 0.0
    for s in range(4):
        sl = slice(4 * s, 4 * s + 4)
        layer = _moe_layer(held_start=4 * s)
        y, st = layer.apply(_moe_params(p_whole, sl), layer.init_state(IT), u)
        got = got + y
        pairs += SparseMoE.stats_dict(st["stats"])["pairs_held"]
        got_ref = got_ref + ref.experts(
            dict(CFG, held_experts_start=4 * s), None, u,
            dict(p_whole, **{k: p_whole[k][sl]
                             for k in ("e_gate", "e_up", "e_down")}))
    _close(got, want)
    _close(got_ref, want)
    assert pairs == B * T * 3


# ---------------------------------------------------------------------------
# The head tied to the embedding, and the whole toy model
# ---------------------------------------------------------------------------


def _model(**kw):
    return MultiLayerNetwork(fam.build_conf(dict(CFG, **kw)))


def _ids(seed=0):
    ids = np.random.default_rng(seed).integers(0, 50, (B, T), dtype=np.int32)
    return ids, np.roll(ids, -1, axis=1)


def _state(model):
    return tuple(l.init_state(it) for l, it in
                 zip(model.layers, model.layer_input_types))


def test_the_tied_head_has_one_matrix_one_gradient_and_one_updater_state():
    """The loss and every gradient are the reference's; the embedding's is
    the sum of the look-up's and the head's uses (either alone is another
    gradient); the output layer owns the final gain alone, so Adam keeps one
    pair of moments for the matrix."""
    model, w = _model(), _weights()
    params = fam.to_program(CFG, w)
    state, (ids, labels) = _state(model), _ids()
    assert model.layers[-1].shared_params() == {"embedding": 0}
    assert set(params[-1]) == {"norm"}

    def prog(p):
        return model._loss(p, state, ids, labels, None, None, None, train=True)[0]

    loss, grads = jax.value_and_grad(prog)(params)
    want, gref = jax.value_and_grad(
        lambda p: ref.loss_rows(CFG, p, ids, labels) / B)(w)
    _close(loss, want, 1e-6)
    named = fam.from_program(CFG, grads)
    assert set(named) == set(gref)
    for k in gref:
        _close(named[k], gref[k], 1e-4)

    def uses(p, looked_up, head):
        """The reference's loss with the embedding's two uses told apart."""
        x = ref.trunk(CFG, dict(p, wte=looked_up), ids)
        return jnp.sum(ref.head_nll(CFG, dict(p, wte=head), x, labels)) / B

    g_look, g_head = jax.grad(uses, (1, 2))(w, w["wte"], w["wte"])
    _close(g_look + g_head, gref["wte"], 1e-5)
    norm = float(jnp.linalg.norm(gref["wte"]))
    assert float(jnp.linalg.norm(g_look)) > 1e-2 * norm
    assert float(jnp.linalg.norm(g_head)) > 1e-2 * norm

    model = fam.new_model(CFG, ref.seed_words(7))
    leaves = lambda t: [x.shape for x in jax.tree_util.tree_leaves(t)]  # noqa: E731
    assert leaves(model.opt_state[-1]["m"]) == [(32,)]
    assert leaves(model.opt_state[0]["m"]) == [(50, 32)]
    assert model.num_params() == ref.num_params(CFG)


def test_the_tied_heads_output_and_an_untied_heads_tree():
    """``output()`` hands the head the embedding too: softmax of the
    reference's logits. Untied, the layer has a matrix of its own and
    declares nothing shared; a tied head takes no bias."""
    model, w = _model(), _weights()
    model.params, model.state = fam.to_program(CFG, w), _state(model)
    ids, _ = _ids(2)
    eps = CFG["norm_eps"]
    h = ref._rms(ref.trunk(CFG, w, ids), w["normf"], eps)
    _close(model.output(ids), jax.nn.softmax(h @ w["wte"].T, -1), 1e-5)
    untied = MTPOutputLayer(n_out=50, eps=eps)
    assert untied.shared_params() == {}
    assert set(untied.init(jax.random.PRNGKey(0), IT)) == {"W", "norm"}
    with pytest.raises(ValueError):
        MTPOutputLayer(n_out=50, tied=True, has_bias=True).init(
            jax.random.PRNGKey(0), IT)


def test_recomputation_changes_no_number_of_the_toy_model():
    w = _weights()
    params, (ids, labels) = fam.to_program(CFG, w), _ids(1)
    out = []
    for remat in (False, True):
        model = _model(recompute_layers=remat)
        state = _state(model)
        out.append(jax.value_and_grad(lambda p: model._loss(
            p, state, ids, labels, None, None, None, train=True)[0])(params))
    assert float(out[0][0]) == float(out[1][0])
    for a, b in zip(jax.tree_util.tree_leaves(out[0][1]),
                    jax.tree_util.tree_leaves(out[1][1])):
        _close(a, b, 1e-6)


def test_the_builders_configuration_survives_json_and_names_its_layers():
    from deeplearning4j_tpu.models import ShortConvLM
    from deeplearning4j_tpu.nn.model import MultiLayerConfiguration

    conf = fam.build_conf(CFG)
    again = MultiLayerConfiguration.from_json(conf.to_json())
    assert again == conf and again.to_json() == conf.to_json()
    kinds = [type(l.mixer).__name__ for l in conf.layers[1:-1]]
    assert kinds == ["ShortConvMixer", "GatedMLP", "GroupedQueryAttention",
                     "SparseMoE", "ShortConvMixer", "SparseMoE"]
    assert conf.layers[-1].tied and not conf.layers[-1].mtp_layers
    with pytest.raises(ValueError):
        ShortConvLM(["conv", "sliding"], vocab_size=50, d_model=32)
    with pytest.raises(ValueError):
        ShortConvLM(["conv"], vocab_size=50, d_model=32, n_dense=2)


def test_fit_trains_the_toy_model_and_feeds_the_expert_counters():
    """``fit()`` lowers the loss and publishes the two expert layers'
    counters (blocks 4 and 6 of the stack) with it, nothing dropped."""
    from deeplearning4j_tpu import obs

    def total(name):
        found = [f for f in obs.registry().families() if f.name == name]
        return dict(found[0].as_dict()) if found else {}

    class Listener:                 # fit() fetches the loss for a listener
        def on_epoch_start(self, *a): pass
        def on_epoch_end(self, *a): pass
        def on_gradient_calculation(self, *a): pass
        def iteration_done(self, *a, **kw): pass

    names = ("dl4j_moe_steps_total", "dl4j_moe_pairs_dropped_total")
    before = {n: total(n) for n in names}
    model = fam.new_model(CFG, ref.seed_words(3))
    model.set_listeners(Listener())
    ids, labels = _ids(4)
    loss0 = model.score((ids, labels))
    model.fit([(ids, labels)] * 6)
    assert model.score((ids, labels)) < loss0
    for layer in ("4", "6"):
        d = lambda n: total(n).get((layer,), 0) - before[n].get((layer,), 0)  # noqa: E731
        assert d("dl4j_moe_steps_total") == 6
        assert d("dl4j_moe_pairs_dropped_total") == 0
