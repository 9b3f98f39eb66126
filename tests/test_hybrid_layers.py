"""The hybrid state-space / attention / sparse-expert layers on the CPU at a
toy size, each against the benchmark's plain reference
(benchmark/reference/nemotron_h.py) on seeded weights: forward and gradients;
the chunked scan against the step-by-step recurrence at a length that is no
multiple of the chunk; the expert shares adding up to the uncut layer; a
planted router that sends every token to one held expert, nothing dropped.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import nemotron_h as ref  # noqa: E402
from deeplearning4j_tpu.nn.input_type import InputType  # noqa: E402
from deeplearning4j_tpu.nn.layers import (  # noqa: E402
    GroupedQueryAttention, Mamba2Mixer, ResidualBlock, RMSNorm, SparseMoE)
from deeplearning4j_tpu.nn.layers.ssm import (  # noqa: E402
    causal_depthwise_conv1d, ssd_chunked_scan)
from deeplearning4j_tpu.ops.grouped_matmul import tiles_needed  # noqa: E402

CFG = {
    "hybrid_override_pattern": "M*E", "hidden_size": 32, "vocab_size": 50,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 8, "conv_kernel": 4, "chunk_size": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "n_routed_experts": 4, "router_experts": 16, "held_experts_start": 8,
    "num_experts_per_tok": 3, "moe_intermediate_size": 12,
    "moe_shared_expert_intermediate_size": 20, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "norm_eps": 1e-5,
}
IT = InputType.recurrent(32, 21)


def _weights(cfg=CFG, seed=7):
    return ref.make_weights(cfg, ref.seed_words(seed), jnp.float32)


def _u(seed=0, B=2, T=21, d=32):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, T, d), jnp.float32)


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(float(np.max(np.abs(b))), 1e-12)
    assert float(np.max(np.abs(a - b))) / scale < tol, \
        float(np.max(np.abs(a - b))) / scale


def _mamba_layer():
    return Mamba2Mixer(n_heads=4, head_dim=8, n_groups=2, state_size=8,
                       conv_kernel=4, chunk=8)


def _moe_layer(**kw):
    base = dict(n_experts=16, top_k=3, expert_width=12, shared_width=20,
                held_start=8, n_held=4, routed_scaling=2.5)
    return SparseMoE(**dict(base, **kw))


def _prog(names: dict, w: dict, i: int) -> dict:
    return {mine: w[f"{theirs}.{i}"] for theirs, mine in names.items()}


M_NAMES = {"m_in": "W_in", "m_conv_w": "conv_w", "m_conv_b": "conv_b",
           "m_dt_bias": "dt_bias", "m_A_log": "A_log", "m_D": "D",
           "m_norm": "norm", "m_out": "W_out"}
A_NAMES = {"a_q": "Wq", "a_k": "Wk", "a_v": "Wv", "a_o": "Wo"}
E_NAMES = {"e_router": "Wr", "e_w1": "W1", "e_w2": "W2", "e_s1": "Ws1",
           "e_s2": "Ws2"}


def _fwd_and_grads(layer, names, mixer, i, state=None, u=None):
    """The layer's forward and its gradients (parameters and input) beside
    the reference mixer's, under one scalar loss."""
    w, u = _weights(), _u() if u is None else u
    p_prog = _prog(names, w, i)
    p_ref = {k: w[f"{k}.{i}"] for k in names}
    st = layer.init_state(IT) if state is None else state
    probe = jax.random.normal(jax.random.PRNGKey(3), u.shape)

    f_prog = lambda p, x: jnp.sum(layer.apply(p, st, x)[0] * probe)   # noqa: E731
    f_ref = lambda p, x: jnp.sum(mixer(CFG, None, x, p) * probe)      # noqa: E731
    _close(layer.apply(p_prog, st, u)[0], mixer(CFG, None, u, p_ref))
    gp, gx = jax.grad(f_prog, (0, 1))(p_prog, u)
    rp, rx = jax.grad(f_ref, (0, 1))(p_ref, u)
    _close(gx, rx)
    for theirs, mine in names.items():
        _close(gp[mine], rp[theirs])


def test_rms_norm_is_the_reference():
    x = _u()
    g = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (32,))
    y, _ = RMSNorm(eps=1e-5).apply({"gamma": g}, {}, x)
    _close(y, ref._rms(x, g, 1e-5), 1e-6)
    assert RMSNorm().init(jax.random.PRNGKey(0), IT)["gamma"].shape == (32,)


@pytest.mark.parametrize("T,chunk", [(37, 8), (16, 8), (5, 8), (64, 16)])
def test_chunked_scan_is_the_step_by_step_recurrence(T, chunk):
    """Forward and every gradient, at lengths that are and are not a
    multiple of the chunk (and one shorter than a chunk)."""
    ks = jax.random.split(jax.random.PRNGKey(T), 5)
    B, H, P, G, N = 2, 4, 8, 2, 8
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) - 1.0)
    A = -jnp.exp(0.5 * jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, T, G, N))
    Cm = jax.random.normal(ks[4], (B, T, G, N))
    probe = jax.random.normal(jax.random.PRNGKey(9), (B, T, H, P))
    f = lambda *a: jnp.sum(ssd_chunked_scan(*a, chunk) * probe)   # noqa: E731
    r = lambda *a: jnp.sum(ref.recurrence(*a) * probe)            # noqa: E731
    args = (x, dt, A, Bm, Cm)
    _close(ssd_chunked_scan(*args, chunk), ref.recurrence(*args))
    for got, want in zip(jax.grad(f, range(5))(*args),
                         jax.grad(r, range(5))(*args)):
        _close(got, want)


def test_causal_depthwise_conv_looks_back_only():
    x = jnp.zeros((1, 6, 3)).at[0, 2].set(1.0)
    w = jnp.arange(1.0, 13.0).reshape(4, 3)
    y = causal_depthwise_conv1d(x, w, jnp.zeros((3,)))
    np.testing.assert_allclose(y[0, :, 0], [0, 0, 10, 7, 4, 1])


def test_mamba2_mixer_forward_and_gradients():
    _fwd_and_grads(_mamba_layer(), M_NAMES, ref.mamba, 0)


def test_mamba2_mask_leaves_the_state_alone():
    """A padded position passes the state on unchanged (its dt is nought):
    the positions before it read as without a mask, those after it do not
    see what it would have written."""
    layer, w = _mamba_layer(), _weights()
    p = _prog(M_NAMES, w, 0)
    u = _u(B=1, T=12)
    y, _ = layer.apply(p, {}, u, mask=jnp.ones((1, 12)).at[0, 5].set(0.0))
    y2, _ = layer.apply(p, {}, u, mask=jnp.ones((1, 12)))
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(y[0, :5], y2[0, :5], rtol=1e-5, atol=1e-6)
    assert not np.allclose(y[0, 6:], y2[0, 6:])


def test_grouped_query_attention_is_the_full_masked_square():
    layer = GroupedQueryAttention(n_heads=4, n_kv_heads=2, head_dim=8)
    _fwd_and_grads(layer, A_NAMES, ref.attention, 1)
    shapes = jax.tree_util.tree_map(
        lambda a: a.shape, layer.init(jax.random.PRNGKey(0), IT))
    assert shapes == {"Wq": (32, 32), "Wk": (32, 16), "Wv": (32, 16),
                      "Wo": (32, 32)}


def test_sparse_moe_forward_and_gradients():
    """At 42 tokens one buffer holds every pair; at 256 three buffer sizes
    are under the pairs' number (the step's own pairs say which runs)."""
    _fwd_and_grads(_moe_layer(), E_NAMES, ref.experts, 2)
    assert _moe_layer().row_caps(42) == (126,)
    assert _moe_layer().row_caps(256) == (256, 384, 640, 768)
    _fwd_and_grads(_moe_layer(), E_NAMES, ref.experts, 2, u=_u(8, B=4, T=64))


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts as 4 shares of 4: the four shares' results, with the
    shared expert counted once, are the uncut reference's layer. The same
    holds among the reference's own shares."""
    whole_cfg = dict(CFG, n_routed_experts=16, held_experts_start=0)
    w = ref.make_weights(whole_cfg, ref.seed_words(11), jnp.float32)
    u = _u(5)
    p_whole = {k: w[f"{k}.2"] for k in E_NAMES}
    want = ref.experts(whole_cfg, None, u, p_whole)
    shared = (jnp.square(jax.nn.relu(u @ p_whole["e_s1"])) @ p_whole["e_s2"])
    got, got_ref, pairs = 0.0, 0.0, 0.0
    for s in range(4):
        sl = slice(4 * s, 4 * s + 4)
        layer = _moe_layer(held_start=4 * s)
        p = {"Wr": p_whole["e_router"], "W1": p_whole["e_w1"][sl],
             "W2": p_whole["e_w2"][sl], "Ws1": p_whole["e_s1"],
             "Ws2": p_whole["e_s2"]}
        y, st = layer.apply(p, layer.init_state(IT), u)
        got = got + (y - shared)
        pairs += SparseMoE.stats_dict(st["stats"])["pairs_held"]
        share_cfg = dict(CFG, held_experts_start=4 * s)
        got_ref = got_ref + ref.experts(
            share_cfg, None, u, dict(p_whole, e_w1=p_whole["e_w1"][sl],
                                     e_w2=p_whole["e_w2"][sl])) - shared
    _close(got + shared, want)
    _close(got_ref + shared, want)
    assert pairs == u.shape[0] * u.shape[1] * 3      # every pair has one home


@pytest.mark.parametrize("planted", [(9,), (9, 10)])
def test_a_planted_router_drops_nothing_and_the_counters_say_so(planted):
    """Every token sent to one held expert (through the routing bias), then
    to two: the expert's load is all N tokens, with two the pairs that land
    here pass twice the even share and a larger buffer takes them (the
    smallest that holds them); the result is the reference's with the same
    bias, and nothing is dropped. The products ran the occupied row tiles
    and no more."""
    layer, w, u = _moe_layer(), _weights(), _u(2, B=3, T=50)
    N = 150
    caps = layer.row_caps(N)
    assert caps == (128, 256, 384, 450)
    p = _prog(E_NAMES, w, 2)
    bias = jnp.zeros((16,)).at[jnp.array(planted)].set(10.0)
    st = dict(layer.init_state(IT), bias=bias)
    probe = jax.random.normal(jax.random.PRNGKey(5), u.shape)
    y, new = jax.jit(lambda p, st, u: layer.apply(p, st, u))(p, st, u)
    stats = SparseMoE.stats_dict(new["stats"])
    assert stats["load_max"] == N and stats["pairs_dropped"] == 0.0
    assert stats["pairs_held"] >= N * len(planted)
    assert (stats["pairs_held"] > 256) == (len(planted) == 2)
    assert stats["load_mean"] == stats["pairs_held"] / 4
    # an expert with all N = 150 tokens has two tiles of 128, the others one
    assert stats["rows_computed"] == 128 * (4 + len(planted))
    fits = min(c for c in caps if c >= stats["pairs_held"])
    assert fits == (256, 384)[len(planted) - 1]
    assert stats["rows_buffer"] == 128 * tiles_needed(fits, 4)
    np.testing.assert_array_equal(new["bias"], bias)     # a buffer: unmoved
    p_ref = dict({k: w[f"{k}.2"] for k in E_NAMES}, e_bias=bias)
    _close(y, ref.experts(CFG, None, u, p_ref))
    # the gradients too
    g = jax.grad(lambda p: jnp.sum(layer.apply(p, st, u)[0] * probe))(p)
    r = jax.grad(lambda p: jnp.sum(ref.experts(CFG, None, u, p) * probe))(p_ref)
    for theirs, mine in E_NAMES.items():
        _close(g[mine], r[theirs])
    # and under an even router twice the even share is enough
    _, even = layer.apply(p, layer.init_state(IT), u)
    even = SparseMoE.stats_dict(even["stats"])
    assert even["pairs_held"] <= 256 and even["pairs_dropped"] == 0.0
    assert even["rows_buffer"] <= 128 * tiles_needed(256, 4)


def test_buffer_sizes_at_the_published_sizes():
    """The even share, twice it, the geometric mean of that and every pair,
    every pair, in whole row tiles: at the three expert cells' sizes, with
    the two sizes the layer had before among them (so that no step takes a
    larger buffer than it took), and one size where every expert is held."""
    layer = SparseMoE(n_experts=128, top_k=6, expert_width=1856,
                      shared_width=3712, n_held=8)
    assert layer.row_caps(4096) == (1536, 3072, 8704, 24576)
    assert layer.row_caps(8 * 4096) == (12288, 24576, 69632, 196608)
    assert dataclasses.replace(layer, n_held=0).row_caps(4096) == (24576,)
    joyai = SparseMoE(n_experts=256, top_k=8, expert_width=768, n_held=16,
                      gated=True)
    assert joyai.row_caps(8192) == (4096, 8192, 23296, 65536)
    lfm2 = SparseMoE(n_experts=32, top_k=4, expert_width=1792, n_held=8,
                     gated=True)
    assert lfm2.row_caps(2 * 8192) == (16384, 32768, 46464, 65536)
    for moe, n, before in ((layer, 4096, (3072, 24576)),
                           (joyai, 8192, (8192, 65536)),
                           (lfm2, 16384, (32768, 65536))):
        caps = moe.row_caps(n)
        assert set(before) <= set(caps) and caps == tuple(sorted(set(caps)))
        assert all(c % 128 == 0 for c in caps) and caps[-1] == n * moe.top_k
        assert [128 * tiles_needed(c, moe.n_held) for c in caps] == [
            c + 128 * moe.n_held for c in caps]
    with pytest.raises(ValueError):
        dataclasses.replace(layer, held_start=124).init(
            jax.random.PRNGKey(0), InputType.recurrent(8, 4))


def _planted_step(m, B=2, T=128):
    """A 16-expert top-2 layer holding four, 256 tokens of which the first
    ``m`` are unmasked and send both their pairs to held experts 9 and 10
    (through the routing bias): the step holds exactly ``2 m`` pairs."""
    layer = SparseMoE(n_experts=16, top_k=2, expert_width=12, shared_width=20,
                      held_start=8, n_held=4, routed_scaling=2.5)
    it = InputType.recurrent(32, T)
    p = layer.init(jax.random.PRNGKey(0), it)
    st = dict(layer.init_state(it),
              bias=jnp.zeros((16,)).at[jnp.array([9, 10])].set(10.0))
    u = jax.random.normal(jax.random.PRNGKey(1), (B, T, 32))
    mask = (jnp.arange(B * T) < m).astype(jnp.float32).reshape(B, T)
    return layer, p, st, u, mask


@functools.lru_cache(maxsize=None)
def _planted_programs():
    """The planted layer's value, counters and gradients on the interpreted
    kernels as compiled programs of ``(p, u, mask)``: under ``None`` the
    layer as it stands, under each of its buffer sizes the layer with that
    size forced (as the smaller of two, the other never taken, so that the
    step keeps its conditional). Compiled once for all the cases."""
    from unittest import mock

    from jax.experimental.pallas import tpu as pltpu

    layer, p, st, u, mask = _planted_step(0)
    caps = layer.row_caps(u.shape[0] * u.shape[1])
    probe = jax.random.normal(jax.random.PRNGKey(2), u.shape)

    def f(p, u, mask):
        y, new = layer.apply(p, st, u, mask=mask)
        return jnp.sum(y * probe), (y, new["stats"])

    def compiled():
        return jax.jit(jax.value_and_grad(f, (0, 1), has_aux=True)).lower(
            p, u, mask).compile()

    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            pltpu.force_tpu_interpret_mode():
        programs = {None: compiled()}
        for cap in caps:
            with mock.patch.object(
                    SparseMoE, "row_caps",
                    lambda self, n, cap=cap: (cap, 2 * caps[-1])):
                programs[cap] = compiled()
    return caps, programs


@pytest.mark.parametrize("pairs", [126, 128, 130, 254, 256, 258, 382, 384,
                                   386, 512])
def test_every_buffer_that_holds_the_pairs_gives_the_same_bits(pairs):
    """The layer on the interpreted kernels, the step's held pairs just
    under, on and just over each size of the buffer: the step takes the
    smallest size that holds them, and in every other size that holds them
    the result and every gradient are the same to the bit: the rows a
    larger buffer adds gather token 0 under weight 0 and add exact zeros."""
    caps, programs = _planted_programs()
    assert caps == (128, 256, 384, 512)
    _, p, _, u, mask = _planted_step(pairs // 2)

    def run(program):
        (_, (y, stats)), g = program(p, u, mask)
        return y, SparseMoE.stats_dict(stats), jax.tree_util.tree_leaves(g)

    y, stats, g = run(programs[None])
    fits = [c for c in caps if c >= pairs]
    assert stats["pairs_held"] == pairs and stats["pairs_dropped"] == 0.0
    assert stats["rows_buffer"] == 128 * tiles_needed(fits[0], 4)
    assert np.abs(np.asarray(g[1])).max() > 0           # W1's gradient
    for cap in fits:
        y1, stats1, g1 = run(programs[cap])
        assert stats1["rows_buffer"] == 128 * tiles_needed(cap, 4)
        assert stats1["rows_computed"] == stats["rows_computed"]
        np.testing.assert_array_equal(y1, y)
        for a, b in zip(g1, g):
            np.testing.assert_array_equal(a, b)


def test_cond_recomputed_runs_and_differentiates_the_taken_branch_alone():
    """Four branches over buffers of four sizes, the index reckoned as the
    layer reckons it (the sizes the count exceeds): the smallest size that
    holds the count runs, forward and, inside the backward pass, once more
    with its own backward; no other branch runs in either pass, and what
    the backward pass keeps is the arguments alone."""
    from jax._src.ad_checkpoint import saved_residuals

    from deeplearning4j_tpu.nn.layers.moe import _cond_recomputed

    caps, ran = (4, 8, 16, 32), []

    def branch(x, n, *, j):
        jax.debug.callback(lambda: ran.append(j))
        buf = jnp.zeros((caps[j],), x.dtype).at[:x.shape[0]].set(x)
        return (j + 1.0) * jnp.sum(jnp.sin(buf) ** 2), jnp.float32(caps[j])

    def layer(x, n):
        taken = jnp.sum(n > jnp.array(caps[:-1]), dtype=jnp.int32)
        return _cond_recomputed(taken, branch,
                                tuple((("j", j),) for j in range(4)),
                                (x,), (n,))

    x = jnp.linspace(0.1, 0.4, 4)
    both = jax.jit(jax.value_and_grad(lambda x, n: layer(x, n)[0]))
    for n, j in ((0, 0), (3, 0), (4, 0), (5, 1), (8, 1), (9, 2), (16, 2),
                 (17, 3), (32, 3)):
        ran.clear()
        y, size = jax.jit(layer)(x, jnp.int32(n))
        jax.effects_barrier()
        assert ran == [j] and size == caps[j]
        np.testing.assert_allclose(y, (j + 1) * np.sum(np.sin(x) ** 2),
                                   rtol=1e-6)
        ran.clear()
        _, g = both(x, jnp.int32(n))
        jax.effects_barrier()
        assert ran == [j, j]
        np.testing.assert_allclose(g, (j + 1) * np.sin(2 * x), rtol=1e-6)
    kept = saved_residuals(lambda x: layer(x, jnp.int32(9))[0], x)
    assert all(np.size(a) <= x.size for a, _ in kept), kept


def test_the_counters_carry_the_buffers_rows_and_obs_adds_them_up():
    """``state["stats"]`` names the rows of the buffer the step took beside
    the other five counters, and ``publish_stats`` adds them to
    ``dl4j_moe_rows_buffer_total`` under the layer's index."""
    from deeplearning4j_tpu import obs
    from deeplearning4j_tpu.nn.layers.moe import _MOE_STATS

    def total(name, layer):
        fam = [f for f in obs.registry().families() if f.name == name]
        return dict(fam[0].as_dict()).get((layer,), 0.0) if fam else 0.0

    assert list(_MOE_STATS)[-1] == "rows_buffer" and len(_MOE_STATS) == 6
    layer, p, st, u, mask = _planted_step(100)
    assert st["stats"].shape == (6,)
    _, new = layer.apply(p, st, u, mask=mask)
    stats = SparseMoE.stats_dict(new["stats"])
    assert list(stats) == list(_MOE_STATS)
    assert stats["pairs_held"] == 200
    assert stats["rows_buffer"] == 128 * tiles_needed(256, 4) == 768
    assert stats["rows_computed"] == 128 * 4 <= stats["rows_buffer"]
    before = {k: total(f"dl4j_moe_{k}_total", "77") for k in _MOE_STATS}
    layer.publish_stats(77, new["stats"])
    layer.publish_stats(77, new["stats"])
    for k in _MOE_STATS:
        assert total(f"dl4j_moe_{k}_total", "77") - before[k] == 2 * stats[k]
    whole = dataclasses.replace(layer, n_held=0, held_start=0)
    pw = whole.init(jax.random.PRNGKey(0), InputType.recurrent(32, 128))
    _, new = whole.apply(pw, whole.init_state(IT), u)
    assert SparseMoE.stats_dict(new["stats"])["rows_buffer"] == \
        128 * tiles_needed(512, 16)


def test_masked_tokens_are_not_routed():
    layer, w, u = _moe_layer(n_held=0, held_start=0), None, _u(4, B=1, T=10)
    p = layer.init(jax.random.PRNGKey(0), IT)
    mask = jnp.ones((1, 10)).at[0, 7:].set(0.0)
    _, st = layer.apply(p, layer.init_state(IT), u, mask=mask)
    assert SparseMoE.stats_dict(st["stats"])["pairs_held"] == 7 * 3


def _tiny_lm(**kw):
    from deeplearning4j_tpu.models import HybridLM

    args = dict(
        pattern="MEM*E", vocab_size=50, d_model=32, max_len=24,
        mamba=dict(n_heads=4, head_dim=8, n_groups=2, state_size=8, chunk=8),
        attention=dict(n_heads=4, n_kv_heads=2, head_dim=8),
        moe=dict(n_experts=16, top_k=3, expert_width=12, shared_width=20,
                 held_start=8, n_held=4, routed_scaling=2.5))
    return HybridLM(**dict(args, **kw))


def test_hybrid_lm_builder_round_trips_and_names_its_layers():
    from deeplearning4j_tpu.nn.model import (
        MultiLayerConfiguration, MultiLayerNetwork)

    conf = _tiny_lm(remat=True)
    assert MultiLayerConfiguration.from_json(conf.to_json()) == conf
    kinds = [type(l.mixer).__name__ for l in conf.layers[1:-2]]
    assert kinds == ["Mamba2Mixer", "SparseMoE", "Mamba2Mixer",
                     "GroupedQueryAttention", "SparseMoE"]
    assert all(l.remat for l in conf.layers[1:-2])
    assert type(conf.layers[-2]).__name__ == "RMSNorm"
    assert conf.layers[-1].has_bias is False
    model = MultiLayerNetwork(conf).init()
    assert "b" not in model.params[-1]
    with pytest.raises(ValueError):
        _tiny_lm(pattern="MXE")


def test_recomputation_is_a_field_and_changes_no_number():
    """``remat`` comes from the configuration, and no environment variable
    is read in the new layers: the flag changes what is kept between the
    passes, not the loss nor a gradient."""
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork

    ids = np.random.RandomState(0).randint(0, 50, (2, 24)).astype(np.int32)
    out = []
    for remat in (False, True):
        m = MultiLayerNetwork(_tiny_lm(remat=remat)).init(seed=3)
        rngs = m._layer_rngs(jax.random.PRNGKey(0))
        (loss, _), g = jax.value_and_grad(
            lambda p: m._loss(p, m.state, ids, np.roll(ids, -1, 1), None,
                              None, rngs), has_aux=True)(m.params)
        out.append((loss, g))
    np.testing.assert_allclose(out[0][0], out[1][0], rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(out[0][1]),
                    jax.tree_util.tree_leaves(out[1][1])):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)
    src = open(os.path.join(ROOT, "deeplearning4j_tpu/nn/layers/residual.py")).read()
    assert "environ" not in src


def test_fit_feeds_the_expert_counters_with_the_loss():
    """``fit()`` publishes each expert layer's step counters to ``obs``
    from the fetch that brings the loss: one series a layer, nothing
    dropped, a step counted for every loss a listener saw."""
    from deeplearning4j_tpu import obs
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork

    class Listener:
        seen = 0

        def on_epoch_start(self, *a): pass
        def on_epoch_end(self, *a): pass
        def on_gradient_calculation(self, *a): pass

        def iteration_done(self, model, it, score, n=0):
            Listener.seen += 1

    def total(name):
        fam = [f for f in obs.registry().families() if f.name == name]
        return dict(fam[0].as_dict()) if fam else {}

    before = {n: total(n) for n in ("dl4j_moe_steps_total",
                                    "dl4j_moe_pairs_held_total",
                                    "dl4j_moe_pairs_dropped_total")}
    m = MultiLayerNetwork(_tiny_lm()).init()
    m.set_listeners(Listener())
    ids = np.random.RandomState(1).randint(0, 50, (2, 24)).astype(np.int32)
    m.fit([(ids, np.roll(ids, -1, 1))] * 3)
    assert Listener.seen == 3
    for layer in ("2", "5"):                    # the two E layers' indices
        key = (layer,)
        d = lambda n: total(n).get(key, 0) - before[n].get(key, 0)  # noqa: E731
        assert d("dl4j_moe_steps_total") == 3
        assert d("dl4j_moe_pairs_dropped_total") == 0
        assert 0 < d("dl4j_moe_pairs_held_total") <= 3 * 48 * 3
