"""The looped language model's layers on the CPU at a toy size, against the
benchmark's plain reference (benchmark/reference/ouro.py) on seeded weights:
each loop's logits and exit gate, the exit distribution, the loss with its
entropy term, the gradient of every leaf the loops share (the sum of what
separate copies a loop would get), one updater state a parameter, the
sandwich norm left off bit for bit where it is not asked for, and the loop's
counters for known loops, blocks and recomputation.
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

from benchmark.families import ouro as fam  # noqa: E402
from benchmark.reference import ouro as ref  # noqa: E402
from deeplearning4j_tpu.nn.input_type import InputType  # noqa: E402
from deeplearning4j_tpu.nn.layers import (  # noqa: E402
    GatedMLP, GroupedQueryAttention, LoopedStack, ResidualBlock)
from deeplearning4j_tpu.nn.layers.normalization import rms_norm  # noqa: E402
from deeplearning4j_tpu.nn.model import (  # noqa: E402
    MultiLayerConfiguration, MultiLayerNetwork)

CFG = {
    "num_hidden_layers": 2, "layer_types": ["full_attention"] * 2,
    "hidden_size": 32, "vocab_size": 53, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 8, "intermediate_size": 40,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "total_ut_steps": 3,
    "exit_entropy_beta": 0.1, "dtype": "float32", "recompute_layers": False,
    "updater": {"type": "adam", "lr": 3e-4},
}
B, S = 2, 19


@pytest.fixture(autouse=True)
def _loop_counters_as_found():
    """The models here fit with other recomputation policies than the
    benchmark's toy cell; a later test in the same process that reads the
    process's ``dl4j_loop_*`` ratios finds them as a fresh process would."""
    yield
    from deeplearning4j_tpu import obs

    for f in obs.registry().families():
        if f.name.startswith("dl4j_loop_"):
            f.clear()


def _cfg(**kw):
    return dict(CFG, **kw)


def _model(cfg, seed=7):
    words = ref.seed_words(seed)
    return fam.new_model(cfg, words), ref.make_weights(cfg, words, jnp.float32)


def _batch(cfg, seed=0):
    ids = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (B, S)).astype(np.int32)
    return ids, np.roll(ids, -1, 1)


def _program_loss(model, ids, labels, params=None):
    loss, (state, _) = model._loss(
        model.params if params is None else params, model.state,
        jnp.asarray(ids), jnp.asarray(labels), None, None, None)
    return loss, state


def _ref_loss(cfg, w, ids, labels, per_loop=None):
    return ref.loss_rows(cfg, w, jnp.asarray(ids), jnp.asarray(labels),
                         per_loop=per_loop) / ids.size


def test_each_loops_logits_and_gate_agree_with_the_reference():
    """The stack hands on every loop's normed state; each loop's head and
    gate over it are the reference's."""
    cfg = _cfg()
    model, w = _model(cfg)
    ids, labels = _batch(cfg)
    with jax.default_matmul_precision("highest"):
        h, *_ = model._forward(model.params, model.state, jnp.asarray(ids),
                               train=False, rngs=None, upto=2)
        states = ref.loop_states(cfg, w, jnp.asarray(ids))
    T = cfg["total_ut_steps"]
    assert h.shape == (T * B, S, cfg["hidden_size"])
    out = model.params[-1]
    for t, (got, want) in enumerate(zip(h.reshape(T, B, S, -1), states)):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5, err_msg=str(t))
        with jax.default_matmul_precision("highest"):
            z, z_ref = got @ out["W"], want @ w["w_head"]
            gate = jax.nn.sigmoid(got @ out["gate"]["W"] + out["gate"]["b"])
            gate_ref = jax.nn.sigmoid(want @ w["gate_w"] + w["gate_b"])
        np.testing.assert_allclose(z, z_ref, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(gate, gate_ref, rtol=1e-5, atol=1e-6)
    # inference reads the last loop's head: every loop runs
    probs = model.output(ids)
    np.testing.assert_allclose(
        probs, jax.nn.softmax(states[-1] @ w["w_head"], -1), rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("beta", [0.0, 0.1, 0.5])
def test_the_loss_with_its_entropy_term_agrees_with_the_reference(beta):
    cfg = _cfg(exit_entropy_beta=beta)
    model, w = _model(cfg)
    ids, labels = _batch(cfg)
    with jax.default_matmul_precision("highest"):
        loss, _ = _program_loss(model, ids, labels)
        want = _ref_loss(cfg, w, ids, labels)
        _, p, ce = ref.exits(cfg, w, ref.loop_states(cfg, w, jnp.asarray(ids)),
                             jnp.asarray(labels))
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    # the entropy term alone: the expected cross-entropy less beta H(p)
    entropy = -jnp.sum(p * jnp.log(p), 0)
    np.testing.assert_allclose(
        loss, jnp.mean(jnp.sum(p * ce, 0)) - beta * jnp.mean(entropy), rtol=1e-5)
    assert float(jnp.mean(entropy)) > 0.5


def test_a_label_mask_leaves_its_tokens_out_of_the_mean():
    cfg = _cfg()
    model, w = _model(cfg)
    ids, labels = _batch(cfg)
    mask = np.ones((B, S), np.float32)
    mask[1, 7:] = 0.0
    with jax.default_matmul_precision("highest"):
        loss, (state, _) = model._loss(
            model.params, model.state, jnp.asarray(ids), jnp.asarray(labels),
            None, jnp.asarray(mask), None)
        per_token, _, _ = ref.exits(cfg, w, ref.loop_states(cfg, w, jnp.asarray(ids)),
                                    jnp.asarray(labels))
    np.testing.assert_allclose(loss, jnp.sum(per_token * mask) / mask.sum(),
                               rtol=1e-5)
    assert np.asarray(state[-1]["stats"])[2 * cfg["total_ut_steps"]] == mask.sum()


@pytest.mark.parametrize("loops", [1, 2, 4])
def test_the_exit_distribution_sums_to_one(loops):
    """In the reference, and in the program's counters: the exit mass over
    the loops is one a token."""
    cfg = _cfg(total_ut_steps=loops)
    model, w = _model(cfg)
    ids, labels = _batch(cfg)
    _, p, _ = ref.exits(cfg, w, ref.loop_states(cfg, w, jnp.asarray(ids)),
                        jnp.asarray(labels))
    assert p.shape == (loops, B, S)
    np.testing.assert_allclose(jnp.sum(p, 0), 1.0, rtol=1e-6)
    _, state = _program_loss(model, ids, labels)
    stats = np.asarray(state[-1]["stats"])
    assert stats.shape == (2 * loops + 3,)
    np.testing.assert_allclose(stats[:loops].sum(), B * S, rtol=1e-5)
    assert stats[2 * loops] == B * S


def test_a_shared_leafs_gradient_is_the_sum_over_loops():
    """The program's gradient of every leaf the loops read equals, in the
    reference that gives each loop a separate copy of the weights, the sum
    of the copies' gradients; and the reference with one shared set gives
    the same."""
    cfg = _cfg(recompute_layers=True)
    model, w = _model(cfg)
    ids, labels = _batch(cfg)
    T = cfg["total_ut_steps"]
    looped = [k for k in w if k != "wte"]         # the embedding is loop 1's
    with jax.default_matmul_precision("highest"):
        grads = jax.grad(lambda p: _program_loss(model, ids, labels, p)[0])(
            model.params)
        copies = {k: jnp.stack([w[k]] * T) for k in looped}
        per_loop = jax.grad(lambda c: _ref_loss(cfg, w, ids, labels, c))(copies)
        shared = jax.grad(lambda w_: _ref_loss(cfg, w_, ids, labels))(w)
    got = fam.from_program(cfg, grads)
    assert set(got) == set(w)
    np.testing.assert_allclose(got["wte"], shared["wte"], rtol=2e-3, atol=2e-6)
    for k in looped:
        total = jnp.sum(per_loop[k], 0)
        np.testing.assert_allclose(shared[k], total, rtol=1e-4, atol=1e-7, err_msg=k)
        np.testing.assert_allclose(got[k], total, rtol=2e-3, atol=2e-6, err_msg=k)
    # every loop's copy of a layer's matrix gets a gradient of its own; the
    # last loop's gate is not read
    for t in range(T):
        assert float(jnp.linalg.norm(per_loop["a_q.0"][t])) > 0, t
        assert float(jnp.linalg.norm(per_loop["gate_w"][t])) > 0 or t == T - 1
    assert not float(jnp.abs(per_loop["gate_w"][T - 1]).max())


def test_one_updater_state_a_parameter_and_training_lowers_the_loss():
    """The loops read one parameter set: the optimizer's state has the
    params' tree (nothing a loop), and fit() trains it."""
    cfg = _cfg(recompute_layers=True)
    model, _ = _model(cfg)
    assert len(model.params) == len(model.opt_state) == 3
    for p, o in zip(model.params, model.opt_state):
        assert jax.tree_util.tree_structure(o["m"]) == \
            jax.tree_util.tree_structure(p)
    assert model.num_params() == ref.num_params(cfg)
    ids, labels = _batch(cfg)
    first, _ = _program_loss(model, ids, labels)
    model.fit([(ids, labels)] * 6)
    last, _ = _program_loss(model, ids, labels)
    assert float(last) < float(first)


def test_the_builder_round_trips_through_json():
    from deeplearning4j_tpu.models import LoopedLM

    conf = fam.build_conf(_cfg(recompute_layers=True))
    again = MultiLayerConfiguration.from_json(conf.to_json())
    assert again == conf
    assert isinstance(conf.layers[1], LoopedStack)
    assert len(conf.layers[1].blocks) == 2 * CFG["num_hidden_layers"]
    assert all(b.post_norm and b.remat for b in conf.layers[1].blocks)
    assert LoopedLM(vocab_size=11, d_model=8, n_layers=1, loops=2,
                    attention=dict(n_heads=2, n_kv_heads=2, head_dim=4),
                    dense_width=12).layers[2].loops == 2


# ---------------------------------------------------------------------------
# The sandwich norm, off and on
# ---------------------------------------------------------------------------

IT = InputType.recurrent(32, S)


@pytest.mark.parametrize("mixer", [
    GatedMLP(width=40),
    GroupedQueryAttention(n_heads=4, n_kv_heads=2, head_dim=8),
])
def test_post_norm_off_is_the_block_as_it_was(mixer):
    """``post_norm=False`` keeps the parameter tree and the traced program of
    ``x + mixer(RMSNorm(x))`` to the bit; on, it norms the mixer's output
    with a gain of its own."""
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, 32), jnp.float32)
    off = ResidualBlock(mixer=mixer, eps=1e-5)
    p = off.init(jax.random.PRNGKey(2), IT)
    assert set(p) == {"norm", "mixer"}

    def before(p, x):           # ResidualBlock.apply as it was before the field
        y, _ = mixer.apply(p["mixer"], {}, rms_norm(x, p["norm"]["gamma"], 1e-5))
        return x + y

    def now(p, x):
        return off.apply(p, {}, x)[0]

    assert str(jax.make_jaxpr(now)(p, x)) == str(jax.make_jaxpr(before)(p, x))
    assert bool((now(p, x) == before(p, x)).all())
    on = ResidualBlock(mixer=mixer, eps=1e-5, post_norm=True)
    q = on.init(jax.random.PRNGKey(2), IT)
    assert set(q) == {"norm", "mixer", "post_norm"}
    q["post_norm"]["gamma"] = q["post_norm"]["gamma"] * 1.5
    y, _ = mixer.apply(q["mixer"], {}, rms_norm(x, q["norm"]["gamma"], 1e-5))
    np.testing.assert_allclose(
        on.apply(q, {}, x)[0], x + rms_norm(y, q["post_norm"]["gamma"], 1e-5),
        rtol=1e-6)


# ---------------------------------------------------------------------------
# The counters
# ---------------------------------------------------------------------------


class _Scores:
    def on_epoch_start(self, model, epoch):
        pass

    on_epoch_end = on_gradient_calculation = on_epoch_start

    def iteration_done(self, model, iteration, score, batch_size=0):
        pass


@pytest.mark.parametrize("loops,layers,remat", [
    (1, 1, False), (3, 2, False), (3, 2, True), (4, 6, True)])
def test_the_counters_give_the_steps_recomputation_policy(loops, layers, remat):
    """A block is one ``ResidualBlock`` (two a layer): ``loops x blocks``
    applications a step, twice as many forward passes where every block is
    recomputed; ``loops`` exits, twice as many head passes where the logits
    are recomputed. The values reach ``obs`` through ``fit()``."""
    from deeplearning4j_tpu import obs

    cfg = _cfg(total_ut_steps=loops, num_hidden_layers=layers,
               layer_types=["full_attention"] * layers, recompute_layers=remat)
    model, _ = _model(cfg)
    stack, out = model.layers[1], model.layers[2]
    blocks = 2 * layers
    passes = 2 if remat else 1
    assert stack.counts() == (loops * blocks, passes * loops * blocks)
    totals = lambda: {f.name: sum(f.as_dict().values())           # noqa: E731
                      for f in obs.registry().families()
                      if f.name.startswith("dl4j_loop_")}
    before = totals()
    ids, labels = _batch(cfg)
    model.set_listeners(_Scores())      # fit() fetches counters for a listener
    model.fit([(ids, labels)] * 2)
    got = {k: v - before.get(k, 0.0) for k, v in totals().items()}
    assert got["dl4j_loop_steps_total"] == 2
    assert got["dl4j_loop_block_applications_total"] == 2 * loops * blocks
    assert got["dl4j_loop_block_passes_total"] == 2 * passes * loops * blocks
    assert got["dl4j_loop_exits_total"] == 2 * loops
    assert got["dl4j_loop_head_passes_total"] == 2 * passes * loops
    assert got["dl4j_loop_tokens_total"] == 2 * B * S
    assert got["dl4j_loop_exit_mass_total"] == pytest.approx(2 * B * S, rel=1e-5)
    # each loop's cross-entropy is near log(vocabulary) a token at the start
    assert got["dl4j_loop_ce_total"] / (2 * B * S * loops) == pytest.approx(
        np.log(CFG["vocab_size"]), rel=0.1)
