"""One step in flight: ``fit()`` reports step N to its listeners after it has
enqueued step N + 1 (``nn/step_program.py`` ``StepReports``), for
``MultiLayerNetwork`` and ``ComputationGraph`` alike, and stays synchronous
where something attached needs the model at its own step.

CPU only: the order of the calls, what each listener is handed, what the
counters say, and (under ``DL4J_TPU_DONATION_GUARD=1``, which deletes donated
inputs as the chip does) that a reported step's ``"stats"`` arrays are alive
when the host fetches them.
"""

import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from deeplearning4j_tpu import obs  # noqa: E402
from deeplearning4j_tpu.nn import step_program  # noqa: E402
from deeplearning4j_tpu.nn.graph import (  # noqa: E402
    ComputationGraph, ComputationGraphConfiguration)
from deeplearning4j_tpu.nn.input_type import InputType  # noqa: E402
from deeplearning4j_tpu.nn.layers import Dense, OutputLayer  # noqa: E402
from deeplearning4j_tpu.nn.model import (  # noqa: E402
    MultiLayerConfiguration, MultiLayerNetwork)
from deeplearning4j_tpu.train.listeners import (  # noqa: E402
    CollectScoresListener, ComposedListener, PerformanceListener,
    ProfilerListener, ScoreIterationListener, TimeIterationListener,
    TrainingListener)

K = 5               # batches a fit() call


@pytest.fixture(autouse=True)
def _per_step_dispatch(monkeypatch):
    # the toy models are small enough for the chained path, which has no
    # listener and nothing to report
    monkeypatch.setenv("DL4J_TPU_CHAIN_STEPS", "0")
    monkeypatch.delenv("DL4J_TPU_DONATION_GUARD", raising=False)


def _data(n=8 * K, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, 4).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rs.randint(0, 2, n)]
    return x, y


def _mln_conf(**kw):
    return MultiLayerConfiguration(
        layers=(Dense(n_out=8, activation="tanh"),
                OutputLayer(n_out=2, activation="softmax")),
        input_type=InputType.feed_forward(4),
        updater={"type": "sgd", "lr": 0.05}, **kw)


def _mln():
    return MultiLayerNetwork(_mln_conf()).init(seed=1)


def _cg():
    conf = (ComputationGraphConfiguration.builder()
            .add_inputs("in")
            .set_input_types(InputType.feed_forward(4))
            .add_layer("h", Dense(n_out=8, activation="tanh"), "in")
            .add_layer("out", OutputLayer(n_out=2, activation="softmax"), "h")
            .set_outputs("out")
            .updater({"type": "sgd", "lr": 0.05}).build())
    return ComputationGraph(conf).init(seed=1)


MODELS = {"mln": _mln, "cg": _cg}
both = pytest.mark.parametrize("site", ["mln", "cg"])


class Scores:
    """A listener that reads score and clock only: a plain object, as the
    benchmark's is, so it declares nothing."""

    def __init__(self, log=None):
        self.calls = []
        self.log = log if log is not None else []
        self.closed = 0

    def on_epoch_start(self, model, epoch):
        self.log.append(("epoch_start", epoch))

    def on_epoch_end(self, model, epoch):
        self.log.append(("epoch_end", epoch))

    def on_gradient_calculation(self, model, iteration):
        pass

    def iteration_done(self, model, iteration, score, batch_size=0):
        assert isinstance(score, float)
        self.calls.append((iteration, score, batch_size))
        self.log.append(("report", iteration))

    def close(self):
        self.closed += 1


class ReadsModel(Scores):
    reads_model = True


def _fetched(site):
    return (step_program._FETCHED.value(site=site),
            step_program._OVERLAPPED.value(site=site))


def _record_dispatches(monkeypatch, log, site):
    """Put ("dispatch", the model's iteration before it) into ``log`` for
    every call of the site's train step."""
    real = step_program.StepProgram.dispatch

    def dispatch(self, *args, **kw):
        if self.site == f"{site}.step":
            log.append(("dispatch", int(args[3])))
        return real(self, *args, **kw)

    monkeypatch.setattr(step_program.StepProgram, "dispatch", dispatch)


# -- (a) what a listener is handed ------------------------------------------


@both
def test_a_listener_gets_every_steps_own_numbers_in_order(site):
    """The same (iteration, score, batch_size), bit for bit, whether the
    report waits for the next dispatch or not; a ragged last batch keeps its
    own row count."""
    x, y = _data(8 * K - 3)
    seen = []
    for kind in (Scores, ReadsModel):
        model = MODELS[site]()
        listener = kind()
        model.set_listeners(listener)
        model.fit((x, y), epochs=2, batch_size=8)
        seen.append(listener.calls)
    assert seen[0] == seen[1]
    assert [c[0] for c in seen[0]] == list(range(1, 2 * K + 1))
    assert [c[2] for c in seen[0]] == [8, 8, 8, 8, 5] * 2


@both
def test_the_model_may_be_a_step_ahead_only_for_a_listener_that_does_not_read_it(site):
    class Ahead(Scores):
        def iteration_done(self, model, iteration, score, batch_size=0):
            self.calls.append(model.iteration - iteration)

    class AheadReads(Ahead):
        reads_model = True

    x, y = _data()
    out = {}
    for kind in (Ahead, AheadReads):
        model = MODELS[site]()
        listener = kind()
        model.set_listeners(listener)
        model.fit((x, y), batch_size=8)
        out[kind] = listener.calls
    assert out[Ahead] == [1] * (K - 1) + [0]
    assert out[AheadReads] == [0] * K


# -- (b) the moment of a report ---------------------------------------------


@both
def test_step_n_is_reported_after_step_n_plus_1_is_dispatched(site, monkeypatch):
    log = []
    _record_dispatches(monkeypatch, log, site)
    model = MODELS[site]()
    model.set_listeners(Scores(log))
    x, y = _data()
    model.fit((x, y), batch_size=8)
    want = [("epoch_start", 0), ("dispatch", 0)]
    for n in range(1, K):
        want += [("dispatch", n), ("report", n)]
    want += [("report", K), ("epoch_end", 0)]
    assert log == want


@both
def test_a_listener_that_reads_the_model_is_reported_to_before_the_next_dispatch(
        site, monkeypatch):
    log = []
    _record_dispatches(monkeypatch, log, site)
    model = MODELS[site]()
    model.set_listeners(ReadsModel(log))
    x, y = _data()
    model.fit((x, y), batch_size=8)
    want = [("epoch_start", 0)]
    for n in range(K):
        want += [("dispatch", n), ("report", n + 1)]
    assert log == want + [("epoch_end", 0)]


@both
def test_a_one_batch_fit_reports_its_step(site, monkeypatch):
    log = []
    _record_dispatches(monkeypatch, log, site)
    model = MODELS[site]()
    listener = Scores(log)
    model.set_listeners(listener)
    x, y = _data(8)
    for call in range(3):
        model.fit((x, y))
    assert [c[0] for c in listener.calls] == [1, 2, 3]
    assert log == [e for n in range(3) for e in (
        ("epoch_start", n), ("dispatch", n), ("report", n + 1),
        ("epoch_end", n))]


@both
def test_the_reports_spans_carry_the_reported_steps_number(site):
    model = MODELS[site]()
    model.set_listeners(Scores())
    x, y = _data()
    obs.reset()
    model.fit((x, y), batch_size=8)
    recs = obs.recent_spans()
    for name in (f"{site}.loss_fetch", f"{site}.listeners"):
        mine = [r for r in recs if r["span"] == name]
        assert [r["attrs"]["step"] for r in mine] == list(range(K))
        # inside the turn that dispatched the next step, or that found the
        # stream at its end
        assert all(r["parent"] == f"{site}.iter" for r in mine)
    by_name = {}
    for r in recs:
        by_name.setdefault(r["span"], []).append(r)
    for n in range(K - 1):
        fetch = by_name[f"{site}.loss_fetch"][n]
        nxt = by_name[f"{site}.step"][n + 1]
        assert nxt["t0_s"] + nxt["wall_s"] <= fetch["t0_s"]


# -- (c) the layers' counters under donation --------------------------------


def _moe_model():
    from deeplearning4j_tpu.models import HybridLM

    conf = HybridLM(
        pattern="MEM*E", vocab_size=50, d_model=32, max_len=24,
        mamba=dict(n_heads=4, head_dim=8, n_groups=2, state_size=8, chunk=8),
        attention=dict(n_heads=4, n_kv_heads=2, head_dim=8),
        moe=dict(n_experts=16, top_k=3, expert_width=12, shared_width=20,
                 held_start=8, n_held=4, routed_scaling=2.5))
    return MultiLayerNetwork(conf).init(seed=3), 50, 24


def _mtp_model():
    from benchmark.families import deepseek_mla as fam
    from benchmark.reference import deepseek_mla as ref

    cfg = {
        "num_hidden_layers": 2, "first_k_dense_replace": 1, "hidden_size": 32,
        "vocab_size": 50, "max_position_embeddings": 64,
        "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 12,
        "rope_theta": 10000.0, "rope_interleave": True,
        "intermediate_size": 40, "moe_intermediate_size": 12,
        "n_shared_experts": 1, "n_routed_experts": 4, "router_experts": 16,
        "held_experts_start": 8, "num_experts_per_tok": 3,
        "routed_scaling_factor": 2.5, "norm_topk_prob": True,
        "num_nextn_predict_layers": 1, "mtp_loss_weight": 0.3,
        "rms_norm_eps": 1e-6, "dtype": "float32", "recompute_layers": True,
        "updater": {"type": "adam", "lr": 3e-4},
    }
    return fam.new_model(cfg, ref.seed_words(11)), 50, 21


@pytest.mark.parametrize("build", [_moe_model, _mtp_model],
                         ids=["sparse-moe", "mtp-output"])
def test_each_steps_own_counters_are_published_once_under_donation(
        build, monkeypatch):
    """The step donates the state, the ``"stats"`` leaves with it. With the
    guard on, a donated input is deleted on the CPU too, so a report that
    read step N's counters out of what step N + 1 was handed would raise
    ``Array has been deleted``."""
    from deeplearning4j_tpu.nn.layers.moe import SparseMoE
    from deeplearning4j_tpu.nn.layers.mtp import MTPOutputLayer

    monkeypatch.setenv("DL4J_TPU_DONATION_GUARD", "1")
    published = []
    for cls in (SparseMoE, MTPOutputLayer):
        real = cls.publish_stats

        def publish(self, index, stats, real=real, cls=cls):
            published.append((cls.__name__, index, np.array(stats)))
            return real(self, index, stats)

        monkeypatch.setattr(cls, "publish_stats", publish)

    runs = {}
    for kind in (Scores, ReadsModel):
        model, vocab, t = build()
        listener = kind()
        model.set_listeners(listener)
        rs = np.random.RandomState(5)
        batches = []
        for _ in range(K):
            ids = rs.randint(0, vocab, (2, t)).astype(np.int32)
            batches.append((ids, np.roll(ids, -1, 1)))
        del published[:]
        before = _fetched("mln")
        model.fit(batches)
        after = _fetched("mln")
        final = [np.asarray(s["stats"]) for s in model.state
                 if isinstance(s, dict) and "stats" in s]
        runs[kind] = (listener.calls, list(published), final)
        assert after[0] - before[0] == K
        assert after[1] - before[1] == (K - 1 if kind is Scores else 0)
    (calls, pub, final), (calls_sync, pub_sync, final_sync) = \
        runs[Scores], runs[ReadsModel]
    assert calls == calls_sync and len(calls) == K
    assert len(pub) == len(pub_sync) and len(pub) % K == 0 and pub
    for (name, index, stats), (name_s, index_s, stats_s) in zip(pub, pub_sync):
        assert (name, index) == (name_s, index_s)
        np.testing.assert_array_equal(stats, stats_s)
    # the steps differ (the batches do), so a counter published twice, or a
    # step's taken for another's, would not pass for the synchronous run's
    per_step = len(pub) // K
    assert any(not np.array_equal(pub[i][2], pub[i + per_step][2])
               for i in range(per_step))
    # and the state ends on the last step's own counters
    for a, b in zip(final, final_sync):
        np.testing.assert_array_equal(a, b)


def test_a_graphs_expert_layer_keeps_its_counters_too(monkeypatch):
    """``ComputationGraph`` keeps its state by vertex name: the same hold."""
    from deeplearning4j_tpu.nn.layers import RnnOutputLayer
    from deeplearning4j_tpu.nn.layers.moe import SparseMoE

    monkeypatch.setenv("DL4J_TPU_DONATION_GUARD", "1")
    published = []
    real = SparseMoE.publish_stats
    monkeypatch.setattr(
        SparseMoE, "publish_stats",
        lambda self, index, stats: (published.append(
            (index, np.array(stats))), real(self, index, stats))[1])

    def graph():
        conf = (ComputationGraphConfiguration.builder()
                .add_inputs("in")
                .set_input_types(InputType.recurrent(16, 6))
                .add_layer("moe", SparseMoE(n_experts=8, top_k=2,
                                            expert_width=12), "in")
                .add_layer("out", RnnOutputLayer(n_out=3,
                                                 activation="softmax"), "moe")
                .set_outputs("out")
                .updater({"type": "sgd", "lr": 0.05}).build())
        return ComputationGraph(conf).init(seed=2)

    rs = np.random.RandomState(0)
    batches = [(rs.randn(4, 6, 16).astype(np.float32),
                np.eye(3, dtype=np.float32)[rs.randint(0, 3, (4, 6))])
               for _ in range(K)]
    runs = []
    for kind in (Scores, ReadsModel):
        g = graph()
        listener = kind()
        g.set_listeners(listener)
        del published[:]
        g.fit(batches)
        runs.append((listener.calls, list(published)))
    assert runs[0][0] == runs[1][0]
    assert [i for i, _ in runs[0][1]] == ["moe"] * K
    for (_, a), (_, b) in zip(*[r[1] for r in runs]):
        np.testing.assert_array_equal(a, b)


# -- (d) when the loop stays synchronous, and the counter that says so ------


@both
def test_score_only_listeners_overlap_every_step_but_the_last_of_a_call(site):
    model = MODELS[site]()
    model.set_listeners(
        ScoreIterationListener(out=lambda s: None), CollectScoresListener(),
        PerformanceListener(out=lambda s: None),
        TimeIterationListener(100, out=lambda s: None),
        ProfilerListener("/nonexistent", start=10 ** 9, stop=10 ** 9 + 1),
        ComposedListener([Scores()]))
    x, y = _data()
    before = _fetched(site)
    model.fit((x, y), batch_size=8)
    model.fit((x, y), epochs=2, batch_size=8)
    fetched, overlapped = np.subtract(_fetched(site), before)
    # three streams, each with its last step reported at its end
    assert (fetched, overlapped) == (3 * K, 3 * (K - 1))


def _checkpoints(tmp_path):
    from deeplearning4j_tpu.train.checkpoint import CheckpointListener

    return [Scores(), CheckpointListener(str(tmp_path),
                                         save_every_n_iterations=2)]


def _composed_reader(tmp_path):
    return [ComposedListener([Scores(), ReadsModel()])]


def _early_stopping_guard(tmp_path):
    # what EarlyStoppingTrainer attaches is made inside its fit(); its kind
    # is a plain object that declares the attribute
    class Guard(Scores):
        reads_model = True

    return [Guard()]


@both
@pytest.mark.parametrize("listeners", [_checkpoints, _composed_reader,
                                       _early_stopping_guard])
def test_a_listener_that_reads_the_model_keeps_the_loop_synchronous(
        site, listeners, tmp_path):
    model = MODELS[site]()
    model.set_listeners(*listeners(tmp_path))
    x, y = _data()
    before = _fetched(site)
    model.fit((x, y), batch_size=8)
    assert tuple(np.subtract(_fetched(site), before)) == (K, 0)


@both
def test_a_divergence_guard_keeps_the_loop_synchronous(site):
    from deeplearning4j_tpu.train.resilience import DivergenceGuard

    model = MODELS[site]()
    listener = Scores()
    model.set_listeners(listener)
    model.set_divergence_guard(DivergenceGuard(policy="skip_batch"))
    x, y = _data()
    before = _fetched(site)
    model.fit((x, y), batch_size=8)
    assert tuple(np.subtract(_fetched(site), before)) == (K, 0)
    assert len(listener.calls) == K


def test_the_solvers_score_is_a_host_float_already():
    model = MultiLayerNetwork(_mln_conf(
        optimization_algo="lbfgs", solver_iterations=2)).init(seed=1)
    listener = Scores()
    model.set_listeners(listener)
    x, y = _data(16)
    before = _fetched("mln")
    model.fit((x, y), batch_size=8)
    assert tuple(np.subtract(_fetched("mln"), before)) == (2, 0)
    assert [c[0] for c in listener.calls] == [1, 2]


def test_which_listeners_declare_that_they_read_the_model(tmp_path):
    from deeplearning4j_tpu.train.checkpoint import CheckpointListener
    from deeplearning4j_tpu.train.listeners import EvaluativeListener
    from deeplearning4j_tpu.ui.convolutional import (
        ConvolutionalIterationListener)
    from deeplearning4j_tpu.ui.stats import StatsListener

    assert TrainingListener.reads_model is False
    for cls in (ScoreIterationListener, PerformanceListener, ProfilerListener,
                CollectScoresListener, TimeIterationListener):
        assert cls.reads_model is False, cls
    for cls in (CheckpointListener, EvaluativeListener, StatsListener,
                ConvolutionalIterationListener):
        assert cls.reads_model is True, cls
    assert ComposedListener([Scores()]).reads_model is False
    assert ComposedListener([Scores(), ComposedListener(
        [ReadsModel()])]).reads_model is True


def test_early_stoppings_iteration_conditions_stop_the_run_at_their_own_step():
    """The trainer's inner listener raises from ``iteration_done`` to end an
    epoch at the step that met a condition: no step is dispatched past it."""
    from deeplearning4j_tpu.train.earlystopping import (
        DataSetLossCalculator, EarlyStoppingConfiguration,
        EarlyStoppingTrainer, MaxEpochsTerminationCondition,
        MaxScoreIterationTerminationCondition)

    x, y = _data()
    model = _mln()
    cfg = EarlyStoppingConfiguration(
        epoch_termination_conditions=[MaxEpochsTerminationCondition(3)],
        iteration_termination_conditions=[
            MaxScoreIterationTerminationCondition(-1.0)],   # met at once
        score_calculator=DataSetLossCalculator((x, y)))
    before = _fetched("mln")
    result = EarlyStoppingTrainer(cfg, model, (x, y), batch_size=8).fit()
    assert result.termination_reason == "IterationTerminationCondition"
    assert model.iteration == 1
    assert tuple(np.subtract(_fetched("mln"), before)) == (1, 0)


def test_no_setting_decides_it():
    """What is attached decides, nothing else: no variable of the
    environment, no argument of ``fit()``."""
    import inspect

    src = inspect.getsource(step_program.StepReports)
    assert "environ" not in src
    for cls in (MultiLayerNetwork, ComputationGraph):
        assert list(inspect.signature(cls.fit).parameters) == [
            "self", "data", "epochs", "batch_size", "resume_from"]


# -- (e) exceptions ----------------------------------------------------------


class Boom(RuntimeError):
    pass


@both
def test_a_feed_that_raises_leaves_no_step_unreported(site, monkeypatch):
    log = []
    _record_dispatches(monkeypatch, log, site)
    x, y = _data()

    def feed():
        for i in range(3):
            yield x[8 * i:8 * i + 8], y[8 * i:8 * i + 8]
        raise Boom("the feed broke")

    model = MODELS[site]()
    listener = Scores(log)
    model.set_listeners(listener)
    before = _fetched(site)
    with pytest.raises(Boom):
        model.fit(feed)
    assert [c[0] for c in listener.calls] == [1, 2, 3]
    assert log[-3:] == [("dispatch", 2), ("report", 2), ("report", 3)]
    assert ("epoch_end", 0) not in log
    assert listener.closed == 1
    assert tuple(np.subtract(_fetched(site), before)) == (3, 2)


def test_a_dispatch_that_raises_reports_the_step_before_and_keeps_its_counters(
        monkeypatch):
    """The chaos harness preempts inside ``_fit_batch``, after the pending
    step's counters were taken out of the state: they are reported, and put
    back."""
    from deeplearning4j_tpu.nn.layers.moe import SparseMoE
    from deeplearning4j_tpu.train import resilience

    monkeypatch.setenv("DL4J_TPU_DONATION_GUARD", "1")
    published = []
    real = SparseMoE.publish_stats
    monkeypatch.setattr(
        SparseMoE, "publish_stats",
        lambda self, index, stats: (published.append(
            (index, np.array(stats))), real(self, index, stats))[1])
    model, vocab, t = _moe_model()
    listener = Scores()
    model.set_listeners(listener)
    ids = np.random.RandomState(1).randint(0, vocab, (2, t)).astype(np.int32)
    resilience.install_chaos("preempt@iter:3")
    try:
        with pytest.raises(resilience.ChaosPreemption):
            model.fit([(ids, np.roll(ids, -1, 1))] * K)
    finally:
        resilience.install_chaos(None)
    assert [c[0] for c in listener.calls] == [1, 2, 3]
    assert listener.closed == 1
    last = {i: s for i, s in published[-2:]}
    for i, s in enumerate(model.state):
        if isinstance(s, dict) and "stats" in s:
            np.testing.assert_array_equal(np.asarray(s["stats"]), last[i])


@both
def test_a_report_that_fails_does_not_mask_the_loops_own_exception(site):
    class Sour(Scores):
        def iteration_done(self, model, iteration, score, batch_size=0):
            super().iteration_done(model, iteration, score, batch_size)
            if iteration == 2:
                raise ValueError("the listener broke")

    x, y = _data()

    def feed():
        yield x[:8], y[:8]
        yield x[8:16], y[8:16]
        raise Boom("the feed broke")

    model = MODELS[site]()
    listener = Sour()
    model.set_listeners(listener)
    with pytest.raises(Boom):
        model.fit(feed)
    assert [c[0] for c in listener.calls] == [1, 2]
    assert listener.closed == 1


@both
def test_a_listener_that_raises_is_not_called_again(site):
    class Stop(Exception):
        pass

    class Stopper(Scores):
        def iteration_done(self, model, iteration, score, batch_size=0):
            super().iteration_done(model, iteration, score, batch_size)
            if iteration == 2:
                raise Stop()

    model = MODELS[site]()
    listener = Stopper()
    model.set_listeners(listener)
    x, y = _data()
    with pytest.raises(Stop):
        model.fit((x, y), batch_size=8)
    assert [c[0] for c in listener.calls] == [1, 2]
    assert listener.closed == 1


# -- what else runs the loop -------------------------------------------------


def test_truncated_bptt_reports_each_batch_with_the_iteration_after_its_chunks():
    from deeplearning4j_tpu.nn.layers import LSTM, RnnOutputLayer

    def build():
        conf = MultiLayerConfiguration(
            layers=(LSTM(n_out=6),
                    RnnOutputLayer(n_out=2, activation="softmax")),
            input_type=InputType.recurrent(3, 12),
            updater={"type": "sgd", "lr": 0.05},
            backprop_type="tbptt", tbptt_fwd_length=4)
        return MultiLayerNetwork(conf).init(seed=4)

    rs = np.random.RandomState(0)
    batches = [(rs.randn(2, 12, 3).astype(np.float32),
                np.eye(2, dtype=np.float32)[rs.randint(0, 2, (2, 12))])
               for _ in range(3)]
    seen = []
    for kind in (Scores, ReadsModel):
        model = build()
        listener = kind()
        model.set_listeners(listener)
        model.fit(batches)
        seen.append(listener.calls)
    assert seen[0] == seen[1]
    assert [c[0] for c in seen[0]] == [3, 6, 9]


def test_without_a_listener_nothing_is_fetched():
    model = _mln()
    x, y = _data()
    before = _fetched("mln")
    model.fit((x, y), batch_size=8)
    assert _fetched("mln") == before
    assert model.iteration == K


def test_a_placeholder_is_placed_as_the_array_it_stands_for():
    """Committed or not, as the step's own outputs are: a jitted call keys
    its cache on it, and a miss would trace the step again."""
    a = jax.numpy.zeros((3,), jax.numpy.float32)
    b = jax.device_put(np.ones((2, 2), np.float32), jax.devices()[0])
    blanks = step_program._blank_like([a, b])
    for src, blank in zip((a, b), blanks):
        assert (blank.shape, blank.dtype, blank.committed) == (
            src.shape, src.dtype, src.committed)
        assert blank.sharding == src.sharding
        assert not np.asarray(blank).any()
