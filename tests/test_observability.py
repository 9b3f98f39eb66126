"""Unified observability layer (ISSUE 5): registry semantics incl. thread
safety, span nesting, JSONL event schema + rotation, Prometheus exposition
via /metrics, obs.snapshot() round-trip through the resilience checkpoint
telemetry field, and the listener satellites (PerformanceListener sample
accounting, listener close() on fit exit)."""

import json
import os
import re
import threading
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
from deeplearning4j_tpu.nn.model import (
    MultiLayerConfiguration,
    MultiLayerNetwork,
)
from deeplearning4j_tpu.obs.events import EventLog
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.obs.spans import SpanTracer
from deeplearning4j_tpu.train import listeners as listeners_mod
from deeplearning4j_tpu.train.listeners import (
    ComposedListener,
    PerformanceListener,
    TrainingListener,
)
from deeplearning4j_tpu.utils import bucketing


@pytest.fixture(autouse=True)
def _obs_isolation(monkeypatch):
    monkeypatch.delenv("DL4J_TPU_OBS", raising=False)
    monkeypatch.delenv("DL4J_TPU_EVENT_LOG", raising=False)
    obs.reset()
    bucketing.telemetry().reset()
    yield
    obs.configure_event_log(None)
    obs.reset()
    bucketing.telemetry().reset()


def _mlp_conf():
    return MultiLayerConfiguration(
        layers=(Dense(n_out=8, activation="tanh"),
                OutputLayer(n_out=2, activation="softmax")),
        input_type=InputType.feed_forward(4),
        updater={"type": "sgd", "lr": 0.05},
        seed=3,
    )


def _toy_data(n=32):
    rs = np.random.RandomState(0)
    x = rs.rand(n, 4).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rs.randint(0, 2, n)]
    return x, y


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_counter_get_or_create_and_first_touch(self):
        reg = MetricsRegistry()
        c = reg.counter("t_total", "help", ("site",))
        assert reg.counter("t_total", "other", ("site",)) is c
        assert c.inc(site="a") == 1      # first touch is detectable
        assert c.inc(2, site="a") == 3
        assert c.value(site="a") == 3
        assert c.value(site="b") == 0

    def test_kind_and_label_mismatch_raise(self):
        reg = MetricsRegistry()
        reg.counter("m", "", ("a",))
        with pytest.raises(ValueError):
            reg.gauge("m", "", ("a",))
        with pytest.raises(ValueError):
            reg.counter("m", "", ("b",))
        with pytest.raises(ValueError):
            reg.counter("m", "", ("a",)).inc(wrong="x")

    def test_histogram_summary(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", "", ("op",))
        for v in range(100):
            h.observe(float(v), op="save")
        s = h.summary(op="save")
        assert s["count"] == 100
        assert s["sum"] == pytest.approx(4950.0)
        assert s["min"] == 0.0 and s["max"] == 99.0
        assert s["p50"] == pytest.approx(50.0, abs=2)
        assert h.summary(op="missing") is None

    def test_reset_keeps_registrations(self):
        reg = MetricsRegistry()
        c = reg.counter("kept", "", ("k",))
        c.inc(k="x")
        reg.reset()
        assert c.value(k="x") == 0
        # the same family object is still wired into the registry
        assert reg.counter("kept", "", ("k",)) is c

    def test_snapshot_shape(self):
        reg = MetricsRegistry()
        reg.counter("c", "", ("site",)).inc(site="s1")
        reg.gauge("g").set(2.5)
        reg.histogram("h", "", ("op",)).observe(1.0, op="x")
        snap = reg.snapshot()
        assert snap["c"] == {"site=s1": 1}
        assert snap["g"] == {"": 2.5}
        assert snap["h"]["op=x"]["count"] == 1
        json.dumps(snap)  # JSON-friendly end to end

    def test_thread_safety_exact_totals(self):
        reg = MetricsRegistry()
        c = reg.counter("conc_total", "", ("site",))
        h = reg.histogram("conc_lat")
        n_threads, per_thread = 8, 500

        def work():
            for _ in range(per_thread):
                c.inc(site="s")
                h.observe(1.0)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value(site="s") == n_threads * per_thread
        assert h.summary()["count"] == n_threads * per_thread


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


class TestSpans:
    def test_nesting_parent_and_depth(self):
        tr = SpanTracer(MetricsRegistry())
        with tr.span("outer"):
            with tr.span("inner"):
                pass
        inner, outer = tr.recent()[-2:]
        assert inner["span"] == "inner"
        assert inner["parent"] == "outer" and inner["depth"] == 1
        assert outer["span"] == "outer"
        assert outer["parent"] is None and outer["depth"] == 0
        assert inner["wall_s"] >= 0 and inner["cpu_s"] >= 0

    def test_error_flag_and_summary(self):
        tr = SpanTracer(MetricsRegistry())
        with pytest.raises(RuntimeError):
            with tr.span("boom"):
                raise RuntimeError("x")
        assert tr.recent()[-1]["error"] is True
        s = tr.summary()["boom"]
        assert s["count"] == 1 and s["wall_sum_s"] >= 0

    def test_disabled_records_nothing(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_OBS", "0")
        tr = SpanTracer(MetricsRegistry())
        with tr.span("off"):
            pass
        assert tr.recent() == []
        assert tr.summary() == {}

    def test_fit_records_model_spans(self):
        x, y = _toy_data()
        model = MultiLayerNetwork(_mlp_conf()).init()
        model.fit((x, y), epochs=2)
        names = {r["span"] for r in obs.recent_spans()}
        assert "mln.fit_batch" in names
        model.output(x)
        names = {r["span"] for r in obs.recent_spans()}
        assert "mln.output" in names


# ---------------------------------------------------------------------------
# event log
# ---------------------------------------------------------------------------


class TestEventLog:
    def test_jsonl_schema(self, tmp_path):
        log = EventLog(MetricsRegistry())
        p = tmp_path / "events.jsonl"
        log.configure(str(p))
        log.emit("checkpoint_saved", path="/x.zip", crc=7, size=100)
        log.emit("divergence", policy="warn", trips=1)
        lines = [json.loads(l) for l in p.read_text().splitlines()]
        assert [l["kind"] for l in lines] == ["checkpoint_saved", "divergence"]
        for l in lines:
            assert isinstance(l["ts"], float)
        assert lines[0]["crc"] == 7
        assert log.counts() == {"checkpoint_saved": 1, "divergence": 1}

    def test_rotation_bounds_disk(self, tmp_path):
        log = EventLog(MetricsRegistry())
        p = tmp_path / "events.jsonl"
        log.configure(str(p), max_bytes=2048)
        for i in range(200):
            log.emit("tick", i=i, pad="x" * 64)
        assert p.exists() and os.path.exists(str(p) + ".1")
        assert os.path.getsize(p) <= 2048
        # both generations still parse line-by-line
        for f in (str(p), str(p) + ".1"):
            for line in open(f):
                json.loads(line)

    def test_never_crashes_on_unserializable(self, tmp_path):
        log = EventLog(MetricsRegistry())
        p = tmp_path / "events.jsonl"
        log.configure(str(p))
        log.emit("weird", obj=object())       # default=str handles it
        log.emit("ok")
        recs = [json.loads(l) for l in p.read_text().splitlines()]
        assert [r["kind"] for r in recs] == ["weird", "ok"]

    def test_env_knob_adopted_lazily(self, tmp_path, monkeypatch):
        p = tmp_path / "env_events.jsonl"
        monkeypatch.setenv("DL4J_TPU_EVENT_LOG", str(p))
        log = EventLog(MetricsRegistry())
        log.emit("via_env")
        assert json.loads(p.read_text())["kind"] == "via_env"

    def test_obs_event_respects_kill_switch(self, tmp_path, monkeypatch):
        p = tmp_path / "events.jsonl"
        obs.configure_event_log(str(p))
        monkeypatch.setenv("DL4J_TPU_OBS", "0")
        obs.event("muted")
        assert not p.exists() or p.read_text() == ""


# ---------------------------------------------------------------------------
# exposition
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.einf+-]+$')


class TestExposition:
    def test_prometheus_text_parses(self):
        obs.counter("dl4j_demo_total", "demo", ("site",)).inc(site="a b")
        obs.histogram("dl4j_demo_seconds", "demo", ("span",)).observe(
            0.5, span="s")
        text = obs.prometheus_text()
        assert '# TYPE dl4j_demo_total counter' in text
        assert '# TYPE dl4j_demo_seconds summary' in text
        assert 'dl4j_demo_total{site="a b"} 1' in text
        assert 'quantile="0.99"' in text
        for line in text.splitlines():
            if line.startswith("#") or not line:
                continue
            assert _SAMPLE_RE.match(line), line

    def test_metrics_route_serves_registry(self):
        from deeplearning4j_tpu.ui.server import UIServer

        bucketing.telemetry().record_trace("mln.step", (32, 4))
        bucketing.telemetry().record_hit("mln.fit", 30, 32)
        obs.event("route_check")
        srv = UIServer().serve(port=0)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/metrics") as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith("text/plain")
                body = resp.read().decode()
        finally:
            srv.stop()
        assert 'dl4j_compiles_total{site="mln.step"} 1' in body
        assert 'dl4j_bucketing_hits_total' in body
        assert 'dl4j_events_total{kind="route_check"} 1' in body


# ---------------------------------------------------------------------------
# snapshot round-trip through the resilience checkpoint telemetry field
# ---------------------------------------------------------------------------


class TestSnapshotRoundTrip:
    def test_snapshot_embeds_all_views(self):
        bucketing.telemetry().record_hit("mln.fit", 30, 32)
        with obs.span("unit"):
            pass
        obs.event("snap_check")
        snap = obs.snapshot()
        assert set(snap) == {"metrics", "spans", "events", "bucketing",
                             "profile"}
        assert set(snap["profile"]) == {"sites"}
        assert snap["bucketing"]["real_examples"] == 30
        assert snap["events"]["snap_check"] == 1
        assert snap["spans"]["unit"]["count"] == 1
        json.dumps(snap)

    def test_checkpoint_telemetry_field_round_trips(self, tmp_path):
        from deeplearning4j_tpu.train import resilience
        from deeplearning4j_tpu.utils import serialization as S

        x, y = _toy_data()
        model = MultiLayerNetwork(_mlp_conf()).init()
        model.fit((x, y), epochs=1)
        path = str(tmp_path / "ckpt.zip")
        info = resilience.save_checkpoint(model, path)
        assert resilience.validate_checkpoint(
            path, crc=info["crc"], size=info["size"])

        tel = S.read_snapshot(path)["train_state"]["telemetry"]
        # the telemetry field IS an obs.snapshot(), intact through the zip
        assert set(tel) == {"metrics", "spans", "events", "bucketing",
                            "profile"}
        assert "mln.fit_batch" in tel["spans"]
        assert tel["bucketing"]["traces"].get("mln.step") == 1

        resilience.load_state_into(MultiLayerNetwork(_mlp_conf()), path)
        reg_snap = obs.snapshot()["metrics"]
        assert reg_snap["dl4j_checkpoint_saves_total"][""] == 1
        assert reg_snap["dl4j_checkpoint_restores_total"][""] == 1
        assert reg_snap["dl4j_checkpoint_save_seconds"][""]["count"] == 1
        assert reg_snap["dl4j_checkpoint_restore_seconds"][""]["count"] == 1
        assert obs.snapshot()["events"]["checkpoint_saved"] == 1
        assert obs.snapshot()["events"]["checkpoint_restored"] == 1


# ---------------------------------------------------------------------------
# profiling: XLA static cost models (obs/profile.py)
# ---------------------------------------------------------------------------


class TestCostModels:
    def test_lazy_cost_round_trip_per_step(self, monkeypatch):
        # per-step AotFunction dispatch: the compile flags the site, the
        # dispatch captures an exemplar, report time prices it
        monkeypatch.setenv("DL4J_TPU_CHAIN_STEPS", "0")
        x, y = _toy_data()
        model = MultiLayerNetwork(_mlp_conf()).init()
        model.fit((x, y), epochs=1)
        rep = obs.cost_report()
        assert "mln.step" in rep["sites"]
        entry = next(iter(rep["sites"]["mln.step"].values()))
        assert entry["source"] == "lazy"
        assert entry["flops"] > 0
        assert entry["bytes_accessed"] > 0
        # the gauges follow the ledger (snapshot keys join labels with |)
        flops = obs.snapshot()["metrics"]["dl4j_xla_flops"]
        assert any("site=mln.step" in k for k in flops)

    def test_chain_site_priced_separately(self, monkeypatch):
        # chained dispatch bypasses AotFunction; the chain executable is
        # harvested under its own site (K steps per dispatch)
        monkeypatch.setenv("DL4J_TPU_CHAIN_STEPS", "2")
        x, y = _toy_data(64)
        model = MultiLayerNetwork(_mlp_conf()).init()
        model.fit((x, y), epochs=1, batch_size=16)
        rep = obs.cost_report()
        assert "mln.chain" in rep["sites"]
        entry = next(iter(rep["sites"]["mln.chain"].values()))
        assert entry["source"] == "lazy"
        assert entry["flops"] > 0

    def test_aot_harvest_adds_memory_analysis(self):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.obs import profile as profile_mod

        a = np.zeros((8, 8), np.float32)
        compiled = jax.jit(lambda u, v: jnp.dot(u, v)).lower(a, a).compile()
        entry = profile_mod.harvest_compiled("unit.site", compiled, key="k0")
        assert entry is not None and entry["source"] == "aot"
        assert entry["flops"] > 0
        rep = obs.cost_report(resolve=False)
        assert rep["sites"]["unit.site"]["k0"]["flops"] == entry["flops"]
        # CPU backend provides memory_analysis: peak-HBM style fields ride
        if "argument_bytes" in entry:
            assert entry["argument_bytes"] > 0

    def test_cost_report_survives_model_collection(self, monkeypatch):
        # exemplars weakref their jit: resolving after the model is gone
        # contributes nothing but must not raise
        monkeypatch.setenv("DL4J_TPU_CHAIN_STEPS", "0")
        x, y = _toy_data()
        model = MultiLayerNetwork(_mlp_conf()).init()
        model.fit((x, y), epochs=1)
        obs.cost_report()          # resolves while alive
        del model
        rep = obs.cost_report()    # no pending left, ledger intact
        assert "mln.step" in rep["sites"]


# ---------------------------------------------------------------------------
# one clock: spans as profiler annotations, scopes and kernel names in the HLO
# ---------------------------------------------------------------------------


class _ScoreListener(TrainingListener):
    def iteration_done(self, model, iteration, score, batch_size=0):
        pass


def _host_annotations(trace_dir):
    """(name, start_ns, end_ns, stats) of every host-plane event of a
    jax.profiler trace that carries the spans' ``span_depth`` stat."""
    import glob

    from jax.profiler import ProfileData

    from deeplearning4j_tpu.obs.spans import SPAN_DEPTH_STAT

    path = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                stats = {k: str(v) for k, v in e.stats}
                if SPAN_DEPTH_STAT in stats:
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                stats))
    return out


def _traced(tmp_path, fn):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _host_annotations(tmp_path)


class TestProfilerClock:
    def test_fit_spans_nest_in_the_profilers_host_plane(self, tmp_path,
                                                        monkeypatch):
        monkeypatch.setenv("DL4J_TPU_CHAIN_STEPS", "0")
        x, y = _toy_data(32)
        model = MultiLayerNetwork(_mlp_conf()).init()
        model.set_listeners(_ScoreListener())
        model.fit((x, y), epochs=1, batch_size=8)      # compile outside
        obs.reset()
        it0 = model.iteration
        ann = _traced(tmp_path, lambda: model.fit((x, y), epochs=1,
                                                  batch_size=8))
        by_step = {}
        for name, a, b, stats in ann:
            by_step.setdefault(int(stats["step"]), {}).setdefault(
                name, []).append((a, b, int(stats["span_depth"])))
        # four batches, and the turn that finds the stream at its end
        assert sorted(by_step) == list(range(it0, it0 + 5))
        for step in range(it0, it0 + 4):
            spans = by_step[step]
            assert set(spans) == {"mln.iter", "mln.feed", "mln.fit_batch",
                                  "mln.step", "mln.loss_fetch",
                                  "mln.listeners"}
            assert all(len(v) == 1 for v in spans.values())
            (ia, ib, idepth), = spans["mln.iter"]
            assert idepth == 0
            children = ["mln.feed", "mln.fit_batch", "mln.loss_fetch",
                        "mln.listeners"]
            for prev, nxt in zip(children, children[1:]):
                assert spans[prev][0][1] <= spans[nxt][0][0]
            for c in children[:2]:
                a, b, depth = spans[c][0]
                assert ia <= a and b <= ib and depth == 1
            # the step's report is made in the next turn, after that turn's
            # dispatch (or in the turn that finds the stream at its end), and
            # carries its own step's number (nn/step_program.py StepReports)
            (na, nb, _), = by_step[step + 1]["mln.iter"]
            after = by_step[step + 1].get("mln.fit_batch",
                                          by_step[step + 1]["mln.feed"])[0][1]
            for c in children[2:]:
                a, b, depth = spans[c][0]
                assert na <= after <= a and b <= nb and depth == 1
            (fa, fb, _), (sa, sb, sdepth) = (spans["mln.fit_batch"][0],
                                             spans["mln.step"][0])
            assert fa <= sa and sb <= fb and sdepth == 2
        assert set(by_step[it0 + 4]) == {"mln.iter", "mln.feed"}
        # the ring holds the same spans with the same step numbers
        ring = [(r["span"], r["attrs"]["step"]) for r in obs.recent_spans()]
        assert sorted(ring) == sorted(
            (name, int(stats["step"])) for name, _, _, stats in ann)

    def test_graph_fit_has_the_same_spans(self, monkeypatch):
        from deeplearning4j_tpu.nn.graph import (
            ComputationGraph, ComputationGraphConfiguration)

        monkeypatch.setenv("DL4J_TPU_CHAIN_STEPS", "0")
        conf = (ComputationGraphConfiguration.builder()
                .add_inputs("in")
                .set_input_types(InputType.feed_forward(4))
                .add_layer("h", Dense(n_out=8, activation="tanh"), "in")
                .add_layer("out", OutputLayer(n_out=2, activation="softmax"),
                           "h")
                .set_outputs("out")
                .updater({"type": "sgd", "lr": 0.05}).build())
        g = ComputationGraph(conf).init()
        g.set_listeners(_ScoreListener())
        x, y = _toy_data(16)
        g.fit((x, y), epochs=1, batch_size=8)
        turns = [r for r in obs.recent_spans() if r["span"] == "cg.iter"]
        assert [r["attrs"]["step"] for r in turns] == [0, 1, 2]
        for name in ("cg.feed", "cg.fit_batch", "cg.loss_fetch",
                     "cg.listeners"):
            recs = [r for r in obs.recent_spans() if r["span"] == name]
            assert recs and all(r["parent"] == "cg.iter" for r in recs), name
        steps = [r for r in obs.recent_spans() if r["span"] == "cg.step"]
        assert [r["parent"] for r in steps] == ["cg.fit_batch"] * 2
        assert [r["attrs"]["step"] for r in steps] == [0, 1]

    def test_every_call_of_a_step_program_is_one_span_of_its_site(self):
        import jax.numpy as jnp

        from deeplearning4j_tpu.nn.step_program import StepProgram

        prog = StepProgram(lambda a: a + 1, "toy.site", donate_argnums=(),
                           aot_wrap=False)
        prog(jnp.ones(3))
        with obs.span("outer", step=41):
            prog.dispatch(jnp.ones(3))
        recs = [r for r in obs.recent_spans() if r["span"] == "toy.site"]
        assert [r["parent"] for r in recs] == [None, "outer"]
        assert "attrs" not in recs[0] and recs[1]["attrs"] == {"step": 41}

    def test_lowered_step_carries_site_layer_loss_and_update_scopes(self):
        import jax
        import jax.numpy as jnp

        model = MultiLayerNetwork(_mlp_conf()).init()
        x, y = _toy_data(8)
        step = model._get_step_fn(False)
        text = step.lower(
            model.params, model.opt_state, model.state,
            jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
            jnp.asarray(x), jnp.asarray(y), None, None, (),
        ).as_text(debug_info=True)
        for scope in ("mln.step/jvp(Dense.0)", "mln.step/transpose(jvp(Dense.0))",
                      "mln.step/jvp(loss)/OutputLayer.1",
                      "mln.step/update/Dense.0",
                      "mln.step/update/OutputLayer.1"):
            assert scope in text, scope

    def test_flash_kernels_carry_their_names(self):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.ops.flash_attention import flash_attention

        q = jnp.ones((1, 128, 2, 64), jnp.float32)

        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True, interpret=True).sum()

        fwd = str(jax.make_jaxpr(loss)(q, q, q))
        both = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
        # each name holds the heads that share a lane block (two of 64) and
        # ends in the blocks the kernel ran at (one 128-row tile)
        names = lambda t: set(re.findall(  # noqa: E731
            r"flash_(?:fwd|bwd_dqkv|bwd_dq|bwd_dkv)(?:_h\d+)?_q\d+_k\d+", t))
        assert names(fwd) == {"flash_fwd_h2_q128_k128"}
        # the backward is one kernel that gives dq, dk and dv together
        assert names(both) == {"flash_fwd_h2_q128_k128",
                               "flash_bwd_dqkv_h2_q128_k128"}

    def test_scopes_leave_the_steps_results_bit_identical(self, monkeypatch):
        import contextlib

        import jax

        monkeypatch.setenv("DL4J_TPU_CHAIN_STEPS", "0")
        x, y = _toy_data()
        scoped = MultiLayerNetwork(_mlp_conf()).init()
        scoped.fit((x, y), epochs=2, batch_size=8)
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        bare = MultiLayerNetwork(_mlp_conf()).init()
        bare.fit((x, y), epochs=2, batch_size=8)
        for a, b in zip(jax.tree_util.tree_leaves(scoped.params),
                        jax.tree_util.tree_leaves(bare.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_kill_switch_opens_no_annotation(self, tmp_path, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_OBS", "0")
        monkeypatch.setenv("DL4J_TPU_CHAIN_STEPS", "0")
        x, y = _toy_data(16)
        model = MultiLayerNetwork(_mlp_conf()).init()
        model.set_listeners(_ScoreListener())
        assert _traced(tmp_path, lambda: model.fit((x, y), epochs=1,
                                                   batch_size=8)) == []
        assert obs.recent_spans() == []

    def test_serving_engine_spans_and_request_waits(self):
        from deeplearning4j_tpu import serve
        from tests.test_generate import _cfg, _lm, _prompt

        reg = serve.ModelRegistry()
        try:
            gw = reg.register_generate("lm", _lm(), warm=True, config=_cfg())
            obs.reset()
            streams = [gw.submit(_prompt(n, seed=n), max_new=4)
                       for n in (5, 19)]
            assert [len(list(s)) for s in streams] == [4, 4]
        finally:
            reg.shutdown()
        recs = obs.recent_spans()
        by_name = {}
        for r in recs:
            by_name.setdefault(r["span"], []).append(r)
        assert all(r["parent"] is None and r["attrs"] == {"model": "lm"}
                   for r in by_name["serve.engine_iter"])
        for name in ("serve.admit", "serve.prefill_chunk",
                     "serve.decode_step", "serve.fanout"):
            assert by_name[name], name
            assert all(r["parent"] == "serve.engine_iter"
                       for r in by_name[name]), name
        # the 19-token prompt takes two chunks of 16, the 5-token one
        assert sorted(r["attrs"]["tc"] for r in by_name["serve.prefill_chunk"]) \
            == [4, 8, 16]
        assert all(set(r["attrs"]) == {"tc", "pages", "model"}
                   for r in by_name["serve.prefill_chunk"])
        for r in by_name["serve.decode_step"]:
            assert set(r["attrs"]) == {"rows", "batch", "pages"}
            assert 1 <= r["attrs"]["rows"] <= r["attrs"]["batch"]
        # each dispatch is the decode.step site's own span inside
        assert {r["parent"] for r in by_name["decode.step"]} == {
            "serve.prefill_chunk", "serve.decode_step"}
        # one queue wait and one prefill wait a request
        c = gw.stats_counters
        assert (c["queue_waits"], c["prefill_waits"]) == (2, 2)
        assert 0 <= c["queue_wait_s"] and 0 < c["prefill_wait_s"]
        waits = obs.registry().histogram(
            "dl4j_request_wait_seconds", "", ("route", "stage"))
        for stage in ("queue", "prefill"):
            assert waits.summary(route=gw.route, stage=stage)["count"] == 2


# ---------------------------------------------------------------------------
# Chrome/Perfetto trace export (obs/trace_export.py)
# ---------------------------------------------------------------------------


class TestTraceExport:
    def test_trace_json_schema_and_nesting(self):
        from deeplearning4j_tpu.obs import trace_export

        with obs.span("outer"):
            with obs.span("inner"):
                pass
        doc = json.loads(trace_export.live_trace())
        assert trace_export.validate(doc) == []
        assert doc["displayTimeUnit"] == "ms"
        evs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
        assert {"outer", "inner"} <= set(evs)
        o, i = evs["outer"], evs["inner"]
        assert i["args"]["parent"] == "outer"
        assert o["ts"] <= i["ts"]
        assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1.0  # 1 us slop
        meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert meta and all(e["name"] == "thread_name" for e in meta)

    def test_cli_round_trip_validates(self, tmp_path):
        from deeplearning4j_tpu.obs import trace_export

        with obs.span("cli_span"):
            pass
        dump = tmp_path / "spans.json"
        assert obs.save_spans(str(dump)) >= 1
        out = tmp_path / "trace.json"
        rc = trace_export.main(
            ["--spans", str(dump), "--out", str(out), "--validate"])
        assert rc == 0
        doc = json.loads(out.read_text())
        assert any(e["name"] == "cli_span" for e in doc["traceEvents"])

    def test_event_instants_overlay(self, tmp_path):
        from deeplearning4j_tpu.obs import trace_export

        obs.configure_event_log(str(tmp_path / "ev.jsonl"))
        with obs.span("with_marker"):
            obs.event("marker", k=1)
        doc = json.loads(trace_export.live_trace(include_events=True))
        assert trace_export.validate(doc) == []
        inst = [e for e in doc["traceEvents"] if e["ph"] == "i"]
        assert any(e["name"] == "marker" for e in inst)


# ---------------------------------------------------------------------------
# serving SLOs (obs/slo.py) + HTTP observability (ui/server.py)
# ---------------------------------------------------------------------------


class TestServingSlo:
    def test_latency_counts_and_burn_rate(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_SLO_LATENCY_MS", "100")
        monkeypatch.setenv("DL4J_TPU_SLO_OBJECTIVE", "0.9")
        for _ in range(8):
            obs.observe_request("unit.route", 0.01)
        obs.observe_request("unit.route", 0.5)                  # slow -> bad
        obs.observe_request("unit.route", 0.01, status="error", error=True)
        snap = obs.snapshot()["metrics"]
        assert snap["dl4j_request_seconds"]["route=unit.route"]["count"] == 10
        totals = snap["dl4j_requests_total"]
        assert totals["route=unit.route|status=ok"] == 9
        assert totals["route=unit.route|status=error"] == 1
        # 2 bad of 10 against a 10% error budget -> burning at 2x
        burn = snap["dl4j_slo_burn_rate"]["route=unit.route"]
        assert burn == pytest.approx(2.0, abs=0.01)

    def test_kill_switch_mutes_requests(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_OBS", "0")
        obs.observe_request("muted", 0.01)
        snap = obs.snapshot()["metrics"]
        assert snap.get("dl4j_requests_total", {}) == {}


class TestHttpObservability:
    def test_debug_trace_route_serves_valid_trace(self):
        from deeplearning4j_tpu.obs import trace_export
        from deeplearning4j_tpu.ui.server import UIServer

        with obs.span("pre_http"):
            pass
        srv = UIServer().serve(port=0)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/debug/trace") as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith(
                    "application/json")
                doc = json.loads(resp.read().decode())
            # a second request sees the first one's latency in /metrics
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/metrics") as resp:
                body = resp.read().decode()
        finally:
            srv.stop()
        assert trace_export.validate(doc) == []
        assert any(e.get("name") == "pre_http" for e in doc["traceEvents"])
        assert ('dl4j_requests_total{route="/debug/trace",status="200"} 1'
                in body)
        assert 'dl4j_request_seconds' in body
        assert 'dl4j_http_in_flight' in body
        assert 'dl4j_slo_burn_rate{route="/debug/trace"}' in body


# ---------------------------------------------------------------------------
# span ring knob (DL4J_TPU_SPAN_RING)
# ---------------------------------------------------------------------------


class TestSpanRing:
    def test_ring_knob_bounds_retention_and_counts_drops(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_SPAN_RING", "4")
        reg = MetricsRegistry()
        tr = SpanTracer(reg)
        for i in range(10):
            with tr.span(f"s{i}"):
                pass
        assert len(tr.recent()) == 4
        assert reg.counter("dl4j_spans_dropped_total").value() == 6

    def test_explicit_ring_size_wins(self):
        tr = SpanTracer(MetricsRegistry(), ring_size=2)
        for i in range(5):
            with tr.span(f"s{i}"):
                pass
        assert len(tr.recent()) == 2


# ---------------------------------------------------------------------------
# listener satellites
# ---------------------------------------------------------------------------


class _Closeable(TrainingListener):
    def __init__(self):
        self.closed = 0

    def close(self):
        self.closed += 1


class TestPerformanceListener:
    def test_first_window_counts_anchor_batch(self, monkeypatch):
        clock = [0.0]
        monkeypatch.setattr(listeners_mod.time, "perf_counter",
                            lambda: clock[0])
        pl = PerformanceListener(frequency=2, out=lambda s: None)
        for it in range(3):           # iterations 0, 1, 2 — one per second
            pl.iteration_done(None, it, 0.1, batch_size=32)
            clock[0] += 1.0
        assert len(pl.history) == 1
        rec = pl.history[0]
        # window covers 2 iterations over 2s; all THREE calls' samples count
        # (the anchoring call's batch used to be discarded -> 32/s)
        assert rec["batches_per_sec"] == pytest.approx(1.0)
        assert rec["samples_per_sec"] == pytest.approx(48.0)

    def test_steady_state_windows_unchanged(self, monkeypatch):
        clock = [0.0]
        monkeypatch.setattr(listeners_mod.time, "perf_counter",
                            lambda: clock[0])
        pl = PerformanceListener(frequency=2, out=lambda s: None)
        for it in range(7):
            pl.iteration_done(None, it, 0.1, batch_size=10)
            clock[0] += 1.0
        # windows at iterations 2, 4, 6; later windows hold 2 batches each
        assert len(pl.history) == 3
        for rec in pl.history[1:]:
            assert rec["samples_per_sec"] == pytest.approx(10.0)


class TestListenerClose:
    def test_fit_closes_listeners(self):
        x, y = _toy_data()
        model = MultiLayerNetwork(_mlp_conf()).init()
        closeable = _Closeable()
        model.set_listeners(closeable)
        model.fit((x, y), epochs=1)
        assert closeable.closed == 1

    def test_fit_closes_even_when_fit_raises(self):
        x, y = _toy_data()
        model = MultiLayerNetwork(_mlp_conf()).init()

        class Bomb(TrainingListener):
            def iteration_done(self, model, iteration, score, batch_size=0):
                raise RuntimeError("listener bomb")

        closeable = _Closeable()
        model.set_listeners(Bomb(), closeable)
        with pytest.raises(RuntimeError):
            model.fit((x, y), epochs=1)
        assert closeable.closed == 1

    def test_composed_listener_fans_out_close(self):
        a, b = _Closeable(), _Closeable()
        ComposedListener([a, b]).close()
        assert (a.closed, b.closed) == (1, 1)

    def test_close_errors_logged_not_raised(self):
        class BadClose(TrainingListener):
            def close(self):
                raise RuntimeError("teardown bomb")

        ok = _Closeable()
        listeners_mod.close_listeners([BadClose(), ok])
        assert ok.closed == 1
