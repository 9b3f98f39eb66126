"""What JAX spends tracing, lowering, compiling and reading its cache, booked
by site and phase (obs/compile_phases.py): the site is the program's own,
phases are self time, a compiled signature books nothing more, the ring shows
which step recompiled, and ``DL4J_TPU_OBS=0`` registers nothing."""

import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.nn import aot
from deeplearning4j_tpu.nn.step_program import StepProgram
from deeplearning4j_tpu.obs import compile_phases
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.obs.spans import SpanTracer

REPO = Path(__file__).resolve().parent.parent
PHASES = ("trace", "lower", "backend", "cache_read")
TRACE, LOWER, BACKEND, CACHE_READ = compile_phases.PHASES


@pytest.fixture(autouse=True)
def _obs_isolation(monkeypatch):
    monkeypatch.delenv("DL4J_TPU_OBS", raising=False)
    obs.reset()
    yield
    obs.reset()


def _series(family: str) -> dict:
    return dict(obs.registry().snapshot().get(family) or {})


def _seconds(site: str) -> dict:
    got = _series("dl4j_compile_seconds_total")
    return {p: got[f"site={site}|phase={p}"] for p in PHASES
            if f"site={site}|phase={p}" in got}


def _program(site: str) -> StepProgram:
    """A step whose body calls a jitted function: the inner function's trace
    lies inside the body's."""
    inner = jax.jit(lambda x: jnp.tanh(x) * 2.0)

    def body(params, opt, state, x):
        return params, opt, state, (inner(x) @ params["w"]).sum()

    return StepProgram(body, site)


def _args():
    return {"w": jnp.ones((8, 8))}, {}, {}, jnp.ones((4, 8))


def test_a_step_books_its_phases_under_its_site_within_the_calls_span():
    prog = _program("unit.step")
    jax.block_until_ready(_args())          # the arguments' own programs
    before = _series("dl4j_compile_seconds_total")
    jax.block_until_ready(prog(*_args()))
    booked = _seconds("unit.step")
    assert set(booked) == {"trace", "lower", "backend"}
    assert all(v > 0 for v in booked.values())
    events = _series("dl4j_compile_events_total")
    assert events["site=unit.step|phase=backend"] == 1
    assert events["site=unit.step|phase=lower"] == 1
    # the body, the inner jitted function and the jnp functions they call
    assert events["site=unit.step|phase=trace"] >= 2
    call = [r for r in obs.recent_spans() if r["span"] == "unit.step"]
    assert len(call) == 1
    # nested traces are not counted twice: self times add up to no more than
    # the span they fell in, and the span's record carries their sum
    assert sum(booked.values()) <= call[0]["wall_s"]
    assert call[0]["compile_s"] == pytest.approx(sum(booked.values()))
    # nothing of the step went to another site
    after = _series("dl4j_compile_seconds_total")
    assert {k for k in after if after[k] != before.get(k)} == {
        f"site=unit.step|phase={p}" for p in booked}


def test_a_second_call_of_a_compiled_signature_books_nothing():
    prog = _program("unit.again")
    jax.block_until_ready(prog(*_args()))
    seconds = _series("dl4j_compile_seconds_total")
    events = _series("dl4j_compile_events_total")
    n_records = len([r for r in obs.recent_spans()
                     if r["span"].startswith("compile.")])
    jax.block_until_ready(prog(*_args()))
    assert _series("dl4j_compile_seconds_total") == seconds
    assert _series("dl4j_compile_events_total") == events
    assert len([r for r in obs.recent_spans()
                if r["span"].startswith("compile.")]) == n_records
    second = [r for r in obs.recent_spans() if r["span"] == "unit.again"][-1]
    assert "compile_s" not in second


def test_a_plain_jit_outside_any_span_books_under_none():
    compile_phases.install()
    f = jax.jit(lambda x: jnp.cos(x) + 3.0)
    jax.block_until_ready(f(jnp.ones(5)))
    booked = _seconds(compile_phases.NO_SITE)
    assert {"trace", "lower", "backend"} <= set(booked)
    assert all("site=none|" in k
               for k in _series("dl4j_compile_seconds_total"))


def test_a_non_site_span_gives_no_site_and_the_innermost_site_span_wins():
    compile_phases.install()
    with obs.site_span("outer.site"):
        with obs.span("unit.feed"):
            jax.block_until_ready(jax.jit(lambda x: x * 5.0)(jnp.ones(3)))
            with obs.compile_span("inner.site", mode="aot"):
                jax.block_until_ready(jax.jit(lambda x: x - 7.0)(jnp.ones(3)))
    assert "backend" in _seconds("outer.site")
    assert "backend" in _seconds("inner.site")
    assert not _seconds("unit.feed")


def test_aot_warm_books_under_its_site_through_the_compile_span():
    fn = aot.AotFunction(jax.jit(lambda x: jnp.sin(x) * 11.0), "unit.warm")
    x = jnp.ones((3, 3))
    jax.block_until_ready(x)
    obs.reset()
    fn.warm(x)
    booked = _seconds("unit.warm")
    assert set(booked) == {"trace", "lower", "backend"}
    span = [r for r in obs.recent_spans() if r["span"] == "compile"]
    assert len(span) == 1 and span[0]["attrs"]["mode"] == "aot"
    assert span[0]["compile_s"] == pytest.approx(sum(booked.values()))
    assert sum(booked.values()) <= span[0]["wall_s"]
    fn.warm(x)                  # idempotent: no second compile, no seconds
    assert _seconds("unit.warm") == booked


def test_the_ring_shows_which_step_recompiled():
    prog = _program("unit.ring")
    args = _args()
    jax.block_until_ready(args)
    obs.reset()
    with obs.span("mln.iter", step=41):
        jax.block_until_ready(prog(*args))
    recs = {r["span"]: r for r in obs.recent_spans()}
    call = recs["unit.ring"]
    for name in ("compile.lower", "compile.backend"):
        r = recs[name]
        assert r["attrs"]["step"] == 41 and r["attrs"]["site"] == "unit.ring"
        assert r["parent"] == "unit.ring" and r["depth"] == call["depth"] + 1
        assert r["attrs"]["fun"] == "jit(body)"
        # back-dated onto the ring's timeline, inside the call's span
        assert call["t0_s"] <= r["t0_s"]
        assert r["t0_s"] + r["wall_s"] <= call["t0_s"] + call["wall_s"] + 1e-3
        assert r["attrs"]["self_s"] <= r["wall_s"] + 1e-3
    assert recs["compile.lower"]["t0_s"] < recs["compile.backend"]["t0_s"]
    from deeplearning4j_tpu.obs import trace_export

    doc = trace_export.trace_events(obs.recent_spans())
    assert trace_export.validate(doc) == []
    assert {"compile.lower", "compile.backend"} <= {
        e["name"] for e in doc["traceEvents"]}


# -- the booking itself, on events handed in --------------------------------


@pytest.fixture
def phases():
    reg = MetricsRegistry()
    return compile_phases.CompilePhases(reg, SpanTracer(reg=reg)), reg


def test_an_event_is_booked_less_what_it_covers(phases):
    p, _ = phases
    # two inner traces, then the outer trace around both
    assert p._own_time(10.3, 0.2) == (pytest.approx(10.1), pytest.approx(0.2))
    assert p._own_time(10.7, 0.3) == (pytest.approx(10.4), pytest.approx(0.3))
    assert p._own_time(11.0, 1.0) == (pytest.approx(10.0), pytest.approx(0.5))
    # a neighbour after it covers nothing
    assert p._own_time(11.5, 0.4) == (pytest.approx(11.1), pytest.approx(0.4))
    # one around everything so far is left with the gaps
    assert p._own_time(12.0, 2.5)[1] == pytest.approx(2.5 - 1.0 - 0.4)
    # an event whose back-dated start falls inside its neighbour (the clocks
    # differ) starts where the neighbour ended and keeps its seconds
    assert p._own_time(12.2, 0.25) == (pytest.approx(12.0),
                                       pytest.approx(0.25))


def test_a_long_trace_subtracts_every_event_inside_it(phases):
    """A step's trace holds thousands of jitted functions' traces, nested
    several deep: every one is subtracted once from what encloses it, and a
    finished outermost event leaves one interval behind."""
    p, _ = phases
    n, own = 5000, 0.0
    for i in range(n):              # 1 ms apart: two leaves and their parent
        t = 100.0 + 1e-3 * i
        own += p._own_time(t + 2e-4, 2e-4)[1] + p._own_time(t + 5e-4, 2e-4)[1]
        own += p._own_time(t + 6e-4, 6e-4)[1]
    assert len(p._tls.booked) == n
    whole = 1e-3 * n + 0.5
    start, outer = p._own_time(100.0 + 1e-3 * n + 0.25, whole)
    assert own + outer == pytest.approx(whole)
    assert start == pytest.approx(99.75) and len(p._tls.booked) == 1


def test_booked_intervals_stay_bounded(phases, monkeypatch):
    p, _ = phases
    monkeypatch.setattr(compile_phases, "_KEEP", 8)
    for i in range(100):
        p._own_time(float(i) + 0.5, 0.5)
    assert len(p._tls.booked) == 8
    # what is still kept is still subtracted
    assert p._own_time(100.0, 4.0)[1] == pytest.approx(2.0)


def test_cache_read_is_taken_out_of_backend_and_other_events_are_ignored(phases):
    p, reg = phases
    with p._tracer.site_span("unit.step", "unit.step"):
        p.on_duration(CACHE_READ, 0.4)
        p.on_duration(BACKEND, 0.45, fun_name="jit(step)")
    p.on_duration("/jax/compilation_cache/compile_time_saved_sec", 12.0)
    snap = reg.snapshot()
    assert snap["dl4j_compile_seconds_total"] == {
        "site=unit.step|phase=backend": pytest.approx(0.05),
        "site=unit.step|phase=cache_read": pytest.approx(0.4)}
    assert snap["dl4j_compile_events_total"] == {
        "site=unit.step|phase=backend": 1,
        "site=unit.step|phase=cache_read": 1}
    ring = {r["span"]: r for r in p._tracer.recent()}
    assert ring["unit.step"]["compile_s"] == pytest.approx(0.45)
    assert ring["compile.backend"]["attrs"] == {
        "site": "unit.step", "self_s": pytest.approx(0.05),
        "fun": "jit(step)"}


def test_a_short_trace_is_counted_and_leaves_no_record(phases):
    p, reg = phases
    p.on_duration(TRACE, 0.002, fun_name="tanh")
    p.on_duration(TRACE, 0.5, fun_name="body")
    assert reg.snapshot()["dl4j_compile_events_total"] == {
        "site=none|phase=trace": 2}
    assert [r["attrs"]["fun"] for r in p._tracer.recent()] == ["body"]


def test_obs_off_books_nothing(phases, monkeypatch):
    p, reg = phases
    monkeypatch.setenv("DL4J_TPU_OBS", "0")
    p.on_duration(BACKEND, 0.3)
    snap = reg.snapshot()
    assert not snap["dl4j_compile_seconds_total"]
    assert not snap["dl4j_compile_events_total"]
    assert p._tracer.recent() == []


def test_obs_off_registers_nothing():
    """In a process of its own: the listener is a process's one."""
    code = f"""
import sys
sys.path.insert(0, {str(REPO)!r})
import jax, jax.numpy as jnp
from jax._src import monitoring
from deeplearning4j_tpu import obs
from deeplearning4j_tpu.nn.step_program import StepProgram
from deeplearning4j_tpu.obs import compile_phases
prog = StepProgram(lambda p, o, s, x: (p, o, s, (x * 2).sum()), "unit.off")
with obs.span("mln.iter", step=0):
    jax.block_until_ready(prog({{}}, {{}}, {{}}, jnp.ones(4)))
assert compile_phases.install() is None
assert monitoring.get_event_duration_listeners() == []
assert monitoring.get_event_listeners() == []
assert not [f.name for f in obs.registry().families()
            if f.name.startswith("dl4j_compile_")]
assert obs.recent_spans() == []
print("NOTHING")
"""
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=180, env={"DL4J_TPU_OBS": "0", "JAX_PLATFORMS": "cpu",
                          "PATH": "/usr/bin:/bin"})
    assert out.returncode == 0, out.stderr
    assert "NOTHING" in out.stdout


def test_a_models_step_books_no_more_than_its_spans_lasted():
    """A real step's trace: hundreds of jitted functions nested in the body's.
    What the site is booked stays within the wall time of its calls."""
    import numpy as np

    from deeplearning4j_tpu.models import TransformerLM
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.train.listeners import TrainingListener

    m = MultiLayerNetwork(TransformerLM(
        vocab_size=64, max_len=16, d_model=32, n_heads=2, n_blocks=2)).init()
    m.set_listeners(TrainingListener())   # fit() opens mln.iter with one
    ids = np.random.RandomState(0).randint(0, 64, (2, 16)).astype(np.int32)
    obs.reset()
    m.fit([(ids, np.roll(ids, -1, 1))] * 2)
    booked = _seconds("mln.step")
    events = _series("dl4j_compile_events_total")
    assert events["site=mln.step|phase=trace"] > 100
    assert events["site=mln.step|phase=backend"] == 1
    calls = [r for r in obs.recent_spans() if r["span"] == "mln.step"]
    assert len(calls) == 2 and "compile_s" not in calls[1]
    assert calls[0]["compile_s"] == pytest.approx(sum(booked.values()))
    assert sum(booked.values()) <= calls[0]["wall_s"]
    first = [r for r in obs.recent_spans() if r["span"] == "compile.backend"]
    assert [r["attrs"]["step"] for r in first] == [calls[0]["attrs"]["step"]]
