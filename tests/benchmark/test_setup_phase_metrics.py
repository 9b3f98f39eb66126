"""CPU tests of what PR 36 added to the benchmark: one reader,
``obs_series`` (the sum of the series of one family of the program's ``obs``
registry whose labels match), and five per-layer metrics that move
``setup_s``, each a file and an entry: the seconds the train step's site spent
in JAX's trace, lowering, backend compile and cache read, and the executables
it compiled or loaded. A program without the families, as the parent
commit's, gives nothing.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import spec  # noqa: E402
from benchmark.readers import obs_series  # noqa: E402

SECONDS, EVENTS = "dl4j_compile_seconds_total", "dl4j_compile_events_total"
METRICS = {
    "step_trace_s.setup": ("s", SECONDS, "trace"),
    "step_lower_s.setup": ("s", SECONDS, "lower"),
    "step_backend_s.setup": ("s", SECONDS, "backend"),
    "step_cache_read_s.setup": ("s", SECONDS, "cache_read"),
    "step_programs_loaded.setup": ("count", EVENTS, "backend"),
}
CELLS = ("gpt2m-f32-train-b8-t1024", "gpt2m-f32-train-b32-t256",
         "twotower-s16-f32-train-b1-t4096",
         "joyai-flash-s16-f32-train-b1-t8192",
         "lfm2-8b-s4-f32-train-b2-t8192")


def _benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_file_resolves_and_agrees_with_its_entry(name):
    unit, family, phase = METRICS[name]
    f = spec.load_json("metrics", name)
    assert hasattr(spec.module("readers", f["reader"]), "read")
    assert (f["reader"], f["family"], f["labels"]) == (
        "obs_series", family, {"site": "mln.step", "phase": phase})
    b = _benchmark()
    entry = next(m for m in b["per_layer"] if m["name"] == name)
    assert (f["layer"], f["unit"], f["better"], f["moves"], f["source"]) == (
        entry["layer"], entry["unit"], entry["better"], entry["moves"],
        entry["source"]) == ("step wiring", unit, "lower", "setup_s",
                             "program_counter")
    # at least the five accepted cells: a later PR's cell may be appended
    assert set(entry["workloads"]) >= set(CELLS)
    assert set(CELLS) <= {w["name"] for w in b["workloads"]}
    # every cell reports setup_s, so every cell tries the file
    assert name in {m["name"] for m in spec.metrics_for({"setup_s"})}
    assert spec.NAME.match(name) and spec.UNIT.match(unit)
    if name in ("step_backend_s.setup", "step_cache_read_s.setup"):
        # the two say the same of each other, as the program's module does
        assert "taken out" in f["what"]


def test_the_entries_stand_in_the_issues_order_among_themselves():
    """Not where they stand among the others: a later PR appends its own."""
    five = ["step_trace_s.setup", "step_lower_s.setup", "step_backend_s.setup",
            "step_cache_read_s.setup", "step_programs_loaded.setup"]
    assert [m["name"] for m in _benchmark()["per_layer"]
            if m["name"] in five] == five


@pytest.fixture
def registry(monkeypatch):
    """A registry filled by hand, in the process's place."""
    from deeplearning4j_tpu import obs
    from deeplearning4j_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    monkeypatch.setattr(obs, "registry", lambda: reg)
    seconds = reg.counter(SECONDS, "", ("site", "phase"))
    seconds.inc(8.5, site="mln.step", phase="trace")
    seconds.inc(1.25, site="mln.step", phase="lower")
    seconds.inc(0.5, site="mln.step", phase="backend")
    seconds.inc(30.0, site="none", phase="trace")
    seconds.inc(2.0, site="decode.step", phase="trace")
    events = reg.counter(EVENTS, "", ("site", "phase"))
    events.inc(1, site="mln.step", phase="backend")
    events.inc(9, site="none", phase="backend")
    reg.gauge("dl4j_demo_level", "", ("site",)).set(3.0, site="a")
    reg.histogram("dl4j_demo_seconds", "", ("site",)).observe(1.0, site="a")
    return reg


def test_obs_series_sums_what_matches(registry):
    def read(family, **labels):
        return obs_series.read({"family": family, "labels": labels}, {})

    assert read(SECONDS, site="mln.step", phase="trace") == 8.5
    assert read(SECONDS, site="mln.step") == 8.5 + 1.25 + 0.5
    assert read(SECONDS, phase="trace") == 8.5 + 30.0 + 2.0
    # a list of values for a label: any of them
    assert read(SECONDS, site=["mln.step", "decode.step"],
                phase="trace") == 8.5 + 2.0
    assert read(SECONDS, site="mln.step", phase=["trace", "lower"]) == 9.75
    assert obs_series.read({"family": SECONDS}, {}) == 42.25
    assert read(EVENTS, site="mln.step", phase="backend") == 1.0
    assert read("dl4j_demo_level", site="a") == 3.0


def test_obs_series_gives_nothing_where_there_is_nothing_to_read(registry):
    def read(family, **labels):
        return obs_series.read({"family": family, "labels": labels}, {})

    assert read("dl4j_not_there_total", site="mln.step") is None
    assert read(SECONDS, site="mln.step", phase="cache_read") is None
    assert read(SECONDS, site="mesh.step") is None
    assert read(SECONDS, rank="0") is None            # no such label
    assert read("dl4j_demo_seconds", site="a") is None    # a histogram


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_program_from_before_the_families_prints_none_of_the_five(
        name, monkeypatch):
    from deeplearning4j_tpu import obs
    from deeplearning4j_tpu.obs.metrics import MetricsRegistry

    monkeypatch.setattr(obs, "registry", MetricsRegistry)
    f = spec.load_json("metrics", name)
    assert spec.module("readers", f["reader"]).read(f, {}) is None


def test_the_files_read_what_a_step_booked():
    """The program's own listener under a ``mln.step`` site span, then the
    five files: the three phases a cold compile has and the one executable;
    no cache read, which the line then leaves out."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import obs
    from deeplearning4j_tpu.nn.step_program import StepProgram

    obs.reset()
    prog = StepProgram(lambda p, o, s, x: (p, o, s, jnp.tanh(x).sum()),
                       "mln.step")
    jax.block_until_ready(prog({}, {}, {}, jnp.ones((4, 4))))
    got = {name: obs_series.read(spec.load_json("metrics", name), {})
           for name in METRICS}
    assert got["step_programs_loaded.setup"] == 1.0
    assert got["step_cache_read_s.setup"] is None
    for name in ("step_trace_s.setup", "step_lower_s.setup",
                 "step_backend_s.setup"):
        assert got[name] > 0.0
    obs.reset()
