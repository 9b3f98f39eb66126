"""CPU tests of what PR 35 added to the benchmark, data only: the metric
``moe_buffer_rows_per_pair.train`` (the rows of the pair buffer a step's
expert layers took, over the pairs sent to held experts) parses, names a
reader that is there, is entered in ``BENCHMARK.json`` beside its twin
``moe_rows_computed_per_pair.train`` for the three expert cells, reads the
program's counter, and reads nothing from a program that has none.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import spec  # noqa: E402

NAME = "moe_buffer_rows_per_pair.train"
TWIN = "moe_rows_computed_per_pair.train"
EXPERT_CELLS = ("twotower-s16-f32-train-b1-t4096",
                "joyai-flash-s16-f32-train-b1-t8192",
                "lfm2-8b-s4-f32-train-b2-t8192")


def _entry(name):
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return next(m for m in b["per_layer"] if m["name"] == name), b


def test_the_metric_file_parses_and_names_a_reader_that_is_there():
    f = spec.load_json("metrics", NAME)
    assert hasattr(spec.module("readers", f["reader"]), "read")
    assert (f["reader"], f["counter"], f["per"]) == (
        "obs_counter", "dl4j_moe_rows_buffer_total",
        "dl4j_moe_pairs_held_total")
    entry, b = _entry(NAME)
    assert (f["layer"], f["unit"], f["better"], f["moves"], f["source"]) == (
        entry["layer"], entry["unit"], entry["better"], entry["moves"],
        entry["source"]) == ("model step", "ratio", "lower",
                             "train_tokens_per_s", "program_counter")
    assert b["per_layer"][-1]["name"] == NAME       # appended, nothing moved
    twin, _ = _entry(TWIN)
    assert {k: v for k, v in entry.items() if k != "name"} == {
        k: v for k, v in twin.items() if k != "name"}
    assert any(m["name"] == NAME for m in spec.metrics_for(
        {"train_tokens_per_s", "setup_s"}))


@pytest.mark.parametrize("cell", EXPERT_CELLS)
def test_the_metric_lists_every_expert_cell(cell):
    entry, b = _entry(NAME)
    # at least these: a later PR's expert cell may be appended
    assert set(entry["workloads"]) >= set(EXPERT_CELLS)
    assert cell in {w["name"] for w in b["workloads"]}
    listed = {m["name"] for m in b["per_layer"]
              if cell in m.get("workloads", [])}
    assert {NAME, TWIN, "moe_dropped_pairs.train"} <= listed
    for w in entry["workloads"]:            # no cell without an expert layer
        assert not w.startswith("gpt2")


def test_the_reader_divides_the_programs_two_counters():
    """A layer's counters of two steps through ``publish_stats``: the metric
    is the registry's rows of the buffers taken over its pairs held, at
    least its twin (a buffer has every row the products ran)."""
    from benchmark.readers import obs_counter
    from deeplearning4j_tpu.nn.layers import SparseMoE
    from deeplearning4j_tpu.nn.layers.moe import _MOE_STATS

    layer = SparseMoE(n_experts=16, top_k=2, expert_width=8, n_held=4)
    for pairs, computed, buffer in ((200.0, 512.0, 768.0),
                                    (90.0, 512.0, 640.0)):
        step = dict.fromkeys(_MOE_STATS, 0.0)
        step.update(pairs_held=pairs, rows_computed=computed,
                    rows_buffer=buffer)
        layer.publish_stats(35, np.array([step[k] for k in _MOE_STATS],
                                         np.float32))
    rows = obs_counter._total("dl4j_moe_rows_buffer_total")
    pairs = obs_counter._total("dl4j_moe_pairs_held_total")
    assert rows >= 768.0 + 640.0 and pairs >= 290.0
    got = obs_counter.read(spec.load_json("metrics", NAME), {})
    assert got == rows / pairs
    assert got >= obs_counter.read(spec.load_json("metrics", TWIN), {}) >= 1.0


def test_a_program_without_the_counter_gives_nothing(monkeypatch):
    """The parent commit's program registers no ``dl4j_moe_rows_buffer_total``:
    the reader returns nothing and the line leaves the metric out."""
    from benchmark.readers import obs_counter

    f = spec.load_json("metrics", NAME)
    total = obs_counter._total
    monkeypatch.setattr(obs_counter, "_total", lambda name: (
        None if name == f["counter"] else total(name)))
    assert obs_counter.read(f, {}) is None
