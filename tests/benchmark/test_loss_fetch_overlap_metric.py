"""CPU tests of what PR 37 added to the benchmark, data only: the metric
``loss_fetch_overlapped_share.train`` (of the steps whose loss ``fit()``
fetched for its listener, the share fetched after the next step had been
enqueued) parses, names a reader that is there, is entered in
``BENCHMARK.json`` for the five training cells, reads the program's two
counters, and reads nothing from a program that has none.
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

NAME = "loss_fetch_overlapped_share.train"
TRAIN_CELLS = ("gpt2m-f32-train-b8-t1024", "gpt2m-f32-train-b32-t256",
               "twotower-s16-f32-train-b1-t4096",
               "joyai-flash-s16-f32-train-b1-t8192",
               "lfm2-8b-s4-f32-train-b2-t8192")


def _entry():
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return next(m for m in b["per_layer"] if m["name"] == NAME), b


def test_the_metric_file_parses_and_names_a_reader_that_is_there():
    f = spec.load_json("metrics", NAME)
    assert hasattr(spec.module("readers", f["reader"]), "read")
    assert (f["reader"], f["counter"], f["per"]) == (
        "obs_counter", "dl4j_fit_fetch_overlapped_total",
        "dl4j_fit_fetch_total")
    entry, b = _entry()
    assert (f["layer"], f["unit"], f["better"], f["moves"], f["source"]) == (
        entry["layer"], entry["unit"], entry["better"], entry["moves"],
        entry["source"]) == ("entry, training", "ratio", "higher",
                             "train_tokens_per_s", "program_counter")
    # the layer is one BENCHMARK.json names already, letter for letter
    assert any(m["layer"] == entry["layer"] for m in b["per_layer"]
               if m["name"] != NAME)
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert any(m["name"] == NAME for m in spec.metrics_for(
        {"train_tokens_per_s", "setup_s"}))


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_the_metric_lists_every_training_cell(cell):
    entry, b = _entry()
    # membership, not position: a later PR's cell or metric may be appended
    assert cell in entry["workloads"]
    assert cell in {w["name"] for w in b["workloads"]}
    e2e = next(m for m in b["end_to_end"] if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(e2e["workloads"])


def test_the_reader_divides_the_programs_two_counters(monkeypatch):
    """A toy ``fit()`` of one batch and one of five, with a score-only
    listener: of the six fetches, the four that were not a call's last lay
    behind a next step."""
    from benchmark.readers import obs_counter
    from deeplearning4j_tpu import obs
    from deeplearning4j_tpu.nn.input_type import InputType
    from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
    from deeplearning4j_tpu.nn.model import (
        MultiLayerConfiguration, MultiLayerNetwork)

    class Listener:
        def on_epoch_start(self, *a): pass
        def on_epoch_end(self, *a): pass
        def iteration_done(self, *a, **kw): pass

    metric = spec.load_json("metrics", NAME)
    obs.reset()
    assert obs_counter.read(metric, {}) is None     # nothing fetched yet
    model = MultiLayerNetwork(MultiLayerConfiguration(
        layers=(Dense(n_out=8, activation="tanh"),
                OutputLayer(n_out=2, activation="softmax")),
        input_type=InputType.feed_forward(4),
        updater={"type": "sgd", "lr": 0.05})).init(seed=0)
    model.set_listeners(Listener())
    rs = np.random.RandomState(0)
    x = rs.randn(40, 4).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rs.randint(0, 2, 40)]
    monkeypatch.setenv("DL4J_TPU_CHAIN_STEPS", "0")
    model.fit((x[:8], y[:8]))                       # as set-up's calls do
    # one fetch, with no next step behind it: the share's counter has no
    # series yet, which reads as nothing and never as 0
    assert obs_counter.read(metric, {}) is None
    model.fit((x, y), batch_size=8)
    assert obs_counter.read(metric, {}) == pytest.approx(4 / 6)


def test_a_program_without_the_counters_reads_nothing(monkeypatch):
    """The parent's program registers neither counter: the reader returns
    nothing and does not raise, and the line leaves the metric out."""
    from benchmark.readers import obs_counter
    from deeplearning4j_tpu import obs

    metric = spec.load_json("metrics", NAME)
    kept = [f for f in obs.registry().families()
            if not f.name.startswith("dl4j_fit_fetch")]
    monkeypatch.setattr(type(obs.registry()), "families",
                        lambda self: kept)
    assert obs_counter.read(metric, {}) is None
