"""CPU tests of the per-layer metric ``flash_bwd_dqkv_roofline``: the one flash
backward kernel's share of its roofline, as a metric file
``benchmark/metrics/flash_bwd_dqkv_roofline.json`` would hold it (``METRIC``).
It names a reader and a shape function that every family of the five cells
that run the kernel has, those cells report the end-to-end metric it moves,
it reads a synthetic trace with one ``flash_bwd_dqkv_…`` event a call below
100% at each cell's shape, and it reads nothing from a trace of the split
dq + dk/dv pair.
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
from benchmark.readers import trace_kernel_roofline as reader  # noqa: E402

NAME = "flash_bwd_dqkv_roofline"
# ops/flash_attention.py names the kernel flash_bwd_dqkv_<heads>_q<bq>_k<bk>
# and a trace event begins with that name; one event is one call, which gives
# dq, dk and dv together
METRIC = {"layer": "kernels", "unit": "%", "better": "higher",
          "source": "device_trace", "moves": "train_tokens_per_s",
          "reader": "trace_kernel_roofline", "patterns": ["^flash_bwd_dqkv_"],
          "events_per_call": 1, "shape_fn": "flash_bwd"}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# cell -> (the kernel's name as the chooser makes it at the cell's call, its
# result type, the calls a step, the fastest time a call the one kernel was
# predicted to take: 1.10 x the split dk/dv kernel's device time a call in
# the cell's breakdown, v5e)
CELLS = {
    "gpt2m-f32-train-b8-t1024": (
        "flash_bwd_dqkv_h2_q512_k1024", "f32[8,1024,1024]", 24, 1.10 * 0.7375e-3),
    "gpt2m-f32-train-b32-t256": (
        "flash_bwd_dqkv_h2_q256_k256", "f32[32,256,1024]", 24, 1.10 * 0.4125e-3),
    "twotower-s16-f32-train-b1-t4096": (
        "flash_bwd_dqkv_h1_q512_k512", "f32[1,4096,4096]", 1, 1.10 * 2.12e-3),
    "lfm2-8b-s4-f32-train-b2-t8192": (
        "flash_bwd_dqkv_h2_q512_k512", "f32[2,8192,2048]", 1, 1.10 * 14.2e-3),
    "ouro-2.6b-p8-f32-train-b1-t4096": (
        "flash_bwd_dqkv_h1_q512_k512", "f32[1,4096,2048]", 24, 1.10 * 1.067e-3),
}


def _facts(cell_name, ops):
    cell = spec.load_cell(cell_name)
    traffic = cell["traffic"]
    return {"trace": {"ops": ops}, "cell": cell, "peaks": PEAKS,
            "batch": traffic["batch"], "seq_len": traffic["seq_len"]}


def _metric():
    return dict(METRIC, name=NAME)


def test_the_metric_names_what_is_there():
    f = METRIC
    assert spec.NAME.match(NAME) and spec.UNIT.match(f["unit"])
    assert hasattr(spec.module("readers", f["reader"]), "read")
    # the layer, unit and source of the accepted backward roofline
    old = spec.load_json("metrics", "flash_bwd_roofline")
    assert all(f[k] == old[k] for k in (
        "layer", "unit", "better", "source", "moves", "reader", "shape_fn"))
    for cell_name in CELLS:
        shapes = spec.module("shapes", spec.load_cell(cell_name)["config"]["shapes"])
        assert callable(getattr(shapes, f["shape_fn"])), cell_name


def test_the_five_cells_report_what_it_moves():
    """Each of the five cells reports ``train_tokens_per_s``, which the
    metric moves, and lists the accepted backward roofline it would succeed
    there."""
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (moved,) = [m for m in b["end_to_end"] if m["name"] == METRIC["moves"]]
    assert set(CELLS) <= set(moved["workloads"])
    old = next(m for m in b["per_layer"] if m["name"] == "flash_bwd_roofline")
    assert set(old["workloads"]) == set(CELLS)


@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_it_reads_one_event_a_call_below_100_percent(cell_name):
    kernel, result, calls, seconds = CELLS[cell_name]
    event = f"{kernel}.1 tpu_custom_call ({result}, {result}, {result})"
    steps = 10
    ops = {event: [0.0, steps * calls * seconds, steps * calls],
           "fusion.12": [0.0, 1.0, 100]}
    facts = _facts(cell_name, ops)
    got = reader.read(_metric(), facts)
    shapes = spec.module("shapes", facts["cell"]["config"]["shapes"])
    least, _ = shapes.least_seconds(
        shapes.flash_bwd(facts["cell"]["config"], facts), PEAKS)
    assert got == pytest.approx(100.0 * least / seconds)
    assert 0.0 < got < 100.0


@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_the_split_pair_and_other_kernels_read_nothing(cell_name):
    """A trace of the parent's backward (the split pair) and of every other
    Mosaic call the cells make: the metric has nothing to read."""
    kernel, result, calls, _ = CELLS[cell_name]
    tag = kernel[len("flash_bwd_dqkv"):]
    ops = {f"flash_bwd_dq{tag}.1 tpu_custom_call {result}": [0.0, 0.1, 10],
           f"flash_bwd_dkv{tag}.1 tpu_custom_call ({result}, {result})":
               [0.0, 0.1, 10],
           f"flash_fwd{tag}.3 tpu_custom_call ({result}, f32[64,1,8192])":
               [0.0, 0.1, 10],
           "mla_flash_bwd_dq_h2_q512_k512.1 tpu_custom_call "
           "(f32[1,8192,4096], f32[1,8192,2048])": [0.0, 0.1, 10],
           "moe_gmm_dw_m128_k2048_n3584.4 tpu_custom_call f32[16384,3584]":
               [0.0, 0.1, 10]}
    assert reader.read(_metric(), _facts(cell_name, ops)) is None
