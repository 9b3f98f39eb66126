"""CPU tests of what PR 38 added to the benchmark: the ``ouro`` family's
configuration, reference, shapes and cell files resolve and count what they
say; the two loop metrics are entered and read the program's counters; a toy
looped cell goes through ``run_cell`` (the sound program ``correct``, the
``bfloat16`` control and the half-batch fault not); the accepted flash
rooflines read the cell through the family's shape functions.
"""

from __future__ import annotations

import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import spec  # noqa: E402

# the fixture that lifts the no-TPU failure, and the half-batch fault, are the
# harness tests' own (pytest puts this directory on the path)
from test_benchmark_harness import _half_batch, on_cpu  # noqa: E402,F401

CELL = "ouro-2.6b-p8-f32-train-b1-t4096"
CONFIG = "ouro-2.6b-p8-f32"
LOOP_METRICS = ("loop_block_passes_per_application.train",
                "loop_head_passes_per_exit.train")
# the lists of BENCHMARK.json the cell stands in
LISTS = ("train_tokens_per_s", "flash_fwd_roofline", "flash_bwd_roofline",
         "feed_ms_p50.train", "loss_fetch_ms_p50.train",
         "dispatch_ms_p50.train", "loss_fetch_overlapped_share.train",
         "step_trace_s.setup", "step_lower_s.setup", "step_backend_s.setup",
         "step_cache_read_s.setup", "step_programs_loaded.setup") + LOOP_METRICS
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        for line in f:
            if '"Ouro-2.6B"' in line:
                return json.loads(line)
    pytest.skip("the catalog has no such row")


def test_the_cut_keeps_every_published_width():
    """Every key of the catalog's config is in the file and equal, but for
    the depth; the file states the published depth, the deployment, its
    assumptions and departures."""
    cfg = spec.load_json("configs", CONFIG)
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"], cfg["rope_theta"],
            cfg["rms_norm_eps"], cfg["total_ut_steps"],
            cfg["tie_word_embeddings"]) == \
        (2048, 16, 16, 128, 5632, 49152, 1_000_000, 1e-6, 4, False)
    assert (cfg["num_hidden_layers"], cfg["n_layer"]) == (6, 6)
    assert cfg["published"]["num_hidden_layers"] == 48
    assert "8-stage pipeline" in cfg["deployment"]
    assert {"exit_entropy_beta", "attention_bias", "qk_norm", "sandwich_norm",
            "loop_norm", "exit_gate", "rope_pairing", "loss", "dtype",
            "updater", "recompute_layers", "initialisation"} <= set(cfg["assumed"])
    assert cfg["exit_entropy_beta"] == 0.1
    assert any("embedding" in d and "head" in d for d in cfg["departures"])
    entry = next(c for c in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
                 ["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    row = _catalog_row()
    assert cfg["source"] == row["source_url"]
    for k, v in row["config"].items():
        assert k in cfg, k
        if k not in cfg["reduced"]:
            assert cfg[k] == v, k


def test_parameter_counts_of_the_stage_and_of_the_whole_model():
    from benchmark.reference import ouro as ref

    cfg = spec.load_json("configs", CONFIG)
    shapes = ref.weight_shapes(cfg)
    count = lambda names: sum(math.prod(shapes[n][0]) for n in names)  # noqa: E731
    assert count(f"{k}.0" for k in ("a_q", "a_k", "a_v", "a_o")) == 16_777_216
    assert count(f"{k}.0" for k in ("f_gate", "f_up", "f_down")) == 34_603_008
    assert count(f"norm{j}.0" for j in (1, 2, 3, 4)) == 8_192
    assert count(f"{k}.0" for k in ref.LAYER) == 51_388_416
    assert count(["wte"]) == count(["w_head"]) == 100_663_296
    assert (count(["normf"]), count(["gate_w", "gate_b"])) == (2_048, 2_049)
    assert ref.num_params(cfg) == cfg["parameters_as_built"] == 509_661_185
    whole = dict(cfg, num_hidden_layers=cfg["published"]["num_hidden_layers"])
    assert ref.num_params(whole) == 2_667_974_657
    assert str(ref.num_params(whole)) in cfg["parameters_published"]
    # 16 bytes a trained parameter: 8.15 GB held
    assert 8.1e9 < 16 * ref.num_params(cfg) < 8.2e9
    # the first, middle and last layer and every leaf outside the layers are
    # compared whole
    kept = ref.kept_names(cfg, (0, 3, 5))
    assert {"wte", "w_head", "normf", "gate_w", "gate_b", "norm4.0", "a_q.3",
            "f_down.5"} <= set(kept) and not [k for k in kept if k.endswith(".1")]


def test_the_flop_count_counts_every_loop():
    """At one loop and without the gate the count is a plain transformer's at
    the same widths (GPT-2's module, its MLP widened to the gated one's three
    products); at four it is four times the layers and four heads, and the
    three gates the loss reads."""
    from benchmark.shapes import gpt2
    from benchmark.shapes import ouro as shapes

    cfg = spec.load_json("configs", CONFIG)
    S = 4096
    one = dict(cfg, total_ut_steps=1)
    plain = {"n_embd": 2048, "n_layer": 6, "n_head": 16, "n_inner": 3 * 5632 // 2,
             "vocab_size": 49152}
    assert shapes.train_flops_per_token(one, S, gate=False) == \
        pytest.approx(gpt2.train_flops_per_token(plain, S))
    assert shapes.train_flops_per_token(one, S) == \
        shapes.train_flops_per_token(one, S, gate=False)
    layer = shapes.layer_matmul_params(cfg)
    assert layer == 51_388_416 - 8_192
    blocks = 6 * 6 * layer + 3 * 6 * (4 * S * 2048) / 2
    heads = 6 * 2048 * 49152
    gates = 6 * 2048
    assert shapes.train_flops_per_token(cfg, S) == \
        pytest.approx(4 * blocks + 4 * heads + 3 * gates)
    f = shapes.train_flops_per_token(cfg, S)
    assert 10.9e9 < f < 11.1e9                      # 11.0 GFLOP a token
    assert 0.21 < 4 * heads / f < 0.23              # the heads' share
    facts = {"tokens": 10 * S, "seq_len": S}
    assert shapes.window_flops_train(cfg, facts) == pytest.approx(10 * S * f)
    facts = {"batch": 1, "seq_len": S}
    fw, bw = shapes.flash_fwd(cfg, facts), shapes.flash_bwd(cfg, facts)
    assert fw["flops"] == 2 * 16 * S * S * 128 and bw["flops"] == 2 * fw["flops"]
    assert fw["bytes"] == 2 * S * 32 * 128 * 4 + 4 * 16 * S
    assert bw["bytes"] == 4 * S * 32 * 128 * 4 + 8 * 16 * S
    assert shapes.least_seconds(fw, PEAKS)[1] == "flops"
    assert shapes.least_seconds(bw, PEAKS)[1] == "flops"


def test_the_new_cell_and_its_files_are_entered():
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    w = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, "b1-t4096", 1)
    assert len(w["why"]) <= 200 and "4 times" in w["why"]
    cell = spec.load_cell(CELL)
    assert cell["why"] == w["why"]
    assert (cell["traffic"]["kind"], cell["traffic"]["batch"],
            cell["traffic"]["seq_len"], cell["traffic"]["pool"],
            cell["driver"]) == ("lm_batches", 1, 4096, 8, "train")
    assert cell["check"]["reference_rows"] == 1
    assert set(cell["check"]["limits"]) == {
        "loss_gap_step1", "loss_gap_step2", "loss_gap_step3", "grad_norm_gap",
        "grad_diff_norm", "change_norm_gap"}
    listed = {m["name"] for m in b["end_to_end"] + b["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed >= set(LISTS)
    # no expert layer, no latent attention: those metrics are not this cell's
    assert not [n for n in listed if n.startswith(("moe_", "mla_"))]


@pytest.mark.parametrize("name", LOOP_METRICS)
def test_the_loop_metrics_are_entered_last_and_read_the_programs_counters(name):
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    names = [m["name"] for m in b["per_layer"]]
    assert names[-2:] == list(LOOP_METRICS)
    entry = b["per_layer"][names.index(name)]
    m = spec.load_json("metrics", name)
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"], entry["workloads"]) == (
        m["unit"], m["better"], m["source"], m["layer"], m["moves"], [CELL])
    assert (m["unit"], m["better"], m["reader"]) == ("ratio", "lower", "obs_counter")
    assert m["counter"].startswith("dl4j_loop_") and m["per"].startswith("dl4j_loop_")


# the events of a trace as benchmark/harness/trace.py names them: the HLO
# instruction's name, the call target, the result types
EVENTS = {
    "flash_fwd_q512_k512.3 tpu_custom_call "
    "(f32[1,4096,2048], f32[16,1,4096])": "flash_fwd_roofline",
    "flash_bwd_dq_q512_k512.1 tpu_custom_call f32[1,4096,2048]":
        "flash_bwd_roofline",
    "flash_bwd_dkv_q512_k512.1 tpu_custom_call "
    "(f32[1,4096,2048], f32[1,4096,2048])": "flash_bwd_roofline",
    "fusion.12": None,
}


def test_the_roofline_reader_reads_this_cells_flash_calls():
    """Fed a trace summary as ``run_cell`` feeds it: 24 attention
    applications a step, so 24 forward, dq and dk/dv calls; the share is the
    shape module's least time over the device time, under 100."""
    import re

    from benchmark.readers import trace_kernel_roofline as reader

    for event, metric in EVENTS.items():
        hits = [n for n in ("flash_fwd_roofline", "flash_bwd_roofline")
                if any(re.search(p, event)
                       for p in spec.load_json("metrics", n)["patterns"])]
        assert hits == ([metric] if metric else []), event
    cell = spec.load_cell(CELL)
    names = list(EVENTS)
    ops = {k: [0.0, s, n] for k, s, n in (
        (names[0], 24 * 0.5e-3, 24), (names[1], 24 * 0.6e-3, 24),
        (names[2], 24 * 0.8e-3, 24))}
    facts = {"trace": {"ops": ops}, "cell": cell, "peaks": PEAKS,
             "batch": 1, "seq_len": 4096}
    got = {n: reader.read(dict(spec.load_json("metrics", n), name=n), facts)
           for n in ("flash_fwd_roofline", "flash_bwd_roofline")}
    least = 2 * 16 * 4096 ** 2 * 128 / 197e12
    assert got["flash_fwd_roofline"] == pytest.approx(100 * least / 0.5e-3)
    assert got["flash_bwd_roofline"] == pytest.approx(100 * 2 * least / 1.4e-3)


# ---------------------------------------------------------------------------
# A whole run of the toy cell on the CPU, through run_cell
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fault,controls", [
    (None, ("bfloat16", "fault:half_batch")),
    (_half_batch, ()),
])
def test_tiny_ouro_cell_is_correct_and_control_and_fault_are_not(
        on_cpu, monkeypatch, fault, controls):
    """fit -> mln.step on the 2-layer toy stack run 3 times agrees with the
    plain reference through the driver's own path (loss, first gradient,
    three Adam steps); the reference in bfloat16 and the reference with half
    of every batch left out, put in the program's place, do not; nor does
    the program with half of its batch cut away."""
    if fault is not None:
        fault(monkeypatch)
    line = on_cpu.run_cell("tiny-ouro-train", 3_000_000_019, 0.5, False,
                           roots=[DATA], controls=controls)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    for rec in line["compared"].values():
        assert rec["limit"] is not None
    if fault is None:
        assert line["correct"] is True, line["compared"]
        for c in controls:
            assert line["controls"][c]["correct"] is False, c
    else:
        assert line["correct"] is False, line["compared"]


def test_the_toy_cells_counters_reach_the_loop_metrics(on_cpu):
    """Every block and every loop's logits recomputed: both ratios read 2."""
    from benchmark.readers import obs_counter

    line = on_cpu.run_cell("tiny-ouro-train", 3_000_000_021, 0.3, False,
                           roots=[DATA])
    assert line["correct"] is True
    got = {name: obs_counter.read(spec.load_json("metrics", name), {})
           for name in LOOP_METRICS}
    assert got == {name: 2.0 for name in LOOP_METRICS}


def test_the_toy_models_tree_maps_to_the_references_names_and_back(on_cpu):
    import jax

    from benchmark.families import ouro as fam
    from benchmark.reference import ouro as ref

    cfg = spec.load_json("configs", "tiny-ouro", [DATA])
    model = fam.new_model(cfg, ref.seed_words(5))
    assert len(model.opt_state) == len(model.params) == 3
    assert set(model.params[1]) == {f"block{i}" for i in range(4)} | {"norm"}
    assert model.num_params() == ref.num_params(cfg)
    named = fam.from_program(cfg, model.params)
    assert set(named) == set(ref.weight_shapes(cfg))
    again = fam.to_program(cfg, named)
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree_util.tree_leaves(again), jax.tree_util.tree_leaves(model.params)))
