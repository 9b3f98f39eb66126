"""CPU tests of what PR 32 added to the benchmark: the ``deepseek_mla``
family's configuration, reference, shapes and cell files resolve and count
what they say; a toy latent-attention cell with its MTP head goes through
``run_cell`` (the sound program ``correct``, the ``bfloat16`` control and the
half-batch fault not); the new roofline files' patterns find the new kernels'
events and nothing else.
"""

from __future__ import annotations

import json
import math
import os
import re
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

CELL = "joyai-flash-s16-f32-train-b1-t8192"
CONFIG = "joyai-llm-flash-s16-f32"


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        for line in f:
            if '"JoyAI-LLM-Flash"' in line:
                return json.loads(line)
    pytest.skip("the catalog has no such row")


def test_the_cut_keeps_every_published_width():
    """Every key of the catalog's config is in the file and equal, but for
    the three ``reduced`` lists; no width is among those; the file states the
    published counts, the deployment and its assumptions."""
    cfg = spec.load_json("configs", CONFIG)
    assert set(cfg["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                   "vocab_size"}
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["q_lora_rank"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["router_experts"]) == \
        (2048, 32, 1536, 512, 128, 64, 128, 7168, 768, 8, 256)
    assert (cfg["num_hidden_layers"], cfg["n_layer"], cfg["n_routed_experts"],
            cfg["held_experts_start"], cfg["vocab_size"]) == (5, 5, 16, 0, 16160)
    assert cfg["published"]["n_routed_experts"] == 256
    assert cfg["published"]["vocab_size"] == 129280 == 8 * cfg["vocab_size"]
    assert cfg["published"]["num_hidden_layers"] == 40
    assert "16 chips share each layer" in cfg["deployment"]
    assert {"equations", "head_dim", "dtype", "updater", "mtp_loss_weight",
            "mtp_merge_order", "router_bias", "recompute_layers",
            "initialisation"} <= set(cfg["assumed"])
    assert any("sum over positions" in d for d in cfg["departures"])
    entry = next(c for c in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
                 ["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    row = _catalog_row()
    assert cfg["source"] == row["source_url"]
    for k, v in row["config"].items():
        assert k in cfg, k
        if k not in cfg["reduced"]:
            assert cfg[k] == v, k


def test_parameter_counts_of_the_cut_and_of_the_whole_model():
    from benchmark.reference import deepseek_mla as ref

    cfg = spec.load_json("configs", CONFIG)
    assert ref.kinds(cfg) == ("dense",) + ("expert",) * 4
    assert ref.num_params(cfg) == cfg["parameters_as_built"] == 680_439_808
    shapes = ref.weight_shapes(cfg)
    count = lambda names: sum(math.prod(shapes[n][0]) for n in names)  # noqa: E731
    attn = [f"{k}.0" for k in ref.ATTN if k != "norm1"]
    assert count(attn) == 26_347_520
    assert count(f"{k}.0" for k in ref.LEAVES["dense"]) == 70_391_808
    expert = [f"{k}.1" for k in ref.LEAVES["expert"]]
    assert count(expert) == 107_091_968
    assert count(["e_gate.1"]) == 16 * 4_718_592 / 3
    assert count(ref.MTP_OWN) + count(
        f"{k}.mtp" for k in ref.LEAVES["expert"]) == 115_486_720
    whole = dict(cfg, num_hidden_layers=40, n_routed_experts=256,
                 vocab_size=129280)
    assert ref.num_params(whole) == 50_190_481_408
    assert ref.num_params(dict(whole, num_nextn_predict_layers=0)) == \
        48_942_532_608
    assert str(ref.num_params(whole)) in cfg["parameters_published"]
    # the dense layer, the middle and the last expert layer and the MTP
    # module are compared whole
    kept = {k.split(".", 1)[-1] if "." in k else k
            for k in ref.kept_names(cfg, (0, 2, 4))}
    assert {"0", "2", "4", "mtp", "normf", "mtp_eh"} <= kept and "1" not in kept


def test_shape_functions_of_the_latent_attention_family():
    from benchmark.shapes import deepseek_mla as shapes

    cfg = spec.load_json("configs", CONFIG)
    per = shapes.layer_matmul_params(cfg)
    attn = 26_347_520 - 1536 - 512
    assert per["attention"] == attn
    assert per["dense"] == attn + 3 * 2048 * 7168
    assert per["expert"] == pytest.approx(
        attn + 2048 * 256 + 3 * 2048 * 768 + 0.5 * 3 * 2048 * 768)
    f = shapes.train_flops_per_token(cfg, 8192)
    params = (per["dense"] + 4 * per["expert"] + 2048 * 16160
              + 2 * 2048 * 2048 + per["expert"] + 2048 * 16160)
    assert f == pytest.approx(6 * params + 3 * 6 * 8192 * 32 * 320)
    assert 3.2e9 < f < 3.6e9
    assert 0.4 < 3 * 6 * 8192 * 32 * 320 / f < 0.5      # attention's share
    facts = {"batch": 1, "seq_len": 8192}
    fwd, bwd = shapes.mla_flash_fwd(cfg, facts), shapes.mla_flash_bwd(cfg, facts)
    assert fwd["flops"] == 32 * 8192 ** 2 * 320 and bwd["flops"] == 2 * fwd["flops"]
    # q, per-head keys and values and the output at 32 heads; the rotary key once
    assert fwd["bytes"] == 4 * 8192 * (32 * 192 + 32 * 256 + 64 + 32 * 128) \
        + 4 * 32 * 8192
    assert bwd["bytes"] == 2 * (fwd["bytes"] - 4 * 32 * 8192) + 8 * 32 * 8192
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert shapes.least_seconds(fwd, peaks)[1] == "flops"
    # the accepted flash rooflines find nothing to read in this family
    assert not hasattr(shapes, "flash_fwd") and not hasattr(shapes, "flash_bwd")


def test_the_new_cell_and_its_metrics_are_entered():
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    w = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, "b1-t8192", 1)
    assert len(w["why"]) <= 200 and "1/16" in w["why"] and "idle" in w["why"]
    cell = spec.load_cell(CELL)
    assert (cell["traffic"]["batch"], cell["traffic"]["seq_len"],
            cell["traffic"]["pool"], cell["driver"]) == (1, 8192, 8, "train")
    assert set(cell["check"]["limits"]) == {
        "loss_gap_step1", "loss_gap_step2", "loss_gap_step3", "grad_norm_gap",
        "grad_diff_norm", "change_norm_gap"}
    listed = {m["name"] for m in b["end_to_end"] + b["per_layer"]
              if CELL in m.get("workloads", [])}
    # at least these: a later PR may list the cell under a metric of its own
    assert listed >= {"train_tokens_per_s", "mla_flash_fwd_roofline",
                      "mla_flash_bwd_roofline", "moe_pairs_per_held_expert.train",
                      "moe_load_max_over_mean.train", "moe_dropped_pairs.train",
                      "moe_rows_computed_per_pair.train"}
    for name in ("mla_flash_fwd_roofline", "mla_flash_bwd_roofline"):
        f = spec.load_json("metrics", name)
        entry = next(m for m in b["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"]
        assert (f["reader"], f["layer"], f["moves"]) == (
            "trace_kernel_roofline", entry["layer"], entry["moves"])


# the events of a trace as benchmark/harness/trace.py names them: the HLO
# instruction's name, the call target, the result types
EVENTS = {
    "mla_flash_fwd_h2_q512_k512.3 tpu_custom_call "
    "(f32[1,8192,4096], f32[32,1,8192])": "mla_flash_fwd_roofline",
    "mla_flash_bwd_dq_h2_q512_k512.1 tpu_custom_call "
    "(f32[1,8192,4096], f32[1,8192,2048])": "mla_flash_bwd_roofline",
    "mla_flash_bwd_dkv_h2_q512_k512.1 tpu_custom_call "
    "(f32[1,8192,8192], f32[1,8192,2048])": "mla_flash_bwd_roofline",
    "flash_fwd_h1_q512_k512.2 tpu_custom_call "
    "(f32[1,4096,4096], f32[32,1,4096])": None,
    "flash_bwd_dq_h1_q512_k512.2 tpu_custom_call f32[1,4096,4096]": None,
    "moe_gmm_fwd_m128_n1536.4 tpu_custom_call f32[4096,1536]": None,
    "fusion.12": None,
}


@pytest.mark.parametrize("event,metric", sorted(EVENTS.items()))
def test_the_new_patterns_find_the_new_kernels_and_them_alone(event, metric):
    hits = [name for name in ("mla_flash_fwd_roofline", "mla_flash_bwd_roofline")
            if any(re.search(p, event)
                   for p in spec.load_json("metrics", name)["patterns"])]
    assert hits == ([metric] if metric else [])


def test_the_roofline_reader_reads_the_new_kernels_and_not_the_old_files(monkeypatch):
    """Fed a trace summary as ``run_cell`` feeds it: the two new files give
    a share of the roofline between 0 and 100, and the accepted flash files,
    whose patterns would match the forward's result shapes, give nothing:
    the family's shapes module has no function for them."""
    from benchmark.readers import trace_kernel_roofline as reader

    cell = spec.load_cell(CELL)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ops = {k: [0.0, s, n] for k, s, n in (
        (list(EVENTS)[0], 12 * 7e-3, 12), (list(EVENTS)[1], 6 * 8e-3, 6),
        (list(EVENTS)[2], 6 * 12e-3, 6))}
    facts = {"trace": {"ops": ops}, "cell": cell, "peaks": peaks,
             "batch": 1, "seq_len": 8192}
    got = {n: reader.read(dict(spec.load_json("metrics", n), name=n), facts)
           for n in ("mla_flash_fwd_roofline", "mla_flash_bwd_roofline",
                     "flash_fwd_roofline", "flash_bwd_roofline")}
    assert got["mla_flash_fwd_roofline"] == pytest.approx(
        100 * (32 * 8192 ** 2 * 320 / 197e12) / 7e-3)
    assert got["mla_flash_bwd_roofline"] == pytest.approx(
        100 * (2 * 32 * 8192 ** 2 * 320 / 197e12) / 20e-3)
    assert got["flash_fwd_roofline"] is None and got["flash_bwd_roofline"] is None


# ---------------------------------------------------------------------------
# A whole run of the toy cell on the CPU, through run_cell
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fault,controls", [
    (None, ("bfloat16", "fault:half_batch")),
    (_half_batch, ()),
])
def test_tiny_mla_cell_is_correct_and_control_and_fault_are_not(
        on_cpu, monkeypatch, fault, controls):
    """fit -> mln.step on the 3-layer toy stack with its MTP head agrees
    with the plain reference through the driver's own path; the reference in
    bfloat16 and the reference with half of every batch left out, put in the
    program's place, do not; nor does the program with half of its batch cut
    away."""
    if fault is not None:
        fault(monkeypatch)
    line = on_cpu.run_cell("tiny-mla-train", 3_000_000_019, 0.5, False,
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


def test_the_toy_cells_counters_reach_the_metric_files(on_cpu):
    """The expert metrics read the same counters here as in the hybrid cell
    (the MTP module's expert layer among them), and the two loss terms are
    counters of their own."""
    from benchmark.readers import obs_counter

    line = on_cpu.run_cell("tiny-mla-train", 3_000_000_021, 0.3, False,
                           roots=[DATA])
    assert line["correct"] is True
    got = {name: obs_counter.read(spec.load_json("metrics", name), {})
           for name in ("moe_pairs_per_held_expert.train",
                        "moe_load_max_over_mean.train",
                        "moe_dropped_pairs.train",
                        "moe_rows_computed_per_pair.train")}
    assert got["moe_pairs_per_held_expert.train"] > 0
    assert 1.0 <= got["moe_load_max_over_mean.train"]
    assert got["moe_dropped_pairs.train"] == 0.0
    assert got["moe_rows_computed_per_pair.train"] >= 1.0
    main = obs_counter.read({"counter": "dl4j_main_loss_total",
                             "per": "dl4j_mtp_loss_total"}, {})
    assert 0.8 < main < 1.25            # random weights: both about T ln V
