"""CPU tests of what PR 34 added to the benchmark: the ``lfm2_moe`` family's
configuration, reference, shapes and cell files resolve and count what they
say; a toy gated-short-convolution expert cell with its tied head goes
through ``run_cell`` (the sound program ``correct``, the ``bfloat16`` control
and the half-batch fault not); the accepted flash rooflines read the cell
through the family's shape functions.
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

CELL = "lfm2-8b-s4-f32-train-b2-t8192"
CONFIG = "lfm2-8b-a1b-s4-f32"
REDUCED = {"num_hidden_layers", "layer_types", "num_dense_layers",
           "num_experts", "vocab_size"}
# the ten lists of BENCHMARK.json the cell stands in
LISTS = {"train_tokens_per_s", "flash_fwd_roofline", "flash_bwd_roofline",
         "feed_ms_p50.train", "loss_fetch_ms_p50.train",
         "dispatch_ms_p50.train", "moe_pairs_per_held_expert.train",
         "moe_load_max_over_mean.train", "moe_dropped_pairs.train",
         "moe_rows_computed_per_pair.train"}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        for line in f:
            if '"LFM2-8B-A1B"' in line:
                return json.loads(line)
    pytest.skip("the catalog has no such row")


def _whole(cfg: dict) -> dict:
    """The configuration with the published counts in place of the cut's."""
    pub = cfg["published"]
    return dict(cfg, **{k: pub[k] for k in REDUCED})


def test_the_cut_keeps_every_published_width():
    """Every key of the catalog's config is in the file and equal, but for
    the ``reduced`` counts; no width is among those; the file states the
    published counts, the deployment and its assumptions."""
    cfg = spec.load_json("configs", CONFIG)
    assert set(cfg["reduced"]) == REDUCED
    # counts alone: no hidden, intermediate or head size, no key that ends
    # in _dim or _rank, not the experts a token
    width = ("hidden_size", "intermediate_size", "_dim", "_rank", "head",
             "per_tok", "L_cache", "theta")
    assert not [k for k in cfg["reduced"] if any(w in k for w in width)]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["router_experts"],
            cfg["conv_L_cache"], cfg["rope_theta"], cfg["norm_eps"]) == \
        (2048, 32, 8, 64, 7168, 1792, 4, 32, 3, 1_000_000, 1e-5)
    assert (cfg["num_hidden_layers"], cfg["n_layer"], cfg["num_dense_layers"],
            cfg["num_experts"], cfg["held_experts_start"],
            cfg["vocab_size"]) == (5, 5, 1, 8, 0, 16384)
    assert cfg["layer_types"] == ["conv", "full_attention", "conv", "conv", "conv"]
    pub = cfg["published"]
    assert pub["num_experts"] == 32 == 4 * cfg["num_experts"]
    assert pub["vocab_size"] == 65536 == 4 * cfg["vocab_size"]
    assert (pub["num_hidden_layers"], pub["num_dense_layers"]) == (24, 2)
    assert "4 chips share each layer" in cfg["deployment"]
    assert {"head_dim", "tie_word_embeddings", "rope_pairing", "route_eps",
            "router_bias", "dtype", "updater", "recompute_layers",
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
    assert pub["layer_types"] == row["config"]["layer_types"]
    # the held layers are published layers 0 and 2-5
    assert cfg["layer_types"] == [pub["layer_types"][i] for i in (0, 2, 3, 4, 5)]


def test_parameter_counts_of_the_cut_and_of_the_whole_model():
    from benchmark.reference import lfm2_moe as ref

    cfg = spec.load_json("configs", CONFIG)
    assert ref.kinds(cfg) == (("conv", "dense"), ("full_attention", "expert")) \
        + (("conv", "expert"),) * 3
    assert ref.num_params(cfg) == cfg["parameters_as_built"] == 507_820_160
    shapes = ref.weight_shapes(cfg)
    count = lambda names: sum(math.prod(shapes[n][0]) for n in names)  # noqa: E731
    layer = lambda i: [f"{k}.{i}" for k in ref.layer_leaves(ref.kinds(cfg)[i])]  # noqa: E731
    assert count(f"{k}.0" for k in ref.OPS["conv"]) == 16_783_360
    assert count(f"{k}.1" for k in ref.OPS["full_attention"]) == 10_485_888
    assert count(f"{k}.0" for k in ref.FFNS["dense"]) == 44_040_192
    assert count(["e_gate.1", "e_up.1", "e_down.1"]) == 8 * 11_010_048
    assert (count(layer(0)), count(layer(1)), count(layer(2))) == \
        (60_827_648, 98_635_904, 104_933_376)
    assert count(["wte"]) == 33_554_432 and "w_head" not in shapes
    whole = _whole(cfg)
    assert ref.num_params(whole) == 8_339_929_856
    assert str(ref.num_params(whole)) in cfg["parameters_published"]
    # untied, the head's matrix would count again: 8.47B where the source
    # says 8.3B
    assert ref.num_params(whole) + 65536 * 2048 == 8_474_147_584
    # the dense layer, the middle and the last expert layer, the attention
    # layer, the embedding and the final gain are compared whole
    kept = ref.kept_names(cfg, (0, 2, 4))
    assert {"wte", "normf", "c_conv.0", "a_qnorm.1", "e_gate.2", "e_down.4"} \
        <= set(kept) and not [k for k in kept if k.endswith(".3")]


def test_shape_functions_of_the_short_convolution_family():
    from benchmark.shapes import lfm2_moe as shapes

    cfg = spec.load_json("configs", CONFIG)
    per = shapes.matmul_params(cfg)
    assert per["conv"] == 16_783_360 - 3 * 2048
    assert per["full_attention"] == 10_485_888 - 128
    assert per["dense"] == 44_040_192
    assert per["expert"] == 65_536 + 1.0 * 11_010_048   # 4 x 8 / 32 experts a token
    T = 8192
    f = shapes.train_flops_per_token(cfg, T)
    params = (4 * per["conv"] + per["full_attention"] + per["dense"]
              + 4 * per["expert"] + 2048 * 16384)
    square = 3 * 2 * T * 32 * 64
    taps = 3 * 4 * (2 * 3 + 2) * 2048
    assert f == pytest.approx(6 * params + square + taps)
    assert 1.25e9 < f < 1.35e9
    # forward shares by mechanism, as the cell's account gives them
    fwd = f / 3
    share = lambda x: x / fwd                                   # noqa: E731
    assert 0.30 < share(2 * 4 * per["conv"]) < 0.32
    assert 0.19 < share(2 * 4 * per["expert"]) < 0.22
    assert 0.19 < share(2 * per["dense"]) < 0.22
    assert 0.14 < share(2 * 2048 * 16384) < 0.17
    assert 0.11 < share(2 * per["full_attention"] + square / 3) < 0.14
    facts = {"batch": 2, "seq_len": T}
    fw, bw = shapes.flash_fwd(cfg, facts), shapes.flash_bwd(cfg, facts)
    assert fw["flops"] == 2 * 2 * 32 * T * T * 64 and bw["flops"] == 2 * fw["flops"]
    assert fw["bytes"] == 2 * 2 * T * 40 * 64 * 4 + 4 * 2 * 32 * T
    assert bw["bytes"] == 4 * 2 * T * 40 * 64 * 4 + 8 * 2 * 32 * T
    assert shapes.least_seconds(fw, PEAKS)[1] == "flops"
    assert shapes.least_seconds(bw, PEAKS)[1] == "flops"
    # the whole model counts the same way (22 expert layers at 4 experts a token)
    whole = shapes.train_flops_per_token(_whole(cfg), T)
    assert 8.5e9 < whole < 10.5e9


def test_the_new_cell_and_its_files_are_entered():
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    w = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, "b2-t8192", 1)
    assert len(w["why"]) <= 200 and "1/4" in w["why"] and "idle" in w["why"]
    cell = spec.load_cell(CELL)
    assert cell["why"] == w["why"]
    assert (cell["traffic"]["kind"], cell["traffic"]["batch"],
            cell["traffic"]["seq_len"], cell["traffic"]["pool"],
            cell["driver"]) == ("lm_batches", 2, 8192, 8, "train")
    assert cell["check"]["reference_rows"] == 2
    assert set(cell["check"]["limits"]) >= {
        "loss_gap_step1", "loss_gap_step2", "loss_gap_step3", "grad_norm_gap",
        "grad_diff_norm", "change_norm_gap"}
    listed = {m["name"] for m in b["end_to_end"] + b["per_layer"]
              if CELL in m.get("workloads", [])}
    # at least these: a later PR may list the cell under a metric of its own
    assert listed >= LISTS
    # the latent-attention kernels' rooflines are not this cell's
    assert not {"mla_flash_fwd_roofline", "mla_flash_bwd_roofline"} & listed


# the events of a trace as benchmark/harness/trace.py names them: the HLO
# instruction's name, the call target, the result types
EVENTS = {
    "flash_fwd_h2_q512_k512.3 tpu_custom_call "
    "(f32[2,8192,2048], f32[64,1,8192])": "flash_fwd_roofline",
    "flash_bwd_dq_h2_q512_k512.1 tpu_custom_call f32[2,8192,2048]":
        "flash_bwd_roofline",
    "flash_bwd_dkv_h2_q512_k512.1 tpu_custom_call "
    "(f32[2,8192,2048], f32[2,8192,2048])": "flash_bwd_roofline",
    "moe_gmm_fwd_m128_n1792.4 tpu_custom_call f32[32768,3584]": None,
    "moe_gmm_dw_m128_k2048_n3584.4 tpu_custom_call f32[16384,3584]": None,
    "fusion.12": None,
}


@pytest.mark.parametrize("event,metric", sorted(EVENTS.items()))
def test_the_accepted_patterns_find_this_cells_attention_kernels_alone(event, metric):
    import re

    hits = [name for name in ("flash_fwd_roofline", "flash_bwd_roofline",
                              "mla_flash_fwd_roofline", "mla_flash_bwd_roofline")
            if any(re.search(p, event)
                   for p in spec.load_json("metrics", name)["patterns"])]
    assert hits == ([metric] if metric else [])


def test_the_roofline_reader_reads_this_cells_flash_calls():
    """Fed a trace summary as ``run_cell`` feeds it: one attention layer, so
    one forward, one dq and one dk/dv call a step; the share is the shape
    module's least time over the device time, under 100."""
    from benchmark.readers import trace_kernel_roofline as reader

    cell = spec.load_cell(CELL)
    names = list(EVENTS)
    ops = {k: [0.0, s, n] for k, s, n in (
        (names[0], 10 * 4e-3, 10), (names[1], 10 * 5e-3, 10),
        (names[2], 10 * 7e-3, 10))}
    facts = {"trace": {"ops": ops}, "cell": cell, "peaks": PEAKS,
             "batch": 2, "seq_len": 8192}
    got = {n: reader.read(dict(spec.load_json("metrics", n), name=n), facts)
           for n in ("flash_fwd_roofline", "flash_bwd_roofline",
                     "mla_flash_fwd_roofline", "mla_flash_bwd_roofline")}
    least = 2 * 2 * 32 * 8192 ** 2 * 64 / 197e12
    assert got["flash_fwd_roofline"] == pytest.approx(100 * least / 4e-3)
    assert got["flash_bwd_roofline"] == pytest.approx(100 * 2 * least / 12e-3)
    assert got["mla_flash_fwd_roofline"] is None
    assert got["mla_flash_bwd_roofline"] is None


# ---------------------------------------------------------------------------
# A whole run of the toy cell on the CPU, through run_cell
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fault,controls", [
    (None, ("bfloat16", "fault:half_batch")),
    (_half_batch, ()),
])
def test_tiny_lfm2_cell_is_correct_and_control_and_fault_are_not(
        on_cpu, monkeypatch, fault, controls):
    """fit -> mln.step on the 3-layer toy stack with its tied head agrees
    with the plain reference through the driver's own path (loss, first
    gradient, three Adam steps); the reference in bfloat16 and the reference
    with half of every batch left out, put in the program's place, do not;
    nor does the program with half of its batch cut away."""
    if fault is not None:
        fault(monkeypatch)
    line = on_cpu.run_cell("tiny-lfm2-train", 3_000_000_019, 0.5, False,
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
    """The expert metrics read the same counters here as in the other two
    expert cells; the toy stack has two expert layers."""
    from benchmark.readers import obs_counter

    line = on_cpu.run_cell("tiny-lfm2-train", 3_000_000_021, 0.3, False,
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


def test_the_toy_models_tree_holds_one_matrix_for_embedding_and_head(on_cpu):
    """The family fills a tree whose output layer has the final gain alone,
    and the optimizer keeps a state a layer with nothing for a head."""
    import jax

    from benchmark.families import lfm2_moe as fam
    from benchmark.reference import lfm2_moe as ref

    cfg = spec.load_json("configs", "tiny-lfm2", [DATA])
    model = fam.new_model(cfg, ref.seed_words(5))
    assert jax.tree_util.tree_structure(model.params[-1]) == \
        jax.tree_util.tree_structure({"norm": {"gamma": 0}})
    assert len(model.opt_state) == len(model.params) == 2 + 2 * 3
    assert model.num_params() == ref.num_params(cfg)
    named = fam.from_program(cfg, model.params)
    assert set(named) == set(ref.weight_shapes(cfg))
    again = fam.to_program(cfg, named)
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree_util.tree_leaves(again), jax.tree_util.tree_leaves(model.params)))
