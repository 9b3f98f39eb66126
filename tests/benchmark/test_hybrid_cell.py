"""CPU tests of what PR 28 added to the benchmark: the ``nemotron_h`` family's
configuration, reference, shapes and cell files resolve and count what they
say; a toy hybrid cell goes through ``run_cell`` (the sound program
``correct``, the ``bfloat16`` control and the half-batch fault not); the
``obs_counter`` reader reads the program's registry and returns nothing where
a counter is not there.
"""

from __future__ import annotations

import json
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

CELL = "twotower-s16-f32-train-b1-t4096"
CONFIG = "nemotron-twotower-30b-s16-f32"


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        for line in f:
            if "Nemotron-Labs-TwoTower-30B-A3B" in line:
                return json.loads(line)
    pytest.skip("the catalog has no such row")


def test_the_cut_keeps_every_published_width():
    """Every number of the catalog's config is in the file under its key and
    equal, but for the keys ``reduced`` lists; no width is among those; the
    file states the published counts, the deployment and its assumptions."""
    cfg = spec.load_json("configs", CONFIG)
    assert set(cfg["reduced"]) == {"num_hidden_layers", "hybrid_override_pattern",
                                   "n_routed_experts", "vocab_size"}
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["mamba_head_dim"],
            cfg["ssm_state_size"], cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["router_experts"]) == \
        (2688, 128, 64, 128, 1856, 3712, 6, 128)
    assert cfg["published"]["n_routed_experts"] == 128
    assert cfg["published"]["vocab_size"] == 131072
    assert cfg["published"]["num_hidden_layers"] == 52
    assert "16 chips share each layer" in cfg["deployment"]
    assert {"positional_encoding", "dtype", "updater", "router_bias"} <= set(cfg["assumed"])
    assert any("denoiser" in d for d in cfg["departures"])
    entry = next(c for c in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
                 ["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    row = _catalog_row()
    assert cfg["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k in cfg["reduced"]:
            continue
        assert cfg[k] == v, k


def test_the_pattern_is_the_first_nine_layers_and_the_count_is_stated():
    from benchmark.reference import nemotron_h as ref

    cfg = spec.load_json("configs", CONFIG)
    pat = cfg["hybrid_override_pattern"]
    assert pat == "MEMEM*EME" == cfg["published"]["hybrid_override_pattern"][:9]
    assert len(pat) == cfg["n_layer"] == cfg["num_hidden_layers"] == 9
    assert (pat.count("M"), pat.count("E"), pat.count("*")) == (4, 4, 1)
    whole = cfg["published"]["hybrid_override_pattern"]
    assert (len(whole), whole.count("M"), whole.count("E"), whole.count("*")) == \
        (52, 23, 23, 6)
    assert ref.num_params(cfg) == cfg["parameters_as_built"] == 666_962_944
    by_kind = {c: ref.num_params(dict(cfg, hybrid_override_pattern=c))
               - 2 * 16384 * 2688 - 2688 for c in "M*E"}     # each with its pre-norm
    assert by_kind == {"M": 38_744_896, "*": 23_399_040,
                       "E": 20_299_776 + 2688 + 8 * 9_977_856}
    assert ref.num_params(dict(cfg, hybrid_override_pattern=whole,
                               n_routed_experts=128, vocab_size=131072)) == \
        31_577_937_344
    assert ref.kept_layers(cfg, (0, 4, 8)) == (0, 4, 5, 8)
    assert [pat[i] for i in ref.kept_layers(cfg, (0, 4, 8))] == ["M", "M", "*", "E"]


def test_shape_functions_of_the_hybrid_family():
    from benchmark.shapes import nemotron_h as shapes

    cfg = spec.load_json("configs", CONFIG)
    per = shapes.layer_matmul_params(cfg)
    assert per["M"] == 2688 * 10304 + 4096 * 2688
    assert per["*"] == 2 * 2688 * 4096 + 2 * 2688 * 256
    assert per["E"] == pytest.approx(
        2688 * 128 + 2 * 2688 * 3712 + 0.375 * 2 * 2688 * 1856)
    f = shapes.train_flops_per_token(cfg, 4096)
    params = 4 * per["M"] + per["*"] + 4 * per["E"] + 2688 * 16384
    assert f == pytest.approx(6 * params + 3 * 4 * 4096 * 4096 / 2
                              + 3 * 4 * 4 * 64 * 64 * 128)
    assert 1.9e9 < f < 2.2e9
    facts = {"batch": 1, "seq_len": 4096}
    fwd, bwd = shapes.flash_fwd(cfg, facts), shapes.flash_bwd(cfg, facts)
    assert fwd["flops"] == 4 * 32 * 4096 ** 2 * 128 / 2 and bwd["flops"] == 2 * fwd["flops"]
    # q and o at 32 heads, k and v at 2
    assert fwd["bytes"] == 2 * 4096 * 34 * 128 * 4 + 4 * 32 * 4096
    assert shapes.least_seconds(fwd, {"bf16_flops_per_s": 197e12,
                                      "hbm_bytes_per_s": 819e9})[1] == "flops"


def test_the_new_cell_and_its_metrics_are_entered():
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    w = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, "b1-t4096", 1)
    cell = spec.load_cell(CELL)
    assert (cell["traffic"]["batch"], cell["traffic"]["seq_len"],
            cell["traffic"]["pool"], cell["driver"]) == (1, 4096, 8, "train")
    listed = {m["name"] for m in b["end_to_end"] + b["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {"train_tokens_per_s", "flash_fwd_roofline",
                      "flash_bwd_roofline", "moe_pairs_per_held_expert.train",
                      "moe_load_max_over_mean.train", "moe_dropped_pairs.train"}
    for name in ("moe_pairs_per_held_expert.train", "moe_load_max_over_mean.train",
                 "moe_dropped_pairs.train"):
        f = spec.load_json("metrics", name)
        assert f["reader"] == "obs_counter" and "workloads" not in f


def test_obs_counter_reads_the_registry_and_nothing_where_nothing_is():
    from benchmark.readers import obs_counter
    from deeplearning4j_tpu import obs

    assert obs_counter.read({"counter": "dl4j_no_such_counter_total"}, {}) is None
    # reading creates nothing
    assert "dl4j_no_such_counter_total" not in {
        f.name for f in obs.registry().families()}
    c = obs.counter("dl4j_test_hybrid_a_total", "", ("layer",))
    d = obs.counter("dl4j_test_hybrid_b_total", "", ("layer",))
    assert obs_counter.read({"counter": "dl4j_test_hybrid_a_total"}, {}) is None
    c.inc(6, layer="2"), c.inc(4, layer="4"), d.inc(4, layer="2")
    assert obs_counter.read({"counter": "dl4j_test_hybrid_a_total"}, {}) == 10.0
    assert obs_counter.read({"counter": "dl4j_test_hybrid_a_total",
                             "per": "dl4j_test_hybrid_b_total"}, {}) == 2.5
    assert obs_counter.read({"counter": "dl4j_test_hybrid_a_total",
                             "per": "dl4j_no_such_counter_total"}, {}) is None


# ---------------------------------------------------------------------------
# A whole run of the toy hybrid cell on the CPU, through run_cell
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fault,controls", [
    (None, ("bfloat16", "fault:half_batch")),
    (_half_batch, ()),
])
def test_tiny_hybrid_cell_is_correct_and_control_and_fault_are_not(
        on_cpu, monkeypatch, fault, controls):
    """fit -> mln.step on the 9-layer toy pattern agrees with the plain
    reference through the driver's own path; the reference in bfloat16 and
    the reference with half of every batch left out, put in the program's
    place, do not; nor does the program with half of its batch cut away."""
    if fault is not None:
        fault(monkeypatch)
    line = on_cpu.run_cell("tiny-hybrid-train", 3_000_000_019, 0.5, False,
                           roots=[DATA], controls=controls)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    for rec in line["compared"].values():
        assert rec["limit"] is not None
    if fault is None:
        assert line["correct"] is True, line["compared"]
        for c in controls:
            assert line["controls"][c]["correct"] is False, c
        # one layer of each kind is compared whole
        leaves = {k.split(".")[0] for k in _kept_names()}
        assert {"m_in", "a_q", "e_w1", "normf"} <= leaves
    else:
        assert line["correct"] is False, line["compared"]


def _kept_names():
    from benchmark.reference import nemotron_h as ref

    return ref.kept_names(spec.load_json("configs", "tiny-hybrid", [DATA]),
                          (0, 4, 8))


def test_traced_line_of_the_toy_cell_carries_the_expert_counters(on_cpu, monkeypatch):
    """The metric files' readers, fed as ``run_cell`` feeds them, give the
    three expert metrics in the hybrid cell and nothing in a GPT-2 cell's
    process that ran no expert layer (their counters are not registered)."""
    from benchmark.readers import obs_counter

    line = on_cpu.run_cell("tiny-hybrid-train", 3_000_000_021, 0.3, False,
                           roots=[DATA])
    assert line["correct"] is True
    got = {name: obs_counter.read(spec.load_json("metrics", name), {})
           for name in ("moe_pairs_per_held_expert.train",
                        "moe_load_max_over_mean.train",
                        "moe_dropped_pairs.train")}
    # 4 rows x 32 tokens x 3 experts a token x 4 held of 16, over 4 experts
    assert 15 < got["moe_pairs_per_held_expert.train"] < 35
    assert 1.0 <= got["moe_load_max_over_mean.train"] < 2.5
    assert got["moe_dropped_pairs.train"] == 0.0
