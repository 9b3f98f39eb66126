"""CPU tests of the benchmark's harness (benchmark/): the files resolve by
name, the yardstick's arithmetic is right, the plain reference agrees with the
program at a toy size through the drivers' own path, and ``correct`` comes out
false for the control and for each planted fault.

The toy configuration and cells live in tests/benchmark/data and are not in
BENCHMARK.json. The no-TPU failure is lifted here by monkeypatch only; no
topology is described at import.
"""

from __future__ import annotations

import json
import os
import re
import statistics

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

import sys  # noqa: E402

if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import compare, spec, trace  # noqa: E402


def _bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------


def test_names_units_and_limits_of_benchmark_json():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in b[group]:
            assert spec.NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    for m in b["end_to_end"] + b["per_layer"]:
        assert spec.UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])
    for w in b["workloads"]:
        assert spec.NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in b["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        assert c["reduced"] == []
    assert 1 <= b["run_seconds"] <= 51
    assert len(json.dumps(b)) < 64 * 1024


def test_every_cell_resolves_by_name():
    """Configuration, traffic, driver, family, reference, shapes and every
    metric's reader are found by the names the files give; each per-layer
    metric moves an end-to-end metric that each of its cells reports."""
    b = _bench()
    e2e = {m["name"]: m for m in b["end_to_end"]}
    files = {m["name"]: m for m in spec.metric_files()}
    reported = {}
    for w in b["workloads"]:
        cell = spec.load_cell(w["name"])
        cfg = cell["config"]
        with open(os.path.join(ROOT, next(
                c["file"] for c in b["configs"] if c["name"] == w["config"]))) as f:
            assert json.load(f) == cfg
        driver = spec.module("drivers", cell["driver"])
        for kind in ("family", "reference", "shapes"):
            spec.module({"family": "families"}.get(kind, kind), cfg[kind])
        reported[w["name"]] = set(driver.END_TO_END) | {"setup_s"}
        assert len(reported[w["name"]]) >= 2
        assert set(cell["check"]["limits"].values()) != {None}
        for m in e2e.values():
            if w["name"] in m.get("workloads", [w["name"]]):
                assert m["name"] in reported[w["name"]], (w["name"], m["name"])
    for m in b["per_layer"]:
        f = files[m["name"]]
        assert (f["layer"], f["unit"], f["moves"], f["source"]) == \
            (m["layer"], m["unit"], m["moves"], m["source"])
        assert hasattr(spec.module("readers", f["reader"]), "read")
        cells = m.get("workloads") or [
            w for w, r in reported.items() if m["moves"] in r]
        assert cells
        for w in cells:
            assert m["moves"] in reported[w]
    assert set(files) == {m["name"] for m in b["per_layer"]}


# ---------------------------------------------------------------------------
# The yardstick's arithmetic
# ---------------------------------------------------------------------------


def _synthetic_trace():
    return [
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            (trace.WINDOW_EVENT, 1000, 10000), ("fetch", 4000, 2500),
            ("inner", 4500, 1000)]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ("while", 1000, 3000), ("fusion.1", 1000, 1000),
                ("_kernel", 2500, 1000), ("fusion.1", 7000, 2000),
                ("late", 10500, 2000)]},
            {"name": "Steps", "events": [("step", 0, 20000)]}]},
    ]


def test_trace_reduction_on_a_hand_computed_trace():
    """Window 1000..11000 ns. Busy: 1000..4000, 7000..9000, 10500..11000 =
    5500 ns, so 45% idle; the ``while`` keeps 1000 ns of its own; the gap
    4000..7000 falls to the host's ``fetch`` (its ``inner`` ends at the gap's
    middle), the gap 9000..10500 to nothing recorded."""
    t = trace.reduce(_synthetic_trace())
    assert t["window_s"] == pytest.approx(10000e-9)
    assert t["busy_s"] == pytest.approx(5500e-9)
    assert t["ops"]["while"][:2] == pytest.approx([1000e-9, 3000e-9])
    assert t["ops"]["_kernel"] == pytest.approx([1000e-9, 1000e-9, 1])
    assert t["ops"]["fusion.1"][2] == 2
    assert dict(map(tuple, t["idle_gaps"])) == pytest.approx(
        {"fetch": 3000e-9, "host:nothing_recorded": 1500e-9})
    from benchmark.readers import trace_idle, trace_kernel_roofline

    assert trace_idle.read({}, {"trace": t}) == pytest.approx(45.0)
    assert trace_idle.read({}, {"trace": None}) is None
    cell = {"config": dict(spec.load_json("configs", "gpt2-medium-f32"),
                           dtype="bfloat16"), "chips": 1}
    facts = {"trace": t, "cell": cell, "batch": 16, "seq_len": 1024,
             "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    metric = {"patterns": ["^_kernel"], "shape_fn": "flash_fwd"}
    # 4*16*16*1024^2*64/2 = 34.36 GFLOP -> 174.4 us at the peak; one call
    # in 1 us of trace reads 17441%
    assert trace_kernel_roofline.read(metric, facts) == pytest.approx(
        100 * (4 * 16 * 16 * 1024 ** 2 * 64 / 2 / 197e12) / 1000e-9)
    assert trace_kernel_roofline.read(
        dict(metric, patterns=["no_such_kernel"]), facts) is None
    assert dict(map(tuple, t["device_ops"]))["fusion"] == pytest.approx(3000e-9)


def test_flash_kernels_are_told_apart_by_their_result_types():
    """The device events are named by the whole HLO instruction; the
    reduction keeps the instruction's name and, for a custom call, its target
    and result type, which the metric files' patterns read."""
    fwd = spec.load_json("metrics", "flash_fwd_roofline")["patterns"]
    bwd = spec.load_json("metrics", "flash_bwd_roofline")["patterns"]
    hit = lambda pats, n: any(re.search(p, n) for p in pats)   # noqa: E731
    tail = (' custom-call(bf16[256,1024,64]{2,1,0:T(8,128)(2,1)} %bitcast.3), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    cases = {
        "%jvp__.24 = (bf16[256,1024,64]{2,1,0:T(8,128)(2,1)S(1)}, "
        "f32[256,1,1024]{2,1,0:T(1,128)})" + tail: (True, False),
        "%jvp__.2 = (f32[128,1024,64]{2,1,0}, f32[128,1,1024]{2,1,0})" + tail:
            (True, False),
        "%transpose_jvp___.61 = (bf16[256,1024,64]{2,1,0:T(8,128)(2,1)}, "
        "bf16[256,1024,64]{2,1,0:T(8,128)(2,1)})" + tail: (False, True),
        "%transpose_jvp___.62 = bf16[256,1024,64]{2,1,0:T(8,128)(2,1)}" + tail:
            (False, True),
        "%custom-call.8 = bf16[50257,1024]{1,0:T(8,128)(2,1)S(1)} custom-call("
        'bf16[12568,1024]{1,0} %slice-done), custom_call_target="ConcatBitcast"':
            (False, False),
        "%fusion.193 = (bf16[1024,50257]{0,1:T(8,128)(2,1)}) fusion(bf16[1] %a), "
        "kind=kOutput": (False, False),
    }
    for text, want in cases.items():
        name = trace.op_name(text)
        assert (hit(fwd, name), hit(bwd, name)) == want, name
    assert trace.op_name("%fusion.193 = (bf16[8]{0}) fusion(bf16[8] %a)") == "fusion.193"


def test_shape_functions_and_parameter_counts():
    from benchmark.reference import gpt2 as ref
    from benchmark.shapes import gpt2 as shapes

    medium = spec.load_json("configs", "gpt2-medium-f32")
    # openai-community/gpt2-large config.json, for the serve shape functions
    large = dict(medium, n_embd=1280, n_layer=36, n_head=20, n_inner=5120)
    assert shapes.matmul_params(medium) == 353_453_056
    assert shapes.train_flops_per_token(medium, 1024) == pytest.approx(
        2.2717e9, rel=1e-4)
    assert ref.num_params(medium) == medium["parameters_as_built"] == 406_336_593
    assert ref.num_params(large) == 838_409_297
    # one decode token at position 100 of gpt2-large: 2 flops per matmul
    # parameter, 4*d per key and block
    d, L, V = 1280, 36, 50257
    assert shapes.serve_token_flops(large, 90, 10) == pytest.approx(
        2 * (12 * d * d * L + d * V) + 4 * d * L * 100)
    assert shapes.least_seconds({"flops": 197e12, "bytes": 1.0},
                                {"bf16_flops_per_s": 197e12,
                                 "hbm_bytes_per_s": 819e9}) == (1.0, "flops")


def test_norm_gap_is_a_gap_of_norms_against_leaf_or_median():
    ref = {"a": 1.0, "b": 10.0, "c": 1e-9}
    gap, where = compare.worst_norm_gap({"a": 1.1, "b": 10.0, "c": 0.5}, ref)
    assert (gap, where) == (pytest.approx(0.5), "c")     # against median 1.0
    gap, where = compare.worst_norm_gap({"a": 1.1, "b": 10.0, "c": 0.5}, ref,
                                        skip={"c"})
    assert (gap, where) == (pytest.approx(0.1 / 5.5), "a")
    v = compare.verdict({"x": 0.5, "y": 0.1}, {"x": 1.0})
    assert not v["correct"] and v["numbers"]["y"]["limit"] is None


def test_traffic_is_a_function_of_the_seed_with_one_set_of_sizes():
    from benchmark.drivers import serve_closed, train

    t = dict(spec.load_json("traffic", "tiny-chat-c4", [DATA]), requests=256,
             prompt_len={"dist": "log_uniform", "lo": 32, "hi": 512})
    a = serve_closed.make_requests(t, 50257, 3_000_000_019)
    b = serve_closed.make_requests(t, 50257, 3_000_000_019)
    c = serve_closed.make_requests(t, 50257, 7)
    assert a == b and a != c
    size = lambda rs: sorted((len(r["prompt"]), r["max_tokens"]) for r in rs)  # noqa: E731
    assert size(a) == size(c)
    lens = [len(r["prompt"]) for r in a]
    assert min(lens) >= 32 and max(lens) <= 512
    assert 100 < statistics.median(lens) < 170          # log-uniform: ~128
    x = train.make_batches({"pool": 2, "batch": 4, "seq_len": 8}, 97, 2 ** 31 + 5)
    assert x[0][0].dtype == np.int32 and (x[0][1][:, :-1] == x[0][0][:, 1:]).all()
    assert len({r.tobytes() for b_ in x for r in b_[0]}) == 8


# ---------------------------------------------------------------------------
# A whole run on the CPU at a toy size, through run_cell
# ---------------------------------------------------------------------------


@pytest.fixture
def on_cpu(monkeypatch):
    """Lift the no-TPU failure for a test (never a flag of the command), and
    keep JAX's global cache settings as the suite has them."""
    import jax

    from benchmark import run
    from benchmark.harness import compiles, device
    from deeplearning4j_tpu.utils import bucketing

    monkeypatch.setattr(device, "require", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(device, "peaks", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(device, "describe", lambda devs: {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs), "memory_peak_bytes": 1})
    monkeypatch.setattr(run, "enable_cache", lambda: "off for the tests")
    made = []

    class Recorded(compiles.Compiles):
        def __init__(self):
            super().__init__()
            made.append(self)

    monkeypatch.setattr(compiles, "Compiles", Recorded)
    yield run
    # a run leaves nothing behind in the suite's process
    from jax._src import monitoring

    for c in made:
        assert c._event not in monitoring.get_event_listeners()
        assert c._duration not in monitoring.get_event_duration_listeners()
    bucketing.telemetry().reset()


def _state_unchanged(monkeypatch):
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork

    orig = MultiLayerNetwork._fit_batch

    def fit_batch(self, x, y, fm, lm, ew=None):
        import jax

        keep = jax.tree_util.tree_map(lambda a: a.copy(),
                                      (self.params, self.opt_state))
        loss = orig(self, x, y, fm, lm, ew=ew)
        self.params, self.opt_state = keep
        return loss

    monkeypatch.setattr(MultiLayerNetwork, "_fit_batch", fit_batch)


def _half_batch(monkeypatch):
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork

    orig = MultiLayerNetwork._fit_batch
    monkeypatch.setattr(
        MultiLayerNetwork, "_fit_batch",
        lambda self, x, y, fm, lm, ew=None: orig(
            self, x[: len(x) // 2], y[: len(y) // 2], fm, lm, ew=ew))


def _token_altered(monkeypatch):
    from deeplearning4j_tpu.serve.scheduler import GenerateWorker

    orig = GenerateWorker._emit

    def emit(self, s, tok, step_bucket, now):
        if s.generated % 3 == 1:
            tok = (tok + 1) % 97
        return orig(self, s, tok, step_bucket, now)

    monkeypatch.setattr(GenerateWorker, "_emit", emit)


@pytest.mark.parametrize("cell,fault,control", [
    ("tiny-train", None, "bfloat16"),
    ("tiny-train", None, "bfloat16_compute"),
    ("tiny-train", _state_unchanged, None),
    ("tiny-train", _half_batch, None),
    ("tiny-serve", None, "fp8"),
    ("tiny-serve", _token_altered, None),
])
def test_correct_is_true_for_the_program_and_false_for_control_and_faults(
        on_cpu, monkeypatch, cell, fault, control):
    """The sound program agrees with the plain reference through the
    driver's own path (fit -> mln.step; HTTP -> GenerateWorker ->
    decode.step), and the control, put in its place, does not; with the
    timed path broken underneath, ``correct`` comes out false."""
    if fault is not None:
        fault(monkeypatch)
    # the serve window has to see tokens arrive on a busy test machine
    seconds = 2.0 if cell == "tiny-serve" else 0.5
    line = on_cpu.run_cell(cell, 3_000_000_019, seconds, False, roots=[DATA],
                           controls=[control] if control else ())
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(line)[-1] == "compared"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    assert all(v["value"] > 0 for v in line["metrics"].values())
    for rec in line["compared"].values():
        assert rec["limit"] is not None
    if fault is None:
        assert line["correct"] is True, line["compared"]
        assert line["controls"][control]["correct"] is False
    else:
        assert line["correct"] is False, line["compared"]


def test_a_run_without_a_tpu_fails_and_prints_no_result(capsys):
    from benchmark import run

    code = run.main(["--workload", "gpt2m-f32-train-b8-t1024", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert code != 0 and out.out == "" and "no result" in out.err
    assert not re.search(r'"correct"', out.err)
