"""CPU tests of what the benchmark reads of the program's own names: scope
paths of device operations, program spans and idle time by span
(benchmark/harness/trace_scopes.py) on a hand-computed trace, the walk of the
xplane protobuf that finds the scope map, and the readers that turn spans,
scopes and idle labels into per-layer metrics.
"""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import trace, trace_scopes  # noqa: E402
from benchmark.readers import span_stat, trace_scope_ms  # noqa: E402


def _trace():
    """The harness test's synthetic trace, with what the program adds: a
    scope for three of the four operations, and its spans in the host
    plane. Window 1000..11000 ns."""
    return [
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            (trace.WINDOW_EVENT, 1000, 10000), ("fetch", 4000, 2500),
            ("inner", 4500, 1000)]}],
         "spans": [("mln.iter", 900, 6000), ("mln.step", 1200, 300),
                   ("mln.loss_fetch", 4000, 2500), ("mln.iter", 6900, 2600),
                   ("mln.step", 7100, 300)]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ("while", 1000, 3000), ("fusion.1", 1000, 1000),
                ("_kernel", 2500, 1000), ("fusion.1", 7000, 2000),
                ("late", 10500, 2000)]},
            {"name": "Steps", "events": [("step", 0, 20000)]}],
         "scopes": {"fusion.1": "mln.step/jvp(Dense.0)",
                    "_kernel": "mln.step/transpose(jvp(Dense.0))",
                    "late": "mln.step/update/Dense.0"}},
    ]


def test_reduce_keeps_every_old_key_as_it_was():
    planes = _trace()
    old, new = trace.reduce(planes), trace_scopes.reduce(planes)
    assert set(new) == set(old) | {"scopes", "spans", "idle_by_span"}
    for k in old:
        assert new[k] == old[k], k
    assert old["busy_s"] == pytest.approx(5500e-9)
    assert dict(map(tuple, old["idle_gaps"])) == pytest.approx(
        {"fetch": 3000e-9, "host:nothing_recorded": 1500e-9})


def test_scopes_add_up_to_busy_time():
    """fusion.1 runs 1000 + 2000 ns, the kernel 1000, ``late`` 500 inside
    the window; the ``while`` keeps 1000 ns of its own and has no scope."""
    t = trace_scopes.reduce(_trace())
    assert t["scopes"] == pytest.approx({
        "mln.step/jvp(Dense.0)": 3000e-9,
        "mln.step/transpose(jvp(Dense.0))": 1000e-9,
        "mln.step/update/Dense.0": 500e-9,
        trace_scopes.UNSCOPED: 1000e-9})
    assert sum(t["scopes"].values()) == pytest.approx(t["busy_s"])


def test_spans_inside_the_slice_and_idle_time_by_span():
    """The first ``mln.iter`` starts before the slice and is left out of
    ``spans``, but still covers the gap 4000..7000 up to 6900: 2500 ns of it
    under the shorter ``mln.loss_fetch``, 400 under the bare first iteration
    and 100 under the second; the gap 9000..10500 runs 500 ns under the
    second ``mln.iter`` and 1000 past it."""
    t = trace_scopes.reduce(_trace())
    assert t["spans"] == pytest.approx({
        "mln.step": [[200e-9, 300e-9], [6100e-9, 300e-9]],
        "mln.loss_fetch": [[3000e-9, 2500e-9]],
        "mln.iter": [[5900e-9, 2600e-9]]})
    assert t["idle_by_span"] == pytest.approx({
        "mln.loss_fetch": 2500e-9, "mln.iter": 1000e-9,
        trace_scopes.OUTSIDE: 1000e-9})
    assert sum(t["idle_by_span"].values()) == pytest.approx(
        t["window_s"] - t["busy_s"])
    # two starts 5900 ns apart in a slice of 10000
    assert trace_scopes.steps_in_slice(t, "mln.step") == pytest.approx(
        10000 / 5900)
    assert trace_scopes.steps_in_slice(t, "mln.loss_fetch") is None


def test_scope_reader_per_step_and_per_busy():
    facts = {"trace": trace_scopes.reduce(_trace())}
    fwd_bwd = {"name": "m", "scopes": [r"^mln\.step/(?!update/).*Dense\.0"],
               "per": "step", "site_span": "mln.step"}
    # 4000 ns over 10000/5900 steps, in ms
    assert trace_scope_ms.read(fwd_bwd, facts) == pytest.approx(
        1e3 * 4000e-9 * 5900 / 10000)
    unscoped = {"name": "m", "scopes": ["^unscoped$"], "per": "busy"}
    assert trace_scope_ms.read(unscoped, facts) == pytest.approx(
        100 * 1000 / 5500)
    assert trace_scope_ms.read(dict(unscoped, scopes=["^no_such"]), facts) is None
    # the harness's own reduction keeps no scopes: nothing, not 0
    assert trace_scope_ms.read(
        unscoped, {"trace": trace.reduce(_trace())}) is None
    assert trace_scope_ms.read(unscoped, {"trace": None}) is None
    with pytest.raises(ValueError):
        trace_scope_ms.read(dict(unscoped, per="mile"), facts)


def test_span_reader_reads_the_programs_ring(monkeypatch):
    from deeplearning4j_tpu import obs

    monkeypatch.delenv("DL4J_TPU_OBS", raising=False)
    obs.reset()
    for _ in range(3):
        with obs.span("bench.toy_span"):
            pass
    rec = [r["wall_s"] for r in obs.recent_spans()
           if r["span"] == "bench.toy_span"]
    m = {"name": "m", "span": "bench.toy_span"}
    assert span_stat.read(m, {}) == pytest.approx(1e3 * sorted(rec)[1])
    assert span_stat.read(dict(m, stat="mean"), {}) == pytest.approx(
        1e3 * sum(rec) / 3)
    assert span_stat.read({"name": "m", "span": "bench.no_such"}, {}) is None
    with pytest.raises(ValueError):
        span_stat.read(dict(m, stat="p99"), {})
    obs.reset()
    monkeypatch.setenv("DL4J_TPU_OBS", "0")
    with obs.span("bench.toy_span"):
        pass
    assert span_stat.read(m, {}) is None


@pytest.mark.parametrize("op_name,path", [
    ("jit(step)/mln.step/jvp(TransformerBlock.3)/attn/dot_general:",
     "mln.step/jvp(TransformerBlock.3)/attn"),
    ("jit(step)/mln.step/transpose(jvp(loss))/RnnOutputLayer.5/"
     "jit(log_softmax)/exp:", "mln.step/transpose(jvp(loss))/"
     "RnnOutputLayer.5/jit(log_softmax)"),
    ("jit(step)/mln.step/update/TransformerBlock.3/sub",
     "mln.step/update/TransformerBlock.3"),
    ("jit(step)/mln.step/add:", "mln.step"),
    ("jit(_threefry_split)/slice:", trace_scopes.UNSCOPED),
    ("", trace_scopes.UNSCOPED),
])
def test_scope_path_keeps_the_scopes_between_jit_and_primitive(op_name, path):
    assert trace_scopes.scope_path(op_name) == path


# -- the protobuf walk -------------------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _bytes_field(num: int, payload: bytes) -> bytes:
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _int_field(num: int, value: int) -> bytes:
    return _varint(num << 3) + _varint(value)


def _entry(key: int, message: bytes) -> bytes:
    return _int_field(1, key) + _bytes_field(2, message)


def test_scope_map_is_read_from_the_event_metadata_of_an_xplane_file(tmp_path):
    """A device plane with two operations: one whose ``tf_op`` stat is a
    string, one whose stat refers to a stat-metadata name (interned), and a
    third with another stat only; a host plane with no such stat at all."""
    stat_meta = (
        _bytes_field(5, _entry(7, _int_field(1, 7) + _bytes_field(2, b"tf_op")))
        + _bytes_field(5, _entry(8, _int_field(1, 8) + _bytes_field(2, b"flops")))
        + _bytes_field(5, _entry(9, _int_field(1, 9) + _bytes_field(
            2, b"jit(step)/mln.step/update/Dense.0/sub:"))))
    fusion = (_int_field(1, 1)
              + _bytes_field(2, b"%fusion.3 = f32[8]{0} fusion(f32[8]{0} %a), "
                                b"kind=kLoop")
              + _bytes_field(5, _int_field(1, 8) + _int_field(3, 12))
              + _bytes_field(5, _int_field(1, 7) + _bytes_field(
                  5, b"jit(step)/mln.step/jvp(Dense.0)/tanh:")))
    update = (_int_field(1, 2) + _bytes_field(2, b"%subtract.9 = f32[8]{0} "
                                                 b"subtract(f32[8]{0} %a)")
              + _bytes_field(5, _int_field(1, 7) + _int_field(7, 9)))
    copy = (_int_field(1, 3) + _bytes_field(2, b"%copy.1 = f32[8]{0} copy(%a)")
            + _bytes_field(5, _int_field(1, 8) + _int_field(3, 1)))
    device = (_int_field(1, 1) + _bytes_field(2, b"/device:TPU:0")
              + _bytes_field(3, b"\x12\x07XLA Ops")      # a line, skipped
              + _bytes_field(4, _entry(1, fusion))
              + _bytes_field(4, _entry(2, update))
              + _bytes_field(4, _entry(3, copy)) + stat_meta)
    host = _int_field(1, 2) + _bytes_field(2, b"/host:CPU") + _bytes_field(
        4, _entry(1, _int_field(1, 1) + _bytes_field(2, b"mln.iter")))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_bytes_field(1, device) + _bytes_field(1, host))
    maps = trace_scopes.scope_maps(str(path))
    assert maps == {"/device:TPU:0": {
        "fusion.3": "jit(step)/mln.step/jvp(Dense.0)/tanh:",
        "subtract.9": "jit(step)/mln.step/update/Dense.0/sub:"}}


def test_a_profiler_trace_of_program_spans_is_loaded_with_them(tmp_path):
    """On the CPU a trace has no device plane to reduce, but its host plane
    holds the program's annotations: ``load_xplane`` keeps them apart from
    the runtime's events by their ``span_depth`` stat."""
    import jax

    from deeplearning4j_tpu import obs

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with obs.span("bench.outer", step=7):
            with obs.span("bench.inner"):
                jax.numpy.ones((4,)).block_until_ready()
        with jax.profiler.TraceAnnotation("not_a_program_span"):
            pass
    finally:
        jax.profiler.stop_trace()
    planes = trace_scopes.load_xplane(trace.find_xplane(str(tmp_path)))
    spans = [s for p in trace.host_planes(planes) for s in p.get("spans", ())]
    by_name = {name: (start, dur) for name, start, dur in spans}
    assert set(by_name) == {"bench.outer", "bench.inner"}
    (o_s, o_d), (i_s, i_d) = by_name["bench.outer"], by_name["bench.inner"]
    assert o_s <= i_s and i_s + i_d <= o_s + o_d
    every = {e[0] for p in trace.host_planes(planes)
             for l in p["lines"] for e in l["events"]}
    assert "not_a_program_span" in every
