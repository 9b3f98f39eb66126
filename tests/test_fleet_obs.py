"""Fleet observability plane (ISSUE 20).

Covers: W3C traceparent mint/adopt semantics at the HTTP front door and
the trace riding the scheduler's coalescing boundary into the dispatch
span; rank/incarnation process-context stamping of spans and JSONL event
lines (every line carrying its own wall<->perf anchor); the mergeable
fixed-boundary histogram export and federated quantiles; two REAL worker
subprocesses publishing snapshots + span dumps into a FileStore with the
collector merging them into one label-correct exposition and
trace_export.merge joining the dumps into one valid multi-track Perfetto
timeline; request_id end-to-end over plain and chunked HTTP; and the
step-skew straggler detector.
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import obs, serve
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
from deeplearning4j_tpu.nn.model import (
    MultiLayerConfiguration,
    MultiLayerNetwork,
)
from deeplearning4j_tpu.obs import fleet, metrics, trace_export
from deeplearning4j_tpu.parallel.netstore import open_store
from deeplearning4j_tpu.serve.admission import ServeConfig
from deeplearning4j_tpu.utils import bucketing


@pytest.fixture(autouse=True)
def _obs_isolation(monkeypatch):
    for var in ("DL4J_TPU_OBS", "DL4J_TPU_EVENT_LOG", "DL4J_TPU_RANK",
                "DL4J_TPU_WID", "DL4J_TPU_SLICE",
                "DL4J_TPU_STRAGGLER_FACTOR", "DL4J_TPU_STRAGGLER_PATIENCE"):
        monkeypatch.delenv(var, raising=False)
    fleet._reset_for_tests()
    obs.reset()
    bucketing.telemetry().reset()
    yield
    obs.configure_event_log(None)
    fleet._reset_for_tests()
    obs.reset()
    bucketing.telemetry().reset()


def _mln(seed=1, n_in=4):
    conf = MultiLayerConfiguration(
        layers=(Dense(n_out=8, activation="tanh"),
                OutputLayer(n_out=2, activation="softmax")),
        input_type=InputType.feed_forward(n_in),
        updater={"type": "sgd", "lr": 0.1},
        seed=seed,
    )
    return MultiLayerNetwork(conf).init()


# ---------------------------------------------------------------------------
# trace context
# ---------------------------------------------------------------------------


class TestTraceContext:
    def test_mint_header_parse_round_trip(self):
        ctx = fleet.TraceContext.mint()
        assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
        back = fleet.TraceContext.parse(ctx.header())
        assert back is not None
        assert back.trace_id == ctx.trace_id
        assert back.span_id == ctx.span_id

    def test_child_keeps_trace_id_fresh_span(self):
        ctx = fleet.TraceContext.mint()
        kid = ctx.child()
        assert kid.trace_id == ctx.trace_id
        assert kid.span_id != ctx.span_id

    @pytest.mark.parametrize("header", [
        None, "", "garbage", "00-xyz-abc-01",
        "00-" + "0" * 32 + "-" + "a" * 16 + "-01",   # all-zero trace id
        "00-" + "a" * 32 + "-" + "0" * 16 + "-01",   # all-zero span id
        "00-" + "a" * 31 + "-" + "b" * 16 + "-01",   # short trace id
    ])
    def test_invalid_headers_rejected(self, header):
        assert fleet.TraceContext.parse(header) is None

    def test_scope_is_thread_local_and_restores(self):
        ctx = fleet.TraceContext.mint()
        assert fleet.current_trace() is None
        with fleet.trace_scope(ctx):
            assert fleet.current_trace() is ctx
            inner = fleet.TraceContext.mint()
            with fleet.trace_scope(inner):
                assert fleet.current_trace() is inner
            assert fleet.current_trace() is ctx
        assert fleet.current_trace() is None


# ---------------------------------------------------------------------------
# stamping: process context on spans + event lines
# ---------------------------------------------------------------------------


class TestStamping:
    def test_span_records_carry_rank_and_trace(self):
        fleet.set_process_context(rank=3, wid="w3", incarnation=2)
        ctx = fleet.TraceContext.mint()
        with fleet.trace_scope(ctx):
            with obs.span("unit.work"):
                pass
        rec = obs.recent_spans()[-1]
        assert rec["rank"] == 3 and rec["inc"] == 2
        assert rec["trace_id"] == ctx.trace_id

    def test_event_lines_carry_host_pid_and_anchor(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        obs.configure_event_log(path)
        fleet.set_process_context(rank=1)
        obs.event("unit_event", payload=7)
        line = json.loads(open(path).read().strip().splitlines()[-1])
        assert line["kind"] == "unit_event"
        assert line["host"] and line["pid"] == os.getpid()
        # the (ts, perf_s) pair IS this line's wall<->perf anchor
        assert isinstance(line["ts"], float)
        assert isinstance(line["perf_s"], float)
        assert line["rank"] == 1

    def test_env_seeded_process_context(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_RANK", "5")
        monkeypatch.setenv("DL4J_TPU_WID", "w5")
        fleet._reset_for_tests()
        ctx = fleet.process_context()
        assert ctx["rank"] == 5 and ctx["wid"] == "w5"


# ---------------------------------------------------------------------------
# mergeable histograms
# ---------------------------------------------------------------------------


class TestMergeableHistograms:
    def test_summary_exports_bucket_counts(self):
        h = obs.histogram("t_lat_seconds", "test")
        for v in (0.01, 0.02, 0.3, 1.5):
            h.observe(v)
        s = h.summary()
        assert s["count"] == 4
        assert len(s["buckets"]) == len(metrics.BUCKET_BOUNDS) + 1
        assert sum(s["buckets"]) == 4

    def test_quantile_from_merged_buckets_beats_q_of_q(self):
        # two workers with disjoint latency populations: the federated p99
        # must land in worker B's range — averaging per-worker p99s cannot
        # get this right, adding bucket counts can
        n = len(metrics.BUCKET_BOUNDS) + 1
        a, b = [0] * n, [0] * n
        from bisect import bisect_left

        for v in [0.001] * 99 + [0.002]:
            a[bisect_left(metrics.BUCKET_BOUNDS, v)] += 1
        for v in [1.0] * 100:
            b[bisect_left(metrics.BUCKET_BOUNDS, v)] += 1
        merged = [x + y for x, y in zip(a, b)]
        q99 = metrics.quantile_from_buckets(merged, 0.99)
        assert 0.5 <= q99 <= 1.0

    def test_overflow_bucket_clamps(self):
        n = len(metrics.BUCKET_BOUNDS) + 1
        counts = [0] * n
        counts[-1] = 10  # everything beyond the last bound
        assert metrics.quantile_from_buckets(counts, 0.5) == \
            metrics.BUCKET_BOUNDS[-1]


# ---------------------------------------------------------------------------
# federation: real subprocesses -> store -> collector + merged timeline
# ---------------------------------------------------------------------------

_WORKER_SCRIPT = r"""
import sys, time
from deeplearning4j_tpu import obs
from deeplearning4j_tpu.obs import fleet
from deeplearning4j_tpu.parallel.netstore import open_store

store_dir, wid, rank, dump = sys.argv[1], sys.argv[2], sys.argv[3], sys.argv[4]
rank = int(rank)
fleet.set_process_context(rank=rank, wid=wid, incarnation=1)
obs.counter("t_requests_total", "test counter").inc(rank + 1)
h = obs.histogram("t_seconds", "test latency")
for v in ([0.01] * 5 if rank == 0 else [0.4] * 5):
    h.observe(v)
with obs.span("worker.step", it=0):
    time.sleep(0.02)
store = open_store(store_dir)
fleet.publish_snapshot(store, wid)
obs.save_spans(dump)
"""


class TestFederation:
    @pytest.fixture()
    def fleet_dir(self, tmp_path):
        store_dir = tmp_path / "store"
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        dumps = []
        for rank, wid in enumerate(("w0", "w1")):
            dump = str(tmp_path / f"spans_{wid}.json")
            subprocess.run(
                [sys.executable, "-c", _WORKER_SCRIPT, str(store_dir),
                 wid, str(rank), dump],
                check=True, env=env, timeout=120)
            dumps.append(dump)
        return store_dir, dumps

    def test_collector_merges_label_correct_exposition(self, fleet_dir):
        store_dir, _ = fleet_dir
        coll = fleet.FleetCollector(open_store(str(store_dir)))
        snaps = coll.collect_snapshots()
        assert [d["wid"] for d in snaps] == ["w0", "w1"]
        assert [d["process"]["rank"] for d in snaps] == [0, 1]
        text = coll.prometheus_text()
        assert "dl4j_fleet_workers 2" in text
        # per-worker series keep their identity labels (sorted order)
        per_worker = [l for l in text.splitlines()
                      if l.startswith("t_requests_total{")]
        assert any('rank="0"' in l for l in per_worker)
        assert any('rank="1"' in l for l in per_worker)
        # counter roll-up: 1 (rank 0) + 2 (rank 1)
        assert "t_requests_total_fleet 3" in text
        # federated histogram quantiles from MERGED bucket counts: the
        # fleet p99 must land in rank 1's (slow) population
        line = next(l for l in text.splitlines()
                    if l.startswith('t_seconds_fleet{quantile="0.99"'))
        assert 0.2 <= float(line.rsplit(" ", 1)[1]) <= 0.5
        assert "t_seconds_fleet_count 10" in text

    def test_merged_timeline_one_track_per_worker(self, fleet_dir):
        _, dumps = fleet_dir
        docs = [json.load(open(p)) for p in dumps]
        assert all(d["process"]["wid"] for d in docs)
        merged = trace_export.merge(docs)
        assert trace_export.validate(merged) == []
        slices = [e for e in merged["traceEvents"] if e["ph"] == "X"]
        assert {e["pid"] for e in slices} == {1, 2}
        names = {e["args"]["name"]
                 for e in merged["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert any("rank 0 (w0)" in n for n in names)
        assert any("rank 1 (w1)" in n for n in names)
        # normalized common wall axis: every ts is finite and >= 0
        assert all(e["ts"] >= 0 for e in slices)
        # per-track monotonic: within each lane, sorted by ts already
        for pid in (1, 2):
            ts = [e["ts"] for e in slices if e["pid"] == pid]
            assert ts == sorted(ts)

    def test_cli_render_and_http_collector(self, fleet_dir, capsys):
        store_dir, _ = fleet_dir
        assert fleet.main(["render", "--store", str(store_dir)]) == 0
        out = capsys.readouterr().out
        assert "dl4j_fleet_workers 2" in out
        httpd, _, port = fleet.serve_collector(open_store(str(store_dir)))
        try:
            text = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/fleet/metrics",
                timeout=30).read().decode()
            assert "dl4j_fleet_workers 2" in text
            assert "t_requests_total_fleet 3" in text
            snaps = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/fleet/snapshots",
                timeout=30).read())["snapshots"]
            assert {d["wid"] for d in snaps} == {"w0", "w1"}
        finally:
            httpd.shutdown()

    def test_collector_skips_torn_snapshot(self, fleet_dir):
        store_dir, _ = fleet_dir
        store = open_store(str(store_dir))
        store.set(fleet.SNAP_PREFIX + "w2", b"{torn json")
        coll = fleet.FleetCollector(store)
        assert [d["wid"] for d in coll.collect_snapshots()] == ["w0", "w1"]


# ---------------------------------------------------------------------------
# HTTP propagation end to end
# ---------------------------------------------------------------------------


class TestHttpPropagation:
    @pytest.fixture()
    def server(self):
        reg = serve.ModelRegistry(config=ServeConfig(max_batch=8, workers=1))
        reg.register("toy", _mln(seed=7), warm=False)
        srv = serve.InferenceServer(reg).start(port=0)
        yield srv
        srv.stop()

    def _post(self, port, payload, headers=()):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/models/toy:predict",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json", **dict(headers)})
        resp = urllib.request.urlopen(req, timeout=30)
        return resp.status, json.loads(resp.read()), dict(resp.headers)

    def test_inbound_trace_adopted_and_echoed(self, server):
        x = np.zeros((2, 4), np.float32).tolist()
        inbound = fleet.TraceContext.mint()
        status, body, headers = self._post(
            server.port, {"inputs": x, "deadline_ms": 30000},
            headers={"traceparent": inbound.header()})
        assert status == 200
        echoed = fleet.TraceContext.parse(headers["traceparent"])
        # same trace, fresh span id (we are a child hop, not an echo)
        assert echoed.trace_id == inbound.trace_id
        assert echoed.span_id != inbound.span_id
        assert body["request_id"] == inbound.trace_id
        # the trace resolved through the scheduler into the dispatch span,
        # and the front-door span itself is stamped. Both close on the
        # server's threads after the reply's last byte is out (the worker
        # fulfils the future inside serve.dispatch, serve/httpcommon.py
        # writes inside http.request), so the client may be here first:
        # wait for each
        def closed(match):
            deadline = time.monotonic() + 5.0
            while True:
                found = [r for r in obs.recent_spans() if match(r)]
                if found or time.monotonic() > deadline:
                    return found
                time.sleep(0.01)

        assert closed(lambda r: r["span"] == "serve.dispatch"
                      and inbound.trace_id in r["attrs"]["traces"])
        assert closed(lambda r: r["span"] == "http.request"
                      and r.get("trace_id") == inbound.trace_id)

    def test_trace_minted_when_absent(self, server):
        x = np.zeros((2, 4), np.float32).tolist()
        status, body, headers = self._post(
            server.port, {"inputs": x, "deadline_ms": 30000})
        assert status == 200
        minted = fleet.TraceContext.parse(headers["traceparent"])
        assert minted is not None
        assert body["request_id"] == minted.trace_id


class TestGenerateStreamRequestId:
    def test_chunked_tail_carries_request_id(self):
        import http.client

        from tests.test_generate import _cfg, _lm, _prompt

        reg = serve.ModelRegistry()
        reg.register_generate("lm", _lm(), warm=True, config=_cfg())
        srv = serve.InferenceServer(reg).start(port=0)
        try:
            inbound = fleet.TraceContext.mint()
            conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                              timeout=60)
            conn.request("POST", "/v1/models/lm:generate",
                         json.dumps({"prompt": _prompt(5),
                                     "max_tokens": 3}).encode(),
                         {"Content-Type": "application/json",
                          "traceparent": inbound.header()})
            resp = conn.getresponse()
            echoed = fleet.TraceContext.parse(resp.getheader("traceparent"))
            body = resp.read().decode()
            conn.close()
            assert resp.status == 200
            assert echoed.trace_id == inbound.trace_id
            tail = json.loads(body.strip().splitlines()[-1])
            assert tail["done"]
            # the NDJSON terminal line resolves the stream to its trace
            assert tail["request_id"] == inbound.trace_id
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# straggler detection
# ---------------------------------------------------------------------------


class TestStragglerDetector:
    def test_flags_after_patience_and_sets_skew(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        obs.configure_event_log(path)
        det = fleet.StragglerDetector(factor=1.5, patience=2)
        walls = {0: 0.1, 1: 0.1, 2: 0.5}
        assert det.observe(0, walls) == []          # patience 1/2
        assert det.observe(1, walls) == [2]         # flagged
        assert det.observe(2, walls) == []          # no double-flag
        assert det.flagged == {2}
        g = obs.gauge("dl4j_step_skew_seconds", "", ("rank",))
        assert g.value(rank=2) == pytest.approx(0.4)
        assert g.value(rank=0) == pytest.approx(0.0)
        events = [json.loads(l) for l in open(path).read().splitlines()]
        hits = [e for e in events if e["kind"] == "straggler_detected"]
        assert len(hits) == 1
        assert hits[0]["rank"] == 2 and hits[0]["iteration"] == 1

    def test_recovered_rank_resets_patience(self):
        det = fleet.StragglerDetector(factor=1.5, patience=2)
        slow = {0: 0.1, 1: 0.5}
        fast = {0: 0.1, 1: 0.1}
        assert det.observe(0, slow) == []
        assert det.observe(1, fast) == []   # streak broken
        assert det.observe(2, slow) == []   # back to 1/2
        assert det.observe(3, slow) == [1]

    def test_single_rank_and_env_knobs(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_STRAGGLER_FACTOR", "3.5")
        monkeypatch.setenv("DL4J_TPU_STRAGGLER_PATIENCE", "7")
        det = fleet.StragglerDetector()
        assert det.factor == 3.5 and det.patience == 7
        assert det.observe(0, {0: 9.0}) == []  # needs >= 2 ranks


# ---------------------------------------------------------------------------
# elastic integration: stepwall keys + results surface
# ---------------------------------------------------------------------------


class TestElasticSurface:
    def test_stepwall_key_layout(self):
        assert fleet.stepwall_key(2, 7, 1) == "obs/stepwall/2/7/1"
        assert fleet.stepwall_key(2, 7, 1).startswith(fleet.STEPWALL_PREFIX)

    @pytest.mark.slow
    def test_two_worker_run_publishes_snapshots_and_stragglers(
            self, tmp_path):
        """2-worker elastic run with a chaos stall pinned to rank 1: the
        run must surface snapshots for both wids, nonzero skew for the
        straggler, and flag it in results (full fleet chain in-process of
        the workers, asserted post-mortem from the store + results)."""
        outdir = tmp_path / "out"
        store_dir = tmp_path / "store"
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   DL4J_TPU_CHAOS="slow_iter:rank1:0.3",
                   DL4J_TPU_STRAGGLER_FACTOR="2.0",
                   DL4J_TPU_STRAGGLER_PATIENCE="2")
        subprocess.run(
            [sys.executable, "-m", "deeplearning4j_tpu.train.elastic",
             "launch", "--store", str(store_dir), "--outdir", str(outdir),
             "--workers", "2", "--world", "2", "--epochs", "2",
             "--batch", "16", "--n", "32", "--timeout", "240"],
            check=True, env=env, timeout=300)
        r0 = json.load(open(outdir / "result_w0.json"))
        assert r0["stragglers"] == [1]
        coll = fleet.FleetCollector(open_store(str(store_dir)))
        snaps = coll.collect_snapshots()
        assert {d["wid"] for d in snaps} == {"w0", "w1"}
        text = coll.prometheus_text()
        assert "dl4j_fleet_workers 2" in text
        # span dumps merge into one valid two-track timeline
        docs = [json.load(open(outdir / f"spans_w{i}.json"))
                for i in range(2)]
        merged = trace_export.merge(docs)
        assert trace_export.validate(merged) == []
        assert {e["pid"] for e in merged["traceEvents"]
                if e["ph"] == "X"} == {1, 2}
