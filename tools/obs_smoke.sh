#!/usr/bin/env bash
# Observability smoke (docs/OBSERVABILITY.md): a tiny fit plus one durable
# checkpoint save/restore cycle must leave a coherent trail across all three
# surfaces — the JSONL event log (expected kinds, in causal order), the
# metrics registry (families for bucketing / spans / checkpoints), and the
# live /metrics Prometheus exposition on the UI server. The fleet phase
# drives the cross-process plane end to end: a 2-worker elastic run with a
# rank-targeted slow_iter chaos stall must flag the straggler, federate
# both workers' snapshots into one /fleet/metrics exposition, resolve a
# /v1/predict trace id to its dispatch span, and merge the per-worker span
# dumps into one valid multi-track Perfetto timeline.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=${JAX_PLATFORMS:-cpu}
workdir=$(mktemp -d)
trap 'rm -rf "$workdir"' EXIT

python - "$workdir" <<'EOF'
import json
import os
import sys
import urllib.request

sys.path.insert(0, os.getcwd())
from __graft_entry__ import _provision_cpu_mesh
_provision_cpu_mesh(8)
import numpy as np

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
from deeplearning4j_tpu.nn.model import MultiLayerConfiguration, MultiLayerNetwork
from deeplearning4j_tpu.train import resilience
from deeplearning4j_tpu.ui.server import UIServer

workdir = sys.argv[1]
log_path = os.path.join(workdir, "events.jsonl")
obs.configure_event_log(log_path)

print("== phase 1: tiny fit + checkpoint save/restore ==")
conf = MultiLayerConfiguration(
    layers=(Dense(n_out=8, activation="tanh"),
            OutputLayer(n_out=3, activation="softmax")),
    input_type=InputType.feed_forward(4),
    updater={"type": "sgd", "lr": 5e-2}, seed=3)
model = MultiLayerNetwork(conf).init()
rs = np.random.RandomState(0)
x = rs.randn(64, 4).astype(np.float32)
y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, 64)]
model.fit((x, y), epochs=1, batch_size=16)

ckpt = os.path.join(workdir, "obs_smoke.zip")
resilience.save_checkpoint(model, ckpt)
resilience.load_state_into(model, ckpt)

print("== phase 2: event log carries the expected kinds, in order ==")
with open(log_path) as fh:
    events = [json.loads(line) for line in fh]
assert events, "event log is empty"
for e in events:
    assert "ts" in e and "kind" in e, f"malformed event: {e}"
kinds = [e["kind"] for e in events]
for expected in ("trace", "checkpoint_saved", "checkpoint_restored"):
    assert expected in kinds, f"missing event kind {expected!r} in {kinds}"
assert kinds.index("trace") < kinds.index("checkpoint_saved") \
    < kinds.index("checkpoint_restored"), f"event order wrong: {kinds}"
print(f"event log OK: {len(events)} events, kinds={sorted(set(kinds))}")

print("== phase 3: snapshot + live /metrics + /debug/trace ==")
snap = obs.snapshot()
for view in ("metrics", "spans", "events", "bucketing", "profile"):
    assert view in snap, f"snapshot missing {view!r}"
assert "mln.fit_batch" in snap["spans"], snap["spans"].keys()
assert snap["profile"]["sites"], "no XLA cost entries harvested"

srv = UIServer().serve(port=0)
try:
    # /debug/trace first: its completed request puts dl4j_requests_total
    # on the board for the /metrics exposition that follows. The request
    # counter ticks in the handler's finally block AFTER the body is sent,
    # so poll briefly rather than racing a single immediate fetch.
    with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/debug/trace", timeout=10) as resp:
        live_doc = json.loads(resp.read().decode())
    url = f"http://127.0.0.1:{srv.port}/metrics"
    import time as _time
    for _ in range(50):
        with urllib.request.urlopen(url, timeout=10) as resp:
            ctype = resp.headers["Content-Type"]
            body = resp.read().decode()
        if "dl4j_requests_total" in body:
            break
        _time.sleep(0.1)
finally:
    srv.stop()
assert "version=0.0.4" in ctype, ctype
assert body.strip(), "/metrics returned an empty body"
for family in ("dl4j_compiles_total", "dl4j_compile_seconds_total",
               "dl4j_span_seconds",
               "dl4j_checkpoint_saves_total", "dl4j_events_total",
               "dl4j_xla_flops", "dl4j_requests_total"):
    assert family in body, f"/metrics missing family {family!r}"
lines = [l for l in body.splitlines() if l and not l.startswith("#")]
print(f"/metrics OK: {len(lines)} samples from {url}")

from deeplearning4j_tpu.obs import trace_export
problems = trace_export.validate(live_doc)
assert not problems, f"/debug/trace invalid: {problems}"
print(f"/debug/trace OK: {len(live_doc['traceEvents'])} events")

print("== phase 4: fit's spans nest in an exported Perfetto trace ==")
os.environ["DL4J_TPU_CHAIN_STEPS"] = "0"
obs.reset()
nested = MultiLayerNetwork(conf).init()
nested.fit((x, y), epochs=1, batch_size=16)
os.environ.pop("DL4J_TPU_CHAIN_STEPS")
dump = os.path.join(workdir, "spans.json")
assert obs.save_spans(dump) > 0, "span dump is empty"
with open(dump) as fh:
    dumped = json.load(fh)
doc = trace_export.trace_events(dumped["spans"], anchor=dumped.get("anchor"))
problems = trace_export.validate(doc)
assert not problems, f"exported trace invalid: {problems}"
slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
names = {e["name"] for e in slices}
for name, parent in (("mln.feed", "mln.iter"), ("mln.fit_batch", "mln.iter"),
                     ("mln.step", "mln.fit_batch")):
    assert name in names, f"missing {name} in trace ({sorted(names)})"
    recs = [e for e in slices if e["name"] == name]
    assert all(e["args"].get("parent") == parent for e in recs), \
        f"{name} spans not nested under {parent}"
print(f"trace export OK: {len(slices)} slices, iter > fit_batch > step nested")

obs.configure_event_log(None)
print("obs smoke OK")
EOF

echo "== phase 5: fleet — trace propagation, federation, stragglers =="
fleetdir="$workdir/fleet"
mkdir -p "$fleetdir/out"
DL4J_TPU_CHAOS="slow_iter:rank1:0.3" \
DL4J_TPU_STRAGGLER_FACTOR=2.0 DL4J_TPU_STRAGGLER_PATIENCE=2 \
python -m deeplearning4j_tpu.train.elastic launch \
    --store "$fleetdir/store" --outdir "$fleetdir/out" \
    --workers 2 --world 2 --epochs 2 --batch 16 --n 32 --timeout 240

python - "$fleetdir" <<'EOF'
import json
import os
import sys
import urllib.request

sys.path.insert(0, os.getcwd())
import numpy as np

from deeplearning4j_tpu import obs, serve
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
from deeplearning4j_tpu.nn.model import MultiLayerConfiguration, MultiLayerNetwork
from deeplearning4j_tpu.obs import fleet
from deeplearning4j_tpu.parallel.netstore import open_store
from deeplearning4j_tpu.serve.admission import ServeConfig

fleetdir = sys.argv[1]

# the chaos'd rank must have been flagged: results + straggler event
r0 = json.load(open(os.path.join(fleetdir, "out", "result_w0.json")))
assert r0["stragglers"] == [1], f"stragglers: {r0['stragglers']}"
events = [json.loads(l)
          for l in open(os.path.join(fleetdir, "out", "events_w0.jsonl"))]
hits = [e for e in events if e["kind"] == "straggler_detected"]
assert hits and hits[0]["rank"] == 1, hits
print(f"straggler OK: rank 1 flagged at boundary {hits[0]['iteration']}")

# merged /fleet/metrics serves both ranks with nonzero skew for rank 1
store = open_store(os.path.join(fleetdir, "store"))
httpd, _, port = fleet.serve_collector(store)
try:
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/fleet/metrics", timeout=30).read().decode()
finally:
    httpd.shutdown()
assert "dl4j_fleet_workers 2" in text, "collector did not merge both workers"
skews = [l for l in text.splitlines()
         if l.startswith("dl4j_step_skew_seconds{") and 'rank="1"' in l]
assert skews and any(float(l.rsplit(" ", 1)[1]) > 0 for l in skews), skews
print(f"/fleet/metrics OK: both ranks merged, rank-1 skew "
      f"{skews[0].rsplit(' ', 1)[1]}s")

# end-to-end correlation: a /v1/predict response's trace id resolves to
# the serving worker's coalesced dispatch span
conf = MultiLayerConfiguration(
    layers=(Dense(n_out=8, activation="tanh"),
            OutputLayer(n_out=2, activation="softmax")),
    input_type=InputType.feed_forward(4),
    updater={"type": "sgd", "lr": 0.1}, seed=7)
reg = serve.ModelRegistry(config=ServeConfig(max_batch=8, workers=1))
reg.register("toy", MultiLayerNetwork(conf).init(), warm=False)
srv = serve.InferenceServer(reg).start(port=0)
try:
    inbound = fleet.TraceContext.mint()
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/v1/models/toy:predict",
        data=json.dumps({"inputs": np.zeros((2, 4)).tolist(),
                         "deadline_ms": 30000}).encode(),
        headers={"Content-Type": "application/json",
                 "traceparent": inbound.header()})
    resp = urllib.request.urlopen(req, timeout=30)
    body = json.loads(resp.read())
    echoed = fleet.TraceContext.parse(resp.headers["traceparent"])
finally:
    srv.stop()
assert echoed.trace_id == inbound.trace_id
assert body["request_id"] == inbound.trace_id
dispatch = [r for r in obs.recent_spans() if r["span"] == "serve.dispatch"]
assert dispatch and inbound.trace_id in dispatch[-1]["attrs"]["traces"], \
    "trace id did not resolve to the dispatch span"
print(f"trace propagation OK: request_id {body['request_id'][:8]}… "
      "resolves to serve.dispatch")
EOF

# merged Perfetto timeline: one track per worker, schema/nesting valid
python -m deeplearning4j_tpu.obs.trace_export \
    --spans "$fleetdir/out/spans_w0.json" "$fleetdir/out/spans_w1.json" \
    --out "$fleetdir/fleet_trace.json" --validate
echo "merged trace OK: $fleetdir/fleet_trace.json validates"

echo "== phase 6: CLI render =="
python -m deeplearning4j_tpu.obs.trace_export --help >/dev/null

echo "obs smoke OK (all phases)"
