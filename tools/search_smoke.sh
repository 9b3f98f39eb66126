#!/usr/bin/env bash
# ANN search-tier smoke (docs/SEARCH.md): proves the full index lifecycle —
# build -> warm -> bundle persist -> COLD restore -> serve — one fresh
# process per phase:
#   1. builds a clustered IVF+PQ index, registers it (warm) through the
#      model registry so the (B, k, nprobe) signature grid compiles once,
#      and persists index zip + .aotbundle + per-tier reference answers;
#   2. a COLD process loads the index, restores the bundle through the same
#      register_index call, answers every tier bit-exactly vs phase 1,
#      serves a concurrent /v1/search burst (coalesced rows == individually
#      served rows, bit for bit) plus the legacy /knn contract, with ZERO
#      compiles on any search site — and under forced overload SHEDS
#      (dl4j_shed_total) with the burn-rate gauge reacting.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=${JAX_PLATFORMS:-cpu}
export DL4J_TPU_AOT_BUNDLE=1   # CPU: persistence is opt-in (nn/aot.py)
workdir=$(mktemp -d)
trap 'rm -rf "$workdir"' EXIT

common=$(cat <<'EOF'
import json, os, sys, threading, time
sys.path.insert(0, os.getcwd())
import numpy as np
from deeplearning4j_tpu.search import IndexConfig, VectorIndex
from deeplearning4j_tpu.serve import ModelRegistry, ServeConfig, ShedError
from deeplearning4j_tpu.utils import bucketing

d = sys.argv[1]
IPATH = os.path.join(d, "ix.zip")
BUNDLE = os.path.join(d, "ix.aotbundle")
REF = os.path.join(d, "ref.npz")

rs = np.random.RandomState(7)
centers = (4.0 * rs.randn(32, 16)).astype(np.float32)
corpus = (centers[rs.randint(0, 32, 4000)]
          + rs.randn(4000, 16)).astype(np.float32)
queries = (centers[rs.randint(0, 32, 12)]
           + rs.randn(12, 16)).astype(np.float32)

SITES = ("search.exact", "search.merge", "search.ivf", "search.ivf_pq")
def search_compiles(tel):
    return sum(tel.compiles(s) for s in SITES)
EOF
)

echo "== phase 1: build + warm + persist index, bundle, references =="
python - "$workdir" <<EOF
$common
ix = VectorIndex.build(corpus, IndexConfig(
    dim=16, nlist=32, nprobe=8, pq_m=4, max_k=16, batch_max=8,
    train_sample=4000, pending_cap=64))
reg = ModelRegistry(ServeConfig(max_batch=8))
w = reg.register_index("vecs", ix, bundle=BUNDLE)
meta = [m for m in reg.describe() if m.get("search")][0]
assert meta["warmed"] > 0, meta
assert os.path.exists(BUNDLE), "search bundle not persisted"
refs = {}
for tier in ix.available_tiers():
    ids, dists = ix.search(queries, k=10, tier=tier)
    refs["ids_" + tier] = ids
    refs["dist_" + tier] = dists
# per-row answers must equal the batch answers (row-independent kernels) —
# established here once so phase 2's coalescing assertion is meaningful
solo = np.concatenate(
    [ix.search(queries[i:i + 1], k=10)[0] for i in range(len(queries))])
assert np.array_equal(solo, refs["ids_" + ix.default_tier]), \
    "single-row answers diverge from the batch answers"
np.savez(REF, **refs)
ix.save(IPATH)
reg.shutdown()
print(f"warmed {meta['warmed']} search executables over tiers "
      f"{ix.available_tiers()}; bundle {os.path.getsize(BUNDLE)} bytes")
EOF

echo "== phase 2: COLD restore, bit-exact serve, zero compiles, shed =="
python - "$workdir" <<EOF
$common
import urllib.request
from deeplearning4j_tpu.obs import slo
from deeplearning4j_tpu.serve.scheduler import SearchWorker
from deeplearning4j_tpu.serve.server import InferenceServer

tel = bucketing.telemetry()
ix = VectorIndex.load(IPATH)
reg = ModelRegistry(ServeConfig(max_batch=8))
w = reg.register_index("vecs", ix, bundle=BUNDLE)
meta = [m for m in reg.describe() if m.get("search")][0]
assert meta["restored"] > 0, f"cold process restored nothing: {meta}"
c0 = search_compiles(tel)

# -- every tier answers bit-exactly vs the warm process -----------------
ref = np.load(REF)
for tier in ix.available_tiers():
    ids, dists = ix.search(queries, k=10, tier=tier)
    assert np.array_equal(ids, ref["ids_" + tier]), \
        f"{tier}: cold-restore ids != warm process"
    assert np.array_equal(dists, ref["dist_" + tier]), \
        f"{tier}: cold-restore distances != warm process"

# -- concurrent /v1/search burst: coalesced == individually served ------
srv = InferenceServer(reg, reg.config).start(port=0)

def post(path, payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())

want = ref["ids_" + ix.default_tier]
outs = [None] * len(queries)
def burst(i):
    outs[i] = post("/v1/search", {"index": "vecs",
                                  "queries": [queries[i].tolist()], "k": 10})
threads = [threading.Thread(target=burst, args=(i,))
           for i in range(len(queries))]
for t in threads: t.start()
for t in threads: t.join()
for i in range(len(queries)):
    assert outs[i]["ids"][0] == want[i].tolist(), \
        f"row {i}: coalesced != individually served"

# -- legacy /knn contract over the unified worker -----------------------
nn = post("/knnnew", {"ndarray": queries[0].tolist(), "k": 5})
assert len(nn["results"]) == 5 and nn["results"][0]["index"] == want[0][0]

compiles = search_compiles(tel) - c0
assert compiles == 0, f"request path compiled {compiles}x after restore"

# -- forced overload: starved queue MUST shed, burn rate MUST react -----
over = SearchWorker("vecs_overload", ix,
                    config=ServeConfig(max_batch=4, queue_limit=1),
                    latency=reg.latency)
shed = [0]
shed_lock = threading.Lock()
def hammer():
    for i in range(40):
        try:
            over.submit(queries[:2], k=10, deadline_s=0.001)
        except ShedError:
            with shed_lock:
                shed[0] += 1
hthreads = [threading.Thread(target=hammer) for _ in range(12)]
for t in hthreads: t.start()
for t in hthreads: t.join()
over.shutdown()

tracker = slo.slo_tracker()
shed_total = tracker._count.value(route="search.vecs_overload",
                                  status="shed")
burn = tracker.burn_rate("search.vecs_overload")
assert shed[0] > 0 and shed_total and shed_total > 0, \
    f"forced overload did not shed (client={shed[0]}, metric={shed_total})"
assert burn and burn > 0, f"burn-rate gauge did not react: {burn}"

srv.stop()
print(f"restored {meta['restored']} search executables; "
      f"{len(ix.available_tiers())} tiers bit-exact vs warm process; "
      f"{len(queries)} coalesced /v1/search rows bit-exact; legacy /knn "
      f"served; 0 request-path compiles; overload shed {shed_total} "
      f"(burn rate {burn})")
EOF

echo "search smoke OK"
