#!/usr/bin/env bash
# Serving-tier smoke (docs/SERVING.md): proves the import -> AOT warm ->
# serve pipeline end to end, one fresh process per phase:
#   1. a warm process imports the Keras fixture, warms the serving ladder
#      through the model registry, and persists the compiled executables as
#      an .aotbundle next to nothing-in-particular (a temp dir);
#   2. a COLD process restores the bundle through the same registry.load
#      call, serves a concurrent HTTP burst with ZERO request-path
#      compiles, answers each row as the warm process did (bit for bit)
#      and, coalesced, as it does alone (to 1e-6: another bucket), and under forced overload SHEDS (429/503 +
#      dl4j_shed_total) instead of queueing without bound.
# The same two phases also carry the GENERATIVE tier: phase 1 warms the
# bucketed KV-cache decode engine (decode.step executable set) for a
# TransformerLM and persists its bundle; phase 2 cold-restores it and
# streams a chunked /v1/models/<name>:generate round trip that must emit
# the SAME tokens with ZERO decode.step compiles.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=${JAX_PLATFORMS:-cpu}
export DL4J_TPU_AOT_BUNDLE=1   # CPU: persistence is opt-in (nn/aot.py)
workdir=$(mktemp -d)
trap 'rm -rf "$workdir"' EXIT

common=$(cat <<'EOF'
import json, os, sys, threading, time
sys.path.insert(0, os.getcwd())
from __graft_entry__ import _provision_cpu_mesh
_provision_cpu_mesh(8)
import numpy as np
from deeplearning4j_tpu.serve import (
    ModelRegistry, ModelWorker, ServeConfig, ShedError)
from deeplearning4j_tpu.utils import bucketing

FIXTURE = "tests/fixtures/keras_cnn.h5"
MAX_BATCH = 8
bundle = sys.argv[1]
x = np.load("tests/fixtures/keras_cnn_io.npz")["x"].astype(np.float32)

# generative tier: conf.seed makes init() deterministic, so the cold
# process rebuilds bit-identical weights and the token stream must match
from deeplearning4j_tpu.models import TransformerLM
from deeplearning4j_tpu.nn.model import MultiLayerNetwork
from deeplearning4j_tpu.serve import GenerateConfig

def lm_model():
    return MultiLayerNetwork(TransformerLM(
        vocab_size=32, max_len=64, d_model=32, n_heads=4, n_blocks=2,
        dtype="float32")).init()

GEN_CFG = GenerateConfig(decode_batch_max=4, kv_page_tokens=8,
                         prefill_chunk=16, max_new_default=8, queue_limit=8)
LM_PROMPT = [3, 1, 4, 1, 5, 9, 2, 6]
lm_bundle = os.path.join(os.path.dirname(bundle), "lm.aotbundle")
lm_tokens_ref = os.path.join(os.path.dirname(bundle), "lm_tokens.json")
EOF
)

echo "== phase 1: warm process imports Keras model, persists ladder =="
python - "$workdir/cnn.aotbundle" <<EOF
$common
reg = ModelRegistry(ServeConfig(max_batch=MAX_BATCH))
w = reg.load("cnn", FIXTURE, bundle=bundle)
meta = reg.describe()[0]
assert meta["warmed"] > 0, meta
assert os.path.exists(bundle), "bundle not persisted"
# row by row, as phase 2 serves them: the same bucket's executable on both
# sides (another bucket's differs in the last bit on XLA:CPU)
ref = np.concatenate([np.asarray(w.submit(x[i:i + 1])) for i in range(len(x))])
np.save(os.path.join(os.path.dirname(bundle), "reference.npy"), ref)

# generative tier: warm the decode executable set, persist, stream once
gw = reg.register_generate("lm", lm_model(), bundle=lm_bundle,
                           config=GEN_CFG)
gmeta = [m for m in reg.describe() if m.get("generate")][0]
assert gmeta["warmed"] > 0, gmeta
assert os.path.exists(lm_bundle), "decode bundle not persisted"
toks = list(gw.submit(LM_PROMPT, max_new=6))
assert len(toks) == 6, toks
with open(lm_tokens_ref, "w") as f:
    json.dump(toks, f)
reg.shutdown()
print(f"warmed {meta['warmed']} predict + {gmeta['warmed']} decode "
      f"executables; bundles {os.path.getsize(bundle)} + "
      f"{os.path.getsize(lm_bundle)} bytes")
EOF

echo "== phase 2: COLD process restores, serves, sheds under overload =="
python - "$workdir/cnn.aotbundle" <<EOF
$common
import urllib.request
from deeplearning4j_tpu.obs import slo
from deeplearning4j_tpu.serve.server import InferenceServer

tel = bucketing.telemetry()
reg = ModelRegistry(ServeConfig(max_batch=MAX_BATCH))
w = reg.load("cnn", FIXTURE, bundle=bundle)
meta = reg.describe()[0]
assert meta["restored"] > 0, f"cold process restored nothing: {meta}"
compiles_warm = tel.compiles("mln.output") + tel.compiles("cg.output")

# -- individually-served vs coalesced: bit-exact ------------------------
solo = [np.asarray(w.submit(x[i:i + 1])) for i in range(len(x))]
ref = np.load(os.path.join(os.path.dirname(bundle), "reference.npy"))

srv = InferenceServer(reg, reg.config).start(port=0)
url = f"http://127.0.0.1:{srv.port}/v1/models/cnn:predict"

def predict(rows):
    body = json.dumps({"inputs": rows.tolist()}).encode()
    req = urllib.request.Request(
        url, data=body, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        return np.asarray(json.loads(resp.read())["outputs"],
                          dtype=np.float32)

# concurrent burst: single dispatcher, so overlapping submits coalesce
outs = [None] * len(x)
def burst(i):
    outs[i] = predict(x[i:i + 1])
threads = [threading.Thread(target=burst, args=(i,)) for i in range(len(x))]
for t in threads: t.start()
for t in threads: t.join()
for i in range(len(x)):
    # a coalesced row ran in a wider bucket's executable: XLA:CPU's
    # convolution there differs from the one-row one in the last bit
    assert np.allclose(outs[i][0], solo[i][0], rtol=0, atol=1e-6), \
        f"row {i}: coalesced != individually served"
    assert np.array_equal(solo[i][0], ref[i]), \
        f"row {i}: cold restore != warm process"

compiles = (tel.compiles("mln.output") + tel.compiles("cg.output")
            - compiles_warm)
assert compiles == 0, f"request path compiled {compiles}x after warm-up"

# -- forced overload: starved queue MUST shed, burn rate MUST react -----
over = ModelWorker("cnn_overload", reg.worker("cnn").model,
                   config=ServeConfig(max_batch=4, queue_limit=1),
                   latency=reg.latency)
shed = [0]
shed_lock = threading.Lock()
def hammer(t):
    for i in range(40):
        try:
            over.submit(x[:2], deadline_s=0.05)
        except ShedError:
            with shed_lock:
                shed[0] += 1
hthreads = [threading.Thread(target=hammer, args=(t,)) for t in range(12)]
for t in hthreads: t.start()
for t in hthreads: t.join()
over.shutdown()

tracker = slo.slo_tracker()
shed_total = tracker._count.value(route="serve.cnn_overload", status="shed")
burn = tracker.burn_rate("serve.cnn_overload")
assert shed[0] > 0 and shed_total and shed_total > 0, \
    f"forced overload did not shed (client={shed[0]}, metric={shed_total})"
assert burn and burn > 0, f"burn-rate gauge did not react: {burn}"

# -- generative tier: cold restore -> streaming generate, zero compiles --
gw = reg.register_generate("lm", lm_model(), bundle=lm_bundle,
                           config=GEN_CFG)
gmeta = [m for m in reg.describe() if m.get("generate")][0]
assert gmeta["restored"] > 0, f"cold decode restore installed nothing: {gmeta}"
gen_compiles_warm = tel.compiles("decode.step")

import http.client
conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
body = json.dumps({"prompt": LM_PROMPT, "max_tokens": 6}).encode()
conn.request("POST", "/v1/models/lm:generate", body,
             {"Content-Type": "application/json"})
resp = conn.getresponse()
assert resp.status == 200, resp.status
assert resp.getheader("Transfer-Encoding") == "chunked", \
    "generate response is not streamed"
lines = [json.loads(l) for l in resp.read().decode().strip().splitlines()]
assert lines[-1]["done"] and lines[-1]["reason"] == "length", lines[-1]
toks = [l["token"] for l in lines[:-1]]
with open(lm_tokens_ref) as f:
    want = json.load(f)
assert toks == want, f"cold-restore stream {toks} != warm process {want}"
gen_compiles = tel.compiles("decode.step") - gen_compiles_warm
assert gen_compiles == 0, \
    f"decode path compiled {gen_compiles}x after cold restore"

srv.stop()
print(f"restored {meta['restored']} predict + {gmeta['restored']} decode "
      f"executables; {len(x)} coalesced HTTP requests equal to 1e-6 vs solo, "
      f"solo bit-exact vs warm process; streaming generate bit-exact vs warm process; "
      f"0 request-path compiles (predict AND decode); overload shed "
      f"{shed_total} (burn rate {burn})")
EOF

echo "serve smoke OK"
