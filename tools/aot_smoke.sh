#!/usr/bin/env bash
# AOT cold-start smoke (nn/aot.py): proves end to end, in one fresh
# process per phase (cold start IS a fresh process), that
#   1. the executable-persistence re-validation harness passes on this
#      backend (serialize -> deserialize -> execute, bitwise parity, run
#      in its own subprocess exactly as the runtime gate invokes it),
#   2. a warm process can persist its compiled ladder as a CRC'd bundle,
#   3. a COLD process restores the bundle and serves its first request and
#      first fit step with ZERO XLA compiles, bit-exact with lazy JIT.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=${JAX_PLATFORMS:-cpu}
# the tiny smoke model would auto-chain fit steps, which bypasses per-step
# AOT dispatch by design — pin chaining off so phase 3 proves the AOT path
export DL4J_TPU_CHAIN_STEPS=0
workdir=$(mktemp -d)
trap 'rm -rf "$workdir"' EXIT

common=$(cat <<'EOF'
import os, sys
sys.path.insert(0, os.getcwd())
from __graft_entry__ import _provision_cpu_mesh
_provision_cpu_mesh(8)
import numpy as np
from deeplearning4j_tpu.nn import aot
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
from deeplearning4j_tpu.nn.model import MultiLayerConfiguration, MultiLayerNetwork
from deeplearning4j_tpu.utils import bucketing

def model():
    conf = MultiLayerConfiguration(
        layers=(Dense(n_out=8, activation="tanh"),
                OutputLayer(n_out=3, activation="softmax")),
        input_type=InputType.feed_forward(4),
        updater={"type": "sgd", "lr": 1e-2}, seed=3)
    return MultiLayerNetwork(conf).init()

def data():
    rs = np.random.RandomState(0)
    x = rs.randn(32, 4).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, 32)]
    return x, y

bundle = sys.argv[1]
EOF
)

echo "== phase 1: re-validation harness (the runtime persistence gate) =="
python -m deeplearning4j_tpu.nn.aot
echo "validation harness OK"

echo "== phase 2: warm process persists its compiled ladder =="
DL4J_TPU_AOT=1 DL4J_TPU_AOT_BUNDLE=1 python - "$workdir/smoke.aotbundle" <<EOF
$common
m = model()
aot.warm_serving(m, 16)
m.fit(data(), epochs=1, batch_size=8)
np.savez(os.path.join(os.path.dirname(bundle), "reference.npz"),
         *[np.asarray(l) for l in __import__("jax").tree_util.tree_leaves(m.params)])
info = aot.save_bundle(m, bundle)
assert info is not None and info["entries"] >= 2, info
print(f"saved {info['entries']} executables, {info['bytes']} bytes")
EOF

echo "== phase 3: COLD process restores, zero compiles, bit-exact =="
DL4J_TPU_AOT=1 DL4J_TPU_AOT_BUNDLE=1 python - "$workdir/smoke.aotbundle" <<EOF
$common
m = model()
n = aot.restore_bundle(m, bundle)
assert n >= 2, f"restored only {n} executables"
tel = bucketing.telemetry()
tel.reset()
out = m.output(np.zeros((5, 4), np.float32))
m.fit(data(), epochs=1, batch_size=8)
compiles = tel.compiles("mln.output") + tel.compiles("mln.step")
assert compiles == 0, f"warm-restore path compiled {compiles}x"
ref = np.load(os.path.join(os.path.dirname(bundle), "reference.npz"))
leaves = [np.asarray(l) for l in __import__("jax").tree_util.tree_leaves(m.params)]
for i, l in enumerate(leaves):
    assert np.array_equal(ref[f"arr_{i}"], l), f"param leaf {i} diverged"
print(f"restored {n} executables; first request + first fit step: 0 compiles; "
      f"params bit-exact vs warm process")
EOF

echo "aot smoke OK"
