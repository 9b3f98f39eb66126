#!/usr/bin/env python
"""Standalone repro: GSPMD miscompiles gradient clipping fused into the
gpipe step (workaround in parallel/gpipe.py
make_train_step).

The bug: when the nonlinear clip/renorm (gradient normalization) is traced
into the SAME jitted program as the pipe-sharded stage stack, the GSPMD
partitioner resolves the clip intermediate inconsistently between its
consumers — the norm sees the per-replica value while the downstream
parameter subtraction consumes a spuriously all-reduced copy, scaling the
applied update by exactly the data*seq replica count (4x on the
data=2 x seq=2 mesh below). The shipped workaround runs the clip math
EAGERLY between two jitted halves (grads / update).

This script builds both variants from the SAME trainer internals:

  split  the production path: grads jit -> eager clip -> update jit
  fused  jax.jit(split_step) — re-inlining the two halves plus the eager
         clip into ONE traced program, i.e. the configuration the
         workaround exists to avoid

then takes one identical training step with each and compares the applied
parameter updates.

Exit codes:
  0  miscompile REPRODUCED (fused update inflated ~data*seq) — the
     eager-clip split in parallel/gpipe.py must stay
  2  NOT reproduced (updates match) — this XLA resolves the clip
     correctly; retire the split in parallel/gpipe.py
  1  the probe itself failed

Run on any host (forces an 8-virtual-CPU-device mesh):
  python tools/repro_gpipe_clip_miscompile.py
"""

import os
import sys

# the virtual mesh must land before jax initializes
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from deeplearning4j_tpu.nn.input_type import InputType  # noqa: E402
from deeplearning4j_tpu.nn.layers import Dense, OutputLayer  # noqa: E402
from deeplearning4j_tpu.nn.model import (  # noqa: E402
    MultiLayerConfiguration, MultiLayerNetwork)
from deeplearning4j_tpu.parallel.gpipe import GPipeTrainer  # noqa: E402
from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh  # noqa: E402


def _conf():
    # threshold far below the typical grad norm so the clip's nonlinear
    # branch (g * thr/||g||) is ACTIVE — a no-op clip can't miscompile
    kw = dict(gradient_normalization="clip_l2_per_layer",
              gradient_normalization_threshold=0.05)
    return MultiLayerConfiguration(
        layers=(Dense(n_out=16, activation="tanh", **kw),
                Dense(n_out=16, activation="tanh", **kw),
                Dense(n_out=16, activation="tanh", **kw),
                OutputLayer(n_out=4, activation="softmax")),
        input_type=InputType.feed_forward(8),
        updater={"type": "sgd", "lr": 0.1},
        seed=13,
    )


def _one_step(fuse: bool):
    """One gn-bearing gpipe step on the data=2 x seq=2 x pipe=2 mesh.
    Returns (params_before, params_after) as flat host arrays."""
    mesh = make_mesh(MeshSpec(data=2, pipe=2, model=1, seq=2))
    tr = GPipeTrainer(_conf(), mesh, n_micro=2)
    before = [{k: np.asarray(v) for k, v in layer.items()}
              for layer in tr.to_model().params]
    step = tr.make_train_step()
    if fuse:
        # re-inline the split into ONE jitted program: the eager clip and
        # both jitted halves all trace into a single GSPMD compilation —
        # the exact configuration the production split avoids
        step = jax.jit(step)
    tr._step = step
    rs = np.random.RandomState(0)
    x = rs.randn(8, 8).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rs.randint(0, 4, 8)]
    tr.fit_batch(x, y)
    after = [{k: np.asarray(v) for k, v in layer.items()}
             for layer in tr.to_model().params]
    return before, after


def main():
    b_s, a_s = _one_step(fuse=False)   # production: eager clip
    b_f, a_f = _one_step(fuse=True)    # fused: clip inside the jit

    replicas = 4  # data=2 x seq=2
    worst = 1.0
    print(f"{'layer/param':<16} {'|Δ| split':>12} {'|Δ| fused':>12} "
          f"{'ratio':>8}")
    for i, (ls, lf) in enumerate(zip(a_s, a_f)):
        for k in sorted(ls):
            ds = float(np.linalg.norm(ls[k] - b_s[i][k]))
            df = float(np.linalg.norm(lf[k] - b_f[i][k]))
            if ds < 1e-12:
                continue
            ratio = df / ds
            worst = max(worst, ratio)
            print(f"{i}/{k:<14} {ds:>12.6g} {df:>12.6g} {ratio:>8.3f}")

    if worst > 1.5:
        print(f"\nREPRODUCED: fused-clip update inflated up to "
              f"{worst:.2f}x (expected ~{replicas}x = data*seq). The "
              f"eager-clip split in parallel/gpipe.py must stay.")
        return 0
    print("\nNOT reproduced: fused and split updates match — this XLA "
          "resolves the fused clip correctly. Retire the eager-clip split "
          "in parallel/gpipe.py make_train_step.")
    return 2


if __name__ == "__main__":
    sys.exit(main())
