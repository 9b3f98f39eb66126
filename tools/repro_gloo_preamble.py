#!/usr/bin/env python
"""Standalone repro: gloo TCP transport crash under multi-host
collective-dense programs (quarantined out of
tests/_multihost_worker.py scenarios 3 and 4).

The bug: a 2-process CPU cluster (4 virtual devices each, gloo transport)
aborts inside gloo's TCP pair with

    gloo/transport/tcp/pair.cc: op.preamble.length <= op.nbytes
    (e.g. 1024 vs 512)

i.e. a peer announces a payload larger than the negotiated buffer — the
two processes matched different collectives on one TCP pair. Two
scenarios pin it, both quarantined out of tests/test_multihost.py:

  tp    TransformerLM train step on a data=4 x model=2 mesh (tensor-
        parallel all-reduces interleaving with data-parallel ones) —
        crashes every observed run;
  ring  sequence-parallel TransformerLM on a data=1 x seq=8 mesh (ring
        attention: every ppermute crosses the host boundary) — crashes
        ~4 out of 5 isolated launches.

Both are independent of this repo's code: the identical programs are
exact single-process (tests/test_longcontext.py, tests/test_tp_hlo.py)
and the multi-host data-parallel scenarios around them are healthy
(tests/test_multihost.py). Upstream: the gloo CPU collective backend
shipped with the pinned jaxlib.

This script relaunches those exact scenarios: 2 subprocesses x 4 virtual
CPU devices each, 2 train steps per scenario.

Exit codes:
  0  crash REPRODUCED in at least one scenario — the quarantines in
     tests/_multihost_worker.py must stay
  2  NOT reproduced (all scenarios finished with finite losses) — retire
     the quarantines in tests/test_multihost.py
  1  the probe itself failed (port/bootstrap trouble, not a verdict)

Run on any host:
  python tools/repro_gloo_preamble.py
"""

import json
import os
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCENARIOS = ("tp", "ring")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def worker(idx: int, nproc: int, port: str, outdir: str, scen: str) -> None:
    sys.path.insert(0, REPO)
    from __graft_entry__ import _provision_cpu_mesh

    _provision_cpu_mesh(4)  # BEFORE distributed init

    from deeplearning4j_tpu.parallel.distributed import init_distributed

    init_distributed(f"127.0.0.1:{port}", num_processes=nproc, process_id=idx)

    import numpy as np

    from deeplearning4j_tpu.models import TransformerLM
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.parallel import ShardedTrainer
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh

    if scen == "tp":
        # quarantined scenario 3, verbatim: multi-host x tensor-parallel
        mesh = make_mesh(MeshSpec(data=4, model=2))
        conf = TransformerLM(vocab_size=32, max_len=16, d_model=32,
                             n_heads=2, n_blocks=1, dtype="float32")
        rs = np.random.RandomState(5)
        xg = rs.randint(0, 32, (8, 16))
        yg = np.eye(32, dtype=np.float32)[rs.randint(0, 32, (8, 16))]
    else:
        # quarantined scenario 4, verbatim: cross-host ring attention
        # (seq=8 spans both processes — every ring ppermute crosses the
        # host boundary)
        mesh = make_mesh(MeshSpec(data=1, model=1, seq=8))
        conf = TransformerLM(vocab_size=32, max_len=32, d_model=32,
                             n_heads=2, n_blocks=1, sequence_parallel=True,
                             dtype="float32", seed=21)
        rs = np.random.RandomState(9)
        xg = rs.randint(0, 32, (2, 32))
        yg = np.eye(32, dtype=np.float32)[rs.randint(0, 32, (2, 32))]

    model = MultiLayerNetwork(conf).init()
    tr = ShardedTrainer(model, mesh)
    l1 = float(tr.fit_batch(xg, yg))
    l2 = float(tr.fit_batch(xg, yg))
    assert np.isfinite(l1) and np.isfinite(l2), (l1, l2)
    if idx == 0:
        with open(os.path.join(outdir, f"losses_{scen}.json"), "w") as f:
            json.dump({"losses": [l1, l2]}, f)


def _probe(scen: str) -> int:
    """Run one scenario's 2-process group; 0 = crashed (reproduced),
    2 = completed, 1 = probe failure."""
    import tempfile

    port = _free_port()
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["PYTHONPATH"] = REPO
    with tempfile.TemporaryDirectory() as outdir:
        procs = [
            subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--worker",
                 str(i), "2", str(port), outdir, scen],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
            for i in range(2)
        ]
        outs = []
        try:
            for p in procs:
                out, _ = p.communicate(timeout=420)
                outs.append(out.decode("utf-8", "replace"))
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            print(f"[{scen}] PROBE FAILED: worker timeout "
                  "(not a crash verdict)")
            return 1
        rcs = [p.returncode for p in procs]
        crashed = any(rc != 0 for rc in rcs)
        preamble = any("preamble" in o for o in outs)
        for i, (rc, o) in enumerate(zip(rcs, outs)):
            print(f"--- [{scen}] worker {i}: rc={rc} ---")
            tail = o[-2000:]
            if tail.strip():
                print(tail)
        if crashed:
            print(f"[{scen}] REPRODUCED: worker exit codes {rcs}"
                  + (" with the gloo preamble assertion in the output"
                     if preamble else
                     " (abnormal termination in the gloo transport)"))
            return 0
        if not os.path.exists(os.path.join(outdir, f"losses_{scen}.json")):
            print(f"[{scen}] PROBE FAILED: workers exited 0 but wrote "
                  "no result")
            return 1
        print(f"[{scen}] completed: both workers finished with finite "
              "losses this launch")
        return 2


def main() -> int:
    verdicts = {scen: _probe(scen) for scen in SCENARIOS}
    print(f"\nverdicts: {verdicts}  (0=crashed, 2=completed, 1=probe "
          "failure)")
    if any(v == 1 for v in verdicts.values()):
        return 1
    if any(v == 0 for v in verdicts.values()):
        print("\nREPRODUCED: the scenario quarantines in "
              "tests/_multihost_worker.py must stay. (The ring flavor is "
              "intermittent — a single completed launch does not retire "
              "it; only an all-scenarios-complete run exits 2, and "
              "confirm over ~10 such runs.)")
        return 0
    print("\nNOT reproduced: every scenario completed. Retire the "
          "quarantines in tests/test_multihost.py (confirm over "
          "~10 consecutive runs first — the ring flavor is "
          "intermittent).")
    return 2


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--worker":
        worker(int(sys.argv[2]), int(sys.argv[3]), sys.argv[4], sys.argv[5],
               sys.argv[6])
        sys.exit(0)
    sys.exit(main())
