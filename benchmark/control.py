"""Reading the two ends of a limit on the chip: the program's numbers on many
seeds (the lower reading) and, judged the same way, the control's and the
planted faults' (the upper reading), all in one process.

    python benchmark/control.py --workload <cell> --seeds 11,12,13 \\
        --controls fp8,fault:half_batch --seconds 5

A control is the plain reference put in the program's place and computed in
the nearest precision below the one the configuration states (``fp8`` for
bfloat16, ``bfloat16`` for float32); ``fault:half_batch`` is the reference
with half of every batch left out. The benchmark's own runs never run this.
One JSON line per seed on standard output.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    a = ap.parse_args(argv)
    from benchmark import run

    controls = [c for c in a.controls.split(",") if c]
    for seed in (int(x) for x in a.seeds.split(",")):
        line = run.run_cell(a.workload, seed, a.seconds, False,
                            controls=controls, t_start=time.perf_counter())
        print(json.dumps({
            "seed": seed, "correct": line["correct"],
            "failed": line["failed"], "attempted": line["attempted"],
            "program": {k: v["value"] for k, v in line["compared"].items()},
            "at": {k: v.get("at") for k, v in line["compared"].items()},
            "controls": {c: {"correct": v["correct"], **{
                k: x["value"] for k, x in v["numbers"].items()}}
                for c, v in line.get("controls", {}).items()},
            "metrics": {k: v["value"] for k, v in line["metrics"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
