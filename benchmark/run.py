"""One process, one cell, one run, one result line.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's files are found by name (benchmark/harness/spec.py), its driver is
``benchmark/drivers/<driver>.py``. Set-up (import, weights on the device from
the seed, compile or cache read, warm-up) runs to the first instant of the
window and is reported as ``setup_s``; the window lasts ``--seconds``; then
the peak memory is read, the program's state is freed and the plain reference
decides ``correct``. ``--trace 0`` prints the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics, with ``busy_s``, ``window_s`` and a
``breakdown`` from the profiler's trace of a slice of the window.

Without a TPU, or with fewer chips than the cell asks for, the run exits with
code 3 and prints no result.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - _T_START:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def enable_cache() -> str:
    """The program's own switch for JAX's persistent compilation cache
    (``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``),
    and every program into it, however short its compile: the second run of
    a cell finds all of them."""
    import jax
    from deeplearning4j_tpu.utils.compile_cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             roots=None, controls=(), t_start: float = None) -> dict:
    """The whole run, as a function: the tests drive it with the device
    check patched and tiny files under ``roots``. ``controls`` also puts the
    reference in a lower precision, or broken, in the program's place and
    judges that the same way (benchmark/control.py and the tests; never set
    by the command)."""
    from benchmark.harness import compare, compiles, device, spec, tracing

    t_start = _T_START if t_start is None else t_start
    cell = spec.load_cell(name, roots)
    devs = device.require(int(cell["chips"]))
    peaks = device.peaks(devs[0].device_kind)

    cache_dir = enable_cache()
    log(f"{name} seed {seed} on {len(devs)} x {devs[0].device_kind}; "
        f"compile cache {cache_dir}")

    driver = spec.module("drivers", cell["driver"])
    ctx = {"cell": cell, "seed": int(seed), "seconds": float(seconds),
           "trace": bool(trace), "log": log, "peaks": peaks, "devices": devs,
           "compiles": compiles.Compiles(), "controls": tuple(controls),
           "tracer": tracing.Tracer(
               bool(trace), name,
               start_s=float(seconds) * float(cell.get("trace_start", 0.4)),
               length_s=min(float(cell.get("trace_seconds", 6.0)),
                            float(seconds) * 0.5))}
    try:
        session = driver.setup(ctx)
        setup_s = time.perf_counter() - t_start
        n_setup = ctx["compiles"].count
        log(f"set-up {setup_s:.1f}s: {n_setup} programs, "
            f"{ctx['compiles'].seconds:.1f}s compiling or reading the cache "
            f"({ctx['compiles'].cache_hits} hits, {ctx['compiles'].cache_misses} "
            "misses)")
        out = driver.window(ctx, session)
        log(f"window closed: {out['metrics']}")
        dev = device.describe(devs)
        summary = ctx["tracer"].summary()
        driver.free(session)
        verdict = driver.check(ctx, session)

        e2e = dict(out["metrics"])
        e2e["setup_s"] = setup_s
        units = {m["name"]: m["unit"]
                 for m in spec.benchmark_json()["end_to_end"]}
        if trace:
            facts = dict(out["facts"], trace=summary, peaks=peaks, cell=cell,
                         setup_s=setup_s)
            metrics = {}
            for m in spec.metrics_for(e2e, roots):
                value = spec.module("readers", m["reader"]).read(m, facts)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            if summary is None:
                raise RuntimeError("the traced run took no trace")
            dev["busy_s"], dev["window_s"] = summary["busy_s"], summary["window_s"]
        else:
            metrics = {k: {"value": v, "unit": units.get(k, "")}
                       for k, v in e2e.items()}
        line = {"correct": verdict["correct"], "attempted": out["attempted"],
                "failed": out["failed"], "metrics": metrics, "device": dev}
        if trace:
            line["breakdown"] = {"device_ops": summary["device_ops"],
                                 "idle_gaps": summary["idle_gaps"]}
        if "controls" in verdict:
            line["controls"] = verdict["controls"]
        line["compared"] = verdict["numbers"]
        compare.print_numbers(verdict)      # the last lines on standard error
        return line
    finally:
        ctx["compiles"].close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    from benchmark.harness.device import NoChip

    try:
        line = run_cell(a.workload, a.seed, a.seconds, bool(a.trace))
    except NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
