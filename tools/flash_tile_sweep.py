"""Device time of the three flash kernels by tile size, read from a profiler
trace on the chip (PERF.md section 5 holds the table this printed; the caps in
``ops/flash_attention.py`` ``_MAX_BLOCK`` stand on it).

    python tools/flash_tile_sweep.py [--shapes gpt2m-f32,...] [--blocks 128,256,512 | chosen]
        [--baseline path/to/another/flash_attention.py] [--yardstick]
        [--out chiprun_out/flash_sweep.jsonl]
    JAX_PLATFORMS=cpu python tools/flash_tile_sweep.py --compile-only

Each variant is one jitted call of one kernel over pre-padded [BH, T, D]
operands, causal, no mask; all variants of a shape run three times inside one
trace and the kernels' own device durations are read back (a stand-alone call
is dispatch-bound on the host's clock, docs/PERF.md). ``--baseline`` times
another copy of the kernel module (the parent commit's) at the same blocks;
``--yardstick`` times jax.experimental.pallas.ops.tpu.flash_attention, to
say what Mosaic allows at the same head width, not to adopt it.
``--compile-only`` compiles every variant for a described v5e without a chip
and prints which ones Mosaic refuses: no time comes out of that.
"""

from __future__ import annotations

import argparse
import functools
import importlib
import importlib.util
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402

SHAPES = {                      # (BH, T, D, dtype): what runs today
    "gpt2m-f32-b8-t1024": (128, 1024, 64, "float32"),
    "gpt2m-f32-b32-t256": (512, 256, 64, "float32"),
    "gpt2m-bf16-b16-t1024": (256, 1024, 64, "bfloat16"),
    "smoke-bf16-t2048-d128": (256, 2048, 128, "bfloat16"),
    "long-bf16-t8192": (32, 8192, 64, "bfloat16"),
}
REPEATS = 3


def load_module(path):
    spec = importlib.util.spec_from_file_location("flash_baseline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kernel_fns(mod, shape, bq, bk):
    """{"fwd": f(q, k, v), "bwd": f(q, k, v, do, lse, delta)} at one tiling;
    ``mod`` is this tree's kernel module or the baseline's (whose backward
    call takes one pair of blocks for both kernels)."""
    BH, T, D, dtype = shape
    if bq is None:              # what the chooser takes, kernel by kernel
        chosen = {kn: mod.choose_blocks(kn, T, T, D, jnp.dtype(dtype).itemsize)
                  for kn in ("fwd", "dq", "dkv")}
    else:
        chosen = {kn: (bq, bk) for kn in ("fwd", "dq", "dkv")}
    common = dict(D=D, q_pad=T, k_pad=T, t_real_k=T, causal=True,
                  scale=1.0 / D ** 0.5, q_off=0, k_off=0, interpret=False,
                  dtype=jnp.dtype(dtype))

    def fwd(q, k, v):
        return mod._fwd_pallas_call(q, k, v, bq=chosen["fwd"][0],
                                    bk=chosen["fwd"][1], **common)

    def bwd(q, k, v, do, lse, delta):
        blocks = ({"blocks": chosen} if hasattr(mod, "choose_blocks")
                  else {"bq": bq, "bk": bk})
        return mod._bwd_pallas_calls(q, k, v, do, lse, delta, t_real_q=T,
                                     **blocks, **common)

    return {"fwd": fwd, "bwd": bwd}


def yardstick_fns(shape, bq, bk):
    from jax.experimental.pallas.ops.tpu import flash_attention as up

    BH, T, D, _ = shape
    sizes = up.BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
        block_q_dkv=bq, block_k_major_dq=bk, block_k_dq=bk, block_q_dq=bq)

    def attn(q, k, v):
        return up.flash_attention(q[:, None], k[:, None], v[:, None],
                                  causal=True, sm_scale=1.0 / D ** 0.5,
                                  block_sizes=sizes)

    def fwd(q, k, v):
        return attn(q, k, v)

    def fwd_bwd(q, k, v, do, lse, delta):
        return jax.vjp(attn, q, k, v)[1](do[:, None])

    return {"fwd": fwd, "bwd": fwd_bwd}


def avals(shape, sharding=None):
    BH, T, D, dtype = shape
    kw = {} if sharding is None else {"sharding": sharding}
    x = jax.ShapeDtypeStruct((BH, T, D), jnp.dtype(dtype), **kw)
    r = jax.ShapeDtypeStruct((BH, 1, T), jnp.float32, **kw)
    return {"fwd": (x, x, x), "bwd": (x, x, x, x, r, r)}


def custom_calls(trace_dir):
    """The Mosaic calls of the trace in time order: (name, seconds)."""
    from benchmark.harness import trace

    planes = trace.load_xplane(trace.find_xplane(trace_dir))
    ev = [e for p in trace.device_planes(planes)[:1] for e in trace.op_events(p)
          if "tpu_custom_call" in e[0]]
    return [(n, d / 1e9) for n, s, d in sorted(ev, key=lambda e: e[1])]


def kind(name):
    return ("dkv" if "dkv" in name else "dq" if "dq" in name else "fwd")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--blocks", default="128,256,512,1024")
    ap.add_argument("--baseline", default=None)
    ap.add_argument("--baseline-blocks", default=None,
                    help="bq:bk,... for the baseline (default: all)")
    ap.add_argument("--yardstick", action="store_true")
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "flash_sweep.jsonl"))
    a = ap.parse_args(argv)
    ours = importlib.import_module("deeplearning4j_tpu.ops.flash_attention")

    sharding = None
    if a.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        sharding = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    elif jax.default_backend() != "tpu":
        print("no TPU: times come from the chip only (--compile-only "
              "rehearses)", file=sys.stderr)
        return 3
    sizes = [] if a.blocks == "chosen" else [
        int(b) for b in a.blocks.split(",")]
    base = load_module(a.baseline) if a.baseline else None
    base_blocks = None
    if a.baseline_blocks:
        base_blocks = {tuple(int(x) for x in p.split(":"))
                       for p in a.baseline_blocks.split(",")}
    os.makedirs(os.path.dirname(a.out), exist_ok=True)

    def emit(rec):
        print(json.dumps(rec), flush=True)
        with open(a.out, "a") as f:
            f.write(json.dumps(rec) + "\n")

    for sname in a.shapes.split(","):
        shape = SHAPES[sname]
        BH, T, D, dtype = shape
        item = jnp.dtype(dtype).itemsize
        emit({"shape": sname, "chosen": {
            kn: ours.choose_blocks(kn, T, T, D, item) for kn in
            ("fwd", "dq", "dkv")}})
        variants = []           # (who, pass, bq, bk, compiled)
        pairs = [(bq, bk) for bq in sizes for bk in sizes
                 if T % bq == 0 and T % bk == 0] or [(None, None)]
        todo = [("ours", functools.partial(kernel_fns, ours), pairs)]
        if base is not None:
            todo.append(("baseline", functools.partial(kernel_fns, base),
                         [p for p in pairs if p[0] and (
                             base_blocks is None or p in base_blocks)]))
        if a.yardstick:
            todo.append(("yardstick", yardstick_fns,
                         [p for p in pairs if p[0] and max(p) <= 512]))
        for who, make, its_pairs in todo:
            for bq, bk in its_pairs:
                for pas, fn in make(shape, bq, bk).items():
                    t = time.perf_counter()
                    try:
                        c = jax.jit(fn).lower(
                            *avals(shape, sharding)[pas]).compile()
                    except Exception as e:      # noqa: BLE001
                        emit({"shape": sname, "who": who, "pass": pas,
                              "bq": bq, "bk": bk, "compile_error":
                              str(e).strip().splitlines()[-1][:300]})
                        continue
                    variants.append((who, pas, bq, bk, c))
                    if a.compile_only:
                        emit({"shape": sname, "who": who, "pass": pas,
                              "bq": bq, "bk": bk, "compile_s":
                              round(time.perf_counter() - t, 2)})
        if a.compile_only:
            continue
        key = jax.random.PRNGKey(0)
        x = [jax.random.normal(jax.random.fold_in(key, i), (BH, T, D),
                               jnp.float32).astype(dtype) for i in range(4)]
        r = [jax.random.normal(jax.random.fold_in(key, 9 + i), (BH, 1, T),
                               jnp.float32) for i in range(2)]
        args = {"fwd": x[:3], "bwd": x + r}
        for _, pas, _, _, c in variants:        # warm every executable
            jax.block_until_ready(c(*args[pas]))
        with tempfile.TemporaryDirectory() as d:
            jax.profiler.start_trace(d)
            for _, pas, _, _, c in variants:
                for _ in range(REPEATS):
                    jax.block_until_ready(c(*args[pas]))
            jax.profiler.stop_trace()
            calls = custom_calls(d)
        emit({"shape": sname, "mosaic_calls_in_trace": len(calls),
              "first": [n for n, _ in calls[:4]]})
        i = 0
        for who, pas, bq, bk, c in variants:
            n = c.as_text().count('custom_call_target="tpu_custom_call"')
            mine, i = calls[i:i + n * REPEATS], i + n * REPEATS
            by = {}
            for j, (name, s) in enumerate(mine):
                # the yardstick's kernels carry no name of ours: the first
                # Mosaic call of a backward pass there is its forward
                k = (kind(name) if who != "yardstick" else
                     "fwd" if pas == "fwd" or j % n == 0 else "bwd_rest")
                by.setdefault(k, []).append(s)
            emit({"shape": sname, "who": who, "pass": pas, "bq": bq, "bk": bk,
                  "ms": {k: round(1e3 * min(_per_call(v, REPEATS)), 4)
                         for k, v in by.items()}})
        if i != len(calls):
            emit({"shape": sname, "warning": f"{len(calls)} Mosaic calls in "
                  f"the trace, {i} expected"})
    return 0


def _per_call(seconds, repeats):
    """Seconds of one kind of kernel per call, one entry per repeat (a call
    may hold several kernels of the kind: the yardstick's backward)."""
    per = len(seconds) // repeats
    return [sum(seconds[r * per:(r + 1) * per]) for r in range(repeats)]


if __name__ == "__main__":
    raise SystemExit(main())
