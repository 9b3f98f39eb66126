"""Device time of the three flash kernels by tile size and by addressing, read
from a profiler trace on the chip (PERF.md section 5 holds the tables this
printed; the caps in ``ops/flash_attention.py`` ``_MAX_BLOCK`` stand on it).

    python tools/flash_tile_sweep.py [--shapes gpt2m-f32,...] [--blocks 128,256,512 | chosen]
        [--baseline path/to/another/flash_attention.py] [--yardstick] [--layer]
        [--out chiprun_out/flash_sweep.jsonl]
    JAX_PLATFORMS=cpu python tools/flash_tile_sweep.py --compile-only

A kernel variant is one jitted call of one kernel over pre-padded operands,
causal, no mask: ``ours`` over three ``[B, T, H*D]`` arrays, ``fused`` over the
one ``[B, T, 3*H*D]`` projection the attention layer hands the kernels,
``baseline`` (``--baseline``: another copy of the kernel module, the parent
commit's) over whatever layout that module's kernels take. ``--layer`` adds,
for this tree and for the baseline, one attention layer's forward and backward
through the public entry point, from the fused projection to its cotangent:
its ``all`` is every device operation of the program, so ``all`` less the
kernels is what the layout costs around them (**kernels + copies**).
``--yardstick`` times jax.experimental.pallas.ops.tpu.flash_attention, to say
what Mosaic allows at the same head width, not to adopt it.

All variants of a shape run three times inside one trace, each call under a
host annotation of its own; the device operations that start inside it are
the call's (a stand-alone call is dispatch-bound on the host's clock),
least of three. ``--compile-only`` compiles every variant for
a described v5e without a chip and prints which ones Mosaic refuses: no time
comes out of that.
"""

from __future__ import annotations

import argparse
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

SHAPES = {                      # (B, H, T, D, dtype): what runs today
    "gpt2m-f32-b8-t1024": (8, 16, 1024, 64, "float32"),
    "gpt2m-f32-b32-t256": (32, 16, 256, 64, "float32"),
    "gpt2m-bf16-b16-t1024": (16, 16, 1024, 64, "bfloat16"),
    "smoke-bf16-t2048-d128": (16, 16, 2048, 128, "bfloat16"),
    "twotower-f32-t4096-d128": (1, 32, 4096, 128, "float32"),
    "long-bf16-t8192": (2, 16, 8192, 64, "bfloat16"),
}
REPEATS = 3
KERNELS = ("fwd", "dq", "dkv")


def load_module(path):
    spec = importlib.util.spec_from_file_location("flash_baseline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _blocks(mod, shape, bq, bk, heads=1):
    B, H, T, D, dtype = shape
    if bq is not None:
        return {kn: (bq, bk) for kn in KERNELS}
    kw = {"heads": heads} if heads > 1 else {}   # what the chooser takes
    return {kn: mod.choose_blocks(kn, T, T, D, jnp.dtype(dtype).itemsize, **kw)
            for kn in KERNELS}


def kernel_fns(mod, shape, bq, bk, fused=False):
    """``({"fwd": f(*x), "bwd": f(*x)}, avals)`` at one tiling: the kernels
    of ``mod``, this tree's module (head-addressed ``[B, T, H*D]`` operands,
    or the one fused array) or an older one (``[B*H, T, D]``)."""
    B, H, T, D, dtype = shape
    dt = jnp.dtype(dtype)
    common = dict(q_pad=T, k_pad=T, t_real_k=T, causal=True,
                  scale=1.0 / D ** 0.5, q_off=0, k_off=0, interpret=False)
    rows = jax.ShapeDtypeStruct((B * H, 1, T), jnp.float32)
    if hasattr(mod, "_layout"):
        lay = mod._layout(H, D, fused=fused)
        chosen = _blocks(mod, shape, bq, bk, lay.heads)
        common["lay"] = lay
        x = jax.ShapeDtypeStruct(
            (B * H, T, D) if lay.transposed else (B, T, H * D), dt)
        qkv = (jax.ShapeDtypeStruct((B, T, 3 * H * D), dt),) if fused else (x,) * 3
        spread = (lambda a: (a[0],) * 3 + a[1:]) if fused else (lambda a: a)
    else:                       # the [B*H, T, D] module
        chosen = _blocks(mod, shape, bq, bk)
        common.update(D=D, dtype=dt)
        x = jax.ShapeDtypeStruct((B * H, T, D), dt)
        qkv, spread = (x,) * 3, (lambda a: a)

    def fwd(*a):
        return mod._fwd_pallas_call(*spread(a), bq=chosen["fwd"][0],
                                    bk=chosen["fwd"][1], **common)

    def bwd(*a):
        blocks = ({"dq_blocks": chosen["dq"], "dkv_blocks": chosen["dkv"]}
                  if "lay" in common else {"blocks": chosen}
                  if hasattr(mod, "choose_blocks") else {"bq": bq, "bk": bk})
        return mod._bwd_pallas_calls(*spread(a), t_real_q=T, **blocks,
                                     **common)

    return ({"fwd": fwd, "bwd": bwd},
            {"fwd": qkv, "bwd": qkv + (x, rows, rows)})


def layer_fns(mod, shape, bq, bk):
    """One attention layer's flash call as the model makes it, from the
    fused projection [B, T, 3*H*D] to [B, T, H*D] and back to the
    projection's cotangent, through ``mod``'s public entry point."""
    B, H, T, D, dtype = shape
    kw = dict(causal=True, block_q=bq, block_k=bk)
    if hasattr(mod, "flash_attention_qkv"):
        attn = lambda qkv: mod.flash_attention_qkv(qkv, H, **kw)  # noqa: E731
    else:
        def attn(qkv):
            q, k, v = jnp.split(qkv.reshape(B, T, 3 * H, D), 3, axis=2)
            return mod.flash_attention(q, k, v, **kw).reshape(B, T, H * D)

    def layer(qkv, g):
        out, vjp = jax.vjp(attn, qkv)
        return out, vjp(g)[0]

    dt = jnp.dtype(dtype)
    return ({"layer": layer},
            {"layer": (jax.ShapeDtypeStruct((B, T, 3 * H * D), dt),
                       jax.ShapeDtypeStruct((B, T, H * D), dt))})


def yardstick_fns(shape, bq, bk):
    from jax.experimental.pallas.ops.tpu import flash_attention as up

    B, H, T, D, dtype = shape
    sizes = up.BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
        block_q_dkv=bq, block_k_major_dq=bk, block_k_dq=bk, block_q_dq=bq)

    def attn(q, k, v):
        return up.flash_attention(q, k, v, causal=True,
                                  sm_scale=1.0 / D ** 0.5, block_sizes=sizes)

    def fwd_bwd(q, k, v, do):
        return jax.vjp(attn, q, k, v)[1](do)

    x = jax.ShapeDtypeStruct((B, H, T, D), jnp.dtype(dtype))
    return {"fwd": attn, "bwd": fwd_bwd}, {"fwd": (x,) * 3, "bwd": (x,) * 4}


def calls_by_annotation(trace_dir, prefix):
    """annotation name -> the device operations that ENDED inside it, in
    time order: (name, seconds). The host's annotations and the device's
    operations lie on one clock (benchmark/harness/trace.py) and a call ends
    in ``block_until_ready`` inside its annotation; in a process's first
    trace the device's stamps ran some tenths of a millisecond early, so a
    call's first kernel began "before" its annotation and was counted to the
    call before: an operation's end is the safer mark, and ``main`` throws a
    first trace away."""
    from benchmark.harness import trace

    planes = trace.load_xplane(trace.find_xplane(trace_dir))
    spans = sorted((s, s + d, n) for p in trace.host_planes(planes)
                   for l in p["lines"] for n, s, d in l["events"]
                   if n.startswith(prefix))
    ops = sorted((e for p in trace.device_planes(planes)[:1]
                  for e in trace.op_events(p)), key=lambda e: e[1] + e[2])
    out, i = {}, 0
    for lo, hi, name in spans:
        while i < len(ops) and ops[i][1] + ops[i][2] < lo:
            i += 1
        j = i
        while j < len(ops) and ops[j][1] + ops[j][2] < hi:
            j += 1
        out[name], i = [(n, d / 1e9) for n, _, d in ops[i:j]], j
    return out


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
    ap.add_argument("--layer", action="store_true",
                    help="also one layer's forward and backward, whole")
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

    def placed(avals):
        return [jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
                for x in avals]

    for sname in a.shapes.split(","):
        shape = SHAPES[sname]
        B, H, T, D, dtype = shape
        item = jnp.dtype(dtype).itemsize
        heads = ours.heads_per_block(H, D) or 1
        emit({"shape": sname, "heads_per_block": ours.heads_per_block(H, D),
              "chosen": {kn: ours.choose_blocks(kn, T, T, D, item,
                                                heads=heads)
                         for kn in KERNELS}})
        pairs = [(bq, bk) for bq in sizes for bk in sizes
                 if T % bq == 0 and T % bk == 0] or [(None, None)]
        given = [p for p in pairs if p[0]]
        todo = [("ours", lambda s, q, k: kernel_fns(ours, s, q, k), pairs),
                ("fused", lambda s, q, k: kernel_fns(ours, s, q, k, True),
                 pairs)]
        if a.layer:
            todo.append(("ours", lambda s, q, k: layer_fns(ours, s, q, k),
                         pairs))
        if base is not None:
            its = [p for p in given if base_blocks is None or p in base_blocks]
            if hasattr(base, "choose_blocks"):
                its = its or pairs
            todo.append(("baseline",
                         lambda s, q, k: kernel_fns(base, s, q, k), its))
            if a.layer:
                todo.append(("baseline",
                             lambda s, q, k: layer_fns(base, s, q, k), its))
        if a.yardstick:
            todo.append(("yardstick", yardstick_fns,
                         [p for p in given if max(p) <= 512]))
        variants = []           # (who, pass, bq, bk, compiled, avals)
        for who, make, its_pairs in todo:
            for bq, bk in its_pairs:
                fns, avals = make(shape, bq, bk)
                for pas, fn in fns.items():
                    t = time.perf_counter()
                    try:
                        c = jax.jit(fn).lower(*placed(avals[pas])).compile()
                    except Exception as e:      # noqa: BLE001
                        emit({"shape": sname, "who": who, "pass": pas,
                              "bq": bq, "bk": bk, "compile_error":
                              str(e).strip().splitlines()[-1][:300]})
                        continue
                    variants.append((who, pas, bq, bk, c, avals[pas]))
                    if a.compile_only:
                        emit({"shape": sname, "who": who, "pass": pas,
                              "bq": bq, "bk": bk, "compile_s":
                              round(time.perf_counter() - t, 2)})
        if a.compile_only:
            continue
        key = jax.random.PRNGKey(0)
        made = {}

        def arrays(avals):
            return [made.setdefault(
                (i, x.shape, str(x.dtype)), jax.random.normal(
                    jax.random.fold_in(key, i), x.shape,
                    jnp.float32).astype(x.dtype)) for i, x in enumerate(avals)]

        for *_, c, avals in variants:           # warm every executable
            jax.block_until_ready(c(*arrays(avals)))
        if sname == a.shapes.split(",")[0]:     # a trace nobody reads
            with tempfile.TemporaryDirectory() as d:
                jax.profiler.start_trace(d)
                jax.block_until_ready(variants[0][4](*arrays(variants[0][5])))
                jax.profiler.stop_trace()
        with tempfile.TemporaryDirectory() as d:
            jax.profiler.start_trace(d)
            for i, (*_, c, avals) in enumerate(variants):
                args = arrays(avals)
                for r in range(REPEATS):
                    with jax.profiler.TraceAnnotation(f"sweep.{i}.{r}"):
                        jax.block_until_ready(c(*args))
            jax.profiler.stop_trace()
            calls = calls_by_annotation(d, "sweep.")
        for i, (who, pas, bq, bk, c, _) in enumerate(variants):
            per = []            # one {kind: seconds} a repeat
            for r in range(REPEATS):
                by = {}
                for name, s in calls.get(f"sweep.{i}.{r}", []):
                    by["all"] = by.get("all", 0.0) + s
                    if "tpu_custom_call" not in name:
                        continue
                    # the yardstick's kernels carry no name of ours: the
                    # first Mosaic call of a backward pass is its forward
                    k = (kind(name) if who != "yardstick" else
                         "fwd" if pas == "fwd" or "fwd" not in by
                         else "bwd_rest")
                    by[k] = by.get(k, 0.0) + s
                per.append(by)
            rec = {"shape": sname, "who": who, "pass": pas, "bq": bq,
                   "bk": bk, "ms": {k: round(1e3 * min(p.get(k, 0.0)
                                                       for p in per), 4)
                                    for k in sorted(set().union(*per))}}
            if pas == "layer":
                ms = rec["ms"]
                rec["ms"]["copies"] = round(ms["all"] - sum(
                    ms.get(k, 0.0) for k in KERNELS), 4)
            emit(rec)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
