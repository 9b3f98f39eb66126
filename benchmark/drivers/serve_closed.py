"""The closed-loop serve driver: in one process, the model behind
``ModelRegistry.register_generate`` -> ``GenerateWorker`` -> ``DecodeProgram``
and ``InferenceServer`` on a loopback port, and ``clients`` threads that each
``POST /v1/models/lm:generate`` (NDJSON stream) and send their next request
when the last one has ended: callers that each wait for a reply.

Times are taken at the client: a request's clock starts when it is written to
the socket, its first token is the first NDJSON line read. Every seed sends
the same set of (prompt length, new tokens) pairs, in another order, with
other token ids. After the window closes no new request is sent and those in
flight are waited for; then the reference runs once over a sample of the
finished requests, the longest among them, and reads how far each served
token's logit lies below the reference's best.
"""

from __future__ import annotations

import gc
import http.client
import json
import math
import threading
import time
from typing import List

import numpy as np

from benchmark.harness import compare, spec

END_TO_END = ("serve_tokens_per_s",)
MODEL = "lm"


def request_sizes(traffic: dict) -> List[tuple]:
    """The mix's fixed set of (prompt length, new tokens): drawn once from
    the traffic file's own ``sizes_seed``."""
    rs = np.random.default_rng(int(traffic["sizes_seed"]))
    p, m = traffic["prompt_len"], traffic["max_new"]
    n = int(traffic["requests"])
    if p["dist"] != "log_uniform" or m["dist"] != "uniform":
        raise ValueError("the generator knows log_uniform prompt lengths and "
                         "uniform new tokens")
    plen = np.exp(rs.uniform(math.log(p["lo"]), math.log(p["hi"]), n))
    plen = np.clip(np.rint(plen), p["lo"], p["hi"]).astype(int)
    new = rs.integers(int(m["lo"]), int(m["hi"]) + 1, n)
    return [(int(a), int(b)) for a, b in zip(plen, new)]


def make_requests(traffic: dict, vocab: int, seed: int) -> List[dict]:
    """``cycles`` blocks, each the whole set of sizes in an order of its own
    with ids of its own. The set is about what one window consumes, so every
    seed's window does the same work; a window that runs past it goes on into
    the next block, and no prompt is ever sent twice."""
    rs = np.random.default_rng(int(seed))
    sizes = request_sizes(traffic)
    return [{"prompt": rs.integers(0, vocab, sizes[i][0]).tolist(),
             "max_tokens": sizes[i][1]}
            for _ in range(int(traffic.get("cycles", 1)))
            for i in rs.permutation(len(sizes))]


class _Record:
    __slots__ = ("req", "t_send", "stamps", "lines", "status", "error")

    def __init__(self, req):
        self.req, self.t_send = req, None
        self.stamps: List[float] = []
        self.lines: List[bytes] = []
        self.status, self.error = None, None


def post(port: int, rec: _Record, timeout: float) -> None:
    """One request, read line by line; the clock starts as it is written."""
    body = json.dumps(rec.req).encode()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.connect()
        rec.t_send = time.perf_counter()
        conn.request("POST", f"/v1/models/{MODEL}:generate", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec.status = resp.status
        while True:
            line = resp.readline()
            if not line:
                break
            rec.stamps.append(time.perf_counter())
            rec.lines.append(line)
    except (OSError, http.client.HTTPException) as e:
        rec.error = repr(e)
    finally:
        conn.close()


def parse(rec: _Record) -> dict:
    """tokens, their stamps, and whether the request ended as asked."""
    toks, stamps, tail = [], [], None
    if rec.status == 200:
        for line, t in zip(rec.lines, rec.stamps):
            obj = json.loads(line)
            if "token" in obj:
                toks.append(int(obj["token"]))
                stamps.append(t)
            elif obj.get("done"):
                tail = obj
    ok = (tail is not None and tail.get("reason") == "length"
          and len(toks) == rec.req["max_tokens"])
    return {"tokens": toks, "stamps": stamps, "ok": ok,
            "reason": (tail or {}).get("reason", rec.error or rec.status)}


def setup(ctx) -> dict:
    import jax
    from deeplearning4j_tpu.serve import (GenerateConfig, InferenceServer,
                                          ModelRegistry)

    cell, cfg = ctx["cell"], ctx["cell"]["config"]
    traffic = cell["traffic"]
    fam = spec.module("families", cfg["family"])
    ref = spec.module("reference", cfg["reference"])
    words = ref.seed_words(ctx["seed"])
    t = time.perf_counter()
    model = fam.new_model(cfg, words, optimizer=False)
    jax.block_until_ready(model.params)
    ctx["log"](f"weights: {model.num_params()} parameters ({cfg['dtype']}) on "
               f"the device in {time.perf_counter() - t:.1f}s")
    t = time.perf_counter()
    reg = ModelRegistry()
    gw = reg.register_generate(
        MODEL, model, warm=True, capacity=int(traffic["capacity"]),
        config=GenerateConfig(decode_batch_max=int(traffic["decode_batch_max"]),
                              queue_limit=max(64, 2 * int(traffic["clients"]))))
    ctx["log"](f"register_generate: {gw.program.compiled_count} executables "
               f"warm (grid {len(gw.program.signature_grid())}) in "
               f"{time.perf_counter() - t:.1f}s")
    srv = InferenceServer(reg).start(port=0)
    reqs = make_requests(traffic, int(cfg["vocab_size"]), ctx["seed"])
    # a few requests through the whole path before the clock: the HTTP
    # threads, the engine's latency ledger, the first page allocations
    warm = [_Record({"prompt": r["prompt"][:32], "max_tokens": 4})
            for r in reqs[-4:]]
    threads = [threading.Thread(target=post, args=(srv.port, w, 120.0))
               for w in warm]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    bad = [p["reason"] for p in map(parse, warm) if not p["ok"]]
    if bad:
        raise RuntimeError(f"warm-up requests did not end as asked: {bad}")
    return {"model": model, "reg": reg, "gw": gw, "srv": srv, "reqs": reqs,
            "words": words, "ref": ref}


def window(ctx, s: dict) -> dict:
    from deeplearning4j_tpu.utils import bucketing

    traffic = ctx["cell"]["traffic"]
    gw, port, reqs = s["gw"], s["srv"].port, s["reqs"]
    tel = bucketing.telemetry()
    hits0 = dict(tel.bucket_hits)
    traces0 = tel.compiles("decode.step")
    compiles0 = ctx["compiles"].count
    stats0 = dict(gw.stats_counters)
    records: List[_Record] = []
    lock = threading.Lock()
    cursor = [0]
    t0 = time.perf_counter()
    t_close = t0 + ctx["seconds"]
    timeout = float(traffic.get("request_timeout_s", 120.0))

    def client():
        while True:
            with lock:
                if time.perf_counter() >= t_close:
                    return
                rec = _Record(reqs[cursor[0] % len(reqs)])
                cursor[0] += 1
                records.append(rec)
            post(port, rec, timeout)

    def trace_clock():
        while time.perf_counter() < t_close and ctx["tracer"].state != "done":
            ctx["tracer"].poll(time.perf_counter() - t0)
            time.sleep(0.05)
        ctx["tracer"].stop()

    threads = [threading.Thread(target=client, name=f"client-{i}")
               for i in range(int(traffic["clients"]))]
    if ctx["tracer"].enabled:
        threads.append(threading.Thread(target=trace_clock, name="trace-clock"))
    for th in threads:
        th.start()
    time.sleep(max(0.0, t_close - time.perf_counter()))
    # counters at the close of the window; requests in flight are then
    # waited for: one that comes late is late, not wrong
    hits1 = dict(tel.bucket_hits)
    stats1 = dict(gw.stats_counters)
    in_window_compiles = (ctx["compiles"].count - compiles0) \
        + (tel.compiles("decode.step") - traces0)
    for th in threads:
        th.join(timeout=timeout + 60.0)
    if any(th.is_alive() for th in threads):
        raise RuntimeError("a client did not end a minute past its time-out")

    done = [(rec, parse(rec)) for rec in records]
    # the traced run's host-clock facts stop where the profiler starts:
    # stopping it takes seconds of the interpreter the engine shares
    cut = min(t_close, ctx["tracer"].started_at or t_close)
    ttft, tpot, events, tokens_in = [], [], [], 0
    for rec, p in done:
        plen = len(rec.req["prompt"])
        for i, t in enumerate(p["stamps"]):
            tokens_in += t <= t_close
            if t <= cut:
                events.append((plen, i))
        if p["ok"] and p["stamps"][-1] > cut and cut < t_close:
            continue                # ended under the profiler: not a clean time
        if p["ok"]:
            ttft.append((p["stamps"][0] - rec.t_send) * 1e3)
            if len(p["stamps"]) > 1:
                tpot.append((p["stamps"][-1] - p["stamps"][0]) * 1e3
                            / (len(p["stamps"]) - 1))
        else:
            ttft.append(math.inf)       # missing: beyond every limit
            tpot.append(math.inf)
    failed = sum(1 for _, p in done if not p["ok"])
    if failed:
        ctx["log"](f"{failed} of {len(done)} requests did not end as asked: "
                   f"{sorted({str(p['reason']) for _, p in done if not p['ok']})}")

    def site(hits, name):
        return sum(c for (s_, _), c in hits.items() if s_ == name)

    decode_disp = site(hits1, "serve.gen.decode") - site(hits0, "serve.gen.decode")
    prefill_disp = site(hits1, "serve.gen.prefill") - site(hits0, "serve.gen.prefill")
    generated = stats1["generated"] - stats0["generated"]
    firsts = sum(1 for _, p in done if p["stamps"] and p["stamps"][0] <= t_close)
    s["finished"] = [(rec.req, p["tokens"]) for rec, p in done if p["ok"]]
    return {
        "attempted": len(done), "failed": failed,
        "metrics": {"serve_tokens_per_s": tokens_in / ctx["seconds"]},
        "facts": {
            # a closed loop at saturation: the tails swing with the smallest
            # change (10% and 6% between runs of one code), so they stand
            # among the per-layer metrics and the rate is the end-to-end one
            "ttft_p95_ms": _p95(ttft), "tpot_p95_ms": _p95(tpot),
            "window_s": cut - t0, "tokens": len(events),
            "token_events": events, "requests": len(done),
            "compiles_in_window": in_window_compiles,
            # a stream's first token comes out of its last prefill chunk, the
            # others out of decode steps
            "decode_rows": max(generated - firsts, 0),
            "decode_dispatches": decode_disp,
            "prefill_dispatches": prefill_disp,
            "max_occupancy": stats1["max_occupancy"],
        },
    }


def _p95(values: List[float]) -> float:
    """Nearest-rank 95th percentile over all requests; where more than one
    request in twenty is missing it has no finite value, and the run reports
    the request time-out's order of magnitude instead."""
    if not values:
        return 1e9
    v = sorted(values)[max(0, math.ceil(0.95 * len(values)) - 1)]
    return v if math.isfinite(v) else 1e9


def free(s: dict) -> None:
    s.pop("srv").stop()                      # shuts the registry down too
    gw = s.pop("gw")
    gw.program.pools = None
    model = s.pop("model")
    model.params = model.state = None
    s.pop("reg")
    del gw, model
    gc.collect()


def sample(finished: List[tuple], seed: int, k: int) -> List[tuple]:
    """The longest finished request and ``k - 1`` more drawn from the seed."""
    if not finished:
        return []
    order = sorted(range(len(finished)), key=lambda i: -(
        len(finished[i][0]["prompt"]) + len(finished[i][1])))
    rs = np.random.default_rng(int(seed) + 1)
    rest = [int(i) for i in rs.permutation(order[1:])[:k - 1]]
    return [finished[i] for i in [order[0]] + rest]


def check(ctx, s: dict) -> dict:
    import jax
    import jax.numpy as jnp

    cell, cfg = ctx["cell"], ctx["cell"]["config"]
    ref, chk = s["ref"], cell["check"]
    picked = sample(s.get("finished", []), ctx["seed"], int(chk["sample"]))
    if not picked:
        return compare.verdict({"served_logit_gap": math.inf}, chk["limits"])
    T, N = int(chk["pad_len"]), int(chk["pad_new"])
    ids = np.zeros((len(picked), T), np.int32)
    pos = np.zeros((len(picked), N), np.int32)
    tok = np.zeros((len(picked), N), np.int32)
    live = np.zeros((len(picked), N), bool)
    for r, (req, toks) in enumerate(picked):
        seq = req["prompt"] + toks[:-1]
        if len(seq) > T or len(toks) > N:
            raise ValueError("a request is longer than the check's padding")
        ids[r, :len(seq)] = seq
        pos[r, :len(toks)] = len(req["prompt"]) - 1 + np.arange(len(toks))
        tok[r, :len(toks)] = toks
        live[r, :len(toks)] = True
    dtype = jnp.dtype(cfg["dtype"])
    w = jax.jit(lambda x: {k: v.astype(jnp.float32) for k, v in
                           ref.make_weights(cfg, x, dtype).items()})(s["words"])
    key = ref.cfg_key(cfg)
    t = time.perf_counter()
    logits = ref.logits_jit(key, None, w, ids, pos)          # [R, N, V]
    best = jnp.max(logits, -1)

    def judge(tokens, what):
        got = jnp.take_along_axis(
            logits, jnp.asarray(tokens)[..., None], -1)[..., 0]
        gaps = np.where(live, np.asarray(best - got), 0.0)
        ctx["log"](f"{what}: {int(live.sum())} tokens of {len(picked)} "
                   f"requests; {int((gaps > 0).sum())} are not the "
                   "reference's first choice")
        r, j = np.unravel_index(int(np.argmax(gaps)), gaps.shape)
        return compare.verdict(
            {"served_logit_gap": float(gaps[r, j])}, chk["limits"],
            {"served_logit_gap": f"request {r} token {j} of {int(live.sum())}"})

    out = judge(tok, "served")
    ctx["log"](f"reference took {time.perf_counter() - t:.1f}s")
    for control in ctx.get("controls", ()):
        # a control: the reference in the lower precision, put in the
        # program's place; it need not decode, its first choice at each
        # position of the same prompts and tokens is its answer
        out.setdefault("controls", {})[control] = judge(np.asarray(jnp.argmax(
            ref.logits_jit(key, control, w, ids, pos), -1)), control)
    return out
