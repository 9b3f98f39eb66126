"""Benchmarks for the BASELINE.md configs, run on the real chip.

Emits one JSON line per sub-benchmark as it completes, then ONE final JSON
line ``{"metric", "value", "unit", "vs_baseline", "extras": [...]}`` whose
headline is ResNet50 images/sec (BASELINE config #2, the north-star metric)
and whose ``extras`` array carries every measured metric, including MFU.

Covered (BASELINE.md "Baselines to measure"):
  #1 LeNet-5 MNIST MultiLayerNetwork            -> samples/sec
  #2 zoo ResNet50 ComputationGraph @ 224^2      -> images/sec + analytic MFU
  #3 GravesLSTM char-RNN (TextGenerationLSTM)   -> tokens/sec + analytic MFU
  #5 Word2Vec skip-gram negative sampling       -> pairs/sec
(#4, multi-device ResNet50, needs >1 chip; the driver validates the sharded
path separately via __graft_entry__.dryrun_multichip.)

The reference publishes no numbers (BASELINE.md), so each ``vs_baseline`` is
measured against a documented NOMINAL estimate of what the reference's
nd4j-cuda path sustains on a V100 — a fixed yardstick that keeps the ratio
comparable across rounds until a true baseline is measured:
  LeNet-5    10,000 samples/sec  (r01/r02 yardstick, unchanged)
  ResNet50      360 images/sec   (public V100 fp32 ResNet50 training rate;
                                  the reference's cuDNN path is at best this)
  char-RNN  100,000 tokens/sec   (cuDNN LSTM 2x256, T=50, V100-class)
  Word2Vec  500,000 pairs/sec    (SkipGram.java on a fast multicore host)

MFU conventions: ResNet50 uses ANALYTIC train FLOPs (2*MACs forward, x3 for
fwd+bwd) so the number is comparable to published MFU figures; the LSTM
bench instead uses XLA's own cost analysis of the compiled step (after
fusion the analytic x3 overcounts what executes) against the bf16 roofline
(jax's default TPU matmul precision multiplies f32 inputs in bf16). Peak is
looked up from the device kind.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# BENCH_SMOKE=1: tiny shapes + few steps, for CPU validation of the harness
# itself (tests / local runs). Real numbers come from the default config.
SMOKE = bool(int(os.environ.get("BENCH_SMOKE", "0")))

# DL4J_TPU_BENCH_BUDGET_S: per-metric wall-clock budget (seconds). Round 5's
# lenet5 run timed out at the subprocess kill (rc=124, no JSON) because the
# dispatch-latency microbench repeats 5 timing loops plus a chained variant
# with no notion of elapsed time. Now every bench arms a deadline at entry:
# _timed() shrinks its measure loop to fit the time remaining and optional
# variants (lenet5's chained arm, extra median reps) are skipped once the
# budget is spent — a full `python bench.py` always emits JSON for every
# metric. 0 disables the budget.
_BUDGET_S = float(os.environ.get("DL4J_TPU_BENCH_BUDGET_S", "120"))
_DEADLINE: float | None = None


def _budget_start():
    global _DEADLINE
    _DEADLINE = (time.perf_counter() + _BUDGET_S) if _BUDGET_S > 0 else None


def _budget_left() -> float:
    if _DEADLINE is None:
        return float("inf")
    return _DEADLINE - time.perf_counter()

NOMINAL = {
    "lenet5_mnist_train_throughput": 10_000.0,
    "resnet50_224_train_throughput": 360.0,
    "lstm_char_rnn_train_throughput": 100_000.0,
    "word2vec_skipgram_throughput": 500_000.0,
}

def _peak_flops(dtype: str) -> float | None:
    # single source of truth for the per-backend roofline (absorbed the
    # table this file carried since PR 3): obs/profile.py
    from deeplearning4j_tpu.obs import profile

    return profile.peak_flops(dtype)


def _mfu_from_cost(compiled, steps_per_sec: float) -> dict:
    """MFU from XLA's own cost analysis of an AOT-compiled step against the
    bf16 roofline (jax's default TPU matmul precision multiplies f32 inputs
    in bf16). Harvests through obs.profile so the same numbers land in the
    cost gauges. Returns {} when unavailable."""
    from deeplearning4j_tpu.obs import profile

    peak = _peak_flops("bfloat16")
    entry = profile.harvest_compiled("bench.step", compiled, key="bench")
    if not peak or not entry or not entry.get("flops"):
        return {}
    return {"mfu": round(entry["flops"] * steps_per_sec / peak, 4),
            "xla_gflops_per_step": round(entry["flops"] / 1e9, 2)}


def _timed(run, warmup_steps: int = 5, steps: int = 30):
    """run(n) executes n steps and blocks on the result. Returns (sec, steps).

    Budget-aware: the timed warmup yields a per-step estimate, and the
    measure loop is clamped so warmup + measure fit the bench's remaining
    DL4J_TPU_BENCH_BUDGET_S (never below 1 step — a shrunk-but-measured
    number beats a killed subprocess with no JSON). The PRE-FLIGHT check
    matters as much as the clamp: first-compile time counts against the
    budget too, so a call that starts past the deadline collapses to the
    1-warmup/1-step minimum instead of running its full warmup (round 5's
    lenet5 rc=124 was five full reps stacked after a long compile, each
    only checking the budget on the way OUT)."""
    if SMOKE:
        warmup_steps, steps = 1, 2
    if _budget_left() <= 0:
        warmup_steps, steps = 1, 1
    t0 = time.perf_counter()
    run(warmup_steps)
    per_step = (time.perf_counter() - t0) / max(warmup_steps, 1)
    left = _budget_left()
    if left != float("inf") and per_step > 0:
        steps = max(1, min(steps, int(left / per_step)))
    t0 = time.perf_counter()
    run(steps)
    return time.perf_counter() - t0, steps


# ---------------------------------------------------------------------------
# Analytic FLOPs
# ---------------------------------------------------------------------------

def _graph_fwd_flops_per_example(cg) -> float:
    """2*MACs of the conv/dense compute in one forward pass of one example,
    walked from the resolved ComputationGraph shapes."""
    from deeplearning4j_tpu.nn.layers.convolution import (
        Conv2D, DepthwiseConv2D, SeparableConv2D)

    total = 0.0
    for name in cg.topo_order:
        v = cg.rt[name]
        if not v.spec.is_layer():
            continue
        cfg, it = v.config, v.input_types[0]
        ot = cg.vertex_types[name]
        if isinstance(cfg, SeparableConv2D):
            kh, kw = cfg.kernel
            mid = it.channels * cfg.depth_multiplier
            total += 2.0 * ot.height * ot.width * mid * kh * kw   # depthwise
            total += 2.0 * ot.height * ot.width * ot.channels * mid  # pointwise
        elif isinstance(cfg, DepthwiseConv2D):
            kh, kw = cfg.kernel
            total += 2.0 * ot.height * ot.width * ot.channels * kh * kw
        elif type(cfg) is Conv2D:
            kh, kw = cfg.kernel
            total += 2.0 * ot.height * ot.width * ot.channels * kh * kw * it.channels
        elif type(cfg).__name__ in ("Dense", "OutputLayer"):
            total += 2.0 * it.flat_size() * cfg.n_out
    return total


# ---------------------------------------------------------------------------
# Benchmarks
# ---------------------------------------------------------------------------

def bench_lenet5():
    """BASELINE #1 — LeNet-5 MNIST-shape training throughput."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import LeNet5
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork

    batch = 256
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.rand(batch, 28, 28, 1).astype(np.float32))
    y = jnp.asarray(np.eye(10, dtype=np.float32)[rs.randint(0, 10, batch)])

    model = MultiLayerNetwork(LeNet5(dtype="float32")).init()
    step = model._get_step_fn(False)
    st = [model.params, model.opt_state, model.state]
    rng = jax.random.PRNGKey(0)

    def run(n):
        loss = None
        for i in range(n):
            st[0], st[1], st[2], _, loss = step(
                st[0], st[1], st[2], jnp.asarray(i, jnp.int32), rng, x, y,
                None, None, ())
        float(loss)  # value fetch: a hard sync

    # dispatch-latency-bound microbench: single draws vary with host
    # jitter, so report the median of k timing loops with the spread —
    # stopping early (with at least one draw) once the budget is spent
    reps = []
    k = 1 if SMOKE else 5
    for _ in range(k):
        # pre-flight: the deadline is checked BEFORE committing to another
        # rep (compiles/warmup count against the budget), not only after
        if reps and _budget_left() <= 0:
            break
        dt, steps = _timed(run, warmup_steps=5, steps=50)
        reps.append(steps * batch / dt)
    reps.sort()
    per_step = reps[len(reps) // 2]

    # ROUND 5: fit()'s chained hot loop — K steps per dispatch (lax.scan
    # of the step body) amortizes the ~4 ms per-dispatch floor that
    # dominates this small model (docs/PERF.md LeNet). The chained arm
    # costs a SECOND full compile, so it is the first thing the budget
    # drops (round 5's rc=124: this compile + 5 more timing loops blew
    # the 900 s subprocess kill with no JSON emitted at all).
    out = {
        "metric": "lenet5_mnist_train_throughput",
        "median_of": len(reps),
        "per_step_dispatch_samples_per_sec": round(per_step, 1),
    }
    if _budget_left() < max(10.0, 0.2 * _BUDGET_S):
        sps = per_step
        out["chained_skipped"] = "bench budget exceeded (DL4J_TPU_BENCH_BUDGET_S)"
    else:
        K = 2 if SMOKE else 10
        chain = model._get_chain_step()
        xs = jnp.stack([x] * K)
        ys = jnp.stack([y] * K)
        st2 = st  # model.params were DONATED by the per-step loop; st is live

        def run_chained(n):
            losses = None
            for i in range(n):
                st2[0], st2[1], st2[2], losses = chain(
                    st2[0], st2[1], st2[2], jnp.asarray(i * K, jnp.int32),
                    jax.random.PRNGKey(i), xs, ys)
            float(losses[-1])  # value fetch
        reps2 = []
        for _ in range(k):
            if reps2 and _budget_left() <= 0:
                break
            dt, disp = _timed(run_chained, warmup_steps=2, steps=10)
            reps2.append(disp * K * batch / dt)
        reps2.sort()
        sps = reps2[len(reps2) // 2]
        out["chain_steps_per_dispatch"] = K
        out["spread_samples_per_sec"] = [round(reps2[0], 1), round(reps2[-1], 1)]
    out.update({
        "value": round(sps, 1),
        "unit": "samples/sec",
        "vs_baseline": round(sps / NOMINAL["lenet5_mnist_train_throughput"], 3),
    })
    return out


def bench_resnet50():
    """BASELINE #2 — zoo ResNet50 @ 224x224, images/sec + analytic MFU.

    Measured MFU (v5e, b128, bf16, round 4): ~0.28 — proven to be the
    chip's ceiling for this op mix by the round-4 null experiment
    (tools/null_resnet50.py: a from-scratch no-framework JAX step measures
    0.288; full head-to-head in docs/PERF.md "Null experiment"). Levers
    that mattered: batch 64->128, BatchNorm folded to per-channel bf16
    scale/shift with the stable shifted-stats form (0.13 -> 0.26), and
    round 4's REMOVAL of the round-3 strided-1x1 slice-then-matmul rewrite
    (+12% then, -12% on the round-4 toolchain). The MLPerf-style
    stem="space_to_depth" variant adds ~+5% but changes parameter layout
    away from reference parity, so the faithful conv7 stem stays here."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.zoo_graph import ResNet50
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    batch, classes, dtype = 128, 1000, "bfloat16"
    size = 224
    if SMOKE:
        batch, classes, size = 2, 10, 64
    cg = ComputationGraph(
        ResNet50(height=size, width=size, num_classes=classes, dtype=dtype)).init()
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.rand(batch, size, size, 3), jnp.bfloat16)
    y = jnp.asarray(np.eye(classes, dtype=np.float32)[rs.randint(0, classes, batch)])

    def run(n):
        loss = None
        for _ in range(n):
            loss = cg.fit_batch((x, y))
        float(loss)  # value fetch: a hard sync

    dt, steps = _timed(run, warmup_steps=3, steps=20)
    ips = steps * batch / dt
    fwd = _graph_fwd_flops_per_example(cg)
    out = {
        "metric": "resnet50_224_train_throughput",
        "value": round(ips, 1),
        "unit": "images/sec",
        "vs_baseline": round(ips / NOMINAL["resnet50_224_train_throughput"], 3),
        "batch": batch,
        "dtype": dtype,
        "analytic_fwd_gflops_per_image": round(fwd / 1e9, 2),
    }
    peak = _peak_flops(dtype)
    if peak:
        out["mfu"] = round(3.0 * fwd * ips / peak, 4)
        out["peak_tflops"] = peak / 1e12

    # TPU-optimized stem variant (SpaceToDepth + 4x4/s1 — NOT the reference
    # layout; reported separately, labeled). Costs a second full compile, so
    # it is opt-in: BENCH_S2D=1 (measured result recorded in docs/PERF.md).
    if not SMOKE and os.environ.get("BENCH_S2D") == "1":
        cg2 = ComputationGraph(
            ResNet50(height=size, width=size, num_classes=classes,
                     dtype=dtype, stem="space_to_depth")).init()

        def run2(n):
            loss = None
            for _ in range(n):
                loss = cg2.fit_batch((x, y))
            float(loss)

        dt2, steps2 = _timed(run2, warmup_steps=3, steps=20)
        ips2 = steps2 * batch / dt2
        fwd2 = _graph_fwd_flops_per_example(cg2)  # the variant's OWN flops
        out["s2d_stem_variant_images_per_sec"] = round(ips2, 1)
        if peak:
            out["s2d_stem_variant_mfu"] = round(3.0 * fwd2 * ips2 / peak, 4)
    return out


def bench_lstm_char_rnn():
    """BASELINE #3 — GravesLSTM char-RNN (TextGenerationLSTM), tokens/sec.

    Round-3 history: hoisting the input projection out of the scan (one
    [B*T,I]x[I,4H] MXU matmul up front, only the recurrent [B,H]x[H,4H]
    inside the scan — nn/layers/recurrent.py ``_input_proj``) took this from
    1.85M to tens of millions of tokens/sec on v5e. MFU here is computed
    from XLA's OWN cost analysis of the compiled step (the analytic
    3x-forward formula overcounts what XLA actually executes after fusion,
    yielding nonsense >1 values at these speeds)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import TextGenerationLSTM
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork

    vocab, timesteps, hidden, batch = 77, 50, 256, 128
    if SMOKE:
        hidden, batch = 32, 4
    rs = np.random.RandomState(0)
    ids = rs.randint(0, vocab, (batch, timesteps))
    x = jnp.asarray(np.eye(vocab, dtype=np.float32)[ids])
    y = jnp.asarray(np.eye(vocab, dtype=np.float32)[np.roll(ids, -1, axis=1)])

    def measure(policy):
        """One arm (scan or the weight-stationary fused kernel); the env
        flag is read at trace time, so a fresh model+compile per arm
        suffices. Returns (tokens/sec, compiled). A broken arm is an
        error: nothing here demotes the kernel to the scan."""
        os.environ["DL4J_TPU_FUSED_LSTM"] = "1" if policy == "fused" else "0"
        model = MultiLayerNetwork(TextGenerationLSTM(
            vocab_size=vocab, timesteps=timesteps, hidden=hidden,
            dtype="float32")).init()
        step = model._get_step_fn(False)
        rng = jax.random.PRNGKey(0)
        compiled = step.lower(
            model.params, model.opt_state, model.state,
            jnp.asarray(0, jnp.int32), rng, x, y, None, None, ()).compile()
        st = [model.params, model.opt_state, model.state]

        def run(n):
            loss = None
            for i in range(n):
                st[0], st[1], st[2], _, loss = compiled(
                    st[0], st[1], st[2], jnp.asarray(i, jnp.int32), rng,
                    x, y, None, None, ())
            float(loss)  # value fetch: a hard sync

        dt, steps = _timed(run, warmup_steps=5, steps=50)
        return steps * batch * timesteps / dt, compiled

    old = os.environ.get("DL4J_TPU_FUSED_LSTM")
    try:
        scan_arm = measure("scan")
        fused_arm = measure("fused")
    finally:
        if old is None:
            os.environ.pop("DL4J_TPU_FUSED_LSTM", None)
        else:
            os.environ["DL4J_TPU_FUSED_LSTM"] = old
    arms = {"scan": scan_arm, "fused": fused_arm}
    best = max(arms, key=lambda k: arms[k][0])
    tps, compiled = arms[best]
    out = {
        "metric": "lstm_char_rnn_train_throughput",
        "value": round(tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(tps / NOMINAL["lstm_char_rnn_train_throughput"], 3),
        "batch": batch,
        "timesteps": timesteps,
        "lstm_path": best,
        "arms_tokens_per_sec": {k: round(v[0], 1) for k, v in arms.items()},
    }
    out.update(_mfu_from_cost(compiled, tps / (batch * timesteps)))
    return out


def bench_word2vec():
    """BASELINE #5 — Word2Vec: fused-step pairs/sec AND end-to-end corpus
    tokens/sec (corpus -> vocab -> subsampled pairs -> device steps).

    ROUND-4 CORRECTION: rounds 1-3 reported ~3B pairs/sec for the fused
    step. That was a sync artifact (block_until_ready returned early on the
    toolchain of the time; the loss-value fetch is a hard sync — docs/PERF.md).
    The honest fused-step rate is ~4-5M pairs/sec, scatter-add bound; the
    earlier 'dispatch-bound below 16K pairs' batch guidance was derived
    from the phantom numbers and is superseded by the end-to-end split
    reported here.
    """
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nlp.embeddings import _sg_ns_step

    vocab_size, dim, batch, negative = 100_000, 100, 65536, 5
    if SMOKE:
        vocab_size, batch = 1000, 64
    rs = np.random.RandomState(0)
    params = {
        "syn0": jnp.asarray((rs.rand(vocab_size, dim).astype(np.float32) - 0.5) / dim),
        "syn1neg": jnp.zeros((vocab_size, dim), jnp.float32),
    }
    step = jax.jit(_sg_ns_step, donate_argnums=(0,))
    centers = jnp.asarray(rs.randint(0, vocab_size, batch, dtype=np.int32))
    contexts = jnp.asarray(rs.randint(0, vocab_size, batch, dtype=np.int32))
    negs = jnp.asarray(rs.randint(0, vocab_size, (batch, negative), dtype=np.int32))
    lr = jnp.asarray(0.025, jnp.float32)

    box = [params]

    def run(n):
        loss = None
        for _ in range(n):
            box[0], loss = step(box[0], centers, contexts, negs, lr)
        # ROUND-4 CORRECTION: block_until_ready here let ~50 queued steps
        # report as done, inflating rounds 1-3 to a phantom 2.95B pairs/sec;
        # the honest fused-step rate is ~4M pairs/sec (scatter-add bound).
        float(loss)  # value fetch: a hard sync

    dt, steps = _timed(run, warmup_steps=5, steps=50)
    pps = steps * batch / dt

    # ---- END-TO-END: corpus -> vocab -> subsampled pairs -> device steps.
    # The reference's bottleneck is exactly this host pipeline
    # (SequenceVectors.java:1021,1127 AsyncSequencer + per-pair threads);
    # here the host side is the vectorized numpy pair backend and device
    # dispatch is async, so pair-gen for batch k+1 overlaps the device
    # executing batch k (JAX's dispatch queue IS the double buffer).
    import time as _time

    from deeplearning4j_tpu.nlp.embeddings import (
        Word2Vec, _fast_pairs, subsample_probs)

    n_tokens, v_eff, sent_len = 2_000_000, 50_000, 1000
    if SMOKE:
        n_tokens, v_eff, sent_len = 20_000, 500, 100
    zipf = rs.zipf(1.3, n_tokens * 2)
    toks = zipf[zipf <= v_eff][:n_tokens].astype(np.int64)
    corpus = [[f"w{t}" for t in toks[i:i + sent_len]]
              for i in range(0, len(toks), sent_len)]

    m = Word2Vec(layer_size=dim, window=5, negative=negative,
                 min_word_frequency=1, epochs=1, seed=1,
                 batch_size=65536, pair_backend="numpy")
    t0 = _time.perf_counter()
    m.build_vocab(corpus)
    t_vocab = _time.perf_counter() - t0
    t0 = _time.perf_counter()
    m.fit(corpus)          # cold: includes XLA compiles of scan + tail
    jax.block_until_ready(m.params["syn0"])
    t_fit_cold = _time.perf_counter() - t0
    idx_seqs = m._index_sequences(corpus)
    t0 = _time.perf_counter()
    m._run_epochs(idx_seqs, 1)   # warm steady-state epoch (the number that
    jax.block_until_ready(m.params["syn0"])  # amortizes over real training)
    t_epoch_warm = _time.perf_counter() - t0
    e2e_tps_cold = n_tokens / (t_vocab + t_fit_cold)
    e2e_tps = n_tokens / (t_vocab / 2 + t_epoch_warm)  # vocab amortized over 2 epochs

    # host-only pair generation (same generator, no device steps) to
    # quantify the host/device split
    keep = subsample_probs(m.vocab, m.sample)
    t0 = _time.perf_counter()
    n_pairs = sum(len(c) for c, _t in _fast_pairs(
        idx_seqs, m.window, keep, np.random.RandomState(1)))
    t_host = _time.perf_counter() - t0

    return {
        "metric": "word2vec_skipgram_throughput",
        "value": round(pps, 1),
        "unit": "pairs/sec",
        "vs_baseline": round(pps / NOMINAL["word2vec_skipgram_throughput"], 3),
        "vocab": vocab_size,
        "dim": dim,
        "end_to_end_tokens_per_sec": round(e2e_tps, 1),
        "end_to_end_tokens_per_sec_cold": round(e2e_tps_cold, 1),
        "end_to_end_corpus_tokens": n_tokens,
        "end_to_end_split_sec": {
            "vocab_build": round(t_vocab, 3),
            "first_epoch_incl_compile": round(t_fit_cold, 3),
            "warm_epoch": round(t_epoch_warm, 3),
            "host_pairgen_alone": round(t_host, 3),
        },
        # first_epoch_incl_compile is XLA-compile-dominated (~5x warm,
        # r4); a persistent cache makes later PROCESSES warm — record the
        # ACTIVE cache dir so the cold number stays interpretable (read the
        # live jax config: it reflects JAX_COMPILATION_CACHE_DIR too)
        "compile_cache_dir": jax.config.jax_compilation_cache_dir or None,
        "host_pairgen_pairs_per_sec": round(n_pairs / max(t_host, 1e-9), 1),
    }


def bench_transformer():
    """Beyond-reference: TransformerLM train step, tokens/sec at T=2048
    (flash-attention path on TPU — the reference has no attention at all;
    recorded so the flagship extension's speed is a tracked number)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import TransformerLM
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork

    # MXU-saturating config (round 4): d_model 2048 fills the 128x128
    # systolic array; the Pallas flash backward keeps attention blockwise
    # in both directions. Round-3 ran d512/B8 (MFU 0.125); this config
    # measures 0.47+ on the same chip.
    vocab, T, d_model, heads, blocks, batch = 2048, 2048, 2048, 16, 8, 16
    if SMOKE:
        vocab, T, d_model, heads, blocks, batch = 64, 32, 32, 2, 2, 2
    model = MultiLayerNetwork(TransformerLM(
        vocab_size=vocab, max_len=T, d_model=d_model, n_heads=heads,
        n_blocks=blocks, updater={"type": "adam", "lr": 1e-4})).init()
    rs = np.random.RandomState(0)
    ids = rs.randint(0, vocab, (batch, T))
    x = jnp.asarray(ids)
    # sparse integer labels (round 4): the [B,T,V] one-hot tensor was 268MB
    # of host->device traffic per compile at this config; same loss math
    # (tests/test_sparse_labels.py asserts bit-equivalence)
    y = jnp.asarray(np.roll(ids, -1, axis=1).astype(np.int32))

    step = model._get_step_fn(False)
    rng = jax.random.PRNGKey(0)
    compiled = step.lower(model.params, model.opt_state, model.state,
                          jnp.asarray(0, jnp.int32), rng, x, y,
                          None, None, ()).compile()
    st = [model.params, model.opt_state, model.state]

    def run(n):
        loss = None
        for i in range(n):
            st[0], st[1], st[2], _, loss = compiled(
                st[0], st[1], st[2], jnp.asarray(i, jnp.int32), rng, x, y,
                None, None, ())
        float(loss)  # value fetch: a hard sync

    dt, steps = _timed(run, warmup_steps=3, steps=15)
    tps = steps * batch * T / dt
    out = {
        "metric": "transformer_lm_train_throughput",
        "value": round(tps, 1),
        "unit": "tokens/sec",
        "batch": batch,
        "seq_len": T,
        "d_model": d_model,
        "note": "beyond-reference flagship (flash-attention path)",
    }
    out.update(_mfu_from_cost(compiled, tps / (batch * T)))
    return out


def bench_serving_mixed():
    """Mixed-batch-size serving — the shape-bucketing tentpole's probe.

    Requests drawn from a fixed size list flow through ParallelInference
    batched mode; without bucketing every distinct coalesced batch size
    compiles a fresh inference executable, with it the ladder collapses
    them onto a handful of buckets. Reports WARM throughput (every bucket
    pre-touched) plus the observed trace/compile count and bucket-hit
    histogram from the utils.bucketing telemetry, so the trajectory tracks
    compile-count regressions alongside examples/sec."""
    from concurrent.futures import ThreadPoolExecutor

    from deeplearning4j_tpu.nn.input_type import InputType
    from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
    from deeplearning4j_tpu.nn.model import (
        MultiLayerConfiguration, MultiLayerNetwork)
    from deeplearning4j_tpu.parallel.inference import ParallelInference
    from deeplearning4j_tpu.utils import bucketing

    n_feat, hidden, classes = 32, 256, 10
    sizes = [1, 2, 3, 5, 7, 9, 12, 17, 21, 27]
    rounds = 8 if SMOKE else 50
    if SMOKE:
        hidden = 16
    conf = MultiLayerConfiguration(
        layers=(Dense(n_out=hidden, activation="relu"),
                OutputLayer(n_out=classes, activation="softmax")),
        input_type=InputType.feed_forward(n_feat),
        updater={"type": "sgd", "lr": 0.05},
    )
    model = MultiLayerNetwork(conf).init()
    rs = np.random.RandomState(0)
    reqs = [rs.rand(s, n_feat).astype(np.float32) for s in sizes]

    tel = bucketing.telemetry()
    tel.reset()
    max_bs = 64
    pi = ParallelInference(model, mode="batched", max_batch_size=max_bs)
    try:
        # warmup: touch every ladder rung up to the coalesce cap — the
        # worker merges queued requests, so a coalesced total can land on
        # any bucket <= max_batch_size, not just the per-request ones.
        # Pre-compiling every rung means the timed window adds ZERO traces.
        rungs, n = [], 1
        while n <= max_bs:
            b = min(bucketing.bucket_size(n), max_bs)
            rungs.append(b)
            n = b + 1
        for b in rungs:
            model.output(np.zeros((b, n_feat), np.float32))
        compiles_warm = tel.compiles("mln.output")
        with ThreadPoolExecutor(max_workers=8) as pool:
            t0 = time.perf_counter()
            futs = [pool.submit(pi.output, reqs[i % len(reqs)])
                    for i in range(rounds * len(sizes))]
            total = sum(len(f.result()) for f in futs)
            dt = time.perf_counter() - t0
    finally:
        pi.shutdown()
    snap = tel.snapshot()
    return {
        "metric": "serving_mixed_batch_throughput",
        "value": round(total / dt, 1),
        "unit": "examples/sec",
        "distinct_request_sizes": len(set(sizes)),
        "distinct_buckets": len(tel.buckets_used("pi.batched")),
        "buckets_warmed": len(set(rungs)),
        "observed_compiles": tel.compiles("mln.output"),
        "compiles_after_warmup": tel.compiles("mln.output") - compiles_warm,
        "bucket_hits": snap["bucket_hits"],
        "padded_examples": snap["padded_examples"],
        "real_examples": snap["real_examples"],
    }


def bench_serving_slo():
    """Serving-tier SLO bench — the serve/ continuous-batching scheduler
    under a closed-loop load generator.

    Three phases:
      ramp      concurrency sweep; each level hammers its own ModelWorker
                (fresh route -> clean quantiles) with mixed-size requests.
                Saturation = the level with the highest request rate.
      headline  p99 latency (ms) AT saturation, from the SLO tracker's
                dl4j_request_seconds P^2 quantiles — the same series the
                /metrics endpoint and burn-rate gauge are built on.
      overload  a deliberately starved worker (queue_limit=2) blasted by
                4x the saturation concurrency; gates that the scheduler
                SHEDS (dl4j_shed_total > 0) and the burn-rate gauge reacts
                rather than letting the queue grow without bound.

    Also gates the AOT contract end-to-end: after registry warm-up the
    entire load run must add ZERO compiles on the request path."""
    import threading
    from deeplearning4j_tpu import obs
    from deeplearning4j_tpu.nn.input_type import InputType
    from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
    from deeplearning4j_tpu.nn.model import (
        MultiLayerConfiguration, MultiLayerNetwork)
    from deeplearning4j_tpu.obs import slo
    from deeplearning4j_tpu.serve import (
        ModelRegistry, ModelWorker, ServeConfig, ShedError)
    from deeplearning4j_tpu.utils import bucketing

    n_feat, hidden, classes = 32, 256, 10
    max_batch = 32
    levels = [1, 2, 4, 8, 16]
    window_s = 1.0
    if SMOKE:
        hidden, max_batch = 16, 16
        levels = [1, 4]
        window_s = 0.25

    conf = MultiLayerConfiguration(
        layers=(Dense(n_out=hidden, activation="relu"),
                OutputLayer(n_out=classes, activation="softmax")),
        input_type=InputType.feed_forward(n_feat),
        updater={"type": "sgd", "lr": 0.05},
    )
    model = MultiLayerNetwork(conf).init()
    tel = bucketing.telemetry()
    tel.reset()

    cfg = ServeConfig(max_batch=max_batch, queue_limit=512,
                      default_deadline_s=1.0)
    reg = ModelRegistry(cfg)
    reg.register("slo", model, warm=True)          # import -> AOT warm
    compiles_warm = tel.compiles("mln.output")

    rs = np.random.RandomState(0)
    sizes = [1, 2, 3, 5, 8]
    reqs = [rs.rand(s, n_feat).astype(np.float32) for s in sizes]
    tracker = slo.slo_tracker()

    def closed_loop(worker, conc, duration, deadline_s):
        """conc threads, each submit-wait-resubmit until the window ends."""
        stats = {"ok": 0, "rows": 0, "shed": 0}
        lock = threading.Lock()
        stop = time.perf_counter() + duration

        def loop(tid):
            i = tid
            while time.perf_counter() < stop:
                try:
                    out = worker.submit(reqs[i % len(reqs)],
                                        deadline_s=deadline_s)
                    with lock:
                        stats["ok"] += 1
                        stats["rows"] += len(out)
                except ShedError:
                    with lock:
                        stats["shed"] += 1
                i += 1

        t0 = time.perf_counter()
        threads = [threading.Thread(target=loop, args=(t,), daemon=True)
                   for t in range(conc)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats["dt"] = time.perf_counter() - t0
        return stats

    ramp = []
    try:
        for conc in levels:
            # Per-level worker => per-level route; the shared LatencyModel
            # keeps admission estimates warm across levels.
            name = "slo_c%d" % conc
            worker = reg.register(name, model, warm=False)
            st = closed_loop(worker, conc, window_s, deadline_s=1.0)
            hist = tracker._hist.summary(route="serve." + name) or {}
            ramp.append({
                "concurrency": conc,
                "rps": round(st["ok"] / st["dt"], 1),
                "rows_per_s": round(st["rows"] / st["dt"], 1),
                "shed": st["shed"],
                "p50_ms": round(hist.get("p50", 0.0) * 1e3, 3),
                "p99_ms": round(hist.get("p99", 0.0) * 1e3, 3),
            })
            if not _budget_left():
                break

        sat = max(ramp, key=lambda r: r["rps"])

        # Forced-overload arm: starved queue, 4x saturation concurrency,
        # tight deadline. MUST shed and MUST move the burn-rate gauge.
        over_cfg = ServeConfig(max_batch=max(4, max_batch // 4),
                               queue_limit=2, default_deadline_s=0.05)
        over = ModelWorker("slo_overload", model, config=over_cfg,
                           latency=reg.latency)
        try:
            ost = closed_loop(over, max(8, 4 * sat["concurrency"]),
                              window_s, deadline_s=0.05)
        finally:
            over.shutdown()
        over_route = "serve.slo_overload"
        overload = {
            "ok": ost["ok"],
            "shed": ost["shed"],
            "shed_total": int(tracker._count.value(
                route=over_route, status="shed") or 0),
            "burn_rate": tracker.burn_rate(over_route) or 0.0,
        }
    finally:
        reg.shutdown()

    return {
        "metric": "serving_slo_p99",
        "value": sat["p99_ms"],
        "unit": "ms",
        "saturation_rps": sat["rps"],
        "saturation_rows_per_s": sat["rows_per_s"],
        "saturation_concurrency": sat["concurrency"],
        "p50_ms_at_saturation": sat["p50_ms"],
        "ramp": ramp,
        "buckets_used": len(
            tel.buckets_used("serve.slo_c%d" % sat["concurrency"])),
        "compiles_warm": compiles_warm,
        "request_path_compiles": tel.compiles("mln.output") - compiles_warm,
        "overload": overload,
        "slo": {"threshold_ms": tracker.threshold_s * 1e3,
                "objective": tracker.objective},
        "note": "p99 at saturation from dl4j_request_seconds quantiles; "
                "overload arm gates shed>0 and burn-rate reaction",
    }


def bench_generate():
    """Generative-serving bench — the token-level continuous-batching decode
    engine (serve/scheduler.GenerateWorker) under an OPEN-LOOP load
    generator: arrivals fire on a fixed schedule regardless of completions,
    so queueing delay shows up in TTFT instead of being absorbed by a
    closed loop's back-off.

    Three phases:
      ramp      arrival-rate sweep (streams/sec); per-level TTFT/ITL
                quantiles from the SLO tracker's dl4j_ttft_seconds /
                dl4j_itl_seconds P^2 series and tokens/s from the
                dl4j_tokens_generated_total counter delta.
      headline  p99 TTFT (ms) at the highest-tokens/s level.
      overload  a starved engine (queue_limit=2, decode_batch_max=2) under
                a deliberately hopeless deadline + arrival blast; gates
                that the engine SHEDS and the burn-rate gauge reacts.

    Also gates the decode AOT contract: after register_generate's warm,
    the whole load run must add ZERO compiles at the decode.step site."""
    import threading
    from deeplearning4j_tpu.models import TransformerLM
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.obs import slo
    from deeplearning4j_tpu.serve import (
        GenerateConfig, ModelRegistry, ShedError)
    from deeplearning4j_tpu.utils import bucketing

    vocab, d_model, n_blocks, max_len = 64, 64, 2, 256
    rates = [2.0, 6.0, 12.0]        # streams/sec, open-loop
    window_s = 3.0
    max_new = 24
    if SMOKE:
        d_model, max_len = 32, 64
        rates = [4.0]
        window_s = 0.6
        max_new = 6

    model = MultiLayerNetwork(TransformerLM(
        vocab_size=vocab, max_len=max_len, d_model=d_model, n_heads=4,
        n_blocks=n_blocks, dtype="float32"))
    model.init()
    tel = bucketing.telemetry()
    tel.reset()

    cfg = GenerateConfig(decode_batch_max=8, kv_page_tokens=16,
                         prefill_chunk=16, max_new_default=max_new,
                         queue_limit=256, default_deadline_s=30.0)
    reg = ModelRegistry()
    worker = reg.register_generate("gen", model, warm=True, config=cfg)
    compiles_warm = tel.compiles("decode.step")
    tracker = slo.slo_tracker()

    rs = np.random.RandomState(0)
    prompt_lens = [4, 9, 17, 30]
    prompts = [rs.randint(0, vocab, size=n).tolist() for n in prompt_lens]

    def open_loop(w, rate, duration, deadline_s=None):
        """Fire submissions on the arrival clock; each stream is consumed
        by its own thread (the consumer IS the chunked-HTTP reader)."""
        stats = {"streams": 0, "tokens": 0, "shed": 0, "shed_mid": 0}
        lock = threading.Lock()
        threads = []

        def consume(i):
            try:
                s = w.submit(prompts[i % len(prompts)], max_new=max_new,
                             deadline_s=deadline_s)
                toks = list(s)
                with lock:
                    stats["streams"] += 1
                    stats["tokens"] += len(toks)
                    if s.finish_reason == "shed:deadline":
                        stats["shed_mid"] += 1
            except ShedError:
                with lock:
                    stats["shed"] += 1

        t0 = time.perf_counter()
        n = int(rate * duration)
        for i in range(n):
            wait = t0 + i / rate - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            t = threading.Thread(target=consume, args=(i,), daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=60)
        stats["dt"] = time.perf_counter() - t0
        return stats

    def tok_count(route):
        return int(tracker._tokens.value(route=route) or 0)

    route = "generate.gen"
    ramp = []
    try:
        for rate in rates:
            tk0 = tok_count(route)
            st = open_loop(worker, rate, window_s)
            ttft = tracker._ttft.summary(route=route) or {}
            itl = tracker._itl.summary(route=route) or {}
            ramp.append({
                "arrival_rate": rate,
                "streams": st["streams"],
                "tokens_per_s": round((tok_count(route) - tk0) / st["dt"], 1),
                "ttft_p50_ms": round(ttft.get("p50", 0.0) * 1e3, 3),
                "ttft_p99_ms": round(ttft.get("p99", 0.0) * 1e3, 3),
                "itl_p50_ms": round(itl.get("p50", 0.0) * 1e3, 3),
                "itl_p99_ms": round(itl.get("p99", 0.0) * 1e3, 3),
                "shed": st["shed"],
            })
            if not _budget_left():
                break

        sat = max(ramp, key=lambda r: r["tokens_per_s"])
        # the zero-compile gate closes HERE: the overload worker below is
        # deliberately cold (warm=False) and its compiles are its own
        request_path_compiles = tel.compiles("decode.step") - compiles_warm

        # Overload arm: starved engine + hopeless deadline; after one
        # measured stream primes the ITL estimate, repriced admission MUST
        # shed (arrival or mid-stream) and move the burn-rate gauge.
        over_cfg = GenerateConfig(decode_batch_max=2, kv_page_tokens=16,
                                  prefill_chunk=16, max_new_default=max_new,
                                  queue_limit=2, default_deadline_s=30.0,
                                  min_samples=1)
        over = reg.register_generate("gen_over", model, warm=False,
                                     config=over_cfg)
        list(over.submit(prompts[0], max_new=max_new))  # prime the ITL model
        ost = open_loop(over, max(8.0, 4 * sat["arrival_rate"]),
                        min(window_s, 1.0), deadline_s=0.001)
        over_route = "generate.gen_over"
        overload = {
            "streams": ost["streams"],
            "shed_arrival": ost["shed"],
            "shed_midstream": ost["shed_mid"]
            + over.stats_counters["shed_midstream"],
            "shed_total": int(tracker._count.value(
                route=over_route, status="shed") or 0),
            "burn_rate": tracker.burn_rate(over_route) or 0.0,
        }
    finally:
        reg.shutdown()

    return {
        "metric": "generate_ttft_p99",
        "value": sat["ttft_p99_ms"],
        "unit": "ms",
        "tokens_per_s": sat["tokens_per_s"],
        "itl_p99_ms": sat["itl_p99_ms"],
        "arrival_rate_at_sat": sat["arrival_rate"],
        "ramp": ramp,
        "max_occupancy": worker.stats_counters["max_occupancy"],
        "generated_total": worker.stats_counters["generated"],
        "compiles_warm": compiles_warm,
        "request_path_compiles": request_path_compiles,
        "overload": overload,
        "note": "open-loop arrivals; TTFT/ITL from dl4j_ttft_seconds / "
                "dl4j_itl_seconds; overload arm gates shed>0 and burn-rate "
                "reaction; decode AOT gate: zero decode.step compiles after "
                "warm",
    }


def _cpu_mesh_env(n: int = 8) -> dict:
    """Env forcing an n-device host-platform mesh (must be set before jax
    initializes) — the dp_comms microbench models an R-replica exchange on
    a single host, like tests/conftest.py's 8 virtual CPU devices."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
    return env


def bench_dp_comms():
    """Tentpole probe — data-parallel gradient-exchange arms on an 8-replica
    mesh (virtual CPU devices; the ratios are static byte accounting, the
    step times are relative sanity only on CPU):

      dense      implicit XLA psum + replicated update (the default path)
      sharded    explicit reduce-scatter -> 1/R-shard update -> all-gather
      compressed ternary threshold encoding, replicated update
      comp+shard both — the full DCN-lean configuration

    Headline value is the gradient wire-byte reduction of comp+shard vs the
    dense all-reduce (the ISSUE gate: >= 4x; ternary packing gives 16x
    modulo shard padding). Param all-gather bytes are reported separately."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.input_type import InputType
    from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
    from deeplearning4j_tpu.nn.model import (
        MultiLayerConfiguration, MultiLayerNetwork)
    from deeplearning4j_tpu.parallel import MeshSpec, ParallelWrapper, make_mesh

    R = min(8, jax.device_count())
    n_feat, hidden, classes, batch = 64, 512, 10, 8 * R
    steps = 2 if SMOKE else 20
    if SMOKE:
        hidden = 32

    def build():
        conf = MultiLayerConfiguration(
            layers=(Dense(n_out=hidden, activation="tanh"),
                    OutputLayer(n_out=classes, activation="softmax")),
            input_type=InputType.feed_forward(n_feat),
            updater={"type": "adam", "lr": 0.01},
            seed=7,
        )
        return MultiLayerNetwork(conf).init()

    rs = np.random.RandomState(0)
    x = rs.rand(batch, n_feat).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rs.randint(0, classes, batch)]
    mesh = make_mesh(MeshSpec(data=R))

    arms = {}
    stats = {}
    for arm, (comp, shard) in (
        ("dense", (False, False)),
        ("sharded", (False, True)),
        ("compressed", (True, False)),
        ("compressed_sharded", (True, True)),
    ):
        model = build()
        pw = ParallelWrapper(model, mesh=mesh, grad_compress=comp,
                             sharded_update=shard, compress_threshold=1e-3)
        pw._replicate_model()
        xs, ys = pw._shard(x), pw._shard(y)
        runner = pw._exchange_runner()
        if runner is not None:
            runner.begin()
            step = lambda: runner.fit_batch(xs, ys, None, None)
        else:
            step = lambda: model._fit_batch(xs, ys, None, None)

        def run(n):
            loss = None
            for _ in range(n):
                loss = step()
            float(loss)  # value fetch: a hard sync

        dt, n_done = _timed(run, warmup_steps=2, steps=steps)
        arms[arm] = round(n_done * batch / dt, 1)
        # dense/implicit moves every gradient once (psum payload)
        stats[arm] = (runner.comm_stats() if runner is not None else None)
        if runner is not None:
            runner.finish()

    full = stats["compressed_sharded"]
    ratio = full["dense_bytes"] / max(full["wire_bytes"], 1)
    return {
        "metric": "dp_comms_grad_bytes_reduction",
        "value": round(ratio, 1),
        "unit": "x (dense grad bytes / compressed wire bytes, per step)",
        "replicas": R,
        "grad_dense_bytes": full["dense_bytes"],
        "grad_wire_bytes": full["wire_bytes"],
        "param_allgather_bytes": full["param_bytes"],
        "arms_samples_per_sec": arms,
        "note": ("virtual-CPU mesh: byte counts are exact (static), step "
                 "times are relative sanity only"),
    }


def bench_mesh_mfu():
    """MULTICHIP promoted (ISSUE 13) — the ONE mesh step program across
    (data, tensor, stage) shapes on an R-device mesh. Each arm trains the
    same MLP from the same seed on the same batch through
    parallel/mesh_step.MeshTrainer: params per the TP rules, optimizer
    moments sharded over the spare axes (arXiv 2004.13336), the gradient
    all-reduce rewritten per shape by GSPMD.

    Gates (tools/bench_smoke.sh):
      gate_tuned_ge_dp_baseline        the best measured shape >= the
                                       pure-DP (d=R,t=1,s=1) default —
                                       holds by construction (the default
                                       is in the race), which is the same
                                       contract the knob registry gives
                                       every tuned default
      gate_shape_parity                fixed-step losses match across every
                                       shape (same math, different layout)
      gate_zero_steady_state_compiles  no mln.step re-traces inside any
                                       arm's measured loop (the output
                                       sharding constraints pin the layout)

    dl4j_mfu per shape lands when the backend has a roofline (TPU); on the
    CPU smoke mesh the throughput ratios carry the gates and MFU is omitted
    rather than fabricated."""
    import jax

    from deeplearning4j_tpu.nn.input_type import InputType
    from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
    from deeplearning4j_tpu.nn.model import (
        MultiLayerConfiguration, MultiLayerNetwork)
    from deeplearning4j_tpu.parallel import MeshSpec, MeshTrainer
    from deeplearning4j_tpu.utils import bucketing

    R = min(8, jax.device_count())
    n_feat, hidden, classes = 64, (32 if SMOKE else 512), 10
    batch = 8 * R
    shapes = [(R, 1, 1)]
    if R >= 2 and R % 2 == 0:
        shapes += [(R // 2, 2, 1), (R // 2, 1, 2)]
    if R >= 4 and R % 4 == 0:
        shapes.append((R // 4, 2, 2))

    def build():
        conf = MultiLayerConfiguration(
            layers=(Dense(n_out=hidden, activation="tanh"),
                    OutputLayer(n_out=classes, activation="softmax")),
            input_type=InputType.feed_forward(n_feat),
            updater={"type": "adam", "lr": 0.01},
            seed=7,
        )
        return MultiLayerNetwork(conf).init()

    rs = np.random.RandomState(0)
    x = rs.rand(batch, n_feat).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rs.randint(0, classes, batch)]

    peak = _peak_flops("bfloat16")
    # analytic train FLOPs (2*MACs forward, x3 fwd+bwd), GLOBAL per step —
    # layout-independent, so cross-shape MFU compares pure efficiency
    train_flops = 3.0 * 2.0 * batch * (n_feat * hidden + hidden * classes)

    tel = bucketing.telemetry()
    arms, mfu, probes, retraces = {}, {}, {}, {}
    for d, t, s in shapes:
        key = f"d{d}t{t}s{s}"
        trainer = MeshTrainer(build(), MeshSpec(data=d, model=t, pipe=s))
        # fixed-step parity probe (compiles land here, outside the timing)
        probes[key] = [round(float(trainer.fit_batch(x, y)), 6)
                       for _ in range(3)]
        traced = tel.traces.get("mln.step", 0)

        def run(n, fit=trainer.fit_batch):
            loss = None
            for _ in range(n):
                loss = fit(x, y)
            float(loss)  # value fetch: a hard sync

        dt, n_done = _timed(run, warmup_steps=1, steps=2 if SMOKE else 20)
        retraces[key] = tel.traces.get("mln.step", 0) - traced
        sps = n_done * batch / dt
        arms[key] = round(sps, 1)
        if peak:
            mfu[key] = round(train_flops * (sps / batch) / (peak * R), 4)
        trainer.finish()

    base_key = f"d{R}t1s1"
    best_key = max(arms, key=arms.get)
    base = np.asarray(probes[base_key])
    dev = max(float(np.max(np.abs(np.asarray(p) - base)
                           / np.maximum(np.abs(base), 1e-9)))
              for p in probes.values())
    out = {
        "metric": "mesh_step_tuned_vs_dp",
        "value": round(arms[best_key] / max(arms[base_key], 1e-9), 3),
        "unit": "x samples/sec, best (d,t,s) over pure-DP (d=R,t=1,s=1)",
        "devices": R,
        "tuned_shape": best_key,
        "arms_samples_per_sec": arms,
        "shape_losses": probes,
        "parity_max_rel_dev": round(dev, 8),
        "steady_state_retraces": retraces,
        "gate_tuned_ge_dp_baseline": arms[best_key] >= arms[base_key],
        "gate_shape_parity": dev < 1e-3,
        "gate_zero_steady_state_compiles": all(
            v == 0 for v in retraces.values()),
    }
    if mfu:
        out["dl4j_mfu"] = mfu
        # land the per-shape MFU in the live gauge the cost layer owns
        from deeplearning4j_tpu.obs import metrics as obs_metrics

        g = obs_metrics.registry().gauge(
            "dl4j_mfu", "model FLOPs utilization: achieved flops/s at the "
            "site's step span over the bf16 roofline", ("site",))
        for k, v in mfu.items():
            g.set(v, site=f"mesh.step.{k}")
    return out


def bench_checkpoint():
    """Durable-checkpoint cycle (docs/ROBUSTNESS.md): atomic full-state save
    (tmp+fsync+rename, CRC over the final bytes) -> CRC validation ->
    full-state restore into a fresh model. The fsync makes this a real
    durability number, not a page-cache write; headline is the end-to-end
    cycle time for a ~1.1M-param MLP (what a save_every_n_iterations
    listener adds to a training step when it fires)."""
    import shutil
    import tempfile

    from deeplearning4j_tpu.nn.input_type import InputType
    from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
    from deeplearning4j_tpu.nn.model import (
        MultiLayerConfiguration, MultiLayerNetwork)
    from deeplearning4j_tpu.train import resilience

    n_feat, hidden, classes, batch = 64, (32 if SMOKE else 1024), 10, 32
    conf = MultiLayerConfiguration(
        layers=(Dense(n_out=hidden, activation="tanh"),
                OutputLayer(n_out=classes, activation="softmax")),
        input_type=InputType.feed_forward(n_feat),
        updater={"type": "adam", "lr": 0.01},
        seed=7,
    )
    model = MultiLayerNetwork(conf).init()
    rs = np.random.RandomState(0)
    x = rs.rand(batch, n_feat).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rs.randint(0, classes, batch)]
    model.fit((x, y), epochs=1, batch_size=batch)  # populate opt state

    workdir = tempfile.mkdtemp(prefix="bench_ckpt_")
    path = os.path.join(workdir, "checkpoint.zip")
    target = MultiLayerNetwork(conf).init()
    phases = {"save": [], "validate": [], "restore": []}
    try:
        def cycle(n):
            for _ in range(n):
                t0 = time.perf_counter()
                info = resilience.save_checkpoint(model, path)
                t1 = time.perf_counter()
                ok = resilience.validate_checkpoint(
                    path, crc=info["crc"], size=info["size"])
                t2 = time.perf_counter()
                resilience.load_state_into(target, path)
                t3 = time.perf_counter()
                if not ok:
                    raise RuntimeError("checkpoint failed its own CRC")
                phases["save"].append(t1 - t0)
                phases["validate"].append(t2 - t1)
                phases["restore"].append(t3 - t2)

        dt, n_done = _timed(cycle, warmup_steps=1, steps=2 if SMOKE else 10)
        size = os.path.getsize(path)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    med = {k: round(1e3 * sorted(v)[len(v) // 2], 2)
           for k, v in phases.items() if v}
    return {
        "metric": "checkpoint_cycle_ms",
        "value": round(1e3 * dt / n_done, 2),
        "unit": "ms per save+validate+restore cycle (fsync durable)",
        "checkpoint_bytes": size,
        "phase_median_ms": med,
        "params": sum(int(np.prod(s)) for s in (
            (n_feat, hidden), (hidden,), (hidden, classes), (classes,))),
    }


def bench_mnist_mlp():
    """Observability-overhead arm (ISSUE 5 gate: <= 2%): the SAME compiled
    MNIST-shape MLP fit loop with the full obs layer live (spans + registry
    + JSONL event log) vs DL4J_TPU_OBS=0. The env knob is read per call, so
    both arms share one process, one model and one executable — the delta
    is the layer itself, not compile or allocator noise."""
    import shutil
    import tempfile

    from deeplearning4j_tpu import obs
    from deeplearning4j_tpu.nn.input_type import InputType
    from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
    from deeplearning4j_tpu.nn.model import (
        MultiLayerConfiguration, MultiLayerNetwork)

    n_feat, hidden, classes, batch = 784, (32 if SMOKE else 256), 10, 128
    n_batches = 4 if SMOKE else 64
    epochs = 1 if SMOKE else 3
    conf = MultiLayerConfiguration(
        layers=(Dense(n_out=hidden, activation="relu"),
                OutputLayer(n_out=classes, activation="softmax")),
        input_type=InputType.feed_forward(n_feat),
        updater={"type": "sgd", "lr": 0.05},
        seed=7,
    )
    model = MultiLayerNetwork(conf).init()
    rs = np.random.RandomState(0)
    n = batch * n_batches
    X = rs.rand(n, n_feat).astype(np.float32)
    Y = np.eye(classes, dtype=np.float32)[rs.randint(0, classes, n)]

    workdir = tempfile.mkdtemp(prefix="bench_obs_")
    old = os.environ.get("DL4J_TPU_OBS")

    def arm(on: bool) -> float:
        os.environ["DL4J_TPU_OBS"] = "1" if on else "0"
        t0 = time.perf_counter()
        model.fit((X, Y), epochs=epochs, batch_size=batch)
        return time.perf_counter() - t0

    try:
        obs.configure_event_log(os.path.join(workdir, "events.jsonl"))
        arm(True)    # warmup: compiles + first-touch of span/event paths
        arm(False)
        on_times, off_times = [], []
        for _ in range(1 if SMOKE else 3):
            off_times.append(arm(False))
            on_times.append(arm(True))
            if _budget_left() <= 0:
                break
    finally:
        if old is None:
            os.environ.pop("DL4J_TPU_OBS", None)
        else:
            os.environ["DL4J_TPU_OBS"] = old
        obs.configure_event_log(None)
        shutil.rmtree(workdir, ignore_errors=True)
    t_on = sorted(on_times)[len(on_times) // 2]
    t_off = sorted(off_times)[len(off_times) // 2]
    overhead = (t_on - t_off) / t_off
    steps = epochs * n_batches
    # the cost report must resolve BEFORE the tuner arm's subprocesses run
    # (the lazy exemplars weakref the jitted step fn of THIS process)
    cost = obs.cost_report()
    tuner = _mnist_tuner_arm(model, X[:batch], Y[:batch])
    return {
        "metric": "mnist_mlp_obs_overhead",
        "value": round(100.0 * overhead, 2),
        "unit": "% fit wall-time, obs on vs DL4J_TPU_OBS=0 (gate: <= 2%)",
        "obs_on_samples_per_sec": round(steps * batch / t_on, 1),
        "obs_off_samples_per_sec": round(steps * batch / t_off, 1),
        "reps": len(on_times),
        "batches_per_arm": steps,
        # resolved while the model is still alive: the lazy cost exemplars
        # weakref the jitted step fn, so report-time resolution must happen
        # before the bench returns and drops it
        "cost": cost,
        "tuner": tuner,
    }


def _mnist_tuner_arm(model, x, y) -> dict:
    """Auto-tuner gate arm (ISSUE 9): successive-halving search over a
    small knob subspace for the SAME MLP, each trial in a fresh subprocess,
    winner persisted to a scratch tuning DB (the real flow, pointed at a
    temp path so a bench run never pollutes the user's DB). The gate is
    tuned >= default at EQUAL step budgets: when the measured winner is not
    the default it is re-confirmed head-to-head, and a winner that fails to
    reproduce is reverted to the default — tuning never ships a config it
    cannot defend, so the gate holds by construction and honestly."""
    import shutil
    import tempfile

    if _budget_left() < 15.0:
        return {"skipped": "bench budget exhausted before tuner arm"}
    from deeplearning4j_tpu import tune
    from deeplearning4j_tpu.tune import search as tsearch
    from deeplearning4j_tpu.tune import trial as ttrial

    workdir = tempfile.mkdtemp(prefix="bench_tune_")
    try:
        db = tune.TuningDB(os.path.join(workdir, "tunedb.zip"))
        overrides = ({"grad_accum": [1, 2]} if SMOKE else
                     {"grad_accum": [1, 2], "chain_steps": ["auto", "8"]})
        timeout = max(60.0, min(_budget_left() + 60.0, 600.0))
        entry = tune.tune_model(
            model, x, y, knob_names=tuple(overrides), overrides=overrides,
            db=db, base_steps=(2 if SMOKE else 8), warmup_steps=1,
            timeout_s=timeout)
        defaults = {n: tune.get(n).default for n in overrides}
        chosen = dict(entry["knobs"])
        tuned_obj = default_obj = entry["objective"]["steps_per_sec"]
        ratio, reverted = 1.0, False
        if chosen != defaults:
            spec = ttrial.build_spec(model, x, y, steps=(2 if SMOKE else 16),
                                     warmup_steps=1)
            confirm_def = tsearch.run_subprocess_trial(
                spec, defaults, timeout_s=timeout)
            confirm_tuned = tsearch.run_subprocess_trial(
                spec, chosen, timeout_s=timeout)
            default_obj = confirm_def.objective
            tuned_obj = confirm_tuned.objective
            ratio = (tuned_obj / default_obj) if default_obj > 0 else 0.0
            if not confirm_tuned.ok or ratio < 1.0:
                chosen, tuned_obj, ratio = defaults, default_obj, 1.0
                reverted = True
        return {
            "chosen_knobs": chosen,
            "default_knobs": defaults,
            "tuned_steps_per_sec": round(tuned_obj, 1),
            "default_steps_per_sec": round(default_obj, 1),
            "tuned_vs_default": round(ratio, 3),
            "gate_tuned_ge_default": ratio >= 1.0,
            "reverted_to_default": reverted,
            "trials": entry["trials"],
            "db_persisted": os.path.exists(db.path),
        }
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"[:300]}
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _cold_start_arm(arm: str, workdir: str) -> dict:
    """One cold-start measurement arm, executed in a FRESH process (spawned
    by bench_cold_start): builds the model from nothing and reports phase
    timings for the serving path (time-to-first-request) and the training
    path (time-to-first-step). ``prep`` is the offline arm that warms the
    ladder and persists the executable bundle the ``bundle`` arm restores."""
    from deeplearning4j_tpu.nn import aot
    from deeplearning4j_tpu.nn.input_type import InputType
    from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
    from deeplearning4j_tpu.nn.model import (
        MultiLayerConfiguration, MultiLayerNetwork)
    from deeplearning4j_tpu.parallel.inference import ParallelInference
    from deeplearning4j_tpu.utils import bucketing

    n_feat, hidden, classes, batch = 32, (16 if SMOKE else 64), 10, 16
    conf = MultiLayerConfiguration(
        layers=(Dense(n_out=hidden, activation="relu"),
                OutputLayer(n_out=classes, activation="softmax")),
        input_type=InputType.feed_forward(n_feat),
        updater={"type": "sgd", "lr": 0.05},
        seed=7,
    )
    rs = np.random.RandomState(0)
    x = rs.rand(batch, n_feat).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rs.randint(0, classes, batch)]
    req = rs.rand(5, n_feat).astype(np.float32)
    bundle = os.path.join(workdir, "cold_start.aotbundle")

    if arm == "prep":
        model = MultiLayerNetwork(conf).init()
        aot.warm_serving(model, batch)
        model.fit((x, y), epochs=1, batch_size=batch)  # warm hook compiles step
        info = aot.save_bundle(model, bundle)
        return {"arm": "prep", "saved": info is not None,
                "entries": (info or {}).get("entries", 0)}

    # the persistence gate (an env read since PR 21), kept outside the
    # timers so the headline tracks the request path
    t0 = time.perf_counter()
    validated = aot.persistence_allowed() if arm == "bundle" else None
    validation_ms = 1e3 * (time.perf_counter() - t0)

    tel = bucketing.telemetry()
    restored = 0
    t0 = time.perf_counter()
    model = MultiLayerNetwork(conf).init()
    if arm == "bundle":
        restored = aot.restore_bundle(model, bundle)
    # the ParallelInference ctor runs warm_serving itself when DL4J_TPU_AOT=1
    pi = ParallelInference(model, mode="batched", max_batch_size=batch)
    startup_ms = 1e3 * (time.perf_counter() - t0)

    c0 = tel.compiles("mln.output")
    t0 = time.perf_counter()
    out = pi.output(req)
    ttfr_ms = 1e3 * (time.perf_counter() - t0)
    request_compiles = tel.compiles("mln.output") - c0
    pi.shutdown()
    if out.shape != (len(req), classes):
        raise RuntimeError(f"bad serving output shape {out.shape}")

    fit_model = MultiLayerNetwork(conf).init()
    if arm == "bundle":
        restored += aot.restore_bundle(fit_model, bundle)
    c0 = tel.compiles("mln.step")
    t0 = time.perf_counter()
    fit_model.fit((x, y), epochs=1, batch_size=batch)
    ttfs_ms = 1e3 * (time.perf_counter() - t0)
    step_compiles = tel.compiles("mln.step") - c0

    from deeplearning4j_tpu import obs

    return {
        "arm": arm,
        "startup_ms": round(startup_ms, 1),
        "ttfr_ms": round(ttfr_ms, 1),
        "ttfs_ms": round(ttfs_ms, 1),
        "request_path_compiles": request_compiles,
        "fit_path_compiles": step_compiles,
        "restored_entries": restored,
        "validation_ms": round(validation_ms, 1),
        "persistence_validated": validated,
        # per-arm XLA cost + roofline view, resolved while the serving and
        # fit models are still alive (lazy exemplars weakref their targets)
        "cost": obs.cost_report(),
    }


def bench_cold_start():
    """Cold-start killer probe (AOT tentpole): time-to-first-request and
    time-to-first-step measured in FRESH subprocesses across three arms —

      none    lazy JIT only; the first request/step pays the XLA compile
      aot     DL4J_TPU_AOT=1; startup pre-compiles the bucket ladder, the
              first request is a warm dispatch (compile moved, not removed)
      bundle  AOT + executable bundle persisted by an offline ``prep`` arm
              and restored at startup: ZERO compiles anywhere on the
              request path (the acceptance gate)

    Headline is the warm-restore arm's TTFR; the gates (bundle TTFR
    strictly below no-AOT, zero request-path compiles) ride along so the
    trajectory catches regressions."""
    import shutil
    import subprocess
    import tempfile

    workdir = tempfile.mkdtemp(prefix="bench_cold_")
    timeout = (3 * _BUDGET_S + 300) if _BUDGET_S > 0 else 900
    here = os.path.abspath(__file__)

    def run_arm(arm: str) -> dict:
        env = dict(os.environ)
        # the tiny rng-free model would auto-chain its fit steps, which
        # bypasses per-step AOT dispatch by design — pin it off so the
        # arms compare the same dispatch path
        env["DL4J_TPU_CHAIN_STEPS"] = "0"
        env.pop("DL4J_TPU_AOT", None)
        env.pop("DL4J_TPU_AOT_BUNDLE", None)
        if arm != "none":
            env["DL4J_TPU_AOT"] = "1"
        if arm in ("prep", "bundle"):
            env["DL4J_TPU_AOT_BUNDLE"] = "1"
        try:
            proc = subprocess.run(
                [sys.executable, here, "--cold-arm", arm, "--cold-dir", workdir],
                capture_output=True, text=True, timeout=timeout, env=env,
                cwd=os.path.dirname(here))
        except subprocess.SubprocessError as e:
            return {"arm": arm, "error": f"{type(e).__name__}: {e}"[:300]}
        for line in reversed(proc.stdout.strip().splitlines()):
            try:
                obj = json.loads(line)
            except (json.JSONDecodeError, ValueError):
                continue
            if isinstance(obj, dict):
                return obj
        return {"arm": arm,
                "error": f"rc={proc.returncode}: {proc.stderr[-300:]}"}

    try:
        prep = run_arm("prep")
        arms = {a: run_arm(a) for a in ("none", "aot", "bundle")}
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    ok = all("error" not in m for m in arms.values()) and "error" not in prep
    result = {
        "metric": "cold_start_ttfr_ms",
        "unit": "ms to first serving response, fresh process "
                "(AOT + restored executable bundle arm)",
        "prep": prep,
        "arms": arms,
    }
    if not ok:
        result["error"] = "one or more arms failed"
        return result
    result["value"] = arms["bundle"]["ttfr_ms"]
    result["ttfr_speedup_vs_no_aot"] = round(
        arms["none"]["ttfr_ms"] / max(arms["bundle"]["ttfr_ms"], 1e-3), 1)
    result["ttfs_speedup_vs_no_aot"] = round(
        arms["none"]["ttfs_ms"] / max(arms["bundle"]["ttfs_ms"], 1e-3), 1)
    result["gate_ttfr_bundle_lt_none"] = (
        arms["bundle"]["ttfr_ms"] < arms["none"]["ttfr_ms"])
    result["gate_zero_request_compiles"] = (
        arms["bundle"]["request_path_compiles"] == 0
        and arms["bundle"]["fit_path_compiles"] == 0)
    return result


def bench_vector_search():
    """ANN search-tier acceptance probe (search tentpole): a 100k x 64
    clustered corpus served by BOTH tiers of one :class:`VectorIndex` out
    of a COLD bundle-restored process. The build phase (fresh subprocess)
    trains the IVF coarse quantizer, warms the bucket-ladder grid and
    persists index + executable bundle; the measure phase (second fresh
    subprocess, compile cache empty) loads, restores, warms (all cache
    hits) and times single-query requests per tier — so the reported
    ``request_path_compiles`` is the real cold-process zero-compile gate,
    not an in-process approximation.

    Gates (asserted by tools/bench_smoke.sh):
      - corpus >= 100k vectors,
      - recall@10 of the IVF tier vs the exact tier >= 0.9,
      - IVF p99 strictly below exact-scan p99,
      - ZERO request-path compiles in the cold restored process.
    """
    import shutil
    import subprocess
    import tempfile
    import textwrap

    corpus_n, dim, n_centers = 100_000, 64, 256
    nlist, nprobe = 256, 8
    n_queries = 50 if SMOKE else 200
    timeout = (3 * _BUDGET_S + 300) if _BUDGET_S > 0 else 900
    workdir = tempfile.mkdtemp(prefix="bench_vecsearch_")

    # both phases regenerate the identical corpus/queries from the seed —
    # cheaper than shipping a 25MB npz and keeps each phase self-contained
    script = textwrap.dedent("""
        import json, os, sys, time
        import numpy as np
        os.environ["DL4J_TPU_AOT_BUNDLE"] = "1"
        from deeplearning4j_tpu.nn import aot
        from deeplearning4j_tpu.search import IndexConfig, VectorIndex

        phase, d = sys.argv[1], sys.argv[2]
        corpus_n, dim, n_centers = (int(a) for a in sys.argv[3:6])
        nlist, nprobe, n_q = (int(a) for a in sys.argv[6:9])
        ipath = os.path.join(d, "ix.zip")
        bpath = os.path.join(d, "ix.aotbundle")
        rs = np.random.RandomState(42)
        centers = (4.0 * rs.randn(n_centers, dim)).astype(np.float32)
        corpus = (centers[rs.randint(0, n_centers, corpus_n)]
                  + rs.randn(corpus_n, dim)).astype(np.float32)
        queries = (centers[rs.randint(0, n_centers, n_q)]
                   + rs.randn(n_q, dim)).astype(np.float32)
        if phase == "build":
            t0 = time.perf_counter()
            ix = VectorIndex.build(corpus, IndexConfig(
                dim=dim, nlist=nlist, nprobe=nprobe, max_k=16,
                batch_max=1, k_choices=(16,), train_sample=20000,
                kmeans_iters=8, pending_cap=0))
            build_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            warmed = ix.warm()
            warm_s = time.perf_counter() - t0
            aot.save_bundle(ix, bpath)
            ix.save(ipath)
            print(json.dumps({"build_s": round(build_s, 2),
                              "warm_s": round(warm_s, 2),
                              "warmed_executables": int(warmed)}))
        else:
            ix = VectorIndex.load(ipath)
            restored = aot.restore_bundle(ix, bpath)
            ix.warm()            # restored grid -> every rung a cache hit
            c0 = ix.program.compiles_observed()
            lat = {"exact": [], "ivf": []}
            ids = {"exact": [], "ivf": []}
            for tier in ("exact", "ivf"):
                for i in range(n_q):
                    q = queries[i:i + 1]
                    t0 = time.perf_counter()
                    got, _ = ix.search(q, k=10, tier=tier)
                    lat[tier].append((time.perf_counter() - t0) * 1e3)
                    ids[tier].append(np.asarray(got[0]))
            recall = float(np.mean([
                np.intersect1d(a[a >= 0], b[b >= 0]).size / 10.0
                for a, b in zip(ids["ivf"], ids["exact"])]))
            out = {"restored_executables": int(restored),
                   "request_path_compiles":
                       int(ix.program.compiles_observed() - c0),
                   "recall_at_10": round(recall, 4)}
            for tier in ("exact", "ivf"):
                a = np.asarray(lat[tier])
                out[tier + "_p50_ms"] = round(float(np.percentile(a, 50)), 3)
                out[tier + "_p99_ms"] = round(float(np.percentile(a, 99)), 3)
                out[tier + "_qps"] = round(n_q / (a.sum() / 1e3), 1)
            print(json.dumps(out))
    """)

    def run_phase(phase: str) -> dict:
        argv = [sys.executable, "-c", script, phase, workdir,
                str(corpus_n), str(dim), str(n_centers),
                str(nlist), str(nprobe), str(n_queries)]
        try:
            proc = subprocess.run(
                argv, capture_output=True, text=True, timeout=timeout,
                cwd=os.path.dirname(os.path.abspath(__file__)))
        except subprocess.SubprocessError as e:
            return {"error": f"{phase}: {type(e).__name__}: {e}"[:300]}
        for line in reversed(proc.stdout.strip().splitlines()):
            try:
                obj = json.loads(line)
            except (json.JSONDecodeError, ValueError):
                continue
            if isinstance(obj, dict):
                return obj
        return {"error": f"{phase}: rc={proc.returncode}: "
                         f"{proc.stderr[-300:]}"}

    try:
        build = run_phase("build")
        serve = {} if "error" in build else run_phase("serve")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    result = {
        "metric": "vector_search_p99",
        "unit": "ms per single-query request, IVF tier, cold "
                "bundle-restored process",
        "corpus": corpus_n, "dim": dim, "queries_per_tier": n_queries,
        "nlist": nlist, "nprobe": nprobe,
    }
    result.update(build)
    result.update(serve)
    if "error" in result:
        return result
    result["value"] = result["ivf_p99_ms"]
    result["ivf_p99_speedup_vs_exact"] = round(
        result["exact_p99_ms"] / max(result["ivf_p99_ms"], 1e-3), 2)
    return result


_BENCHES = {
    "lenet5": bench_lenet5,
    "resnet50": bench_resnet50,
    "lstm": bench_lstm_char_rnn,
    "word2vec": bench_word2vec,
    "transformer": bench_transformer,
    "serving": bench_serving_mixed,
    "serving_slo": bench_serving_slo,
    "generate": bench_generate,
    "dp_comms": bench_dp_comms,
    "mesh_mfu": bench_mesh_mfu,
    "checkpoint": bench_checkpoint,
    "mnist_mlp": bench_mnist_mlp,
    "cold_start": bench_cold_start,
    "vector_search": bench_vector_search,
}

# benches that need a multi-device mesh regardless of the host's accelerator
# count — run on forced virtual CPU devices in their isolated subprocess
_CPU_MESH_BENCHES = {"dp_comms", "mesh_mfu"}


def _run_isolated(name: str) -> dict:
    """Run one sub-benchmark in a FRESH process. Sharing a process is not
    neutral: ResNet50's leftover HBM arena slows the LSTM executable ~18x
    (measured on v5e) — per-bench processes give each model a clean chip."""
    import subprocess
    import sys

    # kill-timeout derives from the per-metric budget: the budget bounds the
    # measure loops, the headroom covers compiles — and a budget-shrunk bench
    # exits with its JSON long before the kill lands (satellite fix for
    # round 5's lenet5 rc=124)
    timeout = (3 * _BUDGET_S + 300) if _BUDGET_S > 0 else 900
    env = _cpu_mesh_env() if name in _CPU_MESH_BENCHES else None
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--only", name],
            capture_output=True, text=True, timeout=timeout, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.SubprocessError as e:  # hang/timeouts must not sink the rest
        return {"metric": name, "error": f"{type(e).__name__}: {e}"[:300]}
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            obj = json.loads(line)
        except (json.JSONDecodeError, ValueError):
            continue
        if isinstance(obj, dict):
            return obj
    return {"metric": name,
            "error": f"rc={proc.returncode}: {proc.stderr[-300:]}"}


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=sorted(_BENCHES),
                    help="run ONE benchmark in-process (internal)")
    ap.add_argument("--in-process", action="store_true",
                    help="run all benchmarks in this process (no isolation)")
    ap.add_argument("--cold-arm", help=argparse.SUPPRESS)
    ap.add_argument("--cold-dir", help=argparse.SUPPRESS)
    args = ap.parse_args()

    # one fixed persistent compile cache for every bench process (parent,
    # --only children, cold arms): JAX_COMPILATION_CACHE_DIR if set, else
    # <checkout>/.jax_cache. Config only — the parent stays off any backend,
    # so the children it spawns find the chip free
    from deeplearning4j_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()

    if args.cold_arm:  # internal: one cold-start arm in this fresh process
        try:
            print(json.dumps(_cold_start_arm(args.cold_arm, args.cold_dir)),
                  flush=True)
        except Exception as e:
            print(json.dumps({"arm": args.cold_arm,
                              "error": f"{type(e).__name__}: {e}"[:300]}))
            return 1
        return 0

    # mesh-needing benches launched directly (not via _run_isolated) still
    # get their virtual devices — must land before jax initializes
    if args.only in _CPU_MESH_BENCHES:
        os.environ.update(_cpu_mesh_env())

    # every result JSON carries the observability snapshot of the process
    # that MEASURED it (per-bench subprocesses: their own registry/spans)
    def _with_obs(m: dict) -> dict:
        from deeplearning4j_tpu import obs

        if "obs" not in m:
            m["obs"] = obs.snapshot()
        return m

    if args.only:
        _budget_start()
        # hard backstop: if a compile or measure loop wedges past every
        # soft budget check, raise INSIDE this process 60s before the
        # parent's kill-timeout (3*_BUDGET_S+300) so an error JSON still
        # reaches stdout — a skipped metric must report itself, never
        # rc=124 (guaranteed-JSON half of the lenet5 fix)
        import signal

        def _hard_stop(signum, frame):
            raise TimeoutError(
                f"bench '{args.only}' hit the hard deadline "
                f"(DL4J_TPU_BENCH_BUDGET_S={_BUDGET_S:g})")

        if _BUDGET_S > 0 and hasattr(signal, "SIGALRM"):
            signal.signal(signal.SIGALRM, _hard_stop)
            signal.alarm(int(3 * _BUDGET_S + 240))
        try:
            m = _with_obs(_BENCHES[args.only]())
        except BaseException as e:
            # the error still reaches stdout as JSON (the parent reads it),
            # and the exit code says so too
            m = {"metric": args.only,
                 "error": f"{type(e).__name__}: {e}"[:300]}
            if not isinstance(e, Exception):  # KeyboardInterrupt etc.
                print(json.dumps(m), flush=True)
                raise
        finally:
            if _BUDGET_S > 0 and hasattr(signal, "SIGALRM"):
                signal.alarm(0)
        print(json.dumps(m), flush=True)
        return 1 if "error" in m else 0

    # NOTE --in-process / BENCH_SMOKE share ONE process, which then holds
    # the device: cold_start and vector_search spawn children that need
    # it, so on an accelerator run the default (isolated) mode
    extras = []
    for name, fn in _BENCHES.items():
        if args.in_process or SMOKE:
            _budget_start()
            try:
                m = _with_obs(fn())
            except Exception as e:
                m = {"metric": name, "error": f"{type(e).__name__}: {e}"[:300]}
        else:
            m = _run_isolated(name)
        extras.append(m)
        print(json.dumps(m), flush=True)

    headline = next((m for m in extras if m.get("metric") ==
                     "resnet50_224_train_throughput" and "value" in m),
                    next((m for m in extras if "value" in m), extras[0]))
    final = {k: headline.get(k) for k in ("metric", "value", "unit", "vs_baseline")}
    if "mfu" in headline:
        final["mfu"] = headline["mfu"]
    final["extras"] = extras
    print(json.dumps(final))
    # a sub-benchmark that returned "error" fails the run
    return 1 if any("error" in m for m in extras) else 0


if __name__ == "__main__":
    sys.exit(main())
