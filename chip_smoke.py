"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py          # on a machine with a TPU; ~6 min cold

One process, five phases in order, at the full width of the flagship
TransformerLM (d2048 / 16 heads / 8 blocks / vocab 2048 / T2048 / B16, bf16,
415M parameters, random weights from a seed):

  device      the platform is a TPU; its kind and count are printed
  kernels     every Pallas entry point (flash attention fwd+bwd, the ring
              blocks + merge, the fused LSTM fwd+bwd) compiled by Mosaic and
              run against its XLA reference at the shapes the models use
  train       MultiLayerNetwork(TransformerLM).fit, then
              ComputationGraph(ResNet50 224 bf16).fit at B128, then
              MultiLayerNetwork(TextGenerationLSTM).fit at B128/T50 — all
              through fit() at default settings; plus a checkpoint save
  serve       the trained TransformerLM behind ModelRegistry ->
              GenerateWorker -> DecodeProgram -> InferenceServer, answering
              POST /v1/models/lm:generate over HTTP from threads of this
              process, checked against the model's own full-sequence forward
  four_chips  (only where jax.device_count() >= 4) the same TransformerLM
              through MeshTrainer on data=4 and data=2 x model=2

The first failing phase ends the run with a non-zero exit code and the
phase's name; nothing catches a failure into a line of output. A run on the
CPU fails in the *device* phase: this script never reports a pass without a
TPU, and never sets JAX_PLATFORMS itself. It starts no other process (one
process per chip) and says so by counting.

The last line of stdout is the contract line
``{"ok": true, "device": {"platform", "kind", "count"}}``; the line before
it is the summary (seconds per phase, compile seconds, cache directory).
"""

from __future__ import annotations

import gc
import http.client
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import jax
import jax.numpy as jnp

# importing the package is part of the contract: in a directory that holds
# this script and nothing else of the repo, this line is where it dies
from deeplearning4j_tpu.train.listeners import TrainingListener
from deeplearning4j_tpu.utils import bucketing
from deeplearning4j_tpu.utils.compile_cache import enable_compilation_cache

# the flagship at full width
LM = dict(vocab_size=2048, max_len=2048, d_model=2048, n_heads=16,
          n_blocks=8)
LM_BATCH = 16
LM_STEPS = 6
# serve: KV capacity per stream. None = the model's max_len (2048): the
# default warm grid, 60 executables (nn/decode.py signature_grid), ~2 s
# each on the v5e. If the time limit ever bites, this is what gets cut —
# never width
SERVE_CAPACITY = None


def log(msg: str = "") -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# Instruments: compile seconds from JAX's own monitoring events; spawned
# processes by wrapping Popen
# ---------------------------------------------------------------------------


class Compiles:
    """Every backend compile this process makes (cache reads included):
    JAX's ``/jax/core/compile/backend_compile_duration`` event wraps
    ``compile_or_get_cached``, so a persistent-cache hit shows up as a short
    duration plus a ``cache_hits`` event."""

    def __init__(self):
        self.events = []            # (fun_name, seconds)
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((str(kw.get("fun_name", "?")), float(seconds)))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    @property
    def count(self) -> int:
        return len(self.events)

    @property
    def seconds(self) -> float:
        return sum(s for _, s in self.events)

    def since(self, mark: int):
        return self.events[mark:]


class Spawns:
    """Every process this one starts. A chip belongs to one process: the
    smoke must end with none started that could want it."""

    def __init__(self):
        self.commands = []
        orig = subprocess.Popen.__init__
        spawns = self

        def init(popen, args, *a, **kw):
            spawns.commands.append(args)
            return orig(popen, args, *a, **kw)

        subprocess.Popen.__init__ = init


def bytes_in_use(dev) -> int:
    return dev.memory_stats()["bytes_in_use"]


def mem(dev=None) -> dict:
    s = (dev or jax.devices()[0]).memory_stats()
    return {"in_use_gib": round(s["bytes_in_use"] / 2 ** 30, 2),
            "peak_gib": round(s["peak_bytes_in_use"] / 2 ** 30, 2),
            "limit_gib": round(s["bytes_limit"] / 2 ** 30, 2)}


def errors(names, got, want) -> dict:
    """Traced: per output, (max abs error normalised by the reference's max
    abs value, all-finite flag). Runs inside the same jit as what it
    compares — no eager ops, nothing fetched but scalars."""
    out = {}
    for name, g, w in zip(names, got, want):
        if g.shape != w.shape:
            raise AssertionError(
                f"{name}: shape {g.shape} != reference {w.shape}")
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        out[name] = (jnp.max(jnp.abs(g - w)) / (jnp.max(jnp.abs(w)) + 1e-30),
                     jnp.all(jnp.isfinite(g)))
    return out


def randn(rs, shape, dtype, scale=1.0):
    """Seeded host normals (jax.random.normal at these sizes costs half a
    minute of compilation on the chip — first v5e run)."""
    return jnp.asarray(rs.standard_normal(shape).astype(np.float32) * scale,
                       dtype)


def check(ctx, name: str, compare, args, tol: float) -> None:
    """Run one jitted kernel-vs-reference comparison and judge it."""
    t0 = time.perf_counter()
    c0 = ctx["compiles"].seconds
    errs = jax.device_get(compare(*args))
    line = "  ".join(f"{k}={float(e):.2e}" for k, (e, _) in errs.items())
    log(f"  {name}: {line}  (tol {tol:g}; {time.perf_counter() - t0:.1f}s, "
        f"{ctx['compiles'].seconds - c0:.1f}s compile)")
    bad = {k: float(e) for k, (e, finite) in errs.items()
           if not (finite and e <= tol)}
    if bad:
        raise AssertionError(f"{name}: non-finite or over tolerance "
                             f"{tol:g}: {bad}")


def n_mosaic(hlo: str) -> int:
    return hlo.count('custom_call_target="tpu_custom_call"')


# ---------------------------------------------------------------------------
# Phase: device
# ---------------------------------------------------------------------------


def phase_device(ctx) -> None:
    import importlib.metadata as md

    import jaxlib

    devs = jax.devices()
    d0 = devs[0]
    ctx["device"] = {"platform": d0.platform, "kind": d0.device_kind,
                     "count": len(devs)}
    log(f"  platform={d0.platform} device_kind={d0.device_kind!r} "
        f"count={len(devs)}")
    log(f"  jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={md.version('libtpu')} python={sys.version.split()[0]}")
    log(f"  JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r} "
        f"compile cache: {ctx['cache_dir']} "
        f"({'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'checkout default'})")
    if d0.platform != "tpu":
        raise AssertionError(
            f"no accelerator: jax.devices()[0].platform is {d0.platform!r}, "
            "not 'tpu' — this smoke never passes on a CPU")
    log(f"  memory: {mem()}")


# ---------------------------------------------------------------------------
# Phase: kernels
# ---------------------------------------------------------------------------


def _flash_checks(ctx, B, T, H, D, dtype, ref_rows, tol) -> None:
    """flash_attention fwd+bwd, causal, with and without kmask, at the full
    shape; the XLA reference (parallel/ring.py local_attention, the layer's
    own non-kernel path, on f32 copies) runs on the first ``ref_rows`` batch
    rows — attention is independent per row and a [B,H,T,T] f32 score tensor
    at the full batch would not fit beside its own vjp."""
    from deeplearning4j_tpu.ops.flash_attention import flash_attention
    from deeplearning4j_tpu.parallel.ring import local_attention

    f32 = jnp.float32
    rs = np.random.RandomState(0)
    q, k, v, w = (randn(rs, (B, T, H, D), dtype) for _ in range(4))
    # ragged key validity; the shortest rows are the ones the reference sees
    lens = T // 4 + (jnp.arange(B) * (T - T // 4)) // B
    kmask = (jnp.arange(T)[None, :] < lens[:, None]).astype(f32)
    r = slice(0, ref_rows)

    for masked in (False, True):
        def compare(q, k, v, w, kmask):
            km = kmask if masked else None

            def loss_k(q, k, v):
                out = flash_attention(q, k, v, kmask=km, causal=True)
                return jnp.sum(out.astype(f32) * w.astype(f32)), out

            def loss_r(q, k, v):
                out = local_attention(q, k, v, causal=True,
                                      kmask=None if km is None else km[r])
                return jnp.sum(out * w[r].astype(f32)), out

            (_, out), grads = jax.value_and_grad(
                loss_k, argnums=(0, 1, 2), has_aux=True)(q, k, v)
            (_, out_r), grads_r = jax.value_and_grad(
                loss_r, argnums=(0, 1, 2), has_aux=True)(
                    *(a[r].astype(f32) for a in (q, k, v)))
            return errors(("out", "dq", "dk", "dv"),
                          (out[r],) + tuple(g[r] for g in grads),
                          (out_r,) + grads_r)

        check(ctx, f"flash_attention ({B},{T},{H},{D}) "
              f"{jnp.dtype(dtype).name} causal "
              f"{'kmask' if masked else 'nomask'}",
              jax.jit(compare), (q, k, v, w, kmask), tol)


def _ring_block_checks(ctx, B, T, H, D, dtype, tol) -> None:
    """The ring path's per-step math (parallel/ring.py _ring_flash_shard):
    attention over two key chunks through flash_attention_block_grad
    (fwd+bwd) and the forward-only flash_attention_block, merged by
    logsumexp with merge_attention_blocks, against the full XLA attention."""
    from deeplearning4j_tpu.ops.flash_attention import (
        flash_attention_block, flash_attention_block_grad,
        merge_attention_blocks)
    from deeplearning4j_tpu.parallel.ring import local_attention

    f32 = jnp.float32
    rs = np.random.RandomState(1)
    q, k, v, w = (randn(rs, (B, T, H, D), dtype) for _ in range(4))
    half = T // 2

    def merged(q, k, v, block_fn):
        return merge_attention_blocks([
            block_fn(q, k[:, s:s + half], v[:, s:s + half], q_offset=0,
                     k_offset=s, causal=True) for s in (0, half)])

    def compare(q, k, v, w):
        def loss_k(q, k, v):
            out = merged(q, k, v, flash_attention_block_grad)
            return jnp.sum(out.astype(f32) * w.astype(f32)), out

        def loss_r(q, k, v):
            out = local_attention(q, k, v, causal=True)
            return jnp.sum(out * w.astype(f32)), out

        (_, out), grads = jax.value_and_grad(
            loss_k, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        out_fwd = merged(q, k, v, flash_attention_block)
        (_, out_r), grads_r = jax.value_and_grad(
            loss_r, argnums=(0, 1, 2), has_aux=True)(
                *(a.astype(f32) for a in (q, k, v)))
        return errors(("out", "out_fwd_only", "dq", "dk", "dv"),
                      (out, out_fwd) + grads, (out_r, out_r) + grads_r)

    check(ctx, f"flash_attention_block[_grad] + merge ({B},{T},{H},{D}) "
          f"{jnp.dtype(dtype).name} 2 chunks", jax.jit(compare),
          (q, k, v, w), tol)


def _lstm_checks(ctx, B, T, H, dtype, tol) -> None:
    """fused_lstm fwd+bwd — standard and peephole cell, masked and not —
    against the layer's own lax.scan path (BaseRecurrent.apply_seq, the path
    the kernel replaces) on f32 copies of the same inputs."""
    from deeplearning4j_tpu.nn.layers.recurrent import (
        BaseRecurrent, GravesLSTM, LSTM)
    from deeplearning4j_tpu.ops.fused_lstm import fused_lstm

    f32 = jnp.float32
    rs = np.random.RandomState(2)
    zx = randn(rs, (B, T, 4 * H), dtype, 0.5)
    wh = randn(rs, (H, 4 * H), dtype, H ** -0.5)
    h0 = randn(rs, (B, H), dtype, 0.3)
    c0 = randn(rs, (B, H), dtype, 0.3)
    peep = randn(rs, (3 * H,), dtype, 0.3)
    w = randn(rs, (B, T, H), f32)
    lens = T // 3 + (jnp.arange(B) * (T - T // 3)) // B
    mask = (jnp.arange(T)[None, :] < lens[:, None]).astype(f32)

    def total(out, hT, cT, w):
        return (jnp.sum(out.astype(f32) * w) + jnp.sum(hT.astype(f32))
                + 0.5 * jnp.sum(cT.astype(f32)))

    for peephole in (False, True):
        # the real layer's cell and scan, fed precomputed zx rows
        cls = GravesLSTM if peephole else LSTM
        layer = type("ScanOnZx", (cls,),
                     {"_input_proj": lambda self, params, x: x})(n_out=H)
        an = (0, 1, 2, 3, 4) if peephole else (0, 1, 2, 3)
        for masked in (False, True):
            def compare(zx, wh, h0, c0, peep, w, mask):
                m = mask if masked else None

                def loss_k(zx, wh, h0, c0, peep):
                    out, (hT, cT) = fused_lstm(
                        zx, wh, h0, c0, m, peep if peephole else None)
                    return total(out, hT, cT, w), (out, hT, cT)

                def loss_r(zx, wh, h0, c0, peep):
                    out, (hT, cT) = BaseRecurrent.apply_seq(
                        layer, {"Wh": wh, "peephole": peep}, zx, (h0, c0), m)
                    return total(out, hT, cT, w), (out, hT, cT)

                args = (zx, wh, h0, c0, peep)
                (_, outs), grads = jax.value_and_grad(
                    loss_k, argnums=an, has_aux=True)(*args)
                (_, outs_r), grads_r = jax.value_and_grad(
                    loss_r, argnums=an, has_aux=True)(
                        *(a.astype(f32) for a in args))
                return errors(
                    ("out", "hT", "cT", "dzx", "dWh", "dh0", "dc0", "dpeep"),
                    outs + grads, outs_r + grads_r)

            check(ctx, f"fused_lstm (B{B},T{T},H{H}) "
                  f"{jnp.dtype(dtype).name} "
                  f"{'peephole' if peephole else 'standard'} "
                  f"{'masked' if masked else 'unmasked'}",
                  jax.jit(compare), (zx, wh, h0, c0, peep, w, mask), tol)


def phase_kernels(ctx, *, flash=((16, 2048, 16, 128, jnp.bfloat16, 2),
                              # two heads of 64 in one 128-lane block
                              (8, 1024, 16, 64, jnp.float32, 2)),
                  ring=((4, 2048, 16, 128, jnp.bfloat16),),
                  lstm=((128, 50, 256, jnp.float32, 1e-2),
                        (512, 50, 1024, jnp.bfloat16, 4e-2))) -> None:
    """Tolerances are those of one bf16 MXU pass: both the kernels and
    XLA's default precision multiply in bf16 and accumulate in f32 (first
    v5e run: flash <= 6e-3, fused LSTM <= 8e-3 bf16 / 3e-3 f32)."""
    mark = ctx["compiles"].count
    for B, T, H, D, dtype, ref_rows in flash:
        _flash_checks(ctx, B, T, H, D, dtype, ref_rows, tol=2e-2)
    for B, T, H, D, dtype in ring:
        _ring_block_checks(ctx, B, T, H, D, dtype, tol=2e-2)
    for B, T, H, dtype, tol in lstm:
        _lstm_checks(ctx, B, T, H, dtype, tol)
    log(f"  backend compiles in this phase: "
        f"{ctx['compiles'].count - mark}; memory: {mem()}")


# ---------------------------------------------------------------------------
# Phase: train
# ---------------------------------------------------------------------------


class _Watch(TrainingListener):
    """The loss of every step, and how many backend compiles had happened
    when step 1 was reported: fit() has enqueued step 2 by then, whose
    retrace would be the site's trace count's to catch."""

    def __init__(self, compiles: Compiles):
        self._compiles = compiles
        self.losses = []
        self.mark_after_first = None

    def iteration_done(self, model, iteration, score, batch_size=0):
        self.losses.append(float(score))
        if self.mark_after_first is None:
            self.mark_after_first = self._compiles.count


def _fit_and_check(ctx, name, model, batches, site) -> "_Watch":
    """fit() on a repeated batch: every loss finite and the last below the
    first, and nothing compiles after the first step (JAX's own compile
    events AND the repo's per-site trace counter)."""
    compiles = ctx["compiles"]
    tel = bucketing.telemetry()
    traces0 = tel.compiles(site)
    watch = _Watch(compiles)
    model.set_listeners(watch)
    t0 = time.perf_counter()
    model.fit(batches, epochs=1)
    dt = time.perf_counter() - t0
    model.set_listeners()
    late = compiles.since(watch.mark_after_first)
    log(f"  {name}: {len(watch.losses)} steps through fit() in {dt:.1f}s "
        f"(first-step compile included); losses "
        f"{[round(l, 4) for l in watch.losses]}")
    log(f"  {name}: traces at {site}: {tel.compiles(site) - traces0}; "
        f"backend compiles after step 1: {len(late)}; memory: {mem()}")
    if len(watch.losses) != len(batches):
        raise AssertionError(f"{name}: {len(watch.losses)} steps, "
                             f"expected {len(batches)}")
    if not all(np.isfinite(watch.losses)):
        raise AssertionError(f"{name}: non-finite loss {watch.losses}")
    if not watch.losses[-1] < watch.losses[0]:
        raise AssertionError(f"{name}: loss did not fall on a repeated "
                             f"batch: {watch.losses}")
    if late:
        raise AssertionError(f"{name}: compiled after the first step: {late}")
    if tel.compiles(site) - traces0 != 1:
        raise AssertionError(
            f"{name}: {tel.compiles(site) - traces0} traces at {site}, "
            "expected exactly 1")
    return watch


def _lm_batch(batch, seq, vocab):
    rs = np.random.RandomState(0)
    ids = rs.randint(0, vocab, (batch, seq))
    # sparse integer next-token labels (same loss math as one-hot)
    return ids.astype(np.int32), np.roll(ids, -1, axis=1).astype(np.int32)


def train_transformer(ctx, lm=LM, batch=LM_BATCH, steps=LM_STEPS):
    from deeplearning4j_tpu.models import TransformerLM
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork

    model = MultiLayerNetwork(TransformerLM(
        **lm, updater={"type": "adam", "lr": 1e-4})).init()
    log(f"  TransformerLM {lm}: {model.num_params() / 1e6:.1f}M parameters, "
        f"dtype {model.dtype}")
    x, y = _lm_batch(batch, lm["max_len"], lm["vocab_size"])
    watch = _fit_and_check(ctx, "TransformerLM", model, [(x, y)] * steps,
                           "mln.step")
    # the HLO of the step fit() just ran: same jitted function, same
    # signature fit() dispatches (tests/test_tp_hlo.py idiom)
    hlo = model._get_step_fn(False).lower(
        model.params, model.opt_state, model.state,
        jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
        jnp.asarray(x), jnp.asarray(y), None, None, (),
        ex_weight=None).compile().as_text()
    n = n_mosaic(hlo)
    log(f"  TransformerLM: Mosaic custom calls in the compiled step: {n} "
        f"({n / lm['n_blocks']:g} per block)")
    if n != 3 * lm["n_blocks"]:
        raise AssertionError(
            f"TransformerLM: {n} Mosaic calls for {lm['n_blocks']} blocks, "
            "expected 3 per block (flash forward + dq + dk/dv): the flash "
            "gate in nn/layers/attention.py did not take the kernel")
    ctx["lm"] = (model, x, watch.losses[0])


def train_resnet50(ctx, size=224, batch=128, classes=1000, steps=3):
    from deeplearning4j_tpu.models.zoo_graph import ResNet50
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    cg = ComputationGraph(ResNet50(height=size, width=size,
                                   num_classes=classes,
                                   dtype="bfloat16")).init()
    rs = np.random.RandomState(0)
    x = rs.rand(batch, size, size, 3).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rs.randint(0, classes, batch)]
    _fit_and_check(ctx, f"ResNet50 {size}x{size} bf16 B{batch}", cg,
                   [(x, y)] * steps, "cg.step")


def train_lstm(ctx, batch=128, steps=3, **conf):
    """BASELINE #3 at default settings (no DL4J_TPU_FUSED_LSTM): on the TPU
    the layer gate picks the fused kernel; the step's HLO says whether it
    did. Then a durable checkpoint of the trained model — the path that
    used to stall in a chip-needing validation child."""
    from deeplearning4j_tpu.models import TextGenerationLSTM
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.train import resilience

    if os.environ.get("DL4J_TPU_FUSED_LSTM") is not None:
        raise AssertionError("unset DL4J_TPU_FUSED_LSTM: this phase proves "
                             "the DEFAULT path")
    conf_obj = TextGenerationLSTM(**conf)
    model = MultiLayerNetwork(conf_obj).init()
    V, T = conf_obj.input_type.size, conf_obj.input_type.timesteps
    rs = np.random.RandomState(0)
    ids = rs.randint(0, V, (batch, T))
    x = np.eye(V, dtype=np.float32)[ids]
    y = np.eye(V, dtype=np.float32)[np.roll(ids, -1, axis=1)]
    _fit_and_check(ctx, f"TextGenerationLSTM B{batch}/T{T}", model,
                   [(x, y)] * steps, "mln.step")
    carries = tuple(l.initial_carry(batch, model.dtype) if f else ()
                    for l, f in zip(model.layers, model._carry_flags))
    hlo = model._get_step_fn(True).lower(
        model.params, model.opt_state, model.state,
        jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
        jnp.asarray(x), jnp.asarray(y), None, None, carries,
    ).compile().as_text()
    n_lstm = sum(1 for l in model.layers if hasattr(l, "_fused_eligible"))
    n = n_mosaic(hlo)
    log(f"  TextGenerationLSTM: Mosaic custom calls in the compiled tBPTT "
        f"step: {n} for {n_lstm} GravesLSTM layers (fused path = 2 each)")
    if n != 2 * n_lstm:
        raise AssertionError(
            f"TextGenerationLSTM: {n} Mosaic calls, expected {2 * n_lstm}: "
            "the default path on the TPU is the fused kernel")

    spawned = len(ctx["spawns"].commands)
    t0 = time.perf_counter()
    info = resilience.save_checkpoint(
        model, os.path.join(ctx["tmp"], "lstm_ckpt.zip"))
    dt = time.perf_counter() - t0
    log(f"  save_checkpoint: {info['size']} bytes in {dt:.2f}s, "
        f"aot bundle: {info.get('aot_bundle')}, processes started: "
        f"{len(ctx['spawns'].commands) - spawned}")
    if len(ctx["spawns"].commands) != spawned:
        raise AssertionError(
            f"save_checkpoint started a process: {ctx['spawns'].commands}")


def phase_train(ctx) -> None:
    train_transformer(ctx)
    train_resnet50(ctx)
    gc.collect()
    train_lstm(ctx)
    gc.collect()


# ---------------------------------------------------------------------------
# Phase: serve
# ---------------------------------------------------------------------------


def _post_generate(port, payload):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    t0 = time.perf_counter()
    conn.request("POST", "/v1/models/lm:generate",
                 json.dumps(payload).encode(),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    body = resp.read().decode()
    chunked = resp.getheader("Transfer-Encoding")
    conn.close()
    if resp.status != 200 or chunked != "chunked":
        raise AssertionError(f"generate: HTTP {resp.status} "
                             f"Transfer-Encoding={chunked!r}: {body[:300]}")
    lines = [json.loads(l) for l in body.strip().splitlines()]
    return lines, time.perf_counter() - t0


def phase_serve(ctx, capacity=SERVE_CAPACITY, max_tokens=16, ref_len=64,
                prompt_lens=(12, 30, 7, 21)) -> None:
    from deeplearning4j_tpu.serve import InferenceServer, ModelRegistry

    model, x, _ = ctx["lm"]
    compiles = ctx["compiles"]
    tel = bucketing.telemetry()
    vocab = model.conf.layers[0].n_in          # EmbeddingSequence
    full = model.conf.layers[1].max_len        # PositionalEmbedding
    if capacity not in (None, full):
        log(f"  CUT: KV capacity {capacity} tokens per stream instead of "
            f"max_len {full} — shrinks the warm grid's page-table axis; "
            "width and depth are the trained model's")
    else:
        log(f"  nothing cut: KV capacity = max_len = {full}, the default "
            "warm grid")

    reg = ModelRegistry()
    spawned = len(ctx["spawns"].commands)
    mark = compiles.count
    t0 = time.perf_counter()
    gw = reg.register_generate(
        "lm", model, warm=True, capacity=capacity,
        bundle=os.path.join(ctx["tmp"], "lm.aotbundle"))
    warm_s = time.perf_counter() - t0
    grid = gw.program.signature_grid()
    log(f"  register_generate: warmed {gw.program.compiled_count} "
        f"executables (grid {len(grid)}) in {warm_s:.1f}s, "
        f"{sum(s for _, s in compiles.since(mark)):.1f}s of it compile; "
        f"processes started: {len(ctx['spawns'].commands) - spawned}; "
        f"memory: {mem()}")
    if gw.program.compiled_count != len(grid):
        raise AssertionError("warm() did not cover the signature grid")
    if len(ctx["spawns"].commands) != spawned:
        raise AssertionError(f"register_generate(bundle=...) started a "
                             f"process: {ctx['spawns'].commands}")

    srv = InferenceServer(reg).start(port=0)
    try:
        prompts = [[int(t) for t in x[i, :n]]
                   for i, n in enumerate(prompt_lens)]
        traces0 = tel.compiles("decode.step")
        mark = compiles.count
        with ThreadPoolExecutor(len(prompts)) as pool:
            results = list(pool.map(
                lambda p: _post_generate(
                    srv.port, {"prompt": p, "max_tokens": max_tokens}),
                prompts))
        req_compiles = compiles.since(mark)
        req_traces = tel.compiles("decode.step") - traces0
    finally:
        srv.stop()
        reg.shutdown()

    streams = []
    for (lines, secs), p in zip(results, prompts):
        toks = [l["token"] for l in lines[:-1]]
        tail = lines[-1]
        log(f"  POST :generate prompt {len(p)} tokens -> {len(toks)} tokens "
            f"in {secs:.2f}s, ttft {tail.get('ttft_ms')} ms, "
            f"reason {tail.get('reason')!r}")
        if not (tail.get("done") and tail.get("reason") == "length"
                and tail.get("tokens") == max_tokens == len(toks)):
            raise AssertionError(f"generate: bad stream tail {tail}")
        if [l["i"] for l in lines[:-1]] != list(range(max_tokens)):
            raise AssertionError("generate: token indices out of order")
        if not all(0 <= t < vocab for t in toks):
            raise AssertionError(f"generate: token outside vocab: {toks}")
        streams.append(toks)
    log(f"  request path: decode.step traces {req_traces}, backend "
        f"compiles {len(req_compiles)} {req_compiles[:4]}")
    if req_traces or req_compiles:
        raise AssertionError("the request path compiled after warm()")

    # reference: the model's own full-sequence forward (mln.output — the
    # training-path attention) over prompt + generated tokens, one padded
    # length, teacher-forced. Causal, so position t sees tokens <= t only.
    # Greedy decode must pick the reference's argmax, up to bf16 near-ties:
    # every served token within the reference's top 5, most of them top 1.
    seqs = np.zeros((len(prompts), ref_len), np.int32)
    for i, (p, toks) in enumerate(zip(prompts, streams)):
        seqs[i, :len(p) + len(toks)] = p + toks
    probs = np.asarray(model.output(seqs), np.float32)
    if probs.shape != (len(prompts), ref_len, vocab) \
            or not np.isfinite(probs).all():
        raise AssertionError(f"reference forward: shape {probs.shape} or "
                             "non-finite")
    top1 = total = 0
    worst_rank = 0
    for i, (p, toks) in enumerate(zip(prompts, streams)):
        for j, tok in enumerate(toks):
            row = probs[i, len(p) - 1 + j]
            rank = int((row > row[tok]).sum())
            worst_rank = max(worst_rank, rank)
            top1 += rank == 0
            total += 1
    log(f"  served tokens vs reference forward: {top1}/{total} are the "
        f"reference argmax, worst rank {worst_rank}")
    if worst_rank >= 5 or top1 < 0.8 * total:
        raise AssertionError(
            f"served tokens disagree with the reference forward: "
            f"{top1}/{total} argmax, worst rank {worst_rank}")
    ctx["serve"] = {"warm_executables": gw.program.compiled_count,
                    "warm_seconds": round(warm_s, 1),
                    "capacity": gw.program.capacity}


# ---------------------------------------------------------------------------
# Phase: four chips
# ---------------------------------------------------------------------------


def phase_four_chips(ctx, lm=LM, batch=LM_BATCH) -> None:
    n = jax.device_count()
    if n < 4:
        log(f"  NOT RUN: jax.device_count() is {n}; the MeshTrainer checks "
            "need 4 chips (chiprun --chips 4)")
        ctx["four_chips"] = "not run"
        return
    from deeplearning4j_tpu.models import TransformerLM
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.parallel import MeshSpec
    from deeplearning4j_tpu.parallel.context import use_mesh
    from deeplearning4j_tpu.parallel.mesh_step import MeshTrainer

    def fresh():
        return MultiLayerNetwork(TransformerLM(
            **lm, updater={"type": "adam", "lr": 1e-4})).init()

    x, y = _lm_batch(batch, lm["max_len"], lm["vocab_size"])
    if "lm" in ctx:
        one_chip = ctx.pop("lm")[2]       # and let the one-chip model go
    else:
        model = fresh()
        watch = _Watch(ctx["compiles"])
        model.set_listeners(watch)
        model.fit([(x, y)], epochs=1)
        one_chip = watch.losses[0]
        del model
    gc.collect()
    log(f"  one-chip first-step loss: {one_chip:.5f}")
    devices = jax.devices()[:4]
    for spec in (MeshSpec(data=4), MeshSpec(data=2, model=2)):
        model = fresh()
        trainer = MeshTrainer(model, spec, devices=devices)
        log(f"  {spec}: mesh devices "
            f"{[[d.id for d in row] for row in trainer.mesh.devices.reshape(spec.resolve(4)[0], -1)]}")
        losses = [float(trainer.fit_batch(x, y)) for _ in range(3)]
        used = [bytes_in_use(d) for d in devices]
        spans = {len(l.sharding.device_set)
                 for l in jax.tree_util.tree_leaves(model.params)}
        xs = trainer._shard_batch(x)
        ys = trainer._shard_batch(jnp.asarray(y))
        with use_mesh(trainer.mesh):
            hlo = trainer._get_step().lower(
                model.params, model.opt_state, model.state,
                jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0), xs, ys,
                None, None, (), ex_weight=None).compile().as_text()
        coll = {c: hlo.count(c) for c in
                ("all-reduce", "reduce-scatter", "all-gather",
                 "collective-permute", "all-to-all")}
        log(f"  {spec}: losses {[round(l, 5) for l in losses]}; "
            f"bytes_in_use per device {[round(u / 2 ** 30, 2) for u in used]} "
            f"GiB; param shardings span {sorted(spans)} devices; "
            f"collectives {coll}; Mosaic calls {n_mosaic(hlo)}")
        rel = abs(losses[0] - one_chip) / abs(one_chip)
        if not rel <= 2e-2:
            raise AssertionError(
                f"{spec}: first-step loss {losses[0]} vs one chip "
                f"{one_chip} (rel {rel:.3g}) — outside bf16 tolerance")
        if not all(np.isfinite(losses)) or not losses[-1] < losses[0]:
            raise AssertionError(f"{spec}: losses {losses}")
        if not all(u > 0 for u in used):
            raise AssertionError(f"{spec}: an idle device: {used}")
        if spans != {4}:
            raise AssertionError(f"{spec}: parameters span {spans} devices")
        if not (coll["all-reduce"] or coll["reduce-scatter"]):
            raise AssertionError(f"{spec}: no collectives in the HLO")
        del trainer, model
        gc.collect()
    ctx["four_chips"] = "passed"


# ---------------------------------------------------------------------------


PHASES = (("device", phase_device), ("kernels", phase_kernels),
          ("train", phase_train), ("serve", phase_serve),
          ("four_chips", phase_four_chips))


def run(phases=PHASES) -> int:
    t_start = time.perf_counter()
    ctx = {"cache_dir": enable_compilation_cache(), "compiles": Compiles(),
           "spawns": Spawns(), "tmp": tempfile.mkdtemp(prefix="chip_smoke_")}
    seconds = {}
    try:
        for name, fn in phases:
            log(f"== phase {name}")
            t0 = time.perf_counter()
            try:
                fn(ctx)
            except BaseException:
                # name the phase, then let the failure end the run: the
                # interpreter prints the traceback and exits non-zero
                log(f"== phase {name} FAILED after "
                    f"{time.perf_counter() - t0:.1f}s")
                raise
            seconds[name] = round(time.perf_counter() - t0, 1)
            log(f"== phase {name} ok in {seconds[name]}s")
    finally:
        shutil.rmtree(ctx["tmp"], ignore_errors=True)

    from deeplearning4j_tpu import native

    spawns, compiles = ctx["spawns"], ctx["compiles"]
    log(f"processes started: {len(spawns.commands)} {spawns.commands}; "
        f"native fastload library loaded: {native._lib is not None}")
    if spawns.commands:
        sys.exit(f"chip_smoke: started processes: {spawns.commands}")
    log("SUMMARY " + json.dumps({
        "phases": seconds,
        "wall_seconds": round(time.perf_counter() - t_start, 1),
        "compile_seconds": round(compiles.seconds, 1),
        "backend_compiles": compiles.count,
        "persistent_cache_hits": compiles.cache_hits,
        "persistent_cache_misses": compiles.cache_misses,
        "cache_dir": ctx["cache_dir"],
        "serve": ctx.get("serve"),
        "four_chips": ctx.get("four_chips"),
        "peak_memory": mem(),
        "device": ctx["device"],
    }))
    if tuple(phases) != PHASES:
        log("partial run (phases chosen by the caller): no contract line")
        return 0
    # the contract line: only ever reached with every phase passed on a TPU
    print(json.dumps({"ok": True, "device": ctx["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run())
