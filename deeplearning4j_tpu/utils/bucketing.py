"""Shape-bucketed execution: one XLA executable per bucket, not per batch size.

Every distinct batch shape that reaches a jitted step compiles a fresh XLA
executable; irregular serving traffic and partial final fit() batches
therefore pay a compile per distinct request size. μ-cuDNN (PAPERS.md) shows
batch-size canonicalization is the lever that keeps a fixed kernel set hot —
the same applies to XLA compile caches. This module is the shared subsystem:

- A geometric **bucket ladder** (``BucketLadder`` / ``bucket_size``): round a
  batch's leading dimension up to the next rung so mixed sizes collapse onto
  a small fixed set of compiled shapes.
- **Padding helpers** that emit the per-example validity weights the
  loss/BatchNorm paths already honor (``pad_fit_batch``/``pad_fit_multi``:
  tiled rows + zero example-weight + a pre-scaled label mask so the loss
  equals the mean over the real rows EXACTLY — same mechanism as
  ParallelWrapper's DP padding), plus zero-padding for row-independent
  inference (``pad_rows_zero``) and ``unpad`` to slice results back.
- Optional **time-axis bucketing** for RNN/sequence inputs (``pad_time``):
  pad T up a rung and extend/synthesize the feature mask so padded steps are
  ignored by mask-honoring layers.
- A process-wide **telemetry counter** (``telemetry()``): jitted callers
  record a trace event from inside the traced python body (which runs once
  per compile) and a bucket-hit event per call, so compile-vs-traffic ratios
  are observable in benchmarks and asserted in tests.

Env knobs (read per call, so tests can flip them; values that reached a jit
are baked into already-compiled executables as shapes, not re-read):

- ``DL4J_TPU_BUCKETING``       master switch for all wired paths (default 1)
- ``DL4J_TPU_BUCKETS``         explicit ascending ladder, e.g. "8,16,32,64";
                               sizes beyond the top rung keep growing
                               geometrically from it
- ``DL4J_TPU_BUCKET_MIN``      smallest rung of the geometric ladder (default 1)
- ``DL4J_TPU_BUCKET_GROWTH``   ladder growth factor (default 2.0, must be >1)
"""

from __future__ import annotations

import math
import os
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "BucketLadder",
    "BucketTelemetry",
    "bucketing_enabled",
    "bucket_size",
    "ladder_from_env",
    "pad_fit_batch",
    "pad_fit_multi",
    "pad_rows_zero",
    "pad_time",
    "padded_label_mask",
    "telemetry",
    "tile_pad",
    "unpad",
]


# ---------------------------------------------------------------------------
# Ladder
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class BucketLadder:
    """Ascending bucket rungs. ``rungs`` may be an explicit list; beyond the
    top rung (or with no explicit rungs) sizes grow geometrically by
    ``growth`` starting at ``min_size``/the top rung, so the ladder covers
    any batch size with O(log n) distinct executables."""

    rungs: Tuple[int, ...] = ()
    min_size: int = 1
    growth: float = 2.0

    def __post_init__(self):
        if self.min_size < 1:
            raise ValueError(f"bucket min_size must be >= 1, got {self.min_size}")
        if self.growth <= 1.0:
            raise ValueError(f"bucket growth must be > 1, got {self.growth}")
        if any(b <= a for a, b in zip(self.rungs, self.rungs[1:])):
            raise ValueError(f"bucket rungs must be strictly ascending, got {self.rungs}")

    def bucket(self, n: int) -> int:
        """Smallest rung >= n."""
        if n <= 0:
            return n
        for r in self.rungs:
            if n <= r:
                return r
        top = self.rungs[-1] if self.rungs else self.min_size
        while top < n:
            top = max(top + 1, int(math.ceil(top * self.growth)))
        return top


def ladder_from_env() -> BucketLadder:
    """Ladder from the DL4J_TPU_BUCKET* env knobs (parsed per call — cheap —
    with clear errors naming the variable)."""
    raw = os.environ.get("DL4J_TPU_BUCKETS")
    rungs: Tuple[int, ...] = ()
    if raw:
        try:
            rungs = tuple(int(tok) for tok in raw.split(",") if tok.strip())
        except ValueError:
            raise ValueError(
                f"DL4J_TPU_BUCKETS must be comma-separated integers, got {raw!r}")
    try:
        min_size = int(os.environ.get("DL4J_TPU_BUCKET_MIN", "1"))
    except ValueError:
        raise ValueError(
            "DL4J_TPU_BUCKET_MIN must be an integer, got "
            f"{os.environ.get('DL4J_TPU_BUCKET_MIN')!r}")
    try:
        growth = float(os.environ.get("DL4J_TPU_BUCKET_GROWTH", "2.0"))
    except ValueError:
        raise ValueError(
            "DL4J_TPU_BUCKET_GROWTH must be a number, got "
            f"{os.environ.get('DL4J_TPU_BUCKET_GROWTH')!r}")
    return BucketLadder(rungs=rungs, min_size=min_size, growth=growth)


def bucketing_enabled() -> bool:
    return os.environ.get("DL4J_TPU_BUCKETING", "1") != "0"


def bucket_size(n: int, ladder: Optional[BucketLadder] = None) -> int:
    """Round ``n`` up to its bucket on ``ladder`` (env ladder by default)."""
    return (ladder or ladder_from_env()).bucket(n)


# ---------------------------------------------------------------------------
# Telemetry
# ---------------------------------------------------------------------------


class BucketTelemetry:
    """Process-wide compile/bucket-hit counters (thread-safe: the
    ParallelInference worker and fit loops record concurrently).

    ``record_trace`` is called from INSIDE jitted python bodies — the body
    runs once per distinct input signature, so ``traces[site]`` counts actual
    traces/compiles, not calls. ``record_hit`` counts one padded dispatch.

    Since PR 5 this class is an **adapter shim** over the obs metrics
    registry (``deeplearning4j_tpu/obs/``): the counters live in registry
    families (``dl4j_compiles_total``, ``dl4j_bucketing_*``,
    ``dl4j_comm_bytes``, ``dl4j_guard_events_total``) so they are scrapeable
    at /metrics, while
    every pre-existing accessor (``traces``, ``bucket_hits``, ``comm``,
    ``guard_events``, ``snapshot()``, ...) keeps its exact shape. The
    process singleton (``telemetry()``) shares the process registry and
    emits trace / bucket-promotion events; ad-hoc instances get a private
    registry so tests can't cross-talk."""

    def __init__(self, registry=None, emit_events: bool = False):
        from deeplearning4j_tpu.obs import metrics as _obs_metrics

        self._lock = threading.Lock()
        self._emit_events = emit_events
        reg = registry if registry is not None else _obs_metrics.MetricsRegistry()
        # the public compile counter (docs/OBSERVABILITY.md) and what the
        # ``traces`` accessor reads: zero delta across a request window
        # proves the request hit only pre-compiled executables
        self._compiles = reg.counter(
            "dl4j_compiles_total",
            "XLA traces/compiles by jitted site (recorded inside traced "
            "bodies, lazy or AOT, so this counts compiles, not calls; see "
            "dl4j_aot_warm_hits_total for AOT dispatch hits and "
            "dl4j_compile_seconds_total for what they cost)", ("site",))
        self._hits = reg.counter(
            "dl4j_bucketing_hits_total",
            "padded dispatches by site and bucket rung", ("site", "bucket"))
        self._padded = reg.counter(
            "dl4j_bucketing_padded_examples_total",
            "padding waste: rows added to reach bucket rungs")
        self._real = reg.counter(
            "dl4j_bucketing_real_examples_total",
            "real rows dispatched through bucketed paths")
        self._comm = reg.gauge(
            "dl4j_comm_bytes",
            "per-step collective bytes by exchange site (dense = hypothetical "
            "dense all-reduce, wire = configured exchange, param = sharded-"
            "update all-gather); describes a configuration, latest wins",
            ("site", "kind"))
        self._guard = reg.counter(
            "dl4j_guard_events_total",
            "divergence-guard events (invalid_score, warn/skip_batch/"
            "rollback trips, rollback_restore)", ("event",))
        self.trace_shapes: Dict[str, set] = {}

    def reset(self):
        with self._lock:
            for fam in (self._compiles, self._hits,
                        self._padded, self._real, self._comm, self._guard):
                fam.clear()
            self.trace_shapes = {}

    def record_trace(self, site: str, shape: Sequence[int]):
        with self._lock:
            self.trace_shapes.setdefault(site, set()).add(tuple(shape))
        count = self._compiles.inc(site=site)
        # flag the site for lazy cost harvest (obs/profile.py): a set add,
        # no jax — runs inside the traced body exactly once per compile
        from deeplearning4j_tpu.obs import profile

        profile.note_trace(site, shape)
        if self._emit_events:
            from deeplearning4j_tpu import obs

            obs.event("trace", site=site, shape=list(shape), compiles=int(count))

    def record_hit(self, site: str, n: int, bucket: int):
        first = self._hits.inc(site=site, bucket=bucket) == 1
        self._real.inc(n)
        self._padded.inc(max(bucket - n, 0))
        if first and self._emit_events:
            from deeplearning4j_tpu import obs

            obs.event("bucket_promotion", site=site, bucket=int(bucket))

    def record_comm(self, site: str, dense_bytes: int, wire_bytes: int,
                    param_bytes: int = 0):
        """Record a site's PER-STEP collective byte accounting (static shape
        arithmetic, recorded when a DataParallelStep plan is built):
        ``dense_bytes`` = what a dense all-reduce of the exchanged gradients
        would move, ``wire_bytes`` = what the configured exchange moves,
        ``param_bytes`` = sharded-update's extra updated-param all-gather.
        Latest values win — the numbers describe a configuration, not a
        running total."""
        self._comm.set(int(dense_bytes), site=site, kind="dense_bytes")
        self._comm.set(int(wire_bytes), site=site, kind="wire_bytes")
        self._comm.set(int(param_bytes), site=site, kind="param_bytes")

    def record_guard(self, event: str):
        """Count one divergence-guard event (``invalid_score``, a policy trip
        ``warn``/``skip_batch``/``rollback``, or ``rollback_restore``) — the
        InvalidScoreIterationTerminationCondition-style counters surfaced in
        snapshots (train/resilience.py)."""
        self._guard.inc(event=event)

    # -- pre-obs accessors (shim views over the registry families) ---------

    @property
    def traces(self) -> Dict[str, int]:
        return {k[0]: int(v) for k, v in self._compiles.as_dict().items()}

    @property
    def bucket_hits(self) -> Dict[Tuple[str, int], int]:
        return {(k[0], int(k[1])): int(v)
                for k, v in self._hits.as_dict().items()}

    @property
    def padded_examples(self) -> int:
        return int(self._padded.value())

    @property
    def real_examples(self) -> int:
        return int(self._real.value())

    @property
    def comm(self) -> Dict[str, Dict[str, int]]:
        out: Dict[str, Dict[str, int]] = {}
        for (site, kind), v in self._comm.as_dict().items():
            out.setdefault(site, {})[kind] = int(v)
        return out

    @property
    def guard_events(self) -> Dict[str, int]:
        return {k[0]: int(v) for k, v in self._guard.as_dict().items()}

    def compiles(self, site: Optional[str] = None) -> int:
        if site is not None:
            return int(self._compiles.value(site=site))
        return sum(self.traces.values())

    def buckets_used(self, site: Optional[str] = None) -> Tuple[int, ...]:
        return tuple(sorted({int(b) for (s, b) in self._hits.as_dict()
                             if site is None or s == site}))

    def snapshot(self) -> dict:
        """JSON-friendly view for bench extras."""
        return {
            "traces": self.traces,
            "bucket_hits": {f"{s}:{b}": c
                            for (s, b), c in sorted(self.bucket_hits.items())},
            "padded_examples": self.padded_examples,
            "real_examples": self.real_examples,
            "comm": self.comm,
            "guard": self.guard_events,
        }


def _process_telemetry() -> BucketTelemetry:
    from deeplearning4j_tpu.obs import metrics as _obs_metrics

    return BucketTelemetry(registry=_obs_metrics.registry(), emit_events=True)


_TELEMETRY = _process_telemetry()


def telemetry() -> BucketTelemetry:
    return _TELEMETRY


# ---------------------------------------------------------------------------
# Padding / unpadding
# ---------------------------------------------------------------------------


def tile_pad(a, pad: int):
    """Append ``pad`` rows to ``a`` by tiling its real rows (zero rows when
    the array is empty). Tiled rows keep batch-coupled numerics benign; the
    caller must zero-weight them in the loss."""
    if a is None:
        return None
    a = np.asarray(a)
    if len(a) == 0:
        return np.zeros((pad,) + a.shape[1:], a.dtype)
    reps = np.concatenate([a] * (pad // len(a) + 1))[:pad]
    return np.concatenate([a, reps])


def pad_rows_zero(a, target: int):
    """Zero-pad the leading (batch) axis up to ``target`` rows. For
    row-independent inference paths (``output()``) padded rows are dead
    compute sliced off by ``unpad``; stays on device for jax arrays."""
    if a is None:
        return None
    n = a.shape[0]
    if n >= target:
        return a
    import jax
    import jax.numpy as jnp

    pad_cfg = [(0, target - n)] + [(0, 0)] * (a.ndim - 1)
    if isinstance(a, jax.Array):
        return jnp.pad(a, pad_cfg)
    return np.pad(np.asarray(a), pad_cfg)


def unpad(out, n: int):
    """Slice a padded result (array or pytree of arrays) back to ``n`` rows."""
    import jax

    return jax.tree_util.tree_map(lambda o: o[:n], out)


def padded_label_mask(y, lm, n: int, scale: Optional[float] = None,
                      force: bool = False):
    """Label mask zero-weighting padded rows [n:] so the jitted step's loss
    averages over the n REAL examples only (exact equivalence with the
    unpadded fit).

    ``average_score`` keeps reference parity for per-example masks (divide by
    the full minibatch size B, BaseOutputLayer.computeScore semantics), so a
    0/1 validity mask alone would yield sum_real/B_pad instead of sum_real/n.
    The validity mask is therefore PRE-SCALED by B_pad/n: the per-example
    branch then gives sum(scores*mask)*(B_pad/n)/B_pad = sum_real/n exactly,
    and the rank-3 sum/sum(mask) branch is scale-invariant so it stays exact.

    Mask shape follows the label rank's masking convention: a user mask is
    multiplied by the scaled row validity; absent one, rank-2/3 labels get a
    per-example [B] weight (a [B,T] mask would flip average_score into its
    per-timestep sum/sum(mask) branch and rescale gradients by 1/T), and
    rank-4 (CnnLossLayer) labels get the per-pixel [B,H,W] mask its score()
    flattens (the flattened denominator B_pad*H*W needs the same B_pad/n
    correction).

    ``force=True`` materializes the (all-ones) mask even for an unpadded
    batch — the shape-bucketed fit path uses ONE calling convention for full
    and padded batches so they share a single compiled executable."""
    y = np.asarray(y)
    total = len(y)
    if scale is None and total == n and lm is None and not force:
        return lm
    valid = np.zeros(total, np.float32)
    valid[:n] = float(total) / float(n) if scale is None else float(scale)
    if lm is not None:
        lm = np.asarray(lm, np.float32)
        return lm * valid.reshape([total] + [1] * (lm.ndim - 1))
    if y.ndim == 4:
        return np.broadcast_to(valid[:, None, None], y.shape[:3]).copy()
    return valid


def pad_fit_batch(x, y, fm, lm, target: int, site: str = "fit"):
    """Pad a training batch's leading axis up to ``target`` rows, emitting
    the validity channels the loss/BatchNorm paths honor.

    Returns ``(x, y, fm, lm, ew)``: rows [n:] are tiled copies of real rows,
    ``ew`` is the per-example 0/1 weight (BatchNorm batch statistics exclude
    zero-weighted rows), and ``lm`` is the pre-scaled validity label mask
    (see ``padded_label_mask``) so the loss equals the mean over the n real
    rows. Called with ``len(x) == target`` it only materializes the all-ones
    channels, keeping ONE calling convention — and therefore one compiled
    executable — for full and partial batches alike."""
    n = len(x)
    if n > target:
        raise ValueError(f"batch of {n} rows exceeds pad target {target}")
    pad = target - n
    telemetry().record_hit(site, n, target)
    x, y, fm = (tile_pad(a, pad) if pad and a is not None else a
                for a in (x, y, fm))
    if pad and lm is not None:
        lm = tile_pad(lm, pad)
    lm = padded_label_mask(y, lm, n, force=True) if y is not None else lm
    ew = np.zeros(target, np.float32)
    ew[:n] = 1.0
    return x, y, fm, lm, ew


def pad_fit_multi(f, l, fm, lm, target: int, site: str = "fit"):
    """``pad_fit_batch`` for MultiDataSet tuples (ComputationGraph fit):
    every features/labels/masks member is row-padded, every output head gets
    its own pre-scaled validity label mask. Returns ``(f, l, fm, lm, ew)``."""
    n = len(f[0])
    if n > target:
        raise ValueError(f"batch of {n} rows exceeds pad target {target}")
    pad = target - n
    telemetry().record_hit(site, n, target)
    pad_t = lambda t: (tuple(tile_pad(a, pad) if a is not None else None
                             for a in t) if t is not None and pad else t)
    f, l, fm, lm = pad_t(f), pad_t(l), pad_t(fm), pad_t(lm)
    if l is not None:
        lms = lm if lm is not None else (None,) * len(l)
        lm = tuple(
            padded_label_mask(yi, lmi, n, force=True) if yi is not None else lmi
            for yi, lmi in zip(l, lms)
        )
        if all(m is None for m in lm):
            lm = None
    ew = np.zeros(target, np.float32)
    ew[:n] = 1.0
    return f, l, fm, lm, ew


def pad_time(x, target: int, fmask=None, axis: int = 1):
    """Pad the time axis of a [B, T, ...] sequence batch up to ``target``
    steps and return ``(x, fmask)`` where the mask zeroes the padded steps
    (synthesized as ones over the real steps when absent) so mask-honoring
    RNN/attention layers ignore them. Optional companion to batch bucketing
    for variable-length sequence serving."""
    x = np.asarray(x)
    t = x.shape[axis]
    if t >= target:
        if fmask is not None:
            fmask = np.asarray(fmask, np.float32)
        return x, fmask
    pad = target - t
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    xp = np.pad(x, cfg)
    if fmask is None:
        fmask = np.ones((x.shape[0], t), np.float32)
    else:
        fmask = np.asarray(fmask, np.float32)
    fmask = np.pad(fmask, [(0, 0), (0, pad)])
    return xp, fmask
