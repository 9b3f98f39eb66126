"""Fault-tolerant training runtime: durable checkpoints, auto-resume,
divergence guard, and a deterministic chaos-injection harness.

The reference stack survives long runs through CheckpointListener retention
policies and early-stopping restores; a preempted TPU job additionally needs
the pieces a model zip alone does not carry — the RNG key driving per-batch
dropout streams, the iterator position inside the epoch, the LR backoff
scale, and the PR-3 compression residuals riding the donated opt carry. This
module owns that full-state contract:

- ``save_checkpoint`` / ``validate_checkpoint``: atomic zip writes
  (tmp + fsync + ``os.replace`` in utils/serialization.py) with a CRC32 +
  size recorded in ``checkpointInfo.json``, so a checkpoint is either whole
  or provably bad.
- ``resume(model, dir)``: load the NEWEST VALID checkpoint (corrupt/truncated
  files fall back to the previous valid one) into an existing model —
  params, optimizer state, BN state, iteration/epoch, RNG key,
  batch-in-epoch position, LR scale, and DP residuals. ``fit(...,
  resume_from=dir)`` on MLN/CG/ParallelWrapper drives this and skips the
  already-consumed batches of the interrupted epoch, so an interrupted +
  resumed run replays the exact same RNG/batch stream as an uninterrupted
  one (bit-exact on CPU; tests/test_resilience.py).
- ``DivergenceGuard``: non-finite / loss-spike detection. The ``skip_batch``
  policy is applied INSIDE the compiled step (``guard_ok``/``guard_select``
  below — a ``jnp.where`` select between the candidate and previous
  params/opt/state, no extra host sync); the host side batches its score
  reads (``flush_every`` window) so warn/skip never add per-step syncs.
  ``rollback`` reloads the last valid checkpoint and applies a capped LR
  backoff.
- Chaos harness: ``DL4J_TPU_CHAOS=preempt@iter:8,corrupt_ckpt@ckpt:1:bitflip``
  style fault grammar (see ``ChaosInjector.parse``) injecting
  kill-at-iteration, checkpoint corruption, NaN gradients (NaN-poisoned
  batches), and stalled iterations — deterministic and one-shot per fault,
  so tests and ``tools/chaos_smoke.sh`` can prove recovery end to end.

See docs/ROBUSTNESS.md for the checkpoint format and recovery semantics.
"""

from __future__ import annotations

import glob
import io
import json
import math
import os
import signal
import time
import warnings
import zipfile
import zlib
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.utils import bucketing

__all__ = [
    "ChaosInjector",
    "ChaosPreemption",
    "DivergenceError",
    "DivergenceGuard",
    "active_chaos",
    "capture_train_state",
    "crc32_file",
    "install_chaos",
    "io_with_retries",
    "load_distributed_checkpoint",
    "load_state_into",
    "note_score",
    "resume",
    "save_checkpoint",
    "validate_checkpoint",
    "write_bytes_durable",
    "write_json_durable",
]


# ---------------------------------------------------------------------------
# Retrying I/O: bounded exponential backoff for checkpoint reads/writes
# ---------------------------------------------------------------------------


def _retry_knobs():
    return (int(os.environ.get("DL4J_TPU_CKPT_RETRIES", "3")),
            float(os.environ.get("DL4J_TPU_CKPT_RETRY_BASE_S", "0.05")),
            float(os.environ.get("DL4J_TPU_CKPT_RETRY_CAP_S", "2.0")))


def io_with_retries(fn: Callable[[], Any], *, what: str = "ckpt_io"):
    """Run a checkpoint I/O callable, retrying ``OSError`` with bounded
    exponential backoff (``DL4J_TPU_CKPT_RETRIES`` attempts beyond the
    first, delay ``base * 2**k`` capped at ``DL4J_TPU_CKPT_RETRY_CAP_S``).
    Network filesystems fail transiently under exactly the membership churn
    the elastic runtime is built for; each retry increments
    ``dl4j_ckpt_retries_total``. Exhaustion re-raises the last error."""
    retries, base, cap = _retry_knobs()
    for attempt in range(retries + 1):
        try:
            return fn()
        except OSError as e:
            if attempt >= retries:
                raise
            delay = min(base * (2 ** attempt), cap)
            obs.counter("dl4j_ckpt_retries_total",
                        "Checkpoint I/O operations retried after a "
                        "transient OSError").inc()
            obs.event("ckpt_io_retry", what=what, attempt=attempt + 1,
                      error=str(e), delay_s=round(delay, 4))
            time.sleep(delay)


def write_bytes_durable(path, data: bytes) -> None:
    """Atomic durable byte write (tmp + fsync + ``os.replace``) with retry
    backoff — the primitive under the distributed checkpoint shards."""

    def attempt():
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "wb") as f:
                f.write(data)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                try:
                    os.unlink(tmp)
                except OSError:
                    pass

    io_with_retries(attempt, what=f"write:{os.path.basename(str(path))}")


def write_json_durable(path, value) -> None:
    write_bytes_durable(path, json.dumps(value, indent=1).encode("utf-8"))


# ---------------------------------------------------------------------------
# Durable checkpoints: CRC + validation + newest-valid fallback
# ---------------------------------------------------------------------------


def crc32_file(path, chunk: int = 1 << 20) -> int:
    """CRC32 of a file's bytes, streamed (checkpoints can be large)."""
    crc = 0
    with open(path, "rb") as f:
        while True:
            buf = f.read(chunk)
            if not buf:
                break
            crc = zlib.crc32(buf, crc)
    return crc & 0xFFFFFFFF


def validate_checkpoint(path, crc: Optional[int] = None,
                        size: Optional[int] = None) -> bool:
    """True when the checkpoint file at ``path`` is intact.

    With a recorded ``crc``/``size`` (checkpointInfo.json entries) the check
    is exact: truncation changes the size, bit flips change the CRC. Legacy
    entries without a CRC fall back to a structural zip check (central
    directory + per-entry CRCs + required entries present)."""
    try:
        if not os.path.isfile(path):
            return False
        if size is not None and os.path.getsize(path) != int(size):
            return False
        if crc is not None:
            return crc32_file(path) == int(crc)
        from deeplearning4j_tpu.utils import serialization as S

        with zipfile.ZipFile(path, "r") as zf:
            if zf.testzip() is not None:
                return False
            names = set(zf.namelist())
            return S.CONFIG_ENTRY in names and S.COEFFICIENTS_ENTRY in names
    except Exception:
        return False


def capture_train_state(model) -> dict:
    """The JSON-able training state a model zip alone does not carry: RNG
    key (per-batch dropout/noise stream position), batch-in-epoch iterator
    position, divergence-guard LR scale, and the full observability snapshot
    — metrics, span aggregates, event counts, bucketing counters
    (informational — restored runs keep their own counters)."""
    state: Dict[str, Any] = {
        "version": 1,
        "batch_in_epoch": int(getattr(model, "batch_in_epoch", 0)),
        "lr_scale": float(getattr(model, "_lr_scale", 1.0)),
        "telemetry": obs.snapshot(),
    }
    rng = getattr(model, "_rng", None)
    if rng is not None:
        arr = np.asarray(rng)  # graftlint: disable=host-sync
        state["rng"] = arr.tolist()
        state["rng_dtype"] = str(arr.dtype)
    return state


def save_checkpoint(model, path, normalizer: Optional[dict] = None) -> dict:
    """Durable full-state checkpoint: atomic zip write + CRC over the final
    bytes. When a DataParallelStep is active on the model, the optimizer
    state is snapshotted OUT of the flat ``[R, m]`` exchange layout (the
    model's structured copy is stale mid-fit) and the per-replica
    compression residuals are captured alongside. Returns
    ``{"path", "crc", "size"}`` for the checkpoint index."""
    from deeplearning4j_tpu.utils import serialization as S

    t0 = time.perf_counter()
    with obs.span("checkpoint.save"):
        opt_state = None
        residuals = None
        runner = getattr(model, "_dp_runner", None)
        if runner is not None:
            if getattr(runner, "_active", False):
                opt_state = runner.snapshot_opt_state()
            residuals = runner.export_residuals() or None
        io_with_retries(
            lambda: S.save_network(model, path, normalizer=normalizer,
                                   train_state=capture_train_state(model),
                                   residuals=residuals, opt_state=opt_state),
            what=f"save_network:{os.path.basename(str(path))}")
        info = {"path": path, "crc": crc32_file(path),
                "size": os.path.getsize(path)}
    dur = time.perf_counter() - t0
    obs.counter("dl4j_checkpoint_saves_total",
                "Checkpoints written via save_checkpoint").inc()
    obs.histogram("dl4j_checkpoint_save_seconds",
                  "Wall time of durable checkpoint writes").observe(dur)
    obs.event("checkpoint_saved", path=str(path), crc=info["crc"],
              size=info["size"], duration_s=round(dur, 6))
    # executable bundle sidecar (nn/aot.py): resume restores params AND
    # compiled executables. save_bundle gates itself (opt-in via
    # DL4J_TPU_AOT_BUNDLE=1) and never raises — the
    # checkpoint above is durable regardless of what happens here.
    from deeplearning4j_tpu.nn import aot

    bundle = aot.save_bundle(model, aot.bundle_path_for(path))
    if bundle is not None:
        info["aot_bundle"] = bundle
    return info


def load_state_into(model, path):
    """Load a checkpoint INTO an existing (config-compatible) model:
    params/state/opt plus the train-state extras. Leaf-count mismatches
    raise (config/checkpoint mismatch) rather than silently truncating."""
    from deeplearning4j_tpu.utils import serialization as S

    t0 = time.perf_counter()
    with obs.span("checkpoint.restore"):
        if model.params is None:
            model.init()
        S.apply_snapshot(model, S.read_snapshot(path))
    dur = time.perf_counter() - t0
    obs.counter("dl4j_checkpoint_restores_total",
                "Checkpoints loaded via load_state_into/resume").inc()
    obs.histogram("dl4j_checkpoint_restore_seconds",
                  "Wall time of checkpoint restores").observe(dur)
    obs.event("checkpoint_restored", path=str(path), duration_s=round(dur, 6))
    return model


def resume(model, directory):
    """Restore ``model`` from the newest VALID checkpoint in ``directory``
    (corrupt/truncated files fall back to older valid ones). Returns the
    Checkpoint record, or None (with a warning) when the directory holds no
    valid checkpoint — training then starts from the model's current state."""
    from deeplearning4j_tpu.train.checkpoint import CheckpointListener

    cp = CheckpointListener.last_valid_checkpoint(directory)
    if cp is None:
        obs.event("checkpoint_corrupt_fallback", directory=str(directory),
                  fallback="none")
        warnings.warn(
            f"resume_from={str(directory)!r}: no valid checkpoint found; "
            "training from the model's current state")
        return None
    path = os.path.join(str(directory), cp.filename)
    load_state_into(model, path)
    # executable bundle sidecar: restore compiled executables alongside the
    # params so the first post-resume step/request is warm. Missing file is
    # a silent no-op; corrupt/mismatched bundles reject to recompile
    # (never raise) — see nn/aot.py.
    from deeplearning4j_tpu.nn import aot

    aot.restore_bundle(model, aot.bundle_path_for(path))
    return cp


# ---------------------------------------------------------------------------
# Distributed checkpoints (elastic multi-host layout)
# ---------------------------------------------------------------------------


def load_distributed_checkpoint(directory) -> Optional[dict]:
    """Load the newest VALID distributed checkpoint from ``directory``.

    The elastic trainer's layout (docs/ROBUSTNESS.md): per-host shard files
    ``shard_<tag>_r<rank>.npz`` (each rank's optimizer segments — its
    primary 1/W slice AND its buddy's mirror — plus compression residuals),
    a replicated ``ckpt_<tag>_params.npz`` (params, dense opt state, layer
    state, meta), and a ``manifest_<tag>.json`` with per-file CRC32 + size
    written LAST by rank 0 — the commit point.

    Validation is per-file: a manifest whose params file fails its CRC falls
    back to the next-older manifest; a corrupt *shard* file is dropped
    individually, because every segment it held also lives in its buddy's
    shard (any host can serve a straggler's shard) — only the trainer can
    judge whether the surviving set covers every segment. Returns
    ``{"manifest", "params", "shards": {rank: arrays}, "path"}`` or None.
    """
    directory = os.fspath(directory)
    manifests = sorted(glob.glob(os.path.join(directory, "manifest_*.json")),
                       reverse=True)
    for mpath in manifests:
        try:
            with open(mpath, "r") as f:
                man = json.load(f)
        except (OSError, ValueError):
            obs.event("checkpoint_corrupt_fallback", path=mpath,
                      reason="manifest unreadable")
            continue
        ppath = os.path.join(directory, man["params"]["file"])
        if not validate_checkpoint(ppath, crc=man["params"]["crc"],
                                   size=man["params"]["size"]):
            obs.event("checkpoint_corrupt_fallback", path=ppath,
                      reason="params file failed CRC/size")
            continue
        pdata = io_with_retries(
            lambda: open(ppath, "rb").read(), what="read:params")
        with np.load(io.BytesIO(pdata), allow_pickle=False) as z:
            params = {k: z[k] for k in z.files}
        shards: Dict[int, Dict[str, np.ndarray]] = {}
        for rank_s, meta in man.get("shards", {}).items():
            spath = os.path.join(directory, meta["file"])
            if not validate_checkpoint(spath, crc=meta["crc"],
                                       size=meta["size"]):
                obs.event("checkpoint_shard_dropped", path=spath,
                          rank=int(rank_s), reason="failed CRC/size")
                continue
            sdata = io_with_retries(
                lambda p=spath: open(p, "rb").read(),
                what=f"read:shard{rank_s}")
            with np.load(io.BytesIO(sdata), allow_pickle=False) as z:
                shards[int(rank_s)] = {k: z[k] for k in z.files}
        obs.event("distributed_checkpoint_loaded", path=mpath,
                  world=man.get("world"), shards=sorted(shards),
                  iteration=man.get("iteration"))
        return {"manifest": man, "params": params, "shards": shards,
                "path": mpath}
    return None


# ---------------------------------------------------------------------------
# Divergence guard
# ---------------------------------------------------------------------------


class DivergenceError(RuntimeError):
    """Raised when the rollback policy exhausts its retry budget (or has no
    valid checkpoint to roll back to)."""


def guard_ok(loss, spike_limit: Optional[float]):
    """Traced predicate: the step's candidate update is acceptable. Runs
    INSIDE the compiled step (device-side; replicated under shard_map since
    the loss is already the replica mean)."""
    ok = jnp.isfinite(loss)
    if spike_limit is not None:
        ok = ok & (loss <= jnp.asarray(spike_limit, loss.dtype))
    return ok


def guard_select(ok, new_tree, old_tree):
    """Traced per-leaf select: keep the candidate when ``ok``, else the
    previous value — the skip_batch policy's whole mechanism, fused into the
    same executable as the step (donation-safe)."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(ok, a, b), new_tree, old_tree)


class DivergenceGuard:
    """Non-finite / loss-spike watchdog for fit loops.

    Policies (``InvalidScoreIterationTerminationCondition`` semantics,
    upgraded from terminate-only to recover):

    - ``warn``: count + warn-once; training proceeds untouched.
    - ``skip_batch``: the compiled step discards the bad update on device
      (``guard_ok``/``guard_select``); the host side only counts/warns.
    - ``rollback``: reload the last valid checkpoint from
      ``checkpoint_dir``, multiply the LR by ``lr_backoff`` (compounding),
      and continue — at most ``max_retries`` times, then
      :class:`DivergenceError`.

    Host syncs: warn/skip batch their score reads in windows of
    ``flush_every`` device scalars (ONE stacked transfer per window, flushed
    again at epoch end) so the guard adds no per-step sync. rollback
    necessarily syncs every step — it must act before the next update.

    Install with ``model.set_divergence_guard(guard)`` (clears the compiled
    step caches: skip_batch is traced into the step).
    """

    POLICIES = ("warn", "skip_batch", "rollback")

    def __init__(self, policy: str = "warn", spike_limit: Optional[float] = None,
                 checkpoint_dir=None, lr_backoff: float = 0.5,
                 max_retries: int = 3, flush_every: int = 32):
        if policy not in self.POLICIES:
            raise ValueError(
                f"DivergenceGuard policy {policy!r} not in {self.POLICIES}")
        if policy == "rollback" and checkpoint_dir is None:
            raise ValueError(
                "DivergenceGuard(policy='rollback') needs checkpoint_dir=")
        self.policy = policy
        self.spike_limit = None if spike_limit is None else float(spike_limit)
        self.checkpoint_dir = checkpoint_dir
        self.lr_backoff = float(lr_backoff)
        self.max_retries = int(max_retries)
        self.flush_every = max(int(flush_every), 1)
        self.trips = 0
        self.retries = 0
        self._pending: List[Any] = []
        self._warned = False

    def _bad_value(self, v: float) -> bool:
        return (not math.isfinite(v)) or (
            self.spike_limit is not None and v > self.spike_limit)

    def observe(self, model, score) -> None:
        """Feed one step's score (device scalar or float) from the fit loop."""
        if self.policy == "rollback":
            v = float(score)  # graftlint: disable=host-sync
            if self._bad_value(v):
                self._trip(model, v)
            return
        self._pending.append(score)
        if len(self._pending) >= self.flush_every:
            self.flush(model)

    def flush(self, model) -> None:
        """Sync the pending window as ONE stacked transfer and act on it."""
        if not self._pending:
            return
        pend, self._pending = self._pending, []
        stacked = jnp.stack([jnp.asarray(v, jnp.float32) for v in pend])
        vals = np.asarray(stacked)  # graftlint: disable=host-sync
        bad = ~np.isfinite(vals)
        if self.spike_limit is not None:
            bad |= vals > self.spike_limit
        if bad.any():
            self._trip(model, float(vals[bad][0]))

    def _trip(self, model, value: float) -> None:
        self.trips += 1
        bucketing.telemetry().record_guard(self.policy)
        obs.event("divergence", policy=self.policy, score=repr(value),
                  trips=self.trips)
        if not self._warned:
            self._warned = True
            warnings.warn(
                f"DivergenceGuard: non-finite or spiking training score "
                f"{value!r} (policy={self.policy}, trip #{self.trips}); see "
                "docs/ROBUSTNESS.md")
        if self.policy != "rollback":
            return
        if self.retries >= self.max_retries:
            raise DivergenceError(
                f"divergence persisted through {self.retries} rollback "
                f"retries (last score {value!r})")
        self.retries += 1
        if resume(model, self.checkpoint_dir) is None:
            raise DivergenceError(
                f"cannot roll back: no valid checkpoint in "
                f"{str(self.checkpoint_dir)!r}")
        # compounding backoff on top of whatever scale the checkpoint carried
        model._lr_scale = getattr(model, "_lr_scale", 1.0) * self.lr_backoff
        model._build_updaters()
        if hasattr(model, "_clear_compiled"):
            model._clear_compiled()
        runner = getattr(model, "_dp_runner", None)
        if runner is not None and getattr(runner, "_active", False):
            runner.reload()
        bucketing.telemetry().record_guard("rollback_restore")
        obs.event("rollback_restore", retries=self.retries,
                  lr_scale=float(model._lr_scale))


_INVALID_SCORE_WARNED = False


def note_score(score: float) -> None:
    """InvalidScoreIterationTerminationCondition semantics on the DEFAULT fit
    path: when the already-synced listener score goes non-finite, count it in
    the bucketing telemetry snapshot and warn once (pointing at the guard
    policies that can act on it). Costs nothing — the score was synced for
    the listeners anyway."""
    if math.isfinite(score):
        return
    bucketing.telemetry().record_guard("invalid_score")
    obs.event("invalid_score", score=repr(score))
    global _INVALID_SCORE_WARNED
    if not _INVALID_SCORE_WARNED:
        _INVALID_SCORE_WARNED = True
        warnings.warn(
            f"training score became non-finite ({score!r}). Attach "
            "DivergenceGuard(policy='skip_batch'|'rollback') via "
            "model.set_divergence_guard(...) to recover automatically, or an "
            "early-stopping InvalidScoreIterationTerminationCondition to "
            "terminate (docs/ROBUSTNESS.md)")


# ---------------------------------------------------------------------------
# Chaos harness
# ---------------------------------------------------------------------------


class ChaosPreemption(RuntimeError):
    """Raised by the chaos injector to simulate a preemption (the in-process
    flavor of kill; ``preempt@iter:K:kill`` sends a real SIGKILL instead)."""


@dataclass
class _Fault:
    kind: str
    at_iter: Optional[int] = None
    at_ckpt: Optional[int] = None
    arg: Optional[str] = None
    fired: bool = False


_FAULT_KINDS = ("preempt", "corrupt_ckpt", "nan_grad", "slow_iter",
                "host_kill", "net_partition", "slice_kill",
                "rack_partition")


def _parse_fault(token: str) -> _Fault:
    name, at_iter, at_ckpt, arg = token, None, None, None
    if "@" in token:
        name, rest = token.split("@", 1)
        parts = rest.split(":")
        if len(parts) < 2 or not parts[1]:
            raise ValueError(
                f"chaos fault {token!r}: anchor must be @iter:K or @ckpt:K")
        where, val = parts[0], parts[1]
        # args may themselves contain ':' (e.g. net_partition's rank1:4.0)
        arg = ":".join(parts[2:]) or None
        if where == "iter":
            at_iter = int(val)
        elif where == "ckpt":
            at_ckpt = int(val)
        else:
            raise ValueError(
                f"chaos fault {token!r}: unknown anchor @{where} "
                "(use @iter:K or @ckpt:K)")
    elif ":" in token:
        name, arg = token.split(":", 1)
    if name not in _FAULT_KINDS:
        raise ValueError(
            f"chaos fault {token!r}: unknown kind {name!r} "
            f"(known: {', '.join(_FAULT_KINDS)})")
    return _Fault(kind=name, at_iter=at_iter, at_ckpt=at_ckpt, arg=arg)


def _nan_like(x):
    """NaN-poison float members of a batch (integer token-id features cannot
    hold NaN and pass through untouched)."""
    if x is None:
        return None
    if isinstance(x, (tuple, list)):
        return type(x)(_nan_like(a) for a in x)
    dt = getattr(x, "dtype", None)
    if dt is None:
        x = np.asarray(x)
        dt = x.dtype
    if jnp.issubdtype(jnp.dtype(dt), jnp.floating):
        # multiply (not fill): preserves shape, dtype, AND device sharding
        return jnp.asarray(x) * jnp.asarray(float("nan"), jnp.dtype(dt))
    return x


def corrupt_file(path, mode: str = "bitflip") -> None:
    """Deterministically damage a file in place: ``truncate`` halves it
    (size mismatch), ``bitflip`` XORs one mid-file byte (CRC mismatch at an
    unchanged size)."""
    size = os.path.getsize(path)
    if mode == "truncate":
        with open(path, "r+b") as f:
            f.truncate(max(size // 2, 1))
        return
    if mode != "bitflip":
        raise ValueError(f"corrupt_ckpt arg {mode!r}: use truncate|bitflip")
    with open(path, "r+b") as f:
        f.seek(size // 2)
        b = f.read(1)
        f.seek(size // 2)
        f.write(bytes([(b[0] ^ 0x40) if b else 0xFF]))


class ChaosInjector:
    """Deterministic fault injector. Grammar (``DL4J_TPU_CHAOS``):

    comma-separated faults, each ``kind[@iter:K|@ckpt:K][:arg]``:

    - ``preempt@iter:K[:kill]`` — die before the step whose iteration
      counter is >= K runs: raise :class:`ChaosPreemption` (default) or send
      a real SIGKILL (``:kill``). Fires once per process.
    - ``nan_grad[@iter:K]`` — NaN-poison the batch features of iteration K
      (every float activation/gradient downstream goes NaN). Fires once.
    - ``slow_iter[@iter:K][:rankN][:seconds]`` — sleep before the step
      (default 0.05 s); without an anchor, every step (a stalled iterator).
      A ``rankN`` target limits the stall to one data-parallel rank — the
      deterministic straggler the fleet skew detector must flag.
    - ``corrupt_ckpt[@ckpt:K][:truncate|bitflip]`` — damage checkpoint
      number K (or the first one written) AFTER its CRC is recorded, so
      validation must catch it. Fires once.
    - ``host_kill@iter:K[:rankN]`` — the distributed flavor of kill: SIGKILL
      the process before the step whose iteration is >= K, only when this
      worker's data-parallel rank matches the ``rankN`` target (no target:
      every rank that consults the hook). Fires once; drives the elastic
      shrink path (tests/test_elastic.py, tools/elastic_smoke.sh).
    - ``net_partition@iter:K[:rankN][:seconds]`` — simulate this worker
      landing on the wrong side of a switch: the elastic runtime suspends
      its lease heartbeat and stalls for ``seconds`` (default 5.0). A stall
      longer than the lease TTL gets the worker expelled; on waking it
      renews its lease and rejoins through the membership handoff.
    - ``slice_kill@iter:K[:sliceN]`` — the fleet-scale flavor of kill: in
      the elastic-of-slices composition each member process IS one
      ``(d,t,s)`` mesh slice (member = slice coordinator), so a slice
      preemption is one SIGKILL of the member whose slice index (= elastic
      rank) matches ``sliceN`` (no target: every slice that consults the
      hook). One membership event per slice, not per chip.
    - ``rack_partition@iter:K[:LABEL][:seconds]`` — ``net_partition`` for a
      whole rack: every worker whose ``DL4J_TPU_RACK`` label equals
      ``LABEL`` (no label: all workers) suspends its heartbeat and stalls
      for ``seconds`` (default 5.0) — the R-way rack-aware mirrors must
      carry every optimizer segment whose owner sat in that rack.

    Faults are host-side and one-shot: a resumed run that re-executes the
    target iteration is NOT re-hit (the process that resumed carries a fresh
    injector only if the spec is still installed — clear the env var /
    ``install_chaos(None)`` for clean resumes).
    """

    def __init__(self, faults, spec: str = ""):
        self.faults = list(faults)
        self.spec = spec

    @staticmethod
    def parse(spec: str) -> "ChaosInjector":
        faults = [_parse_fault(t.strip()) for t in spec.split(",") if t.strip()]
        return ChaosInjector(faults, spec)

    # -- per-iteration hooks (fit dispatch paths) ---------------------------
    def maybe_preempt(self, iteration: int) -> None:
        for f in self.faults:
            if (f.kind == "preempt" and not f.fired
                    and f.at_iter is not None and iteration >= f.at_iter):
                f.fired = True
                obs.event("chaos", fault="preempt", iteration=iteration,
                          arg=f.arg)
                if f.arg == "kill":
                    os.kill(os.getpid(), signal.SIGKILL)
                raise ChaosPreemption(
                    f"chaos: preempted at iteration {iteration}")

    def maybe_slow(self, iteration: int, *, rank: Optional[int] = None) -> None:
        for f in self.faults:
            if f.kind != "slow_iter":
                continue
            # rank-targeted straggler injection (``slow_iter:rank1:0.5``):
            # only the targeted data-parallel rank stalls, so the skew is
            # attributable — the straggler detector's test fixture
            target, rest = self._rank_arg(f.arg)
            if target is not None and (rank is None or rank != target):
                continue
            if f.at_iter is None or (iteration == f.at_iter and not f.fired):
                if f.at_iter is not None:
                    f.fired = True
                    obs.event("chaos", fault="slow_iter", iteration=iteration,
                              rank=rank)
                time.sleep(float(rest) if rest else 0.05)

    def maybe_nan_batch(self, iteration: int, x):
        for f in self.faults:
            if f.kind != "nan_grad" or f.fired:
                continue
            if f.at_iter is None or iteration == f.at_iter:
                f.fired = True
                obs.event("chaos", fault="nan_grad", iteration=iteration)
                return _nan_like(x)
        return x

    # -- distributed hooks (ElasticTrainer step boundary) -------------------
    @staticmethod
    def _prefixed_arg(arg: Optional[str], prefix: str):
        """Split a fault arg into (target_index, rest) for a ``<prefix>N``
        head: ``rank1:4.0`` -> (1, "4.0"), ``slice2`` -> (2, None), a
        non-matching head -> (None, arg)."""
        if not arg:
            return None, None
        head, _, rest = arg.partition(":")
        if head.startswith(prefix) and head[len(prefix):].isdigit():
            return int(head[len(prefix):]), (rest or None)
        return None, arg

    @staticmethod
    def _rank_arg(arg: Optional[str]):
        """Split a fault arg into (target_rank, rest): ``rank1:4.0`` ->
        (1, "4.0"), ``rank2`` -> (2, None), ``3.5`` -> (None, "3.5")."""
        return ChaosInjector._prefixed_arg(arg, "rank")

    def maybe_host_kill(self, iteration: int, *, rank: int) -> None:
        for f in self.faults:
            if (f.kind != "host_kill" or f.fired or f.at_iter is None
                    or iteration < f.at_iter):
                continue
            target, _ = self._rank_arg(f.arg)
            if target is not None and target != rank:
                continue
            f.fired = True
            obs.event("chaos", fault="host_kill", iteration=iteration,
                      rank=rank)
            os.kill(os.getpid(), signal.SIGKILL)

    def maybe_slice_kill(self, iteration: int, *, slice_index: int) -> None:
        """SIGKILL this member process when a ``slice_kill`` fault targets
        its slice index — one whole-slice preemption, one membership
        event (the member process carries the entire slice mesh)."""
        for f in self.faults:
            if (f.kind != "slice_kill" or f.fired or f.at_iter is None
                    or iteration < f.at_iter):
                continue
            target, _ = self._prefixed_arg(f.arg, "slice")
            if target is not None and target != slice_index:
                continue
            f.fired = True
            obs.event("slice_kill", iteration=iteration, slice=slice_index)
            obs.event("chaos", fault="slice_kill", iteration=iteration,
                      slice=slice_index)
            os.kill(os.getpid(), signal.SIGKILL)

    def rack_partition_seconds(self, iteration: int, *, rack: str) -> float:
        """Non-zero when a ``rack_partition`` fault hits this worker's rack
        label at this iteration; the caller owns the mechanics (suspend
        heartbeat + stall), same as :meth:`partition_seconds`."""
        for f in self.faults:
            if (f.kind != "rack_partition" or f.fired or f.at_iter is None
                    or iteration < f.at_iter):
                continue
            label: Optional[str] = None
            secs = 5.0
            if f.arg:
                head, _, rest = f.arg.partition(":")
                try:
                    secs = float(head)   # bare seconds: every rack
                except ValueError:
                    label = head
                    if rest:
                        secs = float(rest)
            if label is not None and label != rack:
                continue
            f.fired = True
            obs.event("chaos", fault="rack_partition", iteration=iteration,
                      rack=rack, seconds=secs)
            return secs
        return 0.0

    def partition_seconds(self, iteration: int, *, rank: int) -> float:
        """Non-zero when a ``net_partition`` fault targets this (iteration,
        rank); the caller owns the mechanics (suspend heartbeat + stall)."""
        for f in self.faults:
            if (f.kind != "net_partition" or f.fired or f.at_iter is None
                    or iteration < f.at_iter):
                continue
            target, rest = self._rank_arg(f.arg)
            if target is not None and target != rank:
                continue
            f.fired = True
            obs.event("chaos", fault="net_partition", iteration=iteration,
                      rank=rank, seconds=rest)
            return float(rest) if rest else 5.0
        return 0.0

    # -- checkpoint hook (CheckpointListener._save) -------------------------
    def maybe_corrupt(self, path, ckpt_number: int) -> None:
        for f in self.faults:
            if f.kind != "corrupt_ckpt" or f.fired:
                continue
            if f.at_ckpt is None or ckpt_number == f.at_ckpt:
                f.fired = True
                obs.event("chaos", fault="corrupt_ckpt", path=str(path),
                          mode=f.arg or "bitflip")
                corrupt_file(path, mode=f.arg or "bitflip")


_UNSET = object()
_chaos_override: Any = _UNSET
_env_injectors: Dict[str, ChaosInjector] = {}


def install_chaos(spec):
    """Programmatic chaos install (wins over ``DL4J_TPU_CHAOS``). Pass a
    grammar string or a :class:`ChaosInjector`; ``None`` clears the override
    (the environment variable rules again). Returns the active injector."""
    global _chaos_override
    if spec is None:
        _chaos_override = _UNSET
        return None
    inj = spec if isinstance(spec, ChaosInjector) else ChaosInjector.parse(spec)
    _chaos_override = inj
    return inj


def active_chaos() -> Optional[ChaosInjector]:
    """The installed injector, the env-configured one, or None. The env
    injector is cached per spec string so one-shot faults stay one-shot
    across the many hooks that consult it."""
    if _chaos_override is not _UNSET:
        return _chaos_override
    spec = os.environ.get("DL4J_TPU_CHAOS")
    if not spec:
        return None
    inj = _env_injectors.get(spec)
    if inj is None:
        inj = ChaosInjector.parse(spec)
        _env_injectors[spec] = inj
    return inj
