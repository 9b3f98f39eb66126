"""Ahead-of-time compilation and safe executable persistence.

Compile time is the worst production latency the framework has: the first
touch of every (shape bucket, step variant) pays an XLA compile in the
request/step path. This module kills that cold start twice over:

1. **AOT warmup** — walk the shared bucket ladder (``utils/bucketing.py``)
   and eagerly ``jit(...).lower(...).compile()`` every (bucket, variant) the
   step and output paths can hit, BEFORE traffic arrives. ``lower().compile()``
   deliberately does not populate jit's internal dispatch cache, so the
   compiled executables are owned here: :class:`AotFunction` wraps each jitted
   entry point and dispatches through the stored ``Compiled`` on a signature
   match, falling back to the lazy jit otherwise (a miss is never an error).
   The enumeration (``reachable_buckets``) is the same ladder arithmetic the
   retrace guard bounds compiles against, and every warmed bucket is
   cross-registered (``retrace_guard.register_aot_warmed``) so AOT and the
   guard check each other: AOT can't warm shapes the guard would flag, and
   guard violations still fire for traffic outside the warmed set.

2. **Safe executable persistence** — serialized executables
   (``jax.experimental.serialize_executable``) ship in a CRC'd, versioned
   zip bundle written with the same ``serialization._atomic_write_zip``
   durability dance as checkpoints, and ride alongside checkpoints so resume
   restores params AND executables. Persistence is OPT-IN
   (``DL4J_TPU_AOT_BUNDLE=1``) and the opt-in is trusted: the library
   validates nothing at run time and starts no process — an accelerator
   belongs to one process at a time, so the process that holds it can
   never hand a check to a child. The serialize→deserialize→execute
   bitwise-parity harness is a standalone tool (``python -m
   deeplearning4j_tpu.nn.aot``) to run once per (backend, toolchain)
   before opting in. Any load failure falls back to plain AOT recompile.

Trust model: bundle payloads deserialize through jax's pickler. A bundle is
a TRUSTED artifact (same trust class as the code itself), which is why the
manifest pins jax/jaxlib versions, backend platform and the model/ladder
signature, and why every entry is CRC-checked — corruption and version skew
are detected and rejected to the recompile path, but bundles must not be
accepted from untrusted sources (checkpoints stay pickle-free; the bundle
is a separate sidecar precisely so this caveat never touches them).

Env knobs (read per call):

- ``DL4J_TPU_AOT``          master switch for the implicit warmup hooks in
                            ``fit()`` / ``ParallelInference`` (default 0 —
                            explicit ``warm_*`` calls always work)
- ``DL4J_TPU_AOT_BUNDLE``   executable persistence: ``1`` on (trusted
                            opt-in), anything else — including the
                            default ``auto`` — off on every backend
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import pickle
import sys
import threading
import time
import zipfile
import zlib
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.analysis import retrace_guard
from deeplearning4j_tpu.obs import profile as _profile
from deeplearning4j_tpu.utils import bucketing

__all__ = [
    "AotFunction",
    "BUNDLE_FORMAT_VERSION",
    "bundle_path_for",
    "distributed_bundle_manifest",
    "distributed_bundle_path",
    "enabled",
    "model_signature",
    "persistence_allowed",
    "reachable_buckets",
    "restore_bundle",
    "restore_distributed_bundle",
    "save_bundle",
    "save_distributed_bundle",
    "toolchain_fingerprint",
    "warm_dp",
    "warm_fit",
    "warm_serving",
    "warm_serving_bundled",
    "wrap",
]

BUNDLE_FORMAT_VERSION = 3
_MANIFEST_ENTRY = "manifest.json"


def enabled() -> bool:
    """Master switch for the implicit warmup hooks (fit/ParallelInference).
    Default OFF: a full ladder walk is a deliberate cost, and the test
    suite must not pay it on every model construction."""
    return os.environ.get("DL4J_TPU_AOT", "0") == "1"


# ---------------------------------------------------------------------------
# Signature keys
# ---------------------------------------------------------------------------


def _leaf_meta(leaf) -> Tuple:
    shape = getattr(leaf, "shape", None)
    if shape is None:
        shape = np.shape(leaf)
    dtype = getattr(leaf, "dtype", None)
    if dtype is None:
        dtype = np.asarray(leaf).dtype
    return (tuple(shape), np.dtype(dtype).str,
            bool(getattr(leaf, "weak_type", False)))


def signature_key(args: tuple, kwargs: dict) -> Tuple:
    """Hashable call signature: the (args, kwargs) pytree structure plus
    per-leaf (shape, dtype, weak_type) — exactly what decides whether jit
    would dispatch to an existing executable or retrace."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (treedef, tuple(_leaf_meta(l) for l in leaves))


def _sig_label(key: Tuple) -> str:
    """Stable short label for a signature key (cost-model gauge label when
    no bucket is known)."""
    return f"sig{abs(hash(key)) % 10**8:08d}"


# ---------------------------------------------------------------------------
# The dispatcher
# ---------------------------------------------------------------------------


class _RestoredStaticCall:
    """Call adapter for bundle-restored executables of static-arg sites:
    strips the static positions from the full-signature dispatch call.
    ``raw_compiled`` stays reachable so re-bundling serializes the real
    executable, not this wrapper."""

    __slots__ = ("raw_compiled", "_statics")

    def __init__(self, compiled, statics):
        self.raw_compiled = compiled
        self._statics = frozenset(statics)

    def __call__(self, *args, **kwargs):
        dyn = tuple(a for i, a in enumerate(args) if i not in self._statics)
        return self.raw_compiled(*dyn, **kwargs)


class AotFunction:
    """A jitted function plus a cache of AOT-compiled executables.

    ``lower().compile()`` does NOT warm jit's internal dispatch cache, so
    ahead-of-time compiles must own dispatch: calls whose signature matches
    a warmed entry go straight to the stored ``Compiled`` (donation
    semantics identical — the executable was lowered from the same jit);
    everything else falls through to the lazy jit. The fast path for
    un-warmed functions is a single truthiness check on an empty dict."""

    def __init__(self, jitted, site: str,
                 static_argnums: Optional[Tuple[int, ...]] = None):
        self._jit = jitted
        self.site = site
        self._static_argnums = tuple(static_argnums or ())
        self._compiled: Dict[Tuple, Any] = {}
        self._lock = threading.Lock()

    def _key(self, args: tuple, kwargs: dict) -> Tuple:
        """Dispatch key. ``signature_key`` sees only shape/dtype, under
        which all python-int static args collide (k=1 and k=16 both read as
        a 0-d int leaf) — but jit keys statics by VALUE, so the AOT cache
        must too or warming k=1 silently shadows every other k."""
        key = signature_key(args, kwargs)
        if self._static_argnums:
            key = key + (tuple(args[i] for i in self._static_argnums
                               if i < len(args)),)
        return key

    # -- warmup ------------------------------------------------------------
    def warm(self, *args, cost_key: Optional[str] = None, **kwargs):
        """Compile (without executing) for this exact call signature and
        cache the executable; returns the ``Compiled`` (idempotent).
        ``cost_key`` labels the executable's cost-model gauges (warmers pass
        the bucket, e.g. ``b64``; defaults to a signature hash)."""
        key = self._key(args, kwargs)
        existing = self._compiled.get(key)
        if existing is not None:
            return existing
        with obs.compile_span(self.site, mode="aot"):
            compiled = self._jit.lower(*args, **kwargs).compile()
        _profile.harvest_compiled(
            self.site, compiled, key=cost_key or _sig_label(key))
        with self._lock:
            # a concurrent warm of the same key wastes one compile at worst
            self._compiled.setdefault(key, compiled)
        return self._compiled[key]

    def install(self, key: Tuple, compiled) -> None:
        """Adopt an already-built executable (bundle restore path)."""
        raw = compiled
        if self._static_argnums:
            # a deserialized executable takes DYNAMIC args only (the
            # serialized in_tree drops static_argnums), while a fresh
            # lower().compile() object takes the full signature — adapt so
            # dispatch stays uniform
            compiled = _RestoredStaticCall(raw, self._static_argnums)
        with self._lock:
            self._compiled[key] = compiled
        _profile.harvest_compiled(self.site, raw, key=_sig_label(key))

    @property
    def compiled_count(self) -> int:
        return len(self._compiled)

    def signatures(self) -> List[Tuple]:
        return list(self._compiled)

    # -- dispatch ----------------------------------------------------------
    def __call__(self, *args, **kwargs):
        if self._compiled:
            key = self._key(args, kwargs)
            compiled = self._compiled.get(key)
            if compiled is not None:
                try:
                    out = compiled(*args, **kwargs)
                except TypeError:
                    # aval/layout mismatch the key was too coarse to see:
                    # raised before execution, so inputs (incl. donated
                    # buffers) are intact — evict and recompile lazily
                    with self._lock:
                        self._compiled.pop(key, None)
                    obs.counter(
                        "dl4j_aot_dispatch_fallbacks_total",
                        "AOT executables evicted on dispatch mismatch",
                        ("site",)).inc(site=self.site)
                    return self._lazy(args, kwargs)
                obs.counter(
                    "dl4j_aot_warm_hits_total",
                    "dispatches served by an AOT/bundle-restored executable",
                    ("site",)).inc(site=self.site)
                return out
        return self._lazy(args, kwargs)

    def _lazy(self, args, kwargs):
        out = self._jit(*args, **kwargs)
        # a compile just happened on this dispatch iff record_trace flagged
        # the site during tracing; capture its abstract signature so
        # cost_report() can price the executable later. One set lookup on
        # the warm path, aval capture only on the (rare) compile path.
        if _profile.wants_exemplar(self.site):
            _profile.note_exemplar(self.site, self, args, kwargs)
        return out

    # convenience parity with jax.jit objects used elsewhere
    def lower(self, *args, **kwargs):
        return self._jit.lower(*args, **kwargs)


def wrap(jitted, site: str, model=None,
         static_argnums: Optional[Tuple[int, ...]] = None) -> AotFunction:
    """Wrap a jitted entry point for AOT dispatch and register it on the
    model's AOT function registry (``model._aot_fns``). Executables restored
    from a bundle before the function existed (``restore_bundle`` on a fresh
    model) are waiting in ``model._aot_pending`` and are adopted here.
    ``static_argnums`` must mirror the jit's own, so dispatch keys carry the
    static VALUES exactly like jit's cache does."""
    fn = AotFunction(jitted, site, static_argnums=static_argnums)
    if model is not None:
        reg = model.__dict__.setdefault("_aot_fns", {})
        reg[site] = fn
        pending = model.__dict__.get("_aot_pending")
        if pending:
            for key, compiled in pending.pop(site, ()):
                fn.install(key, compiled)
    return fn


def clear_sites(model, sites) -> None:
    """Drop registry entries for re-built jitted functions (stale
    executables must not be re-bundled after e.g. an updater change)."""
    reg = model.__dict__.get("_aot_fns")
    if reg:
        for s in sites:
            reg.pop(s, None)


# ---------------------------------------------------------------------------
# Ladder enumeration
# ---------------------------------------------------------------------------


def reachable_buckets(max_n: int,
                      ladder: Optional[bucketing.BucketLadder] = None) -> List[int]:
    """Every bucket a leading dim in [1, max_n] can land on — the exact set
    the retrace guard's predicted-compile bound counts, walked bucket
    boundary by bucket boundary (O(#buckets), not O(max_n))."""
    ladder = ladder or bucketing.ladder_from_env()
    out: List[int] = []
    n = 1
    while n <= max_n:
        b = ladder.bucket(n)
        out.append(b)
        n = b + 1
    return out


# ---------------------------------------------------------------------------
# Warmers
# ---------------------------------------------------------------------------


def _is_graph(model) -> bool:
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    return isinstance(model, ComputationGraph)


def _dummy_features(model, batch: int):
    from deeplearning4j_tpu.nn.memory import _dummy_for

    if _is_graph(model):
        return tuple(_dummy_for(model.conf.input_types[n], batch, model.dtype)
                     for n in model.conf.inputs)
    return _dummy_for(model.conf.input_type, batch, model.dtype)


def warm_serving(model, max_batch: int,
                 ladder: Optional[bucketing.BucketLadder] = None) -> int:
    """AOT-compile the inference path for every ladder bucket reachable by
    batches up to ``max_batch`` (the ParallelInference coalescing cap /
    server warm target). Returns the number of executables now warm."""
    if model.params is None:
        model.init()
    is_graph = _is_graph(model)
    if is_graph and model._has_batch_vertices:
        # Stack/Unstack graphs run unbucketed (output() skips padding), so
        # there is no finite bucket set to enumerate
        obs.event("aot_warmup_skipped", site="cg.output",
                  reason="batch_vertices")
        return 0
    buckets = (reachable_buckets(max_batch, ladder)
               if bucketing.bucketing_enabled() else [max_batch])
    fn = model._get_output_fn()
    site = "cg.output" if is_graph else "mln.output"
    t0 = time.perf_counter()
    for b in buckets:
        feats = _dummy_features(model, b)
        if is_graph:
            fn.warm(model.params, model.state, model._input_dict(feats), None,
                    cost_key=f"b{b}")
        else:
            fn.warm(model.params, model.state, feats, None, cost_key=f"b{b}")
    retrace_guard.register_aot_warmed(site, buckets)
    obs.event("aot_warmup", site=site, buckets=list(buckets),
              executables=fn.compiled_count,
              duration_s=round(time.perf_counter() - t0, 6))
    return fn.compiled_count


def warm_serving_bundled(model, max_batch: int, bundle_path,
                         ladder: Optional[bucketing.BucketLadder] = None
                         ) -> Tuple[int, int]:
    """The serving tier's one-call warm pipeline: restore any executables
    persisted at ``bundle_path``, ladder-warm the inference path up to
    ``max_batch`` (restored signatures dispatch instead of recompiling),
    then persist the now-warm set back (best-effort; both bundle directions
    are validation-gated by ``persistence_allowed``). Returns
    ``(restored, warmed)`` executable counts."""
    restored = restore_bundle(model, bundle_path) if bundle_path else 0
    warmed = warm_serving(model, max_batch, ladder)
    if bundle_path and warmed:
        save_bundle(model, bundle_path)
    return restored, warmed


def _first_fit_batch(model, data, batch_size):
    """(x, y, fm, lm, pad_target) for the first batch fit() will dispatch,
    or None when the source is streaming (not inspectable without consuming
    it) — mirrors fit()'s own _fit_pad_target/_iter_batches handling."""
    from deeplearning4j_tpu.nn import model as M

    source = data() if callable(data) else data
    if hasattr(source, "as_tuple"):
        source = source.as_tuple()
    if not (isinstance(source, (tuple, list)) and len(source) >= 2
            and not isinstance(source[0], (tuple, list, dict))):
        return None
    pad_target = (M._fit_pad_target(source, batch_size)
                  if bucketing.bucketing_enabled() else None)
    x, y, fm, lm = M._as_batch(source)
    b = min(batch_size or len(x), len(x))
    sl = slice(0, b)
    return (x[sl], y[sl] if y is not None else None,
            fm[sl] if fm is not None else None,
            lm[sl] if lm is not None else None, pad_target)


def warm_fit(model, data, batch_size: Optional[int] = None) -> int:
    """AOT-compile the training step for the batch shape(s) fit() is about
    to dispatch — uses the REAL leading arrays (label dtypes matter: sparse
    integer labels trace a different executable than dense floats), sliced,
    never consumed. Streaming sources return 0 (their shapes aren't
    knowable up front). With a bundle already restored this is a pure
    cache-key check: zero compiles, and the first step is warm."""
    from deeplearning4j_tpu.nn.model import _cast_input, _cast_labels

    import jax
    import jax.numpy as jnp

    if _is_graph(model):
        return _warm_fit_graph(model, data, batch_size)
    if model.params is None:
        model.init()
    first = _first_fit_batch(model, data, batch_size)
    if first is None:
        return 0
    x, y, fm, lm, pad_target = first
    ew = None
    if pad_target is not None:
        # the padded-fit calling convention: uniform lm/ew channels so full
        # and partial batches share one executable (bucketing.pad_fit_batch)
        x, y, fm, lm, ew = bucketing.pad_fit_batch(
            x, y, fm, lm, pad_target, site="mln.fit")
    step = model._get_step_fn(False)
    before = step.compiled_count
    t0 = time.perf_counter()
    bucket = pad_target if pad_target is not None else len(x)
    step.warm(
        model.params, model.opt_state, model.state,
        jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
        _cast_input(x, model.dtype), _cast_labels(y, model.dtype),
        jnp.asarray(fm, model.dtype) if fm is not None else None,
        jnp.asarray(lm, model.dtype) if lm is not None else None, (),
        ex_weight=jnp.asarray(ew, model.dtype) if ew is not None else None,
        cost_key=f"b{bucket}",
    )
    retrace_guard.register_aot_warmed("mln.step", [bucket])
    obs.event("aot_warmup", site="mln.step", buckets=[int(bucket)],
              executables=step.compiled_count,
              duration_s=round(time.perf_counter() - t0, 6))
    return step.compiled_count - before


def _warm_fit_graph(model, data, batch_size: Optional[int]) -> int:
    import jax
    import jax.numpy as jnp

    if model.params is None:
        model.init()
    source = data() if callable(data) else data
    if hasattr(source, "as_tuple"):
        source = source.as_tuple()
    if not model._is_single_multibatch(source):
        return 0
    pad_target = (model._fit_pad_target_multi(source, batch_size)
                  if bucketing.bucketing_enabled() else None)
    # _as_multi_batch normalizes/casts exactly as _iter_multi does for the
    # real epoch stream; fit_batch then passes the members verbatim, so no
    # second cast here either
    f, l, fm, lm = model._as_multi_batch(source)
    b = min(batch_size or len(f[0]), len(f[0]))
    sl_t = lambda t: (tuple(a[:b] if a is not None else None for a in t)
                      if t is not None else None)
    f, l, fm, lm = sl_t(f), sl_t(l), sl_t(fm), sl_t(lm)
    ew = None
    if pad_target is not None:
        f, l, fm, lm, ew = bucketing.pad_fit_multi(
            f, l, fm, lm, pad_target, site="cg.fit")
    step = model._get_step_fn(False)
    before = step.compiled_count
    t0 = time.perf_counter()
    bucket = pad_target if pad_target is not None else b
    step.warm(
        model.params, model.opt_state, model.state,
        jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
        model._input_dict(f), l, model._mask_dict(fm), lm, {},
        ex_weight=jnp.asarray(ew, model.dtype) if ew is not None else None,
        cost_key=f"b{bucket}",
    )
    retrace_guard.register_aot_warmed("cg.step", [bucket])
    obs.event("aot_warmup", site="cg.step", buckets=[int(bucket)],
              executables=step.compiled_count,
              duration_s=round(time.perf_counter() - t0, 6))
    return step.compiled_count - before


def warm_dp(runner, x, y, fm=None, lm=None, ew=None) -> int:
    """AOT-compile a DataParallelStep's shard_map step for one global batch
    shape (the grad-exchange variant of the tentpole: compressed and/or
    sharded-update executables are a different trace than the single-chip
    step). Enters the exchange layout if needed — ``lower`` only reads
    avals, so the donated carry is untouched."""
    from deeplearning4j_tpu.nn.model import _cast_input, _cast_labels

    import jax
    import jax.numpy as jnp

    if not runner._active:
        runner.begin()
    model = runner.model
    step = runner._step
    before = step.compiled_count
    t0 = time.perf_counter()
    bucket = len(x[0] if runner.is_graph else x)
    if runner.is_graph:
        f = tuple(_cast_input(a, model.dtype) for a in x)
        step.warm(
            model.params, (runner._opt_flat, runner._residual), model.state,
            jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
            model._input_dict(f), y, model._mask_dict(fm), lm, {},
            jnp.asarray(ew, model.dtype) if ew is not None else None,
            cost_key=f"b{bucket}")
        site = "cg.step"
    else:
        step.warm(
            model.params, (runner._opt_flat, runner._residual), model.state,
            jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
            _cast_input(x, model.dtype), _cast_labels(y, model.dtype),
            jnp.asarray(fm, model.dtype) if fm is not None else None,
            jnp.asarray(lm, model.dtype) if lm is not None else None, (),
            jnp.asarray(ew, model.dtype) if ew is not None else None,
            cost_key=f"b{bucket}")
        site = "mln.step"
    retrace_guard.register_aot_warmed(site, [bucket])
    obs.event("aot_warmup", site="dp.step", buckets=[int(bucket)],
              executables=step.compiled_count,
              duration_s=round(time.perf_counter() - t0, 6))
    return step.compiled_count - before


# ---------------------------------------------------------------------------
# Persistence gating
# ---------------------------------------------------------------------------


def _serialize(compiled) -> dict:
    """``jse.serialize`` plus the ids of the devices the executable runs
    on: ``deserialize_and_load`` otherwise loads it across EVERY local
    device and a one-device executable then demands one shard per device."""
    from jax.experimental import serialize_executable as jse

    payload, in_tree, out_tree = jse.serialize(compiled)
    return {"payload": payload, "in_tree": in_tree, "out_tree": out_tree,
            "device_ids": [d.id for d in
                           compiled.runtime_executable().local_devices()]}


def _deserialize(rec: dict):
    import jax
    from jax.experimental import serialize_executable as jse

    by_id = {d.id: d for d in jax.devices()}
    return jse.deserialize_and_load(
        rec["payload"], rec["in_tree"], rec["out_tree"],
        execution_devices=[by_id[i] for i in rec["device_ids"]])


def _selftest() -> dict:
    """The parity harness (``python -m deeplearning4j_tpu.nn.aot``):
    compile, serialize, deserialize, execute original and restored
    executables on identical inputs, compare BITWISE. Run it once per
    (backend, toolchain) before opting in to bundles; the library itself
    never runs it (see ``persistence_allowed``)."""
    import jax
    import jax.numpy as jnp

    out = {"backend": jax.default_backend(), "ok": False, "cases": []}

    def case(shape, donate):
        def f(w, x):
            return jnp.tanh(x @ w) * 0.5 + x.sum()

        # probe executable, not a training step — exempt from the
        # one-step-program rule
        jitted = jax.jit(  # graftlint: disable=step-wiring
            f, donate_argnums=(0,) if donate else ())
        mk = lambda: (
            jnp.asarray(np.linspace(-1.0, 1.0, shape[1] * shape[1],
                                    dtype=np.float32).reshape(shape[1],
                                                              shape[1])),
            jnp.asarray(np.arange(shape[0] * shape[1],
                                  dtype=np.float32).reshape(shape)),
        )
        compiled = jitted.lower(*mk()).compile()
        rec = _serialize(compiled)
        restored = _deserialize(rec)
        # validation harness, not a hot path: the whole point is comparing
        # materialized bytes on the host
        a = np.asarray(compiled(*mk()))  # graftlint: disable=host-sync
        b = np.asarray(restored(*mk()))  # graftlint: disable=host-sync
        return {"shape": list(shape), "donate": donate,
                "parity": bool(  # graftlint: disable=host-sync
                    a.tobytes() == b.tobytes()),
                "payload_bytes": len(rec["payload"])}

    for shape, donate in (((4, 8), True), ((16, 8), False)):
        out["cases"].append(case(shape, donate))
    out["ok"] = all(c["parity"] for c in out["cases"])
    return out


_opt_in_announced = False


def persistence_allowed() -> bool:
    """Whether executable bundles may be written/read: only under the
    explicit opt-in ``DL4J_TPU_AOT_BUNDLE=1``; the default (``auto``) is
    OFF on every backend — ROADMAP D6 decides the bundle's future.

    The opt-in is TRUSTED: nothing is validated here and no process is
    started (an accelerator belongs to one process at a time, so the one
    that holds it cannot hand validation to a child). Whoever opts in runs
    the parity harness once per (backend, toolchain) on their own —
    ``python -m deeplearning4j_tpu.nn.aot`` — and every restore still
    passes the manifest's version/backend/signature pins and per-entry
    CRCs. Published once per process as an ``aot_validation`` event with
    ``mode="opt_in_trusted"``."""
    if os.environ.get("DL4J_TPU_AOT_BUNDLE", "auto") != "1":
        return False
    global _opt_in_announced
    if not _opt_in_announced:
        _opt_in_announced = True
        obs.event("aot_validation", ok=True, mode="opt_in_trusted",
                  detail="DL4J_TPU_AOT_BUNDLE=1; parity harness: "
                         "python -m deeplearning4j_tpu.nn.aot")
    return True


# ---------------------------------------------------------------------------
# Bundles
# ---------------------------------------------------------------------------


def model_signature(model) -> str:
    """Identity of the model the bundle's executables were compiled for:
    config JSON + class + dtype. A restored bundle whose signature differs
    would hand avals-mismatched executables to the dispatcher, so the
    manifest check rejects it up front."""
    conf = json.loads(model.conf.to_json())
    # the init seed shapes parameter VALUES, not compiled computations; a
    # resume into a differently-seeded fresh model must accept the bundle
    conf.pop("seed", None)
    h = hashlib.sha256()
    h.update(type(model).__name__.encode())
    h.update(str(model.dtype).encode())
    h.update(json.dumps(conf, sort_keys=True).encode())
    return h.hexdigest()


def bundle_path_for(checkpoint_path) -> str:
    """Sidecar path for the executable bundle shipped with a checkpoint.
    A distinct suffix keeps it out of the checkpoint index's globs (it is
    a cache, not state — losing it costs a recompile, nothing else)."""
    return os.fspath(checkpoint_path) + ".aotbundle"


def toolchain_fingerprint() -> dict:
    """The (jax, jaxlib, backend) triple that decides whether a persisted
    executable bundle can still be trusted."""
    import jax

    try:
        import jaxlib

        jaxlib_version = getattr(jaxlib, "__version__", "unknown")
    except ImportError:  # pragma: no cover - jaxlib always ships with jax
        jaxlib_version = "unknown"
    return {
        "jax_version": jax.__version__,
        "jaxlib_version": jaxlib_version,
        "backend": jax.default_backend(),
    }


def _manifest(model, entries) -> dict:
    return {
        "format_version": BUNDLE_FORMAT_VERSION,
        **toolchain_fingerprint(),
        "model_signature": None if model is None else model_signature(model),
        "entries": entries,
    }


def save_bundle(model, path) -> Optional[dict]:
    """Serialize every AOT-compiled executable on ``model`` into a CRC'd,
    versioned zip bundle (atomic write). Returns ``{"path", "entries",
    "bytes"}`` or None when persistence is gated off / nothing is warm.
    Never raises: a checkpoint must not fail over its executable sidecar."""
    from deeplearning4j_tpu.utils import serialization

    try:
        if not persistence_allowed():
            return None
        reg = model.__dict__.get("_aot_fns") or {}
        entries = []
        blobs: List[bytes] = []
        for site, fn in sorted(reg.items()):
            for key in fn.signatures():
                compiled = fn._compiled.get(key)
                if compiled is None:
                    continue
                try:
                    rec = _serialize(
                        getattr(compiled, "raw_compiled", compiled))
                except Exception:
                    # backend refuses to serialize this executable: skip it,
                    # the rest of the bundle is still worth shipping
                    obs.event("aot_bundle_entry_skipped", site=site)
                    continue
                blob = pickle.dumps({"site": site, "key": key, **rec},
                                    protocol=pickle.HIGHEST_PROTOCOL)
                name = f"exec/{len(blobs):04d}.pkl"
                entries.append({"name": name, "site": site,
                                "crc32": zlib.crc32(blob) & 0xFFFFFFFF,
                                "size": len(blob)})
                blobs.append(blob)
        if not blobs:
            return None
        manifest = _manifest(model, entries)

        def write_entries(zf):
            zf.writestr(_MANIFEST_ENTRY, json.dumps(manifest, indent=2))
            for meta, blob in zip(entries, blobs):
                zf.writestr(meta["name"], blob)

        serialization._atomic_write_zip(path, write_entries)
        total = sum(len(b) for b in blobs)
        obs.counter("dl4j_aot_bundle_saved_total",
                    "executable bundles written").inc()
        obs.event("aot_bundle_saved", path=str(path), entries=len(blobs),
                  bytes=total, backend=manifest["backend"])
        return {"path": str(path), "entries": len(blobs), "bytes": total}
    except Exception as e:
        obs.event("aot_bundle_save_failed", path=str(path), error=repr(e))
        return None


def _reject(path, reason: str, **fields) -> int:
    obs.counter("dl4j_aot_bundle_rejected_total",
                "executable bundles rejected (corrupt, version or backend "
                "mismatch) — the system recompiled instead", ("reason",)
                ).inc(reason=reason)
    obs.event("aot_bundle_rejected", path=str(path), reason=reason, **fields)
    return 0


def restore_bundle(model, path) -> int:
    """Load a bundle's executables into ``model``'s AOT dispatchers.
    Opt-in-gated like writes; manifest version/backend/signature skew,
    per-entry CRC failures and deserialization errors all reject to the
    recompile path (counter + event, no exception). Returns the number of
    executables installed. Sites whose jitted function does not exist yet
    (fresh model, DataParallelStep not built) park in ``model._aot_pending``
    and are adopted by ``wrap`` when the function is created."""
    import jax

    try:
        if not os.path.exists(path):
            return 0
        if not persistence_allowed():
            return _reject(path, "persistence_disabled")
        with zipfile.ZipFile(path) as zf:
            manifest = json.loads(zf.read(_MANIFEST_ENTRY))
            if manifest.get("format_version") != BUNDLE_FORMAT_VERSION:
                return _reject(path, "format_version",
                               found=manifest.get("format_version"))
            import jaxlib

            jaxlib_version = getattr(jaxlib, "__version__", "unknown")
            if (manifest.get("jax_version") != jax.__version__
                    or manifest.get("jaxlib_version") != jaxlib_version):
                return _reject(
                    path, "version_mismatch",
                    bundle_jax=manifest.get("jax_version"),
                    bundle_jaxlib=manifest.get("jaxlib_version"))
            if manifest.get("backend") != jax.default_backend():
                return _reject(path, "backend_mismatch",
                               bundle_backend=manifest.get("backend"),
                               backend=jax.default_backend())
            sig = model_signature(model)
            if manifest.get("model_signature") != sig:
                return _reject(path, "model_signature")
            installed = 0
            pending = model.__dict__.setdefault("_aot_pending", {})
            reg = model.__dict__.setdefault("_aot_fns", {})
            for meta in manifest.get("entries", []):
                blob = zf.read(meta["name"])
                if (zlib.crc32(blob) & 0xFFFFFFFF) != meta.get("crc32"):
                    return _reject(path, "crc_mismatch", entry=meta["name"])
                rec = pickle.loads(blob)
                with obs.compile_span(rec["site"], mode="bundle_restore"):
                    compiled = _deserialize(rec)
                fn = reg.get(rec["site"])
                if fn is not None:
                    fn.install(rec["key"], compiled)
                else:
                    pending.setdefault(rec["site"], []).append(
                        (rec["key"], compiled))
                installed += 1
        # materialize the standard step/output dispatchers now so parked
        # executables attach immediately (cheap: jit wrapping, no trace)
        _attach_standard_fns(model)
        obs.counter("dl4j_aot_bundle_restored_total",
                    "executable bundles restored").inc()
        obs.event("aot_bundle_restored", path=str(path), entries=installed)
        return installed
    except Exception as e:
        return _reject(path, "load_error", error=repr(e))


def _attach_standard_fns(model) -> None:
    pending = model.__dict__.get("_aot_pending") or {}
    prefix = "cg" if _is_graph(model) else "mln"
    if f"{prefix}.step" in pending:
        model._get_step_fn(False)
    if f"{prefix}.step.tbptt" in pending:
        model._get_step_fn(True)
    if f"{prefix}.output" in pending:
        model._get_output_fn()


# ---------------------------------------------------------------------------
# Distributed bundles (elastic multi-host checkpoint layout)
# ---------------------------------------------------------------------------


def distributed_bundle_path(base, rank: int) -> str:
    """Per-host executable-bundle shard path under the elastic checkpoint
    layout: ``<base>_r<rank>.aotbundle``."""
    return f"{os.fspath(base)}_r{int(rank)}.aotbundle"


def _distributed_sidecar(base, rank: int) -> str:
    return f"{os.fspath(base)}_r{int(rank)}.aotmanifest.json"


def save_distributed_bundle(model, base, rank: int) -> Optional[dict]:
    """Write this host's executable-bundle shard plus a CRC'd sidecar
    manifest entry. Bundles hold compiled executables for the REPLICATED
    model program — identical across data-parallel ranks — so any rank's
    shard can warm any other rank (the straggler-serving property the
    distributed restore exploits). Gated and non-raising like
    :func:`save_bundle`; returns its info dict or None."""
    path = distributed_bundle_path(base, rank)
    info = save_bundle(model, path)
    if info is None:
        return None
    try:
        entry = {
            "rank": int(rank),
            "file": os.path.basename(path),
            "crc32": _file_crc32(path),
            "size": os.path.getsize(path),
            "model_signature": model_signature(model),
            **toolchain_fingerprint(),
        }
        tmp = f"{_distributed_sidecar(base, rank)}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(entry, f, indent=1)
        os.replace(tmp, _distributed_sidecar(base, rank))
        info["manifest"] = entry
    except Exception as e:
        obs.event("aot_bundle_save_failed", path=str(path), error=repr(e))
    return info


def _file_crc32(path) -> int:
    crc = 0
    with open(path, "rb") as f:
        while True:
            buf = f.read(1 << 20)
            if not buf:
                break
            crc = zlib.crc32(buf, crc)
    return crc & 0xFFFFFFFF


def distributed_bundle_manifest(base) -> Dict[int, dict]:
    """Merge the per-rank sidecar manifests for ``base`` into
    ``{rank: entry}``; unreadable sidecars are dropped (their bundles will
    fail CRC anyway)."""
    import glob as _glob

    out: Dict[int, dict] = {}
    for p in sorted(_glob.glob(f"{os.fspath(base)}_r*.aotmanifest.json")):
        try:
            with open(p, "r") as f:
                entry = json.load(f)
            out[int(entry["rank"])] = entry
        except (OSError, ValueError, KeyError):
            continue
    return out


def restore_distributed_bundle(model, base, rank: int) -> int:
    """Restore executables from the distributed bundle layout: this rank's
    own shard first, then — because the executables are rank-agnostic — ANY
    other rank's CRC-valid shard (a rejoining straggler whose own shard is
    lost or corrupt warms itself from a survivor's). Returns executables
    installed; 0 on nothing usable (the recompile path, never raises)."""
    manifest = distributed_bundle_manifest(base)
    order = [rank] + sorted(t for t in manifest if t != rank)
    for t in order:
        path = distributed_bundle_path(base, t)
        if not os.path.exists(path):
            continue
        entry = manifest.get(t)
        if entry is not None:
            try:
                if (_file_crc32(path) != entry.get("crc32")
                        or os.path.getsize(path) != entry.get("size")):
                    _reject(path, "crc_mismatch", rank=t)
                    continue
            except OSError:
                continue
        n = restore_bundle(model, path)
        if n > 0:
            if t != rank:
                obs.event("aot_bundle_served_by_peer", rank=rank,
                          served_by=t, path=str(path))
            return n
    return 0


# ---------------------------------------------------------------------------
# Harness entry point: python -m deeplearning4j_tpu.nn.aot
# ---------------------------------------------------------------------------


def _main() -> int:
    result = _selftest()
    sys.stdout.write(json.dumps(result) + "\n")
    return 0 if result.get("ok") else 1


if __name__ == "__main__":
    sys.exit(_main())
