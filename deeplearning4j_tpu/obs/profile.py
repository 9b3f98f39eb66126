"""Static XLA cost models of the executables the process compiles.

This module turns the executables the process already produces into a cost
ledger nobody has to pay twice for:

- **AOT harvest** — every ``Compiled`` the ``nn/aot.py`` dispatcher holds is
  passed to :func:`harvest_compiled` right after compilation:
  ``cost_analysis()`` (flops / bytes accessed / transcendentals) plus
  ``memory_analysis()`` (argument/output/temp/code bytes, summed into a
  peak-HBM estimate) land in registry gauges and the in-process ledger.
- **Lazy harvest** — sites that compile through the ordinary ``jit`` path
  can't hand us a ``Compiled``, but ``bucketing.record_trace`` (which runs
  exactly once per XLA compile, inside the traced body) calls
  :func:`note_trace`, flagging the site. After the dispatch returns, the
  ``AotFunction`` wrapper checks :func:`wants_exemplar` (one set lookup —
  the only hot-path cost of this module) and captures the call's *abstract*
  signature via :func:`note_exemplar`: ``shaped_abstractify`` avals plus a
  weakref to the dispatcher, never live buffers. Resolution is deferred to
  :func:`cost_report`: ``jit.lower(*avals)`` with the exact avals hits
  jax's jaxpr cache (no re-trace, no compile-counter pollution) and
  ``Lowered.cost_analysis()`` prices the HLO
  without compiling. Lazy entries have no ``memory_analysis`` (that needs a
  compile), so ``peak_hbm_bytes`` is reported only for AOT-warmed sites.

These are the compiler's static counts, priced at compile time: no time is
measured here and no utilization derived (``benchmark/`` measures the chip).

Hot-path discipline: :func:`note_trace` / :func:`wants_exemplar` are a set
add / set lookup with no jax import; everything that touches jax
(:func:`harvest_compiled`, resolution) runs at compile time or report
time — never per batch. The ``graftlint`` rule
``cost-analysis-off-hot-path`` enforces the same boundary statically.
"""

from __future__ import annotations

import threading
import weakref
from typing import Dict, Optional, Tuple

from deeplearning4j_tpu.obs import metrics

__all__ = [
    "cost_report",
    "harvest_compiled",
    "note_exemplar",
    "note_trace",
    "reset",
    "snapshot",
    "wants_exemplar",
]

_lock = threading.Lock()
# (site, key) -> cost entry dict (see harvest_compiled / _resolve_pending)
_costs: Dict[Tuple[str, str], dict] = {}
# sites flagged by note_trace, cleared when an exemplar is captured
_want_exemplar: set = set()
# site -> {"ref": weakref-or-None, "fn": strong-ref-or-None, "abstract": tree}
# keyed by (site, aval-key) so re-compiles at new shapes get their own entry
_exemplars: Dict[Tuple[str, object], dict] = {}


def _gauges():
    reg = metrics.registry()
    return (
        reg.gauge("dl4j_xla_flops",
                  "XLA cost-model FLOPs of one dispatch of the compiled "
                  "executable", ("site", "key")),
        reg.gauge("dl4j_xla_bytes_accessed",
                  "XLA cost-model bytes accessed by one dispatch",
                  ("site", "key")),
        reg.gauge("dl4j_xla_peak_hbm_bytes",
                  "compiled-executable memory footprint: argument + output "
                  "+ temp + generated code bytes (AOT-warmed sites only)",
                  ("site", "key")),
    )


# ---------------------------------------------------------------------------
# Harvest: AOT path
# ---------------------------------------------------------------------------

def harvest_compiled(site: str, compiled, key: str, dtype: str = "") -> Optional[dict]:
    """Record the cost/memory analysis of a ``Compiled`` executable under
    (site, key). Called from ``nn/aot.py`` at warm/restore time — never on
    the dispatch path. Never raises (backends without cost analysis simply
    contribute no entry)."""
    try:
        ca = compiled.cost_analysis()  # graftlint: disable=cost-analysis-off-hot-path
        ca = ca[0] if isinstance(ca, list) else (ca or {})
    except Exception:
        ca = {}
    entry = {
        "source": "aot",
        "flops": float(ca.get("flops", 0.0) or 0.0),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0) or 0.0),
        "transcendentals": float(ca.get("transcendentals", 0.0) or 0.0),
    }
    if dtype:
        entry["dtype"] = dtype
    try:
        ma = compiled.memory_analysis()  # graftlint: disable=cost-analysis-off-hot-path
    except Exception:
        ma = None
    if ma is not None:
        arg = float(getattr(ma, "argument_size_in_bytes", 0) or 0)
        out = float(getattr(ma, "output_size_in_bytes", 0) or 0)
        tmp = float(getattr(ma, "temp_size_in_bytes", 0) or 0)
        code = float(getattr(ma, "generated_code_size_in_bytes", 0) or 0)
        alias = float(getattr(ma, "alias_size_in_bytes", 0) or 0)
        entry.update({
            "argument_bytes": arg,
            "output_bytes": out,
            "temp_bytes": tmp,
            "generated_code_bytes": code,
            "alias_bytes": alias,
            # what the executable needs resident at dispatch (aliased/donated
            # bytes are double-counted in argument+output, so subtract)
            "peak_hbm_bytes": max(0.0, arg + out + tmp + code - alias),
        })
    if not entry["flops"] and not entry["bytes_accessed"] and ma is None:
        return None  # backend exposes nothing — don't record an empty row
    with _lock:
        _costs[(site, str(key))] = entry
    _set_cost_gauges(site, str(key), entry)
    return entry


def _set_cost_gauges(site: str, key: str, entry: dict):
    g_flops, g_bytes, g_hbm = _gauges()
    if entry.get("flops"):
        g_flops.set(entry["flops"], site=site, key=key)
    if entry.get("bytes_accessed"):
        g_bytes.set(entry["bytes_accessed"], site=site, key=key)
    if entry.get("peak_hbm_bytes"):
        g_hbm.set(entry["peak_hbm_bytes"], site=site, key=key)


# ---------------------------------------------------------------------------
# Harvest: lazy-jit path
# ---------------------------------------------------------------------------

def note_trace(site: str, shape=None):
    """Flag ``site`` as having just compiled through the lazy jit path.
    Called from ``bucketing.record_trace`` inside the traced body — must
    stay jax-free and O(1). ``shape`` is accepted for symmetry but unused
    (the exemplar carries exact avals)."""
    with _lock:
        _want_exemplar.add(site)


def wants_exemplar(site: str) -> bool:
    """One set lookup; the only per-dispatch cost of the lazy harvest."""
    return site in _want_exemplar


def note_exemplar(site: str, fn, args, kwargs):
    """Capture the abstract signature of the dispatch that just compiled.

    ``fn`` is the ``AotFunction`` wrapper (``fn._jit`` is the jitted
    callable). Stores ``shaped_abstractify`` avals — shape/dtype/weak_type
    only, never live buffers — plus a weakref to ``fn`` so a collected
    model doesn't stay pinned. Never raises."""
    try:
        import jax

        abstract = jax.tree_util.tree_map(
            jax.api_util.shaped_abstractify, (tuple(args), dict(kwargs)))
        leaves, treedef = jax.tree_util.tree_flatten(abstract)
        akey = (treedef, tuple((a.shape, str(a.dtype), bool(getattr(a, "weak_type", False)))
                               for a in leaves))
        try:
            ref, strong = weakref.ref(fn), None
        except TypeError:
            ref, strong = None, fn
        with _lock:
            _exemplars[(site, akey)] = {
                "ref": ref, "fn": strong, "abstract": abstract}
            _want_exemplar.discard(site)
    except Exception:
        with _lock:
            _want_exemplar.discard(site)  # a capture that can't work: no retry


def _resolve_pending():
    """Price every captured exemplar via ``jit.lower(*avals)`` +
    ``Lowered.cost_analysis()``. The exact avals hit jax's jaxpr cache, so
    the traced body does NOT re-execute (no compile-counter pollution) and
    nothing is compiled. Resolved exemplars are dropped; failures are
    recorded once as error entries so they aren't retried every report."""
    with _lock:
        pending = dict(_exemplars)
        _exemplars.clear()
    for (site, akey), rec in pending.items():
        fn = rec["fn"] if rec["fn"] is not None else rec["ref"]()
        if fn is None:
            continue  # model was collected; nothing to price
        key = f"sig{abs(hash(akey)) % 10**8:08d}"
        try:
            args2, kwargs2 = rec["abstract"]
            # AotFunction wrappers carry the jitted callable on ._jit;
            # bare jax.jit objects (e.g. the chained fit executable) lower
            # directly
            lowered = getattr(fn, "_jit", fn).lower(*args2, **kwargs2)
            ca = lowered.cost_analysis()  # graftlint: disable=cost-analysis-off-hot-path
            ca = ca[0] if isinstance(ca, list) else (ca or {})
            entry = {
                "source": "lazy",
                "flops": float(ca.get("flops", 0.0) or 0.0),
                "bytes_accessed": float(ca.get("bytes accessed", 0.0) or 0.0),
                "transcendentals": float(ca.get("transcendentals", 0.0) or 0.0),
            }
        except Exception as e:  # pragma: no cover - backend-specific
            entry = {"source": "lazy", "error": type(e).__name__}
        with _lock:
            # an AOT harvest for the same site/shape is strictly richer
            # (adds memory_analysis) — don't clobber it with a lazy probe
            existing = [k for k in _costs if k[0] == site
                        and _costs[k]["source"] == "aot"]
            if not existing or "error" not in entry:
                _costs.setdefault((site, key), entry)
        if "error" not in entry:
            _set_cost_gauges(site, key, entry)


# ---------------------------------------------------------------------------
# Reports
# ---------------------------------------------------------------------------

def cost_report(resolve: bool = True) -> dict:
    """The profiling ledger: per-(site, key) static costs. ``resolve=True``
    prices any pending lazy-compile exemplars first (report time, never the
    hot path)."""
    if resolve:
        _resolve_pending()
    with _lock:
        sites: Dict[str, dict] = {}
        for (site, key), entry in sorted(_costs.items()):
            sites.setdefault(site, {})[key] = dict(entry)
    return {"sites": sites}


def snapshot(resolve: bool = True) -> dict:
    """JSON-friendly view for ``obs.snapshot()`` (checkpoint telemetry).
    Same shape as :func:`cost_report`."""
    try:
        return cost_report(resolve=resolve)
    except Exception:  # never let profiling break a checkpoint save
        return {"sites": {}}


def reset():
    """Drop the ledger and pending exemplars (test isolation)."""
    with _lock:
        _costs.clear()
        _exemplars.clear()
        _want_exemplar.clear()
