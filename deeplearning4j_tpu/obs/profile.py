"""Static XLA cost models + roofline utilization (MFU / memory bandwidth).

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
- **Roofline division** — achieved per-dispatch wall time comes from the
  ``dl4j_span_seconds`` histograms (p50 of the span mapped to each site);
  dividing harvested flops / bytes-accessed by it and by the per-backend
  peak table yields ``dl4j_mfu{site}`` and ``dl4j_membw_util{site}``. The
  peak table absorbs the ad-hoc math previously duplicated in ``bench.py``
  and ``tools/exp_transformer_mfu.py``; ``DL4J_TPU_PEAK_FLOPS`` /
  ``DL4J_TPU_HBM_GBPS`` override it so CPU runs (tests, smoke) can exercise
  the full pipeline.

Hot-path discipline: :func:`note_trace` / :func:`wants_exemplar` are a set
add / set lookup with no jax import; everything that touches jax
(:func:`harvest_compiled`, resolution, :func:`utilization`) runs at
compile time or report time — never per batch. The ``graftlint`` rule
``cost-analysis-off-hot-path`` enforces the same boundary statically.
"""

from __future__ import annotations

import os
import threading
import weakref
from typing import Dict, List, Optional, Tuple

from deeplearning4j_tpu.obs import metrics

__all__ = [
    "cost_report",
    "harvest_compiled",
    "note_exemplar",
    "note_trace",
    "peak_flops",
    "reset",
    "roofline",
    "snapshot",
    "utilization",
    "wants_exemplar",
]

# Per-chip peaks by device_kind substring: (bf16 FLOP/s, f32 FLOP/s,
# HBM bytes/s). FLOP columns match the table bench.py carried since PR 3
# (public TPU spec sheets); HBM column from the same sheets. First
# substring match wins; CPU / unknown kinds return None so utilization is
# omitted rather than fabricated (unless the env overrides below are set).
ROOFLINES: Tuple[Tuple[str, float, float, float], ...] = (
    ("v6", 918e12, 459e12, 1640e9),
    ("v5p", 459e12, 459e12, 2765e9),
    ("v5 lite", 197e12, 98e12, 819e9),
    ("v5e", 197e12, 98e12, 819e9),
    ("v4", 275e12, 137e12, 1228e9),
    ("v3", 123e12, 61e12, 900e9),
    ("v2", 45e12, 22e12, 700e9),
)

# Which span's per-dispatch wall time prices each harvested site. fit spans
# wrap exactly one step dispatch; output spans wrap one forward dispatch.
_SITE_SPANS = {
    "mln.step": "mln.fit_batch",
    "mln.step.tbptt": "mln.fit_batch",
    "mln.chain": "mln.fit_batch",  # one fit_batch span per chain dispatch
    "cg.step": "cg.fit_batch",
    "cg.step.tbptt": "cg.fit_batch",
    "dp.step": "dp.step",
    "mln.output": "mln.output",
    "cg.output": "cg.output",
}

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
# Roofline table
# ---------------------------------------------------------------------------

def _device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def roofline(device_kind: Optional[str] = None) -> dict:
    """Peak numbers for the backend: ``{device_kind, peak_bf16_flops,
    peak_f32_flops, hbm_bytes_per_s, source}``. Peaks are None for CPU /
    unknown kinds unless ``DL4J_TPU_PEAK_FLOPS`` (FLOP/s) /
    ``DL4J_TPU_HBM_GBPS`` (GB/s) override them."""
    kind = device_kind if device_kind is not None else _device_kind()
    bf16 = f32 = hbm = None
    source = "unknown"
    low = kind.lower()
    for sub, peak_bf16, peak_f32, peak_hbm in ROOFLINES:
        if sub in low:
            bf16, f32, hbm = peak_bf16, peak_f32, peak_hbm
            source = "table"
            break
    env_flops = os.environ.get("DL4J_TPU_PEAK_FLOPS")
    if env_flops:
        try:
            bf16 = f32 = float(env_flops)
            source = "env"
        except ValueError:
            pass
    env_hbm = os.environ.get("DL4J_TPU_HBM_GBPS")
    if env_hbm:
        try:
            hbm = float(env_hbm) * 1e9
            source = "env"
        except ValueError:
            pass
    return {
        "device_kind": kind,
        "peak_bf16_flops": bf16,
        "peak_f32_flops": f32,
        "hbm_bytes_per_s": hbm,
        "source": source,
    }


def peak_flops(dtype: str = "bfloat16",
               device_kind: Optional[str] = None) -> Optional[float]:
    """Peak FLOP/s for the backend at the given matmul precision; None for
    CPU / unknown (callers omit MFU rather than fabricate it)."""
    r = roofline(device_kind)
    return r["peak_bf16_flops"] if dtype == "bfloat16" else r["peak_f32_flops"]


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

def utilization(span_summary: Optional[Dict[str, dict]] = None) -> Dict[str, dict]:
    """MFU / memory-bandwidth utilization per harvested site.

    ``achieved = flops / p50_wall_per_dispatch``; MFU divides by the bf16
    roofline (jax's default TPU matmul precision multiplies f32 inputs in
    bf16 — same convention the LSTM bench used), bandwidth by HBM bytes/s.
    Uses the largest-flops entry per site (the biggest bucket dominates a
    saturated ladder). Refreshes ``dl4j_mfu`` / ``dl4j_membw_util`` gauges.
    Empty when the backend has no roofline and no env override."""
    r = roofline()
    peak = r["peak_bf16_flops"]
    hbm = r["hbm_bytes_per_s"]
    if not peak and not hbm:
        return {}
    if span_summary is None:
        from deeplearning4j_tpu.obs import spans

        span_summary = spans.tracer().summary()
    with _lock:
        by_site: Dict[str, dict] = {}
        for (site, key), entry in _costs.items():
            if entry.get("flops", 0) > by_site.get(site, {}).get("flops", -1):
                by_site[site] = {**entry, "key": key}
    reg = metrics.registry()
    g_mfu = reg.gauge("dl4j_mfu",
                      "model FLOPs utilization: achieved flops/s at the "
                      "site's step span over the bf16 roofline", ("site",))
    g_bw = reg.gauge("dl4j_membw_util",
                     "achieved bytes-accessed/s over peak HBM bandwidth",
                     ("site",))
    out: Dict[str, dict] = {}
    for site, entry in by_site.items():
        span = _SITE_SPANS.get(site, site)
        s = span_summary.get(span)
        if not s or not s.get("count") or not s.get("wall_p50_s"):
            continue
        wall = s["wall_p50_s"]
        u = {"span": span, "key": entry["key"], "wall_p50_s": wall,
             "source": entry["source"]}
        if peak and entry.get("flops"):
            u["achieved_flops_per_s"] = entry["flops"] / wall
            u["mfu"] = entry["flops"] / wall / peak
            g_mfu.set(round(u["mfu"], 6), site=site)
        if hbm and entry.get("bytes_accessed"):
            u["achieved_bytes_per_s"] = entry["bytes_accessed"] / wall
            u["membw_util"] = entry["bytes_accessed"] / wall / hbm
            g_bw.set(round(u["membw_util"], 6), site=site)
        if "mfu" in u or "membw_util" in u:
            out[site] = u
    return out


def cost_report(resolve: bool = True) -> dict:
    """The profiling ledger: roofline, per-(site, key) static costs, and
    derived utilization. ``resolve=True`` prices any pending lazy-compile
    exemplars first (report time, never the hot path)."""
    if resolve:
        _resolve_pending()
    with _lock:
        sites: Dict[str, dict] = {}
        for (site, key), entry in sorted(_costs.items()):
            sites.setdefault(site, {})[key] = dict(entry)
    return {
        "roofline": roofline(),
        "sites": sites,
        "utilization": utilization(),
    }


def snapshot(resolve: bool = True) -> dict:
    """JSON-friendly view for ``obs.snapshot()`` (bench results, checkpoint
    telemetry). Same shape as :func:`cost_report`."""
    try:
        return cost_report(resolve=resolve)
    except Exception:  # never let profiling break a checkpoint save
        return {"roofline": {"device_kind": "unknown", "source": "error"},
                "sites": {}, "utilization": {}}


def reset():
    """Drop the ledger and pending exemplars (tests / bench isolation)."""
    with _lock:
        _costs.clear()
        _exemplars.clear()
        _want_exemplar.clear()
