"""Unified observability layer: metrics registry, span tracer, event log.

The four disjoint telemetry islands that grew across PRs 1-4 — bucketing
counters (utils/bucketing.py), comm bytes (parallel/grads.py), guard events
(train/resilience.py) and listener throughput (train/listeners.py) — all
land in ONE process-wide metrics registry, queryable three ways:

- ``obs.snapshot()``      JSON dict (embedded in the resilience checkpoint
                          telemetry field)
- ``/metrics``            Prometheus text exposition on the UI server
- ``obs.recent_spans()``  ring buffer of recent step spans

What JAX spends tracing, lowering, compiling and reading its cache is booked
by site and phase into ``dl4j_compile_seconds_total`` by one listener a
process (obs/compile_phases.py), started where jax is already imported.

Public surface::

    obs.counter/gauge/histogram(name, help, label_names)  # get-or-create
    with obs.span("mln.fit_batch"): ...                   # wall+cpu windows
    obs.event("checkpoint_saved", path=..., crc=...)      # JSONL + counter
    obs.configure_event_log(path)                         # or DL4J_TPU_EVENT_LOG
    obs.snapshot(); obs.prometheus_text(); obs.reset()

Hot-path discipline: recording is host-side dict updates under locks; no
jax import, no device sync, ``block_until_ready`` never called. Set
``DL4J_TPU_OBS=0`` to disable span recording and event emission (counter
shims underneath ``bucketing.telemetry()`` stay live — they ARE the
storage).
"""

from __future__ import annotations

import os
from typing import Optional

from deeplearning4j_tpu.obs import events as _events
from deeplearning4j_tpu.obs import metrics as _metrics
from deeplearning4j_tpu.obs import spans as _spans

__all__ = [
    "compile_span",
    "configure_event_log",
    "cost_report",
    "counter",
    "current_trace",
    "enabled",
    "event",
    "event_log",
    "gauge",
    "histogram",
    "process_context",
    "publish_snapshot",
    "set_process_context",
    "trace_scope",
    "observe_itl",
    "observe_request",
    "observe_shed",
    "observe_ttft",
    "observe_wait",
    "set_decode_occupancy",
    "site_span",
    "prometheus_text",
    "recent_spans",
    "registry",
    "reset",
    "save_spans",
    "snapshot",
    "span",
    "tracer",
]


def enabled() -> bool:
    """Master switch (default on). Read per call so tests can flip it."""
    return os.environ.get("DL4J_TPU_OBS", "1") != "0"


# -- metrics ----------------------------------------------------------------

def registry() -> _metrics.MetricsRegistry:
    return _metrics.registry()


def counter(name: str, help: str = "", label_names=()) -> _metrics.Counter:
    return _metrics.registry().counter(name, help, label_names)


def gauge(name: str, help: str = "", label_names=()) -> _metrics.Gauge:
    return _metrics.registry().gauge(name, help, label_names)


def histogram(name: str, help: str = "", label_names=()) -> _metrics.Histogram:
    return _metrics.registry().histogram(name, help, label_names)


def prometheus_text() -> str:
    # exposition is report-time: resolve pending lazy cost signatures first
    # so the XLA cost gauges reflect every compile seen so far
    try:
        from deeplearning4j_tpu.obs import profile as _profile

        _profile.snapshot()
    except Exception:
        pass
    return _metrics.registry().prometheus_text()


# -- spans ------------------------------------------------------------------

def tracer() -> _spans.SpanTracer:
    return _spans.tracer()


def span(name: str, **attrs):
    """``with obs.span("mln.fit_batch"): ...`` — see obs/spans.py."""
    return _spans.tracer().span(name, **attrs)


def site_span(site: str, **attrs):
    """``with obs.site_span("mln.step"): ...``: the span of one call of a
    jitted site, under the site's name. What JAX traces, lowers and compiles
    inside it is booked to the site (see obs/compile_phases.py)."""
    return _spans.tracer().site_span(site, site, **attrs)


def compile_span(site: str, **attrs):
    """``with obs.compile_span("mln.step", mode="aot"): ...``: the
    ``compile`` span of the AOT and bundle paths, which names the site and
    the mode where no call of the site is open (see obs/spans.py)."""
    return _spans.compile_span(site, **attrs)


def recent_spans(n: Optional[int] = None):
    return _spans.tracer().recent(n)


def save_spans(path: str) -> int:
    """Dump the span ring + timeline anchor as JSON for offline trace
    export (also available via DL4J_TPU_SPAN_DUMP at exit)."""
    return _spans.tracer().dump(path)


# -- profiling / SLOs -------------------------------------------------------

def cost_report(resolve: bool = True) -> dict:
    """XLA static costs per site and key (see obs/profile.py).
    Report-time only — resolution may lower pending lazy signatures."""
    from deeplearning4j_tpu.obs import profile as _profile

    return _profile.cost_report(resolve=resolve)


def observe_request(route: str, latency_s: float, status: str = "ok",
                    error: bool = False):
    """Record one serving/HTTP request against the SLO tracker
    (see obs/slo.py). No-op when DL4J_TPU_OBS=0; never raises."""
    from deeplearning4j_tpu.obs import slo as _slo

    _slo.observe_request(route, latency_s, status=status, error=error)


def observe_shed(route: str, reason: str = "backpressure"):
    """Record one load-shedding decision against the SLO tracker
    (see obs/slo.py). No-op when DL4J_TPU_OBS=0; never raises."""
    from deeplearning4j_tpu.obs import slo as _slo

    _slo.observe_shed(route, reason=reason)


def observe_ttft(route: str, latency_s: float):
    """Record one stream's time-to-first-token (see obs/slo.py).
    No-op when DL4J_TPU_OBS=0; never raises."""
    from deeplearning4j_tpu.obs import slo as _slo

    _slo.observe_ttft(route, latency_s)


def observe_wait(route: str, stage: str, latency_s: float):
    """Record what one generation request waited in ``stage`` (``queue``:
    submit to admit; ``prefill``: admit to first token; see obs/slo.py).
    No-op when DL4J_TPU_OBS=0; never raises."""
    from deeplearning4j_tpu.obs import slo as _slo

    _slo.observe_wait(route, stage, latency_s)


def observe_itl(route: str, latency_s: float):
    """Record one inter-token latency gap (see obs/slo.py).
    No-op when DL4J_TPU_OBS=0; never raises."""
    from deeplearning4j_tpu.obs import slo as _slo

    _slo.observe_itl(route, latency_s)


def set_decode_occupancy(model: str, streams: int):
    """Set the decode-batch occupancy gauge (see obs/slo.py).
    No-op when DL4J_TPU_OBS=0; never raises."""
    from deeplearning4j_tpu.obs import slo as _slo

    _slo.set_decode_occupancy(model, streams)


# -- fleet (cross-process: trace context, federation) -----------------------

def current_trace():
    """The thread's active W3C trace context, or None (see obs/fleet.py)."""
    from deeplearning4j_tpu.obs import fleet as _fleet

    return _fleet.current_trace()


def trace_scope(ctx):
    """``with obs.trace_scope(ctx): ...`` — spans/events recorded inside
    carry ``ctx``'s trace/span ids (see obs/fleet.py)."""
    from deeplearning4j_tpu.obs import fleet as _fleet

    return _fleet.trace_scope(ctx)


def set_process_context(**fields):
    """Tag this process's spans/events with rank/wid/incarnation/slice
    (see obs/fleet.py)."""
    from deeplearning4j_tpu.obs import fleet as _fleet

    _fleet.set_process_context(**fields)


def process_context() -> dict:
    """host/pid plus any identity set via ``set_process_context``."""
    from deeplearning4j_tpu.obs import fleet as _fleet

    return _fleet.process_context()


def publish_snapshot(store, wid: str, extra: Optional[dict] = None) -> str:
    """Publish this process's metrics into the elastic store for the fleet
    collector (see obs/fleet.py). Report-time only — never call from
    traced/per-batch code."""
    from deeplearning4j_tpu.obs import fleet as _fleet

    return _fleet.publish_snapshot(store, wid, extra=extra)


# -- events -----------------------------------------------------------------

def event_log() -> _events.EventLog:
    return _events.event_log()


def event(kind: str, **fields):
    """Emit one structured event (no-op when DL4J_TPU_OBS=0; never raises)."""
    if enabled():
        _events.event_log().emit(kind, **fields)


def configure_event_log(path: Optional[str], max_bytes: int = 4 * 1024 * 1024):
    _events.event_log().configure(path, max_bytes)


# -- aggregate views --------------------------------------------------------

def snapshot() -> dict:
    """JSON-friendly aggregate of everything the registry knows: metric
    families (counters/gauges plain, histograms summarized), per-span
    aggregates, and event counts. Embedded in the resilience checkpoint
    telemetry field (round-trips through JSON)."""
    from deeplearning4j_tpu.obs import profile as _profile
    from deeplearning4j_tpu.utils import bucketing

    return {
        "metrics": _metrics.registry().snapshot(),
        "spans": _spans.tracer().summary(),
        "events": _events.event_log().counts(),
        "bucketing": bucketing.telemetry().snapshot(),
        "profile": _profile.snapshot(),
    }


def reset():
    """Zero every metric series, drop recent spans and the cost ledger,
    keep configuration (event-log path, family registrations). Test
    isolation."""
    from deeplearning4j_tpu.obs import profile as _profile
    from deeplearning4j_tpu.obs import slo as _slo

    _metrics.registry().reset()
    _spans.tracer().clear()
    _profile.reset()
    _slo._reset_tracker()
