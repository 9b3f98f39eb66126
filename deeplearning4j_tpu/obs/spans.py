"""Span tracer: host wall-time + dispatch-time windows around step-level work.

``span("mln.fit_batch")`` wraps one unit of work. On exit it records

- ``wall_s``  — host wall-clock window (``time.perf_counter`` delta). With
  async dispatch this includes any time the host BLOCKED on the device
  (donated-buffer back-pressure, explicit syncs in callers) but never forces
  a sync itself — ``block_until_ready`` is deliberately absent here.
- ``cpu_s``   — the dispatch-time window: CPU time this thread spent inside
  the span (``time.thread_time`` delta). For a healthy async pipeline
  ``cpu_s`` ≈ tracing/dispatch cost and ``wall_s`` ≫ ``cpu_s`` means the
  host was waiting (device-bound or back-pressured) — the two windows
  together locate the bottleneck without device instrumentation.

Nesting is tracked per thread: a span opened while another is active records
the outer span's name as ``parent`` and its own ``depth``, and takes over the
outer span's ``step`` attribute where it has none of its own, so the spans
of one training iteration share one identifier. Finished spans go
to a bounded ring buffer (most recent last) and into the
``dl4j_span_seconds`` histogram family in the metrics registry.

A **site span** (``site_span``) also names the jitted site whose programs
are traced, lowered and compiled inside it: every call of a ``StepProgram``
opens one under the site's own name, ``compile_span`` one named ``compile``.
``open_span`` hands the calling thread's innermost open span to whoever has
to book work to it (``obs/compile_phases.py`` books JAX's compile events to
the innermost site span, whose record then carries ``compile_s``), and
``record`` puts a finished record into the ring for work whose extent is
known only at its end.

A span is also a ``jax.profiler.TraceAnnotation`` of the same name and
extent, carrying ``span_depth`` and the span's scalar attributes: whenever a
profiler trace is being taken (``ProfilerListener``, the benchmark's traced
run) every span lies in the trace's host plane, on the same clock as the
device's operations, and an idle gap of the device can be read off the span
that covers it (docs/OBSERVABILITY.md, "Reading a profile"). With no trace
active the annotation is TraceMe's inactive path (under a microsecond).

Ring records carry everything ``obs/trace_export.py`` needs to render a
Chrome/Perfetto timeline: ``t0_s`` (span start on the process-local
``perf_counter`` timeline), ``tid``/``thread`` (OS thread identity for
per-thread lanes), and the tracer's ``anchor()`` maps that timeline onto
wall-clock so event-log instants (whose ``ts`` is wall-clock by design)
land on the same axis.

Ring capacity defaults to 512 finished spans and is tunable via
``DL4J_TPU_SPAN_RING`` (read at tracer construction, i.e. first import of
the obs layer). Overflow is NOT silent: every record evicted to make room
increments ``dl4j_spans_dropped_total`` — mirroring the
``dl4j_events_dropped_total`` discipline — so a long fit that outruns the
ring is visible in /metrics instead of producing quietly truncated traces.
``DL4J_TPU_SPAN_DUMP=<path>`` dumps the ring (plus the anchor) as JSON at
interpreter exit for offline trace export.
"""

from __future__ import annotations

import atexit
import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from deeplearning4j_tpu.obs import fleet, metrics

__all__ = ["SpanTracer", "compile_span", "tracer"]

_RING_DEFAULT = 512  # finished spans retained unless DL4J_TPU_SPAN_RING


def _ring_capacity() -> int:
    raw = os.environ.get("DL4J_TPU_SPAN_RING", "")
    try:
        n = int(raw)
    except ValueError:
        return _RING_DEFAULT
    return n if n > 0 else _RING_DEFAULT


SPAN_DEPTH_STAT = "span_depth"  # the stat that marks a program span in a trace

_TraceAnnotation = None


def _annotation(name: str, depth: int, attrs: Dict[str, object]):
    """The span as the profiler sees it. jax is imported on the first span,
    not with the obs layer."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation

        from deeplearning4j_tpu.obs import compile_phases

        _TraceAnnotation = TraceAnnotation
        compile_phases.install()    # jax is imported now: listen to it
    scalars = {k: v for k, v in attrs.items()
               if isinstance(v, (bool, int, float, str))}
    scalars[SPAN_DEPTH_STAT] = depth
    return _TraceAnnotation(name, **scalars)


class _ActiveSpan:
    """An open span. ``site`` is the jitted site a site span names (None on
    every other span); ``compile_s`` is what ``obs/compile_phases.py`` has
    booked to it so far."""

    __slots__ = ("name", "attrs", "site", "compile_s", "t0", "c0",
                 "annotation")

    def __init__(self, name: str, attrs: Dict[str, object], depth: int,
                 site: Optional[str] = None):
        self.name = name
        self.attrs = attrs
        self.site = site
        self.compile_s = 0.0
        self.annotation = _annotation(name, depth, attrs)
        self.annotation.__enter__()
        self.t0 = time.perf_counter()
        self.c0 = time.thread_time()


class _SpanContext:
    """Context manager handed out by ``SpanTracer.span``. Re-entrant-safe in
    the sense that each ``with`` creates a fresh context."""

    __slots__ = ("_tracer", "_name", "_attrs", "_site", "_active")

    def __init__(self, tracer: "SpanTracer", name: str,
                 attrs: Dict[str, object], site: Optional[str] = None):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs
        self._site = site
        self._active: Optional[_ActiveSpan] = None

    def __enter__(self):
        self._active = self._tracer._push(self._name, self._attrs, self._site)
        return self

    def __exit__(self, exc_type, exc, tb):
        self._tracer._pop(self._active, error=exc_type is not None)
        return False


class _NullContext:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL = _NullContext()


def _with_step(attrs: Dict[str, object], stack: List[_ActiveSpan]):
    """The spans of one iteration share its step number."""
    if stack and "step" in stack[-1].attrs and "step" not in attrs:
        return dict(attrs, step=stack[-1].attrs["step"])
    return attrs


class SpanTracer:
    def __init__(self, reg: Optional[metrics.MetricsRegistry] = None,
                 ring_size: Optional[int] = None):
        self._reg = reg or metrics.registry()
        self._hist = self._reg.histogram(
            "dl4j_span_seconds",
            "host wall-time of instrumented spans (see dl4j_span_cpu_seconds "
            "for the dispatch-time window)", ("span",))
        self._cpu = self._reg.histogram(
            "dl4j_span_cpu_seconds",
            "thread CPU time inside instrumented spans (dispatch cost; "
            "wall >> cpu means the host was waiting)", ("span",))
        self._dropped = self._reg.counter(
            "dl4j_spans_dropped_total",
            "finished spans evicted from the bounded span ring "
            "(raise DL4J_TPU_SPAN_RING if this grows during a window "
            "you want to trace)")
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=ring_size or _ring_capacity())
        self._tls = threading.local()
        # One (wall-clock, perf_counter) pair sampled back to back: maps the
        # perf_counter timeline every span uses onto wall-clock so trace
        # export can align event-log instants (wall-clock ts) with spans.
        self._anchor = {"wall_s": time.time(), "perf_s": time.perf_counter()}

    # -- recording ---------------------------------------------------------

    def span(self, name: str, **attrs) -> object:
        """Context manager timing one unit of work. With observability
        disabled (DL4J_TPU_OBS=0) returns a shared no-op context."""
        return self._context(name, attrs, None)

    def site_span(self, name: str, site: str, /, **attrs) -> object:
        """``span(name)`` that also names the jitted ``site`` whose programs
        are traced, lowered and compiled inside it."""
        return self._context(name, attrs, site)

    def _context(self, name: str, attrs: Dict[str, object],
                 site: Optional[str]) -> object:
        from deeplearning4j_tpu import obs

        if not obs.enabled():
            return _NULL
        return _SpanContext(self, name, attrs, site)

    def open_span(self, with_site: bool = False) -> Optional[_ActiveSpan]:
        """The innermost span open on the calling thread (``name``,
        ``attrs``, ``site``, ``t0``, ``compile_s``), or None; with
        ``with_site`` the innermost site span."""
        for sp in reversed(self._stack()):
            if sp.site is not None or not with_site:
                return sp
        return None

    def _stack(self) -> List[_ActiveSpan]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _push(self, name: str, attrs: Dict[str, object],
              site: Optional[str] = None) -> _ActiveSpan:
        stack = self._stack()
        sp = _ActiveSpan(name, _with_step(attrs, stack), len(stack), site)
        stack.append(sp)
        return sp

    def _pop(self, sp: Optional[_ActiveSpan], error: bool = False):
        if sp is None:
            return
        wall = time.perf_counter() - sp.t0
        cpu = time.thread_time() - sp.c0
        sp.annotation.__exit__(None, None, None)
        stack = self._stack()
        # tolerate exotic unwinds: pop through to OUR frame
        while stack and stack[-1] is not sp:
            stack.pop()
        if stack:
            stack.pop()
        rec = self._finished(sp.name, sp.t0, wall, cpu, sp.attrs, stack)
        if error:
            rec["error"] = True
        if sp.compile_s:
            rec["compile_s"] = sp.compile_s
        self._keep(rec)
        self._hist.observe(wall, span=sp.name)
        self._cpu.observe(cpu, span=sp.name)

    def record(self, name: str, t0_s: float, wall_s: float, /,
               **attrs) -> None:
        """Put a finished record into the ring for work whose extent is
        known only at its end: ``t0_s`` on the ring's ``perf_counter``
        timeline, ``parent``, ``depth`` and ``step`` from the spans open on
        the calling thread, as a child opened there would have them. The
        ring alone: no histogram series, no profiler annotation (none can be
        back-dated)."""
        stack = self._stack()
        self._keep(self._finished(name, t0_s, wall_s, 0.0,
                                  _with_step(attrs, stack), stack))

    def _finished(self, name: str, t0: float, wall: float, cpu: float,
                  attrs: Dict[str, object], stack: List[_ActiveSpan]) -> dict:
        th = threading.current_thread()
        rec = {
            "span": name,
            "t0_s": t0,
            "wall_s": wall,
            "cpu_s": cpu,
            "parent": stack[-1].name if stack else None,
            "depth": len(stack),
            "tid": th.ident,
            "thread": th.name,
        }
        if attrs:
            rec["attrs"] = attrs
        # rank/incarnation + active trace ids (obs/fleet.py) — cheap dict
        # writes; records keep the rank current when they were recorded,
        # which matters across elastic reforms
        fleet.stamp_span(rec)
        return rec

    def _keep(self, rec: dict) -> None:
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self._dropped.inc()
            self._ring.append(rec)

    # -- views -------------------------------------------------------------

    def recent(self, n: Optional[int] = None) -> List[dict]:
        """Most recent finished spans, oldest first."""
        with self._lock:
            out = list(self._ring)
        return out if n is None else out[-n:]

    def anchor(self) -> Dict[str, float]:
        """The (wall_s, perf_s) pair mapping the span timeline to wall-clock:
        ``wall = anchor.wall_s + (t0_s - anchor.perf_s)``."""
        return dict(self._anchor)

    def capacity(self) -> int:
        return self._ring.maxlen or 0

    def summary(self) -> Dict[str, dict]:
        """Per-span-name {count, wall_sum_s, wall_p50_s, wall_max_s, cpu_sum_s}
        from the registry histograms (JSON-friendly, for ``obs.snapshot()``)."""
        out: Dict[str, dict] = {}
        for key, _ in self._hist.series():
            name = key[0]
            s = self._hist.summary(span=name)
            c = self._cpu.summary(span=name)
            out[name] = {
                "count": s["count"],
                "wall_sum_s": s["sum"],
                "wall_p50_s": s["p50"],
                "wall_max_s": s["max"],
                "cpu_sum_s": c["sum"] if c else 0.0,
            }
        return out

    def dump(self, path: str) -> int:
        """Write the ring + anchor as JSON for offline trace export
        (``python -m deeplearning4j_tpu.obs.trace_export --spans <path>``).
        Returns the number of spans written."""
        spans = self.recent()
        doc = {"anchor": self.anchor(), "spans": spans,
               "process": fleet.process_context()}
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return len(spans)

    def clear(self):
        with self._lock:
            self._ring.clear()


_TRACER = SpanTracer()


def tracer() -> SpanTracer:
    return _TRACER


def _dump_at_exit():
    path = os.environ.get("DL4J_TPU_SPAN_DUMP")
    if not path:
        return
    try:
        _TRACER.dump(path)
    except OSError:
        pass  # exit-time best effort; never mask the real exit status


atexit.register(_dump_at_exit)


def compile_span(site: str, **attrs):
    """The ``compile`` span kind: the site span of compilation work that no
    call of the site surrounds: AOT warm-up and bundle restore
    (``nn/aot.py``). It names the jitted site (the ``site`` attribute, and
    the site ``obs/compile_phases.py`` books JAX's compile events inside it
    to) and the ``mode``. Its wall time is that of those two paths alone: a
    site's first lazy call compiles inside the site's own span, and the
    process's compile cost on every path is the sum of
    ``dl4j_compile_seconds_total``."""
    return tracer().site_span("compile", site, site=site, **attrs)
