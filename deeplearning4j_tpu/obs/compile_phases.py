"""What a process pays before its first step, booked where it is paid.

JAX reports, through ``jax.monitoring``, how long it traced a jitted
function's Python body, lowered the jaxpr to StableHLO, compiled the module
(or loaded it from the persistent cache) and read that cache. One listener
a process books every such second to the **site** and the **phase** it
belongs to: ``dl4j_compile_seconds_total{site,phase}`` and
``dl4j_compile_events_total{site,phase}``, ``phase`` one of ``trace``,
``lower``, ``backend``, ``cache_read``. (A hit of the persistent cache is a
``cache_read`` event, a miss a ``backend`` event with none inside it: JAX's
plain ``cache_hits`` / ``cache_misses`` events are not listened to.)

**Site.** The innermost site span open on the event's thread
(``obs/spans.py``): a call of a ``StepProgram`` (``mln.step``, ``cg.step``,
``decode.step``, ``mesh.step``, ...) or a ``compile`` span of the AOT and
bundle paths; ``none`` where there is neither (a ``jax.jit`` of the user's,
an eager operation, the benchmark's weight and reference programs). The
process's compile cost on every path is the sum of the seconds family, from
``install()`` on (what ran before the first ``StepProgram`` or span is not
in it); the step's own is the series of its site.

**Phases are self time.** An event arrives at its end with its duration. On
jax 0.9.0, as read in its source and found on the chip:

- ``jaxpr_trace_duration`` of a jitted function called inside a body that is
  being traced lies inside the outer function's (``_cond_recomputed``'s
  jitted conditionals, every ``jnp`` function that is itself a ``jit``);
- an operation that runs eagerly while a body is traced (a constant built
  from concrete values) traces, lowers and compiles a small program of its
  own, all three inside the outer ``trace``;
- ``cache_retrieval_time_sec`` lies inside the ``backend_compile_duration``
  that JAX reports around the cache lookup: **``cache_read`` is taken out of
  ``backend``**, so warm ``backend`` is what is left around the read (the
  cache key of the module, the bookkeeping), ``cache_read`` the read, the
  decompression and the executable's load, and cold ``backend`` is XLA's and
  Mosaic's compile with the write of the cache entry;
- ``jaxpr_to_mlir_module_duration`` holds only the traces of the ``jnp``
  functions its lowering rules call (some hundreds a step, of microseconds).

The listener keeps, a thread, the intervals it has booked and books an
arriving event less what it covers of them, so ``trace + lower + backend +
cache_read`` of a site never exceed the wall time of the spans they fell in.

**Which step recompiled.** Every event also leaves a finished record in the
span ring (``compile.trace``, ``compile.lower``, ``compile.backend``,
``compile.cache_read``; attributes ``site``, ``self_s`` and JAX's
``fun_name`` as ``fun``), its start back-dated by its duration, with the
``parent`` and ``step`` of the spans open around it, and the site span's own
record gains ``compile_s``: ``obs.recent_spans()``, ``DL4J_TPU_SPAN_DUMP`` and
``obs/trace_export.py`` show a recompile on the timeline with its step
number. A ``trace`` event shorter than 10 ms leaves no record (its seconds
are counted all the same): a step's trace holds hundreds of jitted ``jnp``
functions of a millisecond each, which would push the steps before a
recompile out of the ring. In a profiler trace JAX's own host events mark
the same intervals.

Nothing here runs on a steady step: the listener fires only when JAX
traces, lowers, compiles or reads its cache. ``DL4J_TPU_OBS=0`` registers
nothing and books nothing.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Optional, Tuple

from deeplearning4j_tpu.obs import metrics, spans

__all__ = ["CompilePhases", "NO_SITE", "PHASES", "install"]

_log = logging.getLogger(__name__)

PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read",
}
NO_SITE = "none"
_KEEP = 65536   # booked intervals kept a thread; the oldest go beyond it
_RING_MIN_TRACE_S = 0.010   # a shorter trace event leaves no ring record


class CompilePhases:
    """The listener and what it books into: two counter families of
    ``reg`` and the ring of ``tracer``. ``install()`` makes the process's
    one; a test makes its own over a private registry and tracer and hands
    it events."""

    def __init__(self, reg: metrics.MetricsRegistry,
                 tracer: spans.SpanTracer):
        self._tracer = tracer
        self._seconds = reg.counter(
            "dl4j_compile_seconds_total",
            "seconds JAX spent tracing (trace), lowering (lower), compiling "
            "or loading (backend) and reading the persistent cache "
            "(cache_read), self time, by the site span they fell in",
            ("site", "phase"))
        self._events = reg.counter(
            "dl4j_compile_events_total",
            "JAX compile events by site and phase: phase=backend counts the "
            "executables a site compiled or loaded", ("site", "phase"))
        self._tls = threading.local()

    def listen(self) -> None:
        """Register with ``jax.monitoring`` (jax is imported by now)."""
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self.on_duration)

    # -- the listener: called from inside JAX's compile path, never raises --

    def on_duration(self, event: str, seconds: float, **kw) -> None:
        phase = PHASES.get(event)
        if phase is None or not _enabled():
            return
        try:
            self._book(phase, float(seconds), kw.get("fun_name"))
        except Exception:   # a fault here must not fail the user's compile
            _log.warning("compile event %s not booked", event, exc_info=True)

    # -- booking -----------------------------------------------------------

    def _book(self, phase: str, seconds: float, fun_name) -> None:
        now = time.perf_counter()
        start, own = self._own_time(now, seconds)
        owner = self._tracer.open_span(with_site=True)
        site = owner.site if owner is not None else NO_SITE
        self._seconds.inc(own, site=site, phase=phase)
        self._events.inc(site=site, phase=phase)
        if owner is not None:
            owner.compile_s += own
        if phase == "trace" and seconds < _RING_MIN_TRACE_S:
            return
        parent = self._tracer.open_span()
        if parent is not None:
            start = max(start, parent.t0)   # a child lies inside its parent
        attrs = {"site": site, "self_s": own}
        if fun_name is not None:
            attrs["fun"] = str(fun_name)
        self._tracer.record(f"compile.{phase}", start, now - start, **attrs)

    def _own_time(self, now: float, seconds: float) -> Tuple[float, float]:
        """Where an event of ``seconds`` that ends ``now`` began, and its
        seconds less those already booked inside it. The thread's booked
        intervals ``(start, end, seconds)`` are disjoint and in order; the
        event swallows those it covers, so what stays is one interval a
        finished outermost event (a step's trace holds thousands until it
        ends; an event around more than ``_KEEP`` others would be booked the
        oldest ones' seconds twice). One that began before the event did is a
        neighbour that ended as this one began (the clocks differ by
        microseconds): the event starts at its end."""
        booked = getattr(self._tls, "booked", None)
        if booked is None:
            booked = self._tls.booked = deque(maxlen=_KEEP)
        start = now - seconds
        inside = 0.0
        while booked and booked[-1][1] > start:
            if booked[-1][0] < start:
                start = booked[-1][1]
                break
            inside += booked.pop()[2]
        booked.append((start, now, max(seconds, inside)))
        return start, max(seconds - inside, 0.0)


def _enabled() -> bool:
    from deeplearning4j_tpu import obs

    return obs.enabled()


_PROCESS: Optional[CompilePhases] = None
_LOCK = threading.Lock()


def install() -> Optional[CompilePhases]:
    """Start the process's one listener over ``obs.registry()`` and
    ``obs.tracer()``: called where jax is already imported (the first
    ``StepProgram`` constructed, the first ``obs.span``), once. With
    ``DL4J_TPU_OBS=0`` nothing is registered, neither listener nor family."""
    global _PROCESS
    if _PROCESS is None and _enabled():
        with _LOCK:
            if _PROCESS is None:
                phases = CompilePhases(metrics.registry(), spans.tracer())
                phases.listen()
                _PROCESS = phases
    return _PROCESS
