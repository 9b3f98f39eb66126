"""Serving SLO instrumentation: latency, saturation, and burn rate.

One :class:`SloTracker` per process watches every request path (HTTP routes
on ``ui/server.py``, the ``ParallelInference`` serving queue) and maintains,
per route:

- ``dl4j_request_seconds{route}``     — latency histogram whose P² streaming
  quantiles (p50/p95/p99, obs/metrics.py) stay accurate over the whole
  stream, not just a recent window. Each series also carries mergeable
  fixed-boundary bucket counts (``metrics.BUCKET_BOUNDS``), so the fleet
  collector (obs/fleet.py) can ADD counts across workers and compute a
  true federated p99 — quantiles themselves never merge;
- ``dl4j_requests_total{route,status}`` — request counter (``status`` is the
  HTTP status class or ``ok``/``error`` for non-HTTP paths);
- ``dl4j_slo_burn_rate{route}``       — how fast the route is spending its
  error budget over a sliding window: ``bad_fraction / (1 - objective)``.
  1.0 = burning budget exactly as fast as the objective allows; >1 = paging
  territory; 0 = clean window. A request is *bad* when it errors, its
  latency exceeds the threshold, or it was SHED by the serving tier;
- ``dl4j_shed_total{route,reason}``   — load-shedding decisions by reason
  (``backpressure`` → HTTP 429, ``deadline`` → HTTP 503; ``serve/``).
  Shed requests also count into ``dl4j_requests_total{status="shed"}`` and
  into the burn-rate window, so overload moves the same gauge paging
  watches for latency SLO violations.

Knobs (read at tracker construction): ``DL4J_TPU_SLO_LATENCY_MS`` (latency
threshold, default 250), ``DL4J_TPU_SLO_ROUTE_LATENCY_MS`` (per-route
overrides as comma-separated ``prefix=ms`` pairs, longest matching prefix
wins — e.g. ``search:http=50,generate=2000`` holds search to 50ms while
generation keeps a 2s envelope), ``DL4J_TPU_SLO_OBJECTIVE`` (good-request
objective, default 0.99), ``DL4J_TPU_SLO_WINDOW_S`` (sliding window,
default 300).

Gauges for saturation live next to the code that owns the resource:
``dl4j_serving_queue_depth`` / ``dl4j_serving_in_flight``
(``parallel/inference.py``) and ``dl4j_http_in_flight`` (``ui/server.py``).

Recording is host-side arithmetic on ``perf_counter`` scalars under a lock
— O(1) amortized per request (stale-window eviction is paid incrementally
by the requests that observe it). Rides the ``DL4J_TPU_OBS=0`` kill switch.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Deque, Dict, Optional, Tuple

from deeplearning4j_tpu.obs import metrics

__all__ = ["SloTracker", "slo_tracker", "observe_request", "observe_shed",
           "observe_ttft", "observe_wait", "observe_itl",
           "set_decode_occupancy"]


def _parse_route_thresholds(spec: str) -> Dict[str, float]:
    """``"search:http=50,generate=2000"`` -> {prefix: seconds}. Malformed
    pairs are skipped — a bad knob value must not take down the tracker."""
    out: Dict[str, float] = {}
    for pair in spec.split(","):
        pair = pair.strip()
        if not pair or "=" not in pair:
            continue
        prefix, _, ms = pair.rpartition("=")
        try:
            out[prefix.strip()] = float(ms) / 1e3
        except ValueError:
            continue
    return out


class SloTracker:
    def __init__(self,
                 reg: Optional[metrics.MetricsRegistry] = None,
                 threshold_s: Optional[float] = None,
                 objective: Optional[float] = None,
                 window_s: Optional[float] = None):
        self._reg = reg or metrics.registry()
        env = os.environ.get
        if threshold_s is None:
            threshold_s = float(env("DL4J_TPU_SLO_LATENCY_MS", "250")) / 1e3
        if objective is None:
            objective = float(env("DL4J_TPU_SLO_OBJECTIVE", "0.99"))
        if window_s is None:
            window_s = float(env("DL4J_TPU_SLO_WINDOW_S", "300"))
        self.threshold_s = threshold_s
        self.route_thresholds_s = _parse_route_thresholds(
            env("DL4J_TPU_SLO_ROUTE_LATENCY_MS", ""))
        self.objective = min(max(objective, 0.0), 0.999999)
        self.window_s = window_s
        self._hist = self._reg.histogram(
            "dl4j_request_seconds",
            "request latency by route (P² streaming quantiles; serving SLO "
            "source of truth)", ("route",))
        self._count = self._reg.counter(
            "dl4j_requests_total", "requests by route and status class",
            ("route", "status"))
        self._burn = self._reg.gauge(
            "dl4j_slo_burn_rate",
            "error-budget burn rate over the sliding window: bad_fraction / "
            "(1 - objective); 1.0 = spending budget exactly at the "
            "objective rate", ("route",))
        self._shed = self._reg.counter(
            "dl4j_shed_total",
            "load-shedding decisions by route and reason (backpressure -> "
            "429, deadline -> 503)", ("route", "reason"))
        # token-level generative serving (serve/scheduler.GenerateWorker):
        # a stream's user experience is TTFT + the ITL tail, not one
        # end-to-end latency, so both get their own histograms and their
        # own thresholds into the SAME burn-rate window — a slow first
        # token or a stuttering stream spends error budget exactly like a
        # slow predict() request
        self.ttft_threshold_s = float(
            env("DL4J_TPU_SLO_TTFT_MS",
                env("DL4J_TPU_SLO_LATENCY_MS", "250"))) / 1e3
        self.itl_threshold_s = float(env("DL4J_TPU_SLO_ITL_MS", "100")) / 1e3
        self._ttft = self._reg.histogram(
            "dl4j_ttft_seconds",
            "time to first generated token by route (prompt queue + prefill; "
            "P2 streaming quantiles)", ("route",))
        self._itl = self._reg.histogram(
            "dl4j_itl_seconds",
            "inter-token latency by route (decode-step cadence as the "
            "stream consumer sees it)", ("route",))
        self._wait = self._reg.histogram(
            "dl4j_request_wait_seconds",
            "what a generation request waited for before its first token, "
            "by route and stage: queue (submit to admit) and prefill (admit "
            "to first token); the two add up to dl4j_ttft_seconds",
            ("route", "stage"))
        self._tokens = self._reg.counter(
            "dl4j_tokens_generated_total",
            "generated tokens by route (every emitted decode token)",
            ("route",))
        self._occupancy = self._reg.gauge(
            "dl4j_decode_batch_occupancy",
            "streams currently in the token-level continuous decode batch",
            ("model",))
        self._lock = threading.Lock()
        # route -> deque[(perf_counter_ts, is_bad)]
        self._windows: Dict[str, Deque[Tuple[float, bool]]] = {}

    def threshold_for(self, route: str) -> float:
        """Latency threshold for ``route``: the longest
        ``DL4J_TPU_SLO_ROUTE_LATENCY_MS`` prefix that matches, else the
        global default. Different request classes carry different latency
        contracts (a vector search answers in tens of ms, a generate stream
        in seconds); one global number would either page on healthy
        generation or sleep through a slow search tier."""
        best = self.threshold_s
        best_len = -1
        for prefix, thr in self.route_thresholds_s.items():
            if route.startswith(prefix) and len(prefix) > best_len:
                best, best_len = thr, len(prefix)
        return best

    def observe(self, route: str, latency_s: float, status: str = "ok",
                error: bool = False):
        """Record one finished request. Never raises (the serving path must
        not die to bookkeeping)."""
        try:
            self._hist.observe(latency_s, route=route)
            self._count.inc(route=route, status=status)
            self._note_window(
                route, error or latency_s > self.threshold_for(route))
        except Exception:
            pass

    def observe_shed(self, route: str, reason: str = "backpressure"):
        """Record one load-shedding decision (``serve/`` scheduler). A shed
        counts as a BAD request for the burn rate — rejecting traffic spends
        error budget, which is exactly what makes the overload visible on
        the same gauge paging watches for latency violations — but it does
        not enter the latency histogram (a shed has no service latency).
        Never raises."""
        try:
            self._count.inc(route=route, status="shed")
            self._shed.inc(route=route, reason=reason)
            self._note_window(route, True)
        except Exception:
            pass

    def _note_window(self, route: str, bad: bool):
        now = time.perf_counter()
        horizon = now - self.window_s
        with self._lock:
            win = self._windows.get(route)
            if win is None:
                win = self._windows[route] = deque()
            win.append((now, bad))
            while win and win[0][0] < horizon:
                win.popleft()
            n_bad = sum(1 for _, b in win if b)
            rate = (n_bad / len(win)) / (1.0 - self.objective)
        self._burn.set(round(rate, 4), route=route)

    def observe_ttft(self, route: str, latency_s: float):
        """Record one stream's time-to-first-token. Counts the first token
        into the token counter and burns budget when it misses the TTFT
        threshold. Never raises."""
        try:
            self._ttft.observe(latency_s, route=route)
            self._tokens.inc(route=route)
            self._note_window(route, latency_s > self.ttft_threshold_s)
        except Exception:
            pass

    def observe_wait(self, route: str, stage: str, latency_s: float):
        """Record one request's wait in ``stage`` (``queue`` or
        ``prefill``). Never raises."""
        try:
            self._wait.observe(latency_s, route=route, stage=stage)
        except Exception:
            pass

    def observe_itl(self, route: str, latency_s: float):
        """Record one inter-token gap; every call is one more generated
        token. A gap over the ITL threshold burns budget — stream stutter
        is an SLO violation even when the total finishes on time. Never
        raises."""
        try:
            self._itl.observe(latency_s, route=route)
            self._tokens.inc(route=route)
            self._note_window(route, latency_s > self.itl_threshold_s)
        except Exception:
            pass

    def set_decode_occupancy(self, model: str, streams: int):
        """Gauge: streams currently holding a decode-batch slot."""
        try:
            self._occupancy.set(int(streams), model=model)
        except Exception:
            pass

    def burn_rate(self, route: str) -> Optional[float]:
        return self._burn.value(route=route)

    def clear(self):
        with self._lock:
            self._windows.clear()


_TRACKER: Optional[SloTracker] = None
_TRACKER_LOCK = threading.Lock()


def slo_tracker() -> SloTracker:
    """Process-global tracker, constructed on first use so env knobs set by
    tests/launchers before the first request are honored."""
    global _TRACKER
    if _TRACKER is None:
        with _TRACKER_LOCK:
            if _TRACKER is None:
                _TRACKER = SloTracker()
    return _TRACKER


def observe_request(route: str, latency_s: float, status: str = "ok",
                    error: bool = False):
    """Module-level convenience; honors the DL4J_TPU_OBS kill switch."""
    from deeplearning4j_tpu import obs

    if obs.enabled():
        slo_tracker().observe(route, latency_s, status=status, error=error)


def observe_shed(route: str, reason: str = "backpressure"):
    """Module-level convenience; honors the DL4J_TPU_OBS kill switch."""
    from deeplearning4j_tpu import obs

    if obs.enabled():
        slo_tracker().observe_shed(route, reason=reason)


def observe_ttft(route: str, latency_s: float):
    """Module-level convenience; honors the DL4J_TPU_OBS kill switch."""
    from deeplearning4j_tpu import obs

    if obs.enabled():
        slo_tracker().observe_ttft(route, latency_s)


def observe_wait(route: str, stage: str, latency_s: float):
    """Module-level convenience; honors the DL4J_TPU_OBS kill switch."""
    from deeplearning4j_tpu import obs

    if obs.enabled():
        slo_tracker().observe_wait(route, stage, latency_s)


def observe_itl(route: str, latency_s: float):
    """Module-level convenience; honors the DL4J_TPU_OBS kill switch."""
    from deeplearning4j_tpu import obs

    if obs.enabled():
        slo_tracker().observe_itl(route, latency_s)


def set_decode_occupancy(model: str, streams: int):
    """Module-level convenience; honors the DL4J_TPU_OBS kill switch."""
    from deeplearning4j_tpu import obs

    if obs.enabled():
        slo_tracker().set_decode_occupancy(model, streams)


def _reset_tracker():
    """Drop the global tracker so the next request re-reads env knobs
    (obs.reset; the registry families are cleared separately)."""
    global _TRACKER
    with _TRACKER_LOCK:
        _TRACKER = None
