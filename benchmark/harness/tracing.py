"""Taking the profiler's trace of a slice of the window, in the traced run
only. The slice starts some way into the window (ramp-up is not steady
state) and is short: traces are large, and a machine's disk is counted.
Starting and above all stopping the profiler takes seconds of the host, so
the traced run's host-clock metrics (the step's share of peak, the tails) are
taken over the part of the window before ``started_at``. The
trace goes to a fixed directory inside the checkout and is overwritten by the
next traced run."""

from __future__ import annotations

import os
import shutil
import time

from benchmark.harness import spec, trace


class Tracer:
    def __init__(self, enabled: bool, cell: str, start_s: float, length_s: float):
        self.enabled = enabled
        self.dir = os.path.join(spec.CHECKOUT, ".bench_trace", cell)
        self.start_s, self.length_s = start_s, length_s
        self.state = "off" if not enabled else "waiting"
        self._mark = None
        self._t_on = None
        self.started_at = None    # perf_counter() when the profiler was started

    def poll(self, elapsed_s: float) -> None:
        """Called by the driver from one thread, at points where the window's
        own work is between two operations."""
        import jax

        if self.state == "waiting" and elapsed_s >= self.start_s:
            self.started_at = time.perf_counter()
            shutil.rmtree(self.dir, ignore_errors=True)
            # host events (the runtime's, and the window's mark) but not the
            # Python tracer: on every call of every thread it slowed the
            # serving engine's loop by a third and inflated the idle share
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._mark = jax.profiler.TraceAnnotation(trace.WINDOW_EVENT)
            self._mark.__enter__()
            self._t_on = time.perf_counter()
            self.state = "on"
        elif self.state == "on" and \
                time.perf_counter() - self._t_on >= self.length_s:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.state == "on":
            self._mark.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"

    def summary(self):
        """The reduced trace, or None where this run took none."""
        if self.state != "done":
            return None
        out = trace.reduce(trace.load_xplane(trace.find_xplane(self.dir)))
        shutil.rmtree(self.dir, ignore_errors=True)
        return out
