"""Training listeners: the hook SPI preserved from the reference.

Parity: optimize/api/TrainingListener.java + impls under optimize/listeners/
(ScoreIterationListener, PerformanceListener with samples/sec at :109,
CollectScoresIterationListener, TimeIterationListener, EvaluativeListener).

On TPU the listener fires on the HOST after each executed step; metrics it
receives are already-computed device scalars. Because the train step is one
XLA executable, listeners cannot observe intra-step activations the way the
reference's onForwardPass could — instead the model offers an explicit
``feed_forward`` debug path (interpret mode) for that use case.
"""

from __future__ import annotations

import logging
import time
from typing import Callable, List, Optional

logger = logging.getLogger("deeplearning4j_tpu")


class TrainingListener:
    """Hook interface. All methods are optional no-ops.

    ``iteration_done(model, iteration, score, batch_size)`` is called once
    for every step, in order, with the step's own ``iteration`` (the count
    of steps made when it ended), ``score`` (its loss, a host float) and
    ``batch_size`` (its real rows). ``fit()`` makes the call after it has
    enqueued the next step, so that the device is not left waiting for the
    host: ``model.params``, ``model.state`` and ``model.iteration`` may then
    be the next step's already. A listener that reads the model's arrays at
    ``iteration_done``, or raises there to stop the run at that very step,
    sets ``reads_model = True``: with one attached ``fit()`` reports every
    step before it dispatches the next. The last step of a stream is
    reported before ``on_epoch_end``, which always sees the model as the
    epoch left it. A listener without the attribute counts as ``False``."""

    reads_model = False

    def on_epoch_start(self, model, epoch: int):  # noqa: D102
        pass

    def on_epoch_end(self, model, epoch: int):  # noqa: D102
        pass

    def iteration_done(self, model, iteration: int, score: float, batch_size: int = 0):
        pass

    def on_gradient_calculation(self, model, iteration: int):
        pass


BaseTrainingListener = TrainingListener


class ScoreIterationListener(TrainingListener):
    """Log the score every N iterations (ScoreIterationListener.java)."""

    def __init__(self, print_every: int = 10, out: Optional[Callable[[str], None]] = None):
        self.print_every = max(1, print_every)
        self.out = out or (lambda s: logger.info(s))

    def iteration_done(self, model, iteration, score, batch_size=0):
        if iteration % self.print_every == 0:
            self.out(f"Score at iteration {iteration} is {score}")


class PerformanceListener(TrainingListener):
    """Throughput reporting: samples/sec, batches/sec
    (PerformanceListener.java:109)."""

    def __init__(self, frequency: int = 10, out: Optional[Callable[[str], None]] = None):
        self.frequency = max(1, frequency)
        self.out = out or (lambda s: logger.info(s))
        self._last_time: Optional[float] = None
        self._last_iter = 0
        self._samples = 0
        self.history: List[dict] = []

    def iteration_done(self, model, iteration, score, batch_size=0):
        now = time.perf_counter()
        # anchor BEFORE accumulating: the anchoring call's batch used to be
        # discarded (_samples zeroed after += batch_size), understating
        # samples/sec for the first window
        if self._last_time is None:
            self._last_time = now
            self._last_iter = iteration
        self._samples += batch_size
        if iteration - self._last_iter >= self.frequency:
            dt = now - self._last_time
            iters = iteration - self._last_iter
            rec = {
                "iteration": iteration,
                "batches_per_sec": iters / dt if dt > 0 else float("inf"),
                "samples_per_sec": self._samples / dt if dt > 0 else float("inf"),
                "score": score,
            }
            self.history.append(rec)
            self.out(
                f"iteration {iteration}: {rec['samples_per_sec']:.1f} samples/sec, "
                f"{rec['batches_per_sec']:.2f} batches/sec, score {score}"
            )
            self._last_time = now
            self._last_iter = iteration
            self._samples = 0


class ProfilerListener(TrainingListener):
    """Capture a jax-profiler (xprof/perfetto) trace for a window of
    training iterations — §5.1 tracing parity; the reference's equivalent is
    the SystemInfo/benchmark tooling, here it is the real XLA profiler.

    Writes a TensorBoard-loadable trace directory::

        model.set_listeners(ProfilerListener("/tmp/trace", start=10, stop=20))
    """

    def __init__(self, log_dir: str, start: int = 10, stop: int = 20):
        if stop <= start:
            raise ValueError("stop must be > start")
        self.log_dir = str(log_dir)
        self.start = start
        self.stop = stop
        self._active = False
        self.captured = False

    def iteration_done(self, model, iteration, score, batch_size=0):
        import jax

        if not self._active and not self.captured and iteration >= self.start:
            jax.profiler.start_trace(self.log_dir)
            self._active = True
        elif self._active and iteration >= self.stop:
            jax.profiler.stop_trace()
            self._active = False
            self.captured = True

    def close(self):
        """Stop an in-flight trace (call when training ends inside the
        window). Epoch boundaries deliberately do NOT stop the trace — a
        window may span epochs (1-iteration-per-epoch fits are common)."""
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False
            self.captured = True


class CollectScoresListener(TrainingListener):
    """Accumulate (iteration, score) pairs
    (CollectScoresIterationListener.java)."""

    def __init__(self, frequency: int = 1):
        self.frequency = max(1, frequency)
        self.scores: List[tuple] = []

    def iteration_done(self, model, iteration, score, batch_size=0):
        if iteration % self.frequency == 0:
            self.scores.append((iteration, float(score)))


class TimeIterationListener(TrainingListener):
    """ETA logging over a known iteration budget (TimeIterationListener.java)."""

    def __init__(self, total_iterations: int, frequency: int = 100,
                 out: Optional[Callable[[str], None]] = None):
        self.total = total_iterations
        self.frequency = max(1, frequency)
        self.out = out or (lambda s: logger.info(s))
        self.start = time.perf_counter()

    def iteration_done(self, model, iteration, score, batch_size=0):
        if iteration and iteration % self.frequency == 0:
            elapsed = time.perf_counter() - self.start
            rate = iteration / elapsed
            remaining = (self.total - iteration) / rate if rate > 0 else float("inf")
            self.out(f"iteration {iteration}/{self.total}, ETA {remaining:.0f}s")


class EvaluativeListener(TrainingListener):
    """Periodically evaluate on a held-out set (EvaluativeListener.java)."""

    reads_model = True

    def __init__(self, data, frequency_epochs: int = 1,
                 out: Optional[Callable[[str], None]] = None):
        self.data = data
        self.frequency_epochs = max(1, frequency_epochs)
        self.out = out or (lambda s: logger.info(s))
        self.evaluations: List[object] = []

    def on_epoch_end(self, model, epoch):
        if epoch % self.frequency_epochs == 0:
            ev = model.evaluate(self.data)
            self.evaluations.append(ev)
            self.out(f"epoch {epoch}: accuracy {ev.accuracy():.4f} f1 {ev.f1():.4f}")


class ComposedListener(TrainingListener):
    """Fan out to several listeners."""

    def __init__(self, listeners: List[TrainingListener]):
        self.listeners = list(listeners)

    @property
    def reads_model(self) -> bool:
        return any(getattr(l, "reads_model", False) for l in self.listeners)

    def on_epoch_start(self, model, epoch):
        for l in self.listeners:
            l.on_epoch_start(model, epoch)

    def on_epoch_end(self, model, epoch):
        for l in self.listeners:
            l.on_epoch_end(model, epoch)

    def iteration_done(self, model, iteration, score, batch_size=0):
        for l in self.listeners:
            l.iteration_done(model, iteration, score, batch_size)

    def on_gradient_calculation(self, model, iteration):
        for l in self.listeners:
            l.on_gradient_calculation(model, iteration)

    def close(self):
        close_listeners(self.listeners)


def close_listeners(listeners) -> None:
    """Call ``close()`` on every listener that defines one (fit teardown:
    stops in-flight ProfilerListener traces, flushes wrapped sinks). Errors
    are logged, not raised — teardown must not mask the fit's own outcome."""
    for l in listeners or ():
        close = getattr(l, "close", None)
        if callable(close):
            try:
                close()
            except Exception:
                logger.exception("listener %r close() failed", type(l).__name__)
