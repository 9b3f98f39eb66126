"""Parallel inference with request batching.

Parity: parallelism/ParallelInference.java:32 (modes:52, output:110-136) and
inference/observers/BatchedInferenceObservable.java. The reference keeps N
model replicas on N devices with a batching queue; on TPU one sharded model
serves all chips, so the capability reduces to: (a) a thread-safe front that
coalesces small requests into padded batches (the BATCHED mode), (b) direct
pass-through (INPLACE/SEQUENTIAL modes).
"""

from __future__ import annotations

import queue
import threading
from typing import List, Optional

import time

import numpy as np

from .. import obs
from ..utils import bucketing


class _Pending:
    __slots__ = ("x", "event", "result", "deadline")

    def __init__(self, x, deadline: Optional[float] = None):
        self.x = x
        self.event = threading.Event()
        self.result = None
        self.deadline = deadline  # perf_counter scale, None = no deadline


class ParallelInference:
    """Batched inference front-end.

    ``mode``: "inplace" (call straight through) or "batched" (coalesce queued
    requests into one device call of at most ``max_batch_size`` examples; a
    single oversized request still dispatches whole).

    ``bucket``: pad each drained batch's row count up to the shared bucket
    ladder (see ``utils.bucketing``) before dispatch, so steady-state mixed
    request sizes hit at most one compiled executable per bucket instead of
    one per distinct coalesced size. Defaults to the DL4J_TPU_BUCKETING env
    switch. Padded rows are zeros (inference is row-independent) and are
    sliced off before results fan back out to requesters.

    ``warmup``: AOT-compile the model's inference executable for EVERY
    bucket a coalesced batch can hit (``nn.aot.warm_serving``) before the
    first request, so time-to-first-request never pays an XLA compile.
    Defaults to the DL4J_TPU_AOT env switch.
    """

    def __init__(self, model, mode: str = "batched", max_batch_size: int = 32,
                 queue_limit: int = 64, worker: bool = True,
                 bucket: Optional[bool] = None, warmup: Optional[bool] = None):
        self.model = model
        self.mode = mode
        self.max_batch_size = max_batch_size
        self.bucket = bucketing.bucketing_enabled() if bucket is None else bucket
        if warmup is None:
            from ..nn import aot

            warmup = aot.enabled()
        if warmup:
            from ..nn import aot

            aot.warm_serving(model, max_batch_size)
        self._queue: "queue.Queue[_Pending]" = queue.Queue(maxsize=queue_limit)
        self._carry: Optional[_Pending] = None  # request deferred by _drain
        self._stop = threading.Event()
        self._lifecycle_lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        if mode == "batched" and worker:
            self._thread = threading.Thread(target=self._worker_loop, daemon=True)
            self._thread.start()

    # -- public ------------------------------------------------------------
    def output(self, x, deadline_ms: Optional[float] = None) -> np.ndarray:
        """``deadline_ms`` (relative to now): a batched request still queued
        when its deadline passes is SHED — it fails fast with
        :class:`~deeplearning4j_tpu.serve.scheduler.ShedError` instead of
        returning a late answer, and counts into ``dl4j_shed_total`` /
        the SLO burn window (serve-tier semantics; docs/SERVING.md)."""
        from ..serve.scheduler import ShedError

        t0 = time.perf_counter()
        try:
            out = self._output(x, deadline_ms=deadline_ms)
        except ShedError:
            raise  # already accounted via observe_shed, not a latency sample
        except Exception:
            obs.observe_request("pi.output", time.perf_counter() - t0,
                                status="error", error=True)
            raise
        obs.observe_request("pi.output", time.perf_counter() - t0)
        return out

    def _output(self, x, deadline_ms: Optional[float] = None) -> np.ndarray:
        x = np.asarray(x)
        if self.mode != "batched" or self._thread is None:
            if self._stop.is_set():
                raise RuntimeError("ParallelInference is shut down")
            return np.asarray(self.model.output(x))
        deadline = (None if deadline_ms is None
                    else time.perf_counter() + float(deadline_ms) / 1e3)
        p = _Pending(x, deadline=deadline)
        # enqueue under the shutdown lock so a request can't slip into the
        # queue after shutdown() drained it (check-then-put race)
        with self._lifecycle_lock:
            if self._stop.is_set():
                raise RuntimeError("ParallelInference is shut down")
            self._queue.put(p)
            if obs.enabled():
                obs.gauge("dl4j_inference_queue_depth",
                          "Requests waiting in the batching queue"
                          ).set(self._queue.qsize())
        p.event.wait()
        if isinstance(p.result, Exception):
            raise p.result
        return p.result

    def shutdown(self):
        with self._lifecycle_lock:
            self._stop.set()
        if self._thread is not None:
            self._queue.put(_Pending(None))  # wake the worker
            self._thread.join(timeout=5)
            with self._lifecycle_lock:
                # fail requests stranded in the queue (or carried by the
                # worker's coalescer) so waiters don't hang
                if self._carry is not None:
                    p, self._carry = self._carry, None
                    if p.x is not None:
                        p.result = RuntimeError("ParallelInference shut down")
                        p.event.set()
                while True:
                    try:
                        p = self._queue.get_nowait()
                    except queue.Empty:
                        break
                    if p.x is not None:
                        p.result = RuntimeError("ParallelInference shut down")
                        p.event.set()

    # -- worker ------------------------------------------------------------
    def _drain(self) -> List[_Pending]:
        """Assemble one device batch: coalesce queued requests until the
        EXAMPLE count reaches ``max_batch_size`` (an oversized single request
        still goes through whole). A request that would overflow the cap is
        carried to the next batch, so the coalesced size — and hence the set
        of shape buckets a serving process can ever compile — is bounded."""
        if self._carry is not None:
            batch, self._carry = [self._carry], None
        else:
            batch = [self._queue.get()]
        n = len(batch[0].x) if batch[0].x is not None else 0
        while n < self.max_batch_size:
            try:
                p = self._queue.get_nowait()
            except queue.Empty:
                break
            if p.x is not None and n + len(p.x) > self.max_batch_size:
                self._carry = p
                break
            batch.append(p)
            if p.x is not None:
                n += len(p.x)
        return self._shed_expired([p for p in batch if p.x is not None])

    def _shed_expired(self, batch: List[_Pending]) -> List[_Pending]:
        """Fail queued requests whose deadline already passed instead of
        spending device time on answers nobody is waiting for."""
        live = [p for p in batch if p.deadline is None
                or p.deadline >= time.perf_counter()]
        for p in batch:
            if p not in live:
                from ..serve.scheduler import ShedError

                obs.observe_shed("pi.output", reason="deadline")
                p.result = ShedError(
                    "deadline", "deadline expired in the batching queue")
                p.event.set()
        return live

    def _worker_loop(self):
        while not self._stop.is_set():
            batch = self._drain()
            if not batch:
                continue
            if obs.enabled():
                obs.gauge("dl4j_inference_in_flight",
                          "Coalesced requests currently on device"
                          ).set(len(batch))
            try:
                sizes = [len(p.x) for p in batch]
                xs = np.concatenate([p.x for p in batch], axis=0)
                total = len(xs)
                if self.bucket and total > 0:
                    target = bucketing.bucket_size(total)
                    bucketing.telemetry().record_hit("pi.batched", total, target)
                    if target > total:
                        xs = np.concatenate(
                            [xs, np.zeros((target - total,) + xs.shape[1:], xs.dtype)])
                out = np.asarray(self.model.output(xs))[:total]
                ofs = 0
                for p, n in zip(batch, sizes):
                    p.result = out[ofs : ofs + n]
                    ofs += n
                    p.event.set()
            except Exception as e:  # propagate to all waiters
                for p in batch:
                    p.result = e
                    p.event.set()
            finally:
                if obs.enabled():
                    obs.gauge("dl4j_inference_in_flight",
                              "Coalesced requests currently on device").set(0)
