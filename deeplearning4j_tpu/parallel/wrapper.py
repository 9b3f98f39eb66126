"""Data-parallel training over a device mesh.

Capability parity with ParallelWrapper
(/root/reference/deeplearning4j-scaleout/deeplearning4j-scaleout-parallelwrapper/
src/main/java/org/deeplearning4j/parallelism/ParallelWrapper.java:58) and the
Spark TrainingMasters — re-designed TPU-first. Where the reference spawns one
replica thread per device and averages parameters every N iterations (or
threshold-encodes gradient updates into a shared ring buffer), here the SAME
jitted step the single-chip path uses is simply fed a globally-sharded batch:
params live replicated on every chip, the batch is split along the ``data``
mesh axis, and XLA inserts the gradient all-reduce (psum over ICI) during
compilation. Parameter averaging, gradient sharing, and the parameter server
are all THIS one mechanism — exact (no compression loss), synchronous, and
overlapped with backprop by the compiler.
"""

from __future__ import annotations

import os
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.nn.model import _iter_batches
from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
from deeplearning4j_tpu.train.listeners import close_listeners
from deeplearning4j_tpu.utils import bucketing
from deeplearning4j_tpu.utils.bucketing import padded_label_mask, tile_pad


def _env_flag(name: str) -> Optional[bool]:
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return None
    return raw != "0"

# DP sharding and shape bucketing share one padding mechanism (tiled rows +
# zero-weighted loss); the canonical implementation lives in utils.bucketing.
# Kept as a module name here for compatibility with existing callers.
_tile_pad = tile_pad


class ParallelWrapper:
    """Drop-in accelerator for a MultiLayerNetwork/ComputationGraph: same
    ``fit`` surface, batch sharded over the mesh's ``data`` axis.

    Usage::

        pw = ParallelWrapper(model)          # all local devices
        pw.fit((x, y), epochs=10, batch_size=512)

    The global batch must divide by the data-axis size (the reference
    round-robins whole DataSets to workers; here the sharding is exact).
    """

    def __init__(self, model, mesh: Optional[Mesh] = None,
                 grad_compress: Optional[bool] = None,
                 sharded_update: Optional[bool] = None,
                 compress_threshold: Optional[float] = None):
        self.model = model
        self.mesh = mesh if mesh is not None else make_mesh(MeshSpec())
        self.n_data = self.mesh.shape["data"]
        self._repl = NamedSharding(self.mesh, P())
        # Explicit-exchange switches (parallel/grads.py): kwargs win, then
        # env (DL4J_TPU_GRAD_COMPRESS / DL4J_TPU_SHARDED_UPDATE /
        # DL4J_TPU_COMPRESS_THRESHOLD), default OFF — on a single
        # ICI-connected slice the implicit dense psum is already optimal
        # (parallel/grads.py's docstring).
        if grad_compress is None:
            grad_compress = _env_flag("DL4J_TPU_GRAD_COMPRESS")
        if sharded_update is None:
            sharded_update = _env_flag("DL4J_TPU_SHARDED_UPDATE")
        if compress_threshold is None:
            compress_threshold = float(
                os.environ.get("DL4J_TPU_COMPRESS_THRESHOLD", "1e-3"))
        self.grad_compress = bool(grad_compress)
        self.sharded_update = bool(sharded_update)
        self.compress_threshold = float(compress_threshold)
        self._runner = None
        # Multi-host (jax.distributed): every process runs this same fit()
        # on its process-LOCAL batch rows; global batch = concat over
        # processes in process order. Per-host batch sizes may be UNEVEN
        # (MLN path): hosts equalize padded sizes via process_allgather and
        # the loss rescale uses the GLOBAL real-row count, so the result
        # equals a single-process run on the concatenated batch exactly
        # (tests/test_multihost.py). Padding granularity is the per-process
        # shard count.
        self._nproc = jax.process_count()
        self._pad_quantum = max(self.n_data // self._nproc, 1)

    def _shard(self, arr):
        if arr is None:
            return None
        from deeplearning4j_tpu.parallel.distributed import global_array

        arr = np.asarray(arr)  # before .ndim: lists welcome
        if arr.dtype.kind not in "iub":
            # preserve integer/bool arrays: token-id features and sparse
            # class labels must not round-trip through the float model dtype
            arr = arr.astype(self.model.dtype)
        spec = P("data", *([None] * (arr.ndim - 1)))
        return global_array(self.mesh, arr, spec)

    def _replicate_model(self):
        from deeplearning4j_tpu.parallel.distributed import replicate_global

        self.model.params = replicate_global(self.mesh, self.model.params)
        self.model.state = replicate_global(self.mesh, self.model.state)
        if self.model.opt_state is not None:
            self.model.opt_state = replicate_global(self.mesh, self.model.opt_state)

    def _pad_to_shardable(self, arrs, record: bool = False):
        """Tile members of a batch so the leading axis divides n_data —
        rounded UP the shared bucketing ladder first (utils.bucketing), so DP
        fit with ragged batch sizes reuses a bounded set of compiled
        executables exactly like the single-chip path (every distinct padded
        size is a fresh XLA compile of the sharded step). Disable via
        DL4J_TPU_BUCKETING=0 to pad only to the shard count.

        Padded rows repeat real examples (benign numerics for batch-coupled
        ops) but MUST be zero-weighted in the loss by the caller — see
        ``_padded_lmask`` — or they would silently double-weight samples in
        the gradient."""
        n = next(len(a) for a in arrs if a is not None)
        q = self._pad_quantum
        target = bucketing.bucket_size(n) if (
            bucketing.bucketing_enabled() and n > 0) else n
        target = max(target, q if n == 0 else n)
        target = -(-target // q) * q            # round up to the shard quantum
        if record:
            bucketing.telemetry().record_hit("dp.fit", n, target)
        if target == n and n > 0:
            return arrs, n
        return tuple(_tile_pad(a, target - n) for a in arrs), n

    def _even_multihost(self, arrs, n):
        """Equalize each process's PADDED local row count to the global max
        (global_array needs equal per-process shards) and return the global
        real-row count + global padded batch size.

        The allgather runs EVERY batch on purpose: it is a collective, and
        skip-when-locally-unchanged caching would deadlock the moment one
        host's batch size changes while another's repeats (each host can
        only see its own key). It moves 16 bytes; the per-batch cost is a
        host-side round-trip, negligible next to the training step."""
        from jax.experimental import multihost_utils

        local = next(len(a) for a in arrs if a is not None)
        info = multihost_utils.process_allgather(
            np.asarray([n, local], np.int64))
        info = np.asarray(info).reshape(self._nproc, 2)
        n_tot = int(info[:, 0].sum())
        target = int(info[:, 1].max())
        if local < target:
            arrs = tuple(_tile_pad(a, target - local) for a in arrs)
        return arrs, n_tot, target * self._nproc

    def _padded_lmask(self, y, lm, n, scale=None):
        """Label mask zero-weighting padded rows [n:] so the jitted step's
        loss averages over the n REAL examples only (exact equivalence with
        the unpadded single-device fit). Canonical implementation — and the
        full derivation of the B_pad/n pre-scaling against average_score's
        branches — lives in utils.bucketing.padded_label_mask."""
        return padded_label_mask(y, lm, n, scale=scale)

    def _exchange_runner(self):
        """The explicit-exchange step runner (parallel/grads.py), or None
        when the implicit dense path applies (both switches off). Built once
        and kept — its compression residuals must persist across fit calls."""
        if not (self.grad_compress or self.sharded_update):
            return None
        if self._nproc > 1:
            warnings.warn(
                "DL4J_TPU_GRAD_COMPRESS/DL4J_TPU_SHARDED_UPDATE are "
                "single-process only for now; multi-host fit falls back to "
                "the implicit dense exchange", stacklevel=3)
            return None
        if self._runner is None:
            from deeplearning4j_tpu.parallel.grads import DataParallelStep

            self._runner = DataParallelStep(
                self.model, self.mesh, compress=self.grad_compress,
                sharded_update=self.sharded_update,
                threshold=self.compress_threshold)
        return self._runner

    def _restore_runner_residuals(self, runner) -> None:
        """Hand checkpointed compression residuals (stashed on the model by
        resume/restore) to the exchange runner — must happen after begin(),
        which otherwise seeds zeros."""
        pending = getattr(self.model, "_pending_residuals", None)
        if pending:
            runner.load_residuals(pending)
            self.model._pending_residuals = None

    def fit(self, data, epochs: int = 1, batch_size: Optional[int] = None,
            resume_from=None):
        """Data-parallel fit: identical semantics to ``model.fit`` on a batch
        ``batch_size`` large, executed across all chips.

        ``resume_from``: a CheckpointListener directory — restore the newest
        VALID checkpoint (including the flat-opt snapshot and compression
        residuals a DP checkpoint carries) and continue; ``epochs`` becomes
        the TOTAL budget and the interrupted epoch skips its consumed
        batches (same contract as model.fit; docs/ROBUSTNESS.md)."""
        if self.model.params is None:
            self.model.init()
        resume_skip = 0
        if resume_from is not None:
            from deeplearning4j_tpu.train import resilience

            if resilience.resume(self.model, resume_from) is not None:
                resume_skip = int(getattr(self.model, "batch_in_epoch", 0))
                epochs = max(epochs - self.model.epoch, 0)
                # rebuild the exchange plan around the restored state (the
                # restored LR scale may have produced new updater objects)
                self._runner = None
        self._replicate_model()
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        if isinstance(self.model, ComputationGraph):
            return self._fit_graph(data, epochs, batch_size, resume_skip)
        model = self.model
        guard = getattr(model, "divergence_guard", None)
        runner = self._exchange_runner()
        if runner is not None:
            runner.begin()
            self._restore_runner_residuals(runner)
        try:
            for _ in range(epochs):
                skip_n, resume_skip = resume_skip, 0
                model.batch_in_epoch = skip_n
                for l in model.listeners:
                    l.on_epoch_start(model, model.epoch)
                source = data() if callable(data) else data
                batch_iter = _iter_batches(source, batch_size)
                for _ in range(skip_n):
                    # resume: skip the interrupted epoch's consumed batches
                    # (the restored RNG key is already past them)
                    if next(batch_iter, None) is None:
                        break
                for batch in batch_iter:
                    # pad so the batch shards exactly (the reference
                    # round-robins whole DataSets to workers; here the split
                    # must be even), then zero-weight the padded rows in the
                    # loss; ew excludes them from batch-coupled statistics
                    # (BatchNorm)
                    (x, y, fm, lm), n = self._pad_to_shardable(
                        batch, record=True)
                    if self._nproc > 1:
                        (x, y, fm, lm), n_tot, gB = self._even_multihost(
                            (x, y, fm, lm), n)
                        # global rescale: every real row weighs gB/n_tot so
                        # the loss equals the single-process mean over n_tot
                        # rows even when hosts contribute different row counts
                        lm = (self._padded_lmask(y, lm, n, scale=gB / n_tot)
                              if n_tot != gB or lm is not None else lm)
                        padded = n_tot != gB
                    else:
                        lm = self._padded_lmask(y, lm, n)
                        padded = len(x) != n
                    ew = None
                    if padded:
                        ew = np.zeros(len(x), np.float32)
                        ew[:n] = 1.0
                    args = (self._shard(x), self._shard(y), self._shard(fm),
                            self._shard(lm))
                    with obs.span("dp.fit_batch"):
                        score = (runner.fit_batch(*args, ew=self._shard(ew))
                                 if runner is not None
                                 else model._fit_batch(*args, ew=self._shard(ew)))
                    model.batch_in_epoch += 1
                    if guard is not None:
                        guard.observe(model, score)
                        # rollback may swap the runner's carries under us —
                        # nothing to do here: runner.reload() re-entered the
                        # exchange layout before observe() returned
                    if model.listeners:
                        score = float(score)
                        from deeplearning4j_tpu.train import resilience

                        resilience.note_score(score)
                        for l in model.listeners:
                            l.iteration_done(model, model.iteration, score, n)
                if guard is not None:
                    guard.flush(model)
                for l in model.listeners:
                    l.on_epoch_end(model, model.epoch)
                model.epoch += 1
        finally:
            if runner is not None:
                runner.finish()
            # same teardown contract as model.fit: stop in-flight
            # ProfilerListener traces even when the loop exits early
            close_listeners(model.listeners)
        return model

    def _fit_graph(self, data, epochs: int, batch_size: Optional[int],
                   resume_skip: int = 0):
        """ComputationGraph variant: shard every member of the MultiDataSet
        (features/labels/masks tuples) along the data axis."""
        model = self.model
        shard_t = lambda t: tuple(self._shard(a) for a in t) if t is not None else None
        runner = self._exchange_runner()
        if runner is not None:
            runner.begin()
            self._restore_runner_residuals(runner)
        try:
            self._fit_graph_loop(data, epochs, batch_size, shard_t, runner,
                                 resume_skip)
        finally:
            if runner is not None:
                runner.finish()
            close_listeners(model.listeners)
        return model

    def _fit_graph_loop(self, data, epochs, batch_size, shard_t, runner,
                        resume_skip: int = 0):
        model = self.model
        guard = getattr(model, "divergence_guard", None)
        for _ in range(epochs):
            skip_n, resume_skip = resume_skip, 0
            model.batch_in_epoch = skip_n
            for l in model.listeners:
                l.on_epoch_start(model, model.epoch)
            source = data() if callable(data) else data
            batch_iter = model._iter_multi(source, batch_size)
            for _ in range(skip_n):
                # resume: skip the interrupted epoch's consumed batches
                if next(batch_iter, None) is None:
                    break
            for f, lbl, fm, lm in batch_iter:
                f, n = self._pad_to_shardable(f, record=True)
                if lbl is not None:
                    lbl, _ = self._pad_to_shardable(lbl)
                if fm is not None:
                    fm, _ = self._pad_to_shardable(fm)
                if lm is not None:
                    lm, _ = self._pad_to_shardable(lm)
                scale = None
                if self._nproc > 1:
                    # equalize padded sizes + global loss rescale, jointly
                    # over every MultiDataSet member (same mechanism as the
                    # MLN path — uneven per-host batches stay exact)
                    lens = [len(t) if t is not None else 0
                            for t in (f, lbl, fm, lm)]
                    flat = sum((list(t) for t in (f, lbl, fm, lm)
                                if t is not None), [])
                    flat, n_tot, gB = self._even_multihost(tuple(flat), n)
                    flat = list(flat)
                    parts = []
                    for ln, t in zip(lens, (f, lbl, fm, lm)):
                        parts.append(tuple(flat[:ln]) if t is not None else None)
                        flat = flat[ln:]
                    f, lbl, fm, lm = parts
                    if n_tot != gB:
                        scale = gB / n_tot
                    padded = n_tot != gB
                else:
                    padded = len(f[0]) != n
                if lbl is not None and (padded or lm is not None):
                    # zero-weight padded rows in every output's loss
                    lms = lm if lm is not None else (None,) * len(lbl)
                    lm = tuple(
                        self._padded_lmask(yi, lmi, n, scale=scale)
                        for yi, lmi in zip(lbl, lms)
                    )
                    if all(m is None for m in lm):
                        lm = None
                ew = None
                total = len(f[0])
                if padded:
                    # exclude padded rows from batch-coupled statistics
                    # (BatchNorm vertices) — same channel as the MLN path
                    ew = np.zeros(total, np.float32)
                    ew[:n] = 1.0
                sharded = (shard_t(f), shard_t(lbl), shard_t(fm), shard_t(lm))
                with obs.span("dp.fit_batch"):
                    score = (runner.fit_batch_graph(sharded, ew=self._shard(ew))
                             if runner is not None
                             else model.fit_batch(sharded, ew=self._shard(ew)))
                model.batch_in_epoch += 1
                if guard is not None:
                    guard.observe(model, score)
                if model.listeners:
                    score = float(score)
                    from deeplearning4j_tpu.train import resilience

                    resilience.note_score(score)
                    for l in model.listeners:
                        l.iteration_done(model, model.iteration, score, n)
            if guard is not None:
                guard.flush(model)
            for l in model.listeners:
                l.on_epoch_end(model, model.epoch)
            model.epoch += 1
        return model

    def output(self, x):
        """Sharded batched inference across the mesh (uneven batches are
        padded for the sharded call and trimmed from the result)."""
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        if isinstance(x, (tuple, list)):
            xs, n = self._pad_to_shardable(tuple(np.asarray(a) for a in x))
            if isinstance(self.model, ComputationGraph):
                out = self.model.output(*[self._shard(a) for a in xs])
            else:
                out = self.model.output(self._shard(xs[0]))
            trim = lambda o: o[:n]
            return jax.tree_util.tree_map(trim, out)
        (xp,), n = self._pad_to_shardable((np.asarray(x),))
        out = self.model.output(self._shard(xp))
        return jax.tree_util.tree_map(lambda o: o[:n], out)
