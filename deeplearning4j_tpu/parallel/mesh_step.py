"""The named-mesh step: first-class (data × tensor × stage) training.

The MULTICHIP dryrun proved a (d2,t2,s2) mesh runs; this module
productionizes it (ISSUE 13). ``MeshTrainer`` drives ONE jitted step
program (``nn/step_program.py``) over a named ``parallel/mesh.py`` mesh:

- **data** axis: the global batch shards over it (pure GSPMD data
  parallelism — XLA inserts the gradient all-reduce during compilation).
- **model** axis: Megatron tensor parallelism via the
  ``parallel/tp.py`` PartitionSpec rules (column/row-parallel projections;
  collectives inserted by GSPMD).
- **pipe** axis: inside the unified step the stage axis carries the
  **sharded weight update** (arXiv 2004.13336): optimizer moments — and
  with them the update math — shard over every spare mesh axis, so each
  device updates only ``1/(d·s)`` of each replicated parameter (GSPMD turns
  the gradient all-reduce into reduce-scatter + all-gather around the
  sharded update). Dedicated stage-COMPUTE composition (the micro-batch
  ring schedule) remains ``parallel/gpipe.py``, which instantiates the same
  step-program abstraction.

With no spec given the trainer reads the mesh shape ``(d, t, s)`` from
``DL4J_TPU_MESH_DATA`` / ``_MODEL`` / ``_PIPE``. Compressed gradient
exchange (PR 3) composes on the pure-data mesh via the explicit shard_map
exchange (``compress=True``); see docs/PARALLELISM.md for why the compressed DCN
tier and the in-jit GSPMD tiers are mutually exclusive per axis.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.nn.step_program import StepProgram, mesh_shape_from_env
from deeplearning4j_tpu.parallel.context import use_mesh
from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
from deeplearning4j_tpu.parallel.tp import tp_param_shardings

__all__ = ["MeshSlice", "MeshTrainer", "shard_update_spec"]


class MeshSlice:
    """One elastic member's device mesh in the elastic-of-slices
    composition (``train/elastic.py``): the member process IS a whole
    ``(d, t, s)`` slice, membership events happen per slice, and the
    member's local compute (the vshard backward pass) runs GSPMD-sharded
    over the slice's devices — batch over ``data``, params/state
    replicated, XLA inserting the in-slice collectives. The fleet-level
    exchange above stays explicit store payloads; preempting the slice
    kills this one process.

    ``spec`` is ``"d[,t[,s]]"`` (e.g. ``"2"``, ``"2,1,1"``). Bit-exactness
    of elastic runs holds across member COUNT at a fixed slice shape — the
    in-slice reduction order is the mesh's, so reference and chaos runs
    must use the same spec.
    """

    def __init__(self, spec, devices=None):
        d, t, s = self.parse_spec(spec)
        self.spec = MeshSpec(data=d, model=t, pipe=s)
        self.mesh = make_mesh(self.spec, list(devices)
                              if devices is not None else jax.devices())
        self.data = int(self.mesh.shape["data"])

    @staticmethod
    def parse_spec(spec) -> Tuple[int, int, int]:
        if isinstance(spec, (tuple, list)):
            parts = [int(v) for v in spec]
        else:
            parts = [int(v) for v in str(spec).split(",") if v.strip()]
        if not parts or len(parts) > 3 or any(v < 1 for v in parts):
            raise ValueError(
                f"slice spec {spec!r}: want 1-3 positive ints 'd[,t[,s]]'")
        return tuple(parts + [1] * (3 - len(parts)))  # type: ignore

    def round_rows(self, rows: int) -> int:
        """Smallest multiple of the data-axis size >= ``rows`` (vshard
        micro-batches must divide evenly over the batch sharding)."""
        return -(-int(rows) // self.data) * self.data

    def shard_batch(self, arr):
        """Place a leading-batch-dim array sharded over ``data``."""
        if arr is None:
            return None
        spec = P("data", *([None] * (np.ndim(arr) - 1)))
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def replicate(self, tree):
        """Place a pytree fully replicated on the slice."""
        repl = NamedSharding(self.mesh, P())
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, repl), tree)

    def run(self, fn, *args, **kwargs):
        """Call ``fn`` under this slice's mesh context (GSPMD partitions
        the jitted computation by the inputs' shardings)."""
        with use_mesh(self.mesh):
            return fn(*args, **kwargs)


def shard_update_spec(spec: P, shape: Tuple[int, ...], mesh: Mesh,
                      axes: Tuple[str, ...] = ("data", "pipe")) -> P:
    """Extend a (possibly empty) TP PartitionSpec with the cross-replica
    weight-update sharding of arXiv 2004.13336: the first dimension the TP
    rules left unsharded and whose size divides evenly shards over the spare
    mesh axes — jointly when possible (``P(("data","pipe"))``), then over
    each alone, else the leaf stays as the TP rules had it. Memory math
    (docs/PARALLELISM.md): adam moments drop from 2·N·4 bytes per device to
    ``2·N·4/(d·s)``; GSPMD rewrites the gradient all-reduce into
    reduce-scatter + sharded update + all-gather, which on a ring moves the
    same bytes as the all-reduce it replaces."""
    if not shape:
        return spec
    dims = list(spec) + [None] * (len(shape) - len(spec))
    live = [a for a in axes if mesh.shape.get(a, 1) > 1]
    for combo in (tuple(live),) + tuple((a,) for a in live):
        if not combo:
            continue
        n = int(np.prod([mesh.shape[a] for a in combo]))
        if n <= 1:
            continue
        for i, d in enumerate(dims):
            if d is None and shape[i] % n == 0 and shape[i] >= n:
                dims[i] = combo if len(combo) > 1 else combo[0]
                return P(*dims)
    return spec


class MeshTrainer:
    """Train a MultiLayerNetwork on a named (data × model × pipe) mesh with
    ONE step program: params per TP rules, batch over ``data``, optimizer
    state and the weight update sharded over every spare axis.

    ``spec=None`` resolves the mesh shape from the ``DL4J_TPU_MESH_*``
    knobs — unset knobs mean pure data parallelism over all devices.

    ``compress=True`` routes through the explicit shard_map exchange
    (``parallel/grads.py``) with PR 3 gradient compression — only legal on
    a pure-data mesh: the compressed wire format packs per-replica flat
    shards, which has no tensor/stage decomposition.
    """

    def __init__(self, model, spec: Optional[MeshSpec] = None, *,
                 devices=None, compress: bool = False):
        self.model = model
        devices = list(devices) if devices is not None else jax.devices()
        if spec is None:
            d, t, s = mesh_shape_from_env(len(devices))
            spec = MeshSpec(data=d, model=t, pipe=s)
        self.spec = spec
        self.mesh = make_mesh(spec, devices)
        self.shape = tuple(spec.resolve(len(devices)))  # (d, t, s_seq, p)
        if model.params is None:
            model.init()
        if compress:
            d, t, _, p = self.shape
            if t > 1 or p > 1:
                raise ValueError(
                    "compressed exchange needs a pure data mesh (t=s=1): "
                    "the packed wire format has no tensor/stage "
                    f"decomposition — got (d={d}, t={t}, s={p})")
            from deeplearning4j_tpu.parallel.grads import DataParallelStep

            self._dp = DataParallelStep(model, self.mesh, compress=True)
        else:
            self._dp = None
            self._param_shardings = tp_param_shardings(model, self.mesh)
            self._opt_shardings = self._make_opt_shardings()
            self._place()
        self._step: Optional[StepProgram] = None

    # -- placement ---------------------------------------------------------
    def _extend(self, spec: P, a) -> NamedSharding:
        return NamedSharding(
            self.mesh, shard_update_spec(spec, np.shape(a), self.mesh))

    def _make_opt_shardings(self):
        """Optimizer-state shardings: moment trees mirror their params' TP
        spec, extended along the spare (data/pipe) axes; structure-mismatch
        slots (scalar counters, stateless updaters) extend from
        replicated."""
        m = self.model
        out = []
        for opt_layer, shard_layer in zip(m.opt_state, self._param_shardings):
            if not isinstance(opt_layer, dict):
                out.append(jax.tree_util.tree_map(
                    lambda a: self._extend(P(), a), opt_layer))
                continue
            placed = {}
            for slot, tree in opt_layer.items():
                try:
                    placed[slot] = jax.tree_util.tree_map(
                        lambda a, s: self._extend(s.spec, a),
                        tree, shard_layer)
                except ValueError:
                    placed[slot] = jax.tree_util.tree_map(
                        lambda a: self._extend(P(), a), tree)
            out.append(placed)
        return tuple(out)

    def _place(self):
        m = self.mesh
        model = self.model
        model.params = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, s),
            model.params, self._param_shardings,
            is_leaf=lambda x: isinstance(x, jax.Array))
        repl = NamedSharding(m, P())
        model.state = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, repl), model.state)
        model.opt_state = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, s),
            model.opt_state, self._opt_shardings)
        # cached step/output executables were traced without the mesh
        model._step_fn = model._tbptt_step_fn = model._output_fn = None

    # -- the one jitted program --------------------------------------------
    def _constrain(self, tree, stree):
        return jax.tree_util.tree_map(
            lambda a, s: jax.lax.with_sharding_constraint(a, s), tree, stree)

    def _build_step(self) -> StepProgram:
        body = self.model._step_body(False)
        p_shard = self._param_shardings
        o_shard = self._opt_shardings

        def wrap_body(step):
            def mesh_step(params, opt_state, state, it, rng, x, y, fm, lm,
                          carries, ex_weight=None):
                p, o, s, c, loss = step(params, opt_state, state, it, rng,
                                        x, y, fm, lm, carries,
                                        ex_weight=ex_weight)
                # pin the 2004.13336 layout: new moments stay sharded over
                # every spare axis (GSPMD reduce-scatters the grads into the
                # sharded update), new params land back on the TP layout
                # (the all-gather half) — outputs then match the donated
                # inputs' shardings, so steady-state dispatch never re-lands
                # buffers and never recompiles
                p = self._constrain(p, p_shard)
                o = self._constrain(o, o_shard)
                return p, o, s, c, loss

            return mesh_step

        return StepProgram(body, "mesh.step", model=self.model,
                           wrap_body=wrap_body, hits_site="mesh.fit")

    def _get_step(self) -> StepProgram:
        if self._step is None:
            self._step = self._build_step()
        return self._step

    # -- dispatch ----------------------------------------------------------
    def _shard_batch(self, arr):
        if arr is None:
            return None
        from deeplearning4j_tpu.nn.model import _cast_input

        arr = _cast_input(arr, self.model.dtype)
        d = self.mesh.shape["data"]
        if arr.shape[0] % d:
            raise ValueError(
                f"batch rows {arr.shape[0]} must divide the data axis ({d})")
        spec = P("data", *([None] * (arr.ndim - 1)))
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def fit_batch(self, x, y, fm=None, lm=None, ew=None):
        """One mesh step; returns the loss (device scalar)."""
        if self._dp is not None:
            return self._dp.fit_batch(x, y, fm, lm, ew=ew)
        from deeplearning4j_tpu.nn.model import _cast_labels

        model = self.model
        step = self._get_step()
        x = self._shard_batch(x)
        y = self._shard_batch(_cast_labels(y, model.dtype))
        fm = self._shard_batch(fm)
        lm = self._shard_batch(lm)
        ew = self._shard_batch(ew)
        with use_mesh(self.mesh):     # dispatch() opens the mesh.step span
            (model.params, model.opt_state, model.state, _,
             loss) = step.dispatch(
                model.params, model.opt_state, model.state,
                jnp.asarray(model.iteration, jnp.int32), model._next_rng(),
                x, y, fm, lm, (), ex_weight=ew)
        model.iteration += 1
        return loss

    def fit(self, data, epochs: int = 1, batch_size: Optional[int] = None):
        from deeplearning4j_tpu.nn.model import _iter_batches

        model = self.model
        for _ in range(epochs):
            source = data() if callable(data) else data
            for xb, yb, fmb, lmb in _iter_batches(source, batch_size):
                score = self.fit_batch(xb, yb, fmb, lmb)
                if model.listeners:
                    # listeners consume host floats (same contract as
                    # model.fit: sync only when someone reads the score)
                    score = float(score)  # graftlint: disable=host-sync
                    for l in model.listeners:
                        l.iteration_done(model, model.iteration, score,
                                         len(xb))
            model.epoch += 1
        return model

    def output(self, x):
        with use_mesh(self.mesh):
            return self.model.output(self._shard_batch(x))

    def finish(self):
        """Leave mesh layout: gather params/opt/state back to replicated so
        the model serializes and runs single-chip as usual. (TP/update
        shardings are a placement, not a format — one device_put undoes
        them.) The compressed-exchange variant delegates to the shard_map
        runner's own finish."""
        if self._dp is not None:
            self._dp.finish()
            return
        model = self.model
        repl = NamedSharding(self.mesh, P())
        for attr in ("params", "opt_state", "state"):
            setattr(model, attr, jax.tree_util.tree_map(
                lambda a: jax.device_put(a, repl), getattr(model, attr)))
        model._step_fn = model._tbptt_step_fn = model._output_fn = None
        self._step = None
