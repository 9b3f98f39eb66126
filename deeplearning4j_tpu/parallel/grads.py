"""Explicit data-parallel gradient exchange: compressed collectives and
cross-replica sharded weight updates.

The default ParallelWrapper path feeds a globally-sharded batch to the
single-chip jitted step and lets XLA insert a dense gradient all-reduce with
the optimizer update replicated on every chip. This module is the explicit
alternative — a ``shard_map`` over the ``data`` mesh axis wrapping the SAME
step body (``nn/model.py`` / ``nn/graph.py`` expose a ``grad_exchange=``
hook) — enabling two reference-capability optimizations the implicit path
cannot express:

1. **Threshold compression** (DL4J SharedTrainingMaster / ND4J
   thresholdEncode parity, ``parallel/compress.py``): each replica ternary-
   quantizes its local gradient against a threshold, carries the remainder in
   a per-replica residual (error feedback), and replicas exchange the 2-bit
   packed encodings by all-gather — 16x fewer wire bytes than a dense f32
   all-reduce. The residual rides in the DONATED step carry (tupled with the
   optimizer state), so compression stays inside the one compiled executable.

2. **Cross-replica sharded weight update** ("Automatic Cross-Replica
   Sharding of Weight Update in Data-Parallel Training", PAPERS.md):
   gradients are reduce-scattered instead of all-reduced, each replica
   applies the optimizer update to its 1/R shard only (optimizer state lives
   sharded over ``data`` as ``[R, m]`` stacks of flat shards), and updated
   params are all-gathered. The redundant R-way replicated update becomes
   1/R of the math and memory.

Both are off by default: on a single ICI-connected slice
the dense fused psum is already near-optimal; these switches matter when the
exchange crosses DCN (multi-slice / multi-host pods) or optimizer state
dominates HBM.

Per-layer plan: a layer/vertex is exchanged flat (modes above) only when its
gradient leaves share one floating dtype and it declares no gradient
normalization (gn needs the full global gradient); otherwise it falls back
to an exact per-leaf ``pmean`` + replicated update inside the same step.
Everything is deterministic: fixed-order reductions, no host round-trips.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.analysis import retrace_guard
from deeplearning4j_tpu.parallel import compress as compression
from deeplearning4j_tpu.train.updaters import apply_gradient_normalization
from deeplearning4j_tpu.utils import bucketing

__all__ = ["DataParallelStep", "GradExchange"]


# ---------------------------------------------------------------------------
# Per-layer exchange plan
# ---------------------------------------------------------------------------


@dataclass
class _Entry:
    """Static exchange metadata for one layer/vertex (captured by the traced
    closures; every field is a python constant, so it never retraces)."""

    key: Any
    treedef: Any                      # params-entry pytree structure
    shapes: Tuple[Tuple[int, ...], ...]
    n: int                            # total elements across leaves
    m: int                            # per-replica shard length
    n_pad: int                        # R * m
    dtype: Any                        # uniform leaf dtype (flat modes)
    mode: str                         # "sharded" | "dense"
    compress: bool
    updater: Any
    cfg: Any                          # layer/vertex config (gn + constraints)


def _flat(tree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    if len(leaves) == 1:
        return leaves[0].reshape(-1)
    return jnp.concatenate([l.reshape(-1) for l in leaves])


def _pad_flat(flat, n_pad: int):
    n = flat.shape[0]
    if n_pad == n:
        return flat
    return jnp.concatenate([flat, jnp.zeros((n_pad - n,), flat.dtype)])


def _unflat(flat, entry: _Entry):
    out, off = [], 0
    for shp in entry.shapes:
        k = int(np.prod(shp)) if shp else 1
        out.append(flat[off:off + k].reshape(shp))
        off += k
    return jax.tree_util.tree_unflatten(entry.treedef, out)


def _apply_entry_constraints(cfg, p_new):
    if getattr(cfg, "constraints", None):
        from deeplearning4j_tpu.nn.constraints import apply_constraints

        p_new = apply_constraints(cfg, p_new)
    return p_new


# ---------------------------------------------------------------------------
# The exchange (runs INSIDE the shard_map-traced step body)
# ---------------------------------------------------------------------------


class GradExchange:
    """Collective gradient exchange + parameter update for one model.

    Instances are handed to the step factories (``_step_body(...,
    grad_exchange=...)``); every method below executes inside the shard_map
    trace, where arrays are the per-replica LOCAL views and collectives over
    ``axis`` are explicit.
    """

    def __init__(self, entries: Dict[Any, _Entry], order, container: str,
                 axis: str, n_shards: int, threshold: float):
        self.entries = entries
        self.order = list(order)
        self.container = container            # "tuple" (MLN) | "dict" (CG)
        self.axis = axis
        self.n_shards = n_shards
        self.threshold = float(threshold)

    # -- replica-mean of the scalar loss and the mutable layer state -------
    def mean_loss(self, loss):
        return lax.pmean(loss, self.axis)

    def mean_state(self, state):
        """Average batch-derived layer state (BatchNorm running stats) over
        replicas; non-float leaves (counters, ()) pass through untouched."""

        def avg(a):
            if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating):
                return lax.pmean(a, self.axis)
            return a

        return jax.tree_util.tree_map(avg, state)

    # -- per-entry update ---------------------------------------------------
    def _dense_entry(self, e: _Entry, g, p, o, it):
        """Exact fallback: per-leaf pmean, gradient normalization on the
        global gradient, replicated structured update — bit-for-bit the
        implicit path's math, minus XLA's fusion freedom."""
        g = jax.tree_util.tree_map(lambda a: lax.pmean(a, self.axis), g)
        gn = getattr(e.cfg, "gradient_normalization", None)
        if gn:
            g = apply_gradient_normalization(
                gn, getattr(e.cfg, "gradient_normalization_threshold", 1.0), g)
        upd, o_new = e.updater.update(g, o, p, it)
        p_new = jax.tree_util.tree_map(lambda a, d: a - d, p, upd)
        return _apply_entry_constraints(e.cfg, p_new), o_new

    def _flat_entry(self, e: _Entry, g, p, o, r_loc, it):
        """Flat exchange: compressed and/or shard-updated."""
        thr = self.threshold
        R = self.n_shards
        g_mean_full = None
        r_new = r_loc
        if e.compress:
            # residual + encode run in f32 regardless of the param dtype so
            # sub-threshold error feedback never rounds away in bf16
            with jax.named_scope("compress"):
                gflat32 = _pad_flat(_flat(g).astype(jnp.float32), e.n_pad)
                packed, r = compression.encode_packed(
                    gflat32, r_loc.reshape(-1), thr)
                gathered = lax.all_gather(packed, self.axis)   # [R, nbytes]
                g_mean_full = compression.decode_gathered(
                    gathered, e.n_pad, thr, jnp.float32) / R
                r_new = r[None]                                # local [1, n_pad]
        if e.mode == "sharded":
            idx = lax.axis_index(self.axis)
            if e.compress:
                g_shard = lax.dynamic_slice(
                    g_mean_full, (idx * e.m,), (e.m,)).astype(e.dtype)
            else:
                g_shard = lax.psum_scatter(
                    _pad_flat(_flat(g), e.n_pad), self.axis,
                    scatter_dimension=0, tiled=True) / R
            p_flat = _pad_flat(_flat(p), e.n_pad)
            p_shard = lax.dynamic_slice(p_flat, (idx * e.m,), (e.m,))
            o_loc = jax.tree_util.tree_map(lambda a: a[0], o)  # [1,m] -> [m]
            upd, o_new_loc = e.updater.update(g_shard, o_loc, p_shard, it)
            p_new_flat = lax.all_gather(
                p_shard - upd, self.axis, tiled=True)          # [n_pad]
            o_new = jax.tree_util.tree_map(lambda a: a[None], o_new_loc)
            p_new = _unflat(p_new_flat[:e.n], e)
        else:
            # compressed, replicated update: every replica decodes the same
            # fixed-order sum, so the updates are identical without any
            # further collective
            g_tree = _unflat(g_mean_full[:e.n].astype(e.dtype), e)
            upd, o_new = e.updater.update(g_tree, o, p, it)
            p_new = jax.tree_util.tree_map(lambda a, d: a - d, p, upd)
        return _apply_entry_constraints(e.cfg, p_new), o_new, r_new

    # -- whole-model update -------------------------------------------------
    def update(self, grads, params, opt_state, residuals, it):
        """Replaces the step body's per-layer update loop. Returns
        ``(new_params, new_opt, new_residuals)`` in the model's container
        type (tuple of layers / dict of vertices)."""
        # a scope, not a span: this method runs inside the shard_map trace,
        # and the scope puts the collectives' device time under dp.step/exchange
        with jax.named_scope("exchange"):
            return self._update_traced(grads, params, opt_state, residuals, it)

    def _update_traced(self, grads, params, opt_state, residuals, it):
        new_p: Dict[Any, Any] = {}
        new_o: Dict[Any, Any] = {}
        new_r: Dict[Any, Any] = {}
        for key in self.order:
            e = self.entries.get(key)
            g = grads[key]
            if e is None or not jax.tree_util.tree_leaves(g):
                new_p[key] = params[key]
                new_o[key] = opt_state[key]
                new_r[key] = residuals[key]
                continue
            if e.mode == "dense":
                new_p[key], new_o[key] = self._dense_entry(
                    e, g, params[key], opt_state[key], it)
                new_r[key] = residuals[key]
            else:
                new_p[key], new_o[key], new_r[key] = self._flat_entry(
                    e, g, params[key], opt_state[key], residuals[key], it)
        if self.container == "tuple":
            keys = self.order
            return (tuple(new_p[k] for k in keys),
                    tuple(new_o[k] for k in keys),
                    tuple(new_r[k] for k in keys))
        return new_p, new_o, new_r


# ---------------------------------------------------------------------------
# Host-side runner
# ---------------------------------------------------------------------------


class DataParallelStep:
    """Explicit-exchange train-step runner for ParallelWrapper.

    Wraps the model's step body in ``shard_map`` over the mesh's ``data``
    axis and jits the result with params/opt-carry/state donated — one
    compiled executable per batch bucket, same as the single-chip path. The
    optimizer carry is ``(opt_state, residuals)``: sharded-mode entries hold
    flat ``[R, m]`` optimizer stats placed with ``P("data")`` (each replica
    owns one row), compressed entries additionally carry an f32 ``[R, n_pad]``
    error-feedback residual. ``begin()`` converts the model's structured
    optimizer state into this layout; ``finish()`` converts it back, so
    outside an active fit the model stays serializable/usable as usual.
    Residuals persist across ``begin``/``finish`` — dropping them would lose
    pending sub-threshold gradient mass.
    """

    COMM_SITE = "dp.grads"

    def __init__(self, model, mesh, *, compress: bool = False,
                 sharded_update: bool = False, threshold: float = 1e-3):
        if jax.process_count() > 1:
            raise NotImplementedError(
                "DataParallelStep supports single-process meshes only; "
                "for multi-process data parallelism use the elastic runtime "
                "(train/elastic.py ElasticTrainer over parallel/elastic.py "
                "membership), which shards the optimizer update and "
                "compresses payloads across hosts")
        if model.params is None:
            model.init()
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        self.model = model
        self.mesh = mesh
        self.is_graph = isinstance(model, ComputationGraph)
        self.R = mesh.shape["data"]
        self.compress = bool(compress)
        self.sharded_update = bool(sharded_update)
        self.threshold = float(threshold)
        self._sharded = NamedSharding(mesh, P("data"))
        self._repl = NamedSharding(mesh, P())
        self._build_plan()
        self.exchange = GradExchange(
            self._entries, self._order,
            "dict" if self.is_graph else "tuple",
            "data", self.R, self.threshold)
        self._step = self._build_step()
        self._opt_flat = None
        self._residual = None
        self._active = False
        self._record_comm()
        # checkpoint/resume integration (train/resilience.py): the save path
        # finds the active runner here to snapshot flat opt state + residuals
        model._dp_runner = self

    # -- plan ---------------------------------------------------------------
    def _build_plan(self):
        model = self.model
        if self.is_graph:
            order = list(model.topo_order)
            updaters = model._updaters
            cfg_of = {k: model.rt[k].config for k in order}
            params_of = model.params
        else:
            order = list(range(len(model.layers)))
            updaters = {i: u for i, u in enumerate(model._updaters)}
            cfg_of = {i: l for i, l in enumerate(model.layers)}
            params_of = {i: p for i, p in enumerate(model.params)}
        entries: Dict[Any, _Entry] = {}
        for key in order:
            p = params_of[key]
            leaves, treedef = jax.tree_util.tree_flatten(p)
            if not leaves:
                continue
            cfg = cfg_of[key]
            n = sum(int(np.prod(l.shape)) for l in leaves)
            dtypes = {jnp.dtype(l.dtype) for l in leaves}
            uniform_float = (len(dtypes) == 1 and
                             jnp.issubdtype(next(iter(dtypes)), jnp.floating))
            gn = getattr(cfg, "gradient_normalization", None)
            eligible = uniform_float and not gn
            if eligible and self.sharded_update:
                mode = "sharded"
            elif eligible and self.compress:
                mode = "replicated"     # compressed exchange, replicated update
            else:
                mode = "dense"          # exact pmean fallback (gn, mixed dtypes)
            m = -(-n // self.R)
            entries[key] = _Entry(
                key=key, treedef=treedef,
                shapes=tuple(tuple(l.shape) for l in leaves),
                n=n, m=m, n_pad=m * self.R,
                dtype=(next(iter(dtypes)) if uniform_float else None),
                mode=mode, compress=(self.compress and eligible),
                updater=updaters[key], cfg=cfg)
        self._entries = entries
        self._order = order

    def comm_stats(self) -> dict:
        """Static per-step byte accounting for the gradient exchange.

        ``dense_bytes``: what a dense all-reduce of every exchanged gradient
        would move (per replica, payload bytes). ``wire_bytes``: what THIS
        configuration moves for gradients. ``param_bytes``: the updated-param
        all-gather added by sharded mode — reported separately so compression
        ratios stay honest about the extra parameter traffic."""
        dense = wire = param = 0
        for e in self._entries.values():
            itemsize = jnp.dtype(e.dtype).itemsize if e.dtype is not None else 4
            nbytes = e.n * itemsize
            dense += nbytes
            if e.compress:
                wire += compression.packed_nbytes(e.n_pad)
            else:
                wire += nbytes
            if e.mode == "sharded":
                param += nbytes
        return {"dense_bytes": dense, "wire_bytes": wire,
                "param_bytes": param,
                "n_entries": len(self._entries),
                "compressed_entries": sum(e.compress
                                          for e in self._entries.values()),
                "sharded_entries": sum(e.mode == "sharded"
                                       for e in self._entries.values())}

    def _record_comm(self):
        s = self.comm_stats()
        bucketing.telemetry().record_comm(
            self.COMM_SITE, s["dense_bytes"], s["wire_bytes"],
            s["param_bytes"])

    # -- step construction --------------------------------------------------
    def _opt_spec(self, e: Optional[_Entry]):
        return P("data") if (e is not None and e.mode == "sharded") else P()

    def _build_step(self):
        if self.is_graph:
            body = self.model._make_step_body(False, grad_exchange=self.exchange)
        else:
            body = self.model._step_body(False, grad_exchange=self.exchange)

        def call(params, opt_carry, state, it, rng, a, b, fm, lm, carries, ew):
            return body(params, opt_carry, state, it, rng, a, b, fm, lm,
                        carries, ex_weight=ew)

        specs = [self._opt_spec(self._entries.get(k)) for k in self._order]
        if self.is_graph:
            opt_spec: Any = dict(zip(self._order, specs))
        else:
            opt_spec = tuple(specs)
        dp, repl = P("data"), P()
        in_specs = (repl, (opt_spec, dp), repl, repl, repl,
                    dp, dp, dp, dp, repl, dp)
        out_specs = (repl, (opt_spec, dp), repl, repl, repl)
        from deeplearning4j_tpu.nn.step_program import StepProgram

        # the grad-exchange step is its own AOT site: the compressed/sharded
        # exchange traces a different executable than the single-chip step,
        # and warmup (aot.warm_dp) / bundle restore must target it. NOT
        # registered under the model's step sites — rebuild_step()/reload()
        # call here again and replace the wrapper wholesale. The guard still
        # watches the model's step site (traces fire inside the body) against
        # dp.fit bucket traffic, +1 for the exchange's own executable.
        return StepProgram(
            call, "dp.step", model=self.model,
            wrap_body=lambda b: shard_map(
                b, mesh=self.mesh, in_specs=in_specs,
                out_specs=out_specs, check_vma=False),
            guard_site="cg.step" if self.is_graph else "mln.step",
            hits_site="dp.fit", extra_allowed=1)

    # -- optimizer-state layout conversion ----------------------------------
    def _to_flat_opt(self, e: _Entry, structured):
        """Structured per-layer opt state -> flat ``[R, m]`` stats, sharded
        over ``data``. Updater states are built leaf-parallel to the params
        (``_zeros_like_tree``), so ``tree_leaves`` yields outer-stat-major
        groups of ``len(e.shapes)`` leaves each, concatenated in the same
        order ``_flat`` uses for params/grads."""
        leaves = jax.tree_util.tree_leaves(structured)
        n_inner = len(e.shapes)
        if leaves and len(leaves) % n_inner != 0:
            raise ValueError(
                f"opt state for {e.key} has {len(leaves)} leaves, not a "
                f"multiple of the {n_inner} param leaves — cannot flatten")
        stats = []
        for i in range(0, len(leaves), n_inner):
            chunk = leaves[i:i + n_inner]
            flat = _pad_flat(
                jnp.concatenate([jnp.ravel(l) for l in chunk])
                if len(chunk) > 1 else jnp.ravel(chunk[0]), e.n_pad)
            stats.append(jax.device_put(
                flat.reshape(self.R, e.m), self._sharded))
        template = e.updater.init(jnp.zeros((e.n_pad,), e.dtype))
        tdef = jax.tree_util.tree_structure(template)
        return jax.tree_util.tree_unflatten(tdef, stats)

    def _from_flat_opt(self, e: _Entry, flat_entry):
        """Inverse of ``_to_flat_opt``: rebuild the structured, replicated
        per-layer opt state from the ``[R, m]`` stats."""
        leaves = jax.tree_util.tree_leaves(flat_entry)
        subtrees = []
        for leaf in leaves:
            flat = jax.device_put(leaf, self._repl).reshape(-1)[:e.n]
            subtrees.append(_unflat(flat, e))
        template = e.updater.init(jnp.zeros((e.n_pad,), e.dtype))
        tdef = jax.tree_util.tree_structure(template)
        return jax.tree_util.tree_unflatten(tdef, subtrees)

    def _init_residual(self):
        res: Dict[Any, Any] = {}
        for key in self._order:
            e = self._entries.get(key)
            if e is not None and e.compress:
                res[key] = jax.device_put(
                    jnp.zeros((self.R, e.n_pad), jnp.float32), self._sharded)
            else:
                res[key] = None
        if self.is_graph:
            return res
        return tuple(res[k] for k in self._order)

    def begin(self):
        """Enter exchange layout: build the donated opt carry from the
        model's (replicated) structured optimizer state."""
        if self._active:
            return
        model = self.model
        opt: Dict[Any, Any] = {}
        for key in self._order:
            e = self._entries.get(key)
            structured = model.opt_state[key]
            if e is not None and e.mode == "sharded":
                opt[key] = self._to_flat_opt(e, structured)
            else:
                opt[key] = jax.device_put(structured, self._repl)
        self._opt_flat = (opt if self.is_graph
                          else tuple(opt[k] for k in self._order))
        if self._residual is None:
            self._residual = self._init_residual()
        # Barrier before the carry enters the donated step chain: begin() runs
        # once per fit, and a restored model's opt leaves are fresh transfers.
        jax.block_until_ready(self._opt_flat)  # graftlint: disable=host-sync
        self._active = True

    def finish(self):
        """Leave exchange layout: write the structured optimizer state back
        onto the model (residuals stay on the runner)."""
        if not self._active:
            return
        model = self.model
        flat = self._opt_flat
        out: Dict[Any, Any] = {}
        for i, key in enumerate(self._order):
            e = self._entries.get(key)
            entry = flat[key] if self.is_graph else flat[i]
            if e is not None and e.mode == "sharded":
                out[key] = self._from_flat_opt(e, entry)
            else:
                out[key] = entry
        model.opt_state = (out if self.is_graph
                           else tuple(out[k] for k in self._order))
        self._opt_flat = None
        self._active = False

    # -- checkpoint/resume integration (train/resilience.py) -----------------
    def snapshot_opt_state(self):
        """The model-structured optimizer state as of NOW, without leaving
        the exchange layout (``finish`` logic, non-mutating) — what a
        checkpoint taken mid-fit must record."""
        if not self._active:
            return self.model.opt_state
        flat = self._opt_flat
        out: Dict[Any, Any] = {}
        for i, key in enumerate(self._order):
            e = self._entries.get(key)
            entry = flat[key] if self.is_graph else flat[i]
            if e is not None and e.mode == "sharded":
                out[key] = self._from_flat_opt(e, entry)
            else:
                out[key] = entry
        return out if self.is_graph else tuple(out[k] for k in self._order)

    def export_residuals(self) -> Dict[str, np.ndarray]:
        """Host copies of the per-replica error-feedback residuals, keyed by
        ``str(entry key)`` (npz-compatible). Empty when nothing compresses."""
        if self._residual is None:
            return {}
        res = (self._residual if self.is_graph
               else dict(zip(self._order, self._residual)))
        return {str(k): np.asarray(v)  # graftlint: disable=host-sync
                for k, v in res.items() if v is not None}

    def load_residuals(self, arrays: Dict[str, np.ndarray]):
        """Re-seed the ``[R, n_pad]`` residuals from a checkpoint's host
        arrays (inverse of ``export_residuals``). Entries absent from
        ``arrays`` stay zero — dropping them would silently lose pending
        sub-threshold gradient mass, so restore runs this before fitting."""
        res: Dict[Any, Any] = {}
        for key in self._order:
            e = self._entries.get(key)
            if e is None or not e.compress:
                res[key] = None
                continue
            a = arrays.get(str(key))
            if a is None:
                res[key] = jax.device_put(
                    jnp.zeros((self.R, e.n_pad), jnp.float32), self._sharded)
            else:
                res[key] = jax.device_put(
                    jnp.asarray(a, jnp.float32).reshape(self.R, e.n_pad),
                    self._sharded)
        self._residual = res if self.is_graph else tuple(
            res[k] for k in self._order)
        # Barrier: these H2D transfers feed a donated carry; materialize them
        # before the first step can reuse the buffers (async dispatch race).
        jax.block_until_ready(self._residual)  # graftlint: disable=host-sync

    def rebuild_step(self):
        """Re-trace the step (the model's divergence-guard config is baked
        into the traced body — see model.set_divergence_guard)."""
        self._step = self._build_step()

    def reload(self):
        """Re-enter the exchange layout around externally reloaded model
        state (divergence-guard rollback: params/opt restored from a
        checkpoint, updaters rebuilt with a backed-off LR). Rebuilds the
        plan/step so the new updater objects are the ones traced, then
        re-seeds residuals from the checkpoint when it carried any."""
        self._active = False
        self._opt_flat = None
        self._build_plan()
        self.exchange = GradExchange(
            self._entries, self._order,
            "dict" if self.is_graph else "tuple",
            "data", self.R, self.threshold)
        self._step = self._build_step()
        self.begin()
        pending = getattr(self.model, "_pending_residuals", None)
        if pending:
            self.load_residuals(pending)
            self.model._pending_residuals = None

    # -- dispatch -----------------------------------------------------------
    def fit_batch(self, x, y, fm, lm, ew=None):
        """MultiLayerNetwork step (mirrors ``model._fit_batch``)."""
        from deeplearning4j_tpu.nn.model import _cast_input, _cast_labels
        from deeplearning4j_tpu.train import resilience

        if not self._active:
            self.begin()
        model = self.model
        chaos = resilience.active_chaos()
        if chaos is not None:
            chaos.maybe_preempt(model.iteration)
            chaos.maybe_slow(model.iteration)
            x = chaos.maybe_nan_batch(model.iteration, x)
        x = _cast_input(x, model.dtype)
        y = _cast_labels(y, model.dtype)
        fm = jnp.asarray(fm, model.dtype) if fm is not None else None
        lm = jnp.asarray(lm, model.dtype) if lm is not None else None
        ew = jnp.asarray(ew, model.dtype) if ew is not None else None
        # dispatch() opens the dp.step span
        (model.params, (self._opt_flat, self._residual), model.state,
         _, loss) = self._step.dispatch(
            model.params, (self._opt_flat, self._residual), model.state,
            jnp.asarray(model.iteration, jnp.int32), model._next_rng(),
            x, y, fm, lm, (), ew)
        model.iteration += 1
        return loss

    def fit_batch_graph(self, batch, ew=None):
        """ComputationGraph step (mirrors ``model.fit_batch`` on an
        already-normalized ``(f, l, fm, lm)`` tuple batch)."""
        from deeplearning4j_tpu.train import resilience

        if not self._active:
            self.begin()
        model = self.model
        f, l, fm, lm = batch
        chaos = resilience.active_chaos()
        if chaos is not None:
            chaos.maybe_preempt(model.iteration)
            chaos.maybe_slow(model.iteration)
            f = chaos.maybe_nan_batch(model.iteration, f)
        ew = jnp.asarray(ew, model.dtype) if ew is not None else None
        (model.params, (self._opt_flat, self._residual), model.state,
         _, loss) = self._step.dispatch(
            model.params, (self._opt_flat, self._residual), model.state,
            jnp.asarray(model.iteration, jnp.int32), model._next_rng(),
            model._input_dict(f), l, model._mask_dict(fm), lm, {}, ew)
        model.iteration += 1
        return loss
