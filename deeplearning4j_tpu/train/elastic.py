"""Elastic multi-host data-parallel training on the membership runtime.

``ElasticTrainer`` runs synchronous data-parallel SGD across N worker
*processes* — each its own single-process JAX instance (dense collectives
stay inside the process/slice where XLA is optimal) — exchanging explicit
gradient payloads through the ``parallel/elastic.py`` :class:`FileStore`
(the DCN stand-in; optionally ternary-compressed per PR 3). When a worker's
lease lapses mid-epoch the survivors drain to the step boundary, re-form at
the reduced world size — re-sharding the arXiv 2004.13336 optimizer-state
segments — and keep training; the preempted worker rejoins through a live
handoff or the distributed checkpoint layout (per-host shards + CRC'd
manifest, ``train/resilience.py``).

Three design decisions make elasticity *bit-exact* rather than merely
tolerant (tests/test_elastic.py asserts equality, not closeness):

- **Virtual shards.** The global batch of every step is split into ``v``
  fixed-shape padded micro-shards (``v`` frozen at bootstrap), and vshard
  ``j`` of step ``s`` draws RNG ``fold_in(base, s*v + j)``. Membership only
  decides WHICH worker computes a vshard (``j % world``), never the
  vshard's data, shape, rng, or weight — so the fixed-order payload sum is
  bitwise invariant under shrink/grow, and a killed-worker run lands on
  exactly the uninterrupted run's parameters.
- **Segmented optimizer state with a buddy mirror.** Eligible layers'
  optimizer stats live as flat per-rank segments (each worker updates 1/W
  of the vector); worker ``r`` additionally maintains rank ``(r+1) % W``'s
  segments, so a single worker's death loses nothing: the buddy serves the
  dead rank's updated params mid-step and its optimizer segments at the
  re-form handoff. Layers with gradient normalization, constraints, or
  mixed dtypes fall back to dense replicated updates (same rule as
  ``parallel/grads.py``).
- **Step-boundary reconfiguration.** Membership changes surface as
  :class:`MembershipChanged` and are handled only between steps: survivors
  re-publish state under the new generation, re-slice segments, and re-run
  the interrupted step at the reduced world — nothing is half-applied.

The CLI (``python -m deeplearning4j_tpu.train.elastic worker|launch``)
drives the synthetic workload used by tests/test_elastic.py and
tools/elastic_smoke.sh: ``launch`` supervises N local worker processes and
can relaunch killed ones (the rejoin path).
"""

from __future__ import annotations

import argparse
import io
import json
import math
import os
import subprocess
import sys
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.obs import fleet
from deeplearning4j_tpu.parallel import compress as compression
from deeplearning4j_tpu.parallel.elastic import (
    ElasticRuntime,
    FileStore,
    MembershipChanged,
    View,
)
from deeplearning4j_tpu.parallel.grads import _flat, _unflat
from deeplearning4j_tpu.parallel.netstore import open_store
from deeplearning4j_tpu.train import resilience
from deeplearning4j_tpu.train.updaters import apply_gradient_normalization
from deeplearning4j_tpu.utils import bucketing

__all__ = ["ElasticTrainer", "mirror_ranks"]


def mirror_ranks(t: int, W: int, R: int,
                 racks: Sequence[str] = ()) -> List[int]:
    """Ranks holding mirrors of rank ``t``'s optimizer segments under
    replication factor ``R`` (owner + R-1 mirrors, capped at the world
    size) with rack-aware placement: candidates in OTHER racks than the
    owner's sort first, ties broken by ring distance ``(t - r) % W`` —
    nearest predecessor first. With uniform racks and R=2 this is exactly
    the classic buddy (the mirror of ``t`` sits at ``(t-1) % W``, i.e.
    worker ``r`` mirrors rank ``(r+1) % W``), which keeps the R=2 layout —
    and with it every existing checkpoint shard and bit-exactness gate —
    unchanged. Deterministic in its inputs, so every member derives the
    same placement from the view's recorded rack labels."""
    W = int(W)
    R = min(int(R), W)
    if R <= 1 or W <= 1:
        return []
    owner_rack = racks[t] if t < len(racks) else ""
    return sorted(
        (r for r in range(W) if r != t),
        key=lambda r: ((racks[r] if r < len(racks) else "") == owner_rack,
                       (t - r) % W))[:R - 1]


# ---------------------------------------------------------------------------
# npz framing for store payloads
# ---------------------------------------------------------------------------


def _pack_arrays(arrays: Dict[str, np.ndarray]) -> bytes:
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    return buf.getvalue()


def _unpack_arrays(data: bytes) -> Dict[str, np.ndarray]:
    with np.load(io.BytesIO(data), allow_pickle=False) as z:
        return {k: z[k] for k in z.files}


def _json_to_array(value: dict) -> np.ndarray:
    return np.frombuffer(json.dumps(value).encode("utf-8"), np.uint8)


def _array_to_json(arr: np.ndarray) -> dict:
    return json.loads(arr.tobytes().decode("utf-8"))


# ---------------------------------------------------------------------------
# Exchange plan (per-layer), mirroring parallel/grads.py eligibility
# ---------------------------------------------------------------------------


@dataclass
class _Entry:
    key: int
    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    n: int
    dtype: Any
    mode: str                  # "flat" | "dense"
    updater: Any
    cfg: Any


class _JobDone(Exception):
    """Internal: the job completed while this worker was expelled; the final
    state was adopted from the ``done`` blob rank 0 leaves in the store."""


class _Prefetcher:
    """Asynchronous DCN payload fetch: polls the store for a set of keys
    from a daemon thread so the fetch overlaps with in-process compute (my
    own vshard backward passes, the dense update) instead of serializing
    behind it at the boundary wait. ``drain()`` hands finished payloads to
    the consumer; the boundary wait only blocks on whatever the overlap
    didn't already cover — that residue is the measured
    ``dl4j_elastic_boundary_stall_seconds``. Purely an ordering
    optimization: payload bytes and the fixed-order combine are untouched,
    so bit-exactness is unaffected (``DL4J_TPU_ELASTIC_ASYNC=0`` falls back
    to the synchronous fetch)."""

    def __init__(self, store, keys: Dict[Any, str], poll: float):
        self.store = store
        self._pending = dict(keys)
        self.poll = float(poll)
        self._lock = threading.Lock()
        self._got: Dict[Any, Dict[str, np.ndarray]] = {}
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name="elastic-prefetch", daemon=True)
        self._thread.start()

    def _loop(self) -> None:
        while self._pending and not self._stop.is_set():
            for ident, key in list(self._pending.items()):
                if self._stop.is_set():
                    return
                try:
                    data = self.store.get(key)
                    arrays = (None if data is None
                              else _unpack_arrays(data))
                except (OSError, ValueError):
                    return  # store gone / garbage: the sync path takes over
                if arrays is not None:
                    with self._lock:
                        self._got[ident] = arrays
                    del self._pending[ident]
            if self._pending:
                self._stop.wait(self.poll)

    def drain(self) -> Dict[Any, Dict[str, np.ndarray]]:
        with self._lock:
            got = dict(self._got)
            self._got.clear()
        return got

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5.0)


class ElasticTrainer:
    """Synchronous elastic data-parallel trainer for a MultiLayerNetwork."""

    def __init__(self, model, store_dir, worker_id: str, *, world: int = 2,
                 vshards: Optional[int] = None, compress: bool = False,
                 threshold: float = 1e-3, ckpt_dir=None, ckpt_every: int = 0,
                 ttl: Optional[float] = None, poll: Optional[float] = None,
                 replication: Optional[int] = None,
                 rack: Optional[str] = None, slice_spec=None,
                 async_exchange: Optional[bool] = None):
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        if isinstance(model, ComputationGraph):
            raise NotImplementedError(
                "ElasticTrainer drives MultiLayerNetwork models; wrap CG "
                "training in the single-host paths meanwhile")
        if model.params is None:
            model.init()
        self.model = model
        self.store = open_store(store_dir)
        self.wid = str(worker_id)
        self.world = int(world)
        self.vshards = None if vshards is None else int(vshards)
        self.compress = bool(compress)
        self.threshold = float(threshold)
        self.ckpt_dir = None if ckpt_dir is None else os.fspath(ckpt_dir)
        self.ckpt_every = int(ckpt_every)
        self.replication = max(1, int(
            os.environ.get("DL4J_TPU_ELASTIC_MIRRORS", "2")
            if replication in (None, 0) else replication))
        self.async_exchange = bool(
            os.environ.get("DL4J_TPU_ELASTIC_ASYNC", "1") != "0"
            if async_exchange is None else async_exchange)
        if slice_spec:
            from deeplearning4j_tpu.parallel.mesh_step import MeshSlice

            self.slice: Optional[Any] = MeshSlice(slice_spec)
        else:
            self.slice = None
        self.rt = ElasticRuntime(self.store, self.wid, ttl=ttl, poll=poll,
                                 rack=rack)
        obs.gauge("dl4j_mirror_replication_factor",
                  "Configured optimizer-segment replication factor R "
                  "(owner + R-1 mirrors, capped at world size)").set(
                      self.replication)
        self.stall_s = 0.0   # cumulative boundary time blocked on payloads
        # fleet observability: slice identity on every span/event, per-rank
        # step-wall skew detection (rank 0 evaluates), snapshot publication
        # throttle (report-time: at most ~1/s into the store)
        if self.slice is not None:
            obs.set_process_context(slice=str(slice_spec))
        self._straggler = fleet.StragglerDetector()
        self._stragglers: set = set()
        self._last_publish = 0.0
        self._build_plan()
        self._bwd = model._get_grads_fn()
        self._base_rng = model._rng
        # formed state: segment stats per flat entry {key: {rank: [S, m]}},
        # dense structured opt per dense entry, residuals per owned vshard
        self._segs: Dict[int, Dict[int, np.ndarray]] = {}
        self._dense_opt: Dict[int, Any] = {}
        self._residuals: Dict[int, np.ndarray] = {}
        self._m: Dict[int, int] = {}
        self._formed = False
        self.losses: List[float] = []
        self.epoch = 0
        self.step_in_epoch = 0
        self._steps_per_epoch = 0

    # -- plan ---------------------------------------------------------------
    def _build_plan(self):
        model = self.model
        entries: Dict[int, _Entry] = {}
        order = list(range(len(model.layers)))
        for key in order:
            p = model.params[key]
            leaves, treedef = jax.tree_util.tree_flatten(p)
            if not leaves:
                continue
            cfg = model.layers[key]
            n = sum(int(np.prod(l.shape)) for l in leaves)
            dtypes = {jnp.dtype(l.dtype) for l in leaves}
            uniform_float = (len(dtypes) == 1 and
                             jnp.issubdtype(next(iter(dtypes)), jnp.floating))
            gn = getattr(cfg, "gradient_normalization", None)
            constraints = getattr(cfg, "constraints", None)
            eligible = uniform_float and not gn and not constraints
            entries[key] = _Entry(
                key=key, treedef=treedef,
                shapes=tuple(tuple(l.shape) for l in leaves), n=n,
                dtype=(next(iter(dtypes)) if uniform_float else None),
                mode="flat" if eligible else "dense",
                updater=model._updaters[key], cfg=cfg)
        self._entries = entries
        self._order = order
        self._flat_keys = [k for k in order
                           if k in entries and entries[k].mode == "flat"]
        self._dense_keys = [k for k in order
                            if k in entries and entries[k].mode == "dense"]
        self._total_n = sum(entries[k].n for k in self._flat_keys)

    def _stat_template(self, e: _Entry, m: int):
        template = e.updater.init(jnp.zeros((m,), e.dtype))
        leaves, tdef = jax.tree_util.tree_flatten(template)
        return len(leaves), tdef

    # -- structured <-> flat optimizer stats --------------------------------
    def _stats_full_from_structured(self, e: _Entry, structured,
                                    length: int) -> np.ndarray:
        """Per-layer structured opt state -> ``[n_stats, length]`` float
        stack (outer-stat-major leaf grouping, same layout as
        ``DataParallelStep._to_flat_opt``)."""
        leaves = jax.tree_util.tree_leaves(structured)
        n_inner = len(e.shapes)
        if leaves and len(leaves) % n_inner != 0:
            raise ValueError(
                f"opt state for layer {e.key} has {len(leaves)} leaves, not "
                f"a multiple of the {n_inner} param leaves")
        stats = []
        for i in range(0, len(leaves), n_inner):
            chunk = leaves[i:i + n_inner]
            flat = np.concatenate(
                [np.ravel(np.asarray(l)) for l in chunk])  # graftlint: disable=host-sync
            row = np.zeros((length,), flat.dtype)
            row[:e.n] = flat
            stats.append(row)
        if not stats:
            return np.zeros((0, length), np.dtype(e.dtype))
        return np.stack(stats)

    def _stats_structured_from_full(self, e: _Entry, full: np.ndarray):
        """Inverse: ``[n_stats, >=n]`` stack -> the model's structured
        per-layer opt state."""
        _, tdef = self._stat_template(e, int(full.shape[1]) if full.size
                                      else e.n)
        subtrees = []
        for row in full:
            subtrees.append(_unflat(jnp.asarray(row[:e.n]), e))
        return jax.tree_util.tree_unflatten(tdef, subtrees)

    # -- vshard / mirror geometry --------------------------------------------
    def _view_racks(self, view: View, prev: bool = False) -> List[str]:
        members = view.prev_members if prev else view.members
        labels = view.prev_racks if prev else view.racks
        return [labels.get(m, "") for m in members]

    def _held_ranks(self, rank: int, W: int,
                    racks: Sequence[str] = ()) -> List[int]:
        """Segments this worker carries: its primary plus every rank whose
        R-way rack-aware mirror set includes it (R=2, uniform racks ⇒ the
        classic ``[rank, (rank+1) % W]`` buddy pair)."""
        return [rank] + [t for t in range(W) if t != rank
                         and rank in mirror_ranks(
                             t, W, self.replication, racks)]

    def _vshard_owner(self, j: int) -> int:
        return j % self.rt.view.world

    def _my_vshards(self) -> List[int]:
        r = self.rt.view.rank_of(self.wid)
        W = self.rt.view.world
        return [j for j in range(self.vshards) if j % W == r]

    # -- forming / re-forming ------------------------------------------------
    def _slice_segs_from_full(self, full_by_key: Dict[int, np.ndarray],
                              view: View):
        """(Re-)slice my primary + R-way mirror segments for the new world
        out of the full per-layer stat stacks."""
        W = view.world
        r = view.rank_of(self.wid)
        held = self._held_ranks(r, W, self._view_racks(view))
        segs: Dict[int, Dict[int, np.ndarray]] = {}
        m_of: Dict[int, int] = {}
        for key in self._flat_keys:
            e = self._entries[key]
            m = -(-e.n // W)
            m_of[key] = m
            full = full_by_key[key]
            n_pad = m * W
            padded = np.zeros((full.shape[0], n_pad), full.dtype)
            padded[:, :min(full.shape[1], n_pad)] = full[:, :n_pad]
            segs[key] = {t: padded[:, t * m:(t + 1) * m].copy()
                         for t in held}
        self._segs = segs
        self._m = m_of

    def _form_fresh(self, view: View):
        """Bootstrap form: every worker derives identical state from the
        (seed-deterministic) model init — no handoff needed."""
        model = self.model
        full = {}
        for key in self._flat_keys:
            e = self._entries[key]
            full[key] = self._stats_full_from_structured(
                e, model.opt_state[key], e.n)
        self._slice_segs_from_full(full, view)
        self._dense_opt = {k: model.opt_state[k] for k in self._dense_keys}
        self._residuals = {j: np.zeros(self._total_n, np.float32)
                           for j in range(self.vshards)
                           if self._vshard_owner(j) == view.rank_of(self.wid)}
        self._formed = True

    def _form_from_checkpoint(self, view: View, ckpt: dict) -> bool:
        """Full-group restart: rebuild params/opt/position from the newest
        valid distributed checkpoint (``resilience.load_distributed_...``)."""
        man = ckpt["manifest"]
        pa = ckpt["params"]
        model = self.model
        # params + dense opt + layer state + meta
        meta = _array_to_json(pa["meta"])
        params = []
        for key in self._order:
            e = self._entries.get(key)
            if e is None:
                params.append(model.params[key])
                continue
            leaves = [jnp.asarray(pa[f"p{key}_{li}"])
                      for li in range(len(e.shapes))]
            params.append(jax.tree_util.tree_unflatten(e.treedef, leaves))
        model.params = tuple(params)
        for key in self._dense_keys:
            e = self._entries[key]
            n_leaves = len(jax.tree_util.tree_leaves(model.opt_state[key]))
            leaves = [jnp.asarray(pa[f"o{key}_{li}"])
                      for li in range(n_leaves)]
            tdef = jax.tree_util.tree_structure(model.opt_state[key])
            self._dense_opt[key] = jax.tree_util.tree_unflatten(tdef, leaves)
        st_leaves = jax.tree_util.tree_leaves(model.state)
        st_def = jax.tree_util.tree_structure(model.state)
        model.state = jax.tree_util.tree_unflatten(
            st_def, [jnp.asarray(pa[f"st{li}"])
                     for li in range(len(st_leaves))])
        self._base_rng = jnp.asarray(
            np.asarray(meta["base_rng"],
                       dtype=np.dtype(meta["base_rng_dtype"])))
        model.iteration = int(meta["iteration"])
        self.epoch, self.step_in_epoch = int(meta["epoch"]), int(meta["step"])
        self.losses = [float(v) for v in meta.get("losses", [])]
        # optimizer segments: assemble the full stacks from the per-host
        # shard files (each carries primary + mirror; any host can serve a
        # straggler's shard), then re-slice for the new world
        W_old = int(man["world"])
        full = {}
        for key in self._flat_keys:
            e = self._entries[key]
            full[key] = self._assemble_full_stats(
                e, W_old, lambda t: self._ckpt_seg(ckpt, key, t))
            if full[key] is None:
                return False
        self._slice_segs_from_full(full, view)
        self._restore_residuals(
            view, lambda j: self._ckpt_res(ckpt, W_old, j))
        self._formed = True
        obs.event("elastic_restart_restore", manifest=ckpt["path"],
                  iteration=model.iteration, epoch=self.epoch,
                  step=self.step_in_epoch)
        return True

    def _ckpt_seg(self, ckpt, key, t):
        for arrays in ckpt["shards"].values():
            a = arrays.get(f"k{key}_t{t}")
            if a is not None:
                return a
        return None

    def _ckpt_res(self, ckpt, W_old, j):
        arrays = ckpt["shards"].get(j % W_old, {})
        return arrays.get(f"res{j}")

    def _assemble_full_stats(self, e: _Entry, W_old: int, seg_of):
        """Rebuild one layer's full ``[n_stats, m_old * W_old]`` stat stack
        from per-rank segment sources (handoff files or checkpoint shards);
        ``seg_of(t)`` returns rank ``t``'s segment from primary or mirror,
        or None when unrecoverable."""
        m_old = -(-e.n // W_old)
        n_stats, _ = self._stat_template(e, m_old)
        full = np.zeros((n_stats, m_old * W_old), np.dtype(e.dtype))
        for t in range(W_old):
            seg = seg_of(t)
            if seg is None:
                obs.event("elastic_segment_unrecoverable", layer=e.key,
                          rank=t, world=W_old)
                return None
            full[:, t * m_old:(t + 1) * m_old] = seg
        return full

    def _restore_residuals(self, view: View, res_of):
        """Residuals move with vshard ownership; a dead worker's pending
        sub-threshold gradient mass is lost (zeros) — the documented,
        tolerance-bounded cost of compressed elasticity."""
        r = view.rank_of(self.wid)
        W = view.world
        res: Dict[int, np.ndarray] = {}
        for j in range(self.vshards):
            if j % W != r:
                continue
            a = res_of(j)
            res[j] = (np.zeros(self._total_n, np.float32) if a is None
                      else np.asarray(a, np.float32).copy())
        self._residuals = res

    # -- reform (handoff) ----------------------------------------------------
    def _reform(self, view: View):
        """Adopt ``view`` and re-form training state at its world size,
        looping through any further churn that lands mid-handoff."""
        while True:
            try:
                self._do_reform(view)
                return
            except MembershipChanged as mc:
                view = mc.view

    def _do_reform(self, view: View):
        self.rt.adopt(view)
        if self.wid not in view.members:
            # expelled (partition outlived the TTL): wait for the survivors
            # to grow the view back around our renewed lease, then take the
            # handoff as a joiner. If the job finishes first (rank 0 leaves
            # the terminal `done` blob), adopt that final state instead.
            self._formed = False
            view = self.rt.await_readmission(
                should_stop=lambda: self.store.exists("done"))
            if view is None:
                self._adopt_done()
                raise _JobDone()
            raise MembershipChanged(view)
        if self.vshards is None:
            self.vshards = max(view.world, 1)
        holders = view.holders()
        if not holders:
            # bootstrap or full-group restart: no live state to hand off
            ckpt = (resilience.load_distributed_checkpoint(self.ckpt_dir)
                    if self.ckpt_dir else None)
            if ckpt is not None and self._form_from_checkpoint(view, ckpt):
                return
            if view.reason == "restart":
                obs.event("elastic_restart_fresh", gen=view.gen)
            self._sync_to(view)
            self._form_fresh(view)
            return
        g = view.gen
        am_holder = self.wid in holders and self._formed
        if am_holder:
            self._publish_handoff(view)
        full, hands = self._await_handoff(view)
        meta = _array_to_json(full["meta"])
        model = self.model
        # adopt the coordinator's full copy (identical to a survivor's own
        # state; REQUIRED for a joiner)
        params = []
        for key in self._order:
            e = self._entries.get(key)
            if e is None:
                params.append(model.params[key])
                continue
            leaves = [jnp.asarray(full[f"p{key}_{li}"])
                      for li in range(len(e.shapes))]
            params.append(jax.tree_util.tree_unflatten(e.treedef, leaves))
        model.params = tuple(params)
        for key in self._dense_keys:
            n_leaves = len(jax.tree_util.tree_leaves(model.opt_state[key]))
            tdef = jax.tree_util.tree_structure(model.opt_state[key])
            self._dense_opt[key] = jax.tree_util.tree_unflatten(
                tdef, [jnp.asarray(full[f"o{key}_{li}"])
                       for li in range(n_leaves)])
        st_def = jax.tree_util.tree_structure(model.state)
        n_st = len(jax.tree_util.tree_leaves(model.state))
        model.state = jax.tree_util.tree_unflatten(
            st_def, [jnp.asarray(full[f"st{li}"]) for li in range(n_st)])
        self._base_rng = jnp.asarray(
            np.asarray(meta["base_rng"],
                       dtype=np.dtype(meta["base_rng_dtype"])))
        self.losses = [float(v) for v in meta.get("losses", [])]
        self._sync_to(view)
        # optimizer segments: primary from the old owner's hand file, buddy
        # mirror from its neighbor when the owner died, then re-slice
        W_old = len(view.prev_members)
        full_stats = {}
        for key in self._flat_keys:
            full_stats[key] = self._assemble_full_stats(
                self._entries[key], W_old,
                lambda t, k=key: self._hand_seg(hands, view, k, t))
            if full_stats[key] is None:
                raise RuntimeError(
                    f"elastic reform gen {g}: layer {key} optimizer "
                    "segments unrecoverable (owner and mirror both lost, "
                    "no checkpoint)")
        self._slice_segs_from_full(full_stats, view)
        self._restore_residuals(
            view, lambda j: self._hand_res(hands, view, j))
        self._formed = True

    def _sync_to(self, view: View):
        self.model.iteration = int(view.iteration)
        self.epoch = int(view.epoch)
        self.step_in_epoch = int(view.step)

    def _hand_seg(self, hands, view: View, key: int, t: int):
        """Rank ``t``'s outgoing segment from its old owner or ANY of its
        old mirrors (R-way, in the previous view's geometry)."""
        prev = view.prev_members
        sources = [t] + mirror_ranks(t, len(prev), self.replication,
                                     self._view_racks(view, prev=True))
        for s in sources:
            a = hands.get(prev[s], {}).get(f"k{key}_t{t}")
            if a is not None:
                return a
        return None

    def _hand_res(self, hands, view: View, j: int):
        prev = view.prev_members
        owner = prev[j % len(prev)] if prev else None
        if owner is None:
            return None
        return hands.get(owner, {}).get(f"res{j}")

    def _publish_handoff(self, view: View):
        g = view.gen
        arrays = {}
        for key in self._flat_keys:
            for t, seg in self._segs[key].items():
                arrays[f"k{key}_t{t}"] = seg
        for j, res in self._residuals.items():
            arrays[f"res{j}"] = res
        self.store.set(f"hand/{g}/{self.wid}", _pack_arrays(arrays))
        if view.holders()[0] != self.wid:
            return
        model = self.model
        full: Dict[str, np.ndarray] = {}
        for key in self._order:
            e = self._entries.get(key)
            if e is None:
                continue
            for li, leaf in enumerate(
                    jax.tree_util.tree_leaves(model.params[key])):
                full[f"p{key}_{li}"] = np.asarray(leaf)  # graftlint: disable=host-sync
        for key in self._dense_keys:
            for li, leaf in enumerate(
                    jax.tree_util.tree_leaves(self._dense_opt[key])):
                full[f"o{key}_{li}"] = np.asarray(leaf)  # graftlint: disable=host-sync
        for li, leaf in enumerate(jax.tree_util.tree_leaves(model.state)):
            full[f"st{li}"] = np.asarray(leaf)  # graftlint: disable=host-sync
        rng = np.asarray(self._base_rng)  # graftlint: disable=host-sync
        full["meta"] = _json_to_array({
            "iteration": int(model.iteration), "epoch": self.epoch,
            "step": self.step_in_epoch,
            "base_rng": rng.tolist(), "base_rng_dtype": str(rng.dtype),
            "losses": [float(v) for v in self.losses],
            "vshards": int(self.vshards),
        })
        self.store.set(f"hand/{g}/full", _pack_arrays(full))

    def _await_handoff(self, view: View):
        g = view.gen
        holders = list(view.holders())
        want = {wid: f"hand/{g}/{wid}" for wid in holders}
        want["__full__"] = f"hand/{g}/full"
        got: Dict[str, Dict[str, np.ndarray]] = {}
        deadline = time.monotonic() + self.rt.wait_timeout
        while want:
            for wid, key in list(want.items()):
                data = self.store.get(key)
                if data is not None:
                    got[wid] = _unpack_arrays(data)
                    del want[wid]
            if not want:
                break
            self.rt.check_for_change()
            dead = [wid for wid in want if wid != "__full__"
                    and not self.rt.member_alive(wid)]
            if dead or ("__full__" in want and holders
                        and not self.rt.member_alive(holders[0])):
                # a holder died mid-handoff (the coordinator, if the full
                # copy is missing): shrink again and retry at the new view
                self.rt.report_dead(dead or [holders[0]],
                                    (view.epoch, view.step, view.iteration))
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"elastic handoff gen {g}: still waiting on "
                    f"{sorted(want)} after {self.rt.wait_timeout:.0f}s")
            time.sleep(self.rt.poll)
        full = got.pop("__full__")
        return full, got

    # -- the step ------------------------------------------------------------
    def _chaos_hooks(self, it: int, rank: int):
        chaos = resilience.active_chaos()
        if chaos is None:
            return
        chaos.maybe_host_kill(it, rank=rank)
        # elastic-of-slices: this member process IS its slice, so a slice
        # preemption is one SIGKILL here (elastic rank == slice index)
        chaos.maybe_slice_kill(it, slice_index=rank)
        secs = chaos.partition_seconds(it, rank=rank)
        if secs > 0:
            # the net_partition fault: stop heartbeating and stall — to the
            # group this worker is on the wrong side of a switch. A stall
            # longer than the TTL gets us expelled; on waking we renew the
            # lease and rejoin through the handoff.
            self.rt.membership.suspend(secs + self.rt.ttl)
            obs.event("elastic_partition_begin", wid=self.wid, rank=rank,
                      iteration=it, seconds=secs)
            time.sleep(secs)
            self.rt.membership.heartbeat_now()
            obs.event("elastic_partition_end", wid=self.wid, rank=rank,
                      iteration=it)
        rsecs = chaos.rack_partition_seconds(it, rack=self.rt.rack)
        if rsecs > 0:
            # rack_partition: same mechanics, rack-wide blast radius — every
            # worker with the matching DL4J_TPU_RACK label goes dark at once
            self.rt.membership.suspend(rsecs + self.rt.ttl)
            obs.event("rack_partition", phase="begin", wid=self.wid,
                      rack=self.rt.rack, rank=rank, iteration=it,
                      seconds=rsecs)
            time.sleep(rsecs)
            self.rt.membership.heartbeat_now()
            obs.event("rack_partition", phase="end", wid=self.wid,
                      rack=self.rt.rack, rank=rank, iteration=it)
        chaos.maybe_preempt(it)
        chaos.maybe_slow(it, rank=rank)

    def _vshard_payload(self, j: int, xb, yb, it: int):
        """Compute vshard ``j``'s weighted contribution and frame it for the
        store. Weights (``n_j / N``) and rng depend only on (step, j) — the
        membership-invariance that makes elastic runs bit-exact."""
        from deeplearning4j_tpu.nn.model import _cast_input, _cast_labels

        model = self.model
        v = self.vshards
        c = self._vshard_rows
        lo, hi = j * c, min((j + 1) * c, len(xb))
        n_j = max(hi - lo, 0)
        if n_j <= 0:
            return _pack_arrays({"n": np.asarray(0, np.int64)})
        N = len(xb)
        w = np.float32(n_j) / np.float32(N)
        x_j, y_j, fm, lm, ew = bucketing.pad_fit_batch(
            xb[lo:hi], yb[lo:hi], None, None, c, site="elastic.fit")
        rng_j = jax.random.fold_in(self._base_rng, it * v + j)
        x_c = _cast_input(x_j, model.dtype)
        y_c = _cast_labels(y_j, model.dtype)
        fm_c = jnp.asarray(fm, model.dtype) if fm is not None else None
        lm_c = jnp.asarray(lm, model.dtype) if lm is not None else None
        ew_c = jnp.asarray(ew, model.dtype) if ew is not None else None
        if self.slice is not None:
            # elastic-of-slices: the vshard's backward runs GSPMD-sharded
            # over this member's (d,t,s) mesh — batch over the data axis,
            # params/state replicated, XLA inserting the in-slice
            # collectives (padded vshard rows are a multiple of d)
            sl = self.slice
            loss, new_state, grads = sl.run(
                self._bwd, sl.replicate(model.params),
                sl.replicate(model.state), sl.shard_batch(x_c),
                sl.shard_batch(y_c), sl.shard_batch(fm_c),
                sl.shard_batch(lm_c), sl.replicate(rng_j),
                sl.shard_batch(ew_c))
        else:
            loss, new_state, grads = self._bwd(
                model.params, model.state, x_c, y_c, fm_c, lm_c, rng_j,
                ew_c)
        arrays: Dict[str, np.ndarray] = {
            "n": np.asarray(n_j, np.int64),
            "loss": np.float32(loss) * w,  # graftlint: disable=host-sync
        }
        if self._flat_keys:
            gflat = np.concatenate([
                np.asarray(_flat(grads[k]), np.float32)  # graftlint: disable=host-sync
                for k in self._flat_keys]) * w
            if self.compress:
                res = self._residuals[j]
                packed, new_res = compression.encode_packed(
                    jnp.asarray(gflat), jnp.asarray(res), self.threshold)
                self._residuals[j] = np.asarray(new_res, np.float32)  # graftlint: disable=host-sync
                arrays["q"] = np.asarray(packed)  # graftlint: disable=host-sync
            else:
                arrays["g"] = gflat
        for key in self._dense_keys:
            for li, leaf in enumerate(jax.tree_util.tree_leaves(grads[key])):
                arrays[f"d{key}_{li}"] = (
                    np.asarray(leaf, np.float32) * w)  # graftlint: disable=host-sync
        for li, leaf in enumerate(jax.tree_util.tree_leaves(new_state)):
            a = np.asarray(leaf)  # graftlint: disable=host-sync
            if np.issubdtype(a.dtype, np.floating):
                a = (a.astype(np.float32) * w)
            arrays[f"s{li}"] = a
        return _pack_arrays(arrays)

    def _await_vshards(self, g: int, it: int, view: View, sync,
                       prefetch: Optional[_Prefetcher] = None,
                       ) -> List[Dict[str, np.ndarray]]:
        """Collect every vshard's payload for this step. A dead owner is
        unrecoverable mid-step (only it computed those gradients), so a
        lapsed lease drives a shrink and the survivors re-run the step."""
        v = self.vshards
        want = {j: f"grad/{g}/{it}/{j}" for j in range(v)}
        got: Dict[int, Dict[str, np.ndarray]] = {}
        deadline = time.monotonic() + self.rt.wait_timeout
        while want:
            if prefetch is not None:
                for j, arrays in prefetch.drain().items():
                    if j in want:
                        got[j] = arrays
                        del want[j]
            for j, key in list(want.items()):
                data = self.store.get(key)
                if data is not None:
                    got[j] = _unpack_arrays(data)
                    del want[j]
            if not want:
                break
            self.rt.check_for_change()
            dead = sorted({view.members[j % view.world] for j in want
                           if not self.rt.member_alive(
                               view.members[j % view.world])})
            if dead:
                self.rt.report_dead(dead, sync)
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"elastic step {it}: vshard payloads {sorted(want)} "
                    f"missing after {self.rt.wait_timeout:.0f}s")
            time.sleep(self.rt.poll)
        return [got[j] for j in range(v)]

    def _combine(self, payloads: List[Dict[str, np.ndarray]]):
        """Fixed-order (ascending vshard) sums of the weighted payloads:
        loss, flat gradient, dense per-leaf gradients, float state leaves.
        Order never depends on membership — the bit-exactness anchor."""
        loss = np.float32(0.0)
        gflat = np.zeros(self._total_n, np.float32)
        dense: Dict[str, np.ndarray] = {}
        state_f: Dict[str, np.ndarray] = {}
        state_i: Dict[str, np.ndarray] = {}
        packed = []
        for p in payloads:
            if int(p["n"]) == 0:
                continue
            loss = loss + p["loss"].astype(np.float32)
            if "q" in p:
                packed.append(p["q"])
            elif "g" in p:
                gflat += p["g"]
            for k, a in p.items():
                if k.startswith("d"):
                    dense[k] = dense[k] + a if k in dense else a.copy()
                elif k.startswith("s"):
                    if np.issubdtype(a.dtype, np.floating):
                        state_f[k] = (state_f[k] + a if k in state_f
                                      else a.copy())
                    elif k not in state_i:
                        state_i[k] = a
        if packed:
            summed = compression.decode_gathered(
                jnp.stack([jnp.asarray(q) for q in packed]),
                self._total_n, self.threshold, jnp.float32)
            gflat = np.asarray(summed, np.float32)  # graftlint: disable=host-sync
        # re-assemble the model state pytree from the summed leaves
        st_def = jax.tree_util.tree_structure(self.model.state)
        old_leaves = jax.tree_util.tree_leaves(self.model.state)
        new_leaves = []
        for li, old in enumerate(old_leaves):
            k = f"s{li}"
            if k in state_f:
                new_leaves.append(jnp.asarray(
                    state_f[k].astype(np.asarray(old).dtype)))  # graftlint: disable=host-sync
            elif k in state_i:
                new_leaves.append(jnp.asarray(state_i[k]))
            else:
                new_leaves.append(old)
        new_state = jax.tree_util.tree_unflatten(st_def, new_leaves)
        return loss, gflat, dense, new_state

    def _segment_update(self, gflat: np.ndarray, it: int, view: View):
        """Sharded optimizer update (arXiv 2004.13336): each worker updates
        its primary 1/W segment AND every segment it mirrors (R-way).
        Elementwise updater math means a segment's values are bitwise
        identical to the same elements of a full-vector update. Returns
        ``(new_segs, pnew_segs, my_pseg_arrays)`` — committed only after
        the whole step succeeds."""
        W = view.world
        r = view.rank_of(self.wid)
        held = self._held_ranks(r, W, self._view_racks(view))
        it_arr = jnp.asarray(it, jnp.int32)
        new_segs: Dict[int, Dict[int, np.ndarray]] = {}
        pnew: Dict[Tuple[int, int], np.ndarray] = {}
        off = 0
        for key in self._flat_keys:
            e = self._entries[key]
            m = self._m[key]
            n_pad = m * W
            g_pad = np.zeros(n_pad, np.float32)
            g_pad[:e.n] = gflat[off:off + e.n]
            off += e.n
            p_full = np.concatenate([
                np.ravel(np.asarray(l))  # graftlint: disable=host-sync
                for l in jax.tree_util.tree_leaves(self.model.params[key])])
            p_pad = np.zeros(n_pad, p_full.dtype)
            p_pad[:e.n] = p_full
            _, tdef = self._stat_template(e, m)
            new_segs[key] = {}
            for t in held:
                sl = slice(t * m, (t + 1) * m)
                g_seg = jnp.asarray(g_pad[sl]).astype(e.dtype)
                p_seg = jnp.asarray(p_pad[sl])
                o_tree = jax.tree_util.tree_unflatten(
                    tdef, [jnp.asarray(row)
                           for row in self._segs[key][t]])
                upd, o_new = e.updater.update(g_seg, o_tree, p_seg, it_arr)
                p_new = p_seg - upd
                leaves = jax.tree_util.tree_leaves(o_new)
                new_segs[key][t] = (
                    np.stack([np.asarray(l) for l in leaves])  # graftlint: disable=host-sync
                    if leaves else np.zeros((0, m), np.dtype(e.dtype)))
                pnew[(key, t)] = np.asarray(p_new)  # graftlint: disable=host-sync
        my_pseg = {f"k{key}": pnew[(key, r)] for key in self._flat_keys}
        return new_segs, pnew, my_pseg

    def _dense_update(self, dense_g: Dict[str, np.ndarray], it: int):
        """Replicated exact update for gn/constraint/mixed-dtype layers —
        the same math as ``model._update_params``, run identically on every
        worker."""
        it_arr = jnp.asarray(it, jnp.int32)
        new_params: Dict[int, Any] = {}
        new_opt: Dict[int, Any] = {}
        for key in self._dense_keys:
            e = self._entries[key]
            leaves = [jnp.asarray(dense_g[f"d{key}_{li}"])
                      for li in range(len(e.shapes))]
            g = jax.tree_util.tree_unflatten(
                e.treedef,
                [l.astype(pl.dtype) for l, pl in zip(
                    leaves,
                    jax.tree_util.tree_leaves(self.model.params[key]))])
            gn = getattr(e.cfg, "gradient_normalization", None)
            if gn:
                g = apply_gradient_normalization(
                    gn,
                    getattr(e.cfg, "gradient_normalization_threshold", 1.0),
                    g)
            upd, o_new = e.updater.update(
                g, self._dense_opt[key], self.model.params[key], it_arr)
            p_new = jax.tree_util.tree_map(
                lambda p, d: p - d, self.model.params[key], upd)
            if getattr(e.cfg, "constraints", None):
                from deeplearning4j_tpu.nn.constraints import apply_constraints

                p_new = apply_constraints(e.cfg, p_new)
            new_params[key] = p_new
            new_opt[key] = o_new
        return new_params, new_opt

    def _await_psegs(self, g: int, it: int, view: View, sync,
                     my_pseg: Dict[str, np.ndarray],
                     pnew: Dict[Tuple[int, int], np.ndarray],
                     prefetch: Optional[_Prefetcher] = None):
        """Collect every rank's updated param segment. A dead rank's segment
        is recoverable while ANY of its R-1 mirrors survives: the first
        surviving mirror (in placement order — every worker derives the
        same order) computed the identical update and serves it
        (``dl4j_elastic_mirror_serves_total``); only the loss of the owner
        AND all its mirrors forces the shrink-and-rerun path."""
        W = view.world
        r = view.rank_of(self.wid)
        racks = self._view_racks(view)
        got: Dict[int, Dict[str, np.ndarray]] = {r: my_pseg}
        want = {t: f"pseg/{g}/{it}/{t}" for t in range(W) if t != r}
        deadline = time.monotonic() + self.rt.wait_timeout
        while want:
            if prefetch is not None:
                for t, arrays in prefetch.drain().items():
                    if t in want:
                        got[t] = arrays
                        del want[t]
            for t, key in list(want.items()):
                data = self.store.get(key)
                if data is not None:
                    got[t] = _unpack_arrays(data)
                    del want[t]
            if not want:
                break
            self.rt.check_for_change()
            unrecoverable = []
            for t in list(want):
                if self.rt.member_alive(view.members[t]):
                    continue
                mirrors = mirror_ranks(t, W, self.replication, racks)
                alive = [s for s in mirrors if s == r
                         or self.rt.member_alive(view.members[s])]
                if not alive:
                    unrecoverable.append(view.members[t])
                elif alive[0] == r:
                    served = {f"k{key}": pnew[(key, t)]
                              for key in self._flat_keys}
                    self.store.set(f"pseg/{g}/{it}/{t}",
                                   _pack_arrays(served))
                    got[t] = served
                    del want[t]
                    obs.counter(
                        "dl4j_elastic_mirror_serves_total",
                        "Dead ranks' param segments served from a "
                        "surviving mirror").inc()
                    obs.event("elastic_mirror_serve", rank=t, by=self.wid,
                              iteration=it, gen=g)
                # else: an earlier surviving mirror serves; keep waiting on
                # the pseg key it will publish
            if unrecoverable:
                self.rt.report_dead(sorted(set(unrecoverable)), sync)
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"elastic step {it}: param segments {sorted(want)} "
                    f"missing after {self.rt.wait_timeout:.0f}s")
            time.sleep(self.rt.poll)
        return got

    def _assemble_params(self, got: Dict[int, Dict[str, np.ndarray]],
                         dense_params: Dict[int, Any], view: View):
        W = view.world
        params = []
        for key in self._order:
            e = self._entries.get(key)
            if e is None:
                params.append(self.model.params[key])
            elif e.mode == "dense":
                params.append(dense_params[key])
            else:
                m = self._m[key]
                flat = np.concatenate(
                    [got[t][f"k{key}"] for t in range(W)])[:e.n]
                params.append(_unflat(jnp.asarray(flat), e))
        self.model.params = tuple(params)

    def _run_step(self, xb, yb):
        view = self.rt.view
        it = int(self.model.iteration)
        sync = (self.epoch, self.step_in_epoch, it)
        r = view.rank_of(self.wid)
        W = view.world
        # the work-wall window opens BEFORE the chaos hooks: an injected
        # slow_iter stall is exactly the straggler signal the skew
        # detector exists to catch
        t_start = time.monotonic()
        self._chaos_hooks(it, r)
        self.rt.poll_boundary(sync)
        g = view.gen
        mine = set(self._my_vshards())
        fetchers: List[_Prefetcher] = []
        stall0 = self.stall_s
        try:
            with obs.span("elastic.step"):
                if self.async_exchange and len(mine) < self.vshards:
                    # overlap fetching the peers' vshard payloads with
                    # computing my own backward passes
                    fetchers.append(_Prefetcher(
                        self.store,
                        {j: f"grad/{g}/{it}/{j}"
                         for j in range(self.vshards) if j not in mine},
                        self.rt.poll))
                for j in self._my_vshards():
                    self.store.set(f"grad/{g}/{it}/{j}",
                                   self._vshard_payload(j, xb, yb, it))
                t0 = time.monotonic()
                payloads = self._await_vshards(
                    g, it, view, sync,
                    prefetch=fetchers[0] if fetchers else None)
                self.stall_s += time.monotonic() - t0
                loss, gflat, dense_g, new_state = self._combine(payloads)
                new_segs, pnew, my_pseg = self._segment_update(
                    gflat, it, view)
                self.store.set(f"pseg/{g}/{it}/{r}", _pack_arrays(my_pseg))
                pf = None
                if self.async_exchange and W > 1:
                    # overlap fetching the peers' param segments with the
                    # dense (replicated) update below
                    pf = _Prefetcher(
                        self.store,
                        {t: f"pseg/{g}/{it}/{t}"
                         for t in range(W) if t != r},
                        self.rt.poll)
                    fetchers.append(pf)
                dense_params, dense_opt = self._dense_update(dense_g, it)
                t0 = time.monotonic()
                got = self._await_psegs(g, it, view, sync, my_pseg, pnew,
                                        prefetch=pf)
                self.stall_s += time.monotonic() - t0
                # commit: nothing above mutated trainer/model state, so a
                # membership change mid-step leaves us at the exact boundary
                # the re-formed group re-runs from
                self._assemble_params(got, dense_params, view)
                self._segs = new_segs
                self._dense_opt.update(dense_opt)
                self.model.state = new_state
                self.model.iteration = it + 1
                self.losses.append(float(loss))
        finally:
            for f in fetchers:
                f.stop()
        stall = self.stall_s - stall0
        obs.histogram("dl4j_elastic_boundary_stall_seconds",
                      "Per-step time blocked waiting on DCN payloads "
                      "(vshards + param segments)").observe(stall)
        # straggler detection input: the WORK wall (total minus time spent
        # blocked on peers' payloads). Total walls equalize across ranks —
        # every waiter stalls on the straggler — so only the stall-free
        # component attributes the skew to the rank that caused it.
        work_s = max(time.monotonic() - t_start - stall, 0.0)
        self._publish_stepwall(g, it, r, W, work_s)
        if r == 0 and it >= 2:
            self.store.prune(f"grad/{g}/{it - 2}")
            self.store.prune(f"pseg/{g}/{it - 2}")
            self.store.prune(f"obs/stepwall/{g}/{it - 2}")
        return float(loss)

    def _publish_stepwall(self, g: int, it: int, r: int, W: int,
                          work_s: float) -> None:
        """Publish this rank's per-step work wall and (on rank 0) evaluate
        the skew detector over iteration ``it - 1``, whose walls every
        rank is guaranteed to have published — the pseg exchange of step
        ``it`` cannot complete before every rank finished step ``it - 1``
        — so the read loop below never waits."""
        try:
            self.store.set(fleet.stepwall_key(g, it, r),
                           json.dumps({"wall_s": work_s}).encode())
            if r != 0 or it < 1 or W < 2:
                return
            walls: Dict[int, float] = {}
            for t in range(W):
                raw = self.store.get(fleet.stepwall_key(g, it - 1, t))
                if raw is None:
                    return  # gen reformed mid-window: skip this boundary
                walls[t] = float(json.loads(raw.decode())["wall_s"])
            self._stragglers.update(self._straggler.observe(it - 1, walls))
        except Exception:
            pass  # observability must never fail the step

    # -- distributed checkpoints ---------------------------------------------
    def _maybe_checkpoint(self):
        if (not self.ckpt_dir or self.ckpt_every <= 0
                or self.model.iteration % self.ckpt_every != 0):
            return
        view = self.rt.view
        r = view.rank_of(self.wid)
        tag = f"{int(self.model.iteration):08d}"
        os.makedirs(self.ckpt_dir, exist_ok=True)
        arrays = {}
        for key in self._flat_keys:
            for t, seg in self._segs[key].items():
                arrays[f"k{key}_t{t}"] = seg
        for j, res in self._residuals.items():
            arrays[f"res{j}"] = res
        shard_name = f"shard_{tag}_r{r}.npz"
        shard_path = os.path.join(self.ckpt_dir, shard_name)
        data = _pack_arrays(arrays)
        resilience.write_bytes_durable(shard_path, data)
        self.store.set_json(f"ckmeta/{view.gen}/{tag}/{r}", {
            "file": shard_name, "crc": resilience.crc32_file(shard_path),
            "size": os.path.getsize(shard_path), "rank": r, "wid": self.wid})
        from deeplearning4j_tpu.nn import aot

        aot.save_distributed_bundle(
            self.model, os.path.join(self.ckpt_dir, f"ckpt_{tag}"), r)
        if r != 0:
            return
        # rank 0 writes the replicated arrays + the CRC'd manifest (the
        # commit point: a manifest only lands after every shard checks in)
        model = self.model
        pa: Dict[str, np.ndarray] = {}
        for key in self._order:
            e = self._entries.get(key)
            if e is None:
                continue
            for li, leaf in enumerate(
                    jax.tree_util.tree_leaves(model.params[key])):
                pa[f"p{key}_{li}"] = np.asarray(leaf)  # graftlint: disable=host-sync
        for key in self._dense_keys:
            for li, leaf in enumerate(
                    jax.tree_util.tree_leaves(self._dense_opt[key])):
                pa[f"o{key}_{li}"] = np.asarray(leaf)  # graftlint: disable=host-sync
        for li, leaf in enumerate(jax.tree_util.tree_leaves(model.state)):
            pa[f"st{li}"] = np.asarray(leaf)  # graftlint: disable=host-sync
        rng = np.asarray(self._base_rng)  # graftlint: disable=host-sync
        pa["meta"] = _json_to_array({
            "iteration": int(model.iteration), "epoch": self.epoch,
            "step": self.step_in_epoch, "base_rng": rng.tolist(),
            "base_rng_dtype": str(rng.dtype),
            "losses": [float(v) for v in self.losses],
            "vshards": int(self.vshards)})
        params_name = f"ckpt_{tag}_params.npz"
        params_path = os.path.join(self.ckpt_dir, params_name)
        resilience.write_bytes_durable(params_path, _pack_arrays(pa))
        metas: Dict[int, dict] = {}
        deadline = time.monotonic() + max(2 * self.rt.ttl, 5.0)
        while len(metas) < view.world:
            for t in range(view.world):
                if t in metas:
                    continue
                d = self.store.get_json(f"ckmeta/{view.gen}/{tag}/{t}")
                if d is not None:
                    metas[t] = d
            if len(metas) == view.world:
                break
            if time.monotonic() > deadline:
                obs.event("elastic_checkpoint_aborted", tag=tag,
                          have=sorted(metas), world=view.world)
                return
            time.sleep(self.rt.poll)
        manifest = {
            "format": 1, "tag": tag, "iteration": int(model.iteration),
            "epoch": self.epoch, "step": self.step_in_epoch,
            "world": view.world, "members": list(view.members),
            "vshards": int(self.vshards),
            "params": {"file": params_name,
                       "crc": resilience.crc32_file(params_path),
                       "size": os.path.getsize(params_path)},
            "shards": {str(t): metas[t] for t in range(view.world)},
        }
        resilience.write_json_durable(
            os.path.join(self.ckpt_dir, f"manifest_{tag}.json"), manifest)
        obs.counter("dl4j_elastic_checkpoints_total",
                    "Distributed checkpoints committed (manifest written)"
                    ).inc()
        obs.event("elastic_checkpoint", tag=tag, world=view.world,
                  iteration=int(model.iteration))

    # -- finalization --------------------------------------------------------
    def _final_gather(self):
        """Assemble the full structured optimizer state back onto the model
        (the fit-exit contract: outside a fit the model stays
        serializable/usable, like ``DataParallelStep.finish``)."""
        view = self.rt.view
        g = view.gen
        arrays = {}
        for key in self._flat_keys:
            for t, seg in self._segs[key].items():
                arrays[f"k{key}_t{t}"] = seg
        self.store.set(f"fin/{g}/{self.wid}", _pack_arrays(arrays))
        sync = (self.epoch, self.step_in_epoch, int(self.model.iteration))
        want = {wid: f"fin/{g}/{wid}" for wid in view.members
                if wid != self.wid}
        got = {self.wid: arrays}
        deadline = time.monotonic() + self.rt.wait_timeout
        while want:
            for wid, key in list(want.items()):
                data = self.store.get(key)
                if data is not None:
                    got[wid] = _unpack_arrays(data)
                    del want[wid]
            if not want:
                break
            self.rt.check_for_change()
            dead = [wid for wid in want if not self.rt.member_alive(wid)]
            if dead:
                self.rt.report_dead(dead, sync)
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"elastic finalize gen {g}: waiting on {sorted(want)}")
            time.sleep(self.rt.poll)
        W = view.world
        new_opt = []
        for key in self._order:
            e = self._entries.get(key)
            if e is None:
                new_opt.append(self.model.opt_state[key])
            elif e.mode == "dense":
                new_opt.append(self._dense_opt[key])
            else:
                racks = self._view_racks(view)
                full = self._assemble_full_stats(
                    e, W,
                    lambda t, k=key: next(
                        (got[view.members[s]][f"k{k}_t{t}"]
                         for s in [t] + mirror_ranks(
                             t, W, self.replication, racks)
                         if view.members[s] in got
                         and f"k{k}_t{t}" in got[view.members[s]]), None))
                if full is None:
                    raise RuntimeError(
                        f"elastic finalize: layer {key} segments missing")
                new_opt.append(self._stats_structured_from_full(e, full))
        self.model.opt_state = tuple(new_opt)

    def _publish_done(self):
        """Rank 0's terminal blob: the fully-gathered final model state, so
        a worker partitioned through the END of the job still lands on the
        uninterrupted run's parameters instead of hanging on readmission."""
        model = self.model
        full: Dict[str, np.ndarray] = {}
        for key in self._order:
            if key not in self._entries:
                continue
            for li, leaf in enumerate(
                    jax.tree_util.tree_leaves(model.params[key])):
                full[f"p{key}_{li}"] = np.asarray(leaf)  # graftlint: disable=host-sync
            for li, leaf in enumerate(
                    jax.tree_util.tree_leaves(model.opt_state[key])):
                full[f"o{key}_{li}"] = np.asarray(leaf)  # graftlint: disable=host-sync
        for li, leaf in enumerate(jax.tree_util.tree_leaves(model.state)):
            full[f"st{li}"] = np.asarray(leaf)  # graftlint: disable=host-sync
        rng = np.asarray(self._base_rng)  # graftlint: disable=host-sync
        full["meta"] = _json_to_array({
            "iteration": int(model.iteration), "epoch": self.epoch,
            "step": self.step_in_epoch, "base_rng": rng.tolist(),
            "base_rng_dtype": str(rng.dtype),
            "losses": [float(v) for v in self.losses]})
        self.store.set("done", _pack_arrays(full))

    def _adopt_done(self):
        model = self.model
        full = _unpack_arrays(self.store.get("done"))
        meta = _array_to_json(full["meta"])
        params, opt = [], []
        for key in self._order:
            e = self._entries.get(key)
            if e is None:
                params.append(model.params[key])
                opt.append(model.opt_state[key])
                continue
            params.append(jax.tree_util.tree_unflatten(
                e.treedef, [jnp.asarray(full[f"p{key}_{li}"])
                            for li in range(len(e.shapes))]))
            n_o = len(jax.tree_util.tree_leaves(model.opt_state[key]))
            opt.append(jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(model.opt_state[key]),
                [jnp.asarray(full[f"o{key}_{li}"]) for li in range(n_o)]))
        model.params = tuple(params)
        model.opt_state = tuple(opt)
        st_def = jax.tree_util.tree_structure(model.state)
        n_st = len(jax.tree_util.tree_leaves(model.state))
        model.state = jax.tree_util.tree_unflatten(
            st_def, [jnp.asarray(full[f"st{li}"]) for li in range(n_st)])
        model.iteration = int(meta["iteration"])
        self.epoch, self.step_in_epoch = int(meta["epoch"]), int(meta["step"])
        self.losses = [float(v) for v in meta.get("losses", [])]
        obs.event("elastic_done_adopted", wid=self.wid,
                  iteration=model.iteration)

    # -- fit -----------------------------------------------------------------
    def fit(self, x, y, *, epochs: int, batch_size: int) -> dict:
        """Train for ``epochs`` over ``(x, y)`` elastically; returns a result
        dict (loss curve, final membership). Deterministic batch order; the
        global batch of step ``s`` is rows ``[s*bs, (s+1)*bs)``."""
        x = np.asarray(x)
        y = np.asarray(y)
        bs = int(batch_size)
        self._steps_per_epoch = max(-(-len(x) // bs), 1)
        view = self.rt.bootstrap(self.world)
        epochs = int(epochs)
        try:
            self._reform_initial(view)
            self._vshard_rows = self._rows_per_vshard(bs)
            while self.epoch < epochs:
                s = self.step_in_epoch
                lo = s * bs
                xb, yb = x[lo:lo + bs], y[lo:lo + bs]
                try:
                    self._run_step(xb, yb)
                except MembershipChanged as mc:
                    self._reform(mc.view)
                    self._vshard_rows = self._rows_per_vshard(bs)
                    continue
                self.step_in_epoch += 1
                if self.step_in_epoch >= self._steps_per_epoch:
                    self.step_in_epoch = 0
                    self.epoch += 1
                self._maybe_checkpoint()
                self._maybe_publish_snapshot()
            while True:
                try:
                    self._final_gather()
                    break
                except MembershipChanged as mc:
                    self._reform(mc.view)
            if self.rt.view.rank_of(self.wid) == 0:
                self._publish_done()
        except _JobDone:
            pass
        self._maybe_publish_snapshot(force=True)
        view = self.rt.view
        return {
            "wid": self.wid,
            "rank": view.rank_of(self.wid),
            "world": view.world,
            "gen": view.gen,
            "iteration": int(self.model.iteration),
            "losses": [float(v) for v in self.losses],
            "final_loss": (float(self.losses[-1]) if self.losses
                           else float("nan")),
            "stall_s": float(self.stall_s),
            "replication": int(self.replication),
            "rack": self.rt.rack,
            "store_backend": getattr(self.store, "backend", "file"),
            "async_exchange": bool(self.async_exchange),
            "stragglers": sorted(self._stragglers),
        }

    def _maybe_publish_snapshot(self, force: bool = False) -> None:
        """Publish this worker's metrics snapshot for the fleet collector —
        report-time only, throttled to ~1/s so the store sees one small
        write per worker per second, not per step."""
        now = time.monotonic()
        if not force and now - self._last_publish < 1.0:
            return
        self._last_publish = now
        try:
            fleet.publish_snapshot(self.store, self.wid)
        except Exception:
            pass  # observability must never fail training

    def _rows_per_vshard(self, bs: int) -> int:
        """Padded rows per vshard micro-batch; rounded up to the slice's
        data-axis size so the in-slice batch sharding divides evenly."""
        rows = -(-bs // self.vshards)
        return self.slice.round_rows(rows) if self.slice else rows

    def _reform_initial(self, view: View):
        """Initial form after bootstrap — same machinery as any reform, via
        a synthetic MembershipChanged so churn-during-handoff retries work
        from the first generation on."""
        self._reform(view)

    def close(self):
        self.rt.leave()


# ---------------------------------------------------------------------------
# CLI: worker + local launcher (tests/test_elastic.py, tools/elastic_smoke.sh)
# ---------------------------------------------------------------------------


def _build_model(args):
    from deeplearning4j_tpu.nn.input_type import InputType
    from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
    from deeplearning4j_tpu.nn.model import (
        MultiLayerConfiguration,
        MultiLayerNetwork,
    )

    hidden = [int(h) for h in str(args.hidden).split(",") if h]
    layers = tuple(Dense(n_out=h, activation="tanh") for h in hidden) + (
        OutputLayer(n_out=int(args.classes), activation="softmax"),)
    conf = MultiLayerConfiguration(
        layers=layers,
        input_type=InputType.feed_forward(int(args.features)),
        updater={"type": "adam", "lr": float(args.lr)},
        seed=int(args.seed),
    )
    return MultiLayerNetwork(conf).init()


def _make_data(args):
    rs = np.random.RandomState(int(args.seed))
    n, f, c = int(args.n), int(args.features), int(args.classes)
    x = rs.randn(n, f).astype(np.float32)
    y = np.eye(c, dtype=np.float32)[rs.randint(0, c, n)]
    return x, y


def _cmd_worker(args) -> int:
    os.makedirs(args.outdir, exist_ok=True)
    obs.configure_event_log(
        os.path.join(args.outdir, f"events_{args.id}.jsonl"))
    model = _build_model(args)
    trainer = ElasticTrainer(
        model, args.store, args.id, world=args.world,
        vshards=args.vshards, compress=args.compress,
        threshold=args.threshold,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        ttl=args.ttl, poll=args.poll,
        replication=args.replication or None,
        rack=args.rack if args.rack else None,
        slice_spec=args.mesh or None,
        async_exchange=None if args.async_exchange < 0
        else bool(args.async_exchange))
    x, y = _make_data(args)
    try:
        result = trainer.fit(x, y, epochs=args.epochs,
                             batch_size=args.batch)
    finally:
        trainer.close()
        # span dump for the merged fleet timeline (trace_export merge):
        # one file per worker, each carrying its own wall<->perf anchor
        # and rank/incarnation process context
        obs.save_spans(os.path.join(args.outdir, f"spans_{args.id}.json"))
    params = {}
    for key, p in enumerate(model.params):
        for li, leaf in enumerate(jax.tree_util.tree_leaves(p)):
            params[f"p{key}_{li}"] = np.asarray(leaf)  # graftlint: disable=host-sync
    # Publish atomically: the harness (and a relaunch supervisor) may read
    # these while a preemption kills this process mid-write — a torn
    # params_N.npz/result_N.json would poison the post-mortem checks.
    params_path = os.path.join(args.outdir, f"params_{args.id}.npz")
    tmp = params_path + f".{os.getpid()}.tmp.npz"  # np.savez appends .npz
    np.savez(tmp, **params)
    os.replace(tmp, params_path)
    result_path = os.path.join(args.outdir, f"result_{args.id}.json")
    tmp = result_path + f".tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(result, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, result_path)
    print(json.dumps(result))
    return 0


def _cmd_launch(args) -> int:
    """Local supervisor: spawn N workers, optionally relaunch killed ones
    (the preempted-worker-rejoins path). Relaunched processes get the chaos
    env stripped — the one-shot fault already fired in the dead process and
    must not re-fire at the (now higher) resume iteration."""
    procs: Dict[str, subprocess.Popen] = {}
    relaunches = int(args.relaunch)
    allowed_failures = int(args.allow_failures)
    failures: List[str] = []

    racks = [r.strip() for r in args.racks.split(",")] if args.racks else []

    def spawn(wid: str, chaos: bool) -> subprocess.Popen:
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        if args.mesh and args.slice_devices:
            # Must land in the child's env before jax imports: device count
            # is fixed at backend init.
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                " --xla_force_host_platform_device_count="
                                f"{args.slice_devices}").strip()
        if not chaos:
            env.pop("DL4J_TPU_CHAOS", None)
        cmd = [sys.executable, "-m", "deeplearning4j_tpu.train.elastic",
               "worker", "--store", args.store, "--outdir", args.outdir,
               "--id", wid, "--world", str(args.world),
               "--epochs", str(args.epochs), "--batch", str(args.batch),
               "--n", str(args.n), "--features", str(args.features),
               "--classes", str(args.classes), "--hidden", str(args.hidden),
               "--lr", str(args.lr), "--seed", str(args.seed),
               "--ttl", str(args.ttl), "--poll", str(args.poll),
               "--threshold", str(args.threshold)]
        if racks:
            wi = int(wid[1:])
            cmd += ["--rack", racks[wi % len(racks)]]
        if args.replication:
            cmd += ["--replication", str(args.replication)]
        if args.mesh:
            cmd += ["--mesh", args.mesh]
        if args.async_exchange >= 0:
            cmd += ["--async-exchange", str(args.async_exchange)]
        if args.vshards:
            cmd += ["--vshards", str(args.vshards)]
        if args.compress:
            cmd += ["--compress"]
        if args.ckpt_dir:
            cmd += ["--ckpt-dir", args.ckpt_dir,
                    "--ckpt-every", str(args.ckpt_every)]
        return subprocess.Popen(cmd, env=env)

    wids = [f"w{i}" for i in range(int(args.workers))]
    for wid in wids:
        procs[wid] = spawn(wid, chaos=True)
    if args.fleet_port >= 0:
        # fleet metrics federation: serve the merged exposition of every
        # worker's published snapshot while the run is live
        from deeplearning4j_tpu.obs import fleet as fleet_mod

        _, _, bound = fleet_mod.serve_collector(open_store(args.store),
                                                port=args.fleet_port)
        print(json.dumps({"fleet_port": bound}), flush=True)
    deadline = time.monotonic() + float(args.timeout)
    done: Dict[str, int] = {}
    while len(done) < len(wids):
        for wid, p in list(procs.items()):
            rc = p.poll()
            if rc is None:
                continue
            del procs[wid]
            if rc == 0:
                done[wid] = 0
            elif relaunches > 0:
                relaunches -= 1
                print(f"[launch] worker {wid} exited rc={rc}; relaunching",
                      flush=True)
                procs[wid] = spawn(wid, chaos=False)
            elif len(failures) < allowed_failures:
                failures.append(wid)
                done[wid] = rc
                print(f"[launch] worker {wid} exited rc={rc} "
                      "(allowed failure)", flush=True)
            else:
                for q in procs.values():
                    q.kill()
                print(f"[launch] worker {wid} exited rc={rc}; aborting",
                      flush=True)
                return 1
        if time.monotonic() > deadline:
            for q in procs.values():
                q.kill()
            print("[launch] timeout", flush=True)
            return 1
        time.sleep(0.05)
    survivors = [w for w in wids if done[w] == 0]
    print(json.dumps({"survivors": survivors, "failures": failures}))
    return 0 if survivors else 1


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m deeplearning4j_tpu.train.elastic",
        description="Elastic data-parallel training: worker process and "
                    "local launcher for the synthetic workload")
    sub = ap.add_subparsers(dest="cmd", required=True)

    def common(p):
        p.add_argument("--store", required=True,
                       help="shared rendezvous/exchange store: a directory "
                            "(or file:DIR) for FileStore, tcp://host:port "
                            "for the network store")
        p.add_argument("--outdir", required=True)
        p.add_argument("--world", type=int, default=2)
        p.add_argument("--epochs", type=int, default=3)
        p.add_argument("--batch", type=int, default=16)
        p.add_argument("--n", type=int, default=48)
        p.add_argument("--features", type=int, default=10)
        p.add_argument("--classes", type=int, default=4)
        p.add_argument("--hidden", default="16,8")
        p.add_argument("--lr", type=float, default=5e-3)
        p.add_argument("--seed", type=int, default=7)
        p.add_argument("--vshards", type=int, default=0)
        p.add_argument("--compress", action="store_true")
        p.add_argument("--threshold", type=float, default=1e-3)
        p.add_argument("--ckpt-dir", dest="ckpt_dir", default=None)
        p.add_argument("--ckpt-every", dest="ckpt_every", type=int,
                       default=0)
        p.add_argument("--ttl", type=float, default=2.0)
        p.add_argument("--poll", type=float, default=0.02)
        p.add_argument("--rack", default="",
                       help="failure-domain label for this worker "
                            "(mirror placement avoids the owner's rack)")
        p.add_argument("--replication", type=int, default=0,
                       help="R-way mirror replication factor "
                            "(0 = env/default)")
        p.add_argument("--mesh", default="",
                       help="per-member slice spec 'd[,t[,s]]' — run each "
                            "member as a mesh_step slice of that shape")
        p.add_argument("--async-exchange", dest="async_exchange",
                       type=int, default=-1,
                       help="1/0 force async DCN payload prefetch on/off "
                            "(-1 = env/default)")

    w = sub.add_parser("worker", help="run one elastic worker")
    common(w)
    w.add_argument("--id", required=True)
    w.set_defaults(fn=_cmd_worker)

    l = sub.add_parser("launch", help="supervise N local workers")
    common(l)
    l.add_argument("--workers", type=int, default=2)
    l.add_argument("--racks", default="",
                   help="comma-separated rack label per worker "
                        "(w0,w1,... ; cycled if shorter than --workers)")
    l.add_argument("--slice-devices", dest="slice_devices", type=int,
                   default=0,
                   help="virtual CPU device count per worker when --mesh "
                        "is set (injects xla_force_host_platform_"
                        "device_count)")
    l.add_argument("--relaunch", type=int, default=0,
                   help="relaunch budget for killed workers (rejoin path)")
    l.add_argument("--fleet-port", dest="fleet_port", type=int, default=-1,
                   help="serve the fleet metrics collector "
                        "(/fleet/metrics) on this port while workers run "
                        "(0 = OS-assigned; -1 = off)")
    l.add_argument("--allow-failures", dest="allow_failures", type=int,
                   default=0)
    l.add_argument("--timeout", type=float, default=300.0)
    l.set_defaults(fn=_cmd_launch)
    return ap


def main(argv=None) -> int:
    args = _parser().parse_args(argv)
    if getattr(args, "vshards", 0) == 0:
        args.vshards = None
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
