"""GPipe pipeline parallelism as a FRAMEWORK feature: train any (stateless)
MultiLayerConfiguration pipelined over the mesh's ``pipe`` axis.

Beyond-reference capability (SURVEY.md §2.5 — the reference is data-parallel
only). ``parallel/pipeline.py`` holds the low-level SPMD ring kernel; this
module makes it a first-class trainer:

- **Auto-partitioning**: the resolved layer list (preprocessors included) is
  split into ``pipe``-many CONTIGUOUS stages balanced by parameter count.
- **Heterogeneous stages in one SPMD program**: per-stage parameter pytrees
  are raveled to f32 vectors, zero-padded to the longest stage, and stacked
  [S, Lmax] — an ordinary array sharded P('pipe'). Each rank recovers ITS
  stage's tree with a static unravel inside ``lax.switch(rank, branches)``;
  XLA's conditional executes only the taken branch per device.
- **Unequal boundary widths**: inter-stage activations are flattened to
  [mb, Fmax] (max boundary width) with exact zero-pad on exit and slice +
  reshape on entry — no lossy projection, so GPipe training is numerically
  EQUIVALENT to single-device training (test_gpipe.py asserts parameter
  equality against plain MultiLayerNetwork.fit).
- **Real updater stack**: the configuration's updater (sgd/adam/rmsprop/...)
  runs on the stacked vectors + loss head — elementwise transforms are
  invariant to the ravel, so updates match the per-layer single-device math.
- **Listeners** fire per iteration like MultiLayerNetwork.fit.
- ``to_model()`` unravels the trained vectors back into an ordinary
  MultiLayerNetwork for inference/serialization/evaluation.

v2 additions:

- **BatchNorm**: train-mode normalization uses per-microbatch statistics
  (standard GPipe semantics); with a data axis > 1 the normalization unit
  is the per-device microbatch SHARD (no cross-shard sync-BN — collectives
  cannot live inside the rank switch). Each stage emits its BN layers'
  batch stats as a fixed-width [all means | all variances] aux vector per
  microbatch; across data shards the variances combine with the stable
  parallel-variance form (no E[x^2]-mean^2 cancellation), and the step
  chains the running-stat EMA over microbatches in order. With data=1 and
  n_micro=1 the trainer is EXACTLY the single-device full-batch step, BN
  included; with data=1, n_micro>1 it matches a single-device run that
  microbatches the same way — both asserted in test_gpipe.py.
- **Dropout and weight noise**: per-(microbatch, layer) keys derived as
  ``fold_in(fold_in(base_rng, micro), global_layer_index)`` (weight noise
  additionally fold_in(., 0x5EED), exactly like MultiLayerNetwork._forward)
  — a scheme a single-device reference reproduces exactly.
- **Per-layer updater overrides**: supported when the override is the
  same updater TYPE differing only in lr (incl. trainable=False == lr 0):
  every updater here is linear in lr with internally-consistent state, so
  a per-position scale vector on the stacked update is exact. Different
  types / non-lr field diffs stay rejected.
- **Per-stage rematerialization** (jax.checkpoint on every stage branch):
  the classic GPipe activation-memory optimization.

Round-5 additions (closing VERDICT r4 #5/#8):

- **Token-id pipelines**: an EmbeddingSequence first layer makes stage 0's
  ring input the raw [B, T] id array (exact in the f32 buffers, never cast
  to a lossy model dtype) — the TransformerLM flagship pipelines.
- **PP x TP composition** (``tp_axis``): the loss head computes OUTSIDE the
  rank switch in shared code, so its (vocab-sized) projection shards
  column-parallel over an ordinary GSPMD axis.
- **Gradient normalization + constraints**: applied per layer on the
  replicated stacked vectors via unravel → per-layer op → re-ravel
  (`_map_stage_layers`) — exact, because grads/params there equal the
  single-device trees.
- **Feature/label masks**: per-stage boundary masks (propagated once,
  statically checked shape-preserving) enter the switch as one
  [S, M, mb, W] operand; each branch threads its slice through its layers;
  the head scores with the label mask or the propagated feature mask.

v2 limitations (explicit, checked): non-BN stateful layers are rejected;
masks require a recurrent [B, T] layout whose mask stays shape-preserving
through every layer — the DP/TP paths cover the rest.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.nn.model import MultiLayerNetwork, _iter_batches
from deeplearning4j_tpu.parallel.pipeline import pvary
from deeplearning4j_tpu.parallel.ring import shard_map
from deeplearning4j_tpu.train.updaters import make_updater


def partition_layers(param_counts: Sequence[int], n_stages: int) -> List[Tuple[int, int]]:
    """Contiguous [start, end) ranges balanced by parameter count (greedy
    prefix split at target boundaries; every stage non-empty)."""
    n = len(param_counts)
    if n_stages > n:
        raise ValueError(f"{n_stages} stages for {n} layers")
    total = float(sum(param_counts)) or 1.0
    bounds = [0]
    acc = 0.0
    for i, c in enumerate(param_counts):
        acc += c
        # must leave enough layers for the remaining stages
        remaining_needed = n_stages - len(bounds)
        if len(bounds) < n_stages and acc >= total * len(bounds) / n_stages \
                and i + 1 <= n - remaining_needed:
            bounds.append(i + 1)
    while len(bounds) < n_stages:
        bounds.append(min(bounds[-1] + 1, n - (n_stages - len(bounds))))
    bounds.append(n)
    return [(bounds[i], bounds[i + 1]) for i in range(n_stages)]


class GPipeTrainer:
    """Pipeline-parallel trainer for a MultiLayerConfiguration.

    Usage::

        mesh = make_mesh(MeshSpec(data=2, pipe=2))
        tr = GPipeTrainer(conf, mesh, n_micro=4)
        tr.fit((x, y), epochs=3)
        model = tr.to_model()     # ordinary MultiLayerNetwork
    """

    def __init__(self, conf, mesh: Mesh, n_micro: int = 2,
                 pipe_axis: str = "pipe", data_axis: str = "data",
                 tp_axis: Optional[str] = None):
        self.conf = conf
        self.mesh = mesh
        self.n_micro = n_micro
        self.pipe_axis = pipe_axis
        self.data_axis = data_axis
        # PP x TP composition: the loss head (usually the vocab-sized
        # projection, the single largest matmul in an LM) runs OUTSIDE the
        # rank switch in shared post-pipeline code, so ordinary GSPMD
        # tensor parallelism applies there: shard its 2-D weights
        # column-parallel over ``tp_axis`` and XLA inserts the collectives.
        # (In-stage TP would need collectives inside lax.switch, which the
        # pipelined program cannot express — see module docstring.)
        self.tp_axis = tp_axis
        self.n_stages = mesh.shape[pipe_axis]
        if self.n_stages < 2:
            raise ValueError("GPipeTrainer needs a pipe axis of size >= 2")

        # Resolve via an ordinary network (preprocessors, n_in inference,
        # initial params) — single source of truth for layer semantics.
        self._ref = MultiLayerNetwork(conf).init()
        self._validate()

        body = list(range(len(self._ref.layers) - 1))   # loss head excluded
        self.head_idx = len(self._ref.layers) - 1
        self.head_cfg = self._ref.layers[self.head_idx]
        counts = [
            sum(int(np.prod(np.shape(l)))
                for l in jax.tree_util.tree_leaves(self._ref.params[i]))
            for i in body
        ]
        self.stage_ranges = partition_layers(counts, self.n_stages)

        self._build_stages()
        self.updater = make_updater(conf.updater)
        self._update_scales = self._build_update_scales()
        self.opt_state = self.updater.init((self.stacked, self.head_params))
        self.iteration = 0
        self.epoch = 0
        self.listeners: list = []
        self._step = None
        self._rng = jax.random.PRNGKey((conf.seed or 0) + 7919)

    def _build_update_scales(self):
        """Per-position lr scale [S, Lmax] for the stacked update + scalar
        head scale. Per-layer overrides must be the conf updater's TYPE
        differing only in lr; trainable=False scales to 0."""
        from deeplearning4j_tpu.train.updaters import normalize_updater

        base = dict(normalize_updater(self.conf.updater))
        base_lr = float(base.get("lr", 0.0)) or 1.0

        def layer_scale(layer) -> float:
            if not getattr(layer, "trainable", True):
                return 0.0
            ov = getattr(layer, "updater", None)
            if ov is None:
                return 1.0
            spec = dict(normalize_updater(ov))
            if spec.get("type") != base.get("type"):
                raise NotImplementedError(
                    "GPipeTrainer v2: per-layer updater override of a "
                    f"DIFFERENT type ({spec.get('type')} vs "
                    f"{base.get('type')}) is unsupported")
            rest_a = {k: v for k, v in spec.items() if k != "lr"}
            rest_b = {k: v for k, v in base.items() if k != "lr"}
            if rest_a != rest_b:
                raise NotImplementedError(
                    "GPipeTrainer v2: per-layer updater overrides may only "
                    "differ in lr")
            if base.get("type") == "adadelta":
                return 1.0  # adadelta has no lr
            return float(spec.get("lr", base_lr)) / base_lr

        scale = np.ones(self.stacked.shape, np.float32)
        for si, (s, e) in enumerate(self.stage_ranges):
            off = 0
            for gi in range(s, e):
                n = sum(int(np.prod(np.shape(l))) for l in
                        jax.tree_util.tree_leaves(self._ref.params[gi]))
                scale[si, off:off + n] = layer_scale(self._ref.layers[gi])
                off += n
        return jnp.asarray(scale), jnp.float32(layer_scale(self.head_cfg))

    # -- validation --------------------------------------------------------
    def _validate(self):
        from deeplearning4j_tpu.nn.layers import BatchNorm

        for i, layer in enumerate(self._ref.layers):
            name = type(layer).__name__
            if jax.tree_util.tree_leaves(self._ref.state[i]) and \
                    not isinstance(layer, BatchNorm):
                raise NotImplementedError(
                    f"GPipeTrainer v2: layer {i} ({name}) carries non-BN "
                    "running state — use DP/TP for such nets")

    # -- stage construction ------------------------------------------------
    def _build_stages(self):
        ref = self._ref
        mb_shapes = []       # static input shape (sans batch) per stage
        self._stage_layers = []
        vecs, unravels, self._stage_lens = [], [], []

        from deeplearning4j_tpu.nn.layers.core import EmbeddingSequence

        for (s, e) in self.stage_ranges:
            stage_params = tuple(ref.params[i] for i in range(s, e))
            vec, unravel = ravel_pytree(stage_params)
            vec = jnp.asarray(vec, jnp.float32)
            vecs.append(vec)
            unravels.append(unravel)
            self._stage_lens.append(vec.size)
            self._stage_layers.append(tuple(ref.layers[i] for i in range(s, e)))
            if s == 0 and isinstance(ref.layers[0], EmbeddingSequence):
                # token-id input: the real array is [B, T] integer ids, not
                # the [B, T, vocab] the recurrent InputType describes (ids
                # ride the f32 ring buffers exactly — vocab < 2^24)
                mb_shapes.append((ref.layer_input_types[0].timesteps,))
            else:
                mb_shapes.append(ref.layer_input_types[s].batch_shape(1)[1:])

        out_shape = ref.layer_input_types[self.head_idx].batch_shape(1)[1:]
        self._boundary_shapes = mb_shapes + [out_shape]
        flat_sizes = [int(np.prod(s)) for s in self._boundary_shapes]
        self.f_max = max(flat_sizes)
        self._in_shapes = mb_shapes
        self._in_sizes = flat_sizes[:-1]
        self.out_size = flat_sizes[-1]
        self.out_shape = out_shape

        l_max = max(self._stage_lens)
        self.stacked = jnp.stack([
            jnp.pad(v, (0, l_max - v.size)) for v in vecs
        ])  # [S, Lmax]
        self.stacked = jax.device_put(
            self.stacked, NamedSharding(self.mesh, P(self.pipe_axis)))
        self._unravels = unravels
        if self.tp_axis and self.mesh.shape.get(self.tp_axis, 1) > 1:
            # column-parallel head: 2-D weights sharded on the OUTPUT dim,
            # 1-D biases alike — GSPMD partitions the head matmul + loss
            def head_spec(a):
                n_tp = self.mesh.shape[self.tp_axis]
                if np.ndim(a) == 2 and np.shape(a)[1] % n_tp == 0:
                    return NamedSharding(self.mesh, P(None, self.tp_axis))
                if np.ndim(a) == 1 and np.shape(a)[0] % n_tp == 0:
                    return NamedSharding(self.mesh, P(self.tp_axis))
                return NamedSharding(self.mesh, P())

            self.head_params = jax.tree_util.tree_map(
                lambda a: jax.device_put(a, head_spec(a)),
                ref.params[self.head_idx])
        else:
            self.head_params = jax.device_put(
                ref.params[self.head_idx],
                NamedSharding(self.mesh, P()))

        # BN metadata per stage: (local pos, global layer idx, n_features,
        # decay, feature offset). The aux vector is laid out as TWO halves,
        # [all means | all variances]: a layout that is uniform across
        # ranks, so the cross-data-shard variance combine (the stable
        # parallel form, not E[x^2]-mean^2 cancellation) can run in shared
        # post-switch code.
        from deeplearning4j_tpu.nn.layers import BatchNorm

        self._stage_bn = []
        feat_widths = []
        for si, (s, e) in enumerate(self.stage_ranges):
            bns = []
            off = 0
            for lp, gi in enumerate(range(s, e)):
                layer = ref.layers[gi]
                if isinstance(layer, BatchNorm):
                    n = int(np.shape(ref.state[gi]["mean"])[0])
                    bns.append((lp, gi, n, float(layer.decay), off))
                    off += n
            self._stage_bn.append(bns)
            feat_widths.append(off)
        self.a_half = max(1, max(feat_widths) if feat_widths else 1)
        self.a_max = 2 * self.a_half
        # running stats, replicated (tiny [C] vectors), keyed by layer idx
        self.bn_state = {
            gi: {k: jnp.asarray(v, jnp.float32)
                 for k, v in ref.state[gi].items()}
            for bns in self._stage_bn for (_lp, gi, _n, _d, _off) in bns
        }

        # per-stage branch: [Lmax], [mb, Fmax], micro, rng
        #   -> ([mb, Fmax], [A_max])
        def make_branch(i):
            unravel = unravels[i]
            layers = self._stage_layers[i]
            in_size, in_shape = self._in_sizes[i], self._in_shapes[i]
            length = self._stage_lens[i]
            s0 = self.stage_ranges[i][0]
            # token-id stage input stays f32 (exact for vocab < 2^24): a
            # bf16 model-dtype cast would corrupt ids > 256
            is_ids = (i == 0 and isinstance(ref.layers[0], EmbeddingSequence))
            bn_at = {lp: (n, decay, off)
                     for (lp, _gi, n, decay, off) in self._stage_bn[i]}

            def branch(vec, xf, micro, rng, masks=None):
                params = unravel(vec[:length])
                x = xf[:, :in_size].reshape((xf.shape[0],) + tuple(in_shape))
                if not is_ids:
                    x = x.astype(self._ref.dtype)
                m = None
                if masks is not None and self._mask_meta and \
                        self._mask_meta[1][s0]:
                    # this stage's input mask for THIS microbatch (masks is
                    # the full [S, M, mb, W] stack — identical operand to
                    # every switch branch; each uses only its own row)
                    m = lax.dynamic_index_in_dim(
                        masks[i], micro, 0, keepdims=False)
                    m = m.astype(self._ref.dtype)
                aux = jnp.zeros((self.a_max,), jnp.float32)
                kmicro = jax.random.fold_in(rng, micro)
                for lp, (layer, p) in enumerate(zip(layers, params)):
                    # per-(micro, GLOBAL layer) key — reproducible by a
                    # single-device microbatched reference
                    lrng = jax.random.fold_in(kmicro, s0 + lp)
                    if layer.weight_noise:
                        # same keying as MultiLayerNetwork._forward
                        p = layer.maybe_weight_noise(
                            p, True, jax.random.fold_in(lrng, 0x5EED))
                    if lp in bn_at:
                        n, decay, off = bn_at[lp]
                        zero = {"mean": jnp.zeros((n,), jnp.float32),
                                "var": jnp.zeros((n,), jnp.float32)}
                        x, ns = layer.apply(p, zero, x, train=True, rng=lrng,
                                            mask=m)
                        # state was 0 => ns = (1-decay) * batch_stat
                        bmean = ns["mean"] / (1.0 - decay)
                        bvar = ns["var"] / (1.0 - decay)
                        aux = lax.dynamic_update_slice(
                            aux, lax.stop_gradient(bmean.astype(jnp.float32)),
                            (off,))
                        aux = lax.dynamic_update_slice(
                            aux, lax.stop_gradient(bvar.astype(jnp.float32)),
                            (self.a_half + off,))
                    else:
                        x, _ = layer.apply(p, self._ref.state[s0 + lp], x,
                                           train=True, rng=lrng, mask=m)
                    if m is not None:
                        m = layer.propagate_mask(
                            m, self._ref.layer_input_types[s0 + lp])
                out = x.reshape(x.shape[0], -1).astype(jnp.float32)
                pad = self.f_max - out.shape[1]
                out = jnp.pad(out, ((0, 0), (0, pad))) if pad else out
                # zero-valued but structurally REAL dependence on the rng
                # (and mask stack): branches must all consume the same
                # inputs or lax.switch's partial-eval produces mismatched
                # residual sets under grad (stages without dropout/masks
                # would otherwise DCE the operand)
                out = out + 0.0 * jax.random.uniform(
                    kmicro, (), dtype=out.dtype)
                if masks is not None:
                    out = out + 0.0 * masks.ravel()[0].astype(out.dtype)
                return out, aux

            return branch

        self._branches = [make_branch(i) for i in range(self.n_stages)]
        self._mask_meta = self._build_mask_meta()

    def _build_mask_meta(self):
        """Static mask topology for the pipelined mask channel: per-layer
        input-mask aliveness, decided ONCE by propagating a dummy [1, W]
        mask through the resolved layer list. Returns (W, alive[list]) for
        [B, T]-shaped recurrent feature masks, or None when this net can't
        take masks (non-recurrent input, or a layer that reshapes its
        mask — those nets use DP/TP)."""
        it0 = self.conf.input_type
        if getattr(it0, "kind", None) != "recurrent" or not it0.timesteps:
            return None
        W = int(it0.timesteps)
        m = jnp.ones((1, W), jnp.float32)
        alive = []
        for layer, it in zip(self._ref.layers, self._ref.layer_input_types):
            alive.append(m is not None)
            if m is not None:
                m = layer.propagate_mask(m, it)
                if m is not None:
                    if tuple(np.shape(m)) != (1, W):
                        return None  # mask-reshaping layer: unsupported
        return W, alive

    def _boundary_masks(self, fm):
        """Propagate the real [B, W] feature mask to every stage boundary
        plus the head input. Returns ([S, B, W] f32, head_mask or None)."""
        W, alive = self._mask_meta
        per_stage = []
        m = jnp.asarray(fm, jnp.float32)
        gi = 0
        for si, (s, e) in enumerate(self.stage_ranges):
            while gi < s:
                if m is not None:
                    m = self._ref.layers[gi].propagate_mask(
                        m, self._ref.layer_input_types[gi])
                gi += 1
            per_stage.append(m if m is not None else jnp.zeros(fm.shape, jnp.float32))
        while gi < self.head_idx:
            if m is not None:
                m = self._ref.layers[gi].propagate_mask(
                    m, self._ref.layer_input_types[gi])
            gi += 1
        return jnp.stack(per_stage), m

    # -- the SPMD pipelined step ------------------------------------------
    def _pipelined_forward(self, stacked, x_micro, rng, masks_all=None):
        """GPipe ring (the shared ``pipeline._gpipe_shard`` kernel) with a
        per-(stage, micro) aux channel: at step t each rank applies its
        stage and also emits its BN layers' batch stats. Returns
        (outs [M, mb, Fmax], aux [S, M, A_max]). ``masks_all``: optional
        [S, M, mb, W] per-stage-boundary feature masks (the mask channel —
        replicated across pipe, data-sharded on mb)."""
        from deeplearning4j_tpu.parallel.pipeline import _gpipe_shard

        branches = self._branches
        axis_name = self.pipe_axis
        data_axis = self.data_axis
        half = self.a_half

        def aux_combine(aux):
            # Cross-data-shard combine of the [means | local vars] halves,
            # OUTSIDE the rank switch (collectives inside a data-dependent
            # branch would not be statically matched across devices). The
            # parallel-variance form is numerically stable — no
            # E[x^2]-mean^2 cancellation (shards are equal-sized, so plain
            # pmeans are exact).
            mu = aux[:half]
            var_loc = aux[half:]
            mu_g = lax.pmean(mu, data_axis)
            var_g = (lax.pmean(var_loc, data_axis)
                     + lax.pmean((mu - mu_g) ** 2, data_axis))
            return jnp.concatenate([mu_g, var_g])

        def make_shard_fn(with_masks: bool):
            def shard_fn(params_local, x_mic, rng_, masks_=None):
                def _pvary(x):
                    return pvary(x, axis_name)

                # Each branch is rematerialized (jax.checkpoint): classic
                # GPipe per-stage activation recomputation, AND it makes
                # every branch's autodiff residuals = its inputs — identical
                # avals across branches, which lax.switch's partial-eval
                # requires (branches that differ in rng/dropout usage
                # otherwise produce unequal residual sets with mismatched
                # device-varying types). Outputs are normalized to
                # pipe-varying for the same reason.
                rng_v = jax.tree_util.tree_map(_pvary, rng_)
                extra = (_pvary(masks_),) if with_masks else ()
                wrapped = [
                    jax.checkpoint(lambda v, xx, mm, *rest, _b=b: tuple(
                        _pvary(o) for o in _b(v, xx, mm, *rest)))
                    for b in branches
                ]

                def stage_apply(params, x, micro):
                    idx = lax.axis_index(axis_name)
                    # every arm is collective-free (stage layers; outputs
                    # pvary-normalized) and check_vma stays on below
                    return lax.switch(idx, wrapped, params, x, micro,  # graftlint: disable=collective-consistency
                                      rng_v, *extra)

                return _gpipe_shard(
                    params_local, _pvary(x_mic), stage_apply=stage_apply,
                    axis_name=axis_name, n_stages=self.n_stages,
                    aux_width=self.a_max, aux_combine=aux_combine)
            return shard_fn

        xspec = P(None, self.data_axis)
        if masks_all is not None:
            in_specs = (P(self.pipe_axis), xspec, P(),
                        P(None, None, self.data_axis, None))
            fn, args = make_shard_fn(True), (stacked, x_micro, rng, masks_all)
        else:
            in_specs = (P(self.pipe_axis), xspec, P())
            fn, args = make_shard_fn(False), (stacked, x_micro, rng)
        out_specs = (xspec, P(self.pipe_axis))
        # NOTE: check_vma must stay ON here — _gpipe_shard's psum/ppermute
        # ring depends on the varying-axes machinery. Pallas kernels (whose
        # outputs carry no vma) therefore cannot run inside stages: the
        # fused-LSTM dispatch is suppressed at trace time (see
        # no_fused_lstm in fit_batch / nn/layers/recurrent.py).
        return shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                         out_specs=out_specs)(*args)

    def _loss(self, params, x_micro, y_micro, rng, masks_all=None,
              head_mask=None):
        stacked, head = params
        outs, aux = self._pipelined_forward(stacked, x_micro, rng, masks_all)
        M, mb = outs.shape[0], outs.shape[1]
        pre = outs[:, :, :self.out_size].reshape(
            (M * mb,) + tuple(self.out_shape)).astype(self._ref.dtype)
        y = y_micro.reshape((M * mb,) + tuple(y_micro.shape[2:]))
        total = self.head_cfg.score(head, pre, y, mask=head_mask, average=True)
        # l1/l2 penalties, computed on the (replicated) stacked vectors —
        # same terms the single-device step adds
        for si in range(self.n_stages):
            tree = self._unravels[si](stacked[si, :self._stage_lens[si]])
            for layer, p in zip(self._stage_layers[si], tree):
                total = total + layer.regularization_penalty(p)
        return total + self.head_cfg.regularization_penalty(head), aux

    def _chain_bn_states(self, bn_state, aux):
        """EMA-chain each BN layer's running stats over the microbatches in
        order: s_{m+1} = d*s_m + (1-d)*batch_m (exactly what a
        single-device microbatched run produces). aux rows are laid out as
        [all means | all variances] halves (data-axis-aggregated via the
        stable parallel-variance combine)."""
        M = aux.shape[1]
        half = self.a_half
        new_state = {}
        for si, bns in enumerate(self._stage_bn):
            for (_lp, gi, n, decay, off) in bns:
                mean = bn_state[gi]["mean"]
                var = bn_state[gi]["var"]
                for m in range(M):
                    bm = aux[si, m, off:off + n]
                    bv = aux[si, m, half + off:half + off + n]
                    mean = decay * mean + (1.0 - decay) * bm
                    var = decay * var + (1.0 - decay) * bv
                new_state[gi] = {"mean": mean, "var": var}
        return new_state

    def _map_stage_layers(self, stacked_vecs, fn):
        """Unravel each stage row, apply ``fn(global_idx, layer, tree) ->
        tree`` per layer, re-ravel. Runs inside the jitted step on the
        replicated [S, Lmax] vectors (cheap elementwise/norm math) — the
        channel that makes per-layer gradient normalization and post-update
        constraints EXACT under pipelining."""
        rows = []
        for si in range(self.n_stages):
            tree = list(self._unravels[si](
                stacked_vecs[si, :self._stage_lens[si]]))
            s, e = self.stage_ranges[si]
            changed = False
            for off, gi in enumerate(range(s, e)):
                new = fn(gi, self._ref.layers[gi], tree[off])
                if new is not tree[off]:
                    tree[off] = new
                    changed = True
            if not changed:
                rows.append(stacked_vecs[si])
                continue
            vec, _ = ravel_pytree(tuple(tree))
            vec = jnp.asarray(vec, jnp.float32)
            rows.append(jnp.pad(vec, (0, stacked_vecs.shape[1] - vec.size)))
        return jnp.stack(rows)

    def make_train_step(self):
        from deeplearning4j_tpu.nn.constraints import apply_constraints
        from deeplearning4j_tpu.train.updaters import (
            apply_gradient_normalization)

        updater = self.updater
        scale, head_scale = self._update_scales
        has_gn = any(getattr(l, "gradient_normalization", None)
                     for l in self._ref.layers)
        has_cn = any(getattr(l, "constraints", None) for l in self._ref.layers)

        def norm_grads(grads):
            sg, hg = grads

            def norm_one(_gi, layer, g_tree):
                gn = getattr(layer, "gradient_normalization", None)
                if not gn or not jax.tree_util.tree_leaves(g_tree):
                    return g_tree
                return apply_gradient_normalization(
                    gn, getattr(layer, "gradient_normalization_threshold", 1.0),
                    g_tree)

            sg = self._map_stage_layers(sg, norm_one)
            gn = getattr(self.head_cfg, "gradient_normalization", None)
            if gn:
                hg = apply_gradient_normalization(
                    gn, getattr(self.head_cfg,
                                "gradient_normalization_threshold", 1.0), hg)
            return sg, hg

        def constrain(params):
            stacked, head = params

            def con_one(_gi, layer, p_tree):
                if not getattr(layer, "constraints", None) or \
                        not jax.tree_util.tree_leaves(p_tree):
                    return p_tree
                return apply_constraints(layer, p_tree)

            stacked = self._map_stage_layers(stacked, con_one)
            if getattr(self.head_cfg, "constraints", None):
                head = apply_constraints(self.head_cfg, head)
            return stacked, head

        def apply_update(params, opt_state, bn_state, it, loss, aux, grads):
            upd, new_opt = updater.update(grads, opt_state, params, it)
            su, hu = upd
            # per-position lr scale (per-layer overrides / frozen layers);
            # exact because every updater here is linear in lr with
            # internally-consistent state (see module docstring)
            su = su * scale
            hu = jax.tree_util.tree_map(lambda d: d * head_scale, hu)
            stacked, head = params
            new_params = (stacked - su,
                          jax.tree_util.tree_map(lambda p, d: p - d, head, hu))
            if has_cn:
                new_params = constrain(new_params)
            new_bn = self._chain_bn_states(bn_state, aux)
            return new_params, new_opt, new_bn, loss

        def step(params, opt_state, bn_state, it, x_micro, y_micro, rng,
                 masks_all=None, head_mask=None):
            (loss, aux), grads = jax.value_and_grad(
                self._loss, has_aux=True)(params, x_micro, y_micro, rng,
                                          masks_all, head_mask)
            return apply_update(params, opt_state, bn_state, it, loss, aux,
                                grads)

        from deeplearning4j_tpu.nn.step_program import StepProgram

        if not has_gn:
            # aot_wrap=False: the gpipe stage-switched executable is built
            # per trainer and warmed by its first dispatch (no bucket ladder
            # over [S, M, mb, W] stacks); StepProgram still owns the
            # donate/trace policy and the cost-exemplar harvest
            return StepProgram(step, "gpipe.step", aot_wrap=False)

        # Gradient normalization must NOT run inside a jitted executable
        # that also sees the pipe-sharded state: the GSPMD partitioner
        # resolves the nonlinear clip/renorm intermediate inconsistently
        # between its consumers — the norm is taken over the per-replica
        # value while the downstream subtraction consumes a spuriously
        # all-reduced copy, scaling the applied update by exactly the
        # data*seq replica count (observed 4x on a data=2 x seq=2 mesh).
        # Sharding constraints, optimization barriers, and materializing
        # the gradients at a jit boundary all fail to stop it; only fully
        # replicated operands compile correctly, which would defeat the
        # pipe-sharded parameter layout. So the clip math runs EAGERLY on
        # the [S, Lmax] stage vectors between the two executables — a few
        # tiny elementwise/norm dispatches per step, only for gn-bearing
        # configs — and the (linear-in-grads) updater half stays jitted.
        # (standalone repro: tools/repro_gpipe_clip_miscompile.py — retire
        # this split once a fixed XLA lands and the repro exits 2)
        grads_jit = StepProgram(
            lambda params, x_micro, y_micro, rng, masks_all=None,
            head_mask=None: jax.value_and_grad(self._loss, has_aux=True)(
                params, x_micro, y_micro, rng, masks_all, head_mask),
            "gpipe.grads", donate_argnums=(), aot_wrap=False)
        update_jit = StepProgram(apply_update, "gpipe.update", aot_wrap=False)

        def split_step(params, opt_state, bn_state, it, x_micro, y_micro,
                       rng, masks_all=None, head_mask=None):
            (loss, aux), grads = grads_jit(params, x_micro, y_micro, rng,
                                           masks_all, head_mask)
            grads = norm_grads(grads)  # eager: see partitioner note above
            return update_jit(params, opt_state, bn_state, it, loss, aux,
                              grads)

        return split_step

    # -- training API ------------------------------------------------------
    def fit_batch(self, x, y, fm=None, lm=None):
        x, y = np.asarray(x), np.asarray(y)
        B = x.shape[0]
        if B % self.n_micro:
            raise ValueError(
                f"batch size {B} must be divisible by n_micro={self.n_micro}")
        mb = B // self.n_micro
        n_data = self.mesh.shape[self.data_axis]
        if mb % n_data:
            raise ValueError(
                f"microbatch size {mb} (= {B}/{self.n_micro}) must be "
                f"divisible by the '{self.data_axis}' mesh axis ({n_data})")
        xm = jnp.asarray(x.reshape((self.n_micro, mb) + x.shape[1:]), jnp.float32)
        # ring buffers carry FLAT activations: flatten+pad input to Fmax
        xm = xm.reshape(self.n_micro, mb, -1)
        pad = self.f_max - xm.shape[-1]
        if pad:
            xm = jnp.pad(xm, ((0, 0), (0, 0), (0, pad)))
        ym = jnp.asarray(y.reshape((self.n_micro, mb) + y.shape[1:]))
        self._rng, k = jax.random.split(self._rng)
        from deeplearning4j_tpu.nn.layers.recurrent import no_fused_lstm

        args = ((self.stacked, self.head_params), self.opt_state,
                self.bn_state, jnp.asarray(self.iteration, jnp.int32),
                xm, ym, k)
        if fm is None and lm is None:
            if self._step is None:
                self._step = self.make_train_step()
            with no_fused_lstm():   # stage switch can't host pallas (vma)
                out = self._step(*args)
        else:
            # mask channel (round 5): per-stage boundary masks ride into
            # the switch as one [S, M, mb, W] stack; the head scores with
            # the label mask (preferred) or the propagated feature mask
            if self._mask_meta is None:
                raise NotImplementedError(
                    "GPipeTrainer masks need a recurrent [B, T] input whose "
                    "mask keeps its shape through every layer — use DP/TP "
                    "for other mask layouts")
            if fm is not None:
                per_stage, head_m = self._boundary_masks(jnp.asarray(fm))
                masks_all = per_stage.reshape(
                    (self.n_stages, self.n_micro, mb, per_stage.shape[-1]))
            else:
                # label-mask-only: no feature-mask channel needed — the
                # single-device step likewise only scores the head with lm
                masks_all, head_m = None, None
            head_mask = jnp.asarray(lm) if lm is not None else head_m
            key = (masks_all is not None, head_mask is not None)
            if getattr(self, "_step_m", None) is None:
                self._step_m = {}
            if key not in self._step_m:
                self._step_m[key] = self.make_train_step()
            with no_fused_lstm():   # stage switch can't host pallas (vma)
                out = self._step_m[key](*args, masks_all, head_mask)
        ((self.stacked, self.head_params), self.opt_state, self.bn_state,
         loss) = out
        self.iteration += 1
        return loss

    def fit(self, data, epochs: int = 1, batch_size: Optional[int] = None):
        for _ in range(epochs):
            for l in self.listeners:
                l.on_epoch_start(self, self.epoch)
            source = data() if callable(data) else data
            for x, y, fm, lm in _iter_batches(source, batch_size):
                loss = self.fit_batch(x, y, fm, lm)
                if self.listeners:
                    loss = float(loss)
                    for l in self.listeners:
                        l.iteration_done(self, self.iteration, loss, len(x))
            for l in self.listeners:
                l.on_epoch_end(self, self.epoch)
            self.epoch += 1
        return self

    def set_listeners(self, *ls):
        self.listeners = list(ls)
        return self

    # -- back to an ordinary model ----------------------------------------
    def to_model(self) -> MultiLayerNetwork:
        """Unravel the trained stage vectors into a plain MultiLayerNetwork
        (params host-local, ready for output/evaluate/serialization)."""
        model = MultiLayerNetwork(self.conf).init()
        stacked = np.asarray(jax.device_get(self.stacked))
        new_params = list(model.params)
        for si, (s, e) in enumerate(self.stage_ranges):
            tree = self._unravels[si](
                jnp.asarray(stacked[si, :self._stage_lens[si]]))
            for off, i in enumerate(range(s, e)):
                new_params[i] = jax.tree_util.tree_map(
                    lambda a: jnp.asarray(a, model.dtype), tree[off])
        new_params[self.head_idx] = jax.tree_util.tree_map(
            lambda a: jnp.asarray(jax.device_get(a), model.dtype),
            self.head_params)
        model.params = tuple(new_params)
        new_state = list(model.state)
        for gi, st in self.bn_state.items():
            new_state[gi] = {k: jnp.asarray(jax.device_get(v), jnp.float32)
                             for k, v in st.items()}
        model.state = tuple(new_state)
        model.iteration = self.iteration
        model.epoch = self.epoch
        return model
