"""Sequential model: MultiLayerConfiguration + MultiLayerNetwork.

Capability parity with the reference's
nn/multilayer/MultiLayerNetwork.java (3,538 LoC: init:548, feedForward:878,
fit:1261, output:2005, computeGradientAndScore:2353) and
nn/conf/MultiLayerConfiguration.java — re-designed TPU-first:

- The whole training iteration (forward, loss, autodiff backward, gradient
  normalization, updater, parameter update) is ONE pure function traced and
  compiled ONCE by XLA, with params/opt-state donated so updates happen
  in-place in HBM. The reference instead drives ~1 JNI kernel dispatch per op
  per layer per iteration (SURVEY.md §3.1).
- Parameters are a pytree (tuple of per-layer dicts), not a flattened view;
  optimizer state lives in a parallel pytree (no UpdaterBlocks).
- Backward comes from jax.grad of the step — the per-layer
  ``backpropGradient`` methods of the reference do not exist.
- Truncated BPTT (MultiLayerNetwork.doTruncatedBPTT:1514) is scan-over-chunks
  with carried RNN state; ``rnn_time_step`` keeps carries on device between
  calls (rnnTimeStep:2371 equivalents).
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.analysis import retrace_guard
from deeplearning4j_tpu.nn import aot
from deeplearning4j_tpu.nn.config import LayerConfig, layer_from_dict, _encode_value
from deeplearning4j_tpu.utils import bucketing
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrent
from deeplearning4j_tpu.nn.preprocessors import infer_preprocessor
from deeplearning4j_tpu.train.updaters import (
    apply_gradient_normalization,
    make_updater,
    normalize_updater,
    scale_lr,
)


@dataclass
class MultiLayerConfiguration:
    """Sequential-network config (MultiLayerConfiguration.java parity).

    ``updater`` is the network default; a layer's ``updater`` field overrides
    it (DL4J per-layer updater semantics). JSON round-trip via
    to_json/from_json is the long-lived artifact contract (§5.6).
    """

    layers: Tuple[LayerConfig, ...] = ()
    input_type: Optional[InputType] = None
    seed: int = 12345
    updater: Any = "sgd"
    dtype: str = "float32"
    backprop_type: str = "standard"        # "standard" | "tbptt"
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20
    # OptimizationAlgorithm dispatch (optimize/Solver.java:50-80):
    # "stochastic_gradient_descent" (the jitted step) or one of the
    # deterministic solvers in train/solvers.py
    optimization_algo: str = "stochastic_gradient_descent"
    solver_iterations: int = 5             # solver steps per batch (non-SGD)

    def __post_init__(self):
        self.layers = tuple(self.layers)

    # -- serde -------------------------------------------------------------
    def to_dict(self) -> dict:
        return {
            "format": "deeplearning4j_tpu/MultiLayerConfiguration",
            "version": 1,
            "layers": [l.to_dict() for l in self.layers],
            "input_type": self.input_type.to_dict() if self.input_type else None,
            "seed": self.seed,
            "updater": _encode_value(self.updater),
            "dtype": self.dtype,
            "backprop_type": self.backprop_type,
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "tbptt_back_length": self.tbptt_back_length,
            "optimization_algo": self.optimization_algo,
            "solver_iterations": self.solver_iterations,
        }

    def to_json(self, **kw) -> str:
        return json.dumps(self.to_dict(), **kw)

    @staticmethod
    def from_dict(d: dict) -> "MultiLayerConfiguration":
        return MultiLayerConfiguration(
            layers=tuple(layer_from_dict(ld) for ld in d["layers"]),
            input_type=InputType.from_dict(d["input_type"]) if d.get("input_type") else None,
            seed=d.get("seed", 12345),
            updater=d.get("updater", "sgd"),
            dtype=d.get("dtype", "float32"),
            backprop_type=d.get("backprop_type", "standard"),
            tbptt_fwd_length=d.get("tbptt_fwd_length", 20),
            tbptt_back_length=d.get("tbptt_back_length", 20),
            optimization_algo=d.get("optimization_algo", "stochastic_gradient_descent"),
            solver_iterations=d.get("solver_iterations", 5),
        )

    @staticmethod
    def from_json(s: str) -> "MultiLayerConfiguration":
        return MultiLayerConfiguration.from_dict(json.loads(s))

    def to_yaml(self) -> str:
        """YAML twin of to_json (MultiLayerConfiguration.toYaml parity)."""
        from deeplearning4j_tpu.nn.config import yaml_dump

        return yaml_dump(self.to_dict())

    @staticmethod
    def from_yaml(s: str) -> "MultiLayerConfiguration":
        from deeplearning4j_tpu.nn.config import yaml_load

        return MultiLayerConfiguration.from_dict(yaml_load(s))


def _cast_input(x, dtype):
    """Cast a feature array to the model dtype, PRESERVING (a) integer/bool
    dtypes (token ids must not round-trip through bf16 — ids >256 would
    corrupt) and (b) float64 arrays (the x64 gradient-check path drives the
    model at double precision on purpose)."""
    if x is None:
        return None
    x = jnp.asarray(x)
    if (
        jnp.issubdtype(x.dtype, jnp.integer)
        or x.dtype == jnp.bool_
        or x.dtype == jnp.float64
    ):
        return x
    return x.astype(dtype)


def _as_batch(batch):
    """Normalize a batch to (features, labels, features_mask, labels_mask).

    Accepts (x, y), (x, y, fmask), (x, y, fmask, lmask) tuples, a dict with
    those keys, or a DataSet object — the DataSet surface of the reference.
    """
    if hasattr(batch, "as_tuple"):  # datasets.DataSet / MultiDataSet
        batch = batch.as_tuple()
    if isinstance(batch, dict):
        return (
            batch["features"],
            batch.get("labels"),
            batch.get("features_mask"),
            batch.get("labels_mask"),
        )
    if isinstance(batch, (tuple, list)):
        x = batch[0]
        y = batch[1] if len(batch) > 1 else None
        fm = batch[2] if len(batch) > 2 else None
        lm = batch[3] if len(batch) > 3 else None
        return x, y, fm, lm
    return batch, None, None, None


# The shared micro-batching policy (chained dispatch, grad-accumulation
# scan) and the compiled-step wiring now live in nn/step_program.py — the
# single step-program module (ISSUE 13). The underscore aliases keep the
# historical import surface (nn.graph, parallel/, tests) intact.
from deeplearning4j_tpu.nn.step_program import (  # noqa: F401,E402
    CHAIN_AUTO_PARAM_LIMIT,
    StepProgram,
    StepReports,
    accum_applicable as _accum_applicable,
    accum_value_and_grad as _accum_value_and_grad,
    chain_k_from_env as _chain_k_from_env,
    grad_accum_from_env as _grad_accum_from_env,
    layer_scope,
)


def _sig_dtype(a):
    # prefer the dtype attribute: np.asarray on a device array would pull
    # it back to host just to read metadata (hurts the prefetched-fit path)
    dt = getattr(a, "dtype", None)
    return np.dtype(dt if dt is not None else np.asarray(a).dtype).str


def _batch_sig(arrays) -> tuple:
    """Shape+dtype signature used to decide whether two batches may share
    one chained dispatch (same-shape different-dtype batches must NOT be
    stacked: jnp.stack would silently dtype-promote, e.g. routing sparse
    integer labels through the dense-loss path)."""
    return tuple((np.shape(a), _sig_dtype(a))
                 for a in arrays if a is not None)


def _cast_labels(y, dtype):
    """Model-dtype cast that PRESERVES integer (sparse) class labels — the
    loss head's sparse path needs the integer dtype intact."""
    if y is None:
        return None
    y = jnp.asarray(y)
    return y if jnp.issubdtype(y.dtype, jnp.integer) else y.astype(dtype)


def _iter_batches(data, batch_size=None):
    """Yield batches from (x, y[, masks]) arrays (optionally minibatched), a
    DataSet object, or any iterable of batches."""
    if hasattr(data, "as_tuple"):  # datasets.DataSet: unpack, then minibatch
        data = data.as_tuple()
    if isinstance(data, (tuple, list)) and len(data) >= 2 and not isinstance(data[0], (tuple, list, dict)):
        x, y, fm, lm = _as_batch(data)
        n = len(x)
        if batch_size is None or batch_size >= n:
            yield (x, y, fm, lm)
            return
        for i in range(0, n, batch_size):  # final partial batch included
            sl = slice(i, min(i + batch_size, n))
            yield (
                x[sl],
                y[sl] if y is not None else None,
                fm[sl] if fm is not None else None,
                lm[sl] if lm is not None else None,
            )
        return
    for b in data:
        yield _as_batch(b)


def _fit_pad_target(source, batch_size) -> Optional[int]:
    """Uniform per-batch row count for a fit() over in-memory arrays, or None.

    When minibatching arrays whose length is not a multiple of batch_size,
    the final partial batch would otherwise trace a SECOND training
    executable just for its odd shape. Returns batch_size in that case so
    every batch — including the tail, padded with zero example-weights — runs
    through one executable. Streaming iterables return None: their batch
    shapes aren't knowable up front, and padding only the surprise tail
    would still cost the extra ew/lmask trace it tries to avoid."""
    if batch_size is None:
        return None
    if hasattr(source, "as_tuple"):
        source = source.as_tuple()
    if (isinstance(source, (tuple, list)) and len(source) >= 2
            and not isinstance(source[0], (tuple, list, dict))):
        n = len(source[0])
        if n > batch_size and n % batch_size != 0:
            return batch_size
    return None


def _device_prefetch_enabled() -> bool:
    import os as _os

    return _os.environ.get("DL4J_TPU_DEVICE_PREFETCH", "1") != "0"


class MultiLayerNetwork:
    """Stateful model facade over pure jitted functions.

    Mutable host state: ``params``, ``state`` (BN running stats etc.),
    ``opt_state``, ``iteration``. The jitted step itself is pure; this class
    is the ergonomic shell matching the reference's MultiLayerNetwork API
    (init/fit/output/score/evaluate/rnnTimeStep).
    """

    def __init__(self, conf: MultiLayerConfiguration):
        if conf.input_type is None:
            raise ValueError("MultiLayerConfiguration.input_type is required")
        self.conf = conf
        self.dtype = jnp.dtype(conf.dtype)
        self._resolve_layers()
        self.params = None
        self.state = None
        self.opt_state = None
        self.iteration = 0
        self.epoch = 0
        self.batch_in_epoch = 0
        self._rng = jax.random.PRNGKey(conf.seed)
        self._step_fn = None
        self._tbptt_step_fn = None
        self._output_fn = None
        self._rnn_carries: Optional[list] = None
        self.listeners: list = []
        self.divergence_guard = None
        self._lr_scale = 1.0
        self._pending_residuals = None

    # -- resolution: preprocessors + n_in inference + per-layer input types --
    def _resolve_layers(self):
        layers: List[LayerConfig] = []
        input_types: List[InputType] = []
        it = self.conf.input_type
        for layer in self.conf.layers:
            pre = infer_preprocessor(it, layer)
            if pre is not None:
                layers.append(pre)
                input_types.append(it)
                it = pre.output_type(it)
            if hasattr(layer, "with_n_in"):
                layer = layer.with_n_in(layer.infer_n_in(it))
            layers.append(layer)
            input_types.append(it)
            it = layer.output_type(it)
        self.layers: List[LayerConfig] = layers
        self.layer_input_types: List[InputType] = input_types
        self.output_type: InputType = it
        self._carry_flags = [
            isinstance(l, BaseRecurrent) and getattr(l, "SUPPORTS_CARRY", False) for l in layers
        ]
        out = self.layers[-1]
        self._has_loss_head = hasattr(out, "score")

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    # -- init --------------------------------------------------------------
    def init(self, seed: Optional[int] = None) -> "MultiLayerNetwork":
        key = jax.random.PRNGKey(self.conf.seed if seed is None else seed)
        keys = jax.random.split(key, len(self.layers))
        self.params = tuple(
            l.init(k, it, self.dtype) for l, k, it in zip(self.layers, keys, self.layer_input_types)
        )
        self.state = tuple(l.init_state(it) for l, it in zip(self.layers, self.layer_input_types))
        self._build_updaters()
        self.opt_state = tuple(u.init(p) for u, p in zip(self._updaters, self.params))
        self.iteration = 0
        self.epoch = 0
        return self

    def _build_updaters(self):
        # _lr_scale is the divergence-guard rollback backoff (resilience.py);
        # 1.0 outside rollback, so this is normalize_updater by default
        scale = float(getattr(self, "_lr_scale", 1.0))
        default = scale_lr(self.conf.updater, scale)
        self._updaters = []
        for l in self.layers:
            if not getattr(l, "trainable", True):
                self._updaters.append(make_updater("noop"))
            elif getattr(l, "updater", None) is not None:
                self._updaters.append(make_updater(scale_lr(l.updater, scale)))
            else:
                self._updaters.append(make_updater(default))

    def _clear_compiled(self):
        """Drop compiled step closures (updaters or divergence-guard config
        changed — both are baked into the trace). AOT-warmed step
        executables are stale for the same reason; the output path is
        untouched (inference doesn't trace updaters or guards)."""
        self._step_fn = None
        self._tbptt_step_fn = None
        self._chain_step_fn = None
        self._solver = None
        aot.clear_sites(self, ("mln.step", "mln.step.tbptt"))

    def set_divergence_guard(self, guard) -> "MultiLayerNetwork":
        """Install a train/resilience.DivergenceGuard (None to remove).
        Clears compiled step caches: the skip_batch policy's select is traced
        into the step executable."""
        self.divergence_guard = guard
        self._clear_compiled()
        runner = getattr(self, "_dp_runner", None)
        if runner is not None:
            runner.rebuild_step()
        return self

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(self.params))

    # -- forward -----------------------------------------------------------
    def _forward(self, params, state, x, *, train, rngs, fmask=None, carries=None,
                 upto: Optional[int] = None, collect=False, ex_weight=None,
                 deterministic=False):
        """Walk the layer stack. Returns (act, new_state, new_carries, mask,
        activations_list). ``ex_weight`` is a per-example [B] validity weight
        consumed only by layers that declare CONSUMES_EXAMPLE_WEIGHT
        (BatchNorm excludes zero-weighted padding rows from batch stats).
        ``deterministic`` (score(train=True) path): layers whose train-mode
        apply draws randomness (dropout / weight noise — ``uses_rng``) run in
        eval mode while everything else keeps train-mode semantics, so
        normalization layers still use batch statistics but the result is a
        pure function of (params, state, x)."""
        n = len(self.layers) if upto is None else upto
        acts_list = []
        new_state = list(state)
        new_carries = list(carries) if carries is not None else None
        mask = fmask
        a = _cast_input(x, self.dtype)
        for i in range(n):
            layer = self.layers[i]
            lrng = rngs[i] if rngs is not None else None
            ltrain = train and not (deterministic and layer.uses_rng())
            p_i = params[i]
            if ltrain and layer.weight_noise and lrng is not None:
                # separate stream from input dropout on the same layer
                p_i = layer.maybe_weight_noise(p_i, ltrain, jax.random.fold_in(lrng, 0x5EED))
            with layer_scope(layer, i):
                if new_carries is not None and self._carry_flags[i]:
                    a2 = layer.maybe_dropout_input(a, ltrain, lrng)
                    a, c = layer.apply_seq(p_i, a2, new_carries[i], mask)
                    new_carries[i] = c
                    ns = state[i]
                elif ex_weight is not None and getattr(layer, "CONSUMES_EXAMPLE_WEIGHT", False):
                    a, ns = layer.apply(p_i, state[i], a, train=ltrain, rng=lrng,
                                        mask=mask, ex_weight=ex_weight)
                else:
                    # a layer that reads other layers' parameters (a head tied
                    # to the embedding) is handed them by reference, as _loss does
                    shared = getattr(layer, "shared_params", dict)()
                    kw = {"shared": {k: params[j] for k, j in shared.items()}} \
                        if shared else {}
                    a, ns = layer.apply(p_i, state[i], a, train=ltrain, rng=lrng,
                                        mask=mask, **kw)
            new_state[i] = ns
            mask = layer.propagate_mask(mask, self.layer_input_types[i])
            if collect:
                acts_list.append(a)
        return a, tuple(new_state), (tuple(new_carries) if new_carries is not None else None), mask, acts_list

    def _layer_rngs(self, rng):
        return list(jax.random.split(rng, len(self.layers)))

    def feed_forward(self, x, train: bool = False):
        """All layer activations (MultiLayerNetwork.feedForward:878). Debug /
        inspection path — not jitted."""
        rngs = self._layer_rngs(self._next_rng()) if train else None
        _, _, _, _, acts = self._forward(
            self.params, self.state, x, train=train, rngs=rngs, collect=True
        )
        return acts

    def _next_rng(self):
        self._rng, k = jax.random.split(self._rng)
        return k

    # -- loss --------------------------------------------------------------
    def _loss(self, params, state, x, y, fmask, lmask, rngs, carries=None, train=True,
              ex_weight=None, deterministic=False):
        """Average score incl. L1/L2 penalties; returns (loss, (new_state, carries))."""
        a, new_state, new_carries, prop_mask, _ = self._forward(
            params, state, x, train=train, rngs=rngs, fmask=fmask,
            carries=carries, upto=len(self.layers) - 1, ex_weight=ex_weight,
            deterministic=deterministic,
        )
        out_layer = self.layers[-1]
        out_mask = lmask if lmask is not None else prop_mask
        with jax.named_scope("loss"):
            shared = getattr(out_layer, "shared_params", dict)()
            with layer_scope(out_layer, len(self.layers) - 1):
                if shared or getattr(out_layer, "SCORES_STATE", False):
                    # an output layer that reads other layers' parameters (a
                    # second head over the embedding) declares them and is
                    # handed them by reference: jax.grad sums the uses; one
                    # that keeps step counters returns its state with the loss
                    loss, out_state = out_layer.score_shared(
                        params[-1], state[-1], a, y,
                        shared={k: params[i] for k, i in shared.items()},
                        mask=out_mask, train=train,
                        rng=rngs[-1] if rngs is not None else None)
                    new_state = new_state[:-1] + (out_state,)
                else:
                    loss = out_layer.score(params[-1], a, y, mask=out_mask, average=True)
            # Unconditional: wrapper layers (Bidirectional etc.) delegate to their
            # inner layer's l1/l2 even when the wrapper's own are zero.
            reg = sum(l.regularization_penalty(p) for l, p in zip(self.layers, params))
            return loss + reg, (new_state, new_carries)

    # -- jitted step -------------------------------------------------------
    def _make_step(self, with_carries: bool) -> StepProgram:
        site = "mln.step.tbptt" if with_carries else "mln.step"
        return StepProgram(self._step_body(with_carries), site, model=self,
                           hits_site="mln.fit")

    def _step_body(self, with_carries: bool, grad_exchange=None):
        """The pure training-step closure. ``grad_exchange`` (a
        ``parallel.grads.GradExchange``) replaces the per-layer update loop
        with an explicit cross-replica exchange; the body then runs under
        shard_map with per-replica local batches, the opt_state slot carries
        ``(opt_state, residuals)``, and loss/state are replica-means — the
        step's signature and return arity are unchanged."""
        from deeplearning4j_tpu.train import resilience

        layers = self.layers
        # divergence-guard skip_batch: the accept/reject select is traced
        # INTO the step (device-side; no extra host sync)
        guard = getattr(self, "divergence_guard", None)
        g_skip = bool(guard is not None and guard.policy == "skip_batch")
        g_limit = None if guard is None else guard.spike_limit
        # gradient-accumulation micro-batch count, baked at step-build time
        accum = _grad_accum_from_env()

        def step(params, opt_state, state, it, rng, x, y, fmask, lmask, carries,
                 ex_weight=None):
            # python body runs once per trace → counts actual compiles
            bucketing.telemetry().record_trace("mln.step", np.shape(x))
            if grad_exchange is not None:
                opt_state, residuals = opt_state
            batch = (x, y, fmask, lmask, ex_weight)
            if not with_carries and _accum_applicable(accum, batch):
                # DL4J_TPU_GRAD_ACCUM: scan over micro-batches, average the
                # grads, run the (single) update/exchange below on the mean —
                # grad_exchange therefore still exchanges ONCE per step
                def make_loss_fn(mb, st, k):
                    x_i, y_i, fm_i, lm_i, ew_i = mb
                    rngs_i = list(jax.random.split(k, len(layers)))

                    def loss_fn(p):
                        return self._loss(p, st, x_i, y_i, fm_i, lm_i, rngs_i,
                                          None, ex_weight=ew_i)

                    return loss_fn

                loss, new_state, grads = _accum_value_and_grad(
                    accum, params, state, batch, rng, make_loss_fn)
                new_carries = None
            else:
                rngs = list(jax.random.split(rng, len(layers)))

                def loss_fn(p):
                    return self._loss(p, state, x, y, fmask, lmask, rngs,
                                      carries if with_carries else None,
                                      ex_weight=ex_weight)

                (loss, (new_state, new_carries)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params)

            if grad_exchange is not None:
                loss = grad_exchange.mean_loss(loss)
                new_state = grad_exchange.mean_state(new_state)
                new_params, new_opt, new_res = grad_exchange.update(
                    grads, params, opt_state, residuals, it)
                if g_skip:
                    # loss is already the replica mean → ok is replicated
                    ok = resilience.guard_ok(loss, g_limit)
                    new_params = resilience.guard_select(ok, new_params, params)
                    new_opt = resilience.guard_select(ok, new_opt, opt_state)
                    new_res = resilience.guard_select(ok, new_res, residuals)
                    new_state = resilience.guard_select(ok, new_state, state)
                return (new_params, (new_opt, new_res), new_state,
                        new_carries, loss)

            out_params, out_opt = self._update_params(
                params, opt_state, grads, it)
            if g_skip:
                ok = resilience.guard_ok(loss, g_limit)
                out_params = resilience.guard_select(ok, out_params, params)
                out_opt = resilience.guard_select(ok, out_opt, opt_state)
                new_state = resilience.guard_select(ok, new_state, state)
            return out_params, out_opt, new_state, new_carries, loss

        return step

    def _update_params(self, params, opt_state, grads, it):
        """The per-layer optimizer update (normalization → updater →
        constraints) of the fused step body, under the scope ``update``."""
        new_params = []
        new_opt = []
        for i, (u, layer) in enumerate(zip(self._updaters, self.layers)):
            g = grads[i]
            if not g:  # param-free layer
                new_params.append(params[i])
                new_opt.append(opt_state[i])
                continue
            with jax.named_scope("update"), layer_scope(layer, i):
                gn = getattr(layer, "gradient_normalization", None)
                if gn:
                    g = apply_gradient_normalization(
                        gn, getattr(layer, "gradient_normalization_threshold", 1.0), g
                    )
                upd, new_s = u.update(g, opt_state[i], params[i], it)
                p_new = jax.tree_util.tree_map(lambda p, d: p - d, params[i], upd)
                if getattr(layer, "constraints", None):
                    # post-update projection, fused into the same executable
                    from deeplearning4j_tpu.nn.constraints import apply_constraints

                    p_new = apply_constraints(layer, p_new)
            new_params.append(p_new)
            new_opt.append(new_s)
        return tuple(new_params), tuple(new_opt)

    def _get_grads_fn(self):
        """The loss's value_and_grad as a program of its own (site
        ``mln.grads``): ``(loss, new_state, grads)`` with nothing donated.
        The elastic trainer's per-vshard backward (train/elastic.py), whose
        update runs segmented across workers, outside any step."""
        if getattr(self, "_grads_fn", None) is None:
            layers = self.layers

            def grads(params, state, x, y, fmask, lmask, rng, ex_weight):
                bucketing.telemetry().record_trace("mln.grads", np.shape(x))
                rngs = list(jax.random.split(rng, len(layers)))

                def loss_fn(p):
                    return self._loss(p, state, x, y, fmask, lmask, rngs, None,
                                      ex_weight=ex_weight)

                (loss, (new_state, _)), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                return loss, new_state, g

            self._grads_fn = StepProgram(grads, "mln.grads", donate_argnums=(),
                                         aot_wrap=False)
        return self._grads_fn

    def _make_chain_step(self):
        """K train steps per DISPATCH: lax.scan of the step body over
        stacked [K, B, ...] minibatches. Small models are dispatch-bound
        (a ~4 ms host->device floor per call through remote links);
        one dispatch covering K steps amortizes it.
        Per-step rngs derive as fold_in(rng, i) — identical math to the
        per-step path for models that draw no randomness (no dropout /
        weight noise), a different-but-equivalent stream otherwise."""
        body_step = self._step_body(False)

        def chain(params, opt_state, state, it0, rng, xs, ys):
            # own cost-attribution site: the chained executable covers K
            # steps per dispatch, so its static costs must not be filed
            # under the per-step mln.step site
            bucketing.telemetry().record_trace("mln.chain", np.shape(xs))

            def body(carry, inp):
                p, o, s, i = carry
                x, y = inp
                k = jax.random.fold_in(rng, i)
                p, o, s, _, loss = body_step(p, o, s, it0 + i, k, x, y,
                                             None, None, ())
                return (p, o, s, i + 1), loss

            (p, o, s, _), losses = jax.lax.scan(
                body, (params, opt_state, state, jnp.asarray(0, jnp.int32)),
                (xs, ys))
            return p, o, s, losses

        # aot_wrap=False: the chained executable bypasses the AOT warm
        # dispatcher (its [K, B, ...] signature never matches the ladder);
        # StepProgram still runs the lazy cost-exemplar harvest for it
        return StepProgram(chain, "mln.chain", aot_wrap=False)

    def _get_chain_step(self):
        if getattr(self, "_chain_step_fn", None) is None:
            self._chain_step_fn = self._make_chain_step()
        return self._chain_step_fn

    def _get_step_fn(self, with_carries: bool):
        if with_carries:
            if self._tbptt_step_fn is None:
                self._tbptt_step_fn = self._make_step(True)
            return self._tbptt_step_fn
        if self._step_fn is None:
            self._step_fn = self._make_step(False)
        return self._step_fn

    # -- training ----------------------------------------------------------
    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def _chain_k(self) -> int:
        """Steps chained per dispatch in fit()'s hot loop (0 = per-step).
        DL4J_TPU_CHAIN_STEPS forces a count; "auto" chains 8 only for
        models that draw NO randomness (identical math to per-step) and
        are small enough to be dispatch-bound."""
        uses_rng = any(l.uses_rng() for l in self.layers)
        return _chain_k_from_env(uses_rng, self.num_params())

    def _fit_chained(self, buf) -> None:
        """One dispatch covering len(buf) train steps (lax.scan of the step
        body over stacked minibatches)."""
        chain = self._get_chain_step()
        xs = jnp.stack([_cast_input(x, self.dtype) for x, _ in buf])
        ys = jnp.stack([_cast_labels(y, self.dtype) for _, y in buf])
        args = (self.params, self.opt_state, self.state,
                jnp.asarray(self.iteration, jnp.int32), self._next_rng(),
                xs, ys)
        # the StepProgram runs the lazy cost-exemplar harvest itself (aval
        # capture only on the rare compile path — donation invalidates
        # buffers, not shapes/dtypes)
        self.params, self.opt_state, self.state, _ = chain(*args)
        self.iteration += len(buf)

    def fit(self, data, epochs: int = 1, batch_size: Optional[int] = None,
            resume_from=None):
        """Train. ``data``: (x, y[, fmask[, lmask]]) arrays, an iterable of
        such batches, or a callable returning a fresh iterable per epoch
        (DataSetIterator equivalent).

        ``resume_from``: a CheckpointListener directory — restore the newest
        VALID checkpoint (params/opt/state, RNG key, iteration/epoch, batch
        position) and continue. ``epochs`` then counts the TOTAL budget
        (already-completed epochs are subtracted) and the interrupted epoch
        skips its already-consumed batches, so the resumed run replays the
        exact RNG/batch stream of an uninterrupted one (docs/ROBUSTNESS.md)."""
        from deeplearning4j_tpu.train import resilience
        from deeplearning4j_tpu.train.listeners import close_listeners

        if self.params is None:
            self.init()
        resume_skip = 0
        if resume_from is not None:
            if resilience.resume(self, resume_from) is not None:
                resume_skip = int(getattr(self, "batch_in_epoch", 0))
                epochs = max(epochs - self.epoch, 0)
        tbptt = self.conf.backprop_type == "tbptt"
        sgd = self.conf.optimization_algo in (
            "stochastic_gradient_descent", "sgd")
        guard = getattr(self, "divergence_guard", None)
        chain_k = (self._chain_k()
                   if sgd and not self.listeners and guard is None else 0)
        if aot.enabled() and sgd and not tbptt and chain_k <= 1:
            # time-to-first-step becomes a warm-path number: the step
            # executable for the exact first-batch signature is compiled
            # (or already bundle-restored) before the epoch loop dispatches
            aot.warm_fit(self, data, batch_size)
        reports = StepReports(self, "mln", self.layers,
                              overlap=sgd and guard is None)
        try:
            for _ in range(epochs):
                skip_n, resume_skip = resume_skip, 0
                self.batch_in_epoch = skip_n
                for l in self.listeners:
                    l.on_epoch_start(self, self.epoch)
                source = data() if callable(data) else data
                buf: list = []
                # pad every batch (incl. the partial tail) to ONE row count
                # with a uniform ew/lmask calling convention → one compiled
                # step. The chained path needs bare (x, y) batches, so it
                # opts out.
                pad_target = (_fit_pad_target(source, batch_size)
                              if sgd and chain_k <= 1
                              and bucketing.bucketing_enabled() else None)

                def flush(full: bool):
                    # full K-groups go out as ONE dispatch; tails use the
                    # per-step path (a different K would be a fresh compile)
                    if not buf:
                        return
                    with obs.span("mln.fit_batch", batches=len(buf)):
                        if full and len(buf) > 1:
                            self._fit_chained(buf)
                        else:
                            for bx, by in buf:
                                self._fit_batch(bx, by, None, None)
                    buf.clear()

                def batches():
                    it = _iter_batches(source, batch_size)
                    # resume: the interrupted epoch's consumed batches are
                    # skipped HERE, before padding/prefetch and without
                    # touching the RNG — the restored key is already past them
                    for _ in range(skip_n):
                        if next(it, None) is None:
                            return
                    for x, y, fm, lm in it:
                        # real-row count taken HERE, before padding, so the
                        # fit loop never syncs ew back from device to learn it
                        n = len(x)
                        if pad_target is not None and not (tbptt and np.ndim(x) == 3):
                            yield bucketing.pad_fit_batch(
                                x, y, fm, lm, pad_target, site="mln.fit") + (n,)
                        else:
                            yield (x, y, fm, lm, None, n)

                stream = batches()
                if sgd and _device_prefetch_enabled():
                    # overlap next batch's host→device transfer with this
                    # step's compute (double buffering); AFTER padding,
                    # which is host-side
                    from deeplearning4j_tpu.datasets.iterator import prefetch_to_device

                    stream = prefetch_to_device(stream)
                stream = iter(stream)
                while True:
                    # one loop turn = one mln.iter span; its children share
                    # its step number (obs/spans.py hands it down), but for
                    # the report of the step before, which names its own
                    step_no = self.iteration
                    with obs.span("mln.iter", step=step_no):
                        with obs.span("mln.feed"):
                            item = next(stream, None)
                        if item is None:
                            reports.flush()
                            break
                        x, y, fm, lm, ew, n_real = item
                        chainable = (
                            chain_k > 1 and fm is None and lm is None
                            and not (tbptt and np.ndim(x) == 3)
                            and (not buf or _batch_sig((x, y))
                                 == _batch_sig((buf[0][0], buf[0][1])))
                        )
                        if chainable:
                            buf.append((x, y))
                            self.batch_in_epoch += 1
                            if len(buf) == chain_k:
                                flush(True)
                            continue
                        flush(False)
                        reports.hold()
                        with obs.span("mln.fit_batch"):
                            if not sgd:
                                score = self._fit_solver(x, y, fm, lm)
                            elif tbptt and np.ndim(x) == 3:
                                score = self._fit_tbptt(x, y, fm, lm)
                            else:
                                score = self._fit_batch(x, y, fm, lm, ew=ew)
                        self.batch_in_epoch += 1
                        if guard is not None:
                            guard.observe(self, score)
                        # score is a device scalar; only sync the host when a
                        # listener actually consumes it (keeps dispatch async),
                        # and then one step behind the dispatch (StepReports);
                        # n_real came from the pre-padding host side of the stream
                        if self.listeners:
                            reports.step(score, step_no, n_real)
                flush(False)
                if guard is not None:
                    guard.flush(self)
                for l in self.listeners:
                    l.on_epoch_end(self, self.epoch)
                self.epoch += 1
        except Exception:
            # the feed, the dispatch or the chaos harness raised, perhaps
            # with a step dispatched and not reported yet
            reports.flush_quietly()
            raise
        finally:
            # a run ending inside a ProfilerListener [start, stop) window
            # (normally or via an exception/chaos preempt) must not leak an
            # open jax.profiler trace
            close_listeners(self.listeners)
        return self

    def _fit_batch(self, x, y, fm, lm, ew=None):
        """One step. Returns the loss as a DEVICE scalar — callers decide
        whether to sync (fit() only syncs when listeners are attached).
        ``ew``: optional per-example validity weight (ParallelWrapper padding)
        consumed by batch-coupled layers — see _forward."""
        from deeplearning4j_tpu.train import resilience

        chaos = resilience.active_chaos()
        if chaos is not None:
            chaos.maybe_preempt(self.iteration)
            chaos.maybe_slow(self.iteration)
            x = chaos.maybe_nan_batch(self.iteration, x)
        x = _cast_input(x, self.dtype)
        y = _cast_labels(y, self.dtype)
        fm = jnp.asarray(fm, self.dtype) if fm is not None else None
        lm = jnp.asarray(lm, self.dtype) if lm is not None else None
        step = self._get_step_fn(False)
        # dispatch() runs the step, then the retrace-guard check the program
        # owns: traces land at mln.step (inside the jitted body), bucket
        # traffic lands at mln.fit (pad_fit_batch) — the guard joins the two
        self.params, self.opt_state, self.state, _, loss = step.dispatch(
            self.params, self.opt_state, self.state,
            jnp.asarray(self.iteration, jnp.int32), self._next_rng(),
            x, y, fm, lm, (),
            ex_weight=jnp.asarray(ew, self.dtype) if ew is not None else None,
        )
        self.iteration += 1
        return loss

    def _fit_solver(self, x, y, fm, lm):
        """Non-SGD OptimizationAlgorithm path (Solver.java dispatch): run
        conf.solver_iterations deterministic solver steps on this batch.
        The Solver (and its jitted value_and_grad) is cached on the model so
        successive batches/epochs reuse one compiled executable per batch
        shape instead of retracing (round-2 advisor finding)."""
        from deeplearning4j_tpu.train.solvers import Solver

        solver = getattr(self, "_solver", None)
        if solver is None or solver.algorithm != self.conf.optimization_algo:
            solver = Solver(self, self.conf.optimization_algo)
            self._solver = solver
        loss = solver.optimize((x, y, fm, lm), iterations=self.conf.solver_iterations)
        self.iteration += 1
        return loss

    def _fit_tbptt(self, x, y, fm, lm):
        """Truncated BPTT: chunk the time axis, carry RNN state across chunks
        (doTruncatedBPTT:1514 — forward/backward chunk length unified)."""
        from deeplearning4j_tpu.train import resilience

        chaos = resilience.active_chaos()
        if chaos is not None:
            chaos.maybe_preempt(self.iteration)
            chaos.maybe_slow(self.iteration)
        step = self._get_step_fn(True)
        T = x.shape[1]
        L = self.conf.tbptt_fwd_length
        carries = tuple(
            l.initial_carry(x.shape[0], self.dtype) if f else ()
            for l, f in zip(self.layers, self._carry_flags)
        )
        total, nchunks = 0.0, 0
        for t0 in range(0, T, L):
            sl = slice(t0, min(t0 + L, T))
            xc = jnp.asarray(x[:, sl], self.dtype)
            # time-sliced labels: one-hot [B,T,C] AND sparse integer [B,T];
            # rank-2 FLOAT labels (sequence-level heads) pass through whole
            y_sliced = (y is not None and (np.ndim(y) == 3 or (
                np.ndim(y) == 2 and np.dtype(_sig_dtype(y)).kind in "iu")))
            yc = _cast_labels(y[:, sl] if y_sliced else y, self.dtype)
            fmc = jnp.asarray(fm[:, sl], self.dtype) if fm is not None else None
            lmc = jnp.asarray(lm[:, sl], self.dtype) if lm is not None else None
            self.params, self.opt_state, self.state, carries, loss = step(
                self.params, self.opt_state, self.state,
                jnp.asarray(self.iteration, jnp.int32), self._next_rng(),
                xc, yc, fmc, lmc, carries,
            )
            # truncation is structural: each chunk is its own jitted step, so
            # the concrete carry arrays carry values, never gradients
            total = total + loss  # device-side accumulation, no host sync
            nchunks += 1
            self.iteration += 1
        return total / max(nchunks, 1)

    # -- inference ---------------------------------------------------------
    def _get_output_fn(self):
        """The jitted inference entry point, AOT-wrapped so warmup
        (``nn/aot.py``) can pre-compile every ladder bucket and bundle
        restore can install persisted executables."""
        if self._output_fn is None:
            def fwd(params, state, x, fmask):
                # python body runs once per trace → counts actual compiles
                bucketing.telemetry().record_trace("mln.output", np.shape(x))
                a, _, _, _, _ = self._forward(params, state, x, train=False, rngs=None,
                                              fmask=fmask)
                return a

            self._output_fn = StepProgram(
                fwd, "mln.output", model=self, donate_argnums=())
        return self._output_fn

    def output(self, x, train: bool = False, fmask=None):
        """Final-layer post-activation output (MultiLayerNetwork.output:2005),
        jit-compiled inference path.

        Batch rows are padded up to the shared bucket ladder before dispatch
        (and sliced back off) so mixed caller batch sizes share one compiled
        executable per bucket — inference is row-independent (BatchNorm uses
        running stats when train=False), so zero-pad rows are dead compute,
        not a numerics change. Disable via DL4J_TPU_BUCKETING=0."""
        self._get_output_fn()
        x = _cast_input(x, self.dtype)
        fmask = jnp.asarray(fmask, self.dtype) if fmask is not None else None
        n = x.shape[0]
        # dispatch() opens the mln.output span
        if bucketing.bucketing_enabled() and n > 0:
            target = bucketing.bucket_size(n)
            bucketing.telemetry().record_hit("mln.output", n, target)
            if target > n:
                x = bucketing.pad_rows_zero(x, target)
                fmask = bucketing.pad_rows_zero(fmask, target)
                return bucketing.unpad(
                    self._output_fn.dispatch(
                        self.params, self.state, x, fmask), n)
        return self._output_fn.dispatch(self.params, self.state, x, fmask)

    def predict(self, x) -> np.ndarray:
        # argmax on device: transfer the [B] class indices, not the full
        # [B, C] activation matrix
        idx = jnp.argmax(self.output(x), axis=-1)
        return np.asarray(idx)  # graftlint: disable=host-sync

    def score(self, batch_or_x, y=None, fmask=None, lmask=None,
              train: bool = False) -> float:
        """Average loss on a batch (MultiLayerNetwork.score(data, training)).

        ``train=True`` scores with training-mode statistics — normalization
        layers use the batch's own mean/var instead of the (one-step-stale)
        running estimates — while dropout / weight noise stay disabled, so
        the result is deterministic. This is the right mode for "did the
        training loss go down" checks on deep BatchNorm stacks, where eval
        statistics lag the params by a step and the error compounds through
        every BN layer."""
        if y is None:
            x, y, fmask, lmask = _as_batch(batch_or_x)
        else:
            x = batch_or_x
        loss, _ = self._loss(
            self.params, self.state,
            _cast_input(x, self.dtype), _cast_labels(y, self.dtype),
            jnp.asarray(fmask, self.dtype) if fmask is not None else None,
            jnp.asarray(lmask, self.dtype) if lmask is not None else None,
            rngs=None,
            train=train,
            deterministic=True,
        )
        return float(loss)

    # -- evaluation --------------------------------------------------------
    def _output_mask(self, fm, lm):
        """Mask for scoring/eval at the network output: the labels mask, or
        the features mask propagated through the layer stack (matches _loss)."""
        if lm is not None:
            return np.asarray(lm)
        if fm is None:
            return None
        mask = jnp.asarray(fm, self.dtype)
        for layer, it in zip(self.layers, self.layer_input_types):
            mask = layer.propagate_mask(mask, it)
            if mask is None:
                return None
        return np.asarray(mask)

    def evaluate(self, data, batch_size: Optional[int] = None, top_n: int = 1):
        from deeplearning4j_tpu.eval import Evaluation

        ev = Evaluation(top_n=top_n)
        for x, y, fm, lm in _iter_batches(data, batch_size):
            preds = self.output(x, fmask=fm)
            ev.eval(np.asarray(y), np.asarray(preds), mask=self._output_mask(fm, lm))
        return ev

    def evaluate_regression(self, data, batch_size: Optional[int] = None):
        from deeplearning4j_tpu.eval import RegressionEvaluation

        ev = RegressionEvaluation()
        for x, y, fm, lm in _iter_batches(data, batch_size):
            preds = self.output(x, fmask=fm)
            ev.eval(np.asarray(y), np.asarray(preds), mask=self._output_mask(fm, lm))
        return ev

    def evaluate_roc(self, data, batch_size: Optional[int] = None, num_bins: int = 200):
        from deeplearning4j_tpu.eval import ROC

        roc = ROC(num_bins)
        for x, y, fm, lm in _iter_batches(data, batch_size):
            preds = self.output(x, fmask=fm)
            roc.eval(np.asarray(y), np.asarray(preds))
        return roc

    # -- streaming RNN inference (rnnTimeStep:2371) ------------------------
    def rnn_time_step(self, x):
        """Feed one or more timesteps, carrying RNN state between calls."""
        x = _cast_input(x, self.dtype)
        squeeze = x.ndim == 2
        if squeeze:
            x = x[:, None, :]
        leaves = (
            jax.tree_util.tree_leaves(self._rnn_carries) if self._rnn_carries is not None else []
        )
        if self._rnn_carries is None or (leaves and leaves[0].shape[0] != x.shape[0]):
            self._rnn_carries = tuple(
                l.initial_carry(x.shape[0], self.dtype) if f else ()
                for l, f in zip(self.layers, self._carry_flags)
            )
        a, _, new_carries, _, _ = self._forward(
            self.params, self.state, x, train=False, rngs=None, carries=self._rnn_carries
        )
        self._rnn_carries = new_carries
        return a[:, 0, :] if squeeze and a.ndim == 3 else a

    def rnn_clear_previous_state(self):
        self._rnn_carries = None

    # -- persistence hooks (utils/serialization.py drives these) -----------
    def clone(self) -> "MultiLayerNetwork":
        m = MultiLayerNetwork(self.conf)
        if self.params is not None:
            m.init()
            # Deep copy: the jitted step DONATES params/opt_state/state, so
            # aliasing the live buffers would leave the clone pointing at
            # deleted arrays after the next fit() on either model.
            copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)
            m.params = copy(self.params)
            m.state = copy(self.state)
            m.opt_state = copy(self.opt_state)
            m.iteration = self.iteration
            m.epoch = self.epoch
        return m

    def summary(self) -> str:
        lines = [f"{'idx':<4} {'type':<22} {'output':<24} {'params':<10}"]
        for i, (l, it) in enumerate(zip(self.layers, self.layer_input_types)):
            out = l.output_type(it)
            n = (
                sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(self.params[i]))
                if self.params is not None
                else "?"
            )
            lines.append(f"{i:<4} {l._type_name:<22} {str(out.batch_shape())[0:24]:<24} {n:<10}")
        lines.append(f"Total params: {self.num_params() if self.params is not None else '?'}")
        return "\n".join(lines)
