"""One compiled step program: the single owner of step wiring policy.

Every training/inference entry point in the framework used to hand-roll the
same five-line stanza — ``jax.jit(body, donate_argnums=(0, 1, 2))``,
``aot.wrap`` at a site name, a ``retrace_guard.check_if_enabled`` after each
dispatch, a grad-accumulation scan spliced into the body, and an exemplar
harvest for the cost model. MultiLayerNetwork, ComputationGraph,
DataParallelStep, the gpipe stages and the serve/decode executors each
carried their own copy, and the copies drifted (ISSUE 13). This module is
now the only place that wiring exists:

- :class:`StepProgram` — one compiled entry point: trace/donate policy,
  AOT-warm dispatch (``nn/aot.py``), retrace-guard hookup
  (``analysis/retrace_guard.py``) and cost-exemplar harvest, behind a
  callable that quacks like the ``AotFunction`` it wraps.
- :class:`StepReports` — what ``fit()`` tells its listeners of each step
  (the loss on the host, the layers' step counters), one step behind the
  dispatch where nothing attached needs the model at its own step, for
  ``MultiLayerNetwork.fit`` and ``ComputationGraph.fit`` alike.
- the **micro-batching policy** shared by every step builder:
  :func:`grad_accum_from_env` / :func:`accum_applicable` /
  :func:`accum_value_and_grad` (the lax.scan gradient accumulation INSIDE
  the donated step) and :func:`chain_k_from_env` (K steps per dispatch).
- the **mesh-shape policy**: :func:`mesh_shape_from_env` resolves the
  ``(data, tensor, stage)`` axes of the named-mesh step
  (``parallel/mesh_step.py``) from the ``DL4J_TPU_MESH_*`` knobs.

A graftlint rule (``step-wiring``, ``analysis/rules.py``) forbids new
direct ``jax.jit(..., donate_argnums=...)`` step construction in ``nn/``
and ``parallel/`` outside this module, so the wiring cannot fork a sixth
time. See docs/PARALLELISM.md.
"""

from __future__ import annotations

import functools
import logging
from typing import Any, Callable, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.analysis import donation_guard, retrace_guard

logger = logging.getLogger("deeplearning4j_tpu")

__all__ = [
    "CHAIN_AUTO_PARAM_LIMIT",
    "StepProgram",
    "StepReports",
    "accum_applicable",
    "accum_value_and_grad",
    "chain_k_from_env",
    "grad_accum_from_env",
    "layer_scope",
    "mesh_shape_from_env",
]


def layer_scope(layer, index):
    """The ``jax.named_scope`` of one layer (or graph vertex) inside a step:
    its class and its index (or vertex name), as in ``TransformerBlock.3``.
    HLO metadata only: a profiler trace shows every device operation under
    ``<site>/<layer scope>/...`` (docs/OBSERVABILITY.md)."""
    return jax.named_scope(f"{type(layer).__name__}.{index}")


class StepProgram:
    """One compiled step/output program and its dispatch policy.

    Owns, in exactly one place, what every model/parallel step used to wire
    by hand:

    - **trace/donate**: ``body`` is jitted with ``donate_argnums`` (the
      params/opt/state carry donates by default, so the step updates in
      place buffer-wise);
    - **AOT**: the jitted function is registered at ``site`` on ``model``'s
      AOT registry (``aot.wrap``) so ladder warmup, bundle persistence and
      warm dispatch all find it — ``aot_wrap=False`` opts out for entry
      points that must bypass the AOT dispatcher (chained steps) while
      keeping the lazy cost-exemplar harvest;
    - **retrace guard**: :meth:`dispatch` runs the call followed by the
      guard check for ``guard_site`` (defaults to ``site``) with the
      configured ``hits_site``/``extra_allowed``, so callers can't forget
      the check or disagree on the budget;
    - **names**: the body runs under ``jax.named_scope(site)``, and every
      call is one ``obs.site_span(site)`` over the signature lookup, the
      enqueue and the guard check: the site's name is on the device
      operations and on the host's timeline of any profiler trace, and what
      JAX traces, lowers, compiles or reads from its cache inside a call is
      booked to the site (``obs/compile_phases.py``).

    ``wrap_body`` (e.g. a ``shard_map`` closure for the explicit DP
    exchange) transforms the body before jit. Everything not implemented
    here delegates to the wrapped callable, so existing code that expects
    an ``AotFunction`` (``warm``/``compiled_count``/``signatures``/
    ``install``/``lower``) keeps working unchanged.
    """

    def __init__(self, body: Callable, site: str, *, model=None,
                 donate_argnums: Tuple[int, ...] = (0, 1, 2),
                 static_argnums: Optional[Tuple[int, ...]] = None,
                 wrap_body: Optional[Callable[[Callable], Callable]] = None,
                 aot_wrap: bool = True,
                 guard_site: Optional[str] = None,
                 hits_site: Optional[str] = None,
                 extra_allowed: int = 0):
        from deeplearning4j_tpu.nn import aot
        from deeplearning4j_tpu.obs import compile_phases

        compile_phases.install()
        self.site = site
        self.guard_site = guard_site or site
        self.hits_site = hits_site
        self.extra_allowed = extra_allowed
        self.donate_argnums = tuple(donate_argnums)
        inner = body if wrap_body is None else wrap_body(body)

        @functools.wraps(inner)
        def fn(*args, **kw):
            with jax.named_scope(site):
                return inner(*args, **kw)

        kwargs: dict = {"donate_argnums": self.donate_argnums}
        if static_argnums is not None:
            kwargs["static_argnums"] = tuple(static_argnums)
        jitted = jax.jit(fn, **kwargs)
        self._aot = bool(aot_wrap)
        self._fn = (aot.wrap(jitted, site, model=model,
                             static_argnums=kwargs.get("static_argnums"))
                    if aot_wrap else jitted)

    # -- dispatch ----------------------------------------------------------
    def __call__(self, *args, **kwargs):
        with obs.site_span(self.site):
            return self._run(*args, **kwargs)

    def _run(self, *args, **kwargs):
        out = self._fn(*args, **kwargs)
        if not self._aot:
            # plain-jit programs (chained dispatch) still feed
            # the cost model: aval capture only on the (rare) compile path
            from deeplearning4j_tpu.obs import profile as _profile

            if _profile.wants_exemplar(self.site):
                _profile.note_exemplar(self.site, self._fn, args, kwargs)
        if self.donate_argnums and donation_guard.enabled():
            # debug mode: poison donated inputs the backend left alive so a
            # use-after-donate the static rule missed fails loudly on CPU too
            donation_guard.check_after_dispatch(
                self.site, args, self.donate_argnums, out)
        return out

    def dispatch(self, *args, **kwargs):
        """Call, then run the retrace-guard check this program owns."""
        with obs.site_span(self.site):
            out = self._run(*args, **kwargs)
            self.guard()
        return out

    def guard(self):
        """The post-dispatch retrace-guard check (no-op unless enabled)."""
        retrace_guard.check_if_enabled(
            self.guard_site, hits_site=self.hits_site,
            extra_allowed=self.extra_allowed)

    # -- AotFunction parity ------------------------------------------------
    def warm(self, *args, **kwargs):
        return self._fn.warm(*args, **kwargs)

    @property
    def compiled_count(self) -> int:
        return getattr(self._fn, "compiled_count", 0)

    def __getattr__(self, name: str):
        # anything else (signatures/install/lower/_compiled/...) is the
        # wrapped callable's business
        return getattr(self.__dict__["_fn"], name)


# ---------------------------------------------------------------------------
# fit()'s reports to its listeners (shared by MLN / CG)
# ---------------------------------------------------------------------------

_FETCHED = obs.counter(
    "dl4j_fit_fetch_total",
    "steps whose loss fit() fetched to the host for its listeners", ("site",))
_OVERLAPPED = obs.counter(
    "dl4j_fit_fetch_overlapped_total",
    "of dl4j_fit_fetch_total, the steps fetched after the next step was "
    "enqueued", ("site",))


def _stats_keys(state) -> list:
    """Where ``state`` (a tuple by layer index, a dict by vertex name) keeps
    a layer's step counters under ``"stats"``."""
    items = state.items() if isinstance(state, dict) else enumerate(state)
    return [k for k, s in items if isinstance(s, dict) and "stats" in s]


def _swap_stats(model, keys, arrays) -> list:
    """Put ``arrays`` under ``"stats"`` at ``keys`` of ``model.state`` and
    return what was there. The tree keeps its structure: the step sees the
    same signature."""
    state = model.state
    old = [state[k]["stats"] for k in keys]
    new = dict(zip(keys, arrays))
    if isinstance(state, dict):
        model.state = {k: {**s, "stats": new[k]} if k in new else s
                       for k, s in state.items()}
    else:
        model.state = tuple({**s, "stats": new[i]} if i in new else s
                            for i, s in enumerate(state))
    return old


def _blank_like(arrays) -> list:
    """Zeros on the device with each array's shape, dtype and placement, made
    by a transfer: nothing is compiled for them."""
    return [jax.device_put(np.zeros(a.shape, a.dtype),
                           a.sharding if a.committed else None)
            for a in arrays]


class StepReports:
    """What ``fit()`` tells its listeners of each step: the loss on the host,
    the step counters of the layers that keep some in their state under
    ``"stats"`` (``layer.publish_stats``, in the same fetch: no second wait
    for the device), ``resilience.note_score``, ``iteration_done``.

    Waiting for step N's loss right after its dispatch leaves the device
    with nothing queued while the host reports, pulls the next batch and
    walks the next call's signature. So the report of step N is made after
    step N + 1 has been enqueued, and the last one when the stream ends:
    every listener gets the ``iteration``, ``score`` and ``batch_size`` of
    every step, in order; only the moment moves. The loop stays synchronous
    where something attached must act on the model at its own step: a
    ``DivergenceGuard``, a listener that declares ``reads_model``, or a
    score that is a host float already (the solvers).

    The step donates the state, ``"stats"`` leaves included, so step N's
    are not in it when step N + 1 is dispatched: :meth:`hold` keeps them
    for the report and puts in their place arrays the step overwrites
    without reading them (blank ones the first time of a ``fit()`` call,
    then those of the step reported last). The compiled step is the same.

    ``site`` is ``"mln"`` or ``"cg"``: the spans ``<site>.loss_fetch`` and
    ``<site>.listeners`` carry the ``step=`` of the step they report, and the
    counters ``dl4j_fit_fetch_total{site}`` /
    ``dl4j_fit_fetch_overlapped_total{site}`` say how often the wait lay
    behind the next step.
    """

    def __init__(self, model, site: str, layers, overlap: bool):
        self.model = model
        self.site = site
        self.layers = layers        # model.state's keys -> layer
        self.overlap = bool(overlap) and not any(
            getattr(l, "reads_model", False) for l in model.listeners)
        self._keys = _stats_keys(model.state)
        self._pending = None        # (loss, step, iteration, n_real)
        self._held = None           # the pending step's "stats", taken out
        self._spare = None          # "stats" arrays of a step reported

    def _stats(self) -> list:
        return [self.model.state[k]["stats"] for k in self._keys]

    def hold(self) -> None:
        """Before a dispatch: the pending step's counters out of the state
        that the dispatch donates."""
        if self._pending is None or not self._keys:
            return
        blanks = self._spare or _blank_like(self._stats())
        self._spare = None
        self._held = _swap_stats(self.model, self._keys, blanks)

    def step(self, loss, step: int, n_real: int) -> None:
        """After a dispatch: report the step before it, and this one too
        where the loop is synchronous."""
        prev, self._pending = self._pending, None
        if prev is not None:
            self._report(prev, overlapped=True)
        this = (loss, step, self.model.iteration, n_real)
        if self.overlap:
            self._pending = this
        else:
            self._report(this, overlapped=False)

    def flush(self) -> None:
        """The stream has ended, or the loop is about to raise: report the
        pending step. Its counters go back into the state if a dispatch that
        never came about had them taken out."""
        pending, self._pending = self._pending, None
        if pending is None:
            return
        if self._held is not None:
            _swap_stats(self.model, self._keys, self._held)
            self._held = None
        self._report(pending, overlapped=False)

    def flush_quietly(self) -> None:
        """:meth:`flush` for a loop that is raising already: what the report
        raises in its turn is logged, and the loop's own exception goes on."""
        try:
            self.flush()
        except Exception:
            logger.exception("%s.fit: the pending step's report failed",
                             self.site)

    def _report(self, pending, overlapped: bool) -> None:
        from deeplearning4j_tpu.train import resilience

        loss, step, iteration, n_real = pending
        model, site = self.model, self.site
        stats, self._held = self._held, None
        if stats is None:
            stats = self._stats()
        with obs.span(f"{site}.loss_fetch", step=step):
            if stats:
                loss, values = jax.device_get(  # graftlint: disable=host-sync
                    (loss, stats))
                for k, v in zip(self._keys, values):
                    self.layers[k].publish_stats(k, v)
            score = float(loss)  # graftlint: disable=host-sync
        _FETCHED.inc(site=site)
        if overlapped:
            _OVERLAPPED.inc(site=site)
            self._spare = stats or None
        resilience.note_score(score)
        with obs.span(f"{site}.listeners", step=step):
            for l in model.listeners:
                l.iteration_done(model, iteration, score, n_real)


# ---------------------------------------------------------------------------
# Micro-batching policy (shared by MLN / CG / DP / mesh step builders)
# ---------------------------------------------------------------------------

# Above this parameter count, "auto" never chains: big models are
# compute-bound, so amortizing dispatch buys nothing and the stacked
# [K, B, ...] batch just costs memory.
CHAIN_AUTO_PARAM_LIMIT = 2_000_000

_CHAIN_RNG_WARNED = False


def chain_k_from_env(uses_rng: bool, n_params: int) -> int:
    """Shared chained-fit gate for MultiLayerNetwork and ComputationGraph:
    DL4J_TPU_CHAIN_STEPS forces a count (0 disables); "auto" chains 8 only
    for rng-free models small enough to be dispatch-bound."""
    import os as _os

    env = _os.environ.get("DL4J_TPU_CHAIN_STEPS", "auto")
    if env != "auto":
        try:
            k = max(int(env), 0)
        except ValueError:
            return 0
        if k > 1 and uses_rng:
            global _CHAIN_RNG_WARNED
            if not _CHAIN_RNG_WARNED:
                _CHAIN_RNG_WARNED = True
                import warnings

                warnings.warn(
                    f"DL4J_TPU_CHAIN_STEPS={env} forces chained dispatch on a "
                    "model that draws randomness (dropout/weight noise): "
                    "per-step rngs derive as fold_in(rng, i) inside the "
                    "chain, a different-but-equivalent stream from the "
                    "per-step path, so losses will not be bitwise "
                    "reproducible against unchained runs.")
        return k
    return 8 if (not uses_rng and n_params < CHAIN_AUTO_PARAM_LIMIT) else 0


_GRAD_ACCUM_WARNED = False


def grad_accum_from_env() -> int:
    """Micro-batch count for gradient accumulation inside the jitted step
    (DL4J_TPU_GRAD_ACCUM, default 1 = off). Shared by MultiLayerNetwork and
    ComputationGraph; read at step-BUILD time, so a change after the first
    compile needs ``_clear_compiled()``."""
    import os as _os

    env = _os.environ.get("DL4J_TPU_GRAD_ACCUM", "1")
    try:
        return max(int(env), 1)
    except ValueError:
        return 1


def accum_applicable(accum: int, batch) -> bool:
    """Trace-time gate for the accumulated step: every batch-major leaf must
    share one leading row count divisible by ``accum`` (micro-batches must be
    equal-sized for the mean-of-means loss to equal the full-batch mean).
    Falls back to the un-accumulated step otherwise — silently for accum<=1,
    with a one-shot warning when the knob is set but the batch doesn't fit."""
    if accum <= 1:
        return False
    leaves = jax.tree_util.tree_leaves(batch)
    if not leaves or leaves[0].ndim == 0:
        return False
    b = leaves[0].shape[0]
    if b < accum or b % accum != 0 or not all(
            l.ndim >= 1 and l.shape[0] == b for l in leaves):
        # warn-once flag: once-per-trace IS the wanted semantic here, and
        # the boolean never feeds the traced computation
        global _GRAD_ACCUM_WARNED  # graftlint: disable=jit-purity
        if not _GRAD_ACCUM_WARNED:
            _GRAD_ACCUM_WARNED = True
            import warnings

            warnings.warn(
                f"DL4J_TPU_GRAD_ACCUM={accum} does not divide the batch "
                f"(leading dims {[l.shape[0] for l in leaves[:4]]}); this "
                "step runs un-accumulated.")
        return False
    return True


def accum_value_and_grad(accum, params, state, batch, rng, make_loss_fn):
    """Gradient accumulation: one ``lax.scan`` over ``accum`` equal
    micro-batches INSIDE the donated step executable. Each micro-batch runs
    forward + backward at 1/accum the activation footprint (the scan re-uses
    one micro-batch's live activations — this is the knob that unlocks
    batches beyond HBM); gradients accumulate in a carry and are averaged
    once, so the single optimizer update downstream sees exactly the
    mean-of-micro-means gradient. For equal micro-batches with no masks that
    equals the full-batch mean bitwise up to fp summation order (the parity
    test pins fp32 tolerance); per-micro-batch means under row masks follow
    the same mean-of-means contract the DP replica exchange already uses.

    ``batch`` is a pytree of batch-major arrays (None leaves allowed).
    ``make_loss_fn(micro_batch, state, rng_i)`` returns the per-micro-batch
    ``loss_fn(params) -> (loss, (new_state, aux))``. Mutable layer state
    (BatchNorm running stats) threads micro-batch to micro-batch, matching
    what sequential small batches would do. Per-micro rngs derive as
    ``fold_in(rng, i)`` — a different-but-equivalent stream from the
    un-accumulated step for models that draw randomness (same caveat as
    chained dispatch)."""
    micro = jax.tree_util.tree_map(
        lambda t: t.reshape((accum, t.shape[0] // accum) + t.shape[1:]),
        batch)

    def body(carry, mb):
        st, g_acc, loss_acc, i = carry
        loss_fn = make_loss_fn(mb, st, jax.random.fold_in(rng, i))
        (loss_i, (st_i, _)), g_i = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        g_acc = jax.tree_util.tree_map(lambda a, g: a + g, g_acc, g_i)
        return (st_i, g_acc, loss_acc + loss_i, i + 1), None

    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    (new_state, g_sum, loss_sum, _), _ = jax.lax.scan(
        body,
        (state, zeros, jnp.asarray(0.0, jnp.float32),
         jnp.asarray(0, jnp.int32)),
        micro)
    inv = 1.0 / accum
    grads = jax.tree_util.tree_map(lambda g: g * inv, g_sum)
    return loss_sum * inv, new_state, grads


# ---------------------------------------------------------------------------
# Mesh-shape policy (the (d, t, s) knobs of the named-mesh step)
# ---------------------------------------------------------------------------


def _axis_env(name: str) -> int:
    import os as _os

    raw = _os.environ.get(name, "0")
    try:
        return max(int(raw), 0)
    except ValueError:
        return 0


def mesh_shape_from_env(n_devices: int) -> Tuple[int, int, int]:
    """Resolve the named-mesh step's ``(data, tensor, stage)`` shape from
    the ``DL4J_TPU_MESH_DATA`` / ``DL4J_TPU_MESH_MODEL`` /
    ``DL4J_TPU_MESH_PIPE`` knobs (0/unset = auto).

    Auto policy: unset tensor/stage axes default to 1 and the unset data
    axis absorbs every remaining device — so with no knobs set this is pure
    DP over all devices. A shape whose product does not divide
    ``n_devices`` is a configuration error and raises."""
    t = _axis_env("DL4J_TPU_MESH_MODEL") or 1
    s = _axis_env("DL4J_TPU_MESH_PIPE") or 1
    d = _axis_env("DL4J_TPU_MESH_DATA")
    if d == 0:
        if n_devices % (t * s):
            raise ValueError(
                f"mesh axes model={t} x pipe={s} do not divide "
                f"{n_devices} devices")
        d = n_devices // (t * s)
    if d * t * s != n_devices:
        raise ValueError(
            f"mesh shape (d={d}, t={t}, s={s}) does not cover "
            f"{n_devices} devices")
    return d, t, s
