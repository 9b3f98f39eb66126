"""Pipeline parallelism over a ``pipe`` mesh axis (GPipe schedule, SPMD).

Beyond-reference capability (the reference is data-parallel only,
SURVEY.md §2.5). Every device holds ONE stage's parameters (the stacked
per-stage pytree is sharded on its leading axis over ``pipe``); microbatches
flow through the ring: at step t each device applies its stage to the
activation it holds and ``ppermute``s the result to the next device. After
``n_micro + n_stages - 1`` steps the last device has produced every
microbatch's output. The whole schedule lives inside one jit/shard_map
program, so backward is just autodiff (the transpose of ppermute is the
reverse ppermute — XLA schedules the bubble-filling automatically).

Constraint: inter-stage activations share one shape (classic GPipe layout —
stages are "blocks of equal width"); stage 0 maps input→hidden internally if
needed via its own parameters.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.parallel.ring import shard_map


def pvary(x, axis_name):
    """Type ``x`` as device-varying over ``axis_name`` (a no-op when it
    already is — ``lax.pcast`` refuses varying -> varying)."""
    if axis_name in jax.typeof(x).vma:
        return x
    return lax.pcast(x, axis_name, to="varying")


def _gpipe_shard(params_local, x_micro, *, stage_apply, axis_name, n_stages,
                 aux_width=None, aux_combine=None):
    """Runs on each pipe rank. params_local: this rank's stage params (leading
    stage axis already stripped to size 1 by shard_map → squeezed here).
    x_micro: [M, mb, ...] microbatched input (replicated across pipe).
    ``stage_apply(params, x, micro)`` is one stage's forward for microbatch
    index ``micro`` (clamped during bubble steps, whose results are
    discarded); with ``aux_width`` set it returns ``(out, aux[aux_width])``
    and this function returns ``(outs, auxs [1, M, aux_width])`` — each
    rank's per-microbatch auxiliary emissions (e.g. BatchNorm batch stats),
    optionally passed through ``aux_combine`` (e.g. a data-axis pmean).
    Returns [M, mb, ...] outputs (valid on the LAST rank, zeros elsewhere;
    psum-broadcast so every rank returns them)."""
    params_local = jax.tree_util.tree_map(lambda a: a[0], params_local)
    idx = lax.axis_index(axis_name)
    M = x_micro.shape[0]
    total = M + n_stages - 1
    perm = [(i, i + 1) for i in range(n_stages - 1)]
    with_aux = aux_width is not None

    def body(t, carry):
        buf, outs, auxs = carry
        micro = jnp.clip(t - idx, 0, M - 1)
        inp = jnp.where(idx == 0, x_micro[jnp.minimum(t, M - 1)], buf)
        res = stage_apply(params_local, inp, micro)
        out, aux = res if with_aux else (res, None)
        shifted = lax.ppermute(out, axis_name, perm)
        # Last rank commits microbatch t-(S-1); earlier (wrapped) writes are
        # overwritten by the later, correct ones.
        outs = lax.dynamic_update_index_in_dim(
            outs, jnp.where(idx == n_stages - 1, out, jnp.zeros_like(out)),
            (t - (n_stages - 1)) % M, 0,
        )
        if with_aux:
            if aux_combine is not None:
                aux = aux_combine(aux)
            # this rank's aux for micro t-idx is valid iff idx <= t < idx+M;
            # late bubble steps would otherwise overwrite earlier valid rows
            # (slot (t-idx) % M wraps)
            slot = (t - idx) % M
            valid = jnp.logical_and(t >= idx, t - idx < M)
            prev = lax.dynamic_index_in_dim(auxs, slot, 0, keepdims=False)
            auxs = lax.dynamic_update_index_in_dim(
                auxs, jnp.where(valid, aux, prev), slot, 0)
        return shifted, outs, auxs

    # carries must be typed as device-varying over the pipe axis from the
    # start (they become varying after the first ppermute/update)
    _pvary = functools.partial(pvary, axis_name=axis_name)

    buf = _pvary(jnp.zeros_like(x_micro[0]))
    outs = _pvary(jnp.zeros_like(x_micro))
    auxs = _pvary(jnp.zeros((M, aux_width if with_aux else 1), jnp.float32))
    buf, outs, auxs = lax.fori_loop(0, total, body, (buf, outs, auxs),
                                    unroll=True)
    # Only the last rank holds real outputs (zeros elsewhere): psum over the
    # pipe ring broadcasts them so the result is replicated across stages.
    outs = lax.psum(outs, axis_name)
    return (outs, auxs[None]) if with_aux else outs


class PipelineParallel:
    """GPipe training driver.

    ``stage_apply(stage_params, x) -> y`` is one stage's forward;
    ``stacked_params`` holds every stage stacked on axis 0.
    ``loss_fn(y, labels) -> scalar`` scores the final stage's output.

    The train step shards microbatches over ``data`` and stages over
    ``pipe`` in ONE compiled program.
    """

    def __init__(
        self,
        stage_apply: Callable,
        n_stages: int,
        mesh: Mesh,
        *,
        loss_fn: Callable,
        data_axis: str = "data",
        pipe_axis: str = "pipe",
        learning_rate: float = 1e-2,
    ):
        if n_stages != mesh.shape[pipe_axis]:
            raise ValueError(
                f"n_stages={n_stages} must equal the mesh's '{pipe_axis}' axis "
                f"size ({mesh.shape[pipe_axis]}): one stage per pipe rank"
            )
        self.stage_apply = stage_apply
        self.n_stages = n_stages
        self.mesh = mesh
        self.loss_fn = loss_fn
        self.data_axis = data_axis
        self.pipe_axis = pipe_axis
        self.lr = learning_rate
        self._step = None

    def forward(self, stacked_params, x_micro):
        """Pipelined forward; returns [M, mb, ...] outputs (from last stage)."""
        fn = functools.partial(
            _gpipe_shard,
            stage_apply=lambda p, x, _micro: self.stage_apply(p, x),
            axis_name=self.pipe_axis,
            n_stages=self.n_stages,
        )
        pspec = jax.tree_util.tree_map(lambda _: P(self.pipe_axis), stacked_params)
        xspec = P(None, self.data_axis)
        out = shard_map(
            fn,
            mesh=self.mesh,
            in_specs=(pspec, xspec),
            out_specs=xspec,
        )(stacked_params, x_micro)
        return out

    def _loss(self, stacked_params, x_micro, y_micro):
        out = self.forward(stacked_params, x_micro)
        # outputs are zero except on the last pipe rank's shard-view; after
        # shard_map they're the assembled global array, so loss is direct
        return self.loss_fn(out, y_micro)

    def make_train_step(self):
        @jax.jit
        def step(stacked_params, x_micro, y_micro):
            loss, grads = jax.value_and_grad(self._loss)(stacked_params, x_micro, y_micro)
            new_params = jax.tree_util.tree_map(lambda p, g: p - self.lr * g, stacked_params, grads)
            return new_params, loss

        return step

    def fit_batch(self, stacked_params, x, y, n_micro: int):
        """Split [B,...] into n_micro microbatches, run one pipelined step."""
        if self._step is None:
            self._step = self.make_train_step()
        B = x.shape[0]
        assert B % n_micro == 0, "batch must divide into microbatches"
        xm = x.reshape(n_micro, B // n_micro, *x.shape[1:])
        ym = y.reshape(n_micro, B // n_micro, *y.shape[1:])
        return self._step(stacked_params, xm, ym)


def stack_stage_params(per_stage: Sequence[Any]):
    """Stack per-stage param pytrees on a new leading ``pipe`` axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_stage)
