"""Numerical-vs-analytic gradient checking harness.

Parity: gradientcheck/GradientCheckUtil.java:109 (MLN), :331 (graph) — the
correctness backbone of the reference's test suite (16 gradient-check suites,
SURVEY.md §4). Central-difference perturbation in float64 against jax.grad
of the model's loss.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def check_gradients(
    model,
    x,
    y,
    fmask=None,
    lmask=None,
    epsilon: float = 1e-6,
    max_rel_error: float = 1e-3,
    min_abs_error: float = 1e-8,
    subset: Optional[int] = None,
    seed: int = 12345,
    print_results: bool = False,
) -> bool:
    """Central-difference check of d(loss)/d(params) for a MultiLayerNetwork
    or ComputationGraph (anything exposing ``_loss``-style via ``loss_for_check``).

    ``subset``: check only N randomly chosen parameters per tensor (the
    reference checks all; sub-sampling keeps CI fast for big nets).
    """
    with jax.enable_x64(True):
        def to64(t):
            if t is None:
                return None
            return jax.tree_util.tree_map(
                lambda a: jnp.asarray(np.asarray(a), jnp.float64), t
            )

        params64 = to64(model.params)
        state64 = to64(model.state)
        # x/y may be tuples of arrays (ComputationGraph multi-input/output)
        x, y, fm, lm = to64(x), to64(y), to64(fmask), to64(lmask)

        def loss_fn(p):
            loss, _ = model._loss(p, state64, x, y, fm, lm, rngs=None, train=False)
            return loss

        analytic = jax.grad(loss_fn)(params64)

        flat_p, treedef = jax.tree_util.tree_flatten(params64)
        flat_g = jax.tree_util.tree_leaves(analytic)
        rng = np.random.RandomState(seed)
        n_fail = 0
        n_checked = 0
        max_err = 0.0

        for ti, (p, g) in enumerate(zip(flat_p, flat_g)):
            pn = np.array(p, np.float64)  # writable copy
            gn = np.asarray(g, np.float64)
            size = pn.size
            if subset is not None and size > subset:
                idxs = rng.choice(size, subset, replace=False)
            else:
                idxs = np.arange(size)
            for flat_idx in idxs:
                orig = pn.flat[flat_idx]
                pn.flat[flat_idx] = orig + epsilon
                flat_p[ti] = jnp.asarray(pn)
                plus = float(loss_fn(jax.tree_util.tree_unflatten(treedef, flat_p)))
                pn.flat[flat_idx] = orig - epsilon
                flat_p[ti] = jnp.asarray(pn)
                minus = float(loss_fn(jax.tree_util.tree_unflatten(treedef, flat_p)))
                pn.flat[flat_idx] = orig
                flat_p[ti] = jnp.asarray(pn)

                numeric = (plus - minus) / (2 * epsilon)
                a = gn.flat[flat_idx]
                denom = abs(a) + abs(numeric)
                rel = abs(a - numeric) / denom if denom > 0 else 0.0
                n_checked += 1
                if rel > max_rel_error and abs(a - numeric) > min_abs_error:
                    n_fail += 1
                    if print_results:
                        print(f"FAIL tensor {ti} idx {flat_idx}: analytic={a:.8g} "
                              f"numeric={numeric:.8g} rel={rel:.4g}")
                max_err = max(max_err, rel if abs(a - numeric) > min_abs_error else 0.0)

        if print_results:
            print(f"Gradient check: {n_checked - n_fail}/{n_checked} passed, "
                  f"max rel error {max_err:.4g}")
        return n_fail == 0
