"""What a recomputed ``ResidualBlock`` keeps between the passes, on the CPU
with the kernels in the Pallas interpreter: around an attention mixer that
runs a flash kernel, the kernel's result and its row statistic besides the
layer's input, so the differentiated program runs the forward kernel once;
around any other mixer, what a plain ``jax.checkpoint`` keeps. ``remat``
changes no number."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers import (
    GatedMLP, GroupedQueryAttention, Mamba2Mixer, MultiHeadAttention,
    MultiHeadLatentAttention, ResidualBlock, SparseMoE)
from deeplearning4j_tpu.ops.flash_attention import ATTN_LSE

C, T = 32, 128
IT = InputType.recurrent(C, T)

# two heads of 64 share a lane block: the [B, T, H*D] addressing
_GQA = functools.partial(GroupedQueryAttention, n_heads=2, n_kv_heads=1,
                         head_dim=64)
_MLA = functools.partial(MultiHeadLatentAttention, n_heads=2, q_rank=24,
                         kv_rank=16, nope_dim=128, rope_dim=64, v_dim=128,
                         rope_theta=32e6)
MIXERS = {
    # on the kernels (with the ``on_kernels`` fixture), and as the layers'
    # defaults have them off the TPU: the XLA path
    "gqa": _GQA, "mla": functools.partial(_MLA, use_flash=True),
    "gqa-xla": _GQA, "mla-xla": _MLA,
    "mamba2": lambda: Mamba2Mixer(n_heads=4, head_dim=8, n_groups=2,
                                  state_size=8, conv_kernel=4, chunk=8),
    "moe": lambda: SparseMoE(n_experts=16, top_k=3, expert_width=12,
                             shared_width=20, held_start=8, n_held=4,
                             routed_scaling=2.5),
    "gated_mlp": lambda: GatedMLP(width=40),
}
# the forward kernel, the width of what it writes and the number of backward
# kernels (one that gives dq, dk and dv, in either module), by attention mixer
KERNELS = {"gqa": ("flash_fwd", 2 * 64, 1), "mla": ("mla_flash_fwd", 2 * 128, 1)}
PLAIN = sorted(set(MIXERS) - set(KERNELS))


@pytest.fixture
def on_kernels(monkeypatch):
    """Attention takes its kernels with their Pallas backward, as on the
    TPU, in the interpreter. (``use_flash=True`` does that for latent
    attention; ``MultiHeadAttention`` off the TPU would pair the forward
    kernel with its XLA backward, which keeps no result.)"""
    monkeypatch.setattr(
        MultiHeadAttention, "_flash", lambda self: (
            dict(causal=self.causal, interpret=True, bwd="pallas"), None))


def _setup(name, remat):
    block = ResidualBlock(mixer=MIXERS[name](), remat=remat)
    p = block.init(jax.random.PRNGKey(0), IT)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, T, C), jnp.float32)
    probe = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    st = block.init_state(IT)
    loss = lambda q, u: jnp.sum(                                  # noqa: E731
        block.apply(q, st, u, train=True)[0] * probe)
    return loss, p, x


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def _kernel_names(jaxpr):
    return [e.params["name"] for e in _eqns(jaxpr)
            if e.primitive.name == "pallas_call"]


def _grad_jaxpr(loss, p, x):
    return jax.make_jaxpr(jax.grad(loss, (0, 1)))(p, x).jaxpr


def _recomputed(jaxpr):
    """The bodies that the differentiated program runs again."""
    return [e.params["jaxpr"] for e in _eqns(jaxpr)
            if e.primitive.name == "remat2" and e.params["differentiated"]]


@pytest.mark.parametrize("name", sorted(MIXERS))
def test_remat_changes_no_number(name, request):
    """Loss and every gradient with ``remat=True`` are those of
    ``remat=False`` to the bit: the backward reads the same operands."""
    if name in KERNELS:
        request.getfixturevalue("on_kernels")
    out = []
    for remat in (False, True):
        loss, p, x = _setup(name, remat)
        out.append(jax.jit(jax.value_and_grad(loss, (0, 1)))(p, x))
    for a, b in zip(*map(jax.tree_util.tree_leaves, out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_a_recomputed_attention_block_runs_its_forward_kernel_once(
        name, on_kernels):
    kernel, width, n_bwd = KERNELS[name]
    loss, p, x = _setup(name, True)
    jaxpr = _grad_jaxpr(loss, p, x)
    whole = _kernel_names(jaxpr)
    assert len([n for n in whole if n.startswith(kernel)]) == 1, whole
    assert len([n for n in whole if "_bwd_" in n]) == n_bwd, whole
    (body,) = _recomputed(jaxpr)
    again = _kernel_names(body)
    # the recomputed layer holds the backward kernels and no forward one
    assert not [n for n in again if n.startswith(kernel)], again
    assert len(again) == n_bwd and all("_bwd_" in n for n in again), again
    # kept from inside the layer: the kernel's two results and nothing else
    # (jax passes a kept value that the forward pass reads too, as ``o`` is,
    # through a full-width ``reduce_precision``, and describes it by that)
    inside = {aval.shape: what for aval, what in saved_residuals(loss, p, x)
              if not what.startswith(("from the argument", "from a constant"))}
    assert len(inside) == 2 and all("ops/flash_" in w for w in inside.values())
    lse = next(s for s, what in inside.items() if f"named '{ATTN_LSE}'" in what)
    assert int(np.prod(lse)) == 2 * T               # a row statistic a head
    assert T * width in {int(np.prod(s)) for s in inside}, inside

    # without the rule the forward kernel is in the recomputed layer again
    plain, _, _ = _setup(name, False)
    (body,) = _recomputed(_grad_jaxpr(jax.checkpoint(plain), p, x))
    assert len([n for n in _kernel_names(body) if n.startswith(kernel)]) == 1


@pytest.mark.parametrize("name", PLAIN)
def test_a_block_that_calls_no_kernel_keeps_what_a_plain_checkpoint_keeps(name):
    """Nothing is named in a state-space mixer, an expert layer, a gated MLP
    or attention on its XLA path (off the TPU): between the passes such a
    block keeps its input, its parameters and its state, as it did."""
    loss, p, x = _setup(name, True)
    shapes = lambda kept: sorted(                                 # noqa: E731
        (a.shape, str(a.dtype)) for a, _ in kept)
    kept = saved_residuals(loss, p, x)
    assert not [what for _, what in kept if what.startswith("named")], kept
    assert not _kernel_names(_grad_jaxpr(loss, p, x))

    plain, _, _ = _setup(name, False)
    assert shapes(kept) == shapes(saved_residuals(jax.checkpoint(plain), p, x))
    # and that is: the layer's input, parameters and state (a constant of
    # this loss, as the probe is), nothing from inside the layer
    assert all(what.startswith(("from the argument", "from a constant"))
               for _, what in kept), kept
