"""State-space layers: the Mamba-2 mixer (Dao & Gu 2024, "Transformers are
SSMs"), as the ``nemotron_h`` family uses it.

One mixer over ``u`` [B, T, C]:

    [z | xBC | dt] = u W_in
    xBC  <- silu(causal depthwise conv1d(xBC, k) + b_conv)
    x [T, H, P], B [T, G, N], C [T, G, N] = split(xBC)
    dt   = softplus(dt + dt_bias);  A = -exp(A_log)          (one per head)
    S_t  = exp(dt_t A_h) S_{t-1} + dt_t x_t (outer) B_t^g    (g = h // (H/G))
    y_t  = S_t C_t^g + D_h x_t
    y    <- RMSNorm over each group's channels of (y * silu(z)), times w
    out  = y W_out

The recurrence is computed by chunks (``ssd_chunked_scan``): inside a chunk
as matrix products over the chunk's positions, between chunks by carrying
the state ``S`` through a ``lax.scan``; every piece is plain ``jax.numpy``,
so ``jax.grad`` gives the backward pass (the scan over chunks is reversed by
autodiff, the within-chunk products by their transposes).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import initializers
from deeplearning4j_tpu.nn.config import LayerConfig, register_layer
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers.normalization import rms_norm


def ssd_chunked_scan(x, dt, A, Bm, Cm, chunk: int):
    """The selective state-space recurrence by chunks.

    ``x`` [B, T, H, P], ``dt`` [B, T, H] (already positive), ``A`` [H]
    (negative), ``Bm``/``Cm`` [B, T, G, N] with ``H % G == 0``. Returns
    ``y`` [B, T, H, P] with ``y_t = S_t C_t`` (the skip ``D x`` is the
    caller's). ``T`` need not be a multiple of ``chunk``: the tail is padded
    with ``dt = 0``, which leaves the state as it is and adds nothing.
    """
    Bsz, T, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Hg = H // G
    Q = int(chunk)
    pad = (-T) % Q
    if pad:
        x, dt, Bm, Cm = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                         for t in (x, dt, Bm, Cm))
    nc = (T + pad) // Q
    f32 = jnp.float32
    # chunked views: heads as [G, Hg] so a group's B and C are shared by its heads
    xc = (x * dt[..., None]).reshape(Bsz, nc, Q, G, Hg, P)     # dt_s x_s
    Bc = Bm.reshape(Bsz, nc, Q, G, N)
    Cc = Cm.reshape(Bsz, nc, Q, G, N)
    a = (dt.astype(f32) * A.astype(f32)).reshape(Bsz, nc, Q, G, Hg)
    cum = jnp.cumsum(a, axis=2)                                # log decay to t, inclusive
    total = cum[:, :, -1]                                      # [B, nc, G, Hg]

    # inside a chunk: y_t += sum_{s<=t} exp(cum_t - cum_s) (C_t . B_s) dt_s x_s
    cb = jnp.einsum("bcqgn,bcsgn->bcgqs", Cc, Bc)
    diff = (jnp.moveaxis(cum, 2, -1)[..., :, None]
            - jnp.moveaxis(cum, 2, -1)[..., None, :])          # [B,nc,G,Hg,Q,Q]
    lower = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf)).astype(x.dtype)
    y = jnp.einsum("bcghqs,bcsghp->bcqghp", cb[:, :, :, None] * decay, xc)

    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(total[:, :, None] - cum).astype(x.dtype)  # [B,nc,Q,G,Hg]
    states = jnp.einsum("bcsgh,bcsghp,bcsgn->bcghpn", to_end, xc, Bc)

    # between chunks: S entering chunk c (the carry), a scan over nc steps
    def step(S, inp):
        s_c, tot = inp
        return jnp.exp(tot)[..., None, None].astype(S.dtype) * S + s_c, S

    S0 = jnp.zeros((Bsz, G, Hg, P, N), x.dtype)
    _, entering = jax.lax.scan(
        step, S0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(total, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                    # [B,nc,G,Hg,P,N]
    y = y + jnp.einsum("bcqgn,bcghpn->bcqghp", Cc, entering) \
        * jnp.exp(cum).astype(x.dtype)[..., None]
    return y.reshape(Bsz, nc * Q, H, P)[:, :T]


def causal_depthwise_conv1d(x, w, b=None):
    """``x`` [B, T, C], ``w`` [k, C] (tap ``k-1`` multiplies the current
    position), ``b`` [C] or none: out_t = b + sum_j w[j] x[t - (k-1-j)],
    zeros before the start."""
    k = w.shape[0]
    T = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    taps = sum(xp[:, j:j + T] * w[j] for j in range(k))
    return taps if b is None else b + taps


@register_layer("mamba2_mixer")
@dataclass
class Mamba2Mixer(LayerConfig):
    """The Mamba-2 mixer over [B, T, C] (no residual, no pre-norm: wrap it in
    a ``ResidualBlock``). ``n_heads * head_dim`` is the inner width;
    ``n_groups`` groups share ``B``/``C`` and the gated norm's statistics."""

    n_heads: int = 8
    head_dim: int = 64
    n_groups: int = 1
    state_size: int = 128
    conv_kernel: int = 4
    chunk: int = 128
    eps: float = 1e-5
    dt_min: float = 1e-3
    dt_max: float = 0.1
    weight_init: Any = "xavier"

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def _sizes(self):
        inner = self.n_heads * self.head_dim
        bc = self.n_groups * self.state_size
        return inner, bc, inner + 2 * bc

    def init(self, key, input_type, dtype=jnp.float32):
        C = input_type.size
        inner, _, conv_dim = self._sizes()
        if self.n_heads % self.n_groups:
            raise ValueError(f"n_groups={self.n_groups} must divide "
                             f"n_heads={self.n_heads}")
        k_in, k_out, k_conv, k_dt = jax.random.split(key, 4)
        proj = inner + conv_dim + self.n_heads
        H = self.n_heads
        # dt_bias so that softplus(dt_bias) is log-uniform in [dt_min, dt_max]
        u = jax.random.uniform(k_dt, (H,), jnp.float32)
        dt0 = jnp.exp(u * (jnp.log(self.dt_max) - jnp.log(self.dt_min))
                      + jnp.log(self.dt_min))
        return {
            "W_in": initializers.initialize(self.weight_init, k_in, (C, proj), C, proj, dtype),
            "conv_w": (jax.random.uniform(k_conv, (self.conv_kernel, conv_dim), jnp.float32,
                                          -1.0, 1.0) / self.conv_kernel ** 0.5).astype(dtype),
            "conv_b": jnp.zeros((conv_dim,), dtype),
            "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype),
            "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)).astype(dtype),
            "D": jnp.ones((H,), dtype),
            "norm": jnp.ones((inner,), dtype),
            "W_out": initializers.initialize(self.weight_init, k_out, (inner, C), inner, C, dtype),
        }

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        Bsz, T, _ = x.shape
        H, P, G, N = self.n_heads, self.head_dim, self.n_groups, self.state_size
        inner, bc, conv_dim = self._sizes()
        with jax.named_scope("ssm"):
            zxbcdt = x @ params["W_in"]
            z, xBC, dt = jnp.split(zxbcdt, [inner, inner + conv_dim], axis=-1)
            xBC = jax.nn.silu(causal_depthwise_conv1d(
                xBC, params["conv_w"], params["conv_b"]))
            xs, Bm, Cm = jnp.split(xBC, [inner, inner + bc], axis=-1)
            dt = jax.nn.softplus(dt + params["dt_bias"])
            if mask is not None and mask.ndim >= 2:
                # a padded position leaves the state as it is
                dt = dt * mask.reshape(Bsz, T, 1).astype(dt.dtype)
            xs = xs.reshape(Bsz, T, H, P)
            with jax.named_scope("scan"):
                y = ssd_chunked_scan(
                    xs, dt, -jnp.exp(params["A_log"].astype(jnp.float32)),
                    Bm.reshape(Bsz, T, G, N), Cm.reshape(Bsz, T, G, N), self.chunk)
            y = y + xs * params["D"][:, None]
            y = (y.reshape(Bsz, T, inner) * jax.nn.silu(z)).reshape(Bsz, T, G, inner // G)
            y = rms_norm(y, None, self.eps).reshape(Bsz, T, inner) * params["norm"]
            return y @ params["W_out"], state
