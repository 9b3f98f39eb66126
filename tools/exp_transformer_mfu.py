"""Round-5 chip session: transformer MFU push (VERDICT r4 #6).

Three measurements on the bench config (d2048, T2048, B16, 8 blocks):

1. The FULL train step as it stands (the flash kernels pick their blocks
   from the shapes; tools/flash_tile_sweep.py sweeps them per kernel).
2. Op-mix attribution: jit + cost-analyze the pieces at bench shapes
   (layernorm, residual add, attention core, MLP, adam update) to bound
   which HBM traffic explains the d512-config MFU 0.112 claim.
3. A remat variant: jax.checkpoint around each TransformerBlock apply,
   measuring whether activation-memory relief buys scheduler headroom.

Usage:  python tools/exp_transformer_mfu.py [step|opmix|remat]
(each mode is one process: a chip belongs to one process at a time).
"""

import os
import sys
import time

import numpy as np


def _setup():
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import TransformerLM
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork

    vocab, T, d_model, heads, blocks, batch = 2048, 2048, 2048, 16, 8, 16
    model = MultiLayerNetwork(TransformerLM(
        vocab_size=vocab, max_len=T, d_model=d_model, n_heads=heads,
        n_blocks=blocks, updater={"type": "adam", "lr": 1e-4})).init()
    rs = np.random.RandomState(0)
    ids = rs.randint(0, vocab, (batch, T))
    x = jnp.asarray(ids)
    y = jnp.asarray(np.roll(ids, -1, axis=1).astype(np.int32))
    return jax, jnp, model, x, y, (vocab, T, d_model, heads, blocks, batch)


def _time_step(jax, jnp, model, x, y, warmup=3, iters=12):
    step = model._get_step_fn(False)
    rng = jax.random.PRNGKey(0)
    compiled = step.lower(model.params, model.opt_state, model.state,
                          jnp.asarray(0, jnp.int32), rng, x, y,
                          None, None, ()).compile()
    st = [model.params, model.opt_state, model.state]
    loss = None
    for i in range(warmup):
        st[0], st[1], st[2], _, loss = compiled(
            st[0], st[1], st[2], jnp.asarray(i, jnp.int32), rng, x, y,
            None, None, ())
    float(loss)
    t0 = time.perf_counter()
    for i in range(iters):
        st[0], st[1], st[2], _, loss = compiled(
            st[0], st[1], st[2], jnp.asarray(i, jnp.int32), rng, x, y,
            None, None, ())
    float(loss)  # value fetch: a hard sync
    dt = (time.perf_counter() - t0) / iters
    return dt, compiled


def _mfu(site, key, compiled, dt):
    """Peak lookup + static cost harvest live in obs/profile.py (the single
    MFU methodology); DL4J_TPU_PEAK_FLOPS overrides unknown backends."""
    from deeplearning4j_tpu.obs import profile

    entry = profile.harvest_compiled(site, compiled, key=key) or {}
    peak = profile.peak_flops("bfloat16")
    if not peak:
        return float("nan")
    return entry.get("flops", 0.0) / dt / peak


def step():
    jax, jnp, model, x, y, cfg = _setup()
    _, T, d, _, _, B = cfg
    dt, compiled = _time_step(jax, jnp, model, x, y)
    mfu = _mfu("exp.transformer", "step", compiled, dt)
    print(f"RESULT step: {dt*1000:.1f} ms/step {B*T/dt:,.0f} tok/s "
          f"MFU={mfu:.3f}", flush=True)


def opmix():
    jax, jnp, model, x, y, cfg = _setup()
    import jax.numpy as jnp  # noqa: F811
    _, T, d, H, nb, B = cfg

    def analyze(tag, fn, *args):
        c = jax.jit(fn).lower(*args).compile()
        ca = c.cost_analysis()
        ca = ca[0] if isinstance(ca, list) else ca
        # time it too
        out = c(*args)
        jax.tree_util.tree_map(lambda a: a, out)
        t0 = time.perf_counter()
        for _ in range(20):
            out = c(*args)
        leaves = jax.tree_util.tree_leaves(out)
        float(jnp.sum(leaves[0][..., :1].astype(jnp.float32)))
        dt = (time.perf_counter() - t0) / 20
        print(f"{tag:24s} {dt*1e3:7.3f} ms  bytes={ca.get('bytes accessed', 0):.3e} "
              f"flops={ca.get('flops', 0):.3e}", flush=True)

    rs = np.random.RandomState(1)
    act = jnp.asarray(rs.rand(B, T, d).astype(np.float32)).astype(jnp.bfloat16)
    gamma = jnp.ones((d,), jnp.bfloat16)
    analyze("layernorm fwd", lambda a, g: (a - a.mean(-1, keepdims=True))
            / (a.std(-1, keepdims=True) + 1e-5) * g, act, gamma)
    analyze("residual add", lambda a, b: a + b, act, act)
    w = jnp.asarray(rs.rand(d, 4 * d).astype(np.float32)).astype(jnp.bfloat16)
    analyze("mlp matmul in", lambda a, w: a @ w, act, w)
    # adam update at full param scale
    p_leaves = jax.tree_util.tree_leaves(model.params)
    nparams = sum(int(np.prod(p.shape)) for p in p_leaves)
    pv = jnp.zeros((nparams // 4,), jnp.float32)  # quarter-scale probe
    analyze("adam-ish update x4", lambda p, g: (p - 1e-4 * g / (jnp.sqrt(g * g) + 1e-8),
                                                0.9 * g), pv, pv)
    print(f"n_params={nparams:,}", flush=True)


def remat():
    os.environ["DL4J_TPU_REMAT_BLOCKS"] = "1"
    jax, jnp, model, x, y, cfg = _setup()
    _, T, d, _, _, B = cfg
    dt, compiled = _time_step(jax, jnp, model, x, y)
    mfu = _mfu("exp.transformer", "remat", compiled, dt)
    print(f"RESULT remat: {dt*1000:.1f} ms/step {B*T/dt:,.0f} tok/s "
          f"MFU={mfu:.3f}", flush=True)


if __name__ == "__main__":
    {"step": step, "opmix": opmix, "remat": remat}[
        sys.argv[1] if len(sys.argv) > 1 else "step"]()
