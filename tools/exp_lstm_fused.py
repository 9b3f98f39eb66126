"""Round-5 chip session: fused vs scan LSTM on the char-RNN bench config.

A/B at the BASELINE shapes (GravesLSTM x2, H=256, B=128, T=50, f32,
rmsprop): full train-step throughput with the scan path vs the
weight-stationary Pallas kernel (DL4J_TPU_FUSED_LSTM). Value-fetch sync.
Run each arm in its own process (the env flag is read at trace time):
    python tools/exp_lstm_fused.py scan
    python tools/exp_lstm_fused.py fused
"""

import os
import sys
import time

import numpy as np

arm = sys.argv[1] if len(sys.argv) > 1 else "fused"
os.environ["DL4J_TPU_FUSED_LSTM"] = "1" if arm == "fused" else "0"

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402

from deeplearning4j_tpu.models import TextGenerationLSTM       # noqa: E402
from deeplearning4j_tpu.nn.model import MultiLayerNetwork      # noqa: E402

vocab, T, H, B = 77, 50, 256, 128
model = MultiLayerNetwork(TextGenerationLSTM(
    vocab_size=vocab, timesteps=T, hidden=H, dtype="float32")).init()
rs = np.random.RandomState(0)
ids = rs.randint(0, vocab, (B, T))
x = jnp.asarray(np.eye(vocab, dtype=np.float32)[ids])
y = jnp.asarray(np.eye(vocab, dtype=np.float32)[np.roll(ids, -1, axis=1)])

step = model._get_step_fn(False)
rng = jax.random.PRNGKey(0)
compiled = step.lower(model.params, model.opt_state, model.state,
                      jnp.asarray(0, jnp.int32), rng, x, y,
                      None, None, ()).compile()
st = [model.params, model.opt_state, model.state]
loss = None
for i in range(5):
    st[0], st[1], st[2], _, loss = compiled(
        st[0], st[1], st[2], jnp.asarray(i, jnp.int32), rng, x, y,
        None, None, ())
float(loss)
t0 = time.perf_counter()
N = 50
for i in range(N):
    st[0], st[1], st[2], _, loss = compiled(
        st[0], st[1], st[2], jnp.asarray(i, jnp.int32), rng, x, y,
        None, None, ())
float(loss)   # value fetch: a hard sync
dt = (time.perf_counter() - t0) / N
tps = B * T / dt
ca = compiled.cost_analysis()
ca = ca[0] if isinstance(ca, list) else ca
mfu = float(ca.get("flops", 0.0)) / dt / 197e12
print(f"RESULT {arm}: {dt*1000:.2f} ms/step  {tps:,.0f} tok/s  MFU={mfu:.4f}",
      flush=True)
