"""NULL EXPERIMENT: hand-rolled ResNet50 train step in plain JAX.

Purpose (docs/PERF.md "ResNet50 roofline"): decide whether the framework's
measured MFU (~0.27 in round 3) is the chip's ceiling for this op mix or a
framework artifact. This file deliberately imports NOTHING from
deeplearning4j_tpu — it is an independent implementation of the same
workload: ResNet-v1 bottlenecks (stride on the first 1x1, like
zoo/model/ResNet50.java), conv7 stem, BatchNorm with batch stats + running
averages, softmax cross-entropy vs one-hot, Adam with f32 moments over
bf16 params, batch 128 @ 224x224 bf16, one step = fwd + bwd + update.

Run ON THE CHIP (single process):  python tools/null_resnet50.py
"""
import functools
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

DTYPE = jnp.bfloat16
STAGES = [((64, 64, 256), 3, 1), ((128, 128, 512), 4, 2),
          ((256, 256, 1024), 6, 2), ((512, 512, 2048), 3, 2)]


def conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    w = jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)
    return (w * np.sqrt(2.0 / fan_in)).astype(DTYPE)


def init_params(key, classes=1000):
    params = {}
    bn = {}
    ks = iter(jax.random.split(key, 256))

    def add_conv_bn(name, kh, kw, cin, cout):
        params[name + "/w"] = conv_init(next(ks), kh, kw, cin, cout)
        params[name + "/gamma"] = jnp.ones((cout,), DTYPE)
        params[name + "/beta"] = jnp.zeros((cout,), DTYPE)
        bn[name + "/mean"] = jnp.zeros((cout,), jnp.float32)
        bn[name + "/var"] = jnp.ones((cout,), jnp.float32)

    add_conv_bn("stem", 7, 7, 3, 64)
    cin = 64
    for si, (filters, blocks, _stride) in enumerate(STAGES):
        f1, f2, f3 = filters
        for b in range(blocks):
            n = f"s{si}b{b}"
            add_conv_bn(n + "a", 1, 1, cin if b == 0 else f3, f1)
            add_conv_bn(n + "b", 3, 3, f1, f2)
            add_conv_bn(n + "c", 1, 1, f2, f3)
            if b == 0:
                add_conv_bn(n + "ds", 1, 1, cin, f3)
        cin = f3
    params["fc/w"] = (jax.random.normal(next(ks), (2048, classes), jnp.float32)
                      * np.sqrt(1.0 / 2048)).astype(DTYPE)
    params["fc/b"] = jnp.zeros((classes,), DTYPE)
    return params, bn


def conv(x, w, stride):
    # bf16 in/out; the MXU accumulates in f32 internally. (An explicit
    # preferred_element_type=f32 breaks the conv transpose rule under
    # autodiff: cotangents become f32 against bf16 primals.)
    return lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC")).astype(jnp.float32)


def bn_apply(x32, gamma, beta, name, bn_state, new_bn, momentum=0.9):
    mean = jnp.mean(x32, axis=(0, 1, 2))
    var = jnp.var(x32, axis=(0, 1, 2))
    new_bn[name + "/mean"] = momentum * bn_state[name + "/mean"] + (1 - momentum) * mean
    new_bn[name + "/var"] = momentum * bn_state[name + "/var"] + (1 - momentum) * var
    inv = lax.rsqrt(var + 1e-5)
    scale = (gamma.astype(jnp.float32) * inv).astype(DTYPE)
    shift = (beta.astype(jnp.float32) - mean * gamma.astype(jnp.float32) * inv
             ).astype(DTYPE)
    return x32.astype(DTYPE) * scale + shift


def conv_bn(x, params, bn_state, new_bn, name, stride=1, relu=True):
    y = conv(x, params[name + "/w"], stride)
    y = bn_apply(y, params[name + "/gamma"], params[name + "/beta"],
                 name, bn_state, new_bn)
    return jax.nn.relu(y) if relu else y


def forward(params, bn_state, x):
    new_bn = {}
    h = conv_bn(x, params, bn_state, new_bn, "stem", stride=2)
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    for si, (filters, blocks, stride) in enumerate(STAGES):
        for b in range(blocks):
            n = f"s{si}b{b}"
            s = stride if b == 0 else 1
            inp = h
            h = conv_bn(inp, params, bn_state, new_bn, n + "a", stride=s)
            h = conv_bn(h, params, bn_state, new_bn, n + "b")
            h = conv_bn(h, params, bn_state, new_bn, n + "c", relu=False)
            if b == 0:
                short = conv_bn(inp, params, bn_state, new_bn, n + "ds",
                                stride=s, relu=False)
            else:
                short = inp
            h = jax.nn.relu(h + short)
    h = jnp.mean(h.astype(jnp.float32), axis=(1, 2)).astype(DTYPE)
    logits = (h @ params["fc/w"]).astype(jnp.float32) + params["fc/b"].astype(jnp.float32)
    return logits, new_bn


def loss_fn(params, bn_state, x, y):
    logits, new_bn = forward(params, bn_state, x)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.sum(y * logp, axis=-1)), new_bn


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def train_step(params, opt, bn_state, x, y, step):
    (loss, new_bn), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, bn_state, x, y)
    b1, b2, eps, lr = 0.9, 0.999, 1e-8, 1e-3
    t = step.astype(jnp.float32) + 1.0
    new_params, new_opt = {}, {}
    for k in params:
        g = grads[k].astype(jnp.float32)
        m = b1 * opt[k][0] + (1 - b1) * g
        v = b2 * opt[k][1] + (1 - b2) * g * g
        upd = lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        new_params[k] = (params[k].astype(jnp.float32) - upd).astype(params[k].dtype)
        new_opt[k] = (m, v)
    return new_params, new_opt, new_bn, loss


def main():
    batch, size, classes = 128, 224, 1000
    key = jax.random.PRNGKey(0)
    params, bn_state = init_params(key, classes)
    opt = {k: (jnp.zeros(v.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32))
           for k, v in params.items()}
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.rand(batch, size, size, 3), DTYPE)
    y = jnp.asarray(np.eye(classes, dtype=np.float32)[
        rs.randint(0, classes, batch)])

    lowered = train_step.lower(params, opt, bn_state, x, y,
                               jnp.asarray(0, jnp.int32))
    compiled = lowered.compile()
    cost = compiled.cost_analysis() or {}
    fl = cost.get("flops", 0.0)
    byt = cost.get("bytes accessed", 0.0)

    st = [params, opt, bn_state]
    loss = None
    for i in range(3):
        st[0], st[1], st[2], loss = compiled(st[0], st[1], st[2], x, y,
                                             jnp.asarray(i, jnp.int32))
    float(loss)
    n = 20
    t0 = time.perf_counter()
    for i in range(n):
        st[0], st[1], st[2], loss = compiled(st[0], st[1], st[2], x, y,
                                             jnp.asarray(i, jnp.int32))
    float(loss)  # scalar value fetch: a hard sync
    dt = (time.perf_counter() - t0) / n
    ips = batch / dt
    print(f"null-resnet50: {dt*1e3:.1f} ms/step  {ips:.1f} images/sec  "
          f"xla_flops={fl/1e9:.1f}G  bytes={byt/1e9:.2f}G  "
          f"MFU={fl/dt/197e12:.3f}")


if __name__ == "__main__":
    main()
