"""Compile a cell's programs for a described v5e (no chip), and print what
the chip's compiler says of them: seconds, Mosaic calls, bytes.

    JAX_PLATFORMS=cpu python benchmark/rehearse.py --workload <name> [--batch N]

A compile that passes is not a run: it finds what the compiler refuses
(kernel blocks, memory) before chip time is spent. Nothing here is read by
``run.py``. Only the train driver's step is rehearsed; a serving cell's
decode grid is tens of programs and is left to the chip.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int)
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.harness import spec

    cell = spec.load_cell(a.workload)
    cfg, traffic = cell["config"], cell["traffic"]
    fam = spec.module("families", cfg["family"])
    ref = spec.module("reference", cfg["reference"])
    B, T = a.batch or traffic["batch"], traffic["seq_len"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda t: jax.tree_util.tree_map(      # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), t)

    from deeplearning4j_tpu.nn.model import MultiLayerNetwork

    model = MultiLayerNetwork(fam.build_conf(cfg))
    words = ref.seed_words(0)
    params = jax.eval_shape(lambda s: fam.to_program(
        cfg, ref.make_weights(cfg, s, model.dtype)), words)
    model._build_updaters()
    opt = jax.eval_shape(lambda p: tuple(
        u.init(pi) for u, pi in zip(model._updaters, p)), params)
    state = tuple(l.init_state(it) for l, it in
                  zip(model.layers, model.layer_input_types))
    body = model._step_body(False)
    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"      # the layer gates' question
    try:
        t0 = time.perf_counter()
        ids = jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=one)
        compiled = jax.jit(
            lambda p, o, s, it, rng, x, y: body(p, o, s, it, rng, x, y,
                                                None, None, ())
        ).lower(sds(params), sds(opt), sds(state),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=one),
                sds(jax.eval_shape(lambda: jax.random.PRNGKey(0))),
                ids, ids).compile()
        dt = time.perf_counter() - t0
    finally:
        jax.default_backend = real_backend
    mem = compiled.memory_analysis()
    gib = 2.0 ** 30
    print(f"{a.workload}: B{B} x T{T} {cfg['dtype']}: compiled for v5e in "
          f"{dt:.1f}s; Mosaic calls {compiled.as_text().count('tpu_custom_call')}; "
          f"arguments {mem.argument_size_in_bytes / gib:.2f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / gib:.2f} GiB, "
          f"outputs {mem.output_size_in_bytes / gib:.2f} GiB "
          f"(aliased {mem.alias_size_in_bytes / gib:.2f})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
