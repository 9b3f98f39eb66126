"""Every Pallas entry point compiles for the TPU — no chip needed.

libtpu ships a compile-only client: ``get_topology_desc("v5e:2x2")`` hands
back four ``TPU v5 lite`` devices that can be compiled FOR (Mosaic included)
but not run on, even under ``JAX_PLATFORMS=cpu``. The interpret-mode tests
(test_flash_attention.py, test_fused_lstm.py) prove the kernels' math; this
file proves the TPU lowering accepts their blocks, layouts and VMEM budgets
— the class of failure that interpret mode cannot see (a (128, 2, 1024)
block of a (128, 50, 1024) array; a sublane broadcast Mosaic rejects).
Shapes are the ones ``chip_smoke.py`` runs on the chip, cut to what
compiles in seconds. Whether they RUN right is chip_smoke.py's business.
"""

import functools

import pytest

import jax
import jax.numpy as jnp

pytest.importorskip("libtpu")

from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from deeplearning4j_tpu.ops.flash_attention import (  # noqa: E402
    flash_attention, flash_attention_block_grad, merge_attention_blocks)
from deeplearning4j_tpu.ops.fused_lstm import fits_vmem, fused_lstm  # noqa: E402


@functools.lru_cache(maxsize=None)
def _topology():
    topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")
    assert "v5" in topo.devices[0].device_kind.lower()
    return topo


def _sharding():
    return SingleDeviceSharding(_topology().devices[0])


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=_sharding())


def _compile(fn, *avals):
    """Lower + compile for the v5e topology; returns the HLO text."""
    return jax.jit(fn).lower(*avals).compile().as_text()


def _n_mosaic(hlo: str) -> int:
    return hlo.count('custom_call_target="tpu_custom_call"')


def _f32sum(*xs):
    return sum(jnp.sum(x.astype(jnp.float32)) for x in xs)


# ---------------------------------------------------------------------------
# flash attention (ops/flash_attention.py)
# ---------------------------------------------------------------------------

FLASH_SHAPES = [
    pytest.param((16, 2048, 16, 128), jnp.bfloat16, id="flagship-bf16"),
    pytest.param((2, 1000, 4, 64), jnp.float32, id="unaligned-f32"),
]


@pytest.mark.parametrize("shape,dtype", FLASH_SHAPES)
@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "kmask"])
def test_flash_fwd_bwd_compiles(shape, dtype, masked):
    B, T = shape[:2]

    def loss(q, k, v, km):
        return _f32sum(flash_attention(
            q, k, v, kmask=km if masked else None, causal=True))

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                   *[_sds(shape, dtype)] * 3, _sds((B, T), jnp.float32))
    assert _n_mosaic(hlo) == 3      # forward + dq + dk/dv kernels


def _q_sized_moves(hlo: str, n: int):
    """The ``copy`` and ``transpose`` instructions of a compiled module whose
    result holds at least ``n`` elements: what a layout change of a q-sized
    array costs the device."""
    import re

    found = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* "
                     r"(copy|transpose)\(", line)
        if m and m.group(2):
            size = 1
            for d in m.group(2).split(","):
                size *= int(d)
            if size >= n:
                found.append(line.strip()[:160])
    return found


ATTENTION_LAYERS = [
    pytest.param("multi_head", 8, 1024, 1024,
                 dict(n_heads=16), 16 * 64, id="gpt2m-b8-t1024"),
    pytest.param("multi_head", 32, 256, 1024,
                 dict(n_heads=16), 16 * 64, id="gpt2m-b32-t256"),
    pytest.param("multi_head", 1, 4096, 4096,
                 dict(n_heads=32), 32 * 128, id="d128-b1-t4096"),
    pytest.param("grouped_query", 1, 4096, 2688,
                 dict(n_heads=32, n_kv_heads=2, head_dim=128), 32 * 128,
                 id="twotower-b1-t4096"),
]


@pytest.mark.parametrize("kind,B,T,C,kw,width", ATTENTION_LAYERS)
def test_attention_layers_move_no_q_sized_array(monkeypatch, kind, B, T, C,
                                                kw, width):
    """The benchmark's attention layers (and one of 32 heads of 128, the
    hybrid cell's q at full key-value heads), float32, forward and backward,
    compiled for v5e: the three Mosaic calls read the projections where the
    matmuls leave them and write where the next matmul reads, so no ``copy``
    or ``transpose`` of an array as large as q is left in the module (the
    parent's held ten a GPT-2 layer). The grouped-query layer keeps two: dk
    and dv, computed a query head, are relaid for the sum over the 16 heads
    that share a key-value head (XLA's ``jnp.repeat`` backward); they go
    when the kernels take k and v at their own head count (ROADMAP S17)."""
    from deeplearning4j_tpu.nn.input_type import InputType
    from deeplearning4j_tpu.nn.layers import (
        GroupedQueryAttention, MultiHeadAttention)

    layer = {"multi_head": MultiHeadAttention,
             "grouped_query": GroupedQueryAttention}[kind](causal=True, **kw)
    params = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), InputType.recurrent(C, T), jnp.float32))
    p_sds = jax.tree_util.tree_map(lambda a: _sds(a.shape, a.dtype), params)

    def loss(p, x):
        return _f32sum(layer.apply(p, {}, x)[0])

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo = _compile(jax.grad(loss, argnums=(0, 1)), p_sds,
                   _sds((B, T, C), jnp.float32))
    assert _n_mosaic(hlo) == 3
    moves = _q_sized_moves(hlo, B * T * width)
    assert len(moves) == (2 if kind == "grouped_query" else 0), moves


def test_flash_ring_blocks_compile():
    """The ring path's building blocks: two differentiable key chunks
    merged by their logsumexp (parallel/ring.py does exactly this per
    ring step)."""
    B, T, H, D = 4, 1024, 8, 128

    def loss(q, k, v):
        half = T // 2
        parts = [
            flash_attention_block_grad(
                q, k[:, s:s + half], v[:, s:s + half], q_offset=0,
                k_offset=s, causal=True)
            for s in (0, half)]
        return _f32sum(merge_attention_blocks(parts))

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                   *[_sds((B, T, H, D), jnp.bfloat16)] * 3)
    assert _n_mosaic(hlo) == 6      # (fwd + dq + dk/dv) x 2 chunks


# ---------------------------------------------------------------------------
# fused LSTM (ops/fused_lstm.py)
# ---------------------------------------------------------------------------

LSTM_SHAPES = [
    pytest.param(128, 50, 256, jnp.float32, id="B128-T50-H256-f32"),
    pytest.param(512, 50, 1024, jnp.bfloat16, id="B512-T50-H1024-bf16"),
    pytest.param(16, 53, 128, jnp.float32, id="prime-T-padded"),
]


@pytest.mark.parametrize("B,T,H,dtype", LSTM_SHAPES)
@pytest.mark.parametrize("peephole", [False, True], ids=["lstm", "graves"])
@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "mask"])
def test_fused_lstm_fwd_bwd_compiles(B, T, H, dtype, peephole, masked):
    assert fits_vmem(B, H, jnp.dtype(dtype).itemsize)

    def loss(zx, wh, h0, c0, m, p):
        out, (hT, cT) = fused_lstm(zx, wh, h0, c0, m if masked else None,
                                   p if peephole else None)
        return _f32sum(out, hT, cT)

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 5)),
                   _sds((B, T, 4 * H), dtype), _sds((H, 4 * H), dtype),
                   _sds((B, H), dtype), _sds((B, H), dtype),
                   _sds((B, T), jnp.float32), _sds((3 * H,), dtype))
    assert _n_mosaic(hlo) == 2      # forward + backward kernels


@pytest.mark.parametrize("d,m,H,C", [(4, 1, 16, 2048), (2, 2, 16, 2048),
                                     (2, 2, 6, 384)],
                         ids=["data4", "data2xmodel2",
                              "data2xmodel2-odd-local-heads"])
def test_flash_under_a_mesh_compiles(monkeypatch, d, m, H, C):
    """GSPMD cannot partition a Mosaic kernel ("Please wrap the call in a
    shard_map" — what MeshTrainer hit on the four-chip host): under an
    active multi-device mesh the attention layer runs the kernel inside a
    shard_map over (data, model). Compiled here for all four chips of the
    v5e:2x2 topology, at the flagship's attention shape; and at six heads of
    64, where each of two model shards holds three: an odd local count pairs
    up into no lane block, so the shard's kernels take the transposed
    arrays (the addressing follows what the call sees, per shard)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.nn.input_type import InputType
    from deeplearning4j_tpu.nn.layers.attention import MultiHeadAttention
    from deeplearning4j_tpu.parallel.context import use_mesh

    B, T = 16, 2048
    layer = MultiHeadAttention(n_heads=H, causal=True)
    params = layer.init(jax.random.PRNGKey(0), InputType.recurrent(C, T),
                        jnp.bfloat16)
    devices = _topology().devices
    mesh = Mesh(np.array(devices).reshape(d, m, 1, 1),
                ("data", "model", "seq", "pipe"))
    repl = NamedSharding(mesh, P())
    p_sds = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=repl),
        params)
    x_sds = jax.ShapeDtypeStruct((B, T, C), jnp.bfloat16,
                                 sharding=NamedSharding(mesh, P("data")))

    def loss(p, x):
        y, _ = layer.apply(p, {}, x)
        return jnp.sum(y.astype(jnp.float32))

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with use_mesh(mesh):
        hlo = jax.jit(jax.grad(loss)).lower(p_sds, x_sds).compile().as_text()
    assert _n_mosaic(hlo) == 3
    assert ("flash_fwd_q" in hlo) == (H // m % 2 == 1)


# ---------------------------------------------------------------------------
# whole train steps: the layer gates take the kernels at default settings
# ---------------------------------------------------------------------------


def _step_hlo(model, x, y, with_carries):
    """The model's own train-step body, compiled for v5e. The layer gates
    (nn/layers/recurrent.py, nn/layers/attention.py) key on
    ``jax.default_backend()`` — the CPU in this sandbox — so callers patch
    it to answer "tpu": the trace then makes the choices it makes on the
    chip (kernel on, ``interpret=False``)."""
    carries = tuple(
        l.initial_carry(x.shape[0], model.dtype) if f else ()
        for l, f in zip(model.layers, model._carry_flags)
    ) if with_carries else ()
    to_sds = lambda t: jax.tree_util.tree_map(   # noqa: E731
        lambda a: _sds(a.shape, a.dtype), t)
    body = model._step_body(with_carries)
    return _compile(
        lambda p, o, s, it, rng, x, y, c: body(p, o, s, it, rng, x, y,
                                               None, None, c),
        to_sds(model.params), to_sds(model.opt_state), to_sds(model.state),
        _sds((), jnp.int32), to_sds(jax.random.PRNGKey(0)), x, y,
        to_sds(carries))


def test_text_generation_lstm_step_takes_the_kernel(monkeypatch):
    """BASELINE #3 at its bench shape and DEFAULT settings: the tBPTT train
    step compiles for v5e with the fused kernel in it, forward + backward
    per GravesLSTM layer."""
    from deeplearning4j_tpu.models import TextGenerationLSTM
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork

    monkeypatch.delenv("DL4J_TPU_FUSED_LSTM", raising=False)
    model = MultiLayerNetwork(TextGenerationLSTM()).init()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, T, V = 128, 50, 77
    hlo = _step_hlo(model, _sds((B, T, V), jnp.float32),
                    _sds((B, T, V), jnp.float32), with_carries=True)
    assert _n_mosaic(hlo) == 4      # (fwd + bwd) x 2 layers


def test_transformer_lm_step_takes_the_kernel(monkeypatch):
    """The flagship at full width (depth cut to one block): the flash gate
    takes the Pallas forward and both Pallas backward kernels."""
    from deeplearning4j_tpu.models import TransformerLM
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork

    model = MultiLayerNetwork(TransformerLM(
        vocab_size=2048, max_len=2048, d_model=2048, n_heads=16,
        n_blocks=1)).init()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, T = 16, 2048
    hlo = _step_hlo(model, _sds((B, T), jnp.int32), _sds((B, T), jnp.int32),
                    with_carries=False)
    assert _n_mosaic(hlo) == 3      # 3 per block


def test_hybrid_lm_step_compiles_at_published_widths(monkeypatch):
    """One layer of each kind of the hybrid stack (``M``, ``E``, ``*``) at
    the benchmark configuration's widths, T = 4096, per-layer recomputation
    on: the train step compiles for v5e; the attention layer takes the three
    flash kernels (each once: the recomputed layer keeps the forward kernel's
    result and does not run it again), the expert layer the
    grouped-matmul kernels (two products forward, two recomputed, two ``dx``
    and two ``dw``, in each of its four buffer sizes), and they are the
    step's only custom calls. None of the ``moe_gmm_*`` calls has a result
    by which the benchmark's accepted patterns find a flash kernel in a
    trace: the only lone rank-3 result is the flash dq kernel's."""
    import json
    import os
    import re

    from deeplearning4j_tpu.models import HybridLM
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork

    conf = HybridLM(
        "ME*", vocab_size=2048, d_model=2688, max_len=4096,
        mamba=dict(n_heads=64, head_dim=64, n_groups=8, state_size=128,
                   conv_kernel=4, chunk=128),
        attention=dict(n_heads=32, n_kv_heads=2, head_dim=128),
        moe=dict(n_experts=128, top_k=6, expert_width=1856, shared_width=3712,
                 n_held=8, routed_scaling=2.5),
        remat=True)
    model = MultiLayerNetwork(conf)
    model.params = jax.eval_shape(lambda: tuple(
        l.init(jax.random.PRNGKey(0), it, model.dtype)
        for l, it in zip(model.layers, model.layer_input_types)))
    model.state = tuple(l.init_state(it) for l, it in
                        zip(model.layers, model.layer_input_types))
    model._build_updaters()
    model.opt_state = jax.eval_shape(lambda p: tuple(
        u.init(pi) for u, pi in zip(model._updaters, p)), model.params)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo = _step_hlo(model, _sds((1, 4096), jnp.int32), _sds((1, 4096), jnp.int32),
                    with_carries=False)
    calls = [l.split(" custom-call(")[0] for l in hlo.splitlines()
             if " custom-call(" in l and "tpu_custom_call" in l]
    flash = [c for c in calls if "flash_" in c]
    gmm = {k: [c for c in calls if f"moe_gmm_{k}_" in c]
           for k in ("fwd", "dx", "dw")}
    (moe,) = [l.mixer for l in model.layers
              if type(getattr(l, "mixer", None)).__name__ == "SparseMoE"]
    sizes = len(moe.row_caps(4096))
    assert len(flash) == 3 and sizes == 4
    assert [len(gmm[k]) for k in ("fwd", "dx", "dw")] == [
        4 * sizes, 2 * sizes, 2 * sizes]
    assert len(calls) == 3 + 8 * sizes
    lone_rank3 = [c for c in calls if re.search(
        r"= \w+\[\d+,\d+,\d+\](\{[^{}]*\})?$", c.strip())]
    assert len(lone_rank3) == 1 and "flash_bwd_dq" in lone_rank3[0], lone_rank3
    # the kernels take an expert stack as XLA keeps it (the first one with
    # its 2688 side minor: ``stored_transposed``): the step copies no stack
    stacks = [l for l in hlo.splitlines() if re.search(
        r"= f32\[8,(2688,1856|1856,2688)\]\S* copy\(", l)]
    assert not stacks, stacks
    # a trace names a Mosaic call "<name> tpu_custom_call <result types>":
    # the accepted roofline patterns, read from their files, match none of
    # the grouped-matmul calls so named
    metrics = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "metrics")
    patterns = [pat for f in ("flash_fwd_roofline.json",
                              "flash_bwd_roofline.json")
                for pat in json.load(open(os.path.join(metrics, f)))["patterns"]]
    assert len(patterns) == 3
    for c in calls:
        name, types = c.strip().lstrip("%").split(" = ")
        event = (name.split(".")[0] + " tpu_custom_call "
                 + re.sub(r"\{[^{}]*\}", "", types))
        hit = [pat for pat in patterns if re.search(pat, event)]
        assert bool(hit) == ("flash_" in name), (event, hit)


def test_grouped_matmul_compiles_at_published_widths():
    """The three grouped-matmul kernels alone at the hybrid cell's first
    expert product, ``[3072, 2688] x [8, 2688, 1856]`` float32, and at the
    second's mirrored shape: forward, ``dx`` and ``dw`` each compile for v5e
    under their chosen blocks, ``dw`` comes out rank 2, and neither stack is
    copied on its way to a kernel or its gradient on the way back."""
    import re

    from deeplearning4j_tpu.ops.grouped_matmul import grouped_matmul

    for K, N in ((2688, 1856), (1856, 2688)):
        def both(x, w, counts):
            y, vjp = jax.vjp(lambda x, w: grouped_matmul(
                x, w, counts, impl="pallas"), x, w)
            return (y,) + vjp(y)

        hlo = _compile(both, _sds((3072, K), jnp.float32),
                       _sds((8, K, N), jnp.float32), _sds((8,), jnp.int32))
        assert _n_mosaic(hlo) == 3
        for kind in ("fwd", "dx", "dw"):
            assert f"moe_gmm_{kind}_m128" in hlo
        # dw of the stack as the kernels take it: [8 * 1856, 2688] for both
        assert "f32[14848,2688]" in hlo
        assert not re.search(r"= f32\[8,\d+,\d+\]\S* copy\(", hlo)


# ---------------------------------------------------------------------------
# latent-attention flash kernels (ops/flash_mla.py)
# ---------------------------------------------------------------------------


def _custom_calls(hlo: str) -> list:
    """``<instruction name> tpu_custom_call <result types>`` of every Mosaic
    call, as benchmark/harness/trace.py names a trace's events."""
    import re

    out = []
    for l in hlo.splitlines():
        if " custom-call(" in l and "tpu_custom_call" in l:
            name, types = l.split(" custom-call(")[0].strip().lstrip(
                "%").split(" = ")
            out.append(f"{name} tpu_custom_call "
                       + re.sub(r"\{[^{}]*\}", "", types))
    return out


def _roofline_patterns() -> dict:
    import json
    import os

    metrics = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "metrics")
    return {f[:-5]: json.load(open(os.path.join(metrics, f)))["patterns"]
            for f in sorted(os.listdir(metrics)) if f.endswith("_roofline.json")}


def test_mla_flash_compiles_at_published_widths_and_is_found_by_name():
    """Forward, dq and dk/dv at 32 heads of 128 | 64 score lanes and 128
    value lanes, T = 8192 float32 (the benchmark cell's call), compile for
    v5e under their chosen blocks and a raised VMEM limit; each call's event
    name is found by the new roofline file meant for it and by no other
    start-anchored pattern."""
    import re

    from deeplearning4j_tpu.ops.flash_mla import flash_mla

    H, T = 32, 8192

    def loss(qn, qr, kv, kr):
        return _f32sum(flash_mla(qn, qr, kv, kr, n_heads=H, scale=192 ** -0.5))

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2, 3)),
                   _sds((1, T, H * 128), jnp.float32),
                   _sds((1, T, H * 64), jnp.float32),
                   _sds((1, T, H * 256), jnp.float32),
                   _sds((1, T, 64), jnp.float32))
    calls = _custom_calls(hlo)
    assert sorted(c.split(".")[0] for c in calls) == [
        "mla_flash_bwd_dkv_h2_q512_k512", "mla_flash_bwd_dq_h2_q512_k512",
        "mla_flash_fwd_h2_q512_k512"], calls
    anchored = {k: [p for p in v if p.startswith("^")]
                for k, v in _roofline_patterns().items()}
    assert {k for k, v in anchored.items() if v} == {
        "mla_flash_fwd_roofline", "mla_flash_bwd_roofline"}
    for c in calls:
        hit = {k for k, v in anchored.items()
               if any(re.search(p, c) for p in v)}
        want = ("mla_flash_fwd_roofline" if c.startswith("mla_flash_fwd")
                else "mla_flash_bwd_roofline")
        assert hit == {want}, (c, hit)
    # no operand is padded or repeated on its way in: the step's only
    # arrays of the rotary key's or a query part's shape are the arguments
    # and the gradients
    assert "f32[1,8192,32,64]" not in hlo and "f32[1,8192,6144]" not in hlo


def test_a_recomputed_latent_attention_block_runs_each_kernel_once(monkeypatch):
    """One ``ResidualBlock`` around latent attention at the benchmark
    configuration's widths, T = 8192 float32, ``remat=True``, forward and
    backward for v5e: the recomputed layer keeps the forward kernel's result
    and its row statistic, so the program holds each of the three kernels
    once."""
    from deeplearning4j_tpu.nn.input_type import InputType
    from deeplearning4j_tpu.nn.layers import (
        MultiHeadLatentAttention, ResidualBlock)

    T, C = 8192, 2048
    block = ResidualBlock(remat=True, eps=1e-6, mixer=MultiHeadLatentAttention(
        n_heads=32, q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64,
        v_dim=128, rope_theta=32e6))
    params = jax.eval_shape(lambda: block.init(
        jax.random.PRNGKey(0), InputType.recurrent(C, T)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss(p, x):
        return _f32sum(block.apply(p, {}, x, train=True)[0])

    hlo = _compile(jax.grad(loss, argnums=(0, 1)), jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype), params), _sds((1, T, C), jnp.float32))
    assert sorted(c.split(".")[0].split(" ")[0] for c in _custom_calls(hlo)) == [
        "mla_flash_bwd_dkv_h2_q512_k512", "mla_flash_bwd_dq_h2_q512_k512",
        "mla_flash_fwd_h2_q512_k512"]


FLASH_NAMES = [
    pytest.param("pairs", (8, 1024, 16, 64), "_h2_q1024_k512", "_h2_q512_k1024",
                 id="D64-pairs"),
    pytest.param("heads", (1, 4096, 32, 128), "_h1_q512_k512", "_h1_q512_k512",
                 id="D128"),
    pytest.param("fused", (8, 1024, 16, 64), "_h2_q1024_k512", "_h2_q512_k1024",
                 id="fused-qkv"),
]


@pytest.mark.parametrize("kind,shape,on_q,on_k", FLASH_NAMES)
def test_existing_flash_calls_keep_their_kernel_names(kind, shape, on_q, on_k):
    """The calls the three accepted cells make (two heads of 64 a lane block,
    one head of 128, the fused qkv projection) lower to the kernels, heads a
    block and blocks they had before ops/flash_mla.py came, and no new
    pattern file finds them."""
    import re

    from deeplearning4j_tpu.ops.flash_attention import flash_attention_qkv

    B, T, H, D = shape
    if kind == "fused":
        hlo = _compile(jax.grad(lambda x: _f32sum(flash_attention_qkv(
            x, H, causal=True))), _sds((B, T, 3 * H * D), jnp.float32))
    else:
        hlo = _compile(jax.grad(lambda q, k, v: _f32sum(flash_attention(
            q, k, v, causal=True)), argnums=(0, 1, 2)),
            *[_sds(shape, jnp.float32)] * 3)
    calls = _custom_calls(hlo)
    assert sorted(c.split(".")[0] for c in calls) == sorted([
        "flash_fwd" + on_q, "flash_bwd_dq" + on_q, "flash_bwd_dkv" + on_k]), calls
    new = [p for k, v in _roofline_patterns().items() if k.startswith("mla_")
           for p in v]
    assert new and not [c for c in calls for p in new if re.search(p, c)]
