"""Operations and bytes the mathematics of an ``ouro`` stack (a looped
language model: the layers run ``total_ut_steps`` times with the same
weights, a head and an exit gate after every loop) needs, from shapes
alone. 6 a matmul parameter a token for every *application*: each layer's
attention and MLP products ``total_ut_steps`` times, the untied head once a
loop, the exit gate once a loop whose gate the loss reads (all but the
last); causal attention at half of the full square, once a layer a loop;
the embedding look-up, norms and rotation count nothing; nothing recomputed.
Kept with the benchmark so that no PR that claims a gain can change the
yardstick."""

from __future__ import annotations

# the bytes of an element and the roofline arithmetic are the GPT-2 module's
from benchmark.shapes.gpt2 import _ITEM, least_seconds  # noqa: F401


def _d(cfg) -> dict:
    d, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return dict(V=int(cfg["vocab_size"]), d=d, H=H,
                Hkv=int(cfg["num_key_value_heads"]),
                Dh=int(cfg.get("head_dim") or d // H),
                F=int(cfg["intermediate_size"]),
                L=int(cfg["num_hidden_layers"]), T=int(cfg["total_ut_steps"]))


def layer_matmul_params(cfg) -> int:
    """Parameters of one layer that multiply a token's activations: q, k, v
    and o; the MLP's gate, up and down."""
    D = _d(cfg)
    return (2 * D["d"] * D["H"] * D["Dh"] + 2 * D["d"] * D["Hkv"] * D["Dh"]
            + 3 * D["d"] * D["F"])


def train_flops_per_token(cfg, seq_len: int, gate: bool = True) -> float:
    """Forward + backward of every loop: ``T x L`` layer applications, ``T``
    heads and ``T - 1`` gates that the loss reads (``gate=False`` leaves the
    gates out), each product 6 a parameter, the attention square 3 times its
    forward."""
    D = _d(cfg)
    T, L = D["T"], D["L"]
    params = (T * L * layer_matmul_params(cfg) + T * D["d"] * D["V"]
              + (T - 1) * D["d"] * gate)
    square = 3.0 * T * L * (4.0 * seq_len * D["H"] * D["Dh"]) / 2.0
    return 6.0 * params + square


def window_flops_train(cfg, facts) -> float:
    return train_flops_per_token(cfg, facts["seq_len"]) * facts["tokens"]


def _attn_call(cfg, facts):
    D = _d(cfg)
    return (facts["batch"], facts["seq_len"], D["H"], D["Hkv"], D["Dh"],
            _ITEM[cfg["dtype"]])


def flash_fwd(cfg, facts) -> dict:
    """One causal attention forward (a layer in a loop: ``T x L`` calls a
    step), as benchmark/shapes/lfm2_moe.py counts it: QK^T and PV, 2 T^2 D
    each per query head for the full square, half of it causal; q and o at
    ``H`` heads, k and v at ``Hkv``, the float32 log-sum-exp written."""
    B, T, H, Hkv, D, item = _attn_call(cfg, facts)
    return {"flops": 4.0 * B * H * T * T * D / 2.0,
            "bytes": 2.0 * B * T * (H + Hkv) * D * item + 4.0 * B * H * T}


def flash_bwd(cfg, facts) -> dict:
    """The backward of that call: four matmuls of 2 T^2 D per query head;
    q, o, do read and dq written at ``H`` heads, k, v read and dk, dv
    written at ``Hkv``, log-sum-exp and the row sums of do*o read."""
    B, T, H, Hkv, D, item = _attn_call(cfg, facts)
    return {"flops": 8.0 * B * H * T * T * D / 2.0,
            "bytes": 4.0 * B * T * (H + Hkv) * D * item + 8.0 * B * H * T}
