"""Operations and bytes the mathematics of an ``lfm2_moe`` stack (gated short
convolutions, grouped-query attention with normed and rotated q and k, gated
experts, a head tied to the embedding) needs, from shapes alone. 6 a matmul
parameter a token outside the routed experts, the tied matrix counted once
(as the head's product: the look-up multiplies nothing); the held experts at
the expected ``num_experts_per_tok * held / router_experts`` experts a token
(what an even router sends here); causal attention at half of the full
square; the convolution's taps (``2 * conv_L_cache * d`` a token forward) and
its two gates (``2 * d``); nothing recomputed; norms and the rotation count
nothing. Kept with the benchmark so that no PR that claims a gain can change
the yardstick."""

from __future__ import annotations

# the bytes of an element and the roofline arithmetic are the GPT-2 module's
from benchmark.shapes.gpt2 import _ITEM, least_seconds  # noqa: F401


def _d(cfg) -> dict:
    d, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return dict(
        V=int(cfg["vocab_size"]), d=d, H=H,
        Hkv=int(cfg["num_key_value_heads"]),
        Dh=int(cfg.get("head_dim") or d // H), k=int(cfg["conv_L_cache"]),
        dense=int(cfg["num_dense_layers"]),
        Fd=int(cfg["intermediate_size"]), F=int(cfg["moe_intermediate_size"]),
        E=int(cfg["num_experts"]),
        R=int(cfg.get("router_experts", cfg["num_experts"])),
        topk=int(cfg["num_experts_per_tok"]))


def matmul_params(cfg) -> dict:
    """Parameters that multiply a token's activations, by half-layer kind;
    for ``expert`` the expected number (the router, and the held experts'
    share of the ``topk`` a token picks)."""
    D = _d(cfg)
    d = D["d"]
    return {
        "conv": 3 * d * d + d * d,
        "full_attention": 2 * d * D["H"] * D["Dh"] + 2 * d * D["Hkv"] * D["Dh"],
        "dense": 3 * d * D["Fd"],
        "expert": d * D["R"] + (D["topk"] * D["E"] / D["R"]) * 3 * d * D["F"],
    }


def train_flops_per_token(cfg, seq_len: int) -> float:
    D = _d(cfg)
    per = matmul_params(cfg)
    ops = list(cfg["layer_types"])
    params = (sum(per[op] for op in ops) + D["dense"] * per["dense"]
              + (len(ops) - D["dense"]) * per["expert"] + D["d"] * D["V"])
    # forward + backward = 3 x forward for the products that hold no parameter
    square = 3.0 * ops.count("full_attention") * (
        4.0 * seq_len * D["H"] * D["Dh"]) / 2.0
    taps = 3.0 * ops.count("conv") * (2.0 * D["k"] + 2.0) * D["d"]
    return 6.0 * params + square + taps


def window_flops_train(cfg, facts) -> float:
    return train_flops_per_token(cfg, facts["seq_len"]) * facts["tokens"]


def _attn_call(cfg, facts):
    D = _d(cfg)
    return (facts["batch"], facts["seq_len"], D["H"], D["Hkv"], D["Dh"],
            _ITEM[cfg["dtype"]])


def flash_fwd(cfg, facts) -> dict:
    """One causal attention forward over ``H`` query heads that share ``Hkv``
    key-value heads, as benchmark/shapes/nemotron_h.py counts it: QK^T and
    PV, 2 T^2 D each per query head for the full square, half of it causal;
    q read and o written at ``H`` heads, k and v read at ``Hkv`` (what the
    mathematics needs; a kernel that is handed them repeated reads more),
    the float32 log-sum-exp written."""
    B, T, H, Hkv, D, item = _attn_call(cfg, facts)
    return {"flops": 4.0 * B * H * T * T * D / 2.0,
            "bytes": 2.0 * B * T * (H + Hkv) * D * item + 4.0 * B * H * T}


def flash_bwd(cfg, facts) -> dict:
    """The backward of that call: dV, dP, dQ, dK are four matmuls of 2 T^2 D
    per query head (recomputed scores count nothing); q, o, do read and dq
    written at ``H`` heads, k, v read and dk, dv written at ``Hkv``,
    log-sum-exp and the row sums of do*o read."""
    B, T, H, Hkv, D, item = _attn_call(cfg, facts)
    return {"flops": 8.0 * B * H * T * T * D / 2.0,
            "bytes": 4.0 * B * T * (H + Hkv) * D * item + 8.0 * B * H * T}
