"""Operations and bytes the mathematics of a ``nemotron_h`` stack needs, from
shapes alone. 6 a matmul parameter a token outside the routed experts; the
held experts at the expected ``num_experts_per_tok * held / router_experts``
experts a token (what an even router sends here); the state-space
recurrence's own products (the state's update and its read-out, one
multiply-add each per state element, position by position: what the chunked
form spends inside a chunk beyond that is the algorithm's and counts
nothing); causal attention at half of the full square; nothing recomputed;
embedding look-ups count nothing. Kept with the benchmark so that no PR that
claims a gain can change the yardstick."""

from __future__ import annotations

# the bytes of an element and the roofline arithmetic are the GPT-2 module's
from benchmark.shapes.gpt2 import _ITEM, least_seconds  # noqa: F401


def _d(cfg) -> dict:
    H, P = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    G, N = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    return dict(
        V=int(cfg["vocab_size"]), d=int(cfg["hidden_size"]), H=H, P=P, G=G, N=N,
        Hq=int(cfg["num_attention_heads"]), Hkv=int(cfg["num_key_value_heads"]),
        Dh=int(cfg["head_dim"]), E=int(cfg["n_routed_experts"]),
        R=int(cfg.get("router_experts", cfg["n_routed_experts"])),
        k=int(cfg["num_experts_per_tok"]), F=int(cfg["moe_intermediate_size"]),
        Fs=int(cfg["moe_shared_expert_intermediate_size"]))


def layer_matmul_params(cfg) -> dict:
    """Parameters that multiply a token's activations, by layer letter; for
    ``E`` the expected number (the router, the shared expert, and the held
    experts' share of the ``k`` a token picks)."""
    D = _d(cfg)
    d, inner = D["d"], D["H"] * D["P"]
    return {
        "M": d * (2 * inner + 2 * D["G"] * D["N"] + D["H"]) + inner * d,
        "*": 2 * d * D["Hq"] * D["Dh"] + 2 * d * D["Hkv"] * D["Dh"],
        "E": d * D["R"] + 2 * d * D["Fs"]
        + (D["k"] * D["E"] / D["R"]) * 2 * d * D["F"],
    }


def train_flops_per_token(cfg, seq_len: int) -> float:
    D = _d(cfg)
    per = layer_matmul_params(cfg)
    pat = str(cfg["hybrid_override_pattern"])
    params = sum(per[c] for c in pat) + D["d"] * D["V"]
    # forward + backward = 3 x forward for the products that hold no parameter
    square = 3.0 * pat.count("*") * (4.0 * seq_len * D["Hq"] * D["Dh"]) / 2.0
    scan = 3.0 * pat.count("M") * 4.0 * D["H"] * D["P"] * D["N"]
    return 6.0 * params + square + scan


def window_flops_train(cfg, facts) -> float:
    return train_flops_per_token(cfg, facts["seq_len"]) * facts["tokens"]


def _attn_call(cfg, facts):
    D = _d(cfg)
    return (facts["batch"], facts["seq_len"], D["Hq"], D["Hkv"], D["Dh"],
            _ITEM[cfg["dtype"]])


def flash_fwd(cfg, facts) -> dict:
    """One causal attention forward over ``Hq`` query heads that share ``Hkv``
    key-value heads: QK^T and PV, 2 T^2 D each per query head for the full
    square, half of it causal; q read and o written at ``Hq`` heads, k and v
    read at ``Hkv`` (what the mathematics needs; a kernel that is handed
    them repeated reads more), the float32 log-sum-exp written."""
    B, T, Hq, Hkv, D, item = _attn_call(cfg, facts)
    return {"flops": 4.0 * B * Hq * T * T * D / 2.0,
            "bytes": 2.0 * B * T * (Hq + Hkv) * D * item + 4.0 * B * Hq * T}


def flash_bwd(cfg, facts) -> dict:
    """The backward of that call: dV, dP, dQ, dK are four matmuls of 2 T^2 D
    per query head (recomputed scores count nothing); q, o, do read and dq
    written at ``Hq`` heads, k, v read and dk, dv written at ``Hkv``,
    log-sum-exp and the row sums of do*o read."""
    B, T, Hq, Hkv, D, item = _attn_call(cfg, facts)
    return {"flops": 8.0 * B * Hq * T * T * D / 2.0,
            "bytes": 4.0 * B * T * (Hq + Hkv) * D * item + 8.0 * B * Hq * T}
