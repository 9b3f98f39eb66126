"""Operations and bytes the mathematics of a DeepSeek-V2/V3-family stack
(latent attention, gated experts, one MTP module) needs, from shapes alone.
6 a matmul parameter a token outside the routed experts (the MTP module's
merge matrix and block and the head's second use included); the held experts
at the expected ``num_experts_per_tok * held / router_experts`` experts a
token (what an even router sends here); causal attention at half of the full
square over the score width ``qk_nope + qk_rope`` and the value width;
nothing recomputed; embedding look-ups and the rotation count nothing. Kept
with the benchmark so that no PR that claims a gain can change the yardstick.

This module defines no ``flash_fwd`` and no ``flash_bwd``: the accepted
``flash_*_roofline`` patterns go by result shapes and would read these cells'
latent-attention calls against the wrong arithmetic; without the functions
their reader returns nothing here."""

from __future__ import annotations

# the bytes of an element and the roofline arithmetic are the GPT-2 module's
from benchmark.shapes.gpt2 import _ITEM, least_seconds  # noqa: F401


def _d(cfg) -> dict:
    return dict(
        V=int(cfg["vocab_size"]), d=int(cfg["hidden_size"]),
        L=int(cfg["num_hidden_layers"]),
        dense=int(cfg.get("first_k_dense_replace", 0)),
        H=int(cfg["num_attention_heads"]), Rq=int(cfg["q_lora_rank"]),
        Rkv=int(cfg["kv_lora_rank"]), Dn=int(cfg["qk_nope_head_dim"]),
        Dr=int(cfg["qk_rope_head_dim"]), Dv=int(cfg["v_head_dim"]),
        Fd=int(cfg["intermediate_size"]), F=int(cfg["moe_intermediate_size"]),
        Fs=int(cfg["moe_intermediate_size"]) * int(cfg.get("n_shared_experts", 0)),
        E=int(cfg["n_routed_experts"]),
        R=int(cfg.get("router_experts", cfg["n_routed_experts"])),
        k=int(cfg["num_experts_per_tok"]),
        mtp=int(cfg.get("num_nextn_predict_layers", 0)))


def layer_matmul_params(cfg) -> dict:
    """Parameters that multiply a token's activations, by layer kind; for
    ``expert`` the expected number (the router, the shared expert, and the
    held experts' share of the ``k`` a token picks)."""
    D = _d(cfg)
    d, H = D["d"], D["H"]
    attn = (d * D["Rq"] + D["Rq"] * H * (D["Dn"] + D["Dr"])
            + d * (D["Rkv"] + D["Dr"]) + D["Rkv"] * H * (D["Dn"] + D["Dv"])
            + H * D["Dv"] * d)
    return {"attention": attn, "dense": attn + 3 * d * D["Fd"],
            "expert": attn + d * D["R"] + 3 * d * D["Fs"]
            + (D["k"] * D["E"] / D["R"]) * 3 * d * D["F"]}


def train_flops_per_token(cfg, seq_len: int) -> float:
    D = _d(cfg)
    per = layer_matmul_params(cfg)
    params = (D["dense"] * per["dense"] + (D["L"] - D["dense"]) * per["expert"]
              + D["d"] * D["V"])
    if D["mtp"]:            # the merge, one expert-kind block, the head again
        params += 2 * D["d"] * D["d"] + per["expert"] + D["d"] * D["V"]
    # forward + backward = 3 x forward for the products that hold no parameter
    square = 3.0 * (D["L"] + D["mtp"]) * (
        2.0 * seq_len * D["H"] * (D["Dn"] + D["Dr"] + D["Dv"])) / 2.0
    return 6.0 * params + square


def window_flops_train(cfg, facts) -> float:
    return train_flops_per_token(cfg, facts["seq_len"]) * facts["tokens"]


def _attn_call(cfg, facts):
    D = _d(cfg)
    return (facts["batch"], facts["seq_len"], D["H"], D["Dn"], D["Dr"], D["Dv"],
            _ITEM[cfg["dtype"]])


def mla_flash_fwd(cfg, facts) -> dict:
    """One causal latent-attention forward: scores over ``Dn + Dr``, values
    over ``Dv``, 2 T^2 a width a head for the full square, half of it causal.
    Bytes: q (both parts), the per-head keys and values and the output at H
    heads, the rotary key once a token, the float32 log-sum-exp written."""
    B, T, H, Dn, Dr, Dv, item = _attn_call(cfg, facts)
    return {"flops": 2.0 * B * H * T * T * (Dn + Dr + Dv) / 2.0,
            "bytes": item * B * T * (H * (Dn + Dr) + H * (Dn + Dv) + Dr + H * Dv)
            + 4.0 * B * H * T}


def mla_flash_bwd(cfg, facts) -> dict:
    """The backward of that call: dV and dP over ``Dv``, dQ and dK over
    ``Dn + Dr``, twice the forward (recomputed scores count nothing); q, k,
    v, the rotary key, o and do read, every gradient written once (the rotary
    key's once a token), log-sum-exp and the row sums of do*o read."""
    B, T, H, Dn, Dr, Dv, item = _attn_call(cfg, facts)
    return {"flops": 4.0 * B * H * T * T * (Dn + Dr + Dv) / 2.0,
            "bytes": item * B * T * 2 * (H * (Dn + Dr) + H * (Dn + Dv) + Dr + H * Dv)
            + 8.0 * B * H * T}
