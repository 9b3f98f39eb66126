"""Operations and bytes the mathematics of a GPT-2 needs, from shapes alone.
Causal attention is counted at half of the full square; nothing recomputed is
counted; embedding look-ups are not matmuls and count nothing. Kept with the
benchmark so that no PR that claims a gain can change the yardstick."""

from __future__ import annotations


_ITEM = {"bfloat16": 2, "float16": 2, "float32": 4}


def _dims(cfg):
    d = int(cfg["n_embd"])
    return (int(cfg["vocab_size"]), d, int(cfg["n_layer"]), int(cfg["n_head"]),
            int(cfg.get("n_inner") or 4 * d))


def matmul_params(cfg) -> int:
    """Parameters that multiply activations: per block qkv 3d^2, out d^2,
    MLP 2 d F; and the untied head d V."""
    V, d, L, _, F = _dims(cfg)
    return L * (4 * d * d + 2 * d * F) + d * V


def train_flops_per_token(cfg, seq_len: int) -> float:
    """Forward + backward: 6 per matmul parameter, and per block the two
    attention matmuls (QK^T, PV: 4 T d for the full square, half of it
    causal) three times over."""
    _, d, L, _, _ = _dims(cfg)
    return 6.0 * matmul_params(cfg) + 3.0 * L * (4.0 * seq_len * d) / 2.0


def window_flops_train(cfg, facts) -> float:
    return train_flops_per_token(cfg, facts["seq_len"]) * facts["tokens"]


def serve_token_flops(cfg, prompt_len: int, i: int) -> float:
    """What producing output token ``i`` of a request needs: for the first,
    the whole prompt's forward (head at its last position only); for a later
    one, one position's forward against ``prompt_len + i`` keys."""
    V, d, L, _, F = _dims(cfg)
    body = 2.0 * L * (4 * d * d + 2 * d * F)
    head = 2.0 * d * V
    if i == 0:
        n = prompt_len
        return body * n + L * 4.0 * d * n * (n + 1) / 2.0 + head
    return body + L * 4.0 * d * (prompt_len + i) + head


def window_flops_serve(cfg, facts) -> float:
    """Over the output tokens the clients received in the window, each as
    (prompt length, index in its request)."""
    return sum(serve_token_flops(cfg, p, i) for p, i in facts["token_events"])


def _attn_call(cfg, facts):
    _, d, _, H, _ = _dims(cfg)
    return facts["batch"], facts["seq_len"], H, d // H, _ITEM[cfg["dtype"]]


def flash_fwd(cfg, facts) -> dict:
    """One causal flash forward call over [B, T, H, D]: QK^T and PV, 2 T^2 D
    each per head for the full square, half of it causal; q, k, v read and o
    written once, the float32 log-sum-exp written."""
    B, T, H, D, item = _attn_call(cfg, facts)
    return {"flops": 4.0 * B * H * T * T * D / 2.0,
            "bytes": 4.0 * B * T * H * D * item + 4.0 * B * H * T}


def flash_bwd(cfg, facts) -> dict:
    """The backward of that call, both kernels together: dV, dP, dQ, dK are
    four matmuls of 2 T^2 D per head (the score matrix the kernels compute
    again is recomputation and counts nothing); q, k, v, o, do read, dq, dk,
    dv written, log-sum-exp and the row sums of do*o read."""
    B, T, H, D, item = _attn_call(cfg, facts)
    return {"flops": 8.0 * B * H * T * T * D / 2.0,
            "bytes": 8.0 * B * T * H * D * item + 8.0 * B * H * T}


def least_seconds(cost: dict, peaks: dict) -> tuple:
    """The least time the chip could take, and which bound binds."""
    tf = cost["flops"] / peaks["bf16_flops_per_s"]
    tb = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
