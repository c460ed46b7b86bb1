"""Operations and bytes the algorithm needs, from true lengths.

Every count takes the prompt or context length a request really has,
never the bucket a prompt was padded to or the cache length a slot was
allocated with: padding and unused cache are work a later change may
remove, and counting them would let the share of a peak pass 100%.
"""
from __future__ import annotations

BF16 = 2


def layer_params(m: dict) -> int:
    """Matrix parameters of one decoder layer (attention and SwiGLU MLP)."""
    d, h, kv, hd, f = m["d"], m["heads"], m["kv_heads"], m["head_dim"], m["d_ff"]
    return d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f


def causal_pairs(n: int) -> int:
    """(query, key) pairs of causal attention over ``n`` tokens."""
    return n * (n + 1) // 2


def flash_attention(m: dict, prompt: int) -> tuple:
    """-> (flops, bytes) of causal attention over one prompt, all layers."""
    h, kv, hd, layers = m["heads"], m["kv_heads"], m["head_dim"], m["layers"]
    flops = layers * 4 * h * hd * causal_pairs(prompt)
    byts = layers * BF16 * prompt * hd * (2 * h + 2 * kv)
    return flops, byts


def decode_attention(m: dict, contexts) -> tuple:
    """-> (flops, bytes) of one decode step's attention, all layers: each
    active slot reads its keys and values up to its own position."""
    h, kv, hd, layers = m["heads"], m["kv_heads"], m["head_dim"], m["layers"]
    flops = byts = 0
    for c in contexts:
        flops += layers * 4 * h * hd * c
        byts += layers * (BF16 * (2 * kv * hd * c + 2 * h * hd) + 4 * c)
    return flops, byts


def prefill_flops(m: dict, prompt: int) -> int:
    """Model FLOPs of one prefill: every token through every layer, causal
    attention, and the output head at the last position only."""
    return (2 * prompt * m["layers"] * layer_params(m)
            + flash_attention(m, prompt)[0] + 2 * m["vocab"] * m["d"])


def decode_flops(m: dict, contexts) -> int:
    """Model FLOPs of one decode step over the active slots."""
    per_token = 2 * m["layers"] * layer_params(m) + 2 * m["vocab"] * m["d"]
    return len(contexts) * per_token + decode_attention(m, contexts)[0]


def roofline_seconds(flops: float, byts: float, peak: dict) -> tuple:
    """-> (least seconds the chip could take, "compute" or "hbm")."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = byts / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "hbm")
