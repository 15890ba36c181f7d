"""Operations and bytes of Qwen3 from shapes: per model token and per
kernel call.

Counts are of the work the algorithm needs, not of what the program
happens to do: attention over the live (causal) positions only, one
output-head row per generated token and one per prompt (its last
position), bf16 operands of 2 bytes.  So a share of a peak computed from
them is a lower bound on the least time, and a program that does extra
work (padding, the all-position head, a masked full-cache rewrite) shows
as a smaller share.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable

from bench.lib.cost import BYTES

from .weights import dims


def matmul_params(config: Dict[str, Any]) -> Dict[str, int]:
    d = dims(config)
    D, H, K, hd, F, V, L = (d[k] for k in ("D", "H", "K", "hd", "F", "V", "L"))
    per_layer = D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F
    return {"layers": L * per_layer, "head": D * V}


def attn_flops(config: Dict[str, Any], ctx: int) -> float:
    """QK^T and PV of one query against `ctx` positions, all layers."""
    d = dims(config)
    return 4.0 * d["H"] * d["hd"] * ctx * d["L"]


def decode_token_flops(config: Dict[str, Any], ctx: int) -> float:
    """One generated token whose attention reads `ctx` positions."""
    p = matmul_params(config)
    return 2.0 * (p["layers"] + p["head"]) + attn_flops(config, ctx)


def prefill_flops(config: Dict[str, Any], n: int) -> float:
    """A prompt of `n` tokens: every layer at every position, causal
    attention, and the head at the last position only."""
    p = matmul_params(config)
    d = dims(config)
    attn = 4.0 * d["H"] * d["hd"] * d["L"] * n * (n + 1) / 2
    return 2.0 * p["layers"] * n + 2.0 * p["head"] + attn


def decode_attention_cost(config: Dict[str, Any], ctxs: Iterable[int]) -> Dict[str, float]:
    """decode_attention over one step's live slots, summed over layers:
    each slot reads its `ctx` cached keys and values once."""
    d = dims(config)
    H, K, hd, L = d["H"], d["K"], d["hd"], d["L"]
    flops = bytes_ = 0.0
    for c in ctxs:
        flops += 4.0 * H * hd * c
        bytes_ += (2 * K * hd * c + 2 * H * hd) * BYTES
    return {"flops": flops * L, "bytes": bytes_ * L}


def flash_attention_cost(config: Dict[str, Any], n: int) -> Dict[str, float]:
    """Causal flash_attention over one prompt of true length `n`, all
    layers: q, k, v read once, the output written once."""
    d = dims(config)
    H, K, hd, L = d["H"], d["K"], d["hd"], d["L"]
    flops = 4.0 * H * hd * n * (n + 1) / 2
    bytes_ = (2 * H * hd + 2 * K * hd) * n * BYTES
    return {"flops": flops * L, "bytes": bytes_ * L}

