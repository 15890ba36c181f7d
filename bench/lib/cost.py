"""The roofline, and the operations and bytes counted for it.

What a model token or a kernel call costs depends on the architecture, and
its binding counts it (`bench/arch/`, from shapes, of the work the
algorithm needs: a share of a peak computed from it is a lower bound on
the least time).  Here is what holds for every architecture.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable

from bench import arch

BYTES = 2  # bf16


def decode_attention_cost(config: Dict[str, Any], ctxs: Iterable[int]) -> Dict[str, float]:
    """decode_attention over one step's live slots (attention lengths
    `ctxs`), as the configuration's binding counts it."""
    return arch.for_config(config).decode_attention_cost(config, ctxs)


def least_time(cost: Dict[str, float], peaks: Dict[str, float]) -> float:
    """The roofline: the larger of operations over peak rate and bytes
    over peak bandwidth."""
    return max(cost["flops"] / peaks["bf16_flops_per_s"], cost["bytes"] / peaks["hbm_bytes_per_s"])
