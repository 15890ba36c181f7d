"""Joins of a reduced trace with what the benchmark recorded of each call.

Each host span in the trace (`bench.admit`, `bench.step_chunk`) carries
the index of its record, whose `info` says what the call did: the true
length of every prompt an `admit` prefilled, in order, and the attention
length of every token a `step_chunk` decoded.  Device programs are laid
into the host span they started in.  A span whose programs do not match
its record (a call cut by the trace's edges) is left out, so every number
below is over whole calls inside the traced window.  Programs of one kind
are separated by far more than the slack (a decode step, a prefill), so
the slack cannot move a program into the wrong call.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from bench import arch

from .trace import Program, Reduced


# Device and host events of one trace are aligned to about a millisecond
# (a program can appear to start before the host call that issued it).
SLACK_NS = 3_000_000


def _inside(red: Reduced, name: str, kind: str) -> List[Tuple[List[Program], Any]]:
    progs = red.of_kind(kind)
    out = []
    for (n, s, e), info in zip(red.host, red.host_info):
        if n != name or info is None:
            continue
        out.append(([p for p in progs if s - SLACK_NS <= p.start < e], info))
    return out


def prefills(red: Reduced) -> List[Tuple[Program, int]]:
    """(prefill program, true prompt length) for every whole admit call."""
    pairs = []
    for progs, info in _inside(red, "admit", "prefill"):
        if len(progs) == len(info):
            pairs.extend((p, n) for p, (_, n) in zip(progs, info))
    return pairs


def decode_chunks(red: Reduced) -> List[Tuple[List[Program], List[int]]]:
    """(decode programs, attention lengths decoded) for every whole chunk."""
    return [
        (progs, info["ctx"])
        for progs, info in _inside(red, "step_chunk", "decode")
        if progs and len(progs) == info["steps"]
    ]


def model_flops(red: Reduced, config: Dict[str, Any]) -> float:
    """Model FLOPs of the whole calls inside the traced window: every
    prompt prefilled and every token decoded, as the configuration's
    binding counts them (`bench/arch/`)."""
    b = arch.for_config(config)
    lo, hi = red.window
    total = 0.0
    for (n, s, e), info in zip(red.host, red.host_info):
        if info is None or s < lo or e > hi:
            continue
        if n == "admit":
            total += sum(b.prefill_flops(config, k) for _, k in info)
        elif n == "step_chunk":
            total += sum(b.decode_token_flops(config, c) for c in info["ctx"])
    return total
