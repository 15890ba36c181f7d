"""What the program measures of itself, read beside the benchmark's trace.

The engine keeps counters in its `stats` (returned by `run`, so in
`Run.stats`) and, while a profiler session is on, records `serve.*` spans
in `repro.serve.tracing.tracer` on `time.perf_counter_ns`.  Those records
are laid on the trace's clock by the benchmark's own host spans: each
`bench.admit` and `bench.step_chunk` event of the trace carries the very
`info` object of its `Spans` record, so the pair gives (trace start -
perf_counter start) for one call; the median over the pairs is the offset.

A program without the counters or the tracer, or a run that was not
traced, reads None: nothing here raises for want of them.
"""

from __future__ import annotations

import importlib
import statistics
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .trace import Reduced, clip, union

MIN_PAIRS = 3  # fewer joined calls than this give no offset


def mean_ms(stats: Dict[str, Any], total_ns: str, count: str) -> Optional[float]:
    """stats[total_ns] / stats[count] in ms; None where either is absent or 0."""
    total, n = stats.get(total_ns), stats.get(count)
    if not total or not n:
        return None
    return total / n * 1e-6


def serve_records() -> Optional[Sequence[Any]]:
    """The program's span records, or None if it has no tracer."""
    try:
        tracing = importlib.import_module("repro.serve.tracing")
    except ImportError:
        return None
    return tracing.tracer.records()


def clock_offset_ns(run) -> Optional[float]:
    """Median of (trace start - perf_counter start) over the benchmark's
    spans found in the trace; None with fewer than MIN_PAIRS."""
    red = run.trace
    if red is None:
        return None
    starts = {id(rec[3]): rec[1] for rec in run.spans.records if rec[3] is not None}
    diffs = [
        s - starts[id(info)]
        for (_, s, _), info in zip(red.host, red.host_info)
        if info is not None and id(info) in starts
    ]
    return statistics.median(diffs) if len(diffs) >= MIN_PAIRS else None


def on_trace_clock(run, records, name: str) -> Optional[List[Tuple[int, int]]]:
    """Intervals of the program's spans called `name`, on the trace's clock."""
    off = clock_offset_ns(run)
    if off is None or records is None:
        return None
    return [
        (int(r.start_ns + off), int(r.end_ns + off)) for r in records if r.name == name
    ]


def readback_idle_ns(red: Reduced, readbacks: List[Tuple[int, int]]) -> List[int]:
    """For each pair of consecutive decode programs (as `decode_gaps_ns`),
    the device-idle time between them that lies inside a read-back span."""
    rb = union(readbacks)
    dec = red.of_kind("decode")
    out = []
    for a, b in zip(dec, dec[1:]):
        idle, t = [], a.end
        for s, e in clip(red.busy, a.end, b.start):
            if s > t:
                idle.append((t, s))
            t = max(t, e)
        if b.start > t:
            idle.append((t, b.start))
        out.append(sum(re - rs for s, e in idle for rs, re in clip(rb, s, e)))
    return out


def readback_idle_ms(run, records) -> Optional[float]:
    """Mean over decode-program pairs of `readback_idle_ns`, in ms."""
    if run.trace is None:
        return None
    spans = on_trace_clock(run, records, "serve.readback")
    if not spans:
        return None
    gaps = readback_idle_ns(run.trace, spans)
    return sum(gaps) / len(gaps) * 1e-6 if gaps else None
