"""From a profiler trace to numbers: busy and idle time, programs, kernels.

`load` reads the `.xplane.pb` that `jax.profiler` wrote: device operations
(the TPU plane's "XLA Ops" line), program executions ("XLA Modules") and
the benchmark's own host spans (`bench.<name>` annotations), all on the
trace's one clock.  `Reduced` does the arithmetic on those intervals, so
a test can feed it a synthetic trace:

  busy        union of device-op intervals inside the window
  programs    each program execution is a decode step, a prefill or
              "other" by the name the engine gave its program
              (`program_kind`); the device time of each kernel that the
              cell's architecture binding names (its `KERNELS`) and that
              ran inside it is summed per kernel
  gaps        device-idle intervals, split over the host spans that
              overlap them (the rest is "outside_spans")
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[str, int, int]  # (name, start_ns, end_ns)

# the engine's jitted functions are named so that a profile tells them
# apart (`serve/continuous.py`); every other program is "other"
PROGRAM_KINDS = {"jit_decode": "decode", "jit_prefill": "prefill"}


def op_name(event_name: str) -> str:
    """The HLO instruction name of an op event; TPU traces name each op by
    its HLO text, `%decode_attention.3 = bf16[...] custom-call(...)`."""
    m = re.match(r"%?([\w.\-]+)\s*=", event_name)
    return m.group(1) if m else event_name


def kernel_of(name: str, kernels: Sequence[str]) -> Optional[str]:
    """The kernel of `kernels` that an op belongs to, by its instruction
    name (`decode_attention`, `decode_attention.3`, ...)."""
    for k in kernels:
        if re.fullmatch(rf"{re.escape(k)}(\.\d+)?", name):
            return k
    return None


def program_kind(module_name: str) -> str:
    """decode, prefill or other, by the name of an "XLA Modules" event:
    the jitted function's name, with the suffixes a trace may add
    (`jit_decode(1234)`, `jit_decode.1`)."""
    m = re.fullmatch(r"(\w+)(?:\(\d+\)|\.\d+)*", module_name)
    return PROGRAM_KINDS.get(m.group(1), "other") if m else "other"


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(ivs: List[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]


@dataclass
class Program:
    name: str  # the "XLA Modules" event's name
    kind: str  # decode | prefill | other
    start: int
    end: int
    kernels: Dict[str, int] = field(default_factory=dict)  # kernel -> summed ns


@dataclass
class Reduced:
    window: Tuple[int, int]
    ops: List[Interval]
    modules: List[Interval]
    host: List[Interval]  # (span name, start, end), host spans in the window
    kernels: Sequence[str]  # the kernels summed per program (a binding's KERNELS)
    host_info: List[Any] = field(default_factory=list)  # per host span, its record's info

    def __post_init__(self) -> None:
        lo, hi = self.window
        self.busy = union(clip([(s, e) for _, s, e in self.ops], lo, hi))
        self.programs = self._programs()

    # ---- device ----------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return sum(e - s for s, e in self.busy) * 1e-9

    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0 or not self.ops:
            return None
        return 1.0 - self.busy_s / self.window_s

    def idle_gaps(self) -> List[Tuple[int, int]]:
        lo, hi = self.window
        gaps, t = [], lo
        for s, e in self.busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        return gaps

    def _programs(self) -> List[Program]:
        lo, hi = self.window
        mods = sorted((s, e, n) for n, s, e in self.modules if s >= lo and e <= hi)
        kern = sorted((s, e, k) for n, s, e in self.ops if (k := kernel_of(n, self.kernels)))
        progs, j = [], 0
        for s, e, name in mods:
            while j < len(kern) and kern[j][0] < s:
                j += 1
            sums: Dict[str, int] = {}
            i = j
            while i < len(kern) and kern[i][0] < e:
                ks, ke, k = kern[i]
                sums[k] = sums.get(k, 0) + (ke - ks)
                i += 1
            progs.append(Program(name, program_kind(name), s, e, sums))
        return progs

    def of_kind(self, kind: str) -> List[Program]:
        return [p for p in self.programs if p.kind == kind]

    def decode_gaps_ns(self) -> List[int]:
        """Device-idle time between consecutive decode programs."""
        dec = self.of_kind("decode")
        out = []
        for a, b in zip(dec, dec[1:]):
            busy = sum(e - s for s, e in clip(self.busy, a.end, b.start))
            out.append((b.start - a.end) - busy)
        return out

    # ---- what the ledger keeps ---------------------------------------------
    def breakdown(self) -> Dict[str, List[List[Any]]]:
        by_op: Dict[str, int] = {}
        lo, hi = self.window
        progs = [(p.start, p.end, p.kind) for p in self.programs]
        for n, s, e in self.ops:
            if e > lo and s < hi:
                kind = next((k for ps, pe, k in progs if ps <= s < pe), "other")
                name = kind + ":" + re.sub(r"[.]\d+$", "", n)
                by_op[name] = by_op.get(name, 0) + (min(e, hi) - max(s, lo))
        by_host: Dict[str, int] = {}
        for s, e in self.idle_gaps():
            covered = 0
            for name, hs, he in self.host:
                ov = min(e, he) - max(s, hs)
                if ov > 0:
                    by_host[name] = by_host.get(name, 0) + ov
                    covered += ov
            if e - s > covered:
                by_host["outside_spans"] = by_host.get("outside_spans", 0) + (e - s - covered)
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
        idle = sorted(by_host.items(), key=lambda kv: -kv[1])[:10]
        return {
            "device_ops": [[n, ns * 1e-9] for n, ns in top],
            "idle_gaps": [[n, ns * 1e-9] for n, ns in idle],
        }


# ---------------------------------------------------------------------------
# reading the profiler's file
# ---------------------------------------------------------------------------

def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:TPU:") and "Core" not in plane_name.split(":")[-1]


def load(path: str, *, host_prefix: str = "bench.") -> Dict[str, Any]:
    """Intervals of the first TPU's ops and modules and of the host spans
    named `bench.*`, from one `.xplane.pb`."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: List[Interval] = []
    modules: List[Interval] = []
    host: List[Tuple[str, int, int, Dict[str, Any]]] = []
    device_seen = None
    for plane in pd.planes:
        if _is_device(plane.name):
            if device_seen is not None and plane.name != device_seen:
                continue
            device_seen = plane.name
            for line in plane.lines:
                target = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if target is None:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    target.append((op_name(ev.name), s, s + int(ev.duration_ns)))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(host_prefix):
                        s = int(ev.start_ns)
                        stats = {}
                        try:
                            stats = {k: v for k, v in ev.stats}
                        except (TypeError, ValueError):
                            stats = {}
                        host.append((ev.name[len(host_prefix):], s, s + int(ev.duration_ns), stats))
    return {"ops": ops, "modules": modules, "host": host, "device": device_seen}


def reduce_dir(logdir: str, spans, kernels: Sequence[str]) -> Optional[Reduced]:
    """The trace under `logdir` reduced over the traced window, with the
    device time of each of `kernels` summed per program.  Host span
    events carry the index of their record in `spans` (`idx`), which joins
    each to what the benchmark recorded of it (tokens, lengths)."""
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        return None
    raw = load(files[0])
    if not raw["ops"]:
        return None
    host = sorted(raw["host"], key=lambda h: h[1])
    lo = min(s for _, s, _ in raw["ops"])
    hi = max(e for _, _, e in raw["ops"])
    if host:
        lo, hi = min(lo, host[0][1]), max(hi, max(e for _, _, e, _ in host))
    by_idx = {r[4]: r for r in spans.records if len(r) > 4}
    info = []
    for name, s, e, stats in host:
        rec = by_idx.get(int(stats.get("idx", -1))) if "idx" in stats else None
        info.append(rec[3] if rec else None)
    return Reduced(
        window=(lo, hi),
        ops=raw["ops"],
        modules=raw["modules"],
        host=[(n, s, e) for n, s, e, _ in host],
        kernels=kernels,
        host_info=info,
    )
