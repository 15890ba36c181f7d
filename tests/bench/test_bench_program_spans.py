"""The readers of the program's own counters and spans, on a synthetic
trace and synthetic span records whose clock offset is known."""

from __future__ import annotations

import sys
from types import SimpleNamespace

import pytest
import tinybench

from bench import arch
from bench.lib import program_spans, spec
from bench.lib.trace import Reduced
from repro.serve.tracing import SpanRecord

MS = 1_000_000  # ns
OFF = 7_000 * MS  # trace clock = perf_counter clock + OFF
NEW = ("lease_wait_ms.chat", "first_token_hold_ms.chat", "step_host_ms.chat",
       "readback_idle_ms.chat")


def _reader(name):
    return spec.metric_reader(name, root=tinybench.ROOT)


def _run(stats=None, traced=True, outlier=True, pairs=4):
    """Three decode programs at 0-5, 10-15 and 20-25 ms with a small
    program at 6-7; bench spans whose records sit OFF before them, one
    of them (the outlier) 50 ms further off."""
    ops = [
        ("decode_attention", 1 * MS, 4 * MS),
        ("argmax", 6 * MS, 7 * MS),
        ("decode_attention", 11 * MS, 14 * MS),
        ("decode_attention", 21 * MS, 24 * MS),
    ]
    modules = [("jit_decode", 0, 5 * MS), ("jit_argmax", 6 * MS, 7 * MS),
               ("jit_decode", 10 * MS, 15 * MS), ("jit_decode", 20 * MS, 25 * MS)]
    host, info, records = [], [], []
    for k in range(pairs):
        s, e = (k * 6 + 1) * MS, (k * 6 + 5) * MS
        obj = {"steps": 1, "ctx": [k]}
        host.append(("step_chunk", s, e))
        info.append(obj)
        shift = 50 * MS if (outlier and k == 1) else 0
        records.append(("step_chunk", s - OFF - shift, e - OFF - shift, obj, k))
    host.append(("lease_requests", 25 * MS, 26 * MS))
    info.append(None)  # a span with no record joins nothing
    red = Reduced(window=(0, 30 * MS), ops=ops, modules=modules, host=host,
                  kernels=arch.binding("qwen3").KERNELS, host_info=info)
    return SimpleNamespace(
        trace=red if traced else None,
        spans=SimpleNamespace(records=records),
        stats={} if stats is None else stats,
        cell=tinybench.cell(),
    )


def _readbacks():
    """Read-back spans at 4-9 and 14-16 ms of the trace, on perf_counter."""
    return [
        SpanRecord("serve.readback", 4 * MS - OFF, 9 * MS - OFF, 1, None, "e", None, {}),
        SpanRecord("serve.decode", 3 * MS - OFF, 9 * MS - OFF, 2, None, "e", None, {}),
        SpanRecord("serve.readback", 14 * MS - OFF, 16 * MS - OFF, 3, None, "e", None, {}),
    ]


def test_clock_offset_survives_one_outlier_pair():
    assert program_spans.clock_offset_ns(_run()) == OFF
    assert program_spans.clock_offset_ns(_run(outlier=False)) == OFF
    assert program_spans.clock_offset_ns(_run(pairs=2, outlier=False)) is None
    assert program_spans.clock_offset_ns(_run(traced=False)) is None


def test_readback_idle_reads_known_value_within_host_gap(monkeypatch):
    run = _run()
    # pair 1: idle 5-6 and 7-10, read-back 4-9 -> 1 + 2 ms; pair 2: idle
    # 15-20, read-back 14-16 -> 1 ms
    assert program_spans.readback_idle_ns(run.trace, [(4 * MS, 9 * MS), (14 * MS, 16 * MS)]) \
        == [3 * MS, 1 * MS]
    monkeypatch.setattr(program_spans, "serve_records", _readbacks)
    got = _reader("readback_idle_ms.chat")(run)
    assert got == pytest.approx(2.0)
    gap = _reader("host_gap_ms.chat")(run)
    assert gap == pytest.approx(4.5) and got <= gap


def test_counter_readers_are_means_in_ms():
    run = _run(stats={
        "leased": 4, "lease_wait_ns": 800 * MS,
        "first_tokens_streamed": 2, "first_token_hold_ns": 700 * MS,
        "decode_steps": 10, "decode_host_ns": 15 * MS,
    })
    assert _reader("lease_wait_ms.chat")(run) == pytest.approx(200.0)
    assert _reader("first_token_hold_ms.chat")(run) == pytest.approx(350.0)
    assert _reader("step_host_ms.chat")(run) == pytest.approx(1.5)


@pytest.mark.parametrize("name", NEW)
def test_reader_is_none_without_the_counters_or_the_trace(name, monkeypatch):
    read = _reader(name)
    assert read(_run(traced=False)) is None
    assert read(_run(stats={"leased": 0, "lease_wait_ns": 0, "decode_steps": 5})) is None
    # a program without the tracer (as before it had one)
    monkeypatch.setitem(sys.modules, "repro.serve.tracing", None)
    assert program_spans.serve_records() is None
    assert read(_run()) is None
