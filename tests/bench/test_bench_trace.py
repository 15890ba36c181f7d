"""The trace reduction, the peaks table and the operation counts, on a
small synthetic trace whose answers are known."""

from __future__ import annotations

import pytest
import tinybench

from bench import arch
from bench.lib import cost, readings, spec, trace
from bench.lib.trace import Reduced

MS = 1_000_000  # ns
KERNELS = arch.binding("qwen3").KERNELS


def _trace():
    """Two decode programs (each with two decode_attention kernels), one
    prefill (one flash_attention) and an idle gap under `lease_requests`."""
    ops = [
        ("fusion.1", 0, 2 * MS),
        ("decode_attention", 2 * MS, 3 * MS),
        ("decode_attention.7", 3 * MS, 4 * MS),
        ("fusion.2", 1 * MS, 5 * MS),  # overlaps: counted once in busy
        ("argmax", 6 * MS, 7 * MS),
        ("decode_attention", 10 * MS, 11 * MS),
        ("decode_attention.7", 11 * MS, 12 * MS),
        ("flash_attention", 20 * MS, 24 * MS),
        ("fusion.9", 24 * MS, 26 * MS),
    ]
    modules = [
        ("jit_decode", 0, 5 * MS),
        ("jit_argmax", 6 * MS, 7 * MS),
        ("jit_decode", 10 * MS, 12 * MS),
        ("jit_prefill", 20 * MS, 26 * MS),
    ]
    host = [
        ("step_chunk", 0, 13 * MS),
        ("lease_requests", 13 * MS, 19 * MS),
        ("admit", 19 * MS, 27 * MS),
    ]
    info = [
        {"steps": 2, "ctx": [100, 200, 101, 201]},
        None,
        [("r00001", 37)],
    ]
    return Reduced(window=(0, 30 * MS), ops=ops, modules=modules, host=host, kernels=KERNELS,
                   host_info=info)


def test_idle_share_is_one_minus_union_of_op_intervals():
    red = _trace()
    # busy: [0,5] + [6,7] + [10,12] + [20,26] = 14 ms of 30
    assert red.busy_s == pytest.approx(14e-3)
    assert red.window_s == pytest.approx(30e-3)
    assert red.idle_share() == pytest.approx(1 - 14 / 30)


def test_programs_split_by_the_engines_program_name():
    red = _trace()
    assert [p.kind for p in red.programs] == ["decode", "other", "decode", "prefill"]
    assert red.programs[0].kernels == {"decode_attention": 2 * MS}
    assert red.programs[3].kernels == {"flash_attention": 4 * MS}
    # idle between the two decode programs: 5 ms gap less 1 ms of argmax
    assert red.decode_gaps_ns() == [4 * MS]


@pytest.mark.parametrize("name, kind", [
    ("jit_decode", "decode"),
    ("jit_decode(8317062183547810941)", "decode"),
    ("jit_decode.3", "decode"),
    ("jit_prefill", "prefill"),
    ("jit_prefill(42)", "prefill"),
    ("jit_decode_foo", "other"),
    ("jit_insert", "other"),
    ("jit_new_cache(7)", "other"),
    ("jit__lambda", "other"),
    ("decode", "other"),
])
def test_program_kind_is_the_engines_program_name(name, kind):
    assert trace.program_kind(name) == kind


def test_a_decode_program_with_no_kernel_is_still_a_decode_step():
    """A decode step written in plain ops (no kernel the binding names)
    is read as decode by the step time, the gaps and the joins."""
    ops = [
        ("fusion.1", 0, 4 * MS),
        ("argmax", 6 * MS, 7 * MS),
        ("fusion.1", 10 * MS, 13 * MS),
    ]
    modules = [("jit_decode", 0, 5 * MS), ("jit_argmax", 6 * MS, 7 * MS),
               ("jit_decode", 10 * MS, 13 * MS)]
    host = [("step_chunk", 0, 14 * MS)]
    info = [{"steps": 2, "ctx": [100, 101]}]
    red = Reduced(window=(0, 20 * MS), ops=ops, modules=modules, host=host, kernels=KERNELS,
                  host_info=info)
    assert [p.kind for p in red.programs] == ["decode", "other", "decode"]
    assert all(p.kernels == {} for p in red.programs)
    run = type("Run", (), {"trace": red})()
    assert spec.metric_reader("decode_step_ms")(run) == pytest.approx(4.0)
    # 5 ms between the two decode programs, 1 ms of it busy with argmax
    assert spec.metric_reader("host_gap_ms.chat")(run) == pytest.approx(4.0)
    chunks = readings.decode_chunks(red)
    assert [(len(progs), ctx) for progs, ctx in chunks] == [(2, [100, 101])]


def test_idle_gaps_attributed_to_host_spans():
    red = _trace()
    b = dict(map(tuple, red.breakdown()["idle_gaps"]))
    # idle: [5,6] [7,10] [12,20] [26,30], split by overlap with host spans
    assert b["step_chunk"] == pytest.approx(5e-3)
    assert b["lease_requests"] == pytest.approx(6e-3)
    assert b["admit"] == pytest.approx(2e-3)
    assert b["outside_spans"] == pytest.approx(3e-3)
    assert sum(b.values()) == pytest.approx(16e-3)
    ops = dict(map(tuple, red.breakdown()["device_ops"]))
    assert ops["decode:decode_attention"] == pytest.approx(4e-3)  # `.7` suffixes merged
    assert ops["prefill:flash_attention"] == pytest.approx(4e-3)
    assert ops["other:argmax"] == pytest.approx(1e-3)


def test_joins_and_rooflines_stay_under_one():
    cfg = tinybench.config()
    red = _trace()
    chunks = readings.decode_chunks(red)
    assert len(chunks) == 1 and chunks[0][1] == [100, 200, 101, 201]
    pre = readings.prefills(red)
    assert [n for _, n in pre] == [37]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    c = cost.decode_attention_cost(cfg, [100, 200, 101, 201])
    # bytes bound: 2 layers x (K/V of 602 positions + q/out of 4 slots)
    assert c["bytes"] == 2 * (2 * 2 * 16 * 602 + 4 * 2 * 4 * 16) * 2
    least = cost.least_time(c, peaks)
    assert least == pytest.approx(c["bytes"] / 819e9)
    # a kernel that ran exactly at the roofline reads 100%, never more
    run = type("Run", (), {})()
    run.trace, run.peaks, run.cell = red, peaks, tinybench.cell()
    red.programs[0].kernels["decode_attention"] = int(least * 1e9 / 2)
    red.programs[2].kernels["decode_attention"] = int(least * 1e9 / 2) + 1
    share = spec.metric_reader("decode_attention_roofline")(run)
    assert 99.0 < share <= 100.0
    prog, n = pre[0]
    flash = cost.least_time(arch.for_config(cfg).flash_attention_cost(cfg, n), peaks)
    assert flash * 1e9 < prog.kernels["flash_attention"]
    assert 0 < spec.metric_reader("step_mfu.chat")(run) < 100.0


def test_no_trace_gives_no_reading():
    run = type("Run", (), {"trace": None})()
    for name in ("decode_step_ms", "host_gap_ms.chat", "device_idle_share.chat",
                 "decode_attention_roofline", "step_mfu.chat"):
        assert spec.metric_reader(name)(run) is None


def test_kernel_names():
    assert trace.kernel_of("decode_attention", KERNELS) == "decode_attention"
    assert trace.kernel_of("flash_attention.12", KERNELS) == "flash_attention"
    assert trace.kernel_of("fusion.3", KERNELS) is None
    assert trace.kernel_of("decode_attention_bwd", KERNELS) is None


def test_peaks_are_keyed_by_device_kind():
    v5e = spec.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks_for("cpu")


def test_prefill_flops_count_the_head_once():
    cfg = tinybench.config()
    qwen3 = arch.for_config(cfg)
    p = qwen3.matmul_params(cfg)
    n = 10
    assert qwen3.prefill_flops(cfg, n) == pytest.approx(
        2 * p["layers"] * n + 2 * p["head"] + 4 * 4 * 16 * 2 * n * (n + 1) / 2
    )
