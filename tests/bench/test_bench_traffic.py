"""Seeded traffic, the metric arithmetic, and finding every piece by name."""

from __future__ import annotations

import json
import math
import os

import pytest
import tinybench

from bench.lib import spec, traffic
from bench.lib.serve import Outcome, Run

MIX = tinybench.CHAT


def test_same_seed_same_requests():
    a = traffic.generate(MIX, 2**33 + 7, 10.0, 512)
    b = traffic.generate(MIX, 2**33 + 7, 10.0, 512)
    assert [(r.due_s, r.prompt, r.max_new) for r in a] == [(r.due_s, r.prompt, r.max_new) for r in b]


def test_seeds_share_the_work_and_differ_in_order():
    a = traffic.generate(MIX, 1, 10.0, 512)
    b = traffic.generate(MIX, 2, 10.0, 512)
    assert len(a) == len(b) == 100  # 10 requests/s over 10 s
    pairs = lambda rs: sorted((len(r.prompt), r.max_new) for r in rs)  # noqa: E731
    assert pairs(a) == pairs(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert sorted(round(r.due_s, 9) for r in a) != sorted(round(r.due_s, 9) for r in b)


def test_arrivals_fill_the_window_and_lengths_keep_their_clip():
    rs = traffic.generate(MIX, 5, 10.0, 512)
    due = [r.due_s for r in rs]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 10.0
    assert all(4 <= len(r.prompt) <= 48 and 4 <= r.max_new <= 40 for r in rs)
    assert all(0 <= t < 512 for r in rs for t in r.prompt)
    back = traffic.generate(tinybench.BACKLOG, 5, 10.0, 512)
    assert len(back) == 100 and all(r.due_s == 0.0 for r in back)


def test_lognormal_median_is_the_stated_median():
    mix = dict(MIX, prompt_len={"dist": "lognormal", "median": 512, "sigma": 0.8,
                                "min": 64, "max": 2048})
    lens = sorted(len(r.prompt) for r in traffic.generate(mix, 3, 25.0, 100))
    assert abs(lens[len(lens) // 2] - 512) <= 8


def test_nearest_rank_counts_unfinished_as_slowest():
    assert traffic.nearest_rank([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 0.9) == 9
    vals = [1.0] * 8 + [math.inf, math.inf]
    assert traffic.nearest_rank(vals, 0.9) == math.inf
    assert traffic.nearest_rank(vals, 0.5) == 1.0


def _run(outcomes, seconds=10.0):
    cell = tinybench.cell()
    r = Run(cell=cell, seconds=seconds, outcomes=outcomes, t_open=100.0, t_close=100.0 + seconds,
            spans=None, stats={}, peaks={}, setup_s=3.5)
    r.completed_in_window = [o for o in outcomes if o.finished and o.last <= r.t_close]
    return r


def _outcome(i, due, first, last, n, max_new=None):
    req = traffic.Request(f"r{i}", due - 100.0, [1] * 10, max_new or n)
    return Outcome(req, due=due, sent=due, first=first, last=last, tokens=[7] * n)


def test_latency_is_timed_from_due_time_at_the_client():
    outs = [_outcome(i, 100.0 + i, 100.0 + i + 0.25, 100.0 + i + 1.25, 11) for i in range(10)]
    run = _run(outs)
    assert spec.metric_reader("ttft_p90_ms")(run) == pytest.approx(250.0)
    assert spec.metric_reader("tpot_p90_ms")(run) == pytest.approx(100.0)
    assert spec.metric_reader("setup_s")(run) == 3.5
    # request 9 finishes at 110.25, after the window closed at 110
    assert [o.req.req_id for o in run.completed_in_window] == [f"r{i}" for i in range(9)]


def test_a_request_short_of_its_tokens_counts_as_a_miss():
    outs = [_outcome(i, 100.0 + i, 100.5 + i, 101.0 + i, 5) for i in range(9)]
    outs.append(_outcome(9, 109.0, 109.1, 109.2, 3, max_new=5))  # never finished
    run = _run(outs)
    assert spec.metric_reader("ttft_p90_ms")(run) == pytest.approx(500.0)
    outs[0].error = "timeout"
    assert spec.metric_reader("ttft_p90_ms")(_run(outs)) == math.inf


def test_a_cell_a_mix_and_a_metric_are_found_by_name(tmp_path):
    """A later change adds files and entries only: a test-only cell, mix,
    configuration and metric are found without touching any other file."""
    root = tmp_path
    for d in ("bench/configs", "bench/traffic", "bench/metrics"):
        os.makedirs(root / d)
    bench = json.load(open(os.path.join(tinybench.ROOT, "BENCHMARK.json")))
    bench["configs"].append({"name": "tiny-qwen3", "source": "test", "file": "bench/configs/tiny-qwen3.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-qwen3.only-test", "config": "tiny-qwen3",
                               "traffic": "only-test", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "only_test_metric", "unit": "ms", "better": "lower",
                               "source": "program_span", "layer": "engine", "moves": "ttft_p90_ms",
                               "workloads": ["tiny-qwen3.only-test"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench/configs/tiny-qwen3.json").write_text(json.dumps(tinybench.config()))
    (root / "bench/traffic/only-test.json").write_text(json.dumps(MIX))
    (root / "bench/metrics/only_test_metric.py").write_text("def read(run):\n    return 42.0\n")
    cell = spec.load_cell("tiny-qwen3.only-test", root=str(root))
    assert cell.config["hidden_size"] == 64 and cell.traffic == MIX
    assert [m.name for m in cell.per_layer] == ["only_test_metric"]
    assert [m.name for m in cell.end_to_end] == ["setup_s"]
    assert spec.metric_reader("only_test_metric", root=str(root))(None) == 42.0
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell", root=str(root))


def test_every_metric_of_benchmark_json_has_a_reader():
    bench = json.load(open(os.path.join(tinybench.ROOT, "BENCHMARK.json")))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        e = cell.config["engine"]
        lo, hi = traffic.length_range(cell.traffic, "prompt_len")
        assert hi + traffic.length_range(cell.traffic, "output_len")[1] <= e["max_len"]
        assert "setup_s" in [m.name for m in cell.end_to_end] and len(cell.end_to_end) >= 2
        assert cell.per_layer
