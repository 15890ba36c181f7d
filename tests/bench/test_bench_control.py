"""`correct` comes out false for the control and for a broken timed path.

Whole runs of a tiny cell on the CPU (the harness's look for a chip is
skipped; everything else is the run `bench/run.py` makes).  Readings at
this size, 12 seeds of 2 s each: the program's widest logit gap 0 to
0.0275; the reference in float8 e4m3 (the control) 0.22 to 0.29 on 4
seeds, in int8 0.024 to 0.045 (int8 does not separate from bf16 here, so
the control is float8).  The tiny limit, 0.08, lies between them.  The
control and the faults (`bench/lib/faults.py`) go through the same checks
as the program and have to turn `correct` false.
"""

from __future__ import annotations

import time

import jax
import pytest
import tinybench

from bench.lib import faults, serve

LIMIT = tinybench.CONFIG["limits"]["logit_gap"]


def _run(seed, cell=None, seconds=2.0, **kw):
    return serve.run_cell(
        cell or tinybench.cell(), jax.devices()[0], seed=seed, seconds=seconds, trace=False,
        t_process=time.perf_counter(), log=lambda m: None, **kw,
    )


def _line(out):
    return serve.result_line(tinybench.cell(), out, trace=False, root=tinybench.ROOT)


@pytest.mark.parametrize("seed", [21, 2**33 + 22])
def test_program_passes_and_control_fails(seed):
    out = _run(seed)
    v = out["verdict"]
    assert v["correct"], v["checks"]
    line = _line(out)
    assert line["correct"] is True and line["device"]["memory_peak_bytes"] >= 0
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert v["checks"]["logit_gap"]["value"] <= LIMIT
    assert v["program_logit_gap"] == v["checks"]["logit_gap"]["value"]
    assert out["failed"] == 0 and out["attempted"] == 20
    # the float8 reference in the program's place, on the same seed, fails
    # `correct` on the gap alone, while the program's own gap over that
    # sample stays under the limit
    out = _run(seed, control="fp8")
    v = out["verdict"]
    assert not v["correct"] and _line(out)["correct"] is False
    assert v["checks"]["logit_gap"]["value"] > LIMIT
    assert v["program_logit_gap"] <= LIMIT
    assert all(c["value"] == 0 for k, c in v["checks"].items()
               if k not in ("logit_gap", "mid_batch_admits"))


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_broken_timed_path_is_not_correct(fault):
    with faults.planted(fault):
        out = _run(31)
    v = out["verdict"]
    assert not v["correct"]
    line = _line(out)
    assert line["correct"] is False
    assert list(line)[-1] == "checks" and line["checks"]["logit_gap"]["limit"] == LIMIT
    assert set(line["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert v["checks"]["logit_gap"]["value"] > LIMIT
    # the other checks still pass: the gap alone caught it
    assert all(c["value"] == 0 for k, c in v["checks"].items()
               if k not in ("logit_gap", "mid_batch_admits"))


def test_offline_batch_runs_and_checks():
    out = _run(41, cell=tinybench.cell(tinybench.BACKLOG), seconds=1.0)
    v = out["verdict"]
    assert v["correct"], v["checks"]
    assert 0 < len(out["run"].completed_in_window) <= 10
    assert out["failed"] == 0
