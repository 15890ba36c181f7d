#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, in one process.

  python3 bench/calibrate.py --config qwen3-32b --traffic chat-r1.5 \
      --seconds 20 --seeds 11,12,13 [--control fp8 | --fault state_unchanged]

For each seed it runs the cell as `bench/run.py` does (weights and traffic
from that seed, a window of `--seconds` at the cell's own load) and prints
one JSON line with the run's `correct`, its checks, and the program's own
widest logit gap.  With `--control`, the reference in that precision
stands in for the served tokens (its `correct` has to be false); with
`--fault`, a fault of `bench/lib/faults.py` is planted in the timed path.
The program's largest gap over a dozen seeds is the lower reading of the
limit; the control's smallest is the upper.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", default=None, help="a precision of the reference's logits_at: int8, fp8")
    ap.add_argument("--fault", default=None, help="a fault of bench/lib/faults.py")
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from bench.lib import boot, spec

    try:
        device = boot.chips(1)
    except boot.NoChip as e:
        print(e, file=sys.stderr)
        return 2
    from bench.lib import faults, serve

    cell = spec.bare_cell(args.config, args.traffic, root=ROOT)
    planted = faults.planted(args.fault) if args.fault else contextlib.nullcontext()
    with planted:
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            out = serve.run_cell(
                cell, device, seed=seed, seconds=args.seconds, trace=False, t_process=t0,
                control=args.control, log=lambda m: print(m, file=sys.stderr, flush=True),
            )
            v = out["verdict"]
            print(json.dumps({
                "seed": seed,
                "control": args.control,
                "fault": args.fault,
                "correct": v["correct"],
                "logit_gap": v["checks"]["logit_gap"]["value"],
                "program_logit_gap": v["program_logit_gap"],
                "checks": {k: c["value"] for k, c in v["checks"].items()},
                "setup_s": out["run"].setup_s,
                "memory_peak_bytes": out["device"]["memory_peak_bytes"],
                "seconds_total": time.perf_counter() - t0,
            }), flush=True)
            del out
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
