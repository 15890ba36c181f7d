#!/usr/bin/env python3
"""Offer a mix at several fixed rates to one warmed engine: the knee sweep.

  python3 bench/sweep.py --config qwen3-32b --traffic chat \
      --rates 1.5,2,2.5,3 --seconds 30 --seed 7

For each rate it prints one JSON line: requests due, finished inside the
window and after it, TTFT p50/p90 over all requests and the median TTFT of
the first and last fifth of the window (a queue that grows through the
window shows as a last fifth far above the first), the requests due but
still waiting for their first token when the window closes, and TPOT p90.
The knee is the highest rate at which the queue does not grow through the
window: the last fifth's median TTFT within 1.5 x the first fifth's, and
no more than a few requests waiting at the close.  Outputs are not checked
here; `bench/run.py` checks them.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from bench.lib import boot, spec

    try:
        device = boot.chips(1)
    except boot.NoChip as e:
        print(e, file=sys.stderr)
        return 2
    from bench.lib import serve, traffic

    cell = spec.bare_cell(args.config, args.traffic, root=ROOT)
    clock = serve.CompileClock()
    engine = serve.prepare(cell, args.seed, device)
    print(json.dumps({"setup_s": time.perf_counter() - T_PROCESS}), flush=True)
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.traffic = dict(cell.traffic, rate_per_s=rate)
        out = serve.offer(
            cell, engine, seed=args.seed, seconds=args.seconds, trace=False,
            t_process=T_PROCESS, device=device, clock=clock,
            log=lambda m: print(m, file=sys.stderr, flush=True),
        )
        run = out["run"]
        ttft = [(o.first - o.due) * 1e3 if o.finished else math.inf for o in run.outcomes]
        tpot = [
            (o.last - o.first) * 1e3 / (len(o.tokens) - 1)
            for o in run.outcomes if o.finished and len(o.tokens) > 1
        ]
        n = len(run.outcomes)
        fifth = max(1, n // 5)
        print(json.dumps({
            "rate_per_s": rate,
            "due": n,
            "finished_in_window": len(run.completed_in_window),
            "finished": sum(o.finished for o in run.outcomes),
            "last_done_after_close_s": max(
                (o.last - run.t_close for o in run.outcomes if o.finished), default=None
            ),
            "ttft_p50_ms": traffic.nearest_rank(ttft, 0.5),
            "ttft_p90_ms": traffic.nearest_rank(ttft, 0.9),
            "ttft_first_fifth_p50_ms": traffic.nearest_rank(ttft[:fifth], 0.5),
            "ttft_last_fifth_p50_ms": traffic.nearest_rank(ttft[-fifth:], 0.5),
            "last_over_first_fifth": traffic.nearest_rank(ttft[-fifth:], 0.5)
            / traffic.nearest_rank(ttft[:fifth], 0.5),
            "waiting_at_close": sum(
                1 for o in run.outcomes if o.due <= run.t_close and not o.first <= run.t_close
            ),
            "tpot_p90_ms": traffic.nearest_rank(tpot, 0.9),
            "engine": run.stats,
        }), flush=True)
        if out["engine_error"]:  # still serving this rate's queue: higher rates would share it
            print(json.dumps({"stopped": out["engine_error"]}), flush=True)
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
