#!/usr/bin/env python3
"""Run one cell of the serving benchmark once, on the chip it is started on.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from `BENCHMARK.json` (see `bench/lib/spec.py`).  The run builds the
engine with weights drawn from the seed, warms every program the mix can
use, offers the mix for `--seconds`, then checks what was served against
the float32 reference.  The last line of stdout is one JSON object:
`correct`, `attempted`, `failed`, `metrics` (the cell's end-to-end metrics,
or with `--trace 1` its per-layer metrics, read from a profiler trace of a
few seconds in the middle of the window), `device`, with `--trace 1`
`breakdown`, and last `checks`: each number compared with its limit.  The
same comparisons are the last lines of stderr.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.  JAX's compilation cache lives in `.jax_cache/` of this
checkout, so that only the first run of a cell here compiles.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    sys.path.insert(0, ROOT)
    from bench.lib import boot, spec

    cell = spec.load_cell(args.workload, root=ROOT)
    try:
        device = boot.chips(cell.chips)
    except boot.NoChip as e:
        log(str(e))
        return 2
    from bench.lib import serve

    out = serve.run_cell(
        cell, device, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_process=T_PROCESS, log=log,
    )
    result = serve.result_line(cell, out, trace=bool(args.trace), root=ROOT)
    run, device = out["run"], result["device"]
    log(
        f"setup_s={run.setup_s} compile_s={out['compile_s']} compiles={out['compiles']} "
        f"memory_peak_bytes={device['memory_peak_bytes']}"
    )
    for name, c in out["verdict"]["checks"].items():
        log(f"{name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
