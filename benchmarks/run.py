"""Benchmark driver: one function per paper table/figure + runtime
microbenchmarks + the dry-run roofline summary.

Prints ``name,us_per_call,derived`` CSV rows.
"""

from __future__ import annotations

import glob
import json
import os
import time


def runtime_overheads(rep) -> None:
    """§4 'Launch Overheads': per-task scheduling overhead of this runtime
    (real wall time, excludes the modeled Lambda cold start)."""
    from repro.core import WrenExecutor, get_all

    with WrenExecutor(num_workers=4) as wex:
        wex.map_get(lambda x: x, [0])  # warm up containers
        n = 200
        t0 = time.perf_counter()
        futs = wex.map(lambda x: x, list(range(n)))
        get_all(futs, timeout_s=120)
        dt = time.perf_counter() - t0
        rep.row("runtime/task_overhead", dt / n * 1e6, tasks=n, wall_s=round(dt, 3))


def kernel_microbench(rep) -> None:
    """Liveness rows: the flash kernel (interpret mode off the TPU), the
    chunked jnp path and the reference each run at a small shape.  The wall
    times only show the paths run; they are not a speed, on any backend."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    from repro.kernels.flash_attention import flash_attention_pallas

    rng = np.random.default_rng(0)
    B, S, H, K, D = 1, 256, 4, 2, 64
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, K, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, K, D)), jnp.float32)

    for name, fn in [
        ("flash_pallas", lambda: flash_attention_pallas(q, k, v, causal=True)),
        ("flash_jnp_chunked", lambda: ops._attention_chunked_jnp(
            q, k, v, causal=True, window=None, logit_cap=None, q_offset=0,
            scale=D**-0.5, block_k=128)),
        ("mha_reference", lambda: ref.mha_reference(q, k, v, causal=True)),
    ]:
        fn()  # compile/warm
        t0 = time.perf_counter()
        reps = 3
        for _ in range(reps):
            fn().block_until_ready()
        rep.row(
            f"liveness/kernel/{name}",
            (time.perf_counter() - t0) / reps * 1e6,
            platform=jax.default_backend(),
        )


def roofline_summary(rep) -> None:
    """Dry-run roofline table (reads reports/dryrun/*.json if present)."""
    root = os.path.join(os.path.dirname(__file__), "..", "reports", "dryrun")
    files = sorted(glob.glob(os.path.join(root, "*.json")))
    if not files:
        rep.row("roofline/none", 0.0, note="run python -m repro.launch.dryrun --all first")
        return
    for f in files:
        with open(f) as fh:
            d = json.load(fh)
        rep.row(
            f"roofline/{d['arch']}/{d['shape']}/{d['mesh']}",
            d["step_bound_s"] * 1e6,
            dominant=d["dominant"],
            compute_ms=round(d["compute_s"] * 1e3, 2),
            memory_ms=round(d["memory_s"] * 1e3, 2),
            collective_ms=round(d["collective_s"] * 1e3, 2),
            useful_ratio=round(d["useful_ratio"], 3),
            roofline_fraction=round(d["roofline_fraction"], 4),
        )


def main() -> None:
    from .common import Reporter
    from .microbench import ALL as MICRO
    from .paper_figures import ALL

    rep = Reporter()
    for bench in ALL:
        bench(rep)
    runtime_overheads(rep)
    for bench in MICRO:
        bench(rep)
    kernel_microbench(rep)
    roofline_summary(rep)
    print(f"\n{len(rep.rows)} benchmark rows emitted")


if __name__ == "__main__":
    main()
