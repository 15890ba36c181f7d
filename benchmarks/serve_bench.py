"""Open-loop serving benchmark: continuous batching under Poisson traffic.

The serving-plane counterpart of `microbench`: an open-loop generator
submits requests at a configured offered load (Poisson arrivals — the
client does NOT wait for responses, so queueing delay is measured, not
hidden), N `ContinuousEngine` workers lease them off the shared KV queue,
and every row reports the latency distribution a real client would see:

  serve/open_loop{suffix}_e{N}_r{RPS}:
    us_per_call     p50 end-to-end latency (submit -> result published)
    p99_ms          p99 end-to-end latency
    ttft_p50_ms     p50 time-to-first-token (submit -> first token sampled)
    ttft_p99_ms     p99 time-to-first-token
    tokens_per_s    sustained decode throughput over the serving window
    offered_rps     the generator's target arrival rate
    n_engines       engine workers sharing the queue
    speedup_vs_e1   tokens_per_s vs the 1-engine run at the same load

The 1->2->4 engine scale-out curve is the paper's elasticity story told
on the serving plane: engines are stateless workers over shared storage,
so capacity is "start another one".  ``speedup_vs_e1`` is the scale-out
acceptance number on a multi-core host (each engine's jitted decode
releases the GIL, so engines overlap across cores); on a single-core box
the engines share the one CPU and the ratio pins near 1, so — exactly as
with the microbench ``speedup_vs_d1`` column — the scale-out claim is
read from multi-core runs and CI gates only the tokens/s floor, never
the ratio blind.

Usage:
  PYTHONPATH=src python -m benchmarks.serve_bench --quick \
      --backends memory,file --json BENCH_serve.json \
      --floor-serve-tokens-per-s 40

Full curve (slower): --backends file,net --engines 1,2,4 --loads 4,16
"""

from __future__ import annotations

import tempfile
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from .microbench import _make_stores

_BACKEND_SUFFIX = {"memory": "", "file": "_file", "net": "_net"}


def _engine_parts(max_batch: int, max_new: int):
    import jax

    from repro.configs import CONFIGS
    from repro.models import init_params
    from repro.serve import ServeConfig

    cfg = CONFIGS["qwen3-32b"].reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    scfg = ServeConfig(
        max_batch=max_batch,
        max_len=96,
        max_new_tokens=max_new,
        decode_chunk=4,
        prefill_bucket=8,
        lease_timeout_s=2.0,
    )
    return cfg, params, scfg


def _open_loop_once(
    rep,
    *,
    backend: str,
    n_engines: int,
    offered_rps: float,
    n_requests: int,
    prompt_lens=(4, 9),  # one prefill bucket: every shape is pre-warmed
    max_batch: int = 4,
    max_new: int = 16,
    seed: int = 0,
    e1_tokens_per_s: Optional[float] = None,
) -> float:
    import jax

    from repro.serve import ContinuousEngine
    from repro.serve import request_plane as rp

    cfg, params, scfg = _engine_parts(max_batch, max_new)
    rng = np.random.default_rng(seed)
    ids = [f"q{i:04d}" for i in range(n_requests)]
    prompts = {
        r: rng.integers(0, cfg.vocab_size, size=int(rng.integers(*prompt_lens))).tolist()
        for r in ids
    }

    with tempfile.TemporaryDirectory() as workdir:
        store, kv, cleanup = _make_stores(backend, workdir)
        try:
            # one process, one engine per device (round-robin when the
            # engines outnumber the devices, as on a one-device CPU host)
            devices = jax.devices()
            engines = [
                ContinuousEngine(cfg, params, scfg, device=devices[i % len(devices)])
                for i in range(n_engines)
            ]
            for e in engines:
                # decode and the prompt bucket's prefill compile outside
                # the measured window: each engine owns its own jit wrappers
                e.warm()
            idle_s = max(2.5, 6.0 / offered_rps)
            threads = [
                threading.Thread(
                    target=e.run,
                    args=(store, kv),
                    kwargs=dict(engine_id=f"e{i}", idle_timeout_s=idle_s),
                    daemon=True,
                )
                for i, e in enumerate(engines)
            ]
            for t in threads:
                t.start()

            submit_ts: Dict[str, float] = {}

            def _client() -> None:
                for r in ids:
                    time.sleep(rng.exponential(1.0 / offered_rps))
                    submit_ts[r] = time.time()
                    rp.submit(store, kv, r, prompts[r], n_queues=scfg.n_queues)

            t0 = time.time()
            client = threading.Thread(target=_client, daemon=True)
            client.start()
            client.join()
            res = rp.get_results(store, ids, timeout_s=120.0)
            for t in threads:
                t.join()
        finally:
            if cleanup:
                cleanup()

    lat = np.asarray([res[r]["t_done"] - submit_ts[r] for r in ids])
    ttft = np.asarray([res[r]["t_first"] - submit_ts[r] for r in ids])
    total_tokens = sum(len(res[r]["tokens"]) for r in ids)
    window = max(res[r]["t_done"] for r in ids) - t0
    tokens_per_s = total_tokens / max(window, 1e-9)

    suffix = _BACKEND_SUFFIX[backend]
    name = f"serve/open_loop{suffix}_e{n_engines}_r{offered_rps:g}"
    extra: Dict[str, float] = {}
    if e1_tokens_per_s:
        extra["speedup_vs_e1"] = round(tokens_per_s / e1_tokens_per_s, 2)
    rep.row(
        name,
        float(np.percentile(lat, 50) * 1e6),  # us_per_call = p50 latency
        p99_ms=round(float(np.percentile(lat, 99) * 1e3), 2),
        ttft_p50_ms=round(float(np.percentile(ttft, 50) * 1e3), 2),
        ttft_p99_ms=round(float(np.percentile(ttft, 99) * 1e3), 2),
        tokens_per_s=round(tokens_per_s, 1),
        offered_rps=offered_rps,
        n_requests=n_requests,
        n_engines=n_engines,
        **extra,
    )
    return tokens_per_s


def open_loop(
    rep,
    *,
    backends: List[str],
    engines: List[int],
    loads: List[float],
    n_requests: int,
) -> None:
    for backend in backends:
        for rps in loads:
            e1: Optional[float] = None
            for n in engines:
                tps = _open_loop_once(
                    rep,
                    backend=backend,
                    n_engines=n,
                    offered_rps=rps,
                    n_requests=n_requests,
                    e1_tokens_per_s=e1,
                )
                if n == 1:
                    e1 = tps


def main(argv=None) -> int:
    import argparse
    import json

    from repro.util import use_compile_cache

    from .common import Reporter

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true", help="small CI budget")
    ap.add_argument("--json", metavar="PATH", help="write rows as JSON")
    ap.add_argument(
        "--backends",
        default="memory",
        help="comma list of memory,file,net (shared-storage substrate "
        "the request plane rides on)",
    )
    ap.add_argument("--engines", default=None, help="comma list of engine counts")
    ap.add_argument("--loads", default=None, help="comma list of offered rps")
    ap.add_argument("--requests", type=int, default=None, help="requests per row")
    ap.add_argument(
        "--floor-serve-tokens-per-s",
        type=float,
        default=None,
        help="fail (exit 1) if the best serve row's sustained tokens/s is "
        "below this (a stall in the decode hot loop, the admission path, "
        "or the lease plane all collapse it)",
    )
    args = ap.parse_args(argv)
    use_compile_cache()

    backends = [b.strip() for b in args.backends.split(",") if b.strip()]
    engines = (
        [int(x) for x in args.engines.split(",")]
        if args.engines
        else ([1, 2] if args.quick else [1, 2, 4])
    )
    loads = (
        [float(x) for x in args.loads.split(",")]
        if args.loads
        else ([8.0] if args.quick else [4.0, 16.0])
    )
    n_requests = args.requests or (16 if args.quick else 48)

    rep = Reporter()
    open_loop(rep, backends=backends, engines=engines, loads=loads, n_requests=n_requests)

    if args.json:
        with open(args.json, "w") as f:
            json.dump(rep.rows, f, indent=2)
        print(f"wrote {len(rep.rows)} rows to {args.json}")

    if args.floor_serve_tokens_per_s is not None:
        best = max((r.get("tokens_per_s", 0.0) for r in rep.rows), default=0.0)
        if best < args.floor_serve_tokens_per_s:
            print(
                f"FLOOR BREACH: best serve tokens/s {best} below floor "
                f"{args.floor_serve_tokens_per_s}"
            )
            return 1
        print(f"serve tokens/s floor ok: {best} >= {args.floor_serve_tokens_per_s}")

    # the scale-out pin: 2 engines must sustain more than 1 at equal load
    pairs = [
        (r["name"], r["speedup_vs_e1"]) for r in rep.rows
        if r.get("n_engines") == 2 and "speedup_vs_e1" in r
    ]
    for name, s in pairs:
        print(f"{name}: 2-engine speedup vs 1 = {s}x")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
