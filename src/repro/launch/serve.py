"""Serving engine worker: one continuous-batching engine over shared storage.

Each invocation is ONE stateless engine worker — the paper's scaling unit.
Point any number of them at the same ``--kv-root``/``--obj-root`` (shared
filesystem) and they cooperatively drain the ``serve/q/*`` request queues:
leases keep two engines off the same request, heartbeats keep live work
fenced, and a worker that dies mid-stream is reaped by the survivors and
its requests re-served byte-identically (per-request PRNG keys).

Worker over a shared directory (start N of these; clients submit with
``repro.serve.request_plane.submit`` against the same roots):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b --reduced \
      --kv-root /srv/kv --obj-root /srv/obj --engine-id e0 --idle-timeout 10

Self-contained demo (no roots -> in-memory stores, submits its own
Poisson-ish traffic and serves it):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b --reduced \
      --demo-requests 12

The worker prints ``READY <engine-id>`` after jit warmup so orchestrators
can wait for it before submitting, and a stats line on idle exit.
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import Optional

import jax
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro.configs import CONFIGS, ModelConfig
from repro.models import init_params
from repro.serve import ContinuousEngine, ServeConfig
from repro.serve import request_plane as rp
from repro.storage import FileBackend, FileKVStore, KVStore, ObjectStore
from repro.util import use_compile_cache


def build_engine(
    cfg: ModelConfig,
    scfg: ServeConfig,
    *,
    device: Optional[jax.Device] = None,
    seed: int = 0,
) -> ContinuousEngine:
    """A warmed engine with random weights from ``seed`` on ``device``.

    The weights are drawn under ``jax.jit`` straight into the device's
    memory: eagerly, each stacked tensor would first exist in float32."""
    device = jax.devices()[0] if device is None else device
    params = jax.jit(
        init_params, static_argnums=0, out_shardings=SingleDeviceSharding(device)
    )(cfg, jax.random.PRNGKey(seed))
    engine = ContinuousEngine(cfg, params, scfg, device=device)
    engine.warm()  # compile before READY, outside any lease
    return engine


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen3-32b", choices=sorted(CONFIGS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--kv-root", help="shared FileKVStore directory (request plane)")
    ap.add_argument("--obj-root", help="shared FileBackend directory (bodies/results)")
    ap.add_argument("--engine-id", default="engine-0")
    ap.add_argument("--idle-timeout", type=float, default=5.0,
                    help="exit after the queue stays empty this long (s)")
    ap.add_argument("--batch", type=int, default=4, help="decode slots")
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--decode-chunk", type=int, default=8,
                    help="decode steps between admission/stream boundaries")
    ap.add_argument("--queues", type=int, default=1, help="serve/q/ shard count")
    ap.add_argument("--lease-timeout", type=float, default=2.0)
    ap.add_argument("--demo-requests", type=int, default=0,
                    help="submit this many synthetic requests first (demo mode; "
                    "uses in-memory stores when no roots are given)")
    args = ap.parse_args()

    if bool(args.kv_root) != bool(args.obj_root):
        ap.error("--kv-root and --obj-root must be given together")
    if args.kv_root:
        kv = FileKVStore(args.kv_root, num_shards=2)
        store = ObjectStore(backend=FileBackend(args.obj_root))
    else:
        if not args.demo_requests:
            ap.error("no shared roots: give --kv-root/--obj-root, or "
                     "--demo-requests N for a self-contained in-memory demo")
        kv = KVStore(num_shards=2)
        store = ObjectStore()

    use_compile_cache()
    cfg = CONFIGS[args.arch]
    if args.reduced:
        cfg = cfg.reduced()
    scfg = ServeConfig(
        max_batch=args.batch,
        max_len=args.max_len,
        max_new_tokens=args.new_tokens,
        decode_chunk=args.decode_chunk,
        n_queues=args.queues,
        lease_timeout_s=args.lease_timeout,
    )
    engine = build_engine(cfg, scfg)
    print(f"READY {args.engine_id}", flush=True)

    if args.demo_requests:
        rng = np.random.default_rng(0)
        cfg = engine.cfg
        for i in range(args.demo_requests):
            prompt = rng.integers(
                0, cfg.vocab_size, size=int(rng.integers(4, 16))
            ).tolist()
            rp.submit(store, kv, f"req-{i:04d}", prompt, n_queues=args.queues)
        print(f"submitted {args.demo_requests} requests", flush=True)

    t0 = time.time()
    stats = engine.run(
        store, kv, engine_id=args.engine_id, idle_timeout_s=args.idle_timeout
    )
    dt = time.time() - t0
    kv_share = stats["decode_kv_positions_read"] / max(stats["decode_kv_positions_held"], 1)
    print(
        f"{args.engine_id}: served {stats['served']} requests, "
        f"{stats['tokens_out']} tokens in {dt:.1f}s "
        f"({stats['tokens_out'] / max(dt, 1e-9):.1f} tok/s; "
        f"{stats['mid_batch_admissions']} mid-batch admissions, "
        f"{stats['decode_steps']} decode steps; "
        f"mean lease wait {_mean_ms(stats, 'lease_wait_ns', 'leased'):.2f} ms, "
        f"first-token hold "
        f"{_mean_ms(stats, 'first_token_hold_ns', 'first_tokens_streamed'):.2f} ms, "
        f"host {_mean_ms(stats, 'decode_host_ns', 'decode_steps'):.3f} ms per decode step; "
        f"{100 * kv_share:.1f}% of the held cache attended to)",
        flush=True,
    )


def _mean_ms(stats, total_ns: str, count: str) -> float:
    return stats[total_ns] / max(stats[count], 1) * 1e-6


if __name__ == "__main__":
    sys.exit(main())
