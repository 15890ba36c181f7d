"""Production training launcher.

Composes: config registry -> mesh -> sharded train state -> stateless
step -> elastic serverless driver.  On this CPU container it runs reduced
configs end-to-end; on a real pod the same entry point drives full configs
(the dry-run proves those lower+compile on the production meshes).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --reduced \
      --steps 40 --seq 64 --batch 4 [--workers 2] [--microbatches 2]
"""

from __future__ import annotations

import argparse
import time


from repro.configs import CONFIGS
from repro.core import WrenExecutor
from repro.data import DataConfig, synthetic_batch
from repro.train import ElasticTrainConfig, adamw, cosine_schedule, train_elastic
from repro.train import checkpoint as ck
from repro.util import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(CONFIGS))
    ap.add_argument("--reduced", action="store_true", help="CPU-size config")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--steps-per-chunk", type=int, default=5)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--run", default=None)
    args = ap.parse_args()
    use_compile_cache()

    cfg = CONFIGS[args.arch]
    if args.reduced:
        cfg = cfg.reduced()
    run = args.run or f"{args.arch}-{'r' if args.reduced else 'f'}"

    dcfg = DataConfig(seq_len=args.seq, global_batch=args.batch, vocab_size=cfg.vocab_size)
    opt = adamw(cosine_schedule(args.lr, warmup=args.steps // 10 + 1, total=args.steps))
    batch_fn = lambda step: synthetic_batch(dcfg, step, cfg)  # noqa: E731

    wex = WrenExecutor(num_workers=args.workers)
    try:
        tcfg = ElasticTrainConfig(
            run=run,
            steps_per_chunk=args.steps_per_chunk,
            total_steps=args.steps,
            microbatches=args.microbatches,
        )
        t0 = time.time()
        hist = train_elastic(wex, cfg, opt, tcfg, batch_fn)
        dt = time.time() - t0
        print(f"arch={args.arch} run={run}")
        print(f"losses: {[round(h['loss'], 4) for h in hist]}")
        print(
            f"{args.steps} steps, {dt:.1f}s, "
            f"{args.steps * args.batch * args.seq / dt:.0f} tok/s, "
            f"checkpoint v{ck.latest_version(wex.store, run)}"
        )
    finally:
        wex.shutdown()


if __name__ == "__main__":
    main()
