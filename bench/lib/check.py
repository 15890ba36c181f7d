"""How `correct` is decided.

Once the window has closed and the engine's state is freed:

  served_once   every result record is the engine's, published once per
                request that finished (engine counter against results)
  stream        every finished request's stream, as its client read it,
                equals its published result, token for token
  unfinished    requests of the window that never finished (open loop:
                all of them are due; offline batch: those the engine took)
  compiles      XLA compiles inside the window
  mid_batch     (open loop) requests admitted into a running batch
  logit_gap     a sample of finished requests drawn from the seed, with the
                longest among them, is run through the float32 reference
                (the binding's `logits_at`, `bench/arch/`) over prompt +
                served tokens; the number is the widest gap by which a
                served token's reference logit lies below the reference's
                best at that position

The limit of `logit_gap` is the configuration's `limits.logit_gap`, set
between the program's readings over a dozen seeds and the control's, as
`PERF.md` records.  The control is the same reference with float8 weights
(`logits_at(..., quant="fp8")`) put in the program's place: a run with
`control` compares the tokens it puts first, and its `correct` has to come
out false.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import arch

from . import system


def sample(outcomes, seed: int, min_tokens: int, max_requests: int) -> List[Any]:
    """Finished requests drawn from the seed: the longest first, then
    others until `min_tokens` served tokens are in the sample."""
    done = [o for o in outcomes if o.finished]
    if not done:
        return []
    longest = max(done, key=lambda o: (len(o.req.prompt) + len(o.tokens), o.req.req_id))
    rest = [o for o in done if o is not longest]
    order = np.random.default_rng([int(seed), 2]).permutation(len(rest))
    picked, n_tok = [longest], len(longest.tokens)
    for i in order:
        if n_tok >= min_tokens or len(picked) >= max_requests:
            break
        picked.append(rest[i])
        n_tok += len(rest[i].tokens)
    return picked


def logit_gaps(w, config, picked, *, quant=None) -> List[float]:
    """Per request, the widest gap of its served tokens below the float32
    reference's best logit.  With `quant`, the served tokens are the ones
    the lower-precision reference puts first (the control)."""
    ref_logits = arch.for_config(config).logits_at
    gaps = []
    for o in picked:
        prompt, out = o.req.prompt, o.tokens
        seq = list(prompt) + list(out[:-1])
        rows = np.arange(len(prompt) - 1, len(seq))
        ref = np.asarray(ref_logits(w, config, seq, rows))
        if quant is None:
            served = np.asarray(out)
        else:
            low = np.asarray(ref_logits(w, config, seq, rows, quant=quant))
            served = low.argmax(-1)
        best = ref.max(-1)
        gaps.append(float((best - ref[np.arange(len(rows)), served]).max()))
    return gaps


def verify(
    run,
    *,
    seed: int,
    engine_error,
    compiles_in_window: int,
    device,
    log: Callable[[str], None],
    control: Optional[str] = None,
) -> Dict[str, Any]:
    """The checks of one run and `correct`.  With `control`, the tokens
    that the reference in that precision puts first stand in for the
    served ones in `logit_gap`; the program's own gap is still read, as
    `program_logit_gap`."""
    cfg = run.cell.config
    limits = cfg["limits"]
    backlog = run.cell.traffic["arrivals"] == "backlog"
    finished = [o for o in run.outcomes if o.finished]
    mismatch = sum(1 for o in finished if o.tokens != o.published)
    served = int(run.stats.get("served", -1))
    results = sum(1 for o in run.outcomes if o.published is not None)
    tried = [o for o in run.outcomes if not backlog or o.error != "not leased"]
    unfinished = sum(1 for o in tried if not o.finished)
    checks: Dict[str, Dict[str, Any]] = {
        "engine_error": {"value": 0 if engine_error is None else 1, "limit": 0},
        "served_once": {"value": abs(served - results), "limit": 0},
        "stream_vs_result": {"value": mismatch, "limit": 0},
        "unfinished": {"value": unfinished, "limit": 0},
        "compiles_in_window": {"value": compiles_in_window, "limit": 0},
    }
    if not backlog:
        checks["mid_batch_admits"] = {
            "value": int(run.stats.get("mid_batch_admissions", 0)), "limit": ">=1",
        }
    picked = sample(finished, seed, int(limits["sample_tokens"]), int(limits["sample_requests"]))
    program_gap = gap = math.inf
    n_tok = sum(len(o.tokens) for o in picked)
    if picked:
        weights = system.reference_weights(cfg, seed, device)
        program_gap = gap = max(logit_gaps(weights, cfg, picked))
        if control:
            gap = max(logit_gaps(weights, cfg, picked, quant=control))
        del weights
    checks["logit_gap"] = {"value": gap, "limit": float(limits["logit_gap"])}
    ok = True
    for name, c in checks.items():
        lim = c["limit"]
        good = c["value"] >= 1 if lim == ">=1" else c["value"] <= lim
        ok = ok and bool(good)
    if engine_error:
        log(f"engine error: {engine_error}")
    log(f"sample: {len(picked)} requests, {n_tok} served tokens")
    if control:
        log(f"control: the reference in {control} stands in for the served tokens; "
            f"program_logit_gap {program_gap}")
    for name, c in checks.items():
        log(f"check {name}: {c['value']} limit {c['limit']}")
    return {"correct": ok, "checks": checks, "program_logit_gap": program_gap}
