"""The benchmark's binding to the system under test.

Everything the benchmark knows of the serving program is here: how a
configuration file becomes a `ServeConfig`, how an engine is built from
the benchmark's own weights and warmed, and which calls of the serving
loop carry spans.  What depends on the model's architecture (its
`ModelConfig`, weights and parameter layout) is its binding's, found by
the configuration's `model_type` (`bench/arch/`).
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Dict, Iterable, List

import jax
from jax.sharding import SingleDeviceSharding

from bench import arch
from repro.models import init_params
from repro.serve import ContinuousEngine, ServeConfig
from repro.serve import request_plane as rp

from .weights import seed_key


def serve_config(config: Dict[str, Any]) -> ServeConfig:
    e = config["engine"]
    return ServeConfig(
        max_batch=int(e["max_batch"]),
        max_len=int(e["max_len"]),
        cache_dtype=e["cache_dtype"],
        decode_chunk=int(e["decode_chunk"]),
        prefill_bucket=int(e["prefill_bucket"]),
        lease_timeout_s=float(e["lease_timeout_s"]),
        heartbeat_interval_s=float(e["heartbeat_interval_s"]),
        temperature=0.0,
        eos_id=-1,
    )


def program_params(config: Dict[str, Any], seed: int, device) -> Any:
    """The program's parameters, made on `device` in one jitted call."""
    binding = arch.for_config(config)
    cfg = binding.model_config(config)
    want = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    make = jax.jit(
        lambda key: binding.to_program(binding.make_weights(config, key), config),
        out_shardings=SingleDeviceSharding(device),
    )
    got = jax.eval_shape(make, seed_key(seed))
    if jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(got) or any(
        (a.shape, a.dtype) != (b.shape, b.dtype)
        for a, b in zip(jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(got))
    ):
        raise RuntimeError(
            f"the program's parameter layout differs from {binding.__file__}'s to_program"
        )
    return make(seed_key(seed))


def reference_weights(config: Dict[str, Any], seed: int, device) -> Any:
    """The same weights in the reference's layout, drawn anew from the seed."""
    binding = arch.for_config(config)
    make = jax.jit(
        lambda key: binding.make_weights(config, key), out_shardings=SingleDeviceSharding(device)
    )
    return make(seed_key(seed))


def build_engine(config: Dict[str, Any], seed: int, device) -> ContinuousEngine:
    cfg = arch.for_config(config).model_config(config)
    return ContinuousEngine(cfg, program_params(config, seed, device), serve_config(config), device=device)


def bucket_lengths(engine: ContinuousEngine, lo: int, hi: int) -> List[int]:
    """Every padded prefill length a prompt of lo..hi tokens can take."""
    return sorted({engine._pad_len(n) for n in range(lo, hi + 1)})


def warm(engine: ContinuousEngine, lengths: Iterable[int]) -> None:
    """Compile (or load from the cache) every program the window runs:
    one prefill per padded length, the slot insert, decode and sampling."""
    for n in lengths:
        engine.admit([(f"warm-{n}", [1 + (i % 7) for i in range(n)], 3)])
        while engine.n_live():
            engine.step_chunk()
    for k in engine.stats:
        engine.stats[k] = 0


class Spans:
    """Host spans around the serving loop's calls, kept in memory.

    Each record is (name, start_ns, end_ns, info, idx) on
    `time.perf_counter_ns`; the same spans enter the profiler's trace as
    `TraceAnnotation`s named `bench.<name>` that carry `idx`, so a trace
    reader can lay them beside device events and join them to `info`."""

    def __init__(self) -> None:
        self.records: List[tuple] = []
        self._lock = threading.Lock()
        self._next = itertools.count()

    def wrap(self, name: str, fn, info=None):
        def wrapped(*args, **kwargs):
            idx = next(self._next)
            t0 = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation(f"bench.{name}", idx=idx):
                out = fn(*args, **kwargs)
            t1 = time.perf_counter_ns()
            extra = info(args, kwargs, out) if info else None
            with self._lock:
                self.records.append((name, t0, t1, extra, idx))
            return out

        return wrapped


def _admit_info(args, kwargs, out):
    reqs = args[0]
    return [(r, len(p)) for r, p, _ in reqs]


def instrument(engine: ContinuousEngine, spans: Spans):
    """Wrap the engine's and the request plane's calls with spans.

    `step_chunk` also records, per chunk, the attention length of every
    token it decoded (prompt + tokens so far), read from the public slots
    before and after the call.  Returns a function that undoes the wraps."""
    saved = {n: getattr(rp, n) for n in ("lease_requests", "stream_chunks", "publish_results")}
    for n, fn in saved.items():
        setattr(rp, n, spans.wrap(n, fn))
    engine.admit = spans.wrap("admit", engine.admit, _admit_info)
    step = engine.step_chunk

    def step_chunk(*args, **kwargs):
        before = [(s, len(s.out)) for s in engine.slots if s is not None]
        steps0 = engine.stats["decode_steps"]
        out = step(*args, **kwargs)
        ctx = []
        for s, o0 in before:
            ctx.extend(s.prompt_len + o for o in range(o0, len(s.out)))
        return out, {"steps": engine.stats["decode_steps"] - steps0, "ctx": ctx}

    def step_info(args, kwargs, out):
        return out[1]

    wrapped = spans.wrap("step_chunk", step_chunk, step_info)
    engine.step_chunk = lambda *a, **k: wrapped(*a, **k)[0]

    def undo():
        for n, fn in saved.items():
            setattr(rp, n, fn)
        del engine.admit
        del engine.step_chunk

    return undo
