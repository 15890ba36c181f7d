"""The benchmark's binding to the system under test.

Everything the benchmark knows of the program is here: how a configuration
file becomes a `ModelConfig` and a `ServeConfig`, how the benchmark's own
weights are laid out as the program's parameters, how an engine is built
and warmed, and which calls of the serving loop carry spans.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Dict, Iterable, List

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ModelConfig
from repro.models import init_params
from repro.serve import ContinuousEngine, ServeConfig
from repro.serve import request_plane as rp

from .weights import dims, make_weights, seed_key


def model_config(config: Dict[str, Any]) -> ModelConfig:
    d = dims(config)
    return ModelConfig(
        name=config["name"],
        family="dense",
        n_layers=d["L"],
        d_model=d["D"],
        n_heads=d["H"],
        n_kv_heads=d["K"],
        d_ff=d["F"],
        vocab_size=d["V"],
        head_dim=d["hd"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        qk_norm=True,
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype="bfloat16",
        param_dtype="bfloat16",
    )


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


def to_program(w: Dict[str, Any]) -> Dict[str, Any]:
    """The benchmark's weights in the program's parameter layout: layers
    stacked (L, 1, ...), heads split out of the projections, and norm
    weights stored as offsets from 1 (the program scales by 1 + w)."""
    lw = w["layers"]
    L, D, _ = lw["wq"].shape
    hd = lw["q_norm"].shape[-1]

    def off(g):  # exact in bf16: g is 1 + a multiple of 2^-7 near 1
        return (g.astype(jnp.float32) - 1.0).astype(g.dtype)

    def one(x):
        return x[:, None]

    p = {
        "embed": {"tok": w["embed"]},
        "final_norm": off(w["final_norm"]),
        "decoder": {
            "ln1": one(off(lw["attn_norm"])),
            "ln2": one(off(lw["mlp_norm"])),
            "attn": {
                "wq": one(lw["wq"].reshape(L, D, -1, hd)),
                "wk": one(lw["wk"].reshape(L, D, -1, hd)),
                "wv": one(lw["wv"].reshape(L, D, -1, hd)),
                "wo": one(lw["wo"].reshape(L, -1, hd, D)),
                "q_norm": one(off(lw["q_norm"])),
                "k_norm": one(off(lw["k_norm"])),
            },
            "mlp": {
                "w_gate": one(lw["w_gate"]),
                "w_up": one(lw["w_up"]),
                "w_down": one(lw["w_down"]),
            },
        },
    }
    if "lm_head" in w:
        p["lm_head"] = w["lm_head"]
    return p


def program_params(config: Dict[str, Any], seed: int, device) -> Any:
    """The program's parameters, made on `device` in one jitted call."""
    cfg = model_config(config)
    want = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    make = jax.jit(
        lambda key: to_program(make_weights(config, key)),
        out_shardings=SingleDeviceSharding(device),
    )
    got = jax.eval_shape(make, seed_key(seed))
    if jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(got) or any(
        (a.shape, a.dtype) != (b.shape, b.dtype)
        for a, b in zip(jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(got))
    ):
        raise RuntimeError("the program's parameter layout differs from bench/lib/system.py")
    return make(seed_key(seed))


def reference_weights(config: Dict[str, Any], seed: int, device) -> Any:
    """The same weights in the reference's layout, drawn anew from the seed."""
    make = jax.jit(
        lambda key: make_weights(config, key), out_shardings=SingleDeviceSharding(device)
    )
    return make(seed_key(seed))


def build_engine(config: Dict[str, Any], seed: int, device) -> ContinuousEngine:
    cfg = model_config(config)
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
