#!/usr/bin/env python3
"""Bring-up check: serve llama3-8b on a TPU chip through the request plane.

Drives the serving main path once, through the entry points a user calls:
``repro.launch.serve.build_engine`` builds a warmed ``ContinuousEngine``,
clients ``request_plane.submit`` prompts and read the token streams, and
``ContinuousEngine.run`` leases, prefills, decodes, streams and publishes.

The model is llama3-8b at its published widths (d_model 4096, 32 heads,
8 KV heads, head_dim 128, d_ff 14336, vocab 128256) with depth cut from 32
to 16 layers: 4.54 B params, 9.1 GB in bf16, so that params, a bf16 cache
of 4 slots x 1024 positions and the prefill logits fit one 16 GB v5e chip.
Weights are random, drawn from ``--seed``; so are the prompts (64-512
tokens, 32 new tokens each, greedy).

One chip (the default) prints one line per check, then the result:
  config    the served configuration
  serve     every request completes exactly once with its full token
            count, its stream equals its published result, and at least
            one request was admitted into a running batch
  programs  the compiled decode and prefill programs contain the
            decode_attention and flash_attention Pallas kernels
  kernel    each kernel, run once at the served shapes, is within its
            stated bf16 tolerance of the float32 oracle in kernels/ref.py
  memory    peak device bytes in use, and seconds spent compiling

``--chips 4`` runs only the multi-engine phase: one engine per device,
four threads of this process draining one request queue, compared with
the same requests served by the device-0 engine alone.  Greedy tokens
must agree per request, every engine must serve, and every engine's
arrays must sit on its own device.

The last line of stdout is ``{"ok": true, "device": {...}}`` with the
device as JAX reports it.  Without a TPU, or when any check fails, the
script exits non-zero and prints no such line.  These are bring-up checks:
the times it prints are not a benchmark.

Usage:  python chip_smoke.py [--seed N] [--chips 4]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import CONFIGS  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.decode_attention import decode_attention_pallas  # noqa: E402
from repro.kernels.flash_attention import flash_attention_pallas  # noqa: E402
from repro.launch.serve import build_engine  # noqa: E402
from repro.serve import ServeConfig  # noqa: E402
from repro.serve import request_plane as rp  # noqa: E402
from repro.storage import KVStore, ObjectStore  # noqa: E402
from repro.util import use_compile_cache  # noqa: E402

N_LAYERS = 16
MAX_NEW = 32
PROMPT_LENS = (64, 513)  # [lo, hi) tokens
# One bf16 rounding of the output is <= 2^-9 relative; f32 probabilities
# may enter the MXU as bf16, adding about as much per term.  2e-2 leaves
# room for both while catching any masking or indexing error (O(1)).
KERNEL_ATOL = KERNEL_RTOL = 2e-2
TIMEOUT_S = 600.0


class CheckFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def served_config():
    cfg = dataclasses.replace(CONFIGS["llama3-8b"], n_layers=N_LAYERS)
    scfg = ServeConfig(
        max_batch=4,
        max_len=1024,
        max_new_tokens=MAX_NEW,
        cache_dtype="bfloat16",
        decode_chunk=8,
        prefill_bucket=512,  # every 64-512 token prompt shares one shape
    )
    return cfg, scfg


def make_prompts(seed: int, n: int, vocab: int):
    rng = np.random.default_rng(seed)
    return {
        f"req-{i:02d}": rng.integers(0, vocab, size=int(rng.integers(*PROMPT_LENS))).tolist()
        for i in range(n)
    }


class CompileClock:
    """Sums XLA backend compile time (persistent-cache hits do not count)."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.count = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += duration
                self.count += 1


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def serve_phase(engine, prompts, *, first_wave: int) -> dict:
    """Serve `prompts` through the request plane on one engine.

    The client submits `first_wave` requests, waits for the first streamed
    tokens, then submits the rest — so they arrive while the batch runs."""
    store, kv = ObjectStore(), KVStore(num_shards=2)
    ids = list(prompts)
    streamed: dict = {}
    client_errors: list = []

    def client() -> None:
        try:
            for r in ids[:first_wave]:
                rp.submit(store, kv, r, prompts[r], max_new_tokens=MAX_NEW)
            chunks = rp.stream_result(store, kv, ids[0], timeout_s=TIMEOUT_S)
            first = next(chunks)
            for r in ids[first_wave:]:
                rp.submit(store, kv, r, prompts[r], max_new_tokens=MAX_NEW)
            streamed[ids[0]] = first + [t for c in chunks for t in c]
            for r in ids[1:]:
                streamed[r] = [
                    t for c in rp.stream_result(store, kv, r, timeout_s=TIMEOUT_S)
                    for t in c
                ]
        except Exception as e:  # noqa: BLE001 — reported by the caller
            client_errors.append(e)

    t0 = time.perf_counter()
    th = threading.Thread(target=client, daemon=True)
    th.start()
    stats = engine.run(
        store, kv, engine_id="engine-0", idle_timeout_s=TIMEOUT_S,
        max_requests=len(ids),
    )
    th.join(TIMEOUT_S)
    wall = time.perf_counter() - t0
    check(not th.is_alive(), "client thread did not finish")
    check(not client_errors, f"client failed: {client_errors!r}")
    results = rp.get_results(store, ids, timeout_s=60.0)

    check(stats["admissions"] == len(ids), f"admissions {stats['admissions']} != {len(ids)}")
    check(stats["served"] == len(ids), f"served {stats['served']} != {len(ids)}")
    for r in ids:
        toks = results[r]["tokens"]
        check(len(toks) == MAX_NEW, f"{r}: {len(toks)} tokens, want {MAX_NEW}")
        check(streamed[r] == toks, f"{r}: stream differs from the published result")
    check(stats["mid_batch_admissions"] >= 1, "no request was admitted mid-batch")
    print(
        f"serve: ok requests={len(ids)} served_once={stats['served']} "
        f"tokens={stats['tokens_out']} mid_batch_admissions={stats['mid_batch_admissions']} "
        f"decode_steps={stats['decode_steps']} "
        f"stream==result wall_s={wall}",
        flush=True,
    )
    return {r: results[r]["tokens"] for r in ids}


def _kernel_names(compiled) -> set:
    txt = compiled.as_text()
    return set(re.findall(r"%(\w+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"", txt))


def programs_phase(engine) -> None:
    """The compiled serve programs call the Pallas kernels."""
    B = engine.scfg.max_batch
    toks = engine._put(np.zeros((B, 1), np.int32))
    lens = engine._put(np.full((B,), 7, np.int32))
    dec = _kernel_names(engine._decode.lower(engine.params, toks, engine.cache, lens).compile())
    Lpad = engine._pad_len(PROMPT_LENS[0])
    batch = {"tokens": engine._put(np.zeros((1, Lpad), np.int32))}
    pre = _kernel_names(
        engine._prefill.lower(engine.params, batch, engine._new_cache(1)).compile()
    )
    check("decode_attention" in dec, f"decode program kernels: {sorted(dec)}")
    check("flash_attention" in pre, f"prefill program kernels: {sorted(pre)}")
    print(f"programs: ok decode={sorted(dec)} prefill={sorted(pre)}", flush=True)


def _compare(name: str, out, exp, shape) -> None:
    out = np.asarray(out, np.float32)
    exp = np.asarray(exp, np.float32)
    check(out.shape == exp.shape, f"{name}: shape {out.shape} != {exp.shape}")
    check(bool(np.isfinite(out).all()), f"{name}: non-finite output")
    err = np.abs(out - exp)
    bound = KERNEL_ATOL + KERNEL_RTOL * np.abs(exp)
    worst = float((err / bound).max())
    check(worst <= 1.0, f"{name}: error {worst} x tolerance")
    print(
        f"kernel {name}: ok shape={shape} max_abs_err={float(err.max())} "
        f"max_err/tolerance={worst} tolerance=atol {KERNEL_ATOL} + rtol {KERNEL_RTOL}*|ref|",
        flush=True,
    )


def kernels_phase(cfg, scfg, seed: int) -> None:
    """Each kernel once at the served shapes, bf16 in, vs the f32 oracle."""
    H, K, D = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    B, S = scfg.max_batch, scfg.max_len
    Lpad = -(-(PROMPT_LENS[1] - 1) // scfg.prefill_bucket) * scfg.prefill_bucket
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)

    def rand(k, shape):
        return jax.random.normal(k, shape, jnp.float32).astype(jnp.bfloat16)

    def f32(*xs):
        return [x.astype(jnp.float32) for x in xs]

    q = rand(keys[0], (B, H, D))
    kc, vc = rand(keys[1], (B, S, K, D)), rand(keys[2], (B, S, K, D))
    clen = jnp.asarray(
        np.random.default_rng(seed).integers(1, S + 1, size=B), jnp.int32
    )
    out = jax.jit(decode_attention_pallas)(q, kc, vc, clen)
    with jax.default_matmul_precision("highest"):
        exp = ref.decode_attention_reference(*f32(q, kc, vc), clen)
    _compare("decode_attention", out, exp, (B, S, H, K, D))

    # the served prefill shape (one prompt at its bucket), then a length off
    # the 128 block (the padded and masked path)
    for j, L in enumerate((Lpad, PROMPT_LENS[0] + 37)):
        kq, kk, kv = jax.random.split(keys[3 + j], 3)
        q = rand(kq, (1, L, H, D))
        k, v = rand(kk, (1, L, K, D)), rand(kv, (1, L, K, D))
        out = jax.jit(flash_attention_pallas)(q, k, v)
        with jax.default_matmul_precision("highest"):
            exp = ref.mha_reference(*f32(q, k, v), causal=True)
        _compare("flash_attention", out, exp, (1, L, H, K, D))


def one_chip(seed: int, clock: CompileClock) -> None:
    cfg, scfg = served_config()
    total, _ = cfg.param_count()
    print(
        f"config: {cfg.name} d_model={cfg.d_model} heads={cfg.n_heads} "
        f"kv_heads={cfg.n_kv_heads} head_dim={cfg.hd} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} layers={cfg.n_layers}/{CONFIGS[cfg.name].n_layers} "
        f"params={total} dtype={cfg.param_dtype} max_batch={scfg.max_batch} "
        f"max_len={scfg.max_len} cache={scfg.cache_dtype}",
        flush=True,
    )
    t0 = time.perf_counter()
    engine = build_engine(cfg, scfg, seed=seed)
    print(f"build: engine warmed in {time.perf_counter() - t0} s", flush=True)

    prompts = make_prompts(seed, 6, cfg.vocab_size)
    serve_phase(engine, prompts, first_wave=3)
    programs_phase(engine)
    kernels_phase(cfg, scfg, seed)

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    check(peak is not None, "device reports no peak_bytes_in_use")
    print(
        f"memory: peak_bytes_in_use={peak} ({peak / 2**30} GiB) "
        f"bytes_limit={stats.get('bytes_limit')} compile_s={clock.seconds} "
        f"compiles={clock.count}",
        flush=True,
    )


# ---------------------------------------------------------------------------
# four chips: one engine per device, one process
# ---------------------------------------------------------------------------

def _devices_of(tree) -> set:
    return {d for leaf in jax.tree_util.tree_leaves(tree) for d in leaf.devices()}


def replicas_phase(engines, prompts) -> dict:
    """All engines, each in a thread, drain one queue; returns the results."""
    store, kv = ObjectStore(), KVStore(num_shards=2)
    ids = list(prompts)
    # queue everything first: each engine's first lease takes a full batch
    rp.submit_many(store, kv, prompts)
    errors: list = []

    def serve(i: int, e) -> None:
        try:
            e.run(store, kv, engine_id=f"engine-{i}", idle_timeout_s=5.0)
        except Exception as exc:  # noqa: BLE001 — reported below
            errors.append((i, exc))

    threads = [
        threading.Thread(target=serve, args=(i, e), daemon=True)
        for i, e in enumerate(engines)
    ]
    for t in threads:
        t.start()
    results = rp.get_results(store, ids, timeout_s=TIMEOUT_S)
    for t in threads:
        t.join(TIMEOUT_S)
    check(not any(t.is_alive() for t in threads), "an engine thread did not finish")
    check(not errors, f"engine failed: {errors!r}")
    by_engine: dict = {}
    for r in ids:
        by_engine[results[r]["engine"]] = by_engine.get(results[r]["engine"], 0) + 1
    for i, e in enumerate(engines):
        check(by_engine.get(f"engine-{i}", 0) >= 1, f"engine-{i} served nothing: {by_engine}")
    print(f"replicas: served per engine {dict(sorted(by_engine.items()))}", flush=True)
    return results


def four_chips(seed: int, clock: CompileClock) -> None:
    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, found {len(devices)}")
    cfg, scfg = served_config()
    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(devices)) as pool:
        futs = [pool.submit(build_engine, cfg, scfg, device=d, seed=seed) for d in devices]
        engines = [f.result() for f in futs]
    print(f"build: {len(engines)} engines warmed in {time.perf_counter() - t0} s", flush=True)
    for d, e in zip(devices, engines):
        on = _devices_of((e.params, e.cache))
        check(on == {d}, f"engine for {d} holds arrays on {on}")
    print(f"placement: ok engine i on {[str(d) for d in devices]}", flush=True)

    prompts = make_prompts(seed, 4 * len(devices), cfg.vocab_size)
    solo = serve_phase(engines[0], prompts, first_wave=scfg.max_batch - 1)
    multi = replicas_phase(engines, prompts)
    diff = {
        r: (multi[r]["engine"],
            next(i for i, (a, b) in enumerate(zip(multi[r]["tokens"], solo[r])) if a != b))
        for r in prompts if multi[r]["tokens"] != solo[r]
    }
    check(not diff, f"greedy tokens differ from the one-engine pass (request: engine, first index): {diff}")
    print(
        f"compare: ok {len(prompts)} requests, tokens identical to the "
        f"one-engine pass on {devices[0]}; compile_s={clock.seconds} "
        f"compiles={clock.count}",
        flush=True,
    )


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    ap.add_argument("--seed", type=int, default=0, help="weights, prompts, kernel inputs")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the multi-engine phase, one engine per chip")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"FAIL: no TPU: JAX found {dev.platform} ({dev.device_kind})", file=sys.stderr)
        return 2
    use_compile_cache()
    clock = CompileClock()
    try:
        if args.chips == 4:
            four_chips(args.seed, clock)
        else:
            one_chip(args.seed, clock)
    except CheckFailed as e:
        print(f"FAIL: {e}", flush=True)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
