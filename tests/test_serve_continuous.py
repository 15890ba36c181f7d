"""Continuous batching + lease-driven request plane.

The pins, straight from the PR contract:
  * a request arriving mid-decode is admitted at the next chunk boundary
    WITHOUT draining the running batch;
  * slot-cache isolation: a slot's new occupant never reads the previous
    occupant's KV;
  * parity: continuous batching emits exactly what the batch-synchronous
    `Engine.generate` emits for the same requests (greedy AND sampled);
  * leases: a lapsed lease is reaped and requeued exactly once; published
    results are never requeued;
  * SIGKILL one of two engines mid-stream: zero lost requests, zero
    duplicated/overwritten results (real subprocess, shared file backend).
"""

import json
import os
import re
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import CONFIGS
from repro.models import init_params
from repro.serve import ContinuousEngine, Engine, ServeConfig
from repro.serve import request_plane as rp
from repro.storage import FileBackend, FileKVStore, KVStore, ObjectStore

_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir, "src"))

_PARAMS = {}


def _setup(arch="qwen3-32b", **kw):
    cfg = CONFIGS[arch].reduced()
    if arch not in _PARAMS:
        _PARAMS[arch] = init_params(cfg, jax.random.PRNGKey(0))
    kw.setdefault("max_batch", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("max_new_tokens", 6)
    kw.setdefault("decode_chunk", 2)
    kw.setdefault("prefill_bucket", 8)
    scfg = ServeConfig(**kw)
    return cfg, _PARAMS[arch], scfg


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=n).tolist() for n in lens]


# ---------------------------------------------------------------------------
# slot engine semantics (no request plane)
# ---------------------------------------------------------------------------

def _aliased_params(compiled):
    """Parameter numbers the compiled program writes its outputs into."""
    head = compiled.as_text().split("\n", 1)[0]
    return {int(n) for n in re.findall(r"\((\d+), \{\}, (?:may|must)-alias\)", head)}


def test_decode_and_insert_update_the_cache_in_place():
    """The persistent cache is donated to `jit_decode` and `jit_insert`
    (each aliases it to its output), and a decode step writes each slot's
    row with a scatter, never with a masked rewrite: no `select` in the
    optimised program is shaped like a layer's K/V cache.  `jit_prefill`
    writes into the fresh one-row cache it is given the same way."""
    cfg, params, scfg = _setup()
    eng = ContinuousEngine(cfg, params, scfg)
    B = scfg.max_batch
    cache_params = len(jax.tree_util.tree_leaves(eng.cache))
    toks = eng._put(np.zeros((B, 1), np.int32))
    dec = eng._decode.lower(
        eng.params, toks, eng.cache, eng._put(np.zeros((B,), np.int32))
    ).compile()
    first = len(jax.tree_util.tree_leaves((eng.params, toks)))
    assert _aliased_params(dec) == set(range(first, first + cache_params))
    layer = ",".join(map(str, eng.cache["decoder"]["k"].shape[2:]))  # B,S,K,hd
    selects = [ln for ln in dec.as_text().splitlines() if " select(" in ln and f"{layer}]" in ln]
    assert not selects, selects
    ins = eng._insert.lower(eng.cache, eng._new_cache(1), eng._put(np.asarray([0]))).compile()
    assert _aliased_params(ins) == set(range(cache_params))
    batch = {"tokens": eng._put(np.zeros((1, scfg.prefill_bucket), np.int32))}
    pre = eng._prefill.lower(eng.params, batch, eng._new_cache(1)).compile()
    first = len(jax.tree_util.tree_leaves((eng.params, batch)))
    assert _aliased_params(pre) == set(range(first, first + cache_params))


def test_mid_stream_admission_without_draining():
    """A request admitted at a chunk boundary joins slots that are mid-
    decode; the running batch keeps its positions and is never drained."""
    cfg, params, scfg = _setup(max_new_tokens=10)
    eng = ContinuousEngine(cfg, params, scfg)
    pa, pb = _prompts(cfg, [5, 9])
    eng.admit([("a", pa, 10)])
    eng.step_chunk(2)
    a_slot = next(s for s in eng.slots if s is not None)
    a_pos = int(eng.cache_lens[eng.slots.index(a_slot)])
    assert len(a_slot.out) == 3  # 1 at admit + 2 decode steps
    # b arrives mid-decode: admitted into a free slot, a is untouched
    eng.admit([("b", pb, 10)])
    assert eng.stats["mid_batch_admissions"] == 1
    assert eng.n_live() == 2
    assert len(a_slot.out) == 3  # no drain, no re-prefill
    assert int(eng.cache_lens[eng.slots.index(a_slot)]) == a_pos
    finished = {}
    for _ in range(20):
        done, _ = eng.step_chunk()
        finished.update({r: s.out for r, s in done.items()})
        if len(finished) == 2:
            break
    # both complete, and both match the batch-synchronous reference
    ref = Engine(cfg, params, scfg)
    for rid, prompt in (("a", pa), ("b", pb)):
        exp = ref.generate(jnp.asarray([prompt], jnp.int32))[0].tolist()
        assert finished[rid] == exp, rid


def test_slot_reuse_never_reads_prior_occupants_kv():
    """Serve a long-prompt request, then a short one through the SAME slot:
    the short request's output must equal a fresh single-request run (the
    insert replaces the slot's cache rows wholesale)."""
    cfg, params, scfg = _setup(max_batch=1)
    eng = ContinuousEngine(cfg, params, scfg)
    long_p, short_p = _prompts(cfg, [40, 4], seed=3)
    eng.admit([("long", long_p, 6)])
    while eng.n_live():
        eng.step_chunk()
    eng.admit([("short", short_p, 6)])
    out = {}
    while eng.n_live():
        done, _ = eng.step_chunk()
        out.update({r: s.out for r, s in done.items()})
    fresh = ContinuousEngine(cfg, params, scfg)
    fresh.admit([("short", short_p, 6)])
    exp = {}
    while fresh.n_live():
        done, _ = fresh.step_chunk()
        exp.update({r: s.out for r, s in done.items()})
    assert out["short"] == exp["short"]


@pytest.mark.parametrize("arch", ["qwen3-32b", "deepseek-v3-671b", "xlstm-1.3b"])
def test_parity_with_batch_synchronous_generate(arch):
    """Mixed-length requests served continuously == per-request generate
    (which left-pads nothing at B=1).  Covers dense/GQA, MoE/MLA latent
    caches, and recurrent-state (exact-length prefill groups) families."""
    cfg, params, scfg = _setup(arch)
    eng = ContinuousEngine(cfg, params, scfg)
    store, kv = ObjectStore(), KVStore(num_shards=2)
    prompts = _prompts(cfg, [3, 11, 7, 5, 9], seed=1)
    for i, p in enumerate(prompts):
        rp.submit(store, kv, f"r{i}", p)
    eng.run(store, kv, engine_id="e0", idle_timeout_s=0.3)
    ref = Engine(cfg, params, scfg)
    res = rp.get_results(store, [f"r{i}" for i in range(len(prompts))], timeout_s=5)
    for i, p in enumerate(prompts):
        exp = ref.generate(jnp.asarray([p], jnp.int32))[0].tolist()
        assert res[f"r{i}"]["tokens"] == exp, f"r{i}"


def test_sampled_decode_per_request_deterministic_and_independent():
    cfg, params, scfg = _setup(temperature=0.8)
    store, kv = ObjectStore(), KVStore(num_shards=2)
    prompt = _prompts(cfg, [6], seed=5)[0]
    eng = ContinuousEngine(cfg, params, scfg)
    # same prompt, two ids -> independent streams
    rp.submit(store, kv, "x", prompt)
    rp.submit(store, kv, "y", prompt)
    eng.run(store, kv, engine_id="e0", idle_timeout_s=0.3)
    res = rp.get_results(store, ["x", "y"], timeout_s=5)
    assert res["x"]["tokens"] != res["y"]["tokens"]
    # re-serving the same id (fresh engine) replays the identical stream
    store2, kv2 = ObjectStore(), KVStore(num_shards=2)
    rp.submit(store2, kv2, "x", prompt)
    eng2 = ContinuousEngine(cfg, params, scfg)
    eng2.run(store2, kv2, engine_id="other", idle_timeout_s=0.3)
    assert store2.get(rp.done_key("x"))["tokens"] == res["x"]["tokens"]
    # and the batch-synchronous engine agrees when keyed the same way
    ref = Engine(cfg, params, scfg)
    exp = ref.generate(
        jnp.asarray([prompt], jnp.int32), seeds=[rp.request_seed("x")]
    )[0].tolist()
    assert res["x"]["tokens"] == exp


def test_streaming_chunks_arrive_before_completion():
    cfg, params, scfg = _setup(max_new_tokens=8, decode_chunk=2)
    eng = ContinuousEngine(cfg, params, scfg)
    store, kv = ObjectStore(), KVStore(num_shards=2)
    rp.submit(store, kv, "s", _prompts(cfg, [5])[0])
    leased = rp.lease_requests(store, kv, "e0", 1)
    eng.admit([(r, b["prompt"], 8) for r, b in leased])
    done, chunks = eng.step_chunk()
    rp.stream_chunks(kv, chunks, worker="e0")
    assert not done  # still mid-stream...
    assert kv.lrange(rp.stream_key("s")) == [{"off": 0, "toks": chunks["s"][1]}]
    while eng.n_live():
        done, chunks = eng.step_chunk()
        rp.stream_chunks(kv, chunks, worker="e0")
    rp.publish_results(store, kv, "e0", {r: {"tokens": s.out} for r, s in done.items()})
    # the streamed chunks concatenate to the published result, exactly once
    seen = [t for c in kv.lrange(rp.stream_key("s")) if "off" in c for t in c["toks"]]
    assert seen == store.get(rp.done_key("s"))["tokens"]


# ---------------------------------------------------------------------------
# request plane: leases, reaping
# ---------------------------------------------------------------------------

def test_lease_lapse_reaped_and_requeued_exactly_once():
    store, kv = ObjectStore(), KVStore(num_shards=2)
    rp.submit(store, kv, "r0", [1, 2, 3])
    leased = rp.lease_requests(store, kv, "dead", 4, lease_timeout_s=0.05)
    assert [r for r, _ in leased] == ["r0"]
    assert kv.llen(rp.queue_key(0)) == 0
    time.sleep(0.06)  # the lease lapses (its engine is "dead")
    assert rp.reap_expired(store, kv) == 1
    assert rp.reap_expired(store, kv) == 0  # exactly once
    relea = rp.lease_requests(store, kv, "alive", 4)
    assert [r for r, _ in relea] == ["r0"]
    rec = kv.mget([rp.lease_key("r0")])[0]
    assert rec["engine"] == "alive" and rec["term"] == 2  # re-serve = new term


def test_reap_drops_already_published_results():
    store, kv = ObjectStore(), KVStore(num_shards=2)
    rp.submit(store, kv, "r0", [1, 2])
    rp.lease_requests(store, kv, "e0", 4, lease_timeout_s=0.05)
    rp.publish_results(store, kv, "e0", {"r0": {"tokens": [7]}})
    time.sleep(0.06)
    assert rp.reap_expired(store, kv) == 0  # published: nothing to requeue
    assert kv.llen(rp.queue_key(0)) == 0
    # ...and a queue replay of a served id is consumed without re-leasing
    kv.rpush(rp.queue_key(0), "r0")
    assert rp.lease_requests(store, kv, "e1", 4) == []


def test_live_lease_blocks_other_engines():
    store, kv = ObjectStore(), KVStore(num_shards=2)
    rp.submit(store, kv, "r0", [1])
    assert len(rp.lease_requests(store, kv, "e0", 4, lease_timeout_s=30.0)) == 1
    kv.rpush(rp.queue_key(0), "r0")  # duplicate enqueue (e.g. double reap)
    assert rp.lease_requests(store, kv, "e1", 4) == []  # e0 still owns it
    rp.heartbeat_leases(kv, "e0", ["r0"], lease_timeout_s=30.0)
    rec = kv.mget([rp.lease_key("r0")])[0]
    assert rec["engine"] == "e0"


# ---------------------------------------------------------------------------
# SIGKILL one of two engines: zero lost, zero duplicated
# ---------------------------------------------------------------------------

_ENGINE_SCRIPT = r"""
import sys, time
import jax
from repro.configs import CONFIGS
from repro.models import init_params
from repro.serve import ContinuousEngine, ServeConfig
from repro.serve import request_plane as rp
from repro.storage import FileBackend, FileKVStore, ObjectStore

kv_root, obj_root, engine_id = sys.argv[1], sys.argv[2], sys.argv[3]
kv = FileKVStore(kv_root, num_shards=2)
store = ObjectStore(backend=FileBackend(obj_root))
cfg = CONFIGS["qwen3-32b"].reduced()
params = init_params(cfg, jax.random.PRNGKey(0))
scfg = ServeConfig(max_batch=2, max_len=64, max_new_tokens=12,
                   decode_chunk=1, lease_timeout_s=1.0)
eng = ContinuousEngine(cfg, params, scfg)
print("READY", flush=True)
# Throttled serve loop (one decode step per tick) so the parent can land a
# SIGKILL while requests are demonstrably mid-stream with live leases.
while True:
    free = eng.free_slots()
    if free:
        leased = rp.lease_requests(store, kv, engine_id, len(free),
                                   lease_timeout_s=1.0, wait_s=0.2)
        if leased:
            eng.admit([(r, b["prompt"], int(b.get("max_new", 12)))
                       for r, b in leased])
    if eng.n_live() == 0:
        continue
    finished, chunks = eng.step_chunk(1)
    rp.stream_chunks(kv, chunks, worker=engine_id)
    rp.heartbeat_leases(kv, engine_id, eng.live_req_ids(), lease_timeout_s=1.0)
    if finished:
        rp.publish_results(store, kv, engine_id,
                           {r: {"tokens": s.out} for r, s in finished.items()})
    time.sleep(0.12)
"""


def test_sigkill_engine_zero_lost_zero_duplicated(tmp_path):
    kv_root, obj_root = str(tmp_path / "kv"), str(tmp_path / "obj")
    kv = FileKVStore(kv_root, num_shards=2)
    store = ObjectStore(backend=FileBackend(obj_root))
    cfg = CONFIGS["qwen3-32b"].reduced()
    ids = [f"k{i}" for i in range(6)]
    prompts = _prompts(cfg, [4, 7, 5, 9, 6, 3], seed=11)
    for r, p in zip(ids, prompts):
        rp.submit(store, kv, r, p)

    env = dict(os.environ, PYTHONPATH=_SRC)
    proc = subprocess.Popen(
        [sys.executable, "-c", _ENGINE_SCRIPT, kv_root, obj_root, "victim"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    try:
        assert proc.stdout.readline().strip() == "READY"
        # kill once >=1 result is published but in-flight work remains
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            done = store.exists_many([rp.done_key(r) for r in ids])
            if 1 <= len(done) < len(ids):
                break
            time.sleep(0.05)
        else:
            pytest.fail("victim engine never reached a mid-stream state")
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()

    survivors_before = {
        k: store.get(k) for k in store.exists_many([rp.done_key(r) for r in ids])
    }
    assert survivors_before and len(survivors_before) < len(ids)

    # the second engine reaps the victim's lapsed leases and finishes
    params = init_params(cfg, jax.random.PRNGKey(0))
    scfg = ServeConfig(max_batch=2, max_len=64, max_new_tokens=12,
                       decode_chunk=1, lease_timeout_s=1.0)
    eng_b = ContinuousEngine(cfg, params, scfg)
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        eng_b.run(store, kv, engine_id="survivor", idle_timeout_s=3.0)
        if len(store.exists_many([rp.done_key(r) for r in ids])) == len(ids):
            break
    res = rp.get_results(store, ids, timeout_s=10)

    # zero lost: every request has a result, and it is the correct one
    ref = Engine(cfg, params, scfg)
    for r, p in zip(ids, prompts):
        exp = ref.generate(jnp.asarray([p], jnp.int32))[0].tolist()
        assert res[r]["tokens"] == exp, r
    # zero duplicated: the victim's published results were not overwritten
    # by the survivor's replay (first-writer-wins pinned via the engine tag)
    for k, rec in survivors_before.items():
        now = store.get(k)
        assert now["engine"] == rec["engine"] == "victim", k
        assert now["tokens"] == rec["tokens"], k
    assert eng_b.stats["served"] >= 1
    kv.close()


# ---------------------------------------------------------------------------
# one process, one engine per device
# ---------------------------------------------------------------------------

_MULTI_DEVICE_SCRIPT = r"""
import json, threading
import jax
import numpy as np
from repro.configs import CONFIGS
from repro.launch.serve import build_engine
from repro.serve import ServeConfig
from repro.serve import request_plane as rp
from repro.storage import KVStore, ObjectStore

cfg = CONFIGS["qwen3-32b"].reduced()
scfg = ServeConfig(max_batch=2, max_len=64, max_new_tokens=6, decode_chunk=2,
                   prefill_bucket=8)
devices = jax.devices()
assert len(devices) == 4, devices
engines = [build_engine(cfg, scfg, device=d) for d in devices]
for d, e in zip(devices, engines):
    held = {x for a in jax.tree_util.tree_leaves((e.params, e.cache)) for x in a.devices()}
    assert held == {d}, (d, held)
rng = np.random.default_rng(0)
prompts = {f"r{i}": rng.integers(0, cfg.vocab_size, size=int(rng.integers(3, 8))).tolist()
           for i in range(8)}

def serve(engs):
    store, kv = ObjectStore(), KVStore(num_shards=1)
    threads = [threading.Thread(target=e.run, args=(store, kv),
                                kwargs=dict(engine_id=f"e{i}", idle_timeout_s=2.0))
               for i, e in enumerate(engs)]
    for t in threads:
        t.start()
    rp.submit_many(store, kv, prompts)  # engines are parked in blpop
    res = rp.get_results(store, list(prompts), timeout_s=120)
    for t in threads:
        t.join(60)
        assert not t.is_alive()
    return res

solo = serve(engines[:1])
multi = serve(engines)
assert all(multi[r]["tokens"] == solo[r]["tokens"] for r in prompts)
print(json.dumps(sorted({multi[r]["engine"] for r in prompts})))
"""


def test_engines_one_per_device_match_one_engine():
    """Four engines in one process, one per (virtual) device, drain one
    queue: each holds its arrays on its own device, and greedy tokens equal
    the one-engine pass (per-request determinism across devices)."""
    env = dict(
        os.environ, PYTHONPATH=_SRC, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    out = subprocess.run(
        [sys.executable, "-c", _MULTI_DEVICE_SCRIPT],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    engines = json.loads(out.stdout.strip().splitlines()[-1])  # who served
    assert len(engines) >= 2, engines
