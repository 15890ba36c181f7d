"""The engine measures itself: counters in `stats`, `serve.*` spans while a
profiler session is on, and stable names for its jitted programs."""

import glob

import jax
import numpy as np
import pytest

from repro.configs import CONFIGS
from repro.models import init_params
from repro.serve import ContinuousEngine, ServeConfig
from repro.serve import request_plane as rp
from repro.serve.tracing import tracer
from repro.storage import KVStore, ObjectStore

N_REQ = 4
SPANS = (
    "serve.reap", "serve.lease", "serve.admit", "serve.prefill", "serve.first_token",
    "serve.chunk", "serve.decode", "serve.readback", "serve.stream", "serve.publish",
    "serve.heartbeat",
)


@pytest.fixture(scope="module")
def engine():
    cfg = CONFIGS["qwen3-32b"].reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    scfg = ServeConfig(max_batch=3, max_len=64, max_new_tokens=5, decode_chunk=2,
                       prefill_bucket=8)
    eng = ContinuousEngine(cfg, params, scfg)
    eng.warm()
    return eng


def _serve(eng, tag):
    """Submit N_REQ requests (more than the slots) and serve them all."""
    for k in eng.stats:
        eng.stats[k] = 0
    store, kv = ObjectStore(), KVStore(num_shards=2)
    rng = np.random.default_rng(0)
    ids = [f"{tag}-{i}" for i in range(N_REQ)]
    for r in ids:
        rp.submit(store, kv, r, rng.integers(0, 100, size=6).tolist(), max_new_tokens=5)
    stats = eng.run(store, kv, engine_id="e-trace", idle_timeout_s=0.2, max_requests=N_REQ)
    return ids, stats


def test_counters_count_every_request(engine):
    before = len(tracer.records())
    _, stats = _serve(engine, "plain")
    assert stats["served"] == N_REQ
    assert stats["leased"] == stats["admissions"] == N_REQ
    assert stats["first_tokens_streamed"] == N_REQ
    for k in ("lease_wait_ns", "first_token_hold_ns", "decode_host_ns", "decode_steps"):
        assert stats[k] > 0, k
    # with no profiler session no span is recorded
    assert len(tracer.records()) == before


def test_spans_nest_and_carry_request_ids(engine, tmp_path):
    before = len(tracer.records())
    jax.profiler.start_trace(str(tmp_path))
    try:
        ids, stats = _serve(engine, "traced")
    finally:
        jax.profiler.stop_trace()
    recs = tracer.records()[before:]
    assert stats["served"] == N_REQ
    assert {r.name for r in recs} == set(SPANS)
    assert len({r.span_id for r in recs}) == len(recs)
    assert all(r.engine_id == "e-trace" and r.start_ns <= r.end_ns for r in recs)
    by_id = {r.span_id: r for r in recs}

    def parent(r):
        return by_id[r.parent_id].name if r.parent_id is not None else None

    for r in recs:
        want = {
            "serve.readback": "serve.decode",
            "serve.decode": "serve.chunk",
            "serve.prefill": "serve.admit",
            "serve.first_token": "serve.prefill",
        }.get(r.name)
        if want is not None:
            assert parent(r) == want, r
            p = by_id[r.parent_id]
            assert p.start_ns <= r.start_ns <= r.end_ns <= p.end_ns
        elif r.name in ("serve.chunk", "serve.admit", "serve.lease", "serve.stream"):
            assert r.parent_id is None, r
    prefills = [r for r in recs if r.name == "serve.prefill"]
    assert sorted(r.req for r in prefills) == sorted(ids)
    firsts = {r.req for r in recs if r.name == "serve.first_token"}
    assert firsts == set(ids)
    leased = [q for r in recs if r.name == "serve.lease" for q in r.req]
    assert sorted(leased) == sorted(ids)
    assert sorted(q for r in recs if r.name == "serve.publish" for q in r.req) == sorted(ids)
    assert sum(r.name == "serve.decode" for r in recs) == stats["decode_steps"]
    # the same spans sit in the profiler's own trace, on its host plane
    from jax.profiler import ProfileData

    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {
        ev.name
        for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host")
        for line in plane.lines for ev in line.events
    }
    assert set(SPANS) <= names


def _lowered(engine, name):
    cache1 = engine._new_cache(1)
    toks = engine._put(np.zeros((1, 8), np.int32))
    B = engine.scfg.max_batch
    return {
        "decode": lambda: engine._decode.lower(
            engine.params, engine._put(np.zeros((B, 1), np.int32)), engine.cache,
            engine._put(np.zeros((B,), np.int32))),
        "prefill": lambda: engine._prefill.lower(engine.params, {"tokens": toks}, cache1),
        "new_cache": lambda: engine._new_cache.lower(1),
        "insert": lambda: engine._insert.lower(
            engine.cache, cache1, engine._put(np.asarray([0]))),
    }[name]()


@pytest.mark.parametrize("name", ["decode", "prefill", "new_cache", "insert"])
def test_jitted_programs_have_stable_names(engine, name):
    text = _lowered(engine, name).as_text()
    assert f"module @jit_{name} " in text
    assert "jit__lambda" not in text
