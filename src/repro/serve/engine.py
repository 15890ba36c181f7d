"""Batched serving engine: prefill + decode with KV-cache management.

Requests flow through the object store (PyWren style): clients `submit`
prompts as objects; the engine leases batches, prefills, decodes with a
jitted single-token step, and publishes results atomically.  The engine
itself is a stateless function over (model version, request batch): kill it
mid-stream and a restart re-serves the batch idempotently.

Serving modes:
  * `generate`: greedy/temperature sampling for N steps (batch-synchronous
    continuous batching-lite: finished rows are masked, new rows join at
    chunk boundaries);
  * `serve_step` export for the dry-run: the one-token decode step lowered
    at (arch x decode shape).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import decode_step, init_cache, prefill
from repro.storage import ObjectStore


@dataclass
class ServeConfig:
    max_batch: int = 8
    max_len: int = 256
    max_new_tokens: int = 32
    temperature: float = 0.0  # 0 = greedy
    cache_dtype: str = "float32"
    eos_id: int = -1  # -1 = never stop early
    # ---- continuous batching / request plane (serve.continuous) ----
    decode_chunk: int = 8  # decode steps between admission boundaries
    prefill_bucket: int = 16  # right-pad prompts up to a multiple of this
    n_queues: int = 1  # request-queue shards (serve/q/{i})
    lease_timeout_s: float = 2.0
    heartbeat_interval_s: float = 0.5


def sample_tokens(
    logits: jnp.ndarray,  # (B, V)
    keys: Optional[jnp.ndarray],  # (B, 2) uint32 per-request PRNG keys
    steps,  # scalar or (B,) int32: per-request decode step index
    temperature: float,
) -> jnp.ndarray:
    """Per-row sampling: row i draws from fold_in(keys[i], steps[i]).

    Keying by (request, step) — not by engine-global state — is what makes
    sampling deterministic per request, independent across requests, and
    invariant to batch composition: the same request produces the same
    stream whether it decodes alone, in a full batch, or on the engine
    that re-serves it after a SIGKILL."""
    if temperature <= 0 or keys is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    B = logits.shape[0]
    steps = jnp.broadcast_to(jnp.asarray(steps, jnp.uint32), (B,))

    def one(k, s, row):
        return jax.random.categorical(jax.random.fold_in(k, s), row / temperature)

    return jax.vmap(one)(keys, steps, logits).astype(jnp.int32)


def request_keys(seeds) -> jnp.ndarray:
    """(B, 2) uint32 key array from per-request integer seeds."""
    return jnp.asarray(np.stack([np.asarray(jax.random.PRNGKey(int(s))) for s in seeds]))


class Engine:
    def __init__(self, cfg: ModelConfig, params: Any, scfg: ServeConfig) -> None:
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        self._decode = jax.jit(lambda p, t, c, l: decode_step(p, cfg, t, c, l), donate_argnums=2)
        self._prefill = jax.jit(lambda p, b, c: prefill(p, cfg, b, c))

    # ---- batch generation ------------------------------------------------
    def generate(
        self,
        prompts: jnp.ndarray,
        extras: Optional[Dict[str, jnp.ndarray]] = None,
        *,
        seeds: Optional[List[int]] = None,
    ) -> np.ndarray:
        """prompts: (B, S) int32 -> (B, max_new_tokens) int32.

        ``seeds`` (one per row, e.g. `request_plane.request_seed(req_id)`)
        key the sampling stream per request: deterministic per request,
        independent across requests.  Default `range(B)` — previously every
        row of every batch shared one fixed PRNGKey(0) stream."""
        B, S = prompts.shape
        scfg = self.scfg
        dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[scfg.cache_dtype]
        cache = init_cache(self.cfg, B, scfg.max_len, cache_dtype=dtype)
        batch = {"tokens": prompts}
        if extras:
            batch.update(extras)
        logits, cache, clen = self._prefill(self.params, batch, cache)

        keys = None
        if scfg.temperature > 0:
            keys = request_keys(range(B) if seeds is None else seeds)
        out = np.zeros((B, scfg.max_new_tokens), np.int32)
        done = np.zeros((B,), bool)
        tok = sample_tokens(logits[:, -1], keys, 0, scfg.temperature)
        for t in range(scfg.max_new_tokens):
            out[:, t] = np.where(done, 0, np.asarray(tok))
            if scfg.eos_id >= 0:
                done |= np.asarray(tok) == scfg.eos_id
                if done.all():
                    break
            logits, cache = self._decode(self.params, tok[:, None], cache, clen)
            clen = clen + 1
            tok = sample_tokens(logits[:, 0], keys, t + 1, scfg.temperature)
        return out


# ---------------------------------------------------------------------------
# storage-mediated request plane (the PyWren pattern)
# ---------------------------------------------------------------------------

def submit_request(store: ObjectStore, req_id: str, prompt: List[int]) -> str:
    key = f"serve/req/{req_id}"
    store.put(key, {"prompt": prompt, "ts": time.time()})
    return key


def serve_pending(
    store: ObjectStore, engine: Engine, *, batch_size: int = 8, worker: str = "engine"
) -> int:
    """Lease pending requests, serve a batch, publish results atomically.
    Returns number served.  Idempotent: results publish with put_if_absent.

    Batched control plane end to end: one list + one ``exists_many``
    filters out already-served requests, one ``get_many`` fetches the
    batch, and the whole result set publishes in one
    ``put_many(if_absent=True)`` — per-key first-writer-wins semantics
    are unchanged, but N requests cost a handful of amortized
    round-trips instead of ~3N."""
    def _done_key(k: str) -> str:
        return k.replace("serve/req/", "serve/done/")

    all_reqs = store.list("serve/req/", worker=worker)
    served = store.exists_many([_done_key(k) for k in all_reqs], worker=worker)
    req_keys = [k for k in all_reqs if _done_key(k) not in served][:batch_size]
    if not req_keys:
        return 0
    got = store.get_many(req_keys, worker=worker, missing="error")
    reqs = [got[k] for k in req_keys]
    maxlen = max(len(r["prompt"]) for r in reqs)
    prompts = np.zeros((len(reqs), maxlen), np.int32)
    for i, r in enumerate(reqs):
        prompts[i, maxlen - len(r["prompt"]):] = r["prompt"]  # left-pad
    out = engine.generate(jnp.asarray(prompts))
    store.put_many(
        {_done_key(k): {"tokens": out[i].tolist()} for i, k in enumerate(req_keys)},
        worker=worker,
        if_absent=True,
    )
    return len(reqs)
