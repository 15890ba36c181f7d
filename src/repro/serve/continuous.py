"""Continuous batching: a persistent slot-based decode batch.

The engine owns ONE cache of `max_batch` slots for its whole life.  Every
iteration runs a single jitted one-token decode step over all slots — live
or not — with a per-slot `cache_len` vector (the decode kernels mask
variable lengths, so prompts are never left-padded to a common length).
Finished rows are evicted immediately; freed slots are refilled at chunk
boundaries by interleaved prefills: each new prompt prefills alone into a
fresh one-row cache which is scattered into the persistent one with
`cache_update.insert_rows` (whole-row replacement — a new occupant can
never read its predecessor's KV).  The running batch never drains.

Shapes are jit-stable by construction: the decode step always sees
(max_batch, 1) tokens against the (max_batch, …) cache, so it compiles
exactly once; prefill compiles once per bucketed prompt length.  A prompt
never shares a prefill program with others: on a TPU, prompts prefilled in
groups of different sizes came out with different greedy tokens (the
programs for n and m rows round differently, and with bf16 logits a near
tie flips the argmax).  One program per bucket keeps every request's tokens
a function of the request alone, which is what lets a peer re-serve it
byte-identically.

`ContinuousEngine.run` plugs the slot machinery into the lease-driven
request plane (`serve.request_plane`): lease -> admit -> decode chunk ->
stream -> publish, with lease heartbeats and expired-lease reaping riding
the chunk cadence.

The loop measures itself.  `stats` keeps counters that are always on: the
requests leased and their summed wait from submit to lease
(`lease_wait_ns`), the first tokens streamed and their summed hold from
sampling to the return of the push that carried them
(`first_token_hold_ns`), and the engine thread's time per decode step
outside the token read-back (`decode_host_ns`, over `decode_steps`).
While a profiler session is on, the loop also opens `serve.*` spans
(`serve.tracing`); docs/ARCHITECTURE.md lists them and how they nest.

An engine serves on one device: its params, persistent cache and every
prefill's cache live there.  Several engines in one process, one per
device, share a queue like engines in separate processes do.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ModelConfig
from repro.models import cache_batch_axes, decode_step, init_cache
from repro.models import prefill as model_prefill
from repro.models.cache_update import insert_rows

from . import request_plane as rp
from .engine import ServeConfig, request_keys, sample_tokens
from .tracing import tracer


@dataclass
class Slot:
    req_id: str
    prompt_len: int
    max_new: int
    out: List[int] = field(default_factory=list)  # sampled tokens so far
    streamed: int = 0  # tokens already pushed to serve/stream/{req}
    done: bool = False
    t_first: float = 0.0  # wall time of the first sampled token (TTFT)
    t_first_ns: int = 0  # the same instant on time.perf_counter_ns


class ContinuousEngine:
    """Slot-based continuous-batching engine over one persistent cache."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        scfg: ServeConfig,
        device: Optional[jax.Device] = None,
    ) -> None:
        if cfg.family == "encdec":
            raise NotImplementedError("encdec serving needs encoder inputs per request")
        self.cfg = cfg
        self.device = jax.devices()[0] if device is None else device
        self.params = jax.device_put(params, self.device)
        self.scfg = scfg
        self._dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[scfg.cache_dtype]
        # recurrent-state families carry prompt state, not a masked KV
        # buffer: right-pad tokens would corrupt the state, so their
        # prompts prefill at *exact* length instead of a bucket.
        self._exact_len = cfg.family in ("ssm", "hybrid")

        # named functions: the programs are jit_decode, jit_prefill,
        # jit_new_cache and jit_insert in a profile
        def decode(p, t, c, l):
            return decode_step(p, cfg, t, c, l)

        def prefill(p, b, c):
            return model_prefill(p, cfg, b, c, all_logits=True)

        def new_cache(n):
            return init_cache(cfg, n, scfg.max_len, cache_dtype=self._dtype)

        axes = cache_batch_axes(cfg, scfg.max_len, self._dtype)

        def insert(big, small, slots):
            return jax.tree_util.tree_map(
                lambda b, s, ax: insert_rows(b, s, slots, ax), big, small, axes
            )

        # caches are donated: a decode step writes each slot's new rows into
        # the persistent cache in place, an insert only the slots it fills,
        # and a prefill its prompt's rows into the fresh one-row cache
        self._decode = jax.jit(decode, donate_argnums=2)
        self._prefill = jax.jit(prefill, donate_argnums=2)
        # caches are built in place on the device (no host or device-0 copy)
        self._new_cache = jax.jit(
            new_cache, static_argnums=0, out_shardings=SingleDeviceSharding(self.device)
        )
        self._insert = jax.jit(insert, donate_argnums=0)
        self.engine_id: Optional[str] = None  # set by `run`; tags spans

        B = scfg.max_batch
        self.cache = self._new_cache(B)
        self.cache_lens = np.zeros((B,), np.int32)
        self.tokens = np.zeros((B,), np.int32)  # next token fed per slot
        self.steps = np.zeros((B,), np.int32)  # per-request sample index
        self.keys = np.zeros((B, 2), np.uint32)  # per-request PRNG keys
        self.slots: List[Optional[Slot]] = [None] * B
        self.stats: Dict[str, int] = {
            "served": 0,
            "tokens_out": 0,
            "admissions": 0,
            "mid_batch_admissions": 0,
            "decode_steps": 0,
            "leased": 0,
            "lease_wait_ns": 0,
            "first_tokens_streamed": 0,
            "first_token_hold_ns": 0,
            "decode_host_ns": 0,
        }

    def _span(self, name: str, **kw):
        return tracer.span(name, engine_id=self.engine_id, **kw)

    def _put(self, host_array: np.ndarray) -> jax.Array:
        return jax.device_put(host_array, self.device)

    # ---- slot bookkeeping ------------------------------------------------

    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def n_live(self) -> int:
        return sum(s is not None for s in self.slots)

    def live_req_ids(self) -> List[str]:
        return [s.req_id for s in self.slots if s is not None]

    def _evict(self, i: int) -> None:
        self.slots[i] = None
        self.cache_lens[i] = 0
        self.tokens[i] = 0
        self.steps[i] = 0
        self.keys[i] = 0

    # ---- admission: interleaved prefills ---------------------------------

    def _pad_len(self, plen: int) -> int:
        if self._exact_len:
            return plen
        b = max(1, self.scfg.prefill_bucket)
        return min(-(-plen // b) * b, self.scfg.max_len - 1)

    def admit(self, requests: Sequence[Tuple[str, Sequence[int], int]]) -> int:
        """Admit requests into free slots: [(req_id, prompt, max_new), ...].

        Runs at chunk boundaries while other slots hold live decodes — the
        running batch is untouched (their rows of the persistent cache are
        not written by `insert_rows`).  Each admitted slot samples its
        first token here, from the prefill logits at its own true last
        prompt position (right-padding is invisible under causal
        attention).  Returns the number admitted."""
        free = self.free_slots()
        if len(requests) > len(free):
            raise ValueError(f"admit {len(requests)} > {len(free)} free slots")
        if not requests:
            return 0
        was_live = self.n_live() > 0
        with self._span("serve.admit", n=len(requests)):
            for req_id, prompt, max_new in requests:
                with self._span("serve.prefill", req=req_id):
                    self._admit_one(free.pop(0), req_id, prompt, max_new)
        self.stats["admissions"] += len(requests)
        if was_live:
            self.stats["mid_batch_admissions"] += len(requests)
        return len(requests)

    def _admit_one(self, i: int, req_id: str, prompt: Sequence[int], max_new: int) -> None:
        """Prefill one prompt alone, insert it into slot `i` and sample its
        first token."""
        scfg = self.scfg
        prompt = list(prompt)[: scfg.max_len - 1]  # leave room to decode
        n_tok = len(prompt)
        toks = np.zeros((1, self._pad_len(n_tok)), np.int32)
        toks[0, :n_tok] = prompt
        logits_all, small, _ = self._prefill(
            self.params, {"tokens": self._put(toks)}, self._new_cache(1)
        )
        last = logits_all[:, n_tok - 1]  # (1, V) at the last true token
        self.cache = self._insert(self.cache, small, self._put(np.asarray([i])))
        keys = None
        if scfg.temperature > 0:
            keys = request_keys([rp.request_seed(req_id)])
        sampled = sample_tokens(last, keys, 0, scfg.temperature)
        with self._span("serve.first_token", req=req_id):
            tok0 = int(np.asarray(sampled)[0])
        s = Slot(req_id, n_tok, max_new, t_first=time.time(), t_first_ns=time.perf_counter_ns())
        s.out.append(tok0)
        if len(s.out) >= max_new or (scfg.eos_id >= 0 and tok0 == scfg.eos_id):
            s.done = True
        self.slots[i] = s
        self.cache_lens[i] = n_tok
        self.tokens[i] = tok0
        self.steps[i] = 1
        if keys is not None:
            self.keys[i] = np.asarray(keys[0])

    def warm(self) -> None:
        """Compile decode, prefill at the first prompt bucket and the slot
        insert before any request is leased: a compile inside `run` would
        stall heartbeats past the lease timeout.  Resets the stats."""
        self.admit([("warm", [1, 2, 3], 2)])
        while self.n_live():
            self.step_chunk()
        for k in self.stats:
            self.stats[k] = 0

    # ---- the decode chunk ------------------------------------------------

    def step_chunk(
        self, n_steps: Optional[int] = None
    ) -> Tuple[Dict[str, Slot], Dict[str, Tuple[int, List[int]]]]:
        """Run up to `n_steps` jitted decode iterations over all slots.

        Returns (finished, chunks): finished maps req_id -> its Slot
        (evicted, `out` complete); chunks maps req_id -> (offset, new
        tokens since last stream push) for every slot that progressed —
        the stream payloads for `request_plane.stream_chunks`."""
        scfg = self.scfg
        n_steps = scfg.decode_chunk if n_steps is None else n_steps
        finished: Dict[str, Slot] = {}
        touched: List[Slot] = []

        def _finish(i: int, s: Slot) -> None:
            finished[s.req_id] = s
            self.stats["served"] += 1
            self.stats["tokens_out"] += len(s.out)
            self._evict(i)

        # slots completed at admission (max_new==1 / instant eos)
        for i, s in enumerate(self.slots):
            if s is not None and s.done:
                touched.append(s)
                _finish(i, s)

        with self._span("serve.chunk", n=n_steps):
            for _ in range(n_steps):
                if not self._decode_one(touched, _finish):
                    break

        chunks: Dict[str, Tuple[int, List[int]]] = {}
        for s in touched:
            new = s.out[s.streamed :]
            if new:
                chunks[s.req_id] = (s.streamed, new)
                s.streamed = len(s.out)
        return finished, chunks

    def _decode_one(self, touched: List[Slot], finish) -> bool:
        """One decode step over all slots; False when no slot is live.
        The engine thread's time here outside the token read-back counts
        into `stats["decode_host_ns"]`."""
        t0 = time.perf_counter_ns()
        live = [i for i, s in enumerate(self.slots) if s is not None]
        if not live:
            return False
        scfg = self.scfg
        with self._span("serve.decode", live=len(live)):
            logits, self.cache = self._decode(
                self.params,
                self._put(self.tokens[:, None]),
                self.cache,
                self._put(self.cache_lens),
            )
            self.stats["decode_steps"] += 1
            keys = self._put(self.keys) if scfg.temperature > 0 else None
            sampled = sample_tokens(logits[:, 0], keys, self.steps, scfg.temperature)
            t_rb = time.perf_counter_ns()
            with self._span("serve.readback"):
                toks = np.asarray(sampled)
            t_rb = time.perf_counter_ns() - t_rb
            for i in live:
                s = self.slots[i]
                self.cache_lens[i] += 1  # fed token now resides in the cache
                t = int(toks[i])
                s.out.append(t)
                self.steps[i] += 1
                self.tokens[i] = t
                if s not in touched:
                    touched.append(s)
                if (
                    len(s.out) >= s.max_new
                    or (scfg.eos_id >= 0 and t == scfg.eos_id)
                    or self.cache_lens[i] >= scfg.max_len - 1
                ):
                    finish(i, s)
        self.stats["decode_host_ns"] += time.perf_counter_ns() - t0 - t_rb
        return True

    # ---- request-plane loop ----------------------------------------------

    def run(
        self,
        store,
        kv,
        *,
        engine_id: str = "engine-0",
        idle_timeout_s: float = 2.0,
        max_requests: Optional[int] = None,
        reap: bool = True,
    ) -> Dict[str, int]:
        """Serve until the queue stays empty for `idle_timeout_s` (or
        `max_requests` have been served).  Leases, heartbeats, streaming
        and publishing all ride the chunk cadence; an idle engine parks in
        `blpop` on its home queue shard and is pushed awake by a submit."""
        scfg = self.scfg
        self.engine_id = engine_id
        last_beat = 0.0
        last_reap = 0.0
        idle_deadline = time.monotonic() + idle_timeout_s
        while True:
            if max_requests is not None and self.stats["served"] >= max_requests:
                break
            now = time.time()
            if reap and now - last_reap >= scfg.lease_timeout_s:
                with self._span("serve.reap"):
                    rp.reap_expired(store, kv, n_queues=scfg.n_queues, worker=engine_id)
                last_reap = now
            free = self.free_slots()
            if free:
                wait_s = 0.0
                if self.n_live() == 0:
                    wait_s = max(0.0, min(0.5, idle_deadline - time.monotonic()))
                with self._span("serve.lease", n=len(free), wait=wait_s > 0) as sp:
                    leased = rp.lease_requests(
                        store, kv, engine_id, len(free),
                        lease_timeout_s=scfg.lease_timeout_s,
                        wait_s=wait_s,
                        n_queues=scfg.n_queues,
                    )
                    sp.set(req=[r for r, _ in leased])
                if leased:
                    now = time.time()
                    self.stats["leased"] += len(leased)
                    self.stats["lease_wait_ns"] += sum(
                        int((now - float(body["ts"])) * 1e9) for _, body in leased
                    )
                    self.admit([
                        (r, body["prompt"], int(body.get("max_new", scfg.max_new_tokens)))
                        for r, body in leased
                    ])
            if self.n_live() == 0:
                if time.monotonic() >= idle_deadline:
                    break
                continue  # the blpop above is the idle wait — no sleep loop
            idle_deadline = time.monotonic() + idle_timeout_s

            firsts = {s.req_id: s for s in self.slots if s is not None and s.streamed == 0}
            finished, chunks = self.step_chunk()
            with self._span("serve.stream", req=list(chunks)):
                rp.stream_chunks(kv, chunks, worker=engine_id)
            t_pushed = time.perf_counter_ns()
            for r, (off, _) in chunks.items():
                if off == 0:
                    self.stats["first_tokens_streamed"] += 1
                    self.stats["first_token_hold_ns"] += t_pushed - firsts[r].t_first_ns
            if finished:
                t_done = time.time()
                with self._span("serve.publish", req=list(finished)):
                    rp.publish_results(
                        store, kv, engine_id,
                        {
                            r: {
                                "tokens": s.out,
                                "t_first": s.t_first,
                                "t_done": t_done,
                            }
                            for r, s in finished.items()
                        },
                    )
            now = time.time()
            if now - last_beat >= scfg.heartbeat_interval_s:
                with self._span("serve.heartbeat"):
                    rp.heartbeat_leases(
                        kv, engine_id, self.live_req_ids(),
                        lease_timeout_s=scfg.lease_timeout_s,
                    )
                last_beat = now
        return dict(self.stats)
