"""One run of one cell: build, warm, offer the mix, measure, check.

The window drives `ContinuousEngine.run` on its own thread; the clients
are threads of the same process that `request_plane.submit` each request
when it is due and read its tokens with `request_plane.stream_result`.
Latency is taken at the client from the time a request was due, so a
stall of the engine or of the sender counts against every request it
delays.  An offline batch (`arrivals: backlog`) is submitted before the
window opens; its requests count when their results are published inside
the window.
"""

from __future__ import annotations

import gc
import math
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback
from collections import Counter
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from bench import arch
from repro.serve import request_plane as rp
from repro.storage import KVStore, ObjectStore

from . import check, spec, system, traffic
from .spec import Cell

LEAD_S = 0.2  # threads start this long before the first request is due
STALL_S = 0.1  # a send this late records where the engine and tracer are


class CompileClock:
    """Counts XLA backend compiles (persistent-cache hits do not count)."""

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


@dataclass
class Outcome:
    """What the client saw of one request."""

    req: traffic.Request
    due: float  # perf_counter seconds
    sent: float = math.nan
    first: float = math.nan
    last: float = math.nan
    tokens: List[int] = field(default_factory=list)
    error: Optional[str] = None
    published: Optional[List[int]] = None  # the result record's tokens

    @property
    def finished(self) -> bool:
        return self.error is None and len(self.tokens) == self.req.max_new


@dataclass
class Run:
    """Everything a metric reader may read of one run."""

    cell: Cell
    seconds: float
    outcomes: List[Outcome]
    t_open: float
    t_close: float
    spans: system.Spans
    stats: Dict[str, int]
    peaks: Dict[str, float]
    completed_in_window: List[Outcome] = field(default_factory=list)
    setup_s: float = math.nan  # process start to the first due request
    trace: Any = None  # trace.Reduced, with --trace 1


def _client(store, kv, o: Outcome, timeout_s: float) -> None:
    try:
        for chunk in rp.stream_result(store, kv, o.req.req_id, timeout_s=timeout_s):
            now = time.perf_counter()
            if not o.tokens:
                o.first = now
            o.tokens.extend(chunk)
            o.last = now
    except Exception as e:  # noqa: BLE001 — a failed request is counted, not raised
        o.error = repr(e)


def _witness(names=("engine", "tracer")) -> str:
    """Where the named threads are now: the innermost frames of each."""
    frames = sys._current_frames()
    out = []
    for th in threading.enumerate():
        f = frames.get(th.ident)
        if th.name in names and f is not None:
            stack = traceback.extract_stack(f)[-4:]
            out.append(th.name + ": " + " < ".join(
                f"{os.path.basename(fr.filename)}:{fr.lineno} {fr.name}" for fr in reversed(stack)))
    return "; ".join(out)


def _sender(store, kv, outcomes: List[Outcome], clients: list, drain_s: float, horizon: float,
            stalls: List[str]):
    for o in outcomes:
        delay = o.due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        if time.perf_counter() - o.due > STALL_S:  # who held the process up
            stalls.append(f"{o.req.req_id} {1e3 * (time.perf_counter() - o.due):.1f} ms late; "
                          + _witness())
        rp.submit(store, kv, o.req.req_id, o.req.prompt, max_new_tokens=o.req.max_new)
        o.sent = time.perf_counter()
        th = threading.Thread(
            target=_client, args=(store, kv, o, horizon - o.sent), daemon=True
        )
        th.start()
        clients.append(th)


def _tracer(t_start: float, t_stop: float, logdir: str) -> None:
    time.sleep(max(0.0, t_start - time.perf_counter()))
    jax.profiler.start_trace(logdir)
    time.sleep(max(0.0, t_stop - time.perf_counter()))
    jax.profiler.stop_trace()


def prepare(cell: Cell, seed: int, device):
    """The engine with weights drawn from `seed` on `device`, every program
    that the cell's mix can run compiled or loaded from the cache."""
    mix = cell.traffic
    max_len = int(cell.config["engine"]["max_len"])
    p_lo, p_hi = traffic.length_range(mix, "prompt_len")
    _, o_hi = traffic.length_range(mix, "output_len")
    # a request holds prompt + max_new - 1 positions when its last token is out
    if p_hi + o_hi > max_len:
        raise ValueError(f"mix needs {p_hi + o_hi} positions, engine holds {max_len}")
    engine = system.build_engine(cell.config, seed, device)
    system.warm(engine, system.bucket_lengths(engine, p_lo, p_hi))
    return engine


def offer(
    cell: Cell,
    engine,
    *,
    seed: int,
    seconds: float,
    trace: bool,
    t_process: float,
    device,
    clock: CompileClock,
    log: Callable[[str], None] = print,
) -> Dict[str, Any]:
    """Offer the cell's mix, drawn from `seed`, to a warmed `engine` for
    `seconds`, and wait for what is due.  Returns the run and what the
    result line needs besides the verdict."""
    mix = cell.traffic
    requests = traffic.generate(mix, seed, seconds, int(cell.config["vocab_size"]))
    compiles_warm = clock.count
    spans = system.Spans()
    undo = system.instrument(engine, spans)
    for k in engine.stats:
        engine.stats[k] = 0
    store, kv = ObjectStore(), KVStore(num_shards=2)
    backlog = mix["arrivals"] == "backlog"
    drain_s = float(mix["drain_s"])

    if backlog:  # the whole batch is queued before the window opens
        for r in requests:
            rp.submit(store, kv, r.req_id, r.prompt, max_new_tokens=r.max_new)
    # what set-up made lives on: a full collection in the window need not
    # walk it again (about 0.1 s for a warmed engine's objects)
    gc.collect()
    gc.freeze()
    t_open = time.perf_counter() + LEAD_S
    outcomes = [Outcome(r, due=t_open + r.due_s) for r in requests]
    t_close = t_open + seconds
    horizon = t_close + drain_s
    if backlog:
        for o in outcomes:
            o.sent = o.due
        max_requests, idle_s = None, 1.0
    else:
        gaps = np.diff([o.due for o in outcomes] + [t_close])
        max_requests, idle_s = len(outcomes), 5.0 + 2.0 * float(np.max(gaps, initial=0.0))
    setup_s = t_open - t_process

    stats: Dict[str, int] = {}
    engine_err: List[str] = []

    def serve() -> None:
        try:
            stats.update(engine.run(
                store, kv, engine_id="engine-0", idle_timeout_s=idle_s,
                max_requests=max_requests,
            ))
        except Exception as e:  # noqa: BLE001 — reported as a failed run
            engine_err.append(repr(e))

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    threads = []
    if trace:
        t0 = t_open + 0.4 * seconds
        threads.append(threading.Thread(
            target=_tracer, args=(t0, t0 + min(6.0, 0.3 * seconds), trace_dir),
            daemon=True, name="tracer",
        ))
    eng = threading.Thread(target=serve, daemon=True, name="engine")
    clients: list = []
    stalls: List[str] = []
    if not backlog:
        threads.append(threading.Thread(
            target=_sender, args=(store, kv, outcomes, clients, drain_s, horizon, stalls),
            daemon=True,
        ))
    for th in threads:
        th.start()
    while time.perf_counter() < t_open:
        time.sleep(0.001)
    eng.start()

    # ---- the window ----------------------------------------------------
    time.sleep(max(0.0, t_close - time.perf_counter()))
    compiles_window_end = clock.count
    if backlog:
        left = kv.lpop_n(rp.queue_key(0), len(outcomes), worker="client")
        log(f"window closed: {len(left)} queued requests withdrawn")
    for th in threads:
        th.join(max(0.0, horizon - time.perf_counter()))
    for th in list(clients):
        th.join(max(0.0, horizon - time.perf_counter()))
    eng.join(max(0.0, horizon + idle_s + 5.0 - time.perf_counter()))
    gc.unfreeze()
    compiles_in_window = compiles_window_end - compiles_warm
    undo()
    engine_alive = eng.is_alive()

    mem = device.memory_stats() or {}
    dev = {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": cell.chips,
        "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0)),
    }

    leased = _leased(spans)
    done = store.get_many(
        [rp.done_key(o.req.req_id) for o in outcomes], worker="client", missing="omit"
    )
    wall_close = time.time() - (time.perf_counter() - t_close)
    for o in outcomes:
        rec = done.get(rp.done_key(o.req.req_id))
        o.published = None if rec is None else list(rec["tokens"])
        if not backlog:
            continue
        if rec is None:
            o.error = "not leased" if o.req.req_id not in leased else "not completed"
            continue
        o.tokens = _stream_of(kv, o.req.req_id)
        o.first = o.last = t_close - (wall_close - float(rec["t_done"]))
    in_window = [o for o in outcomes if o.finished and o.last <= t_close]

    run = Run(
        cell=cell, seconds=seconds, outcomes=outcomes, t_open=t_open, t_close=t_close,
        spans=spans, stats=stats,
        peaks=spec.peaks_for(device.device_kind) if trace else {},
        completed_in_window=in_window, setup_s=setup_s,
    )
    sent = [o for o in outcomes if not math.isnan(o.sent)]
    late = [] if backlog else [(o.sent - o.due, o.due - t_open) for o in sent]
    worst = max(late, default=(0.0, 0.0))
    tried = [o for o in outcomes if o.req.req_id in leased] if backlog else sent
    attempted = len(tried)
    failed = sum(1 for o in tried if not o.finished)
    log(
        f"window: seconds={seconds} requests={len(outcomes)} sent={len(sent)} "
        f"completed_in_window={len(in_window)} failed={failed} "
        f"compiles_in_window={compiles_in_window} "
        f"sender_late_p50_ms={1e3 * traffic.nearest_rank([x for x, _ in late], 0.5) if late else 0.0} "
        f"sender_late_max_ms={1e3 * worst[0]} at_s={worst[1]} "
        f"engine_stats={stats}"
    )
    for st in stalls:
        log(f"sender stall: {st}")

    if trace:
        from . import trace as trace_mod

        run.trace = trace_mod.reduce_dir(trace_dir, spans, arch.for_config(cell.config).KERNELS)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if run.trace is not None:
            from . import readings

            kinds = Counter(p.kind for p in run.trace.programs)
            names = Counter(p.name for p in run.trace.programs)
            log(
                f"trace: window_s={run.trace.window_s} busy_s={run.trace.busy_s} "
                f"ops={len(run.trace.ops)} modules={len(run.trace.modules)} "
                f"host_spans={len(run.trace.host)} "
                f"joined={sum(i is not None for i in run.trace.host_info)} "
                f"programs={dict(kinds)} program_names={dict(names)} "
                f"prefills_matched={len(readings.prefills(run.trace))} "
                f"chunks_matched={len(readings.decode_chunks(run.trace))}"
            )
        else:
            log("trace: no device events read")

    return {
        "run": run,
        "device": dev,
        "attempted": attempted,
        "failed": failed,
        "engine_error": engine_err[0] if engine_err else (
            "engine did not stop" if engine_alive else None),
        "compiles_in_window": compiles_in_window,
    }


def run_cell(
    cell: Cell,
    device,
    *,
    seed: int,
    seconds: float,
    trace: bool,
    t_process: float,
    log: Callable[[str], None] = print,
    control: Optional[str] = None,
) -> Dict[str, Any]:
    """Run `cell` once on `device`: build and warm, offer the mix, then
    decide `correct` with the engine freed.  With `control` (a precision
    of the binding's `logits_at`), the reference in that precision stands in for the
    served tokens in the comparison, and `correct` has to come out false."""
    clock = CompileClock()
    engine = prepare(cell, seed, device)
    out = offer(
        cell, engine, seed=seed, seconds=seconds, trace=trace, t_process=t_process,
        device=device, clock=clock, log=log,
    )
    del engine  # the reference needs the engine's memory
    gc.collect()
    out["verdict"] = check.verify(
        out["run"],
        seed=seed,
        engine_error=out["engine_error"],
        compiles_in_window=out["compiles_in_window"],
        device=device,
        log=log,
        control=control,
    )
    out["compile_s"], out["compiles"] = clock.seconds, clock.count
    return out


def _stream_of(kv, req_id: str) -> List[int]:
    """The tokens of a request's stream as the engine pushed them."""
    toks: List[int] = []
    for chunk in kv.lrange(rp.stream_key(req_id), worker="client"):
        if "off" in chunk and int(chunk["off"]) == len(toks):
            toks.extend(chunk["toks"])
    return toks


def _leased(spans: system.Spans) -> set:
    return {r for name, _, _, info, _ in spans.records if name == "admit" for r, _ in info}


def result_line(cell: Cell, out: Dict[str, Any], *, trace: bool, root: str) -> Dict[str, Any]:
    """The run's last line: `correct`, `attempted`, `failed`, the cell's
    end-to-end metrics (or, traced, its per-layer ones), `device`,
    `breakdown` when traced, and last `checks`, each number beside its
    limit.  A metric whose reader finds nothing to read is left out."""
    run = out["run"]
    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        value = spec.metric_reader(m.name, root=root)(run)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    device = dict(out["device"])
    line = {
        "correct": out["verdict"]["correct"],
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": metrics,
        "device": device,
    }
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        line["breakdown"] = run.trace.breakdown()
    line["checks"] = out["verdict"]["checks"]
    return line
