"""Spans of the serving loop, taken while a profiler session is on.

One process-wide `tracer`.  `tracer.span(name, req=..., **attrs)` is a
context manager:

  * with no profiler session (`jax.profiler.start_trace` not called) it
    costs one `TraceAnnotation.is_enabled()` check and returns a shared
    null span: there is no option and no environment variable to set;
  * while a session is on, it records `SpanRecord`s on
    `time.perf_counter_ns` in a bounded in-memory deque (parents from a
    per-thread stack), and enters a `jax.profiler.TraceAnnotation` of the
    same name that carries the span's id, so the span also sits in the
    profiler's own trace, on the clock of the device events, for a
    TensorBoard or Perfetto view.

`req` is one request id or a list of them: every span of one request
carries its id.  `Span.set(...)` adds what is known only when the body has
run (the ids a lease returned).  `tracer.records()` is a read-only copy.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple, Union

from jax.profiler import TraceAnnotation

Req = Union[None, str, Sequence[str]]


class SpanRecord(NamedTuple):
    name: str
    start_ns: int  # time.perf_counter_ns
    end_ns: int
    span_id: int
    parent_id: Optional[int]
    engine_id: Optional[str]
    req: Req
    attrs: Dict[str, Any]


def _arg(v: Any) -> Any:
    """A value a `TraceAnnotation` keeps whole (its metadata splits on ',')."""
    if isinstance(v, (list, tuple)):
        return " ".join(map(str, v))
    return v


class Span:
    """One open span; it is recorded when it closes."""

    __slots__ = ("_tracer", "name", "engine_id", "req", "attrs", "span_id", "parent_id",
                 "start_ns", "_ann")

    def __init__(self, tracer: "Tracer", name: str, engine_id: Optional[str], req: Req,
                 attrs: Dict[str, Any]) -> None:
        self._tracer = tracer
        self.name = name
        self.engine_id = engine_id
        self.req = req
        self.attrs = attrs

    def set(self, *, req: Req = None, **attrs: Any) -> None:
        if req is not None:
            self.req = req
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        stack = self._tracer._stack()
        self.span_id = next(self._tracer._ids)
        self.parent_id = stack[-1].span_id if stack else None
        stack.append(self)
        args = {k: _arg(v) for k, v in self.attrs.items()}
        if self.req is not None:
            args["req"] = _arg(self.req)
        if self.parent_id is not None:
            args["parent"] = self.parent_id
        self._ann = TraceAnnotation(self.name, span=self.span_id, **args)
        self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc: Any) -> None:
        end_ns = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self._tracer._stack().pop()
        self._tracer._records.append(SpanRecord(
            self.name, self.start_ns, end_ns, self.span_id, self.parent_id,
            self.engine_id, self.req, self.attrs,
        ))


class _NullSpan:
    """What `span` returns with no profiler session: does nothing."""

    __slots__ = ()

    def set(self, *, req: Req = None, **attrs: Any) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: Any) -> None:
        pass


_NULL = _NullSpan()


MAX_RECORDS = 200_000  # a busy engine opens a few hundred spans a second


class Tracer:
    def __init__(self) -> None:
        self._records: "collections.deque[SpanRecord]" = collections.deque(maxlen=MAX_RECORDS)
        self._ids = itertools.count(1)
        self._local = threading.local()

    def _stack(self) -> List[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, *, req: Req = None, engine_id: Optional[str] = None,
             **attrs: Any) -> Union[Span, _NullSpan]:
        if not TraceAnnotation.is_enabled():
            return _NULL
        return Span(self, name, engine_id, req, attrs)

    def records(self) -> Tuple[SpanRecord, ...]:
        return tuple(self._records)


tracer = Tracer()
