"""Span tracing threaded through task → agent → engine, and the host-lane
spans that put the engine's threads on the profiler's clock.

The reference has no tracing at all (SURVEY.md §5.1 — only ad-hoc
``execution_time`` stamps). Here every task execution opens a span tree
(``Tracer``, kept in memory for black-box dumps). ``host_span`` is apart
from it: a ``jax.profiler.TraceAnnotation`` and nothing else, so that a
profiler trace shows what each host thread was doing beside the device's
operations.
"""

from __future__ import annotations

import contextlib
import contextvars
import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional


@dataclass
class Span:
    name: str
    span_id: str
    parent_id: Optional[str]
    trace_id: str
    start: float = field(default_factory=time.perf_counter)
    end: Optional[float] = None
    attributes: Dict[str, Any] = field(default_factory=dict)

    @property
    def duration(self) -> Optional[float]:
        return None if self.end is None else self.end - self.start

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "trace_id": self.trace_id,
            # perf_counter timestamps: one process-wide monotonic clock,
            # shared with the engine step ring — the Perfetto exporter
            # relies on the two aligning.
            "start": self.start,
            "end": self.end,
            "duration": self.duration,
            "attributes": self.attributes,
        }


class Tracer:
    """Minimal in-process tracer.

    Span stacks live in a ``contextvars.ContextVar`` (not threading.local):
    interleaved asyncio tasks on one event loop each see their own stack, so
    concurrent task executions (``ServeConfig.max_concurrent_tasks`` > 1)
    get correct span parentage.
    """

    def __init__(self, max_finished: int = 10000) -> None:
        self._stack_var: contextvars.ContextVar[tuple] = contextvars.ContextVar(
            f"pilottai_span_stack_{id(self)}", default=()
        )
        self._finished: List[Span] = []
        self._lock = threading.Lock()
        self._max_finished = max_finished

    def current(self) -> Optional[Span]:
        stack = self._stack_var.get()
        return stack[-1] if stack else None

    @contextlib.contextmanager
    def span(
        self,
        name: str,
        trace_id: Optional[str] = None,
        **attributes: Any,
    ) -> Iterator[Span]:
        """Open a span. ``trace_id`` seeds a ROOT span's trace (the HTTP
        edge passes the request's ``x-request-id`` here); a span with a
        live parent always inherits the parent's trace instead — one
        request, one trace, no matter what a nested caller passes."""
        parent = self.current()
        span = Span(
            name=name,
            span_id=uuid.uuid4().hex[:16],
            parent_id=parent.span_id if parent else None,
            trace_id=(
                parent.trace_id if parent
                else (trace_id or uuid.uuid4().hex[:16])
            ),
            attributes=attributes,
        )
        token = self._stack_var.set(self._stack_var.get() + (span,))
        try:
            yield span
        finally:
            span.end = time.perf_counter()
            self._stack_var.reset(token)
            with self._lock:
                self._finished.append(span)
                if len(self._finished) > self._max_finished:
                    del self._finished[: len(self._finished) // 2]

    def emit(
        self,
        name: str,
        *,
        trace_id: str,
        start: float,
        end: float,
        parent_id: Optional[str] = None,
        **attributes: Any,
    ) -> Span:
        """Record an already-finished span directly. For code that runs
        outside any task context (the batcher's device/reader threads,
        where the contextvar stack doesn't propagate): the engine emits
        its per-request span at completion time with the parent span id
        the request carried in, so the request's tree still nests
        server → handler → batcher."""
        span = Span(
            name=name,
            span_id=uuid.uuid4().hex[:16],
            parent_id=parent_id,
            trace_id=trace_id,
            start=start,
            end=end,
            attributes=attributes,
        )
        with self._lock:
            self._finished.append(span)
            if len(self._finished) > self._max_finished:
                del self._finished[: len(self._finished) // 2]
        return span

    def finished(self, name: Optional[str] = None) -> List[Span]:
        with self._lock:
            spans = list(self._finished)
        if name is not None:
            spans = [s for s in spans if s.name == name]
        return spans

    def for_trace(self, trace_id: str) -> List[Span]:
        """Every finished span of one trace, in finish order (a flight
        recorder dump wants exactly this tree)."""
        with self._lock:
            return [s for s in self._finished if s.trace_id == trace_id]

    def clear(self) -> None:
        with self._lock:
            self._finished.clear()


global_tracer = Tracer()


def host_span(name: str, **sizes: Any) -> Any:
    """A span in the profiler's host lanes, on the device trace's clock:
    a context manager over ``jax.profiler.TraceAnnotation``. It records
    nothing in any ``Tracer`` and costs an inactive ``TraceMe`` while no
    profiler session runs.

    A host-lane span holds host work, or the device thread's wait for
    requests, and never a wait on another layer: the trace reducer names
    a device-idle gap by the span that covers most of it. ``name`` is a
    fixed string (the reducer keys by it); sizes (rows, bucket, blocks)
    go as keyword arguments, which the trace shows as the event's
    arguments."""
    import jax.profiler

    return jax.profiler.TraceAnnotation(name, **sizes)
