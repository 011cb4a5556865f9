"""Per-request flight recorder: phase timestamps from accept to last token.

LLM-Pilot (arxiv 2410.02425) argues per-phase characterization — queue
wait, prefill, time-to-first-token, per-token decode — is the
prerequisite for capacity planning; an aggregate request latency can't
tell an admission backlog from a slow decode. Each request therefore
accumulates a ``RequestFlight``: the handler opens one keyed by a
per-request ``flight_id`` (the shared ``trace_id`` rides along for
correlation — many flights can share one orchestrator trace), the
engine layers mark phases as they happen (admission on the device
thread, token folds on the reader thread), and ``finish`` derives the
serving metrics and feeds them into ``global_metrics`` histograms:

===========================  ==========================================
``request.queue_wait_s``     start → batcher admission (slot granted)
``request.ttft_s``           start → first generated token on the host
``request.itl_s``            inter-token latency, observed per fold
``request.tpot_s``           (last − first token) / (n − 1)
``request.e2e_s``            start → the handler's finish
===========================  ==========================================

plus ``request.completed`` / ``request.failed`` counters labelled by the
finish status in ``request.finished.<status>``.

One timeline per request, one clock (``time.perf_counter``). Each layer
stamps a mark where the request crosses its boundary, first stamp wins:

=====================  ================================================
``edge_received``      server.py: headers and body are in
``handler_entered``    the handler's first ``start()``; ``started`` too
``submitted``          ``batcher.submit``
``admitted``           the admission's prefill is dispatched
first / last token     ``token()`` from the reader thread's folds
``batcher_done``       the reader thread resolves the request's future
``handler_returned``   the handler's ``finish()``; ``ended`` too
``edge_last_byte``     server.py: the reply (or the error) is written
=====================  ================================================

and ``derived()`` gives every layer its self time, a span less what its
child spans cover: ``edge_self_s``, ``handler_self_s``,
``batcher_wait_s`` (submitted → admitted), ``prefill_s`` (admitted →
first token), ``decode_s`` (first → last token). For a finished HTTP
flight the five sum to the edge's span (``edge_s``) less the stretch
between the last token and ``batcher_done`` (``batcher_tail_s``).

The HTTP edge holds the flight it opened (``open_edge``) across the
handler's ``finish`` and closes it itself (``close_edge``) once the last
byte is out: metrics are observed and finish listeners fire there, once.
A caller with no edge (``Serve``, a bare ``LLMHandler``) closes at the
handler's ``finish`` as before.

Backends that cannot see individual tokens (the mock, pre-token-callback
custom backends) call ``synthesize_tokens`` with the response envelope —
TTFT/TPOT become envelope-derived estimates rather than absent, so
mock-engine runs still produce the full percentile surface.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional

from pilottai_tpu.utils.metrics import MetricsRegistry, global_metrics


@dataclass
class RequestFlight:
    """One request's phase ledger. All timestamps are
    ``time.perf_counter()`` — the tracer's clock.

    ``flight_id`` is the UNIQUE ledger key (one per engine request);
    ``trace_id`` is the shared correlation id — orchestrator traffic
    runs many engine calls under one trace, and keying the ledger by
    trace would merge concurrent siblings' phases (review finding)."""

    flight_id: str
    trace_id: str
    started: float = field(default_factory=time.perf_counter)
    attributes: Dict[str, Any] = field(default_factory=dict)
    marks: Dict[str, float] = field(default_factory=dict)
    n_tokens: int = 0
    first_token_at: Optional[float] = None
    last_token_at: Optional[float] = None
    status: Optional[str] = None  # set by finish()
    ended: Optional[float] = None
    # The HTTP edge opened this flight and closes it (``close_edge``):
    # the handler's finish() settles status and ``ended`` but leaves it
    # active until the reply is written.
    edge_held: bool = False

    def to_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "flight_id": self.flight_id,
            "trace_id": self.trace_id,
            "status": self.status,
            "attributes": dict(self.attributes),
            "marks": {
                k: round(v - self.started, 6) for k, v in self.marks.items()
            },
            "tokens": self.n_tokens,
        }
        for name, value in self.derived().items():
            out[name] = round(value, 6)
        return out

    def derived(self) -> Dict[str, float]:
        """Phase durations computable from the ledger so far."""
        out: Dict[str, float] = {}
        admitted = self.marks.get("admitted")
        if admitted is not None:
            out["queue_wait_s"] = max(admitted - self.started, 0.0)
        if self.first_token_at is not None:
            out["ttft_s"] = max(self.first_token_at - self.started, 0.0)
        if (
            self.n_tokens > 1
            and self.first_token_at is not None
            and self.last_token_at is not None
        ):
            out["tpot_s"] = max(
                (self.last_token_at - self.first_token_at)
                / (self.n_tokens - 1),
                0.0,
            )
        if self.ended is not None:
            out["e2e_s"] = max(self.ended - self.started, 0.0)
        out.update(self._self_times())
        return out

    def _self_times(self) -> Dict[str, float]:
        """Each layer's span less what its child spans cover. A key whose
        marks are not all there is left out, never reported as 0."""
        m = self.marks

        def span(a: str, b: str) -> Optional[float]:
            return m[b] - m[a] if a in m and b in m else None

        out: Dict[str, float] = {}
        edge = span("edge_received", "edge_last_byte")
        handler = span("handler_entered", "handler_returned")
        batcher = span("submitted", "batcher_done")
        wait = span("submitted", "admitted")
        if edge is not None:
            out["edge_s"] = edge
        if edge is not None and handler is not None:
            out["edge_self_s"] = edge - handler
        if handler is not None and batcher is not None:
            out["handler_self_s"] = handler - batcher
        if wait is not None:
            out["batcher_wait_s"] = wait
        if "admitted" in m and self.first_token_at is not None:
            out["prefill_s"] = self.first_token_at - m["admitted"]
        if self.first_token_at is not None and self.last_token_at is not None:
            out["decode_s"] = self.last_token_at - self.first_token_at
        if self.last_token_at is not None and "batcher_done" in m:
            out["batcher_tail_s"] = m["batcher_done"] - self.last_token_at
        # Marks out of order (a retry re-entered a phase) give no self time.
        return {k: v for k, v in out.items() if v >= 0.0}


class FlightRecorder:
    """Registry of in-flight and recently finished request flights.

    Thread-safe: the HTTP edge and handler run on the event loop while
    the batcher marks phases from its device and reader threads. All
    mutation happens under one lock; every method is a cheap no-op for
    unknown trace ids, so instrumentation call sites never need guards.
    """

    def __init__(
        self,
        max_finished: int = 1024,
        registry: MetricsRegistry = global_metrics,
    ) -> None:
        self._active: Dict[str, RequestFlight] = {}
        self._finished: Deque[RequestFlight] = deque(maxlen=max_finished)
        self._lock = threading.Lock()
        self._registry = registry
        # Finish listeners: called with the closed RequestFlight after
        # its metrics are observed (obs/__init__ wires the SLO tracker
        # here). Outside the lock; exceptions are swallowed — derived
        # telemetry must never fail the request path.
        self._listeners: List[Any] = []
        # Start listeners: called once per flight on its FIRST start()
        # (the idempotent re-entry that merely enriches attributes does
        # not re-fire) — the arrival event the workload profiler and
        # the seasonal forecaster key on. Same outside-the-lock,
        # swallow-exceptions contract as finish listeners.
        self._start_listeners: List[Any] = []

    def add_finish_listener(self, fn: Any) -> None:
        """Register ``fn(flight: RequestFlight)`` to run on every
        ``finish`` (any status)."""
        self._listeners.append(fn)

    def add_start_listener(self, fn: Any) -> None:
        """Register ``fn(flight: RequestFlight)`` to run once per flight
        when it is first opened."""
        self._start_listeners.append(fn)

    # ------------------------------------------------------------------ #
    # Lifecycle (handler / HTTP edge)
    # ------------------------------------------------------------------ #

    def open_edge(
        self, flight_id: str, trace_id: str, received_at: float
    ) -> None:
        """The HTTP edge opens the request's flight before it calls the
        handler, and holds it: the handler's ``finish`` settles the
        status, ``close_edge`` closes it. ``received_at`` is when the
        request's headers and body were in."""
        with self._lock:
            if flight_id in self._active:
                return
            flight = RequestFlight(
                flight_id=flight_id, trace_id=trace_id, edge_held=True
            )
            flight.marks["edge_received"] = received_at
            self._active[flight_id] = flight

    def start(
        self,
        flight_id: str,
        trace_id: Optional[str] = None,
        **attributes: Any,
    ) -> RequestFlight:
        """Get-or-create the active flight for ``flight_id`` (idempotent:
        the edge or the cell may open it before the handler enriches
        it). The first call is the request entering the handler: it
        stamps ``handler_entered`` and, on a flight the edge opened,
        moves ``started`` there, so that ``ttft_s`` and ``e2e_s`` start
        where they always did. ``trace_id`` defaults to the flight id
        for callers with a one-request trace."""
        with self._lock:
            flight = self._active.get(flight_id)
            if flight is None:
                flight = RequestFlight(
                    flight_id=flight_id, trace_id=trace_id or flight_id
                )
                self._active[flight_id] = flight
            entered = "handler_entered" not in flight.marks
            if entered:
                if flight.edge_held:
                    flight.started = time.perf_counter()
                flight.marks["handler_entered"] = flight.started
            flight.attributes.update(attributes)
        if entered:
            for listener in self._start_listeners:
                try:
                    listener(flight)
                except Exception:  # noqa: BLE001 — telemetry must not raise
                    pass
        return flight

    def finish(self, flight_id: str, status: str = "ok") -> Optional[Dict[str, Any]]:
        """The handler is done with the request: settle status and
        ``ended`` (the ``handler_returned`` mark) and, unless the HTTP
        edge holds the flight, close it. Returns the flight's summary
        dict, or None when no active flight exists (already finished,
        or never started) — safe to call from every error path without
        bookkeeping."""
        with self._lock:
            flight = self._active.get(flight_id)
            if flight is None or flight.ended is not None:
                return None
            flight.status = status
            flight.ended = time.perf_counter()
            flight.marks.setdefault("handler_returned", flight.ended)
            if flight.edge_held:
                return flight.to_dict()
        return self._close(flight_id)

    def close_edge(self, flight_id: str, status: str) -> None:
        """The HTTP edge has written the reply's last byte, or given up
        on a client that went away (``status`` then says so and
        overrides the handler's ``ok``; a failure the handler settled
        stands). A request that never reached the handler is settled
        here with ``status``."""
        now = time.perf_counter()
        with self._lock:
            flight = self._active.get(flight_id)
            if flight is None:
                return
            flight.marks.setdefault("edge_last_byte", now)
            if flight.ended is None:
                flight.ended = now
            if flight.status in (None, "ok"):
                flight.status = status
        self._close(flight_id)

    def _close(self, flight_id: str) -> Optional[Dict[str, Any]]:
        """Move the settled flight to the finished ring, observe its
        phase metrics and fire the finish listeners, once.

        Phase histograms are observed for ``ok`` flights ONLY: a storm
        of shed/breaker-fast-fails would otherwise flood the (window-
        aware) latency percentiles with ~0 ms samples and make p99 read
        "healthy" mid-outage — failures are counted, not timed."""
        with self._lock:
            flight = self._active.pop(flight_id, None)
            if flight is None:
                return None
            self._finished.append(flight)
        status = flight.status
        if status == "ok":
            for name, value in flight.derived().items():
                self._registry.observe(f"request.{name}", value)
            self._registry.inc("request.completed")
        else:
            self._registry.inc("request.failed")
        self._registry.inc(f"request.finished.{status}")
        for listener in self._listeners:
            try:
                listener(flight)
            except Exception:  # noqa: BLE001 — telemetry must not raise
                pass
        return flight.to_dict()

    # ------------------------------------------------------------------ #
    # Phase marks (any thread)
    # ------------------------------------------------------------------ #

    def mark(self, flight_id: str, phase: str, at: Optional[float] = None) -> None:
        """Stamp a named phase (first stamp wins — a retry re-entering a
        phase must not erase when the request FIRST reached it)."""
        with self._lock:
            flight = self._active.get(flight_id)
            if flight is not None:
                flight.marks.setdefault(
                    phase, at if at is not None else time.perf_counter()
                )

    def token(self, flight_id: str, n: int = 1, at: Optional[float] = None) -> None:
        """Record ``n`` generated tokens surfacing on the host at ``at``.
        The first call fixes TTFT; later calls observe the inter-token
        gap (per token) into ``request.itl_s``."""
        if n <= 0:
            return
        at = at if at is not None else time.perf_counter()
        itl: Optional[float] = None
        with self._lock:
            flight = self._active.get(flight_id)
            if flight is None:
                return
            if flight.first_token_at is None:
                flight.first_token_at = at
                if n > 1:
                    itl = max(at - flight.started, 0.0) / n
            else:
                prev = flight.last_token_at or flight.first_token_at
                itl = max(at - prev, 0.0) / n
            flight.last_token_at = at
            flight.n_tokens += n
        if itl is not None:
            self._registry.observe("request.itl_s", itl)

    def synthesize_tokens(
        self, flight_id: str, n: int, t_start: float, t_end: float
    ) -> None:
        """Envelope fallback for backends with no token visibility: model
        ``n`` tokens spread uniformly over [t_start, t_end], so TTFT ≈
        latency/n and TPOT ≈ latency/n. No-op when real token marks
        already landed (the native engine's batcher feeds those)."""
        if n <= 0:
            return
        with self._lock:
            flight = self._active.get(flight_id)
            if flight is None or flight.n_tokens:
                return
            per_tok = max(t_end - t_start, 0.0) / n
            flight.first_token_at = t_start + per_tok
            flight.last_token_at = t_end
            flight.n_tokens = n

    def reset_tokens(self, flight_id: str) -> None:
        """Clear the token timeline at a retry boundary: a new attempt's
        first token must not register as an inter-token gap from the
        aborted attempt's last token (the backoff sleep would land in
        ``request.itl_s`` as a multi-second sample). ``started`` and the
        phase marks stay — TTFT/e2e remain client-perceived, retries
        included."""
        with self._lock:
            flight = self._active.get(flight_id)
            if flight is not None:
                flight.n_tokens = 0
                flight.first_token_at = None
                flight.last_token_at = None

    def set_token_envelope(
        self, flight_id: str, n: int, first_at: float, last_at: float
    ) -> None:
        """Stream fallback: the consumer observed ``n`` deltas between
        ``first_at``/``last_at`` but the backend recorded no per-token
        marks (mock/custom backends) — adopt the delta envelope as the
        token timeline. No-op when real marks exist."""
        if n <= 0:
            return
        with self._lock:
            flight = self._active.get(flight_id)
            if flight is None or flight.n_tokens:
                return
            flight.first_token_at = first_at
            flight.last_token_at = last_at
            flight.n_tokens = n

    # ------------------------------------------------------------------ #
    # Inspection
    # ------------------------------------------------------------------ #

    def get(self, flight_id: str) -> Optional[RequestFlight]:
        with self._lock:
            return self._active.get(flight_id)

    def describe(self, trace_id: str) -> Optional[Dict[str, Any]]:
        """Summary of the trace's most recent flight, active or finished
        (black-box dumps call this for the request that tripped them —
        by TRACE id, the correlation key the dump carries)."""
        with self._lock:
            flight = next(
                (f for f in self._active.values() if f.trace_id == trace_id),
                None,
            )
            if flight is None:
                for done in reversed(self._finished):
                    if done.trace_id == trace_id:
                        flight = done
                        break
            return flight.to_dict() if flight is not None else None

    def finished(self, n: Optional[int] = None) -> List[Dict[str, Any]]:
        with self._lock:
            records = list(self._finished)
        if n is not None:
            records = records[-n:]
        return [f.to_dict() for f in records]

    def active_count(self) -> int:
        with self._lock:
            return len(self._active)


global_flight = FlightRecorder()
