"""HTTP serving endpoint: OpenAI-compatible chat completions over the
in-tree engine, plus orchestrator task submission.

The reference FRAMEWORK is an API *client* (litellm → remote providers,
``pilott/engine/llm.py:59``) and its only networked surface is a
declared-but-unimplemented websocket config (``pilott/core/config.py:
153-156``, SURVEY §2.12-i). This framework owns the inference path, so
it can BE the provider: any OpenAI-SDK client (or plain HTTP) points at
this endpoint and gets the native engine — continuous batching,
speculation, prefix caching, grammar-masked JSON and SSE streaming
included.

Routes
------
* ``POST /v1/chat/completions`` — OpenAI wire format. ``stream: true``
  returns Server-Sent Events chunks (``chat.completion.chunk`` deltas,
  terminated by ``data: [DONE]``) fed by ``LLMHandler.astream``;
  ``response_format: {"type": "json_object"}`` maps to the engine's
  grammar-constrained ``json_mode``; ``tools`` (function specs) map to
  ``ToolSpec`` and structured ``tool_calls`` come back in the message.
* ``GET /v1/models`` — the registry's model list.
* ``POST /v1/tasks`` — framework-specific: submit a task description to
  an attached ``Serve`` orchestrator and wait for its ``TaskResult``
  (503 when the server wraps a bare handler).
* ``GET /healthz`` — liveness; ``GET /metrics`` — the unified metrics
  snapshot (JSON; same shape as the dashboard's ``/metrics.json``), or
  Prometheus text exposition with ``?format=prometheus``.
* ``GET /slo.json`` — per-class SLO attainment, burn rate and latency
  percentiles (obs/slo.py).

Every request accepts (and every completion/task response echoes) an
``x-request-id`` header: the flight-recorder trace id correlating spans,
structured logs, phase metrics and black-box dumps across the server →
handler → batcher boundary (docs/OBSERVABILITY.md). A ``slo_class``
body field (or ``x-slo-class`` header) assigns the request to an SLO
service class ("interactive"/"batch"); unknown classes are a 400. A
``session_id`` body field (or ``x-session-id`` header) names the
client's conversation for the engine's KV cache tier
(engine/kvcache/): turns sending the same id pin their prefix lineage
so a resume restores spilled KV from host RAM instead of re-prefilling
the transcript; malformed ids are a 400.

Implementation is stdlib-asyncio only (``asyncio.start_server`` + a
minimal HTTP/1.1 parser): SSE needs the event loop the engine's futures
resolve on, which rules out the threaded ``http.server`` the metrics
dashboard uses. One request per connection (``Connection: close``) —
agent/SDK traffic reconnects per call and it keeps the parser honest.

Auth mirrors the control plane's posture (``distributed/control_plane``):
optional shared bearer token for private-network deployments; terminate
TLS in front for anything else (documented in docs/SERVING.md).
"""

from __future__ import annotations

import asyncio
import hmac
import json
import re
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple
from urllib.parse import parse_qs

from pilottai_tpu.engine.types import GenerationParams, ToolSpec
from pilottai_tpu.obs import global_flight, metrics_snapshot, prometheus_text
from pilottai_tpu.reliability import (
    CircuitOpenError,
    DeadlineExceeded,
    EngineOverloaded,
)
from pilottai_tpu.utils.logging import get_logger
from pilottai_tpu.utils.metrics import global_metrics
from pilottai_tpu.utils.tracing import global_tracer, host_span

# Client-supplied x-request-id values become trace ids threaded through
# logs, span trees and black-box dumps — constrain the alphabet so a
# hostile header can't inject into JSONL journals or log greps.
_REQUEST_ID_RE = re.compile(r"[A-Za-z0-9._\-]{1,64}")

_MAX_HEADER = 32 * 1024
_MAX_BODY = 10 * 1024 * 1024

_STATUS_TEXT = {
    200: "OK", 400: "Bad Request", 401: "Unauthorized", 404: "Not Found",
    405: "Method Not Allowed", 408: "Request Timeout",
    413: "Payload Too Large", 429: "Too Many Requests",
    500: "Internal Server Error", 503: "Service Unavailable",
}


class _HttpError(Exception):
    def __init__(
        self,
        status: int,
        message: str,
        kind: str = "invalid_request_error",
        extra: Optional[Dict[str, Any]] = None,
    ):
        super().__init__(message)
        self.status = status
        self.message = message
        self.kind = kind
        self.extra = extra or {}


class _EdgeFlight:
    """The edge's hold on one connection's request flight
    (obs/flight.py). ``_handle_conn`` stamps ``received_at`` once
    headers and body are in; ``_chat_completions`` opens the flight
    before it calls the handler; ``_handle_conn`` closes it after the
    reply, or the error body, is written. ``outcome`` is the edge's own
    verdict, which stands only where the handler settled ``ok`` or
    nothing: a connection that ends without one was cut off."""

    __slots__ = ("received_at", "flight_id", "outcome")

    def __init__(self) -> None:
        self.received_at = 0.0
        self.flight_id: Optional[str] = None
        self.outcome = "cancelled"

    def open(self, trace_id: str) -> str:
        self.flight_id = uuid.uuid4().hex[:16]
        global_flight.open_edge(self.flight_id, trace_id, self.received_at)
        return self.flight_id

    def close(self) -> None:
        if self.flight_id is not None:
            global_flight.close_edge(self.flight_id, self.outcome)


def _overload_error(exc: Exception) -> _HttpError:
    """Reliability exceptions → structured HTTP errors (documented in
    docs/SERVING.md "Overload & failure semantics"): deadline exceeded →
    408 timeout_error; breaker open → 503 overloaded_error (with a
    retry_after hint); queue shed → 429 overloaded_error."""
    if isinstance(exc, DeadlineExceeded):
        return _HttpError(
            408, str(exc) or "request deadline exceeded", "timeout_error"
        )
    if isinstance(exc, CircuitOpenError):
        return _HttpError(
            503, str(exc), "overloaded_error",
            extra={"retry_after": round(exc.retry_after, 3)},
        )
    return _HttpError(
        429, str(exc) or "engine overloaded; request shed", "overloaded_error"
    )


class APIServer:
    """Serve an ``LLMHandler`` (and optionally a ``Serve``) over HTTP."""

    def __init__(
        self,
        handler: Any,                    # LLMHandler, or {model_name: LLMHandler}
        serve: Optional[Any] = None,     # Serve orchestrator for /v1/tasks
        embedder: Optional[Any] = None,  # memory.Embedder for /v1/embeddings
        host: str = "127.0.0.1",
        port: int = 0,
        auth_token: Optional[str] = None,
    ) -> None:
        # Multi-model serving: a dict maps the request's ``model`` field
        # to a handler (unknown names 404, OpenAI ``model_not_found``).
        # A single handler serves every request regardless of ``model``
        # — the common one-model deployment.
        if isinstance(handler, dict):
            if not handler:
                raise ValueError("handler dict must not be empty")
            self.handlers: Dict[str, Any] = dict(handler)
            self.handler = next(iter(handler.values()))  # default
        else:
            self.handlers = {}
            self.handler = handler
        self.serve = serve
        self.embedder = embedder
        self.host = host
        self.port = port
        self.auth_token = auth_token
        self._server: Optional[asyncio.AbstractServer] = None
        self._log = get_logger("server")

    def _pick_handler(self, model: Optional[str]) -> Any:
        if not self.handlers or model is None:
            return self.handler
        try:
            return self.handlers[model]
        except KeyError:
            raise _HttpError(
                404, f"model {model!r} not found; available: "
                f"{sorted(self.handlers)}", "model_not_found",
            ) from None

    # ------------------------------------------------------------------ #

    async def start(self) -> "APIServer":
        self._server = await asyncio.start_server(
            self._handle_conn, self.host, self.port
        )
        self.port = self._server.sockets[0].getsockname()[1]
        self._log.info("API server on http://%s:%d", self.host, self.port)
        return self

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
            self._server = None

    # ------------------------------------------------------------------ #
    # HTTP plumbing
    # ------------------------------------------------------------------ #

    async def _handle_conn(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        edge = _EdgeFlight()
        try:
            try:
                method, path, query, headers, body = await self._read_request(
                    reader
                )
            except _HttpError as exc:
                await self._send_error(writer, exc)
                return
            edge.received_at = time.perf_counter()
            try:
                self._check_auth(path, headers)
                await self._route(
                    method, path, query, headers, body, writer, edge
                )
                edge.outcome = "ok"
            except _HttpError as exc:
                await self._send_error(writer, exc)
                edge.outcome = "error"
            except (DeadlineExceeded, EngineOverloaded, CircuitOpenError) as exc:
                # Overload/deadline shedding is routine under load — a
                # structured client error, not a 500 with a stack trace.
                global_metrics.inc("server.shed_responses")
                await self._send_error(writer, _overload_error(exc))
                edge.outcome = "error"
            except (ConnectionError, asyncio.IncompleteReadError):
                # Routine client drop (usually mid-SSE): no error log, and
                # never write a 500 body into an already-started response.
                raise
            except Exception as exc:  # noqa: BLE001 — request boundary
                self._log.error("request failed: %s", exc, exc_info=True)
                await self._send_error(
                    writer, _HttpError(500, "internal error", "server_error")
                )
                edge.outcome = "error"
        except (ConnectionError, asyncio.IncompleteReadError):
            pass  # client went away
        finally:
            # The flight's last mark, after whatever was written: a
            # dropped client closes it too.
            edge.close()
            try:
                writer.close()
                await writer.wait_closed()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass

    async def _read_request(
        self, reader: asyncio.StreamReader
    ) -> Tuple[str, str, str, Dict[str, str], bytes]:
        try:
            head = await asyncio.wait_for(
                reader.readuntil(b"\r\n\r\n"), timeout=30.0
            )
        except (asyncio.LimitOverrunError, ValueError) as exc:
            raise _HttpError(413, "headers too large") from exc
        except asyncio.TimeoutError as exc:
            raise _HttpError(400, "timed out reading request") from exc
        if len(head) > _MAX_HEADER:
            raise _HttpError(413, "headers too large")
        lines = head.decode("latin-1").split("\r\n")
        try:
            method, path, _version = lines[0].split(" ", 2)
        except ValueError as exc:
            raise _HttpError(400, "malformed request line") from exc
        headers: Dict[str, str] = {}
        for line in lines[1:]:
            if not line:
                continue
            k, _, v = line.partition(":")
            headers[k.strip().lower()] = v.strip()
        try:
            length = int(headers.get("content-length", "0") or "0")
        except ValueError as exc:
            raise _HttpError(400, "invalid Content-Length") from exc
        if length > _MAX_BODY:
            raise _HttpError(413, "body too large")
        if length:
            # Same bound as the header read: a client that sends headers
            # then withholds the body must not pin this connection task
            # (slowloris).
            try:
                body = await asyncio.wait_for(
                    reader.readexactly(length), timeout=30.0
                )
            except asyncio.TimeoutError as exc:
                raise _HttpError(400, "timed out reading body") from exc
        else:
            body = b""
        path, _, query = path.partition("?")
        return method, path, query, headers, body

    def _check_auth(self, path: str, headers: Dict[str, str]) -> None:
        if self.auth_token is None or path == "/healthz":
            return
        got = headers.get("authorization", "")
        if not hmac.compare_digest(got, f"Bearer {self.auth_token}"):
            raise _HttpError(401, "missing or invalid bearer token",
                             "authentication_error")

    async def _send(
        self,
        writer: asyncio.StreamWriter,
        status: int,
        payload: Dict[str, Any],
        extra_headers: Optional[Dict[str, str]] = None,
    ) -> None:
        # Payload to bytes and the write, without the drain: a host-lane
        # span holds no wait, and none is left open across an await.
        with host_span("edge.write"):
            self._write(
                writer, status, json.dumps(payload).encode(),
                "application/json", extra_headers,
            )
        await writer.drain()

    async def _send_raw(
        self,
        writer: asyncio.StreamWriter,
        status: int,
        data: bytes,
        ctype: str,
        extra_headers: Optional[Dict[str, str]] = None,
    ) -> None:
        self._write(writer, status, data, ctype, extra_headers)
        await writer.drain()

    @staticmethod
    def _write(
        writer: asyncio.StreamWriter,
        status: int,
        data: bytes,
        ctype: str,
        extra_headers: Optional[Dict[str, str]] = None,
    ) -> None:
        head = (
            f"HTTP/1.1 {status} {_STATUS_TEXT.get(status, '')}\r\n"
            f"Content-Type: {ctype}\r\n"
            f"Content-Length: {len(data)}\r\n"
        )
        for key, value in (extra_headers or {}).items():
            head += f"{key}: {value}\r\n"
        writer.write(head.encode() + b"Connection: close\r\n\r\n" + data)

    async def _send_error(self, writer: asyncio.StreamWriter, exc: _HttpError) -> None:
        await self._send(
            writer, exc.status,
            {"error": {"message": exc.message, "type": exc.kind, **exc.extra}},
        )

    # Shared SSE scaffolding — one definition for every streaming route
    # (chat completions AND task streams), so status line, event shape
    # and terminator can't drift apart.

    @staticmethod
    async def _sse_start(
        writer: asyncio.StreamWriter,
        extra_headers: Optional[Dict[str, str]] = None,
    ) -> None:
        head = (
            "HTTP/1.1 200 OK\r\n"
            "Content-Type: text/event-stream\r\n"
            "Cache-Control: no-cache\r\n"
        )
        for key, value in (extra_headers or {}).items():
            head += f"{key}: {value}\r\n"
        writer.write(head.encode() + b"Connection: close\r\n\r\n")
        await writer.drain()

    @staticmethod
    def _sse_event(writer: asyncio.StreamWriter, payload: Dict[str, Any]) -> None:
        with host_span("edge.write"):
            writer.write(("data: " + json.dumps(payload) + "\n\n").encode())

    def _sse_error(self, writer: asyncio.StreamWriter, exc: Exception) -> None:
        """In-band error event: the 200 + SSE status line is already on
        the wire, so errors can't change it anymore. Reliability errors
        keep their structured type (timeout_error / overloaded_error) so
        SSE clients can tell a shed from a crash."""
        if isinstance(exc, (DeadlineExceeded, EngineOverloaded, CircuitOpenError)):
            err = _overload_error(exc)
            self._log.warning("stream shed: %s", exc)
            self._sse_event(
                writer,
                {"error": {"message": err.message, "type": err.kind, **err.extra}},
            )
            return
        self._log.error("stream failed: %s", exc, exc_info=True)
        self._sse_event(
            writer, {"error": {"message": str(exc), "type": "server_error"}}
        )

    @staticmethod
    async def _sse_done(writer: asyncio.StreamWriter) -> None:
        writer.write(b"data: [DONE]\n\n")
        await writer.drain()

    # ------------------------------------------------------------------ #
    # Routing
    # ------------------------------------------------------------------ #

    async def _route(
        self,
        method: str,
        path: str,
        query: str,
        headers: Dict[str, str],
        body: bytes,
        writer: asyncio.StreamWriter,
        edge: Optional[_EdgeFlight] = None,
    ) -> None:
        if path == "/healthz" and method == "GET":
            # Liveness AND engine liveness: a watchdog-declared stall (a
            # hung dispatch — reliability/watchdog.py) flips this to 503
            # with a retry_after hint, so load balancers stop routing to
            # a process whose device can't serve, long before clients'
            # own timeouts would reveal it.
            from pilottai_tpu.reliability import global_engine_health

            cell_health = getattr(self.handler, "health_snapshot", None)
            if callable(cell_health):
                # Serving cell (distributed/cell.py): health aggregates
                # across replicas — the cell is up while ANY replica is
                # routable; one stalled replica degrades, not grounds.
                snap = cell_health()
                status = 200 if snap.get("ok") else 503
                await self._send(writer, status, {
                    "status": "ok" if snap.get("ok") else "unhealthy",
                    **{k: v for k, v in snap.items() if k != "ok"},
                })
            elif global_engine_health.healthy():
                await self._send(writer, 200, {"status": "ok"})
            else:
                snap = global_engine_health.snapshot()
                await self._send(writer, 503, {
                    "status": "stalled",
                    "reason": snap.get("reason"),
                    "stalled_for_s": snap.get("stalled_for_s"),
                    "retry_after": snap.get("retry_after"),
                })
        elif path == "/metrics" and method == "GET":
            handler_metrics = (
                {n: _jsonable(h.get_metrics()) for n, h in self.handlers.items()}
                if self.handlers else _jsonable(self.handler.get_metrics())
            )
            # ONE snapshot shape shared with the dashboard
            # (obs.metrics_snapshot); ?format=prometheus serves the text
            # exposition a scraper consumes directly.
            snap = metrics_snapshot(component=handler_metrics)
            if parse_qs(query).get("format") == ["prometheus"]:
                await self._send_raw(
                    writer, 200, prometheus_text(snap).encode(),
                    "text/plain; version=0.0.4; charset=utf-8",
                )
            else:
                # Back-compat aliases: pre-unification clients read the
                # handler block under "handler" and the registry
                # snapshot under "global".
                snap_j = _jsonable(snap)
                await self._send(
                    writer, 200,
                    {
                        **snap_j,
                        "handler": handler_metrics,
                        "global": {
                            k: snap_j[k]
                            for k in ("uptime_s", "counters", "gauges",
                                      "histograms")
                        },
                    },
                )
        elif path == "/slo.json" and method == "GET":
            # Per-class SLO attainment / burn rate (obs/slo.py) — the
            # page an operator (or the autoscaler's dashboard) watches
            # during an incident. A serving cell aggregates per-replica
            # trackers (request-weighted attainment/burn, worst-replica
            # p99) and attaches each replica's own snapshot.
            cell_slo = getattr(self.handler, "slo_snapshot", None)
            if callable(cell_slo):
                await self._send(writer, 200, _jsonable(cell_slo()))
            else:
                from pilottai_tpu.obs import global_slo

                await self._send(writer, 200, global_slo.snapshot())
        elif path == "/topology.json" and method == "GET":
            # Disaggregated-serving topology (ISSUE 19): per-replica
            # tier roles plus the handoff counters — the page the drain
            # runbook reads before draining a prefill-tier replica
            # (docs/SERVING.md). A single engine reports itself as one
            # "mixed" replica so the shape is stable across deployments.
            from pilottai_tpu.utils.metrics import global_metrics as _gm

            cell_health = getattr(self.handler, "health_snapshot", None)
            tiers = (
                cell_health().get("tiers", {}) if callable(cell_health)
                else {"engine": "mixed"}
            )
            await self._send(writer, 200, {
                "tiers": tiers,
                "disaggregated": any(t != "mixed" for t in tiers.values()),
                "handoffs": _gm.get("cell.handoffs"),
                "handoff_fallbacks": _gm.get("cell.handoff_fallbacks"),
                "handoff_rejected": _gm.get("cell.handoff_rejected"),
                "handoff_tokens": _gm.get("cell.handoff_tokens"),
                "prefix_bypass": _gm.get("cell.tier.bypass"),
            })
        elif path == "/profile.json" and method == "GET":
            # Workload fingerprint (obs/profile.py): the rolling
            # length/arrival/class-mix shape of this deployment's
            # traffic, plus the seasonal forecast state — the input
            # `scripts/recommend.py` replays through the cost model.
            from pilottai_tpu.obs import global_profile

            await self._send(writer, 200, _jsonable(global_profile.fingerprint()))
        elif path == "/dag.json" and method == "GET":
            # Task-DAG attribution (obs/dag.py): active task summaries +
            # recent finished breakdowns with critical paths; ?task_id=
            # returns one task's full node-level ledger.
            from pilottai_tpu.obs import global_dag

            task_id = (parse_qs(query).get("task_id") or [None])[0]
            if task_id:
                described = global_dag.describe(task_id)
                if described is None:
                    raise _HttpError(404, f"no dag for task {task_id!r}")
                await self._send(writer, 200, _jsonable(described))
            else:
                await self._send(writer, 200, _jsonable(global_dag.snapshot()))
        elif path == "/v1/models" and method == "GET":
            await self._send(writer, 200, self._models())
        elif path == "/v1/chat/completions":
            if method != "POST":
                raise _HttpError(405, "POST required")
            with host_span("edge.parse"):
                req = _parse_json(body)
            await self._chat_completions(req, writer, headers, edge)
        elif path == "/v1/embeddings":
            if method != "POST":
                raise _HttpError(405, "POST required")
            await self._embeddings(_parse_json(body), writer)
        elif path == "/v1/tasks":
            if method != "POST":
                raise _HttpError(405, "POST required")
            await self._submit_task(_parse_json(body), writer, headers)
        else:
            raise _HttpError(404, f"no route for {method} {path}")

    def _models(self) -> Dict[str, Any]:
        if self.handlers:
            # Multi-model mode: the servable set IS the route map.
            names = sorted(self.handlers)
        else:
            try:
                from pilottai_tpu.models.registry import list_models

                names = list_models()
            except Exception:  # noqa: BLE001 — registry is engine-optional
                names = []
            configured = getattr(
                getattr(self.handler, "config", None), "model_name", None
            )
            if configured and configured not in names:
                names = [configured] + names
        return {
            "object": "list",
            "data": [{"id": n, "object": "model", "owned_by": "pilottai-tpu"}
                     for n in names],
        }

    # ------------------------------------------------------------------ #
    # /v1/chat/completions
    # ------------------------------------------------------------------ #

    def _gen_params(self, req: Dict[str, Any]) -> Tuple[
        List[Dict[str, Any]], Optional[List[ToolSpec]], GenerationParams, bool
    ]:
        messages = req.get("messages")
        if not isinstance(messages, list) or not messages:
            raise _HttpError(400, "'messages' must be a non-empty list")
        normed = []
        for m in messages:
            if not isinstance(m, dict) or "content" not in m:
                raise _HttpError(400, "each message needs 'role' and 'content'")
            # OpenAI's own wire shape uses content: null on assistant
            # tool-call turns — normalize rather than 500 downstream.
            normed.append({
                "role": str(m.get("role") or "user"),
                "content": "" if m["content"] is None else str(m["content"]),
            })
        messages = normed
        tools = None
        if req.get("tools"):
            tools = []
            for t in req["tools"]:
                fn = t.get("function", t) if isinstance(t, dict) else {}
                if not isinstance(fn, dict) or not fn.get("name"):
                    raise _HttpError(400, "each tool needs function.name")
                params_schema = fn.get("parameters") or {}
                if not isinstance(params_schema, dict):
                    raise _HttpError(400, "tool parameters must be an object")
                tools.append(ToolSpec(
                    name=str(fn["name"]),
                    description=str(fn.get("description", "")),
                    parameters=params_schema,
                ))
        stop = req.get("stop") or []
        if isinstance(stop, str):
            stop = [stop]
        if not isinstance(stop, list):
            raise _HttpError(400, "'stop' must be a string or list")
        rf = req.get("response_format") or {}
        if not isinstance(rf, dict):
            raise _HttpError(400, "'response_format' must be an object")
        json_schema = None
        strict = False
        if rf.get("type") == "json_schema":
            # OpenAI nests {name, schema, strict} under json_schema.
            spec = rf.get("json_schema")
            if not isinstance(spec, dict) or not isinstance(
                spec.get("schema"), dict
            ):
                raise _HttpError(
                    400, "response_format json_schema needs "
                    "{'json_schema': {'schema': {...}}}"
                )
            json_schema = spec["schema"]
            strict = bool(spec.get("strict"))
        # Absent vs present-but-invalid: a client's explicit
        # "max_tokens": 0 is a 400, not silently the 256 default
        # (`or` would swallow any falsy value).
        max_tokens = req.get("max_tokens")
        if max_tokens is None:
            max_tokens = req.get("max_completion_tokens")
        if max_tokens is None:
            max_tokens = 256
        if isinstance(max_tokens, bool) or not isinstance(max_tokens, int):
            # No coercion: 2.7 truncating to 2 (or true to 1) would run a
            # different budget than the client sent.
            raise _HttpError(400, "'max_tokens' must be an integer")
        if max_tokens < 1:
            raise _HttpError(400, "'max_tokens' must be >= 1")
        try:
            # Client values are untrusted: a non-numeric temperature or
            # seed is a 400 invalid_request_error (OpenAI parity), not a
            # 500 from int()/pydantic deep in the handler.
            params = GenerationParams(
                max_new_tokens=max_tokens,
                temperature=float(req.get("temperature", 0.7)),
                top_k=int(req.get("top_k", 0)),
                top_p=float(req.get("top_p", 1.0)),
                seed=int(req["seed"]) if req.get("seed") is not None else None,
                stop=[str(s) for s in stop],
                json_mode=rf.get("type") in ("json_object", "json_schema"),
                json_schema=json_schema,
            )
        except (TypeError, ValueError) as exc:
            # (pydantic's ValidationError subclasses ValueError)
            raise _HttpError(400, f"invalid sampling parameter: {exc}") from exc
        return messages, tools, params, strict

    def _request_deadline(
        self, req: Dict[str, Any], headers: Dict[str, str], handler: Any
    ) -> Optional[float]:
        """Derive the request's absolute monotonic deadline: body
        ``timeout`` beats the ``x-request-timeout`` header beats the
        deployment's ``ReliabilityConfig.default_timeout``; whatever wins
        is capped at ``max_timeout``. None = no deadline."""
        raw = req.get("timeout")
        if raw is None:
            raw = headers.get("x-request-timeout")
        rel = getattr(
            getattr(handler, "config", None), "reliability", None
        )
        if raw is None and rel is not None:
            raw = rel.default_timeout
        if raw is None:
            return None
        if isinstance(raw, bool) or not isinstance(raw, (int, float, str)):
            raise _HttpError(400, "'timeout' must be a number of seconds")
        try:
            t = float(raw)
        except ValueError as exc:
            raise _HttpError(
                400, "'timeout' must be a number of seconds"
            ) from exc
        if t <= 0:
            raise _HttpError(400, "'timeout' must be > 0")
        if rel is not None:
            t = min(t, rel.max_timeout)
        return time.monotonic() + t

    @staticmethod
    def _slo_class(
        req: Dict[str, Any], headers: Optional[Dict[str, str]]
    ) -> Optional[str]:
        """The request's SLO service class: body ``slo_class`` beats the
        ``x-slo-class`` header. Unknown classes are a 400 — a typo'd
        class would otherwise silently fall into the default class and
        exempt that traffic from the objective the client asked for."""
        raw = req.get("slo_class")
        if raw is None:
            raw = (headers or {}).get("x-slo-class")
        if raw is None:
            return None
        from pilottai_tpu.obs import global_slo

        if not isinstance(raw, str) or raw not in global_slo.classes:
            raise _HttpError(
                400, f"unknown slo_class {raw!r}; available: "
                f"{sorted(global_slo.classes)}"
            )
        return raw

    @staticmethod
    def _priority(
        req: Dict[str, Any], headers: Optional[Dict[str, str]]
    ) -> Optional[int]:
        """The request's scheduling priority (pilottai_tpu/sched/):
        body ``priority`` beats the ``x-priority`` header; accepts the
        rung number (0-3) or its name (low/normal/high/critical).
        Out-of-lattice values are a 400 — a typo'd priority silently
        falling to NORMAL would exempt the request from the ordering
        the client asked for."""
        raw = req.get("priority")
        if raw is None:
            raw = (headers or {}).get("x-priority")
        if raw is None:
            return None
        names = {"low": 0, "normal": 1, "high": 2, "critical": 3}
        if isinstance(raw, str) and raw.strip().lower() in names:
            return names[raw.strip().lower()]
        try:
            if isinstance(raw, bool) or (
                isinstance(raw, float) and not raw.is_integer()
            ):
                # int(2.7) would silently truncate to HIGH — the same
                # reject-don't-coerce contract as everything else here.
                value = None
            else:
                value = int(raw)
        except (TypeError, ValueError):
            value = None
        if value is None or not 0 <= value <= 3:
            raise _HttpError(
                400, "'priority' must be 0-3 or one of "
                "low/normal/high/critical"
            )
        return value

    @staticmethod
    def _session_id(
        req: Dict[str, Any], headers: Optional[Dict[str, str]]
    ) -> Optional[str]:
        """The request's KV-cache session handle: body ``session_id``
        beats the ``x-session-id`` header. Sanitized with the same
        charset as request ids — a malformed id is a 400, not a silent
        anonymous request (the client asked for lineage pinning and
        would otherwise re-prefill every turn without any signal
        why)."""
        raw = req.get("session_id")
        if raw is None:
            raw = (headers or {}).get("x-session-id")
        if raw is None:
            return None
        if not isinstance(raw, str) or not _REQUEST_ID_RE.fullmatch(raw):
            raise _HttpError(
                400, "'session_id' must be 1-64 characters of "
                "[A-Za-z0-9._-]"
            )
        return raw

    @staticmethod
    def _trace_id(headers: Optional[Dict[str, str]]) -> str:
        """The request's flight-recorder id: accept the client's
        ``x-request-id`` (sanitized) or mint one. Echoed back as a
        response header and threaded through handler → batcher spans,
        logs and black-box dumps (docs/OBSERVABILITY.md)."""
        raw = (headers or {}).get("x-request-id", "")
        if raw and _REQUEST_ID_RE.fullmatch(raw):
            return raw
        return uuid.uuid4().hex[:16]

    async def _chat_completions(
        self,
        req: Dict[str, Any],
        writer: asyncio.StreamWriter,
        headers: Optional[Dict[str, str]] = None,
        edge: Optional[_EdgeFlight] = None,
    ) -> None:
        trace_id = self._trace_id(headers)
        # Root span of the request's trace: the handler's engine.generate
        # span nests under it (same asyncio task), the batcher's emitted
        # span under that — one tree, server → handler → batcher. It
        # stays out of the profiler's host lanes (utils/tracing.py
        # host_span): it would cover every gap of the request's seconds.
        with global_tracer.span(
            "server.request", trace_id=trace_id,
            route="/v1/chat/completions",
        ):
            await self._chat_completions_traced(
                req, writer, headers, trace_id, edge
            )

    def _chat_params(
        self, req: Dict[str, Any], headers: Dict[str, str], trace_id: str,
    ) -> Tuple[Any, List[Any], Optional[List[ToolSpec]], GenerationParams, str]:
        """Body and headers to the handler's arguments: ``(handler,
        messages, tools, params, model)``. Raises ``_HttpError`` for
        what this deployment cannot serve."""
        messages, tools, params, strict = self._gen_params(req)
        handler = self._pick_handler(req.get("model"))
        deadline = self._request_deadline(req, headers, handler)
        params = params.model_copy(update={"trace_id": trace_id})
        if deadline is not None:
            params = params.model_copy(update={"deadline": deadline})
        slo_class = self._slo_class(req, headers)
        if slo_class is not None:
            params = params.model_copy(update={"slo_class": slo_class})
        session_id = self._session_id(req, headers)
        if session_id is not None:
            params = params.model_copy(update={"session_id": session_id})
        priority = self._priority(req, headers)
        if priority is not None:
            params = params.model_copy(update={"priority": priority})
        model = req.get("model") or getattr(
            getattr(handler, "config", None), "model_name", "default"
        )
        if params.json_schema is not None and strict:
            # OpenAI strict-mode parity: a schema the deployment cannot
            # enforce is a 400 up front, never a 200 whose body silently
            # degraded to the generic JSON grammar.
            support = getattr(
                getattr(handler, "backend", None), "schema_support", None
            )
            reason = (
                support(params.json_schema) if support is not None
                else "this model deployment cannot enforce json_schema"
            )
            if reason is not None:
                raise _HttpError(
                    400, f"response_format json_schema with strict=true "
                    f"is not enforceable here: {reason}"
                )
        return handler, messages, tools, params, model

    async def _chat_completions_traced(
        self,
        req: Dict[str, Any],
        writer: asyncio.StreamWriter,
        headers: Optional[Dict[str, str]],
        trace_id: str,
        edge: Optional[_EdgeFlight] = None,
    ) -> None:
        with host_span("edge.parse"):
            handler, messages, tools, params, model = self._chat_params(
                req, headers or {}, trace_id
            )
        if edge is not None:
            # The request is the handler's from here: open its flight
            # (closed by _handle_conn once the reply is written).
            params = params.model_copy(
                update={"flight_id": edge.open(trace_id)}
            )
        rid = f"chatcmpl-{uuid.uuid4().hex[:24]}"
        created = int(time.time())

        if req.get("stream"):
            await self._sse_start(writer, {"x-request-id": trace_id})

            def chunk(delta: Dict[str, Any], finish: Optional[str],
                      **extra: Any) -> None:
                self._sse_event(writer, {
                    "id": rid, "object": "chat.completion.chunk",
                    "created": created, "model": model,
                    "choices": [{
                        "index": 0, "delta": delta,
                        "finish_reason": finish,
                    }],
                    **extra,
                })

            try:
                chunk({"role": "assistant"}, None)
                text_parts: List[str] = []
                stream_info: Dict[str, Any] = {}
                async for delta in handler.astream(
                    messages, tools=tools, params=params, info=stream_info
                ):
                    text_parts.append(delta)
                    chunk({"content": delta}, None)
                    await writer.drain()
                # Streamed function calling: the engine's tool protocol
                # is JSON text, so calls are parseable only once the
                # stream ends — emit them as one final tool_calls delta
                # (clients that only read content still saw the text).
                finish = stream_info.get("finish_reason", "stop")
                if tools:
                    from pilottai_tpu.engine.base import parse_tool_calls

                    calls = parse_tool_calls(
                        "".join(text_parts), [t.name for t in tools]
                    )
                    if calls:
                        finish = "tool_calls"
                        chunk({"tool_calls": [{
                            "index": i, "id": tc.id, "type": "function",
                            "function": {
                                "name": tc.name,
                                "arguments": json.dumps(tc.arguments),
                            },
                        } for i, tc in enumerate(calls)]}, None)
                extra: Dict[str, Any] = {}
                if params.json_schema is not None:
                    # Non-stream parity: streamed clients must also be
                    # able to tell enforced from best-effort output.
                    extra["schema_enforced"] = bool(
                        stream_info.get("schema_enforced")
                    )
                if "completion_tokens" in stream_info:
                    extra["usage"] = {
                        "completion_tokens": stream_info["completion_tokens"],
                    }
                chunk({}, finish, **extra)
            except (ConnectionError, asyncio.CancelledError):
                raise  # client gone / shutdown: astream's finally cancels
            except Exception as exc:  # noqa: BLE001 — surface in-band
                self._sse_error(writer, exc)
            await self._sse_done(writer)
            return

        response = await handler.generate_response(
            messages, tools=tools, params=params
        )
        message: Dict[str, Any] = {
            "role": "assistant", "content": response.content,
        }
        if response.tool_calls:
            message["tool_calls"] = [{
                "id": tc.id, "type": "function",
                "function": {
                    "name": tc.name,
                    "arguments": json.dumps(tc.arguments),
                },
            } for tc in response.tool_calls]
        payload: Dict[str, Any] = {
            "id": rid, "object": "chat.completion",
            "created": created, "model": response.model or model,
            "choices": [{
                "index": 0, "message": message,
                "finish_reason": response.finish_reason or "stop",
            }],
            "usage": {
                "prompt_tokens": response.usage.prompt_tokens,
                "completion_tokens": response.usage.completion_tokens,
                "total_tokens": response.usage.total_tokens,
            },
        }
        if params.json_schema is not None:
            # Non-strict requests proceed on best effort; tell the client
            # whether the output was actually DFA-enforced (mock and
            # non-schema backends report not-enforced rather than None —
            # the field exists exactly so clients never have to guess).
            payload["schema_enforced"] = bool(response.schema_enforced)
        await self._send(
            writer, 200, payload, extra_headers={"x-request-id": trace_id}
        )

    # ------------------------------------------------------------------ #
    # /v1/embeddings
    # ------------------------------------------------------------------ #

    async def _embeddings(
        self, req: Dict[str, Any], writer: asyncio.StreamWriter
    ) -> None:
        if self.embedder is None:
            raise _HttpError(
                503, "no embedder attached to this endpoint", "server_error"
            )
        texts = req.get("input")
        if isinstance(texts, str):
            texts = [texts]
        if (
            not isinstance(texts, list) or not texts
            or not all(isinstance(t, str) for t in texts)
        ):
            raise _HttpError(400, "'input' must be a string or list of strings")
        # encode() is synchronous jit compute behind a thread lock — keep
        # the event loop responsive (SURVEY §7 hard part 5).
        loop = asyncio.get_running_loop()
        vecs = await loop.run_in_executor(
            None, self.embedder.encode, list(texts)
        )
        # Exact usage: what the encoder actually consumed (its own
        # tokenizer, its own max_len truncation) — clients metering on
        # the OpenAI usage field must not get a chars/4 guess.
        tok = getattr(self.embedder, "tokenizer", None)
        max_len = getattr(self.embedder, "max_len", None)
        if tok is not None:
            n_tokens = sum(
                len(tok.encode(t)[:max_len] if max_len else tok.encode(t))
                for t in texts
            )
        else:
            n_tokens = sum(len(t) // 4 for t in texts)
        await self._send(writer, 200, {
            "object": "list",
            "model": getattr(
                getattr(self.embedder, "cfg", None), "name", "embedder"
            ),
            "data": [
                {"object": "embedding", "index": i, "embedding": v.tolist()}
                for i, v in enumerate(vecs)
            ],
            "usage": {
                "prompt_tokens": n_tokens,
                "total_tokens": n_tokens,
            },
        })

    # ------------------------------------------------------------------ #
    # /v1/tasks
    # ------------------------------------------------------------------ #

    async def _submit_task(
        self,
        req: Dict[str, Any],
        writer: asyncio.StreamWriter,
        headers: Optional[Dict[str, str]] = None,
    ) -> None:
        trace_id = self._trace_id(headers)
        # Same trace posture as chat completions: serve.execute_task's
        # span (and every agent/engine span under it) joins this trace,
        # so one x-request-id greps an entire task execution.
        with global_tracer.span(
            "server.request", trace_id=trace_id, route="/v1/tasks"
        ):
            await self._submit_task_traced(req, writer, headers, trace_id)

    async def _submit_task_traced(
        self,
        req: Dict[str, Any],
        writer: asyncio.StreamWriter,
        headers: Optional[Dict[str, str]],
        trace_id: str,
    ) -> None:
        if self.serve is None:
            raise _HttpError(
                503, "no orchestrator attached to this endpoint",
                "server_error",
            )
        task = req.get("task") or req.get("description")
        if not task:
            raise _HttpError(400, "'task' (or 'description') is required")
        # Same precedence and caps as chat completions: body beats the
        # x-request-timeout header beats reliability.default_timeout, all
        # capped at max_timeout. Serve threads the budget into
        # ``task.timeout`` so agents honor it too.
        timeout = req.get("timeout")
        if timeout is None:
            timeout = (headers or {}).get("x-request-timeout")
        rel = getattr(
            getattr(self.handler, "config", None), "reliability", None
        )
        if timeout is None and rel is not None:
            timeout = rel.default_timeout
        try:
            timeout = float(timeout) if timeout is not None else None
        except (TypeError, ValueError) as exc:
            raise _HttpError(400, "'timeout' must be a number") from exc
        if timeout is not None and timeout <= 0:
            raise _HttpError(400, "'timeout' must be > 0")
        if timeout is not None and rel is not None:
            timeout = min(timeout, rel.max_timeout)

        def result_payload(result) -> Dict[str, Any]:
            return {
                "object": "task.result",
                "success": result.success,
                "output": _jsonable(result.output),
                "error": result.error,
                "execution_time": result.execution_time,
                "metadata": _jsonable(result.metadata),
            }

        if req.get("stream"):
            # Live lifecycle feed: subscribe BEFORE submitting so the
            # received/analyzed/queued events aren't missed, then SSE
            # every event (subtask events roll up) and close with the
            # final result + [DONE]. Subscription and header flush both
            # live INSIDE the try: a client that drops before the
            # headers drain must still unsubscribe (leak regression).
            task_obj = self.serve.prepare_task(task)
            q = self.serve.subscribe_events(task_obj.id)
            exec_task = None
            getter = None
            try:
                await self._sse_start(writer, {"x-request-id": trace_id})
                exec_task = asyncio.ensure_future(
                    self.serve.execute_task(task_obj, timeout=timeout)
                )
                while not exec_task.done():
                    getter = asyncio.ensure_future(q.get())
                    done, _ = await asyncio.wait(
                        {getter, exec_task},
                        return_when=asyncio.FIRST_COMPLETED,
                    )
                    if getter in done:
                        self._sse_event(writer, _jsonable(getter.result()))
                        getter = None
                        await writer.drain()
                    else:
                        getter.cancel()
                        getter = None
                while not q.empty():  # events emitted before completion
                    self._sse_event(writer, _jsonable(q.get_nowait()))
                result = await exec_task
                self._sse_event(writer, result_payload(result))
            except (ConnectionError, asyncio.CancelledError):
                raise
            except Exception as exc:  # noqa: BLE001 — surface in-band
                self._sse_error(writer, exc)
            finally:
                self.serve.unsubscribe_events(task_obj.id, q)
                # Handler cancellation mid-asyncio.wait leaves BOTH
                # futures pending — cancel whatever is still in flight.
                if getter is not None and not getter.done():
                    getter.cancel()
                if exec_task is not None and not exec_task.done():
                    exec_task.cancel()
            await self._sse_done(writer)
            return

        try:
            result = await self.serve.execute_task(task, timeout=timeout)
        except asyncio.TimeoutError:
            # The caller's budget elapsed before the orchestrator finished
            # (execute_task threaded the same budget into task.timeout, so
            # the execution side is winding the task down too).
            raise _HttpError(
                408, f"task did not complete within {timeout}s",
                "timeout_error",
            ) from None
        await self._send(
            writer, 200, result_payload(result),
            extra_headers={"x-request-id": trace_id},
        )


def _parse_json(body: bytes) -> Dict[str, Any]:
    try:
        data = json.loads(body or b"{}")
    except json.JSONDecodeError as exc:
        raise _HttpError(400, f"invalid JSON body: {exc}") from exc
    if not isinstance(data, dict):
        raise _HttpError(400, "request body must be a JSON object")
    return data


def _jsonable(value: Any) -> Any:
    """Best-effort conversion to JSON-serializable structures (task
    outputs and metrics may carry arbitrary objects)."""
    try:
        json.dumps(value)
        return value
    except (TypeError, ValueError):
        if isinstance(value, dict):
            return {str(k): _jsonable(v) for k, v in value.items()}
        if isinstance(value, (list, tuple)):
            return [_jsonable(v) for v in value]
        return repr(value)
