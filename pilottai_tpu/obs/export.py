"""Standard-format exporters: Prometheus text exposition and
Chrome/Perfetto ``trace_event`` JSON.

One ``metrics_snapshot`` feeds every metrics endpoint — the API server's
``/metrics`` and the dashboard's ``/metrics.json`` previously built
different shapes from the same registry, so dashboards and scrapers
could not share tooling. Both now serve this snapshot, and both accept
``?format=prometheus`` for the text exposition a Prometheus scraper (or
``promtool check metrics``) consumes directly.

The Perfetto exporter turns finished span trees plus engine step-ring
records into ``{"traceEvents": [...]}`` JSON loadable at
https://ui.perfetto.dev (or chrome://tracing). Spans become complete
("X") slices — one track per trace id, nesting by time containment —
and engine steps become counter ("C") tracks (slot occupancy, tokens
per chunk, free KV pages, queue depth), on the same
``time.perf_counter`` clock so host spans line up with the device-side
``jax.profiler.TraceAnnotation`` markers the tracer already emits into
XLA traces.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Iterable, List, Optional

from pilottai_tpu.utils.metrics import MetricsRegistry, global_metrics
from pilottai_tpu.utils.tracing import Span

_NAME_OK = re.compile(r"[^a-zA-Z0-9_:]")
_LABEL_ESC = str.maketrans({"\\": r"\\", '"': r"\"", "\n": r"\n"})


def metrics_snapshot(
    component: Optional[Any] = None,
    registry: MetricsRegistry = global_metrics,
) -> Dict[str, Any]:
    """THE metrics snapshot: registry counters/gauges/histogram summaries
    plus an optional component's ``get_metrics()`` dict (a Serve, an
    LLMHandler, a handler map). Component failures degrade to an error
    entry — a metrics endpoint must never 500 because one source did."""
    snap = registry.snapshot()
    if component is not None:
        if hasattr(component, "get_metrics"):
            try:
                snap["component"] = component.get_metrics()
            except Exception as exc:  # noqa: BLE001 — metrics must not raise
                snap["component"] = {"error": str(exc)}
        else:
            snap["component"] = component
    return snap


def _metric_name(prefix: str, name: str) -> str:
    name = _NAME_OK.sub("_", name)
    if not re.match(r"[a-zA-Z_:]", name):
        name = "_" + name
    return f"{prefix}_{name}" if prefix else name


def _fmt(value: Any) -> Optional[str]:
    if isinstance(value, bool):
        return "1" if value else "0"
    if isinstance(value, (int, float)):
        return repr(float(value))
    return None


def prometheus_text(
    snapshot: Dict[str, Any], prefix: str = "pilottai"
) -> str:
    """Render a ``metrics_snapshot`` dict as Prometheus text exposition
    (version 0.0.4). Counters/gauges map directly; histograms render as
    summaries (quantile-labelled lines + ``_count``/``_sum``); numeric
    leaves of the component dict flatten under ``<prefix>_component_``.
    """
    lines: List[str] = []

    def emit(name: str, kind: str, samples: Iterable[str]) -> None:
        lines.append(f"# TYPE {name} {kind}")
        lines.extend(samples)

    for raw, value in sorted(snapshot.get("counters", {}).items()):
        val = _fmt(value)
        if val is not None:
            name = _metric_name(prefix, raw)
            emit(name, "counter", [f"{name} {val}"])
    gauges = dict(snapshot.get("gauges", {}))
    if "uptime_s" in snapshot:
        gauges.setdefault("uptime_s", snapshot["uptime_s"])
    for raw, value in sorted(gauges.items()):
        val = _fmt(value)
        if val is not None:
            name = _metric_name(prefix, raw)
            emit(name, "gauge", [f"{name} {val}"])
    for raw, summary in sorted(snapshot.get("histograms", {}).items()):
        name = _metric_name(prefix, raw)
        samples = []
        for q_label, key in (("0.5", "p50"), ("0.9", "p90"), ("0.99", "p99")):
            val = _fmt(summary.get(key))
            if val is not None:
                samples.append(f'{name}{{quantile="{q_label}"}} {val}')
        count = summary.get("count", 0)
        mean = summary.get("mean") or 0.0
        samples.append(f"{name}_count {_fmt(count)}")
        samples.append(f"{name}_sum {_fmt(count * mean)}")
        emit(name, "summary", samples)

    component = snapshot.get("component")
    if isinstance(component, dict):
        flat: Dict[str, Any] = {}
        _flatten(component, "", flat)
        for raw, value in sorted(flat.items()):
            val = _fmt(value)
            if val is not None:
                name = _metric_name(f"{prefix}_component", raw)
                emit(name, "gauge", [f"{name} {val}"])
    return "\n".join(lines) + "\n"


def _flatten(tree: Dict[str, Any], path: str, out: Dict[str, Any]) -> None:
    for key, value in tree.items():
        sub = f"{path}_{key}" if path else str(key)
        if isinstance(value, dict):
            _flatten(value, sub, out)
        elif isinstance(value, (int, float, bool)):
            out[sub] = value


def export_completeness(
    registry: MetricsRegistry = global_metrics, prefix: str = "pilottai"
) -> List[str]:
    """Walk the registry's DECLARED series and verify each reaches both
    export surfaces: the ``metrics_snapshot`` dict and the Prometheus
    text exposition. Returns the list of problems (empty = fully wired).

    This is the ship-gate for new metrics (tests/test_slo.py): a series
    a subsystem registers via ``MetricsRegistry.declare`` but that never
    surfaces in ``/metrics`` — because an exporter filters it, renames
    it into a collision, or the declaration kind mismatches the writer —
    fails CI instead of shipping half-wired."""
    problems: List[str] = []
    snap = metrics_snapshot(registry=registry)
    text = prometheus_text(snap, prefix=prefix)
    section = {"counter": "counters", "gauge": "gauges",
               "histogram": "histograms"}
    # How each declared kind renders in the exposition (histograms are
    # emitted as Prometheus summaries).
    prom_kind = {"counter": "counter", "gauge": "gauge",
                 "histogram": "summary"}
    exposed: Dict[str, set] = {}
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            _, _, mname, mkind = line.split()
            exposed.setdefault(mname, set()).add(mkind)
    for name, kind in sorted(registry.declared().items()):
        if name not in snap.get(section[kind], {}):
            problems.append(f"{name} ({kind}): missing from metrics_snapshot")
            continue
        # Declared one kind, written as another: the declaration's
        # zero-fill makes the declared section look populated while the
        # real data lives in a sibling section under the same name.
        others = [
            k for k, sec in section.items()
            if k != kind and name in snap.get(sec, {})
        ]
        if others:
            problems.append(
                f"{name}: declared {kind} but also written as "
                f"{'/'.join(others)}"
            )
        kinds = exposed.get(_metric_name(prefix, name))
        if not kinds:
            problems.append(
                f"{name} ({kind}): missing from Prometheus exposition"
            )
        elif prom_kind[kind] not in kinds:
            problems.append(
                f"{name} ({kind}): exposed as {'/'.join(sorted(kinds))}, "
                f"expected {prom_kind[kind]}"
            )
    return problems


# ---------------------------------------------------------------------- #
# Perfetto / Chrome trace_event
# ---------------------------------------------------------------------- #

_SPAN_PID = 1
_ENGINE_PID = 2

# Step-record fields exported as counter tracks.
_STEP_COUNTERS = (
    "slots_active", "tokens", "queue_depth", "kv_pages_free",
    "chunk_blocks", "utilization",
)


def perfetto_trace(
    spans: Iterable[Any],
    steps: Optional[Iterable[Dict[str, Any]]] = None,
) -> Dict[str, Any]:
    """Build a Chrome ``trace_event`` JSON object from finished spans
    (``Span`` objects or their ``to_dict`` form) and optional step-ring
    records. Each trace id gets its own named thread track so concurrent
    requests render side by side; parent/child nesting is preserved by
    time containment within the track."""
    events: List[Dict[str, Any]] = []
    tids: Dict[str, int] = {}

    def tid_for(trace_id: str) -> int:
        if trace_id not in tids:
            tids[trace_id] = len(tids) + 1
            events.append({
                "ph": "M", "name": "thread_name", "pid": _SPAN_PID,
                "tid": tids[trace_id],
                "args": {"name": f"trace {trace_id}"},
            })
        return tids[trace_id]

    for span in spans:
        d = span.to_dict() if isinstance(span, Span) else dict(span)
        start, end = d.get("start"), d.get("end")
        if start is None or end is None:
            continue  # still open — a complete event needs both edges
        args = {
            "trace_id": d.get("trace_id"),
            "span_id": d.get("span_id"),
            "parent_id": d.get("parent_id"),
            **(d.get("attributes") or {}),
        }
        events.append({
            "name": d.get("name", "span"),
            "ph": "X",
            "ts": start * 1e6,           # perf_counter seconds → µs
            "dur": max(end - start, 0.0) * 1e6,
            "pid": _SPAN_PID,
            "tid": tid_for(str(d.get("trace_id"))),
            "cat": "request",
            "args": args,
        })

    if steps:
        named_engine = False
        for rec in steps:
            ts = rec.get("ts_mono")
            if ts is None:
                continue
            if not named_engine:
                named_engine = True
                events.append({
                    "ph": "M", "name": "process_name", "pid": _ENGINE_PID,
                    "tid": 0, "args": {"name": "engine steps"},
                })
            kind = rec.get("kind", "step")
            for field in _STEP_COUNTERS:
                if field in rec:
                    events.append({
                        "name": f"engine/{field}",
                        "ph": "C",
                        "ts": ts * 1e6,
                        "pid": _ENGINE_PID,
                        "args": {field: rec[field]},
                    })
            if kind not in ("engine.chunk",):
                # Discrete events (admits, sheds, handler requests) show
                # as instants on the engine track.
                events.append({
                    "name": kind,
                    "ph": "i",
                    "s": "p",
                    "ts": ts * 1e6,
                    "pid": _ENGINE_PID,
                    "tid": 0,
                    "args": {
                        k: v for k, v in rec.items()
                        if k not in ("ts", "ts_mono", "kind")
                        and isinstance(v, (int, float, str, bool))
                    },
                })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# ---------------------------------------------------------------------- #
# Phase percentiles (bench / capacity planning)
# ---------------------------------------------------------------------- #

_PHASES = {
    "queue_wait": "request.queue_wait_s",
    "prefill": "engine.prefill_latency",
    "ttft": "request.ttft_s",
    "tpot": "request.tpot_s",
    "itl": "request.itl_s",
    "e2e": "request.e2e_s",
}


def phase_summary(
    registry: MetricsRegistry = global_metrics,
) -> Dict[str, Dict[str, Any]]:
    """Per-phase latency percentiles (ms) from the flight-recorder
    histograms — the breakdown bench.py emits so perf PRs get a
    phase-attributed trajectory instead of an aggregate step rate.
    Percentiles are window-aware (the most recent ≤4096 samples)."""
    hists = registry.snapshot()["histograms"]
    out: Dict[str, Dict[str, Any]] = {}
    for phase, metric in _PHASES.items():
        summary = hists.get(metric)
        if not summary or not summary.get("count"):
            continue
        out[phase] = {
            "p50_ms": _ms(summary.get("p50")),
            "p90_ms": _ms(summary.get("p90")),
            "p99_ms": _ms(summary.get("p99")),
            "count": summary.get("count"),
        }
    return out


def _ms(seconds: Optional[float]) -> Optional[float]:
    return None if seconds is None else round(seconds * 1e3, 3)
