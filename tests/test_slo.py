"""SLO observability layer tests: per-class attainment/burn-rate
arithmetic, flight-class separation, Prometheus exposition of the new
series, the export-completeness wiring check, and the HTTP edge's
slo_class threading."""

import asyncio
import json

import pytest

from pilottai_tpu.core.config import LLMConfig
from pilottai_tpu.engine.handler import LLMHandler
from pilottai_tpu.engine.mock import MockBackend
from pilottai_tpu.engine.types import GenerationParams
from pilottai_tpu.obs import (
    export_completeness,
    global_flight,
    global_slo,
    metrics_snapshot,
    prometheus_text,
)
from pilottai_tpu.obs.slo import DEFAULT_CLASSES, SLOClass, SLOTracker
from pilottai_tpu.utils.metrics import MetricsRegistry, global_metrics


def _mock_handler(**mock_kwargs) -> LLMHandler:
    return LLMHandler(
        LLMConfig(provider="mock", model_name="mock-slo"),
        backend=MockBackend(**mock_kwargs),
    )


# ---------------------------------------------------------------------- #
# Tracker arithmetic
# ---------------------------------------------------------------------- #


def test_burn_rate_arithmetic_on_synthetic_miss_pattern():
    """Burn rate = miss rate over the burn window ÷ budgeted miss rate.
    A 99% objective budgets 1% misses: 10 misses in 100 requests burns
    at 10x; zero misses burns at 0."""
    registry = MetricsRegistry()
    tracker = SLOTracker(
        classes=[SLOClass(name="interactive", ttft_s=1.0,
                          attainment_target=0.99)],
        registry=registry,
    )
    for i in range(100):
        # Every 10th request misses its TTFT target.
        tracker.record(
            "interactive", ttft_s=5.0 if i % 10 == 0 else 0.1, ok=True
        )
    g = registry.snapshot()["gauges"]
    assert g["slo.interactive.attainment"] == pytest.approx(0.90)
    assert g["slo.interactive.burn_rate"] == pytest.approx(10.0)
    assert registry.get("slo.interactive.requests") == 100
    assert registry.get("slo.interactive.missed") == 10

    # Failures are misses regardless of timing — a shed request consumed
    # budget even though no latency was observed.
    tracker.record("interactive", ok=False)
    assert registry.get("slo.interactive.missed") == 11

    # An all-met stream converges attainment back up and burn reflects
    # the window's miss fraction, not all-time counters.
    tracker2 = SLOTracker(
        classes=[SLOClass(name="batch", ttft_s=10.0,
                          attainment_target=0.95)],
        registry=MetricsRegistry(), window=50,
    )
    for _ in range(50):
        tracker2.record("batch", ttft_s=0.5)
    assert tracker2.snapshot()["batch"]["attainment"] == 1.0
    assert tracker2.snapshot()["batch"]["burn_rate"] == 0.0


def test_burn_window_outlives_the_attainment_count_window():
    """Review regression: a fixed maxlen=window ledger silently shrank
    the 300 s burn window to ~window/rate seconds at high request rates.
    Misses older than the last `window` entries but inside the burn
    window must still burn budget (and attainment stays count-bounded)."""
    import time as _time

    registry = MetricsRegistry()
    tracker = SLOTracker(
        classes=[SLOClass(name="interactive", ttft_s=1.0,
                          attainment_target=0.99)],
        registry=registry, window=100, burn_window_s=300.0,
    )
    t0 = _time.monotonic()
    # 100 misses, then 100 hits, all within 20 s of "now": the count
    # window (last 100) is all hits, the burn window sees all 200.
    for i in range(100):
        tracker.record("interactive", ttft_s=5.0, at=t0 + i * 0.05)
    for i in range(100):
        tracker.record("interactive", ttft_s=0.1, at=t0 + 5.0 + i * 0.05)
    g = registry.snapshot()["gauges"]
    assert g["slo.interactive.attainment"] == pytest.approx(1.0)
    assert g["slo.interactive.burn_rate"] == pytest.approx(50.0)  # 0.5/0.01


def test_burn_rate_decays_after_traffic_stops():
    """Review regression: the gauges are only written when a flight
    finishes, so a scaler reading them raw after an outage-then-silence
    would see the final burn value forever. refresh_gauges recomputes
    against NOW; the autoscaler calls it before every read."""
    import time as _time

    registry = MetricsRegistry()
    tracker = SLOTracker(registry=registry, burn_window_s=300.0)
    old = _time.monotonic() - 400.0  # outside the burn window by now
    for _ in range(10):
        tracker.record("interactive", ok=False, at=old)
    # Frozen at record time: every request in the then-current window
    # missed, so the gauge reads full burn.
    assert registry.snapshot()["gauges"]["slo.interactive.burn_rate"] > 1.0
    tracker.refresh_gauges()
    g = registry.snapshot()["gauges"]
    assert g["slo.interactive.burn_rate"] == 0.0
    # Attainment is count-windowed (those misses are still the last
    # 1024 flights) — only the TIME-based burn signal decays.
    assert g["slo.interactive.attainment"] == 0.0


def test_unconstrained_and_unobserved_dimensions_do_not_miss():
    """None targets and unobserved dimensions never fail a request — a
    1-token reply has no TPOT; a class without an e2e target ignores
    e2e entirely."""
    cls = SLOClass(name="x", ttft_s=1.0, tpot_s=None, e2e_s=None)
    assert cls.met(ttft_s=0.5, tpot_s=99.0, e2e_s=99.0)
    assert cls.met(ttft_s=None, tpot_s=None, e2e_s=None)
    assert not cls.met(ttft_s=2.0, tpot_s=None, e2e_s=None)


def test_unknown_class_falls_back_to_default():
    registry = MetricsRegistry()
    tracker = SLOTracker(registry=registry)
    tracker.record("no-such-class", ttft_s=0.1)
    assert registry.get("slo.interactive.requests") == 1


def test_slo_reset_clears_windows_but_not_counters():
    registry = MetricsRegistry()
    tracker = SLOTracker(registry=registry)
    tracker.record("interactive", ttft_s=99.0)  # miss
    assert registry.snapshot()["gauges"]["slo.interactive.attainment"] == 0.0
    tracker.reset()
    snap = tracker.snapshot()["interactive"]
    assert snap["window"] == 0
    assert snap["attainment"] == 1.0
    assert snap["burn_rate"] == 0.0
    # Cumulative counters survive — bench sections measure by delta.
    assert registry.get("slo.interactive.requests") == 1


# ---------------------------------------------------------------------- #
# Flight integration: per-class separation
# ---------------------------------------------------------------------- #


@pytest.mark.asyncio
async def test_per_class_flight_separation_when_interleaved():
    """Interactive and batch requests interleaving through one handler
    must land in their OWN class ledgers (counters and per-class
    histograms), not blend."""
    handler = _mock_handler(latency=0.002)
    global_metrics.reset_histograms("slo.")
    base = (
        global_metrics.get("slo.interactive.requests"),
        global_metrics.get("slo.batch.requests"),
    )

    async def one(i):
        params = GenerationParams(
            slo_class="interactive" if i % 2 == 0 else "batch",
            max_new_tokens=8,
        )
        await handler.generate_response([f"ping {i}"], params=params)

    await asyncio.gather(*[one(i) for i in range(8)])
    assert (
        global_metrics.get("slo.interactive.requests") - base[0] == 4
    )
    assert global_metrics.get("slo.batch.requests") - base[1] == 4
    hists = global_metrics.snapshot()["histograms"]
    assert hists["slo.interactive.ttft_s"]["count"] >= 4
    assert hists["slo.batch.ttft_s"]["count"] >= 4


@pytest.mark.asyncio
async def test_slo_class_defaults_when_absent():
    """A request with no class lands in the default class — no traffic
    is exempt from SLO accounting."""
    handler = _mock_handler(latency=0.001)
    base = global_metrics.get("slo.interactive.requests")
    await handler.apredict("untagged request")
    assert global_metrics.get("slo.interactive.requests") == base + 1


@pytest.mark.asyncio
async def test_orchestrator_task_priority_maps_to_slo_class():
    """Agent LLM steps carry the task-kind class: LOW-priority tasks run
    as batch, NORMAL as interactive."""
    from pilottai_tpu.core.agent import BaseAgent
    from pilottai_tpu.core.config import AgentConfig
    from pilottai_tpu.core.task import Task

    agent = BaseAgent(
        config=AgentConfig(role="worker"), llm=_mock_handler()
    )
    assert agent._slo_class_for(Task(description="x", priority="low")) == (
        "batch"
    )
    assert agent._slo_class_for(Task(description="x")) == "interactive"
    assert agent._slo_class_for(None) == "interactive"

    await agent.start()
    base = global_metrics.get("slo.batch.requests")
    await agent.execute_task(Task(description="background sweep",
                                  priority="low"))
    await agent.stop()
    # Every LLM step of the LOW-priority task (analysis, planning,
    # evaluation) recorded as batch.
    assert global_metrics.get("slo.batch.requests") >= base + 2


# ---------------------------------------------------------------------- #
# Exposition: Prometheus + export completeness
# ---------------------------------------------------------------------- #


def test_prometheus_exposition_carries_slo_and_attribution_series():
    """slo.* / engine.mfu / engine.collective_frac surface in the text
    exposition as parseable sample lines (declared series appear even
    before first observation)."""
    registry = MetricsRegistry()
    SLOTracker(registry=registry)
    from pilottai_tpu.obs.attribution import DeviceTimeAttributor

    attr = DeviceTimeAttributor(registry=registry)
    attr.configure(flops_per_token=1e9, device_kind="cpu",
                   mesh_axes=("model", "data"))
    attr.record("decode", 0.01, tokens=4)
    text = prometheus_text(metrics_snapshot(registry=registry))
    for needle in (
        "pilottai_slo_interactive_attainment",
        "pilottai_slo_interactive_burn_rate",
        "pilottai_slo_batch_attainment",
        "pilottai_slo_interactive_ttft_s_count",
        "pilottai_engine_mfu",
        "pilottai_engine_collective_frac",
        "pilottai_engine_collective_frac_model",
        "pilottai_engine_device_busy_frac",
    ):
        assert needle in text, needle
    # Parseability: every non-comment line is "name{labels} value".
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        parts = line.rsplit(" ", 1)
        assert len(parts) == 2 and parts[0], line
        float(parts[1])  # must parse


def test_export_completeness_walks_declared_series():
    """The CI wiring check: every registry-declared series must reach
    both metrics_snapshot and the Prometheus exposition; a series that
    an exporter drops (simulated here with a name the sanitizer
    collides) is reported."""
    registry = MetricsRegistry()
    registry.declare("engine.mfu", "gauge")
    registry.declare("slo.interactive.requests", "counter")
    registry.declare("request.ttft_s", "histogram")
    assert export_completeness(registry) == []
    # An observation-only series (never declared) is NOT checked — the
    # contract covers registrations.
    registry.inc("some.ad.hoc.counter")
    assert export_completeness(registry) == []
    # Kind mismatch: declared counter but written via set_gauge — the
    # declaration's zero-fill makes the counters section look populated
    # while the real data ships under a gauge of the same name.
    registry.declare("half.wired", "counter")
    registry.set_gauge("half.wired", 5.0)
    problems = export_completeness(registry)
    assert any("half.wired" in p and "gauge" in p for p in problems), problems


def test_export_completeness_on_global_registry():
    """The real deployment surface: everything obs subsystems declared
    on the process-global registry is fully wired. This is the gate
    that keeps new metrics from shipping half-exported."""
    problems = export_completeness(global_metrics)
    assert problems == [], problems
    declared = global_metrics.declared()
    # And the check is non-vacuous: the new subsystems' series are
    # actually declared there.
    for name in (
        "slo.interactive.attainment", "slo.batch.burn_rate",
        "engine.mfu", "engine.device_busy_frac", "engine.collective_frac",
        "engine.queue_depth",
    ):
        assert name in declared, name


# ---------------------------------------------------------------------- #
# HTTP edge
# ---------------------------------------------------------------------- #


@pytest.mark.asyncio
async def test_http_slo_class_threading_and_validation():
    from tests.test_server import _request

    from pilottai_tpu.server import APIServer

    server = await APIServer(_mock_handler(latency=0.001)).start()
    try:
        # Body field wins; the flight records the class.
        base = global_metrics.get("slo.batch.requests")
        status, _, _ = await _request(
            server.port, "POST", "/v1/chat/completions",
            {"messages": [{"role": "user", "content": "hi"}],
             "slo_class": "batch"},
        )
        assert status == 200
        assert global_metrics.get("slo.batch.requests") == base + 1
        flights = global_flight.finished()
        assert flights[-1]["attributes"]["slo_class"] == "batch"

        # Unknown class → 400, not silent default.
        status, _, body = await _request(
            server.port, "POST", "/v1/chat/completions",
            {"messages": [{"role": "user", "content": "hi"}],
             "slo_class": "turbo"},
        )
        assert status == 400
        assert b"slo_class" in body

        # /slo.json snapshot surface.
        status, _, body = await _request(server.port, "GET", "/slo.json")
        assert status == 200
        snap = json.loads(body)
        assert "interactive" in snap and "batch" in snap
        assert "burn_rate" in snap["batch"]
        assert snap["batch"]["targets"]["ttft_s"] is not None
    finally:
        await server.stop()


@pytest.mark.asyncio
async def test_http_slo_class_header_fallback():
    from pilottai_tpu.server import APIServer

    server = await APIServer(_mock_handler(latency=0.001)).start()
    try:
        base = global_metrics.get("slo.batch.requests")
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", server.port
        )
        payload = json.dumps(
            {"messages": [{"role": "user", "content": "hi"}]}
        ).encode()
        writer.write(
            b"POST /v1/chat/completions HTTP/1.1\r\nHost: t\r\n"
            b"x-slo-class: batch\r\n"
            + f"Content-Length: {len(payload)}\r\n".encode()
            + b"Connection: close\r\n\r\n" + payload
        )
        await writer.drain()
        raw = await reader.read()
        writer.close()
        await writer.wait_closed()
        assert b" 200 " in raw.split(b"\r\n", 1)[0]
        assert global_metrics.get("slo.batch.requests") == base + 1
    finally:
        await server.stop()
