"""Profile-guided configuration loop (ISSUE 18): workload profiler,
cost model, seasonal arrival forecasting and the glue around them —
atomic profile/autotune stores, the recommend CLI, the engine's boot
divergence warning and export-completeness over every new series."""

import json
import os
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

import pytest

from pilottai_tpu.obs.costmodel import CostModel, validate_knobs
from pilottai_tpu.obs.flight import FlightRecorder
from pilottai_tpu.obs.forecast import ArrivalForecast, burstiness_cv
from pilottai_tpu.obs.profile import WorkloadProfiler
from pilottai_tpu.utils.metrics import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE_SAMPLES = os.path.join(REPO, "tests", "fixtures",
                               "autoconf_samples.json")
FIXTURE_PROFILE = os.path.join(REPO, "tests", "fixtures",
                               "autoconf_profile.json")


# --------------------------------------------------------------------- #
# Forecast
# --------------------------------------------------------------------- #

def _sine_rate(phase, n_phases, lo=4.0, hi=16.0):
    import math

    return lo + (hi - lo) * 0.5 * (
        1.0 + math.sin(2.0 * math.pi * phase / n_phases)
    )


def test_forecast_tracks_shifted_diurnal_sine():
    """After a few replayed 'days' of a sinusoidal arrival trace, the
    forecast at lead L must track the TRUE rate L seconds ahead — the
    whole point of the seasonal curve is that forecast(now+L) is read
    off the learned shape, not extrapolated from the current rate."""
    bucket_s, n_phases = 1.0, 24
    fc = ArrivalForecast(bucket_s=bucket_s, period_s=bucket_s * n_phases,
                         alpha=0.5, gamma=0.5)
    for b in range(4 * n_phases):
        # Integer-rounded counts: the forecaster only ever sees whole
        # arrivals, tolerance below absorbs the quantization.
        fc.ingest_bucket(
            round(_sine_rate(b % n_phases, n_phases) * bucket_s),
            at=b * bucket_s,
        )
    assert fc.ready()
    now = 4 * n_phases * bucket_s
    for lead_phases in (2, 6, 12):
        lead = lead_phases * bucket_s
        predicted = fc.forecast_rps(lead_s=lead, now=now)
        truth = _sine_rate((4 * n_phases + lead_phases) % n_phases,
                           n_phases)
        assert abs(predicted - truth) <= 0.25 * truth + 1.0, (
            f"lead {lead_phases} phases: predicted {predicted:.2f} "
            f"vs truth {truth:.2f}"
        )


def test_forecast_leads_recurring_step_burst():
    """A recurring step burst must be visible in the forecast BEFORE it
    arrives: standing just ahead of the learned burst window, the
    lead-time forecast has to be a multiple of the current rate."""
    bucket_s, n_phases = 1.0, 20
    burst = set(range(12, 15))
    fc = ArrivalForecast(bucket_s=bucket_s, period_s=bucket_s * n_phases,
                         alpha=0.5, gamma=0.5)
    # Three periods of history, then live traffic up to phase 10 of the
    # fourth — the forecaster must not be read across a silent gap here
    # (silence is data and would rightly pull the level down).
    for b in range(3 * n_phases + 10):
        rate = 20.0 if (b % n_phases) in burst else 4.0
        fc.ingest_bucket(int(rate * bucket_s), at=b * bucket_s)
    assert fc.ready()
    now = (3 * n_phases + 10) * bucket_s  # phase 10: two phases pre-burst
    current = fc.current_rps(now=now)
    ahead = fc.forecast_rps(lead_s=2 * bucket_s, now=now)
    assert ahead >= 3.0 * current, (
        f"forecast {ahead:.2f} does not lead current {current:.2f}"
    )
    # And the forecast past the burst window falls back to base rate.
    after = fc.forecast_rps(lead_s=7 * bucket_s, now=now)
    assert after <= 2.0 * current


def test_forecast_not_ready_until_full_period():
    fc = ArrivalForecast(bucket_s=1.0, period_s=10.0)
    for b in range(9):
        fc.ingest_bucket(5, at=float(b))
    assert not fc.ready()
    # Consumers see the open-bucket estimate, and DynamicScaling's
    # boost stays 1.0 (gated on ready()) — checked in the scaling test.
    fc.ingest_bucket(5, at=9.0)
    fc.ingest_bucket(5, at=10.0)  # closes bucket 9 -> full period
    assert fc.ready()


def test_forecast_counts_silence_and_bounds_gaps():
    """Empty buckets are data (rate 0); a gap longer than one period
    folds in at most one period of silence."""
    fc = ArrivalForecast(bucket_s=1.0, period_s=4.0)
    fc.ingest_bucket(8, at=0.0)
    fc.ingest_bucket(8, at=1.0)
    # Jump far ahead: only n_phases empty buckets close.
    fc.observe(at=100.0, n=1)
    snap = fc.snapshot()
    assert snap["ready"]
    assert snap["seasonal_mean_rps"] < 4.0  # silence pulled the curve down


def test_burstiness_cv():
    assert burstiness_cv([1.0] * 10) == pytest.approx(0.0)
    bursty = [0.01] * 9 + [10.0]
    assert burstiness_cv(bursty) > 1.5
    assert burstiness_cv([]) == 0.0
    assert burstiness_cv([5.0]) == 0.0


# --------------------------------------------------------------------- #
# Cost model
# --------------------------------------------------------------------- #

def _samples_1d():
    return [
        {"knobs": {"engine_chunk": 8, "engine_slots": 8},
         "metrics": {"attainment": 0.80, "steps_per_s": 10.0},
         "workload": "interactive"},
        {"knobs": {"engine_chunk": 24, "engine_slots": 8},
         "metrics": {"attainment": 0.92, "steps_per_s": 14.0},
         "workload": "interactive"},
    ]


def test_costmodel_exact_on_recorded_points():
    model = CostModel(samples=_samples_1d())
    assert model.predict({"engine_chunk": 8, "engine_slots": 8},
                         "attainment") == pytest.approx(0.80)
    assert model.predict({"engine_chunk": 24, "engine_slots": 8},
                         "steps_per_s") == pytest.approx(14.0)


def test_costmodel_monotone_between_recorded_points():
    """Between two recorded 1-D knob points the interpolation is a
    convex combination: values stay inside the recorded bracket and move
    monotonically as the query slides from one point to the other."""
    model = CostModel(samples=_samples_1d())
    preds = [
        model.predict({"engine_chunk": c, "engine_slots": 8}, "attainment")
        for c in (8, 12, 16, 20, 24)
    ]
    assert all(0.80 <= p <= 0.92 for p in preds)
    assert preds == sorted(preds), f"not monotone: {preds}"


def test_costmodel_recommend_weights_by_class_mix():
    """The recommendation must follow the profile's class mix: a vector
    that wins interactive loses to one that wins batch when the measured
    traffic is batch-heavy, and vice versa."""
    samples = [
        {"knobs": {"engine_chunk": 8}, "workload": "interactive",
         "metrics": {"attainment": 0.95, "steps_per_s": 10.0}},
        {"knobs": {"engine_chunk": 8}, "workload": "batch",
         "metrics": {"attainment": 0.60, "steps_per_s": 10.0}},
        {"knobs": {"engine_chunk": 32}, "workload": "interactive",
         "metrics": {"attainment": 0.70, "steps_per_s": 10.0}},
        {"knobs": {"engine_chunk": 32}, "workload": "batch",
         "metrics": {"attainment": 0.90, "steps_per_s": 10.0}},
    ]
    model = CostModel(samples=samples)
    rec_i = model.recommend(
        profile={"class_mix": {"interactive": 0.9, "batch": 0.1}}
    )
    rec_b = model.recommend(
        profile={"class_mix": {"interactive": 0.1, "batch": 0.9}}
    )
    assert rec_i["knobs"]["engine_chunk"] == 8
    assert rec_b["knobs"]["engine_chunk"] == 32


def test_costmodel_recommend_deterministic_with_deltas():
    model = CostModel(samples=_samples_1d())
    profile = {"class_mix": {"interactive": 1.0}}
    default = {"engine_chunk": 8, "engine_slots": 8}
    a = model.recommend(profile=profile, default_knobs=default)
    b = model.recommend(profile=profile, default_knobs=default)
    assert a == b
    assert a["knobs"]["engine_chunk"] == 24
    assert a["delta"]["attainment"] == pytest.approx(0.12)
    assert a["violations"] == []


def test_validate_knobs_flags_out_of_bounds_and_unknown():
    problems = validate_knobs({
        "engine_chunk": 9999,           # outside [1, 512]
        "engine_slots": 8,              # fine
        "engine_chunk_policy": "magic",  # not in the categorical set
        "made_up_knob": 3,              # unknown
    })
    assert any("engine_chunk=9999" in p for p in problems)
    assert any("engine_chunk_policy" in p for p in problems)
    assert any("made_up_knob" in p for p in problems)
    assert not any("engine_slots" in p for p in problems)
    assert validate_knobs({"engine_chunk": 16}) == []


# --------------------------------------------------------------------- #
# Atomic stores
# --------------------------------------------------------------------- #

@pytest.fixture()
def _cache_dir(tmp_path, monkeypatch):
    from pilottai_tpu.utils import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(compile_cache, "_enabled_dir", None)
    return tmp_path


def test_store_autotune_survives_concurrent_writers(_cache_dir):
    """N threads each persist their own key into the shared autotune
    store; the merge-under-race discipline (write-temp + rename +
    verify-own-key) must keep every entry — a plain read-modify-rename
    loses whichever writer renamed first."""
    from pilottai_tpu.utils.compile_cache import load_autotune, store_autotune

    n = 12
    barrier = threading.Barrier(n)

    def writer(i):
        barrier.wait()
        store_autotune(f"race_key_{i}", 100 + i)

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    lost = [i for i in range(n) if load_autotune(f"race_key_{i}") != 100 + i]
    assert not lost, f"store race lost keys {lost}"


def test_store_profile_roundtrip_preserves_other_keys(_cache_dir):
    from pilottai_tpu.utils.compile_cache import load_profile, store_profile

    store_profile("dep-a", {"fingerprint": {"requests": 10}})
    store_profile("dep-b", {"recommendation": {"knobs": {"engine_chunk": 8}}})
    assert load_profile("dep-a") == {"fingerprint": {"requests": 10}}
    assert load_profile("dep-b")["recommendation"]["knobs"] == {
        "engine_chunk": 8
    }
    # Corrupt store starts fresh instead of raising.
    (_cache_dir / "profiles.json").write_text("{not json")
    assert load_profile("dep-a") is None
    store_profile("dep-c", {"x": 1})
    assert load_profile("dep-c") == {"x": 1}


# --------------------------------------------------------------------- #
# Profiler
# --------------------------------------------------------------------- #

def _stub_flight(**attrs):
    return SimpleNamespace(
        attributes=attrs, n_tokens=attrs.pop("_n_tokens", 0)
    )


def test_profiler_fingerprint_and_gauges():
    reg = MetricsRegistry()
    fc = ArrivalForecast(bucket_s=1.0, period_s=10.0)
    prof = WorkloadProfiler(window=64, registry=reg, forecast=fc)
    prof.configure("dep-test")
    for i in range(10):
        prof.observe_start(_stub_flight())
        prof.observe_flight(_stub_flight(
            prompt_tokens=100 + i, _n_tokens=20,
            slo_class="interactive" if i % 2 else "batch",
            session_id="s1" if i < 5 else None,
            dag_node="stage-a" if i < 3 else None,
        ))
    fp = prof.fingerprint()
    assert fp["deployment"] == "dep-test"
    assert fp["requests"] == 10
    assert 100 <= fp["prompt_tokens"]["p50"] <= 109
    assert fp["output_tokens"]["p50"] == 20
    assert fp["class_mix"] == {"batch": 0.5, "interactive": 0.5}
    assert fp["session_frac"] == pytest.approx(0.5)
    assert fp["dag"]["frac"] == pytest.approx(0.3)
    assert fp["dag"]["stage_mix"] == {"stage-a": 1.0}
    assert fp["arrival"]["observed"] == 10
    assert "forecast" in fp

    prof.refresh_gauges()
    gauges = reg.snapshot()["gauges"]
    assert gauges["profile.class_frac.interactive"] == pytest.approx(0.5)
    assert gauges["profile.session_frac"] == pytest.approx(0.5)
    assert gauges["profile.prompt_tokens_p50"] >= 100

    prof.reset()
    assert prof.fingerprint()["requests"] == 0


def test_profiler_persist_roundtrip(_cache_dir):
    from pilottai_tpu.utils.compile_cache import load_profile, store_profile

    reg = MetricsRegistry()
    prof = WorkloadProfiler(
        window=16, registry=reg,
        forecast=ArrivalForecast(bucket_s=1.0, period_s=4.0),
    )
    prof.configure("dep-persist")
    prof.observe_flight(_stub_flight(prompt_tokens=42, _n_tokens=7))
    # A stored recommendation must survive a fingerprint persist.
    store_profile("dep-persist", {"recommendation": {"knobs": {"x": 1}}})
    assert prof.persist() == "dep-persist"
    blob = load_profile("dep-persist")
    assert blob["fingerprint"]["requests"] == 1
    assert blob["recommendation"] == {"knobs": {"x": 1}}


def test_flight_start_listener_fires_once_per_flight():
    rec = FlightRecorder(max_finished=16)
    fired = []
    rec.add_start_listener(lambda f: fired.append(f.flight_id))
    rec.start("f-1", slo_class="interactive")
    rec.start("f-1", prompt_tokens=12)  # attribute merge, not an arrival
    rec.start("f-2")
    assert fired == ["f-1", "f-2"]
    # A raising listener must not break the hot path.
    rec.add_start_listener(lambda f: 1 / 0)
    rec.start("f-3")
    assert fired[-1] == "f-3"


# --------------------------------------------------------------------- #
# Scaling integration + export completeness
# --------------------------------------------------------------------- #

def _sim_orchestrator(n_agents=2, util=0.0):
    class _Agent:
        queue_utilization = util
        current_tasks = ()
        success_rate = 1.0
        status = "busy"

        class task_queue:  # noqa: N801 — queue-shaped stub
            @staticmethod
            def qsize():
                return 0

    return SimpleNamespace(
        agents={f"a{i}": object() for i in range(n_agents)},
        task_queue=[],
        running_tasks={},
        config=SimpleNamespace(max_queue_size=100, max_concurrent_tasks=16),
        agent_list=lambda: [_Agent() for _ in range(n_agents)],
    )


def test_scaling_forecast_boost_gated_and_exported():
    """A primed forecaster showing a coming ramp multiplies the load
    signal (capped); a cold forecaster or ``forecast_enabled=False``
    leaves the load untouched. Both cases export scaling.forecast_*."""
    from pilottai_tpu.core.config import ScalingConfig
    from pilottai_tpu.orchestration.scaling import DynamicScaling

    now = [0.0]
    fc = ArrivalForecast(bucket_s=1.0, period_s=10.0,
                         alpha=0.5, gamma=0.5, clock=lambda: now[0])
    burst = {7, 8}
    for b in range(35):  # 3 periods + live traffic up to phase 5
        rate = 20.0 if (b % 10) in burst else 4.0
        fc.ingest_bucket(int(rate), at=float(b))
    assert fc.ready()
    now[0] = 35.0  # phase 5: burst is 2 phases ahead

    reg = MetricsRegistry()
    scaler = DynamicScaling(
        _sim_orchestrator(),
        ScalingConfig(forecast_enabled=True, forecast_lead_s=2.0,
                      forecast_boost_cap=3.0),
        registry=reg, forecast=fc,
    )
    sig = scaler.signals()
    assert sig["forecast_boost"] > 2.0  # 20/4 capped at 3.0
    assert sig["forecast_rps"] > 10.0
    gauges = reg.snapshot()["gauges"]
    assert gauges["scaling.forecast_rps"] > 10.0
    assert gauges["scaling.forecast_lead_s"] == 2.0
    # Boost multiplies the blended load.
    base = {k: 0.0 for k in sig}
    base.update(agent_queue_util=0.3, forecast_boost=sig["forecast_boost"])
    assert scaler.system_load(signals=base) == pytest.approx(
        min(1.0, 0.3 * sig["forecast_boost"])
    )

    # Disabled: boost pinned to 1.0 even with the same hot forecaster.
    reg2 = MetricsRegistry()
    off = DynamicScaling(
        _sim_orchestrator(),
        ScalingConfig(forecast_enabled=False, forecast_lead_s=2.0),
        registry=reg2, forecast=fc,
    )
    assert off.signals()["forecast_boost"] == 1.0

    # Cold forecaster: not ready -> boost 1.0.
    reg3 = MetricsRegistry()
    cold = DynamicScaling(
        _sim_orchestrator(),
        ScalingConfig(forecast_enabled=True),
        registry=reg3, forecast=ArrivalForecast(bucket_s=1.0, period_s=10.0),
    )
    assert cold.signals()["forecast_boost"] == 1.0


def test_export_completeness_clean_over_new_series():
    """Every series this PR adds — profile.*, scaling.forecast_*,
    engine.spec_acceptance — must reach both export surfaces from
    declaration alone (zero-filled before traffic)."""
    from pilottai_tpu import obs
    from pilottai_tpu.core.config import ScalingConfig
    from pilottai_tpu.orchestration.scaling import DynamicScaling

    # Global surface: profiler gauges + engine.spec_acceptance are
    # declared at import; the global registry must stay clean.
    assert obs.export_completeness() == []
    snap = obs.metrics_snapshot()
    for name in ("profile.arrival_rps", "profile.class_frac.interactive",
                 "engine.spec_acceptance"):
        assert name in snap["gauges"], f"{name} missing from snapshot"

    # Isolated scaler surface: scaling.* declared at construction.
    reg = MetricsRegistry()
    WorkloadProfiler(registry=reg,
                     forecast=ArrivalForecast(bucket_s=1.0, period_s=4.0))
    DynamicScaling(_sim_orchestrator(), ScalingConfig(), registry=reg)
    assert obs.export_completeness(registry=reg) == []
    gauges = obs.metrics_snapshot(registry=reg)["gauges"]
    for name in ("scaling.forecast_rps", "scaling.forecast_lead_s",
                 "profile.burstiness_cv"):
        assert name in gauges, f"{name} missing from isolated snapshot"


@pytest.mark.asyncio
async def test_profile_json_on_api_server_and_dashboard():
    """The fingerprint ships on BOTH http surfaces with the same shape
    (server.py + utils/dashboard.py mirror every export route)."""
    import urllib.request

    from pilottai_tpu.core.config import LLMConfig
    from pilottai_tpu.engine.handler import LLMHandler
    from pilottai_tpu.engine.mock import MockBackend
    from pilottai_tpu.server import APIServer
    from pilottai_tpu.utils.dashboard import MetricsDashboard
    from tests.test_server import _request

    llm = LLMHandler(LLMConfig(provider="mock"), backend=MockBackend())
    server = await APIServer(llm).start()
    dash = MetricsDashboard().start()
    try:
        status, _, body = await _request(server.port, "GET", "/profile.json")
        assert status == 200
        fp = json.loads(body)
        for key in ("arrival", "class_mix", "prompt_tokens",
                    "output_tokens", "forecast", "session_frac"):
            assert key in fp, f"{key} missing from /profile.json"

        with urllib.request.urlopen(
            f"http://127.0.0.1:{dash.port}/profile.json", timeout=10
        ) as resp:
            dfp = json.loads(resp.read())
        assert set(dfp) == set(fp)
    finally:
        dash.stop()
        await server.stop()


# --------------------------------------------------------------------- #
# Boot divergence warning
# --------------------------------------------------------------------- #

def test_engine_boot_warning_on_knob_divergence(monkeypatch):
    """One-shot advisory when the active knob vector diverges from the
    stored recommendation; silent when nothing is stored."""
    from pilottai_tpu.core.config import LLMConfig
    from pilottai_tpu.engine.native import NativeEngine
    from pilottai_tpu.utils import compile_cache

    warnings = []

    def _engine(model="warn-test-model"):
        eng = NativeEngine.__new__(NativeEngine)
        eng.config = LLMConfig(model_name=model, provider="cpu",
                               engine_chunk=16)
        eng._log = SimpleNamespace(
            warning=lambda msg, *a: warnings.append(msg % a if a else msg)
        )
        return eng

    # Nothing stored: silent.
    monkeypatch.setattr(compile_cache, "load_profile", lambda key: None)
    _engine()._warn_knob_divergence()
    assert warnings == []

    # Stored recommendation diverges: exactly one warning per engine.
    monkeypatch.setattr(
        compile_cache, "load_profile",
        lambda key: {"recommendation": {"knobs": {"engine_chunk": 24}}},
    )
    eng = _engine()
    eng._warn_knob_divergence()
    eng._warn_knob_divergence()
    assert len(warnings) == 1
    assert "engine_chunk=16" in warnings[0]
    assert "24" in warnings[0]

    # Matching vector: silent.
    monkeypatch.setattr(
        compile_cache, "load_profile",
        lambda key: {"recommendation": {"knobs": {"engine_chunk": 16}}},
    )
    warnings.clear()
    _engine()._warn_knob_divergence()
    assert warnings == []


# --------------------------------------------------------------------- #
# recommend CLI over the committed fixtures (the CI autoconf lane gate)
# --------------------------------------------------------------------- #

def _run_recommend():
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "recommend.py"),
         "--json"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout)


def test_recommend_cli_deterministic_and_in_bounds():
    a = _run_recommend()
    b = _run_recommend()
    assert a == b, "recommendation is not deterministic"
    assert a["violations"] == []
    assert validate_knobs(a["knobs"]) == []
    # The recommendation must not lose to the default on its own
    # weighted-score axis over the recorded workload.
    assert a["score"]["attainment"] >= a["default_score"]["attainment"]


def test_recommend_fixtures_are_committed_and_consistent():
    with open(FIXTURE_SAMPLES) as fh:
        samples = json.load(fh)["samples"]
    assert len(samples) >= 4
    for s in samples:
        assert validate_knobs(s["knobs"]) == [], s
        assert "attainment" in s["metrics"]
        assert "steps_per_s" in s["metrics"]
    with open(FIXTURE_PROFILE) as fh:
        profile = json.load(fh)
    fp = profile.get("fingerprint", profile)
    assert fp["class_mix"], "profile fixture has no class mix"
