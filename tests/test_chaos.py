"""Chaos suite: fault-injection driven tests of the reliability layer.

Every test here provokes a failure path through the *named injection
registry* (pilottai_tpu/reliability/inject.py) — no monkeypatching of
engine internals — and asserts the system stays bounded: deadlines bound
wall time end-to-end, overload sheds instead of queueing unboundedly,
the breaker fast-fails and recovers, and an injected device failure
fails exactly the in-flight work while queued requests survive.

The whole module carries the ``chaos`` marker (the CI chaos job runs
``pytest -m chaos``); soak variants are additionally ``slow`` so they
stay out of the tier-1 lane.
"""

import asyncio
import json
import time

import jax
import jax.numpy as jnp
import pytest

from pilottai_tpu.core.config import (
    AgentConfig,
    FaultToleranceConfig,
    LLMConfig,
    ReliabilityConfig,
    ServeConfig,
)
from pilottai_tpu.engine.batcher import ContinuousBatcher, GenRequest
from pilottai_tpu.engine.handler import LLMHandler
from pilottai_tpu.engine.mock import MockBackend
from pilottai_tpu.engine.types import GenerationParams
from pilottai_tpu.models.common import init_params
from pilottai_tpu.models.registry import get_model_config
from pilottai_tpu.reliability import (
    CircuitBreaker,
    CircuitOpenError,
    DeadlineExceeded,
    DegradeLadder,
    EngineHealth,
    EngineOverloaded,
    FaultInjector,
    PoisonedOutput,
    Watchdog,
    global_engine_health,
    global_injector,
    inject,
)
from pilottai_tpu.utils.metrics import global_metrics

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _clean_injector():
    global_injector.reset()
    global_engine_health.reset()
    yield
    global_injector.reset()
    global_engine_health.reset()


def _tiny_batcher(max_seq=64, n_slots=2, **kw):
    cfg = get_model_config("llama-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return ContinuousBatcher(
        cfg, params, n_slots=n_slots, max_seq_len=max_seq,
        cache_dtype=jnp.float32, **kw,
    )


# ----------------------------- injector -------------------------------- #

def test_injector_noop_arm_times_and_scope():
    # Unarmed = production fast path: returns None, no record.
    assert global_injector.fire("engine.step") is None
    assert global_injector.fired("engine.step") == 0

    global_injector.arm("x.point", value=42, times=2)
    assert global_injector.fire("x.point") == 42
    assert global_injector.armed("x.point")
    assert global_injector.fire("x.point") == 42
    # times exhausted -> auto-disarmed, count survives.
    assert not global_injector.armed("x.point")
    assert global_injector.fire("x.point") is None
    assert global_injector.fired("x.point") == 2

    with inject("y.point", RuntimeError, times=None):
        with pytest.raises(RuntimeError, match="injected fault at 'y.point'"):
            global_injector.fire("y.point")
    # Context exit disarms even with times=None.
    assert global_injector.fire("y.point") is None


def test_injector_probability_is_seeded_and_partial():
    def run(seed):
        reg = FaultInjector(seed=seed)
        reg.arm("p", value=1, times=None, probability=0.5)
        return [reg.fire("p") for _ in range(200)]

    fires = sum(v == 1 for v in run(7))
    assert 40 < fires < 160  # partial, not all-or-nothing
    assert run(7) == run(7)  # reproducible chaos soaks


def test_injector_delay_blocks_then_returns():
    global_injector.arm("d", delay=0.05, value="v")
    t0 = time.perf_counter()
    assert global_injector.fire("d") == "v"
    assert time.perf_counter() - t0 >= 0.05


# ----------------------------- breaker --------------------------------- #

def test_breaker_opens_after_threshold_and_fast_fails():
    br = CircuitBreaker(failure_threshold=3, recovery_timeout=30.0, name="t1")
    for _ in range(2):
        assert br.allow()
        br.record_failure()
    assert br.state == "closed"
    assert br.allow()
    br.record_failure()  # third consecutive -> open
    assert br.state == "open"
    assert not br.allow()
    assert br.retry_after() > 0
    err = br.open_error()
    assert isinstance(err, CircuitOpenError) and err.retry_after > 0


def test_breaker_half_open_probe_paths():
    br = CircuitBreaker(
        failure_threshold=1, recovery_timeout=0.05, half_open_max=1, name="t2"
    )
    br.record_failure()
    assert br.state == "open" and not br.allow()
    time.sleep(0.06)
    assert br.state == "half_open"
    assert br.allow()       # the probe slot
    assert not br.allow()   # only half_open_max probes pass
    br.record_failure()     # probe failed -> re-open, window re-armed
    assert br.state == "open"
    time.sleep(0.06)
    assert br.allow()
    br.record_success()     # probe succeeded -> closed
    assert br.state == "closed" and br.allow()


def test_breaker_released_probe_does_not_wedge_half_open():
    # A probe that ends with NO verdict (cancelled mid-flight) must give
    # its slot back — leaked slots would pin allow() False forever.
    br = CircuitBreaker(
        failure_threshold=1, recovery_timeout=0.05, half_open_max=1, name="t4"
    )
    br.record_failure()
    time.sleep(0.06)
    assert br.allow()        # probe reserved...
    br.release_probe()       # ...but the call was cancelled: release
    assert br.allow()        # the slot is available again
    br.record_success()
    assert br.state == "closed"


def test_breaker_success_resets_consecutive_count():
    br = CircuitBreaker(failure_threshold=2, name="t3")
    br.record_failure()
    br.record_success()
    br.record_failure()
    assert br.state == "closed"  # never 2 consecutive


# ------------------------- handler reliability -------------------------- #

def _handler(backend, **rel_kw):
    cfg_kw = {
        k: rel_kw.pop(k)
        for k in ("retries", "retry_delay", "timeout")
        if k in rel_kw
    }
    return LLMHandler(
        LLMConfig(
            provider="mock",
            reliability=ReliabilityConfig(**rel_kw),
            **cfg_kw,
        ),
        backend=backend,
    )


def test_backoff_is_exponential_capped_and_jittered():
    h = _handler(
        MockBackend(), retries=0, retry_delay=1.0,
        retry_max_delay=4.0, retry_jitter=False,
    )
    assert [h._backoff_delay(a) for a in range(4)] == [1.0, 2.0, 4.0, 4.0]
    hj = _handler(
        MockBackend(), retries=0, retry_delay=1.0, retry_max_delay=4.0,
    )
    for attempt, base in enumerate([1.0, 2.0, 4.0, 4.0]):
        for _ in range(20):
            d = hj._backoff_delay(attempt)
            assert 0.5 * base <= d <= base


@pytest.mark.asyncio
async def test_handler_breaker_opens_then_recovers_half_open():
    calls = {"n": 0, "healthy": False}

    class Flaky(MockBackend):
        async def generate(self, messages, tools=None, params=None):
            calls["n"] += 1
            if not calls["healthy"]:
                raise RuntimeError("device gone")
            return await super().generate(messages, tools, params)

    h = _handler(
        Flaky(), retries=0, retry_delay=0.0,
        breaker_failure_threshold=2, breaker_recovery_timeout=0.1,
    )
    for _ in range(2):
        with pytest.raises(RuntimeError):
            await h.apredict("x")
    assert calls["n"] == 2 and h.breaker.state == "open"
    # Open -> fast fail without touching the backend.
    with pytest.raises(CircuitOpenError):
        await h.apredict("x")
    assert calls["n"] == 2
    # Recovery window -> half-open probe -> success closes it.
    calls["healthy"] = True
    await asyncio.sleep(0.12)
    assert await h.apredict("x")
    assert h.breaker.state == "closed" and calls["n"] == 3


@pytest.mark.asyncio
async def test_handler_timeout_injection_feeds_breaker():
    """Breaker open -> fast-fail -> half-open recovery, driven purely by
    the injection registry (acceptance criterion)."""
    backend_calls = {"n": 0}

    class Counting(MockBackend):
        async def generate(self, messages, tools=None, params=None):
            backend_calls["n"] += 1
            return await super().generate(messages, tools, params)

    h = _handler(
        Counting(), retries=0, retry_delay=0.0,
        breaker_failure_threshold=2, breaker_recovery_timeout=0.1,
    )
    global_injector.arm("handler.timeout", asyncio.TimeoutError, times=2)
    for _ in range(2):
        with pytest.raises(RuntimeError, match="failed after 1 attempt"):
            await h.apredict("x")
    assert backend_calls["n"] == 0  # fault fired before the backend
    assert h.breaker.state == "open"
    with pytest.raises(CircuitOpenError):
        await h.apredict("x")
    await asyncio.sleep(0.12)
    assert await h.apredict("x")  # injection exhausted -> probe succeeds
    assert h.breaker.state == "closed"
    assert global_injector.fired("handler.timeout") == 2


@pytest.mark.asyncio
async def test_handler_deadline_preempts_backend_and_backoff():
    calls = {"n": 0}

    class Slow(MockBackend):
        async def generate(self, messages, tools=None, params=None):
            calls["n"] += 1
            await asyncio.sleep(0.5)
            return await super().generate(messages, tools, params)

    h = _handler(Slow(), retries=3, retry_delay=5.0, breaker_enabled=False)
    # Born expired: no backend call at all.
    with pytest.raises(DeadlineExceeded):
        await h.apredict(
            "x", params=GenerationParams(deadline=time.monotonic() - 1)
        )
    assert calls["n"] == 0
    # Deadline clips the wait: fails in ~0.1s, and the 5s backoff must
    # not be slept through either (the deadline pre-empts the retry).
    t0 = time.perf_counter()
    with pytest.raises(DeadlineExceeded):
        await h.apredict(
            "x", params=GenerationParams(deadline=time.monotonic() + 0.1)
        )
    assert time.perf_counter() - t0 < 0.45
    assert calls["n"] == 1


@pytest.mark.asyncio
async def test_handler_overload_is_not_retried_and_not_breaker_failure():
    calls = {"n": 0}

    class Shedding(MockBackend):
        async def generate(self, messages, tools=None, params=None):
            calls["n"] += 1
            raise EngineOverloaded("queue full")

    h = _handler(
        Shedding(), retries=3, retry_delay=0.0, breaker_failure_threshold=1,
    )
    with pytest.raises(EngineOverloaded):
        await h.apredict("x")
    assert calls["n"] == 1  # no retry: push-back means push-back
    assert h.breaker.state == "closed"  # shed != device failure


@pytest.mark.asyncio
async def test_astream_shed_is_not_a_breaker_failure():
    class SheddingStream(MockBackend):
        async def generate_stream(
            self, messages, tools=None, params=None, info=None
        ):
            raise EngineOverloaded("stream shed")
            yield  # pragma: no cover — makes this an async generator

    h = _handler(SheddingStream(), retries=0, breaker_failure_threshold=1)
    with pytest.raises(EngineOverloaded):
        async for _ in h.astream("x"):
            pass
    assert h.breaker.state == "closed"  # unary-path parity: shed != failure


# --------------------------- batcher chaos ------------------------------ #

def test_deadline_bounds_request_against_slow_engine():
    """Acceptance: a short deadline against a chaos-slowed engine returns
    a structured timeout error and the slot is NOT leaked (n_slots=1 —
    the follow-up request can only complete through the freed slot)."""
    b = _tiny_batcher(n_slots=1)
    b.start()
    try:
        with inject("engine.prefill", delay=0.3, times=None):
            req = GenRequest(
                prompt_ids=[3, 4, 5], max_new_tokens=48,
                deadline=time.monotonic() + 0.1,
            )
            fut = b.submit(req)
            with pytest.raises(DeadlineExceeded):
                fut.result(timeout=120)
        assert global_injector.fired("engine.prefill") >= 1
        # Slot freed: a fresh request (no deadline) completes through it.
        req2 = GenRequest(prompt_ids=[6, 7], max_new_tokens=4)
        out = b.submit(req2).result(timeout=120)
        assert isinstance(out, list) and len(out) >= 1
        assert b._thread.is_alive()
    finally:
        b.stop()


def test_deadline_expired_in_backlog_rejected_at_admission():
    b = _tiny_batcher(n_slots=1)
    req = GenRequest(
        prompt_ids=[3, 4], max_new_tokens=4,
        deadline=time.monotonic() + 0.05,
    )
    fut = b.submit(req)  # queued while the loop isn't running yet
    time.sleep(0.1)
    before = global_metrics.get("engine.expired")
    b.start()
    try:
        with pytest.raises(DeadlineExceeded, match="before admission"):
            fut.result(timeout=60)
        assert global_metrics.get("engine.expired") >= before + 1
    finally:
        b.stop()


def test_deadline_expired_before_submit_costs_nothing():
    b = _tiny_batcher(n_slots=1)  # never started: submit path only
    req = GenRequest(
        prompt_ids=[3], max_new_tokens=4, deadline=time.monotonic() - 1,
    )
    fut = b.submit(req)
    with pytest.raises(DeadlineExceeded, match="before submit"):
        fut.result(timeout=1)
    assert b.queue_depth() == 0  # no queue entry exists for it


def test_queue_depth_shedding_while_inflight_completes():
    """Acceptance: submits beyond max_queue_depth raise EngineOverloaded
    (the HTTP edge maps it to 429) while already-accepted requests
    complete untouched."""
    b = _tiny_batcher(n_slots=1, max_queue_depth=2)
    futs = [
        b.submit(GenRequest(prompt_ids=[3, 4], max_new_tokens=3))
        for _ in range(2)
    ]
    assert b.saturated()
    with pytest.raises(EngineOverloaded, match="shedding"):
        b.submit(GenRequest(prompt_ids=[5], max_new_tokens=3))
    assert global_metrics.get("engine.shed") >= 1
    b.start()
    try:
        for fut in futs:  # the accepted work still completes
            assert isinstance(fut.result(timeout=120), list)
    finally:
        b.stop()


def test_injected_step_failure_fails_occupied_not_queued():
    """Chaos regression for the device-failure path with recovery OFF
    (recovery_max_attempts=0, the pre-0.10 contract): the in-flight
    request fails with the ORIGINAL exception; the queued request
    survives and completes."""
    b = _tiny_batcher(n_slots=1, recovery_max_attempts=0)
    global_injector.arm(
        "engine.step", RuntimeError("injected device failure"), times=1
    )
    b.start()
    try:
        fut1 = b.submit(GenRequest(prompt_ids=[3, 4, 5], max_new_tokens=32))
        fut2 = b.submit(GenRequest(prompt_ids=[6, 7], max_new_tokens=4))
        with pytest.raises(RuntimeError, match="injected device failure"):
            fut1.result(timeout=120)
        out = fut2.result(timeout=120)  # queued work survived the failure
        assert isinstance(out, list) and len(out) >= 1
        assert b._thread.is_alive() and b._reader.is_alive()
        assert global_injector.fired("engine.step") == 1
    finally:
        b.stop()


@pytest.mark.slow
def test_chaos_soak_probabilistic_step_failures():
    """Soak (chaos lane only): every request resolves — result or the
    injected error — under randomized dispatch failures, and the engine
    stays serviceable afterwards."""
    b = _tiny_batcher(n_slots=2)
    b.start()
    try:
        with inject(
            "engine.step", RuntimeError("soak fault"),
            times=None, probability=0.3,
        ):
            futs = [
                b.submit(GenRequest(
                    prompt_ids=[3 + i, 4, 5], max_new_tokens=8, seed=i,
                ))
                for i in range(12)
            ]
            resolved = 0
            for fut in futs:
                try:
                    assert isinstance(fut.result(timeout=180), list)
                except RuntimeError as exc:
                    assert "soak fault" in str(exc)
                resolved += 1
            assert resolved == 12
        out = b.submit(
            GenRequest(prompt_ids=[9, 9], max_new_tokens=4)
        ).result(timeout=120)
        assert isinstance(out, list)
    finally:
        b.stop()


# ----------------------- engine fault domain ---------------------------- #
# In-flight recovery, the device watchdog, poison containment and the
# degradation ladder (ISSUE 9). Everything here drives the failure paths
# through the named injection registry — no monkeypatching.


def test_injected_step_failure_recovers_in_flight_byte_identical():
    """Acceptance: an injected engine.step failure mid-decode → every
    in-flight request completes with byte-identical greedy output vs an
    uninjected run, zero client-visible errors, engine.rebuilds == 1."""
    from pilottai_tpu.obs import global_blackbox

    b = _tiny_batcher(n_slots=2)
    b.start()
    try:
        prompts = [[3, 4, 5], [6, 7]]
        ref = [
            b.submit(GenRequest(prompt_ids=list(p), max_new_tokens=12))
            .result(timeout=120)
            for p in prompts
        ]
        before = global_metrics.get("engine.rebuilds")
        global_injector.arm(
            "engine.step", RuntimeError("injected device failure"), times=1
        )
        futs = [
            b.submit(GenRequest(prompt_ids=list(p), max_new_tokens=12))
            for p in prompts
        ]
        got = [f.result(timeout=120) for f in futs]  # no client errors
        assert got == ref
        assert global_injector.fired("engine.step") == 1
        assert global_metrics.get("engine.rebuilds") == before + 1
        assert global_metrics.get("engine.recovered_requests") >= 1
        # Satellite: the failure-path rebuild writes a black-box dump
        # and counts under engine.rebuilds{reason=} (was log-lines only).
        assert any(
            r["reason"] == "engine_rebuild" for r in global_blackbox.recent(20)
        )
        assert global_metrics.get("engine.rebuilds.device_loop_error") >= 1
    finally:
        b.stop()


def test_recovery_replays_folded_tokens_and_streams_without_duplicates():
    """Mid-decode fault AFTER tokens already streamed: the re-admission
    re-prefills over prompt+generated (tokens_replayed counts them), the
    stream resumes at the next NEW token (no duplicates — the collected
    stream equals the final result), and greedy output matches the
    uninjected run."""
    # 192 tokens = a dozen chunks: the device thread runs at most
    # PIPELINE_DEPTH + 2 dispatches ahead of the reader, so most of them
    # are still to come when the first fold arms the fault (at 64 tokens
    # every chunk could already be in flight).
    b = _tiny_batcher(n_slots=1, max_seq=256)
    b.start()
    try:
        ref = b.submit(
            GenRequest(prompt_ids=[3, 4, 5], max_new_tokens=192)
        ).result(timeout=120)
        before = global_metrics.get("engine.tokens_replayed")
        got: list = []

        def on_tokens(ids):
            # Break the device from INSIDE the first fold: real tokens
            # have streamed, and most of the budget is still to dispatch
            # — arming from the test thread after polling raced a fast
            # decode that could finish first.
            if not got:
                global_injector.arm(
                    "engine.step",
                    RuntimeError("mid-decode device failure"), times=1,
                )
            got.extend(ids)

        req = GenRequest(
            prompt_ids=[3, 4, 5], max_new_tokens=192, on_tokens=on_tokens,
        )
        fut = b.submit(req)
        out = fut.result(timeout=120)
        assert out == ref
        assert got == out  # stream == result: nothing duplicated or lost
        assert global_metrics.get("engine.tokens_replayed") > before
        assert req.recovery_attempts == 1
    finally:
        b.stop()


def test_recovery_strikes_exhausted_fails_with_original_exception():
    """N strikes → the ORIGINAL exception surfaces, and the engine stays
    serviceable for new work afterwards."""
    b = _tiny_batcher(n_slots=1, recovery_max_attempts=2)
    b.start()
    try:
        before = global_metrics.get("engine.recovery_failed")
        with inject(
            "engine.step", RuntimeError("persistent device failure"),
            times=None,
        ):
            fut = b.submit(GenRequest(prompt_ids=[3, 4], max_new_tokens=8))
            with pytest.raises(RuntimeError, match="persistent device"):
                fut.result(timeout=120)
        assert global_metrics.get("engine.recovery_failed") >= before + 1
        out = b.submit(
            GenRequest(prompt_ids=[5, 6], max_new_tokens=4)
        ).result(timeout=120)
        assert isinstance(out, list) and len(out) >= 1
    finally:
        b.stop()


def test_prefill_dispatch_failure_unwinds_prep_and_recovers():
    """Satellite: injected ``engine.prefill`` failure against a
    _PreparedAdmission mid-flight — slot reservations (``_prep_reserved``)
    and allocated pages fully release (no leak), admission resumes, and
    the group's requests complete via bounded re-admission."""
    b = _tiny_batcher(
        n_slots=2, paged=True, page_size=16, overlap_admission=True,
    )
    before = global_metrics.get("engine.recovery_requeued")
    global_injector.arm(
        "engine.prefill", RuntimeError("injected prefill fault"), times=1
    )
    b.start()
    try:
        futs = [
            b.submit(GenRequest(prompt_ids=[3 + i, 4, 5], max_new_tokens=6))
            for i in range(2)
        ]
        for fut in futs:
            out = fut.result(timeout=120)
            assert isinstance(out, list) and len(out) >= 1
        assert global_injector.fired("engine.prefill") == 1
        assert global_metrics.get("engine.recovery_requeued") >= before + 1
        # Resources fully unwound once everything completed: no leaked
        # reservation (admission would wedge) and no leaked pages (the
        # pool would shrink forever).
        t_end = time.time() + 30
        while time.time() < t_end and (
            b._prep_reserved or b.alloc.free_pages < b.num_pages - 1
        ):
            time.sleep(0.05)
        assert b._prep_reserved == set()
        assert b.alloc.free_pages == b.num_pages - 1
    finally:
        b.stop()


def test_fold_corruption_poisons_only_affected_request():
    """Poison containment: an injected out-of-vocab fold fails ONLY the
    affected request (PoisonedOutput); the other occupant completes and
    the engine stays serviceable."""
    b = _tiny_batcher(n_slots=2)
    b.start()
    try:
        r1 = GenRequest(prompt_ids=[3, 4, 5], max_new_tokens=48)
        r2 = GenRequest(prompt_ids=[6, 7], max_new_tokens=48)
        f1, f2 = b.submit(r1), b.submit(r2)
        # Wait for both to occupy slots, then poison r2's slot.
        t_end = time.time() + 60
        idx = None
        while time.time() < t_end and idx is None:
            idx = next(
                (
                    i for i, s in enumerate(b._slots)
                    if s is not None and s.request is r2
                ),
                None,
            )
            time.sleep(0.005)
        assert idx is not None
        before = global_metrics.get("engine.poisoned")
        global_injector.arm("engine.fold.corrupt", value=idx, times=1)
        with pytest.raises(PoisonedOutput, match="out-of-vocab"):
            f2.result(timeout=120)
        out = f1.result(timeout=120)  # the other occupant is untouched
        assert isinstance(out, list) and len(out) >= 1
        assert global_metrics.get("engine.poisoned") == before + 1
        out2 = b.submit(
            GenRequest(prompt_ids=[9, 9], max_new_tokens=4)
        ).result(timeout=120)
        assert isinstance(out2, list)
    finally:
        b.stop()


# ----------------------------- watchdog --------------------------------- #


def test_watchdog_unit_trip_and_recover():
    """Deterministic (fake-clock) watchdog semantics: idle never trips;
    stale heartbeats WITH work trip (breaker force-opened via the health
    registry, on_stall fired); a late beat recovers."""
    health = EngineHealth()
    br = CircuitBreaker(name="wd-unit")
    health.subscribe(br.on_engine_stall)
    stalls: list = []
    busy = {"v": False}
    t = {"now": 0.0}
    wd = Watchdog(
        stall_s=1.0, has_work=lambda: busy["v"],
        on_stall=stalls.append, health=health,
        clock=lambda: t["now"], poll_s=0.005,
    )
    wd.start()
    try:
        def wait_for(cond, timeout=5.0):
            end = time.time() + timeout
            while time.time() < end and not cond():
                time.sleep(0.005)
            assert cond()

        t["now"] = 50.0  # huge clock jump while IDLE: never a stall
        time.sleep(0.05)
        assert health.healthy()
        busy["v"] = True
        t["now"] = 50.5  # busy but not stale yet
        time.sleep(0.05)
        assert health.healthy()
        t["now"] = 52.0  # stale with work in flight → stalled
        wait_for(lambda: not health.healthy())
        assert br.state == "open"
        assert stalls and stalls[0]["stall_s"] == 1.0
        assert global_metrics.get("engine.watchdog_stalls") >= 1
        wd.beat()  # the hang resolved
        wait_for(health.healthy)
    finally:
        wd.stop()


def test_watchdog_trips_on_hung_dispatch_then_engine_recovers():
    """Acceptance: an injected dispatch hang (a stuck XLA call — never
    raises, never reaches an except arm) trips the watchdog within
    stall_s + grace: health flips, the subscribed breaker force-opens,
    a black-box dump is written. When the hang resolves the request
    still completes and health recovers."""
    from pilottai_tpu.obs import global_blackbox

    b = _tiny_batcher(n_slots=1, watchdog_stall_s=0.5)
    b.start()
    try:
        # Prime: compiles the admission + decode executables so the
        # injected phase measures the hang, not the compiler.
        b.submit(
            GenRequest(prompt_ids=[3, 4, 5], max_new_tokens=8)
        ).result(timeout=120)
        global_engine_health.reset()  # drop any compile-phase stall
        br = CircuitBreaker(name="wd-hang")
        global_engine_health.subscribe(br.on_engine_stall)
        before = global_metrics.get("engine.watchdog_stalls")
        global_injector.arm("engine.dispatch.hang", delay=2.5, times=1)
        fut = b.submit(GenRequest(prompt_ids=[3, 4, 5], max_new_tokens=8))
        # Trip within stall_s + grace (poll granularity + scheduling).
        t_end = time.time() + 2.0
        while time.time() < t_end and global_engine_health.healthy():
            time.sleep(0.01)
        assert not global_engine_health.healthy()
        assert global_engine_health.snapshot()["retry_after"] > 0
        assert global_metrics.get("engine.watchdog_stalls") >= before + 1
        # The subscriber fires right after the health flip — poll
        # briefly rather than racing mark_stalled's callback loop.
        t_end = time.time() + 2.0
        while time.time() < t_end and br.state != "open":
            time.sleep(0.01)
        assert br.state == "open"  # new requests now fast-fail 503
        assert any(
            r["reason"] == "watchdog_stall"
            for r in global_blackbox.recent(20)
        )
        # The hang resolves: the request completes and health recovers.
        out = fut.result(timeout=120)
        assert isinstance(out, list) and len(out) >= 1
        t_end = time.time() + 5.0
        while time.time() < t_end and not global_engine_health.healthy():
            time.sleep(0.01)
        assert global_engine_health.healthy()
    finally:
        b.stop()


@pytest.mark.asyncio
async def test_healthz_and_chat_503_when_engine_stalled():
    """HTTP surface of a stall: /healthz flips to 503 with retry_after;
    the handler's breaker (subscribed at construction) force-opens so
    chat requests fast-fail 503 with retry_after."""
    from pilottai_tpu.server import APIServer

    h = _handler(MockBackend(), breaker_recovery_timeout=60.0)
    server = await APIServer(h).start()
    try:
        status, _ = await _request(server.port, "GET", "/healthz")
        assert status == 200
        global_engine_health.mark_stalled(
            reason="device loop heartbeat stale (test)", retry_after=2.5,
        )
        status, data = await _request(server.port, "GET", "/healthz")
        assert status == 503
        assert data["status"] == "stalled"
        assert data["retry_after"] == 2.5
        assert "stale" in data["reason"]
        status, data = await _request(
            server.port, "POST", "/v1/chat/completions",
            {"messages": [{"role": "user", "content": "hi"}]},
        )
        assert status == 503
        assert data["error"]["type"] == "overloaded_error"
        assert data["error"]["retry_after"] > 0
        global_engine_health.mark_recovered()
        status, _ = await _request(server.port, "GET", "/healthz")
        assert status == 200
    finally:
        await server.stop()


# ------------------------- degradation ladder --------------------------- #


def test_degrade_ladder_steps_and_promotes_on_clean_soak():
    t = {"now": 0.0}
    lad = DegradeLadder(
        fault_threshold=2, window_s=10.0, promote_s=30.0,
        clock=lambda: t["now"],
    )
    assert lad.level() == 0
    lad.record_fault("a")
    assert lad.level() == 0  # below threshold
    lad.record_fault("b")
    assert lad.level() == 1  # burst crossed the threshold
    lad.record_fault("c")
    lad.record_fault("d")
    assert lad.level() == 2  # each rung needs a fresh burst
    # Faults outside the rolling window never accumulate into a step.
    t["now"] = 100.0
    lad.record_fault("e")
    t["now"] = 120.0  # > window_s later
    lad.record_fault("f")
    assert lad.level() <= 2
    # Clean soak: one rung back per promote_s period.
    t["now"] = 300.0
    assert lad.level() == 0
    # Disabled ladder counts faults but never steps.
    off = DegradeLadder(fault_threshold=1, enabled=False)
    off.record_fault("x")
    off.record_fault("y")
    assert off.level() == 0


def test_degrade_rungs_cap_chunks_slots_and_shed_batch():
    """Batcher integration: rung 2 clamps dispatches to the smallest
    compiled chunk bucket, rung 3 halves admissible slots, rung 4 sheds
    batch-class submits outright while interactive still queues."""
    from pilottai_tpu.engine.batcher import _Slot

    lad = DegradeLadder(fault_threshold=1, window_s=60.0, promote_s=3600.0)
    b = _tiny_batcher(n_slots=4, degrade=lad, max_queue_depth=16)
    # Rung 2: a slot needing ~100 tokens would normally take the largest
    # bucket; degraded it takes the smallest.
    b._slots[0] = _Slot(
        request=GenRequest(prompt_ids=[1, 2], max_new_tokens=100),
        prompt_len=2,
    )
    assert b._pick_chunk_blocks() == b.chunk_buckets[-1]
    lad.record_fault("t")
    lad.record_fault("t")
    assert lad.level() == 2
    assert b._pick_chunk_blocks() == b.chunk_buckets[0]
    b._slots[0] = None
    # Rung 3: selection caps occupancy at n_slots // 2.
    lad.record_fault("t")
    assert lad.level() == 3
    for i in range(4):
        b._backlog.append(GenRequest(prompt_ids=[3 + i], max_new_tokens=4))
    groups, seg, _epoch = b._select_groups()
    assert seg is None
    assert sum(len(g) for _, g in groups) == 2
    for _, g in groups:  # unwind the white-box selection
        for idx, req in g:
            b._prep_reserved.discard(idx)
    b._backlog.clear()
    # Rung 4: batch sheds outright (empty queue!), interactive queues.
    lad.record_fault("t")
    assert lad.level() == 4
    before = global_metrics.get("engine.shed.batch")
    with pytest.raises(EngineOverloaded, match="shedding batch-class"):
        b.submit(GenRequest(
            prompt_ids=[5], max_new_tokens=2, slo_class="batch",
        ))
    assert global_metrics.get("engine.shed.batch") == before + 1
    fut = b.submit(GenRequest(prompt_ids=[5], max_new_tokens=2))
    assert not fut.done()  # interactive accepted (engine not started)


def test_batch_class_sheds_at_lower_queue_depth():
    """Satellite: per-SLO-class shed thresholds — batch sheds at
    batch_shed_frac × max_queue_depth, interactive at the full depth,
    each counted under engine.shed.<class>."""
    b = _tiny_batcher(n_slots=1, max_queue_depth=4, batch_shed_frac=0.5)
    b.submit(GenRequest(prompt_ids=[1], max_new_tokens=2))
    b.submit(GenRequest(prompt_ids=[2], max_new_tokens=2))
    # Depth 2 == the batch limit (4 × 0.5): batch sheds...
    before = global_metrics.get("engine.shed.batch")
    with pytest.raises(EngineOverloaded, match="batch-class limit 2"):
        b.submit(GenRequest(
            prompt_ids=[3], max_new_tokens=2, slo_class="batch",
        ))
    assert global_metrics.get("engine.shed.batch") == before + 1
    # ...while interactive still gets the remaining depth.
    b.submit(GenRequest(prompt_ids=[4], max_new_tokens=2))
    b.submit(GenRequest(prompt_ids=[5], max_new_tokens=2))
    before_i = global_metrics.get("engine.shed.interactive")
    with pytest.raises(EngineOverloaded, match="interactive-class limit 4"):
        b.submit(GenRequest(prompt_ids=[6], max_new_tokens=2))
    assert global_metrics.get("engine.shed.interactive") == before_i + 1


# ----------------------------- HTTP edge -------------------------------- #

async def _request(port, method, path, body=None, headers=None):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    payload = json.dumps(body).encode() if body is not None else b""
    extra = "".join(f"{k}: {v}\r\n" for k, v in (headers or {}).items())
    writer.write(
        f"{method} {path} HTTP/1.1\r\nHost: t\r\n"
        f"Content-Length: {len(payload)}\r\n{extra}"
        f"Connection: close\r\n\r\n".encode() + payload
    )
    await writer.drain()
    raw = await reader.read()
    writer.close()
    await writer.wait_closed()
    head, _, body_bytes = raw.partition(b"\r\n\r\n")
    status = int(head.split(b"\r\n")[0].split(b" ")[1])
    return status, json.loads(body_bytes) if body_bytes else {}


class _RaisingBackend(MockBackend):
    def __init__(self, exc):
        super().__init__()
        self._exc = exc

    async def generate(self, messages, tools=None, params=None):
        raise self._exc


@pytest.mark.asyncio
async def test_http_shed_is_429_with_structured_error():
    from pilottai_tpu.server import APIServer

    h = _handler(_RaisingBackend(EngineOverloaded("queue depth 64 at limit")))
    server = await APIServer(h).start()
    try:
        status, data = await _request(
            server.port, "POST", "/v1/chat/completions",
            {"messages": [{"role": "user", "content": "hi"}]},
        )
        assert status == 429
        assert data["error"]["type"] == "overloaded_error"
        assert "queue depth" in data["error"]["message"]
    finally:
        await server.stop()


@pytest.mark.asyncio
async def test_http_deadline_is_408_and_breaker_open_is_503():
    from pilottai_tpu.server import APIServer

    class Slow(MockBackend):
        async def generate(self, messages, tools=None, params=None):
            await asyncio.sleep(0.5)
            return await super().generate(messages, tools, params)

    h = _handler(
        Slow(), retries=0, retry_delay=0.0,
        breaker_failure_threshold=1, breaker_recovery_timeout=60.0,
    )
    server = await APIServer(h).start()
    try:
        # Deadline from the x-request-timeout header -> structured 408.
        status, data = await _request(
            server.port, "POST", "/v1/chat/completions",
            {"messages": [{"role": "user", "content": "hi"}]},
            headers={"x-request-timeout": "0.05"},
        )
        assert status == 408
        assert data["error"]["type"] == "timeout_error"
        # That deadline blowout opened the breaker (threshold 1):
        # the next request fast-fails 503 with a retry_after hint.
        status, data = await _request(
            server.port, "POST", "/v1/chat/completions",
            {"messages": [{"role": "user", "content": "hi"}]},
        )
        assert status == 503
        assert data["error"]["type"] == "overloaded_error"
        assert data["error"]["retry_after"] > 0
    finally:
        await server.stop()


@pytest.mark.asyncio
async def test_http_timeout_field_validation():
    from pilottai_tpu.server import APIServer

    server = await APIServer(_handler(MockBackend())).start()
    try:
        for bad in ("soon", -1, 0, True):
            status, data = await _request(
                server.port, "POST", "/v1/chat/completions",
                {"messages": [{"role": "user", "content": "hi"}],
                 "timeout": bad},
            )
            assert status == 400, bad
        # A generous valid timeout: request completes normally.
        status, data = await _request(
            server.port, "POST", "/v1/chat/completions",
            {"messages": [{"role": "user", "content": "hi"}],
             "timeout": 30},
        )
        assert status == 200
    finally:
        await server.stop()


@pytest.mark.asyncio
async def test_http_task_timeout_is_408():
    from pilottai_tpu.server import APIServer

    class HangingServe:
        async def execute_task(self, task, timeout=None):
            await asyncio.wait_for(asyncio.sleep(60), timeout)

    server = await APIServer(
        _handler(MockBackend()), serve=HangingServe()
    ).start()
    try:
        status, data = await _request(
            server.port, "POST", "/v1/tasks",
            {"task": "hangs forever", "timeout": 0.1},
        )
        assert status == 408
        assert data["error"]["type"] == "timeout_error"
    finally:
        await server.stop()


# ------------------------ orchestration chaos --------------------------- #

def _worker(**cfg):
    from pilottai_tpu.core.agent import BaseAgent

    return BaseAgent(
        config=AgentConfig(role="worker", **cfg),
        llm=LLMHandler(LLMConfig(provider="mock")),
    )


@pytest.mark.asyncio
async def test_heartbeat_stall_injection_degrades_health():
    from pilottai_tpu.core.status import HealthStatus
    from pilottai_tpu.orchestration.fault_tolerance import FaultTolerance
    from pilottai_tpu.serve import Serve

    agent = _worker()
    await agent.start()
    serve = Serve(name="chaos", agents=[agent])
    ft = FaultTolerance(serve, FaultToleranceConfig(
        heartbeat_timeout=60.0, max_recovery_attempts=0,
    ))
    ft.register_agent(agent)
    assert (await ft.check_once())[agent.id] == HealthStatus.HEALTHY
    # Inject a 120s stall: the agent LOOKS silent without being wedged.
    global_injector.arm("agent.heartbeat.stall", value=120.0, times=1)
    assert (await ft.check_once())[agent.id] == HealthStatus.UNHEALTHY
    # Injection consumed -> next pass sees the real (fresh) heartbeat.
    assert (await ft.check_once())[agent.id] == HealthStatus.HEALTHY
    await agent.stop()


@pytest.mark.asyncio
async def test_heartbeat_stall_attributed_as_dag_retry_node():
    """An injected ``agent.heartbeat.stall`` that triggers recovery must
    surface in the affected task's DAG as a ``retry`` node carrying the
    observed stall seconds — chaos-induced dead time is attributed, not
    silently swallowed (obs/dag.py)."""
    from pilottai_tpu.core.task import Task
    from pilottai_tpu.obs import global_dag
    from pilottai_tpu.orchestration.fault_tolerance import FaultTolerance
    from pilottai_tpu.serve import Serve

    agent = _worker()
    await agent.start()
    serve = Serve(name="chaos-dag", agents=[agent])
    ft = FaultTolerance(serve, FaultToleranceConfig(
        heartbeat_timeout=60.0, max_recovery_attempts=1,
        recovery_cooldown=0.0,
    ))
    ft.register_agent(agent)
    task = Task(description="work interrupted by a stalled heartbeat")
    global_dag.start(task.id, trace_id="chaos-dag-stall-1")
    await agent.add_task(task)
    global_injector.arm("agent.heartbeat.stall", value=120.0, times=1)
    await ft.check_once()  # UNHEALTHY -> in-place recovery path
    try:
        d = global_dag.describe(task.id)
        assert d is not None
        retries = [
            n for n in d["nodes"]
            if n["kind"] == "retry" and n["name"] == "agent_recovery"
        ]
        assert retries, [n["name"] for n in d["nodes"]]
        # The injected 120 s stall (minus the loop's own wall) is
        # attributed on the retry node.
        assert retries[0]["attributes"]["stall_s"] >= 60.0
        assert retries[0]["attributes"]["agent_id"] == agent.id[:8]
    finally:
        global_dag.finish(task.id, "cancelled")
        await agent.stop()


@pytest.mark.asyncio
async def test_fault_requeue_adapts_to_orchestrator_signature():
    """The requeue kwargs are filtered per-parameter against the
    orchestrator's signature: a `reason`-only orchestrator must not be
    handed stall_s (TypeError → task lost), a **kwargs one gets the
    full attribution, and a bare legacy one gets the task alone."""
    from pilottai_tpu.core.task import Task
    from pilottai_tpu.orchestration.fault_tolerance import FaultTolerance

    task = Task(description="requeue me")
    calls = []

    class ReasonOnly:
        def agent_list(self):
            return []

        async def requeue_task(self, task, reason=""):
            calls.append(("reason_only", reason))

    class FullKwargs:
        def agent_list(self):
            return []

        async def requeue_task(self, task, reason="", **attrs):
            calls.append(("full", reason, attrs))

    class Legacy:
        def agent_list(self):
            return []

        async def requeue_task(self, task):
            calls.append(("legacy",))

    for orch in (ReasonOnly(), FullKwargs(), Legacy()):
        ft = FaultTolerance(orch, FaultToleranceConfig())
        await ft._requeue(task, stall_s=12.0)
    assert calls == [
        ("reason_only", "fault_recovery"),
        ("full", "fault_recovery", {"stall_s": 12.0}),
        ("legacy",),
    ]


@pytest.mark.asyncio
async def test_health_gauge_keyed_by_full_id_and_reaped():
    from pilottai_tpu.orchestration.fault_tolerance import FaultTolerance
    from pilottai_tpu.serve import Serve

    agent = _worker()
    await agent.start()
    serve = Serve(name="chaos", agents=[agent])
    ft = FaultTolerance(serve, FaultToleranceConfig(max_recovery_attempts=0))
    await ft.check_once()
    gauges = global_metrics.snapshot()["gauges"]
    assert f"fault.health.{agent.id}" in gauges  # full id, not id[:8]
    assert f"fault.health.{agent.id[:8]}" not in gauges
    # Agent leaves the pool -> record AND gauge reaped.
    serve.agents.pop(agent.id)
    await ft.check_once()
    gauges = global_metrics.snapshot()["gauges"]
    assert f"fault.health.{agent.id}" not in gauges
    assert agent.id not in ft.health
    await agent.stop()


@pytest.mark.asyncio
async def test_execute_task_timeout_threads_into_task_timeout():
    from pilottai_tpu.serve import Serve

    agent = _worker()
    serve = Serve(
        name="chaos", agents=[agent],
        manager_llm=LLMHandler(LLMConfig(provider="mock")),
        config=ServeConfig(max_concurrent_tasks=2),
    )
    await serve.start()
    try:
        result = await serve.execute_task("trivial thing", timeout=7.5)
        assert result.success
        task = next(
            t for t in serve.all_tasks.values()
            if t.description == "trivial thing"
        )
        assert task.timeout == 7.5  # agents see the caller's budget
    finally:
        await serve.stop()


def test_journal_write_failure_degrades_not_crashes(tmp_path):
    from pilottai_tpu.checkpoint.journal import TaskJournal
    from pilottai_tpu.core.task import Task

    journal = TaskJournal(tmp_path / "j.jsonl")
    before = global_metrics.get("journal.write_failures")
    global_injector.arm("checkpoint.write", OSError("disk full"), times=1)
    journal.record_task(Task(description="survives injected disk failure"))
    assert global_metrics.get("journal.write_failures") == before + 1
    # Disk "recovers": subsequent records land and replay sees them.
    t2 = Task(description="after recovery")
    journal.record_task(t2)
    journal.close()
    assert t2.id in TaskJournal.replay(journal.path)
