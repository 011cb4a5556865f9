"""One request timeline, one clock: the flight's layer-boundary marks and self
times through the HTTP edge, the batcher's phases in the profiler's host
lanes, the fill counters at the two dispatch sites, and the benchmark's
readers of all three. CPU, tiny model; every wait has a limit."""

import asyncio
import glob
import json
import time

import jax
import jax.numpy as jnp
import pytest

from perfbench import run as bench_run
from perfbench import timeline
from pilottai_tpu.core.config import LLMConfig
from pilottai_tpu.engine.batcher import ContinuousBatcher, GenRequest
from pilottai_tpu.engine.handler import LLMHandler
from pilottai_tpu.models.common import init_params
from pilottai_tpu.models.registry import get_model_config
from pilottai_tpu.obs import global_flight
from pilottai_tpu.server import APIServer
from pilottai_tpu.utils.metrics import global_metrics
from pilottai_tpu.utils.tracing import Span, Tracer, global_tracer, host_span

LIMIT_S = 240.0
SELF_TIMES = timeline.SELF_TIMES
MARKS = ("edge_received", "handler_entered", "submitted", "admitted",
         "first_token", "last_token", "batcher_done", "handler_returned",
         "edge_last_byte")


class Listener:
    """A finish listener that keeps each flight and when it was told."""

    def __init__(self):
        self.calls = []

    def __call__(self, flight):
        self.calls.append((time.perf_counter(), flight))

    def of(self, trace_id):
        return [(at, f) for at, f in self.calls if f.trace_id == trace_id]


@pytest.fixture
def listener():
    seen = Listener()
    global_flight.add_finish_listener(seen)
    yield seen
    global_flight._listeners.remove(seen)


def _handler():
    return LLMHandler(LLMConfig(
        model_name="llama-tiny", provider="cpu",
        engine_slots=2, engine_max_seq=256, engine_chunk=4,
    ))


async def _post(port, trace_id, body, drop_after=None):
    """One chat request over a raw socket. ``drop_after`` bytes of reply, the
    client resets the connection instead of reading on."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    payload = json.dumps(body).encode()
    writer.write(
        f"POST /v1/chat/completions HTTP/1.1\r\nHost: t\r\n"
        f"x-request-id: {trace_id}\r\nContent-Length: {len(payload)}\r\n"
        f"Connection: close\r\n\r\n".encode() + payload
    )
    await writer.drain()
    if drop_after is None:
        raw = await reader.read()
        writer.close()
        await writer.wait_closed()
        return int(raw.split(b" ", 2)[1])
    got = b""
    while len(got) < drop_after:
        piece = await reader.read(256)
        assert piece, "the reply ended before the client could drop it"
        got += piece
    writer.transport.abort()
    return None


def _all_marks(flight):
    marks = dict(flight.marks)
    marks["first_token"] = flight.first_token_at
    marks["last_token"] = flight.last_token_at
    return marks


async def _until(predicate):
    while not predicate():
        await asyncio.sleep(0.01)


async def _served(listener, stream):
    handler = _handler()
    server = await APIServer(handler).start()
    body = {"messages": [{"role": "user", "content": "where did the time go"}],
            "max_tokens": 12, "temperature": 0, "stream": stream}
    try:
        assert await _post(server.port, "warm", body) == 200   # starts the engine
        assert await _post(server.port, "timed", body) == 200
        await _until(lambda: listener.of("timed"))
    finally:
        await server.stop()
        await handler.stop()
    return listener.of("timed")


@pytest.mark.asyncio
@pytest.mark.parametrize("stream", [False, True], ids=["unary", "sse"])
async def test_http_flight_has_every_mark_in_order_and_self_times_that_add_up(
    listener, stream
):
    calls = await asyncio.wait_for(_served(listener, stream), LIMIT_S)
    assert len(calls) == 1, "the finish listener fires once per request"
    told_at, flight = calls[0]
    assert flight.status == "ok" and global_flight.get(flight.flight_id) is None
    marks = _all_marks(flight)
    stamps = [marks.get(name) for name in MARKS]
    assert None not in stamps, dict(zip(MARKS, stamps))
    assert stamps == sorted(stamps), dict(zip(MARKS, stamps))
    assert told_at >= marks["edge_last_byte"]
    assert flight.started == marks["handler_entered"]
    assert flight.ended == marks["handler_returned"]
    d = flight.derived()
    assert all(d[k] >= 0.0 for k in SELF_TIMES)
    edge = marks["edge_last_byte"] - marks["edge_received"]
    tail = marks["batcher_done"] - marks["last_token"]
    assert d["edge_s"] == pytest.approx(edge) and d["batcher_tail_s"] == pytest.approx(tail)
    assert abs(sum(d[k] for k in SELF_TIMES) - (edge - tail)) < 2e-3
    # what the SLO tracker and batcher.ttft_p50_ms read keeps its definition
    assert d["ttft_s"] == pytest.approx(marks["first_token"] - flight.started)
    assert d["e2e_s"] == pytest.approx(flight.ended - flight.started)


@pytest.mark.asyncio
async def test_dropped_client_still_closes_the_flight(listener):
    async def body():
        handler = _handler()
        server = await APIServer(handler).start()
        req = {"messages": [{"role": "user", "content": "talk for a long while"}],
               "max_tokens": 200, "temperature": 0, "stream": True}
        try:
            assert await _post(server.port, "warm", dict(req, max_tokens=2)) == 200
            await _post(server.port, "dropped", req, drop_after=300)
            await _until(lambda: listener.of("dropped"))
            await asyncio.sleep(0.2)   # a second close would have come by now
        finally:
            await server.stop()
            await handler.stop()

    await asyncio.wait_for(body(), LIMIT_S)
    calls = listener.of("dropped")
    assert len(calls) == 1
    _, flight = calls[0]
    assert flight.status not in (None, "ok")
    assert global_flight.get(flight.flight_id) is None
    assert "edge_last_byte" in flight.marks
    d = flight.derived()
    assert all(v > 0.0 for k, v in d.items() if k in SELF_TIMES), d


@pytest.mark.asyncio
async def test_bare_handler_flight_has_no_edge_and_keeps_its_definitions(listener):
    async def body():
        handler = _handler()
        try:
            await handler.generate_response(
                [{"role": "user", "content": "warm"}])
            n = len(listener.calls)
            await handler.generate_response(
                [{"role": "user", "content": "no edge here"}])
            return listener.calls[n:]
        finally:
            await handler.stop()

    calls = await asyncio.wait_for(body(), LIMIT_S)
    assert len(calls) == 1
    _, flight = calls[0]
    d, marks = flight.derived(), flight.marks
    assert flight.status == "ok"
    assert not [k for k in list(d) + list(marks) if k.startswith("edge_")]
    assert d["queue_wait_s"] == pytest.approx(marks["admitted"] - flight.started)
    assert d["ttft_s"] == pytest.approx(flight.first_token_at - flight.started)
    assert d["e2e_s"] == pytest.approx(flight.ended - flight.started)
    assert {"handler_self_s", "batcher_wait_s", "prefill_s", "decode_s"} <= set(d)


def _batcher(**kwargs):
    cfg = get_model_config("llama-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return ContinuousBatcher(
        cfg, params, n_slots=4, max_seq_len=160, cache_dtype=jnp.float32,
        chunk_size=4, prefix_cache=0, use_pallas=False, **kwargs,
    )


def test_batcher_phases_are_in_the_profilers_host_lanes(tmp_path):
    from jax.profiler import ProfileData

    b = _batcher()
    b.start()
    try:
        warm = GenRequest(prompt_ids=list(range(3, 20)), max_new_tokens=6)
        b.submit(warm)
        warm.future.result(timeout=LIMIT_S)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with global_tracer.span("server.request", route="none"):
                req = GenRequest(prompt_ids=list(range(5, 30)), max_new_tokens=10)
                b.submit(req)
                req.future.result(timeout=LIMIT_S)
            time.sleep(0.15)   # the device thread, with nothing to dispatch
        finally:
            jax.profiler.stop_trace()
    finally:
        b.stop()
    found = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    assert len(found) == 1
    lines_of = {}
    for plane in ProfileData.from_file(found[0]).planes:
        if plane.name != "/host:CPU":
            continue
        for index, line in enumerate(plane.lines):   # one line per thread
            for event in line.events:
                lines_of.setdefault(event.name, set()).add(index)
    threads = {
        name: lines_of.get(name) for name in (
            "batcher.dispatch_chunk", "batcher.dispatch_prefill.full",
            "batcher.wait_for_work", "prep.prepare_prefill", "reader.process_chunk")
    }
    assert all(threads.values()), threads
    device, prep, reader = (
        threads["batcher.dispatch_chunk"], threads["prep.prepare_prefill"],
        threads["reader.process_chunk"])
    assert threads["batcher.dispatch_prefill.full"] == device
    assert threads["batcher.wait_for_work"] == device
    assert len(device) == len(prep) == len(reader) == 1
    assert len(device | prep | reader) == 3
    assert "server.request" not in lines_of


def test_host_span_outside_a_trace_records_nothing():
    tracer = Tracer()
    before = len(global_tracer._finished)
    with host_span("batcher.dispatch_chunk", blocks=4) as span:
        assert not isinstance(span, Span)
        assert global_tracer.current() is None and tracer.current() is None
    assert len(global_tracer._finished) == before and not tracer._finished
    assert "device" not in Tracer.span.__wrapped__.__code__.co_varnames


def _counters():
    return dict(global_metrics.snapshot()["counters"])


def test_fill_counters_on_a_hand_worked_schedule():
    b = _batcher()
    lens, budgets = (23, 140), (7, 5)
    reqs = [
        GenRequest(prompt_ids=list(range(3, 3 + n)), max_new_tokens=m, eos_id=-1)
        for n, m in zip(lens, budgets)
    ]
    for r in reqs:     # both before the start: one admission group
        b.submit(r)
    before = _counters()
    b.start()
    try:
        outs = [r.future.result(timeout=LIMIT_S) for r in reqs]
    finally:
        b.stop()
    after = _counters()

    def delta(name):
        return after.get(name, 0.0) - before.get(name, 0.0)

    assert [len(o) for o in outs] == list(budgets)
    assert delta("engine.admitted") == 2
    assert delta("engine.prefill_tokens_real") == sum(lens)
    # one group of two: the two-row rung x the longer prompt's bucket
    assert b._bucket(max(lens)) == 160 and b.admit_batch == 4
    assert delta("engine.prefill_tokens_run") == 2 * 160
    first_tokens = len(reqs)
    assert delta("engine.decode_rows_active") == sum(budgets) - first_tokens
    assert delta("engine.decode_rows_active") == delta("engine.generated_tokens_device")
    assert delta("engine.decode_rows_run") == b.n_slots * delta("engine.decode_steps")
    assert delta("engine.decode_steps") == max(budgets) - 1


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_after_warmup_no_group_size_compiles_an_admission(paged):
    """``warmup()`` sweeps every rung a prompt bucket's groups can run at,
    so a group of any size up to ``admit_batch`` finds its full-prefill
    program built: nothing is added to ``admit_group``'s cache while
    serving."""
    from pilottai_tpu.engine.batcher import admit_group

    extra = dict(paged=True, page_size=16) if paged else {}
    b = _batcher(**extra)
    assert (b.n_slots, b.admit_batch, b.max_seq_len) == (4, 4, 160)
    b.start()
    try:
        b.warmup()
        built = admit_group._cache_size()
        before = _counters()
        buckets = sorted({b._bucket(n) for n in range(1, b.max_seq_len + 1)})
        assert buckets == [64, 128, 160]
        for bucket in buckets:
            plen = min(bucket, b.max_seq_len - 8)
            for n in range(1, b.admit_batch + 1):
                reqs = [
                    GenRequest(prompt_ids=list(range(9 + i, 9 + i + plen)),
                               max_new_tokens=2)
                    for i in range(n)
                ]
                b._submit_together(reqs)
                for r in reqs:
                    r.future.result(timeout=LIMIT_S)
    finally:
        b.stop()
    # every group ran as one dispatch: at its rung at the top bucket, at
    # admit_batch rows below it
    assert [[b._row_bucket(n, bk) for n in (1, 2, 3, 4)] for bk in buckets] == [
        [4, 4, 4, 4], [4, 4, 4, 4], [1, 2, 4, 4]]
    run = _counters()["engine.prefill_tokens_run"] - before.get("engine.prefill_tokens_run", 0.0)
    assert run == 16 * 64 + 16 * 128 + 11 * 160
    # ... and none of them was new to the compiler
    assert admit_group._cache_size() == built


def _flight(edge, handler, wait, prefill, decode, tpot):
    return {"edge_self_s": edge, "handler_self_s": handler, "batcher_wait_s": wait,
            "prefill_s": prefill, "decode_s": decode, "tpot_s": tpot,
            "edge_s": edge + handler + wait + prefill + decode + 0.0005,
            "batcher_tail_s": 0.0005}


def _ctx():
    flights = {
        "a": _flight(0.001, 0.002, 0.5, 0.8, 4.7, 0.10),
        "b": _flight(0.003, 0.004, 1.5, 0.9, 7.05, 0.15),
        "c": _flight(0.002, 0.006, 2.5, 1.0, 9.4, 0.20),
        "late": _flight(9.0, 9.0, 9.0, 9.0, 9.0, 9.0),      # not due in the window
    }
    records = [
        {"id": i, "ok": True, "in_window": i != "late", "sent": 10.0,
         "done": 10.0 + f["edge_s"] + 0.001}
        for i, f in flights.items()
    ]
    counters = {
        "engine.prefill_tokens_real": 17_057.0, "engine.prefill_tokens_run": 204_800.0,
        "engine.decode_rows_active": 1_392.0, "engine.decode_rows_run": 3_600.0,
    }
    return {"records": records, "flights": flights, "counters": counters}


READERS = {
    "edge.self_p50_ms": 2.0,
    "handler.self_p50_ms": 4.0,
    "batcher.queue_wait_p50_ms": 1500.0,
    "model_step.prefill_p50_ms": 900.0,
    "batcher.tpot_p50_ms": 150.0,
    "batcher.prefill_fill_pct.latency": 100.0 * 17_057 / 204_800,
    "batcher.prefill_fill_pct.rate": 100.0 * 17_057 / 204_800,
    "batcher.decode_fill_pct.latency": 100.0 * 1_392 / 3_600,
    "batcher.decode_fill_pct.rate": 100.0 * 1_392 / 3_600,
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_the_hand_worked_number_and_none_on_nothing(name):
    assert bench_run.read_metric(name, _ctx()) == pytest.approx(READERS[name])
    empty = {"records": _ctx()["records"], "flights": {}, "counters": {}}
    assert bench_run.read_metric(name, empty) is None
    # a program without the marks or the counters (the parent of this change)
    old = _ctx()
    old["flights"] = {i: {"ttft_s": 1.0} for i in old["flights"]}
    old["counters"] = {"engine.admitted": 29.0}
    assert bench_run.read_metric(name, old) is None


def test_every_new_reader_is_declared_for_a_cell_that_reports_what_it_moves():
    bench = bench_run.load_benchmark()
    declared = {m["name"]: m for m in bench["per_layer"]}
    reports = {}
    for metric in bench["end_to_end"]:
        for cell in metric.get("workloads", [w["name"] for w in bench["workloads"]]):
            reports.setdefault(cell, set()).add(metric["name"])
    for name in READERS:
        assert name in declared, name
        for cell in declared[name]["workloads"]:
            assert declared[name]["moves"] in reports[cell]


def test_telescope_reads_the_timeline_check_the_builder_prints():
    found = timeline.telescope(_ctx())
    assert found["flights"] == 3
    assert found["sum_off_worst_ms"] == pytest.approx(0.0, abs=1e-6)
    assert found["client_less_edge_p50_ms"] == pytest.approx(1.0)
    assert timeline.telescope({"records": [], "flights": {}, "counters": {}}) is None
