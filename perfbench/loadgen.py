"""Load over loopback HTTP from one thread, and the arithmetic of a window.

No JAX here. ``drive_open`` and ``drive_closed`` send ``POST
/v1/chat/completions`` as the traffic file schedules them and return one
record per request with its times on this process's ``perf_counter``.
"""

from __future__ import annotations

import asyncio
import json
import statistics
import time
from typing import Any, Awaitable, Callable, Dict, List

from perfbench import traffic

FAILED_MS = 600_000.0   # a failed or refused step counts as slower than any
WAIT_AFTER_CLOSE_S = 60.0

Edge = Callable[[], Awaitable[None]]


async def post(port: int, rid: str, body: Dict[str, Any], timeout: float = 300.0):
    """One request on a connection of its own (the server closes each).
    Returns ``(status, parsed body or None, seconds of the last byte)``."""
    payload = json.dumps(body).encode()
    head = (
        f"POST /v1/chat/completions HTTP/1.1\r\nHost: 127.0.0.1\r\n"
        f"Content-Type: application/json\r\nx-request-id: {rid}\r\n"
        f"Content-Length: {len(payload)}\r\nConnection: close\r\n\r\n"
    ).encode()
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
    except OSError:
        return 0, None, time.perf_counter()
    try:
        writer.write(head + payload)
        await writer.drain()
        raw = await asyncio.wait_for(reader.read(-1), timeout)
        done = time.perf_counter()
    except (OSError, asyncio.TimeoutError, asyncio.IncompleteReadError):
        return 0, None, time.perf_counter()
    finally:
        writer.close()
    try:
        head_b, _, body_b = raw.partition(b"\r\n\r\n")
        status = int(head_b.split(b" ", 2)[1])
        return status, json.loads(body_b), done
    except (ValueError, IndexError):
        return 0, None, done


async def _one(port, rid, body, expect_prompt, record):
    record["sent"] = time.perf_counter()
    status, answer, done = await post(port, rid, body)
    record["done"] = done
    record["status"] = status
    usage = (answer or {}).get("usage") or {}
    record["completion_tokens"] = int(usage.get("completion_tokens") or 0)
    record["prompt_tokens"] = int(usage.get("prompt_tokens") or 0)
    record["ok"] = (
        status == 200
        and record["completion_tokens"] == record["max_tokens"]
        and record["prompt_tokens"] == expect_prompt
    )


async def drive_open(
    mix, seed: int, port: int, seconds: float, on_open: Edge, on_close: Edge,
    rate_scale: float = 1.0, tag: str = "r",
) -> Dict[str, Any]:
    """Run the stored schedule from its start; the window is ``[lead_in,
    lead_in + seconds)`` of it. Requests due after the close keep going out
    until every request due inside has its answer (the late ones' batches
    would otherwise run emptier than the early ones')."""
    lead = float(mix["lead_in_s"])
    plan = traffic.open_schedule(mix, lead + seconds + WAIT_AFTER_CLOSE_S, rate_scale)
    bodies = [traffic.request_body(mix, seed, s, j) for _, s, j in plan]
    records: List[Dict[str, Any]] = []
    tasks: List[asyncio.Task] = []
    origin = time.perf_counter() + 0.05
    t0, t1 = origin + lead, origin + lead + seconds
    opened = closed = False
    for i, ((due, s, j), (body, n_prompt)) in enumerate(zip(plan, bodies)):
        at = origin + due
        if not opened and at >= t0:
            await _sleep_until(t0)
            await on_open()
            opened = True
        if not closed and at >= t1:
            await _sleep_until(t1)
            await on_close()
            closed = True
        if closed and all(
            t.done() for t, r in zip(tasks, records) if r["in_window"]
        ):
            break
        await _sleep_until(at)
        rec = {
            "id": f"{tag}{i}", "session": s, "turn": j, "due": at,
            "max_tokens": body["max_tokens"], "expect_prompt": n_prompt,
            "in_window": t0 <= at < t1,
        }
        records.append(rec)
        tasks.append(asyncio.create_task(_one(port, rec["id"], body, n_prompt, rec)))
    if not opened:
        await _sleep_until(t0)
        await on_open()
    if not closed:
        await _sleep_until(t1)
        await on_close()
    await _settle(tasks, t1)
    return {"records": records, "t0": t0, "t1": t1}


async def drive_closed(
    mix, seed: int, port: int, seconds: float, on_open: Edge, on_close: Edge,
    tag: str = "r",
) -> Dict[str, Any]:
    """Every session is one waiting caller. They start one after another at
    their ``start`` offsets; the window opens once each has an answer and
    closes ``seconds`` later, after which no new request goes out."""
    sessions = mix["sessions"]
    records: List[Dict[str, Any]] = []
    first_done = [asyncio.Event() for _ in sessions]
    state = {"closed": False}
    origin = time.perf_counter() + 0.05

    async def caller(s: int):
        await _sleep_until(origin + sessions[s]["start"])
        n = 0
        turns = sessions[s]["turns"]
        while not state["closed"]:
            turn, lap = n % len(turns), n // len(turns)
            body, n_prompt = traffic.request_body(mix, seed, s, turn, lap)
            rec = {
                "id": f"{tag}{s}x{n}", "session": s, "turn": turn,
                "due": time.perf_counter(), "max_tokens": body["max_tokens"],
                "expect_prompt": n_prompt,
            }
            records.append(rec)
            await _one(port, rec["id"], body, n_prompt, rec)
            first_done[s].set()
            if not rec["ok"]:
                await asyncio.sleep(0.5)   # a refusing server is not hammered
            n += 1

    callers = [asyncio.create_task(caller(s)) for s in range(len(sessions))]
    await asyncio.gather(*(e.wait() for e in first_done))
    t0 = time.perf_counter()
    await on_open()
    t1 = t0 + seconds
    await _sleep_until(t1)
    state["closed"] = True
    await on_close()
    await _settle(callers, t1)
    for r in records:
        # one that never got its answer still counts: it was sent inside
        r["in_window"] = "sent" in r and r["sent"] < t1 and r.get("done", t1) > t0
    return {"records": [r for r in records if "sent" in r], "t0": t0, "t1": t1}


async def _sleep_until(t: float) -> None:
    delay = t - time.perf_counter()
    if delay > 0:
        await asyncio.sleep(delay)


async def _settle(tasks, t1: float) -> None:
    """Wait for every answer, a minute past the close if need be; what has
    not come by then never came."""
    pending = [t for t in tasks if not t.done()]
    if pending:
        left = max(t1 + WAIT_AFTER_CLOSE_S - time.perf_counter(), 0.1)
        _, late = await asyncio.wait(pending, timeout=left)
        for t in late:
            t.cancel()
        await asyncio.gather(*late, return_exceptions=True)
    for t in tasks:
        if t.done() and not t.cancelled() and t.exception() is not None:
            raise t.exception()


# --------------------------------------------------------------------- #
# The arithmetic of a window
# --------------------------------------------------------------------- #

def overlap_share(sent: float, done: float, t0: float, t1: float) -> float:
    """The share of ``[sent, done]`` that lies inside ``[t0, t1]``."""
    if done <= sent:
        return 1.0 if t0 <= sent < t1 else 0.0
    return max(min(done, t1) - max(sent, t0), 0.0) / (done - sent)


def credited_tokens(records, t0: float, t1: float) -> float:
    """Output tokens delivered in the window: a request inside it is credited
    its ``completion_tokens``, one that straddles an edge the share of them
    that its send-to-last-byte interval's overlap is of the interval."""
    return sum(
        r["completion_tokens"] * overlap_share(r["sent"], r["done"], t0, t1)
        for r in records if r.get("ok")
    )


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile (no interpolation past the sample)."""
    ordered = sorted(values)
    rank = max(int(-(-q * len(ordered) // 1)), 1)
    return ordered[min(rank, len(ordered)) - 1]


def step_latencies_ms(run: Dict[str, Any]) -> List[float]:
    """Of every request due in the window: last byte received less the instant
    it was due; a failed or refused one counts as slower than any."""
    return [
        1e3 * (r["done"] - r["due"]) if r.get("ok") else FAILED_MS
        for r in run["records"] if r.get("in_window")
    ]


def window_summary(run: Dict[str, Any], loop: str) -> Dict[str, Any]:
    t0, t1 = run["t0"], run["t1"]
    inside = [r for r in run["records"] if r.get("in_window")]
    unanswered = [r for r in inside if "done" not in r or r.get("status", 0) == 0]
    failed = [r for r in inside if not r.get("ok")]
    out: Dict[str, Any] = {
        "attempted": len(inside), "failed": len(failed),
        "unanswered": len(unanswered),
        "short_answers": sum(
            1 for r in inside
            if r.get("status") == 200 and r["completion_tokens"] != r["max_tokens"]
        ),
        "prompt_tokens_due": sum(r["expect_prompt"] for r in inside),
        "output_tokens_due": sum(r["max_tokens"] for r in inside),
        "seconds": t1 - t0,
    }
    done = [r for r in inside if r.get("ok")]
    if loop == "open":
        late = [r["sent"] - r["due"] for r in inside if "sent" in r]
        out["late_worst_ms"] = 1e3 * max(late, default=0.0)
        out["late_mean_ms"] = 1e3 * (statistics.fmean(late) if late else 0.0)
        lat = step_latencies_ms(run)
        if lat:
            # the median, and the highest percentile with ten requests beyond it
            out["step_latency_p50_ms"] = percentile(lat, 0.50)
            out["supported_percentile"] = max(int(100 * (len(lat) - 10) / len(lat)), 50)
            out["step_latency_supported_ms"] = percentile(lat, out["supported_percentile"] / 100)
            out["step_latency_worst_ms"] = max(lat)
    else:
        out["late_worst_ms"] = out["late_mean_ms"] = 0.0
        out["tokens_per_s"] = credited_tokens(done, t0, t1) / (t1 - t0)
        out["completed_inside"] = sum(
            1 for r in done if r["sent"] >= t0 and r["done"] <= t1
        )
    return out
