"""What the readers of the request timeline share. The program's flight
recorder stamps a mark where a request crosses each layer's boundary and
derives every layer's self time (``obs/flight.py``: ``edge_self_s``,
``handler_self_s``, ``batcher_wait_s``, ``prefill_s``, ``decode_s``,
``tpot_s``); it counts padded against real work where admissions and decode
chunks are dispatched (``engine.prefill_tokens_*``, ``engine.decode_rows_*``).
A program that has none of these (the parent of the PR that added them) gives
every reader here nothing to read, and each returns None."""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from perfbench import loadgen, readers

SELF_TIMES = ("edge_self_s", "handler_self_s", "batcher_wait_s", "prefill_s", "decode_s")


def flight_median_ms(ctx: Dict[str, Any], key: str) -> Optional[float]:
    """Median, in ms, of one derived time over the window's finished requests."""
    values = [1e3 * f[key] for _, f in readers.flights(ctx) if key in f]
    return loadgen.percentile(values, 0.50) if values else None


def fill_pct(ctx: Dict[str, Any], real: str, run: str) -> Optional[float]:
    """Real work as a share of the work the program ran, from two counters'
    differences across the window."""
    done, ran = ctx["counters"].get(real), ctx["counters"].get(run)
    if not done or not ran:
        return None
    return 100.0 * done / ran


def telescope(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """How well the timeline adds up, over the window's flights: the five self
    times against the edge's span less the stretch between the last token and
    the batcher's hand-back (worst difference, ms), and the client's latency
    less the edge's span (median, ms). None where the flights carry no edge."""
    gaps: List[float] = []
    outside: List[float] = []
    for r, f in readers.flights(ctx):
        if not all(k in f for k in SELF_TIMES + ("edge_s", "batcher_tail_s")):
            continue
        whole = f["edge_s"] - f["batcher_tail_s"]
        gaps.append(1e3 * abs(sum(f[k] for k in SELF_TIMES) - whole))
        outside.append(1e3 * ((r["done"] - r["sent"]) - f["edge_s"]))
    if not gaps:
        return None
    return {
        "flights": len(gaps), "sum_off_worst_ms": max(gaps),
        "client_less_edge_p50_ms": loadgen.percentile(outside, 0.50),
        "client_less_edge_worst_ms": max(outside),
    }
