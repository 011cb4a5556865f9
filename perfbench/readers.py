"""What the per-layer readers in ``metrics/`` share: how a request's work is
laid over a stretch of time. A reader gets ``ctx`` (see ``run.py:assemble``)
and returns a number, or None where it found nothing to read."""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional, Tuple

from perfbench import loadgen, shapes, trace_reduce


def flights(
    ctx: Dict[str, Any], in_window_only: bool = True
) -> Iterable[Tuple[Dict[str, Any], Dict[str, float]]]:
    """Finished requests with their flight-recorder ledger: those due in the
    window, or (for the traced slice, which sees whatever is on the device)
    every one of the run."""
    for r in ctx["records"]:
        flight = ctx["flights"].get(r["id"])
        if r.get("ok") and flight and (r.get("in_window") or not in_window_only):
            yield r, flight


def counter_rate(ctx: Dict[str, Any], name: str) -> Optional[float]:
    delta = ctx["counters"].get(name)
    return None if not delta else delta / ctx["seconds"]


def hbm_stream_pct(ctx: Dict[str, Any]) -> Optional[float]:
    """Decode steps in the window x the bytes of weights one step streams,
    over the window, as a share of the chip's memory bandwidth."""
    steps = counter_rate(ctx, "engine.decode_steps")
    if steps is None or ctx["peaks"] is None:
        return None
    need = steps * shapes.decode_step_weight_bytes(ctx["model"])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"]


def required_flops(ctx: Dict[str, Any]) -> float:
    """Operations the model requires for the window's requests, each credited
    by the share of its send-to-last-byte interval that lies in the window."""
    t0, t1 = ctx["t0"], ctx["t1"]
    return sum(
        shapes.request_flops(ctx["model"], r["prompt_tokens"], r["completion_tokens"])
        * loadgen.overlap_share(r["sent"], r["done"], t0, t1)
        for r in ctx["records"] if r.get("ok")
    )


def idle_pct(ctx: Dict[str, Any]) -> Optional[float]:
    tr = ctx.get("trace")
    if not tr or not tr.get("window_s") or not tr.get("busy_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def traced_prefill_context(ctx: Dict[str, Any]) -> Tuple[float, float]:
    """(query tokens, sum of keys seen) of the prefill work that fell inside
    the traced slice: a request's prefill runs from its admission to its first
    token, and its work is laid evenly over that stretch."""
    span = ctx.get("trace_span") or [0.0, 0.0]
    q = c = 0.0
    for r, f in flights(ctx, in_window_only=False):
        if "first_token_at" not in f:
            continue
        share = loadgen.overlap_share(f["admitted_at"], f["first_token_at"], *span)
        p = r["prompt_tokens"]
        q += share * p
        c += share * p * (p + 1) / 2.0
    return q, c


def roofline_pct(
    ctx: Dict[str, Any], pattern: str, flops: float, nbytes: float
) -> Optional[float]:
    """The least time the chip could take for the work (the larger of
    operations over peak and bytes over bandwidth) over the device time of the
    operations whose name holds ``pattern``. None where the trace has none."""
    tr, peaks = ctx.get("trace"), ctx.get("peaks")
    if not tr or peaks is None:
        return None
    seconds = trace_reduce.kernel_seconds(tr, pattern)
    if seconds <= 0.0 or (flops <= 0.0 and nbytes <= 0.0):
        return None
    least = max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
