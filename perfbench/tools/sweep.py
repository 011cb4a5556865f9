"""Find an open mix's knee once, on the chip: one server, the stored schedule
squeezed to each rate in turn (``rate_scale``), a short window each.

    python3 perfbench/tools/sweep.py <workload> <seed> <seconds> <rate> [<rate> ...]

Prints one JSON line per rate: requests in flight a third of the way through
and at the close, failures, latency. The knee is the highest rate at which the
count in flight does not grow and nothing is refused; the cell runs at four
fifths of it, and that number is written into the traffic file by hand."""
import asyncio
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import loadgen, run, traffic  # noqa: E402


async def main(workload: str, seed: int, seconds: float, rates) -> int:
    spec = run.cell_spec(run.load_benchmark(), workload)
    mix = dict(traffic.load_mix(spec["traffic"]), lead_in_s=8.0)   # a short lead-in a rate
    base = float(mix["steps_per_s"])
    child = await run.Child.start({
        "config": spec["config"], "seed": seed, "platform": "tpu", "chips": 1, "trace": False})
    try:
        ready = await child.read("ready", run.READY_TIMEOUT_S)
        port = ready["port"]
        print("[sweep] ready", json.dumps(ready["boot"]), flush=True)
        print("[sweep] warm", json.dumps(await run.warm_shapes(mix, seed, port)), flush=True)
        for k, rate in enumerate(rates):
            marks = {}

            async def on_open():
                marks["open"] = await child.ask({"cmd": "open"}, "open", 30.0)

            async def on_close():
                marks["close"] = await child.ask({"cmd": "close"}, "close", 60.0)

            got = await loadgen.drive_open(
                mix, seed + k, port, seconds, on_open, on_close,
                rate_scale=rate / base, tag=f"q{k}r")
            t0, t1 = got["t0"], got["t1"]
            recs = got["records"]

            def in_flight(t):
                return sum(1 for r in recs if r.get("sent", 1e18) <= t and r.get("done", 1e18) > t)

            s = loadgen.window_summary(got, "open")
            print(json.dumps({
                "rate": rate, "due": s["attempted"], "failed": s["failed"],
                "in_flight_third": in_flight(t0 + (t1 - t0) / 3),
                "in_flight_two_thirds": in_flight(t0 + 2 * (t1 - t0) / 3),
                "in_flight_close": in_flight(t1),
                "p50_ms": s.get("step_latency_p50_ms"), "worst_ms": s.get("step_latency_worst_ms"),
                "late_worst_ms": s["late_worst_ms"],
                "built_in_window": marks["close"]["compiles_in_window"],
                "prefix_hits": marks["close"]["counters"].get("engine.prefix_hits"),
                "admitted": marks["close"]["counters"].get("engine.admitted"),
                "tokens_saved": marks["close"]["counters"].get("engine.kvcache.prefill_tokens_saved"),
                "decode_steps": marks["close"]["counters"].get("engine.decode_steps"),
            }), flush=True)
            await asyncio.sleep(2.0)
    finally:
        await child.end()
    return 0


if __name__ == "__main__":
    sys.exit(asyncio.run(main(
        sys.argv[1], int(sys.argv[2]), float(sys.argv[3]), [float(r) for r in sys.argv[4:]])))
