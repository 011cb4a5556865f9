"""Choose an open mix's schedule by how closely its median repeats, on the chip:
one server, every candidate file run for a whole window twice, the two that
repeated best run on while the time lasts (PR 29: a loaded open loop's
nearest-rank median follows a few arrivals that fall at an admission's edge,
and the generator's constant decides how far; PERF.md, section 6).

    python3 perfbench/tools/pick_schedule.py <workload> <seed> <seconds> <out dir> <budget s> <mix.json> ...

The candidates come from the cell's generator (``make_chat_burst.py <constant>
<out.json>``); the workload only names the configuration to serve. Each
window gets prompt bytes of its own. Every window's request times go to
``<out dir>/<mix>.<k>.json``; the last lines give each candidate's medians,
their spread (quartiles over the median, the run farthest from the median
left out where that narrows it, as the driver reads a set) and the steadiest.
The chosen file is then copied to ``perfbench/traffic/`` by hand.
"""
import asyncio
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import loadgen, run, traffic  # noqa: E402

T_START = time.perf_counter()


def spread(values) -> float:
    if len(values) < 3:
        return (max(values) - min(values)) / statistics.median(values) if len(values) > 1 else float("inf")

    def quartiles(v):
        q = statistics.quantiles(v, n=4)
        return (q[2] - q[0]) / statistics.median(v)

    mid = statistics.median(values)
    kept = list(values)
    kept.remove(max(values, key=lambda x: abs(x - mid)))
    return min(quartiles(values), quartiles(kept))


async def main(workload: str, seed: int, seconds: float, out_dir: str, budget_s: float, files) -> int:
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    spec = run.cell_spec(run.load_benchmark(), workload)
    mixes = {f: traffic.load_mix(f) for f in files}
    medians = {f: [] for f in files}
    need = seconds + max(float(m["lead_in_s"]) for m in mixes.values()) + 12.0
    child = await run.Child.start({
        "config": spec["config"], "seed": seed, "platform": "tpu", "chips": 1, "trace": False})
    windows = 0

    def time_left() -> bool:
        return budget_s - (time.perf_counter() - T_START) >= need

    async def window(f: str) -> None:
        nonlocal windows
        marks = {}

        async def on_open():
            marks["open"] = await child.ask({"cmd": "open"}, "open", 30.0)

        async def on_close():
            marks["close"] = await child.ask({"cmd": "close"}, "close", 120.0)

        got = await loadgen.drive_open(
            mixes[f], seed + 1000 * (windows + 1), port, seconds, on_open, on_close, tag=f"x{windows}r")
        s = loadgen.window_summary(got, "open")
        counters = marks["close"]["counters"]
        line = {
            "mix": Path(f).stem, "window": windows, "due": s["attempted"], "failed": s["failed"],
            "p50_ms": s.get("step_latency_p50_ms"), "late_worst_ms": s["late_worst_ms"],
            "built_in_window": marks["close"]["compiles_in_window"]["requests"],
            "decode_steps": counters.get("engine.decode_steps"),
            "prefill_tokens_run": counters.get("engine.prefill_tokens_run"),
        }
        print(json.dumps(line), flush=True)
        (out / f"{Path(f).stem}.{windows}.json").write_text(json.dumps(
            {"t0": got["t0"], "t1": got["t1"], "records": got["records"], "line": line}))
        if not s["failed"] and s.get("step_latency_p50_ms"):
            medians[f].append(s["step_latency_p50_ms"])
        windows += 1
        await asyncio.sleep(1.5)

    try:
        ready = await child.read("ready", run.READY_TIMEOUT_S)
        port = ready["port"]
        print("[pick] ready", json.dumps(ready["boot"]), flush=True)
        print("[pick] warm", json.dumps(await run.warm_shapes(mixes[files[0]], seed, port)), flush=True)
        for _ in range(2):
            for f in files:
                if time_left():
                    await window(f)

        def apart(f: str) -> float:
            v = medians[f]
            return abs(v[0] - v[1]) / statistics.mean(v) if len(v) >= 2 else float("inf")

        best = sorted(files, key=apart)[:2]
        print("[pick] two windows apart:", json.dumps({Path(f).stem: apart(f) for f in files}),
              "; going on with", [Path(f).stem for f in best], flush=True)
        for _ in range(3):
            for f in best:
                if time_left():
                    await window(f)
    finally:
        await child.end()
    most = max(len(v) for v in medians.values())
    finalists = [f for f in files if len(medians[f]) >= max(most - 1, 2)] or files
    chosen = min(finalists, key=lambda f: spread(medians[f]) if len(medians[f]) > 1 else float("inf"))
    print("[pick]", json.dumps({
        Path(f).stem: {"p50_ms": medians[f], "spread": spread(medians[f]) if len(medians[f]) > 1 else None}
        for f in files}), flush=True)
    print("[pick] steadiest:", chosen, flush=True)
    return 0


if __name__ == "__main__":
    a = sys.argv
    sys.exit(asyncio.run(main(a[1], int(a[2]), float(a[3]), a[4], float(a[5]), a[6:])))
