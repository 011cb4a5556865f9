#!/usr/bin/env python3
"""One run of one cell:

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process stays off JAX. It reads the cell from ``BENCHMARK.json``, starts
the serving process (``serving.py``, which holds the chip), warms the shapes
of the cell's own traffic, drives the window over loopback HTTP
(``loadgen.py``), has the serving process compare a sample of what it served
with the plain reference, and prints the contract's object as the last line
of standard output. Everything before it is commentary.
"""

from __future__ import annotations

_T_START = __import__("time").perf_counter()

import argparse
import asyncio
import importlib.util
import json
import random
import re
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench import loadgen, shapes, traffic  # noqa: E402
from perfbench.trace_reduce import top  # noqa: E402
from perfbench.weights import load_config, model_from_config  # noqa: E402

READY_TIMEOUT_S = 1150.0
CHECK_TIMEOUT_S = 330.0     # a run's; the proof runs' controls get CONTROL_TIMEOUT_S more each
CONTROL_TIMEOUT_S = 600.0
WARM_SESSIONS = 8   # of an open mix, unless its file says (warm_sessions)
WARM_MAX_TOKENS = 4


def say(msg: str) -> None:
    print(f"[run] {msg}", flush=True)


def load_benchmark() -> Dict[str, Any]:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_spec(bench: Dict[str, Any], workload: str) -> Dict[str, Any]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]

    def mine(metric: Dict[str, Any]) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    limits_file = HERE / "limits" / f"{workload}.json"
    return {
        "workload": workload, "config": cell["config"], "traffic": cell["traffic"],
        "chips": int(cell["chips"]),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
        "limits": json.loads(limits_file.read_text()) if limits_file.exists() else {},
    }


class Child:
    """The serving process and the pipe of JSON lines to it."""

    def __init__(self, proc: asyncio.subprocess.Process) -> None:
        self.proc = proc

    @classmethod
    async def start(cls, spec: Dict[str, Any]) -> "Child":
        proc = await asyncio.create_subprocess_exec(
            sys.executable, str(HERE / "serving.py"), json.dumps(spec),
            stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE,
            limit=1 << 26, cwd=str(ROOT),
        )
        return cls(proc)

    async def read(self, event: str, timeout: float) -> Dict[str, Any]:
        while True:
            line = await asyncio.wait_for(self.proc.stdout.readline(), timeout)
            if not line:
                code = await self.proc.wait()
                raise ChildGone(code)
            try:
                msg = json.loads(line)
            except ValueError:
                continue   # a stray print of the program's
            if isinstance(msg, dict) and msg.get("event") == event:
                return msg

    async def ask(self, cmd: Dict[str, Any], event: str, timeout: float) -> Dict[str, Any]:
        self.proc.stdin.write((json.dumps(cmd) + "\n").encode())
        await self.proc.stdin.drain()
        return await self.read(event, timeout)

    async def end(self) -> None:
        if self.proc.returncode is None:
            try:
                self.proc.stdin.write(b'{"cmd": "quit"}\n')
                await self.proc.stdin.drain()
                await asyncio.wait_for(self.proc.wait(), 60.0)
            except (asyncio.TimeoutError, ConnectionError, RuntimeError):
                self.proc.kill()
                await self.proc.wait()


class ChildGone(Exception):
    def __init__(self, code: int) -> None:
        super().__init__(f"the serving process ended with code {code}")
        self.code = code


async def warm_shapes(mix: Dict[str, Any], seed: int, port: int) -> Dict[str, Any]:
    """Send once, before any timing, every shape of request the schedule
    holds, so that its programs are built (or read from the compile cache)
    outside the window: the first sessions of an open mix turn by turn, every
    distinct length of a closed one. Bytes differ from the run's (lap -1),
    except the shared pieces, which the run finds cached as a steady server
    would."""
    t = time.perf_counter()
    sessions = mix["sessions"]
    sent = failed = 0

    async def one(s: int, j: int, cap: Optional[int]) -> None:
        nonlocal sent, failed
        body, _ = traffic.request_body(mix, seed, s, j, lap=-1)
        if cap:
            body["max_tokens"] = min(body["max_tokens"], cap)
        status, _, _ = await loadgen.post(port, f"w{s}x{j}", body, timeout=1100.0)
        sent += 1
        failed += status != 200

    if mix["loop"] == "open":
        async def walk(s: int) -> None:
            for j in range(len(sessions[s]["turns"])):
                await one(s, j, None)

        await asyncio.gather(*(walk(s) for s in range(min(int(mix.get("warm_sessions", WARM_SESSIONS)), len(sessions)))))
    else:
        seen, todo = set(), []
        for s, sess in enumerate(sessions):
            for j, turn in enumerate(sess["turns"]):
                key = (turn["user"], turn["max_tokens"])
                if key not in seen:
                    seen.add(key)
                    todo.append((s, j))
        gate = asyncio.Semaphore(len(sessions))

        async def gated(s: int, j: int) -> None:
            async with gate:
                await one(s, j, WARM_MAX_TOKENS)

        await asyncio.gather(*(gated(s, j) for s, j in todo))
    return {"requests": sent, "failed": failed, "seconds": time.perf_counter() - t}


def draw_sample(records: List[Dict[str, Any]], seed: int, n: int) -> List[str]:
    """``n`` of the window's finished requests, drawn from the seed, the
    longest among them."""
    done = sorted(
        (r for r in records if r.get("in_window") and r.get("ok")), key=lambda r: r["id"]
    )
    if not done:
        return []
    longest = max(done, key=lambda r: (r["expect_prompt"] + r["max_tokens"], r["id"]))
    rest = [r for r in done if r is not longest]
    picked = random.Random(int(seed)).sample(rest, min(max(n - 1, 0), len(rest)))
    return [longest["id"]] + [r["id"] for r in picked]


def read_metric(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    """``metrics/<name>.py`` is the metric's reader: ``read(ctx)`` gives the
    number, or None where it found nothing to read."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"perfbench_metric_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    value = module.read(ctx)
    return None if value is None else float(value)


async def run_cell(
    spec: Dict[str, Any], seed: int, seconds: float, trace: bool,
    platform: str = "tpu", fault: Optional[str] = None, modes: Optional[List[str]] = None,
    dump: Optional[str] = None,
) -> Dict[str, Any]:
    """Everything of a run but the look for a chip's result line. ``platform``
    and ``fault`` are for the tests, which rehearse this on the CPU at a tiny
    size; ``modes`` adds the controls' readings and ``dump`` writes every
    request's times and every sampled token's reading to a file (the proof
    runs)."""
    cfg = load_config(spec["config"])
    model = model_from_config(cfg)
    mix = traffic.load_mix(spec["traffic"])
    child = await Child.start({
        "config": spec["config"], "seed": seed, "platform": platform,
        "chips": spec["chips"], "trace": trace, "fault": fault,
    })
    try:
        ready = await child.read("ready", READY_TIMEOUT_S)
        port, device = ready["port"], ready["device"]
        boot = ready["boot"]
        say(f"server ready after {time.perf_counter() - _T_START:.1f} s (engine "
            f"{ready['engine_up_s']:.1f} s) on {device['count']} x {device['kind']}; "
            f"programs built {boot['compiles']['requests']}, from the compile cache "
            f"{boot['compiles']['cache_hits']}; page strip {boot['page_strip']}, "
            f"pallas {boot['use_pallas']}, pages {boot['num_pages']}")
        warm = await warm_shapes(mix, seed, port)
        say(f"shape warm-up: {warm['requests']} requests ({warm['failed']} failed) "
            f"in {warm['seconds']:.1f} s")
        marks: Dict[str, Any] = {}

        async def on_open() -> Optional[bool]:
            marks["setup_s"] = time.perf_counter() - _T_START
            marks["open"] = await child.ask({"cmd": "open"}, "open", 30.0)
            marks["ramp_stall_s"] = (
                marks["open"]["compiles"]["stall_s"] - marks["mark"]["compiles"]["stall_s"])
            return None

        async def on_close() -> None:
            marks["close"] = await child.ask({"cmd": "close"}, "close", 120.0)

        marks["mark"] = await child.ask({"cmd": "mark"}, "mark", 30.0)
        if mix["loop"] == "open":
            run = await loadgen.drive_open(mix, seed, port, seconds, on_open, on_close)
        else:
            run = await loadgen.drive_closed(mix, seed, port, seconds, on_open, on_close)
        summary = loadgen.window_summary(run, mix["loop"])
        ids = draw_sample(run["records"], seed, int(mix.get("check_requests", 4)))
        checked = await child.ask(
            {"cmd": "check", "ids": ids, "modes": modes or [],
             "router_tie": (spec.get("limits") or {}).get("router_tie", 0.0),
             "per_token": bool(dump)},
            "check", CHECK_TIMEOUT_S + CONTROL_TIMEOUT_S * len(modes or []))
        await asyncio.wait_for(child.proc.wait(), 120.0)
    finally:
        await child.end()
    if dump:
        Path(dump).parent.mkdir(parents=True, exist_ok=True)
        Path(dump).write_text(json.dumps({
            "workload": spec["workload"], "seed": seed, "loop": mix["loop"],
            "t0": run["t0"], "t1": run["t1"],
            "records": run["records"], "counters": marks["close"]["counters"],
            "readings": {k: v for k, v in checked.items()
                         if k == "reference" or k.startswith("control_")},
        }))
        for name in [k for k in checked if k == "reference" or k.startswith("control_")]:
            checked[name].pop("per_token", None)
    return assemble(spec, cfg, model, mix, seed, trace, device, boot, warm, marks,
                    run, summary, ids, checked)


def judge(summary: Dict[str, Any], healing: Dict[str, float], missing: int,
          reading: Dict[str, Any], limits: Dict[str, Any]):
    """Every number compared, beside its limit, and whether all hold. The
    controls' readings are judged by this too, put in the program's place."""
    checks: Dict[str, Dict[str, Any]] = {
        "unanswered": {"value": summary["unanswered"], "limit": 0},
        "failed_requests": {"value": summary["failed"], "limit": 0},
        "short_answers": {"value": summary["short_answers"], "limit": 0},
        "healing_counters": {"value": sum(healing.values()), "limit": 0},
        "sample_missing": {"value": missing, "limit": 0},
    }
    for name in ("gap_max", "gap_mean"):
        checks[name] = {"value": reading.get(name), "limit": limits.get(name)}
    correct = all(
        c["value"] is not None and c["limit"] is not None and c["value"] <= c["limit"]
        for c in checks.values()
    )
    return checks, bool(correct)


def assemble(spec, cfg, model, mix, seed, trace, device, boot, warm, marks, run,
             summary, ids, checked) -> Dict[str, Any]:
    close = marks["close"]
    counters = close["counters"]
    in_window = close["compiles_in_window"]
    healing = {k: v for k, v in close["totals"].items() if v}
    lat = loadgen.step_latencies_ms(run) if mix["loop"] == "open" else []
    say(f"window {summary['seconds']:.2f} s: due {summary['attempted']}, failed "
        f"{summary['failed']}, unanswered {summary['unanswered']}, short answers "
        f"{summary['short_answers']}; prompt tokens due {summary['prompt_tokens_due']}, "
        f"output tokens due {summary['output_tokens_due']}")
    say("engine in the window: " + ", ".join(
        f"{k.split('engine.')[-1]} {counters.get(k, 0):g}" for k in (
            "engine.admitted", "engine.completed", "engine.prefill_segments",
            "engine.paged_chunks.kernel", "engine.paged_chunks.gather",
            "engine.decode_steps", "engine.prefix_hits",
            "engine.kvcache.prefill_tokens_saved", "engine.generated_tokens_device")))
    say(f"programs built or read from the cache between the shape warm-up and the "
        f"window's opening: {marks.get('ramp_stall_s', 0.0):.1f} s")
    if lat:
        say(f"step latency over {len(lat)} requests: p50 {summary['step_latency_p50_ms']:.1f} ms, "
            f"p{summary['supported_percentile']} (ten requests beyond it) "
            f"{summary['step_latency_supported_ms']:.1f} ms, worst "
            f"{summary['step_latency_worst_ms']:.1f} ms")
    say(f"generator lateness: worst {summary['late_worst_ms']:.2f} ms, mean "
        f"{summary['late_mean_ms']:.3f} ms; compile-cache hits in all "
        f"{close['compiles_total']['cache_hits']} of {close['compiles_total']['requests']} "
        f"programs; programs built inside the window {in_window['requests']} "
        f"{close.get('built_names') or ''}; peak "
        f"bytes_in_use {checked['memory_peak_bytes']}; fault, rebuild, recovery and "
        f"shed counters {healing or 0}")
    ref = checked.get("reference") or {}
    say(f"reference over {checked['sampled']} requests, {ref.get('tokens', 0)} served "
        f"tokens, in {checked['reference_s']:.1f} s: gap_max {ref.get('gap_max')}, "
        f"gap_mean {ref.get('gap_mean')}, argmax agreement {ref.get('argmax_agree')}")
    ctx = {
        "model": model, "config": cfg, "mix": mix, "seed": seed,
        "records": run["records"], "t0": run["t0"], "t1": run["t1"],
        "seconds": summary["seconds"], "summary": summary, "counters": counters,
        "flights": checked.get("flights") or {}, "trace": checked.get("trace"),
        "trace_span": close.get("trace_span"), "peaks": shapes.peaks(device["kind"])
        if device["platform"] == "tpu" else None,
    }
    values: Dict[str, Optional[float]] = {
        "setup_s": marks["setup_s"], "tokens_per_s": summary.get("tokens_per_s"),
    }
    for m in spec["end_to_end"]:   # step_latency_p<q>_ms: any percentile a cell names
        named = re.fullmatch(r"step_latency_p(\d+)_ms", m["name"])
        if named and lat:
            values[m["name"]] = loadgen.percentile(lat, int(named.group(1)) / 100)
    metrics: Dict[str, Any] = {}
    if not trace:
        for m in spec["end_to_end"]:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        for m in spec["per_layer"]:
            value = read_metric(m["name"], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    limits = spec.get("limits") or {}
    checks, correct = judge(summary, healing, checked["missing"], ref, limits)
    controls = {}
    for key in sorted(k for k in checked if k.startswith("control_")):
        c_checks, c_correct = judge(summary, healing, checked["missing"], checked[key], limits)
        controls[key] = dict(checked[key], correct=c_correct)
        say(f"{key} in the program's place: correct {c_correct}; " + ", ".join(
            f"{n} {c_checks[n]['value']} (limit {c_checks[n]['limit']})"
            for n in ("gap_max", "gap_mean")) + f", over {checked[key].get('tokens')} tokens")
    dev = dict(device, memory_peak_bytes=checked["memory_peak_bytes"])
    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": summary["attempted"],
        "failed": summary["failed"], "metrics": metrics, "device": dev,
    }
    tr = checked.get("trace")
    if trace and tr:
        dev["busy_s"], dev["window_s"] = tr["busy_s"], tr["window_s"]
        result["breakdown"] = {
            "device_ops": top(tr.get("outer_ops_s") or {}),
            "idle_gaps": top(tr.get("idle_gaps_s") or {}),
        }
        say(f"trace: {tr['window_s']:.3f} s traced, busy {tr['busy_s']:.3f} s, read in "
            f"{tr.get('read_s', 0):.1f} s")
    result["window"] = {
        "workload": spec["workload"], "seed": seed, "summary": summary,
        "counters": {k: v for k, v in counters.items() if v}, "boot": boot,
        "warm": warm, "compiles_in_window": in_window,
        "built_names": close.get("built_names"), "reference": ref,
        "latencies_ms": sorted(
            round(1e3 * (r["done"] - r["due"])) for r in run["records"]
            if r.get("in_window") and r.get("ok")),
        "controls": controls,
        "values": values,
        "ops_at_any_depth": top((checked.get("trace") or {}).get("all_ops_s") or {}, 30),
    }
    result["checks"] = checks
    return result


def main(
    argv: Optional[List[str]] = None, bench: Optional[Dict[str, Any]] = None,
    platform: str = "tpu", fault: Optional[str] = None,
) -> int:
    """``bench``, ``platform`` and ``fault`` are the tests' way in: they skip
    the look for a chip and drive the rest of a run on the CPU."""
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--controls", default="",
                        help="proof runs only: also read these lower precisions, e.g. act8,w4")
    parser.add_argument("--dump", default=None,
                        help="proof runs only: write request times and per-token readings here")
    args = parser.parse_args(argv)
    if not (ROOT / "pilottai_tpu").is_dir():
        print("the program (pilottai_tpu/) is not in this directory", file=sys.stderr)
        return 2
    spec = cell_spec(bench or load_benchmark(), args.workload)
    if bench and "limits" in bench:
        spec["limits"] = bench["limits"]
    say(f"cell {args.workload}, seed {args.seed}, {args.seconds:g} s, trace {args.trace}")
    try:
        result = asyncio.run(run_cell(
            spec, args.seed, args.seconds, bool(args.trace), platform=platform,
            fault=fault, modes=[m for m in args.controls.split(",") if m], dump=args.dump))
    except ChildGone as gone:
        print(f"no result: {gone}", file=sys.stderr)
        return gone.code or 1
    for name, c in result["checks"].items():
        print(f"[check] {name}: {c['value']} (limit {c['limit']})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
