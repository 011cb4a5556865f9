"""The serving process: the only process of a run that touches JAX.

It registers the cell's configuration with the program's registry, makes the
weights from the seed, starts the program's own server (``cli._build_parser``
and ``cli.run_serve``, as ``pilottai-tpu serve`` would) and then obeys the
parent over a pipe of JSON lines: ``open`` and ``close`` mark the window's
edges (counters and compilations are read there, the trace is taken inside),
``check`` reads the device's peak, stops the server, frees its state and runs
the plain reference over the sample the parent drew.

What it takes from the program: the server under test, its counters
(``global_metrics``), its flight recorder, and the token ids of each request as
the batcher hands them back (a tap on ``batcher.submit``).
"""

from __future__ import annotations

import asyncio
import contextlib
import gc
import json
import logging
import os
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

NO_CHIP_EXIT = 3
TRACE_AFTER_OPEN_S = 1.0
TRACE_SLICE_S = 3.0


def say(msg: str) -> None:
    print(f"[serve] {msg}", file=sys.stderr, flush=True)


# The pipe to the parent is the process's own standard output; whatever the
# program prints goes to standard error instead.
_PIPE = os.fdopen(os.dup(1), "w")
os.dup2(2, 1)


def reply(obj: Dict[str, Any]) -> None:
    _PIPE.write(json.dumps(obj) + "\n")
    _PIPE.flush()


class CompileCounter:
    """Counts program builds through JAX's monitoring events: every compile
    request, and those of them that the persistent cache answered."""

    REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
    HIT = "/jax/compilation_cache/cache_hits"
    BACKEND = "/jax/core/compile/backend_compile_duration"
    RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self) -> None:
        self.counts = {"requests": 0, "cache_hits": 0, "backend_compiles": 0, "stall_s": 0.0}
        self._lock = threading.Lock()

    def install(self) -> None:
        import jax.monitoring

        def on_event(name: str, **_: Any) -> None:
            key = {self.REQUEST: "requests", self.HIT: "cache_hits"}.get(name)
            if key:
                with self._lock:
                    self.counts[key] += 1

        def on_duration(name: str, secs: float, **_: Any) -> None:
            if name in (self.BACKEND, self.RETRIEVAL):
                with self._lock:
                    self.counts["backend_compiles"] += name == self.BACKEND
                    self.counts["stall_s"] += float(secs)

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self.counts)

    def name_programs(self, on: bool) -> List[str]:
        """Between the window's edges JAX logs each program it builds; the
        names say which shape the warm-up missed. Returns those seen."""
        import jax

        log = logging.getLogger("jax")
        if on:
            self.names: List[str] = []
            self._handler = _Names(self.names)
            log.addHandler(self._handler)
        jax.config.update("jax_log_compiles", on)
        if not on and getattr(self, "_handler", None) is not None:
            log.removeHandler(self._handler)
            self._handler = None
        return list(getattr(self, "names", []))


class _Names(logging.Handler):
    def __init__(self, names: List[str]) -> None:
        super().__init__(level=logging.DEBUG)
        self.names = names

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith(("Compiling ", "Finished tracing")):
            self.names.append(msg[:160])


class Tap:
    """Keeps, per ``x-request-id``, the prompt ids the engine was given and
    the token ids it handed back. A ``fault`` (tests only) breaks the answer
    where it is produced, before the server renders it: ``alter_token``
    changes one token, ``cut_short`` drops the last."""

    def __init__(self, vocab: int, fault: Optional[str] = None) -> None:
        self.vocab = vocab
        self.fault = fault
        self.seen: Dict[str, Dict[str, Any]] = {}
        self._lock = threading.Lock()

    def attach(self, batcher: Any) -> None:
        inner = batcher.submit

        def submit(request: Any):
            future = inner(request)
            entry = {"prompt": list(request.prompt_ids), "served": None}
            rid = getattr(request, "trace_id", None)
            if rid:
                with self._lock:
                    self.seen[rid] = entry

            def done(fut: Any) -> None:
                if fut.cancelled() or fut.exception() is not None:
                    return
                ids = fut.result()
                if self.fault == "alter_token" and len(ids) > 2:
                    ids[len(ids) // 2] = (ids[len(ids) // 2] + 1) % self.vocab
                elif self.fault == "cut_short" and len(ids) > 2:
                    del ids[-1]
                entry["served"] = list(ids)

            future.add_done_callback(done)
            return future

        batcher.submit = submit

    def sample(self, ids: List[str]) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(self.seen[i], id=i) for i in ids if i in self.seen]


class Server:
    def __init__(self, spec: Dict[str, Any]) -> None:
        self.spec = spec
        self.seed = int(spec["seed"])
        self.platform = spec.get("platform", "tpu")
        self.trace = bool(spec.get("trace"))
        self.compiles = CompileCounter()
        self.engines: List[Any] = []
        self.flights: Dict[str, Dict[str, float]] = {}
        self.edges: Dict[str, Dict[str, Any]] = {}
        self.trace_dir: Optional[str] = None
        self.trace_span = [0.0, 0.0]
        self._trace_task: Optional[asyncio.Task] = None

    # -- set-up ---------------------------------------------------------- #

    def prepare(self) -> None:
        from perfbench import archs
        from perfbench.weights import load_config, model_from_config

        if self.platform == "cpu":
            os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        devices = jax.devices()
        self.device = devices[0]
        if self.platform == "tpu" and (
            devices[0].platform != "tpu" or len(devices) < int(self.spec.get("chips", 1))
        ):
            say(f"needs {self.spec.get('chips', 1)} TPU chip(s); JAX found "
                f"{len(devices)} x {devices[0].platform} ({devices[0].device_kind})")
            raise SystemExit(NO_CHIP_EXIT)
        self.cfg = load_config(self.spec["config"])
        self.model = model_from_config(self.cfg)
        arch = archs.of(self.model)
        self.compiles.install()

        from pilottai_tpu.engine import native
        from pilottai_tpu.models.registry import register_model
        from pilottai_tpu.obs import global_flight

        register_model(arch.program_config(self.cfg, self.model))
        argv = list(self.cfg["serve"]["argv"])
        int8 = "int8" in _flag(argv, "--quantize", "")
        model, seed = self.model, self.seed

        def init_from_seed(cfg: Any, key: Any, dtype: Any = None, quantize: bool = False):
            return arch.program_params(model, seed, int8)

        native.init_params = init_from_seed
        start = native.NativeEngine._start_blocking
        engines = self.engines

        def start_and_keep(engine: Any) -> None:
            # No request may end before max_tokens: -1 is GenRequest's own
            # "no end-of-sequence id", read per request from the tokenizer.
            if not hasattr(engine.tokenizer, "eos_id"):
                raise RuntimeError("the engine's tokenizer has no eos_id to switch off")
            engine.tokenizer.eos_id = -1
            engines.append(engine)
            start(engine)

        native.NativeEngine._start_blocking = start_and_keep

        def on_finish(flight: Any) -> None:
            d = flight.derived()
            d["started"] = flight.started
            d["status_ok"] = 1.0 if flight.status == "ok" else 0.0
            if flight.first_token_at is not None:
                d["first_token_at"] = flight.first_token_at
            if flight.ended is not None:
                d["ended"] = flight.ended
            d["admitted_at"] = flight.marks.get("admitted", flight.started)
            self.flights[flight.trace_id] = d

        global_flight.add_finish_listener(on_finish)
        self.argv = [
            "serve", "--model", self.model.name, "--provider", self.platform,
            "--host", "127.0.0.1", "--port", "0", *argv,
        ]

    async def start(self) -> int:
        from pilottai_tpu import cli

        args = cli._build_parser().parse_args(self.argv)
        say("pilottai-tpu " + " ".join(self.argv))
        self.ready, self.stop = asyncio.Event(), asyncio.Event()
        self.task = asyncio.create_task(cli.run_serve(args, self.ready, self.stop))
        waiter = asyncio.create_task(self.ready.wait())
        done, _ = await asyncio.wait({self.task, waiter}, return_when=asyncio.FIRST_COMPLETED)
        if self.task in done:
            waiter.cancel()
            self.task.result()
            raise RuntimeError("run_serve returned before it was ready")
        if len(self.engines) != 1 or self.engines[0].batcher is None:
            raise RuntimeError(f"expected one started engine, found {len(self.engines)}")
        engine = self.engines[0]
        if engine.tokenizer.eos_id != -1:
            raise RuntimeError("the engine's tokenizer took its eos_id back")
        self.tap = Tap(self.model.vocab, self.spec.get("fault"))
        self.tap.attach(engine.batcher)
        b = engine.batcher
        self.boot = {
            "page_strip": getattr(b, "page_strip", None),
            "use_pallas": bool(getattr(b, "use_pallas", False)),
            "num_pages": getattr(b, "num_pages", None),
            "n_slots": getattr(b, "n_slots", None),
            "compiles": self.compiles.snapshot(),
        }
        return args._bound_port

    # -- the window ------------------------------------------------------ #

    def _edge(self) -> Dict[str, Any]:
        from pilottai_tpu.utils.metrics import global_metrics

        stats = self.device.memory_stats() or {}
        return {
            "at": time.perf_counter(),
            "counters": global_metrics.snapshot()["counters"],
            "compiles": self.compiles.snapshot(),
            "bytes_in_use": int(stats.get("bytes_in_use", 0)),
        }

    async def open(self) -> Dict[str, Any]:
        self.edges["open"] = self._edge()
        self.compiles.name_programs(True)
        if self.trace:
            self._trace_task = asyncio.create_task(self._take_trace())
        return {"at": self.edges["open"]["at"], "compiles": self.edges["open"]["compiles"]}

    async def _take_trace(self) -> None:
        import jax

        await asyncio.sleep(TRACE_AFTER_OPEN_S)
        self.trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, jax.profiler.start_trace, self.trace_dir)
        self.trace_span[0] = time.perf_counter()
        await asyncio.sleep(TRACE_SLICE_S)
        self.trace_span[1] = time.perf_counter()
        await loop.run_in_executor(None, jax.profiler.stop_trace)

    async def close(self) -> Dict[str, Any]:
        self.edges["close"] = self._edge()
        built = self.compiles.name_programs(False)
        if self._trace_task is not None:
            await self._trace_task
        a, b = self.edges["open"], self.edges["close"]
        names = set(a["counters"]) | set(b["counters"])
        return {
            "at": b["at"],
            "counters": {
                n: b["counters"].get(n, 0.0) - a["counters"].get(n, 0.0) for n in sorted(names)
            },
            "totals": {n: v for n, v in b["counters"].items() if _healing(n)},
            "compiles_in_window": {
                k: b["compiles"][k] - a["compiles"][k] for k in b["compiles"]
            },
            "compiles_total": b["compiles"],
            "built_names": built[:20],
            "bytes_in_use": b["bytes_in_use"],
            "trace_span": list(self.trace_span),
        }

    # -- after the window ------------------------------------------------ #

    async def check(
        self, ids: List[str], modes: List[str], router_tie: float = 0.0,
        per_token: bool = False,
    ) -> Dict[str, Any]:
        """The device's peak is read and the server stopped and freed before
        the reference touches the chip. ``modes`` and ``per_token`` are the
        proof runs'."""
        from perfbench import reference

        stats = self.device.memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
        samples = [s for s in self.tap.sample(ids) if s["served"]]
        flights = dict(self.flights)
        self.stop.set()
        with contextlib.suppress(asyncio.CancelledError):
            await self.task
        self.engines.clear()
        gc.collect()
        out: Dict[str, Any] = {
            "memory_peak_bytes": peak, "flights": flights, "sampled": len(samples),
            "missing": len(ids) - len(samples) + (0 if ids else 1),
        }
        if self.trace_dir is not None:
            import shutil

            from perfbench import trace_reduce

            t = time.perf_counter()
            out["trace"] = trace_reduce.reduce_dir(self.trace_dir)
            out["trace"]["read_s"] = time.perf_counter() - t
            shutil.rmtree(self.trace_dir, ignore_errors=True)
        loop = asyncio.get_running_loop()
        t = time.perf_counter()
        if samples:
            def read(mode: str, against=()):
                return reference.served_gaps(
                    self.model, self.seed, samples, mode, against, router_tie)

            ref = await loop.run_in_executor(None, read, "f32")
            logits = ref.pop("logits")
            out["reference"] = ref
            for mode in modes:
                ctl = await loop.run_in_executor(None, read, mode, logits)
                ctl.pop("logits")
                out[f"control_{mode}"] = ctl
            if not per_token:
                for name in [k for k in out if k == "reference" or k.startswith("control_")]:
                    out[name].pop("per_token", None)
        out["reference_s"] = time.perf_counter() - t
        return out


def _healing(name: str) -> bool:
    return name.startswith(("engine.faults.", "engine.rebuilds", "engine.shed")) or name in (
        "engine.recovered_requests", "engine.recovery_requeued", "engine.recovery_failed",
        "engine.errors", "engine.poisoned", "engine.expired", "server.shed_responses",
    )


def _flag(argv: List[str], name: str, default: str) -> str:
    return argv[argv.index(name) + 1] if name in argv else default


async def serve(spec: Dict[str, Any]) -> int:
    server = Server(spec)
    t0 = time.perf_counter()
    server.prepare()
    port = await server.start()
    d = server.device
    reply({
        "event": "ready", "port": port, "engine_up_s": time.perf_counter() - t0,
        "boot": server.boot,
        "device": {"platform": d.platform, "kind": d.device_kind,
                   "count": len(__import__("jax").devices())},
    })
    loop = asyncio.get_running_loop()
    while True:
        line = await loop.run_in_executor(None, sys.stdin.readline)
        if not line:
            break
        cmd = json.loads(line)
        if cmd["cmd"] == "open":
            reply({"event": "open", **await server.open()})
        elif cmd["cmd"] == "mark":
            reply({"event": "mark", "compiles": server.compiles.snapshot()})
        elif cmd["cmd"] == "close":
            reply({"event": "close", **await server.close()})
        elif cmd["cmd"] == "check":
            reply({"event": "check", **await server.check(
                cmd["ids"], cmd.get("modes", []), float(cmd.get("router_tie", 0.0)),
                bool(cmd.get("per_token")))})
            return 0
        elif cmd["cmd"] == "quit":
            break
    server.stop.set()
    with contextlib.suppress(asyncio.CancelledError):
        await server.task
    return 0


def main(argv: List[str]) -> int:
    logging.getLogger("pilottai_tpu").setLevel(logging.WARNING)
    spec = json.loads(argv[1])
    return asyncio.run(serve(spec))


if __name__ == "__main__":
    sys.exit(main(sys.argv))
