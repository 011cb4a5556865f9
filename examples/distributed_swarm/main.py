"""Distributed swarm demo: one orchestrator, N worker hosts, real TCP.

    python examples/distributed_swarm/main.py [--workers 2]
                                              [--provider mock|cpu|tpu]
                                              [--kill-one]

The orchestrator runs :class:`~pilottai_tpu.serve.Serve` with a
:class:`~pilottai_tpu.distributed.ServeEndpoint` listener. Each worker is
a REAL subprocess hosting agents behind its own LLM engine
(``--provider cpu|tpu`` boots the in-tree JAX engine inside every worker
— the TPU-VM deployment story, where each host serves its agents from
its local chips). A chip belongs to ONE process: on a single host
``--provider tpu`` only works with ``--workers 1`` (a second worker
process fails or hangs reaching a chip the first one holds); several
``tpu`` workers need one host each. Tasks fan out over the wire; results,
heartbeats and load stats flow back.

``--kill-one`` SIGKILLs a worker mid-run to demonstrate the BASELINE
config #5 behavior: its in-flight tasks fail into Serve's retry path and
complete on the surviving workers.

No reference counterpart — the reference declared networking intent it
never implemented (websockets dep, ``pilott/pyproject.toml:19``).
"""

from __future__ import annotations

import argparse
import asyncio
import signal
import subprocess
import sys
import tempfile
import textwrap
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

WORKER_SCRIPT = textwrap.dedent(
    """
    import asyncio, os, sys
    PROVIDER_ENV = {provider!r}
    if PROVIDER_ENV != "tpu":  # tpu workers must keep the real backend
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, {repo!r})
    import jax
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    from pilottai_tpu.core.agent import BaseAgent
    from pilottai_tpu.core.config import AgentConfig, LLMConfig, SamplingConfig
    from pilottai_tpu.distributed import AgentWorker
    from pilottai_tpu.engine.handler import LLMHandler
    from pilottai_tpu.engine.mock import MockBackend

    PROVIDER = {provider!r}
    WORKER_IX = {ix}

    def make_llm():
        if PROVIDER == "mock":
            # A little latency so tasks overlap and routing/load stats
            # are visible in the demo.
            return LLMHandler(
                LLMConfig(provider="mock"), backend=MockBackend(latency=0.3)
            )
        return LLMHandler(LLMConfig(
            model_name="llama-tiny", provider=PROVIDER, engine_slots=4,
            engine_max_seq=256, engine_chunk=4,
            dtype="float32" if PROVIDER == "cpu" else "bfloat16",
            sampling=SamplingConfig(max_new_tokens=32, temperature=0.0),
        ))

    async def main():
        agents = [
            BaseAgent(
                config=AgentConfig(role=f"worker{{WORKER_IX}}-agent{{i}}"),
                llm=make_llm(),
            )
            for i in range(2)
        ]
        w = AgentWorker("127.0.0.1", {port}, agents, heartbeat_interval=0.5)
        await w.start()
        print(f"worker {{WORKER_IX}} up with {{len(agents)}} agents", flush=True)
        await w.run_until_stopped()

    asyncio.run(main())
    """
)


async def run(n_workers: int, provider: str, kill_one: bool) -> None:
    from pilottai_tpu.core.config import LLMConfig, ServeConfig
    from pilottai_tpu.distributed import ServeEndpoint
    from pilottai_tpu.engine.handler import LLMHandler
    from pilottai_tpu.engine.mock import MockBackend
    from pilottai_tpu.serve import Serve

    serve = Serve(
        name="swarm",
        manager_llm=LLMHandler(LLMConfig(provider="mock"), backend=MockBackend()),
        config=ServeConfig(
            decomposition_enabled=False, fault_tolerance_enabled=True,
            max_retry_attempts=3,
        ),
    )
    await serve.start()
    endpoint = ServeEndpoint(serve)
    await endpoint.start()
    print(f"orchestrator listening on 127.0.0.1:{endpoint.port}")

    repo = str(Path(__file__).resolve().parents[2])
    procs = []
    tmp = Path(tempfile.mkdtemp())
    for ix in range(n_workers):
        script = tmp / f"worker{ix}.py"
        script.write_text(WORKER_SCRIPT.format(
            repo=repo, port=endpoint.port, provider=provider, ix=ix,
        ))
        procs.append(subprocess.Popen([sys.executable, str(script)]))

    try:
        want = n_workers * 2
        deadline = time.time() + 300
        while len(serve.agents) < want and time.time() < deadline:
            await asyncio.sleep(0.2)
        print(f"registered {len(serve.agents)}/{want} remote agents")

        tasks = [
            await serve.add_task(f"analyze shard {i} of the quarterly data")
            for i in range(3 * want)
        ]
        if kill_one and procs:
            await asyncio.sleep(0.5)
            print("SIGKILLing worker 0 mid-run …")
            procs[0].send_signal(signal.SIGKILL)

        results = await asyncio.gather(
            *[serve.wait_for(t.id, timeout=300) for t in tasks]
        )
        ok = sum(r.success for r in results)
        agents_used = sorted({t.agent_id[:8] for t in tasks if t.agent_id})
        print(f"{ok}/{len(results)} tasks completed")
        print(f"executed across agents: {agents_used}")
        m = serve.get_metrics()
        print("orchestrator metrics:", {
            k: m[k] for k in ("tasks_completed", "tasks_failed", "tasks_retried")
        })
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        await endpoint.stop()
        await serve.stop()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument(
        "--provider", default="mock", choices=["mock", "cpu", "tpu"],
        help="engine inside every worker process. tpu: a chip belongs to "
             "one process, so on one host use --workers 1",
    )
    ap.add_argument("--kill-one", action="store_true")
    args = ap.parse_args()
    asyncio.run(run(args.workers, args.provider, args.kill_one))


if __name__ == "__main__":
    main()
