#!/usr/bin/env python
"""Cross-subsystem chaos soak (ISSUE 16): a seeded, randomized fault
schedule driven through the *named injection registry* against a live
serving cell — two engine replicas on ``{'model':2,'data':2}`` survivor
ladders over the virtual 8-device CPU platform.

Each round runs a fixed greedy probe wave plus a session turn, injects
ONE fault drawn from the shuffled deck (shard loss, mid-decode step
fault, prefill fault, host-RAM rot at spill/restore, migration-frame
rot, prefill→decode handoff-frame rot, a stuck-dispatch latency
blip), and the soak then asserts the
system-wide invariants the fault domain promises:

* ``recovered_frac == 1.0`` — every non-shed request completed;
* **byte-identity** — every probe wave matches the clean reference
  wave byte for byte (recovery re-prefills; it never rewrites);
* **integrity** — every injected corruption is DETECTED (counted under
  ``engine.kvcache.integrity_failures``), never served (the final
  sweep resumes every soak session so each spilled entry crosses the
  restore verifier);
* **no stuck flights** — the cell drains to zero in-flight work;
* **export completeness** — a clean post-soak migration lands every
  entry (``accepted == entries``, nothing silently dropped).

Prints one JSON summary line and exits non-zero on any violation.
Wall clock is bounded by ``--budget-s`` (rounds stop early, the
invariant sweep always runs). The schedule is a pure function of
``--seed`` — rerunning a red CI seed locally reproduces the schedule.
"""

import argparse
import json
import os
import sys
import time


def _force_virtual_devices() -> None:
    """8 virtual CPU devices, set BEFORE jax's first import (device
    topology is fixed then — same trick as tests/conftest.py). The soak
    is a CPU run by construction: it never takes a chip (a chip belongs
    to one process), and nothing it reports is a device number."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()


_force_virtual_devices()
# Runnable as `python scripts/chaos_soak.py` from a source checkout.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import asyncio  # noqa: E402
import random  # noqa: E402

MESH = {"model": 2, "data": 2}
PROBES = [
    "chaos soak probe alpha: report fleet status",
    "the quick brown fox jumps over the lazy dog",
    "chaos soak probe gamma: shard the kv pool",
]
GREEDY = {"max_new_tokens": 12, "temperature": 0.0}


def _session_prompt(i: int) -> str:
    # Long enough to clear the host tier's entry floor on its own, and
    # divergent per session (distinct lineages).
    return (
        f"Session {i:03d} memory: persona agent-{i}; "
        f"goals g{i * 7}, g{i * 11}; constraints c{i * 13}. "
        + "analyze the quarterly report and respond with JSON please. " * 3
        + f"user: step {i}?"
    )


def _build_deck(rng: random.Random):
    """One entry per fault family; shuffled per-seed. ``max_shard``
    bounds permanent degradation so the ladders stay viable."""
    from pilottai_tpu.reliability.inject import global_injector as inj

    deck = [
        ("mesh.shard_loss", lambda: inj.arm(
            "mesh.shard_loss", value=rng.randrange(4), times=1, skip=1,
        )),
        ("engine.step", lambda: inj.arm(
            "engine.step", RuntimeError("chaos soak step fault"),
            times=1, skip=1,
        )),
        ("engine.prefill", lambda: inj.arm(
            "engine.prefill", RuntimeError("chaos soak prefill fault"),
            times=1,
        )),
        ("kvcache.spill.corrupt", lambda: inj.arm(
            "kvcache.spill.corrupt", value=True, times=1,
        )),
        ("kvcache.restore.corrupt", lambda: inj.arm(
            "kvcache.restore.corrupt", value=True, times=1,
        )),
        ("cell.migrate.corrupt", lambda: inj.arm(
            "cell.migrate.corrupt", value=True, times=1,
        )),
        ("cell.handoff.corrupt", lambda: inj.arm(
            "cell.handoff.corrupt", value=True, times=1,
        )),
        ("engine.dispatch.hang", lambda: inj.arm(
            "engine.dispatch.hang", delay=0.2, times=1,
        )),
    ]
    rng.shuffle(deck)
    return deck


async def soak(seed: int, rounds: int, budget_s: float):
    from pilottai_tpu.core.config import LLMConfig
    from pilottai_tpu.distributed import ServingCell
    from pilottai_tpu.engine.handler import LLMHandler
    from pilottai_tpu.engine.types import GenerationParams
    from pilottai_tpu.reliability.inject import global_injector
    from pilottai_tpu.utils.metrics import global_metrics

    rng = random.Random(seed)
    t_start = time.monotonic()

    def cfg():
        return LLMConfig(
            model_name="llama-tiny", provider="cpu", dtype="float32",
            mesh_shape=dict(MESH),
            engine_slots=2, engine_max_seq=256, engine_chunk=8,
            engine_prefix_cache=1, engine_kvcache_host_mb=64,
        )

    # Disaggregated topology (ISSUE 19): cold long prompts route
    # through the prefill tier + KV handoff, so the handoff wire frame
    # is live in the soak and ``cell.handoff.corrupt`` has a real
    # payload to rot. Short probes go decode-direct; a corrupted or
    # unavailable handoff falls back colocated — every invariant below
    # must hold regardless of which path served a request.
    cell = ServingCell([LLMHandler(cfg()) for _ in range(2)],
                       cell_disagg="1p1d")
    await cell.start()
    global_injector.reset()
    params = GenerationParams(**GREEDY)
    results = []          # "ok" | "error" per request
    violations = []
    injections = []
    corrupt_fires = 0
    session_turns = {}    # sid -> (prompt, reply)

    async def probe_wave():
        got = await asyncio.gather(*[
            cell.apredict(p, params=params) for p in PROBES
        ], return_exceptions=True)
        for g in got:
            results.append("error" if isinstance(g, Exception) else "ok")
        return got

    async def session_turn(i):
        sid = f"cs-{i}"
        prompt = _session_prompt(i)
        try:
            reply = await cell.apredict(prompt, params=params,
                                        session_id=sid)
            session_turns[sid] = (prompt, reply)
            results.append("ok")
        except Exception:  # noqa: BLE001 — scored, not fatal
            results.append("error")

    fails0 = global_metrics.get("engine.kvcache.integrity_failures")
    losses0 = global_metrics.get("engine.shard_losses")
    handoffs0 = global_metrics.get("cell.handoffs")

    reference = await probe_wave()
    if any(isinstance(g, Exception) for g in reference):
        violations.append("clean reference wave errored")
    identical_waves = 0

    deck = _build_deck(rng)
    schedule = [deck[i % len(deck)] for i in range(rounds)]
    shard_events = 0
    done_rounds = 0
    for i, (name, arm) in enumerate(schedule):
        if time.monotonic() - t_start > budget_s * 0.8:
            break
        if name == "mesh.shard_loss":
            if shard_events >= 2:  # keep every ladder viable
                continue
            shard_events += 1
        arm()
        if name == "cell.handoff.corrupt":
            # A fresh cold long prompt forces a handoff attempt; the
            # rotted frame must be rejected by the integrity framing
            # (counted below) and the request served colocated anyway.
            prompt = (
                f"cold dossier {i}: "
                + f"shard {i} telemetry segment; " * 6
                + "summarize."
            )
            try:
                await cell.apredict(prompt, params=params)
                results.append("ok")
            except Exception:  # noqa: BLE001 — scored, not fatal
                results.append("error")
        if name == "cell.migrate.corrupt" and session_turns:
            sid = rng.choice(sorted(session_turns))
            try:
                report = await cell.migrate_session(sid)
                if report["accepted"] != 0 or (
                    report["entries"] and not report["rejected"]
                ):
                    violations.append(
                        f"round {i}: corrupt migration landed KV "
                        f"({report})"
                    )
            except Exception as exc:  # noqa: BLE001 — scored
                violations.append(f"round {i}: migrate raised {exc!r}")
        wave = await probe_wave()
        await session_turn(i)
        fired = global_injector.fired(name)
        injections.append({"round": i, "fault": name, "fired": fired})
        if name.endswith(".corrupt"):
            corrupt_fires += fired
        if all(
            not isinstance(g, Exception) and g == r
            for g, r in zip(wave, reference)
        ):
            identical_waves += 1
        else:
            violations.append(f"round {i} ({name}): probe wave diverged")
        global_injector.reset()
        done_rounds += 1

    # Invariant sweep 1: resume EVERY soak session so each spilled
    # entry crosses the restore verifier — a rotted one must be
    # detected (counted + dropped) and re-prefill byte-consistently.
    for sid, (prompt, reply) in sorted(session_turns.items()):
        try:
            await cell.apredict(
                prompt + reply + " user: and then?", params=params,
                session_id=sid,
            )
            results.append("ok")
        except Exception:  # noqa: BLE001 — scored
            results.append("error")

    # Invariant sweep 2: a clean migration must land every entry.
    export_complete = None
    if session_turns:
        sid = sorted(session_turns)[-1]
        try:
            report = await cell.migrate_session(sid)
            export_complete = (
                report["rejected"] == 0
                and report["accepted"] == report["entries"]
            )
            if not export_complete:
                violations.append(
                    f"post-soak migration incomplete: {report}"
                )
        except Exception as exc:  # noqa: BLE001 — scored
            export_complete = False
            violations.append(f"post-soak migration raised {exc!r}")

    # Invariant: the cell drains — no stuck flights anywhere.
    deadline = time.monotonic() + 60
    def inflight():
        return sum(r.inflight for r in cell.replicas.values())
    while inflight() and time.monotonic() < deadline:
        await asyncio.sleep(0.05)
    stuck = inflight()
    queued = sum(
        s.queue_depth for s in cell.signals()
    )
    if stuck or queued:
        violations.append(
            f"stuck flights after drain: inflight={stuck} queued={queued}"
        )

    detected = (
        global_metrics.get("engine.kvcache.integrity_failures") - fails0
    )
    if detected < corrupt_fires:
        violations.append(
            f"integrity: {corrupt_fires} corruption(s) injected, only "
            f"{detected} detected"
        )
    errors = results.count("error")
    recovered_frac = (
        round(results.count("ok") / len(results), 4) if results else 0.0
    )
    if recovered_frac < 1.0:
        violations.append(f"{errors} request(s) died (of {len(results)})")

    mesh_rungs = sorted(
        int(s.mesh_rung) for s in cell.signals()
    )
    await cell.stop()
    return {
        "seed": seed,
        "rounds": done_rounds,
        "rounds_requested": rounds,
        "requests": len(results),
        "recovered_frac": recovered_frac,
        "client_errors": errors,
        "identical_waves": identical_waves,
        "waves_injected": done_rounds,
        "byte_identity_ok": identical_waves == done_rounds,
        "shard_losses": int(
            global_metrics.get("engine.shard_losses") - losses0
        ),
        "mesh_rungs": mesh_rungs,
        "corruptions_injected": corrupt_fires,
        "corruptions_detected": int(detected),
        "handoffs": int(global_metrics.get("cell.handoffs") - handoffs0),
        "handoff_fallbacks": int(
            global_metrics.get("cell.handoff_fallbacks")
        ),
        "stuck_flights": int(stuck),
        "export_completeness": export_complete,
        "injections": injections,
        "wall_s": round(time.monotonic() - t_start, 1),
        "violations": violations,
        "ok": not violations,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=7)
    ap.add_argument("--budget-s", type=float, default=600.0,
                    help="wall-clock bound; rounds stop early past 80%%")
    args = ap.parse_args(argv)
    summary = asyncio.run(soak(args.seed, args.rounds, args.budget_s))
    print(json.dumps(summary))
    if not summary["ok"]:
        print("CHAOS SOAK VIOLATIONS:", file=sys.stderr)
        for v in summary["violations"]:
            print(f"  - {v}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
