"""Headline benchmark: agent-steps/sec/chip through the in-tree engine,
plus orchestrator-level numbers through ``Serve`` itself.

An "agent step" is one LLM call inside the agent's plan/act/evaluate loop
(SURVEY.md §3.4: a simple task is ≥4 such calls; the reference pays a
remote HTTPS round-trip per step, ``pilott/engine/llm.py:59``). Here the
same step runs on local devices through the continuous batcher.

Five sections on accelerator:

* ``llama3-1b-byte`` — 32-way concurrency throughput section;
* ``llama3-8b-byte`` — the BASELINE.md north-star model, int8
  weight-only + speculative decoding (D=6 verify blocks, early-exit
  chunks), 8-way, over COLD prompts (every request's task suffix is
  unique — prefix caching may share the page-aligned/LCP preamble, the
  way real agent traffic shares the rules preamble, but no request is
  an exact repeat); its p50 vs the ≤500 ms target is the headline
  (``vs_baseline`` = 500 / p50_8b);
* ``llama3-8b-byte @ 4K paged`` — the long-context serving path: paged
  KV + int8 KV cache + speculation + block-granular prefix caching
  composed (round 3 silently lost all three under paging);
* ``pipeline`` — BASELINE config #3 end-to-end: Serve + manager + 3
  specialist workers running the document pipeline on the real 1B
  engine, task-completion p50 *through* ``Serve.execute``;
* ``swarm`` — BASELINE config #4: 32 agents on one Serve sharing the
  1B engine, agent LLM steps/s through the orchestrator.

Host-side wall clock is noisy (the load generator, the server's host
code and the tracer share one machine's cores); a single epoch can land
in a bad window and misreport the engine. Engine sections therefore run
several epochs and report the best one (peak sustained throughput) PLUS
the median epoch and every epoch's rate, so the flattering statistic
never stands alone.

State of this file (PR 21): it has NOT run on the chip in this round and
is not the round's benchmark — ROADMAP Queue 1 item 1 replaces it with a
table of workloads. The "multichip" and "chaos" sections run as CPU
child processes (this process holds the chip, and a chip belongs to one
process): what they report is never a device number. A section that
fails is noted on stderr and the process exits non-zero.

Prints ONE JSON line.
"""

import asyncio
import gc
import json
import os
import statistics
import sys
import time

# The persistent compilation cache is placed by utils/compile_cache.py:
# JAX_COMPILATION_CACHE_DIR where set, else <checkout>/.jax_cache.

import jax

MAX_NEW_TOKENS = 48    # JSON-ish agent-step reply length
TARGET_P50_MS = 500.0  # BASELINE.md north star for llama3-8b

PREAMBLE = (
    "Analyze the task and respond with JSON: "
    '{"requires_decomposition": false, "complexity": 3, '
    '"estimated_resources": {"agents": 1}}. Task: '
)


def _prompt(uid: int, pad_to: int = 0) -> str:
    """Agent-step prompt with a UNIQUE task suffix (cold request). The
    shared preamble mirrors real traffic (rules.yaml is byte-identical
    across calls); ``pad_to`` repeats it to reach long-context sizes."""
    pre = PREAMBLE
    while pad_to and len(pre) < pad_to:
        pre += PREAMBLE
    return pre + f"summarize document {uid} for the executive team"


async def bench_model(cfg, concurrency, steps, epochs, n_chips=1,
                      pad_to=0):
    """Run one engine section; returns the result dict."""
    from pilottai_tpu.engine.handler import LLMHandler
    from pilottai_tpu.engine.types import GenerationParams
    from pilottai_tpu.obs import peak_flops_per_chip

    handler = LLMHandler(cfg)
    on_accel = cfg.provider != "cpu"
    peak_flops = peak_flops_per_chip(
        jax.devices()[0].device_kind if on_accel else "cpu"
    )
    # Section-pure phase percentiles: drop the previous section's
    # request-phase samples so the `phases` block below describes ONLY
    # this section's traffic (counts and windows included).
    from pilottai_tpu.utils.metrics import global_metrics as _gm

    _gm.reset_histograms("request.")
    _gm.reset_histograms("engine.prefill_latency")
    # Host-gap histogram is section-pure too: each section's
    # host_gap_p50_ms must describe ONLY its own dispatches, or a slow
    # warmup section poisons every later section's number.
    _gm.reset_histograms("engine.host_gap_ms")
    params = GenerationParams(max_new_tokens=MAX_NEW_TOKENS, temperature=0.0)
    uid = [0]

    async def one_step():
        uid[0] += 1
        return await handler.apredict(
            _prompt(uid[0], pad_to), params=params
        )

    # Warmup: two full waves — the first compiles prefill buckets +
    # decode, the second the PREFIX-HIT admission variants and settles
    # the speculative acceptance EMA (with only one wave those compiles
    # land inside timed epoch 1 and drag the reported median).
    for _ in range(2):
        await asyncio.gather(*[one_step() for _ in range(concurrency)])

    # Chunk-utilization counters are cumulative — snapshot AFTER the
    # warmup waves (the engine's lazy boot runs its bucket compile
    # sweep inside the first one, and those 2-token probes would bias
    # the section's useful/dispatched ratio far below steady state).
    blocks0 = (
        _gm.get("engine.blocks_dispatched"),
        _gm.get("engine.blocks_useful"),
        _gm.get("engine.chunk_folds"),
    )
    # Attribution counters for the section's LIVE MFU: prefill tokens +
    # ACCEPTED decode tokens (folded validity — obs/attribution.py feeds
    # both), achieved FLOPs via ModelConfig.flops_per_token(). Same
    # formula as the live engine.mfu gauge, measured as a delta over the
    # timed epochs. (The old number used decode tokens only with an
    # inline 2*n_params guess — prefill and speculative acceptance were
    # invisible to it.)
    attr0 = (
        _gm.get("engine.prefill_tokens"),
        _gm.get("engine.generated_tokens_device"),
        _gm.get("engine.achieved_flops"),
    )
    t_meas0 = time.perf_counter()

    async def epoch():
        latencies = []
        done = 0
        t0 = time.perf_counter()

        async def worker():
            nonlocal done
            while done < steps:
                done += 1
                s = time.perf_counter()
                await one_step()
                latencies.append(time.perf_counter() - s)

        await asyncio.gather(*[worker() for _ in range(concurrency)])
        return latencies, time.perf_counter() - t0

    runs = [await epoch() for _ in range(epochs)]
    wall_meas = time.perf_counter() - t_meas0
    attr1 = (
        _gm.get("engine.prefill_tokens"),
        _gm.get("engine.generated_tokens_device"),
        _gm.get("engine.achieved_flops"),
    )

    # The device's own account: a STEADY-STATE window under
    # jax.profiler — the device's own busy time per step can't be
    # confused with host noise. One un-traced settle wave first (so
    # first-wave admission, compile stragglers and the acceptance EMA
    # never pollute the trace — r5's single isolated wave reported an
    # internally impossible 104.9 device-only vs 146.3 wall), then the
    # trace starts mid-epoch and spans ≥3 consecutive waves.
    # steps_per_sec_device_only is what co-located hardware would
    # sustain if the device were the only bottleneck; busy_frac shows
    # how much of the window the device sat idle waiting for the host.
    PROFILE_WAVES = 3
    device = None
    if cfg.provider != "cpu":
        from pilottai_tpu.utils.device_profile import DeviceWindow

        try:
            await asyncio.gather(  # settle wave — excluded from trace
                *[one_step() for _ in range(concurrency)]
            )
            flops_w0 = _gm.get("engine.achieved_flops")
            win = DeviceWindow().start()
            t0 = time.perf_counter()
            try:
                for _ in range(PROFILE_WAVES):
                    await asyncio.gather(
                        *[one_step() for _ in range(concurrency)]
                    )
            finally:
                # The profiler trace is process-global: leaving it
                # running after a failed wave breaks every later
                # section's profiling.
                window_wall = time.perf_counter() - t0
                prof = win.stop()
            profiled = PROFILE_WAVES * concurrency
            flops_w = _gm.get("engine.achieved_flops") - flops_w0
            if prof["device_busy_s"] > 0:
                device = {
                    "device_ms_per_step": round(
                        prof["device_busy_s"] * 1000.0 / profiled, 2
                    ),
                    "steps_per_sec_device_only": round(
                        profiled / prof["device_busy_s"] / n_chips, 3
                    ),
                    "device_busy_frac": round(prof["busy_frac"], 3),
                    "profiled_steps": profiled,
                    "profiled_waves": PROFILE_WAVES,
                    "profiled_window_steps_per_sec": round(
                        profiled / window_wall / n_chips, 3
                    ),
                    # MFU over the PROFILER-measured window: achieved
                    # FLOPs (attribution counters) over the profiled
                    # wall, and over the device's own busy time — the
                    # reconciliation pair for the section-level live
                    # `mfu` below (slow-marker test pins the same pair
                    # on the CPU engine; tests/test_attribution.py).
                    "mfu_profiled_window": round(
                        flops_w / (window_wall * peak_flops * n_chips), 4
                    ),
                    "mfu_device_busy": round(
                        flops_w
                        / (prof["device_busy_s"] * peak_flops * n_chips),
                        4,
                    ),
                }
        except Exception as exc:  # noqa: BLE001 — profiling is best-effort
            _note("device profile FAILED", {"error": str(exc)})

    # Per-phase breakdown (queue wait / prefill / TTFT / TPOT / ITL
    # percentiles) from the flight-recorder histograms, captured while
    # this section's samples are still the recent window — future perf
    # PRs get a phase-attributed trajectory, not just aggregate rates.
    from pilottai_tpu.obs import phase_summary

    phases = phase_summary()
    blocks_disp = _gm.get("engine.blocks_dispatched") - blocks0[0]
    blocks_used = _gm.get("engine.blocks_useful") - blocks0[1]
    n_folds = _gm.get("engine.chunk_folds") - blocks0[2]
    # Host-gap percentiles for THIS section (histogram reset above):
    # the device-idle bubble between fold-complete and next dispatch.
    # p50 ≈ 0 means the overlapped pipeline kept the device fed; a
    # regression here is attributable before device_busy_frac moves.
    gap = _gm.snapshot()["histograms"].get("engine.host_gap_ms") or {}
    host_gap_p50 = gap.get("p50")
    host_gap_p90 = gap.get("p90")

    await handler.stop()
    del handler
    gc.collect()

    epoch_rates = [round(len(l) / w / n_chips, 3) for l, w in runs]
    latencies, wall = max(runs, key=lambda e: len(e[0]) / e[1])
    steps_per_sec = len(latencies) / wall / n_chips
    p50_ms = statistics.median(latencies) * 1000.0

    # LIVE section MFU: achieved-FLOPs delta over the timed epochs
    # (prefill tokens + accepted speculative/decode tokens from folded
    # validity x ModelConfig.flops_per_token() — exactly the live
    # engine.mfu gauge's accounting, measured per chip over the
    # measurement wall).
    prefill_toks = attr1[0] - attr0[0]
    accepted_toks = attr1[1] - attr0[1]
    flops_meas = attr1[2] - attr0[2]
    mfu_live = (
        flops_meas / (wall_meas * peak_flops * n_chips)
        if wall_meas > 0 else 0.0
    )

    # Internal-consistency check BEFORE the number is emitted (VERDICT
    # r5 next-step 2): (a) the device can't be slower than the wall that
    # includes transport — steps_per_sec_device_only ≥ the wall rate;
    # (b) busy_frac × device-only rate must reproduce the profiled
    # window's own wall rate within tolerance (they are the same window
    # measured two ways). A violation means the profiled window was not
    # steady-state — the r5 failure mode this check exists to catch.
    if device is not None:
        dev_rate = device["steps_per_sec_device_only"]
        window_rate = device["profiled_window_steps_per_sec"]
        product = device["device_busy_frac"] * dev_rate
        rel_err = abs(product - window_rate) / max(window_rate, 1e-9)
        # Live-vs-profiler MFU reconciliation (acceptance bar: within
        # 15% on the 1B dense section): the section's live MFU against
        # the same accounting over the profiler-measured window. Drift
        # here means the attribution counters disagree with the
        # profiler's clock — the silent-drift failure the slow-marker
        # test (tests/test_attribution.py) pins on CPU.
        mfu_rel_err = (
            abs(device["mfu_profiled_window"] - mfu_live)
            / max(mfu_live, 1e-9)
        )
        device["device_consistency"] = {
            "device_only_ge_wall": bool(dev_rate >= steps_per_sec * 0.98),
            "busy_x_device_vs_window_rel_err": round(rel_err, 3),
            "mfu_live_vs_profiled_rel_err": round(mfu_rel_err, 3),
            "mfu_ok": bool(mfu_rel_err <= 0.15),
            "ok": bool(dev_rate >= steps_per_sec * 0.98 and rel_err <= 0.25),
        }
        _note(f"device consistency [{cfg.model_name}]", {
            "steps_per_sec_device_only": dev_rate,
            "steps_per_sec_per_chip": round(steps_per_sec, 3),
            "busy_frac_x_device_only": round(product, 3),
            "profiled_window_steps_per_sec": window_rate,
            "mfu_live": round(mfu_live, 4),
            "mfu_profiled_window": device["mfu_profiled_window"],
            **device["device_consistency"],
        })
    decode_tok_s = len(latencies) * MAX_NEW_TOKENS / wall / n_chips
    return {
        "model": cfg.model_name,
        "steps_per_sec_per_chip": round(steps_per_sec, 3),
        "median_epoch_steps_per_sec": round(
            statistics.median(epoch_rates), 3
        ),
        "p50_step_ms": round(p50_ms, 1),
        "decode_tokens_per_sec_per_chip": round(decode_tok_s, 1),
        # Live MFU (see attr0/attr1 above): prefill + accepted tokens,
        # ModelConfig.flops_per_token(), per chip, over the measurement
        # wall — the same formula as the live engine.mfu gauge.
        "mfu": round(mfu_live, 4),
        "mfu_prefill_tokens": int(prefill_toks),
        "mfu_accepted_tokens": int(accepted_toks),
        "concurrency": concurrency,
        "steps": len(latencies),
        "speculate": cfg.engine_speculate,
        "quantize": cfg.quantize,
        "paged": bool(cfg.engine_paged_kv),
        "kv_quantize": cfg.engine_kv_quantize,
        "epoch_steps_per_sec": epoch_rates,
        # Section-pure: the request-phase histograms were reset at this
        # section's start, so counts and percentiles cover only it.
        "phases": phases,
        # Adaptive-chunk scheduling outcome for this section: useful
        # decode blocks ÷ dispatched blocks, and the mean per-dispatch
        # chunk size the policy actually picked.
        "chunk_policy": cfg.engine_chunk_policy,
        "chunk_utilization": (
            round(blocks_used / blocks_disp, 4) if blocks_disp else None
        ),
        "chunk_blocks_dispatched": int(blocks_disp),
        "chunk_blocks_mean": (
            round(blocks_disp / n_folds, 2) if n_folds else None
        ),
        # Device-feed health: host-side gap percentiles (ms) and the
        # profiled busy fraction. device_busy_frac is None on CPU runs
        # (no device profile); the device dict overrides it on accel.
        "host_gap_p50_ms": (
            round(host_gap_p50, 3) if host_gap_p50 is not None else None
        ),
        "host_gap_p90_ms": (
            round(host_gap_p90, 3) if host_gap_p90 is not None else None
        ),
        "device_busy_frac": None,
        **(device or {}),
    }


async def bench_slo(cfg, rate_rps, duration_s=30.0, n_chips=1, seed=7,
                    burst_factor=2.0, derate=False):
    """Open-loop SLO section (ROADMAP item 5): Poisson arrivals at
    ``rate_rps`` over a multi-tenant mix with a 2x burst through the
    middle fifth of the run. The mix carries the three first-class
    workload shapes the cost model covers (ISSUE 18) next to plain
    chat: **multi-turn sessions** (a persistent ``session_id`` whose
    transcript grows turn over turn — the PR 9 kvcache tier's prefix
    path), **long-context RAG** (a fat padded context ahead of a short
    question, batch class) and **schema-constrained tool loops** (two
    chained grammar-constrained calls per arrival). Open-loop means
    arrivals do NOT wait for completions (closed-loop fixed concurrency
    self-throttles and can never show queueing collapse); the headline
    is per-class SLO attainment and p99s from obs/slo.py, not
    throughput.
    """
    import random as _random

    from pilottai_tpu.engine.handler import LLMHandler
    from pilottai_tpu.engine.types import GenerationParams
    from pilottai_tpu.obs import global_slo
    from pilottai_tpu.reliability import EngineOverloaded
    from pilottai_tpu.utils.metrics import global_metrics as _gm

    TOOL_SCHEMA = {
        "type": "object",
        "properties": {
            "action": {"type": "string"},
            "count": {"type": "integer"},
        },
        "required": ["action", "count"],
    }
    # (name, weight, slo_class, max_new_tokens, pad_to, json_schema)
    # Tenant behavior beyond the tuple (session transcripts, tool-loop
    # chaining) keys off the name in one().
    tenants = [
        ("chat", 0.35, "interactive", 32, 0, None),
        ("sessions", 0.25, "interactive", 24, 0, None),
        ("rag", 0.2, "batch", 48, 1200, None),
        ("toolloop", 0.2, "interactive", 24, 0, TOOL_SCHEMA),
    ]
    handler = LLMHandler(cfg)
    rng = _random.Random(seed)
    uid = [0]
    # Multi-turn session state: a small pool of persistent sessions
    # whose transcripts grow — successive turns share an ever-longer
    # prefix under one session_id, the exact shape the kvcache tier
    # (and its knobs) exist for.
    n_session_pool = 6
    session_log: dict = {}

    async def one(tenant, warm=False):
        name, _, slo_class, max_new, pad_to, schema = tenant
        uid[0] += 1
        # Per-request RNG keyed by arrival index: in-task draws must not
        # interleave with the arrival loop's shared rng, or two runs of
        # the same seed would see different workloads (the AUTOCONF
        # section compares knob vectors on the SAME recorded workload).
        req_rng = _random.Random((seed << 20) ^ uid[0])
        params = GenerationParams(
            max_new_tokens=max_new, temperature=0.0,
            slo_class=slo_class, json_schema=schema,
            json_mode=schema is not None,
        )
        try:
            if name == "sessions":
                sid = f"slo-sess-{req_rng.randrange(n_session_pool)}"
                log = session_log.setdefault(sid, [])
                log.append(f"turn {len(log)}: question {uid[0]}")
                if len(log) > 8:  # bound transcript growth
                    del log[:-8]
                params = params.model_copy(update={"session_id": sid})
                await handler.apredict("\n".join(log), params=params)
            elif name == "toolloop":
                # Tool loop: two chained schema-constrained calls — the
                # second consumes the first's (fixed-shape) output, the
                # agentic pattern the scheduler sees as a short chain.
                out = await handler.apredict(
                    _prompt(uid[0], pad_to), params=params
                )
                await handler.apredict(
                    f"given {str(out)[:120]}, next call {uid[0]}",
                    params=params,
                )
            else:
                await handler.apredict(
                    _prompt(uid[0], pad_to), params=params
                )
            return "ok"
        except EngineOverloaded:
            return "shed"
        except Exception as exc:  # noqa: BLE001 — harness keeps running
            if not warm:
                _note("slo request FAILED", {"tenant": name,
                                             "error": str(exc)[:200]})
            return "error"

    # Warm every tenant shape (prefill buckets + schema DFA + the
    # acceptance EMA) so compiles never land inside the measured run.
    for tenant in tenants:
        await asyncio.gather(*[one(tenant, warm=True) for _ in range(2)])

    names = [t[0] for t in tenants]
    weights = [t[1] for t in tenants]
    # Headline honesty (ISSUE 19 satellite): BENCH_r07 printed
    # slo_attainment_interactive 0.0 because the offered rate was set
    # from the agent-step rate, which overstates what one engine absorbs
    # on this heavier mix (every arrival decodes 24-48 tokens; RAG pads
    # 1200 chars) — the section measured unbounded queue growth, not SLO
    # behavior. With ``derate`` on, a short closed-loop burst over the
    # same weighted mix measures the mix's own capacity and the offered
    # rate clamps to 80% of it (the requested rate is still reported as
    # ``target_rps``). Off by default: AUTOCONF replays this harness per
    # knob candidate and must offer every candidate the SAME load.
    measured_capacity_rps = None
    effective_rate = rate_rps
    if derate:
        calib_rng = _random.Random(seed ^ 0x5CA1AB1E)
        calib_t0 = time.perf_counter()
        calib_reqs = 0
        for _ in range(3):
            wave = calib_rng.choices(tenants, weights=weights, k=4)
            await asyncio.gather(*[one(t, warm=True) for t in wave])
            calib_reqs += len(wave)
        calib_wall = max(time.perf_counter() - calib_t0, 1e-6)
        measured_capacity_rps = round(calib_reqs / calib_wall, 2)
        effective_rate = min(
            rate_rps, max(round(0.8 * measured_capacity_rps, 2), 0.5)
        )

    # Section-pure SLO windows: the warmup's compile-wall misses must
    # not burn this section's budget. requests/missed are cumulative
    # process counters (earlier bench sections feed the same global
    # tracker), so the section reports DELTAS from here.
    global_slo.reset()
    _gm.reset_histograms("request.")
    count0 = {
        cls: (_gm.get(f"slo.{cls}.requests"), _gm.get(f"slo.{cls}.missed"))
        for cls in global_slo.classes
    }

    t_start = time.perf_counter()
    burst_lo = t_start + 0.4 * duration_s
    burst_hi = t_start + 0.6 * duration_s
    inflight: list = []
    offered = {n: 0 for n in names}
    while True:
        now = time.perf_counter()
        if now >= t_start + duration_s:
            break
        rate = effective_rate * (
            burst_factor if burst_lo <= now < burst_hi else 1.0
        )
        await asyncio.sleep(rng.expovariate(max(rate, 1e-3)))
        tenant = rng.choices(tenants, weights=weights, k=1)[0]
        offered[tenant[0]] += 1
        inflight.append(asyncio.create_task(one(tenant)))
    # Offered load is defined by the ARRIVAL window — stamp it before
    # draining in-flight work, or saturation (queued requests completing
    # long after arrivals stop) would dilute offered_rps exactly when
    # the open-loop harness is demonstrating queueing collapse.
    arrival_wall = time.perf_counter() - t_start
    outcomes = await asyncio.gather(*inflight)
    drain_wall = time.perf_counter() - t_start - arrival_wall
    snap = global_slo.snapshot()
    await handler.stop()
    gc.collect()

    per_class = {}
    for cls, entry in snap.items():
        req0, miss0 = count0.get(cls, (0.0, 0.0))
        requests = entry["requests"] - req0
        if not requests:
            continue
        per_class[cls] = {
            "ttft_p99_s": entry["ttft_p99_s"],
            "tpot_p99_s": entry["tpot_p99_s"],
            "e2e_p99_s": entry["e2e_p99_s"],
            "attainment": entry["attainment"],
            "burn_rate": entry["burn_rate"],
            "requests": int(requests),
            "missed": int(entry["missed"] - miss0),
            "targets": entry["targets"],
        }
    completed = outcomes.count("ok")
    offered_rps = sum(offered.values()) / arrival_wall
    # Saturation stamp: if completions couldn't keep pace with arrivals
    # (or the post-arrival drain dwarfs the run), the percentiles above
    # describe queueing collapse and the attainment headline must be
    # read with that caveat.
    saturated = bool(
        completed / arrival_wall < 0.8 * offered_rps
        or drain_wall > 0.5 * arrival_wall
    )
    return {
        "offered_rps": round(offered_rps, 2),
        "target_rps": rate_rps,
        "derated_rps": effective_rate if derate else None,
        "measured_capacity_rps": measured_capacity_rps,
        "saturated": saturated,
        "burst_factor": burst_factor,
        "duration_s": round(arrival_wall, 1),
        "drain_s": round(drain_wall, 1),
        "offered": offered,
        "completed": completed,
        "shed": outcomes.count("shed"),
        "errors": outcomes.count("error"),
        "classes": per_class,
        "model": cfg.model_name,
        "n_chips": n_chips,
    }


async def bench_autoconf(model_name, common, rate_rps, duration_s=10.0,
                         n_chips=1, seed=11):
    """AUTOCONF section (ISSUE 18): close the measurement→configuration
    loop end to end, twice over.

    **Knob half** — run the (widened) ``bench_slo`` workload under a
    small candidate-knob sweep with the SAME seed (same recorded
    arrival trace), capture the workload profiler's fingerprint during
    the default run, fit the cost model over the per-class sample
    points and ask it for a recommendation weighted by the measured
    class mix. The recommended and default sub-blocks are the measured
    runs for those two knob vectors — recommended must meet or beat
    default on the workload it was fitted to.

    **Forecast half** — a scripted burst trace (recurring 5× burst in a
    short synthetic 'day') replayed through ``ArrivalForecast`` with an
    injected clock, driving a real ``DynamicScaling`` over a simulated
    agent pool: with ``forecast_enabled`` capacity must move BEFORE the
    interactive burn rate crosses 1.0; with it off the scaler only
    reacts after. Pure simulation — no engine, so the result isolates
    the predictive term rather than CPU-bound decode noise.
    """
    from pilottai_tpu.core.config import (
        LLMConfig,
        ReliabilityConfig,
        ScalingConfig,
    )
    from pilottai_tpu.obs import global_profile
    from pilottai_tpu.obs.costmodel import CostModel
    from pilottai_tpu.obs.forecast import ArrivalForecast
    from pilottai_tpu.orchestration.scaling import DynamicScaling
    from pilottai_tpu.utils.compile_cache import load_profile, store_profile
    from pilottai_tpu.utils.metrics import MetricsRegistry

    # ------------------------------------------------------------------ #
    # Knob half: candidate sweep → samples + fingerprint → recommend.
    # ------------------------------------------------------------------ #
    # "default" is LLMConfig's field defaults for the modeled knobs (the
    # do-nothing config scripts/recommend.py diffs against); the other
    # two bracket it (more batching + a host KV tier vs a lean/small
    # vector) so the model has a real choice on both score axes.
    candidates = {
        "default": dict(engine_slots=8, engine_chunk=16, engine_speculate=0,
                        engine_prefix_cache=4, engine_kvcache_host_mb=0),
        "batchy": dict(engine_slots=16, engine_chunk=24, engine_speculate=0,
                       engine_prefix_cache=4, engine_kvcache_host_mb=64),
        "lean": dict(engine_slots=4, engine_chunk=8, engine_speculate=0,
                     engine_prefix_cache=2, engine_kvcache_host_mb=0),
    }
    runs = {}
    samples = []
    fingerprint = None
    for name, knobs in candidates.items():
        if name == "default":
            # Fingerprint the DEFAULT run: the profile describes the
            # workload as the un-tuned deployment sees it.
            global_profile.reset()
        run = await bench_slo(
            LLMConfig(
                model_name=model_name,
                reliability=ReliabilityConfig(max_queue_depth=256),
                **knobs, **common,
            ),
            rate_rps=rate_rps, duration_s=duration_s,
            n_chips=n_chips, seed=seed,
        )
        if name == "default":
            fingerprint = global_profile.fingerprint()
        steps_per_s = round(
            run["completed"] / max(run["duration_s"], 1e-9), 3
        )
        for cls, entry in (run.get("classes") or {}).items():
            samples.append({
                "knobs": knobs,
                "workload": cls,
                "metrics": {
                    "attainment": entry["attainment"],
                    "ttft_p99_s": entry["ttft_p99_s"],
                    "tpot_p99_s": entry["tpot_p99_s"],
                    "burn_rate": entry["burn_rate"],
                    "steps_per_s": steps_per_s,
                },
            })
        runs[name] = {
            "knobs": knobs,
            "steps_per_s": steps_per_s,
            "completed": run["completed"],
            "shed": run["shed"],
            "errors": run["errors"],
            "classes": run["classes"],
        }

    model = CostModel(samples=samples)
    rec = model.recommend(
        profile=fingerprint, default_knobs=candidates["default"]
    )
    rec_name = next(
        (n for n, k in candidates.items() if k == rec["knobs"]), None
    )
    # Persist fingerprint + recommendation into the profile store (next
    # to autotune.json) — the engine's boot check and recommend.py
    # --deployment both read from here.
    try:
        blob = load_profile(model_name) or {}
        blob["fingerprint"] = fingerprint
        blob["recommendation"] = {
            "knobs": rec["knobs"], "score": rec["score"],
            "predicted": rec["predicted"],
        }
        store_profile(model_name, blob)
    except Exception:  # noqa: BLE001 — the store is best-effort
        pass

    # ------------------------------------------------------------------ #
    # Forecast half: scripted recurring burst, forecast on vs off.
    # ------------------------------------------------------------------ #
    BUCKET_S, N_PHASES = 20.0, 30
    BASE_RPS, BURST_RPS = 4.0, 20.0
    BURST_PHASES = (18, 19, 20, 21)
    CAP_RPS_PER_AGENT = 4.0

    def _trace_rps(phase):
        return BURST_RPS if phase in BURST_PHASES else BASE_RPS

    async def _burst_sim(forecast_on):
        sim_now = [0.0]
        fc = ArrivalForecast(
            bucket_s=BUCKET_S, period_s=BUCKET_S * N_PHASES,
            alpha=0.5, gamma=0.5, clock=lambda: sim_now[0],
        )
        # Two synthetic 'days' of history teach the seasonal curve the
        # recurring burst; level settles at ~1.
        for b in range(2 * N_PHASES):
            sim_now[0] = b * BUCKET_S
            fc.ingest_bucket(
                int(_trace_rps(b % N_PHASES) * BUCKET_S), at=sim_now[0]
            )

        class _SimAgent:
            def __init__(self, util):
                self.queue_utilization = util
                self.current_tasks = []
                self.success_rate = 1.0
                self.status = "busy"  # never IDLE: sim never drains

                class _Q:
                    @staticmethod
                    def qsize():
                        return 1

                self.task_queue = _Q()

        class _SimOrch:
            def __init__(self, n):
                self.agents = {f"a{i}": object() for i in range(n)}
                self.task_queue = []
                self.running_tasks = {}
                self.config = type(
                    "C", (), {"max_queue_size": 100,
                              "max_concurrent_tasks": 16},
                )()
                self.util = 0.0

            def agent_list(self):
                return [_SimAgent(self.util) for _ in self.agents]

            async def create_agent(self, agent_type):
                aid = f"a{len(self.agents)}"
                self.agents[aid] = object()
                return type("A", (), {"id": aid})()

            async def remove_agent(self, aid):
                self.agents.pop(aid, None)

        orch = _SimOrch(2)
        reg = MetricsRegistry()
        scaler = DynamicScaling(
            orch,
            ScalingConfig(
                min_agents=2, max_agents=10, cooldown=0.0,
                forecast_enabled=forecast_on,
                # 3 buckets of lead: the scaler sees the learned burst
                # while the trace is still at base rate. Cap 4 ≈ the
                # burst/base ratio (the boost a 5x recurring burst
                # actually warrants) so the pre-scale can finish before
                # the burst instead of stalling one agent short.
                forecast_lead_s=3 * BUCKET_S,
                forecast_boost_cap=4.0,
            ),
            registry=reg, forecast=fc,
        )
        backlog = 0.0
        first_up = None
        burn_cross = None
        agents_at_burst = None
        peak_burn = 0.0
        # Day 3: tick per bucket. Demand beyond pool capacity queues;
        # queued interactive work past one tick is an SLO miss, and the
        # miss fraction over the 1% budget is the burn rate.
        for b in range(2 * N_PHASES, 3 * N_PHASES):
            phase = b % N_PHASES
            sim_now[0] = b * BUCKET_S
            if phase == BURST_PHASES[0] and agents_at_burst is None:
                agents_at_burst = len(orch.agents)
            demand = _trace_rps(phase) * BUCKET_S
            fc.observe(at=sim_now[0], n=int(demand))
            capacity = len(orch.agents) * CAP_RPS_PER_AGENT * BUCKET_S
            served = min(backlog + demand, capacity)
            backlog = backlog + demand - served
            miss_frac = backlog / max(demand, 1.0)
            burn = min(miss_frac / 0.01, 50.0)
            peak_burn = max(peak_burn, burn)
            reg.set_gauge("slo.interactive.burn_rate", burn)
            orch.util = min((backlog + demand) / max(capacity, 1.0), 1.0)
            decision = await scaler.scale_once()
            if decision == "up" and first_up is None:
                first_up = phase
            if burn > 1.0 and burn_cross is None:
                burn_cross = phase
        return {
            "forecast_enabled": forecast_on,
            "first_scale_up_phase": first_up,
            "burn_exceeds_1_phase": burn_cross,
            "burst_start_phase": BURST_PHASES[0],
            "scaled_before_burn": (
                first_up is not None
                and (burn_cross is None or first_up < burn_cross)
            ),
            "agents_at_burst_start": agents_at_burst,
            "peak_burn": round(peak_burn, 2),
            "final_agents": len(orch.agents),
            "forecast_lead_s": 3 * BUCKET_S,
            "bucket_s": BUCKET_S,
        }

    fc_on = await _burst_sim(True)
    fc_off = await _burst_sim(False)
    # Measured lead: how many seconds before the burst the forecast-on
    # run moved capacity (None if it never scaled).
    lead = (
        (fc_on["burst_start_phase"] - fc_on["first_scale_up_phase"])
        * BUCKET_S
        if fc_on["first_scale_up_phase"] is not None else None
    )

    return {
        "workload": {
            "rate_rps": rate_rps, "duration_s": duration_s, "seed": seed,
            "model": model_name, "n_chips": n_chips,
            "tenants": ["chat", "sessions", "rag", "toolloop"],
        },
        "candidates": runs,
        "samples": samples,
        "profile": fingerprint,
        "recommendation": rec,
        "recommended": {"name": rec_name, **(runs.get(rec_name) or {})},
        "default": runs["default"],
        "forecast": {"on": fc_on, "off": fc_off},
        "forecast_lead_s": lead,
        "caveats": [
            "CPU runs: absolute steps/s and percentiles are not TPU "
            "numbers; the section's claims are relative (recommended vs "
            "default on the same recorded workload, forecast on vs off "
            "on the same scripted trace).",
            "recommended/default sub-blocks are the measured candidate "
            "runs (same seed = same arrival trace), not a re-run.",
        ] if common.get("provider") != "tpu" else [
            "recommended/default sub-blocks are the measured candidate "
            "runs (same seed = same arrival trace), not a re-run.",
        ],
    }


async def bench_recovery(cfg, n_requests=6, max_new_tokens=48):
    """RECOVERY section (ISSUE 9): scripted single-fault soak. A wave of
    greedy requests decodes concurrently; one injected ``engine.step``
    failure lands mid-decode (``skip=1`` lets the first dispatch through
    so real tokens have folded); every in-flight request must complete
    through the engine's in-flight recovery with output byte-identical
    to an uninjected reference wave. Reports ``recovered_frac`` (1.0 =
    every requeued request completed), ``recovery_ms`` p50/p99
    (fault-snapshot → re-admission wall) and ``tokens_replayed`` (tokens
    re-prefilled over prompt+generated)."""
    from pilottai_tpu.engine.handler import LLMHandler
    from pilottai_tpu.engine.types import GenerationParams
    from pilottai_tpu.reliability import global_injector
    from pilottai_tpu.utils.metrics import global_metrics as _gm

    handler = LLMHandler(cfg)
    await handler.start()
    try:
        prompts = [_prompt(9000 + i) for i in range(n_requests)]

        async def wave():
            return await asyncio.gather(*[
                handler.apredict(
                    p,
                    params=GenerationParams(
                        max_new_tokens=max_new_tokens, temperature=0.0,
                    ),
                )
                for p in prompts
            ], return_exceptions=True)

        base = await wave()
        counters = (
            "engine.recovery_requeued", "engine.recovered_requests",
            "engine.recovery_failed", "engine.tokens_replayed",
            "engine.rebuilds",
        )
        before = {k: _gm.get(k) for k in counters}
        _gm.reset_histograms("engine.recovery_ms")
        global_injector.arm(
            "engine.step", RuntimeError("bench-injected device fault"),
            times=1, skip=1,
        )
        t0 = time.perf_counter()
        injected = await wave()
        wall = time.perf_counter() - t0
        delta = {k: _gm.get(k) - before[k] for k in counters}
        errors = sum(isinstance(o, Exception) for o in injected)
        identical = sum(
            1 for a, b in zip(base, injected)
            if not isinstance(b, Exception) and a == b
        )
        hist = (_gm.snapshot()["histograms"].get("engine.recovery_ms")
                or {})
        requeued = delta["engine.recovery_requeued"]
        return {
            # 1.0 ⇔ every request the fault interrupted completed anyway.
            "recovered_frac": (
                round(delta["engine.recovered_requests"] / requeued, 4)
                if requeued else (1.0 if errors == 0 else 0.0)
            ),
            "outputs_identical": identical == n_requests,
            "client_errors": errors,
            "requests": n_requests,
            "requeued": int(requeued),
            "recovery_failed": int(delta["engine.recovery_failed"]),
            "recovery_ms_p50": hist.get("p50"),
            "recovery_ms_p99": hist.get("p99"),
            "tokens_replayed": int(delta["engine.tokens_replayed"]),
            "rebuilds": int(delta["engine.rebuilds"]),
            "fault_fired": global_injector.fired("engine.step") > 0,
            "wall_s": round(wall, 2),
            "model": cfg.model_name,
        }
    finally:
        global_injector.disarm("engine.step")
        await handler.stop()
        gc.collect()


async def bench_kvcache(cfg, n_sessions=6, turns=3, max_new_tokens=24):
    """KVCACHE section (ISSUE 10): multi-turn session workload against
    the global KV cache tier. ``n_sessions`` conversations interleave
    round-robin, each turn re-sending the session's full transcript
    (the multi-turn agent shape). The device-resident store is
    deliberately tiny (``engine_prefix_cache`` in cfg), so by the time
    a session's next turn arrives its entry has been evicted — and with
    the host tier enabled the eviction SPILLED instead of discarding,
    so the resume restores from host RAM and prefills only the new
    tail. Headlines: ``prefix_hit_rate`` (hits ÷ lookups; > 0 on resume
    after eviction is the acceptance bar), ``prefill_tokens_saved`` and
    restore p50/p99 (host-side staging wall). Greedy parity tier on/off
    is pinned by tests/test_kvcache.py, not re-measured here."""
    from pilottai_tpu.engine.handler import LLMHandler
    from pilottai_tpu.engine.types import GenerationParams
    from pilottai_tpu.utils.metrics import global_metrics as _gm

    handler = LLMHandler(cfg)
    await handler.start()
    try:
        # Per-session preambles diverge immediately (distinct lineages:
        # cross-session LCP entries must not mask the cold tier) and
        # clear the store's 64-token entry floor on their own.
        def preamble(s):
            return (
                f"Session {s:03d} memory: persona agent-{s}; "
                f"goals g{s * 7}, g{s * 11}; constraints c{s * 13}. "
                + PREAMBLE
            )

        history = {s: "" for s in range(n_sessions)}
        counters = (
            "lookups", "hits", "host_hits", "spills", "restores",
            "prefill_tokens_saved",
        )
        before = {
            k: _gm.get(f"engine.kvcache.{k}") for k in counters
        }
        _gm.reset_histograms("engine.kvcache.restore_ms")
        t0 = time.perf_counter()
        for turn in range(turns):
            for s in range(n_sessions):
                prompt = (
                    preamble(s) + history[s]
                    + f"\nuser: next step for item {turn}?\nassistant:"
                )
                params = GenerationParams(
                    max_new_tokens=max_new_tokens, temperature=0.0,
                    session_id=f"bench-sess-{s}",
                )
                reply = await handler.apredict(prompt, params=params)
                history[s] += (
                    f"\nuser: next step for item {turn}?"
                    f"\nassistant: {reply}"
                )
        wall = time.perf_counter() - t0
        delta = {
            k: _gm.get(f"engine.kvcache.{k}") - before[k] for k in counters
        }
        hist = (
            _gm.snapshot()["histograms"].get("engine.kvcache.restore_ms")
            or {}
        )
        return {
            "prefix_hit_rate": round(
                delta["lookups"] and delta["hits"] / delta["lookups"], 4
            ),
            "prefill_tokens_saved": int(delta["prefill_tokens_saved"]),
            "host_hits": int(delta["host_hits"]),
            "spills": int(delta["spills"]),
            "restores": int(delta["restores"]),
            "restore_ms_p50": hist.get("p50"),
            "restore_ms_p99": hist.get("p99"),
            "host_bytes": int(_gm.get("engine.kvcache.host_bytes")),
            "sessions": n_sessions,
            "turns": turns,
            "requests": n_sessions * turns,
            "wall_s": round(wall, 2),
            "model": cfg.model_name,
        }
    finally:
        await handler.stop()
        gc.collect()


async def bench_cell(cfg, n_replicas=3, rate_rps=8.0, duration_s=12.0,
                     single_rps=None, n_sessions=6, seed=11, n_chips=1):
    """CELL section (ISSUE 11): an N-replica serving cell under the
    ``bench_slo`` open-loop harness at a deliberate overload — the
    offered rate is ≥10× what ONE engine absorbs, so the section shows
    the cell doing its actual job: KV-affinity routing (sessionful
    tenants pin to their replica; ``affinity_hit_rate``), per-class
    SLO-aware shedding at the cell boundary (``classes.*.shed`` — batch
    sheds first, interactive is defended), a scripted mid-soak session
    migration and a scripted replica drain with session KV moving in
    the host tier's transfer format. Headline: interactive attainment
    at the overload, affinity hit rate, per-class shed counts."""
    import random as _random

    from pilottai_tpu.distributed import ServingCell
    from pilottai_tpu.engine.handler import LLMHandler
    from pilottai_tpu.engine.types import GenerationParams
    from pilottai_tpu.reliability import EngineOverloaded
    from pilottai_tpu.utils.metrics import global_metrics as _gm

    cell = ServingCell([LLMHandler(cfg) for _ in range(n_replicas)])
    await cell.start()
    rng = _random.Random(seed)
    uid = [0]

    def session_prompt(k):
        # Stable per-session transcript head: the routing table's
        # affinity primitive (same bytes → same radix path) and the
        # engine tier's lineage in one.
        return (
            f"Session cell-{k:02d} memory: persona agent-{k}; "
            + PREAMBLE + f"continue thread {k}"
        )

    # (name, weight, slo_class, max_new_tokens, session_k)
    tenants = [
        ("chat", 0.4, "interactive", 24, None),
        ("session", 0.4, "interactive", 24, "cycle"),
        ("batch", 0.2, "batch", 32, None),
    ]

    async def one(tenant, warm=False):
        name, _, slo_class, max_new, kind = tenant
        uid[0] += 1
        sid = None
        if kind == "cycle":
            k = uid[0] % n_sessions
            prompt = session_prompt(k)
            sid = f"cellbench-{k}"
        else:
            prompt = _prompt(uid[0])
        params = GenerationParams(
            max_new_tokens=max_new, temperature=0.0, slo_class=slo_class,
            session_id=sid,
        )
        try:
            await cell.apredict(prompt, params=params)
            return "ok"
        except EngineOverloaded:
            return "shed"
        except Exception as exc:  # noqa: BLE001 — harness keeps running
            if not warm:
                _note("cell request FAILED", {"tenant": name,
                                              "error": str(exc)[:200]})
            return "error"

    # Warm every replica (compiles + one session turn each).
    for tenant in tenants:
        await asyncio.gather(*[one(tenant, warm=True) for _ in range(
            n_replicas)])

    counters = (
        "cell.routed.interactive", "cell.routed.batch",
        "cell.shed.interactive", "cell.shed.batch",
        "cell.affinity_lookups", "cell.affinity_hits",
        "cell.migrations", "cell.migrated_tokens", "cell.rerouted",
    )
    before = {k: _gm.get(k) for k in counters}
    _gm.reset_histograms("cell.migration_ms")
    _gm.reset_histograms("cell.drain_s")
    for rep in cell.replicas.values():
        rep.slo.reset()
    # reset() clears the rolling windows (attainment/burn are
    # section-pure from here) but requests/missed are cumulative
    # registry counters — report section DELTAS, same discipline as
    # bench_slo.
    slo0 = cell.slo_snapshot()["classes"]

    names = [t[0] for t in tenants]
    weights = [t[1] for t in tenants]
    t_start = time.perf_counter()
    t_end = t_start + duration_s
    inflight: list = []
    offered = {n: 0 for n in names}
    migrated = None
    drained = None
    drain_task = None
    next_at = t_start
    while True:
        now = time.perf_counter()
        frac = (now - t_start) / duration_s
        if now >= t_end:
            break
        if migrated is None and frac >= 0.4 and cell.sessions:
            # Scripted rebalance: move one hot session's KV lineage.
            sid = sorted(cell.sessions)[0]
            try:
                migrated = await cell.migrate_session(sid)
            except Exception as exc:  # noqa: BLE001 — report, keep going
                migrated = {"error": str(exc)}
        if drained is None and frac >= 0.6:
            # Scripted zero-downtime drain of one replica mid-soak; its
            # sessions migrate, its in-flight work re-admits elsewhere.
            rid = next(iter(cell.replicas))
            drained = rid
            drain_task = asyncio.create_task(cell.drain(rid, grace_s=1.0))
        # Catch-up arrivals: spawn every arrival whose Poisson time has
        # come. Open-loop means arrivals wait for NOTHING — not for
        # completions, and not for the event loop's sleep granularity
        # (a per-arrival sleep silently caps the offered rate at the
        # loop's wakeup resolution, diluting the overload the section
        # exists to demonstrate).
        while next_at <= now and next_at < t_end:
            tenant = rng.choices(tenants, weights=weights, k=1)[0]
            offered[tenant[0]] += 1
            inflight.append(asyncio.create_task(one(tenant)))
            next_at += rng.expovariate(max(rate_rps, 1e-3))
        await asyncio.sleep(min(max(next_at - now, 0.0), 0.02))
    arrival_wall = time.perf_counter() - t_start
    outcomes = await asyncio.gather(*inflight)
    if drain_task is not None:
        drain_report = await drain_task
    else:
        drain_report = None
    drain_wall = time.perf_counter() - t_start - arrival_wall
    slo = cell.slo_snapshot()
    delta = {k: _gm.get(k) - before[k] for k in counters}
    mig_hist = (_gm.snapshot()["histograms"].get("cell.migration_ms")
                or {})
    await cell.stop()
    gc.collect()

    classes = {}
    for cls, entry in (slo.get("classes") or {}).items():
        base = slo0.get(cls) or {}
        requests = int(entry["requests"] - base.get("requests", 0))
        if not requests:
            continue
        classes[cls] = {
            "attainment": entry["attainment"],
            "burn_rate": entry["burn_rate"],
            "requests": requests,
            "missed": int(entry["missed"] - base.get("missed", 0)),
            "e2e_p99_s": entry.get("e2e_p99_s"),
            "routed": int(delta.get(f"cell.routed.{cls}", 0)),
            "shed": int(delta.get(f"cell.shed.{cls}", 0)),
        }
    lookups = delta["cell.affinity_lookups"]
    offered_rps = sum(offered.values()) / arrival_wall
    return {
        "replicas": n_replicas,
        "offered_rps": round(offered_rps, 2),
        "target_rps": rate_rps,
        "duration_s": round(arrival_wall, 1),
        "drain_wall_s": round(drain_wall, 1),
        # The overload multiple: offered load vs what ONE engine
        # sustains closed-loop (the 1B/tiny section's measured rate).
        "single_engine_rps": single_rps,
        "load_multiple": (
            round(offered_rps / single_rps, 1) if single_rps else None
        ),
        "offered": offered,
        "completed": outcomes.count("ok"),
        "shed": outcomes.count("shed"),
        "errors": outcomes.count("error"),
        "affinity_hit_rate": round(
            delta["cell.affinity_hits"] / lookups, 4
        ) if lookups else None,
        "rerouted": int(delta["cell.rerouted"]),
        "migrations": int(delta["cell.migrations"]),
        "migrated_tokens": int(delta["cell.migrated_tokens"]),
        "migration_ms_p50": mig_hist.get("p50"),
        "migration_ms_p99": mig_hist.get("p99"),
        "drained_replica": drained,
        "drain_s": (drain_report or {}).get("drain_s"),
        "drain_readmitted": (drain_report or {}).get("readmitted"),
        "drain_migrated_sessions": (
            (drain_report or {}).get("migrated_sessions")
        ),
        "classes": classes,
        "model": cfg.model_name,
        "n_chips": n_chips,
    }


async def bench_disagg(cfg, rate_rps, prefill_rps, duration_s=6.0,
                       n_sessions=4, seed=13, n_chips=1):
    """DISAGG section (ISSUE 19): the same mixed workload — sticky
    interactive sessions (decode-heavy) plus a stream of long cold RAG
    prefills — against a 2-replica cell COLOCATED (both mixed) and then
    DISAGGREGATED (``1p1d``). Each run measures two phases: decode
    traffic alone (baseline TPOT), then decode traffic with the long
    prefills running concurrently. The headline is the interference
    ratio — mixed-phase interactive TPOT p99 over baseline — which
    disaggregation must hold closer to 1.0 than colocation: the prefill
    tier absorbs the chunked prefill work, the decode tier restores the
    handed-off KV and only decodes. Handoff health rides along:
    ``handoff_success`` ((handoffs - fallbacks) / handoffs) and the
    ``cell.handoff_ms`` p50/p99.

    Caveat (stamped as ``host_cores`` / ``isolation_measurable``):
    in-process replicas share the host's cores, so on a single-core
    CPU host the prefill work steals the decode tier's cycles through
    the OS scheduler no matter which replica runs it — the interference
    ratios then read as parity and the measurable claims are handoff
    health + tier routing; the TPOT separation needs per-replica
    silicon (accelerator hosts, or a multi-core CPU host).

    TPOT percentiles come from the SLO tracker's flight listener. The
    1-token prefill legs of handoffs contribute no TPOT sample (TPOT
    needs a second token), so the interference axis is clean; their
    TTFT samples do land in the interactive pool, so the disagg run's
    TTFT p99 reads as the p99 over client requests AND prefill legs —
    a mild downward dilution, called out here rather than filtered."""
    import random as _random

    from pilottai_tpu.distributed import ServingCell
    from pilottai_tpu.engine.handler import LLMHandler
    from pilottai_tpu.engine.types import GenerationParams
    from pilottai_tpu.obs import global_slo
    from pilottai_tpu.reliability import EngineOverloaded
    from pilottai_tpu.utils.metrics import global_metrics as _gm

    counters = (
        "cell.handoffs", "cell.handoff_fallbacks", "cell.handoff_rejected",
        "cell.handoff_tokens", "cell.tier.prefill_routed",
        "cell.tier.decode_routed", "cell.tier.bypass",
    )

    async def _run(disagg):
        cell = ServingCell(
            [LLMHandler(cfg) for _ in range(2)],
            cell_disagg="1p1d" if disagg else None,
        )
        await cell.start()
        rng = _random.Random(seed)
        uid = [0]
        session_log: dict = {}

        async def decode_turn(k):
            uid[0] += 1
            log = session_log.setdefault(k, [
                f"Session disagg-{k:02d} memory: persona agent-{k}; "
                + f"context: thread {k} telemetry baseline; " * 3
            ])
            log.append(f"turn {len(log)}: user question {uid[0]}")
            if len(log) > 6:
                # Bound transcript growth but keep the head line — it
                # carries the session's routing-table identity.
                del log[1:len(log) - 5]
            params = GenerationParams(
                max_new_tokens=16, temperature=0.0,
                slo_class="interactive", session_id=f"disagg-sess-{k}",
            )
            try:
                await cell.apredict("\n".join(log), params=params)
                return "ok"
            except EngineOverloaded:
                return "shed"
            except Exception as exc:  # noqa: BLE001 — harness runs on
                _note("disagg decode FAILED", {"error": str(exc)[:200]})
                return "error"

        async def rag_one():
            uid[0] += 1
            # Unique per-request body: a shared preamble would go
            # prefix-hot after the first arrival and bypass the prefill
            # tier — the section exists to measure the handoff path.
            seg = f"retrieved shard {uid[0]}: fleet telemetry chunk; "
            # 420 + suffix + chat-template overhead stays under the
            # handoff keep-window (engine_max_seq - 1 - max_new_tokens):
            # a longer body is non-migratable and serves colocated.
            body = (seg * 12)[:420] + f" summarize incident {uid[0]}."
            params = GenerationParams(
                max_new_tokens=8, temperature=0.0, slo_class="batch",
            )
            try:
                await cell.apredict(body, params=params)
                return "ok"
            except EngineOverloaded:
                return "shed"
            except Exception as exc:  # noqa: BLE001 — harness runs on
                _note("disagg rag FAILED", {"error": str(exc)[:200]})
                return "error"

        # Warm: establish every session's pin (first turns hand off on
        # the disagg run) and compile the decode + RAG prefill shapes.
        # Seven rounds, not one — transcripts grow until the 6-line
        # bound and walk through new prefill buckets on the way; a
        # compile landing inside the baseline phase would dominate its
        # TPOT p99 (the first topology run pays all compiles for both
        # otherwise).
        for _ in range(7):
            await asyncio.gather(*[decode_turn(k) for k in range(n_sessions)])
            await rag_one()

        before = {k: _gm.get(k) for k in counters}
        _gm.reset_histograms("cell.handoff_ms")

        async def phase(with_prefills):
            global_slo.reset()
            _gm.reset_histograms("request.")
            rag_offered = [0]
            t0 = time.perf_counter()
            t_end = t0 + duration_s
            inflight: list = []
            next_dec = t0
            next_rag = t0
            while True:
                now = time.perf_counter()
                if now >= t_end:
                    break
                while next_dec <= now and next_dec < t_end:
                    inflight.append(asyncio.create_task(
                        decode_turn(rng.randrange(n_sessions))
                    ))
                    next_dec += rng.expovariate(max(rate_rps, 1e-3))
                while with_prefills and next_rag <= now and next_rag < t_end:
                    rag_offered[0] += 1
                    inflight.append(asyncio.create_task(rag_one()))
                    next_rag += rng.expovariate(max(prefill_rps, 1e-3))
                nxt = min(next_dec, next_rag) if with_prefills else next_dec
                await asyncio.sleep(min(max(nxt - now, 0.0), 0.02))
            outcomes = await asyncio.gather(*inflight)
            inter = (global_slo.snapshot() or {}).get("interactive") or {}
            return {
                "offered": len(outcomes),
                "rag_offered": rag_offered[0],
                "completed": outcomes.count("ok"),
                "shed": outcomes.count("shed"),
                "errors": outcomes.count("error"),
                "ttft_p99_s": inter.get("ttft_p99_s"),
                "tpot_p50_s": inter.get("tpot_p50_s"),
                "tpot_p99_s": inter.get("tpot_p99_s"),
                "e2e_p99_s": inter.get("e2e_p99_s"),
                "attainment": inter.get("attainment"),
            }

        base = await phase(False)
        mixed = await phase(True)
        delta = {k: _gm.get(k) - before[k] for k in counters}
        hand_hist = (
            _gm.snapshot()["histograms"].get("cell.handoff_ms") or {}
        )
        await cell.stop()
        gc.collect()

        tp_base = base.get("tpot_p99_s")
        tp_mixed = mixed.get("tpot_p99_s")
        tp50_base = base.get("tpot_p50_s")
        tp50_mixed = mixed.get("tpot_p50_s")
        handoffs = int(delta["cell.handoffs"])
        fallbacks = int(delta["cell.handoff_fallbacks"])
        return {
            "topology": "1p1d" if disagg else "colocated",
            "baseline": base,
            "mixed": mixed,
            "tpot_interference": (
                round(tp_mixed / tp_base, 3)
                if tp_base and tp_mixed else None
            ),
            # p50-based secondary: far fewer samples land in a short
            # phase's p99 (it degenerates toward the max), so the p50
            # ratio is the stabler read on a noisy host.
            "tpot_interference_p50": (
                round(tp50_mixed / tp50_base, 3)
                if tp50_base and tp50_mixed else None
            ),
            "handoffs": handoffs,
            "handoff_fallbacks": fallbacks,
            "handoff_rejected": int(delta["cell.handoff_rejected"]),
            "handoff_tokens": int(delta["cell.handoff_tokens"]),
            "handoff_success": (
                round((handoffs - fallbacks) / handoffs, 4)
                if handoffs else None
            ),
            "handoff_ms_p50": hand_hist.get("p50"),
            "handoff_ms_p99": hand_hist.get("p99"),
            "prefill_routed": int(delta["cell.tier.prefill_routed"]),
            "decode_routed": int(delta["cell.tier.decode_routed"]),
            "prefix_bypass": int(delta["cell.tier.bypass"]),
        }

    colocated = await _run(False)
    disagg = await _run(True)
    import os as _os

    host_cores = len(_os.sched_getaffinity(0)) if hasattr(
        _os, "sched_getaffinity") else (_os.cpu_count() or 1)
    return {
        "colocated": colocated,
        "disagg": disagg,
        "rate_rps": rate_rps,
        "prefill_rps": prefill_rps,
        "duration_s": duration_s,
        # Honesty stamp: in-process replicas timeshare the host's
        # cores. On a single-core host the compute-isolation half of
        # disaggregation is physically invisible (both topologies burn
        # the same core) and the interference ratios read as parity —
        # the split shows up in handoff health, tier routing and slot
        # separation; the TPOT win needs per-replica silicon.
        "host_cores": host_cores,
        "isolation_measurable": host_cores > 1,
        "model": cfg.model_name,
        "n_chips": n_chips,
    }


async def bench_pipeline(provider: str, rounds: int = 4):
    """BASELINE config #3 through the orchestrator: Serve + manager + 3
    specialists on the document pipeline, real engine, measured at
    ``Serve.execute`` granularity (routing, evaluation, retry and
    journaling included)."""
    from examples.document_pipeline.pipeline import (
        SAMPLE_DOC,
        build_pipeline,
        stage_tasks,
    )

    serve, _memory = build_pipeline(provider=provider)
    # The trained protocol model completes a stage in one tool step +
    # one completion step; two iterations is that realistic shape (and
    # keeps a missing-checkpoint fallback from measuring the
    # max_iterations=20 cap instead of the orchestrator).
    for a in serve.agents.values():
        a.config.max_iterations = 2
    _reset_task_attribution()
    await serve.start()
    try:
        waves = []
        task_lat = []
        ok = total = 0
        for r in range(rounds + 1):  # round 0 is warmup/compile
            tasks = stage_tasks(
                str(SAMPLE_DOC), f"What are the key findings? (round {r})"
            )
            t0 = time.perf_counter()
            results = await serve.execute(list(tasks))
            wall = time.perf_counter() - t0
            if r == 0:
                # Warmup-pure attribution: round 0's compile-inflated
                # task times must not land in the section fractions.
                _reset_task_attribution()
            if r > 0:
                waves.append(wall)
                ok += sum(1 for res in results if res.success)
                total += len(results)
                task_lat += [
                    res.execution_time for res in results
                    if res.execution_time
                ]
        # Capture while the agents are still registered — stop()
        # retires each role from the occupancy tracker.
        attribution = _task_attribution("pipeline")
    finally:
        await serve.stop()
    gc.collect()
    from pilottai_tpu.train.protocol import has_checkpoint

    return {
        "pipeline_p50_ms": round(statistics.median(task_lat) * 1000.0, 1),
        "pipeline_wall_s": round(statistics.median(waves), 2),
        "pipeline_success": f"{ok}/{total}",
        "rounds": rounds,
        "stages_per_round": len(tasks),
        "pipeline_model": "protocol-s" if provider != "mock" else "mock",
        "pipeline_trained_checkpoint": has_checkpoint(),
        # Orchestrator-cost curve (obs/dag.py): how much of summed task
        # e2e the orchestration layer itself ate, and how busy each
        # specialist actually was — tracked alongside steps/s and MFU.
        **attribution,
    }


async def bench_swarm(model: str, provider: str, n_agents: int = 32,
                      n_tasks: int = 96):
    """BASELINE config #4 through the orchestrator: a swarm of agents on
    one Serve sharing a single engine. Reports LLM agent-steps/s (the
    analyze/evaluate/step calls Serve's task flow actually makes) and
    task-completion p50 through ``Serve.execute_task``."""
    from pilottai_tpu.core.agent import BaseAgent
    from pilottai_tpu.core.config import AgentConfig, LLMConfig, ServeConfig
    from pilottai_tpu.engine.handler import LLMHandler
    from pilottai_tpu.serve import Serve
    from pilottai_tpu.utils.metrics import global_metrics

    from pilottai_tpu.core.config import SamplingConfig
    from pilottai_tpu.train.protocol import (
        DEFAULT_CHECKPOINT,
        SERVE_MAX_NEW,
        SERVE_MAX_SEQ,
        has_checkpoint,
    )

    has_ckpt = has_checkpoint()
    llm = LLMHandler(LLMConfig(
        model_name=model, provider=provider,
        # The in-tree-trained protocol checkpoint: agents make their
        # decisions from real decoded tokens and tasks SUCCEED
        # (train/protocol.py; random weights without it — reported).
        checkpoint_path=str(DEFAULT_CHECKPOINT) if has_ckpt else None,
        # Swarm traffic trickles in (each task's calls are sequential),
        # so admission groups stay small — admit_batch at n_agents would
        # pad every 1-4 arrivals to 32 prefill rows.
        engine_slots=n_agents, engine_admit_batch=8,
        engine_max_seq=SERVE_MAX_SEQ, engine_chunk=16,
        dtype="bfloat16" if provider == "tpu" else "float32",
        engine_speculate=4,
        sampling=SamplingConfig(temperature=0.0, max_new_tokens=SERVE_MAX_NEW),
    ))
    agents = [
        BaseAgent(
            config=AgentConfig(
                role=f"worker{i}", specializations=["generic"],
                max_iterations=2,  # see bench_pipeline's note
            ),
            llm=llm,
        )
        for i in range(n_agents)
    ]
    serve = Serve(
        name="swarm-bench", agents=agents, manager_llm=llm,
        config=ServeConfig(
            decomposition_enabled=False, max_concurrent_tasks=n_agents,
        ),
    )
    await serve.start()
    try:
        # Warmup wave (compiles + acceptance EMA).
        await asyncio.gather(*[
            serve.execute_task(f"warm task {i}") for i in range(n_agents)
        ])
        # Task attribution is section-pure AND warmup-pure: the compile
        # wave's inflated task times must not land in the overhead or
        # busy_frac fractions.
        _reset_task_attribution()
        c0 = global_metrics.get("engine.completed")
        t0 = time.perf_counter()
        results = await asyncio.gather(*[
            serve.execute_task(f"swarm task {i}: check inventory {i}")
            for i in range(n_tasks)
        ])
        wall = time.perf_counter() - t0
        llm_steps = global_metrics.get("engine.completed") - c0
        lat = [r.execution_time for r in results if r.execution_time]
        ok = sum(1 for r in results if r.success)
        attribution = _task_attribution("swarm")  # before stop() retires roles
    finally:
        await serve.stop()
    gc.collect()
    return {
        "swarm_steps_per_sec": round(llm_steps / wall, 2),
        "swarm_task_p50_ms": round(statistics.median(lat) * 1000.0, 1),
        "swarm_tasks_per_sec": round(n_tasks / wall, 2),
        "swarm_success": f"{ok}/{n_tasks}",
        "agents": n_agents,
        "swarm_model": model,
        "swarm_trained_checkpoint": has_ckpt,
        **attribution,
    }


async def bench_sched(model, provider, n_waves=4, gang=3, n_bg=6,
                      max_iterations=1):
    """SCHED section (ISSUE 12 / ROADMAP item 4): the DAG-aware
    scheduler's on-vs-off comparison on ONE workload — fan-out waves of
    ``gang`` HIGH-priority sibling tasks (gang-tagged, rolled up under
    a synthetic parent dag so PR 7's straggler/critical-path
    attribution applies) contending with LOW-priority background
    traffic on a deliberately saturated engine (2 slots), run twice:
    ``engine_sched_policy="fifo"`` + scheduler policy off, then
    ``"dag"`` + policy on.

    Reported per mode, in PR 7's field shapes:

    * ``swarm_straggler_frac`` — Σ parent ``straggler_s`` ÷ Σ task
      ``e2e_s`` (the task.* histograms, section-pure): the price of
      each join waiting on its slowest branch. Gang admission +
      critical-path priority attack exactly this.
    * ``swarm_critical_path_frac`` — Σ parent ``critical_path_s`` ÷ Σ
      task ``e2e_s``: the PARENT's wall (its critical path ≈ the
      fan-out's makespan) as a fraction of all task time spent. More
      parallel efficiency → smaller numerator on the same work.

    The acceptance bar (ISSUE 12): both lower with the scheduler on,
    greedy outputs byte-identical on/off (pinned by
    tests/test_sched.py, not re-measured here), and scheduler-on task
    success ≥ scheduler-off (tests/test_mini_swarm.py CI lane)."""
    from pilottai_tpu.core.agent import BaseAgent
    from pilottai_tpu.core.config import (
        AgentConfig,
        LLMConfig,
        SamplingConfig,
        ServeConfig,
    )
    from pilottai_tpu.core.task import Task
    from pilottai_tpu.obs.dag import global_dag
    from pilottai_tpu.sched import global_scheduler
    from pilottai_tpu.serve import Serve
    from pilottai_tpu.train.protocol import (
        DEFAULT_CHECKPOINT,
        SERVE_MAX_NEW,
        SERVE_MAX_SEQ,
        has_checkpoint,
    )
    from pilottai_tpu.utils.metrics import global_metrics as _gm

    has_ckpt = has_checkpoint()
    counters = (
        "sched.gang_admits", "sched.gang_partial", "sched.priority_aged",
        "sched.priority_boosts", "sched.prewarms", "sched.prewarm_hits",
    )
    out = {
        "waves": n_waves, "gang": gang, "background_per_wave": n_bg,
        "model": model if has_ckpt or provider == "tpu" else "untrained",
    }
    try:
        for mode in ("off", "on"):
            global_scheduler.configure(policy="dag" if mode == "on" else "off")
            global_scheduler.reset()
            global_dag.reset()
            from pilottai_tpu.engine.handler import LLMHandler

            llm = LLMHandler(LLMConfig(
                model_name=model, provider=provider,
                checkpoint_path=str(DEFAULT_CHECKPOINT) if has_ckpt else None,
                # Small on purpose: the scheduler only matters when an
                # engine backlog exists — two slots against gang +
                # background concurrency keeps a backlog standing for
                # the whole wave, so admission ORDER (the thing under
                # test) is what decides who progresses.
                engine_slots=2, engine_admit_batch=2,
                engine_max_seq=SERVE_MAX_SEQ, engine_chunk=16,
                dtype="bfloat16" if provider == "tpu" else "float32",
                engine_sched_policy="dag" if mode == "on" else "fifo",
                # The aging floor must scale with service time: at the
                # default 2 s a LOW background call ages to CRITICAL
                # within ONE slow-engine LLM call and neutralizes the
                # priority signal this section exists to measure. 30 s
                # still guarantees no starvation across the run.
                engine_priority_aging_s=30.0,
                # Gang wait sized to the fan-out's emission spread (the
                # siblings below arrive ~0.3 s apart, as a real
                # decomposition emits them): the gang holds until its
                # siblings are present — or this bound — then admits
                # together ahead of the background.
                engine_gang_wait_ms=1500.0,
                # Pre-warm needs the KV cache tier; tiny hot store so
                # the cold tier actually serves.
                engine_prefix_cache=2, engine_kvcache_host_mb=64,
                sampling=SamplingConfig(
                    temperature=0.0, max_new_tokens=SERVE_MAX_NEW
                ),
            ))
            agents = [
                BaseAgent(
                    config=AgentConfig(
                        role=f"worker{i}", specializations=["generic"],
                        max_iterations=max_iterations,
                    ),
                    llm=llm,
                )
                for i in range(gang + n_bg)
            ]
            serve = Serve(
                name=f"sched-bench-{mode}", agents=agents, manager_llm=llm,
                config=ServeConfig(
                    decomposition_enabled=False,
                    max_concurrent_tasks=gang + n_bg,
                ),
            )
            await serve.start()
            try:
                # Warmup: compiles + the scheduler's stage model (two
                # tasks per role teach the stage transitions and
                # converge the pre-warm prefixes).
                await asyncio.gather(*[
                    serve.execute_task(f"warm task {i}")
                    for i in range(gang + n_bg)
                ])
                _reset_task_attribution()
                before = {k: _gm.get(k) for k in counters}
                steps0 = _gm.get("engine.completed")
                parent_bd = []
                wave_walls = []
                ok = total = 0
                t0 = time.perf_counter()
                for w in range(n_waves):
                    parent_id = f"sched-{mode}-wave-{w}"
                    gang_id = f"bench-gang-{mode}-{w}"
                    global_dag.start(parent_id, type="fanout")
                    # The straggler shape (ISSUE 12: "a task's slowest
                    # branch stops straggling behind unrelated
                    # traffic"): siblings are emitted ~0.3 s apart, the
                    # way a real decomposition streams its subtasks
                    # out, and an unrelated LOW-priority BURST lands
                    # between the second-to-last and LAST sibling.
                    # Under FIFO exactly that one branch queues behind
                    # the whole burst while its siblings already ran —
                    # slowest − median spikes by the burst's drain
                    # time. (Uniform background can't show this: FIFO
                    # fairness delays every branch EQUALLY, and
                    # straggler_s measures imbalance, not delay.) With
                    # the scheduler on, the late sibling's HIGH
                    # priority + the gang sort it ahead of the burst.
                    def _bg(i):
                        return asyncio.create_task(serve.execute_task(
                            Task(
                                description=(
                                    f"background {w}-{i}: tally ledger "
                                    f"{w * 10 + i}"
                                ),
                                priority="low",
                            )
                        ))

                    def _sib(i):
                        return asyncio.create_task(serve.execute_task(
                            Task(
                                description=(
                                    f"branch {w}-{i}: check inventory "
                                    f"{w * 10 + i}"
                                ),
                                priority="high",
                                parent_task_id=parent_id,
                                metadata={
                                    "gang_id": gang_id,
                                    "gang_size": gang,
                                },
                            )
                        ))

                    background = [_bg(0)]
                    await asyncio.sleep(0.2)
                    tw = time.perf_counter()
                    sib_handles = []
                    for i in range(gang - 1):
                        sib_handles.append(_sib(i))
                        await asyncio.sleep(0.3)
                    background += [_bg(i) for i in range(1, n_bg)]
                    await asyncio.sleep(0.3)
                    sib_handles.append(_sib(gang - 1))  # the straggler
                    sibs = await asyncio.gather(*sib_handles)
                    wave_walls.append(time.perf_counter() - tw)
                    summary = global_dag.finish(parent_id, "ok")
                    parent_bd.append((summary or {}).get("breakdown") or {})
                    bg = await asyncio.gather(*background)
                    ok += sum(1 for r in list(sibs) + list(bg) if r.success)
                    total += gang + len(bg)
                wall = time.perf_counter() - t0
                llm_steps = _gm.get("engine.completed") - steps0
                hists = _gm.snapshot()["histograms"]

                def _total(name):
                    h = hists.get(name) or {}
                    return (h.get("count") or 0) * (h.get("mean") or 0.0)

                e2e_total = _total("task.e2e_s")
                parent_cp = sum(
                    float(bd.get("critical_path_s") or 0.0)
                    for bd in parent_bd
                )
                delta = {
                    k.split(".", 1)[1]: int(_gm.get(k) - before[k])
                    for k in counters
                }
                out[mode] = {
                    "swarm_straggler_frac": (
                        round(_total("task.straggler_s") / e2e_total, 4)
                        if e2e_total else None
                    ),
                    "swarm_critical_path_frac": (
                        round(parent_cp / e2e_total, 4) if e2e_total else None
                    ),
                    "wave_p50_ms": round(
                        statistics.median(wave_walls) * 1000.0, 1
                    ),
                    "steps_per_sec": round(llm_steps / wall, 2),
                    "success": f"{ok}/{total}",
                    **delta,
                }
            finally:
                await serve.stop()
                await llm.stop()
            gc.collect()
    finally:
        # The process default: policy on (engine_sched_policy defaults
        # to "dag" too) — later sections must not inherit "off".
        global_scheduler.configure(policy="dag")
    on, off = out.get("on") or {}, out.get("off") or {}

    def _lower(key):
        a, b = on.get(key), off.get(key)
        return bool(a is not None and b is not None and a < b)

    out["straggler_frac_improved"] = _lower("swarm_straggler_frac")
    out["critical_path_frac_improved"] = _lower("swarm_critical_path_frac")
    return out


async def bench_multichip(
    model_name: str,
    provider: str,
    mesh_shape,
    concurrency: int = 8,
    steps: int = 24,
    epochs: int = 2,
):
    """MULTICHIP section (ISSUE 13): a REAL tensor-parallel serving soak
    — not the 32-token dryrun the early MULTICHIP_r* records hold. The engine
    boots on ``mesh_shape`` with the paged KV pool sharded over the
    ``model`` axis and admission replicated over ``data``, runs the same
    closed-loop agent-step workload as the single-chip sections, and
    reports per-chip steps/s, MFU, and the per-axis collective-time
    split (``engine.collective_frac.model`` / ``.data``,
    parallel/collectives.py) next to a single-device run of the SAME
    config for parallel efficiency. Runnable on CPU via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
    subprocess path ``python bench.py --multichip`` sets that up
    itself); greedy output parity sharded-vs-single is pinned by
    tests/test_multichip.py, so this section only measures."""
    from pilottai_tpu.core.config import LLMConfig
    from pilottai_tpu.models.registry import get_model_config
    from pilottai_tpu.parallel.mesh import MeshConfig, create_mesh
    from pilottai_tpu.parallel.sharding import validate_serving_mesh
    from pilottai_tpu.utils.metrics import global_metrics as _gm

    mesh_cfg = MeshConfig.from_dict(mesh_shape)
    n_chips = mesh_cfg.n_devices
    on_accel = provider != "cpu"
    model_cfg = get_model_config(model_name)
    report = validate_serving_mesh(
        create_mesh(mesh_cfg), model_cfg, concurrency
    )

    def _cfg(mesh):
        return LLMConfig(
            model_name=model_name,
            provider=provider,
            mesh_shape=mesh,
            engine_slots=concurrency,
            engine_admit_batch=concurrency,
            engine_chunk=8,
            engine_speculate=4,
            # The flagship sharded combo: paged pool + int8 KV — the
            # shapes ISSUE 13's byte-identity matrix pins.
            engine_paged_kv=True,
            engine_page_size=32,
            engine_kv_quantize="int8",
            engine_max_seq=512,
            dtype="bfloat16" if on_accel else "float32",
            quantize="int8" if on_accel else None,
            timeout=600.0,
        )

    from pilottai_tpu.obs.attribution import PHASES

    def _attr():
        out = {
            phase: _gm.get(f"engine.attributed_{phase}_s")
            for phase in PHASES
        }
        for axis in ("model", "data"):
            out[f"collective.{axis}"] = _gm.get(
                f"engine.attributed_collective_s.{axis}"
            )
        return out

    attr0 = _attr()
    sec = await bench_model(
        _cfg(dict(mesh_shape)), concurrency, steps, epochs, n_chips=n_chips
    )
    attr1 = _attr()
    d_attr = {k: attr1[k] - attr0[k] for k in attr1}
    # Section-exact fractions from the cumulative counters (the rolling
    # gauges sample a 60 s window; deltas cover exactly this soak).
    attributed = sum(d_attr[p] for p in PHASES)
    coll_frac = d_attr["collective"] / attributed if attributed > 0 else 0.0
    coll_model = (
        d_attr["collective.model"] / attributed if attributed > 0 else 0.0
    )
    coll_data = (
        d_attr["collective.data"] / attributed if attributed > 0 else 0.0
    )
    n_steps = max(sec.get("steps") or steps, 1)

    # Single-device reference: the SAME engine config on one chip — the
    # denominator for parallel efficiency (and the parity partner the
    # test matrix pins byte-identical).
    single = await bench_model(
        _cfg({"data": 1}), concurrency, max(steps // 2, 8), 1, n_chips=1
    )

    sharded_rate = sec["steps_per_sec_per_chip"] * n_chips
    single_rate = max(single["steps_per_sec_per_chip"], 1e-9)
    out = {
        "mesh": {k: int(v) for k, v in mesh_shape.items()},
        "n_chips": n_chips,
        "model": model_name,
        "kv_heads_sharded": bool(report["kv_heads_sharded"]),
        "data_groups": int(report["data_groups"]),
        "steps_per_sec_per_chip": sec["steps_per_sec_per_chip"],
        "p50_step_ms": sec["p50_step_ms"],
        "decode_tokens_per_sec_per_chip": sec[
            "decode_tokens_per_sec_per_chip"
        ],
        "mfu": sec["mfu"],
        "paged": True,
        "kv_quantize": "int8",
        "speculate": 4,
        "steps": sec["steps"],
        # Collective attribution (parallel/collectives.py estimates
        # carved out of measured dispatch walls — see PERF_NOTES round
        # 10 for the methodology and its error bars).
        "collective_frac": round(coll_frac, 4),
        "collective_frac_model": round(coll_model, 4),
        "collective_frac_data": round(coll_data, 4),
        "collective_ms_per_step": round(
            d_attr["collective"] * 1000.0 / n_steps, 3
        ),
        "single_chip": {
            "steps_per_sec_per_chip": single["steps_per_sec_per_chip"],
            "p50_step_ms": single["p50_step_ms"],
            "mfu": single["mfu"],
        },
        # Sharded per-chip rate over the single-device rate: 1.0 = ideal
        # scaling. On the virtual CPU mesh the 8 "devices" share the
        # same cores, so this reads as partitioning overhead only;
        # accelerator rounds give the real number.
        "per_chip_efficiency": round(
            sec["steps_per_sec_per_chip"] / single_rate, 4
        ),
        "total_speedup": round(sharded_rate / single_rate, 4),
    }
    return out


def _multichip_subprocess(timeout_s: float = 2400.0):
    """Run the MULTICHIP section in a child process with a forced
    8-device host platform. The parent bench process initialized jax
    long ago (1 CPU device); device topology is fixed at first import,
    so the virtual mesh must be a fresh process — exactly how the CI
    multichip lane and tests/conftest.py get theirs."""
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--multichip"],
        capture_output=True, text=True, timeout=timeout_s, env=env,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"multichip subprocess rc={proc.returncode}: "
            f"{(proc.stderr or '')[-400:]}"
        )
    for line in reversed((proc.stdout or "").strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError("multichip subprocess produced no JSON")


def _chaos_subprocess(timeout_s: float = 900.0, seed: int = 16):
    """Run the CHAOS section (scripts/chaos_soak.py) in a child process
    with a forced 8-device host platform — the soak's survivor-ladder
    meshes need a virtual multichip topology, which is fixed at jax's
    first import (same constraint as _multichip_subprocess). A
    violation exit still yields the summary: the section records the
    red soak instead of erasing it."""
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts",
        "chaos_soak.py",
    )
    proc = subprocess.run(
        [sys.executable, script, "--seed", str(seed),
         "--budget-s", str(timeout_s * 0.8)],
        capture_output=True, text=True, timeout=timeout_s, env=env,
    )
    for line in reversed((proc.stdout or "").strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError(
        f"chaos subprocess rc={proc.returncode} produced no JSON: "
        f"{(proc.stderr or '')[-400:]}"
    )


async def run_multichip_cli():
    """``python bench.py --multichip``: the MULTICHIP section alone,
    one JSON line on stdout (the parent bench embeds it; the committed
    MULTICHIP_r*.json artifact wraps it)."""
    platform = jax.default_backend()
    on_accel = platform == "tpu"
    n = len(jax.devices())
    if n < 8:
        print(json.dumps({
            "skipped": True,
            "reason": f"{n} device(s); the multichip soak needs 8 "
                      f"(set XLA_FLAGS=--xla_force_host_platform_"
                      f"device_count=8 on CPU)",
        }))
        return
    sec = await bench_multichip(
        model_name="llama3-8b-byte" if on_accel else "protocol-s",
        provider="tpu" if on_accel else "cpu",
        mesh_shape={"model": 4, "data": 2},
        concurrency=8,
        steps=24 if on_accel else 16,
        epochs=2,
    )
    _note("multichip", sec)
    print(json.dumps(sec))


async def bench_quant(on_accel, n_chips=1):
    """QUANT section (ISSUE 14 / ROADMAP item 3): the same serving shape
    per weight-quantization mode, so the decode-roofline claim is a
    measured series — bytes/token read (the ``engine.weight_bytes*``
    gauges set at engine boot), steps/s and MFU per mode, and the
    int4-vs-int8 bytes ratio as the headline cost axis.

    On an accelerator the modes are int8/int4 on the 8B north-star
    model (the dense bf16 tree does not fit a 16 GB chip — that is the
    point of the series). On CPU the section is plumbing proof on the
    protocol-s shape: none/int8/int4, honest tiny-model caveat — its
    tied fp32 embed is a far larger share of bytes/token than at 8B,
    so the CPU ratio understates the 8B win (the layer-stream ratio is
    pinned ≤ 0.55 by tests/test_quant_parity.py either way)."""
    from pilottai_tpu.core.config import LLMConfig
    from pilottai_tpu.utils.metrics import global_metrics as _gm

    if on_accel:
        model, modes = "llama3-8b-byte", ("int8", "int4")
        shape = dict(
            engine_slots=8, engine_chunk=16, engine_speculate=6,
        )
        load = dict(concurrency=8, steps=24, epochs=2)
    else:
        model, modes = "protocol-s", ("none", "int8", "int4")
        shape = dict(engine_slots=4, engine_chunk=8, engine_speculate=0)
        load = dict(concurrency=4, steps=12, epochs=1)
    group = 128
    out = {"model": model, "quant_group": group, "modes": {}}
    for mode in modes:
        cfg = LLMConfig(
            model_name=model,
            provider="tpu" if on_accel else "cpu",
            engine_max_seq=512,
            dtype="bfloat16" if on_accel else "float32",
            engine_quant=mode,
            engine_quant_group=group,
            timeout=600.0,
            **shape,
        )
        sec = await bench_model(cfg, n_chips=n_chips, **load)
        out["modes"][mode] = {
            "steps_per_sec_per_chip": sec["steps_per_sec_per_chip"],
            "p50_step_ms": sec["p50_step_ms"],
            "decode_tokens_per_sec_per_chip": sec[
                "decode_tokens_per_sec_per_chip"
            ],
            "mfu": sec["mfu"],
            # Gauges set by THIS engine's boot (sections run serially,
            # last writer is this mode's batcher).
            "weight_bytes": int(_gm.get("engine.weight_bytes")),
            "weight_bytes_per_token": int(
                _gm.get("engine.weight_bytes_per_token")
            ),
            **(
                {"device_ms_per_step": sec.get("device_ms_per_step"),
                 "device_busy_frac": sec.get("device_busy_frac")}
                if sec.get("device_ms_per_step") is not None else {}
            ),
        }
        _note(f"quant[{mode}]", out["modes"][mode])
    if "int8" in out["modes"] and "int4" in out["modes"]:
        out["bytes_per_token_int4_vs_int8"] = round(
            out["modes"]["int4"]["weight_bytes_per_token"]
            / max(out["modes"]["int8"]["weight_bytes_per_token"], 1),
            4,
        )
    return out


_FAILED_SECTIONS = []


def _note(tag, payload):
    """Section progress to stderr — a crash in a later section must not
    lose the numbers already measured. A ``... FAILED`` note is kept: the
    process exits non-zero when any section failed (``_exit_code``)."""
    if tag.endswith("FAILED"):
        _FAILED_SECTIONS.append(tag)
    print(f"[bench] {tag}: {json.dumps(payload)}", file=sys.stderr, flush=True)


def _exit_code() -> int:
    if _FAILED_SECTIONS:
        print(f"[bench] {len(_FAILED_SECTIONS)} section(s) failed: "
              f"{_FAILED_SECTIONS}", file=sys.stderr, flush=True)
        return 1
    return 0


def _reset_task_attribution():
    """Section-pure task-DAG attribution: drop the previous section's
    ``task.*`` histograms and the occupancy windows so this section's
    overhead/critical-path fractions and busy_frac describe ONLY its own
    tasks (same discipline as the ``request.`` resets above)."""
    from pilottai_tpu.obs import global_occupancy
    from pilottai_tpu.utils.metrics import global_metrics as _gm

    _gm.reset_histograms("task.")
    global_occupancy.reset()


def _task_attribution(prefix):
    """Orchestrator-cost fields for a Serve-driven section (obs/dag.py):
    orchestration overhead and critical-path time as fractions of
    summed task e2e, plus per-agent-role busy fractions. Histogram
    count×mean = sum because the section reset the ``task.`` histograms
    at its start."""
    from pilottai_tpu.obs import global_occupancy
    from pilottai_tpu.utils.metrics import global_metrics as _gm

    hists = _gm.snapshot()["histograms"]

    def total(name):
        h = hists.get(name) or {}
        return (h.get("count") or 0) * (h.get("mean") or 0.0)

    e2e = total("task.e2e_s")
    fracs = global_occupancy.refresh()
    out = {
        f"{prefix}_orchestration_overhead_frac": (
            round(total("task.orchestrator_overhead_s") / e2e, 4)
            if e2e else None
        ),
        f"{prefix}_critical_path_frac": (
            round(total("task.critical_path_s") / e2e, 4) if e2e else None
        ),
        f"{prefix}_straggler_frac": (
            round(total("task.straggler_s") / e2e, 4) if e2e else None
        ),
        f"{prefix}_agent_busy_frac_mean": (
            round(statistics.mean(fracs.values()), 4) if fracs else None
        ),
        f"{prefix}_agent_busy_frac_max": (
            round(max(fracs.values()), 4) if fracs else None
        ),
    }
    # Full per-role map only when small (pipeline's 4 specialists, not
    # the swarm's 32 workers — the driver tail-captures the JSON).
    if fracs and len(fracs) <= 8:
        out[f"{prefix}_agent_busy_frac"] = {
            role: round(frac, 4) for role, frac in sorted(fracs.items())
        }
    return out


async def run_bench():
    from pilottai_tpu.core.config import LLMConfig
    from pilottai_tpu.obs import phase_summary

    # The provider is named after the backend jax found.
    platform = jax.default_backend()
    on_accel = platform == "tpu"
    n_chips = max(len(jax.devices()), 1) if on_accel else 1

    common = dict(
        provider="tpu" if on_accel else "cpu",
        engine_max_seq=512,
        dtype="bfloat16" if on_accel else "float32",
        quantize="int8" if on_accel else None,
        # First-wave compiles can exceed the default 120 s; a timeout
        # there cancels and RE-SUBMITS the whole wave
        # (measured as minutes of cascading retries in the 4K section).
        timeout=600.0,
    )

    async def _section(tag, coro):
        try:
            sec = await coro
            _note(tag, sec)
            return sec
        except Exception as exc:  # noqa: BLE001 — keep earlier sections
            _note(f"{tag} FAILED", {"error": str(exc)})
            return None

    # Section 1: 1B throughput model (byte vocab: runs without a
    # checkpoint download in the zero-egress environment).
    sec_1b = await bench_model(
        LLMConfig(
            model_name="llama3-1b-byte" if on_accel else "llama-tiny",
            engine_slots=32,
            # One fused admission per 32-slot wave; early-exit chunks
            # make a generous width free (decode stops at all-done).
            engine_admit_batch=32,
            # Early-exit makes a generous chunk free: 24 blocks covers
            # the slowest slot's 48 tokens in one dispatch even at the
            # straggler's acceptance (round-4 A/B: beat chunk 12/16 at
            # both D=4 and D=6).
            engine_chunk=24,
            engine_speculate=4,
            **common,
        ),
        concurrency=32, steps=96, epochs=3, n_chips=n_chips,
    )
    _note("1b", sec_1b)

    # Section 2: the north-star model over COLD prompts. D=6 verify
    # blocks won the round-4 sweep (D 4/6/8 x chunk): acceptance ~3.7
    # caps tokens/pass, early exit stops the chunk at all-done.
    sec_8b = None
    sec_8b_long = None
    sec_8b_8k = None
    if on_accel:
        sec_8b = await _section("8b", bench_model(
            LLMConfig(
                model_name="llama3-8b-byte", engine_slots=8,
                engine_chunk=16, engine_speculate=6,
                engine_draft_layers=2,
                **common,
            ),
            concurrency=8, steps=32, epochs=2, n_chips=n_chips,
        ))

        # Section 3: long-context serving — the paged pool with every
        # fast path composed (VERDICT r3 next-step 1 done-criterion:
        # p50 within ~1.3x of the dense section).
        sec_8b_long = await _section("8b-long", bench_model(
            LLMConfig(
                model_name="llama3-8b-byte", engine_slots=8,
                engine_chunk=16, engine_speculate=6,
                **{**common, "engine_max_seq": 4096},
                # Page 64: the block-prefix tail a cold prompt must
                # prefill is uniform(0, P) — page 128 measured ~80 ms
                # slower p50 at 4K than 64 (round-4 A/B).
                engine_paged_kv=True, engine_page_size=64,
                engine_kv_quantize="int8",
            ),
            # 3 epochs: host stall windows hit short epochs
            # hardest and this section's pass/fail bar is a RATIO to the
            # dense section — best-of-3 keeps one bad window from
            # deciding it.
            concurrency=8, steps=24, epochs=3, n_chips=n_chips,
            pad_to=1200,  # ~1.2K-char shared preamble + unique tails
        ))
        if sec_8b_long is not None:
            sec_8b_long["model"] = "llama3-8b-byte@4k-paged"

        # Section 3b: 8K context — the capacity the paged pool was built
        # for. The ~7K shared preamble admits once via chunked prefill
        # (segments interleave with live decode, engine/batcher.py
        # _advance_segment) and is then block-shared; each request
        # prefills only its unique tail. Pool sized for 8 full-8K
        # residents (1024 usable pages ≈ 4.3 GB int8 next to 8.5 GB of
        # weights).
        sec_8b_8k = await _section("8b-8k", bench_model(
            LLMConfig(
                model_name="llama3-8b-byte", engine_slots=8,
                engine_chunk=16, engine_speculate=6,
                **{**common, "engine_max_seq": 8192},
                # Page 128 at 8K (round-5 A/B, device-only ms/step:
                # 64→268, 128→243, 256→309): decode here is the paged
                # kernel's per-grid-cell latency, so fewer/bigger pages
                # win until tail-prefill cost overtakes at 256.
                engine_paged_kv=True, engine_page_size=128,
                engine_kv_pages=513,
                engine_kv_quantize="int8",
            ),
            concurrency=8, steps=16, epochs=2, n_chips=n_chips,
            pad_to=7000,
        ))
        if sec_8b_8k is not None:
            sec_8b_8k["model"] = "llama3-8b-byte@8k-paged"

    # Sections 4-5: orchestrator-level numbers (VERDICT r3 next-step 6).
    provider = "tpu" if on_accel else "mock"
    try:
        sec_pipeline = await bench_pipeline(provider=provider)
        _note("pipeline", sec_pipeline)
    except Exception as exc:  # noqa: BLE001 — keep earlier sections
        _note("pipeline FAILED", {"error": str(exc)})
        sec_pipeline = {"pipeline_p50_ms": None, "pipeline_error": str(exc)}
    sec_swarm = None
    if on_accel:
        try:
            sec_swarm = await bench_swarm("protocol-s", "tpu")
            _note("swarm", sec_swarm)
        except Exception as exc:  # noqa: BLE001 — keep earlier sections
            _note("swarm FAILED", {"error": str(exc)})
            sec_swarm = {"swarm_steps_per_sec": None,
                         "swarm_error": str(exc)}

    # Section 6: open-loop SLO harness (ROADMAP item 5) — Poisson + 2x
    # burst arrivals over the multi-tenant mix at ~70% of the 1B
    # section's measured capacity, per-class attainment as the headline.
    sec_slo = None
    try:
        from pilottai_tpu.core.config import ReliabilityConfig

        slo_rate = max(
            1.0, min(0.7 * sec_1b["steps_per_sec_per_chip"] * n_chips, 64.0)
        )
        sec_slo = await bench_slo(
            LLMConfig(
                model_name="llama3-1b-byte" if on_accel else "llama-tiny",
                engine_slots=32, engine_admit_batch=8, engine_chunk=24,
                engine_speculate=4,
                # Shed (429) instead of unbounded queue growth when the
                # burst outruns capacity — sheds land in the SLO ledger
                # as budget burn, which is the point.
                reliability=ReliabilityConfig(max_queue_depth=256),
                **common,
            ),
            rate_rps=round(slo_rate, 1),
            duration_s=30.0 if on_accel else 12.0,
            n_chips=n_chips,
            # Clamp the offered rate to the mix's own measured capacity
            # (ISSUE 19 satellite): the r07 headline printed attainment
            # 0.0 purely from CPU saturation, which the CELL section
            # then contradicted at 0.958.
            derate=True,
        )
        _note("slo", sec_slo)
    except Exception as exc:  # noqa: BLE001 — keep earlier sections
        _note("slo FAILED", {"error": str(exc)})
        sec_slo = {"slo_error": str(exc)}

    # Section 7: scripted single-fault recovery soak (ISSUE 9) — one
    # injected mid-decode device failure against a concurrent greedy
    # wave; the engine's in-flight recovery must complete every request
    # byte-identically (recovered_frac == 1.0 is the acceptance bar).
    sec_recovery = None
    try:
        sec_recovery = await bench_recovery(
            LLMConfig(
                model_name="llama3-1b-byte" if on_accel else "llama-tiny",
                engine_slots=8, engine_chunk=16,
                **common,
            ),
            n_requests=6 if on_accel else 4,
            max_new_tokens=48,
        )
        _note("recovery", sec_recovery)
    except Exception as exc:  # noqa: BLE001 — keep earlier sections
        _note("recovery FAILED", {"error": str(exc)})
        sec_recovery = {"recovery_error": str(exc)}

    # Section 8: global KV cache tier (ISSUE 10) — multi-turn sessions
    # against a deliberately tiny device-resident store, so session
    # resumes exercise the spill→restore path: hit-rate > 0 with
    # restores > 0 means the cold tier served KV that eviction would
    # previously have thrown away.
    sec_kvcache = None
    try:
        sec_kvcache = await bench_kvcache(
            LLMConfig(
                model_name="llama3-1b-byte" if on_accel else "llama-tiny",
                engine_slots=4, engine_chunk=8,
                # Two hot entries vs six sessions: every resume lands
                # after its entry was evicted (and spilled).
                engine_prefix_cache=2,
                engine_kvcache_host_mb=256,
                **common,
            ),
            n_sessions=6 if on_accel else 4,
            turns=3 if on_accel else 2,
        )
        _note("kvcache", sec_kvcache)
    except Exception as exc:  # noqa: BLE001 — keep earlier sections
        _note("kvcache FAILED", {"error": str(exc)})
        sec_kvcache = {"kvcache_error": str(exc)}

    # Section 9: serving cell (ISSUE 11) — 3 in-process replicas behind
    # the KV-affinity router, driven open-loop at ≥10× the single-engine
    # rate measured in section 1. The point is cell behavior under
    # overload: per-class boundary shedding, session affinity, scripted
    # migration + drain.
    sec_cell = None
    try:
        from pilottai_tpu.core.config import ReliabilityConfig

        single_rps = sec_1b["steps_per_sec_per_chip"] * n_chips
        # ≥10× the single-engine rate is the acceptance bar; the cap is
        # only a task-count sanity bound for very fast engines.
        cell_rate = min(10.0 * max(single_rps, 1.0), 1500.0)
        sec_cell = await bench_cell(
            LLMConfig(
                model_name="llama3-1b-byte" if on_accel else "llama-tiny",
                engine_slots=4, engine_chunk=8,
                engine_prefix_cache=2,
                engine_kvcache_host_mb=64,
                reliability=ReliabilityConfig(max_queue_depth=32),
                **common,
            ),
            n_replicas=3,
            rate_rps=round(cell_rate, 1),
            duration_s=20.0 if on_accel else 12.0,
            single_rps=round(single_rps, 2),
            n_chips=n_chips,
        )
        _note("cell", sec_cell)
    except Exception as exc:  # noqa: BLE001 — keep earlier sections
        _note("cell FAILED", {"error": str(exc)})
        sec_cell = {"cell_error": str(exc)}

    # Section 10: DAG-aware scheduler (ISSUE 12 / ROADMAP item 4) — the
    # same fan-out-plus-background workload with the scheduler off then
    # on; straggler_frac and (parent) critical_path_frac must come DOWN
    # with it on. Runs the protocol checkpoint so agents actually
    # complete tasks; greedy on/off parity is pinned by
    # tests/test_sched.py rather than re-measured here.
    sec_sched = None
    try:
        sec_sched = await bench_sched(
            "protocol-s", "tpu" if on_accel else "cpu",
            n_waves=4 if on_accel else 3,
            gang=4 if on_accel else 3,
            n_bg=6 if on_accel else 4,
        )
        _note("sched", sec_sched)
    except Exception as exc:  # noqa: BLE001 — keep earlier sections
        _note("sched FAILED", {"error": str(exc)})
        sec_sched = {"sched_error": str(exc)}

    # Section 11: MULTICHIP (ISSUE 13 / ROADMAP item 1) — the
    # tensor-parallel serving soak on mesh={'model':4,'data':2}: paged
    # KV pool sharded over the model axis, admission replicated over
    # data, per-chip steps/s + per-axis collective attribution + MFU as
    # the FIRST multichip headline since the r01–r05 dryruns. On an
    # accelerator host with ≥8 chips it runs in-process on the real
    # mesh; on CPU it re-execs itself with a forced 8-device host
    # platform (device topology is fixed at jax's first import).
    sec_multichip = None
    try:
        if on_accel and n_chips >= 8:
            sec_multichip = await bench_multichip(
                model_name="llama3-8b-byte",
                provider="tpu",
                mesh_shape={"model": 4, "data": 2},
                concurrency=8, steps=24, epochs=2,
            )
        else:
            loop = asyncio.get_running_loop()
            sec_multichip = await loop.run_in_executor(
                None, _multichip_subprocess
            )
        _note("multichip", sec_multichip)
    except Exception as exc:  # noqa: BLE001 — keep earlier sections
        _note("multichip FAILED", {"error": str(exc)})
        sec_multichip = {"multichip_error": str(exc)}

    # Section 12: QUANT (ISSUE 14 / ROADMAP item 3) — the decode weight
    # stream per quantization mode: bytes/token (measured gauges),
    # steps/s and MFU for int8 vs int4 (plus dense on CPU), with the
    # int4/int8 bytes ratio as the cost headline. The fused greedy
    # epilogue is on per the LLMConfig default, so these numbers are
    # the composed fast path.
    sec_quant = None
    try:
        sec_quant = await bench_quant(on_accel, n_chips=n_chips)
        _note("quant", sec_quant)
    except Exception as exc:  # noqa: BLE001 — keep earlier sections
        _note("quant FAILED", {"error": str(exc)})
        sec_quant = {"quant_error": str(exc)}

    # Section 13: CHAOS (ISSUE 16) — the cross-subsystem chaos soak
    # (scripts/chaos_soak.py): a seeded randomized fault schedule
    # (shard loss + KV corruption + step/prefill faults + latency
    # blips) against a 2-replica serving cell on survivor-ladder
    # meshes. Like MULTICHIP on CPU it needs 8 virtual devices, so it
    # always runs as a fresh subprocess. Invariant headlines:
    # recovered_frac, byte_identity_ok, corruptions detected vs
    # injected, stuck_flights.
    sec_chaos = None
    try:
        loop = asyncio.get_running_loop()
        sec_chaos = await loop.run_in_executor(None, _chaos_subprocess)
        _note("chaos", sec_chaos)
    except Exception as exc:  # noqa: BLE001 — keep earlier sections
        _note("chaos FAILED", {"error": str(exc)})
        sec_chaos = {"chaos_error": str(exc)}

    # Section 14: AUTOCONF (ISSUE 18) — measurement→configuration loop.
    # Knob-candidate sweep over the widened SLO workload (same seed =
    # same recorded arrival trace) feeds the cost model, the profiler's
    # fingerprint weights the recommendation, and a scripted recurring
    # burst drives DynamicScaling forecast-on vs forecast-off. The
    # recommendation + fingerprint also land in the profile store, where
    # the engine's boot divergence check and scripts/recommend.py read
    # them.
    sec_autoconf = None
    try:
        auto_rate = max(
            1.0, min(0.7 * sec_1b["steps_per_sec_per_chip"] * n_chips, 64.0)
        )
        sec_autoconf = await bench_autoconf(
            "llama3-1b-byte" if on_accel else "llama-tiny",
            common,
            rate_rps=round(auto_rate, 1),
            duration_s=12.0 if on_accel else 8.0,
            n_chips=n_chips,
        )
        _note("autoconf", sec_autoconf)
    except Exception as exc:  # noqa: BLE001 — keep earlier sections
        _note("autoconf FAILED", {"error": str(exc)})
        sec_autoconf = {"autoconf_error": str(exc)}

    # Section 15: DISAGG (ISSUE 19) — disaggregated prefill/decode
    # serving: the same sessions+RAG mix against a colocated then a
    # 1p1d 2-replica cell; interference ratio (mixed-phase interactive
    # TPOT p99 / decode-only baseline) per topology, plus handoff
    # success rate and handoff_ms percentiles.
    sec_disagg = None
    try:
        from pilottai_tpu.core.config import ReliabilityConfig

        single_rps = sec_1b["steps_per_sec_per_chip"] * n_chips
        # Below the knee on purpose: at ~0.5x single-engine rate the
        # decode stream alone saturates a 2x2-slot cell, the prefill
        # tier's queue backs up, handoff legs get shed mid-flight and
        # handoff_ms degenerates into queue wait — measuring overload,
        # not the handoff. (The SLO/CELL sections own the saturation
        # story; this one isolates the handoff + interference axes.)
        disagg_rate = max(1.0, min(0.25 * single_rps, 12.0))
        sec_disagg = await bench_disagg(
            LLMConfig(
                model_name="llama3-1b-byte" if on_accel else "llama-tiny",
                # Scarce slots: slot occupancy is the interference axis
                # an in-process cell can demonstrate even where compute
                # isolation can't be (see bench_disagg's caveat).
                engine_slots=2, engine_chunk=8,
                engine_prefix_cache=2,
                engine_kvcache_host_mb=64,
                reliability=ReliabilityConfig(max_queue_depth=32),
                **common,
            ),
            rate_rps=round(disagg_rate, 1),
            prefill_rps=round(max(disagg_rate / 4.0, 0.5), 1),
            duration_s=10.0 if on_accel else 6.0,
            n_chips=n_chips,
        )
        _note("disagg", sec_disagg)
    except Exception as exc:  # noqa: BLE001 — keep earlier sections
        _note("disagg FAILED", {"error": str(exc)})
        sec_disagg = {"disagg_error": str(exc)}

    headline = sec_8b or sec_1b
    out = {
        "metric": "agent_steps_per_sec_per_chip",
        "value": sec_1b["steps_per_sec_per_chip"],
        "unit": "steps/s/chip",
        # ≥ 1.0 ⇔ the north-star model meets the ≤500 ms p50 target.
        "vs_baseline": round(TARGET_P50_MS / headline["p50_step_ms"], 3),
        "p50_step_ms": sec_1b["p50_step_ms"],
        "p50_step_ms_8b": sec_8b["p50_step_ms"] if sec_8b else None,
        "p50_step_ms_8b_long": (
            sec_8b_long["p50_step_ms"] if sec_8b_long else None
        ),
        "p50_step_ms_8b_8k": (
            sec_8b_8k["p50_step_ms"] if sec_8b_8k else None
        ),
        # From the device trace: the device's own sustainable rate and how
        # much of the benchmark wall the device was actually busy
        # (utils/device_profile.py; per-section values under models.*).
        "steps_per_sec_device_only_1b": sec_1b.get(
            "steps_per_sec_device_only"
        ),
        "device_ms_per_step_8b": (
            (sec_8b or {}).get("device_ms_per_step")
        ),
        # Device-feed headline (BENCH_r05: 8b busy_frac 0.65 — ~30% of
        # wall the device waited on the host; r6 target ≥ 0.80):
        "device_busy_frac_8b": (sec_8b or {}).get("device_busy_frac"),
        "device_busy_frac_1b": sec_1b.get("device_busy_frac"),
        "host_gap_p50_ms_8b": (sec_8b or {}).get("host_gap_p50_ms"),
        # Live MFU headlines (ROADMAP item 3 tracks ≥ 0.15 on 8B dense;
        # per-section values + profiler reconciliation under models.*).
        "mfu_1b": sec_1b.get("mfu"),
        "mfu_8b": (sec_8b or {}).get("mfu"),
        # SLO attainment headline (ROADMAP item 5): interactive-class
        # attainment under open-loop Poisson+burst load; full per-class
        # breakdown under SLO.classes.
        "slo_attainment_interactive": (
            (sec_slo.get("classes") or {}).get("interactive", {})
            .get("attainment") if sec_slo else None
        ),
        # Honesty caveat (ISSUE 19 satellite): when the SLO section
        # saturated anyway, the attainment headline above describes
        # queueing collapse, not serving quality.
        "slo_saturated": (
            sec_slo.get("saturated") if sec_slo else None
        ),
        "SLO": sec_slo,
        # Fault-domain headline (ISSUE 9): fraction of fault-interrupted
        # requests that completed anyway (full breakdown under RECOVERY).
        "recovered_frac": (
            sec_recovery.get("recovered_frac") if sec_recovery else None
        ),
        "RECOVERY": sec_recovery,
        # KV cache tier headline (ISSUE 10): session-resume hit rate on
        # the multi-turn workload (full breakdown under KVCACHE).
        "kvcache_prefix_hit_rate": (
            sec_kvcache.get("prefix_hit_rate") if sec_kvcache else None
        ),
        "KVCACHE": sec_kvcache,
        # Serving-cell headlines (ISSUE 11): interactive attainment at
        # ≥10× single-engine offered load, and the affinity hit rate
        # (full breakdown incl. per-class shed + migration/drain under
        # CELL).
        "cell_attainment_interactive": (
            (sec_cell.get("classes") or {}).get("interactive", {})
            .get("attainment") if sec_cell else None
        ),
        "cell_affinity_hit_rate": (
            sec_cell.get("affinity_hit_rate") if sec_cell else None
        ),
        "CELL": sec_cell,
        # DAG-aware scheduler headlines (ISSUE 12): straggler fraction
        # with the scheduler on vs off on the same workload (full
        # on/off blocks under SCHED).
        "sched_straggler_frac_on": (
            (sec_sched.get("on") or {}).get("swarm_straggler_frac")
            if sec_sched else None
        ),
        "sched_straggler_frac_off": (
            (sec_sched.get("off") or {}).get("swarm_straggler_frac")
            if sec_sched else None
        ),
        "SCHED": sec_sched,
        # Multichip serving headlines (ISSUE 13): the first bench round
        # since r05 whose headline is not a single-chip number — per-chip
        # steps/s on mesh={'model':4,'data':2} with the per-axis
        # collective split (full breakdown incl. the single-device
        # reference under MULTICHIP, reordered to the tail below so the
        # driver capture keeps it).
        "multichip_steps_per_sec_per_chip": (
            sec_multichip.get("steps_per_sec_per_chip")
            if sec_multichip else None
        ),
        "multichip_mfu": (
            sec_multichip.get("mfu") if sec_multichip else None
        ),
        "multichip_collective_frac_model": (
            sec_multichip.get("collective_frac_model")
            if sec_multichip else None
        ),
        "multichip_collective_frac_data": (
            sec_multichip.get("collective_frac_data")
            if sec_multichip else None
        ),
        "MULTICHIP": sec_multichip,
        # Weight-quantization headlines (ISSUE 14): 8B int4 MFU on the
        # accel path (None on CPU runs — the CPU QUANT section is
        # plumbing proof on the protocol-s shape) and the measured
        # bytes/token ratio int4 vs int8 (the ≤ 0.55 acceptance axis at
        # 8B; CPU understates it — tiny tied embed, see bench_quant).
        "mfu_8b_quant": (
            ((sec_quant.get("modes") or {}).get("int4") or {}).get("mfu")
            if sec_quant and on_accel else None
        ),
        "quant_bytes_per_token_ratio": (
            sec_quant.get("bytes_per_token_int4_vs_int8")
            if sec_quant else None
        ),
        "QUANT": sec_quant,
        # Chaos-soak headlines (ISSUE 16): every request survived the
        # fault schedule, every probe wave stayed byte-identical, and
        # every injected corruption was detected (full schedule +
        # invariant breakdown under CHAOS).
        "chaos_recovered_frac": (
            sec_chaos.get("recovered_frac") if sec_chaos else None
        ),
        "chaos_byte_identity_ok": (
            sec_chaos.get("byte_identity_ok") if sec_chaos else None
        ),
        "CHAOS": sec_chaos,
        # Auto-configuration headlines (ISSUE 18): cost-model-recommended
        # vs default knob vector on the SAME recorded workload (weighted
        # interactive+batch attainment — the recommendation's own score
        # axis), and the measured seconds of lead the arrival forecast
        # bought before the scripted burst (full sweep + forecast on/off
        # blocks under AUTOCONF).
        "autoconf_attainment_recommended": (
            ((sec_autoconf.get("recommendation") or {}).get("score") or {})
            .get("attainment") if sec_autoconf else None
        ),
        "autoconf_attainment_default": (
            ((sec_autoconf.get("recommendation") or {})
             .get("default_score") or {})
            .get("attainment") if sec_autoconf else None
        ),
        "autoconf_forecast_lead_s": (
            sec_autoconf.get("forecast_lead_s") if sec_autoconf else None
        ),
        "AUTOCONF": sec_autoconf,
        # Disaggregated-serving headlines (ISSUE 19): the decode-tier
        # interference ratio for each topology (disagg must hold closer
        # to 1.0), handoff success and the handoff wall (full per-phase
        # breakdown under DISAGG).
        "disagg_tpot_interference": (
            (sec_disagg.get("disagg") or {}).get("tpot_interference")
            if sec_disagg else None
        ),
        "colocated_tpot_interference": (
            (sec_disagg.get("colocated") or {}).get("tpot_interference")
            if sec_disagg else None
        ),
        "disagg_handoff_success": (
            (sec_disagg.get("disagg") or {}).get("handoff_success")
            if sec_disagg else None
        ),
        "disagg_handoff_ms_p99": (
            (sec_disagg.get("disagg") or {}).get("handoff_ms_p99")
            if sec_disagg else None
        ),
        "DISAGG": sec_disagg,
        **sec_pipeline,
        **(sec_swarm or {}),
        # Orchestrator-path phase percentiles: traffic since the last
        # engine section's reset — i.e. the pipeline + swarm sections
        # (per engine-section values live under models.*.phases).
        "phases": phase_summary(),
        "provider": "tpu" if on_accel else "cpu",
        "n_chips": n_chips,
        "models": {
            sec_1b["model"]: sec_1b,
            **({sec_8b["model"]: sec_8b} if sec_8b else {}),
            **({sec_8b_long["model"]: sec_8b_long} if sec_8b_long else {}),
            **({sec_8b_8k["model"]: sec_8b_8k} if sec_8b_8k else {}),
        },
    }
    # The driver captures the LAST 2,000 bytes of output: the
    # orchestrator headline (pipeline/swarm success — or the error that
    # replaced it when a section failed) must be the final keys or the
    # big `models` dict truncates it away — the round-5 12/12 and 96/96
    # claims were unverifiable from BENCH_r05.json for exactly this
    # reason (VERDICT r5 next-step 3a).
    for key in (
        # Multichip headlines ride the tail too (ISSUE 13): the MULTICHIP
        # block is small and the driver's 2,000-byte window must keep it
        # — the whole point of the round is a non-single-chip headline.
        "MULTICHIP",
        "multichip_steps_per_sec_per_chip", "multichip_mfu",
        "multichip_collective_frac_model", "multichip_collective_frac_data",
        # QUANT headlines (ISSUE 14): the round's point is the decode
        # roofline — the per-mode block and both scalar headlines must
        # survive the driver's 2,000-byte tail window.
        "QUANT", "mfu_8b_quant", "quant_bytes_per_token_ratio",
        # AUTOCONF headlines (ISSUE 18): recommended-vs-default and the
        # forecast lead are the round's point — keep them in the tail
        # window (the big AUTOCONF block itself stays mid-payload; the
        # scalars are what the driver must see).
        "autoconf_attainment_recommended", "autoconf_attainment_default",
        "autoconf_forecast_lead_s",
        # DISAGG headlines (ISSUE 19): the round's point is the
        # interference split — both topology ratios, the handoff health
        # scalars and the (small) DISAGG block ride the tail so the
        # driver's 2,000-byte window keeps them.
        "DISAGG",
        "disagg_tpot_interference", "colocated_tpot_interference",
        "disagg_handoff_success", "disagg_handoff_ms_p99",
        "slo_saturated",
        "pipeline_error", "swarm_error", "pipeline_success", "swarm_success",
    ):
        if key in out:
            out[key] = out.pop(key)
    print(json.dumps(out))


if __name__ == "__main__":
    if "--multichip" in sys.argv[1:]:
        asyncio.run(run_multichip_cli())
    else:
        asyncio.run(run_bench())
    sys.exit(_exit_code())
