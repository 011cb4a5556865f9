#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one chip (default), the entry points a user would call:

1. ``logits``  — the engine's jitted prefill + cached decode steps at
   Llama-3-8B's published widths (depth cut to ``LOGIT_LAYERS`` for this
   comparison alone; every width untouched), against an independent plain
   float32 forward of the same seeded, dequantised parameters.
2. ``serve-4k`` — ``pilottai-tpu serve --model llama3-8b --provider tpu
   --quantize int8 --max-seq 4096 --kv-quantize int8 --speculate 6`` built
   with the CLI's own parser and driven through ``cli.run_serve``: all 32
   layers, vocabulary 128,256, untied head; a few ``POST
   /v1/chat/completions`` over loopback, each response checked.
3. ``serve-dense`` — the same model and entry point at ``--max-seq 512``
   (dense cache, no paging: the repo's old north-star shape).
4. ``orchestrator`` — ``Serve`` with a manager and workers on the shipped
   ``protocol-s`` checkpoint executes tasks through ``Serve.execute_task``.

After each engine the fault / recovery / retry counters must all be zero:
recovery is a feature, in a smoke it is a masked failure.

``--chips 4`` runs ONLY the tensor-parallel phase (the driver never passes
it): llama3-1b single device vs ``mesh_shape={"model": 4}`` logits, then
llama3-8b in bf16 — which one chip cannot hold — served on four.

The last line of stdout is the contract's JSON object and nothing else.
Any failed phase, a device that is not a TPU, or an unexpected exception
exits non-zero with ``"ok": false``.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import http.client
import json
import logging
import sys
import time
import traceback
from typing import Any, Dict, List, Optional, Sequence, Tuple

SEED = 0

# --- phase 1 -----------------------------------------------------------
LOGIT_MODEL = "llama3-8b"
LOGIT_LAYERS = 2          # depth cut for the comparison alone; widths full
LOGIT_PROMPT = (
    "Plan the next step for the inventory agent and answer in one line."
)
LOGIT_DECODE_STEPS = 4
# Tolerance on relative RMS error of the logits (||engine - ref|| / ||ref||
# over every compared row) against the float32 "highest" reference.
#
# What the CLI promises is weight-only int8 with bf16 activations: the
# weights in the reference are the SAME int8 values dequantised, so the only
# error left is bf16 rounding of activations (unit roundoff 2^-9 per
# rounding, a handful of roundings per layer, fp32 accumulation). At this
# depth and these widths that measures 0.60-0.65% (host run of this very
# function, PR 21). Rounding the activations to 8 bits instead (per-row
# absmax/127 steps — the integer-operand arm of models/qmatmul.py) costs
# ~1% PER MATMUL INPUT and measures 2.4-2.6% here. 1.2% sits between the
# two with a factor of two on either side: anything less precise than
# "weight-only int8, bf16 activations" fails, and the promised precision
# passes with room for the chip's own rounding order.
LOGIT_REL_RMS_TOL = 0.012

# --- phase 2 -----------------------------------------------------------
SERVE_4K_ARGV = [
    "serve", "--model", "llama3-8b", "--provider", "tpu",
    "--quantize", "int8", "--max-seq", "4096", "--kv-quantize", "int8",
    "--speculate", "6",
    # Cut to fit one 16 GB chip and the 1200 s limit — never a width:
    # 12 slots is the smallest count at which the 4096 bound leaves the
    # XLA gather for the Pallas paged kernel (engine/batcher.py gather
    # budget: slots x bound > 40960); one decode block per dispatch keeps
    # the warm-up grid at one executable per prefix bound (each 32-layer
    # step program takes ~35 s to compile; the default ladder of four
    # chunk sizes would make it 24 of them).
    "--slots", "12", "--chunk", "1",
    "--host", "127.0.0.1", "--port", "0",
]
# The repo's old north-star shape: dense cache, no paging. Its prompts stay
# under the dense prefix store's 64-token floor, so the streamed repeat is a
# full prefill like the unary one (a prefix hit would admit through the
# tail path: same answer on real weights, not on random near-flat logits).
SERVE_DENSE_ARGV = [
    "serve", "--model", "llama3-8b", "--provider", "tpu",
    "--quantize", "int8", "--max-seq", "512", "--speculate", "6",
    "--slots", "8", "--chunk", "1", "--host", "127.0.0.1", "--port", "0",
]
DENSE_PROMPT = "Name three release checks."
SHORT_PROMPT = "List three checks before shipping a release."
MAX_TOKENS = 24
# ~3.8K byte tokens, > 2 x 1024: admitted in three 1024-token segments
# plus a tail (engine/batcher.py chunked prefill) — the ladder warm-up
# compiled — and its decode runs at the 4096 prefix bound.
LONG_PROMPT = " ".join(
    f"step {i}: verify shard {i % 17} and record the checksum." for i in range(76)
)

# --- phase 3 -----------------------------------------------------------
ORCH_TASKS = [
    "check inventory 42 and report the result",
    "check inventory 7 and report the result",
    "summarize the status of order 1138",
]

# Counters/gauges that must read zero after a healthy run (§1.4).
HEAL_PREFIXES = ("engine.faults.", "engine.rebuilds", "engine.shed")
HEAL_COUNTERS = (
    "engine.recovered_requests", "engine.recovery_requeued",
    "engine.recovery_failed", "engine.errors", "engine.poisoned",
    "engine.expired",
)
HEAL_GAUGES = ("engine.degrade_level", "engine.mesh_plan")


def say(msg: str) -> None:
    print(msg, flush=True)


def final_line(ok: bool, device: Optional[Dict[str, Any]], **extra: Any) -> str:
    """The contract's last line. ``ok: true`` carries the device and
    nothing else; a failure adds what failed."""
    out: Dict[str, Any] = {"ok": bool(ok), "device": device}
    if not ok:
        out.update(extra)
    return json.dumps(out)


def device_report() -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


class PhaseFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


# ---------------------------------------------------------------------- #
# Healing counters
# ---------------------------------------------------------------------- #

def healed(
    snapshot: Dict[str, Any],
    handler_metrics: Optional[Dict[str, Any]] = None,
    baseline: Optional[Dict[str, Any]] = None,
) -> List[str]:
    """Every way the run repaired itself, from a ``global_metrics``
    snapshot (+ a handler's ``get_metrics()`` for its breaker). Counters
    are read against ``baseline`` (the registry is process-wide: what an
    earlier engine in this process counted is not this phase's). Empty =
    the chip did the work the first time."""
    bad: List[str] = []
    before = (baseline or {}).get("counters", {})
    gauges = snapshot.get("gauges", {})
    for name, total in sorted(snapshot.get("counters", {}).items()):
        val = total - before.get(name, 0.0)
        if val and (name in HEAL_COUNTERS or name.startswith(HEAL_PREFIXES)):
            bad.append(f"{name}={val:g}")
    for name in HEAL_GAUGES:
        if gauges.get(name):
            bad.append(f"{name}={gauges[name]:g}")
    breaker = (handler_metrics or {}).get("breaker")
    if breaker and (
        breaker.get("state") != "closed" or breaker.get("consecutive_failures")
    ):
        bad.append(f"breaker={breaker}")
    return bad


def metrics_baseline() -> Dict[str, Any]:
    from pilottai_tpu.utils.metrics import global_metrics

    return global_metrics.snapshot()


def check_not_healed(
    label: str, baseline: Dict[str, Any],
    handler_metrics: Optional[Dict[str, Any]] = None,
) -> None:
    from pilottai_tpu.utils.metrics import global_metrics

    bad = healed(global_metrics.snapshot(), handler_metrics, baseline)
    check(not bad, f"{label}: the run healed itself: {', '.join(bad)}")
    say(f"[{label}] fault/recovery/retry counters all zero, breaker closed")


# ---------------------------------------------------------------------- #
# Phase 1: logits against an independent reference
# ---------------------------------------------------------------------- #

def reference_logits(params: Dict[str, Any], cfg: Any, tokens: Any) -> Any:
    """Plain full-sequence forward, independent of the engine's code:
    XLA attention, no cache, weights dequantised, float32 at "highest"
    matmul precision. ``tokens`` [T] → logits [T, V]."""
    import jax
    import jax.numpy as jnp

    def f32(w: Any) -> Any:
        if hasattr(w, "q"):  # QTensor: int8 values x per-channel scales
            return w.q.astype(jnp.float32) * w.s.astype(jnp.float32)
        return w.astype(jnp.float32)

    def norm(x: Any, scale: Any) -> Any:
        return x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + cfg.rms_eps
        ) * f32(scale)

    T = tokens.shape[0]
    N, K, H = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    half = H // 2
    freqs = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]

    def rope(v: Any) -> Any:  # [T, heads, H], rotate-half
        v1, v2 = v[..., :half], v[..., half:]
        return jnp.concatenate([v1 * cos - v2 * sin, v2 * cos + v1 * sin], -1)

    causal = jnp.tril(jnp.ones((T, T), bool))
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"])[tokens]
        for l in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[l], params["layers"])
            h = norm(x, lp["ln1"]["scale"])
            q = rope((h @ f32(lp["attn"]["wq"])).reshape(T, N, H))
            k = rope((h @ f32(lp["attn"]["wk"])).reshape(T, K, H))
            v = (h @ f32(lp["attn"]["wv"])).reshape(T, K, H)
            k = jnp.repeat(k, N // K, axis=1)  # GQA: head n reads kv n // G
            v = jnp.repeat(v, N // K, axis=1)
            s = jnp.einsum("tnh,snh->nts", q, k) * H ** -0.5
            p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
            attn = jnp.einsum("nts,snh->tnh", p, v).reshape(T, N * H)
            x = x + attn @ f32(lp["attn"]["wo"])
            h = norm(x, lp["ln2"]["scale"])
            gate = jax.nn.silu(h @ f32(lp["mlp"]["wg"]))
            x = x + (gate * (h @ f32(lp["mlp"]["wu"]))) @ f32(lp["mlp"]["wd"])
        x = norm(x, params["final_norm"]["scale"])
        head = (
            f32(params["lm_head"]) if "lm_head" in params
            else f32(params["embed"]).T
        )
        return x @ head


def engine_logits(
    params: Dict[str, Any], cfg: Any, prompt_ids: Sequence[int],
    next_ids: Sequence[int], use_flash: bool, flash_mesh: Any = None,
) -> Any:
    """The engine's own jitted prefill (flash on the chip) and cached
    single-token decode steps, teacher-forced on ``next_ids``, on
    whatever devices ``params`` live on (jit follows their shardings).
    One row of logits per input token: [len(prompt) + len(next), V]."""
    import jax.numpy as jnp
    import numpy as np

    from pilottai_tpu.models.transformer import forward_decode, forward_prefill
    from pilottai_tpu.ops.kvcache import KVCache, write_prompts

    n = len(prompt_ids)
    T = 64
    while T < n:
        T *= 2
    tokens = np.zeros((1, T), np.int32)
    tokens[0, :n] = prompt_ids
    positions = jnp.arange(T, dtype=jnp.int32)[None]
    valid = jnp.asarray([n], jnp.int32)
    logits_p, ks, vs = forward_prefill(
        params, cfg, jnp.asarray(tokens), positions, valid,
        use_flash=use_flash, flash_mesh=flash_mesh,
    )
    cache = KVCache.create(
        cfg.n_layers, 1, T + 64, cfg.n_kv_heads, cfg.head_dim, dtype=cfg.dtype
    )
    cache = write_prompts(cache, jnp.asarray([0], jnp.int32), ks, vs, valid)
    rows = [logits_p[0, :n]]
    active = jnp.asarray([True])
    for tok in next_ids:
        logits_d, cache = forward_decode(
            params, cfg, jnp.asarray([tok], jnp.int32), cache, active
        )
        rows.append(logits_d)
    return jnp.concatenate(rows, axis=0)


def rel_rms(a: Any, b: Any) -> float:
    """||a - b|| / ||b||, on the host (the two may live on different
    devices)."""
    import numpy as np

    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


@contextlib.contextmanager
def _qmatmul_arm(mode: str):
    """Trace under ``PILOTTAI_QMATMUL=<mode>``: the arm is read at trace
    time, so jit's caches are dropped on the way in and out."""
    import os

    import jax

    prev = os.environ.get("PILOTTAI_QMATMUL")
    os.environ["PILOTTAI_QMATMUL"] = mode
    jax.clear_caches()
    try:
        yield
    finally:
        if prev is None:
            del os.environ["PILOTTAI_QMATMUL"]
        else:
            os.environ["PILOTTAI_QMATMUL"] = prev
        jax.clear_caches()


def logits_phase(
    model: str, layers: int, quantize: bool, dtype: str, on_tpu: bool,
    tol: float = LOGIT_REL_RMS_TOL,
) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    from pilottai_tpu.engine.tokenizer import ByteTokenizer
    from pilottai_tpu.models.common import init_params
    from pilottai_tpu.models.qmatmul import native_quant_matmul_ok
    from pilottai_tpu.models.registry import get_model_config

    full = get_model_config(model)
    cfg = full.replace(
        n_layers=min(layers, full.n_layers),
        dtype=jnp.bfloat16 if dtype == "bfloat16" else jnp.float32,
    )
    say(
        f"[logits] {model}: hidden {cfg.hidden_size}, heads {cfg.n_heads}/"
        f"{cfg.n_kv_heads}x{cfg.head_dim}, ffn {cfg.intermediate_size}, "
        f"vocab_size {cfg.vocab_size}, tie_embeddings {cfg.tie_embeddings}; "
        f"depth cut {full.n_layers}->{cfg.n_layers} for this comparison "
        f"alone; weights {'int8' if quantize else dtype}, activations {dtype}; "
        f"qmatmul arm: {'native int8 x int8' if quantize and native_quant_matmul_ok() else 'fused dequant'}"
    )
    params = init_params(cfg, jax.random.PRNGKey(SEED), quantize=quantize)
    prompt_ids = ByteTokenizer().encode(LOGIT_PROMPT)
    ref_fn = jax.jit(reference_logits, static_argnums=(1,))

    # Teacher-force the decode steps on the reference's own greedy tokens.
    ids = list(prompt_ids)
    for _ in range(LOGIT_DECODE_STEPS):
        ref = ref_fn(params, cfg, jnp.asarray(ids, jnp.int32))
        ids.append(int(jnp.argmax(ref[-1])))
    next_ids = ids[len(prompt_ids):]
    ref = ref_fn(params, cfg, jnp.asarray(ids[:-1], jnp.int32))
    got = engine_logits(params, cfg, prompt_ids, next_ids[:-1], use_flash=on_tpu)
    check(got.shape == ref.shape, f"logits shape {got.shape} != {ref.shape}")
    check(bool(jnp.isfinite(got).all()), "engine logits are not finite")
    n = len(prompt_ids)
    out = {
        "prefill_rel_rms": rel_rms(got[:n], ref[:n]),
        "decode_rel_rms": rel_rms(got[n:], ref[n:]),
        "max_abs_err": float(jnp.max(jnp.abs(got - ref))),
        "argmax_agree": float(
            jnp.mean(jnp.argmax(got, -1) == jnp.argmax(ref, -1))
        ),
    }
    say(
        f"[logits] {n} prefill rows + {got.shape[0] - n} cached decode rows x "
        f"V={got.shape[1]}: rel RMS err prefill {out['prefill_rel_rms']:.5f}, "
        f"decode {out['decode_rel_rms']:.5f} (tolerance {tol}); max |err| "
        f"{out['max_abs_err']:.4f}; argmax agreement {out['argmax_agree']:.3f}"
    )
    check(
        out["prefill_rel_rms"] <= tol and out["decode_rel_rms"] <= tol,
        f"logits disagree with the float32 reference beyond {tol}: {out}",
    )
    if quantize and not native_quant_matmul_ok():
        # For the record, not a check: the integer-operand arm that used to
        # be the chip's default, held to the same reference.
        with _qmatmul_arm("native"):
            nat = engine_logits(
                params, cfg, prompt_ids, next_ids[:-1], use_flash=on_tpu
            )
        out["native_prefill_rel_rms"] = rel_rms(nat[:n], ref[:n])
        out["native_decode_rel_rms"] = rel_rms(nat[n:], ref[n:])
        worst = max(out["native_prefill_rel_rms"], out["native_decode_rel_rms"])
        say(
            f"[logits] for the record, PILOTTAI_QMATMUL=native (int8 "
            f"activations x int8 weights, not the default): rel RMS err "
            f"prefill {out['native_prefill_rel_rms']:.5f}, decode "
            f"{out['native_decode_rel_rms']:.5f} — "
            f"{'beyond' if worst > tol else 'within'} the tolerance"
        )
    return out


# ---------------------------------------------------------------------- #
# Phase 2: the engine over HTTP through cli.run_serve
# ---------------------------------------------------------------------- #

class _LogTap(logging.Handler):
    """Keeps the engine's boot lines (device loop, strip autotune)."""

    def __init__(self) -> None:
        super().__init__(level=logging.INFO)
        self.lines: List[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        try:
            self.lines.append(record.getMessage())
        except Exception:  # noqa: BLE001 — a log line must not fail the run
            pass


def _post(port: int, body: Dict[str, Any], timeout: float = 600.0) -> Tuple[int, Dict[str, Any]]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(
            "POST", "/v1/chat/completions", json.dumps(body),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"{}")
    finally:
        conn.close()


def _post_stream(port: int, body: Dict[str, Any], timeout: float = 600.0) -> Tuple[int, str, int]:
    """SSE: (status, concatenated content deltas, completion_tokens)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(
            "POST", "/v1/chat/completions", json.dumps({**body, "stream": True}),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        text, tokens = [], 0
        for raw in resp:
            line = raw.decode("utf-8").strip()
            if not line.startswith("data:"):
                continue
            data = line[5:].strip()
            if data == "[DONE]":
                break
            event = json.loads(data)
            check("error" not in event, f"stream error event: {event}")
            for choice in event.get("choices", []):
                text.append(choice.get("delta", {}).get("content") or "")
            tokens = event.get("usage", {}).get("completion_tokens", tokens)
        return resp.status, "".join(text), tokens
    finally:
        conn.close()


def _get(port: int, path: str) -> Dict[str, Any]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60.0)
    try:
        conn.request("GET", path)
        return json.loads(conn.getresponse().read())
    finally:
        conn.close()


def _chat(prompt: str, **extra: Any) -> Dict[str, Any]:
    return {
        "messages": [{"role": "user", "content": prompt}],
        "max_tokens": MAX_TOKENS, "temperature": 0.0, **extra,
    }


def _check_unary(label: str, status: int, body: Dict[str, Any]) -> str:
    check(status == 200, f"{label}: HTTP {status}: {body}")
    n = body.get("usage", {}).get("completion_tokens", 0)
    check(n > 0, f"{label}: completion_tokens {n}")
    content = body["choices"][0]["message"]["content"]
    say(f"[serve] {label}: 200, {body['usage']['prompt_tokens']} prompt tokens, "
        f"{n} completion tokens")
    return content


async def serve_phase(
    argv: Sequence[str], label: str, expect_vocab: int, expect_tied: bool,
    expect_pallas: Optional[bool] = None, long_prompt: Optional[str] = None,
    prompt: str = SHORT_PROMPT,
) -> Dict[str, Any]:
    """Drive ``cli.run_serve`` exactly as ``pilottai-tpu <argv>`` would and
    answer a few requests over loopback HTTP."""
    from pilottai_tpu import cli
    from pilottai_tpu.utils.compile_cache import cache_hits, default_cache_dir
    from pilottai_tpu.utils.metrics import global_metrics

    args = cli._build_parser().parse_args(list(argv))
    say(f"[{label}] pilottai-tpu {' '.join(argv)}")
    from pilottai_tpu.utils.logging import get_logger

    get_logger("chip_smoke")  # the framework's logging set-up runs once,
    tap = _LogTap()           # at the first logger, and resets handlers
    root = logging.getLogger("pilottai_tpu")
    root.addHandler(tap)
    ready, stop = asyncio.Event(), asyncio.Event()
    baseline = metrics_baseline()
    hits0 = cache_hits()
    t0 = time.perf_counter()
    task = asyncio.create_task(cli.run_serve(args, ready, stop))
    info: Dict[str, Any] = {}
    try:
        waiter = asyncio.create_task(ready.wait())
        done, _ = await asyncio.wait(
            {task, waiter}, return_when=asyncio.FIRST_COMPLETED
        )
        if task in done:  # run_serve ended before it was ready: it failed
            waiter.cancel()
            task.result()
            raise PhaseFailed(f"{label}: run_serve returned before ready")
        info["engine_up_s"] = time.perf_counter() - t0
        info["compile_cache_hits"] = cache_hits() - hits0
        say(f"[{label}] engine up in {info['engine_up_s']:.1f}s; compile cache "
            f"{default_cache_dir()} hits {info['compile_cache_hits']}")
        port = args._bound_port
        boot = [l for l in tap.lines if "device loop starting" in l]
        strip = [l for l in tap.lines if l.startswith("paged strip")]
        for line in boot + strip:
            say(f"[{label}] log: {line}")
        if expect_pallas is not None:
            check(
                any(f"pallas={expect_pallas}" in l for l in boot),
                f"{label}: device loop line does not say pallas={expect_pallas}: {boot}",
            )
        kernel0 = global_metrics.get("engine.paged_chunks.kernel")

        t_req = time.perf_counter()
        unary = _check_unary(
            "unary", *await asyncio.to_thread(_post, port, _chat(prompt))
        )
        status, streamed, n_stream = await asyncio.to_thread(
            _post_stream, port, _chat(prompt)
        )
        check(status == 200, f"stream: HTTP {status}")
        check(n_stream > 0, f"stream: completion_tokens {n_stream}")
        check(
            streamed == unary,
            f"stream != unary for the same greedy prompt: {streamed!r} vs {unary!r}",
        )
        say(f"[serve] stream: 200, {n_stream} completion tokens, "
            f"concatenation equals the unary answer")
        body = _check_unary("json", *await asyncio.to_thread(
            _post, port,
            _chat("Reply with a JSON object describing one task.",
                  response_format={"type": "json_object"}),
        ))
        try:
            json.loads(body)
        except ValueError:
            raise PhaseFailed(f"json: body does not parse: {body!r}") from None
        say(f"[serve] json: body parses ({len(body)} chars)")
        pair = await asyncio.gather(*[
            asyncio.to_thread(_post, port, _chat(f"{prompt} ({i})"))
            for i in range(2)
        ])
        for i, (st, bd) in enumerate(pair):
            _check_unary(f"concurrent[{i}]", st, bd)
        if long_prompt is not None:
            seg0 = global_metrics.get("engine.prefill_segments")
            st, bd = await asyncio.to_thread(_post, port, _chat(long_prompt))
            _check_unary("long", st, bd)
            check(
                bd["usage"]["prompt_tokens"] > 2048,
                f"long: only {bd['usage']['prompt_tokens']} prompt tokens",
            )
            segs = global_metrics.get("engine.prefill_segments") - seg0
            check(segs >= 2, f"long: admitted in {segs:g} segments, expected >= 2")
            check(
                bd["usage"]["prompt_tokens"] + MAX_TOKENS < 4096,
                "long: prompt does not fit --max-seq 4096 whole",
            )
            say(f"[serve] long: admitted in {segs:g} segments + tail")
        info["requests_s"] = time.perf_counter() - t_req

        metrics = await asyncio.to_thread(_get, port, "/metrics")
        backend = metrics["handler"]["backend"]
        info["vocab_size"] = backend.get("vocab_size")
        info["tie_embeddings"] = backend.get("tie_embeddings")
        info["quant"] = backend.get("quant")
        info["page_strip"] = backend.get("page_strip")
        info["strip_from_store"] = any("autotune cache" in l for l in strip)
        say(f"[{label}] served model {backend.get('model')}: vocab_size "
            f"{info['vocab_size']}, tie_embeddings {info['tie_embeddings']}, "
            f"backend.quant {info['quant']}, " + (
                "dense cache (no page strip)" if info["page_strip"] is None
                else f"page strip {info['page_strip']} "
                f"({'from the autotune store' if info['strip_from_store'] else 'timed now'})"
            ))
        check(info["vocab_size"] == expect_vocab,
              f"{label}: served vocab {info['vocab_size']} != {expect_vocab}")
        check(info["tie_embeddings"] == expect_tied,
              f"{label}: tie_embeddings {info['tie_embeddings']} != {expect_tied}")
        if expect_pallas:
            kernel = global_metrics.get("engine.paged_chunks.kernel") - kernel0
            gather = global_metrics.get("engine.paged_chunks.gather")
            say(f"[{label}] paged decode chunks after warm-up: {kernel:g} "
                f"through the Pallas kernel ({gather:g} gather chunks in total)")
            check(kernel > 0, f"{label}: no decode chunk went through the Pallas kernel")
        check_not_healed(label, baseline, metrics["handler"])
        say(f"[{label}] requests took {info['requests_s']:.1f}s")
    finally:
        stop.set()
        with contextlib.suppress(asyncio.CancelledError):
            await task
        root.removeHandler(tap)
    return info


# ---------------------------------------------------------------------- #
# Phase 3: the orchestrator
# ---------------------------------------------------------------------- #

async def orchestrator_phase(provider: str, n_agents: int = 2) -> None:
    from pilottai_tpu.core.agent import BaseAgent
    from pilottai_tpu.core.config import (
        AgentConfig, LLMConfig, SamplingConfig, ServeConfig,
    )
    from pilottai_tpu.engine.handler import LLMHandler
    from pilottai_tpu.serve import Serve
    from pilottai_tpu.train.protocol import (
        DEFAULT_CHECKPOINT, SERVE_MAX_NEW, SERVE_MAX_SEQ, has_checkpoint,
    )

    check(has_checkpoint(), f"no protocol-s checkpoint at {DEFAULT_CHECKPOINT}")
    baseline = metrics_baseline()
    t0 = time.perf_counter()
    llm = LLMHandler(LLMConfig(
        model_name="protocol-s", provider=provider,
        checkpoint_path=str(DEFAULT_CHECKPOINT),
        engine_slots=n_agents, engine_max_seq=SERVE_MAX_SEQ, engine_chunk=16,
        dtype="float32",
        sampling=SamplingConfig(temperature=0.0, max_new_tokens=SERVE_MAX_NEW),
    ))
    serve = Serve(
        name="chip-smoke",
        manager_llm=llm,
        agents=[
            BaseAgent(
                config=AgentConfig(
                    role=f"worker{i}", specializations=["generic"],
                    max_iterations=2,
                ),
                llm=llm,
            )
            for i in range(n_agents)
        ],
        config=ServeConfig(
            decomposition_enabled=False, max_concurrent_tasks=n_agents,
        ),
    )
    try:
        await llm.start()
        say(f"[orchestrator] protocol-s ({provider}) engine up in "
            f"{time.perf_counter() - t0:.1f}s")
        await serve.start()
        results = await asyncio.gather(
            *[serve.execute_task(t) for t in ORCH_TASKS]
        )
        for text, res in zip(ORCH_TASKS, results):
            check(res.success, f"task {text!r} failed: {res.error}")
            say(f"[orchestrator] task ok: {text!r}")
        check_not_healed("orchestrator", baseline, llm.get_metrics())
    finally:
        await serve.stop()
        await llm.stop()


# ---------------------------------------------------------------------- #
# Four chips: tensor-parallel serving (run by the builder, --chips 4)
# ---------------------------------------------------------------------- #

# Rel RMS, model=4 vs one device. Both sides are bf16: each is off exact
# arithmetic by ~1.7% at llama3-1b's 16 layers (0.6% at depth 2, growing
# with sqrt(depth)), and sharding moves the rounding points — every shard's
# partial product of a row-parallel matmul rounds to bf16 before the
# all-reduce — so the two differ by about as much as either errs: 1.75%
# measured on four virtual host devices at these widths (PR 21). 4% is
# twice that. A wrong shard, a missing reduce or a head reading another
# shard's KV is an error of order 100%.
TP_TOL = 0.04
TP_BYTES_BAND = (0.20, 0.30)  # each chip's bytes_in_use / total: a quarter
               # +- a fifth of it. Weights (embedding included) and KV heads
               # shard four ways; only norms and per-slot vectors replicate.


def tp_logits_phase(model: str = "llama3-1b") -> None:
    """(a) one device vs mesh {"model": 4}: the same seeded parameters,
    the engine's prefill + a few cached decode steps, logits compared."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pilottai_tpu.engine.tokenizer import ByteTokenizer
    from pilottai_tpu.models.common import init_params, param_logical_axes
    from pilottai_tpu.models.registry import get_model_config
    from pilottai_tpu.parallel.mesh import MeshConfig, create_mesh
    from pilottai_tpu.parallel.sharding import shard_params

    cfg = get_model_config(model).replace(dtype=jnp.bfloat16)
    say(f"[tp-logits] {model}: hidden {cfg.hidden_size}, layers {cfg.n_layers}, "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads}x{cfg.head_dim}, vocab_size "
        f"{cfg.vocab_size}, tie_embeddings {cfg.tie_embeddings}, bf16")
    devices = jax.devices()
    with jax.default_device(devices[0]):
        params = init_params(cfg, jax.random.PRNGKey(SEED))
    params = jax.device_put(params, devices[0])
    prompt_ids = ByteTokenizer().encode(LOGIT_PROMPT)
    rng = np.random.default_rng(SEED)
    next_ids = [int(t) for t in rng.integers(0, 256, LOGIT_DECODE_STEPS)]
    single = engine_logits(params, cfg, prompt_ids, next_ids, use_flash=True)
    mesh = create_mesh(MeshConfig(model=4), devices[:4])
    sharded_params = shard_params(params, param_logical_axes(cfg), mesh)
    sharded = engine_logits(
        sharded_params, cfg, prompt_ids, next_ids, use_flash=True,
        flash_mesh=mesh,
    )
    n = len(prompt_ids)
    err_p = rel_rms(sharded[:n], single[:n])
    err_d = rel_rms(sharded[n:], single[n:])
    say(f"[tp-logits] model=4 vs one device: rel RMS err prefill {err_p:.5f}, "
        f"decode {err_d:.5f} (tolerance {TP_TOL})")
    check(bool(jnp.isfinite(sharded).all()), "sharded logits are not finite")
    check(err_p <= TP_TOL and err_d <= TP_TOL,
          f"model=4 logits disagree with one device beyond {TP_TOL}")


async def tp_serve_phase(model: str = "llama3-8b", provider: str = "tpu") -> None:
    """(b) the model in bf16 — more weight bytes than one chip holds —
    served on mesh {"model": 4} through LLMHandler. (``provider="cpu"``
    is the rehearsal on virtual devices: no memory stats there.)"""
    from pilottai_tpu.core.config import LLMConfig
    from pilottai_tpu.engine.handler import LLMHandler
    from pilottai_tpu.engine.types import GenerationParams
    from pilottai_tpu.models.registry import get_model_config

    cfg = get_model_config(model)
    say(f"[tp-serve] {model} bf16 on mesh model=4: vocab_size {cfg.vocab_size}, "
        f"tie_embeddings {cfg.tie_embeddings}, "
        f"{cfg.param_count() * 2 / 1e9:.1f} GB of weights")
    baseline = metrics_baseline()
    t0 = time.perf_counter()
    handler = LLMHandler(LLMConfig(
        model_name=model, provider=provider, mesh_shape={"model": 4},
        engine_slots=4, engine_max_seq=512, engine_chunk=1, seed=SEED,
    ))
    try:
        await handler.start()
        say(f"[tp-serve] engine up in {time.perf_counter() - t0:.1f}s")
        params = GenerationParams(max_new_tokens=MAX_TOKENS, temperature=0.0)
        outs = await asyncio.gather(*[
            handler.apredict(f"{SHORT_PROMPT} ({i})", params=params)
            for i in range(3)
        ])
        say(f"[tp-serve] answered {len(outs)} requests")
        devices = handler.backend.mesh.devices.flat
        used = [(d.memory_stats() or {}).get("bytes_in_use", 0) for d in devices]
        total = sum(used)
        check(total > 0 or provider != "tpu", "no memory_stats on the TPU")
        if total:
            shares = [u / total for u in used]
            say(f"[tp-serve] bytes_in_use per device: "
                f"{[round(u / 2**30, 2) for u in used]} GiB; shares "
                f"{[round(s, 3) for s in shares]} (band {TP_BYTES_BAND})")
            check(
                all(TP_BYTES_BAND[0] <= s <= TP_BYTES_BAND[1] for s in shares),
                f"per-device bytes outside {TP_BYTES_BAND} of the total: {shares}",
            )
        hlo = decode_program_hlo(handler)
        n_ar = hlo.count("all-reduce")
        say(f"[tp-serve] decode program HLO: {n_ar} all-reduce mentions")
        check(n_ar > 0, "no all-reduce in the decode program's HLO")
        check_not_healed("tp-serve", baseline, handler.get_metrics())
    finally:
        await handler.stop()


def decode_program_hlo(handler: Any) -> str:
    """Compiled HLO text of the decode chunk the running engine dispatches
    (same arguments the batcher passes, one block)."""
    from pilottai_tpu.engine.decode import decode_chunk

    b = handler.backend.batcher
    return decode_chunk.lower(
        b.params, b.cfg, b.cache, b.dstate, b.sampling, 1, False,
        prefix_bound=b._decode_bucket(64), fused_epilogue=True,
    ).compile().as_text()


# ---------------------------------------------------------------------- #

@contextlib.contextmanager
def timed(label: str):
    t0 = time.perf_counter()
    yield
    say(f"[{label}] phase took {time.perf_counter() - t0:.1f}s")


def run_one_chip() -> None:
    with timed("logits"):
        logits_phase(
            LOGIT_MODEL, LOGIT_LAYERS, quantize=True, dtype="bfloat16",
            on_tpu=True,
        )
    with timed("serve-4k"):
        asyncio.run(serve_phase(
            SERVE_4K_ARGV, "serve-4k", expect_vocab=128_256,
            expect_tied=False, expect_pallas=True, long_prompt=LONG_PROMPT,
        ))
    with timed("serve-dense"):
        asyncio.run(serve_phase(
            SERVE_DENSE_ARGV, "serve-dense", expect_vocab=128_256,
            expect_tied=False, expect_pallas=False, prompt=DENSE_PROMPT,
        ))
    with timed("orchestrator"):
        asyncio.run(orchestrator_phase("tpu"))


def run_four_chips() -> None:
    import gc

    with timed("tp-logits"):
        tp_logits_phase()
    gc.collect()  # phase (a)'s parameters leave the chips before (b) counts bytes
    with timed("tp-serve"):
        asyncio.run(tp_serve_phase())


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, default=1, choices=(1, 4),
        help="1 (default): the whole one-chip smoke. 4: ONLY the "
             "tensor-parallel phase, on a four-chip host.",
    )
    args = ap.parse_args(argv)
    device: Optional[Dict[str, Any]] = None
    t_start = time.perf_counter()
    try:
        from pilottai_tpu.utils.compile_cache import enable_compilation_cache

        device = device_report()
        say(f"device: {json.dumps(device)}")
        if device["platform"] != "tpu":
            raise PhaseFailed(
                f"JAX found platform {device['platform']!r} "
                f"({device['kind']}), not a TPU: nothing was run"
            )
        if device["count"] != args.chips:
            raise PhaseFailed(
                f"--chips {args.chips} but JAX reports {device['count']} device(s)"
            )
        # Before the first compile: JAX decides once whether the cache is on.
        say(f"compile cache: {enable_compilation_cache()}")
        if args.chips == 4:
            run_four_chips()
        else:
            run_one_chip()
        import jax

        peaks = [
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.devices()
        ]
        say(f"peak_bytes_in_use per device: {peaks}")
        say(f"total {time.perf_counter() - t_start:.1f}s")
    except Exception as exc:  # noqa: BLE001 — every failure ends in the last line
        if not isinstance(exc, PhaseFailed):
            traceback.print_exc()
        say(f"FAILED after {time.perf_counter() - t_start:.1f}s: {exc}")
        print(final_line(False, device, error=f"{type(exc).__name__}: {exc}"[:500]),
              flush=True)
        return 1
    print(final_line(True, device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
