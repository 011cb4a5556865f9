"""Unified configuration layer — one pydantic model per subsystem.

Reference parity: ``pilott/core/config.py`` (SecureConfig/LLMConfig/
LogConfig/AgentConfig), ``pilott/pilott.py:17-27`` (ServeConfig),
``pilott/core/router.py:15-20`` (RouterConfig),
``pilott/orchestration/load_balancer.py:22-30`` (LoadBalancerConfig),
``pilott/orchestration/orchestration.py:19-28`` (ScalingConfig),
``pilott/orchestration/scaling.py:49-58`` (FaultToleranceConfig).

The reference ships TWO incompatible ``AgentConfig`` classes
(SURVEY.md §2.12-c); here there is exactly one, carrying the union of the
fields actually read anywhere in the reference tree.
"""

from __future__ import annotations

import json
import os
import shutil
import time
from pathlib import Path
from typing import Any, ClassVar, Dict, List, Literal, Optional

from pydantic import BaseModel, Field, SecretStr, field_validator

from pilottai_tpu.core.status import AgentRole

Provider = Literal["tpu", "cpu", "mock"]


class SecureConfig:
    """Symmetric encryption helper for sensitive config values.

    Reference: ``pilott/core/config.py:10-39`` (Fernet). The cryptography
    dependency is optional here; without it the helpers raise cleanly
    instead of breaking import of the whole config layer.
    """

    def __init__(self, key: Optional[bytes] = None) -> None:
        try:
            from cryptography.fernet import Fernet
        except ImportError as exc:  # pragma: no cover - env dependent
            raise RuntimeError("cryptography is not installed") from exc
        self._fernet = Fernet(key or Fernet.generate_key())

    @staticmethod
    def generate_key() -> bytes:
        from cryptography.fernet import Fernet

        return Fernet.generate_key()

    def encrypt(self, value: str) -> str:
        return self._fernet.encrypt(value.encode()).decode()

    def decrypt(self, token: str) -> str:
        return self._fernet.decrypt(token.encode()).decode()


class SamplingConfig(BaseModel):
    """Decode-time sampling parameters (engine surface, no reference analog —
    the reference forwards temperature/max_tokens to remote APIs,
    ``pilott/engine/llm.py:49``)."""

    temperature: float = Field(default=0.7, ge=0.0)
    top_k: int = Field(default=0, ge=0)  # 0 = disabled
    top_p: float = Field(default=1.0, gt=0.0, le=1.0)
    max_new_tokens: int = Field(default=256, ge=1)
    seed: Optional[int] = None
    json_mode: bool = False  # grammar-constrained JSON decoding


class ReliabilityConfig(BaseModel):
    """Overload, deadline and failure-handling knobs (reliability/ — no
    reference analog: the reference has no admission control at all).

    Semantics are documented in docs/SERVING.md "Overload & failure
    semantics": queue-depth shedding → 429, breaker open → 503, deadline
    exceeded → 408.
    """

    # Engine admission control: submits beyond this many queued-but-not-
    # admitted requests are rejected (EngineOverloaded → HTTP 429).
    # None = unbounded (the seed behavior).
    max_queue_depth: Optional[int] = Field(default=None, ge=1)
    # Per-request deadline defaults at the HTTP edge. Clients set
    # ``timeout`` in the body or an ``x-request-timeout`` header;
    # ``default_timeout`` applies when they don't (None = no deadline),
    # and ``max_timeout`` caps whatever they ask for.
    default_timeout: Optional[float] = Field(default=None, gt=0)
    max_timeout: float = Field(default=600.0, gt=0)
    # Retry backoff shaping (engine/handler.py): capped exponential with
    # jitter — synchronized retry herds re-break a recovering backend.
    retry_max_delay: float = Field(default=30.0, ge=0)
    retry_jitter: bool = True
    # Circuit breaker over engine calls (reliability/breaker.py).
    breaker_enabled: bool = True
    breaker_failure_threshold: int = Field(default=5, ge=1)
    breaker_recovery_timeout: float = Field(default=30.0, gt=0)
    breaker_half_open_max: int = Field(default=1, ge=1)
    # In-flight request recovery (engine/batcher.py): on a device/reader
    # failure each occupied slot's progress (prompt + accepted tokens)
    # re-admits through the normal admission path after the device-state
    # rebuild instead of failing the request — greedy output stays
    # byte-identical across a mid-decode crash. Attempts are bounded per
    # request; exhausting them fails with the original exception.
    # 0 disables (the pre-0.10 fail-all behavior).
    recovery_max_attempts: int = Field(default=2, ge=0)
    # Device watchdog (reliability/watchdog.py): declare the engine
    # stalled when fold/prefill heartbeats go stale this many seconds
    # with work in flight — a hung dispatch becomes a 503 with
    # diagnostics instead of silent client hangs. Must exceed the
    # slowest healthy dispatch (warmup compiles are excluded). None
    # disables.
    watchdog_stall_s: Optional[float] = Field(default=None, gt=0)
    # Degradation ladder (reliability/degrade.py): this many faults
    # inside the rolling window step capability down one rung
    # (drafting → chunk size → slots → batch-class shed); a clean
    # promote-window soak steps back up.
    degrade_enabled: bool = True
    degrade_fault_threshold: int = Field(default=3, ge=1)
    degrade_window_s: float = Field(default=30.0, gt=0)
    degrade_promote_s: float = Field(default=60.0, gt=0)
    # Per-SLO-class shedding: non-interactive (batch) requests shed at
    # this fraction of max_queue_depth, so backlog pressure sheds the
    # traffic nobody is watching before the traffic someone is.
    batch_shed_frac: float = Field(default=0.5, gt=0, le=1.0)


class LLMConfig(BaseModel):
    """LLM engine configuration (reference: ``pilott/core/config.py:41-77``).

    ``provider`` selects an in-tree backend instead of a remote API:
    ``"tpu"`` (JAX engine on TPU), ``"cpu"`` (same engine on host JAX),
    ``"mock"`` (deterministic scripted backend for tests — the first-class
    test fixture SURVEY.md §4 calls for).
    """

    model_name: str = "llama3-8b"
    provider: Provider = "mock"
    api_key: Optional[SecretStr] = None  # kept for config-file parity; unused by in-tree providers
    checkpoint_path: Optional[str] = None
    tokenizer_path: Optional[str] = None

    sampling: SamplingConfig = Field(default_factory=SamplingConfig)
    function_calling: bool = True

    # Client-side throttling (reference: max_rpm limiter ``engine/llm.py:68-89``,
    # Semaphore(5) concurrency cap ``engine/llm.py:36``).
    max_rpm: Optional[int] = None
    max_concurrent_requests: int = Field(default=64, ge=1)
    retries: int = Field(default=3, ge=0)
    retry_delay: float = Field(default=1.0, ge=0)
    timeout: float = Field(default=120.0, gt=0)

    # Engine placement / serving shape
    mesh_shape: Optional[Dict[str, int]] = None  # e.g. {"data": 1, "model": 8}
    # Degraded-mesh ladder (parallel/meshplan.py): the ordered list of
    # mesh plans the engine may re-plan onto when a shard is lost
    # mid-serving. "auto" derives a halving ladder from the boot plan
    # (parallel axes halve first, model last, down to single-chip);
    # "off" disables shard-loss re-planning (a lost device fails over
    # PR 8's generic recovery path instead); an explicit list of plan
    # dicts (e.g. [{"model": 4, "data": 2}, {"model": 4}, {"model": 2}])
    # pins the rungs — every rung must fit the boot device set.
    engine_mesh_ladder: Any = "auto"
    dtype: str = "bfloat16"
    # Weight-only quantization for serving — legacy spelling, kept as an
    # alias for ``engine_quant`` ("int8"/"int4" or None). Shrinks the
    # per-token HBM weight stream that bounds decode (models/quant.py).
    quantize: Optional[str] = None
    # Weight quantization mode ("none" | "int8" | "int4"; None = follow
    # the ``quantize`` alias above). int8 halves the decode weight
    # stream with per-output-channel scales; int4 halves it AGAIN with
    # packed nibbles + per-group scales (``engine_quant_group``), with
    # quantization-sensitive fallbacks: lm_head stays int8, the MoE
    # router stays dense. Greedy output of the packed path is
    # byte-identical to an unpacked int4-dequant reference
    # (tests/test_quant_parity.py).
    engine_quant: Optional[str] = None

    @field_validator("quantize")
    @classmethod
    def _valid_quantize(cls, v: Optional[str]) -> Optional[str]:
        # Same value set as engine_quant — the fields are aliases.
        if v not in (None, "none", "int8", "int4"):
            raise ValueError(
                f"unknown quantize mode {v!r}; "
                "supported: 'none', 'int8', 'int4'"
            )
        return v

    @field_validator("engine_quant")
    @classmethod
    def _valid_engine_quant(cls, v: Optional[str]) -> Optional[str]:
        if v not in (None, "none", "int8", "int4"):
            raise ValueError(
                "engine_quant must be 'none', 'int8' or 'int4'"
            )
        return v

    @field_validator("engine_mesh_ladder")
    @classmethod
    def _valid_mesh_ladder(cls, v: Any) -> Any:
        if isinstance(v, str):
            if v not in ("auto", "off"):
                raise ValueError(
                    "engine_mesh_ladder must be 'auto', 'off' or a "
                    "list of mesh-plan dicts"
                )
            return v
        if isinstance(v, (list, tuple)):
            for plan in v:
                if not isinstance(plan, dict) or not all(
                    isinstance(a, str)
                    and isinstance(n, int) and n >= 1
                    for a, n in plan.items()
                ):
                    raise ValueError(
                        "engine_mesh_ladder rungs must be dicts of "
                        "axis name -> positive int, e.g. "
                        "[{'model': 4, 'data': 2}, {'model': 2}]"
                    )
            return list(v)
        raise ValueError(
            "engine_mesh_ladder must be 'auto', 'off' or a list of "
            "mesh-plan dicts"
        )
    # int4 scale-group width over the contraction axis (rows per shared
    # scale). Smaller groups bound quantization error tighter at
    # 4/group extra bits per weight; 128 is the standard trade. Also
    # part of the page-strip autotune key — a winner timed under one
    # quantization shape is never silently reused under another.
    engine_quant_group: int = Field(default=128, ge=1)
    # Fused decode epilogue (engine/decode.py:fused_greedy_epilogue):
    # when every occupied slot is greedy (temperature 0) and
    # unconstrained (no JSON/schema grammar), the logits projection and
    # sampling fuse into one vocab-tiled argmax — the [B, V] fp32
    # logits never round-trip HBM and the sampler's full-vocab sort
    # masks are skipped. Byte-identical on/off (the non-fusable shapes
    # — JSON/schema decoding, sampled slots — take the unfused path per
    # dispatch automatically).
    engine_fused_epilogue: bool = True
    engine_slots: int = Field(default=8, ge=1)       # continuous-batching slots
    # Admission group width: the MOST prompts one fused admission
    # dispatch prefills. A dispatch runs the smallest power-of-two row
    # count that holds its group (1, 2, 4, ... up to this), so compile
    # variants stay bounded and a lone request runs one row. A full
    # 32-slot wave admits in ceil(32/width) dispatches.
    engine_admit_batch: int = Field(default=8, ge=1)
    engine_max_seq: Optional[int] = None             # KV length cap (default model max)
    engine_chunk: int = Field(default=16, ge=1)      # decode tokens per dispatch
    # Chunk-length scheduling (engine/batcher.py:_pick_chunk_blocks):
    # "adaptive" sizes each decode dispatch from the live slots'
    # remaining-token budgets, deadline budgets and the speculation
    # acceptance EMA, quantized to engine_chunk_buckets — finished slots
    # fold (and release their pages) at the earliest useful boundary
    # instead of riding out the straggler's full chunk. "fixed" restores
    # the constant engine_chunk dispatch. Greedy output is byte-identical
    # either way (tests/test_adaptive_chunk.py).
    engine_chunk_policy: str = Field(default="adaptive")
    # Adaptive dispatch sizes (blocks). None = a quartile ladder of
    # engine_chunk ({4, 8, 12, 16} at the default 16). The ladder is the
    # compile-cache bound: one decode executable per bucket per
    # prefix-bound rung, all compiled at warmup.
    engine_chunk_buckets: Optional[List[int]] = None

    @field_validator("engine_chunk_policy")
    @classmethod
    def _valid_chunk_policy(cls, v: str) -> str:
        if v not in ("fixed", "adaptive"):
            raise ValueError(
                "engine_chunk_policy must be 'fixed' or 'adaptive'"
            )
        return v
    # Decode dispatch pipeline depth: chunks in flight before the device
    # thread blocks on the reader. Each extra level hides one
    # host↔device sync behind compute — the lever when the sync cost is
    # large next to a chunk's device time; early-exit chunks keep
    # over-dispatched levels nearly free (a chunk whose slots are all
    # done retires without running a weight pass). Every level carries
    # its own dispatch-time D2H copy, so any depth ≥ 1 pipelines.
    engine_pipeline: int = Field(default=2, ge=1)
    # Overlapped admission (engine/batcher.py:_prep_loop): admission
    # prep — slot selection, page allocation, prefix matching, staging-
    # buffer packing — runs on a dedicated prep thread, and the device
    # thread only enqueues the prebuilt prefill behind in-flight decode
    # chunks. Greedy output is byte-identical on/off
    # (tests/test_overlap_admission.py); False restores the inline path.
    engine_overlap_admission: bool = True
    # Paged KV cache (ops/paged.py): None = auto (paged when the per-slot
    # capacity is ≥ 4096 — that is where dense slots × max_seq reservation
    # stops fitting HBM). Pool size in pages; None = the HBM a dense
    # min(max_seq, 2048) cache would use.
    engine_paged_kv: Optional[bool] = None
    engine_kv_pages: Optional[int] = None
    engine_page_size: int = Field(default=128, ge=8)
    # Pages per paged-attention grid cell (the strip width of
    # ops/pallas/paged_attention.py). The long-context decode path is
    # grid-cell-latency bound (round-5 page A/B: 64→268, 128→243,
    # 256→309 device ms/step — a per-cell launch/index floor), so wider
    # strips amortize the per-cell overhead. None = autotune over
    # {1, 2, 4, 8} at warmup on TPU (result cached alongside the compile
    # cache); an explicit int forces it.
    engine_page_strip: Optional[int] = Field(default=None, ge=1)
    # Speculative decoding: verify-blocks of N tokens per weight pass via
    # n-gram self-drafting (0 = off; >= 2 enables; dense KV only). Decode
    # is weight-stream-bound, so accepted drafts are nearly free tokens
    # (engine/decode.py:decode_chunk_spec).
    engine_speculate: int = Field(default=0, ge=0)
    # Automatic prefix caching: keep the K/V of the last N admitted
    # prompt prefixes on device; repeated/shared prefixes skip their
    # prefill FLOPs (engine/prefix_cache.py). 0 disables; dense KV only.
    # Entry HBM cost: 2 (K and V) x L x K x bucket(len, cap 1024) x H x
    # itemsize — ~67 MB for llama3-8b bf16 at bucket 512.
    engine_prefix_cache: int = Field(default=4, ge=0)
    # Global KV cache tier (engine/kvcache/): host-RAM cold-tier budget
    # in MB. Evicted prefix KV (dense panel entries, paged chain pages)
    # spills to pinned host buffers via async D2H instead of being
    # dropped; a session resume or repeated preamble restores via async
    # H2D instead of re-prefilling. 0 disables the cold tier (evictions
    # discard KV — the pre-tier behavior). Greedy output is
    # byte-identical on/off (tests/test_kvcache.py).
    engine_kvcache_host_mb: int = Field(default=0, ge=0)
    # Tier eviction policy ("cost" | "lru"): "cost" scores entries by
    # recency x reconstruction cost (prefill FLOPs saved per byte held),
    # so densely packed preambles outlive equally old mostly-padding
    # entries; "lru" is plain recency. Applies to the device-resident
    # dense store and the host tier.
    engine_kvcache_policy: str = Field(default="cost")

    @field_validator("engine_kvcache_policy")
    @classmethod
    def _valid_kvcache_policy(cls, v: str) -> str:
        if v not in ("cost", "lru"):
            raise ValueError(
                "engine_kvcache_policy must be 'cost' or 'lru'"
            )
        return v
    # DAG-aware admission scheduling (pilottai_tpu/sched/ +
    # engine/batcher.py, ROADMAP item 4). "dag" orders the admission
    # backlog by request priority (Task.priority threads the full
    # lattice through GenerationParams.priority), groups gang-tagged
    # fan-out siblings, and ages waiting work one rung per
    # engine_priority_aging_s so nothing starves; "fifo" is the seed's
    # submission order. Greedy output is byte-identical either way
    # (tests/test_sched.py).
    engine_sched_policy: str = Field(default="dag")

    @field_validator("engine_sched_policy")
    @classmethod
    def _valid_sched_policy(cls, v: str) -> str:
        if v not in ("fifo", "dag"):
            raise ValueError(
                "engine_sched_policy must be 'fifo' or 'dag'"
            )
        return v
    # Gang admission wait bound (ms): how long an incomplete gang — or
    # one the free slots+pages can't take whole — may defer behind
    # other work before it admits partially anyway.
    engine_gang_wait_ms: float = Field(default=50.0, ge=0)
    # Aging floor: seconds of backlog wait per promoted priority rung
    # (LOW reaches CRITICAL after 3x this and can never starve under
    # sustained critical-path load). 0 disables aging.
    engine_priority_aging_s: float = Field(default=2.0, ge=0)
    # Speculative stage pre-warm depth: how many tokens of a predicted
    # next-stage prompt prefix the scheduler may ask the engine to
    # pre-warm (KV cache tier restore staged on the prep thread — the
    # next hop's prefill finds device-resident KV). 0 detaches the
    # engine from the scheduler's pre-warm loop entirely.
    engine_prewarm_depth: int = Field(default=512, ge=0)
    # Dense prefix-store entry floor in tokens (None = the prefill
    # bucket floor, 64 by default): prompts at or below it never cache
    # — the engine warns ONCE when such a prompt is seen instead of
    # missing silently (engine/prefix_cache.py).
    engine_prefix_min_len: Optional[int] = Field(default=None, ge=1)
    # Adaptive draft-model speculation: >0 enables shallow-layer
    # self-drafting (the target's own first N layers + unembed propose
    # drafts — LayerSkip-style, no second checkpoint, no extra HBM) for
    # slots whose n-gram acceptance collapses on novel text
    # (engine/decode.py:_model_drafts). Requires engine_speculate >= 2.
    engine_draft_layers: int = Field(default=0, ge=0)
    # Chunked prefill: long cold prompts admit in page-aligned segments
    # of this many tokens, one per device-loop cycle, so live slots'
    # decode chunks interleave with the prefill instead of stalling
    # behind it (paged KV only). None = auto (1024 when paged); 0 = off.
    engine_prefill_chunk: Optional[int] = None
    # int8 KV cache ("int8" or None): panels stored int8 with symmetric
    # per-token-per-head scales (ops/kvcache.py:quantize_kv). Doubles
    # resident context per HBM GB everywhere; the decode-bandwidth win
    # (int8-sized cache reads) is realized on the paged-Pallas path,
    # where dequant happens in-VMEM — XLA paths may materialize
    # dequantized panels once per chunk. ~1e-3 relative attention error;
    # composes with paged KV, speculation and prefix caching.
    engine_kv_quantize: Optional[str] = None
    # Persistent XLA compilation cache (utils/compile_cache.py): None =
    # enabled at <checkout>/.jax_cache; "off" disables; else the
    # directory. JAX_COMPILATION_CACHE_DIR, where set, places the cache
    # and overrides this field.
    # Warm restarts (FaultTolerance respawns, worker redeploys) reuse
    # compiled programs instead of paying minutes of recompilation.
    engine_compile_cache: Optional[str] = None
    # Disaggregated prefill/decode serving (distributed/cell.py, ISSUE
    # 19): per-tier replica counts as "<P>p<D>d" (e.g. "1p2d" = one
    # prefill-tier replica, two decode-tier replicas; replicas past
    # P+D stay "mixed"). A ServingCell built over handlers with this
    # config splits its replicas into tiers and moves freshly prefilled
    # requests to the decode tier via the KV handoff path. None (the
    # default) keeps every replica "mixed" — the colocated topology, an
    # exact no-op on routing and output.
    cell_disagg: Optional[str] = None

    @field_validator("cell_disagg")
    @classmethod
    def _valid_cell_disagg(cls, v: Optional[str]) -> Optional[str]:
        if v is None:
            return v
        import re

        spec = v.strip().lower()
        m = re.fullmatch(r"(\d+)p\+?(\d+)d", spec)
        if not m or int(m.group(1)) + int(m.group(2)) < 1:
            raise ValueError(
                "cell_disagg must be '<P>p<D>d' (e.g. '1p2d'); "
                f"got {v!r}"
            )
        return spec
    seed: int = 0                                    # param init seed when no checkpoint
    # Deadlines, shedding, breaker (reliability/): defaults keep the seed
    # behavior except the breaker, which only changes anything once the
    # backend fails 5 times in a row.
    reliability: ReliabilityConfig = Field(default_factory=ReliabilityConfig)


class LogConfig(BaseModel):
    """Logging configuration (reference: ``pilott/core/config.py:80-100``)."""

    level: str = "INFO"
    log_to_file: bool = False
    log_dir: str = "logs"
    json_format: bool = True
    rotate_max_bytes: int = 10 * 1024 * 1024
    rotate_backups: int = 5

    @field_validator("level")
    @classmethod
    def _valid_level(cls, v: str) -> str:
        allowed = {"DEBUG", "INFO", "WARNING", "ERROR", "CRITICAL"}
        v = v.upper()
        if v not in allowed:
            raise ValueError(f"log level must be one of {sorted(allowed)}")
        return v


class AgentConfig(BaseModel):
    """The single, unified agent configuration.

    Union of the fields read anywhere in the reference: identity/prompting
    (``core/config.py:103-125``), feature flags (``:127-134``), resource
    limits (``:137-151``), plus the minimal class's fields
    (``core/agent.py:19-29``).
    """

    role: str = "worker"
    role_type: AgentRole = AgentRole.WORKER
    goal: str = "complete assigned tasks accurately"
    description: str = ""
    backstory: str = ""

    knowledge_sources: List[str] = Field(default_factory=list)
    tools: List[str] = Field(default_factory=list)
    required_capabilities: List[str] = Field(default_factory=list)
    specializations: List[str] = Field(default_factory=list)

    # Reasoning loop bounds (reference: max_iterations=20 ``core/config.py:128``)
    max_iterations: int = Field(default=20, ge=1)
    max_rpm: Optional[int] = None
    retry_limit: int = Field(default=2, ge=0)
    code_execution_mode: Literal["safe", "restricted", "unrestricted"] = "safe"

    # Feature flags (reference ``core/config.py:130-134``)
    memory_enabled: bool = True
    delegation_enabled: bool = False
    caching_enabled: bool = True
    code_execution_enabled: bool = False
    verbose: bool = False

    # Resource limits (reference ``core/config.py:137-151``)
    max_child_agents: int = Field(default=10, ge=0)
    max_queue_size: int = Field(default=100, ge=1)
    max_task_complexity: int = Field(default=5, ge=1, le=10)
    delegation_threshold: float = Field(default=0.7, ge=0.0, le=1.0)
    max_concurrent_tasks: int = Field(default=5, ge=1)
    task_timeout: float = Field(default=300.0, gt=0)

    llm: Optional[LLMConfig] = None
    log: LogConfig = Field(default_factory=LogConfig)

    # ---------------- persistence (reference ``core/config.py:198-249``) --- #

    SENSITIVE_KEYS: ClassVar[tuple] = ("api_key", "secret", "password", "token")

    def has_sensitive_data(self) -> bool:
        def scan(obj: Any) -> bool:
            if isinstance(obj, dict):
                return any(
                    any(s in str(k).lower() for s in self.SENSITIVE_KEYS) and v
                    or scan(v)
                    for k, v in obj.items()
                )
            if isinstance(obj, list):
                return any(scan(x) for x in obj)
            return False

        return scan(self.model_dump())

    def save(self, path: str | Path) -> None:
        """Atomic JSON save with backup-and-restore semantics.

        SecretStr fields are revealed on disk (pydantic would otherwise
        serialize the mask ``**********`` and destroy the key on round-trip);
        callers holding secrets should prefer env vars or ``SecureConfig``.
        """
        path = Path(path)
        data = self.model_dump(mode="json")
        if self.llm is not None and self.llm.api_key is not None:
            data["llm"]["api_key"] = self.llm.api_key.get_secret_value()
        backup = path.with_suffix(path.suffix + ".bak")
        if path.exists():
            shutil.copy2(path, backup)
        tmp = path.with_suffix(path.suffix + f".tmp{os.getpid()}")
        try:
            tmp.write_text(json.dumps(data, indent=2))
            tmp.replace(path)
        except Exception:
            if backup.exists():
                shutil.copy2(backup, path)
            raise
        finally:
            tmp.unlink(missing_ok=True)

    @classmethod
    def load(cls, path: str | Path) -> "AgentConfig":
        return cls(**json.loads(Path(path).read_text()))


class ServeConfig(BaseModel):
    """Orchestrator configuration (reference: ``pilott/pilott.py:17-27``)."""

    name: str = "pilott-tpu"
    max_concurrent_tasks: int = Field(default=5, ge=1)
    task_timeout: float = Field(default=300.0, gt=0)
    max_queue_size: int = Field(default=1000, ge=1)
    cleanup_interval: float = Field(default=3600.0, gt=0)
    task_retention: float = Field(default=86400.0, gt=0)
    max_retry_attempts: int = Field(default=3, ge=0)
    decomposition_enabled: bool = True
    evaluation_enabled: bool = True
    # Integrated side services (the reference never wires these into
    # Serve.start(), SURVEY.md §3.1 — here they are part of one lifecycle).
    load_balancing_enabled: bool = False
    dynamic_scaling_enabled: bool = False
    fault_tolerance_enabled: bool = False
    # Manager-side delegation (delegation/delegator.py): when enabled and a
    # manager agent with children is attached, tasks route through
    # TaskDelegator.evaluate_delegation BEFORE the router (reference
    # ``delegation/task_delegator.py:41-111`` — never wired there).
    delegation_enabled: bool = False
    # Durable task journal (checkpoint/journal.py; SURVEY.md §5.4 — the
    # reference loses all queue state on crash/preemption).
    journal_path: Optional[str] = None
    journal_fsync: bool = False
    journal_recover: bool = True  # replay the journal on start()


class RouterConfig(BaseModel):
    """Task router configuration (reference: ``pilott/core/router.py:15-20``)."""

    load_check_interval: float = Field(default=5.0, ge=0)  # score cache TTL (0 = no caching)
    load_threshold: float = Field(default=0.8, ge=0.0, le=1.0)
    route_timeout: float = Field(default=30.0, gt=0)
    route_attempts: int = Field(default=3, ge=1)
    retry_backoff: float = Field(default=1.0, ge=0)


class LoadBalancerConfig(BaseModel):
    """Reference: ``pilott/orchestration/load_balancer.py:22-30``."""

    check_interval: float = Field(default=30.0, gt=0)
    overload_threshold: float = Field(default=0.8, ge=0.0, le=1.0)
    underload_threshold: float = Field(default=0.2, ge=0.0, le=1.0)
    max_tasks_per_cycle: int = Field(default=3, ge=1)
    task_move_timeout: float = Field(default=30.0, gt=0)
    trend_window: int = Field(default=5, ge=1)


class ScalingConfig(BaseModel):
    """Reference: ``pilott/orchestration/orchestration.py:19-28``."""

    check_interval: float = Field(default=60.0, gt=0)
    scale_up_threshold: float = Field(default=0.8, ge=0.0, le=1.0)
    scale_down_threshold: float = Field(default=0.3, ge=0.0, le=1.0)
    min_agents: int = Field(default=2, ge=0)
    max_agents: int = Field(default=10, ge=1)
    cooldown: float = Field(default=300.0, ge=0)
    trend_window: int = Field(default=5, ge=1)
    # Normalizer for the engine admission-queue signal when the engine
    # runs without a shed limit (engine.max_queue_depth gauge absent):
    # this many queued-not-admitted requests read as 100% queue pressure.
    queue_depth_ref: int = Field(default=64, ge=1)
    # Predictive autoscaling (obs/forecast.py): when the seasonal
    # arrival forecaster has a full period of history, the load signal
    # is boosted by forecast(now + forecast_lead_s) / current rate —
    # capacity moves BEFORE the predicted ramp arrives instead of after
    # burn rate crosses 1. Boost-only (a predicted lull never shrinks
    # early) and capped at forecast_boost_cap so a cold forecaster or a
    # spiky trace can't slam the pool to max. No-op until the forecaster
    # is ready, so enabling it is safe on day one.
    forecast_enabled: bool = True
    forecast_lead_s: float = Field(default=120.0, ge=0)
    forecast_boost_cap: float = Field(default=2.0, ge=1.0)


class FaultToleranceConfig(BaseModel):
    """Reference: ``pilott/orchestration/scaling.py:49-58``."""

    check_interval: float = Field(default=30.0, gt=0)
    heartbeat_timeout: float = Field(default=60.0, gt=0)
    max_recovery_attempts: int = Field(default=3, ge=0)
    recovery_cooldown: float = Field(default=300.0, ge=0)
    resource_threshold: float = Field(default=0.9, ge=0.0, le=1.0)
    stuck_task_timeout: float = Field(default=1800.0, gt=0)
    error_threshold: int = Field(default=5, ge=1)


def utcnow() -> float:
    return time.time()
