"""Continuous batcher: many concurrent small generations on one device loop.

The workload shape (SURVEY.md §3.4): agent steps are bursty, short,
JSON-bound generations — dozens in flight, each a few hundred tokens. The
batcher multiplexes them onto fixed-shape device computations:

* a dedicated *device thread* runs prefill/decode (never the asyncio loop —
  the reference's blocking-psutil-in-async-loop bug, SURVEY §2.12-h, is the
  cautionary tale);
* decode runs as **fused multi-token chunks** (``engine/decode.py``): one
  dispatch per CHUNK tokens, with sampling + EOS/budget tracking on
  device, because every dispatch and every host<->device sync has a
  fixed cost that a one-token step cannot amortize — per-token syncing
  left the loop latency-bound long before the chip was;
* the chunk LENGTH is a scheduling decision (``_pick_chunk_blocks``):
  adaptive sizing from remaining budgets + the acceptance EMA,
  quantized to a small bucket ladder so executables stay bounded —
  slots finishing mid-chunk fold (and early-release their pages) at
  the nearest useful boundary instead of riding out a
  straggler-sized chunk (PERF_NOTES round 7);
* chunk dispatches are **pipelined** (depth 2): the host reads chunk N-1's
  tokens while chunks N and N+1 compute, so even the once-per-chunk sync
  overlaps device work;
* admissions happen between chunks in **batched groups**: one prefill for
  up to ``admit_batch`` prompts (its rows follow the group up a
  power-of-two ladder, ``_row_bucket``: compile variants stay bounded
  and a lone request does not run a full group's rows), KV written by
  one batched scatter, first token sampled on device with the slot's
  own sampling params (no host-side sampling duplicate);
* admission **prep is overlapped** (PERF_NOTES round 8): bucket/slot
  selection, page allocation, prefix matching and staging-buffer
  packing run on a dedicated prep thread (``_prep_loop``), so between
  decode dispatches the device thread only *enqueues* the already-built
  prefill behind the in-flight chunks — it never sits building host
  arrays while the TPU drains (``overlap_admission=False`` restores the
  inline path, byte-identical output either way);
* the per-admission scalar metadata rides **one packed staging buffer**
  per dtype (``decode.pack_admit_meta``) instead of ~10 tiny H2D
  transfers, each of which paid a dispatch/transfer-setup floor;
* folds are **non-blocking**: every dispatch starts its D2H copy
  immediately (``_HostCopy``), and the reader materializes the
  already-in-flight copy — chunk N−1 folds from its completed copy
  while chunk N executes; ``jax.device_get`` never runs on the
  dispatch/fold path (tests/test_no_blocking_hotpath.py trips on
  reintroduction);
* prefills compile per power-of-two length bucket; the decode chunk
  compiles once.

All shapes static → zero recompiles at steady state.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import Future
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pilottai_tpu.engine.decode import (
    AI_BUDGET,
    AI_EOS,
    AI_JSON,
    AI_LEN,
    AI_SCHEMA,
    AI_PLEN,
    AI_SEED,
    AI_SLOT,
    AI_TOPK,
    AF_TEMP,
    AF_TOPP,
    DecodeState,
    _paged_kernel_for,
    _refuse_recurrent,
    admit_group,
    admit_group_prefix,
    admit_group_prefix_paged,
    decode_chunk,
    decode_chunk_spec,
    export_prefix,
    extend_prompt_paged,
    pack_admit_meta,
    release_decode,
)
from pilottai_tpu.engine.kvcache import KVCacheIndex, SpillCopy
from pilottai_tpu.engine.page_prefix import PagePrefixIndex
from pilottai_tpu.engine.prefix_cache import PrefixStore
from pilottai_tpu.engine.sampling import SamplingState
from pilottai_tpu.models.common import ModelConfig
from pilottai_tpu.models.quant import weight_stream_bytes
from pilottai_tpu.ops.kvcache import KVCache, StatePool, free_slots
from pilottai_tpu.ops.paged import PageAllocator, PagedKVCache
from pilottai_tpu.ops.pallas.decode_attention import decode_shapes_ok
from pilottai_tpu.ops.pallas.paged_attention import paged_sharding_ok
from pilottai_tpu.parallel.collectives import CollectiveModel
from pilottai_tpu.parallel.meshplan import (
    MeshLadderExhausted,
    MeshPlanLadder,
    ShardLossError,
    classify_device_error,
    plan_label,
)
from pilottai_tpu.parallel.sharding import kv_shard_axes, place_kv_cache
from pilottai_tpu.obs import (
    global_attribution,
    global_blackbox,
    global_flight,
    global_steps,
)
from pilottai_tpu.reliability import (
    DeadlineExceeded,
    DegradeLadder,
    EngineOverloaded,
    PoisonedOutput,
    Watchdog,
    global_injector,
)
from pilottai_tpu.reliability import degrade as degrade_levels
from pilottai_tpu.utils.logging import get_logger
from pilottai_tpu.utils.metrics import global_metrics
from pilottai_tpu.utils.tracing import global_tracer, host_span


#: Priority-rung names for the per-priority backlog-wait histograms
#: (index = the 0..3 lattice; mirrors core.task.TaskPriority).
_PRIO_NAMES = ("low", "normal", "high", "critical")


@dataclass
class GenRequest:
    prompt_ids: List[int]
    max_new_tokens: int = 256
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    eos_id: int = -1
    # Grammar-constrained JSON decoding (engine/json_mask.py): byte
    # automaton for byte tokenizers, token→byte product for subword ones
    # (the batcher's json_tables).
    json_mode: bool = False
    # Schema-constrained decoding: row into the engine's SchemaBank
    # (engine/json_schema.py), -1 = generic grammar. Byte tokenizers
    # only; implies json_mode.
    json_schema_id: int = -1
    stop_ids: List[int] = field(default_factory=list)
    future: Future = field(default_factory=Future)
    submitted_at: float = field(default_factory=time.perf_counter)
    # Set by the caller (any thread) to abandon the request; the device loop
    # frees its slot at the next chunk boundary instead of decoding dead work.
    cancelled: bool = False
    # End-to-end deadline: absolute ``time.monotonic()`` time. Checked at
    # submit, again at admission (a request that expired in the backlog
    # never costs a prefill), and swept every device-loop cycle so an
    # occupied slot whose deadline passes mid-decode is force-released
    # (its future fails with DeadlineExceeded). None = no deadline.
    deadline: Optional[float] = None
    # Streaming: called from the READER thread with each batch of newly
    # folded output tokens (eos/stop ids already filtered — exactly the
    # ids the future's final result will contain, in order). Must be
    # cheap and non-blocking (bridge to asyncio via
    # ``loop.call_soon_threadsafe``); exceptions are swallowed.
    on_tokens: Optional[Any] = None
    # Flight-recorder correlation (obs/flight.py): admission and token
    # folds mark phases against ``flight_id`` (unique per request; falls
    # back to trace_id for direct submitters), the request's engine span
    # is emitted under ``trace_id``/``parent_span_id``, and black-box
    # dumps on deadline expiry cite the trace. None (warmup, direct
    # batcher tests) = untracked.
    trace_id: Optional[str] = None
    flight_id: Optional[str] = None
    parent_span_id: Optional[str] = None
    # SLO service class (obs/slo.py): per-class shed thresholds — batch
    # traffic sheds at a lower queue depth than interactive, and the
    # degradation ladder's last rung sheds it outright. None =
    # interactive semantics.
    slo_class: Optional[str] = None
    # DAG-aware scheduling (pilottai_tpu/sched/): the full task-priority
    # lattice (0=LOW … 3=CRITICAL), threaded Task.priority →
    # GenerationParams.priority → here. Under sched_policy="dag" the
    # backlog is priority-ordered (with an aging floor so LOW cannot
    # starve); under "fifo" the field is carried but ignored.
    priority: int = 1
    # Gang admission: sibling fan-out branches from one decompose stage
    # share a gang_id and are admitted as a group when slots+pages
    # suffice for all ``gang_size`` members (bounded wait, then partial
    # admit) — a task's slowest branch stops straggling behind
    # unrelated backlog. None = ungoverned (FIFO/priority only).
    gang_id: Optional[str] = None
    gang_size: int = 0
    # KV-cache session handle (engine/kvcache/): multi-turn agent
    # conversations send the same id every turn, pinning their KV
    # lineage in the host tier across device-cache evictions — a resume
    # restores from host RAM instead of re-prefilling the whole
    # history. None = anonymous (cacheable, but not eviction-pinned).
    session_id: Optional[str] = None
    # In-flight recovery bookkeeping (engine fault domain): on a
    # device/reader failure the batcher snapshots this request's
    # progress and re-admits it — ``recovered_tokens`` carries the
    # already-accepted output (prepended to the final result and never
    # re-emitted to ``on_tokens``), ``recovery_attempts`` bounds the
    # strikes before the request fails with the original exception, and
    # ``recovery_started_at`` times the snapshot→re-admission span for
    # the ``engine.recovery_ms`` histogram.
    recovery_attempts: int = 0
    recovered_tokens: List[int] = field(default_factory=list)
    recovery_started_at: Optional[float] = None
    # engine.kvcache.lookups/hits are per-REQUEST counters: a
    # page-blocked backlog head re-runs the prefix lookup every prep
    # cycle (~20 ms), and counting each attempt would inflate the
    # bench's prefix_hit_rate arbitrarily. Set by the first counted
    # lookup.
    kv_counted: bool = field(default=False, repr=False)
    # Aging-floor rungs already granted (and counted) by the priority
    # backlog — sched.priority_aged must count each promotion once, not
    # once per selection cycle.
    aged_rungs: int = field(default=0, repr=False)

    @property
    def flight_key(self) -> Optional[str]:
        return self.flight_id or self.trace_id


@dataclass
class _Slot:
    request: GenRequest
    generated: List[int] = field(default_factory=list)
    prompt_len: int = 0
    # First generated token still living on device (read lazily with the
    # admission group's array; None once folded into ``generated``).
    first_pending: bool = True
    # In-flight chunk accounting. A dispatched-but-unread chunk will
    # deliver between 1 and D tokens per block (D = 1 without
    # speculation): ``est_pending`` carries the rate-EMA estimate the
    # device loop uses to decide whether ANOTHER chunk would still be
    # useful, ``hi_pending`` the hard maximum the prefix-bound
    # computation needs. Both are reduced when the reader folds the
    # chunk and the slot's ``generated`` absorbs the actual tokens, so
    # estimates self-correct every read: an over-estimate can pause
    # dispatching for at most one fold cycle (the fold wakes the loop),
    # never hang it.
    est_pending: float = 0.0
    hi_pending: int = 0


# Handle for a device→host read whose transfer was STARTED at dispatch
# time (``copy_to_host_async``) and is only awaited at fold time — the
# reader materializes an already-in-flight copy instead of issuing a
# fresh blocking round trip (``jax.device_get`` would). ONE definition
# shared with the KV cache tier's spill path (the same discipline at
# eviction time); the AST tripwire (tests/test_no_blocking_hotpath.py)
# sanctions exactly this shape on both surfaces.
_HostCopy = SpillCopy


@dataclass
class _PreparedAdmission:
    """One admission group with every host-side input prebuilt (numpy
    staging buffers packed, slots reserved, pages allocated) — all that
    remains for the device thread is the jnp upload + jitted dispatch.
    ``epoch`` stamps the allocator generation the pages came from: a
    device-state rebuild invalidates older preps (their block-table rows
    mean nothing in the fresh allocator), which requeue instead of
    dispatching garbage."""

    kind: str                       # "full" | "prefix" | "prefix_paged"
    group: List[Tuple[int, GenRequest]]
    entry: Any
    epoch: int
    meta_i32: np.ndarray
    meta_f32: np.ndarray
    tokens: Optional[np.ndarray] = None       # full-prefill [A, T]
    tail_tokens: Optional[np.ndarray] = None  # prefix paths [A, Tt]
    full_tokens: Optional[np.ndarray] = None  # prefix paths [A, Tf]
    pages_arr: Optional[np.ndarray] = None    # paged-prefix chain pages
    page_rows: Optional[np.ndarray] = None    # [A, max_pages]
    n_prefix_bucket: int = 1
    has_json: bool = False
    has_schema: bool = False

    @property
    def prefilled(self) -> np.ndarray:
        """The token array the program prefills: whole prompts, or the
        tails past a cached prefix. Its shape is the work that runs."""
        return self.tokens if self.tokens is not None else self.tail_tokens


@dataclass
class _SegmentStart:
    """Prep-queue marker: a chunked-prefill admission whose pages are
    allocated; the device thread installs it as ``_segmenting`` and
    advances one segment per loop cycle."""

    seg: List[Any]                  # [slot_idx, request, tokens_done]
    epoch: int


class ContinuousBatcher:
    """Slot-based continuous batching over jitted prefill / fused-decode."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        n_slots: int = 8,
        max_seq_len: Optional[int] = None,
        min_bucket: int = 64,
        cache_dtype=jnp.bfloat16,
        chunk_size: int = 16,
        admit_batch: int = 8,
        use_pallas: Optional[bool] = None,
        on_tpu: Optional[bool] = None,
        mesh: Optional[Any] = None,
        paged: bool = False,
        page_size: int = 128,
        num_pages: Optional[int] = None,
        page_strip: Optional[int] = None,  # pages per paged-kernel grid
                                           # cell (None = autotune at warmup)
        json_tables: Optional[Tuple[Any, Any]] = None,
        speculate: int = 0,
        prefix_cache: int = 4,  # mirrors LLMConfig.engine_prefix_cache
        kv_quantize: bool = False,  # int8 cache panels + per-token scales
        draft_layers: int = 0,  # shallow-layer self-drafting (adaptive)
        pipeline_depth: int = 2,  # decode chunks in flight (hides the sync)
        schema_bank: Optional[Any] = None,  # json_schema.SchemaBank
        prefill_chunk: Optional[int] = None,  # chunked-prefill segment size
        max_queue_depth: Optional[int] = None,  # admission control (shed)
        chunk_policy: str = "adaptive",  # "fixed" | "adaptive" chunk sizing
        chunk_buckets: Optional[Tuple[int, ...]] = None,  # adaptive sizes
        overlap_admission: bool = True,  # prep admissions off the device
                                         # thread's critical path
        recovery_max_attempts: int = 2,  # in-flight re-admissions per
                                         # request before the original
                                         # exception wins (0 = off)
        watchdog_stall_s: Optional[float] = None,  # heartbeat-staleness
                                                   # bound (None = no dog)
        mesh_ladder: Any = "auto",      # degraded-mesh plans: "auto"
                                        # (halving ladder), "off", or an
                                        # explicit list of plan dicts
                                        # (parallel/meshplan.py)
        degrade: Optional[DegradeLadder] = None,  # capability ladder
                                                  # (None = default knobs)
        batch_shed_frac: float = 0.5,   # batch-class shed depth as a
                                        # fraction of max_queue_depth
        kvcache_host_mb: int = 0,       # host-RAM cold tier for evicted
                                        # prefix KV (0 = off)
        kvcache_policy: str = "cost",   # tier eviction: "cost" | "lru"
        sched_policy: str = "fifo",     # backlog order: "fifo" | "dag"
                                        # (priority + gang + aging)
        gang_wait_ms: float = 50.0,     # bounded wait for gang siblings
                                        # / capacity before partial admit
        priority_aging_s: float = 2.0,  # seconds of backlog wait per
                                        # aged priority rung (starvation
                                        # floor; 0 = no aging)
        prefix_min_len: Optional[int] = None,  # dense-store entry floor
                                               # (None = min_bucket)
        weight_quant: str = "none",     # weight quantization mode the
                                        # params carry ("none"|"int8"|
                                        # "int4") — autotune keys and the
                                        # QUANT bench read it here
        quant_group: int = 128,         # int4 scale-group width (part of
                                        # the autotune key)
        fused_epilogue: bool = True,    # fuse projection+greedy sampling
                                        # on all-greedy non-JSON chunks
    ) -> None:
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        # Weight-quantization bookkeeping (ISSUE 14): the mode/group ride
        # the page-strip autotune key (a winner timed under bf16 weights
        # must never be reused under int4 — different HBM contention
        # around the kernel), and the measured weight-stream bytes land
        # in gauges so the bytes-halved claim is a series, not a
        # docstring. Gauge values are GLOBAL logical bytes (divide by
        # the TP shard count for per-chip).
        self.weight_quant = weight_quant
        self.quant_group = int(quant_group)
        self.fused_epilogue = bool(fused_epilogue)
        wb = weight_stream_bytes(params)
        self.weight_bytes = wb["total"]
        self.weight_bytes_per_token = wb["per_token"]
        global_metrics.set_gauge("engine.weight_bytes", float(wb["total"]))
        global_metrics.set_gauge(
            "engine.weight_bytes_per_token", float(wb["per_token"])
        )
        self.PIPELINE_DEPTH = max(1, pipeline_depth)
        self.max_seq_len = min(max_seq_len or cfg.max_seq_len, cfg.max_seq_len)
        self.min_bucket = min_bucket
        self.chunk_size = chunk_size
        # Adaptive chunk scheduling (PERF_NOTES r7): the decode chunk
        # length becomes a per-dispatch scheduling decision driven by the
        # live slots' remaining-token budgets and the acceptance EMA,
        # quantized to a small bucket set so the compiled-executable
        # count stays bounded at len(buckets) per prefix-bound rung
        # (pinned by tests/test_compile_cache.py). "fixed" restores the
        # constant chunk_size.
        if chunk_policy not in ("fixed", "adaptive"):
            raise ValueError(
                f"unknown chunk_policy {chunk_policy!r}; "
                f"supported: 'fixed', 'adaptive'"
            )
        self.chunk_policy = chunk_policy
        if chunk_policy == "adaptive":
            if chunk_buckets:
                buckets = {int(b) for b in chunk_buckets}
                bad = sorted(b for b in buckets if not 1 <= b <= chunk_size)
                if bad:
                    # Silently dropping these would degrade "adaptive"
                    # to fixed with no signal why utilization never
                    # moves.
                    raise ValueError(
                        f"chunk_buckets {bad} outside [1, chunk_size="
                        f"{chunk_size}]"
                    )
            else:
                # Quartile ladder: {4, 8, 12, 16} at the default chunk 16.
                buckets = {
                    max(1, (chunk_size * q) // 4) for q in (1, 2, 3, 4)
                }
            # The largest bucket must cover a full fixed chunk, or a
            # saturated wave would need several dispatches where one did.
            self.chunk_buckets = sorted(buckets | {chunk_size})
        else:
            self.chunk_buckets = [chunk_size]
        # Warmup's compile sweep pins the bucket per request via this
        # override so every (bucket x prefix-bound) decode executable
        # compiles before serving (None = policy decides).
        self._force_chunk: Optional[int] = None
        # Wall-seconds per dispatched block EMA (each chunk's
        # dispatch→fold latency over its blocks), the deadline-budget
        # term of the sizing policy: blocks past a slot's deadline are
        # never worth dispatching. 0 = unknown yet.
        self._block_seconds = 0.0
        self.admit_batch = min(admit_batch, n_slots)
        # Overload shedding: submits beyond this many queued-not-admitted
        # requests raise EngineOverloaded instead of growing the queue
        # unboundedly (the HTTP edge maps it to 429). None = unbounded.
        # Batch-class requests shed at batch_shed_frac of the depth —
        # backlog pressure drops the traffic nobody is watching first.
        self.max_queue_depth = max_queue_depth
        self.batch_shed_frac = batch_shed_frac
        # DAG-aware backlog scheduling (pilottai_tpu/sched/, ROADMAP
        # item 4): "dag" orders admission by effective priority
        # (request priority + aging-floor promotions, gang siblings
        # grouped), "fifo" keeps the seed's submission order. Greedy
        # output is byte-identical either way — ordering changes WHEN a
        # request admits, never what it computes (tests/test_sched.py).
        if sched_policy not in ("fifo", "dag"):
            raise ValueError(
                f"unknown sched_policy {sched_policy!r}; "
                f"supported: 'fifo', 'dag'"
            )
        self.sched_policy = sched_policy
        self.gang_wait_ms = max(0.0, gang_wait_ms)
        self.priority_aging_s = max(0.0, priority_aging_s)
        # Gang bookkeeping: first-seen stamp per gang (the bounded-wait
        # clock; pruned when the gang's last member leaves the
        # backlog), the gangs the LAST ordering pass deferred
        # (selection blocks on them instead of admitting a sibling
        # subset early), and a bounded memory of gangs that ALREADY
        # dispatched (metrics fire once per gang, and a late or
        # fault-recovered sibling of a gang that already went must
        # admit at its own priority immediately — re-deferring it
        # behind the whole backlog for another wait bound would be the
        # exact inversion the feature exists to remove).
        self._gang_seen: Dict[str, float] = {}
        self._gang_counted: "OrderedDict[str, bool]" = OrderedDict()
        self._gang_deferred: set = set()
        # Speculative stage pre-warm (sched/ → prep thread): predicted
        # next-stage prompt prefixes waiting for a KV-tier lookup whose
        # host hit stages the restore before the real request arrives.
        # Bounded — pre-warm is advisory, a full queue just drops.
        self._prewarm_queue: deque = deque(maxlen=32)
        # One-shot dense-store floor warning (see _warn_min_len).
        self._warned_min_len = False
        # Engine fault domain: bounded in-flight recovery, the capability
        # ladder, and (optionally) the device watchdog.
        self.recovery_max_attempts = max(0, recovery_max_attempts)
        self.degrade = degrade if degrade is not None else DegradeLadder()
        # Device-thread rebuild request from other threads' failure paths
        # (reader errors, failed failure-path rebuilds): consumed at the
        # top of the device loop, where rebuilds are safe.
        self._rebuild_requested: Optional[str] = None
        self._watchdog: Optional[Watchdog] = None
        if watchdog_stall_s:
            self._watchdog = Watchdog(
                stall_s=watchdog_stall_s,
                has_work=self._watchdog_has_work,
                on_stall=self._on_watchdog_stall,
                # Unique health-registry source per batcher: in a
                # multi-engine process, one engine recovering must not
                # clear a sibling's stall from /healthz.
                name=f"{cfg.name}:{id(self) & 0xFFFF:04x}",
            )
        # Whether this batcher's computations actually run on a TPU (the
        # cpu provider can run on a machine whose default backend IS a
        # TPU, so the process-level check is not enough for the Pallas
        # prefill/decode kernels).
        if on_tpu is None:
            on_tpu = jax.default_backend() == "tpu"
        self.on_tpu = on_tpu
        # int8 KV: doubles resident context per HBM GB (~1e-3 relative
        # attention error). The decode-bandwidth win lands on the paged
        # Pallas kernel (in-VMEM dequant, int8-sized HBM streams); XLA
        # paths dequantize panels at chunk scope, so their win is
        # capacity, not per-step traffic. The dense Pallas kernel
        # (opt-in A/B only) predates scales — force the XLA path.
        self.kv_quantize = bool(kv_quantize)
        if self.kv_quantize and not paged and use_pallas:
            use_pallas = False
        if use_pallas is None:
            if paged:
                # The paged kernel is the point of paging on TPU: its VMEM
                # need is one page (K*P*H), and the XLA fallback gathers
                # dense slots×bound panels per layer — the footprint the
                # paged cache exists to avoid.
                use_pallas = self.on_tpu
            else:
                # Dense mode. Measured on v5e: with the cache read-only
                # inside the chunk scan, XLA's dense attention beats the
                # Pallas prefix kernel at both S=512 and S=2048 — the
                # kernel stays available for A/B via
                # PILOTTAI_DECODE_PALLAS=1.
                use_pallas = (
                    os.environ.get("PILOTTAI_DECODE_PALLAS", "").lower()
                    in ("1", "true", "yes")
                    and self.on_tpu
                    and not self.kv_quantize
                    and decode_shapes_ok(
                        self.max_seq_len, cfg.head_dim,
                        jnp.dtype(cache_dtype).itemsize,
                    )
                )
        self.use_pallas = use_pallas
        # Multi-chip serving mesh (ISSUE 13) + degraded-mesh fault
        # domain (ISSUE 16). All mesh-derived state — flash/kv meshes,
        # kv-head sharding, data groups, the collective model and the
        # attribution config — is computed by _apply_mesh_plan so a
        # shard-loss rebuild can re-derive it for the surviving
        # sub-mesh exactly the way boot derived it for the full one.
        self._log = get_logger("engine.batcher")
        self._apply_mesh_plan(mesh, paged=paged)
        # Degraded-mesh ladder: the ordered mesh plans this engine may
        # fall back to when a shard dies (parallel/meshplan.py). Only a
        # real multi-chip mesh gets one — a single-chip engine has no
        # rung to fall to, and "off" pins the boot plan (a shard loss
        # then follows the plain PR 8 device_loop_error path).
        self._mesh_ladder: Optional[MeshPlanLadder] = None
        if (
            mesh is not None and mesh.devices.size > 1
            and mesh_ladder != "off"
        ):
            self._mesh_ladder = MeshPlanLadder(
                mesh,
                rungs=(
                    mesh_ladder
                    if isinstance(mesh_ladder, (list, tuple)) else None
                ),
                name=cfg.name,
            )
            global_metrics.set_gauge("engine.mesh_plan", 0.0)
        # Subword JSON grammar tables (token_bytes [V, L], token_len [V])
        # from json_mask.token_byte_table — None for byte tokenizers,
        # whose 256-entry byte mask is cheaper.
        self.json_tables = (
            tuple(jnp.asarray(t) for t in json_tables)
            if json_tables is not None else None
        )
        # Schema-constrained decoding: compiled DFA bank shared by all
        # slots; device copies refresh lazily when the bank version moves
        # (a few MB uploaded once per NEW schema, not per dispatch).
        self.schema_bank = schema_bank
        self._schema_dev: Optional[Tuple[Any, Any, Any]] = None
        self._schema_seen = -1

        # Speculative decoding: verify-blocks of ``speculate`` tokens per
        # weight pass (engine/decode.py:decode_chunk_spec) — both caches
        # (the paged chunk reads its prefix through the block table).
        self.speculate = speculate if speculate >= 2 else 0
        # A stack of unlike layers (cfg.layer_kinds) runs what was built
        # for it and refuses the rest by name; nothing may run and drop
        # the recurrent state.
        _refuse_recurrent(cfg, "speculative decoding", bool(self.speculate))
        if cfg.layer_kinds and (weight_quant != "none" or kv_quantize):
            raise NotImplementedError(
                f"{cfg.name} ({cfg.family}) is served in bfloat16 only: weight "
                f"and KV quantization are not built for its layers"
            )
        # Warmup sweeps must compile the FULL-prefill buckets — gate the
        # paged index during warmup so warmup prompts (which share
        # prefixes by construction) don't short-circuit into the
        # tail-prefill path.
        self._warming = False
        # HBM budget for transiently materialized dense prefix panels on
        # the paged path (see _dispatch_chunk); beyond it the Pallas
        # per-page kernel takes over.
        self._gather_budget = int(
            os.environ.get("PILOTTAI_GATHER_BUDGET", 5 * 1024**3)
        )
        # Observed tokens-per-block EMA (1.0 = no acceptance; up to D).
        # Drives the in-flight token estimates: dispatching assuming no
        # acceptance wastes whole weight passes on no-op chunks (measured
        # 4x wave time on v5e), assuming full acceptance stalls the
        # pipeline when drafts miss.
        self._spec_rate = 1.0
        # Adaptive draft source (engine/decode.py:_model_drafts): slots
        # whose PER-SLOT acceptance EMA collapses under n-gram drafting
        # (novel text — nothing in history to copy) switch to
        # shallow-layer model drafting; hysteresis keeps flappers stable.
        self.draft_layers = (
            min(draft_layers, cfg.n_layers - 1)
            if draft_layers > 0 and self.speculate else 0
        )
        self._slot_rate = np.full(
            (n_slots,), float(max(self.speculate, 1)), np.float32
        )
        self._draft_on = np.zeros((n_slots,), bool)

        self.cache_dtype = cache_dtype
        # Paged KV: shared page pool + host-side block table/allocator
        # (ops/paged.py). Slots reserve only the pages their prompt+budget
        # needs, so long per-slot capacity doesn't multiply HBM by slots.
        self.paged = paged
        self.page_size = page_size
        if paged:
            # Default pool: the HBM a dense cache would spend on
            # min(max_seq, 2048)-wide slots (+ the scratch page).
            self.num_pages = num_pages or (
                n_slots * min(self.max_seq_len, 2048) // page_size + 1
            )
            # The pool must at least hold one full-capacity request, or
            # admission can never make progress (degenerate configs like a
            # page bigger than the whole pool would otherwise clamp
            # max_seq to 0 and hang every request with no error).
            min_pages = -(-min(self.max_seq_len, 2 * page_size) // page_size)
            if self.num_pages - 1 < min_pages:
                raise ValueError(
                    f"paged KV pool of {self.num_pages} pages x {page_size} "
                    f"can't hold a single request; raise engine_kv_pages "
                    f"or lower engine_page_size"
                )
            # A single request can never need more pages than the pool
            # holds — without this clamp an oversized request blocks
            # admission forever (its can_allocate is never true).
            usable = (self.num_pages - 1) * page_size
            if usable < self.max_seq_len:
                self.max_seq_len = usable
            self.max_pages_per_slot = -(-self.max_seq_len // page_size)
        # Paged-kernel strip width: pages per grid cell
        # (ops/pallas/paged_attention.py). The 8K decode path is
        # grid-cell-latency bound, so the per-cell launch/index floor
        # amortizes over the strip. None → warmup() times {1, 2, 4, 8}
        # on the real pool and keeps the winner (persisted alongside the
        # compile cache); until then a VMEM-safe default serves.
        self._strip_autotune_pending = (
            page_strip is None and paged and self.use_pallas and self.on_tpu
        )
        if paged:
            if page_strip is not None:
                self.page_strip = max(1, min(page_strip,
                                             self.max_pages_per_slot))
            elif self.use_pallas and self.on_tpu:
                self.page_strip = self._max_safe_strip(4)
            else:
                self.page_strip = 1
        else:
            self.page_strip = 1
        # Chunked prefill (VERDICT r5 #6): long cold prompts admit in
        # page-aligned segments, one per device-loop cycle, so live
        # slots' decode chunks interleave instead of stalling behind one
        # monolithic multi-thousand-token prefill. Auto-on for the paged
        # pool (where long contexts live); 0 disables.
        if prefill_chunk is None:
            prefill_chunk = 1024 if paged else 0
        self.prefill_chunk = (
            -(-prefill_chunk // page_size) * page_size
            if paged and prefill_chunk > 0 else 0
        )
        # The longest prompt a monolithic (group) prefill takes: past it
        # prompts segment. Top of warmup's bucket sweep, and the one
        # bucket whose groups get the row ladder (_row_bucket).
        self.full_prefill_cap = self.max_seq_len
        if self.prefill_chunk:
            self.full_prefill_cap = min(
                self.max_seq_len, 2 * self.prefill_chunk
            )
        # In-flight segmented admission: [slot_idx, request, tokens_done]
        # (device thread only; the slot is excluded from free lists until
        # the final segment installs it). _seg_epoch is the allocator
        # epoch it was prepared against — _advance_segment re-admits from
        # scratch if a rebuild swapped the pool out from under it.
        self._segmenting: Optional[List[Any]] = None
        self._seg_epoch = 0
        # Automatic prefix caching. Dense cache: panel-copy store
        # (engine/prefix_cache.py). Paged cache: block-granular radix of
        # refcounted pages (engine/page_prefix.py) — shared prefixes are
        # MAPPED into new slots' block tables, never copied, and
        # granularity is per page rather than per whole prompt.
        self.prefix_store = None
        self.page_index = None
        # A shared prefix is only KV; a recurrent layer would need a
        # snapshot of its state at the boundary, which nothing takes. So
        # a model with recurrent state builds no store and no index, and
        # every admission that would have looked is counted.
        self._prefix_bypass = bool(cfg.recurrent and prefix_cache > 0)
        if self._prefix_bypass:
            prefix_cache = 0
        if prefix_cache > 0:
            if paged:
                self.page_index = PagePrefixIndex(
                    page_size,
                    # Cap pinned pages at a quarter of the allocatable
                    # pool so caching can never crowd out admissions'
                    # working set (admission pressure can also reclaim
                    # on demand via evict()).
                    capacity_pages=max((self.num_pages - 1) // 4, 1),
                )
            else:
                self.prefix_store = PrefixStore(
                    capacity=prefix_cache,
                    # Entry floor: prompts shorter than this never cache
                    # (engine_prefix_min_len; None = the prefill bucket
                    # floor). Prompts below it get a one-shot warning at
                    # export/pre-warm time instead of silently never
                    # hitting (_warn_min_len).
                    min_len=(
                        prefix_min_len if prefix_min_len is not None
                        else min_bucket
                    ),
                    # Prompt-length cap bounds HBM: a 2048-row 8B entry
                    # is ~540 MB; capacity x 1024 rows keeps the store
                    # around 0.5 GB worst case next to 8 GB of weights
                    # on a 16 GB chip.
                    max_len=min(max_seq_len or cfg.max_seq_len, 1024),
                    policy=kvcache_policy,
                )
        # Global KV cache tier (engine/kvcache/): ONE lookup over the
        # dense store and the paged radix, plus (when kvcache_host_mb >
        # 0) the host-RAM cold tier — evictions spill via async D2H and
        # session resumes restore via async H2D instead of
        # re-prefilling. Greedy output is byte-identical tier on/off
        # (tests/test_kvcache.py).
        self.kvcache: Optional[KVCacheIndex] = None
        if prefix_cache > 0:
            self.kvcache = KVCacheIndex(
                prefix_store=self.prefix_store,
                page_index=self.page_index,
                page_size=page_size,
                host_bytes=int(kvcache_host_mb) * 1024 * 1024,
                policy=kvcache_policy,
                get_cache=lambda: self.cache,
                min_len=prefix_min_len,
                # Host-tier restores upload already split over the
                # 'model' axis when the pool is (ISSUE 13) — the
                # restore scatter then consumes them shard-local.
                place=self._restore_place,
            )
        # Restored page chains awaiting their device-thread pool write
        # (engine/kvcache/index.py:PendingRestore; appended under the
        # slot lock at lookup time, drained by _apply_restores before
        # any dispatch can read the pages).
        self._pending_restores: List[Any] = []
        # Slot table / gen / release / first_reads / allocator are shared
        # between the device thread, the reader thread (completion) and
        # the admission-prep thread (selection) — the lock exists before
        # the first _rebuild_device_state, which swaps the allocator and
        # bumps the epoch under it.
        self._lock = threading.Lock()
        self._alloc_epoch = 0  # bumped by _rebuild_device_state
        self._rebuild_device_state()
        self._slots: List[Optional[_Slot]] = [None] * n_slots
        # Admission generation per slot: chunk results are stamped with the
        # generation vector at dispatch, so a chunk dispatched before a slot
        # was re-admitted can never fold tokens into the new occupant.
        self._gen: List[int] = [0] * n_slots
        self._pending: "queue.Queue[GenRequest]" = queue.Queue()
        # Device-thread FIFO the pending queue drains into (page-gated
        # admission peeks at the head without losing submission order).
        self._backlog: deque = deque()
        self._release: List[int] = []  # slots to force-stop at next admission
        # (group_slots, first_tokens device array) awaiting lazy host read
        self._first_reads: deque = deque()
        self._drain_queued = False  # a drain sentinel is in _results
        # Dispatched chunks awaiting host read. Bounded so the device
        # thread can't run unboundedly ahead of completions. The depth is
        # the one knob (engine_pipeline): each item carries its own
        # _HostCopy, so any depth ≥ 1 pipelines — nothing about the
        # read-back is structural anymore.
        self._results: "queue.Queue" = queue.Queue(maxsize=self.PIPELINE_DEPTH)
        # Overlapped admission (PERF_NOTES r8): a prep thread runs group
        # selection / page allocation / staging-buffer packing and hands
        # _PreparedAdmission items over this queue, so the device thread
        # only enqueues the prefill dispatch behind in-flight chunks.
        # False = the seed's inline path (same code, same thread).
        self.overlap_admission = bool(overlap_admission)
        self._prepped: "queue.Queue" = queue.Queue()
        self._prep_depth = 2            # prepared waves ahead, max
        self._prep_reserved: set = set()  # slots picked but not installed
        self._prepped_reqs = 0          # requests inside _prepped (approx)
        self._seg_pending = False       # a segmentation owns admission
        self._prep_gate = threading.Lock()  # quiesces prep for requeues
        self._prep_wake = threading.Event()
        # Host-gap telemetry: time from the last fold-complete (or
        # prefill feed) to the next chunk dispatch while NOTHING was in
        # flight — the host-side bubble the overlap work exists to
        # close. 0 whenever the pipeline still held work.
        self._inflight = 0
        self._last_fold_done: Optional[float] = None
        self._last_prefill_t: Optional[float] = None
        # Device-time attribution (obs/attribution.py): decode time is
        # estimated as the fold-to-fold interval minus the measured idle
        # gap and the prefill enqueue walls that landed inside it
        # (accumulated here between folds, under the lock).
        self._last_attr_mark: Optional[float] = None
        self._prefill_since_fold = 0.0
        # (Live MFU/attribution gauges configure inside _apply_mesh_plan
        # — the FLOPs formula is constant but n_chips/mesh_axes follow
        # the ACTIVE plan across degradations.)
        # (engine.queue_depth is declared at obs import — the exported
        # surface exists from process boot; the batcher only sets it.)
        if self.max_queue_depth is not None:
            global_metrics.set_gauge(
                "engine.max_queue_depth", float(self.max_queue_depth)
            )
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._reader: Optional[threading.Thread] = None
        self._prep_thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------------ #
    # Lifecycle
    # ------------------------------------------------------------------ #

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="pilottai-device-loop", daemon=True
        )
        self._reader = threading.Thread(
            target=self._read_loop, name="pilottai-reader", daemon=True
        )
        self._thread.start()
        self._reader.start()
        if self.overlap_admission:
            self._prep_thread = threading.Thread(
                target=self._prep_loop, name="pilottai-admit-prep",
                daemon=True,
            )
            self._prep_thread.start()
        if self._watchdog is not None:
            self._watchdog.start()

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        self._prep_wake.set()
        if self._watchdog is not None:
            self._watchdog.stop()
        if self._prep_thread is not None:
            self._prep_thread.join(timeout=60)
            self._prep_thread = None
        if self._thread is not None:
            self._thread.join(timeout=60)
            self._thread = None
        if self._reader is not None:
            self._reader.join(timeout=60)
            self._reader = None
        # Restores staged but not yet scattered: apply them now (threads
        # are joined — this thread owns the device state) so a restart
        # can never serve a registered chain whose pages were never
        # written.
        try:
            self._apply_restores()
        except Exception:  # noqa: BLE001 — best-effort on shutdown
            pass
        # Quiesce the device: chunks dispatched right before stop may still
        # be executing, and tearing the process down mid-computation
        # crashes the backend's thread pool at exit.
        try:
            if not self.cache.lengths.is_deleted():
                jax.block_until_ready(self.cache.lengths)
        except Exception:  # noqa: BLE001 — best-effort quiesce
            pass
        self._prewarm_queue.clear()  # advisory: staged pre-warms drop
        # Fail any stranded requests.
        stranded = list(self._backlog)
        self._backlog.clear()
        if self._segmenting is not None:  # mid-chunked-prefill request
            stranded.append(self._segmenting[1])
            if self.alloc is not None:
                self.alloc.release(self._segmenting[0])
            self._segmenting = None
        self._seg_pending = False
        while True:  # prepared-but-never-dispatched admissions
            try:
                item = self._prepped.get_nowait()
            except queue.Empty:
                break
            # Release their page allocations too: a stranded prep's
            # pages otherwise survive into the next start() and the
            # first selection that reuses the slot trips allocate()'s
            # held-pages invariant — admission wedges permanently.
            if isinstance(item, _SegmentStart):
                stranded.append(item.seg[1])
                if self.alloc is not None:
                    self.alloc.release(item.seg[0])
            else:
                stranded.extend(req for _, req in item.group)
                if self.alloc is not None:
                    for idx, _ in item.group:
                        self.alloc.release(idx)
        self._prepped_reqs = 0
        self._prep_reserved.clear()
        while True:
            try:
                stranded.append(self._pending.get_nowait())
            except queue.Empty:
                break
        for req in stranded:
            if not req.future.done():
                req.future.set_exception(RuntimeError("engine stopped"))
        for idx, slot in enumerate(self._slots):
            if slot is None:
                continue
            if not slot.request.future.done():
                slot.request.future.set_exception(RuntimeError("engine stopped"))
            if self.alloc is not None:
                self.alloc.release(idx)
        self._slots = [None] * self.n_slots

    # ------------------------------------------------------------------ #
    # Device watchdog (reliability/watchdog.py)
    # ------------------------------------------------------------------ #

    def _beat(self) -> None:
        """Progress heartbeat: folds, prefill installs and segment
        advances call this so the watchdog can tell a hung dispatch from
        a healthy slow one (any thread; a plain float store). The mesh
        ladder's per-shard table beats alongside: a completed fold
        proves the whole active mesh answered, so a shard whose stamp
        stops moving (frozen by the mesh.shard_loss hang variant, or a
        real per-device probe) stands out against beating siblings."""
        wd = self._watchdog
        if wd is not None:
            wd.beat()
        ladder = self._mesh_ladder
        if ladder is not None:
            ladder.beat_all()
            # Shard-stale triage on the HEALTHY path too: a shard whose
            # stamp stopped moving while the engine keeps folding (the
            # chip stopped answering but nothing wedged — the hang
            # variant of mesh.shard_loss, or a production per-device
            # probe) never trips the engine watchdog, so the fold
            # heartbeat is where it stands out against its siblings.
            if wd is not None:
                stale = ladder.stale(wd.stall_s)
                if stale and len(stale) < len(ladder.surviving()):
                    for idx in stale:
                        ladder.mark_lost(idx)
                        global_metrics.inc("engine.shard_losses")
                    self._log.error(
                        "shard heartbeat(s) %s stale while the engine "
                        "keeps serving — treating as shard loss", stale,
                    )
                    self._rebuild_requested = "shard_loss"
                    self._wake.set()

    def _watchdog_has_work(self) -> bool:
        """Anything in flight or queued? (watchdog thread; lock-free
        approximation — a one-poll-late answer only shifts the stall
        clock by poll_s). Warmup is excluded: its compile sweeps stall
        heartbeats for legitimate minutes."""
        if self._warming:
            return False
        return (
            self._inflight > 0
            or any(s is not None for s in self._slots)
            or bool(self._backlog)
            or self._pending.qsize() > 0
            or self._segmenting is not None
            # Prepared-but-not-installed admissions: during a PREFILL
            # dispatch the group's slots live only in _prep_reserved
            # (slots install after admit_group returns, _prepped_reqs
            # decrements at pop) — without these a hung prefill on an
            # otherwise idle engine would never trip the watchdog.
            or bool(self._prep_reserved)
            or self._prepped_reqs > 0
        )

    def _on_watchdog_stall(self, info: Dict[str, Any]) -> None:
        """Stall diagnostics (watchdog thread): the black-box dump is
        the flight recorder for "what was the engine doing when it
        hung"; the ladder counts the stall as a fault.

        Per-shard triage (ISSUE 16): when the mesh ladder's heartbeat
        table shows SOME shards stale while siblings kept beating, the
        stall is a shard loss, not a whole-engine hang — mark the stale
        shards lost and request a shard_loss rebuild. The device thread
        consumes the request at its next cycle (when the hung dispatch
        resolves or raises); until then the watchdog's normal 503
        containment holds."""
        ladder = self._mesh_ladder
        if ladder is not None and self._watchdog is not None:
            stale = ladder.stale(self._watchdog.stall_s)
            info = dict(info, stale_shards=stale)
            if stale and len(stale) < len(ladder.surviving()):
                for idx in stale:
                    ladder.mark_lost(idx)
                    global_metrics.inc("engine.shard_losses")
                self._log.error(
                    "watchdog: shard heartbeat(s) %s stale while "
                    "siblings beat — treating as shard loss", stale,
                )
                self._rebuild_requested = "shard_loss"
        global_steps.record("engine.watchdog_stall", **info)
        global_blackbox.dump("watchdog_stall", **info)
        self.degrade.record_fault("stall")

    # ------------------------------------------------------------------ #
    # Mesh plan (ISSUE 13 boot layout + ISSUE 16 degraded re-planning)
    # ------------------------------------------------------------------ #

    def _apply_mesh_plan(self, mesh: Optional[Any],
                         paged: Optional[bool] = None) -> None:
        """Derive every mesh-dependent piece of engine state from
        ``mesh`` — at boot (the ISSUE 13 layout rules) and again on a
        shard-loss re-plan, so the surviving sub-mesh is configured by
        exactly the code path that configured the boot mesh.

        ``mesh`` drives four things beyond the flash prefill:
        * the KV pool / dense cache panels are CREATED on their
          sharded layout (_rebuild_device_state → place_kv_cache):
          kv-heads over 'model', dense slots over 'data' — the paged
          8B pool stops being resident whole on any one chip;
        * the paged Pallas decode kernel runs per-shard under
          shard_map (kv_mesh → decode_chunk/decode_chunk_spec);
        * admission replicates over the 'data' axis: slots partition
          into ``data_groups`` contiguous groups and
          _free_slot_indices interleaves selection across them;
        * per-dispatch collective time is attributed per axis
          (parallel/collectives.py → engine.collective_frac[.axis]).
        """
        if paged is None:
            paged = self.paged
        cfg = self.cfg
        # Prefill's flash kernel runs per-shard under shard_map
        # (ops/pallas/flash_attention.py). One device → plain
        # single-chip dispatch inside _full_seq_block.
        self.flash_mesh = (
            mesh if mesh is not None and mesh.devices.size > 1 else None
        )
        self.mesh = self.flash_mesh
        kv_axes = kv_shard_axes(
            self.mesh, n_kv_heads=cfg.n_kv_heads, n_slots=self.n_slots
        )
        self.kv_heads_sharded = kv_axes["heads"] is not None
        self.data_groups = int(kv_axes["data_groups"])
        # Fewest rows an admission dispatch may have (_row_bucket): the
        # sharded flash prefill splits the batch over the data axes and
        # takes the dense fallback when they do not divide it
        # (flash_sharding_ok), so on a mesh the row ladder starts at
        # their product, not at 1.
        shape = dict(self.mesh.shape) if self.mesh is not None else {}
        self.row_floor = int(shape.get("data", 1) * shape.get("fsdp", 1))
        # The dense Pallas decode kernel (opt-in A/B path,
        # PILOTTAI_DECODE_PALLAS) has no shard_map wrapper: on a mesh
        # whose dense panels shard it cannot lower per-shard — demote
        # to the XLA dense path, which GSPMD partitions fine (and which
        # beats the kernel at serving sizes anyway). The demotion only
        # ever turns the kernel OFF, so re-applying on a smaller mesh
        # never resurrects it mid-serving.
        if (
            self.mesh is not None and not paged and self.use_pallas
            and (kv_axes["heads"] is not None or kv_axes["slots"] is not None)
        ):
            self.use_pallas = False
        self.kv_mesh = None
        if (
            self.mesh is not None and paged and self.use_pallas
            and paged_sharding_ok(self.mesh, self.n_slots, cfg.n_kv_heads)
        ):
            self.kv_mesh = self.mesh
        # KV placement mesh: the pool/panels shard per kv_shard_axes —
        # EXCEPT when the paged Pallas kernel will run but cannot run
        # sharded (slots don't divide the data axes, or a seq axis is
        # present): a model-sharded pool under the UNWRAPPED kernel
        # would force a whole-pool gather (or fail to lower) on every
        # dispatch, so the pool stays replicated and only the weights
        # shard. The XLA fallback path partitions any layout.
        self._kv_place_mesh = self.mesh
        if paged and self.use_pallas and self.kv_mesh is None:
            self._kv_place_mesh = None
            if self.kv_heads_sharded:
                # Report the EFFECTIVE placement: an operator debugging
                # HBM pressure must not be told the pool is split across
                # TP shards while it is resident whole on every chip.
                self.kv_heads_sharded = False
                self._log.warning(
                    "paged Pallas kernel cannot run sharded on this "
                    "mesh; KV pool stays replicated — only weights shard"
                )
        self.collective_model = CollectiveModel.for_mesh(
            self.mesh, cfg,
            platform="tpu" if self.on_tpu else "cpu",
            paged=paged, kv_quantize=self.kv_quantize,
        )
        # Live MFU/attribution gauges: the model's FLOPs formula, the
        # device's published peak and the ACTIVE mesh shape — the same
        # ModelConfig.flops_per_token() bench.py uses, so live and
        # bench MFU reconcile by construction, and a degraded engine's
        # MFU is normalized to the chips it still has.
        device_kind = "cpu"
        if self.on_tpu:
            device = (
                self.mesh.devices.flat[0] if self.mesh is not None
                else jax.devices()[0]
            )
            device_kind = device.device_kind
        global_attribution.configure(
            flops_per_token=cfg.flops_per_token(),
            device_kind=device_kind,
            n_chips=(
                int(self.mesh.devices.size) if self.mesh is not None else 1
            ),
            mesh_axes=(
                tuple(str(a) for a in self.mesh.axis_names)
                if self.mesh is not None else ()
            ),
        )

    def _replan_mesh(self) -> None:
        """Shard-loss re-plan (device thread, inside the rebuild):
        walk the ladder to the first rung fitting the surviving
        devices, re-derive all mesh state for it and re-place the
        weights on the new plan. Raises ``MeshLadderExhausted`` when no
        rung fits — the caller's recovery contract already failed the
        in-flight requests with the original exception by then.

        Weight re-placement re-uses each leaf's own partition spec on
        the new mesh (axis names are constant across rungs). Under
        simulated loss (CPU virtual devices, chaos tests) every shard
        is still readable and the device_put is a plain reshard; a
        production backend that lost the only holder of a 'model' shard
        must reload those leaves from the host checkpoint instead —
        see SERVING.md's failure-domain table."""
        ladder = self._mesh_ladder
        assert ladder is not None
        t0 = time.perf_counter()
        old_plan = plan_label(ladder.plan())
        new_mesh = ladder.replan()
        self._apply_mesh_plan(new_mesh)
        from jax.sharding import NamedSharding

        def _put(leaf):
            spec = getattr(getattr(leaf, "sharding", None), "spec", None)
            if spec is not None:
                return jax.device_put(leaf, NamedSharding(new_mesh, spec))
            # No NamedSharding → the leaf was never committed to the
            # old mesh (boot leaves params uncommitted and lets GSPMD
            # place them). Leave it uncommitted: committing it to any
            # single device here would conflict with the new mesh's
            # committed cache at the next jit dispatch.
            return leaf

        self.params = jax.tree_util.tree_map(_put, self.params)
        # Dense device-resident prefix panels live on the OLD mesh's
        # layout (possibly on the dead shard) — drop them; the host
        # tier's entries survive and restore onto the new layout via
        # _restore_place. (The paged index is cleared by every rebuild
        # already.)
        if self.prefix_store is not None:
            # clear(), not eviction: spilling would D2H panels resident
            # on a device that may be the dead one.
            self.prefix_store.clear()
        global_metrics.set_gauge("engine.mesh_plan", float(ladder.rung))
        global_metrics.observe(
            "engine.mesh_rebuild_ms", (time.perf_counter() - t0) * 1e3
        )
        self._log.warning(
            "mesh degraded: %s -> %s (rung %d, lost=%s)",
            old_plan, plan_label(ladder.plan()), ladder.rung, ladder.lost(),
        )

    def _max_safe_strip(self, want: int) -> int:
        """Largest strip ≤ ``want`` the paged kernel's VMEM model admits
        for this pool (``ops/pallas/paged_attention.py:max_safe_strip``)."""
        from pilottai_tpu.ops.pallas.paged_attention import max_safe_strip

        return max_safe_strip(
            want, self.max_pages_per_slot, self.page_size,
            self.cfg.n_kv_heads, self.cfg.head_dim,
            1 if self.kv_quantize else jnp.dtype(self.cache_dtype).itemsize,
            self.kv_quantize,
        )

    def _strip_autotune_keys(self) -> Tuple[str, str]:
        """(key, wide_key) for the persisted page-strip winner. The
        WEIGHT quantization mode (and the int4 scale-group width) is
        part of both: the strip timing runs with the weight set resident
        in HBM, so a winner timed under bf16 weights reflects different
        bandwidth contention than one under int4 — reusing it silently
        across a quant-mode change was the ISSUE 14 satellite bug.
        'none' adds no tag, so pre-existing cache entries stay valid for
        unquantized deployments."""
        mesh_tag = (
            ":mesh" + "x".join(
                f"{a}{s}" for a, s in sorted(dict(self.kv_mesh.shape).items())
                if s > 1
            )
            if self.kv_mesh is not None else ""
        )
        # The scale group only shapes int4 weights — tagging it under
        # int8 would spuriously invalidate cached winners when an
        # operator carries a group setting across modes.
        if self.weight_quant == "int4":
            wq_tag = f":wq{self.weight_quant}:g{self.quant_group}"
        elif self.weight_quant not in (None, "none"):
            wq_tag = f":wq{self.weight_quant}"
        else:
            wq_tag = ""
        key = (
            f"paged_strip:{self.cfg.name}:P{self.page_size}"
            f":nb{self.max_pages_per_slot}:K{self.cfg.n_kv_heads}"
            f":H{self.cfg.head_dim}:hd{self.cfg.n_heads}"
            f":q{int(self.kv_quantize)}:B{self.n_slots}{mesh_tag}{wq_tag}"
        )
        wide_key = (
            f"paged_strip:{self.cfg.name}:P{self.page_size}"
            f":K{self.cfg.n_kv_heads}:H{self.cfg.head_dim}"
            f":hd{self.cfg.n_heads}:q{int(self.kv_quantize)}"
            f":B{self.n_slots}{mesh_tag}{wq_tag}"
        )
        return key, wide_key

    def _autotune_page_strip(self) -> None:
        """Pick the paged-kernel strip width by timing the real kernel on
        the real pool (device thread idle — called from warmup before the
        compile sweep, so the decode ladder compiles against the winner).
        The result persists alongside the XLA compile cache: a warm
        restart reloads the strip its cached executables were built with
        instead of re-timing and recompiling."""
        from pilottai_tpu.utils.compile_cache import (
            load_autotune,
            store_autotune,
        )
        # The key deliberately carries NO decode-chunk terms: the timing
        # exercises the attention kernel alone, so two deployments that
        # differ only in chunk_size / chunk_policy / chunk buckets must
        # share one persisted winner (re-timing on every chunk retune
        # was a measured cold-start tax). The wide key additionally
        # drops the per-slot block count: the strip winner amortizes a
        # per-cell launch floor that is nb-insensitive, so a max_seq
        # change reuses the winner (clamped to the new VMEM-safe range)
        # instead of re-timing.
        # Sharded dispatch times the shard_map-wrapped kernel over
        # per-shard heads/slots — a different launch grid than single
        # chip, so the winner is keyed by mesh shape (empty off-mesh:
        # existing single-chip cache entries stay valid).
        key, wide_key = self._strip_autotune_keys()
        cached = load_autotune(key)
        if cached is None:
            cached = load_autotune(wide_key)
        if cached is not None:
            self.page_strip = self._max_safe_strip(int(cached))
            self._log.info(
                "paged strip %d (autotune cache)", self.page_strip
            )
            return
        try:
            n_blocks = self.max_pages_per_slot
            B = self.n_slots
            # Full-occupancy worst case: every slot at capacity, pages
            # cycling over the real pool (contents are zeros — timing
            # only; the kernel's work is shape-, not value-, dependent).
            tbl = np.arange(B * n_blocks, dtype=np.int32).reshape(
                B, n_blocks
            ) % max(self.num_pages - 1, 1)
            tbl_j = jnp.asarray(tbl)
            last = jnp.full((B,), self.max_seq_len - 1, jnp.int32)
            q = jnp.zeros(
                (B, self.cfg.n_heads, self.cfg.head_dim), self.cfg.dtype
            )
            k_pool, v_pool = self.cache.layers[0]
            sc = None if self.cache.scales is None else self.cache.scales[0]
            # Time the kernel the dispatch path will actually run: on a
            # serving mesh the pool is model-sharded and the unwrapped
            # pallas_call must never see it (it would gather the whole
            # pool per rep — or fail to lower — and pick the strip from
            # gather-dominated timings).
            kernel = _paged_kernel_for(self.kv_mesh)
            candidates = sorted({
                self._max_safe_strip(s) for s in (1, 2, 4, 8)
            })
            timings = {}
            for strip in candidates:
                def run(strip=strip):
                    return kernel(
                        q, k_pool, v_pool, tbl_j, last,
                        n_blocks=n_blocks, n_strip=strip,
                        softcap=self.cfg.attn_softcap,
                        k_scales=None if sc is None else sc[0],
                        v_scales=None if sc is None else sc[1],
                    )
                jax.block_until_ready(run())  # compile outside the timer
                reps = 10
                t0 = time.perf_counter()
                for _ in range(reps):
                    out = run()
                jax.block_until_ready(out)
                timings[strip] = (time.perf_counter() - t0) / reps
            best = min(timings, key=timings.get)
            self.page_strip = best
            store_autotune(key, best)
            store_autotune(wide_key, best)
            self._log.info(
                "paged strip autotune: %s -> strip %d",
                {s: f"{t * 1e3:.2f}ms" for s, t in sorted(timings.items())},
                best,
            )
        except Exception as exc:  # noqa: BLE001 — best-effort off the chip
            # On a TPU a failure here includes "the kernel that is about
            # to serve does not compile": swallowing it would boot an
            # engine whose decode path is already known broken.
            if self.on_tpu:
                raise
            self._log.warning(
                "paged strip autotune failed (%s); keeping strip %d",
                exc, self.page_strip,
            )

    def warmup(self, prompt_lens: Optional[Tuple[int, ...]] = None) -> None:
        """Compile the admission path for EVERY prefill bucket at every
        row count its groups can run at, plus the decode chunk, up
        front, so steady-state serving never waits on the compiler. A
        full prefill has ``_row_bucket(len(group), bucket)`` rows, so
        each bucket is swept once per rung (one below the top bucket,
        the whole ladder at it) with a wave of that many requests
        submitted together: the same batched write/sample/admit shapes
        any production group of 1..``admit_batch`` hits.

        With chunked prefill active, buckets past the segmentation
        threshold never run as monolithic group prefills at serve time —
        and must not compile as such here either: an admit_batch×8192
        prefill executable alone exceeds a v5e's HBM next to 8B int8
        weights (measured: 17.97G of 15.75G). Instead the sweep stops at
        the threshold and one long prompt warms the segment ladder
        (extend_prompt_paged variants + the final tail admission, one
        row each)."""
        if prompt_lens is None:
            cap = self.full_prefill_cap
            prompt_lens = tuple(sorted(
                {self._bucket(n) for n in range(1, cap + 1)}
            ))
            if self.prefill_chunk and self.max_seq_len > cap:
                prompt_lens = prompt_lens + (self.max_seq_len - 8,)
        # Strip autotune BEFORE the sweep: the sweep compiles the decode
        # ladder, and it must compile against the strip that will serve.
        if self._strip_autotune_pending:
            self._strip_autotune_pending = False
            self._autotune_page_strip()
        self._warming = True
        try:
            # Adaptive chunking widens the decode grid to
            # (chunk bucket x prefix bound): each prompt bucket runs one
            # warmup request per chunk bucket (pinned via _force_chunk —
            # the policy alone would pick the smallest bucket for these
            # 2-token requests), so a serve-time bucket switch never
            # waits on the compiler. Prompt ids shift per request so the
            # repeats don't short-circuit into the prefix-cache tail
            # path, which would skip the full-prefill compile.
            for plen in prompt_lens:
                plen = min(plen, self.max_seq_len - 8)
                for ci, cb in enumerate(self.chunk_buckets):
                    self._force_chunk = cb
                    self._warm_wave(plen, 1, shift=ci)
                if plen > self.full_prefill_cap:
                    continue  # segmented: never a group, always one row
                # The lone requests above ran the bucket's lowest rung;
                # the decode grid does not depend on the rows admitted.
                bucket = self._bucket(plen)
                rungs = sorted({
                    self._row_bucket(n, bucket)
                    for n in range(1, self.admit_batch + 1)
                })
                for rows in rungs[1:]:
                    self._warm_wave(
                        plen, rows, shift=len(self.chunk_buckets)
                    )
        finally:
            self._warming = False
            self._force_chunk = None

    def _warm_wave(self, plen: int, n: int, shift: int) -> None:
        """Admit ``n`` warmup prompts of ``plen`` tokens as ONE group and
        wait for them."""
        reqs = [
            GenRequest(
                prompt_ids=list(range(2 + shift + i, 2 + shift + i + plen)),
                max_new_tokens=2,
            )
            for i in range(n)
        ]
        self._submit_together(reqs)
        for req in reqs:
            req.future.result(timeout=900)

    def _submit_together(self, reqs: List[GenRequest]) -> None:
        """Submit ``reqs`` so that one selection takes them all (they
        still split by prefix hit, pages and ``admit_batch``). Under the
        slot lock, which selection holds from its drain of the
        submission queue on, so no selection sees part of them; and only
        once as many slots are selectable (a finished slot is not until
        the device thread's next cycle), or the group would split at the
        last free slot. Warmup's waves, whose rung would otherwise stay
        uncompiled; after 5 s they go in as they are."""
        give_up = time.monotonic() + 5.0
        while True:
            with self._lock:
                if (
                    len(self._selectable_slots_locked()) >= len(reqs)
                    or time.monotonic() >= give_up
                ):
                    for req in reqs:
                        self.submit(req)
                    return
            time.sleep(0.002)

    # ------------------------------------------------------------------ #
    # Submission (any thread)
    # ------------------------------------------------------------------ #

    def queue_depth(self) -> int:
        """Requests submitted but not yet admitted to a slot (any thread;
        approximate — the containers move concurrently). Prepared-but-
        not-yet-dispatched admissions still count: they hold no slot."""
        return (
            self._pending.qsize() + len(self._backlog) + self._prepped_reqs
        )

    @property
    def watchdog_source(self):
        """This engine's ``EngineHealth`` source name (None without a
        watchdog) — the serving cell checks per-replica health by it."""
        return self._watchdog.name if self._watchdog is not None else None

    def routing_signals(self) -> Dict[str, Any]:
        """The replica-side routing signals of ISSUE 11, as one cheap
        snapshot: queue depth + shed-limit fraction, degrade rung and
        watchdog verdict. Per-class SLO burn comes from the cell's own
        per-replica tracker (in-process) or the control-plane heartbeat
        (remote) — the engine doesn't know its replica's service
        classes."""
        depth = self.queue_depth()
        # Without admission control there is no hard shed depth; 8 slots'
        # worth of backlog per slot is the soft norm the router uses to
        # compare replicas (never to shed — only max_queue_depth sheds).
        limit = self.max_queue_depth or 8 * self.n_slots
        return {
            "queue_depth": depth,
            "queue_frac": depth / max(limit, 1),
            "degrade_level": self.degrade.level(),
            "healthy": self._watchdog is None or not self._watchdog.stalled,
            # Degraded-mesh rung (0 = boot plan): the cell's router
            # down-scores replicas serving on a sub-mesh, and the cell
            # prefers migrating sessions off them.
            "mesh_rung": (
                self._mesh_ladder.rung
                if self._mesh_ladder is not None else 0
            ),
        }

    def export_session_kv(self, session_id: str):
        """Cross-replica migration, source side (ISSUE 11): the
        session's KV lineage in the host tier's transfer format, taken
        under the slot lock so no spill/restore interleaves. None when
        the KV cache tier is off or the session is unknown — callers
        treat that as 'nothing to move' (the target re-prefills)."""
        _refuse_recurrent(self.cfg, "export_session_kv")
        if self.kvcache is None or self.kvcache.host is None:
            return None
        with self._lock:
            return self.kvcache.export_session(session_id)

    def import_session_kv(self, export) -> Dict[str, int]:
        """Cross-replica migration, target side: land the exported
        entries in this engine's host tier so the session's next turn
        restores here instead of re-prefilling. Returns the accepted
        entry/token counts (budget pressure may reject some)."""
        _refuse_recurrent(self.cfg, "import_session_kv")
        if self.kvcache is None or self.kvcache.host is None or not export:
            return {"accepted": 0, "tokens": 0, "rejected": 0}
        with self._lock:
            return self.kvcache.import_session(export)

    def export_request_kv(self, prompt_ids, session_id: Optional[str] = None):
        """Prefill→decode handoff, source side (ISSUE 19): package the
        KV a just-prefilled request left in this engine's cache tier —
        the admission-time dense panel, pinned page chain, or host
        spills covering the prompt — as the same checksummed wire
        frames session migration uses. Copy-only (no session pin
        moves): a failed handoff leaves this replica able to serve the
        colocated fallback from its own warm cache. Taken under the
        slot lock so the export overlaps only between device steps,
        never mid-gather. None when the cache tier is off or holds
        nothing for this prompt — the caller serves colocated."""
        _refuse_recurrent(self.cfg, "export_request_kv")
        if self.kvcache is None:
            return None
        with self._lock:
            return self.kvcache.export_request(
                tuple(prompt_ids), session_id=session_id
            )

    def import_request_kv(self, export) -> Dict[str, int]:
        """Prefill→decode handoff, target side: land the prefilled
        request's KV in this engine's host tier so admitting the
        request here restores it (``_PreparedAdmission`` in prefix /
        prefix_paged mode — decode resumes, no re-prefill). Same
        integrity gate as session import: a corrupt frame rejects,
        counts ``engine.kvcache.integrity_failures``, and the request
        falls back to colocated serving."""
        _refuse_recurrent(self.cfg, "import_request_kv")
        if self.kvcache is None or self.kvcache.host is None or not export:
            return {"accepted": 0, "tokens": 0, "rejected": 0}
        # Deliberately NOT under the batcher lock: the import only
        # writes the host tier (which takes its own lock per op), and
        # holding the admission lock through checksums + array copies
        # of a whole prompt's KV would stall the decode loop this tier
        # exists to keep smooth.
        return self.kvcache.import_session(export)

    def saturated(self) -> bool:
        return (
            self.max_queue_depth is not None
            and self.queue_depth() >= self.max_queue_depth
        )

    def _shed_reason(self, request: GenRequest) -> Optional[str]:
        """Why this submit must shed, or None. Per-SLO-class thresholds:
        interactive traffic sheds at the full ``max_queue_depth``; any
        other class (batch) at ``batch_shed_frac`` of it — under backlog
        pressure the fan-out branches nobody is watching drop before the
        stream a human is. The degradation ladder's last rung sheds
        batch outright: a faulting engine's remaining capacity defends
        the interactive SLO class.

        Only the literal ``batch`` class gets the early-shed policy:
        ``slo_class`` is a free-form client string (the HTTP edge
        validates it, direct SDK callers may not), and treating every
        unknown string as batch would silently early-shed typo'd or
        deployment-defined latency-sensitive classes."""
        cls = self._shed_class(request)
        if (
            cls == "batch"
            and self.degrade.level() >= degrade_levels.SHED_BATCH
        ):
            return (
                f"engine degraded to level {degrade_levels.SHED_BATCH} "
                f"({degrade_levels.LEVEL_NAMES[degrade_levels.SHED_BATCH]}); "
                f"shedding {cls}-class requests"
            )
        limit = self.max_queue_depth
        if limit is None:
            return None
        if cls == "batch":
            limit = max(1, int(limit * self.batch_shed_frac))
        depth = self.queue_depth()
        if depth >= limit:
            return (
                f"engine queue depth {depth} at configured "
                f"{cls}-class limit {limit}; shedding"
            )
        return None

    @staticmethod
    def _shed_class(request: GenRequest) -> str:
        """Shed-policy class: ``batch``, ``interactive``, or ``other``
        (unknown strings — interactive semantics, but a bounded metrics
        key so free-form client strings can't grow the registry)."""
        cls = request.slo_class or "interactive"
        return cls if cls in ("interactive", "batch") else "other"

    def submit(self, request: GenRequest) -> Future:
        # Admission control first: a shed request must cost nothing — no
        # queue entry, no truncation work, no future resolution. Raising
        # (rather than failing the future) lets the HTTP edge turn this
        # into a structured 429 before any engine state exists for it.
        shed = self._shed_reason(request)
        if shed is not None:
            cls = self._shed_class(request)
            global_metrics.inc("engine.shed")
            global_metrics.inc(f"engine.shed.{cls}")
            global_metrics.set_gauge(
                "engine.queue_depth", float(self.queue_depth())
            )
            global_steps.record(
                "engine.shed",
                queue_depth=self.queue_depth(),
                max_queue_depth=self.max_queue_depth,
                slo_class=cls,
                trace_id=request.trace_id,
            )
            raise EngineOverloaded(shed)
        # A request born expired (edge queueing, client retry storms)
        # fails immediately instead of wasting a prefill.
        if (
            request.deadline is not None
            and time.monotonic() >= request.deadline
        ):
            global_metrics.inc("engine.expired")
            request.future.set_exception(
                DeadlineExceeded("request deadline expired before submit")
            )
            return request.future
        # An empty prompt would be indistinguishable from an admission
        # padding row (lens <= 0 => dropped) and hang; decode from a single
        # pad token instead.
        if not request.prompt_ids:
            request.prompt_ids = [0]
        # Leave room for at least one generated token; clamp the keep window
        # so it can never be <= 0 (a negative-zero slice would keep the whole
        # oversized prompt and crash the prefill copy).
        keep = self.max_seq_len - 1 - request.max_new_tokens
        keep = min(max(keep, 1), self.max_seq_len - 2)
        if len(request.prompt_ids) > keep:
            request.prompt_ids = request.prompt_ids[-keep:]
        if request.flight_key is not None:
            global_flight.mark(
                request.flight_key, "submitted", at=request.submitted_at
            )
        self._pending.put(request)
        # Gauge on EVERY enqueue, not just admit/fold/shed: a backlog
        # building while the device thread is pinned (e.g. segmenting
        # one long prefill) must be visible to the autoscaler's
        # engine_queue_frac signal as it grows, not after it drains.
        global_metrics.set_gauge(
            "engine.queue_depth", float(self.queue_depth())
        )
        self._wake.set()
        self._prep_wake.set()
        return request.future

    # ------------------------------------------------------------------ #
    # Device loop (device thread only)
    # ------------------------------------------------------------------ #

    def _bucket(self, n: int) -> int:
        # Power-of-two buckets only. Finer (1.5x-midpoint) buckets save
        # padded prefill FLOPs but triple the executable count, which
        # thrashes bounded compile/executable caches — measured as
        # multi-second dispatch stalls on every admission.
        b = self.min_bucket
        while b < n:
            b *= 2
        return min(b, self.max_seq_len)

    def _tail_bucket(self, n: int) -> int:
        """Prefix-cache tail ladder: 8-floor power-of-two (the 64-floor
        prompt ladder would spend ~25% of a full 8B prefill on a
        one-token tail)."""
        b = 8
        while b < n:
            b *= 2
        return b

    def _row_bucket(self, n: int, bucket: Optional[int] = None) -> int:
        """Rows of an admission dispatch that holds ``n`` requests: the
        smallest rung of a power-of-two ladder from ``row_floor`` to
        ``admit_batch`` (1, 2, 4, 8 on one chip at the default) — a row
        is a whole forward pass over the bucket, so a lone request does
        not pay for a full group's.

        ``bucket`` is the token bucket of a FULL prefill, whose programs
        warmup() builds up front, every rung of every bucket, and each
        one costs its trace and its executable's load at every start
        (3.4 s a program at 7B with a warm compile cache, measured). So
        only the top bucket, ``full_prefill_cap``, whose row is the
        costliest, gets the ladder; groups at smaller buckets pad to
        ``admit_batch`` (at most half as many tokens as a full group of
        the top bucket). Prefix-hit admissions pass no bucket and use
        every rung: their programs are built when first met."""
        if bucket is not None and bucket < self.full_prefill_cap:
            return self.admit_batch
        rows = self.row_floor
        while rows < n:
            rows *= 2
        return min(rows, self.admit_batch)

    def _prefix_hit(self, req: GenRequest):
        """Prefix-store match that also fits: the tail write lands at
        [prefix_len, prefix_len + tail_bucket) and dynamic_update_slice
        CLAMPS out-of-range starts — an oversized hit would silently
        shift the tail onto the cached prefix rows (KV corruption), so
        it must fall back to the full-prefill path instead.

        Paged cache: block-granular radix match instead — returns a
        PageNode whose ``path_pages`` get mapped (not copied) into the
        slot's block table. No clamp hazard there (writes go through the
        table), so the only fit check is that the prefix leaves room.

        Both shapes route through ONE lookup — the KV cache tier
        (engine/kvcache/index.py): device-resident hit first, then the
        host-RAM cold tier, whose hit RESTORES the spilled KV (async
        H2D staged here on the prep thread; the pool write for paged
        chains runs on the device thread via _apply_restores) instead
        of re-prefilling. Called under the slot lock."""
        if self._prefix_bypass and not self._warming and not req.kv_counted:
            req.kv_counted = True
            global_metrics.inc("engine.prefix_bypassed_recurrent")
        if self.kvcache is None or self._warming:
            # Warmup gate: the sweep's ascending same-start prompts
            # would otherwise hit earlier rungs' entries and admit via
            # the tail path — skipping the full-prefill compile the
            # sweep exists to guarantee.
            return None
        count = not req.kv_counted
        req.kv_counted = True
        if self.page_index is not None:
            need = min(
                len(req.prompt_ids) + req.max_new_tokens, self.max_seq_len
            )
            node, rec = self.kvcache.lookup_paged(
                req.prompt_ids,
                session_id=req.session_id,
                alloc=self.alloc,
                max_seq_len=self.max_seq_len,
                need_tokens=need,
                epoch=self._alloc_epoch,
                count=count,
            )
            if rec is not None:
                self._pending_restores.append(rec)
            if node is None:
                return None
            if node.depth * self.page_size >= self.max_seq_len:
                return None
            return node
        if self.prefix_store is None:
            return None
        n = len(req.prompt_ids)

        def fits(plen: int, p_bucket: int) -> bool:
            return (
                plen + self._tail_bucket(n - plen) <= self.max_seq_len
                and p_bucket <= self.max_seq_len
            )

        return self.kvcache.lookup_dense(
            req.prompt_ids, session_id=req.session_id, fits=fits,
            bucket=self._bucket, count=count,
        )

    def _decode_bucket(self, n: int) -> int:
        """Prefix-bound bucket for a decode chunk: the prefill bucket
        ladder with a 128 floor (so tiny bounds don't churn recompiles and
        executable variants stay O(log S)). Sharing the ladder means
        warmup's prefill sweep compiles every decode variant too."""
        return max(self._bucket(n), min(128, self.max_seq_len))

    def _selectable_slots_locked(self) -> List[int]:
        """Slots an admission may take now. A slot completed but not
        yet device-released is not yet admissible: its release ops
        (decode stop, page free) run next device cycle, and admitting
        into it now would let that stale release wipe the new occupant.
        One cycle of patience. Slots a previous selection reserved
        (prepared admission not yet installed) are off the table too."""
        not_yet = set(self._release)
        return [
            i for i in self._free_slot_indices()
            if i not in not_yet and i not in self._prep_reserved
        ]

    def _free_slot_indices(self) -> List[int]:
        free = [i for i, s in enumerate(self._slots) if s is None]
        if self.data_groups <= 1 or len(free) <= 1:
            return free
        # Data-axis admission replication (ISSUE 13): slots partition
        # into ``data_groups`` contiguous blocks — the exact split the
        # batch-dim NamedSharding uses — and selection interleaves
        # across groups, least-occupied first. A bursty admission wave
        # then spreads its requests over every data shard's slots
        # instead of filling group 0 while groups 1..D-1 idle, so a
        # {'model':M,'data':D} engine genuinely serves D concurrent
        # decode groups.
        per = self.n_slots // self.data_groups
        groups: List[List[int]] = [[] for _ in range(self.data_groups)]
        for i in free:
            groups[min(i // per, self.data_groups - 1)].append(i)
        order = sorted(
            range(self.data_groups),
            key=lambda g: (per - len(groups[g]), g),  # occupancy, stable
        )
        out: List[int] = []
        for rank in range(per):
            for g in order:
                if rank < len(groups[g]):
                    out.append(groups[g][rank])
        return out

    def _expire_deadlines(self) -> None:
        """Force-release occupied slots whose deadline passed mid-decode
        (device thread, once per loop cycle). Mirrors _check_finished's
        release protocol: slot → None now, the stop/free device ops run
        through ``_release`` at the next admission, and the ``slot is
        None`` guard plus the admission generation stamp keep any
        still-in-flight chunk from folding into the freed slot."""
        now = time.monotonic()
        expired: List[Tuple[int, _Slot]] = []
        with self._lock:
            for i, slot in enumerate(self._slots):
                if slot is None:
                    continue
                req = slot.request
                if req.deadline is None or now < req.deadline:
                    continue
                self._slots[i] = None
                self._release.append(i)
                self._release_pages_locked(i)
                global_metrics.inc("engine.expired")
                global_metrics.inc("engine.deadline_releases")
                expired.append((i, slot))
                if not req.future.done():
                    req.future.set_exception(DeadlineExceeded(
                        f"request deadline expired after "
                        f"{len(slot.generated)} generated token(s)"
                    ))
        if expired:
            self._prep_wake.set()  # freed pages/slots — prep can select
        # Observability OUTSIDE the lock: the black-box dump snapshots
        # the step ring and may write a journal line — file IO must not
        # stall the reader thread's folds.
        for i, slot in expired:
            req = slot.request
            if req.trace_id is None:
                continue
            end = time.perf_counter()
            global_tracer.emit(
                "engine.batch_decode",
                trace_id=req.trace_id,
                parent_id=req.parent_span_id,
                start=req.submitted_at,
                end=end,
                slot=i,
                prompt_len=slot.prompt_len,
                tokens=len(slot.generated),
                status="deadline",
            )
            global_blackbox.dump(
                "deadline_expired",
                trace_id=req.trace_id,
                slot=i,
                generated_tokens=len(slot.generated),
                prompt_len=slot.prompt_len,
            )

    def _drain_pending(self) -> None:
        """Drain the thread-safe submission queue into the FIFO backlog
        (page-gated admission needs to peek at the head without losing
        submission order). Runs on the prep thread when overlapping,
        the device thread inline — exactly one drainer per mode."""
        while True:
            try:
                self._backlog.append(self._pending.get_nowait())
            except queue.Empty:
                break

    # ------------------------------------------------------------------ #
    # DAG-aware backlog scheduling (pilottai_tpu/sched/, ROADMAP item 4)
    # ------------------------------------------------------------------ #

    def _eff_priority(self, req: GenRequest, now: float) -> int:
        """Effective priority: the request's rung plus aging-floor
        promotions — one rung per ``priority_aging_s`` of backlog wait,
        so sustained critical-path traffic can delay LOW work but never
        starve it (the starvation regression test pins this). Promotion
        deltas are counted once per request (``sched.priority_aged``)."""
        p = max(0, min(int(req.priority), 3))
        if self.priority_aging_s > 0 and p < 3:
            aged = int((now - req.submitted_at) / self.priority_aging_s)
            if aged > 0:
                boosted = min(3, p + aged)
                if boosted - p > req.aged_rungs:
                    global_metrics.inc(
                        "sched.priority_aged", boosted - p - req.aged_rungs
                    )
                    req.aged_rungs = boosted - p
                p = boosted
        return p

    def _order_backlog_locked(self) -> None:
        """Priority-order the backlog in place (slot lock held;
        ``sched_policy="dag"`` only). Stable sort by effective priority
        then submission time — uniform-priority traffic therefore keeps
        EXACT FIFO order (aging is monotone in wait, so it can never
        invert two same-priority requests), and recovered re-admissions
        (earliest ``submitted_at``) stay at the head.

        Gang handling: members of one gang sort together on the gang's
        BEST effective priority (one critical sibling lifts the whole
        fan-out) and its earliest submission; a gang still missing
        siblings, or whose whole membership doesn't fit the free
        slots+pages right now, is DEFERRED behind ungoverned work until
        either both hold or its bounded wait (``gang_wait_ms``) expires
        — after which it admits partially rather than holding the line
        forever. Ordering changes only WHEN a request admits, never
        what it computes: greedy output is byte-identical under any
        ordering (tests/test_sched.py pins it)."""
        now = time.perf_counter()
        items = list(self._backlog)
        members: Dict[str, List[GenRequest]] = {}
        for r in items:
            if r.gang_id:
                members.setdefault(r.gang_id, []).append(r)
        # Prune the wait clocks of gangs that fully left the backlog.
        # _gang_counted deliberately survives (bounded, see __init__):
        # it marks gangs that already dispatched, so their stragglers
        # skip deferral below.
        for gid in list(self._gang_seen):
            if gid not in members:
                self._gang_seen.pop(gid, None)
        free_slots = sum(
            1 for i, s in enumerate(self._slots)
            if s is None and i not in self._prep_reserved
        ) - len(self._release)
        deferred: set = set()
        gang_eff: Dict[str, int] = {}
        gang_anchor: Dict[str, float] = {}
        for gid, reqs in members.items():
            seen = self._gang_seen.setdefault(gid, now)
            gang_eff[gid] = max(self._eff_priority(r, now) for r in reqs)
            gang_anchor[gid] = min(r.submitted_at for r in reqs)
            if gid in self._gang_counted:
                # The gang already dispatched: a late-arriving or
                # fault-recovered sibling admits at its own priority
                # NOW — waiting for siblings that already ran would
                # manufacture the straggler this machinery removes.
                continue
            if (now - seen) * 1e3 >= self.gang_wait_ms:
                continue  # wait bound expired: partial-admit fallback
            size = max((r.gang_size for r in reqs), default=0)
            if size > self.n_slots:
                # Unsatisfiable by construction: a gang wider than the
                # engine can never co-admit, so deferring it would be
                # pure priority inversion (lower-priority work taking
                # every freed slot for the whole wait bound). Admit at
                # priority immediately; the pop-time accounting counts
                # it partial.
                continue
            complete = size <= len(reqs)
            capacity = len(reqs) <= max(free_slots, 0)
            if capacity and self.alloc is not None:
                # Conservative whole-gang page check (ignores prefix
                # sharing — a false defer only costs the bounded wait).
                need_pages = sum(
                    self.alloc.pages_needed(min(
                        len(r.prompt_ids) + r.max_new_tokens,
                        self.max_seq_len,
                    ))
                    for r in reqs
                )
                if need_pages > self.num_pages - 1:
                    continue  # can never fit the pool: same clamp
                capacity = need_pages <= self.alloc.free_pages
            if not (complete and capacity):
                deferred.add(gid)
        # The selection loop consults this: a deferred gang at the
        # backlog head BLOCKS (like a page-gated head) instead of
        # admitting a sibling subset early — the sort below already put
        # every admissible request in front of it, so only the gang
        # itself waits. Recomputed every selection; the wait bound
        # guarantees it clears.
        self._gang_deferred = deferred
        if len(items) < 2:
            return

        def key(r: GenRequest):
            if r.gang_id:
                return (
                    1 if r.gang_id in deferred else 0,
                    -gang_eff[r.gang_id],
                    gang_anchor[r.gang_id],
                    r.submitted_at,
                )
            return (0, -self._eff_priority(r, now), r.submitted_at, 0.0)

        items.sort(key=key)
        self._backlog = deque(items)

    def _note_admission_pop(self, req: GenRequest) -> None:
        """Backlog-pop bookkeeping (slot lock held): the per-priority
        submit→admission wait histogram — priority inversion shows up
        as a crossed percentile here, not in a debugger — and one
        admit/partial outcome count per gang."""
        wait_ms = max(0.0, (time.perf_counter() - req.submitted_at) * 1e3)
        prio = _PRIO_NAMES[max(0, min(int(req.priority), 3))]
        global_metrics.observe(f"engine.backlog_wait_ms.{prio}", wait_ms)
        gid = req.gang_id
        # Gang accounting only under the policy that actually groups
        # gangs — under "fifo" the outcome counters would be
        # meaningless ("partial" = siblings hadn't arrived yet) and the
        # dispatched-gang memory would never serve its purpose.
        if (
            self.sched_policy == "dag"
            and gid and gid not in self._gang_counted
        ):
            self._gang_counted[gid] = True
            while len(self._gang_counted) > 1024:
                self._gang_counted.popitem(last=False)
            present = 1 + sum(1 for r in self._backlog if r.gang_id == gid)
            if req.gang_size and present < req.gang_size:
                global_metrics.inc("sched.gang_partial")
            else:
                global_metrics.inc("sched.gang_admits")

    # ------------------------------------------------------------------ #
    # Speculative stage pre-warm (sched/ → prep thread → KV cache tier)
    # ------------------------------------------------------------------ #

    def prewarm(
        self, prompt_ids: List[int], session_id: Optional[str] = None
    ) -> bool:
        """Stage a KV-tier lookup for a PREDICTED prompt prefix (any
        thread; advisory). The lookup runs on the prep thread
        (``_drain_prewarms``): a host-tier hit starts its restore
        exactly as a real admission's would — async H2D staged off the
        device thread, pool scatter via ``_apply_restores`` — so when
        the predicted request actually arrives its prefill finds
        device-resident KV. No slot, no decode, no output: pre-warm can
        reorder nothing and is byte-identity-neutral by construction.
        Returns False when the engine cannot pre-warm (no KV cache
        tier, warming up, or the advisory queue is full)."""
        if self.kvcache is None or self._warming or not prompt_ids:
            global_metrics.inc("sched.prewarm_skipped")
            return False
        if len(self._prewarm_queue) >= self._prewarm_queue.maxlen:
            global_metrics.inc("sched.prewarm_skipped")
            return False
        self._prewarm_queue.append((list(prompt_ids), session_id))
        self._prep_wake.set()
        if not self.overlap_admission:
            self._wake.set()
        return True

    def _drain_prewarms(self) -> None:
        """Run queued pre-warm lookups (prep thread when overlapping,
        device thread inline — the same thread that runs selection, so
        the slot-lock discipline is identical to ``_prefix_hit``)."""
        while True:
            try:
                ids, sid = self._prewarm_queue.popleft()
            except IndexError:
                return
            global_metrics.inc("sched.prewarms")
            if self.kvcache is None or self._warming:
                global_metrics.inc("sched.prewarm_skipped")
                continue
            if (
                self.page_index is None
                and len(ids) <= self.kvcache.min_len
            ):
                # A dense entry stores the prompt minus its last token,
                # so anything at or below the floor can never hit
                # (KVCacheIndex.min_len — the documented
                # engine_prefix_min_len knob).
                self._warn_min_len(len(ids), "pre-warm")
                global_metrics.inc("sched.prewarm_skipped")
                continue
            hit = False
            try:
                with self._lock:
                    if self.page_index is not None:
                        node, rec = self.kvcache.lookup_paged(
                            ids, session_id=sid, alloc=self.alloc,
                            max_seq_len=self.max_seq_len,
                            need_tokens=min(len(ids), self.max_seq_len),
                            epoch=self._alloc_epoch, count=False,
                        )
                        if rec is not None:
                            self._pending_restores.append(rec)
                        hit = node is not None or rec is not None
                    elif self.prefix_store is not None:
                        n = len(ids)

                        def fits(plen: int, p_bucket: int) -> bool:
                            return (
                                plen + self._tail_bucket(max(n - plen, 1))
                                <= self.max_seq_len
                                and p_bucket <= self.max_seq_len
                            )

                        hit = self.kvcache.lookup_dense(
                            ids, session_id=sid, fits=fits,
                            bucket=self._bucket, count=False,
                        ) is not None
            except Exception as exc:  # noqa: BLE001 — advisory path
                self._log.warning("prewarm lookup failed: %s", exc)
                continue
            if hit:
                global_metrics.inc("sched.prewarm_hits")
                # A staged restore scatters at the device thread's next
                # _apply_restores drain — wake it.
                self._wake.set()

    def _warn_min_len(self, n: int, where: str) -> None:
        """One-shot dense-store floor warning: prompts at or below
        ``min_len`` silently never cache (entries store the prompt
        minus its last token) — say so ONCE per engine instead of
        letting bench or pre-warm prompts miss forever with no
        signal."""
        if self._warned_min_len:
            return
        self._warned_min_len = True
        floor = (
            self.kvcache.min_len if self.kvcache is not None
            else (self.prefix_store.min_len
                  if self.prefix_store is not None else 0)
        )
        self._log.warning(
            "%s prompt of %d token(s) is at or below the dense "
            "prefix-store floor (min_len=%d): prompts this short are "
            "never cached or pre-warmed — lower engine_prefix_min_len "
            "(docs/SERVING.md) if this workload should cache",
            where, n, floor,
        )

    def _admit(self) -> None:
        """Stop released slots, then dispatch pending admissions. With
        overlapped admission (the default) the groups arrive PREBUILT
        from the prep thread and this thread only performs the device
        dispatches — the prefill lands on the device stream behind the
        in-flight decode chunks, with no host-side array building in
        between. Inline mode prepares on this thread (the seed path;
        byte-identical output either way). Admits until slots or
        pending run out — completions arrive in waves, and refilling
        only one group per chunk would leave slots idle."""
        # Pending host-tier restores scatter into the pool FIRST: any
        # admission this cycle may map the restored pages.
        self._apply_restores()
        with self._lock:
            released = list(self._release)
            self._release.clear()

        if released:
            # Fixed-size release vector (padded with OOB indices) so the
            # jitted release path compiles exactly once. Must precede the
            # prompt writes below when a released slot is being reused.
            # The slot's KV pages were already returned to the pool at
            # the moment it finished/expired (_release_pages_locked —
            # per-slot early release, so backfill admissions are funded
            # one pipeline cycle earlier); only the device-side stop and
            # length-free ops remain for this thread.
            rel = np.full((self.n_slots,), self.n_slots, np.int32)
            rel[: len(released)] = released[: self.n_slots]
            rel_j = jnp.asarray(rel)
            self.dstate = release_decode(self.dstate, rel_j)
            self.cache = free_slots(self.cache, rel_j)
            # The released slots are selectable the moment their device
            # stop ops are enqueued — tell the prep thread.
            self._prep_wake.set()

        if not self.overlap_admission:
            self._drain_pending()
            self._drain_prewarms()  # inline mode: same-thread parity

        # A segmented admission in flight: advance it by ONE segment and
        # yield the cycle — the caller dispatches a decode chunk next, so
        # live slots keep decoding between segments.
        if self._segmenting is not None:
            self._advance_segment()
            return

        preps: List[Any] = []
        if self.overlap_admission:
            while True:
                try:
                    item = self._prepped.get_nowait()
                except queue.Empty:
                    break
                with self._lock:
                    n = (
                        1 if isinstance(item, _SegmentStart)
                        else len(item.group)
                    )
                    self._prepped_reqs = max(0, self._prepped_reqs - n)
                preps.append(item)
            if preps:
                self._prep_wake.set()  # look-ahead slots freed up
        else:
            groups, seg, epoch = self._select_groups()
            for entry, group in groups:
                try:
                    preps.append(
                        self._prepare_prefill(group, entry, epoch=epoch)
                    )
                except Exception as exc:  # noqa: BLE001 — host-side prep
                    # Array building touches no device state: fail these
                    # requests only, the engine stays serviceable.
                    self._log.error(
                        "admission prep failed: %s", exc, exc_info=True
                    )
                    self._fail_group(group, exc)
            if seg is not None:
                preps.append(_SegmentStart(seg, epoch))
        self._dispatch_admissions(preps)

        # A segmentation picked up in THIS call starts immediately (the
        # early-return gate above owns advancing it on later cycles).
        if self._segmenting is not None:
            self._advance_segment()

    def _dispatch_admissions(self, preps: List[Any]) -> None:
        """Dispatch prepared admissions in order (device thread only),
        with the per-group failure semantics of the inline path: a
        failed dispatch fails only its group; a failure that consumed
        the donated device state rebuilds it and REQUEUES everything not
        yet dispatched (their page allocations died with the old
        allocator — prefilling against the fresh one's sentinel rows
        silently produced garbage completions, test_engine_mesh.py)."""
        # Stale preps requeue in ONE batch after the loop: per-item
        # _requeue_prepared calls would each appendleft in front of the
        # previous call's requests, reversing FIFO admission order (and
        # under page pressure FIFO is what stops head-of-line reqs from
        # starving). Stale items precede fresh ones in `preps`, and the
        # batch requeue runs after any preps[gi+1:] requeue below, so
        # the earlier-submitted stale requests land at the very head.
        stale_preps: List[Any] = []
        for gi, prep in enumerate(preps):
            if prep.epoch != self._alloc_epoch:
                stale_preps.append(prep)
                continue
            if isinstance(prep, _SegmentStart):
                if stale_preps:
                    # FIFO: the stale preps carry EARLIER-submitted
                    # requests — installing this fresh segmentation
                    # would run its multi-cycle prefill ahead of them
                    # (prep stays parked on _seg_pending meanwhile).
                    # Requeue everything in submission order instead
                    # and let selection re-form the wave.
                    self._requeue_prepared(
                        stale_preps + [prep] + preps[gi + 1:]
                    )
                    stale_preps = []
                    break
                self._segmenting = prep.seg
                self._seg_epoch = prep.epoch
                # Group formation stopped at the segmentation (FIFO
                # order), so nothing can legitimately follow it.
                self._requeue_prepared(preps[gi + 1:])
                break
            # Deadline re-check at dispatch time: a prep can wait in
            # _prepped across a whole chunked-prefill segmentation
            # (admission early-returns for its duration — seconds for an
            # 8K prompt), long past the selection-time
            # sweep. A group whose every member expired or was cancelled
            # meanwhile would spend a full fused prefill on 100% dead
            # work; drop it instead. Mixed groups still dispatch — the
            # live members need the prefill anyway, and the next
            # _expire_deadlines cycle reaps the rest (releasing their
            # pages mid-dispatch here would race the in-flight page
            # writes against a concurrent re-allocation).
            now = time.monotonic()
            if all(
                req.cancelled or req.future.cancelled()
                or (req.deadline is not None and now >= req.deadline)
                for _, req in prep.group
            ):
                n_expired = sum(
                    1 for _, req in prep.group
                    if req.deadline is not None and now >= req.deadline
                    and not req.future.done()
                )
                if n_expired:
                    global_metrics.inc("engine.expired", n_expired)
                self._fail_group(prep.group, DeadlineExceeded(
                    "request deadline expired before admission dispatch"
                ))
                continue
            try:
                self._dispatch_prefill(prep)
            except Exception as exc:  # noqa: BLE001 — contain to this group
                self._log.error("prefill failed: %s", exc, exc_info=True)
                # A failed prefill DISPATCH is a device fault: the group
                # re-admits (bounded strikes) instead of failing — no
                # tokens existed for it yet, so the retry is transparent.
                self._fail_group(prep.group, exc, recover=True)
                self.degrade.record_fault("prefill")
                # admit_group donates cache/dstate/sampling: a dispatch
                # that failed mid-flight may have consumed them. If so the
                # engine state is gone with it — recover in-flight work
                # and rebuild fresh state so the engine stays serviceable
                # (silently keeping deleted buffers would crash the next
                # chunk and kill every request anyway, without recovery).
                if self.cache.lengths.is_deleted():
                    self._fail_occupied_slots(exc, record_fault=False)
                    self._rebuild_device_state(reason="prefill_failure")
                    self._requeue_prepared(preps[gi + 1:])
                    break
        if stale_preps:
            self._requeue_prepared(stale_preps)

    def _fail_group(self, group: List[Tuple[int, GenRequest]],
                    exc: Exception, recover: bool = False) -> None:
        """Fail one admission group's requests and return their
        resources (either thread). With ``recover=True`` (the prefill
        DISPATCH failure path — a device fault, not a client one) the
        group's requests requeue at the backlog head instead, bounded
        by the same per-request strike budget as slot recovery: an
        admission group has no accepted tokens yet, so its replay is a
        pure re-admission."""
        now = time.monotonic()
        t_snap = time.perf_counter()
        requeue: List[GenRequest] = []
        with self._lock:
            for idx, req in group:
                self._slots[idx] = None
                self._prep_reserved.discard(idx)
                # Reclaim the group's KV pages (under the lock — the
                # reader thread releases pages too) — leaking them here
                # permanently shrinks the pool AND trips allocate()'s
                # held-pages invariant when the slot is reused.
                if self.alloc is not None:
                    self.alloc.release(idx)
                if req.future.done():
                    continue
                if not recover:
                    req.future.set_exception(exc)
                    continue
                if self._recovery_decision_locked(req, exc, now, t_snap):
                    requeue.append(req)
            for req in reversed(requeue):
                self._backlog.appendleft(req)
        if requeue:
            global_metrics.inc("engine.recovery_requeued", len(requeue))
            self._prep_wake.set()
            self._wake.set()

    def _requeue_prepared(self, items: List[Any]) -> None:
        """Return prepared-but-undispatchable admissions to the backlog
        HEAD, in order (device thread only). Their page allocations are
        dropped (release is idempotent, and a no-op on a freshly rebuilt
        allocator) and their slots unreserved; the next selection
        re-admits them against live state. Anything the prep thread had
        queued BEHIND them drains too — under the prep gate, so no
        concurrent prep round can land an item after the drain."""
        with self._prep_gate:
            drained: List[Any] = []
            while True:
                try:
                    drained.append(self._prepped.get_nowait())
                except queue.Empty:
                    break
            with self._lock:
                self._prepped_reqs = 0
                reqs: List[GenRequest] = []
                for item in list(items) + drained:
                    if isinstance(item, _SegmentStart):
                        idx, req = item.seg[0], item.seg[1]
                        self._seg_pending = False
                        pairs = [(idx, req)]
                    else:
                        pairs = item.group
                    for idx, req in pairs:
                        self._prep_reserved.discard(idx)
                        if self.alloc is not None:
                            self.alloc.release(idx)
                        reqs.append(req)
                for req in reversed(reqs):
                    self._backlog.appendleft(req)
        self._prep_wake.set()
        self._wake.set()

    def _prep_loop(self) -> None:
        """Admission-prep thread: everything host-side an admission
        needs — backlog draining, deadline/cancel sweeps at the head,
        slot selection, page allocation, prefix matching and
        staging-buffer packing — runs HERE, off the device thread's
        dispatch path. The slot-lock + allocator-under-lock discipline
        (PR 4's early-release work) is what makes this safe: selection
        and allocation serialize against the reader's fold-time releases
        exactly as they did on the device thread. Look-ahead is bounded
        (``_prep_depth`` waves) so prep can never run unboundedly ahead
        of installs."""
        while not self._stop.is_set():
            self._drain_pending()
            # Speculative pre-warms ride the prep thread too: the
            # restore staging (host memcpy + async H2D) lands exactly
            # where a real admission's would, never on the device
            # thread.
            self._drain_prewarms()
            if (
                self._segmenting is not None
                or self._seg_pending
                or self._prepped.qsize() >= self._prep_depth
                or (not self._backlog and not self._pending.qsize())
            ):
                self._prep_wake.wait(timeout=0.02)
                self._prep_wake.clear()
                continue
            made = False
            sel_failed = False
            with self._prep_gate:
                if self._stop.is_set():
                    break
                try:
                    with host_span("prep.select_groups"):
                        groups, seg, epoch = self._select_groups()
                except Exception as exc:  # noqa: BLE001 — keep prep alive
                    # A dead prep thread wedges every future admission
                    # (requests queue forever, the breaker opens on the
                    # timeouts). Best effort: log loudly and keep the
                    # thread alive — later selections can still serve
                    # the rest of the backlog. The backoff wait happens
                    # OUTSIDE the gate: sleeping with it held would
                    # block the device thread's _requeue_prepared (the
                    # rebuild/segmentation recovery paths) for 100 ms a
                    # pop — a host-side stall of the very dispatch loop
                    # this pipeline exists to keep fed.
                    self._log.error(
                        "admission selection failed: %s", exc,
                        exc_info=True,
                    )
                    sel_failed = True
                    groups, seg = [], None
                for entry, group in groups:
                    try:
                        with host_span(
                            "prep.prepare_prefill", requests=len(group)
                        ):
                            prep = self._prepare_prefill(
                                group, entry, epoch=epoch
                            )
                    except Exception as exc:  # noqa: BLE001 — prep only
                        self._log.error(
                            "admission prep failed: %s", exc, exc_info=True
                        )
                        self._fail_group(group, exc)
                        continue
                    with self._lock:
                        self._prepped_reqs += len(group)
                    self._prepped.put(prep)
                    made = True
                if seg is not None:
                    self._seg_pending = True
                    with self._lock:
                        self._prepped_reqs += 1
                    self._prepped.put(_SegmentStart(seg, epoch))
                    made = True
            if sel_failed:
                self._prep_wake.wait(timeout=0.1)
                self._prep_wake.clear()
                continue
            if made:
                self._wake.set()
            else:
                self._prep_wake.wait(timeout=0.02)
                self._prep_wake.clear()
        self._log.info("admission prep stopped")

    def _select_groups(self):
        """Form admission groups from the backlog head (prep thread when
        overlapping, device thread inline; slot lock held inside).
        Returns ``(groups, seg, epoch)``: groups as ``[(prefix_entry,
        [(slot, request), ...])]``, ``seg`` a started chunked-prefill
        admission ``[slot, request, tokens_done]`` with pages already
        allocated (or None), and the allocator epoch the allocations
        were made under. Chosen slots are reserved until install or
        failure so overlapping selections can't double-book them."""
        seg = None
        with self._lock:
            # Drained under the lock (the callers' own drain only keeps
            # their idle checks cheap): requests submitted under it —
            # warmup's waves — reach the backlog all together or not yet.
            self._drain_pending()
            # DAG-aware ordering first (policy-gated; warmup keeps the
            # compile sweep's deterministic submission order): priority
            # + aging floor + gang grouping decide who the "head" is.
            if self.sched_policy == "dag" and not self._warming:
                self._order_backlog_locked()
            epoch = self._alloc_epoch
            free = self._selectable_slots_locked()
            # Degrade rung 3+ (reliability/degrade.py): cap live
            # occupancy at half the slots — less work in flight per
            # fault, faster drains, smaller recovery replays.
            if self.degrade.level() >= degrade_levels.HALF_SLOTS:
                occupied = (
                    sum(s is not None for s in self._slots)
                    + len(self._prep_reserved)
                )
                cap = max(1, self.n_slots // 2)
                free = free[: max(0, cap - occupied)]
            groups: List[Tuple[Any, List[Tuple[int, GenRequest]]]] = []
            # The in-progress group lives outside the try so the unwind
            # below sees it even when the failure lands mid-formation.
            group: List[Tuple[int, GenRequest]] = []
            blocked = False
            try:
                while free and not blocked:
                    group = []
                    group_key = None
                    while (
                        free and self._backlog
                        and len(group) < self.admit_batch
                    ):
                        req = self._backlog[0]
                        if req.cancelled or req.future.cancelled():
                            self._backlog.popleft()
                            continue
                        # Expired while queued: admitting would spend a
                        # prefill on work whose caller already gave up.
                        if (
                            req.deadline is not None
                            and time.monotonic() >= req.deadline
                        ):
                            self._backlog.popleft()
                            global_metrics.inc("engine.expired")
                            if not req.future.done():
                                req.future.set_exception(DeadlineExceeded(
                                    "request deadline expired before admission"
                                ))
                            continue
                        # A deferred gang at the head waits (bounded by
                        # gang_wait_ms) for its siblings or for enough
                        # slots+pages to take the WHOLE gang — the
                        # ordering pass already moved every admissible
                        # request in front of it, so nothing else is
                        # being held up.
                        if (
                            self.sched_policy == "dag"
                            and req.gang_id
                            and req.gang_id in self._gang_deferred
                        ):
                            blocked = True
                            break
                        # Prefix-cache match keys the group: one shared
                        # cached prefix per admission dispatch.
                        key = self._prefix_hit(req)
                        # Long un-cached tail → chunked-prefill admission
                        # (own slot, one segment per cycle), never a
                        # monolithic group prefill.
                        long_req = False
                        if self.prefill_chunk:
                            chain = (
                                len(key.path_pages)
                                if self.page_index is not None
                                and key is not None else 0
                            )
                            tail_len = (
                                len(req.prompt_ids) - chain * self.page_size
                            )
                            long_req = tail_len > 2 * self.prefill_chunk
                        if group and (key is not group_key or long_req):
                            break  # next group (or segmentation) takes it
                        group_key = key
                        prefix_pages: Tuple[int, ...] = ()
                        if self.page_index is not None and key is not None:
                            prefix_pages = key.path_pages
                        if self.alloc is not None:
                            # Clamp to slot capacity: decode stops at
                            # ctx-full anyway, so the cache never holds
                            # more (an unclamped huge max_new_tokens
                            # would make can_allocate permanently false
                            # and deadlock the FIFO head).
                            need = min(
                                len(req.prompt_ids) + req.max_new_tokens,
                                self.max_seq_len,
                            )
                            if not self.alloc.can_allocate(
                                need, len(prefix_pages)
                            ):
                                # Reclaim cached prefix pages before
                                # declaring the head blocked — caching
                                # must never starve admission. The hit's
                                # own chain is protected (evicting it
                                # would free pages we are about to map).
                                short = (
                                    self.alloc.pages_needed(need)
                                    - len(prefix_pages)
                                    - self.alloc.free_pages
                                )
                                if not (
                                    self.page_index is not None
                                    and short > 0
                                    and self.page_index.evict(
                                        short, self.alloc,
                                        protect=frozenset(prefix_pages),
                                    ) > 0
                                    and self.alloc.can_allocate(
                                        need, len(prefix_pages)
                                    )
                                ):
                                    # Head-of-line waits for pages (FIFO
                                    # fairness); completions free them.
                                    blocked = True
                                    break
                        self._backlog.popleft()
                        self._note_admission_pop(req)
                        idx = free.pop(0)
                        self._prep_reserved.add(idx)
                        if self.alloc is not None:
                            try:
                                ok = self.alloc.allocate(
                                    idx, need, prefix_pages=prefix_pages
                                )
                                assert ok, "can_allocate/allocate disagree"
                            except Exception:
                                # Undo the pop + reservation for THIS
                                # request before the outer unwind (which
                                # only knows committed members) runs:
                                # its appendleft lands behind the
                                # committed requests the unwind restores
                                # in front, so FIFO order holds.
                                self._prep_reserved.discard(idx)
                                self._backlog.appendleft(req)
                                raise
                        if long_req:
                            # Pages are allocated; segments run one per
                            # device-loop cycle once the device thread
                            # installs it. No further groups this wave —
                            # admission order holds.
                            self._prep_reserved.discard(idx)
                            seg = [
                                idx, req,
                                len(prefix_pages) * self.page_size,
                            ]
                            blocked = True
                            break
                        group.append((idx, req))
                    if not group:
                        break
                    groups.append((group_key, group))
            except Exception:
                # A failure mid-selection (prefix match, eviction, the
                # allocate assert) must not leak what this call already
                # committed: without this unwind, every earlier member —
                # the in-progress group AND fully formed groups — kept
                # its _prep_reserved entry and page allocation forever
                # while its request vanished from every queue (future
                # never resolves, slot pool permanently shrinks; the
                # prep loop's keep-alive catch only logs). Roll back all
                # of them and restore backlog FIFO order before
                # re-raising.
                pairs = [p for _, g in groups for p in g] + group
                for idx, _req in pairs:
                    self._prep_reserved.discard(idx)
                    if self.alloc is not None:
                        self.alloc.release(idx)
                for _idx, r in reversed(pairs):
                    self._backlog.appendleft(r)
                raise
            # Reserved slots stay None until install, so the picks stay
            # valid after the lock drops even with selection and install
            # on different threads.
        return groups, seg, epoch

    def _end_segmentation(self) -> None:
        """Segmentation over — installed, cancelled, expired or failed:
        group formation may resume (device thread only)."""
        self._segmenting = None
        self._seg_pending = False
        self._prep_wake.set()

    def _advance_segment(self) -> None:
        """Dispatch one chunked-prefill segment (device thread only).
        Intermediate segments run ``extend_prompt_paged`` (KV writes
        only); the final segment admits through the normal prefix-paged
        path, which samples the first token and installs the slot."""
        idx, req, done = self._segmenting
        # A segmented admission's chain may include freshly restored
        # pages (its prefix hit ran the host-tier path at selection):
        # they must be pool-resident before extend_prompt_paged attends
        # over them.
        self._apply_restores()
        if self._seg_epoch != self._alloc_epoch:
            # Device state was rebuilt mid-segmentation (a concurrent
            # dispatch failure consumed the buffers): the KV written so
            # far died with the old pool and alloc.table[idx] now reads
            # the fresh allocator's sentinel rows — continuing would
            # silently produce a garbage completion. Re-admit from the
            # backlog head instead (release is a no-op on the new pool).
            with self._lock:
                if self.alloc is not None:
                    self.alloc.release(idx)
                self._backlog.appendleft(req)
            self._end_segmentation()
            self._wake.set()
            return
        expired_now = (
            req.deadline is not None and time.monotonic() >= req.deadline
        )
        if req.cancelled or req.future.cancelled() or expired_now:
            # Release BEFORE ending segmentation: _end_segmentation wakes
            # the prep thread, and a slot that is empty but still holds
            # pages trips allocate()'s held-pages invariant if selection
            # wins the race to the lock.
            if self.alloc is not None:
                with self._lock:
                    self.alloc.release(idx)
            self._end_segmentation()
            if expired_now:
                global_metrics.inc("engine.expired")
                if not req.future.done():
                    req.future.set_exception(DeadlineExceeded(
                        "request deadline expired mid-prefill"
                    ))
            return
        try:
            remaining = len(req.prompt_ids) - done
            if remaining > self.prefill_chunk:
                seg = self.prefill_chunk
                k = done // self.page_size
                kb = 1
                while kb < max(k, 1):
                    kb *= 2
                pages_arr = np.full((kb,), self.alloc.sentinel, np.int32)
                pages_arr[:k] = self.alloc.table[idx, :k]
                seg_tokens = np.zeros((1, seg), np.int32)
                seg_tokens[0] = req.prompt_ids[done: done + seg]
                t_seg = time.perf_counter()
                with host_span(
                    "batcher.dispatch_prefill.segment", rows=1, bucket=seg,
                ), global_metrics.timer("engine.prefill_latency"):
                    self.cache = extend_prompt_paged(
                        self.params, self.cfg, self.cache,
                        jnp.asarray(pages_arr), jnp.int32(done),
                        jnp.asarray(seg_tokens),
                        jnp.asarray([seg], np.int32),
                        jnp.asarray(self.alloc.table[idx][None]),
                        slot=(
                            jnp.asarray([idx], np.int32)
                            if self.cfg.layer_kinds else None
                        ),
                    )
                global_metrics.inc("engine.prefill_segments")
                if not self._warming:
                    # A segment is one unpadded row: real and run agree.
                    global_metrics.inc("engine.prefill_tokens_real", seg)
                    global_metrics.inc("engine.prefill_tokens_run", seg)
                    seg_dur = time.perf_counter() - t_seg
                    self._record_attributed(
                        "prefill", seg_dur, seg,
                        est=(
                            self.collective_model.prefill_seconds(seg)
                            if self.collective_model is not None else None
                        ),
                    )
                    with self._lock:
                        self._prefill_since_fold += seg_dur
                self._segmenting[2] = done + seg
                self._beat()  # segment landed: watchdog-visible progress
                self._wake.set()  # next cycle advances without the idle wait
                return
            # Final segment: the tokens already written are this slot's
            # own page chain — admit exactly like a block-prefix hit, at
            # one row whatever the mesh's row floor (every padding row
            # against an 8K chain multiplies the prefix-score tensor —
            # at admit_batch rows a measured compile OOM). Re-reserve
            # the slot across the handoff: segmentation ends here but
            # the slot is not installed until _dispatch_prefill, and the
            # prep thread (woken by _end_segmentation) must not select
            # an empty slot that still holds this request's pages.
            # Install (or the failure path below) clears the
            # reservation.
            with self._lock:
                self._prep_reserved.add(idx)
            self._end_segmentation()
            k = done // self.page_size
            entry = SimpleNamespace(
                depth=k,
                path_pages=tuple(int(p) for p in self.alloc.table[idx, :k]),
                segmented=True,  # own chain, not a cache hit (metrics)
            )
            self._dispatch_prefill(
                self._prepare_prefill([(idx, req)], entry, n_rows=1)
            )
        except Exception as exc:  # noqa: BLE001 — contain to this request
            self._log.error("chunked prefill failed: %s", exc, exc_info=True)
            # Cleanup before _end_segmentation for the same reason as the
            # cancel branch: once prep wakes, the slot must either hold
            # no pages or stay reserved — never "empty with pages". A
            # segmented admission has produced no tokens yet, so a
            # device fault here re-admits from scratch (bounded strikes)
            # rather than failing the request.
            now = time.monotonic()
            with self._lock:
                self._slots[idx] = None
                self._prep_reserved.discard(idx)
                if self.alloc is not None:
                    self.alloc.release(idx)
                if not req.future.done():
                    if self._recovery_decision_locked(
                        req, exc, now, time.perf_counter()
                    ):
                        self._backlog.appendleft(req)
                        global_metrics.inc("engine.recovery_requeued")
            self._end_segmentation()
            self.degrade.record_fault("prefill")
            if self.cache.lengths.is_deleted():
                self._fail_occupied_slots(exc, record_fault=False)
                self._rebuild_device_state(reason="prefill_failure")

    def _prepare_prefill(
        self,
        group: List[Tuple[int, GenRequest]],
        entry: Optional[Any] = None,
        n_rows: Optional[int] = None,
        epoch: Optional[int] = None,
    ) -> _PreparedAdmission:
        """Build every host-side input of one admission dispatch (either
        thread). The per-row scalars pack into ONE int32 + ONE float32
        staging buffer (``decode.pack_admit_meta`` layout): the ~10 tiny
        per-field ``jnp.asarray`` uploads this replaces each paid a
        transfer-setup/dispatch floor. No device work
        happens here — that is the point."""
        T = self._bucket(max(len(r.prompt_ids) for _, r in group))
        A = n_rows if n_rows is not None else self._row_bucket(
            len(group), T if entry is None else None
        )
        mi, mf = pack_admit_meta(A, pad_slot=self.n_slots)
        for row, (idx, req) in enumerate(group):
            mi[AI_SLOT, row] = idx
            mi[AI_TOPK, row] = req.top_k
            mi[AI_SEED, row] = req.seed
            mi[AI_EOS, row] = req.eos_id
            mi[AI_BUDGET, row] = req.max_new_tokens - 1
            mi[AI_JSON, row] = int(req.json_mode)
            mi[AI_SCHEMA, row] = req.json_schema_id
            mf[AF_TEMP, row] = req.temperature
            mf[AF_TOPP, row] = req.top_p
        prep = _PreparedAdmission(
            kind="full", group=list(group), entry=entry,
            epoch=self._alloc_epoch if epoch is None else epoch,
            meta_i32=mi, meta_f32=mf,
            has_json=any(req.json_mode for _, req in group),
            has_schema=bool((mi[AI_SCHEMA] >= 0).any()),
        )

        if entry is not None and self.paged:
            # Paged block-granular hit (or a chunked-prefill final
            # segment reading its own chain): the shared chain's pages are
            # already mapped into each slot's block table by the
            # allocator — no panel copy exists anywhere. Prefill only
            # the tails, with prefix attention reading the shared pages.
            k = entry.depth
            plen = k * self.page_size
            kb = 1
            while kb < k:
                kb *= 2
            pages_arr = np.full((kb,), self.alloc.sentinel, np.int32)
            pages_arr[:k] = entry.path_pages
            Tt = self._tail_bucket(
                max(len(r.prompt_ids) - plen for _, r in group)
            )
            tail_tokens = np.zeros((A, Tt), np.int32)
            full_tokens = np.zeros((A, T), np.int32)
            for row, (idx, req) in enumerate(group):
                tail = req.prompt_ids[plen:]
                tail_tokens[row, : len(tail)] = tail
                mi[AI_LEN, row] = len(tail)
                full_tokens[row, : len(req.prompt_ids)] = req.prompt_ids
            mi[AI_PLEN] = plen
            # Block-table rows under the lock: the reader thread mutates
            # rows at early page release.
            with self._lock:
                pr = np.full(
                    (A, self.max_pages_per_slot), self.alloc.sentinel,
                    np.int32,
                )
                for row, (idx, _) in enumerate(group):
                    pr[row] = self.alloc.table[idx]
            prep.kind = "prefix_paged"
            prep.pages_arr = pages_arr
            prep.tail_tokens = tail_tokens
            prep.full_tokens = full_tokens
            prep.page_rows = pr
            prep.n_prefix_bucket = kb
        elif entry is not None:
            # Cached-prefix admission: copy the stored panels, prefill
            # only the tails (an exact repeat is a one-token tail). Tail
            # buckets get an 8-floor ladder of their own: the 64-floor
            # prompt ladder would spend ~25% of a full 8B prefill on a
            # one-token tail.
            plen = len(entry.ids)
            Tt = self._tail_bucket(
                max(len(r.prompt_ids) - plen for _, r in group)
            )
            assert plen + Tt <= self.max_seq_len  # _prefix_hit guarantees
            tail_tokens = np.zeros((A, Tt), np.int32)
            full_tokens = np.zeros((A, T), np.int32)
            for row, (idx, req) in enumerate(group):
                tail = req.prompt_ids[plen:]
                tail_tokens[row, : len(tail)] = tail
                mi[AI_LEN, row] = len(tail)
                full_tokens[row, : len(req.prompt_ids)] = req.prompt_ids
            mi[AI_PLEN] = plen
            prep.kind = "prefix"
            prep.tail_tokens = tail_tokens
            prep.full_tokens = full_tokens
        else:
            tokens = np.zeros((A, T), np.int32)
            for row, (idx, req) in enumerate(group):
                ids = req.prompt_ids
                tokens[row, : len(ids)] = ids
                mi[AI_LEN, row] = len(ids)
            prep.tokens = tokens
            if self.alloc is not None:
                with self._lock:
                    pr = np.full(
                        (A, self.max_pages_per_slot), self.alloc.sentinel,
                        np.int32,
                    )
                    for row, (idx, _) in enumerate(group):
                        pr[row] = self.alloc.table[idx]
                prep.page_rows = pr
        return prep

    def _dispatch_prefill(self, prep: _PreparedAdmission) -> None:
        """Upload the prepared staging buffers and run the fused
        admission dispatch, then install the slots (device thread only).
        This is ALL the admission work left on the dispatch path: the
        prefill is enqueued on the device stream BEHIND whatever decode
        chunks are already in flight — chunked-prefill segments and
        decode interleave with no host-side bubble between them."""
        rows, bucket = prep.prefilled.shape
        with host_span(
            f"batcher.dispatch_prefill.{prep.kind}", rows=rows,
            bucket=bucket, requests=len(prep.group),
        ):
            self._dispatch_prefill_traced(prep)

    def _dispatch_prefill_traced(self, prep: _PreparedAdmission) -> None:
        group = prep.group
        entry = prep.entry
        # Restored page chains must be pool-resident before this
        # dispatch can read them: drain here (not only in _admit) so a
        # prep whose restore record landed between _admit's drain and
        # its own dequeue still scatters first — the drain and this
        # dispatch share the device thread, so program order holds.
        self._apply_restores()
        # Chaos point: a slow (delay=) or failed (exc=) admission prefill.
        # Raises land in _dispatch_admissions' per-group failure handling
        # — exactly the production path a device fault would take.
        global_injector.fire("engine.prefill", n_requests=len(group))
        # Bake the token tables into this dispatch only when the group
        # actually constrains: with a 128k-vocab the B x V x L automaton
        # simulation is pure waste for non-JSON traffic. Two jit variants
        # total (with/without), both cached after first use. Schema
        # tables follow the same two-variant discipline (their ids ride
        # the packed meta buffer either way).
        group_json = self.json_tables if prep.has_json else None
        group_schema = self._schema_tables() if prep.has_schema else None
        meta_i32 = jnp.asarray(prep.meta_i32)
        meta_f32 = jnp.asarray(prep.meta_f32)
        t_pf = time.perf_counter()

        if prep.kind == "prefix_paged":
            with global_metrics.timer("engine.prefill_latency"):
                (
                    self.cache, self.dstate, self.sampling, first,
                    self.history,
                ) = admit_group_prefix_paged(
                    self.params, self.cfg, self.cache, self.dstate,
                    self.sampling, jnp.asarray(prep.pages_arr),
                    jnp.asarray(prep.tail_tokens),
                    jnp.asarray(prep.full_tokens),
                    jnp.asarray(prep.page_rows), meta_i32, meta_f32,
                    n_prefix_bucket=prep.n_prefix_bucket,
                    json_tables=group_json, history=self.history,
                    schema_tables=group_schema,
                )
            if not getattr(entry, "segmented", False):
                # A chunked-prefill final reads its OWN chain — counting
                # it as a cache hit would report near-100% hit rates on
                # deployments with the prefix cache disabled.
                global_metrics.inc("engine.prefix_hits", len(group))
                # Tokens the shared chain saved this dispatch: every
                # group member skipped the chain's prefill FLOPs.
                global_metrics.inc(
                    "engine.kvcache.prefill_tokens_saved",
                    entry.depth * self.page_size * len(group),
                )
            # Blocks past the shared chain that the prompt fully covers
            # are immutable too — register them as chain extensions.
            self._maybe_register(group)
        elif prep.kind == "prefix":
            with global_metrics.timer("engine.prefill_latency"):
                (
                    self.cache, self.dstate, self.sampling, first,
                    self.history,
                ) = admit_group_prefix(
                    self.params, self.cfg, self.cache, self.dstate,
                    self.sampling, entry.ks, entry.vs,
                    jnp.asarray(prep.tail_tokens),
                    jnp.asarray(prep.full_tokens), meta_i32, meta_f32,
                    json_tables=group_json, history=self.history,
                    schema_tables=group_schema,
                )
            global_metrics.inc("engine.prefix_hits", len(group))
            global_metrics.inc(
                "engine.kvcache.prefill_tokens_saved",
                len(entry.ids) * len(group),
            )
        else:
            with global_metrics.timer("engine.prefill_latency"):
                # One fused dispatch for the whole admission (prefill +
                # cache write + sampler + first token + decode install +
                # history) — separate dispatches each pay their own
                # dispatch + sync cost.
                (
                    self.cache, self.dstate, self.sampling, first,
                    self.history,
                ) = admit_group(
                    self.params, self.cfg, self.cache, self.dstate,
                    self.sampling, jnp.asarray(prep.tokens), meta_i32,
                    meta_f32, use_flash=self.on_tpu,
                    flash_mesh=self.flash_mesh,
                    page_rows=(
                        jnp.asarray(prep.page_rows)
                        if prep.page_rows is not None else None
                    ),
                    json_tables=group_json, history=self.history,
                    schema_tables=group_schema,
                )
            if self.paged:
                self._maybe_register(group)
            else:
                self._maybe_export(group)
        # The first tokens' D2H copy starts NOW; the reader materializes
        # the in-flight copy at fold time (no fresh round trip).
        first_copy = _HostCopy((first,))
        self._last_prefill_t = time.perf_counter()
        admit_at = time.perf_counter()
        self._beat()  # prefill enqueued: watchdog-visible progress
        if not self._warming:
            # Attribution: tokens actually prefilled this dispatch (the
            # AI_LEN rows carry tail lengths on prefix paths — prefix-hit
            # pages were NOT recomputed and must not count as achieved
            # FLOPs). The enqueue wall doubles as the prefill-time
            # estimate.
            pf_dur = admit_at - t_pf
            pf_tokens = int(prep.meta_i32[AI_LEN].sum())
            # Fill of this dispatch: the tokens the requests brought
            # against the padded rows x bucket the program ran.
            global_metrics.inc("engine.prefill_tokens_real", pf_tokens)
            global_metrics.inc(
                "engine.prefill_tokens_run", prep.prefilled.size
            )
            self._record_attributed(
                "prefill", pf_dur, pf_tokens,
                est=(
                    self.collective_model.prefill_seconds(pf_tokens)
                    if self.collective_model is not None else None
                ),
                at=admit_at,
            )
            idle_s = 0.0
            with self._lock:
                if self._inflight == 0:
                    # Device was DRAINED when this admission arrived: the
                    # span from the last fold to here was genuine idle —
                    # the decode-dispatch gap telemetry can't see it
                    # (its marks get masked by _last_prefill_t) — and
                    # the next fold's decode interval must restart at
                    # this prefill's END. Without both, idle-then-burst
                    # traffic books the whole idle span as decode time
                    # and busy_frac reads ~1.0 on an idle engine.
                    if self._last_attr_mark is not None:
                        idle_s = max(t_pf - self._last_attr_mark, 0.0)
                    self._last_attr_mark = admit_at
                else:
                    # Decode chunks in flight: the enclosing fold-to-fold
                    # interval spans this prefill; remember the wall so
                    # the fold doesn't count it twice.
                    self._prefill_since_fold += pf_dur
            if idle_s > 0.0:
                global_attribution.record_gap(idle_s, at=t_pf)
        with self._lock:
            for idx, req in group:
                self._slots[idx] = _Slot(
                    request=req, prompt_len=len(req.prompt_ids)
                )
                self._gen[idx] += 1
                self._prep_reserved.discard(idx)
                # Fresh occupant: optimistic n-gram first (its lookups
                # are free); the per-slot EMA demotes to model drafting
                # only if this request's output proves unpredictable.
                self._slot_rate[idx] = float(max(self.speculate, 1))
                self._draft_on[idx] = False
                if req.recovery_started_at is not None:
                    # Snapshot → re-admission wall: the latency a
                    # recovered request paid for the fault (bench
                    # RECOVERY reports p50/p99).
                    global_metrics.observe(
                        "engine.recovery_ms",
                        (admit_at - req.recovery_started_at) * 1e3,
                    )
                    req.recovery_started_at = None
            self._first_reads.append(
                ([(idx, self._gen[idx]) for idx, _ in group], first_copy)
            )
            slots_active = sum(s is not None for s in self._slots)
        if self.cfg.recurrent:
            global_metrics.set_gauge("engine.state_slots_live", float(slots_active))
        for _, req in group:
            # Queue wait = submit → slot granted: the flight's admitted
            # mark is THE source of request.queue_wait_s (one histogram,
            # one definition — a second batcher-side one with a slightly
            # different start point would disagree at the tails).
            if req.flight_key is not None:
                global_flight.mark(req.flight_key, "admitted", at=admit_at)
        depth = self.queue_depth()
        global_metrics.set_gauge("engine.queue_depth", float(depth))
        global_steps.record(
            "engine.admit",
            n=len(group),
            slots_active=slots_active,
            queue_depth=depth,
        )
        global_metrics.inc("engine.admitted", len(group))

    def _apply_restores(self) -> None:
        """Scatter pending host-tier page restores into the pool (device
        thread only; a donated jitted write per chain — enqueued on the
        device stream, never awaited). Runs before any admission or
        segment dispatch, so a restored chain is always pool-resident by
        the time something reads it. Stale-epoch records (their pool was
        rebuilt) are dropped inside apply_restores."""
        if self.kvcache is None:
            return
        with self._lock:
            if not self._pending_restores:
                return
            records = self._pending_restores
            self._pending_restores = []
            epoch = self._alloc_epoch
        self.cache = self.kvcache.apply_restores(self.cache, records, epoch)
        with self._lock:
            # Writes are enqueued: the unwritten-page spill guard lifts
            # (stale records too — their pages died with the old pool,
            # and holding ids hostage would suppress spills of innocent
            # same-numbered pages in the new allocator).
            self.kvcache.mark_written(records)
        self._beat()  # restore landed: watchdog-visible progress

    def _schema_tables(self):
        """Device copies of the SchemaBank tables, refreshed when the
        bank gained a schema (device thread only)."""
        bank = self.schema_bank
        if bank is None or len(bank) == 0:
            return None
        if bank.version != self._schema_seen:
            # Snapshot the version BEFORE copying: register() on the
            # request thread mutates rows first and bumps version last,
            # so reading version after the copy could mark a torn
            # mid-registration copy as current forever.
            seen = bank.version
            self._schema_dev = tuple(jnp.asarray(t) for t in bank.tables())
            self._schema_seen = seen
        return self._schema_dev

    def _maybe_register(self, group: List[Tuple[int, GenRequest]]) -> None:
        """After a paged admission (miss or hit), pin the admitted
        prompts' fully-covered pages into the radix index so future
        prompts sharing page-aligned prefixes map them directly. Only
        blocks fully inside the prompt are registered — they are
        immutable (decode writes start at ``prompt_len``); the partial
        last block keeps taking decode writes and stays private."""
        if self.page_index is None or self._warming:
            return
        P = self.page_size
        # Under the slot lock: the reader thread releases finished slots'
        # pages at fold time now, so every allocator mutation (and the
        # table reads feeding pin()) must serialize against it.
        with self._lock:
            for idx, req in group:
                nb = len(req.prompt_ids) // P
                if nb == 0:
                    continue
                pages = [int(p) for p in self.alloc.table[idx, :nb]]
                self.page_index.register(
                    req.prompt_ids[: nb * P], pages, self.alloc
                )

    def _maybe_export(self, group: List[Tuple[int, GenRequest]]) -> None:
        """After a miss admission, copy new prompts' K/V out of the slot
        cache into the prefix store (plus derived longest-common-prefix
        entries, which converge on shared preambles). Best-effort — a
        failed export never fails the requests."""
        store = self.prefix_store
        if store is None or self._warming:
            return
        seen = set()
        for idx, req in group:
            # Store the prompt MINUS its last token: match() requires a
            # proper prefix (a tail token must produce the first-token
            # logits), so this is what makes an exact repeat hit — as a
            # one-token tail. Prompts past the HBM cap store their first
            # max_len tokens (prefix K/V is suffix-independent) — the
            # long-prompt workload is the one that needs caching most.
            ids = tuple(req.prompt_ids[:-1])[: store.max_len]
            if len(ids) < store.min_len:
                # Below the entry floor: this prompt will never cache —
                # one-shot warning instead of the PR 9 NOTE's silence.
                self._warn_min_len(len(req.prompt_ids), "admitted")
                continue
            with self._lock:
                known = ids in seen or store.has(ids)
            if known:
                continue
            seen.add(ids)
            try:
                pb = self._bucket(len(ids))
                # Quantized caches export in float32: dequant→requantize
                # is lossless only when nothing rounds in between — a
                # bf16 store entry would re-quantize to slightly
                # different int8 on the hit path and break repeat
                # determinism (review finding). Costs 2x entry HBM.
                export_dtype = (
                    jnp.float32 if self.kv_quantize else self.cache_dtype
                )
                ks, vs = export_prefix(
                    self.cache, idx, p_bucket=pb, dtype=export_dtype
                )
                # Store bookkeeping under the slot lock: the admission
                # prep thread runs match() against this store.
                with self._lock:
                    store.store(ids, ks, vs, pb)
                    lcps = store.lcp_candidates(ids)
                for p in lcps:
                    pb2 = self._bucket(p)
                    with self._lock:
                        store.store(
                            ids[:p], ks[:, :, :pb2], vs[:, :, :pb2], pb2
                        )
            except Exception as exc:  # noqa: BLE001 — cache is optional
                self._log.warning("prefix export failed: %s", exc)
                return

    def _fold_first_tokens(
        self, groups, hosts: List[np.ndarray],
        poisoned: Optional[List] = None,
    ) -> List:
        """Fold prefill-sampled first tokens into their slots (lock held).
        Entries carry the admission generation, so a stale entry from a
        failed/aborted generation can never feed the slot's next occupant.
        Returns ``(on_tokens, ids)`` stream emissions for the caller to
        fire AFTER releasing the lock; poisoned slots append to
        ``poisoned`` for the caller's outside-the-lock reporting."""
        emits: List = []
        for (rows, _), host in zip(groups, hosts):
            host = np.asarray(host)
            for row, (idx, gen) in enumerate(rows):
                slot = self._slots[idx]
                if slot is None or not slot.first_pending or gen != self._gen[idx]:
                    continue
                slot.first_pending = False
                tok = int(host[row])
                # Poison containment at the fold boundary: an
                # out-of-vocab first token (the host-visible symptom of
                # NaN logits / corrupted device memory) fails THIS
                # request, not the engine.
                if not 0 <= tok < self.cfg.vocab_size:
                    entry = self._poison_slot_locked(idx, [tok])
                    if poisoned is not None:
                        poisoned.append(entry)
                    continue
                slot.generated.append(tok)
                req = slot.request
                if tok != req.eos_id and tok not in req.stop_ids:
                    # TTFT lands here: the flight's first token mark must
                    # precede _check_finished (which may resolve the
                    # future and let the handler close the flight).
                    if req.flight_key is not None:
                        global_flight.token(req.flight_key, 1)
                    if req.on_tokens is not None:
                        emits.append((req.on_tokens, [tok]))
                self._check_finished(idx)
        return emits

    def _poison_slot_locked(
        self, idx: int, bad_ids: List[int]
    ) -> Tuple[int, GenRequest]:
        """Contain a poisoned fold to ITS request (slot lock held): the
        slot releases and the future fails with PoisonedOutput; the
        engine and every other occupant keep serving. Callers run the
        dump/ladder bookkeeping outside the lock."""
        slot = self._slots[idx]
        req = slot.request
        self._slots[idx] = None
        self._gen[idx] += 1
        self._release.append(idx)
        self._release_pages_locked(idx)
        if not req.future.done():
            req.future.set_exception(PoisonedOutput(
                f"decode fold produced out-of-vocab token id(s) "
                f"{bad_ids[:4]} (vocab {self.cfg.vocab_size}, slot {idx}); "
                f"failing this request only"
            ))
        global_metrics.inc("engine.poisoned")
        return idx, req

    def _report_poisoned(
        self, poisoned: List[Tuple[int, GenRequest]]
    ) -> None:
        """Poison observability OUTSIDE the slot lock (dump = file IO)."""
        for idx, req in poisoned:
            self.degrade.record_fault("poison")
            global_steps.record(
                "engine.poison", slot=idx, trace_id=req.trace_id
            )
            global_blackbox.dump(
                "poisoned_fold", trace_id=req.trace_id, slot=idx,
            )
        if poisoned:
            self._prep_wake.set()

    def _drain_first_reads(self) -> None:
        """Reader thread ONLY: fold pending first tokens outside a chunk
        read — the completion path for max_new_tokens <= 1 requests, whose
        zero decode budget never dispatches a chunk. Running this on the
        device thread raced the reader's chunk processing (the reader would
        see first_pending still True mid-drain and silently drop the
        chunk's tokens), so the device thread requests it via a sentinel in
        the results queue instead."""
        with self._lock:
            groups = list(self._first_reads)
            self._first_reads.clear()
        if not groups:
            return
        # Each entry's copy started at admission dispatch; materializing
        # here is not a fresh device round trip.
        hosts = [copy.wait()[0] for _, copy in groups]
        poisoned: List = []
        with host_span("reader.fold_first_tokens"):
            with self._lock:
                emits = self._fold_first_tokens(groups, hosts, poisoned)
            self._report_poisoned(poisoned)
            self._fire_stream(emits)
        self._beat()

    def _check_finished(self, idx: int) -> None:
        """Apply host-side completion rules to a slot; complete + free it
        when generation is over."""
        slot = self._slots[idx]
        if slot is None:
            return
        req = slot.request
        out = slot.generated
        finished = False
        if req.cancelled or req.future.cancelled():
            finished = True
        elif out and (out[-1] == req.eos_id or out[-1] in req.stop_ids):
            finished = True
        elif len(out) >= req.max_new_tokens:
            finished = True
        elif slot.prompt_len + len(out) >= self.max_seq_len - 1:
            finished = True
        if not finished:
            return
        self._slots[idx] = None
        self._release.append(idx)
        # Per-slot early release: the pages go back to the pool NOW (the
        # reader's fold), not at the next admission wave — with the wake
        # below, a page-gated backlog head re-checks can_allocate one
        # pipeline cycle earlier than the wave boundary.
        self._release_pages_locked(idx)
        self._wake.set()
        self._prep_wake.set()
        if out and (out[-1] == req.eos_id or out[-1] in req.stop_ids):
            out = out[:-1]
        now = time.perf_counter()
        latency = now - req.submitted_at
        global_metrics.observe("engine.request_e2e_latency", latency)
        global_metrics.inc("engine.completed")
        global_metrics.inc("engine.generated_tokens", len(out))
        if req.trace_id is not None:
            # The device threads have no asyncio context; emit the
            # request's engine span directly so its trace still nests
            # server → handler → batcher (parent = the handler's
            # engine.generate span id the request carried in).
            global_tracer.emit(
                "engine.batch_decode",
                trace_id=req.trace_id,
                parent_id=req.parent_span_id,
                start=req.submitted_at,
                end=now,
                slot=idx,
                prompt_len=slot.prompt_len,
                tokens=len(out),
            )
        if not req.future.done():
            # A recovered request's result is the tokens accepted BEFORE
            # the fault plus this (re-admitted) generation — the exact
            # sequence an uninterrupted run would have produced for
            # greedy sampling, and exactly what the streaming callbacks
            # already emitted (recovered tokens were streamed pre-fault,
            # never re-emitted).
            if req.recovered_tokens:
                out = req.recovered_tokens + out
            if req.flight_key is not None:
                global_flight.mark(req.flight_key, "batcher_done", at=now)
            req.future.set_result(out)
            if req.recovery_attempts:
                global_metrics.inc("engine.recovered_requests")

    def _release_pages_locked(self, idx: int) -> None:
        """Return a finished/expired/failed slot's KV pages to the pool
        immediately (slot lock held; idempotent — release() clears the
        held list). Device-side stop/free ops still run through
        ``_release`` at the next admission; reusing the pages before
        then is safe because every device op is issued by the device
        thread in program order, so a new occupant's prefill always
        lands AFTER any stale in-flight chunk's writes."""
        if self.alloc is not None:
            if self.alloc.holds(idx):
                global_metrics.inc("engine.early_page_releases")
            self.alloc.release(idx)

    def _active_any(self) -> bool:
        return any(s is not None for s in self._slots)

    def _chunk_useful(self) -> bool:
        """True when at least one occupied slot still has decode budget
        that folded tokens plus in-flight estimates don't already cover
        (lock held)."""
        # Half-a-block tolerance under speculation: the acceptance EMA
        # sits just under D (request tails emit partial blocks), so an
        # exact-boundary check would dispatch one whole wasted weight
        # pass per wave. A boundary miss costs only one fold cycle (the
        # fold corrects the ledger and wakes this loop).
        tol = self._spec_rate / 2 if self.speculate else 0.0
        for s in self._slots:
            if s is None:
                continue
            folded = max(0, len(s.generated) - 1)  # decode tokens landed
            if folded + s.est_pending < s.request.max_new_tokens - 1 - tol:
                return True
        return False

    def _pick_chunk_blocks(self) -> int:
        """Choose the next dispatch's block count (lock held).

        The fixed policy recreates the seed behavior (always
        ``chunk_size``). The adaptive policy projects each live slot's
        remaining need in blocks — remaining token budget minus what
        in-flight chunks are already expected to deliver, divided by
        the speculation-acceptance EMA, capped by the slot's deadline
        budget — and sizes the dispatch to the MEAN projected need
        rather than the straggler's (the r6 profile's 16-block chunks
        against a 12.6-block average). Slots needing more simply get
        the next pipelined chunk; slots finishing inside the chunk fold
        (and early-release) sooner. With queued work waiting, the pick
        drops to the SMALLEST need so a finishing slot's fold/release
        boundary — and therefore backfill — arrives at the earliest
        opportunity (Orca-style iteration-level scheduling). The result
        quantizes UP to the bucket ladder so compiled executables stay
        bounded at len(chunk_buckets) per prefix-bound rung."""
        if self._force_chunk is not None:  # warmup compile sweep
            return max(1, min(self._force_chunk, self.chunk_size))
        # Degrade rung 2+ (reliability/degrade.py): clamp to the
        # smallest compiled bucket — short dispatches mean a short blast
        # radius per fault and fast fold heartbeats for the watchdog.
        if self.degrade.level() >= degrade_levels.MIN_CHUNK:
            return self.chunk_buckets[0]
        if self.chunk_policy != "adaptive":
            return self.chunk_size
        rate = self._spec_rate if self.speculate else 1.0
        rate = max(rate, 0.5)
        now = time.monotonic()
        needs: List[int] = []
        for s in self._slots:
            if s is None:
                continue
            folded = max(0, len(s.generated) - 1)
            rem = (
                s.request.max_new_tokens - 1 - folded - s.est_pending
            )
            if rem <= 0:
                continue
            need = int(-(-rem // rate))
            ddl = s.request.deadline
            if ddl is not None and self._block_seconds > 0:
                # Blocks past the deadline are pure waste: the sweep
                # force-releases the slot before they fold.
                cap = int((ddl - now) / self._block_seconds)
                need = min(need, max(cap, 1))
            needs.append(max(need, 1))
        if not needs:
            return self.chunk_buckets[0]
        target = sum(needs) / len(needs)
        if self._backlog or self._pending.qsize() or self._prepped_reqs:
            target = min(target, float(min(needs)))
        for b in self.chunk_buckets:
            if b >= target:
                return b
        return self.chunk_buckets[-1]

    def _dispatch_chunk(
        self, prefix_bound: int, n_blocks: int, est: float = 0.0,
        hi: int = 0, table_np: Optional[np.ndarray] = None,
    ):
        # Chaos point: a failed decode dispatch. Raises propagate to the
        # device loop boundary → _fail_occupied_slots RECOVERS the
        # occupants (re-admission after rebuild) or, strikes exhausted,
        # fails them with this exception; queued requests are untouched.
        global_injector.fire("engine.step")
        # Chaos point: a serving-mesh device fails mid-decode. value=
        # the boot-order device index — the dispatch raises
        # ShardLossError, the device-loop boundary classifies it and
        # the rebuild re-plans onto the surviving sub-mesh. The dict
        # form {"device": i, "hang": True} freezes that shard's
        # heartbeat instead (no raise): the per-shard watchdog triage
        # is then the only detector, exactly like a chip that stops
        # answering without erroring.
        loss = global_injector.fire("mesh.shard_loss")
        if loss is not None:
            if isinstance(loss, dict) and loss.get("hang"):
                if self._mesh_ladder is not None:
                    self._mesh_ladder.freeze(int(loss.get("device", 0)))
            else:
                raise ShardLossError(
                    0 if isinstance(loss, bool) else int(loss),
                    detail="injected",
                )
        # Chaos point: a STUCK dispatch — delay= pins the device thread
        # here without raising, exactly the shape of a hung XLA call or
        # a wedged collective. Nothing downstream ever observes it; the
        # watchdog's heartbeat staleness is the only detector.
        global_injector.fire("engine.dispatch.hang")
        # How long the device sat with NOTHING in flight between the
        # last fold/feed and this dispatch; 0 whenever the pipeline
        # still held work. A host-side approximation that only the
        # attribution gauges read: the profiler trace, with this
        # thread's spans in its host lanes, is what measures idle.
        t_dispatch = time.perf_counter()
        with self._lock:
            idle = self._inflight == 0
            marks = [
                t for t in (self._last_fold_done, self._last_prefill_t)
                if t is not None
            ]
        gap_ms = (
            max(0.0, (t_dispatch - max(marks)) * 1e3)
            if idle and marks else 0.0
        )
        if gap_ms > 0.0 and not self._warming:
            # Measured device-idle bubble: the live busy-frac gauge is
            # the complement of these over its window.
            global_attribution.record_gap(gap_ms / 1e3, at=t_dispatch)
        # Block table from the caller's under-lock snapshot (the reader
        # thread mutates rows at early release); absent when dense.
        table = jnp.asarray(table_np) if table_np is not None else None
        # Paged prefix reads: the per-page Pallas kernel streams only the
        # pages a slot owns, but pays a per-grid-cell latency that
        # dominates at serving-sized bounds (profiled on v5e: ~2x block
        # time at a 2K bound vs materializing dense panels once per
        # chunk and letting XLA's dense attention read them). Use the
        # gather fallback while the transient panels fit comfortably in
        # HBM; switch to the kernel only at bounds where they would not.
        use_pallas_now = self.use_pallas
        if self.paged and self.use_pallas:
            gather_bytes = (
                2 * self.cfg.n_kv_layers * self.n_slots * self.cfg.n_kv_heads
                * prefix_bound * self.cfg.head_dim
                * jnp.dtype(self.cfg.dtype).itemsize
            )
            use_pallas_now = gather_bytes > self._gather_budget
        if self.paged:
            # Which prefix reader this chunk ran: `pallas=True` in the
            # boot line says the kernel MAY run, these say it did.
            global_metrics.inc(
                "engine.paged_chunks.kernel" if use_pallas_now
                else "engine.paged_chunks.gather"
            )
        # Token-mask tables ride along only while a live slot constrains
        # (see _dispatch_prefill). Lock-free read is safe: slots are INSTALLED
        # on this thread (so a constraining slot is always seen), and the
        # reader only clears them (worst case: tables ride one extra
        # chunk).
        chunk_json = (
            self.json_tables
            if any(
                s is not None and s.request.json_mode for s in self._slots
            ) else None
        )
        chunk_schema = (
            self._schema_tables()
            if any(
                s is not None and s.request.json_schema_id >= 0
                for s in self._slots
            ) else None
        )
        # Fused decode epilogue (ISSUE 14): when every OCCUPIED slot is
        # greedy and unconstrained, the chunk's sampler fuses into the
        # vocab-tiled projection+argmax (engine/decode.py). Same
        # lock-free slot read as the table gating above — slots install
        # on this thread, so a sampled/JSON occupant is always seen; the
        # reader only clears, worst case one conservative (unfused)
        # chunk. Static flag → at most one extra executable per decode
        # variant, compiled at warmup (warmup traffic is greedy).
        # NOTE: gate on the REQUESTS, not on chunk_json/chunk_schema —
        # byte tokenizers constrain through the built-in byte automaton
        # with json_tables=None, so "no tables riding" does NOT imply
        # "no constrained slot".
        fused_now = (
            self.fused_epilogue
            and all(
                s is None or (
                    s.request.temperature <= 0.0
                    and not s.request.json_mode
                    and s.request.json_schema_id < 0
                )
                for s in self._slots
            )
        )
        # Degrade rung 1+ (reliability/degrade.py): speculative MODEL
        # drafting off — n-gram drafts only. The mode vector is a traced
        # input, so an all-False vector reuses the compiled executable
        # while skipping the shallow-layer draft passes on a device that
        # is already faulting.
        draft_vec = self._draft_on
        if (
            self.draft_layers
            and self.degrade.level() >= degrade_levels.NO_DRAFT
        ):
            draft_vec = np.zeros_like(self._draft_on)
        with global_metrics.timer("engine.chunk_dispatch_latency"):
            if self.speculate:
                (
                    toks, valid, self.cache, self.dstate, self.sampling,
                    self.history,
                ) = decode_chunk_spec(
                    self.params, self.cfg, self.cache, self.dstate,
                    self.sampling, self.history, n_blocks,
                    self.speculate, prefix_bound=prefix_bound,
                    json_tables=chunk_json, schema_tables=chunk_schema,
                    table=table,
                    use_pallas=self.paged and use_pallas_now,
                    page_strip=self.page_strip,
                    kv_mesh=(
                        self.kv_mesh
                        if self.paged and use_pallas_now else None
                    ),
                    draft_layers=self.draft_layers,
                    draft_mode=(
                        jnp.asarray(draft_vec)
                        if self.draft_layers else None
                    ),
                    fused_epilogue=fused_now,
                )
            else:
                toks, valid, self.cache, self.dstate, self.sampling = (
                    decode_chunk(
                        self.params, self.cfg, self.cache, self.dstate,
                        self.sampling, n_blocks, use_pallas_now,
                        prefix_bound=prefix_bound, table=table,
                        json_tables=chunk_json, schema_tables=chunk_schema,
                        page_strip=self.page_strip,
                        kv_mesh=(
                            self.kv_mesh
                            if self.paged and use_pallas_now else None
                        ),
                        fused_epilogue=fused_now,
                    )
                )
        # Start the D2H transfer the moment the chunk is enqueued: the
        # reader folds from this already-in-flight copy one pipeline
        # cycle later (a wait on a landed transfer, not a fresh blocking
        # device→host sync — and never a jax.device_get).
        # The expert layers' running counts ride the cache, which the next
        # dispatch donates: a copy of the two numbers is what goes home.
        routed = (
            (jnp.copy(self.cache.state.routed),)
            if self.cache.state is not None else ()
        )
        copies = _HostCopy((toks, valid) + routed)
        with self._lock:
            self._inflight += 1
        # engine.decode_steps is counted at fold time (_process_chunk)
        # from folded validity — executed block-steps, not the
        # dispatched chunk length, which overcounted whenever early
        # exit / done slots ran fewer blocks than dispatched. The
        # dispatch stamp feeds the per-block wall-time EMA.
        return (
            copies, tuple(self._gen), est, hi, n_blocks,
            time.perf_counter(), gap_ms,
        )

    def _process_chunk(
        self, copies, gen_stamp, est, hi, n_blocks, t_dispatch, gap_ms,
    ) -> None:
        """Fold one finished chunk's tokens into slots (reader thread).
        The chunk's D2H copy started at dispatch time (``_HostCopy``);
        this wait materializes it — while chunk N+1 executes on device —
        rather than opening a fresh blocking round trip. Pending
        first-token copies (started at their admission dispatch) fold on
        the same pass."""
        with self._lock:
            groups = list(self._first_reads)
            self._first_reads.clear()
        with global_metrics.timer("engine.chunk_read_latency"):
            toks_h, valid_h, *routed_h = copies.wait()
            first_hosts = [copy.wait()[0] for _, copy in groups]
        if routed_h:
            # Token-expert pairs routed, and those that landed on experts
            # held here, since the last fold (admissions' included); the
            # device's counts wrap at 2**32, so the difference does too.
            delta = routed_h[0] - self._routed_seen
            self._routed_seen = routed_h[0]
            if not self._warming:
                global_metrics.inc("engine.moe_assignments", int(delta[0]))
                global_metrics.inc("engine.moe_assignments_held", int(delta[1]))
        # The device's result is on the host: what follows is this
        # thread's own work.
        with host_span("reader.process_chunk", blocks=n_blocks):
            self._fold_chunk(
                toks_h, valid_h, groups, first_hosts, gen_stamp, est, hi,
                n_blocks, t_dispatch, gap_ms,
            )

    def _fold_chunk(
        self, toks_h, valid_h, groups, first_hosts, gen_stamp, est, hi,
        n_blocks, t_dispatch, gap_ms,
    ) -> None:
        # Chaos point: poison one slot's folded ids with an out-of-vocab
        # value at the fold boundary (value= the slot index, or True for
        # the first slot that emitted) — drives the containment path a
        # real NaN-logits / corrupted-HBM fold would take.
        corrupt = global_injector.fire("engine.fold.corrupt")
        if corrupt is not None and toks_h.size:
            toks_h = toks_h.copy()
            if isinstance(corrupt, bool) or not isinstance(corrupt, int):
                cols = np.flatnonzero(valid_h.any(axis=0))
                corrupt = int(cols[0]) if cols.size else 0
            toks_h[:, corrupt] = self.cfg.vocab_size + 7
        n, B = toks_h.shape
        # Poison precheck, vectorized: one pass over the fold buffer; the
        # per-slot containment below only runs when something is actually
        # out of vocab (never on the healthy hot path).
        bad_valid = ((toks_h < 0) | (toks_h >= self.cfg.vocab_size)) & valid_h
        any_bad = bool(bad_valid.any())
        # One block-validity view serves the draft EMA, the utilization
        # counters and the acceptance EMA below.
        blk_any = valid_h.reshape(
            n_blocks, self.speculate or 1, B
        ).any(axis=1)                                        # [n_blocks, B]
        if self.speculate and self.draft_layers:
            slot_blocks = blk_any.sum(axis=0)                # [B]
            slot_tokens = valid_h.sum(axis=0)
        emits: List = []
        poisoned: List = []
        with self._lock:
            # First tokens were sampled before this chunk ran — fold them
            # first so token order inside each slot is right.
            if groups:
                with host_span("reader.fold_first_tokens"):
                    emits = self._fold_first_tokens(
                        groups, first_hosts, poisoned
                    )
            for b in range(B):
                slot = self._slots[b]
                if slot is None or gen_stamp[b] != self._gen[b]:
                    continue
                if self.speculate and self.draft_layers and slot_blocks[b]:
                    # Per-slot acceptance EMA + hysteresis for the draft
                    # source — under the lock AND behind the generation
                    # stamp, so a late chunk from an evicted request can
                    # never demote the slot's new occupant to the paid
                    # model-draft mode (review finding). Thresholds scale
                    # with D: at small D the absolute 3.0 hand-back was
                    # unreachable and draft mode latched on forever.
                    obs_b = slot_tokens[b] / slot_blocks[b]
                    self._slot_rate[b] = (
                        0.5 * self._slot_rate[b] + 0.5 * obs_b
                    )
                    D = self.speculate
                    enter = 1.0 + 0.125 * D
                    if not self._draft_on[b] and self._slot_rate[b] < enter:
                        self._draft_on[b] = True
                    elif self._draft_on[b] and (
                        self._slot_rate[b] > enter + 0.25 * D
                    ):
                        self._draft_on[b] = False
                # This chunk's contribution leaves the in-flight ledger
                # whether or not tokens landed (same occupant only).
                slot.est_pending = max(0.0, slot.est_pending - est)
                slot.hi_pending = max(0, slot.hi_pending - hi)
                if slot.first_pending:
                    continue
                req = slot.request
                # Poison containment: validate what crosses the fold
                # boundary. Out-of-vocab ids are the host-visible symptom
                # of NaN logits or corrupted device memory; they fail
                # ONLY this slot's request — folding them would crash (or
                # corrupt) the tokenizer and detokenized stream instead.
                if any_bad and bad_valid[:, b].any():
                    bad = [int(t) for t in toks_h[bad_valid[:, b], b]]
                    poisoned.append(self._poison_slot_locked(b, bad))
                    continue
                fresh: List[int] = []
                for i in range(n):
                    if not valid_h[i, b]:
                        continue
                    tok = int(toks_h[i, b])
                    slot.generated.append(tok)
                    if tok != req.eos_id and tok not in req.stop_ids:
                        fresh.append(tok)
                        # Per-token flight mark (ITL/TPOT) — before
                        # _check_finished can resolve the future.
                        if req.flight_key is not None:
                            global_flight.token(req.flight_key, 1)
                    self._check_finished(b)
                    if self._slots[b] is None:
                        break
                if fresh and req.on_tokens is not None:
                    emits.append((req.on_tokens, fresh))
            slots_active = sum(s is not None for s in self._slots)
        self._report_poisoned(poisoned)
        self._fire_stream(emits)
        # Chunk utilization: blocks where at least one slot emitted ÷
        # blocks dispatched. The gap is exactly the straggler/tail waste
        # adaptive sizing attacks — a fixed 16-block chunk whose slots
        # all finished by block 5 scores 5/16, an adaptive 8-block pick
        # 5/8. The gauge is cumulative (counters carry the exact
        # numerator/denominator); the ring record carries this
        # dispatch's own numbers for the Perfetto counter track.
        useful_blocks = int(blk_any.any(axis=1).sum())
        accepted = int(valid_h.sum())
        global_metrics.inc("engine.blocks_dispatched", n_blocks)
        global_metrics.inc("engine.blocks_useful", useful_blocks)
        disp_total = global_metrics.get("engine.blocks_dispatched")
        if disp_total > 0:
            global_metrics.set_gauge(
                "engine.chunk_utilization",
                global_metrics.get("engine.blocks_useful") / disp_total,
            )
        # decode_steps = device block-steps that actually emitted,
        # counted HERE from folded validity rather than
        # chunk_size-per-dispatch at dispatch time — early exit and
        # done slots made the old count overstate executed work, so
        # rate derivations (and SERVING.md's acceptance formula
        # tokens ÷ (decode_steps × slots)) disagreed with reality.
        global_metrics.inc("engine.decode_steps", useful_blocks)
        global_metrics.inc("engine.chunk_folds")
        # Wall-seconds per block EMA for the sizing policy's deadline
        # budget: THIS chunk's dispatch→fold latency over its blocks.
        # (A fold-to-fold gap would absorb idle time between requests
        # on low-traffic deployments and inflate the estimate 10-100x,
        # clamping every deadline-bound dispatch to the smallest
        # bucket.) Pipeline overlap makes this a mild overestimate —
        # conservative in the right direction for a deadline cap.
        per_block = (time.perf_counter() - t_dispatch) / max(n_blocks, 1)
        if 0.0 < per_block < 5.0:
            self._block_seconds = (
                0.5 * self._block_seconds + 0.5 * per_block
                if self._block_seconds else per_block
            )
        # Engine step telemetry: one bounded ring record per folded chunk
        # — what the black-box dump replays when a request dies.
        depth = self.queue_depth()
        global_metrics.set_gauge("engine.queue_depth", float(depth))
        global_steps.record(
            "engine.chunk",
            tokens=accepted,
            chunk_blocks=n_blocks,
            blocks_useful=useful_blocks,
            utilization=round(useful_blocks / max(n_blocks, 1), 3),
            slots_active=slots_active,
            queue_depth=depth,
            page_strip=self.page_strip,
            pipeline_depth=self.PIPELINE_DEPTH,
            **(
                {"kv_pages_free": self.alloc.free_pages,
                 "kv_pages_total": self.num_pages - 1}
                if self.alloc is not None else {}
            ),
        )
        if self.speculate:
            # Observed tokens-per-block over blocks that actually emitted
            # (done-slot and trailing no-op blocks excluded — counting
            # them drags the EMA back toward 1 and re-creates the wasted
            # weight passes the estimate exists to avoid).
            D = self.speculate
            active_blocks = int(blk_any.sum())
            if active_blocks > 0:
                obs = accepted / active_blocks
                obs = min(max(obs, 0.5), float(D))
                self._spec_rate = 0.5 * self._spec_rate + 0.5 * obs
                # Exported as a 0..1 acceptance fraction (EMA tokens
                # per block over the draft depth) — the workload
                # fingerprint reads this back to characterize how
                # speculation-friendly the traffic is (obs/profile.py).
                global_metrics.set_gauge(
                    "engine.spec_acceptance", self._spec_rate / float(D)
                )
        global_metrics.inc("engine.generated_tokens_device", accepted)
        # Host-gap bookkeeping: this chunk has left the pipeline; the
        # next dispatch measures its bubble from here.
        t_fold = time.perf_counter()
        with self._lock:
            self._inflight = max(0, self._inflight - 1)
            self._last_fold_done = t_fold
            prev_mark = self._last_attr_mark
            self._last_attr_mark = t_fold
            pf_since = self._prefill_since_fold
            self._prefill_since_fold = 0.0
        if not self._warming:
            # Fill of this chunk: rows that held a live request against
            # the rows the program ran, over the blocks that emitted.
            global_metrics.inc("engine.decode_rows_active", int(blk_any.sum()))
            global_metrics.inc(
                "engine.decode_rows_run", self.n_slots * useful_blocks
            )
            # Decode device-time estimate: the fold-to-fold interval
            # minus the measured idle gap and any prefill enqueue walls
            # inside it (already attributed above). Pipelined chunks make
            # per-dispatch walls overlap; fold-to-fold sums to occupancy
            # instead of double-counting. Achieved FLOPs count ACCEPTED
            # tokens only (folded validity) — rejected speculative rows
            # ran the weights but did no useful work.
            if prev_mark is not None:
                dur = max(t_fold - prev_mark - gap_ms / 1e3 - pf_since, 0.0)
            else:
                dur = max(t_fold - t_dispatch, 0.0)
            self._record_attributed(
                "decode", dur, accepted,
                est=(
                    self.collective_model.decode_seconds(
                        n_blocks, self.n_slots, accepted
                    )
                    if self.collective_model is not None else None
                ),
                at=t_fold,
            )
        # Fold landed: the watchdog's definition of forward progress.
        self._beat()

    def _restore_place(self, arr):
        """Host→device upload for KV-tier restore panels, following the
        pool's 'model'-axis sharding when it has one (identity layout
        otherwise). Shapes: dense entries [L, K, rows, H]; paged restore
        chains [L, 1, rows, K, H]."""
        mesh = self._kv_place_mesh
        if mesh is None or not self.kv_heads_sharded:
            return jax.device_put(arr)
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        K = self.cfg.n_kv_heads
        if arr.ndim == 4 and arr.shape[1] == K:
            spec = P(None, "model", None, None)
        elif arr.ndim == 5 and arr.shape[3] == K:
            spec = P(None, None, None, "model", None)
        else:
            return jax.device_put(arr)
        return jax.device_put(arr, NamedSharding(mesh, spec))

    def _record_attributed(
        self,
        phase: str,
        wall_s: float,
        tokens: int,
        est: Optional[Dict[str, float]] = None,
        at: Optional[float] = None,
    ) -> None:
        """One dispatch's device-time attribution, with per-axis
        collective time carved out of the measured wall (ISSUE 13).
        ``est`` is the CollectiveModel's per-axis seconds estimate for
        this dispatch; the split never invents time — collective +
        compute records sum to exactly the measured wall, so
        ``engine.collective_frac[.axis]`` is a share of real device
        time. Off-mesh (est None/empty) this is the plain single-record
        path the gauges always had."""
        if est:
            compute_s, coll = self.collective_model.split(wall_s, est)
            global_attribution.record(
                phase, compute_s, tokens=tokens, at=at, collective=coll,
            )
        else:
            global_attribution.record(phase, wall_s, tokens=tokens, at=at)

    def _fire_stream(self, emits: List) -> None:
        """Fire streaming callbacks OUTSIDE the slot lock (reader thread).
        A callback is user code bridging into an event loop; holding the
        lock across it would let a slow consumer stall folding."""
        for cb, ids in emits:
            try:
                cb(ids)
            except Exception as exc:  # noqa: BLE001 — consumer's problem
                self._log.warning("stream callback failed: %s", exc)

    def _read_loop(self) -> None:
        """Reader thread: blockingly reads dispatched chunks and resolves
        completions, so the device thread never stalls on a transfer."""
        while True:
            try:
                item = self._results.get(timeout=0.1)
            except queue.Empty:
                if self._stop.is_set():
                    break
                continue
            try:
                if item is None:  # drain-first-tokens sentinel
                    with self._lock:
                        self._drain_queued = False
                    self._drain_first_reads()
                    self._wake.set()
                    continue
                self._process_chunk(*item)
            except Exception as exc:  # noqa: BLE001 — reader boundary
                # The chunk's tokens are lost on the host while the device
                # has already consumed their budget; swallowing would hang
                # the affected requests forever and leak their slots.
                # Recovery re-admits the occupants; the rebuild request
                # (consumed by the device thread, where rebuilds are
                # safe) resets the pool a failed transfer makes suspect.
                # Flag BEFORE the sweep: _fail_occupied_slots wakes the
                # device thread, and it must observe the rebuild request
                # before it can re-admit the recovered requests — or
                # they would prefill against the suspect pool and the
                # deferred rebuild would then swap state under live
                # occupants (silent output corruption).
                self._log.error("reader error: %s", exc, exc_info=True)
                self._rebuild_requested = "reader_error"
                self._fail_occupied_slots(exc)
                # The failed chunk left the pipeline without reaching
                # _process_chunk's bookkeeping tail. Sentinel failures
                # (first-token drains) never entered the pipeline, so
                # decrementing for them would mark a still-executing
                # chunk's window as idle and fake a host-gap sample.
                if item is not None:
                    with self._lock:
                        self._inflight = max(0, self._inflight - 1)
            self._wake.set()
        self._log.info("reader stopped")

    def _rebuild_device_state(self, reason: Optional[str] = None) -> None:
        """(Re)create cache/sampling/decode state — at construction, and
        after a failed dispatch left the previous buffers consumed or
        suspect (device thread only; failure callers must fail/recover
        the occupants first). The allocator swap and epoch bump happen
        under the slot lock, so a concurrent admission prep can never
        allocate half in the old pool and half in the new: a prep
        stamped with the old epoch requeues at dispatch time instead of
        prefilling against the fresh allocator's sentinel rows.

        ``reason`` marks a FAILURE-path rebuild (None = construction):
        those were previously visible only as log lines — now each one
        counts under ``engine.rebuilds{reason=}``, lands in the step
        ring and writes a black-box dump, so an engine quietly
        rebuilding once a minute shows up on a dashboard instead of in
        grep."""
        if reason is not None:
            # Chaos point: a rebuild that itself fails (exc=) — retried
            # next device-loop cycle via _rebuild_requested.
            global_injector.fire("engine.rebuild", reason=reason)
        if reason == "shard_loss" and self._mesh_ladder is not None:
            # Degraded-mesh rebuild (ISSUE 16): re-plan onto the
            # surviving sub-mesh and re-place the weights BEFORE the
            # pool is recreated, so place_kv_cache below lays the fresh
            # KV out on the new plan. The occupants were already swept
            # into recovery by the failure arm; their re-prefill runs
            # on the degraded mesh and greedy output stays
            # byte-identical (nothing trusts the old pool). Raises
            # MeshLadderExhausted only if the ladder emptied between
            # the failure arm's viable() check and here — the caller's
            # retry path handles it like any failed rebuild.
            self._replan_mesh()
        # KV for the layers that keep KV; beside it, for a stack of unlike
        # layers, the per-slot pool of the others' state.
        state = StatePool.create(self.cfg, self.n_slots, self.cache_dtype)
        self._routed_seen = np.zeros((2,), np.uint32)
        if self.paged:
            cache = PagedKVCache.create(
                self.cfg.n_kv_layers, self.n_slots, self.num_pages,
                self.page_size, self.cfg.n_kv_heads, self.cfg.head_dim,
                dtype=self.cache_dtype, quantized=self.kv_quantize,
                state=state,
            )
            alloc = PageAllocator(
                self.num_pages, self.page_size, self.n_slots,
                self.max_pages_per_slot,
            )
        else:
            cache = KVCache.create(
                self.cfg.n_kv_layers, self.n_slots, self.max_seq_len,
                self.cfg.n_kv_heads, self.cfg.head_dim,
                dtype=self.cache_dtype, quantized=self.kv_quantize,
                state=state,
            )
            alloc = None
        # Serving-mesh layout AT CREATION (parallel/sharding.py): paged
        # pool kv-heads shard over 'model', dense panels over
        # ('data'/'fsdp', 'model'). The cache is donated through every
        # dispatch, so the initial committed layout is what jit's
        # argument shardings follow — placing it here means the first
        # dispatch starts sharded instead of paying a whole-pool
        # reshard, and a failure-path rebuild restores the same layout.
        cache = place_kv_cache(
            cache, self._kv_place_mesh,
            n_kv_heads=self.cfg.n_kv_heads, n_slots=self.n_slots,
        )
        with self._lock:
            self.cache = cache
            self.alloc = alloc
            self._alloc_epoch += 1
            # A fresh pool invalidates every cached page — reset the
            # index's bookkeeping (the allocator above is new, so no
            # unpinning against the old one).
            if self.paged and getattr(self, "page_index", None) is not None:
                self.page_index.clear()
        self.sampling = SamplingState.create(self.n_slots)
        self.dstate = DecodeState.create(self.n_slots)
        # Per-slot token-id history by position (speculative drafting).
        self.history = (
            jnp.zeros((self.n_slots, self.max_seq_len), jnp.int32)
            if self.speculate else None
        )
        if reason is not None:
            global_metrics.inc("engine.rebuilds")
            global_metrics.inc(f"engine.rebuilds.{reason}")
            global_steps.record("engine.rebuild", reason=reason)
            global_blackbox.dump("engine_rebuild", rebuild_reason=reason)
            self._log.warning("device state rebuilt (reason=%s)", reason)
            # The rebuild IS forward progress — recovery re-admissions
            # must not race the watchdog's stall clock.
            self._beat()

    def _recoverable(self, req: GenRequest, now: float) -> bool:
        """May this request re-admit instead of failing? (lock held)"""
        return (
            self.recovery_max_attempts > 0
            and req.recovery_attempts < self.recovery_max_attempts
            and not req.cancelled
            and not req.future.cancelled()
            and (req.deadline is None or now < req.deadline)
        )

    def _recovery_decision_locked(
        self, req: GenRequest, exc: Exception, now: float, t_snap: float
    ) -> bool:
        """ONE requeue-or-fail policy for every failure arm (slot lock
        held). True → the request re-admits: attempts bumped, recovery
        stamp set — the CALLER appends it to the backlog so each site
        keeps its own FIFO ordering. False → the future was failed with
        ``exc`` (strike accounting included)."""
        if self._recoverable(req, now):
            req.recovery_attempts += 1
            req.recovery_started_at = t_snap
            return True
        if (
            self.recovery_max_attempts > 0
            and req.recovery_attempts >= self.recovery_max_attempts
        ):
            global_metrics.inc("engine.recovery_failed")
        req.future.set_exception(exc)
        return False

    def _fail_occupied_slots(
        self, exc: Exception, record_fault: bool = True,
        allow_recovery: bool = True,
    ) -> None:
        """Contain a device/transfer failure to the ENGINE, not its
        requests (either thread). Every occupied slot's progress —
        original prompt plus the tokens already accepted — is
        snapshotted and re-admitted at the backlog head through the
        normal admission path: the re-prefill runs over prompt+generated
        (the prefix cache absorbs most of it when the pool survived), so
        a greedy request's final output is byte-identical to an
        uninterrupted run, and streaming consumers resume at the next
        NEW token (``recovered_tokens`` are never re-emitted). Attempts
        are bounded per request (``recovery_max_attempts`` strikes →
        fail with the original exception); cancelled/expired requests
        and grammar-constrained requests that already streamed tokens
        fail immediately (the JSON automaton's state is derived from
        the position *after the prompt*, so a spliced replay prompt
        would constrain against the wrong state — restart-from-scratch
        is only transparent when nothing was emitted).

        ``allow_recovery=False`` ends the containment contract: every
        occupant fails with the original exception regardless of
        remaining strikes — the mesh ladder exhausted, so there is no
        device state left to recover ONTO (PR 8's strikes-exhausted
        semantics, reached structurally instead of by count)."""
        now = time.monotonic()
        t_snap = time.perf_counter()
        recovered: List[GenRequest] = []
        failed = 0
        with self._lock:
            for i, slot in enumerate(self._slots):
                if slot is None:
                    continue
                self._slots[i] = None
                self._gen[i] += 1
                self._release.append(i)
                self._release_pages_locked(i)
                req = slot.request
                if req.future.done():
                    continue
                replay = list(slot.generated)
                if not allow_recovery:
                    req.future.set_exception(exc)
                    failed += 1
                    continue
                json_bound = req.json_mode or req.json_schema_id >= 0
                if json_bound and replay and req.on_tokens is not None:
                    # Streamed grammar-constrained output can neither be
                    # spliced (DFA state is position-derived) nor
                    # restarted (the consumer already saw tokens).
                    req.future.set_exception(exc)
                    failed += 1
                    continue
                if not self._recovery_decision_locked(req, exc, now, t_snap):
                    failed += 1
                    continue
                if json_bound and replay:
                    # Restart the whole generation (nothing was
                    # streamed): the grammar mask re-derives cleanly
                    # from the original prompt, and greedy output is
                    # the same either way.
                    replay = []
                if replay:
                    # New list, not in-place extend: callers hold
                    # references to the original prompt (usage counting).
                    req.prompt_ids = req.prompt_ids + replay
                    req.recovered_tokens.extend(replay)
                    req.max_new_tokens -= len(replay)
                    global_metrics.inc("engine.tokens_replayed", len(replay))
                recovered.append(req)
            self._first_reads.clear()
            # Backlog HEAD in original submission order: these requests
            # were admitted earliest, so FIFO fairness keeps holding.
            for req in reversed(recovered):
                self._backlog.appendleft(req)
        if recovered or failed:
            global_metrics.inc("engine.recovery_requeued", len(recovered))
            global_steps.record(
                "engine.recovery",
                requeued=len(recovered),
                failed=failed,
                error=str(exc)[:200],
            )
            self._log.warning(
                "engine failure (%s): %d in-flight request(s) requeued "
                "for recovery, %d failed", exc, len(recovered), failed,
            )
        if record_fault:
            # record_fault=False when the caller already counted this
            # incident (the prefill-failure arms record "prefill" first)
            # — one incident must step the ladder once, not twice.
            self.degrade.record_fault("device")
        self._prep_wake.set()
        self._wake.set()

    def _run(self) -> None:
        self._log.info(
            "device loop starting (slots=%d, max_seq=%d, chunk=%d, pallas=%s)",
            self.n_slots, self.max_seq_len, self.chunk_size, self.use_pallas,
        )
        while not self._stop.is_set():
            try:
                # Self-heal after any donated dispatch (decode_chunk too,
                # not just admission) failed mid-flight and consumed the
                # state buffers — or after another thread's failure path
                # requested a rebuild; the failure arms already
                # failed/recovered the occupants on the way here.
                if (
                    self.cache.lengths.is_deleted()
                    or self._rebuild_requested is not None
                ):
                    reason = self._rebuild_requested or "state_consumed"
                    self._rebuild_requested = None
                    # A deferred rebuild can race an admission that was
                    # mid-dispatch when the requesting thread swept its
                    # occupants (slots install only after admit_group
                    # returns): anyone occupying a slot NOW must be
                    # recovered before the swap, or they would decode
                    # against the fresh allocator's sentinel rows.
                    # Idempotent when the original sweep got everyone.
                    if any(s is not None for s in self._slots):
                        self._fail_occupied_slots(
                            RuntimeError(
                                f"device state rebuilt ({reason}) with "
                                f"request in flight"
                            ),
                            record_fault=False,
                        )
                    self._rebuild_device_state(reason=reason)
                with host_span("batcher.expire_deadlines"):
                    self._expire_deadlines()
                with host_span("batcher.admit"):
                    self._admit()
                with host_span("batcher.pick_chunk"), self._lock:
                    useful = self._chunk_useful()
                    if useful:
                        # Scheduling decision: this dispatch's block
                        # count, from remaining budgets + acceptance EMA
                        # (bucket-quantized; constant under "fixed").
                        n_blocks = self._pick_chunk_blocks()
                        # Upper bound on any live slot's cache length at
                        # chunk start (device lengths ≤ prompt + folded
                        # decode tokens + the in-flight chunks' hard
                        # maximum), taken BEFORE this chunk's own tokens
                        # are counted.
                        bound = max(
                            s.prompt_len + min(
                                max(0, len(s.generated) - 1)
                                + s.hi_pending,
                                s.request.max_new_tokens - 1,
                            )
                            for s in self._slots
                            if s is not None
                        )
                        est = n_blocks * (
                            self._spec_rate if self.speculate else 1.0
                        )
                        hi = n_blocks * (self.speculate or 1)
                        for s in self._slots:
                            if s is not None:
                                s.est_pending += est
                                s.hi_pending += hi
                        # Block-table snapshot under the lock: the
                        # reader mutates rows at early page release.
                        table_np = (
                            self.alloc.table.copy()
                            if self.alloc is not None else None
                        )
                if useful:
                    bucket = self._decode_bucket(bound)
                    with host_span(
                        "batcher.dispatch_chunk", blocks=n_blocks,
                        bound=bucket,
                    ):
                        item = self._dispatch_chunk(
                            bucket, n_blocks, est, hi, table_np,
                        )
                    try:
                        self._results.put_nowait(item)
                    except queue.Full:
                        # The reader is behind: this thread has nothing
                        # to do but wait for it. One short span a try:
                        # a profiler session records only spans that
                        # begin and end inside it, and a single span
                        # over a wait of seconds would leave the traced
                        # slice with nothing from this thread.
                        while not self._stop.is_set():
                            try:
                                with host_span("batcher.results_full"):
                                    self._results.put(item, timeout=0.05)
                                break
                            except queue.Full:
                                continue
                else:
                    with self._lock:
                        need_drain = (
                            bool(self._first_reads) and not self._drain_queued
                        )
                        if need_drain:
                            self._drain_queued = True
                    if need_drain:
                        self._results.put(None)  # reader folds, in order
                    with host_span("batcher.wait_for_work"):
                        self._wake.wait(timeout=0.05)
                    self._wake.clear()
            except Exception as exc:  # noqa: BLE001 — device loop boundary
                self._log.error("device loop error: %s", exc, exc_info=True)
                # Shard-loss triage (ISSUE 16): an error that names a
                # failed DEVICE is a loss of that shard, not a generic
                # dispatch failure — mark it lost and rebuild onto the
                # surviving sub-mesh. When the ladder has no rung left
                # for the survivors, the containment contract ends and
                # the occupants fail with the original exception (the
                # PR 8 strikes-exhausted semantics).
                reason = "device_loop_error"
                recover = True
                ladder = self._mesh_ladder
                if isinstance(exc, MeshLadderExhausted):
                    recover = False
                elif ladder is not None:
                    dev = classify_device_error(exc)
                    if dev is not None:
                        ladder.mark_lost(dev)
                        global_metrics.inc("engine.shard_losses")
                        if ladder.viable():
                            reason = "shard_loss"
                        else:
                            recover = False
                self._fail_occupied_slots(exc, allow_recovery=recover)
                # Conservative containment: a dispatch that raised
                # mid-flight may have partially mutated device state even
                # when the donated buffers survived — rebuild fresh so
                # recovered re-admissions never decode against suspect
                # KV. (This is what makes recovered greedy output
                # byte-identical by construction: everything re-prefills
                # from the tokens, nothing trusts the old pool.)
                try:
                    self._rebuild_device_state(reason=reason)
                except MeshLadderExhausted as rexc:
                    # Raced to exhaustion after the viable() check:
                    # nothing to rebuild onto — fail anything that
                    # slipped into recovery and stop re-planning.
                    self._log.error("mesh ladder exhausted: %s", rexc)
                    self._fail_occupied_slots(
                        exc, record_fault=False, allow_recovery=False
                    )
                    self._rebuild_requested = "rebuild_retry"
                except Exception as rexc:  # noqa: BLE001 — retry next cycle
                    self._log.error(
                        "device-state rebuild failed: %s", rexc,
                        exc_info=True,
                    )
                    self._rebuild_requested = "rebuild_retry"
        self._log.info("device loop stopped")

    # ------------------------------------------------------------------ #

    def get_metrics(self) -> Dict[str, Any]:
        return {
            "slots_total": self.n_slots,
            "slots_active": sum(s is not None for s in self._slots),
            # queue_depth(), not pending+backlog: prepared-but-not-yet-
            # dispatched admissions count toward shedding, so they must
            # be visible here too or shed storms look causeless.
            "pending": self.queue_depth(),
            **(
                {"kv_pages_free": self.alloc.free_pages,
                 "kv_pages_total": self.num_pages - 1,
                 "page_strip": self.page_strip}
                if self.alloc is not None else {}
            ),
            **(
                {"prefix_entries": len(self.prefix_store),
                 "prefix_hits": global_metrics.get("engine.prefix_hits")}
                if self.prefix_store is not None else {}
            ),
            **(
                {"prefix_pages": self.page_index.pinned_pages,
                 "prefix_hits": global_metrics.get("engine.prefix_hits")}
                if self.page_index is not None else {}
            ),
            **(
                {"kvcache": {
                    "host_mb": round(
                        self.kvcache.host.bytes_held / (1024 * 1024), 2
                    ),
                    "host_entries": len(self.kvcache.host),
                    "lookups": global_metrics.get("engine.kvcache.lookups"),
                    "hits": global_metrics.get("engine.kvcache.hits"),
                    "host_hits": global_metrics.get(
                        "engine.kvcache.host_hits"
                    ),
                    "spills": global_metrics.get("engine.kvcache.spills"),
                    "restores": global_metrics.get(
                        "engine.kvcache.restores"
                    ),
                    "prefill_tokens_saved": global_metrics.get(
                        "engine.kvcache.prefill_tokens_saved"
                    ),
                }}
                if self.kvcache is not None and self.kvcache.host is not None
                else {}
            ),
            "decode_steps": global_metrics.get("engine.decode_steps"),
            # DAG-aware scheduling (pilottai_tpu/sched/): backlog
            # ordering policy + gang/pre-warm outcome counters.
            "sched": {
                "policy": self.sched_policy,
                "gang_admits": global_metrics.get("sched.gang_admits"),
                "gang_partial": global_metrics.get("sched.gang_partial"),
                "priority_aged": global_metrics.get("sched.priority_aged"),
                "prewarms": global_metrics.get("sched.prewarms"),
                "prewarm_hits": global_metrics.get("sched.prewarm_hits"),
            },
            # Weight quantization (ISSUE 14): mode, int4 scale group,
            # measured weight-stream bytes (the gauges set at boot) and
            # whether the fused greedy epilogue is enabled.
            "quant": {
                "weight_quant": self.weight_quant,
                "quant_group": self.quant_group,
                "weight_bytes": self.weight_bytes,
                "weight_bytes_per_token": self.weight_bytes_per_token,
                "fused_epilogue": self.fused_epilogue,
            },
            "overlap_admission": self.overlap_admission,
            "pipeline_depth": self.PIPELINE_DEPTH,
            "chunk_policy": self.chunk_policy,
            "chunk_buckets": list(self.chunk_buckets),
            "chunk_utilization": round(
                global_metrics.get("engine.blocks_useful")
                / max(global_metrics.get("engine.blocks_dispatched"), 1),
                4,
            ),
            "completed": global_metrics.get("engine.completed"),
            # Live attribution gauges (obs/attribution.py): rolling-
            # window MFU and the measured-idle complement.
            "mfu": round(global_metrics.get("engine.mfu"), 4),
            "device_busy_frac": round(
                global_metrics.get("engine.device_busy_frac"), 4
            ),
            "collective_frac": round(
                global_metrics.get("engine.collective_frac"), 4
            ),
            # ACTIVE mesh plan, not the boot plan: after a shard-loss
            # re-plan this reports the rung the engine is actually
            # serving on (the single-chip rung sets self.mesh = None,
            # so the ladder — which remembers the boot set — keeps the
            # section alive with shape {} / n_chips 1).
            **(
                {"mesh": {
                    "shape": (
                        {
                            str(a): int(s)
                            for a, s in self.mesh.shape.items()
                            if int(s) > 1
                        }
                        if self.mesh is not None else {}
                    ),
                    "n_chips": (
                        int(self.mesh.devices.size)
                        if self.mesh is not None else 1
                    ),
                    "kv_heads_sharded": self.kv_heads_sharded,
                    "data_groups": self.data_groups,
                    **(
                        {
                            "rung": self._mesh_ladder.rung,
                            "plan": plan_label(self._mesh_ladder.plan()),
                            "lost_devices": self._mesh_ladder.lost(),
                            "shard_losses": global_metrics.get(
                                "engine.shard_losses"
                            ),
                        }
                        if self._mesh_ladder is not None else {}
                    ),
                    "collective_frac_model": round(
                        global_metrics.get("engine.collective_frac.model"),
                        4,
                    ),
                    "collective_frac_data": round(
                        global_metrics.get("engine.collective_frac.data"),
                        4,
                    ),
                }}
                if self.mesh is not None or self._mesh_ladder is not None
                else {}
            ),
            **(
                {"max_queue_depth": self.max_queue_depth,
                 "shed": global_metrics.get("engine.shed")}
                if self.max_queue_depth is not None else {}
            ),
            "expired": global_metrics.get("engine.expired"),
            # Engine fault domain: ladder rung, failure-path rebuilds,
            # in-flight recovery accounting and fold-poison containment.
            "degrade_level": self.degrade.level(),
            "rebuilds": global_metrics.get("engine.rebuilds"),
            "poisoned": global_metrics.get("engine.poisoned"),
            "recovery": {
                "max_attempts": self.recovery_max_attempts,
                "requeued": global_metrics.get("engine.recovery_requeued"),
                "recovered": global_metrics.get("engine.recovered_requests"),
                "failed": global_metrics.get("engine.recovery_failed"),
                "tokens_replayed": global_metrics.get("engine.tokens_replayed"),
            },
            **(
                {"watchdog_stalled": self._watchdog.stalled}
                if self._watchdog is not None else {}
            ),
        }
