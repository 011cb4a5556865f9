"""The in-tree JAX engine backend: ``provider="tpu"`` / ``provider="cpu"``.

This is the component that replaces the reference's remote-API path
(``pilott/engine/llm.py:59`` → litellm → HTTPS): weights live on local
devices, sharded over a ``jax.sharding.Mesh``; generations run through the
continuous batcher's device thread; asyncio callers await futures bridged
from that thread. Zero external API calls.
"""

from __future__ import annotations

import asyncio
import gc
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from pilottai_tpu.core.config import LLMConfig
from pilottai_tpu.engine.base import (
    LLMBackend,
    parse_tool_calls,
    render_generic_request,
    tool_preamble,
)
from pilottai_tpu.engine.batcher import ContinuousBatcher, GenRequest
from pilottai_tpu.engine.tokenizer import (
    ByteTokenizer,
    IncrementalDecoder,
    load_tokenizer,
)
from pilottai_tpu.engine.types import (
    ChatMessage,
    GenerationParams,
    LLMResponse,
    ToolSpec,
    Usage,
)
from pilottai_tpu.models.common import init_params, param_logical_axes
from pilottai_tpu.models.registry import get_model_config
from pilottai_tpu.parallel.mesh import (
    MeshConfig,
    best_mesh_config,
    create_mesh,
    initialize_distributed,
)
from pilottai_tpu.parallel.sharding import param_shardings
from pilottai_tpu.reliability import DegradeLadder
from pilottai_tpu.utils.logging import get_logger
from pilottai_tpu.utils.tracing import host_span


class NativeEngine(LLMBackend):
    """JAX/XLA serving engine with continuous batching."""

    def __init__(self, config: LLMConfig, platform: Optional[str] = None) -> None:
        self.config = config
        self.platform = platform  # None = default backend; "cpu" = host jax
        self.name = platform or "tpu"
        self._log = get_logger(f"engine.{self.name}")
        self.batcher: Optional[ContinuousBatcher] = None
        self.tokenizer = load_tokenizer(config.tokenizer_path)
        # A named model keeps its registry shape (vocabulary and head
        # included) with or without a checkpoint: byte-tokenizer ids are
        # valid in any vocabulary, and the models meant for cheap
        # checkpoint-free serving (``*-byte``, ``llama-tiny``,
        # ``protocol-*``) declare their own small vocabularies.
        self.model_cfg = get_model_config(config.model_name)
        dtype = jnp.bfloat16 if config.dtype == "bfloat16" else jnp.float32
        self.model_cfg = self.model_cfg.replace(dtype=dtype)
        # Weight quantization mode: engine_quant wins; the legacy
        # ``quantize`` field is an alias ("int8"/"int4"); "none" = dense.
        self.quant_mode = config.engine_quant or config.quantize or "none"
        self.mesh = None
        # Subword JSON grammar tables (built lazily at start; None = byte
        # automaton or tokenizer can't derive token bytes).
        self._json_tables = None
        # Compiled JSON-Schema DFAs for response_format json_schema
        # (byte tokenizers only; engine/json_schema.py).
        self.schema_bank = None
        if isinstance(self.tokenizer, ByteTokenizer):
            from pilottai_tpu.engine.json_schema import SchemaBank

            self.schema_bank = SchemaBank()
        self._start_lock = asyncio.Lock()

    # ------------------------------------------------------------------ #

    async def start(self) -> None:
        # Lock closes the check-then-act race: concurrent first generate()
        # calls must not both run the multi-second init and leak a second
        # device thread.
        async with self._start_lock:
            if self.batcher is not None:
                return
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(None, self._start_blocking)

    def _start_blocking(self) -> None:
        t0 = time.perf_counter()
        # Persistent compilation cache BEFORE the first dispatch: a warm
        # restart (FaultTolerance respawn, worker redeploy) reloads the
        # prefill ladder + decode chunk executables instead of spending
        # minutes recompiling them (round-3 bench: 141.7 s engine-up).
        from pilottai_tpu.utils.compile_cache import enable_compilation_cache

        if self.config.engine_compile_cache is not None or self.platform != "cpu":
            # Default-on for the real backend; the cpu provider (test
            # suites churning hundreds of tiny engines) opts in by
            # setting the knob explicitly.
            enable_compilation_cache(self.config.engine_compile_cache)
        # Multi-host bring-up over DCN when JAX_COORDINATOR_ADDRESS et al
        # are set; a no-op for single-process serving.
        initialize_distributed()
        if self.platform == "cpu":
            devices = jax.local_devices(backend="cpu")
        else:
            devices = jax.devices()
            if devices[0].platform != "tpu":
                # Serving from whatever JAX found would report CPU work
                # under the name of the chip.
                raise RuntimeError(
                    f'provider="tpu" needs a TPU, but JAX found platform '
                    f"{devices[0].platform!r} ({devices[0].device_kind}); "
                    f'use provider="cpu" to serve from the host'
                )
        if self.model_cfg.layer_kinds and not self.config.mesh_shape:
            # A stack of Mamba-2 and latent-expert layers is one chip's
            # share of its deployment (ModelConfig.experts_held): with no
            # mesh asked for it takes the first device, however many the
            # host has. An explicit mesh is refused where the parameters
            # are laid out (models/common.py:param_logical_axes).
            devices = devices[:1]
        mesh_cfg = (
            MeshConfig.from_dict(self.config.mesh_shape)
            if self.config.mesh_shape
            else best_mesh_config(len(devices))
        )
        self.mesh = create_mesh(mesh_cfg, devices)
        self._log.info(
            "loading %s (%.2fB params) on mesh %s",
            self.model_cfg.name,
            self.model_cfg.param_count() / 1e9,
            dict(mesh_cfg.shape),
        )
        # Tensor-parallel serving shardability (ISSUE 13): which KV dims
        # will shard on this mesh and which degrade to replication — one
        # loud line at boot instead of a silently replicated pool.
        if self.mesh.devices.size > 1:
            from pilottai_tpu.parallel.sharding import validate_serving_mesh

            report = validate_serving_mesh(
                self.mesh, self.model_cfg, self.config.engine_slots
            )
            self._log.info(
                "serving mesh: kv_heads_sharded=%s data_groups=%d",
                report["kv_heads_sharded"], report["data_groups"],
            )
            for warning in report["warnings"]:
                self._log.warning("serving mesh: %s", warning)
        if self.config.checkpoint_path:
            # Format-dispatching: HF safetensors or a native orbax tree
            # (in-tree trained models, e.g. protocol-s).
            from pilottai_tpu.models.loader import load_checkpoint

            params = load_checkpoint(
                self.model_cfg, self.config.checkpoint_path, mesh=self.mesh,
                dtype=self.model_cfg.dtype,
            )
        else:
            # Random init. Single chip + int8: quantize leaf-by-leaf at
            # generation time — a full bf16 8B tree alone would overflow a
            # 16 GB chip before quantize_params could shrink it. Multi-
            # chip: init dense UNDER the target shardings (each chip
            # generates only its own shards — an eager init would land
            # the whole tree, 16 GB for 8B bf16, on the first chip
            # before shard_params ever ran), then the quantize pass
            # below shrinks the sharded leaves.
            # int4 always quantizes FROM the dense init (no eager int8
            # intermediate): the packed values must match across the
            # single-chip and sharded boot paths for the byte-identity
            # matrix (tests/test_multichip.py) — a random-init 8B that
            # cannot hold the dense tree on one chip should load a
            # checkpoint or serve int8.
            single = len(devices) == 1
            if single:
                # Eager init ops follow the DEFAULT backend, which is not
                # necessarily this engine's (a cpu-provider engine on a
                # TPU host must not land its params on the TPU) — pin the
                # device for the whole init.
                with jax.default_device(devices[0]):
                    params = init_params(
                        self.model_cfg, jax.random.PRNGKey(self.config.seed),
                        quantize=(self.quant_mode == "int8"),
                    )
                # Commit (default_device arrays are uncommitted and jit
                # would migrate them back to the default backend).
                params = jax.device_put(params, devices[0])
            else:
                model_cfg = self.model_cfg  # the closure keeps only this
                params = jax.jit(
                    lambda key: init_params(model_cfg, key),
                    out_shardings=param_shardings(
                        param_logical_axes(model_cfg), self.mesh
                    ),
                )(jax.random.PRNGKey(self.config.seed))
        if self.quant_mode in ("int8", "int4"):
            from pilottai_tpu.models.quant import quantize_params

            # Weight-only quantization on device: shrinks the decode
            # weight stream AND the params' HBM footprint (already-
            # quantized leaves from the init path pass through untouched;
            # donation keeps the 8B tree from being double-resident).
            # int4 packs two nibbles per byte with per-group scales and
            # falls sensitive leaves back (lm_head → int8, router →
            # dense); see models/quant.py.
            params = quantize_params(
                params, dtype=self.model_cfg.dtype, donate=True,
                bits=4 if self.quant_mode == "int4" else 8,
                group=self.config.engine_quant_group,
            )
            self._log.info(
                "quantized matmul weights to %s (weight-only%s)",
                self.quant_mode,
                f", group {self.config.engine_quant_group}"
                if self.quant_mode == "int4" else "",
            )
        # Subword vocab → precompute the token→byte product tables so
        # json_mode works for real checkpoints' tokenizers, not just the
        # byte tokenizer (VERDICT r2 missing #2). One linear vocab scan.
        if not isinstance(self.tokenizer, ByteTokenizer):
            from pilottai_tpu.engine.json_mask import token_byte_table

            try:
                self._json_tables = token_byte_table(self.tokenizer)
                self._log.info(
                    "built JSON token mask table (%d usable / %d tokens)",
                    int((self._json_tables[1] > 0).sum()),
                    self.tokenizer.vocab_size,
                )
            except Exception as exc:  # noqa: BLE001 — degrade to retry-parse
                self._log.warning(
                    "JSON token table build failed (%s); json_mode falls "
                    "back to unconstrained sampling", exc,
                )
                self._json_tables = None
        if self.config.engine_kv_quantize not in (None, "int8"):
            raise ValueError(
                f"unknown engine_kv_quantize mode "
                f"{self.config.engine_kv_quantize!r}; supported: 'int8'"
            )
        max_seq = self.config.engine_max_seq or min(self.model_cfg.max_seq_len, 2048)
        # Placement flows from the params' NamedShardings; jit propagates
        # them through the cache and activations, no mesh context needed.
        paged = self.config.engine_paged_kv
        if paged is None:
            paged = max_seq >= 4096
        self.batcher = ContinuousBatcher(
            self.model_cfg,
            params,
            n_slots=self.config.engine_slots,
            admit_batch=self.config.engine_admit_batch,
            max_seq_len=max_seq,
            cache_dtype=self.model_cfg.dtype,
            chunk_size=self.config.engine_chunk,
            chunk_policy=self.config.engine_chunk_policy,
            chunk_buckets=(
                tuple(self.config.engine_chunk_buckets)
                if self.config.engine_chunk_buckets else None
            ),
            on_tpu=self.platform != "cpu",
            mesh=self.mesh,
            paged=paged,
            page_size=self.config.engine_page_size,
            num_pages=self.config.engine_kv_pages,
            page_strip=self.config.engine_page_strip,
            json_tables=self._json_tables,
            speculate=self.config.engine_speculate,
            prefix_cache=self.config.engine_prefix_cache,
            # Global KV cache tier (engine/kvcache/): host-RAM cold tier
            # budget + cost-aware eviction policy for both tiers.
            kvcache_host_mb=self.config.engine_kvcache_host_mb,
            kvcache_policy=self.config.engine_kvcache_policy,
            # DAG-aware admission scheduling (pilottai_tpu/sched/):
            # priority-ordered backlog + gang admission + aging floor.
            sched_policy=self.config.engine_sched_policy,
            gang_wait_ms=self.config.engine_gang_wait_ms,
            priority_aging_s=self.config.engine_priority_aging_s,
            prefix_min_len=self.config.engine_prefix_min_len,
            kv_quantize=self.config.engine_kv_quantize == "int8",
            # Weight quantization bookkeeping + the fused greedy
            # epilogue knob (ISSUE 14).
            weight_quant=self.quant_mode,
            quant_group=self.config.engine_quant_group,
            fused_epilogue=self.config.engine_fused_epilogue,
            draft_layers=self.config.engine_draft_layers,
            pipeline_depth=self.config.engine_pipeline,
            overlap_admission=self.config.engine_overlap_admission,
            schema_bank=self.schema_bank,
            prefill_chunk=self.config.engine_prefill_chunk,
            max_queue_depth=self.config.reliability.max_queue_depth,
            # Engine fault domain (ReliabilityConfig): bounded in-flight
            # recovery, per-class shedding, the capability ladder and
            # (when configured) the device watchdog.
            recovery_max_attempts=self.config.reliability.recovery_max_attempts,
            watchdog_stall_s=self.config.reliability.watchdog_stall_s,
            mesh_ladder=self.config.engine_mesh_ladder,
            batch_shed_frac=self.config.reliability.batch_shed_frac,
            degrade=DegradeLadder(
                fault_threshold=self.config.reliability.degrade_fault_threshold,
                window_s=self.config.reliability.degrade_window_s,
                promote_s=self.config.reliability.degrade_promote_s,
                enabled=self.config.reliability.degrade_enabled,
            ),
        )
        self.batcher.start()
        self.batcher.warmup()
        # Speculative stage pre-warm (pilottai_tpu/sched/): the global
        # scheduler's predicted next-stage prefixes land here — encoded,
        # clamped to engine_prewarm_depth tokens, and staged on the
        # batcher's prep thread. Depth 0 = stay detached.
        if self.config.engine_prewarm_depth > 0:
            from pilottai_tpu.sched import global_scheduler

            global_scheduler.attach_prewarm(id(self), self._sched_prewarm)
        # Profile-guided configuration (obs/profile.py): tag the global
        # workload profiler with this deployment's store key, and warn
        # once if the active knob vector diverges from a stored
        # recommendation for its recorded workload.
        from pilottai_tpu.obs import global_profile

        global_profile.configure(self.config.model_name)
        self._warn_knob_divergence()
        self._log.info("engine up in %.1fs", time.perf_counter() - t0)

    _warned_knob_divergence = False  # one-shot boot warning guard

    def _warn_knob_divergence(self) -> None:
        """One-shot boot warning when the active engine knob vector
        diverges from the recommendation stored for this deployment's
        profile (``scripts/recommend.py`` writes it into the profile
        store next to ``autotune.json``). Mirrors the scheduler's
        one-shot ``min_len`` floor warning: advisory, once, and silent
        when no profile/recommendation is stored — a fresh deployment
        must boot quietly."""
        if self._warned_knob_divergence:
            return
        from pilottai_tpu.utils.compile_cache import load_profile

        blob = load_profile(self.config.model_name) or {}
        recommended = (blob.get("recommendation") or {}).get("knobs") or {}
        diverged = []
        for name, want in sorted(recommended.items()):
            have = getattr(self.config, name, None)
            if have != want:
                diverged.append(f"{name}={have!r} (recommended {want!r})")
        if diverged:
            self._warned_knob_divergence = True
            self._log.warning(
                "knob vector diverges from the stored recommendation for "
                "deployment %r: %s — scripts/recommend.py re-derives it "
                "from the current workload profile",
                self.config.model_name, ", ".join(diverged),
            )

    def _sched_prewarm(self, prompt, session_id=None) -> bool:
        """Scheduler pre-warm entry point (any thread): render the
        predicted prefix through the SAME chat framing as
        ``_build_request`` — the structured ``{"system", "user"}`` form
        re-renders via the chat template / generic transcript, so the
        pre-warmed token prefix byte-matches the admission that follows
        (a raw-text pre-warm would key the radix on different tokens
        and never hit) — then hand it to the batcher's advisory
        queue."""
        batcher = self.batcher
        if batcher is None:
            return False
        if isinstance(prompt, dict):
            # Mirror _build_request's assembly EXACTLY per path: the
            # chat template frames the tool preamble as the first
            # system turn; the generic (template-less) path prepends it
            # RAW ahead of the transcript (render_generic_request's
            # tools kwarg). Framing it as a system turn on the generic
            # path would diverge at byte 0 and the pre-warm would never
            # match a tool-bearing admission.
            tool_text = prompt.get("tools")
            msgs = [
                {"role": role, "content": str(prompt[role])}
                for role in ("system", "user") if prompt.get(role)
            ]
            msg_dicts = (
                [{"role": "system", "content": str(tool_text)}]
                if tool_text else []
            ) + msgs
            rendered = self.tokenizer.render_chat(msg_dicts)
            if rendered is not None:
                ids = self.tokenizer.encode(rendered, add_bos=False)
            else:
                text = render_generic_request(
                    [ChatMessage(**m) for m in msgs]
                )
                if tool_text:
                    text = f"{tool_text}\n\n{text}"
                ids = self.tokenizer.encode(text)
        else:
            ids = self.tokenizer.encode(str(prompt))
        return batcher.prewarm(
            ids[: self.config.engine_prewarm_depth], session_id=session_id
        )

    async def stop(self) -> None:
        from pilottai_tpu.sched import global_scheduler

        global_scheduler.detach_prewarm(id(self))
        if self.batcher is not None:
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(None, self.batcher.stop)
            self.batcher = None
            # The batcher's threads, closures and futures form reference
            # cycles: without a collection the stopped engine's weights
            # and KV pool (10+ GB at 8B) stay on the device until the
            # cyclic collector happens to run, and the next engine in
            # this process fails to allocate (seen on the chip, PR 21).
            gc.collect()

    # ------------------------------------------------------------------ #

    def _build_request(
        self,
        messages: Sequence[ChatMessage],
        tools: Optional[Sequence[ToolSpec]],
        params: GenerationParams,
    ) -> GenRequest:
        tool_text = tool_preamble(tools) if tools else None
        # Checkpoint-native chat rendering first (HF chat_template via
        # the tokenizer; instruct models are fine-tuned on their own
        # header format) — the tool preamble rides as a system turn.
        # Byte tokenizers and template-less checkpoints fall back to the
        # generic transcript, byte-identical to previous behavior (and to
        # the protocol-model training data, train/protocol.py).
        msg_dicts = [{"role": m.role, "content": m.content} for m in messages]
        if tool_text:
            msg_dicts = [{"role": "system", "content": tool_text}] + msg_dicts
        rendered = self.tokenizer.render_chat(msg_dicts)
        if rendered is not None:
            # Templates emit their own BOS text; add_bos would double it.
            prompt_ids = self.tokenizer.encode(rendered, add_bos=False)
        else:
            prompt_ids = self.tokenizer.encode(
                render_generic_request(messages, tools)
            )
        # Schema-constrained decoding: compile/look up in the bank
        # (byte tokenizers only). Unsupported schemas, full banks and
        # subword vocabs degrade to the generic grammar — still valid
        # JSON by construction, just not shape-checked.
        schema_id = -1
        want_json = params.json_mode
        if params.json_schema is not None:
            want_json = True
            if self.schema_bank is not None:
                from pilottai_tpu.engine.json_schema import UnsupportedSchema

                try:
                    schema_id = self.schema_bank.register(params.json_schema)
                except UnsupportedSchema as exc:
                    self._log.warning(
                        "json_schema not enforceable (%s); falling back "
                        "to generic JSON grammar", exc,
                    )
            else:
                self._log.warning(
                    "json_schema requires a byte tokenizer; falling back "
                    "to generic JSON grammar"
                )
        return GenRequest(
            prompt_ids=prompt_ids,
            max_new_tokens=params.max_new_tokens,
            temperature=params.temperature,
            top_k=params.top_k,
            top_p=params.top_p,
            seed=params.seed if params.seed is not None else 0,
            eos_id=self.tokenizer.eos_id,
            # Byte tokenizers use the byte automaton; subword tokenizers
            # the token→byte product tables. Only a tokenizer whose table
            # build failed falls back to free sampling + tolerant parsing.
            json_mode=want_json and (
                isinstance(self.tokenizer, ByteTokenizer)
                or self._json_tables is not None
            ),
            json_schema_id=schema_id,
            deadline=params.deadline,
            # Per-class engine shedding: batch-class traffic sheds at a
            # lower backlog depth than interactive (and outright at the
            # degradation ladder's last rung).
            slo_class=params.slo_class,
            # KV-cache session lineage: the batcher's prefix lookup pins
            # this session's host-tier entries against eviction.
            session_id=params.session_id,
            # DAG-aware scheduling: the full priority lattice + gang
            # tag, into the batcher's priority-ordered backlog.
            priority=params.priority if params.priority is not None else 1,
            gang_id=params.gang_id,
            gang_size=params.gang_size,
            # Flight-recorder correlation: the batcher marks admission /
            # token phases against the flight id and emits its span
            # against the trace id.
            trace_id=params.trace_id,
            flight_id=params.flight_id,
            parent_span_id=params.parent_span_id,
        )

    def schema_support(self, schema: Dict[str, Any]) -> Optional[str]:
        """None when ``schema`` can be enforced by constrained decoding
        on this engine; else a human-readable reason. Used by the HTTP
        server to reject strict-mode requests up front (OpenAI returns
        400 for unsupported strict schemas) instead of degrading
        silently. A successful check registers the schema, so the
        subsequent generation reuses the same bank row."""
        if self.schema_bank is None:
            return "json_schema enforcement requires a byte tokenizer"
        from pilottai_tpu.engine.json_schema import UnsupportedSchema

        try:
            self.schema_bank.register(schema)
        except UnsupportedSchema as exc:
            return str(exc)
        return None

    async def generate(
        self,
        messages: Sequence[ChatMessage],
        tools: Optional[Sequence[ToolSpec]] = None,
        params: Optional[GenerationParams] = None,
    ) -> LLMResponse:
        if self.batcher is None:
            await self.start()
        assert self.batcher is not None
        params = params or GenerationParams()
        start = time.perf_counter()

        with host_span("handler.render"):  # messages to token ids
            request = self._build_request(messages, tools, params)
        prompt_ids = request.prompt_ids
        future = self.batcher.submit(request)
        try:
            token_ids = await _to_asyncio_future(future)
        except asyncio.CancelledError:
            # Caller timed out / cancelled: tell the device loop to free the
            # slot instead of decoding dead work to max_new_tokens.
            request.cancelled = True
            raise
        text = self.tokenizer.decode(token_ids)
        cut = _stop_cut(text, params.stop)
        if cut is not None:
            text = text[:cut]
        # Structured function calling on the native path (VERDICT r1 #5):
        # the same wire contract as the mock backend and the reference
        # (``pilott/engine/llm.py:91-104``).
        tool_calls = (
            parse_tool_calls(text, [t.name for t in tools]) if tools else []
        )
        return LLMResponse(
            content=text,
            tool_calls=tool_calls,
            model=self.model_cfg.name,
            usage=Usage(
                prompt_tokens=len(prompt_ids), completion_tokens=len(token_ids)
            ),
            latency=time.perf_counter() - start,
            finish_reason="stop" if len(token_ids) < params.max_new_tokens else "length",
            schema_enforced=(
                request.json_schema_id >= 0
                if params.json_schema is not None else None
            ),
        )

    async def generate_stream(
        self,
        messages: Sequence[ChatMessage],
        tools: Optional[Sequence[ToolSpec]] = None,
        params: Optional[GenerationParams] = None,
        info: Optional[Dict[str, Any]] = None,
    ):
        """Async generator of text deltas: tokens surface as each fused
        decode chunk folds on the host (every ``engine_chunk`` device
        steps — streaming granularity IS the chunk, the latency/dispatch
        trade the engine already makes), detokenized incrementally. The
        concatenated deltas equal ``generate()``'s content for the same
        request (same slot path, same sampler); stop-string truncation
        included. Exiting the generator early cancels the request — the
        device loop frees its slot at the next chunk boundary."""
        if self.batcher is None:
            await self.start()
        assert self.batcher is not None
        params = params or GenerationParams()
        with host_span("handler.render"):  # messages to token ids
            request = self._build_request(messages, tools, params)

        loop = asyncio.get_running_loop()
        q: "asyncio.Queue[Optional[list]]" = asyncio.Queue()
        request.on_tokens = lambda ids: loop.call_soon_threadsafe(
            q.put_nowait, list(ids)
        )
        future = self.batcher.submit(request)
        afut = _to_asyncio_future(future)
        # Wake the drain loop when generation ends (the final fold may
        # emit nothing, e.g. a lone EOS).
        afut.add_done_callback(lambda _f: q.put_nowait(None))

        decoder = IncrementalDecoder(self.tokenizer)
        # Stop strings can span delta boundaries: hold back the longest
        # stop's len-1 tail until the stream ends.
        holdback = max((len(s) for s in params.stop), default=0)
        emitted = 0  # chars of decoder.text already yielded
        n_seen = 0   # token ids already pushed into the decoder

        try:
            stopped = False
            while True:
                item = await q.get()
                final = item is None and afut.done()
                if item:
                    n_seen += len(item)
                    decoder.push(item)
                if final:
                    # The done sentinel can BEAT the last token batch into
                    # this queue: the batcher resolves the future inside
                    # its fold lock but fires ``on_tokens`` after
                    # releasing it, and the event loop may run the
                    # done-callback in the gap (observed on the real-TPU
                    # path). The future's result is the authoritative
                    # stream content (same ids, same filtering), so
                    # reconcile against it instead of trusting arrival
                    # order.
                    if not afut.cancelled() and afut.exception() is None:
                        ids = afut.result()
                        if n_seen < len(ids):
                            decoder.push(ids[n_seen:])
                            n_seen = len(ids)
                    decoder.flush()
                text = decoder.text
                # Same ``_stop_cut`` as generate(), so parity holds by
                # construction. Streamed text can discover occurrences
                # out of start-position order — a longer stop may
                # complete later yet start earlier — but any occurrence
                # not yet complete must start within the last
                # ``holdback`` chars, so a cut at or before
                # ``len(text) - holdback`` is committed.
                cut = _stop_cut(text, params.stop)
                if final:
                    stopped = cut is not None
                    safe = cut if cut is not None else len(text)
                elif cut is not None and cut <= len(text) - holdback:
                    stopped = True
                    safe = cut
                else:
                    bound = len(text) if not holdback else max(
                        emitted, len(text) - holdback
                    )
                    safe = bound if cut is None else min(cut, bound)
                if safe > emitted:
                    yield text[emitted:safe]
                    emitted = safe
                if stopped or final:
                    break
            if info is not None:
                # generate() parity: a stream that consumed the full
                # token budget finished for "length" unless a stop
                # string truncated it first.
                info["finish_reason"] = (
                    "stop" if stopped or n_seen < params.max_new_tokens
                    else "length"
                )
                info["completion_tokens"] = n_seen
                if params.json_schema is not None:
                    info["schema_enforced"] = request.json_schema_id >= 0
            # Surface generation errors (engine stopped, device failure).
            if afut.done() and not afut.cancelled():
                exc = afut.exception()
                if exc is not None:
                    raise exc
        finally:
            if not afut.done():
                request.cancelled = True

    # ------------------------------------------------------------------ #
    # Serving-cell surface (distributed/cell.py, ISSUE 11)
    # ------------------------------------------------------------------ #

    def routing_signals(self) -> Dict[str, Any]:
        """Replica routing signals (queue/degrade/health); empty dict
        before the engine booted (the cell treats that as idle)."""
        return (
            self.batcher.routing_signals() if self.batcher is not None
            else {}
        )

    def export_session_kv(self, session_id: str):
        """Migration source: the session's KV in the host tier's
        transfer format (blocking device→host gathers — a control-plane
        operation, run it off the event loop)."""
        return (
            self.batcher.export_session_kv(session_id)
            if self.batcher is not None else None
        )

    def import_session_kv(self, export) -> Dict[str, int]:
        return (
            self.batcher.import_session_kv(export)
            if self.batcher is not None else {"accepted": 0, "tokens": 0}
        )

    def render_request_ids(
        self,
        messages: Sequence[ChatMessage],
        tools: Optional[Sequence[ToolSpec]],
        params: GenerationParams,
    ) -> Tuple[List[int], bool]:
        """``(prompt_ids, truncated)`` for a request WITHOUT submitting
        it — the exact token ids ``generate`` would run, plus whether
        the batcher's keep-window would truncate them. The handoff path
        (ISSUE 19) needs both: the ids key the KV export, and a
        truncated prompt is a non-migratable shape — the prefill and
        decode legs could truncate differently (their ``max_new_tokens``
        differ by construction), so handoff is gated to prompts that fit
        whole."""
        if self.batcher is None:
            raise RuntimeError("engine not started")
        ids = list(self._build_request(messages, tools, params).prompt_ids)
        # Mirror submit()'s keep-window clamp (engine/batcher.py): room
        # for one generated token, never a non-positive slice.
        keep = self.batcher.max_seq_len - 1 - params.max_new_tokens
        keep = min(max(keep, 1), self.batcher.max_seq_len - 2)
        return ids, len(ids) > keep

    def export_request_kv(self, prompt_ids, session_id=None):
        """Handoff source (ISSUE 19): a just-prefilled request's KV in
        the wire transfer format, keyed by its prompt ids (blocking
        device→host gathers — run off the event loop)."""
        return (
            self.batcher.export_request_kv(prompt_ids, session_id)
            if self.batcher is not None else None
        )

    def import_request_kv(self, export) -> Dict[str, int]:
        """Handoff target: land a prefilled request's KV so admission
        here decode-resumes instead of re-prefilling."""
        return (
            self.batcher.import_request_kv(export)
            if self.batcher is not None
            else {"accepted": 0, "tokens": 0, "rejected": 0}
        )

    def get_metrics(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "backend": self.name, "model": self.model_cfg.name,
            # The shape actually served (not the registry's promise).
            "vocab_size": self.model_cfg.vocab_size,
            "tie_embeddings": self.model_cfg.tie_embeddings,
        }
        if self.batcher is not None:
            out.update(self.batcher.get_metrics())
        return out


def _stop_cut(text: str, stops) -> Optional[int]:
    """Truncation point for stop strings: the EARLIEST occurrence of any
    stop in ``text``, or None. One definition shared by ``generate`` and
    ``generate_stream`` — the parity contract (streamed deltas
    concatenate to the non-streamed content) holds by construction, and
    the semantics are order-independent: with stops ["cd", "bc"] over
    "abcd", the cut is at "bc" (position 1) regardless of list order,
    where a list-order truncation loop would depend on which stop is
    checked first when one occurrence straddles another's cut."""
    cut = None
    for stop in stops:
        pos = text.find(stop)
        if pos >= 0:
            cut = pos if cut is None else min(cut, pos)
    return cut


def _to_asyncio_future(fut) -> "asyncio.Future":
    """Bridge a concurrent.futures.Future without blocking the loop."""
    return asyncio.wrap_future(fut) if not isinstance(fut, asyncio.Future) else fut


def register_native_backends() -> None:
    from pilottai_tpu.engine.handler import register_backend

    register_backend("tpu", lambda cfg: NativeEngine(cfg, platform=None))
    register_backend("cpu", lambda cfg: NativeEngine(cfg, platform="cpu"))
