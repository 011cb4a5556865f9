"""Multi-chip SERVING certification (VERDICT r2 next-step 1).

Round 2 certified multi-chip *training* (dryrun + sharded train step);
the serving path — ``NativeEngine``/``ContinuousBatcher`` with sharded
params, ``admit_group``/``decode_chunk`` under a mesh, int8 ``QTensor``
leaves, the paged cache — had zero >1-device coverage. These tests run
the full engine end-to-end on the virtual 8-device CPU mesh
(tests/conftest.py) and assert generation parity with the single-device
engine. BASELINE.md's target hardware is v5e-8: serving on a mesh is the
framework's headline claim, so it gets the same treatment training got.
"""

import asyncio

import jax
import jax.numpy as jnp
import pytest

from pilottai_tpu.core.config import LLMConfig
from pilottai_tpu.engine.batcher import ContinuousBatcher, GenRequest
from pilottai_tpu.engine.handler import LLMHandler
from pilottai_tpu.engine.types import ChatMessage, GenerationParams
from pilottai_tpu.models.common import init_params
from pilottai_tpu.models.registry import get_model_config

PROMPTS = [
    "alpha beta gamma delta",
    "the quick brown fox jumps over",
    "zeta",
    "multi chip serving parity check",
]


async def _generate_all(
    mesh_shape,
    model_name="llama-tiny",
    quantize=None,
    paged=False,
    max_new=10,
):
    cfg = LLMConfig(
        model_name=model_name,
        provider="cpu",
        mesh_shape=mesh_shape,
        quantize=quantize,
        engine_slots=4,
        engine_max_seq=128,
        engine_chunk=4,
        engine_paged_kv=paged,
        engine_page_size=16,
        dtype="float32",  # greedy argmax parity across shardings
    )
    handler = LLMHandler(cfg)
    await handler.start()
    try:
        resps = await asyncio.gather(*[
            handler.generate_response(
                [ChatMessage(role="user", content=p)],
                params=GenerationParams(max_new_tokens=max_new, temperature=0.0),
            )
            for p in PROMPTS
        ])
        return [r.content for r in resps]
    finally:
        await handler.stop()


@pytest.mark.asyncio
async def test_serving_parity_tp2_dp2():
    """Dense bf16→fp32 engine on a {model:2, data:2} mesh produces the
    same greedy generations as the single-device engine."""
    single = await _generate_all({"data": 1})
    meshed = await _generate_all({"model": 2, "data": 2})
    assert meshed == single
    assert any(s for s in single)  # not all-empty


@pytest.mark.asyncio
async def test_serving_parity_tp4_int8_paged():
    """The 8B-on-mesh configuration in miniature: int8-quantized sharded
    params + paged KV cache on a pure-TP {model:4} mesh. This is the exact
    path VERDICT r2 Weak #6 flagged as never having run on >1 device
    (quantize_params on a sharded tree)."""
    single = await _generate_all({"data": 1}, quantize="int8", paged=True)
    meshed = await _generate_all({"model": 4}, quantize="int8", paged=True)
    assert meshed == single


@pytest.mark.asyncio
async def test_serving_parity_moe_tp2():
    """MoE serving on a mesh: expert-parallel rides the model axis."""
    single = await _generate_all({"data": 1}, model_name="moe-tiny")
    meshed = await _generate_all({"model": 2}, model_name="moe-tiny")
    assert meshed == single


def test_quantize_params_sharded_tree_preserves_shardings():
    """quantize_params on an already-sharded tree must keep each leaf's
    NamedSharding (scale reduction must not silently reshard) and match
    the values of quantizing the unsharded tree."""
    import numpy as np

    from pilottai_tpu.models.common import param_logical_axes
    from pilottai_tpu.models.quant import QTensor, quantize_params
    from pilottai_tpu.parallel.mesh import MeshConfig, create_mesh
    from pilottai_tpu.parallel.sharding import shard_params

    cfg = get_model_config("llama-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    q_plain = quantize_params(params, dtype=jnp.float32)

    mesh = create_mesh(MeshConfig(model=2, data=2), jax.devices()[:4])
    sharded = shard_params(params, param_logical_axes(cfg), mesh)
    shardings_before = jax.tree.map(
        lambda a: a.sharding, sharded,
        is_leaf=lambda x: isinstance(x, jax.Array),
    )
    q_sharded = quantize_params(sharded, dtype=jnp.float32)

    flat_plain = jax.tree.leaves(
        q_plain, is_leaf=lambda x: isinstance(x, QTensor)
    )
    flat_sharded = jax.tree.leaves(
        q_sharded, is_leaf=lambda x: isinstance(x, QTensor)
    )
    assert len(flat_plain) == len(flat_sharded)
    for a, b in zip(flat_plain, flat_sharded):
        if isinstance(a, QTensor):
            assert isinstance(b, QTensor)
            np.testing.assert_array_equal(np.asarray(a.q), np.asarray(b.q))
            np.testing.assert_allclose(
                np.asarray(a.s), np.asarray(b.s), rtol=1e-6
            )
            # The int8 payload must stay sharded the way the weight was.
            assert not b.q.sharding.is_fully_replicated or (
                a.q.ndim < 2
            ), "sharded weight lost its sharding through quantize"


def test_rebuild_requeues_later_groups():
    """ADVICE r2 (medium): when a failed donated admission forces a device-
    state rebuild mid-wave, the REMAINING groups of that wave hold page
    allocations from the dead allocator — they must be requeued (and then
    complete correctly), not prefilled against the fresh allocator's
    sentinel rows (which silently produced garbage completions)."""
    import pilottai_tpu.engine.batcher as bmod

    cfg = get_model_config("llama-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    batcher = ContinuousBatcher(
        cfg, params, n_slots=2, max_seq_len=64, cache_dtype=jnp.float32,
        admit_batch=1, paged=True, page_size=8,
        # Recovery off: this regression pins the REQUEUE of later groups
        # after a mid-wave rebuild; with recovery on, req1 would simply
        # re-admit and complete too (that contract is test_chaos.py's).
        recovery_max_attempts=0,
    )
    real_admit = bmod.admit_group
    calls = {"n": 0}

    def poison_once(params_, cfg_, cache, dstate, sampling, *a, **k):
        calls["n"] += 1
        if calls["n"] == 1:
            for k_, v_ in cache.layers:
                k_.delete()
                v_.delete()
            cache.lengths.delete()
            raise RuntimeError("device lost mid-dispatch")
        return real_admit(params_, cfg_, cache, dstate, sampling, *a, **k)

    bmod.admit_group = poison_once
    try:
        # Submit BOTH before start so one admission wave builds two
        # single-request groups (admit_batch=1).
        req1 = GenRequest(prompt_ids=[3, 4, 5], max_new_tokens=4)
        req2 = GenRequest(prompt_ids=[6, 7, 8, 9], max_new_tokens=4)
        batcher.submit(req1)
        batcher.submit(req2)
        batcher.start()
        with pytest.raises(RuntimeError, match="device lost"):
            req1.future.result(timeout=60)
        # req2 was requeued and admitted against the REBUILT allocator:
        # it completes with real tokens (admission actually ran again).
        out2 = req2.future.result(timeout=60)
        assert isinstance(out2, list) and 1 <= len(out2) <= 4
        assert calls["n"] >= 2
        # Fresh allocator bookkeeping is consistent after completion.
        assert batcher.alloc is not None
    finally:
        bmod.admit_group = real_admit
        batcher.stop()


# --------------------------------------------------------------------- #
# Scaling harness (VERDICT r4 #8): parity proves correctness; this
# records parallel *efficiency* so a TP/DP serving regression (a stray
# all-gather, a resharding copy in the decode hot path) shows up in CI
# as a rate collapse, not just in a hand-run profile. Absolute CPU-mesh
# numbers are meaningless; the sanity bound is deliberately loose.
# --------------------------------------------------------------------- #

MESH_LADDER = (
    {"data": 1},
    {"model": 2},
    {"model": 4, "data": 2},
)


async def _measure_mesh_rate(mesh_shape, steps=12, concurrency=4):
    import time

    cfg = LLMConfig(
        model_name="llama-tiny",
        provider="cpu",
        mesh_shape=mesh_shape,
        engine_slots=concurrency,
        engine_max_seq=128,
        engine_chunk=4,
        dtype="float32",
    )
    handler = LLMHandler(cfg)
    await handler.start()
    try:
        params = GenerationParams(max_new_tokens=16, temperature=0.0)

        async def one(i):
            await handler.generate_response(
                [ChatMessage(role="user", content=f"scale probe {i}")],
                params=params,
            )

        await asyncio.gather(*[one(i) for i in range(concurrency)])  # warm
        t0 = time.perf_counter()
        done = 0
        while done < steps:
            n = min(concurrency, steps - done)
            await asyncio.gather(*[one(100 + done + i) for i in range(n)])
            done += n
        return steps / (time.perf_counter() - t0)
    finally:
        await handler.stop()


@pytest.mark.asyncio
async def test_mesh_scaling_ladder_stays_serviceable():
    """Every rung of the serving-mesh ladder sustains throughput. The
    regression bound: no sharded config may collapse below 10% of the
    single-device rate (a resharding bug costs far more than mesh
    overhead on a virtual CPU mesh, where communication is memcpy).

    Deflaked: the bound is a RATE RATIO measured on a shared, noisy
    box — one loaded-CPU window can sink any single wall-clock
    measurement (observed failing at the seed commit in isolation while
    passing in suite order). A rung that lands under the bound
    re-measures, best-of-3, before the assertion decides; a real
    resharding regression fails all three attempts identically."""
    async def _best_rate(shape, floor=None, attempts=3):
        best = 0.0
        for _ in range(attempts):
            best = max(best, await _measure_mesh_rate(shape))
            if floor is None or best > floor:
                break  # already clears the bound — no retries needed
        return best

    base = await _best_rate({"data": 1})
    rates = {"data=1": base}
    for shape in MESH_LADDER[1:]:
        key = ",".join(f"{k}={v}" for k, v in shape.items())
        rates[key] = await _best_rate(shape, floor=0.1 * base)
    print("\nmesh scaling (virtual 8-CPU, llama-tiny):", rates)
    assert all(r > 0 for r in rates.values())
    for key, rate in rates.items():
        assert rate > 0.1 * base, (key, rates)


def test_row_floor_is_the_data_axis_and_a_lone_request_admits():
    """The admission row ladder starts where the mesh's batch axes can
    still split the rows (the sharded flash prefill takes the dense
    fallback otherwise): 2 on a data axis of 2, where a lone request
    is dispatched at two rows and decodes what one device decodes."""
    from pilottai_tpu.parallel.mesh import MeshConfig, create_mesh
    from pilottai_tpu.utils.metrics import global_metrics

    cfg = get_model_config("llama-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)

    def run(mesh):
        b = ContinuousBatcher(
            cfg, params, n_slots=4, max_seq_len=128,
            cache_dtype=jnp.float32, chunk_size=4, prefix_cache=0,
            use_pallas=False, mesh=mesh,
        )
        ladder = [b._row_bucket(n) for n in range(1, b.admit_batch + 1)]
        b.start()
        try:
            run0 = global_metrics.get("engine.prefill_tokens_run")
            out = b.submit(GenRequest(
                prompt_ids=list(range(5, 105)), max_new_tokens=6, eos_id=-1,
            )).result(timeout=240)
            rows = (global_metrics.get("engine.prefill_tokens_run") - run0) / 128
        finally:
            b.stop()
        return b.row_floor, ladder, rows, out

    one = run(None)
    two = run(create_mesh(MeshConfig(data=2), jax.devices()[:2]))
    assert one[:3] == (1, [1, 2, 4, 4], 1)
    assert two[:3] == (2, [2, 2, 4, 4], 2)
    assert len(one[3]) == 6 and two[3] == one[3]
