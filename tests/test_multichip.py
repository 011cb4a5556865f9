"""Tensor-parallel serving certification (ISSUE 13).

PR 13 makes the mesh first-class end to end: the paged KV pool and the
dense cache panels are CREATED sharded (kv-heads over ``model``, dense
slots over ``data`` — ``parallel/sharding.py:place_kv_cache``),
admission replicates over the ``data`` axis as balanced decode groups,
and per-dispatch collective time is attributed per mesh axis
(``parallel/collectives.py`` → ``engine.collective_frac[.axis]``).

Fast tests pin the pieces' arithmetic (gauge math from synthetic
dispatch records, the collective cost model, sharding-spec gating, the
data-group interleave, the HLO collective inspector). Slow tests run
the full engine on the virtual 8-device CPU mesh (tests/conftest.py)
and pin the acceptance bar: greedy output byte-identical sharded vs
single-device across dense/paged × spec on/off × int8 KV, the PR 9
spill→evict→restore path under sharding, and a PR 8 mid-decode
rebuild/recovery on a sharded engine — the multichip CI lane runs them
(tests.yml), same shape as the cell/chaos lanes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pilottai_tpu.engine.batcher import ContinuousBatcher, GenRequest
from pilottai_tpu.models.common import init_params
from pilottai_tpu.models.registry import get_model_config
from pilottai_tpu.parallel.collectives import (
    CollectiveModel,
    collective_bytes_by_axis,
    collective_ops,
)
from pilottai_tpu.parallel.mesh import MeshConfig, create_mesh
from pilottai_tpu.parallel.sharding import (
    kv_cache_shardings,
    kv_shard_axes,
    place_kv_cache,
    validate_serving_mesh,
)
from pilottai_tpu.utils.metrics import global_metrics

MESH = {"model": 2, "data": 2}


def _mesh(shape=None):
    return create_mesh(MeshConfig.from_dict(shape or MESH))


# --------------------------------------------------------------------- #
# Fast: collective gauge arithmetic from synthetic dispatch records
# (ISSUE 13 satellite — the gauge had never seen >1 device)
# --------------------------------------------------------------------- #

def test_collective_gauge_arithmetic_synthetic():
    """engine.collective_frac[.axis] from hand-fed dispatch records:
    frac = collective share of attributed device time, per-axis gauges
    split by the records' axis tags — pure window arithmetic, no
    engine."""
    from pilottai_tpu.obs.attribution import DeviceTimeAttributor
    from pilottai_tpu.utils.metrics import MetricsRegistry

    reg = MetricsRegistry()
    attr = DeviceTimeAttributor(registry=reg, window_s=60.0)
    attr.configure(
        flops_per_token=1e9, device_kind="cpu", n_chips=8,
        mesh_axes=("data", "model"),
    )
    t0 = 1000.0
    attr.record("decode", 0.8, tokens=64, at=t0 + 1.0)
    attr.record("collective", 0.15, flops=0.0, axis="model", at=t0 + 1.0)
    attr.record("collective", 0.05, flops=0.0, axis="data", at=t0 + 1.0)
    snap = reg.snapshot()["gauges"]
    assert snap["engine.collective_frac"] == pytest.approx(0.2)
    assert snap["engine.collective_frac.model"] == pytest.approx(0.15)
    assert snap["engine.collective_frac.data"] == pytest.approx(0.05)
    # Cumulative counters: section consumers (bench) take deltas —
    # total and per-axis.
    counters = reg.snapshot()["counters"]
    assert counters["engine.attributed_collective_s"] == pytest.approx(0.2)
    assert counters["engine.attributed_collective_s.model"] == (
        pytest.approx(0.15)
    )
    assert counters["engine.attributed_collective_s.data"] == (
        pytest.approx(0.05)
    )
    # Off-window records prune back out.
    attr.record("decode", 0.1, tokens=8, at=t0 + 100.0)
    snap = reg.snapshot()["gauges"]
    assert snap["engine.collective_frac"] == pytest.approx(0.0)
    assert snap["engine.collective_frac.model"] == pytest.approx(0.0)
    # The batcher's fold path folds the per-axis split into ONE record
    # call (one lock/gauge pass on the reader thread); the window
    # arithmetic must match the separate-records form above.
    attr.record(
        "decode", 0.8, tokens=64, at=t0 + 101.0,
        collective={"model": 0.15, "data": 0.05},
    )
    snap = reg.snapshot()["gauges"]
    assert snap["engine.collective_frac"] == pytest.approx(0.2 / 1.1)
    assert snap["engine.collective_frac.model"] == pytest.approx(0.15 / 1.1)
    assert snap["engine.collective_frac.data"] == pytest.approx(0.05 / 1.1)
    counters = reg.snapshot()["counters"]
    assert counters["engine.attributed_collective_s"] == pytest.approx(0.4)
    assert counters["engine.attributed_collective_s.model"] == (
        pytest.approx(0.3)
    )


def test_collective_model_arithmetic():
    """The analytic per-dispatch estimate: model-axis bytes follow the
    2-all-reduces-per-layer + logits-gather formula, data-axis bytes
    exist only for the data-replicated paged pool's writes, and split()
    carves out of — never invents — measured wall time."""
    cfg = get_model_config("llama-tiny")
    mesh = _mesh({"model": 4, "data": 2})
    cm = CollectiveModel.for_mesh(
        mesh, cfg, platform="cpu", paged=True, kv_quantize=False,
    )
    assert cm is not None and cm.model_size == 4 and cm.data_size == 2
    # One block, 8 slots, 8 written tokens.
    est = cm.decode_seconds(1, 8, 8)
    assert est["model"] > 0 and est["data"] > 0
    # Closed form, model axis: rows = blocks * B / data; ring all-reduce
    # moves 2(M-1)/M of 2 activations per layer + (M-1)/M of the logits.
    rows = 1 * 8 / 2
    m = 4
    expect = (
        2.0 * cfg.n_layers * rows * cfg.hidden_size * cm.dtype_bytes
        * 2.0 * (m - 1) / m
        + rows * cfg.vocab_size * 4.0 * (m - 1) / m
    ) / cm.bytes_per_s
    assert est["model"] == pytest.approx(expect, rel=1e-6)
    # Data axis: written tokens' K/V rows all-gather across groups.
    expect_d = 8 * cm.kv_bytes_per_token * (2 - 1) / 2 / cm.bytes_per_s
    assert est["data"] == pytest.approx(expect_d, rel=1e-6)
    # split(): the estimate is capped at half the wall, compute +
    # collective always sum to the measured wall.
    compute, coll = cm.split(1.0, {"model": 0.9, "data": 0.3})
    assert compute + sum(coll.values()) == pytest.approx(1.0)
    assert sum(coll.values()) == pytest.approx(0.5)
    compute, coll = cm.split(1.0, {"model": 0.01})
    assert coll["model"] == pytest.approx(0.01)
    assert compute == pytest.approx(0.99)
    # Off-mesh: nothing to attribute.
    assert CollectiveModel.for_mesh(
        None, cfg, platform="cpu", paged=True, kv_quantize=False,
    ) is None
    single = create_mesh(MeshConfig(), jax.devices()[:1])
    assert CollectiveModel.for_mesh(
        single, cfg, platform="cpu", paged=True, kv_quantize=False,
    ) is None
    # Dense cache (batch sharded over data): no data-axis term.
    cm_dense = CollectiveModel.for_mesh(
        mesh, cfg, platform="cpu", paged=False, kv_quantize=False,
    )
    assert "data" not in cm_dense.decode_seconds(1, 8, 8)
    # fsdp as the batch axis: the pool-coherence term must land under
    # the mesh's REAL axis name — the per-axis gauges and declared
    # counters only exist for actual mesh axes.
    cm_fsdp = CollectiveModel.for_mesh(
        _mesh({"model": 2, "fsdp": 2}), cfg,
        platform="cpu", paged=True, kv_quantize=False,
    )
    assert cm_fsdp.data_axis == "fsdp" and cm_fsdp.data_size == 2
    est_f = cm_fsdp.decode_seconds(1, 8, 8)
    assert est_f["fsdp"] > 0 and "data" not in est_f


def test_collective_hlo_inspector():
    """collective_ops / collective_bytes_by_axis: parse op kind, payload
    bytes and replica groups out of HLO text and map groups to mesh
    axes — on a synthetic line (deterministic) AND on a real lowered
    sharded matmul (the premise check: GSPMD really inserts a
    model-axis all-reduce for a row-parallel contraction)."""
    mesh = _mesh({"model": 2, "data": 2})
    # Linear device ids grid is reshape(data=2, fsdp=1, model=2, seq=1):
    # model groups {0,1},{2,3}; data groups {0,2},{1,3}.
    text = (
        "  %ar = f32[4,128]{1,0} all-reduce(f32[4,128]{1,0} %x), "
        "replica_groups={{0,1},{2,3}}, to_apply=%add\n"
        "  %ag = bf16[8,64]{1,0} all-gather(bf16[8,32]{1,0} %y), "
        "replica_groups={{0,2},{1,3}}, dimensions={1}\n"
    )
    ops = collective_ops(text, mesh)
    assert [op.kind for op in ops] == ["all-reduce", "all-gather"]
    assert ops[0].axis == "model" and ops[0].bytes == 4 * 128 * 4
    assert ops[1].axis == "data" and ops[1].bytes == 8 * 64 * 2
    by_axis = collective_bytes_by_axis(text, mesh)
    assert by_axis == {"model": 4 * 128 * 4, "data": 8 * 64 * 2}

    # Async lowering splits each collective into a -start/-done pair,
    # BOTH carrying the full result payload (and -done without replica
    # groups); only the -start half may count or TPU-optimized HLO
    # reports ~2x bytes with half of it unattributable.
    async_text = (
        "  %s = f32[4,128]{1,0} all-reduce-start(f32[4,128]{1,0} %x), "
        "replica_groups={{0,1},{2,3}}, to_apply=%add\n"
        "  %d = f32[4,128]{1,0} all-reduce-done(f32[4,128]{1,0} %s)\n"
    )
    async_ops = collective_ops(async_text, mesh)
    assert len(async_ops) == 1 and async_ops[0].axis == "model"
    assert collective_bytes_by_axis(async_text, mesh) == {
        "model": 4 * 128 * 4
    }

    # The real thing: x @ w1 (col-parallel) @ w2 (row-parallel) must
    # all-reduce over the model axis.
    from jax.sharding import NamedSharding, PartitionSpec as P

    x = jax.device_put(
        np.ones((4, 16), np.float32), NamedSharding(mesh, P())
    )
    w1 = jax.device_put(
        np.ones((16, 32), np.float32), NamedSharding(mesh, P(None, "model"))
    )
    w2 = jax.device_put(
        np.ones((32, 16), np.float32), NamedSharding(mesh, P("model", None))
    )
    compiled = (
        jax.jit(lambda a, b, c: a @ b @ c).lower(x, w1, w2).compile()
    )
    hlo = compiled.as_text()
    real = collective_bytes_by_axis(hlo, mesh)
    assert real.get("model", 0) + real.get("other", 0) > 0, (
        "sharded row-parallel matmul lowered without any collective — "
        "the analytic model's premise does not hold"
    )


# --------------------------------------------------------------------- #
# Fast: KV sharding specs + placement
# --------------------------------------------------------------------- #

def test_kv_shard_axes_gating():
    mesh = _mesh({"model": 2, "data": 2})
    axes = kv_shard_axes(mesh, n_kv_heads=2, n_slots=4)
    assert axes["heads"] == "model"
    assert axes["slots"] == ("data",)
    assert axes["data_groups"] == 2
    # Non-divisible kv-heads: replicate heads, keep the data split.
    axes = kv_shard_axes(_mesh({"model": 4, "data": 2}), n_kv_heads=2,
                         n_slots=4)
    assert axes["heads"] is None and axes["data_groups"] == 2
    # Non-divisible slots: single admission group.
    axes = kv_shard_axes(mesh, n_kv_heads=2, n_slots=3)
    assert axes["slots"] is None and axes["data_groups"] == 1
    # Single device: nothing shards.
    single = create_mesh(MeshConfig(), jax.devices()[:1])
    axes = kv_shard_axes(single, n_kv_heads=2, n_slots=4)
    assert axes == {"heads": None, "slots": None, "data_groups": 1}


def test_place_kv_cache_layouts():
    """Dense panels shard (data, model); the paged pool shards kv-heads
    over model with pages replicated; lengths replicate everywhere."""
    from pilottai_tpu.ops.kvcache import KVCache
    from pilottai_tpu.ops.paged import PagedKVCache

    mesh = _mesh({"model": 2, "data": 2})
    dense = KVCache.create(2, 4, 64, 2, 8, dtype=jnp.float32,
                           quantized=True)
    dense = place_kv_cache(dense, mesh, n_kv_heads=2, n_slots=4)
    k0 = dense.layers[0][0]
    spec = k0.sharding.spec
    assert tuple(spec) == (("data",), "model", None, None) or tuple(
        spec
    ) == ("data", "model", None, None)
    assert dense.lengths.sharding.is_fully_replicated
    assert tuple(dense.scales[0][0].sharding.spec)[:2] == (
        tuple(spec)[0], "model",
    )

    pool = PagedKVCache.create(2, 4, 9, 16, 2, 8, dtype=jnp.float32)
    pool = place_kv_cache(pool, mesh, n_kv_heads=2, n_slots=4)
    pspec = tuple(pool.layers[0][0].sharding.spec)
    assert pspec[0] == "model" and all(s is None for s in pspec[1:])
    assert pool.lengths.sharding.is_fully_replicated

    # Nothing shardable → identity (no device_put, no spec tree).
    tiny = KVCache.create(1, 3, 16, 3, 4, dtype=jnp.float32)
    assert kv_cache_shardings(
        _mesh({"model": 2}), tiny, n_kv_heads=3, n_slots=3
    ) is None


def test_validate_serving_mesh_warnings():
    cfg = get_model_config("llama-tiny")  # 4 heads, 2 kv-heads
    report = validate_serving_mesh(_mesh({"model": 2, "data": 2}), cfg, 4)
    assert report["kv_heads_sharded"] and report["data_groups"] == 2
    assert report["warnings"] == []
    report = validate_serving_mesh(_mesh({"model": 4, "data": 2}), cfg, 3)
    assert not report["kv_heads_sharded"]
    assert report["data_groups"] == 1
    assert any("n_kv_heads" in w for w in report["warnings"])
    assert any("n_slots" in w for w in report["warnings"])


# --------------------------------------------------------------------- #
# Fast: data-axis admission groups
# --------------------------------------------------------------------- #

def test_free_slots_interleave_data_groups():
    """With data_groups=2, selection interleaves free slots across the
    contiguous group blocks, least-occupied group first — a burst
    admission spreads over every data shard's slots."""
    cfg = get_model_config("llama-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    b = ContinuousBatcher(
        cfg, params, n_slots=4, max_seq_len=64, cache_dtype=jnp.float32,
        mesh=_mesh({"model": 2, "data": 2}),
    )
    try:
        assert b.data_groups == 2
        assert b._free_slot_indices() == [0, 2, 1, 3]
        b._slots[0] = object()  # occupy group 0
        assert b._free_slot_indices() == [2, 1, 3]
        b._slots[2] = object()  # both groups at 1 occupied
        assert b._free_slot_indices() == [1, 3]
    finally:
        b._slots = [None] * 4
        b.stop()


def test_pallas_gating_on_sharded_mesh():
    """Kernel/layout gates stay consistent on a mesh: the opt-in dense
    Pallas decode kernel (no shard_map wrapper) demotes to the XLA path
    when the dense panels would shard, and a paged Pallas engine whose
    slots don't divide the data axes keeps its pool REPLICATED (the
    unwrapped kernel must never see a model-sharded pool)."""
    cfg = get_model_config("llama-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    mesh = _mesh({"model": 2, "data": 2})
    # Dense + forced pallas on a shardable mesh → demoted to XLA.
    b = ContinuousBatcher(
        cfg, params, n_slots=4, max_seq_len=64, cache_dtype=jnp.float32,
        mesh=mesh, use_pallas=True,
    )
    try:
        assert not b.use_pallas
        assert not b.cache.layers[0][0].sharding.is_fully_replicated
    finally:
        b.stop()
    # Paged + forced pallas, slots don't divide data → sharded-kernel
    # gate fails; the pool must stay replicated (and kv_mesh unset).
    b = ContinuousBatcher(
        cfg, params, n_slots=3, max_seq_len=64, cache_dtype=jnp.float32,
        paged=True, page_size=16, mesh=mesh, use_pallas=True,
    )
    try:
        assert b.kv_mesh is None and b._kv_place_mesh is None
        assert b.cache.layers[0][0].sharding.is_fully_replicated
    finally:
        b.stop()
    # Paged + forced pallas, everything divides → sharded kernel AND
    # sharded pool.
    b = ContinuousBatcher(
        cfg, params, n_slots=4, max_seq_len=64, cache_dtype=jnp.float32,
        paged=True, page_size=16, mesh=mesh, use_pallas=True,
    )
    try:
        assert b.kv_mesh is mesh and b._kv_place_mesh is mesh
        assert not b.cache.layers[0][0].sharding.is_fully_replicated
    finally:
        b.stop()


def test_batcher_off_mesh_single_group():
    cfg = get_model_config("llama-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    b = ContinuousBatcher(
        cfg, params, n_slots=4, max_seq_len=64, cache_dtype=jnp.float32,
    )
    try:
        assert b.data_groups == 1 and b.mesh is None
        assert b.collective_model is None
        assert b._free_slot_indices() == [0, 1, 2, 3]
    finally:
        b.stop()


# --------------------------------------------------------------------- #
# Fast: the shard_map'd paged kernel itself (interpret mode) — the TPU
# serving path's per-shard dispatch, bit-identical to the plain kernel
# --------------------------------------------------------------------- #

def test_paged_kernel_sharded_matches_unsharded():
    """paged_decode_attention_sharded under shard_map (kv-heads over
    'model', slots over 'data') returns exactly the single-dispatch
    kernel's stats: heads are independent, so per-shard runs over
    disjoint head/slot blocks must reproduce the unsharded output bit
    for bit (the cross-shard merge lives in the o-projection, outside
    the kernel)."""
    from functools import partial

    from pilottai_tpu.ops.pallas.paged_attention import (
        paged_decode_attention,
        paged_decode_attention_sharded,
        paged_sharding_ok,
    )

    mesh = _mesh({"model": 2, "data": 2})
    B, K, G, H, P_, n_pages, max_pages = 4, 2, 2, 8, 9, 16, 4
    assert paged_sharding_ok(mesh, B, K)
    assert not paged_sharding_ok(mesh, B, 3)  # heads don't divide
    key = jax.random.PRNGKey(7)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, K * G, H), jnp.float32)
    k_pool = jax.random.normal(kk, (K, n_pages, P_, H), jnp.float32)
    v_pool = jax.random.normal(kv, (K, n_pages, P_, H), jnp.float32)
    table = jnp.asarray(
        np.arange(B * max_pages).reshape(B, max_pages) % (n_pages - 1),
        jnp.int32,
    )
    last_valid = jnp.asarray([30, 17, 0, 25], jnp.int32)
    kw = dict(n_blocks=2, scale=0.3, softcap=0.0, window=0, interpret=True)
    acc, m, l = paged_decode_attention(
        q, k_pool, v_pool, table, last_valid, **kw
    )
    acc_s, m_s, l_s = jax.jit(
        partial(paged_decode_attention_sharded, mesh, **kw)
    )(q, k_pool, v_pool, table, last_valid)
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(acc_s))
    np.testing.assert_array_equal(np.asarray(m), np.asarray(m_s))
    np.testing.assert_array_equal(np.asarray(l), np.asarray(l_s))


# --------------------------------------------------------------------- #
# Slow: the acceptance matrix — greedy byte-identity sharded vs single
# device across dense/paged × spec on/off × int8 KV (multichip CI lane)
# --------------------------------------------------------------------- #

PROMPTS = [
    "tensor parallel serving parity probe one",
    "the quick brown fox jumps over the lazy dog",
    "shard the kv pool over the model axis",
]


async def _generate_all(mesh_shape, *, paged, speculate, kv_int8,
                        max_new=8, weight_quant=None):
    import asyncio

    from pilottai_tpu.core.config import LLMConfig
    from pilottai_tpu.engine.handler import LLMHandler
    from pilottai_tpu.engine.types import ChatMessage, GenerationParams

    cfg = LLMConfig(
        model_name="llama-tiny",
        provider="cpu",
        mesh_shape=mesh_shape,
        engine_slots=4,
        engine_max_seq=128,
        engine_chunk=4,
        engine_speculate=speculate,
        engine_paged_kv=paged,
        engine_page_size=16,
        engine_kv_quantize="int8" if kv_int8 else None,
        engine_quant=weight_quant,
        dtype="float32",  # greedy argmax parity across shardings
    )
    handler = LLMHandler(cfg)
    await handler.start()
    try:
        resps = await asyncio.gather(*[
            handler.generate_response(
                [ChatMessage(role="user", content=p)],
                params=GenerationParams(
                    max_new_tokens=max_new, temperature=0.0,
                ),
            )
            for p in PROMPTS
        ])
        return [r.content for r in resps]
    finally:
        await handler.stop()


@pytest.mark.slow
@pytest.mark.parametrize(
    "paged,speculate,kv_int8",
    [
        (False, 0, False), (False, 0, True),
        (False, 4, False), (False, 4, True),
        (True, 0, False), (True, 0, True),
        (True, 4, False), (True, 4, True),
    ],
    ids=[
        "dense", "dense-int8kv", "dense-spec", "dense-spec-int8kv",
        "paged", "paged-int8kv", "paged-spec", "paged-spec-int8kv",
    ],
)
@pytest.mark.asyncio
async def test_sharded_greedy_byte_identity(paged, speculate, kv_int8):
    """The ISSUE 13 acceptance bar: greedy output byte-identical on
    mesh={'model':2,'data':2} (sharded pool, balanced admission groups,
    per-shard dispatch) vs the single-device engine, for every
    cache/speculation/quantization combination the serving path has."""
    single = await _generate_all(
        {"data": 1}, paged=paged, speculate=speculate, kv_int8=kv_int8,
    )
    meshed = await _generate_all(
        MESH, paged=paged, speculate=speculate, kv_int8=kv_int8,
    )
    assert meshed == single
    assert any(s for s in single)  # non-vacuous


@pytest.mark.slow
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.asyncio
async def test_sharded_int4_greedy_byte_identity(paged):
    """ISSUE 14: packed int4 weights compose with the sharded mesh path
    — Q4Tensor leaves shard like QTensor (q + group scales placed by
    the same logical axes) and greedy output on {'model':2,'data':2}
    stays byte-identical to the single-device int4 engine. Both boot
    paths quantize FROM the dense init, so the packed values match by
    construction (engine/native.py)."""
    single = await _generate_all(
        {"data": 1}, paged=paged, speculate=4, kv_int8=False,
        weight_quant="int4",
    )
    meshed = await _generate_all(
        MESH, paged=paged, speculate=4, kv_int8=False, weight_quant="int4",
    )
    assert meshed == single
    assert any(s for s in single)


# --------------------------------------------------------------------- #
# Slow: PR 9 kvcache tier under sharding — spill → evict → restore
# --------------------------------------------------------------------- #

def _kv_counters():
    return {
        k: global_metrics.get(f"engine.kvcache.{k}")
        for k in ("spills", "restores", "host_hits")
    }


def _run_session_seq(mesh, *, paged):
    cfg = get_model_config("llama-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    kwargs = dict(
        n_slots=2, max_seq_len=256, cache_dtype=jnp.float32, chunk_size=4,
        prefix_cache=1 if not paged else 4, kvcache_host_mb=64,
        use_pallas=False, mesh=mesh,
    )
    if paged:
        kwargs.update(paged=True, page_size=16)
    b = ContinuousBatcher(cfg, params, **kwargs)
    if paged and b.page_index is not None:
        b.page_index.capacity = 2
    base = [(i % 90) + 5 for i in range(80)]
    other = [(i % 70) + 11 for i in range(80)]
    resume = base + [7, 9, 11, 13]
    b.start()
    try:
        outs = []
        for prompt, sess in (
            (base, "s-mc"), (other, None), (resume, "s-mc"),
        ):
            req = GenRequest(
                prompt_ids=list(prompt), max_new_tokens=6, session_id=sess,
            )
            outs.append(b.submit(req).result(timeout=600))
        return outs
    finally:
        b.stop()


@pytest.mark.slow
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_sharded_spill_evict_restore_parity(paged):
    """The PR 9 cold-tier path with a SHARDED pool: turn 1 caches,
    unrelated traffic evicts (the spill gathers from sharded panels),
    the session resume restores through the sharding-aware placer —
    outputs byte-identical to the single-device engine running the
    identical sequence, and the tier demonstrably exercised."""
    single = _run_session_seq(None, paged=paged)
    before = _kv_counters()
    meshed = _run_session_seq(_mesh(MESH), paged=paged)
    delta = {k: _kv_counters()[k] - before[k] for k in before}
    assert meshed == single
    assert delta["spills"] >= 1, "sharded run never spilled"
    assert delta["restores"] >= 1, "sharded run never restored"
    assert all(len(o) >= 1 for o in single)


# --------------------------------------------------------------------- #
# Slow: PR 8 fault domain under sharding — mid-decode rebuild/recovery
# --------------------------------------------------------------------- #

@pytest.mark.slow
@pytest.mark.chaos
def test_sharded_mid_decode_rebuild_recovers_byte_identical():
    """An injected mid-decode dispatch failure on the SHARDED engine:
    the device-state rebuild re-places the pool on its mesh layout
    (place_kv_cache runs on the rebuild path), in-flight requests
    re-admit through recovery_max_attempts, and greedy output matches
    the unfaulted sharded run byte for byte."""
    from pilottai_tpu.reliability import global_injector

    cfg = get_model_config("llama-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    global_injector.reset()
    b = ContinuousBatcher(
        cfg, params, n_slots=2, max_seq_len=64, cache_dtype=jnp.float32,
        mesh=_mesh(MESH), recovery_max_attempts=2,
    )
    b.start()
    try:
        prompts = [[3, 4, 5], [6, 7]]
        ref = [
            b.submit(GenRequest(prompt_ids=list(p), max_new_tokens=12))
            .result(timeout=300)
            for p in prompts
        ]
        rebuilds = global_metrics.get("engine.rebuilds")
        global_injector.arm(
            "engine.step", RuntimeError("injected sharded fault"), times=1,
        )
        futs = [
            b.submit(GenRequest(prompt_ids=list(p), max_new_tokens=12))
            for p in prompts
        ]
        got = [f.result(timeout=300) for f in futs]
        assert got == ref
        assert global_injector.fired("engine.step") == 1
        assert global_metrics.get("engine.rebuilds") == rebuilds + 1
        # The rebuilt pool kept its mesh layout.
        k0 = b.cache.layers[0][0]
        assert not k0.sharding.is_fully_replicated
    finally:
        global_injector.reset()
        b.stop()


# --------------------------------------------------------------------- #
# Slow: the wired gauge reports nonzero under a sharded soak
# --------------------------------------------------------------------- #

@pytest.mark.slow
def test_collective_frac_nonzero_under_sharded_soak():
    """ISSUE 13 satellite end-to-end: a real sharded decode soak drives
    engine.collective_frac and .model above zero (the gauge existed
    since PR 6 and had never reported a nonzero value), while the
    single-device contract — exactly 0 — still holds."""
    cfg = get_model_config("llama-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    b = ContinuousBatcher(
        cfg, params, n_slots=4, max_seq_len=128, cache_dtype=jnp.float32,
        paged=True, page_size=16, mesh=_mesh(MESH),
    )
    assert b.collective_model is not None
    b.start()
    try:
        futs = [
            b.submit(GenRequest(
                prompt_ids=[5 + i, 6, 7, 8], max_new_tokens=16,
            ))
            for i in range(4)
        ]
        for f in futs:
            f.result(timeout=300)
    finally:
        b.stop()
    assert global_metrics.get("engine.collective_frac") > 0.0
    assert global_metrics.get("engine.collective_frac.model") > 0.0
    assert (
        global_metrics.get("engine.attributed_collective_s") > 0.0
    )
