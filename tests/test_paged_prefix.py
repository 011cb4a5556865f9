"""Composed fast paths on the paged KV cache (VERDICT r3 next-step 1).

Round 3's speculation and prefix caching were dense-only; the paged
cache — the long-context path, and the auto-selected one for large
contexts — silently lost both. These tests certify the composition:
block-granular prefix caching (``engine/page_prefix.py``) and
speculative decoding (``decode_chunk_spec`` with a block table) each
produce BIT-IDENTICAL greedy output to a cold dense engine, separately
and together, on one device and on the virtual 8-device mesh.
"""

import asyncio

import numpy as np
import pytest

from pilottai_tpu.core.config import LLMConfig
from pilottai_tpu.engine.handler import LLMHandler
from pilottai_tpu.engine.page_prefix import PagePrefixIndex
from pilottai_tpu.engine.types import ChatMessage, GenerationParams
from pilottai_tpu.ops.paged import PageAllocator
from pilottai_tpu.utils.metrics import global_metrics


# --------------------------------------------------------------------- #
# PagePrefixIndex + refcounted allocator units
# --------------------------------------------------------------------- #

def test_index_match_is_proper_prefix_and_block_granular():
    alloc = PageAllocator(num_pages=17, page_size=4, n_slots=4,
                          max_pages_per_slot=8)
    idx = PagePrefixIndex(page_size=4, capacity_pages=8)
    ids = list(range(100, 116))  # 4 full blocks
    assert alloc.allocate(0, len(ids) + 4)
    pages = [int(p) for p in alloc.table[0, :4]]
    idx.register(ids, pages, alloc)

    # Exact ids: only 3 blocks may match (a tail token must remain).
    node = idx.match(ids)
    assert node is not None and node.depth == 3
    assert list(node.path_pages) == pages[:3]
    # Longer prompt sharing all blocks: full 4-block chain.
    assert idx.match(ids + [7, 8]).depth == 4
    # Diverging within block 2: only 1 block shared.
    div = ids[:6] + [999] * 10
    assert idx.match(div).depth == 1
    # Diverging in block 0: no match.
    assert idx.match([999] * 16) is None


def test_allocator_refcounts_shared_pages():
    alloc = PageAllocator(num_pages=9, page_size=4, n_slots=4,
                          max_pages_per_slot=8)
    assert alloc.allocate(0, 8)          # 2 private pages
    shared = list(alloc._held[0])
    # Pin both (the index), then release the slot: pages stay live.
    for p in shared:
        alloc.pin(p)
    alloc.release(0)
    assert alloc.free_pages == 8 - 2
    # Map them into a new slot as a shared prefix + 1 fresh page.
    assert alloc.allocate(1, 12, prefix_pages=shared)
    assert list(alloc.table[1, :2]) == shared
    alloc.release(1)
    assert alloc.free_pages == 8 - 2     # still pinned
    for p in shared:
        alloc.unpin(p)
    assert alloc.free_pages == 8         # everything back


def test_index_eviction_respects_protect_and_leaves():
    alloc = PageAllocator(num_pages=17, page_size=2, n_slots=4,
                          max_pages_per_slot=8)
    idx = PagePrefixIndex(page_size=2, capacity_pages=16)
    assert alloc.allocate(0, 8)
    pages = [int(p) for p in alloc.table[0, :4]]
    idx.register(list(range(8)), pages, alloc)
    alloc.release(0)
    free0 = alloc.free_pages
    # Protected chain: nothing evictable.
    assert idx.evict(4, alloc, protect=frozenset(pages)) == 0
    # Unprotected: leaves evict deepest-first (leaf-only), pages free.
    assert idx.evict(2, alloc) == 2
    assert alloc.free_pages == free0 + 2
    assert idx.match(list(range(8)) + [1]).depth == 2


def test_index_capacity_bounds_pins():
    alloc = PageAllocator(num_pages=33, page_size=2, n_slots=4,
                          max_pages_per_slot=16)
    idx = PagePrefixIndex(page_size=2, capacity_pages=3)
    assert alloc.allocate(0, 16)
    pages = [int(p) for p in alloc.table[0, :8]]
    idx.register(list(range(16)), pages, alloc)
    assert idx.pinned_pages <= 3
    alloc.release(0)


# --------------------------------------------------------------------- #
# Engine parity: every fast-path combination vs a cold dense engine
# --------------------------------------------------------------------- #

LONG = ("You are the orchestrator. Analyze the task and respond with "
        "strict JSON as instructed by the rules preamble. Task: ")


async def _run_engine(prompts, *, paged=False, speculate=0, prefix=0,
                      mesh=None, max_new=14):
    h = LLMHandler(LLMConfig(
        model_name="llama-tiny", provider="cpu", engine_slots=4,
        engine_max_seq=256, engine_chunk=4, dtype="float32",
        engine_paged_kv=paged, engine_page_size=16,
        engine_speculate=speculate, engine_prefix_cache=prefix,
        mesh_shape=mesh,
    ))
    await h.start()
    try:
        outs = []
        for p in prompts:
            r = await h.generate_response(
                [ChatMessage(content=p)],
                params=GenerationParams(max_new_tokens=max_new,
                                        temperature=0.0),
            )
            outs.append(r.content)
        return outs, h.get_metrics()["backend"]
    finally:
        await h.stop()


@pytest.mark.asyncio
@pytest.mark.parametrize("speculate", [0, 4])
async def test_paged_prefix_hit_identical_to_cold_dense(speculate):
    """Exact repeat on the paged engine must hit the block-granular
    cache (prompt >= one 16-token page) and emit the same bits as a
    cold DENSE engine — with and without speculation on top."""
    prompt = LONG + "summarize the quarterly report"
    (want,), _ = await _run_engine([prompt])

    h0 = global_metrics.get("engine.prefix_hits")
    outs, metrics = await _run_engine(
        [prompt, prompt, prompt],
        paged=True, speculate=speculate, prefix=8,
    )
    assert outs == [want] * 3
    assert global_metrics.get("engine.prefix_hits") - h0 >= 1
    assert metrics.get("prefix_pages", 0) >= 1


@pytest.mark.asyncio
async def test_paged_spec_identical_to_plain_dense():
    """decode_chunk_spec over the block table: greedy output parity on
    repetitive AND novel prompts (prefix cache off isolates spec)."""
    prompts = [LONG + "abc abc abc abc", "one shot novel text"]
    want, _ = await _run_engine(prompts)
    got, _ = await _run_engine(prompts, paged=True, speculate=4)
    assert got == want


@pytest.mark.asyncio
async def test_paged_block_sharing_without_full_repeat():
    """Block granularity replaces the dense store's LCP derivation: two
    different prompts sharing the page-aligned preamble make the THIRD
    distinct prompt hit — no full repeat ever seen."""
    (want3,), _ = await _run_engine([LONG + "third unseen task"])
    h0 = global_metrics.get("engine.prefix_hits")
    outs, _ = await _run_engine(
        [LONG + "first task", LONG + "second very different task",
         LONG + "third unseen task"],
        paged=True, prefix=8,
    )
    hits = global_metrics.get("engine.prefix_hits") - h0
    assert hits >= 1, "shared page-aligned preamble never hit"
    assert outs[2] == want3


@pytest.mark.asyncio
async def test_paged_all_features_on_mesh():
    """The full composition on the virtual 8-device mesh: paged KV +
    speculation + block-granular prefix cache + model/data sharding,
    parity against the same engine's own miss output."""
    prompt = LONG + "mesh parity with every fast path on"
    (want,), _ = await _run_engine([prompt])
    outs, _ = await _run_engine(
        [prompt, prompt],
        paged=True, speculate=4, prefix=8,
        mesh={"model": 2, "data": 2},
    )
    assert outs == [want, want]


@pytest.mark.asyncio
async def test_paged_prefix_pressure_evicts_not_starves():
    """A pool too small to hold cached chains + a new admission must
    reclaim cached pages instead of deadlocking the queue."""
    h = LLMHandler(LLMConfig(
        model_name="llama-tiny", provider="cpu", engine_slots=2,
        engine_max_seq=512, engine_chunk=4, dtype="float32",
        engine_paged_kv=True, engine_page_size=16, engine_kv_pages=13,
        engine_prefix_cache=8,
    ))
    await h.start()
    try:
        outs = []
        for i in range(5):
            outs.append(await h.apredict(
                f"task number {i}: " + "pad " * 30,
                params=GenerationParams(max_new_tokens=8, temperature=0.0),
            ))
        assert all(isinstance(o, str) for o in outs)
    finally:
        await h.stop()


# --------------------------------------------------------------------- #
# Admission rows follow the group: every kind of admission, every rung
# --------------------------------------------------------------------- #

BASE = [(i % 90) + 5 for i in range(80)]     # five whole pages of 16
# Tails that share no first token (no chain deeper than BASE forms) and
# whose longest comes first, so every group's tail bucket is 16.
TAILS = [
    [100 + 7 * i + j for j in range(n)]
    for i, n in enumerate((9, 3, 5, 7, 4, 6, 8, 2))
]
# kind: (batcher arguments, bucket the dispatch runs, rows by group size).
# A prefix hit uses every rung of the ladder, and so does a full prefill at
# the top bucket (``full_prefill_cap``, here max_seq_len); at a smaller
# bucket a full prefill pads to ``admit_batch`` as it always did.
EVERY_RUNG = {1: 1, 2: 2, 3: 4, 5: 8, 8: 8}
KINDS = {
    "full": (dict(prefix_cache=0, max_seq_len=128), 128, EVERY_RUNG),
    "full_half_bucket": (dict(prefix_cache=0, max_seq_len=256), 128,
                         {1: 8, 2: 8, 3: 8, 5: 8, 8: 8}),
    "full_paged": (dict(prefix_cache=0, max_seq_len=128, paged=True,
                        page_size=16), 128, EVERY_RUNG),
    "prefix_paged": (dict(prefix_cache=8, max_seq_len=256, paged=True,
                          page_size=16), 16, EVERY_RUNG),
    "prefix": (dict(prefix_cache=8, max_seq_len=256), 16, EVERY_RUNG),
}
WAIT_S = 240.0


def _ladder_batcher(kind):
    import jax
    import jax.numpy as jnp

    from pilottai_tpu.engine.batcher import ContinuousBatcher
    from pilottai_tpu.models.common import init_params
    from pilottai_tpu.models.registry import get_model_config

    cfg = get_model_config("llama-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return ContinuousBatcher(
        cfg, params, n_slots=8, cache_dtype=jnp.float32, chunk_size=4,
        use_pallas=False, **KINDS[kind][0],
    )


def _ladder_run(kind, n, together):
    """Outputs of the first ``n`` requests and the counters' movement
    over their admission, after the shared prefix (where the kind has
    one) was cached by a request of its own."""
    from pilottai_tpu.engine.batcher import GenRequest

    b = _ladder_batcher(kind)
    b.start()
    try:
        if not kind.startswith("full"):
            b.submit(GenRequest(prompt_ids=list(BASE), max_new_tokens=2)
                     ).result(timeout=WAIT_S)
        reqs = [
            GenRequest(prompt_ids=BASE + tail, max_new_tokens=6, eos_id=-1)
            for tail in TAILS[:n]
        ]
        before = dict(global_metrics.snapshot()["counters"])
        if together:
            b._submit_together(reqs)
            outs = [r.future.result(timeout=WAIT_S) for r in reqs]
        else:
            outs = [b.submit(r).result(timeout=WAIT_S) for r in reqs]
        after = dict(global_metrics.snapshot()["counters"])
    finally:
        b.stop()
    return outs, {
        k: after.get(k, 0.0) - before.get(k, 0.0)
        for k in ("engine.admitted", "engine.prefix_hits",
                  "engine.prefill_tokens_run", "engine.prefill_tokens_real",
                  "engine.kvcache.prefill_tokens_saved")
    }


_ALONE = {}


@pytest.mark.parametrize("n", [1, 2, 3, 5, 8])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_group_runs_its_rung_of_rows_and_the_same_tokens(kind, n):
    """A group of ``n`` is dispatched at its rung of rows, not at
    ``admit_batch``, and each request's greedy tokens are those it gets
    when admitted alone (its own rung, its own tail bucket)."""
    if kind not in _ALONE:
        _ALONE[kind] = _ladder_run(kind, len(TAILS), together=False)[0]
    outs, moved = _ladder_run(kind, n, together=True)
    assert [len(o) for o in outs] == [6] * n
    assert outs == _ALONE[kind][:n]
    _, bucket, rungs = KINDS[kind]
    rung = rungs[n]
    hit = not kind.startswith("full")
    assert moved["engine.admitted"] == n
    assert moved["engine.prefix_hits"] == (n if hit else 0)
    # one dispatch of rung x bucket: the tails' bucket where the prefix
    # was mapped or copied, the prompts' where it was not
    assert moved["engine.prefill_tokens_run"] == rung * bucket
    saved = moved["engine.kvcache.prefill_tokens_saved"]
    assert (saved >= n * (len(BASE) - 1)) == hit
    assert moved["engine.prefill_tokens_real"] + saved == sum(
        len(BASE) + len(t) for t in TAILS[:n]
    )
