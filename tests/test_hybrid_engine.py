"""A stack of unlike layers (``ModelConfig.layer_kinds``: Mamba-2, latent
experts, attention) through the batcher, the admission and decode programs
and both caches, tiny and float32 on the CPU. What is held to the model's own
full forward pass is what the served path produced: prefill into the KV cache
and the state pool, then decode steps that move both.

In float32 on one backend the two paths differ by summation order only, so
greedy tokens are compared for equality (the logits themselves are held to
the plain reference in ``tests/test_perfbench_archs.py``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pilottai_tpu.engine import batcher as engine_batcher
from pilottai_tpu.engine.batcher import ContinuousBatcher, GenRequest
from pilottai_tpu.models import get_model_config, init_params
from pilottai_tpu.models.common import param_logical_axes
from pilottai_tpu.models.hybrid import forward_prefill_hybrid
from pilottai_tpu.utils.metrics import global_metrics

CFG = get_model_config("nemotron-h-tiny").replace(dtype=jnp.float32)
NEW = 6


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


def greedy(params, prompt, n=NEW):
    """``n`` greedy tokens by the full forward pass over the growing sequence:
    no cache, no state pool, no batching."""
    seq = list(prompt)
    for _ in range(n):
        T = 16
        while T < len(seq):
            T *= 2
        tokens = np.zeros((1, T), np.int32)
        tokens[0, :len(seq)] = seq
        logits, *_ = forward_prefill_hybrid(
            params, CFG, jnp.asarray(tokens), jnp.asarray([len(seq)]), use_flash=False,
            logit_positions=jnp.asarray([len(seq) - 1]))
        seq.append(int(jnp.argmax(logits[0])))
    return seq[len(prompt):]


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, 380, size=n)] for n in lengths]


def batcher(params, **kw):
    kw = dict(dict(n_slots=4, max_seq_len=256, min_bucket=16, cache_dtype=jnp.float32,
                   chunk_size=2, admit_batch=4, on_tpu=False), **kw)
    b = ContinuousBatcher(CFG, params, **kw)
    b.start()
    return b


def serve(b, ps, together=True, n=NEW):
    reqs = [GenRequest(prompt_ids=p, max_new_tokens=n, eos_id=-1) for p in ps]
    if together:
        b._submit_together(reqs)
    else:
        for r in reqs:
            b.submit(r)
    return [r.future.result(timeout=600) for r in reqs]


@pytest.fixture(scope="module")
def dense(params):
    b = batcher(params)
    yield b
    b.stop()


@pytest.fixture(scope="module")
def paged(params):
    b = batcher(params, paged=True, page_size=16, prefill_chunk=32)
    yield b
    b.stop()


def test_the_cache_holds_kv_for_the_attention_layer_and_a_state_pool_beside_it(dense, paged):
    for b in (dense, paged):
        assert len(b.cache.layers) == 1 == CFG.n_kv_layers      # 1 of 7 layers keeps KV
        pool = b.cache.state
        assert len(pool.conv) == len(pool.ssm) == 3             # the three M layers
        assert pool.conv[0].shape == (4, CFG.ssm_conv - 1, CFG.ssm_conv_dim)
        assert pool.ssm[0].shape == (4, 4, 16, 8) and pool.ssm[0].dtype == jnp.float32


@pytest.mark.parametrize("cache", ["dense", "paged"])
def test_prefill_then_decode_is_the_full_forward_pass_for_unequal_rows_in_one_group(
        cache, params, request):
    b = request.getfixturevalue(cache)
    ps = prompts(40, 23, 7)
    before = global_metrics.get("engine.admitted")
    got = serve(b, ps)
    assert global_metrics.get("engine.admitted") == before + 3
    for p, tokens in zip(ps, got):
        assert tokens == greedy(params, p)


def test_a_prompt_admitted_in_segments_is_one_admission(paged, dense, params):
    (p,) = prompts(100, seed=4)
    segments = global_metrics.get("engine.prefill_segments")
    in_segments = serve(paged, [p])[0]
    # 100 tokens at 32 a segment: three segments, then the last tail admits
    assert global_metrics.get("engine.prefill_segments") == segments + 3
    assert in_segments == serve(dense, [p])[0] == greedy(params, p)


def test_decode_between_the_segments_of_a_prompt_leaves_its_state_alone(paged, params):
    """While a long prompt is admitted segment by segment, the slots that are
    live keep decoding; the slot in between is not active and its rows of the
    pool must stand still."""
    short, long = prompts(9, 120, seed=5)
    r1 = GenRequest(prompt_ids=short, max_new_tokens=40, eos_id=-1)
    r2 = GenRequest(prompt_ids=long, max_new_tokens=NEW, eos_id=-1)
    paged.submit(r1)
    paged.submit(r2)
    assert r2.future.result(timeout=600) == greedy(params, long)
    assert r1.future.result(timeout=600) == greedy(params, short, 40)


def test_decode_with_live_slots_apart_and_one_ending_mid_chunk_is_the_full_forward_pass(
        params, monkeypatch):
    """Four slots, chunks of four steps: the second and fourth requests end
    after one and two decode steps, inside the first chunk, and the first and
    third decode on from slots with a free one between them. Every answer
    is still the full forward pass's, so each live row moved its own state
    and no other."""
    seen = []       # per decode dispatch: slots live entering it, valid [n, B]
    decode_chunk = engine_batcher.decode_chunk

    def recorded(params_, cfg, cache, dstate, *a, **kw):
        live = ~np.asarray(dstate.done)
        out = decode_chunk(params_, cfg, cache, dstate, *a, **kw)
        seen.append((live, np.asarray(out[1])))
        return out

    monkeypatch.setattr(engine_batcher, "decode_chunk", recorded)
    b = batcher(params, chunk_size=4)
    try:
        ps = prompts(30, 12, 25, 9, seed=9)
        budgets = (11, 2, 13, 3)
        reqs = [GenRequest(prompt_ids=p, max_new_tokens=n, eos_id=-1) for p, n in zip(ps, budgets)]
        b._submit_together(reqs)
        got = [r.future.result(timeout=600) for r in reqs]
    finally:
        b.stop()
    for p, n, tokens in zip(ps, budgets, got):
        assert tokens == greedy(params, p, n)
    # what the test is about did happen: a chunk in which a slot stopped
    # after its first step while others went on, and a chunk whose live
    # slots have a free one between them
    assert any(v[0].sum() > v[1].sum() > 0 for _, v in seen)
    assert any(np.flatnonzero(live).size > 1 and np.any(np.diff(np.flatnonzero(live)) > 1)
               for live, _ in seen)


def test_a_slot_reused_after_a_long_request_answers_as_a_fresh_one(params):
    long, short = prompts(90, 11, seed=6)
    b = batcher(params, n_slots=1, admit_batch=1)
    try:
        first = serve(b, [long], n=30)[0]
        again = serve(b, [short])[0]          # the one slot, nothing cleared between
    finally:
        b.stop()
    assert first == greedy(params, long, 30)
    assert again == greedy(params, short)


@pytest.mark.parametrize("cache", ["dense", "paged"])
def test_a_repeated_prompt_answers_the_same_and_hits_no_prefix(cache, request):
    b = request.getfixturevalue(cache)
    assert b.prefix_store is None and b.page_index is None and b.kvcache is None
    (p,) = prompts(70, seed=7)
    hits = global_metrics.get("engine.prefix_hits")
    bypassed = global_metrics.get("engine.prefix_bypassed_recurrent")
    assert serve(b, [p])[0] == serve(b, [p])[0]
    assert global_metrics.get("engine.prefix_hits") == hits
    assert global_metrics.get("engine.prefix_bypassed_recurrent") == bypassed + 2


def test_the_expert_layers_count_pairs_routed_and_pairs_held(dense):
    routed = global_metrics.get("engine.moe_assignments")
    held = global_metrics.get("engine.moe_assignments_held")
    serve(dense, prompts(33, 12, seed=8))
    d_routed = global_metrics.get("engine.moe_assignments") - routed
    d_held = global_metrics.get("engine.moe_assignments_held") - held
    # 3 expert layers x top-3 x (45 prompt tokens + 2 x 5 decode steps)
    assert d_routed == 3 * 3 * (45 + 2 * (NEW - 1))
    assert 0 < d_held < d_routed            # 4 of 16 experts are held
    assert global_metrics.snapshot()["gauges"]["engine.state_slots_live"] >= 1


def test_what_would_drop_the_state_is_refused_by_name(params, dense):
    with pytest.raises(NotImplementedError, match="nemotron-h-tiny.*speculative"):
        ContinuousBatcher(CFG, params, n_slots=2, max_seq_len=64, speculate=4, on_tpu=False)
    with pytest.raises(NotImplementedError, match="bfloat16 only"):
        ContinuousBatcher(CFG, params, n_slots=2, max_seq_len=64, kv_quantize=True, on_tpu=False)
    for call in (lambda: dense.export_request_kv([1, 2, 3]), lambda: dense.import_request_kv({}),
                 lambda: dense.export_session_kv("s"), lambda: dense.import_session_kv({})):
        with pytest.raises(NotImplementedError, match="nemotron-h-tiny.*drop the state"):
            call()
    with pytest.raises(ValueError, match="nemotron-h-tiny.*bfloat16"):
        init_params(CFG, jax.random.PRNGKey(0), quantize=True)
    with pytest.raises(NotImplementedError, match="exchange across chips"):
        param_logical_axes(CFG)


def test_the_counts_are_the_new_layers_and_no_llama_count(params):
    leaves = sum(int(a.size) for a in jax.tree.leaves(params))
    assert CFG.param_count() == leaves
    as_llama = CFG.replace(layer_kinds=()).param_count()
    assert as_llama != leaves
    # of top-3 among 16 a token finds 0.75 of its experts among the 4 held
    one = 2 * CFG.moe_latent * CFG.moe_intermediate
    assert CFG.active_param_count() == leaves - 3 * int((4 - 0.75) * one)
    assert CFG.recurrent and not get_model_config("llama-tiny").recurrent
