"""A compile-level guard on the KV writes (PR 30).

The step programs donate the KV store and write a few rows into it. Until
PR 30 the write was an advanced-index scatter whose indexed dimensions did
not lead (``pool.at[:, pages, off].set``), and every XLA backend moves a
scatter's indexed dimensions to the front: the whole pool was transposed,
scattered into and copied back, on every dispatch (27% of the device's
busy time on ``mistral-7b.agent-loop``, ledger PR 29). The CPU backend
shows the same ``transpose`` + ``copy`` pair, so the guard runs here: no
program may hold a ``copy`` or ``transpose`` whose result has a pool's, a
scale pool's or a dense panel's full shape, and a ``scatter`` of such a
shape must index the store's LEADING dimensions, which is the form no
compiler has to re-lay (``ops/kvcache.py:write_rows``).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pilottai_tpu.engine.decode import (
    ADMIT_F32_ROWS,
    ADMIT_I32_ROWS,
    DecodeState,
    admit_group,
    admit_group_prefix_paged,
    decode_chunk,
    extend_prompt_paged,
)
from pilottai_tpu.engine.sampling import SamplingState
from pilottai_tpu.models.common import init_params
from pilottai_tpu.models.registry import get_model_config
from pilottai_tpu.ops.kvcache import KVCache
from pilottai_tpu.ops.paged import PagedKVCache

# Sizes no other tensor of the tiny model's programs shares.
B, PAGES, P, WIDTH, S = 4, 19, 32, 6, 176

_INSTR = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\((.*)$"
)


def store_shapes(cache):
    """The full shapes of the cache's pools / panels and their scales."""
    shapes = {tuple(cache.layers[0][0].shape)}
    if cache.scales is not None:
        shapes.add(tuple(cache.scales[0][0].shape))
    return {",".join(map(str, s)) for s in shapes}


def relaying_ops(hlo: str, shapes) -> list:
    """Instructions of the optimised module that copy, transpose or
    re-lay a whole store: ``copy`` / ``transpose`` at a store's shape,
    and any ``scatter`` there whose indexed dimensions do not lead."""
    bad = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m or m.group(1) not in shapes:
            continue
        op = m.group(2)
        if op in ("copy", "transpose"):
            bad.append(line.strip()[:160])
        elif op == "scatter":
            dims = re.search(r"scatter_dims_to_operand_dims=\{([\d,]*)\}", line)
            lead = [int(d) for d in dims.group(1).split(",")]
            if lead != list(range(len(lead))):
                bad.append(line.strip()[:160])
    return bad


def test_the_guard_sees_the_old_scatter():
    """The write this PR removed, compiled alone: the guard names its
    transpose and its copy (and would name them in a step program)."""
    pool = jnp.zeros((2, PAGES, P, 32), jnp.int8)

    def old(kp, pg, off, new):
        return kp.at[:, pg, off].set(new, mode="drop")

    hlo = jax.jit(old, donate_argnums=0).lower(
        pool, jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
        jnp.zeros((2, B, 32), jnp.int8),
    ).compile().as_text()
    ops = " ".join(relaying_ops(hlo, {f"2,{PAGES},{P},32"}))
    assert " transpose(" in ops and " copy(" in ops


@pytest.fixture(scope="module")
def tiny():
    cfg = get_model_config("llama-tiny")
    return cfg, init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)


def _cache(cfg, kind):
    if kind == "dense":
        return KVCache.create(cfg.n_layers, B, S, cfg.n_kv_heads, cfg.head_dim)
    return PagedKVCache.create(
        cfg.n_layers, B, PAGES, P, cfg.n_kv_heads, cfg.head_dim,
        quantized=kind == "paged-int8",
    )


@pytest.mark.parametrize("n_steps", [1, 4], ids=["chunk1", "chunk4"])
@pytest.mark.parametrize("kind", ["paged-int8", "paged-bf16", "dense"])
def test_decode_chunk_writes_in_place(tiny, kind, n_steps):
    cfg, params = tiny
    cache = _cache(cfg, kind)
    table = None if kind == "dense" else jnp.zeros((B, WIDTH), jnp.int32)
    hlo = decode_chunk.lower(
        params, cfg, cache, DecodeState.create(B), SamplingState.create(B),
        n_steps, use_pallas=False, table=table,
    ).compile().as_text()
    assert relaying_ops(hlo, store_shapes(cache)) == []


@pytest.mark.parametrize("kind", ["paged-int8", "paged-bf16"])
@pytest.mark.parametrize("program", ["full", "prefix_hit", "segment"])
def test_paged_admission_writes_in_place(tiny, kind, program):
    """The three admissions of a paged pool: a full prefill of two rows,
    a prefix hit's tail behind two shared pages, a segment of a long
    prompt."""
    cfg, params = tiny
    cache = _cache(cfg, kind)
    A, T = 2, 40
    rows = jnp.zeros((A, WIDTH), jnp.int32)
    meta = (
        jnp.zeros((ADMIT_I32_ROWS, A), jnp.int32),
        jnp.zeros((ADMIT_F32_ROWS, A), jnp.float32),
    )
    state = (DecodeState.create(B), SamplingState.create(B))
    tokens = jnp.zeros((A, T), jnp.int32)
    if program == "full":
        lowered = admit_group.lower(
            params, cfg, cache, *state, tokens, *meta, use_flash=False,
            page_rows=rows,
        )
    elif program == "prefix_hit":
        lowered = admit_group_prefix_paged.lower(
            params, cfg, cache, *state, jnp.zeros((2,), jnp.int32), tokens,
            jnp.zeros((A, 2 * P + T), jnp.int32), rows, *meta,
            n_prefix_bucket=2,
        )
    else:
        lowered = extend_prompt_paged.lower(
            params, cfg, cache, jnp.zeros((2,), jnp.int32), jnp.int32(2 * P),
            tokens[:1], jnp.full((1,), T, jnp.int32), rows[:1],
        )
    assert relaying_ops(lowered.compile().as_text(), store_shapes(cache)) == []
