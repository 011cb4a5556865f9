"""A compile-level guard on the writes into the KV and state stores.

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

The float32 state pool of a model with Mamba-2 layers (``StatePool.ssm``)
rides the decode loop beside the KV, and a decode step reads and writes
the live slots' rows of it alone (``models/ssm.py:mamba_step``). So at the
pool's shape the decode program may hold only the loop's plumbing and row
writes in place: no ``copy``, ``transpose`` or ``select``, and no fusion
but one whose root is a ``dynamic-update-slice`` or ``scatter``. The
update over every row that it replaced shows as such a fusion (a multiply
and add over the whole pool) and as copies of the pool beside the loop.
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
from pilottai_tpu.ops.kvcache import KVCache, StatePool
from pilottai_tpu.ops.paged import PagedKVCache

# Sizes no other tensor of the tiny models' programs shares.
B, PAGES, P, WIDTH, S = 4, 19, 32, 6, 176
STATE_SLOTS = 5      # nemotron-h-tiny's pool: [5, 4, 16, 8], as [5, 2, 2, 16, 8] grouped

_INSTR = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\((.*)$"
)
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(")
_ROOT = re.compile(r"^\s*ROOT %?[\w.\-]+ = \S+ ([\w\-]+)\(")
# what a loop's carry passes through without touching a byte of it
_PLUMBING = ("parameter", "get-tuple-element", "tuple", "bitcast", "while")
_IN_PLACE = ("dynamic-update-slice", "scatter")


def store_shapes(cache):
    """The full shapes of the cache's pools / panels and their scales."""
    shapes = {tuple(cache.layers[0][0].shape)}
    if cache.scales is not None:
        shapes.add(tuple(cache.scales[0][0].shape))
    return {",".join(map(str, s)) for s in shapes}


def state_shapes(cfg, cache):
    """The state pool's full shape, flat and with its heads grouped."""
    if cache.state is None:
        return set()
    n, heads, p, s = cache.state.ssm[0].shape
    g = cfg.ssm_groups
    return {f"{n},{heads},{p},{s}", f"{n},{g},{heads // g},{p},{s}"}


def _roots(hlo: str) -> dict:
    """Each computation's name -> the opcode of its ROOT."""
    roots, name = {}, None
    for line in hlo.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            name = head.group(1)
        root = _ROOT.match(line)
        if root and name:
            roots[name] = root.group(1)
    return roots


def relaying_ops(hlo: str, shapes, states=()) -> list:
    """Instructions of the optimised module that copy, transpose or
    re-lay a whole store: ``copy`` / ``transpose`` at a store's shape,
    and any ``scatter`` there whose indexed dimensions do not lead; at a
    state pool's shape (``states``), anything but plumbing and row writes
    in place, outside the fused computations whose roots are judged."""
    bad = []
    roots = _roots(hlo)
    fused = set(re.findall(r" fusion\(.*?calls=%?([\w.\-]+)", hlo))
    name = None
    for line in hlo.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            name = head.group(1)
        m = _INSTR.match(line)
        if not m:
            continue
        op = m.group(2)
        if m.group(1) in states and name not in fused:
            calls = re.search(r"calls=%?([\w.\-]+)", line)
            root = roots.get(calls.group(1)) if op == "fusion" and calls else op
            if op not in _PLUMBING and root not in _IN_PLACE:
                bad.append(line.strip()[:160])
            continue
        if m.group(1) not in shapes:
            continue
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


def test_the_guard_sees_the_whole_pool_update():
    """The state update this guard keeps out, compiled alone: every row of
    a donated pool decayed and fed, ``d`` zero where a row is not live."""
    pool = jnp.zeros((STATE_SLOTS, 4, 16, 8), jnp.float32)

    def whole(h, d, x, b):
        d = jnp.where(d > 0.5, d, 0.0)
        return h * jnp.exp(-d)[:, :, None, None] + (d[..., None] * x)[..., None] * b[:, None, None, :]

    hlo = jax.jit(whole, donate_argnums=0).lower(
        pool, jnp.zeros((STATE_SLOTS, 4)), jnp.zeros((STATE_SLOTS, 4, 16)),
        jnp.zeros((STATE_SLOTS, 8)),
    ).compile().as_text()
    assert relaying_ops(hlo, set(), {f"{STATE_SLOTS},4,16,8"})


@pytest.fixture(scope="module")
def tiny():
    cfg = get_model_config("llama-tiny")
    return cfg, init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)


@pytest.fixture(scope="module")
def hybrid():
    cfg = get_model_config("nemotron-h-tiny")
    return cfg, init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)


def _cache(cfg, kind):
    if kind == "hybrid":
        return KVCache.create(
            cfg.n_kv_layers, STATE_SLOTS, S, cfg.n_kv_heads, cfg.head_dim,
            state=StatePool.create(cfg, STATE_SLOTS),
        )
    if kind == "dense":
        return KVCache.create(cfg.n_layers, B, S, cfg.n_kv_heads, cfg.head_dim)
    return PagedKVCache.create(
        cfg.n_layers, B, PAGES, P, cfg.n_kv_heads, cfg.head_dim,
        quantized=kind == "paged-int8",
    )


@pytest.mark.parametrize("n_steps", [1, 4], ids=["chunk1", "chunk4"])
@pytest.mark.parametrize("kind", ["paged-int8", "paged-bf16", "dense", "hybrid"])
def test_decode_chunk_writes_in_place(kind, n_steps, request):
    """``hybrid``: Mamba-2, expert and attention layers with a dense KV
    cache and the state pool beside it."""
    cfg, params = request.getfixturevalue("hybrid" if kind == "hybrid" else "tiny")
    cache = _cache(cfg, kind)
    slots = cache.n_slots
    table = None if kind in ("dense", "hybrid") else jnp.zeros((B, WIDTH), jnp.int32)
    hlo = decode_chunk.lower(
        params, cfg, cache, DecodeState.create(slots), SamplingState.create(slots),
        n_steps, use_pallas=False, table=table,
    ).compile().as_text()
    assert relaying_ops(hlo, store_shapes(cache), state_shapes(cfg, cache)) == []


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
