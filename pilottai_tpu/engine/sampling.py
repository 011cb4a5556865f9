"""Device-side token sampling: greedy, temperature, top-k, top-p.

Runs inside the jitted decode step (no host round-trip per token).
Per-slot temperature lets one batched decode serve requests with different
sampling settings — agent workloads mix deterministic JSON steps
(temperature 0) with creative generation in the same batch.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp


class SamplingState(NamedTuple):
    """Per-slot sampling parameters living on device."""

    temperature: jax.Array  # [B] fp32; 0 => greedy
    top_k: jax.Array        # [B] int32; 0 => disabled
    top_p: jax.Array        # [B] fp32; 1.0 => disabled
    key: jax.Array          # [B, 2] uint32 per-slot PRNG keys
    eos_id: jax.Array       # [B] int32; -1 => disabled (device EOS detect)
    # JSON grammar automaton coords (engine/json_mask.py); enabled per slot
    # by GenerationParams.json_mode on byte tokenizers.
    json_enabled: jax.Array  # [B] bool
    json_state: jax.Array    # [B] int32
    json_stack: jax.Array    # [B] int32 (container-type bit per level)
    json_depth: jax.Array    # [B] int32
    # Schema-constrained slots (engine/json_schema.py): row into the
    # engine's SchemaBank, -1 = generic JSON automaton. Schema slots
    # reuse ``json_state`` as their DFA state (start = 1, accept = 0).
    json_schema_id: jax.Array  # [B] int32

    @classmethod
    def create(cls, n_slots: int, seed: int = 0) -> "SamplingState":
        keys = jax.random.split(jax.random.PRNGKey(seed), n_slots)
        return cls(
            temperature=jnp.zeros((n_slots,), jnp.float32),
            top_k=jnp.zeros((n_slots,), jnp.int32),
            top_p=jnp.ones((n_slots,), jnp.float32),
            key=keys,
            eos_id=jnp.full((n_slots,), -1, jnp.int32),
            json_enabled=jnp.zeros((n_slots,), bool),
            json_state=jnp.zeros((n_slots,), jnp.int32),
            json_stack=jnp.zeros((n_slots,), jnp.int32),
            json_depth=jnp.zeros((n_slots,), jnp.int32),
            json_schema_id=jnp.full((n_slots,), -1, jnp.int32),
        )


def _mask_top_k(logits: jax.Array, k: jax.Array) -> jax.Array:
    """Per-row top-k mask with traced k (0 disables). [B, V]."""
    V = logits.shape[-1]
    sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]  # desc
    idx = jnp.clip(k - 1, 0, V - 1)
    kth = jnp.take_along_axis(sorted_logits, idx[:, None], axis=-1)
    keep = (logits >= kth) | (k[:, None] <= 0)
    return jnp.where(keep, logits, -jnp.inf)


def _mask_top_p(logits: jax.Array, p: jax.Array) -> jax.Array:
    """Nucleus mask with traced p (1.0 disables). [B, V]."""
    sort_idx = jnp.argsort(logits, axis=-1)[:, ::-1]
    sorted_logits = jnp.take_along_axis(logits, sort_idx, axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # Keep tokens whose cumulative mass (exclusive) is below p.
    keep_sorted = (cum - probs) < p[:, None]
    keep = jnp.zeros_like(keep_sorted).at[
        jnp.arange(logits.shape[0])[:, None], sort_idx
    ].set(keep_sorted)
    return jnp.where(keep | (p[:, None] >= 1.0), logits, -jnp.inf)


def _apply_json_mask(
    logits: jax.Array,
    state: SamplingState,
    remaining: jax.Array | None = None,
    token_tables: tuple[jax.Array, jax.Array] | None = None,
    schema_tables: tuple[jax.Array, jax.Array, jax.Array] | None = None,
) -> jax.Array:
    """Constrain logits of json-enabled slots to grammar-legal tokens.
    ``remaining`` (budget left, [B]) enables forced document closure.
    ``token_tables`` = (token_bytes [Vt, L], token_len [Vt]) switches from
    the byte automaton to the token→byte product (subword vocabs).
    ``schema_tables`` = the SchemaBank's (ALLOWED [N,S,256],
    NEXT [N,S,256], MINCOST [N,S]) — slots with ``json_schema_id >= 0``
    mask against their compiled schema DFA instead of the generic
    grammar (byte tokenizers only; budget feasibility is the exact
    shortest-completion cost)."""
    from pilottai_tpu.engine.json_mask import (
        S_DONE,
        json_allowed_bytes,
        json_allowed_tokens,
    )

    B, V = logits.shape
    if token_tables is not None:
        tb, tl = token_tables
        tok_ok = json_allowed_tokens(
            state.json_state, state.json_stack, state.json_depth,
            tb, tl, remaining,
        )                                               # [B, Vt]
        full = jnp.zeros((B, V), bool).at[:, : tb.shape[0]].set(
            tok_ok[:, :V]
        )
    else:
        byte_ok = json_allowed_bytes(
            state.json_state, state.json_stack, state.json_depth, remaining
        )                                               # [B, 256]
        full = jnp.zeros((B, V), bool).at[:, :256].set(byte_ok[:, :V])
    schema_slot = state.json_schema_id >= 0
    if schema_tables is not None and token_tables is None:
        s_allowed, s_next, s_cost = schema_tables
        sid = jnp.clip(state.json_schema_id, 0, s_allowed.shape[0] - 1)
        st = state.json_state
        ok = s_allowed[sid, st]                          # [B, 256]
        nxt = s_next[sid, st]                            # [B, 256]
        cost = s_cost[sid[:, None], nxt]                 # [B, 256]
        if remaining is not None:
            ok = ok & (cost <= remaining[:, None] - 1)
        s_full = jnp.zeros((B, V), bool).at[:, :256].set(ok[:, :V])
        full = jnp.where(schema_slot[:, None], s_full, full)
        done = jnp.where(schema_slot, st == 0, state.json_state == S_DONE)
    else:
        done = state.json_state == S_DONE
    # Document closed: force EOS when the slot has one (else pad spaces).
    eos_ok = done & (state.eos_id >= 0)
    eos_onehot = jax.nn.one_hot(
        jnp.clip(state.eos_id, 0, V - 1), V, dtype=bool
    )
    full = jnp.where(eos_ok[:, None], eos_onehot, full)
    # Empty-mask fallback (token mode under an infeasible budget / odd
    # vocab): an all-False row would argmax to pad-token garbage forever.
    # Degrade the way the byte path's budget-exhaustion does: end the
    # generation (EOS) when the slot has one, else sample unconstrained.
    empty = ~full.any(axis=-1)
    full = jnp.where(
        (empty & (state.eos_id >= 0))[:, None], eos_onehot, full
    )
    full = full | (empty & (state.eos_id < 0))[:, None]
    masked = jnp.where(full, logits, -2.0**30)
    return jnp.where(state.json_enabled[:, None], masked, logits)


def _advance_json(
    state: SamplingState,
    tokens: jax.Array,
    token_tables: tuple[jax.Array, jax.Array] | None = None,
    schema_tables: tuple[jax.Array, jax.Array, jax.Array] | None = None,
) -> SamplingState:
    from pilottai_tpu.engine.json_mask import (
        json_advance,
        json_advance_tokens,
    )

    if token_tables is not None:
        ns, stack, depth = json_advance_tokens(
            state.json_state, state.json_stack, state.json_depth, tokens,
            *token_tables,
        )
    else:
        ns, stack, depth = json_advance(
            state.json_state, state.json_stack, state.json_depth, tokens
        )
    if schema_tables is not None and token_tables is None:
        _, s_next, _ = schema_tables
        sid = jnp.clip(state.json_schema_id, 0, s_next.shape[0] - 1)
        byte = jnp.clip(tokens, 0, 255)
        s_ns = s_next[sid, state.json_state, byte]
        # Non-byte tokens (EOS/specials) don't advance the DFA.
        s_ns = jnp.where(tokens < 256, s_ns, state.json_state)
        schema_slot = state.json_schema_id >= 0
        ns = jnp.where(schema_slot, s_ns, ns)
        stack = jnp.where(schema_slot, state.json_stack, stack)
        depth = jnp.where(schema_slot, state.json_depth, depth)
    en = state.json_enabled
    return state._replace(
        json_state=jnp.where(en, ns, state.json_state),
        json_stack=jnp.where(en, stack, state.json_stack),
        json_depth=jnp.where(en, depth, state.json_depth),
    )


def fused_verify_rows(
    logits: jax.Array,        # [B, D-1, V] verify rows 1..D-1 of a block
    draft_tokens: jax.Array,  # [B, D-1] the draft path those rows follow
    state: SamplingState,     # coords BEFORE the block's row-0 sample
    budget: jax.Array,        # [B] remaining budget entering the block
    token_tables: tuple[jax.Array, jax.Array] | None = None,
    schema_tables: tuple[jax.Array, jax.Array, jax.Array] | None = None,
) -> jax.Array:
    """Masked-greedy verify rows for one speculative block as ONE
    vectorized mask+argmax over all D-1 rows.

    Byte-identical to the per-row loop it replaces (advance coords by
    draft token j, mask row j with ``remaining = budget - j``, argmax):
    the JSON-coordinate chain — a few [B] table lookups per row, cheap
    and inherently sequential — still walks the draft path row by row,
    but the expensive part (the [B, V] grammar/schema mask build and
    the argmax, previously one dispatch per row) flattens the (slot,
    row) pair into the batch axis and runs once per block. At D=6 that
    cuts five mask+argmax dispatches per verify block to one — the
    small-op sampler floor the r6 profile measured at ~2.3 ms/block.

    Returns the greedy rows ``[B, D-1] int32``."""
    B, Dm1, V = logits.shape
    states, stacks, depths = [], [], []
    coords = state
    for j in range(Dm1):
        coords = _advance_json(
            coords, draft_tokens[:, j], token_tables, schema_tables
        )
        states.append(coords.json_state)
        stacks.append(coords.json_stack)
        depths.append(coords.json_depth)
    # Flatten (b, j) row-major to match logits.reshape(B * Dm1, V).
    flat = state._replace(
        json_state=jnp.stack(states, axis=1).reshape(-1),
        json_stack=jnp.stack(stacks, axis=1).reshape(-1),
        json_depth=jnp.stack(depths, axis=1).reshape(-1),
        json_enabled=jnp.repeat(state.json_enabled, Dm1),
        json_schema_id=jnp.repeat(state.json_schema_id, Dm1),
        eos_id=jnp.repeat(state.eos_id, Dm1),
    )
    remaining = (
        budget[:, None] - (jnp.arange(Dm1, dtype=budget.dtype)[None, :] + 1)
    ).reshape(-1)
    masked = _apply_json_mask(
        logits.reshape(B * Dm1, V), flat, remaining,
        token_tables, schema_tables,
    )
    return jnp.argmax(masked, axis=-1).astype(jnp.int32).reshape(B, Dm1)


def split_step_keys(keys: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One sampling step's PRNG advance: per-slot ``(step_keys,
    carry_keys)`` from ``[B, 2]`` keys. THE key-split scheme — shared by
    ``sample_core`` and the fused greedy epilogue
    (engine/decode.py:_advance_keys), whose bit-identity contract
    requires both paths to advance keys identically; change it here or
    nowhere."""
    new_keys = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    return new_keys[:, 0], new_keys[:, 1]


@jax.named_scope("sampler")
def sample_core(
    logits: jax.Array,  # [B, V] fp32
    state: SamplingState,
    json_remaining: jax.Array | None = None,  # [B] budget incl. this token
    json_token_tables: tuple[jax.Array, jax.Array] | None = None,
    json_schema_tables: tuple[jax.Array, jax.Array, jax.Array] | None = None,
) -> tuple[jax.Array, SamplingState]:
    """Sample one token per slot; greedy where temperature == 0.

    Plain function (no jit) so the decode chunk can inline it inside its
    step scan; ``sample_tokens`` is the standalone jitted wrapper."""
    logits = _apply_json_mask(
        logits, state, json_remaining, json_token_tables, json_schema_tables
    )
    greedy = jnp.argmax(logits, axis=-1)

    temp = jnp.maximum(state.temperature, 1e-6)[:, None]
    scaled = logits / temp
    scaled = _mask_top_k(scaled, state.top_k)
    scaled = _mask_top_p(scaled, state.top_p)

    def sample_row(key, row):
        return jax.random.categorical(key, row)

    step_keys, carry_keys = split_step_keys(state.key)
    sampled = jax.vmap(sample_row)(step_keys, scaled)

    tokens = jnp.where(state.temperature <= 0.0, greedy, sampled).astype(
        jnp.int32
    )
    state = _advance_json(
        state._replace(key=carry_keys), tokens, json_token_tables,
        json_schema_tables,
    )
    return tokens, state


@partial(jax.jit, donate_argnames=("state",))
def sample_tokens(
    logits: jax.Array,  # [B, V] fp32
    state: SamplingState,
) -> tuple[jax.Array, SamplingState]:
    return sample_core(logits, state)


def update_slot(
    state: SamplingState,
    slot: int | jax.Array,
    temperature: float,
    top_k: int,
    top_p: float,
    seed: int,
    eos_id: int = -1,
    json_mode: bool = False,
    json_schema_id: int = -1,
) -> SamplingState:
    """Host-side admission: install one request's sampling params."""
    return state._replace(
        temperature=state.temperature.at[slot].set(temperature),
        top_k=state.top_k.at[slot].set(top_k),
        top_p=state.top_p.at[slot].set(top_p),
        key=state.key.at[slot].set(jax.random.PRNGKey(seed)[None][0]),
        eos_id=state.eos_id.at[slot].set(eos_id),
        json_enabled=state.json_enabled.at[slot].set(json_mode),
        # Schema DFAs start at state 1 (engine/json_schema.py:START);
        # the generic automaton at 0.
        json_state=state.json_state.at[slot].set(
            1 if json_schema_id >= 0 else 0
        ),
        json_stack=state.json_stack.at[slot].set(0),
        json_depth=state.json_depth.at[slot].set(0),
        json_schema_id=state.json_schema_id.at[slot].set(json_schema_id),
    )


@partial(jax.jit, donate_argnames=("state",))
def admit_sampling(
    state: SamplingState,
    slots: jax.Array,        # [A] int32; out-of-range rows are dropped
    temperature: jax.Array,  # [A] fp32
    top_k: jax.Array,        # [A] int32
    top_p: jax.Array,        # [A] fp32
    seeds: jax.Array,        # [A] int32
    eos_id: jax.Array,       # [A] int32
    json_mode: jax.Array,    # [A] bool — grammar-constrained decoding
    schema_ids: jax.Array | None = None,  # [A] int32; -1 = generic
) -> SamplingState:
    """Batched admission: install a group of requests' sampling params."""
    keys = jax.vmap(jax.random.PRNGKey)(seeds)
    zeros = jnp.zeros_like(slots)
    if schema_ids is None:
        schema_ids = jnp.full_like(slots, -1)
    # Schema DFAs start at state 1 (engine/json_schema.py:START).
    init_state = jnp.where(schema_ids >= 0, 1, 0).astype(jnp.int32)
    return state._replace(
        temperature=state.temperature.at[slots].set(temperature, mode="drop"),
        top_k=state.top_k.at[slots].set(top_k, mode="drop"),
        top_p=state.top_p.at[slots].set(top_p, mode="drop"),
        key=state.key.at[slots].set(keys, mode="drop"),
        eos_id=state.eos_id.at[slots].set(eos_id, mode="drop"),
        json_enabled=state.json_enabled.at[slots].set(json_mode, mode="drop"),
        json_state=state.json_state.at[slots].set(init_state, mode="drop"),
        json_stack=state.json_stack.at[slots].set(zeros, mode="drop"),
        json_depth=state.json_depth.at[slots].set(zeros, mode="drop"),
        json_schema_id=state.json_schema_id.at[slots].set(
            schema_ids, mode="drop"
        ),
    )
