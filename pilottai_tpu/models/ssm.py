"""The Mamba-2 mixer: a prefill over right-padded rows of unequal length, and
a one-token update.

With ``u`` the layer's normed input: ``[z | xBC | dt] = u W_in``; ``xBC`` goes
through a causal depthwise convolution (kernel ``ssm_conv``, with bias) and
SiLU, and splits into ``xs`` (heads x head_dim), ``B`` and ``C`` (groups x
state; head ``h`` reads group ``h // (heads / groups)``). Per head,
``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, and

    h_t = exp(dt_t A) h_{t-1} + dt_t xs_t (x) B_t        y_t = h_t C_t + D xs_t

then ``y <- y silu(z)``, RMS-normalised within each group's channels, times a
weight, and ``out = y W_out``.

Two kinds of state cross a call, both per row: the last ``ssm_conv - 1``
inputs of the convolution (activation dtype) and ``h`` (float32,
``[heads, head_dim, state]``). ``mamba_prefill`` takes and returns both, so a
prompt admitted in segments carries them from segment to segment, and beyond
a row's length it lets them stand still: ``dt`` is 0 there (``exp(0) = 1``,
no input), and the convolution state kept is the last real positions'.
``mamba_step`` reads and writes the state of the ``active`` rows only and
leaves the others exactly as they were.

The prefill is a blocked scan in plain ``jax.numpy`` (chunks of
``ssm_chunk``: inside a chunk the recurrence is a masked matmul, between
chunks a short ``lax.scan`` over the chunk states), one row at a time under
``lax.map`` so that its temporaries are one row's. The chunk changes no
mathematics (``tests/test_ssm.py`` holds it to the step-by-step recurrence).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from pilottai_tpu.models.qmatmul import qmatmul
from pilottai_tpu.ops.pallas.ssm_update import ssm_update, ssm_update_ok


def _split(cfg: Any, zxbcdt: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    I, C = cfg.ssm_inner, cfg.ssm_conv_dim
    return zxbcdt[..., :I], zxbcdt[..., I:I + C], zxbcdt[..., I + C:]


def _heads(cfg: Any, xbc: jax.Array):
    """Convolved ``xBC [..., C]`` -> ``xs [..., G, R, P]``, ``B`` and ``C``
    ``[..., G, N]`` in float32 (R heads share a group)."""
    I, G, N = cfg.ssm_inner, cfg.ssm_groups, cfg.ssm_state
    lead = xbc.shape[:-1]
    f = xbc.astype(jnp.float32)
    xs = f[..., :I].reshape(lead + (G, cfg.ssm_heads // G, cfg.ssm_head_dim))
    Bm = f[..., I:I + G * N].reshape(lead + (G, N))
    Cm = f[..., I + G * N:].reshape(lead + (G, N))
    return xs, Bm, Cm


def _gated_out(cfg: Any, p: Dict[str, Any], y: jax.Array, z: jax.Array) -> jax.Array:
    """``y silu(z)``, RMS-normalised within each group, times the weight,
    through the output projection. ``y`` float32 ``[..., inner]``."""
    G = cfg.ssm_groups
    y = y * jax.nn.silu(z.astype(jnp.float32))
    g = y.reshape(y.shape[:-1] + (G, cfg.ssm_inner // G))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg.rms_eps)
    y = g.reshape(y.shape) * p["norm"].astype(jnp.float32)
    return qmatmul(y.astype(z.dtype), p["out_proj"])


def _dt(p: Dict[str, Any], dt: jax.Array) -> jax.Array:
    return jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))


@jax.named_scope("ssm_scan")
def ssd_scan(
    xs: jax.Array,    # [T, G, R, P] float32
    dt: jax.Array,    # [T, G, R]   float32, 0 beyond the row's length
    A: jax.Array,     # [G, R]      float32, negative
    Bm: jax.Array,    # [T, G, N]
    Cm: jax.Array,    # [T, G, N]
    h0: jax.Array,    # [G, R, P, N]
    chunk: int,
) -> Tuple[jax.Array, jax.Array]:
    """One row's recurrence in chunks: ``(y [T, G, R, P], h_T)``."""
    T = xs.shape[0]
    Q = min(chunk, T)
    if T % Q:
        raise ValueError(f"a row of {T} tokens is no multiple of the chunk {Q}")
    nc = T // Q
    c = lambda a: a.reshape((nc, Q) + a.shape[1:])
    xdt = c(xs * dt[..., None])                        # [nc, Q, G, R, P]
    acs = jnp.cumsum(c(dt * A), axis=1)                # [nc, Q, G, R], <= 0, falling
    Bc, Cc = c(Bm), c(Cm)
    # inside a chunk: y_i += sum_{j<=i} exp(acs_i - acs_j) (C_i . B_j) dt_j x_j
    seg = acs[:, :, None] - acs[:, None, :]            # [nc, Q(i), Q(j), G, R]
    causal = (jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :])[None, :, :, None, None]
    L = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    CB = jnp.einsum("cign,cjgn->cijg", Cc, Bc)
    y = jnp.einsum("cijgr,cjgrp->cigrp", L * CB[..., None], xdt)
    # what each chunk adds to the state by its end, and how it decays the state
    to_end = jnp.exp(acs[:, -1:] - acs)                # [nc, Q, G, R]
    S = jnp.einsum("cjgn,cjgrp->cgrpn", Bc, xdt * to_end[..., None])
    decay = jnp.exp(acs[:, -1])                        # [nc, G, R]

    def carry(h, sd):
        s, d = sd
        return h * d[..., None, None] + s, h           # emits the state entering

    h_last, h_in = jax.lax.scan(carry, h0, (S, decay))
    # the entering state's share of every position of its chunk
    y = y + jnp.einsum("cign,cgrpn->cigrp", Cc, h_in) * jnp.exp(acs)[..., None]
    return y.reshape(xs.shape), h_last


def ssd_steps(xs, dt, A, Bm, Cm, h0):
    """The same recurrence one token at a time (the tests' yardstick)."""

    def step(h, t):
        x, d, b, c = t
        h = h * jnp.exp(d * A)[..., None, None] + (d[..., None] * x)[..., None] * b[:, None, None, :]
        return h, jnp.einsum("grpn,gn->grp", h, c)

    h, y = jax.lax.scan(step, h0, (xs, dt, Bm, Cm))
    return y, h


@jax.named_scope("ssm")
def mamba_prefill(
    cfg: Any,
    p: Dict[str, Any],
    u: jax.Array,                       # [A, T, E] normed input, right-padded
    lens: jax.Array,                    # [A] tokens of this call that are real
    conv0: Optional[jax.Array] = None,  # [A, K-1, C]; None = a fresh row
    ssm0: Optional[jax.Array] = None,   # [A, heads, head_dim, state] float32
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``(out [A, T, E], conv state, ssm state)`` after each row's ``lens``
    tokens, continuing from ``conv0`` / ``ssm0``."""
    A_, T, _ = u.shape
    K, C = cfg.ssm_conv, cfg.ssm_conv_dim
    G, R = cfg.ssm_groups, cfg.ssm_heads // cfg.ssm_groups
    P, N = cfg.ssm_head_dim, cfg.ssm_state
    z, xbc, dt = _split(cfg, qmatmul(u, p["in_proj"]))
    if conv0 is None:
        conv0 = jnp.zeros((A_, K - 1, C), xbc.dtype)
    if ssm0 is None:
        ssm0 = jnp.zeros((A_, cfg.ssm_heads, P, N), jnp.float32)
    with jax.named_scope("ssm_conv"):
        padded = jnp.concatenate([conv0.astype(xbc.dtype), xbc], axis=1)   # [A, K-1+T, C]
        w = p["conv_w"].astype(jnp.float32)
        acc = sum(
            padded[:, j:j + T].astype(jnp.float32) * w[j] for j in range(K)
        ) + p["conv_b"].astype(jnp.float32)
        conv = jax.nn.silu(acc)
        # the last K-1 real inputs: padded[len : len + K-1] are inputs len-(K-1) .. len-1
        conv_state = jax.vmap(
            lambda row, n: jax.lax.dynamic_slice_in_dim(row, n, K - 1, axis=0)
        )(padded, lens)
    xs, Bm, Cm = _heads(cfg, conv)
    real = jnp.arange(T)[None, :] < lens[:, None]
    dts = jnp.where(real[..., None], _dt(p, dt), 0.0).reshape(A_, T, G, R)
    Aneg = -jnp.exp(p["A_log"].astype(jnp.float32)).reshape(G, R)
    y, h = jax.lax.map(
        lambda r: ssd_scan(r[0], r[1], Aneg, r[2], r[3], r[4], cfg.ssm_chunk),
        (xs, dts, Bm, Cm, ssm0.reshape(A_, G, R, P, N)),
    )
    y = y + p["D"].astype(jnp.float32).reshape(G, R)[..., None] * xs
    out = _gated_out(cfg, p, y.reshape(A_, T, cfg.ssm_inner), z)
    return out, conv_state, h.reshape(A_, cfg.ssm_heads, P, N)


def _live_rows(pool, decay, dx, Bm, Cm, active):
    """The update of ``mamba_step`` in plain ``jax``, one live row at a
    time: ``(pool', y [B, G, R, P])``."""
    B, G, R, P = dx.shape
    live = jnp.nonzero(active, size=B, fill_value=0)[0]

    def row(k, carry):
        pool, y = carry
        b = live[k]
        at = lambda a: jax.lax.dynamic_slice_in_dim(a, b, 1)
        h = at(pool).reshape(1, G, R, P, -1)
        h = h * at(decay)[..., None, None] + at(dx)[..., None] * at(Bm)[:, :, None, None, :]
        pool = jax.lax.dynamic_update_slice_in_dim(pool, h.reshape((1,) + pool.shape[1:]), b, 0)
        # the read-out reads the row back from the pool: reading the old
        # row instead would have to keep the old pool beside the new one
        h = at(pool).reshape(h.shape)
        y_b = jnp.einsum("bgrpn,bgn->bgrp", h, at(Cm))
        return pool, jax.lax.dynamic_update_slice_in_dim(y, y_b, b, 0)

    return jax.lax.fori_loop(
        0, jnp.sum(active, dtype=jnp.int32), row, (pool, jnp.zeros(dx.shape, jnp.float32)))


@jax.named_scope("ssm")
def mamba_step(
    cfg: Any,
    p: Dict[str, Any],
    u: jax.Array,          # [B, 1, E] normed input
    conv0: jax.Array,      # [B, K-1, C]
    ssm0: jax.Array,       # [B, heads, head_dim, state] float32
    active: jax.Array,     # [B] bool: rows whose state moves
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One token for every row; a row that is not ``active`` keeps both
    states to the bit (a slot between two segments of its prompt is such a
    row, and so is a free slot or one that finished earlier in the chunk).

    The float32 state of the active rows alone is read and written, in
    place on ``ssm0``: the rows are found on the device from ``active``, so
    the program is the same whatever is live. The pool is 64 slots x 4.2 MB
    a layer at Nemotron-3-Super's widths, and updating every row, live or
    not, took a third of a decode step's device time with four live
    (PERF.md §6). On a TPU, at the shapes it takes, the update is one
    Pallas kernel over the live rows (``ops/pallas/ssm_update.py``: a row
    read and written once); elsewhere a loop of ``dynamic_update_slice``
    a live row. A row that is not active reads ``y = 0`` under the ``D xs``
    term: finite, and discarded by the caller.
    """
    B = u.shape[0]
    H, G, R = cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_heads // cfg.ssm_groups
    P, N = cfg.ssm_head_dim, cfg.ssm_state
    z, xbc, dt = _split(cfg, qmatmul(u[:, 0], p["in_proj"]))
    with jax.named_scope("ssm_conv"):
        window = jnp.concatenate([conv0, xbc[:, None].astype(conv0.dtype)], axis=1)
        acc = jnp.einsum(
            "bkc,kc->bc", window.astype(jnp.float32), p["conv_w"].astype(jnp.float32)
        ) + p["conv_b"].astype(jnp.float32)
        conv = jax.nn.silu(acc)
        conv_state = jnp.where(active[:, None, None], window[:, 1:], conv0)
    xs, Bm, Cm = _heads(cfg, conv)                          # [B, G, R, P], [B, G, N]
    with jax.named_scope("ssm_scan"):
        d = _dt(p, dt).reshape(B, G, R)
        Aneg = -jnp.exp(p["A_log"].astype(jnp.float32)).reshape(G, R)
        decay = jnp.exp(d * Aneg)                           # [B, G, R]
        dx = d[..., None] * xs                              # [B, G, R, P]
        if jax.default_backend() == "tpu" and ssm_update_ok(H, P, N):
            ssm, y = ssm_update(
                ssm0, decay.reshape(B, H), dx.reshape(B, H, P), Bm, Cm, active, groups=G)
            y = y.reshape(B, G, R, P)
        else:
            ssm, y = _live_rows(ssm0, decay, dx, Bm, Cm, active)
    y = y + p["D"].astype(jnp.float32).reshape(G, R)[..., None] * xs
    out = _gated_out(cfg, p, y.reshape(B, cfg.ssm_inner), z)
    return out[:, None], conv_state, ssm
