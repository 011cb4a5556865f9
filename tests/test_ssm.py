"""The Mamba-2 mixer (``models/ssm.py``) at a tiny size on the CPU, float32:
the blocked scan against the step-by-step recurrence, rows of unequal length
in one group against each row alone, a prompt in segments against one pass,
and the one-token update against one more token of prefill."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pilottai_tpu.models import get_model_config, init_params, ssm
from pilottai_tpu.ops.pallas.ssm_update import ssm_update

CFG = get_model_config("nemotron-h-tiny").replace(dtype=jnp.float32)
# float32 sums in another order: a few units in the last place of values of
# order one (the scan's matmul form adds what the recurrence multiplies up)
TOL = dict(rtol=2e-5, atol=2e-5)


@pytest.fixture(scope="module")
def layer():
    params = init_params(CFG, jax.random.PRNGKey(3))
    return params["layers"][0]["ssm"]      # the pattern starts with an M layer


def _inputs(key, T, G=2, R=2, P=16, N=8):
    k = jax.random.split(key, 6)
    return (
        jax.random.normal(k[0], (T, G, R, P)),
        jax.nn.softplus(jax.random.normal(k[1], (T, G, R))) * 0.1,
        -jnp.exp(jax.random.normal(k[2], (G, R))),
        jax.random.normal(k[3], (T, G, N)), jax.random.normal(k[4], (T, G, N)),
        jax.random.normal(k[5], (G, R, P, N)),
    )


@pytest.mark.parametrize("T, chunk", [(64, 16), (64, 64), (32, 128), (48, 8)])
def test_blocked_scan_is_the_step_by_step_recurrence(T, chunk):
    xs, dt, A, B, C, h0 = _inputs(jax.random.PRNGKey(T + chunk), T)
    y1, h1 = ssm.ssd_scan(xs, dt, A, B, C, h0, chunk)
    y2, h2 = ssm.ssd_steps(xs, dt, A, B, C, h0)
    np.testing.assert_allclose(y1, y2, **TOL)
    np.testing.assert_allclose(h1, h2, **TOL)


def test_the_state_stands_still_where_dt_is_zero():
    xs, dt, A, B, C, h0 = _inputs(jax.random.PRNGKey(5), 32)
    dt = dt.at[20:].set(0.0)
    _, h_all = ssm.ssd_scan(xs, dt, A, B, C, h0, 8)
    _, h_20 = ssm.ssd_steps(xs[:20], dt[:20], A, B[:20], C[:20], h0)
    np.testing.assert_allclose(h_all, h_20, **TOL)


def test_a_chunk_that_does_not_divide_the_row_is_refused():
    xs, dt, A, B, C, h0 = _inputs(jax.random.PRNGKey(6), 40)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        ssm.ssd_scan(xs, dt, A, B, C, h0, 16)


def test_rows_of_unequal_length_in_one_group_are_each_row_alone(layer):
    u = jax.random.normal(jax.random.PRNGKey(7), (3, 64, CFG.hidden_size))
    lens = jnp.array([64, 37, 2])
    out, conv, state = ssm.mamba_prefill(CFG, layer, u, lens)
    for r, n in enumerate([64, 37, 2]):
        o1, c1, s1 = ssm.mamba_prefill(CFG, layer, u[r:r + 1], lens[r:r + 1])
        np.testing.assert_allclose(out[r, :n], o1[0, :n], **TOL)
        np.testing.assert_allclose(conv[r], c1[0], **TOL)
        np.testing.assert_allclose(state[r], s1[0], **TOL)
        # ... and what lies beyond a row's length moves nothing: the same
        # row padded with other bytes ends in the same two states
        noise = u[r:r + 1].at[:, n:].set(9.0)
        _, c2, s2 = ssm.mamba_prefill(CFG, layer, noise, lens[r:r + 1])
        np.testing.assert_array_equal(c1, c2)
        np.testing.assert_array_equal(s1, s2)


def test_the_conv_state_kept_is_the_last_real_positions(layer):
    u = jax.random.normal(jax.random.PRNGKey(8), (1, 16, CFG.hidden_size))
    _, conv, _ = ssm.mamba_prefill(CFG, layer, u, jnp.array([2]))
    assert conv.shape == (1, CFG.ssm_conv - 1, CFG.ssm_conv_dim)
    np.testing.assert_array_equal(conv[0, 0], 0.0)     # before the prompt: nothing
    assert np.abs(np.asarray(conv[0, 1:])).min() > 0.0


@pytest.mark.parametrize("split", [(32, 32), (16, 48), (48, 16)])
def test_a_prompt_in_segments_carries_both_states(layer, split):
    T = 64
    u = jax.random.normal(jax.random.PRNGKey(9), (1, T, CFG.hidden_size))
    whole, conv, state = ssm.mamba_prefill(CFG, layer, u, jnp.array([T]))
    a, b = split
    o1, c1, s1 = ssm.mamba_prefill(CFG, layer, u[:, :a], jnp.array([a]))
    # the second segment right-padded into a longer row, as a bucket pads it
    seg = jnp.zeros((1, T, CFG.hidden_size)).at[:, :b].set(u[:, a:])
    o2, c2, s2 = ssm.mamba_prefill(CFG, layer, seg, jnp.array([b]), c1, s1)
    np.testing.assert_allclose(jnp.concatenate([o1, o2[:, :b]], axis=1), whole, **TOL)
    np.testing.assert_allclose(c2, conv, **TOL)
    np.testing.assert_allclose(s2, state, **TOL)


def test_the_one_token_update_is_one_more_token_of_prefill(layer):
    u = jax.random.normal(jax.random.PRNGKey(10), (2, 16, CFG.hidden_size))
    lens = jnp.array([9, 4])
    _, conv, state = ssm.mamba_prefill(CFG, layer, u, lens)
    nxt = jnp.stack([u[0, 9], u[1, 4]])[:, None]
    out, conv1, state1 = ssm.mamba_step(CFG, layer, nxt, conv, state, jnp.array([True, True]))
    want, wconv, wstate = ssm.mamba_prefill(CFG, layer, u, lens + 1)
    np.testing.assert_allclose(out[0, 0], want[0, 9], **TOL)
    np.testing.assert_allclose(out[1, 0], want[1, 4], **TOL)
    np.testing.assert_allclose(conv1, wconv, **TOL)
    np.testing.assert_allclose(state1, wstate, **TOL)
    # a row that is not active keeps both states to the bit
    _, conv2, state2 = ssm.mamba_step(CFG, layer, nxt, conv, state, jnp.array([True, False]))
    np.testing.assert_array_equal(conv2[1], conv[1])
    np.testing.assert_array_equal(state2[1], state[1])
    np.testing.assert_allclose(state2[0], wstate[0], **TOL)


def whole_pool_step(cfg, p, u, conv0, ssm0, active):
    """The one-token update over every row of the pool, ``d`` zeroed where a
    row is not active: the form ``mamba_step`` had before it moved the live
    rows alone, kept here as the yardstick."""
    B = u.shape[0]
    G, R = cfg.ssm_groups, cfg.ssm_heads // cfg.ssm_groups
    P, N = cfg.ssm_head_dim, cfg.ssm_state
    z, xbc, dt = ssm._split(cfg, u[:, 0] @ p["in_proj"])
    window = jnp.concatenate([conv0, xbc[:, None].astype(conv0.dtype)], axis=1)
    acc = jnp.einsum("bkc,kc->bc", window, p["conv_w"]) + p["conv_b"]
    conv_state = jnp.where(active[:, None, None], window[:, 1:], conv0)
    xs, Bm, Cm = ssm._heads(cfg, jax.nn.silu(acc))
    d = jnp.where(active[:, None], ssm._dt(p, dt), 0.0).reshape(B, G, R)
    A = -jnp.exp(p["A_log"]).reshape(G, R)
    h = ssm0.reshape(B, G, R, P, N)
    h = h * jnp.exp(d * A)[..., None, None] + (d[..., None] * xs)[..., None] * Bm[:, :, None, None, :]
    y = jnp.einsum("bgrpn,bgn->bgrp", h, Cm) + p["D"].reshape(G, R)[..., None] * xs
    out = ssm._gated_out(cfg, p, y.reshape(B, cfg.ssm_inner), z)
    return out[:, None], conv_state, h.reshape(ssm0.shape)


LIVE = dict(argvalues=[(), (3,), (0, 5, 7), tuple(range(8))],
            ids=["no_row", "one_row", "rows_0_5_7", "every_row"])


@pytest.mark.parametrize("live", **LIVE)
def test_the_update_moves_the_live_rows_as_the_whole_pool_formula_does(layer, live):
    """Eight slots whose pool holds state from a prefill: the live rows come
    out as the whole-pool formula computes them, every other row of both
    states stays as it was to the bit, and every output is finite."""
    B = 8
    k = jax.random.split(jax.random.PRNGKey(11), 2)
    u = jax.random.normal(k[0], (B, 12, CFG.hidden_size))
    _, conv, state = ssm.mamba_prefill(CFG, layer, u, jnp.arange(5, 5 + B))
    nxt = jax.random.normal(k[1], (B, 1, CFG.hidden_size))
    active = jnp.zeros((B,), bool).at[jnp.asarray(live, jnp.int32)].set(True)
    out, conv1, state1 = jax.jit(ssm.mamba_step, static_argnums=0)(
        CFG, layer, nxt, conv, state, active)
    want, wconv, wstate = whole_pool_step(CFG, layer, nxt, conv, state, active)
    on = np.asarray(active)
    np.testing.assert_array_equal(np.asarray(conv1)[~on], np.asarray(conv)[~on])
    np.testing.assert_array_equal(np.asarray(state1)[~on], np.asarray(state)[~on])
    np.testing.assert_allclose(np.asarray(conv1)[on], np.asarray(wconv)[on], **TOL)
    np.testing.assert_allclose(np.asarray(state1)[on], np.asarray(wstate)[on], **TOL)
    np.testing.assert_allclose(np.asarray(out)[on], np.asarray(want)[on], **TOL)
    assert np.isfinite(np.asarray(out)).all()
    if live:       # the live rows did move
        assert not np.array_equal(np.asarray(state1)[on], np.asarray(state)[on])


@pytest.mark.parametrize("live", **LIVE)
def test_the_kernel_moves_the_live_rows_as_the_whole_pool_formula_does(live):
    """``ops/pallas/ssm_update.py`` in interpret mode, at a shape it takes
    (a state of 128, two heads of 64 to a tile), on eight slots: the live
    rows as the whole-pool formula computes them, every other row of the
    pool as it was to the bit and of ``y`` zero."""
    B, G, R, P, N = 8, 2, 2, 64, 128
    k = jax.random.split(jax.random.PRNGKey(12), 5)
    pool = jax.random.normal(k[0], (B, G * R, P, N))
    decay = jax.random.uniform(k[1], (B, G * R))
    dx = jax.random.normal(k[2], (B, G * R, P)) * 0.1
    Bm, Cm = jax.random.normal(k[3], (2, B, G, N))
    active = jnp.zeros((B,), bool).at[jnp.asarray(live, jnp.int32)].set(True)
    new, y = jax.jit(lambda *a: ssm_update(*a, groups=G, interpret=True))(
        pool, decay, dx, Bm, Cm, active)
    h = (pool.reshape(B, G, R, P, N) * decay.reshape(B, G, R)[..., None, None]
         + dx.reshape(B, G, R, P)[..., None] * Bm[:, :, None, None, :])
    want_y = jnp.einsum("bgrpn,bgn->bgrp", h, Cm).reshape(B, G * R, P)
    on = np.asarray(active)
    np.testing.assert_array_equal(np.asarray(new)[~on], np.asarray(pool)[~on])
    np.testing.assert_array_equal(np.asarray(y)[~on], 0.0)
    np.testing.assert_allclose(np.asarray(new)[on], np.asarray(h.reshape(pool.shape))[on], **TOL)
    np.testing.assert_allclose(np.asarray(y)[on], np.asarray(want_y)[on], **TOL)
