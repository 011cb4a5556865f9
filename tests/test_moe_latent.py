"""The expert layer that is told which experts it holds
(``models/moe.py:latent_moe``), tiny and float32 on the CPU: the share test of
the sizing guide (the shares' routed parts, with the shared expert counted
once, add up to the uncut layer), the grouped dispatch against a plain loop
over tokens, what is counted, and that the other family keeps its path."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pilottai_tpu.models import get_model_config, init_params
from pilottai_tpu.models import moe
from pilottai_tpu.models.transformer import _activation, _mlp

TINY = get_model_config("nemotron-h-tiny").replace(dtype=jnp.float32)
WHOLE = TINY.replace(experts_held=None)
# float32 sums in another order (sorted by expert against token by token)
TOL = dict(rtol=1e-5, atol=1e-5)


@pytest.fixture(scope="module")
def whole():
    """An expert layer's parameters with all 16 experts, and an input."""
    p = init_params(WHOLE, jax.random.PRNGKey(1))["layers"][1]["moe"]
    p = dict(p, bias=0.05 * jax.random.normal(jax.random.PRNGKey(2), p["bias"].shape))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, WHOLE.hidden_size))
    return p, x


def run(cfg, p, x, real=None, shared=True):
    real = jnp.ones(x.shape[:2], bool) if real is None else real
    zero = lambda t: jnp.zeros_like(t)
    return moe.latent_moe(
        cfg, p, x, real, partial(_activation, cfg),
        (lambda t: _mlp(cfg, {"mlp": p["shared"]}, t)[0]) if shared else zero)


def share(p, lo, hi):
    return dict(p, w_up=p["w_up"][lo:hi], w_down=p["w_down"][lo:hi])


def test_the_four_shares_with_the_shared_expert_once_add_up_to_the_uncut_layer(whole):
    p, x = whole
    full, n_full = run(WHOLE, p, x)
    parts, landed = 0.0, 0
    for i in range(4):
        cfg = WHOLE.replace(experts_held=(4 * i, 4 * i + 4))
        out, n = run(cfg, share(p, 4 * i, 4 * i + 4), x, shared=(i == 0))
        parts = parts + out
        landed += int(n[1])
        assert int(n[0]) == int(n_full[0]) == 2 * 24 * WHOLE.n_active_experts
    np.testing.assert_allclose(parts, full, **TOL)
    assert landed == int(n_full[1]) == int(n_full[0])     # every pair lands on one share


def test_grouped_dispatch_is_the_plain_sum_over_the_chosen_experts_held(whole):
    p, x = whole
    cfg = WHOLE.replace(experts_held=(4, 8))
    out, n = run(cfg, share(p, 4, 8), x, shared=False)
    xf = np.asarray(x, np.float64).reshape(-1, WHOLE.hidden_size)
    P = {k: np.asarray(v, np.float64) for k, v in p.items() if k != "shared"}
    s = 1.0 / (1.0 + np.exp(-(xf @ P["router"])))
    want, landed = np.zeros_like(xf), 0
    for t in range(xf.shape[0]):
        chosen = np.argsort(-(s[t] + P["bias"]), kind="stable")[:WHOLE.n_active_experts]
        w = s[t, chosen] / s[t, chosen].sum() * WHOLE.moe_scale
        lat, acc = xf[t] @ P["w_in"], np.zeros(WHOLE.moe_latent)
        for e, we in zip(chosen, w):
            if 4 <= e < 8:
                landed += 1
                acc = acc + we * (np.maximum(lat @ P["w_up"][e], 0.0) ** 2 @ P["w_down"][e])
        want[t] = acc @ P["w_out"]
    np.testing.assert_allclose(np.asarray(out).reshape(want.shape), want, rtol=2e-4, atol=2e-4)
    assert int(n[1]) == landed and 0 < landed < int(n[0])


def test_padding_is_neither_routed_nor_counted(whole):
    p, x = whole
    cfg = WHOLE.replace(experts_held=(0, 4))
    real = jnp.arange(24)[None, :] < jnp.array([24, 5])[:, None]
    out, n = run(cfg, share(p, 0, 4), x, real)
    alone, n1 = run(cfg, share(p, 0, 4), x[1:, :8], real[1:, :8])
    assert int(n[0]) == (24 + 5) * WHOLE.n_active_experts
    np.testing.assert_allclose(out[1, :5], alone[0, :5], **TOL)
    # only the routed part is withheld from padding; the count says so
    full, nf = run(cfg, share(p, 0, 4), x)
    assert int(nf[0]) == 48 * WHOLE.n_active_experts and int(nf[1]) >= int(n[1])


def test_a_long_prefill_runs_block_by_block_to_the_same_sum(whole, monkeypatch):
    p, x = whole
    cfg = WHOLE.replace(experts_held=(0, 4))
    x = jnp.concatenate([x, x[:, ::-1]], axis=1)[:, :32]           # 2 x 32 = 64 tokens
    one, n1 = run(cfg, share(p, 0, 4), x)
    monkeypatch.setattr(moe, "LATENT_MOE_BLOCK", 16)
    blocks, n2 = run(cfg, share(p, 0, 4), x)
    np.testing.assert_allclose(one, blocks, **TOL)
    assert [int(v) for v in n1] == [int(v) for v in n2]


def test_the_other_family_keeps_dense_dispatch():
    cfg = get_model_config("moe-tiny")
    assert cfg.moe_router == "softmax" and not cfg.layer_kinds
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert set(params["layers"]["moe"]) == {"router", "wg", "wu", "wd"}
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    out, aux = _mlp(cfg, lp, jnp.ones((1, 4, cfg.hidden_size), cfg.dtype))
    assert out.shape == (1, 4, cfg.hidden_size) and float(aux) > 0.0
