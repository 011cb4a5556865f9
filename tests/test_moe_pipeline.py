"""MoE (expert parallelism) and GPipe pipeline building block."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from pilottai_tpu.models.common import init_params
from pilottai_tpu.models.registry import get_model_config
from pilottai_tpu.models.transformer import forward_prefill
from pilottai_tpu.parallel.mesh import MeshConfig, create_mesh
from pilottai_tpu.parallel.pipeline import pipeline_apply, split_layers_to_stages
from pilottai_tpu.train import Trainer, TrainConfig, synthetic_batches


# ------------------------------- MoE ---------------------------------- #

def test_moe_single_expert_equals_dense():
    """n_experts=1, top-1: routing is a no-op, output must equal the dense
    MLP with identical weights."""
    dense = get_model_config("llama-tiny")
    moe = dense.replace(name="moe1", n_experts=1, n_active_experts=1)
    p_dense = init_params(dense, jax.random.key(0), dtype=jnp.float32)
    p_moe = init_params(moe, jax.random.key(0), dtype=jnp.float32)
    # Copy dense weights into expert 0; attn/norm/embed already match.
    for name in ("wg", "wu", "wd"):
        p_moe["layers"]["moe"][name] = p_dense["layers"]["mlp"][name][:, None]
    p_moe["layers"] = {
        **{k: v for k, v in p_dense["layers"].items() if k != "mlp"},
        "moe": p_moe["layers"]["moe"],
    }
    B, T = 2, 16
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, dense.vocab_size, (B, T)), jnp.int32
    )
    positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    valid = jnp.full((B,), T, jnp.int32)
    ld, _, _ = forward_prefill(p_dense, dense, tokens, positions, valid)
    lm, _, _ = forward_prefill(p_moe, moe, tokens, positions, valid)
    # einsum vs @ contraction order differs slightly in f32
    np.testing.assert_allclose(ld, lm, atol=1e-4, rtol=1e-4)


def test_moe_trains_with_expert_parallelism():
    cfg = get_model_config("moe-tiny")
    mesh = create_mesh(MeshConfig(data=1, fsdp=2, model=2, seq=2))
    t = Trainer(
        cfg,
        TrainConfig(
            learning_rate=1e-2, warmup_steps=1, total_steps=20,
            context_parallel=True,
        ),
        mesh=mesh,
    )
    state = t.init(jax.random.key(0))
    wg = state[0]["layers"]["moe"]["wg"]
    assert "model" in jax.tree.leaves(
        [wg.sharding.spec]
    )[0] or wg.sharding.spec[1] == "model"  # expert axis on 'model'
    batch = next(synthetic_batches(cfg, 4, 32))
    losses = []
    for _ in range(6):
        state, m = t.step(state, batch)
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_moe_registry_param_counts():
    mixtral = get_model_config("mixtral-8x7b")
    assert 45e9 < mixtral.param_count() < 50e9  # 8x7B ≈ 46.7B total
    assert get_model_config("moe-tiny").n_experts == 4


# ----------------------------- pipeline -------------------------------- #

def _mlp_stack(L=8, D=16, seed=0):
    rng = np.random.default_rng(seed)
    params = {
        "w": jnp.asarray(rng.normal(size=(L, D, D)) * 0.3, jnp.float32),
        "b": jnp.asarray(rng.normal(size=(L, D)) * 0.1, jnp.float32),
    }

    def block_fn(p, x):
        def layer(x, lp):
            return jnp.tanh(x @ lp[0] + lp[1]), None
        x, _ = jax.lax.scan(layer, x, (p["w"], p["b"]))
        return x

    return params, block_fn


@pytest.fixture(scope="module")
def stage_mesh():
    devs = np.asarray(jax.devices()).reshape(4, 2)
    return Mesh(devs, ("stage", "data"))


def test_pipeline_matches_sequential(stage_mesh):
    params, block_fn = _mlp_stack()
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(8, 4, 16)), jnp.float32)
    ref = jax.vmap(lambda xi: block_fn(params, xi))(x)
    staged = split_layers_to_stages(params, 4)
    with jax.set_mesh(stage_mesh):
        got = jax.jit(
            lambda p, x: pipeline_apply(
                block_fn, p, x, stage_mesh, batch_axes=("data",)
            )
        )(staged, x)
    np.testing.assert_allclose(ref, got, atol=1e-6)


def test_pipeline_gradients_match(stage_mesh):
    params, block_fn = _mlp_stack()
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(8, 4, 16)), jnp.float32)
    staged = split_layers_to_stages(params, 4)

    def loss_seq(params):
        return jnp.sum(jax.vmap(lambda xi: block_fn(params, xi))(x) ** 2)

    def loss_pp(staged):
        return jnp.sum(
            pipeline_apply(block_fn, staged, x, stage_mesh, batch_axes=("data",))
            ** 2
        )

    g_ref = jax.grad(loss_seq)(params)
    with jax.set_mesh(stage_mesh):
        g_pp = jax.jit(jax.grad(loss_pp))(staged)
    for k in ("w", "b"):
        np.testing.assert_allclose(
            g_ref[k].reshape(g_pp[k].shape), g_pp[k], atol=1e-4
        )


def test_pipeline_fewer_microbatches_than_stages(stage_mesh):
    """n_micro < n_stages: pipeline still correct (all-bubble edge case)."""
    params, block_fn = _mlp_stack()
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 4, 16)), jnp.float32)
    ref = jax.vmap(lambda xi: block_fn(params, xi))(x)
    staged = split_layers_to_stages(params, 4)
    with jax.set_mesh(stage_mesh):
        got = jax.jit(
            lambda p, x: pipeline_apply(
                block_fn, p, x, stage_mesh, batch_axes=("data",)
            )
        )(staged, x)
    np.testing.assert_allclose(ref, got, atol=1e-6)
