"""Ring attention (context parallelism) on the virtual 8-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pilottai_tpu.models.registry import get_model_config
from pilottai_tpu.ops.attention import dot_product_attention, make_attention_mask
from pilottai_tpu.parallel.mesh import MeshConfig, create_mesh
from pilottai_tpu.parallel.ring_attention import ring_attention
from pilottai_tpu.train import Trainer, TrainConfig, synthetic_batches


@pytest.fixture(scope="module")
def mesh():
    return create_mesh(MeshConfig(data=2, model=2, seq=2))


@pytest.fixture(scope="module")
def mesh_seq4():
    return create_mesh(MeshConfig(data=2, seq=4))


def _setup(B=4, T=64, N=4, K=2, H=16, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, T, N, H)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, K, H)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, K, H)), jnp.float32)
    ps = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T)).astype(jnp.int32)
    return q, k, v, ps


@pytest.mark.parametrize("window,softcap", [(0, 0.0), (16, 0.0), (0, 30.0)])
def test_ring_matches_reference(mesh, window, softcap):
    q, k, v, ps = _setup()
    T, H = q.shape[1], q.shape[3]
    valid = jnp.asarray([64, 50, 64, 40], jnp.int32)
    mask = make_attention_mask(ps, T, valid, window=window)
    ref = dot_product_attention(
        q, k, v, mask=mask, scale=H**-0.5, logit_softcap=softcap
    )
    with jax.set_mesh(mesh):
        got = jax.jit(
            lambda *a: ring_attention(
                *a, scale=H**-0.5, softcap=softcap, mesh=mesh
            )
        )(q, k, v, ps, valid, jnp.int32(window))
    for b in range(4):
        n = int(valid[b])
        np.testing.assert_allclose(ref[b, :n], got[b, :n], atol=1e-5, rtol=1e-5)


def test_ring_four_way(mesh_seq4):
    q, k, v, ps = _setup(T=128)
    T, H = q.shape[1], q.shape[3]
    valid = jnp.full((4,), T, jnp.int32)
    mask = make_attention_mask(ps, T, valid)
    ref = dot_product_attention(q, k, v, mask=mask, scale=H**-0.5)
    with jax.set_mesh(mesh_seq4):
        got = jax.jit(
            lambda *a: ring_attention(*a, scale=H**-0.5, mesh=mesh_seq4)
        )(q, k, v, ps, valid, jnp.int32(0))
    np.testing.assert_allclose(ref, got, atol=1e-5, rtol=1e-5)


def test_ring_gradients_match(mesh):
    q, k, v, ps = _setup()
    T, H = q.shape[1], q.shape[3]
    valid = jnp.asarray([64, 50, 64, 40], jnp.int32)
    wmask = jnp.arange(T)[None, :, None, None] < valid[:, None, None, None]
    mask = make_attention_mask(ps, T, valid)

    def loss_ref(q, k, v):
        o = dot_product_attention(q, k, v, mask=mask, scale=H**-0.5)
        return jnp.sum((o * wmask) ** 2)

    def loss_ring(q, k, v):
        o = ring_attention(q, k, v, ps, valid, jnp.int32(0),
                           scale=H**-0.5, mesh=mesh)
        return jnp.sum((o * wmask) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    with jax.set_mesh(mesh):
        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_trainer_context_parallel_matches_dense(mesh):
    """Same seed, same batch: context-parallel loss == regular loss."""
    cfg = get_model_config("llama-tiny")
    batch = next(synthetic_batches(cfg, 4, 32))
    losses = {}
    for cp in (False, True):
        t = Trainer(
            cfg,
            TrainConfig(warmup_steps=1, total_steps=10, context_parallel=cp),
            mesh=mesh,
        )
        state = t.init(jax.random.key(0))
        _, m = t.step(state, batch)
        losses[cp] = float(m["loss"])
    assert np.isfinite(losses[True])
    np.testing.assert_allclose(losses[True], losses[False], rtol=2e-3)


def test_ring_flash_path_matches_reference(mesh):
    """The flash-in-ring path (per-step Pallas kernel + lse merge,
    VERDICT r2 next-step 8) — forced on with interpret mode on CPU —
    must match the dense oracle including ragged valid lengths."""
    q, k, v, ps = _setup()
    T, H = q.shape[1], q.shape[3]
    valid = jnp.asarray([64, 50, 64, 40], jnp.int32)
    mask = make_attention_mask(ps, T, valid)
    ref = dot_product_attention(q, k, v, mask=mask, scale=H**-0.5)
    with jax.set_mesh(mesh):
        got = jax.jit(
            lambda *a: ring_attention(
                *a, scale=H**-0.5, mesh=mesh,
                use_flash=True, interpret=True,
            )
        )(q, k, v, ps, valid, jnp.int32(0))
    for b in range(4):
        n = int(valid[b])
        np.testing.assert_allclose(ref[b, :n], got[b, :n], atol=1e-5, rtol=1e-5)


def test_ring_flash_gradients_match(mesh):
    """Training goes through the flash-in-ring path: gradients must match
    the dense reference (lse cotangents through the kernel VJP)."""
    q, k, v, ps = _setup()
    T, H = q.shape[1], q.shape[3]
    valid = jnp.asarray([64, 50, 64, 40], jnp.int32)
    wmask = jnp.arange(T)[None, :, None, None] < valid[:, None, None, None]
    mask = make_attention_mask(ps, T, valid)

    def loss_ref(q, k, v):
        o = dot_product_attention(q, k, v, mask=mask, scale=H**-0.5)
        return jnp.sum((o * wmask) ** 2)

    def loss_ring(q, k, v):
        o = ring_attention(q, k, v, ps, valid, jnp.int32(0),
                           scale=H**-0.5, mesh=mesh,
                           use_flash=True, interpret=True)
        return jnp.sum((o * wmask) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    with jax.set_mesh(mesh):
        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(a, b, atol=1e-3, rtol=1e-3)
