"""Persistent compilation cache: warm restarts reuse compiled programs.

Every process start used to recompile the whole engine (minutes at 8B),
so FaultTolerance's respawn story cost minutes of dead time. The restart
path must provably hit the on-disk cache — asserted via the hit counter,
not wall-clock (CI machines are noisy) — and the cache must be placeable
from outside: ``JAX_COMPILATION_CACHE_DIR`` where set, else the fixed
``<checkout>/.jax_cache``.
"""

import json
import os
import subprocess
import sys

import pytest

from pilottai_tpu.utils.compile_cache import (
    cache_hits,
    enable_compilation_cache,
)

_BOOT = r"""
import asyncio, json, os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
from pilottai_tpu.core.config import LLMConfig
from pilottai_tpu.engine.handler import LLMHandler
from pilottai_tpu.engine.types import GenerationParams
from pilottai_tpu.utils.compile_cache import cache_hits

async def main():
    h = LLMHandler(LLMConfig(
        model_name="llama-tiny", provider="cpu", engine_slots=2,
        engine_max_seq=128, engine_chunk=4, dtype="float32",
        engine_compile_cache=sys.argv[1],
    ))
    t0 = time.perf_counter()
    await h.start()
    up = time.perf_counter() - t0
    out = await h.apredict(
        "hello", params=GenerationParams(max_new_tokens=4, temperature=0.0)
    )
    await h.stop()
    print(json.dumps({"up": up, "hits": cache_hits(), "ok": len(out) >= 0}))

asyncio.run(main())
"""


def _boot(cache_dir: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(__file__))
    env.pop("XLA_FLAGS", None)  # single-device process, like a respawn
    # The explicit directory is under test: no placement from outside.
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-c", _BOOT, cache_dir],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_respawned_engine_reuses_cache(tmp_path):
    """Process 1 populates the cache; process 2 — the FaultTolerance
    respawn / worker-redeploy shape — must record persistent-cache hits
    while producing a working engine."""
    cache = str(tmp_path / "xla-cache")
    cold = _boot(cache)
    assert cold["ok"]
    assert os.listdir(cache), "first boot persisted nothing"
    warm = _boot(cache)
    assert warm["ok"]
    assert warm["hits"] > 0, (
        f"respawned engine recompiled everything (cold {cold}, warm {warm})"
    )


def test_adaptive_chunk_buckets_bound_decode_executables():
    """Compile-cache tripwire for adaptive chunk scheduling: the bucket
    ladder is the ONLY degree of freedom the scheduler has, so a config
    with one prefix-bound rung must compile at most len(chunk_buckets)
    decode executables no matter how budgets vary — an unquantized pick
    (or a bucket set that grows with traffic) would thrash the compile
    cache with one executable per distinct length."""
    import jax
    import jax.numpy as jnp

    from pilottai_tpu.engine import decode
    from pilottai_tpu.engine.batcher import ContinuousBatcher, GenRequest
    from pilottai_tpu.models.common import init_params
    from pilottai_tpu.models.registry import get_model_config

    cfg = get_model_config("llama-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    # max_seq 64 keeps _decode_bucket on a single rung, so the only
    # static-axis variation left is the chunk bucket itself.
    batcher = ContinuousBatcher(
        cfg, params, n_slots=2, max_seq_len=64, cache_dtype=jnp.float32,
        chunk_size=8, chunk_policy="adaptive", chunk_buckets=(2, 4, 8),
        prefix_cache=0, use_pallas=False,
    )
    decode.decode_chunk._clear_cache()
    batcher.start()
    try:
        # Warmup's compile sweep covers every bucket...
        batcher.warmup(prompt_lens=(8,))
        after_warmup = decode.decode_chunk._cache_size()
        # ...and varied serve-time budgets may only ever re-hit them.
        for mnt in (2, 3, 5, 7, 9, 12, 17):
            req = GenRequest(
                prompt_ids=list(range(3, 3 + (mnt % 5) + 2)),
                max_new_tokens=mnt,
            )
            batcher.submit(req).result(timeout=120)
    finally:
        batcher.stop()
    n_exec = decode.decode_chunk._cache_size()
    assert after_warmup == len(batcher.chunk_buckets), (
        f"warmup compiled {after_warmup} decode executables, expected "
        f"one per bucket {batcher.chunk_buckets}"
    )
    assert n_exec <= len(batcher.chunk_buckets), (
        f"{n_exec} decode executables for bucket set "
        f"{batcher.chunk_buckets}: adaptive chunking is leaking compiles"
    )


@pytest.fixture()
def _cache_state(monkeypatch):
    """``(compile_cache module, {jax config name: value set in code})``
    for a resolution-order case, with both variables unset. Leaves the
    process as it was: it runs the rest of the suite, and must not keep
    the cache pointed at a tmp dir pytest is about to delete."""
    import jax

    import pilottai_tpu.utils.compile_cache as cc

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("PILOTTAI_COMPILE_CACHE", raising=False)
    monkeypatch.setattr(cc, "_enabled_dir", None)
    prev_dir = jax.config.jax_compilation_cache_dir
    updates = {}
    real_update = jax.config.update

    def recording_update(name, val):
        updates[name] = val
        real_update(name, val)

    monkeypatch.setattr(jax.config, "update", recording_update)
    yield cc, updates
    real_update("jax_compilation_cache_dir", prev_dir)


def test_placed_from_outside_uses_that_dir_and_sets_none_in_code(
    _cache_state, tmp_path, monkeypatch
):
    """``JAX_COMPILATION_CACHE_DIR`` set: that directory wins over the
    argument (the ``engine_compile_cache`` field), the autotune and
    profile stores follow it, and no directory is set in code."""
    cc, updates = _cache_state
    placed = tmp_path / "placed"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(placed))
    assert enable_compilation_cache(str(tmp_path / "field")) == str(placed)
    assert enable_compilation_cache() == str(placed)
    assert "jax_compilation_cache_dir" not in updates
    assert placed.is_dir() and not (tmp_path / "field").exists()
    assert cc.default_cache_dir() == str(placed)
    cc.store_autotune("k", 4)
    cc.store_profile("dep", {"a": 1})
    assert (placed / "autotune.json").exists()
    assert (placed / "profiles.json").exists()
    assert cc.load_autotune("k") == 4


def test_unset_defaults_to_checkout_jax_cache_and_old_variable_is_ignored(
    _cache_state, tmp_path, monkeypatch
):
    """Unset: the fixed ``<checkout>/.jax_cache`` — never the home
    directory, a temporary name, a pid or a time — and the retired
    ``PILOTTAI_COMPILE_CACHE`` changes nothing."""
    cc, updates = _cache_state
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(checkout, ".jax_cache")
    assert cc.default_cache_dir() == want
    monkeypatch.setenv("PILOTTAI_COMPILE_CACHE", str(tmp_path / "old"))
    monkeypatch.setenv("HOME", str(tmp_path / "home"))
    assert cc.default_cache_dir() == want
    assert enable_compilation_cache() == want
    assert updates["jax_compilation_cache_dir"] == want
    assert not (tmp_path / "old").exists() and not (tmp_path / "home").exists()
    assert str(cc._autotune_path()) == os.path.join(want, "autotune.json")


def test_enable_is_idempotent_and_off_disables(tmp_path, monkeypatch):
    import jax

    import pilottai_tpu.utils.compile_cache as cc

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_enabled = cc._enabled_dir
    d = str(tmp_path / "cc")
    try:
        assert enable_compilation_cache("off") is None
        p1 = enable_compilation_cache(d)
        p2 = enable_compilation_cache(d)
        assert p1 == p2 == d
        assert isinstance(cache_hits(), int)
    finally:
        # This process runs the rest of the suite: don't leave the cache
        # pointed at a tmp dir pytest is about to delete.
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        cc._enabled_dir = prev_enabled
