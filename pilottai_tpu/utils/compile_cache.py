"""Persistent XLA compilation cache for warm engine restarts.

Every engine boot compiles the same programs: the prefill bucket ladder,
the fused decode chunk, the admission variants. That is minutes of dead
time per process start at 8B — paid again on every FaultTolerance
respawn and every worker redeploy unless the executables persist.

This module points JAX's persistent compilation cache at a durable
directory and exposes a hit counter so restart paths can *assert* they
reused it instead of hoping. Serving engines call
:func:`enable_compilation_cache` before their first dispatch
(``engine/native.py``); anything else (bench, trainers, workers) can
too — the cache is process-global and idempotent.

Where the directory is, in order:

1. ``JAX_COMPILATION_CACHE_DIR`` — JAX's own variable. When it is set
   the operator has placed the cache: that directory is used, and this
   module sets no directory in code (explicit arguments and the
   ``engine_compile_cache`` field included).
2. the explicit argument (``engine_compile_cache``);
3. ``<checkout>/.jax_cache`` — a fixed path next to the package, never
   the home directory, a temporary name, a pid or a time: the path is
   part of the cache key, so a directory that moves never hits.

Entries are keyed by program + topology + compiler version, so a stale
cache is never wrong, only useless. The autotune and profile stores
below live in the same directory.

No reference counterpart (the reference compiles nothing); this is
TPU-operational surface.
"""

from __future__ import annotations

import os
import threading
from pathlib import Path
from typing import Optional

from pilottai_tpu.utils.logging import get_logger
from pilottai_tpu.utils.metrics import global_metrics

_lock = threading.Lock()
_enabled_dir: Optional[str] = None
_listener_installed = False

HIT_METRIC = "engine.compile_cache_hits"
_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def default_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where set, else the fixed
    ``<checkout>/.jax_cache`` (the directory that holds the package)."""
    return os.environ.get(_ENV_DIR) or str(
        Path(__file__).resolve().parents[2] / ".jax_cache"
    )


def _install_hit_listener() -> None:
    """Count persistent-cache hits into the global metrics registry via
    jax's monitoring events (the only signal the cache exposes)."""
    global _listener_installed
    if _listener_installed:
        return
    import jax.monitoring

    def _on_event(name: str, **kwargs) -> None:
        if name == _HIT_EVENT:
            global_metrics.inc(HIT_METRIC)

    jax.monitoring.register_event_listener(_on_event)
    _listener_installed = True


def enable_compilation_cache(cache_dir: Optional[str] = None) -> Optional[str]:
    """Enable JAX's persistent compilation cache (idempotent; returns the
    active directory, or None when disabling failed/was requested).

    ``cache_dir`` of ``"off"`` disables nothing retroactively — callers
    that do not want the cache simply never call this."""
    global _enabled_dir
    if cache_dir == "off":
        return None
    with _lock:
        placed = os.environ.get(_ENV_DIR)
        path = placed or str(Path(cache_dir or default_cache_dir()))
        if _enabled_dir == path:
            _install_hit_listener()
            return path
        try:
            import jax

            Path(path).mkdir(parents=True, exist_ok=True)
            if not placed:
                jax.config.update("jax_compilation_cache_dir", path)
                # JAX decides once, at its first compile, whether the
                # cache is in use; a directory set after that takes
                # effect only after a reset.
                from jax.experimental.compilation_cache import (
                    compilation_cache,
                )

                compilation_cache.reset_cache()
            # Cache everything: a sub-second compile still costs more
            # than a disk read, and entry-size floors would silently
            # skip the small admission variants.
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        except Exception as exc:  # noqa: BLE001 — cache is an optimization
            get_logger("utils.compile_cache").warning(
                "persistent compilation cache unavailable: %s", exc
            )
            return None
        _enabled_dir = path
        _install_hit_listener()
        get_logger("utils.compile_cache").info(
            "persistent compilation cache at %s", path
        )
        return path


def cache_hits() -> int:
    return int(global_metrics.get(HIT_METRIC) or 0)


# --------------------------------------------------------------------- #
# Autotune results live NEXT TO the compiled executables: both are
# warm-restart state keyed by program shape, and a FaultTolerance respawn
# that reloads executables from here should reload the strip choice the
# executables were compiled WITH (re-timing would risk picking a different
# strip and recompiling the whole decode ladder it just restored).
#
# The same directory also carries the per-DEPLOYMENT workload profile
# store (``profiles.json``): fingerprints from obs/profile.py and the
# knob recommendations scripts/recommend.py derives from them. Both
# files share one merge-under-race discipline below — two replicas in a
# ServingCell point at one cache dir, and a plain read→merge→rename
# loses whichever writer renamed first.
# --------------------------------------------------------------------- #

_AUTOTUNE_FILE = "autotune.json"
_PROFILE_FILE = "profiles.json"
_STORE_RETRIES = 4
# Same-process writers (batcher tuner thread + profiler persist on the
# event loop) serialize here; the verify-own-key retry below only has to
# cover OTHER processes sharing the cache dir.
_STORE_LOCK = threading.Lock()


def _autotune_path() -> Path:
    return Path(_enabled_dir or default_cache_dir()) / _AUTOTUNE_FILE


def _profile_path() -> Path:
    return Path(_enabled_dir or default_cache_dir()) / _PROFILE_FILE


def _read_json_store(path: Path) -> dict:
    import json

    try:
        data = json.loads(path.read_text())
        return data if isinstance(data, dict) else {}
    except Exception:  # noqa: BLE001 — absence/corruption starts fresh
        return {}


def _store_json_key(path: Path, key: str, value) -> None:
    """Merge ``{key: value}`` into the JSON dict at ``path`` atomically.

    Write-temp + rename keeps readers torn-write-safe, but rename alone
    does not make read-modify-write safe: two replicas sharing the cache
    dir can both read, both merge their own key, and the second rename
    erases the first one's entry. So after renaming we re-read and
    verify OUR key landed; a concurrent winner that dropped it triggers
    a re-merge on top of the winner's file (bounded retries — this is a
    cache, livelock protection beats completeness)."""
    import json

    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(f".tmp{os.getpid()}-{threading.get_ident()}")
    with _STORE_LOCK:
        for _ in range(_STORE_RETRIES):
            data = _read_json_store(path)
            data[key] = value
            tmp.write_text(json.dumps(data, indent=2, sort_keys=True))
            tmp.replace(path)
            check = _read_json_store(path)
            if check.get(key) == value:
                return
    raise OSError(f"lost store race {_STORE_RETRIES}x on {path.name}:{key}")


def load_autotune(key: str) -> Optional[int]:
    """Best-effort read of a previously tuned integer for ``key``."""
    try:
        val = _read_json_store(_autotune_path()).get(key)
        return int(val) if val is not None else None
    except Exception:  # noqa: BLE001 — a missing/corrupt cache just re-tunes
        return None


def store_autotune(key: str, value: int) -> None:
    """Best-effort persist of a tuned integer under ``key``."""
    try:
        _store_json_key(_autotune_path(), key, int(value))
    except Exception as exc:  # noqa: BLE001 — tuning cache is an optimization
        get_logger("utils.compile_cache").warning(
            "autotune cache write failed: %s", exc
        )


def load_profile(key: str) -> Optional[dict]:
    """Best-effort read of the stored profile/recommendation blob for a
    deployment ``key`` (a dict as stored; None when absent/corrupt)."""
    try:
        val = _read_json_store(_profile_path()).get(key)
        return dict(val) if isinstance(val, dict) else None
    except Exception:  # noqa: BLE001 — profile store is advisory
        return None


def store_profile(key: str, value: dict) -> None:
    """Best-effort persist of a deployment profile blob under ``key``
    (same atomic merge-under-race discipline as the autotune store)."""
    try:
        _store_json_key(_profile_path(), key, dict(value))
    except Exception as exc:  # noqa: BLE001 — profile store is advisory
        get_logger("utils.compile_cache").warning(
            "profile store write failed: %s", exc
        )


__all__ = ["enable_compilation_cache", "cache_hits", "default_cache_dir",
           "load_autotune", "store_autotune", "load_profile",
           "store_profile", "HIT_METRIC"]
