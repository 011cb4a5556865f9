"""What every architecture's weights are made with: the configuration file as
the benchmark reads it, the key from ``--seed``, and the two kinds of leaf.

A weight IS int8 values times a bfloat16 scale per output channel, so the
served int8 tree and the float32 reference hold exactly the same numbers and
no quantisation choice stands between them. Which leaves a model has, in how
many stacks, is its module's business (``perfbench/archs/<arch>.py``, named by
the configuration's ``"arch"`` key; ``archs/__init__.py`` lists what a module
gives).

Nothing here imports the program.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict

from perfbench import archs

ROOT = Path(__file__).resolve().parent

# Standard deviation of an integer drawn uniformly from [-127, 127].
_INT8_STD = (127 * 128 / 3) ** 0.5


def load_config(name: str) -> Dict[str, Any]:
    """``configs/<name>.json`` as a dict (the file BENCHMARK.json names); a
    name that ends in ``.json`` is a path (the tests' tiny shapes). Fails on
    a key that neither the harness nor the file's architecture module knows;
    an ``arch`` that is a path is made absolute from the file's directory."""
    path = Path(name) if name.endswith(".json") else ROOT / "configs" / f"{name}.json"
    cfg = json.loads(path.read_text())
    arch = cfg.get("arch")
    if isinstance(arch, str) and arch.endswith(".py"):
        cfg["arch"] = arch = str((path.resolve().parent / arch).resolve())
    unread = archs.unread_keys(cfg, archs.load(arch))
    if unread:
        raise ValueError(
            f"{path}: nothing reads the key(s) {unread}: the module {arch!r} lists what it "
            "reads under READS and what changes nothing, with the reason, under IGNORES")
    return cfg


def model_from_config(cfg: Dict[str, Any]):
    """The model as the configuration's own module shapes it."""
    return archs.load(cfg.get("arch")).model_from_config(cfg)


def seed_key(seed: int):
    """A key from any whole number up to 2**63 (the driver's seeds pass
    2**31): the low 31 bits seed it, the rest is folded in."""
    import jax

    seed = int(seed)
    if seed < 0:
        raise ValueError("--seed must not be negative")
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def _qleaf(key, shape, fan_in):
    """(int8 values, bfloat16 scale [..., 1, out]) with the product's
    standard deviation at ``fan_in ** -0.5``."""
    import jax
    import jax.numpy as jnp

    kq, ks = jax.random.split(key)
    bits = jax.random.bits(kq, shape, dtype=jnp.uint8)
    q = jnp.clip(bits.astype(jnp.int32) - 128, -127, 127).astype(jnp.int8)
    spread = 0.75 + 0.5 * jax.random.uniform(
        ks, shape[:-2] + (1, shape[-1]), dtype=jnp.float32
    )
    s = (spread * (fan_in ** -0.5 / _INT8_STD)).astype(jnp.bfloat16)
    return q, s


def _norm_scale(key, n):
    import jax
    import jax.numpy as jnp

    return (
        0.75 + 0.5 * jax.random.uniform(key, (n,), dtype=jnp.float32)
    ).astype(jnp.bfloat16)
