"""Device mesh construction for the engine and trainer.

Axes (superset; size-1 axes cost nothing under XLA):

* ``data``  — data parallel (batch replicas; gradients psum over it)
* ``fsdp``  — parameter/optimizer sharding (weights gathered per layer)
* ``model`` — tensor parallel (heads / ffn sharded; activations
  all-reduced over ICI)
* ``seq``   — sequence/context parallel (ring attention over ICI)

No reference counterpart (SURVEY.md §2.13). Multi-host: `initialize()`
wraps ``jax.distributed.initialize`` so the same mesh spans hosts over DCN.
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_NAMES = ("data", "fsdp", "model", "seq")


@dataclass(frozen=True)
class MeshConfig:
    data: int = 1
    fsdp: int = 1
    model: int = 1
    seq: int = 1

    @property
    def shape(self) -> Dict[str, int]:
        return {"data": self.data, "fsdp": self.fsdp, "model": self.model, "seq": self.seq}

    @property
    def n_devices(self) -> int:
        return self.data * self.fsdp * self.model * self.seq

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, int]]) -> "MeshConfig":
        if not d:
            return cls()
        return cls(**{k: int(v) for k, v in d.items() if k in AXIS_NAMES})


def create_mesh(
    config: Optional[MeshConfig] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a 4-axis mesh over ``devices`` (default: all local devices).

    Device order follows jax.devices(), which on TPU respects the physical
    torus ordering so the innermost axis (``model``) lands on the
    fastest-ICI neighbors.
    """
    devices = list(devices if devices is not None else jax.devices())
    config = config or best_mesh_config(len(devices))
    if config.n_devices > len(devices):
        raise ValueError(
            f"mesh {config.shape} needs {config.n_devices} devices, "
            f"only {len(devices)} available"
        )
    devices = devices[: config.n_devices]
    grid = np.asarray(devices).reshape(config.data, config.fsdp, config.model, config.seq)
    return Mesh(grid, AXIS_NAMES)


def best_mesh_config(n_devices: int, tp_max: int = 8) -> MeshConfig:
    """Default layout: fill tensor parallel up to ``tp_max`` (keeps the
    all-reduce inside one slice's ICI), spread the rest over data."""
    model = math.gcd(n_devices, tp_max)
    data = n_devices // model
    return MeshConfig(data=data, model=model)


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host bring-up over DCN (reference has no equivalent — its
    "distributed" is one asyncio loop, SURVEY.md §2.14).

    No-ops when single-process or when jax.distributed is already live, so
    it is safe to call unconditionally at engine start.
    """
    if num_processes in (None, 1) and "JAX_COORDINATOR_ADDRESS" not in os.environ:
        return
    # Only double-initialization is ignorable. A genuine bring-up failure
    # (unreachable coordinator, wrong world size) must be LOUD — swallowing
    # it would let each host proceed with a local-only mesh and silently
    # inconsistent sharding.
    if jax.distributed.is_initialized():
        return
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    except RuntimeError as exc:
        if "already initialized" in str(exc).lower():
            return
        raise
