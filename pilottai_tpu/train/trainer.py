"""Mesh-sharded training loop: loss, optimizer, jitted train step.

The reference has **no training path at all** (SURVEY.md §1 "What the
reference is NOT" — it is an asyncio orchestration layer over remote LLM
APIs). Training is introduced by the TPU north star: agents fine-tuned
in-tree must run the same sharded compute path the serving engine uses.

Design (scaling-book recipe):
* one 4-axis ``Mesh`` (data/fsdp/model/seq — ``parallel/mesh.py``),
* parameters placed by logical-axis rules (``parallel/sharding.py``),
* the train step is a single ``jax.jit`` with donated state; XLA inserts
  the gradient psum over data/fsdp and the TP all-reduces over ICI,
* ``jax.checkpoint`` remat inside the layer scan trades FLOPs for HBM.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pilottai_tpu.models.common import ModelConfig, init_params, param_logical_axes
from pilottai_tpu.models.transformer import forward_train
from pilottai_tpu.parallel.mesh import create_mesh
from pilottai_tpu.parallel.sharding import (
    logical_to_spec,
    shard_params,
    spec_tree_for,
)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    grad_clip: float = 1.0
    remat: bool = True
    param_dtype: Any = jnp.float32  # master weights fp32; compute casts to bf16
    # Ring attention over the mesh's 'seq' axis (context parallelism) —
    # K/V chunks rotate over ICI instead of XLA all-gathering them.
    context_parallel: bool = False
    # Weight on the MoE load-balancing auxiliary loss (Switch-style);
    # ignored for dense models.
    moe_aux_weight: float = 0.01


def make_optimizer(tc: TrainConfig) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=tc.learning_rate,
        warmup_steps=tc.warmup_steps,
        decay_steps=max(tc.total_steps, tc.warmup_steps + 1),
        end_value=tc.learning_rate * 0.1,
    )
    return optax.chain(
        optax.clip_by_global_norm(tc.grad_clip),
        optax.adamw(
            schedule, b1=tc.b1, b2=tc.b2, weight_decay=tc.weight_decay
        ),
    )


def next_token_loss(
    logits: jax.Array,   # [B, T, V] fp32
    tokens: jax.Array,   # [B, T]
    valid: jax.Array,    # [B]
    loss_start: Optional[jax.Array] = None,  # [B] first TARGET index
) -> jax.Array:
    """Mean next-token cross-entropy over valid (non-pad) positions.

    ``loss_start[b]`` masks the loss to predictions of tokens at indices
    >= loss_start[b] — prompt-masked supervised fine-tuning (the protocol
    model learns the *response*, not to model its own prompts). None (or
    zeros) is plain LM loss over the whole row.
    """
    T = tokens.shape[1]
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    pos = jnp.arange(T - 1)[None, :]  # position i predicts token i+1
    mask = (pos < (valid - 1)[:, None]).astype(jnp.float32)
    if loss_start is not None:
        mask = mask * (pos + 1 >= loss_start[:, None]).astype(jnp.float32)
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


class Trainer:
    """Owns mesh, sharded state and the compiled train step.

    Usage::

        t = Trainer(model_cfg, TrainConfig(), mesh=my_mesh)
        state = t.init(jax.random.key(0))
        state, metrics = t.step(state, batch)   # batch: tokens/valid
    """

    def __init__(
        self,
        model_cfg: ModelConfig,
        train_cfg: Optional[TrainConfig] = None,
        mesh: Optional[Mesh] = None,
        rules: Optional[Dict[str, Any]] = None,
    ) -> None:
        self.model_cfg = model_cfg
        self.train_cfg = train_cfg or TrainConfig()
        self.mesh = mesh if mesh is not None else create_mesh()
        self.rules = rules
        self.optimizer = make_optimizer(self.train_cfg)
        self._param_axes = param_logical_axes(model_cfg)
        self._param_specs = spec_tree_for(self._param_axes, rules)
        self._opt_shardings_tree = None
        self._step = self._build_step()

    # ------------------------------------------------------------- #
    # State init
    # ------------------------------------------------------------- #
    def init(self, rng: jax.Array) -> Tuple[Any, Any]:
        """Initialize (params, opt_state), placed on the mesh.

        Params are constrained to their logical shardings inside jit so
        the fp32 master copy is materialized already-sharded (never one
        full replica per host); optimizer moments inherit the same
        placement through XLA's sharding propagation.
        """
        cfg, tc = self.model_cfg, self.train_cfg
        param_shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self._param_specs
        )

        def _init(rng):
            params = init_params(cfg, rng, dtype=tc.param_dtype)
            params = jax.lax.with_sharding_constraint(params, param_shardings)
            opt_state = self.optimizer.init(params)
            return params, opt_state

        # Pin the opt-state layout here AND on the train step's outputs:
        # leaving it unspecified lets the two jitted programs pick
        # different layouts, and the step's donated state then fails
        # aliasing at dispatch (jax 0.4.x rejects it; newer jax silently
        # copies — either way the donation is lost).
        with jax.set_mesh(self.mesh):
            return jax.jit(
                _init,
                out_shardings=(param_shardings, self._opt_shardings()),
            )(rng)

    # ------------------------------------------------------------- #
    # Train step
    # ------------------------------------------------------------- #
    def _build_step(self):
        cfg, tc = self.model_cfg, self.train_cfg
        optimizer = self.optimizer
        compute_dtype = cfg.dtype

        ring_mesh = (
            self.mesh
            if tc.context_parallel and self.mesh.shape.get("seq", 1) > 1
            else None
        )
        # No sequence sharding → the Pallas flash kernel (fwd + bwd) runs
        # per-shard under shard_map on TPU meshes; ring attention owns the
        # seq-sharded case. _full_seq_block falls back to XLA dense when
        # off-TPU or shapes don't divide.
        flash_mesh = (
            self.mesh
            if ring_mesh is None and self.mesh.devices.size > 1
            else None
        )

        def train_step(params, opt_state, tokens, valid, loss_start):
            B, T = tokens.shape
            positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))

            def loss_fn(p):
                compute_p = jax.tree.map(
                    lambda a: a.astype(compute_dtype)
                    if jnp.issubdtype(a.dtype, jnp.floating)
                    else a,
                    p,
                )
                logits, moe_aux = forward_train(
                    compute_p, cfg, tokens, positions, valid,
                    remat=tc.remat, ring_mesh=ring_mesh,
                    flash_mesh=flash_mesh,
                )
                lm_loss = next_token_loss(logits, tokens, valid, loss_start)
                return lm_loss + tc.moe_aux_weight * moe_aux, (lm_loss, moe_aux)

            (loss, (lm_loss, moe_aux)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            metrics = {
                "loss": lm_loss,
                "total_loss": loss,
                "moe_aux": moe_aux,
                "grad_norm": optax.global_norm(grads),
                "tokens": jnp.sum(valid).astype(jnp.float32),
            }
            return params, opt_state, metrics

        batch_spec = logical_to_spec(("batch", "seq"), self.rules)
        valid_spec = logical_to_spec(("batch",), self.rules)
        param_shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self._param_specs
        )
        return jax.jit(
            train_step,
            in_shardings=(
                param_shardings,
                self._opt_shardings(),  # must match init's output layout
                NamedSharding(self.mesh, batch_spec),
                NamedSharding(self.mesh, valid_spec),
                NamedSharding(self.mesh, valid_spec),  # loss_start
            ),
            # Pin output params AND opt state to the same placement as
            # the inputs so the donated state aliases cleanly and
            # round-trips through step() without resharding.
            out_shardings=(param_shardings, self._opt_shardings(), None),
            donate_argnums=(0, 1),
        )

    def _opt_shardings(self):
        """NamedShardings for the optimizer state: moment leaves mirror
        their parameters' shardings (``optax.tree_map_params`` walks the
        state's param-shaped subtrees), everything else — step counts,
        empty states — replicates. One tree shared by ``init`` and the
        train step keeps the donated state's layout bit-stable across
        both programs."""
        if self._opt_shardings_tree is None:
            param_shardings = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), self._param_specs
            )
            shapes = jax.eval_shape(
                lambda r: init_params(
                    self.model_cfg, r, dtype=self.train_cfg.param_dtype
                ),
                jax.random.key(0),
            )
            opt_shape = jax.eval_shape(self.optimizer.init, shapes)
            repl = NamedSharding(self.mesh, P())
            self._opt_shardings_tree = optax.tree_map_params(
                self.optimizer,
                lambda _leaf, sharding: sharding,
                opt_shape,
                param_shardings,
                transform_non_params=lambda _leaf: repl,
            )
        return self._opt_shardings_tree

    def step(
        self, state: Tuple[Any, Any], batch: Dict[str, jax.Array]
    ) -> Tuple[Tuple[Any, Any], Dict[str, jax.Array]]:
        params, opt_state = state
        tokens, valid, loss_start = self.shard_batch(batch)
        with jax.set_mesh(self.mesh):
            params, opt_state, metrics = self._step(
                params, opt_state, tokens, valid, loss_start
            )
        return (params, opt_state), metrics

    def shard_batch(
        self, batch: Dict[str, Any]
    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        tokens = jnp.asarray(batch["tokens"], jnp.int32)
        valid = jnp.asarray(batch["valid"], jnp.int32)
        loss_start = jnp.asarray(
            batch.get("loss_start", np.zeros(tokens.shape[0])), jnp.int32
        )
        tok_sh = NamedSharding(self.mesh, logical_to_spec(("batch", "seq"), self.rules))
        val_sh = NamedSharding(self.mesh, logical_to_spec(("batch",), self.rules))
        return (
            jax.device_put(tokens, tok_sh),
            jax.device_put(valid, val_sh),
            jax.device_put(loss_start, val_sh),
        )


def synthetic_batches(
    model_cfg: ModelConfig,
    batch_size: int,
    seq_len: int,
    seed: int = 0,
) -> Iterator[Dict[str, np.ndarray]]:
    """Deterministic synthetic LM batches for benches and tests."""
    rng = np.random.default_rng(seed)
    while True:
        yield {
            "tokens": rng.integers(
                0, model_cfg.vocab_size, size=(batch_size, seq_len), dtype=np.int32
            ),
            "valid": np.full((batch_size,), seq_len, dtype=np.int32),
        }
