"""Logical-axis sharding rules: annotate once, let XLA insert collectives.

Arrays carry *logical* axis names; one rules table maps logical axes to
mesh axes. This is the scaling-book recipe (pick a mesh, annotate
shardings, let XLA do the rest) — no reference counterpart (SURVEY §2.13).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (or tuple of mesh axes, or None = replicated)
DEFAULT_RULES: Dict[str, Any] = {
    "batch": ("data", "fsdp"),
    "seq": "seq",           # activation sequence axis (context parallel)
    "embed": "fsdp",        # weight embed axis sharded over fsdp
    "heads": "model",       # attention heads: tensor parallel
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",         # ffn hidden: tensor parallel
    "vocab": "model",       # embedding/logits vocab axis
    "layers": None,         # stacked-layer leading axis: never sharded
    "expert": "model",      # MoE experts (expert parallel rides the model axis
                            # by default; override with a dedicated axis)
    "mlp_expert": None,     # per-expert ffn hidden: already sharded by expert
}


def logical_to_spec(
    logical_axes: Sequence[Optional[str]],
    rules: Optional[Dict[str, Any]] = None,
) -> P:
    """Map ('batch', 'seq', 'embed') -> PartitionSpec via the rules table."""
    rules = {**DEFAULT_RULES, **(rules or {})}
    entries = []
    for axis in logical_axes:
        if axis is None:
            entries.append(None)
        else:
            entries.append(rules.get(axis))
    return P(*entries)


def named_sharding(
    mesh: Mesh,
    logical_axes: Sequence[Optional[str]],
    rules: Optional[Dict[str, Any]] = None,
) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(logical_axes, rules))


def _is_axes_leaf(x: Any) -> bool:
    return x is None or (
        isinstance(x, tuple)
        and all(a is None or isinstance(a, str) for a in x)
    )


def param_shardings(
    logical_tree: Any,
    mesh: Mesh,
    rules: Optional[Dict[str, Any]] = None,
) -> Any:
    """The ``NamedSharding`` pytree for a pytree of logical axis tuples
    (``None`` leaf = replicated) — what ``shard_params`` places onto, and
    what a jitted init takes as ``out_shardings`` so every chip generates
    only its own shards."""
    return jax.tree.map(
        lambda axes: (
            NamedSharding(mesh, P())
            if axes is None
            else named_sharding(mesh, axes, rules)
        ),
        logical_tree,
        is_leaf=_is_axes_leaf,
    )


def shard_params(
    params: Any,
    logical_tree: Any,
    mesh: Mesh,
    rules: Optional[Dict[str, Any]] = None,
) -> Any:
    """Device-put a param pytree according to a parallel pytree of logical
    axis tuples (``None`` leaf = replicated)."""
    # Map over logical_tree FIRST so bare-None leaves ("replicated") are
    # honored — with params first, a None in the second tree would be
    # treated as an empty subtree and raise a structure mismatch.
    return jax.tree.map(
        lambda axes, sharding, leaf: jax.device_put(leaf, sharding),
        logical_tree,
        param_shardings(logical_tree, mesh, rules),
        params,
        is_leaf=_is_axes_leaf,
    )


def with_logical_constraint(
    x: jax.Array,
    logical_axes: Sequence[Optional[str]],
    mesh: Optional[Mesh] = None,
    rules: Optional[Dict[str, Any]] = None,
) -> jax.Array:
    """``lax.with_sharding_constraint`` by logical axes; no-op outside jit
    mesh contexts so model code runs unchanged on one device."""
    try:
        mesh = mesh or _current_mesh()
        if mesh is None or mesh.empty:
            return x
        return jax.lax.with_sharding_constraint(
            x, named_sharding(mesh, logical_axes, rules)
        )
    except (ValueError, RuntimeError):
        return x


def _current_mesh() -> Optional[Mesh]:
    try:
        from jax._src.mesh import thread_resources

        mesh = thread_resources.env.physical_mesh
        return None if mesh.empty else mesh
    except Exception:
        return None


# --------------------------------------------------------------------- #
# Serving KV-cache shardings (ISSUE 13: tensor-parallel serving)
# --------------------------------------------------------------------- #
#
# The engine's KV state is built OUTSIDE jit (ops/kvcache.py /
# ops/paged.py ``create``) and then donated through every dispatch, so
# its initial placement decides where the pool lives for the whole
# serving lifetime. Until ISSUE 13 the pool was created on the default
# device and XLA resharded it into whatever propagation chose on the
# first dispatch; these helpers give it an explicit layout instead:
#
# * dense cache panels [B, K, S, H]: slots shard over ``data``/``fsdp``
#   (each data group owns its slots' context — the dense capacity win),
#   kv-heads over ``model`` (each TP shard streams only its heads);
# * paged pool panels [K, pages, P, H]: kv-heads over ``model``. Pages
#   are a GLOBAL resource (any slot may hold any page), so the page dim
#   replicates over ``data`` — cross-replica data-parallel KV capacity
#   is the serving cell's job (distributed/cell.py), while the in-mesh
#   ``data`` axis parallelizes compute over slots;
# * per-slot control vectors ([B] lengths, decode/sampling state) stay
#   replicated: they are bytes, and sharding them buys collectives, not
#   capacity.
#
# Non-shardable shapes degrade per-axis (documented in
# docs/SERVING.md): a kv-head count that doesn't divide the ``model``
# extent replicates the head dim (weights still shard — GSPMD pads),
# and a slot count that doesn't divide the data extent replicates the
# slot dim.


def _divides(n: int, by: int) -> bool:
    return by > 1 and n % by == 0


def kv_shard_axes(
    mesh: Optional[Mesh],
    *,
    n_kv_heads: int,
    n_slots: int,
) -> Dict[str, Any]:
    """Which KV-cache dims can shard on ``mesh``: ``{"heads": mesh-axis
    or None, "slots": axis-tuple or None, "data_groups": int}``.
    ``data_groups`` is the number of independent admission groups the
    batcher runs over the batch axes (1 = no batch parallelism)."""
    out: Dict[str, Any] = {"heads": None, "slots": None, "data_groups": 1}
    if mesh is None or mesh.devices.size <= 1:
        return out
    shape = dict(mesh.shape)
    model = int(shape.get("model", 1))
    batch_axes = tuple(
        a for a in ("data", "fsdp") if int(shape.get(a, 1)) > 1
    )
    db = 1
    for a in batch_axes:
        db *= int(shape[a])
    if _divides(n_kv_heads, model):
        out["heads"] = "model"
    if batch_axes and _divides(n_slots, db):
        out["slots"] = batch_axes
        out["data_groups"] = db
    return out


def kv_cache_shardings(
    mesh: Optional[Mesh],
    cache: Any,
    *,
    n_kv_heads: int,
    n_slots: int,
) -> Optional[Any]:
    """A sharding pytree matching ``cache`` (``ops/kvcache.KVCache`` or
    ``ops/paged.PagedKVCache``): panel/scale leaves shard per
    :func:`kv_shard_axes`; ``lengths`` and any other per-slot vector
    replicate. None when the mesh gives nothing to shard."""
    axes = kv_shard_axes(mesh, n_kv_heads=n_kv_heads, n_slots=n_slots)
    if mesh is None or (axes["heads"] is None and axes["slots"] is None):
        return None
    paged = hasattr(cache, "num_pages")  # PagedKVCache vs KVCache
    head, slots = axes["heads"], axes["slots"]
    if paged:
        panel = P(head, None, None, None)       # [K, pages, P, H]
        scale = P(head, None, None)             # [K, pages, P]
    else:
        panel = P(slots, head, None, None)      # [B, K, S, H]
        scale = P(slots, head, None)            # [B, K, S]
    repl = NamedSharding(mesh, P())

    def _leaf(spec: P) -> NamedSharding:
        return NamedSharding(mesh, spec)

    layers = tuple(
        (_leaf(panel), _leaf(panel)) for _ in cache.layers
    )
    scales = (
        tuple((_leaf(scale), _leaf(scale)) for _ in cache.scales)
        if cache.scales is not None else None
    )
    return cache._replace(layers=layers, lengths=repl, scales=scales)


def place_kv_cache(
    cache: Any,
    mesh: Optional[Mesh],
    *,
    n_kv_heads: int,
    n_slots: int,
) -> Any:
    """Device-put a freshly created KV cache onto its serving layout
    (identity off-mesh). Donation-friendly: every later jitted dispatch
    sees inputs already in the layout propagation would choose, so the
    donated buffers alias instead of resharding."""
    shardings = kv_cache_shardings(
        mesh, cache, n_kv_heads=n_kv_heads, n_slots=n_slots
    )
    if shardings is None:
        return cache
    return jax.device_put(cache, shardings)


def validate_serving_mesh(
    mesh: Optional[Mesh],
    cfg: Any,
    n_slots: int,
) -> Dict[str, Any]:
    """Shardability report for an engine boot: which KV dims shard,
    which degrade to replication, and why — so a mis-shaped mesh logs
    one line at start instead of silently serving replicated KV.
    Returns ``{"kv_heads_sharded", "slots_sharded", "data_groups",
    "warnings": [...]}``."""
    report: Dict[str, Any] = {
        "kv_heads_sharded": False, "slots_sharded": False,
        "data_groups": 1, "warnings": [],
    }
    if mesh is None or mesh.devices.size <= 1:
        return report
    shape = dict(mesh.shape)
    model = int(shape.get("model", 1))
    axes = kv_shard_axes(
        mesh, n_kv_heads=cfg.n_kv_heads, n_slots=n_slots
    )
    report["kv_heads_sharded"] = axes["heads"] is not None
    report["slots_sharded"] = axes["slots"] is not None
    report["data_groups"] = axes["data_groups"]
    if model > 1 and axes["heads"] is None:
        report["warnings"].append(
            f"n_kv_heads={cfg.n_kv_heads} does not divide mesh "
            f"model={model}; KV panels replicate over the model axis "
            f"(weights still shard)"
        )
    if model > 1 and cfg.n_heads % model:
        report["warnings"].append(
            f"n_heads={cfg.n_heads} does not divide mesh model={model}; "
            f"attention-head sharding pads"
        )
    db = 1
    for a in ("data", "fsdp"):
        db *= int(shape.get(a, 1))
    if db > 1 and axes["slots"] is None:
        report["warnings"].append(
            f"n_slots={n_slots} does not divide the batch axes "
            f"(data*fsdp={db}); slot dim replicates and admission runs "
            f"a single group"
        )
    return report


def spec_tree_for(logical_tree: Any, rules: Optional[Dict[str, Any]] = None) -> Any:
    """Parallel pytree of PartitionSpecs (for pjit in/out shardings)."""
    return jax.tree.map(
        lambda axes: P() if axes is None else logical_to_spec(axes, rules),
        logical_tree,
        is_leaf=lambda x: x is None or (isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x
        )),
    )
