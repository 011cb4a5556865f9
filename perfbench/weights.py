"""The model's shape as the benchmark reads it from a configuration file, and
the weights it makes from ``--seed``.

The weights ARE int8 values times a bfloat16 scale per output channel, so the
served int8 tree and the float32 reference hold exactly the same numbers and
no quantisation choice stands between them. Every layer's leaves come from
``fold_in(fold_in(key, layer), leaf)``: the launcher makes the whole stack in
one jitted call (``lax.map`` over layers, so the temporaries are one layer's),
and the reference makes one layer at a time from the same function.

Nothing here imports the program; the launcher wraps the arrays in the
program's ``QTensor`` container.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Dict

ROOT = Path(__file__).resolve().parent

# Standard deviation of an integer drawn uniformly from [-127, 127].
_INT8_STD = (127 * 128 / 3) ** 0.5


@dataclasses.dataclass(frozen=True)
class Model:
    name: str
    hidden: int
    ffn: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    experts: int          # 0 = dense MLP
    experts_per_tok: int
    rms_eps: float
    rope_theta: float
    max_positions: int

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim


def load_config(name: str) -> Dict[str, Any]:
    """``configs/<name>.json`` as a dict (the file BENCHMARK.json names); a
    name that ends in ``.json`` is a path (the tests' tiny shapes)."""
    path = Path(name) if name.endswith(".json") else ROOT / "configs" / f"{name}.json"
    return json.loads(path.read_text())


def model_from_config(cfg: Dict[str, Any]) -> Model:
    if cfg.get("sliding_window"):
        raise ValueError("a uniform sliding window is not expressible here")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("tied embeddings are not generated here")
    heads = int(cfg["num_attention_heads"])
    return Model(
        name=str(cfg["name"]),
        hidden=int(cfg["hidden_size"]),
        ffn=int(cfg["intermediate_size"]),
        layers=int(cfg["num_hidden_layers"]),
        heads=heads,
        kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg.get("head_dim") or cfg["hidden_size"] // heads),
        vocab=int(cfg["vocab_size"]),
        experts=int(cfg.get("num_local_experts", 0)),
        experts_per_tok=int(cfg.get("num_experts_per_tok", 0)),
        rms_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]),
        max_positions=int(cfg["max_position_embeddings"]),
    )


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


def layer_leaves(m: Model, key, layer) -> Dict[str, Any]:
    """One layer's leaves: ``{"ln1", "ln2", "wq", "wk", "wv", "wo", and
    "wg", "wu", "wd" [+ "router"]}``; a weight is an ``(int8, scale)`` pair.
    ``layer`` may be traced."""
    import jax
    import jax.numpy as jnp

    lk = jax.random.fold_in(key, layer + 1)
    k = [jax.random.fold_in(lk, i) for i in range(10)]
    E, F = m.hidden, m.ffn
    out: Dict[str, Any] = {
        "ln1": _norm_scale(k[0], E),
        "ln2": _norm_scale(k[1], E),
        "wq": _qleaf(k[2], (E, m.q_dim), E),
        "wk": _qleaf(k[3], (E, m.kv_dim), E),
        "wv": _qleaf(k[4], (E, m.kv_dim), E),
        "wo": _qleaf(k[5], (m.q_dim, E), m.q_dim),
    }
    if m.experts:
        X = m.experts

        def expert(x):
            ek = jax.random.fold_in(k[6], x)
            g, u, d = (jax.random.fold_in(ek, i) for i in range(3))
            return (_qleaf(g, (E, F), E), _qleaf(u, (E, F), E),
                    _qleaf(d, (F, E), F))

        out["wg"], out["wu"], out["wd"] = jax.lax.map(expert, jnp.arange(X))
        out["router"] = (
            jax.random.normal(k[9], (E, X), dtype=jnp.float32) * E ** -0.5
        ).astype(jnp.bfloat16)
    else:
        out["wg"] = _qleaf(k[6], (E, F), E)
        out["wu"] = _qleaf(k[7], (E, F), E)
        out["wd"] = _qleaf(k[8], (F, E), F)
    return out


def outer_leaves(m: Model, key) -> Dict[str, Any]:
    """Embedding (bfloat16), final norm, and the untied head (int8 pair)."""
    import jax
    import jax.numpy as jnp

    ke, kn, kh = (jax.random.fold_in(key, 1_000_000 + i) for i in range(3))
    return {
        "embed": jax.random.normal(
            ke, (m.vocab, m.hidden), dtype=jnp.float32
        ).astype(jnp.bfloat16),
        "final_norm": _norm_scale(kn, m.hidden),
        "lm_head": _qleaf(kh, (m.hidden, m.vocab), m.hidden),
    }


def make_stack(m: Model, seed: int) -> Dict[str, Any]:
    """The whole model in one jitted call: ``{"outer": ..., "layers": ...}``
    with every layer leaf stacked on a leading ``[layers]`` axis."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def build(key):
        layers = jax.lax.map(
            lambda l: layer_leaves(m, key, l), jnp.arange(m.layers)
        )
        return {"outer": outer_leaves(m, key), "layers": layers}

    return build(seed_key(seed))
