"""A test architecture, brought as new files alone, that ``archs/mistral.py``
cannot say: a sliding window on three layers of every four (``sliding_window``
with ``sliding_pattern``: every ``pattern``-th layer sees the whole context)
and a norm after attention and after the feed-forward (``post_norms``), both
of which the program's ``ModelConfig`` already has. It bears no model's name,
is no benchmark configuration and stands in no cell: it proves that the seam
(``perfbench/archs/__init__.py``) takes a second family without an edit.

Its own leaves (``ln1_post``, ``ln2_post``), its own reference (the window mask
by layer, the two further norms) and its own counts (a window layer's query
sees at most ``window`` keys). Dense SwiGLU only, no router: ``margin`` is inf.

``PERFBENCH_TINY_WINDOW_FORGET=1`` in the environment makes the reference
forget the window, for the one test that has to see such a reference come out
not correct; the harness knows nothing of it.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from perfbench.reference import PAD_TO, _deq, _mm, _rms, _rope
from perfbench.weights import _norm_scale, _qleaf, seed_key

READS = (
    "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "vocab_size", "rms_norm_eps", "rope_theta",
    "max_position_embeddings", "sliding_window", "sliding_pattern", "post_norms",
)
IGNORES: Dict[str, str] = {}


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
    window: int           # keys a window layer's query sees, itself included
    pattern: int          # every pattern-th layer is full
    rms_eps: float
    rope_theta: float
    max_positions: int

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def full_layers(self) -> int:
        return self.layers // self.pattern


def model_from_config(cfg: Dict[str, Any]) -> Model:
    if not cfg.get("post_norms"):
        raise ValueError("this test architecture is the one with post_norms")
    window, pattern = int(cfg["sliding_window"]), int(cfg["sliding_pattern"])
    if window <= 0 or pattern <= 1:
        raise ValueError("sliding_window and sliding_pattern (over 1) must both be given")
    heads = int(cfg["num_attention_heads"])
    return Model(
        name=str(cfg["name"]), hidden=int(cfg["hidden_size"]),
        ffn=int(cfg["intermediate_size"]), layers=int(cfg["num_hidden_layers"]),
        heads=heads, kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["hidden_size"]) // heads, vocab=int(cfg["vocab_size"]),
        window=window, pattern=pattern, rms_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]),
        max_positions=int(cfg["max_position_embeddings"]),
    )


NORMS = ("ln1", "ln1_post", "ln2", "ln2_post")
ATTN = ("wq", "wk", "wv", "wo")
MLP = ("wg", "wu", "wd")


def layer_leaves(m: Model, key, layer) -> Dict[str, Any]:
    """One layer's leaves; a weight is an ``(int8, scale)`` pair."""
    import jax

    lk = jax.random.fold_in(key, layer + 1)
    k = {n: jax.random.fold_in(lk, i) for i, n in enumerate(NORMS + ATTN + MLP)}
    E, F = m.hidden, m.ffn
    out: Dict[str, Any] = {n: _norm_scale(k[n], E) for n in NORMS}
    out["wq"] = _qleaf(k["wq"], (E, m.q_dim), E)
    out["wk"] = _qleaf(k["wk"], (E, m.kv_dim), E)
    out["wv"] = _qleaf(k["wv"], (E, m.kv_dim), E)
    out["wo"] = _qleaf(k["wo"], (m.q_dim, E), m.q_dim)
    out["wg"] = _qleaf(k["wg"], (E, F), E)
    out["wu"] = _qleaf(k["wu"], (E, F), E)
    out["wd"] = _qleaf(k["wd"], (F, E), F)
    return out


def outer_leaves(m: Model, key) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    ke, kn, kh = (jax.random.fold_in(key, 1_000_000 + i) for i in range(3))
    return {
        "embed": jax.random.normal(ke, (m.vocab, m.hidden), dtype=jnp.float32).astype(jnp.bfloat16),
        "final_norm": _norm_scale(kn, m.hidden),
        "lm_head": _qleaf(kh, (m.hidden, m.vocab), m.hidden),
    }


def make_stack(m: Model, seed: int) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def build(key):
        layers = jax.lax.map(lambda l: layer_leaves(m, key, l), jnp.arange(m.layers))
        return {"outer": outer_leaves(m, key), "layers": layers}

    return build(seed_key(seed))


def program_config(cfg: Dict[str, Any], m: Model):
    from pilottai_tpu.models.common import ModelConfig

    return ModelConfig(
        name=m.name, family="llama", vocab_size=m.vocab, hidden_size=m.hidden,
        n_layers=m.layers, n_heads=m.heads, n_kv_heads=m.kv_heads, head_dim=m.head_dim,
        intermediate_size=m.ffn, max_seq_len=m.max_positions, rope_theta=m.rope_theta,
        rms_eps=m.rms_eps, tie_embeddings=False, post_norms=True,
        sliding_window=m.window, sliding_pattern=m.pattern,
    )


def program_params(m: Model, seed: int, int8: bool) -> Dict[str, Any]:
    import jax.numpy as jnp

    from pilottai_tpu.models.quant import QTensor

    stack = make_stack(m, seed)

    def weight(pair):
        q, s = pair
        return QTensor(q=q, s=s) if int8 else q.astype(jnp.bfloat16) * s

    lay, outer = stack["layers"], stack["outer"]
    layers: Dict[str, Any] = {n: {"scale": lay[n]} for n in NORMS}
    layers["attn"] = {k: weight(lay[k]) for k in ATTN}
    layers["mlp"] = {k: weight(lay[k]) for k in MLP}
    return {
        "embed": outer["embed"], "layers": layers,
        "final_norm": {"scale": outer["final_norm"]},
        "lm_head": weight(outer["lm_head"]),
    }


def _attention(m: Model, q, k, v, window):
    """Causal grouped-query attention; ``window`` (traced) is 0 on a full
    layer, else the number of keys a query sees, itself included."""
    import jax
    import jax.numpy as jnp

    T = q.shape[0]
    rep = m.heads // m.kv_heads
    hi = jax.lax.Precision.HIGHEST
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = (i >= j) & ((window == 0) | (i - j < window))

    def group(args):
        qg, kg, vg = args
        s = jnp.einsum("tnd,sd->nts", qg, kg, precision=hi) * m.head_dim ** -0.5
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("nts,sd->tnd", p, vg, precision=hi)

    qg = q.reshape(T, m.kv_heads, rep, m.head_dim).transpose(1, 0, 2, 3)
    out = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2, 3).reshape(T, m.q_dim)


@functools.lru_cache(maxsize=None)
def _layer_fn(m: Model, mode: str, forget_window: bool):
    import jax
    import jax.numpy as jnp

    def layer(x, key, index):
        lw = layer_leaves(m, key, index)
        T = x.shape[0]
        window = jnp.where((index + 1) % m.pattern == 0, 0, 0 if forget_window else m.window)
        h = _rms(x, lw["ln1"], m.rms_eps)
        q = _mm(h, _deq(lw["wq"], mode), mode).reshape(T, m.heads, m.head_dim)
        k = _mm(h, _deq(lw["wk"], mode), mode).reshape(T, m.kv_heads, m.head_dim)
        v = _mm(h, _deq(lw["wv"], mode), mode).reshape(T, m.kv_heads, m.head_dim)
        attn = _attention(m, _rope(q, m.rope_theta), _rope(k, m.rope_theta), v, window)
        x = x + _rms(_mm(attn, _deq(lw["wo"], mode), mode), lw["ln1_post"], m.rms_eps)
        h = _rms(x, lw["ln2"], m.rms_eps)
        gate = jax.nn.silu(_mm(h, _deq(lw["wg"], mode), mode))
        out = _mm(gate * _mm(h, _deq(lw["wu"], mode), mode), _deq(lw["wd"], mode), mode)
        return x + _rms(out, lw["ln2_post"], m.rms_eps)

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(m: Model, mode: str):
    import jax

    def head(x, rows, key):
        outer = outer_leaves(m, key)
        h = _rms(x[rows], outer["final_norm"], m.rms_eps)
        return _mm(h, _deq(outer["lm_head"], mode, layer_weight=False), mode)

    return jax.jit(head)


@functools.lru_cache(maxsize=None)
def _embed_fn(m: Model):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda tokens, key: outer_leaves(m, key)["embed"][tokens].astype(jnp.float32))


def logits_at(
    m: Model, seed: int, sequences: Sequence[Sequence[int]], n_last: Sequence[int],
    mode: str = "f32",
) -> List[Any]:
    import jax.numpy as jnp

    key = seed_key(seed)
    layer = _layer_fn(m, mode, os.environ.get("PERFBENCH_TINY_WINDOW_FORGET") == "1")
    out = []
    for seq, last in zip(sequences, n_last):
        n = len(seq)
        tokens = np.zeros((-(-n // PAD_TO) * PAD_TO,), np.int32)
        tokens[:n] = np.asarray(seq, np.int32)
        x = _embed_fn(m)(jnp.asarray(tokens), key)
        for index in range(m.layers):
            x = layer(x, key, jnp.int32(index))
        rows = jnp.arange(n - last, n, dtype=jnp.int32)
        out.append((np.asarray(_head_fn(m, mode)(x, rows, key)),
                    np.full((last,), np.inf, np.float32)))
    return out


# -- counts ------------------------------------------------------------- #

def attn_params(m: Model) -> int:
    return 2 * m.hidden * m.q_dim + 2 * m.hidden * m.kv_dim


def mlp_params_one(m: Model) -> int:
    return 3 * m.hidden * m.ffn


def params_held(m: Model) -> int:
    layer = attn_params(m) + mlp_params_one(m) + 4 * m.hidden
    return m.layers * layer + 2 * m.vocab * m.hidden + m.hidden


def params_active(m: Model, with_head: bool = True) -> int:
    layer = attn_params(m) + mlp_params_one(m)
    return m.layers * layer + (m.vocab * m.hidden if with_head else 0)


def keys_seen(first: int, last: int, window: int = 0) -> float:
    """Sum over the queries at positions ``first`` to ``last - 1`` of the keys
    each sees: ``position + 1`` on a full layer, at most ``window`` on a
    window layer."""
    def causal(a: int, b: int) -> float:
        return (b * (b + 1) - a * (a + 1)) / 2.0

    if not window:
        return causal(first, last)
    ramp_end = min(max(first, window), last)       # from here on a query sees `window` keys
    return causal(first, ramp_end) + (last - ramp_end) * window


def attention_flops(m: Model, context_sum: float,
                    window_context_sum: Optional[float] = None) -> float:
    """QK^T and PV: 4 * heads * head_dim for every (query, key) pair.
    ``context_sum`` is the full layers' sum of keys seen; the window layers'
    is ``window_context_sum``. A caller that has one sum only (the generic
    ``flash_prefill_roofline`` reader lays out the full causal one) gets the
    window layers counted at it too, which is above what they require: a cell
    of a window model brings a reader that lays out both."""
    if window_context_sum is None:
        window_context_sum = context_sum
    pairs = m.full_layers * context_sum + (m.layers - m.full_layers) * window_context_sum
    return 4.0 * m.heads * m.head_dim * pairs


def request_flops(m: Model, prompt: int, output: int, cached_prefix: int = 0) -> float:
    fresh = prompt - cached_prefix
    n_dec = max(output - 1, 0)
    trunk = 2.0 * params_active(m, with_head=False)
    head = 2.0 * m.vocab * m.hidden
    return (
        trunk * (fresh + n_dec) + head * max(output, 0)
        + attention_flops(m, keys_seen(cached_prefix, prompt + n_dec),
                          keys_seen(cached_prefix, prompt + n_dec, m.window))
    )


def decode_step_weight_bytes(m: Model) -> float:
    E, F = m.hidden, m.ffn
    attn = attn_params(m) + 2 * (m.q_dim + 2 * m.kv_dim + E)
    mlp = 3 * E * F + 2 * (2 * F + E)
    layer = attn + mlp + 4 * 2 * E
    return float(m.layers * layer + m.vocab * E + 2 * m.vocab + 2 * E)


def flash_prefill_flops(m: Model, context_sum: float) -> float:
    return attention_flops(m, context_sum)


def flash_prefill_bytes(m: Model, q_tokens: float) -> float:
    return 2.0 * m.layers * q_tokens * (2 * m.q_dim + 2 * m.kv_dim)
