"""Mistral and Mixtral as their public code states them: RMSNorm, rotate-half
RoPE on every layer, grouped-query causal attention, SwiGLU; for Mixtral a
softmax router over all experts, top-k, weights renormalised over the chosen k.
Every layer is of one kind and is held whole.

The weights ARE int8 values times a bfloat16 scale per output channel, so the
served int8 tree and the float32 reference hold exactly the same numbers and
no quantisation choice stands between them. Every layer's leaves come from
``fold_in(fold_in(key, layer), leaf)``: ``make_stack`` makes the whole stack in
one jitted call (``lax.map`` over layers, so the temporaries are one layer's),
and the reference makes one layer at a time from the same function, after the
program's state is freed, and never holds more than one layer.

Nothing here imports the program but ``program_config`` and
``program_params``, when the launcher calls them. The counts are operations and
bytes the model *requires*: for a mixture of experts only the routed experts
count (the program's dense dispatch computes all of them, and that is work the
model does not require).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Sequence

import numpy as np

from perfbench.reference import PAD_TO, _deq, _mm, _rms, _rope
from perfbench.weights import _norm_scale, _qleaf, seed_key

READS = (
    "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "vocab_size", "num_local_experts",
    "num_experts_per_tok", "rms_norm_eps", "rope_theta", "max_position_embeddings",
    "sliding_window", "tie_word_embeddings", "hidden_act",
)
IGNORES = {
    "architectures": "names the family's class; the arch key chooses this module",
    "model_type": "names the family; the arch key chooses this module",
    "torch_dtype": "the checkpoint's type; precision is stated under the file's own key",
    "router_aux_loss_coef": "a training loss term; nothing of the forward pass",
}


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


def model_from_config(cfg: Dict[str, Any]) -> Model:
    if cfg.get("sliding_window"):
        raise ValueError("a uniform sliding window is not expressible here")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("tied embeddings are not generated here")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the feed-forward here is SwiGLU: hidden_act must be silu")
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


def program_config(cfg: Dict[str, Any], model: Model):
    from pilottai_tpu.models.common import ModelConfig

    return ModelConfig(
        name=model.name, family="llama", vocab_size=model.vocab,
        hidden_size=model.hidden, n_layers=model.layers, n_heads=model.heads,
        n_kv_heads=model.kv_heads, head_dim=model.head_dim,
        intermediate_size=model.ffn, max_seq_len=model.max_positions,
        rope_theta=model.rope_theta, rms_eps=model.rms_eps,
        tie_embeddings=False, n_experts=model.experts,
        n_active_experts=model.experts_per_tok or 2,
    )


def program_params(model: Model, seed: int, int8: bool) -> Dict[str, Any]:
    """The seed's weights in the tree the program serves: one jitted call
    makes them, this only wraps the pairs in the program's ``QTensor``."""
    import jax.numpy as jnp

    from pilottai_tpu.models.quant import QTensor

    stack = make_stack(model, seed)

    def weight(pair):
        q, s = pair
        return QTensor(q=q, s=s) if int8 else q.astype(jnp.bfloat16) * s

    lay, outer = stack["layers"], stack["outer"]
    layers: Dict[str, Any] = {
        "ln1": {"scale": lay["ln1"]}, "ln2": {"scale": lay["ln2"]},
        "attn": {k: weight(lay[k]) for k in ("wq", "wk", "wv", "wo")},
    }
    mlp = {k: weight(lay[k]) for k in ("wg", "wu", "wd")}
    if model.experts:
        layers["moe"] = dict(mlp, router=lay["router"])
    else:
        layers["mlp"] = mlp
    return {
        "embed": outer["embed"], "layers": layers,
        "final_norm": {"scale": outer["final_norm"]},
        "lm_head": weight(outer["lm_head"]),
    }


def _attention(m: Model, q, k, v):
    """Causal grouped-query attention, one KV head's group at a time."""
    import jax
    import jax.numpy as jnp

    T = q.shape[0]
    rep = m.heads // m.kv_heads
    hi = jax.lax.Precision.HIGHEST
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    def group(args):
        qg, kg, vg = args                      # [T, rep, D], [T, D], [T, D]
        s = jnp.einsum("tnd,sd->nts", qg, kg, precision=hi) * m.head_dim ** -0.5
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("nts,sd->tnd", p, vg, precision=hi)

    qg = q.reshape(T, m.kv_heads, rep, m.head_dim).transpose(1, 0, 2, 3)
    out = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2, 3).reshape(T, m.q_dim)


def _mlp(m: Model, lw, h, mode: str):
    import jax
    import jax.numpy as jnp

    if not m.experts:
        gate = jax.nn.silu(_mm(h, _deq(lw["wg"], mode), mode))
        out = _mm(gate * _mm(h, _deq(lw["wu"], mode), mode), _deq(lw["wd"], mode), mode)
        return out, jnp.full((h.shape[0],), jnp.inf, jnp.float32)
    logits = jnp.matmul(
        h, lw["router"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST
    )
    probs = jax.nn.softmax(logits, axis=-1)
    k = m.experts_per_tok
    ranked = jax.lax.top_k(logits, k + 1)[0]
    margin = ranked[..., k - 1] - ranked[..., k]      # last chosen over first left out
    top_w, top_i = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    combine = jnp.sum(
        jax.nn.one_hot(top_i, m.experts, dtype=jnp.float32) * top_w[..., None], axis=-2
    )                                                       # [T, X]

    def expert(acc, args):
        wg, wu, wd, c = args
        gate = jax.nn.silu(_mm(h, _deq(wg, mode), mode))
        y = _mm(gate * _mm(h, _deq(wu, mode), mode), _deq(wd, mode), mode)
        return acc + y * c[:, None], None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(h), (lw["wg"], lw["wu"], lw["wd"], combine.T)
    )
    return out, margin


@functools.lru_cache(maxsize=None)
def _layer_fn(m: Model, mode: str):
    import jax

    def layer(x, key, index):
        lw = layer_leaves(m, key, index)
        T = x.shape[0]
        h = _rms(x, lw["ln1"], m.rms_eps)
        q = _mm(h, _deq(lw["wq"], mode), mode).reshape(T, m.heads, m.head_dim)
        k = _mm(h, _deq(lw["wk"], mode), mode).reshape(T, m.kv_heads, m.head_dim)
        v = _mm(h, _deq(lw["wv"], mode), mode).reshape(T, m.kv_heads, m.head_dim)
        attn = _attention(m, _rope(q, m.rope_theta), _rope(k, m.rope_theta), v)
        x = x + _mm(attn, _deq(lw["wo"], mode), mode)
        out, margin = _mlp(m, lw, _rms(x, lw["ln2"], m.rms_eps), mode)
        return x + out, margin

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

    return jax.jit(
        lambda tokens, key: outer_leaves(m, key)["embed"][tokens].astype(jnp.float32)
    )


def logits_at(
    m: Model, seed: int, sequences: Sequence[Sequence[int]], n_last: Sequence[int],
    mode: str = "f32",
) -> List[np.ndarray]:
    """For each token sequence, the logits ``[n_last[i], vocab]`` at its last
    ``n_last[i]`` positions, and beside them the least margin, over the layers,
    by which the router at that position chose its experts (inf for a dense
    model). Layers are the outer loop and each call makes
    its layer's weights from the seed inside the program, so no more than one
    layer's weights are ever held."""
    import jax.numpy as jnp

    key = seed_key(seed)
    xs, lens = [], []
    for seq in sequences:
        n = len(seq)
        padded = -(-n // PAD_TO) * PAD_TO
        tokens = np.zeros((padded,), np.int32)
        tokens[:n] = np.asarray(seq, np.int32)
        xs.append(_embed_fn(m)(jnp.asarray(tokens), key))
        lens.append(n)
    layer = _layer_fn(m, mode)
    margins = [jnp.full((x.shape[0],), jnp.inf, jnp.float32) for x in xs]
    for index in range(m.layers):
        stepped = [layer(x, key, jnp.int32(index)) for x in xs]
        xs = [x for x, _ in stepped]
        margins = [jnp.minimum(a, b) for a, (_, b) in zip(margins, stepped)]
    out = []
    for x, mg, n, last in zip(xs, margins, lens, n_last):
        rows = jnp.arange(n - last, n, dtype=jnp.int32)
        out.append((np.asarray(_head_fn(m, mode)(x, rows, key)), np.asarray(mg[n - last:n])))
    return out


def attn_params(m: Model) -> int:
    """Projection weights of one layer's attention."""
    return 2 * m.hidden * m.q_dim + 2 * m.hidden * m.kv_dim


def mlp_params_one(m: Model) -> int:
    """One SwiGLU feed-forward (dense, or one expert)."""
    return 3 * m.hidden * m.ffn


def params_held(m: Model) -> int:
    """Every parameter the configuration holds on the chip."""
    mlp = mlp_params_one(m) * max(m.experts, 1) + m.hidden * m.experts
    layer = attn_params(m) + mlp + 2 * m.hidden
    return m.layers * layer + 2 * m.vocab * m.hidden + m.hidden


def params_active(m: Model, with_head: bool = True) -> int:
    """Matmul parameters one token passes through: attention, the router,
    its routed experts (or the dense MLP), and the head if it is read."""
    mlp = mlp_params_one(m) * (m.experts_per_tok if m.experts else 1)
    layer = attn_params(m) + mlp + m.hidden * m.experts
    return m.layers * layer + (m.vocab * m.hidden if with_head else 0)


def attention_flops(m: Model, context_sum: float) -> float:
    """QK^T and PV over all layers: 4 * heads * head_dim for every (query,
    key) pair; ``context_sum`` is the sum over queries of the keys each sees."""
    return 4.0 * m.heads * m.head_dim * m.layers * context_sum


def request_flops(m: Model, prompt: int, output: int, cached_prefix: int = 0) -> float:
    """Operations one request requires: ``prompt - cached_prefix`` prompt
    tokens through the trunk (the head is read at the last one only), then
    ``output - 1`` decode steps with the head, each token attending causally."""
    fresh = prompt - cached_prefix
    trunk = 2.0 * params_active(m, with_head=False)
    head = 2.0 * m.vocab * m.hidden
    ctx_prefill = (prompt * (prompt + 1) - cached_prefix * (cached_prefix + 1)) / 2.0
    n_dec = max(output - 1, 0)
    ctx_decode = n_dec * prompt + n_dec * (n_dec + 1) / 2.0
    return (
        trunk * (fresh + n_dec) + head * max(output, 0)
        + attention_flops(m, ctx_prefill + ctx_decode)
    )


def decode_step_weight_bytes(m: Model) -> float:
    """Bytes of weights one decode step has to stream when they are int8 with
    a bfloat16 scale per output channel: every layer matmul (for a mixture,
    every expert: a full batch routes somewhere in each), the norms' scales
    and the head. The embedding is a gather of one row a slot."""
    E, F = m.hidden, m.ffn
    per_mlp = 3 * E * F + 2 * (2 * F + E)
    attn = attn_params(m) + 2 * (m.q_dim + 2 * m.kv_dim + E)
    mlp = per_mlp * max(m.experts, 1) + 2 * E * m.experts
    layer = attn + mlp + 2 * 2 * E
    return float(m.layers * layer + m.vocab * E + 2 * m.vocab + 2 * E)


def flash_prefill_flops(m: Model, context_sum: float) -> float:
    """Operations of causal prefill attention; ``context_sum`` as above."""
    return attention_flops(m, context_sum)


def flash_prefill_bytes(m: Model, q_tokens: float) -> float:
    """q, k, v read and the output written once, bfloat16, all layers."""
    return 2.0 * m.layers * q_tokens * (2 * m.q_dim + 2 * m.kv_dim)
