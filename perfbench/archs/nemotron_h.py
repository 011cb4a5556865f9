"""The ``nemotron_h`` family as its public configuration states it: a stack in a
published order (``hybrid_override_pattern``) of layers that are each one mixer
behind one RMSNorm, ``x <- x + Mixer(RMSNorm(x))``, of three kinds.

``M``, Mamba-2. With ``u`` the normed input: ``[z | xBC | dt] = u W_in``
(inner | inner + 2 groups state | heads, no bias); ``xBC <- silu(conv1d(xBC))``,
causal, depthwise, kernel ``conv_kernel``, with bias; ``xBC`` splits into ``xs``
(heads x head_dim), ``B`` and ``C`` (groups x state; head ``h`` reads group
``h // (heads / groups)``). A head has ``dt = softplus(dt + dt_bias)``,
``A = -exp(A_log)``, state ``h_t = exp(dt_t A) h_{t-1} + dt_t xs_t (x) B_t`` and
``y_t = h_t C_t + D xs_t``. Then ``y <- y silu(z)``, RMS-normalised within each
group's channels, times a weight, and ``out = y W_out``. ``chunk_size`` is the
chunk of a blocked scan and changes no mathematics: the reference runs the
recurrence one token at a time.

``*``, attention: grouped-query causal softmax at ``head_dim ** -0.5``, no bias
and NO rotary embedding (the state-space layers carry position).

``E``, latent experts. The router, in float32, scores all ``n_routed_experts``
as published: ``s = sigmoid(u W_r)``; the chosen set is the top-k of ``s + b``
(``b`` the selection bias); weights ``w_i = scale s_i / sum of the chosen s``.
``l = u W_in_lat``; expert ``i`` is ``relu(l W_up,i)^2 W_down,i`` in the latent
space; ``routed = (sum_i w_i e_i(l)) W_out_lat``; a shared expert
``relu(u W_su)^2 W_sd`` is added.

**This chip's share** (guide section 4): the sum over the chosen experts runs
over those HELD here only (``n_routed_experts`` in the file: the first that
many of the published count, which ``reduced_from_source`` states and the
router keeps), and the vocabulary is its first ``vocab_size`` rows. The
partial result goes on to the next layer, in the program and here alike.

Weights are bfloat16 as published (the leaves ARE the bfloat16 numbers, so the
served tree and this float32 reference hold the same values); the router, its
bias and the per-head ``A_log``, ``dt_bias``, ``D`` are float32. Nothing here
imports the program but ``program_config`` and ``program_params``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from perfbench.reference import PAD_TO, _deq, _mm, _rms
from perfbench.weights import _norm_scale, seed_key

READS = (
    "hidden_size", "hybrid_override_pattern", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "vocab_size", "max_position_embeddings",
    "layer_norm_epsilon", "tie_word_embeddings", "attention_bias", "mlp_bias", "use_bias",
    "mamba_proj_bias", "use_conv_bias", "mlp_hidden_act", "mamba_hidden_act",
    "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel",
    "chunk_size", "expand", "time_step_min", "time_step_max", "time_step_floor",
    "n_routed_experts", "num_experts_per_tok", "moe_intermediate_size", "moe_latent_size",
    "moe_shared_expert_intermediate_size", "n_shared_experts", "routed_scaling_factor",
    "norm_topk_prob", "n_group", "topk_group", "num_nextn_predict_layers",
    "sliding_window",
)
IGNORES = {
    "model_type": "names the family; the arch key chooses this module",
    "rope_theta": "nemotron_h's attention applies no rotary embedding, so no angle is computed",
    "partial_rotary_factor": "no rotary embedding is applied (see rope_theta)",
    "intermediate_size": "the width of a dense MLP layer ('-' in the pattern); this pattern has none, and the experts' width is moe_intermediate_size",
    "norm_eps": "the family's code reads layer_norm_epsilon for every norm; the two are equal here",
    "num_logits_to_keep": "a generation-API setting: how many positions the head is applied to",
    "rescale_prenorm_residual": "initialisation only: weights here come from --seed",
    "residual_in_fp32": "false as published: the residual stream is in the activation type; the reference is float32 throughout",
    "use_mamba_kernels": "chooses fused CUDA kernels for the same mathematics",
    "moe_shared_expert_overlap": "a scheduling hint (overlap the shared expert with the exchange); same sum",
    "mtp_hybrid_override_pattern": "the multi-token-prediction module's layers; num_nextn_predict_layers is 0 here, so there are none",
}

BYTES = 2   # bfloat16 weights and activations


@dataclasses.dataclass(frozen=True)
class Model:
    name: str
    hidden: int
    pattern: str              # one character a layer: M, E or *
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int                # rows held (the slice)
    experts_held: int         # experts 0 .. experts_held - 1 live here
    experts_routed: int       # the router's width, as published
    experts_per_tok: int
    expert_width: int
    latent: int
    shared_width: int
    scale: float
    ssm_heads: int
    ssm_head_dim: int
    ssm_groups: int
    ssm_state: int
    conv: int
    chunk: int
    dt_min: float
    dt_max: float
    dt_floor: float
    rms_eps: float
    max_positions: int
    slots: int                # rows of a decode step (serve.argv), for the counts

    @property
    def layers(self) -> int:
        return len(self.pattern)

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.ssm_groups * self.ssm_state

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)


def model_from_config(cfg: Dict[str, Any]) -> Model:
    pattern = str(cfg["hybrid_override_pattern"])
    if set(pattern) - set("ME*"):
        raise ValueError(f"pattern {pattern!r}: this module writes M, E and * layers (no dense '-')")
    if len(pattern) != int(cfg["num_hidden_layers"]):
        raise ValueError("hybrid_override_pattern has another length than num_hidden_layers")
    for key in ("attention_bias", "mlp_bias", "use_bias", "mamba_proj_bias", "tie_word_embeddings"):
        if cfg.get(key):
            raise ValueError(f"{key} is not written here: the published value is false")
    if not cfg.get("use_conv_bias", True):
        raise ValueError("the convolution here has its bias: use_conv_bias must be true")
    if cfg.get("mlp_hidden_act") != "relu2" or cfg.get("mamba_hidden_act", "silu") != "silu":
        raise ValueError("experts here are squared ReLU and the Mamba mixer SiLU")
    if int(cfg.get("n_group", 1)) != 1 or int(cfg.get("topk_group", 1)) != 1:
        raise ValueError("group-limited routing is not written here: n_group and topk_group are 1")
    if not cfg.get("norm_topk_prob", True):
        raise ValueError("un-normalised top-k weights are not written here: norm_topk_prob must be true")
    if int(cfg.get("n_shared_experts", 1)) != 1:
        raise ValueError("one shared expert is written here")
    if int(cfg.get("num_nextn_predict_layers", 0)) != 0:
        raise ValueError("the multi-token-prediction module is not written here: num_nextn_predict_layers must be 0")
    if cfg.get("sliding_window"):
        raise ValueError("a sliding window is not written here")
    heads, hd = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    if heads * hd != int(cfg["expand"]) * int(cfg["hidden_size"]):
        raise ValueError("mamba_num_heads x mamba_head_dim is not expand x hidden_size")
    published = cfg.get("reduced_from_source") or {}
    argv = list((cfg.get("serve") or {}).get("argv") or [])
    return Model(
        name=str(cfg["name"]), hidden=int(cfg["hidden_size"]), pattern=pattern,
        heads=int(cfg["num_attention_heads"]), kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]), vocab=int(cfg["vocab_size"]),
        experts_held=int(cfg["n_routed_experts"]),
        experts_routed=int(
            (published.get("n_routed_experts") or {}).get("published", cfg["n_routed_experts"])),
        experts_per_tok=int(cfg["num_experts_per_tok"]),
        expert_width=int(cfg["moe_intermediate_size"]), latent=int(cfg["moe_latent_size"]),
        shared_width=int(cfg["moe_shared_expert_intermediate_size"]),
        scale=float(cfg["routed_scaling_factor"]),
        ssm_heads=heads, ssm_head_dim=hd, ssm_groups=int(cfg["n_groups"]),
        ssm_state=int(cfg["ssm_state_size"]), conv=int(cfg["conv_kernel"]),
        chunk=int(cfg["chunk_size"]), dt_min=float(cfg["time_step_min"]),
        dt_max=float(cfg["time_step_max"]), dt_floor=float(cfg["time_step_floor"]),
        rms_eps=float(cfg["layer_norm_epsilon"]),
        max_positions=int(cfg["max_position_embeddings"]),
        slots=int(argv[argv.index("--slots") + 1]) if "--slots" in argv else 8,
    )


# --------------------------------------------------------------------- #
# Weights
# --------------------------------------------------------------------- #

_INT8_STD = (127 * 128 / 3) ** 0.5


def _bleaf(key, shape, fan_in, centred=False):
    """A bfloat16 weight with standard deviation ``fan_in ** -0.5``: uniform
    int8 values times a per-output-channel scale, rounded to bfloat16 once.
    The bfloat16 numbers are the weight; the reference reads them as float32.

    ``centred`` takes each output channel's mean over its inputs away. It is
    for the matrices that read a squared ReLU, which is never negative: with
    random weights its mean would put one common vector into every token's
    residual stream (a third of its size after two expert layers, measured),
    every later router would lean to the same experts for every token, and
    how many of them are held here would be the seed's luck (23.7-27.5% a
    layer where 25 is even). Centring gives the experts' outputs the zero
    mean a trained model's have; the load itself is spread by the selection
    bias (``stack_layers``)."""
    import jax
    import jax.numpy as jnp

    kq, ks = jax.random.split(key)
    bits = jax.random.bits(kq, shape, dtype=jnp.uint8)
    q = jnp.clip(bits.astype(jnp.int32) - 128, -127, 127).astype(jnp.float32)
    if centred:
        q = q - jnp.mean(q, axis=-2, keepdims=True)
    spread = 0.75 + 0.5 * jax.random.uniform(ks, shape[:-2] + (1, shape[-1]), dtype=jnp.float32)
    return (q * spread * (fan_in ** -0.5 / _INT8_STD)).astype(jnp.bfloat16)


def layer_leaves(m: Model, kind: str, key, index) -> Dict[str, Any]:
    """The leaves of layer ``index``, which is of ``kind``. ``index`` may be
    traced, so one program a kind makes every layer of that kind."""
    import jax
    import jax.numpy as jnp

    lk = jax.random.fold_in(key, index + 1)
    k = [jax.random.fold_in(lk, i) for i in range(12)]
    E = m.hidden
    out: Dict[str, Any] = {"norm": _norm_scale(k[0], E)}
    if kind == "*":
        out.update(
            wq=_bleaf(k[1], (E, m.q_dim), E), wk=_bleaf(k[2], (E, m.kv_dim), E),
            wv=_bleaf(k[3], (E, m.kv_dim), E), wo=_bleaf(k[4], (m.q_dim, E), m.q_dim),
        )
    elif kind == "M":
        I, H, C = m.inner, m.ssm_heads, m.conv_dim
        # as the family initialises them: dt log-uniform in [dt_min, dt_max],
        # floored, stored as softplus^-1; A uniform in 1..16; D = 1
        dt = jnp.maximum(jnp.exp(
            jax.random.uniform(k[3], (H,), dtype=jnp.float32)
            * (math.log(m.dt_max) - math.log(m.dt_min)) + math.log(m.dt_min)
        ), m.dt_floor)
        out.update(
            in_proj=_bleaf(k[1], (E, I + C + H), E),
            conv_w=_bleaf(k[2], (m.conv, C), m.conv),
            conv_b=(0.1 * jax.random.normal(k[6], (C,), dtype=jnp.float32)).astype(jnp.bfloat16),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
            A_log=jnp.log(jax.random.uniform(k[4], (H,), dtype=jnp.float32, minval=1.0, maxval=16.0)),
            D=jnp.ones((H,), jnp.float32),
            gnorm=_norm_scale(k[7], I),
            out_proj=_bleaf(k[5], (I, E), I),
        )
    else:
        X, Z, F, S = m.experts_held, m.latent, m.expert_width, m.shared_width

        def expert(x):
            ek = jax.random.fold_in(k[3], x)
            return (_bleaf(jax.random.fold_in(ek, 0), (Z, F), Z),
                    _bleaf(jax.random.fold_in(ek, 1), (F, Z), F, centred=True))

        w_up, w_down = jax.lax.map(expert, jnp.arange(X))
        out.update(
            # the router's columns are the published count's; column i is expert i
            router=jax.random.normal(k[1], (E, m.experts_routed), dtype=jnp.float32) * E ** -0.5,
            # the selection bias is balanced on the layer's input: ``stack_layers``
            bias=jnp.zeros((m.experts_routed,), jnp.float32),
            w_in=_bleaf(k[4], (E, Z), E), w_up=w_up, w_down=w_down,
            w_out=_bleaf(k[5], (Z, E), Z),
            ws_up=_bleaf(k[6], (E, S), E), ws_down=_bleaf(k[7], (S, E), S, centred=True),
        )
    return out


def outer_leaves(m: Model, key) -> Dict[str, Any]:
    """Embedding, final norm and the untied head, over the vocabulary slice."""
    import jax
    import jax.numpy as jnp

    ke, kn, kh = (jax.random.fold_in(key, 1_000_000 + i) for i in range(3))
    return {
        "embed": jax.random.normal(ke, (m.vocab, m.hidden), dtype=jnp.float32).astype(jnp.bfloat16),
        "final_norm": _norm_scale(kn, m.hidden),
        "lm_head": _bleaf(kh, (m.hidden, m.vocab), m.hidden),
    }


@functools.lru_cache(maxsize=None)
def _leaves_fn(m: Model, kind: str):
    import jax

    return jax.jit(lambda key, index: layer_leaves(m, kind, key, index))


CALIBRATION = (8, 512)   # sequences x tokens the selection biases are balanced on


@functools.lru_cache(maxsize=None)
def _balance_fn(m: Model):
    import jax
    import jax.numpy as jnp

    def balance(xs, norm, router):
        u = _rms(jnp.concatenate(xs), norm, m.rms_eps)
        s = jax.nn.sigmoid(jnp.matmul(u, router, precision=jax.lax.Precision.HIGHEST))
        q = jnp.quantile(s, 1.0 - m.experts_per_tok / m.experts_routed, axis=0)
        return jnp.mean(q) - q

    return jax.jit(balance)


def stack_layers(m: Model, key):
    """``(index, kind, leaves)`` of every layer in the published order, one
    layer's weights made at a time, **each expert layer's selection bias
    balanced as a trained one is**: random sequences (``CALIBRATION``, from
    the seed) run through the layers as they are made, in float32, and an
    expert layer gets the ``b`` with which every expert's ``s_i + b_i`` passes
    one threshold at ``experts_per_tok / experts_routed`` of those tokens, so
    every expert is chosen about equally often. A zero or random bias leaves
    the load to the seed: the 22 most chosen of 512 took 13% of the pairs
    where 4.3% is even, the share held here moved by a point and a loaded
    decode step's time by 1.2% either way between seeds (PERF.md, section 6,
    PR 29). ``make_stack`` and ``logits_at`` both walk this, so the program
    and the reference hold the same bias."""
    import jax
    import jax.numpy as jnp

    rows, length = CALIBRATION
    tokens = jax.random.randint(
        jax.random.fold_in(key, 2_000_000), (rows, length), 0, m.vocab, dtype=jnp.int32)
    xs = [_embed_fn(m)(t, key) for t in tokens]
    for index, kind in enumerate(m.pattern):
        lw = _leaves_fn(m, kind)(key, jnp.int32(index))
        if kind == "E":
            lw = dict(lw, bias=_balance_fn(m)(xs, lw["norm"], lw["router"]))
        yield index, kind, lw
        if "E" in m.pattern[index + 1:]:
            xs = [_layer_fn(m, kind, "f32")(x, lw)[0] for x in xs]


def make_stack(m: Model, seed: int) -> Dict[str, Any]:
    """The whole model: ``{"outer": ..., "layers": (one dict a layer, in the
    published order)}``, one jitted call a layer (a program a kind, the layer's
    index traced: three programs and not eleven, and the temporaries are one
    layer's)."""
    import jax

    key = seed_key(seed)
    return {
        "outer": jax.jit(lambda k: outer_leaves(m, k))(key),
        "layers": tuple(lw for _, _, lw in stack_layers(m, key)),
    }


def program_config(cfg: Dict[str, Any], m: Model):
    try:
        from pilottai_tpu.models.nemotron_h import nemotron_h
    except ImportError as err:
        raise SystemExit(
            f"this checkout's program cannot run arch 'nemotron_h' ({m.name}): it has no "
            f"pilottai_tpu/models/nemotron_h.py ({err})")

    return nemotron_h(
        m.name, m.pattern, vocab_size=m.vocab, hidden_size=m.hidden, n_heads=m.heads,
        n_kv_heads=m.kv_heads, head_dim=m.head_dim, max_seq_len=m.max_positions,
        rms_eps=m.rms_eps, n_experts=m.experts_routed, n_active_experts=m.experts_per_tok,
        experts_held=(0, m.experts_held), moe_intermediate=m.expert_width,
        moe_latent=m.latent, moe_shared_intermediate=m.shared_width, moe_scale=m.scale,
        ssm_heads=m.ssm_heads, ssm_head_dim=m.ssm_head_dim,
        ssm_groups=m.ssm_groups, ssm_state=m.ssm_state, ssm_conv=m.conv, ssm_chunk=m.chunk,
    )


def program_params(m: Model, seed: int, int8: bool) -> Dict[str, Any]:
    """The seed's weights in the tree the program serves (bfloat16 only)."""
    if int8:
        raise ValueError(f"{m.name} is served in bfloat16 as published: no --quantize")
    stack = make_stack(m, seed)
    layers = []
    for kind, lw in zip(m.pattern, stack["layers"]):
        norm = {"scale": lw["norm"]}
        if kind == "*":
            layers.append({"norm": norm, "attn": {k: lw[k] for k in ("wq", "wk", "wv", "wo")}})
        elif kind == "M":
            ssm = {k: lw[k] for k in ("in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D", "out_proj")}
            layers.append({"norm": norm, "ssm": dict(ssm, norm=lw["gnorm"])})
        else:
            moe = {k: lw[k] for k in ("router", "bias", "w_in", "w_up", "w_down", "w_out")}
            moe["shared"] = {"wu": lw["ws_up"], "wd": lw["ws_down"]}
            layers.append({"norm": norm, "moe": moe})
    outer = stack["outer"]
    return {
        "embed": outer["embed"], "layers": tuple(layers),
        "final_norm": {"scale": outer["final_norm"]}, "lm_head": outer["lm_head"],
    }


# --------------------------------------------------------------------- #
# The plain reference
# --------------------------------------------------------------------- #

def _w(leaf, mode: str, layer_weight: bool = True):
    """A bfloat16 leaf as the float32 matrix the reference multiplies by
    (``_deq`` with a scale of one, so ``w4`` rounds it as it rounds the rest)."""
    import jax.numpy as jnp

    return _deq((leaf, jnp.ones((), jnp.float32)), mode, layer_weight)


def _attention(m: Model, q, k, v):
    """Causal grouped-query attention, one KV head's group at a time."""
    import jax
    import jax.numpy as jnp

    T = q.shape[0]
    rep = m.heads // m.kv_heads
    hi = jax.lax.Precision.HIGHEST
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    def group(args):
        qg, kg, vg = args
        s = jnp.einsum("tnd,sd->nts", qg, kg, precision=hi) * m.head_dim ** -0.5
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("nts,sd->tnd", p, vg, precision=hi)

    qg = q.reshape(T, m.kv_heads, rep, m.head_dim).transpose(1, 0, 2, 3)
    out = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2, 3).reshape(T, m.q_dim)


def _mamba(m: Model, lw, u, mode: str):
    """The recurrence as written, one token at a time."""
    import jax
    import jax.numpy as jnp

    T = u.shape[0]
    I, C, H, P = m.inner, m.conv_dim, m.ssm_heads, m.ssm_head_dim
    G, N, K = m.ssm_groups, m.ssm_state, m.conv
    zxd = _mm(u, _w(lw["in_proj"], mode), mode)
    z, xbc, dt = zxd[:, :I], zxd[:, I:I + C], zxd[:, I + C:]
    padded = jnp.concatenate([jnp.zeros((K - 1, C), jnp.float32), xbc])
    cw = lw["conv_w"].astype(jnp.float32)
    xbc = jax.nn.silu(
        sum(padded[j:j + T] * cw[j] for j in range(K)) + lw["conv_b"].astype(jnp.float32))
    xs = xbc[:, :I].reshape(T, H, P)
    rep = H // G
    Bm = jnp.repeat(xbc[:, I:I + G * N].reshape(T, G, N), rep, axis=1)     # [T, H, N]
    Cm = jnp.repeat(xbc[:, I + G * N:].reshape(T, G, N), rep, axis=1)
    dt = jax.nn.softplus(dt + lw["dt_bias"])                               # [T, H]
    A = -jnp.exp(lw["A_log"])

    def step(h, t):
        x, d, b, c = t
        h = jnp.exp(d * A)[:, None, None] * h + (d[:, None] * x)[:, :, None] * b[:, None, :]
        return h, jnp.sum(h * c[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32), (xs, dt, Bm, Cm))
    y = (y + lw["D"][:, None] * xs).reshape(T, I) * jax.nn.silu(z)
    g = y.reshape(T, G, I // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + m.rms_eps)
    y = g.reshape(T, I) * lw["gnorm"].astype(jnp.float32)
    return _mm(y, _w(lw["out_proj"], mode), mode)


EXPERT_BLOCK = 8   # held experts computed at a time by the reference


def _experts(m: Model, lw, u, mode: str):
    """``(out, margin)``: the held experts' share of the routed sum through
    the latent projections, plus the shared expert; ``margin`` is the lead of
    the last chosen expert over the first left out, in the selection score
    ``s + b`` (the unit a limits file's ``router_tie`` is set in)."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    k = m.experts_per_tok
    s = jax.nn.sigmoid(jnp.matmul(u, lw["router"], precision=hi))          # [T, routed]
    ranked, chosen = jax.lax.top_k(s + lw["bias"], k + 1)
    margin = ranked[:, k - 1] - ranked[:, k]
    chosen = chosen[:, :k]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * m.scale
    combine = jnp.sum(
        jax.nn.one_hot(chosen, m.experts_routed, dtype=jnp.float32) * w[..., None], axis=-2
    )[:, :m.experts_held]                                                  # [T, held]
    lat = _mm(u, _w(lw["w_in"], mode), mode)
    nb = m.experts_held // EXPERT_BLOCK if m.experts_held % EXPERT_BLOCK == 0 else m.experts_held
    per = m.experts_held // nb

    def block(acc, args):
        up, down, c = args                                                 # [per, Z, F], [per, F, Z], [per, T]
        for j in range(per):
            h = jnp.square(jax.nn.relu(_mm(lat, _w(up[j], mode), mode)))
            acc = acc + _mm(h, _w(down[j], mode), mode) * c[j][:, None]
        return acc, None

    grouped = lambda a: a.reshape((nb, per) + a.shape[1:])
    routed, _ = jax.lax.scan(
        block, jnp.zeros_like(lat),
        (grouped(lw["w_up"]), grouped(lw["w_down"]), grouped(combine.T)),
    )
    out = _mm(routed, _w(lw["w_out"], mode), mode)
    shared = jnp.square(jax.nn.relu(_mm(u, _w(lw["ws_up"], mode), mode)))
    return out + _mm(shared, _w(lw["ws_down"], mode), mode), margin


@functools.lru_cache(maxsize=None)
def _layer_fn(m: Model, kind: str, mode: str):
    import jax
    import jax.numpy as jnp

    def layer(x, lw):
        T = x.shape[0]
        u = _rms(x, lw["norm"], m.rms_eps)
        margin = jnp.full((T,), jnp.inf, jnp.float32)
        if kind == "M":
            out = _mamba(m, lw, u, mode)
        elif kind == "*":
            q = _mm(u, _w(lw["wq"], mode), mode).reshape(T, m.heads, m.head_dim)
            k = _mm(u, _w(lw["wk"], mode), mode).reshape(T, m.kv_heads, m.head_dim)
            v = _mm(u, _w(lw["wv"], mode), mode).reshape(T, m.kv_heads, m.head_dim)
            out = _mm(_attention(m, q, k, v), _w(lw["wo"], mode), mode)
        else:
            out, margin = _experts(m, lw, u, mode)
        return x + out, margin

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(m: Model, mode: str):
    import jax

    def head(x, rows, key):
        outer = outer_leaves(m, key)
        h = _rms(x[rows], outer["final_norm"], m.rms_eps)
        return _mm(h, _w(outer["lm_head"], mode, layer_weight=False), mode)

    return jax.jit(head)


@functools.lru_cache(maxsize=None)
def _embed_fn(m: Model):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda tokens, key: outer_leaves(m, key)["embed"][tokens].astype(jnp.float32))


def logits_at(
    m: Model, seed: int, sequences: Sequence[Sequence[int]], n_last: Sequence[int],
    mode: str = "f32",
) -> List[Tuple[np.ndarray, np.ndarray]]:
    """For each token sequence the logits ``[n_last[i], vocab]`` at its last
    ``n_last[i]`` positions and, beside them, the least margin over the expert
    layers by which the router at that position chose. Layers are the outer
    loop: a layer's weights are made once from the seed and dropped before
    the next, so no more than one layer's are ever held."""
    import jax.numpy as jnp

    key = seed_key(seed)
    xs, lens = [], []
    for seq in sequences:
        n = len(seq)
        tokens = np.zeros((-(-n // PAD_TO) * PAD_TO,), np.int32)
        tokens[:n] = np.asarray(seq, np.int32)
        xs.append(_embed_fn(m)(jnp.asarray(tokens), key))
        lens.append(n)
    margins = [jnp.full((x.shape[0],), jnp.inf, jnp.float32) for x in xs]
    for _, kind, lw in stack_layers(m, key):
        stepped = [_layer_fn(m, kind, mode)(x, lw) for x in xs]
        xs = [x for x, _ in stepped]
        margins = [jnp.minimum(a, b) for a, (_, b) in zip(margins, stepped)]
    out = []
    for x, mg, n, last in zip(xs, margins, lens, n_last):
        rows = jnp.arange(n - last, n, dtype=jnp.int32)
        out.append((np.asarray(_head_fn(m, mode)(x, rows, key)), np.asarray(mg[n - last:n])))
    return out


# --------------------------------------------------------------------- #
# What the model requires, from its shapes alone
# --------------------------------------------------------------------- #

def attn_params(m: Model) -> int:
    """Projection weights of one attention layer."""
    return 2 * m.hidden * m.q_dim + 2 * m.hidden * m.kv_dim


def mlp_params_one(m: Model) -> int:
    """One routed expert, in the latent space, no gate."""
    return 2 * m.latent * m.expert_width


def mamba_params(m: Model, matmul_only: bool = False) -> int:
    proj = m.hidden * (m.inner + m.conv_dim + m.ssm_heads) + m.inner * m.hidden
    small = (m.conv + 1) * m.conv_dim + 3 * m.ssm_heads + m.inner
    return proj if matmul_only else proj + small


def expert_layer_params(m: Model, experts: float) -> float:
    """An expert layer with ``experts`` routed experts counted: the router
    (and its bias), the latent projections, the shared expert, the experts."""
    return (
        m.hidden * m.experts_routed + 2 * m.hidden * m.latent
        + 2 * m.hidden * m.shared_width + experts * mlp_params_one(m)
    )


def held_share(m: Model) -> float:
    """The share of a token's routed experts that lies on this chip, when the
    router spreads them evenly: the expected share, which only the module can
    state (``engine.moe_assignments_held`` over ``engine.moe_assignments``
    is the measured one)."""
    return m.experts_held / m.experts_routed


def params_held(m: Model) -> int:
    """Every parameter on the chip."""
    per = {
        "M": mamba_params(m) + m.hidden,
        "*": attn_params(m) + m.hidden,
        "E": int(expert_layer_params(m, m.experts_held)) + m.experts_routed + m.hidden,
    }
    return sum(per[k] for k in m.pattern) + 2 * m.vocab * m.hidden + m.hidden


def params_active(m: Model, with_head: bool = True) -> int:
    """Matmul parameters one token passes through on this chip: of its
    ``experts_per_tok`` routed experts only the expected share held here."""
    per = {
        "M": mamba_params(m, matmul_only=True),
        "*": attn_params(m),
        "E": expert_layer_params(m, m.experts_per_tok * held_share(m)),
    }
    return int(sum(per[k] for k in m.pattern) + (m.vocab * m.hidden if with_head else 0))


def attention_flops(m: Model, context_sum: float) -> float:
    """QK^T and PV of the attention layers (one of eleven here)."""
    return 4.0 * m.heads * m.head_dim * m.count("*") * context_sum


def ssm_scan_flops(m: Model, tokens: float) -> float:
    """The recurrence of every Mamba layer for ``tokens`` tokens: decay and
    input into the state (3 operations a state element) and the read-out
    (2), plus ``D xs``."""
    state = m.ssm_heads * m.ssm_head_dim * m.ssm_state
    return tokens * m.count("M") * (5.0 * state + 2.0 * m.inner)


def ssm_scan_bytes(m: Model, prefill_tokens: float, decode_tokens: float, prompts: float) -> float:
    """Bytes the recurrence has to move: each token's ``xs``, ``B``, ``C``,
    ``dt`` in and ``y`` out (activation type), and the float32 state read and
    written once a decode step a row, written once a prompt."""
    per_token = BYTES * (m.conv_dim + m.ssm_heads + m.inner)
    state = 4.0 * m.ssm_heads * m.ssm_head_dim * m.ssm_state
    return m.count("M") * (
        (prefill_tokens + decode_tokens) * per_token + state * (2.0 * decode_tokens + prompts))


def experts_reached(m: Model, tokens: float) -> float:
    """Expected number of held experts that ``tokens`` tokens reach, each
    choosing ``experts_per_tok`` of ``experts_routed`` evenly."""
    miss = (1.0 - m.experts_per_tok / m.experts_routed) ** max(tokens, 0.0)
    return m.experts_held * (1.0 - miss)


def moe_grouped_flops(m: Model, pairs: float) -> float:
    """The two grouped products for ``pairs`` token-expert pairs that landed
    on held experts, all expert layers' pairs counted by the caller."""
    return 2.0 * pairs * mlp_params_one(m)


def moe_grouped_bytes(m: Model, pairs: float, call_tokens: Sequence[float]) -> float:
    """Bytes of the grouped products: a pair's rows in and out of both
    (latent in, width out and in, latent out), and an expert's two matrices
    once a call if any token of the call reached it (``call_tokens``: the
    tokens of each call, one call an expert layer a dispatch)."""
    rows = BYTES * pairs * 2 * (m.latent + m.expert_width)
    weights = BYTES * mlp_params_one(m) * sum(experts_reached(m, t) for t in call_tokens)
    return rows + weights


def request_flops(m: Model, prompt: int, output: int, cached_prefix: int = 0) -> float:
    """Operations one request requires of this chip: ``prompt`` tokens through
    the trunk (nothing is shared: a cached prefix saves nothing for a model
    with recurrent state), then ``output - 1`` decode steps with the head."""
    n_dec = max(output - 1, 0)
    trunk = 2.0 * params_active(m, with_head=False)
    head = 2.0 * m.vocab * m.hidden
    ctx = prompt * (prompt + 1) / 2.0 + n_dec * prompt + n_dec * (n_dec + 1) / 2.0
    return (
        trunk * (prompt + n_dec) + head * max(output, 0) + attention_flops(m, ctx)
        + ssm_scan_flops(m, prompt + n_dec)
    )


def decode_step_weight_bytes(m: Model) -> float:
    """Bytes of bfloat16 weights one decode step has to stream at ``slots``
    rows: every layer matrix, of the held experts those that the step's rows
    reach (94% at 64 rows if the router spreads evenly: an assumption, the
    measured share is ``moe.held_share_pct``'s business), the norms and the
    head. The embedding is a gather of one row a slot."""
    reached = experts_reached(m, m.slots)
    per = {
        "M": mamba_params(m) + m.hidden,
        "*": attn_params(m) + m.hidden,
        "E": expert_layer_params(m, reached) + m.experts_routed + m.hidden,
    }
    body = sum(per[k] for k in m.pattern) + m.vocab * m.hidden + m.hidden
    # the router, its bias and the per-head scalars are float32
    f32 = m.count("E") * (m.hidden + 1) * m.experts_routed + m.count("M") * 3 * m.ssm_heads
    return float(BYTES * body + 2 * f32)


def flash_prefill_flops(m: Model, context_sum: float) -> float:
    return attention_flops(m, context_sum)


def flash_prefill_bytes(m: Model, q_tokens: float) -> float:
    """q, k, v read and the output written once, the attention layers."""
    return float(BYTES) * m.count("*") * q_tokens * (2 * m.q_dim + 2 * m.kv_dim)


# --------------------------------------------------------------------- #
# What the new per-layer readers share (perfbench/metrics/moe*.py, ssm*.py)
# --------------------------------------------------------------------- #

def traced_work(ctx: Dict[str, Any]) -> Dict[str, Any]:
    """The work that fell inside the traced slice, laid out as
    ``readers.traced_prefill_context`` lays a prefill: a request's prompt
    evenly from its admission to its first token, its decode steps evenly
    from there to its end. ``decode_steps`` is the window's count scaled to
    the slice."""
    from perfbench import loadgen, readers

    span = ctx.get("trace_span") or [0.0, 0.0]
    prefill = decode = prompts = 0.0
    admissions: List[float] = []
    for r, f in readers.flights(ctx, in_window_only=False):
        if "first_token_at" not in f or "ended" not in f:
            continue
        share = loadgen.overlap_share(f["admitted_at"], f["first_token_at"], *span)
        if share > 0.0:
            prefill += share * r["prompt_tokens"]
            prompts += share
            admissions.append(share * r["prompt_tokens"])
        decode += (
            loadgen.overlap_share(f["first_token_at"], f["ended"], *span)
            * max(r["completion_tokens"] - 1, 0))
    steps = (ctx["counters"].get("engine.decode_steps") or 0.0) * (
        (span[1] - span[0]) / ctx["seconds"] if ctx.get("seconds") else 0.0)
    return {"prefill_tokens": prefill, "decode_tokens": decode, "prompts": prompts,
            "admissions": admissions, "decode_steps": steps}


def ssm_scan_op(m: Model, short_name: str) -> bool:
    """Whether a device operation (``trace_reduce.short_name``: instruction,
    type, dimensions) is of the recurrence. The reducer keys time by
    instruction name and keeps no scope, so the scan's operations are known
    by what they produce: a tensor over the state's ``[heads, head_dim
    (, state)]`` (heads also as groups x heads a group) or over a chunk's
    ``[chunk, chunk]`` scores a group. Checked against the scopes in the
    compiled programs' metadata (sandbox compile, PR 29): it takes the
    operations under ``ssm_scan``, the read-out's ``+ D xs`` beside them and
    the admission's write of the state pool, and nothing of another layer."""
    dims: List[int] = []
    for part in reversed(short_name.split("_")):
        if not part.isdigit():
            break
        dims.insert(0, int(part))
    G, R = m.ssm_groups, m.ssm_heads // m.ssm_groups
    H, P, Q = m.ssm_heads, m.ssm_head_dim, m.chunk
    has = lambda *seq: any(
        tuple(dims[i:i + len(seq)]) == seq for i in range(len(dims) - len(seq) + 1))
    return has(H, P) or has(G, R, P) or has(Q, G, R) or has(G, Q, Q) or has(Q, Q, G)
