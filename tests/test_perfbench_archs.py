"""The seam between the benchmark's generic harness and an architecture's
module (``perfbench/archs/<arch>.py``), guarded by something the driver runs:
the cases of ``perfbench/tests/test_archs.py`` (pinned leaf hashes, an unknown
``arch`` refused with the modules found, an unread key refused by name, the
contract on every module found and on one brought by path), and for
``archs/nemotron_h.py`` its counts against the published ones and the served
path against its plain reference."""
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import archs, reference, shapes
from perfbench.weights import load_config, model_from_config
from pilottai_tpu.engine.batcher import ContinuousBatcher, GenRequest

DATA = Path(__file__).resolve().parents[1] / "perfbench" / "tests" / "data"
PINS = json.loads((DATA / "pins.json").read_text())
SEED = 2_147_484_001        # over 2**31, as the driver's are
COUNTS = (("attn_params", ()), ("mlp_params_one", ()), ("params_held", ()),
          ("params_active", (False,)), ("attention_flops", (10.0,)),
          ("request_flops", (3, 2, 1)), ("decode_step_weight_bytes", ()),
          ("flash_prefill_flops", (10.0,)), ("flash_prefill_bytes", (7.0,)))


def sha(a) -> str:
    a = np.asarray(a)
    if a.dtype.name == "bfloat16":
        a = a.view(np.uint16)
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()


def test_the_accepted_configurations_weights_are_the_numbers_pinned_before_this_pr():
    """A leaf's hash is exact on every machine: the two accepted cells serve
    the weights they served before the harness learnt a second family."""
    m = model_from_config(load_config(str(DATA / "tiny.json")))
    flat = jax.tree_util.tree_flatten_with_path(archs.of(m).make_stack(m, SEED))[0]
    got = {jax.tree_util.keystr(p): sha(v) for p, v in flat}
    assert got == PINS[f"tiny.json:{SEED}:leaves"]


def write_config(tmp_path, base="tiny.json", **changes):
    cfg = json.loads((DATA / base).read_text())
    cfg.update(changes)
    path = tmp_path / "cfg.json"
    path.write_text(json.dumps({k: v for k, v in cfg.items() if v != "<drop>"}))
    return str(path)


@pytest.mark.parametrize("arch", ["mixtral", "<drop>"])
def test_an_unknown_or_missing_arch_is_refused_with_the_modules_found(tmp_path, arch):
    with pytest.raises(ValueError) as err:
        load_config(write_config(tmp_path, arch=arch))
    assert "mistral" in str(err.value) and "nemotron_h" in str(err.value)
    assert "arch" in str(err.value)


@pytest.mark.parametrize("base, key", [("tiny.json", "attention_bias"),
                                       ("tiny-nemotron.json", "moe_router_topk_scaling")])
def test_a_key_nobody_reads_is_refused_by_name(tmp_path, base, key):
    with pytest.raises(ValueError, match=key):
        load_config(write_config(tmp_path, base, **{key: 1}))


@pytest.mark.parametrize("module", ["mistral", "nemotron_h",
                                    str(DATA / "archs" / "tiny_window.py")])
def test_the_contract_holds_on_the_module(module):
    mod = archs.load(module)
    assert all(hasattr(mod, n) for n in archs.REQUIRED)
    assert not set(mod.READS) & set(mod.IGNORES)
    assert all(isinstance(r, str) and r for r in mod.IGNORES.values())


@pytest.mark.parametrize("config", ["mixtral-8x7b", "nemotron-3-super-ep4"])
def test_shapes_hands_on_to_the_configurations_own_module(config):
    cfg = load_config(config)           # every published key is read or ignored
    m = model_from_config(cfg)
    assert hash(m) == hash(model_from_config(load_config(config)))
    for fn, args in COUNTS:
        assert getattr(shapes, fn)(m, *args) == getattr(archs.of(m), fn)(m, *args)


def test_nemotron_counts_are_the_published_ones_and_the_cut_is_stated():
    import dataclasses

    cfg = load_config("nemotron-3-super-ep4")
    m = model_from_config(cfg)
    a = archs.of(m)
    cut = cfg["reduced_from_source"]
    assert m.pattern == "MEMEMEM*EME" == cut["hybrid_override_pattern"]["published"][:11]
    assert (m.experts_held, m.experts_routed, m.experts_per_tok) == (128, 512, 22)
    assert (m.vocab, cut["vocab_size"]["published"]) == (32768, 131072)
    # this chip: 4.648 B held (9.30 GB in bfloat16), 1.14 B active a token
    assert a.params_held(m) == 4_648_163_712
    assert round(a.params_active(m) / 1e9, 3) == 1.142
    assert (a.mamba_params(m), a.attn_params(m), a.mlp_params_one(m)) == (
        109_635_968, 35_651_584, 5_505_024)
    # the equations reproduce the published totals: 120.67 B, 12.23 B active
    whole = dataclasses.replace(
        m, pattern=cut["hybrid_override_pattern"]["published"], experts_held=512, vocab=131072)
    assert round(a.params_held(whole) / 1e9, 2) == 120.67
    active = sum({"M": a.mamba_params(whole), "*": a.attn_params(whole),
                  "E": a.expert_layer_params(whole, 22)}[k] for k in whole.pattern)
    assert round((active + whole.vocab * whole.hidden) / 1e9, 2) == 12.23
    # a decode step at 64 rows: 94% of the held experts reached, about 8.6 GB
    assert round(a.experts_reached(m, 64) / 128, 2) == 0.94
    assert 8.5e9 < a.decode_step_weight_bytes(m) < 8.7e9
    # the program is told the same share
    pc = a.program_config(cfg, m)
    assert pc.param_count() == a.params_held(m)
    assert (pc.experts_held, pc.n_experts, pc.n_kv_layers, pc.recurrent) == ((0, 128), 512, 1, True)


def test_which_device_operations_are_the_recurrences():
    m = model_from_config(load_config("nemotron-3-super-ep4"))
    a = archs.of(m)
    scan = ("multiply_reduce_fusion_f32_64_128_64", "broadcast_multiply_fusion_f32_64_128_64",
            "fusion_f32_8_128_128_8_16", "convolution-base-dilated_f32_8_8_16_64_128",
            "fusion_bf16_8_128_8_16_64", "bitcast_add_fusion_f32_8_16_64_128")
    other = ("ragged-dot-none_bf16_1408_2688", "fusion_bf16_64_4096", "while_s32",
             "flash_attention_bf16_8_1024_32_128", "fusion_f32_64_32768", "copy_bf16_64_2_2048_128")
    assert all(a.ssm_scan_op(m, n) for n in scan)
    assert not any(a.ssm_scan_op(m, n) for n in other)


@pytest.fixture(scope="module")
def tiny():
    cfg = load_config(str(DATA / "tiny-nemotron.json"))
    m = model_from_config(cfg)
    return cfg, m, archs.of(m)


def served(cfg, m, a, seed, dtype, lengths=(150, 90, 33), new=20):
    """What the program serves for a group of unequal prompts: prefill into
    the KV cache and the state pool, then decode steps through both."""
    pc = a.program_config(cfg, m).replace(dtype=dtype)
    params = jax.tree.map(
        lambda x: x.astype(dtype) if x.dtype == jnp.bfloat16 else x,
        a.program_params(m, seed, False))
    b = ContinuousBatcher(pc, params, n_slots=4, max_seq_len=512, chunk_size=1,
                          admit_batch=4, on_tpu=False, cache_dtype=dtype)
    b.start()
    try:
        rng = np.random.default_rng(seed)
        prompts = [[int(t) for t in rng.integers(1, m.vocab, size=n)] for n in lengths]
        reqs = [GenRequest(prompt_ids=p, max_new_tokens=new, eos_id=-1) for p in prompts]
        b._submit_together(reqs)
        return [{"prompt": p, "served": r.future.result(timeout=600)}
                for p, r in zip(prompts, reqs)]
    finally:
        b.stop()


@pytest.mark.parametrize("seed", [7, 11, 13, SEED])
def test_the_selection_bias_spreads_fresh_tokens_evenly_over_the_experts(tiny, seed):
    """The bias is balanced when the weights are made (``stack_layers``), so
    which experts are busy, and how many of them this chip holds, is not the
    seed's luck. Tokens it was not balanced on load every expert of every
    layer with 0.7-1.3 of the mean (1,024 tokens x 3 of 16: sampling alone
    is 0.07 either way an expert), and the stack the program serves holds the same bias."""
    from perfbench.weights import seed_key

    _, m, a = tiny
    key = seed_key(seed)
    served = [lw["bias"] for lw in a.make_stack(m, seed)["layers"] if "bias" in lw]
    tokens = jax.random.randint(jax.random.fold_in(key, 5), (4, 256), 0, m.vocab)
    xs = [a._embed_fn(m)(t, key) for t in tokens]
    for _, kind, lw in a.stack_layers(m, key):
        if kind == "E":
            assert np.array_equal(lw["bias"], served.pop(0)) and np.any(np.asarray(lw["bias"]))
            u = reference._rms(jnp.concatenate(xs), lw["norm"], m.rms_eps)
            _, chosen = jax.lax.top_k(
                jax.nn.sigmoid(u @ lw["router"]) + lw["bias"], m.experts_per_tok)
            load = np.bincount(np.asarray(chosen).ravel(), minlength=m.experts_routed)
            assert 0.7 <= load.min() / load.mean() and load.max() / load.mean() <= 1.3
        xs = [a._layer_fn(m, kind, "f32")(x, lw)[0] for x in xs]
    assert not served


def test_the_served_path_agrees_with_the_plain_reference(tiny):
    """Teacher-forced over what was served, the reference's full forward pass
    (float32 at ``highest``, the recurrence one token at a time, every held
    expert computed for every token) puts each served token first. In float32
    the program differs from it by summation order alone: 1e-3 of a logit is
    a hundred times that, and under what a lower precision reads at the mean
    (``act8`` 0.004-0.025 by seed, ``w4`` 0.17-0.36)."""
    cfg, m, a = tiny
    samples = served(cfg, m, a, 7, jnp.float32)
    ref = reference.served_gaps(m, 7, samples, "f32")
    assert ref["tokens"] == 60 and ref["gap_max"] <= 1e-3 and ref["argmax_agree"] == 1.0
    logits = ref["logits"]
    assert logits[0][0].shape == (20, m.vocab) and np.isfinite(logits[0][1]).all()
    for mode, least in (("act8", 0.002), ("w4", 0.05)):
        ctl = reference.served_gaps(m, 7, samples, mode, logits)
        assert ctl["gap_mean"] > least, mode      # the controls' modes are honoured


def test_in_bfloat16_the_gap_is_rounding_and_router_ties(tiny):
    """As served (bfloat16 activations and cache, float32 state): away from a
    tie of the router the served token lies within rounding of the
    reference's best; at a tie (the 3rd and 4th selection scores within 0.01)
    the program may as rightly take the other expert, which moves a logit by
    a whole expert and is not read."""
    cfg, m, a = tiny
    samples = served(cfg, m, a, 11, jnp.bfloat16)
    clear = reference.served_gaps(m, 11, samples, "f32", router_tie=0.01)
    assert clear["tokens"] >= 15 and clear["tokens_at_a_router_tie"] > 0
    assert clear["gap_max"] <= 0.25 and clear["gap_mean"] <= 0.02


def test_the_whole_command_runs_the_new_family_on_the_cpu(capsys):
    """``perfbench/run.py`` end to end at the tiny shape, five seconds, on the
    CPU (a rehearsal, no measurement): the server starts through ``cli.
    run_serve``, the open schedule is served, nothing is built inside the
    window, the reference agrees, and the traced line carries the counter
    metric and leaves out the two that need a device trace."""
    from perfbench import run

    bench = {
        "workloads": [{"name": "tiny.cell", "config": str(DATA / "tiny-nemotron.json"),
                       "traffic": str(DATA / "tiny-open.json"), "chips": 1}],
        "end_to_end": [{"name": "step_latency_p50_ms", "unit": "ms"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": n, "unit": "%"} for n in (
            "moe.held_share_pct", "moe_grouped_roofline", "ssm_scan_roofline",
            "batcher.prefix_hit_pct")],
        # bfloat16 as served, ties read: a flipped expert of three moves a
        # logit by up to 0.5 at this width (see the test above)
        "limits": {"gap_max": 1.0, "gap_mean": 0.05, "router_tie": 0.0},
    }
    code = run.main(["--workload", "tiny.cell", "--seed", str(SEED), "--seconds", "5",
                     "--trace", "1"], bench=bench, platform="cpu")
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert code == 0 and line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["window"]["built_names"] == []
    assert 15.0 < line["metrics"]["moe.held_share_pct"]["value"] < 35.0   # 4 of 16 held
    assert set(line["metrics"]) == {"moe.held_share_pct"}
    counters = line["window"]["counters"]
    assert counters["engine.prefix_bypassed_recurrent"] == counters["engine.admitted"]
    assert "engine.prefix_hits" not in counters
