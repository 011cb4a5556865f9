"""The seam between the generic harness and an architecture's module
(``perfbench/archs/``): the weights and the reference are the numbers they were
before the Mistral family's code moved into its module, a configuration cannot
name a module that is not there or carry a key nobody reads, and a module
that lacks a name of the contract fails when it is loaded, by that name."""
import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from perfbench import archs, shapes
from perfbench.weights import load_config, model_from_config

DATA = Path(__file__).parent / "data"
# Recorded at commit 1c737b2, before anything moved, by the same calls
# (``weights.make_stack`` and ``reference.logits_at`` there): SHA-256 of every
# leaf's bytes on two seeds, one of them over 2**31, and of the logits and the
# margins for one 40-token sequence in each mode. A leaf's hash is exact
# everywhere; a float32 pass can differ in its last bits on another CPU or
# jaxlib, so where a logits hash differs look at the leaves first.
PINS = json.loads((DATA / "pins.json").read_text())
SEEDS = (7, 2_147_484_001)
SEQ = [(i * 37 + 11) % 512 for i in range(40)]


def sha(a) -> str:
    a = np.asarray(a)
    if a.dtype.name == "bfloat16":
        a = a.view(np.uint16)
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()


@pytest.mark.parametrize("config", ["tiny.json", "tiny-moe.json"])
def test_weights_and_reference_are_the_numbers_recorded_before_the_move(config):
    m = model_from_config(load_config(str(DATA / config)))
    arch = archs.of(m)
    assert arch.__name__ == "perfbench.archs.mistral"
    for seed in SEEDS:
        flat = jax.tree_util.tree_flatten_with_path(arch.make_stack(m, seed))[0]
        got = {jax.tree_util.keystr(p): sha(v) for p, v in flat}
        assert got == PINS[f"{config}:{seed}:leaves"]
    for mode in ("f32", "act8", "w4"):
        (logits, margin), = arch.logits_at(m, SEEDS[1], [SEQ], [8], mode)
        pin = PINS[f"{config}:{SEEDS[1]}:logits:{mode}"]
        assert logits.shape == (8, m.vocab) and margin.shape == (8,)
        assert (sha(logits), sha(margin)) == (pin["logits"], pin["margin"]), mode


def write_config(tmp_path, **changes):
    cfg = json.loads((DATA / "tiny.json").read_text())
    cfg.update(changes)
    path = tmp_path / "cfg.json"
    path.write_text(json.dumps({k: v for k, v in cfg.items() if v != "<drop>"}))
    return str(path)


@pytest.mark.parametrize("arch", ["mixtral", "<drop>"])
def test_an_unknown_or_missing_arch_is_refused_with_the_modules_found(tmp_path, arch):
    with pytest.raises(ValueError) as err:
        load_config(write_config(tmp_path, arch=arch))
    assert "mistral" in str(err.value) and "arch" in str(err.value)


def test_a_key_nobody_reads_is_refused_by_name(tmp_path):
    with pytest.raises(ValueError, match="attention_bias"):
        load_config(write_config(tmp_path, attention_bias=True))
    # a published key that changes nothing loads, under the module's reason for it
    assert archs.load("mistral").IGNORES["torch_dtype"]
    assert load_config(write_config(tmp_path, torch_dtype="bfloat16"))["arch"] == "mistral"


def test_what_the_module_refuses_it_still_refuses(tmp_path):
    for changes, word in (({"sliding_window": 4096}, "sliding window"),
                          ({"tie_word_embeddings": True}, "tied"),
                          ({"hidden_act": "gelu"}, "silu")):
        with pytest.raises(ValueError, match=word):
            model_from_config(load_config(write_config(tmp_path, **changes)))


def test_a_module_that_lacks_a_name_fails_at_load_by_that_name(tmp_path):
    source = Path(archs.__file__).with_name("mistral.py").read_text()
    lacking = tmp_path / "lacking.py"
    lacking.write_text(source.replace("def decode_step_weight_bytes(", "def _gone("))
    with pytest.raises(AttributeError, match="decode_step_weight_bytes"):
        load_config(write_config(tmp_path, arch=str(lacking)))


def test_the_contract_holds_on_each_module_found_and_shapes_hands_on():
    assert "mistral" in archs.found()
    for name in archs.found():
        module = archs.load(name)
        assert all(hasattr(module, n) for n in archs.REQUIRED)
        assert not set(module.READS) & set(module.IGNORES)
        assert all(isinstance(r, str) and r for r in module.IGNORES.values())
    m = model_from_config(load_config("mixtral-8x7b"))
    module = archs.of(m)
    assert hash(m) == hash(model_from_config(load_config("mixtral-8x7b")))
    for fn, args in (("attn_params", ()), ("mlp_params_one", ()), ("params_held", ()),
                     ("params_active", (False,)), ("attention_flops", (10.0,)),
                     ("request_flops", (3, 2, 1)), ("decode_step_weight_bytes", ()),
                     ("flash_prefill_flops", (10.0,)), ("flash_prefill_bytes", (7.0,))):
        assert getattr(shapes, fn)(m, *args) == getattr(module, fn)(m, *args)
