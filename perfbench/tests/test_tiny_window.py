"""The test architecture (``data/archs/tiny_window.py``, named by
``data/tiny-window.json``) against the module contract, and its counts against
values worked by hand. The whole command on it is rehearsed in
``test_rehearsal.py``."""
from pathlib import Path

import pytest

from perfbench import archs, shapes
from perfbench.weights import load_config, model_from_config

DATA = Path(__file__).parent / "data"
E, F, V, L = 128, 256, 512, 4
ATTN = 2 * E * 128 + 2 * E * 64               # wq, wo, wk, wv: 4 heads and 2 KV heads of 32
MLP = 3 * E * F


@pytest.fixture(scope="module")
def cfg():
    return load_config(str(DATA / "tiny-window.json"))


@pytest.fixture(scope="module")
def model(cfg):
    return model_from_config(cfg)


def test_it_is_found_through_the_configuration_and_keeps_the_contract(cfg, model):
    module = archs.of(model)
    assert Path(cfg["arch"]) == DATA / "archs" / "tiny_window.py" and module is archs.load(cfg["arch"])
    assert all(hasattr(module, n) for n in archs.REQUIRED)
    assert archs.unread_keys(cfg, module) == []
    assert (model.name, model.vocab, model.window, model.pattern) == ("tiny-window-test", V, 16, 4)
    assert hash(model) == hash(model_from_config(cfg))
    # what the benchmark's own family cannot say, and says so
    with pytest.raises(ValueError, match="sliding window"):
        archs.load("mistral").model_from_config(cfg)
    assert set(archs.unread_keys(cfg, archs.load("mistral"))) == {"post_norms", "sliding_pattern"}


def test_its_leaves_are_its_own(model):
    stack = archs.of(model).make_stack(model, 2_147_484_001)
    assert set(stack["layers"]) == {
        "ln1", "ln1_post", "ln2", "ln2_post", "wq", "wk", "wv", "wo", "wg", "wu", "wd"}
    assert stack["layers"]["ln1_post"].shape == (L, E)
    assert stack["layers"]["wq"][0].shape == (L, E, 128)


def test_counts_by_hand_through_the_generic_hand_ons(model):
    assert shapes.params_held(model) == L * (ATTN + MLP + 4 * E) + 2 * V * E + E == 723_072
    assert shapes.params_active(model) == L * (ATTN + MLP) + V * E
    # 20 prompt tokens, 3 output tokens: 22 queries. The one full layer's see
    # 1 + 2 + ... + 22 = 253 keys; each of the three window layers' see
    # 1 + ... + 16 = 136 and then 16 for the other six.
    pairs = 253 + 3 * (136 + 6 * 16)
    want = 2 * L * (ATTN + MLP) * 22 + 2 * V * E * 3 + 4 * 4 * 32 * pairs
    assert shapes.request_flops(model, 20, 3) == pytest.approx(want, rel=1e-12)
    # a cached prefix of 18 leaves the queries at positions 18 to 21: 19 + 20 +
    # 21 + 22 = 82 keys on the full layer, 4 x 16 on a window layer
    want = 2 * L * (ATTN + MLP) * 4 + 2 * V * E * 3 + 4 * 4 * 32 * (82 + 3 * 64)
    assert shapes.request_flops(model, 20, 3, cached_prefix=18) == pytest.approx(want, rel=1e-12)
    attn = ATTN + 2 * (128 + 64 + 64 + E)
    mlp = MLP + 2 * (F + F + E)
    assert shapes.decode_step_weight_bytes(model) == L * (attn + mlp + 4 * 2 * E) + V * E + 2 * V + 2 * E
    assert shapes.flash_prefill_bytes(model, 10) == 2 * L * 10 * (2 * 128 + 2 * 64)
    # one sum only: every layer counted at it (an upper bound for the window layers)
    assert shapes.flash_prefill_flops(model, 253.0) == 4 * 4 * 32 * L * 253.0
