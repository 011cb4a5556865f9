"""The operation and byte functions against values worked by hand at the two
configurations' widths."""
import pytest

from perfbench import shapes
from perfbench.weights import load_config, model_from_config

E, F, V = 4096, 14336, 32000
ATTN = 2 * E * 4096 + 2 * E * 1024            # wq, wo, wk, wv = 41,943,040
MLP = 3 * E * F                               # 176,160,768


@pytest.fixture(scope="module")
def mistral():
    return model_from_config(load_config("mistral-7b"))


@pytest.fixture(scope="module")
def mixtral():
    return model_from_config(load_config("mixtral-8x7b"))


def test_parameters_held(mistral, mixtral):
    assert ATTN == 41_943_040 and MLP == 176_160_768
    # 32 x (attention + MLP + two norms) + embedding + head + final norm
    assert shapes.params_held(mistral) == 32 * (ATTN + MLP + 2 * E) + 2 * V * E + E == 7_241_732_096
    # 6 x (attention + 8 experts + router + two norms) + embedding + head + final norm
    per_layer = ATTN + 8 * MLP + E * 8 + 2 * E
    assert per_layer == 1_451_270_144
    assert shapes.params_held(mixtral) == 6 * per_layer + 2 * V * E + E == 8_969_768_960


def test_parameters_active_count_routed_experts_only(mistral, mixtral):
    assert shapes.params_active(mistral) == 32 * (ATTN + MLP) + V * E == 7_110_393_856
    # two of eight experts, the router, and the head: 2.50 B a token
    assert shapes.params_active(mixtral) == 6 * (ATTN + 2 * MLP + 8 * E) + V * E == 2_496_856_064
    assert shapes.params_active(mixtral, with_head=False) == 2_496_856_064 - V * E


def test_request_flops_by_hand(mistral):
    # 3 prompt tokens, 2 output tokens, nothing cached: 3 + 1 tokens through the
    # trunk, the head twice, and (1+2+3) + (3+1) = 10 query-key pairs a head.
    trunk = 2 * 32 * (ATTN + MLP)
    want = trunk * 4 + 2 * V * E * 2 + 4 * 32 * 128 * 32 * 10
    assert shapes.request_flops(mistral, 3, 2) == pytest.approx(want, rel=1e-12)
    # a cached prefix of 2 tokens leaves 1 prompt token and its 3 pairs
    want = trunk * 2 + 2 * V * E * 2 + 4 * 32 * 128 * 32 * (3 + 4)
    assert shapes.request_flops(mistral, 3, 2, cached_prefix=2) == pytest.approx(want, rel=1e-12)


def test_decode_step_weight_bytes(mistral):
    # int8 weights, one bfloat16 scale per output column, bfloat16 norms
    attn = ATTN + 2 * (4096 + 1024 + 1024 + 4096)
    mlp = MLP + 2 * (F + F + E)
    want = 32 * (attn + mlp + 2 * 2 * E) + V * E + 2 * V + 2 * E
    assert shapes.decode_step_weight_bytes(mistral) == want == 7_113_742_848


def test_flash_prefill(mistral):
    # one 1,024-token prompt: 1024 * 1025 / 2 causal pairs
    pairs = 1024 * 1025 / 2
    assert shapes.flash_prefill_flops(mistral, pairs) == 4 * 32 * 128 * 32 * pairs
    assert shapes.flash_prefill_bytes(mistral, 1024) == 2 * 32 * 1024 * (2 * 4096 + 2 * 1024)


def test_peaks_table_refuses_an_unknown_kind():
    assert shapes.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert shapes.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        shapes.peaks("cpu")
