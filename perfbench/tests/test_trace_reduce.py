"""The trace reduction on ``data/small.xplane.pb``: recorded on one v5e chip
by ``tools/record_trace.py`` (three rounds of an 8-step scan of 1024 x 1024
matmuls and one flat matmul, each round followed by a 20 ms host sleep under a
``bench.host_wait`` annotation). The expected numbers were read off that file
once, by hand from its event list."""
from pathlib import Path

import pytest

from perfbench import trace_reduce

TRACE = str(Path(__file__).parent / "data" / "small.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_file(TRACE)


def test_busy_union_and_window(reduced):
    assert reduced["planes"] == 1
    # the session between start_trace's return and stop_trace's call
    assert reduced["window_s"] == pytest.approx(0.067128493, rel=1e-6)
    # union of the device's operation intervals inside it: two and a bit
    # rounds of (scan 103 us + matmul 15 us); the first round began before
    # start_trace returned and is clipped
    assert reduced["busy_s"] == pytest.approx(0.000237137, rel=1e-6)
    assert 0.0 < reduced["busy_s"] < reduced["window_s"]


def test_operations_by_name_count_nothing_twice(reduced):
    outer, every = reduced["outer_ops_s"], reduced["all_ops_s"]
    # three while loops of ~97 us; their bodies' fusions are inside them
    assert outer["while_s32"] == pytest.approx(0.000290874, rel=1e-6)
    assert "fusion_bf16_1024_1024" not in outer
    assert every["fusion_bf16_1024_1024"] == pytest.approx(0.000277743, rel=1e-6)
    assert every["fusion_bf16_1024_1024"] < outer["while_s32"]
    assert trace_reduce.kernel_seconds(reduced, "fusion_bf16_1024") == every["fusion_bf16_1024_1024"]
    assert trace_reduce.kernel_seconds(reduced, "no_such_kernel") == 0.0


def test_idle_gaps_are_named_by_the_host(reduced):
    gaps = reduced["idle_gaps_s"]
    # three sleeps of 20 ms and more under the annotation
    assert gaps["host:bench.host_wait"] == pytest.approx(0.06689134, rel=1e-6)
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    assert trace_reduce.top(gaps, 1)[0][0] == "host:bench.host_wait"


def test_short_names():
    assert trace_reduce.short_name(
        "%fusion.8 = bf16[1024,14336]{1,0:T(8,128)(2,1)} fusion(bf16[1,2]{1,0} %p)"
    ) == "fusion_bf16_1024_14336"
    assert trace_reduce.short_name(
        "%while = (s32[]{:T(128)}, bf16[4,4]{1,0}) while((s32[]) %t)") == "while_s32"
    assert trace_reduce.short_name("%copy-done.1 = bf16[8]{0} copy-done(%x)") == "copy-done_bf16_8"


def test_union():
    total, merged = trace_reduce.union_s([(0, 10), (5, 20), (30, 40), (40, 41)])
    assert total == pytest.approx(31e-9)
    assert merged == [(0, 20), (30, 41)]
