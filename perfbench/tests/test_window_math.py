"""The arithmetic of a window, with no server: overlap credit, percentiles,
and that no schedule quantity comes from the seed."""
import pytest

from perfbench import loadgen, traffic


def rec(sent, done, n, ok=True):
    return {"sent": sent, "done": done, "completion_tokens": n, "ok": ok}


def test_credit_of_a_straddling_request_sums_to_its_tokens_across_windows():
    r = rec(8.0, 13.0, 100)
    windows = [(0.0, 10.0), (10.0, 20.0)]
    shares = [loadgen.credited_tokens([r], a, b) for a, b in windows]
    assert shares == [pytest.approx(40.0), pytest.approx(60.0)]
    assert sum(shares) == pytest.approx(100.0)
    # across three windows, over both edges of the middle one
    long = rec(9.0, 21.0, 120)
    parts = [loadgen.credited_tokens([long], a, b) for a, b in [(0, 10), (10, 20), (20, 30)]]
    assert parts == [pytest.approx(10.0), pytest.approx(100.0), pytest.approx(10.0)]


def test_credit_inside_outside_and_failed():
    assert loadgen.credited_tokens([rec(2.0, 4.0, 64)], 0.0, 10.0) == 64
    assert loadgen.credited_tokens([rec(12.0, 14.0, 64)], 0.0, 10.0) == 0
    assert loadgen.credited_tokens([rec(2.0, 4.0, 64, ok=False)], 0.0, 10.0) == 0


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert loadgen.percentile(values, 0.95) == 95
    assert loadgen.percentile(values, 0.50) == 50
    assert loadgen.percentile([7.0], 0.95) == 7.0
    assert loadgen.percentile([1, 2, 3, 4], 0.95) == 4


def test_a_failed_step_counts_as_slower_than_any():
    run = {"t0": 0.0, "t1": 10.0, "records": [
        {"id": "a", "due": 1.0, "sent": 1.0, "done": 2.0, "status": 200, "ok": True,
         "in_window": True, "completion_tokens": 8, "max_tokens": 8, "expect_prompt": 10},
        {"id": "b", "due": 2.0, "sent": 2.0, "done": 2.5, "status": 429, "ok": False,
         "in_window": True, "completion_tokens": 0, "max_tokens": 8, "expect_prompt": 10},
    ]}
    s = loadgen.window_summary(run, "open")
    assert s["attempted"] == 2 and s["failed"] == 1
    assert s["step_latency_worst_ms"] == loadgen.FAILED_MS
    assert loadgen.step_latencies_ms(run) == [1000.0, loadgen.FAILED_MS]


@pytest.mark.parametrize("mix_name", ["agent-loop", "rag-prefill"])
def test_the_seed_reaches_only_the_bytes(mix_name):
    mix = traffic.load_mix(mix_name)
    for s in (0, len(mix["sessions"]) - 1):
        last = len(mix["sessions"][s]["turns"]) - 1
        a, na = traffic.request_body(mix, 1, s, last)
        b, nb = traffic.request_body(mix, 2_147_483_999, s, last)
        assert na == nb and a["max_tokens"] == b["max_tokens"]
        assert [len(m["content"]) for m in a["messages"]] == [len(m["content"]) for m in b["messages"]]
        assert a["messages"][-1]["content"] != b["messages"][-1]["content"]
        assert na + a["max_tokens"] <= 4096 - 8
    if mix["loop"] == "open":
        assert traffic.open_schedule(mix, 60.0) == traffic.open_schedule(mix, 60.0)


def test_shared_pieces_share_bytes_and_laps_do_not():
    mix = traffic.load_mix("agent-loop")
    a = traffic.build_messages(mix, 5, 0, 1)
    b = traffic.build_messages(mix, 5, 2, 0)      # same role, another session
    assert a[0]["content"] == b[0]["content"] and len(a[0]["content"]) == 2048
    assert a[1]["content"] != b[1]["content"]
    # a step's scratchpad is rendered afresh: nothing of the last step's in it
    assert not a[1]["content"].startswith(traffic.build_messages(mix, 5, 0, 0)[1]["content"])
    # the tail past the shared prompt stays under the 1,024 the program compiles
    for s, sess in enumerate(mix["sessions"][:16]):
        _, n = traffic.request_body(mix, 5, s, len(sess["turns"]) - 1)
        assert 2048 < n <= 3072
    closed = traffic.load_mix("rag-prefill")
    assert (traffic.build_messages(closed, 5, 0, 0, lap=0)[0]["content"]
            != traffic.build_messages(closed, 5, 0, 0, lap=1)[0]["content"])
    assert traffic.prompt_tokens([{"role": "user", "content": "abc"}]) == 1 + len(
        "<|user|>\nabc\n<|assistant|>\n")
