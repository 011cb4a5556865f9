"""A traced tiny cell rehearsed on the CPU with the nine readers of the
request timeline declared: every one finds something to read in a program that
stamps the marks and counts the fills, and the flights' self times add up to
the edge's span. A rehearsal and no measurement."""
import json

from perfbench.tests.test_rehearsal import bench
from perfbench.tools import timeline as tool

NINE = {
    "edge.self_p50_ms": "ms", "handler.self_p50_ms": "ms",
    "batcher.queue_wait_p50_ms": "ms", "model_step.prefill_p50_ms": "ms",
    "batcher.tpot_p50_ms": "ms",
    "batcher.prefill_fill_pct.latency": "%", "batcher.prefill_fill_pct.rate": "%",
    "batcher.decode_fill_pct.latency": "%", "batcher.decode_fill_pct.rate": "%",
}


def test_traced_tiny_cell_reports_all_nine_and_a_timeline_that_adds_up(capsys):
    declared = bench("tiny-open.json")
    declared["per_layer"] = [{"name": n, "unit": u} for n, u in NINE.items()]
    argv = ["--workload", "tiny.cell", "--seed", "2147483900", "--seconds", "5",
            "--trace", "1"]
    code = tool.main(argv, bench=declared, platform="cpu")
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert code == 0 and line["correct"] is True
    assert set(line["metrics"]) == set(NINE)
    for name, unit in NINE.items():
        got = line["metrics"][name]
        assert got["unit"] == unit and got["value"] >= 0.0
        assert unit == "ms" or 0.0 < got["value"] <= 100.0     # a fill is a share
    said = [l for l in out.err.splitlines() if l.startswith("[timeline] ")]
    found = json.loads(said[-1][len("[timeline] "):])
    assert found["flights"] == line["attempted"] and found["sum_off_worst_ms"] < 5.0
