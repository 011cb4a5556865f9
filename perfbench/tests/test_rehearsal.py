"""The whole command rehearsed on the CPU at a two-layer shape and a
five-second window: everything of a run but the look for a chip. A result
here is a rehearsal and no measurement.

Also the control and the faults, at a size a test run can hold: the reference
in int4 weights put in the program's place must read over the limit, and a
run whose timed path is broken underneath (a token altered where it is
produced; an answer cut short) must come out as not correct."""
import json
from pathlib import Path

import pytest

from perfbench import run

DATA = Path(__file__).parent / "data"
# The tiny shape's own limits, between its own readings on the CPU over 16
# sampled requests (about 160 tokens clear of a router tie) on six seeds:
# program gap_max 0.0-0.0096, gap_mean 0-0.00006; int4-weight control gap_max
# 0.33-1.36, gap_mean 0.017-0.029. Int8 activations read 0.017-0.052 and
# 0.0002-0.0008 here: too near the program's for a limit at this width, so
# that control is judged at the cells' own size on the chip, by the same
# ``judge`` (``--controls act8``; readings in PERF.md).
LIMITS = {"gap_max": 0.06, "gap_mean": 0.003, "router_tie": 0.05}
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def bench(mix: str, config: str = "tiny.json"):
    return {
        "workloads": [{"name": "tiny.cell", "config": str(DATA / config),
                       "traffic": str(DATA / mix), "chips": 1}],
        "end_to_end": [
            {"name": "step_latency_p50_ms", "unit": "ms"},
            {"name": "step_latency_p65_ms", "unit": "ms"},
            {"name": "tokens_per_s", "unit": "tokens/s"},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "batcher.ttft_p50_ms", "unit": "ms"},
            {"name": "batcher.generated_tokens_per_s", "unit": "tokens/s"},
            {"name": "flash_prefill_roofline", "unit": "%"},
            {"name": "device.idle_pct.rate", "unit": "%"}],
        "limits": LIMITS,
    }


def drive(capsys, mix, seed, trace=0, fault=None, controls="", config="tiny.json"):
    argv = ["--workload", "tiny.cell", "--seed", str(seed), "--seconds", "5",
            "--trace", str(trace)] + (["--controls", controls] if controls else [])
    code = run.main(argv, bench=bench(mix, config), platform="cpu", fault=fault)
    out = capsys.readouterr()
    return code, json.loads(out.out.strip().splitlines()[-1]), out


def test_last_line_and_equal_schedules_for_two_seeds(capsys):
    code, first, out = drive(capsys, "tiny-open.json", 11)
    assert code == 0 and KEYS <= set(first) and list(first)[-1] == "checks"
    assert first["correct"] is True and first["failed"] == 0
    assert set(first["metrics"]) == {"step_latency_p50_ms", "step_latency_p65_ms", "setup_s"}
    assert first["device"]["platform"] == "cpu"          # a rehearsal, said so
    assert "[check] gap_max:" in out.err.strip().splitlines()[-2]
    assert first["window"]["latencies_ms"] and first["window"]["built_names"] == []
    _, second, _ = drive(capsys, "tiny-open.json", 2_147_484_001)
    a, b = first["window"]["summary"], second["window"]["summary"]
    for key in ("attempted", "prompt_tokens_due", "output_tokens_due"):
        assert a[key] == b[key] and a[key] > 0
    assert second["window"]["compiles_in_window"]["requests"] == 0


def test_traced_run_reports_per_layer_metrics_and_leaves_out_what_it_cannot_read(capsys):
    code, line, _ = drive(capsys, "tiny-closed.json", 12, trace=1)
    assert code == 0 and line["correct"] is True
    assert "batcher.generated_tokens_per_s" in line["metrics"]
    # no device plane on the CPU: the roofline and the idle share have nothing
    # to read and are left out, never reported as 0
    assert "flash_prefill_roofline" not in line["metrics"]
    assert "device.idle_pct.rate" not in line["metrics"]
    assert "tokens_per_s" not in line["metrics"] and "breakdown" in line


def test_control_in_lower_precision_comes_out_not_correct(capsys, mode="w4"):
    """The control goes through the run's own ``judge``: put in the program's
    place with everything else of the run as it was, it is not correct."""
    _, line, out = drive(capsys, "tiny-closed.json", 13, controls=mode, config="tiny-moe.json")
    assert line["correct"] is True
    control = line["window"]["controls"][f"control_{mode}"]
    assert control["correct"] is False
    assert f"control_{mode} in the program's place: correct False" in out.out


@pytest.mark.parametrize("fault, check", [
    ("alter_token", "gap_max"), ("cut_short", "short_answers")])
def test_a_broken_timed_path_is_not_correct(capsys, fault, check):
    code, line, _ = drive(capsys, "tiny-closed.json", 14, fault=fault)
    assert code == 0 and line["correct"] is False
    c = line["checks"][check]
    assert c["value"] > c["limit"]
    if fault == "cut_short":
        assert line["failed"] == line["attempted"] > 0


WINDOW_FORGOTTEN = "PERFBENCH_TINY_WINDOW_FORGET"   # data/archs/tiny_window.py's own switch


@pytest.mark.parametrize("forget", [False, True])
def test_an_architecture_brought_as_new_files_runs_the_whole_command(capsys, monkeypatch, forget):
    """``data/tiny-window.json`` names ``data/archs/tiny_window.py``: a sliding
    window on three layers of four (16 keys; the prompts are 120-200 tokens)
    and post-norms, neither of which ``archs/mistral.py`` can say. The run is
    correct against the module's own reference, and not correct against the
    same reference made to forget the window."""
    if forget:
        monkeypatch.setenv(WINDOW_FORGOTTEN, "1")
    code, line, _ = drive(capsys, "tiny-closed.json", 15, config="tiny-window.json")
    assert code == 0 and line["failed"] == 0 and line["attempted"] > 0
    assert line["correct"] is not forget
    gap = line["checks"]["gap_max"]
    assert (gap["value"] > gap["limit"]) is forget
