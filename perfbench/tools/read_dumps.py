"""Read the proof runs' dumps (``proof.sh``) off the chip:

    python3 perfbench/tools/read_dumps.py [--span 50] chiprun_out/<tag>.*.dump.json

For each run, the program's and the controls' ``gap_max`` / ``gap_mean`` over
the positions clear of a router tie at several thresholds, so that the
threshold and the limits are set from readings; and for a closed loop, the
window's rate by three counts (credited by overlap, credited at completion,
the device's own counter) and the overlap credit of every ``--span`` second
window laid inside the run, a second apart."""
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perfbench import loadgen  # noqa: E402

TIES = (0.0, 0.005, 0.01, 0.02, 0.03, 0.05)


def gaps(reading, tie):
    pairs = [g for g, m in zip(reading["per_token"]["gap"], reading["per_token"]["margin"]) if m >= tie]
    return (max(pairs), sum(pairs) / len(pairs), len(pairs)) if pairs else (None, None, 0)


def main(paths, span=50.0):
    for path in paths:
        d = json.loads(Path(path).read_text())
        print(f"== {d['workload']} seed {d['seed']}")
        for tie in TIES:
            row = [f"tie {tie:<5}"]
            for name, reading in sorted(d["readings"].items()):
                mx, mean, n = gaps(reading, tie)
                row.append(f"{name}: max {mx:.4f} mean {mean:.5f} n {n}")
            print("   ", " | ".join(row))
        if d["loop"] != "closed":
            continue    # an open loop: no rate to read
        recs = [r for r in d["records"] if r.get("ok")]
        t0, t1 = d["t0"], d["t1"]
        done_in = sum(r["completion_tokens"] for r in recs if t0 <= r["done"] < t1)
        print(f"    window {t1 - t0:.1f} s: by overlap {loadgen.credited_tokens(recs, t0, t1) / (t1 - t0):.2f}, "
              f"at completion {done_in / (t1 - t0):.2f}, device counter "
              f"{d['counters'].get('engine.generated_tokens_device', 0) / (t1 - t0):.2f} tokens/s")
        if t1 - t0 >= span + 1:
            rates = []
            a = t0
            while a + span <= t1:
                rates.append(loadgen.credited_tokens(recs, a, a + span) / span)
                a += 1.0
            print(f"    {span:g} s windows a second apart, by overlap: least {min(rates):.2f}, "
                  f"most {max(rates):.2f}: " + " ".join(f"{r:.1f}" for r in rates[::5]))
            ends = []
            a = t0
            while a + span <= t1:
                ends.append(sum(r["completion_tokens"] for r in recs if a <= r["done"] < a + span) / span)
                a += 1.0
            print(f"    the same windows, at completion: least {min(ends):.2f}, most {max(ends):.2f}")


if __name__ == "__main__":
    if sys.argv[1:2] == ["--span"]:
        main(sys.argv[3:], float(sys.argv[2]))
    else:
        main(sys.argv[1:])
