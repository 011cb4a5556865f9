"""Made ``traffic/chat-burst.json`` once (PR 29). It is data now: the harness
never runs this, and ``--seed`` never reaches a schedule.

    python3 perfbench/tools/make_chat_burst.py
    python3 perfbench/tools/make_chat_burst.py <constant> <out.json>   # a candidate for tools/pick_schedule.py

The constants below are the only randomness; rerunning rewrites the same file.
``MEAN_RATE`` is half of the highest rate the system sustained in the sweep on
the chip (``tools/sweep.py``: 3.5 requests a second held, 5.0 did not): 1.75 a
second, four to six rows live. The issue sets 0.6 of that rate and falls to 0.5
where 0.6 spreads by more than 0.4% of the median: at 2.1 a second six seeds
spread ``step_latency_p50_ms`` by 3.2% and, once the selection bias was
balanced, three seeds by 1.6% (PERF.md, section 6, PR 29).

``CONSTANT`` was chosen on the chip. The nearest-rank median of some 85
requests follows a handful of arrivals that fall within milliseconds of an
admission's edge (an admission is 0.07-0.34 s of device time and every live
row waits for it), so the first constant, 29_2026 (79 due), spread the median
by 1.5% in the driver's two sets of six. Five other constants of this same
generator (274, 951, 1437, 1453, 330) ran on one server, twice each, and the
two that repeated best three times more: 330 read 2,779.8-2,785.6 ms in four
windows of five and 2,780.0-2,789.9 on three further seeds in runs of their
own (PERF.md, section 6, PR 29, third round). Rate, lengths, bursts and
``max_tokens`` are the issue's for every constant; 89 requests fall due in the
window (expected at this rate: 87.5), the bursts hold 6, 10 and 10 arrivals.
"""
import json
import math
import random
from pathlib import Path

CONSTANT = 330
OUT = Path(__file__).resolve().parents[1] / "traffic"

MEAN_RATE = 1.75           # requests a second over the window
WINDOW_S = 50.0
LEAD_IN_S = 15.0
BURSTS_AT_S = (10.0, 25.0, 40.0)   # of the window
BURST_S, BURST_X = 2.0, 3.0
TEMPLATE_TOKENS = 25       # BOS, "<|user|>\n", "\n<|assistant|>\n" around the user piece
LO, HI, MEDIAN, TENTH_OVER = 128, 1024, 320, 800
WARM_SESSIONS = 24


def rate_at(t: float, base: float) -> float:
    w = t - LEAD_IN_S
    return base * (BURST_X if any(a <= w < a + BURST_S for a in BURSTS_AT_S) else 1.0)


def chat_burst(constant: int = CONSTANT, horizon_s: float = LEAD_IN_S + WINDOW_S + 70.0):
    """One-turn sessions, nothing shared. Arrivals: exponential gaps at a base
    rate, three times it inside the bursts (thinning of a process at the
    burst rate). Prompt lengths log-normal: half under 320, a tenth over 800,
    held to 128-1,024 tokens with the chat template's 25 counted in."""
    rng = random.Random(constant)
    burst_s = len(BURSTS_AT_S) * BURST_S
    base = MEAN_RATE * WINDOW_S / (WINDOW_S - burst_s + BURST_X * burst_s)
    sigma = (math.log(TENTH_OVER) - math.log(MEDIAN)) / 1.2816
    sessions, t = [], 0.0
    while True:
        t += rng.expovariate(base * BURST_X)
        if t >= horizon_s:
            break
        if rng.random() * base * BURST_X >= rate_at(t, base):
            continue
        n = min(max(round(rng.lognormvariate(math.log(MEDIAN), sigma)), LO), HI)
        sessions.append({
            "start": round(t, 4),
            "turns": [{"user": n - TEMPLATE_TOKENS,
                       "max_tokens": (64, 128, 192)[len(sessions) % 3]}],
        })
    return {
        "loop": "open",
        "why": "chat front ends and agents that fan a task out: one-turn sessions, nothing "
               "shared, prompts 128-1,024 tokens (half under 320, a tenth over 800), answers "
               "of 64, 128 or 192 tokens in equal parts; exponential arrivals with three "
               "bursts of 2 s at three times the base rate, 10, 25 and 40 s into the window",
        "steps_per_s": MEAN_RATE,
        "base_rate_per_s": round(base, 4),
        "lead_in_s": LEAD_IN_S,
        "warm_sessions": WARM_SESSIONS,
        "turn_gap_s": 1.0,
        "shared": {},
        "check_requests": 8,
        "sessions": sessions,
    }


def main(argv):
    constant, out = (int(argv[0]), Path(argv[1])) if argv else (CONSTANT, OUT / "chat-burst.json")
    mix = chat_burst(constant)
    s = mix["sessions"]
    due = [x for x in s if LEAD_IN_S <= x["start"] < LEAD_IN_S + WINDOW_S]
    lens = sorted(x["turns"][0]["user"] + TEMPLATE_TOKENS for x in due)

    def bucket(n):
        b = 64
        while b < n:
            b *= 2
        return b

    warm = {bucket(x["turns"][0]["user"] + TEMPLATE_TOKENS) for x in s[:WARM_SESSIONS]}
    assert warm >= {bucket(n) for n in lens}, "the warm sessions miss a prompt bucket"
    out.write_text(json.dumps(mix, indent=1) + "\n")
    print("chat-burst", len(s), "sessions;", len(due), "due in the window; prompt tokens mean",
          sum(lens) / len(lens), "median", lens[len(lens) // 2], "over 800:",
          sum(n > 800 for n in lens) / len(lens), "output mean",
          sum(x["turns"][0]["max_tokens"] for x in due) / len(due))


if __name__ == "__main__":
    import sys
    main(sys.argv[1:])
