"""Made the schedules in ``traffic/`` once (PR 24). They are data now: the
harness never runs this, and ``--seed`` never reaches a schedule.

    python3 perfbench/tools/make_schedules.py

The constant below is the only randomness; rerunning rewrites the same files.
"""
import json
import random
from pathlib import Path

CONSTANT = 24_2026
OUT = Path(__file__).resolve().parents[1] / "traffic"


def agent_loop(steps_per_s: float, horizon_s: float = 140.0):
    """Steps of a stateless worker. Each step's prompt is the role's shared
    system prompt and one scratchpad: the tool results and 48-token notes of
    the session so far, rendered afresh (fresh bytes) at every step, so the
    role prompt is all that two requests ever share. The scratchpad is held
    under 988 tokens, because a prefix hit whose tail passes 1,024 tokens does
    not compile in this program (PERF.md, Open questions)."""
    rng = random.Random(CONSTANT)
    turns = 6
    per_s = steps_per_s / turns
    sessions, t = [], 0.0
    tool_lengths = [64, 72, 80, 96, 104, 112, 128, 144]
    note, framing = 48, 12
    while t < horizon_s:
        t += rng.expovariate(per_s)
        k = len(sessions)
        tools = [tool_lengths[(k * 5 + j * 3) % len(tool_lengths)] for j in range(turns)]
        pads = [sum(tools[: j + 1]) + j * (note + framing) for j in range(turns)]
        assert pads[-1] <= 987
        sessions.append({
            "start": round(t, 4),
            "shared": f"role{k % 2}",
            "turns": [{"user": n, "max_tokens": 48} for n in pads],
        })
    return {
        "loop": "open",
        "why": "agent swarm: sessions of 6 steps 3 s apart, two roles with a "
               "2,048-token system prompt each, exponential session starts; a "
               "step's scratchpad grows by a tool result of 64-144 tokens and a "
               "48-token note",
        "steps_per_s": steps_per_s,
        "lead_in_s": 12.0,
        "warm_sessions": 2,
        "turn_gap_s": 3.0,
        "shared": {"role0": 2048, "role1": 2048},
        "check_requests": 5,
        "sessions": sessions,
    }


def closed(n_clients, lo, hi, step, max_tokens, per_client, stagger_s, check, why):
    rng = random.Random(CONSTANT + n_clients)
    grid = list(range(lo, hi + 1, step))
    clients = []
    for c in range(n_clients):
        # every client walks the same grid of lengths from another offset and
        # stride, so each length is used equally often across the clients
        lens = [grid[(c * 7 + j * 5) % len(grid)] for j in range(per_client)]
        clients.append({
            "start": round(c * stagger_s, 4),
            "turns": [{"user": n, "max_tokens": max_tokens} for n in lens],
        })
    rng.shuffle(clients[0]["turns"])  # the first caller walks its lengths out of stride
    return {
        "loop": "closed",
        "why": why,
        "shared": {},
        "check_requests": check,
        "sessions": clients,
    }


def main():
    OUT.mkdir(exist_ok=True)
    files = {
        "agent-loop": agent_loop(steps_per_s=0.6),
        "rag-prefill": closed(
            16, 1024, 3072, 128, 64, 17, 0.45, 8,
            "retrieval-augmented questions: 16 waiting callers, unshared prompts "
            "of 1,024-3,072 tokens (mean 2,048), answers of 64 tokens"),
    }
    for name, body in files.items():
        (OUT / f"{name}.json").write_text(json.dumps(body, indent=1) + "\n")
        print(name, len(body["sessions"]), "sessions")


if __name__ == "__main__":
    main()
