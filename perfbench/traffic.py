"""The one general traffic generator: a mix is a data file, this reads it.

``traffic/<mix>.json`` holds the schedule outright (see PERF.md, "Adding a
cell"): ``sessions`` with a ``start`` offset and a list of ``turns``; each turn
gives the length of its ``user`` piece and ``max_tokens``. ``loop`` is ``open``
(turn j of a session is due at ``start + j * turn_gap_s`` whether or not the
earlier reply is in) or ``closed`` (a session is one waiting caller: its next
turn goes out when the last reply is in; its list is walked round and round).
``shared`` names prompt pieces that several sessions begin with.

``--seed`` reaches only ``piece_bytes``: the bytes of a piece. No length,
arrival, session or ``max_tokens`` is drawn from it.
"""

from __future__ import annotations

import hashlib
import json
from pathlib import Path
from typing import Any, Dict, List

import numpy as np

ROOT = Path(__file__).resolve().parent
_ALPHABET = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz     .,:;0123456789", np.uint8)


def load_mix(name: str) -> Dict[str, Any]:
    path = Path(name) if name.endswith(".json") else ROOT / "traffic" / f"{name}.json"
    mix = json.loads(path.read_text())
    if mix.get("loop") not in ("open", "closed"):
        raise ValueError(f"traffic/{name}.json: loop must be open or closed")
    return mix


def piece_bytes(seed: int, piece_id: str, n: int) -> str:
    """``n`` ASCII characters (one byte, one token each) fixed by the seed and
    the piece's name: two requests that name the same piece share its bytes."""
    digest = hashlib.sha256(f"{int(seed)}/{piece_id}".encode()).digest()
    rng = np.random.Generator(np.random.PCG64(int.from_bytes(digest[:16], "little")))
    return _ALPHABET[rng.integers(0, len(_ALPHABET), size=n)].tobytes().decode("ascii")


def prompt_tokens(messages: List[Dict[str, str]]) -> int:
    """What the byte tokenizer makes of the generic chat transcript: BOS, then
    ``<|role|>\\n<content>`` joined by newlines, then ``<|assistant|>\\n``."""
    parts = [f"<|{m['role']}|>\n{m['content']}" for m in messages] + ["<|assistant|>\n"]
    return 1 + len("\n".join(parts).encode("utf-8"))


def build_messages(
    mix: Dict[str, Any], seed: int, session: int, turn: int, lap: int = 0
) -> List[Dict[str, str]]:
    """The messages of one turn. ``lap`` counts how often a closed session has
    walked its list round, so a replayed length never replays its bytes."""
    sess = mix["sessions"][session]
    messages: List[Dict[str, str]] = []
    if sess.get("shared"):
        n = mix["shared"][sess["shared"]]
        messages.append(
            {"role": "system", "content": piece_bytes(seed, f"shared/{sess['shared']}", n)}
        )
    n = sess["turns"][turn]["user"]
    messages.append(
        {"role": "user", "content": piece_bytes(seed, f"s{session}/l{lap}/t{turn}/user", n)}
    )
    return messages


def request_body(mix: Dict[str, Any], seed: int, session: int, turn: int, lap: int = 0):
    messages = build_messages(mix, seed, session, turn, lap)
    body = {
        "messages": messages,
        "max_tokens": mix["sessions"][session]["turns"][turn]["max_tokens"],
        "temperature": 0.0,
    }
    return body, prompt_tokens(messages)


def open_schedule(mix: Dict[str, Any], horizon_s: float, rate_scale: float = 1.0):
    """``[(due_s, session, turn)]`` of an open mix up to ``horizon_s``, in
    order of time. ``rate_scale`` squeezes the stored session starts (the
    sweep's only use of it; a cell runs at 1)."""
    gap = float(mix["turn_gap_s"])
    plan = []
    for s, sess in enumerate(mix["sessions"]):
        for j in range(len(sess["turns"])):
            due = sess["start"] / rate_scale + j * gap
            if due < horizon_s:
                plan.append((due, s, j))
    plan.sort()
    return plan
