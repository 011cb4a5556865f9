#!/usr/bin/env python3
"""A run of one cell (``run.py``'s own arguments; ``--trace 1`` to have the
per-layer readers called) that also says how the request timeline adds up and
what the program's host-lane spans held:

- on standard error, over the window's flights, the worst difference between
  the five self times and the edge's span less the batcher's hand-back, and the
  client's latency less the edge's span (``perfbench/timeline.py:telescope``);
- with ``--spans <file>``, from the traced slice's ``.xplane.pb`` before it is
  thrown away: each program span's count, total, median and longest, and for
  the longest dispatch spans the host events of the same thread inside them,
  the Python tracer's ``$`` events included (what a dispatch that blocks is
  doing).

The result line stays the last line of standard output.

    python3 perfbench/tools/timeline.py --workload mistral-7b.agent-loop \\
        --seed 7 --seconds 50 --trace 1 --spans chiprun_out/spans.7.json
"""

from __future__ import annotations

import asyncio
import glob
import json
import os
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

SPANS_ENV = "PERFBENCH_TIMELINE_SPANS"
OURS = ("batcher.", "prep.", "reader.", "edge.", "handler.")


def host_spans(path: str, longest: int = 4, inside: int = 14) -> Dict[str, Any]:
    """The program's spans in the host lanes of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    by_name: Dict[str, List[float]] = {}
    dispatches = []
    outside: Dict[str, Any] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for index, line in enumerate(plane.lines):
            events = [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                      for e in line.events]
            for name, a, b in events:
                if name.startswith(OURS):
                    by_name.setdefault(name, []).append((b - a) * 1e-6)
                    if name.startswith("batcher.dispatch_"):
                        dispatches.append((b - a, name, a, b, index, events))
            if any(name == "batcher.admit" for name, _, _ in events):
                outside = _outside_ours(events, inside)
    out: Dict[str, Any] = {"spans": {}, "longest_dispatches": [], "outside": outside}
    for name, ms in sorted(by_name.items()):
        ms.sort()
        out["spans"][name] = {
            "count": len(ms), "total_ms": sum(ms), "p50_ms": ms[len(ms) // 2],
            "max_ms": ms[-1],
        }
    for dur, name, a, b, index, events in sorted(dispatches, key=lambda d: -d[0])[:longest]:
        held: Dict[str, List[float]] = {}
        for other, c, d in events:
            if c >= a and d <= b and (other, c, d) != (name, a, b):
                held.setdefault(other, []).append((d - c) * 1e-6)
        top = sorted(held.items(), key=lambda kv: -sum(kv[1]))[:inside]
        out["longest_dispatches"].append({
            "name": name, "ms": dur * 1e-6, "thread_line": index,
            "inside": [[k[:120], len(v), sum(v)] for k, v in top],
        })
    return out


def _outside_ours(events: List[Any], top: int) -> Dict[str, Any]:
    """Of the device thread's line: the stretch between its first and last
    program span that no program span covers, and the other events (the
    Python tracer's among them) by how much of that stretch each overlaps:
    what the thread does where the program names nothing."""
    from perfbench.trace_reduce import union_s

    ours = [(a, b) for name, a, b in events if name.startswith(OURS)]
    lo, hi = min(a for a, _ in ours), max(b for _, b in ours)
    _, merged = union_s(ours)
    holes = [(b, c) for (_, b), (c, _) in zip(merged, merged[1:]) if c > b]
    held: Dict[str, float] = {}
    for name, a, b in events:
        if name.startswith(OURS) or b <= lo or a >= hi:
            continue
        over = sum(max(0.0, min(b, d) - max(a, c)) for c, d in holes if c < b and d > a)
        if over > 0.0:
            held[name[:120]] = held.get(name[:120], 0.0) + over * 1e-6
    return {
        "stretch_ms": (hi - lo) * 1e-6, "uncovered_ms": sum(d - c for c, d in holes) * 1e-6,
        "longest_hole_ms": max((d - c for c, d in holes), default=0.0) * 1e-6,
        "held_by": sorted(([k, v] for k, v in held.items()), key=lambda kv: -kv[1])[:top],
    }


def child(argv: List[str]) -> int:
    """The serving process, with the traced slice's spans written out before
    the trace directory goes."""
    from perfbench import serving, trace_reduce

    reduce_dir = trace_reduce.reduce_dir

    def reduce_and_keep(trace_dir: str) -> Dict[str, Any]:
        found = sorted(glob.glob(trace_dir + "/plugins/profile/*/*.xplane.pb"))
        if found:
            Path(os.environ[SPANS_ENV]).write_text(json.dumps(host_spans(found[-1])))
        return reduce_dir(trace_dir)

    trace_reduce.reduce_dir = reduce_and_keep
    return serving.main(argv)


async def _start_child(cls: Any, spec: Dict[str, Any]) -> Any:
    """``run.Child.start``, with this file's ``child`` as the serving process."""
    proc = await asyncio.create_subprocess_exec(
        sys.executable, __file__, "--child", json.dumps(spec),
        stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE,
        limit=1 << 26, cwd=str(ROOT),
    )
    return cls(proc)


def main(argv: Optional[List[str]] = None, **kwargs: Any) -> int:
    from perfbench import run, timeline

    argv = list(sys.argv[1:] if argv is None else argv)
    if "--spans" in argv:
        at = argv.index("--spans")
        os.environ[SPANS_ENV] = str(Path(argv[at + 1]).resolve())
        del argv[at:at + 2]
        run.Child.start = classmethod(_start_child)
    seen: Dict[str, Any] = {}
    read_metric = run.read_metric

    def keep_ctx(name: str, ctx: Dict[str, Any]) -> Optional[float]:
        seen["ctx"] = ctx
        return read_metric(name, ctx)

    run.read_metric = keep_ctx
    try:
        code = run.main(argv, **kwargs)
    finally:
        run.read_metric = read_metric
    if "ctx" in seen:
        print("[timeline] " + json.dumps(timeline.telescope(seen["ctx"])),
              file=sys.stderr, flush=True)
    return code


if __name__ == "__main__":
    sys.exit(child(sys.argv[1:]) if sys.argv[1:2] == ["--child"] else main())
