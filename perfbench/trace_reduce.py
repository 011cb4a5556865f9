"""From a profiler trace (``.xplane.pb``) to numbers, with nothing but JAX's
own reader. Checked on ``tests/data/small.xplane.pb`` (recorded on a v5e).

- busy: the union of the intervals in which an operation ran on a device's
  ``XLA Ops`` line, averaged over the device planes; the window is the span
  of the host's lanes between the profiler's own ``start_trace`` and
  ``stop_trace`` calls;
- operations by short name: outermost ones (a ``while`` holds its body's
  time; nothing is counted twice) and, apart, every operation at any depth,
  which is where a kernel inside the layer scan is found;
- idle gaps, named by the host event that covers most of each.
"""

from __future__ import annotations

import glob
import re
from typing import Any, Dict, List, Tuple

OPS_LINE = "XLA Ops"
_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)* = \(?(\w+)\[([\d,]*)\]")
_HOST_SKIP = ("$", "ThreadpoolListener")


def short_name(hlo: str) -> str:
    """``%fusion.8 = bf16[1024,14336]{...} fusion(...)`` -> ``fusion_bf16_1024_14336``."""
    m = _NAME.match(hlo)
    if not m:
        return re.sub(r"[^\w\-.]+", "_", hlo)[:60]
    dims = m.group(3).replace(",", "_")
    return f"{m.group(1)}_{m.group(2)}_{dims}" if dims else f"{m.group(1)}_{m.group(2)}"


def union_s(intervals: List[Tuple[float, float]]) -> Tuple[float, List[Tuple[float, float]]]:
    """Seconds covered by the union of ``(start_ns, end_ns)``, and the merged
    intervals."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged) * 1e-9, [(a, b) for a, b in merged]


def reduce_file(path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host_events, host_span = [], [], [float("inf"), 0.0]
    session = [0.0, float("inf")]   # between the profiler's own start and stop calls
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append([
                        (e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                        for e in line.events
                    ])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    a, b = float(e.start_ns), float(e.start_ns + e.duration_ns)
                    host_span = [min(host_span[0], a), max(host_span[1], b)]
                    if e.name.endswith(" start_trace"):
                        session[0] = max(session[0], b)
                    elif e.name.endswith(" stop_trace"):
                        session[1] = min(session[1], a)
                    if b > a and not e.name.startswith(_HOST_SKIP):
                        host_events.append((a, b, e.name))
    devices = [d for d in devices if d]
    if not devices:
        return {"busy_s": 0.0, "window_s": 0.0, "planes": 0}
    host_span = [max(host_span[0], session[0]), min(host_span[1], session[1])]
    window_ns = host_span[1] - host_span[0]
    busy, outer_by, any_by, gaps_by = [], {}, {}, {}
    for events in devices:
        total, merged = union_s([
            (max(a, host_span[0]), min(b, host_span[1])) for _, a, b in events
            if b > host_span[0] and a < host_span[1]
        ])
        busy.append(total)
        end = -1.0
        for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
            key = short_name(name)
            any_by[key] = any_by.get(key, 0.0) + (b - a) * 1e-9
            if a >= end:                       # not inside the last outermost
                outer_by[key] = outer_by.get(key, 0.0) + (b - a) * 1e-9
                end = b
        edges = [(host_span[0], host_span[0])] + merged + [(host_span[1], host_span[1])]
        for (_, gap_a), (gap_b, _) in zip(edges, edges[1:]):
            if gap_b - gap_a <= 0:
                continue
            best, cover = "no_event_in_the_host_lanes", 0.0
            if gap_b - gap_a >= 20_000:        # name only gaps of 20 us and more
                for a, b, name in host_events:
                    c = min(b, gap_b) - max(a, gap_a)
                    if c > cover:
                        best, cover = name, c
            else:
                best = "shorter_gaps_not_named"
            key = "host:" + re.sub(r"[^\w\-.:>=]+", "_", best)[:60]
            gaps_by[key] = gaps_by.get(key, 0.0) + (gap_b - gap_a) * 1e-9
    n = len(devices)
    return {
        "planes": n,
        "busy_s": sum(busy) / n,
        "window_s": window_ns * 1e-9,
        "outer_ops_s": {k: v / n for k, v in outer_by.items()},
        "all_ops_s": {k: v / n for k, v in any_by.items()},
        "idle_gaps_s": {k: v / n for k, v in gaps_by.items()},
    }


def reduce_dir(trace_dir: str) -> Dict[str, Any]:
    found = sorted(glob.glob(trace_dir + "/plugins/profile/*/*.xplane.pb"))
    if not found:
        return {"busy_s": 0.0, "window_s": 0.0, "planes": 0}
    return reduce_file(found[-1])


def top(table: Dict[str, float], n: int = 10) -> List[List[Any]]:
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def kernel_seconds(trace: Dict[str, Any], pattern: str) -> float:
    """Device seconds of every operation, at any depth, whose short name
    holds ``pattern``; 0.0 when the trace has none."""
    return sum(v for k, v in (trace.get("all_ops_s") or {}).items() if pattern in k)
