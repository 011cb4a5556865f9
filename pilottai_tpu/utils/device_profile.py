"""Device-side timing from JAX profiler traces — what the device did,
apart from what the host did around it.

End-to-end wall-clock includes dispatch, host↔device syncs and whatever
else the host was doing, so it conflates engine regressions with host
noise (a round-over-round headline once moved 23% with no way to tell
which). The fix is to measure the DEVICE's own busy time: run a window under
``jax.profiler.trace`` and sum the execution lanes of the device process
from the perfetto JSON the profiler writes (the same method
docs/PERF_NOTES.md used by hand, automated).

No tensorboard/profile-plugin dependency: the ``*.trace.json.gz`` file is
plain perfetto JSON.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import tempfile
import time
from typing import Any, Callable, Dict, Optional

from pilottai_tpu.utils.logging import get_logger

_log = get_logger("device_profile")


def parse_trace_dir(trace_dir: str) -> Dict[str, Any]:
    """Parse the newest ``*.trace.json.gz`` under ``trace_dir``.

    Returns ``{device_busy_s, wall_s, busy_frac, lane, n_events}`` where
    ``device_busy_s`` is the largest per-thread interval UNION over the
    device process's lanes. Union, not sum: profiler lanes carry nested
    events ("XLA Ops" rows overlap hierarchically — a raw sum
    over-counted a measured 1B wave by ~1.8×), and merging intervals
    yields the time the device actually spent executing regardless of
    nesting. Falls back to host execution lanes when no ``/device:``
    process exists (CPU backend), and to zeros when no trace was
    written.
    """
    files = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                  recursive=True),
        key=os.path.getmtime,
    )
    empty = {"device_busy_s": 0.0, "wall_s": 0.0, "busy_frac": 0.0,
             "lane": None, "n_events": 0}
    if not files:
        return empty
    with gzip.open(files[-1], "rt") as f:
        events = json.load(f).get("traceEvents", [])

    proc_names: Dict[int, str] = {}
    thread_names: Dict[tuple, str] = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            proc_names[e["pid"]] = str(e.get("args", {}).get("name", ""))
        elif e.get("name") == "thread_name":
            thread_names[(e["pid"], e.get("tid"))] = str(
                e.get("args", {}).get("name", "")
            )

    device_pids = {
        pid for pid, name in proc_names.items() if "/device:" in name
    }
    if not device_pids:
        # CPU backend: XLA's client threads are the closest analog; the
        # "python" lane is host bookkeeping, not execution.
        device_pids = set(proc_names)

        def lane_ok(pid: int, tid) -> bool:
            return "python" not in thread_names.get((pid, tid), "")
    else:
        def lane_ok(pid: int, tid) -> bool:
            return True

    intervals: Dict[tuple, list] = {}
    t_min, t_max = float("inf"), float("-inf")
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        if not lane_ok(e["pid"], e.get("tid")):
            continue
        key = (e["pid"], e.get("tid"))
        dur = float(e.get("dur", 0.0))
        ts = float(e.get("ts", 0.0))
        intervals.setdefault(key, []).append((ts, ts + dur))
        t_min = min(t_min, ts)
        t_max = max(t_max, ts + dur)
    if not intervals:
        return empty

    def union_us(spans: list) -> float:
        spans.sort()
        total = 0.0
        cur_start, cur_end = spans[0]
        for s, t in spans[1:]:
            if s > cur_end:
                total += cur_end - cur_start
                cur_start, cur_end = s, t
            else:
                cur_end = max(cur_end, t)
        return total + (cur_end - cur_start)

    unions = {k: union_us(v) for k, v in intervals.items()}
    lane_key = max(unions, key=lambda k: unions[k])
    busy_s = unions[lane_key] / 1e6
    wall_s = max(t_max - t_min, 0.0) / 1e6
    return {
        "device_busy_s": busy_s,
        "wall_s": wall_s,
        "busy_frac": busy_s / wall_s if wall_s > 0 else 0.0,
        "lane": thread_names.get(lane_key)
        or proc_names.get(lane_key[0], "?"),
        "n_events": len(intervals[lane_key]),
    }


class DeviceWindow:
    """``start()``/``stop()`` profiling window for async code paths (the
    bench can't wrap an ``await`` in a context manager argument)."""

    def __init__(self, trace_dir: Optional[str] = None) -> None:
        self._own_dir = trace_dir is None
        self.trace_dir = trace_dir or tempfile.mkdtemp(prefix="pilottai-prof-")
        self._t0 = 0.0
        self.wall_s = 0.0

    def start(self) -> "DeviceWindow":
        import jax

        jax.profiler.start_trace(self.trace_dir)
        self._t0 = time.perf_counter()
        return self

    def stop(self) -> Dict[str, Any]:
        import jax

        self.wall_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        out = parse_trace_dir(self.trace_dir)
        if self._own_dir:
            # Self-created temp dir: traces of multi-request waves run
            # tens of MB; leaking one per profiled section fills tmpfs
            # on long-lived hosts.
            import shutil

            shutil.rmtree(self.trace_dir, ignore_errors=True)
        out["window_wall_s"] = self.wall_s
        if self.wall_s > 0:
            # Busy fraction against the measured host window (the trace's
            # own extent understates idle time at the edges).
            out["busy_frac"] = min(out["device_busy_s"] / self.wall_s, 1.0)
        return out


def profile_device_window(
    fn: Callable[[], Any], trace_dir: Optional[str] = None
) -> Dict[str, Any]:
    """Run ``fn()`` under a profiler trace; return device-side timing."""
    win = DeviceWindow(trace_dir)
    win.start()
    try:
        fn()
    finally:
        out = win.stop()
    return out
