"""Continuous in-process device-time and MFU attribution.

Until now the repo's MFU was a single division at bench time and its
device-time breakdown an offline Perfetto post-process
(``utils/device_profile.py``) — nothing answered "what is the engine's
MFU *right now*" or "what fraction of device time is prefill vs decode"
on a live deployment. This module is the cheap always-on estimate:

* the batcher reports each dispatch as it folds — phase (``prefill`` /
  ``decode`` / ``sampling`` / ``collective``), host-observed duration,
  tokens landed — plus the idle gaps its host-gap telemetry already
  measures;
* achieved FLOPs are derived as ``tokens x ModelConfig.flops_per_token()``
  (prefill tokens + *accepted* decode tokens from folded validity — the
  same formula bench.py uses, so live and bench MFU reconcile by
  construction);
* rolling-window gauges update on every fold:

  ==================================  =================================
  ``engine.mfu``                      achieved FLOPs / (window x peak
                                      x n_chips)
  ``engine.device_busy_frac``         1 − measured idle gaps / window
  ``engine.collective_frac``          collective share of attributed
                                      device time (0 on a single chip)
  ``engine.collective_frac.<axis>``   per-mesh-axis collective share
  ==================================  =================================

  and cumulative counters (``engine.achieved_flops``,
  ``engine.prefill_tokens``, ``engine.attributed_<phase>_s``,
  ``engine.idle_gap_s``) so section-scoped consumers (bench) take
  deltas.

Accuracy contract: durations are HOST-observed (dispatch-to-fold and
enqueue walls stand in for device occupancy, the same approximation the
host-gap telemetry makes) — pipelined chunks and interleaved prefills
can overlap, so treat per-phase seconds as attribution *shares*, not an
oscilloscope. The FLOPs/token accounting, however, is exact in tokens,
and the whole estimate is reconciled against the profiler-derived truth
(``utils/device_profile.py``) in a slow-marker test
(tests/test_attribution.py) so drift cannot ship silently.

Import cost: stdlib + utils only — no jax (``obs`` package constraint);
``peak_flops_per_chip`` takes the ``device_kind`` string its caller
read from jax instead of sniffing devices.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Deque, Dict, Optional, Tuple

from pilottai_tpu.utils.metrics import MetricsRegistry, global_metrics

PHASES = ("prefill", "decode", "sampling", "collective")

# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
# A device that is not in the table is an error, not a default: an MFU
# over a guessed peak is not a measurement. Add a part with its source.
#   "TPU v5 lite" (v5e) — Google Cloud documentation, "TPU v5e":
#       197 TFLOP/s bf16, 16 GB HBM at 819 GB/s.
#   "cpu" — NOT a published peak: a nominal 1 TFLOP/s so host runs (the
#       test suite) produce finite, comparable-within-themselves shares.
#       Nothing computed from it is a device metric.
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
    "cpu": {"flops": 1e12, "hbm_bytes_per_s": 0.0},
}


def peak_flops_per_chip(device_kind: str) -> float:
    """Per-chip peak bf16 FLOP/s for a jax ``device_kind``; an unknown
    kind raises (callers with another part pass ``peak_flops=`` to
    ``configure`` or add the part to ``DEVICE_PEAKS``)."""
    try:
        return DEVICE_PEAKS[device_kind]["flops"]
    except KeyError:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}; add it "
            f"to obs/attribution.py:DEVICE_PEAKS with its source"
        ) from None


class DeviceTimeAttributor:
    """Windowed phase/FLOPs accountant behind the live MFU gauges.

    One global instance is shared by however many engines the process
    runs (the same sharing ``global_metrics`` already has); ``configure``
    is called at each engine boot with that model's FLOPs formula.
    """

    def __init__(
        self,
        registry: MetricsRegistry = global_metrics,
        window_s: float = 60.0,
    ) -> None:
        self._registry = registry
        self._lock = threading.Lock()
        self.window_s = window_s
        self._flops_per_token = 0.0
        self._peak_flops = DEVICE_PEAKS["cpu"]["flops"]
        self._n_chips = 1
        self._mesh_axes: Tuple[str, ...] = ()
        # (t_end, phase, dur_s, flops, axis) events and (t, gap_s) idle
        # gaps, pruned to the window — with RUNNING window aggregates
        # maintained on append/evict. record()/record_gap() execute on
        # the batcher's device and reader threads per dispatch/fold;
        # full-window scans there would add O(events) host work to the
        # exact hot paths the async-feed pipeline keeps lean.
        self._events: Deque[Tuple[float, str, float, float, Optional[str]]] = (
            deque()
        )
        self._gaps: Deque[Tuple[float, float]] = deque()
        self._w_flops = 0.0
        self._w_dur = 0.0
        self._w_coll = 0.0
        self._w_gap = 0.0
        self._w_axis: Dict[str, float] = {}
        self._t0: Optional[float] = None
        registry.declare("engine.mfu", "gauge")
        registry.declare("engine.device_busy_frac", "gauge")
        registry.declare("engine.collective_frac", "gauge")
        registry.declare("engine.achieved_flops", "counter")
        registry.declare("engine.prefill_tokens", "counter")
        registry.declare("engine.idle_gap_s", "counter")
        for phase in PHASES:
            registry.declare(f"engine.attributed_{phase}_s", "counter")

    # ------------------------------------------------------------------ #

    def configure(
        self,
        *,
        flops_per_token: float,
        device_kind: str = "cpu",
        peak_flops: Optional[float] = None,
        n_chips: int = 1,
        mesh_axes: Tuple[str, ...] = (),
    ) -> None:
        """Engine boot hook: the model's FLOPs/token formula
        (``ModelConfig.flops_per_token()``), the device's published
        peak (by ``device_kind``; unknown kinds raise) and the mesh
        shape. Also declares the per-axis collective gauges so the
        full exposition surface exists before the first collective."""
        with self._lock:
            self._flops_per_token = float(flops_per_token)
            self._peak_flops = (
                peak_flops if peak_flops is not None
                else peak_flops_per_chip(device_kind)
            )
            self._n_chips = max(int(n_chips), 1)
            self._mesh_axes = tuple(mesh_axes)
        for axis in mesh_axes:
            self._registry.declare(f"engine.collective_frac.{axis}", "gauge")
            # Cumulative per-axis collective seconds next to the rolling
            # gauge: section-scoped consumers (bench MULTICHIP) take
            # exact deltas instead of sampling a 60 s window.
            self._registry.declare(
                f"engine.attributed_collective_s.{axis}", "counter"
            )

    # ------------------------------------------------------------------ #

    def record(
        self,
        phase: str,
        duration_s: float,
        *,
        tokens: int = 0,
        flops: Optional[float] = None,
        axis: Optional[str] = None,
        at: Optional[float] = None,
        collective: Optional[Dict[str, float]] = None,
    ) -> None:
        """One dispatch's attribution. ``flops`` defaults to
        ``tokens x flops_per_token``; pass it explicitly for work the
        token formula doesn't describe (collectives: 0). ``axis`` tags
        collective time to a mesh axis for the per-axis gauges.
        ``collective`` is this dispatch's per-axis collective-seconds
        split (the batcher's CollectiveModel carve-out): the axis events
        land in the window under the SAME lock/gauge pass as the phase
        record, so a sharded fold stays one attributor call instead of
        one per axis on the reader-thread hot path."""
        if phase not in PHASES:
            raise ValueError(f"unknown phase {phase!r}; expected {PHASES}")
        now = at if at is not None else time.perf_counter()
        duration_s = max(float(duration_s), 0.0)
        if flops is None:
            flops = tokens * self._flops_per_token
        coll = {
            ax: float(s) for ax, s in (collective or {}).items() if s > 0.0
        }
        with self._lock:
            if self._t0 is None:
                self._t0 = now - duration_s - sum(coll.values())
            self._events.append((now, phase, duration_s, flops, axis))
            self._w_flops += flops
            self._w_dur += duration_s
            if phase == "collective":
                self._w_coll += duration_s
                if axis is not None:
                    self._w_axis[axis] = (
                        self._w_axis.get(axis, 0.0) + duration_s
                    )
            for ax, coll_s in coll.items():
                self._events.append((now, "collective", coll_s, 0.0, ax))
                self._w_dur += coll_s
                self._w_coll += coll_s
                self._w_axis[ax] = self._w_axis.get(ax, 0.0) + coll_s
            self._prune_locked(now)
            gauges = self._gauges_locked(now)
        reg = self._registry
        reg.inc(f"engine.attributed_{phase}_s", duration_s)
        if phase == "collective" and axis is not None:
            reg.inc(f"engine.attributed_collective_s.{axis}", duration_s)
        for ax, coll_s in coll.items():
            reg.inc("engine.attributed_collective_s", coll_s)
            reg.inc(f"engine.attributed_collective_s.{ax}", coll_s)
        if flops:
            reg.inc("engine.achieved_flops", flops)
        if phase == "prefill" and tokens:
            reg.inc("engine.prefill_tokens", tokens)
        for name, value in gauges.items():
            reg.set_gauge(name, value)

    def record_gap(self, gap_s: float, at: Optional[float] = None) -> None:
        """One measured device-idle bubble (the batcher's host-gap
        telemetry: time the device sat with nothing in flight before a
        dispatch). The busy gauge is the complement of these over the
        window — idle is *measured*, busy inferred, so an engine that
        stops dispatching shows its last-known busy_frac rather than a
        fabricated one."""
        if gap_s <= 0.0:
            return
        now = at if at is not None else time.perf_counter()
        with self._lock:
            if self._t0 is None:
                self._t0 = now - gap_s
            self._gaps.append((now, gap_s))
            self._w_gap += gap_s
            self._prune_locked(now)
            gauges = self._gauges_locked(now)
        self._registry.inc("engine.idle_gap_s", gap_s)
        for name, value in gauges.items():
            self._registry.set_gauge(name, value)

    # ------------------------------------------------------------------ #

    def _prune_locked(self, now: float) -> None:
        cutoff = now - self.window_s
        while self._events and self._events[0][0] < cutoff:
            _, phase, dur, flops, axis = self._events.popleft()
            self._w_flops -= flops
            self._w_dur -= dur
            if phase == "collective":
                self._w_coll -= dur
                if axis is not None:
                    self._w_axis[axis] = self._w_axis.get(axis, 0.0) - dur
        while self._gaps and self._gaps[0][0] < cutoff:
            self._w_gap -= self._gaps.popleft()[1]
        if not self._events and not self._gaps:
            # Empty window: reset the running sums so float residue from
            # long add/subtract chains can't accumulate into the gauges.
            self._w_flops = self._w_dur = self._w_coll = self._w_gap = 0.0
            self._w_axis.clear()

    def _elapsed_locked(self, now: float) -> float:
        if self._t0 is None:
            return 0.0
        return max(min(now - self._t0, self.window_s), 1e-9)

    def _gauges_locked(self, now: float) -> Dict[str, float]:
        """O(1): reads the running window aggregates, no event scans."""
        elapsed = self._elapsed_locked(now)
        if elapsed <= 0.0:
            return {}
        busy = max(min(1.0 - self._w_gap / elapsed, 1.0), 0.0)
        denom = elapsed * self._peak_flops * self._n_chips
        out = {
            "engine.mfu": max(self._w_flops, 0.0) / denom
            if denom > 0 else 0.0,
            "engine.device_busy_frac": busy,
        }
        total_dur = self._w_dur
        out["engine.collective_frac"] = (
            max(self._w_coll, 0.0) / total_dur if total_dur > 0 else 0.0
        )
        for ax in self._mesh_axes:
            ax_dur = max(self._w_axis.get(ax, 0.0), 0.0)
            out[f"engine.collective_frac.{ax}"] = (
                ax_dur / total_dur if total_dur > 0 else 0.0
            )
        return out

    # ------------------------------------------------------------------ #

    def snapshot(self) -> Dict[str, Any]:
        """Live window view: per-phase seconds/share, window FLOPs, the
        gauge values, configuration. The bench's per-section numbers use
        the cumulative counters instead (delta across the section)."""
        now = time.perf_counter()
        with self._lock:
            self._prune_locked(now)
            elapsed = self._elapsed_locked(now)
            events = list(self._events)
            idle_w = sum(g for _, g in self._gaps)
            gauges = self._gauges_locked(now)
            cfg = {
                "flops_per_token": self._flops_per_token,
                "peak_flops_per_chip": self._peak_flops,
                "n_chips": self._n_chips,
                "mesh_axes": list(self._mesh_axes),
                "window_s": self.window_s,
            }
        total_dur = sum(e[2] for e in events)
        phases: Dict[str, Any] = {}
        for phase in PHASES:
            dur = sum(e[2] for e in events if e[1] == phase)
            phases[phase] = {
                "seconds": round(dur, 6),
                "share": round(dur / total_dur, 4) if total_dur > 0 else 0.0,
            }
        return {
            "window_elapsed_s": round(elapsed, 3),
            "attributed_s": round(total_dur, 6),
            "idle_gap_s": round(idle_w, 6),
            "achieved_flops": sum(e[3] for e in events),
            "phases": phases,
            "mfu": round(gauges.get("engine.mfu", 0.0), 6),
            "device_busy_frac": round(
                gauges.get("engine.device_busy_frac", 0.0), 4
            ),
            "collective_frac": round(
                gauges.get("engine.collective_frac", 0.0), 4
            ),
            **cfg,
        }

    def reset_window(self) -> None:
        """Drop the rolling window (gauges keep their last values until
        the next record). Cumulative counters are untouched — bench
        sections measure by delta, not by reset."""
        with self._lock:
            self._events.clear()
            self._gaps.clear()
            self._w_flops = self._w_dur = self._w_coll = self._w_gap = 0.0
            self._w_axis.clear()
            self._t0 = None


global_attribution = DeviceTimeAttributor()
