"""Engine step telemetry ring: the in-memory half of the flight recorder.

Every decode chunk, admission wave and handler request appends one small
dict to a bounded ring. The ring is cheap enough to run always-on (a
deque append under a lock, a few hundred bytes per record) and is what
the black-box dumper snapshots when something goes wrong: the last N
steps before a deadline blew or the breaker opened are exactly the
context a postmortem needs and exactly what process logs lose.

Record shape (by ``kind``):

``engine.chunk``   one fused decode chunk folded on the host — slot
                   occupancy, tokens landed, dispatched block count
                   (``chunk_blocks``, the adaptive scheduler's per-
                   dispatch pick) and useful-block utilization, queue
                   depth, KV page-pool utilization, active strip width,
                   pipeline depth.
``engine.admit``   one admission wave — group size, queue depth.
``engine.shed``    an admission-control shed.
``handler.request`` one completed/failed LLMHandler request — status,
                   latency, tokens (the only kind mock deployments emit).

Every record carries ``ts`` (epoch seconds, human correlation) and
``ts_mono`` (``time.perf_counter()``, the tracer's clock) so steps line
up with span trees in the Perfetto export.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional


class StepRing:
    """Thread-safe bounded ring of telemetry step records."""

    def __init__(self, capacity: int = 512) -> None:
        self._records: Deque[Dict[str, Any]] = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._seq = 0

    def record(self, kind: str, **fields: Any) -> Dict[str, Any]:
        rec = {
            "kind": kind,
            "ts": time.time(),
            "ts_mono": time.perf_counter(),
            **fields,
        }
        with self._lock:
            self._seq += 1
            rec["seq"] = self._seq
            self._records.append(rec)
        return rec

    def snapshot(self, n: Optional[int] = None) -> List[Dict[str, Any]]:
        """The last ``n`` records (all retained when None), oldest first."""
        with self._lock:
            records = list(self._records)
        if n is not None and n >= 0:
            records = records[-n:]
        return records

    def clear(self) -> None:
        with self._lock:
            self._records.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._records)


global_steps = StepRing()
