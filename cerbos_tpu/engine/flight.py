"""Batch flight recorder: a bounded ring buffer of recent device batches.

A production incident on the device path (a breaker trip, a poisoned batch,
a latency cliff) is reconstructable after the fact only if the server kept
the evidence: which requests were co-batched, how long each pipeline stage
took, how full the padded device layout actually was, and what the fault
machinery did about failures. The recorder keeps the last N batch records
plus a parallel ring of discrete events (breaker transitions, bisect
outcomes, quarantine additions), dumpable as JSON via the
``/_cerbos/debug/flight`` endpoint and printed to stderr on ``SIGQUIT``.

Recording is a dict append under a lock — never an allocation spike, never
I/O — so it is safe on the batcher drain loop's hot path.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import signal
import sys
import threading
import time
from collections import deque
from typing import Any, Optional

DEFAULT_CAPACITY = 256


class FlightRecorder:
    """Thread-safe bounded ring of batch records + events."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY, enabled: bool = True):
        self.capacity = max(1, int(capacity))
        self.enabled = enabled
        self._records: deque[dict] = deque(maxlen=self.capacity)
        self._events: deque[dict] = deque(maxlen=self.capacity)
        self._ids = itertools.count(1)
        self._lock = threading.Lock()

    def next_batch_id(self) -> int:
        return next(self._ids)

    def record_batch(
        self,
        batch_id: int,
        *,
        trace_ids: list[str],
        requests: int,
        inputs: int,
        timings: dict[str, float],
        outcome: str,
        occupancy: Optional[float] = None,
        layout_key: Optional[str] = None,
        breaker_state: Optional[str] = None,
        shard: Optional[int] = None,
        submitted_monotonic_ns: Optional[int] = None,
    ) -> None:
        if not self.enabled:
            return
        rec = {
            "batch_id": batch_id,
            "ts": time.time(),
            "trace_ids": trace_ids,
            "requests": requests,
            "inputs": inputs,
            "timings": {k: round(v, 6) for k, v in timings.items()},
            "outcome": outcome,
            "occupancy": round(occupancy, 4) if occupancy is not None else None,
            "layout_key": layout_key,
            "breaker_state": breaker_state,
            # which lane of the sharded pool carried this batch; None when a
            # single evaluator serves (pre-shard records keep their shape)
            "shard": shard,
            # time.monotonic_ns() at submit return: the clock a profiler
            # capture reports at both its ends (tpu/profiler.py), so the
            # flight can be placed on that capture's trace
            "submitted_monotonic_ns": submitted_monotonic_ns,
        }
        with self._lock:
            self._records.append(rec)

    def lane(self, shard: int) -> list[dict]:
        """The recent batch records for one shard lane, oldest first."""
        with self._lock:
            return [r for r in self._records if r.get("shard") == shard]

    def record_event(self, kind: str, **fields: Any) -> None:
        """Discrete device-path events: breaker transitions, bisect results,
        quarantine additions, deadline storms."""
        if not self.enabled:
            return
        ev = {"kind": kind, "ts": time.time(), **fields}
        with self._lock:
            self._events.append(ev)

    def dump(self) -> dict:
        with self._lock:
            records = list(self._records)
            events = list(self._events)
        return {
            "capacity": self.capacity,
            "batches": records,
            "events": events,
        }

    def clear(self) -> None:
        with self._lock:
            self._records.clear()
            self._events.clear()


_recorder = FlightRecorder()

# optional provider of the latency-budget slow-request ring, bound by
# bootstrap to BudgetTracker.slow_dump so the SIGQUIT forensics dump
# carries the worst recent waterfalls next to the batch records
_slow_provider: Optional[Any] = None


def recorder() -> FlightRecorder:
    return _recorder


def bind_slow_requests(provider: Optional[Any]) -> None:
    global _slow_provider
    _slow_provider = provider


def configure(capacity: int = DEFAULT_CAPACITY, enabled: bool = True) -> FlightRecorder:
    """Re-bound the process-wide recorder (bootstrap wiring). Existing
    references keep working: the instance is mutated, not replaced."""
    rec = _recorder
    with rec._lock:
        rec.capacity = max(1, int(capacity))
        rec.enabled = enabled
        rec._records = deque(rec._records, maxlen=rec.capacity)
        rec._events = deque(rec._events, maxlen=rec.capacity)
    return rec


def install_sigquit_dump() -> bool:
    """Print the flight dump to stderr on SIGQUIT (the classic "what was the
    server just doing" signal). Returns False off-main-thread or where the
    signal doesn't exist; the HTTP debug endpoint still works there."""
    if not hasattr(signal, "SIGQUIT"):
        return False

    prev = signal.getsignal(signal.SIGQUIT)

    def dump(_sig, _frm):
        try:
            out = _recorder.dump()
            if _slow_provider is not None:
                with contextlib.suppress(Exception):
                    out["slow_requests"] = _slow_provider()
            sys.stderr.write(json.dumps(out, default=str) + "\n")
            sys.stderr.flush()
        except Exception:  # noqa: BLE001  (diagnostics must never kill serving)
            pass
        if callable(prev):
            prev(_sig, _frm)

    with contextlib.suppress(ValueError):  # non-main threads can't set handlers
        signal.signal(signal.SIGQUIT, dump)
        return True
    return False
