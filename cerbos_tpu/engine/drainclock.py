"""The drain thread's own clock: one cursor, so its states tile the thread's life.

``engine/budget.py:Waterfall.mark`` books the time since the previous mark
to a stage of one REQUEST; this is the same idiom for one THREAD, the
batcher's drain loop, which is where a flight's host milliseconds go.
``to(state)`` reads ``time.perf_counter()`` once and books the elapsed wall
seconds to the state being LEFT, so nothing is counted twice and nothing is
left out: the wall seconds over all states add up to the thread's lifetime.

The thread's CPU seconds are booked beside them, not by state: on the
serving host (a v5e machine, gVisor kernel) ``time.thread_time()`` costs
6 us a call and 107 us with sixteen busy threads, and ticks in steps of
10 ms (PERF.md, PR 24), so a read per state change would cost more than the
states it measures and resolve none of them. It is read at most every
``CPU_EVERY_S`` and all of it is booked as ``work`` (a blocked thread uses
none): where it falls short of the wall seconds of the ``work`` states, the
thread held work and no CPU (another thread had the interpreter lock, or
the kernel had descheduled it).

The batcher installs one clock per drain thread; the evaluator reaches it
through :func:`to`, which does nothing on any other thread (probes, the
bisect thread, direct ``check()`` callers), so ``submit``/``collect`` grow
no parameter. While the profiler has a capture open every state is also a
region on the device trace (``observability.region``).
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from .. import observability

IDLE = "idle"          # wait: queue empty and nothing in flight, or parked at a cutover barrier
WINDOW = "window"      # wait: the coalescing window, up to batchWindowMs for a second plan query (a check never waits)
PACK = "pack"          # rows of the flight's inputs -> PackedBatch
STACK = "stack"        # variant choice, candidate remap, pad + stack into transfer matrices
DISPATCH = "dispatch"  # the jitted call (host->device puts) and the start of the result copy
COMPILE = "compile"    # first call of a new jit key: trace + XLA compile or cache load
ORACLE = "oracle"      # a flight evaluated inside submit(): the oracle under minDeviceBatch, else the numpy backend or a mesh
FETCH = "fetch"        # wait: the device and the one device->host fetch
ASSEMBLE = "assemble"  # result slicing + CheckOutput assembly
SETTLE = "settle"      # futures resolved, waterfalls booked
POST = "post"          # after settle: flight record, hot rules, sentinel hand-off
OTHER = "other"        # whatever is left: locks, queue pops, metric updates, plan flights

ALL = "all"            # the label of the CPU series, which is not split by state
CPU_EVERY_S = 0.1      # the thread's CPU clock is read at most this often

STATES = (IDLE, WINDOW, PACK, STACK, DISPATCH, COMPILE, ORACLE, FETCH, ASSEMBLE, SETTLE, POST, OTHER)
WAIT = frozenset((IDLE, WINDOW, FETCH))

# names on the profiler's trace: the loop's own waits under ``batcher.``, a
# flight's states under ``batch.`` beside the spans start_span emits there
REGIONS = {s: ("batcher." if s in (IDLE, WINDOW, OTHER) else "batch.") + s for s in STATES}

_tls = threading.local()


class DrainClock:
    """Owned by one thread. ``lap`` holds the wall seconds per state since the
    last :meth:`take_lap`: the batcher reads a flight's stages from it."""

    __slots__ = ("state", "lap", "_wall", "_cpu", "_cpu_due", "_vec", "_keys", "_cpu_key", "_region")

    def __init__(self, shard: str = "0"):
        self._vec = observability.metrics().counter_vec(
            "cerbos_tpu_batcher_thread_seconds_total",
            "seconds of the batcher drain thread's life: clock=wall by state and kind (wait|work), adding up "
            "to the thread's lifetime; clock=cpu for the whole thread (state=all), read every 0.1 s",
            label=("state", "kind", "clock", "shard"),
        )
        self._keys = {s: (s, "wait" if s in WAIT else "work", "wall", shard) for s in STATES}
        self._cpu_key = (ALL, "work", "cpu", shard)
        self.state = OTHER
        self.lap: dict[str, float] = {}
        self._region = None  # the open region of the profiler's trace, while a capture is open
        self._wall = time.perf_counter()
        self._cpu = time.thread_time()
        self._cpu_due = self._wall + CPU_EVERY_S

    def to(self, state: str) -> float:
        """Returns the wall seconds booked to the state being left."""
        wall = time.perf_counter()
        left = self.state
        d_wall = wall - self._wall
        self._vec.inc(self._keys[left], d_wall)
        self.lap[left] = self.lap.get(left, 0.0) + d_wall
        self._wall, self.state = wall, state
        if wall >= self._cpu_due:
            self.book_cpu()
        if self._region is not None:
            self._region.__exit__(None, None, None)
            self._region = None
        if observability.capture_open:
            self._region = observability.region(REGIONS[state])
            self._region.__enter__()
        return d_wall

    def book_cpu(self) -> None:
        cpu = time.thread_time()
        self._vec.inc(self._cpu_key, cpu - self._cpu)
        self._cpu, self._cpu_due = cpu, self._wall + CPU_EVERY_S

    def take_lap(self) -> dict[str, float]:
        lap, self.lap = self.lap, {}
        return lap


def install(shard: str = "0") -> DrainClock:
    """Give the calling thread its clock (the drain loop, once, at its start)."""
    _tls.clock = clock = DrainClock(shard)
    return clock


def to(state: str) -> None:
    """Move the calling thread's clock to ``state``; nothing where it has none."""
    clock: Optional[DrainClock] = getattr(_tls, "clock", None)
    if clock is not None:
        clock.to(state)
