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

Below a state: its PARTS, on a second cursor inside it (PR 38). A state is
entered with the name of its first part (``to(PACK, PACK_PLAN)``) and
:func:`part` moves on to the next; each reads ``perf_counter`` once and books
the seconds since the last such read to the part being left, into the lap
alone. ``to()`` closes the open part with the very reading that closes the
state, so the parts of a state tile it by construction: ``pack``,
``dispatch`` and ``assemble`` of a flight ARE the sums of their parts, to the
rounding of a float addition (``assemble`` is entered as ``assemble_outputs``
and leaves it for ``assemble_schema`` only while a flight's device-served
inputs are validated: with ``schema.enforcement: none`` that part is never
entered and ``assemble_outputs`` is all of ``assemble``). Sub-states that also add into their parent were the other
choice; a second cursor was taken because it leaves ``to()``, the states and
``batcher_thread_seconds_total{state="pack"}`` exactly what they were (the
same calls at the same places: nothing a reader of the states sees can move),
costs no counter update under a lock, and needs no guard for the one place
where the packer runs inside another state: ``part()`` does nothing in a
state that was entered without a part (the numpy backend and a mesh pack
inside ``oracle``), as it does nothing on a thread without a clock (the layout
preloader, probes, direct ``check()`` callers, a front end's inline route).
While a capture is open the region on the trace is the PART's
(``batch.pack_gather``, in place of ``batch.pack``), so an idle gap of the
device names the part the drain thread was in.
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

# the parts of ``pack`` (tpu/packer.py), of ``dispatch`` and of ``assemble`` (tpu/evaluator.py): observed once a flight as
# ``cerbos_tpu_batch_stage_seconds{stage=<part>}``, carried in the flight record's ``timings``
PACK_PLAN = "pack_plan"        # the per-input loop: shape key, shape-memo hit (or the shape's build), InputPlan
PACK_GATHER = "pack_gather"    # K/J/D, the candidate blocks' stack and its six gathers, the scope-permission rows
PACK_SCALARS = "pack_scalars"  # the scalar attribute columns: the native encoders and the one store, or the per-path loop without them
PACK_LISTS = "pack_lists"      # the list columns
PACK_TS = "pack_ts"            # the timestamp columns and the batch's now()
PACK_PREDS = "pack_preds"      # the host-evaluated predicates, then the PackedBatch itself
DISPATCH_CALL = "dispatch_call"  # fn(**stacked): JAX's handling of a keyword call, the one put, the enqueue
DISPATCH_COPY = "dispatch_copy"  # copy_to_host_async() and the handle
ASSEMBLE_SCHEMA = "assemble_schema"    # schema validation of the flight's device-served inputs; never entered with enforcement none
ASSEMBLE_OUTPUTS = "assemble_outputs"  # everything else of assemble: the decision rows' bytes, the memo, the CheckOutputs
PARTS = {
    PACK: (PACK_PLAN, PACK_GATHER, PACK_SCALARS, PACK_LISTS, PACK_TS, PACK_PREDS),
    DISPATCH: (DISPATCH_CALL, DISPATCH_COPY),
    ASSEMBLE: (ASSEMBLE_SCHEMA, ASSEMBLE_OUTPUTS),
}

ALL = "all"            # the label of the CPU series, which is not split by state
CPU_EVERY_S = 0.1      # the thread's CPU clock is read at most this often

STATES = (IDLE, WINDOW, PACK, STACK, DISPATCH, COMPILE, ORACLE, FETCH, ASSEMBLE, SETTLE, POST, OTHER)
WAIT = frozenset((IDLE, WINDOW, FETCH))

# names on the profiler's trace: the loop's own waits under ``batcher.``, a
# flight's states under ``batch.`` beside the spans start_span emits there
REGIONS = {s: ("batcher." if s in (IDLE, WINDOW, OTHER) else "batch.") + s for s in STATES}
REGIONS.update({p: "batch." + p for parts in PARTS.values() for p in parts})

_tls = threading.local()


class DrainClock:
    """Owned by one thread. ``lap`` holds the wall seconds per state, and per
    part of a state entered with one, since the last :meth:`take_lap`: the
    batcher reads a flight's stages from it."""

    __slots__ = ("state", "lap", "_wall", "_cpu", "_cpu_due", "_vec", "_keys", "_cpu_key", "_region",
                 "_part", "_part_wall")

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
        self._part: Optional[str] = None  # the open part of the state, where the state was entered with one
        self._wall = self._part_wall = time.perf_counter()
        self._cpu = time.thread_time()
        self._cpu_due = self._wall + CPU_EVERY_S

    def to(self, state: str, part: Optional[str] = None) -> float:
        """Returns the wall seconds booked to the state being left. ``part``:
        the first part of ``state``, for a state that is tiled by parts."""
        wall = time.perf_counter()
        left = self.state
        d_wall = wall - self._wall
        self._vec.inc(self._keys[left], d_wall)
        lap = self.lap
        lap[left] = lap.get(left, 0.0) + d_wall
        if self._part is not None:
            lap[self._part] = lap.get(self._part, 0.0) + (wall - self._part_wall)
        self._wall, self.state = wall, state
        self._part, self._part_wall = part, wall
        if wall >= self._cpu_due:
            self.book_cpu()
        if self._region is not None or observability.capture_open:
            self._mark_region(state if part is None else part)
        return d_wall

    def part(self, name: str) -> None:
        """Move on to the next part of the state; nothing in a state that was
        entered without one."""
        left = self._part
        if left is None:
            return
        wall = time.perf_counter()
        self.lap[left] = self.lap.get(left, 0.0) + (wall - self._part_wall)
        self._part, self._part_wall = name, wall
        if self._region is not None or observability.capture_open:
            self._mark_region(name)

    def _mark_region(self, name: str) -> None:
        """Only while a capture is open, or to close the region one left open."""
        if self._region is not None:
            self._region.__exit__(None, None, None)
            self._region = None
        if observability.capture_open:
            self._region = observability.region(REGIONS[name])
            self._region.__enter__()

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


def to(state: str, part: Optional[str] = None) -> None:
    """Move the calling thread's clock to ``state``; nothing where it has none."""
    clock: Optional[DrainClock] = getattr(_tls, "clock", None)
    if clock is not None:
        clock.to(state, part)


def part(name: str) -> None:
    """Move the calling thread's clock to the next part of its state; nothing
    where it has no clock, or in a state that is not tiled by parts."""
    clock: Optional[DrainClock] = getattr(_tls, "clock", None)
    if clock is not None:
        clock.part(name)
