"""Request micro-batcher: packs concurrent requests into streamed device batches.

The north-star BatchEvaluator (BASELINE.json): the reference fans requests
onto a goroutine pool (engine.go:74-144); here concurrent CheckResources
calls enqueue and a batcher thread drains them into padded device batches.
Requests block on a future and get their slice of the batch output back.

The batcher drives the evaluator's STREAMING pipeline, not its blocking
``check()``: each drained group is queued on the device via ``submit()``
(async dispatch — the call returns before the device runs) and its ticket
joins an in-flight window of up to ``max_inflight`` batches. While earlier
tickets' transfers + compute are in flight, the batcher keeps draining and
submitting newer requests; ``collect()`` settles each ticket's futures as
its results land. Wall-clock under concurrent load approaches
max(host pack/assembly, device work) instead of their sum (``bench.py``'s
streaming leg keeps several tickets in flight the same way).

Requests coalesce by queueing behind the flight in progress. A queue that
holds a check is drained at once: a page is a device batch already, and no
traffic measured on the chip brings a lone check its fifteen companions within
``batchWindowMs`` (PERF.md section 6, PR 25). The loop itself waits for more
only with nothing in flight and plan queries alone queued (``_plans_alone``).

A request crosses to the drain thread only when crossing can change the
flight: one under the evaluator's ``min_device_batch`` that finds the queue
empty would make a flight of its own that ``submit()`` hands to the CPU oracle,
so ``check()`` answers it from that oracle on the caller's thread
(``_serve_inline``; PERF.md section 6, PR 30).

The device path is a supervised fault domain (docs/ROBUSTNESS.md):

- a ``DeviceHealth`` breaker routes ``check()`` straight to the CPU oracle
  while open (no request ever waits out the future timeout against a dead
  device) and re-closes via background probe batches;
- a failed device batch is never surfaced to its co-batched requests:
  each waiter re-serves its own inputs from the oracle, and the group is
  bisected off-path to find and quarantine the poison input;
- per-request deadlines ride in ``_Pending`` and expire at drain time with
  ``DeadlineExceeded`` instead of spending device work on dead requests;
- a dead drain loop fails fast: waiters are settled immediately and new
  requests take the oracle, instead of hanging until timeout forever.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FutureTimeoutError
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence

from ..observability import SpanContext, current_span, export_span, start_span
from ..ruletable import check_input
from . import types as T
from .admission import OverloadRefused
from .budget import (
    FRONT_ENQUEUE,
    POINT_DEVICE_SUBMIT,
    POINT_ENQUEUE,
    STAGE_ADMISSION,
    STAGE_COLLECT,
    STAGE_DEVICE,
    STAGE_EVALUATE,
    STAGE_ORACLE,
    STAGE_PACK,
    STAGE_QUEUE_WAIT,
    STAGE_SETTLE,
    Waterfall,
)
from . import drainclock, hotrules
from .budget import tracker as budget_tracker
from .flight import recorder as flight_recorder
from .health import DeviceHealth  # noqa: F401  (re-exported for wiring/tests)

_log = logging.getLogger("cerbos_tpu.engine.batcher")


class DeadlineExceeded(Exception):
    """The request's deadline expired before a decision was produced.

    Maps to gRPC DEADLINE_EXCEEDED / HTTP 504 at the server layer."""


class _BatchFailed(Exception):
    """Internal: the device batch carrying this request failed. The waiting
    ``check()`` thread catches this and re-serves its own inputs from the
    CPU oracle — co-batched requests each recover independently instead of
    all erroring together."""

    def __init__(self, cause: Optional[BaseException], reason: str = "batch_error"):
        super().__init__(reason)
        self.cause = cause
        self.reason = reason


@dataclass
class _Pending:
    inputs: list[T.CheckInput]
    params: Optional[T.EvalParams]
    future: Future
    enqueued_at: float = field(default_factory=time.perf_counter)
    deadline: Optional[float] = None  # absolute time.monotonic() deadline
    # the request's span context, detached on the request thread so the
    # batcher drain thread can parent/link device-batch spans into the
    # request's trace (span parenting via observability._current is
    # thread-local and dies at this hop otherwise)
    ctx: Optional[SpanContext] = None
    # the request's latency-budget waterfall (engine/budget.py); like ctx it
    # migrates with the request across the thread hop, and the drain thread
    # books queue_wait/pack/device/collect/settle into it at settle time
    wf: Optional[Waterfall] = None
    # admission priority class ('' = unclassified → the default lane);
    # selects the weighted priority lane this request queues in
    pclass: str = ""
    # "check" pendings carry CheckInputs for the device evaluator; "plan"
    # pendings carry PlanInputs for the attached batched planner and ride
    # the dedicated low-priority plan lane
    kind: str = "check"
    # the policy epoch this request's batch was submitted under — assigned
    # on the drain thread at submit time (happens-before the future
    # resolves), read back on the request thread to stamp the decision
    epoch: Optional[int] = None


class _Lane:
    """One priority lane: a FIFO deque plus its scheduling parameters."""

    __slots__ = ("name", "priority", "weight", "budget", "q", "credit")

    def __init__(self, name: str, priority: int = 0, weight: int = 1, budget: int = 0):
        self.name = name
        self.priority = int(priority)          # lower preempts
        self.weight = max(1, int(weight))      # fair share within a band
        self.budget = max(0, int(budget))      # max queued; 0 = unlimited
        self.q: deque[_Pending] = deque()
        self.credit = 0.0                      # smooth-WRR accumulator


class _PriorityLanes:
    """Weighted priority lanes over the pending queue.

    Selection is strict priority across bands (the lowest ``priority``
    value with work wins — interactive traffic preempts bulk outright at
    overload, which is the point) and smooth weighted round-robin within a
    band (deterministic nginx-style credit counters, no RNG). Per-class
    queue budgets bound each lane so one class's backlog cannot starve the
    ring for everyone else.

    Unconfigured, everything rides one default lane — byte-for-byte the
    old FIFO behavior. Every method runs under the batcher lock; ``peek``
    and ``popleft`` agree because ``_pick`` is pure and nothing interleaves
    between them.
    """

    __slots__ = ("_lanes", "_order", "_default", "_len")

    def __init__(self):
        self._default = _Lane("default")
        self._lanes: dict[str, _Lane] = {"default": self._default}
        self._order: list[_Lane] = [self._default]
        self._len = 0

    def configure(self, lane_confs) -> None:
        """Rebuild lanes from (name, priority, weight, budget) tuples;
        anything already queued migrates into the new lanes."""
        queued = list(self)
        lanes: dict[str, _Lane] = {}
        order: list[_Lane] = []
        default: Optional[_Lane] = None
        for name, priority, weight, budget in lane_confs or ():
            lane = _Lane(str(name), priority, weight, budget)
            lanes[lane.name] = lane
            order.append(lane)
            if lane.name == "default":
                default = lane
        if default is None:
            default = _Lane("default", priority=1)
            lanes["default"] = default
            order.append(default)
        self._lanes, self._order, self._default = lanes, order, default
        self._len = 0
        for p in queued:
            self.append(p)

    def _lane(self, pclass: str) -> _Lane:
        return self._lanes.get(pclass or "default", self._default)

    def over_budget(self, pclass: str) -> bool:
        lane = self._lane(pclass)
        return lane.budget > 0 and len(lane.q) >= lane.budget

    def append(self, p: _Pending) -> None:
        self._lane(p.pclass).q.append(p)
        self._len += 1

    def _pick(self) -> Optional[_Lane]:
        band_prio: Optional[int] = None
        band: list[_Lane] = []
        for lane in self._order:
            if not lane.q:
                continue
            if band_prio is None or lane.priority < band_prio:
                band_prio, band = lane.priority, [lane]
            elif lane.priority == band_prio:
                band.append(lane)
        if not band:
            return None
        if len(band) == 1:
            return band[0]
        # max() is stable: ties resolve to declaration order
        return max(band, key=lambda ln: ln.credit + ln.weight)

    def peek(self) -> _Pending:
        lane = self._pick()
        if lane is None:
            raise IndexError("peek from empty lanes")
        return lane.q[0]

    def popleft(self) -> _Pending:
        lane = self._pick()
        if lane is None:
            raise IndexError("pop from empty lanes")
        band = [ln for ln in self._order if ln.q and ln.priority == lane.priority]
        if len(band) > 1:
            # smooth WRR advance: credit += weight for the whole band, the
            # winner pays back the band's total
            total = 0
            for ln in band:
                ln.credit += ln.weight
                total += ln.weight
            lane.credit -= total
        self._len -= 1
        return lane.q.popleft()

    def remove(self, p: _Pending) -> None:
        self._lane(p.pclass).q.remove(p)  # ValueError if absent, like deque
        self._len -= 1

    def clear(self) -> None:
        for lane in self._order:
            lane.q.clear()
        self._len = 0

    def __iter__(self):
        for lane in self._order:
            yield from lane.q

    def __len__(self) -> int:
        return self._len

    def __bool__(self) -> bool:
        return self._len > 0

    def depths(self) -> dict[str, int]:
        return {lane.name: len(lane.q) for lane in self._order if lane.q}


@dataclass
class _Inflight:
    """One submitted device batch awaiting collection."""

    ticket: Any
    group: list[_Pending]
    batch_id: int = 0
    n_inputs: int = 0
    batch_ctx: Optional[SpanContext] = None  # the batch.submit span
    timings: dict = field(default_factory=dict)  # stage -> seconds
    submitted_at: float = 0.0  # perf_counter at submit return
    submitted_wall_ns: int = 0
    # the same instant on the clock a profiler capture reports
    # (tpu/profiler.py), so a flight can be placed on a device trace
    submitted_monotonic_ns: int = 0
    occupancy: float = 1.0
    layout_key: Optional[str] = None
    kind: str = "check"


class _ShardStageView:
    """Binds the shard dimension of the (stage, shard)-labeled stage-latency
    HistogramVec so hot-path call sites keep the one-argument
    ``observe(stage, v)`` shape."""

    __slots__ = ("vec", "shard", "_children")

    def __init__(self, vec: Any, shard: str):
        self.vec = vec
        self.shard = shard
        self._children: dict[str, Any] = {}  # stage -> child histogram, bound once

    def observe(self, stage: str, v: float) -> None:
        child = self._children.get(stage)
        if child is None:
            child = self._children[stage] = self.vec.labels((stage, self.shard))
        child.observe(v)


def _settle(fut: Future, result: Any = None, error: Optional[BaseException] = None) -> None:
    """Resolve a future without ever raising out of the batcher thread — the
    waiter may have timed out and abandoned it."""
    try:
        if error is not None:
            fut.set_exception(error)
        else:
            fut.set_result(result)
    except Exception:  # noqa: BLE001  (InvalidStateError and kin)
        pass


def _fingerprint(inp: T.CheckInput) -> int:
    """Stable identity of a check input for the quarantine set (attrs may
    hold unhashable values, so they hash via a sorted repr)."""
    pr, rs = inp.principal, inp.resource
    return hash(
        (
            pr.id,
            tuple(pr.roles or ()),
            pr.policy_version,
            pr.scope,
            repr(sorted((pr.attr or {}).items())),
            rs.kind,
            rs.id,
            rs.policy_version,
            rs.scope,
            repr(sorted((rs.attr or {}).items())),
            tuple(inp.actions or ()),
        )
    )


def oracle_walk(
    rt: Any,
    epoch: Optional[int],
    inputs: Sequence[T.CheckInput],
    params: T.EvalParams,
    schema_mgr: Any,
    route: str = "oracle",
) -> list[T.CheckOutput]:
    """The CPU oracle's answer on the calling thread, from ONE table: the
    caller read it once, so a cutover between inputs cannot split the request
    across two, and ``epoch`` (stamped on the decisions) names that table.
    Shared by this module's routes and a pool front end's (engine/ipc.py);
    ``route`` is what the schema manager counts its validations under."""
    T.set_current_epoch(epoch)
    return [check_input(rt, i, params, schema_mgr, route) for i in inputs]


def route_families(reg: Any) -> tuple[Any, Any]:
    """The two families that a request answered with no flight moves, for
    whoever answers one: this module, and a pool's front end (whose series
    carry its ``worker`` label in the pool's scrape). ``(checks_total by
    route, batch_stage_seconds by stage and shard)``."""
    checks = reg.counter_vec(
        "cerbos_tpu_batcher_checks_total",
        "check() and check_async() calls past the refusal ladder, by route: inline (under minDeviceBatch on an "
        "empty queue, or in a pool's front end that holds the owner's committed policy set: answered by the CPU "
        "oracle on the caller's thread, no flight) or queued (check_async, the pool owner's door, always queues)",
        label="route",
    )
    stages = reg.histogram_vec(
        "cerbos_tpu_batch_stage_seconds",
        "device-batch pipeline stage seconds on the drain thread's clock, once per flight, by shard: "
        "pack (= pack_plan + pack_gather + pack_scalars + pack_lists + pack_ts + pack_preds), submit (= stack + "
        "dispatch + compiles; dispatch = dispatch_call + dispatch_copy), device (host-clock GAP between submit "
        "returning and collect starting, not device time), collect (= fetch + assemble; assemble = assemble_schema "
        "+ assemble_outputs, the first observed only by a flight that validated inputs), settle; "
        "oracle (synchronous check of a flight or of a request under minDeviceBatch), post (after settle)",
        label=("stage", "shard"),
        buckets=[0.0001, 0.0005, 0.001, 0.002, 0.005, 0.01, 0.025, 0.05, 0.1, 0.5, 1.0],
    )
    return checks, stages


# a flight's parts of pack and of dispatch (engine/drainclock.py), and every
# stage that is observed once per flight where its submit returns
_FLIGHT_PARTS = drainclock.PARTS[drainclock.PACK] + drainclock.PARTS[drainclock.DISPATCH]
_SUBMIT_STAGES = ("pack", "submit", "stack", "dispatch", "oracle") + _FLIGHT_PARTS


class BatchingEvaluator:
    """Wraps a batch evaluator (TpuEvaluator) with cross-request batching
    and an in-flight streaming window over its submit/collect pipeline.

    ``check()`` routes a request before it queues it. One of fewer inputs than
    the evaluator's ``min_device_batch`` that finds the queue empty (and no
    cutover barrier pending) is answered on the caller's own thread by the CPU
    oracle, which is where ``submit()`` would send a flight of it alone: no
    ``_Pending``, no flight, no two thread crossings. Every other request
    queues, so a single that arrives behind a queued page still rides the
    device with it. ``cerbos_tpu_batcher_checks_total{route}`` counts both.
    ``check_async()`` (the pool owner's door) always queues, and is counted
    as ``queued``."""

    # Engine forwards per-request deadlines only to evaluators that opt in.
    supports_deadline = True
    # Engine forwards latency-budget waterfalls only to evaluators that
    # book their own stages (admission/queue/pack/device/collect/settle).
    supports_waterfall = True
    # Engine forwards the admission priority class only to evaluators with
    # priority lanes (engine/admission.py classifies at ingress).
    supports_pclass = True

    def __init__(
        self,
        evaluator: Any,
        max_batch: int = 4096,
        max_wait_ms: float = 2.0,
        min_batch_to_wait: int = 2,
        request_timeout_s: float = 30.0,
        max_inflight: int = 3,
        health: Optional[DeviceHealth] = None,
        quarantine_max: int = 128,
        bisect_budget: int = 64,
        shard_id: Optional[int] = None,
    ):
        self.evaluator = evaluator
        # shard identity: which lane of the sharded pool this batcher drives.
        # None means "the only batcher" (single-evaluator serving); metrics
        # and flight records are still labeled shard="0" so dashboards see
        # one schema either way.
        self.shard_id = shard_id
        self._shard_label = str(shard_id) if shard_id is not None else "0"
        self.max_batch = max_batch
        self.request_timeout = request_timeout_s
        # the coalescing window, entered for plan queries alone (_plans_alone):
        # how long it may last, and how many REQUESTS end it
        self.max_wait = max_wait_ms / 1000.0
        self.min_batch_to_wait = min_batch_to_wait
        self.max_inflight = max(1, int(max_inflight))
        self.health = health
        # parity sentinel (engine/sentinel.py), attached post-construction;
        # when set, completed device batches are offered for shadow-oracle
        # sampling from the drain thread
        self.sentinel: Optional[Any] = None
        # batched planner (plan/batch.py BatchPlanner), attached
        # post-construction; when set, plan() coalesces PlanResources
        # queries into vectorized partial-evaluation flights on the same
        # drain loop, riding the low-priority "plan" lane
        self.plan_planner: Optional[Any] = None
        self.quarantine_max = max(1, int(quarantine_max))
        self.bisect_budget = max(3, int(bisect_budget))
        self._queue = _PriorityLanes()
        self._lock = threading.Lock()
        self._wakeup = threading.Condition(self._lock)
        self._stop = False
        self._dead: Optional[BaseException] = None
        self._draining: list[_Pending] = []
        # policy epoch this lane is serving (rollout.py stamps it inside the
        # cutover barrier); None until a RolloutController seeds/commits one
        self.epoch: Optional[int] = None
        # pending cutover barrier (rollout.SwapBarrier): when set, the drain
        # loop submits nothing new, collects every in-flight batch, then
        # parks at the flight boundary until the controller releases it —
        # the mechanism that guarantees no request spans two rule tables
        self._swap_barrier: Optional[Any] = None
        self._qlock = threading.Lock()
        self._quarantine: dict[int, bool] = {}  # insertion-ordered, bounded
        self._bisect_busy = False
        self.stats = {
            "batches": 0,
            "batched_requests": 0,
            "inflight_peak": 0,
            "oracle_fallbacks": 0,
            "batch_errors": 0,
            "deadline_drops": 0,
            "quarantined": 0,
            "lane_refusals": 0,
            "plan_batches": 0,
            "plan_requests": 0,
            "plan_fallbacks": 0,
        }
        self._init_metrics()
        # instantiate the process-global hot-rule recorder eagerly so its
        # metric families exist from bootstrap (scrapes see zeroed series
        # before the first decision, and the registry lint covers them)
        hotrules.recorder()
        tname = "check-batcher" if shard_id is None else f"check-batcher-s{shard_id}"
        self._thread = threading.Thread(target=self._loop, daemon=True, name=tname)
        self._thread.start()

    def _init_metrics(self) -> None:
        from ..observability import metrics

        reg = metrics()
        self.m_batch_size = reg.histogram(
            "cerbos_tpu_batcher_batch_size",
            "inputs per device batch",
            buckets=[1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096],
        )
        self.m_queue_wait = reg.histogram(
            "cerbos_tpu_batcher_queue_wait_seconds",
            "request wait from enqueue to device submit",
            buckets=[0.0005, 0.001, 0.002, 0.005, 0.01, 0.025, 0.05, 0.1, 0.5, 1.0],
        )
        self.m_inflight = reg.gauge_vec(
            "cerbos_tpu_batcher_inflight",
            "device batches currently in flight, by shard",
            label="shard",
            track_max=True,
        ).labels(self._shard_label)
        self.m_oracle_fallbacks = reg.counter_vec(
            "cerbos_tpu_batcher_oracle_fallbacks_total",
            "requests served from the CPU oracle instead of the device path, by reason",
            label="reason",
        )
        self.m_batches = reg.counter(
            "cerbos_tpu_batcher_batches_total", "device batches submitted"
        )
        self.m_requests = reg.counter(
            "cerbos_tpu_batcher_requests_total", "requests coalesced into device batches"
        )
        self.m_checks, self._m_stage_vec = route_families(reg)
        self.m_deadline_drops = reg.counter(
            "cerbos_tpu_batcher_deadline_drops_total",
            "requests dropped with DEADLINE_EXCEEDED before device work",
        )
        self.m_quarantined = reg.counter(
            "cerbos_tpu_batcher_quarantined_total",
            "poison inputs quarantined after batch bisection",
        )
        # device-economics: how full the padded device layouts actually are,
        # and the per-stage latency attribution the traces aggregate over
        self.m_occupancy = reg.gauge_vec(
            "cerbos_tpu_batch_occupancy",
            "real rows / padded rows of the last device batch (1.0 = no padding waste), by shard",
            label="shard",
        ).labels(self._shard_label)
        self.m_padding_waste = reg.counter_vec(
            "cerbos_tpu_batch_padding_waste_rows_total",
            "padded device rows that carried no real input, by shard",
            label="shard",
        )
        self.m_queue_budget = reg.counter_vec(
            "cerbos_tpu_admission_queue_budget_total",
            "requests refused because their priority class's lane queue budget was full, by class",
            label="pclass",
        )
        self.m_stage_seconds = _ShardStageView(self._m_stage_vec, self._shard_label)
        self._m_stage_vec.labels((drainclock.ASSEMBLE_SCHEMA, self._shard_label))  # at 0 from boot: no flight may ever observe it
        self.m_device_calls = reg.histogram_vec(
            "cerbos_tpu_batch_device_calls",
            "jitted device calls per device-served flight (one pack, dispatch, fetch and assemble each): "
            "1 unless a direct batch is larger than pipelineChunk; an oracle-served flight is not observed, "
            "by shard",
            label="shard",
            buckets=[1, 2, 3, 4, 8, 16],
        ).labels(self._shard_label)
        self.m_window_wait = reg.histogram_vec(
            "cerbos_tpu_batcher_window_wait_seconds",
            "per flight: how long the drain loop deliberately waited (at most batchWindowMs) for a second "
            "plan query before draining the queue, 0 where it did not wait (every queue that held a "
            "check); part of every rider's queue wait, by shard",
            label="shard",
            buckets=[0.0001, 0.0005, 0.001, 0.002, 0.005, 0.01, 0.025, 0.05, 0.1],
        ).labels(self._shard_label)

    # -- oracle fallback ----------------------------------------------------

    def _oracle_outputs(
        self, inputs: Sequence[T.CheckInput], params: Optional[T.EvalParams], route: str = "oracle"
    ) -> list[T.CheckOutput]:
        """The CPU oracle's answer on the calling thread, for a fallback and
        for a request that never needed a flight (``route="inline"``) alike."""
        ev = self.evaluator
        rt = ev.rule_table  # read once; the epoch stamp travels with the table
        out = oracle_walk(
            rt, getattr(rt, "policy_epoch", None), inputs, params or T.EvalParams(), ev.schema_mgr, route
        )
        # oracle-served decisions carry source="oracle" from check_input;
        # fold them into the hot-rule heatmap so attribution-rate and
        # device-vs-oracle splits cover them too
        hotrules.recorder().observe(out)
        return out

    def _serve_oracle(
        self,
        inputs: Sequence[T.CheckInput],
        params: Optional[T.EvalParams],
        reason: str,
        wf: Optional[Waterfall] = None,
    ) -> list[T.CheckOutput]:
        self.stats["oracle_fallbacks"] += 1
        self.m_oracle_fallbacks.inc(reason)
        if wf is not None:
            wf.note_fallback(reason)
        out = self._oracle_outputs(inputs, params)
        if wf is not None:
            wf.mark(STAGE_ORACLE)
        return out

    def _serve_inline(
        self,
        inputs: Sequence[T.CheckInput],
        params: Optional[T.EvalParams],
        deadline: Optional[float],
        wf: Optional[Waterfall],
    ) -> list[T.CheckOutput]:
        """Answer a request under ``min_device_batch`` here, on its own thread.
        Not a fallback: the oracle is where ``submit()`` would have sent a
        flight of this request alone, with the same table read and the same
        epoch stamp. It books what has a reader and nothing of a flight, since
        none was made: no batch size, window wait, flight record or batch id."""
        span = current_span()
        if span is not None and span.name == "engine.Check":
            span.set_attribute("path", "inline")  # it said "device" on the way in
        self._admit_wf(wf, deadline)
        if wf is not None:
            wf.mark(STAGE_QUEUE_WAIT)  # a true wait of nothing
        t0 = time.perf_counter()
        out = self._oracle_outputs(inputs, params, route="inline")
        self.m_stage_seconds.observe("oracle", time.perf_counter() - t0)
        if wf is not None:
            wf.mark(STAGE_EVALUATE)
        sentinel = self.sentinel
        if sentinel is not None:
            # no replay (an oracle answer against the oracle proves nothing),
            # but the rollout gate replays the sentinel's ring of live inputs
            # before a cutover, and on a sidecar-only host it would go empty
            sentinel.observe_inline(self.shard_id or 0, inputs)
        return out

    # -- request path -------------------------------------------------------

    # queued plan queries beyond this refuse with OverloadRefused instead of
    # growing an unbounded analytical backlog behind interactive checks
    PLAN_QUEUE_BUDGET = 256

    def configure_lanes(self, lane_confs) -> None:
        """Install the weighted priority lanes (one per admission class,
        plus the default catch-all) from (name, priority, weight,
        queue_budget) tuples — ``AdmissionController.lane_confs()``. A
        "plan" lane is appended below every configured band unless the
        config names one explicitly: plan queries are analytical traffic
        that must never preempt an interactive check."""
        confs = list(lane_confs or ())
        if confs and not any(str(c[0]) == "plan" for c in confs):
            floor = max(int(c[1]) for c in confs)
            confs.append(("plan", floor + 1, 1, self.PLAN_QUEUE_BUDGET))
        with self._wakeup:
            self._queue.configure(confs)

    def lane_depths(self) -> dict[str, int]:
        with self._lock:
            return self._queue.depths()

    def _enqueue(self, pending: _Pending) -> bool:
        """Enqueue under the lane's queue budget; False = budget full (the
        caller refuses — per-class backlog must not starve the ring)."""
        with self._wakeup:
            if self._queue.over_budget(pending.pclass):
                self.stats["lane_refusals"] += 1
                self.m_queue_budget.inc(pending.pclass or "default")
                return False
            self._queue.append(pending)
            self._wakeup.notify()
            return True

    def check(
        self,
        inputs: Sequence[T.CheckInput],
        params: Optional[T.EvalParams] = None,
        deadline: Optional[float] = None,
        wf: Optional[Waterfall] = None,
        pclass: Optional[str] = None,
    ) -> list[T.CheckOutput]:
        T.set_current_shard(self.shard_id if self.shard_id is not None else 0)
        if wf is not None:
            wf.shard = self.shard_id if self.shard_id is not None else 0
        if deadline is not None and time.monotonic() >= deadline:
            self._count_deadline_drop()
            raise DeadlineExceeded("request deadline expired before evaluation")
        if self._quarantine and self._has_quarantined(inputs):
            return self._serve_oracle(inputs, params, "quarantine", wf=wf)
        health = self.health
        if health is not None and not health.allow_device():
            # breaker open: serve from the oracle with NO device wait; a due
            # probe rides this request's inputs off-path to test re-close
            token = health.should_probe()
            if token is not None:
                self._spawn_probe(token, list(inputs)[:16], params)
            return self._serve_oracle(inputs, params, "breaker_open", wf=wf)
        if self._stop or self._dead is not None or not self._thread.is_alive():
            # drain loop gone (shutdown or crash): fail fast to the oracle
            return self._serve_oracle(inputs, params, "batcher_dead", wf=wf)
        # A request crosses to the drain thread only when crossing can change
        # the flight. The look at the queue and the barrier is a racy read
        # without the lock, by design (as load()'s is): both routes give the
        # same answer bit for bit, so a stale read costs one crossing, or
        # spares one, and nothing else. An evaluator with no min_device_batch
        # has no oracle of its own to be sent to: it never takes the route.
        if (
            len(inputs) < getattr(self.evaluator, "min_device_batch", 0)
            and not self._queue
            and self._swap_barrier is None
        ):
            self.m_checks.inc("inline")
            return self._serve_inline(inputs, params, deadline, wf)
        self.m_checks.inc("queued")
        with start_span("batcher.enqueue", inputs=len(inputs)) as span:
            fut: Future = Future()
            # the span context crosses the batcher thread hop in _Pending so
            # the device batch's spans land in this request's trace
            pending = _Pending(
                list(inputs), params, fut, deadline=deadline, ctx=span.context, wf=wf,
                pclass=pclass or "",
            )
            self._admit_wf(wf, deadline)
            if not self._enqueue(pending):
                span.set_attribute("outcome", "queue_budget")
                raise OverloadRefused(pending.pclass, "queue_budget", retry_after=0.1)
            wait = self.request_timeout
            if deadline is not None:
                wait = min(wait, max(0.0, deadline - time.monotonic()))
            try:
                outs = fut.result(timeout=wait)
                # assigned on the drain thread at submit time (after the
                # cutover-barrier check): the epoch this batch actually ran on
                T.set_current_epoch(pending.epoch)
                return outs
            except DeadlineExceeded:
                span.set_attribute("outcome", "deadline_exceeded")
                raise
            except _BatchFailed as e:
                # the device batch failed (or the batcher is shutting down /
                # dead, or the breaker opened while queued): recover this
                # request's own inputs from the oracle
                span.set_attribute("outcome", e.reason)
                return self._serve_oracle(pending.inputs, params, e.reason, wf=wf)
            except (TimeoutError, FutureTimeoutError):  # distinct classes before 3.11
                # a wedged device must not block server threads forever: drop the
                # request from the queue (if still there) and serve it from the
                # CPU oracle. The future is NOT cancelled — if the device call
                # eventually returns, _collect's set_result on it must stay legal.
                with self._wakeup:
                    try:
                        self._queue.remove(pending)
                    except ValueError:
                        pass
                if deadline is not None and time.monotonic() >= deadline:
                    self._count_deadline_drop()
                    raise DeadlineExceeded("request deadline expired while queued") from None
                if health is not None:
                    health.record_timeout()
                span.set_attribute("outcome", "timeout")
                return self._serve_oracle(pending.inputs, params, "timeout", wf=wf)

    def check_async(
        self,
        inputs: Sequence[T.CheckInput],
        params: Optional[T.EvalParams] = None,
        deadline: Optional[float] = None,
        ctx: Optional[SpanContext] = None,
        wf: Optional[Waterfall] = None,
        pclass: Optional[str] = None,
    ) -> Future:
        """Non-blocking enqueue for callers that hold many tickets at once
        (the IPC server fronting N worker processes cannot burn a thread per
        ticket). Same admission ladder as ``check()``, but refusals settle
        the returned future with the exception instead of serving the oracle
        here — the front-end process owns its own COW-shared oracle and the
        batcher process keeps its cycles for device work. The future resolves
        to ``list[CheckOutput]`` or raises ``DeadlineExceeded``/``_BatchFailed``.
        """
        fut: Future = Future()
        if wf is not None:
            wf.shard = self.shard_id if self.shard_id is not None else 0
        if deadline is not None and time.monotonic() >= deadline:
            self._count_deadline_drop()
            _settle(fut, error=DeadlineExceeded("request deadline expired before evaluation"))
            return fut
        if self._quarantine and self._has_quarantined(inputs):
            _settle(fut, error=_BatchFailed(None, "quarantine"))
            return fut
        health = self.health
        if health is not None and not health.allow_device():
            token = health.should_probe()
            if token is not None:
                self._spawn_probe(token, list(inputs)[:16], params)
            _settle(fut, error=_BatchFailed(None, "breaker_open"))
            return fut
        if self._stop or self._dead is not None or not self._thread.is_alive():
            _settle(fut, error=_BatchFailed(self._dead, "batcher_dead"))
            return fut
        self.m_checks.inc("queued")
        pending = _Pending(
            list(inputs), params, fut, deadline=deadline, ctx=ctx, wf=wf,
            pclass=pclass or "",
        )
        self._admit_wf(wf, deadline)
        if not self._enqueue(pending):
            # rides the existing ERR-frame path: the front end turns this
            # into HTTP 429 / RESOURCE_EXHAUSTED, costing the batcher nothing
            _settle(fut, error=_BatchFailed(None, "queue_budget"))
        return fut

    # -- plan path ----------------------------------------------------------

    def plan(
        self,
        inputs: Sequence[Any],
        params: Optional[T.EvalParams] = None,
        deadline: Optional[float] = None,
        wf: Optional[Waterfall] = None,
    ) -> list[Any]:
        """Batched PlanResources: enqueue PlanInputs on the low-priority
        "plan" lane and let the drain loop coalesce concurrent queries into
        one vectorized partial-evaluation flight (plan/batch.py). Failures
        fall back to the sequential planner per query — a plan query never
        errors because a co-batched sibling did."""
        planner = self.plan_planner
        if planner is None:
            raise RuntimeError("no batched planner attached to this batcher")
        if deadline is not None and time.monotonic() >= deadline:
            self._count_deadline_drop()
            raise DeadlineExceeded("plan deadline expired before evaluation")
        if self._stop or self._dead is not None or not self._thread.is_alive():
            return self._serve_plan_sequential(inputs, params, "batcher_dead", wf=wf)
        with start_span("batcher.plan_enqueue", inputs=len(inputs)) as span:
            fut: Future = Future()
            pending = _Pending(
                list(inputs), params, fut, deadline=deadline, ctx=span.context, wf=wf,
                pclass="plan", kind="plan",
            )
            self._admit_wf(wf, deadline)
            if not self._enqueue(pending):
                span.set_attribute("outcome", "queue_budget")
                raise OverloadRefused("plan", "queue_budget", retry_after=0.1)
            wait = self.request_timeout
            if deadline is not None:
                wait = min(wait, max(0.0, deadline - time.monotonic()))
            try:
                return fut.result(timeout=wait)
            except DeadlineExceeded:
                span.set_attribute("outcome", "deadline_exceeded")
                raise
            except _BatchFailed as e:
                span.set_attribute("outcome", e.reason)
                return self._serve_plan_sequential(pending.inputs, params, e.reason, wf=wf)
            except (TimeoutError, FutureTimeoutError):
                with self._wakeup:
                    try:
                        self._queue.remove(pending)
                    except ValueError:
                        pass
                if deadline is not None and time.monotonic() >= deadline:
                    self._count_deadline_drop()
                    raise DeadlineExceeded("plan deadline expired while queued") from None
                span.set_attribute("outcome", "timeout")
                return self._serve_plan_sequential(pending.inputs, params, "timeout", wf=wf)

    def _serve_plan_sequential(
        self,
        inputs: Sequence[Any],
        params: Optional[T.EvalParams],
        reason: str,
        wf: Optional[Waterfall] = None,
    ) -> list[Any]:
        """Per-query recovery through the sequential walk of the attached
        planner (BatchPlanner extends Planner; without a batch context every
        rule routes symbolically, which is exactly the reference path)."""
        self.stats["plan_fallbacks"] += 1
        self.m_oracle_fallbacks.inc(f"plan_{reason}")
        if wf is not None:
            wf.note_fallback(f"plan_{reason}")
        planner = self.plan_planner
        out = [planner.plan(i, params) for i in inputs]
        if wf is not None:
            wf.mark(STAGE_ORACLE)
        return out

    def _admit_wf(self, wf: Optional[Waterfall], deadline: Optional[float]) -> None:
        """Book the admission stage at enqueue and sample the remaining
        deadline budget at the enqueue point."""
        shard = self.shard_id if self.shard_id is not None else 0
        if wf is not None:
            wf.mark(STAGE_ADMISSION, part=FRONT_ENQUEUE)
        if deadline is not None:
            budget_tracker().observe_budget(
                POINT_ENQUEUE, deadline - time.monotonic(), shard=shard
            )

    def _count_deadline_drop(self) -> None:
        self.stats["deadline_drops"] += 1
        self.m_deadline_drops.inc()

    # -- shard-pool routing surface -----------------------------------------

    def load(self) -> int:
        """Requests queued + in flight on this lane — the least-loaded
        routing signal for the sharded pool. Reads are racy by design (a
        routing decision needs a hint, not a barrier)."""
        return len(self._queue) + int(self.m_inflight.value)

    def routable(self, inputs: Optional[Sequence[T.CheckInput]] = None) -> bool:
        """Can this lane take device traffic right now? False while its
        breaker refuses, its drain loop is gone, or (when ``inputs`` are
        given) this lane has quarantined one of them — the pool then prefers
        a sibling shard over this lane's oracle fallback."""
        if self._stop or self._dead is not None or not self._thread.is_alive():
            return False
        if self.health is not None and not self.health.allow_device():
            return False
        if inputs is not None and self._quarantine and self._has_quarantined(inputs):
            return False
        return True

    def _queue_nonempty(self) -> bool:
        with self._lock:
            return bool(self._queue)

    # -- cutover barrier ----------------------------------------------------

    def request_swap(self, barrier: Any) -> bool:
        """Ask the drain loop to park at its next flight boundary: it stops
        submitting, collects every in-flight batch, then calls
        ``barrier.park(self)`` until the rollout controller has swapped the
        shared tables (rollout.SwapBarrier). Returns False when the drain
        loop is dead or stopping — no flight can race the swap then, and
        the controller must not wait for a thread that will never park."""
        with self._wakeup:
            if self._stop or self._dead is not None or not self._thread.is_alive():
                return False
            self._swap_barrier = barrier
            self._wakeup.notify_all()
        return True

    # -- drain loop ---------------------------------------------------------

    def _loop(self) -> None:
        # the thread's own clock (engine/drainclock.py): every line below runs
        # in exactly one of its states
        self._clock = drainclock.install(self._shard_label)
        inflight: deque[_Inflight] = deque()
        try:
            self._loop_inner(inflight)
        except BaseException as e:  # noqa: BLE001  (watchdog: fail fast, not hang)
            self._dead = e
            _log.exception("check-batcher drain loop died; requests fail over to the CPU oracle")
            draining, self._draining = self._draining, []
            for p in draining:
                _settle(p.future, error=_BatchFailed(e, "batcher_dead"))
        # drain on shutdown: settle everything still in flight, then any
        # requests still queued (waiters must not sleep out their timeout
        # against a thread that no longer exists)
        while inflight:
            flight = inflight.popleft()
            try:
                self._collect(flight)
            except BaseException as e:  # noqa: BLE001
                for p in flight.group:
                    _settle(p.future, error=_BatchFailed(e, "batcher_dead"))
            self.m_inflight.set(len(inflight))
        self._settle_residual_queue()
        self._clock.to(drainclock.OTHER)  # book the thread's last stretch
        self._clock.book_cpu()

    def _loop_inner(self, inflight: deque) -> None:
        clock = self._clock
        while True:
            window_s = 0.0
            with self._wakeup:
                if self._stop:
                    break
                barrier = self._swap_barrier
                if barrier is None and not self._queue:
                    if not inflight:
                        clock.to(drainclock.IDLE)
                        self._wakeup.wait()
                        clock.to(drainclock.OTHER)
                        continue
                elif barrier is None and not inflight and self.max_wait > 0 and self._plans_alone():
                    # small wait to let concurrent plan queries coalesce (only
                    # while the pipeline is empty: with batches in flight the
                    # collect below provides the coalescing window for free)
                    clock.to(drainclock.WINDOW)
                    deadline = time.monotonic() + self.max_wait
                    while self._plans_alone() and not self._stop and self._swap_barrier is None:
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            break
                        self._wakeup.wait(remaining)
                    window_s = clock.to(drainclock.OTHER)
                    barrier = self._swap_barrier
                pending: list[_Pending] = []
                total = 0
                now = time.monotonic()
                # with a cutover barrier pending, submit nothing new: the
                # queue keeps admitting (requests just wait out the barrier),
                # while the collect loop below drains the device pipeline to
                # the flight boundary the swap requires
                while barrier is None and self._queue and total < self.max_batch:
                    p = self._queue.peek()
                    if pending and total + len(p.inputs) > self.max_batch:
                        break
                    self._queue.popleft()
                    if p.deadline is not None and now >= p.deadline:
                        # expired while queued: don't spend device work on it
                        self._count_deadline_drop()
                        _settle(
                            p.future,
                            error=DeadlineExceeded("request deadline expired while queued"),
                        )
                        continue
                    pending.append(p)
                    total += len(p.inputs)
            if pending:
                health = self.health
                if health is not None and not health.allow_device():
                    # breaker opened while these were queued: bounce them to
                    # their waiters, which recover in parallel via the oracle
                    for p in pending:
                        _settle(p.future, error=_BatchFailed(None, "breaker_open"))
                else:
                    self._draining = pending
                    self._submit(pending, inflight, window_s)
                    self._draining = []
            # Collect when the window is full, or when there's nothing left
            # to submit (the pipeline drains while new requests may still
            # arrive; re-check the queue between collects so a fresh burst
            # re-enters the submit path with batches still in flight).
            while inflight:
                if (
                    barrier is None
                    and len(inflight) < self.max_inflight
                    and self._queue_nonempty()
                ):
                    break
                self._collect(inflight.popleft())
                self.m_inflight.set(len(inflight))
            if barrier is not None:
                # flight boundary reached: nothing in flight, nothing mid-
                # submit. Park here while the controller swaps the shared
                # tables and stamps the new epoch, then resume draining.
                clock.to(drainclock.IDLE)  # a wait with nothing to submit or collect
                barrier.park(self)
                clock.to(drainclock.OTHER)
                with self._wakeup:
                    if self._swap_barrier is barrier:
                        self._swap_barrier = None

    def _plans_alone(self) -> bool:
        """Under the lock: is the queue one the coalescing window may hold back?
        Only fewer than ``min_batch_to_wait`` plan queries and nothing else: the
        batched planner gains by deduplication inside a flight. A check never
        waits (so the scan below meets at most ``min_batch_to_wait`` - 1 items)."""
        q = self._queue
        return len(q) < self.min_batch_to_wait and all(p.kind == "plan" for p in q)

    def _submit(self, pending: list[_Pending], inflight: deque, window_s: float = 0.0) -> None:
        # group by (kind, params identity): globals etc. must match within a
        # batch, and plan pendings must never mix into a device check batch
        groups: dict[tuple[str, int], list[_Pending]] = {}
        for p in pending:
            # the epoch pin: everything submitted between two cutover
            # barriers ran against exactly this lane epoch's tables
            p.epoch = self.epoch
            groups.setdefault((p.kind, id(p.params)), []).append(p)
        now = time.perf_counter()
        shard = self.shard_id if self.shard_id is not None else 0
        clock = self._clock
        for group in groups.values():
            if group[0].kind == "plan":
                self._submit_plan(group, inflight, now)
                continue
            all_inputs: list[T.CheckInput] = []
            for p in group:
                all_inputs.extend(p.inputs)
                self.m_queue_wait.observe(now - p.enqueued_at)
                if p.wf is not None:
                    p.wf.mark(STAGE_QUEUE_WAIT)
                if p.deadline is not None:
                    # the second budget sample point: requests that reach the
                    # device already near-expired show up here, not at enqueue
                    budget_tracker().observe_budget(
                        POINT_DEVICE_SUBMIT, p.deadline - time.monotonic(), shard=shard
                    )
            batch_id = flight_recorder().next_batch_id()
            submit = getattr(self.evaluator, "submit", None)
            # parent the batch under the first co-batched request's trace and
            # link the rest: one trace gets real descendants, every other
            # co-batched trace still reaches the batch via its link
            links = [p.ctx for p in group if p.ctx is not None]
            parent = links[0] if links else None
            try:
                with start_span(
                    "batch.submit",
                    parent=parent,
                    links=links,
                    batch_id=batch_id,
                    requests=len(group),
                    inputs=len(all_inputs),
                ) as span:
                    batch_ctx = span.context
                    t0 = time.perf_counter()
                    if submit is not None:
                        ticket = submit(all_inputs, group[0].params)
                    else:
                        # plain evaluator without a streaming API: evaluate
                        # synchronously and carry the result as a ready ticket
                        clock.to(drainclock.ORACLE)
                        ticket = _ReadyTicket(self.evaluator.check(all_inputs, group[0].params))
                    clock.to(drainclock.OTHER)
                    submit_s = time.perf_counter() - t0
            except Exception as e:  # noqa: BLE001
                clock.to(drainclock.OTHER)
                clock.take_lap()
                self._batch_failed(group, all_inputs, e, batch_id=batch_id)
                continue
            # stack, dispatch and oracle seconds are booked inside submit
            # alone, and every submit ends in a take_lap: these are this flight's
            lap = clock.take_lap()
            self.stats["batches"] += 1
            self.stats["batched_requests"] += len(group)
            self.m_batches.inc()
            self.m_requests.inc(len(group))
            self.m_batch_size.observe(len(all_inputs))
            # stage timings: pack, like stack and dispatch, happens inside the
            # evaluator's submit and is read from the thread's clock; layout
            # economics come as ticket attributes (sync evaluators have no
            # packed device layout, so occupancy is 1.0)
            pack_s = lap.get(drainclock.PACK, 0.0)
            occupancy = getattr(ticket, "occupancy", None)
            if occupancy is None:
                occupancy = 1.0
            padded_rows = getattr(ticket, "padded_rows", None)
            flight = _Inflight(
                ticket,
                group,
                batch_id=batch_id,
                n_inputs=len(all_inputs),
                batch_ctx=batch_ctx,
                timings={
                    "window": window_s,
                    "pack": pack_s,
                    "submit": max(0.0, submit_s - pack_s),
                    # parts of submit, from the thread's clock; what is left
                    # of it is a first-call compile or chunking
                    "stack": lap.get(drainclock.STACK, 0.0),
                    "dispatch": lap.get(drainclock.DISPATCH, 0.0),
                    "oracle": lap.get(drainclock.ORACLE, 0.0),
                    # the parts of pack and of dispatch, which tile them (0.0
                    # for a flight that had none: oracle-served, or its call
                    # was the layout's compile)
                    **{part: lap.get(part, 0.0) for part in _FLIGHT_PARTS},
                },
                submitted_at=time.perf_counter(),
                submitted_wall_ns=time.time_ns(),
                submitted_monotonic_ns=time.monotonic_ns(),
                occupancy=float(occupancy),
                layout_key=getattr(ticket, "layout_key", None),
            )
            window_s = 0.0  # a drain that splits into several flights waited once
            self.m_window_wait.observe(flight.timings["window"])
            for stage in _SUBMIT_STAGES:
                self.m_stage_seconds.observe(stage, flight.timings[stage])
            self.m_occupancy.set(float(occupancy))
            parts = getattr(ticket, "parts", None)
            if parts:
                self.m_device_calls.observe(len(parts))
            if padded_rows:
                waste = int(round(padded_rows * (1.0 - float(occupancy))))
                if waste > 0:
                    self.m_padding_waste.inc(self._shard_label, waste)
            inflight.append(flight)
            depth = len(inflight)
            self.m_inflight.set(depth)
            if depth > self.stats["inflight_peak"]:
                self.stats["inflight_peak"] = depth

    def _submit_plan(self, group: list[_Pending], inflight: deque, now: float) -> None:
        """One coalesced plan flight: run the batched planner synchronously
        (plan_batch is host-driven — there is no streaming ticket to overlap)
        and park the ready outputs in the inflight window for settle."""
        all_inputs: list[Any] = []
        for p in group:
            all_inputs.extend(p.inputs)
            self.m_queue_wait.observe(now - p.enqueued_at)
            if p.wf is not None:
                p.wf.mark(STAGE_QUEUE_WAIT)
        batch_id = flight_recorder().next_batch_id()
        links = [p.ctx for p in group if p.ctx is not None]
        parent = links[0] if links else None
        try:
            with start_span(
                "plan_batch.submit",
                parent=parent,
                links=links,
                batch_id=batch_id,
                requests=len(group),
                inputs=len(all_inputs),
            ) as span:
                batch_ctx = span.context
                ticket = _ReadyTicket(self.plan_planner.plan_batch(all_inputs, group[0].params))
        except Exception as e:  # noqa: BLE001
            self._batch_failed(group, all_inputs, e, batch_id=batch_id)
            return
        self.stats["plan_batches"] += 1
        self.stats["plan_requests"] += len(group)
        flight = _Inflight(
            ticket,
            group,
            batch_id=batch_id,
            n_inputs=len(all_inputs),
            batch_ctx=batch_ctx,
            submitted_at=time.perf_counter(),
            submitted_wall_ns=time.time_ns(),
            kind="plan",
        )
        inflight.append(flight)
        self.m_inflight.set(len(inflight))

    def _collect_plan(self, flight: _Inflight) -> None:
        group = flight.group
        outputs = flight.ticket.outputs
        settle_start = time.perf_counter()
        with start_span(
            "plan_batch.settle", parent=flight.batch_ctx, batch_id=flight.batch_id
        ):
            offset = 0
            for p in group:
                _settle(p.future, result=outputs[offset : offset + len(p.inputs)])
                offset += len(p.inputs)
                if p.wf is not None:
                    p.wf.mark(STAGE_SETTLE)
        flight.timings["settle"] = time.perf_counter() - settle_start
        self._record_flight(flight, outcome="ok")
        sentinel = self.sentinel
        if sentinel is not None:
            all_inputs: list[Any] = []
            for p in group:
                all_inputs.extend(p.inputs)
            # after settle so plan parity replays never add request latency
            sentinel.observe_plan_batch(self, all_inputs, group[0].params, outputs)

    def _collect(self, flight: _Inflight) -> None:
        if flight.kind == "plan":
            self._collect_plan(flight)
            return
        group = flight.group
        clock = self._clock
        collect_start = time.perf_counter()
        # the GAP on the host's clock between submit returning and collect
        # starting. It is not device time: in three flights of five it is
        # under 0.1 ms, in the others the drain thread submits the next
        # flight first (milliseconds), and the device's work, some 0.07 ms on
        # a v5e (PERF.md), lies somewhere inside. The synthetic span is kept so
        # a request's trace shows no hole between batch.submit and batch.collect
        if flight.submitted_at:
            device_s = max(0.0, collect_start - flight.submitted_at)
            flight.timings["device"] = device_s
            self.m_stage_seconds.observe("device", device_s)
            export_span(
                "batch.device",
                flight.batch_ctx,
                flight.submitted_wall_ns,
                device_s,
                batch_id=flight.batch_id,
            )
        try:
            with start_span(
                "batch.collect", parent=flight.batch_ctx, batch_id=flight.batch_id
            ):
                if isinstance(flight.ticket, _ReadyTicket):
                    outputs = flight.ticket.outputs
                else:
                    outputs = self.evaluator.collect(flight.ticket)
            clock.to(drainclock.SETTLE)
        except Exception as e:  # noqa: BLE001
            clock.to(drainclock.OTHER)
            clock.take_lap()
            flight.timings["collect"] = time.perf_counter() - collect_start
            all_inputs: list[T.CheckInput] = []
            for p in group:
                all_inputs.extend(p.inputs)
            self._batch_failed(group, all_inputs, e, flight=flight)
            return
        collect_s = time.perf_counter() - collect_start
        flight.timings["collect"] = collect_s
        self.m_stage_seconds.observe("collect", collect_s)
        # parts of collect, from the thread's clock: the wait for the device
        # and the one fetch, then slicing and assembly
        lap = clock.take_lap()
        for stage in (drainclock.FETCH, drainclock.ASSEMBLE, drainclock.ASSEMBLE_OUTPUTS):
            flight.timings[stage] = lap.get(stage, 0.0)
            self.m_stage_seconds.observe(stage, flight.timings[stage])
        # assemble's other part, for the flights that entered it: with schema
        # enforcement none there is no such flight, and assemble_outputs is assemble
        schema_s = lap.get(drainclock.ASSEMBLE_SCHEMA)
        if schema_s is not None:
            flight.timings[drainclock.ASSEMBLE_SCHEMA] = schema_s
            self.m_stage_seconds.observe(drainclock.ASSEMBLE_SCHEMA, schema_s)
        if self.health is not None:
            self.health.record_success()
        settle_start = time.perf_counter()
        with start_span(
            "request.settle", parent=flight.batch_ctx, batch_id=flight.batch_id
        ):
            offset = 0
            for p in group:
                if p.wf is not None:
                    # batch-level stage durations attributed to every rider;
                    # the residual (inflight-slot waits, scheduling) folds
                    # into the settle mark so the stage sum still tiles the
                    # request's wall clock
                    p.wf.add(
                        STAGE_PACK,
                        flight.timings.get("pack", 0.0) + flight.timings.get("submit", 0.0),
                    )
                    p.wf.add(STAGE_DEVICE, flight.timings.get("device", 0.0))
                    p.wf.add(STAGE_COLLECT, collect_s)
                _settle(p.future, result=outputs[offset : offset + len(p.inputs)])
                offset += len(p.inputs)
                if p.wf is not None:
                    p.wf.mark(STAGE_SETTLE)
        settle_s = time.perf_counter() - settle_start
        flight.timings["settle"] = settle_s
        self.m_stage_seconds.observe("settle", settle_s)
        # after settle, so none of this adds to THIS flight's latency; it is
        # the drain thread's time all the same, and the next flight's queue
        # wait: stage "post" says how much
        clock.to(drainclock.POST)
        self._record_flight(flight, outcome="ok")
        # hot-rule heatmap (ISSUE 20)
        hotrules.recorder().observe(outputs)
        sentinel = self.sentinel
        if sentinel is not None:
            # observe_batch is guaranteed non-raising and non-blocking
            sentinel.observe_batch(self, flight, outputs)
        self.m_stage_seconds.observe("post", clock.to(drainclock.OTHER))

    def _record_flight(self, flight: _Inflight, outcome: str) -> None:
        health = self.health
        flight_recorder().record_batch(
            flight.batch_id,
            trace_ids=sorted({p.ctx.trace_id for p in flight.group if p.ctx is not None}),
            requests=len(flight.group),
            inputs=flight.n_inputs,
            timings=flight.timings,
            outcome=outcome,
            occupancy=flight.occupancy,
            layout_key=flight.layout_key,
            breaker_state=health.state if health is not None else None,
            shard=self.shard_id,
            submitted_monotonic_ns=flight.submitted_monotonic_ns or None,
        )

    def _batch_failed(
        self,
        group: list[_Pending],
        all_inputs: list[T.CheckInput],
        e: Exception,
        batch_id: int = 0,
        flight: Optional[_Inflight] = None,
    ) -> None:
        """A device batch raised: settle each co-batched waiter with
        _BatchFailed so they each re-serve from the oracle (never a 5xx),
        feed the breaker, and bisect the batch off-path for poison. Plan
        flights settle the same way (waiters re-plan sequentially) but
        never feed the breaker or bisect — a planner bug is not a device
        health signal, and PlanInputs have no check fingerprint."""
        is_plan = bool(group) and group[0].kind == "plan"
        self.stats["batch_errors"] += 1
        if self.health is not None and not is_plan:
            self.health.record_failure()
        _log.warning(
            "device batch failed; co-batched requests fall back to the CPU oracle",
            extra={"fields": {"inputs": len(all_inputs), "error": repr(e)}},
        )
        if flight is None:
            flight = _Inflight(None, group, batch_id=batch_id, n_inputs=len(all_inputs))
        self._record_flight(flight, outcome=f"error:{type(e).__name__}")
        flight_recorder().record_event(
            "batch_failed",
            batch_id=flight.batch_id,
            inputs=len(all_inputs),
            error=repr(e),
            shard=self.shard_id,
        )
        for p in group:
            _settle(p.future, error=_BatchFailed(e))
        if not is_plan:
            self._schedule_bisect(all_inputs, group[0].params)

    # -- poison bisection + quarantine --------------------------------------

    def _schedule_bisect(self, inputs: list[T.CheckInput], params) -> None:
        # a lone failing input has no sibling to prove the device itself is
        # healthy, so it can't be told apart from a device-wide failure
        if len(inputs) < 2 or self._bisect_busy:
            return
        with self._qlock:
            if self._bisect_busy:
                return
            self._bisect_busy = True
        threading.Thread(
            target=self._bisect,
            args=(list(inputs), params),
            daemon=True,
            name="check-batcher-bisect",
        ).start()

    def _bisect(self, inputs: list[T.CheckInput], params) -> None:
        """Off-path halving search over a failed batch. Quarantine single
        inputs that still fail ONLY when some sibling sub-batch succeeded —
        otherwise the device is simply down and nothing is poisoned."""
        try:
            stack: list[list[T.CheckInput]] = [inputs]
            budget = self.bisect_budget
            ok_any = False
            poisons: list[T.CheckInput] = []
            while stack and budget > 0:
                part = stack.pop()
                budget -= 1
                try:
                    self.evaluator.check(part, params)
                    ok_any = True
                    continue
                except Exception:  # noqa: BLE001
                    pass
                if len(part) == 1:
                    poisons.append(part[0])
                else:
                    mid = len(part) // 2
                    stack.append(part[:mid])
                    stack.append(part[mid:])
            if ok_any:
                for inp in poisons:
                    self._quarantine_add(inp)
            flight_recorder().record_event(
                "bisect_done",
                inputs=len(inputs),
                sibling_ok=ok_any,
                poisons=len(poisons) if ok_any else 0,
            )
        except Exception:  # noqa: BLE001  (bisect is best-effort, off-path)
            pass
        finally:
            self._bisect_busy = False

    def _quarantine_add(self, inp: T.CheckInput) -> None:
        fp = _fingerprint(inp)
        with self._qlock:
            if fp in self._quarantine:
                return
            self._quarantine[fp] = True
            while len(self._quarantine) > self.quarantine_max:
                self._quarantine.pop(next(iter(self._quarantine)))
        self.stats["quarantined"] += 1
        self.m_quarantined.inc()
        flight_recorder().record_event(
            "quarantine_add",
            principal=inp.principal.id,
            resource_kind=inp.resource.kind,
            resource_id=inp.resource.id,
            shard=self.shard_id,
        )
        _log.error(
            "quarantined poison input: it crashes device batches and will be "
            "served by the CPU oracle",
            extra={
                "fields": {
                    "principal": inp.principal.id,
                    "resourceKind": inp.resource.kind,
                    "resourceId": inp.resource.id,
                    "actions": list(inp.actions or ()),
                }
            },
        )

    def _has_quarantined(self, inputs: Sequence[T.CheckInput]) -> bool:
        with self._qlock:
            return any(_fingerprint(i) in self._quarantine for i in inputs)

    # -- breaker probes -----------------------------------------------------

    def _spawn_probe(self, token: int, inputs: list[T.CheckInput], params) -> None:
        threading.Thread(
            target=self._probe,
            args=(token, inputs, params),
            daemon=True,
            name="check-batcher-probe",
        ).start()

    def _probe(self, token: int, inputs: list[T.CheckInput], params) -> None:
        health = self.health
        if health is None:
            return
        try:
            submit = getattr(self.evaluator, "submit", None)
            if submit is not None:
                self.evaluator.collect(submit(inputs, params))
            else:
                self.evaluator.check(inputs, params)
        except Exception:  # noqa: BLE001
            health.probe_failed(token)
        else:
            health.probe_succeeded(token)

    # -- shutdown -----------------------------------------------------------

    def _settle_residual_queue(self) -> None:
        with self._wakeup:
            residual = list(self._queue)
            self._queue.clear()
        for p in residual:
            _settle(p.future, error=_BatchFailed(None, "shutdown"))

    def close(self) -> None:
        with self._wakeup:
            self._stop = True
            self._wakeup.notify_all()
        self._thread.join(timeout=5)
        if self._thread.is_alive():
            # drain loop is wedged in a device call: settle queued waiters
            # from here so shutdown doesn't strand them for request_timeout
            self._settle_residual_queue()


class _ReadyTicket:
    __slots__ = ("outputs",)

    def __init__(self, outputs):
        self.outputs = outputs
