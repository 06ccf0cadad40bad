"""Per-request latency-budget waterfall and goodput accounting.

PR 4 instrumented *batches* (``cerbos_tpu_batch_stage_seconds``); this
module instruments *requests*: a compact stage-timestamp record created at
ingress (before the request body is even decoded, so parse cost is visible)
and carried with the request through admission, the IPC hop, the batcher
queue, the device window, settlement, and reply encoding. Each stage is the
delta between consecutive marks, so the stage durations tile the request's
wall clock by construction — the reconciliation property
``tests/test_latency_budget.py`` asserts (≥95% of a request's wall clock
attributed to named stages).

Cross-process carriage reuses ``engine/ipc.py``'s deadline idiom: monotonic
clocks are process-local, so only RELATIVE values cross the socket. The
front end ships ``(age, attributed)`` — how old the request is and how much
of that age its stages already explain — and the batcher re-anchors
``t0 = now - age`` on its own clock, booking the unexplained remainder as
the ``transit`` stage. The reply carries the batcher-side stages plus its
final age back, and the front end books the residual as ``ipc_return``.
Clock skew between the processes cancels exactly the way it does for
deadlines.

On top of the waterfall:

- **goodput accounting** — ``cerbos_tpu_decisions_total{outcome=...}``
  splits throughput from goodput: ``deadline_met`` (served by the device
  path inside its budget), ``oracle_fallback`` (served correctly, but by
  the CPU oracle after a device-path degradation), ``expired`` (deadline
  blown — a 504 the caller already gave up on), ``refused`` (rejected at
  admission, e.g. request limits).
- **slow-request ring** — a bounded ring (the ``engine/flight.py``
  pattern) of the waterfalls of requests slower than a threshold, served
  at ``/_cerbos/debug/slow`` with the flight recorder's ``?shard=``
  filter; each entry carries the trace id so an operator can pivot to the
  trace and the flight-recorder batch.
- ``cerbos_tpu_deadline_budget_remaining_seconds{point,shard}`` — the
  remaining deadline budget sampled at enqueue and at device-submit, so
  requests that reach the device already near-expired are visible before
  ROADMAP item 5 adds early refusal.

**The front and the back of a request, in parts** (PR 27). ``admission`` is
everything between "decoded" and "enqueued" and ``reply_encode`` everything
between the drain thread's ``settle`` and the response message, each one
number. The handlers stamp the same record at the seams inside them
(``Waterfall.part``: one ``time.monotonic()`` and one tuple a part), and
``finish`` flushes them beside the stages into
``cerbos_tpu_request_front_seconds{part}`` (``validate``, ``auxdata``,
``convert``, ``admit``, ``span``, ``enqueue``: contiguous from the end of the
decode to the mark that ends ``admission``, or ``ipc_encode`` in a front end)
and ``cerbos_tpu_request_back_seconds{part}`` (``wake``, ``audit``: the
decision entry built and queued on the request's thread, 0 with audit off;
``encode``, and ``serialize``, which for gRPC lies after ``reply_encode`` and
is observed by the response serializer). ``cerbos_tpu_request_handler_seconds`` is the
handler's whole extent, raw bytes in to bytes out. On with the waterfall,
off with it; no option of their own.

One process-global tracker (the flight-recorder pattern): bootstrap
configures it from ``engine.tpu.latencyBudget.*``, every layer marks
through it.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Optional

from ..observability import metrics

# stage glossary, in waterfall order (docs/OBSERVABILITY.md "Latency
# budget & pressure" documents the boundaries)
STAGE_INGRESS_PARSE = "ingress_parse"    # raw bytes on the wire → request decoded
STAGE_ADMISSION = "admission"            # decoded → accepted into the engine (validate, convert, span setup)
STAGE_IPC_ENCODE = "ipc_encode"          # ticket encoded for the shared batcher (front-end topology)
STAGE_TRANSIT = "transit"                # front-end send → batcher receipt (cross-process)
STAGE_QUEUE_WAIT = "queue_wait"          # batcher enqueue → drain-loop pickup
STAGE_PACK = "pack"                      # host staging + device dispatch of the batch
STAGE_DEVICE = "device"                  # host-clock gap, submit return → collect start (the device's work lies inside it)
STAGE_COLLECT = "collect"                # device readback + row decode
STAGE_SETTLE = "settle"                  # result slicing + future settlement (includes in-flight slot waits)
STAGE_IPC_RETURN = "ipc_return"          # batcher settle → response frame on the front end
STAGE_REPLY_ENCODE = "reply_encode"      # engine result → response bytes
STAGE_EVALUATE = "evaluate"              # non-batched evaluation (serial path / direct device call)
STAGE_ORACLE = "oracle"                  # CPU-oracle evaluation after a device-path degradation

STAGES = (
    STAGE_INGRESS_PARSE,
    STAGE_ADMISSION,
    STAGE_IPC_ENCODE,
    STAGE_TRANSIT,
    STAGE_QUEUE_WAIT,
    STAGE_PACK,
    STAGE_DEVICE,
    STAGE_COLLECT,
    STAGE_SETTLE,
    STAGE_IPC_RETURN,
    STAGE_REPLY_ENCODE,
    STAGE_EVALUATE,
    STAGE_ORACLE,
)

# parts of the front (tile ``admission``) and of the back (``wake`` + ``audit``
# + ``encode`` tile ``reply_encode`` for gRPC; for HTTP ``serialize`` lies inside it too)
FRONT_VALIDATE = "validate"  # wire validation of the decoded request (gRPC: the worker's wake-up; the native reader validated)
FRONT_AUXDATA = "auxdata"    # the token's extraction and verification (a field test where there is none)
FRONT_CONVERT = "convert"    # message -> CheckInputs (gRPC: the token's AuxData attached to what the native reader built)
FRONT_ADMIT = "admit"        # admission class and try_admit
FRONT_SPAN = "span"          # deadline, traceparent, request limits, call id, the request span's set-up
FRONT_ENQUEUE = "enqueue"    # engine.check entry -> the mark that ends admission (lane choice, the queue's lock)
FRONT_PARTS = (
    FRONT_VALIDATE, FRONT_AUXDATA, FRONT_CONVERT, FRONT_ADMIT, FRONT_SPAN, FRONT_ENQUEUE,
)
BACK_WAKE = "wake"            # the last mark of another thread (settle) -> the handler's thread running again
BACK_AUDIT = "audit"          # the decision entry built and queued for the audit writer (0 with audit off)
BACK_ENCODE = "encode"        # span end, the access entry queued, outputs -> response message (gRPC: -> its bytes, native)
BACK_SERIALIZE = "serialize"  # response message -> bytes (gRPC: a pass-through where the native writer made them)
BACK_PARTS = (BACK_WAKE, BACK_AUDIT, BACK_ENCODE, BACK_SERIALIZE)

OUTCOME_MET = "deadline_met"
OUTCOME_EXPIRED = "expired"
OUTCOME_ORACLE = "oracle_fallback"
OUTCOME_REFUSED = "refused"
OUTCOMES = (OUTCOME_MET, OUTCOME_EXPIRED, OUTCOME_ORACLE, OUTCOME_REFUSED)

POINT_ENQUEUE = "enqueue"
POINT_DEVICE_SUBMIT = "device_submit"

# request stages span ~100µs (reply encode) to seconds (queue under
# overload); the default registry buckets bottom out at 1ms and would
# blur every fast stage into one bucket
_STAGE_BUCKETS = [
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
]
# a part is 1 us (a field test) to a millisecond (50 resources converted)
_PART_BUCKETS = [
    0.000005, 0.00001, 0.000025, 0.00005, 0.0001, 0.00025, 0.0005, 0.001,
    0.0025, 0.005, 0.01, 0.05, 0.25, 1.0,
]
# budget remaining is read against deadlines of ~10ms..30s
_BUDGET_BUCKETS = [0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 30.0]


class Waterfall:
    """One request's stage-timestamp record.

    Owned by exactly one thread at a time (it migrates with the request:
    request thread → drain thread → writer thread), so marks are
    lock-free. ``mark`` books the delta since the previous mark; ``add``
    books an externally measured duration and advances the cursor by it,
    so a later ``mark`` only books the residual — the invariant throughout
    is that the recorded stages tile ``[t0, _last]`` exactly.
    """

    __slots__ = (
        "t0", "wall_ns", "stages", "_last", "trace_id", "deadline",
        "shard", "served_by", "fallback_reason", "parts", "_part_from",
    )

    def __init__(
        self,
        t0: Optional[float] = None,
        wall_ns: Optional[int] = None,
        trace_id: str = "",
        deadline: Optional[float] = None,
    ):
        now = time.monotonic() if t0 is None else t0
        self.t0 = now
        self._last = now
        self.wall_ns = time.time_ns() if wall_ns is None else wall_ns
        self.stages: list[tuple[str, float]] = []
        # the seams inside admission and reply_encode: every stage mark moves
        # the parts' cursor with it, so the parts stamped after a mark tile
        # the time since that mark
        self.parts: list[tuple[str, float]] = []
        self._part_from = now
        self.trace_id = trace_id
        self.deadline = deadline
        self.shard: Optional[int] = None
        self.served_by = "device"
        self.fallback_reason = ""

    def mark(self, stage: str, now: Optional[float] = None, part: Optional[str] = None) -> float:
        """``part``: the part that ends at this very instant (``enqueue`` at
        the admission mark), so the parts add up to the stage exactly."""
        now = time.monotonic() if now is None else now
        dur = max(0.0, now - self._last)
        self.stages.append((stage, dur))
        if part is not None:
            self.parts.append((part, max(0.0, now - self._part_from)))
        self._last = self._part_from = now
        return dur

    def part(self, name: str) -> None:
        """Stamp a seam inside the stage in progress: books the time since
        the previous seam (or stage mark) to ``name``. One clock read."""
        now = time.monotonic()
        self.parts.append((name, max(0.0, now - self._part_from)))
        self._part_from = now

    def add(self, stage: str, dur: float) -> None:
        dur = max(0.0, float(dur))
        self.stages.append((stage, dur))
        self._last += dur

    def age(self, now: Optional[float] = None) -> float:
        return max(0.0, (time.monotonic() if now is None else now) - self.t0)

    def attributed(self) -> float:
        return sum(d for _, d in self.stages)

    def note_fallback(self, reason: str) -> None:
        self.served_by = "oracle"
        self.fallback_reason = reason or ""

    # -- cross-process carriage (relative values only; see module doc) ------

    def carry(self, now: Optional[float] = None) -> tuple[float, float]:
        """Ship over IPC: (age of the request, seconds already attributed)."""
        return (self.age(now), self.attributed())

    @classmethod
    def from_carry(
        cls,
        spec,
        trace_id: str = "",
        deadline: Optional[float] = None,
    ) -> "Waterfall":
        """Batcher side: re-anchor ``t0`` on the local monotonic clock from
        the carried age (the deadline re-anchoring idiom) and book the
        unattributed remainder — encode, socket, frame decode — as
        ``transit``."""
        # index reads, not unpacking: a newer front end may append carry
        # elements (the admission class rides as spec[2]) that this record
        # does not consume
        age, attributed = spec[0], spec[1]
        now = time.monotonic()
        wf = cls(t0=now - max(0.0, float(age)), trace_id=trace_id, deadline=deadline)
        wf._last = wf.t0 + min(max(0.0, float(attributed)), wf.age(now))
        wf.mark(STAGE_TRANSIT, now=now)
        return wf

    def reply_spec(self, now: Optional[float] = None):
        """Batcher side: everything the front end needs to splice the
        batcher-visible stages back into its own record."""
        return (
            self.age(now),
            list(self.stages),
            self.served_by,
            self.fallback_reason,
            self.shard,
        )

    def splice_reply(self, spec, now: Optional[float] = None) -> None:
        """Front-end side: append the batcher's stages and book the
        residual — writer-thread encode, socket, response decode — as
        ``ipc_return``."""
        now = time.monotonic() if now is None else now
        _age_b, stages_b, served_by, reason, shard = spec
        self.stages.extend((str(s), max(0.0, float(d))) for s, d in stages_b)
        if served_by == "oracle":
            self.note_fallback(str(reason))
        if shard is not None:
            self.shard = int(shard)
        ret = (now - self.t0) - self.attributed()
        self.stages.append((STAGE_IPC_RETURN, max(0.0, ret)))
        self._last = self._part_from = now

    def snapshot(self) -> dict:
        """Slow-ring / debug-endpoint entry (milliseconds for humans)."""
        total = self.attributed()
        out = {
            "trace_id": self.trace_id,
            "total_ms": round(total * 1000, 3),
            "stages": [(s, round(d * 1000, 3)) for s, d in self.stages],
            "served_by": self.served_by,
            "wall_time_ns": self.wall_ns,
        }
        if self.parts:
            out["parts"] = [(p, round(d * 1000, 3)) for p, d in self.parts]
        if self.shard is not None:
            out["shard"] = self.shard
        if self.fallback_reason:
            out["fallback_reason"] = self.fallback_reason
        if self.deadline is not None:
            out["budget_remaining_ms"] = round((self.deadline - self._last) * 1000, 3)
        return out


class BudgetTracker:
    """Process-global waterfall config, metric families, and slow ring."""

    def __init__(self, slow_capacity: int = 64, slow_threshold_s: float = 0.25):
        reg = metrics()
        self.m_stage = reg.histogram_vec(
            "cerbos_tpu_request_stage_seconds",
            "Per-request latency-budget waterfall: seconds spent in each named stage",
            label=("stage", "shard"),
            buckets=_STAGE_BUCKETS,
        )
        self.m_total = reg.histogram(
            "cerbos_tpu_request_total_seconds",
            "Per-request wall clock from ingress to reply encode (the waterfall total)",
            buckets=_STAGE_BUCKETS,
        )
        m_front = reg.histogram_vec(
            "cerbos_tpu_request_front_seconds",
            "Per-request parts of the front half (decode end to the admission mark): "
            "validate, auxdata, convert, admit, span, enqueue; they add up to the admission stage",
            label="part",
            buckets=_PART_BUCKETS,
        )
        m_back = reg.histogram_vec(
            "cerbos_tpu_request_back_seconds",
            "Per-request parts of the back half: wake (settle to the handler's thread running), "
            "audit (the decision entry built and queued; 0 with audit off), "
            "encode (outputs to response message), serialize (message to bytes)",
            label="part",
            buckets=_PART_BUCKETS,
        )
        # part -> child histogram, resolved once: the flush and the gRPC
        # serializer observe without a vec-level lock or a label lookup
        self._part_children = {p: m_front.labels(p) for p in FRONT_PARTS}
        self._part_children.update((p, m_back.labels(p)) for p in BACK_PARTS)
        self.m_handler = reg.histogram(
            "cerbos_tpu_request_handler_seconds",
            "The CheckResources handler's whole extent: raw request bytes in to response bytes out "
            "(client latency less this is outside the program: transport, thread dispatch, the caller)",
            buckets=_STAGE_BUCKETS,
        )
        self.m_budget = reg.histogram_vec(
            "cerbos_tpu_deadline_budget_remaining_seconds",
            "Deadline budget remaining at the sampled point (enqueue, device_submit); 0 = already expired",
            label=("point", "shard"),
            buckets=_BUDGET_BUCKETS,
        )
        self.m_decisions = reg.counter_vec(
            "cerbos_tpu_decisions_total",
            "Decisions by API and outcome: deadline_met, oracle_fallback, expired, "
            "refused (goodput = met + fallback); api=plan books PlanResources "
            "traffic so shed_plan brownouts show as refused instead of vanishing",
            label=("api", "outcome"),
        )
        self.m_slow = reg.counter(
            "cerbos_tpu_slow_requests_total",
            "Requests slower than latencyBudget.slowThresholdMs (captured in the slow ring)",
        )
        self.enabled = True
        self.slow_threshold_s = float(slow_threshold_s)
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=int(slow_capacity))
        # (stage, shard) → child Histogram, bypassing the vec-level lock on
        # the per-request flush; the key space is small (13 stages × shards)
        # and plain-dict reads are GIL-atomic, so misses just fall through
        # to the locked labels() path once
        self._stage_children: dict = {}
        self._budget_children: dict = {}

    def configure(
        self,
        enabled: Optional[bool] = None,
        slow_capacity: Optional[int] = None,
        slow_threshold_ms: Optional[float] = None,
    ) -> None:
        """Mutate in place (the flight-recorder pattern) so references held
        by already-wired layers stay valid."""
        with self._lock:
            if enabled is not None:
                self.enabled = bool(enabled)
            if slow_threshold_ms is not None:
                self.slow_threshold_s = float(slow_threshold_ms) / 1000.0
            if slow_capacity is not None and slow_capacity != self._ring.maxlen:
                self._ring = deque(self._ring, maxlen=max(1, int(slow_capacity)))

    # -- record lifecycle ---------------------------------------------------

    def start(
        self,
        trace_id: str = "",
        deadline: Optional[float] = None,
        t0: Optional[float] = None,
        wall_ns: Optional[int] = None,
    ) -> Optional[Waterfall]:
        if not self.enabled:
            return None
        return Waterfall(t0=t0, wall_ns=wall_ns, trace_id=trace_id, deadline=deadline)

    def resume(self, spec, trace_id: str = "", deadline: Optional[float] = None) -> Optional[Waterfall]:
        """Batcher side of the IPC hop: rebuild the record from the carried
        relative spec (None when the front end runs with the budget off)."""
        if not self.enabled or spec is None:
            return None
        try:
            return Waterfall.from_carry(spec, trace_id=trace_id, deadline=deadline)
        except Exception:  # noqa: BLE001 — a malformed carry must not fail the request
            return None

    def observe_budget(self, point: str, remaining: float, shard: Optional[int] = None) -> None:
        if not self.enabled:
            return
        key = (point, str(shard if shard is not None else 0))
        child = self._budget_children.get(key)
        if child is None:
            child = self.m_budget.labels(key)
            self._budget_children[key] = child
        child.observe(max(0.0, remaining))

    def finish(
        self,
        wf: Optional[Waterfall],
        outcome: str,
        final_stage: Optional[str] = None,
        api: str = "check",
        final_part: Optional[str] = None,
    ) -> Optional[float]:
        """Count the decision and flush the waterfall's stages and parts to
        the histograms; slower-than-threshold requests land in the slow ring.
        ``final_part`` ends at the same instant as ``final_stage``. Returns
        that instant (None with the waterfall off)."""
        self.m_decisions.inc((api, outcome))
        if wf is None:
            return None
        now = time.monotonic()
        if final_stage is not None:
            wf.mark(final_stage, now=now, part=final_part)
        shard = str(wf.shard if wf.shard is not None else 0)
        children = self._stage_children
        for stage, dur in wf.stages:
            child = children.get((stage, shard))
            if child is None:
                child = self.m_stage.labels((stage, shard))
                children[(stage, shard)] = child
            child.observe(dur)
        parts = self._part_children
        for name, dur in wf.parts:
            parts[name].observe(dur)
        total = wf.attributed()
        self.m_total.observe(total)
        if total >= self.slow_threshold_s:
            self.m_slow.inc()
            entry = wf.snapshot()
            entry["outcome"] = outcome
            with self._lock:
                self._ring.append(entry)
        return now

    def observe_reply(self, t_raw: float, t_serialize: float, now: float) -> None:
        """gRPC: the response serializer's two observations, after the
        waterfall is flushed: ``serialize`` and the handler's extent."""
        self._part_children[BACK_SERIALIZE].observe(max(0.0, now - t_serialize))
        self.m_handler.observe(max(0.0, now - t_raw))

    def count(self, outcome: str, api: str = "check") -> None:
        """Goodput accounting for the waterfall-disabled path."""
        self.m_decisions.inc((api, outcome))

    # -- slow ring ----------------------------------------------------------

    def slow_dump(self, shard: Optional[int] = None, top: int = 0) -> dict:
        with self._lock:
            entries = list(self._ring)
            capacity = self._ring.maxlen
        if shard is not None:
            entries = [e for e in entries if e.get("shard", 0) == shard]
        entries.sort(key=lambda e: e.get("total_ms", 0.0), reverse=True)
        if top > 0:
            entries = entries[:top]
        return {
            "capacity": capacity,
            "threshold_ms": round(self.slow_threshold_s * 1000, 3),
            "enabled": self.enabled,
            "requests": entries,
        }

    def reset(self) -> None:
        """Test hook: drop captured slow requests."""
        with self._lock:
            self._ring.clear()


_tracker = BudgetTracker()


def tracker() -> BudgetTracker:
    return _tracker
