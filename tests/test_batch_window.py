"""When the batcher's drain loop waits (engine/batcher.py:_plans_alone).

A queue that holds a check is drained at once, whatever ``batchWindowMs``
says: requests coalesce by queueing behind the flight in progress, and no
traffic measured on the chip could fill a window (PERF.md section 6, PR 25).
The coalescing window is left for plan queries alone. Fake evaluators and
windows of half a minute, so that a test which waits where it should not
fails by its timeout and not by a few milliseconds.
"""

import itertools
import threading
import time

import pytest
from flightgate import EchoPlanner, FlightGate

from cerbos_tpu import observability as obs
from cerbos_tpu.engine import drainclock as dc
from cerbos_tpu.engine import flight
from cerbos_tpu.engine import types as T
from cerbos_tpu.engine.batcher import BatchingEvaluator, _Pending

LONG_MS = 30_000.0  # a window nobody waits out: a test that enters it unasked times out
SOON = 5.0  # "at once", in seconds, on a loaded test machine
UNDER_WINDOW = 0.8 * LONG_MS / 1000.0  # a wait the window alone can outlast: a stalled test machine does not


class DeviceEvaluator:
    """Streams, and would serve flights under ``min_device_batch`` from its oracle."""

    rule_table = None
    schema_mgr = None
    min_device_batch = 16

    def __init__(self):
        self.flights: list[int] = []

    def submit(self, inputs, params=None):
        self.flights.append(len(inputs))
        return inputs

    def collect(self, ticket):
        return [T.CheckOutput(request_id="", resource_id=str(k)) for k in range(len(ticket))]


class PlainEvaluator:
    """No streaming API and no ``min_device_batch``."""

    rule_table = None
    schema_mgr = None

    def check(self, inputs, params=None):
        return [T.CheckOutput(request_id="", resource_id=str(k)) for k in range(len(inputs))]


_shards = itertools.count(20_000)


@pytest.fixture()
def shard():
    """A shard label of the test's own: its series start at zero. Counted, not
    hashed from the test's name: among this file's 23 names a hash into 9,000
    labels met another test's in one process of thirty (``PYTHONHASHSEED=110``:
    one case of eight of the first test read ``(2, 0.0)``)."""
    return next(_shards)


def window_waits(shard: int) -> tuple[int, float]:
    """(count, sum) of ``batcher_window_wait_seconds`` for the shard."""
    h = obs.metrics().histogram_vec("cerbos_tpu_batcher_window_wait_seconds", label="shard").labels(str(shard))
    return h.count, h.sum


def window_seconds(b: BatchingEvaluator) -> float:
    """Wall seconds the batcher's drain thread has spent in its ``window`` state."""
    vec = obs.metrics().counter_vec("cerbos_tpu_batcher_thread_seconds_total", label=("state", "kind", "clock", "shard"))
    return vec.get((dc.WINDOW, "wait", "wall", b._shard_label))


def check_flights_of(shard: int) -> list[dict]:
    return [r for r in flight.recorder().dump()["batches"] if r["shard"] == shard and "window" in r["timings"]]


def in_window(b: BatchingEvaluator) -> None:
    end = time.monotonic() + SOON
    while b._clock.state != dc.WINDOW:
        assert time.monotonic() < end, f"the drain loop is in {b._clock.state!r}, not in its window"
        time.sleep(0.001)


def inputs(n: int) -> list:
    return [object()] * n


@pytest.mark.parametrize("n", [1, 5, 16, 50], ids=["single", "small", "device-batch", "page"])
@pytest.mark.parametrize("evaluator", [DeviceEvaluator, PlainEvaluator])
def test_a_lone_check_request_flies_at_once(shard, evaluator, n):
    b = BatchingEvaluator(evaluator(), max_wait_ms=LONG_MS, shard_id=shard)
    try:
        assert len(b.check_async(inputs(n)).result(timeout=UNDER_WINDOW)) == n
        # the flight's record is written after its futures settle (stage "post"), and close() gives
        # the drain thread's join 5 s: wait for the record itself
        end = time.monotonic() + UNDER_WINDOW
        while not check_flights_of(shard) and time.monotonic() < end:
            time.sleep(0.001)
    finally:
        b.close()
    assert window_waits(shard) == (1, 0.0)  # observed once per flight, also where nothing waited
    (rec,) = check_flights_of(shard)
    assert rec["inputs"] == n and rec["timings"]["window"] == 0.0


def test_checks_one_after_another_never_enter_the_window(shard):
    ev = DeviceEvaluator()
    b = BatchingEvaluator(ev, max_wait_ms=LONG_MS, shard_id=shard)
    try:
        for _ in range(20):
            assert len(b.check_async(inputs(2)).result(timeout=SOON)) == 2
        assert window_seconds(b) == 0.0
    finally:
        b.close()
    assert ev.flights == [2] * 20
    assert window_waits(shard) == (20, 0.0)


def test_requests_coalesce_behind_the_flight_in_progress(shard):
    ev = DeviceEvaluator()
    gate = FlightGate(ev)
    b = BatchingEvaluator(gate, max_wait_ms=LONG_MS, shard_id=shard)
    try:
        plug = gate.hold(b, inputs(16))
        futs = [b.check_async(inputs(1)) for _ in range(50)]
        gate.release(b, queued=50)
        for fut in [plug] + futs:
            fut.result(timeout=SOON)
    finally:
        b.close()
    assert ev.flights == [16, 50]
    assert window_waits(shard) == (2, 0.0)


def test_a_lone_plan_query_waits_for_a_second_one_as_before(shard):
    planner = EchoPlanner()
    b = BatchingEvaluator(DeviceEvaluator(), max_wait_ms=LONG_MS, min_batch_to_wait=2, shard_id=shard)
    b.plan_planner = planner
    out: dict[str, list] = {}
    try:
        threads = [threading.Thread(target=lambda k=k: out.update({k: b.plan([k])})) for k in ("a", "b")]
        threads[0].start()
        in_window(b)
        time.sleep(0.05)
        assert b._clock.state == dc.WINDOW and not out
        threads[1].start()
        for t in threads:
            t.join(timeout=SOON)
        assert 0.05 <= window_seconds(b) < SOON
    finally:
        b.close()
    assert out == {"a": ["plan:a"], "b": ["plan:b"]}
    assert planner.flights == [2] and b.stats["plan_batches"] == 1


def test_a_plan_querys_window_runs_out_at_max_wait(shard):
    planner = EchoPlanner()
    b = BatchingEvaluator(DeviceEvaluator(), max_wait_ms=50.0, shard_id=shard)
    b.plan_planner = planner
    try:
        t0 = time.monotonic()
        assert b.plan(["a"]) == ["plan:a"]
        assert 0.049 <= time.monotonic() - t0 < SOON
        assert 0.049 <= window_seconds(b) < SOON
    finally:
        b.close()
    assert planner.flights == [1]


def test_no_window_where_max_wait_is_zero(shard):
    b = BatchingEvaluator(DeviceEvaluator(), max_wait_ms=0.0, shard_id=shard)
    b.plan_planner = EchoPlanner()
    try:
        assert b.plan(["a"]) == ["plan:a"]
        assert window_seconds(b) == 0.0
    finally:
        b.close()


def test_a_check_ends_the_window_a_plan_query_waits_in(shard):
    ev, planner = DeviceEvaluator(), EchoPlanner()
    b = BatchingEvaluator(ev, max_wait_ms=LONG_MS, min_batch_to_wait=100, shard_id=shard)
    b.plan_planner = planner
    out: dict[str, list] = {}
    try:
        planning = threading.Thread(target=lambda: out.update(a=b.plan(["a"])))
        planning.start()
        in_window(b)
        assert len(b.check_async(inputs(1)).result(timeout=SOON)) == 1  # 98 requests were still missing
        planning.join(timeout=SOON)
    finally:
        b.close()
    assert out == {"a": ["plan:a"]} and planner.flights == [1] and ev.flights == [1]
    count, waited = window_waits(shard)
    assert count == 1 and 0.0 < waited < SOON  # the check's flight carries what the drain waited


class Barrier:
    """``rollout.SwapBarrier`` as far as the drain loop knows it."""

    def __init__(self):
        self.parked = threading.Event()
        self.go = threading.Event()

    def park(self, batcher):
        self.parked.set()
        assert self.go.wait(timeout=30)


def test_a_check_queued_beside_a_plan_query_is_not_held_back(shard):
    ev, planner = DeviceEvaluator(), EchoPlanner()
    b = BatchingEvaluator(ev, max_wait_ms=LONG_MS, min_batch_to_wait=100, shard_id=shard)
    b.plan_planner = planner
    barrier = Barrier()
    out: dict[str, list] = {}
    try:
        assert b.request_swap(barrier) and barrier.parked.wait(timeout=SOON)
        planning = threading.Thread(target=lambda: out.update(a=b.plan(["a"])))
        planning.start()
        check = b.check_async(inputs(1))
        while len(b._queue) < 2:
            time.sleep(0.001)
        barrier.go.set()
        # nothing in flight, a plan query and a check queued: both fly at once
        assert len(check.result(timeout=SOON)) == 1
        planning.join(timeout=SOON)
        assert window_seconds(b) == 0.0
    finally:
        b.close()
    assert out == {"a": ["plan:a"]} and planner.flights == [1] and ev.flights == [1]
    assert window_waits(shard) == (1, 0.0)


def test_a_pending_barrier_pre_empts_an_open_window(shard):
    planner = EchoPlanner()
    b = BatchingEvaluator(DeviceEvaluator(), max_wait_ms=LONG_MS, shard_id=shard)
    b.plan_planner = planner
    barrier = Barrier()
    out: dict[str, list] = {}
    try:
        planning = threading.Thread(target=lambda: out.update(a=b.plan(["a"])))
        planning.start()
        in_window(b)
        assert b.request_swap(barrier)
        assert barrier.parked.wait(timeout=SOON)  # half a minute of window was left
        assert not out and planner.flights == []  # nothing is submitted across the cutover
        barrier.go.set()
        assert b.plan(["b"]) == ["plan:b"]  # the second query the first one waits for
        planning.join(timeout=SOON)
    finally:
        b.close()
    assert out == {"a": ["plan:a"]} and sum(planner.flights) == 2


def pending(kind: str) -> _Pending:
    return _Pending(inputs(1), None, None, pclass="plan" if kind == "plan" else "", kind=kind)


@pytest.mark.parametrize(
    "queued, min_requests, waits",
    [
        (["plan"], 2, True),
        (["plan", "plan"], 2, False),  # the second request the window waits for
        (["plan", "plan"], 100, True),
        (["check"], 2, False),
        (["check"], 100, False),
        (["plan", "check"], 100, False),  # a check never waits, whoever queues beside it
        (["check", "plan", "plan"], 100, False),
    ],
)
def test_only_a_short_queue_of_plan_queries_alone_may_be_held_back(shard, queued, min_requests, waits):
    b = BatchingEvaluator(PlainEvaluator(), max_wait_ms=LONG_MS, min_batch_to_wait=min_requests, shard_id=shard)
    b.close()  # the drain thread is gone: the queue is this test's alone
    for kind in queued:
        b._queue.append(pending(kind))
    assert b._plans_alone() is waits
