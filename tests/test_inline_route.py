"""A request under ``min_device_batch`` that finds the queue empty is answered
on its own thread (engine/batcher.py:_serve_inline; PERF.md section 6, PR 30).

Through a real ``BatchingEvaluator`` over a real ``TpuEvaluator``, numpy and
jax: which requests take the route and which queue, that the answer is the CPU
oracle's element for element, what an inline answer books (the route counter,
``stage="oracle"``, the waterfall's ``queue_wait`` and ``evaluate``, the
sentinel's ring) and what it does not (anything of a flight), and that the
refusal ladder and the cutover barrier keep their precedence.
"""

import sys
import threading
import time

import pytest
from flightgate import FlightGate
from test_batch_window import Barrier
from test_streaming_serving import POLICY, inp, sans_source, table

from cerbos_tpu import observability as obs
from cerbos_tpu.compile import compile_policy_set
from cerbos_tpu.engine import flight, hotrules
from cerbos_tpu.engine import types as T
from cerbos_tpu.engine.batcher import BatchingEvaluator, DeadlineExceeded, _BatchFailed
from cerbos_tpu.engine.budget import (
    STAGE_ADMISSION,
    STAGE_EVALUATE,
    STAGE_QUEUE_WAIT,
    Waterfall,
)
from cerbos_tpu.engine.engine import Engine
from cerbos_tpu.engine.sentinel import ParitySentinel
from cerbos_tpu.policy.parser import parse_policies
from cerbos_tpu.ruletable import build_rule_table, check_input
from cerbos_tpu.tpu import TpuEvaluator

SOON = 30.0  # a jax flight on the CPU backend compiles its layout first, on a loaded test machine
POLICY_V2 = POLICY.replace("effect: EFFECT_ALLOW\n      roles: [user]", "effect: EFFECT_DENY\n      roles: [user]")


@pytest.fixture()
def shard(request):
    """A shard label of the test's own: its series start at zero."""
    return 40_000 + abs(hash(request.node.name)) % 9000


@pytest.fixture(params=["numpy", "jax"])
def evaluator(request):
    return TpuEvaluator(table(), use_jax=request.param == "jax")  # min_device_batch 16, the default


def routes() -> dict[str, float]:
    vec = obs.metrics().counter_vec("cerbos_tpu_batcher_checks_total", label="route")
    return {r: vec.get(r) for r in ("inline", "queued")}


def moved(before: dict[str, float]) -> dict[str, float]:
    return {r: v - before[r] for r, v in routes().items()}


def oracle_stage(shard: int):
    vec = obs.metrics().histogram_vec("cerbos_tpu_batch_stage_seconds", label=("stage", "shard"))
    return vec.labels(("oracle", str(shard)))


def batch_sizes() -> int:
    return obs.metrics().histogram("cerbos_tpu_batcher_batch_size").count


def flights_of(shard: int) -> list[dict]:
    return [r for r in flight.recorder().dump()["batches"] if r["shard"] == shard]


def oracle(ev, inputs):
    return [check_input(ev.rule_table, i, T.EvalParams(), ev.schema_mgr) for i in inputs]


def sources(outs) -> set[str]:
    return {e.source for o in outs for e in o.actions.values()}


def source_counts() -> dict[str, float]:
    hotrules.recorder().snapshot()  # flushes the recorder's pending decisions into the counter
    vec = obs.metrics().counter_vec("cerbos_tpu_decision_source_total", label="source")
    return {s: vec.get(s) for s in ("oracle", "device")}


@pytest.mark.parametrize("n", [1, 3, 15])
def test_a_request_under_the_threshold_on_an_empty_queue_is_answered_inline(evaluator, shard, n):
    b = BatchingEvaluator(evaluator, shard_id=shard)
    inputs = [inp(i) for i in range(n)]
    before, sizes, decided = routes(), batch_sizes(), source_counts()
    wf = Waterfall()
    try:
        out = b.check(inputs, wf=wf)
        wall = wf.age()
    finally:
        b.close()
    assert out == oracle(evaluator, inputs) and sources(out) == {"oracle"}
    assert moved(before) == {"inline": 1, "queued": 0}
    after = source_counts()
    assert (after["oracle"] - decided["oracle"], after["device"] - decided["device"]) == (n, 0)
    # nothing of a flight: none was made
    assert flights_of(shard) == [] and batch_sizes() == sizes
    assert b.stats["batches"] == 0 and b.stats["batched_requests"] == 0 and b.stats["oracle_fallbacks"] == 0
    # what has a reader: the evaluation's seconds, once
    stage = oracle_stage(shard)
    assert stage.count == 1 and 0 < stage.sum < wall
    # the waterfall: admission, a wait of nothing, the evaluation; they tile the request
    assert [s for s, _ in wf.stages] == [STAGE_ADMISSION, STAGE_QUEUE_WAIT, STAGE_EVALUATE]
    stages = dict(wf.stages)
    assert stages[STAGE_QUEUE_WAIT] < 0.001 and stages[STAGE_EVALUATE] >= stage.sum
    assert wf.attributed() == pytest.approx(wf.age(now=wf._last), abs=1e-9) and wf.attributed() <= wall
    assert wf.shard == shard and wf.served_by == "device" and wf.fallback_reason == ""  # not a fallback


def test_a_request_at_the_threshold_is_queued_and_makes_one_flight(evaluator, shard):
    b = BatchingEvaluator(evaluator, shard_id=shard)
    inputs = [inp(i) for i in range(16)]
    before = routes()
    try:
        out = b.check(inputs)
    finally:
        b.close()
    assert [o.actions["view"].effect for o in out] == [o.actions["view"].effect for o in oracle(evaluator, inputs)]
    assert sources(out) == {"device"}
    assert moved(before) == {"inline": 0, "queued": 1}
    (rec,) = flights_of(shard)
    assert rec["inputs"] == 16 and rec["requests"] == 1 and rec["outcome"] == "ok"
    assert oracle_stage(shard).count == 1  # once per flight, as before


def test_a_single_behind_a_queued_page_rides_that_pages_flight(evaluator, shard):
    gate = FlightGate(evaluator)
    b = BatchingEvaluator(gate, shard_id=shard)
    box: dict[str, list] = {}
    before = routes()
    try:
        plug = gate.hold(b, [inp(i) for i in range(16)])  # the drain thread is inside this flight's submit
        page = b.check_async([inp(100 + i) for i in range(20)])  # held in the queue behind it
        single = threading.Thread(target=lambda: box.update(out=b.check([inp(7)])))
        single.start()
        gate.release(b, queued=2)  # the single found the page queued, and queued too
        single.join(timeout=SOON)
        assert not single.is_alive()
        plug.result(timeout=SOON), page.result(timeout=SOON)
    finally:
        b.close()
    assert moved(before) == {"inline": 0, "queued": 3}  # the single, and the plug and the page through check_async
    assert sorted(r["inputs"] for r in flights_of(shard)) == [16, 21]
    assert sources(box["out"]) == {"device"}
    assert box["out"][0].actions["view"].effect == oracle(evaluator, [inp(7)])[0].actions["view"].effect


@pytest.mark.parametrize("n", [1, 3, 16, 40])
def test_check_async_always_queues_and_is_counted_as_queued(evaluator, shard, n):
    """The pool owner's door: one increment of ``route="queued"`` per call past
    the ladder, whatever the size, so ``inline_share`` reads 0.0 in a pool."""
    b = BatchingEvaluator(evaluator, shard_id=shard)
    inputs = [inp(i) for i in range(n)]
    before, sizes = routes(), batch_sizes()
    try:
        out = b.check_async(inputs).result(timeout=SOON)
        assert moved(before) == {"inline": 0, "queued": 1}
        assert sans_source(out) == sans_source(oracle(evaluator, inputs))
        assert batch_sizes() == sizes + 1  # a flight, even of one: the door never answers on the caller's thread
        # check() beside it counts as it did: a single on the empty queue inline, a page queued
        assert b.check([inp(0)]) and moved(before) == {"inline": 1, "queued": 1}
        assert b.check([inp(i) for i in range(16)]) and moved(before) == {"inline": 1, "queued": 2}
    finally:
        b.close()


class OpenBreaker:
    """``DeviceHealth`` while open with no probe due."""

    def allow_device(self):
        return False

    def should_probe(self):
        return None


def quarantined(b):
    b._quarantine_add(inp(1))


def breaker_open(b):
    b.health = OpenBreaker()


def loop_dead(b):
    b.close()


@pytest.mark.parametrize(
    "arrange, reason", [(quarantined, "quarantine"), (breaker_open, "breaker_open"), (loop_dead, "batcher_dead")]
)
def test_the_ladder_answers_before_the_route_is_chosen(shard, arrange, reason):
    ev = TpuEvaluator(table(), use_jax=False)
    b = BatchingEvaluator(ev, shard_id=shard)
    fallbacks = obs.metrics().counter_vec("cerbos_tpu_batcher_oracle_fallbacks_total")
    try:
        arrange(b)
        before, fell = routes(), fallbacks.get(reason)
        wf = Waterfall()
        assert b.check([inp(1)], wf=wf) == oracle(ev, [inp(1)])
    finally:
        b.close()
    assert moved(before) == {"inline": 0, "queued": 0}  # it never got past the ladder
    assert fallbacks.get(reason) == fell + 1 and b.stats["oracle_fallbacks"] == 1
    assert wf.fallback_reason == reason and wf.served_by == "oracle"
    assert oracle_stage(shard).count == 0


@pytest.mark.parametrize("arrange", [quarantined, breaker_open, loop_dead])
def test_check_async_refused_by_the_ladder_is_not_counted(shard, arrange):
    b = BatchingEvaluator(TpuEvaluator(table(), use_jax=False), shard_id=shard)
    try:
        arrange(b)
        before = routes()
        with pytest.raises(_BatchFailed):
            b.check_async([inp(1)]).result(timeout=SOON)
        with pytest.raises(DeadlineExceeded):
            b.check_async([inp(1)], deadline=time.monotonic() - 0.001).result(timeout=SOON)
    finally:
        b.close()
    assert moved(before) == {"inline": 0, "queued": 0}


def test_an_expired_deadline_raises_before_the_route_is_chosen(shard):
    b = BatchingEvaluator(TpuEvaluator(table(), use_jax=False), shard_id=shard)
    before = routes()
    try:
        with pytest.raises(DeadlineExceeded):
            b.check([inp(1)], deadline=time.monotonic() - 0.001)
        assert b.check([inp(1)], deadline=time.monotonic() + 30)  # one with time left is answered inline
    finally:
        b.close()
    assert moved(before) == {"inline": 1, "queued": 0} and b.stats["deadline_drops"] == 1


def test_a_request_that_meets_a_pending_barrier_waits_it_out_in_the_queue(shard):
    ev = TpuEvaluator(table(), use_jax=False)
    ev.rule_table.policy_epoch = 1
    b = BatchingEvaluator(ev, shard_id=shard)
    b.epoch = 1
    new_table = build_rule_table(compile_policy_set(list(parse_policies(POLICY_V2))))
    new_table.policy_epoch = 2
    barrier = Barrier()
    box: dict = {}

    def caller():
        out = b.check([inp(0)])
        box.update(effect=out[0].actions["view"].effect, epoch=T.current_epoch())

    try:
        assert b.check([inp(0)])[0].actions["view"].effect == "EFFECT_ALLOW" and T.current_epoch() == 1
        before = routes()
        assert b.request_swap(barrier) and barrier.parked.wait(timeout=SOON)
        waiting = threading.Thread(target=caller)
        waiting.start()
        end = time.monotonic() + SOON
        while len(b._queue) < 1:
            assert time.monotonic() < end, "the request did not queue behind the barrier"
            time.sleep(0.001)
        assert not box  # parked: nothing answers across the cutover
        # the cutover, as rollout._commit makes it under the barrier
        ev.rule_table = ev.lowered.table = new_table
        ev.refresh()
        b.epoch = 2
        barrier.go.set()
        waiting.join(timeout=SOON)
        assert not waiting.is_alive()
        assert moved(before) == {"inline": 0, "queued": 1}
        assert box == {"effect": "EFFECT_DENY", "epoch": 2}  # the new table's answer under the new table's epoch
        # and the next one, inline again, reads the epoch from the table it read
        end = time.monotonic() + SOON
        while b._swap_barrier is not None:
            assert time.monotonic() < end
            time.sleep(0.001)
        assert b.check([inp(0)])[0].actions["view"].effect == "EFFECT_DENY" and T.current_epoch() == 2
        assert moved(before) == {"inline": 1, "queued": 1}
    finally:
        barrier.go.set()
        b.close()


class NoThreshold:
    """An evaluator with an oracle's attributes and no ``min_device_batch``: every fake of the other test files."""

    def __init__(self):
        self.rule_table = table()
        self.schema_mgr = None
        self.flights: list[int] = []

    def check(self, inputs, params=None):
        self.flights.append(len(inputs))
        return [check_input(self.rule_table, i, params or T.EvalParams(), None) for i in inputs]


def test_an_evaluator_without_a_threshold_never_goes_inline(shard):
    ev = NoThreshold()
    b = BatchingEvaluator(ev, shard_id=shard)
    before = routes()
    try:
        for n in (1, 3, 15):
            assert len(b.check([inp(i) for i in range(n)])) == n
    finally:
        b.close()
    assert moved(before) == {"inline": 0, "queued": 3} and ev.flights == [1, 3, 15]
    assert [r["inputs"] for r in flights_of(shard)] == [1, 3, 15]


def test_sixteen_threads_of_single_checks_all_get_the_oracles_answer(evaluator, shard):
    """The oracle on up to sixteen request threads at once, with a short switch
    interval; a page now and then, so that some singles find the queue taken."""
    threads, each = 16, 200
    b = BatchingEvaluator(evaluator, shard_id=shard)
    want = oracle(evaluator, [inp(i) for i in range(each)])
    page = [inp(1000 + i) for i in range(16)]
    wrong: list = []

    def caller(k: int):
        for i in range(each):
            out = b.check([inp(i)])
            # a single that rode a page's flight is labelled by the device: all but the label is the oracle's
            if sans_source(out) != sans_source([want[i]]):
                wrong.append((k, i, out))
            if k == 0 and i % 20 == 0:
                b.check_async(page)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        b.check(page)  # the jax backend compiles the page's layout before the clocked part
        before = routes()
        workers = [threading.Thread(target=caller, args=(k,)) for k in range(threads)]
        for t in workers:
            t.start()
        for t in workers:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in workers)
    finally:
        sys.setswitchinterval(interval)
        b.close()
    assert not wrong, wrong[:3]
    got = moved(before)
    pages = len(range(0, each, 20))  # thread 0's, through check_async: counted as queued
    assert got["inline"] + got["queued"] == threads * each + pages and got["inline"] > 0


def test_inline_traffic_keeps_the_sentinels_ring_of_live_inputs_and_queues_no_replay(shard):
    ev = TpuEvaluator(table(), use_jax=False)
    b = BatchingEvaluator(ev, shard_id=shard)
    sentinel = ParitySentinel(sample_rate=1.0)
    b.sentinel = sentinel  # not attach(): the replay worker must not be needed
    inputs = [inp(i) for i in range(5)]
    try:
        for i in inputs:
            b.check([i])
    finally:
        b.close()
        sentinel.close()
    assert sentinel.recent_inputs() == inputs  # what the rollout gate replays before a cutover
    assert sentinel.backlog() == 0 and sentinel._thread is None
    assert sentinel.stats["sampled"] == 5 and sentinel.stats["checks"] == 0


def test_a_sentinel_that_samples_nothing_is_offered_nothing(shard):
    b = BatchingEvaluator(TpuEvaluator(table(), use_jax=False), shard_id=shard)
    b.sentinel = sentinel = ParitySentinel(sample_rate=0.0)
    try:
        b.check([inp(1)])
    finally:
        b.close()
    assert sentinel.recent_inputs() == []


def test_the_engines_span_says_inline_and_has_no_batch_span_under_it(shard):
    ev = TpuEvaluator(table(), use_jax=False)
    b = BatchingEvaluator(ev, shard_id=shard)
    engine = Engine(ev.rule_table, tpu_evaluator=b, tpu_batch_threshold=1)

    class Capture(obs.SpanExporter):
        spans: list = []

        def export(self, span, duration_ms):
            self.spans.append(span)

    old = obs._exporter
    obs.set_exporter(Capture())
    try:
        with obs.start_span("request.CheckResources") as root:
            engine.check([inp(1)])
            engine.check([inp(i) for i in range(16)])
        time.sleep(0.05)  # the flight's last spans export on the drain thread, after the reply
    finally:
        obs.set_exporter(old)
        b.close()
    spans = [s for s in Capture.spans if s.trace_id == root.trace_id]
    checks = [s for s in spans if s.name == "engine.Check"]
    assert [s.attributes["path"] for s in checks] == ["inline", "device"]
    assert all(s.parent_id == root.span_id for s in checks)
    # every batcher.* and batch.* span of the trace hangs under the page's engine.Check, none under the single's
    by_id = {s.span_id: s for s in spans}
    below_single = [s.name for s in spans if s.parent_id == checks[0].span_id]
    assert below_single == [], below_single
    enqueue = [s for s in spans if s.name == "batcher.enqueue"]
    assert len(enqueue) == 1 and by_id[enqueue[0].parent_id] is checks[1]
    assert root.attributes.get("path") is None  # only the engine's own span is relabelled
