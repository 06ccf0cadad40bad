"""Latency-budget waterfall, goodput accounting, and pressure signals.

Covers the PR's acceptance surface at the unit/integration level:

- stage-sum reconciliation: the recorded stages tile each request's wall
  clock (the ``mark``/``add`` cursor invariant), in-process and through a
  real batcher;
- cross-process clock anchoring: only RELATIVE values cross the IPC hop,
  so an arbitrary monotonic-clock skew between front end and batcher
  cancels out of the reassembled waterfall;
- goodput vs throughput: ``cerbos_tpu_decisions_total{outcome}`` splits
  under a ``wedge_after`` chaos drill (expired requests count against
  throughput, not goodput);
- slow-request ring capture with the ``?shard=`` filter;
- pressure under backlog: the queue component rises before deadlines die,
  and the high-water crossing leaves a flight-recorder breadcrumb;

across all three topologies: single batcher, the frontends ticket queue
(``BatcherIpcServer``/``RemoteBatcherClient`` in-process pair), and the
sharded pool.
"""

import time

import pytest

from cerbos_tpu.compile import compile_policy_set
from cerbos_tpu.engine import CheckInput, EvalParams, Principal, Resource
from cerbos_tpu.engine import budget as budget_mod
from cerbos_tpu.engine import flight
from cerbos_tpu.engine.batcher import BatchingEvaluator, DeadlineExceeded
from cerbos_tpu.engine.budget import (
    OUTCOME_EXPIRED,
    OUTCOME_MET,
    OUTCOME_ORACLE,
    STAGE_ADMISSION,
    STAGE_INGRESS_PARSE,
    STAGE_IPC_ENCODE,
    STAGE_IPC_RETURN,
    STAGE_QUEUE_WAIT,
    STAGE_REPLY_ENCODE,
    STAGE_SETTLE,
    STAGE_TRANSIT,
    STAGES,
    Waterfall,
)
from cerbos_tpu.engine.health import DeviceHealth
from cerbos_tpu.engine.pressure import HIGH_WATER, PressureMonitor
from cerbos_tpu.engine.shards import ShardedBatchingEvaluator
from cerbos_tpu.policy.parser import parse_policies
from cerbos_tpu.ruletable import build_rule_table, check_input

POLICY = """
apiVersion: api.cerbos.dev/v1
resourcePolicy:
  resource: album
  version: default
  rules:
    - actions: ["view"]
      effect: EFFECT_ALLOW
      roles: [user]
      condition:
        match:
          expr: request.resource.attr.owner == request.principal.id || request.resource.attr.public == true
"""


def table():
    return build_rule_table(compile_policy_set(list(parse_policies(POLICY))))


def inp(i: int, **attr) -> CheckInput:
    return CheckInput(
        principal=Principal(id=f"u{i}", roles=["user"]),
        resource=Resource(
            kind="album",
            id=f"a{i}",
            attr={"owner": f"u{i % 7}", "public": i % 3 == 0, **attr},
        ),
        actions=["view"],
        request_id=f"rq{i}",
    )


class OracleEvaluator:
    """CPU-oracle-backed evaluator with the streaming surface (no jax)."""

    def __init__(self, rt, submit_delay_s: float = 0.0):
        self.rule_table = rt
        self.schema_mgr = None
        self.submit_delay_s = submit_delay_s
        self.stats = {"device_inputs": 0}

    def check(self, inputs, params=None):
        return [check_input(self.rule_table, i, params or EvalParams()) for i in inputs]

    def submit(self, inputs, params=None):
        if self.submit_delay_s:
            time.sleep(self.submit_delay_s)
        self.stats["device_inputs"] += len(inputs)
        return self.check(inputs, params)

    def collect(self, ticket):
        return ticket


@pytest.fixture()
def rt():
    return table()


@pytest.fixture()
def tracker():
    trk = budget_mod.tracker()
    prev = (trk.enabled, trk.slow_threshold_s, trk._ring.maxlen)
    trk.configure(enabled=True)
    trk.reset()
    yield trk
    trk.configure(
        enabled=prev[0], slow_threshold_ms=prev[1] * 1000, slow_capacity=prev[2]
    )
    trk.reset()


def stage_names(wf):
    return [s for s, _ in wf.stages]


def finish_like_server(trk, wf, fn):
    """The server layer's outcome classification, distilled for unit tests."""
    try:
        out = fn()
    except DeadlineExceeded:
        trk.finish(wf, OUTCOME_EXPIRED)
        return None
    trk.finish(
        wf,
        OUTCOME_ORACLE if wf is not None and wf.served_by == "oracle" else OUTCOME_MET,
        final_stage=STAGE_REPLY_ENCODE,
    )
    return out


class TestWaterfallRecord:
    def test_marks_tile_wall_clock(self):
        wf = Waterfall()
        wf.mark(STAGE_INGRESS_PARSE)
        time.sleep(0.002)
        wf.mark(STAGE_ADMISSION)
        assert wf.attributed() == pytest.approx(wf.age(now=wf._last), abs=1e-9)

    def test_add_advances_cursor_so_marks_book_residual(self):
        wf = Waterfall(t0=100.0)
        wf.add("pack", 0.010)
        wf.add("device", 0.020)
        # external durations moved the cursor to t0+0.030; a mark at
        # t0+0.050 books only the 0.020 residual
        wf.mark(STAGE_SETTLE, now=100.050)
        assert dict(wf.stages)[STAGE_SETTLE] == pytest.approx(0.020)
        assert wf.attributed() == pytest.approx(0.050)

    def test_snapshot_carries_trace_outcome_fields(self):
        wf = Waterfall(trace_id="t-123", deadline=time.monotonic() + 1.0)
        wf.shard = 2
        wf.note_fallback("breaker_open")
        wf.mark("oracle")
        snap = wf.snapshot()
        assert snap["trace_id"] == "t-123"
        assert snap["shard"] == 2
        assert snap["served_by"] == "oracle"
        assert snap["fallback_reason"] == "breaker_open"
        assert snap["budget_remaining_ms"] > 0


class TestCrossProcessAnchoring:
    def test_carry_resume_books_transit_from_unattributed_age(self):
        spec = (0.010, 0.004)  # 10ms old, 4ms already attributed
        wf = Waterfall.from_carry(spec, trace_id="t-x")
        stages = dict(wf.stages)
        assert stages[STAGE_TRANSIT] == pytest.approx(0.006, abs=2e-3)
        assert wf.age() == pytest.approx(0.010, abs=2e-3)

    def test_clock_skew_cancels(self):
        """Both processes only ever exchange RELATIVE values, so the
        reassembled waterfall is identical no matter how far apart the two
        monotonic clocks sit. Simulated with explicit clock offsets."""
        fe_now = 1000.0  # front-end clock
        wf_fe = Waterfall(t0=fe_now)
        wf_fe.mark(STAGE_INGRESS_PARSE, now=fe_now + 0.001)
        wf_fe.mark(STAGE_IPC_ENCODE, now=fe_now + 0.003)
        carry = wf_fe.carry(now=fe_now + 0.005)  # 2ms in flight so far
        assert carry == (pytest.approx(0.005), pytest.approx(0.003))

        # batcher clock sits 9000s away; only the carried age matters
        wf_b = Waterfall.from_carry(carry)
        stages_b = dict(wf_b.stages)
        assert stages_b[STAGE_TRANSIT] == pytest.approx(0.002, abs=2e-3)
        wf_b.mark(STAGE_QUEUE_WAIT)
        reply = wf_b.reply_spec()

        # front end splices the batcher stages and books the return residual
        wf_fe.splice_reply(reply, now=fe_now + 0.009)
        names = stage_names(wf_fe)
        assert names[:2] == [STAGE_INGRESS_PARSE, STAGE_IPC_ENCODE]
        assert STAGE_TRANSIT in names and STAGE_QUEUE_WAIT in names
        assert names[-1] == STAGE_IPC_RETURN
        # reconciliation: every recorded stage tiles the front-end wall clock
        assert wf_fe.attributed() == pytest.approx(0.009, abs=3e-3)

    def test_malformed_carry_resumes_to_none(self, tracker):
        assert tracker.resume("not-a-spec") is None
        assert tracker.resume(None) is None


class TestSingleBatcherTopology:
    def test_stage_sum_reconciles_through_batcher(self, rt, tracker):
        b = BatchingEvaluator(OracleEvaluator(rt), max_wait_ms=1.0)
        try:
            t0 = time.monotonic()
            wf = tracker.start(trace_id="t-single")
            out = finish_like_server(tracker, wf, lambda: b.check([inp(1)], wf=wf))
            wall = time.monotonic() - t0
            assert out is not None
            names = stage_names(wf)
            assert set(names) <= set(STAGES)
            for want in (STAGE_ADMISSION, STAGE_QUEUE_WAIT, STAGE_SETTLE, STAGE_REPLY_ENCODE):
                assert want in names, names
            # >=95% of the request's wall clock attributed to named stages
            assert wf.attributed() >= 0.95 * (wall - 0.001)
            assert wf.attributed() <= wall + 0.005
            assert wf.shard == 0
        finally:
            b.close()

    def test_budget_sampled_at_enqueue_and_device_submit(self, rt, tracker):
        b = BatchingEvaluator(OracleEvaluator(rt), max_wait_ms=1.0)
        try:
            before_enq = tracker.m_budget.labels(("enqueue", "0")).snapshot()[2]
            before_sub = tracker.m_budget.labels(("device_submit", "0")).snapshot()[2]
            wf = tracker.start(deadline=time.monotonic() + 5.0)
            b.check([inp(2)], deadline=time.monotonic() + 5.0, wf=wf)
            assert tracker.m_budget.labels(("enqueue", "0")).snapshot()[2] == before_enq + 1
            assert (
                tracker.m_budget.labels(("device_submit", "0")).snapshot()[2]
                == before_sub + 1
            )
        finally:
            b.close()

    def test_breaker_open_notes_oracle_fallback(self, rt, tracker):
        health = DeviceHealth(failure_threshold=1)
        b = BatchingEvaluator(OracleEvaluator(rt), max_wait_ms=1.0, health=health)
        try:
            health.record_failure()  # threshold=1: trips the breaker open
            wf = tracker.start()
            finish_like_server(tracker, wf, lambda: b.check([inp(3)], wf=wf))
            assert wf.served_by == "oracle"
            assert wf.fallback_reason == "breaker_open"
            assert "oracle" in stage_names(wf)
        finally:
            b.close()


class TestGoodputUnderWedge:
    def test_expired_counts_against_throughput_not_goodput(self, rt, tracker):
        from cerbos_tpu.engine.faults import FaultInjector

        # the first request's submit+collect succeed (2 device calls), then
        # the device wedges: later requests blow their deadlines and must
        # land in outcome=expired
        wedged = FaultInjector(OracleEvaluator(rt), "wedge_after:2,wedge_sleep_s:1")
        b = BatchingEvaluator(wedged, max_wait_ms=1.0)
        vec = tracker.m_decisions
        before = {k: vec.get(("check", k)) for k in (OUTCOME_MET, OUTCOME_EXPIRED)}
        try:
            wf = tracker.start()
            assert finish_like_server(tracker, wf, lambda: b.check([inp(1)], wf=wf))
            for i in range(2):
                deadline = time.monotonic() + 0.2
                wf = tracker.start(deadline=deadline)
                out = finish_like_server(
                    tracker, wf, lambda: b.check([inp(10 + i)], deadline=deadline, wf=wf)
                )
                assert out is None  # deadline expired while the device wedged
        finally:
            b.close()
        met = vec.get(("check", OUTCOME_MET)) - before[OUTCOME_MET]
        expired = vec.get(("check", OUTCOME_EXPIRED)) - before[OUTCOME_EXPIRED]
        assert met == 1
        assert expired == 2


class TestSlowRing:
    def test_captures_above_threshold_with_shard_filter(self, rt, tracker):
        tracker.configure(slow_threshold_ms=0.0, slow_capacity=8)
        b = BatchingEvaluator(OracleEvaluator(rt), max_wait_ms=1.0, shard_id=3)
        try:
            wf = tracker.start(trace_id="t-slow")
            finish_like_server(tracker, wf, lambda: b.check([inp(5)], wf=wf))
        finally:
            b.close()
        dump = tracker.slow_dump()
        assert dump["requests"], dump
        entry = dump["requests"][0]
        assert entry["trace_id"] == "t-slow"
        assert entry["outcome"] == OUTCOME_MET
        assert entry["shard"] == 3
        assert any(s == STAGE_QUEUE_WAIT for s, _ in entry["stages"])
        # shard filter: matching shard keeps the entry, others drop it
        assert tracker.slow_dump(shard=3)["requests"]
        assert not tracker.slow_dump(shard=7)["requests"]

    def test_ring_is_bounded(self, tracker):
        tracker.configure(slow_threshold_ms=0.0, slow_capacity=4)
        for i in range(10):
            wf = tracker.start(trace_id=f"t{i}")
            wf.mark(STAGE_ADMISSION)
            tracker.finish(wf, OUTCOME_MET)
        assert len(tracker.slow_dump()["requests"]) == 4

    def test_disabled_tracker_still_counts_decisions(self, tracker):
        tracker.configure(enabled=False)
        before = tracker.m_decisions.get(("check", OUTCOME_MET))
        assert tracker.start() is None
        tracker.finish(None, OUTCOME_MET)
        tracker.count(OUTCOME_MET)
        assert tracker.m_decisions.get(("check", OUTCOME_MET)) == before + 2
        assert not tracker.slow_dump()["requests"]


class TestFrontendsTopology:
    @pytest.mark.parametrize("transport", ["shm", "uds"])
    def test_waterfall_crosses_ticket_queue(self, tmp_path, rt, tracker, transport):
        """Attribution must hold on BOTH data planes: the shm frame rings
        (native codec, ipc_encode marked before the carry is cut) and the
        uds marshal fallback tile the front end's wall clock identically."""
        from cerbos_tpu import native
        from cerbos_tpu.engine.ipc import BatcherIpcServer, RemoteBatcherClient

        if transport == "shm" and native.get() is None:
            pytest.skip("native module unavailable: shm plane cannot grant")
        batcher = BatchingEvaluator(OracleEvaluator(rt), max_wait_ms=1.0)
        server = BatcherIpcServer(str(tmp_path / "b.sock"), batcher, transport=transport)
        server.start()
        client = RemoteBatcherClient(
            server.socket_path,
            rt,
            worker_label="fe-test",
            status_poll_s=0.05,
            transport=transport,
        )
        try:
            deadline = time.monotonic() + 10.0
            assert client._connected.wait(5.0)
            assert client.transport == transport
            t0 = time.monotonic()
            wf = tracker.start(trace_id="t-fe", deadline=deadline)
            out = finish_like_server(
                tracker, wf, lambda: client.check([inp(1)], deadline=deadline, wf=wf)
            )
            wall = time.monotonic() - t0
            assert out is not None
            names = stage_names(wf)
            # front-end stages, batcher stages, and the return residual all
            # present, in one record (no settle: the ticket server rides the
            # async path, so the reply spec is cut on the drain loop)
            for want in (
                STAGE_IPC_ENCODE,
                STAGE_TRANSIT,
                STAGE_ADMISSION,
                STAGE_QUEUE_WAIT,
                STAGE_IPC_RETURN,
                STAGE_REPLY_ENCODE,
            ):
                assert want in names, names
            assert set(names) <= set(STAGES)
            # reconciliation across the process boundary: attribution covers
            # the front end's measured wall clock
            assert wf.attributed() >= 0.95 * (wall - 0.001)
            assert wf.attributed() <= wall + 0.005
        finally:
            client.close()
            server.close()
            batcher.close()

    def test_oracle_fallback_crosses_reply_spec(self, tmp_path, rt, tracker):
        """A batcher-side oracle serve must be visible to the front end's
        outcome classification via the reply spec."""
        from cerbos_tpu.engine.ipc import BatcherIpcServer, RemoteBatcherClient

        health = DeviceHealth(failure_threshold=1)
        batcher = BatchingEvaluator(OracleEvaluator(rt), max_wait_ms=1.0, health=health)
        server = BatcherIpcServer(str(tmp_path / "b.sock"), batcher)
        server.start()
        client = RemoteBatcherClient(
            server.socket_path, rt, worker_label="fe-test", status_poll_s=0.05
        )
        try:
            assert client._connected.wait(5.0)
            health.record_failure()  # threshold=1: trips the breaker open
            wf = tracker.start()
            out = finish_like_server(tracker, wf, lambda: client.check([inp(2)], wf=wf))
            assert out is not None
            assert wf.served_by == "oracle"
        finally:
            client.close()
            server.close()
            batcher.close()

    def test_ipc_slow_and_pressure_snapshots(self, tmp_path, rt, tracker):
        from cerbos_tpu.engine.ipc import BatcherIpcServer, RemoteBatcherClient

        tracker.configure(slow_threshold_ms=0.0)
        batcher = BatchingEvaluator(OracleEvaluator(rt), max_wait_ms=1.0)
        server = BatcherIpcServer(str(tmp_path / "b.sock"), batcher)
        server.start()
        client = RemoteBatcherClient(
            server.socket_path, rt, worker_label="fe-test", status_poll_s=0.05
        )
        try:
            assert client._connected.wait(5.0)
            wf = tracker.start(trace_id="t-ring")
            finish_like_server(tracker, wf, lambda: client.check([inp(3)], wf=wf))
            # in-process pair shares one tracker, so the ring holds the entry;
            # the frames themselves must round-trip the dump + pressure sample
            slow = client.fetch_slow()
            assert slow["requests"], slow
            assert "pid" in slow
            pres = client.fetch_pressure()
            assert "score" in pres and "components" in pres
        finally:
            client.close()
            server.close()
            batcher.close()


class TestShardedTopology:
    def test_waterfall_carries_lane_shard_id(self, rt, tracker):
        lanes = [
            BatchingEvaluator(OracleEvaluator(rt), max_wait_ms=1.0, shard_id=i)
            for i in range(2)
        ]
        pool = ShardedBatchingEvaluator(lanes, routing="round_robin")
        try:
            seen = set()
            for i in range(4):
                wf = tracker.start()
                out = finish_like_server(
                    tracker, wf, lambda: pool.check([inp(i)], wf=wf)
                )
                assert out is not None
                assert wf.shard in (0, 1)
                seen.add(wf.shard)
                assert STAGE_QUEUE_WAIT in stage_names(wf)
            assert seen == {0, 1}  # round robin hit both lanes
        finally:
            pool.close()


class TestPressure:
    def make_monitor(self):
        clock = {"t": 0.0}
        mon = PressureMonitor(clock=lambda: clock["t"])
        mon.configure(enabled=True, window_s=10.0)
        return mon, clock

    def test_queue_backlog_raises_score_before_expiry(self):
        mon, clock = self.make_monitor()
        load = {"v": 0}
        mon.bind(queue=lambda: (load["v"], 100))
        snap = mon.sample()
        assert snap["score"] == 0.0
        # backlog builds: queue load climbs toward capacity over the window
        for i, v in enumerate((50, 80, 95, 98)):
            clock["t"] += 1.0
            load["v"] = v
            snap = mon.sample()
        assert snap["components"]["queue"] >= 0.9
        assert snap["score"] >= 0.9

    def test_high_water_crossing_records_flight_event(self):
        mon, clock = self.make_monitor()
        full = {"v": 0}
        mon.bind(inflight=lambda: (full["v"], 4))
        rec = flight.recorder()
        rec.clear()
        full["v"] = 4
        for _ in range(3):  # crossing records ONE event, not one per tick
            clock["t"] += 1.0
            mon.sample()
        events = [e for e in rec.dump()["events"] if e["kind"] == "pressure_high"]
        assert len(events) == 1
        assert events[0]["score"] >= HIGH_WATER
        # falling below re-arms the edge
        full["v"] = 0
        for _ in range(12):
            clock["t"] += 1.0
            mon.sample()
        full["v"] = 4
        clock["t"] += 1.0
        mon.sample()
        events = [e for e in rec.dump()["events"] if e["kind"] == "pressure_high"]
        assert len(events) == 2
        rec.clear()

    def test_fallback_fraction_is_windowed(self):
        mon, clock = self.make_monitor()
        counts = {"fb": 0.0, "dec": 0.0}
        mon.bind(fallbacks=lambda: counts["fb"], decisions=lambda: counts["dec"])
        mon.sample()
        # 100 decisions, 40 fallbacks inside the window
        clock["t"] += 1.0
        counts.update(fb=40.0, dec=100.0)
        snap = mon.sample()
        assert snap["components"]["fallback"] == pytest.approx(0.4)
        # window slides past the burst: the fraction decays to 0
        counts.update(fb=40.0, dec=200.0)
        for _ in range(12):
            clock["t"] += 1.0
            snap = mon.sample()
        assert snap["components"]["fallback"] == pytest.approx(0.0)

    def test_breaker_and_parity_map_to_degraded(self):
        mon, _clock = self.make_monitor()
        state = {"s": "closed", "shards": []}
        mon.bind(breaker=lambda: state["s"], parity=lambda: state["shards"])
        assert mon.sample()["components"]["degraded"] == 0.0
        state["s"] = "half_open"
        assert mon.sample()["components"]["degraded"] == 0.5
        state["s"] = "open"
        assert mon.sample()["components"]["degraded"] == 1.0
        state.update(s="closed", shards=[2])
        assert mon.sample()["components"]["degraded"] == 1.0

    def test_dead_sources_read_as_zero(self):
        mon, _clock = self.make_monitor()

        def boom():
            raise RuntimeError("dead source")

        mon.bind(queue=boom, inflight=boom, fallbacks=boom, breaker=boom)
        snap = mon.sample()
        assert snap["score"] == 0.0

    def test_compile_storm_inside_window(self):
        mon, clock = self.make_monitor()
        storms = {"v": 3.0}
        mon.bind(storms=lambda: storms["v"])
        assert mon.sample()["components"]["compile"] == 0.0
        clock["t"] += 1.0
        storms["v"] = 4.0  # a storm fired since the window opened
        assert mon.sample()["components"]["compile"] == 1.0
