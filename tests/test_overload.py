"""Overload-graceful serving: admission control, priority lanes, brownout.

Every controller under test takes an injectable clock (or an explicit
``now``), so the token bucket, the brownout hold timers, and the pressure
window all run on fake time — no sleeps, no flakes. Metric assertions are
deltas: the instruments are process-global (get-or-create registry) and
other suites in the same run share them.
"""

import marshal
import re
import time
from concurrent.futures import Future

import pytest

from cerbos_tpu import observability as obs
from cerbos_tpu.engine import CheckInput, EvalParams, Principal, Resource
from cerbos_tpu.engine import flight
from cerbos_tpu.engine.admission import (
    AdmissionController,
    OverloadRefused,
    PriorityClass,
    _NullTicket,
    retry_after_header,
)
from cerbos_tpu.engine.batcher import (
    BatchingEvaluator,
    _BatchFailed,
    _Pending,
    _PriorityLanes,
)
from cerbos_tpu.engine.brownout import BrownoutController
from cerbos_tpu.engine.budget import Waterfall
from cerbos_tpu.engine.ipc import RemoteBatcherClient
from cerbos_tpu.engine.pressure import HIGH_WATER, PressureMonitor
from cerbos_tpu.engine.readiness import ReadinessState
from flightgate import FlightGate

pytestmark = pytest.mark.overload


def _event_count(kind: str) -> int:
    return sum(1 for e in flight.recorder().dump()["events"] if e["kind"] == kind)


# ---------------------------------------------------------------------------
# priority classes: compilation + classification
# ---------------------------------------------------------------------------


class TestPriorityClass:
    def test_from_conf_defaults(self):
        c = PriorityClass.from_conf({"name": "gold"})
        assert (c.priority, c.weight, c.rate, c.max_concurrent, c.queue_budget) == (
            0,
            1,
            0.0,
            0,
            0,
        )
        # burst defaults to max(rate, 1): a rate below 1 rps must still
        # admit whole requests
        assert PriorityClass.from_conf({"name": "a", "rate": 0.5}).burst == 1.0
        assert PriorityClass.from_conf({"name": "a", "rate": 40}).burst == 40.0
        assert PriorityClass.from_conf({"name": "a", "rate": 40, "burst": 80}).burst == 80.0
        # priority-0 classes are protected from shed_low_priority by default
        assert PriorityClass.from_conf({"name": "a"}).sheddable is False
        assert PriorityClass.from_conf({"name": "a", "priority": 2}).sheddable is True
        assert (
            PriorityClass.from_conf({"name": "a", "priority": 2, "sheddable": False}).sheddable
            is False
        )
        # weight floors at 1 (a zero-weight lane would never drain)
        assert PriorityClass.from_conf({"name": "a", "weight": 0}).weight == 1

    def test_match_dimensions_and_globs(self):
        c = PriorityClass.from_conf(
            {
                "name": "gold",
                "match": {"roles": ["admin*"], "kinds": ["album"]},
            }
        )
        assert c.matches("u1", ["admin"], ["album"], "check")
        assert c.matches("u1", ["administrator"], ["album"], "check")
        # every NON-empty dimension must hit
        assert not c.matches("u1", ["user"], ["album"], "check")
        assert not c.matches("u1", ["admin"], ["report"], "check")
        # an empty dimension is a wildcard
        wide = PriorityClass.from_conf({"name": "any"})
        assert wide.matches("whoever", [], [], "plan")

    def test_classify_first_match_wins(self):
        ctrl = AdmissionController(clock=lambda: 0.0)
        ctrl.configure(
            {
                "enabled": True,
                "classes": [
                    {"name": "first", "match": {"principals": ["svc-*"]}},
                    {"name": "second", "match": {"principals": ["svc-a"]}},
                ],
            }
        )
        # svc-a hits both declared classes: declaration order wins
        assert ctrl.classify("svc-a").name == "first"
        assert ctrl.classify("svc-zzz").name == "first"
        # nothing matches -> the implicit default class
        assert ctrl.classify("alice").name == "default"
        assert ctrl.classify("alice").priority == 1

    def test_lane_conf_shape(self):
        c = PriorityClass.from_conf(
            {"name": "gold", "priority": 0, "weight": 4, "queueBudget": 32}
        )
        assert c.lane_conf() == ("gold", 0, 4, 32)


# ---------------------------------------------------------------------------
# admission controller: token bucket, concurrency, shed, disabled path
# ---------------------------------------------------------------------------


class TestAdmissionController:
    def _ctrl(self, conf, t0=0.0):
        state = {"now": t0}
        ctrl = AdmissionController(clock=lambda: state["now"])
        ctrl.configure(conf)
        return ctrl, state

    def test_disabled_path_hands_out_null_tickets(self):
        # no classes and no default caps: admission compiles to disabled and
        # the hot path costs one attribute read
        ctrl, _ = self._ctrl({"enabled": True, "classes": [], "default": {}})
        assert ctrl.enabled is False
        t = ctrl.try_admit(ctrl.default)
        assert isinstance(t, _NullTicket)
        t.release()  # born released; must be a no-op
        # explicit off wins even with classes declared
        ctrl2, _ = self._ctrl(
            {"enabled": False, "classes": [{"name": "gold", "rate": 1}]}
        )
        assert ctrl2.enabled is False

    def test_token_bucket_refuses_and_refills_on_fake_time(self):
        ctrl, state = self._ctrl(
            {"enabled": True, "classes": [{"name": "gold", "rate": 2, "burst": 2}]}
        )
        gold = ctrl.classes[0]
        admitted = ctrl.m_total.get(("gold", "admitted"))
        refused = ctrl.m_total.get(("gold", "refused_rate"))
        t1 = ctrl.try_admit(gold, now=0.0)
        t2 = ctrl.try_admit(gold, now=0.0)
        with pytest.raises(OverloadRefused) as ei:
            ctrl.try_admit(gold, now=0.0)
        assert ei.value.reason == "rate"
        assert ei.value.pclass == "gold"
        # the bucket is empty: a full token is 1/rate = 0.5 s away
        assert ei.value.retry_after == pytest.approx(0.5)
        # half a second of fake time refills exactly one token
        state["now"] = 0.5
        t3 = ctrl.try_admit(gold)
        with pytest.raises(OverloadRefused):
            ctrl.try_admit(gold, now=0.5)
        assert ctrl.m_total.get(("gold", "admitted")) == admitted + 3
        assert ctrl.m_total.get(("gold", "refused_rate")) == refused + 2
        for t in (t1, t2, t3):
            t.release()

    def test_concurrency_cap_and_ticket_release(self):
        ctrl, _ = self._ctrl(
            {"enabled": True, "classes": [{"name": "gold", "maxConcurrent": 1}]}
        )
        gold = ctrl.classes[0]
        t1 = ctrl.try_admit(gold, now=0.0)
        assert ctrl.m_inflight.get("gold") == 1.0
        with pytest.raises(OverloadRefused) as ei:
            ctrl.try_admit(gold, now=0.0)
        assert ei.value.reason == "concurrency"
        t1.release()
        t1.release()  # double release must not underflow the cap
        assert ctrl.m_inflight.get("gold") == 0.0
        t2 = ctrl.try_admit(gold, now=0.0)
        t2.release()

    def test_brownout_shed_refuses_sheddable_classes_only(self):
        ctrl, _ = self._ctrl(
            {
                "enabled": True,
                "classes": [
                    {"name": "gold", "priority": 0},
                    {"name": "bulk", "priority": 2},
                ],
            }
        )
        gold, bulk = ctrl.classes
        ctrl.set_shed(True)
        with pytest.raises(OverloadRefused) as ei:
            ctrl.try_admit(bulk, now=0.0)
        assert ei.value.reason == "brownout"
        # priority-0 traffic rides through the shed
        ctrl.try_admit(gold, now=0.0).release()
        ctrl.set_shed(False)
        ctrl.try_admit(bulk, now=0.0).release()

    def test_retry_after_header_is_integral_and_floored(self):
        mk = lambda ra: OverloadRefused("c", "rate", retry_after=ra)
        assert retry_after_header(mk(0.5)) == "1"
        assert retry_after_header(mk(3.2)) == "4"
        assert retry_after_header(mk(0.0)) == "1"
        assert retry_after_header(mk(0.0005)) == "1"
        # negative retry_after is clamped at construction
        assert mk(-5.0).retry_after == 0.0

    def test_snapshot_shape(self):
        ctrl, _ = self._ctrl(
            {"enabled": True, "classes": [{"name": "gold", "rate": 5, "maxConcurrent": 2}]}
        )
        ticket = ctrl.try_admit(ctrl.classes[0], now=0.0)
        snap = ctrl.snapshot()
        assert snap["enabled"] is True
        assert snap["shed_low_priority"] is False
        by_name = {c["name"]: c for c in snap["classes"]}
        assert set(by_name) == {"gold", "default"}
        assert by_name["gold"]["inflight"] == 1
        assert by_name["gold"]["maxConcurrent"] == 2
        assert by_name["gold"]["sheddable"] is False
        ticket.release()

    def test_lane_confs_cover_every_class_plus_default(self):
        ctrl, _ = self._ctrl(
            {
                "enabled": True,
                "classes": [
                    {"name": "gold", "priority": 0, "weight": 4, "queueBudget": 16},
                    {"name": "bulk", "priority": 2, "weight": 1, "queueBudget": 8},
                ],
            }
        )
        confs = ctrl.lane_confs()
        assert confs == [
            ("gold", 0, 4, 16),
            ("bulk", 2, 1, 8),
            ("default", 1, 1, 0),
        ]


# ---------------------------------------------------------------------------
# brownout ladder: hold timers, hysteresis, appliers
# ---------------------------------------------------------------------------

STAGES = {
    "enabled": True,
    "hysteresis": 0.05,
    "holdSeconds": 2.0,
    "stages": [
        {"name": "shed_audit", "enterAbove": 0.85},
        {"name": "shed_parity", "enterAbove": 0.90},
        {"name": "shed_plan", "enterAbove": 0.95},
        {"name": "shed_low_priority", "enterAbove": 0.98},
    ],
}


class TestBrownoutLadder:
    def _ctl(self):
        ctl = BrownoutController(clock=lambda: 0.0)
        ctl.configure(STAGES)
        return ctl

    def test_enter_requires_hold(self):
        ctl = self._ctl()
        ctl.observe(0.86, now=0.0)
        ctl.observe(0.86, now=1.9)
        assert ctl.level() == 0
        ctl.observe(0.86, now=2.0)
        assert ctl.level() == 1
        assert ctl.active("shed_audit")
        assert ctl.stage_name() == "shed_audit"

    def test_hold_resets_when_score_dips(self):
        ctl = self._ctl()
        ctl.observe(0.86, now=0.0)
        ctl.observe(0.50, now=1.0)  # excursion breaks the hold
        ctl.observe(0.86, now=1.5)
        ctl.observe(0.86, now=3.0)  # only 1.5 s of continuous pressure
        assert ctl.level() == 0
        ctl.observe(0.86, now=3.5)
        assert ctl.level() == 1

    def test_one_stage_per_observation(self):
        ctl = self._ctl()
        # even a 0.99 spike walks the ladder one rung at a time, each rung
        # needing a fresh hold of ITS threshold
        t, levels = 0.0, []
        while ctl.level() < 4 and t < 20.0:
            ctl.observe(0.99, now=t)
            levels.append(ctl.level())
            t += 1.0
        assert ctl.level() == 4
        assert all(b - a <= 1 for a, b in zip(levels, levels[1:]))
        assert ctl.stage_name() == "shed_low_priority"

    def test_hysteresis_band_holds_the_stage(self):
        ctl = self._ctl()
        ctl.observe(0.86, now=0.0)
        ctl.observe(0.86, now=2.0)
        assert ctl.level() == 1
        # 0.82 is below enter (0.85) but above exit (0.80): stage holds
        for t in (3.0, 5.0, 9.0):
            ctl.observe(0.82, now=t)
        assert ctl.level() == 1
        # below the exit line, held for hold_s: stage releases
        ctl.observe(0.79, now=10.0)
        ctl.observe(0.79, now=12.0)
        assert ctl.level() == 0
        assert ctl.stage_name() == ""

    def test_oscillation_across_exit_line_never_flaps(self):
        ctl = self._ctl()
        ctl.observe(0.86, now=0.0)
        ctl.observe(0.86, now=2.0)
        assert ctl.level() == 1
        enters = ctl.m_transitions.get(("shed_audit", "enter"))
        exits = ctl.m_transitions.get(("shed_audit", "exit"))
        # flip between just-below-exit and inside-the-band faster than the
        # hold: the below-timer resets every other sample, so no exit fires
        t = 3.0
        for i in range(12):
            ctl.observe(0.79 if i % 2 == 0 else 0.83, now=t)
            t += 1.0
        assert ctl.level() == 1
        assert ctl.m_transitions.get(("shed_audit", "enter")) == enters
        assert ctl.m_transitions.get(("shed_audit", "exit")) == exits

    def test_appliers_fire_on_enter_and_exit(self):
        ctl = self._ctl()
        calls = []
        ctl.bind_applier("shed_audit", lambda engaged: calls.append(engaged))
        ctl.bind_applier("shed_parity", lambda engaged: calls.append(("parity", engaged)))
        ctl.observe(0.92, now=0.0)
        ctl.observe(0.92, now=2.0)  # enter shed_audit
        ctl.observe(0.92, now=4.0)  # parity's own hold starts here
        ctl.observe(0.92, now=6.0)  # enter shed_parity
        assert calls == [True, ("parity", True)]
        ctl.observe(0.70, now=7.0)
        ctl.observe(0.70, now=9.0)   # exit shed_parity
        ctl.observe(0.70, now=10.0)  # audit's own release hold starts here
        ctl.observe(0.70, now=12.0)  # exit shed_audit
        assert calls == [True, ("parity", True), ("parity", False), False]

    def test_broken_applier_never_wedges_the_ladder(self):
        ctl = self._ctl()

        def boom(engaged):
            raise RuntimeError("applier down")

        ctl.bind_applier("shed_audit", boom)
        ctl.observe(0.86, now=0.0)
        ctl.observe(0.86, now=2.0)
        assert ctl.level() == 1  # transition happened despite the applier

    def test_reset_and_reconfigure_release_engaged_stages(self):
        ctl = self._ctl()
        released = []
        ctl.bind_applier("shed_audit", lambda engaged: released.append(engaged))
        ctl.observe(0.86, now=0.0)
        ctl.observe(0.86, now=2.0)
        assert ctl.level() == 1
        ctl.reset()
        assert ctl.level() == 0
        assert released == [True, False]
        # a config reload with a stage engaged must not leave work shed
        ctl.observe(0.86, now=10.0)
        ctl.observe(0.86, now=12.0)
        ctl.configure(STAGES)
        assert ctl.level() == 0
        assert released == [True, False, True, False]

    def test_snapshot_shape(self):
        ctl = self._ctl()
        ctl.observe(0.86, now=0.0)
        ctl.observe(0.86, now=2.0)
        snap = ctl.snapshot()
        assert snap["enabled"] is True
        assert snap["level"] == 1
        assert snap["stage"] == "shed_audit"
        assert [s["name"] for s in snap["stages"]] == [
            "shed_audit",
            "shed_parity",
            "shed_plan",
            "shed_low_priority",
        ]
        assert snap["stages"][0]["engaged"] is True
        assert snap["stages"][0]["exit"] == pytest.approx(0.80)
        assert snap["stages"][1]["engaged"] is False

    def test_disabled_ladder_ignores_observations(self):
        ctl = BrownoutController(clock=lambda: 0.0)
        ctl.configure({"enabled": False, "stages": STAGES["stages"]})
        ctl.observe(1.0, now=0.0)
        ctl.observe(1.0, now=10.0)
        assert ctl.level() == 0


# ---------------------------------------------------------------------------
# pressure monitor: high-water edges + observers
# ---------------------------------------------------------------------------


class TestPressureEdges:
    def _mon(self):
        mon = PressureMonitor(clock=lambda: 0.0)
        mon.configure(enabled=True, window_s=30.0, interval_s=0.5)
        return mon

    def test_rising_and_falling_edges_record_flight_events(self):
        mon = self._mon()
        load = {"pair": (10, 10)}
        mon.bind(queue=lambda: load["pair"])
        high0 = _event_count("pressure_high")
        rec0 = _event_count("pressure_recovered")
        snap = mon.sample(now=0.0)
        assert snap["score"] >= HIGH_WATER
        assert _event_count("pressure_high") == high0 + 1
        assert _event_count("pressure_recovered") == rec0
        # still high: the edge fires once per excursion, not per sample
        mon.sample(now=1.0)
        assert _event_count("pressure_high") == high0 + 1
        # the queue component is a rolling p90: recovery needs the hot
        # samples to age out of the window
        load["pair"] = (0, 10)
        snap = mon.sample(now=40.0)
        assert snap["score"] < HIGH_WATER
        assert _event_count("pressure_recovered") == rec0 + 1
        # and the next excursion records a fresh rising edge
        load["pair"] = (10, 10)
        mon.sample(now=80.0)
        assert _event_count("pressure_high") == high0 + 2

    def test_observers_fire_with_score_components_and_now(self):
        mon = self._mon()
        mon.bind(queue=lambda: (5, 10))
        seen = []
        fn = lambda score, components, now: seen.append((score, components, now))
        mon.add_observer(fn)
        mon.add_observer(fn)  # identity dedup: wired once
        mon.sample(now=7.0)
        assert len(seen) == 1
        score, components, now = seen[0]
        assert now == 7.0
        assert score == components["queue"] == 0.5
        mon.remove_observer(fn)
        mon.sample(now=8.0)
        assert len(seen) == 1

    def test_broken_observer_never_breaks_sampling(self):
        mon = self._mon()

        def boom(score, components, now):
            raise RuntimeError("observer down")

        mon.add_observer(boom)
        snap = mon.sample(now=0.0)
        assert "score" in snap

    def test_unbind_clears_sources_and_observers(self):
        mon = self._mon()
        mon.bind(queue=lambda: (10, 10))
        seen = []
        mon.add_observer(lambda *a: seen.append(a))
        mon.sample(now=0.0)
        assert len(seen) == 1
        mon.unbind()
        snap = mon.sample(now=1.0)
        assert snap["score"] == 0.0
        assert len(seen) == 1


# ---------------------------------------------------------------------------
# the control loop end to end: pressure -> brownout -> audit shed
# ---------------------------------------------------------------------------


class TestPressureDrivesBrownout:
    def test_audit_shed_engages_and_recovers(self):
        from cerbos_tpu.audit.log import AuditLog

        class Backend:
            def __init__(self):
                self.entries = []

            def write(self, entry):
                self.entries.append(entry)

        mon = PressureMonitor(clock=lambda: 0.0)
        mon.configure(enabled=True, window_s=5.0)
        ctl = BrownoutController(clock=lambda: 0.0)
        ctl.configure(STAGES)
        mon.add_observer(ctl.observe)
        backend = Backend()
        log = AuditLog(backend=backend)
        try:
            ctl.bind_applier("shed_audit", log.set_shed)
            load = {"pair": (9, 10)}
            mon.bind(queue=lambda: load["pair"])
            shed0 = ctl.m_shed.get("audit")
            # 0.9 sustained past the hold engages shed_audit via the observer
            mon.sample(now=0.0)
            mon.sample(now=2.5)
            assert ctl.active("shed_audit")
            # writes are dropped at the door and counted as evidence; the
            # global controller owns the counter, but it is the same
            # registry instrument this ctl holds
            log.write_access("dropped-1", "check")
            assert ctl.m_shed.get("audit") == shed0 + 1
            # pressure falls, the hot window ages out, the stage releases
            load["pair"] = (0, 10)
            mon.sample(now=10.0)
            mon.sample(now=13.0)
            assert not ctl.active("shed_audit")
            log.write_access("kept-1", "check")
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                ids = [e.get("callId") for e in backend.entries]
                if "kept-1" in ids:
                    break
                time.sleep(0.01)
            ids = [e.get("callId") for e in backend.entries]
            assert "kept-1" in ids
            assert "dropped-1" not in ids
        finally:
            log.close()

    def test_shed_low_priority_stage_drives_admission(self):
        ctl = BrownoutController(clock=lambda: 0.0)
        ctl.configure(STAGES)
        adm = AdmissionController(clock=lambda: 0.0)
        adm.configure(
            {"enabled": True, "classes": [{"name": "bulk", "priority": 2}]}
        )
        ctl.bind_applier("shed_low_priority", adm.set_shed)
        bulk = adm.classes[0]
        adm.try_admit(bulk, now=0.0).release()
        # drive the full ladder: each rung needs its own hold
        t = 0.0
        for _ in range(9):
            ctl.observe(0.99, now=t)
            t += 2.0
        assert ctl.stage_name() == "shed_low_priority"
        with pytest.raises(OverloadRefused) as ei:
            adm.try_admit(bulk, now=t)
        assert ei.value.reason == "brownout"
        ctl.reset()
        adm.try_admit(bulk, now=t).release()


# ---------------------------------------------------------------------------
# weighted priority lanes
# ---------------------------------------------------------------------------


def _p(pclass: str = "") -> _Pending:
    return _Pending([], None, Future(), pclass=pclass)


class TestPriorityLanes:
    def test_unconfigured_is_plain_fifo(self):
        lanes = _PriorityLanes()
        items = [_p(), _p("unknown-class"), _p()]
        for it in items:
            lanes.append(it)
        assert len(lanes) == 3
        assert [lanes.popleft() for _ in range(3)] == items
        assert not lanes

    def test_strict_priority_preempts_across_bands(self):
        lanes = _PriorityLanes()
        lanes.configure([("gold", 0, 1, 0), ("bulk", 2, 1, 0), ("default", 1, 1, 0)])
        b1, g1, d1, g2 = _p("bulk"), _p("gold"), _p(""), _p("gold")
        for it in (b1, g1, d1, g2):
            lanes.append(it)
        # arrival order is bulk-first, but gold drains first, then default
        assert [lanes.popleft() for _ in range(4)] == [g1, g2, d1, b1]

    def test_smooth_wrr_within_a_band(self):
        lanes = _PriorityLanes()
        lanes.configure([("a", 0, 3, 0), ("b", 0, 1, 0), ("default", 1, 1, 0)])
        for _ in range(4):
            lanes.append(_p("a"))
        for _ in range(4):
            lanes.append(_p("b"))
        order = [lanes.popleft().pclass for _ in range(8)]
        # nginx-style smooth WRR at 3:1 interleaves instead of bursting,
        # then the exhausted lane's band-mate drains the tail
        assert order == ["a", "a", "b", "a", "a", "b", "b", "b"]

    def test_peek_agrees_with_popleft(self):
        lanes = _PriorityLanes()
        lanes.configure([("a", 0, 3, 0), ("b", 0, 2, 0), ("default", 1, 1, 0)])
        for cls in ("b", "a", "b", "a", "a"):
            lanes.append(_p(cls))
        while lanes:
            head = lanes.peek()
            assert lanes.popleft() is head

    def test_queue_budget_bounds_one_lane_only(self):
        lanes = _PriorityLanes()
        lanes.configure([("bulk", 2, 1, 2), ("default", 1, 1, 0)])
        assert not lanes.over_budget("bulk")
        lanes.append(_p("bulk"))
        lanes.append(_p("bulk"))
        assert lanes.over_budget("bulk")
        # the budget is per-lane: default stays open
        assert not lanes.over_budget("")
        lanes.popleft()
        assert not lanes.over_budget("bulk")

    def test_reconfigure_migrates_queued_items(self):
        lanes = _PriorityLanes()
        items = [_p("gold"), _p(""), _p("gone-class")]
        for it in items:
            lanes.append(it)
        lanes.configure([("gold", 0, 4, 0), ("default", 1, 1, 0)])
        assert len(lanes) == 3
        assert lanes.depths() == {"gold": 1, "default": 2}
        # gold preempts; the unknown class rode into default in FIFO order
        assert [lanes.popleft() for _ in range(3)] == [items[0], items[1], items[2]]

    def test_remove_and_clear(self):
        lanes = _PriorityLanes()
        lanes.configure([("gold", 0, 1, 0), ("default", 1, 1, 0)])
        a, b = _p("gold"), _p("")
        lanes.append(a)
        lanes.append(b)
        lanes.remove(a)
        assert len(lanes) == 1
        with pytest.raises(ValueError):
            lanes.remove(a)
        lanes.clear()
        assert len(lanes) == 0 and not lanes.depths()


# ---------------------------------------------------------------------------
# batcher integration: queue budgets refuse at the door
# ---------------------------------------------------------------------------

POLICY = """
apiVersion: api.cerbos.dev/v1
resourcePolicy:
  resource: album
  version: default
  rules:
    - actions: ["view"]
      effect: EFFECT_ALLOW
      roles: [user]
"""


def _plain_batcher(wrap=lambda ev: ev, **kw):
    from cerbos_tpu.compile import compile_policy_set
    from cerbos_tpu.policy.parser import parse_policies
    from cerbos_tpu.ruletable import build_rule_table, check_input

    rt = build_rule_table(compile_policy_set(list(parse_policies(POLICY))))

    class PlainEvaluator:
        rule_table = rt
        schema_mgr = None

        def check(self, inputs, params=None):
            return [check_input(rt, i, params or EvalParams()) for i in inputs]

    return BatchingEvaluator(wrap(PlainEvaluator()), **kw)


def _inp(i: int) -> CheckInput:
    return CheckInput(
        principal=Principal(id=f"u{i}", roles=["user"]),
        resource=Resource(kind="album", id=f"a{i}", attr={}),
        actions=["view"],
    )


class TestBatcherQueueBudget:
    def test_over_budget_lane_refuses_without_touching_the_ring(self):
        # a first flight held on the drain thread parks what is enqueued
        # behind it in the lanes, so the budget check sees a stable backlog
        batcher = _plain_batcher(wrap=FlightGate)
        gate = batcher.evaluator
        try:
            batcher.configure_lanes([("gold", 0, 4, 0), ("default", 1, 1, 1)])
            plug = gate.hold(batcher, [_inp(1000)])
            refusals0 = batcher.stats["lane_refusals"]
            mq0 = batcher.m_queue_budget.get("default")
            fut1 = batcher.check_async([_inp(0)])
            assert batcher.lane_depths() == {"default": 1}
            # the blocking path refuses instantly — no thread parked, the
            # pending never reaches the lane
            with pytest.raises(OverloadRefused) as ei:
                batcher.check([_inp(1)])
            assert ei.value.reason == "queue_budget"
            assert ei.value.retry_after == pytest.approx(0.1)
            # the async path settles the future with the ERR the IPC server
            # ships back to the front end
            fut2 = batcher.check_async([_inp(2)])
            with pytest.raises(_BatchFailed) as bf:
                fut2.result(timeout=5.0)
            assert bf.value.reason == "queue_budget"
            assert batcher.stats["lane_refusals"] == refusals0 + 2
            assert batcher.m_queue_budget.get("default") == mq0 + 2
            # the unbudgeted gold lane still admits
            fut3 = batcher.check_async([_inp(3)], pclass="gold")
            assert batcher.lane_depths() == {"gold": 1, "default": 1}
            gate.release()
            for fut in (plug, fut1, fut3):
                assert len(fut.result(timeout=10)) == 1
        finally:
            batcher.close()

    def test_wiring_from_admission_lane_confs(self):
        ctrl = AdmissionController(clock=lambda: 0.0)
        ctrl.configure(
            {
                "enabled": True,
                "classes": [{"name": "gold", "priority": 0, "weight": 4, "queueBudget": 2}],
            }
        )
        batcher = _plain_batcher(max_wait_ms=1.0)
        try:
            batcher.configure_lanes(ctrl.lane_confs())
            out = batcher.check([_inp(0)], pclass="gold")
            assert out[0].actions["view"].effect == "EFFECT_ALLOW"
        finally:
            batcher.close()


# ---------------------------------------------------------------------------
# pclass carriage over IPC
# ---------------------------------------------------------------------------


class TestCarrySpec:
    def test_pclass_rides_without_a_waterfall(self):
        assert RemoteBatcherClient._carry_spec(None, None) is None
        assert RemoteBatcherClient._carry_spec(None, "") is None
        assert RemoteBatcherClient._carry_spec(None, "gold") == (None, None, "gold")

    def test_pclass_appends_to_the_waterfall_carry(self):
        wf = Waterfall(t0=time.monotonic() - 0.25)
        spec = RemoteBatcherClient._carry_spec(wf, "gold")
        assert len(spec) == 3 and spec[2] == "gold"
        assert spec[0] == pytest.approx(0.25, abs=0.05)
        # no class: the pre-pclass 2-tuple carry, unchanged in shape
        bare = RemoteBatcherClient._carry_spec(wf, None)
        assert len(bare) == 2
        assert bare[0] == pytest.approx(spec[0], abs=0.05)

    def test_carry_survives_the_wire_codec_and_resume(self):
        wf = Waterfall(t0=time.monotonic() - 0.1)
        spec = RemoteBatcherClient._carry_spec(wf, "gold")
        wired = marshal.loads(marshal.dumps(spec))
        assert tuple(wired) == tuple(spec)
        # the batcher resumes the budget record by index reads, so extra
        # carry elements (the pclass) never break an older consumer
        resumed = Waterfall.from_carry(wired)
        assert resumed.age() == pytest.approx(0.1, abs=0.05)
        # class-only carry resumes no budget record and must not crash
        assert RemoteBatcherClient._carry_spec(None, "gold")[0] is None


# ---------------------------------------------------------------------------
# readiness surfaces the engaged stage
# ---------------------------------------------------------------------------


class TestReadinessBrownout:
    def test_snapshot_carries_stage_and_reason(self):
        rs = ReadinessState()
        stage = {"name": ""}
        rs.bind_brownout(lambda: stage["name"])
        snap = rs.snapshot()
        assert "brownout_stage" not in snap and snap.get("reason") is None
        stage["name"] = "shed_audit"
        snap = rs.snapshot()
        assert snap["brownout_stage"] == "shed_audit"
        assert snap["reason"] == "brownout"
        # brownout degrades the snapshot, never the serving gate
        assert snap["status"] == "ready"
        assert rs.serving()

    def test_provider_errors_read_as_no_stage(self):
        rs = ReadinessState()
        rs.bind_brownout(lambda: 1 / 0)
        assert "brownout_stage" not in rs.snapshot()


# ---------------------------------------------------------------------------
# metrics hygiene: families, help text, pooled-scrape plumbing
# ---------------------------------------------------------------------------

OVERLOAD_FAMILIES = {
    "cerbos_tpu_admission_total": (obs.CounterVec, ("pclass", "outcome")),
    "cerbos_tpu_admission_inflight": (obs.GaugeVec, "pclass"),
    "cerbos_tpu_admission_refusal_seconds": (obs.Histogram, None),
    "cerbos_tpu_admission_queue_budget_total": (obs.CounterVec, "pclass"),
    "cerbos_tpu_brownout_stage": (obs.Gauge, None),
    "cerbos_tpu_brownout_transitions_total": (obs.CounterVec, ("stage", "direction")),
    "cerbos_tpu_brownout_shed_total": (obs.CounterVec, "target"),
}


class TestMetricsHygiene:
    def test_overload_families_registered_with_help_and_labels(self):
        # the module-global controllers register the admission/brownout
        # families at import; the queue-budget counter registers with the
        # first batcher (constructed by the suite above either way)
        _plain_batcher(max_wait_ms=1.0).close()
        inst = obs.metrics().instruments()
        for name, (klass, label) in OVERLOAD_FAMILIES.items():
            assert name in inst, name
            m = inst[name]
            assert isinstance(m, klass), name
            assert re.fullmatch(r"cerbos_tpu_[a-z0-9_]+", name)
            assert m.help and len(m.help) > 10, name
            if label is not None:
                assert m.label == label, name

    def test_rendered_families_relabel_and_merge_for_pooled_scrapes(self):
        ctrl = AdmissionController(clock=lambda: 0.0)
        ctrl.configure({"enabled": True, "classes": [{"name": "gold"}]})
        ctrl.try_admit(ctrl.classes[0], now=0.0).release()
        text = obs.metrics().render()
        for name in OVERLOAD_FAMILIES:
            assert f"# TYPE {name} " in text, name
        # worker pools stamp each process's scrape with its identity before
        # merging: every admission sample line gains the worker label
        w0 = obs.relabel_metrics_text(text, "worker", "w0")
        for line in w0.splitlines():
            if line.startswith("cerbos_tpu_admission_total"):
                assert 'worker="w0"' in line, line
        merged = obs.merge_metrics_texts(w0, obs.relabel_metrics_text(text, "worker", "w1"))
        # family metadata appears once; both workers' samples survive
        assert merged.count("# TYPE cerbos_tpu_admission_total counter") == 1
        admitted = [
            line
            for line in merged.splitlines()
            if line.startswith("cerbos_tpu_admission_total")
            and 'pclass="gold"' in line
            and 'outcome="admitted"' in line
        ]
        assert {('worker="w0"' in line, 'worker="w1"' in line) for line in admitted} == {
            (True, False),
            (False, True),
        }

    def test_refusal_latency_histogram_observes(self):
        ctrl = AdmissionController(clock=lambda: 0.0)
        _, total0, count0 = ctrl.m_refusal_seconds.snapshot()
        ctrl.observe_refusal(0.002)
        ctrl.observe_refusal(-1.0)  # clamped, never negative
        _, total, count = ctrl.m_refusal_seconds.snapshot()
        assert count == count0 + 2
        assert total == pytest.approx(total0 + 0.002)


# ---------------------------------------------------------------------------
# shipped defaults keep the subsystem dormant until configured
# ---------------------------------------------------------------------------


class TestShippedDefaults:
    def test_default_overload_block_compiles_to_disabled_admission(self):
        from cerbos_tpu.config import DEFAULTS

        conf = DEFAULTS["overload"]
        ctrl = AdmissionController(clock=lambda: 0.0)
        ctrl.configure(conf)
        # no classes, no default caps: the front door stays wide open
        assert ctrl.enabled is False
        # while the brownout ladder arms with the documented stages
        ctl = BrownoutController(clock=lambda: 0.0)
        ctl.configure(conf["brownout"])
        assert ctl.enabled is True
        assert [s.name for s in ctl.stages] == [
            "shed_audit",
            "shed_parity",
            "shed_plan",
            "shed_low_priority",
        ]
        assert ctl.hold_s == 2.0
        assert ctl.stages[0].exit == pytest.approx(0.80)
