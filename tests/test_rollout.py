"""Safe policy rollout drills (docs/ROBUSTNESS.md, "Safe policy rollout").

Proves the acceptance criteria of the rollout tentpole: every swap is a
staged build → lower → gate → cutover → canary ladder; cutovers are
epoch-versioned and barrier-atomic (zero lost requests, zero mixed-epoch
decisions under continuous traffic); a gate-rejected bundle never serves a
request; a poisoned bundle is auto-rolled back by the canary; the committed
epoch propagates over the ticket queue to front ends within bounded skew;
and the `swap_fail:STAGE` knob injects failures at exactly one stage.
"""

import os
import threading
import time

import pytest

from cerbos_tpu.compile import compile_policy_set
from cerbos_tpu.engine import CheckInput, EvalParams, Principal, Resource
from cerbos_tpu.engine import rollout as rollout_mod
from cerbos_tpu.engine import types as T
from cerbos_tpu.engine.batcher import BatchingEvaluator
from cerbos_tpu.engine.faults import parse_fault_spec
from cerbos_tpu.engine.rollout import (
    EPOCH_ATTR,
    OUTCOME_FAILED,
    OUTCOME_REJECTED,
    OUTCOME_ROLLED_BACK,
    OUTCOME_SERVING,
    RolloutController,
    SwapBarrier,
    bundle_hash_of,
    epoch_of,
)
from cerbos_tpu.policy.parser import parse_policies
from cerbos_tpu.ruletable import build_rule_table, check_input

pytestmark = pytest.mark.rollout

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
    - actions: ["*"]
      effect: EFFECT_ALLOW
      roles: [admin]
"""

# the same policy with the user rule flipped to a deny: a legitimate (if
# dramatic) policy change whose behavior diff the gate's replay must surface
POLICY_V2 = POLICY.replace("effect: EFFECT_ALLOW\n      roles: [user]", "effect: EFFECT_DENY\n      roles: [user]")

# runtime.effectiveDerivedRoles membership is oracle-only by construction
# (tests/test_analyze.py) — the bundle `failOn: oracle-only` must reject
ORACLE_ONLY_POLICY = """
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
          expr: '"admin" in runtime.effectiveDerivedRoles'
"""


def table(src: str = POLICY):
    return build_rule_table(compile_policy_set(list(parse_policies(src))))


def inp(i: int, **attr) -> CheckInput:
    return CheckInput(
        principal=Principal(id=f"u{i}", roles=["user"]),
        resource=Resource(
            kind="album",
            id=f"a{i}",
            attr={"owner": f"u{i}", "public": False, **attr},
        ),
        actions=["view"],
    )


def wait_for(cond, timeout=10.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


class OracleEvaluator:
    """Minimal evaluator backed by the CPU oracle (as in test_chaos)."""

    def __init__(self, rt):
        self.rule_table = rt
        self.schema_mgr = None
        self.stats = {"device_inputs": 0}

    def check(self, inputs, params=None):
        return [check_input(self.rule_table, i, params or EvalParams()) for i in inputs]

    def submit(self, inputs, params=None):
        self.stats["device_inputs"] += len(inputs)
        return self.check(inputs, params)

    def collect(self, ticket):
        return ticket


class FakeManager:
    """RuleTableManager stand-in: `policy_text` is "the store"; build_table
    compiles it fresh and commit_table publishes, like the real thing."""

    def __init__(self, policy_text: str = POLICY):
        self.policy_text = policy_text
        self.rule_table = table(policy_text)
        self.commits: list = []

    def build_table(self):
        return table(self.policy_text)

    def commit_table(self, rt):
        self.rule_table = rt
        self.commits.append(rt)


class FakeSentinel:
    """The slice of ParitySentinel the controller reads: the stats dict the
    canary baselines, the recent-input ring the gate replays, set_boost."""

    def __init__(self, inputs=None):
        self.stats = {"divergences": 0, "storms": 0, "checks": 0}
        self._recent = list(inputs or [])
        self.boosts: list = []

    def recent_inputs(self):
        return list(self._recent)

    def set_boost(self, rate, duration_s):
        self.boosts.append((rate, duration_s))


def make_ctl(manager=None, sentinel=None, lanes=None, **conf):
    # the canary consults the process-global pressure monitor, which other
    # suites (brownout, overload) saturate; keep module tests hermetic by
    # defaulting the pressure trigger out of reach
    conf.setdefault("rollbackAt", 9.9)
    ctl = RolloutController(
        manager if manager is not None else FakeManager(),
        conf=conf,
        sentinel=sentinel,
    )
    if lanes is not None:
        ctl.bind_lanes(lanes)
    ctl.seed(ctl.manager.rule_table)
    return ctl


class TestFaultSpec:
    def test_swap_fail_grammar(self):
        assert parse_fault_spec("swap_fail:gate") == {"swap_fail": "gate"}
        assert parse_fault_spec("swap_fail:build,shard:1") == {"swap_fail": "build", "shard": 1}

    @pytest.mark.parametrize("stage", ["build", "lower", "gate", "canary"])
    def test_all_stages_accepted(self, stage):
        assert parse_fault_spec(f"swap_fail:{stage}")["swap_fail"] == stage

    def test_unknown_stage_rejected(self):
        with pytest.raises(ValueError):
            parse_fault_spec("swap_fail:bogus")


class TestSwapBarrier:
    def test_no_lanes_is_trivially_parked(self):
        b = SwapBarrier(timeout_s=0.2)
        assert b.start([]) is True
        assert not b.timed_out
        b.release()

    def test_parks_and_releases_live_lanes(self):
        b = SwapBarrier(timeout_s=5.0)
        parked_at = []
        released_at = []

        class Lane:
            def request_swap(self, barrier):
                def drain():
                    parked_at.append(time.monotonic())
                    barrier.park(self)
                    released_at.append(time.monotonic())

                threading.Thread(target=drain, daemon=True).start()
                return True

        lanes = [Lane(), Lane()]
        assert b.start(lanes) is True
        assert b.expected == 2
        assert len(parked_at) == 2
        assert not released_at  # stopped world: lanes hold until release
        b.release()
        assert wait_for(lambda: len(released_at) == 2)

    def test_wedged_lane_cannot_hold_cutover_hostage(self):
        b = SwapBarrier(timeout_s=0.2)

        class WedgedLane:
            def request_swap(self, barrier):
                return True  # accepts, never parks

        t0 = time.monotonic()
        assert b.start([WedgedLane()]) is False
        assert b.timed_out
        assert time.monotonic() - t0 < 2.0
        b.release()

    def test_dead_lane_is_not_counted(self):
        b = SwapBarrier(timeout_s=0.5)

        class DeadLane:
            def request_swap(self, barrier):
                return False

        assert b.start([DeadLane()]) is True
        assert b.expected == 0


class TestEpochIdentity:
    def test_seed_stamps_epoch_one(self):
        ctl = make_ctl()
        assert ctl.epoch.number == 1
        assert ctl.epoch.source == "boot"
        assert epoch_of(ctl.manager.rule_table) == 1

    def test_bundle_hash_is_content_stable(self):
        assert bundle_hash_of(table()) == bundle_hash_of(table())
        assert bundle_hash_of(table()) != bundle_hash_of(table(POLICY_V2))
        assert len(bundle_hash_of(table())) == 16

    def test_never_committed_table_has_no_epoch(self):
        assert epoch_of(table()) is None


class TestStagedRollout:
    def test_good_swap_walks_the_ladder(self):
        mgr = FakeManager()
        ctl = make_ctl(mgr)
        seen = []
        ctl.subscribe("probe", lambda ep: seen.append(ep))
        mgr.policy_text = POLICY_V2
        run = ctl.run_rollout(trigger="test")
        assert run.outcome == OUTCOME_SERVING
        assert (run.from_epoch, run.to_epoch) == (1, 2)
        by_stage = {s["stage"]: s["status"] for s in run.stages}
        assert by_stage == {
            "build": "ok",
            "lower": "ok",
            "gate": "ok",
            "cutover": "ok",
            "canary": "skipped",
        }
        assert ctl.epoch.number == 2
        assert epoch_of(mgr.rule_table) == 2
        assert mgr.commits and mgr.commits[-1] is ctl.epoch.rule_table
        assert [ep.number for ep in seen] == [2]
        assert run.bundle_hash == bundle_hash_of(mgr.rule_table)
        # the displaced epoch stays resident for rollback
        assert [e.number for e in ctl.history] == [1]

    def test_gate_rejects_oracle_only_bundle(self):
        mgr = FakeManager()
        ctl = make_ctl(mgr, failOn="oracle-only")
        old_table = mgr.rule_table
        mgr.policy_text = ORACLE_ONLY_POLICY
        run = ctl.run_rollout(trigger="test")
        assert run.outcome == OUTCOME_REJECTED
        assert run.error == "analyzer:oracle-only"
        # the rejected bundle never became the serving table
        assert mgr.rule_table is old_table
        assert not mgr.commits
        assert ctl.epoch.number == 1
        gate = run.to_dict()["gate"]
        assert gate["fail_on"] == "oracle-only"
        assert gate["findings"], "rejection must carry reason-coded findings"
        assert all({"code", "severity", "message"} <= set(f) for f in gate["findings"])
        # live analysis objects never leak into the serialized report
        assert "_analysis_report" not in gate

    def test_replay_surfaces_behavior_diffs(self):
        owner_view = inp(3)  # owner matches -> ALLOW under v1, DENY under v2
        mgr = FakeManager()
        ctl = make_ctl(mgr, sentinel=FakeSentinel([owner_view]))
        mgr.policy_text = POLICY_V2
        run = ctl.run_rollout(trigger="test")
        assert run.outcome == OUTCOME_SERVING  # a diff is news, not an error
        replay = run.gate["replay"]
        assert replay["replayed"] == 1
        assert replay["diffs"] == 1
        assert replay["samples"][0]["principal"] == "u3"

    def test_require_ack_turns_diffs_into_rejection(self):
        mgr = FakeManager()
        ctl = make_ctl(mgr, sentinel=FakeSentinel([inp(3)]), requireAck=True)
        mgr.policy_text = POLICY_V2
        run = ctl.run_rollout(trigger="test")
        assert run.outcome == OUTCOME_REJECTED
        assert run.error == "diffs_require_ack:1"
        assert ctl.epoch.number == 1
        assert not mgr.commits

    @pytest.mark.parametrize("stage", ["build", "lower", "gate"])
    def test_swap_fail_knob_fails_exactly_that_stage(self, stage):
        mgr = FakeManager()
        ctl = RolloutController(mgr, conf={}, faults=parse_fault_spec(f"swap_fail:{stage}"))
        ctl.seed(mgr.rule_table)
        mgr.policy_text = POLICY_V2
        run = ctl.run_rollout(trigger="test")
        assert run.outcome == OUTCOME_FAILED
        assert f"swap_fail:{stage}" in run.error
        failed = [s for s in run.stages if s["status"] == "failed"]
        assert [s["stage"] for s in failed] == [stage]
        assert ctl.epoch.number == 1  # last valid epoch kept serving
        assert not mgr.commits

    def test_operator_rollback_and_epoch_numbers_never_reused(self):
        mgr = FakeManager()
        ctl = make_ctl(mgr)
        mgr.policy_text = POLICY_V2
        assert ctl.run_rollout(trigger="test").to_epoch == 2
        report = ctl.rollback(reason="operator")
        assert report["outcome"] == OUTCOME_ROLLED_BACK
        assert ctl.epoch.number == 1
        assert ctl.epoch.source == "rollback"
        assert epoch_of(mgr.rule_table) == 1
        # the next rollout takes the next UNUSED number — 2 is burned
        mgr.policy_text = POLICY
        assert ctl.run_rollout(trigger="test").to_epoch == 3

    def test_rollback_without_resident_history_is_refused(self):
        ctl = make_ctl()
        assert ctl.rollback(reason="operator") is None
        assert ctl.epoch.number == 1

    def test_failing_subscriber_never_tears_the_commit(self):
        mgr = FakeManager()
        ctl = make_ctl(mgr)
        after = []
        ctl.subscribe("bad", lambda ep: (_ for _ in ()).throw(RuntimeError("boom")))
        ctl.subscribe("good", lambda ep: after.append(ep.number))
        mgr.policy_text = POLICY_V2
        run = ctl.run_rollout(trigger="test")
        assert run.outcome == OUTCOME_SERVING
        assert after == [2]  # later subscribers still ran

    def test_wait_report_blocks_until_terminal(self):
        mgr = FakeManager()
        ctl = make_ctl(mgr)
        gen = ctl.generation
        mgr.policy_text = POLICY_V2
        done = []
        t = threading.Thread(target=lambda: done.append(ctl.wait_report(gen, timeout=10.0)))
        t.start()
        ctl.run_rollout(trigger="test")
        t.join(timeout=10.0)
        assert done and done[0]["outcome"] == OUTCOME_SERVING
        assert done[0]["to_epoch"] == 2
        # nothing newer than the latest generation: bounded timeout, None
        assert ctl.wait_report(ctl.generation, timeout=0.1) is None

    def test_snapshot_shape(self):
        ctl = make_ctl(lanes=[])
        snap = ctl.snapshot()
        assert snap["mode"] == "full"
        assert snap["epoch"]["epoch"] == 1
        assert set(snap) == {"mode", "epoch", "history", "lanes", "runs", "config"}
        assert snap["config"]["enabled"] is True

    def test_disabled_controller_swaps_without_gate(self):
        mgr = FakeManager()
        ctl = make_ctl(mgr, enabled=False)
        mgr.policy_text = POLICY_V2
        run = ctl.run_rollout(trigger="test")
        assert run.outcome == OUTCOME_SERVING
        by_stage = {s["stage"]: s["status"] for s in run.stages}
        assert by_stage["lower"] == "skipped"
        assert by_stage["gate"] == "skipped"
        assert ctl.epoch.number == 2  # still epoch-versioned and atomic


class TestCanary:
    def test_fresh_divergence_triggers_auto_rollback(self):
        mgr = FakeManager()
        sent = FakeSentinel()
        ctl = make_ctl(mgr, sentinel=sent, canarySec=30, canaryPollMs=10, canaryBoost=4.0)
        try:
            mgr.policy_text = POLICY_V2
            run = ctl.run_rollout(trigger="test")
            assert ctl.epoch.number == 2  # cutover done, canary holding
            assert not run.terminal
            assert sent.boosts == [(4.0, 30.0)]
            sent.stats["divergences"] += 1
            assert run.wait(10.0)
            assert run.outcome == OUTCOME_ROLLED_BACK
            assert run.canary["trigger"] == "parity_divergence:1"
            assert ctl.epoch.number == 1
            assert ctl.epoch.source == "rollback"
            assert epoch_of(mgr.rule_table) == 1
        finally:
            ctl.close()

    def test_quiet_canary_passes(self):
        mgr = FakeManager()
        ctl = make_ctl(mgr, sentinel=FakeSentinel(), canarySec=0.2, canaryPollMs=10)
        try:
            mgr.policy_text = POLICY_V2
            run = ctl.run_rollout(trigger="test")
            assert run.wait(10.0)
            assert run.outcome == OUTCOME_SERVING
            assert run.canary["result"] == "pass"
            assert ctl.epoch.number == 2
        finally:
            ctl.close()

    def test_swap_fail_canary_knob_drills_the_rollback_path(self):
        mgr = FakeManager()
        ctl = RolloutController(
            mgr,
            conf={"canarySec": 30, "canaryPollMs": 10},
            faults=parse_fault_spec("swap_fail:canary"),
        )
        ctl.seed(mgr.rule_table)
        try:
            mgr.policy_text = POLICY_V2
            run = ctl.run_rollout(trigger="test")
            assert run.wait(10.0)
            assert run.outcome == OUTCOME_ROLLED_BACK
            assert run.canary["trigger"] == "fault:swap_fail:canary"
            assert ctl.epoch.number == 1
        finally:
            ctl.close()

    def test_new_rollout_supersedes_the_canary_hold(self):
        mgr = FakeManager()
        ctl = make_ctl(mgr, sentinel=FakeSentinel(), canarySec=30, canaryPollMs=10)
        try:
            mgr.policy_text = POLICY_V2
            first = ctl.run_rollout(trigger="test")
            assert not first.terminal
            mgr.policy_text = POLICY
            second = ctl.run_rollout(trigger="test")
            assert first.wait(10.0)
            assert first.outcome == OUTCOME_SERVING
            assert first.canary["result"] == "superseded"
            assert second.to_epoch == 3
        finally:
            ctl.close()


class TestAtomicCutoverUnderTraffic:
    def test_zero_lost_zero_mixed_epoch_with_live_lane(self):
        """Continuous traffic through a real batcher lane across repeated
        cutovers: every request is answered, every decision carries exactly
        one epoch, and the effect each decision reports is the one its
        epoch's table produces — no request spans two tables."""
        mgr = FakeManager()
        ev = OracleEvaluator(mgr.rule_table)
        lane = BatchingEvaluator(ev, max_wait_ms=1.0)
        ctl = make_ctl(mgr, lanes=[lane])
        ctl.subscribe("evaluator", lambda ep: setattr(ev, "rule_table", ep.rule_table))
        stop = threading.Event()
        decisions: list[tuple] = []
        errors: list = []

        def traffic():
            k = 0
            while not stop.is_set():
                k += 1
                try:
                    out = lane.check([inp(3)])  # owner view: v1 ALLOW / v2 DENY
                    decisions.append((T.current_epoch(), out[0].actions["view"].effect))
                except Exception as e:  # noqa: BLE001 — a lost request fails the drill
                    errors.append(e)

        threads = [threading.Thread(target=traffic, daemon=True) for _ in range(4)]
        try:
            for t in threads:
                t.start()
            wait_for(lambda: len(decisions) > 20)
            for text in (POLICY_V2, POLICY, POLICY_V2):
                mgr.policy_text = text
                run = ctl.run_rollout(trigger="test")
                assert run.outcome == OUTCOME_SERVING
                wait_for(lambda n=len(decisions): len(decisions) > n + 20)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=5.0)
            lane.close()
            ctl.close()

        assert not errors, errors[:3]
        assert all(ep is not None for ep, _ in decisions)
        # atomicity: one epoch -> exactly one behavior, and it is the
        # behavior that epoch's policy text defines
        effect_by_epoch = {}
        for ep, effect in decisions:
            effect_by_epoch.setdefault(ep, set()).add(effect)
        assert all(len(v) == 1 for v in effect_by_epoch.values()), effect_by_epoch
        expected = {1: "EFFECT_ALLOW", 2: "EFFECT_DENY", 3: "EFFECT_ALLOW", 4: "EFFECT_DENY"}
        for ep, effects in effect_by_epoch.items():
            assert effects == {expected[ep]}, (ep, effects)
        assert set(effect_by_epoch) >= {1, 4}  # saw first and last epoch
        assert lane.epoch == 4

    def test_sharded_pool_cuts_over_all_lanes(self):
        from cerbos_tpu.engine.shards import build_shard_pool
        from cerbos_tpu.tpu.evaluator import TpuEvaluator

        mgr = FakeManager()
        base = TpuEvaluator(mgr.rule_table, use_jax=False, min_device_batch=1)
        pool = build_shard_pool(
            base, n_shards=2, routing="round_robin", max_wait_ms=0.0, request_timeout_s=10.0
        )
        ctl = make_ctl(mgr, lanes=pool.swap_lanes())

        def swap_evaluator(ep):
            base.rule_table = ep.rule_table
            base.lowered.table = ep.rule_table
            base.refresh()

        ctl.subscribe("evaluator", swap_evaluator)
        ctl.subscribe("shards", lambda ep: pool.refresh_shards(ep.rule_table))
        try:
            before = [pool.check([inp(3)])[0].actions["view"].effect for _ in range(4)]
            assert set(before) == {"EFFECT_ALLOW"}
            mgr.policy_text = POLICY_V2
            run = ctl.run_rollout(trigger="test")
            assert run.outcome == OUTCOME_SERVING
            # both lanes stamped — round-robin hits each shard
            assert [lane.epoch for lane in pool.swap_lanes()] == [2, 2]
            after = [pool.check([inp(3)])[0].actions["view"].effect for _ in range(4)]
            assert set(after) == {"EFFECT_DENY"}
        finally:
            ctl.close()
            pool.close()


class TestIpcEpochPropagation:
    def test_two_frontends_converge_within_bounded_skew(self, tmp_path):
        """`--frontends 2 --shards 2` shape, in-process: the committed epoch
        rides the STATUS frames from a sharded pool's process; both front
        ends observe the cutover within a couple of status-poll intervals,
        and their decisions stamp the batcher's epoch."""
        from cerbos_tpu.engine.ipc import BatcherIpcServer, RemoteBatcherClient
        from cerbos_tpu.engine.shards import build_shard_pool
        from cerbos_tpu.tpu.evaluator import TpuEvaluator

        mgr = FakeManager()
        base = TpuEvaluator(mgr.rule_table, use_jax=False, min_device_batch=1)
        pool = build_shard_pool(
            base, n_shards=2, routing="round_robin", max_wait_ms=1.0, request_timeout_s=10.0
        )
        ctl = make_ctl(mgr, lanes=pool.swap_lanes())

        def swap_evaluator(ep):
            base.rule_table = ep.rule_table
            base.lowered.table = ep.rule_table
            base.refresh()

        ctl.subscribe("evaluator", swap_evaluator)
        ctl.subscribe("shards", lambda ep: pool.refresh_shards(ep.rule_table))
        poll_s = 0.05
        server = BatcherIpcServer(
            str(tmp_path / "batcher.sock"),
            pool,
            readiness=lambda: {"status": "ready", **ctl.epoch_info()},
        )
        server.start()
        clients = [
            RemoteBatcherClient(
                server.socket_path,
                mgr.rule_table,
                request_timeout_s=10.0,
                worker_label=f"fe{i}",
                status_poll_s=poll_s,
                connect_retry_s=0.05,
            )
            for i in range(2)
        ]
        ctl.subscribe("clients", lambda ep: [c.refresh_table(ep.rule_table) for c in clients])

        def client_epoch(c):
            last = c._last_status or {}
            return last.get("policy_epoch")

        try:
            assert wait_for(lambda: all(client_epoch(c) == 1 for c in clients))
            mgr.policy_text = POLICY_V2
            run = ctl.run_rollout(trigger="test")
            assert run.outcome == OUTCOME_SERVING
            t0 = time.monotonic()
            assert wait_for(lambda: all(client_epoch(c) == 2 for c in clients), timeout=5.0)
            skew = time.monotonic() - t0
            assert skew < poll_s * 20 + 1.0, f"unbounded cutover skew: {skew:.3f}s"
            assert [lane.epoch for lane in pool.swap_lanes()] == [2, 2]
            for c in clients:
                out = c.check([inp(3)])
                assert out[0].actions["view"].effect == "EFFECT_DENY"
                assert T.current_epoch() == 2
        finally:
            for c in clients:
                c.close()
            server.close()
            pool.close()
            ctl.close()


class TestBootstrapIntegration:
    def _boot(self, tmp_path, policy=POLICY, overrides=()):
        from cerbos_tpu.bootstrap import initialize
        from cerbos_tpu.config import Config

        (tmp_path / "album.yaml").write_text(policy)
        config = Config.load(overrides=[f"storage.disk.directory={tmp_path}", *overrides])
        return initialize(config)

    def _rewrite(self, tmp_path, core, policy):
        path = tmp_path / "album.yaml"
        path.write_text(policy)
        # defeat mtime granularity so the disk store's change scan sees it
        bump = time.time() + 5
        os.utime(path, (bump, bump))
        core.store.check_for_changes()

    def test_storage_event_runs_a_staged_rollout(self, tmp_path):
        core = self._boot(tmp_path)
        try:
            ctl = core.rollout
            assert ctl is not None and ctl.mode == "full"
            assert ctl.epoch.number == 1
            assert "engine" in ctl.subscribers
            out = core.engine.check([inp(3)])
            assert out[0].actions["view"].effect == "EFFECT_ALLOW"
            assert T.current_epoch() == 1

            self._rewrite(tmp_path, core, POLICY_V2)
            assert ctl.epoch.number == 2
            run = ctl.runs[-1]
            assert run.outcome == OUTCOME_SERVING
            assert run.trigger == "storage"
            out = core.engine.check([inp(3)])
            assert out[0].actions["view"].effect == "EFFECT_DENY"
            assert T.current_epoch() == 2
            info = ctl.epoch_info()
            assert info["policy_epoch"] == 2
            assert info["policy_epoch_committed_at"] > 0
        finally:
            core.close()

    def test_gate_rejected_bundle_never_serves_a_request(self, tmp_path):
        core = self._boot(tmp_path, overrides=["engine.tpu.rollout.failOn=oracle-only"])
        try:
            ctl = core.rollout
            gen = ctl.generation
            self._rewrite(tmp_path, core, ORACLE_ONLY_POLICY)
            report = ctl.wait_report(gen, timeout=30.0)
            assert report is not None
            assert report["outcome"] == OUTCOME_REJECTED
            assert report["error"] == "analyzer:oracle-only"
            assert report["gate"]["findings"]
            # still serving epoch 1 with epoch-1 behavior
            assert ctl.epoch.number == 1
            out = core.engine.check([inp(3)])
            assert out[0].actions["view"].effect == "EFFECT_ALLOW"
            assert T.current_epoch() == 1
        finally:
            core.close()

    def test_poisoned_device_path_rolls_back_in_canary(self, tmp_path, monkeypatch):
        """The acceptance drill: the device path flips effects silently
        (flip_effect:1.0); the gate's CPU-side replay cannot see it, the
        cutover happens, and the canary's boosted sentinel sampling catches
        the divergence and rolls back — zero lost requests."""
        monkeypatch.setenv("CERBOS_TPU_FAULTS", "flip_effect:1.0")
        core = self._boot(
            tmp_path,
            overrides=[
                "engine.tpu.rollout.canarySec=20",
                "engine.tpu.rollout.canaryPollMs=20",
                "engine.tpu.rollout.canaryBoost=100",
                "engine.tpu.paritySentinel.sampleRate=1.0",
                "engine.tpu.paritySentinel.stormThreshold=1000",
                # the drill's one-input requests have to ride the device path
                # that is poisoned; under the threshold they never would (PR 30)
                "engine.tpu.minDeviceBatch=1",
            ],
        )
        try:
            ctl = core.rollout
            batcher = core.engine.tpu_evaluator
            self._rewrite(tmp_path, core, POLICY_V2)
            run = ctl.runs[-1]
            assert run.to_epoch == 2
            answered = 0
            deadline = time.monotonic() + 30.0
            while not run.terminal and time.monotonic() < deadline:
                answered += len(batcher.check([inp(answered)]))
                time.sleep(0.01)
            assert run.terminal, "canary never resolved"
            assert run.outcome == OUTCOME_ROLLED_BACK
            assert run.canary["trigger"].startswith("parity_")
            assert answered > 0  # traffic flowed throughout; none lost
            assert ctl.epoch.number == 1
            assert ctl.epoch.source == "rollback"
        finally:
            core.close()


class TestCtlReportRendering:
    def test_print_rollout_report_renders_stages_and_findings(self, capsys):
        from cerbos_tpu.ctl import _print_rollout_report

        _print_rollout_report(
            {
                "generation": 3,
                "trigger": "storage",
                "outcome": OUTCOME_REJECTED,
                "from_epoch": 1,
                "to_epoch": None,
                "bundle_hash": "abcd1234",
                "stages": [
                    {"stage": "build", "status": "ok", "seconds": 0.5},
                    {"stage": "gate", "status": "rejected", "seconds": 0.1, "reason": "analyzer:oracle-only"},
                ],
                "gate": {
                    "analysis": {"classes": {"oracle-only": 1}},
                    "findings": [
                        {
                            "severity": "error",
                            "code": "operand_unsupported",
                            "policy": "album",
                            "rule": "r1",
                            "message": "oracle-only condition",
                        }
                    ],
                    "replay": {"replayed": 4, "diffs": 1, "errors": 0, "samples": []},
                },
                "canary": {},
                "error": "analyzer:oracle-only",
            }
        )
        out = capsys.readouterr().out
        assert "build" in out and "gate" in out
        assert "rejected" in out
        assert "operand_unsupported" in out
        assert "outcome: rejected" in out

    def test_module_handle_mirrors_bootstrap(self):
        ctl = make_ctl()
        rollout_mod.install(ctl)
        try:
            assert rollout_mod.active() is ctl
        finally:
            rollout_mod.install(None)


class TestDiskStoreReload:
    """Operator `store reload` must rescan the directory before notifying:
    the base EVENT_RELOAD contract rebuilds from the store's cached
    snapshot, so an admin-triggered rollout would gate and serve the STALE
    bundle (the on-disk edit only landing at the next watch poll — or never
    with watching disabled)."""

    def _store(self, tmp_path):
        from cerbos_tpu.storage.disk import DiskStore

        (tmp_path / "album.yaml").write_text(POLICY)
        return DiskStore(str(tmp_path), watch_for_changes=False)

    def test_reload_picks_up_disk_edits_without_a_watcher(self, tmp_path):
        store = self._store(tmp_path)
        events: list = []
        store.subscribe(lambda evs: events.extend(evs))
        old_hash = bundle_hash_of(build_rule_table(compile_policy_set(store.get_all())))

        path = tmp_path / "album.yaml"
        path.write_text(POLICY_V2)
        os.utime(path, (time.time() + 5, time.time() + 5))
        store.reload()

        assert events and events[0].kind == "ADD_OR_UPDATE"
        new_hash = bundle_hash_of(build_rule_table(compile_policy_set(store.get_all())))
        assert new_hash != old_hash  # subscribers rebuild what is on disk NOW

    def test_unchanged_reload_still_fires_the_full_rebuild_signal(self, tmp_path):
        store = self._store(tmp_path)
        events: list = []
        store.subscribe(lambda evs: events.extend(evs))
        store.reload()
        # `reload --wait` needs a rollout run to report on even when the
        # directory is unchanged
        assert [e.kind for e in events] == ["RELOAD"]
