"""Sharded serving pool: routing, per-shard fault domains, recovery.

The tentpole acceptance drill lives here: a shard-scoped fault
(``submit_raise:1.0,shard:0`` through the engine/faults.py grammar) trips
ONLY that shard's breaker; the router keeps traffic on the remaining lanes
with zero lost requests, and recovery half-opens only the sick shard.
"""

import concurrent.futures
import time

import pytest

from cerbos_tpu.compile import compile_policy_set
from cerbos_tpu.engine import CheckInput, EvalParams, Principal, Resource
from cerbos_tpu.engine.faults import FaultInjector
from cerbos_tpu.engine.shards import build_shard_pool
from cerbos_tpu.observability import metrics
from cerbos_tpu.policy.parser import parse_policies
from cerbos_tpu.ruletable import build_rule_table, check_input
from cerbos_tpu.tpu.evaluator import TpuEvaluator

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
    )


def effects(outs):
    return [{a: (e.effect, e.policy) for a, e in o.actions.items()} for o in outs]


def oracle(rt, inputs, params=None):
    return [check_input(rt, i, params or EvalParams()) for i in inputs]


def numpy_pool(rt, n_shards=4, fault_spec="", breaker_conf=None, **kw):
    """A pool over the numpy backend — fast, no device needed, but the full
    shard topology (clones, per-lane breakers, router) is real."""
    base = TpuEvaluator(rt, use_jax=False, min_device_batch=1)
    return build_shard_pool(
        base,
        n_shards=n_shards,
        max_wait_ms=kw.pop("max_wait_ms", 0.0),
        request_timeout_s=kw.pop("request_timeout_s", 10.0),
        fault_spec=fault_spec,
        breaker_conf=breaker_conf or {},
        **kw,
    )


class TestPoolTopology:
    def test_clone_per_shard_shares_lowered_table(self):
        rt = table()
        pool = numpy_pool(rt, n_shards=4)
        try:
            assert len(pool.shards) == 4
            evs = [lane.evaluator for lane in pool.shards]
            assert len({id(e) for e in evs}) == 4  # distinct clones
            base_lowered = evs[0].lowered
            assert all(e.lowered is base_lowered for e in evs)  # shared lowering
            assert all(e.rule_table is rt for e in evs)
            # per-shard mutable state is NOT shared
            assert len({id(e.packer) for e in evs}) == 4
            assert [lane.shard_id for lane in pool.shards] == [0, 1, 2, 3]
        finally:
            pool.close()

    def test_parity_and_balanced_routing(self):
        rt = table()
        pool = numpy_pool(rt, n_shards=4)
        reqs = [[inp(i)] for i in range(32)]
        try:
            with concurrent.futures.ThreadPoolExecutor(max_workers=8) as ex:
                futs = [ex.submit(pool.check, r) for r in reqs]
                outs = [f.result(timeout=15)[0] for f in futs]
            assert effects(outs) == effects(oracle(rt, [r[0] for r in reqs]))
            assert sum(pool.routed) == 32
            assert all(c > 0 for c in pool.routed)  # every lane took traffic
            assert pool.routing_imbalance() < 4.0
        finally:
            pool.close()

    def test_round_robin_routing_is_even(self):
        rt = table()
        pool = numpy_pool(rt, n_shards=4, routing="round_robin")
        try:
            for i in range(16):
                pool.check([inp(i)])
            assert pool.routed == [4, 4, 4, 4]
            assert pool.routing_imbalance() == 1.0
        finally:
            pool.close()

    def test_pool_stats_aggregate_lane_stats(self):
        rt = table()
        pool = numpy_pool(rt, n_shards=2)
        try:
            for i in range(8):
                pool.check([inp(i)])
            stats = pool.stats
            assert stats["batched_requests"] == sum(
                lane.stats["batched_requests"] for lane in pool.shards
            )
            assert stats["routed"] == pool.routed
            assert [lane.health.state for lane in pool.shards] == ["closed", "closed"]
        finally:
            pool.close()

    def test_refresh_shards_points_every_clone_at_new_table(self):
        rt = table()
        pool = numpy_pool(rt, n_shards=3, fault_spec="seed:1")  # injector-wrapped lanes
        rt2 = table()
        try:
            pool.refresh_shards(rt2)
            for lane in pool.shards:
                ev = getattr(lane.evaluator, "_ev", lane.evaluator)
                assert ev.rule_table is rt2  # the REAL evaluator, not the wrapper
        finally:
            pool.close()

    def test_health_state_aggregates_for_readiness(self):
        rt = table()
        pool = numpy_pool(rt, n_shards=3, breaker_conf={"failureThreshold": 1, "probeBackoffBaseMs": 600000})
        try:
            assert pool.health_state() == "closed"
            # one sick lane is a capacity event, not an availability event
            pool.shards[0].health.record_failure()
            assert pool.shards[0].health.state == "open"
            assert pool.health_state() == "closed"
            # every lane open -> the pool reports open
            for lane in pool.shards[1:]:
                lane.health.record_failure()
            assert pool.health_state() == "open"
        finally:
            pool.close()

    def test_shard_labeled_metric_families_render(self):
        rt = table()
        pool = numpy_pool(rt, n_shards=2)
        try:
            for i in range(6):
                pool.check([inp(i)])
            text = metrics().render()
            for fam in ("cerbos_tpu_batcher_inflight", "cerbos_tpu_batch_occupancy", "cerbos_tpu_breaker_state"):
                assert f'{fam}{{shard="0"}}' in text, fam
                assert f'{fam}{{shard="1"}}' in text, fam
            assert 'cerbos_tpu_batch_stage_seconds_bucket{stage="pack",shard=' in text
        finally:
            pool.close()


@pytest.mark.chaos
class TestShardFaultDomain:
    def test_shard_scoped_fault_trips_only_that_lane(self):
        """Acceptance drill: shard 0 faults at 100%; ONLY its breaker trips,
        the router keeps serving on the other lanes, and every request gets
        a correct answer — zero lost requests."""
        rt = table()
        pool = numpy_pool(
            rt,
            n_shards=4,
            fault_spec="submit_raise:1.0,shard:0",
            breaker_conf={"failureThreshold": 2, "probeBackoffBaseMs": 600000},
        )
        reqs = [[inp(i)] for i in range(60)]
        try:
            # only lane 0 carries the injector
            assert isinstance(pool.shards[0].evaluator, FaultInjector)
            assert not any(isinstance(l.evaluator, FaultInjector) for l in pool.shards[1:])
            with concurrent.futures.ThreadPoolExecutor(max_workers=8) as ex:
                futs = [ex.submit(pool.check, r) for r in reqs]
                outs = [f.result(timeout=20)[0] for f in futs]  # nothing raises, nothing hangs
            # zero lost requests, all bit-exact vs the oracle
            assert effects(outs) == effects(oracle(rt, [r[0] for r in reqs]))
            # fault domain: exactly the sick shard's breaker tripped
            assert pool.shards[0].health.state == "open"
            assert pool.shards[0].health.stats["trips"] == 1
            for lane in pool.shards[1:]:
                assert lane.health.state == "closed"
                assert lane.health.stats["trips"] == 0
            # service continued at (N-1)/N: healthy lanes did real device batches
            healthy_batches = sum(l.stats["batches"] for l in pool.shards[1:])
            assert healthy_batches > 0
            # the pool is still "available" for readiness purposes
            assert pool.health_state() == "closed"
            # once open, the router steers admission off the sick lane
            routed_before = pool.routed[0]
            for i in range(12):
                pool.check([inp(100 + i)])
            assert pool.routed[0] == routed_before
        finally:
            pool.close()

    def test_recovery_half_opens_only_the_sick_shard(self):
        rt = table()
        pool = numpy_pool(
            rt,
            n_shards=3,
            fault_spec="submit_raise:1.0,shard:0",
            breaker_conf={
                "failureThreshold": 1,
                "probeBackoffBaseMs": 20,
                "probeBackoffCapMs": 100,
            },
        )
        try:
            # trip lane 0: route to it directly so the injector fires
            sick = pool.shards[0]
            for i in range(3):
                sick.check([inp(i)])
            assert sick.health.state == "open"
            # the device heals (chaos drill flips the fault off at runtime)
            sick.evaluator.spec.pop("submit_raise")
            deadline = time.monotonic() + 10.0
            while sick.health.state != "closed" and time.monotonic() < deadline:
                # pool traffic: the router's probe trickle donates inputs
                pool.check([inp(1)])
                time.sleep(0.01)
            assert sick.health.state == "closed"
            assert sick.health.stats["probes"] >= 1
            # the healthy lanes never probed or tripped — recovery was scoped
            for lane in pool.shards[1:]:
                assert lane.health.stats["trips"] == 0
                assert lane.health.stats["probes"] == 0
            # live traffic is back on the recovered lane's device path
            before = sick.stats["batches"]
            sick.check([inp(5)])
            assert sick.stats["batches"] == before + 1
        finally:
            pool.close()

    def test_unscoped_fault_spec_wraps_every_lane(self):
        rt = table()
        pool = numpy_pool(rt, n_shards=3, fault_spec="seed:9")
        try:
            assert all(isinstance(l.evaluator, FaultInjector) for l in pool.shards)
        finally:
            pool.close()


@pytest.mark.multichip
class TestDeviceMeshPool:
    """The jax path over the virtual 8-device mesh (conftest forces
    --xla_force_host_platform_device_count=8 in-process)."""

    def _jax_pool(self, rt, **kw):
        import jax

        if len(jax.devices()) < 2:
            pytest.skip("virtual multi-device mesh unavailable")
        base = TpuEvaluator(rt, use_jax=True, min_device_batch=2)
        return build_shard_pool(base, max_wait_ms=1.0, **kw), base

    def test_one_lane_per_device_with_pinning(self):
        import jax

        rt = table()
        pool, base = self._jax_pool(rt)
        try:
            devices = jax.devices()
            assert len(pool.shards) == len(devices)
            pinned = [lane.evaluator.device for lane in pool.shards]
            assert pinned == devices  # one lane per device, in order
        finally:
            pool.close()

    def test_mesh_parity_and_per_lane_flight_records(self):
        from cerbos_tpu.engine.flight import recorder

        rt = table()
        pool, base = self._jax_pool(rt)
        reqs = [[inp(i), inp(i + 100)] for i in range(24)]
        try:
            with concurrent.futures.ThreadPoolExecutor(max_workers=8) as ex:
                futs = [ex.submit(pool.check, r) for r in reqs]
                outs = [f.result(timeout=60) for f in futs]
            flat_in = [i for r in reqs for i in r]
            flat_out = [o for ro in outs for o in ro]
            assert effects(flat_out) == effects(oracle(rt, flat_in))
            # the flight recorder can replay a single lane's history
            busy = [i for i, c in enumerate(pool.routed) if c > 0]
            assert busy, pool.routed
            lane_records = recorder().lane(busy[0])
            assert lane_records and all(r.get("shard") == busy[0] for r in lane_records)
        finally:
            pool.close()
