"""Chaos suite for the device-path fault domain (docs/ROBUSTNESS.md).

Uses the FaultInjector to inject deterministic device failures and proves
the acceptance criteria of the robustness tentpole: the breaker opens
within its failure threshold and keeps latency off the 30s timeout path; a
poison input degrades only itself; deadlines drop dead requests; a dead
drain loop fails fast; and degraded-mode decisions stay bit-exact vs the
CPU oracle.
"""

import concurrent.futures
import time
from collections import deque
from concurrent.futures import Future

import pytest

from cerbos_tpu.compile import compile_policy_set
from cerbos_tpu.engine import CheckInput, EvalParams, Principal, Resource
from cerbos_tpu.engine import batcher as batcher_mod
from cerbos_tpu.engine.batcher import BatchingEvaluator, DeadlineExceeded, _Pending
from cerbos_tpu.engine.faults import DeviceFault, FaultInjector, parse_fault_spec
from cerbos_tpu.engine.health import DeviceHealth
from cerbos_tpu.observability import metrics
from cerbos_tpu.policy.parser import parse_policies
from cerbos_tpu.ruletable import build_rule_table, check_input
from flightgate import FlightGate

pytestmark = pytest.mark.chaos

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


class OracleEvaluator:
    """Minimal streaming evaluator backed by the CPU oracle — lets the
    chaos tests exercise the batcher's fault handling without jax."""

    def __init__(self, rt):
        self.rule_table = rt
        self.schema_mgr = None
        self.stats = {"device_inputs": 0}

    def check(self, inputs, params=None):
        return oracle(self.rule_table, inputs, params)

    def submit(self, inputs, params=None):
        self.stats["device_inputs"] += len(inputs)
        return self.check(inputs, params)

    def collect(self, ticket):
        return ticket


def p99(latencies):
    ordered = sorted(latencies)
    return ordered[min(len(ordered) - 1, int(len(ordered) * 0.99))]


class TestFaultSpec:
    def test_grammar(self):
        assert parse_fault_spec(
            "submit_raise:0.5, collect_delay_ms:200,wedge_after:50,poison_attr:bad,seed:42"
        ) == {
            "submit_raise": 0.5,
            "collect_delay_ms": 200,
            "wedge_after": 50,
            "poison_attr": "bad",
            "seed": 42,
        }
        assert parse_fault_spec("") == {}
        assert parse_fault_spec(None) == {}

    @pytest.mark.parametrize("bad", ["bogus:1", "submit_raise", "submit_raise:", ":0.5"])
    def test_rejects_malformed(self, bad):
        with pytest.raises(ValueError):
            parse_fault_spec(bad)

    def test_probabilistic_injection_is_deterministic(self):
        rt = table()

        def outcomes():
            inj = FaultInjector(OracleEvaluator(rt), "submit_raise:0.5,seed:7")
            pattern = []
            for i in range(32):
                try:
                    inj.submit([inp(i)])
                    pattern.append(True)
                except DeviceFault:
                    pattern.append(False)
            return pattern

        first, second = outcomes(), outcomes()
        assert first == second
        assert True in first and False in first  # 0.5 actually fires both ways

    def test_delegates_to_wrapped_evaluator(self):
        rt = table()
        inj = FaultInjector(OracleEvaluator(rt), "")
        assert inj.rule_table is rt
        assert effects(inj.check([inp(0)])) == effects(oracle(rt, [inp(0)]))


class TestDeviceHealth:
    def test_trip_probe_reclose_cycle(self):
        clk = [0.0]
        h = DeviceHealth(
            failure_threshold=2,
            probe_backoff_base_s=1.0,
            probe_backoff_cap_s=8.0,
            probe_timeout_s=5.0,
            clock=lambda: clk[0],
        )
        assert h.allow_device()
        h.record_failure()
        assert h.state == "closed"
        h.record_failure()
        assert h.state == "open" and not h.allow_device()
        assert h.stats["trips"] == 1
        assert h.should_probe() is None  # backoff (1s) not elapsed
        clk[0] = 1.1
        tok = h.should_probe()
        assert tok is not None and h.state == "half_open"
        assert h.should_probe() is None  # one probe at a time
        h.probe_failed(tok)
        assert h.state == "open"
        clk[0] = 2.0
        assert h.should_probe() is None  # second backoff doubled to 2s
        clk[0] = 3.2
        tok2 = h.should_probe()
        assert tok2 is not None
        h.probe_succeeded(tok2)
        assert h.state == "closed" and h.allow_device()

    def test_success_resets_consecutive_failures(self):
        h = DeviceHealth(failure_threshold=3)
        h.record_failure()
        h.record_failure()
        h.record_success()
        h.record_failure()
        h.record_failure()
        assert h.state == "closed"

    def test_timeout_rate_trip(self):
        clk = [0.0]
        h = DeviceHealth(
            timeout_rate_threshold=0.5, timeout_min_samples=4, clock=lambda: clk[0]
        )
        h.record_success()
        h.record_success()
        h.record_timeout()
        assert h.state == "closed"  # 1/3 below min samples + rate
        h.record_timeout()
        assert h.state == "open"  # 2/4 hits the 50% rate
        assert h.stats["trips"] == 1

    def test_wedged_probe_expires_and_reopens(self):
        clk = [0.0]
        h = DeviceHealth(
            failure_threshold=1,
            probe_backoff_base_s=1.0,
            probe_timeout_s=2.0,
            clock=lambda: clk[0],
        )
        h.record_failure()
        clk[0] = 1.5
        tok = h.should_probe()
        assert tok is not None and h.state == "half_open"
        clk[0] = 4.0  # probe never reported back: expire it
        assert h.state == "open"
        h.probe_succeeded(tok)  # the wedged probe's late result is stale
        assert h.state == "open"

    def test_disabled_never_trips(self):
        h = DeviceHealth(failure_threshold=1, enabled=False)
        for _ in range(10):
            h.record_failure()
            h.record_timeout()
        assert h.allow_device() and h.should_probe() is None


class TestBreakerServing:
    def test_breaker_opens_and_skips_device_wait(self):
        """Acceptance: at 100% submit_raise the breaker opens within the
        failure threshold and faulted p99 stays < 2x the healthy p99 (no
        request rides out the request timeout once open)."""
        rt = table()
        healthy = BatchingEvaluator(
            OracleEvaluator(rt), max_wait_ms=0.0, request_timeout_s=30.0
        )
        lat_healthy = []
        try:
            for i in range(40):
                t0 = time.perf_counter()
                healthy.check([inp(i)])
                lat_healthy.append(time.perf_counter() - t0)
        finally:
            healthy.close()

        health = DeviceHealth(failure_threshold=3, probe_backoff_base_s=60.0)
        inj = FaultInjector(OracleEvaluator(rt), "submit_raise:1.0")
        batcher = BatchingEvaluator(
            inj, max_wait_ms=0.0, request_timeout_s=30.0, health=health
        )
        lat_faulted = []
        results = []
        try:
            for i in range(40):
                t0 = time.perf_counter()
                results.append(batcher.check([inp(i)])[0])
                lat_faulted.append(time.perf_counter() - t0)
        finally:
            batcher.close()

        assert health.state == "open"
        assert health.stats["trips"] == 1
        # breaker opened within the threshold: only the first few requests
        # ever reached the (raising) device
        assert batcher.stats["batch_errors"] <= health.failure_threshold
        fallbacks = metrics().counter_vec("cerbos_tpu_batcher_oracle_fallbacks_total")
        assert fallbacks.get("breaker_open") >= 40 - health.failure_threshold
        # every decision still correct
        assert effects(results) == effects(oracle(rt, [inp(i) for i in range(40)]))
        # latency acceptance (floor guards timer noise on tiny absolute values)
        assert p99(lat_faulted) < max(2 * p99(lat_healthy), 0.25), (
            p99(lat_faulted),
            p99(lat_healthy),
        )

    def test_breaker_recloses_via_probe(self):
        rt = table()
        health = DeviceHealth(
            failure_threshold=2, probe_backoff_base_s=0.02, probe_backoff_cap_s=0.1
        )
        inj = FaultInjector(OracleEvaluator(rt), "submit_raise:1.0")
        batcher = BatchingEvaluator(
            inj, max_wait_ms=0.0, request_timeout_s=5.0, health=health
        )
        try:
            for i in range(4):
                batcher.check([inp(i)])
            assert health.state == "open"
            inj.spec.pop("submit_raise")  # the device heals
            deadline = time.monotonic() + 10.0
            while health.state != "closed" and time.monotonic() < deadline:
                batcher.check([inp(1)])  # oracle-served; donates probe inputs
                time.sleep(0.01)
            assert health.state == "closed"
            assert health.stats["probes"] >= 1
            # live traffic is back on the device path
            before = batcher.stats["batches"]
            out = batcher.check([inp(2)])
            assert batcher.stats["batches"] == before + 1
            assert effects(out) == effects(oracle(rt, [inp(2)]))
        finally:
            batcher.close()


class TestPoisonQuarantine:
    def test_poison_degrades_only_itself(self):
        """Acceptance: a poison input fails its batch, but co-batched
        requests all get correct answers (never an error), and the poison is
        bisected out and quarantined."""
        rt = table()
        gate = FlightGate(FaultInjector(OracleEvaluator(rt), "poison_attr:poison"))
        health = DeviceHealth(failure_threshold=100)  # keep the breaker out of this test
        batcher = BatchingEvaluator(gate, request_timeout_s=10.0, health=health)
        poison = inp(99, poison=True)
        goods = [inp(i) for i in range(8)]
        try:
            # a concurrent burst behind a flight in progress, so poison and
            # innocents co-batch
            plug = gate.hold(batcher, [inp(1000)])
            with concurrent.futures.ThreadPoolExecutor(max_workers=9) as pool:
                good_futs = [pool.submit(batcher.check, [g]) for g in goods]
                poison_fut = pool.submit(batcher.check, [poison])
                gate.release(batcher, queued=9)
                good_results = [f.result(timeout=15)[0] for f in good_futs]
                poison_result = poison_fut.result(timeout=15)
            assert len(plug.result(timeout=15)) == 1
            assert batcher.stats["batches"] == 1  # the plug's; the burst was one flight, and it failed
            # nobody errored, everybody is bit-exact vs the oracle
            assert effects(good_results) == effects(oracle(rt, goods))
            assert effects(poison_result) == effects(oracle(rt, [poison]))
            assert batcher.stats["batch_errors"] >= 1
            # the off-path bisect identifies and quarantines exactly the poison
            deadline = time.monotonic() + 10.0
            while batcher.stats["quarantined"] < 1 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert batcher.stats["quarantined"] == 1
            assert metrics().counter("cerbos_tpu_batcher_quarantined_total").value >= 1
            # re-requesting the poison bypasses batching entirely...
            before = batcher.stats["batches"]
            out = batcher.check([poison])
            assert batcher.stats["batches"] == before
            assert effects(out) == effects(oracle(rt, [poison]))
            fallbacks = metrics().counter_vec("cerbos_tpu_batcher_oracle_fallbacks_total")
            assert fallbacks.get("quarantine") >= 1
            # ...while innocents still ride the device path
            out2 = batcher.check([goods[0]])
            assert batcher.stats["batches"] == before + 1
            assert effects(out2) == effects(oracle(rt, [goods[0]]))
        finally:
            batcher.close()

    def test_whole_device_failure_quarantines_nothing(self):
        """When every sub-batch fails (device down, not poison), the bisect
        must not quarantine innocent inputs."""
        rt = table()
        gate = FlightGate(FaultInjector(OracleEvaluator(rt), "submit_raise:1.0,check_raise:1.0"))
        health = DeviceHealth(failure_threshold=100)
        batcher = BatchingEvaluator(gate, request_timeout_s=10.0, health=health)
        try:
            # four requests in one flight, so the bisect has siblings to try
            plug = gate.hold(batcher, [inp(1000)])
            with concurrent.futures.ThreadPoolExecutor(max_workers=4) as pool:
                futs = [pool.submit(batcher.check, [inp(i)]) for i in range(4)]
                gate.release(batcher, queued=4)
                results = [f.result(timeout=15)[0] for f in futs]
            assert plug.exception(timeout=15) is not None  # its flight failed like every other
            assert effects(results) == effects(oracle(rt, [inp(i) for i in range(4)]))
            assert batcher.stats["batch_errors"] == 2  # the plug's flight and ONE flight of four
            # give the bisect thread a beat, then confirm it stayed silent
            deadline = time.monotonic() + 2.0
            while batcher._bisect_busy and time.monotonic() < deadline:
                time.sleep(0.02)
            assert batcher.stats["quarantined"] == 0
        finally:
            batcher.close()

    def test_quarantine_set_is_bounded(self):
        rt = table()
        batcher = BatchingEvaluator(OracleEvaluator(rt), quarantine_max=4)
        try:
            for i in range(10):
                batcher._quarantine_add(inp(i))
            assert len(batcher._quarantine) == 4
            assert batcher.stats["quarantined"] == 10
            # oldest evicted, newest kept
            assert not batcher._has_quarantined([inp(0)])
            assert batcher._has_quarantined([inp(9)])
        finally:
            batcher.close()


class TestDeadlines:
    def test_already_expired_request_is_dropped(self):
        rt = table()
        batcher = BatchingEvaluator(OracleEvaluator(rt))
        try:
            with pytest.raises(DeadlineExceeded):
                batcher.check([inp(0)], deadline=time.monotonic() - 0.01)
            assert batcher.stats["deadline_drops"] == 1
            assert metrics().counter("cerbos_tpu_batcher_deadline_drops_total").value >= 1
        finally:
            batcher.close()

    def test_expired_while_queued_dropped_at_drain(self):
        """White-box: an already-expired _Pending in the queue is settled
        with DeadlineExceeded at drain time, not submitted to the device."""
        rt = table()
        ev = OracleEvaluator(rt)
        batcher = BatchingEvaluator(ev, max_wait_ms=0.0)
        try:
            fut: Future = Future()
            stale = _Pending([inp(0)], None, fut, deadline=time.monotonic() - 1.0)
            with batcher._wakeup:
                batcher._queue.append(stale)
                batcher._wakeup.notify()
            with pytest.raises(DeadlineExceeded):
                fut.result(timeout=5)
            assert batcher.stats["deadline_drops"] == 1
            assert ev.stats["device_inputs"] == 0  # no device work spent on it
        finally:
            batcher.close()

    def test_deadline_clamps_wait_on_wedged_device(self):
        """A request with a short deadline against a wedged device raises
        DEADLINE_EXCEEDED at its own deadline, not at the 30s timeout."""
        rt = table()

        class WedgedEvaluator(OracleEvaluator):
            def submit(self, inputs, params=None):
                time.sleep(1.0)
                return super().submit(inputs, params)

        batcher = BatchingEvaluator(
            WedgedEvaluator(rt), max_wait_ms=0.0, request_timeout_s=30.0
        )
        try:
            t0 = time.perf_counter()
            with pytest.raises(DeadlineExceeded):
                batcher.check([inp(0)], deadline=time.monotonic() + 0.1)
            assert time.perf_counter() - t0 < 1.0
        finally:
            batcher.close()

    def test_grpc_deadline_maps_to_deadline_exceeded(self):
        """An expired client deadline surfaces as gRPC DEADLINE_EXCEEDED."""
        import grpc

        from cerbos_tpu.engine.engine import Engine
        from cerbos_tpu.server.server import _grpc_rpcs
        from cerbos_tpu.server.service import CerbosService

        rt = table()
        batcher = BatchingEvaluator(OracleEvaluator(rt))
        engine = Engine(rt, tpu_evaluator=batcher, tpu_batch_threshold=1)
        svc = CerbosService(engine)
        handler = _grpc_rpcs(svc)["CheckResources"].unary_unary

        from cerbos_tpu.api.cerbos.request.v1 import request_pb2

        req = request_pb2.CheckResourcesRequest(request_id="d-1")
        p = req.principal
        p.id = "u1"
        p.roles.append("user")
        entry = req.resources.add()
        entry.actions.append("view")
        entry.resource.kind = "album"
        entry.resource.id = "a1"

        class Ctx:
            def __init__(self, remaining):
                self.code = None
                self._remaining = remaining

            def time_remaining(self):
                return self._remaining

            def abort(self, code, details):
                self.code = code
                raise RuntimeError(details)

        try:
            ctx = Ctx(remaining=-0.5)  # client deadline already expired
            with pytest.raises(RuntimeError):
                handler(req, ctx)
            assert ctx.code == grpc.StatusCode.DEADLINE_EXCEEDED
            ctx_ok = Ctx(remaining=30.0)
            resp = handler(req, ctx_ok)
            if isinstance(resp, bytes):  # written by the native codec; a message where it is not loaded
                from cerbos_tpu.api.cerbos.response.v1 import response_pb2

                resp = response_pb2.CheckResourcesResponse.FromString(resp)
            assert ctx_ok.code is None and resp.results
        finally:
            batcher.close()


class TestWatchdogAndShutdown:
    def test_drain_loop_death_fails_fast(self):
        """If the drain loop dies (BaseException out of submit), in-drain
        waiters settle immediately and later requests skip the dead thread —
        nothing hangs until the request timeout."""
        rt = table()

        class _Die(BaseException):
            pass

        class KillerEvaluator(OracleEvaluator):
            def submit(self, inputs, params=None):
                raise _Die("drain loop killed")

        batcher = BatchingEvaluator(
            KillerEvaluator(rt), max_wait_ms=0.0, request_timeout_s=30.0
        )
        try:
            t0 = time.perf_counter()
            out = batcher.check([inp(0)])
            assert time.perf_counter() - t0 < 5.0
            assert effects(out) == effects(oracle(rt, [inp(0)]))
            batcher._thread.join(timeout=5)
            assert not batcher._thread.is_alive()
            assert batcher._dead is not None
            # new requests detect the dead thread and go straight to the oracle
            out2 = batcher.check([inp(1)])
            assert effects(out2) == effects(oracle(rt, [inp(1)]))
            fallbacks = metrics().counter_vec("cerbos_tpu_batcher_oracle_fallbacks_total")
            assert fallbacks.get("batcher_dead") >= 2
        finally:
            batcher.close()

    def test_close_settles_queued_requests(self):
        """Satellite bug fix: close() under load must not strand queued
        waiters for the full request timeout."""
        rt = table()

        class SlowEvaluator(OracleEvaluator):
            def check(self, inputs, params=None):
                time.sleep(0.2)
                return super().check(inputs, params)

            submit = None  # force the sync ready-ticket path (blocks the drain loop)

        batcher = BatchingEvaluator(
            SlowEvaluator(rt), max_wait_ms=0.0, request_timeout_s=30.0
        )
        inputs = [inp(i) for i in range(12)]
        with concurrent.futures.ThreadPoolExecutor(max_workers=12) as pool:
            futs = [pool.submit(batcher.check, [inputs[0]])]
            time.sleep(0.05)  # drain loop is now sleeping inside check()
            futs += [pool.submit(batcher.check, [i]) for i in inputs[1:]]
            time.sleep(0.05)  # stragglers are queued behind the busy drain
            t0 = time.perf_counter()
            batcher.close()
            results = [f.result(timeout=10)[0] for f in futs]
            elapsed = time.perf_counter() - t0
        assert elapsed < 10.0, "queued waiters must settle at close, not at timeout"
        assert effects(results) == effects(oracle(rt, inputs))
        fallbacks = metrics().counter_vec("cerbos_tpu_batcher_oracle_fallbacks_total")
        assert fallbacks.get("shutdown") >= 1

    def test_queue_is_a_deque(self):
        """Satellite perf nit: O(1) popleft instead of list.pop(0) — the
        priority lanes keep one deque per lane."""
        rt = table()
        batcher = BatchingEvaluator(OracleEvaluator(rt))
        try:
            assert all(isinstance(lane.q, deque) for lane in batcher._queue._order)
            assert batcher._queue.depths() == {}
        finally:
            batcher.close()

    def test_oracle_import_is_hoisted(self):
        """Satellite: check_input is a module-level import, not re-imported
        on every timeout fallback."""
        assert hasattr(batcher_mod, "check_input")


class TestBootstrapWiring:
    def test_env_fault_spec_wires_injector_and_breaker(self, tmp_path, monkeypatch):
        """CERBOS_TPU_FAULTS wraps the device evaluator in a FaultInjector
        and the configured breaker trips under it — full bootstrap path."""
        from cerbos_tpu.bootstrap import initialize
        from cerbos_tpu.config import Config

        (tmp_path / "album.yaml").write_text(POLICY)
        monkeypatch.setenv("CERBOS_TPU_FAULTS", "submit_raise:1.0")
        # minDeviceBatch=1: a one-input request has to reach submit() to meet the
        # fault; under the threshold it is answered before the queue (PR 30)
        config = Config.load(
            overrides=[f"storage.disk.directory={tmp_path}", "engine.tpu.minDeviceBatch=1"]
        )
        core = initialize(config)
        try:
            batcher = core.engine.tpu_evaluator
            assert isinstance(batcher, BatchingEvaluator)
            assert isinstance(batcher.evaluator, FaultInjector)
            assert batcher.health is not None and batcher.health.enabled
            i = inp(0)
            for _ in range(batcher.health.failure_threshold + 2):
                out = batcher.check([i])
                assert effects(out) == effects(oracle(batcher.evaluator.rule_table, [i]))
            assert batcher.health.state == "open"
        finally:
            core.close()


class TestDegradedModeParity:
    def test_degraded_mode_parity(self):
        """Acceptance: every degraded-mode decision (CPU-oracle fallback) is
        bit-exact vs the device path on the same inputs."""
        from cerbos_tpu.tpu import TpuEvaluator
        from cerbos_tpu.util import bench_corpus

        rt = build_rule_table(
            compile_policy_set(list(parse_policies(bench_corpus.corpus_yaml(8))))
        )
        ev = TpuEvaluator(rt, use_jax=True, min_device_batch=4)
        batcher = BatchingEvaluator(ev, max_wait_ms=0.0)
        inputs = bench_corpus.requests(256, 8)
        params = EvalParams()
        try:
            device = ev.check(list(inputs), params)
            degraded = batcher._serve_oracle(inputs, params, "parity_test")
        finally:
            batcher.close()
        for i, (g, w) in enumerate(zip(device, degraded)):
            assert {a: (e.effect, e.policy, e.scope) for a, e in g.actions.items()} == {
                a: (e.effect, e.policy, e.scope) for a, e in w.actions.items()
            }, f"effect mismatch for input {i}: {inputs[i]}"
            assert g.effective_derived_roles == w.effective_derived_roles, i
            assert g.effective_policies == w.effective_policies, i
            assert sorted((o.src, o.action, repr(o.val)) for o in g.outputs) == sorted(
                (o.src, o.action, repr(o.val)) for o in w.outputs
            ), i

    def test_batch_error_fallback_is_bit_exact(self):
        """The batch_error recovery path (the one production hits when a
        batch dies) returns the same decisions the healthy path would."""
        rt = table()
        inj = FaultInjector(OracleEvaluator(rt), "submit_raise:1.0")
        health = DeviceHealth(failure_threshold=100)
        batcher = BatchingEvaluator(inj, max_wait_ms=0.0, health=health)
        inputs = [inp(i) for i in range(16)]
        try:
            got = [batcher.check([i])[0] for i in inputs]
        finally:
            batcher.close()
        assert effects(got) == effects(oracle(rt, inputs))
        fallbacks = metrics().counter_vec("cerbos_tpu_batcher_oracle_fallbacks_total")
        assert fallbacks.get("batch_error") >= 16
