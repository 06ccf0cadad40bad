"""Parity sentinel: shadow-oracle sampling, divergence capture, storm policy.

The sentinel's contract (engine/sentinel.py): deterministically sample
completed device batches, replay them on the CPU oracle off the hot path,
compare effect rows bit-exactly, capture divergences into a replayable
corpus, and promote divergence storms into the lane breaker so traffic
rides the oracle (correct-over-fast). The acceptance drill — silent effect
corruption via the ``flip_effect`` fault knob detected in every serving
topology — runs here at the unit level for the single batcher, the IPC
front door, and the sharded pool.
"""

import json
import threading
import time

import pytest

from cerbos_tpu.audit.log import AuditLog, _entry_from_decision
from cerbos_tpu.compile import compile_policy_set
from cerbos_tpu.engine import CheckInput, EvalParams, Principal, Resource
from cerbos_tpu.engine import types as T
from cerbos_tpu.engine.batcher import BatchingEvaluator
from cerbos_tpu.engine.faults import FaultInjector, parse_fault_spec
from cerbos_tpu.engine.flight import recorder as flight_recorder
from cerbos_tpu.engine.health import DeviceHealth
from cerbos_tpu.engine.readiness import ReadinessState
from cerbos_tpu.engine.sentinel import (
    DivergenceCorpus,
    ParitySentinel,
    _Sample,
    compare_rows,
    effect_rows,
    from_config,
    input_from_json,
    input_to_json,
)
from cerbos_tpu.policy.parser import parse_policies
from cerbos_tpu.ruletable import build_rule_table, check_input

pytestmark = pytest.mark.parity_sentinel

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
        request_id=f"rq{i}",
    )


def oracle(rt, inputs, params=None):
    return [check_input(rt, i, params or EvalParams()) for i in inputs]


def flipped(outputs):
    """Hand-corrupted copies: every effect inverted (the silent-corruption
    fault the sentinel exists to catch)."""
    out = []
    for o in outputs:
        actions = {
            a: T.ActionEffect(
                effect="EFFECT_DENY" if e.effect == "EFFECT_ALLOW" else "EFFECT_ALLOW",
                policy=e.policy,
                scope=e.scope,
            )
            for a, e in o.actions.items()
        }
        out.append(
            T.CheckOutput(request_id=o.request_id, resource_id=o.resource_id, actions=actions)
        )
    return out


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


class OracleEvaluator:
    """CPU-oracle-backed evaluator (the test_ipc harness shape): enough
    surface for the batcher AND the sentinel's replay capture
    (``rule_table`` / ``schema_mgr``)."""

    def __init__(self, rt):
        self.rule_table = rt
        self.schema_mgr = None

    def check(self, inputs, params=None):
        params = params or EvalParams()
        return [check_input(self.rule_table, i, params, self.schema_mgr) for i in inputs]

    # streaming surface: the batcher (and FaultInjector's delegation) probe
    # for submit/collect, so serve a pre-evaluated ticket
    def submit(self, inputs, params=None):
        return self.check(inputs, params)

    def collect(self, ticket):
        return ticket


def make_sample(rt, inputs, outputs, shard=0, clock=None, health=None, batch_id=1):
    return _Sample(
        shard=shard,
        inputs=inputs,
        outputs=outputs,
        params=EvalParams(),
        rule_table=rt,
        schema_mgr=None,
        batch_id=batch_id,
        trace_ids=["t-%d" % batch_id],
        done_at=clock() if clock else time.monotonic(),
        health=health,
    )


@pytest.fixture()
def rt():
    return table()


def wait_for(cond, timeout=10.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


class TestSampler:
    def test_first_batch_always_sampled(self):
        s = ParitySentinel(sample_rate=0.01, enabled=True)
        try:
            assert s.should_sample(0) is True  # acc seeded at 1.0
        finally:
            s.close()

    def test_deterministic_fraction(self):
        # rate 0.25: accumulator crossings at batches 1, 4, 8, 12, ... —
        # a pure function of the batch count, identical across instances
        picks = []
        s = ParitySentinel(sample_rate=0.25)
        try:
            picks = [i for i in range(1, 101) if s.should_sample(0)]
        finally:
            s.close()
        assert picks[:4] == [1, 4, 8, 12]
        assert len(picks) == 26  # floor(1.0 + 0.25 * 100) crossings
        s2 = ParitySentinel(sample_rate=0.25)
        try:
            assert [i for i in range(1, 101) if s2.should_sample(0)] == picks
        finally:
            s2.close()

    def test_per_shard_accumulators_are_independent(self):
        s = ParitySentinel(sample_rate=0.01)
        try:
            for _ in range(50):
                s.should_sample(0)
            # shard 1's FIRST batch is still sampled regardless of shard 0
            assert s.should_sample(1) is True
        finally:
            s.close()

    def test_disabled_and_zero_rate_never_sample(self):
        s = ParitySentinel(sample_rate=0.5, enabled=False)
        try:
            assert not s.enabled
            assert all(not s.should_sample(0) for _ in range(10))
        finally:
            s.close()
        z = ParitySentinel(sample_rate=0.0)
        try:
            assert not z.enabled
        finally:
            z.close()

    def test_rate_one_samples_every_batch(self):
        s = ParitySentinel(sample_rate=1.0)
        try:
            assert all(s.should_sample(0) for _ in range(10))
        finally:
            s.close()


class TestComparator:
    def test_identical_outputs_have_no_divergence(self, rt):
        outs = oracle(rt, [inp(i) for i in range(8)])
        assert compare_rows(effect_rows(outs), effect_rows(outs)) == []

    def test_flipped_effect_is_divergent(self, rt):
        outs = oracle(rt, [inp(i) for i in range(8)])
        bad = outs[:3] + flipped(outs[3:4]) + outs[4:]
        assert compare_rows(effect_rows(bad), effect_rows(outs)) == [3]

    def test_policy_provenance_is_compared_bit_exactly(self, rt):
        outs = oracle(rt, [inp(0)])
        rows = effect_rows(outs)
        mutated = json.loads(json.dumps(rows))
        for eff in mutated[0]["actions"].values():
            eff["policy"] = "somewhere.else"
        assert compare_rows(rows, mutated) == [0]

    def test_length_mismatch_marks_trailing_rows(self, rt):
        outs = oracle(rt, [inp(i) for i in range(4)])
        rows = effect_rows(outs)
        assert compare_rows(rows, rows[:2]) == [2, 3]
        assert compare_rows(rows[:2], rows) == [2, 3]

    def test_corpus_input_roundtrip_preserves_decisions(self, rt):
        inputs = [inp(i) for i in range(6)]
        inputs[0].aux_data = T.AuxData(jwt={"sub": "u0", "aud": ["x"]})
        rebuilt = [input_from_json(input_to_json(i)) for i in inputs]
        assert effect_rows(oracle(rt, rebuilt)) == effect_rows(oracle(rt, inputs))
        assert rebuilt[0].aux_data is not None
        assert rebuilt[0].aux_data.jwt["sub"] == "u0"


class TestDivergenceCorpus:
    def test_append_load_roundtrip(self, tmp_path):
        corpus = DivergenceCorpus(str(tmp_path), max_records=8)
        p1 = corpus.append({"shard": 0, "batch_id": 7})
        p2 = corpus.append({"shard": 1, "batch_id": 9})
        assert p1 and p2 and corpus.size() == 2
        records = DivergenceCorpus.load(str(tmp_path))
        assert [r["batch_id"] for _, r in records] == [7, 9]  # oldest first

    def test_bounded_oldest_pruned(self, tmp_path):
        corpus = DivergenceCorpus(str(tmp_path), max_records=3)
        for i in range(7):
            corpus.append({"batch_id": i})
        assert corpus.size() == 3
        assert [r["batch_id"] for _, r in DivergenceCorpus.load(str(tmp_path))] == [4, 5, 6]

    def test_unreadable_record_is_skipped(self, tmp_path):
        corpus = DivergenceCorpus(str(tmp_path), max_records=8)
        corpus.append({"batch_id": 1})
        (tmp_path / "divergence-9999999999999-000001.json").write_text("{not json")
        records = DivergenceCorpus.load(str(tmp_path))
        assert [r["batch_id"] for _, r in records] == [1]

    def test_empty_dir_disables_capture(self):
        corpus = DivergenceCorpus("", max_records=8)
        assert corpus.append({"x": 1}) is None
        assert corpus.size() == 0


class TestStormPolicy:
    """Fake-clock storm lifecycle: divergences accumulate in a sliding
    window, the threshold trips the lane breaker exactly once per window,
    and the storm clears when the window slides past."""

    def make(self, clock, tmp_path=None, threshold=2, window=10.0):
        return ParitySentinel(
            sample_rate=1.0,
            window_sec=window,
            storm_threshold=threshold,
            corpus_dir=str(tmp_path) if tmp_path else "",
            clock=clock,
        )

    def test_matching_batch_is_not_a_divergence(self, rt):
        clock = FakeClock()
        s = self.make(clock)
        try:
            outs = oracle(rt, [inp(i) for i in range(4)])
            s._verify(make_sample(rt, [inp(i) for i in range(4)], outs, clock=clock))
            assert s.stats["checks"] == 1
            assert s.stats["divergences"] == 0
            assert s.storm_shards() == []
        finally:
            s.close()

    def test_storm_trips_breaker_and_recovers(self, rt, tmp_path):
        clock = FakeClock()
        flight_recorder().clear()
        health = DeviceHealth(enabled=True, clock=clock)
        s = self.make(clock, tmp_path=tmp_path, threshold=2, window=10.0)
        try:
            inputs = [inp(0)]
            bad = flipped(oracle(rt, inputs))
            s._verify(make_sample(rt, inputs, bad, clock=clock, health=health, batch_id=1))
            # one divergence: captured but below the storm threshold
            assert s.stats["divergences"] == 1
            assert s.storm_shards() == []
            assert health.state == "closed"
            clock.advance(2.0)
            s._verify(make_sample(rt, inputs, bad, clock=clock, health=health, batch_id=2))
            # second divergence inside the window: storm — lane trips open
            assert s.stats["storms"] == 1
            assert s.storm_shards() == [0]
            assert health.state == "open"
            # a third divergence in the SAME window must not re-trip
            clock.advance(1.0)
            s._verify(make_sample(rt, inputs, bad, clock=clock, health=health, batch_id=3))
            assert s.stats["storms"] == 1
            # the corpus captured every divergence, replayably
            records = DivergenceCorpus.load(str(tmp_path))
            assert len(records) == 3
            _, rec = records[0]
            assert rec["shard"] == 0 and rec["divergent_indices"] == [0]
            assert effect_rows(oracle(rt, [input_from_json(j) for j in rec["inputs"]])) == rec[
                "oracle_effects"
            ]
            # flight recorder saw both event kinds with shard provenance
            events = flight_recorder().dump()["events"]
            kinds = [e["kind"] for e in events]
            assert "parity_divergence" in kinds and "parity_storm" in kinds
            div = next(e for e in events if e["kind"] == "parity_divergence")
            assert div["shard"] == 0 and div["batch_id"] == 1
            # recovery: the window slides past the divergences
            clock.advance(60.0)
            assert s.storm_shards() == []
        finally:
            s.close()
            flight_recorder().clear()

    def test_oracle_replay_crash_counts_as_divergence(self, rt, tmp_path):
        clock = FakeClock()
        s = self.make(clock, tmp_path=tmp_path, threshold=99)
        try:
            inputs = [inp(0)]
            outs = oracle(rt, inputs)
            sample = make_sample(rt, inputs, outs, clock=clock)
            sample.rule_table = object()  # replay against garbage → crash
            s._verify(sample)
            assert s.stats["replay_errors"] == 1
            assert s.stats["divergences"] == 1
            _, rec = DivergenceCorpus.load(str(tmp_path))[0]
            assert rec["replay_error"]
        finally:
            s.close()

    def test_readiness_degrades_with_parity_reason(self, rt):
        clock = FakeClock()
        health = DeviceHealth(enabled=False, clock=clock)
        s = self.make(clock, threshold=1, window=10.0)
        rstate = ReadinessState(clock=clock)
        rstate.bind_parity(s.storm_shards)
        try:
            assert rstate.status() == "ready"
            inputs = [inp(0)]
            s._verify(make_sample(rt, inputs, flipped(oracle(rt, inputs)), clock=clock, health=health))
            assert rstate.status() == "degraded"
            snap = rstate.snapshot()
            assert snap["reason"] == "parity"
            assert snap["parity_shards"] == [0]
            clock.advance(60.0)
            assert rstate.status() == "ready"
            assert "reason" not in rstate.snapshot()
        finally:
            s.close()


class TestSingleBatcherTopology:
    def test_flip_effect_detected_end_to_end(self, rt, tmp_path):
        """The acceptance drill, single-batcher form: a silently corrupting
        device path answers requests normally (no errors, no timeouts) and
        the sentinel is the ONLY mechanism that notices."""
        faulty = FaultInjector(OracleEvaluator(rt), "flip_effect:1.0")
        batcher = BatchingEvaluator(faulty, max_wait_ms=0.0)
        sentinel = ParitySentinel(
            sample_rate=1.0, storm_threshold=99, corpus_dir=str(tmp_path)
        ).attach(batcher)
        try:
            outs = batcher.check([inp(i) for i in range(4)])
            assert len(outs) == 4  # requests answered (wrongly) — not lost
            # the drain thread offers the batch to the sentinel AFTER it
            # settles the waiter: wait for the offer before draining
            deadline = time.monotonic() + 10.0
            while sentinel.stats["sampled"] < 1 and time.monotonic() < deadline:
                time.sleep(0.005)
            assert sentinel.drain(timeout=10.0)
            assert sentinel.stats["checks"] >= 1
            assert sentinel.stats["divergences"] >= 1
            assert sentinel.snapshot()["corpus_records"] >= 1
        finally:
            sentinel.close()
            batcher.close()

    def test_healthy_batcher_has_zero_divergences(self, rt):
        batcher = BatchingEvaluator(OracleEvaluator(rt), max_wait_ms=0.0)
        sentinel = ParitySentinel(sample_rate=1.0, storm_threshold=99).attach(batcher)
        try:
            for i in range(6):
                batcher.check([inp(i)])
            assert sentinel.drain(timeout=10.0)
            assert sentinel.stats["checks"] >= 1
            assert sentinel.stats["divergences"] == 0
        finally:
            sentinel.close()
            batcher.close()

    def test_unsampled_batches_never_enqueue(self, rt):
        batcher = BatchingEvaluator(OracleEvaluator(rt), max_wait_ms=0.0)
        sentinel = ParitySentinel(sample_rate=1.0, enabled=False).attach(batcher)
        try:
            batcher.check([inp(0)])
            assert sentinel.backlog() == 0
            assert sentinel.stats["sampled"] == 0
        finally:
            sentinel.close()
            batcher.close()


class TestIpcTopology:
    def test_sentinel_samples_in_the_batcher_process(self, rt, tmp_path):
        """``--frontends N`` topology: the sentinel rides the shared-batcher
        process (where the device is); front-end tickets crossing the unix
        socket are covered without any front-end wiring."""
        from cerbos_tpu.engine.ipc import BatcherIpcServer, RemoteBatcherClient

        faulty = FaultInjector(OracleEvaluator(rt), "flip_effect:1.0")
        batcher = BatchingEvaluator(faulty, max_wait_ms=1.0)
        sentinel = ParitySentinel(sample_rate=1.0, storm_threshold=99).attach(batcher)
        server = BatcherIpcServer(str(tmp_path / "batcher.sock"), batcher)
        server.start()
        client = RemoteBatcherClient(
            server.socket_path,
            rt,
            request_timeout_s=10.0,
            worker_label="fe-test",
            status_poll_s=0.05,
            connect_retry_s=0.05,
        )
        try:
            assert wait_for(client._connected.is_set)
            outs = client.check([inp(i) for i in range(8)])
            assert len(outs) == 8
            assert sentinel.drain(timeout=10.0)
            assert sentinel.stats["divergences"] >= 1
        finally:
            client.close()
            server.close()
            sentinel.close()
            batcher.close()


class TestShardedTopology:
    def test_flip_effect_storm_trips_only_the_sick_shard(self, rt, tmp_path):
        """The acceptance drill, sharded form: ``flip_effect:1.0,shard:0``
        corrupts ONE lane silently; the sentinel detects it, storms, and
        trips shard 0's breaker while shard 1 keeps serving — zero requests
        lost."""
        from cerbos_tpu.engine.shards import build_shard_pool
        from cerbos_tpu.tpu.evaluator import TpuEvaluator

        base = TpuEvaluator(rt, use_jax=False, min_device_batch=1)
        pool = build_shard_pool(
            base,
            n_shards=2,
            routing="round_robin",
            max_wait_ms=0.0,
            request_timeout_s=10.0,
            fault_spec="flip_effect:1.0,shard:0",
        )
        sentinel = ParitySentinel(
            sample_rate=1.0, storm_threshold=1, corpus_dir=str(tmp_path)
        ).attach(pool)
        try:
            assert all(lane.sentinel is sentinel for lane in pool.shards)
            answered = 0
            for i in range(12):
                answered += len(pool.check([inp(i)]))
            assert answered == 12  # zero lost requests
            assert sentinel.drain(timeout=10.0)
            assert wait_for(lambda: sentinel.stats["storms"] >= 1)
            snap = sentinel.snapshot()
            # divergences are shard 0's alone; shard 1's checks all pass
            assert snap["divergences"] >= 1
            assert sentinel.storm_shards() == [0]
            assert pool.shards[0].health.state == "open"
            assert pool.shards[1].health.state == "closed"
            assert snap["lanes"][1]["sampled"] >= 1
            # corpus records carry shard-0 provenance for offline replay
            for _, rec in DivergenceCorpus.load(str(tmp_path)):
                assert rec["shard"] == 0
        finally:
            sentinel.close()
            pool.close()


class TestAuditTraceCorrelation:
    def test_decision_entries_carry_trace_and_shard(self, rt):
        inputs = [inp(0)]
        outputs = oracle(rt, inputs)
        entry = _entry_from_decision("c1", inputs, outputs, trace_id="abc123", shard=3)
        assert entry["traceId"] == "abc123"
        assert entry["shard"] == 3
        # shard 0 is a real shard id, not an empty value to drop
        assert _entry_from_decision("c2", inputs, outputs, trace_id="t", shard=0)["shard"] == 0
        bare = _entry_from_decision("c3", inputs, outputs)
        assert "traceId" not in bare and "shard" not in bare

    def test_write_decision_never_blocks_on_a_wedged_backend(self, rt):
        release = threading.Event()
        written = []

        class WedgedBackend:
            def write(self, entry):
                release.wait(timeout=30)
                written.append(entry)

        log = AuditLog(backend=WedgedBackend())
        inputs = [inp(0)]
        outputs = oracle(rt, inputs)
        try:
            t0 = time.perf_counter()
            # queue bound is 4096: overflow it while the writer is wedged
            for i in range(5000):
                log.write_decision(f"c{i}", inputs, outputs, trace_id="t", shard=0)
            elapsed = time.perf_counter() - t0
            assert elapsed < 5.0  # drops, never blocks the hot path
            assert log._queue.qsize() >= 4095
        finally:
            release.set()
            log.close()
        assert written  # the writer drained once unwedged


class TestServerIntegration:
    def test_bootstrap_attaches_sentinel_and_flight_shard_filter(self, tmp_path_factory):
        """Bootstrap wires the sentinel onto the real batcher, and the flight
        endpoint narrows to one lane via ``?shard=N`` (non-int → 400)."""
        import urllib.error
        import urllib.request

        from cerbos_tpu.bootstrap import initialize
        from cerbos_tpu.config import Config
        from cerbos_tpu.server.server import Server, ServerConfig

        policy_dir = tmp_path_factory.mktemp("parity-policies")
        (policy_dir / "album.yaml").write_text(POLICY)
        config = Config.load(overrides=[f"storage.disk.directory={policy_dir}"])
        core = initialize(config)
        core.tpu_evaluator.use_jax = False  # keep the test jax-independent
        srv = Server(
            core.service,
            ServerConfig(http_listen_addr="127.0.0.1:0", grpc_listen_addr="127.0.0.1:0"),
        )
        srv.start()
        try:
            assert core.sentinel is not None and core.sentinel.enabled
            assert core.batcher.sentinel is core.sentinel
            body = {
                "requestId": "ps-1",
                "principal": {"id": "alice", "roles": ["user"]},
                "resources": [
                    {
                        "actions": ["view"],
                        "resource": {"kind": "album", "id": "a1", "attr": {"owner": "alice"}},
                    }
                ],
            }
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.http_port}/api/check/resources",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"},
                method="POST",
            )
            with urllib.request.urlopen(req) as resp:
                assert json.loads(resp.read())["results"]

            def flight(q=""):
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.http_port}/_cerbos/debug/flight{q}"
                ) as resp:
                    return json.loads(resp.read())

            assert flight()["batches"]  # the request produced a batch record
            mine = flight("?shard=0")
            assert mine["shard_filter"] == 0 and mine["batches"]
            other = flight("?shard=7")
            assert other["shard_filter"] == 7 and other["batches"] == []
            with pytest.raises(urllib.error.HTTPError) as err:
                flight("?shard=bogus")
            assert err.value.code == 400
        finally:
            srv.stop()
            core.close()


class TestConfigAndFaultGrammar:
    def test_from_config_reads_the_knob_block(self, tmp_path):
        s = from_config(
            {
                "enabled": True,
                "sampleRate": 0.5,
                "windowSec": 7,
                "stormThreshold": 9,
                "corpusDir": str(tmp_path / "corpus"),
                "corpusMax": 5,
            }
        )
        try:
            assert s.enabled and s.sample_rate == 0.5
            assert s.window_sec == 7.0 and s.storm_threshold == 9
            assert s.corpus.dir == str(tmp_path / "corpus")
            assert s.corpus.max_records == 5
        finally:
            s.close()
        off = from_config({"enabled": False})
        try:
            assert not off.enabled
        finally:
            off.close()

    def test_flip_effect_knob_parses_and_flips(self, rt):
        knobs = parse_fault_spec("flip_effect:1.0,shard:0")
        assert knobs["flip_effect"] == 1.0 and knobs["shard"] == 0
        faulty = FaultInjector(OracleEvaluator(rt), "flip_effect:1.0")
        inputs = [inp(i) for i in range(4)]
        device = effect_rows(faulty.check(inputs))
        clean = effect_rows(oracle(rt, inputs))
        assert compare_rows(device, clean) == [0, 1, 2, 3]
        # the injector corrupts silently: same rows, same actions, flipped
        # effects only — exactly the failure the breaker can never see
        for bad, good in zip(device, clean):
            assert bad["resourceId"] == good["resourceId"]
            assert set(bad["actions"]) == set(good["actions"])

    def test_flip_effect_zero_probability_is_inert(self, rt):
        faulty = FaultInjector(OracleEvaluator(rt), "flip_effect:0.0")
        inputs = [inp(i) for i in range(4)]
        assert effect_rows(faulty.check(inputs)) == effect_rows(oracle(rt, inputs))
