"""End-to-end device-path tracing and the batch flight recorder.

The ISSUE acceptance check lives here: a CheckResources call carrying a W3C
``traceparent`` header produces a single trace in which the device batch's
submit/collect spans are descendants of the request span across the batcher
thread hop, and ``/_cerbos/debug/flight`` returns the corresponding batch
record with non-zero stage timings and occupancy <= 1.0. Plus: the metrics
lint over the registry, flight-recorder unit behavior, and breaker
state-transition accounting.
"""

import json
import re
import threading
import time
import urllib.request

from cerbos_tpu import observability as obs
from cerbos_tpu.bootstrap import initialize
from cerbos_tpu.config import Config
from cerbos_tpu.engine import flight
from cerbos_tpu.engine.flight import FlightRecorder
from cerbos_tpu.engine.health import DeviceHealth

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
          expr: request.resource.attr.owner == request.principal.id
"""


class _CaptureExporter(obs.SpanExporter):
    def __init__(self):
        self.spans = []
        self._lock = threading.Lock()

    def export(self, span, duration_ms):
        with self._lock:
            self.spans.append(span)

    def in_trace(self, trace_id):
        with self._lock:
            return [s for s in self.spans if s.trace_id == trace_id]


def _boot(tmp_path_factory, name, overrides=()):
    policy_dir = tmp_path_factory.mktemp(name)
    (policy_dir / "album.yaml").write_text(POLICY)
    config = Config.load(overrides=[f"storage.disk.directory={policy_dir}", *overrides])
    core = initialize(config)
    core.tpu_evaluator.use_jax = False  # keep the test jax-independent
    return core


class TestEndToEndTracing:
    def test_traceparent_joins_device_batch_trace(self, tmp_path_factory):
        """The acceptance check: one trace from the remote caller down to the
        device batch, stitched across the batcher thread hop, plus the
        matching flight-recorder record."""
        from cerbos_tpu.server.server import Server, ServerConfig

        # minDeviceBatch=1 so that the one-resource request makes a flight; at the
        # default it is answered on its own thread (the next test)
        core = _boot(tmp_path_factory, "tracing-policies", ["engine.tpu.minDeviceBatch=1"])
        cap = _CaptureExporter()
        old_exporter = obs._exporter
        obs.set_exporter(cap)
        srv = Server(
            core.service,
            ServerConfig(http_listen_addr="127.0.0.1:0", grpc_listen_addr="127.0.0.1:0"),
        )
        srv.start()
        trace_id = obs.new_trace_id()
        remote_span_id = obs.new_span_id()
        header = f"00-{trace_id}-{remote_span_id}-01"
        try:
            body = {
                "requestId": "tr-1",
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
                headers={"Content-Type": "application/json", "traceparent": header},
                method="POST",
            )
            with urllib.request.urlopen(req) as resp:
                assert json.loads(resp.read())["results"]
                # the response tells the caller which trace the PDP joined
                assert resp.headers.get("traceparent") == header

            # batch.collect / request.settle export on the drain thread just
            # after the response future resolves: wait for them briefly
            want = {
                "request.CheckResources",
                "batcher.enqueue",
                "batch.submit",
                "batch.collect",
                "request.settle",
            }
            deadline = time.time() + 10
            while time.time() < deadline:
                if want <= {s.name for s in cap.in_trace(trace_id)}:
                    break
                time.sleep(0.02)
            trace = cap.in_trace(trace_id)
            names = {s.name for s in trace}
            assert want <= names, sorted(names)

            spans = {s.name: s for s in trace}
            by_id = {s.span_id: s for s in trace}

            # batch.submit is a DESCENDANT of the remote request span even
            # though it runs on the batcher drain thread
            chain = []
            cur = spans["batch.submit"]
            while cur.parent_id in by_id:
                cur = by_id[cur.parent_id]
                chain.append(cur.name)
            assert "batcher.enqueue" in chain and "request.CheckResources" in chain, chain
            # ...and the topmost local span parents under the remote caller's id
            assert cur.parent_id == remote_span_id

            # the rest of the batch pipeline hangs off the batch span
            assert spans["batch.collect"].parent_id == spans["batch.submit"].span_id
            assert spans["request.settle"].parent_id == spans["batch.submit"].span_id
            # the batch span links every co-batched request's context
            assert spans["batcher.enqueue"].context in spans["batch.submit"].links

            # flight recorder: the batch record for this trace is retrievable
            with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.http_port}/_cerbos/debug/flight"
            ) as resp:
                dump = json.loads(resp.read())
            recs = [r for r in dump["batches"] if trace_id in r["trace_ids"]]
            assert recs, dump
            rec = recs[-1]
            assert rec["outcome"] == "ok"
            assert rec["occupancy"] is not None and rec["occupancy"] <= 1.0
            assert any(v > 0 for v in rec["timings"].values()), rec
            assert rec["requests"] >= 1 and rec["inputs"] >= 1
        finally:
            obs.set_exporter(old_exporter)
            srv.stop()
            core.close()


    def test_a_one_resource_check_is_answered_with_no_flight_in_its_trace(self, tmp_path_factory):
        """At the default ``minDeviceBatch`` a one-resource request that finds
        the queue empty is answered on its own thread: its trace is the
        request and the engine's span, and no flight record names it."""
        from cerbos_tpu.server.server import Server, ServerConfig

        core = _boot(tmp_path_factory, "inline-tracing-policies")
        cap = _CaptureExporter()
        old_exporter = obs._exporter
        obs.set_exporter(cap)
        srv = Server(
            core.service,
            ServerConfig(http_listen_addr="127.0.0.1:0", grpc_listen_addr="127.0.0.1:0"),
        )
        srv.start()
        trace_id = obs.new_trace_id()
        header = f"00-{trace_id}-{obs.new_span_id()}-01"
        try:
            body = {
                "requestId": "tr-2",
                "principal": {"id": "alice", "roles": ["user"]},
                "resources": [
                    {"actions": ["view"], "resource": {"kind": "album", "id": "a1", "attr": {"owner": "alice"}}}
                ],
            }
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.http_port}/api/check/resources",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json", "traceparent": header},
                method="POST",
            )
            with urllib.request.urlopen(req) as resp:
                results = json.loads(resp.read())["results"]
                assert results[0]["actions"]["view"] == "EFFECT_ALLOW"
            deadline = time.time() + 10
            while time.time() < deadline and "request.CheckResources" not in {
                s.name for s in cap.in_trace(trace_id)
            }:
                time.sleep(0.02)
            time.sleep(0.1)  # a flight's spans would export on the drain thread just after the reply
            trace = cap.in_trace(trace_id)
            assert sorted(s.name for s in trace) == ["engine.Check", "request.CheckResources"]
            spans = {s.name: s for s in trace}
            assert spans["engine.Check"].attributes["path"] == "inline"
            assert spans["engine.Check"].parent_id == spans["request.CheckResources"].span_id
            with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.http_port}/_cerbos/debug/flight"
            ) as resp:
                dump = json.loads(resp.read())
            assert not [r for r in dump["batches"] if trace_id in r["trace_ids"]]
        finally:
            obs.set_exporter(old_exporter)
            srv.stop()
            core.close()


class TestMetricsLint:
    def test_registry_lints_clean_after_bootstrap(self, tmp_path_factory):
        """Every registered instrument: conformant name, help text, and a
        single known instrument type (the registry raising on conflicts is
        covered in test_observability)."""
        core = _boot(tmp_path_factory, "lint-policies")
        try:
            # the async audit path registers its queue metrics at
            # construction; default config has audit off, so build one here
            from cerbos_tpu.audit.log import AuditLog

            AuditLog(backend=None).close()
            inst = obs.metrics().instruments()
            # the device-path instruments this PR adds must be registered
            for name in (
                "cerbos_tpu_batch_occupancy",
                "cerbos_tpu_batch_padding_waste_rows_total",
                "cerbos_tpu_batch_stage_seconds",
                "cerbos_tpu_breaker_state",
                "cerbos_tpu_breaker_transitions_total",
                # compile-economy family (docs/OBSERVABILITY.md)
                "cerbos_tpu_xla_compiles_total",
                "cerbos_tpu_xla_compile_seconds",
                "cerbos_tpu_jit_cache_hits_total",
                "cerbos_tpu_jit_cache_misses_total",
                "cerbos_tpu_xla_layout_cardinality",
                "cerbos_tpu_recompile_storms_total",
                "cerbos_tpu_readiness_state",
                "cerbos_tpu_warmup_expected_layouts",
                "cerbos_tpu_warmup_compiled_layouts",
                # parity-sentinel family (engine/sentinel.py): bootstrap
                # attaches the sentinel to every local batcher by default
                "cerbos_tpu_parity_checks_total",
                "cerbos_tpu_parity_divergence_total",
                "cerbos_tpu_parity_lag_seconds",
                "cerbos_tpu_parity_sample_rate",
                "cerbos_tpu_parity_dropped_total",
                "cerbos_tpu_parity_replay_seconds_total",
                "cerbos_tpu_parity_storms_total",
                "cerbos_tpu_parity_corpus_records",
                # async audit-path family (audit/log.py)
                "cerbos_tpu_audit_queue_depth",
                "cerbos_tpu_audit_dropped_total",
                # latency-budget waterfall + goodput family (engine/budget.py)
                "cerbos_tpu_request_stage_seconds",
                "cerbos_tpu_request_total_seconds",
                "cerbos_tpu_deadline_budget_remaining_seconds",
                "cerbos_tpu_decisions_total",
                "cerbos_tpu_slow_requests_total",
                # saturation pressure family (engine/pressure.py)
                "cerbos_tpu_pressure_score",
                "cerbos_tpu_pressure_queue",
                "cerbos_tpu_pressure_inflight",
                "cerbos_tpu_pressure_ipc",
                "cerbos_tpu_pressure_fallback",
                "cerbos_tpu_pressure_degraded",
                "cerbos_tpu_pressure_compile",
                # static policy analysis family (tpu/analyze.py): bootstrap
                # publishes a report for the boot table and re-publishes on
                # every swap; the compile-rejection counter registers with
                # the condition compiler itself
                "cerbos_tpu_policy_analysis_total",
                "cerbos_tpu_cond_compile_unsupported_total",
                # batched PlanResources family (plan/batch.py + the plan-mode
                # parity leg in engine/sentinel.py)
                "cerbos_tpu_plan_batch_seconds",
                "cerbos_tpu_plan_queries_total",
                "cerbos_tpu_plan_residual_rules",
                "cerbos_tpu_plan_parity_checks_total",
                "cerbos_tpu_plan_parity_divergence_total",
                # safe policy rollout family (engine/rollout.py); the skew
                # gauge is frontend-only (ipc client) so it is not listed
                "cerbos_tpu_rollout_total",
                "cerbos_tpu_rollout_duration_seconds",
                "cerbos_tpu_policy_epoch",
                # decision-provenance family (engine/hotrules.py): the
                # batcher instantiates the recorder at construction so the
                # series exist before the first decision
                "cerbos_tpu_rule_hits_total",
                "cerbos_tpu_decision_source_total",
            ):
                assert name in inst, name
            known = (obs.Counter, obs.CounterVec, obs.Gauge, obs.GaugeVec, obs.Histogram, obs.HistogramVec)
            for name, m in inst.items():
                assert re.fullmatch(r"cerbos_tpu_[a-z0-9_]+", name), name
                assert isinstance(m, known), (name, type(m))
                assert m.help, f"metric {name!r} has no help text"
            # sharded serving (docs/OBSERVABILITY.md "Per-shard row"): these
            # families carry a shard label so one sick chip is visible as
            # ONE sick series, not a poisoned aggregate
            sharded = {
                "cerbos_tpu_batcher_inflight": obs.GaugeVec,
                "cerbos_tpu_batch_occupancy": obs.GaugeVec,
                "cerbos_tpu_breaker_state": obs.GaugeVec,
                "cerbos_tpu_batch_padding_waste_rows_total": obs.CounterVec,
                "cerbos_tpu_breaker_trips_total": obs.CounterVec,
                "cerbos_tpu_parity_checks_total": obs.CounterVec,
                "cerbos_tpu_parity_divergence_total": obs.CounterVec,
                "cerbos_tpu_parity_storms_total": obs.CounterVec,
            }
            for name, typ in sharded.items():
                m = inst.get(name)
                assert isinstance(m, typ), (name, type(m))
                label = m.label if isinstance(m.label, str) else None
                assert label == "shard", (name, m.label)
            # multi-dimension vecs keep shard as the LAST label dimension
            for name in (
                "cerbos_tpu_batch_stage_seconds",
                "cerbos_tpu_breaker_transitions_total",
                "cerbos_tpu_request_stage_seconds",
                "cerbos_tpu_deadline_budget_remaining_seconds",
            ):
                m = inst.get(name)
                assert isinstance(m.label, tuple) and m.label[-1] == "shard", (name, m.label)
            # goodput accounting splits on (api, outcome) so PlanResources
            # traffic is booked alongside checks (process-global)
            m = inst.get("cerbos_tpu_decisions_total")
            assert isinstance(m, obs.CounterVec) and m.label == ("api", "outcome"), m.label
            # rollout stage accounting splits on (stage, outcome) so a gate
            # rejection and a canary rollback are distinct series
            m = inst.get("cerbos_tpu_rollout_total")
            assert isinstance(m, obs.CounterVec) and m.label == ("stage", "outcome"), m.label
            m = inst.get("cerbos_tpu_rollout_duration_seconds")
            assert isinstance(m, obs.HistogramVec) and m.label == "stage", m.label
            # rendered exposition carries the label on every child series
            text = obs.metrics().render()
            for line in text.splitlines():
                if line.startswith("cerbos_tpu_breaker_state{"):
                    assert 'shard="' in line, line
        finally:
            core.close()


class TestFlightRecorder:
    def _record(self, rec, batch_id, **kw):
        defaults = dict(
            trace_ids=[], requests=1, inputs=1, timings={"submit": 0.001}, outcome="ok"
        )
        defaults.update(kw)
        rec.record_batch(batch_id, **defaults)

    def test_capacity_bound_drops_oldest(self):
        rec = FlightRecorder(capacity=4)
        for i in range(10):
            self._record(rec, i)
        dump = rec.dump()
        assert dump["capacity"] == 4
        assert [r["batch_id"] for r in dump["batches"]] == [6, 7, 8, 9]

    def test_event_ring_is_bounded_too(self):
        rec = FlightRecorder(capacity=2)
        for i in range(3):
            rec.record_event("bisect_done", idx=i)
        evs = rec.dump()["events"]
        assert [e["idx"] for e in evs] == [1, 2]
        assert all(e["kind"] == "bisect_done" and e["ts"] > 0 for e in evs)

    def test_disabled_records_nothing(self):
        rec = FlightRecorder(capacity=4, enabled=False)
        self._record(rec, 1)
        rec.record_event("x")
        assert rec.dump() == {"capacity": 4, "batches": [], "events": []}

    def test_record_fields_and_rounding(self):
        rec = FlightRecorder()
        self._record(
            rec,
            7,
            trace_ids=["t1", "t2"],
            timings={"pack": 0.123456789},
            occupancy=0.87654321,
            layout_key="B64xBA128",
            breaker_state="closed",
        )
        r = rec.dump()["batches"][0]
        assert r["timings"]["pack"] == 0.123457
        assert r["occupancy"] == 0.8765
        assert r["layout_key"] == "B64xBA128"
        assert r["breaker_state"] == "closed"
        assert r["trace_ids"] == ["t1", "t2"]

    def test_batch_ids_monotonic(self):
        rec = FlightRecorder()
        assert rec.next_batch_id() < rec.next_batch_id()

    def test_clear(self):
        rec = FlightRecorder()
        self._record(rec, 1)
        rec.record_event("x")
        rec.clear()
        dump = rec.dump()
        assert dump["batches"] == [] and dump["events"] == []

    def test_configure_mutates_global_in_place(self):
        """Bootstrap re-bounds the process recorder without replacing it, so
        modules holding a reference keep recording into the live ring."""
        rec = flight.recorder()
        old_capacity, old_enabled = rec.capacity, rec.enabled
        try:
            got = flight.configure(capacity=3, enabled=True)
            assert got is rec and flight.recorder() is rec
            assert rec.capacity == 3
            for i in range(5):
                rec.record_event("cfg_probe", i=i)
            assert len(rec.dump()["events"]) <= 3
        finally:
            flight.configure(capacity=old_capacity, enabled=old_enabled)


class TestBreakerTransitions:
    def test_each_edge_is_counted_and_recorded(self):
        clock = [0.0]
        h = DeviceHealth(
            failure_threshold=2,
            probe_backoff_base_s=0.1,
            probe_backoff_cap_s=0.1,
            clock=lambda: clock[0],
        )
        vec = h.m_transitions  # global counter_vec: compare deltas, not totals
        # children keyed (transition, shard); an unsharded breaker is shard "0"
        edges = tuple(
            (t, "0") for t in ("closed_open", "open_half_open", "half_open_open", "half_open_closed")
        )
        base = {e: vec.get(e) for e in edges}
        ev_base = len(
            [e for e in flight.recorder().dump()["events"] if e["kind"] == "breaker_transition"]
        )

        h.record_failure()
        assert h.state == "closed"  # below threshold: no transition yet
        h.record_failure()
        assert h.state == "open"
        assert vec.get(("closed_open", "0")) == base[("closed_open", "0")] + 1
        assert h.m_state.value == 1.0

        clock[0] += 1000.0
        token = h.should_probe()
        assert token is not None
        assert vec.get(("open_half_open", "0")) == base[("open_half_open", "0")] + 1
        assert h.m_state.value == 2.0

        h.probe_failed(token)
        assert vec.get(("half_open_open", "0")) == base[("half_open_open", "0")] + 1

        clock[0] += 1000.0
        token = h.should_probe()
        assert token is not None
        h.probe_succeeded(token)
        assert h.state == "closed"
        assert vec.get(("half_open_closed", "0")) == base[("half_open_closed", "0")] + 1
        assert h.m_state.value == 0.0

        # 5 edges total: trip, half-open, re-open, half-open, re-close
        trans = [
            e for e in flight.recorder().dump()["events"] if e["kind"] == "breaker_transition"
        ]
        assert len(trans) == ev_base + 5
        assert (trans[-1]["frm"], trans[-1]["to"]) == ("half_open", "closed")
