"""Decision provenance (ISSUE 20): device-side rule attribution end to end.

The contract: every ``ActionEffect`` names the rule-table row that won it
(``matched_rule``/``rule_row_id``) and the evaluator that produced it
(``source`` = device | oracle). The differential gate is the tentpole —
for every (resource, action) the device's winning rule must equal the CPU
oracle's, and must appear among the oracle tracer's ACTIVATED rules —
including principal-policy and scoped-policy wins. Around it: fallback
labeling under chaos, codec carriage on both IPC legs, sharded lane
attribution, the hot-rule recorder, includeMeta/audit surfacing, and the
parity sentinel's both-sides rule annotation rendered by
``cerbos-tpuctl replay-divergences --explain``.

The whole file must pass with and without the native codec
(``CERBOS_TPU_NO_NATIVE=1``) — the Makefile runs both legs.
"""

import json
import random

import pytest

from cerbos_tpu import native
from cerbos_tpu.compile import compile_policy_set
from cerbos_tpu.engine import CheckInput, EvalParams, Principal, Resource
from cerbos_tpu.engine import types as T
from cerbos_tpu.engine.batcher import BatchingEvaluator
from cerbos_tpu.engine.faults import FaultInjector
from cerbos_tpu.engine.health import DeviceHealth
from cerbos_tpu.engine.hotrules import HotRuleRecorder
from cerbos_tpu.engine.ipc import decode_outputs, encode_outputs
from cerbos_tpu.engine.sentinel import DivergenceCorpus, ParitySentinel, provenance_rows
from cerbos_tpu.policy.parser import parse_policies
from cerbos_tpu.ruletable import build_rule_table, check_input
from cerbos_tpu.tpu.evaluator import TpuEvaluator

pytestmark = pytest.mark.provenance

needs_native = pytest.mark.skipif(
    native.get() is None, reason="native module unavailable (CERBOS_TPU_NO_NATIVE?)"
)

# resource policy + scoped override + principal policy: the three win kinds
# the differential gate must attribute correctly
POLICIES = """
apiVersion: api.cerbos.dev/v1
derivedRoles:
  name: prov_roles
  definitions:
    - name: owner
      parentRoles: [viewer, editor]
      condition:
        match:
          expr: R.attr.owner == P.id
---
apiVersion: api.cerbos.dev/v1
resourcePolicy:
  resource: widget
  version: default
  importDerivedRoles: [prov_roles]
  rules:
    - name: read-any
      actions: ["read"]
      effect: EFFECT_ALLOW
      roles: [viewer, editor]
    - name: write-owner
      actions: ["write"]
      effect: EFFECT_ALLOW
      derivedRoles: [owner]
    - name: purge-protected
      actions: ["purge"]
      effect: EFFECT_DENY
      roles: ["*"]
      condition:
        match:
          expr: R.attr.protected == true
    - name: purge-editor
      actions: ["purge"]
      effect: EFFECT_ALLOW
      roles: [editor]
---
apiVersion: api.cerbos.dev/v1
resourcePolicy:
  resource: widget
  version: default
  scope: team
  rules:
    - name: team-read-deny
      actions: ["read"]
      effect: EFFECT_DENY
      roles: [viewer]
      condition:
        match:
          expr: R.attr.restricted == true
---
apiVersion: api.cerbos.dev/v1
principalPolicy:
  principal: special
  version: default
  rules:
    - resource: widget
      actions:
        - name: special-read
          action: "read"
          effect: EFFECT_ALLOW
        - name: special-purge
          action: "purge"
          effect: EFFECT_DENY
"""


def table():
    return build_rule_table(compile_policy_set(list(parse_policies(POLICIES))))


@pytest.fixture()
def rt():
    return table()


def fuzz_inputs(n=120, seed=7):
    rng = random.Random(seed)
    inputs = []
    for i in range(n):
        roles = rng.sample(["viewer", "editor", "ghost"], k=rng.randint(1, 2))
        pid = rng.choice(["u1", "u2", "special"])
        attr = {}
        if rng.random() < 0.8:
            attr["owner"] = rng.choice(["u1", "u2"])
        if rng.random() < 0.5:
            attr["protected"] = rng.choice([True, False])
        if rng.random() < 0.4:
            attr["restricted"] = rng.choice([True, False])
        inputs.append(
            CheckInput(
                principal=Principal(id=pid, roles=roles),
                resource=Resource(
                    kind="widget", id=f"w{i}", attr=attr, scope=rng.choice(["", "team"])
                ),
                actions=rng.sample(["read", "write", "purge"], k=rng.randint(1, 3)),
                request_id=f"rq{i}",
            )
        )
    return inputs


def device(rt):
    return TpuEvaluator(rt, use_jax=False, min_device_batch=1)


def oracle(rt, inputs, params=None):
    return [check_input(rt, i, params or EvalParams()) for i in inputs]


# -- the differential gate ---------------------------------------------------


class TestDifferentialAttribution:
    def test_device_winning_rule_matches_oracle_everywhere(self, rt):
        """For every (resource, action): same effect, same winning rule FQN,
        same rule-table row id — across resource-policy, scoped-policy, and
        principal-policy wins."""
        inputs = fuzz_inputs()
        dev = device(rt).check(inputs, EvalParams())
        ora = oracle(rt, inputs)
        assert len(dev) == len(ora) == len(inputs)
        seen_kinds = set()
        for d, o in zip(dev, ora):
            assert set(d.actions) == set(o.actions)
            for a in d.actions:
                da, oa = d.actions[a], o.actions[a]
                ctx = f"{d.resource_id}/{a}"
                assert da.effect == oa.effect, ctx
                assert da.matched_rule == oa.matched_rule, ctx
                assert da.rule_row_id == oa.rule_row_id, ctx
                assert da.source == "device", ctx
                assert oa.source == "oracle", ctx
                if da.matched_rule.startswith("principal"):
                    seen_kinds.add("principal")
                elif "team" in da.matched_rule:
                    seen_kinds.add("scoped")
                elif da.matched_rule:
                    seen_kinds.add("resource")
        # the corpus genuinely exercised all three win kinds
        assert seen_kinds == {"principal", "scoped", "resource"}, seen_kinds

    def test_winning_rule_is_activated_in_the_tracer(self, rt):
        """The device's claimed rule must appear among the oracle tracer's
        ACTIVATED rules for that action — provenance is explainable, not
        just self-consistent."""
        from cerbos_tpu.tracer import traced_check

        inputs = fuzz_inputs(n=48, seed=11)
        dev = device(rt).check(inputs, EvalParams())
        checked = 0
        for i, d in zip(inputs, dev):
            _, rec = traced_check(rt, i, EvalParams())
            for a, ae in d.actions.items():
                if not ae.matched_rule or ae.matched_rule.startswith("principal"):
                    # the tracer walks resource-policy bindings only
                    continue
                activated = set()
                for e in rec.events:
                    if not e.activated:
                        continue
                    comps = {c["kind"]: c["id"] for c in e.components}
                    if comps.get("action") == a and "rule" in comps:
                        activated.add(f"{comps.get('policy')}#{comps['rule']}")
                assert ae.matched_rule in activated, (d.resource_id, a, ae.matched_rule, activated)
                checked += 1
        assert checked > 20  # the assertion actually ran

    def test_no_match_carries_no_rule(self, rt):
        out = device(rt).check(
            [
                CheckInput(
                    principal=Principal(id="x", roles=["ghost"]),
                    resource=Resource(kind="widget", id="w0"),
                    actions=["read"],
                )
            ],
            EvalParams(),
        )[0]
        ae = out.actions["read"]
        assert ae.effect == "EFFECT_DENY"
        assert ae.matched_rule == ""
        assert ae.rule_row_id == -1
        assert ae.source == "device"

    def test_bench_corpus_attribution_parity(self):
        """The golden corpus (``bench.py``'s workload) end to end."""
        from cerbos_tpu.util import bench_corpus

        rt = build_rule_table(
            compile_policy_set(list(parse_policies(bench_corpus.corpus_yaml(2))))
        )
        inputs = bench_corpus.requests(128, 2)
        dev = device(rt).check(inputs, EvalParams())
        ora = oracle(rt, inputs)
        for d, o in zip(dev, ora):
            for a in d.actions:
                assert d.actions[a].matched_rule == o.actions[a].matched_rule
                assert d.actions[a].rule_row_id == o.actions[a].rule_row_id


# -- oracle-fallback labeling under chaos ------------------------------------


class OracleEvaluator:
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


def inp(i: int) -> CheckInput:
    return CheckInput(
        principal=Principal(id="u1", roles=["viewer"]),
        resource=Resource(kind="widget", id=f"w{i}", attr={"owner": "u1"}),
        actions=["read"],
        request_id=f"rq{i}",
    )


class TestFallbackLabeling:
    def test_breaker_open_fallback_is_labeled_oracle(self, rt):
        health = DeviceHealth(failure_threshold=1)
        b = BatchingEvaluator(device(rt), max_wait_ms=1.0, health=health)
        try:
            health.record_failure()  # breaker open: requests ride the oracle
            outs = b.check([inp(0), inp(1)])
            for o in outs:
                for ae in o.actions.values():
                    assert ae.source == "oracle"
                    assert ae.matched_rule  # attribution survives the fallback
        finally:
            b.close()

    def test_submit_crash_fallback_is_labeled_oracle(self, rt):
        """Chaos leg: the device path dies mid-flight; the batcher's oracle
        rescue must label its outputs honestly."""
        faulty = FaultInjector(device(rt), "submit_raise:1.0,seed:1")
        b = BatchingEvaluator(faulty, max_wait_ms=1.0)
        try:
            outs = b.check([inp(2)])
            assert outs[0].actions["read"].source == "oracle"
        finally:
            b.close()

    def test_device_path_is_labeled_device(self, rt):
        b = BatchingEvaluator(device(rt), max_wait_ms=1.0)
        try:
            outs = b.check([inp(3)])
            assert outs[0].actions["read"].source == "device"
        finally:
            b.close()


# -- codec carriage ----------------------------------------------------------


class TestCodecCarriage:
    def test_marshal_roundtrip_carries_provenance(self, rt):
        outs = oracle(rt, [inp(i) for i in range(4)])
        decoded = decode_outputs(encode_outputs(outs))
        for o, d in zip(outs, decoded):
            for a in o.actions:
                assert d.actions[a].matched_rule == o.actions[a].matched_rule
                assert d.actions[a].rule_row_id == o.actions[a].rule_row_id
                assert d.actions[a].source == o.actions[a].source

    @needs_native
    def test_native_reply_roundtrip_carries_provenance(self, rt):
        nat = native.get()
        outs = oracle(rt, [inp(i) for i in range(4)])
        assert any(ae.matched_rule for o in outs for ae in o.actions.values())
        frame = nat.reply_pack(outs, (0.001, [], "device", None, 0))
        decoded, _spec = nat.reply_unpack(
            frame, T.CheckOutput, T.ActionEffect, T.ValidationError, T.OutputEntry
        )
        for o, d in zip(outs, decoded):
            for a in o.actions:
                assert d.actions[a].matched_rule == o.actions[a].matched_rule
                assert d.actions[a].rule_row_id == o.actions[a].rule_row_id
                assert d.actions[a].source == o.actions[a].source

    def test_ipc_end_to_end_carries_provenance(self, rt, tmp_path):
        """Front-door topology: the winning rule crosses the ticket queue on
        whichever transport the pair negotiates (shm when native, else uds)."""
        import time

        from cerbos_tpu.engine.ipc import BatcherIpcServer, RemoteBatcherClient

        batcher = BatchingEvaluator(device(rt), max_wait_ms=1.0)
        server = BatcherIpcServer(str(tmp_path / "batcher.sock"), batcher)
        server.start()
        client = RemoteBatcherClient(
            server.socket_path, rt, worker_label="prov-test", status_poll_s=0.05
        )
        try:
            deadline = time.monotonic() + 10.0
            while not client._connected.is_set() and time.monotonic() < deadline:
                time.sleep(0.01)
            assert client._connected.is_set()
            inputs = [inp(i) for i in range(6)]
            outs = client.check(inputs)
            ora = oracle(rt, inputs)
            for d, o in zip(outs, ora):
                for a in o.actions:
                    assert d.actions[a].matched_rule == o.actions[a].matched_rule
                    assert d.actions[a].rule_row_id == o.actions[a].rule_row_id
                    assert d.actions[a].source == "device"
            # hot-rule counters live in the batcher process: the control
            # plane snapshot op must reach them
            snap = client.fetch_hotrules(k=5)
            assert snap["decisions"] >= 6
            assert snap["top"], snap
        finally:
            client.close()
            server.close()
            batcher.close()


# -- sharded lanes -----------------------------------------------------------


class TestShardedAttribution:
    def test_every_lane_attributes_identically(self, rt):
        from cerbos_tpu.engine.shards import build_shard_pool

        pool = build_shard_pool(
            device(rt), n_shards=2, routing="round_robin", max_wait_ms=0.0
        )
        try:
            inputs = [inp(i) for i in range(10)]
            outs = [pool.check([i])[0] for i in inputs]
            ora = oracle(rt, inputs)
            for d, o in zip(outs, ora):
                for a in o.actions:
                    assert d.actions[a].matched_rule == o.actions[a].matched_rule
                    assert d.actions[a].source == "device"
        finally:
            pool.close()


# -- hot-rule recorder -------------------------------------------------------


class TestHotRules:
    def test_snapshot_ranks_and_labels(self, rt):
        rec = HotRuleRecorder()
        outs = oracle(rt, [inp(i) for i in range(8)])
        rec.observe(outs)
        snap = rec.snapshot(k=5, rule_table=rt)
        assert snap["decisions"] == 8
        assert snap["attributed"] == 8
        assert snap["attribution_rate"] == 1.0
        assert snap["by_source"] == {"oracle": 8}
        top = snap["top"]
        assert top and top[0]["hits"] == 8
        assert top[0]["rule"].endswith("#read-any")
        assert 0.99 <= sum(e["share"] for e in top) <= 1.01

    def test_unattributed_counts_separately(self):
        rec = HotRuleRecorder()
        out = T.CheckOutput(
            request_id="r",
            resource_id="x",
            actions={
                "read": T.ActionEffect(
                    effect=T.EFFECT_DENY, policy=T.NO_POLICY_MATCH, source="device"
                )
            },
        )
        rec.observe([out])
        snap = rec.snapshot()
        assert snap["decisions"] == 1
        assert snap["attributed"] == 0
        assert snap["unattributed"] == 1
        assert snap["attribution_rate"] == 0.0

    def test_observe_never_raises(self):
        rec = HotRuleRecorder()
        rec.observe([object()])  # garbage in, telemetry must shrug


# -- includeMeta + audit surfacing -------------------------------------------


class TestSurfacing:
    def test_include_meta_json_carries_rule_and_source(self, rt):
        from cerbos_tpu.server import convert

        body = {
            "requestId": "rq-m",
            "includeMeta": True,
            "principal": {"id": "u1", "roles": ["viewer"]},
            "resources": [
                {"resource": {"kind": "widget", "id": "w1", "attr": {"owner": "u1"}}, "actions": ["read"]}
            ],
        }
        inputs, request_id, include_meta = convert.json_to_check_inputs(body, None)
        assert include_meta
        outs = device(rt).check(inputs, EvalParams())
        resp = convert.outputs_to_json(body, outs, request_id, include_meta, provenance=True)
        meta = resp["results"][0]["meta"]["actions"]["read"]
        assert meta["matchedPolicy"] == "resource.widget.vdefault"
        assert meta["matchedRule"].endswith("#read-any")
        assert meta["source"] == "device"
        # oracle path: same rule, honestly labeled
        resp2 = convert.outputs_to_json(
            body, oracle(rt, inputs), request_id, include_meta, provenance=True
        )
        meta2 = resp2["results"][0]["meta"]["actions"]["read"]
        assert meta2["matchedRule"] == meta["matchedRule"]
        assert meta2["source"] == "oracle"
        # without the opt-in the meta block stays upstream-schema clean —
        # strict proto clients must keep parsing the default response
        plain = convert.outputs_to_json(body, outs, request_id, include_meta)
        assert set(plain["results"][0]["meta"]["actions"]["read"]) == {
            "matchedPolicy",
            "matchedScope",
        }

    def test_audit_entry_records_matched_rule(self, rt):
        from cerbos_tpu.audit.log import _entry_from_decision

        inputs = [inp(0)]
        outs = device(rt).check(inputs, EvalParams())
        entry = _entry_from_decision("c1", inputs, outs, trace_id="t1", shard=2)
        # provenance lives in the top-level PDP-extension block next to
        # traceId/shard — the Cerbos-schema checkResources part stays clean
        action = entry["provenance"][0]["actions"]["read"]
        assert action["matchedRule"].endswith("#read-any")
        assert action["source"] == "device"
        assert "matchedRule" not in entry["checkResources"]["outputs"][0]["actions"]["read"]
        assert entry["traceId"] == "t1" and entry["shard"] == 2


# -- sentinel annotation + replay --explain ----------------------------------


class TestSentinelAnnotation:
    def test_divergence_record_names_both_winning_rules(self, rt, tmp_path):
        """The acceptance drill: a seeded ``flip_effect`` produces a corpus
        record naming the winning rule on BOTH paths, and
        ``replay-divergences --explain`` renders the diff."""
        faulty = FaultInjector(device(rt), "flip_effect:1.0,seed:3")
        batcher = BatchingEvaluator(faulty, max_wait_ms=1.0)
        sentinel = ParitySentinel(
            sample_rate=1.0, storm_threshold=99, corpus_dir=str(tmp_path / "corpus")
        ).attach(batcher)
        try:
            import time

            batcher.check([inp(i) for i in range(4)])
            # the sample is enqueued by the collect thread after check()
            # settles: poll, don't just drain
            deadline = time.monotonic() + 10.0
            while sentinel.stats["divergences"] < 1 and time.monotonic() < deadline:
                sentinel.drain(timeout=0.2)
                time.sleep(0.01)
            assert sentinel.stats["divergences"] >= 1
        finally:
            sentinel.close()
            batcher.close()
        records = DivergenceCorpus.load(str(tmp_path / "corpus"))
        assert records
        _, rec = records[0]
        dev_p, ora_p = rec["device_provenance"], rec["oracle_provenance"]
        assert dev_p and ora_p
        # flip_effect corrupts the effect but PRESERVES the device's claimed
        # rule — triage sees what the device said won
        for row in dev_p:
            for ae in row["actions"].values():
                assert ae["source"] == "device"
                assert ae["matchedRule"]
        for drow, orow in zip(dev_p, ora_p):
            for a in drow["actions"]:
                assert drow["actions"][a]["matchedRule"] == orow["actions"][a]["matchedRule"]

        # the CLI renders the per-record winning-rule diff offline
        import io
        from contextlib import redirect_stdout

        from cerbos_tpu.ctl import _explain_record

        buf = io.StringIO()
        with redirect_stdout(buf):
            _explain_record(rec)
        text = buf.getvalue()
        assert "device[device]" in text
        assert "#read-any" in text

    def test_explain_record_handles_legacy_records(self, capsys):
        from cerbos_tpu.ctl import _explain_record

        _explain_record({"divergent_indices": [0]})
        assert "predates provenance" in capsys.readouterr().out

    def test_provenance_rows_shape(self, rt):
        rows = provenance_rows(oracle(rt, [inp(0)]))
        assert rows[0]["actions"]["read"]["source"] == "oracle"
        assert rows[0]["actions"]["read"]["matchedRule"].endswith("#read-any")


# -- ctl analyze --hot merge -------------------------------------------------


class TestAnalyzeHotMerge:
    def test_ranks_oracle_extinction_targets(self, tmp_path, capsys):
        from cerbos_tpu import ctl

        pol = tmp_path / "policies.yaml"
        pol.write_text(POLICIES)
        rec = HotRuleRecorder()
        rt = table()
        rec.observe(oracle(rt, [inp(i) for i in range(5)]))
        snap = rec.snapshot(k=10, rule_table=rt)
        hot = tmp_path / "hot.json"
        hot.write_text(json.dumps(snap))
        code = ctl.main(["analyze", str(pol), "--hot", str(hot)])
        out = capsys.readouterr().out
        assert code == 0
        assert "hot-rule snapshot" in out
        assert "#read-any" in out


# -- debug endpoints ---------------------------------------------------------


class TestDebugEndpoints:
    def _app(self, rt, evaluator=None):
        from cerbos_tpu.engine.engine import Engine
        from cerbos_tpu.server.server import Server
        from cerbos_tpu.server.service import CerbosService

        eng = Engine(rt, tpu_evaluator=evaluator, tpu_batch_threshold=1)
        return Server(CerbosService(eng))._http_app()

    def test_hotrules_endpoint_local(self, rt):
        import asyncio

        from aiohttp.test_utils import TestClient, TestServer

        rec_rt = rt
        HotRuleRecorder()  # registry warm; the endpoint uses the singleton
        from cerbos_tpu.engine.hotrules import recorder

        recorder().observe(oracle(rec_rt, [inp(i) for i in range(3)]))

        async def run():
            async with TestClient(TestServer(self._app(rec_rt))) as client:
                resp = await client.get("/_cerbos/debug/hotrules?k=3")
                body = await resp.json()
                assert resp.status == 200
                assert body["source"] == "local"
                assert body["decisions"] >= 3
                assert len(body["top"]) <= 3
                bad = await client.get("/_cerbos/debug/hotrules?k=x")
                assert bad.status == 400

        asyncio.run(run())

    def test_explain_endpoint_cross_checks(self, rt):
        import asyncio

        from aiohttp.test_utils import TestClient, TestServer

        app = self._app(rt, evaluator=device(rt))

        async def run():
            async with TestClient(TestServer(app)) as client:
                resp = await client.post(
                    "/_cerbos/debug/explain",
                    json={
                        "requestId": "rq-x",
                        "principal": {"id": "u1", "roles": ["viewer"]},
                        "resources": [
                            {
                                "resource": {"kind": "widget", "id": "w9", "attr": {"owner": "u1"}},
                                "actions": ["read"],
                            }
                        ],
                    },
                )
                body = await resp.json()
                assert resp.status == 200, body
                assert body["device_path"] == "device"
                act = body["results"][0]["actions"]["read"]
                assert act["agree"] is True
                assert act["device"]["matched_rule"].endswith("#read-any")
                assert act["device"]["source"] == "device"
                assert act["device"]["matched_rule"] == act["oracle"]["matched_rule"]
                assert act["device"]["matched_rule"] in act["trace_activated"]
                bad = await client.post("/_cerbos/debug/explain", data=b"{nope")
                assert bad.status == 400

        asyncio.run(run())

    def test_include_meta_provenance_header_opt_in(self, rt):
        """The HTTP check path only emits matchedRule/source when the caller
        sends X-Cerbos-TPU-Provenance — the default includeMeta response
        stays parseable by strict upstream-proto clients (the golden
        compatibility suite holds it to that)."""
        import asyncio

        from aiohttp.test_utils import TestClient, TestServer

        app = self._app(rt, evaluator=device(rt))
        body = {
            "requestId": "rq-h",
            "includeMeta": True,
            "principal": {"id": "u1", "roles": ["viewer"]},
            "resources": [
                {"resource": {"kind": "widget", "id": "wh", "attr": {"owner": "u1"}}, "actions": ["read"]}
            ],
        }

        async def run():
            async with TestClient(TestServer(app)) as client:
                plain = await (await client.post("/api/check/resources", json=body)).json()
                meta = plain["results"][0]["meta"]["actions"]["read"]
                assert set(meta) == {"matchedPolicy", "matchedScope"}
                opted = await (
                    await client.post(
                        "/api/check/resources",
                        json=body,
                        headers={"X-Cerbos-TPU-Provenance": "1"},
                    )
                ).json()
                meta2 = opted["results"][0]["meta"]["actions"]["read"]
                assert meta2["matchedRule"].endswith("#read-any")
                assert meta2["source"] in ("device", "oracle")

        asyncio.run(run())
