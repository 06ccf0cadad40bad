"""Schema enforcement through the served path (cerbos_tpu/schema.py, its call in
``TpuEvaluator._assemble_batch`` and in ``ruletable/check.py``, the reply's
``validation_errors``; docs/OBSERVABILITY.md "Schema validation").

The tiny classic corpus (3 name-mods) with the template's own schemas, served
by ``python -m cerbos_tpu.cli server`` at ``warn``, ``reject`` and ``none`` and
as a ``--frontends 2`` pool, driven over gRPC on the device route (pages) and
the inline route (singles); the oracle route (a flight under
``minDeviceBatch``) through a ``BatchingEvaluator``'s ``check_async``. Every
result's errors are held to the plain reading
(``benchmarks/tools/schema_check.py``: no validator library, nothing of the
program), its effects to the plain reference's; then the instruments: who
counted what under which route, the two parts of ``assemble``, the assembly
memo's outcomes, the cache across a store event, the audit entry, the span.
"""

import json
import logging
import os
import sys
import threading
import time
from datetime import datetime, timedelta, timezone

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.lib import corpus, prom, reference, workload  # noqa: E402
from benchmarks.lib.server import ServerProc, write_policies  # noqa: E402
from benchmarks.tools import schema_check  # noqa: E402

from cerbos_tpu import observability as obs  # noqa: E402
from cerbos_tpu import schema as schema_mod  # noqa: E402
from cerbos_tpu.engine import types as T  # noqa: E402
from cerbos_tpu.policy import model  # noqa: E402
from cerbos_tpu.schema import SchemaManager  # noqa: E402

MODS = 3
METHOD = "/cerbos.svc.v1.CerbosService/CheckResources"
VALIDATIONS = "cerbos_tpu_schema_validations_total"
ERRORS = "cerbos_tpu_schema_errors_total"
STAGE = "cerbos_tpu_batch_stage_seconds"
MEMO = "cerbos_tpu_assemble_memo_total"
PAGES = workload.build(8, MODS, 4301, {"resources": [16, 50]})
SINGLES = workload.build(60, MODS, 4302, {"resources": [1, 1]})
workload.serialize(PAGES + SINGLES)
TABLE = schema_check.Table.of_corpus(MODS)


def write_corpus(directory) -> str:
    policy_dir = os.path.join(str(directory), "policies")
    write_policies(policy_dir, corpus.corpus_yaml(MODS).split("\n---\n"), MODS)
    return policy_dir


# -- served processes, one per level and topology, shared by the tests below ------


class Served:
    def __init__(self, tmp, level: str, frontends: int = 0):
        work = str(tmp)
        settings = {"schema.enforcement": level, "engine.tpu.requestTimeoutMs": 120000}
        if frontends:
            settings.update({"server.frontends": frontends, "engine.tpu.sharedBatcher.requestTimeoutMs": 120000})
        self.srv = ServerProc(work, write_corpus(work), settings, log=lambda line: None)
        self.srv.wait_serving(timeout=180)
        import grpc

        self.channel = grpc.insecure_channel(f"127.0.0.1:{self.srv.grpc_port}")
        self.call = self.channel.unary_unary(METHOD, request_serializer=None, response_deserializer=None)

    def send(self, reqs) -> list:
        """(request, decoded reply, raw reply, clock before, clock after), one request at a time."""
        from cerbos_tpu.api.cerbos.response.v1 import response_pb2

        out = []
        for req in reqs:
            lo = datetime.now(timezone.utc) - timedelta(seconds=2)
            raw = self.call(req.wire, timeout=120)
            hi = datetime.now(timezone.utc) + timedelta(seconds=2)
            out.append((req, response_pb2.CheckResourcesResponse.FromString(raw), raw, lo, hi))
        return out

    def scrape(self) -> dict:
        return self.srv.scrape()[0]

    def flights(self) -> list[dict]:
        return self.srv.get_json("/_cerbos/debug/flight")["batches"]

    def close(self) -> None:
        self.channel.close()
        assert self.srv.stop() == 0


@pytest.fixture(scope="module")
def servers(tmp_path_factory):
    """``servers(level, frontends=0)``: booted at first use, stopped with the module."""
    booted: dict = {}

    def get(level: str, frontends: int = 0) -> Served:
        key = (level, frontends)
        if key not in booted:
            booted[key] = Served(tmp_path_factory.mktemp(f"{level}{frontends}"), level, frontends)
        return booted[key]

    yield get
    for s in booted.values():
        s.close()


def served_errors(reply) -> list[list[tuple[str, str, str]]]:
    from cerbos_tpu.api.cerbos.schema.v1 import schema_pb2

    return [
        [(schema_pb2.ValidationError.Source.Name(e.source), e.path, e.message) for e in result.validation_errors]
        for result in reply.results
    ]


def effects_problem(req, reply, lo, hi, rejecting: bool) -> str | None:
    """The reference's effects (either end of the clock's bracket), with every
    action of an input that has errors denied where the level rejects."""
    got = [{a: workload.EFFECT_NAMES.get(e, str(e)) for a, e in r.actions.items()} for r in reply.results]
    wants = []
    for now in (lo, hi):
        want = req.expected(now)
        if rejecting:
            want = [dict.fromkeys(w, reference.DENY) if found else w for w, found in zip(want, TABLE.expected(req))]
        wants.append(want)
    return None if got in wants else f"got {got} want {wants[0]}"


def moved(before: dict, after: dict, name: str, **labels) -> float:
    return prom.total(prom.delta(before, after), name, **labels)


ROUTE_TRAFFIC = {"device": PAGES, "inline": SINGLES}


@pytest.mark.parametrize("route", ["device", "inline"])
@pytest.mark.parametrize("level", ["warn", "reject"])
def test_a_served_reply_carries_the_plain_readings_errors_and_the_levels_effects(servers, level, route):
    s = servers(level)
    reqs = ROUTE_TRAFFIC[route]
    before = s.scrape()
    answers = s.send(reqs)
    after = s.scrape()
    for req, reply, _, lo, hi in answers:
        assert reply.request_id == req.request_id
        assert schema_check.diff(TABLE.expected(req), served_errors(reply)) is None
        assert effects_problem(req, reply, lo, hi, rejecting=level == "reject") is None
    want = schema_check.totals(TABLE, reqs)
    assert want["errors"] > 0 and want["inputs_with_errors"] < want["inputs"]
    # counted once, under the route that answered, by exactly what the plain reading finds
    assert moved(before, after, ERRORS) == want["errors"]
    assert moved(before, after, ERRORS, source="principal") == want["errors_principal"]
    assert moved(before, after, "cerbos_tpu_schema_validate_seconds_count") == want["validations"]
    # ... each run made by a compiled validator (tests/test_schema_compiled.py), python-jsonschema's none
    assert moved(before, after, "cerbos_tpu_schema_validator_runs_total", engine="compiled") == want["validations"]
    assert moved(before, after, "cerbos_tpu_schema_validator_runs_total", engine="generic") == 0
    assert prom.total(after, "cerbos_tpu_schema_validators_compiled") == 3 * MODS
    for r in ("device", "inline", "oracle"):
        ran = sum(moved(before, after, VALIDATIONS, route=r, outcome=o) for o in ("valid", "invalid"))
        assert ran == (want["validations"] if r == route else 0), r
    assert moved(before, after, VALIDATIONS, outcome="invalid", source="resource") == want["inputs_failing_resource"]


@pytest.mark.parametrize("route", ["device", "inline"])
def test_under_warn_no_effect_differs_from_none(servers, route):
    """The guarantee ``correct`` holds the cell to: the same requests, the two
    levels, the harness's own comparison."""
    for level in ("warn", "none"):
        for req, _, raw, lo, hi in servers(level).send(ROUTE_TRAFFIC[route]):
            assert workload.compare(req, raw, lo, hi) is None


@pytest.mark.parametrize("route", ["device", "inline"])
def test_with_none_nothing_is_validated_counted_or_loaded(servers, route):
    s = servers("none")
    before = s.scrape()
    answers = s.send(ROUTE_TRAFFIC[route])
    after = s.scrape()
    assert all(not any(served_errors(reply)) for _, reply, _, _, _ in answers)
    # every series is there from boot, at 0, and stays there
    assert len([k for k in after if k[0] == VALIDATIONS]) == 2 * 4 * 3 and len([k for k in after if k[0] == ERRORS]) == 2
    for name in (VALIDATIONS, ERRORS, "cerbos_tpu_schema_validate_seconds_count", "cerbos_tpu_schema_validators",
                 "cerbos_tpu_schema_validators_compiled", "cerbos_tpu_schema_validator_runs_total",
                 "cerbos_tpu_schema_cache_resets_total"):
        assert prom.has(after, name) and prom.total(after, name) == 0, name
    assert moved(before, after, MEMO, result="bypass_validation") == 0


def test_with_none_assemble_never_enters_its_schema_part(servers):
    s = servers("none")
    before = s.scrape()
    s.send(PAGES)
    after = s.scrape()
    assert moved(before, after, STAGE + "_count", stage="assemble_outputs") == len(PAGES)
    assert prom.total(after, STAGE + "_count", stage="assemble_schema") == 0
    assert (STAGE + "_count", (("shard", "0"), ("stage", "assemble_schema"))) in after  # at 0 from boot
    assert moved(before, after, STAGE + "_sum", stage="assemble_outputs") == pytest.approx(
        moved(before, after, STAGE + "_sum", stage="assemble"), abs=1e-9
    )
    assert all("assemble_schema" not in f["timings"] for f in s.flights() if "assemble" in f["timings"])


def test_under_warn_the_two_parts_tile_assemble_flight_by_flight(servers):
    s = servers("warn")
    before = s.scrape()
    s.send(PAGES)
    after = s.scrape()
    parts = {p: moved(before, after, STAGE + "_sum", stage=p) for p in ("assemble", "assemble_schema", "assemble_outputs")}
    assert parts["assemble_schema"] > 0 and parts["assemble_outputs"] > 0
    assert parts["assemble_schema"] + parts["assemble_outputs"] == pytest.approx(parts["assemble"], abs=1e-9)
    for p in parts:
        assert moved(before, after, STAGE + "_count", stage=p) == len(PAGES)
    flights = [f["timings"] for f in s.flights() if f["timings"].get("assemble_schema")]
    assert len(flights) >= len(PAGES)
    for t in flights:  # each rounded to a microsecond in the record
        assert t["assemble_schema"] + t["assemble_outputs"] == pytest.approx(t["assemble"], abs=1.6e-6)


@pytest.mark.parametrize("level", ["warn", "none"])
def test_the_memos_outcomes_add_up_to_the_device_served_inputs(servers, level):
    s = servers(level)
    s.send(PAGES)  # the memo has met these pages: the second pass hits wherever it may
    before = s.scrape()
    s.send(PAGES)
    after = s.scrape()
    want = schema_check.totals(TABLE, PAGES)
    got = {r: moved(before, after, MEMO, result=r) for r in ("hit", "miss", "bypass_validation", "bypass_other")}
    # every input of a device flight but those with no candidate rule (some of the no-policy salary records: trivial)
    no_policy = sum(res["kind"].startswith("salary_record") for req in PAGES for res, _ in req.entries)
    assert want["inputs"] - no_policy <= sum(got.values()) <= want["inputs"]
    assert moved(before, after, "cerbos_tpu_decision_source_total", source="device") == sum(
        len(a) for req in PAGES for _, a in req.entries
    )
    assert got["bypass_validation"] == (want["inputs_with_errors"] if level == "warn" else 0)
    assert got["miss"] == 0 and got["bypass_other"] == 0 and got["hit"] == sum(got.values()) - got["bypass_validation"]


def test_a_kind_without_schemas_is_counted_no_schema_and_carries_no_error(servers):
    s = servers("warn")
    req = next(r for r in SINGLES if r.entries[0][0]["kind"].startswith("diverse_record"))
    before = s.scrape()
    ((_, reply, _, _, _),) = s.send([req])
    after = s.scrape()
    assert served_errors(reply) == [[]]
    for source in ("principal", "resource"):
        assert moved(before, after, VALIDATIONS, source=source, outcome="no_schema", route="inline") == 1
    assert moved(before, after, VALIDATIONS) == 2 and moved(before, after, "cerbos_tpu_schema_validate_seconds_count") == 0


def test_the_validators_are_loaded_at_boot_not_inside_a_request(servers):
    s = servers("reject")
    stats = s.scrape()
    assert prom.total(stats, "cerbos_tpu_schema_validators", state="loaded") == 3 * MODS  # every schema the policies name
    assert prom.total(stats, "cerbos_tpu_schema_validators", state="failed") == 0
    with open(s.srv.stderr_path) as f:
        assert "failed to load" not in f.read()  # the warning a missing schema gets at boot (the in-process test below reads it)


@pytest.mark.parametrize("shape", ["page", "single"])
def test_a_front_end_answers_with_the_same_errors_counted_where_they_were_found(servers, shape):
    s = servers("warn", frontends=2)
    reqs = PAGES[:4] if shape == "page" else SINGLES[:30]
    before = s.scrape()
    answers = s.send(reqs)
    after = s.scrape()
    for req, reply, raw, lo, hi in answers:
        assert schema_check.diff(TABLE.expected(req), served_errors(reply)) is None
        assert workload.compare(req, raw, lo, hi) is None
    want = schema_check.totals(TABLE, reqs)
    by_worker = {
        w: sum(moved(before, after, VALIDATIONS, worker=w, outcome=o) for o in ("valid", "invalid"))
        for w in ("fe1", "fe2", "batcher")
    }
    if shape == "page":  # a page is the owner's flight: validated on its drain thread
        assert by_worker == {"fe1": 0, "fe2": 0, "batcher": want["validations"]}
        assert moved(before, after, VALIDATIONS, worker="batcher", route="device") >= want["validations"]
    else:  # a single is answered by the front end that took it, on the request's thread
        assert by_worker["batcher"] == 0 and by_worker["fe1"] + by_worker["fe2"] == want["validations"]
        assert moved(before, after, VALIDATIONS, route="inline") == 2 * len(reqs)
    assert moved(before, after, ERRORS) == want["errors"]


# -- the oracle route, the cache and the instruments, in this process ----------------


@pytest.fixture()
def disk(tmp_path):
    from cerbos_tpu.storage.disk import DiskStore

    store = DiskStore(write_corpus(tmp_path))
    yield store
    store.close()


def rule_table_of(store):
    from cerbos_tpu.compile import compile_policy_set
    from cerbos_tpu.ruletable import build_rule_table

    return build_rule_table(compile_policy_set(store.get_all()))


def inputs_of(req) -> list[T.CheckInput]:
    p = req.principal
    principal = T.Principal(id=p["id"], roles=list(p["roles"]), attr=p["attr"], policy_version=p["policyVersion"], scope=p["scope"])
    return [
        T.CheckInput(
            request_id=req.request_id, principal=principal, actions=list(actions),
            resource=T.Resource(kind=r["kind"], id=r["id"], attr=r["attr"], policy_version=r["policyVersion"], scope=r["scope"]),
        )
        for r, actions in req.entries
    ]


def errors_of(outputs) -> list[list[tuple[str, str, str]]]:
    return [[(e.source, e.path, e.message) for e in o.validation_errors] for o in outputs]


def counted(name: str, label, key) -> float:
    return obs.metrics().counter_vec(name, label=label).get(key)


def validations(route: str) -> float:
    return sum(
        counted(VALIDATIONS, ("source", "outcome", "route"), (s, o, route))
        for s in ("principal", "resource") for o in ("valid", "invalid")
    )


@pytest.mark.parametrize("level", ["warn", "reject"])
def test_a_flight_under_min_device_batch_is_validated_on_the_oracle_route(disk, level):
    from cerbos_tpu.engine.batcher import BatchingEvaluator
    from cerbos_tpu.tpu import TpuEvaluator

    mgr = SchemaManager(disk, level)
    ev = TpuEvaluator(rule_table_of(disk), schema_mgr=mgr, use_jax=False)  # min_device_batch 16
    batcher = BatchingEvaluator(ev, max_wait_ms=1.0)
    req = next(r for r in SINGLES if r.entries[0][0]["kind"].startswith("leave_request") and TABLE.expected(r)[0])
    before = {r: validations(r) for r in schema_mod.ROUTES}
    try:
        outputs = batcher.check_async(inputs_of(req)).result(timeout=30)  # check_async always queues: a flight of one
    finally:
        batcher.close()
    assert schema_check.diff(TABLE.expected(req), errors_of(outputs)) is None
    assert {r: validations(r) - before[r] for r in schema_mod.ROUTES} == {"device": 0, "oracle": 2, "inline": 0}
    effects = {a.effect for a in outputs[0].actions.values()}
    assert (effects == {T.EFFECT_DENY}) if level == "reject" else (T.EFFECT_ALLOW in effects)
    if level == "reject":
        assert {a.policy for a in outputs[0].actions.values()} == {f"resource.{req.entries[0][0]['kind']}.v20210210"}


def test_a_replay_that_answers_no_one_is_validated_and_not_counted(disk):
    from cerbos_tpu.ruletable import check_input

    mgr = SchemaManager(disk, "reject")
    rt = rule_table_of(disk)
    req = next(r for r in SINGLES if TABLE.expected(r)[0])
    before = obs.metrics().counter_vec(VALIDATIONS, label=("source", "outcome", "route")).value
    seconds = obs.metrics().histogram_vec("cerbos_tpu_schema_validate_seconds", label="source")
    observed = sum(seconds.labels(s).count for s in ("principal", "resource"))
    (inp,) = inputs_of(req)
    out = check_input(rt, inp, T.EvalParams(), mgr, schema_mod.ROUTE_SHADOW)  # the parity sentinel's and the rollout gate's route
    assert schema_check.diff(TABLE.expected(req), errors_of([out])) is None  # it decides effects under reject: validated
    assert {a.effect for a in out.actions.values()} == {T.EFFECT_DENY}
    assert obs.metrics().counter_vec(VALIDATIONS, label=("source", "outcome", "route")).value == before
    assert sum(seconds.labels(s).count for s in ("principal", "resource")) == observed


def schema_series() -> dict:
    return {k: v for k, v in prom.parse(obs.metrics().render()).items() if k[0].startswith("cerbos_tpu_schema_v") or k[0] == ERRORS}


def test_a_flights_tally_books_what_its_validations_would_have_booked_one_by_one(disk):
    mgr = SchemaManager(disk, "warn")
    rt = rule_table_of(disk)
    mgr.load(rt)
    page = [(rt.get_schema(f"cerbos.resource.{i.resource.kind}.v{i.resource.policy_version or 'default'}"), i) for i in inputs_of(PAGES[2])]
    before = schema_series()
    one_by_one = [mgr.validate_check_input(schemas, inp, route="device") for schemas, inp in page]
    middle = schema_series()
    tally = schema_mod.Tally()
    gathered = [mgr.validate_check_input(schemas, inp, route="device", tally=tally) for schemas, inp in page]
    assert schema_series() == middle  # nothing is booked until the flight books it
    mgr.book(tally)
    after = schema_series()
    assert gathered == one_by_one and any(errors for errors, _ in gathered)
    counts = [k for k in after if not k[0].endswith(("_sum", "_bucket")) or ("le", "+Inf") in k[1]]  # the seconds themselves differ
    moved_1 = {k: middle[k] - before[k] for k in counts}
    moved_2 = {k: after[k] - middle[k] for k in counts}
    assert moved_1 == moved_2 and sum(moved_1.values()) > 0
    runs = sum(v for k, v in moved_2.items() if k[0].endswith("_count"))
    assert runs == sum(v for k, v in moved_2.items() if k[0] == VALIDATIONS and dict(k[1])["outcome"] in ("valid", "invalid"))


@pytest.mark.parametrize("actions,ignored", [(["view:public", "view:private"], True), (["view:public", "approve"], False)])
def test_ignore_when_actions_skips_the_validation_and_says_so(disk, actions, ignored):
    mgr = SchemaManager(disk, "warn")
    schemas = model.Schemas(
        principal_schema=model.SchemaRef("cerbos:///principal_0.json", ignore_when_actions=["view:*"]),
        resource_schema=model.SchemaRef("cerbos:///leave_request_0.json"),
    )
    inp = T.CheckInput(
        request_id="x", principal=T.Principal(id="p", roles=["employee"], attr={}), actions=actions,
        resource=T.Resource(kind="leave_request_0", id="r", attr={}),
    )
    was = counted(VALIDATIONS, ("source", "outcome", "route"), ("principal", "ignored", "oracle"))
    errors, reject = mgr.validate_check_input(schemas, inp)
    assert not reject
    assert counted(VALIDATIONS, ("source", "outcome", "route"), ("principal", "ignored", "oracle")) - was == (1 if ignored else 0)
    assert [e.source for e in errors] == (["SOURCE_RESOURCE"] if ignored else ["SOURCE_PRINCIPAL", "SOURCE_RESOURCE"])
    assert all(e.path == "/" and e.message.startswith("missing properties: 'department', 'geography', 'team'") for e in errors)


def test_a_missing_schema_is_counted_failed_at_load_and_still_answers_per_input(disk, caplog):
    os.remove(os.path.join(disk.directory, "_schemas", "leave_request_1.json"))
    with open(os.path.join(disk.directory, "_schemas", "leave_request_2.json"), "w") as f:
        f.write("{not json")
    mgr = SchemaManager(disk, "warn")
    rt = rule_table_of(disk)
    with caplog.at_level(logging.WARNING, logger="cerbos_tpu.schema"):
        assert mgr.load(rt) == (3 * MODS - 2, 2)
    assert "2 of 9 schemas named by the policies failed to load" in caplog.text and "leave_request_1.json" in caplog.text
    gauges = obs.metrics().gauge_vec("cerbos_tpu_schema_validators", label="state")
    assert (gauges.get("loaded"), gauges.get("failed")) == (3 * MODS - 2, 2)
    req = next(r for r in SINGLES if r.entries[0][0]["kind"] == "leave_request_1")
    errors, _ = mgr.validate_check_input(rt.get_schema("cerbos.resource.leave_request_1.vdefault"), inputs_of(req)[0])
    assert [(e.source, e.path, e.message) for e in errors if e.source == "SOURCE_RESOURCE"] == [
        ("SOURCE_RESOURCE", "", "failed to load schema cerbos:///leave_request_1.json")
    ]
    assert schema_check.keyword_of(errors[-1].message) == "load"


def test_with_none_load_builds_nothing(disk):
    mgr = SchemaManager(disk, "none")
    assert mgr.load(rule_table_of(disk)) == (0, 0) and mgr._cache == {}


def test_a_store_event_empties_the_cache_and_the_next_request_sees_the_new_schema(disk):
    mgr = SchemaManager(disk, "warn")
    rt = rule_table_of(disk)
    mgr.load(rt)
    req = next(r for r in SINGLES if r.entries[0][0]["kind"] == "leave_request_0" and not r.entries[0][0]["scope"])
    (inp,) = inputs_of(req)
    schemas = rt.get_schema("cerbos.resource.leave_request_0.v20210210")
    assert [e.message for e in mgr.validate_check_input(schemas, inp)[0]] == ["missing properties: 'team'"]
    path = os.path.join(disk.directory, "_schemas", "leave_request_0.json")
    with open(path) as f:
        relaxed = json.load(f)
    relaxed["required"].remove("team")
    with open(path, "w") as f:
        json.dump(relaxed, f)
    resets = obs.metrics().counter("cerbos_tpu_schema_cache_resets_total")
    was = resets.value
    assert mgr.validate_check_input(schemas, inp)[0]  # the file changed and no event came: the loaded validator still holds
    disk.reload()  # the operator's `store reload`: an event
    assert resets.value - was == 1 and mgr._cache == {}
    assert obs.metrics().gauge_vec("cerbos_tpu_schema_validators", label="state").get("loaded") == 0
    assert mgr.validate_check_input(schemas, inp) == ([], False)
    assert mgr.load(rt) == (3 * MODS, 0)  # what a cutover's subscriber does: the rest of the table's refs, ahead of traffic


def test_a_validator_built_from_what_the_store_held_before_an_event_is_never_filed_after_it(disk):
    mgr = SchemaManager(disk, "warn")
    get_schema = disk.get_schema
    entered, go = threading.Event(), threading.Event()

    def slow_get_schema(schema_id):
        raw = get_schema(schema_id)  # what the store holds NOW
        entered.set()
        assert go.wait(10)
        return raw

    disk.get_schema = slow_get_schema
    built = []
    t = threading.Thread(target=lambda: built.append(mgr._validator("cerbos:///principal_0.json")))
    t.start()
    assert entered.wait(10)
    disk.reload()  # the event lands while the old bytes are being compiled
    go.set()
    t.join(10)
    assert not t.is_alive() and built[0].engine == schema_mod.ENGINE_COMPILED  # the request in hand is answered
    assert "cerbos:///principal_0.json" not in mgr._cache  # ... and the next one builds from the store again


def test_the_bootstrap_loads_the_validators_at_boot_and_again_at_a_cutover(tmp_path):
    from cerbos_tpu import bootstrap
    from cerbos_tpu.config import Config

    core = bootstrap.initialize(
        Config({"storage": {"driver": "disk", "disk": {"directory": write_corpus(tmp_path)}},
                "schema": {"enforcement": "warn"}, "engine": {"tpu": {"enabled": False}}}),
    )
    try:
        gauges = obs.metrics().gauge_vec("cerbos_tpu_schema_validators", label="state")
        assert gauges.get("loaded") == 3 * MODS
        assert "schemas" in core.rollout.subscribers
        core.store.reload()  # event -> cache emptied -> staged rollout -> cutover -> the subscriber loads them again
        deadline = time.monotonic() + 30
        while gauges.get("loaded") != 3 * MODS and time.monotonic() < deadline:
            time.sleep(0.05)
        assert gauges.get("loaded") == 3 * MODS
    finally:
        core.close()


def test_the_audit_entry_of_a_validated_page_carries_the_replys_errors(disk, tmp_path):
    from cerbos_tpu.audit.log import new_audit_log
    from cerbos_tpu.engine.batcher import BatchingEvaluator
    from cerbos_tpu.engine.engine import Engine
    from cerbos_tpu.server.service import CerbosService
    from cerbos_tpu.tpu import TpuEvaluator

    mgr = SchemaManager(disk, "warn")
    rt = rule_table_of(disk)
    batcher = BatchingEvaluator(TpuEvaluator(rt, schema_mgr=mgr, use_jax=False), max_wait_ms=1.0)
    log = new_audit_log({"enabled": True, "backend": "file", "file": {"path": str(tmp_path / "audit.log")}})
    page = PAGES[0]
    try:
        svc = CerbosService(Engine(rt, schema_mgr=mgr, tpu_evaluator=batcher, tpu_batch_threshold=1), audit_log=log)
        outputs, _ = svc.check_resources(inputs_of(page))
    finally:
        batcher.close()
        log.close()
    with open(tmp_path / "audit.log") as f:
        (entry,) = [e for e in map(json.loads, f) if e["kind"] == "decision"]
    logged = [
        [(v["source"], v["path"], v["message"]) for v in o.get("validationErrors", [])]
        for o in entry["checkResources"]["outputs"]
    ]
    assert logged == errors_of(outputs) and any(logged)
    assert schema_check.diff(TABLE.expected(page), logged) is None


class Spans(obs.SpanExporter):
    def __init__(self):
        self.spans = []

    def export(self, span, duration_ms):
        self.spans.append(span)


@pytest.mark.parametrize("level", ["warn", "none"])
def test_the_requests_span_counts_its_validation_errors(disk, level):
    from cerbos_tpu.engine.engine import Engine
    from cerbos_tpu.server.service import CerbosService

    mgr = SchemaManager(disk, level)
    rt = rule_table_of(disk)
    page = PAGES[1]
    exporter, old = Spans(), obs._exporter
    obs.set_exporter(exporter)
    try:
        CerbosService(Engine(rt, schema_mgr=mgr)).check_resources(inputs_of(page))
    finally:
        obs.set_exporter(old)
    (span,) = [s for s in exporter.spans if s.name == "request.CheckResources"]
    if level == "warn":
        assert span.attributes["validation_errors"] == sum(len(found) for found in TABLE.expected(page)) > 0
    else:
        assert "validation_errors" not in span.attributes


# -- the wording: python-jsonschema's errors as upstream's validator words them -----

WORDING = {
    "required, one error for the object": (
        {"type": "object", "required": ["a", "b", "c"]}, {"b": 1}, [("/", "missing properties: 'a', 'c'")]
    ),
    "enum": ({"properties": {"d": {"enum": ["x", "y", 3]}}}, {"d": "z"}, [("/d", 'value must be one of "x", "y", 3')]),
    "type": ({"properties": {"d": {"type": "string"}}}, {"d": 5}, [("/d", "expected string, but got number")]),
    "type, of several": (
        {"properties": {"d": {"type": ["object", "boolean"]}}}, {"d": "s"}, [("/d", "expected object or boolean, but got string")]
    ),
    "a wrong type is checked no further": (
        {"properties": {"d": {"type": "string", "enum": ["x"]}}}, {"d": None}, [("/d", "expected string, but got null")]
    ),
    "additionalProperties": (
        {"properties": {"a": {}}, "additionalProperties": False}, {"a": 1, "x": 2, "y": 3},
        [("/", "additionalProperties 'x', 'y' not allowed")],
    ),
    "nested required, at the object's path": (
        {"properties": {"o": {"type": "object", "required": ["k", "l"]}}, "required": ["o", "p"]}, {"o": {}},
        [("/", "missing properties: 'p'"), ("/o", "missing properties: 'k', 'l'")],
    ),
    "a keyword with no wording of upstream's here keeps python-jsonschema's": (
        {"properties": {"n": {"minLength": 3}}}, {"n": "ab"}, [("/n", "'ab' is too short")]
    ),
}


@pytest.mark.parametrize("name", list(WORDING))
def test_errors_read_as_upstreams_validator_words_them(name):
    raw, value, want = WORDING[name]

    class OneSchema:
        def get_schema(self, schema_id):
            return json.dumps(raw).encode()

        def subscribe(self, fn):
            pass

    mgr = SchemaManager(OneSchema(), "warn")
    inp = T.CheckInput(
        request_id="x", principal=T.Principal(id="p", roles=[], attr=value), actions=["a"],
        resource=T.Resource(kind="k", id="r", attr={}),
    )
    errors, _ = mgr.validate_check_input(model.Schemas(principal_schema=model.SchemaRef("cerbos:///s.json")), inp)
    assert sorted((e.path, e.message) for e in errors) == sorted(want)
    assert {e.source for e in errors} == {"SOURCE_PRINCIPAL"}
