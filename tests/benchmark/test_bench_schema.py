"""The validated deployment (``classic-800-schema``) under the harness, at a tiny
size on the CPU: its ``schema`` block through ``Session``, device-served pages
and singles answered by the CPU walk both; before the server stops the test
sends the window's requests once more itself and holds every result's
``validation_errors`` to the plain reading (``benchmarks/tools/schema_check.py``);
the window's count of errors is the tool's total; the stale-policies control
still comes out wrong; and the traced line reads a number for each of the
cell's six metrics. No chip: nothing measured here is a device number."""

import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchmark_rig as rig  # noqa: E402

from benchmarks import run  # noqa: E402
from benchmarks.lib import loadgen, session, spec, trace_reduce, workload  # noqa: E402
from benchmarks.tools import schema_check  # noqa: E402
from benchmarks.tools.control import stale_policies  # noqa: E402

CELL = "classic-800-schema.pages"
SIX = {
    "assemble_schema_mean_ms.pages", "assemble_outputs_mean_ms.pages", "schema_validate_mean_us.pages",
    "schema_validations_per_page.pages", "schema_errors_per_page.pages", "assemble_memo_hit_share.pages",
}


def add_tiny_schema(root: str) -> None:
    """``tiny-schema``: the validated configuration at 3 name-mods with a cell
    on each mix, added as ``benchmark_rig.add_tiny`` adds its own."""
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(bench, "configs", "classic-800-schema.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "tiny-schema"
    cfg["corpus"]["mods"] = rig.TINY_MODS
    with open(os.path.join(bench, "configs", "tiny-schema.json"), "w") as f:
        json.dump(cfg, f)
    for mix, rate in (("pages", 40.0), ("sidecar", 80.0)):
        with open(os.path.join(bench, "traffic", "rates", f"tiny-schema.{mix}.json"), "w") as f:
            json.dump({"rate": rate}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "tiny-schema", "source": cfg["source"], "file": "benchmarks/configs/tiny-schema.json", "reduced": [], "why": "test"}
    )
    for mix, metric in (("pages", "page_p50_ms"), ("sidecar", "check_p50_ms")):
        manifest["workloads"].append({"name": f"tiny-schema.{mix}", "config": "tiny-schema", "traffic": mix, "chips": 1, "why": "test"})
        for m in manifest["end_to_end"]:
            if m["name"] == metric:
                m["workloads"].append(f"tiny-schema.{mix}")
    for m in manifest["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-schema.pages")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)


@pytest.fixture()
def root(tmp_path):
    os.makedirs(tmp_path / "bench_root")
    root = rig.copy_benchmark(str(tmp_path / "bench_root"))
    add_tiny_schema(root)
    return root


@pytest.fixture()
def resent(monkeypatch):
    """Before the session stops its server, every request of the window is sent
    to it once more, one at a time, and the replies kept: ``{index: reply}``."""
    import grpc

    from cerbos_tpu.api.cerbos.response.v1 import response_pb2

    box: dict = {}
    stop = session.Session.stop

    def resend_then_stop(ses, out_dir):
        with open(os.path.join(ses.work, "wires.pickle"), "rb") as f:
            import pickle

            wires = pickle.load(f)  # written by this process's own Session.prepare
        with grpc.insecure_channel(f"127.0.0.1:{ses.srv.grpc_port}") as ch:
            call = ch.unary_unary(loadgen.METHOD, request_serializer=None, response_deserializer=None)
            for k, wire in enumerate(wires):
                box[k] = response_pb2.CheckResourcesResponse.FromString(call(wire, timeout=60))
        return stop(ses, out_dir)

    monkeypatch.setattr(session.Session, "stop", resend_then_stop)
    return box


def served_errors(reply) -> list[list[tuple[str, str, str]]]:
    from cerbos_tpu.api.cerbos.schema.v1 import schema_pb2

    return [
        [(schema_pb2.ValidationError.Source.Name(e.source), e.path, e.message) for e in result.validation_errors]
        for result in reply.results
    ]


def hold_to_the_plain_reading(replies: dict, reqs: list) -> dict:
    table = schema_check.Table.of_corpus(rig.TINY_MODS)
    assert len(replies) >= len(reqs)
    for req in reqs:
        assert replies[req.index].request_id == req.request_id
        assert schema_check.diff(table.expected(req), served_errors(replies[req.index])) is None
    return schema_check.totals(table, reqs)


def test_the_cell_is_in_the_manifest_with_the_issues_parameters():
    cell = spec.Cell(rig.REPO, CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == ("classic-800-schema", "pages", 1)
    assert cell.pair["rate"] in (40, 30, 20, 10)  # 40, or what the rate's rule stepped down to (PERF.md section 4)
    assert cell.traffic["connections"] == 4 and cell.traffic["request"] == {"resources": [16, 50]}
    assert cell.config["reduced"] == [] and cell.config["corpus"] == {"generator": "classic", "mods": 100}
    server = {k: v["value"] for k, v in cell.config["assumed"]["server"].items()}
    assert server == {"engine.tpu.requestTimeoutMs": 600000, "schema.enforcement": "warn"}
    assert all(v["why"] for v in cell.config["assumed"]["server"].values())
    for key in ("enforcement_level", "requests_not_clean", "error_wording"):
        assert cell.config["assumed"][key]
    assert [m["name"] for m in cell.end_to_end] == ["page_p50_ms", "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    # every .pages metric of the twin cell follows by the manifest's rule, and the six are this cell's alone
    twin = {m["name"] for m in spec.Cell(rig.REPO, "classic-800.pages").per_layer}
    assert names - twin == SIX and twin <= names
    with open(os.path.join(rig.REPO, "benchmarks", "configs", "classic-800.json")) as f:
        base = json.load(f)
    for key in ("corpus", "layout", "reduced"):
        assert cell.config[key] == base[key]
    assert cell.config["guarantees"][: len(base["guarantees"])] == base["guarantees"] and len(cell.config["guarantees"]) == 6
    assert "schema_check" in cell.config["guarantees_held_by"] and "schema_check" in cell.config["reference"]


def test_the_pinned_digest_is_the_twin_cells_own():
    expected = os.path.join(rig.REPO, "benchmarks", "expected")
    with open(os.path.join(expected, "classic-800-schema.pages.seed0.sha256")) as f, \
            open(os.path.join(expected, "classic-800.pages.seed0.sha256")) as g:
        assert f.read() == g.read()  # under warn no effect changes: the reference's answers are classic-800's


def test_traced_pages_run_is_correct_validates_every_page_and_reads_the_six(root, tmp_path, monkeypatch, resent):
    # no TPU plane in a CPU trace: the host's plane stands in, to drive the plumbing only
    monkeypatch.setattr(trace_reduce, "DEVICE_PLANE", re.compile(r"^/host:CPU$"))
    seed = 2**31 + 71
    res = run.run_cell("tiny-schema.pages", seed, 2.0, 1, root=root, require_platform=None, out_dir=str(tmp_path / "out"))
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 80
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert SIX <= set(got), SIX - set(got)
    assert got["oracle_share.pages"] == 0.0 and got["inline_share.pages"] == 0.0  # every page device-served
    parts = got["assemble_schema_mean_ms.pages"] + got["assemble_outputs_mean_ms.pages"]
    assert parts == pytest.approx(got["assemble_mean_ms.pages"], rel=0.01)  # the two parts tile the stage, on the line as in the program
    assert got["assemble_schema_mean_ms.pages"] > 0 and got["schema_validate_mean_us.pages"] > 0
    reqs = workload.build(80, rig.TINY_MODS, seed, {"resources": [16, 50]})
    want = hold_to_the_plain_reading(resent, reqs)
    # the window's counters against the tool's totals for the seed: exact
    assert got["schema_errors_per_page.pages"] * 80 == pytest.approx(want["errors"], abs=1e-6) and want["errors"] > 0
    assert got["schema_validations_per_page.pages"] * 80 == pytest.approx(want["validations"], abs=1e-6)
    # inputs with errors go round the memo, so it hits for fewer than the inputs without
    assert 0 <= got["assemble_memo_hit_share.pages"] <= 100 * (1 - want["inputs_with_errors"] / want["inputs"]) + 1e-9


def test_singles_answered_by_the_cpu_walk_carry_the_same_errors(root, tmp_path, resent):
    seed = 2**31 + 72
    res = run.run_cell("tiny-schema.sidecar", seed, 2.0, 0, root=root, require_platform=None, out_dir=str(tmp_path / "out"))
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 160
    want = hold_to_the_plain_reading(resent, workload.build(160, rig.TINY_MODS, seed, {"resources": [1, 1]}))
    assert want["errors"] > 0
    # none of these reached the device, and each was counted under the route that answered it
    with open(tmp_path / "out" / "metrics_after.txt") as f:
        text = f.read()
    routes = {m.group(1): float(m.group(2)) for m in re.finditer(
        r'cerbos_tpu_schema_validations_total\{source="resource",outcome="invalid",route="(\w+)"\} (\S+)', text)}
    assert routes["device"] == 0 and routes["inline"] + routes["oracle"] > 0


def test_stale_policies_still_come_out_wrong(root, tmp_path):
    res = run.run_cell(
        "tiny-schema.sidecar", 2**31 + 73, 2.0, 0, root=root, require_platform=None, out_dir=str(tmp_path / "out"),
        policy_transform=stale_policies,
    )
    assert res["correct"] is False and res["failed"] > 0


# -- the plain reading itself ---------------------------------------------------

SCHEMA = {
    "type": "object",
    "properties": {
        "department": {"type": "string", "enum": ["marketing", "engineering"]},
        "team": {"type": "string"},
        "address": {"type": "object", "properties": {"zip": {"type": "integer"}}, "required": ["zip", "city"]},
    },
    "required": ["department", "team"],
}

CASES = {
    "clean": ({"department": "marketing", "team": "a"}, []),
    "one required error names every missing property": ({}, [("/", "required")]),
    "an enum": ({"department": "sales", "team": "a"}, [("/department", "enum")]),
    "a wrong type is checked no further": ({"department": 5, "team": "a"}, [("/department", "type")]),
    "nested, at the object's own path": (
        {"department": "marketing", "team": "a", "address": {"zip": "x"}}, [("/address", "required"), ("/address/zip", "type")]
    ),
    "an integer written as a float": ({"department": "marketing", "team": "a", "address": {"zip": 7.0, "city": "x"}}, []),
    "the root of the wrong type": ([], [("/", "type")]),
    "a property the schema does not name is allowed": ({"department": "marketing", "team": "a", "x": 1}, []),
}


@pytest.mark.parametrize("name", list(CASES))
def test_schema_check_finds_what_upstreams_validator_would(name):
    value, want = CASES[name]
    assert sorted(schema_check.errors(SCHEMA, value)) == sorted(want)


@pytest.mark.parametrize("schema", [{"type": "object", "additionalProperties": False}, {"properties": {"a": {"minLength": 1}}}])
def test_schema_check_refuses_a_keyword_it_does_not_check(schema):
    with pytest.raises(schema_check.SchemaError):
        schema_check.check_schema(schema)


def test_the_kinds_with_schemas_are_read_from_the_documents_and_the_roots_schemas_hold_for_its_scopes():
    table = schema_check.Table.of_corpus(rig.TINY_MODS)
    assert table.kinds_with_schemas() == {f"{k}_{i}" for k in ("leave_request", "employee_record") for i in range(rig.TINY_MODS)}
    leave = {"kind": "leave_request_1", "id": "x", "attr": {}, "policyVersion": "", "scope": "acme.hr.uk"}
    assert table.refs_for(leave) == ("cerbos:///principal_1.json", "cerbos:///leave_request_1.json")
    assert table.refs_for({**leave, "scope": "acme.nowhere"}) == (None, None)  # no policy for that scope: nothing is validated
    assert table.refs_for({**leave, "kind": "salary_record_1"}) == (None, None)
    assert table.refs_for({**leave, "kind": "diverse_record_1", "scope": ""}) == (None, None)  # a policy, and no schemas


def test_diff_says_what_differs():
    want = [[("SOURCE_PRINCIPAL", "/", "required")], []]
    ok = [[("SOURCE_PRINCIPAL", "/", "missing properties: 'team'")], []]
    assert schema_check.diff(want, ok) is None
    assert "1 results" in schema_check.diff(want, ok[:1])
    assert "no message" in schema_check.diff(want, [[("SOURCE_PRINCIPAL", "/", "")], []])
    assert "result 1" in schema_check.diff(want, [ok[0], [("SOURCE_RESOURCE", "/", "missing properties: 'id'")]])
    assert "result 0" in schema_check.diff(want, [[("SOURCE_PRINCIPAL", "/", "'team' is a required property")], []])  # not upstream's wording
