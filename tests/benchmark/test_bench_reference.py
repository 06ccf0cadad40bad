"""The plain reference: its pinned digests, and that the program's own CPU
evaluator answers as it does (so a PR that changes what the oracle answers is
seen even in a cell where every decision is oracle-served)."""

import json
import os
import sys
from datetime import datetime, timezone

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchmark_rig as rig  # noqa: E402

from benchmarks import run  # noqa: E402
from benchmarks.lib import corpus, reference, spec, workload  # noqa: E402

with open(os.path.join(rig.REPO, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
WORKLOADS = [w["name"] for w in MANIFEST["workloads"]]


@pytest.mark.parametrize("name", WORKLOADS)
def test_pinned_digest_is_what_the_reference_answers(name):
    cell = spec.Cell(rig.REPO, name)
    assert run.check_digest(cell) is True


def test_a_changed_answer_changes_the_digest():
    reqs = workload.build(50, 100, 0, {"resources": [1, 1]})
    a = workload.digest(reqs, run.DIGEST_NOW)
    reqs[17].entries[0][1][:] = ["create"]  # another action asked of one resource
    assert workload.digest(reqs, run.DIGEST_NOW) != a


@pytest.fixture(scope="module", params=sorted({w["config"] for w in MANIFEST["workloads"]}))
def table(request):
    from cerbos_tpu.compile import compile_policy_set
    from cerbos_tpu.policy.parser import parse_policies
    from cerbos_tpu.ruletable import build_rule_table

    with open(os.path.join(rig.REPO, "benchmarks", "configs", request.param + ".json")) as f:
        mods = json.load(f)["corpus"]["mods"]
    return mods, build_rule_table(compile_policy_set(list(parse_policies(corpus.corpus_yaml(mods)))))


@pytest.mark.parametrize("shape, n", [({"resources": [1, 1]}, 2000), ({"resources": [16, 50]}, 150)], ids=["sidecar", "pages"])
def test_the_programs_oracle_answers_as_the_reference_does(table, shape, n):
    from google.protobuf import json_format

    from cerbos_tpu.api.cerbos.request.v1 import request_pb2
    from cerbos_tpu.cel.values import Timestamp
    from cerbos_tpu.engine import types as T
    from cerbos_tpu.ruletable import check_input
    from cerbos_tpu.server import convert

    mods, rt = table
    now = datetime(2026, 5, 17, 12, tzinfo=timezone.utc)  # inside 2026: both sides of the now() family occur
    params = T.EvalParams(now_fn=lambda: Timestamp.from_datetime(now))
    reqs = workload.build(n, mods, 0, shape)
    workload.serialize(reqs)
    decisions = 0
    for r in reqs:
        # the oracle sees what the server sees: the wire bytes, through the server's own conversion
        body = json_format.MessageToDict(request_pb2.CheckResourcesRequest.FromString(r.wire))
        aux = T.AuxData(jwt=r.jwt) if r.jwt is not None else None
        inputs, _, _ = convert.json_to_check_inputs(body, aux)
        for inp, want in zip(inputs, r.expected(now), strict=True):
            got = {a: e.effect for a, e in check_input(rt, inp, params).actions.items()}
            assert got == want, (r.principal, inp.resource)
            decisions += len(got)
    assert decisions > 3000


def test_clock_dependent_answers_are_named_and_bracketed():
    res = {"kind": "diverse_record_2", "id": "DV1", "policyVersion": "", "scope": "",
           "attr": {"created": "2026-06-15T10:00:00Z"}}
    principal = {"id": "user1", "roles": ["user"], "attr": {}}
    assert reference.uses_now(res, ["op0"]) and not reference.uses_now(res, ["op1"])
    before, after = datetime(2026, 6, 15, 9, tzinfo=timezone.utc), datetime(2026, 6, 15, 11, tzinfo=timezone.utc)
    assert reference.effects(principal, res, ["op0"], before) == {"op0": reference.DENY}
    assert reference.effects(principal, res, ["op0"], after) == {"op0": reference.ALLOW}
    # a reply with either answer is accepted when the server's clock lay between the two
    req = workload.Request(0, principal, [(res, ["op0"])], None)
    from cerbos_tpu.api.cerbos.response.v1 import response_pb2

    for effect in (1, 2):
        resp = response_pb2.CheckResourcesResponse(request_id="r0")
        out = resp.results.add()
        out.resource.id = "DV1"
        out.actions["op0"] = effect
        assert workload.compare(req, resp.SerializeToString(), before, after) is None
        assert (workload.compare(req, resp.SerializeToString(), after, after) is None) == (effect == 1)


def test_an_incomplete_reply_is_wrong():
    from cerbos_tpu.api.cerbos.response.v1 import response_pb2

    reqs = workload.build(1, 100, 3, {"resources": [16, 50]})
    now = datetime(2026, 5, 17, tzinfo=timezone.utc)
    resp = response_pb2.CheckResourcesResponse(request_id="r0")
    for (res, _), eff in list(zip(reqs[0].entries, reqs[0].expected(now)))[:-1]:
        out = resp.results.add()
        out.resource.id = res["id"]
        for a, e in eff.items():
            out.actions[a] = 1 if e == reference.ALLOW else 2
    assert workload.compare(reqs[0], resp.SerializeToString(), now, now).startswith("incomplete reply")
