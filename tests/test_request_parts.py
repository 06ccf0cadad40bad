"""The per-request front and back on a clock (PR 27): the parts the handlers
stamp into a request's waterfall tile its ``admission`` stage (``ipc_encode``
in a front end) and its ``reply_encode`` stage, each part once a request, over
gRPC and HTTP, through ``check_resources`` and ``check_resources_async``; the
handler's whole extent covers the waterfall and the serialization.

Real listeners, a real ``BatchingEvaluator`` over the CPU oracle (the
test_ipc harness), and for the front-end topology a real ticket queue.
"""

import json
import urllib.request

import grpc
import pytest

from cerbos_tpu.engine import budget as budget_mod
from cerbos_tpu.engine.batcher import BatchingEvaluator
from cerbos_tpu.engine.budget import (
    BACK_PARTS,
    FRONT_PARTS,
    STAGE_ADMISSION,
    STAGE_IPC_ENCODE,
    STAGE_REPLY_ENCODE,
)
from cerbos_tpu.engine.engine import Engine
from cerbos_tpu.engine.ipc import BatcherIpcServer, RemoteBatcherClient
from cerbos_tpu.server.server import Server, ServerConfig
from cerbos_tpu.server.service import CerbosService

from test_ipc import OracleEvaluator, table, wait_for

BODY = {
    "requestId": "parts-1",
    "principal": {"id": "u1", "roles": ["user"]},
    "resources": [
        {"actions": ["view"], "resource": {"kind": "album", "id": f"a{i}", "attr": {"owner": "u1", "public": i % 2 == 0}}}
        for i in range(3)
    ],
}


@pytest.fixture()
def tracker():
    trk = budget_mod.tracker()
    prev = (trk.enabled, trk.slow_threshold_s, trk._ring.maxlen)
    trk.configure(enabled=True)
    trk.reset()
    yield trk
    trk.configure(enabled=prev[0], slow_threshold_ms=prev[1] * 1000, slow_capacity=prev[2])
    trk.reset()


@pytest.fixture()
def finished(tracker, monkeypatch):
    """Every waterfall the handlers finish, in order."""
    seen = []
    real = tracker.finish

    def finish(wf, *args, **kwargs):
        out = real(wf, *args, **kwargs)
        if wf is not None:
            seen.append(wf)
        return out

    monkeypatch.setattr(tracker, "finish", finish)
    return seen


def serve(tmp_path, topology, grpc_async=False, evaluator=None, limits=None):
    """A started Server in the given topology, and what to close after.
    ``evaluator``: the engine's, in place of the batcher over the CPU oracle;
    ``limits``: the service's request limits."""
    rt = table()
    closers = []
    if evaluator is None:
        evaluator = batcher = BatchingEvaluator(OracleEvaluator(rt), max_wait_ms=1.0)
        closers = [batcher.close]
    if topology == "frontend":
        ipc = BatcherIpcServer(str(tmp_path / "b.sock"), batcher)
        ipc.start()
        evaluator = RemoteBatcherClient(ipc.socket_path, rt, worker_label="fe1", status_poll_s=0.05)
        assert wait_for(evaluator._connected.is_set)
        closers = [evaluator.close, ipc.close, batcher.close]
    svc = CerbosService(Engine(rt, tpu_evaluator=evaluator, tpu_batch_threshold=1), limits=limits)
    srv = Server(
        svc,
        ServerConfig(http_listen_addr="127.0.0.1:0", grpc_listen_addr="127.0.0.1:0", grpc_async=grpc_async),
    )
    srv.start()
    return srv, [srv.stop] + closers


def grpc_request(body):
    from cerbos_tpu.api.cerbos.request.v1 import request_pb2
    from cerbos_tpu.server.convert import py_to_value

    req = request_pb2.CheckResourcesRequest(request_id=body["requestId"])
    req.principal.id = body["principal"]["id"]
    req.principal.roles.extend(body["principal"]["roles"])
    for r in body["resources"]:
        entry = req.resources.add()
        entry.actions.extend(r["actions"])
        entry.resource.kind = r["resource"]["kind"]
        entry.resource.id = r["resource"]["id"]
        for k, v in r["resource"]["attr"].items():
            entry.resource.attr[k].CopyFrom(py_to_value(v))
    return req


def send_grpc(srv):
    from cerbos_tpu.api.cerbos.response.v1 import response_pb2

    req = grpc_request(BODY)
    with grpc.insecure_channel(f"127.0.0.1:{srv.grpc_port}") as ch:
        stub = ch.unary_unary(
            "/cerbos.svc.v1.CerbosService/CheckResources",
            request_serializer=lambda m: m.SerializeToString(),
            response_deserializer=response_pb2.CheckResourcesResponse.FromString,
        )
        resp = stub(req, timeout=10)
    assert len(resp.results) == 3


def http_request(srv, body):
    return urllib.request.Request(
        f"http://127.0.0.1:{srv.http_port}/api/check/resources",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )


def send_http(srv):
    with urllib.request.urlopen(http_request(srv, BODY), timeout=10) as resp:
        assert len(json.loads(resp.read())["results"]) == 3


def counts(tracker):
    out = {p: (h.count, h.sum) for p, h in tracker._part_children.items()}
    out["handler"] = (tracker.m_handler.count, tracker.m_handler.sum)
    return out


# surface, topology, the service method that carries the request, the stage the front parts tile
CASES = [
    pytest.param("grpc", "single", False, STAGE_ADMISSION, id="grpc-sync"),
    pytest.param("http", "single", False, STAGE_ADMISSION, id="http-sync"),
    pytest.param("grpc", "single", True, STAGE_ADMISSION, id="grpc-aio-sync"),
    pytest.param("grpc", "frontend", False, STAGE_IPC_ENCODE, id="grpc-frontend-sync"),
    pytest.param("http", "frontend", False, STAGE_IPC_ENCODE, id="http-frontend-async"),
    # the aio listener in a front end: its handlers are synchronous and the ticket client blocks
    # (direct_dispatch is false there), so the call hops to the executor and takes check_resources
    pytest.param("grpc", "frontend", True, STAGE_IPC_ENCODE, id="grpc-aio-frontend"),
]


@pytest.mark.parametrize("surface,topology,grpc_async,front_stage", CASES)
def test_parts_tile_admission_and_reply_encode(tmp_path, tracker, finished, surface, topology, grpc_async, front_stage):
    srv, closers = serve(tmp_path, topology, grpc_async)
    try:
        if surface == "http" and topology == "frontend":
            assert srv.svc.engine.supports_async  # this case is check_resources_async
        before = counts(tracker)
        (send_grpc if surface == "grpc" else send_http)(srv)
        assert wait_for(lambda: counts(tracker)["handler"][0] == before["handler"][0] + 1)
        after = counts(tracker)
    finally:
        for close in closers:
            close()
    (wf,) = finished
    stages = dict(wf.stages)
    parts = dict(wf.parts)
    names = [p for p, _ in wf.parts]
    assert len(names) == len(set(names)), names  # each part once
    # the front: all six, in order, and they add up to the stage they tile
    assert names[:6] == list(FRONT_PARTS)
    assert sum(parts[p] for p in FRONT_PARTS) == pytest.approx(stages[front_stage], abs=2e-6)
    assert all(d >= 0.0 for _, d in wf.parts)
    # the back: wake, audit (0 here: no audit log) and encode tile reply_encode
    # for gRPC (the bytes are made after it); for HTTP the JSON dump is inside
    # it as serialize
    in_record = ["wake", "audit", "encode"] if surface == "grpc" else list(BACK_PARTS)
    assert names[6:] == in_record
    assert sum(parts[p] for p in in_record) == pytest.approx(stages[STAGE_REPLY_ENCODE], abs=2e-6)
    # every part observed once, the serializer's and the handler's included
    grew = {k: (after[k][0] - before[k][0], after[k][1] - before[k][1]) for k in after}
    assert {k: n for k, (n, _) in grew.items()} == {**{p: 1 for p in FRONT_PARTS + BACK_PARTS}, "handler": 1}
    back = sum(grew[p][1] for p in BACK_PARTS)
    assert back <= stages[STAGE_REPLY_ENCODE] + grew["serialize"][1] + 2e-6
    # the handler's extent is the waterfall and, for gRPC, the serialization after it
    extent = wf.attributed() + (grew["serialize"][1] if surface == "grpc" else 0.0)
    assert grew["handler"][1] == pytest.approx(extent, abs=5e-6)


class Raising:
    """An engine's evaluator that answers every batch by raising."""

    def __init__(self, exc):
        self.exc = exc

    def check(self, inputs, params=None, **kwargs):
        raise self.exc


def refused_grpc(srv, body):
    """What a gRPC client sees of a refusal: its status and its words."""
    from cerbos_tpu.api.cerbos.response.v1 import response_pb2

    with grpc.insecure_channel(f"127.0.0.1:{srv.grpc_port}") as ch:
        stub = ch.unary_unary(
            "/cerbos.svc.v1.CerbosService/CheckResources",
            request_serializer=lambda m: m.SerializeToString(),
            response_deserializer=response_pb2.CheckResourcesResponse.FromString,
        )
        with pytest.raises(grpc.RpcError) as err:
            stub(grpc_request(body), timeout=10)
    return err.value.code(), err.value.details()


def refused_http(srv, body):
    """What an HTTP client sees of one: status, the JSON error body, Retry-After."""
    import urllib.error

    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(http_request(srv, body), timeout=10)
    return err.value.code, json.loads(err.value.read()), err.value.headers.get("Retry-After")


TWICE = {**BODY, "resources": [{**BODY["resources"][0], "actions": ["view", "view"]}]}

# the row's exception; where it is raised; then what each wire and the books must show: gRPC status, HTTP status,
# the JSON body's code, Retry-After, the message, the decision counted, refusal latency observed, a ticket taken
REFUSED = [
    pytest.param("WireViolation", "wire", "INVALID_ARGUMENT", 400, 3, None,
                 "resources[0].actions: items must be unique", "refused", False, False, id="wire-violation"),
    pytest.param("OverloadRefused", "admission", "RESOURCE_EXHAUSTED", 429, 8, "1",
                 "overloaded: concurrency (class 'default')", "refused", True, False, id="overload-at-admission"),
    pytest.param("OverloadRefused", "evaluator", "RESOURCE_EXHAUSTED", 429, 8, "3",
                 "overloaded: queue budget (class 'default')", "refused", True, True, id="overload-below"),
    pytest.param("RequestLimitExceeded", "service", "INVALID_ARGUMENT", 400, 3, None,
                 "number of resources exceeds the limit of 2", "refused", False, True, id="request-limit"),
    pytest.param("DeadlineExceeded", "evaluator", "DEADLINE_EXCEEDED", 504, 4, None,
                 "too late", "expired", False, True, id="deadline"),
    pytest.param("Exception", "evaluator", "INTERNAL", 500, 13, None,
                 "check failed: boom", None, False, True, id="anything-else"),
]


@pytest.mark.parametrize("surface", ["grpc", "http"])
@pytest.mark.parametrize("exc,where,status,http,code,retry_after,message,outcome,timed,ticketed", REFUSED)
def test_a_refusal_is_one_row_on_both_wires(
    tmp_path, tracker, surface, exc, where, status, http, code, retry_after, message, outcome, timed, ticketed
):
    """Every row of ``checkcall.REFUSALS`` through the real listeners: what the
    client sees on each wire, what is booked, and that the admission ticket
    taken for the call is given back."""
    from cerbos_tpu.engine import admission
    from cerbos_tpu.engine.admission import OverloadRefused
    from cerbos_tpu.engine.batcher import DeadlineExceeded
    from cerbos_tpu.engine.budget import OUTCOMES
    from cerbos_tpu.server import checkcall
    from cerbos_tpu.server.service import ServiceLimits

    (row,) = [r for r in checkcall.REFUSALS if r.exc.__name__ == exc]
    assert (row.grpc.name, row.http, row.code, row.retry_after, row.outcome, row.timed) == (
        status, http, code, retry_after is not None, outcome, timed,
    )
    raised = {
        "OverloadRefused": OverloadRefused("default", "queue budget", retry_after=2.5),
        "DeadlineExceeded": DeadlineExceeded("too late"),
        "Exception": RuntimeError("boom"),
    }
    adm = admission.controller()
    # a default class with a cap makes admission live: every call that reaches it takes a ticket
    adm.configure({"enabled": True, "default": {"maxConcurrent": 1}})
    held = adm.try_admit(adm.default) if where == "admission" else None  # the class's one place
    srv, closers = serve(
        tmp_path, "single",
        evaluator=Raising(raised[exc]) if where == "evaluator" else None,
        limits=ServiceLimits(max_resources_per_request=2) if where == "service" else None,
    )
    try:
        decided = {o: tracker.m_decisions.get(("check", o)) for o in OUTCOMES}
        admitted = adm.m_total.get(("default", "admitted"))
        latencies = adm.m_refusal_seconds.count
        body = TWICE if where == "wire" else BODY
        if surface == "grpc":
            assert refused_grpc(srv, body) == (getattr(grpc.StatusCode, status), message)
        else:
            assert refused_http(srv, body) == (http, {"code": code, "message": message}, retry_after)
        grew = {o: tracker.m_decisions.get(("check", o)) - n for o, n in decided.items()}
        assert grew == {o: (1 if o == outcome else 0) for o in OUTCOMES}
        assert adm.m_refusal_seconds.count - latencies == (1 if timed else 0)
        assert adm.m_total.get(("default", "admitted")) - admitted == (1 if ticketed else 0)
        if held is not None:
            held.release()
        assert [c["inflight"] for c in adm.snapshot()["classes"]] == [0]  # and given back
    finally:
        for close in [*closers, lambda: adm.configure(None)]:
            close()


def test_parts_are_off_with_the_waterfall(tmp_path, tracker):
    """No option of their own: with ``latencyBudget`` off nothing is stamped,
    observed or keyed by a response's identity."""
    from cerbos_tpu.server import server as server_mod

    tracker.configure(enabled=False)
    srv, closers = serve(tmp_path, "single")
    try:
        before = counts(tracker)
        # the store is the process's: an earlier test's in-process handler
        # call may have left its stamp there (evicted FIFO, by design)
        stamps_before = dict(server_mod._GRPC_REPLY_STAMPS._stamps)
        send_grpc(srv)
        send_http(srv)
        assert counts(tracker) == before
        assert server_mod._GRPC_REPLY_STAMPS._stamps.items() <= stamps_before.items()  # these two left none
    finally:
        for close in closers:
            close()


def test_slow_ring_entry_carries_the_parts(tmp_path, tracker, finished):
    tracker.configure(slow_threshold_ms=0.0)
    srv, closers = serve(tmp_path, "single")
    try:
        send_grpc(srv)
    finally:
        for close in closers:
            close()
    (entry,) = tracker.slow_dump()["requests"]
    assert [p for p, _ in entry["parts"]] == list(FRONT_PARTS) + ["wake", "audit", "encode"]


def clock_cost_us(n: int = 20000) -> dict:
    """What the parts, the serializer's stamp and the two extra observations
    add to one gRPC request, in microseconds: the same calls the handlers
    make, timed in a loop, less the loop that makes none of them. Run on the
    serving host (``pytest tests/test_request_parts.py -k cost -s``); PERF.md
    section 6 has the chip host's numbers."""
    import time

    from cerbos_tpu.engine.budget import BACK_ENCODE, BACK_WAKE, BudgetTracker, Waterfall
    from cerbos_tpu.server.server import _IngressStamps

    trk = BudgetTracker()
    stamps = _IngressStamps()
    front = FRONT_PARTS[:-1]

    def request(with_parts: bool) -> None:
        wf = Waterfall()
        wf.mark("ingress_parse")
        if with_parts:
            for p in front:
                wf.part(p)
            wf.mark(STAGE_ADMISSION, part="enqueue")
        else:
            wf.mark(STAGE_ADMISSION)
        wf.mark("queue_wait")
        wf.mark("settle")
        if with_parts:
            wf.part(BACK_WAKE)
            t = trk.finish(wf, "deadline_met", final_stage=STAGE_REPLY_ENCODE, final_part=BACK_ENCODE)
            stamps.put(id(wf), wf.t0, t)
            got = stamps.pop(id(wf))
            trk.observe_reply(got[0], got[1], time.monotonic())
        else:
            trk.finish(wf, "deadline_met", final_stage=STAGE_REPLY_ENCODE)

    out = {}
    for name, flag in (("without", False), ("with", True), ("without_again", False), ("with_again", True)):
        t0 = time.perf_counter()
        for _ in range(n):
            request(flag)
        out[name] = (time.perf_counter() - t0) / n * 1e6
    out["added_us"] = min(out["with"], out["with_again"]) - min(out["without"], out["without_again"])
    return out


def test_clock_cost_is_a_few_microseconds_a_request():
    """The issue's budget is 15 us a request on the serving host; here, on a
    shared CPU under the test runner, only that it is not an order off."""
    cost = clock_cost_us()
    print(f"clock cost per request, us: { {k: round(v, 2) for k, v in cost.items()} }")
    assert cost["added_us"] < 150.0
