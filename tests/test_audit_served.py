"""The audit log through the served path (audit/log.py, audit/file.py,
server/server.py, server/service.py; docs/ROBUSTNESS.md "The audit log").

Real listeners over a real ``BatchingEvaluator`` (the test_request_parts
harness) with a real ``AuditLog`` over the file backend: an access entry per
call under the decision entry's call id; rotation; a ``close()`` that drains;
SIGTERM to a served process; every way an entry is lost on one counter, by
exactly the entries lost, with the reply never waiting; the hand-off on a
clock of its own; the device's entry against the oracle's; and the inline
routes' entries under the owner's epoch.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request
from types import SimpleNamespace

import pytest
import yaml
from test_ipc import OracleEvaluator, wait_for
from test_request_parts import BODY, send_grpc, send_http
from test_streaming_serving import POLICY, inp, table

from cerbos_tpu import native
from cerbos_tpu import observability as obs
from cerbos_tpu.audit.file import FileBackend
from cerbos_tpu.audit.log import AuditLog, new_audit_log
from cerbos_tpu.engine import budget as budget_mod
from cerbos_tpu.engine.batcher import BatchingEvaluator
from cerbos_tpu.engine.budget import BACK_AUDIT, BACK_ENCODE, BACK_WAKE, STAGE_REPLY_ENCODE
from cerbos_tpu.engine.engine import Engine
from cerbos_tpu.server.server import Server, ServerConfig
from cerbos_tpu.server.service import CerbosService, ServiceLimits
from cerbos_tpu.tpu import TpuEvaluator

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
METHOD = "/cerbos.svc.v1.CerbosService/CheckResources"
PLAN_METHOD = "/cerbos.svc.v1.CerbosService/PlanResources"


def lost(kind: str, reason: str) -> float:
    return obs.metrics().counter_vec("cerbos_tpu_audit_lost_total", label=("kind", "reason")).get((kind, reason))


def entries_counted(kind: str, outcome: str) -> float:
    return obs.metrics().counter_vec("cerbos_tpu_audit_entries_total", label=("kind", "outcome")).get((kind, outcome))


def read_log(path) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f]


def audit_conf(path, **over) -> dict:
    return {"enabled": True, "backend": "file", "file": {"path": str(path)}, **over}


class GatedBackend:
    """A backend whose writes wait for ``gate``: the writer thread stands still
    with one entry in hand, so the queue's content is the test's to set."""

    def __init__(self):
        self.gate = threading.Event()
        self.taken = threading.Event()
        self.written: list[dict] = []

    def write(self, entry: dict) -> None:
        self.taken.set()
        assert self.gate.wait(30)
        self.written.append(entry)


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
def served(tmp_path):
    """``serve(audit_log)`` -> a started Server over the oracle-backed batcher;
    everything is closed at the end, the audit log after the listeners as the
    CLI does."""
    closers = []

    def serve(audit_log, limits=None):
        rt = table()
        batcher = BatchingEvaluator(OracleEvaluator(rt), max_wait_ms=1.0)
        svc = CerbosService(Engine(rt, tpu_evaluator=batcher, tpu_batch_threshold=1), audit_log=audit_log, limits=limits)
        srv = Server(svc, ServerConfig(http_listen_addr="127.0.0.1:0", grpc_listen_addr="127.0.0.1:0"))
        srv.start()
        closers.extend([srv.stop, batcher.close] + ([audit_log.close] if audit_log is not None else []))
        return srv

    serve.close = lambda: [c() for c in closers] and closers.clear()
    yield serve
    serve.close()


# -- access entries ------------------------------------------------------------


@pytest.mark.parametrize("surface", ["grpc", "http"])
def test_every_call_writes_an_access_entry_under_the_decision_entrys_call_id(tmp_path, served, surface):
    srv = served(new_audit_log(audit_conf(tmp_path / "a.log")))
    for _ in range(3):
        (send_grpc if surface == "grpc" else send_http)(srv)
    served.close()
    log = read_log(tmp_path / "a.log")
    decisions = [e for e in log if e["kind"] == "decision"]
    access = [e for e in log if e["kind"] == "access"]
    assert len(decisions) == len(access) == 3
    assert sorted(e["callId"] for e in decisions) == sorted(e["callId"] for e in access)
    assert len({e["callId"] for e in decisions}) == 3
    assert all(e["method"] == METHOD and e["peer"] and e["log.logger"] == "cerbos.audit" for e in access)
    assert all(len(e["checkResources"]["inputs"]) == 3 and e["checkResources"]["inputs"][0]["requestId"] == "parts-1" for e in decisions)


@pytest.mark.parametrize("surface", ["grpc", "http"])
def test_no_access_entry_where_access_logs_are_off(tmp_path, served, surface):
    before = entries_counted("access", "queued")
    srv = served(new_audit_log(audit_conf(tmp_path / "a.log", accessLogsEnabled=False)))
    (send_grpc if surface == "grpc" else send_http)(srv)
    served.close()
    assert [e["kind"] for e in read_log(tmp_path / "a.log")] == ["decision"]
    assert entries_counted("access", "queued") == before


def test_the_http_replys_call_id_is_the_entries(tmp_path, served):
    srv = served(new_audit_log(audit_conf(tmp_path / "a.log")))
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.http_port}/api/check/resources", data=json.dumps(BODY).encode(), method="POST"
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        call_id = json.loads(resp.read())["cerbosCallId"]
    served.close()
    assert [(e["kind"], e["callId"]) for e in read_log(tmp_path / "a.log")] == [("decision", call_id), ("access", call_id)]


@pytest.mark.parametrize("surface", ["grpc", "http"])
def test_a_call_the_service_refuses_still_writes_its_access_entry_naming_the_error(tmp_path, served, surface):
    srv = served(new_audit_log(audit_conf(tmp_path / "a.log")), limits=ServiceLimits(max_resources_per_request=2))
    with pytest.raises(Exception, match="INVALID_ARGUMENT|400"):
        (send_grpc if surface == "grpc" else send_http)(srv)  # three resources
    served.close()
    (only,) = read_log(tmp_path / "a.log")  # no decision was made, so no decision entry
    assert (only["kind"], only["method"], only["error"]) == ("access", METHOD, "RequestLimitExceeded") and only["peer"]


class Planner:
    def __init__(self, fails: bool):
        self.fails = fails

    def plan(self, plan_input, params=None):
        if self.fails:
            raise RuntimeError("no plan")
        return SimpleNamespace(kind="KIND_ALWAYS_ALLOWED", condition=None, effective_policies={})


@pytest.mark.parametrize("fails", [False, True], ids=["answered", "raises"])
def test_a_plan_call_writes_an_access_entry_beside_its_decision_entry(tmp_path, fails):
    log = new_audit_log(audit_conf(tmp_path / "a.log"))
    svc = CerbosService(Engine(table()), audit_log=log, planner=Planner(fails))
    plan_input = SimpleNamespace(request_id="p1", actions=["view"], principal=inp(0).principal, resource_kind="album")
    access = svc.access_of(PLAN_METHOD, lambda: "ipv4:127.0.0.1:1")
    if fails:
        with pytest.raises(RuntimeError):
            svc.plan_resources(plan_input, access=access)
        call_id = None
    else:
        _, call_id = svc.plan_resources(plan_input, access=access)
    log.close()
    entries = read_log(tmp_path / "a.log")
    assert [e["kind"] for e in entries] == (["access"] if fails else ["decision", "access"])
    assert entries[-1]["method"] == PLAN_METHOD and entries[-1].get("error") == ("RuntimeError" if fails else None)
    assert call_id is None or {e["callId"] for e in entries} == {call_id}


def test_the_service_reads_no_peer_where_no_access_entry_is_written(tmp_path):
    def peer():
        raise AssertionError("read")

    assert CerbosService(Engine(table())).access_of(METHOD, peer) is None
    log = new_audit_log(audit_conf(tmp_path / "a.log", accessLogsEnabled=False))
    assert CerbosService(Engine(table()), audit_log=log).access_of(METHOD, peer) is None
    log.close()


# -- the file backend: rotation ------------------------------------------------


def entry(k: int, pad: int = 200) -> dict:
    return {"callId": f"c{k}", "kind": "decision", "timestamp": "t", "n": k, "pad": "x" * pad}


def test_rotation_at_the_size_limit_keeps_max_file_count_files_and_splits_no_line(tmp_path):
    rotations = obs.metrics().counter("cerbos_tpu_audit_rotations_total")
    before = rotations.value
    path = tmp_path / "logs" / "audit.log"
    limit = 10 * 1024
    be = FileBackend(str(path), rotation={"maxFileSizeMB": limit / (1 << 20), "maxFileCount": 3})
    sizes = [be.write(entry(k)) for k in range(400)]
    be.close()
    files = be.rotated_files() + [str(path)]
    assert len(files) == 4 and len(os.listdir(path.parent)) == 4  # maxFileCount rotated files and the live one
    assert all(os.path.getsize(p) <= limit for p in files)
    kept = [e["n"] for p in files for e in read_log(p)]  # every line parses: none was split
    assert kept == list(range(kept[0], 400))  # the newest entries, in order across the files, none missing
    assert sum(os.path.getsize(p) for p in files) == sum(sizes[kept[0] :])
    held = due = 0
    for size in sizes:  # a rotation falls due where the next line would take the file past the limit
        if held and held + size > limit:
            due, held = due + 1, 0
        held += size
    assert rotations.value - before == due >= 9  # some 38 lines a file; all but the last three rotated files deleted


def test_a_file_over_the_limit_at_open_is_rotated_at_once(tmp_path):
    path = tmp_path / "audit.log"
    path.write_text("x" * 5000 + "\n")  # what a build without rotation left behind
    be = FileBackend(str(path), rotation={"maxFileSizeMB": 1000 / (1 << 20), "maxFileCount": 2})
    assert os.path.getsize(path) == 0 and [os.path.getsize(p) for p in be.rotated_files()] == [5001]
    be.write(entry(0))
    be.close()
    assert [e["n"] for e in read_log(path)] == [0]


def test_no_rotation_block_appends_for_ever_and_a_missing_directory_is_made(tmp_path):
    path = tmp_path / "a" / "b" / "audit.log"
    for _ in range(2):  # a restart appends
        be = FileBackend(str(path))
        for k in range(50):
            be.write(entry(k))
        be.close()
    assert len(read_log(path)) == 100 and os.listdir(path.parent) == ["audit.log"]


def test_rotated_files_older_than_max_file_age_days_go_at_the_next_rotation(tmp_path):
    path = tmp_path / "audit.log"
    be = FileBackend(str(path), rotation={"maxFileSizeMB": 2000 / (1 << 20), "maxFileAgeDays": 1})
    for k in range(30):
        be.write(entry(k))
    old = be.rotated_files()
    assert len(old) >= 2
    os.utime(old[0], (time.time() - 2 * 86400,) * 2)
    for k in range(30, 40):
        be.write(entry(k))
    be.close()
    assert old[0] not in be.rotated_files() and old[1] in be.rotated_files()


def test_two_writers_of_one_path_lose_no_line_across_rotations(tmp_path):
    """Two backends on one path stand for two processes of a pool: a line is one
    append, and a writer whose file another rotated reopens the path."""
    path = tmp_path / "audit.log"
    rotation = {"maxFileSizeMB": 20_000 / (1 << 20), "maxFileCount": 1000}
    writers = [FileBackend(str(path), rotation=rotation) for _ in range(2)]

    def work(w: int) -> None:
        for k in range(500):
            writers[w].write(entry(w * 1000 + k))

    threads = [threading.Thread(target=work, args=(w,)) for w in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for w in writers:
        w.close()
    files = writers[0].rotated_files() + [str(path)]
    assert len(files) > 5
    assert sorted(e["n"] for p in files for e in read_log(p)) == sorted(list(range(500)) + list(range(1000, 1500)))


# -- the queue: drained at close, counted when lost ----------------------------


def test_close_writes_all_2000_entries_that_are_queued(tmp_path):
    log = new_audit_log(audit_conf(tmp_path / "a.log"))
    real, gate = log.backend.write, threading.Event()
    log.backend.write = lambda e: (gate.wait(30), real(e))[1]  # the writer stands still until the queue is full of them
    for k in range(2000):
        assert log.write_access(f"c{k}", METHOD, "peer") == "queued"
    assert log.m_depth.value >= 1999
    threading.Timer(0.05, gate.set).start()
    log.close()
    assert [e["callId"] for e in read_log(tmp_path / "a.log")] == [f"c{k}" for k in range(2000)]
    assert log.m_depth.value == 0
    assert log.write_access("late", METHOD, "peer") == "dropped"  # after close: counted, not queued


def test_a_full_queue_drops_counts_every_entry_lost_and_delays_no_reply(served, tracker):
    backend = GatedBackend()
    log = AuditLog(backend=backend, backend_name="gated")
    srv = served(log)
    before = {k: lost(*k) for k in (("access", "dropped"), ("decision", "dropped"))}
    queued0, written0, dropped0 = entries_counted("access", "queued"), entries_counted("access", "written"), log.m_dropped.value
    log.write_access("first", METHOD)
    assert backend.taken.wait(5)  # the writer holds "first" and stands still
    size = log._queue.maxsize
    outcomes = [log.write_access(f"c{k}", METHOD) for k in range(size + 7)]
    assert outcomes.count("queued") == size and outcomes.count("dropped") == 7
    t0 = time.monotonic()
    send_grpc(srv)  # answered while the queue is full and the writer stands still
    assert time.monotonic() - t0 < 2.0
    assert lost("access", "dropped") - before[("access", "dropped")] == 8  # the 7 and the call's
    assert lost("decision", "dropped") - before[("decision", "dropped")] == 1
    assert log.m_dropped.value - dropped0 == 9  # the old name still counts them
    # queued = written + what the queue holds (+ the one in the writer's hand)
    assert entries_counted("access", "queued") - queued0 == size + 1
    assert entries_counted("access", "written") - written0 == 0 and log._queue.qsize() == size
    backend.gate.set()
    served.close()
    assert entries_counted("access", "written") - written0 == size + 1 == len(backend.written)


def test_the_shed_audit_rung_counts_every_entry_it_sheds_and_delays_no_reply(tmp_path, served):
    log = new_audit_log(audit_conf(tmp_path / "a.log"))
    srv = served(log)
    before = (lost("decision", "shed"), lost("access", "shed"), entries_counted("decision", "queued"))
    log.set_shed(True)
    for _ in range(4):
        send_grpc(srv)
    log.set_shed(False)
    send_http(srv)
    served.close()
    assert (lost("decision", "shed") - before[0], lost("access", "shed") - before[1]) == (4, 4)
    assert entries_counted("decision", "queued") - before[2] == 1
    assert [e["kind"] for e in read_log(tmp_path / "a.log")] == ["decision", "access"]


def test_a_failed_backend_write_is_counted_and_the_writer_goes_on(tmp_path):
    log = new_audit_log(audit_conf(tmp_path / "a.log"))
    real = log.backend.write

    def write(e):
        if e["callId"] == "bad":
            raise OSError("disk full")
        return real(e)

    log.backend.write = write
    before = (lost("access", "failed"), entries_counted("access", "written"), entries_counted("access", "queued"))
    for call in ("a", "bad", "b"):
        log.write_access(call, METHOD)
    log.close()
    assert [e["callId"] for e in read_log(tmp_path / "a.log")] == ["a", "b"]
    assert lost("access", "failed") - before[0] == 1
    assert entries_counted("access", "written") - before[1] == 2 and entries_counted("access", "queued") - before[2] == 3


def test_filtered_decisions_are_counted_and_the_writers_seconds_add_up(tmp_path):
    conf = audit_conf(tmp_path / "a.log", decisionLogFilters={"checkResources": {"ignoreAllowAll": True}})
    t0 = time.monotonic()
    log = new_audit_log(conf)
    writer = obs.metrics().counter_vec("cerbos_tpu_audit_writer_seconds_total", label="state")
    seconds0 = writer.value
    before = (entries_counted("decision", "filtered"), entries_counted("decision", "queued"))
    rt = table()
    allowed, mixed = [inp(0)], [inp(0), inp(8)]  # a0 is public; a8 is u1's, not u8's, and not public
    outs = [[c for i in batch for c in OracleEvaluator(rt).check([i])] for batch in (allowed, mixed)]
    assert log.write_decision("c1", allowed, outs[0]) == "filtered"
    assert log.write_decision("c2", mixed, outs[1]) == "queued"
    time.sleep(0.3)
    log.close()
    lived = time.monotonic() - t0
    assert (entries_counted("decision", "filtered") - before[0], entries_counted("decision", "queued") - before[1]) == (1, 1)
    assert [e["callId"] for e in read_log(tmp_path / "a.log")] == ["c2"]
    # idle + write = the thread's life (booked at each wake-up, at least once a second)
    assert 0.25 < writer.value - seconds0 <= lived + 0.01
    sizes = obs.metrics().histogram_vec("cerbos_tpu_audit_entry_bytes", label="kind").labels("decision")
    assert sizes.count >= 1 and sizes.sum >= os.path.getsize(tmp_path / "a.log")


def test_the_writers_seconds_per_entry_are_booked_by_kind(tmp_path):
    hist = obs.metrics().histogram_vec("cerbos_tpu_audit_write_seconds", label=("backend", "kind"))
    before = {k: hist.labels(("file", k)).count for k in ("decision", "access")}
    log = new_audit_log(audit_conf(tmp_path / "a.log"))
    svc = CerbosService(Engine(table()), audit_log=log)
    for _ in range(3):
        svc.check_resources([inp(0)], access=svc.access_of(METHOD, lambda: "peer"))
    log.write_access("lone", METHOD)
    log.close()
    assert {k: hist.labels(("file", k)).count - before[k] for k in before} == {"decision": 3, "access": 4}
    assert 'cerbos_tpu_audit_write_seconds_count{backend="file",kind="decision"}' in obs.metrics().render()


# -- the hand-off on a clock of its own ----------------------------------------


@pytest.mark.parametrize("surface,audit", [("grpc", True), ("http", True), ("grpc", False)])
def test_the_audit_part_is_observed_once_a_request_and_tiles_reply_encode(tmp_path, served, tracker, monkeypatch, surface, audit):
    seen = []
    real = tracker.finish
    monkeypatch.setattr(tracker, "finish", lambda wf, *a, **kw: (seen.append(wf), real(wf, *a, **kw))[1])
    srv = served(new_audit_log(audit_conf(tmp_path / "a.log")) if audit else None)
    part = tracker._part_children[BACK_AUDIT]
    count0, sum0 = part.count, part.sum
    (send_grpc if surface == "grpc" else send_http)(srv)
    assert wait_for(lambda: len(seen) == 1)
    served.close()
    (wf,) = seen
    parts, stages = dict(wf.parts), dict(wf.stages)
    assert part.count - count0 == 1 and part.sum - sum0 == pytest.approx(parts[BACK_AUDIT], abs=1e-9)
    back = [BACK_WAKE, BACK_AUDIT, BACK_ENCODE] + (["serialize"] if surface == "http" else [])
    assert [p for p, _ in wf.parts][6:] == back
    assert sum(parts[p] for p in back) == pytest.approx(stages[STAGE_REPLY_ENCODE], abs=2e-6)
    if audit:
        assert parts[BACK_AUDIT] > 5e-6  # an entry of three inputs was built in it
    else:
        assert parts[BACK_AUDIT] < 50e-6  # one clock read after the mark before it


def test_the_decision_entry_carries_the_request_spans_trace_id(tmp_path, monkeypatch):
    spans = []

    class Keep(obs.SpanExporter):
        def export(self, span, duration_ms):
            spans.append(span)

    monkeypatch.setattr(obs, "_exporter", Keep())
    log = new_audit_log(audit_conf(tmp_path / "a.log"))
    rt = table()
    svc = CerbosService(Engine(rt), audit_log=log)
    svc.check_resources([inp(0), inp(1)])
    log.close()
    (span,) = [s for s in spans if s.name == "request.CheckResources"]
    assert read_log(tmp_path / "a.log")[0]["traceId"] == span.trace_id


# -- what an entry says: the device's against the oracle's, and the inline routes' epoch


def decision_entry(path) -> dict:
    (e,) = [e for e in read_log(path) if e["kind"] == "decision"]
    return e


def sans(entry: dict) -> dict:
    """An entry less what differs between two calls, and less who evaluated it."""
    out = {k: v for k, v in entry.items() if k not in ("callId", "timestamp", "traceId")}
    out["provenance"] = [
        {**p, "actions": {a: {k: v for k, v in e.items() if k != "source"} for a, e in p.get("actions", {}).items()}}
        for p in entry.get("provenance", [])
    ]
    return out


def test_the_entry_of_a_device_served_page_is_the_oracles_but_for_the_source(tmp_path):
    rt = table()
    page = [inp(i) for i in range(32)]
    logged = {}
    for who in ("device", "oracle"):
        log = new_audit_log(audit_conf(tmp_path / f"{who}.log"))
        if who == "device":
            batcher = BatchingEvaluator(TpuEvaluator(rt, use_jax=True), max_wait_ms=1.0)
            engine = Engine(rt, tpu_evaluator=batcher, tpu_batch_threshold=1)
        else:
            batcher, engine = None, Engine(rt)
        CerbosService(engine, audit_log=log).check_resources(page)
        if batcher is not None:
            batcher.close()
        log.close()
        logged[who] = decision_entry(tmp_path / f"{who}.log")
    sources = lambda e: {a["source"] for p in e["provenance"] for a in p["actions"].values()}  # noqa: E731
    assert sources(logged["device"]) == {"device"} and sources(logged["oracle"]) <= {"oracle", ""}
    device, oracle = sans(logged["device"]), sans(logged["oracle"])
    device.pop("shard", None), oracle.pop("shard", None)  # the lane that evaluated it: the batcher's alone
    assert device == oracle
    assert len(device["checkResources"]["outputs"]) == 32 and len(device["provenance"]) == 32


def test_an_inline_check_logs_its_entry_under_the_tables_epoch(tmp_path):
    ev = TpuEvaluator(table(), use_jax=False)  # min_device_batch 16: one input is answered inline
    ev.rule_table.policy_epoch = 5
    batcher = BatchingEvaluator(ev, max_wait_ms=1.0)
    batcher.epoch = 5
    routes = obs.metrics().counter_vec("cerbos_tpu_batcher_checks_total", label="route")
    inline0 = routes.get("inline")
    log = new_audit_log(audit_conf(tmp_path / "a.log"))
    CerbosService(Engine(ev.rule_table, tpu_evaluator=batcher, tpu_batch_threshold=1), audit_log=log).check_resources([inp(0)])
    batcher.close()
    log.close()
    assert routes.get("inline") - inline0 == 1
    e = decision_entry(tmp_path / "a.log")
    assert e["policyEpoch"] == 5 and len(e["checkResources"]["inputs"]) == 1


@pytest.mark.skipif(native.get() is None, reason="the shared page needs the native module's shm plane")
def test_a_front_ends_inline_answer_logs_its_entry_under_the_owners_epoch(tmp_path):
    from test_frontend_inline import EPOCH, Pool
    from test_rollout import inp as rollout_inp
    from test_rollout import table as rollout_table

    pool = Pool(tmp_path, rollout_table())
    log = new_audit_log(audit_conf(tmp_path / "a.log"))
    try:
        svc = CerbosService(Engine(pool.client.rule_table, tpu_evaluator=pool.client, tpu_batch_threshold=1), audit_log=log)
        svc.check_resources([rollout_inp(0)])
        assert pool.moved() == {"inline": 1.0, "queued": 0.0}
    finally:
        pool.close()
        log.close()
    assert decision_entry(tmp_path / "a.log")["policyEpoch"] == EPOCH


# -- SIGTERM to a served process -----------------------------------------------


def test_sigterm_under_a_thread_of_singles_leaves_one_entry_per_answered_request(tmp_path):
    (tmp_path / "policies").mkdir()
    (tmp_path / "policies" / "album.yaml").write_text(POLICY)
    log_path = tmp_path / "logs" / "audit.log"
    config = {
        "server": {"httpListenAddr": "127.0.0.1:0", "grpcListenAddr": "127.0.0.1:0"},
        "storage": {"driver": "disk", "disk": {"directory": str(tmp_path / "policies")}},
        "engine": {"tpu": {"enabled": False}},
        "audit": audit_conf(log_path, file={"path": str(log_path), "logRotation": {"maxFileSizeMB": 0.05, "maxFileCount": 1000}}),
    }
    (tmp_path / "config.yaml").write_text(yaml.safe_dump(config))
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""), JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "cerbos_tpu.cli", "server", "--config", str(tmp_path / "config.yaml")],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True, env=env, cwd=REPO,
    )
    try:
        port = 0
        for line in proc.stdout:
            if line.startswith("cerbos-tpu serving:"):
                port = int(dict(t.split("=", 1) for t in line.split() if "=" in t)["http"])
                break
        assert port, "server never announced"
        body = json.dumps({**BODY, "resources": BODY["resources"][:1]}).encode()
        answered, stop = [], threading.Event()

        def singles() -> None:
            while not stop.is_set():
                req = urllib.request.Request(f"http://127.0.0.1:{port}/api/check/resources", data=body, method="POST")
                try:
                    with urllib.request.urlopen(req, timeout=5) as resp:
                        answered.append(json.loads(resp.read())["cerbosCallId"])
                except OSError:
                    time.sleep(0.01)  # the listener is gone: the process is on its way out

        sender = threading.Thread(target=singles)
        sender.start()
        assert wait_for(lambda: len(answered) >= 300, timeout=60)
        proc.send_signal(signal.SIGTERM)  # while the thread still sends
        assert proc.wait(timeout=60) == 0
        stop.set()
        sender.join(timeout=10)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    from glob import glob

    files = sorted(glob(str(tmp_path / "logs" / "audit-*.log"))) + [str(log_path)]
    assert len(files) > 2  # rotated on the way
    log = [e for p in files for e in read_log(p)]
    for kind in ("decision", "access"):
        ids = [e["callId"] for e in log if e["kind"] == kind]
        assert len(ids) == len(set(ids))  # none twice
        assert set(answered) <= set(ids)  # one for every request that got its answer
        assert len(ids) - len(answered) <= 2  # and at most the one or two evaluated whose reply the shutdown cut
