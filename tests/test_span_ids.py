"""Where a request's identifiers come from (observability.py; docs/OBSERVABILITY.md
"Where identifiers come from").

The call id, the trace id and every span id are drawn from one generator of the
process, seeded from the system when the module is imported and again in every
forked child: a served request makes no system call for an identifier, on its own
thread or on the drain thread, and a pool's front ends, which fork after load, hand
out no id twice between them.
"""

import json
import os
import re
import select
import signal
import sys
import threading
import uuid

import pytest
from test_ipc import wait_for
from test_tracing import _boot, _CaptureExporter

from cerbos_tpu import observability as obs
from cerbos_tpu.engine import types as T

DRAWS = {"trace_id": (obs.new_trace_id, 32), "span_id": (obs.new_span_id, 16), "call_id": (obs.new_call_id, 32)}


@pytest.mark.parametrize("kind", sorted(DRAWS))
def test_an_id_is_lowercase_hex_of_its_width(kind):
    draw, width = DRAWS[kind]
    for _ in range(2000):
        assert re.fullmatch(r"[0-9a-f]{%d}" % width, draw())


@pytest.mark.parametrize("kind", sorted(DRAWS))
def test_an_id_is_never_all_zero(kind, monkeypatch):
    """A generator that hands out zero is asked again (W3C: an all-zero id is invalid)."""
    draw, width = DRAWS[kind]

    class ZeroFirst:
        asked = 0

        def getrandbits(self, bits):
            self.asked += 1
            return 0 if self.asked < 3 else 0xABC

    zero_first = ZeroFirst()
    monkeypatch.setattr(obs, "_ids", zero_first)
    assert draw() == "abc".rjust(width, "0")
    assert zero_first.asked == 3


def test_sixteen_threads_draw_no_duplicate():
    per_thread, threads = 2000, 16
    start = threading.Barrier(threads)
    drawn: list[list[str]] = [[] for _ in range(threads)]

    def work(out):
        start.wait(10)
        for _ in range(per_thread // 2):
            out.append(obs.new_trace_id())
            out.append(obs.new_span_id())

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ts = [threading.Thread(target=work, args=(out,)) for out in drawn]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(interval)
    ids = [i for out in drawn for i in out]
    assert len(ids) == per_thread * threads
    assert len(set(ids)) == len(ids)


def _forked_child_draws(n: int) -> list[str]:
    """Fork as ``server/workers.py`` does, draw ``n`` ids in the child, read them here."""
    r, w = os.pipe()
    pid = os.fork()
    if pid == 0:
        status = 1
        try:
            os.close(r)
            ids = [obs.new_trace_id() for _ in range(n // 2)] + [obs.new_span_id() for _ in range(n // 2)]
            with os.fdopen(w, "w") as f:
                json.dump(ids, f)
            status = 0
        finally:
            os._exit(status)
    os.close(w)
    with os.fdopen(r) as f:
        if not select.select([f], [], [], 30)[0]:  # a child that hangs fails the test and does not hold it
            os.kill(pid, signal.SIGKILL)
        text = f.read()
    assert os.waitpid(pid, 0)[1] == 0
    return json.loads(text)


def test_forked_children_share_no_id_with_their_parent_nor_with_each_other():
    """Two children forked from ONE state of a warmed parent: without the
    re-seed after the fork each would hand out the parent's next ids."""
    for _ in range(100):
        obs.new_trace_id()
    state = obs._ids.getstate()
    first = _forked_child_draws(1000)
    obs._ids.setstate(state)
    second = _forked_child_draws(1000)
    obs._ids.setstate(state)
    parent = [obs.new_trace_id() for _ in range(500)] + [obs.new_span_id() for _ in range(500)]
    assert len(set(first)) == len(set(second)) == len(set(parent)) == 1000
    assert not set(first) & set(parent)
    assert not set(second) & set(parent)
    assert not set(first) & set(second)


def _input(i: int) -> T.CheckInput:
    return T.CheckInput(
        request_id=f"r{i}",
        principal=T.Principal(id="alice", roles=["user"]),
        resource=T.Resource(kind="album", id=f"a{i}", attr={"owner": "alice" if i % 2 == 0 else "bob"}),
        actions=["view"],
    )


@pytest.fixture(scope="module")
def core(tmp_path_factory):
    """The engine over a ``BatchingEvaluator`` on the numpy backend, with the audit log on."""
    log = tmp_path_factory.mktemp("ids-audit") / "audit.log"
    core = _boot(tmp_path_factory, "ids-policies", ["audit.enabled=true", "audit.backend=file", f"audit.file.path={log}"])
    core.audit_path = log
    yield core
    core.close()


@pytest.fixture()
def spans():
    cap = _CaptureExporter()
    old = obs._exporter
    obs.set_exporter(cap)
    yield cap
    obs.set_exporter(old)


def _wait_for_spans(cap, trace_id, want):
    """The flight's last spans export on the drain thread just after the reply."""
    wait_for(lambda: want <= {s.name for s in cap.in_trace(trace_id)}, timeout=10)
    return {s.name: s for s in cap.in_trace(trace_id)}


# ``batch.pack`` is the jitted route's alone: the numpy backend evaluates inside ``submit``
FLIGHT = {"request.CheckResources", "engine.Check", "batcher.enqueue", "batch.submit",
          "batch.device", "batch.collect", "request.settle"}
ROUTES = {"inline": (1, {"request.CheckResources", "engine.Check"}), "device": (32, FLIGHT)}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_a_served_request_asks_the_system_for_no_identifier(core, spans, monkeypatch, route):
    """After boot neither ``os.urandom`` nor ``uuid.uuid4`` is called, on the
    request's thread or on the drain thread, and every span is still made."""
    n, want = ROUTES[route]
    inputs = [_input(i) for i in range(n)]
    core.service.check_resources(inputs)  # the first flight of a process starts what runs once
    calls: list[tuple[str, str]] = []
    real_urandom, real_uuid4 = os.urandom, uuid.uuid4

    def counted_urandom(size):
        calls.append(("os.urandom", threading.current_thread().name))
        return real_urandom(size)

    def counted_uuid4():
        calls.append(("uuid.uuid4", threading.current_thread().name))
        return real_uuid4()

    monkeypatch.setattr(os, "urandom", counted_urandom)
    monkeypatch.setattr(uuid, "uuid4", counted_uuid4)
    ctx = obs.SpanContext(obs.new_trace_id(), obs.new_span_id())
    outputs, call_id = core.service.check_resources(inputs, trace_ctx=ctx)
    made = _wait_for_spans(spans, ctx.trace_id, want)
    assert set(made) >= want, sorted(made)
    assert calls == []
    assert len(outputs) == n and re.fullmatch(r"[0-9a-f]{32}", call_id)
    assert made["request.CheckResources"].attributes["call_id"] == call_id
    assert made["engine.Check"].attributes["path"] == route
    ids = [s.span_id for s in spans.in_trace(ctx.trace_id)]
    assert len(set(ids)) == len(ids) and all(re.fullmatch(r"[0-9a-f]{16}", i) for i in ids)


def test_the_callers_trace_id_is_on_the_request_span_the_flights_spans_and_the_audit_entry(core, spans):
    remote = obs.SpanContext(obs.new_trace_id(), obs.new_span_id())
    _, call_id = core.service.check_resources([_input(i) for i in range(32)], trace_ctx=remote)
    made = _wait_for_spans(spans, remote.trace_id, FLIGHT)
    assert set(made) >= FLIGHT, sorted(made)
    assert made["request.CheckResources"].parent_id == remote.span_id
    assert made["batch.collect"].parent_id == made["batch.submit"].span_id
    assert made["batcher.enqueue"].context in made["batch.submit"].links

    def decision_entry():
        with open(core.audit_path) as f:
            return next((e for e in map(json.loads, f) if e.get("callId") == call_id and e["kind"] == "decision"), None)

    assert wait_for(decision_entry, timeout=10)  # the writer thread's
    entry = decision_entry()
    assert entry["traceId"] == remote.trace_id
