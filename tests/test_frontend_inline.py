"""A pool's front end answers a request under the owner's ``min_device_batch``
from its own table when it can see that the table is the owner's committed one
(engine/ipc.py: ``RemoteBatcherClient._inline_route``; PERF.md section 6, PR 33).

Server and client in one process, over a real shm segment: which requests take
the route and which a ticket, that the answer is the ticket route's element for
element and carries the owner's epoch number, what it books and what it does
not (a fallback), that nothing is answered from a table the owner has not
committed (either side ahead, a cutover pending, a live rollout under traffic),
what the identity covers, and that the owner's sentinel ring still fills.
"""

import asyncio
import os
import sys
import threading
import time

import pytest
from test_ipc import OracleEvaluator, wait_for
from test_rollout import POLICY, POLICY_V2, FakeManager, inp, make_ctl, table

from cerbos_tpu import native
from cerbos_tpu import observability as obs
from cerbos_tpu.compile import compile_policy_set
from cerbos_tpu.engine import types as T
from cerbos_tpu.engine.batcher import BatchingEvaluator
from cerbos_tpu.engine.budget import STAGE_ADMISSION, STAGE_EVALUATE, STAGE_QUEUE_WAIT, Waterfall
from cerbos_tpu.engine.ipc import BatcherIpcServer, RemoteBatcherClient
from cerbos_tpu.engine.rollout import OUTCOME_SERVING, Epoch, bundle_hash_of
from cerbos_tpu.engine.sentinel import ParitySentinel
from cerbos_tpu.observability import start_span
from cerbos_tpu.policy.parser import parse_policies
from cerbos_tpu.ruletable import build_rule_table, check_input

pytestmark = pytest.mark.skipif(native.get() is None, reason="the shared page needs the native module's shm plane")

FLOOR = 16
EPOCH = 7  # what the owner publishes where no rollout controller does: not a number a default would give


class FloorEvaluator(OracleEvaluator):
    """The CPU oracle with a ``min_device_batch``, as the device evaluator has."""

    min_device_batch = FLOOR


def epoch_of(rt, number=EPOCH):
    return Epoch(number=number, rule_table=rt, bundle_hash=bundle_hash_of(rt))


class Pool:
    """One owner (batcher + ticket server) and one attached front end."""

    def __init__(self, tmp_path, owner_rt, fe_rt=None, transport="shm", sentinel=None):
        self.batcher = BatchingEvaluator(FloorEvaluator(owner_rt), max_wait_ms=1.0)
        self.server = BatcherIpcServer(str(tmp_path / "batcher.sock"), self.batcher, transport=transport, sentinel=sentinel)
        self.server.publish_epoch(epoch_of(owner_rt))  # a test with a rollout controller wires it as build_batcher_ipc does
        self.server.start()
        self.client = RemoteBatcherClient(
            self.server.socket_path,
            fe_rt if fe_rt is not None else owner_rt,
            request_timeout_s=10.0,
            worker_label="fe-test",
            status_poll_s=0.05,
            connect_retry_s=0.05,
            transport=transport,
        )
        assert wait_for(self.client._connected.is_set)
        self._routes = routes()

    def moved(self) -> dict[str, float]:
        return {r: v - self._routes[r] for r, v in routes().items()}

    def close(self):
        self.client.close()
        self.server.close()
        self.batcher.close()


@pytest.fixture()
def pool(tmp_path):
    made = []

    def make(owner_rt=None, **kw):
        made.append(Pool(tmp_path, owner_rt if owner_rt is not None else table(), **kw))
        return made[-1]

    yield make
    for p in made:
        p.close()


def routes() -> dict[str, float]:
    vec = obs.metrics().counter_vec("cerbos_tpu_batcher_checks_total", label="route")
    return {r: vec.get(r) for r in ("inline", "queued")}


def fallbacks() -> float:
    return obs.metrics().counter_vec("cerbos_tpu_batcher_oracle_fallbacks_total", label="reason").value


def oracle_stage():
    vec = obs.metrics().histogram_vec("cerbos_tpu_batch_stage_seconds", label=("stage", "shard"))
    return vec.labels(("oracle", "0"))


def oracle(rt, inputs):
    return [check_input(rt, i, T.EvalParams()) for i in inputs]


def view(outs) -> list[str]:
    return [o.actions["view"].effect for o in outs]


def ask(client, inputs, how, **kw):
    if how == "check":
        return client.check(inputs, **kw)

    async def go():
        out = await client.check_await(inputs, **kw)
        return out, T.current_epoch()  # the stamp lives in the task's context

    out, epoch = asyncio.run(go())
    T.set_current_epoch(epoch)
    return out


HOW = ["check", "check_await"]


@pytest.mark.parametrize("how", HOW)
@pytest.mark.parametrize("n", [1, 3, FLOOR - 1])
def test_a_request_under_the_owners_floor_is_answered_in_the_front_end(pool, n, how):
    p = pool()
    inputs = [inp(i) for i in range(n)]
    seen, fell, stage = p.server.stats["checks"], fallbacks(), oracle_stage().count
    wf = Waterfall()
    T.set_current_epoch(None)
    with start_span("engine.Check") as span:
        span.set_attribute("path", "device")
        out = ask(p.client, inputs, how, wf=wf)
        assert span.attributes["path"] == "inline"
    assert out == oracle(p.client.rule_table, inputs)
    assert T.current_epoch() == EPOCH  # the owner's number, read beside the identity
    assert p.moved() == {"inline": 1, "queued": 0} and p.client.stats["inline"] == 1
    assert p.server.stats["checks"] == seen  # no ticket reached the owner
    # not a fallback, anywhere it would show
    assert fallbacks() == fell and p.client.stats["oracle_fallbacks"] == 0 and p.batcher.stats["oracle_fallbacks"] == 0
    assert wf.served_by == "device" and wf.fallback_reason == ""
    # booked as the single process books an inline answer
    assert [s for s, _ in wf.stages] == [STAGE_ADMISSION, STAGE_QUEUE_WAIT, STAGE_EVALUATE]
    assert dict(wf.stages)[STAGE_QUEUE_WAIT] < 0.001 and wf.parts[-1][0] == "enqueue" and wf.shard == 0
    assert oracle_stage().count == stage + 1


@pytest.mark.parametrize("how", HOW)
@pytest.mark.parametrize("n", [FLOOR, 40])
def test_a_request_at_or_over_the_floor_takes_a_ticket(pool, n, how):
    p = pool()
    inputs = [inp(i) for i in range(n)]
    seen = p.server.stats["checks"]
    out = ask(p.client, inputs, how)
    assert view(out) == view(oracle(p.client.rule_table, inputs))
    assert p.moved() == {"inline": 0, "queued": 1} and p.client.stats["inline"] == 0
    assert p.server.stats["checks"] == seen + 1 and p.client.stats["oracle_fallbacks"] == 0


def _over_uds(pool):
    p = pool(transport="uds")
    assert p.client.transport == "uds" and p.client._inline_under == 0
    return p


def _front_end_ahead(pool):
    # its watcher has rebuilt from a store the owner has not yet gated (or has refused)
    return pool(table(POLICY), fe_rt=table(POLICY_V2))


def _owner_ahead(pool):
    return pool(table(POLICY_V2), fe_rt=table(POLICY))


def _cutover_pending(pool):
    p = pool()
    p.server.publish_epoch(None)  # the controller's word before it asks for the barrier
    return p


def _no_identity(pool):
    p = pool()  # bundle_hash_of gave the owner "": that matches nothing, not even itself
    p.server.publish_epoch(Epoch(number=EPOCH, rule_table=None, bundle_hash=""))
    return p


def _owner_without_a_floor(pool):
    p = pool()
    p.client._inline_under = 0  # what HELLO_R carries for an evaluator with no min_device_batch
    return p


NOT_TAKEN = {
    "over_uds": _over_uds,
    "front_end_ahead_of_the_owner": _front_end_ahead,
    "owner_ahead_of_the_front_end": _owner_ahead,
    "generation_odd": _cutover_pending,
    "owner_publishes_no_identity": _no_identity,
    "owner_without_a_floor": _owner_without_a_floor,
}


@pytest.mark.parametrize("how", HOW)
@pytest.mark.parametrize("case", sorted(NOT_TAKEN))
def test_on_any_doubt_the_ticket_route_is_taken(pool, case, how):
    p = NOT_TAKEN[case](pool)
    owner_rt = p.batcher.evaluator.rule_table
    out = ask(p.client, [inp(3)], how)
    assert out == oracle(owner_rt, [inp(3)])  # the OWNER's table answered, whatever the front end holds
    assert p.moved() == {"inline": 0, "queued": 1} and p.client.stats["inline"] == 0
    assert p.client.stats["oracle_fallbacks"] == 0


@pytest.mark.parametrize("how", HOW)
def test_detached_it_falls_back_as_before_and_takes_the_route_again_once_attached(pool, how):
    p = pool()
    assert ask(p.client, [inp(1)], how) and p.client.stats["inline"] == 1
    p.server.close()
    assert wait_for(lambda: not p.client._connected.is_set())
    assert p.client._inline_under == 0
    T.set_current_epoch(None)
    ask(p.client, [inp(1)], how)
    assert p.client.stats["inline"] == 1 and p.client.stats["oracle_fallbacks"] == 1  # batcher_down, counted
    assert T.current_epoch() is None  # a fallback is unversioned, as before
    # a new owner on the same path: a fresh segment, published before HELLO_R
    p.server = BatcherIpcServer(p.server.socket_path, p.batcher)
    p.server.publish_epoch(epoch_of(p.client.rule_table, number=9))
    p.server.start()
    assert wait_for(lambda: p.client._inline_under == FLOOR)
    ask(p.client, [inp(1)], how)
    assert p.client.stats["inline"] == 2 and T.current_epoch() == 9


def test_the_page_is_written_once_per_attach_and_per_cutover_never_per_request(pool, monkeypatch):
    p = pool()
    writes = []
    seg = p.server._segs[0]
    real = seg.publish_epoch
    monkeypatch.setattr(seg, "publish_epoch", lambda *a: (writes.append(a), real(*a)))
    for i in range(50):
        p.client.check([inp(i)])
    assert writes == [] and p.client.stats["inline"] == 50
    p.server.publish_epoch(None)
    p.server.publish_epoch(epoch_of(p.client.rule_table, number=8))
    assert [w[0] for w in writes] == [True, False]
    p.client.check([inp(0)])
    assert T.current_epoch() == 8


WRITER = """
import sys, time
from cerbos_tpu.engine.ipc import _ShmSegment
seg, sets, k = _ShmSegment.attach(sys.argv[1]), ("a" * 16, "b" * 16), 0
until = time.monotonic() + 1.5
while time.monotonic() < until:
    k += 1
    seg.publish_epoch(True, k - 1, sets[(k - 1) % 2])  # pending: the old words stay, the generation goes odd
    seg.publish_epoch(False, k, sets[k % 2])
print(k)
"""


def test_a_reader_never_pairs_one_epochs_number_with_anothers_identity():
    """The page's sequence lock under stress, across processes as it is used:
    a writer in a process of its own cuts over as fast as it can between two
    policy sets, even numbers for one and odd for the other, while more reader
    threads than cores read here, the interpreter switching between them every
    few bytecodes. A read is None or a matched pair."""
    import subprocess

    from cerbos_tpu.engine.ipc import _identity_words, _ShmSegment

    seg = _ShmSegment.create("stress", 64 * 1024)
    seg.publish_epoch(False, 0, "a" * 16)
    want = {0: _identity_words("a" * 16), 1: _identity_words("b" * 16)}
    stop, torn, reads, seen = threading.Event(), [], [], set()

    def read():
        n = 0
        while not stop.is_set():
            got = seg.read_epoch()
            if got is not None:
                n += 1
                seen.add(got[0])
                if got[1] != want[got[0] % 2]:
                    torn.append(got)
        reads.append(n)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    threads = [threading.Thread(target=read, daemon=True) for _ in range(2 * (os.cpu_count() or 4))]
    try:
        writer = subprocess.Popen([sys.executable, "-c", WRITER, seg.path], stdout=subprocess.PIPE, text=True)
        for t in threads:
            t.start()
        out, _ = writer.communicate(timeout=60)
    finally:
        stop.set()
        for t in threads:
            t.join(5.0)
        sys.setswitchinterval(was)
        seg.unlink()
        seg.close()
    assert writer.returncode == 0 and int(out) > 100  # it cut over, many times
    assert not any(t.is_alive() for t in threads)
    assert torn == [] and sum(reads) > 100 and len(seen) > 10  # and the readers saw it happen


def test_a_reload_in_the_front_end_recomputes_the_identity_there_and_not_per_request(pool, monkeypatch):
    from cerbos_tpu.engine import ipc

    calls = []
    monkeypatch.setattr(ipc, "bundle_hash_of", lambda rt: (calls.append(rt), bundle_hash_of(rt))[1])
    p = pool()
    assert len(calls) == 1  # the constructor's table
    for i in range(20):
        p.client.check([inp(i)])
    assert len(calls) == 1
    v2 = table(POLICY_V2)
    p.client.refresh_table(v2)
    assert len(calls) == 2 and p.client.rule_table is v2
    assert view(p.client.check([inp(3)])) == ["EFFECT_ALLOW"]  # the owner's (v1), by ticket
    assert p.client.stats["inline"] == 20


# -- the answer is the ticket route's, over the benchmark's own request mix ------


@pytest.fixture(scope="module")
def classic():
    """The benchmark's corpus at three name-mods and a seeded draw of its two
    request mixes, through the server's own conversion."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark"))
    import benchmark_rig  # noqa: F401  (puts the repo root on sys.path for `benchmarks`)
    from google.protobuf import json_format

    from benchmarks.lib import corpus, workload
    from cerbos_tpu.api.cerbos.request.v1 import request_pb2
    from cerbos_tpu.server import convert

    mods = 3
    rt = build_rule_table(compile_policy_set(list(parse_policies(corpus.corpus_yaml(mods)))))
    requests = []
    for shape, n in (({"resources": [1, 1]}, 300), ({"resources": [2, 15]}, 60)):
        reqs = workload.build(n, mods, 2**31 + 33, shape)
        workload.serialize(reqs)
        for r in reqs:
            body = json_format.MessageToDict(request_pb2.CheckResourcesRequest.FromString(r.wire))
            aux = T.AuxData(jwt=r.jwt) if r.jwt is not None else None
            requests.append(convert.json_to_check_inputs(body, aux)[0])
    return rt, requests


def test_the_answer_is_the_ticket_routes_for_the_benchmarks_request_mix(pool, classic):
    rt, requests = classic
    p = pool(rt)
    inline = [p.client.check(r) for r in requests]
    assert p.client.stats["inline"] == len(requests)
    p.server.publish_epoch(None)  # the same requests again, every one by ticket
    ticket = [p.client.check(r) for r in requests]
    assert p.client.stats["inline"] == len(requests) and p.client.stats["oracle_fallbacks"] == 0
    assert inline == ticket  # effects, policies, rule rows, sources; outputs, validation errors, derived roles
    assert any(o.effective_derived_roles for outs in inline for o in outs)
    assert {e.effect for outs in inline for o in outs for e in o.actions.values()} >= {"EFFECT_ALLOW", "EFFECT_DENY"}


# -- a live cutover under traffic ---------------------------------------------------


class Sender(threading.Thread):
    """Singles, one after the other, each with what it saw: when it started
    and ended, the effect, the epoch stamped, and whether it was answered here."""

    def __init__(self, client):
        super().__init__(daemon=True)
        self.client, self.stop, self.seen, self.error = client, threading.Event(), [], None

    def run(self):
        try:
            k = 0
            while not self.stop.is_set():
                k += 1
                before = self.client.stats["inline"]
                t0 = time.monotonic()
                (out,) = self.client.check([inp(k)])
                t1 = time.monotonic()
                self.seen.append((t0, t1, out.actions["view"].effect, T.current_epoch(), self.client.stats["inline"] > before))
        except Exception as e:  # noqa: BLE001
            self.error = e


OLD, NEW = "EFFECT_ALLOW", "EFFECT_DENY"  # inp(k) owns its album: v1 allows the view, v2 denies it


@pytest.mark.parametrize("first", ["front_end_reloads_first", "owner_commits_first"])
def test_no_answer_crosses_a_cutover(pool, first):
    mgr = FakeManager()
    p = pool(mgr.rule_table)
    ctl = make_ctl(mgr, lanes=[p.batcher])
    ctl.subscribe("evaluator", lambda ep: setattr(p.batcher.evaluator, "rule_table", ep.rule_table))
    edges = {}

    def watched(epoch):
        if epoch is None:
            edges["pending"] = time.monotonic()  # before the barrier is asked for
        p.server.publish_epoch(epoch)

    p.server.publish_epoch(ctl.epoch)
    ctl.on_cutover = watched
    sender = Sender(p.client)
    sender.start()
    try:
        assert wait_for(lambda: len(sender.seen) > 50)
        mgr.policy_text = POLICY_V2
        if first == "front_end_reloads_first":
            p.client.refresh_table(table(POLICY_V2))
            edges["fe"] = time.monotonic()
            n = len(sender.seen)
            assert wait_for(lambda: len(sender.seen) > n + 20)  # served meanwhile, by the owner
        run = ctl.run_rollout(trigger="test")
        edges["committed"] = time.monotonic()  # the commit has returned
        assert run.outcome == OUTCOME_SERVING and run.to_epoch == 2
        if first == "owner_commits_first":
            n = len(sender.seen)
            assert wait_for(lambda: len(sender.seen) > n + 20)
            edges["fe"] = time.monotonic()
            p.client.refresh_table(table(POLICY_V2))
        n = len(sender.seen)
        assert wait_for(lambda: len(sender.seen) > n + 50)
    finally:
        sender.stop.set()
        sender.join(5.0)
        ctl.close()
    assert sender.error is None and p.client.stats["oracle_fallbacks"] == 0
    seen = sender.seen
    assert all(effect == OLD for _, t1, effect, _, _ in seen if t1 < edges["pending"])
    assert all(effect == NEW for t0, _, effect, _, _ in seen if t0 > edges["committed"])
    # an answer given here names the epoch it came from, exactly
    here = [(effect, epoch) for _, _, effect, epoch, inline in seen if inline]
    assert set(here) == {(OLD, 1), (NEW, 2)}
    # while the two sides disagree every request goes to the owner
    lo, hi = sorted((edges["fe"], edges["committed"] if first == "front_end_reloads_first" else edges["pending"]))
    between = [inline for t0, t1, _, _, inline in seen if t0 > lo and t1 < hi]
    assert len(between) >= 10 and not any(between)
    # and once both hold the new set, the front end answers again
    assert all(inline for t0, _, _, _, inline in seen if t0 > max(edges["fe"], edges["committed"]) + 0.01)


def test_a_refused_bundle_and_a_rollback_leave_the_front_end_asking_the_owner(pool):
    mgr = FakeManager()
    p = pool(mgr.rule_table)
    ctl = make_ctl(mgr, lanes=[p.batcher])
    ctl.subscribe("evaluator", lambda ep: setattr(p.batcher.evaluator, "rule_table", ep.rule_table))
    p.server.publish_epoch(ctl.epoch)
    ctl.on_cutover = p.server.publish_epoch
    try:
        # refused at the gate (swap_fail:gate stands in for any refusal): the front end's watcher has no gate
        ctl.faults = {"swap_fail": "gate"}
        mgr.policy_text = POLICY_V2
        p.client.refresh_table(table(POLICY_V2))
        assert ctl.run_rollout(trigger="test").outcome != OUTCOME_SERVING
        assert view(p.client.check([inp(1)])) == [OLD] and p.client.stats["inline"] == 0
        # accepted: both hold v2
        ctl.faults = {}
        assert ctl.run_rollout(trigger="test").outcome == OUTCOME_SERVING
        assert view(p.client.check([inp(1)])) == [NEW] and p.client.stats["inline"] == 1 and T.current_epoch() == 2
        # rolled back: the owner serves epoch 1 again, the front end still holds v2
        assert ctl.rollback(reason="test")["outcome"] == "rolled_back"
        assert view(p.client.check([inp(1)])) == [OLD] and p.client.stats["inline"] == 1
        # its watcher follows the store back: the identities meet again, under the old number
        p.client.refresh_table(table(POLICY))
        assert view(p.client.check([inp(1)])) == [OLD] and p.client.stats["inline"] == 2 and T.current_epoch() == 1
    finally:
        ctl.close()
    assert p.client.stats["oracle_fallbacks"] == 0


# -- the identity ---------------------------------------------------------------------

BUNDLE = """
apiVersion: api.cerbos.dev/v1
derivedRoles:
  name: album_roles
  definitions:
    - name: owner
      parentRoles: [user]
      condition:
        match:
          expr: request.resource.attr.owner == request.principal.id
---
apiVersion: api.cerbos.dev/v1
exportVariables:
  name: common_vars
  definitions:
    is_public: request.resource.attr.public == true
---
apiVersion: api.cerbos.dev/v1
exportConstants:
  name: common_consts
  definitions:
    limit: 10
---
apiVersion: api.cerbos.dev/v1
resourcePolicy:
  resource: album
  version: default
  importDerivedRoles: [album_roles]
  variables:
    import: [common_vars]
  constants:
    import: [common_consts]
  rules:
    - actions: ["view"]
      effect: EFFECT_ALLOW
      roles: [user]
      condition:
        match:
          all:
            of:
              - expr: V.is_public
              - expr: request.resource.attr.size < C.limit
      output:
        when:
          ruleActivated: '"viewed"'
    - actions: ["edit"]
      effect: EFFECT_ALLOW
      derivedRoles: [owner]
---
apiVersion: api.cerbos.dev/v1
resourcePolicy:
  resource: album
  version: default
  scope: acme
  scopePermissions: SCOPE_PERMISSIONS_OVERRIDE_PARENT
  rules:
    - actions: ["view"]
      effect: EFFECT_DENY
      roles: [guest]
---
apiVersion: api.cerbos.dev/v1
rolePolicy:
  role: intern
  scope: acme
  parentRoles: [guest]
  rules:
    - resource: album
      allowActions: ["view"]
"""

# one edit of each kind of document check_input reads; all but the first left PR 32's hash of rule rows as it was
EDITS = {
    "a_rules_effect": ("effect: EFFECT_DENY\n      roles: [guest]", "effect: EFFECT_ALLOW\n      roles: [guest]"),
    "a_condition_inside_all": ("request.resource.attr.size < C.limit", "request.resource.attr.size <= C.limit"),
    "a_derived_roles_condition": ("attr.owner == request.principal.id", "attr.owner != request.principal.id"),
    "an_exported_variable": ("is_public: request.resource.attr.public == true", "is_public: request.resource.attr.public == false"),
    "an_exported_constant": ("limit: 10", "limit: 11"),
    "an_output_expression": ("'\"viewed\"'", "'\"seen\"'"),
    "a_scopes_permissions": ("SCOPE_PERMISSIONS_OVERRIDE_PARENT", "SCOPE_PERMISSIONS_REQUIRE_PARENTAL_CONSENT_FOR_ALLOWS"),
    "a_role_policys_parents": ("parentRoles: [guest]", "parentRoles: [user]"),
}


@pytest.mark.parametrize("edit", sorted(EDITS))
def test_every_kind_of_document_the_oracle_reads_moves_the_identity(edit):
    old, new = EDITS[edit]
    assert old in BUNDLE
    base, again, edited = table(BUNDLE), table(BUNDLE), table(BUNDLE.replace(old, new))
    assert bundle_hash_of(base) == bundle_hash_of(again) and len(bundle_hash_of(base)) == 16
    assert bundle_hash_of(edited) != bundle_hash_of(base)


def test_the_identity_is_kept_with_the_table_and_dropped_when_the_table_is_edited():
    rt = table(BUNDLE)
    first = bundle_hash_of(rt)
    assert rt.bundle_hash_memo == first and bundle_hash_of(rt) == first
    rt.delete_policy("cerbos.resource.album.vdefault/acme")
    assert rt.bundle_hash_memo is None and bundle_hash_of(rt) != first

    class Broken:
        idx = None

    assert bundle_hash_of(Broken()) == ""  # no identity: matches nothing


# -- the owner's sentinel ring -------------------------------------------------------


def test_what_a_front_end_answers_reaches_the_owners_ring_at_the_samplers_rate_and_the_gate_replays_it(pool):
    mgr = FakeManager()
    sentinel = ParitySentinel(enabled=True, sample_rate=0.1)
    p = pool(mgr.rule_table, sentinel=sentinel)
    assert p.client._sample_rate == pytest.approx(0.1)
    ctl = make_ctl(mgr, sentinel=sentinel, lanes=[p.batcher])
    n = 200
    try:
        before = sentinel.stats["seen"]
        for i in range(n):
            p.client.check([inp(i)])
        assert p.client.stats["inline"] == n
        # the status thread carries the sample over, after the answers were handed back
        assert wait_for(lambda: len(sentinel.recent_inputs()) >= 19)
        time.sleep(0.15)
        ring = sentinel.recent_inputs()
        assert 19 <= len(ring) <= 21  # the rate, not its square: the owner does not sample the sample
        assert {i.resource.id for i in ring} <= {f"a{i}" for i in range(n)}
        assert sentinel.stats["seen"] == before  # nothing was offered to the owner's own sampler
        assert p.moved() == {"inline": n, "queued": 0} and p.server.stats["checks"] == 0  # no ticket, no flight, no route count
        mgr.policy_text = POLICY_V2
        run = ctl.run_rollout(trigger="test")
        assert run.outcome == OUTCOME_SERVING
        assert run.gate["replay"]["replayed"] == len(ring) and run.gate["replay"]["diffs"] == len(ring)
    finally:
        ctl.close()
        sentinel.close()


def test_an_owner_with_no_sentinel_is_sent_nothing(pool):
    p = pool()
    assert p.client._sample_rate == 0.0
    for i in range(30):
        p.client.check([inp(i)])
    assert not p.client._observed
