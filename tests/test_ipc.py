"""The multi-process front door's seam: the ticket queue (engine/ipc.py).

In-process pairs of ``BatcherIpcServer`` (over a ``BatchingEvaluator`` backed
by the CPU oracle) and ``RemoteBatcherClient`` on a temp unix socket prove the
PR's acceptance criteria at the unit level: decision parity with the
single-process path, deadline propagation across the process boundary,
zero-loss settling when the batcher side dies mid-flight, backpressure and
wedged-ring fallbacks, and the pool readiness ladder (warming until the shared
batcher's first SERVING report, degraded-but-live after a disconnect).
"""

import asyncio
import re
import threading
import time

import pytest

from cerbos_tpu.compile import compile_policy_set
from cerbos_tpu.engine import CheckInput, EvalParams, Principal, Resource
from cerbos_tpu.engine.batcher import BatchingEvaluator, DeadlineExceeded, _BatchFailed
from cerbos_tpu.engine.health import DeviceHealth
from cerbos_tpu.engine.ipc import (
    BatcherIpcServer,
    RemoteBatcherClient,
    decode_inputs,
    decode_outputs,
    encode_inputs,
    encode_outputs,
)
from cerbos_tpu import observability as obs
from cerbos_tpu.observability import merge_metrics_texts, relabel_metrics_text
from cerbos_tpu.policy.parser import parse_policies
from cerbos_tpu.ruletable import build_rule_table, check_input

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


def effects(outs):
    return [{a: (e.effect, e.policy) for a, e in o.actions.items()} for o in outs]


def oracle(rt, inputs, params=None):
    return [check_input(rt, i, params or EvalParams()) for i in inputs]


class OracleEvaluator:
    """CPU-oracle-backed streaming evaluator (the test_chaos harness): the
    ticket queue's behavior must not depend on jax being importable."""

    def __init__(self, rt, submit_delay_s: float = 0.0):
        self.rule_table = rt
        self.schema_mgr = None
        self.submit_delay_s = submit_delay_s
        self.stats = {"device_inputs": 0}

    def check(self, inputs, params=None):
        return oracle(self.rule_table, inputs, params)

    def submit(self, inputs, params=None):
        if self.submit_delay_s:
            time.sleep(self.submit_delay_s)
        self.stats["device_inputs"] += len(inputs)
        return self.check(inputs, params)

    def collect(self, ticket):
        return ticket


def wait_for(cond, timeout=5.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


@pytest.fixture()
def rt():
    return table()


def make_pair(
    tmp_path,
    rt,
    submit_delay_s=0.0,
    readiness=None,
    max_outstanding=4096,
    faults=None,
    health=None,
    request_timeout_s=30.0,
):
    batcher = BatchingEvaluator(
        OracleEvaluator(rt, submit_delay_s=submit_delay_s), max_wait_ms=1.0, health=health
    )
    server = BatcherIpcServer(
        str(tmp_path / "batcher.sock"),
        batcher,
        readiness=readiness,
        max_outstanding=max_outstanding,
        faults=faults,
    )
    server.start()
    client = RemoteBatcherClient(
        server.socket_path,
        rt,
        request_timeout_s=request_timeout_s,
        worker_label="fe-test",
        status_poll_s=0.05,
        connect_retry_s=0.05,
    )
    assert wait_for(client._connected.is_set)
    return batcher, server, client


class TestCodec:
    def test_inputs_roundtrip(self, rt):
        inputs = [inp(i) for i in range(7)]
        decoded = decode_inputs(encode_inputs(inputs))
        assert effects(oracle(rt, decoded)) == effects(oracle(rt, inputs))
        assert [d.request_id for d in decoded] == [i.request_id for i in inputs]
        # attrs arrive pre-normalized: no __post_init__ re-run on decode
        assert decoded[0].principal.id == "u0"
        assert decoded[3].resource.attr["public"] is True

    def test_outputs_roundtrip(self, rt):
        outs = oracle(rt, [inp(i) for i in range(7)])
        decoded = decode_outputs(encode_outputs(outs))
        assert effects(decoded) == effects(outs)
        assert [d.resource_id for d in decoded] == [o.resource_id for o in outs]


class TestTicketQueue:
    def test_decision_parity_with_single_process_path(self, tmp_path, rt):
        """Acceptance pin: the multi-process path must produce bit-identical
        decisions to the single-process batcher/oracle path."""
        batcher, server, client = make_pair(tmp_path, rt)
        try:
            inputs = [inp(i) for i in range(64)]
            remote = client.check(inputs)
            assert effects(remote) == effects(batcher.check(inputs))
            assert effects(remote) == effects(oracle(rt, inputs))
            assert client.stats["oracle_fallbacks"] == 0
        finally:
            client.close()
            server.close()
            batcher.close()

    def test_check_await_parity(self, tmp_path, rt):
        batcher, server, client = make_pair(tmp_path, rt)
        try:
            inputs = [inp(i) for i in range(16)]

            async def go():
                return await client.check_await(inputs)

            remote = asyncio.run(go())
            assert effects(remote) == effects(oracle(rt, inputs))
        finally:
            client.close()
            server.close()
            batcher.close()

    def test_expired_deadline_raises(self, tmp_path, rt):
        batcher, server, client = make_pair(tmp_path, rt)
        try:
            with pytest.raises(DeadlineExceeded):
                client.check([inp(1)], deadline=time.monotonic() - 0.01)
        finally:
            client.close()
            server.close()
            batcher.close()

    def test_deadline_crosses_process_boundary(self, tmp_path, rt):
        """The deadline rides the ticket as relative remaining time and the
        batcher drops expired work at drain time."""
        batcher, server, client = make_pair(tmp_path, rt, submit_delay_s=0.3)
        try:
            with pytest.raises(DeadlineExceeded):
                # saturate the drain loop so the second ticket expires queued
                t = threading.Thread(target=lambda: client.check([inp(0)]))
                t.start()
                try:
                    client.check([inp(1)], deadline=time.monotonic() + 0.05)
                finally:
                    t.join()
        finally:
            client.close()
            server.close()
            batcher.close()

    def test_batcher_down_serves_oracle_fast(self, tmp_path, rt):
        client = RemoteBatcherClient(
            str(tmp_path / "nobody-home.sock"),
            rt,
            status_poll_s=0.05,
            connect_retry_s=0.05,
        )
        try:
            t0 = time.perf_counter()
            outs = client.check([inp(i) for i in range(8)])
            # no connection: the fallback must not wait out any timeout
            assert time.perf_counter() - t0 < 1.0
            assert effects(outs) == effects(oracle(rt, [inp(i) for i in range(8)]))
            assert client.stats["oracle_fallbacks"] == 1
        finally:
            client.close()

    def test_midflight_death_loses_zero_requests(self, tmp_path, rt):
        """Kill the batcher side with tickets in flight: every waiter must
        settle promptly via the local oracle with correct decisions."""
        batcher, server, client = make_pair(tmp_path, rt, submit_delay_s=0.5)
        results = {}

        def one(i):
            results[i] = client.check([inp(i)])

        threads = [threading.Thread(target=one, args=(i,)) for i in range(12)]
        try:
            for t in threads:
                t.start()
            assert wait_for(lambda: len(client._pending) > 0)
            server.close()
            batcher.close()
            t0 = time.perf_counter()
            for t in threads:
                t.join(timeout=10.0)
            assert all(not t.is_alive() for t in threads)
            # settled by the disconnect, not by the 30s request timeout
            assert time.perf_counter() - t0 < 10.0
            assert len(results) == 12
            for i, outs in results.items():
                assert effects(outs) == effects(oracle(rt, [inp(i)]))
        finally:
            client.close()

    def test_breaker_open_refusal_serves_frontend_oracle(self, tmp_path, rt):
        health = DeviceHealth(failure_threshold=1)
        health.record_failure()
        assert health.state == "open"
        batcher, server, client = make_pair(tmp_path, rt, health=health)
        try:
            outs = client.check([inp(i) for i in range(4)])
            assert effects(outs) == effects(oracle(rt, [inp(i) for i in range(4)]))
            assert client.stats["oracle_fallbacks"] == 1
            # the refusal reason travels back over the queue
            assert client.m_fallbacks.get("breaker_open") >= 1
        finally:
            client.close()
            server.close()
            batcher.close()

    def test_wedged_ring_falls_back_via_timeout(self, tmp_path, rt):
        batcher, server, client = make_pair(
            tmp_path, rt, faults={"ipc_wedge_after": 1}, request_timeout_s=0.3
        )
        try:
            assert effects(client.check([inp(0)])) == effects(oracle(rt, [inp(0)]))
            t0 = time.perf_counter()
            outs = client.check([inp(1)])
            assert 0.2 < time.perf_counter() - t0 < 5.0
            assert effects(outs) == effects(oracle(rt, [inp(1)]))
            assert server.stats["wedged_drops"] >= 1
            assert client.stats["oracle_fallbacks"] == 1
        finally:
            client.close()
            server.close()
            batcher.close()

    def test_full_queue_backpressure(self, tmp_path, rt):
        batcher, server, client = make_pair(tmp_path, rt, submit_delay_s=0.3, max_outstanding=1)
        try:
            full0 = client.m_full.value
            t = threading.Thread(target=lambda: client.check([inp(0)]))
            t.start()
            assert wait_for(lambda: server._outstanding >= 1)
            outs = client.check([inp(1)])
            t.join()
            assert effects(outs) == effects(oracle(rt, [inp(1)]))
            assert server.stats["rejected_full"] >= 1
            # full refusals are counted ONCE per pool, on the front end that
            # receives the ERR — the batcher keeps only the stats entry. In
            # this in-process harness both sides alias the same registry
            # instrument, so an exact +1 proves neither side double-counts.
            assert client.m_full.value == full0 + 1
        finally:
            client.close()
            server.close()
            batcher.close()


class TestPoolReadiness:
    def test_warming_until_first_ready_then_degraded_on_disconnect(self, tmp_path, rt):
        status = {"status": "warming"}
        batcher, server, client = make_pair(tmp_path, rt, readiness=lambda: dict(status))
        try:
            assert wait_for(lambda: client._last_status is not None)
            assert client.remote_status()["status"] == "warming"
            # batcher warmup completes → the pool opens
            status["status"] = "ready"
            assert wait_for(lambda: client.remote_status()["status"] == "ready")
            # batcher dies → degraded-but-live, never back to warming
            server.close()
            batcher.close()
            assert wait_for(lambda: client.remote_status()["status"] == "degraded")
            assert client.remote_status()["attached"] is False
        finally:
            client.close()

    def test_never_attached_reports_warming(self, tmp_path, rt):
        client = RemoteBatcherClient(
            str(tmp_path / "nobody-home.sock"), rt, status_poll_s=0.05, connect_retry_s=0.05
        )
        try:
            assert client.remote_status()["status"] == "warming"
        finally:
            client.close()


class TestControlFrames:
    def test_flight_and_metrics_frames(self, tmp_path, rt):
        batcher, server, client = make_pair(tmp_path, rt, readiness=lambda: {"status": "ready"})
        try:
            client.check([inp(i) for i in range(8)])
            dump = client.fetch_flight()
            assert "flight" in dump and "pid" in dump
            assert {"capacity", "batches", "events"} <= set(dump["flight"])
            text = client.fetch_metrics_text()
            assert "cerbos_tpu_ipc_ring_depth" in text
            assert "cerbos_tpu_batcher_batches_total" in text
        finally:
            client.close()
            server.close()
            batcher.close()


@pytest.fixture()
def fake_profiler(tmp_path, monkeypatch):
    """The profiler enabled, with the jax capture stood in for: it lasts as
    long as asked and returns the four clocks the real one publishes."""
    from cerbos_tpu.tpu import profiler

    def run_trace(path, seconds):
        start = {"trace_start_monotonic_ns": time.monotonic_ns(), "trace_start_unix_ns": time.time_ns()}
        time.sleep(seconds)
        return {**start, "trace_stop_monotonic_ns": time.monotonic_ns(), "trace_stop_unix_ns": time.time_ns()}

    monkeypatch.setattr(profiler, "_run_trace", run_trace)
    profiler.configure(enabled=True, dir=str(tmp_path / "profiles"), max_seconds=5.0)
    yield profiler
    profiler.configure(enabled=False)


def frontend_http(client, label="fe1"):
    """An HTTP/gRPC server in the front-end role over ``client``."""
    from cerbos_tpu.engine.engine import Engine
    from cerbos_tpu.server.server import Server, ServerConfig
    from cerbos_tpu.server.service import CerbosService

    svc = CerbosService(Engine(client.rule_table, tpu_evaluator=client, tpu_batch_threshold=1))
    srv = Server(
        svc,
        ServerConfig(http_listen_addr="127.0.0.1:0", grpc_listen_addr="127.0.0.1:0", worker_label=label),
    )
    srv.start()
    return srv


def http_get(port, path, timeout=10.0):
    import json
    import urllib.error
    import urllib.request

    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


class TestProfileForward:
    """A front end holds no device: ``/_cerbos/debug/profile`` runs in the
    device owner, over the control connection (PR 27)."""

    def test_capture_runs_in_the_owner_and_checks_keep_flowing(self, tmp_path, rt, fake_profiler):
        import os

        batcher, server, client = make_pair(tmp_path, rt)
        try:
            box = {}
            t = threading.Thread(target=lambda: box.update(client.fetch_profile(0.6)))
            t0 = time.monotonic()
            t.start()
            assert wait_for(lambda: fake_profiler._active)
            # tickets and status frames keep flowing while the capture runs
            outs = client.check([inp(i) for i in range(4)])
            assert effects(outs) == effects(oracle(rt, [inp(i) for i in range(4)]))
            assert client.fetch_flight()["pid"] == os.getpid()
            assert time.monotonic() - t0 < 0.5 and fake_profiler._active
            # a second capture meanwhile is refused as busy, as locally
            assert client.fetch_profile(0.1)["kind"] == "busy"
            t.join(timeout=5)
            art = box["artifact"]
            assert art["pid"] == os.getpid() and art["seconds"] == 0.6
            assert art["path"].startswith(str(tmp_path / "profiles"))
            assert art["trace_start_monotonic_ns"] < art["trace_stop_monotonic_ns"]
            assert art["trace_start_unix_ns"] < art["trace_stop_unix_ns"]
        finally:
            client.close()
            server.close()
            batcher.close()

    def test_owner_errors_carry_their_kind(self, tmp_path, rt, fake_profiler):
        batcher, server, client = make_pair(tmp_path, rt)
        try:
            assert client.fetch_profile(0.0)["kind"] == "invalid"
            fake_profiler.configure(enabled=False)
            out = client.fetch_profile(0.1)
            assert out["kind"] == "disabled" and "disabled" in out["error"]
        finally:
            client.close()
            server.close()
            batcher.close()

    def test_http_maps_the_owner_reply_as_the_local_handler_does(self, tmp_path, rt, fake_profiler, monkeypatch):
        import os

        batcher, server, client = make_pair(tmp_path, rt)
        srv = frontend_http(client)
        try:
            box = {}
            t = threading.Thread(
                target=lambda: box.update(first=http_get(srv.http_port, "/_cerbos/debug/profile?seconds=0.5"))
            )
            t.start()
            assert wait_for(lambda: fake_profiler._active)
            status, body = http_get(srv.http_port, "/_cerbos/debug/profile?seconds=0.1")
            assert status == 409 and "already running" in body["error"]
            t.join(timeout=5)
            status, body = box["first"]
            assert status == 200 and body["pid"] == os.getpid() and "trace_stop_unix_ns" in body
            assert http_get(srv.http_port, "/_cerbos/debug/profile?seconds=0")[0] == 400
            assert http_get(srv.http_port, "/_cerbos/debug/profile?seconds=x")[0] == 400
            # disabled in the owner alone (a front end's own switch is checked first)
            fake_profiler.configure(enabled=False)
            assert http_get(srv.http_port, "/_cerbos/debug/profile")[0] == 403
            monkeypatch.setattr(fake_profiler, "enabled", lambda: True)
            status, body = http_get(srv.http_port, "/_cerbos/debug/profile")
            assert status == 403 and "disabled" in body["error"]
            # no owner: an answer, not a hang
            server.close()
            assert wait_for(lambda: not client._connected.is_set())
            assert http_get(srv.http_port, "/_cerbos/debug/profile")[0] == 503
        finally:
            srv.stop()
            client.close()
            server.close()
            batcher.close()


class TestPoolScrape:
    """``/_cerbos/metrics`` answered by any front end holds every attached
    front end and the owner, rendered for that request (PR 27). In one test
    process every party shares one registry, so this proves who is asked and
    how the texts are labelled and merged; that the counts of separate
    processes add up is tests/test_workers.py's."""

    def test_owner_gathers_every_other_front_end(self, tmp_path, rt):
        batcher, server, fe1 = make_pair(tmp_path, rt)
        fe2 = RemoteBatcherClient(server.socket_path, rt, worker_label="fe2", status_poll_s=0.05)
        fe1.worker_label = "fe1"
        silent = RemoteBatcherClient(server.socket_path, rt, worker_label="fe3", status_poll_s=0.05)
        try:
            assert wait_for(fe2._connected.is_set) and wait_for(silent._connected.is_set)
            assert wait_for(lambda: len(server._peers) == 3)
            fe1.check([inp(i) for i in range(4)])
            text = fe1.fetch_metrics_text()
            # the owner and the two siblings, not the asker (it adds its own)
            workers = set(re.findall(r'cerbos_tpu_decisions_total\{worker="([^"]+)"', text))
            workers |= set(re.findall(r'cerbos_tpu_batcher_batches_total\{worker="([^"]+)"', text))
            assert workers == {"batcher", "fe2", "fe3"}
            assert text.count("# TYPE cerbos_tpu_batcher_batches_total counter") == 1
            # a sibling that does not answer is left out after the wait, not waited for for ever
            silent.local_metrics_text = lambda: time.sleep(30) or ""
            t0 = time.monotonic()
            text = fe1.fetch_metrics_text()
            assert 2.0 <= time.monotonic() - t0 < 4.5
            assert 'worker="fe2"' in text and 'worker="fe3"' not in text and 'worker="batcher"' in text
            assert not server._scrapes
        finally:
            for c in (fe1, fe2, silent):
                c.close()
            server.close()
            batcher.close()

    def test_http_scrape_through_a_front_end_holds_the_pool(self, tmp_path, rt):
        batcher, server, fe1 = make_pair(tmp_path, rt)
        fe2 = RemoteBatcherClient(server.socket_path, rt, worker_label="fe2", status_poll_s=0.05)
        srv1, srv2 = frontend_http(fe1, "fe1"), frontend_http(fe2, "fe2")
        try:
            assert wait_for(lambda: len(server._peers) == 2)
            import urllib.request

            with urllib.request.urlopen(f"http://127.0.0.1:{srv1.http_port}/_cerbos/metrics", timeout=10) as r:
                text = r.read().decode()
            # each process's own scrape body, the service's counters included, under its label
            for w in ("fe1", "fe2"):
                assert f'cerbos_dev_engine_check_count{{worker="{w}"}}' in text
                assert f'cerbos_tpu_request_handler_seconds_count{{worker="{w}"}}' in text
            assert 'cerbos_tpu_ipc_connections{worker="batcher"} 2' in text
            assert text.count("# TYPE cerbos_tpu_request_front_seconds histogram") == 1
            # and the new families pass the relabel/merge lint like any other
            assert re.search(
                r'cerbos_tpu_request_front_seconds_bucket\{worker="fe2",part="enqueue",le="[^"]+"\} \d+', text
            )
            assert re.search(r'cerbos_tpu_request_back_seconds_sum\{worker="fe1",part="wake"\} ', text)
        finally:
            srv1.stop()
            srv2.stop()
            fe1.close()
            fe2.close()
            server.close()
            batcher.close()


class TestCheckAsync:
    """BatchingEvaluator.check_async refuses via the settled future so the
    front-end process (not the batcher) serves the oracle."""

    def test_settles_with_result(self, rt):
        b = BatchingEvaluator(OracleEvaluator(rt), max_wait_ms=1.0)
        try:
            fut = b.check_async([inp(i) for i in range(4)])
            outs = fut.result(timeout=5.0)
            assert effects(outs) == effects(oracle(rt, [inp(i) for i in range(4)]))
        finally:
            b.close()

    def test_expired_deadline_settles_exception(self, rt):
        b = BatchingEvaluator(OracleEvaluator(rt), max_wait_ms=1.0)
        try:
            fut = b.check_async([inp(0)], deadline=time.monotonic() - 1.0)
            with pytest.raises(DeadlineExceeded):
                fut.result(timeout=1.0)
        finally:
            b.close()

    def test_breaker_open_settles_batch_failed(self, rt):
        health = DeviceHealth(failure_threshold=1)
        health.record_failure()
        b = BatchingEvaluator(OracleEvaluator(rt), max_wait_ms=1.0, health=health)
        try:
            fut = b.check_async([inp(0)])
            with pytest.raises(_BatchFailed) as ei:
                fut.result(timeout=1.0)
            assert ei.value.reason == "breaker_open"
        finally:
            b.close()

    def test_closed_batcher_settles_dead(self, rt):
        b = BatchingEvaluator(OracleEvaluator(rt), max_wait_ms=1.0)
        b.close()
        fut = b.check_async([inp(0)])
        with pytest.raises(_BatchFailed) as ei:
            fut.result(timeout=1.0)
        assert ei.value.reason == "batcher_dead"


class TestMetricsRelabel:
    def test_relabel_injects_worker_label(self):
        text = '# TYPE a counter\na 1\nb{x="1"} 2\n'
        out = relabel_metrics_text(text, "worker", "fe1")
        assert 'a{worker="fe1"} 1' in out
        assert 'b{worker="fe1",x="1"} 2' in out
        assert "# TYPE a counter" in out

    def test_relabel_keeps_a_samples_own_label_as_exported(self):
        """The owner's ``cerbos_tpu_ipc_enqueue_seconds`` is labelled by front
        end under ``worker`` itself: stamped with ``worker="batcher"`` it held
        the name twice (seen in a pool's scrape on the chip, PR 32)."""
        text = 'e_bucket{worker="fe1",le="0.1"} 3\ne_sum{worker="fe1"} 0.2\nf{coworker="x",note="worker=\\"1\\""} 1\n'
        out = relabel_metrics_text(text, "worker", "batcher")
        assert 'e_bucket{worker="batcher",exported_worker="fe1",le="0.1"} 3' in out
        assert 'e_sum{worker="batcher",exported_worker="fe1"} 0.2' in out
        assert 'f{worker="batcher",coworker="x",note="worker=\\"1\\""} 1' in out  # another name, and a value, are left alone
        assert all(line.count(' worker="') + line.count('{worker="') + line.count(',worker="') == 1 for line in out.splitlines())

    def test_merge_dedupes_family_comments(self):
        a = "# TYPE m counter\n# HELP m help\nm{worker=\"fe1\"} 1\n"
        b = "# TYPE m counter\n# HELP m help\nm{worker=\"batcher\"} 2\n"
        merged = merge_metrics_texts(a, b)
        assert merged.count("# TYPE m counter") == 1
        assert merged.count("# HELP m help") == 1
        assert 'm{worker="fe1"} 1' in merged
        assert 'm{worker="batcher"} 2' in merged

    def test_relabel_and_merge_cover_budget_and_pressure_families(self):
        """The PR 9 families flow through the purely textual relabel/merge
        machinery like any other series: labeled histograms keep their
        stage/shard labels, gauges pick up the worker label, and merging a
        front end's text with the batcher's keeps both processes' series."""
        fe = (
            "# TYPE cerbos_tpu_request_stage_seconds histogram\n"
            'cerbos_tpu_request_stage_seconds_bucket{stage="ipc_encode",shard="0",le="0.001"} 3\n'
            'cerbos_tpu_request_stage_seconds_sum{stage="ipc_encode",shard="0"} 0.002\n'
            "# TYPE cerbos_tpu_decisions_total counter\n"
            'cerbos_tpu_decisions_total{api="check",outcome="deadline_met"} 7\n'
            "# TYPE cerbos_tpu_pressure_score gauge\n"
            "cerbos_tpu_pressure_score 0.25\n"
        )
        batcher = (
            "# TYPE cerbos_tpu_request_stage_seconds histogram\n"
            'cerbos_tpu_request_stage_seconds_bucket{stage="queue_wait",shard="1",le="0.001"} 5\n'
            "# TYPE cerbos_tpu_pressure_score gauge\n"
            "cerbos_tpu_pressure_score 0.75\n"
        )
        fe_rel = relabel_metrics_text(fe, "worker", "fe0")
        b_rel = relabel_metrics_text(batcher, "worker", "batcher")
        assert (
            'cerbos_tpu_request_stage_seconds_bucket{worker="fe0",stage="ipc_encode",shard="0",le="0.001"} 3'
            in fe_rel
        )
        assert 'cerbos_tpu_decisions_total{worker="fe0",api="check",outcome="deadline_met"} 7' in fe_rel
        assert 'cerbos_tpu_pressure_score{worker="batcher"} 0.75' in b_rel
        merged = merge_metrics_texts(fe_rel, b_rel)
        assert merged.count("# TYPE cerbos_tpu_request_stage_seconds histogram") == 1
        assert merged.count("# TYPE cerbos_tpu_pressure_score gauge") == 1
        assert 'cerbos_tpu_pressure_score{worker="fe0"} 0.25' in merged
        assert 'cerbos_tpu_pressure_score{worker="batcher"} 0.75' in merged
        assert (
            'cerbos_tpu_request_stage_seconds_bucket{worker="batcher",stage="queue_wait",shard="1",le="0.001"} 5'
            in merged
        )

    def test_relabel_and_merge_cover_request_part_families(self):
        """The PR 27 families, rendered by the live registry: the part label
        survives relabeling, the unlabelled handler histogram picks the
        worker label up, and two front ends' texts merge under one TYPE."""
        from cerbos_tpu.engine import budget as budget_mod

        trk = budget_mod.tracker()
        wf = budget_mod.Waterfall()
        wf.part("validate")
        wf.mark("admission", part="enqueue")
        trk.finish(wf, "deadline_met", final_stage="reply_encode", final_part="encode")
        trk.observe_reply(wf.t0, wf.t0, wf.t0 + 0.0007)  # inside the le="0.001" bucket, not on its edge
        text = "\n".join(
            line for line in obs.metrics().render().splitlines()
            if re.match(r"(# TYPE )?cerbos_tpu_request_(front|back|handler)_seconds", line)
        ) + "\n"
        merged = merge_metrics_texts(
            relabel_metrics_text(text, "worker", "fe1"), relabel_metrics_text(text, "worker", "fe2")
        )
        for family in ("front", "back", "handler"):
            assert merged.count(f"# TYPE cerbos_tpu_request_{family}_seconds histogram") == 1
        for w in ("fe1", "fe2"):
            assert re.search(rf'cerbos_tpu_request_front_seconds_count\{{worker="{w}",part="enqueue"\}} [1-9]', merged)
            assert re.search(rf'cerbos_tpu_request_back_seconds_sum\{{worker="{w}",part="serialize"\}} ', merged)
            assert re.search(rf'cerbos_tpu_request_handler_seconds_bucket\{{worker="{w}",le="0\.001"\}} [1-9]', merged)

    def test_relabel_and_merge_cover_transport_families(self):
        """The PR 10 transport families flow through the textual machinery
        like any other series: transport/dir labels survive relabeling, and
        cerbos_tpu_ipc_full_total — registered by BOTH sides of the queue —
        dedupes its family comment when the two processes' texts merge."""
        fe = (
            "# TYPE cerbos_tpu_ipc_frame_bytes histogram\n"
            'cerbos_tpu_ipc_frame_bytes_bucket{transport="shm",dir="out",le="1024"} 9\n'
            'cerbos_tpu_ipc_frame_bytes_sum{transport="shm",dir="out"} 4096\n'
            "# TYPE cerbos_tpu_ipc_full_total counter\n"
            'cerbos_tpu_ipc_full_total{transport="shm"} 2\n'
            "# TYPE cerbos_tpu_ipc_client_rtt_seconds histogram\n"
            'cerbos_tpu_ipc_client_rtt_seconds_bucket{transport="shm",le="0.005"} 11\n'
        )
        batcher = (
            "# TYPE cerbos_tpu_ipc_ring_depth gauge\n"
            'cerbos_tpu_ipc_ring_depth{transport="shm"} 3\n'
            "# TYPE cerbos_tpu_ipc_full_total counter\n"
            'cerbos_tpu_ipc_full_total{transport="uds"} 1\n'
        )
        fe_rel = relabel_metrics_text(fe, "worker", "fe0")
        b_rel = relabel_metrics_text(batcher, "worker", "batcher")
        assert (
            'cerbos_tpu_ipc_frame_bytes_bucket{worker="fe0",transport="shm",dir="out",le="1024"} 9'
            in fe_rel
        )
        assert 'cerbos_tpu_ipc_client_rtt_seconds_bucket{worker="fe0",transport="shm",le="0.005"} 11' in fe_rel
        merged = merge_metrics_texts(fe_rel, b_rel)
        assert merged.count("# TYPE cerbos_tpu_ipc_full_total counter") == 1
        assert 'cerbos_tpu_ipc_full_total{worker="fe0",transport="shm"} 2' in merged
        assert 'cerbos_tpu_ipc_full_total{worker="batcher",transport="uds"} 1' in merged
        assert 'cerbos_tpu_ipc_ring_depth{worker="batcher",transport="shm"} 3' in merged

    def test_relabel_and_merge_cover_policy_analysis_families(self):
        """The PR 14 static-analysis families are multi-label gauges and
        reason-coded counters; both processes publish them (the batcher
        owns the live table, a front end may analyze a candidate bundle),
        so the merged scrape must keep each worker's verdicts distinct."""
        batcher = (
            "# TYPE cerbos_tpu_policy_analysis_total gauge\n"
            'cerbos_tpu_policy_analysis_total{class="device",reason="ok"} 75\n'
            'cerbos_tpu_policy_analysis_total{class="oracle-only",reason="operand_unsupported"} 3\n'
            "# TYPE cerbos_tpu_cond_compile_unsupported_total counter\n"
            'cerbos_tpu_cond_compile_unsupported_total{reason="unsupported_membership"} 3\n'
        )
        fe = (
            "# TYPE cerbos_tpu_policy_analysis_total gauge\n"
            'cerbos_tpu_policy_analysis_total{class="tagged-fallback",reason="eq_collection_operand"} 49\n'
            "# TYPE cerbos_tpu_cond_compile_unsupported_total counter\n"
            'cerbos_tpu_cond_compile_unsupported_total{reason="undefined_global"} 1\n'
        )
        b_rel = relabel_metrics_text(batcher, "worker", "batcher")
        fe_rel = relabel_metrics_text(fe, "worker", "fe0")
        assert (
            'cerbos_tpu_policy_analysis_total{worker="batcher",class="oracle-only",reason="operand_unsupported"} 3'
            in b_rel
        )
        merged = merge_metrics_texts(b_rel, fe_rel)
        assert merged.count("# TYPE cerbos_tpu_policy_analysis_total gauge") == 1
        assert merged.count("# TYPE cerbos_tpu_cond_compile_unsupported_total counter") == 1
        assert 'cerbos_tpu_policy_analysis_total{worker="batcher",class="device",reason="ok"} 75' in merged
        assert (
            'cerbos_tpu_policy_analysis_total{worker="fe0",class="tagged-fallback",reason="eq_collection_operand"} 49'
            in merged
        )
        assert 'cerbos_tpu_cond_compile_unsupported_total{worker="batcher",reason="unsupported_membership"} 3' in merged
        assert 'cerbos_tpu_cond_compile_unsupported_total{worker="fe0",reason="undefined_global"} 1' in merged

    def test_relabel_and_merge_cover_rollout_families(self):
        """The rollout families span both processes: the batcher owns the
        rollout machinery (stage counters, epoch gauge), while each front
        end exports its own policy_epoch plus the skew gauge measuring lag
        behind the batcher's STATUS frames. A merged scrape must keep the
        per-worker epochs distinct — epoch disagreement across workers IS
        the mixed-epoch alert signal."""
        batcher = (
            "# TYPE cerbos_tpu_rollout_total counter\n"
            'cerbos_tpu_rollout_total{stage="gate",outcome="ok"} 4\n'
            'cerbos_tpu_rollout_total{stage="canary",outcome="rolled_back"} 1\n'
            "# TYPE cerbos_tpu_rollout_duration_seconds histogram\n"
            'cerbos_tpu_rollout_duration_seconds_bucket{stage="cutover",le="0.1"} 4\n'
            'cerbos_tpu_rollout_duration_seconds_sum{stage="cutover"} 0.12\n'
            "# TYPE cerbos_tpu_policy_epoch gauge\n"
            "cerbos_tpu_policy_epoch 7\n"
        )
        fe = (
            "# TYPE cerbos_tpu_policy_epoch gauge\n"
            "cerbos_tpu_policy_epoch 7\n"
            "# TYPE cerbos_tpu_policy_epoch_skew_seconds gauge\n"
            "cerbos_tpu_policy_epoch_skew_seconds 0.31\n"
        )
        b_rel = relabel_metrics_text(batcher, "worker", "batcher")
        fe_rel = relabel_metrics_text(fe, "worker", "fe0")
        assert 'cerbos_tpu_rollout_total{worker="batcher",stage="canary",outcome="rolled_back"} 1' in b_rel
        assert (
            'cerbos_tpu_rollout_duration_seconds_bucket{worker="batcher",stage="cutover",le="0.1"} 4'
            in b_rel
        )
        merged = merge_metrics_texts(b_rel, fe_rel)
        # policy_epoch is registered by BOTH sides: family comment dedupes,
        # both workers' series survive so skew is observable per process
        assert merged.count("# TYPE cerbos_tpu_policy_epoch gauge") == 1
        assert 'cerbos_tpu_policy_epoch{worker="batcher"} 7' in merged
        assert 'cerbos_tpu_policy_epoch{worker="fe0"} 7' in merged
        assert 'cerbos_tpu_policy_epoch_skew_seconds{worker="fe0"} 0.31' in merged
        assert 'cerbos_tpu_rollout_total{worker="batcher",stage="gate",outcome="ok"} 4' in merged

    def test_relabel_and_merge_cover_plan_families(self):
        """The batched-planner families ride the same textual machinery:
        mode/path labels survive relabeling, plan traffic booked under
        decisions_total{api="plan"} keeps its api dimension, and the
        plan-parity counters merge alongside the check-parity ones."""
        batcher = (
            "# TYPE cerbos_tpu_plan_batch_seconds histogram\n"
            'cerbos_tpu_plan_batch_seconds_bucket{mode="numpy",le="0.01"} 12\n'
            'cerbos_tpu_plan_batch_seconds_sum{mode="numpy"} 0.05\n'
            "# TYPE cerbos_tpu_plan_queries_total counter\n"
            'cerbos_tpu_plan_queries_total{path="device"} 900\n'
            'cerbos_tpu_plan_queries_total{path="symbolic"} 100\n'
            "# TYPE cerbos_tpu_plan_parity_checks_total counter\n"
            "cerbos_tpu_plan_parity_checks_total 40\n"
            "# TYPE cerbos_tpu_plan_parity_divergence_total counter\n"
            "cerbos_tpu_plan_parity_divergence_total 0\n"
        )
        fe = (
            "# TYPE cerbos_tpu_decisions_total counter\n"
            'cerbos_tpu_decisions_total{api="plan",outcome="deadline_met"} 31\n'
            'cerbos_tpu_decisions_total{api="plan",outcome="refused"} 4\n'
        )
        b_rel = relabel_metrics_text(batcher, "worker", "batcher")
        fe_rel = relabel_metrics_text(fe, "worker", "fe0")
        assert 'cerbos_tpu_plan_batch_seconds_bucket{worker="batcher",mode="numpy",le="0.01"} 12' in b_rel
        assert 'cerbos_tpu_plan_queries_total{worker="batcher",path="device"} 900' in b_rel
        assert 'cerbos_tpu_plan_parity_divergence_total{worker="batcher"} 0' in b_rel
        merged = merge_metrics_texts(b_rel, fe_rel)
        assert merged.count("# TYPE cerbos_tpu_plan_queries_total counter") == 1
        assert 'cerbos_tpu_decisions_total{worker="fe0",api="plan",outcome="refused"} 4' in merged
        assert 'cerbos_tpu_plan_parity_checks_total{worker="batcher"} 40' in merged


class TestTransportMetricsLint:
    def test_ipc_families_register_with_transport_labels(self, tmp_path, rt):
        """Extends the registry lint (test_tracing.TestMetricsLint) to the
        transport families, which only register once an ipc pair exists:
        conformant names, help text, and the transport label dimension in
        the documented position."""
        batcher, server, client = make_pair(tmp_path, rt)
        try:
            client.check([inp(0)])
            inst = obs.metrics().instruments()
            want = {
                "cerbos_tpu_ipc_ring_depth": (obs.GaugeVec, "transport"),
                "cerbos_tpu_ipc_full_total": (obs.CounterVec, "transport"),
                "cerbos_tpu_ipc_frame_bytes": (obs.HistogramVec, ("transport", "dir")),
                "cerbos_tpu_ipc_client_rtt_seconds": (obs.HistogramVec, "transport"),
                "cerbos_tpu_ipc_client_reconnects_total": (obs.CounterVec, "transport"),
            }
            for name, (typ, label) in want.items():
                m = inst.get(name)
                assert isinstance(m, typ), (name, type(m))
                assert m.label == label, (name, m.label)
                assert re.fullmatch(r"cerbos_tpu_[a-z0-9_]+", name), name
                assert m.help, f"metric {name!r} has no help text"
            # rendered exposition carries the label on every child series
            text = obs.metrics().render()
            for line in text.splitlines():
                if line.startswith("cerbos_tpu_ipc_client_rtt_seconds_bucket{"):
                    assert 'transport="' in line, line
        finally:
            client.close()
            server.close()
            batcher.close()
