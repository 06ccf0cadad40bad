"""The zero-copy front door (PR 10): shm frame rings + native codec.

Three layers, bottom up: the native ring/codec kernels in isolation, the
transport negotiation ladder (shm granted only when both ends can run it,
uds otherwise — never a failed boot), and the full degradation story on the
shm data plane: ring-full backpressure onto the oracle, wedged-ring
swallowing, batcher death mid-flight with zero lost requests, and reattach
re-granting shm after the batcher returns.

Every test here must ALSO pass with ``CERBOS_TPU_NO_NATIVE=1`` (the suite
skips what can't run and proves the uds fallback for the rest) — CI runs
both legs.
"""

import json
import os
import threading
import time

import pytest

from cerbos_tpu import native
from cerbos_tpu.compile import compile_policy_set
from cerbos_tpu.engine import CheckInput, EvalParams, Principal, Resource
from cerbos_tpu.engine.batcher import BatchingEvaluator
from cerbos_tpu.engine.ipc import (
    BatcherIpcServer,
    RemoteBatcherClient,
    _ShmSegment,
    decode_inputs,
    encode_inputs,
)
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

needs_native = pytest.mark.skipif(
    native.get() is None, reason="native module unavailable (CERBOS_TPU_NO_NATIVE?)"
)


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
    server_transport="shm",
    client_transport="shm",
    submit_delay_s=0.0,
    faults=None,
    request_timeout_s=30.0,
    ring_kib=1024,
    max_outstanding=4096,
):
    batcher = BatchingEvaluator(
        OracleEvaluator(rt, submit_delay_s=submit_delay_s), max_wait_ms=1.0
    )
    server = BatcherIpcServer(
        str(tmp_path / "batcher.sock"),
        batcher,
        max_outstanding=max_outstanding,
        faults=faults,
        transport=server_transport,
    )
    server.start()
    client = RemoteBatcherClient(
        server.socket_path,
        rt,
        request_timeout_s=request_timeout_s,
        worker_label="fe-shm-test",
        status_poll_s=0.05,
        connect_retry_s=0.05,
        transport=client_transport,
        ring_kib=ring_kib,
    )
    assert wait_for(client._connected.is_set)
    return batcher, server, client


def close_pair(batcher, server, client):
    client.close()
    server.close()
    batcher.close()


# -- native ring kernels -----------------------------------------------------


@needs_native
class TestRing:
    RING = 1 << 16

    def _ring(self):
        buf = bytearray(256 + self.RING)
        native.get().ring_init(memoryview(buf))
        return memoryview(buf)

    def test_push_pop_fifo_with_wraparound(self):
        nat = native.get()
        mv = self._ring()
        # payloads sized so the ring wraps many times over the run
        for i in range(2000):
            payload = bytes([i & 0xFF]) * (100 + (i % 700))
            assert nat.ring_push(mv, 3, i, payload)
            got = nat.ring_pop(mv)
            assert got == (3, i, payload)
        assert nat.ring_pop(mv) is None
        used, cap, pushed, popped, full = nat.ring_stats(mv)
        assert used == 0 and cap == self.RING
        assert pushed == popped == 2000

    def test_interleaved_backlog_preserves_order(self):
        nat = native.get()
        mv = self._ring()
        for i in range(50):
            assert nat.ring_push(mv, 7, i, b"x" * i)
        for i in range(50):
            assert nat.ring_pop(mv) == (7, i, b"x" * i)

    def test_full_ring_refuses_and_counts(self):
        nat = native.get()
        mv = self._ring()
        n = 0
        while nat.ring_push(mv, 1, n, b"y" * 1000):
            n += 1
        assert 0 < n < 70  # 64KiB ring, ~1KiB records
        assert not nat.ring_push(mv, 1, n, b"y" * 1000)
        *_, full_events = nat.ring_stats(mv)
        assert full_events >= 2
        # draining one record frees space for exactly one more
        assert nat.ring_pop(mv) is not None
        assert nat.ring_push(mv, 1, n, b"y" * 1000)

    def test_oversized_frame_raises(self):
        nat = native.get()
        mv = self._ring()
        with pytest.raises(ValueError):
            nat.ring_push(mv, 1, 0, b"z" * (self.RING + 16))

    def test_wait_times_out_then_wakes_cross_thread(self):
        nat = native.get()
        mv = self._ring()
        seq = nat.ring_seq(mv, 0)
        t0 = time.monotonic()
        nat.ring_wait(mv, 0, seq, 80)
        assert time.monotonic() - t0 >= 0.05  # actually blocked

        woke = threading.Event()

        def waiter():
            s = nat.ring_seq(mv, 0)
            nat.ring_wait(mv, 0, s, 5000)
            woke.set()

        t = threading.Thread(target=waiter, daemon=True)
        t.start()
        time.sleep(0.05)
        nat.ring_push(mv, 1, 0, b"ping")
        assert woke.wait(2.0), "push did not wake the futex waiter"
        t.join(timeout=2.0)


# -- native frame codec ------------------------------------------------------


@needs_native
class TestFrameCodec:
    def test_ticket_roundtrip_matches_marshal_codec(self, rt):
        import cerbos_tpu.engine.types as T

        nat = native.get()
        inputs = [
            inp(i, note="café \U0001f680", nested={"a": [1, 2.5, None, True]})
            for i in range(9)
        ]
        frame = nat.ticket_pack(inputs, 1.25, "00-ab-cd-01", (0.002, [["stage", 0.001]]))
        deadline_rel, traceparent, decoded, carry = nat.ticket_unpack(
            frame, T.Principal, T.Resource, T.AuxData, T.CheckInput
        )
        assert deadline_rel == 1.25
        assert traceparent == "00-ab-cd-01"
        # containers decode as lists (the carry spec is shape-compatible)
        assert carry == [0.002, [["stage", 0.001]]]
        # decision parity against the marshal codec path AND the originals
        legacy = decode_inputs(encode_inputs(inputs))
        assert effects(oracle(rt, decoded)) == effects(oracle(rt, legacy))
        assert [d.request_id for d in decoded] == [i.request_id for i in inputs]
        assert decoded[3].resource.attr["note"] == "café \U0001f680"
        assert decoded[3].resource.attr["nested"] == {"a": [1, 2.5, None, True]}

    def test_ticket_none_deadline_and_carry(self, rt):
        import cerbos_tpu.engine.types as T

        nat = native.get()
        frame = nat.ticket_pack([inp(0)], None, None, None)
        deadline_rel, traceparent, decoded, carry = nat.ticket_unpack(
            frame, T.Principal, T.Resource, T.AuxData, T.CheckInput
        )
        assert deadline_rel is None and traceparent is None and carry is None
        assert len(decoded) == 1

    def test_reply_roundtrip(self, rt):
        import cerbos_tpu.engine.types as T

        nat = native.get()
        outs = oracle(rt, [inp(i) for i in range(9)])
        spec = (0.004, [["device_submit", 0.003]], "device", None, 2)
        frame = nat.reply_pack(outs, spec)
        decoded, got_spec = nat.reply_unpack(
            frame, T.CheckOutput, T.ActionEffect, T.ValidationError, T.OutputEntry
        )
        assert effects(decoded) == effects(outs)
        assert [d.resource_id for d in decoded] == [o.resource_id for o in outs]
        assert got_spec == [0.004, [["device_submit", 0.003]], "device", None, 2] or tuple(
            got_spec
        ) == spec

    def test_truncated_frames_raise_not_crash(self, rt):
        import cerbos_tpu.engine.types as T

        nat = native.get()
        frame = nat.ticket_pack([inp(i) for i in range(3)], 1.0, None, None)
        for cut in (0, 1, 5, len(frame) // 2, len(frame) - 1):
            with pytest.raises(ValueError):
                nat.ticket_unpack(
                    frame[:cut], T.Principal, T.Resource, T.AuxData, T.CheckInput
                )


# -- negotiation ladder ------------------------------------------------------


class TestNegotiation:
    def test_shm_granted_when_both_sides_native(self, tmp_path, rt):
        if native.get() is None:
            pytest.skip("native module unavailable")
        batcher, server, client = make_pair(tmp_path, rt)
        try:
            assert client.transport == "shm"
            assert server.stats["shm_conns"] == 1
            # the segment name is unlinked right after the grant: a SIGKILL
            # on either side cannot leak segments into /dev/shm
            assert client._shm is not None
            assert not os.path.exists(client._shm.path)
        finally:
            close_pair(batcher, server, client)

    def test_server_forced_uds_downgrades_shm_client(self, tmp_path, rt):
        batcher, server, client = make_pair(tmp_path, rt, server_transport="uds")
        try:
            assert client.transport == "uds"
            inputs = [inp(i) for i in range(8)]
            assert effects(client.check(inputs)) == effects(oracle(rt, inputs))
            assert client.stats["oracle_fallbacks"] == 0
        finally:
            close_pair(batcher, server, client)

    def test_client_forced_uds_never_offers_shm(self, tmp_path, rt):
        batcher, server, client = make_pair(tmp_path, rt, client_transport="uds")
        try:
            assert client.transport == "uds"
            assert server.stats["shm_conns"] == 0
            inputs = [inp(i) for i in range(8)]
            assert effects(client.check(inputs)) == effects(oracle(rt, inputs))
        finally:
            close_pair(batcher, server, client)

    def test_missing_native_module_falls_back_to_uds(self, tmp_path, rt, monkeypatch):
        """A front end without the built .so (heterogeneous fleet) keeps
        working: the HELLO never offers shm and traffic rides the socket."""
        import cerbos_tpu.engine.ipc as ipc_mod

        monkeypatch.setattr(ipc_mod.native, "get", lambda: None)
        batcher, server, client = make_pair(tmp_path, rt)
        try:
            assert client.transport == "uds"
            inputs = [inp(i) for i in range(8)]
            assert effects(client.check(inputs)) == effects(oracle(rt, inputs))
        finally:
            close_pair(batcher, server, client)

    def test_segment_layout_validation_rejects_garbage(self, tmp_path):
        p = tmp_path / "bogus.shm"
        p.write_bytes(b"\x00" * 8192)
        with pytest.raises(Exception):
            _ShmSegment.attach(str(p))


# -- shm data plane ----------------------------------------------------------


@needs_native
class TestShmDataPlane:
    def test_decision_parity_and_stats(self, tmp_path, rt):
        batcher, server, client = make_pair(tmp_path, rt)
        try:
            assert client.transport == "shm"
            inputs = [inp(i) for i in range(64)]
            remote = client.check(inputs)
            assert effects(remote) == effects(batcher.check(inputs))
            assert effects(remote) == effects(oracle(rt, inputs))
            assert client.stats["oracle_fallbacks"] == 0
            ts = client.transport_stats()
            assert ts["transport"] == "shm"
            assert ts["frames_out"] >= 1 and ts["frames_in"] >= 1
            assert ts["encode_ns_per_frame"] > 0 and ts["decode_ns_per_frame"] > 0
            assert json.dumps(ts)  # /_cerbos/debug/transport serves this verbatim
        finally:
            close_pair(batcher, server, client)

    def test_check_await_parity_on_shm(self, tmp_path, rt):
        import asyncio

        batcher, server, client = make_pair(tmp_path, rt)
        try:
            assert client.transport == "shm"

            async def go():
                return await client.check_await([inp(i) for i in range(16)])

            remote = asyncio.run(go())
            assert effects(remote) == effects(oracle(rt, [inp(i) for i in range(16)]))
        finally:
            close_pair(batcher, server, client)

    def test_concurrent_frontend_threads_multiplex_one_ring(self, tmp_path, rt):
        """Many request threads share one client (the aiohttp process model):
        the GIL serializes ring pushes and req_ids demultiplex settles."""
        batcher, server, client = make_pair(tmp_path, rt)
        results = {}
        try:
            assert client.transport == "shm"

            def worker(tid):
                inputs = [inp(tid * 100 + j) for j in range(10)]
                results[tid] = (effects(client.check(inputs)), effects(oracle(rt, inputs)))

            threads = [threading.Thread(target=worker, args=(t,)) for t in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30.0)
            assert len(results) == 8
            for got, want in results.values():
                assert got == want
        finally:
            close_pair(batcher, server, client)

    def test_oversized_ticket_sheds_to_oracle_as_ipc_full(self, tmp_path, rt):
        """A frame that cannot ever fit the ring is a backpressure event,
        not an error: the front end serves its oracle and counts it."""
        batcher, server, client = make_pair(tmp_path, rt, ring_kib=64)
        try:
            assert client.transport == "shm"
            big = [inp(i, blob="x" * 4096) for i in range(40)]  # >64KiB packed
            outs = client.check(big)
            assert effects(outs) == effects(oracle(rt, big))
            assert client.stats["ring_full"] >= 1
            assert client.stats["oracle_fallbacks"] >= 1
            assert client.m_fallbacks.get("ipc_full") >= 1
        finally:
            close_pair(batcher, server, client)

    def test_wedged_ring_swallows_tickets_then_oracle(self, tmp_path, rt):
        """engine/faults.py ipc_wedge_after generalized to the shm plane:
        past the threshold the batcher swallows tickets off the ring, the
        front end times out, and the request settles from the oracle."""
        batcher, server, client = make_pair(
            tmp_path, rt, faults={"ipc_wedge_after": 2}, request_timeout_s=0.5
        )
        try:
            assert client.transport == "shm"
            for i in range(3):
                assert effects(client.check([inp(i)])) == effects(oracle(rt, [inp(i)]))
            # past the wedge threshold: swallowed off the ring, oracle serves
            out = client.check([inp(99)])
            assert effects(out) == effects(oracle(rt, [inp(99)]))
            assert server.stats["wedged_drops"] >= 1
            assert client.m_fallbacks.get("ipc_timeout") >= 1
        finally:
            close_pair(batcher, server, client)

    def test_batcher_death_midflight_loses_zero_requests(self, tmp_path, rt):
        """The chaos pin on the shm plane: the batcher dies with tickets on
        the ring. Liveness rides the SOCKET (the shm mapping would survive a
        dead peer silently), so the close fails pending futures immediately
        and every request settles from the COW oracle."""
        batcher, server, client = make_pair(tmp_path, rt, submit_delay_s=0.3)
        results = []
        try:
            assert client.transport == "shm"

            def worker(i):
                results.append(effects(client.check([inp(i)])))

            threads = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
            for t in threads:
                t.start()
            time.sleep(0.1)  # tickets in flight on the ring
            server.close()
            batcher.close()
            for t in threads:
                t.join(timeout=15.0)
            assert len(results) == 6, "requests were lost on batcher death"
            for i, eff in enumerate(results):
                assert eff  # settled with a real decision, not an exception
            assert client.stats["oracle_fallbacks"] >= 1
            assert client.transport == "none"
        finally:
            client.close()

    def test_reattach_regrants_shm_after_batcher_returns(self, tmp_path, rt):
        """detach -> oracle -> reattach: a respawned batcher on the same
        socket re-runs the HELLO negotiation and the data plane comes back
        as shm, with a fresh segment (the old one died with the peer)."""
        batcher, server, client = make_pair(tmp_path, rt)
        sock_path = server.socket_path
        try:
            assert client.transport == "shm"
            first_seg = client._shm
            server.close()
            batcher.close()
            assert wait_for(lambda: not client._connected.is_set())
            # down: the oracle serves
            assert effects(client.check([inp(1)])) == effects(oracle(rt, [inp(1)]))
            assert client.transport == "none"
            # respawn on the same path
            batcher2 = BatchingEvaluator(OracleEvaluator(rt), max_wait_ms=1.0)
            server2 = BatcherIpcServer(sock_path, batcher2, transport="shm")
            server2.start()
            try:
                assert wait_for(client._connected.is_set)
                assert client.transport == "shm"
                assert client._shm is not first_seg
                inputs = [inp(i) for i in range(8)]
                assert effects(client.check(inputs)) == effects(oracle(rt, inputs))
            finally:
                client.close()
                server2.close()
                batcher2.close()
        finally:
            client.close()
