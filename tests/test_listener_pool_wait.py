"""The wait for a listener thread (PR 38): the sync gRPC server's pool stamps
``submit`` and observes ``cerbos_tpu_listener_pool_wait_seconds`` as a worker
thread starts the call: once a call, before the handler's extent and not in
it; the aio server has no pool and observes nothing. Real listeners (the
test_request_parts harness)."""

import threading
import time

from cerbos_tpu import observability as obs
from cerbos_tpu.server import server as server_mod
from cerbos_tpu.server.server import _StampingPool

from test_request_parts import send_grpc, serve, tracker  # noqa: F401  (the fixture: the waterfall on, so the handler is observed)

WAIT = "cerbos_tpu_listener_pool_wait_seconds"
HANDLER = "cerbos_tpu_request_handler_seconds"


def hist(name):
    return obs.metrics().histogram(name)


def test_pool_wait_is_observed_once_a_call_and_is_not_in_the_handler(tmp_path, tracker):
    srv, closers = serve(tmp_path, "standalone")
    try:
        send_grpc(srv)  # the channel's first call: whatever gRPC does once, before the count
        waits, handled, handled_s = hist(WAIT).count, hist(HANDLER).count, hist(HANDLER).sum
        waited_s = hist(WAIT).sum
        for _ in range(7):
            send_grpc(srv)
        assert hist(WAIT).count - waits == 7
        assert hist(HANDLER).count - handled == 7
        # a wake-up and a wait for the interpreter lock: something, and far less than a stall
        assert 0 < hist(WAIT).sum - waited_s < 7 * 0.25
        assert hist(HANDLER).sum - handled_s > 0
    finally:
        for close in closers:
            close()


def test_the_aio_server_has_no_pool_and_observes_nothing(tmp_path, tracker):
    srv, closers = serve(tmp_path, "standalone", grpc_async=True)
    try:
        waits, handled = hist(WAIT).count, hist(HANDLER).count
        for _ in range(3):
            send_grpc(srv)
        assert hist(HANDLER).count - handled == 3
        assert hist(WAIT).count == waits
    finally:
        for close in closers:
            close()


def test_the_wait_is_from_submit_to_the_workers_start_and_the_call_keeps_its_result():
    pool = _StampingPool(1)
    try:
        before, before_s = hist(WAIT).count, hist(WAIT).sum
        blocker = pool.submit(time.sleep, 0.05)  # the one worker is busy: the next call waits for it
        fut = pool.submit(lambda a, b=0: a + b, 2, b=3)
        assert fut.result(timeout=10) == 5 and blocker.result(timeout=10) is None
        assert hist(WAIT).count - before == 2
        assert 0.04 <= hist(WAIT).sum - before_s < 5
    finally:
        pool.shutdown()


def test_the_sync_server_decodes_a_request_on_its_serving_thread_and_handles_it_on_a_worker(tmp_path, tracker, monkeypatch):
    """Under the sync server gRPC runs the request deserializer in its
    ``receive_message`` callback, on the one thread that serves the completion
    queue; the pool's worker, already started (``pool_wait``), waits on the
    call's condition for the decoded message: so the handler's extent starts
    on one thread and goes on on another, and its ``front_validate`` part holds
    a second thread's wake-up."""
    threads: dict[str, list[threading.Thread]] = {"decode": [], "handle": []}

    class Stamps(server_mod._IngressStamps):
        def put(self, key, t_raw, t_decoded):  # the stamping deserializer's
            threads["decode"].append(threading.current_thread())
            super().put(key, t_raw, t_decoded)

        def pop(self, key):  # the handler's first statement
            threads["handle"].append(threading.current_thread())
            return super().pop(key)

    monkeypatch.setattr(server_mod, "_GRPC_STAMPS", Stamps())
    srv, closers = serve(tmp_path, "standalone")
    try:
        for _ in range(5):
            send_grpc(srv)
    finally:
        for close in closers:
            close()
    assert len(threads["decode"]) == len(threads["handle"]) == 5
    assert len({t.ident for t in threads["decode"]}) == 1  # one serving thread
    assert not {t.ident for t in threads["decode"]} & {t.ident for t in threads["handle"]}
    assert all(t.name.startswith("ThreadPoolExecutor") for t in threads["handle"])  # the pool's workers
    assert not threads["decode"][0].name.startswith("ThreadPoolExecutor")
