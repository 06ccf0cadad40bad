"""Streaming serving path: the batcher drives submit/collect with several
device batches in flight, a direct check() takes the same route chunk by
chunk, and the pad+stack transfer staging round-trips to the plainly padded
arrays.
"""

import concurrent.futures
import dataclasses
import re
import time

import numpy as np
import pytest

from cerbos_tpu.compile import compile_policy_set
from cerbos_tpu.engine import CheckInput, EvalParams, Principal, Resource
from cerbos_tpu.engine.batcher import BatchingEvaluator
from cerbos_tpu.policy.parser import parse_policies
from cerbos_tpu.ruletable import build_rule_table, check_input
from cerbos_tpu.tpu import TpuEvaluator
from cerbos_tpu.tpu import evaluator as evmod
from flightgate import FlightGate

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


def inp(i: int) -> CheckInput:
    return CheckInput(
        principal=Principal(id=f"u{i}", roles=["user"]),
        resource=Resource(
            kind="album",
            id=f"a{i}",
            attr={"owner": f"u{i % 7}", "public": i % 3 == 0},
        ),
        actions=["view"],
    )


def effects(outs):
    return [{a: (e.effect, e.policy) for a, e in o.actions.items()} for o in outs]


def sans_source(outs):
    """Whole outputs, less the label that says who answered (device or oracle)."""
    return [
        dataclasses.replace(o, actions={a: dataclasses.replace(e, source="") for a, e in o.actions.items()})
        for o in outs
    ]


class TestStreamingBatcher:
    def test_concurrent_requests_keep_batches_in_flight(self):
        """The acceptance check: concurrent CheckResources through the
        batcher reach the device via submit/collect with >= 2 batches in
        flight, and every output is bit-exact vs the CPU oracle."""
        rt = table()
        # min_device_batch=1: a request under the threshold that finds the queue
        # empty is answered on its own thread (PR 30); these are to queue
        ev = TpuEvaluator(rt, use_jax=True, min_device_batch=1)
        # max_batch=16 forces 64 requests to drain as 4+ tickets; the whole
        # burst queues behind a first flight that the gate holds, so the
        # submit loop demonstrably stacks tickets instead of racing the clients
        gate = FlightGate(ev)
        batcher = BatchingEvaluator(gate, max_batch=16, max_inflight=3)
        inputs = [inp(i) for i in range(64)]
        try:
            plug = gate.hold(batcher, [inp(1000)])
            with concurrent.futures.ThreadPoolExecutor(max_workers=64) as pool:
                futs = [pool.submit(batcher.check, [i]) for i in inputs]
                gate.release(batcher, queued=64)
                results = [f.result(timeout=60)[0] for f in futs]
            assert len(plug.result(timeout=60)) == 1
        finally:
            batcher.close()

        want = [check_input(rt, i, EvalParams()) for i in inputs]
        assert effects(results) == effects(want)
        assert batcher.stats["batches"] == 1 + 4  # the plug's, then 64 requests in flights of max_batch
        assert batcher.stats["batched_requests"] == 1 + 64
        assert batcher.stats["inflight_peak"] >= 2, batcher.stats
        assert ev.stats["device_inputs"] > 0  # the device path actually ran

    def test_sync_evaluator_fallback(self):
        """Evaluators without a streaming API still work through the batcher
        (ready tickets, no in-flight window)."""
        rt = table()

        class PlainEvaluator:
            rule_table = rt
            schema_mgr = None

            def check(self, inputs, params=None):
                return [check_input(rt, i, params or EvalParams()) for i in inputs]

        batcher = BatchingEvaluator(PlainEvaluator(), max_wait_ms=1.0)
        inputs = [inp(i) for i in range(8)]
        try:
            with concurrent.futures.ThreadPoolExecutor(max_workers=8) as pool:
                results = list(pool.map(lambda i: batcher.check([i])[0], inputs))
        finally:
            batcher.close()
        assert effects(results) == effects([check_input(rt, i, EvalParams()) for i in inputs])

    def test_timeout_serves_from_oracle(self):
        """A wedged device falls back to the CPU oracle per request, and the
        fallback is counted (it used to be invisible)."""
        rt = table()

        class WedgedEvaluator:
            rule_table = rt
            schema_mgr = None

            def check(self, inputs, params=None):
                time.sleep(0.5)
                return [check_input(rt, i, params or EvalParams()) for i in inputs]

        from cerbos_tpu.observability import metrics

        before = metrics().counter("cerbos_tpu_batcher_oracle_fallbacks_total").value
        batcher = BatchingEvaluator(WedgedEvaluator(), max_wait_ms=1.0, request_timeout_s=0.05)
        try:
            out = batcher.check([inp(0)])
        finally:
            batcher.close()
        assert effects(out) == effects([check_input(rt, inp(0), EvalParams())])
        assert batcher.stats["oracle_fallbacks"] == 1
        assert metrics().counter("cerbos_tpu_batcher_oracle_fallbacks_total").value == before + 1


def nokind(i: int) -> CheckInput:
    """An input no policy covers: trivial to the packer, no candidate row."""
    return dataclasses.replace(inp(i), resource=Resource(kind="nokind", id=f"n{i}", attr={}))


class TestOneRoute:
    @pytest.mark.parametrize("n, cuts", [(63, [32, 31]), (64, [32, 32]), (65, [32, 33]), (130, [32, 32, 32, 34])])
    def test_direct_check_cut_into_chunks_is_bit_exact(self, n, cuts):
        """A direct check() over pipeline_chunk is dispatched chunk by chunk
        and collected in order: element for element the oracle's answer."""
        rt = table()
        ev = TpuEvaluator(rt, use_jax=True, min_device_batch=4, pipeline_chunk=32)
        inputs = [inp(i) for i in range(n)]
        params = EvalParams()
        assert [len(c) for c in ev._chunk_inputs(inputs)] == cuts
        got = ev.check(inputs, params)
        want = [check_input(rt, i, params) for i in inputs]
        assert sans_source(got) == sans_source(want)
        assert ev.stats["device_inputs"] == n

    @pytest.mark.parametrize(
        "n, chunk, layouts", [(40, 4096, ["B64xBA64"]), (100, 32, ["B32xBA32", "B32xBA32", "B64xBA64"])]
    )
    def test_check_is_submit_and_collect(self, n, chunk, layouts):
        """check() and collect(submit()) are one route: the same outputs, the
        oracle's but for ``source``, and the same jit-cache entries (whichever
        comes second compiles nothing)."""
        from cerbos_tpu.tpu import compilestats

        rt = table()
        ev = TpuEvaluator(rt, use_jax=True, pipeline_chunk=chunk)
        inputs = [inp(i) for i in range(n)]
        params = EvalParams()

        def counts():
            snap = compilestats.stats().snapshot()
            return snap["cache_hits"], snap["cache_misses"]

        h0, m0 = counts()
        direct = ev.check(inputs, params)
        h1, m1 = counts()
        distinct = len(set(layouts))
        assert (h1 - h0, m1 - m0) == (len(layouts) - distinct, distinct)
        ticket = ev.submit(inputs, params)
        assert ticket.layout_key == "+".join(layouts)
        streamed = ev.collect(ticket)
        h2, m2 = counts()
        assert (h2 - h1, m2 - m1) == (len(layouts), 0)
        assert streamed == direct
        assert sans_source(direct) == sans_source([check_input(rt, i, params) for i in inputs])
        assert len([k for k in ev._jit_cache if k != ("_variant_budget",)]) == distinct

    @pytest.mark.parametrize("backend", ["jax", "numpy"])
    def test_a_batch_with_no_candidate_row_is_served(self, backend):
        """Twenty inputs no policy covers make a packed batch the device is
        never asked about: submit() books no layout for it and both doors
        answer what the oracle answers."""
        rt = table()
        ev = TpuEvaluator(rt, use_jax=backend == "jax")
        inputs = [nokind(i) for i in range(20)]
        params = EvalParams()
        ticket = ev.submit(inputs, params)
        assert (ticket.layout_key, ticket.padded_rows, ticket.occupancy) == (None, None, None)
        want = sans_source([check_input(rt, i, params) for i in inputs])
        assert sans_source(ev.collect(ticket)) == want
        assert sans_source(ev.check(inputs + [inp(0)], params)) == want + sans_source([check_input(rt, inp(0), params)])

    @pytest.mark.parametrize(
        "n, want",
        [(n, [n]) for n in (16, 31, 32, 33, 47, 48, 50, 100, 1024, 4096)]
        + [
            (4097, [4097]),  # a tail of 1 rides with its neighbour
            (4096 + 16, [4096, 16]),
            (3 * 4096 + 5, [4096, 4096, 4096 + 5]),
        ],
    )
    def test_a_batch_that_fits_one_pipeline_chunk_is_one_chunk(self, n, want):
        """The flight's length alone decides: one chunk up to pipeline_chunk,
        pipeline_chunk-sized slices beyond it."""
        ev = TpuEvaluator(table(), use_jax=False)  # the defaults a server boots with
        assert (ev.pipeline_chunk, ev.min_device_batch) == (4096, 16)
        inputs = list(range(n))  # _chunk_inputs only slices
        chunks = ev._chunk_inputs(inputs)
        assert [len(c) for c in chunks] == want
        assert [i for c in chunks for i in c] == inputs

    @pytest.mark.parametrize("n, old_cuts", [(34, (16, 18)), (48, (16, 16, 16))])
    def test_one_chunk_flight_returns_what_the_split_flight_returned(self, n, old_cuts):
        """submit/collect of a page that used to be cut in two or three is,
        element for element, the oracle's answer and what evaluating the
        parent's chunks one by one gives."""
        rt = table()
        ev = TpuEvaluator(rt, use_jax=True)
        inputs = [inp(i) for i in range(n)]
        params = EvalParams()
        ticket = ev.submit(inputs, params)
        assert len(ticket.parts) == 1
        got = ev.collect(ticket)
        bounds = np.cumsum((0,) + old_cuts)
        old_chunks = [inputs[a:b] for a, b in zip(bounds, bounds[1:])]
        assert sum(len(ch) for ch in old_chunks) == n
        assert got == [out for ch in old_chunks for out in ev.check(ch, params)]
        want = [check_input(rt, i, params) for i in inputs]
        assert sans_source(got) == sans_source(want)  # all but who answered


def device_calls(shard: int) -> tuple[int, float]:
    """(count, sum) of ``cerbos_tpu_batch_device_calls`` for the shard."""
    from cerbos_tpu.observability import metrics

    h = metrics().histogram_vec("cerbos_tpu_batch_device_calls", label="shard").labels(str(shard))
    return h.count, h.sum


class TestDeviceCallsHistogram:
    @pytest.mark.parametrize("n, observed", [(3, 0), (15, 0), (16, 1), (34, 1), (48, 1), (100, 1), (4096, 1)])
    def test_observed_once_per_device_served_flight(self, n, observed):
        """One observation of value 1 for a flight the device serves, up to a
        whole pipeline_chunk; none for one under min_device_batch (the oracle
        answers it)."""
        rt = table()
        shard = 7000 + n  # a label of this case's own: its series start at zero
        batcher = BatchingEvaluator(TpuEvaluator(rt, use_jax=True), shard_id=shard)
        inputs = [inp(i) for i in range(n)]
        try:
            got = batcher.check(inputs)
        finally:
            batcher.close()
        assert effects(got) == effects([check_input(rt, i, EvalParams()) for i in inputs])
        assert device_calls(shard) == (observed, float(observed))


class TestFusedPadStack:
    def _packed(self, n=10):
        rt = table()
        ev = TpuEvaluator(rt, use_jax=False, min_device_batch=0)
        return ev.packer.pack([inp(i) for i in range(n)], EvalParams())

    def _round_trip(self, batch):
        """_unstack_padded of what _pad_stack wrote, beside _pad_arrays of the
        same batch: the format's writer and reader against the plain padding,
        field by field, whatever the bytes in between look like."""
        B_pad = evmod._next_bucket(batch.scope_sp.shape[0])
        BA_pad = evmod._next_bucket(batch.cand_cond.shape[0])
        args = (batch, batch.columns, batch.cand_cond, batch.cand_drcond, B_pad, BA_pad)
        want = evmod._pad_arrays(*args)
        stacked, layout, leased = evmod._pad_stack(*args)
        got = evmod._unstack_padded(np, layout, stacked)
        assert set(got) == set(want)
        for name, w in want.items():
            g = got[name]
            if isinstance(w, dict):
                assert set(g) == set(w), name
                for k in w:
                    assert np.array_equal(g[k], w[k]), (name, k)
            elif w is None:
                assert g is None, name
            else:
                assert np.array_equal(g, w), name
        return leased

    def test_unstack_gives_back_what_pad_arrays_gives(self):
        evmod._buffer_pool.release(self._round_trip(self._packed()))

    def test_dirty_pool_buffers_are_fully_overwritten(self, monkeypatch):
        """Recycled buffers carry garbage; a second pass over the same shapes
        must still round-trip to the freshly padded arrays."""
        pool = evmod._BufferPool()  # this test's own, so the second pass leases what the first returned
        monkeypatch.setattr(evmod, "_buffer_pool", pool)
        batch = self._packed()
        leased = self._round_trip(batch)
        for a in leased:
            a.fill(-1 if a.dtype != np.bool_ else True)  # poison
        pool.release(leased)
        assert {id(a) for a in self._round_trip(batch)} == {id(a) for a in leased}

    def test_buffer_pool_recycles(self):
        pool = evmod._BufferPool()
        a = pool.lease((4, 8), np.int32)
        pool.release([a])
        b = pool.lease((4, 8), np.int32)
        assert b is a
        c = pool.lease((4, 8), np.int32)
        assert c is not a
        pool.release([b, c])

    def test_layout_marshalling_memoized(self):
        batch = self._packed()
        cols = batch.columns
        lay1 = evmod._marshal_layout(cols, batch.scope_sp.shape[2], cols.now_hi is not None)
        lay2 = evmod._marshal_layout(cols, batch.scope_sp.shape[2], cols.now_hi is not None)
        assert lay1 is lay2

    def test_native_stack_pad_rows(self):
        from cerbos_tpu import native as native_mod

        native = native_mod.get()
        if native is None or not hasattr(native, "stack_pad_rows"):
            pytest.skip("native extension unavailable")
        dst = np.full((3, 8), 7, dtype=np.int32)
        rows = [
            np.arange(5, dtype=np.int32),
            np.arange(8, dtype=np.int32),
            np.zeros(0, dtype=np.int32),
        ]
        native.stack_pad_rows(dst, rows)
        assert dst[0].tolist() == [0, 1, 2, 3, 4, 0, 0, 0]
        assert dst[1].tolist() == list(range(8))
        assert dst[2].tolist() == [0] * 8
        with pytest.raises(ValueError):
            native.stack_pad_rows(np.zeros((1, 2), np.int32), [np.arange(5, dtype=np.int32)])


class TestMetricsEndpoint:
    def test_batcher_metrics_visible(self, tmp_path_factory):
        """The satellite check: batcher counters reach /_cerbos/metrics."""
        import json
        import urllib.request

        from cerbos_tpu.bootstrap import initialize
        from cerbos_tpu.config import Config
        from cerbos_tpu.server.server import Server, ServerConfig

        policy_dir = tmp_path_factory.mktemp("metrics-policies")
        (policy_dir / "album.yaml").write_text(POLICY)
        config = Config.load(overrides=[f"storage.disk.directory={policy_dir}"])
        core = initialize(config)
        core.tpu_evaluator.use_jax = False  # keep the test jax-independent
        srv = Server(
            core.service,
            ServerConfig(http_listen_addr="127.0.0.1:0", grpc_listen_addr="127.0.0.1:0"),
        )
        srv.start()
        try:
            body = {
                "requestId": "m-1",
                "principal": {"id": "alice", "roles": ["user"]},
                "resources": [
                    {"actions": ["view"], "resource": {"kind": "album", "id": "a1", "attr": {"owner": "alice"}}}
                ],
            }
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.http_port}/api/check/resources",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"},
                method="POST",
            )
            with urllib.request.urlopen(req) as resp:
                assert json.loads(resp.read())["results"]
            with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.http_port}/_cerbos/metrics"
            ) as resp:
                text = resp.read().decode()
        finally:
            srv.stop()
            core.close()

        m = re.search(r"^cerbos_tpu_batcher_batches_total (\d+)", text, re.M)
        assert m and int(m.group(1)) >= 1, text
        assert "cerbos_tpu_batcher_batch_size_bucket" in text
        assert "cerbos_tpu_batcher_queue_wait_seconds_bucket" in text
        assert "cerbos_tpu_batcher_inflight" in text
        # device-path fault domain metrics (docs/ROBUSTNESS.md)
        assert "cerbos_tpu_breaker_state" in text
        assert "cerbos_tpu_breaker_trips_total" in text
        assert "cerbos_tpu_batcher_deadline_drops_total" in text
        assert "cerbos_tpu_batcher_quarantined_total" in text
