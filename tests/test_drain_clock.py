"""The batcher drain thread's own clock (engine/drainclock.py) and what reads it.

One cursor on one thread: the states tile the thread's life; a flight's new
stages (stack, dispatch, oracle, fetch, assemble, post) are observed once per
flight and lie inside the stages they split (submit, collect); ``pack`` and
``dispatch`` are tiled by their parts on a second cursor (PR 38), and a
device-served call's bytes are what it stacked and what it fetched; the window
wait is per flight; a compile is blamed on the first new dimension of its jit
key; the profiler runs with the Python tracer off and the program's regions
land on its trace with the clock readings at both ends. CPU backend, no
native extension needed.
"""

import json
import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest
from flightgate import EchoPlanner

from cerbos_tpu import observability as obs
from cerbos_tpu.engine import drainclock as dc
from cerbos_tpu.engine import flight
from cerbos_tpu.engine import types as T
from cerbos_tpu.engine.batcher import BatchingEvaluator
from cerbos_tpu.tpu import compilestats, profiler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.lib import prom, spec, trace_reduce  # noqa: E402

THREAD = "cerbos_tpu_batcher_thread_seconds_total"
STAGE = "cerbos_tpu_batch_stage_seconds"
WINDOW = "cerbos_tpu_batcher_window_wait_seconds"
NOVEL = "cerbos_tpu_xla_compile_novel_total"
NEW_STAGES = ("stack", "dispatch", "oracle", "fetch", "assemble", "post")
PACK_PARTS, DISPATCH_PARTS, ASSEMBLE_PARTS = dc.PARTS[dc.PACK], dc.PARTS[dc.DISPATCH], dc.PARTS[dc.ASSEMBLE]
TRANSFER = "cerbos_tpu_batch_transfer_bytes"
ROUNDING = 0.5001e-6  # a flight record's timings are rounded to a microsecond each


def spin(seconds: float) -> None:
    """Hold the CPU (a sleep would book wall time and no CPU time)."""
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


class Ticket:
    def __init__(self, inputs):
        self.inputs = inputs
        self.occupancy = 1.0
        self.padded_rows = None
        self.layout_key = "B16xBA16"


class FakeStreamingEvaluator:
    """``submit``/``collect`` that walk the clock's states the way
    ``TpuEvaluator`` does, reaching it through the thread-local alone."""

    rule_table = None
    schema_mgr = None

    def __init__(self, gate: threading.Event | None = None, validates: bool = False):
        self.gate = gate  # the first submit waits for it, holding the drain thread
        self.validates = validates  # as with schema.enforcement warn: assemble leaves its outputs part for the schema part

    def submit(self, inputs, params=None):
        if self.gate is not None:
            self.gate.wait(timeout=10)
            self.gate = None
        t = Ticket(inputs)
        dc.to(dc.PACK, dc.PACK_PLAN)
        spin(0.00015)
        for part in PACK_PARTS[1:]:
            dc.part(part)
            spin(0.00005)
        dc.to(dc.STACK)
        spin(0.0003)
        dc.to(dc.DISPATCH, dc.DISPATCH_CALL)
        spin(0.00015)
        dc.part(dc.DISPATCH_COPY)
        spin(0.00005)
        return t

    def collect(self, ticket):
        dc.to(dc.FETCH)
        time.sleep(0.001)
        dc.to(dc.ASSEMBLE, dc.ASSEMBLE_OUTPUTS)
        spin(0.0001)
        if self.validates:
            dc.part(dc.ASSEMBLE_SCHEMA)
            spin(0.0002)
            dc.part(dc.ASSEMBLE_OUTPUTS)
        spin(0.0002)
        return [T.CheckOutput(request_id="", resource_id=str(k)) for k in range(len(ticket.inputs))]


class SyncEvaluator:
    """No streaming API: the batcher calls ``check`` on the drain thread."""

    rule_table = None
    schema_mgr = None

    def check(self, inputs, params=None):
        spin(0.0005)
        return [T.CheckOutput(request_id="", resource_id=str(k)) for k in range(len(inputs))]


def scrape() -> dict:
    return prom.parse(obs.metrics().render())


def fly(batcher: BatchingEvaluator, flights: int, inputs: int = 3) -> None:
    for _ in range(flights):
        assert len(batcher.check([object()] * inputs)) == inputs


@pytest.fixture()
def shard(request):
    """A shard label of the test's own: its series start at zero."""
    return 900 + abs(hash(request.node.name)) % 9000


def test_states_tile_the_threads_life_and_cpu_is_the_work_it_held_the_cpu_for(shard):
    before = scrape()
    t0 = time.perf_counter()
    b = BatchingEvaluator(FakeStreamingEvaluator(), max_wait_ms=1.0, shard_id=shard)
    b.plan_planner = EchoPlanner()
    try:
        fly(b, 50)
        assert b.plan(["q"]) == ["plan:q"]  # a lone plan query waits out the window; a check never enters it
    finally:
        b.close()  # joins the drain thread
    lifetime = time.perf_counter() - t0
    assert not b._thread.is_alive()
    d = prom.delta(before, scrape())
    walls = {s: prom.total(d, THREAD, state=s, clock="wall", shard=str(shard)) for s in dc.STATES}
    assert sum(walls.values()) == pytest.approx(lifetime, rel=0.02, abs=0.005)  # the thread takes a few ms to start
    assert prom.total(d, THREAD, clock="wall", shard=str(shard)) == pytest.approx(sum(walls.values()))
    # every state the fake walks was booked, waits as waits and work as work
    for s in (dc.WINDOW, dc.PACK, dc.STACK, dc.DISPATCH, dc.FETCH, dc.ASSEMBLE, dc.SETTLE, dc.POST, dc.OTHER):
        assert walls[s] > 0, s
    assert prom.total(d, THREAD, kind="wait", clock="wall", shard=str(shard)) == pytest.approx(
        walls[dc.IDLE] + walls[dc.WINDOW] + walls[dc.FETCH]
    )
    # the CPU clock is the thread's, not split by state, and all of it is work: the fake spins for
    # 1.2 ms a flight and sleeps through its fetch, so it lies between the spinning and the working wall time
    cpu = prom.total(d, THREAD, clock="cpu", shard=str(shard))
    assert cpu == prom.total(d, THREAD, state=dc.ALL, kind="work", clock="cpu", shard=str(shard))
    work = prom.total(d, THREAD, kind="work", clock="wall", shard=str(shard))
    assert 50 * 0.0012 * 0.8 <= cpu <= work + 0.011  # the coarsest CPU clock met ticks in 10 ms
    assert cpu < walls[dc.FETCH] + walls[dc.IDLE] + walls[dc.WINDOW] + work


def test_each_new_stage_is_observed_once_per_flight_inside_the_stage_it_splits(shard):
    before = scrape()
    b = BatchingEvaluator(FakeStreamingEvaluator(), max_wait_ms=0.5, shard_id=shard)
    try:
        fly(b, 20)
    finally:
        b.close()
    d = prom.delta(before, scrape())

    def count(stage):
        return prom.total(d, STAGE + "_count", stage=stage, shard=str(shard))

    def seconds(stage):
        return prom.total(d, STAGE + "_sum", stage=stage, shard=str(shard))

    for stage in NEW_STAGES + ("pack", "submit", "device", "collect", "settle"):
        assert count(stage) == 20, stage
    assert prom.total(d, WINDOW + "_count", shard=str(shard)) == 20
    assert 0 < seconds("stack") + seconds("dispatch") <= seconds("submit")
    assert 0 < seconds("fetch") + seconds("assemble") <= seconds("collect")
    assert seconds("stack") >= 20 * 0.0003 and seconds("dispatch") >= 20 * 0.0002
    assert seconds("fetch") >= 20 * 0.001 and seconds("post") > 0
    assert seconds("oracle") == 0  # a streamed flight has no synchronous check


def test_a_flight_without_a_streaming_evaluator_is_booked_as_oracle(shard):
    before = scrape()
    b = BatchingEvaluator(SyncEvaluator(), max_wait_ms=0.5, shard_id=shard)
    try:
        fly(b, 10, inputs=1)
    finally:
        b.close()
    d = prom.delta(before, scrape())
    assert prom.total(d, STAGE + "_count", stage="oracle", shard=str(shard)) == 10
    oracle = prom.total(d, STAGE + "_sum", stage="oracle", shard=str(shard))
    assert 10 * 0.0005 <= oracle <= prom.total(d, STAGE + "_sum", stage="submit", shard=str(shard))
    assert prom.total(d, THREAD, state="oracle", clock="wall", shard=str(shard)) == pytest.approx(oracle)
    for stage in ("stack", "dispatch", "fetch", "assemble"):
        assert prom.total(d, STAGE + "_sum", stage=stage, shard=str(shard)) == 0


def flights_of(shard: int) -> list[dict]:
    return [r for r in flight.recorder().dump()["batches"] if r["shard"] == shard]


def test_the_parts_tile_pack_and_dispatch_of_the_same_flight_and_are_observed_once_each(shard):
    before = scrape()
    b = BatchingEvaluator(FakeStreamingEvaluator(), max_wait_ms=0.5, shard_id=shard)
    try:
        fly(b, 20)
    finally:
        b.close()
    d = prom.delta(before, scrape())
    records = flights_of(shard)
    assert len(records) == 20
    for rec in records:
        t = rec["timings"]
        # booked from the same readings of the cursor: equal, but that the record rounds each to a microsecond
        assert sum(t[p] for p in PACK_PARTS) == pytest.approx(t["pack"], abs=ROUNDING * (len(PACK_PARTS) + 1))
        assert sum(t[p] for p in DISPATCH_PARTS) == pytest.approx(t["dispatch"], abs=ROUNDING * (len(DISPATCH_PARTS) + 1))
        assert t["pack_plan"] >= 0.00015 and t["dispatch_call"] >= 0.00015
        assert all(t[p] >= 0.00005 for p in PACK_PARTS[1:] + DISPATCH_PARTS[1:])

    def seconds(stage):
        return prom.total(d, STAGE + "_sum", stage=stage, shard=str(shard))

    for stage in PACK_PARTS + DISPATCH_PARTS:
        assert prom.total(d, STAGE + "_count", stage=stage, shard=str(shard)) == 20, stage
    # the histograms take the unrounded seconds: over 20 flights the parts ARE the whole
    assert sum(seconds(p) for p in PACK_PARTS) == pytest.approx(seconds("pack"), abs=1e-9)
    assert sum(seconds(p) for p in DISPATCH_PARTS) == pytest.approx(seconds("dispatch"), abs=1e-9)
    # the states read what they read before there were parts: the thread's pack seconds are the flights' own
    assert prom.total(d, THREAD, state="pack", clock="wall", shard=str(shard)) == pytest.approx(seconds("pack"))
    assert prom.total(d, THREAD, state="dispatch", clock="wall", shard=str(shard)) == pytest.approx(seconds("dispatch"))
    assert not [k for k in d if k[0] == THREAD and dict(k[1])["state"] not in dc.STATES + (dc.ALL,)]


@pytest.mark.parametrize("validates", [False, True], ids=["enforcement-none", "enforcement-warn"])
def test_assembles_two_parts_tile_it_and_the_schema_part_is_observed_only_by_a_flight_that_entered_it(shard, validates):
    before = scrape()
    b = BatchingEvaluator(FakeStreamingEvaluator(validates=validates), max_wait_ms=0.5, shard_id=shard)
    try:
        fly(b, 20)
    finally:
        b.close()
    d = prom.delta(before, scrape())
    records = flights_of(shard)
    assert len(records) == 20

    def seconds(stage):
        return prom.total(d, STAGE + "_sum", stage=stage, shard=str(shard))

    def count(stage):
        return prom.total(d, STAGE + "_count", stage=stage, shard=str(shard))

    for rec in records:
        t = rec["timings"]
        assert ("assemble_schema" in t) is validates
        assert t.get("assemble_schema", 0.0) + t["assemble_outputs"] == pytest.approx(t["assemble"], abs=ROUNDING * 3)
        assert t["assemble_outputs"] >= 0.0003 and t.get("assemble_schema", 0.0002) >= 0.0002
    assert count("assemble") == count("assemble_outputs") == 20
    assert count("assemble_schema") == (20 if validates else 0)
    # the series is there at 0 from the batcher's start, whether or not a flight ever enters the part
    assert (STAGE + "_count", (("shard", str(shard)), ("stage", "assemble_schema"))) in scrape()
    assert seconds("assemble_schema") + seconds("assemble_outputs") == pytest.approx(seconds("assemble"), abs=1e-9)
    assert prom.total(d, THREAD, state="assemble", clock="wall", shard=str(shard)) == pytest.approx(seconds("assemble"))
    assert (seconds("assemble_schema") >= 20 * 0.0002) is validates


def test_a_part_is_nothing_in_a_state_entered_without_one_and_an_oracle_flight_has_none(shard):
    clock = dc.install(str(shard))
    try:
        clock.to(dc.ORACLE)  # the numpy backend and a mesh pack inside this state
        for part in PACK_PARTS:
            dc.part(part)
        clock.to(dc.PACK, dc.PACK_PLAN)
        dc.part(dc.PACK_GATHER)
        clock.to(dc.OTHER)
        dc.part(dc.PACK_PREDS)  # after the state was left: nothing
        lap = clock.take_lap()
    finally:
        del dc._tls.clock
    assert set(lap) == {dc.OTHER, dc.ORACLE, dc.PACK, dc.PACK_PLAN, dc.PACK_GATHER}
    assert lap[dc.PACK_PLAN] + lap[dc.PACK_GATHER] == pytest.approx(lap[dc.PACK], abs=1e-9)
    # a flight with no streaming evaluator: every part is observed, as nothing
    before = scrape()
    b = BatchingEvaluator(SyncEvaluator(), max_wait_ms=0.5, shard_id=shard)
    try:
        fly(b, 5, inputs=1)
    finally:
        b.close()
    d = prom.delta(before, scrape())
    for stage in PACK_PARTS + DISPATCH_PARTS:
        assert prom.total(d, STAGE + "_count", stage=stage, shard=str(shard)) == 5
        assert prom.total(d, STAGE + "_sum", stage=stage, shard=str(shard)) == 0


def test_window_wait_is_nothing_for_a_lone_check(shard):
    b = BatchingEvaluator(FakeStreamingEvaluator(), max_wait_ms=30_000.0, shard_id=shard)
    try:
        fly(b, 1)
    finally:
        b.close()
    (rec,) = flights_of(shard)
    assert rec["timings"]["window"] == 0.0
    assert rec["submitted_monotonic_ns"] <= time.monotonic_ns()
    assert time.monotonic_ns() - rec["submitted_monotonic_ns"] < 60e9


def test_window_wait_of_a_check_that_ends_a_plan_querys_window_is_what_the_drain_waited(shard):
    b = BatchingEvaluator(FakeStreamingEvaluator(), max_wait_ms=30_000.0, shard_id=shard)
    b.plan_planner = EchoPlanner()
    out = {}
    try:
        planning = threading.Thread(target=lambda: out.update(q=b.plan(["q"])))
        planning.start()
        end = time.monotonic() + 10
        while b._clock.state != dc.WINDOW:
            assert time.monotonic() < end
            time.sleep(0.001)
        time.sleep(0.03)
        fly(b, 1)  # half a minute of window was left
        planning.join(timeout=10)
    finally:
        b.close()
    assert out == {"q": ["plan:q"]}
    (rec,) = [r for r in flights_of(shard) if "window" in r["timings"]]  # the check's flight, not the plan's
    assert 0.03 <= rec["timings"]["window"] < 10


def test_window_wait_is_nothing_when_a_flight_is_in_the_air(shard):
    gate = threading.Event()
    b = BatchingEvaluator(FakeStreamingEvaluator(gate), max_wait_ms=30_000.0, shard_id=shard)
    try:
        first = b.check_async([object()])
        time.sleep(0.1)  # the first flight's submit holds the drain thread
        second, third = b.check_async([object()]), b.check_async([object()])
        gate.set()
        for fut in (first, second, third):
            assert len(fut.result(timeout=10)) == 1
    finally:
        b.close()
    lone, pair = flights_of(shard)
    assert lone["requests"] == 1 and lone["timings"]["window"] == 0.0
    assert pair["requests"] == 2 and pair["timings"]["window"] == 0.0


# -- which dimension of the jit key made a compile necessary ---------------------

V1, V2 = ((0, None),), ((0, None), (1, (2, 3)))
S1, S2 = (("a",), (), (), (), (), 1, False), (("a", "b"), (), (), (), (), 1, False)


def test_novelty_classifier_on_a_hand_written_sequence():
    nc = compilestats.NoveltyClassifier()
    keys = [
        ((32, 64, 2, 4, 1, V1, S1), "shape"),        # everything is new: blamed on the first
        ((32, 128, 2, 4, 1, V1, S1), "shape"),       # BA_pad alone
        ((32, 64, 2, 8, 1, V1, S1), "class"),        # J alone
        ((32, 64, 2, 4, 1, V2, S1), "variant"),
        ((32, 64, 2, 4, 1, V1, S2), "columns"),
        ((32, 128, 2, 8, 1, V2, S2), "combination"),  # every component seen, never together
        ((64, 128, 2, 8, 2, V2, S2), "shape"),       # shape before class
        ((32, 64, 2, 8, 2, V1, S1), "class"),        # that (K, J, D) came with a new shape, but THIS shape was built at another: a growth
        ((16, 16, 1, 1, 1, V1), "shape"),            # the mesh path's key has no column layout
    ]
    for key, want in keys:
        assert nc.observe(compilestats.key_components(key)) == want, key
    assert compilestats.key_components(("a",)) is None
    assert compilestats.key_components(None) is None


def test_the_xla_compile_event_carries_the_seven_components_and_the_novel_one():
    st = compilestats.CompileStats()
    before = scrape()
    st.record_compile("B4096xBA8192", 0.25, source="fresh", trace_key=(4096, 8192, 3, 5, 2, V2, S2))
    st.record_compile("B4096xBA8192", 0.25, source="persistent", trace_key=(4096, 8192, 3, 5, 2, V1, S2))
    st.record_compile("B16xBA16", 0.1, trace_key=("opaque",))  # a key of another shape: no component is guessed
    events = [e for e in flight.recorder().dump()["events"] if e["kind"] == "xla_compile"][-3:]
    assert {k: events[0][k] for k in ("B_pad", "BA_pad", "K", "J", "D", "novel", "layout_key", "source")} == {
        "B_pad": 4096, "BA_pad": 8192, "K": 3, "J": 5, "D": 2, "novel": "shape",
        "layout_key": "B4096xBA8192", "source": "fresh",
    }
    assert events[1]["novel"] == "variant" and events[1]["source"] == "persistent"
    assert events[0]["columns"] == events[1]["columns"] and events[0]["variant"] != events[1]["variant"]
    assert re.fullmatch(r"[0-9a-f]{8}", events[0]["variant"]) and re.fullmatch(r"[0-9a-f]{8}", events[0]["columns"])
    assert "novel" not in events[2] and "B_pad" not in events[2]
    d = prom.delta(before, scrape())
    assert prom.total(d, NOVEL, dim="shape") == 1 and prom.total(d, NOVEL, dim="variant") == 1
    assert prom.total(d, NOVEL) == 2


# -- the profiler: options, regions, the shared clock ----------------------------


def test_run_trace_turns_the_python_tracer_off(monkeypatch, tmp_path):
    from jax import profiler as jprof

    from cerbos_tpu.tpu import jitcache

    import contextlib

    seen = {}

    @contextlib.contextmanager
    def trace(path, profiler_options=None, **kw):
        seen.update(path=path, options=profiler_options, open_at_start=obs.capture_open)
        yield
        seen["open_at_stop"] = obs.capture_open

    monkeypatch.setattr(jprof, "trace", trace)
    monkeypatch.setattr(jitcache, "device", lambda: {"platform": "cpu"})
    monkeypatch.setattr(time, "sleep", lambda s: seen.update(open_inside=obs.capture_open, slept=s))
    clocks = profiler._run_trace(str(tmp_path), 0.5)
    assert seen["options"].python_tracer_level == 0
    assert seen["options"].host_tracer_level == 1
    assert seen["slept"] == 0.5 and seen["open_inside"] is True
    assert seen["open_at_start"] is False and seen["open_at_stop"] is False and obs.capture_open is False
    assert set(clocks) == {
        "trace_start_monotonic_ns", "trace_start_unix_ns", "trace_stop_monotonic_ns", "trace_stop_unix_ns",
    }
    assert clocks["trace_start_monotonic_ns"] <= clocks["trace_stop_monotonic_ns"] <= time.monotonic_ns()


def test_a_front_end_cannot_open_a_capture(monkeypatch, tmp_path):
    from cerbos_tpu.tpu import jitcache

    monkeypatch.setattr(jitcache, "device", lambda: None)
    with pytest.raises(profiler.ProfilerDisabled):
        profiler._run_trace(str(tmp_path), 0.1)
    assert obs.capture_open is False


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """One real capture of a second over a few flights of the real evaluator,
    on the CPU backend: the reply and the planes of its trace."""
    from test_streaming_serving import inp, table

    from cerbos_tpu.tpu import TpuEvaluator, jitcache

    from cerbos_tpu.tpu import evaluator as evaluator_mod

    jitcache.open_device()
    ev = TpuEvaluator(table(), use_jax=True, min_device_batch=4, shard_id=77)
    b = BatchingEvaluator(ev, max_wait_ms=1.0, shard_id=77)
    stacked_bytes, fetched_bytes = [], []
    pad_stack, finalize = evaluator_mod._pad_stack, evaluator_mod._device_finalize

    def spy_pad_stack(*a, **kw):
        out = pad_stack(*a, **kw)
        stacked_bytes.append(sum(v.nbytes for v in out[0].values()))
        return out

    def spy_finalize(h):
        res = finalize(h)
        if h.out is not None:
            fetched_bytes.append(np.asarray(h.out).nbytes)
        return res

    mp = pytest.MonkeyPatch()
    mp.setattr(evaluator_mod, "_pad_stack", spy_pad_stack)
    mp.setattr(evaluator_mod, "_device_finalize", spy_finalize)
    before = scrape()
    base = tmp_path_factory.mktemp("profiles")
    profiler.configure(enabled=True, dir=str(base))
    try:
        b.check([inp(i) for i in range(8)])  # compiles before the capture
        b.plan_planner = EchoPlanner()
        box = {}
        thread = threading.Thread(target=lambda: box.update(profiler.capture(1.0)))
        thread.start()
        time.sleep(0.15)
        for k in range(6):
            b.check([inp(i) for i in range(8)])
            # under minDeviceBatch through the door that always queues: the drain thread's oracle
            # state (check() would answer it on this thread, with no flight: PR 30)
            b.check_async([inp(k)]).result(timeout=30)
            b.plan([k])  # alone in the queue: the window state, 1 ms
            time.sleep(0.01)
        thread.join(timeout=60)
        assert not thread.is_alive()
    finally:
        mp.undo()
        profiler.configure()
        b.close()
    path = trace_reduce.find_xplane(box["path"])
    assert path is not None
    with open(path, "rb") as f:
        planes = trace_reduce.read_planes(f.read())
    records = [r for r in flights_of(77) if r["inputs"] == 8]
    return {"reply": box, "planes": planes, "bytes": os.path.getsize(path), "flights": records,
            "stacked_bytes": stacked_bytes, "fetched_bytes": fetched_bytes, "moved": prom.delta(before, scrape())}


def host_event_names(planes) -> dict[str, int]:
    names: dict[str, int] = {}
    for p in planes:
        if p["name"].startswith("/host:"):
            for line in p["lines"]:
                for _, _, name in line["events"]:
                    names[name] = names.get(name, 0) + 1
    return names


def test_a_real_capture_holds_the_programs_regions_and_no_python_frame(capture):
    names = host_event_names(capture["planes"])
    # ``batch.pack`` is still there as the span's region; the STATES pack and dispatch show as their parts
    for want in ("batch.pack", "batch.stack", "batch.fetch", "batch.assemble_outputs", "batch.post",
                 "batch.oracle", "batcher.idle", "batcher.window", "batch.submit", "batch.collect", "request.settle"
                 ) + tuple("batch." + p for p in PACK_PARTS + DISPATCH_PARTS):
        assert names.get(want, 0) >= 6, (want, names)
    # a part's region takes the state's place, it does not nest in it; nothing is validated here (no schema manager)
    assert not {"batch.dispatch", "batch.assemble", "batch.assemble_schema"} & set(names)
    assert names["cerbos.clock"] == 2
    frames = [n for n in names if ".py" in n or n.startswith("$")]
    assert not frames, frames
    assert capture["bytes"] < 2 << 20  # six flights: hundreds of kilobytes, not the Python tracer's megabytes


def test_the_real_packers_parts_tile_pack_and_the_real_dispatchs_tile_dispatch(capture):
    assert len(capture["flights"]) == 7
    for rec in capture["flights"]:
        t = rec["timings"]
        assert sum(t[p] for p in PACK_PARTS) == pytest.approx(t["pack"], abs=ROUNDING * (len(PACK_PARTS) + 1))
        assert sum(t[p] for p in DISPATCH_PARTS) == pytest.approx(t["dispatch"], abs=ROUNDING * (len(DISPATCH_PARTS) + 1))
        assert all(t[p] > 0 for p in PACK_PARTS) and t["dispatch_copy"] > 0
    # the first flight's call was its layout's compile (state ``compile``): the copy is all its dispatch had
    first, rest = capture["flights"][0], capture["flights"][1:]
    assert first["timings"]["dispatch_call"] == 0.0 and all(r["timings"]["dispatch_call"] > 0 for r in rest)


def test_a_flights_put_and_fetch_bytes_are_what_it_stacked_and_what_it_fetched(capture):
    d, stacked, fetched = capture["moved"], capture["stacked_bytes"], capture["fetched_bytes"]
    assert len(stacked) == len(fetched) == 7 and min(stacked) > 0 and min(fetched) > 0
    for direction, want in (("put", stacked), ("fetch", fetched)):
        assert prom.total(d, TRANSFER + "_count", dir=direction, shard="77") == 7
        assert prom.total(d, TRANSFER + "_sum", dir=direction, shard="77") == sum(want)
    # the layout's compile event says the same of its layout
    event = [e for e in flight.recorder().dump()["events"] if e["kind"] == "xla_compile" and "put_bytes" in e][-1]
    assert event["put_bytes"] in stacked and event["fetch_bytes"] in fetched


def test_the_captures_reply_places_flights_on_the_trace(capture):
    reply = capture["reply"]
    assert reply["seconds"] == 1.0 and os.path.isdir(reply["path"])
    lo, hi = reply["trace_start_monotonic_ns"], reply["trace_stop_monotonic_ns"]
    assert 0.99e9 <= hi - lo < 3e9
    assert abs((reply["trace_stop_unix_ns"] - reply["trace_start_unix_ns"]) - (hi - lo)) < 50e6
    inside = [r for r in capture["flights"] if lo <= r["submitted_monotonic_ns"] <= hi]
    assert len(inside) == 6  # the flight before the capture is outside it
    assert not obs.capture_open


def test_region_with_no_capture_open_imports_no_jax_and_emits_nothing():
    code = (
        "import sys\n"
        "from cerbos_tpu import observability as obs\n"
        "from cerbos_tpu.engine import drainclock\n"
        "clock = drainclock.install('0')\n"
        "for state in drainclock.STATES:\n"
        "    clock.to(state)\n"
        "assert clock._region is None\n"
        "assert obs.region('batch.pack') is obs.region('batcher.idle')\n"
        "with obs.region('x', k=1):\n"
        "    pass\n"
        "with obs.start_span('y'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        "print('ok')\n"
    )
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_to_and_part_are_nothing_on_a_thread_without_a_clock():
    box = {}

    def other_thread():
        box["clock"] = getattr(dc._tls, "clock", None)
        dc.to(dc.PACK, dc.PACK_PLAN)
        dc.part(dc.PACK_GATHER)
        box["done"] = True

    t = threading.Thread(target=other_thread)
    t.start()
    t.join(timeout=10)
    assert box == {"clock": None, "done": True}


def test_span_export_builds_nothing_when_debug_logging_is_off():
    class Exploding:  # a mapping whose unpacking into the record would show
        def keys(self):
            raise AssertionError("the record was built")

    import logging

    span = obs.Span(name="x", trace_id=obs.new_trace_id())
    span.attributes = Exploding()
    log = logging.getLogger("cerbos_tpu.tracing")
    old = log.level
    try:
        log.setLevel(logging.INFO)
        obs.SpanExporter().export(span, 1.0)
        log.setLevel(logging.DEBUG)
        with pytest.raises(AssertionError):
            obs.SpanExporter().export(span, 1.0)
    finally:
        log.setLevel(old)


def test_one_flights_worth_of_the_clock_costs_microseconds(shard):
    """12 ``to()``, 6 ``part()``, 2 ``take_lap`` and 16 histogram observes (9 of
    them the parts', PR 38 and ``assemble_outputs``): what a flight
    pays with no capture open (the CPU clock, read ten times a second, apart). Printed for PERF.md (``pytest -s``); the limit
    is loose, a tenth of the cheapest stage."""
    from cerbos_tpu.engine.batcher import _ShardStageView

    clock = dc.install(str(shard))
    try:
        b = BatchingEvaluator.__new__(BatchingEvaluator)
        b._shard_label = str(shard)
        b._init_metrics()
        stages: _ShardStageView = b.m_stage_seconds
        walk = (dc.IDLE, dc.OTHER, dc.WINDOW, dc.OTHER, dc.PACK, dc.STACK, dc.DISPATCH, dc.OTHER,
                dc.FETCH, dc.ASSEMBLE, dc.SETTLE, dc.POST)

        def one_flight():
            for state in walk[:4]:
                clock.to(state)
            clock.to(dc.PACK, dc.PACK_PLAN)
            for part in PACK_PARTS[1:]:
                dc.part(part)
            clock.to(dc.STACK)
            clock.to(dc.DISPATCH, dc.DISPATCH_CALL)
            dc.part(dc.DISPATCH_COPY)
            clock.to(dc.OTHER)
            lap = clock.take_lap()
            b.m_window_wait.observe(0.002)
            for stage in ("stack", "dispatch", "oracle") + PACK_PARTS + DISPATCH_PARTS:
                stages.observe(stage, lap.get(stage, 0.0))
            dc.to(dc.FETCH)
            dc.to(dc.ASSEMBLE, dc.ASSEMBLE_OUTPUTS)
            for state in walk[10:]:
                dc.to(state)
            lap = clock.take_lap()
            for stage in ("fetch", "assemble", "assemble_outputs"):
                stages.observe(stage, lap.get(stage, 0.0))
            stages.observe("post", clock.to(dc.OTHER))

        for _ in range(500):
            one_flight()
        best = min(_timed(one_flight, 2000) for _ in range(5))
    finally:
        del dc._tls.clock
    print(f"\ndrain clock, per flight, no capture open: {best * 1e6:.1f} us")
    assert best < 500e-6


def _timed(fn, n: int) -> float:
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n


# -- the new per-layer metric files, on hand-written scrapes ----------------------

BENCH = os.path.join(REPO, "benchmarks")


def thread_series(walls: dict, cpu: float) -> str:
    kind = {s: "wait" if s in dc.WAIT else "work" for s in dc.STATES}
    return f'{THREAD}{{state="all",kind="work",clock="cpu",shard="0"}} {cpu}\n' + "".join(
        f'{THREAD}{{state="{s}",kind="{kind[s]}",clock="wall",shard="0"}} {v}\n' for s, v in walls.items()
    )


def stage_series(rows: dict) -> str:
    return "".join(
        f'{STAGE}_sum{{stage="{s}",shard="0"}} {total}\n{STAGE}_count{{stage="{s}",shard="0"}} {n}\n'
        for s, (total, n) in rows.items()
    )


BEFORE = (
    stage_series({"pack": (1.0, 100), "stack": (0.5, 100), "dispatch": (0.25, 100), "fetch": (0.125, 100),
                  "assemble": (2.0, 100), "settle": (0.0625, 100), "post": (0.75, 100), "oracle": (0.3, 100),
                  "device": (9.0, 100)})
    + f'{WINDOW}_sum{{shard="0"}} 0.19\n{WINDOW}_count{{shard="0"}} 100\n'
    + thread_series({"idle": 50.0, "window": 2.0, "pack": 10.0, "fetch": 1.0}, cpu=9.0)
    + f'{NOVEL}{{dim="shape"}} 12\n{NOVEL}{{dim="columns"}} 30\n{NOVEL}{{dim="combination"}} 6\n'
)
AFTER = (
    stage_series({"pack": (1.0 + 0.2, 200), "stack": (0.5 + 0.11, 200), "dispatch": (0.25 + 0.07, 200),
                  "fetch": (0.125 + 0.03, 200), "assemble": (2.0 + 0.31, 200), "settle": (0.0625 + 0.009, 200),
                  "post": (0.75 + 0.05, 200), "oracle": (0.3 + 0.045, 200), "device": (9.0 + 0.18, 200)})
    + f'{WINDOW}_sum{{shard="0"}} {0.19 + 0.19}\n{WINDOW}_count{{shard="0"}} 200\n'
    + thread_series({"idle": 50.0 + 26.0, "window": 2.0 + 3.0, "pack": 10.0 + 10.0, "fetch": 1.0 + 1.0}, cpu=9.0 + 8.0)
    + f'{NOVEL}{{dim="shape"}} 13\n{NOVEL}{{dim="columns"}} 30\n{NOVEL}{{dim="combination"}} 6\n'
)
EXPECTED = {
    "pack_mean_ms.pages": 2.0, "stack_mean_ms.pages": 1.1, "dispatch_mean_ms.pages": 0.7,
    "fetch_wait_mean_ms.pages": 0.3, "assemble_mean_ms.pages": 3.1, "settle_mean_ms.pages": 0.09,
    "post_settle_mean_ms.pages": 0.5, "oracle_eval_mean_ms.sidecar": 0.45,
    "window_wait_mean_ms.pages": 1.9, "window_wait_mean_ms.sidecar": 1.9,
    # wall: idle 26 + window 3 + fetch 1 waiting, pack 10 working, of 40 s
    "batcher_busy_share.pages": 25.0, "batcher_busy_share.sidecar": 25.0,
    # the working 10 s of wall held the CPU for 8 s
    "batcher_cpu_share.pages": 80.0, "batcher_cpu_share.sidecar": 80.0,
    "warm_layouts.pages": 48.0, "warm_shapes.pages": 12.0,  # at the window's open
}


def read_metric(name: str, before: str, after: str):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        body = json.load(f)
    ctx = {"before": prom.parse(before), "after": prom.parse(after)}
    return spec.load_reader(BENCH, body["reader"])(ctx, **body["args"])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_new_metric_file_reads_two_hand_written_scrapes(name):
    assert read_metric(name, BEFORE, AFTER) == pytest.approx(EXPECTED[name])


PARENT = 'cerbos_tpu_batch_stage_seconds_sum{stage="pack",shard="0"} 1\ncerbos_tpu_xla_compiles_total{source="fresh"} 3\n'


@pytest.mark.parametrize("name", sorted(set(EXPECTED) - {"pack_mean_ms.pages"}))
def test_new_metric_file_reads_nothing_from_a_program_without_the_clock(name):
    """The parent commit has none of these series: the line leaves the metric out."""
    assert read_metric(name, PARENT, PARENT) is None


CALLS = "cerbos_tpu_batch_device_calls"


@pytest.mark.parametrize("calls, flights, want", [(100, 100, 1.0), (160, 100, 1.6), (0, 0, None)])
def test_device_calls_mean_is_jitted_calls_per_device_served_flight(calls, flights, want):
    """PR 28's file: the histogram's growth over the window; nothing from a
    program without the series, or in a window with no device-served flight."""
    before = f'{CALLS}_sum{{shard="0"}} 7\n{CALLS}_count{{shard="0"}} 5\n'
    after = f'{CALLS}_sum{{shard="0"}} {7 + calls}\n{CALLS}_count{{shard="0"}} {5 + flights}\n'
    assert read_metric("device_calls_mean.pages", before, after) == want
    assert read_metric("device_calls_mean.pages", PARENT, PARENT) is None


ROUTES = "cerbos_tpu_batcher_checks_total"


@pytest.mark.parametrize("cell", ["sidecar", "pages"])
@pytest.mark.parametrize("inline, queued, want", [(300, 0, 100.0), (0, 40, 0.0), (3, 1, 75.0), (0, 0, None)])
def test_inline_share_is_the_share_of_checks_answered_with_no_flight(cell, inline, queued, want):
    """PR 30's files: the route counter's growth over the window; nothing from a
    program without the counter, or in a window in which no check got past the ladder."""
    before = f'{ROUTES}{{route="inline"}} 11\n{ROUTES}{{route="queued"}} 5\n'
    after = f'{ROUTES}{{route="inline"}} {11 + inline}\n{ROUTES}{{route="queued"}} {5 + queued}\n'
    assert read_metric(f"inline_share.{cell}", before, after) == want
    assert read_metric(f"inline_share.{cell}", PARENT, PARENT) is None


def test_every_new_metric_is_in_the_manifest_under_its_layer():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    # since PR 30 the single process makes no flight of one-resource checks, so the three of these that read a
    # flight or the drain thread in the sidecar mix are held to the one accepted cell (PR 32): a pool's cell,
    # where they are live, reads them through twins of its own
    held = {"window_wait_mean_ms.sidecar", "batcher_busy_share.sidecar", "batcher_cpu_share.sidecar"}
    for name in EXPECTED:
        entry = per_layer[name]
        assert entry.get("workloads") == (["classic-800.sidecar"] if name in held else None)
        assert entry["moves"] == ("check_p50_ms" if name.endswith(".sidecar") else "page_p50_ms")
        assert entry["source"] == ("program_counter" if "share" in name or name.startswith("warm_") else "program_span")
