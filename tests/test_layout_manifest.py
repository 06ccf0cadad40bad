"""The layout manifest and the preloader (PR 37): a process loads the layouts
its table's traffic is known to meet as soon as its first flight takes the
device route, from a manifest kept beside the compile cache.

CPU backend, a tiny table. jax's own persistent cache stays where the test
process has it; only the manifest's directory is each test's own, so an entry
the walk brings in counts as ``loaded`` or ``fresh`` by what that cache holds.
"""

import json
import threading

import pytest

from cerbos_tpu.compile import compile_policy_set
from cerbos_tpu.engine import CheckInput, EvalParams, Principal, Resource
from cerbos_tpu.engine.flight import recorder
from cerbos_tpu.policy.parser import parse_policies
from cerbos_tpu.ruletable import build_rule_table, check_input
from cerbos_tpu.tpu import TpuEvaluator, compilestats, jitcache, layoutmanifest
from cerbos_tpu.tpu import evaluator as evmod

POLICY = """
apiVersion: api.cerbos.dev/v1
resourcePolicy:
  resource: report
  version: default
  rules:
    - actions: ["view"]
      effect: EFFECT_ALLOW
      roles: [user]
      condition:
        match:
          expr: request.resource.attr.owner == request.principal.id || request.resource.attr.%s == true
    - actions: ["*"]
      effect: EFFECT_ALLOW
      roles: [admin]
"""

SIZES = (20, 40, 70)  # three shape buckets: B32, B64, B128


def table(flag: str = "public"):
    return build_rule_table(compile_policy_set(list(parse_policies(POLICY % flag))))


def inputs(n: int, flag: str = "public") -> list[CheckInput]:
    return [
        CheckInput(
            principal=Principal(id=f"u{i}", roles=["user"]),
            resource=Resource(kind="report", id=f"r{i}", attr={"owner": f"u{i % 7}", flag: i % 3 == 0}),
            actions=["view"],
        )
        for i in range(n)
    ]


def keys(ev: TpuEvaluator) -> set:
    return {k for k in ev._jit_cache if k != ("_variant_budget",)}


def preloads() -> dict:
    vec = compilestats.stats().m_preloads
    return {o: vec.get(o) for o in compilestats.PRELOAD_OUTCOMES}


def grown(before: dict) -> dict:
    return {o: v - before[o] for o, v in preloads().items()}


def sans_source(outs):
    import dataclasses

    return [
        dataclasses.replace(o, actions={a: dataclasses.replace(e, source="") for a, e in o.actions.items()})
        for o in outs
    ]


@pytest.fixture(autouse=True)
def layout_manifest(monkeypatch, tmp_path):
    """Overrides conftest's: the manifest is on, under a directory of this test's own."""
    jitcache.enable()
    monkeypatch.setattr(jitcache, "_enabled", str(tmp_path))
    monkeypatch.setattr(layoutmanifest, "_warned", False)
    return tmp_path / "layouts" / "manifest.json"


def the_table(p) -> dict:
    """The one table of the manifest at ``p``: its class and its entries."""
    (t,) = json.loads(p.read_text())["tables"].values()
    return t


def filed(p) -> dict:
    return the_table(p)["entries"]


def first_process(flag: str = "public") -> tuple[TpuEvaluator, dict]:
    """An evaluator that meets three layouts inside its own flights and files them."""
    ev = TpuEvaluator(table(flag), use_jax=True)
    answers = {n: ev.check(inputs(n, flag), EvalParams()) for n in SIZES}
    ev._preloader.thread.join(30)
    return ev, answers


def walked(ev: TpuEvaluator) -> None:
    ev._preloader.thread.join(60)
    assert not ev._preloader.thread.is_alive()


def test_a_second_process_holds_every_layout_after_one_flight_and_answers_the_same(layout_manifest):
    first, answers = first_process()
    assert len(keys(first)) == len(SIZES)
    assert len(layoutmanifest.entries(next(iter(json.loads(layout_manifest.read_text())["tables"])))) == len(SIZES)
    assert len(filed(layout_manifest)) == len(SIZES)
    before, snap0 = preloads(), compilestats.stats().snapshot()
    second = TpuEvaluator(table(), use_jax=True)
    assert second.check(inputs(SIZES[0]), EvalParams()) == answers[SIZES[0]]
    walked(second)
    # one flight, one layout dispatched: the other two came from the manifest
    assert keys(second) == keys(first)
    got = grown(before)
    assert got["loaded"] + got["fresh"] == len(SIZES) - 1 and got["held"] == 1 and got["failed"] == 0
    snap1 = compilestats.stats().snapshot()
    assert snap1["cache_misses"] - snap0["cache_misses"] == 1
    for n in SIZES[1:]:
        assert second.check(inputs(n), EvalParams()) == answers[n]
    snap2 = compilestats.stats().snapshot()
    assert snap2["cache_misses"] == snap1["cache_misses"] and snap2["cache_hits"] - snap1["cache_hits"] == 2
    assert snap2["compiles"] == snap1["compiles"]
    params = EvalParams()
    assert sans_source(answers[SIZES[2]]) == sans_source([check_input(second.rule_table, i, params) for i in inputs(SIZES[2])])


def test_the_walk_is_on_the_instruments(layout_manifest):
    first_process()
    stats = compilestats.stats()
    count0, sum0, compiles0 = stats.m_preload_seconds.count, stats.m_preload_seconds.sum, stats.snapshot()["compiles"]
    second = TpuEvaluator(table(), use_jax=True)
    second.check(inputs(SIZES[0]), EvalParams())
    walked(second)
    assert stats.m_preload_seconds.count - count0 == len(SIZES)
    assert stats.m_preload_seconds.sum > sum0
    # the walk's loads are compiles since boot like any other: one flight's own and two of the walk
    assert stats.snapshot()["compiles"] - compiles0 == len(SIZES)
    done = [e for e in recorder().dump()["events"] if e["kind"] == "xla_preload_done"][-1]
    assert done["loaded"] + done["fresh"] == len(SIZES) - 1 and done["held"] == 1 and done["stopped"] is False
    assert done["seconds"] > 0
    text = "\n".join(stats.m_preloads.render() + stats.m_preload_seconds.render())
    for outcome in compilestats.PRELOAD_OUTCOMES:
        assert f'cerbos_tpu_xla_preloads_total{{outcome="{outcome}"}}' in text
    assert "cerbos_tpu_xla_preload_seconds_sum" in text
    assert jitcache.status()["manifest"] == {"path": str(layout_manifest), "bytes": layout_manifest.stat().st_size}


def test_no_flight_no_thread_and_no_manifest_read(layout_manifest, monkeypatch):
    first_process()
    reads = []
    real = layoutmanifest._read
    monkeypatch.setattr(layoutmanifest, "_read", lambda p: reads.append(p) or real(p))
    compiles0 = compilestats.stats().snapshot()["compiles"]
    ev = TpuEvaluator(table(), use_jax=True)
    # under min_device_batch the oracle answers: nothing is packed, nothing read
    ev.check(inputs(3), EvalParams())
    assert jitcache.status()["manifest"]["bytes"] > 0  # the boot line's status is a stat, not a read
    assert reads == []
    numpy_ev = TpuEvaluator(table(), use_jax=False)
    numpy_ev.check(inputs(SIZES[0]), EvalParams())
    assert numpy_ev._preloader.thread is None and reads == []
    # a batch no policy covers is packed for the device and never reaches it: the table's class is read
    # ahead of that first pack, once (a file read: no thread, no load, no compile), and not again
    strangers = [
        CheckInput(principal=Principal(id="u", roles=["user"]), resource=Resource(kind="nothing", id=str(i)), actions=["view"])
        for i in range(20)
    ]
    ev.check(strangers, EvalParams())
    ev.check(strangers, EvalParams())
    assert ev._preloader.thread is None and len(reads) == 1
    assert not [t for t in threading.enumerate() if t.name == "xla-preload"]
    assert compilestats.stats().snapshot()["compiles"] == compiles0


def test_without_a_cache_directory_nothing_is_recorded_or_loaded(layout_manifest, monkeypatch):
    monkeypatch.setattr(jitcache, "_enabled", False)
    ev = TpuEvaluator.__new__(TpuEvaluator)  # not through __init__, which enables the cache
    ev.__init__(table(), use_jax=False)
    ev.use_jax = True
    assert ev.check(inputs(SIZES[0]), EvalParams())
    assert ev._preloader.thread is None and layoutmanifest.path() is None
    assert not layout_manifest.exists()


def _corrupt(p, first):
    p.write_text("{not json")


def _another_table(p, first):
    doc = json.loads(p.read_text())
    doc["tables"] = {"0123456789abcdef" + k[16:]: t for k, t in doc["tables"].items()}
    p.write_text(json.dumps(doc))


def _another_jax(p, first):
    doc = json.loads(p.read_text())
    doc["tables"] = {k.replace("|jax=", "|jax=0.0.1+"): t for k, t in doc["tables"].items()}
    p.write_text(json.dumps(doc))


def _another_format(p, first):
    doc = json.loads(p.read_text())
    doc["format"] = 99
    p.write_text(json.dumps(doc))


def _a_list(p, first):
    p.write_text("[1, 2, 3]")


OLD_ARGS = ("i32_cols", "i8_cols", "bool_cols", "lists", "cand_i32", "cand_i8", "ba_input", "now")


def _the_format_before_one_buffer(p, first):
    """The file as the commit before PR 40 wrote it: format 1, each entry with
    the eight arguments of the program it described."""
    doc = json.loads(p.read_text())
    doc["format"] = 1
    doc["tables"] = {k: t["entries"] for k, t in doc["tables"].items()}
    for t in doc["tables"].values():
        for e in t.values():
            e["args"] = {name: [[2, 16], "<i4"] for name in OLD_ARGS}
    p.write_text(json.dumps(doc))


def _the_format_before_the_class(p, first):
    """The file as PR 40 to PR 45 wrote it: format 2, a table is its entries."""
    doc = json.loads(p.read_text())
    doc["format"] = 2
    doc["tables"] = {k: t["entries"] for k, t in doc["tables"].items()}
    p.write_text(json.dumps(doc))


def _an_entry_of_another_class(p, first):
    """Format 3, ill-formed: every entry of a table is of the table's class."""
    doc = json.loads(p.read_text())
    for t in doc["tables"].values():
        next(iter(t["entries"].values()))["depth"] = [8, 8, 8]
    p.write_text(json.dumps(doc))


@pytest.mark.parametrize(
    "spoil",
    [
        _corrupt, _another_table, _another_jax, _another_format, _a_list, _the_format_before_one_buffer,
        _the_format_before_the_class, _an_entry_of_another_class,
    ],
)
def test_a_manifest_that_is_not_this_process_s_loads_nothing_and_fails_nothing(layout_manifest, spoil):
    first, answers = first_process()
    spoil(layout_manifest, first)
    before = preloads()
    second = TpuEvaluator(table(), use_jax=True)
    assert second.check(inputs(SIZES[0]), EvalParams()) == answers[SIZES[0]]
    walked(second)
    assert grown(before) == dict.fromkeys(compilestats.PRELOAD_OUTCOMES, 0)
    assert len(keys(second)) == 1
    # and it serves as a process without a manifest does: each layout inside its own flight
    for n in SIZES[1:]:
        assert second.check(inputs(n), EvalParams()) == answers[n]
    assert keys(second) == keys(first)


@pytest.mark.parametrize("older", [_the_format_before_one_buffer, _the_format_before_the_class])
def test_a_manifest_of_the_old_format_is_ignored_not_walked_and_the_next_record_starts_anew(
    layout_manifest, caplog, older
):
    first_process()
    older(layout_manifest, None)
    (scope,) = json.loads(layout_manifest.read_text())["tables"]
    before = preloads()
    with caplog.at_level("WARNING", logger="cerbos_tpu.layoutmanifest"):
        assert layoutmanifest.entries(scope) == [] and layoutmanifest.layout_class(scope) is None
    assert any("not of format 3" in r.getMessage() for r in caplog.records)
    second = TpuEvaluator(table(), use_jax=True)
    second.check(inputs(SIZES[1]), EvalParams())
    walked(second)
    assert grown(before) == dict.fromkeys(compilestats.PRELOAD_OUTCOMES, 0)  # nothing walked: no ``failed`` either
    doc = json.loads(layout_manifest.read_text())
    assert doc["format"] == layoutmanifest.FORMAT == 3
    assert doc["tables"][scope]["class"] == [1, 1, 1]
    (entry,) = doc["tables"][scope]["entries"].values()
    assert entry["shape"][0] == 64 and entry["met"] == 1 and "args" not in entry


def test_an_entry_that_cannot_be_built_is_counted_and_skipped(layout_manifest):
    first, answers = first_process()
    doc = json.loads(layout_manifest.read_text())
    (t,) = doc["tables"].values()
    entries = t["entries"]
    ids = sorted(entries, key=lambda i: entries[i]["seq"])
    entries[ids[1]]["variant"] = [[99, None]]  # a group this table does not have
    del entries[ids[2]]["layout"]["paths"]
    layout_manifest.write_text(json.dumps(doc))
    before = preloads()
    second = TpuEvaluator(table(), use_jax=True)
    assert second.check(inputs(SIZES[0]), EvalParams()) == answers[SIZES[0]]
    walked(second)
    got = grown(before)
    assert got["failed"] == 2 and got["held"] == 1 and got["loaded"] + got["fresh"] == 0
    assert second.check(inputs(SIZES[2]), EvalParams()) == answers[SIZES[2]]


class _Gate:
    """Holds the preloader's thread inside ``_jit_run`` until the test lets it go."""

    def __init__(self, monkeypatch):
        self.inside, self.go = threading.Event(), threading.Event()
        real = evmod._jit_run

        def held(*args):
            if threading.current_thread().name == "xla-preload":
                self.inside.set()
                assert self.go.wait(60)
            return real(*args)

        monkeypatch.setattr(evmod, "_jit_run", held)


def test_invalidate_mid_walk_publishes_nothing_for_the_old_table(layout_manifest, monkeypatch):
    first, _ = first_process()
    gate = _Gate(monkeypatch)
    before = preloads()
    second = TpuEvaluator(table(), use_jax=True)
    second.check(inputs(SIZES[0]), EvalParams())
    assert gate.inside.wait(60)  # the walk holds an entry it has not built yet
    old_walk = second._preloader.thread
    second.invalidate()
    assert keys(second) == set()
    gate.go.set()
    old_walk.join(60)
    assert keys(second) == set()
    assert grown(before)["loaded"] + grown(before)["fresh"] == 0
    done = [e for e in recorder().dump()["events"] if e["kind"] == "xla_preload_done"][-1]
    assert done["stopped"] is True and done["loaded"] + done["fresh"] == 0
    # the table that is in place now is served, and ITS first device flight starts ITS walk (here the same
    # identity: ``invalidate()`` alone changes no table), which brings in what its flight did not build
    assert len(second.check(inputs(SIZES[1]), EvalParams())) == SIZES[1]
    assert second._preloader.thread is not old_walk
    walked(second)
    assert keys(second) == keys(first)
    done = [e for e in recorder().dump()["events"] if e["kind"] == "xla_preload_done"][-1]
    assert done["stopped"] is False and done["loaded"] + done["fresh"] == len(SIZES) - 1 and done["held"] == 1


def test_a_walk_started_under_a_stopped_one_waits_for_it(layout_manifest, monkeypatch):
    """Two tables in a row, the second in place while the first's walk still
    has an entry in hand: one ``xla-preload`` thread inside XLA at a time, and
    ``close()`` (the interpreter's exit) waits for both."""
    first_process()
    gate = _Gate(monkeypatch)
    second = TpuEvaluator(table(), use_jax=True)
    second.check(inputs(SIZES[0]), EvalParams())
    assert gate.inside.wait(60)
    old_walk = second._preloader.thread
    second.invalidate()
    second.check(inputs(SIZES[1]), EvalParams())
    new_walk = second._preloader.thread
    assert new_walk is not old_walk and old_walk.is_alive() and new_walk.is_alive()
    before = preloads()
    closer = threading.Thread(target=second._preloader.close)
    closer.start()
    closer.join(0.5)
    assert closer.is_alive() and grown(before) == dict.fromkeys(compilestats.PRELOAD_OUTCOMES, 0)
    gate.go.set()
    closer.join(60)
    assert not closer.is_alive() and not old_walk.is_alive() and not new_walk.is_alive()


def test_a_flight_and_the_walk_racing_on_one_key_leave_one_function(layout_manifest, monkeypatch):
    first, answers = first_process()
    gate = _Gate(monkeypatch)
    before = preloads()
    second = TpuEvaluator(table(), use_jax=True)
    second.check(inputs(SIZES[0]), EvalParams())
    assert gate.inside.wait(60)  # the walk is at the second layout, nothing published yet
    assert second.check(inputs(SIZES[1]), EvalParams()) == answers[SIZES[1]]  # the flight builds it itself
    (raced,) = [k for k in keys(second) if k[0] == 64]
    flights_fn = second._jit_cache[raced]
    gate.go.set()
    walked(second)
    assert second._jit_cache[raced] is flights_fn  # the walk finished second and dropped its copy
    got = grown(before)
    assert got["held"] == 2 and got["loaded"] + got["fresh"] == 1 and got["failed"] == 0
    assert keys(second) == keys(first)
    for n in SIZES:
        assert second.check(inputs(n), EvalParams()) == answers[n]
    # the flight met the layout before the walk did: it is counted once more
    assert sorted(e["met"] for e in filed(layout_manifest).values()) == [1, 2, 2]


def test_the_walk_never_feeds_the_storm_detector(layout_manifest, monkeypatch):
    first_process()
    stats = compilestats.stats()
    seen = []
    real = stats.detector.observe
    monkeypatch.setattr(stats.detector, "observe", lambda k: seen.append(threading.current_thread().name) or real(k))
    storms0 = stats.m_storms.value
    second = TpuEvaluator(table(), use_jax=True)
    second.check(inputs(SIZES[0]), EvalParams())
    walked(second)
    assert "xla-preload" not in seen and len(seen) == 1  # the one layout the flight compiled itself
    assert stats.m_storms.value == storms0


def test_record_compile_keeps_a_deliberate_load_from_the_detector():
    cs = compilestats.CompileStats(storm_threshold=1)
    storms0 = cs.m_storms.value
    cs.record_compile("B32xBA32", 0.1, source="persistent", trace_key=(32, 32, 1, 1, 1, (), ()), storm=False)
    assert cs.detector.storms == 0 and cs.m_storms.value == storms0
    cs.record_compile("B32xBA32", 0.1, source="persistent", trace_key=(32, 32, 1, 1, 1, (), ()))
    assert cs.detector.storms == 1 and cs.m_storms.value == storms0 + 1


def test_entry_count_is_the_same_with_and_without_a_manifest(layout_manifest, tmp_path):
    (tmp_path / "jit_run-abc").write_bytes(b"x")
    assert jitcache.entry_count() == 1
    first_process()
    assert layout_manifest.exists()
    assert jitcache.entry_count() == 1
    assert sorted(p.name for p in tmp_path.iterdir()) == ["jit_run-abc", "layouts"]
    assert [p.name for p in layout_manifest.parent.iterdir()] == ["manifest.json"]  # no temp file left


def _entry(n: int) -> dict:
    return {"shape": [n, n], "depth": [1, 1, 1], "variant": [], "layout": {}}


def test_the_manifest_is_bounded_and_the_least_met_go_first(layout_manifest, monkeypatch):
    monkeypatch.setattr(layoutmanifest, "MAX_ENTRIES", 4)
    for n in (1, 2, 3):
        layoutmanifest.record("old", _entry(n))
    layoutmanifest.record("old", _entry(2))  # met twice
    for n in (4, 5, 6):
        layoutmanifest.record("new", _entry(n))
    assert sum(len(t["entries"]) for t in json.loads(layout_manifest.read_text())["tables"].values()) == 4
    # of those met once the oldest went: 1 and 3; the one met twice stays, and leads its table
    assert [e["shape"][0] for e in layoutmanifest.entries("old")] == [2]
    assert [e["shape"][0] for e in layoutmanifest.entries("new")] == [4, 5, 6]
    for n in (7, 8, 9, 10):
        layoutmanifest.record("newer", _entry(n))
    assert sorted(json.loads(layout_manifest.read_text())["tables"]) == ["newer", "old"]
    assert layoutmanifest.size() == {"path": str(layout_manifest), "bytes": layout_manifest.stat().st_size}
    assert [e["shape"][0] for e in layoutmanifest.entries("old")] == [2]
    assert layoutmanifest.entries("new") == []


def test_entries_come_most_met_first_then_in_the_order_met(layout_manifest):
    for n in (1, 2, 3, 3, 2, 3):
        layoutmanifest.record("t", _entry(n))
    assert [(e["shape"][0], e["met"]) for e in layoutmanifest.entries("t")] == [(3, 3), (2, 2), (1, 1)]
    assert layoutmanifest.entries("another") == []


def test_an_unreadable_manifest_is_logged_once(layout_manifest, caplog):
    layout_manifest.parent.mkdir()
    layout_manifest.write_text("{not json")
    with caplog.at_level("WARNING", logger="cerbos_tpu.layoutmanifest"):
        assert layoutmanifest.entries("t") == []
        assert layoutmanifest.entries("t") == []
        assert layoutmanifest.size()["bytes"] == len("{not json")
    assert len([r for r in caplog.records if "unreadable" in r.getMessage()]) == 1
    # the next process to meet a layout starts it anew
    layoutmanifest.record("t", _entry(1))
    assert [e["met"] for e in layoutmanifest.entries("t")] == [1]


def test_a_directory_that_cannot_be_written_records_nothing_and_raises_nothing(layout_manifest, tmp_path):
    (tmp_path / "layouts").write_text("a file where the directory should be")
    ev = TpuEvaluator(table(), use_jax=True)
    assert len(ev.check(inputs(SIZES[0]), EvalParams())) == SIZES[0]
    walked(ev)
    assert layoutmanifest.entries("t") == []


def test_an_entry_round_trips_to_the_key_a_flight_computes(layout_manifest):
    first, _ = first_process()
    rebuilt = set()
    for entry in filed(layout_manifest).values():
        key, cut, zeros = evmod._entry_parts(entry)
        rebuilt.add(key)
        assert key[6] == cut.sig
        assert evmod._manifest_entry(key, cut.lay) == {k: v for k, v in entry.items() if k not in ("met", "seq")}
    assert rebuilt == keys(first)


def test_jax_s_cache_events_are_read_per_thread():
    import jax
    import jax.numpy as jnp

    jitcache.enable()
    with compilestats.cache_events() as mine:
        other: list = []

        def compile_elsewhere():
            with compilestats.cache_events() as theirs:
                jax.jit(lambda x: jnp.sin(x) * 37.5 + 1.25)(jnp.arange(7.0)).block_until_ready()
            other.extend(theirs)

        t = threading.Thread(target=compile_elsewhere)
        t.start()
        t.join(60)
    assert mine == [] and compilestats.source_of(mine) is None
    assert compilestats.source_of(other) in ("fresh", "persistent")
    assert compilestats.source_of(["/jax/compilation_cache/compile_requests_use_cache"]) == "fresh"
    assert (
        compilestats.source_of(
            ["/jax/compilation_cache/compile_requests_use_cache", "/jax/compilation_cache/cache_hits"]
        )
        == "persistent"
    )


def test_flights_on_many_threads_beside_the_walk_leave_one_function_a_key(layout_manifest):
    """More callers than cores, a short switch interval: whoever builds a key
    first, flight or walk, every key ends with one function, every answer is
    the first process's, and no key is counted as brought in twice."""
    import os
    import sys
    from concurrent.futures import ThreadPoolExecutor

    first, answers = first_process()
    before = preloads()
    second = TpuEvaluator(table(), use_jax=True)
    workers = (os.cpu_count() or 4) + 2
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with ThreadPoolExecutor(workers) as pool:
            # a TpuEvaluator is driven by one thread at a time (the drain thread): the callers take turns
            turn = threading.Lock()

            def call(k):
                n = SIZES[k % len(SIZES)]
                with turn:
                    return n, second.check(inputs(n), EvalParams())

            for n, got in pool.map(call, range(4 * workers), timeout=120):
                assert got == answers[n]
        walked(second)
    finally:
        sys.setswitchinterval(old)
    assert keys(second) == keys(first)
    got = grown(before)
    assert got["failed"] == 0 and got["loaded"] + got["fresh"] + got["held"] == len(SIZES)
    assert got["loaded"] + got["fresh"] <= len(SIZES) - 1  # the first flight's own key is never the walk's


def test_close_ends_the_walk_and_waits_for_the_entry_in_hand(layout_manifest, monkeypatch):
    """What the interpreter's exit calls while a walk is on (a daemon thread that the
    exit finds inside XLA aborts the process): nothing more is published, and the
    thread is gone when it returns."""
    first_process()
    gate = _Gate(monkeypatch)
    second = TpuEvaluator(table(), use_jax=True)
    second.check(inputs(SIZES[0]), EvalParams())
    assert gate.inside.wait(60)
    held = set(keys(second))
    closer = threading.Thread(target=second._preloader.close)
    closer.start()
    closer.join(0.2)
    assert closer.is_alive()  # it waits for the entry the walk holds
    gate.go.set()
    closer.join(60)
    assert not closer.is_alive() and not second._preloader.thread.is_alive()
    assert keys(second) == held
    second._preloader.close()  # and again, after the walk: nothing to wait for
