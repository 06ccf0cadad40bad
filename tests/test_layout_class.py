"""One ``(K, J, D)`` class a table (PR 46): a batch is packed and dispatched
at the table's layout class, the running maximum of what its request shapes
have needed, and not at the batch's own maxima.

Held here: the class is monotone and capped and every batch carries it; a
batch packed at ANY class from its own up to the caps gives the oracle's
outputs and the same ``role_results`` / ``win_j`` in every slot assembly
reads (numpy backend and the jitted program on the CPU); a packer that
starts at the table's class meets one jit key a shape bucket where one that
sizes each batch by its own maxima meets several; the manifest files the
class with the first layout built at it and hands it back ahead of the next
process's first pack; a push starts the new table from ITS class.
"""

import json
import random

import numpy as np
import pytest

from cerbos_tpu.compile import compile_policy_set
from cerbos_tpu.engine import CheckInput, EvalParams, Principal, Resource
from cerbos_tpu.engine.flight import recorder
from cerbos_tpu.policy.parser import parse_policies
from cerbos_tpu.ruletable import build_rule_table, check_input
from cerbos_tpu.tpu import TpuEvaluator, compilestats
from cerbos_tpu.tpu import evaluator as evmod
from cerbos_tpu.tpu.packer import LayoutClass, Packer

from test_layout_manifest import keys as keys_of
from test_layout_manifest import layout_manifest, sans_source, the_table, walked  # noqa: F401  (the fixture: the manifest on, under a directory of each test's own)
from test_transfer_format import benchmark_pages, corpus_table, pad_args

CAPS = (8, 32, 8)

# a table whose request shapes differ in every extent: one role or three, one candidate a slot or three
# (two rules and a wildcard on ``view``), no scope or a chain of three
POLICIES = """
apiVersion: api.cerbos.dev/v1
resourcePolicy:
  resource: doc
  version: default
  rules:
    - actions: ["view"]
      effect: EFFECT_ALLOW
      roles: [user]
      condition:
        match:
          expr: request.resource.attr.owner == request.principal.id
    - actions: ["view"]
      effect: EFFECT_DENY
      roles: [user]
      condition:
        match:
          expr: request.resource.attr.%(flag)s == true
    - actions: ["*"]
      effect: EFFECT_ALLOW
      roles: [admin]
    - actions: ["edit"]
      effect: EFFECT_ALLOW
      roles: [editor, user]
      condition:
        match:
          expr: request.resource.attr.owner == request.principal.id
---
apiVersion: api.cerbos.dev/v1
resourcePolicy:
  resource: doc
  version: default
  scope: acme
  rules:
    - actions: ["view"]
      effect: EFFECT_DENY
      roles: [user]
      condition:
        match:
          expr: request.resource.attr.%(flag)s == true
---
apiVersion: api.cerbos.dev/v1
resourcePolicy:
  resource: doc
  version: default
  scope: acme.hr
  rules:
    - actions: ["edit"]
      effect: EFFECT_ALLOW
      roles: [editor]
"""

ROLE_SETS = (["user"], ["admin"], ["user", "editor"], ["editor", "user", "admin"], ["guest"])
SCOPES = ("", "acme", "acme.hr")
ACTION_SETS = (["view"], ["edit"], ["view", "edit"], ["view", "edit", "delete"])


def table(flag: str = "secret"):
    return build_rule_table(compile_policy_set(list(parse_policies(POLICIES % {"flag": flag}))))


def draw(rng: random.Random, n: int, flag: str = "secret", roles=ROLE_SETS, scopes=SCOPES) -> list[CheckInput]:
    return [
        CheckInput(
            principal=Principal(id=f"u{i % 5}", roles=list(rng.choice(roles))),
            resource=Resource(
                kind="doc", id=f"d{i}", scope=rng.choice(scopes),
                attr={"owner": f"u{rng.randrange(5)}", flag: rng.random() < 0.3},
            ),
            actions=list(rng.choice(ACTION_SETS)),
        )
        for i in range(n)
    ]


def plain(n: int, flag: str = "secret") -> list[CheckInput]:
    """Shapes that need no more than (1, 1, 1) ... (1, 2, 1): one role, no scope."""
    return draw(random.Random(n), n, flag, roles=(["user"],), scopes=("",))


def rich(n: int, flag: str = "secret") -> list[CheckInput]:
    return draw(random.Random(1000 + n), n, flag)


def oracle(rt, inputs):
    params = EvalParams()
    return sans_source([check_input(rt, i, params) for i in inputs])


def own_class(lt, inputs) -> tuple:
    """What the batch needs of its own: a fresh packer's class after one pack."""
    packer = Packer(lt)
    batch = packer.pack(inputs, EvalParams())
    return (batch.K, batch.J, batch.D)


def classes_from(own: tuple) -> list[tuple]:
    """Every class from a batch's own up to the caps, one dimension doubled at a time and all at once."""
    out = [own]
    for dim in range(3):
        kjd = list(own)
        while kjd[dim] < CAPS[dim]:
            kjd[dim] *= 2
            out.append(tuple(kjd))
    k, j, d = own
    while (k, j, d) != CAPS:
        k, j, d = min(2 * k, CAPS[0]), min(2 * j, CAPS[1]), min(2 * d, CAPS[2])
        out.append((k, j, d))
    return sorted(set(out))


# -- the class itself ---------------------------------------------------------


def test_the_class_is_monotone_capped_and_a_power_of_two():
    cls = LayoutClass(*CAPS)
    assert cls.kjd == (1, 1, 1)
    seen = []
    rng = random.Random(7)
    for _ in range(200):
        need = (rng.randrange(1, 12), rng.randrange(1, 50), rng.randrange(1, 12))
        before = cls.kjd
        got = cls.cover(*need)
        assert got == cls.kjd and all(b >= a for a, b in zip(before, got))
        assert all(x & (x - 1) == 0 and x <= cap for x, cap in zip(got, CAPS))
        assert all(x >= min(n, cap) for x, n, cap in zip(got, need, CAPS))
        seen.append(got)
    assert seen[-1] == CAPS and cls.cover(1, 1, 1) == CAPS


def test_a_restart_begins_at_one_and_a_restore_happens_once_and_only_raises():
    cls = LayoutClass(*CAPS)
    cls.cover(2, 3, 1)
    cls.restore(lambda: (1, 2, 4))
    assert cls.kjd == (2, 4, 4) and cls.restored
    cls.restore(lambda: pytest.fail("restored twice in one table's life"))
    cls.restart()
    assert cls.kjd == (1, 1, 1) and not cls.restored
    cls.restore(lambda: None)
    assert cls.kjd == (1, 1, 1) and cls.restored
    cls.restart()
    cls.restore(lambda: (64, 3, 8))  # a file from a process with larger caps: this one's caps hold
    assert cls.kjd == (8, 4, 8)


def test_lanes_raising_one_class_at_once_lose_no_growth_and_count_each_once():
    """More threads than cores on one shared class, a short switch interval:
    the class ends at the largest need of any of them, never falls under a
    reader, and every doubling of a dimension is counted as one growth."""
    import os
    import sys
    import threading

    cls = LayoutClass(*CAPS)
    stats = compilestats.stats()
    grows0 = {d: stats.m_class_grows.get(d) for d in compilestats.CLASS_DIMS}
    workers = (os.cpu_count() or 4) + 2
    needs = [[(random.Random(w * 1000 + i).randrange(1, 9), random.Random(w + i).randrange(1, 33), 1 + (w + i) % 8)
              for i in range(300)] for w in range(workers)]
    fell = []

    def lane(mine):
        seen = (1, 1, 1)
        for need in mine:
            got = cls.cover(*need)
            if any(g < s for g, s in zip(got, seen)) or any(g < min(n, c) for g, n, c in zip(got, need, CAPS)):
                fell.append((seen, need, got))
            seen = got

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=lane, args=(n,)) for n in needs]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and fell == []
    assert cls.kjd == CAPS
    rose = {d: stats.m_class_grows.get(d) - grows0[d] for d in compilestats.CLASS_DIMS}
    assert all(1 <= rose[d] <= 5 for d in compilestats.CLASS_DIMS)  # 1 -> 8, 1 -> 32, 1 -> 8: at most a growth a doubling


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_every_batch_of_a_packer_carries_the_class_and_the_class_never_falls(backend):
    ev = TpuEvaluator(table(), use_jax=backend == "jax")
    rng = random.Random(11)
    before = (1, 1, 1)
    flights = [plain(20), plain(40)] + [draw(rng, rng.randrange(16, 70)) for _ in range(10)] + [plain(20)]
    for inputs in flights:
        batch = ev.packer.pack(inputs, EvalParams())
        kjd = ev.packer.layout_class.kjd
        assert (batch.K, batch.J, batch.D) == kjd and all(b >= a for a, b in zip(before, kjd))
        assert batch.cand_cond.shape[1:] == kjd[:2] and batch.scope_sp.shape[1:] == (2, kjd[2])
        assert batch.cand_valid.shape == batch.cand_depth.shape == batch.cand_cond.shape
        before = kjd
    assert before == (4, 4, 4)  # three roles, three candidates a slot, a chain of three: each rounded up
    assert oracle(ev.rule_table, flights[0]) == sans_source(ev.check(flights[0], EvalParams()))


def test_lanes_of_one_table_share_one_class_and_only_the_owner_restarts_it():
    base = TpuEvaluator(table(), use_jax=False)
    lanes = [base.shard_clone(None, shard_id=i) for i in range(2)]
    assert all(lane.packer.layout_class is base.packer.layout_class for lane in lanes)
    lanes[0].packer.pack(rich(40), EvalParams())
    grown = base.packer.layout_class.kjd
    assert grown != (1, 1, 1)
    batch = lanes[1].packer.pack(plain(20), EvalParams())
    assert (batch.K, batch.J, batch.D) == grown
    lanes[0].invalidate()
    assert base.packer.layout_class.kjd == grown
    base.invalidate()
    assert base.packer.layout_class.kjd == (1, 1, 1)


# -- the same answers at any class --------------------------------------------


def read_slots(ev, batch, res):
    """``role_results`` and ``win_j`` where assembly reads them: the role slots
    a plan has (``_reconstruct``), both policy types."""
    final, role_results, win_j, _sat, _col_map = res
    out = []
    for plan in batch.plans:
        if plan.oracle or plan.trivial:
            out.append(None)
            continue
        start, end = plan.ba_range
        ks = min(len(plan.roles), batch.K)
        out.append(
            (np.asarray(final)[start:end, :2].tolist(), np.asarray(role_results)[start:end, :ks, :, 0].tolist(),
             _decided(role_results, win_j, start, end, ks))
        )
    return out


def _decided(role_results, win_j, start, end, ks):
    """The depth and the winning column of every slot that decided (a slot
    that did not carries ``D`` and -1, which nothing reads)."""
    rr, wj = np.asarray(role_results)[start:end, :ks], np.asarray(win_j)[start:end, :ks]
    hit = rr[..., 0] != evmod.CODE_NO_MATCH
    return np.where(hit, rr[..., 1], -1).tolist(), np.where(hit, wj, -1).tolist()


def eval_at(ev, inputs, kjd):
    ev.packer.layout_class.restart()
    ev.packer.layout_class.restore(lambda: kjd)
    batch = ev.packer.pack(inputs, EvalParams())
    assert (batch.K, batch.J, batch.D) == kjd
    mesh = None
    res = evmod._host_or_mesh_eval(ev.lowered, batch, mesh, ev._jit_cache)
    return batch, res


FLIGHTS = {
    "plain": lambda: (table(), plain(24)),
    "rich": lambda: (table(), rich(48)),
    "drawn": lambda: (table(), draw(random.Random(5), 33)),
    "corpus_page": lambda: (corpus_table(), benchmark_pages(3)[0]),
    "corpus_two_pages": lambda: (corpus_table(), sum(benchmark_pages(3)[1:], [])),
}


@pytest.mark.parametrize("which", sorted(FLIGHTS))
def test_a_batch_packed_at_any_class_up_to_the_caps_gives_the_oracle_s_outputs_numpy(which):
    rt, inputs = FLIGHTS[which]()
    ev = TpuEvaluator(rt, use_jax=False)
    want = oracle(rt, inputs)
    own = own_class(ev.lowered, inputs)
    base = None
    for kjd in classes_from(own):
        batch, res = eval_at(ev, inputs, kjd)
        ev._assemble_memo.clear()
        assert sans_source(ev._assemble_batch(batch, *res, EvalParams())) == want, kjd
        slots = read_slots(ev, batch, res)
        base = base if base is not None else slots
        assert slots == base, kjd


@pytest.mark.parametrize("which", ["rich", "corpus_page"])
def test_the_jitted_program_at_a_larger_class_gives_the_oracle_s_outputs(which):
    """CPU jit, the single-device route a served flight takes: the batch's own
    class, the corpus' final one, and one past it in every extent."""
    rt, inputs = FLIGHTS[which]()
    want = oracle(rt, inputs)
    own = own_class(TpuEvaluator(rt, use_jax=False).lowered, inputs)
    larger = tuple(min(2 * x, cap) for x, cap in zip(own, CAPS))
    for kjd in (own, larger):
        ev = TpuEvaluator(rt, use_jax=True)
        ev.packer.layout_class.restore(lambda: kjd)
        assert sans_source(ev.check(inputs, EvalParams())) == want, kjd
        (key,) = (k for k in ev._jit_cache if k != ("_variant_budget",))
        assert key[2:5] == kjd


# -- one key a shape ----------------------------------------------------------


def jit_keys(packer, flights) -> set:
    keys = set()
    for inputs in flights:
        batch = packer.pack(inputs, EvalParams())
        stacked, cut, leased = evmod._pad_stack(*pad_args(batch))
        evmod._buffer_pool.release(leased)
        keys.add((cut.B_pad, cut.BA_pad, batch.K, batch.J, batch.D, cut.sig))
    return keys


def test_a_packer_seeded_with_the_class_meets_one_key_a_shape_over_six_seeds_of_pages():
    """The corpus' pages mix, six seeds, one- and two-page flights: at the
    table's class a shape bucket is one key; sized by each batch's own maxima
    (what the parent did: a fresh class a batch) the same flights meet several
    keys a bucket."""
    lt = TpuEvaluator(corpus_table(), use_jax=False).lowered
    flights = []
    for seed in range(6):
        pages = benchmark_pages(10, seed=2147480001 + 7919 * seed)
        flights += pages[:6] + [pages[i] + pages[i + 1] for i in (6, 8)]
    own = {own_class(lt, f) for f in flights}
    final = tuple(max(c[d] for c in own) for d in range(3))
    assert len(own) >= 3  # the pages differ in what they hold
    seeded = Packer(lt)
    seeded.layout_class.restore(lambda: final)
    keys = jit_keys(seeded, flights)
    shapes = {k[:2] for k in keys}
    assert len(keys) == len(shapes) and {k[2:5] for k in keys} == {final}
    assert seeded.layout_class.kjd == final  # nothing grew: the class the manifest hands back is the last one
    by_batch = set()
    for f in flights:
        by_batch |= jit_keys(Packer(lt), [f])
    assert {k[:2] for k in by_batch} == shapes and len(by_batch) >= 2 * len(shapes)
    # a packer that starts at (1, 1, 1) ends at the same class, having met the shapes before each growth twice
    cold = Packer(lt)
    cold_keys = jit_keys(cold, flights)
    assert cold.layout_class.kjd == final and keys <= cold_keys and len(cold_keys) <= len(shapes) + 3 * 2


# -- the manifest carries the class -------------------------------------------


def settle(ev) -> None:
    if ev._preloader.thread is not None:
        walked(ev)


def test_the_class_is_filed_with_the_first_layout_built_at_it_and_a_grown_one_drops_the_smaller_s_entries(layout_manifest):
    ev = TpuEvaluator(table(), use_jax=True)
    ev.check(plain(20), EvalParams())
    ev.check(plain(40), EvalParams())
    small = ev.packer.layout_class.kjd
    t = the_table(layout_manifest)
    assert tuple(t["class"]) == small and sorted(e["shape"][0] for e in t["entries"].values()) == [32, 64]
    ev.check(rich(40), EvalParams())  # the class grows: B64 again, at the grown class
    grown = ev.packer.layout_class.kjd
    assert grown != small and all(b >= a for a, b in zip(small, grown))
    t = the_table(layout_manifest)
    assert tuple(t["class"]) == grown
    assert [(e["shape"][0], tuple(e["depth"])) for e in t["entries"].values()] == [(64, grown)]
    settle(ev)


def test_the_filed_class_is_restored_before_the_first_pack_and_every_shape_is_met_once(layout_manifest):
    first = TpuEvaluator(table(), use_jax=True)
    for inputs in (plain(20), rich(40), plain(20), rich(100)):
        first.check(inputs, EvalParams())
    settle(first)
    final = first.packer.layout_class.kjd
    assert len(keys_of(first)) == 4  # B32 before the growth and after it, B64 and B128 after
    grows0 = {d: compilestats.stats().m_class_grows.get(d) for d in compilestats.CLASS_DIMS}
    second = TpuEvaluator(table(), use_jax=True)
    assert second.packer.layout_class.kjd == (1, 1, 1) and not second.packer.layout_class.restored
    want = oracle(second.rule_table, plain(20))
    assert sans_source(second.check(plain(20), EvalParams())) == want
    assert second.packer.layout_class.kjd == final  # a plain page, packed at the table's class
    settle(second)
    for inputs in (rich(40), rich(100), plain(40)):
        assert sans_source(second.check(inputs, EvalParams())) == oracle(second.rule_table, inputs)
    assert {k[2:5] for k in keys_of(second)} == {final} and len(keys_of(second)) == 3
    assert {d: compilestats.stats().m_class_grows.get(d) for d in compilestats.CLASS_DIMS} == grows0
    assert [compilestats.stats().m_class.get(d) for d in compilestats.CLASS_DIMS] == list(final)


def test_the_walk_skips_an_entry_of_a_class_the_table_has_grown_out_of(layout_manifest):
    first = TpuEvaluator(table(), use_jax=True)
    first.check(plain(20), EvalParams())
    first.check(plain(40), EvalParams())
    settle(first)
    filed = first.packer.layout_class.kjd
    vec = compilestats.stats().m_preloads
    before = {o: vec.get(o) for o in compilestats.PRELOAD_OUTCOMES}
    second = TpuEvaluator(table(), use_jax=True)
    second.check(rich(100), EvalParams())  # the first flight restores the filed class and grows past it
    settle(second)
    assert second.packer.layout_class.kjd != filed
    assert {o: vec.get(o) - before[o] for o in before} == dict.fromkeys(compilestats.PRELOAD_OUTCOMES, 0)
    assert len(keys_of(second)) == 1


def pushed(ev, rt) -> None:
    """The push, as bootstrap's rollout subscriber makes it."""
    ev.rule_table = ev.lowered.table = rt
    ev.refresh()


def test_a_push_starts_the_new_table_from_its_own_class_and_walks_its_own_entries(layout_manifest):
    """A2 (d): ``invalidate()`` ends the old table's walk and arms the next;
    the new table's first device flight restores ITS class, never the old
    table's, and its walk brings in ITS entries. (A process that was PUSHED
    to a table lowers it into the compiler it has, so its programs are its
    history's: the entries that fit it are those of a process pushed the same
    way, here a replica that took the push earlier. The class is the table's
    whatever the history.)"""
    a, b = table("secret"), table("sealed")
    earlier = TpuEvaluator(a, use_jax=True)
    for inputs in (rich(40), rich(100)):
        earlier.check(inputs, EvalParams())
    settle(earlier)
    a_class = earlier.packer.layout_class.kjd
    pushed(earlier, b)
    for inputs in (plain(20, "sealed"), plain(40, "sealed")):
        earlier.check(inputs, EvalParams())
    settle(earlier)
    b_class = earlier.packer.layout_class.kjd
    assert a_class != b_class and all(x <= y for x, y in zip(b_class, a_class))
    doc = json.loads(layout_manifest.read_text())
    assert sorted(tuple(t["class"]) for t in doc["tables"].values()) == sorted([a_class, b_class])

    ev = TpuEvaluator(a, use_jax=True)
    ev.check(rich(40), EvalParams())
    settle(ev)
    assert ev.packer.layout_class.kjd == a_class and len(keys_of(ev)) == 2
    pushed(ev, b)
    assert ev.packer.layout_class.kjd == (1, 1, 1) and keys_of(ev) == set()
    inputs = plain(20, "sealed")
    assert sans_source(ev.check(inputs, EvalParams())) == oracle(b, inputs)
    assert ev.packer.layout_class.kjd == b_class
    settle(ev)
    assert sorted(k[0] for k in keys_of(ev)) == [32, 64] and {k[2:5] for k in keys_of(ev)} == {b_class}
    done = [e for e in recorder().dump()["events"] if e["kind"] == "xla_preload_done"][-1]
    assert done["stopped"] is False and done["loaded"] + done["fresh"] == 1 and done["held"] == 1
    # and a process BOOTED on the pushed table takes the class all the same; the pushed replicas' entries
    # do not fit its programs: each is one failed trace, no compile, and its own flights file its own
    compiles0 = compilestats.stats().snapshot()["compiles"]
    booted = TpuEvaluator(b, use_jax=True)
    booted.check(plain(20, "sealed"), EvalParams())
    settle(booted)
    assert booted.packer.layout_class.kjd == b_class and len(keys_of(booted)) == 1
    done = [e for e in recorder().dump()["events"] if e["kind"] == "xla_preload_done"][-1]
    assert done["failed"] == 2 and done["loaded"] + done["fresh"] + done["held"] == 0
    assert compilestats.stats().snapshot()["compiles"] == compiles0 + 1


# -- counted ------------------------------------------------------------------


def test_the_class_and_its_growths_are_on_the_instruments_and_on_the_compile_event():
    stats = compilestats.stats()
    grows0 = {d: stats.m_class_grows.get(d) for d in compilestats.CLASS_DIMS}
    ev = TpuEvaluator(table(), use_jax=True)
    ev.check(plain(20), EvalParams())
    small = ev.packer.layout_class.kjd
    assert [stats.m_class.get(d) for d in compilestats.CLASS_DIMS] == list(small)
    ev.check(rich(40), EvalParams())
    grown = ev.packer.layout_class.kjd
    assert [stats.m_class.get(d) for d in compilestats.CLASS_DIMS] == list(grown)
    rose = {d: stats.m_class_grows.get(d) - grows0[d] for d in compilestats.CLASS_DIMS}
    # from (1, 1, 1): a dimension counts once each time a request shape raised it
    assert all(rose[d] >= (1 if g > 1 else 0) for d, g in zip(compilestats.CLASS_DIMS, grown))
    assert sum(rose.values()) <= 6
    event = [e for e in recorder().dump()["events"] if e["kind"] == "xla_compile"][-1]
    assert (event["K"], event["J"], event["D"]) == grown and event["layout_class"] == list(grown)
    rendered = "\n".join(stats.m_class.render() + stats.m_class_grows.render() + stats.m_novel.render())
    for dim in compilestats.CLASS_DIMS:
        assert f'cerbos_tpu_xla_layout_class{{dim="{dim}"}}' in rendered
        assert f'cerbos_tpu_xla_layout_class_grows_total{{dim="{dim}"}}' in rendered
    assert 'dim="depth"' not in rendered


def test_a_compile_blamed_on_the_class_is_a_growth():
    nc = compilestats.NoveltyClassifier()
    sig = ((("a",),), (), (), (), (), 4, False)
    assert nc.observe(compilestats.key_components((32, 64, 1, 2, 4, (), sig))) == "shape"
    assert nc.observe(compilestats.key_components((32, 64, 2, 2, 4, (), sig))) == "class"
    assert nc.observe(compilestats.key_components((64, 128, 4, 2, 4, (), sig))) == "shape"  # a new shape brings (4, 2, 4)
    assert nc.observe(compilestats.key_components((32, 64, 4, 2, 4, (), sig))) == "class"  # and the old one is built again
    assert nc.observe(compilestats.key_components((32, 64, 4, 2, 4, (), sig))) == "combination"  # a flight and the walk raced
    assert "class" in compilestats.NOVEL_DIMS and "depth" not in compilestats.NOVEL_DIMS
