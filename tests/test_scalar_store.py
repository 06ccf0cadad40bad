"""The packer's scalar store (PR 40): the matrices the C encoders write ARE
the columns, the fallback tags are tested once a flight on the whole matrix,
and ``ColumnBatch``'s five dictionaries hold row views. Held here to what the
store did before, one path at a time (kept below as the reference): the same
keys, VALUES and DTYPES (``int8`` tags, ``int32`` his/los/sids, ``bool``
nans), for flights whose inputs are all active and for flights with trivial
or oracle-routed inputs between active ones, on pages drawn from the
benchmark's generator and on a table with paths the fused C pass does not
take (a scope, a deep path).
"""

import numpy as np
import pytest

from cerbos_tpu import native as native_mod
from cerbos_tpu.compile import compile_policy_set
from cerbos_tpu.engine import CheckInput, EvalParams, Principal, Resource
from cerbos_tpu.policy.parser import parse_policies
from cerbos_tpu.ruletable import build_rule_table, check_input
from cerbos_tpu.tpu import TpuEvaluator
from cerbos_tpu.tpu import evaluator as evmod
from cerbos_tpu.tpu import packer as packer_mod
from cerbos_tpu.tpu.columns import TAG_OTHER, ColumnBatch

from test_transfer_format import benchmark_pages, corpus_table, pad_args

FAMILIES = {"tags": np.int8, "his": np.int32, "los": np.int32, "sids": np.int32, "nans": np.bool_}


@pytest.fixture(scope="module")
def native():
    mod = native_mod.get()
    if mod is None or not hasattr(mod, "encode_attr_columns_multi"):
        pytest.skip("the native encoders are not built: the packer takes its pure-Python store")
    return mod


def per_path_store(packer, plans, native) -> ColumnBatch:
    """The store as it was before PR 40: the same C passes, then per PATH an
    ``np.isin`` over the trigger tags, two ``astype`` copies and five
    dictionary stores (and five scatters where not every input is active)."""
    B = len(plans)
    cb = ColumnBatch(size=B)
    lt = packer.lt
    active = [(bi, plan) for bi, plan in enumerate(plans) if not (plan.trivial or plan.oracle)]
    na = len(active)
    act_inputs = [plan.input for _, plan in active]
    act_ix = np.fromiter((bi for bi, _ in active), dtype=np.int64, count=na)
    paths = sorted(lt.paths)
    missing, err = packer_mod._MISSING_SENTINEL, packer_mod._ERR_SENTINEL

    def store(p, t, h, l, s, nn):
        trig = lt.fallback_tags.get(p)
        if trig:
            bad = np.isin(t, np.fromiter(trig, dtype=np.uint8))
            for bi in np.nonzero(bad)[0]:
                plan = plans[int(bi)]
                if not (plan.trivial or plan.oracle):
                    plan.oracle = True
        cb.tags[p] = t.astype(np.int8)
        cb.his[p], cb.los[p], cb.sids[p] = h, l, s
        cb.nans[p] = nn.astype(bool)

    fused = [p for p in paths if packer._fused_mode(p) is not None] if act_inputs else []
    if fused:
        P = len(fused)
        MT, MN = np.zeros((P, na), dtype=np.uint8), np.zeros((P, na), dtype=np.uint8)
        MH, ML, MS = (np.zeros((P, na), dtype=np.int32) for _ in range(3))
        native.encode_attr_columns_multi(
            act_inputs, [packer._fused_mode(p) for p in fused], lt.interner.ids, missing, err,
            memoryview(MT), memoryview(MH), memoryview(ML), memoryview(MS), memoryview(MN),
        )
        for pi, p in enumerate(fused):
            cols = []
            for M in (MT, MH, ML, MS, MN):
                col = np.zeros(B, dtype=M.dtype)
                col[act_ix] = M[pi]
                cols.append(col)
            store(p, *cols)
    for p in paths:
        if p in fused:
            continue
        t, nn = np.zeros(B, dtype=np.uint8), np.zeros(B, dtype=np.uint8)
        h, l, s = (np.zeros(B, dtype=np.int32) for _ in range(3))
        accessor = packer._path_accessor(p)
        values = [missing] * B
        for bi, plan in active:
            values[bi] = accessor(plan.input)
        native.encode_column(
            values, lt.interner.ids, missing, err,
            memoryview(t), memoryview(h), memoryview(l), memoryview(s), memoryview(nn),
        )
        store(p, t, h, l, s, nn)
    return cb


def check_equal(cb: ColumnBatch, want: ColumnBatch):
    for family, dtype in FAMILIES.items():
        got_d, want_d = getattr(cb, family), getattr(want, family)
        assert set(got_d) == set(want_d), family
        for p, w in want_d.items():
            g = got_d[p]
            assert g.dtype == w.dtype == dtype, (family, p, g.dtype, w.dtype)
            assert g.shape == w.shape and g.flags["C_CONTIGUOUS"], (family, p)
            assert np.array_equal(g, w), (family, p)


@pytest.mark.parametrize("page", range(12))
def test_a_benchmark_page_stores_what_the_per_path_store_gave(native, page):
    """Each store on a table of its own, so both intern the page's strings in
    the same order. Some pages hold inputs with no policy (salary_record):
    not every flight is all-active."""
    inputs = benchmark_pages()[page]
    new, ref = TpuEvaluator(corpus_table(), use_jax=False), TpuEvaluator(corpus_table(), use_jax=False)
    batch = new.packer.pack(inputs, EvalParams())
    want_plans = _plans_before_store(ref.packer, inputs)
    want = per_path_store(ref.packer, want_plans, native)
    check_equal(batch.columns, want)
    assert [p.oracle for p in batch.plans] == [p.oracle for p in want_plans]
    assert [p.trivial for p in batch.plans] == [p.trivial for p in want_plans]
    assert batch.columns.scalars is not None and batch.columns.scalars[0] == tuple(sorted(new.lowered.paths))


def _plans_before_store(packer, inputs):
    """``pack``'s plans with the column encoding left out: the per-path store
    is then the first to intern this table's strings, in the order the new
    store's table saw them."""
    real = packer._encode_columns
    packer._encode_columns = lambda plans, params: ColumnBatch(size=len(plans))
    try:
        return packer.pack(inputs, EvalParams()).plans
    finally:
        packer._encode_columns = real


def test_all_active_and_mixed_flights_are_both_among_the_pages():
    packer = TpuEvaluator(corpus_table(), use_jax=False).packer
    kinds = set()
    for inputs in benchmark_pages():
        plans = _plans_before_store(packer, inputs)
        kinds.add(all(not (p.trivial or p.oracle) for p in plans))
    assert kinds == {True, False}


MIXED_POLICY = """
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
          any:
            of:
              - expr: request.resource.attr.owner == request.principal.id
              - expr: request.resource.attr.geo.country == "NZ"
              - expr: request.principal.scope == "acme"
              - expr: request.resource.attr.level > 3
"""


def mixed_table():
    return build_rule_table(compile_policy_set(list(parse_policies(MIXED_POLICY))))


def mixed_inputs(n: int, bad=(), trivial=()) -> list[CheckInput]:
    """``bad``: inputs whose owner is a LIST (TAG_OTHER at a compared scalar
    path: the device cannot hold it). ``trivial``: inputs of a kind with no
    policy."""
    out = []
    for i in range(n):
        attr = {"owner": f"u{i % 5}", "geo": {"country": "NZ" if i % 4 == 0 else "AU"}, "level": float(i % 7)}
        if i in bad:
            attr["owner"] = [f"u{i}"]
        out.append(
            CheckInput(
                principal=Principal(id=f"u{i % 3}", roles=["user"], scope="acme" if i % 6 == 0 else ""),
                resource=Resource(kind="nothing" if i in trivial else "doc", id=f"d{i}", attr=attr),
                actions=["view"],
            )
        )
    return out


MIXED_FLIGHTS = {
    "all_active": dict(n=20),
    "one_input": dict(n=1),
    "trivial_between_active": dict(n=23, trivial=(0, 5, 6, 22)),
    "bad_between_active": dict(n=19, bad=(3, 4, 18)),
    "trivial_and_bad": dict(n=33, bad=(1, 9), trivial=(2, 8, 32)),
    "all_trivial": dict(n=17, trivial=tuple(range(17))),
}


@pytest.mark.parametrize("flight", sorted(MIXED_FLIGHTS))
def test_fused_and_unfused_rows_store_what_the_per_path_store_gave(native, flight):
    inputs = mixed_inputs(**MIXED_FLIGHTS[flight])
    new, ref = TpuEvaluator(mixed_table(), use_jax=False), TpuEvaluator(mixed_table(), use_jax=False)
    unfused = [p for p in new.lowered.paths if new.packer._fused_mode(p) is None]
    assert len(unfused) >= 2 and len(unfused) < len(new.lowered.paths)  # the scope and the deep path, beside fused ones
    batch = new.packer.pack(inputs, EvalParams())
    want_plans = _plans_before_store(ref.packer, inputs)
    want = per_path_store(ref.packer, want_plans, native)
    check_equal(batch.columns, want)
    assert [p.oracle for p in batch.plans] == [p.oracle for p in want_plans]


def test_an_input_whose_tag_is_a_fallback_tag_goes_to_the_oracle_and_no_other(native):
    kw = MIXED_FLIGHTS["trivial_and_bad"]
    inputs = mixed_inputs(**kw)
    ev = TpuEvaluator(mixed_table(), use_jax=False)
    assert TAG_OTHER in ev.lowered.fallback_tags[("resource", "attr", "owner")]
    batch = ev.packer.pack(inputs, EvalParams())
    assert {i for i, p in enumerate(batch.plans) if p.oracle} == set(kw["bad"])
    assert {i for i, p in enumerate(batch.plans) if p.trivial} == set(kw["trivial"])
    # a path with no trigger tag is not among the lookup's rows
    plan = ev.packer._scalar_plan
    tested = plan.paths if isinstance(plan.trig_rows, slice) else [plan.paths[i] for i in plan.trig_rows]
    assert set(tested) == {p for p, tags in ev.lowered.fallback_tags.items() if tags and p in ev.lowered.paths}
    # and the answers are the oracle's, whoever gave them
    rt = ev.rule_table
    got = ev.check(inputs, EvalParams())
    want = [check_input(rt, i, EvalParams()) for i in inputs]
    assert [{a: e.effect for a, e in o.actions.items()} for o in got] == [
        {a: e.effect for a, e in o.actions.items()} for o in want
    ]
    assert ev.stats["oracle_inputs"] == len(kw["bad"]) and ev.stats["trivial_inputs"] == len(kw["trivial"])


def test_the_lookup_is_rebuilt_with_the_table(native):
    ev = TpuEvaluator(mixed_table(), use_jax=False)
    ev.packer.pack(mixed_inputs(4), EvalParams())
    first = ev.packer._scalar_plan
    assert first is not None and ev.packer.pack(mixed_inputs(4), EvalParams()) and ev.packer._scalar_plan is first
    ev.refresh()
    assert ev.packer._scalar_plan is None
    ev.packer.pack(mixed_inputs(4), EvalParams())
    assert ev.packer._scalar_plan is not first and ev.packer._scalar_plan.paths == first.paths


def test_no_view_of_a_pooled_buffer_outlives_the_flight(native, monkeypatch):
    """The dictionaries hold views of the packer's own matrices, never of the
    staging buffer: poisoning the buffer once the flight is collected (as the
    next lease will) changes no column."""
    pool = evmod._BufferPool()
    monkeypatch.setattr(evmod, "_buffer_pool", pool)
    packer = TpuEvaluator(corpus_table(), use_jax=False).packer
    batch = packer.pack(benchmark_pages()[0], EvalParams())
    cb = batch.columns
    before = {f: {p: a.copy() for p, a in getattr(cb, f).items()} for f in FAMILIES}
    stacked, cut, leased = evmod._pad_stack(*pad_args(batch))
    (buf,) = leased
    for f in FAMILIES:
        for p, a in getattr(cb, f).items():
            assert not np.shares_memory(a, buf), (f, p)
            assert a.base is not None and any(a.base is m or np.shares_memory(a, m) for m in cb.scalars[1:]), (f, p)
    buf.fill(-1)
    pool.release(leased)
    assert pool.lease(buf.shape, buf.dtype) is buf
    for f in FAMILIES:
        for p, a in getattr(cb, f).items():
            assert np.array_equal(a, before[f][p]), (f, p)


def test_a_served_flight_releases_its_one_buffer_at_finalize(monkeypatch):
    import test_layout_manifest as small

    pool = evmod._BufferPool()
    monkeypatch.setattr(evmod, "_buffer_pool", pool)
    ev = TpuEvaluator(small.table(), use_jax=True)
    ticket = ev.submit(small.inputs(20), EvalParams())
    ((batch, handle),) = ticket.parts
    (buf,) = handle.leased
    assert not pool._free
    ev.collect(ticket)
    assert handle.leased == () and [a for free in pool._free.values() for a in free] == [buf]
    assert all(not np.shares_memory(a, buf) for a in batch.columns.tags.values())
