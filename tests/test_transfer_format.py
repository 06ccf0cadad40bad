"""The transfer format of the single-device path (PR 40): a flight's inputs are
laid into ONE staging buffer of int32 words and cut back apart inside the
trace. The sections the TRACED cut yields (``jnp`` under ``jax.jit``, so
``lax.bitcast_convert_type`` for the one-byte sections) are held, bit for
bit and dtype for dtype, to what ``_pad_arrays`` builds for the mesh route:
for the layouts real flights meet (a small table's, and pages drawn from the
benchmark's generator on its corpus) and for hand-made edge cases. Also here:
the manifest entry's round trip to the key and a zero buffer, and the
``batch_device_puts`` histogram.
"""

import json

import numpy as np
import pytest

from cerbos_tpu.compile import compile_policy_set
from cerbos_tpu.engine import AuxData, CheckInput, EvalParams, Principal, Resource
from cerbos_tpu.engine.batcher import BatchingEvaluator
from cerbos_tpu.policy.parser import parse_policies
from cerbos_tpu.ruletable import build_rule_table, check_input
from cerbos_tpu.tpu import TpuEvaluator
from cerbos_tpu.tpu import evaluator as evmod
from cerbos_tpu.tpu.columns import ColumnBatch
from cerbos_tpu.tpu.packer import PackedBatch

import test_layout_manifest as small

MODS = 10
PAGES = 12


def corpus_table():
    from benchmarks.lib import corpus

    return build_rule_table(compile_policy_set(list(parse_policies(corpus.corpus_yaml(MODS)))))


def page_inputs(req) -> list[CheckInput]:
    """One request of the benchmark's generator as the engine's inputs."""
    P = req.principal
    principal = Principal(
        id=P["id"], roles=list(P["roles"]), attr=P["attr"], policy_version=P["policyVersion"], scope=P["scope"]
    )
    aux = AuxData(jwt=req.jwt) if req.jwt else None
    return [
        CheckInput(
            request_id=req.request_id,
            principal=principal,
            resource=Resource(
                kind=r["kind"], id=r["id"], attr=r["attr"], policy_version=r["policyVersion"], scope=r["scope"]
            ),
            actions=list(actions),
            aux_data=aux,
        )
        for r, actions in req.entries
    ]


def benchmark_pages(n: int = PAGES, seed: int = 2147480001) -> list[list[CheckInput]]:
    from benchmarks.lib import workload

    return [page_inputs(r) for r in workload.build(n, MODS, seed, {"resources": [16, 50]})]


@pytest.fixture(scope="module")
def corpus_packer():
    return TpuEvaluator(corpus_table(), use_jax=False).packer


@pytest.fixture(scope="module")
def small_packer():
    return TpuEvaluator(small.table(), use_jax=False).packer


def pad_args(batch: PackedBatch):
    B_pad = evmod._next_bucket(batch.scope_sp.shape[0])
    BA_pad = evmod._next_bucket(batch.cand_cond.shape[0])
    return (batch, batch.columns, batch.cand_cond, batch.cand_drcond, B_pad, BA_pad)


def same(got, want, where):
    """Bit for bit and dtype for dtype, through dictionaries and None."""
    if isinstance(want, dict):
        assert set(got) == set(want), where
        for k in want:
            same(got[k], want[k], (where, k))
    elif want is None:
        assert got is None, where
    else:
        got, want = np.asarray(got), np.asarray(want)
        assert got.dtype == want.dtype, (where, got.dtype, want.dtype)
        assert got.shape == want.shape, (where, got.shape, want.shape)
        assert np.array_equal(got, want), where


def traced_cut_equals_pad_arrays(batch: PackedBatch) -> evmod._TransferCut:
    import jax
    import jax.numpy as jnp

    args = pad_args(batch)
    want = evmod._pad_arrays(*args)
    stacked, cut, leased = evmod._pad_stack(*args)
    try:
        assert list(stacked) == ["buf"] and stacked["buf"].dtype == np.int32
        assert stacked["buf"].shape == (cut.words,) and leased[0] is stacked["buf"]
        got = jax.jit(lambda buf: evmod._unstack_padded(jnp, cut, {"buf": buf}))(stacked["buf"])
        same(got, want, "traced")
        same(evmod._unstack_padded(np, cut, stacked), want, "host views")
    finally:
        evmod._buffer_pool.release(leased)
    return cut


@pytest.mark.parametrize("n", small.SIZES + (16, 17, 128))
def test_a_small_tables_flights_cut_to_what_pad_arrays_builds(small_packer, n):
    cut = traced_cut_equals_pad_arrays(small_packer.pack(small.inputs(n), EvalParams()))
    assert cut.B_pad == evmod._next_bucket(n) and not cut.lay.list_paths and not cut.lay.ts_paths


@pytest.mark.parametrize("page", range(PAGES))
def test_a_benchmark_pages_flight_cuts_to_what_pad_arrays_builds(corpus_packer, page):
    """Lists, a timestamp path, now(), host predicates, trivial inputs (the
    corpus's salary_record has no policy) between active ones."""
    batch = corpus_packer.pack(benchmark_pages()[page], EvalParams())
    cut = traced_cut_equals_pad_arrays(batch)
    lay = cut.lay
    assert lay.list_paths and lay.ts_paths and lay.pred_ids and lay.has_now
    assert batch.columns.scalars is not None and batch.columns.scalars[0] == lay.paths  # the block copies ran


def test_two_pages_in_one_flight_cut_to_what_pad_arrays_builds(corpus_packer):
    pages = benchmark_pages()
    traced_cut_equals_pad_arrays(corpus_packer.pack(pages[0] + pages[1], EvalParams()))


def synthetic(seed, B, BA, K=2, J=4, D=2, P=3, Tn=1, widths=(4, 8), Q=2, has_now=True, scalars="rows"):
    """A hand-made batch of random bytes in the packer's dtypes. ``scalars``:
    ``rows`` leaves the columns as separate arrays, ``sorted`` keeps matrices
    whose rows are in the layout's order, ``shuffled`` keeps matrices in
    another order (the block copy must not be taken)."""
    rng = np.random.default_rng(seed)

    def i32(*shape):
        return rng.integers(-(2**31), 2**31, size=shape, dtype=np.int64).astype(np.int32)

    def i8(*shape):
        return rng.integers(-128, 128, size=shape, dtype=np.int64).astype(np.int8)

    def flag(*shape):
        return rng.integers(0, 2, size=shape).astype(bool)

    paths = [("resource", "attr", f"p{i}") for i in range(P)]
    cb = ColumnBatch(size=B)
    if scalars == "rows":
        for p in paths:
            cb.tags[p], cb.his[p], cb.los[p], cb.sids[p], cb.nans[p] = i8(B), i32(B), i32(B), i32(B), flag(B)
    else:
        order = list(paths) if scalars == "sorted" else list(reversed(paths))
        M32, MT, MN = i32(3, P, B), i8(P, B), flag(P, B)
        cb.tags.update(zip(order, MT))
        cb.his.update(zip(order, M32[0]))
        cb.los.update(zip(order, M32[1]))
        cb.sids.update(zip(order, M32[2]))
        cb.nans.update(zip(order, MN))
        cb.scalars = (tuple(order), M32, MT, MN)
    for i in range(Tn):
        p = ("resource", "attr", f"t{i}")
        cb.ts_his[p], cb.ts_los[p], cb.ts_states[p] = i32(B), i32(B), i8(B)
    for i, w in enumerate(widths):
        p = ("resource", "attr", f"l{i}")
        cb.list_sids[p], cb.list_states[p] = i32(B, w), i8(B)
    for q in range(Q):
        cb.pred_vals[q], cb.pred_errs[q] = flag(B), flag(B)
    if has_now:
        cb.now_hi, cb.now_lo = np.asarray(i32(1)[0]), np.asarray(i32(1)[0])
    else:
        cb.now_hi = cb.now_lo = None
    return PackedBatch(
        plans=[], columns=cb, ba_input=rng.integers(0, max(B, 1), size=BA).astype(np.int32), ba_action=[],
        cand_cond=i32(BA, K, J), cand_drcond=i32(BA, K, J), cand_effect=i8(BA, K, J), cand_pt=i8(BA, K, J),
        cand_depth=i8(BA, K, J), cand_valid=flag(BA, K, J), scope_sp=i8(B, 2, D), cand_entries=[], K=K, J=J, D=D,
    )


EDGE_CASES = {
    "plain": {},
    "no_list_paths": {"widths": ()},
    "no_timestamp_paths": {"Tn": 0},
    "no_lists_no_timestamps_no_preds": {"widths": (), "Tn": 0, "Q": 0},
    "has_now_false": {"has_now": False},
    "depth_0": {"D": 0},
    "no_scalar_paths": {"P": 0},
    "nothing_but_candidates": {"P": 0, "Tn": 0, "widths": (), "Q": 0, "D": 0, "has_now": False},
    "BA_a_bucket": {"BA": 64},
    "B_and_BA_buckets": {"B": 32, "BA": 32},
    "BA_one_over_a_bucket": {"BA": 65},
    "one_role_one_candidate": {"K": 1, "J": 1},
    "matrices_in_the_layouts_order": {"scalars": "sorted"},
    "matrices_in_another_order": {"scalars": "shuffled"},
    "matrices_and_no_padding": {"scalars": "sorted", "B": 16},
    "matrices_alone": {"scalars": "sorted", "Tn": 0, "widths": (), "Q": 0, "D": 0},
}


@pytest.mark.parametrize("case", sorted(EDGE_CASES))
def test_a_hand_made_edge_case_cuts_to_what_pad_arrays_builds(case):
    kw = {"B": 21, "BA": 37, **EDGE_CASES[case]}
    traced_cut_equals_pad_arrays(synthetic(sorted(EDGE_CASES).index(case), **kw))


def test_a_dirty_recycled_buffer_is_wholly_overwritten(monkeypatch):
    """Every word of the buffer belongs to a section and every section is
    written to its padded end: a buffer that comes back full of ones yields
    the same sections as a fresh one."""
    pool = evmod._BufferPool()
    monkeypatch.setattr(evmod, "_buffer_pool", pool)
    batch = synthetic(99, 21, 37, scalars="sorted")
    args = pad_args(batch)
    stacked, cut, leased = evmod._pad_stack(*args)
    clean = stacked["buf"].copy()
    assert sum(hi - lo for lo, hi, _, _ in cut.sections.values()) == cut.words == clean.size
    leased[0].fill(-1)
    pool.release(leased)
    stacked2, _, leased2 = evmod._pad_stack(*args)
    assert leased2[0] is leased[0] and np.array_equal(stacked2["buf"], clean)


def test_the_cut_is_a_function_of_the_jit_key_alone(corpus_packer):
    """Two flights of one layout and one shape bucket get the same cut object,
    and a cut rebuilt from the key's parts lies where the flight's does."""
    pages = benchmark_pages()
    cuts = {}
    for page in pages:
        args = pad_args(corpus_packer.pack(page, EvalParams()))
        stacked, cut, leased = evmod._pad_stack(*args)
        evmod._buffer_pool.release(leased)
        key = (cut.B_pad, cut.BA_pad, cut.K, cut.J, cut.sig)
        assert cuts.setdefault(key, cut) is cut
        again = evmod._TransferCut(cut.lay, cut.B_pad, cut.BA_pad, cut.K, cut.J)
        assert again.sections == cut.sections and again.words == cut.words
        # the int32 sections first, then the one-byte ones: every host view is aligned
        sizes = [d.itemsize for _, _, d, _ in cut.sections.values()]
        assert sizes == sorted(sizes, reverse=True)
    assert len(cuts) >= 2


def flight_keys(packer, flights):
    for inputs in flights:
        batch = packer.pack(inputs, EvalParams())
        stacked, cut, leased = evmod._pad_stack(*pad_args(batch))
        evmod._buffer_pool.release(leased)
        variant = tuple((gi, None) for gi in range(len(packer.lt.compiler.groups)))
        # since PR 46 the key's (K, J, D) is the table's layout class, whatever this flight holds of its own
        assert (batch.K, batch.J, batch.D) == packer.layout_class.kjd == (cut.K, cut.J, cut.lay.D)
        yield (cut.B_pad, cut.BA_pad, batch.K, batch.J, batch.D, variant, cut.sig), cut


@pytest.mark.parametrize("which", ["small", "corpus"])
def test_a_manifest_entry_rebuilds_the_key_and_a_zero_buffer_of_the_right_length(which, small_packer, corpus_packer):
    packer, flights = (
        (small_packer, [small.inputs(n) for n in small.SIZES]) if which == "small" else (corpus_packer, benchmark_pages())
    )
    packer.lt.compiler.build_groups()
    for key, cut in flight_keys(packer, flights):
        entry = json.loads(json.dumps(evmod._manifest_entry(key, cut.lay)))
        assert set(entry) == {"shape", "depth", "variant", "layout"}  # the cut follows from these: no argument list
        key2, cut2, zeros = evmod._entry_parts(entry)
        assert key2 == key and hash(key2) == hash(key)
        assert cut2.sections == cut.sections and cut2.words == cut.words
        assert list(zeros) == ["buf"]
        buf = zeros["buf"]
        assert buf.dtype == np.int32 and buf.shape == (cut.words,) and not buf.any()


def test_an_entry_with_a_batch_width_that_is_no_bucket_is_refused():
    lay = evmod._StackLayout((), (), (), (), (), 0, False)
    entry = evmod._manifest_entry((18, 16, 1, 1, 0, (), lay.sig), lay)
    with pytest.raises(ValueError):
        evmod._entry_parts(entry)


def device_puts(shard: int) -> tuple[int, float]:
    from cerbos_tpu.observability import metrics

    h = metrics().histogram_vec("cerbos_tpu_batch_device_puts", label="shard").labels(str(shard))
    return h.count, h.sum


@pytest.mark.parametrize("n, calls", [(3, 0), (15, 0), (16, 1), (34, 1), (100, 1)])
def test_device_puts_is_observed_as_1_once_a_device_served_call(n, calls):
    rt = small.table()
    shard = 7400 + n  # a label of this case's own: its series start at zero
    batcher = BatchingEvaluator(TpuEvaluator(rt, use_jax=True, shard_id=shard), shard_id=shard)
    inputs = small.inputs(n)
    try:
        got = batcher.check(inputs)
    finally:
        batcher.close()
    params = EvalParams()
    assert small.sans_source(got) == small.sans_source([check_input(rt, i, params) for i in inputs])
    assert device_puts(shard) == (calls, float(calls))


def test_a_batch_cut_into_chunks_observes_one_put_a_call():
    shard = 7499
    ev = TpuEvaluator(small.table(), use_jax=True, shard_id=shard, pipeline_chunk=32)
    ticket = ev.submit(small.inputs(80), EvalParams())
    assert len(ticket.parts) == 3 and all(h.puts == 1 for _, h in ticket.parts)
    assert device_puts(shard) == (0, 0.0)  # observed where the result is collected, beside the bytes
    ev.collect(ticket)
    assert device_puts(shard) == (3, 3.0)
