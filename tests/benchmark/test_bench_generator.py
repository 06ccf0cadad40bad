"""The load generator and what the harness makes of its rows: latency from due
time, what counts as failed, and that both are functions of the seed alone."""

import os
import sys
from datetime import datetime, timedelta, timezone

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchmark_rig as rig  # noqa: E402

from benchmarks.lib import loadgen, session, spec, workload  # noqa: E402

SIDECAR = {"resources": [1, 1]}
PAGES = {"resources": [16, 50]}


def test_schedule_is_a_function_of_the_seed_alone():
    a = workload.poisson_schedule(50, 10, seed=2**31 + 5)
    assert a == workload.poisson_schedule(50, 10, seed=2**31 + 5)
    assert a != workload.poisson_schedule(50, 10, seed=2**31 + 6)
    assert len(a) == 500 and a == sorted(a) and 0 <= a[0] and a[-1] < 10
    # every seed gets the same number of arrivals
    assert len(workload.poisson_schedule(50, 10, seed=1)) == 500


@pytest.mark.parametrize("shape", [SIDECAR, PAGES], ids=["sidecar", "pages"])
def test_requests_are_a_function_of_the_seed_alone(shape):
    def wires(seed):
        reqs = workload.build(40, 100, seed, shape)
        workload.serialize(reqs)
        return [r.wire for r in reqs]

    assert wires(2**31 + 9) == wires(2**31 + 9)
    assert wires(2**31 + 9) != wires(2**31 + 10)


def test_every_seed_gets_the_same_page_sizes_in_another_order():
    a = [len(r.entries) for r in workload.build(70, 100, 3, PAGES)]
    b = [len(r.entries) for r in workload.build(70, 100, 4, PAGES)]
    assert sorted(a) == sorted(b) and a != b
    assert min(a) == 16 and max(a) == 50


def _cell(tmp_path, name):
    root = rig.copy_benchmark(str(tmp_path))
    rig.add_tiny(root)
    return spec.Cell(root, name)


def _run_open(pdp, reqs, due, deadline_s=5.0):
    gen = loadgen.Generator()
    try:
        gen.load([r.wire for r in reqs], pdp.target, connections=2)
        return gen.run({"kind": "open_poisson", "first": 0, "due": due, "deadline_s": deadline_s})
    finally:
        gen.close()


def _rows(res, reqs):
    now = datetime.now(timezone.utc)
    return session.outcome_rows(res, {r.index: r for r in reqs}, now - timedelta(seconds=5), now + timedelta(seconds=5))


def test_a_stall_shows_in_the_latency_of_the_requests_behind_it(tmp_path):
    reqs = workload.build(12, rig.TINY_MODS, 5, SIDECAR)
    workload.serialize(reqs)
    due = [0.2 * k for k in range(12)]  # one every 200 ms
    pdp = rig.FakePdp(stall={2: 1.0})  # the server stops for a second at request 2
    try:
        res = _run_open(pdp, reqs, due)
    finally:
        pdp.close()
    rows = _rows(res, reqs)
    lat = [r["done"] - r["due"] for r in rows]
    # timed from due time: request 3 was due 200 ms after request 2 and waited
    # out the rest of the stall, request 4 200 ms less, and so on (the limits
    # leave room for a test host that is busy with other tests)
    assert lat[2] >= 1.0
    assert 0.75 <= lat[3] <= 1.3 and 0.55 <= lat[4] <= 1.1 and lat[3] > lat[4] > lat[5]
    assert lat[0] < 0.4 and lat[11] < 0.4
    # the generator kept to its schedule through the stall: request 3 went out
    # while request 2 was still unanswered
    assert rows[3]["sent"] < rows[2]["done"] - 0.5 and max(r["sent"] - r["due"] for r in rows) < 0.3
    # late is not failed
    assert all(r["reason"] is None for r in rows)
    g = session.generator_stats(_cell(tmp_path, "tiny.sidecar"), rows, seconds=0.9)
    assert g["attempted"] == 12 and g["failed"] == 0
    assert g["backlog_at_close"] >= 1  # replies still due when the window closed


def test_refused_wrong_and_overdue_replies_fail_and_late_ones_do_not(tmp_path):
    reqs = workload.build(8, rig.TINY_MODS, 6, PAGES)
    workload.serialize(reqs)
    due = [0.02 * k for k in range(8)]
    # 1 is refused, 3 answers with one effect flipped, 5 stalls past the deadline (0.4 s)
    pdp = rig.FakePdp(stall={5: 0.8}, refuse=[1], falsify=[3])
    try:
        res = _run_open(pdp, reqs, due, deadline_s=0.4)
    finally:
        pdp.close()
    rows = _rows(res, reqs)
    reasons = {r["index"]: r["reason"] for r in rows}
    assert reasons[1].startswith("status RESOURCE_EXHAUSTED")
    assert reasons[3].startswith("wrong reply")
    assert reasons[5].startswith("status DEADLINE_EXCEEDED")
    assert reasons[0] is None and reasons[2] is None and reasons[4] is None
    g = session.generator_stats(_cell(tmp_path, "tiny.pages"), rows, seconds=1.0)
    assert g["failed"] == sum(1 for r in reasons.values() if r) and g["wrong"] == 1
    # failures are left out of the latency sample and of the decisions completed
    assert g["decisions_per_s"] == sum(r["decisions"] for r in rows if r["reason"] is None)


def test_the_generator_knows_no_other_kind_of_traffic():
    with pytest.raises(ValueError, match="closed"):
        loadgen.Generator().run({"kind": "closed"})


def test_longest_stall_is_the_longest_wait_with_no_reply():
    rows = [
        {"due": 0.00, "done": 0.01},
        {"due": 0.10, "done": 0.11},  # nothing was waiting between 0.01 and 0.10: not a stall
        {"due": 0.12, "done": 0.50},  # waited from 0.12 (its due time, after the last reply) to 0.50
        {"due": 0.20, "done": 0.51},
        {"due": 0.60, "done": None},  # never answered: no completion to measure to
    ]
    stall, at = session.longest_stall(rows)
    assert stall == pytest.approx(0.38) and at == pytest.approx(0.12)
    assert session.longest_stall([]) == (0.0, 0.0)
