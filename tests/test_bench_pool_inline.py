"""What the benchmark's own harness reads of a pool whose front ends answer
one-resource checks themselves (PR 33), on the CPU at a tiny size: the fan-in
cell's traced run is correct, ``inline_share.sidecar`` says the front ends
answered, no fallback is counted, the owner sees the traced run's four pages
and little else, the eight metrics that read the ticket's way and the owner's
flights find nothing to read and are left out of the line, and the control
(the whole pool booted with stale policies) still comes out not correct: a
front end's copy is the owner's, so it cannot hide the owner's table. Two pool
boots. No chip: nothing measured here is a device number.

It lives outside ``tests/benchmark/`` because those files are the benchmark's
own: ``test_bench_pool.py`` there still expects ``route="inline"`` 0 and a
number for each of the eight, which a ``benchmark`` PR has to put right
(PERF.md section 7 (10), ROADMAP B0)."""

import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark"))
import benchmark_rig as rig  # noqa: E402
from test_bench_pool import SECONDS, TINY, add_tiny_pool  # noqa: E402

from benchmarks import run  # noqa: E402
from benchmarks.lib import prom, trace_reduce  # noqa: E402
from benchmarks.tools.control import stale_policies  # noqa: E402

CELL = f"{TINY}.sidecar-fanin"
SILENT = {
    f"{base}.sidecar-fanin"
    for base in ("ipc_encode_mean_ms", "ipc_transit_mean_ms", "ipc_return_mean_ms", "ipc_rtt_mean_ms",
                 "flight_inputs_mean", "window_wait_mean_ms", "batcher_busy_share", "batcher_cpu_share")
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = rig.copy_benchmark(str(tmp_path_factory.mktemp("bench_pool_inline_root")))
    add_tiny_pool(root)
    return root


@pytest.fixture(scope="module")
def traced(root, tmp_path_factory):
    """ONE traced run of the tiny fan-in cell: its result and its last scrape."""
    out = str(tmp_path_factory.mktemp("bench_pool_inline_out"))
    with pytest.MonkeyPatch.context() as mp:
        # no TPU plane in a CPU trace: the host's plane stands in, to drive the plumbing only
        mp.setattr(trace_reduce, "DEVICE_PLANE", re.compile(r"^/host:CPU$"))
        res = run.run_cell(CELL, 2**31 + 61, SECONDS, 1, root=root, require_platform=None, out_dir=out)
    with open(os.path.join(out, "metrics_after.txt")) as f:
        return res, prom.parse(f.read())


def test_the_fanin_run_is_correct_and_the_front_ends_answered_it(traced):
    res, _ = traced
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 300
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["inline_share.sidecar"] >= 95.0
    assert m["oracle_share.sidecar"] == 100.0  # a front end's decisions are counted where it makes them
    assert m["oracle_eval_mean_ms.sidecar"] > 0  # observed on the request's thread, in the front end
    assert m["queue_wait_mean_ms.sidecar"] < 0.1  # booked, as a wait of nothing
    assert m["admission_mean_ms.sidecar"] > 0 and m["handler_mean_ms.sidecar"] > 0
    assert 33.0 <= m["frontend_share_max.sidecar-fanin"] <= 100.0
    assert res["device"]["busy_s"] > 0 and res["breakdown"]["device_ops"]  # the four pages still reach the owner


def test_no_fallback_is_counted_and_the_owner_sees_the_pages_alone(traced):
    _, after = traced
    assert prom.total(after, "cerbos_tpu_batcher_oracle_fallbacks_total") == 0
    inline = {dict(labels).get("worker"): v for (n, labels), v in after.items()
              if n == "cerbos_tpu_batcher_checks_total" and dict(labels).get("route") == "inline"}
    assert sum(inline.values()) >= 600 and all(w.startswith("fe") for w in inline)  # warm replay, window, traced replay
    assert sum(1 for v in inline.values() if v > 0) >= 2, inline
    # at the owner's door: the traced run's four 32-resource pages, and whatever a front end sent before its first attach settled
    assert prom.total(after, "cerbos_tpu_batcher_checks_total", worker="batcher", route="inline") == 0
    assert 4 <= prom.total(after, "cerbos_tpu_batcher_checks_total", worker="batcher", route="queued") <= 20


def test_the_metrics_of_the_tickets_way_and_of_the_owners_flights_read_nothing(traced):
    res, _ = traced
    assert not set(res["metrics"]) & SILENT
    assert not set(res["metrics"]) & {"check_p50_ms", "setup_s"}


def test_stale_policies_under_the_pool_still_come_out_not_correct(root, tmp_path):
    """The control: every process of the pool boots with the stale set, so the
    front ends' identity IS the owner's and they answer from it: wrong."""
    res = run.run_cell(CELL, 2**31 + 62, SECONDS, 0, root=root, require_platform=None, out_dir=str(tmp_path),
                       policy_transform=stale_policies)
    assert res["correct"] is False and res["failed"] > 0
    with open(os.path.join(str(tmp_path), "failures.jsonl")) as f:
        reasons = [json.loads(line)["reason"] for line in f]
    assert len(reasons) == res["failed"] and all(r.startswith("wrong reply") for r in reasons)
