"""What the benchmark's own harness reads of the inline route (PR 30), on the
CPU at a tiny size: a traced run of the one-resource mix reports
``inline_share.sidecar`` 100 from a real server's scrape, and the metrics that
read a flight (``cerbos_tpu_batcher_batch_size``, the window wait, the drain
thread's clock) find nothing to read there and are left out of the line, not
raised over. No chip: nothing measured here is a device number.

It lives outside ``tests/benchmark/`` because those files are the benchmark's
own: ``test_bench_e2e.py`` there still expects ``flight_inputs_mean.sidecar``
in this line, which a ``benchmark`` PR has to put right (PERF.md section 7)."""

import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark"))
import benchmark_rig as rig  # noqa: E402

from benchmarks import run  # noqa: E402
from benchmarks.lib import trace_reduce  # noqa: E402

SILENT = {"flight_inputs_mean.sidecar", "window_wait_mean_ms.sidecar", "batcher_cpu_share.sidecar", "batcher_busy_share.sidecar"}


def test_traced_sidecar_run_reads_the_inline_route_and_no_flight(tmp_path, monkeypatch):
    (tmp_path / "root").mkdir()
    (tmp_path / "out").mkdir()
    root = rig.copy_benchmark(str(tmp_path / "root"))
    rig.add_tiny(root)
    # no TPU plane in a CPU trace: the host's plane stands in, to drive the plumbing only
    monkeypatch.setattr(trace_reduce, "DEVICE_PLANE", re.compile(r"^/host:CPU$"))
    res = run.run_cell("tiny.sidecar", 2**31 + 47, 2.0, 1, root=root, require_platform=None, out_dir=str(tmp_path / "out"))
    assert res["correct"] is True and res["failed"] == 0
    m = res["metrics"]
    assert m["inline_share.sidecar"]["value"] == 100.0 and m["inline_share.sidecar"]["unit"] == "%"
    assert m["oracle_share.sidecar"]["value"] == 100.0
    assert m["queue_wait_mean_ms.sidecar"]["value"] < 0.1
    assert m["oracle_eval_mean_ms.sidecar"]["value"] > 0
    assert not set(m) & SILENT
