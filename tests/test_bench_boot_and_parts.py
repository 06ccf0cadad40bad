"""What the benchmark's own harness reads of PR 38's clocks, on the CPU at a
tiny size: a traced run of the pages mix reports all eighteen new metrics from
a real server's scrapes (the six parts of ``pack`` and the two of ``dispatch``
add up to the stages they tile, a flight's bytes are positive, the boot phases
lie under ``setup_s``, the pool wait is a number), and a run of the
one-resource mix reports the boot phases and the pool wait. No chip: nothing
measured here is a device number.

It lives outside ``tests/benchmark/`` like ``test_bench_inline_share.py``.
The new entries of ``BENCHMARK.json`` each list their ``workloads``, so the
tiny cells are appended to those lists here as a cell-adding PR would."""

import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark"))
import benchmark_rig as rig  # noqa: E402

from benchmarks import run  # noqa: E402
from benchmarks.lib import trace_reduce  # noqa: E402

PACK = [f"pack_{p}_mean_ms.pages" for p in ("plan", "gather", "scalars", "lists", "ts", "preds")]
DISPATCH = ["dispatch_call_mean_ms.pages", "dispatch_copy_mean_ms.pages"]
BYTES = ["put_kb_mean.pages", "fetch_kb_mean.pages"]
BOOT = [f"boot_{p}_s" for p in ("import", "load", "compile", "table", "lower", "ready")]
NEW = PACK + DISPATCH + BYTES + BOOT + ["pool_wait_mean_ms.pages", "pool_wait_mean_ms.sidecar"]
PUTS = "device_puts_mean.pages"  # PR 40: arrays a device-served call hands the device


def tiny_root(tmp_path) -> str:
    (tmp_path / "root").mkdir()
    (tmp_path / "out").mkdir()
    root = rig.copy_benchmark(str(tmp_path / "root"))
    rig.add_tiny(root)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert len(NEW) == 18 and set(NEW) <= set(entries)
    for name in NEW + [PUTS]:
        for twin, tiny in (("classic-800.pages", "tiny.pages"), ("classic-800.sidecar", "tiny.sidecar")):
            if twin in entries[name]["workloads"]:
                entries[name]["workloads"].append(tiny)
    with open(path, "w") as f:
        json.dump(manifest, f)
    return root


def setup_s(out_dir) -> float:
    """A traced run's line holds the per-layer metrics alone; its set-up is in the run's own file."""
    with open(os.path.join(str(out_dir), "run.json")) as f:
        return json.load(f)["gen"]["setup_s"]


def value(metrics: dict, name: str) -> float:
    assert name in metrics, (name, sorted(metrics))
    assert isinstance(metrics[name]["value"], float), (name, metrics[name])
    return metrics[name]["value"]


def test_traced_pages_run_reads_all_eighteen(tmp_path, monkeypatch):
    root = tiny_root(tmp_path)
    # no TPU plane in a CPU trace: the host's plane stands in, to drive the plumbing only
    monkeypatch.setattr(trace_reduce, "DEVICE_PLANE", re.compile(r"^/host:CPU$"))
    res = run.run_cell("tiny.pages", 2**31 + 38, 2.0, 1, root=root, require_platform=None, out_dir=str(tmp_path / "out"))
    assert res["correct"] is True and res["failed"] == 0
    m = res["metrics"]
    got = {name: value(m, name) for name in NEW if not name.endswith(".sidecar")}
    assert "pool_wait_mean_ms.sidecar" not in m
    # booked from the same readings of one cursor, observed once a flight each: the means add up
    assert sum(got[n] for n in PACK) == pytest.approx(value(m, "pack_mean_ms.pages"), rel=1e-6)
    assert sum(got[n] for n in DISPATCH) == pytest.approx(value(m, "dispatch_mean_ms.pages"), rel=1e-6)
    assert all(got[n] > 0 for n in PACK + DISPATCH + BYTES)
    assert m["put_kb_mean.pages"]["unit"] == "KB" and got["put_kb_mean.pages"] > got["fetch_kb_mean.pages"]
    assert value(m, PUTS) == 1.0 and m[PUTS]["unit"] == "puts"  # one staging buffer a call
    assert 0 < got["pool_wait_mean_ms.pages"] < 250
    boot = [got[n] for n in BOOT[:-1]]
    assert all(s > 0 for s in boot) and sum(boot) <= got["boot_ready_s"] < setup_s(tmp_path / "out")
    # PR 49: every page read and every reply written by the listener's native codec
    assert value(m, "wire_native_share.pages") == 100.0 and "wire_native_share.sidecar" not in m


def test_traced_sidecar_run_reads_the_boot_phases_and_the_pool_wait(tmp_path, monkeypatch):
    root = tiny_root(tmp_path)
    monkeypatch.setattr(trace_reduce, "DEVICE_PLANE", re.compile(r"^/host:CPU$"))
    res = run.run_cell("tiny.sidecar", 2**31 + 39, 2.0, 1, root=root, require_platform=None, out_dir=str(tmp_path / "out"))
    assert res["correct"] is True and res["failed"] == 0
    m = res["metrics"]
    boot = [value(m, n) for n in BOOT]
    assert sum(boot[:-1]) <= boot[-1] < setup_s(tmp_path / "out")
    assert 0 < value(m, "pool_wait_mean_ms.sidecar") < 250
    assert value(m, "wire_native_share.sidecar") == 100.0 and "wire_native_share.pages" not in m
    assert not set(m) & set(PACK + DISPATCH + BYTES + ["pool_wait_mean_ms.pages", PUTS])
