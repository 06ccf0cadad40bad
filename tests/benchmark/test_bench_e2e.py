"""The harness end to end on the CPU at a tiny size: a sound run, a run with
the timed path broken underneath (the control), a traced run, and the two ways
the command must fail without a result. No chip: ``require_platform=None``
skips the look for one, and nothing measured here is a device number."""

import json
import os
import re
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchmark_rig as rig  # noqa: E402

from benchmarks import run  # noqa: E402
from benchmarks.lib import trace_reduce  # noqa: E402
from benchmarks.tools.control import stale_policies  # noqa: E402

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = rig.copy_benchmark(str(tmp_path_factory.mktemp("bench_root")))
    rig.add_tiny(root)
    return root


def test_sound_run_is_correct_and_reports_the_end_to_end_metrics(root, tmp_path):
    res = run.run_cell("tiny.sidecar", 2**31 + 41, 2.0, 0, root=root, require_platform=None, out_dir=str(tmp_path))
    assert set(res) == RESULT_KEYS
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 160
    assert set(res["metrics"]) == {"check_p50_ms", "setup_s"}
    assert all(m["value"] > 0 and m["unit"] for m in res["metrics"].values())
    assert set(res["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert os.path.getsize(os.path.join(str(tmp_path), "failures.jsonl")) == 0
    for kept in ("slow.json", "pressure.json", "server.stderr", "metrics_after.txt"):
        assert os.path.exists(os.path.join(str(tmp_path), kept))


def test_stale_policies_under_the_timed_path_come_out_not_correct(root, tmp_path):
    """The control, at a size a test can hold: the server answers from an
    older policy set than the configuration's, the reference does not."""
    res = run.run_cell(
        "tiny.sidecar", 2**31 + 42, 2.0, 0, root=root, require_platform=None, out_dir=str(tmp_path),
        policy_transform=stale_policies,
    )
    assert res["correct"] is False and res["failed"] > 0
    with open(os.path.join(str(tmp_path), "failures.jsonl")) as f:
        reasons = [json.loads(line)["reason"] for line in f]
    assert len(reasons) == res["failed"] and all(r.startswith("wrong reply") for r in reasons)


def test_traced_run_reports_the_per_layer_metrics_and_a_breakdown(root, tmp_path, monkeypatch):
    # no TPU plane in a CPU trace: the host's plane stands in, to drive the plumbing only
    monkeypatch.setattr(trace_reduce, "DEVICE_PLANE", re.compile(r"^/host:CPU$"))
    res = run.run_cell("tiny.sidecar", 2**31 + 43, 2.0, 1, root=root, require_platform=None, out_dir=str(tmp_path))
    assert set(res) == RESULT_KEYS | {"breakdown"}
    assert res["correct"] is True
    # the window is the span of the traced traffic (a 2 s slice and the device touch), not the 13.5 s capture
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"] and 1.5 < res["device"]["window_s"] < 8
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 1 <= len(res["breakdown"]["device_ops"]) <= 10 and len(res["breakdown"]["idle_gaps"]) <= 10
    names = set(res["metrics"])
    assert {"gen_late_p99_ms.sidecar", "check_p99_ms.sidecar", "admission_mean_ms.sidecar", "oracle_share.sidecar",
            "compiles_in_window.sidecar", "brownout_stage_max.sidecar", "flight_inputs_mean.sidecar"} <= names
    assert not names & {"check_p50_ms", "setup_s"}
    assert res["metrics"]["oracle_share.sidecar"]["value"] == 100.0


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", *args], cwd=cwd, env=env, capture_output=True, text=True, timeout=300
    )


def _has_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return "correct" in json.loads(lines[-1])
    except ValueError:
        return False


def test_a_server_without_a_tpu_fails_the_run_without_a_result():
    p = _cli(rig.REPO, "--workload", "classic-800.sidecar", "--seed", "3", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and not _has_result(p.stdout)
    assert "platform='cpu'" in p.stderr


def test_a_directory_without_the_program_fails_the_run_without_a_result(tmp_path):
    root = rig.copy_benchmark(str(tmp_path))
    os.makedirs(os.path.join(root, "tests"))
    p = _cli(root, "--workload", "classic-800.sidecar", "--seed", "3", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and not _has_result(p.stdout)
