"""The front-door pool under the harness, on the CPU at a tiny size: the
configuration ``classic-800-pool4`` cut to three name-mods and added to the
rig's copy as files, then its two cells run as ``benchmarks/run.py`` runs
them: a sound run of the fan-in mix, the control under the pool (a front end
must not be able to hide the owner's table), a traced run whose line holds
every metric the cell reads, and the pages mix, which has to reach the device
through the pool (marked ``slow``: out of tier-1). Four server boots. No chip: ``require_platform=None`` skips
the look for one, and nothing measured here is a device number."""

import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchmark_rig as rig  # noqa: E402

from benchmarks import run  # noqa: E402
from benchmarks.lib import prom, spec, trace_reduce  # noqa: E402
from benchmarks.tools.control import stale_policies  # noqa: E402

POOL, TINY = "classic-800-pool4", "tiny-pool4"
RATES = {"sidecar-fanin": 100.0, "pages-fanin": 15.0}
SECONDS = 3.0
FANIN_METRICS = {
    f"{base}.sidecar-fanin"
    for base in ("flight_inputs_mean", "window_wait_mean_ms", "batcher_busy_share", "batcher_cpu_share",
                 "ipc_encode_mean_ms", "ipc_transit_mean_ms", "ipc_return_mean_ms", "ipc_rtt_mean_ms", "frontend_share_max")
}


def add_tiny_pool(root: str) -> None:
    """``tiny-pool4``: the pool's configuration at 3 name-mods, a rate file for
    each of its mixes, its two cells, and their names wherever the real cells'
    names stand (the end-to-end metrics and the per-layer metrics that list the
    one cell they are for). No file that is there is edited."""
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(bench, "configs", f"{POOL}.json")) as f:
        cfg = json.load(f)
    cfg["name"] = TINY
    cfg["corpus"]["mods"] = rig.TINY_MODS
    with open(os.path.join(bench, "configs", f"{TINY}.json"), "w") as f:
        json.dump(cfg, f)
    for mix, rate in RATES.items():
        with open(os.path.join(bench, "traffic", "rates", f"{TINY}.{mix}.json"), "w") as f:
            json.dump({"rate": rate}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": TINY, "source": cfg["source"] + " (test)", "file": f"benchmarks/configs/{TINY}.json", "reduced": [], "why": "test"}
    )
    for mix in RATES:
        twin, name = f"{POOL}.{mix}", f"{TINY}.{mix}"
        manifest["workloads"].append({"name": name, "config": TINY, "traffic": mix, "chips": 1, "why": "test"})
        for m in manifest["end_to_end"] + manifest["per_layer"]:
            if twin in m.get("workloads", []):
                m["workloads"].append(name)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = rig.copy_benchmark(str(tmp_path_factory.mktemp("bench_pool_root")))
    add_tiny_pool(root)
    return root


def _scrape(out_dir) -> prom.Scrape:
    with open(os.path.join(str(out_dir), "metrics_after.txt")) as f:
        return prom.parse(f.read())


def test_the_tiny_pool_cells_read_what_the_real_ones_read(root):
    for mix in RATES:
        tiny, real = spec.Cell(root, f"{TINY}.{mix}"), spec.Cell(root, f"{POOL}.{mix}")
        assert [m["name"] for m in tiny.per_layer] == [m["name"] for m in real.per_layer]
        assert tiny.config["assumed"]["server"]["server.frontends"]["value"] == 3 and tiny.config["layout"]["processes"] == 4
    names = {m["name"] for m in spec.Cell(root, f"{TINY}.sidecar-fanin").per_layer}
    assert FANIN_METRICS <= names and "inline_share.sidecar" in names
    # the four that fell silent in the single process are read there alone, and here through their twins
    assert not names & {"flight_inputs_mean.sidecar", "window_wait_mean_ms.sidecar", "batcher_busy_share.sidecar", "batcher_cpu_share.sidecar"}


def test_fanin_run_is_correct_and_spreads_over_the_front_ends(root, tmp_path):
    res = run.run_cell(f"{TINY}.sidecar-fanin", 2**31 + 51, SECONDS, 0, root=root, require_platform=None, out_dir=str(tmp_path))
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 300
    assert set(res["metrics"]) == {"check_p50_ms", "setup_s"}
    after = _scrape(tmp_path)
    handled = {dict(labels).get("worker"): v for (n, labels), v in after.items() if n == "cerbos_tpu_request_handler_seconds_count"}
    assert sum(1 for w, v in handled.items() if w and w.startswith("fe") and v > 0) >= 2, handled
    assert sum(handled.values()) >= 300
    # the owner's door is counted: every ticket queued, none answered inline
    assert prom.total(after, "cerbos_tpu_batcher_checks_total", worker="batcher", route="queued") >= 300
    assert prom.total(after, "cerbos_tpu_batcher_checks_total", route="inline") == 0
    # one scrape of the pool is valid exposition: no sample names a label twice (the owner's per-front-end series did)
    with open(os.path.join(str(tmp_path), "metrics_after.txt")) as f:
        twice = [line for line in f if len(re.findall(r'[{,]worker="', line)) > 1]
    assert not twice, twice[:3]
    assert prom.total(after, "cerbos_tpu_ipc_enqueue_seconds_count", worker="batcher") >= 300


def test_stale_policies_under_the_pool_come_out_not_correct(root, tmp_path):
    """The control: the whole pool boots with the stale set, so the owner's
    table is stale. No front end's copy can put that right."""
    res = run.run_cell(
        f"{TINY}.sidecar-fanin", 2**31 + 52, SECONDS, 0, root=root, require_platform=None, out_dir=str(tmp_path),
        policy_transform=stale_policies,
    )
    assert res["correct"] is False and res["failed"] > 0
    with open(os.path.join(str(tmp_path), "failures.jsonl")) as f:
        reasons = [json.loads(line)["reason"] for line in f]
    assert len(reasons) == res["failed"] and all(r.startswith("wrong reply") for r in reasons)


def test_traced_fanin_run_reads_every_metric_of_the_pool(root, tmp_path, monkeypatch):
    # no TPU plane in a CPU trace: the host's plane stands in, to drive the plumbing only
    monkeypatch.setattr(trace_reduce, "DEVICE_PLANE", re.compile(r"^/host:CPU$"))
    res = run.run_cell(f"{TINY}.sidecar-fanin", 2**31 + 53, SECONDS, 1, root=root, require_platform=None, out_dir=str(tmp_path))
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["busy_s"] > 0 and res["breakdown"]["device_ops"]  # the capture came from the owner, through a front end
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert FANIN_METRICS <= set(m) and all(isinstance(m[k], float) for k in FANIN_METRICS)
    assert m["flight_inputs_mean.sidecar-fanin"] >= 1.0
    assert 33.0 <= m["frontend_share_max.sidecar-fanin"] <= 100.0
    assert m["oracle_share.sidecar"] == 100.0 and m["inline_share.sidecar"] == 0.0
    assert m["ipc_rtt_mean_ms.sidecar-fanin"] > m["ipc_transit_mean_ms.sidecar-fanin"] > 0
    assert not set(m) & {"check_p50_ms", "setup_s"}


@pytest.mark.slow  # its layouts compile for most of a minute on a CPU: with it tier-1's longest worker ran 134 to 169 s, over ISSUE 32's 150
def test_pages_through_the_pool_are_device_served(root, tmp_path):
    res = run.run_cell(f"{TINY}.pages-fanin", 2**31 + 54, SECONDS, 0, root=root, require_platform=None, out_dir=str(tmp_path))
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 45
    assert set(res["metrics"]) == {"page_p50_ms", "setup_s"}
    after = _scrape(tmp_path)
    assert prom.total(after, "cerbos_tpu_decision_source_total", worker="batcher", source="device") > 0
    assert prom.total(after, "cerbos_tpu_decision_source_total", source="oracle") == 0
