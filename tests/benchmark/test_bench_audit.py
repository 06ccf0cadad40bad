"""The audited deployment (``classic-800-audit``) under the harness, at a tiny
size on the CPU: its audit block through ``Session`` with the file under the
test's own directory, device-served pages and oracle-served singles both, then
the log's plain reading (``benchmarks/tools/audit_check.py``) over what the
server left behind; the stale-policies control must come out wrong in the LOG
as it does in the replies; and the traced line reads a number for each of the
cell's seven metrics. No chip: nothing measured here is a device number."""

import json
import os
import re
import sys
from datetime import datetime, timedelta, timezone

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchmark_rig as rig  # noqa: E402

from benchmarks import run  # noqa: E402
from benchmarks.lib import spec, trace_reduce, workload  # noqa: E402
from benchmarks.tools import audit_check  # noqa: E402
from benchmarks.tools.control import stale_policies  # noqa: E402

SEVEN = {
    "back_audit_mean_ms.pages", "audit_write_mean_ms.pages", "audit_entry_kb_mean.pages", "audit_writer_busy_share.pages",
    "audit_queue_depth_max.pages", "audit_lost.pages", "audit_logged_share.pages",
}


def add_tiny_audit(root: str, log_path: str) -> None:
    """``tiny-audit``: the audited configuration at 3 name-mods, its file under
    ``log_path`` and rotated every megabyte (nothing deleted), with a cell on
    each mix, added as ``benchmark_rig.add_tiny`` adds its own."""
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(bench, "configs", "classic-800-audit.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "tiny-audit"
    cfg["corpus"]["mods"] = rig.TINY_MODS
    server = cfg["assumed"]["server"]
    server["audit.file.path"]["value"] = log_path
    server["audit.file.logRotation.maxFileSizeMB"]["value"] = 1
    server["audit.file.logRotation.maxFileCount"]["value"] = 100
    with open(os.path.join(bench, "configs", "tiny-audit.json"), "w") as f:
        json.dump(cfg, f)
    for mix, rate in (("pages", 40.0), ("sidecar", 80.0)):
        with open(os.path.join(bench, "traffic", "rates", f"tiny-audit.{mix}.json"), "w") as f:
            json.dump({"rate": rate}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "tiny-audit", "source": cfg["source"], "file": "benchmarks/configs/tiny-audit.json", "reduced": [], "why": "test"}
    )
    for mix, metric in (("pages", "page_p50_ms"), ("sidecar", "check_p50_ms")):
        manifest["workloads"].append({"name": f"tiny-audit.{mix}", "config": "tiny-audit", "traffic": mix, "chips": 1, "why": "test"})
        for m in manifest["end_to_end"]:
            if m["name"] == metric:
                m["workloads"].append(f"tiny-audit.{mix}")
    for m in manifest["per_layer"]:
        if "classic-800-audit.pages" in m.get("workloads", []):
            m["workloads"].append("tiny-audit.pages")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)


@pytest.fixture()
def root(tmp_path):
    os.makedirs(tmp_path / "bench_root")
    root = rig.copy_benchmark(str(tmp_path / "bench_root"))
    add_tiny_audit(root, str(tmp_path / "audit" / "pdp.log"))  # the directory is the backend's to make
    return root


def test_the_cell_is_in_the_manifest_with_the_issues_parameters():
    cell = spec.Cell(rig.REPO, "classic-800-audit.pages")
    assert (cell.config_name, cell.traffic_name, cell.chips, cell.pair) == ("classic-800-audit", "pages", 1, {"rate": 40})
    assert cell.traffic["connections"] == 4 and cell.traffic["request"] == {"resources": [16, 50]}
    assert cell.config["reduced"] == [] and cell.config["corpus"] == {"generator": "classic", "mods": 100}
    server = {k: v["value"] for k, v in cell.config["assumed"]["server"].items()}
    assert server == {
        "engine.tpu.requestTimeoutMs": 600000, "audit.enabled": True, "audit.backend": "file",
        "audit.accessLogsEnabled": True, "audit.decisionLogsEnabled": True,
        "audit.file.path": "${TMPDIR:/tmp}/cerbos-tpu-bench-audit.log",
        "audit.file.logRotation.maxFileSizeMB": 64, "audit.file.logRotation.maxFileCount": 2,
    }
    assert all(v["why"] for v in cell.config["assumed"]["server"].values())
    assert [m["name"] for m in cell.end_to_end] == ["page_p50_ms", "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert SEVEN <= names
    # every .pages metric of the twin cell follows by the manifest's rule, and the seven are this cell's alone
    twin = {m["name"] for m in spec.Cell(rig.REPO, "classic-800.pages").per_layer}
    assert names - twin == SEVEN and twin <= names
    with open(os.path.join(rig.REPO, "benchmarks", "configs", "classic-800.json")) as f:
        base = json.load(f)
    for key in ("corpus", "layout", "reduced"):
        assert cell.config[key] == base[key]
    assert cell.config["guarantees"][: len(base["guarantees"])] == base["guarantees"]


@pytest.mark.parametrize("tmpdir", ["a", "b", None])
def test_each_side_of_a_comparison_logs_under_the_tmpdir_it_was_given(tmpdir, tmp_path, monkeypatch):
    """Parent and change are measured in turn on one machine, each with a
    TMPDIR of its own: the server (its config file's ``${VAR:default}``, which
    ``ServerProc`` writes the value into) and ``audit_check`` read one path,
    and another under another TMPDIR."""
    import yaml

    from cerbos_tpu.config import Config

    if tmpdir is None:
        monkeypatch.delenv("TMPDIR", raising=False)
        want = "/tmp/cerbos-tpu-bench-audit.log"
    else:
        monkeypatch.setenv("TMPDIR", str(tmp_path / tmpdir))
        want = str(tmp_path / tmpdir / "cerbos-tpu-bench-audit.log")
    value = spec.Cell(rig.REPO, "classic-800-audit.pages").config["assumed"]["server"]["audit.file.path"]["value"]
    (tmp_path / "cerbos.yaml").write_text(yaml.safe_dump({"audit": {"file": {"path": value}}}))
    assert Config.load(str(tmp_path / "cerbos.yaml")).data["audit"]["file"]["path"] == want
    assert audit_check.expand(value) == want


def test_traced_pages_run_logs_every_page_and_reads_the_seven(root, tmp_path, monkeypatch):
    # no TPU plane in a CPU trace: the host's plane stands in, to drive the plumbing only
    monkeypatch.setattr(trace_reduce, "DEVICE_PLANE", re.compile(r"^/host:CPU$"))
    out = str(tmp_path / "out")
    res = run.run_cell("tiny-audit.pages", 2**31 + 61, 2.0, 1, root=root, require_platform=None, out_dir=out)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 80
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert SEVEN <= set(got), SEVEN - set(got)
    assert got["audit_lost.pages"] == 0 and got["audit_logged_share.pages"] == pytest.approx(100.0)
    assert got["back_audit_mean_ms.pages"] > 0 and got["audit_write_mean_ms.pages"] > 0
    assert 5 < got["audit_entry_kb_mean.pages"] < 60 and 0 < got["audit_writer_busy_share.pages"] < 100
    # the three parts tile the stage, on the line as in the program
    parts = got["back_wake_mean_ms.pages"] + got["back_audit_mean_ms.pages"] + got["back_encode_mean_ms.pages"]
    assert parts == pytest.approx(got["reply_encode_mean_ms.pages"], rel=0.01)
    assert got["oracle_share.pages"] == 0.0 and got["inline_share.pages"] == 0.0  # every page device-served
    report = audit_check.check_run(root, out)
    assert report["ok"], report
    assert report["decision_entries"] == 80 and report["missing"] == report["twice"] == report["wrong_effect"] == 0
    assert report["lines"] > report["window_entries"] >= 160  # the warm replay and the traced replay are in the files too
    assert len(report["files"]) > 1  # rotated at 1 MB, and read across the files
    assert all(os.path.getsize(p) <= (1 << 20) for p in audit_check.log_files(str(tmp_path / "audit" / "pdp.log")))


def test_singles_served_by_the_oracle_are_logged_one_entry_each(root, tmp_path):
    out = str(tmp_path / "out")
    res = run.run_cell("tiny-audit.sidecar", 2**31 + 62, 2.0, 0, root=root, require_platform=None, out_dir=out)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 160
    report = audit_check.check_run(root, out)
    assert report["ok"], report
    assert report["decision_entries"] == 160 and report["access_entries"] >= 160


def test_stale_policies_come_out_wrong_in_the_log_as_in_the_replies(root, tmp_path):
    out = str(tmp_path / "out")
    res = run.run_cell(
        "tiny-audit.sidecar", 2**31 + 63, 2.0, 0, root=root, require_platform=None, out_dir=out,
        policy_transform=stale_policies,
    )
    assert res["correct"] is False and res["failed"] > 0
    report = audit_check.check_run(root, out)
    assert not report["ok"]
    # the log records the replies: as many entries with another effect than the reference's as wrong replies
    assert report["wrong_effect"] == res["failed"] and report["missing"] == report["twice"] == report["wrong_input"] == 0
    assert any(x.startswith("wrong_effect") for x in report["examples"])


# -- the plain reading itself, on logs written here ---------------------------

NOW = datetime(2026, 7, 1, tzinfo=timezone.utc)


def entries_for(reqs, at=NOW):
    """What a sound server logs for ``reqs``: a decision entry and an access entry each."""
    out = []
    for k, req in enumerate(reqs):
        call = f"call{k}"
        inputs = [
            {"requestId": req.request_id, "resource": {k2: v for k2, v in res.items() if v not in ("", {}, [])},
             "principal": {k2: v for k2, v in req.principal.items() if v not in ("", {}, [])}, "actions": list(actions)}
            for res, actions in req.entries
        ]
        outputs = [
            {"requestId": req.request_id, "resourceId": res["id"], "actions": {a: {"effect": e} for a, e in eff.items()}}
            for (res, _), eff in zip(req.entries, req.expected(at))
        ]
        stamp = (at + timedelta(milliseconds=k)).isoformat()
        out.append({"callId": call, "timestamp": stamp, "kind": "decision", "checkResources": {"inputs": inputs, "outputs": outputs}})
        out.append({"callId": call, "timestamp": stamp, "kind": "access", "method": "/cerbos.svc.v1.CerbosService/CheckResources"})
    return out


def flip(entry):
    first = entry["checkResources"]["outputs"][0]["actions"]
    a = sorted(first)[0]
    first[a]["effect"] = "EFFECT_DENY" if first[a]["effect"] == "EFFECT_ALLOW" else "EFFECT_ALLOW"


FAULTS = {
    "sound": (lambda es: es, {}),
    "a decision entry missing": (lambda es: es[2:], {"missing": 1}),
    "a decision entry twice": (lambda es: es + [es[0]], {"twice": 1}),
    "an access entry missing": (lambda es: [es[0]] + es[2:], {"access_missing": 1}),
    "an access entry twice": (lambda es: es + [es[1]], {"access_twice": 1}),
    "an effect other than the reference's": (lambda es: (flip(es[4]), es)[1], {"wrong_effect": 1}),
    "an input other than the request's": (
        lambda es: (es[0]["checkResources"]["inputs"][0]["resource"].update(id="other"), es)[1], {"wrong_input": 1}
    ),
    "an action left out of an input": (
        lambda es: (es[0]["checkResources"]["inputs"][0]["actions"].pop(), es)[1], {"wrong_input": 1}
    ),
}


@pytest.mark.parametrize("name", list(FAULTS))
def test_audit_check_counts_each_fault_once(name):
    reqs = workload.build(6, rig.TINY_MODS, 11, {"resources": [2, 4]})
    change, want = FAULTS[name]
    report = audit_check.check(reqs, change(entries_for(reqs)), NOW, NOW)
    faults = {k: report[k] for k in ("missing", "twice", "wrong_effect", "wrong_input", "access_missing", "access_twice")}
    assert faults == {**dict.fromkeys(faults, 0), **want}
    assert report["ok"] is (not want) and report["requests"] == 6


def test_audit_check_reads_the_window_by_time_across_rotated_files(tmp_path):
    reqs = workload.build(4, rig.TINY_MODS, 12, {"resources": [1, 1]})
    path = tmp_path / "a.log"
    warm, window, replay = (entries_for(reqs, NOW + timedelta(seconds=s)) for s in (-30, 0.5, 3.2))
    (tmp_path / "a-2026-07-01T00-00-00.000001.log").write_text("".join(json.dumps(e) + "\n" for e in warm + window[:3]))
    path.write_text("".join(json.dumps(e) + "\n" for e in window[3:] + replay[:4]) + "not an entry\n")
    files = audit_check.log_files(str(path))
    assert [os.path.basename(p) for p in files] == ["a-2026-07-01T00-00-00.000001.log", "a.log"]
    # from the window's open on: the warm replay is out by its time, the traced replay (of r0 and r1) is in
    entries, lines, broken = audit_check.read_entries(files, NOW.timestamp(), NOW.timestamp() + 60)
    assert (len(entries), lines, broken) == (12, 21, 1)
    assert audit_check.check(reqs, entries, NOW, NOW)["twice"] == 2
    # ... and is cut where the last request id was first logged, however late the window ended
    window_only = audit_check.cut_replay(entries)
    assert len(window_only) == 8 and audit_check.check(reqs, window_only, NOW, NOW)["ok"]
    # an entry written twice inside the window is still seen
    assert audit_check.check(reqs, audit_check.cut_replay(entries + [entries[2]]), NOW, NOW)["twice"] == 1


def test_counter_ratio_reads_nothing_where_either_counter_is_absent():
    read = spec.load_reader(os.path.join(rig.REPO, "benchmarks"), "counter_ratio")
    q = ("cerbos_tpu_audit_entries_total", (("kind", "decision"), ("outcome", "queued")))
    w = ("cerbos_tpu_audit_entries_total", (("kind", "decision"), ("outcome", "written")))
    h = ("cerbos_tpu_request_handler_seconds_count", ())
    args = {"metric": q[0], "labels": {"kind": "decision", "outcome": "queued"}, "over": h[0], "scale": 100.0}
    assert read({"before": {q: 10, w: 10, h: 10}, "after": {q: 90, w: 89, h: 90}}, **args) == 100.0
    assert read({"before": {q: 10, h: 10}, "after": {q: 50, h: 90}}, **args) == 50.0
    assert read({"before": {h: 10}, "after": {h: 90}}, **args) is None  # the parent: no such instrument
    assert read({"before": {q: 1}, "after": {q: 2}}, **args) is None
    assert read({"before": {q: 1, h: 5}, "after": {q: 1, h: 5}}, **args) is None  # nothing answered
