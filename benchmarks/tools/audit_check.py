#!/usr/bin/env python3
"""The audit log's plain reading: what the log of a run must hold.

Takes a run's requests (rebuilt from the seed, as ``workload.build`` makes
them) and the files of the server's file audit backend, and says of the
entries whose ``timestamp`` lies in the run's window:

- one decision entry for each request id of the window, none twice;
- each entry's ``checkResources.inputs`` are the request's (principal,
  resources in order, actions) and its ``outputs[*].actions[*].effect`` are
  the plain reference's for that request (``lib/reference.py``; the ``now()``
  family is bracketed by the window's own ends, as ``workload.compare`` does);
- one access entry for each decision entry's call id.

The warm replay before the window, and a traced run's replay after it, send
the same request ids: the window is told from them by time alone, so it needs
``run.json`` (``window_open_unix_s``, ``seconds``) of the run. The window ends
where its last request was first logged (``cut_replay``): the replay starts a
second after the window's last reply at the soonest. Imports nothing of
``cerbos_tpu``.

    python benchmarks/tools/audit_check.py --run-dir chiprun_out/benchmarks/classic-800-audit.pages.s7.t0
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from datetime import datetime, timezone

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import spec, workload  # noqa: E402

REPLAY_LEAD_S = 1.0  # a traced run's replay starts at least this long after the window's last reply (session.TRACE_LEAD_S)
SHOWN = 5  # examples kept of each kind of fault
_ENV = re.compile(r"\$\{([A-Za-z_][A-Za-z0-9_]*)(?::([^}]*))?\}")


def expand(value: str) -> str:
    """``${VAR:default}`` as the server reads it in its config file: the
    configuration's path is under the ``TMPDIR`` of whoever made the run, so
    this reads the right files only in that environment."""
    return _ENV.sub(lambda m: os.environ.get(m.group(1), m.group(2) or ""), value)


def log_files(path: str) -> list[str]:
    """The live file and the rotated ones beside it (``<stem>-<stamp><ext>``), oldest first."""
    stem, ext = os.path.splitext(path)
    return sorted(glob.glob(f"{glob.escape(stem)}-*{glob.escape(ext)}")) + ([path] if os.path.exists(path) else [])


def read_entries(paths: list[str], t_lo: float, t_hi: float) -> tuple[list[dict], int, int]:
    """(entries stamped inside [t_lo, t_hi], each with its instant under ``_at``;
    lines read; lines that are not an entry)."""
    kept, lines, broken = [], 0, 0
    for path in paths:
        with open(path, encoding="utf-8") as f:
            for line in f:
                lines += 1
                try:
                    entry = json.loads(line)
                    at = datetime.fromisoformat(entry["timestamp"]).timestamp()
                except (ValueError, KeyError, TypeError):
                    broken += 1
                    continue
                if t_lo <= at <= t_hi:
                    entry["_at"] = at
                    kept.append(entry)
    return kept, lines, broken


def request_id_of(entry: dict) -> str | None:
    """The request id of a CheckResources decision entry, else None."""
    inputs = entry.get("checkResources", {}).get("inputs") or [{}]
    return inputs[0].get("requestId")


def cut_replay(entries: list[dict]) -> list[dict]:
    """A traced run replays the window's first slice after the window, no
    sooner than ``REPLAY_LEAD_S`` after its last reply: the window's entries
    are those up to half of that after the instant at which the last of the
    request ids was FIRST logged. (A window that lost the entry of a replayed
    request ends too late by this rule, and reads the replay's as written
    twice: a fault either way.)"""
    first: dict[str, float] = {}
    for e in entries:
        rid = request_id_of(e)
        if rid is not None and e["_at"] < first.get(rid, float("inf")):
            first[rid] = e["_at"]
    if not first:
        return entries
    cut = max(first.values()) + REPLAY_LEAD_S / 2
    return [e for e in entries if e["_at"] <= cut]


def _same(logged, sent) -> bool:
    """An entry leaves out what is empty; numbers crossed the wire as doubles."""
    return (logged or None) == (sent or None)


def input_diff(req: workload.Request, inputs: list[dict]) -> str | None:
    if len(inputs) != len(req.entries):
        return f"{len(inputs)} inputs for {len(req.entries)} resources"
    for k, (got, (res, actions)) in enumerate(zip(inputs, req.entries)):
        p, r = got.get("principal", {}), got.get("resource", {})
        pairs = [
            ("requestId", got.get("requestId"), req.request_id), ("actions", got.get("actions"), list(actions)),
            ("principal.id", p.get("id"), req.principal["id"]), ("principal.roles", p.get("roles"), list(req.principal["roles"])),
            ("principal.attr", p.get("attr"), req.principal["attr"]),
            ("principal.policyVersion", p.get("policyVersion"), req.principal["policyVersion"]),
            ("principal.scope", p.get("scope"), req.principal["scope"]),
            ("resource.kind", r.get("kind"), res["kind"]), ("resource.id", r.get("id"), res["id"]),
            ("resource.attr", r.get("attr"), res["attr"]), ("resource.policyVersion", r.get("policyVersion"), res["policyVersion"]),
            ("resource.scope", r.get("scope"), res["scope"]),
        ]
        for name, logged, sent in pairs:
            if not _same(logged, sent):
                return f"input {k} {name}: logged {logged!r}, sent {sent!r}"
    return None


def effect_diff(req: workload.Request, outputs: list[dict], now_lo: datetime, now_hi: datetime) -> str | None:
    if len(outputs) != len(req.entries):
        return f"{len(outputs)} outputs for {len(req.entries)} resources"
    want = req.expected(now_lo)
    alt = req.expected(now_hi) if req.uses_now() else want
    for k, (out, (res, _)) in enumerate(zip(outputs, req.entries)):
        if out.get("resourceId") != res["id"]:
            return f"output {k} is for resource {out.get('resourceId')!r}, not {res['id']!r}"
        got = {a: e.get("effect") for a, e in out.get("actions", {}).items()}
        if got != want[k] and got != alt[k]:
            return f"resource {res['kind']}/{res['id']}: logged {got} want {want[k]}"
    return None


def check(reqs: list[workload.Request], entries: list[dict], now_lo: datetime, now_hi: datetime) -> dict:
    """The verdict over the entries of one window; ``ok`` is every count of a fault at 0."""
    by_id = {r.request_id: r for r in reqs}
    decisions: dict[str, list[dict]] = {}
    access: dict[str, int] = {}
    foreign = 0
    for e in entries:
        if e.get("kind") == "access":
            access[e.get("callId")] = access.get(e.get("callId"), 0) + 1
        elif "checkResources" in e:
            rid = request_id_of(e)
            if rid in by_id:
                decisions.setdefault(rid, []).append(e)
            else:
                foreign += 1
    report = {
        "requests": len(reqs), "decision_entries": sum(len(v) for v in decisions.values()), "access_entries": sum(access.values()),
        "missing": 0, "twice": 0, "wrong_effect": 0, "wrong_input": 0, "access_missing": 0, "access_twice": 0,
        "foreign_decision_entries": foreign, "examples": [],
    }

    def fault(kind: str, what: str) -> None:
        report[kind] += 1
        if sum(1 for x in report["examples"] if x.startswith(kind)) < SHOWN:
            report["examples"].append(f"{kind}: {what}")

    for rid, req in by_id.items():
        found = decisions.get(rid, [])
        if not found:
            fault("missing", rid)
            continue
        if len(found) > 1:
            fault("twice", f"{rid}: {len(found)} entries")
        for e in found:
            diff = input_diff(req, e["checkResources"].get("inputs") or [])
            if diff:
                fault("wrong_input", f"{rid}: {diff}")
            diff = effect_diff(req, e["checkResources"].get("outputs") or [], now_lo, now_hi)
            if diff:
                fault("wrong_effect", f"{rid}: {diff}")
            n = access.get(e.get("callId"), 0)
            if n == 0:
                fault("access_missing", f"{rid}: call {e.get('callId')}")
            elif n > 1:
                fault("access_twice", f"{rid}: call {e.get('callId')}: {n} entries")
    report["ok"] = not any(
        report[k] for k in ("missing", "twice", "wrong_effect", "wrong_input", "access_missing", "access_twice")
    )
    return report


def check_run(root: str, run_dir: str, log_path: str | None = None) -> dict:
    """Check the log against the run that ``run_dir``'s ``run.json`` describes."""
    with open(os.path.join(run_dir, "run.json")) as f:
        run = json.load(f)
    cell = spec.Cell(root, run["cell"])
    if log_path is None:
        log_path = expand(cell.config["assumed"]["server"]["audit.file.path"]["value"])
    seconds, t_open = float(run["seconds"]), float(run["window_open_unix_s"])
    n = round(cell.pair["rate"] * seconds)
    reqs = workload.build(n, int(cell.config["corpus"]["mods"]), int(run["seed"]), cell.traffic["request"])
    # whatever is stamped from the window's open to the client deadline after
    # its end is the window's, but for a traced run's replay after it
    t_hi = t_open + seconds + float(cell.traffic["deadline_s"])
    files = log_files(log_path)
    entries, lines, broken = read_entries(files, t_open, t_hi)
    if run.get("trace"):
        entries = cut_replay(entries)
    now_lo, now_hi = (datetime.fromtimestamp(t, timezone.utc) for t in (t_open - 2, t_hi + 2))
    report = check(reqs, entries, now_lo, now_hi)
    report.update(
        cell=cell.name, seed=run["seed"], files=[f"{p} ({os.path.getsize(p)} B)" for p in files], lines=lines,
        lines_not_an_entry=broken, window_entries=len(entries),
    )
    report["ok"] = report["ok"] and broken == 0
    return report


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--run-dir", required=True, help="the run's directory under chiprun_out/benchmarks/ (holds run.json)")
    ap.add_argument("--log", default=None, help="the audit file's path (default: the configuration's audit.file.path, expanded in this environment)")
    args = ap.parse_args()
    report = check_run(ROOT, args.run_dir, args.log)
    print("AUDIT_CHECK " + json.dumps(report), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
