#!/usr/bin/env python3
"""Builder's tool, run on the chip: the control of the comparison that decides
``correct``. The system states no precision; the guarantee broken here is "the
server answers from the policy set it was booted with, none cached across
policy versions": the server is booted with a STALE set, in which one
name-mod in ten carries an older ``public-view`` rule (granted to ``tester``,
not to ``any_employee``), while the reference reads the configuration's own
documents. Every run must come out ``correct: false``.

    python benchmarks/tools/control.py --workload classic-800.pages --seeds 31,32,33 --seconds 10
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

_MOD = re.compile(r"resource: leave_request_(\d+)\n")
FRESH = "derivedRoles: [any_employee]\n      name: public-view"
STALE = "derivedRoles: [tester]\n      name: public-view"


def stale_policies(docs: list[str]) -> list[str]:
    """The documents with the older ``public-view`` rule in every tenth name-mod."""
    out = []
    for doc in docs:
        m = _MOD.search(doc)
        if m and int(m.group(1)) % 10 == 0:
            doc = doc.replace(FRESH, STALE)
        out.append(doc)
    return out


def main() -> int:
    from benchmarks.lib import spec
    from benchmarks.lib.session import Session

    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    cell = spec.Cell(ROOT, args.workload)
    ses = Session(cell, policy_transform=stale_policies, log=lambda m: print(m, flush=True))
    verdicts = []
    try:
        ses.ready("tpu")
        for seed in (int(s) for s in args.seeds.split(",")):
            prepared = ses.prepare(seed, args.seconds)
            ses.load(prepared)
            # one server for all the seeds, and one slice of warm replay each: the
            # verdict is about effects, so the windows need not open on a settled server
            ses.gen.run(dict(prepared["slices"][0], out=os.path.join(ses.work, "warm.pickle")))
            g = ses.measure(prepared)["gen"]
            line = {"workload": args.workload, "seed": seed, "correct": g["wrong"] == 0, "attempted": g["attempted"],
                    "wrong_replies": g["wrong"], "failed": g["failed"]}
            print("CONTROL " + json.dumps(line), flush=True)
            verdicts.append(line["correct"])
        out_dir = os.path.join(ROOT, "chiprun_out", "benchmarks", f"control.{cell.name}")
        os.makedirs(out_dir, exist_ok=True)
        ses.stop(out_dir)
    finally:
        ses.close()
    return 1 if any(verdicts) else 0


if __name__ == "__main__":
    sys.exit(main())
