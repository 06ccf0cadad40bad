#!/usr/bin/env python3
"""Builder's tool: write ``benchmarks/expected/<config>.<traffic>.seed0.sha256`` for
every cell of BENCHMARK.json that has none yet — the digest of the plain
reference's effects for seed 0's first requests of the cell, at a fixed clock.
A digest that exists is never rewritten: delete it by hand in a benchmark PR
that means to change what the reference answers, and say why in PERF.md."""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.lib import spec, workload  # noqa: E402


def main() -> int:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    for name in names:
        cell = spec.Cell(ROOT, name)
        path = os.path.join(cell.bench_dir, "expected", f"{cell.config_name}.{cell.traffic_name}.seed0.sha256")
        if os.path.exists(path):
            print(f"kept {path}")
            continue
        reqs = workload.build(run.DIGEST_REQUESTS, int(cell.config["corpus"]["mods"]), 0, cell.traffic["request"])
        with open(path, "w") as f:
            f.write(f"{workload.digest(reqs, run.DIGEST_NOW)}  seed 0, first {run.DIGEST_REQUESTS} requests, clock {run.DIGEST_NOW.isoformat()}\n")
        print(f"wrote {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
