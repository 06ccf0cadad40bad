#!/usr/bin/env python3
"""Builder's tool, run on the chip: one server, the cell's traffic at several
rates, one window each, rising.

    python benchmarks/tools/sweep.py --workload classic-800.pages --seed 11 --seconds 15 --values 40,80,120,160

Prints one JSON line per value: latency percentiles from due time, failures,
the backlog at the window's close, how late the generator sent, and what the
server's counters say of the window. The knee is read off these lines, and
four fifths of it or less goes into the traffic file as a number (PERF.md
section 4 keeps the sweeps). Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import prom, spec  # noqa: E402
from benchmarks.lib.session import Session  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--values", required=True, help="comma-separated rates (requests/s)")
    args = ap.parse_args()
    cell = spec.Cell(ROOT, args.workload)
    ses = Session(cell, log=lambda m: print(m, flush=True))
    try:
        ses.ready("tpu")
        for k, raw in enumerate(args.values.split(",")):
            cell.pair = dict(cell.pair, rate=float(raw))
            prepared = ses.prepare(args.seed + k, args.seconds)
            ses.load(prepared)
            ses.t["start"] = time.monotonic()  # each rate gets a set-up's time to settle
            rounds = ses.warm(prepared)
            m = ses.measure(prepared)
            line = {"rate": cell.pair["rate"], "warm_rounds": rounds, **m["gen"]}
            line["compiles_in_window"] = prom.moved(m, "cerbos_tpu_xla_compiles_total")
            line["refused"] = prom.moved(m, "cerbos_tpu_admission_total") - prom.moved(
                m, "cerbos_tpu_admission_total", {"outcome": "admitted"}
            )
            line["flights"] = prom.moved(m, "cerbos_tpu_batcher_batches_total")
            line["flight_inputs_mean"] = prom.hist_mean(m["before"], m["after"], "cerbos_tpu_batcher_batch_size")
            src = {s: prom.moved(m, "cerbos_tpu_decision_source_total", {"source": s}) for s in ("device", "oracle")}
            line["decisions_by_source"] = src
            line["brownout_stage_after"] = prom.total(m["after"], "cerbos_tpu_brownout_stage")
            print("SWEEP " + json.dumps(line), flush=True)
        out_dir = os.path.join(ROOT, "chiprun_out", "benchmarks", f"sweep.{cell.name}")
        os.makedirs(out_dir, exist_ok=True)
        ses.stop(out_dir)
    finally:
        ses.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
