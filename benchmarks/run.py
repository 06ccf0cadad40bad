#!/usr/bin/env python3
"""One cell, one run: ``python benchmarks/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``.

Writes the configuration's policies to a temporary directory, boots ``python
-m cerbos_tpu.cli server`` as a child that owns the chip, fails unless that
server reports ``platform=tpu``, replays the cell's own traffic until the
server has settled (all of that is ``setup_s``), measures for ``--seconds``,
stops the server with SIGTERM, compares every reply of the window with the
plain reference, and prints the result as the last line of stdout. This
process never imports jax. See ``benchmarks/README.md``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import sys
import time
from datetime import datetime, timezone

T_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import prom, spec, trace_reduce, workload  # noqa: E402
from benchmarks.lib.server import HarnessError  # noqa: E402
from benchmarks.lib.session import Session, report_failures  # noqa: E402

PLATFORM = "tpu"
DIGEST_NOW = datetime(2026, 7, 1, tzinfo=timezone.utc)  # the clock the pinned digests were taken at
DIGEST_REQUESTS = 2000
KEEP_TRACE_BYTES = 8 << 20


def log(msg: str) -> None:
    print(msg, flush=True)


def check_digest(cell: spec.Cell) -> bool | None:
    """Seed 0 only: the reference's effects for the first requests of the cell
    against the digest pinned in ``benchmarks/expected``. None = none pinned."""
    path = os.path.join(cell.bench_dir, "expected", f"{cell.config_name}.{cell.traffic_name}.seed0.sha256")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        pinned = f.read().split()[0]
    mods = int(cell.config["corpus"]["mods"])
    return workload.digest(workload.build(DIGEST_REQUESTS, mods, 0, cell.traffic["request"]), DIGEST_NOW) == pinned


def reduce_trace(profile: dict, span: tuple[float, float], out_dir: str) -> dict:
    if "capture" not in profile:
        raise HarnessError(f"the profiler gave no trace: {profile.get('error')}")
    path = trace_reduce.find_xplane(profile["capture"]["path"])
    if path is None:
        raise HarnessError(f"no .xplane.pb under {profile['capture']['path']}")
    traced = trace_reduce.reduce_file(path, span)
    if traced is None:
        raise HarnessError(f"no operation ran on a device between {span[0]:.2f} s and {span[1]:.2f} s of the capture")
    log(
        f"trace: {traced['events']} device operations inside the traced traffic's span of {traced['window_s']:.3f} s, "
        f"{traced['events_outside']} outside it (left out, between {traced['outside_from_s']:.2f} s and "
        f"{traced['outside_to_s']:.2f} s of the capture); busy {traced['busy_s']:.6f} s; the trace holds "
        f"{os.path.getsize(path) / 1e6:.1f} MB"
    )
    if os.path.getsize(path) <= KEEP_TRACE_BYTES:  # kept beside the run when small
        shutil.copyfile(path, os.path.join(out_dir, os.path.basename(path)))
    return traced


def run_cell(
    workload_name: str, seed: int, seconds: float, trace: int, *,
    root: str = ROOT, require_platform: str | None = PLATFORM, policy_transform=None, out_dir: str | None = None,
    t_start: float | None = None,
) -> dict:
    """Drive one run and return the result object (the last line's content).

    ``require_platform=None`` skips the look for a chip and ``policy_transform``
    rewrites the documents the SERVER loads (never what the reference reads):
    both are for the tests and the control runs under ``benchmarks/tools``."""
    cell = spec.Cell(root, workload_name)
    out_dir = out_dir or os.path.join(root, "chiprun_out", "benchmarks", f"{cell.name}.s{seed}.t{trace}")
    os.makedirs(out_dir, exist_ok=True)
    ses = Session(cell, trace=bool(trace), policy_transform=policy_transform, log=log)
    t = ses.t
    if t_start is not None:
        t["start"] = t_start
    try:
        prepared = ses.prepare(seed, seconds)  # while the server boots
        digest_ok = check_digest(cell) if seed == 0 else None
        t["requests"] = time.monotonic()
        dev = ses.ready(require_platform)
        log(
            f"server: platform={dev['platform']} device_kind={dev['device_kind']!r} count={dev['count']} "
            f"native={ses.srv.native}; {ses.n_docs} policy documents; {len(prepared['reqs'])} window requests "
            f"({prepared['decisions']} decisions), rehearsed in {len(prepared['slices'])} slices"
        )
        ses.load(prepared)
        rounds = ses.warm(prepared)
        ses.touch_device(prepared)  # a traced run of a mix that bypasses the device: its touch compiles now, not in the capture
        t["warm"] = time.monotonic()
        m = ses.measure(prepared)
        g = m["gen"]
        g["setup_s"] = m["t_open"] - t["start"]
        with open(os.path.join(out_dir, "metrics_after.txt"), "w") as f:
            f.write(m["after_text"])
        report_failures(m["rows"], out_dir)
        status = ses.stop(out_dir)
        t["stopped"] = time.monotonic()
        traced = reduce_trace(m["profile"], m["span"], out_dir) if trace else None

        log(
            "set-up: policy write %.1f s, requests built by %.1f s, boot to ready %.1f s, warm replay and settle "
            "%.1f s (%d rounds), window opened at %.1f s"
            % (t["policies"] - t["start"], t["requests"] - t["start"], t["ready"] - t["start"],
               t["warm"] - t["ready"], rounds, g["setup_s"])
        )
        after = [x - m["t_close"] for x in (m["t_captured"], m["t_compared"], t["stopped"], time.monotonic())]
        log(
            ("after the window: the capture ended at %.1f s, " % after[0] if trace else "after the window: ")
            + "replies decoded and compared by %.1f s, server stopped by %.1f s" % (after[1], after[2])
            + (", trace reduced by %.1f s" % after[3] if trace else "")
        )
        compiles_in_window = prom.moved(m, "cerbos_tpu_xla_compiles_total")
        log(
            f"window: {seconds:g} s, attempted {g['attempted']}, failed {g['failed']}; compiles in window "
            f"{compiles_in_window:.0f}; brownout stage at open {prom.total(m['before'], 'cerbos_tpu_brownout_stage'):.0f}, "
            f"at close {prom.total(m['after'], 'cerbos_tpu_brownout_stage'):.0f}; "
            + "; ".join(f"{k} {v:.6g}" for k, v in sorted(g.items()) if k not in ("attempted", "failed", "wrong"))
        )
        log(f"compared: wrong or incomplete replies {g['wrong']} (limit 0) of {g['attempted']} replies of the window")
        if digest_ok is not None:
            log(f"compared: seed 0 reference digest differs from the pinned one: {int(not digest_ok)} (limit 0)")
        with open(os.path.join(out_dir, "run.json"), "w") as f:
            # the window on the wall clock, to find its requests in slow.json (wall_time_ns)
            json.dump({"cell": cell.name, "seed": seed, "seconds": seconds, "trace": trace, "gen": g,
                       "window_open_unix_s": m["open_unix_s"], "warm_rounds": rounds,
                       "setup_phases_s": {k: v - t["start"] for k, v in t.items() if k != "start"}}, f)

        ctx = {
            "before": m["before"], "after": m["after"], "gen": g, "seconds": seconds, "cell": cell.name,
            "trace": traced, "trace_before": m.get("trace_before"), "trace_after": m.get("trace_after"),
        }
        metrics = {}
        if trace:
            for pm in cell.per_layer:
                value = cell.reader(pm)(ctx, **pm.get("args", {}))
                if value is not None:
                    metrics[pm["name"]] = {"value": value, "unit": pm["unit"]}
        else:
            for em in cell.end_to_end:
                if em["name"] not in g:
                    raise HarnessError(f"the run gives no value for the end-to-end metric {em['name']!r}")
                metrics[em["name"]] = {"value": g[em["name"]], "unit": em["unit"]}
        peaks = [v for (n, _), v in m["after"].items() if n == "cerbos_tpu_device_memory_peak_bytes_in_use"]
        peaks += [d.get("peak_bytes_in_use", 0) for d in status.get("device_memory", [])]
        device = {
            "platform": str(dev["platform"]), "kind": str(dev["device_kind"]), "count": int(dev["count"]),
            "memory_peak_bytes": int(max(peaks, default=0)),
        }
        result = {
            "correct": g["wrong"] == 0 and digest_ok is not False,
            "attempted": g["attempted"], "failed": g["failed"], "metrics": metrics, "device": device,
        }
        if traced is not None:
            device["busy_s"], device["window_s"] = traced["busy_s"], traced["window_s"]
            result["breakdown"] = {"device_ops": traced["device_ops"], "idle_gaps": traced["idle_gaps"]}
        return result
    finally:
        ses.close()


def _terminate(signum, frame):  # noqa: ARG001
    raise SystemExit(128 + signum)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    signal.signal(signal.SIGTERM, _terminate)
    try:
        import cerbos_tpu  # noqa: F401 - the system under test, and the API classes the requests are built from
    except ImportError as e:
        print(f"benchmark run failed: the cerbos_tpu package is not beside benchmarks/ ({e})", file=sys.stderr)
        return 3
    try:
        result = run_cell(args.workload, args.seed, args.seconds, args.trace, t_start=T_START)
    except (HarnessError, spec.SpecError) as e:
        print(f"benchmark run failed: {e}", file=sys.stderr)
        return 1
    if "jax" in sys.modules:
        print("benchmark run failed: the harness imported jax, which would hold the chip", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
