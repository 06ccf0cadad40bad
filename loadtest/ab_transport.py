#!/usr/bin/env python
"""A/B drill: the ticket-queue data plane, uds marshal vs shm frame rings.

Identical topology on both legs — one ``BatcherIpcServer`` over a
``BatchingEvaluator``, one ``RemoteBatcherClient``, the same client thread
population and request mix — with the transport knob as the ONLY variable.
The serving side is a precomputed-output memo (near-free) so the
measurement isolates what this drill is for: frame encode, the queue/ring
hop, and reply decode. Its output is a host figure (PERF.md, A4).

Usage:
    python loadtest/ab_transport.py [--duration 10] [--threads 8]
                                    [--req-size 4] [--json PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cerbos_tpu.compile import compile_policy_set  # noqa: E402
from cerbos_tpu.engine import EvalParams  # noqa: E402
from cerbos_tpu.engine.batcher import BatchingEvaluator  # noqa: E402
from cerbos_tpu.engine.ipc import BatcherIpcServer, RemoteBatcherClient  # noqa: E402
from cerbos_tpu.policy.parser import parse_policies  # noqa: E402
from cerbos_tpu.ruletable import build_rule_table, check_input  # noqa: E402
from cerbos_tpu.util import bench_corpus  # noqa: E402

N_MODS = 50


class MemoEvaluator:
    """Near-free serving side: outputs precomputed once on the CPU oracle,
    looked up by request_id at serve time. Evaluation cost would otherwise
    dominate both legs identically and bury the transport delta this drill
    exists to measure — the front door IS the workload here."""

    def __init__(self, rt, memo):
        self.rule_table = rt
        self.schema_mgr = None
        self.memo = memo
        self.stats = {"device_inputs": 0}

    def check(self, inputs, params=None):
        return [self.memo[i.request_id] for i in inputs]

    def submit(self, inputs, params=None):
        self.stats["device_inputs"] += len(inputs)
        return self.check(inputs, params)

    def collect(self, ticket):
        return ticket


def run_leg(transport: str, rt, memo, reqs, duration: float, threads: int) -> dict:
    batcher = BatchingEvaluator(MemoEvaluator(rt, memo), max_wait_ms=1.0)
    sock = os.path.join(tempfile.mkdtemp(prefix=f"cerbos-ab-{transport}-"), "b.sock")
    server = BatcherIpcServer(sock, batcher, transport=transport)
    server.start()
    client = RemoteBatcherClient(
        sock, rt, worker_label=f"ab-{transport}", status_poll_s=0.25, transport=transport
    )
    if not client._connected.wait(10.0):
        raise SystemExit("ticket queue never attached")
    if client.transport != transport:
        print(
            f"WARNING: requested {transport}, negotiated {client.transport} "
            "(native module missing?)",
            file=sys.stderr,
        )
    lock = threading.Lock()
    latencies: list[float] = []
    counts = [0] * threads
    stop = threading.Event()

    def worker(wid: int) -> None:
        local: list[float] = []
        n = 0
        while not stop.is_set():
            r = reqs[(wid + n) % len(reqs)]
            t0 = time.perf_counter()
            client.check(r)
            local.append((time.perf_counter() - t0) * 1000)
            n += 1
        counts[wid] = n
        with lock:
            latencies.extend(local)

    # warmup outside the timed window (jit-free here, but the batcher's
    # wait heuristics and the ring's futex paths deserve a settle)
    for r in reqs[:32]:
        client.check(r)
    ths = [threading.Thread(target=worker, args=(w,), daemon=True) for w in range(threads)]
    t_start = time.perf_counter()
    for t in ths:
        t.start()
    time.sleep(duration)
    stop.set()
    for t in ths:
        t.join(timeout=10)
    elapsed = time.perf_counter() - t_start
    stats = client.transport_stats()
    fallbacks = client.stats["oracle_fallbacks"]
    client.close()
    server.close()
    batcher.close()
    total = sum(counts)
    lat = sorted(latencies)

    def pct(p: float) -> float:
        return lat[min(len(lat) - 1, int(p * len(lat)))] if lat else 0.0

    return {
        "transport": stats["transport"],
        "requests": total,
        "rps": round(total / elapsed, 1),
        "p50_ms": round(pct(0.50), 3),
        "p99_ms": round(pct(0.99), 3),
        "oracle_fallbacks": fallbacks,
        "stats": stats,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=float, default=10.0)
    ap.add_argument("--threads", type=int, default=8)
    ap.add_argument("--req-size", type=int, default=4, help="inputs per request")
    ap.add_argument("--json", metavar="PATH", default="")
    args = ap.parse_args()

    rt = build_rule_table(
        compile_policy_set(list(parse_policies(bench_corpus.corpus_yaml(N_MODS))))
    )
    inputs = bench_corpus.requests(2048, N_MODS)
    reqs = [inputs[b : b + args.req_size] for b in range(0, len(inputs), args.req_size)]
    params = EvalParams()
    memo = {i.request_id: check_input(rt, i, params) for i in inputs}

    # uds first, shm second: any page-cache/branch-predictor warmth favors
    # the leg under test LAST being the baseline's problem, not shm's
    uds = run_leg("uds", rt, memo, reqs, args.duration, args.threads)
    shm = run_leg("shm", rt, memo, reqs, args.duration, args.threads)
    speedup = round(shm["rps"] / uds["rps"], 3) if uds["rps"] else 0.0
    result = {
        "threads": args.threads,
        "req_size": args.req_size,
        "duration_s": args.duration,
        "host_cores": len(os.sched_getaffinity(0)),
        "uds": uds,
        "shm": shm,
        "shm_speedup": speedup,
    }
    print(json.dumps(result, indent=2))
    print(
        f"\nshm vs uds at identical topology: {uds['rps']} -> {shm['rps']} rps "
        f"({(speedup - 1) * 100:+.1f}%), p50 {uds['p50_ms']} -> {shm['p50_ms']} ms",
        file=sys.stderr,
    )
    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=2)
            f.write("\n")


if __name__ == "__main__":
    main()
