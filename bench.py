#!/usr/bin/env python
"""Benchmark: batched CheckResources decisions/sec on the TPU evaluator.

Workload mirrors the reference's classic load test at full fidelity
(hack/loadtest/templates/classic): 100 name-mods × 9 policy documents = 900
docs, i.e. at least the reference's "800 policies" configuration, including
the inIPAddrRange location variable, JWT defer conditions, schema refs and
the default-version scope chain — plus the condition-diversity extension
(util/bench_corpus.diverse_docs) so the device path is exercised over ≥50
distinct condition kernels, not a memo-friendly handful. The reference's
800-policy config peaks at 8,638 req/s × 4 decisions/req ≈ 34.6k
decisions/s on a 4-vCPU c3-standard-4 (BASELINE.md). Prints one JSON line;
vs_baseline is decisions/sec relative to that anchor.

Every main initializes JAX in-process and names the device it ran on
(``platform``/``device_kind``/count) in its JSON line. A run that finds no
TPU exits non-zero instead of timing the host — unless ``JAX_PLATFORMS=cpu``
was set on purpose (CI's ``--plan --smoke`` parity leg), in which case every
unit says ``cpu``. The device path is the headline; the numpy backend is
reported beside it as a host reference, never under a chip unit.
"""

import argparse
import json
import os
import statistics
import sys
import time

from cerbos_tpu.compile import compile_policy_set
from cerbos_tpu.engine import EvalParams
from cerbos_tpu.policy.parser import parse_policies
from cerbos_tpu.ruletable import build_rule_table
from cerbos_tpu.tpu import TpuEvaluator
from cerbos_tpu.util import bench_corpus, gctune

REFERENCE_DECISIONS_PER_SEC = 8638 * 4  # BASELINE.md: max RPS @800 policies × 4 decisions/req
N_MODS = 100  # × 9 docs per mod = 900 docs (≥ the classic "800 policies" config)
BATCH = 4096
ITERS = 8


def _timed(fn, *args) -> float:
    t0 = time.perf_counter()
    fn(*args)
    return time.perf_counter() - t0


def _measure(ev, inputs, params, decisions_per_batch, label, n_iters=ITERS, warm=True):
    """Optionally warm up, then time n_iters batches."""
    warm_excess = 0.0
    if warm:
        t_warm0 = time.perf_counter()
        ev.check(inputs, params)  # warmup: caches + jit compile
        warm1 = time.perf_counter() - t_warm0
        warm2 = _timed(ev.check, inputs, params)
        warm_excess = max(warm1 - warm2, 0.0)
        # freeze the warmed table/caches out of the GC's scan set (the
        # reference serves at GOGC=100 after a GOGC=10 build; see
        # util/gctune for the CPython analogue and measurements)
        gctune.tune_for_serving()
    iter_times = []
    outs = None
    for _ in range(n_iters):
        t0 = time.perf_counter()
        outs = ev.check(inputs, params)
        iter_times.append(time.perf_counter() - t0)
    med = statistics.median(iter_times)
    rate = decisions_per_batch / med
    sustained = decisions_per_batch * n_iters / sum(iter_times)
    print(
        f"{label}: median {rate:.0f} dec/s, sustained {sustained:.0f} over {n_iters} batches "
        f"(best {decisions_per_batch / min(iter_times):.0f}, worst {decisions_per_batch / max(iter_times):.0f})",
        flush=True,
    )
    return rate, iter_times, warm_excess, outs


def _device_or_exit() -> dict:
    """Open the JAX backend in this process and return what it reports
    (``jitcache.open_device``: platform, device_kind, count). Exits 2 when
    the platform is not a TPU, unless JAX_PLATFORMS=cpu asked for the CPU
    backend on purpose."""
    from cerbos_tpu import native
    from cerbos_tpu.tpu import jitcache

    dev = dict(jitcache.open_device())
    dev.pop("pid")
    if dev["platform"] != "tpu" and os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        print(
            f"bench: no TPU found (platform={dev['platform']}); refusing to time the host "
            "under a device metric. Set JAX_PLATFORMS=cpu to run on the CPU backend on purpose.",
            file=sys.stderr,
        )
        sys.exit(2)
    dev["native"] = native.get() is not None
    print(
        f"device: platform={dev['platform']} device_kind={dev['device_kind']!r} "
        f"count={dev['count']} native={str(dev['native']).lower()}",
        flush=True,
    )
    return dev


def _probe_link():
    """Measure the host<->device link: dispatch round-trip, fetch latency
    floor (1 KB computed result), fetch and put of 2 MB. Best of 3 each."""
    import jax
    import numpy as np

    f = jax.jit(lambda x: x + 1)
    small = jax.device_put(np.zeros(1024, np.int8))
    big = np.zeros(2 * 1024 * 1024, np.int8)
    jax.block_until_ready(f(small))

    def best(fn, n=3):
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return min(ts)

    rtt = best(lambda: jax.block_until_ready(f(small)))
    fetch_small = best(lambda: np.asarray(f(small)))
    d_big = jax.device_put(big)
    jax.block_until_ready(d_big)
    jax.block_until_ready(f(d_big))  # compile the 2 MB shape outside the timing
    put_big = best(lambda: jax.block_until_ready(jax.device_put(big)))
    fetch_big = best(lambda: np.asarray(f(d_big)))
    return {
        "dispatch_rtt_ms": round(rtt * 1e3, 3),
        "fetch_1kb_ms": round(fetch_small * 1e3, 3),
        "fetch_2mb_ms": round(fetch_big * 1e3, 3),
        "put_2mb_ms": round(put_big * 1e3, 3),
    }


def index_query_tuples(requests):
    """Expand CheckResources requests into the raw index query tuples the
    engine issues per (action, policy-kind) pair — the memo-cold unit of work."""
    from cerbos_tpu import namer
    from cerbos_tpu.ruletable.rows import KIND_PRINCIPAL, KIND_RESOURCE

    qs = []
    for r in requests:
        sanitized = namer.sanitize(r.resource.kind)
        version = r.resource.policy_version or "default"
        scope = r.resource.scope or ""
        roles = list(r.principal.roles)
        for action in r.actions:
            for pt in (KIND_PRINCIPAL, KIND_RESOURCE):
                pid = r.principal.id if pt == KIND_PRINCIPAL else ""
                qs.append((version, sanitized, scope, action, roles, pt, pid))
    return qs


def index_only_main(smoke: bool) -> int:
    """--index-only: memo-cold rule-index micro-bench + bitmap/legacy parity.

    Builds the bench corpus once into both index backends with the
    request-shape memos disabled, replays every cold query through each, and
    fails (exit 1) on any result divergence. Prints one JSON line.
    """
    device = _device_or_exit()
    n_requests = 256 if smoke else 1024
    policies = list(parse_policies(bench_corpus.corpus_yaml(N_MODS)))
    compiled = compile_policy_set(policies)
    rt_bitmap = build_rule_table(compiled, index_backend="bitmap")
    rt_legacy = build_rule_table(compiled, index_backend="legacy")
    rt_bitmap.idx.set_memo_enabled(False)
    rt_legacy.idx.set_memo_enabled(False)

    qs = index_query_tuples(bench_corpus.requests(n_requests, N_MODS))

    mismatches = 0
    for q in qs:
        got = [
            (r.id, r.origin_fqn, r.action, r.effect)
            for r in rt_bitmap.idx.query(*q)
        ]
        want = [
            (r.id, r.origin_fqn, r.action, r.effect)
            for r in rt_legacy.idx.query(*q)
        ]
        if got != want:
            mismatches += 1
    parity_ok = mismatches == 0

    rates = {}
    reps = 2 if smoke else 5
    for name, rt in (("legacy", rt_legacy), ("bitmap", rt_bitmap)):
        query = rt.idx.query
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for q in qs:
                query(*q)
            best = min(best, time.perf_counter() - t0)
        rates[name] = len(qs) / best
        print(f"index cold {name}: {rates[name]:.0f} queries/s", flush=True)

    from cerbos_tpu.ruletable import index as index_mod

    record = {
        "metric": "index_cold_queries_per_sec",
        "value": round(rates["bitmap"], 1),
        "unit": "queries/s/host-core",  # the rule index never touches the device
        "device": device,
        "legacy": round(rates["legacy"], 1),
        "speedup": round(rates["bitmap"] / rates["legacy"], 2),
        "queries": len(qs),
        "parity": "ok" if parity_ok else f"{mismatches} mismatches",
        "kernel": "native" if index_mod._native_bitmap_sweep is not None else "numpy",
    }
    print(json.dumps(record))
    return 0 if parity_ok else 1


def _plan_queries(n: int) -> list:
    """PlanResources sweep derived from the classic check workload: every
    CheckInput becomes a PlanInput whose resource attributes are all KNOWN
    (a list-endpoint pre-filter planning against concrete rows), so the
    ternary device path should settle most (query, condition) cells and
    only time-dependent / analyzer-refused conditions stay symbolic."""
    from cerbos_tpu.plan.types import PlanInput

    out = []
    for inp in bench_corpus.requests(n, N_MODS):
        out.append(
            PlanInput(
                request_id=inp.request_id,
                actions=list(inp.actions),
                principal=inp.principal,
                resource_kind=inp.resource.kind,
                resource_attr=dict(inp.resource.attr),
                resource_policy_version=inp.resource.policy_version,
                resource_scope=inp.resource.scope,
                aux_data=inp.aux_data,
            )
        )
    return out


PLAN_POOL = 24  # distinct (principal, action, kind) archetypes in the replay sweep


def _plan_replay(n: int, pool: int) -> list:
    """Serving-shaped plan sweep: ``pool`` distinct archetypes replayed to
    ``n`` queries under fresh request ids. PlanResources traffic looks like
    this in production — every list-endpoint hit re-plans the same
    (principal, action, kind) triple — which is exactly the shape the
    batched planner's dedup collapses; the cold sweep below keeps it honest
    on all-distinct input."""
    import dataclasses
    import random

    archetypes = _plan_queries(pool)
    rng = random.Random(41)
    out = []
    for i in range(n):
        a = rng.choice(archetypes)
        out.append(dataclasses.replace(a, request_id=f"replay-{i}"))
    return out


def _plan_ab(sequential, batched, queries, params, reps) -> tuple[float, float, int]:
    """(seq_qps, batched_qps, parity mismatches) over one sweep; the parity
    pass doubles as warmup for both paths."""
    want = [json.dumps(sequential.plan(q, params).to_json(), sort_keys=True) for q in queries]
    have = [json.dumps(o.to_json(), sort_keys=True) for o in batched.plan_batch(queries, params)]
    mismatches = sum(1 for w, h in zip(want, have) if w != h)

    t_seq = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for q in queries:
            sequential.plan(q, params)
        t_seq = min(t_seq, time.perf_counter() - t0)
    t_bat = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        batched.plan_batch(queries, params)
        t_bat = min(t_bat, time.perf_counter() - t0)
    return len(queries) / t_seq, len(queries) / t_bat, mismatches


def plan_only_main(smoke: bool) -> int:
    """--plan: batched-vs-sequential PlanResources A/B + filter-AST parity.

    Two sweeps through the sequential ``Planner`` and the vectorized
    ``BatchPlanner`` on the same rule table: a serving-shaped replay
    (bounded archetype pool — the headline number) and a memo-cold sweep of
    all-distinct queries (the dedup-free floor). Fails (exit 1) on any
    byte-level serialized-filter divergence in either sweep. The batched
    planner runs its condition kernels through jax, as the served plan lane
    does (bootstrap wires ``use_jax`` from the evaluator), so the unit names
    the platform it ran on. Prints one JSON line.
    """
    from cerbos_tpu.plan import BatchPlanner, Planner

    device = _device_or_exit()
    n_queries = 256 if smoke else 2048
    policies = list(parse_policies(bench_corpus.corpus_yaml(N_MODS)))
    rt = build_rule_table(compile_policy_set(policies))
    params = EvalParams()
    replay = _plan_replay(n_queries, PLAN_POOL)
    cold = _plan_queries(n_queries)
    print(
        f"plan sweep: {len(replay)} replay ({PLAN_POOL} archetypes) + "
        f"{len(cold)} cold queries over {len(policies)} policy docs",
        flush=True,
    )

    sequential = Planner(rt)
    batched = BatchPlanner(rt, use_jax=True)
    reps = 2 if smoke else 5

    seq_qps, bat_qps, bad_replay = _plan_ab(sequential, batched, replay, params, reps)
    cold_seq, cold_bat, bad_cold = _plan_ab(sequential, batched, cold, params, reps)
    mismatches = bad_replay + bad_cold
    parity_ok = mismatches == 0
    print(f"filter-AST parity: {'ok' if parity_ok else f'{mismatches} DIVERGENT'}", flush=True)

    st = batched.stats.as_dict()
    rules_total = st["device_rules"] + st["symbolic_rules"]
    record = {
        "metric": "plan_queries_per_sec",
        "value": round(bat_qps, 1),
        "unit": f"queries/s/{device['platform']}",
        "device": device,
        "sequential": round(seq_qps, 1),
        "speedup": round(bat_qps / seq_qps, 2),
        "cold_speedup": round(cold_bat / cold_seq, 2),
        "cold_queries_per_sec": round(cold_bat, 1),
        "queries": len(replay),
        "pool": PLAN_POOL,
        "parity": "ok" if parity_ok else f"{mismatches} divergent",
        "mode": batched._mode(),
        "device_query_share": round(st["device_queries"] / max(st["queries"], 1), 3),
        "memo_query_share": round(st["memo_queries"] / max(st["queries"], 1), 3),
        "residual_rule_share": round(st["symbolic_rules"] / max(rules_total, 1), 4),
        "stats": st,
    }
    print(json.dumps(record))
    return 0 if parity_ok else 1


def _compile_economy() -> dict:
    """Compile-side economics for the perf artifact: how much XLA work the
    run paid and how well the jit cache amortized it — the figures that
    make compile amortization diffable across PRs."""
    from cerbos_tpu.engine.flight import recorder as flight_recorder
    from cerbos_tpu.tpu import jitcache
    from cerbos_tpu.tpu.compilestats import stats as compile_stats

    snap = compile_stats().snapshot()
    return {
        "compiles": snap["compiles"],
        "compile_seconds_total": snap["compile_seconds_total"],
        "persistent_loads": snap["persistent_loads"],
        "cache_hits": snap["cache_hits"],
        "layout_cardinality": snap["layout_cardinality"],
        "xla_cache_dir": jitcache.directory(),
        # one entry per compile, in order: layout, seconds, fresh|persistent
        "per_compile": [
            {k: e[k] for k in ("layout_key", "seconds", "source")}
            for e in flight_recorder().dump()["events"]
            if e["kind"] == "xla_compile"
        ],
    }


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--smoke", action="store_true",
        help="reduced iteration counts for CI",
    )
    parser.add_argument(
        "--index-only", action="store_true",
        help="memo-cold rule-index micro-bench + bitmap/legacy parity check only",
    )
    parser.add_argument(
        "--plan", action="store_true",
        help="batched-vs-sequential PlanResources A/B + filter-AST parity gate only",
    )
    args = parser.parse_args()
    if args.index_only:
        sys.exit(index_only_main(smoke=args.smoke))
    if args.plan:
        sys.exit(plan_only_main(smoke=args.smoke))

    device = _device_or_exit()

    policies = list(parse_policies(bench_corpus.corpus_yaml(N_MODS)))
    print(f"policy documents: {len(policies)} ({N_MODS} mods)", flush=True)
    t_build0 = time.perf_counter()
    rt = build_rule_table(compile_policy_set(policies))
    build_s = time.perf_counter() - t_build0
    params = EvalParams()
    inputs = bench_corpus.requests(BATCH, N_MODS)
    decisions_per_batch = sum(len(i.actions) for i in inputs)

    # host reference: the same evaluator on the numpy backend. Reported
    # beside the device figures, never as the headline.
    ev_np = TpuEvaluator(rt, use_jax=False)
    numpy_rate, _, _, _ = _measure(ev_np, inputs, params, decisions_per_batch, "numpy (host)")

    ev = TpuEvaluator(rt, use_jax=True)
    rate, _, warm_excess, outs = _measure(
        ev, inputs, params, decisions_per_batch, f"jax-{device['platform']}"
    )
    compile_s = round(warm_excess, 2)  # first-call excess ≈ trace + XLA compile

    # sustained streaming mode: a serving loop keeps several batches in
    # flight (submit/collect), so the device's transfer+compute latency
    # overlaps host pack/assembly of neighboring batches instead of stalling
    # each call
    depth = 3
    tickets = []
    stream_outs = None
    t0 = time.perf_counter()
    for _ in range(ITERS):
        tickets.append(ev.submit(inputs, params))
        if len(tickets) >= depth:
            # assembly timed; keep the latest batch so output verification
            # exercises what the streaming path actually produced
            stream_outs = ev.collect(tickets.pop(0))
    while tickets:
        stream_outs = ev.collect(tickets.pop(0))
    stream_wall = time.perf_counter() - t0
    stream_rate = decisions_per_batch * ITERS / stream_wall
    print(
        f"jax-{device['platform']} streaming (depth {depth}): sustained {stream_rate:.0f} dec/s "
        f"over {ITERS} in-flight batches",
        flush=True,
    )

    # characterize the host<->device link: per-transfer put/fetch costs next
    # to the per-batch figures above say how much of a batch is transfer
    link = _probe_link()
    print(f"link: {json.dumps(link)}", flush=True)

    # adversarial (memo-cold) phase on the device backend: every iteration
    # uses fresh inputs with globally-unique attribute values and principal
    # ids (bench_corpus.requests_unique), defeating the assembly/shape/value
    # memos — this bounds worst-case steady-state throughput. Input
    # generation happens OUTSIDE the timed region.
    cold_sets = [
        bench_corpus.requests_unique(BATCH, N_MODS, seed=100 + i) for i in range(4)
    ]
    cold_times = []
    # structural warmup with a DISJOINT seed so the timed sets' value and
    # assembly memos stay cold
    ev.check(bench_corpus.requests_unique(BATCH, N_MODS, seed=999), params)
    for cs in cold_sets:
        t0 = time.perf_counter()
        cold_outs = ev.check(cs, params)
        cold_times.append(time.perf_counter() - t0)
    cold_dec = sum(len(i.actions) for i in cold_sets[0])
    cold_rate = cold_dec / statistics.median(cold_times)
    cold_allow = sum(
        1 for o in cold_outs for e in o.actions.values() if e.effect == "EFFECT_ALLOW"
    )
    assert cold_allow > 0, "memo-cold workload produced no allows — corpus is broken"
    print(f"memo-cold (jax-{device['platform']}): median {cold_rate:.0f} dec/s", flush=True)

    for label, o in (("batch", outs), ("streaming", stream_outs)):
        allow = sum(1 for r in o for e in r.actions.values() if e.effect == "EFFECT_ALLOW")
        assert allow > 0, f"{label} workload produced no allows — corpus is broken"

    # coverage fractions on the faithful corpus: how much of the workload
    # the device path actually serves, and how much rides host predicate
    # columns or falls back to the oracle
    total_inputs = sum(ev.stats[k] for k in ("device_inputs", "oracle_inputs", "trivial_inputs"))
    n_kernels = len(ev.lowered.compiler.kernels)
    n_device_kernels = sum(1 for k in ev.lowered.compiler.kernels if k.emit is not None)
    n_preds = len(ev.lowered.compiler.preds)
    coverage = {
        "device_input_fraction": round(ev.stats["device_inputs"] / max(total_inputs, 1), 4),
        "oracle_input_fraction": round(ev.stats["oracle_inputs"] / max(total_inputs, 1), 4),
        "condition_kernels": n_kernels,
        "device_kernels": n_device_kernels,
        "host_predicate_columns": n_preds,
    }
    print(f"coverage: {json.dumps(coverage)}", flush=True)
    print(f"table build: {build_s:.2f} s; jit compile: {compile_s} s", flush=True)

    # median batch rate: robust to noisy-neighbor spikes on shared hosts
    # without inflating toward the best-case single iteration
    record = {
        "metric": "check_decisions_per_sec",
        "value": round(rate, 1),
        "unit": f"decisions/s/{device['platform']}",
        "vs_baseline": round(rate / REFERENCE_DECISIONS_PER_SEC, 2),
        "backend": f"jax-{device['platform']}",
        "device": device,
        "streaming": round(stream_rate, 1),
        "memo_cold": round(cold_rate, 1),
        "host_numpy_decisions_per_sec": round(numpy_rate, 1),
        "jit_compile_s": compile_s,
        "compile": _compile_economy(),
        "link": link,
    }
    print(json.dumps(record))


if __name__ == "__main__":
    main()
