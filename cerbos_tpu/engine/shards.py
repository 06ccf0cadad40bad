"""Sharded serving pool: N per-device batcher lanes behind one front door.

Evaluation over a data mesh (``__graft_entry__.dryrun_multichip``) spreads
one batch over the devices, but a serving path that drives a single
evaluator uses one of them. Here the pool owns one ``BatchingEvaluator`` lane
per shard, each wrapping a ``TpuEvaluator`` clone pinned to its device (or
per-shard mesh slice) via ``parallel.mesh.shard_devices``. The clones share
the expensive read-only artifacts — rule table, lowered device tables —
and own everything the hot path mutates (packer, jit cache, memos), so the
lanes run lock-free against each other.

Routing is per request at admission: ``least_loaded`` picks the routable
lane with the fewest queued + in-flight requests (ties broken round-robin),
``round_robin`` rotates blindly. A lane is routable when its drain loop is
alive, its breaker admits device traffic, and it has not quarantined any of
the request's inputs — so the pool steers around a sick shard instead of
letting that lane's oracle fallback eat the request.

Fault isolation is the point (docs/ROBUSTNESS.md): every lane carries its
own ``DeviceHealth`` breaker, quarantine set, bisect thread, and
flight-recorder lane (``shard=`` on metrics and flight records). One sick
chip trips ONE breaker; the router sends traffic to the other N-1 lanes and
service degrades to (N-1)/N device capacity instead of 0/N. Requests
already queued or in flight on the sick lane recover individually through
the lane's own ``_BatchFailed`` → oracle machinery — zero lost requests.
Recovery is also per-lane: probe batches half-open only the sick shard.
"""

from __future__ import annotations

import logging
import threading
from concurrent.futures import Future
from typing import Any, Optional, Sequence

from ..observability import SpanContext
from . import types as T
from .batcher import BatchingEvaluator
from .health import STATE_CLOSED, STATE_HALF_OPEN, STATE_OPEN

_log = logging.getLogger("cerbos_tpu.engine.shards")

ROUTING_LEAST_LOADED = "least_loaded"
ROUTING_ROUND_ROBIN = "round_robin"


class ShardedBatchingEvaluator:
    """Routes each request to one of N ``BatchingEvaluator`` shard lanes.

    Implements the same dispatch surface as a single BatchingEvaluator
    (``check``/``check_async``/``close``/``stats``), so the engine, the IPC
    ticket server, and ``Core.batcher`` plumbing are shard-count agnostic.
    """

    supports_deadline = True
    supports_waterfall = True
    supports_pclass = True

    def __init__(
        self,
        shards: Sequence[BatchingEvaluator],
        routing: str = ROUTING_LEAST_LOADED,
    ):
        if not shards:
            raise ValueError("sharded pool needs at least one shard lane")
        self.shards = list(shards)
        self.routing = routing if routing in (ROUTING_LEAST_LOADED, ROUTING_ROUND_ROBIN) else ROUTING_LEAST_LOADED
        self._rr = 0
        self._rr_lock = threading.Lock()
        # per-shard routed-request counts: what routing_imbalance() reads
        # (max/min over these ≈ 1.0 means fair routing)
        self.routed = [0] * len(self.shards)

    # -- routing ------------------------------------------------------------

    def _next_rr(self) -> int:
        with self._rr_lock:
            i = self._rr
            self._rr += 1
        return i

    def route(self, inputs: Optional[Sequence[T.CheckInput]] = None) -> BatchingEvaluator:
        """Pick the lane for one request. Prefers routable lanes (alive,
        breaker closed, inputs not quarantined there); if none qualify, falls
        back to round-robin over ALL lanes so the chosen lane's own admission
        ladder serves its oracle / runs its probe machinery."""
        n = len(self.shards)
        if n == 1:
            lane = self.shards[0]
            self.routed[0] += 1
            return lane
        start = self._next_rr()
        # probe trickle: a breaker-open lane whose backoff has elapsed gets
        # this one request as a probe donor — the lane serves it via its
        # oracle and rides its inputs on the probe batch, so recovery
        # half-opens ONLY the sick shard while the router keeps live
        # traffic on the healthy ones
        for k in range(n):
            i = (start + k) % n
            h = self.shards[i].health
            if h is not None and h.probe_due():
                self.routed[i] += 1
                return self.shards[i]
        if self.routing == ROUTING_ROUND_ROBIN:
            order = [(start + k) % n for k in range(n)]
            idx = next((i for i in order if self.shards[i].routable(inputs)), order[0])
        else:
            best: Optional[int] = None
            best_load = None
            for k in range(n):
                i = (start + k) % n  # rotate tie-breaks across lanes
                lane = self.shards[i]
                if not lane.routable(inputs):
                    continue
                load = lane.load()
                if best_load is None or load < best_load:
                    best, best_load = i, load
            idx = best if best is not None else start % n
        self.routed[idx] += 1
        return self.shards[idx]

    # -- dispatch surface ---------------------------------------------------

    def check(
        self,
        inputs: Sequence[T.CheckInput],
        params: Optional[T.EvalParams] = None,
        deadline: Optional[float] = None,
        wf: Optional[Any] = None,
        pclass: Optional[str] = None,
    ) -> list[T.CheckOutput]:
        return self.route(inputs).check(
            inputs, params, deadline=deadline, wf=wf, pclass=pclass
        )

    def check_async(
        self,
        inputs: Sequence[T.CheckInput],
        params: Optional[T.EvalParams] = None,
        deadline: Optional[float] = None,
        ctx: Optional[SpanContext] = None,
        wf: Optional[Any] = None,
        pclass: Optional[str] = None,
    ) -> Future:
        return self.route(inputs).check_async(
            inputs, params, deadline=deadline, ctx=ctx, wf=wf, pclass=pclass
        )

    def configure_lanes(self, lane_confs: Sequence[tuple]) -> None:
        """Install the admission controller's priority-lane layout on every
        shard lane: each shard schedules its own queue, but the class →
        (priority, weight, budget) map is pool-wide."""
        for lane in self.shards:
            lane.configure_lanes(lane_confs)

    def lane_depths(self) -> dict:
        """Pool-wide queued depth per priority lane (debug/overload view)."""
        out: dict = {}
        for lane in self.shards:
            for name, depth in lane.lane_depths().items():
                out[name] = out.get(name, 0) + depth
        return out

    def close(self) -> None:
        for lane in self.shards:
            lane.close()

    # -- policy reload ------------------------------------------------------

    def refresh_shards(self, rule_table: Any) -> None:
        """After a policy swap re-lowered the SHARED lowered table (the base
        evaluator's refresh hook), point every clone at the new rule table
        and drop its derived caches."""
        for lane in self.shards:
            # unwrap a FaultInjector: setattr on the wrapper would shadow,
            # not update, the real evaluator's table
            ev = getattr(lane.evaluator, "_ev", lane.evaluator)
            ev.rule_table = rule_table
            ev.invalidate()

    def swap_lanes(self) -> list[Any]:
        """The per-shard BatchingEvaluators a rollout cutover must park at a
        flight boundary before mutating the shared lowered tables — the
        clones all read those tables, so the barrier is pool-wide."""
        return list(self.shards)

    # -- aggregate views ----------------------------------------------------

    @property
    def evaluator(self) -> Any:
        """The first lane's evaluator — gives shard-count-agnostic plumbing
        (oracle fallbacks, table reads) something to hold."""
        return self.shards[0].evaluator

    @property
    def stats(self) -> dict:
        """Pool-wide totals in the single-batcher stats shape, plus the
        routing distribution."""
        keys = self.shards[0].stats.keys()
        out = {k: sum(lane.stats[k] for lane in self.shards) for k in keys}
        out["inflight_peak"] = max(lane.stats["inflight_peak"] for lane in self.shards)
        out["routed"] = list(self.routed)
        return out

    def routing_imbalance(self) -> float:
        """max/min over per-shard routed counts (1.0 = perfectly fair);
        counts of 0 make it infinity, reported as 0.0 before any traffic."""
        if not any(self.routed):
            return 0.0
        lo = min(self.routed)
        return float("inf") if lo == 0 else max(self.routed) / lo

    def health_state(self) -> str:
        """Aggregate breaker state for readiness: the pool is 'closed' while
        ANY lane takes device traffic (a sick shard degrades capacity, not
        availability), 'half_open' when the best lane is probing, and 'open'
        only when every lane refuses."""
        states = [
            lane.health.state for lane in self.shards if lane.health is not None
        ]
        if not states or STATE_CLOSED in states:
            return STATE_CLOSED
        if STATE_HALF_OPEN in states:
            return STATE_HALF_OPEN
        return STATE_OPEN


def build_shard_pool(
    base_evaluator: Any,
    *,
    n_shards: int = 0,
    per_shard_inflight: int = 0,
    routing: str = ROUTING_LEAST_LOADED,
    max_batch: int = 4096,
    max_wait_ms: float = 2.0,
    request_timeout_s: float = 30.0,
    inflight_depth: int = 3,
    quarantine_max: int = 128,
    breaker_conf: Optional[dict] = None,
    fault_spec: str = "",
) -> ShardedBatchingEvaluator:
    """Build the pool: clone the base evaluator once per shard placement,
    wrap each in its own fault domain (breaker + batcher lane), and front
    them with the router.

    ``fault_spec`` is the chaos grammar from ``engine/faults.py``; its
    ``shard:N`` knob scopes the injected faults to that one lane (the
    shard-kill chaos drill), otherwise every lane gets the wrapper.
    """
    from ..parallel.mesh import shard_devices
    from .faults import FaultInjector, parse_fault_spec
    from .health import DeviceHealth

    breaker_conf = breaker_conf or {}
    placements = shard_devices(n_shards or None)
    use_jax = bool(getattr(base_evaluator, "use_jax", False))
    if not use_jax:
        # numpy backend has no devices to spread over; still honor the
        # requested shard count so the fault-domain topology is testable
        n = max(1, int(n_shards)) if n_shards else len(placements)
        placements = [None] * n

    fault_shard: Optional[int] = None
    if fault_spec:
        knobs = parse_fault_spec(fault_spec)
        if knobs.get("shard") is not None:
            fault_shard = int(knobs["shard"])

    inflight = int(per_shard_inflight) or int(inflight_depth)
    lanes: list[BatchingEvaluator] = []
    for i, devices in enumerate(placements):
        ev = base_evaluator.shard_clone(devices, shard_id=i)
        dispatch: Any = ev
        if fault_spec and (fault_shard is None or fault_shard == i):
            dispatch = FaultInjector(ev, fault_spec)
        health = DeviceHealth(
            failure_threshold=int(breaker_conf.get("failureThreshold", 5)),
            timeout_rate_threshold=float(breaker_conf.get("timeoutRateThreshold", 0.5)),
            timeout_window_s=float(breaker_conf.get("timeoutWindowSeconds", 30)),
            timeout_min_samples=int(breaker_conf.get("timeoutMinSamples", 10)),
            probe_backoff_base_s=float(breaker_conf.get("probeBackoffBaseMs", 500)) / 1000.0,
            probe_backoff_cap_s=float(breaker_conf.get("probeBackoffCapMs", 30000)) / 1000.0,
            probe_timeout_s=float(breaker_conf.get("probeTimeoutMs", 5000)) / 1000.0,
            enabled=bool(breaker_conf.get("enabled", True)),
            shard_id=i,
        )
        lanes.append(
            BatchingEvaluator(
                dispatch,
                max_batch=max_batch,
                max_wait_ms=max_wait_ms,
                request_timeout_s=request_timeout_s,
                max_inflight=inflight,
                health=health,
                quarantine_max=quarantine_max,
                shard_id=i,
            )
        )
    _log.info(
        "sharded serving pool: %d shard(s), routing=%s, per-shard inflight=%d",
        len(lanes),
        routing,
        inflight,
    )
    return ShardedBatchingEvaluator(lanes, routing=routing)
