"""Wiring: config → store → compiler → rule table → engine → server.

Behavioral reference: internal/server/common.go:36-152 (InitializeCerbosCore):
audit log → store → policy loader → rule table → schema manager → rule-table
manager (subscribed to store events) → engine → aux data.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import Any, Optional

from . import bootclock
from .audit import new_audit_log
from .auxdata import AuxDataManager
from .config import Config
from .engine import EvalParams
from .engine.engine import Engine
from .plan import Planner
from .ruletable.manager import RuleTableManager
from .schema import SchemaManager
from .server.service import CerbosService, ServiceLimits
from .storage import new_store

_log = logging.getLogger("cerbos_tpu.bootstrap")


@dataclass
class Core:
    config: Config
    store: Any
    manager: RuleTableManager
    engine: Engine
    service: CerbosService
    schema_mgr: SchemaManager
    audit_log: Any
    tpu_evaluator: Any = None
    batcher: Any = None
    sentinel: Any = None
    rollout: Any = None

    def close(self) -> None:
        if self.rollout is not None:
            self.rollout.close()
        if self.sentinel is not None:
            self.sentinel.close()
        if self.batcher is not None:
            self.batcher.close()
        if self.audit_log is not None:
            self.audit_log.close()
        self.store.close()


@dataclass
class Prebuilt:
    """Expensive artifacts built once before forking worker processes.

    The parent builds the rule table (and, if enabled, the lowered device
    tables inside a TpuEvaluator) with no background threads running, then
    forks; children adopt these via ``initialize(..., prebuilt=...)`` so the
    big read-only structures are COW-shared instead of rebuilt per worker
    (ref: the reference loads once and shares across its goroutine pool,
    engine.go:74-88 — processes + COW are the Python analogue).
    """

    rule_table: Any
    tpu_evaluator: Any = None


def _make_evaluator(rule_table: Any, engine_conf: dict, schema_mgr: Any = None) -> Any:
    """The single construction site for TpuEvaluator config wiring, shared
    by single-process initialize() and the pre-fork prebuild() path."""
    import os as _os

    from .tpu import TpuEvaluator

    tpu_conf = engine_conf.get("tpu", {})
    backend = _os.environ.get("CERBOS_TPU_BACKEND", tpu_conf.get("backend", "jax"))
    bootclock.mark(bootclock.OTHER)
    evaluator = TpuEvaluator(
        rule_table,
        globals_=engine_conf.get("globals", {}) or {},
        schema_mgr=schema_mgr,
        max_roles=int(tpu_conf.get("maxRoles", 8)),
        max_candidates=int(tpu_conf.get("maxCandidates", 32)),
        max_depth=int(tpu_conf.get("maxDepth", 8)),
        use_jax=backend != "numpy",
        min_device_batch=int(tpu_conf.get("minDeviceBatch", 16)),
        pipeline_chunk=int(tpu_conf.get("pipelineChunk", 4096)),
    )
    bootclock.mark(bootclock.LOWER)
    return evaluator


def prebuild(config: Config, use_tpu: Optional[bool] = None) -> Prebuilt:
    """Parse → compile → build → lower, with no threads or listeners."""
    bootclock.mark(bootclock.OTHER)
    store = new_store(config.section("storage"))
    bootclock.mark(bootclock.LOAD)
    try:
        manager = RuleTableManager(store)
        rule_table = manager.rule_table
        # the table's identity, once, before any fork: the owner publishes it
        # as epoch 1's and every front end compares its own with it
        from .engine.rollout import bundle_hash_of

        bundle_hash_of(rule_table)
        engine_conf = config.section("engine")
        tpu_conf = engine_conf.get("tpu", {})
        tpu_enabled = tpu_conf.get("enabled", True) if use_tpu is None else use_tpu
        tpu_evaluator = None
        if tpu_enabled:
            tpu_evaluator = _make_evaluator(rule_table, engine_conf)
        return Prebuilt(rule_table=rule_table, tpu_evaluator=tpu_evaluator)
    finally:
        store.close()


def initialize(
    config: Config,
    use_tpu: Optional[bool] = None,
    prebuilt: Optional[Prebuilt] = None,
    role: str = "standalone",
    ipc_socket: Optional[str] = None,
    worker_label: str = "",
) -> Core:
    """``role`` selects the process topology this Core participates in:

    - ``standalone`` (default) — the single-process PDP: device evaluator,
      batcher, warmup, everything in this process.
    - ``frontend`` — one of N HTTP/gRPC front-end processes: no device, no
      warmup; checks ride the ticket queue at ``ipc_socket`` to the shared
      batcher process via ``engine/ipc.RemoteBatcherClient``, readiness
      mirrors the batcher's, and the COW-shared rule table backs the local
      CPU oracle: the fallback when the batcher is down or refuses, and the
      answer to a request under the owner's ``min_device_batch`` while that
      table is the owner's committed one (engine/ipc.py).

    The batcher process itself uses :func:`build_batcher_ipc` on top of a
    standalone Core.

    The boot clock's marks (``bootclock.py``) do nothing in a process that
    began none; a front end drops the one it inherited from the pool's parent.
    """
    if role == "frontend":
        bootclock.abandon()
    audit_log = new_audit_log(config.section("audit"))
    bootclock.mark(bootclock.OTHER)
    store = new_store(config.section("storage"))
    bootclock.mark(bootclock.LOAD)

    schema_mgr = SchemaManager(store, enforcement=config.get("schema.enforcement", "none"))

    engine_conf = config.section("engine")
    eval_params = EvalParams(
        globals=engine_conf.get("globals", {}) or {},
        default_policy_version=engine_conf.get("defaultPolicyVersion", "default"),
        default_scope=engine_conf.get("defaultScope", ""),
        lenient_scope_search=bool(engine_conf.get("lenientScopeSearch", False)),
    )

    manager = RuleTableManager(store, prebuilt_table=prebuilt.rule_table if prebuilt else None)

    tpu_conf = engine_conf.get("tpu", {})
    flight_conf = tpu_conf.get("flightRecorder", {}) or {}
    from .engine import flight as _flight

    _flight.configure(
        capacity=int(flight_conf.get("capacity", _flight.DEFAULT_CAPACITY)),
        enabled=bool(flight_conf.get("enabled", True)),
    )
    _flight.install_sigquit_dump()
    # on-demand device profiling endpoint (off unless explicitly enabled)
    prof_conf = tpu_conf.get("profiler", {}) or {}
    from .tpu import profiler as _profiler

    _profiler.configure(
        enabled=bool(prof_conf.get("enabled", False)),
        dir=str(prof_conf.get("dir", "") or ""),
        max_artifacts=int(prof_conf.get("maxArtifacts", 4)),
        max_seconds=float(prof_conf.get("maxSeconds", 30)),
    )
    # per-request latency-budget waterfall + goodput accounting; the
    # saturation pressure monitor binds its role-specific signal sources
    # further down, once the batcher topology exists
    budget_conf = tpu_conf.get("latencyBudget", {}) or {}
    from .engine import budget as _budget

    _budget.tracker().configure(
        enabled=bool(budget_conf.get("enabled", True)),
        slow_capacity=int(budget_conf.get("slowRingCapacity", 64)),
        slow_threshold_ms=float(budget_conf.get("slowThresholdMs", 250)),
    )
    _flight.bind_slow_requests(_budget.tracker().slow_dump)
    pressure_conf = tpu_conf.get("pressure", {}) or {}
    from .engine import pressure as _pressure

    _pressure.monitor().configure(
        enabled=bool(pressure_conf.get("enabled", True)),
        window_s=float(pressure_conf.get("windowSec", 30)),
        interval_s=float(pressure_conf.get("intervalMs", 500)) / 1000.0,
    )
    # overload control: compile the admission classes (the rule-table idiom
    # — declarative globs → compiled matchers, once) and the brownout
    # ladder; both servers and the batcher lanes consult the compiled form
    overload_conf = config.section("overload")
    from .engine import admission as _admission
    from .engine import brownout as _brownout

    _admission.controller().configure(overload_conf)
    _brownout.controller().configure(overload_conf.get("brownout") or {})

    # fault injection (chaos testing): CERBOS_TPU_FAULTS env wins over the
    # engine.tpu.faults config key; empty means no wrapper at all. Parsed
    # once here — the rollout controller reads the swap_fail knob, the
    # batcher lanes get the device knobs.
    import os as _os

    fault_spec = _os.environ.get("CERBOS_TPU_FAULTS", "") or str(tpu_conf.get("faults", "") or "")
    from .engine.faults import parse_fault_spec as _parse_faults

    fault_knobs = _parse_faults(fault_spec) if fault_spec else {}

    # safe policy rollout: every storage event now routes through the
    # staged shadow-build → analyzer-gate → epoch-versioned cutover →
    # canary ladder instead of the bare build-and-swap; the swap hooks
    # that used to chain through manager.on_swap register below as named
    # cutover subscribers. Front ends run the controller in passive mode:
    # no epoch authority (that is the batcher's), just the subscriber
    # registry over the local oracle-fallback table.
    from .engine import rollout as _rollout

    rollout_ctl = _rollout.RolloutController(
        manager,
        conf=tpu_conf.get("rollout", {}) or {},
        mode="passive" if role == "frontend" else "full",
        globals_=engine_conf.get("globals", {}) or {},
        schema_mgr=schema_mgr,
        faults=fault_knobs,
    )
    manager.rollout = rollout_ctl
    _rollout.install(rollout_ctl)
    # validators of the schemas the policies name: built now and at every
    # cutover, not inside the first request that meets a ref (a store event
    # has emptied the cache by the time its table is cut over to)
    schema_mgr.load(manager.rule_table)
    rollout_ctl.subscribe("schemas", lambda ep: schema_mgr.load(ep.rule_table))

    tpu_enabled = tpu_conf.get("enabled", True) if use_tpu is None else use_tpu
    tpu_evaluator = None
    dispatch_evaluator = None
    batcher = None
    health = None
    if role == "frontend":
        from .engine.ipc import RemoteBatcherClient, default_socket_path

        shared_conf = tpu_conf.get("sharedBatcher", {}) or {}
        client = RemoteBatcherClient(
            ipc_socket or default_socket_path(str(shared_conf.get("socketPath", "") or "")),
            manager.rule_table,
            schema_mgr=schema_mgr,
            params=eval_params,
            request_timeout_s=float(
                shared_conf.get("requestTimeoutMs", tpu_conf.get("requestTimeoutMs", 30000))
            )
            / 1000.0,
            worker_label=worker_label or "fe",
            status_poll_s=float(shared_conf.get("statusPollMs", 500)) / 1000.0,
            transport=str(shared_conf.get("transport", "shm") or "shm"),
            ring_kib=int(shared_conf.get("ringKiB", 1024)),
        )
        dispatch_evaluator = client
        # Core.batcher doubles as "the thing check() awaits on" for the
        # server's dispatch decision and for close(); the client fits both
        batcher = client

        # policy reload: keep the local oracle fallback on the new table
        rollout_ctl.subscribe("client", lambda ep, _c=client: _c.refresh_table(ep.rule_table))
    elif tpu_enabled:
        if prebuilt is not None and prebuilt.tpu_evaluator is not None:
            # adopt the pre-lowered evaluator (COW-shared across forked
            # workers); only the per-process schema manager needs rewiring
            tpu_evaluator = prebuilt.tpu_evaluator
            tpu_evaluator.schema_mgr = schema_mgr
        else:
            tpu_evaluator = _make_evaluator(manager.rule_table, engine_conf, schema_mgr)
        if getattr(tpu_evaluator, "use_jax", False):
            # this process dispatches to the device: open it NOW (after any
            # fork, never in prebuild) so a backend that cannot initialize
            # fails the boot with its own error (jitcache.DeviceInitError).
            # Only faults after a successful boot are the breaker's.
            from .tpu import jitcache

            bootclock.mark(bootclock.OTHER)
            jitcache.open_device()
            bootclock.mark(bootclock.DEVICE)

        def _sub_evaluator(ep, _ev=tpu_evaluator) -> None:
            # re-lower the SHARED lowered table first; every later subscriber
            # (shard clones, engine, planners) sees the refreshed device
            # state. Runs inside the drain barrier: no flight is in the air.
            _ev.rule_table = ep.rule_table
            _ev.lowered.table = ep.rule_table
            _ev.refresh()

        rollout_ctl.subscribe("evaluator", _sub_evaluator)
        dispatch_evaluator = tpu_evaluator
        mesh_conf = tpu_conf.get("mesh", {}) or {}
        shards_knob = mesh_conf.get("shards", 0)
        n_shards = 0
        if str(shards_knob).strip().lower() == "auto":
            n_shards = -1  # one shard per visible device
        elif shards_knob:
            n_shards = int(shards_knob)
        sharded = (
            tpu_conf.get("requestBatching", True)
            and (n_shards == -1 or n_shards > 1)
            and hasattr(tpu_evaluator, "shard_clone")
        )
        if sharded:
            # sharded serving pool: one batcher lane per device shard, each
            # with its own breaker/quarantine/flight lane; faults (optionally
            # shard-scoped via the shard:N knob) wrap inside the lane
            from .engine.shards import build_shard_pool

            batcher = build_shard_pool(
                tpu_evaluator,
                n_shards=0 if n_shards == -1 else n_shards,
                per_shard_inflight=int(mesh_conf.get("perShardInflight", 0)),
                routing=str(mesh_conf.get("routing", "least_loaded")),
                max_batch=int(tpu_conf.get("maxBatch", 4096)),
                max_wait_ms=float(tpu_conf.get("batchWindowMs", 2.0)),
                request_timeout_s=float(tpu_conf.get("requestTimeoutMs", 30000)) / 1000.0,
                inflight_depth=int(tpu_conf.get("inflightDepth", 3)),
                quarantine_max=int(tpu_conf.get("quarantineMax", 128)),
                breaker_conf=tpu_conf.get("breaker", {}) or {},
                fault_spec=fault_spec,
            )
            dispatch_evaluator = batcher

            # the evaluator subscriber re-lowered the SHARED table; the
            # clones only need their table pointer + derived caches refreshed
            rollout_ctl.subscribe(
                "shards", lambda ep, _pool=batcher: _pool.refresh_shards(ep.rule_table)
            )
        else:
            if fault_spec:
                from .engine.faults import FaultInjector

                dispatch_evaluator = FaultInjector(tpu_evaluator, fault_spec)
            if tpu_conf.get("requestBatching", True):
                from .engine.batcher import BatchingEvaluator, DeviceHealth

                breaker_conf = tpu_conf.get("breaker", {}) or {}
                health = DeviceHealth(
                    failure_threshold=int(breaker_conf.get("failureThreshold", 5)),
                    timeout_rate_threshold=float(breaker_conf.get("timeoutRateThreshold", 0.5)),
                    timeout_window_s=float(breaker_conf.get("timeoutWindowSeconds", 30)),
                    timeout_min_samples=int(breaker_conf.get("timeoutMinSamples", 10)),
                    probe_backoff_base_s=float(breaker_conf.get("probeBackoffBaseMs", 500)) / 1000.0,
                    probe_backoff_cap_s=float(breaker_conf.get("probeBackoffCapMs", 30000)) / 1000.0,
                    probe_timeout_s=float(breaker_conf.get("probeTimeoutMs", 5000)) / 1000.0,
                    enabled=bool(breaker_conf.get("enabled", True)),
                )
                batcher = BatchingEvaluator(
                    dispatch_evaluator,
                    max_batch=int(tpu_conf.get("maxBatch", 4096)),
                    max_wait_ms=float(tpu_conf.get("batchWindowMs", 2.0)),
                    request_timeout_s=float(tpu_conf.get("requestTimeoutMs", 30000)) / 1000.0,
                    max_inflight=int(tpu_conf.get("inflightDepth", 3)),
                    health=health,
                    quarantine_max=int(tpu_conf.get("quarantineMax", 128)),
                )
                dispatch_evaluator = batcher

    # readiness (split from liveness) + the compile-economy warmup driver:
    # /_cerbos/ready and the gRPC health service withhold traffic until the
    # dominant device layouts are compiled, then report degraded-but-live
    # whenever the breaker routes around the device
    from .engine import readiness as _readiness

    rstate = _readiness.state()
    if role == "frontend":
        # readiness is the SHARED batcher's readiness: 503 until its warmup
        # pre-compiles finish, degraded-but-live when it dies (the local
        # oracle keeps serving) — never a 0/N outage
        rstate.bind_remote(dispatch_evaluator.remote_status)
    elif batcher is not None and hasattr(batcher, "health_state"):
        # sharded pool: degraded only when EVERY lane's breaker refuses —
        # one sick shard is a capacity event, not an availability event
        rstate.bind_health(batcher.health_state)
    else:
        rstate.bind_health((lambda: health.state) if health is not None else None)

    # parity sentinel: online shadow-oracle sampling of completed device
    # batches. It attaches wherever real batcher lanes live — standalone,
    # the shared-batcher process of the --frontends topology, and every
    # lane of the sharded pool. Front ends carry no device, so nothing to
    # sample there.
    sentinel = None
    if role != "frontend" and batcher is not None:
        from .engine import sentinel as _sentinel

        s = _sentinel.from_config(tpu_conf.get("paritySentinel", {}) or {})
        if s.enabled:
            sentinel = s.attach(batcher)
    rstate.bind_parity(sentinel.storm_shards if sentinel is not None else None)

    # rollout wiring that needs the serving topology: the sentinel drives
    # the canary (boosted sampling + divergence triggers), the batcher
    # lanes are what the cutover barrier parks, and the boot table becomes
    # epoch 1. Front ends carry neither — their epoch arrives in STATUS
    # frames from the batcher process.
    rollout_ctl.sentinel = sentinel
    if role != "frontend":
        if batcher is not None and hasattr(batcher, "swap_lanes"):
            rollout_ctl.bind_lanes(batcher.swap_lanes())
        elif batcher is not None:
            rollout_ctl.bind_lanes([batcher])
        rollout_ctl.seed(manager.rule_table)
        rstate.bind_epoch(rollout_ctl.epoch_info)

    # pressure monitor: bind whatever saturation sources this role actually
    # has (zero-arg callables, read defensively at sample time) and start
    # the ticker so the rolling windows stay warm between scrapes
    mon = _pressure.monitor()
    mon.bind(decisions=lambda: _budget.tracker().m_decisions.value)
    if role == "frontend":
        client = batcher
        mon.bind(
            ipc=lambda c=client, s=shared_conf: (
                len(c._pending),
                int(s.get("maxOutstanding", 4096)),
            ),
            fallbacks=lambda c=client: c.stats["oracle_fallbacks"],
            breaker=lambda c=client: ((c._last_status or {}).get("breaker", "")),
        )
    elif batcher is not None and hasattr(batcher, "shards"):
        pool = batcher
        mon.bind(
            queue=lambda p=pool: (
                sum(l.load() for l in p.shards),
                sum(l.max_batch for l in p.shards),
            ),
            inflight=lambda p=pool: (
                sum(l.m_inflight.value for l in p.shards),
                sum(l.max_inflight for l in p.shards),
            ),
            fallbacks=lambda p=pool: p.stats["oracle_fallbacks"],
            breaker=pool.health_state,
        )
    elif batcher is not None:
        b = batcher
        mon.bind(
            queue=lambda b=b: (b.load(), b.max_batch),
            inflight=lambda b=b: (b.m_inflight.value, b.max_inflight),
            fallbacks=lambda b=b: b.stats["oracle_fallbacks"],
            breaker=(lambda h=health: h.state) if health is not None else None,
        )
    if sentinel is not None:
        mon.bind(parity=sentinel.storm_shards)
    if tpu_evaluator is not None:
        from .tpu import compilestats as _compilestats

        mon.bind(storms=lambda: _compilestats.stats().detector.storms)
    mon.start_ticker()

    # staged brownout: driven by this process's pressure samples (observer),
    # shedding where the work lives HERE — audit/plan/admission at a front
    # end, parity in the device-owning process — and surfacing the deepest
    # engaged stage through readiness. Appliers are reversible by contract.
    bctl = _brownout.controller()
    if audit_log is not None:
        bctl.bind_applier("shed_audit", audit_log.set_shed)
    if sentinel is not None:
        bctl.bind_applier("shed_parity", sentinel.set_shed)
    bctl.bind_applier("shed_low_priority", _admission.controller().set_shed)
    mon.add_observer(bctl.observe)
    rstate.bind_brownout(bctl.stage_name)
    # priority lanes: whatever owns a request queue in this process gets the
    # compiled class layout (single batcher or every shard lane; front ends
    # carry no queue — their tickets are prioritized in the batcher process)
    if batcher is not None and hasattr(batcher, "configure_lanes"):
        batcher.configure_lanes(_admission.controller().lane_confs())

    warm_conf = tpu_conf.get("warmup", {}) or {}
    if role == "frontend":
        pass
    elif tpu_enabled and tpu_evaluator is not None and bool(warm_conf.get("enabled", False)):
        from .tpu.warmup import WarmupDriver

        # sharded pool: every lane's clone owns its own jit cache, so warm
        # each shard before readiness opens (unwrap any FaultInjector — the
        # chaos wrapper must not fail warmup)
        warm_evs = None
        if batcher is not None and hasattr(batcher, "shards"):
            warm_evs = [getattr(l.evaluator, "_ev", l.evaluator) for l in batcher.shards]
        driver = WarmupDriver(
            tpu_evaluator,
            batch_sizes=[int(s) for s in (warm_conf.get("batchSizes") or [16, 64])],
            corpus=warm_conf.get("synthetic") or None,
            max_kinds=int(warm_conf.get("maxKinds", 8)),
            timeout_s=float(warm_conf.get("timeoutSeconds", 120)),
            readiness=rstate,
            evaluators=warm_evs,
        )
        rstate.begin_warmup(expected=driver.expected)
        if bool(warm_conf.get("background", True)):
            driver.start()
        else:
            driver.run()
    else:
        rstate.mark_ready()

    if tpu_evaluator is not None and getattr(tpu_evaluator, "use_jax", False):
        from .tpu import jitcache as _jitcache

        cache_status = _jitcache.status()
        _log.info(
            "xla persistent cache: enabled=%s dir=%s entries=%s warm=%s",
            cache_status["enabled"],
            cache_status["dir"],
            cache_status["entries"],
            cache_status["warm_at_enable"],
        )

    engine = Engine(
        manager.rule_table,
        schema_mgr=schema_mgr,
        eval_params=eval_params,
        tpu_evaluator=dispatch_evaluator,
        # with cross-request batching every request goes through the batcher;
        # otherwise small batches take the serial oracle path (engine.go:229-235)
        tpu_batch_threshold=1 if batcher is not None else int(tpu_conf.get("batchThreshold", 5)),
    )

    # keep the engine pointed at the latest table after cutovers
    def _sub_engine(ep) -> None:
        engine.rule_table = ep.rule_table
        # keep traffic on the batcher (it wraps the refreshed evaluator);
        # rewiring to the raw evaluator here would silently drop
        # cross-request batching after the first policy reload
        engine.tpu_evaluator = dispatch_evaluator

    rollout_ctl.subscribe("engine", _sub_engine)

    aux_mgr = AuxDataManager.from_config(config.section("auxData"))

    limits_conf = config.get("server.requestLimits", {}) or {}
    planner = Planner(manager.rule_table, schema_mgr=schema_mgr)
    rollout_ctl.subscribe("planner", lambda ep, _p=planner: setattr(_p, "rt", ep.rule_table))

    # static policy analysis: published at boot and republished on every
    # cutover so cerbos_tpu_policy_analysis_total and /_cerbos/debug/analysis
    # always describe the table currently serving. A gated rollout already
    # analyzed the shadow lowering — that report is republished verbatim;
    # ungated commits (rollout disabled, passive front ends) analyze fresh,
    # reusing the evaluator's lowering where one exists.
    from .tpu import analyze as _analyze

    engine_globals = dict(engine_conf.get("globals", {}) or {})

    def publish_analysis(rt) -> None:
        try:
            lowered = tpu_evaluator.lowered if tpu_evaluator is not None else None
            _analyze.publish(_analyze.analyze_table(rt, engine_globals, lowered=lowered))
        except Exception:
            _log.exception("policy analysis failed; keeping previous report")

    publish_analysis(manager.rule_table)

    def _sub_analysis(ep) -> None:
        if getattr(ep, "analysis_report", None) is not None:
            _analyze.publish(ep.analysis_report)
        else:
            publish_analysis(ep.rule_table)

    rollout_ctl.subscribe("analysis", _sub_analysis)

    # batched PlanResources: attach a BatchPlanner to the (first) batcher
    # lane so concurrent plan queries coalesce into vectorized partial-
    # evaluation flights on the plan lane. The planner owns its own lowered
    # table (no interner sharing with check batches) and refreshes on swap.
    plan_batcher = None
    plan_lane = None
    if batcher is not None:
        plan_lane = batcher.shards[0] if hasattr(batcher, "shards") else batcher
        if not hasattr(plan_lane, "plan_planner"):
            plan_lane = None
    if plan_lane is not None:
        from .plan import BatchPlanner

        try:
            batch_planner = BatchPlanner(
                manager.rule_table,
                schema_mgr=schema_mgr,
                globals_=engine_globals,
                use_jax=bool(getattr(tpu_evaluator, "use_jax", False)),
            )
            plan_lane.plan_planner = batch_planner
            plan_batcher = plan_lane
            rollout_ctl.subscribe(
                "batch-planner",
                lambda ep, _bp=batch_planner: _bp.refresh(ep.rule_table),
            )
        except Exception:
            _log.exception("batched planner unavailable; PlanResources stays sequential")

    service = CerbosService(
        engine,
        aux_data_mgr=aux_mgr,
        limits=ServiceLimits(
            max_actions_per_resource=int(limits_conf.get("maxActionsPerResource", 50)),
            max_resources_per_request=int(limits_conf.get("maxResourcesPerRequest", 50)),
        ),
        audit_log=audit_log,
        planner=planner,
        plan_batcher=plan_batcher,
    )
    return Core(
        config=config,
        store=store,
        manager=manager,
        engine=engine,
        service=service,
        schema_mgr=schema_mgr,
        audit_log=audit_log,
        tpu_evaluator=tpu_evaluator,
        batcher=batcher,
        sentinel=sentinel,
        rollout=rollout_ctl,
    )


def build_batcher_ipc(core: Core, socket_path: str):
    """Attach the ticket-queue server to a standalone Core, turning this
    process into the pool's shared batcher. The Core must have been built
    with request batching on (``engine.tpu.requestBatching``); front ends
    connect to ``socket_path`` and their tickets join the same drain loop,
    breaker, and quarantine as local traffic would."""
    import os as _os

    from .engine import readiness as _readiness
    from .engine.faults import parse_fault_spec
    from .engine.ipc import BatcherIpcServer

    if core.batcher is None:
        raise RuntimeError(
            "shared-batcher process requires engine.tpu.enabled and "
            "engine.tpu.requestBatching"
        )
    tpu_conf = core.config.section("engine").get("tpu", {})
    shared_conf = tpu_conf.get("sharedBatcher", {}) or {}
    fault_spec = _os.environ.get("CERBOS_TPU_FAULTS", "") or str(tpu_conf.get("faults", "") or "")
    faults = parse_fault_spec(fault_spec) if fault_spec else {}
    server = BatcherIpcServer(
        socket_path,
        core.batcher,
        readiness=_readiness.state().snapshot,
        max_outstanding=int(shared_conf.get("maxOutstanding", 4096)),
        faults=faults,
        transport=str(shared_conf.get("transport", "shm") or "shm"),
        sentinel=core.sentinel,
    )
    # the committed epoch, where a front end reads it per request: the boot
    # epoch now, and both edges of every cutover from the controller
    if core.rollout is not None:
        server.publish_epoch(core.rollout.epoch)
        core.rollout.on_cutover = server.publish_epoch
    # this process fronts the ticket ring: its occupancy is the ipc
    # pressure component (front ends see their own pending count instead)
    from .engine import pressure as _pressure

    _pressure.monitor().bind(
        ipc=lambda s=server: (s._outstanding, s.max_outstanding)
    )
    server.start()
    return server
