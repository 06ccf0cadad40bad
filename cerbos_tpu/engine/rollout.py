"""Safe policy rollout: shadow-gated, epoch-versioned atomic cutover with a
live canary and automatic rollback (ROADMAP item 4's cutover substrate).

A policy reload used to be the least-defended moment in the serving path:
``RuleTableManager.on_storage_event`` rebuilt the table and then fired a
hand-chained stack of ``on_swap`` closures that mutated live engine state
one after another — a request in flight could evaluate half its inputs
against the old table and half against the new one, a pathological bundle
hit traffic with no gate beyond "build didn't throw", and there was no way
back. :class:`RolloutController` turns every swap into a staged, observable,
reversible rollout:

``build``
    the new :class:`RuleTable` is compiled off the serving path; failures
    keep the last valid epoch serving (the manager's historical contract).
``lower``
    the table is lowered off the serving path, proving the device lowering
    before any traffic can see it; the shadow lowering also feeds the gate.
``gate``
    the static analyzer (PR 14) runs against the shadow lowering —
    ``engine.tpu.rollout.failOn`` rejects e.g. ``oracle-only`` bundles
    outright — and the parity corpus plus a bounded sample of recently
    served inputs is differentially replayed old-vs-new. Effect diffs are
    summarized in the rollout report (an expected policy change is news,
    not an error) unless ``requireAck`` is set, in which case any diff
    rejects the swap.
``cutover``
    the new epoch — ``(rule_table, lowered tables, analyzer report, bundle
    hash, epoch N+1)`` — commits atomically: every batcher lane parks at a
    flight boundary (no device batch in flight), the named subscribers run
    while the world is stopped, lanes stamp the new epoch and resume. No
    request spans two tables; in-flight work keeps the epoch it started on.
``canary``
    for ``canarySec`` after cutover the parity sentinel samples at an
    elevated rate; a parity divergence / storm, a recompile storm (PR 5
    detector), or a pressure-score spike sustained above ``rollbackAt`` for
    ``holdSec`` rolls back to the still-resident epoch N automatically.
    ``cerbos-tpuctl store rollback`` gives operators the same lever.

Epoch numbers are never reused: a rollback reinstates epoch N (same number,
same table object) and the next successful rollout takes the next unused
number, so every decision's ``policyEpoch`` stamp maps to exactly one table
ever committed. The current epoch rides readiness snapshots and therefore
IPC STATUS frames, which is how ``--frontends`` processes observe cutovers
within a bounded, measured skew window
(``cerbos_tpu_policy_epoch_skew_seconds``). What a front end may ANSWER from
is decided without that window: ``on_cutover`` announces both edges of every
commit to the pool's ticket server, which publishes them where each front
end reads them per request (engine/ipc.py).
"""

from __future__ import annotations

import hashlib
import logging
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

from ..ruletable import check_input
from ..schema import ROUTE_SHADOW
from . import flight
from . import types as T

log = logging.getLogger("cerbos_tpu.rollout")

STAGE_BUILD = "build"
STAGE_LOWER = "lower"
STAGE_GATE = "gate"
STAGE_CUTOVER = "cutover"
STAGE_CANARY = "canary"
STAGES = (STAGE_BUILD, STAGE_LOWER, STAGE_GATE, STAGE_CUTOVER, STAGE_CANARY)

OUTCOME_SERVING = "serving"
OUTCOME_REJECTED = "rejected"
OUTCOME_FAILED = "failed"
OUTCOME_ROLLED_BACK = "rolled_back"
OUTCOME_IN_PROGRESS = "in_progress"
TERMINAL_OUTCOMES = (OUTCOME_SERVING, OUTCOME_REJECTED, OUTCOME_FAILED, OUTCOME_ROLLED_BACK)

# attribute stamped onto committed RuleTable objects; oracle paths that only
# hold a table reference (serial engine, batcher fallback) resolve their
# decision's epoch through it with no extra synchronization — the table was
# read once per request, so the (table, epoch) pair is consistent by design
EPOCH_ATTR = "policy_epoch"

_GATE_FINDINGS_MAX = 20
_DIFF_SAMPLES_MAX = 5


def epoch_of(rule_table: Any) -> Optional[int]:
    """The epoch a table was committed as, or None for never-committed
    tables (direct construction in tests, frontend-local rebuilds)."""
    return getattr(rule_table, EPOCH_ATTR, None)


def bundle_hash_of(rule_table: Any) -> str:
    """Stable content hash of everything ``check_input`` reads of a table:
    every field of every rule row (its condition tree, its derived role's
    condition, its output expressions, and the constants and variables both
    resolve against), each policy's derived roles, schema references and
    metadata, and the role policies' parent roles. It is the identity printed
    in rollout reports and flight events, AND what a front end compares with
    the owner's committed epoch before it answers from its own table
    (engine/ipc.py): two tables of one identity give the same answers.
    ``""`` on any error, which matches nothing. Computed once per table object
    (a table is never edited once it serves; ``RuleTable`` drops the memo if
    it is)."""
    memo = getattr(rule_table, "bundle_hash_memo", None)
    if memo:
        return memo
    try:
        digest = _table_digest(rule_table)
    except Exception:  # noqa: BLE001 — no identity, never fatal
        return ""
    rule_table.bundle_hash_memo = digest
    return digest


def _table_digest(rt: Any) -> str:
    # conditions, outputs and params are shared by the rows of one rule or
    # one policy: each is written out once and named by its position after
    seen: dict[int, str] = {}

    def expr(e: Any) -> str:
        return repr(e.original) if e is not None else "-"

    def shared(obj: Any, write: Callable[[Any], str]) -> str:
        if obj is None:
            return "-"
        ref = seen.get(id(obj))
        if ref is None:
            ref = seen[id(obj)] = f"#{len(seen)}"
            return f"{ref}={write(obj)}"
        return ref

    def cond(c: Any) -> str:
        return shared(c, lambda c: f"{c.kind}<{expr(c.expr)}>[{','.join(cond(k) for k in c.children)}]")

    def output(o: Any) -> str:
        return shared(o, lambda o: f"{expr(o.rule_activated)}/{expr(o.condition_not_met)}")

    def params(p: Any) -> str:
        return shared(
            p,
            lambda p: f"{sorted(p.constants.items())!r};"
            + ";".join(f"{v.name}={expr(v.expr)}" for v in p.ordered_variables),
        )

    h = hashlib.sha256()
    for r in sorted(rt.idx.get_all_rows(), key=lambda r: (r.origin_fqn, r.id)):
        actions = r.action if r.action is not None else sorted(r.allow_actions or ())
        h.update(
            f"{r.origin_fqn}|{r.id}|{r.evaluation_key}|{r.name}|{r.effect}|{r.role}|{actions!r}"
            f"|{r.scope}|{r.version}|{r.policy_kind}|{r.resource}|{r.principal}|{r.scope_permissions}"
            f"|{r.origin_derived_role}|{r.from_role_policy}|{r.no_match_for_scope_permissions}"
            f"|{cond(r.condition)}|{cond(r.derived_role_condition)}|{output(r.emit_output)}"
            f"|{params(r.params)}|{params(r.derived_role_params)}\n".encode()
        )
    for mod_id in sorted(rt.meta):
        m = rt.meta[mod_id]
        h.update(f"meta|{m.fqn}|{m.name}|{m.version}|{m.kind}|{m.source_attributes!r}|{m.annotations!r}\n".encode())
        for name, dr in sorted((rt.policy_derived_roles.get(mod_id) or {}).items()):
            h.update(f"dr|{name}|{sorted(dr.parent_roles)!r}|{cond(dr.condition)}|{params(dr.params)}\n".encode())
        h.update(f"schemas|{rt.schemas.get(mod_id)!r}\n".encode())
    h.update(f"parents|{sorted((s, sorted(p.items())) for s, p in rt.scope_parent_roles.items())!r}\n".encode())
    return h.hexdigest()[:16]


class RolloutFault(RuntimeError):
    """Raised by the ``swap_fail:STAGE`` fault knob (engine/faults.py)."""


@dataclass
class Epoch:
    """One immutable committed policy generation. Everything a cutover (or
    rollback) needs travels together: the table, its shadow lowering, the
    analyzer verdict, and the bundle identity."""

    number: Optional[int]
    rule_table: Any
    bundle_hash: str = ""
    committed_at: float = 0.0  # wall clock at commit (skew reference)
    analysis: Optional[dict] = None  # analyzer summary captured at the gate
    source: str = "rollout"  # boot | rollout | rollback | local
    # full AnalysisReport for the analysis subscriber to republish without
    # re-running the analyzer at commit time; not serialized
    analysis_report: Any = field(default=None, repr=False)
    lowered: Any = field(default=None, repr=False)

    def describe(self) -> dict:
        return {
            "epoch": self.number,
            "bundle_hash": self.bundle_hash,
            "committed_at": self.committed_at,
            "source": self.source,
            "analysis": self.analysis,
        }


class SwapBarrier:
    """Flight-boundary stop-the-world across batcher lanes.

    The controller hands the barrier to every lane via
    ``BatchingEvaluator.request_swap``; each drain loop finishes its current
    flights, submits nothing new, and calls :meth:`park`. Once every live
    lane is parked (or the bounded drain timeout expires — a wedged device
    must not hold a cutover hostage forever), the controller mutates the
    shared state and :meth:`release` resumes everyone."""

    def __init__(self, timeout_s: float = 5.0):
        self.timeout_s = max(0.05, float(timeout_s))
        self._release = threading.Event()
        self._parked = threading.Semaphore(0)
        self.expected = 0
        self.timed_out = False

    def start(self, lanes: list) -> bool:
        """Request a park from every lane and wait for all of them to reach
        a flight boundary. Returns False when the drain timeout expired with
        lanes still in flight (the cutover proceeds anyway, recorded)."""
        self.expected = 0
        for lane in lanes:
            try:
                if lane.request_swap(self):
                    self.expected += 1
            except Exception:  # noqa: BLE001 — a dying lane never blocks cutover
                log.exception("rollout: lane refused swap barrier")
        deadline = time.monotonic() + self.timeout_s
        for _ in range(self.expected):
            remaining = deadline - time.monotonic()
            if remaining <= 0 or not self._parked.acquire(timeout=remaining):
                self.timed_out = True
                return False
        return True

    def park(self, lane: Any) -> None:
        """Called on a lane's drain thread at a flight boundary: report in,
        then hold position until the controller finishes the swap. The wait
        is bounded so a crashed controller can never wedge serving."""
        self._parked.release()
        self._release.wait(self.timeout_s * 2 + 1.0)

    def release(self) -> None:
        self._release.set()


class RolloutRun:
    """One staged rollout attempt: the stage ladder, the gate verdict, the
    canary result, and the terminal outcome — the report ``store reload
    --wait`` renders and ``/_cerbos/debug/rollout`` serves."""

    def __init__(self, generation: int, trigger: str, from_epoch: Optional[int]):
        self.generation = generation
        self.trigger = trigger
        self.from_epoch = from_epoch
        self.to_epoch: Optional[int] = None
        self.bundle_hash = ""
        self.outcome = OUTCOME_IN_PROGRESS
        self.stages: list[dict] = []
        self.gate: dict = {}
        self.canary: dict = {}
        self.error = ""
        self.started_at = time.time()
        self.finished_at: Optional[float] = None
        self.cancelled = False  # a newer rollout superseded the canary hold
        self._done = threading.Event()

    @property
    def terminal(self) -> bool:
        return self.outcome in TERMINAL_OUTCOMES

    @property
    def current_stage(self) -> str:
        return self.stages[-1]["stage"] if self.stages else ""

    def stage(self, name: str, status: str, seconds: float, **detail: Any) -> None:
        entry = {"stage": name, "status": status, "seconds": round(seconds, 6)}
        if detail:
            entry.update(detail)
        self.stages.append(entry)

    def finish(self, outcome: str, error: str = "") -> None:
        if self.terminal:
            return
        self.outcome = outcome
        self.error = error or self.error
        self.finished_at = time.time()
        self._done.set()

    def wait(self, timeout: float) -> bool:
        return self._done.wait(timeout)

    def to_dict(self) -> dict:
        return {
            "generation": self.generation,
            "trigger": self.trigger,
            "outcome": self.outcome,
            "from_epoch": self.from_epoch,
            "to_epoch": self.to_epoch,
            "bundle_hash": self.bundle_hash,
            "stages": list(self.stages),
            # underscore keys carry live objects (the AnalysisReport) for
            # the cutover path, not for serialization
            "gate": {k: v for k, v in self.gate.items() if not k.startswith("_")},
            "canary": dict(self.canary),
            "error": self.error,
            "started_at": self.started_at,
            "finished_at": self.finished_at,
        }


class RolloutController:
    """Owns the swap path end to end: named subscribers replace the hand-
    chained ``on_swap`` closures, commits are epoch-versioned and atomic
    behind a lane drain barrier, and every attempt leaves a report.

    ``mode="full"`` gates, versions, and canaries (device-owning roles);
    ``mode="passive"`` only runs the subscriber registry on each rebuild
    (front ends — their epoch authority is the batcher: its STATUS frames
    for the gauges, and per request the words ``on_cutover`` has it publish
    in the shared descriptor page, engine/ipc.py)."""

    def __init__(
        self,
        manager: Any,
        *,
        conf: Optional[dict] = None,
        mode: str = "full",
        globals_: Optional[dict] = None,
        schema_mgr: Any = None,
        sentinel: Any = None,
        faults: Optional[dict] = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        conf = dict(conf or {})
        self.manager = manager
        self.mode = mode
        self.globals_ = globals_ or {}
        self.schema_mgr = schema_mgr
        self.sentinel = sentinel
        self.faults = faults
        self._clock = clock

        self.enabled = bool(conf.get("enabled", True))
        self.fail_on = str(conf.get("failOn", "") or "")
        self.require_ack = bool(conf.get("requireAck", False))
        self.replay_max = max(0, int(conf.get("replayMax", 128)))
        self.canary_sec = max(0.0, float(conf.get("canarySec", 0.0)))
        self.canary_boost = float(conf.get("canaryBoost", 1.0))
        self.hold_sec = max(0.0, float(conf.get("holdSec", 5.0)))
        self.rollback_at = float(conf.get("rollbackAt", 0.9))
        self.canary_divergences = max(1, int(conf.get("canaryDivergences", 1)))
        self.drain_timeout_s = max(0.05, float(conf.get("drainTimeoutMs", 5000)) / 1000.0)
        self.poll_s = max(0.01, float(conf.get("canaryPollMs", 100)) / 1000.0)
        self.history_max = max(1, int(conf.get("epochHistory", 2)))
        self.runs_max = max(1, int(conf.get("runHistory", 8)))

        self._subs: list[tuple[str, Callable[[Epoch], None]]] = []
        # both edges of a cutover, for whoever publishes the committed epoch
        # beyond this process (a pool's owner, to its front ends' shared
        # pages): called with None before the drain barrier is requested,
        # and with the epoch once its subscribers have run
        self.on_cutover: Optional[Callable[[Optional[Epoch]], None]] = None
        self._lanes: list[Any] = []
        self._lock = threading.RLock()  # epoch / history / runs bookkeeping
        self._run_lock = threading.Lock()  # one rollout (or rollback) at a time
        self.epoch: Optional[Epoch] = None
        self.history: deque[Epoch] = deque(maxlen=self.history_max)
        self.runs: deque[RolloutRun] = deque(maxlen=self.runs_max)
        self.generation = 0
        self._max_number = 0
        self._canary_thread: Optional[threading.Thread] = None
        self._canary_run: Optional[RolloutRun] = None
        self._init_metrics()

    def _init_metrics(self) -> None:
        from ..observability import metrics

        reg = metrics()
        self.m_total = reg.counter_vec(
            "cerbos_tpu_rollout_total",
            "rollout stage transitions by outcome (ok/failed/rejected/rolled_back/pass)",
            label=("stage", "outcome"),
        )
        self.m_duration = reg.histogram_vec(
            "cerbos_tpu_rollout_duration_seconds",
            "wall time spent per rollout stage",
            label="stage",
            buckets=[0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0, 120.0],
        )
        self.m_epoch = reg.gauge(
            "cerbos_tpu_policy_epoch",
            "policy epoch currently serving (monotone except across a rollback)",
        )

    # -- wiring ---------------------------------------------------------------

    def subscribe(self, name: str, fn: Callable[[Epoch], None]) -> None:
        """Register a named cutover subscriber. Subscribers run in
        registration order inside the stopped-world window; a failing
        subscriber is logged and skipped, never aborts a commit midway."""
        self._subs.append((name, fn))

    @property
    def subscribers(self) -> list[str]:
        return [name for name, _ in self._subs]

    def bind_lanes(self, lanes: list) -> None:
        """The batcher lanes that must park at a flight boundary before the
        shared lowered tables mutate (one BatchingEvaluator per shard, or a
        single-element list for the unsharded batcher)."""
        self._lanes = [lane for lane in lanes if lane is not None]

    def seed(self, rule_table: Any, source: str = "boot") -> Epoch:
        """Adopt the boot-time table as epoch 1 without gating (it is
        already serving — there is nothing to cut over from)."""
        ep = Epoch(
            number=1,
            rule_table=rule_table,
            bundle_hash=bundle_hash_of(rule_table),
            committed_at=time.time(),
            source=source,
        )
        with self._lock:
            self.epoch = ep
            self._max_number = max(self._max_number, 1)
        try:
            setattr(rule_table, EPOCH_ATTR, 1)
        except Exception:  # noqa: BLE001 — slots-style tables stay unstamped
            pass
        for lane in self._lanes:
            lane.epoch = 1
        self.m_epoch.set(1)
        return ep

    # -- fault hooks ----------------------------------------------------------

    def _fault_stage(self) -> str:
        spec = self.faults
        if not spec:
            return ""
        return str(spec.get("swap_fail", "") or "")

    def _fault_check(self, stage: str) -> None:
        if self._fault_stage() == stage:
            shard = self.faults.get("shard") if self.faults else None
            scope = f" (shard {shard})" if shard is not None else ""
            raise RolloutFault(f"injected swap_fail:{stage}{scope}")

    # -- the staged rollout ----------------------------------------------------

    def on_storage_event(self, events: Any = None) -> Optional[RolloutRun]:
        """The manager's storage-event delegate. Never raises: the store's
        notify path treats subscriber exceptions as lost, so every failure
        is captured in the run report instead."""
        try:
            if self.mode == "passive":
                return self._run_passive()
            return self.run_rollout(trigger="storage")
        except Exception:  # noqa: BLE001
            log.exception("rollout: unhandled failure; last valid epoch kept")
            return None

    def _run_passive(self) -> Optional[RolloutRun]:
        """Front-end rebuild: no gate, no epoch authority — just the named
        subscriber registry over the fresh local table."""
        try:
            rt = self.manager.build_table()
        except Exception:  # noqa: BLE001
            log.exception("policy reload failed; keeping last valid state")
            return None
        self.manager.commit_table(rt)
        self._notify_subscribers(Epoch(number=None, rule_table=rt, source="local"))
        return None

    def run_rollout(self, trigger: str = "storage") -> RolloutRun:
        self._cancel_canary()
        with self._run_lock:
            with self._lock:
                self.generation += 1
                old = self.epoch
                run = RolloutRun(
                    self.generation, trigger, old.number if old else None
                )
                self.runs.append(run)

            # build ----------------------------------------------------------
            try:
                rt = self._timed(run, STAGE_BUILD, self._stage_build)
            except Exception as e:  # noqa: BLE001 — keep last valid state
                log.error("policy reload failed; keeping last valid state: %s", e)
                run.finish(OUTCOME_FAILED, error=str(e))
                return run
            run.bundle_hash = bundle_hash_of(rt)

            if not self.enabled:
                run.stage(STAGE_LOWER, "skipped", 0.0)
                run.stage(STAGE_GATE, "skipped", 0.0)
                epoch = self._make_epoch(rt, None, None)
                self._timed(run, STAGE_CUTOVER, lambda: self._commit(epoch))
                run.to_epoch = epoch.number
                run.stage(STAGE_CANARY, "skipped", 0.0)
                run.finish(OUTCOME_SERVING)
                self.m_total.inc((STAGE_CUTOVER, "ok"))
                return run

            # lower ----------------------------------------------------------
            try:
                lowered = self._timed(run, STAGE_LOWER, lambda: self._stage_lower(rt))
            except Exception as e:  # noqa: BLE001
                log.error("rollout: lowering failed; keeping last valid state: %s", e)
                run.finish(OUTCOME_FAILED, error=str(e))
                return run

            # gate -----------------------------------------------------------
            t0 = self._clock()
            try:
                verdict = self._stage_gate(run, rt, lowered, old)
            except Exception as e:  # noqa: BLE001
                dt = self._clock() - t0
                run.stage(STAGE_GATE, "failed", dt, error=str(e))
                self.m_duration.observe(STAGE_GATE, dt)
                self.m_total.inc((STAGE_GATE, OUTCOME_FAILED))
                log.error("rollout: gate errored; keeping last valid state: %s", e)
                run.finish(OUTCOME_FAILED, error=str(e))
                return run
            dt = self._clock() - t0
            self.m_duration.observe(STAGE_GATE, dt)
            if verdict is not None:
                run.stage(STAGE_GATE, "rejected", dt, reason=verdict)
                self.m_total.inc((STAGE_GATE, OUTCOME_REJECTED))
                flight.recorder().record_event(
                    "rollout_rejected",
                    generation=run.generation,
                    reason=verdict,
                    bundle_hash=run.bundle_hash,
                )
                log.warning("rollout: bundle rejected at gate (%s); not serving it", verdict)
                run.finish(OUTCOME_REJECTED, error=verdict)
                return run
            run.stage(STAGE_GATE, "ok", dt, fail_on=self.fail_on or None)
            self.m_total.inc((STAGE_GATE, "ok"))

            # cutover --------------------------------------------------------
            report = run.gate.get("_analysis_report")
            run.gate.pop("_analysis_report", None)
            epoch = self._make_epoch(rt, lowered, report)
            self._timed(run, STAGE_CUTOVER, lambda: self._commit(epoch))
            run.to_epoch = epoch.number
            self.m_total.inc((STAGE_CUTOVER, "ok"))

            # canary ---------------------------------------------------------
            if self.canary_sec <= 0:
                run.stage(STAGE_CANARY, "skipped", 0.0)
                run.finish(OUTCOME_SERVING)
                return run
            self._start_canary(run, epoch)
            return run

    def _timed(self, run: RolloutRun, name: str, fn: Callable[[], Any]) -> Any:
        t0 = self._clock()
        try:
            out = fn()
        except Exception as e:
            dt = self._clock() - t0
            run.stage(name, "failed", dt, error=str(e))
            self.m_duration.observe(name, dt)
            self.m_total.inc((name, OUTCOME_FAILED))
            raise
        dt = self._clock() - t0
        run.stage(name, "ok", dt)
        self.m_duration.observe(name, dt)
        if name != STAGE_CUTOVER:  # cutover's ok is counted by the caller
            self.m_total.inc((name, "ok"))
        return out

    def _stage_build(self) -> Any:
        self._fault_check(STAGE_BUILD)
        return self.manager.build_table()

    def _stage_lower(self, rt: Any) -> Any:
        self._fault_check(STAGE_LOWER)
        from ..tpu.lowering import lower_table

        return lower_table(rt, self.globals_)

    def _stage_gate(
        self, run: RolloutRun, rt: Any, lowered: Any, old: Optional[Epoch]
    ) -> Optional[str]:
        """Run the analyzer and the differential replay. Returns a rejection
        reason, or None when the bundle may serve."""
        self._fault_check(STAGE_GATE)
        from ..tpu import analyze as _analyze

        report = _analyze.analyze_table(rt, self.globals_, lowered=lowered)
        run.gate["analysis"] = report.summary()
        run.gate["fail_on"] = self.fail_on
        run.gate["_analysis_report"] = report

        if self.fail_on:
            try:
                gate_failed = report.failed(self.fail_on)
            except ValueError as e:
                log.warning("rollout: unknown failOn %r ignored: %s", self.fail_on, e)
                gate_failed = False
            if gate_failed:
                run.gate["findings"] = [
                    {
                        "kind": f.kind,
                        "code": f.code,
                        "severity": f.severity,
                        "policy": f.policy,
                        "rule": f.rule_name,
                        "message": f.message,
                    }
                    for f in report.findings[:_GATE_FINDINGS_MAX]
                ]
                return f"analyzer:{self.fail_on}"

        replay = self._differential_replay(old.rule_table if old else None, rt)
        run.gate["replay"] = replay
        if self.require_ack and replay.get("diffs", 0) > 0:
            return f"diffs_require_ack:{replay['diffs']}"
        return None

    # -- differential replay ---------------------------------------------------

    def _replay_inputs(self) -> list:
        """Parity-corpus inputs plus the sentinel's bounded ring of recently
        sampled live inputs — the traffic the old table actually served."""
        inputs: list = []
        sent = self.sentinel
        if sent is None or self.replay_max == 0:
            return inputs
        corpus = getattr(sent, "corpus", None)
        corpus_dir = getattr(corpus, "dir", "") if corpus is not None else ""
        if corpus_dir:
            from .sentinel import DivergenceCorpus, input_from_json

            for _path, rec in DivergenceCorpus.load(corpus_dir):
                for ij in rec.get("inputs") or []:
                    try:
                        inputs.append(input_from_json(ij))
                    except Exception:  # noqa: BLE001 — a stale record never gates
                        pass
        recent = getattr(sent, "recent_inputs", None)
        if callable(recent):
            inputs.extend(recent())
        return inputs[-self.replay_max :]

    def _differential_replay(self, old_rt: Any, new_rt: Any) -> dict:
        from .sentinel import effect_rows

        inputs = self._replay_inputs()
        if old_rt is None or not inputs:
            return {"replayed": 0, "diffs": 0, "errors": 0, "samples": []}
        params = T.EvalParams()
        diffs: list[dict] = []
        errors = 0
        for inp in inputs:
            try:
                before = effect_rows([check_input(old_rt, inp, params, self.schema_mgr, ROUTE_SHADOW)])[0]
                after = effect_rows([check_input(new_rt, inp, params, self.schema_mgr, ROUTE_SHADOW)])[0]
            except Exception:  # noqa: BLE001 — replay is advisory
                errors += 1
                continue
            if before != after:
                diffs.append(
                    {
                        "principal": inp.principal.id,
                        "resource": f"{inp.resource.kind}:{inp.resource.id}",
                        "old": before,
                        "new": after,
                    }
                )
        return {
            "replayed": len(inputs),
            "diffs": len(diffs),
            "errors": errors,
            "samples": diffs[:_DIFF_SAMPLES_MAX],
        }

    # -- commit / rollback -----------------------------------------------------

    def _make_epoch(self, rt: Any, lowered: Any, report: Any) -> Epoch:
        with self._lock:
            number = self._max_number + 1
        return Epoch(
            number=number,
            rule_table=rt,
            bundle_hash=bundle_hash_of(rt),
            analysis=report.summary() if report is not None else None,
            analysis_report=report,
            lowered=lowered,
            source="rollout",
        )

    def _notify_subscribers(self, epoch: Epoch) -> None:
        for name, fn in self._subs:
            try:
                fn(epoch)
            except Exception:  # noqa: BLE001 — one bad subscriber, not a torn commit
                log.exception("rollout: subscriber %r failed during cutover", name)

    def _announce(self, epoch: Optional[Epoch]) -> None:
        if self.on_cutover is not None:
            try:
                self.on_cutover(epoch)
            except Exception:  # noqa: BLE001 — a reader left behind asks this process; the commit goes on
                log.exception("rollout: cutover announcement failed")

    def _commit(self, epoch: Epoch, rollback: bool = False) -> None:
        """The atomic cutover: park every lane at a flight boundary, swap
        the world under the barrier, stamp lane epochs, resume."""
        epoch.committed_at = time.time()
        if epoch.number is not None:
            try:
                setattr(epoch.rule_table, EPOCH_ATTR, epoch.number)
            except Exception:  # noqa: BLE001
                pass
        self._announce(None)
        barrier = SwapBarrier(timeout_s=self.drain_timeout_s)
        parked = barrier.start(self._lanes)
        if not parked:
            flight.recorder().record_event(
                "rollout_barrier_timeout",
                epoch=epoch.number,
                lanes=barrier.expected,
                timeout_s=self.drain_timeout_s,
            )
            log.warning(
                "rollout: %d lane(s) missed the %.2fs drain barrier; cutting over anyway",
                barrier.expected,
                self.drain_timeout_s,
            )
        try:
            self.manager.commit_table(epoch.rule_table)
            self._notify_subscribers(epoch)
            for lane in self._lanes:
                lane.epoch = epoch.number
            # a commit that raised before this line stays announced as
            # pending: its readers keep asking this process, which is safe
            self._announce(epoch)
        finally:
            barrier.release()
        with self._lock:
            prev = self.epoch
            if rollback:
                # reinstating history[-1]: remove it from history (it is
                # current again); the rolled-back epoch's table is dropped
                if self.history and self.history[-1] is not prev and self.history[-1].number == epoch.number:
                    self.history.pop()
            elif prev is not None:
                self.history.append(prev)
            self.epoch = epoch
            if epoch.number is not None:
                self._max_number = max(self._max_number, epoch.number)
        self.m_epoch.set(epoch.number or 0)
        flight.recorder().record_event(
            "rollout_cutover",
            epoch=epoch.number,
            from_epoch=prev.number if prev else None,
            bundle_hash=epoch.bundle_hash,
            source=epoch.source,
            barrier_parked=parked,
        )

    def rollback(self, reason: str = "operator", run: Optional[RolloutRun] = None) -> Optional[dict]:
        """Reinstate the still-resident previous epoch. Used by the canary
        (``run`` is the rollout being reverted) and by operators via
        ``cerbos-tpuctl store rollback`` (a synthetic run is recorded)."""
        if run is None:
            # operator-triggered: an active canary hold is watching the epoch
            # this rollback removes — stand it down before reverting
            self._cancel_canary()
        with self._run_lock:
            with self._lock:
                if not self.history:
                    return None
                prev = self.history[-1]
                bad = self.epoch
                if run is None:
                    self.generation += 1
                    run = RolloutRun(
                        self.generation, f"rollback:{reason}", bad.number if bad else None
                    )
                    self.runs.append(run)
            restored = Epoch(
                number=prev.number,
                rule_table=prev.rule_table,
                bundle_hash=prev.bundle_hash,
                analysis=prev.analysis,
                analysis_report=prev.analysis_report,
                lowered=prev.lowered,
                source="rollback",
            )
            t0 = self._clock()
            self._commit(restored, rollback=True)
            dt = self._clock() - t0
            run.stage("rollback", "ok", dt, reason=reason, restored_epoch=prev.number)
            self.m_duration.observe("rollback", dt)
            self.m_total.inc(("rollback", OUTCOME_ROLLED_BACK))
            flight.recorder().record_event(
                "rollout_rollback",
                reason=reason,
                from_epoch=bad.number if bad else None,
                to_epoch=prev.number,
            )
            log.warning(
                "rollout: rolled back epoch %s -> %s (%s)",
                bad.number if bad else None,
                prev.number,
                reason,
            )
            run.finish(OUTCOME_ROLLED_BACK, error=reason)
            return run.to_dict()

    # -- canary ----------------------------------------------------------------

    def _start_canary(self, run: RolloutRun, epoch: Epoch) -> None:
        sent = self.sentinel
        if sent is not None and self.canary_boost > 0:
            boost = getattr(sent, "set_boost", None)
            if callable(boost):
                boost(self.canary_boost, self.canary_sec)
        # baseline on THIS thread, at cutover: a divergence landing before
        # the watcher thread gets scheduled must count against the canary,
        # not silently fold into its baseline
        baseline = self._canary_baseline(sent)
        t = threading.Thread(
            target=self._canary_watch,
            args=(run, epoch, baseline),
            daemon=True,
            name=f"rollout-canary-{epoch.number}",
        )
        with self._lock:
            self._canary_thread = t
            self._canary_run = run
        t.start()

    def _cancel_canary(self) -> None:
        """A newer rollout supersedes an active canary hold: the held epoch
        is declared serving (the new rollout replaces it anyway)."""
        with self._lock:
            run, t = self._canary_run, self._canary_thread
            self._canary_run, self._canary_thread = None, None
        if run is not None and not run.terminal:
            run.cancelled = True
        if t is not None and t.is_alive() and t is not threading.current_thread():
            t.join(timeout=self.poll_s * 4 + 1.0)

    def _canary_baseline(self, sent: Any) -> tuple[int, int, int]:
        from ..tpu import compilestats

        base_div = base_storms = 0
        if sent is not None:
            st = sent.stats
            base_div = int(st.get("divergences", 0))
            base_storms = int(st.get("storms", 0))
        return base_div, base_storms, compilestats.stats().detector.storms

    def _canary_watch(
        self, run: RolloutRun, epoch: Epoch, baseline: tuple[int, int, int]
    ) -> None:
        from . import pressure
        from ..tpu import compilestats

        sent = self.sentinel
        base_div, base_storms, base_compile = baseline
        mon = pressure.monitor()

        t0 = self._clock()
        deadline = t0 + self.canary_sec
        hard_deadline = deadline + self.hold_sec
        over_since: Optional[float] = None
        trigger = ""
        while True:
            now = self._clock()
            if now >= deadline and (over_since is None or now >= hard_deadline):
                break
            if run.cancelled:
                run.canary["result"] = "superseded"
                run.finish(OUTCOME_SERVING)
                return
            time.sleep(self.poll_s)
            if self._fault_stage() == STAGE_CANARY:
                trigger = "fault:swap_fail:canary"
                break
            if sent is not None:
                st = sent.stats
                if int(st.get("storms", 0)) - base_storms > 0:
                    trigger = "parity_storm"
                    break
                div = int(st.get("divergences", 0)) - base_div
                run.canary["divergences"] = div
                if div >= self.canary_divergences:
                    trigger = f"parity_divergence:{div}"
                    break
            if compilestats.stats().detector.storms - base_compile > 0:
                trigger = "recompile_storm"
                break
            score = float(getattr(mon, "last_score", 0.0))
            run.canary["pressure"] = score
            if score > self.rollback_at:
                over_since = over_since if over_since is not None else self._clock()
                if self._clock() - over_since >= self.hold_sec:
                    trigger = f"pressure:{score:.2f}"
                    break
            else:
                over_since = None

        dt = self._clock() - t0
        self.m_duration.observe(STAGE_CANARY, dt)
        with self._lock:
            if self._canary_run is run:
                self._canary_run, self._canary_thread = None, None
        if trigger:
            run.canary["trigger"] = trigger
            run.stage(STAGE_CANARY, "rolled_back", dt, trigger=trigger)
            self.m_total.inc((STAGE_CANARY, OUTCOME_ROLLED_BACK))
            self.rollback(reason=trigger, run=run)
        else:
            run.canary["result"] = "pass"
            run.stage(STAGE_CANARY, "ok", dt)
            self.m_total.inc((STAGE_CANARY, "pass"))
            run.finish(OUTCOME_SERVING)

    # -- introspection ---------------------------------------------------------

    def epoch_info(self) -> dict:
        """The epoch block merged into readiness snapshots — and therefore
        into IPC STATUS frames, which is how front ends learn about
        cutovers (``committed_at`` is the skew reference)."""
        with self._lock:
            ep = self.epoch
            run = self.runs[-1] if self.runs else None
        if ep is None or ep.number is None:
            return {}
        out: dict = {
            "policy_epoch": ep.number,
            "policy_epoch_committed_at": ep.committed_at,
        }
        if run is not None and not run.terminal:
            out["rollout_stage"] = run.current_stage or OUTCOME_IN_PROGRESS
        return out

    def snapshot(self) -> dict:
        """The ``/_cerbos/debug/rollout`` payload."""
        with self._lock:
            ep = self.epoch
            history = [e.describe() for e in self.history]
            runs = [r.to_dict() for r in self.runs]
            lanes = [
                {"epoch": getattr(lane, "epoch", None)} for lane in self._lanes
            ]
        return {
            "mode": self.mode,
            "epoch": ep.describe() if ep is not None else None,
            "history": history,
            "lanes": lanes,
            "runs": runs,
            "config": {
                "enabled": self.enabled,
                "failOn": self.fail_on,
                "requireAck": self.require_ack,
                "replayMax": self.replay_max,
                "canarySec": self.canary_sec,
                "canaryBoost": self.canary_boost,
                "holdSec": self.hold_sec,
                "rollbackAt": self.rollback_at,
                "canaryDivergences": self.canary_divergences,
                "drainTimeoutMs": self.drain_timeout_s * 1000.0,
                "epochHistory": self.history_max,
            },
        }

    def wait_report(self, after_generation: int, timeout: float = 60.0) -> Optional[dict]:
        """Block until a run newer than ``after_generation`` reaches a
        terminal stage and return its report (``store reload --wait``)."""
        deadline = time.monotonic() + max(0.0, timeout)
        while True:
            with self._lock:
                candidates = [r for r in self.runs if r.generation > after_generation]
            for r in candidates:
                if r.terminal:
                    return r.to_dict()
            waiter = candidates[0] if candidates else None
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return None
            if waiter is not None:
                waiter.wait(min(remaining, 0.25))
            else:
                time.sleep(min(remaining, 0.05))

    def close(self) -> None:
        self._cancel_canary()


# -- process-wide handle ------------------------------------------------------

# the debug endpoint and admin handlers reach the controller through the
# Core; the module-level handle mirrors analyze.publish()'s semantics for
# surfaces with no Core reference (last bootstrap wins — fine in a process
# that serves one engine, which is every production topology)
_active: Optional[RolloutController] = None


def install(controller: Optional[RolloutController]) -> None:
    global _active
    _active = controller


def active() -> Optional[RolloutController]:
    return _active
