"""Audit log core: access/decision entries, filtering, async writes.

Behavioral reference: internal/audit/{log,conf,decision_filter}.go —
pluggable backends via a registry, decision log filters (accessLogsEnabled /
decisionLogsEnabled, filter by action/kind), async buffered writes
(log.go:142-195).

An entry is built on the request's thread and queued; one writer thread
serialises and writes it. The request never waits for the backend: a full
queue drops the entry and the ``shed_audit`` rung of the brownout ladder
refuses it at the door. Every entry is accounted for by kind:
``cerbos_tpu_audit_entries_total{kind,outcome}`` (``queued``, ``written``,
``filtered``) and ``cerbos_tpu_audit_lost_total{kind,reason}`` (``dropped``,
``shed``, ``failed``), so ``queued`` = ``written`` + ``failed`` + what the
queue still holds, and "nothing lost" is ``audit_lost_total`` not moving.
``close()`` drains the queue before it closes the backend.
"""

from __future__ import annotations

import contextlib
import datetime
import logging
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

from .. import globs
from ..engine import types as T


@dataclass
class DecisionFilter:
    """Ref: internal/audit/decision_filter.go (ignoreAllowAll + filtered actions)."""

    ignore_allow_all: bool = False
    ignored_actions: list[str] = field(default_factory=list)

    def keep(self, inputs: list[T.CheckInput], outputs: list[T.CheckOutput]) -> bool:
        if self.ignore_allow_all and all(
            e.effect == T.EFFECT_ALLOW for o in outputs for e in o.actions.values()
        ):
            return False
        if self.ignored_actions:
            all_ignored = all(
                any(globs.matches_glob(pat, a) for pat in self.ignored_actions)
                for i in inputs
                for a in i.actions
            )
            if all_ignored:
                return False
        return True


def _now_iso() -> str:
    return datetime.datetime.now(datetime.timezone.utc).isoformat()


def _drop_empty(d: dict) -> dict:
    """Proto-JSON convention: default/empty fields are omitted."""
    return {k: v for k, v in d.items() if v not in ("", [], {}, None)}


def _input_json(i: T.CheckInput) -> dict:
    return _drop_empty(
        {
            "requestId": i.request_id,
            "resource": _drop_empty(
                {
                    "kind": i.resource.kind,
                    "policyVersion": i.resource.policy_version,
                    "id": i.resource.id,
                    "attr": i.resource.attr,
                    "scope": i.resource.scope,
                }
            ),
            "principal": _drop_empty(
                {
                    "id": i.principal.id,
                    "policyVersion": i.principal.policy_version,
                    "roles": list(i.principal.roles),
                    "attr": i.principal.attr,
                    "scope": i.principal.scope,
                }
            ),
            "actions": list(i.actions),
            "auxData": _drop_empty({"jwt": i.aux_data.jwt}) if i.aux_data else {},
        }
    )


def _output_json(o: T.CheckOutput) -> dict:
    return _drop_empty(
        {
            "requestId": o.request_id,
            "resourceId": o.resource_id,
            "actions": {
                a: _drop_empty({"effect": e.effect, "policy": e.policy, "scope": e.scope})
                for a, e in o.actions.items()
            },
            "effectiveDerivedRoles": list(o.effective_derived_roles),
            "outputs": [
                _drop_empty({"src": x.src, "action": x.action, "val": x.val, "error": x.error})
                for x in o.outputs
            ],
            "validationErrors": [
                {"path": v.path, "message": v.message, "source": v.source}
                for v in o.validation_errors
            ],
        }
    )


def _entry_from_decision(
    call_id: str,
    inputs: list[T.CheckInput],
    outputs: list[T.CheckOutput],
    trace_id: str = "",
    shard: Optional[int] = None,
    epoch: Optional[int] = None,
) -> dict:
    """Ref: auditv1.DecisionLogEntry (checkResources + auditTrail shape as
    compared by engine_test.go's wantDecisionLogs). ``traceId`` and ``shard``
    correlate the decision entry with the request's trace and the device
    lane that evaluated it — the join key between audit, /_cerbos/debug
    traces, and the flight recorder. ``policyEpoch`` records which committed
    policy epoch evaluated the request (engine/rollout.py) — the stamp the
    mixed-table chaos drills audit. ``provenance`` is the same kind of PDP
    extension: the winning rule-table row and the evaluator (device/oracle)
    per action — kept OUTSIDE the Cerbos-schema ``checkResources`` block so
    log consumers comparing against the upstream entry shape stay clean."""
    effective: dict[str, dict] = {}
    for o in outputs:
        for key, attrs in o.effective_policies.items():
            effective.setdefault(key, {"attributes": dict(attrs)})
    provenance = [
        _drop_empty(
            {
                "resourceId": o.resource_id,
                "actions": {
                    a: _drop_empty(
                        {
                            "matchedRule": e.matched_rule,
                            "ruleRowId": e.rule_row_id if e.rule_row_id >= 0 else None,
                            "source": e.source,
                        }
                    )
                    for a, e in o.actions.items()
                    if e.matched_rule or e.source
                },
            }
        )
        for o in outputs
    ]
    if all(not p.get("actions") for p in provenance):
        provenance = []
    return _drop_empty(
        {
            "callId": call_id,
            "timestamp": _now_iso(),
            "kind": "decision",
            "traceId": trace_id,
            "shard": shard,
            "policyEpoch": epoch,
            "provenance": provenance,
            "checkResources": {
                "inputs": [_input_json(i) for i in inputs],
                "outputs": [_output_json(o) for o in outputs],
            },
            "auditTrail": {"effectivePolicies": effective} if effective else {},
        }
    )


KIND_ACCESS = "access"
KIND_DECISION = "decision"
# how long close() lets the writer empty the queue: well inside the 60 s a
# supervisor gives a SIGTERM, long against 4,096 entries at a millisecond each
DRAIN_TIMEOUT_S = 30.0
# an entry is some 1 KB (one check) to 30 KB (a page of 50 resources)
_BYTES_BUCKETS = [256, 512, 1024, 2048, 4096, 8192, 16384, 32768, 65536, 131072, 524288]
# serialise + write + flush: 40 us (one check) to a millisecond (a page), more where the disk stalls
_WRITE_BUCKETS = [0.00001, 0.000025, 0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.05, 0.25, 1.0]

_log = logging.getLogger("cerbos_tpu.audit")


class AuditLog:
    """Async audit writer over a backend."""

    def __init__(
        self,
        backend: Any = None,
        decision_filter: Optional[DecisionFilter] = None,
        access_logs_enabled: bool = True,
        decision_logs_enabled: bool = True,
        backend_name: str = "",
    ):
        self.backend = backend
        self.backend_name = backend_name or type(backend).__name__
        self.decision_filter = decision_filter or DecisionFilter()
        self.access_logs_enabled = access_logs_enabled
        self.decision_logs_enabled = decision_logs_enabled
        # brownout shed flag (engine/brownout.py shed_audit): while set,
        # entries are dropped at the door — the decision still happens,
        # only its record is lost, and each loss is counted as evidence
        self._shed = False
        self._closed = False
        self._queue: "queue.Queue[Optional[dict]]" = queue.Queue(maxsize=4096)
        self._init_metrics()
        self._worker = threading.Thread(target=self._drain, daemon=True, name="audit-writer")
        self._worker.start()

    def _init_metrics(self) -> None:
        from ..observability import metrics

        reg = metrics()
        self.m_depth = reg.gauge(
            "cerbos_tpu_audit_queue_depth",
            "audit entries buffered for the async writer; sustained growth means the backend is slower than the decision rate",
        )
        self.m_dropped = reg.counter(
            "cerbos_tpu_audit_dropped_total",
            "audit entries dropped because the async queue was full (the hot path never blocks on audit)",
        )
        self.m_entries = reg.counter_vec(
            "cerbos_tpu_audit_entries_total",
            "audit entries by kind (decision, access) and outcome: queued for the writer, written by the "
            "backend, filtered by decisionLogFilters; queued = written + lost{reason=failed} + queue depth",
            label=("kind", "outcome"),
        )
        self.m_lost = reg.counter_vec(
            "cerbos_tpu_audit_lost_total",
            "audit entries lost, by kind and reason: dropped (queue full, or queued after close), shed "
            "(brownout stage shed_audit), failed (the backend's write raised); 0 growth = nothing lost",
            label=("kind", "reason"),
        )
        self.m_write = reg.histogram_vec(
            "cerbos_tpu_audit_write_seconds",
            "writer thread, per entry by backend and kind: serialise, write, flush (rotation included "
            "where one falls due); an access entry is some 1/40 of a page's decision entry",
            label=("backend", "kind"),
            buckets=_WRITE_BUCKETS,
        )
        self.m_bytes = reg.histogram_vec(
            "cerbos_tpu_audit_entry_bytes",
            "serialised size of a written entry by kind, where the backend reports it (file)",
            label="kind",
            buckets=_BYTES_BUCKETS,
        )
        self.m_writer = reg.counter_vec(
            "cerbos_tpu_audit_writer_seconds_total",
            "wall seconds of the writer thread by state (write: from taking an entry to the backend's "
            "return; idle: waiting for one); over both states they grow by the seconds that pass",
            label="state",
        )
        for kind in (KIND_DECISION, KIND_ACCESS):
            for outcome in ("queued", "written", "filtered"):
                self.m_entries.inc((kind, outcome), 0.0)
            for reason in ("dropped", "shed", "failed"):
                self.m_lost.inc((kind, reason), 0.0)

    def _drain(self) -> None:
        from ..observability import region

        t_from = time.monotonic()
        while True:
            try:
                # the timeout only books the idle seconds so far: a scrape
                # must see the two states add up to the seconds that passed
                entry = self._queue.get(timeout=1.0)
            except queue.Empty:
                continue
            finally:
                t_from = self._book("idle", t_from)
            self.m_depth.set(float(self._queue.qsize()))
            if entry is None:
                return
            kind = entry["kind"]
            try:
                with region("audit.write", kind=kind):
                    size = self.backend.write(entry)
            except Exception:  # noqa: BLE001
                self.m_lost.inc((kind, "failed"))
                _log.exception("audit write failed")
            else:
                self.m_entries.inc((kind, "written"))
                if isinstance(size, int):
                    self.m_bytes.observe(kind, float(size))
            t_done = self._book("write", t_from)
            self.m_write.observe((self.backend_name, kind), t_done - t_from)
            t_from = t_done

    def _book(self, state: str, t_from: float) -> float:
        now = time.monotonic()
        self.m_writer.inc(state, now - t_from)
        return now

    def set_shed(self, flag: bool) -> None:
        """Brownout applier (stage ``shed_audit``). Reversible: clearing the
        flag resumes writes with the queue and worker untouched."""
        self._shed = bool(flag)

    def _shedding(self, kind: str) -> bool:
        if not self._shed:
            return False
        from ..engine import brownout

        brownout.controller().note_shed("audit")
        self.m_lost.inc((kind, "shed"))
        return True

    def _submit(self, entry: dict) -> str:
        """Queue the entry; what became of it: ``queued`` or ``dropped``."""
        kind = entry["kind"]
        if not self._closed:
            try:
                self._queue.put_nowait(entry)
            except queue.Full:
                self.m_dropped.inc()  # drop rather than block the request path
            else:
                self.m_entries.inc((kind, "queued"))
                self.m_depth.set(float(self._queue.qsize()))
                return "queued"
        self.m_lost.inc((kind, "dropped"))
        return "dropped"

    def write_access(self, call_id: str, method: str, peer: str = "", error: str = "") -> Optional[str]:
        """One entry per call that reached the service (server/service.py:
        ``_access_entry``), under the call id of its decision entry;
        ``error`` names what a call that was not answered raised."""
        if not self.access_logs_enabled or self.backend is None:
            return None
        if self._shedding(KIND_ACCESS):
            return "shed"
        entry = {"callId": call_id, "timestamp": _now_iso(), "kind": KIND_ACCESS, "method": method, "peer": peer}
        if error:
            entry["error"] = error
        return self._submit(entry)

    def write_decision(
        self,
        call_id: str,
        inputs: list[T.CheckInput],
        outputs: list[T.CheckOutput],
        trace_id: str = "",
        shard: Optional[int] = None,
        epoch: Optional[int] = None,
    ) -> Optional[str]:
        """What became of the entry (``queued``, ``filtered``, ``shed``,
        ``dropped``), or None where decision logs are off."""
        if not self.decision_logs_enabled or self.backend is None:
            return None
        if self._shedding(KIND_DECISION):
            return "shed"
        if not self.decision_filter.keep(inputs, outputs):
            self.m_entries.inc((KIND_DECISION, "filtered"))
            return "filtered"
        return self._submit(
            _entry_from_decision(
                call_id, inputs, outputs, trace_id=trace_id, shard=shard, epoch=epoch
            )
        )

    def write_plan(self, call_id: str, plan_input: Any, plan_output: Any) -> None:
        """Plan decision entry mirroring DecisionLogEntry.PlanResources
        (api/public/cerbos/audit/v1/audit.proto: input {requestId, action(s),
        principal, resource}, output {filter, filterDebug}) plus
        auditTrail.effectivePolicies (engine.go:186-200)."""
        if not self.decision_logs_enabled or self.backend is None:
            return
        if self._shedding(KIND_DECISION):
            return
        principal = getattr(plan_input, "principal", None)
        cond = getattr(plan_output, "condition", None)
        entry = {
            "callId": call_id,
            "timestamp": _now_iso(),
            "kind": KIND_DECISION,
            "planResources": {
                "input": {
                    "requestId": getattr(plan_input, "request_id", ""),
                    "actions": list(getattr(plan_input, "actions", [])),
                    "principal": {
                        "id": getattr(principal, "id", ""),
                        "roles": list(getattr(principal, "roles", [])),
                        "policyVersion": getattr(principal, "policy_version", ""),
                        "scope": getattr(principal, "scope", ""),
                    },
                    "resource": {
                        "kind": getattr(plan_input, "resource_kind", ""),
                        "policyVersion": getattr(plan_input, "resource_policy_version", ""),
                        "scope": getattr(plan_input, "resource_scope", ""),
                    },
                },
                "output": {
                    "requestId": getattr(plan_input, "request_id", ""),
                    "filter": {
                        "kind": getattr(plan_output, "kind", ""),
                        **({"condition": cond.to_json()} if cond is not None else {}),
                    },
                    "filterDebug": cond.debug_str() if cond is not None else getattr(plan_output, "kind", ""),
                },
            },
        }
        effective = getattr(plan_output, "effective_policies", None)
        if effective:
            # same SourceAttributes wrapping as the check path, so log
            # consumers read one shape (audit.proto AuditTrail)
            entry["auditTrail"] = {
                "effectivePolicies": {k: {"attributes": v} for k, v in effective.items()}
            }
        self._submit(entry)

    def close(self) -> None:
        """Write what is queued, then close the backend. The listeners have
        stopped by now (cli: ``server.stop()`` comes first); an entry that
        still arrives is counted ``dropped``. A writer that cannot empty the
        queue in ``DRAIN_TIMEOUT_S`` (a wedged backend) is left behind and
        what it had not taken is counted ``dropped`` too, by kind."""
        if self._closed:
            return
        self._closed = True
        with contextlib.suppress(queue.Full):
            self._queue.put(None, timeout=DRAIN_TIMEOUT_S)
        self._worker.join(timeout=DRAIN_TIMEOUT_S)
        if self._worker.is_alive():
            left = 0
            with contextlib.suppress(queue.Empty):
                while True:
                    entry = self._queue.get_nowait()
                    if entry is not None:
                        left += 1
                        self.m_lost.inc((entry["kind"], "dropped"))
            _log.error("audit writer did not drain in %.0f s: %d entries dropped", DRAIN_TIMEOUT_S, left)
        self.m_depth.set(float(self._queue.qsize()))
        if self.backend is not None and hasattr(self.backend, "close"):
            self.backend.close()


_BACKENDS: dict[str, Callable[[dict], Any]] = {}


def register_backend(name: str, factory: Callable[[dict], Any]) -> None:
    _BACKENDS[name] = factory


# backends living outside this module register on first use (the storage
# registry's _LAZY_DRIVERS pattern)
_LAZY_BACKENDS = {"remote": "cerbos_tpu.audit.remote", "kafka": "cerbos_tpu.audit.kafka"}


def new_audit_log(conf: dict) -> Optional[AuditLog]:
    if not conf.get("enabled", False):
        return None
    backend_name = conf.get("backend", "local")
    factory = _BACKENDS.get(backend_name)
    if factory is None and backend_name in _LAZY_BACKENDS:
        import importlib

        importlib.import_module(_LAZY_BACKENDS[backend_name])
        factory = _BACKENDS.get(backend_name)
    if factory is None:
        raise ValueError(f"unknown audit backend {backend_name!r} (known: {sorted(_BACKENDS)})")
    backend = factory(conf.get(backend_name, {}))
    dconf = conf.get("decisionLogFilters", {})
    check_resources = dconf.get("checkResources", {})
    return AuditLog(
        backend=backend,
        decision_filter=DecisionFilter(
            ignore_allow_all=bool(check_resources.get("ignoreAllowAll", False)),
            ignored_actions=list(check_resources.get("ignoredActions", [])),
        ),
        access_logs_enabled=bool(conf.get("accessLogsEnabled", True)),
        decision_logs_enabled=bool(conf.get("decisionLogsEnabled", True)),
        backend_name=backend_name,
    )
