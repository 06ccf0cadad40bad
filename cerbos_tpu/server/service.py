"""CerbosService: the request-handling core shared by gRPC and HTTP.

Behavioral reference: internal/svc/cerbos_svc.go (CheckResources,
PlanResources, ServerInfo; request limits cerbos_svc.go:346-362).
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Any, Optional

from .. import __version__
from ..engine import types as T
from ..engine.budget import BACK_AUDIT, BACK_WAKE, FRONT_SPAN
from ..engine.engine import Engine
from ..observability import SpanContext, new_call_id, start_span


class RequestLimitExceeded(ValueError):
    pass


@dataclass
class ServiceLimits:
    """Ref: internal/server/conf.go:34-35 (defaults 50x50)."""

    max_actions_per_resource: int = 50
    max_resources_per_request: int = 50


@dataclass
class ServiceMetrics:
    check_count: int = 0
    plan_count: int = 0
    check_latency_ms: list = field(default_factory=list)
    batch_sizes: list = field(default_factory=list)

    def record_check(self, latency_ms: float, batch: int) -> None:
        self.check_count += 1
        self.check_latency_ms.append(latency_ms)
        self.batch_sizes.append(batch)
        if len(self.check_latency_ms) > 10000:
            del self.check_latency_ms[:5000]
            del self.batch_sizes[:5000]

    def snapshot(self) -> dict[str, float]:
        """Gauge snapshot for the OTLP metrics exporter (the same series the
        Prometheus handler renders — metrics.go:129-147 analogues)."""
        lat = sorted(self.check_latency_ms)

        def pct(p: float) -> float:
            return lat[min(len(lat) - 1, int(p * len(lat)))] if lat else 0.0

        return {
            "cerbos_dev_engine_check_count": float(self.check_count),
            "cerbos_dev_engine_plan_count": float(self.plan_count),
            "cerbos_dev_engine_check_latency_ms_p50": pct(0.50),
            "cerbos_dev_engine_check_latency_ms_p95": pct(0.95),
            "cerbos_dev_engine_check_latency_ms_p99": pct(0.99),
            "cerbos_dev_engine_check_batch_size_total": float(sum(self.batch_sizes)),
        }


class _Checked:
    """What ``CerbosService._checking`` hands its block: the call's id, and
    the place for the engine's answer."""

    __slots__ = ("call_id", "outputs")

    def __init__(self, call_id: str):
        self.call_id = call_id
        self.outputs: list[T.CheckOutput] = []


class CerbosService:
    def __init__(
        self,
        engine: Engine,
        aux_data_mgr: Any = None,
        limits: Optional[ServiceLimits] = None,
        audit_log: Any = None,
        planner: Any = None,
        plan_batcher: Any = None,
    ):
        self.engine = engine
        self.aux_data_mgr = aux_data_mgr
        self.limits = limits or ServiceLimits()
        self.audit_log = audit_log
        self.planner = planner
        # a BatchingEvaluator with a BatchPlanner attached (plan lane):
        # when present, plan queries coalesce into vectorized partial-
        # evaluation flights instead of walking the rule table one by one
        self.plan_batcher = plan_batcher
        self.metrics = ServiceMetrics()

    def _extract_aux_data(self, jwt_token: str, key_set_id: str) -> Optional[T.AuxData]:
        if not jwt_token:
            return None
        if self.aux_data_mgr is None:
            return None
        return self.aux_data_mgr.extract(jwt_token, key_set_id)

    def check_resources(
        self,
        inputs: list[T.CheckInput],
        params: Optional[T.EvalParams] = None,
        deadline: Optional[float] = None,
        trace_ctx: Optional[SpanContext] = None,
        wf: Optional[Any] = None,
        pclass: Optional[str] = None,
        access: Optional[tuple[str, str]] = None,
    ) -> tuple[list[T.CheckOutput], str]:
        with self._checking(inputs, trace_ctx, wf, access) as call:
            call.outputs = self.engine.check(inputs, params=params, deadline=deadline, wf=wf, pclass=pclass)
        return call.outputs, call.call_id

    async def check_resources_async(
        self,
        inputs: list[T.CheckInput],
        params: Optional[T.EvalParams] = None,
        deadline: Optional[float] = None,
        trace_ctx: Optional[SpanContext] = None,
        wf: Optional[Any] = None,
        pclass: Optional[str] = None,
        access: Optional[tuple[str, str]] = None,
    ) -> tuple[list[T.CheckOutput], str]:
        """``check_resources`` for evaluators that settle on the event loop
        (front-end mode): the handler coroutine awaits the batcher ticket
        directly, with no thread-pool hop per request."""
        with self._checking(inputs, trace_ctx, wf, access) as call:
            call.outputs = await self.engine.check_await(inputs, params=params, deadline=deadline, wf=wf, pclass=pclass)
        return call.outputs, call.call_id

    @contextlib.contextmanager
    def _checking(
        self,
        inputs: list[T.CheckInput],
        trace_ctx: Optional[SpanContext],
        wf: Optional[Any],
        access: Optional[tuple[str, str]],
    ):
        """Everything of a CheckResources call but the engine's answer, which
        the block puts into the ``_Checked`` it is given (``outputs``): before
        it the call id, the request limits, the span and the ``span`` part;
        after it the validation count, ``wake``, the audit hand-off, ``audit``
        and the service's own latency; around it the access entry."""
        call = _Checked(new_call_id())
        try:
            self._validate_check(inputs)
            t0 = time.perf_counter()
            # trace_ctx is the caller's W3C traceparent (gRPC metadata / HTTP
            # header); with parent=None this still roots a fresh local trace
            with start_span("request.CheckResources", parent=trace_ctx, resources=len(inputs)) as span:
                span.set_attribute("call_id", call.call_id)
                # clear any shard/epoch affinity left by a previous request on
                # this thread; the evaluator that resolves this request
                # re-stamps both
                T.set_current_shard(None)
                T.set_current_epoch(None)
                if wf is not None:
                    if not wf.trace_id:
                        wf.trace_id = span.context.trace_id
                    wf.part(FRONT_SPAN)
                yield call
                self._note_validation(span, call.outputs)
                if wf is not None:
                    wf.part(BACK_WAKE)
                self._audit_decision(span, call.call_id, inputs, call.outputs)
                if wf is not None:
                    wf.part(BACK_AUDIT)
            self.metrics.record_check((time.perf_counter() - t0) * 1000, len(inputs))
        except BaseException as e:
            self._access_entry(call.call_id, access, error=type(e).__name__)
            raise
        self._access_entry(call.call_id, access)

    def _note_validation(self, span: Any, outputs: list[T.CheckOutput]) -> None:
        """The request's count of schema validation errors, on its span; not
        counted, and no attribute, with ``schema.enforcement: none``."""
        mgr = getattr(self.engine, "schema_mgr", None)
        if mgr is not None and mgr.enabled:
            span.set_attribute("validation_errors", sum(len(o.validation_errors) for o in outputs))

    def _audit_decision(
        self, span: Any, call_id: str, inputs: list[T.CheckInput], outputs: list[T.CheckOutput]
    ) -> None:
        """The audit hand-off: the decision entry built and queued on this
        thread (audit/log.py), inside the request's span and between the
        ``wake`` and ``audit`` marks of its waterfall."""
        if self.audit_log is not None:
            self.audit_log.write_decision(
                call_id,
                inputs,
                outputs,
                trace_id=span.context.trace_id,
                shard=T.current_shard(),
                epoch=T.current_epoch(),
            )

    def access_of(self, method: str, peer: Any) -> Optional[tuple[str, str]]:
        """What a call's access entry names, for the listener to pass as
        ``access=``: the RPC and the caller's address (``peer`` is called for
        it), or None where no access entry is written, and nothing is read.
        Read BEFORE the engine answers: gRPC's ``ctx.peer()`` leaves the
        interpreter lock, and right after the decision entry is queued that
        hands it to the audit writer for a whole serialisation."""
        log = self.audit_log
        if log is None or not log.access_logs_enabled:
            return None
        return method, peer() or ""

    def _access_entry(self, call_id: str, access: Optional[tuple[str, str]], error: str = "") -> None:
        """One access entry for a call that reached the service, under the
        call id of its decision entry, whether it was answered or raised (the
        entry then names the error). A call refused before it reaches the
        service (wire validation, admission, the ``shed_plan`` rung) writes
        none."""
        if access is not None:
            self.audit_log.write_access(call_id, *access, error=error)

    def _validate_check(self, inputs: list[T.CheckInput]) -> None:
        if len(inputs) > self.limits.max_resources_per_request:
            raise RequestLimitExceeded(
                f"number of resources exceeds the limit of {self.limits.max_resources_per_request}"
            )
        for i in inputs:
            if len(i.actions) > self.limits.max_actions_per_resource:
                raise RequestLimitExceeded(
                    f"number of actions exceeds the limit of {self.limits.max_actions_per_resource}"
                )
            if not i.actions:
                raise RequestLimitExceeded("at least one action must be specified")

    def plan_resources(
        self, input: Any, params: Optional[T.EvalParams] = None, access: Optional[tuple[str, str]] = None
    ) -> tuple[Any, str]:
        call_id = new_call_id()
        try:
            output = self._plan(call_id, input, params)
        except BaseException as e:
            self._access_entry(call_id, access, error=type(e).__name__)
            raise
        self._access_entry(call_id, access)
        return output, call_id

    def _plan(self, call_id: str, input: Any, params: Optional[T.EvalParams]) -> Any:
        if self.planner is None and self.plan_batcher is None:
            raise NotImplementedError("PlanResources is not configured")
        pb = self.plan_batcher
        if pb is not None and getattr(pb, "plan_planner", None) is not None:
            # plan-lane path: OverloadRefused propagates (the handlers turn
            # it into 429/RESOURCE_EXHAUSTED and book outcome=refused);
            # anything else degrades to the sequential walk below
            from ..engine.admission import OverloadRefused

            try:
                output = pb.plan([input], params=params)[0]
            except OverloadRefused:
                raise
            except Exception:  # noqa: BLE001
                if self.planner is None:
                    raise
                output = self.planner.plan(input, params=params)
        else:
            output = self.planner.plan(input, params=params)
        self.metrics.plan_count += 1
        if self.audit_log is not None:
            self.audit_log.write_plan(call_id, input, output)
        return output

    def server_info(self) -> dict[str, str]:
        return {"version": f"cerbos-tpu {__version__}", "commit": "", "buildDate": ""}
