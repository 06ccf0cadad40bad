"""A CheckResources call, written once: what happens to it, in what order,
under which stamps, and what each refusal becomes on each wire.

The listeners (``server.py``) are two adapters over this module. A surface
decodes its own wire and hands ``front`` the fields it found; it reads its own
deadline, trace context and peer into the record; it goes through ``enter``
(from an event loop: ``enter_from_loop``) into the service; it encodes its own
reply and calls ``back``; and whatever is raised on the way it hands to
``refuse``, which books the refusal and gives the row of ``REFUSALS`` to
render. The record's ``release`` is the adapter's one ``finally``.

Imports nothing from ``server.py``.
"""

from __future__ import annotations

import asyncio
import time
from typing import Any, Callable, NamedTuple, Optional

import grpc

from ..engine.admission import OverloadRefused, retry_after_header
from ..engine.admission import controller as admission_controller
from ..engine.batcher import DeadlineExceeded
from ..engine.budget import (
    FRONT_ADMIT,
    FRONT_AUXDATA,
    FRONT_CONVERT,
    FRONT_VALIDATE,
    OUTCOME_EXPIRED,
    OUTCOME_MET,
    OUTCOME_ORACLE,
    OUTCOME_REFUSED,
    STAGE_INGRESS_PARSE,
    STAGE_REPLY_ENCODE,
)
from ..engine.budget import tracker as budget_tracker
from .service import CerbosService, RequestLimitExceeded


class CheckCall:
    """One request's record: the only thing the sequence allocates for it.

    ``t_raw`` is when its bytes arrived and ``t_parsed`` when they were
    decoded (None where nobody stamped the decode: a handler called with a
    message). ``front`` fills ``wf``, ``inputs``, ``pclass`` and ``ticket``;
    the surface fills the rest."""

    __slots__ = (
        "t_raw", "t_parsed", "wf", "inputs", "request_id", "include_meta",
        "deadline", "trace_ctx", "access", "pclass", "ticket",
    )

    def __init__(self, t_raw: float, t_parsed: Optional[float] = None):
        self.t_raw = t_raw
        self.t_parsed = t_parsed
        self.wf = None
        self.inputs = None
        self.request_id = ""
        self.include_meta = False
        self.deadline = None
        self.trace_ctx = None
        self.access = None
        self.pclass = None
        self.ticket = None

    def due(self, deadline: float) -> None:
        """The client's deadline (gRPC's), on the monotonic clock: it rides
        down the device path, so that work already expired is dropped and not
        evaluated, and into the waterfall."""
        self.deadline = deadline
        if self.wf is not None:
            self.wf.deadline = deadline

    def release(self) -> None:
        if self.ticket is not None:
            self.ticket.release()


class WireViolation(ValueError):
    """The decoded request breaks a rule of the wire (``wire_validate``, or the
    native reader, which applies the same rules on the bytes)."""


def front(
    svc: CerbosService,
    call: CheckCall,
    violation: Optional[str],
    token: Optional[str],
    key_set_id: str,
    inputs: Optional[list],
    make: Callable[[Any, Any], list],
    source: Any,
) -> None:
    """From the decode's end to the admission ticket. ``inputs`` are at hand
    (the native reader built them) or ``make(source, aux)`` makes them."""
    if violation:
        # before the waterfall exists: ``refuse`` then only counts the outcome
        raise WireViolation(violation)
    # the waterfall starts when the request BYTES arrived, so the decode's
    # cost is a visible stage and not unattributed time
    wf = call.wf = budget_tracker().start(t0=call.t_raw)
    if wf is not None:
        if call.t_parsed is not None:
            wf.mark(STAGE_INGRESS_PARSE, now=call.t_parsed)
        wf.part(FRONT_VALIDATE)
    aux = svc._extract_aux_data(token, key_set_id) if token else None
    if wf is not None:
        wf.part(FRONT_AUXDATA)
    if inputs is None:
        inputs = make(source, aux)
    elif aux is not None:
        for i in inputs:
            i.aux_data = aux
    call.inputs = inputs
    if wf is not None:
        wf.part(FRONT_CONVERT)
    # front-door admission: classify and gate BEFORE any dispatch; a refusal
    # costs the parse and one bucket update and never reaches the batcher,
    # the ticket ring or a device batch
    adm = admission_controller()
    if adm.enabled:
        first = inputs[0] if inputs else None
        cls = adm.classify(
            first.principal.id if first is not None else "",
            first.principal.roles if first is not None else (),
            [i.resource.kind for i in inputs],
            api="check",
        )
        call.pclass = cls.name
        call.ticket = adm.try_admit(cls)
    if wf is not None:
        wf.part(FRONT_ADMIT)


def enter(check: Callable, call: CheckCall):
    """Into the service by ``check``, its ``check_resources`` (the answer:
    ``(outputs, call_id)``) or its ``check_resources_async`` (the coroutine
    that gives it), with what the front and the surface put into the record."""
    return check(
        call.inputs, deadline=call.deadline, trace_ctx=call.trace_ctx, wf=call.wf,
        pclass=call.pclass, access=call.access,
    )


# the three ways from an event loop into the service
AWAITED = "awaited"    # the evaluator settles on the loop: await it, no hop
INLINE = "inline"      # evaluation is the short serial path: run it on the loop
EXECUTOR = "executor"  # the engine blocks on the batcher: hop to the pool


def way_in(engine: Any, direct_dispatch: bool, can_await: bool) -> str:
    """How a call that arrives on the event loop reaches the service, from two
    observations: whether the engine's evaluator settles on a loop (a front
    end's ticket client) and ``ServerConfig.direct_dispatch``. A surface whose
    handlers are synchronous (the aio gRPC listener's) cannot await and says
    so. The sync gRPC server is on a pool thread already and does not ask."""
    if can_await and getattr(engine, "supports_async", False):
        return AWAITED
    # inline is correct (and saves the hop, ~100 us + GIL churn) only when
    # nothing blocks; with the cross-request batcher a handler BLOCKS until a
    # batch fills, and holding the shared loop would keep every other request
    # from ever joining its batch
    return INLINE if direct_dispatch else EXECUTOR


async def enter_from_loop(svc: CerbosService, call: CheckCall, way: str) -> tuple[list, str]:
    """``enter`` for a coroutine, by the way ``way_in`` chose."""
    if way is AWAITED:
        return await enter(svc.check_resources_async, call)
    if way is INLINE:
        return enter(svc.check_resources, call)
    return await asyncio.get_running_loop().run_in_executor(None, enter, svc.check_resources, call)


def back(call: CheckCall, final_part: str) -> Optional[float]:
    """The decision counted and the waterfall flushed, ``reply_encode`` ending
    at this instant with the part the surface names: ``encode`` where the
    reply's bytes are made after it (gRPC: by the wrapped serializer),
    ``serialize`` where the dump is inside it (HTTP). Returns that instant
    (None with the waterfall off)."""
    wf = call.wf
    outcome = OUTCOME_ORACLE if wf is not None and wf.served_by == "oracle" else OUTCOME_MET
    return budget_tracker().finish(wf, outcome, final_stage=STAGE_REPLY_ENCODE, final_part=final_part)


class Refusal(NamedTuple):
    """What one kind of failure becomes: in the books and on each wire."""

    exc: type
    outcome: Optional[str]  # the decision it is counted as (and the waterfall flushed under); None: not counted
    timed: bool             # the ingress-to-refusal wall time is observed (admission's refusal latency)
    grpc: grpc.StatusCode
    http: int
    code: int               # the ``code`` of the JSON error body (google.rpc.Code)
    retry_after: bool       # HTTP answers with a Retry-After header
    prefix: str = ""        # before the exception's own words


# in order: the first row whose type the exception is an instance of
REFUSALS = (
    Refusal(WireViolation, OUTCOME_REFUSED, False, grpc.StatusCode.INVALID_ARGUMENT, 400, 3, False),
    Refusal(OverloadRefused, OUTCOME_REFUSED, True, grpc.StatusCode.RESOURCE_EXHAUSTED, 429, 8, True),
    Refusal(RequestLimitExceeded, OUTCOME_REFUSED, False, grpc.StatusCode.INVALID_ARGUMENT, 400, 3, False),
    Refusal(DeadlineExceeded, OUTCOME_EXPIRED, False, grpc.StatusCode.DEADLINE_EXCEEDED, 504, 4, False),
    Refusal(Exception, None, False, grpc.StatusCode.INTERNAL, 500, 13, False, "check failed: "),
)


def refuse(call: CheckCall, exc: Exception) -> tuple[Refusal, str, Optional[dict]]:
    """Book what ``exc`` is and say how to answer it: its row, the message,
    and HTTP's headers (``Retry-After``, or None). A surface renders that:
    gRPC aborts with ``row.grpc``, HTTP answers ``row.http`` with a JSON body
    of ``row.code`` and the message."""
    row = next(r for r in REFUSALS if isinstance(exc, r.exc))
    if row.timed:
        admission_controller().observe_refusal(time.monotonic() - call.t_raw)
    if row.outcome is not None:
        budget_tracker().finish(call.wf, row.outcome)
    headers = {"Retry-After": retry_after_header(exc)} if row.retry_after else None
    return row, f"{row.prefix}{exc}", headers
