"""gRPC + HTTP API server.

Behavioral reference: internal/server/server.go — two listeners (gRPC on
3593, HTTP on 3592), the HTTP surface mirroring the grpc-gateway routes
(/api/check/resources, /api/plan/resources), health at /_cerbos/health,
Prometheus metrics at /_cerbos/metrics. The gRPC service registers under the
reference's full method names so existing Cerbos gRPC clients connect
unchanged.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import threading
import time
from concurrent import futures
from dataclasses import dataclass
from typing import Any, Optional

import grpc
from aiohttp import web

from ..engine import brownout as brownout_ctl
from ..engine import types as T
from ..engine.admission import OverloadRefused, retry_after_header
from ..engine.admission import controller as admission_controller
from ..engine.budget import BACK_ENCODE, BACK_SERIALIZE, OUTCOME_MET, OUTCOME_REFUSED
from ..engine.budget import tracker as budget_tracker
from .. import fastjson, native
from ..engine.flight import recorder as flight_recorder
from ..engine.pressure import monitor as pressure_monitor
from ..engine.readiness import state as readiness_state
from ..observability import metrics, parse_traceparent
from . import checkcall, convert, wire_validate
from .service import CerbosService, RequestLimitExceeded


class _IngressStamps:
    """Raw-bytes ingress timestamps for the gRPC path.

    The latency waterfall must start when the request BYTES arrive, not
    after protobuf decode — otherwise decode cost is invisible and the
    stage sum can never reconcile with socket-level wall clock. gRPC gives
    handlers only the decoded message, so the request deserializer (which
    runs on the raw bytes) records ``(t_raw, t_decoded)`` keyed by the
    decoded message's identity, and the handler pops its stamp by the same
    key. Bounded: an entry whose handler never runs (abort between decode
    and dispatch) is evicted FIFO instead of leaking."""

    def __init__(self, cap: int = 4096):
        self._lock = threading.Lock()
        self._stamps: dict[int, tuple[float, float]] = {}  # insertion-ordered
        self._cap = cap

    def put(self, key: int, t_raw: float, t_decoded: float) -> None:
        with self._lock:
            self._stamps.pop(key, None)  # re-insert at the tail on id reuse
            self._stamps[key] = (t_raw, t_decoded)
            while len(self._stamps) > self._cap:
                self._stamps.pop(next(iter(self._stamps)))

    def pop(self, key: int) -> Optional[tuple[float, float]]:
        with self._lock:
            return self._stamps.pop(key, None)


_GRPC_STAMPS = _IngressStamps()
# the other end: (t_raw, end of reply_encode) keyed by the RESPONSE message's
# identity, put by the handler and popped by the wrapped response serializer
_GRPC_REPLY_STAMPS = _IngressStamps()


def _stamping_deserializer(deserialize):
    """Wrap a request deserializer so decode start/end are captured at
    the raw-bytes boundary (works under both the sync and aio servers —
    each runs the deserializer before dispatching to the handler)."""

    def wrapped(data: bytes):
        t_raw = time.monotonic()
        msg = deserialize(data)
        _GRPC_STAMPS.put(id(msg), t_raw, time.monotonic())
        return msg

    return wrapped


def _stamping_serializer(serialize):
    """Wrap a protobuf ``SerializeToString`` so the handler's extent ends
    where the response BYTES exist: observes ``serialize`` (end of
    ``reply_encode`` to here) and the handler histogram, once each, for the
    responses the handler stamped (none with the waterfall off). A reply the
    native codec wrote is bytes already and passes through."""

    def wrapped(msg) -> bytes:
        stamp = _GRPC_REPLY_STAMPS.pop(id(msg))
        data = msg if type(msg) is bytes else serialize(msg)
        if stamp is not None:
            budget_tracker().observe_reply(stamp[0], stamp[1], time.monotonic())
        return data

    return wrapped


class _WireCodec:
    """What stands between a CheckResources request's wire bytes and the
    engine's types, in both directions: the native module's reader and writer
    (``check_request_decode``, ``check_reply_encode``) where it is loaded and
    takes the bytes or the outputs at hand, else protobuf's own parse with
    ``convert`` and ``wire_validate``: the definition, which answers whatever
    the native codec declines (it returns None: a singular message field met
    twice, a group, deep nesting, a pattern's non-ASCII subject, an output
    value ``py_to_value`` would stringify) and raises what is to be raised
    (malformed bytes: ``DecodeError``). Which path took a request or a reply
    is decided by what it holds, never by a setting, and counted."""

    def __init__(self):
        from ..api.cerbos.request.v1 import request_pb2

        self._from_string = request_pb2.CheckResourcesRequest.FromString
        mod = native.get()
        self._decode = getattr(mod, "check_request_decode", None)
        self._encode = getattr(mod, "check_reply_encode", None)
        self._count = metrics().counter_vec(
            "cerbos_tpu_wire_codec_total",
            "gRPC CheckResources requests read (dir=request) and replies written (dir=reply), by what did it: "
            "native (cerbos_native, no protobuf message built) or python (protobuf + server/convert.py: the native "
            "module is not loaded, or it declined what the request or the reply holds)",
            label=("dir", "path"),
        ).inc

    def decode(self, data: bytes):
        """The request a handler takes: the native reader's tuple ``(inputs,
        request_id, include_meta, token, key_set_id, violation, data)``, or
        the message."""
        if self._decode is not None:
            req = self._decode(data, T.Principal, T.Resource, T.CheckInput)
            if req is not None:
                self._count(("request", "native"))
                return req
        req = self._from_string(data)
        self._count(("request", "python"))
        return req

    def fields(self, req):
        """What a handler reads of a request, whichever ``decode`` gave (or a
        test or a shim hands it: a message): ``(inputs | None, request_id,
        include_meta, token, key_set_id, violation)``. The native reader built
        the inputs and applied the wire rules on the bytes; a message's inputs
        are still to be made (``convert.check_resources_request_to_inputs``)."""
        if type(req) is tuple:
            return req[:6]
        jwt = req.aux_data.jwt if req.HasField("aux_data") else None
        token, key_set_id = (jwt.token, jwt.key_set_id) if jwt is not None else ("", "")
        return None, req.request_id, req.include_meta, token, key_set_id, wire_validate.check_resources_proto(req)

    def encode(self, req, request_id: str, call_id: str, inputs, outputs, include_meta: bool):
        """The reply a handler returns: its bytes, or the message. ``req`` is
        what ``decode`` gave, for the message path to read."""
        if self._encode is not None:
            data = self._encode(request_id, call_id, inputs, outputs, include_meta)
            if data is not None:
                self._count(("reply", "native"))
                return data
        if type(req) is tuple:
            req = self._from_string(req[6])
        self._count(("reply", "python"))
        return convert.outputs_to_check_resources_response(req, outputs, call_id)


class _StampingPool(futures.ThreadPoolExecutor):
    """The sync gRPC server's thread pool, with one clock on it: the wait
    between gRPC's core handing a call to the pool (``submit``) and a worker
    thread starting it: a thread's wake-up and its wait for the interpreter
    lock. It lies BEFORE the handler's extent and is not added into it; every
    call gRPC submits is observed, health checks included. (That extent starts
    in the stamping deserializer, which the sync server runs on its SERVING
    thread, in the ``receive_message`` callback: the worker started here then
    waits on the call's condition for the decoded message, a second wake-up,
    inside the extent's ``validate`` part.) Two ``perf_counter`` reads and one
    closure a call."""

    def __init__(self, max_workers: int):
        super().__init__(max_workers=max_workers)
        self._observe_wait = metrics().histogram(
            "cerbos_tpu_listener_pool_wait_seconds",
            "sync gRPC server: seconds between gRPC's core handing a call to the listener's thread pool and a "
            "worker thread starting it (a thread wake-up and the wait for the interpreter lock); before the "
            "handler's extent, not part of it; the aio server has no pool and observes nothing",
            buckets=[0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.05, 0.25, 1.0],
        ).observe

    def submit(self, fn, /, *args, **kwargs):
        observe, handed = self._observe_wait, time.perf_counter()

        def started():
            observe(time.perf_counter() - handed)
            return fn(*args, **kwargs)

        return super().submit(started)


@dataclass
class ServerConfig:
    """Ref: internal/server/conf.go (default ports 3592/3593; TCP or UDS
    listeners server.go:152-162; TLS server.go:219-268)."""

    http_listen_addr: str = "0.0.0.0:3592"
    grpc_listen_addr: str = "0.0.0.0:3593"
    max_workers: int = 16
    tls_cert: str = ""
    tls_key: str = ""
    # CORS (ref: server/conf.go:90-99, middleware.go:150-186)
    cors_disabled: bool = False
    cors_allowed_origins: tuple = ()
    cors_allowed_headers: tuple = ()
    cors_max_age_s: int = 0
    tls_watch_interval_s: float = 5.0  # certinel-style rotation poll
    # multi-process worker pools bind every worker's listeners to the same
    # ports; the kernel load-balances accepted connections (SO_REUSEPORT)
    reuse_port: bool = False
    # run check/plan handlers inline on the event loop instead of hopping to
    # the thread pool. Correct (and faster: the hop costs ~100µs + GIL churn)
    # when evaluation is the short serial path; MUST stay False when the
    # engine blocks on the cross-request batcher, which needs concurrent
    # requests in flight to fill a batch
    direct_dispatch: bool = False
    # serve gRPC through grpc.aio on the same event loop as HTTP (no
    # per-call thread hop; handlers stay synchronous — an adapter translates
    # abort semantics). Measured on the single-core dev host the asyncio
    # hop costs slightly MORE than the thread hop (1,075 vs 1,258 RPS), so
    # the threaded sync server stays the default; multi-core deployments
    # wanting fewer threads per worker can flip server.grpcAsync
    grpc_async: bool = False
    # worker-pool identity: stamped as a worker="..." label on every
    # /_cerbos/metrics sample so a scrape that lands on a random
    # SO_REUSEPORT sibling stays distinguishable (docs/OBSERVABILITY.md)
    worker_label: str = ""

    def ssl_context(self):
        if not (self.tls_cert and self.tls_key):
            return None
        import ssl

        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        ctx.load_cert_chain(self.tls_cert, self.tls_key)
        return ctx


class _CertWatcher:
    """Hot cert rotation without restart (ref: server.go:219-268, certinel
    fswatcher): polls the cert/key mtimes; on change reloads the chain into
    the live SSLContext (new HTTP handshakes pick it up immediately) and
    bumps a generation counter the gRPC credential fetcher reads."""

    def __init__(self, cert: str, key: str, ssl_ctx, interval: float):
        self.cert = cert
        self.key = key
        self.ssl_ctx = ssl_ctx
        self.interval = interval
        self.generation = 0
        self._stamp = self._mtimes()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True, name="cert-watcher")

    def _mtimes(self):
        import os

        try:
            return (os.stat(self.cert).st_mtime_ns, os.stat(self.key).st_mtime_ns)
        except OSError:
            return self._stamp if hasattr(self, "_stamp") else (0, 0)

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()

    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            stamp = self._mtimes()
            if stamp == self._stamp:
                continue
            self._stamp = stamp
            try:
                if self.ssl_ctx is not None:
                    self.ssl_ctx.load_cert_chain(self.cert, self.key)
                self.generation += 1
            except Exception:  # noqa: BLE001  (mid-rotation partial write: retry next tick)
                pass

    def grpc_credentials(self):
        """Server credentials whose cert configuration re-reads the files
        whenever the watcher has seen a rotation."""
        seen = -1
        config = [None]

        def fetch():
            nonlocal seen
            if self.generation != seen or config[0] is None:
                seen = self.generation
                with open(self.key, "rb") as kf, open(self.cert, "rb") as cf:
                    config[0] = grpc.ssl_server_certificate_configuration(((kf.read(), cf.read()),))
            return config[0]

        return grpc.dynamic_ssl_server_credentials(fetch(), fetch)


class _ShimAbort(Exception):
    def __init__(self, code, details: str):
        self.code = code
        self.details = details
        super().__init__(details)


class _SyncAbortShim:
    """Presents the sync ServicerContext surface over an aio context: the
    handlers call ``ctx.abort`` expecting it to raise immediately (sync
    semantics); here it raises _ShimAbort, which the aio adapter translates
    into an awaited abort. Everything else forwards."""

    def __init__(self, ctx):
        self._ctx = ctx

    def abort(self, code, details: str):
        raise _ShimAbort(code, details)

    def __getattr__(self, name):
        return getattr(self._ctx, name)


def _aio_unary(behavior, inline: bool):
    async def handler(request, context):
        try:
            if inline:
                return behavior(request, _SyncAbortShim(context))
            # with the cross-request batcher the handler BLOCKS until a
            # batch fills; it must not hold the shared event loop (no other
            # request could ever join its batch) — hop to the pool instead
            loop = asyncio.get_running_loop()
            return await loop.run_in_executor(None, behavior, request, _SyncAbortShim(context))
        except _ShimAbort as e:
            await context.abort(e.code, e.details)

    return handler


def _aio_stream(behavior, inline: bool):
    async def handler(request, context):
        try:
            if inline:
                for item in behavior(request, _SyncAbortShim(context)):
                    yield item
                return
            loop = asyncio.get_running_loop()
            items = await loop.run_in_executor(
                None, lambda: list(behavior(request, _SyncAbortShim(context)))
            )
            for item in items:
                yield item
        except _ShimAbort as e:
            await context.abort(e.code, e.details)

    return handler


def aio_generic_handler(service_name: str, rpcs: dict, inline: bool = True):
    """Sync rpc method handlers → an aio-compatible generic handler.

    ``inline=True`` runs behaviors directly on the event loop (correct and
    fastest when handlers are short and non-blocking); ``inline=False`` hops
    each call to the default executor — required when the engine blocks on
    the cross-request batcher, which needs concurrent requests in flight."""
    wrapped = {}
    for name, h in rpcs.items():
        if h.unary_unary is not None:
            wrapped[name] = grpc.unary_unary_rpc_method_handler(
                _aio_unary(h.unary_unary, inline),
                request_deserializer=h.request_deserializer,
                response_serializer=h.response_serializer,
            )
        elif h.unary_stream is not None:
            wrapped[name] = grpc.unary_stream_rpc_method_handler(
                _aio_stream(h.unary_stream, inline),
                request_deserializer=h.request_deserializer,
                response_serializer=h.response_serializer,
            )
        else:  # pragma: no cover - no client/bidi streaming rpcs exist here
            raise ValueError(f"unsupported rpc kind for {name}")
    return grpc.method_handlers_generic_handler(service_name, wrapped)


# the method an access entry names (audit.accessLogsEnabled; the service
# queues the entry, service.py: _access_entry): upstream's HTTP gateway logs
# the gRPC method it forwards to, so both listeners name the same
_SVC = "/cerbos.svc.v1.CerbosService/"


def _grpc_rpcs(svc: CerbosService):
    from ..api.cerbos.request.v1 import request_pb2
    from ..api.cerbos.response.v1 import response_pb2

    codec = _WireCodec()

    def check_resources(req, ctx: grpc.ServicerContext):
        """gRPC's adapter over ``checkcall``. ``req`` is what the codec's reader
        gave the deserializer: the native one's tuple, or a
        ``CheckResourcesRequest`` (the Python path; tests and shims hand the
        handler one too)."""
        # the wrapped deserializer's stamp: when the request BYTES arrived and
        # when they were decoded
        stamp = _GRPC_STAMPS.pop(id(req))
        call = checkcall.CheckCall(*stamp) if stamp is not None else checkcall.CheckCall(time.monotonic())
        inputs, call.request_id, call.include_meta, token, key_set_id, violation = codec.fields(req)
        try:
            checkcall.front(
                svc, call, violation, token, key_set_id, inputs, convert.check_resources_request_to_inputs, req
            )
            remaining = ctx.time_remaining()
            if remaining is not None:
                call.due(time.monotonic() + remaining)
            # W3C trace-context rides gRPC metadata; the parsed context
            # parents the request span so the device batch joins the
            # caller's trace (shim contexts may lack the metadata accessor)
            meta_fn = getattr(ctx, "invocation_metadata", None)
            call.trace_ctx = parse_traceparent(
                dict(meta_fn() or ()).get("traceparent") if meta_fn is not None else None
            )
            call.access = svc.access_of(_SVC + "CheckResources", lambda: ctx.peer())
            outputs, call_id = checkcall.enter(svc.check_resources, call)
            if call.trace_ctx is not None:
                with contextlib.suppress(Exception):  # shim contexts may lack it
                    ctx.set_trailing_metadata((("traceparent", call.trace_ctx.to_traceparent()),))
            resp = codec.encode(req, call.request_id, call_id, call.inputs, outputs, call.include_meta)
            t_encoded = checkcall.back(call, BACK_ENCODE)
            if t_encoded is not None:
                _GRPC_REPLY_STAMPS.put(id(resp), call.t_raw, t_encoded)
            return resp
        except Exception as e:  # noqa: BLE001  (every row of checkcall.REFUSALS, the last one anything else)
            row, message, _ = checkcall.refuse(call, e)
        finally:
            call.release()
        ctx.abort(row.grpc, message)

    def plan_resources(req: request_pb2.PlanResourcesRequest, ctx: grpc.ServicerContext):
        if brownout_ctl.controller().active("shed_plan"):
            # staged brownout: plan queries yield to interactive checks
            brownout_ctl.controller().note_shed("plan")
            budget_tracker().count(OUTCOME_REFUSED, api="plan")
            ctx.abort(
                grpc.StatusCode.RESOURCE_EXHAUSTED,
                "overloaded: plan queries are shed (brownout)",
            )
        verr = wire_validate.plan_resources_proto(req)
        if verr:
            ctx.abort(grpc.StatusCode.INVALID_ARGUMENT, verr)
        try:
            aux = None
            if req.HasField("aux_data") and req.aux_data.jwt.token:
                aux = svc._extract_aux_data(req.aux_data.jwt.token, req.aux_data.jwt.key_set_id)
            body = {
                "requestId": req.request_id,
                "action": req.action,
                "actions": list(req.actions),
                "principal": {
                    "id": req.principal.id,
                    "roles": list(req.principal.roles),
                    "attr": {k: convert.value_to_py(v) for k, v in req.principal.attr.items()},
                    "policyVersion": req.principal.policy_version,
                    "scope": req.principal.scope,
                },
                "resource": {
                    "kind": req.resource.kind,
                    "attr": {k: convert.value_to_py(v) for k, v in req.resource.attr.items()},
                    "policyVersion": req.resource.policy_version,
                    "scope": req.resource.scope,
                },
                "includeMeta": req.include_meta,
            }
            resp_json, call_id = _plan_from_json(svc, body, aux, svc.access_of(_SVC + "PlanResources", lambda: ctx.peer()))
            budget_tracker().count(OUTCOME_MET, api="plan")
            return _plan_json_to_proto(resp_json, response_pb2)
        except OverloadRefused as e:
            # the batcher's plan-lane queue budget filled: same refusal
            # surface as the brownout shed above
            budget_tracker().count(OUTCOME_REFUSED, api="plan")
            ctx.abort(grpc.StatusCode.RESOURCE_EXHAUSTED, str(e))
        except NotImplementedError as e:
            ctx.abort(grpc.StatusCode.UNIMPLEMENTED, str(e))
        except RequestLimitExceeded as e:
            ctx.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        except Exception as e:  # noqa: BLE001
            ctx.abort(grpc.StatusCode.INTERNAL, f"plan failed: {e}")

    def server_info(req, ctx):
        info = svc.server_info()
        return response_pb2.ServerInfoResponse(version=info["version"], commit=info["commit"], build_date=info["buildDate"])

    def check_resource_set(req: request_pb2.CheckResourceSetRequest, ctx: grpc.ServicerContext):
        if not req.resource.instances:
            ctx.abort(grpc.StatusCode.INVALID_ARGUMENT, "at least one resource instance must be specified")
        verr = wire_validate.check_resource_set_proto(req)
        if verr:
            ctx.abort(grpc.StatusCode.INVALID_ARGUMENT, verr)
        try:
            aux = None
            if req.HasField("aux_data") and req.aux_data.jwt.token:
                aux = svc._extract_aux_data(req.aux_data.jwt.token, req.aux_data.jwt.key_set_id)
            principal = convert.principal_from_proto(req.principal)
            inputs = []
            rids = []
            for rid, inst in req.resource.instances.items():
                rids.append(rid)
                inputs.append(T.CheckInput(
                    request_id=req.request_id,
                    principal=principal,
                    resource=T.Resource(
                        kind=req.resource.kind,
                        id=rid,
                        attr={k: convert.value_to_py(v) for k, v in inst.attr.items()},
                        policy_version=req.resource.policy_version,
                        scope=req.resource.scope,
                    ),
                    actions=list(req.actions),
                    aux_data=aux,
                ))
            outputs, call_id = svc.check_resources(inputs, access=svc.access_of(_SVC + "CheckResourceSet", lambda: ctx.peer()))
            resp = response_pb2.CheckResourceSetResponse(request_id=req.request_id, cerbos_call_id=call_id)
            from ..api.cerbos.effect.v1 import effect_pb2

            for rid, out in zip(rids, outputs):
                inst_out = resp.resource_instances[rid]
                for action, ae in out.actions.items():
                    inst_out.actions[action] = convert._EFFECT_TO_ENUM.get(ae.effect, effect_pb2.EFFECT_DENY)
                for ve in out.validation_errors:
                    inst_out.validation_errors.add(
                        path=ve.path, message=ve.message, source=convert._SOURCE_TO_ENUM.get(ve.source, 0)
                    )
                if req.include_meta:
                    am = resp.meta.resource_instances[rid]
                    for action, ae in out.actions.items():
                        am.actions[action].matched_policy = ae.policy
                        am.actions[action].matched_scope = ae.scope
                    am.effective_derived_roles.extend(out.effective_derived_roles)
            return resp
        except RequestLimitExceeded as e:
            ctx.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        except Exception as e:  # noqa: BLE001
            ctx.abort(grpc.StatusCode.INTERNAL, f"check failed: {e}")

    def check_resource_batch(req: request_pb2.CheckResourceBatchRequest, ctx: grpc.ServicerContext):
        if not req.resources:
            ctx.abort(grpc.StatusCode.INVALID_ARGUMENT, "at least one resource must be specified")
        verr = wire_validate.check_resource_batch_proto(req)
        if verr:
            ctx.abort(grpc.StatusCode.INVALID_ARGUMENT, verr)
        try:
            aux = None
            if req.HasField("aux_data") and req.aux_data.jwt.token:
                aux = svc._extract_aux_data(req.aux_data.jwt.token, req.aux_data.jwt.key_set_id)
            principal = convert.principal_from_proto(req.principal)
            inputs = [
                T.CheckInput(
                    request_id=req.request_id,
                    principal=principal,
                    resource=convert.resource_from_proto(entry.resource),
                    actions=list(entry.actions),
                    aux_data=aux,
                )
                for entry in req.resources
            ]
            outputs, call_id = svc.check_resources(inputs, access=svc.access_of(_SVC + "CheckResourceBatch", lambda: ctx.peer()))
            resp = response_pb2.CheckResourceBatchResponse(request_id=req.request_id, cerbos_call_id=call_id)
            from ..api.cerbos.effect.v1 import effect_pb2

            for out in outputs:
                r = resp.results.add(resource_id=out.resource_id)
                for action, ae in out.actions.items():
                    r.actions[action] = convert._EFFECT_TO_ENUM.get(ae.effect, effect_pb2.EFFECT_DENY)
                for ve in out.validation_errors:
                    r.validation_errors.add(
                        path=ve.path, message=ve.message, source=convert._SOURCE_TO_ENUM.get(ve.source, 0)
                    )
            return resp
        except RequestLimitExceeded as e:
            ctx.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        except Exception as e:  # noqa: BLE001
            ctx.abort(grpc.StatusCode.INTERNAL, f"check failed: {e}")

    return {
        "CheckResourceSet": grpc.unary_unary_rpc_method_handler(
            check_resource_set,
            request_deserializer=request_pb2.CheckResourceSetRequest.FromString,
            response_serializer=lambda m: m.SerializeToString(),
        ),
        "CheckResourceBatch": grpc.unary_unary_rpc_method_handler(
            check_resource_batch,
            request_deserializer=request_pb2.CheckResourceBatchRequest.FromString,
            response_serializer=lambda m: m.SerializeToString(),
        ),
        "CheckResources": grpc.unary_unary_rpc_method_handler(
            check_resources,
            # stamped at the raw-bytes boundary: decode cost is waterfall
            # stage one, not invisible pre-handler time
            request_deserializer=_stamping_deserializer(codec.decode),
            # and the handler's extent ends where the response bytes exist
            response_serializer=_stamping_serializer(
                response_pb2.CheckResourcesResponse.SerializeToString
            ),
        ),
        "PlanResources": grpc.unary_unary_rpc_method_handler(
            plan_resources,
            request_deserializer=request_pb2.PlanResourcesRequest.FromString,
            response_serializer=lambda m: m.SerializeToString(),
        ),
        "ServerInfo": grpc.unary_unary_rpc_method_handler(
            server_info,
            request_deserializer=request_pb2.ServerInfoRequest.FromString,
            response_serializer=lambda m: m.SerializeToString(),
        ),
    }


def _grpc_handlers(svc: CerbosService):
    return grpc.method_handlers_generic_handler("cerbos.svc.v1.CerbosService", _grpc_rpcs(svc))


# -- grpc.health.v1 ---------------------------------------------------------
#
# The standard gRPC health protocol, hand-encoded: the container does not
# ship grpcio-health-checking, and the two messages involved are trivial.
# HealthCheckRequest{string service = 1} is ignored (one readiness domain
# covers the whole PDP); HealthCheckResponse{ServingStatus status = 1} is a
# single varint field: SERVING=1, NOT_SERVING=2.

_HEALTH_SERVING = b"\x08\x01"
_HEALTH_NOT_SERVING = b"\x08\x02"


def _health_rpcs() -> dict:
    def check(req: bytes, ctx) -> bytes:
        return _HEALTH_SERVING if readiness_state().serving() else _HEALTH_NOT_SERVING

    return {
        "Check": grpc.unary_unary_rpc_method_handler(
            check,
            request_deserializer=lambda b: b,
            response_serializer=lambda b: b,
        ),
    }


def _health_handler():
    return grpc.method_handlers_generic_handler("grpc.health.v1.Health", _health_rpcs())


def _json_inputs(body: dict, aux: Optional[T.AuxData]) -> list[T.CheckInput]:
    """JSON's maker of a request's inputs, for ``checkcall.front``."""
    return convert.json_to_check_inputs(body, aux)[0]


def _plan_from_json(
    svc: CerbosService, body: dict, aux: Optional[T.AuxData], access: Optional[tuple[str, str]] = None
) -> tuple[dict, str]:
    from ..plan.types import PlanInput

    pj = body.get("principal") or {}
    rj = body.get("resource") or {}
    # the deprecated singular `action` wins over `actions` and flips the
    # response to the singular field shape (cerbos_svc.go PlanResources)
    one_action = body.get("action") or ""
    actions = [one_action] if one_action else list(body.get("actions") or [])
    plan_input = PlanInput(
        request_id=body.get("requestId", ""),
        actions=actions,
        principal=T.Principal(
            id=pj.get("id", ""),
            roles=list(pj.get("roles", [])),
            attr=pj.get("attr", {}) or {},
            policy_version=pj.get("policyVersion", ""),
            scope=pj.get("scope", ""),
        ),
        resource_kind=rj.get("kind", ""),
        resource_attr=rj.get("attr", {}) or {},
        resource_policy_version=rj.get("policyVersion", ""),
        resource_scope=rj.get("scope", ""),
        aux_data=aux,
        include_meta=bool(body.get("includeMeta", False)),
    )
    output, call_id = svc.plan_resources(plan_input, access=access)
    j = output.to_json(call_id)
    if one_action:
        j.pop("actions", None)
        j["action"] = one_action
        meta = j.get("meta")
        if meta is not None:
            scopes = meta.pop("matchedScopes", {}) or {}
            if scopes.get(one_action):
                meta["matchedScope"] = scopes[one_action]
    return j, call_id


def _plan_json_to_proto(j: dict, response_pb2):
    from google.protobuf import json_format

    return json_format.ParseDict(j, response_pb2.PlanResourcesResponse(), ignore_unknown_fields=True)


class Server:
    """Serves the Cerbos API over gRPC and HTTP concurrently."""

    def __init__(
        self,
        service: CerbosService,
        config: Optional[ServerConfig] = None,
        admin_service: Any = None,
        extra_services: Optional[list[Any]] = None,
    ):
        self.svc = service
        self.config = config or ServerConfig()
        self.admin_service = admin_service
        self.extra_services = extra_services or []
        self._grpc_server: Optional[grpc.Server] = None
        self._grpc_aio_server = None
        self._http_runner: Optional[web.AppRunner] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread: Optional[threading.Thread] = None
        self.http_port: int = 0
        self.grpc_port: int = 0
        self._cert_watcher: Optional[_CertWatcher] = None

    # -- gRPC --------------------------------------------------------------

    def _grpc_options(self):
        return [("grpc.so_reuseport", 1 if self.config.reuse_port else 0)]

    def _start_grpc(self) -> None:
        """Threaded sync gRPC server (grpc_async=False fallback)."""
        server = grpc.server(
            _StampingPool(self.config.max_workers),
            options=self._grpc_options(),
        )
        server.add_generic_rpc_handlers((_grpc_handlers(self.svc), _health_handler()))
        if self.admin_service is not None:
            handler = self.admin_service.grpc_handler()
            if handler is not None:
                server.add_generic_rpc_handlers((handler,))
        addr = self.config.grpc_listen_addr  # "host:port" or "unix:/path"
        if self._cert_watcher is not None:
            port = server.add_secure_port(addr, self._cert_watcher.grpc_credentials())
        else:
            port = server.add_insecure_port(addr)
        self.grpc_port = port
        server.start()
        self._grpc_server = server

    async def _start_grpc_aio(self):
        """grpc.aio server sharing the HTTP event loop: handlers run inline
        (they are short and synchronous), so a call costs no thread hop —
        the sync server's dominant per-call overhead on small hosts."""
        server = grpc.aio.server(options=self._grpc_options())
        # its handlers are synchronous: they cannot await the evaluator
        way = checkcall.way_in(self.svc.engine, self.config.direct_dispatch, can_await=False)
        inline = way is checkcall.INLINE
        handlers = [
            aio_generic_handler("cerbos.svc.v1.CerbosService", _grpc_rpcs(self.svc), inline),
            # health checks are tiny and non-blocking: always inline
            aio_generic_handler("grpc.health.v1.Health", _health_rpcs(), inline=True),
        ]
        if self.admin_service is not None:
            handlers.append(
                aio_generic_handler(
                    "cerbos.svc.v1.CerbosAdminService", self.admin_service.grpc_rpcs(), inline
                )
            )
        server.add_generic_rpc_handlers(tuple(handlers))
        addr = self.config.grpc_listen_addr
        if self._cert_watcher is not None:
            port = server.add_secure_port(addr, self._cert_watcher.grpc_credentials())
        else:
            port = server.add_insecure_port(addr)
        self.grpc_port = port
        await server.start()
        self._grpc_aio_server = server

    # -- HTTP --------------------------------------------------------------

    @web.middleware
    async def _cors_middleware(self, request: web.Request, handler):
        """Ref: middleware.go:150-186 (rs/cors defaults + user-agent header)."""
        conf = self.config
        origin = request.headers.get("Origin", "")
        allowed = "*"
        if conf.cors_allowed_origins and "*" not in conf.cors_allowed_origins:
            allowed = origin if origin in conf.cors_allowed_origins else ""
        headers = conf.cors_allowed_headers or ("accept", "content-type", "user-agent", "x-requested-with")
        if request.method == "OPTIONS" and "Access-Control-Request-Method" in request.headers:
            resp = web.Response(status=204)
            resp.headers["Vary"] = "Origin"
            if allowed:
                resp.headers["Access-Control-Allow-Origin"] = allowed
                resp.headers["Access-Control-Allow-Methods"] = "HEAD, GET, POST, PUT, PATCH, DELETE"
                resp.headers["Access-Control-Allow-Headers"] = ", ".join(headers)
                if conf.cors_max_age_s:
                    resp.headers["Access-Control-Max-Age"] = str(conf.cors_max_age_s)
            return resp
        resp = await handler(request)
        if allowed and origin:
            resp.headers["Access-Control-Allow-Origin"] = allowed
            resp.headers["Vary"] = "Origin"
        return resp

    def _http_app(self) -> web.Application:
        middlewares = [] if self.config.cors_disabled else [self._cors_middleware]
        app = web.Application(client_max_size=16 * 1024 * 1024, middlewares=middlewares)
        app.router.add_post("/api/check/resources", self._h_check_resources)
        app.router.add_post("/api/plan/resources", self._h_plan_resources)
        # deprecated APIs kept for older SDKs (ref: cerbos_svc.go:123-252)
        app.router.add_post("/api/check", self._h_check_resource_set)
        app.router.add_post("/api/check_resource_batch", self._h_check_resource_batch)
        # legacy alias kept for clients that used the pre-parity route
        app.router.add_post("/api/x/check_resource_batch", self._h_check_resource_batch)
        app.router.add_get("/_cerbos/health", self._h_health)
        app.router.add_get("/_cerbos/ready", self._h_ready)
        app.router.add_get("/_cerbos/metrics", self._h_metrics)
        app.router.add_get("/_cerbos/debug/flight", self._h_flight)
        app.router.add_get("/_cerbos/debug/slow", self._h_slow)
        app.router.add_get("/_cerbos/debug/pressure", self._h_pressure)
        app.router.add_get("/_cerbos/debug/transport", self._h_transport)
        app.router.add_get("/_cerbos/debug/overload", self._h_overload)
        app.router.add_get("/_cerbos/debug/analysis", self._h_analysis)
        app.router.add_get("/_cerbos/debug/hotrules", self._h_hotrules)
        app.router.add_post("/_cerbos/debug/explain", self._h_explain)
        app.router.add_get("/_cerbos/debug/rollout", self._h_rollout)
        app.router.add_get("/_cerbos/debug/profile", self._h_profile)
        app.router.add_get("/api/server_info", self._h_server_info)
        # OpenAPI document + self-contained API explorer (ref: server.go:441-447)
        app.router.add_get("/schema/swagger.json", self._h_swagger)
        app.router.add_get("/", self._h_explorer)
        if self.admin_service is not None:
            self.admin_service.add_http_routes(app)
        for svc in self.extra_services:
            svc.add_http_routes(app)
        return app

    async def _h_health(self, request: web.Request) -> web.Response:
        return web.json_response({"status": "SERVING"})

    async def _h_ready(self, request: web.Request) -> web.Response:
        """Readiness, split from liveness: 503 while the warmup driver is
        still pre-compiling device layouts, 200 once warm — including
        ``degraded`` (breaker open, oracle serving), which is live."""
        snap = readiness_state().snapshot()
        return web.json_response(snap, status=200 if snap["status"] != "warming" else 503)

    async def _h_flight(self, request: web.Request) -> web.Response:
        """Flight-recorder dump: the last N device batches (trace ids, stage
        timings, occupancy, outcome) plus breaker/bisect/quarantine events.
        The persistent-XLA-cache status rides a response header so one curl
        answers both "what just happened" and "is the compile cache live".

        Front-end mode: the flight recorder (and breaker state) live in the
        shared batcher process — fetch its dump over the ticket queue so the
        debug surface keeps pointing at where device batches actually run.
        A dead batcher falls back to the (empty) local ring with a note.

        ``?shard=N`` narrows the dump to one lane of the sharded pool
        (batch records via their ``shard`` field — ``FlightRecorder.lane``
        semantics, with single-batcher records counting as shard 0 — and
        events carrying a matching ``shard``; shard-less events such as
        config notes stay, they are global)."""
        shard_q = request.query.get("shard")
        shard_filter: Optional[int] = None
        if shard_q is not None:
            try:
                shard_filter = int(shard_q)
            except ValueError:
                return web.json_response(
                    {"error": f"invalid shard {shard_q!r} (want an integer)"}, status=400
                )

        def narrowed(body: dict) -> dict:
            if shard_filter is None:
                return body
            norm = lambda v: 0 if v is None else v  # noqa: E731
            body = dict(body)
            body["batches"] = [
                r for r in body.get("batches") or [] if norm(r.get("shard")) == shard_filter
            ]
            body["events"] = [
                e
                for e in body.get("events") or []
                if "shard" not in e or norm(e.get("shard")) == shard_filter
            ]
            body["shard_filter"] = shard_filter
            return body

        ev = getattr(self.svc.engine, "tpu_evaluator", None)
        if ev is not None and hasattr(ev, "fetch_flight"):
            try:
                remote = await asyncio.get_running_loop().run_in_executor(None, ev.fetch_flight)
                body = narrowed(dict(remote.get("flight") or {}))
                body["source"] = "batcher"
                body["batcher_pid"] = remote.get("pid")
                resp = web.json_response(body, dumps=lambda o: json.dumps(o, default=str))
                if remote.get("jitcache") is not None:
                    resp.headers["X-Cerbos-Jitcache"] = json.dumps(
                        remote["jitcache"], default=str
                    )
                return resp
            except Exception as e:  # noqa: BLE001
                body = narrowed(dict(flight_recorder().dump()))
                body["source"] = "frontend"
                body["batcher_error"] = f"{type(e).__name__}: {e}"
                return web.json_response(body, dumps=lambda o: json.dumps(o, default=str))
        resp = web.json_response(
            narrowed(flight_recorder().dump()), dumps=lambda o: json.dumps(o, default=str)
        )
        try:
            from ..tpu import jitcache

            resp.headers["X-Cerbos-Jitcache"] = json.dumps(jitcache.status(), default=str)
        except Exception:  # pragma: no cover - status must never break the dump
            pass
        return resp

    async def _h_slow(self, request: web.Request) -> web.Response:
        """Slow-request ring: the top-K waterfalls (trace id, per-stage ms,
        outcome) of requests slower than ``latencyBudget.slowThresholdMs``.
        ``?shard=N`` narrows to one lane; ``?top=K`` caps the list. In the
        front-door topology the batcher process keeps its own (usually
        empty — requests finish on the front ends) ring; it is merged in so
        the surface stays one URL in every topology."""
        shard_q = request.query.get("shard")
        shard_filter: Optional[int] = None
        if shard_q is not None:
            try:
                shard_filter = int(shard_q)
            except ValueError:
                return web.json_response(
                    {"error": f"invalid shard {shard_q!r} (want an integer)"}, status=400
                )
        try:
            top = int(request.query.get("top", "0"))
        except ValueError:
            return web.json_response({"error": "top must be an integer"}, status=400)
        body = budget_tracker().slow_dump(shard=shard_filter, top=top)
        ev = getattr(self.svc.engine, "tpu_evaluator", None)
        if ev is not None and hasattr(ev, "fetch_slow"):
            try:
                remote = await asyncio.get_running_loop().run_in_executor(
                    None, lambda: ev.fetch_slow(shard=shard_filter)
                )
                extra = remote.get("requests") or []
                if extra:
                    merged = body["requests"] + list(extra)
                    merged.sort(key=lambda e: e.get("total_ms", 0.0), reverse=True)
                    body["requests"] = merged[:top] if top > 0 else merged
                body["batcher_pid"] = remote.get("pid")
            except Exception:  # noqa: BLE001  (batcher down: local ring only)
                pass
        return web.json_response(body, dumps=lambda o: json.dumps(o, default=str))

    async def _h_pressure(self, request: web.Request) -> web.Response:
        """Aggregate saturation pressure: a fresh sample of every bound
        signal, the 0..1 components, and the headline score — the input
        surface admission control (ROADMAP item 5) will consume. In the
        front-door topology the batcher's snapshot (queue, inflight,
        breaker — the signals that live with the device) is attached and
        the headline is the max of both processes."""
        loop = asyncio.get_running_loop()
        body = await loop.run_in_executor(None, pressure_monitor().sample)
        ev = getattr(self.svc.engine, "tpu_evaluator", None)
        if ev is not None and hasattr(ev, "fetch_pressure"):
            try:
                remote = await loop.run_in_executor(None, ev.fetch_pressure)
                body["batcher"] = remote
                body["score"] = max(
                    float(body.get("score", 0.0)), float(remote.get("score", 0.0))
                )
            except Exception:  # noqa: BLE001
                pass
        return web.json_response(body, dumps=lambda o: json.dumps(o, default=str))

    async def _h_overload(self, request: web.Request) -> web.Response:
        """Overload-control state for THIS process: the compiled admission
        classes with live token/inflight state, and the brownout ladder with
        per-stage thresholds and engagement. The operator's first stop when
        429s appear — it answers 'which class, which stage, and why'."""
        body = {
            "admission": admission_controller().snapshot(),
            "brownout": brownout_ctl.controller().snapshot(),
        }
        ev = getattr(self.svc.engine, "tpu_evaluator", None)
        lane_depths = getattr(ev, "lane_depths", None)
        if callable(lane_depths):
            with contextlib.suppress(Exception):
                body["lanes"] = lane_depths()
        return web.json_response(body, dumps=lambda o: json.dumps(o, default=str))

    async def _h_analysis(self, request: web.Request) -> web.Response:
        """Static policy-analysis report for the table currently serving:
        per-rule device-eligibility classes (device / tagged-fallback /
        oracle-only with stable reason codes), divergence-risk lints, and
        policy-graph findings. Recomputed by the bootstrap swap hook, so
        this is always the verdict on the live bundle. ``?summary=1``
        returns just the rollup."""
        from ..tpu import analyze as analyze_mod

        report = analyze_mod.latest()
        if report is None:
            return web.json_response(
                {"error": "no analysis published (core not bootstrapped)"}, status=404
            )
        if request.query.get("summary"):
            return web.json_response(report.summary())
        loop = asyncio.get_running_loop()
        body = await loop.run_in_executor(None, report.to_dict)
        return web.json_response(body, dumps=lambda o: json.dumps(o, default=str))

    async def _h_hotrules(self, request: web.Request) -> web.Response:
        """Hot-rule heatmap: top-K rule-table rows by live decision hits,
        with analyzer class, traffic share, and the device/oracle source
        split — the ranking input for oracle-extinction work. ``?k=N`` caps
        the list (default 20). In the front-door topology the counters live
        in the shared batcher process and are fetched over the ticket queue;
        a dead batcher falls back to this process's (front-end-local)
        recorder with a note."""
        try:
            k = int(request.query.get("k", "20"))
        except ValueError:
            return web.json_response({"error": "k must be an integer"}, status=400)
        from ..engine import hotrules

        loop = asyncio.get_running_loop()

        def local_snapshot() -> dict:
            return hotrules.recorder().snapshot(k=k, rule_table=self.svc.engine.rule_table)

        ev = getattr(self.svc.engine, "tpu_evaluator", None)
        if ev is not None and hasattr(ev, "fetch_hotrules"):
            try:
                body = await loop.run_in_executor(None, lambda: ev.fetch_hotrules(k=k))
                body["source"] = "batcher"
                return web.json_response(body, dumps=lambda o: json.dumps(o, default=str))
            except Exception as e:  # noqa: BLE001
                body = await loop.run_in_executor(None, local_snapshot)
                body["source"] = "frontend"
                body["batcher_error"] = f"{type(e).__name__}: {e}"
                return web.json_response(body, dumps=lambda o: json.dumps(o, default=str))
        body = await loop.run_in_executor(None, local_snapshot)
        body["source"] = "local"
        return web.json_response(body, dumps=lambda o: json.dumps(o, default=str))

    async def _h_explain(self, request: web.Request) -> web.Response:
        """Sampled explain mode: POST a CheckResources-shaped body and get,
        per (resource, action), the device decision with its winning rule
        next to a CPU-oracle traced replay — the trace's ACTIVATED rule is
        the ground truth the device attribution must match. Intended for
        replaying captured requests (divergence corpus records, audit
        samples), NOT for per-request serving: the oracle leg walks the rule
        table on CPU."""
        try:
            body = fastjson.loads(await request.read())
        except json.JSONDecodeError:
            return web.json_response({"code": 3, "message": "invalid JSON payload"}, status=400)
        if not isinstance(body, dict):
            return web.json_response({"code": 3, "message": "invalid JSON payload"}, status=400)
        verr = wire_validate.check_resources_body(body)
        if verr:
            return web.json_response({"code": 3, "message": verr}, status=400)
        try:
            aux = None
            aux_j = (body.get("auxData") or {}).get("jwt") or {}
            if aux_j.get("token"):
                aux = self.svc._extract_aux_data(aux_j["token"], aux_j.get("keySetId", ""))
            inputs, request_id, _ = convert.json_to_check_inputs(body, aux)
        except RequestLimitExceeded as e:
            return web.json_response({"code": 3, "message": str(e)}, status=400)

        engine = self.svc.engine
        rt = engine.rule_table
        ev = getattr(engine, "tpu_evaluator", None)
        loop = asyncio.get_running_loop()

        def device_leg() -> tuple[list, str]:
            # bypass the small-batch threshold: explain exists to audit the
            # DEVICE attribution, so dispatch straight at the evaluator
            if ev is not None:
                try:
                    return ev.check(list(inputs), engine.eval_params), "device"
                except Exception as e:  # noqa: BLE001
                    note = f"oracle (device leg failed: {type(e).__name__}: {e})"
            else:
                note = "oracle (no device evaluator)"
            from ..ruletable import check_input

            return [check_input(rt, i, engine.eval_params, engine.schema_mgr) for i in inputs], note

        def oracle_leg() -> list:
            from ..tracer import traced_check

            return [traced_check(rt, i, engine.eval_params, engine.schema_mgr) for i in inputs]

        (dev_outputs, dev_path), traced = await asyncio.gather(
            loop.run_in_executor(None, device_leg),
            loop.run_in_executor(None, oracle_leg),
        )

        def rule_of(comps: list) -> str:
            policy = next((c["id"] for c in comps if c.get("kind") == "policy"), "")
            rule = next((c["id"] for c in comps if c.get("kind") == "rule"), "")
            return f"{policy}#{rule}"

        results = []
        agreements = disagreements = 0
        for idx, inp in enumerate(inputs):
            d_out = dev_outputs[idx]
            o_out, rec = traced[idx]
            actions: dict[str, Any] = {}
            for action in inp.actions:
                dae = d_out.actions.get(action)
                oae = o_out.actions.get(action)
                activated = [
                    rule_of(e.components)
                    for e in rec.events
                    if e.activated
                    and any(c.get("kind") == "action" and c.get("id") == action for c in e.components)
                ]
                agree = (
                    dae is not None
                    and oae is not None
                    and dae.effect == oae.effect
                    and dae.matched_rule == oae.matched_rule
                )
                agreements += 1 if agree else 0
                disagreements += 0 if agree else 1
                actions[action] = {
                    "device": None
                    if dae is None
                    else {
                        "effect": dae.effect,
                        "policy": dae.policy,
                        "matched_rule": dae.matched_rule,
                        "rule_row_id": dae.rule_row_id,
                        "source": dae.source,
                    },
                    "oracle": None
                    if oae is None
                    else {
                        "effect": oae.effect,
                        "policy": oae.policy,
                        "matched_rule": oae.matched_rule,
                        "rule_row_id": oae.rule_row_id,
                    },
                    "trace_activated": activated,
                    "agree": agree,
                }
            results.append(
                {
                    "resource": {"kind": inp.resource.kind, "id": inp.resource.id},
                    "actions": actions,
                    "trace": rec.to_json(),
                }
            )
        payload = {
            "requestId": request_id,
            "device_path": dev_path,
            "results": results,
            "summary": {
                "actions": agreements + disagreements,
                "agreements": agreements,
                "disagreements": disagreements,
            },
        }
        return web.json_response(payload, dumps=lambda o: json.dumps(o, default=str))

    async def _h_rollout(self, request: web.Request) -> web.Response:
        """Policy-rollout state for THIS process: the serving epoch, the
        still-resident rollback history, lane epoch stamps, and the recent
        run reports (stage ladder, gate verdict with analyzer findings and
        replay diffs, canary outcome). A front end has no epoch authority —
        it reports what the batcher's STATUS frames last carried, which is
        exactly the bounded-skew view its decisions are stamped with."""
        from ..engine import rollout as rollout_mod

        ctl = rollout_mod.active()
        if ctl is None:
            return web.json_response(
                {"error": "no rollout controller (core not bootstrapped)"}, status=404
            )
        body = ctl.snapshot()
        ev = getattr(self.svc.engine, "tpu_evaluator", None)
        if body.get("mode") == "passive" and ev is not None and hasattr(ev, "remote_status"):
            with contextlib.suppress(Exception):
                last = ev.remote_status() or {}
                body["batcher"] = {
                    k: last.get(k)
                    for k in ("policy_epoch", "policy_epoch_committed_at", "rollout_stage")
                    if k in last
                }
        return web.json_response(body, dumps=lambda o: json.dumps(o, default=str))

    async def _h_transport(self, request: web.Request) -> web.Response:
        """Ticket-queue data-plane stats for THIS front end: the active
        plane (shm ring / uds socket), requested vs granted transport, frame
        counts, native codec cost per frame, and ring-full shed events. The
        single-process topology (no ticket queue) reports transport=local."""
        ev = getattr(self.svc.engine, "tpu_evaluator", None)
        if ev is not None and hasattr(ev, "transport_stats"):
            return web.json_response(ev.transport_stats())
        return web.json_response({"transport": "local"})

    async def _h_profile(self, request: web.Request) -> web.Response:
        """Operator-gated jax.profiler capture; see tpu/profiler.py. A front
        end holds no device: it forwards the capture to the device owner over
        the control connection and answers with the owner's reply (its pid,
        the artifact's path on this machine, the trace's clocks)."""
        from ..tpu import profiler

        if not profiler.enabled():
            return web.json_response(
                {"error": "profiling disabled (set engine.tpu.profiler.enabled)"}, status=403
            )
        try:
            seconds = float(request.query.get("seconds", "2"))
        except ValueError:
            return web.json_response({"error": "seconds must be a number"}, status=400)
        loop = asyncio.get_running_loop()
        ev = getattr(self.svc.engine, "tpu_evaluator", None)
        if ev is not None and hasattr(ev, "fetch_profile"):
            try:
                remote = await loop.run_in_executor(None, ev.fetch_profile, seconds)
            except Exception as e:  # noqa: BLE001  (owner down or gone mid-capture)
                return web.json_response(
                    {"error": f"device owner unreachable: {type(e).__name__}: {e}"}, status=503
                )
            if "artifact" in remote:
                return web.json_response(remote["artifact"])
            status = {"busy": 409, "disabled": 403, "invalid": 400}.get(remote.get("kind"), 500)
            return web.json_response({"error": remote.get("error", "")}, status=status)
        try:
            artifact = await loop.run_in_executor(None, profiler.capture, seconds)
        except ValueError as e:
            return web.json_response({"error": str(e)}, status=400)
        except profiler.ProfilerBusy as e:
            return web.json_response({"error": str(e)}, status=409)
        except profiler.ProfilerDisabled as e:
            return web.json_response({"error": str(e)}, status=403)
        return web.json_response(artifact)

    async def _h_swagger(self, request: web.Request) -> web.Response:
        from .openapi import build_swagger

        return web.json_response(build_swagger())

    async def _h_explorer(self, request: web.Request) -> web.Response:
        from .openapi import EXPLORER_HTML

        return web.Response(text=EXPLORER_HTML, content_type="text/html")

    async def _h_server_info(self, request: web.Request) -> web.Response:
        return web.json_response(self.svc.server_info())

    def _local_metrics_text(self) -> str:
        """This process's scrape body: the service's counters and the
        registry, under ``worker="<label>"`` in a pool."""
        m = self.svc.metrics
        lat = sorted(m.check_latency_ms)

        def pct(p: float) -> float:
            if not lat:
                return 0.0
            return lat[min(len(lat) - 1, int(p * len(lat)))]

        lines = [
            "# TYPE cerbos_dev_engine_check_count counter",
            f"cerbos_dev_engine_check_count {m.check_count}",
            "# TYPE cerbos_dev_engine_plan_count counter",
            f"cerbos_dev_engine_plan_count {m.plan_count}",
            "# TYPE cerbos_dev_engine_check_latency_ms summary",
            f'cerbos_dev_engine_check_latency_ms{{quantile="0.5"}} {pct(0.5):.3f}',
            f'cerbos_dev_engine_check_latency_ms{{quantile="0.95"}} {pct(0.95):.3f}',
            f'cerbos_dev_engine_check_latency_ms{{quantile="0.99"}} {pct(0.99):.3f}',
            "# TYPE cerbos_dev_engine_check_batch_size_total counter",
            f"cerbos_dev_engine_check_batch_size_total {sum(m.batch_sizes)}",
        ]
        from ..observability import metrics as _obs_metrics
        from ..observability import relabel_metrics_text

        # refresh the pressure gauges so every scrape sees current saturation,
        # not the last background tick
        mon = pressure_monitor()
        if mon.enabled:
            try:
                mon.sample()
            except Exception:  # noqa: BLE001  (a dead signal source must not break scrapes)
                pass
        body = "\n".join(lines) + "\n" + _obs_metrics().render()
        label = self.config.worker_label
        # pool mode: a scrape lands on whichever sibling the kernel picked;
        # the worker label keeps per-process series distinguishable
        return relabel_metrics_text(body, "worker", label) if label else body

    async def _h_metrics(self, request: web.Request) -> web.Response:
        loop = asyncio.get_running_loop()
        body = await loop.run_in_executor(None, self._local_metrics_text)
        ev = getattr(self.svc.engine, "tpu_evaluator", None)
        if self.config.worker_label and ev is not None and hasattr(ev, "fetch_metrics_text"):
            # front-end mode: one pool is one server to a scrape. The device
            # owner answers with its own registry (worker="batcher": batch
            # sizes, occupancy, ipc queue depth) and with what every OTHER
            # attached front end renders for this very request, so two
            # scrapes that land on different siblings still subtract
            from ..observability import merge_metrics_texts

            try:
                rest = await loop.run_in_executor(None, ev.fetch_metrics_text)
                body = merge_metrics_texts(body, rest)
            except Exception:  # noqa: BLE001  (batcher down: local series only)
                pass
        return web.Response(text=body, content_type="text/plain")

    async def _h_check_resources(self, request: web.Request) -> web.Response:
        """HTTP's adapter over ``checkcall``."""
        # ingress stamp BEFORE the body is read: the waterfall starts at the
        # raw-bytes boundary, so the JSON decode's cost is stage one
        call = checkcall.CheckCall(time.monotonic())
        try:
            # parse from raw bytes via the native JSON kernel when built
            # (fastjson falls back to stdlib): skips aiohttp's str decode
            body = fastjson.loads(await request.read())
        except json.JSONDecodeError:
            return web.json_response({"code": 3, "message": "invalid JSON payload"}, status=400)
        if not isinstance(body, dict):
            return web.json_response({"code": 3, "message": "invalid JSON payload"}, status=400)
        # the parse stage ends where the body is decoded, as for gRPC: wire
        # validation is the first part of admission
        call.t_parsed = time.monotonic()
        violation = wire_validate.check_resources_body(body)
        try:
            jwt = (body.get("auxData") or {}).get("jwt") or {}
            call.request_id, call.include_meta = body.get("requestId", ""), bool(body.get("includeMeta", False))
            checkcall.front(
                self.svc, call, violation, jwt.get("token"), jwt.get("keySetId", ""), None, _json_inputs, body
            )
            call.trace_ctx = parse_traceparent(request.headers.get("traceparent"))
            call.access = self.svc.access_of(_SVC + "CheckResources", lambda: request.remote)
            way = checkcall.way_in(self.svc.engine, self.config.direct_dispatch, can_await=True)
            outputs, call_id = await checkcall.enter_from_loop(self.svc, call, way)
            payload = convert.outputs_to_json(
                body,
                outputs,
                call.request_id,
                call.include_meta,
                call_id,
                provenance="X-Cerbos-TPU-Provenance" in request.headers,
            )
            if call.wf is not None:
                call.wf.part(BACK_ENCODE)
            resp = web.Response(body=fastjson.dumps(payload), content_type="application/json")
            if call.trace_ctx is not None:
                # echo the trace the work joined so callers can correlate
                resp.headers["traceparent"] = call.trace_ctx.to_traceparent()
            # the JSON dump is inside reply_encode here: the handler's extent
            # ends at the same instant
            t_done = checkcall.back(call, BACK_SERIALIZE)
            if t_done is not None:
                budget_tracker().m_handler.observe(t_done - call.t_raw)
            return resp
        except Exception as e:  # noqa: BLE001  (every row of checkcall.REFUSALS, the last one anything else)
            row, message, headers = checkcall.refuse(call, e)
        finally:
            call.release()
        return web.json_response({"code": row.code, "message": message}, status=row.http, headers=headers)

    async def _h_check_resource_set(self, request: web.Request) -> web.Response:
        """Deprecated CheckResourceSet: one resource kind, instance map."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"code": 3, "message": "invalid JSON payload"}, status=400)
        verr = wire_validate.check_resource_set_body(body)
        if verr:
            return web.json_response({"code": 3, "message": verr}, status=400)
        try:
            rs = body.get("resource") or {}
            instances = rs.get("instances") or {}
            actions = list(body.get("actions", []))
            inner = {
                "requestId": body.get("requestId", ""),
                "includeMeta": bool(body.get("includeMeta", False)),
                "principal": body.get("principal") or {},
                "resources": [
                    {
                        "actions": actions,
                        "resource": {
                            "kind": rs.get("kind", ""),
                            "policyVersion": rs.get("policyVersion", ""),
                            "scope": rs.get("scope", ""),
                            "id": rid,
                            "attr": (inst or {}).get("attr", {}) or {},
                        },
                    }
                    for rid, inst in instances.items()
                ],
            }
            aux = None
            aux_j = (body.get("auxData") or {}).get("jwt") or {}
            if aux_j.get("token"):
                aux = self.svc._extract_aux_data(aux_j["token"], aux_j.get("keySetId", ""))
            inputs, request_id, include_meta = convert.json_to_check_inputs(inner, aux)
            access = self.svc.access_of(_SVC + "CheckResourceSet", lambda: request.remote)
            outputs, call_id = await asyncio.get_running_loop().run_in_executor(
                None, lambda: self.svc.check_resources(inputs, access=access)
            )
            resource_instances = {}
            for entry, out in zip(inner["resources"], outputs):
                inst: dict = {"actions": {a: ae.effect for a, ae in out.actions.items()}}
                if out.validation_errors:
                    inst["validationErrors"] = [
                        {"path": v.path, "message": v.message, "source": v.source}
                        for v in out.validation_errors
                    ]
                resource_instances[entry["resource"]["id"]] = inst
            resp: dict = {"requestId": request_id, "resourceInstances": resource_instances, "cerbosCallId": call_id}
            if include_meta:
                resp["meta"] = {
                    "resourceInstances": {
                        entry["resource"]["id"]: {
                            "actions": {
                                a: {"matchedPolicy": ae.policy, "matchedScope": ae.scope}
                                for a, ae in out.actions.items()
                            },
                            "effectiveDerivedRoles": out.effective_derived_roles,
                        }
                        for entry, out in zip(inner["resources"], outputs)
                    }
                }
            return web.json_response(resp)
        except RequestLimitExceeded as e:
            return web.json_response({"code": 3, "message": str(e)}, status=400)
        except Exception as e:  # noqa: BLE001
            return web.json_response({"code": 13, "message": f"check failed: {e}"}, status=500)

    async def _h_check_resource_batch(self, request: web.Request) -> web.Response:
        """Deprecated CheckResourceBatch: per-resource action lists."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"code": 3, "message": "invalid JSON payload"}, status=400)
        verr = wire_validate.check_resource_batch_body(body)
        if verr:
            return web.json_response({"code": 3, "message": verr}, status=400)
        try:
            aux = None
            aux_j = (body.get("auxData") or {}).get("jwt") or {}
            if aux_j.get("token"):
                aux = self.svc._extract_aux_data(aux_j["token"], aux_j.get("keySetId", ""))
            inputs, request_id, _ = convert.json_to_check_inputs(body, aux)
            access = self.svc.access_of(_SVC + "CheckResourceBatch", lambda: request.remote)
            outputs, call_id = await asyncio.get_running_loop().run_in_executor(
                None, lambda: self.svc.check_resources(inputs, access=access)
            )
            return web.json_response(
                {
                    "requestId": request_id,
                    "cerbosCallId": call_id,
                    "results": [
                        {
                            "resourceId": out.resource_id,
                            "actions": {a: ae.effect for a, ae in out.actions.items()},
                            "validationErrors": [
                                {"path": v.path, "message": v.message, "source": v.source}
                                for v in out.validation_errors
                            ] or None,
                        }
                        for out in outputs
                    ],
                }
            )
        except RequestLimitExceeded as e:
            return web.json_response({"code": 3, "message": str(e)}, status=400)
        except Exception as e:  # noqa: BLE001
            return web.json_response({"code": 13, "message": f"check failed: {e}"}, status=500)

    async def _h_plan_resources(self, request: web.Request) -> web.Response:
        if brownout_ctl.controller().active("shed_plan"):
            # staged brownout: analytical plan traffic yields to interactive
            # checks while the ladder is at shed_plan or deeper
            brownout_ctl.controller().note_shed("plan")
            budget_tracker().count(OUTCOME_REFUSED, api="plan")
            return web.json_response(
                {"code": 8, "message": "overloaded: plan queries are shed (brownout)"},
                status=429,
                headers={"Retry-After": "1"},
            )
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"code": 3, "message": "invalid JSON payload"}, status=400)
        verr = wire_validate.plan_resources_body(body)
        if verr:
            return web.json_response({"code": 3, "message": verr}, status=400)
        try:
            aux = None
            aux_j = (body.get("auxData") or {}).get("jwt") or {}
            if aux_j.get("token"):
                aux = self.svc._extract_aux_data(aux_j["token"], aux_j.get("keySetId", ""))
            access = self.svc.access_of(_SVC + "PlanResources", lambda: request.remote)
            resp, _call_id = await asyncio.get_running_loop().run_in_executor(
                None, _plan_from_json, self.svc, body, aux, access
            )
            budget_tracker().count(OUTCOME_MET, api="plan")
            return web.json_response(resp)
        except OverloadRefused as e:
            budget_tracker().count(OUTCOME_REFUSED, api="plan")
            return web.json_response(
                {"code": 8, "message": str(e)},
                status=429,
                headers={"Retry-After": retry_after_header(e)},
            )
        except NotImplementedError as e:
            return web.json_response({"code": 12, "message": str(e)}, status=501)
        except RequestLimitExceeded as e:
            return web.json_response({"code": 3, "message": str(e)}, status=400)
        except Exception as e:  # noqa: BLE001
            return web.json_response({"code": 13, "message": f"plan failed: {e}"}, status=500)

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        ev = getattr(self.svc.engine, "tpu_evaluator", None)
        if hasattr(ev, "local_metrics_text"):
            # front-end mode: what this process sends when the device owner
            # gathers the pool for a scrape another front end is answering
            ev.local_metrics_text = self._local_metrics_text
        if self.config.tls_cert and self.config.tls_key:
            self._cert_watcher = _CertWatcher(
                self.config.tls_cert,
                self.config.tls_key,
                self.config.ssl_context(),
                self.config.tls_watch_interval_s,
            )
            self._cert_watcher.start()
        if not self.config.grpc_async:
            self._start_grpc()
        started = threading.Event()

        def run_http() -> None:
            loop = asyncio.new_event_loop()
            asyncio.set_event_loop(loop)
            self._loop = loop
            runner = web.AppRunner(self._http_app())
            loop.run_until_complete(runner.setup())
            addr = self.config.http_listen_addr
            # share the watcher's context so rotations apply to new handshakes
            ssl_ctx = self._cert_watcher.ssl_ctx if self._cert_watcher is not None else None
            if addr.startswith("unix:"):
                site: web.BaseSite = web.UnixSite(runner, addr[len("unix:"):], ssl_context=ssl_ctx)
            else:
                host, _, port = addr.rpartition(":")
                if host.startswith("[") and host.endswith("]"):
                    host = host[1:-1]  # bracketed IPv6 → bare for getaddrinfo
                site = web.TCPSite(
                    runner,
                    host or "0.0.0.0",
                    int(port),
                    ssl_context=ssl_ctx,
                    reuse_port=self.config.reuse_port or None,
                )
            loop.run_until_complete(site.start())
            if not addr.startswith("unix:"):
                for s in runner.sites:
                    self.http_port = s._server.sockets[0].getsockname()[1]  # type: ignore[union-attr]
            self._http_runner = runner
            if self.config.grpc_async:
                loop.run_until_complete(self._start_grpc_aio())
            started.set()
            loop.run_forever()

        self._start_error: Optional[BaseException] = None

        def run_guarded() -> None:
            try:
                run_http()
            except BaseException as e:  # noqa: BLE001 — surfaced to start()'s caller
                self._start_error = e
                started.set()

        self._thread = threading.Thread(target=run_guarded, daemon=True, name="http-server")
        self._thread.start()
        started.wait(timeout=10)
        if self._start_error is not None:
            # a listener that bound but whose loop died must not look alive
            raise RuntimeError(f"server startup failed: {self._start_error}") from self._start_error

    def stop(self) -> None:
        if self._cert_watcher is not None:
            self._cert_watcher.stop()
        if self._grpc_server is not None:
            self._grpc_server.stop(grace=1).wait()
        if self._loop is not None:
            loop = self._loop

            async def shutdown() -> None:
                if self._grpc_aio_server is not None:
                    await self._grpc_aio_server.stop(grace=1)
                if self._http_runner is not None:
                    await self._http_runner.cleanup()
                loop.stop()

            asyncio.run_coroutine_threadsafe(shutdown(), loop)
            if self._thread is not None:
                self._thread.join(timeout=5)
