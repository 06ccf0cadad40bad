"""Replay of the reference's server wire corpus.

`tests/golden/server/**` is `/root/reference/internal/test/testdata/server/*`
ported verbatim (request/response pairs the reference replays over real gRPC
and HTTP+JSON — internal/server/server_test.go + tests.go). This suite boots
the repo's REAL server (HTTP + gRPC listeners) against the ported golden
store fixture and replays every case, comparing responses proto-semantically
with the reference's own normalization rules (tests.go compareProto):
sorted effectiveDerivedRoles / outputs / validationErrors, cerbos_call_id
ignored-but-required, playground error-details context ignored.

Template constructs in the corpus ({{ fileString `..` | b64enc }} and
{{- readPolicy ".." | toPolicyJSON }}) mirror internal/test/template.go.

Known divergences are listed in tests/golden/UNSUPPORTED.md.
"""

import base64
import json
import pathlib
import re
import urllib.error
import urllib.request

import grpc
import pytest
import yaml
from google.protobuf import json_format

from cerbos_tpu.api.cerbos.request.v1 import request_pb2
from cerbos_tpu.api.cerbos.response.v1 import response_pb2
from cerbos_tpu.api.cerbos.policy.v1 import policy_pb2
from cerbos_tpu.bootstrap import initialize
from cerbos_tpu.config import Config
from cerbos_tpu.server.admin import AdminService
from cerbos_tpu.server.authzen import AuthZenService
from cerbos_tpu.server.playground import PlaygroundService
from cerbos_tpu.server.server import Server, ServerConfig

GOLDEN = pathlib.Path(__file__).parent / "golden"
SERVER_DIR = GOLDEN / "server"

_FILESTRING_RE = re.compile(r"{{\s*fileString\s+`([^`]+)`\s*\|\s*b64enc\s*}}")
_READPOLICY_RE = re.compile(r'{{-?\s*readPolicy\s+"([^"]+)"\s*\|\s*toPolicyJSON\s*-?}}')


def _render_template(text: str) -> str:
    """The two template constructs the corpus uses (internal/test/template.go:
    sprig b64enc over fileString, and readPolicy|toPolicyJSON)."""

    def file_b64(m: re.Match) -> str:
        data = (GOLDEN / m.group(1)).read_bytes()
        return base64.b64encode(data).decode()

    def policy_json(m: re.Match) -> str:
        raw = yaml.safe_load((GOLDEN / m.group(1)).read_text())
        pol = json_format.ParseDict(raw, policy_pb2.Policy(), ignore_unknown_fields=True)
        return json_format.MessageToJson(pol, indent=None)

    text = _FILESTRING_RE.sub(file_b64, text)
    text = _READPOLICY_RE.sub(policy_json, text)
    return text


def load_cases(*dirs: str) -> list[tuple[str, dict]]:
    cases = []
    for d in dirs:
        root = SERVER_DIR / d
        for f in sorted(root.rglob("*.yaml")):
            doc = yaml.safe_load(_render_template(f.read_text()))
            if isinstance(doc, dict):
                cases.append((str(f.relative_to(SERVER_DIR)), doc))
    return cases


# -- response normalization (tests.go compareProto) -------------------------

_SORT_LISTS = {"effectiveDerivedRoles"}


def _sort_key(v):
    return json.dumps(v, sort_keys=True)


def normalize(obj, *, drop_call_id=True):
    """Canonicalize a protojson-shaped response dict for comparison:
    - drop cerbosCallId (asserted non-empty separately)
    - sort effectiveDerivedRoles everywhere
    - sort outputs entries by (src, action)
    - sort validationErrors by content
    - sort playground failure errors by content; drop their error context
    - drop authzen response 'context'
    """
    if isinstance(obj, list):
        return [normalize(x, drop_call_id=drop_call_id) for x in obj]
    if not isinstance(obj, dict):
        return obj
    out = {}
    for k, v in obj.items():
        if drop_call_id and k == "cerbosCallId":
            continue
        if k in _SORT_LISTS and isinstance(v, list):
            out[k] = sorted(v)
            continue
        if k in ("outputs", "validationErrors", "errors") and isinstance(v, list):
            out[k] = sorted(
                (normalize(x, drop_call_id=drop_call_id) for x in v), key=_sort_key
            )
            continue
        out[k] = normalize(v, drop_call_id=drop_call_id)
    return out


def canon(resp_cls, payload: dict) -> dict:
    """protojson dict → proto → canonical dict (field presence, enum names
    and defaults normalized exactly the way protojson would emit them)."""
    msg = json_format.ParseDict(payload, resp_cls(), ignore_unknown_fields=False)
    return json_format.MessageToDict(msg)


# -- server fixtures ---------------------------------------------------------


def _mk_server(tmp_path, storage_overrides: list[str]):
    config = Config.load(
        overrides=[
            *storage_overrides,
            "server.httpListenAddr=127.0.0.1:0",
            "server.grpcListenAddr=127.0.0.1:0",
            "server.adminAPI.enabled=true",
            # the reference's wire-corpus server runs with lowered limits
            # (server_test.go:386-388) so the "too many" cases trip
            "server.requestLimits.maxActionsPerResource=5",
            "server.requestLimits.maxResourcesPerRequest=5",
            "schema.enforcement=reject",
            f"auxData.jwt.keySets=[{{\"id\": \"cerbos\", \"local\": {{\"file\": \"{GOLDEN}/auxdata/keys/verify_key.jwk\"}}}}]",
            "engine.tpu.enabled=false",
        ]
    )
    core = initialize(config, use_tpu=False)
    admin = AdminService(core, username="cerbos", password="cerbosAdmin")
    srv = Server(
        core.service,
        ServerConfig(http_listen_addr="127.0.0.1:0", grpc_listen_addr="127.0.0.1:0"),
        admin_service=admin,
        extra_services=[AuthZenService(core.service), PlaygroundService()],
    )
    srv.start()
    return core, srv


@pytest.fixture(scope="module")
def disk_server():
    core, srv = _mk_server(None, [f"storage.disk.directory={GOLDEN / 'store'}"])
    yield srv
    srv.stop()
    core.close()


@pytest.fixture(scope="module")
def sqlite_server(tmp_path_factory):
    db = tmp_path_factory.mktemp("db") / "cerbos.sqlite"
    core, srv = _mk_server(
        None,
        ["storage.driver=sqlite3", f"storage.sqlite3.dsn={db}"],
    )
    yield srv
    srv.stop()
    core.close()


# -- call-kind registry ------------------------------------------------------

# kind -> (http path, grpc method, request class, response class)
KINDS = {
    "checkResources": (
        "/api/check/resources",
        "/cerbos.svc.v1.CerbosService/CheckResources",
        request_pb2.CheckResourcesRequest,
        response_pb2.CheckResourcesResponse,
    ),
    "checkResourceSet": (
        "/api/check",
        "/cerbos.svc.v1.CerbosService/CheckResourceSet",
        request_pb2.CheckResourceSetRequest,
        response_pb2.CheckResourceSetResponse,
    ),
    "checkResourceBatch": (
        "/api/check_resource_batch",
        "/cerbos.svc.v1.CerbosService/CheckResourceBatch",
        request_pb2.CheckResourceBatchRequest,
        response_pb2.CheckResourceBatchResponse,
    ),
    "planResources": (
        "/api/plan/resources",
        "/cerbos.svc.v1.CerbosService/PlanResources",
        request_pb2.PlanResourcesRequest,
        response_pb2.PlanResourcesResponse,
    ),
}


def http_post_raw(server, path, body, auth=None):
    headers = {"Content-Type": "application/json"}
    if auth:
        tok = base64.b64encode(f"{auth[0]}:{auth[1]}".encode()).decode()
        headers["Authorization"] = f"Basic {tok}"
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.http_port}{path}",
        data=json.dumps(body).encode(),
        headers=headers,
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        try:
            return e.code, json.loads(e.read())
        except Exception:  # noqa: BLE001
            return e.code, {}


def _case_kind(doc: dict):
    for k in doc:
        if k not in ("description", "name", "wantStatus", "wantError"):
            return k
    return None


def replay_http(server, doc: dict, name: str, auth=None):
    kind = _case_kind(doc)
    call = doc[kind]
    want_status = (doc.get("wantStatus") or {}).get("httpStatusCode", 200)
    if kind in KINDS:
        path, _, _, resp_cls = KINDS[kind]
    elif kind == "accessEvaluation":
        path, resp_cls = "/access/v1/evaluation", None
    elif kind == "accessEvaluationBatch":
        path, resp_cls = "/access/v1/evaluations", None
    elif kind == "playgroundValidate":
        path, resp_cls = "/api/playground/validate", None
    elif kind == "playgroundEvaluate":
        path, resp_cls = "/api/playground/evaluate", None
    elif kind == "playgroundTest":
        path, resp_cls = "/api/playground/test", None
    elif kind == "playgroundProxy":
        path, resp_cls = "/api/playground/proxy", None
    elif kind == "adminAddOrUpdatePolicy":
        path, resp_cls = "/admin/policy", None
    elif kind == "adminAddOrUpdateSchema":
        path, resp_cls = "/admin/schema", None
    else:
        pytest.fail(f"{name}: unknown call kind {kind}")
    status, have = http_post_raw(server, path, call["input"], auth=auth)
    assert status == want_status, f"{name}: HTTP {status} != {want_status}: {have}"
    if doc.get("wantError") or want_status != 200:
        return
    want = call.get("wantResponse", {})
    if resp_cls is not None:
        want_n = normalize(canon(resp_cls, want))
        have_n = normalize(canon(resp_cls, have))
    else:
        want_n = normalize(want)
        have_n = normalize(have)
    assert have_n == want_n, (
        f"{name}: response mismatch\nwant: {json.dumps(want_n, indent=2, sort_keys=True)}\n"
        f"have: {json.dumps(have_n, indent=2, sort_keys=True)}"
    )


def replay_grpc(server, doc: dict, name: str, auth=None):
    kind = _case_kind(doc)
    if kind not in KINDS:
        pytest.skip(f"{kind} not exposed over gRPC in this build")
    call = doc[kind]
    want_code = (doc.get("wantStatus") or {}).get("grpcStatusCode", 0)
    _, method, req_cls, resp_cls = KINDS[kind]
    req = json_format.ParseDict(call["input"], req_cls(), ignore_unknown_fields=True)
    channel = grpc.insecure_channel(f"127.0.0.1:{server.grpc_port}")
    try:
        stub = channel.unary_unary(
            method,
            request_serializer=lambda m: m.SerializeToString(),
            response_deserializer=resp_cls.FromString,
        )
        metadata = []
        if auth:
            tok = base64.b64encode(f"{auth[0]}:{auth[1]}".encode()).decode()
            metadata.append(("authorization", f"Basic {tok}"))
        try:
            resp = stub(req, timeout=30, metadata=metadata or None)
            code = 0
        except grpc.RpcError as e:
            code = e.code().value[0]
            resp = None
        assert code == want_code, f"{name}: gRPC code {code} != {want_code}"
        if doc.get("wantError") or want_code != 0:
            return
        want = call.get("wantResponse", {})
        want_n = normalize(canon(resp_cls, want))
        have_n = normalize(json_format.MessageToDict(resp))
        assert have_n == want_n, (
            f"{name}: gRPC response mismatch\n"
            f"want: {json.dumps(want_n, indent=2, sort_keys=True)}\n"
            f"have: {json.dumps(have_n, indent=2, sort_keys=True)}"
        )
    finally:
        channel.close()


CHECK_CASES = load_cases("checks", "plan_resources")


@pytest.mark.parametrize("name,doc", CHECK_CASES, ids=[c[0] for c in CHECK_CASES])
def test_http_checks(disk_server, name, doc):
    replay_http(disk_server, doc, name)


@pytest.mark.parametrize("name,doc", CHECK_CASES, ids=[c[0] for c in CHECK_CASES])
def test_grpc_checks(disk_server, name, doc):
    replay_grpc(disk_server, doc, name)
