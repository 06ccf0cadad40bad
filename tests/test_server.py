"""Full-server integration tests: real gRPC + HTTP against an in-process
server with a disk store (modeled on internal/server/tests.go)."""

import json
import time
import urllib.request

import grpc
import pytest

from cerbos_tpu.bootstrap import initialize
from cerbos_tpu.config import Config
from cerbos_tpu.server.server import Server, ServerConfig
from cerbos_tpu.server.admin import AdminService

POLICY = """
apiVersion: api.cerbos.dev/v1
resourcePolicy:
  resource: album
  version: default
  rules:
    - actions: ["view"]
      effect: EFFECT_ALLOW
      roles: [user]
      condition:
        match:
          expr: request.resource.attr.owner == request.principal.id || request.resource.attr.public == true
    - actions: ["*"]
      effect: EFFECT_ALLOW
      roles: [admin]
"""


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    policy_dir = tmp_path_factory.mktemp("policies")
    (policy_dir / "album.yaml").write_text(POLICY)
    config = Config.load(
        overrides=[
            f"storage.disk.directory={policy_dir}",
            "server.httpListenAddr=127.0.0.1:0",
            "server.grpcListenAddr=127.0.0.1:0",
            "server.adminAPI.enabled=true",
            "audit.enabled=true",
            "audit.backend=local",
            # the CPU oracle path keeps server tests independent of jax
            "engine.tpu.enabled=false",
        ]
    )
    core = initialize(config, use_tpu=False)
    admin = AdminService(core, username="cerbos", password="cerbosAdmin")
    srv = Server(
        core.service,
        ServerConfig(http_listen_addr="127.0.0.1:0", grpc_listen_addr="127.0.0.1:0"),
        admin_service=admin,
    )
    srv.start()
    yield srv
    srv.stop()
    core.close()


def http_post(server, path, body, auth=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.http_port}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(auth or {})},
        method="POST",
    )
    with urllib.request.urlopen(req) as resp:
        return json.loads(resp.read())


def http_get(server, path, auth=None):
    req = urllib.request.Request(f"http://127.0.0.1:{server.http_port}{path}", headers=auth or {})
    with urllib.request.urlopen(req) as resp:
        return resp.read()


CHECK_BODY = {
    "requestId": "test-1",
    "includeMeta": True,
    "principal": {"id": "alice", "roles": ["user"], "attr": {"dept": "eng"}},
    "resources": [
        {"actions": ["view", "delete"], "resource": {"kind": "album", "id": "a1", "attr": {"owner": "alice"}}},
        {"actions": ["view"], "resource": {"kind": "album", "id": "a2", "attr": {"owner": "bob", "public": False}}},
    ],
}


class TestHTTP:
    def test_check_resources(self, server):
        resp = http_post(server, "/api/check/resources", CHECK_BODY)
        assert resp["requestId"] == "test-1"
        r1, r2 = resp["results"]
        assert r1["actions"] == {"view": "EFFECT_ALLOW", "delete": "EFFECT_DENY"}
        assert r1["meta"]["actions"]["view"]["matchedPolicy"] == "resource.album.vdefault"
        assert r2["actions"] == {"view": "EFFECT_DENY"}
        assert resp.get("cerbosCallId")

    def test_health(self, server):
        assert json.loads(http_get(server, "/_cerbos/health")) == {"status": "SERVING"}

    def test_metrics(self, server):
        text = http_get(server, "/_cerbos/metrics").decode()
        assert "cerbos_dev_engine_check_count" in text

    def test_invalid_json(self, server):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.http_port}/api/check/resources",
            data=b"{not json", headers={"Content-Type": "application/json"}, method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req)
        assert e.value.code == 400

    def test_limits(self, server):
        body = dict(CHECK_BODY)
        body["resources"] = [CHECK_BODY["resources"][0]] * 51
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.http_port}/api/check/resources",
            data=json.dumps(body).encode(), headers={"Content-Type": "application/json"}, method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req)
        assert e.value.code == 400

    def test_plan_resources(self, server):
        resp = http_post(server, "/api/plan/resources", {
            "requestId": "plan-1",
            "actions": ["view"],
            "principal": {"id": "alice", "roles": ["user"]},
            "resource": {"kind": "album"},
            "includeMeta": True,
        })
        assert resp["filter"]["kind"] == "KIND_CONDITIONAL"
        cond = resp["filter"]["condition"]["expression"]
        assert cond["operator"] == "or"
        debug = resp["meta"]["filterDebug"]
        assert "request.resource.attr.owner" in debug

    def test_plan_always_allowed(self, server):
        resp = http_post(server, "/api/plan/resources", {
            "requestId": "plan-2",
            "actions": ["delete"],
            "principal": {"id": "root", "roles": ["admin"]},
            "resource": {"kind": "album"},
        })
        assert resp["filter"]["kind"] == "KIND_ALWAYS_ALLOWED"

    def test_plan_always_denied(self, server):
        resp = http_post(server, "/api/plan/resources", {
            "requestId": "plan-3",
            "actions": ["delete"],
            "principal": {"id": "alice", "roles": ["user"]},
            "resource": {"kind": "album"},
        })
        assert resp["filter"]["kind"] == "KIND_ALWAYS_DENIED"


class TestGRPC:
    def test_check_resources_grpc(self, server):
        from cerbos_tpu.api.cerbos.request.v1 import request_pb2
        from cerbos_tpu.api.cerbos.response.v1 import response_pb2
        from cerbos_tpu.server.convert import py_to_value

        channel = grpc.insecure_channel(f"127.0.0.1:{server.grpc_port}")
        stub = channel.unary_unary(
            "/cerbos.svc.v1.CerbosService/CheckResources",
            request_serializer=lambda m: m.SerializeToString(),
            response_deserializer=response_pb2.CheckResourcesResponse.FromString,
        )
        req = request_pb2.CheckResourcesRequest(request_id="grpc-1")
        req.principal.id = "alice"
        req.principal.roles.append("user")
        entry = req.resources.add()
        entry.actions.append("view")
        entry.resource.kind = "album"
        entry.resource.id = "a1"
        entry.resource.attr["owner"].CopyFrom(py_to_value("alice"))
        resp = stub(req, timeout=10)
        assert resp.request_id == "grpc-1"
        assert resp.results[0].actions["view"] == 1  # EFFECT_ALLOW
        channel.close()

    def test_server_info_grpc(self, server):
        from cerbos_tpu.api.cerbos.request.v1 import request_pb2
        from cerbos_tpu.api.cerbos.response.v1 import response_pb2

        channel = grpc.insecure_channel(f"127.0.0.1:{server.grpc_port}")
        stub = channel.unary_unary(
            "/cerbos.svc.v1.CerbosService/ServerInfo",
            request_serializer=lambda m: m.SerializeToString(),
            response_deserializer=response_pb2.ServerInfoResponse.FromString,
        )
        resp = stub(request_pb2.ServerInfoRequest(), timeout=10)
        assert "cerbos-tpu" in resp.version
        channel.close()


class TestAdmin:
    AUTH = {"Authorization": "Basic " + __import__("base64").b64encode(b"cerbos:cerbosAdmin").decode()}

    def test_unauthenticated(self, server):
        with pytest.raises(urllib.error.HTTPError) as e:
            http_get(server, "/admin/policies")
        assert e.value.code == 401

    def test_list_policies(self, server):
        resp = json.loads(http_get(server, "/admin/policies", auth=self.AUTH))
        assert "resource.album.vdefault" in resp["policyIds"]

    def test_reload_store(self, server):
        assert json.loads(http_get(server, "/admin/store/reload", auth=self.AUTH)) == {}

    GRPC_AUTH = [("authorization", "Basic " + __import__("base64").b64encode(b"cerbos:cerbosAdmin").decode())]

    def _admin_call(self, server, method, req, resp_cls, metadata=None):
        import grpc

        with grpc.insecure_channel(f"127.0.0.1:{server.grpc_port}") as ch:
            fn = ch.unary_unary(
                f"/cerbos.svc.v1.CerbosAdminService/{method}",
                request_serializer=lambda m: m.SerializeToString(),
                response_deserializer=resp_cls.FromString,
            )
            return fn(req, metadata=metadata or self.GRPC_AUTH, timeout=10)

    def test_grpc_admin_unauthenticated(self, server):
        import grpc

        from cerbos_tpu.api.cerbos.request.v1 import request_pb2
        from cerbos_tpu.api.cerbos.response.v1 import response_pb2

        with pytest.raises(grpc.RpcError) as e:
            self._admin_call(server, "ListPolicies", request_pb2.ListPoliciesRequest(),
                             response_pb2.ListPoliciesResponse, metadata=[("authorization", "Basic bad")])
        assert e.value.code() == grpc.StatusCode.UNAUTHENTICATED

    def test_grpc_admin_list_and_get(self, server):
        from cerbos_tpu.api.cerbos.request.v1 import request_pb2
        from cerbos_tpu.api.cerbos.response.v1 import response_pb2

        resp = self._admin_call(server, "ListPolicies", request_pb2.ListPoliciesRequest(),
                                response_pb2.ListPoliciesResponse)
        assert "resource.album.vdefault" in resp.policy_ids

        # regexps match per component (name/version/scope), so anchored
        # patterns work like the reference's per-column filters
        resp = self._admin_call(
            server, "ListPolicies",
            request_pb2.ListPoliciesRequest(name_regexp="^album$", version_regexp="^default$"),
            response_pb2.ListPoliciesResponse)
        assert "resource.album.vdefault" in resp.policy_ids
        resp = self._admin_call(
            server, "ListPolicies",
            request_pb2.ListPoliciesRequest(name_regexp="^lbum$"),
            response_pb2.ListPoliciesResponse)
        assert not resp.policy_ids

        got = self._admin_call(server, "GetPolicy",
                               request_pb2.GetPolicyRequest(id=["resource.album.vdefault"]),
                               response_pb2.GetPolicyResponse)
        assert len(got.policies) == 1
        assert got.policies[0].resource_policy.resource == "album"
        assert got.policies[0].resource_policy.rules[0].actions == ["view"]

    def test_grpc_admin_inspect_and_reload(self, server):
        from cerbos_tpu.api.cerbos.request.v1 import request_pb2
        from cerbos_tpu.api.cerbos.response.v1 import response_pb2

        resp = self._admin_call(server, "InspectPolicies", request_pb2.InspectPoliciesRequest(),
                                response_pb2.InspectPoliciesResponse)
        result = resp.results["resource.album.vdefault"]
        assert "view" in result.actions
        self._admin_call(server, "ReloadStore", request_pb2.ReloadStoreRequest(),
                         response_pb2.ReloadStoreResponse)

    def test_audit_log(self, server):
        # ensure at least one decision exists, then wait for the async writer
        http_post(server, "/api/check/resources", CHECK_BODY)
        deadline = time.time() + 5
        entries = []
        while time.time() < deadline:
            resp = json.loads(http_get(server, "/admin/auditlog/list/decision_logs", auth=self.AUTH))
            entries = resp["entries"]
            if entries:
                break
            time.sleep(0.1)
        assert entries, "no decision log entries recorded"
        assert entries[0]["kind"] == "decision"


class TestDeprecatedAPIs:
    def test_check_resource_set(self, server):
        resp = http_post(server, "/api/check", {
            "requestId": "set-1",
            "actions": ["view"],
            "principal": {"id": "alice", "roles": ["user"]},
            "resource": {
                "kind": "album",
                "instances": {"a1": {"attr": {"owner": "alice"}}, "a2": {"attr": {"owner": "bob"}}},
            },
            "includeMeta": True,
        })
        insts = resp["resourceInstances"]
        assert insts["a1"]["actions"]["view"] == "EFFECT_ALLOW"
        assert insts["a2"]["actions"]["view"] == "EFFECT_DENY"
        assert resp["meta"]["resourceInstances"]["a1"]["actions"]["view"]["matchedPolicy"] == "resource.album.vdefault"

    def test_check_resource_batch(self, server):
        resp = http_post(server, "/api/x/check_resource_batch", {
            "requestId": "batch-1",
            "principal": {"id": "alice", "roles": ["user"]},
            "resources": [
                {"actions": ["view"], "resource": {"kind": "album", "id": "a1", "attr": {"owner": "alice"}}},
            ],
        })
        assert resp["results"][0]["actions"]["view"] == "EFFECT_ALLOW"


class TestInspect:
    AUTH = {"Authorization": "Basic " + __import__("base64").b64encode(b"cerbos:cerbosAdmin").decode()}

    def test_inspect_policies(self, server):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.http_port}/admin/policies/inspect",
            data=b"{}", headers={"Content-Type": "application/json", **self.AUTH}, method="POST",
        )
        with urllib.request.urlopen(req) as resp:
            body = json.loads(resp.read())
        insp = body["results"]["resource.album.vdefault"]
        assert "view" in insp["actions"]
        attrs = {a["name"] for a in insp["attributes"]}
        assert {"owner", "public"} <= attrs


class TestRequestBatching:
    def test_batched_serving(self, tmp_path_factory):
        """Concurrent requests coalesce into device batches (numpy backend)."""
        import concurrent.futures

        policy_dir = tmp_path_factory.mktemp("batch-policies")
        (policy_dir / "album.yaml").write_text(POLICY)
        config = Config.load(overrides=[
            f"storage.disk.directory={policy_dir}",
            # a request under minDeviceBatch on an empty queue is answered on its
            # own thread and never queues (PR 30): these one-resource requests are
            # to coalesce, so the threshold is lowered to where they all queue
            "engine.tpu.minDeviceBatch=1",
        ])
        core = initialize(config)  # tpu enabled (numpy fallback inside evaluator when jax off)
        core.tpu_evaluator.use_jax = False  # force numpy path for the test env
        try:
            def one(i):
                from cerbos_tpu.engine import CheckInput, Principal, Resource

                out = core.engine.check([CheckInput(
                    principal=Principal(id=f"u{i}", roles=["user"]),
                    resource=Resource(kind="album", id=f"a{i}", attr={"owner": f"u{i}"}),
                    actions=["view"],
                )])[0]
                return out.actions["view"].effect

            with concurrent.futures.ThreadPoolExecutor(max_workers=16) as pool:
                results = list(pool.map(one, range(64)))
            assert all(r == "EFFECT_ALLOW" for r in results)
            assert core.batcher is not None
            assert core.batcher.stats["batches"] >= 1
            # at least some coalescing happened
            assert core.batcher.stats["batched_requests"] == 64
        finally:
            core.close()


class TestListeners:
    def test_tls(self, tmp_path_factory):
        import ssl
        import subprocess

        tmp = tmp_path_factory.mktemp("tls")
        cert, key = str(tmp / "cert.pem"), str(tmp / "key.pem")
        subprocess.run(
            ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
             "-keyout", key, "-out", cert, "-days", "1", "-subj", "/CN=localhost"],
            check=True, capture_output=True,
        )
        policy_dir = tmp_path_factory.mktemp("tls-policies")
        (policy_dir / "album.yaml").write_text(POLICY)
        config = Config.load(overrides=[
            f"storage.disk.directory={policy_dir}", "engine.tpu.enabled=false",
        ])
        core = initialize(config, use_tpu=False)
        srv = Server(core.service, ServerConfig(
            http_listen_addr="127.0.0.1:0", grpc_listen_addr="127.0.0.1:0",
            tls_cert=cert, tls_key=key,
        ))
        srv.start()
        try:
            ctx = ssl.create_default_context()
            ctx.check_hostname = False
            ctx.verify_mode = ssl.CERT_NONE
            req = urllib.request.Request(f"https://127.0.0.1:{srv.http_port}/_cerbos/health")
            with urllib.request.urlopen(req, context=ctx) as resp:
                assert json.loads(resp.read())["status"] == "SERVING"
        finally:
            srv.stop()
            core.close()

    def test_unix_socket_grpc(self, tmp_path_factory):
        tmp = tmp_path_factory.mktemp("uds")
        sock = str(tmp / "cerbos.sock")
        policy_dir = tmp_path_factory.mktemp("uds-policies")
        (policy_dir / "album.yaml").write_text(POLICY)
        config = Config.load(overrides=[
            f"storage.disk.directory={policy_dir}", "engine.tpu.enabled=false",
        ])
        core = initialize(config, use_tpu=False)
        srv = Server(core.service, ServerConfig(
            http_listen_addr="127.0.0.1:0", grpc_listen_addr=f"unix:{sock}",
        ))
        srv.start()
        try:
            from cerbos_tpu.api.cerbos.request.v1 import request_pb2
            from cerbos_tpu.api.cerbos.response.v1 import response_pb2

            channel = grpc.insecure_channel(f"unix:{sock}")
            stub = channel.unary_unary(
                "/cerbos.svc.v1.CerbosService/ServerInfo",
                request_serializer=lambda m: m.SerializeToString(),
                response_deserializer=response_pb2.ServerInfoResponse.FromString,
            )
            resp = stub(request_pb2.ServerInfoRequest(), timeout=10)
            assert "cerbos-tpu" in resp.version
            channel.close()
        finally:
            srv.stop()
            core.close()


class TestDeprecatedGRPC:
    def test_check_resource_set_grpc(self, server):
        from cerbos_tpu.api.cerbos.request.v1 import request_pb2
        from cerbos_tpu.api.cerbos.response.v1 import response_pb2
        from cerbos_tpu.server.convert import py_to_value

        channel = grpc.insecure_channel(f"127.0.0.1:{server.grpc_port}")
        stub = channel.unary_unary(
            "/cerbos.svc.v1.CerbosService/CheckResourceSet",
            request_serializer=lambda m: m.SerializeToString(),
            response_deserializer=response_pb2.CheckResourceSetResponse.FromString,
        )
        req = request_pb2.CheckResourceSetRequest(request_id="set-grpc", include_meta=True)
        req.actions.append("view")
        req.principal.id = "alice"
        req.principal.roles.append("user")
        req.resource.kind = "album"
        req.resource.instances["a1"].attr["owner"].CopyFrom(py_to_value("alice"))
        req.resource.instances["a2"].attr["owner"].CopyFrom(py_to_value("bob"))
        resp = stub(req, timeout=10)
        assert resp.resource_instances["a1"].actions["view"] == 1
        assert resp.resource_instances["a2"].actions["view"] == 2
        assert resp.meta.resource_instances["a1"].actions["view"].matched_policy == "resource.album.vdefault"
        channel.close()

    def test_check_resource_batch_grpc(self, server):
        from cerbos_tpu.api.cerbos.request.v1 import request_pb2
        from cerbos_tpu.api.cerbos.response.v1 import response_pb2
        from cerbos_tpu.server.convert import py_to_value

        channel = grpc.insecure_channel(f"127.0.0.1:{server.grpc_port}")
        stub = channel.unary_unary(
            "/cerbos.svc.v1.CerbosService/CheckResourceBatch",
            request_serializer=lambda m: m.SerializeToString(),
            response_deserializer=response_pb2.CheckResourceBatchResponse.FromString,
        )
        req = request_pb2.CheckResourceBatchRequest(request_id="batch-grpc")
        req.principal.id = "alice"
        req.principal.roles.append("user")
        e = req.resources.add()
        e.actions.append("view")
        e.resource.kind = "album"
        e.resource.id = "a1"
        e.resource.attr["owner"].CopyFrom(py_to_value("alice"))
        resp = stub(req, timeout=10)
        assert resp.results[0].resource_id == "a1"
        assert resp.results[0].actions["view"] == 1
        channel.close()


class TestTLSHotReload:
    @staticmethod
    def _self_signed(cn: str):
        import datetime

        pytest.importorskip("cryptography", reason="TLS tests need cert generation")
        from cryptography import x509
        from cryptography.hazmat.primitives import hashes, serialization
        from cryptography.hazmat.primitives.asymmetric import rsa
        from cryptography.x509.oid import NameOID

        key = rsa.generate_private_key(public_exponent=65537, key_size=2048)
        name = x509.Name([x509.NameAttribute(NameOID.COMMON_NAME, cn)])
        now = datetime.datetime.now(datetime.timezone.utc)
        cert = (
            x509.CertificateBuilder()
            .subject_name(name)
            .issuer_name(name)
            .public_key(key.public_key())
            .serial_number(x509.random_serial_number())
            .not_valid_before(now - datetime.timedelta(minutes=1))
            .not_valid_after(now + datetime.timedelta(days=1))
            .add_extension(x509.SubjectAlternativeName([x509.DNSName("localhost")]), critical=False)
            .sign(key, hashes.SHA256())
        )
        return (
            cert.public_bytes(serialization.Encoding.PEM),
            key.private_bytes(
                serialization.Encoding.PEM,
                serialization.PrivateFormat.TraditionalOpenSSL,
                serialization.NoEncryption(),
            ),
        )

    def test_cert_rotation_without_restart(self, tmp_path):
        import ssl as ssl_mod

        from cerbos_tpu.compile import compile_policy_set
        from cerbos_tpu.engine import Engine
        from cerbos_tpu.policy.parser import parse_policies
        from cerbos_tpu.server.service import CerbosService
        from cerbos_tpu.server.server import Server, ServerConfig

        cert_path, key_path = tmp_path / "tls.crt", tmp_path / "tls.key"
        pem1, key1 = self._self_signed("cerbos-one")
        cert_path.write_bytes(pem1)
        key_path.write_bytes(key1)

        engine = Engine.from_policies(compile_policy_set(list(parse_policies(POLICY))))
        srv = Server(
            CerbosService(engine),
            ServerConfig(
                http_listen_addr="127.0.0.1:0",
                grpc_listen_addr="127.0.0.1:0",
                tls_cert=str(cert_path),
                tls_key=str(key_path),
                tls_watch_interval_s=0.1,
            ),
        )
        srv.start()
        try:
            def served_cn() -> str:
                pem = ssl_mod.get_server_certificate(("127.0.0.1", srv.http_port))
                from cryptography import x509

                cert = x509.load_pem_x509_certificate(pem.encode())
                return cert.subject.rfc4514_string()

            assert "cerbos-one" in served_cn()

            pem2, key2 = self._self_signed("cerbos-two")
            cert_path.write_bytes(pem2)
            key_path.write_bytes(key2)
            deadline = time.time() + 5
            while time.time() < deadline:
                if "cerbos-two" in served_cn():
                    break
                time.sleep(0.1)
            assert "cerbos-two" in served_cn(), "rotated cert never served"

            # gRPC side also serves the rotated cert
            import grpc as grpc_mod

            creds = grpc_mod.ssl_channel_credentials(root_certificates=pem2)
            with grpc_mod.secure_channel(
                f"localhost:{srv.grpc_port}", creds,
                options=(("grpc.ssl_target_name_override", "localhost"),),
            ) as ch:
                grpc_mod.channel_ready_future(ch).result(timeout=10)
        finally:
            srv.stop()


class TestCtlGrpc:
    def test_ctl_grpc_roundtrip(self, server, capsys):
        from cerbos_tpu import ctl

        addr = f"127.0.0.1:{server.grpc_port}"
        rc = ctl.main(["--server", addr, "--grpc", "get", "policies"])
        assert rc in (0, None)
        out = capsys.readouterr().out
        assert "resource.album.vdefault" in out

        rc = ctl.main(["--server", addr, "--grpc", "get", "policy", "resource.album.vdefault"])
        assert rc in (0, None)
        out = capsys.readouterr().out
        assert "resourcePolicy" in out

        rc = ctl.main(["--server", addr, "--grpc", "store", "reload"])
        assert rc in (0, None)


class TestCORS:
    def test_preflight_and_origin_header(self, server):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.http_port}/api/check/resources",
            method="OPTIONS",
            headers={"Origin": "https://app.example", "Access-Control-Request-Method": "POST"},
        )
        with urllib.request.urlopen(req) as resp:
            assert resp.status == 204
            assert resp.headers["Access-Control-Allow-Origin"] == "*"
            assert "POST" in resp.headers["Access-Control-Allow-Methods"]
            assert "user-agent" in resp.headers["Access-Control-Allow-Headers"]

    def test_simple_request_gets_origin(self, server):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.http_port}/_cerbos/health",
            headers={"Origin": "https://app.example"},
        )
        with urllib.request.urlopen(req) as resp:
            assert resp.headers["Access-Control-Allow-Origin"] == "*"


class TestOpenAPI:
    def test_swagger_document(self, server):
        doc = json.loads(http_get(server, "/schema/swagger.json"))
        assert doc["swagger"] == "2.0"
        assert "/api/check/resources" in doc["paths"]
        assert "/api/plan/resources" in doc["paths"]
        assert "/admin/policies" in doc["paths"]
        assert "Principal" in doc["definitions"]

    def test_api_explorer(self, server):
        html = http_get(server, "/").decode()
        assert "/schema/swagger.json" in html
        assert "<html" in html


class TestOtlpMetrics:
    def test_export_posts_gauges(self):
        import threading
        from http.server import BaseHTTPRequestHandler, HTTPServer

        from cerbos_tpu.observability import OTLPMetricsExporter
        from cerbos_tpu.server.service import ServiceMetrics

        received = []

        class Handler(BaseHTTPRequestHandler):
            def do_POST(self):
                body = self.rfile.read(int(self.headers.get("Content-Length", "0")))
                received.append((self.path, json.loads(body)))
                self.send_response(200)
                self.send_header("Content-Length", "2")
                self.end_headers()
                self.wfile.write(b"{}")

            def log_message(self, *a):
                pass

        httpd = HTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        try:
            m = ServiceMetrics()
            m.record_check(1.5, 2)
            m.record_check(3.5, 1)
            mx = OTLPMetricsExporter(f"http://127.0.0.1:{httpd.server_address[1]}", interval_s=3600)
            mx.add_source(m.snapshot)
            mx.close()  # close flushes
            assert received and received[0][0] == "/v1/metrics"
            metrics = received[0][1]["resourceMetrics"][0]["scopeMetrics"][0]["metrics"]
            by_name = {x["name"]: x["gauge"]["dataPoints"][0]["asDouble"] for x in metrics}
            assert by_name["cerbos_dev_engine_check_count"] == 2.0
            assert by_name["cerbos_dev_engine_check_batch_size_total"] == 3.0
        finally:
            httpd.shutdown()


class TestAuditProtos:
    def test_decision_log_entry_wire_shape(self):
        """Audit proto family is wire-compatible: DecisionLogEntry round-trips
        with the reference's field numbers (audit.proto)."""
        from google.protobuf import json_format

        from cerbos_tpu.api.cerbos.audit.v1 import audit_pb2

        e = audit_pb2.DecisionLogEntry(call_id="01HXYZ")
        e.peer.address = "10.0.0.1"
        e.check_resources.inputs.add(request_id="r1")
        e.audit_trail.effective_policies["resource.doc.vdefault"].attributes["source"].string_value = "doc.yaml"
        raw = e.SerializeToString()
        back = audit_pb2.DecisionLogEntry.FromString(raw)
        assert back.call_id == "01HXYZ"
        assert back.WhichOneof("method") == "check_resources"
        j = json_format.MessageToDict(back)
        assert j["auditTrail"]["effectivePolicies"]["resource.doc.vdefault"]["attributes"]["source"] == "doc.yaml"

    def test_telemetry_proto_shape(self):
        from cerbos_tpu.api.cerbos.telemetry.v1 import telemetry_pb2

        launch = telemetry_pb2.ServerLaunch(version="1.0")
        launch.features.storage.driver = "disk"
        launch.features.storage.disk.watch = True
        launch.stats.policy.count["RESOURCE"] = 9
        back = telemetry_pb2.ServerLaunch.FromString(launch.SerializeToString())
        assert back.features.storage.WhichOneof("store") == "disk"
        assert back.stats.policy.count["RESOURCE"] == 9


class TestAuthZenProtos:
    def test_authzen_wire_shapes(self):
        from google.protobuf import json_format

        from cerbos_tpu.api.authzen.authorization.v1 import evaluation_pb2

        req = evaluation_pb2.AccessEvaluationRequest()
        req.subject.type = "user"
        req.subject.id = "alice"
        req.resource.type = "doc"
        req.action.name = "view"
        back = evaluation_pb2.AccessEvaluationRequest.FromString(req.SerializeToString())
        assert back.subject.id == "alice"
        # AuthZEN wire JSON uses snake_case metadata field names (json_name)
        meta = evaluation_pb2.MetadataResponse(access_evaluation_endpoint="/access/v1/evaluation")
        j = json_format.MessageToDict(meta)
        assert j == {"access_evaluation_endpoint": "/access/v1/evaluation"}


class TestAioGrpc:
    """The grpc.aio listener variant (server.grpcAsync): same handlers on
    the HTTP event loop; abort semantics translated by the shim."""

    @pytest.fixture(scope="class")
    def aio_server(self, tmp_path_factory):
        policy_dir = tmp_path_factory.mktemp("policies-aio")
        (policy_dir / "album.yaml").write_text(POLICY)
        config = Config.load(
            overrides=[
                f"storage.disk.directory={policy_dir}",
                "audit.enabled=true",
                "audit.backend=local",
                "engine.tpu.enabled=false",
            ]
        )
        core = initialize(config, use_tpu=False)
        admin = AdminService(core, username="cerbos", password="cerbosAdmin")
        srv = Server(
            core.service,
            ServerConfig(
                http_listen_addr="127.0.0.1:0",
                grpc_listen_addr="127.0.0.1:0",
                grpc_async=True,
            ),
            admin_service=admin,
        )
        srv.start()
        yield srv
        srv.stop()
        core.close()

    def test_check_over_aio(self, aio_server):
        from cerbos_tpu.api.cerbos.request.v1 import request_pb2
        from cerbos_tpu.api.cerbos.response.v1 import response_pb2
        from cerbos_tpu.server.convert import py_to_value

        with grpc.insecure_channel(f"127.0.0.1:{aio_server.grpc_port}") as ch:
            stub = ch.unary_unary(
                "/cerbos.svc.v1.CerbosService/CheckResources",
                request_serializer=lambda m: m.SerializeToString(),
                response_deserializer=response_pb2.CheckResourcesResponse.FromString,
            )
            req = request_pb2.CheckResourcesRequest(request_id="aio-1")
            req.principal.id = "alice"
            req.principal.roles.append("user")
            entry = req.resources.add()
            entry.actions.append("view")
            entry.resource.kind = "album"
            entry.resource.id = "a1"
            entry.resource.attr["owner"].CopyFrom(py_to_value("alice"))
            resp = stub(req, timeout=10)
            assert resp.results[0].actions["view"] == 1  # EFFECT_ALLOW

    def test_abort_translates(self, aio_server):
        from cerbos_tpu.api.cerbos.request.v1 import request_pb2
        from cerbos_tpu.api.cerbos.response.v1 import response_pb2

        with grpc.insecure_channel(f"127.0.0.1:{aio_server.grpc_port}") as ch:
            stub = ch.unary_unary(
                "/cerbos.svc.v1.CerbosAdminService/ListPolicies",
                request_serializer=lambda m: m.SerializeToString(),
                response_deserializer=response_pb2.ListPoliciesResponse.FromString,
            )
            with pytest.raises(grpc.RpcError) as e:
                stub(request_pb2.ListPoliciesRequest(), timeout=10)  # no auth
            assert e.value.code() == grpc.StatusCode.UNAUTHENTICATED

    def test_admin_streaming_over_aio(self, aio_server):
        import base64

        from cerbos_tpu.api.cerbos.request.v1 import request_pb2
        from cerbos_tpu.api.cerbos.response.v1 import response_pb2

        # generate at least one decision entry
        self.test_check_over_aio(aio_server)
        auth = [("authorization", "Basic " + base64.b64encode(b"cerbos:cerbosAdmin").decode())]
        with grpc.insecure_channel(f"127.0.0.1:{aio_server.grpc_port}") as ch:
            stub = ch.unary_stream(
                "/cerbos.svc.v1.CerbosAdminService/ListAuditLogEntries",
                request_serializer=lambda m: m.SerializeToString(),
                response_deserializer=response_pb2.ListAuditLogEntriesResponse.FromString,
            )
            req = request_pb2.ListAuditLogEntriesRequest(
                kind=request_pb2.ListAuditLogEntriesRequest.KIND_DECISION, tail=10
            )
            entries = list(stub(req, metadata=auth, timeout=10))
            assert entries, "decision entries must stream over the aio server"
