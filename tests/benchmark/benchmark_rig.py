"""Shared by the benchmark's tests: a temporary copy of the benchmark with a
tiny configuration and cells of its own, added as a later PR would add them
(new files and new ``workloads`` entries, no file that exists edited)."""

from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY_MODS = 3


def copy_benchmark(dst: str) -> str:
    """``dst`` becomes a root holding BENCHMARK.json and benchmarks/."""
    shutil.copyfile(os.path.join(REPO, "BENCHMARK.json"), os.path.join(dst, "BENCHMARK.json"))
    shutil.copytree(
        os.path.join(REPO, "benchmarks"), os.path.join(dst, "benchmarks"),
        ignore=shutil.ignore_patterns("__pycache__", "out"),
    )
    return dst


def add_tiny(root: str, rate: float = 40.0) -> dict:
    """Add configuration ``tiny`` (3 name-mods), a rate file for each mix, and
    the cells ``tiny.pages`` and ``tiny.sidecar``, as a later PR would add a
    cell on a mix that is there: new files, a ``workloads`` entry, and the
    cell's name under the end-to-end metrics it reports. The per-layer
    metrics that move those follow by themselves. Returns the manifest."""
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(bench, "configs", "classic-800.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "tiny"
    cfg["corpus"]["mods"] = TINY_MODS
    with open(os.path.join(bench, "configs", "tiny.json"), "w") as f:
        json.dump(cfg, f)
    pairs = {"pages": {"rate": rate}, "sidecar": {"rate": rate * 2}}
    for mix, params in pairs.items():
        with open(os.path.join(bench, "traffic", "rates", f"tiny.{mix}.json"), "w") as f:
            json.dump(params, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "tiny", "source": cfg["source"], "file": "benchmarks/configs/tiny.json", "reduced": [], "why": "test"}
    )
    for mix in pairs:
        twin, name = f"classic-800.{mix}", f"tiny.{mix}"
        manifest["workloads"].append({"name": name, "config": "tiny", "traffic": mix, "chips": 1, "why": "test"})
        for m in manifest["end_to_end"]:
            if twin in m.get("workloads", []):
                m["workloads"].append(name)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return manifest


class FakePdp:
    """A gRPC server in this process that answers CheckResources from the
    plain reference, one request at a time (one worker thread), with hooks to
    stall, refuse or falsify chosen requests by their index."""

    def __init__(self, stall: dict | None = None, refuse=(), falsify=()):
        from concurrent import futures
        from datetime import datetime, timezone

        import grpc
        from google.protobuf import json_format

        from benchmarks.lib import loadgen, reference
        from cerbos_tpu.api.cerbos.request.v1 import request_pb2
        from cerbos_tpu.api.cerbos.response.v1 import response_pb2

        self.stall, self.refuse, self.falsify = stall or {}, set(refuse), set(falsify)
        effect_no = {reference.ALLOW: 1, reference.DENY: 2}

        def answer(raw: bytes, context):
            import time

            req = request_pb2.CheckResourcesRequest.FromString(raw)
            index = int(req.request_id[1:])
            if index in self.stall:
                time.sleep(self.stall[index])
            if index in self.refuse:
                context.abort(grpc.StatusCode.RESOURCE_EXHAUSTED, "admission refused: test")
            body = json_format.MessageToDict(req)
            p = body["principal"]
            principal = {"id": p["id"], "roles": p.get("roles", []), "attr": p.get("attr", {})}
            resp = response_pb2.CheckResourcesResponse(request_id=req.request_id)
            for entry in body.get("resources", []):
                r = entry["resource"]
                resource = {
                    "kind": r["kind"], "id": r.get("id", ""), "attr": r.get("attr", {}),
                    "policyVersion": r.get("policyVersion", ""), "scope": r.get("scope", ""),
                }
                eff = reference.effects(principal, resource, entry["actions"], datetime.now(timezone.utc))
                out = resp.results.add()
                out.resource.id = resource["id"]
                for a, e in eff.items():
                    out.actions[a] = effect_no[e]
            if index in self.falsify:
                first = resp.results[0]
                a = sorted(first.actions)[0]
                first.actions[a] = 3 - first.actions[a]
            return resp.SerializeToString()

        handler = grpc.method_handlers_generic_handler(
            "cerbos.svc.v1.CerbosService",
            {"CheckResources": grpc.unary_unary_rpc_method_handler(answer, request_deserializer=None, response_serializer=None)},
        )
        assert loadgen.METHOD == "/cerbos.svc.v1.CerbosService/CheckResources"
        self.server = grpc.server(futures.ThreadPoolExecutor(max_workers=1))
        self.server.add_generic_rpc_handlers((handler,))
        self.port = self.server.add_insecure_port("127.0.0.1:0")
        self.server.start()

    @property
    def target(self) -> str:
        return f"127.0.0.1:{self.port}"

    def close(self) -> None:
        self.server.stop(grace=0).wait()
