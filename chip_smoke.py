#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path runs on the chip.

Drives the normal entry point — ``python -m cerbos_tpu.cli server`` in its
OWN process, HTTP and gRPC ``CheckResources`` in, effects out — at upstream's
smallest published scale (the ``hack/loadtest`` classic template,
``bench_corpus.corpus_yaml(100)``: the "800 policies" configuration), and
fails unless the SERVER'S OWN counters say the decisions came from a TPU.

The served path answers correctly with no device at all (flights under
``minDeviceBatch`` and every device fault are served by the CPU oracle with a
200), so correct effects prove nothing about the chip. What is checked, per
topology (default single process, then ``--frontends 2``), each in a fresh
server process:

- every returned effect equals ``ruletable.check_input`` evaluated here (pure
  Python/numpy: this parent never imports jax — the chip belongs to the server);
- the device owner's boot status (``X-Cerbos-Jitcache`` on
  ``/_cerbos/debug/flight``) says ``platform=tpu``, and only that process
  holds device file descriptors;
- in a checked pass over requests a cold pass has already sent:
  ``decision_source_total{source="device"}`` covers >= 0.9 of the batch-shaped
  decisions, no oracle fallback for any reason, no breaker trip, no XLA
  compile; overall: >= 1 compile, >= 1 parity check and 0 divergences, device
  memory in use > 0, the native module loaded, exit 0 on SIGTERM; every gRPC
  request read and every reply written by the listener's native codec
  (``wire_codec_total`` shows no ``path="python"`` in either direction, in
  whichever process listened);
- under ``--frontends 2``, that the pool is one server to its operator: every
  scrape is ONE request and must hold ``fe1``, ``fe2`` and ``batcher``, and one
  profiler capture asked of the served HTTP port (a front end, which holds no
  device) comes back from the device owner and holds TPU operations;
- after each topology, the same topology booted ONCE MORE on the same cache
  directory (``run_restart``; not under ``--lanes``, ``--audit`` or ``--schema``): the
  second process must load layouts ahead of traffic from the layout manifest
  the first one filed (``xla_preloads_total{outcome="loaded"}`` > 0), build
  fewer layouts inside requests over the same pass than a first boot that
  found no manifest did, and raise no recompile storm by its walk.

Traffic: (a) upstream-shaped requests, 1 resource x its actions, from 64
concurrent connections — these coalesce by timing, so how many reach the
device varies run to run and is printed, not judged; (b) batch-API-shaped
requests of up to 50 resources sent one at a time, so the device layouts they
hit are the same on every run. After the checked pass 16 of the shape-(b)
requests go out once more, all at once on a connection each: that burst is
reported, not judged beyond its effects — it coalesces into flights of
B >= 64, the layouts whose cold compile runs up to and past the default
``requestTimeoutMs``.

Prints observations (platform, device_kind, cache directory, compile seconds
per layout by source, device/oracle split per traffic shape, per-stage p50s)
then a JSON summary of them ending ``"claim": null`` and, as the last line of
stdout, ``{"ok": true, "device": {"platform", "kind", "count"}}`` with exactly
those keys. Exits non-zero and prints no result when no accelerator is found. Platform, corpus scale and request
counts are constants: no option makes a chipless or toy-size run end in
``"ok": true``.
"""

from __future__ import annotations

import argparse
import base64
import glob
import hashlib
import hmac
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
JWT_SECRET = b"cerbos-tpu-chip-smoke-secret"
PLATFORM = "tpu"  # what the server's device owner must report
MODS = 100  # classic-template name mods: the 800-policy configuration
CONNECTIONS = 64  # shape (a): upstream's loadtest drives this many connections
N_SINGLE = 2048  # shape (a) requests per protocol per pass
N_BATCH = 30  # shape (b) requests per protocol per pass
MAX_RESOURCES = 50  # server.requestLimits.maxResourcesPerRequest
# shape (b) sizes, cycled: mostly the API limit, plus two smaller pow2 buckets
BATCH_SIZES = (50, 50, 50, 32, 16)
DEVICE_SHARE_MIN = 0.9
CHECKED_ATTEMPTS = 4
BURST_CONNECTIONS = 16  # the reported burst: this many shape-(b) requests at once
CAPTURE_S = 3.0  # the pooled leg's profiler capture, taken through the served HTTP port
DEFAULT_REQUEST_TIMEOUT_S = 30.0  # both requestTimeoutMs keys when the config does not set them

# existing config keys the smoke sets beyond addresses, storage and the JWT
# key set, each with its reason; printed at start
CONFIG_SET = {
    "engine.tpu.requestTimeoutMs": (
        600000,
        "cold XLA compiles run inside the first requests; at the 30 s default a timed-out "
        "waiter is oracle-served and counted against the breaker",
    ),
    "engine.tpu.sharedBatcher.requestTimeoutMs": (
        600000,
        "a front end's wait on the shared batcher is its own key, defaulted to 30 s in "
        "config.py, so it does not follow the key above; past it the front end serves its oracle",
    ),
}


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


# -- metrics ------------------------------------------------------------------

_SERIES = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{(.*)\})?\s+(\S+)\s*$")
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_metrics(text: str) -> dict[tuple, float]:
    """Prometheus text -> ``{(name, ((label, value), ...)): value}``."""
    out: dict[tuple, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SERIES.match(line)
        if not m:
            continue
        name, labels, raw = m.groups()
        try:
            val = float(raw)
        except ValueError:
            continue
        out[(name, tuple(sorted(_LABEL.findall(labels or ""))))] = val
    return out


def msum(metrics: dict[tuple, float], name: str, **want: str) -> float:
    """Sum of every series of ``name`` whose labels include ``want`` (so the
    per-process ``worker`` label of pool topologies folds away)."""
    total = 0.0
    for (n, labels), v in metrics.items():
        if n == name and all((k, w) in labels for k, w in want.items()):
            total += v
    return total


def by_label(metrics: dict[tuple, float], name: str, label: str) -> dict[str, int]:
    """Non-zero totals of the counter ``name`` per value of ``label``."""
    out: dict[str, int] = {}
    for (n, labels), v in sorted(metrics.items()):
        if n == name and v:
            key = dict(labels).get(label, "")
            out[key] = out.get(key, 0) + int(v)
    return out


def delta(before: dict[tuple, float], after: dict[tuple, float]) -> dict[tuple, float]:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def stage_p50s(d: dict[tuple, float], name: str) -> dict[str, float]:
    """p50 per ``stage`` label of a histogram, from (delta) cumulative buckets."""
    per_stage: dict[str, dict[float, float]] = {}
    for (n, labels), v in d.items():
        if n != name + "_bucket":
            continue
        lab = dict(labels)
        le = float("inf") if lab["le"] == "+Inf" else float(lab["le"])
        b = per_stage.setdefault(lab.get("stage", ""), {})
        b[le] = b.get(le, 0.0) + v
    out = {}
    for stage, buckets in sorted(per_stage.items()):
        count = buckets.get(float("inf"), 0.0)
        if count <= 0:
            continue
        lo, prev = 0.0, 0.0
        for le in sorted(buckets):
            cum = buckets[le]
            if cum >= count / 2:
                if le == float("inf"):
                    out[stage] = lo
                else:
                    frac = (count / 2 - prev) / (cum - prev) if cum > prev else 0.0
                    out[stage] = lo + (le - lo) * frac
                break
            lo, prev = le, cum
    return out


# -- the checks ---------------------------------------------------------------


def check_platform(status: dict) -> None:
    dev = status.get("device")
    if not dev:
        raise SmokeFailure(
            "the server's device owner reports no device (X-Cerbos-Jitcache carries no "
            "'device' block): nothing opened a JAX backend"
        )
    if dev["platform"] != PLATFORM:
        raise SmokeFailure(
            f"the server reports platform={dev['platform']!r} (device_kind="
            f"{dev['device_kind']!r}), not {PLATFORM!r}: no accelerator behind the served path"
        )


def check_pass(
    before: dict[tuple, float],
    after: dict[tuple, float],
    batch_decisions: int,
    batch_device_decisions: float,
) -> list[str]:
    """The checked pass, from two scrapes of /_cerbos/metrics around it:
    nothing may have routed around the device and nothing may have compiled.
    Returns failures; each compile-only failure starts with ``compile:``."""
    d = delta(before, after)
    failures = []
    share = batch_device_decisions / batch_decisions if batch_decisions else 0.0
    if share < DEVICE_SHARE_MIN:
        failures.append(
            f'decision_source_total{{source="device"}} moved by {batch_device_decisions:.0f} over '
            f"{batch_decisions} batch-shaped decisions (share {share:.3f} < {DEVICE_SHARE_MIN}): "
            "the device did not serve them"
        )
    for reason, n in sorted(by_label(d, "cerbos_tpu_batcher_oracle_fallbacks_total", "reason").items()):
        if n > 0:
            failures.append(f'batcher_oracle_fallbacks_total{{reason="{reason}"}} moved by {n:.0f}')
    trips = msum(d, "cerbos_tpu_breaker_trips_total")
    if trips > 0:
        failures.append(f"breaker_trips_total moved by {trips:.0f}")
    compiles = msum(d, "cerbos_tpu_xla_compiles_total")
    if compiles > 0:
        failures.append(f"compile: xla_compiles_total moved by {compiles:.0f} during the checked pass")
    return failures


def check_totals(final: dict[tuple, float]) -> list[str]:
    """What must hold over the server's whole life, from the last scrape."""
    failures = []
    if msum(final, "cerbos_tpu_xla_compiles_total") < 1:
        failures.append("xla_compiles_total is 0: nothing was ever compiled for the device")
    if msum(final, "cerbos_tpu_parity_checks_total") < 1:
        failures.append("parity_checks_total is 0: the sentinel never replayed a device batch")
    div = msum(final, "cerbos_tpu_parity_divergence_total")
    if div > 0:
        failures.append(f"parity_divergence_total is {div:.0f}: device and oracle disagree")
    # a CPU backend reports no memory stats, so this is also a platform check
    if msum(final, "cerbos_tpu_device_memory_bytes_in_use") <= 0:
        failures.append("device_memory_bytes_in_use is 0: the backend holds no device memory")
    return failures


# -- requests and their expected effects --------------------------------------


def _jwt(claims: dict) -> str:
    def b64(b: bytes) -> bytes:
        return base64.urlsafe_b64encode(b).rstrip(b"=")

    head = b64(json.dumps({"alg": "HS256", "typ": "JWT"}).encode())
    payload = b64(json.dumps(claims).encode())
    sig = b64(hmac.new(JWT_SECRET, head + b"." + payload, hashlib.sha256).digest())
    return (head + b"." + payload + b"." + sig).decode()


def _body(principal, aux, entries, request_id: str) -> dict:
    body = {
        "requestId": request_id,
        "principal": {
            "id": principal.id,
            "roles": principal.roles,
            "policyVersion": principal.policy_version,
            "scope": principal.scope,
            "attr": principal.attr,
        },
        "resources": [
            {
                "actions": i.actions,
                "resource": {
                    "kind": i.resource.kind,
                    "id": i.resource.id,
                    "policyVersion": i.resource.policy_version,
                    "scope": i.resource.scope,
                    "attr": i.resource.attr,
                },
            }
            for i in entries
        ],
    }
    if aux is not None:
        body["auxData"] = {"jwt": {"token": _jwt(aux.jwt)}}
    return body


class Request:
    """One CheckResources request: wire bodies plus the oracle's effects."""

    def __init__(self, body: dict, claims, rule_table):
        from cerbos_tpu.engine import types as T
        from cerbos_tpu.ruletable import check_input
        from cerbos_tpu.server import convert

        self.body = body
        self.http = json.dumps(body).encode()
        # the oracle sees what the server sees: the body after a JSON round
        # trip, through the server's own request conversion
        aux = T.AuxData(jwt=claims) if claims is not None else None
        inputs, _, _ = convert.json_to_check_inputs(json.loads(self.http), aux)
        params = T.EvalParams()
        self.expected = [
            {a: e.effect for a, e in check_input(rule_table, i, params).actions.items()}
            for i in inputs
        ]
        self.decisions = sum(len(e) for e in self.expected)
        self.errors = None  # --schema: each result's validation errors by the plain reading (expect_validation)


def build_requests(mods: int, seed: int, n_single: int, n_batch: int):
    from cerbos_tpu.compile import compile_policy_set
    from cerbos_tpu.policy.parser import parse_policies
    from cerbos_tpu.ruletable import build_rule_table
    from cerbos_tpu.util import bench_corpus

    rt = build_rule_table(compile_policy_set(list(parse_policies(bench_corpus.corpus_yaml(mods)))))
    singles = [
        Request(_body(i.principal, i.aux_data, [i], i.request_id), i.aux_data and i.aux_data.jwt, rt)
        for i in bench_corpus.requests(n_single, mods, seed=seed)
    ]
    # shape (b): one principal (the first input's) over the resources of a
    # run of consecutive corpus inputs, as a list endpoint would send them
    pool = bench_corpus.requests(n_batch * MAX_RESOURCES, mods, seed=seed + 1)
    batches = []
    for k in range(n_batch):
        chunk = pool[k * MAX_RESOURCES :][: BATCH_SIZES[k % len(BATCH_SIZES)]]
        first = chunk[0]
        batches.append(
            Request(
                _body(first.principal, first.aux_data, chunk, f"batch-{k}"),
                first.aux_data and first.aux_data.jwt,
                rt,
            )
        )
    return singles, batches


def expect_validation(reqs: list[Request], mods: int, level: str) -> dict:
    """``--schema``: what the plain reading (``benchmarks/tools/schema_check.py``:
    no validator library, nothing of the program) says each result's
    ``validationErrors`` must be; under ``reject`` an input with any error
    reads EFFECT_DENY for every action, the others as without schemas."""
    from types import SimpleNamespace

    from benchmarks.tools import schema_check
    from cerbos_tpu.util import bench_corpus

    table = schema_check.Table(bench_corpus.corpus_yaml(mods).split("\n---\n"), bench_corpus.schemas(mods))
    seen = {"results": 0, "with_errors": 0, "errors": 0}
    for req in reqs:
        body = req.body
        plain = SimpleNamespace(
            principal=body["principal"], entries=[(e["resource"], e["actions"]) for e in body["resources"]]
        )
        req.errors = table.expected(plain)
        for k, found in enumerate(req.errors):
            seen["results"] += 1
            seen["with_errors"] += bool(found)
            seen["errors"] += len(found)
            if found and level == "reject":
                req.expected[k] = dict.fromkeys(req.expected[k], "EFFECT_DENY")
    return seen


def served_problem(req: Request, results: list[dict]) -> str | None:
    """What is wrong with a reply's results: effects against the oracle's
    and, under ``--schema``, validation errors against the plain reading."""
    got = [r.get("actions", {}) for r in results]
    if got != req.expected:
        return f"got {got} want {req.expected}"
    if req.errors is None:
        return None
    from benchmarks.tools import schema_check

    served = [
        [(e.get("source", ""), e.get("path", ""), e.get("message", "")) for e in r.get("validationErrors", [])]
        for r in results
    ]
    return schema_check.diff(req.errors, served)


def write_policies(policy_dir: str, mods: int) -> int:
    """One policy per file plus the schemas, as ``benchmarks/lib/server.py`` does."""
    from cerbos_tpu.util import bench_corpus

    docs = bench_corpus.corpus_yaml(mods).split("\n---\n")
    for i, doc in enumerate(docs):
        with open(os.path.join(policy_dir, f"policy_{i:05d}.yaml"), "w") as f:
            f.write(doc)
    schema_dir = os.path.join(policy_dir, "_schemas")
    os.makedirs(schema_dir, exist_ok=True)
    for name, data in bench_corpus.schemas(mods).items():
        with open(os.path.join(schema_dir, name), "wb") as f:
            f.write(data)
    return len(docs)


# -- the server process -------------------------------------------------------


class ServerProc:
    def __init__(
        self, name: str, policy_dir: str, extra_args: list[str], tpu_conf: dict, audit_path: str = "", schema: str = ""
    ):
        import yaml

        self.name = name
        self.stderr_path = os.path.join(OUT_DIR, f"{name}.server.stderr")
        tpu = {"enabled": True, **tpu_conf}
        for key, (value, _) in CONFIG_SET.items():
            *path, leaf = key.removeprefix("engine.tpu.").split(".")
            node = tpu
            for part in path:
                node = node.setdefault(part, {})
            node[leaf] = value
        cfg = {
            "server": {"httpListenAddr": "127.0.0.1:0", "grpcListenAddr": "127.0.0.1:0"},
            "storage": {"driver": "disk", "disk": {"directory": policy_dir}},
            "engine": {"tpu": tpu},
            "auxData": {
                "jwt": {
                    "keySets": [
                        {
                            "id": "default",
                            "algorithm": "HS256",
                            "local": {"data": base64.b64encode(JWT_SECRET).decode()},
                        }
                    ]
                }
            },
        }
        if audit_path:
            # upstream's audit block, file backend, rotated often and nothing deleted, so
            # that every entry of the run is still on disk when check_audit reads it
            cfg["audit"] = {
                "enabled": True, "backend": "file", "accessLogsEnabled": True, "decisionLogsEnabled": True,
                "file": {"path": audit_path, "logRotation": {"maxFileSizeMB": AUDIT_ROTATE_MB, "maxFileCount": 1000}},
            }
        if schema:
            cfg["schema"] = {"enforcement": schema}  # upstream's schema block, over the template's own _schemas/
        cfg_path = os.path.join(os.path.dirname(policy_dir), f"{name}.cerbos.yaml")
        with open(cfg_path, "w") as f:
            yaml.safe_dump(cfg, f)
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        self._stderr = open(self.stderr_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "cerbos_tpu.cli", "server", "--config", cfg_path, *extra_args],
            stdout=subprocess.PIPE,
            stderr=self._stderr,
            text=True,
            env=env,
            cwd=REPO,
        )
        self.http_port = self.grpc_port = 0
        self.native = None
        self.last_scrape = ""
        self.call_ids: list[str] = []  # every reply's cerbosCallId, for check_call_ids
        # the server's stdout is read by its own thread for as long as the
        # pipe is open: every line is echoed, the serving line is kept
        self._serving_line = ""
        self._serving = threading.Event()
        self._pump = threading.Thread(target=self._pump_stdout, daemon=True)
        self._pump.start()

    def _pump_stdout(self) -> None:
        for line in self.proc.stdout:
            line = line.rstrip("\n")
            log(f"  [{self.name}] {line}")
            if line.startswith("cerbos-tpu serving:"):
                self._serving_line = line
                self._serving.set()

    def wait_serving(self, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        while not self._serving.wait(0.25):
            if self.proc.poll() is not None:
                raise SmokeFailure(f"server exited {self.proc.returncode} before announcing ports")
            if time.monotonic() >= deadline:
                raise SmokeFailure(f"no 'cerbos-tpu serving:' line within {timeout:.0f} s")
        fields = dict(t.split("=", 1) for t in self._serving_line.split() if "=" in t)
        self.http_port = int(fields["http"])
        self.grpc_port = int(fields["grpc"])
        self.native = fields.get("native")
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(self.url("/_cerbos/ready"), timeout=2) as r:
                    if r.status == 200:
                        return
            except OSError:
                pass
            if self.proc.poll() is not None:
                raise SmokeFailure(f"server exited {self.proc.returncode} before becoming ready")
            time.sleep(0.25)
        raise SmokeFailure(f"server not ready within {timeout:.0f} s")

    def url(self, path: str) -> str:
        return f"http://127.0.0.1:{self.http_port}{path}"

    def status(self) -> tuple[dict, dict]:
        """(device owner's jitcache status, flight dump) — the same surface in
        both topologies: a front end relays its batcher's."""
        with urllib.request.urlopen(self.url("/_cerbos/debug/flight"), timeout=30) as r:
            return json.loads(r.headers.get("X-Cerbos-Jitcache") or "{}"), json.loads(r.read())

    def scrape(self, workers: tuple[str, ...] = ()) -> dict[tuple, float]:
        """ONE scrape of /_cerbos/metrics. Pool front ends share one port
        (SO_REUSEPORT) and whichever answers holds the whole pool: itself,
        every other front end and the batcher, each under its ``worker``
        label. A scrape that lacks one of ``workers`` fails the smoke."""
        # the hot-rule recorder folds decision_source_total every 256
        # decisions or on snapshot: ask for one so the counters are current
        with urllib.request.urlopen(self.url("/_cerbos/debug/hotrules"), timeout=30) as r:
            r.read()
        with urllib.request.urlopen(self.url("/_cerbos/metrics"), timeout=30) as r:
            self.last_scrape = r.read().decode()
        m = parse_metrics(self.last_scrape)
        answered = {dict(labels).get("worker", "") for _, labels in m}
        if not all(w in answered for w in workers):
            raise SmokeFailure(f"one scrape holds workers {sorted(answered)}, wanted all of {workers}")
        return m

    def pids(self) -> list[int]:
        """The server process and its descendants."""
        ppid: dict[int, int] = {}
        for entry in os.listdir("/proc"):
            if entry.isdigit():
                try:
                    with open(f"/proc/{entry}/stat") as f:
                        ppid[int(entry)] = int(f.read().rsplit(")", 1)[1].split()[1])
                except (OSError, ValueError, IndexError):
                    continue
        out, frontier = [self.proc.pid], [self.proc.pid]
        while frontier:
            parent = frontier.pop()
            kids = [p for p, pp in ppid.items() if pp == parent]
            out += kids
            frontier += kids
        return out

    def stop(self) -> int | None:
        """SIGTERM, wait; returns the exit code (None = had to be killed)."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.kill()
                return None
        self._pump.join(timeout=5)
        self._stderr.close()
        return self.proc.returncode

    def kill(self) -> None:
        for pid in reversed(self.pids()):
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        self.proc.wait()
        self._pump.join(timeout=5)
        self._stderr.close()

    def stderr_tail(self, n: int = 60) -> str:
        try:
            with open(self.stderr_path, errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""


def device_holders(pids: list[int]) -> dict[int, list[str]]:
    """pid -> accelerator device nodes it holds open (/dev/accel*, /dev/vfio/*)."""
    out = {}
    for pid in pids:
        held = set()
        try:
            for fd in os.listdir(f"/proc/{pid}/fd"):
                try:
                    target = os.readlink(f"/proc/{pid}/fd/{fd}")
                except OSError:
                    continue
                if target.startswith(("/dev/accel", "/dev/vfio/")):
                    held.add(target)
        except OSError:
            continue
        out[pid] = sorted(held)
    return out


# -- traffic ------------------------------------------------------------------


def _http_caller(srv: ServerProc, timeout: float):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", srv.http_port, timeout=timeout)

    def call(req: Request) -> list[dict]:
        conn.request(
            "POST", "/api/check/resources", body=req.http, headers={"Content-Type": "application/json"}
        )
        resp = conn.getresponse()
        raw = resp.read()
        if resp.status != 200:
            raise RuntimeError(f"status {resp.status} {raw[:200]!r}")
        reply = json.loads(raw)
        srv.call_ids.append(reply.get("cerbosCallId", ""))
        return reply.get("results", [])

    return call, conn.close


def _grpc_caller(srv: ServerProc, timeout: float):
    import grpc
    from google.protobuf import json_format

    from cerbos_tpu.api.cerbos.request.v1 import request_pb2
    from cerbos_tpu.api.cerbos.response.v1 import response_pb2

    channel = grpc.insecure_channel(f"127.0.0.1:{srv.grpc_port}")
    stub = channel.unary_unary(
        "/cerbos.svc.v1.CerbosService/CheckResources",
        request_serializer=lambda m: m.SerializeToString(),
        response_deserializer=response_pb2.CheckResourcesResponse.FromString,
    )

    def call(req: Request) -> list[dict]:
        msg = json_format.ParseDict(
            req.body, request_pb2.CheckResourcesRequest(), ignore_unknown_fields=True
        )
        resp = stub(msg, timeout=timeout)
        srv.call_ids.append(resp.cerbos_call_id)
        return json_format.MessageToDict(resp).get("results", [])

    return call, channel.close


def send(make_caller, srv: ServerProc, reqs: list[Request], connections: int, timeout: float) -> list[str]:
    """Send ``reqs`` over ``connections`` concurrent connections, each request
    once, comparing every answer with the oracle's. Returns what went wrong."""
    errors: list[str] = []
    bad = [0]

    def worker(w: int) -> None:
        call, close = make_caller(srv, timeout)
        try:
            for req in reqs[w::connections]:
                try:
                    problem = served_problem(req, call(req))
                except Exception as e:  # noqa: BLE001 - any failed request fails the pass, with its cause
                    problem = f"{type(e).__name__}: {e}"
                if problem:
                    bad[0] += 1
                    if len(errors) < 5:
                        errors.append(f"{req.body['requestId']}: {problem}")
        finally:
            close()

    threads = [
        threading.Thread(target=worker, args=(w,), daemon=True)
        for w in range(min(connections, len(reqs)))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if bad[0]:
        errors.insert(0, f"{bad[0]} of {len(reqs)} requests failed or returned wrong effects")
    return errors


def scrape_settled(srv, workers, prev: dict[tuple, float], want: int):
    """Scrape until the source counters cover the ``want`` decisions just
    answered: the hot-rule recorder counts a flight's sources after its
    replies are sent. Returns the scrape and the source split since ``prev``."""
    for _ in range(50):
        cur = srv.scrape(workers)
        src = by_label(delta(prev, cur), "cerbos_tpu_decision_source_total", "source")
        if sum(src.values()) >= want:
            break
        time.sleep(0.1)
    return cur, src


def run_burst(srv, batches, workers, prev: dict[tuple, float]) -> dict:
    """Reported, not judged beyond its effects: one shape-(b) request on each
    of ``BURST_CONNECTIONS`` connections at once, over HTTP. Sent one at a time
    they only ever meet B16/B32 layouts; concurrent ones coalesce into flights
    of B >= 64, whose cold XLA:TPU compiles are the ones that run up to and
    past the default ``requestTimeoutMs`` — where each timed-out waiter would
    be oracle-served and counted against the breaker. Which layouts the burst
    meets depends on timing, which is why nothing here is a check."""
    reqs = batches[:BURST_CONNECTIONS]
    t0, wall0 = time.monotonic(), time.time()
    errors = send(_http_caller, srv, reqs, BURST_CONNECTIONS, timeout=900)
    wall = time.monotonic() - t0
    if errors:
        raise SmokeFailure("burst: " + "; ".join(errors))
    want = sum(r.decisions for r in reqs)
    cur, src = scrape_settled(srv, workers, prev, want)
    d = delta(prev, cur)
    _, flight = srv.status()
    compiles = [
        e for e in flight.get("events", []) if e.get("kind") == "xla_compile" and e.get("ts", 0) >= wall0
    ]
    slow = [e for e in compiles if e["seconds"] >= DEFAULT_REQUEST_TIMEOUT_S]
    out = {
        "split": src,
        "flights": int(msum(d, "cerbos_tpu_batcher_batches_total")),
        "compiles": by_label(d, "cerbos_tpu_xla_compiles_total", "source"),
        "compile_seconds": round(msum(d, "cerbos_tpu_xla_compile_seconds_sum"), 3),
        "max_compile_seconds": max((e["seconds"] for e in compiles), default=0.0),
        "compiles_over_default_timeout": len(slow),
        "fallbacks": by_label(d, "cerbos_tpu_batcher_oracle_fallbacks_total", "reason"),
        "breaker_trips": int(msum(d, "cerbos_tpu_breaker_trips_total")),
    }
    log(
        f"  burst (reported, not judged): {len(reqs)} batch-shaped requests at once over HTTP, "
        f"one per connection, {want} decisions, effects == oracle, {wall:.2f} s; "
        f"source {src}; flights +{out['flights']}; compiles {out['compiles'] or 'none'}, "
        f"{out['compile_seconds']:.2f} s in XLA; fallbacks by reason {out['fallbacks'] or 'none'}; "
        f"breaker trips {out['breaker_trips']}"
    )
    for e in compiles:
        log(f"    compile {e['layout_key']}: {e['seconds']:.3f} s ({e['source']})")
    if slow:
        log(
            f"  burst: {len(slow)} compile(s) ran for >= {DEFAULT_REQUEST_TIMEOUT_S:.0f} s, the default "
            "requestTimeoutMs: a server at the default config would have timed their waiters out "
            "(oracle-served, counted against the breaker)"
        )
    return out


def run_capture(srv, batches, owner_pid: int) -> dict:
    """The pooled leg: one profiler capture asked of the served HTTP port,
    where the kernel hands it to a front end that holds no device, over
    batch-shaped requests sent meanwhile. It must come back from the device
    owner and hold TPU operations; read with the benchmark's own reducer."""
    from benchmarks.lib import trace_reduce

    box: dict = {}

    def capture() -> None:
        try:
            url = srv.url(f"/_cerbos/debug/profile?seconds={CAPTURE_S}")
            with urllib.request.urlopen(url, timeout=CAPTURE_S + 600) as r:
                box["reply"] = json.loads(r.read())
        except Exception as e:  # noqa: BLE001 - reported below with its cause
            box["error"] = f"{type(e).__name__}: {e}"
            if hasattr(e, "read"):
                box["error"] += f" {e.read()[:300]!r}"

    thread = threading.Thread(target=capture, daemon=True)
    thread.start()
    time.sleep(1.0)
    errors = send(_http_caller, srv, batches, 1, timeout=120)
    thread.join(timeout=CAPTURE_S + 660)
    if errors:
        raise SmokeFailure("requests inside the capture: " + "; ".join(errors))
    reply = box.get("reply")
    if reply is None:
        raise SmokeFailure(f"capture through the served port gave no trace: {box.get('error', 'no answer')}")
    if reply.get("pid") != owner_pid:
        raise SmokeFailure(f"the capture was taken by pid {reply.get('pid')}, the device owner is {owner_pid}")
    path = trace_reduce.find_xplane(reply["path"])
    traced = trace_reduce.reduce_file(path, (0.0, 3600.0)) if path else None
    if traced is None:
        raise SmokeFailure(f"the capture under {reply['path']} holds no TPU operation")
    log(
        f"  capture through the served port: taken by the device owner (pid {owner_pid}), "
        f"{traced['events']} TPU operations on {traced['devices']}, busy {traced['busy_s']:.6f} s "
        f"of {reply['seconds']:g} s; trace clocks "
        f"{(reply['trace_stop_monotonic_ns'] - reply['trace_start_monotonic_ns']) / 1e9:.3f} s apart"
    )
    return {"tpu_operations": traced["events"], "busy_s": round(traced["busy_s"], 6), "owner_pid": owner_pid}


def run_pass(srv, singles, batches, workers, timeout: float, label: str) -> dict:
    """Send (a) then (b) over HTTP, then over gRPC, scraping around each
    phase. Returns the scrapes and the per-phase source split."""
    phases = [
        ("http single", _http_caller, singles, CONNECTIONS),
        ("http batch", _http_caller, batches, 1),
        ("grpc single", _grpc_caller, singles, CONNECTIONS),
        ("grpc batch", _grpc_caller, batches, 1),
    ]
    before = srv.scrape(workers)
    out = {"before": before, "batch_device": 0, "split": {}}
    prev = before
    for name, make_caller, reqs, conns in phases:
        t0 = time.monotonic()
        errors = send(make_caller, srv, reqs, conns, timeout)
        wall = time.monotonic() - t0
        if errors:
            raise SmokeFailure(f"{label} pass, {name}: " + "; ".join(errors))
        want = sum(r.decisions for r in reqs)
        cur, src = scrape_settled(srv, workers, prev, want)
        d = delta(prev, cur)
        out["split"][name] = src
        if name.endswith("batch"):
            out["batch_device"] += src.get("device", 0)
        log(
            f"  {label} {name}: {len(reqs)} requests, {want} decisions, effects == oracle, "
            f"{wall:.2f} s; source {out['split'][name]}; "
            f"compiles +{msum(d, 'cerbos_tpu_xla_compiles_total'):.0f}, "
            f"flights +{msum(d, 'cerbos_tpu_batcher_batches_total'):.0f}"
        )
        prev = cur
    out["after"] = prev
    return out


# -- one topology -------------------------------------------------------------


AUDIT_ROTATE_MB = 4


def check_call_ids(call_ids: list[str]) -> list[str]:
    """Every reply of a topology names its call: 32 hex digits, and no two the
    same, whichever process answered (a pool's front ends fork after load, and
    each draws its ids from a generator of its own seed: observability.py)."""
    malformed = sum(1 for c in call_ids if not re.fullmatch(r"[0-9a-f]{32}", c))
    repeated = len(call_ids) - len(set(call_ids))
    failures = []
    if malformed:
        failures.append(f"{malformed} of {len(call_ids)} replies carry no call id of 32 hex digits")
    if repeated:
        failures.append(f"{repeated} of {len(call_ids)} replies repeat another reply's call id")
    return failures


def check_codec(final: dict[tuple, float]) -> list[str]:
    """The gRPC listener's codec on the classic traffic: every request read
    and every reply written natively, in whichever process listened (the
    requests come from protobuf's own encoder and the classic template emits
    no rule outputs, so one ``python`` is a codec that lost its way)."""
    failures = []
    for direction in ("request", "reply"):
        took = {p: int(msum(final, "cerbos_tpu_wire_codec_total", dir=direction, path=p)) for p in ("native", "python")}
        if took["python"] or not took["native"]:
            failures.append(f"wire codec, {direction}: {took} (want every gRPC {direction} native)")
    return failures


def check_audit(path: str, final: dict[tuple, float]) -> dict:
    """``--audit``: what the server left in its audit files once it has exited,
    against its own counters at the last scrape (no check is sent after it):
    every line an entry, one entry on disk for each one queued, no call id
    twice, one access entry for each decision entry (and one, naming the
    error, for a call the service did not answer), none dropped and no write
    failed. What the brownout ladder shed (a cold machine compiles its layouts
    inside the first pass, and the compile storm engages ``shed_audit``) is
    the ladder's to decide: reported, not judged. In a pool
    the ``worker`` label says which process queued how many: the front ends
    build and write the entries, all into the one path; the device owner opens
    it and writes nothing."""
    stem, ext = os.path.splitext(path)
    files = sorted(glob.glob(f"{glob.escape(stem)}-*{glob.escape(ext)}")) + [path]
    calls: dict[str, list[str]] = {"decision": [], "access": []}
    broken = 0
    unanswered: set[str] = set()
    for p in files:
        with open(p) as f:
            for line in f:
                try:
                    e = json.loads(line)
                    calls[e["kind"]].append(e["callId"])
                    if "error" in e:
                        unanswered.add(e["callId"])
                except (ValueError, KeyError):
                    broken += 1
    queued = {k: int(msum(final, "cerbos_tpu_audit_entries_total", kind=k, outcome="queued")) for k in calls}
    out = {
        "files": len(files), "bytes": sum(os.path.getsize(p) for p in files), "largest_file": max(os.path.getsize(p) for p in files),
        "entries": {k: len(v) for k, v in calls.items()}, "unanswered": len(unanswered), "queued": queued,
        "queued_by_worker": by_label(
            {k: v for k, v in final.items() if ("outcome", "queued") in k[1]}, "cerbos_tpu_audit_entries_total", "worker"
        ),
        "lost": by_label(final, "cerbos_tpu_audit_lost_total", "reason"),
        "rotations": int(msum(final, "cerbos_tpu_audit_rotations_total")),
    }
    log(f"  audit: {out}")
    failures = []
    if broken:
        failures.append(f"{broken} lines of the audit files are not entries")
    if set(out["lost"]) - {"shed"}:
        failures.append(f"cerbos_tpu_audit_lost_total reads {out['lost']}")
    for kind, ids in calls.items():
        if len(ids) != len(set(ids)):
            failures.append(f"{len(ids) - len(set(ids))} {kind} entries share a call id")
        if len(ids) != queued[kind]:
            failures.append(f"{len(ids)} {kind} entries on disk, {queued[kind]} queued at the last scrape")
    if not out["lost"] and set(calls["decision"]) != set(calls["access"]) - unanswered:
        failures.append("the call ids of the access entries are not those of the decision entries")
    if out["largest_file"] > AUDIT_ROTATE_MB << 20:
        failures.append(f"a file of {out['largest_file']} B, over the {AUDIT_ROTATE_MB} MB it is rotated at")
    if failures:
        raise SmokeFailure("audit: " + "; ".join(failures))
    return out


def check_schema_counters(final: dict[tuple, float], level: str) -> dict:
    """``--schema``: every reply has already been held to the plain reading
    (``served_problem``); this reads what the server says of its own
    validating: the validators it loaded at boot, that every one of them was
    compiled (cerbos_tpu/schema.py) and made the runs, and that the device route
    did validate (the batch-shaped requests) beside the CPU walk's routes."""
    name = "cerbos_tpu_schema_validations_total"
    out = {
        "level": level,
        "validators": {k: int(v) for k, v in by_label(final, "cerbos_tpu_schema_validators", "state").items()},
        "validators_compiled": int(msum(final, "cerbos_tpu_schema_validators_compiled")),
        "runs_by_engine": by_label(final, "cerbos_tpu_schema_validator_runs_total", "engine"),
        "validations_by_route": {k: int(v) for k, v in by_label(final, name, "route").items()},
        "validations_by_outcome": {k: int(v) for k, v in by_label(final, name, "outcome").items()},
        "errors": int(msum(final, "cerbos_tpu_schema_errors_total")),
        "memo": {k: int(v) for k, v in by_label(final, "cerbos_tpu_assemble_memo_total", "result").items()},
    }
    log(f"  schema: {out}")
    failures = []
    if out["validators"].get("loaded", 0) < 2 * MODS or out["validators"].get("failed", 0):
        failures.append(f"schema_validators reads {out['validators']}: the template ships {3 * MODS} schemas and its policies name them all")
    # the template's schemas hold type, properties, required and enum alone: each compiles, in every process
    compiled, loaded = out["validators_compiled"], out["validators"].get("loaded", 0)
    if compiled < 3 * MODS or compiled != loaded:
        failures.append(f"{compiled} of the {loaded} loaded validators are compiled; the template's {3 * MODS} schemas all should be")
    if out["runs_by_engine"].get("generic", 0) or out["runs_by_engine"].get("compiled", 0) <= 0:
        failures.append(f"validator runs by engine read {out['runs_by_engine']}: python-jsonschema should have made none")
    if out["validations_by_route"].get("device", 0) <= 0:
        failures.append("no validation was counted on the device route")
    if out["errors"] <= 0 or out["memo"].get("bypass_validation", 0) <= 0:
        failures.append("no validation error was counted: the sample is not clean, and the replies said so")
    if failures:
        raise SmokeFailure("schema: " + "; ".join(failures))
    return out


def run_topology(name, extra_args, tpu_conf, workers, policy_dir, singles, batches, args) -> dict:
    log(f"== topology {name}: cerbos_tpu.cli server {' '.join(extra_args)} {tpu_conf or ''}")
    audit_path = os.path.join(os.path.dirname(policy_dir), "audit", f"{name}.log") if args.audit else ""
    srv = ServerProc(name, policy_dir, extra_args, tpu_conf, audit_path, args.schema)
    try:
        srv.wait_serving(timeout=300)
        status, _ = srv.status()
        check_platform(status)
        dev = status["device"]
        log(
            f"  device owner pid {dev['pid']}: platform={dev['platform']} "
            f"device_kind={dev['device_kind']!r} count={dev['count']}; xla_cache dir={status['dir']} "
            f"(from {'JAX_COMPILATION_CACHE_DIR' if status['external'] else 'the checkout'}), "
            f"{status['entries_at_enable']} entries at boot; native={srv.native}"
        )
        if srv.native != "true":
            raise SmokeFailure(
                f"the server reports native={srv.native}: cerbos_native did not build or load "
                "(g++ missing?) and the host path degraded to pure Python"
            )
        check_ownership(srv, dev["pid"], pooled=bool(extra_args))

        cold = run_pass(srv, singles, batches, workers, timeout=900, label="cold")
        d = delta(cold["before"], cold["after"])
        _, flight = srv.status()
        compiles = [e for e in flight.get("events", []) if e.get("kind") == "xla_compile"]
        log(
            f"  cold pass: compiles by source {by_label(d, 'cerbos_tpu_xla_compiles_total', 'source')}, "
            f"{msum(d, 'cerbos_tpu_xla_compile_seconds_sum'):.2f} s in XLA; fallbacks by reason "
            f"{by_label(d, 'cerbos_tpu_batcher_oracle_fallbacks_total', 'reason') or 'none'}; "
            f"breaker trips {msum(d, 'cerbos_tpu_breaker_trips_total'):.0f}"
        )
        for e in compiles:
            log(f"    compile {e['layout_key']}: {e['seconds']:.3f} s ({e['source']})")

        checked = None
        for attempt in range(1, CHECKED_ATTEMPTS + 1):
            run = run_pass(srv, singles, batches, workers, timeout=120, label=f"checked#{attempt}")
            batch_decisions = 2 * sum(r.decisions for r in batches)
            failures = check_pass(run["before"], run["after"], batch_decisions, run["batch_device"])
            if not failures:
                checked = run
                break
            log(f"  checked#{attempt}: " + "; ".join(failures))
            if not all(f.startswith("compile:") for f in failures):
                raise SmokeFailure(f"checked pass: " + "; ".join(failures))
            # shape (a) coalesces by timing: a flight size not seen before is
            # a layout not compiled before. Only that may be retried.
        if checked is None:
            raise SmokeFailure(
                f"{CHECKED_ATTEMPTS} passes over the same requests each compiled a new layout: "
                "the layout space does not settle"
            )
        dchk = delta(checked["before"], checked["after"])
        batch_decisions = 2 * sum(r.decisions for r in batches)
        share = checked["batch_device"] / batch_decisions
        log(
            f"  checked pass ok: device share of batch-shaped decisions {share:.4f} "
            f"({batch_decisions - checked['batch_device']:.0f} of {batch_decisions} are the "
            "analyzer's oracle-only residue); 0 compiles, 0 fallbacks, 0 breaker trips"
        )
        log(f"  batch stage p50 s: { {k: round(v, 6) for k, v in stage_p50s(dchk, 'cerbos_tpu_batch_stage_seconds').items()} }")
        log(f"  request stage p50 s: { {k: round(v, 6) for k, v in stage_p50s(dchk, 'cerbos_tpu_request_stage_seconds').items()} }")

        # not under --lanes: every lane compiles its own copy of each layout,
        # so the burst would cost four times the compiles and show the same
        burst = None
        if not args.lanes:
            burst = run_burst(srv, batches, workers, checked["after"])

        capture = run_capture(srv, batches, dev["pid"]) if workers else None

        # the sentinel replays off the request path: give its first check a moment
        for _ in range(100):
            final = srv.scrape(workers)
            if msum(final, "cerbos_tpu_parity_checks_total") >= 1:
                break
            time.sleep(0.1)
        # kept beside the server's stderr: what the verdict was read from
        with open(os.path.join(OUT_DIR, f"{name}.metrics.txt"), "w") as f:
            f.write(srv.last_scrape)
        failures = check_totals(final)
        status, flight = srv.status()
        with open(os.path.join(OUT_DIR, f"{name}.flight.json"), "w") as f:
            json.dump({"jitcache": status, "flight": flight}, f)
        if args.lanes:
            failures += check_lanes(status, flight, args.lanes)
        failures += check_call_ids(srv.call_ids)
        failures += check_codec(final)
        if failures:
            raise SmokeFailure("; ".join(failures))
        log(
            f"  totals: compiles {int(msum(final, 'cerbos_tpu_xla_compiles_total'))} "
            f"{by_label(final, 'cerbos_tpu_xla_compiles_total', 'source')}, "
            f"parity checks {int(msum(final, 'cerbos_tpu_parity_checks_total'))} divergences 0, "
            f"device memory in use {int(msum(final, 'cerbos_tpu_device_memory_bytes_in_use'))} B "
            f"(peak {int(msum(final, 'cerbos_tpu_device_memory_peak_bytes_in_use'))} B)"
        )
        code = srv.stop()
        if code != 0:
            raise SmokeFailure(f"server exit code on SIGTERM: {code} (None = had to be killed)")
        log(f"  server exited 0 on SIGTERM")
        audit = check_audit(audit_path, final) if audit_path else None
        schema = check_schema_counters(final, args.schema) if args.schema else None
        return {
            "device": {"platform": dev["platform"], "kind": dev["device_kind"], "count": dev["count"]},
            "xla_cache": status["dir"],
            # up to the end of the checked pass; the burst's are its own
            "compiles": by_label(checked["after"], "cerbos_tpu_xla_compiles_total", "source"),
            "compile_seconds": round(msum(checked["after"], "cerbos_tpu_xla_compile_seconds_sum"), 3),
            "device_share_batch": round(share, 4),
            "split": checked["split"],
            "call_ids_distinct": len(set(srv.call_ids)),
            "wire_codec": by_label(final, "cerbos_tpu_wire_codec_total", "path"),
            "burst": burst,
            "capture": capture,
            **({"audit": audit} if audit else {}),
            **({"schema": schema} if schema else {}),
            # for run_restart: what this boot built inside requests over its first pass
            "first_pass": {
                "built": int(msum(cold["after"], "cerbos_tpu_jit_cache_misses_total")),
                "loaded": int(msum(final, "cerbos_tpu_xla_preloads_total", outcome="loaded")),
            },
        }
    except BaseException:
        if srv.proc.poll() is None:
            srv.kill()
        log(f"--- last lines of {srv.stderr_path}:\n{srv.stderr_tail()}---")
        raise


RESTART_WALK_S = 180.0  # how long a restarted server's preload walk may take
STORM_THRESHOLD = 8  # compilestats.STORM_THRESHOLD, the server's default: distinct layouts compiled in 120 s


def run_restart(name, extra_args, tpu_conf, workers, policy_dir, singles, batches, first: dict) -> dict:
    """The served phase once more: the same topology booted again on the same
    cache directory, one pass of the same requests. The first boot filed the
    layouts its flights built in the layout manifest beside the compile cache;
    this one must load them ahead of traffic (``xla_preloads_total{outcome=
    "loaded"}`` > 0) and build fewer inside requests than the first did over
    the same pass (``jit_cache_misses_total``: one miss is one layout a flight
    built itself). Where the first boot already found a manifest (an earlier
    call's, kept with the machine's cache) it had little left to build, and
    only the loads are held to."""
    log(f"== topology {name}, restarted on the same cache directory")
    srv = ServerProc(f"{name}-restart", policy_dir, extra_args, tpu_conf)
    try:
        srv.wait_serving(timeout=300)
        status, _ = srv.status()
        check_platform(status)
        log(f"  layout manifest at boot: {status.get('manifest')}")
        run = run_pass(srv, singles, batches, workers, timeout=900, label="restart")
        deadline = time.monotonic() + RESTART_WALK_S
        done = []
        while not done and time.monotonic() < deadline:
            _, flight = srv.status()
            done = [e for e in flight.get("events", []) if e.get("kind") == "xla_preload_done"]
            if not done:
                time.sleep(1.0)
        if not done:
            raise SmokeFailure(f"restart: no xla_preload_done event {RESTART_WALK_S:.0f} s after the pass: the walk did not end")
        final = srv.scrape(workers)
        with open(os.path.join(OUT_DIR, f"{name}-restart.metrics.txt"), "w") as f:
            f.write(srv.last_scrape)
        preloads = by_label(final, "cerbos_tpu_xla_preloads_total", "outcome")
        built = int(msum(run["after"], "cerbos_tpu_jit_cache_misses_total"))
        log(
            f"  restart: preloads {preloads}, {msum(final, 'cerbos_tpu_xla_preload_seconds_sum'):.2f} s in the walk's "
            f"loads ({done[-1]['seconds']:.1f} s wall); layouts built inside requests {built} "
            f"(first boot, same pass: {first['built']}, with {first['loaded']} loaded ahead); compiles by source "
            f"{by_label(final, 'cerbos_tpu_xla_compiles_total', 'source')}"
        )
        failures = []
        if preloads.get("loaded", 0) <= 0:
            failures.append(f'restart: xla_preloads_total{{outcome="loaded"}} is {preloads.get("loaded", 0)}: nothing was loaded ahead of traffic')
        # shape (a) coalesces by timing, so two boots that both loaded ahead differ by a layout
        # or two either way: the count is held to the first boot's only where that one loaded none
        if first["loaded"] == 0 and built >= first["built"]:
            failures.append(
                f"restart: {built} layouts built inside requests, the first boot built {first['built']} "
                "with none loaded ahead: the manifest bought nothing"
            )
        storms = msum(final, "cerbos_tpu_recompile_storms_total")
        if storms > 0 and built < STORM_THRESHOLD:
            failures.append(
                f"restart: recompile_storms_total is {storms:.0f} with {built} layouts built inside requests "
                f"(a storm takes {STORM_THRESHOLD}): the walk fed the storm detector"
            )
        if failures:
            raise SmokeFailure("; ".join(failures))
        code = srv.stop()
        if code != 0:
            raise SmokeFailure(f"restart: server exit code on SIGTERM: {code} (None = had to be killed)")
        return {"preloads": preloads, "built_in_requests": built, "first_built_in_requests": first["built"],
                "walk_s": done[-1]["seconds"]}
    except BaseException:
        if srv.proc.poll() is None:
            srv.kill()
        log(f"--- last lines of {srv.stderr_path}:\n{srv.stderr_tail()}---")
        raise


def check_ownership(srv: ServerProc, owner: int, pooled: bool) -> None:
    """One process per chip: only the pid that reported the device holds
    accelerator device nodes, and in a pool that is a child, not the parent
    (which imported jax and forked)."""
    pids = srv.pids()
    holders = {p: h for p, h in device_holders(pids).items() if h}
    log(f"  processes {pids}; accelerator fds: {holders or 'none visible'}")
    strangers = [p for p in holders if p != owner]
    if strangers:
        raise SmokeFailure(f"processes {strangers} hold the device besides its owner {owner}")
    if owner not in pids:
        raise SmokeFailure(f"device owner pid {owner} is not one of the server's processes {pids}")
    if pooled and owner == srv.proc.pid:
        raise SmokeFailure("the pool's parent process opened the device; it must belong to the batcher child")


def check_lanes(status: dict, flight: dict, lanes: int) -> list[str]:
    """Sharded pool: every lane dispatched device batches, on its own device."""
    failures = []
    per_shard: dict[int, int] = {}
    for rec in flight.get("batches", []):
        if rec.get("layout_key") and rec.get("outcome") == "ok":
            shard = rec.get("shard") or 0
            per_shard[shard] = per_shard.get(shard, 0) + 1
    log(f"  device batches per lane (last {flight.get('capacity')} flights): {dict(sorted(per_shard.items()))}")
    idle = [s for s in range(lanes) if not per_shard.get(s)]
    if idle:
        failures.append(f"lanes {idle} dispatched no device batch")
    mem = status.get("device_memory", [])
    log(f"  per-device memory: {mem}")
    cold = [m["id"] for m in mem if m["peak_bytes_in_use"] <= 0]
    if len(mem) != lanes or cold:
        failures.append(f"expected {lanes} devices with memory in use; devices {cold} never held any (of {len(mem)})")
    return failures


def check_workers_refused(policy_dir: str) -> None:
    """--workers 2 with the device path is two PDPs on one chip: the 2nd
    cannot open it, and the pool must fail at boot saying so."""
    log("== --workers 2 on the device path must fail at boot")
    srv = ServerProc("workers2", policy_dir, ["--workers", "2"], {})
    try:
        try:
            srv.proc.wait(timeout=300)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                "--workers 2 is still up after 300 s: its 2nd worker neither opened the chip nor failed"
            ) from None
        srv.stop()
        tail = srv.stderr_tail(200)
        if srv.proc.returncode == 0 or "--frontends" not in tail:
            raise SmokeFailure(f"--workers 2 exited {srv.proc.returncode} without naming --frontends")
        log(f"  exited {srv.proc.returncode}; stderr names --frontends")
    except BaseException:
        if srv.proc.poll() is None:
            srv.kill()
        log(f"--- last lines of {srv.stderr_path}:\n{srv.stderr_tail()}---")
        raise


def result_line(device: dict) -> str:
    """The last line of stdout, and nothing else on it: what the chip check
    parses. Exactly these keys; the device is what the server's device owner
    read from ``jax.devices()`` at boot."""
    return json.dumps(
        {
            "ok": True,
            "device": {"platform": str(device["platform"]), "kind": str(device["kind"]), "count": int(device["count"])},
        }
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=7, help="request generator seed")
    ap.add_argument(
        "--lanes", type=int, default=0,
        help="builder-run, four-chip host: run ONLY the single-process topology with "
        "engine.tpu.mesh.shards=auto and require device work on this many lanes/devices",
    )
    ap.add_argument(
        "--workers-check", action="store_true",
        help="builder-run: also require that --workers 2 on the device path fails at boot",
    )
    ap.add_argument(
        "--audit", action="store_true",
        help="builder-run: boot both topologies with the audit log on (file backend, access and decision "
        "logs, rotated) and hold what they leave on disk to their own counters",
    )
    ap.add_argument(
        "--schema", choices=("warn", "reject"), default="",
        help="builder-run: boot both topologies with schema.enforcement at this level over the template's own "
        "schemas and hold every reply's validationErrors to benchmarks/tools/schema_check.py (under reject an "
        "input with errors must read EFFECT_DENY for every action)",
    )
    args = ap.parse_args()

    sys.path.insert(0, REPO)
    try:
        import cerbos_tpu  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the cerbos_tpu package is not next to this script ({e})", file=sys.stderr)
        return 3

    t_start = time.monotonic()
    os.makedirs(OUT_DIR, exist_ok=True)
    for key, (value, why) in CONFIG_SET.items():
        log(f"config set: {key}={value} — {why}")
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        policy_dir = os.path.join(work, "policies")
        os.makedirs(policy_dir)
        n_docs = write_policies(policy_dir, MODS)
        singles, batches = build_requests(MODS, args.seed, N_SINGLE, N_BATCH)
        log(
            f"corpus: {n_docs} policy documents ({MODS} mods); requests (seed {args.seed}): "
            f"{len(singles)} single-resource ({sum(r.decisions for r in singles)} decisions), "
            f"{len(batches)} batch-shaped ({sum(len(r.expected) for r in batches)} resources, "
            f"{sum(r.decisions for r in batches)} decisions), each sent over HTTP and gRPC; "
            f"oracle effects computed in {time.monotonic() - t_start:.1f} s"
        )
        if args.schema:
            seen = expect_validation(singles + batches, MODS, args.schema)
            log(f"schema.enforcement {args.schema}: the plain reading expects {seen} over the sample")
        if args.lanes:
            topologies = [("single-shards", [], {"mesh": {"shards": "auto"}}, ())]
        else:
            topologies = [
                ("single", [], {}, ()),
                (
                    "frontends2",
                    ["--frontends", "2"],
                    {"profiler": {"enabled": True, "dir": os.path.join(work, "profiles"), "maxSeconds": CAPTURE_S}},
                    ("fe1", "fe2", "batcher"),
                ),
            ]
        results = {}
        for name, extra, tpu_conf, workers in topologies:
            results[name] = run_topology(name, extra, tpu_conf, workers, policy_dir, singles, batches, args)
            if not args.lanes and not args.audit and not args.schema:
                results[name]["restart"] = run_restart(
                    name, extra, tpu_conf, workers, policy_dir, singles, batches, results[name].pop("first_pass")
                )
        if args.workers_check:
            check_workers_refused(policy_dir)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)

    assert "jax" not in sys.modules, "the smoke's parent imported jax: it would hold the chip"
    log(f"chip_smoke passed in {time.monotonic() - t_start:.0f} s")
    # the observations, for a reader; kept beside the server logs too
    summary = json.dumps({"topologies": results, "claim": None})
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        f.write(summary + "\n")
    log(summary)
    print(result_line(next(iter(results.values()))["device"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
