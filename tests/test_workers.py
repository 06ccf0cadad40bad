"""Multi-process worker pool: SO_REUSEPORT serving, crash restart, shutdown.

Boots the real CLI (``cerbos_tpu.cli server --workers 2``) as a subprocess —
the same entry a production pool uses — and drives it over HTTP.
"""

import json
import os
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

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
          expr: request.resource.attr.public == true
    - actions: ["*"]
      effect: EFFECT_ALLOW
      roles: [admin]
"""

CHECK_BODY = {
    "requestId": "w1",
    "principal": {"id": "alice", "roles": ["user"]},
    "resources": [
        {"actions": ["view", "delete"], "resource": {"kind": "album", "id": "a1", "attr": {"public": True}}}
    ],
}


def _check(port: int, timeout: float = 5.0) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api/check/resources",
        data=json.dumps(CHECK_BODY).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _worker_pids(pool_pid: int) -> list[int]:
    out = subprocess.run(
        ["ps", "-o", "pid=", "--ppid", str(pool_pid)], capture_output=True, text=True
    )
    return [int(p) for p in out.stdout.split()]


@pytest.fixture(scope="module")
def pool(tmp_path_factory):
    policy_dir = tmp_path_factory.mktemp("policies")
    (policy_dir / "album.yaml").write_text(POLICY)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "cerbos_tpu.cli", "server",
            "--workers", "2",
            "--set", f"storage.disk.directory={policy_dir}",
            "--set", "server.httpListenAddr=127.0.0.1:0",
            "--set", "server.grpcListenAddr=127.0.0.1:0",
            "--set", "engine.tpu.enabled=false",
        ],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        env=env,
        cwd=REPO,
    )
    http_port = None
    deadline = time.time() + 60
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        if line.startswith("cerbos-tpu serving:"):
            for tok in line.split():
                if tok.startswith("http="):
                    http_port = int(tok.split("=")[1])
            break
    assert http_port, "pool never announced its ports"
    # wait until a worker actually serves
    deadline = time.time() + 60
    last_err = None
    while time.time() < deadline:
        try:
            _check(http_port)
            break
        except Exception as e:  # noqa: BLE001
            last_err = e
            time.sleep(0.25)
    else:
        proc.terminate()
        raise AssertionError(f"pool never became ready: {last_err}")
    yield proc, http_port
    if proc.poll() is None:
        proc.terminate()
        proc.wait(timeout=15)


def test_pool_serves_decisions(pool):
    proc, port = pool
    for _ in range(10):
        resp = _check(port)
        actions = resp["results"][0]["actions"]
        assert actions["view"] == "EFFECT_ALLOW"
        assert actions["delete"] == "EFFECT_DENY"


def test_pool_has_n_workers(pool):
    proc, port = pool
    assert len(_worker_pids(proc.pid)) == 2


def test_pool_restarts_crashed_worker(pool):
    proc, port = pool
    before = _worker_pids(proc.pid)
    os.kill(before[0], signal.SIGKILL)
    deadline = time.time() + 30
    while time.time() < deadline:
        pids = _worker_pids(proc.pid)
        if len(pids) == 2 and pids != before:
            break
        time.sleep(0.2)
    else:
        raise AssertionError("killed worker was not replaced")
    # the pool keeps serving throughout (the surviving worker + the new one)
    resp = _check(port)
    assert resp["results"][0]["actions"]["view"] == "EFFECT_ALLOW"


def test_pool_shuts_down_cleanly(pool):
    proc, port = pool
    proc.terminate()
    assert proc.wait(timeout=20) == 0


# -- multi-process front door: N front ends + 1 shared batcher ---------------


def _get(port: int, path: str, timeout: float = 5.0):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _batcher_pid(port: int) -> int:
    """The batcher process self-identifies through the routed flight dump."""
    status, body = _get(port, "/_cerbos/debug/flight")
    assert status == 200
    doc = json.loads(body)
    assert doc.get("source") == "batcher", doc
    return int(doc["batcher_pid"])


@pytest.fixture(scope="module")
def frontdoor(tmp_path_factory):
    """Real CLI boot of the PR 6 topology: 2 HTTP front-end processes feeding
    one shared batcher process over the unix ticket queue (numpy device
    backend so the subprocess boots fast and jax-free)."""
    policy_dir = tmp_path_factory.mktemp("policies")
    (policy_dir / "album.yaml").write_text(POLICY)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "cerbos_tpu.cli", "server",
            "--frontends", "2",
            "--set", f"storage.disk.directory={policy_dir}",
            "--set", "server.httpListenAddr=127.0.0.1:0",
            "--set", "server.grpcListenAddr=127.0.0.1:0",
            "--set", "engine.tpu.backend=numpy",
            "--set", "engine.tpu.profiler.enabled=true",
        ],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        env=env,
        cwd=REPO,
    )
    http_port = None
    deadline = time.time() + 60
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        if line.startswith("cerbos-tpu serving:"):
            for tok in line.split():
                if tok.startswith("http="):
                    http_port = int(tok.split("=")[1])
            break
    assert http_port, "front door never announced its ports"
    deadline = time.time() + 60
    last_err = None
    while time.time() < deadline:
        try:
            _check(http_port)
            break
        except Exception as e:  # noqa: BLE001
            last_err = e
            time.sleep(0.25)
    else:
        proc.terminate()
        raise AssertionError(f"front door never became ready: {last_err}")
    yield proc, http_port
    if proc.poll() is None:
        proc.terminate()
        proc.wait(timeout=15)


def test_frontdoor_serves_decisions(frontdoor):
    proc, port = frontdoor
    for _ in range(10):
        resp = _check(port)
        actions = resp["results"][0]["actions"]
        assert actions["view"] == "EFFECT_ALLOW"
        assert actions["delete"] == "EFFECT_DENY"


def test_frontdoor_topology(frontdoor):
    proc, port = frontdoor
    # 2 front ends + 1 batcher
    assert len(_worker_pids(proc.pid)) == 3
    assert _batcher_pid(port) in _worker_pids(proc.pid)


def test_frontdoor_ready_and_worker_labeled_metrics(frontdoor):
    proc, port = frontdoor
    status, body = _get(port, "/_cerbos/ready")
    assert status == 200
    assert json.loads(body)["status"] in ("ready", "degraded")
    # one scrape sees this front end's series AND the batcher process's
    # (ipc queue depth et al), each stamped with its worker identity
    _check(port)
    status, body = _get(port, "/_cerbos/metrics")
    assert status == 200
    text = body.decode()
    assert 'worker="fe' in text
    assert 'worker="batcher"' in text
    assert "cerbos_tpu_ipc_ring_depth" in text
    # the pool's HELLO negotiation granted the shm data plane (the native
    # module is built in this image); the SIGKILL chaos test below therefore
    # exercises the ring transport, not the uds fallback
    from cerbos_tpu import native

    if native.get() is not None:
        assert 'transport="shm"' in text, "front door did not grant shm"


def _handled_by_worker(port: int) -> tuple[dict, str]:
    """One scrape: the CheckResources handlers each process has run."""
    status, body = _get(port, "/_cerbos/metrics")
    assert status == 200
    text = body.decode()
    found = re.findall(r'^cerbos_tpu_request_handler_seconds_count\{worker="([^"]+)"\} (\S+)$', text, re.M)
    return {w: float(v) for w, v in found}, text


def test_frontdoor_one_scrape_holds_the_whole_pool(frontdoor):
    """PR 27: whichever front end the kernel hands a scrape to, it holds the
    series of EVERY front end and of the batcher, rendered for that request,
    so two scrapes subtract even when they land on different siblings."""
    proc, port = frontdoor
    before, _ = _handled_by_worker(port)
    burst = 64
    for _ in range(burst):  # a connection each: the kernel spreads them over both front ends
        _check(port)
    after, text = _handled_by_worker(port)
    assert set(after) == {"fe1", "fe2", "batcher"}, sorted(after)
    assert after.pop("batcher") == 0  # the owner runs no handler: requests finish on the front ends
    assert 'cerbos_tpu_ipc_connections{worker="batcher"} 2' in text
    grew = {w: after[w] - before.get(w, 0.0) for w in after}
    assert sum(grew.values()) == burst, grew
    assert all(n > 0 for n in grew.values()), grew  # sent through two, counted in two
    # the parts of the front door's clock, summed over the pool as the benchmark's readers sum them
    for part in ("validate", "auxdata", "convert", "admit", "span", "enqueue"):
        got = re.findall(
            rf'^cerbos_tpu_request_front_seconds_count\{{worker="fe[12]",part="{part}"\}} (\S+)$', text, re.M
        )
        assert len(got) == 2 and sum(map(float, got)) == sum(after.values()), (part, got)


def test_frontdoor_profile_is_answered_by_the_device_owner(frontdoor):
    """PR 27: a front end forwards ``/_cerbos/debug/profile``. This pool's
    owner runs the numpy backend and holds no JAX device, so what comes back
    through the served port is the OWNER's refusal, not the front end's."""
    proc, port = frontdoor
    status, body = _get(port, "/_cerbos/debug/profile?seconds=0.2", timeout=30)
    assert status == 403, body
    assert "owns no device" in json.loads(body)["error"]
    assert _check(port)["results"][0]["actions"]["view"] == "EFFECT_ALLOW"


def test_frontdoor_batcher_sigkill_midload_loses_zero_requests(frontdoor):
    """The PR's chaos acceptance: SIGKILL the batcher process under live
    traffic — every request settles (front ends fall back to their
    COW-shared oracle), readiness stays live, the supervisor respawns the
    batcher, and the ticket queue re-attaches."""
    proc, port = frontdoor
    victim = _batcher_pid(port)
    results = {"ok": 0, "bad": []}
    stop_at = time.time() + 6.0

    def hammer():
        while time.time() < stop_at:
            try:
                resp = _check(port, timeout=10.0)
                if resp["results"][0]["actions"]["view"] == "EFFECT_ALLOW":
                    results["ok"] += 1
                else:
                    results["bad"].append(resp)
            except Exception as e:  # noqa: BLE001
                results["bad"].append(repr(e))

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(1.0)
    os.kill(victim, signal.SIGKILL)
    # while the batcher is down/respawning, front ends stay live (degraded
    # serves from the oracle) — readiness must NOT flip back to 503
    status, body = _get(port, "/_cerbos/ready")
    assert status == 200
    for t in threads:
        t.join(timeout=30.0)
    assert not results["bad"], f"lost/failed requests: {results['bad'][:5]}"
    assert results["ok"] > 0
    # the supervisor replaced the batcher and the queue re-attached
    deadline = time.time() + 30
    new_pid = None
    while time.time() < deadline:
        try:
            new_pid = _batcher_pid(port)
            if new_pid != victim:
                break
        except AssertionError:
            pass
        time.sleep(0.5)
    assert new_pid is not None and new_pid != victim, "batcher was not respawned"
    status, body = _get(port, "/_cerbos/ready")
    assert status == 200


# -- one process per chip ------------------------------------------------------

# the real CLI with libtpu's lockfile stood in for: the first process to
# "open the chip" (create argv[1]) holds it, every later one cannot. Front
# ends open no device.
_CLI_WITH_ONE_CHIP = """
import os, sys
import cerbos_tpu.bootstrap as bootstrap
from cerbos_tpu import cli
from cerbos_tpu.tpu.jitcache import DeviceInitError

real_initialize = bootstrap.initialize

def initialize(config, **kw):
    if kw.get("role") != "frontend":
        try:
            os.close(os.open(sys.argv[1], os.O_CREAT | os.O_EXCL))
        except FileExistsError:
            raise DeviceInitError("device backend failed to initialize: TPU is already in use")
    return real_initialize(config, **kw)

bootstrap.initialize = initialize
sys.exit(cli.main(sys.argv[2:]))
"""


def _run_cli_with_one_chip(tmp_path, chip_free: bool, topology: list[str]):
    policy_dir = tmp_path / "policies"
    policy_dir.mkdir()
    (policy_dir / "album.yaml").write_text(POLICY)
    lock = tmp_path / "chip.lock"
    if not chip_free:
        lock.touch()
    env = dict(os.environ, PYTHONPATH=REPO)
    t0 = time.monotonic()
    p = subprocess.run(
        [
            sys.executable, "-c", _CLI_WITH_ONE_CHIP, str(lock), "server", *topology,
            "--set", f"storage.disk.directory={policy_dir}",
            "--set", "server.httpListenAddr=127.0.0.1:0",
            "--set", "server.grpcListenAddr=127.0.0.1:0",
            "--set", "engine.tpu.backend=numpy",
        ],
        capture_output=True, text=True, timeout=60, env=env,
    )
    assert time.monotonic() - t0 < 30  # the healthy processes were stopped, not waited out
    return p


def test_pool_fails_at_boot_when_a_worker_cannot_open_the_device(tmp_path):
    """--workers N on the device path is N PDPs on one chip: the one that
    cannot open it must take the pool down with a message naming
    --frontends — not be restarted, and not serve from the oracle."""
    p = _run_cli_with_one_chip(tmp_path, chip_free=True, topology=["--workers", "2"])
    assert p.returncode == 1
    assert "DeviceInitError: device backend failed to initialize: TPU is already in use" in p.stderr
    assert "could not open the device at boot" in p.stderr and "--frontends" in p.stderr
    assert "restarting" not in p.stderr


def test_frontdoor_without_a_device_fails_at_boot_and_gives_no_topology_advice(tmp_path):
    """Under --frontends the batcher is the one device owner: when it cannot
    open the device the cause is the device, and telling the operator to use
    --frontends would hide it."""
    p = _run_cli_with_one_chip(tmp_path, chip_free=False, topology=["--frontends", "2"])
    assert p.returncode == 1
    assert "DeviceInitError: device backend failed to initialize: TPU is already in use" in p.stderr
    assert "worker 0 could not open the device at boot" in p.stderr and "device unavailable" in p.stderr
    assert "--frontends" not in p.stderr
    assert "restarting" not in p.stderr


# -- single process: SIGTERM leaves through the drain --------------------------


def _boot_single(tmp_path, script=None):
    policy_dir = tmp_path / "policies"
    policy_dir.mkdir()
    (policy_dir / "album.yaml").write_text(POLICY)
    args = [
        "server",
        "--set", f"storage.disk.directory={policy_dir}",
        "--set", "server.httpListenAddr=127.0.0.1:0",
        "--set", "server.grpcListenAddr=127.0.0.1:0",
        "--set", "engine.tpu.backend=numpy",
    ]
    launcher = ["-c", script] if script else ["-m", "cerbos_tpu.cli"]
    return subprocess.Popen(
        [sys.executable, *launcher, *args],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=dict(os.environ, PYTHONPATH=REPO),
    )


def test_single_process_exits_0_on_repeated_sigterm(tmp_path):
    """A supervisor that repeats its SIGTERM must not abort the drain: the
    second signal lands while listeners, batcher and device are closing."""
    proc = _boot_single(tmp_path)
    try:
        line = proc.stdout.readline()
        assert line.startswith("cerbos-tpu serving:"), line + proc.stderr.read()
        proc.send_signal(signal.SIGTERM)
        for _ in range(20):  # across the whole drain (grpc grace alone is up to 1 s)
            time.sleep(0.05)
            if proc.poll() is not None:
                break
            proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=30) == 0
        assert "Traceback" not in proc.stderr.read()
    finally:
        if proc.poll() is None:
            proc.kill()


_SIGTERM_DURING_INIT = """
import os, signal, sys
import cerbos_tpu.bootstrap as bootstrap
from cerbos_tpu import cli

real_initialize = bootstrap.initialize

def initialize(config, **kw):
    os.kill(os.getpid(), signal.SIGTERM)  # the supervisor gives up while the table is still building
    return real_initialize(config, **kw)

bootstrap.initialize = initialize
sys.exit(cli.main(sys.argv[1:]))
"""


def test_single_process_sigterm_during_boot_drains_and_exits_0(tmp_path):
    proc = _boot_single(tmp_path, script=_SIGTERM_DURING_INIT)
    out, err = proc.communicate(timeout=60)
    assert proc.returncode == 0, err
    assert "serving:" not in out  # it never opened its listeners
    assert "Traceback" not in err
