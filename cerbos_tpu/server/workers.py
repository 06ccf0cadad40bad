"""Multi-process worker pool: fork-after-load serving.

Behavioral reference: internal/engine/engine.go:74-144 — the reference
saturates its CPUs with a NumCPU+4 goroutine pool behind one listener.
Goroutines have no Python analogue under the GIL, so the equivalent here is
processes: the parent builds the expensive artifacts once (parse → compile →
rule table → lowered device tables, ``bootstrap.prebuild``), calls
``gc.freeze()`` so refcount churn doesn't dirty the shared pages, then forks
N workers. Each worker finishes its own initialization (store watcher, audit
writer, batcher threads — threads must start *after* fork) and binds its own
gRPC + HTTP listeners on the SAME ports with ``SO_REUSEPORT``; the kernel
load-balances accepted connections across workers.

The parent is a supervisor: it restarts crashed workers (preserving the
prebuilt artifacts, so a restart is cheap) and fans SIGTERM/SIGINT out to
the pool for graceful shutdown.
"""

from __future__ import annotations

import gc
import os
import signal
import socket
import sys
import time
from typing import Callable, Optional

from .. import bootclock
from ..util import gctune

_RESTART_LIMIT = 10  # per worker slot; a crash-looping config must not spin forever
_RESTART_WINDOW_S = 60.0
# child exit code for "could not open the device" (sysexits EX_CONFIG): at
# first boot the pool shuts down instead of restarting into the same wall
_EXIT_DEVICE_INIT = 78


def resolve_listen_addr(addr: str) -> str:
    """Resolve ":0" to a concrete ephemeral port for the pool.

    SO_REUSEPORT workers must all bind the SAME port, so a wildcard port is
    chosen once by the parent. The reserving socket is bound with REUSEPORT
    but never listens — bind-only sockets take no part in the kernel's
    accept distribution — and stays open so the port cannot be claimed by
    an unrelated process between worker restarts.

    ``unix:`` addresses are rejected: SO_REUSEPORT does not load-balance
    unix sockets, so a pooled config must use TCP (run workers=1 for a
    unix-socket listener).
    """
    if addr.startswith("unix:"):
        raise ValueError(
            "worker pools need TCP listeners (SO_REUSEPORT does not load-"
            f"balance unix sockets); got {addr!r} — use host:port or workers=1"
        )
    host, _, port = addr.rpartition(":")
    host = host or "0.0.0.0"
    if host.startswith("[") and host.endswith("]"):
        family, bind_host = socket.AF_INET6, host[1:-1]
    else:
        family, bind_host = socket.AF_INET, host
    s = socket.socket(family, socket.SOCK_STREAM)
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
    s.bind((bind_host, int(port)))
    chosen = s.getsockname()[1]
    _reservations.append(s)  # keep alive for the pool's lifetime
    return f"{host}:{chosen}"


_reservations: list[socket.socket] = []


class WorkerPool:
    """Fork N serving workers and supervise them.

    ``worker_main(worker_idx, respawn)`` runs in each child; it must block
    until the process receives SIGTERM (the child's own signal handling) and
    then return for a clean exit. Exceptions exit the child non-zero,
    triggering a supervised restart with ``respawn=True`` — restarted
    workers must NOT reuse boot-time prebuilt state (policies may have
    changed since boot; a stale table would diverge from sibling workers).
    """

    def __init__(
        self,
        n_workers: int,
        worker_main: Callable[[int, bool], None],
        log=None,
        device_init_hint: str = "",
    ):
        self.n = n_workers
        self.worker_main = worker_main
        self.log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
        # what to tell the operator when a worker cannot open the device at
        # first boot, where the topology itself is the likely cause
        self.device_init_hint = device_init_hint
        self._children: dict[int, int] = {}  # pid -> worker idx
        self._restarts: dict[int, list[float]] = {}  # idx -> restart stamps
        self._shutdown = False

    def _spawn(self, idx: int, respawn: bool = False) -> None:
        # SIGTERM stays blocked across the fork until the child has dropped the
        # supervisor's handler: one delivered in between (a pool that shuts
        # down right at boot) would run handle_term IN the child and be lost
        # to it, leaving that worker serving under a parent that waits for it
        signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGTERM})
        pid = os.fork()
        if pid == 0:
            # child: default signal dispositions; worker_main installs its own
            signal.signal(signal.SIGTERM, signal.SIG_DFL)
            signal.signal(signal.SIGINT, signal.SIG_IGN)  # parent fans out SIGTERM
            signal.pthread_sigmask(signal.SIG_UNBLOCK, {signal.SIGTERM})
            try:
                self.worker_main(idx, respawn)
                os._exit(0)
            except BaseException as e:  # noqa: BLE001
                from ..tpu.jitcache import DeviceInitError

                print(f"worker {idx} crashed: {type(e).__name__}: {e}", file=sys.stderr, flush=True)
                os._exit(_EXIT_DEVICE_INIT if isinstance(e, DeviceInitError) else 1)
        self._children[pid] = idx
        signal.pthread_sigmask(signal.SIG_UNBLOCK, {signal.SIGTERM})

    def run(self) -> int:
        """Blocking supervisor loop; returns the pool's exit code."""
        # the prebuilt artifacts are effectively immutable from here on:
        # freeze them out of gc so child refcount updates touch fewer pages
        gc.freeze()

        def handle_term(signum, frame):
            self._shutdown = True
            for pid in list(self._children):
                try:
                    os.kill(pid, signal.SIGTERM)
                except ProcessLookupError:
                    pass

        signal.signal(signal.SIGTERM, handle_term)
        signal.signal(signal.SIGINT, handle_term)

        for i in range(self.n):
            self._spawn(i)
        self.log(f"worker pool: {self.n} workers {sorted(self._children)}")

        exit_code = 0
        while self._children:
            try:
                pid, status = os.wait()
            except ChildProcessError:
                break
            except InterruptedError:
                continue
            idx = self._children.pop(pid, None)
            if idx is None:
                continue
            if self._shutdown:
                continue
            code = os.waitstatus_to_exitcode(status)
            if code == _EXIT_DEVICE_INIT and not self._restarts.get(idx):
                # the device could not be opened at FIRST boot: restarting
                # cannot help, and serving this slot from the oracle would
                # hide the missing device. (A respawn that hits it goes
                # through the normal restart budget: the chip may free up.)
                self.log(
                    f"worker {idx} could not open the device at boot (its backend's error is "
                    f"on the crash line above): device unavailable. {self.device_init_hint}"
                    "Shutting pool down"
                )
                exit_code = 1
                handle_term(signal.SIGTERM, None)
                continue
            stamps = self._restarts.setdefault(idx, [])
            now = time.monotonic()
            stamps[:] = [t for t in stamps if now - t < _RESTART_WINDOW_S] + [now]
            if len(stamps) > _RESTART_LIMIT:
                self.log(f"worker {idx} crash-looping (exit {code}); shutting pool down")
                exit_code = 1
                handle_term(signal.SIGTERM, None)
                continue
            self.log(f"worker {idx} (pid {pid}) exited {code}; restarting")
            self._spawn(idx, respawn=True)
        return exit_code


def run_server_pool(
    config,
    n_workers: int,
    build_server: Callable[..., object],
    use_tpu: Optional[bool] = None,
    announce=None,
    post_fork: Optional[Callable[[], None]] = None,
    post_init: Optional[Callable[[object], None]] = None,
    pre_exit: Optional[Callable[[], None]] = None,
) -> int:
    """Boot a pool of full PDP servers from one prebuilt core.

    ``build_server(core, config, http_addr, grpc_addr, reuse_port)`` must
    return a started-able Server (cli wires admin/authzen/playground the
    same way for 1 or N workers).

    Cross-worker policy propagation: each worker owns a store; mutations
    made through one worker's Admin API reach the others via the shared
    backing medium (disk files / DB rows), so pool mode force-enables the
    disk store's change watcher — without it, siblings would keep serving
    the old policy until restart.
    """
    from ..bootstrap import initialize, prebuild

    server_conf = config.section("server")
    http_addr = resolve_listen_addr(server_conf.get("httpListenAddr", "0.0.0.0:3592"))
    grpc_addr = resolve_listen_addr(server_conf.get("grpcListenAddr", "0.0.0.0:3593"))

    # section() returns a detached {} when the key is absent; write through
    # config.data so the workers' new_store calls see the override
    storage_conf = config.data.setdefault("storage", {})
    if storage_conf.get("driver", "disk") == "disk":
        storage_conf.setdefault("disk", {})["watchForChanges"] = True

    prebuilt = prebuild(config, use_tpu=use_tpu)

    def worker_main(idx: int, respawn: bool) -> None:
        # install the handler BEFORE the (slow) init so a pool-wide SIGTERM
        # during startup still exits through the graceful path
        stop = {"flag": False}

        def on_term(signum, frame):
            stop["flag"] = True

        signal.signal(signal.SIGTERM, on_term)
        if respawn:
            bootclock.begin()  # its own boot, from its fork; the first workers carry on the parent's clock
        if post_fork is not None:
            post_fork()
        # a respawned worker rebuilds from the store: the boot-time prebuilt
        # table may be stale (policies can have changed since the pool came
        # up, and this worker's fresh store snapshot won't re-emit events
        # for already-applied changes)
        core = initialize(config, use_tpu=use_tpu, prebuilt=None if respawn else prebuilt)
        if post_init is not None:
            post_init(core)
        # worker-local tables are built and listeners not yet started: freeze
        # them and pace the collector for the request path (util/gctune —
        # the serving-time analogue of the reference's GOGC handling)
        gctune.tune_for_serving()
        server = build_server(core, config, http_addr, grpc_addr, True, worker_label=f"w{idx}")
        try:
            if not stop["flag"]:
                server.start()
                bootclock.listening()
            while not stop["flag"]:
                time.sleep(0.2)
        finally:
            server.stop()
            core.close()
            if pre_exit is not None:
                pre_exit()

    if announce is not None:
        announce(http_addr, grpc_addr)
    pool = WorkerPool(
        n_workers,
        worker_main,
        device_init_hint=(
            "A chip belongs to ONE process — use --frontends N (one device-owning "
            "batcher) instead of --workers N (a CPU topology). "
        ),
    )
    return pool.run()


def run_frontdoor_pool(
    config,
    n_frontends: int,
    build_server: Callable[..., object],
    use_tpu: Optional[bool] = None,
    announce=None,
    post_fork: Optional[Callable[[], None]] = None,
    post_init: Optional[Callable[[object], None]] = None,
    pre_exit: Optional[Callable[[], None]] = None,
) -> int:
    """Boot the multi-process front door: N HTTP/gRPC front-end processes
    feeding ONE shared batcher/evaluator process over the unix ticket queue
    (`engine/ipc.py`).

    The SO_REUSEPORT pool (`run_server_pool`) multiplies full PDPs — and
    fragments device batches across N evaluators, N jit caches, N breakers.
    This topology splits roles instead: worker slot 0 owns the device (the
    only process that compiles or dispatches), slots 1..N are GIL-light
    request parsers. The parent builds + lowers once and forks, so the rule
    table and lowered tables are COW-shared three ways: the batcher
    evaluates on them, and every front end keeps an oracle fallback over
    the same pages for when the batcher is down, refusing (breaker open,
    quarantine, queue full), or slow.

    Supervision matches the pool: either role is restarted on death. A dead
    batcher does NOT take the pool to 0/N — front ends flip to
    degraded-but-live (oracle serving, `/_cerbos/ready` stays 200) until
    the respawned batcher re-warms and re-attaches.
    """
    from ..bootstrap import build_batcher_ipc, initialize, prebuild
    from ..engine.ipc import default_socket_path

    server_conf = config.section("server")
    http_addr = resolve_listen_addr(server_conf.get("httpListenAddr", "0.0.0.0:3592"))
    grpc_addr = resolve_listen_addr(server_conf.get("grpcListenAddr", "0.0.0.0:3593"))

    storage_conf = config.data.setdefault("storage", {})
    if storage_conf.get("driver", "disk") == "disk":
        storage_conf.setdefault("disk", {})["watchForChanges"] = True
    # the batcher process is the device owner; its Core must carry the
    # cross-request batcher for the ticket queue to feed
    tpu_section = config.data.setdefault("engine", {}).setdefault("tpu", {})
    tpu_section["requestBatching"] = True

    shared_conf = tpu_section.get("sharedBatcher", {}) or {}
    socket_path = default_socket_path(str(shared_conf.get("socketPath", "") or ""))

    prebuilt = prebuild(config, use_tpu=use_tpu)

    def batcher_main(respawn: bool) -> None:
        stop = {"flag": False}

        def on_term(signum, frame):
            stop["flag"] = True

        signal.signal(signal.SIGTERM, on_term)
        if respawn:
            bootclock.begin()  # its own boot, from its fork; the first owner carries on the parent's clock
        if post_fork is not None:
            post_fork()
        core = initialize(config, use_tpu=use_tpu, prebuilt=None if respawn else prebuilt)
        if post_init is not None:
            post_init(core)
        gctune.tune_for_serving()
        ipc_server = build_batcher_ipc(core, socket_path)
        # the pool's boot ends where its device owner takes tickets and serves;
        # a front end's bind, later, is in no series (bootclock.py)
        bootclock.listening()
        try:
            while not stop["flag"]:
                time.sleep(0.2)
        finally:
            ipc_server.close()
            core.close()
            if pre_exit is not None:
                pre_exit()

    def frontend_main(idx: int, respawn: bool) -> None:
        stop = {"flag": False}

        def on_term(signum, frame):
            stop["flag"] = True

        signal.signal(signal.SIGTERM, on_term)
        if post_fork is not None:
            post_fork()
        core = initialize(
            config,
            use_tpu=use_tpu,
            prebuilt=None if respawn else prebuilt,
            role="frontend",
            ipc_socket=socket_path,
            worker_label=f"fe{idx}",
        )
        if post_init is not None:
            post_init(core)
        gctune.tune_for_serving()
        server = build_server(core, config, http_addr, grpc_addr, True, worker_label=f"fe{idx}")
        try:
            if not stop["flag"]:
                server.start()
            while not stop["flag"]:
                time.sleep(0.2)
        finally:
            server.stop()
            core.close()
            if pre_exit is not None:
                pre_exit()

    def worker_main(idx: int, respawn: bool) -> None:
        if idx == 0:
            batcher_main(respawn)
        else:
            frontend_main(idx, respawn)

    if announce is not None:
        announce(http_addr, grpc_addr)
    pool = WorkerPool(n_frontends + 1, worker_main)
    return pool.run()
