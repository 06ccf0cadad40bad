"""Process-crossing ticket queue: N front-end processes → one shared batcher.

The GIL caps a single PDP process at a request rate far below the decision
rate of batched evaluation (PERF.md, A4). An SO_REUSEPORT pool of full PDPs doesn't close the gap either:
each forked worker drives its OWN evaluator, fragmenting batches and
multiplying XLA compiles per process. The fix is topological — many HTTP/gRPC
front-end processes parse and validate traffic, ONE batcher process owns the
device — and this module is the seam between them: a per-worker ticket
queue over a unix domain socket carrying compact check tickets in and packed
effect/meta rows out.

Transport: two interchangeable data planes under one control plane.

- The control plane is always a SOCK_STREAM unix socket, one connection per
  front-end process: HELLO negotiation, status/flight/metrics/slow/pressure
  snapshots, the profiler capture a front end forwards to the device owner
  (PROFILE), the one frame the OWNER originates (SCRAPE: "render your own
  registry", so that a scrape answered by any front end holds every
  process of the pool), and — critically — liveness. A dying peer closes the socket,
  and that close is what fails in-flight tickets instantly and flips the
  front end onto its oracle, whichever data plane carried the tickets.
- ``transport: uds`` (fallback) carries check tickets on that same socket as
  length-prefixed ``marshal`` frames — the kernel socket buffer IS the ring,
  with blocking-read wakeups for free, and it works on pure-Python hosts.
- ``transport: shm`` (default where the native module builds) moves the hot
  frames — CHECK in, RESULT/ERR out — onto a pair of shared-memory byte
  rings (one per direction) with futex wakeups, packed and unpacked by the
  native frame codec (``ticket_pack``/``reply_pack``): no marshal, no
  socket syscall, no intermediate row tuples on the per-request path. The
  front end creates the segment, offers it in HELLO, and the batcher maps
  it or refuses (HELLO_R), so a native-less peer on either end degrades the
  pair to uds automatically.

Not every check crosses. A request of fewer inputs than the owner's
``min_device_batch`` is one the owner would hand to the CPU oracle in a
flight of its own, and every front end holds that oracle. The owner
publishes its committed policy epoch (a generation word, the epoch's number,
the policy set's identity) in each attached segment's descriptor page, and a
front end whose own table has that identity, with no cutover pending,
answers such a request itself on the request's thread
(``RemoteBatcherClient._inline_route``; docs/ROBUSTNESS.md, "What a front
end answers from"). The ``uds`` plane has no shared page and never does.

All padding/stacking of decoded tickets stays on the batcher side via the
evaluator's pooled ``_pad_stack`` staging buffers, so the marshalling cost
the device cares about never leaves the device-owning process.

Fault semantics mirror docs/ROBUSTNESS.md, distributed:

- the batcher's fast-path refusals (breaker open, quarantine hit, dead drain
  loop, full queue) come back as compact ERR frames and the FRONT END serves
  its own COW-shared CPU oracle — the batcher process spends no cycles on
  degraded traffic;
- a dead batcher process settles every in-flight ticket with a connection
  error immediately (no timeout wait); front ends degrade to their oracle and
  a background loop reconnects when the supervisor respawns the batcher;
- per-request deadlines travel as RELATIVE remaining seconds (monotonic
  clocks are not comparable across processes) and re-anchor on arrival.
"""

from __future__ import annotations

import asyncio
import logging
import marshal
import mmap
import os
import socket
import struct
import tempfile
import threading
import time
from collections import deque
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FutureTimeoutError
from typing import Any, Callable, Optional, Sequence

from .. import native
from ..observability import current_span, current_span_context, parse_traceparent
from . import hotrules
from . import types as T
from .admission import OverloadRefused
from .batcher import DeadlineExceeded, _BatchFailed, oracle_walk, route_families
from .budget import (
    FRONT_ENQUEUE,
    POINT_ENQUEUE,
    STAGE_ADMISSION,
    STAGE_EVALUATE,
    STAGE_IPC_ENCODE,
    STAGE_ORACLE,
    STAGE_QUEUE_WAIT,
    Waterfall,
)
from .budget import tracker as budget_tracker
from .rollout import bundle_hash_of

_log = logging.getLogger("cerbos_tpu.engine.ipc")

# -- frame protocol ----------------------------------------------------------

_HDR = struct.Struct("<IBQ")  # payload length, frame type, request id

T_HELLO = 1
T_CHECK = 2
T_RESULT = 3
T_ERR = 4
T_STATUS = 5
T_STATUS_R = 6
T_FLIGHT = 7
T_FLIGHT_R = 8
T_METRICS = 9
T_METRICS_R = 10
T_SLOW = 11
T_SLOW_R = 12
T_PRESSURE = 13
T_PRESSURE_R = 14
T_HELLO_R = 15
T_HOTRULES = 16
T_HOTRULES_R = 17
T_PROFILE = 18    # front end -> owner: run a profiler capture ({"seconds": s})
T_PROFILE_R = 19
T_SCRAPE = 20     # OWNER -> front end: send me your metrics text (the one reverse request)
T_SCRAPE_R = 21
T_OBSERVE = 22    # front end -> owner, no reply: inputs it answered from its own table, sampled for the sentinel's ring

_SCRAPE_WAIT_S = 2.0  # a sibling that has not answered by then is left out of the pooled scrape

_MAX_FRAME = 64 * 1024 * 1024  # a corrupt length must not allocate the moon


class IpcError(Exception):
    """Transport-level failure (framing, codec, connection)."""


class IpcDisconnected(IpcError):
    """The peer went away; in-flight tickets must settle immediately."""


def _send_frame(sock: socket.socket, mtype: int, req_id: int, payload: bytes) -> None:
    sock.sendall(_HDR.pack(len(payload), mtype, req_id) + payload)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise IpcDisconnected("peer closed the ticket queue")
        buf.extend(chunk)
    return bytes(buf)


def _recv_frame(sock: socket.socket) -> tuple[int, int, bytes]:
    length, mtype, req_id = _HDR.unpack(_recv_exact(sock, _HDR.size))
    if length > _MAX_FRAME:
        raise IpcError(f"oversized frame ({length} bytes)")
    return mtype, req_id, _recv_exact(sock, length) if length else b""


# -- shared-memory segment ---------------------------------------------------
#
# One file-backed mmap per front-end connection: a 4 KiB descriptor page
# (magic / version / ring size, and on a cache line of its own the OWNER's
# committed policy epoch: see ``publish_epoch``) followed by two native byte
# rings — tickets toward the batcher (c2s) and replies back (s2c). The FRONT END creates and
# sizes the segment, offers its path in HELLO, and unlinks the name as soon
# as the handshake settles either way: from then on the mapping lives exactly
# as long as the two processes that hold it, and a SIGKILL on either side
# cannot leak a name into /dev/shm.

_SHM_MAGIC = 0x43544652
_SHM_VER = 1
_SHM_HDR = struct.Struct("<IIQ")
# the owner's words, written by the owner alone, each an aligned 64-bit word
# stored and loaded whole through a memoryview cast (``struct.pack_into``
# zeroes its target before it fills it, which a reader in another process
# can see): a generation (odd while a cutover is pending or the words are
# being written), the committed epoch's number, and its policy set's identity
# in two words (``_identity_words``; both zero: none). A segment no owner has
# written reads generation 0 and no identity, which matches nothing.
_SHM_EPOCH_AT = 64
_SHM_EPOCH_WORDS = 4
_NO_IDENTITY = (0, 0)
_RING_HDR_BYTES = 256
_shm_counter = 0


def _identity_words(identity: str) -> tuple[int, int]:
    """A policy set's identity (rollout.bundle_hash_of: 16 hex characters) as
    the descriptor page holds it: two 64-bit words. No identity is
    ``_NO_IDENTITY``, which no front end treats as a match."""
    raw = identity.encode("ascii", "replace")[:16].ljust(16, b"\0")
    return int.from_bytes(raw[:8], "little"), int.from_bytes(raw[8:], "little")


def _align_page(n: int) -> int:
    return (n + 4095) & ~4095


def _shm_dir() -> str:
    return "/dev/shm" if os.path.isdir("/dev/shm") else tempfile.gettempdir()


class _ShmSegment:
    """The mapped segment plus the two ring memoryviews the native kernels
    operate on. ``create`` is the front-end side, ``attach`` the batcher
    side; both hold identical mappings once the HELLO handshake grants shm."""

    def __init__(self, path: str, mm: mmap.mmap, ring_bytes: int):
        self.path = path
        self.mm = mm
        self.ring_bytes = ring_bytes
        span = _align_page(_RING_HDR_BYTES + ring_bytes)
        view = memoryview(mm)
        self._view = view
        self._words = view[_SHM_EPOCH_AT : _SHM_EPOCH_AT + 8 * _SHM_EPOCH_WORDS].cast("Q")
        self.c2s = view[4096 : 4096 + _RING_HDR_BYTES + ring_bytes]
        self.s2c = view[4096 + span : 4096 + span + _RING_HDR_BYTES + ring_bytes]

    @classmethod
    def create(cls, name_hint: str, ring_bytes: int) -> "_ShmSegment":
        global _shm_counter
        _shm_counter += 1
        nat = native.get()
        if nat is None:
            raise IpcError("native module unavailable")
        path = os.path.join(
            _shm_dir(), f"cerbos-tpu-ring-{os.getpid()}-{_shm_counter}-{name_hint}"
        )
        span = _align_page(_RING_HDR_BYTES + ring_bytes)
        total = 4096 + 2 * span
        fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_RDWR, 0o600)
        try:
            os.ftruncate(fd, total)
            mm = mmap.mmap(fd, total)
        except BaseException:
            os.close(fd)
            try:
                os.unlink(path)
            except OSError:
                pass
            raise
        os.close(fd)
        _SHM_HDR.pack_into(mm, 0, _SHM_MAGIC, _SHM_VER, ring_bytes)
        seg = cls(path, mm, ring_bytes)
        nat.ring_init(seg.c2s)
        nat.ring_init(seg.s2c)
        return seg

    @classmethod
    def attach(cls, path: str) -> "_ShmSegment":
        if native.get() is None:
            raise IpcError("native module unavailable")
        fd = os.open(path, os.O_RDWR)
        try:
            size = os.fstat(fd).st_size
            mm = mmap.mmap(fd, size)
        finally:
            os.close(fd)
        magic, ver, ring_bytes = _SHM_HDR.unpack_from(mm, 0)
        span = _align_page(_RING_HDR_BYTES + ring_bytes)
        if magic != _SHM_MAGIC or ver != _SHM_VER or size != 4096 + 2 * span:
            mm.close()
            raise IpcError(f"not a cerbos-tpu ring segment: {path}")
        return cls(path, mm, ring_bytes)

    def unlink(self) -> None:
        try:
            os.unlink(self.path)
        except OSError:
            pass

    def publish_epoch(self, pending: bool, number: int, identity: str) -> None:
        """The owner's side of the descriptor page: which policy epoch its
        tickets are answered from. A sequence lock with one writer: the
        generation goes odd, the words change, and it goes even again unless a
        cutover is ``pending``, in which case it stays odd until the call that
        publishes the committed epoch. Each word is one aligned store, and the
        stores reach the other process in program order (x86); a reader that
        saw an odd generation, or two different ones around its read, has read
        nothing. Raises ValueError on a segment that is closed."""
        w = self._words
        odd = w[0] | 1
        w[0] = odd
        w[1] = number
        w[2], w[3] = _identity_words(identity)
        if not pending:
            w[0] = odd + 1

    def read_epoch(self) -> Optional[tuple[int, tuple[int, int]]]:
        """The front end's side: the number of the owner's committed epoch and
        its identity (``_identity_words``), or None while a cutover is pending
        (or the words moved under the read). Five loads of shared memory, no
        system call."""
        w = self._words
        gen = w[0]
        if gen & 1:
            return None
        number, identity = w[1], (w[2], w[3])
        return (number, identity) if w[0] == gen else None

    def close(self) -> None:
        try:
            self._words.release()
            self.c2s.release()
            self.s2c.release()
            self._view.release()
            self.mm.close()
        except (BufferError, ValueError, OSError):
            pass


# -- ticket codec ------------------------------------------------------------
#
# CheckInput/CheckOutput → plain tuples marshal can swallow. Attribute values
# were already normalized (structpb double semantics) at the front end's
# ingestion, so decode reconstructs the dataclasses via __new__ and skips
# __post_init__ — re-normalizing on the batcher would double that work.


def encode_inputs(inputs: Sequence[T.CheckInput]) -> list:
    rows = []
    for i in inputs:
        p, r = i.principal, i.resource
        rows.append(
            (
                i.request_id,
                (p.id, list(p.roles or ()), p.attr, p.policy_version, p.scope),
                (r.kind, r.id, r.attr, r.policy_version, r.scope),
                list(i.actions or ()),
                i.aux_data.jwt if i.aux_data is not None else None,
            )
        )
    return rows


def decode_inputs(rows: list) -> list[T.CheckInput]:
    out = []
    for request_id, prow, rrow, actions, jwt in rows:
        p = T.Principal.__new__(T.Principal)
        p.id, p.roles, p.attr, p.policy_version, p.scope = prow
        r = T.Resource.__new__(T.Resource)
        r.kind, r.id, r.attr, r.policy_version, r.scope = rrow
        aux = None
        if jwt is not None:
            aux = T.AuxData.__new__(T.AuxData)
            aux.jwt = jwt
        inp = T.CheckInput.__new__(T.CheckInput)
        inp.request_id, inp.principal, inp.resource = request_id, p, r
        inp.actions, inp.aux_data = actions, aux
        out.append(inp)
    return out


def encode_outputs(outputs: Sequence[T.CheckOutput]) -> list:
    rows = []
    for o in outputs:
        rows.append(
            (
                o.request_id,
                o.resource_id,
                [
                    (a, ae.effect, ae.policy, ae.scope, ae.matched_rule, ae.rule_row_id, ae.source)
                    for a, ae in o.actions.items()
                ],
                list(o.effective_derived_roles),
                [(v.path, v.message, v.source) for v in o.validation_errors],
                [(e.src, e.action, e.val, e.error) for e in o.outputs],
                o.effective_policies,
            )
        )
    return rows


def decode_outputs(rows: list) -> list[T.CheckOutput]:
    out = []
    for request_id, resource_id, actions, edr, verrs, oents, epols in rows:
        out.append(
            T.CheckOutput(
                request_id=request_id,
                resource_id=resource_id,
                actions={
                    a: T.ActionEffect(
                        effect=e, policy=pol, scope=sc,
                        matched_rule=rule, rule_row_id=row, source=src,
                    )
                    for a, e, pol, sc, rule, row, src in actions
                },
                effective_derived_roles=list(edr),
                validation_errors=[
                    T.ValidationError(path=p, message=m, source=s) for p, m, s in verrs
                ],
                outputs=[
                    T.OutputEntry(src=src, action=act, val=val, error=err)
                    for src, act, val, err in oents
                ],
                effective_policies=epols,
            )
        )
    return out


# -- batcher-side server -----------------------------------------------------


class _ConnWriter:
    """Per-connection outbound queue + writer thread: reply encoding and
    socket writes never run on the batcher's drain loop (future callbacks
    fire there) or block the reader."""

    def __init__(self, sock: socket.socket, name: str):
        self._sock = sock
        self._queue: deque = deque()
        self._cond = threading.Condition()
        self._closed = False
        self._thread = threading.Thread(target=self._loop, daemon=True, name=name)
        self._thread.start()

    def send(self, mtype: int, req_id: int, encode: Callable[[], bytes]) -> None:
        with self._cond:
            if self._closed:
                return
            self._queue.append((mtype, req_id, encode))
            self._cond.notify()

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify()

    def _loop(self) -> None:
        while True:
            with self._cond:
                while not self._queue and not self._closed:
                    self._cond.wait()
                if not self._queue and self._closed:
                    return
                mtype, req_id, encode = self._queue.popleft()
            try:
                _send_frame(self._sock, mtype, req_id, encode())
            except Exception:  # noqa: BLE001  (dead peer: drop replies, reader cleans up)
                self.close()
                return


class _ShmWriter:
    """The shm counterpart of ``_ConnWriter``: reply encoding (native
    ``reply_pack``) and ring pushes happen on this thread, never on the
    batcher's drain loop, and the single thread keeps the s2c ring SPSC no
    matter how many device lanes settle futures concurrently. A full ring
    gets a bounded space-futex wait; a consumer that stays gone past the
    budget costs a dropped reply (the front end times out onto its oracle
    exactly as for a wedged uds socket)."""

    def __init__(self, seg: _ShmSegment, name: str, on_frame=None, on_drop=None):
        self._seg = seg
        self._on_frame = on_frame
        self._on_drop = on_drop
        self._queue: deque = deque()
        self._cond = threading.Condition()
        self._closed = False
        self._thread = threading.Thread(target=self._loop, daemon=True, name=name)
        self._thread.start()

    def send(self, mtype: int, req_id: int, encode: Callable[[], bytes]) -> None:
        with self._cond:
            if self._closed:
                return
            self._queue.append((mtype, req_id, encode))
            self._cond.notify()

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify()
        nat = native.get()
        if nat is not None:
            try:
                nat.ring_wake(self._seg.s2c, 1)  # unblock a space wait
            except (ValueError, OSError):
                pass

    def _loop(self) -> None:
        nat = native.get()
        mv = self._seg.s2c
        while True:
            with self._cond:
                while not self._queue and not self._closed:
                    self._cond.wait()
                if self._closed:
                    return
                mtype, req_id, encode = self._queue.popleft()
            try:
                payload = encode()
            except Exception:  # noqa: BLE001  (unpackable reply: front end times out → oracle)
                continue
            pushed = False
            try:
                for _ in range(20):  # ~1s of space waits before dropping
                    seq = nat.ring_seq(mv, 1)
                    if nat.ring_push(mv, mtype, req_id, payload):
                        pushed = True
                        break
                    if self._closed:
                        return
                    nat.ring_wait(mv, 1, seq, 50)
            except (ValueError, OSError):
                return  # segment gone mid-teardown
            if pushed:
                if self._on_frame is not None:
                    self._on_frame(len(payload))
            elif self._on_drop is not None:
                self._on_drop()


class BatcherIpcServer:
    """The device-owning process's end of the ticket queue.

    Listens on a unix socket; each front-end process holds one connection.
    CHECK tickets decode into the shared ``BatchingEvaluator.check_async``
    queue (the same drain loop, breaker, quarantine, and deadline machinery
    as the single-process path); control frames serve the batcher's
    readiness snapshot, flight-recorder dump, and metrics text so the
    front ends can re-export them (docs/OBSERVABILITY.md).
    """

    def __init__(
        self,
        socket_path: str,
        batcher: Any,
        readiness: Optional[Callable[[], dict]] = None,
        max_outstanding: int = 4096,
        faults: Optional[dict] = None,
        transport: str = "shm",
        sentinel: Any = None,
    ):
        self.socket_path = socket_path
        self.batcher = batcher
        self.readiness = readiness
        # the parity sentinel whose ring of recent inputs the rollout gate
        # replays: front ends send it a sample of what they answer themselves
        self.sentinel = sentinel
        self.max_outstanding = max(1, int(max_outstanding))
        self.faults = dict(faults or {})
        # the transport this server is WILLING to grant; a front end still
        # has to offer a segment, and either side without the native module
        # degrades the pair to uds
        self.transport = transport if transport in ("shm", "uds") else "shm"
        self._listener: Optional[socket.socket] = None
        self._conns: list[socket.socket] = []
        # attached front ends' control writers by connection (registered at
        # HELLO), and the SCRAPE requests this side has in the air
        self._peers: dict[socket.socket, _ConnWriter] = {}
        self._scrapes: dict[int, Future] = {}
        self._scrape_id = 0
        self._lock = threading.Lock()
        # what every attached segment's descriptor page says of the policy
        # epoch (``publish_epoch``): pending, number, identity. Pending and
        # nameless until the rollout controller's first word.
        self._epoch_lock = threading.Lock()
        self._epoch_words: tuple[bool, int, str] = (True, 0, "")
        self._segs: list[_ShmSegment] = []
        self._outstanding = 0
        self._out_by = {"uds": 0, "shm": 0}
        self._checks_seen = 0
        self._stop = False
        self.stats = {
            "connections": 0,
            "checks": 0,
            "rejected_full": 0,
            "wedged_drops": 0,
            "shm_conns": 0,
            "reply_drops": 0,
        }
        self._init_metrics()

    def _init_metrics(self) -> None:
        from ..observability import metrics

        reg = metrics()
        self.m_depth = reg.gauge_vec(
            "cerbos_tpu_ipc_ring_depth",
            "check tickets accepted from front ends and not yet answered",
            label="transport",
            track_max=True,
        )
        self._g_depth = {t: self.m_depth.labels(t) for t in ("uds", "shm")}
        self.m_full = reg.counter_vec(
            "cerbos_tpu_ipc_full_total",
            "tickets refused because the shared batcher queue or ring was full (front end served its oracle)",
            label="transport",
        )
        self.m_frame_bytes = reg.histogram_vec(
            "cerbos_tpu_ipc_frame_bytes",
            "check/reply frame payload sizes crossing the ticket queue",
            label=("transport", "dir"),
            buckets=[64, 128, 256, 512, 1024, 4096, 16384, 65536, 1 << 20],
        )
        self.m_enqueue = reg.histogram_vec(
            "cerbos_tpu_ipc_enqueue_seconds",
            "ticket decode + batcher enqueue latency on the batcher process, per front-end worker",
            label="worker",
            buckets=[0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.05],
        )
        self.m_conns = reg.gauge(
            "cerbos_tpu_ipc_connections", "front-end processes currently attached"
        )

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> None:
        try:
            os.unlink(self.socket_path)
        except OSError:
            pass
        listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        listener.bind(self.socket_path)
        listener.listen(64)
        self._listener = listener
        threading.Thread(target=self._accept_loop, daemon=True, name="ipc-accept").start()

    def close(self) -> None:
        self._stop = True
        if self._listener is not None:
            try:
                self._listener.close()
            except OSError:
                pass
        with self._lock:
            conns = list(self._conns)
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        try:
            os.unlink(self.socket_path)
        except OSError:
            pass

    def _accept_loop(self) -> None:
        while not self._stop:
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return
            with self._lock:
                self._conns.append(conn)
            self.stats["connections"] += 1
            self.m_conns.set(len(self._conns))
            threading.Thread(
                target=self._serve_conn, args=(conn,), daemon=True, name="ipc-conn"
            ).start()

    # -- per-connection protocol --------------------------------------------

    def _serve_conn(self, conn: socket.socket) -> None:
        conn.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1 << 20)
        writer = _ConnWriter(conn, "ipc-writer")
        worker = "?"
        seg: Optional[_ShmSegment] = None
        shm_writer: Optional[_ShmWriter] = None
        shm_stop = threading.Event()
        try:
            while True:
                mtype, req_id, payload = _recv_frame(conn)
                if mtype == T_HELLO:
                    hello = marshal.loads(payload)
                    worker = str(hello.get("worker", "?"))
                    grant = "uds"
                    if (
                        seg is None
                        and self.transport == "shm"
                        and hello.get("transport") == "shm"
                        and hello.get("shm_path")
                        and native.get() is not None
                    ):
                        try:
                            seg = _ShmSegment.attach(str(hello["shm_path"]))
                            self._adopt_segment(seg)
                            grant = "shm"
                        except (IpcError, OSError, ValueError, struct.error):
                            seg = None
                    if seg is not None:
                        self.stats["shm_conns"] += 1
                        shm_writer = _ShmWriter(
                            seg,
                            "ipc-shm-writer",
                            on_frame=lambda n: self.m_frame_bytes.observe(("shm", "out"), n),
                            on_drop=self._count_reply_drop,
                        )
                        threading.Thread(
                            target=self._shm_serve_loop,
                            args=(worker, seg, shm_writer, shm_stop),
                            daemon=True,
                            name="ipc-shm-serve",
                        ).start()
                    # HELLO_R must be the first frame back on this connection:
                    # the client blocks on it before sending any traffic, so
                    # the writer queue is empty here by construction
                    writer.send(T_HELLO_R, req_id, lambda r=self._hello_reply(grant): marshal.dumps(r))
                    with self._lock:
                        self._peers[conn] = writer
                elif mtype == T_CHECK:
                    self._handle_check(worker, req_id, payload, writer)
                elif mtype == T_OBSERVE:
                    self._handle_observe(payload)
                elif mtype == T_STATUS:
                    snap = self._status_snapshot()
                    writer.send(T_STATUS_R, req_id, lambda s=snap: marshal.dumps(s))
                elif mtype == T_FLIGHT:
                    dump = self._flight_snapshot()
                    writer.send(T_FLIGHT_R, req_id, lambda d=dump: marshal.dumps(d))
                elif mtype == T_METRICS:
                    # the whole pool but the asker: gathered on a thread of
                    # its own, this one keeps reading tickets
                    threading.Thread(
                        target=self._pool_metrics,
                        args=(conn, req_id, writer),
                        daemon=True,
                        name="ipc-pool-scrape",
                    ).start()
                elif mtype == T_SCRAPE_R:
                    with self._lock:
                        fut = self._scrapes.pop(req_id, None)
                    if fut is not None:
                        fut.set_result(payload)
                elif mtype == T_PROFILE:
                    # seconds long: a thread of its own, so tickets and status
                    # frames of this front end keep flowing meanwhile
                    threading.Thread(
                        target=self._run_profile,
                        args=(req_id, payload, writer),
                        daemon=True,
                        name="ipc-profile",
                    ).start()
                elif mtype == T_SLOW:
                    dump = self._slow_snapshot(payload)
                    writer.send(T_SLOW_R, req_id, lambda d=dump: marshal.dumps(d))
                elif mtype == T_PRESSURE:
                    snap = self._pressure_snapshot()
                    writer.send(T_PRESSURE_R, req_id, lambda s=snap: marshal.dumps(s))
                elif mtype == T_HOTRULES:
                    snap = self._hotrules_snapshot(payload)
                    writer.send(T_HOTRULES_R, req_id, lambda s=snap: marshal.dumps(s))
        except (IpcError, OSError, EOFError, ValueError, TypeError):
            pass
        finally:
            writer.close()
            shm_stop.set()
            if shm_writer is not None:
                shm_writer.close()
            if seg is not None:
                with self._epoch_lock:
                    if seg in self._segs:
                        self._segs.remove(seg)
                nat = native.get()
                if nat is not None:
                    try:
                        nat.ring_wake(seg.c2s, 0)  # unblock the shm serve loop
                    except (ValueError, OSError):
                        pass
            with self._lock:
                if conn in self._conns:
                    self._conns.remove(conn)
                self._peers.pop(conn, None)
            self.m_conns.set(len(self._conns))
            try:
                conn.close()
            except OSError:
                pass

    def _count_reply_drop(self) -> None:
        self.stats["reply_drops"] += 1

    # -- the committed epoch, where a front end reads it per request ---------

    def publish_epoch(self, epoch: Any) -> None:
        """The rollout controller's cutover hook (``RolloutController.on_cutover``),
        and its first word at boot. ``None``: a cutover is pending, from before
        the drain barrier is requested; every front end takes the ticket route
        from its next request on. An epoch: it is committed, its subscribers
        have run; a front end whose own table has its identity may answer
        requests under ``min_device_batch`` itself again. Writes every attached
        segment's descriptor page; never per request."""
        with self._epoch_lock:
            if epoch is None:
                self._epoch_words = (True, *self._epoch_words[1:])
            elif epoch.number is None:  # an epoch with no number names no table: nothing to match
                self._epoch_words = (False, 0, "")
            else:
                self._epoch_words = (False, int(epoch.number), str(epoch.bundle_hash or ""))
            for seg in self._segs:
                try:
                    seg.publish_epoch(*self._epoch_words)
                except ValueError:
                    pass  # closed under us: its connection's teardown takes it off the list

    def _adopt_segment(self, seg: _ShmSegment) -> None:
        with self._epoch_lock:
            seg.publish_epoch(*self._epoch_words)
            self._segs.append(seg)

    def _hello_reply(self, grant: str) -> dict:
        """What a front end learns once per attach, beside the plane: the
        owner's ``min_device_batch`` (a request under it is one the owner
        would hand to the CPU oracle; 0 = never answer one yourself: no shared
        page over uds, or an evaluator with no such floor) and the sentinel's
        sample rate, at which it reports what it answered itself."""
        lanes = getattr(self.batcher, "shards", None) or [self.batcher]
        floor = min(int(getattr(getattr(b, "evaluator", None), "min_device_batch", 0) or 0) for b in lanes)
        sentinel = self.sentinel
        rate = float(sentinel.sample_rate) if sentinel is not None and sentinel.enabled else 0.0
        return {"transport": grant, "min_device_batch": floor if grant == "shm" else 0, "sample_rate": rate}

    def _handle_observe(self, payload: bytes) -> None:
        """Inputs a front end answered from its own table, already sampled
        there at the sentinel's rate: straight into the ring the rollout gate
        replays. No reply, no flight, no route count."""
        sentinel = self.sentinel
        if sentinel is None:
            return
        try:
            sentinel.remember(decode_inputs(marshal.loads(payload)))
        except Exception:  # noqa: BLE001 — a malformed sample is dropped
            pass

    def _shm_serve_loop(
        self,
        worker: str,
        seg: _ShmSegment,
        writer: _ShmWriter,
        stop: threading.Event,
    ) -> None:
        """Ticket consumer for one front end's c2s ring. The socket reader
        (`_serve_conn`) owns lifecycle: when the connection drops it sets
        ``stop`` and wakes the ring, and THIS loop must release its
        memoryview references before the segment closes under it — hence
        the stop checks on both sides of the pop."""
        nat = native.get()
        mv = seg.c2s
        try:
            while not stop.is_set():
                seq = nat.ring_seq(mv, 0)
                item = nat.ring_pop(mv)
                if item is None:
                    nat.ring_wait(mv, 0, seq, 200)
                    continue
                mtype, req_id, payload = item
                if stop.is_set():
                    return
                if mtype == T_CHECK:
                    self._handle_check(worker, req_id, payload, writer, transport="shm")
        except (ValueError, OSError):
            return  # segment torn down mid-pop
        finally:
            seg.close()

    def _wedged(self) -> bool:
        wedge_after = self.faults.get("ipc_wedge_after")
        if wedge_after is None:
            return False
        return self._checks_seen > int(wedge_after)

    def _handle_check(
        self,
        worker: str,
        req_id: int,
        payload: bytes,
        writer: Any,
        transport: str = "uds",
    ) -> None:
        t0 = time.perf_counter()
        self._checks_seen += 1
        self.stats["checks"] += 1
        self.m_frame_bytes.observe((transport, "in"), len(payload))
        if self._wedged():
            # simulated wedged ring (engine/faults.py ipc_wedge_after): the
            # ticket is swallowed whichever transport carried it; the front
            # end times out onto its oracle
            self.stats["wedged_drops"] += 1
            return
        if transport == "shm":
            # shm ERR payloads are the raw utf-8 reason (no codec at all);
            # outbound sizes are observed by the _ShmWriter push loop
            def err(reason: str) -> Callable[[], bytes]:
                return lambda r=str(reason): r.encode()

        else:

            def err(reason: str) -> Callable[[], bytes]:
                return lambda r=reason: self._sized("uds", marshal.dumps(r))

        try:
            if transport == "shm":
                nat = native.get()
                deadline_rel, traceparent, inputs, carry = nat.ticket_unpack(
                    payload, T.Principal, T.Resource, T.AuxData, T.CheckInput
                )
            else:
                decoded = marshal.loads(payload)
                deadline_rel, traceparent, rows = decoded[0], decoded[1], decoded[2]
                # 4th element: latency-budget carry spec (age, attributed) —
                # absent from pre-waterfall front ends, None when disabled
                carry = decoded[3] if len(decoded) > 3 else None
                inputs = decode_inputs(rows)
        except Exception:  # noqa: BLE001
            writer.send(T_ERR, req_id, err("codec"))
            return
        with self._lock:
            if self._outstanding >= self.max_outstanding:
                full = True
            else:
                full = False
                self._outstanding += 1
                self._out_by[transport] += 1
                depth = self._out_by[transport]
        if full:
            # counted ONCE per pool, in the front end that receives this ERR
            # (RemoteBatcherClient incs its m_full on the remote-origin
            # reason): a merged scrape across the worker pool must not see
            # the same refusal from both sides of the socket
            self.stats["rejected_full"] += 1
            writer.send(T_ERR, req_id, err("ipc_full"))
            return
        self._g_depth[transport].set(depth)
        deadline = time.monotonic() + deadline_rel if deadline_rel is not None else None
        ctx = parse_traceparent(traceparent) if traceparent else None
        # 3rd carry element: the admission priority class (absent from
        # pre-overload front ends; (None, None, pclass) when the waterfall
        # is off but a class rides along)
        pclass = None
        if carry is not None and len(carry) > 2:
            pclass = str(carry[2]) if carry[2] else None
            carry = carry[:2] if carry[0] is not None else None
        # rebuild the waterfall from the carried relative spec; the
        # unattributed remainder (encode + ring/socket + decode) books as
        # transit
        wf = budget_tracker().resume(
            carry, trace_id=getattr(ctx, "trace_id", "") or "", deadline=deadline
        )
        fut = self.batcher.check_async(
            inputs, deadline=deadline, ctx=ctx, wf=wf, pclass=pclass
        )
        self.m_enqueue.observe(worker, time.perf_counter() - t0)

        def settle(f: Future) -> None:
            with self._lock:
                self._outstanding -= 1
                self._out_by[transport] -= 1
                depth = self._out_by[transport]
            self._g_depth[transport].set(depth)
            try:
                outs = f.result()
            except DeadlineExceeded:
                writer.send(T_ERR, req_id, err("deadline"))
            except _BatchFailed as e:
                writer.send(T_ERR, req_id, err(e.reason))
            except BaseException as e:  # noqa: BLE001
                writer.send(T_ERR, req_id, err(f"batch_error:{type(e).__name__}"))
            else:
                # reply spec is snapshotted here (the drain thread is done
                # with the record); writer-queue time lands in the front
                # end's ipc_return residual. Encode runs on the writer
                # thread, not here (the callback fires on the batcher drain
                # loop, which must stay hot).
                spec = wf.reply_spec() if wf is not None else None
                if transport == "shm":
                    writer.send(
                        T_RESULT,
                        req_id,
                        lambda o=outs, s=spec: native.get().reply_pack(o, s),
                    )
                else:
                    writer.send(
                        T_RESULT,
                        req_id,
                        lambda o=outs, s=spec: self._sized(
                            "uds", marshal.dumps((encode_outputs(o), s))
                        ),
                    )

        fut.add_done_callback(settle)

    def _sized(self, transport: str, data: bytes) -> bytes:
        self.m_frame_bytes.observe((transport, "out"), len(data))
        return data

    def _status_snapshot(self) -> dict:
        snap: dict = {"pid": os.getpid()}
        if self.readiness is not None:
            try:
                snap.update(self.readiness())
            except Exception:  # noqa: BLE001
                snap.setdefault("status", "ready")
        else:
            snap["status"] = "ready"
        health = getattr(self.batcher, "health", None)
        if health is not None:
            snap["breaker"] = health.state
        stats = getattr(self.batcher, "stats", None)
        if isinstance(stats, dict):
            snap["batcher_stats"] = dict(stats)
        snap["ipc"] = dict(self.stats)
        return snap

    def _flight_snapshot(self) -> dict:
        from .flight import recorder

        out = {"flight": recorder().dump(), "pid": os.getpid()}
        try:
            from ..tpu import jitcache

            out["jitcache"] = jitcache.status()
        except Exception:  # noqa: BLE001
            pass
        return out

    def _pool_metrics(self, asker: socket.socket, req_id: int, writer: _ConnWriter) -> None:
        """One scrape of the whole pool for the front end that asked: this
        process's registry as ``worker="batcher"`` and the text every OTHER
        attached front end renders now (each labels its own series), merged.
        A sibling that does not answer within ``_SCRAPE_WAIT_S`` is left out."""
        from ..observability import merge_metrics_texts, metrics, relabel_metrics_text

        asked: list[tuple[int, Future]] = []
        with self._lock:
            for conn, peer in self._peers.items():
                if conn is asker:
                    continue
                self._scrape_id += 1
                fut: Future = Future()
                self._scrapes[self._scrape_id] = fut
                asked.append((self._scrape_id, fut))
                peer.send(T_SCRAPE, self._scrape_id, lambda: b"")
        texts = [relabel_metrics_text(metrics().render(), "worker", "batcher")]
        until = time.monotonic() + _SCRAPE_WAIT_S
        for scrape_id, fut in asked:
            try:
                texts.append(fut.result(timeout=max(0.0, until - time.monotonic())).decode())
            except (FutureTimeoutError, TimeoutError, UnicodeDecodeError):
                with self._lock:
                    self._scrapes.pop(scrape_id, None)
        merged = merge_metrics_texts(*texts)
        writer.send(T_METRICS_R, req_id, lambda t=merged: t.encode())

    def _run_profile(self, req_id: int, payload: bytes, writer: _ConnWriter) -> None:
        """A front end's ``/_cerbos/debug/profile``: the capture runs HERE,
        in the process that owns the device. The reply is what the local
        handler would answer, plus this process's pid, or the error with the
        kind the front end maps to a status."""
        from ..tpu import profiler

        try:
            seconds = float((marshal.loads(payload) or {}).get("seconds", 2.0))
            out = {"artifact": {**profiler.capture(seconds), "pid": os.getpid()}}
        except profiler.ProfilerBusy as e:
            out = {"kind": "busy", "error": str(e)}
        except profiler.ProfilerDisabled as e:
            out = {"kind": "disabled", "error": str(e)}
        except (ValueError, TypeError, EOFError) as e:
            out = {"kind": "invalid", "error": str(e)}
        except Exception as e:  # noqa: BLE001 — the front end must get an answer, not a timeout
            out = {"kind": "failed", "error": f"{type(e).__name__}: {e}"}
        writer.send(T_PROFILE_R, req_id, lambda o=out: marshal.dumps(o))

    def _slow_snapshot(self, payload: bytes) -> dict:
        """Slow-request ring dump for `/_cerbos/debug/slow` on a front end
        (the ring lives here, where requests actually settle)."""
        shard = None
        try:
            args = marshal.loads(payload) if payload else {}
            if isinstance(args, dict) and args.get("shard") is not None:
                shard = int(args["shard"])
        except Exception:  # noqa: BLE001
            pass
        out = budget_tracker().slow_dump(shard=shard)
        out["pid"] = os.getpid()
        return out

    def _pressure_snapshot(self) -> dict:
        from .pressure import monitor

        try:
            out = monitor().sample()
        except Exception:  # noqa: BLE001
            out = {"score": 0.0, "components": {}}
        out["pid"] = os.getpid()
        return out

    def _hotrules_snapshot(self, payload: bytes) -> dict:
        """Hot-rule heatmap for `/_cerbos/debug/hotrules` on a front end:
        the hit array aggregates in this (batcher) process, where decisions
        settle; rule labels resolve against the batcher's current table."""
        from .hotrules import recorder as hotrule_recorder

        k = 20
        try:
            args = marshal.loads(payload) if payload else {}
            if isinstance(args, dict) and args.get("k"):
                k = int(args["k"])
        except Exception:  # noqa: BLE001
            pass
        rt = getattr(getattr(self.batcher, "evaluator", None), "rule_table", None)
        out = hotrule_recorder().snapshot(k=k, rule_table=rt)
        out["pid"] = os.getpid()
        return out


# -- front-end client --------------------------------------------------------


class RemoteBatcherClient:
    """``Engine.check()``-compatible evaluator that forwards to the shared
    batcher process, with the PR 3 degradation ladder preserved end to end:
    deadline propagation (as relative remaining time), ERR fast paths and
    timeouts falling back to this process's COW-shared CPU oracle, and a
    background reconnect loop so a respawned batcher picks traffic back up
    without restarting the front end.

    A request of fewer inputs than the owner's ``min_device_batch`` is one the
    owner would hand to the CPU oracle in a flight of its own. This process
    holds that oracle, so it answers such a request itself, on the request's
    own thread, when it can SEE that the answer is the owner's: attached over
    shm, and the descriptor page says that no cutover is pending and that the
    owner's committed policy set has the identity of the local table
    (``_inline_route``). No ticket, no ring crossing, no flight. On any doubt
    (uds, detached, a cutover pending, a bundle the owner refused or rolled
    back, a watcher that lags on either side) the ticket route is taken as
    before. ``cerbos_tpu_batcher_checks_total{route="inline"}`` counts them.

    Also exposes ``check_await`` — the asyncio-native path the HTTP front
    end uses to await tickets directly on the event loop, with no
    thread-pool hop per request (the single biggest per-call overhead the
    multi-process front door removes on small hosts).
    """

    supports_deadline = True
    supports_waterfall = True
    supports_pclass = True

    def __init__(
        self,
        socket_path: str,
        rule_table: Any,
        schema_mgr: Any = None,
        params: Optional[T.EvalParams] = None,
        request_timeout_s: float = 30.0,
        worker_label: str = "fe",
        status_poll_s: float = 0.5,
        connect_retry_s: float = 0.25,
        transport: str = "shm",
        ring_kib: int = 1024,
    ):
        self.socket_path = socket_path
        self.schema_mgr = schema_mgr
        self.params = params or T.EvalParams()
        self.refresh_table(rule_table)
        # learnt at each attach (HELLO_R) and forgotten at each detach: the
        # owner's min_device_batch, 0 unless shm was granted, and the rate at
        # which answers given here are sampled for the owner's sentinel
        self._inline_under = 0
        self._sample_rate = 0.0
        self._sample_acc = 0.0
        self._observed: deque = deque(maxlen=256)
        self.request_timeout = request_timeout_s
        self.worker_label = worker_label
        self.status_poll_s = status_poll_s
        self.connect_retry_s = connect_retry_s
        # requested transport; the ACTIVE one is renegotiated per attach
        # (native module present on both ends, server willing) and visible
        # as .transport (/_cerbos/debug/transport reports it)
        self.transport_requested = transport if transport in ("shm", "uds") else "shm"
        self.ring_bytes = max(64 * 1024, int(ring_kib) * 1024)
        self._transport_active = "uds"
        self._shm: Optional[_ShmSegment] = None
        self._sock: Optional[socket.socket] = None
        self._send_lock = threading.Lock()
        self._plock = threading.Lock()
        self._pending: dict[int, Future] = {}
        self._next_id = 0
        self._connected = threading.Event()
        self._ever_ready = False
        self._last_status: Optional[dict] = None
        self._stop = False
        # what this process answers the owner's SCRAPE with; the HTTP server
        # installs its own scrape body (the registry and the service's
        # counters, labelled), until then the registry alone
        self.local_metrics_text: Callable[[], str] = self._registry_text
        self.stats = {
            "oracle_fallbacks": 0,
            "inline": 0,
            "reconnects": 0,
            "checks": 0,
            "enc_ns": 0,
            "enc_frames": 0,
            "dec_ns": 0,
            "dec_frames": 0,
            "ring_full": 0,
        }
        self._init_metrics()
        self._conn_thread = threading.Thread(
            target=self._connection_loop, daemon=True, name="ipc-client"
        )
        self._conn_thread.start()
        self._status_thread = threading.Thread(
            target=self._status_loop, daemon=True, name="ipc-client-status"
        )
        self._status_thread.start()

    @property
    def transport(self) -> str:
        """The data plane actually carrying tickets right now."""
        return self._transport_active if self._connected.is_set() else "none"

    def _init_metrics(self) -> None:
        from ..observability import metrics

        reg = metrics()
        self.m_rtt = reg.histogram_vec(
            "cerbos_tpu_ipc_client_rtt_seconds",
            "front-end round trip through the shared batcher (encode to decode)",
            label="transport",
            buckets=[0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.5, 1.0],
        )
        self.m_reconnects = reg.counter_vec(
            "cerbos_tpu_ipc_client_reconnects_total",
            "times the front end (re)attached to the shared batcher, by granted transport",
            label="transport",
        )
        # shares the server's family name, but ALL full refusals are counted
        # here: local ring-full pushes directly, and batcher queue-full
        # refusals when their remote-origin "ipc_full" ERR lands. One
        # decisions view per worker — a merged scrape never double-counts a
        # refusal that crossed the socket
        self.m_full = reg.counter_vec(
            "cerbos_tpu_ipc_full_total",
            "tickets refused because the shared batcher queue or ring was full (front end served its oracle)",
            label="transport",
        )
        # same family the in-process batcher exports, so existing fallback
        # dashboards keep working against front-end processes
        self.m_fallbacks = reg.counter_vec(
            "cerbos_tpu_batcher_oracle_fallbacks_total",
            "requests served from the CPU oracle instead of the device path, by reason",
            label="reason",
        )
        # what a request answered here with no ticket moves, as one answered
        # with no flight does in a single process (this process is shard 0)
        self.m_checks, stages = route_families(reg)
        self._m_oracle_stage = stages.labels(("oracle", "0"))
        # rollout visibility (engine/rollout.py): the batcher's committed
        # epoch as observed from this front end, and how long each cutover
        # took to become visible here — the "bounded, measured skew window"
        # the epoch design promises. Same family names the device-owning
        # process exports, so a merged scrape tells the fleet-wide story.
        self.m_policy_epoch = reg.gauge(
            "cerbos_tpu_policy_epoch",
            "policy epoch currently serving (monotone except across a rollback)",
        )
        self.m_epoch_skew = reg.gauge(
            "cerbos_tpu_policy_epoch_skew_seconds",
            "delay between the batcher committing a policy epoch and this front end observing it",
        )
        self._epoch_seen: Optional[int] = None

    # -- connection management ----------------------------------------------

    def _connection_loop(self) -> None:
        while not self._stop:
            # until the FIRST attach succeeds, retry fast: at boot the
            # batcher's listen() and this loop race, and a front end that
            # loses by a millisecond must not serve warming 503s for a
            # full steady-state retry period after its HTTP listener opens
            retry_s = self.connect_retry_s if self.stats["reconnects"] else min(
                0.025, self.connect_retry_s
            )
            try:
                sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
                sock.connect(self.socket_path)
            except OSError:
                try:
                    sock.close()
                except OSError:
                    pass
                time.sleep(retry_s)
                continue
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1 << 20)
            seg: Optional[_ShmSegment] = None
            hello = {"worker": self.worker_label, "pid": os.getpid()}
            if self.transport_requested == "shm" and native.get() is not None:
                try:
                    seg = _ShmSegment.create(self.worker_label, self.ring_bytes)
                    hello.update(
                        {"transport": "shm", "shm_path": seg.path, "ring_bytes": self.ring_bytes}
                    )
                except (IpcError, OSError):
                    seg = None  # no /dev/shm headroom etc.: run uds
            granted = "uds"
            try:
                _send_frame(sock, T_HELLO, 0, marshal.dumps(hello))
                # synchronous handshake: HELLO_R is the first frame the
                # server sends on a connection, so a blocking read here
                # races nothing — and no traffic may enter either plane
                # until the grant decides which one carries it
                sock.settimeout(5.0)
                try:
                    mtype, _, payload = _recv_frame(sock)
                finally:
                    sock.settimeout(None)
                reply: dict = {}
                if mtype == T_HELLO_R:
                    reply = marshal.loads(payload)
                    granted = str(reply.get("transport", "uds"))
            except (IpcError, OSError, socket.timeout, ValueError, TypeError, EOFError):
                if seg is not None:
                    seg.unlink()
                    seg.close()
                try:
                    sock.close()
                except OSError:
                    pass
                time.sleep(retry_s)
                continue
            if seg is not None:
                # the name has served its purpose: both ends hold the
                # mapping (or the grant fell back) — unlink so a SIGKILL on
                # either side cannot leak segments into /dev/shm
                seg.unlink()
                if granted != "shm":
                    seg.close()
                    seg = None
            shm_stop = threading.Event()
            shm_thread: Optional[threading.Thread] = None
            if seg is not None:
                shm_thread = threading.Thread(
                    target=self._shm_read_loop,
                    args=(seg, shm_stop),
                    daemon=True,
                    name="ipc-client-shm",
                )
            self._shm = seg
            self._transport_active = "shm" if seg is not None else "uds"
            self._inline_under = int(reply.get("min_device_batch", 0) or 0) if seg is not None else 0
            self._sample_rate = float(reply.get("sample_rate", 0.0) or 0.0)
            self._sock = sock
            if shm_thread is not None:
                shm_thread.start()
            self._connected.set()
            self.stats["reconnects"] += 1
            self.m_reconnects.inc(self._transport_active)
            _log.info(
                "attached to shared batcher at %s (transport=%s)",
                self.socket_path,
                self._transport_active,
            )
            try:
                self._read_loop(sock)
            except (IpcError, OSError):
                pass
            finally:
                self._inline_under = 0
                self._connected.clear()
                self._sock = None
                self._shm = None
                shm_stop.set()
                if seg is not None:
                    nat = native.get()
                    if nat is not None:
                        try:
                            nat.ring_wake(seg.s2c, 0)  # unblock the shm reader
                        except (ValueError, OSError):
                            pass
                    if shm_thread is not None:
                        shm_thread.join(timeout=2.0)
                    seg.close()
                try:
                    sock.close()
                except OSError:
                    pass
                self._fail_all_pending(IpcDisconnected("shared batcher connection lost"))
                if not self._stop:
                    _log.warning(
                        "shared batcher connection lost; serving from the CPU oracle "
                        "until it returns"
                    )
            time.sleep(self.connect_retry_s)

    def _read_loop(self, sock: socket.socket) -> None:
        while True:
            mtype, req_id, payload = _recv_frame(sock)
            self._settle_frame(mtype, req_id, payload)

    def _shm_read_loop(self, seg: _ShmSegment, stop: threading.Event) -> None:
        """Reply consumer for the s2c ring: pops RESULT/ERR frames and
        settles the matching futures, exactly as ``_read_loop`` does for
        socket frames. Liveness still belongs to the socket — a dead
        batcher is noticed there, and the connection loop wakes this thread
        to exit before closing the segment under it."""
        nat = native.get()
        mv = seg.s2c
        try:
            while not stop.is_set():
                seq = nat.ring_seq(mv, 0)
                item = nat.ring_pop(mv)
                if item is None:
                    nat.ring_wait(mv, 0, seq, 200)
                    continue
                self._settle_frame(*item)
        except (ValueError, OSError):
            return  # segment torn down mid-pop

    def _registry_text(self) -> str:
        from ..observability import metrics, relabel_metrics_text

        return relabel_metrics_text(metrics().render(), "worker", self.worker_label)

    def _answer_scrape(self, req_id: int) -> None:
        try:
            self._send(T_SCRAPE_R, req_id, self.local_metrics_text().encode())
        except Exception:  # noqa: BLE001 — the owner leaves a silent sibling out
            pass

    def _settle_frame(self, mtype: int, req_id: int, payload: bytes) -> None:
        if mtype == T_SCRAPE:
            # the owner's request, numbered by the owner: rendered off the
            # reader thread, which also carries check replies on uds
            threading.Thread(
                target=self._answer_scrape, args=(req_id,), daemon=True, name="ipc-client-scrape"
            ).start()
            return
        with self._plock:
            fut = self._pending.pop(req_id, None)
        if fut is None:
            return  # abandoned (timed-out) ticket: drop the late reply
        try:
            if fut.set_running_or_notify_cancel():
                fut.set_result((mtype, payload))
        except Exception:  # noqa: BLE001
            pass

    def _fail_all_pending(self, err: Exception) -> None:
        with self._plock:
            pending, self._pending = self._pending, {}
        for fut in pending.values():
            try:
                if fut.set_running_or_notify_cancel():
                    fut.set_exception(err)
            except Exception:  # noqa: BLE001
                pass

    def _status_loop(self) -> None:
        while not self._stop:
            if not self._connected.is_set():
                # block on the attach event rather than sleeping a full
                # steady-state period: front-end readiness hinges on the
                # first status frame, so a boot-order race between the
                # batcher's listen() and this loop must not cost 500ms
                self._connected.wait(timeout=self.status_poll_s)
                if self._stop:
                    return
            if self._connected.is_set():
                try:
                    mtype, payload = self._request(T_STATUS, b"", timeout=2.0)
                    if mtype == T_STATUS_R:
                        snap = marshal.loads(payload)
                        self._last_status = snap
                        if snap.get("status") in ("ready", "degraded"):
                            self._ever_ready = True
                        self._note_epoch(snap)
                    self._send_observed()
                except (IpcError, OSError, FutureTimeoutError, TimeoutError, ValueError):
                    pass
            # fast cadence until the first frame lands, configured cadence after
            time.sleep(self.status_poll_s if self._last_status is not None else 0.05)

    def _note_epoch(self, snap: dict) -> None:
        """Track the batcher's committed epoch as it becomes visible here.
        The skew gauge is measured on the observing edge: wall-clock now
        minus the commit timestamp the STATUS frame carried — bounded by
        the status poll cadence plus the cutover itself."""
        epoch = snap.get("policy_epoch")
        if epoch is None:
            return
        try:
            self.m_policy_epoch.set(epoch)
            if epoch != self._epoch_seen:
                self._epoch_seen = epoch
                committed_at = snap.get("policy_epoch_committed_at")
                if committed_at:
                    self.m_epoch_skew.set(max(0.0, time.time() - float(committed_at)))
        except Exception:  # noqa: BLE001 — status bookkeeping never kills the poll loop
            pass

    # -- raw request/response -----------------------------------------------

    def _register(self) -> tuple[int, Future]:
        with self._plock:
            self._next_id += 1
            req_id = self._next_id
            fut: Future = Future()
            self._pending[req_id] = fut
        return req_id, fut

    def _unregister(self, req_id: int) -> None:
        with self._plock:
            self._pending.pop(req_id, None)

    def _send(self, mtype: int, req_id: int, payload: bytes) -> None:
        sock = self._sock
        if sock is None:
            raise IpcDisconnected("not attached to the shared batcher")
        try:
            with self._send_lock:
                _send_frame(sock, mtype, req_id, payload)
        except OSError as e:
            raise IpcDisconnected(str(e)) from e

    def _request(self, mtype: int, payload: bytes, timeout: float) -> tuple[int, bytes]:
        req_id, fut = self._register()
        try:
            self._send(mtype, req_id, payload)
            return fut.result(timeout=timeout)
        finally:
            self._unregister(req_id)

    # -- the local oracle: a fallback, or the owner's own answer --------------

    def _serve_oracle(
        self,
        inputs: Sequence[T.CheckInput],
        params: Optional[T.EvalParams],
        reason: str,
        wf: Optional[Waterfall] = None,
    ) -> list[T.CheckOutput]:
        self.stats["oracle_fallbacks"] += 1
        self.m_fallbacks.inc(reason)
        if wf is not None:
            wf.note_fallback(reason)
        # the owner refused or is gone, so nothing says whose epoch the local
        # table is: a fallback stamps None, honestly unversioned
        rt = self.rule_table
        out = oracle_walk(rt, getattr(rt, "policy_epoch", None), inputs, params or self.params, self.schema_mgr)
        if wf is not None:
            # books everything since the last mark — including any dead
            # round trip that preceded the fallback — as the oracle stage
            wf.mark(STAGE_ORACLE)
        return out

    def _inline_route(self, n_inputs: int) -> Optional[tuple[Any, int]]:
        """The rule, for ``check`` and ``check_await`` alike: ``(table,
        epoch)`` when a request of ``n_inputs`` is to be answered here, from
        ``table``, as the owner's epoch number ``epoch``; None for the ticket
        route. A request at or over the owner's ``min_device_batch`` pays one
        comparison; one under it five loads of the shared page. The page is
        read AFTER the local pair, so a reload here between the two reads
        costs a ticket, never an answer from a table the page did not name."""
        if n_inputs >= self._inline_under:
            return None  # also: over uds, while detached, before the first attach (0)
        seg = self._shm
        rt, identity = self._local
        if seg is None or identity == _NO_IDENTITY:
            return None
        try:
            committed = seg.read_epoch()
        except ValueError:
            return None  # the segment closed under the read: detached
        if committed is None or committed[1] != identity:
            return None
        return rt, committed[0]

    def _serve_inline(
        self,
        route: tuple[Any, int],
        inputs: Sequence[T.CheckInput],
        params: Optional[T.EvalParams],
        deadline: Optional[float],
        wf: Optional[Waterfall],
    ) -> list[T.CheckOutput]:
        """Answer a request here, on its own thread: what the owner's flight
        of this request alone would have answered (its committed table's
        identity is this one's), booked as ``BatchingEvaluator._serve_inline``
        books it in a single process. Not a fallback, and counted as none."""
        rt, epoch = route
        self.stats["inline"] += 1
        self.m_checks.inc("inline")
        span = current_span()
        if span is not None and span.name == "engine.Check":
            span.set_attribute("path", "inline")  # it said "device" on the way in
        if wf is not None:
            wf.shard = 0
            wf.mark(STAGE_ADMISSION, part=FRONT_ENQUEUE)
        if deadline is not None:
            budget_tracker().observe_budget(POINT_ENQUEUE, deadline - time.monotonic(), shard=0)
        if wf is not None:
            wf.mark(STAGE_QUEUE_WAIT)  # a true wait of nothing
        t0 = time.perf_counter()
        out = oracle_walk(rt, epoch, inputs, params or self.params, self.schema_mgr, route="inline")
        self._m_oracle_stage.observe(time.perf_counter() - t0)
        hotrules.recorder().observe(out)  # this process's decision_source_total; the owner's heatmap sees none of it
        if wf is not None:
            wf.mark(STAGE_EVALUATE)
        # the owner's sentinel never sees these inputs, and the rollout gate
        # replays its ring before a cutover: sample here, at its rate, and let
        # the status thread carry the sample over (``_send_observed``)
        self._sample_acc += self._sample_rate
        if self._sample_acc >= 1.0:
            self._sample_acc -= 1.0
            self._observed.append(inputs)
        return out

    def _send_observed(self) -> None:
        """From the status thread, after the answers were handed back: what
        ``_serve_inline`` sampled since the last poll, as ONE frame that
        expects no reply."""
        sampled: list[T.CheckInput] = []
        while self._observed:
            sampled.extend(self._observed.popleft())
        if sampled:
            self._send(T_OBSERVE, 0, marshal.dumps(encode_inputs(sampled)))

    # -- check surface ------------------------------------------------------

    @staticmethod
    def _carry_spec(
        wf: Optional[Waterfall], pclass: Optional[str]
    ) -> Optional[tuple]:
        """The ticket's carry: (age, attributed) from the waterfall, plus
        the admission priority class as an optional 3rd element. A class
        with no waterfall ships ``(None, None, pclass)`` — the batcher reads
        the class and resumes no budget record."""
        carry = wf.carry() if wf is not None else None
        if pclass:
            return (carry[0], carry[1], pclass) if carry is not None else (None, None, pclass)
        return carry

    def _encode_check(
        self,
        inputs: Sequence[T.CheckInput],
        deadline: Optional[float],
        wf: Optional[Waterfall] = None,
        transport: str = "uds",
        pclass: Optional[str] = None,
    ) -> Optional[bytes]:
        deadline_rel = None
        if deadline is not None:
            deadline_rel = max(0.0, deadline - time.monotonic())
        ctx = current_span_context()
        traceparent = ctx.to_traceparent() if ctx is not None else ""
        try:
            if transport == "shm":
                # the native pack runs AFTER the carry snapshot (the carry
                # rides inside the frame), so its cost books into the
                # batcher's transit stage — transit genuinely is
                # "pack + ring + unpack" on this plane, and ipc_encode
                # shrinks to the admission bookkeeping above it
                if wf is not None:
                    wf.mark(STAGE_IPC_ENCODE, part=FRONT_ENQUEUE)
                carry = self._carry_spec(wf, pclass)
                t0 = time.perf_counter_ns()
                frame = native.get().ticket_pack(inputs, deadline_rel, traceparent, carry)
                self.stats["enc_ns"] += time.perf_counter_ns() - t0
                self.stats["enc_frames"] += 1
                return frame
            t0 = time.perf_counter_ns()
            rows = encode_inputs(inputs)
            # book the row conversion as ipc_encode BEFORE taking the carry
            # spec, so the batcher's transit stage (age-at-receipt minus
            # attributed-at-carry) covers only marshal + socket + decode and
            # never double-counts the encode
            if wf is not None:
                wf.mark(STAGE_IPC_ENCODE, part=FRONT_ENQUEUE)
            carry = self._carry_spec(wf, pclass)
            frame = marshal.dumps((deadline_rel, traceparent, rows, carry))
            self.stats["enc_ns"] += time.perf_counter_ns() - t0
            self.stats["enc_frames"] += 1
            return frame
        except Exception:  # noqa: BLE001  (unencodable attr value: oracle handles it)
            return None

    def _send_check(self, req_id: int, payload: bytes, transport: str) -> bool:
        """Dispatch one CHECK ticket on the active plane. Returns False when
        the shm ring stayed full through the bounded space wait — the caller
        serves its oracle under the ``ipc_full`` reason, the same degradation
        the batcher signals for a full admission queue."""
        if transport != "shm":
            self._send(T_CHECK, req_id, payload)
            return True
        seg = self._shm
        nat = native.get()
        if seg is None or nat is None:
            raise IpcDisconnected("shm plane detached")
        try:
            mv = seg.c2s
            for _ in range(3):  # immediate try + two bounded space waits
                seq = nat.ring_seq(mv, 1)
                if nat.ring_push(mv, T_CHECK, req_id, payload):
                    return True
                nat.ring_wait(mv, 1, seq, 50)
            self.stats["ring_full"] += 1
            self.m_full.inc("shm")
            return False
        except ValueError:
            # frame larger than the ring, or segment torn down mid-push:
            # either way this ticket cannot cross — the oracle serves it
            self.stats["ring_full"] += 1
            self.m_full.inc("shm")
            return False

    def _wait_budget(self, deadline: Optional[float]) -> float:
        wait = self.request_timeout
        if deadline is not None:
            wait = min(wait, max(0.0, deadline - time.monotonic()))
        return wait

    def _decode_result(
        self, payload: bytes, wf: Optional[Waterfall], transport: str = "uds"
    ) -> list[T.CheckOutput]:
        # the batcher evaluated this ticket under its current epoch; the
        # nearest view this side of the socket is the last STATUS frame —
        # exact to within the measured skew window the epoch gauges expose
        last = self._last_status
        if last is not None:
            T.set_current_epoch(last.get("policy_epoch"))
        t0 = time.perf_counter_ns()
        if transport == "shm":
            outs, spec = native.get().reply_unpack(
                payload, T.CheckOutput, T.ActionEffect, T.ValidationError, T.OutputEntry
            )
        else:
            obj = marshal.loads(payload)
            if isinstance(obj, tuple):
                rows, spec = obj
            else:  # pre-waterfall batcher: bare row list
                rows, spec = obj, None
            outs = decode_outputs(rows)
        self.stats["dec_ns"] += time.perf_counter_ns() - t0
        self.stats["dec_frames"] += 1
        if wf is not None and spec is not None:
            try:
                wf.splice_reply(spec)
            except Exception:  # noqa: BLE001 — a malformed spec must not fail the request
                pass
        return outs

    @staticmethod
    def _err_reason(payload: bytes, transport: str) -> str:
        if transport == "shm":
            return payload.decode("utf-8", "replace")
        return str(marshal.loads(payload))

    def _remote_err(
        self, reason: str, transport: str, pclass: Optional[str]
    ) -> None:
        """Shared handling for remote-origin ERR reasons that do NOT fall
        back to the oracle. ``queue_budget`` is a true refusal — the lane's
        queue budget said no — raised to the server layer, which maps it to
        429/RESOURCE_EXHAUSTED and books ``outcome=refused`` in THIS
        worker's decisions view. A remote ``ipc_full`` counts against the
        shared family here (the batcher only tallies its internal
        ``rejected_full`` stat)."""
        if reason == "deadline":
            raise DeadlineExceeded("request deadline expired in the shared batcher")
        if reason == "queue_budget":
            raise OverloadRefused(pclass or "default", "queue_budget", retry_after=0.1)
        if reason == "ipc_full":
            self.m_full.inc(transport)

    def _settle_reply(
        self,
        mtype: int,
        payload: bytes,
        inputs: Sequence[T.CheckInput],
        params: Optional[T.EvalParams],
        wf: Optional[Waterfall] = None,
        transport: str = "uds",
        pclass: Optional[str] = None,
    ) -> list[T.CheckOutput]:
        if mtype == T_RESULT:
            return self._decode_result(payload, wf, transport)
        if mtype == T_ERR:
            reason = self._err_reason(payload, transport)
            self._remote_err(reason, transport, pclass)
            return self._serve_oracle(inputs, params, reason, wf=wf)
        return self._serve_oracle(inputs, params, "protocol", wf=wf)

    def check(
        self,
        inputs: Sequence[T.CheckInput],
        params: Optional[T.EvalParams] = None,
        deadline: Optional[float] = None,
        wf: Optional[Waterfall] = None,
        pclass: Optional[str] = None,
    ) -> list[T.CheckOutput]:
        if deadline is not None and time.monotonic() >= deadline:
            raise DeadlineExceeded("request deadline expired before evaluation")
        self.stats["checks"] += 1
        route = self._inline_route(len(inputs))
        if route is not None:
            return self._serve_inline(route, inputs, params, deadline, wf)
        if not self._connected.is_set():
            return self._serve_oracle(inputs, params, "batcher_down", wf=wf)
        # pin the plane for this request: a reconnect mid-flight may
        # renegotiate, but reconnects also fail every pending future, so a
        # reply never arrives encoded for a different transport than pinned
        tr = self._transport_active
        payload = self._encode_check(inputs, deadline, wf=wf, transport=tr, pclass=pclass)
        if payload is None:
            return self._serve_oracle(inputs, params, "codec", wf=wf)
        t0 = time.perf_counter()
        req_id, fut = self._register()
        try:
            if not self._send_check(req_id, payload, tr):
                self._unregister(req_id)
                return self._serve_oracle(inputs, params, "ipc_full", wf=wf)
            mtype, data = fut.result(timeout=self._wait_budget(deadline))
        except IpcDisconnected:
            self._unregister(req_id)
            return self._serve_oracle(inputs, params, "batcher_down", wf=wf)
        except (TimeoutError, FutureTimeoutError):
            self._unregister(req_id)
            if deadline is not None and time.monotonic() >= deadline:
                raise DeadlineExceeded("request deadline expired while queued") from None
            return self._serve_oracle(inputs, params, "ipc_timeout", wf=wf)
        self._unregister(req_id)
        self.m_rtt.observe(tr, time.perf_counter() - t0)
        return self._settle_reply(
            mtype, data, inputs, params, wf=wf, transport=tr, pclass=pclass
        )

    async def check_await(
        self,
        inputs: Sequence[T.CheckInput],
        params: Optional[T.EvalParams] = None,
        deadline: Optional[float] = None,
        wf: Optional[Waterfall] = None,
        pclass: Optional[str] = None,
    ) -> list[T.CheckOutput]:
        """Event-loop-native check: awaits the reply future with zero
        thread-pool hops; only degraded-path oracle work leaves the loop."""
        loop = asyncio.get_running_loop()

        def oracle(reason: str):
            return loop.run_in_executor(
                None, self._serve_oracle, list(inputs), params, reason, wf
            )

        if deadline is not None and time.monotonic() >= deadline:
            raise DeadlineExceeded("request deadline expired before evaluation")
        self.stats["checks"] += 1
        route = self._inline_route(len(inputs))
        if route is not None:
            # on the loop, as Engine.check_await's serial walk is: a fifth of a millisecond
            return self._serve_inline(route, inputs, params, deadline, wf)
        if not self._connected.is_set():
            return await oracle("batcher_down")
        tr = self._transport_active
        payload = self._encode_check(inputs, deadline, wf=wf, transport=tr, pclass=pclass)
        if payload is None:
            return await oracle("codec")
        t0 = time.perf_counter()
        req_id, fut = self._register()
        try:
            if not self._send_check(req_id, payload, tr):
                self._unregister(req_id)
                return await oracle("ipc_full")
            mtype, data = await asyncio.wait_for(
                asyncio.wrap_future(fut), timeout=self._wait_budget(deadline)
            )
        except IpcDisconnected:
            self._unregister(req_id)
            return await oracle("batcher_down")
        except asyncio.TimeoutError:
            self._unregister(req_id)
            if deadline is not None and time.monotonic() >= deadline:
                raise DeadlineExceeded("request deadline expired while queued") from None
            return await oracle("ipc_timeout")
        self._unregister(req_id)
        self.m_rtt.observe(tr, time.perf_counter() - t0)
        if mtype == T_RESULT:
            return self._decode_result(data, wf, tr)
        if mtype == T_ERR:
            reason = self._err_reason(data, tr)
            self._remote_err(reason, tr, pclass)
            return await oracle(reason)
        return await oracle("protocol")

    # -- pool observability surfaces ----------------------------------------

    def transport_stats(self) -> dict:
        """What ``/_cerbos/debug/transport`` reports: which plane carried
        tickets, frame counts, and mean encode/decode ns per frame."""
        s = self.stats
        return {
            "transport": self.transport,
            "requested": self.transport_requested,
            "ring_kib": self.ring_bytes // 1024,
            "frames_out": s["enc_frames"],
            "frames_in": s["dec_frames"],
            "encode_ns_per_frame": (s["enc_ns"] // s["enc_frames"]) if s["enc_frames"] else 0,
            "decode_ns_per_frame": (s["dec_ns"] // s["dec_frames"]) if s["dec_frames"] else 0,
            "ring_full_events": s["ring_full"],
        }

    def remote_status(self) -> dict:
        """Front-end readiness provider (engine/readiness.bind_remote):

        - ``warming`` until the shared batcher has reported SERVING once
          (its PR 5 warmup pre-compiles gate the whole pool's readiness);
        - the batcher's own status (``ready``/``degraded``) while attached;
        - ``degraded`` — live, oracle-serving — when the batcher is down or
          re-warming after a respawn: a once-ready pool never 503s again.
        """
        last = self._last_status
        if self._connected.is_set() and last is not None:
            st = str(last.get("status", "ready"))
            if st in ("ready", "degraded"):
                return {**last, "status": st, "attached": True}
            if not self._ever_ready:
                return {**last, "status": "warming", "attached": True}
            return {**last, "status": "degraded", "attached": True}
        if not self._ever_ready:
            return {"status": "warming", "attached": False}
        return {"status": "degraded", "attached": False}

    def fetch_flight(self, timeout: float = 5.0) -> dict:
        """The PR 4 debug surface under the new topology: the flight
        recorder lives in the batcher process; front ends fetch its dump."""
        mtype, payload = self._request(T_FLIGHT, b"", timeout=timeout)
        if mtype != T_FLIGHT_R:
            raise IpcError("unexpected reply to flight request")
        return marshal.loads(payload)

    def fetch_slow(self, shard: Optional[int] = None, timeout: float = 5.0) -> dict:
        """Slow-request ring dump from the batcher process — requests settle
        there, so that is where the ring fills."""
        payload = marshal.dumps({"shard": shard} if shard is not None else {})
        mtype, data = self._request(T_SLOW, payload, timeout=timeout)
        if mtype != T_SLOW_R:
            raise IpcError("unexpected reply to slow-ring request")
        return marshal.loads(data)

    def fetch_pressure(self, timeout: float = 5.0) -> dict:
        """Pressure snapshot from the batcher process (queue, inflight, and
        breaker signals live there; the front end has only its own view)."""
        mtype, data = self._request(T_PRESSURE, b"", timeout=timeout)
        if mtype != T_PRESSURE_R:
            raise IpcError("unexpected reply to pressure request")
        return marshal.loads(data)

    def fetch_hotrules(self, k: int = 20, timeout: float = 5.0) -> dict:
        """Hot-rule heatmap from the batcher process — the hit array
        aggregates there, where decisions settle (ISSUE 20)."""
        payload = marshal.dumps({"k": int(k)})
        mtype, data = self._request(T_HOTRULES, payload, timeout=timeout)
        if mtype != T_HOTRULES_R:
            raise IpcError("unexpected reply to hotrules request")
        return marshal.loads(data)

    def fetch_metrics_text(self, timeout: float = 5.0) -> str:
        """The rest of the pool, rendered for this request: the owner's
        registry as ``worker="batcher"`` and every other attached front
        end's scrape body under its own label, merged."""
        mtype, payload = self._request(T_METRICS, b"", timeout=timeout)
        if mtype != T_METRICS_R:
            raise IpcError("unexpected reply to metrics request")
        return payload.decode()

    def fetch_profile(self, seconds: float, timeout: float = 600.0) -> dict:
        """Run a profiler capture in the device owner (this process holds no
        device). ``{"artifact": {...}}`` with the owner's pid and the trace's
        clocks, or ``{"kind", "error"}``. The wait covers the capture and the
        minutes it can take to stop and write a trace; a dead owner fails the
        pending request at once."""
        mtype, data = self._request(
            T_PROFILE, marshal.dumps({"seconds": float(seconds)}), timeout=seconds + timeout
        )
        if mtype != T_PROFILE_R:
            raise IpcError("unexpected reply to profile request")
        return marshal.loads(data)

    def refresh_table(self, rule_table: Any) -> None:
        """Policy-reload hook (and the constructor's): keep the local oracle
        on the latest table, with its identity beside it, computed here and
        never per request (for the boot table the pool's parent computed it
        before the fork). ONE attribute, so a request reads a matched pair."""
        self._local = (rule_table, _identity_words(bundle_hash_of(rule_table)))

    @property
    def rule_table(self) -> Any:
        return self._local[0]

    def close(self) -> None:
        self._stop = True
        self._connected.clear()
        sock = self._sock
        if sock is not None:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass
        self._fail_all_pending(IpcDisconnected("client closed"))


def default_socket_path(config_val: str = "") -> str:
    """Socket path resolution: config wins; otherwise a per-pool temp path
    keyed by the supervisor pid (two pools on one host must not collide)."""
    if config_val:
        return config_val
    import tempfile

    return os.path.join(tempfile.gettempdir(), f"cerbos-tpu-batcher-{os.getpid()}.sock")
