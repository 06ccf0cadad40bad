"""Readiness split from liveness.

``/_cerbos/health`` answers "is the process alive" and must stay green the
moment the listeners bind. But a replica whose dominant device layouts are
not compiled yet will hand its first unlucky callers a multi-second XLA
compile — so ``/_cerbos/ready`` (HTTP and the gRPC health service) answers
the different question "is it safe to route traffic here", reporting
``{status, compiled_layouts, expected}``:

- ``warming``  — the warmup driver is still pre-compiling; NOT serving
  (HTTP 503 / gRPC NOT_SERVING) so load balancers hold traffic back;
- ``ready``    — all expected layouts compiled (or no warmup configured);
- ``degraded`` — warm, but the device circuit breaker is open and requests
  are riding the CPU oracle. Still SERVING: degraded-but-live beats a
  restart loop, and the breaker state is exported for alerting.

One process-global instance (the flight-recorder pattern): bootstrap drives
the transitions, both servers read it.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional

from .. import bootclock
from ..observability import metrics

_STATUS_CODE = {"warming": 0.0, "ready": 1.0, "degraded": 2.0}


class ReadinessState:
    """Thread-safe readiness snapshot: warming → ready (→ degraded while the
    breaker is open). ``clock`` is injectable for tests."""

    def __init__(self, clock: Callable[[], float] = time.monotonic):
        reg = metrics()
        self.m_state = reg.gauge(
            "cerbos_tpu_readiness_state",
            "0 warming (not serving), 1 ready, 2 degraded (breaker open, oracle serving)",
        )
        self.m_expected = reg.gauge(
            "cerbos_tpu_warmup_expected_layouts",
            "Device layouts the warmup driver intends to pre-compile",
        )
        self.m_compiled = reg.gauge(
            "cerbos_tpu_warmup_compiled_layouts",
            "Device layouts the warmup driver has pre-compiled so far",
        )
        self._clock = clock
        self._lock = threading.Lock()
        # a server with no warmup configured is born ready: readiness must
        # never gate deployments that opted out of pre-compilation
        self._ready = True
        self._expected = 0
        self._compiled = 0
        self._warmup_error: Optional[str] = None
        self._warmed_at: Optional[float] = None
        self._health: Optional[Callable[[], str]] = None
        self._remote: Optional[Callable[[], dict]] = None
        self._parity: Optional[Callable[[], list]] = None
        self._brownout: Optional[Callable[[], str]] = None
        self._epoch: Optional[Callable[[], dict]] = None
        self.m_state.set(_STATUS_CODE["ready"])

    # -- transitions (driven by bootstrap / the warmup driver) -------------

    def begin_warmup(self, expected: int) -> None:
        with self._lock:
            self._ready = False
            self._expected = int(expected)
            self._compiled = 0
            self._warmup_error = None
            self._warmed_at = None
        self.m_expected.set(float(expected))
        self.m_compiled.set(0.0)
        self.m_state.set(_STATUS_CODE["warming"])

    def layout_compiled(self) -> None:
        with self._lock:
            self._compiled += 1
            compiled = self._compiled
        self.m_compiled.set(float(compiled))

    def mark_ready(self, error: Optional[str] = None) -> None:
        """Warmup finished — or failed: a failed warmup still opens the
        gates (with the error recorded), because never-ready is a worse
        failure mode than cold-compiling under traffic."""
        with self._lock:
            self._ready = True
            self._warmup_error = error
            self._warmed_at = self._clock()
        # a warm-up that ends after the listeners are up is where boot ends
        bootclock.readiness_changed()

    def bind_health(self, provider: Optional[Callable[[], str]]) -> None:
        """Wire the device breaker's state in: an open breaker after warmup
        reports ``degraded`` (still serving). ``provider`` returns the
        breaker state string (``closed`` / ``open`` / ``half_open``)."""
        self._health = provider

    def bind_parity(self, provider: Optional[Callable[[], list]]) -> None:
        """Wire the parity sentinel's storm state in: any shard inside a
        divergence storm reports ``degraded`` with reason ``parity`` (still
        serving — the tripped lane rides the CPU oracle, which is correct by
        definition). ``provider`` returns the storming shard ids."""
        self._parity = provider

    def bind_brownout(self, provider: Optional[Callable[[], str]]) -> None:
        """Wire the brownout controller's stage in: while any shed stage is
        engaged the snapshot carries ``reason: "brownout"`` + the deepest
        stage name (still serving — shedding optional work IS how the
        service stays live). ``provider`` returns the stage name or ''."""
        self._brownout = provider

    def bind_epoch(self, provider: Optional[Callable[[], dict]]) -> None:
        """Wire the rollout controller's epoch block in: ``{"policy_epoch":
        N, "policy_epoch_committed_at": wall_ts, ...}`` merged into every
        snapshot. Because the shared batcher's STATUS frames are built from
        this snapshot, front ends learn about cutovers on their next status
        poll with no IPC frame change — ``committed_at`` is the wall-clock
        reference the skew gauge measures against."""
        self._epoch = provider

    def _epoch_info(self) -> dict:
        provider = getattr(self, "_epoch", None)
        if provider is None:
            return {}
        try:
            return dict(provider() or {})
        except Exception:
            return {}

    def bind_remote(self, provider: Optional[Callable[[], dict]]) -> None:
        """Front-end mode: this process has no device of its own — readiness
        is the SHARED batcher process's readiness, fetched over the ticket
        queue. ``provider`` returns a snapshot dict with at least
        ``{"status": warming|ready|degraded}``; it overrides the local state
        machine entirely (the local process never warms anything)."""
        self._remote = provider

    # -- reads (servers, probes, tests) ------------------------------------

    def status(self) -> str:
        remote = getattr(self, "_remote", None)
        if remote is not None:
            st = "degraded"
            try:
                st = str(remote().get("status", "degraded"))
            except Exception:
                pass
            if st not in _STATUS_CODE:
                st = "degraded"
            self.m_state.set(_STATUS_CODE[st])
            return st
        with self._lock:
            ready = self._ready
        st = "ready"
        if not ready:
            st = "warming"
        else:
            provider = self._health
            if provider is not None:
                try:
                    if provider() == "open":
                        st = "degraded"
                except Exception:
                    pass
            if st == "ready" and self._parity_shards():
                st = "degraded"
        self.m_state.set(_STATUS_CODE[st])
        return st

    def _parity_shards(self) -> list:
        provider = getattr(self, "_parity", None)
        if provider is None:
            return []
        try:
            return list(provider())
        except Exception:
            return []

    def _brownout_stage(self) -> str:
        provider = getattr(self, "_brownout", None)
        if provider is None:
            return ""
        try:
            return str(provider() or "")
        except Exception:
            return ""

    def serving(self) -> bool:
        """Gate decision: warming withholds traffic; degraded is live."""
        return self.status() != "warming"

    def snapshot(self) -> dict:
        remote = getattr(self, "_remote", None)
        if remote is not None:
            snap: dict = {}
            try:
                snap = dict(remote())
            except Exception:
                pass
            st = str(snap.get("status", "degraded"))
            snap["status"] = st if st in _STATUS_CODE else "degraded"
            snap.setdefault("attached", False)
            snap["topology"] = "frontend"
            # the front end runs its OWN brownout ladder (admission-side
            # sheds happen here); the batcher's stage arrives inside the
            # remote snapshot and the deeper of the two wins
            local_stage = self._brownout_stage()
            if local_stage and not snap.get("brownout_stage"):
                snap["brownout_stage"] = local_stage
                snap.setdefault("reason", "brownout")
            self.m_state.set(_STATUS_CODE[snap["status"]])
            return snap
        st = self.status()
        parity_shards = self._parity_shards()
        brownout_stage = self._brownout_stage()
        with self._lock:
            out = {
                "status": st,
                "compiled_layouts": self._compiled,
                "expected": self._expected,
            }
            if self._warmup_error:
                out["warmup_error"] = self._warmup_error
        if parity_shards:
            out["reason"] = "parity"
            out["parity_shards"] = parity_shards
        if brownout_stage:
            # parity keeps the reason slot if both fire (it signals possible
            # wrong answers; brownout only signals shed work)
            out.setdefault("reason", "brownout")
            out["brownout_stage"] = brownout_stage
        out.update(self._epoch_info())
        return out


_state = ReadinessState()


def state() -> ReadinessState:
    return _state
