"""Boot to ready, by phase: one cursor over a process's start.

``engine/drainclock.py`` tiles a thread's life and ``engine/budget.py`` a
request's; this is the same idiom for the one stretch no clock covered, from
the start of the process to the first instant it would answer a readiness
probe with SERVING. :func:`mark` reads ``time.monotonic()`` once and books the
seconds since the previous mark to the phase that just ENDED, so the phases
add up to ``ready`` by construction:

- ``import``: process start to the serve command with its modules loaded
  (the interpreter, the CLI, then the command's own imports: gRPC, aiohttp,
  numpy). Process start is the kernel's: field 22 of ``/proc/self/stat``
  against ``/proc/uptime``, to the clock tick (10 ms); 0 where ``/proc``
  cannot say, and 0 for ``serve.serve()``, whose host application's life
  before the call is not this program's boot
- ``load``: the store opened, its files read and parsed
- ``compile``: ``compile_policy_set``
- ``table``: ``build_rule_table``
- ``lower``: ``bootstrap._make_evaluator`` (lowering, the packer)
- ``device``: ``jitcache.open_device`` (the backend's start; not in a front end)
- ``listen``: the rest of ``initialize``, threads and listeners, up to ready
- ``other``: whatever lies between those (config, signal handlers, the audit
  log, the table's identity)
- ``ready``: process start to that instant

They are published ONCE, as ``cerbos_tpu_boot_seconds{phase}``, with one log
line, when the process is listening and its readiness answers SERVING
(:func:`listening`, :func:`readiness_changed`); after that every call here
does nothing, so a table rebuilt after a policy push books nothing (that is
the rollout's, which has series of its own). A process that never began a
clock (tests, ``embedded()``, tools) publishes nothing.

In a pool the clock is begun by the pool's parent, which builds and lowers
before it forks (``server/workers.py``): the children inherit the cursor and
the parent's phases, the DEVICE OWNER goes on booking its own and publishes,
and a front end drops what it inherited (:func:`abandon`), so a merged scrape
holds one value a phase, under ``worker="batcher"``. The owner's ``ready``
is the instant ITS ticket plane is up and its readiness serves; a front
end's bind comes some tenths of a second later and is in no series. A worker
that the pool restarts begins a clock of its own at its fork.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Optional

from . import observability

_log = logging.getLogger("cerbos_tpu.boot")

IMPORT, LOAD, COMPILE, TABLE, LOWER, DEVICE, LISTEN, OTHER, READY = (
    "import", "load", "compile", "table", "lower", "device", "listen", "other", "ready"
)
PHASES = (IMPORT, LOAD, COMPILE, TABLE, LOWER, DEVICE, LISTEN, OTHER)


def process_age_s() -> float:
    """Seconds since the kernel started this process (see the module's text)."""
    try:
        with open("/proc/self/stat", "rb") as f:
            start_ticks = int(f.read().rsplit(b")", 1)[1].split()[19])
        with open("/proc/uptime", "rb") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


class BootClock:
    def __init__(self, age_s: float):
        self.phases = dict.fromkeys(PHASES, 0.0)
        self.phases[IMPORT] = age_s
        self._cursor = time.monotonic()
        self._start = self._cursor - age_s
        self._listening = False

    def mark(self, phase: str) -> None:
        now = time.monotonic()
        self.phases[phase] += now - self._cursor
        self._cursor = now


_lock = threading.Lock()
_clock: Optional[BootClock] = None
published: Optional[dict] = None  # the phases and ``ready`` as published, for the debug surfaces and tests


def begin(process_start: bool = True) -> None:
    """The entry of the serve command. A second call in one process restarts
    nothing once the first clock has been published."""
    global _clock
    with _lock:
        if published is None:
            _clock = BootClock(process_age_s() if process_start else 0.0)


def mark(phase: str) -> None:
    """The phase that ends here; nothing without a clock or after ready."""
    clock = _clock
    if clock is not None:
        clock.mark(phase)


def abandon() -> None:
    """A front end: the pool's boot is the device owner's to publish."""
    global _clock
    with _lock:
        _clock = None


def listening() -> None:
    """The listeners (in a pool's owner: the ticket plane) are up."""
    clock = _clock
    if clock is not None:
        clock._listening = True
        readiness_changed()


def readiness_changed() -> None:
    """Publish, once, if the process is listening and would answer SERVING.
    Called where either becomes true: :func:`listening`, and
    ``ReadinessState.mark_ready`` (a warm-up that ends after the bind)."""
    global _clock, published
    clock = _clock
    if clock is None or not clock._listening:
        return
    from .engine import readiness

    if not readiness.state().serving():
        return
    with _lock:
        if _clock is not clock:
            return
        _clock = None
        clock.mark(LISTEN)
        published = {**clock.phases, READY: clock._cursor - clock._start}
    gauge = observability.metrics().gauge_vec(
        "cerbos_tpu_boot_seconds",
        "seconds from the start of the process (the kernel's: /proc/self/stat field 22 against /proc/uptime; "
        "in a pool the parent's, which builds before it forks) to the first instant it listened and answered "
        "SERVING, by phase: import (to the serve command with its modules loaded), load (store opened, files read and "
        "parsed), compile, table, lower, device (the backend's start), listen (the rest, up to ready), other; "
        "phase=ready is their sum. Set once; a policy push does not touch it; in a pool the device owner's alone",
        label="phase",
    )
    for phase, seconds in published.items():
        gauge.set(phase, seconds)
    _log.info(
        "boot to ready in %.3f s: %s",
        published[READY],
        " ".join(f"{p}={published[p]:.3f}" for p in PHASES),
        extra={"fields": {f"boot_{p}_s": round(s, 4) for p, s in published.items()}},
    )
