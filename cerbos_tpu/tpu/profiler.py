"""Operator-gated on-demand device profiling.

``GET /_cerbos/debug/profile?seconds=N`` captures a ``jax.profiler.trace``
for N seconds of whatever the serving path is doing and returns the
artifact directory — the tool for "the batch stage histogram says device
time doubled, WHAT is the device doing". Gated off by default
(``engine.tpu.profiler.enabled``): a trace capture perturbs the device and
writes files, so it must be an explicit operator decision.

Artifacts land under a bounded directory: each capture gets its own
timestamped subdirectory and the oldest captures beyond ``maxArtifacts``
are pruned, so a flapping operator cannot fill the disk.

The capture runs with the Python tracer OFF: with it on (jax's default) a
capture stopped the server for seconds inside itself and could not lie
over live traffic; with it off, pages served inside a capture are 4%
slower. Stopping and writing still takes some 24 s for 13.5 s of 40
pages/s (25 MB, most of it the device's own events), and the server is
about 45% slower while that lasts (PERF.md, PR 24). What the host was
doing is named by the program instead: while a capture is open
``observability.region`` emits a ``TraceAnnotation`` for every span and for
every state of the batcher's drain thread (``engine/drainclock.py``), on
the trace's own clock. The capture reads ``time.monotonic_ns()`` and
``time.time_ns()`` right after the trace starts and right before it stops,
returns them and writes them into the trace as ``cerbos.clock`` events, so
a flight (``submitted_monotonic_ns`` in its flight record), a request or a
load generator's own timestamps can be placed on the trace.
"""

from __future__ import annotations

import logging
import os
import shutil
import tempfile
import threading
import time

from .. import observability

_log = logging.getLogger("cerbos_tpu.profiler")

PYTHON_TRACER_LEVEL = 0  # off: the regions name the host's work, not Python frames
HOST_TRACER_LEVEL = 1  # TraceMe level 1 (critical): the level TraceAnnotation records at


class ProfilerDisabled(RuntimeError):
    """Profiling is not enabled in the configuration."""


class ProfilerBusy(RuntimeError):
    """A capture is already in flight (one at a time: overlapping device
    traces corrupt each other)."""


_lock = threading.Lock()
_enabled = False
_dir = ""
_max_artifacts = 4
_max_seconds = 30.0
_active = False
_seq = 0


def configure(
    enabled: bool = False,
    dir: str = "",
    max_artifacts: int = 4,
    max_seconds: float = 30.0,
) -> None:
    global _enabled, _dir, _max_artifacts, _max_seconds
    with _lock:
        _enabled = bool(enabled)
        _dir = str(dir or "")
        _max_artifacts = max(1, int(max_artifacts))
        _max_seconds = float(max_seconds)


def enabled() -> bool:
    return _enabled


def base_dir() -> str:
    return _dir or os.path.join(tempfile.gettempdir(), "cerbos_tpu_profiles")


def _prune(base: str, keep: int) -> None:
    try:
        entries = sorted(
            (e for e in os.scandir(base) if e.is_dir()), key=lambda e: e.name
        )
    except OSError:
        return
    for e in entries[:-keep] if keep < len(entries) else []:
        shutil.rmtree(e.path, ignore_errors=True)


def _clock_mark(jprof, edge: str) -> dict:
    """Both host clocks at one instant, returned and written into the trace."""
    mono, unix = time.monotonic_ns(), time.time_ns()
    with jprof.TraceAnnotation("cerbos.clock", edge=edge, monotonic_ns=mono, unix_ns=unix):
        pass
    return {f"trace_{edge}_monotonic_ns": mono, f"trace_{edge}_unix_ns": unix}


def _run_trace(path: str, seconds: float) -> dict:
    """Separated for testability: the actual jax capture. Returns the four
    clock readings."""
    from . import jitcache

    if jitcache.device() is None:
        # jax.profiler initializes a backend: a front end asking would try
        # to open the chip its batcher holds
        raise ProfilerDisabled("this process owns no device; only the device owner can trace it")
    from jax import profiler as jprof

    options = jprof.ProfileOptions()
    options.python_tracer_level = PYTHON_TRACER_LEVEL
    options.host_tracer_level = HOST_TRACER_LEVEL
    with jprof.trace(path, profiler_options=options):
        clocks = _clock_mark(jprof, "start")
        observability.set_capture_open(True)
        try:
            time.sleep(seconds)
        finally:
            observability.set_capture_open(False)
        clocks.update(_clock_mark(jprof, "stop"))
    return clocks


def capture(seconds: float) -> dict:
    """Blocking capture; returns ``{path, seconds}`` and the clock readings
    at both ends of the trace for the response body.

    Raises ProfilerDisabled / ProfilerBusy / ValueError (bad duration) —
    the HTTP handler maps each to a status.
    """
    global _active, _seq
    if not _enabled:
        raise ProfilerDisabled("profiling disabled (engine.tpu.profiler.enabled)")
    seconds = float(seconds)
    if seconds <= 0:
        raise ValueError("seconds must be > 0")
    seconds = min(seconds, _max_seconds)
    with _lock:
        if _active:
            raise ProfilerBusy("a profile capture is already running")
        _active = True
    try:
        base = base_dir()
        os.makedirs(base, exist_ok=True)
        _seq += 1
        name = time.strftime("%Y%m%dT%H%M%S") + f"-p{os.getpid()}-{_seq:03d}"
        path = os.path.join(base, name)
        _log.info("profile capture: %.1fs -> %s", seconds, path)
        clocks = _run_trace(path, seconds) or {}
        _prune(base, _max_artifacts)
        return {"path": path, "seconds": seconds, **clocks}
    finally:
        with _lock:
            _active = False
