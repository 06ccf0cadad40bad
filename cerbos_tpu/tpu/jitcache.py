"""Persistent XLA compilation cache, and what device this process holds.

Each distinct device layout of the lowered table's sat/lattice graph is an
XLA compile (seconds each; PERF.md records them per layout), which the
reference's stateless-replica restart model cannot absorb (its cold start is
~1 s: load = deserialize, `index/marshal.go:20,240`). JAX ships a persistent
compilation cache keyed by (HLO, compile options, jaxlib version, device
topology); enabling it makes every process after the first load the compiled
binary from disk instead of re-running XLA.

Cache location — the directory is part of the deployment, so it is placed
from outside:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX itself reads it; that directory is
  used and nothing here sets another.
- unset: ``<checkout>/.xla_cache`` (so a checked-out tree warms itself), or
  ``~/.cache/cerbos_tpu/xla`` for an installed package with no checkout.

The path never carries a pid, a time or a temp component: it is part of the
cache key's lifetime, and a directory that moves never hits.

One process per chip: :func:`open_device` is the single place the serving
path initializes a JAX backend. It runs in the process that dispatches to
the device (``bootstrap.initialize`` for the standalone and batcher roles,
after any fork) and never in a pre-fork parent or a front end, so
``status()["device"]`` doubles as "does THIS process own the device".
"""

from __future__ import annotations

import logging
import os
import pathlib

_log = logging.getLogger("cerbos_tpu.jitcache")

# False until enable() runs; afterwards the cache directory string —
# enable()/status() report it wherever it came from
_enabled: "str | bool" = False
_external = False  # directory came from JAX_COMPILATION_CACHE_DIR / the embedding app
_entries_at_enable: "int | None" = None
# set by open_device() in the device-owning process only
_device: "dict | None" = None


class DeviceInitError(RuntimeError):
    """The JAX backend could not be opened (no device, or the chip is held by
    another process). Fatal to a boot: never an oracle fallback."""


def _default_dir() -> pathlib.Path:
    # cerbos_tpu/tpu/jitcache.py -> repo root two levels up, but only when
    # running from a checkout — an installed package must not write into
    # site-packages' parent
    root = pathlib.Path(__file__).resolve().parents[2]
    if (root / "pyproject.toml").exists() or (root / ".git").exists():
        return root / ".xla_cache"
    return pathlib.Path.home() / ".cache" / "cerbos_tpu" / "xla"


def enable() -> str | None:
    """Idempotently point jax at a persistent compilation cache directory.

    Returns the directory in use, or None when the default directory cannot
    be created (read-only filesystem). Repeat calls return the same
    directory. Touches only ``jax.config`` — no backend is initialized, so a
    pre-fork parent may call it.
    """
    global _enabled, _external, _entries_at_enable
    if _enabled:
        return _enabled if isinstance(_enabled, str) else None
    import jax

    # JAX_COMPILATION_CACHE_DIR (read by jax at import) or an embedding
    # application's own jax.config: use it, set no other
    existing = jax.config.jax_compilation_cache_dir
    if existing:
        _enabled = str(existing)
        _external = True
    else:
        cand = _default_dir()
        try:
            cand.mkdir(parents=True, exist_ok=True)
        except OSError as e:
            _log.warning("xla persistent cache disabled: cannot create %s: %s", cand, e)
            return None
        jax.config.update("jax_compilation_cache_dir", str(cand))
        _enabled = str(cand)
        _external = False
    # persist every compile on both routes: the default thresholds skip
    # sub-second compiles, which compilestats.timed_first_call would then
    # misread as loaded-from-disk (no new entry appeared)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _entries_at_enable = entry_count()
    return _enabled


def open_device() -> dict:
    """Initialize the JAX backend in THIS process and record what it holds.

    Returns ``{"platform", "device_kind", "count", "pid"}`` as jax reports
    them. A backend that cannot initialize raises :class:`DeviceInitError`
    carrying the backend's own error — the caller's boot fails instead of
    serving from the oracle under a device banner. Logs at WARNING when the platform is ``cpu`` and
    ``JAX_PLATFORMS`` did not ask for it (an accelerator was expected).
    """
    global _device
    if _device is not None and _device["pid"] == os.getpid():
        return _device
    import jax

    try:
        devs = jax.devices()
    except Exception as e:
        raise DeviceInitError(f"device backend failed to initialize: {e}") from e
    _device = {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "count": len(devs),
        "pid": os.getpid(),
    }
    asked = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if _device["platform"] == "cpu" and asked != "cpu":
        _log.warning(
            "device path is running on the CPU backend (JAX_PLATFORMS=%r did not ask for it): "
            "no accelerator was found",
            asked,
        )
    else:
        _log.info(
            "device: platform=%s device_kind=%s count=%d",
            _device["platform"],
            _device["device_kind"],
            _device["count"],
        )
    return _device


def device() -> dict | None:
    """What :func:`open_device` recorded, or None when this process does not
    own the device (never opened it, or inherited the record across a fork)."""
    if _device is not None and _device["pid"] == os.getpid():
        return _device
    return None


def device_memory() -> list[dict]:
    """``memory_stats()`` of every local device, in device order; empty in a
    process that does not own the device or on a backend that reports none
    (the CPU backend). Never initializes a backend: only the owner asks."""
    if device() is None:
        return []
    import jax

    out = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        if stats:
            out.append(
                {
                    "id": d.id,
                    "bytes_in_use": int(stats.get("bytes_in_use", 0)),
                    "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
                    "bytes_limit": int(stats.get("bytes_limit", 0)),
                }
            )
    return out


def directory() -> str | None:
    """The persistent cache directory in use, or None when not enabled."""
    return _enabled if isinstance(_enabled, str) else None


def entry_count() -> int | None:
    """Files currently in the cache directory (None when disabled or
    unreadable); the layout manifest lives in a subdirectory and is not one
    of them. Cheap relative to any compile, and the before/after delta is
    what classifies a compile as fresh vs persistent-loaded where jax's own
    cache events say nothing (``compilestats.timed_first_call``)."""
    d = directory()
    if not d:
        return None
    try:
        return sum(1 for p in pathlib.Path(d).iterdir() if p.is_file())
    except OSError:
        return None


def status() -> dict:
    """Cache and device evidence for the bootstrap log line,
    ``/_cerbos/debug/flight`` (``X-Cerbos-Jitcache``), and operators asking
    "did the restart actually skip the compile?" or "what is this replica
    running on?": the directory, whether it held entries when we enabled it
    (a warm restart), how many compiles this process loaded from it, the
    size of the layout manifest kept beside it (:mod:`layoutmanifest`: what a
    restart loads ahead of traffic), and the device this process opened
    (None in a process that owns none)."""
    from . import layoutmanifest
    from .compilestats import stats as _compile_stats

    return {
        "enabled": bool(_enabled),
        "dir": directory(),
        "external": _external,
        "entries": entry_count(),
        "entries_at_enable": _entries_at_enable,
        # hit evidence: pre-existing entries mean this process can load
        # instead of compile; persistent_loads counts the times it did
        "warm_at_enable": bool(_entries_at_enable),
        "persistent_loads": _compile_stats().snapshot()["persistent_loads"],
        "manifest": layoutmanifest.size(),
        "device": device(),
        "device_memory": device_memory(),
    }
