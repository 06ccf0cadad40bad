"""The layout manifest: which device layouts a table's traffic has met.

The persistent compilation cache (:mod:`jitcache`) turns a compile into a
load, but a process still meets its layouts one by one, each inside a
request: XLA can only be asked for an executable once somebody has traced
the program, and the program's shape is known only when traffic presents
it. This file remembers the shapes. Every jitted function the dispatch site
builds (``evaluator._device_dispatch``) is filed here with what is needed to
build it again without a batch, so the next process serving the same table
loads them ahead of traffic (``evaluator._LayoutPreloader``).

Where it lives: ``<jitcache.directory()>/layouts/manifest.json``. A
subdirectory, so ``jitcache.entry_count()`` (files of the cache directory
itself, the count that tells a fresh compile from a load) never sees it.
Entries are filed per *scope*: the lowered table's identity
(``rollout.bundle_hash_of``), the jax and jaxlib versions and the device
kind. An entry holds shapes and names only, never code: the function is
always built from the reading process's own table, so a stale entry costs
one failed build and cannot change an answer.

Written atomically (temp file + rename; two writers lose at worst one
update), read tolerantly (an unreadable or foreign file is an empty one,
logged once), bounded by ``MAX_ENTRIES`` over all scopes (least-met entries
go first, the oldest among equals).
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pathlib
import tempfile
from typing import Optional

from . import jitcache

_log = logging.getLogger("cerbos_tpu.layoutmanifest")

# 2: an entry is the jit key's parts alone; the device program takes ONE staging buffer whose cut follows
# from them (1 listed the eight arguments of the program it described, which no process builds any more)
FORMAT = 2
MAX_ENTRIES = 512
_SUBDIR = "layouts"
_FILE = "manifest.json"

_warned = False


def path() -> Optional[pathlib.Path]:
    """The manifest's file, or None when no persistent cache is enabled: then
    nothing is recorded and nothing is loaded."""
    d = jitcache.directory()
    return pathlib.Path(d) / _SUBDIR / _FILE if d else None


def scope(identity: str, device_kind: str) -> str:
    """What an entry is filed under: a layout is worth loading only for the
    table it was met on, and loads from the cache only under the versions
    and the device kind that compiled it."""
    import jax
    import jaxlib

    return f"{identity}|jax={jax.__version__}|jaxlib={jaxlib.__version__}|device={device_kind}"


def entry_id(entry: dict) -> str:
    """One layout, however often it is met: a digest of the jit key's parts."""
    parts = {k: entry[k] for k in ("shape", "depth", "variant", "layout")}
    return hashlib.sha1(json.dumps(parts, sort_keys=True).encode()).hexdigest()[:16]


def _warn_once(msg: str, *args) -> None:
    global _warned
    if not _warned:
        _warned = True
        _log.warning(msg, *args)


def _empty() -> dict:
    return {"format": FORMAT, "seq": 0, "tables": {}}


def _read(p: pathlib.Path) -> dict:
    try:
        raw = json.loads(p.read_text())
    except FileNotFoundError:
        return _empty()
    except (OSError, ValueError) as e:
        _warn_once("layout manifest %s is unreadable, read as empty: %s", p, e)
        return _empty()
    tables = raw.get("tables") if isinstance(raw, dict) else None
    if (
        not isinstance(tables, dict)
        or raw.get("format") != FORMAT
        or not isinstance(raw.get("seq"), int)
        or not all(
            isinstance(t, dict) and all(isinstance(e, dict) and isinstance(e.get("met"), int) for e in t.values())
            for t in tables.values()
        )
    ):
        _warn_once("layout manifest %s is not of format %d, read as empty", p, FORMAT)
        return _empty()
    return raw


def _rank(entry: dict) -> tuple:
    return (-entry["met"], entry.get("seq", 0))


def entries(scope_key: str) -> list[dict]:
    """The layouts met under ``scope_key``, most-met first, and among equals
    in the order traffic first presented them."""
    p = path()
    if p is None:
        return []
    return sorted(_read(p)["tables"].get(scope_key, {}).values(), key=_rank)


def record(scope_key: str, entry: dict) -> None:
    """One process met ``entry`` under ``scope_key``: file it, or count it
    once more. Never raises: a manifest that cannot be written is a process
    that records nothing."""
    p = path()
    if p is None:
        return
    try:
        doc = _read(p)
        doc["seq"] += 1
        table = doc["tables"].setdefault(scope_key, {})
        eid = entry_id(entry)
        if eid in table:
            table[eid]["met"] += 1
        else:
            table[eid] = dict(entry, met=1, seq=doc["seq"])
        _bound(doc)
        p.parent.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=p.parent, prefix=_FILE, suffix=".tmp")
        try:
            os.fchmod(fd, 0o644)  # as the cache's own entries: mkstemp's 0600 would hide it from a sibling's user
            with os.fdopen(fd, "w") as f:
                json.dump(doc, f, separators=(",", ":"))
            os.replace(tmp, p)
        except BaseException:
            os.unlink(tmp)
            raise
    except OSError as e:
        _warn_once("layout manifest %s cannot be written: %s", p, e)


def _bound(doc: dict) -> None:
    over = sum(len(t) for t in doc["tables"].values()) - MAX_ENTRIES
    if over <= 0:
        return
    ranked = sorted(
        (e["met"], e.get("seq", 0), sk, eid) for sk, t in doc["tables"].items() for eid, e in t.items()
    )
    for _, _, sk, eid in ranked[:over]:
        del doc["tables"][sk][eid]
    doc["tables"] = {sk: t for sk, t in doc["tables"].items() if t}


def size() -> dict:
    """For ``jitcache.status()``: where the file is and how large. A stat, not
    a read: a process that never dispatches never reads the manifest."""
    p = path()
    try:
        return {"path": str(p) if p else None, "bytes": p.stat().st_size if p else 0}
    except OSError:
        return {"path": str(p), "bytes": 0}
