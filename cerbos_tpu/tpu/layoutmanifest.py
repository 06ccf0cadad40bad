"""The layout manifest: which device layouts a table's traffic has met.

The persistent compilation cache (:mod:`jitcache`) turns a compile into a
load, but a process still meets its layouts one by one, each inside a
request: XLA can only be asked for an executable once somebody has traced
the program, and the program's shape is known only when traffic presents
it. This file remembers the shapes. Every jitted function the dispatch site
builds (``evaluator._device_dispatch``) is filed here with what is needed to
build it again without a batch, so the next process serving the same table
loads them ahead of traffic (``evaluator._LayoutPreloader``). Beside a
table's layouts it keeps the table's layout CLASS, the ``(K, J, D)`` its
batches are packed at (``packer.LayoutClass``): the next process starts
there, so it packs no batch at a smaller class and every layout it needs is
one the manifest holds. A table's entries are all OF its class: the entry
that raises the class takes the smaller class's entries out of the file (no
process of this table builds them again).

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

# 3: a table is {"class": [K, J, D], "entries": {...}}, every entry of that class (in 2 a table was its entries,
# of whatever (K, J, D) each batch had of its own; 2 was the first whose entry is the jit key's parts alone). A file
# of an older format is read as empty and overwritten by the first layout a flight builds
FORMAT = 3
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
        or not all(_well_formed(t) for t in tables.values())
    ):
        _warn_once("layout manifest %s is not of format %d, read as empty", p, FORMAT)
        return _empty()
    return raw


def _is_class(kjd) -> bool:
    return isinstance(kjd, list) and len(kjd) == 3 and all(isinstance(x, int) and x > 0 for x in kjd)


def _well_formed(table) -> bool:
    return (
        isinstance(table, dict)
        and _is_class(table.get("class"))
        and isinstance(table.get("entries"), dict)
        and all(
            isinstance(e, dict) and isinstance(e.get("met"), int) and e.get("depth") == table["class"]
            for e in table["entries"].values()
        )
    )


def _rank(entry: dict) -> tuple:
    return (-entry["met"], entry.get("seq", 0))


def _table(scope_key: str) -> Optional[dict]:
    p = path()
    return _read(p)["tables"].get(scope_key) if p is not None else None


def entries(scope_key: str) -> list[dict]:
    """The layouts met under ``scope_key``, all of its class, most-met first,
    and among equals in the order traffic first presented them."""
    table = _table(scope_key)
    return sorted(table["entries"].values(), key=_rank) if table else []


def layout_class(scope_key: str) -> Optional[tuple[int, int, int]]:
    """The class filed under ``scope_key``, or None: one read of the file, no
    load and no compile."""
    table = _table(scope_key)
    return tuple(table["class"]) if table else None


def record(scope_key: str, entry: dict) -> None:
    """One process met ``entry`` under ``scope_key``: file it, or count it
    once more. An entry larger than the table's class in any extent raises
    the class to it and takes the entries of the smaller class out; one
    smaller than the class (a batch packed before a growth and built after
    it) is not filed. Never raises: a manifest that cannot be written is a
    process that records nothing."""
    p = path()
    if p is None:
        return
    try:
        doc = _read(p)
        doc["seq"] += 1
        depth = [int(x) for x in entry["depth"]]
        table = doc["tables"].setdefault(scope_key, {"class": depth, "entries": {}})
        kjd = [max(a, b) for a, b in zip(table["class"], depth)]
        grew = kjd != table["class"]
        if grew:
            table["class"], table["entries"] = kjd, {}
        if depth == kjd:
            eid = entry_id(entry)
            if eid in table["entries"]:
                table["entries"][eid]["met"] += 1
            else:
                table["entries"][eid] = dict(entry, met=1, seq=doc["seq"])
        elif not grew:
            return
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
    over = sum(len(t["entries"]) for t in doc["tables"].values()) - MAX_ENTRIES
    if over <= 0:
        return
    ranked = sorted(
        (e["met"], e.get("seq", 0), sk, eid) for sk, t in doc["tables"].items() for eid, e in t["entries"].items()
    )
    for _, _, sk, eid in ranked[:over]:
        del doc["tables"][sk]["entries"][eid]
    doc["tables"] = {sk: t for sk, t in doc["tables"].items() if t["entries"]}


def size() -> dict:
    """For ``jitcache.status()``: where the file is and how large. A stat, not
    a read: a process that never dispatches never reads the manifest."""
    p = path()
    try:
        return {"path": str(p) if p else None, "bytes": p.stat().st_size if p else 0}
    except OSError:
        return {"path": str(p), "bytes": 0}
