"""From a profiler trace (``*.xplane.pb``) to device busy time, the operations
that took most of it, and the longest idle gaps with what the host was doing.

Reads the file with a wire-format decoder of its own (the ``XSpace`` message of
``tsl/profiler/protobuf/xplane.proto``: planes, lines, events, event
metadata), so the harness needs neither jax nor tensorflow to reduce a trace,
and no PR that claims a gain can change how the number is made.

Busy time of a device is the length of the union of the intervals in which an
operation ran on it: the events of the device plane's ``XLA Ops`` line (of all
its lines but ``Steps`` where a trace has no such line). Idle is the rest of
the window. The window is the span of the traced traffic, first send to last
reply, in seconds from the start of the capture (a trace counts its time from
there); what the capture holds before and after it is left out, so that the
capture's empty lead and tail are not read as idleness.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SKIP_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Name Scope", "Framework Ops", "Source code")
TOP = 10


# -- protobuf wire format -------------------------------------------------------


def _varint(buf, pos: int) -> tuple[int, int]:
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7


def fields(buf):
    """(field number, value) for each field of a message: an int for a varint,
    a memoryview for a length-delimited field; fixed-width fields are skipped."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, pos = _varint(buf, pos)
            yield num, val
        elif wt == 2:
            n, pos = _varint(buf, pos)
            yield num, buf[pos : pos + n]
            pos += n
        elif wt == 1:
            pos += 8
        elif wt == 5:
            pos += 4
        else:
            raise ValueError(f"wire type {wt} at byte {pos}")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _event_metadata(buf) -> tuple[int, str]:
    mid, name, display = 0, "", ""
    for num, val in fields(buf):
        if num == 1:
            mid = val
        elif num == 2:
            name = bytes(val).decode("utf-8", "replace")
        elif num == 4:
            display = bytes(val).decode("utf-8", "replace")
    return mid, display or name


def _line(buf) -> dict:
    name, display, ts_ns, events = "", "", 0, []
    for num, val in fields(buf):
        if num == 2:
            name = bytes(val).decode("utf-8", "replace")
        elif num == 11:
            display = bytes(val).decode("utf-8", "replace")
        elif num == 3:
            ts_ns = _signed(val)
        elif num == 4:
            mid = off = dur = 0
            for n2, v2 in fields(val):
                if n2 == 1:
                    mid = v2
                elif n2 == 2:
                    off = _signed(v2)
                elif n2 == 3:
                    dur = _signed(v2)
            events.append((mid, off, dur))
    base = ts_ns * 1000  # picoseconds
    return {"name": display or name, "events": [(base + off, base + off + dur, mid) for mid, off, dur in events]}


def read_planes(raw: bytes) -> list[dict]:
    """``[{name, lines: [{name, events: [(start_ps, end_ps, event name)]}]}]``."""
    planes = []
    for num, val in fields(memoryview(raw)):
        if num != 1:
            continue
        name, lines, meta = "", [], {}
        for n2, v2 in fields(val):
            if n2 == 2:
                name = bytes(v2).decode("utf-8", "replace")
            elif n2 == 3:
                lines.append(_line(v2))
            elif n2 == 4:
                for n3, v3 in fields(v2):
                    if n3 == 2:
                        mid, mname = _event_metadata(v3)
                        meta[mid] = mname
        for line in lines:
            line["events"] = [(s, e, meta.get(mid, str(mid))) for s, e, mid in line["events"]]
        planes.append({"name": name, "lines": lines})
    return planes


# -- the reduction ---------------------------------------------------------------


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Sorted, merged, non-empty intervals."""
    merged: list[list[int]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _device_events(plane: dict) -> list[tuple[int, int, str]]:
    ops = [ln for ln in plane["lines"] if ln["name"] == OPS_LINE]
    lines = ops or [ln for ln in plane["lines"] if ln["name"] not in SKIP_LINES]
    return [ev for ln in lines for ev in ln["events"]]


def _attribute(gap: tuple[int, int], host_events: list[tuple[int, int, str]]) -> str:
    """The host event that covers most of the gap, when it covers half of it
    and is not a span many times the gap's length (a thread's whole life)."""
    gs, ge = gap
    best, best_cover = "unattributed", 0
    for s, e, name in host_events:
        if e <= gs or s >= ge or e - s > 4 * (ge - gs):
            continue
        cover = min(e, ge) - max(s, gs)
        if cover > best_cover:
            best, best_cover = name, cover
    if 2 * best_cover < ge - gs:
        return "host:unattributed"
    return "host:" + best[:56]


def reduce_planes(planes: list[dict], span: tuple[float, float]) -> dict | None:
    """``span``: the window, in seconds from the start of the capture. None
    when no operation ran on a device inside it."""
    lo, hi = round(span[0] * 1e12), round(span[1] * 1e12)
    devices = [(p["name"], _device_events(p)) for p in planes if DEVICE_PLANE.match(p["name"])]
    outside = [(s, e) for _, ev in devices for s, e, _ in ev if e <= lo or s >= hi]
    devices = [(n, [(max(s, lo), min(e, hi), name) for s, e, name in ev if e > lo and s < hi]) for n, ev in devices]
    devices = [(n, ev) for n, ev in devices if ev]
    if not devices:
        return None
    host_events = [
        ev for p in planes if p["name"].startswith("/host:") for ln in p["lines"] for ev in ln["events"]
    ]
    busy_ps, op_ps, gaps = [], {}, []
    for _, events in devices:
        merged = union([(s, e) for s, e, _ in events])
        busy_ps.append(sum(e - s for s, e in merged))
        for s, e, name in events:
            op_ps[name] = op_ps.get(name, 0) + (e - s)
        edges = [lo] + [t for s, e in merged for t in (s, e)] + [hi]
        gaps += [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2) if edges[k + 1] > edges[k]]
    gaps.sort(key=lambda g: g[0] - g[1])
    # an operation that encloses others (a while loop, a call) is counted with
    # them on the Ops line: the ranking is by each name's own total
    top_ops = sorted(op_ps.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "devices": [n for n, _ in devices],
        "busy_s": sum(busy_ps) / len(busy_ps) / 1e12,
        "window_s": (hi - lo) / 1e12,
        "device_ops": [[name, ps / 1e12] for name, ps in top_ops],
        "idle_gaps": [[_attribute(g, host_events), (g[1] - g[0]) / 1e12] for g in gaps[:TOP]],
        "events": sum(len(ev) for _, ev in devices),
        "events_outside": len(outside),  # left out: what the capture holds before and after the traced traffic
        "outside_from_s": min((s for s, _ in outside), default=0) / 1e12,
        "outside_to_s": max((e for _, e in outside), default=0) / 1e12,
    }


def find_xplane(profile_dir: str) -> str | None:
    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def reduce_file(path: str, span: tuple[float, float]) -> dict | None:
    with open(path, "rb") as f:
        return reduce_planes(read_planes(f.read()), span)
