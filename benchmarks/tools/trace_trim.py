"""Builder's tool: cut a recorded ``.xplane.pb`` down to its first events per
line, in the same wire format: a sample of a trace that is small enough to
look at by hand and to test ``trace_reduce`` on (``tests/benchmark/data``). Device planes keep every
line; of the host's plane the first lines are kept. Event statistics and
plane-level statistics, which the reduction does not read, are dropped."""

from __future__ import annotations

from benchmarks.lib.trace_reduce import _varint

HOST_LINES = 3


def _enc_varint(v: int) -> bytes:
    if v < 0:
        v += 1 << 64
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _vi(num: int, v: int) -> bytes:
    return _enc_varint(num << 3) + _enc_varint(v)


def _ld(num: int, data: bytes) -> bytes:
    return _enc_varint(num << 3 | 2) + _enc_varint(len(data)) + bytes(data)


def _raw(buf):
    """(field number, varint value or None, bytes or None) of every varint and length-delimited field."""
    buf = memoryview(buf)
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, pos = _varint(buf, pos)
            yield num, v, None
        elif wt == 2:
            n, pos = _varint(buf, pos)
            yield num, None, bytes(buf[pos : pos + n])
            pos += n
        elif wt == 1:
            pos += 8
        elif wt == 5:
            pos += 4
        else:
            raise ValueError(f"wire type {wt}")


def _copy(buf, drop=()) -> bytes:
    return b"".join(_vi(n, v) if d is None else _ld(n, d) for n, v, d in _raw(buf) if n not in drop)


def _trim_line(line: bytes, keep: int) -> tuple[bytes, set]:
    out, used, n = b"", set(), 0
    for num, v, data in _raw(line):
        if num != 4:
            out += _vi(num, v) if data is None else _ld(num, data)
            continue
        n += 1
        if n <= keep:
            event = _copy(data, drop=(4,))
            out += _ld(4, event)
            used |= {v2 for n2, v2, _ in _raw(event) if n2 == 1}
    return out, used


def _trim_plane(plane: bytes, keep: int, max_lines: int | None) -> bytes:
    out, used, lines, metas = b"", set(), [], []
    for num, v, data in _raw(plane):
        if num == 3:
            lines.append(data)
        elif num == 4:
            metas.append(data)
        elif num not in (5, 6):
            out += _vi(num, v) if data is None else _ld(num, data)
    for line in lines[:max_lines]:
        trimmed, ids = _trim_line(line, keep)
        out += _ld(3, trimmed)
        used |= ids
    for entry in metas:  # map<int64, XEventMetadata>: key = 1, value = 2
        fields = {n: (v, d) for n, v, d in _raw(entry)}
        if fields[1][0] in used:
            out += _ld(4, _vi(1, fields[1][0]) + _ld(2, _copy(fields[2][1], drop=(3, 5, 6))))
    return out


def trim(raw: bytes, keep: int) -> bytes:
    out = b""
    for num, _, data in _raw(raw):
        if num != 1:
            continue
        name = next((d.decode("utf-8", "replace") for n, _, d in _raw(data) if n == 2), "")
        if name.startswith("/device:"):
            out += _ld(1, _trim_plane(data, keep, None))
        elif name.startswith("/host:"):
            out += _ld(1, _trim_plane(data, keep, HOST_LINES))
    return out
