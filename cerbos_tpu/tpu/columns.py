"""SoA attribute columns and exact-parity scalar encodings.

Doubles are encoded as order-preserving (hi, lo) int32 pairs so the device
can compare them bit-exactly without f64 arithmetic (TPUs emulate f64; the
sortable-key trick keeps comparisons in native i32). Strings are interned to
batch-local i32 ids (equality-only). Each referenced attribute path becomes
one column set: tag, hi, lo, sid, nan.
"""

from __future__ import annotations

import struct
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

TAG_MISSING = 0
TAG_NULL = 1
TAG_BOOL = 2
TAG_NUM = 3
TAG_STR = 4
TAG_OTHER = 5


def double_key(v: float) -> int:
    """Map a double to a uint64 preserving order (NaN excluded).

    -0.0 normalizes to 0.0 first: CEL compares them equal, so they must
    encode to the same key.
    """
    v = float(v)
    if v == 0.0:
        v = 0.0
    (bits,) = struct.unpack("<Q", struct.pack("<d", v))
    if bits & (1 << 63):
        return (~bits) & ((1 << 64) - 1)
    return bits | (1 << 63)


def split_key(key: int) -> tuple[int, int]:
    """uint64 sortable key → sign-biased (hi, lo) int32 pair.

    Each 32-bit word is XORed with 0x80000000 before reinterpreting as
    signed, so plain *signed* int32 comparison on device preserves the
    unsigned key order (device kernels compare hi then lo as signed ints).
    """
    hi = ((key >> 32) & 0xFFFFFFFF) ^ 0x80000000
    lo = (key & 0xFFFFFFFF) ^ 0x80000000
    if hi >= 1 << 31:
        hi -= 1 << 32
    if lo >= 1 << 31:
        lo -= 1 << 32
    return hi, lo


_TS_EPOCH = None


def _days_from_civil(y: int, m: int, d: int) -> int:
    """Days since 1970-01-01 for a proleptic-Gregorian civil date
    (Howard Hinnant's civil_from_days inverse — pure int arithmetic)."""
    y -= m <= 2
    era = y // 400
    yoe = y - era * 400
    doy = (153 * (m - 3 if m > 2 else m + 9) + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


_DAYS_IN_MONTH = (31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31)


def _fast_iso_key(s: str) -> "Optional[tuple[int, int]]":
    """Direct key for the exact 'YYYY-MM-DDTHH:MM:SSZ' form — the dominant
    shape in request corpora. None (caller falls back to the CEL
    conversion) for anything else, INCLUDING values the CEL function would
    reject, so error behavior is identical. Equivalence with the generic
    path is pinned by tests/test_fastpred.py::test_fast_iso_key."""
    if (
        len(s) != 20
        or not s.isascii()
        or s[4] != "-" or s[7] != "-" or s[10] != "T"
        or s[13] != ":" or s[16] != ":" or s[19] != "Z"
    ):
        return None
    ys, mos, ds, hs, mis, ss = s[0:4], s[5:7], s[8:10], s[11:13], s[14:16], s[17:19]
    if not (
        ys.isdigit() and mos.isdigit() and ds.isdigit()
        and hs.isdigit() and mis.isdigit() and ss.isdigit()
    ):
        return None
    y, mo, d = int(ys), int(mos), int(ds)
    h, mi, sec = int(hs), int(mis), int(ss)
    if not (1 <= y <= 9999 and 1 <= mo <= 12 and h < 24 and mi < 60 and sec < 60):
        return None
    dim = _DAYS_IN_MONTH[mo - 1]
    if mo == 2 and (y % 4 == 0 and (y % 100 != 0 or y % 400 == 0)):
        dim = 29
    if not (1 <= d <= dim):
        return None
    micros = (_days_from_civil(y, mo, d) * 86400 + h * 3600 + mi * 60 + sec) * 1_000_000
    return split_key((micros + (1 << 63)) & ((1 << 64) - 1))


def timestamp_key(v: Any) -> tuple[int, int]:
    """CEL-convertible timestamp value → order-preserving (hi, lo) i32 pair.

    Uses the same conversion as the CEL runtime's ``timestamp()`` overloads
    (str RFC3339 / int epoch-seconds / Timestamp), then maps exact epoch
    MICROseconds (int arithmetic — no float rounding at far dates) onto the
    signed-biased key space device kernels compare. Raises on anything the
    CEL function would reject."""
    global _TS_EPOCH
    import datetime as _dt

    if type(v) is str:
        k = _fast_iso_key(v)
        if k is not None:
            return k

    from ..cel.stdlib import _to_timestamp

    ts = _to_timestamp(v)
    if _TS_EPOCH is None:
        _TS_EPOCH = _dt.datetime(1970, 1, 1, tzinfo=_dt.timezone.utc)
    micros = (ts - _TS_EPOCH) // _dt.timedelta(microseconds=1)
    return split_key((micros + (1 << 63)) & ((1 << 64) - 1))


class StringInterner:
    """Batch-local string → i32 id (0 reserved for 'absent')."""

    def __init__(self) -> None:
        self.ids: dict[str, int] = {}

    def intern(self, s: str) -> int:
        i = self.ids.get(s)
        if i is None:
            i = len(self.ids) + 1
            self.ids[s] = i
        return i


@dataclass
class ColumnBatch:
    """Encoded columns for one batch: path → arrays of shape [B]."""

    size: int
    tags: dict[tuple, np.ndarray] = field(default_factory=dict)
    his: dict[tuple, np.ndarray] = field(default_factory=dict)
    los: dict[tuple, np.ndarray] = field(default_factory=dict)
    sids: dict[tuple, np.ndarray] = field(default_factory=dict)
    nans: dict[tuple, np.ndarray] = field(default_factory=dict)
    # host-evaluated predicate columns: pred_id -> (val[B], err[B])
    pred_vals: dict[int, np.ndarray] = field(default_factory=dict)
    pred_errs: dict[int, np.ndarray] = field(default_factory=dict)
    # string-list membership columns: path -> sids [B, L] / state [B]
    # (state 0=missing, 1=ok, 2=error)
    list_sids: dict[tuple, np.ndarray] = field(default_factory=dict)
    list_states: dict[tuple, np.ndarray] = field(default_factory=dict)
    # parsed-timestamp columns for paths used inside timestamp(...) calls:
    # path -> key halves [B] + state [B] (0=missing, 1=ok, 2=error)
    ts_his: dict[tuple, np.ndarray] = field(default_factory=dict)
    ts_los: dict[tuple, np.ndarray] = field(default_factory=dict)
    ts_states: dict[tuple, np.ndarray] = field(default_factory=dict)
    # request-stable now() as a batch-constant key (0-d arrays: value varies
    # per batch without retriggering jit tracing)
    now_hi: np.ndarray = field(default_factory=lambda: np.zeros((), dtype=np.int32))
    now_lo: np.ndarray = field(default_factory=lambda: np.zeros((), dtype=np.int32))
    # where the packer stored the scalar columns as whole matrices:
    # (paths, (3, P, B) int32 of his/los/sids, (P, B) int8 tags, (P, B) bool
    # nans), row i the column of paths[i]; the five dictionaries above then
    # hold row VIEWS of them. None where the columns were made one by one.
    scalars: Optional[tuple] = None


def resolve_path(input_obj: Any, path: tuple[str, ...]) -> tuple[bool, Any]:
    """Walk a path (e.g. ('resource','attr','status')) through a CheckInput.

    Returns (present, value). Intermediate misses → absent.
    """
    cur: Any = input_obj
    for seg in path:
        if isinstance(cur, dict):
            if seg not in cur:
                return False, None
            cur = cur[seg]
        else:
            if not hasattr(cur, seg):
                return False, None
            cur = getattr(cur, seg)
    return True, cur


def encode_value(v: Any, present: bool, interner: StringInterner) -> tuple[int, int, int, int, bool]:
    """→ (tag, hi, lo, sid, is_nan)."""
    if not present:
        return TAG_MISSING, 0, 0, 0, False
    if v is None:
        return TAG_NULL, 0, 0, 0, False
    if isinstance(v, bool):
        return TAG_BOOL, 1 if v else 0, 0, 0, False
    if isinstance(v, (int, float)):
        f = float(v)
        if f != f:
            return TAG_NUM, 0, 0, 0, True
        hi, lo = split_key(double_key(f))
        return TAG_NUM, hi, lo, 0, False
    if isinstance(v, str):
        return TAG_STR, 0, 0, interner.intern(v), False
    return TAG_OTHER, 0, 0, 0, False
