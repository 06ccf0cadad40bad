"""Local queryable audit backend (SQLite, TTL retention).

Behavioral reference: internal/audit/local/badgerdb.go — embedded queryable
store with retention; entries listable through the Admin API
(ListAuditLogEntries).
"""

from __future__ import annotations

import datetime
import json
import sqlite3
import threading
import uuid
from typing import Optional

from .log import register_backend

_SCHEMA = """
CREATE TABLE IF NOT EXISTS audit_entries (
    id TEXT PRIMARY KEY,
    kind TEXT NOT NULL,
    ts TEXT NOT NULL,
    entry TEXT NOT NULL
);
CREATE INDEX IF NOT EXISTS idx_audit_ts ON audit_entries (ts);
"""


class LocalBackend:
    def __init__(self, storage_path: str = ":memory:", retention_days: float = 7.0):
        self.retention_days = retention_days
        self._lock = threading.Lock()
        self._conn = sqlite3.connect(storage_path, check_same_thread=False)
        with self._lock:
            self._conn.executescript(_SCHEMA)
            self._conn.commit()

    def write(self, entry: dict) -> None:
        # a call's access entry and its decision entry share the call id
        # (upstream keys them under a prefix per kind): the row's id is both
        kind = entry.get("kind", "")
        with self._lock:
            self._conn.execute(
                "INSERT OR REPLACE INTO audit_entries (id, kind, ts, entry) VALUES (?, ?, ?, ?)",
                (f"{kind}/{entry.get('callId') or uuid.uuid4().hex}", kind, entry.get("timestamp", ""), json.dumps(entry, default=str)),
            )
            self._conn.commit()
        self._maybe_expire()

    def _maybe_expire(self) -> None:
        cutoff = (
            datetime.datetime.now(datetime.timezone.utc) - datetime.timedelta(days=self.retention_days)
        ).isoformat()
        with self._lock:
            self._conn.execute("DELETE FROM audit_entries WHERE ts < ?", (cutoff,))
            self._conn.commit()

    def query(self, kind: str = "decision", limit: int = 100, since: Optional[str] = None) -> list[dict]:
        q = "SELECT entry FROM audit_entries WHERE kind = ?"
        args: list = [kind]
        if since:
            q += " AND ts >= ?"
            args.append(since)
        q += " ORDER BY ts DESC LIMIT ?"
        args.append(limit)
        with self._lock:
            rows = self._conn.execute(q, args).fetchall()
        return [json.loads(r[0]) for r in rows]

    def close(self) -> None:
        with self._lock:
            self._conn.close()


register_backend("local", lambda conf: LocalBackend(
    storage_path=conf.get("storagePath", ":memory:"),
    retention_days=float(conf.get("retentionPeriodDays", 7.0)),
))
