"""File audit backend: JSON lines to a file or stdout.

Behavioral reference: internal/audit/file/log.go (zap-based JSON file
sink) and its ``logRotation`` block (internal/audit/file/conf.go, lumberjack
underneath): ``maxFileSizeMB``, ``maxFileCount``, ``maxFileAgeDays``.

Rotation is size-triggered and runs on the thread that writes (the audit
writer): when the next line would take the file past ``maxFileSizeMB`` the
file is renamed to ``<stem>-<UTC timestamp><ext>`` (lumberjack's naming), a
new one is opened at the path, and rotated files beyond ``maxFileCount`` (or
older than ``maxFileAgeDays``) are deleted, oldest first. So the path holds
at most ``maxFileCount`` + 1 files of at most ``maxFileSizeMB``, and a line
is never split across two. A file found over the limit at open is rotated at
once. A line is one ``write(2)`` on an ``O_APPEND`` descriptor, so several
processes of a pool appending to one path do not interleave; each looks
before a line whether the path still names the file it holds (another
process rotated it: reopen) and rotates under a lock on the directory.
"""

from __future__ import annotations

import datetime
import fcntl
import json
import os
import sys
import threading
import time
from typing import Optional, TextIO

from .log import register_backend

_STAMP = "%Y-%m-%dT%H-%M-%S.%f"  # of a rotated file's name, UTC
_STREAMS = {"stdout": lambda: sys.stdout, "-": lambda: sys.stdout, "stderr": lambda: sys.stderr}


class FileBackend:
    def __init__(self, path: str = "stdout", rotation: Optional[dict] = None):
        self.path = path
        self._lock = threading.Lock()
        self._stream: Optional[TextIO] = _STREAMS[path]() if path in _STREAMS else None
        self._fd = -1
        rotation = rotation or {}
        self._max_bytes = int(float(rotation.get("maxFileSizeMB", 0) or 0) * (1 << 20))
        self._max_files = int(rotation.get("maxFileCount", 0) or 0)
        self._max_age_s = float(rotation.get("maxFileAgeDays", 0) or 0) * 86400.0
        if self._stream is not None:
            return
        from ..observability import metrics

        self.m_rotations = metrics().counter(
            "cerbos_tpu_audit_rotations_total",
            "times the file audit backend rotated its file (audit.file.logRotation.maxFileSizeMB reached)",
        )
        self._dir = os.path.dirname(os.path.abspath(path))
        self._stem, self._ext = os.path.splitext(os.path.basename(path))
        os.makedirs(self._dir, exist_ok=True)
        self._open()
        if self._max_bytes and os.fstat(self._fd).st_size > self._max_bytes:
            self._rotate(0)

    def _open(self) -> None:
        if self._fd >= 0:
            os.close(self._fd)
        self._fd = os.open(self.path, os.O_WRONLY | os.O_APPEND | os.O_CREAT, 0o644)

    def rotated_files(self) -> list[str]:
        """The rotated files beside the path, oldest first (their names sort by time)."""
        head, out = self._stem + "-", []
        for n in os.listdir(self._dir):
            if n.startswith(head) and n.endswith(self._ext):
                try:
                    datetime.datetime.strptime(n[len(head) : len(n) - len(self._ext)], _STAMP)
                except ValueError:
                    continue
                out.append(os.path.join(self._dir, n))
        return sorted(out)

    def _rotate(self, incoming: int) -> None:
        """Under a lock on the directory, so that of several processes one
        rotates: the others find the path under the limit again and reopen."""
        dir_fd = os.open(self._dir, os.O_RDONLY)
        try:
            fcntl.flock(dir_fd, fcntl.LOCK_EX)
            try:
                size = os.stat(self.path).st_size
            except FileNotFoundError:
                size = 0
            if size and size + incoming > self._max_bytes:
                stamp = datetime.datetime.now(datetime.timezone.utc)
                while True:
                    name = f"{self._stem}-{stamp.strftime(_STAMP)}{self._ext}"
                    target = os.path.join(self._dir, name)
                    if not os.path.exists(target):
                        break
                    stamp += datetime.timedelta(microseconds=1)
                os.rename(self.path, target)
                self.m_rotations.inc()
                old = self.rotated_files()
                cut = time.time() - self._max_age_s
                for k, path in enumerate(old):
                    beyond = self._max_files and k < len(old) - self._max_files
                    if beyond or (self._max_age_s and os.stat(path).st_mtime < cut):
                        os.unlink(path)
            self._open()
        finally:
            os.close(dir_fd)  # releases the lock

    def _held_file_is_at_path(self, held: os.stat_result) -> bool:
        try:
            at_path = os.stat(self.path)
        except FileNotFoundError:
            return False
        return (at_path.st_ino, at_path.st_dev) == (held.st_ino, held.st_dev)

    def write(self, entry: dict) -> int:
        """Returns the bytes of the line."""
        line = json.dumps({"log.logger": "cerbos.audit", **entry}, separators=(",", ":"), default=str)
        with self._lock:
            if self._stream is not None:
                self._stream.write(line + "\n")
                self._stream.flush()
                return len(line) + 1
            data = (line + "\n").encode("utf-8")
            if self._max_bytes:
                held = os.fstat(self._fd)
                if not self._held_file_is_at_path(held):
                    self._open()
                    held = os.fstat(self._fd)
                if held.st_size and held.st_size + len(data) > self._max_bytes:
                    self._rotate(len(data))
            view = memoryview(data)
            while view:
                view = view[os.write(self._fd, view) :]
            return len(data)

    def close(self) -> None:
        with self._lock:
            if self._fd >= 0:
                os.close(self._fd)
                self._fd = -1


register_backend(
    "file", lambda conf: FileBackend(path=conf.get("path", "stdout"), rotation=conf.get("logRotation"))
)
