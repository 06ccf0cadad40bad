"""The system under test as a child process: ``python -m cerbos_tpu.cli server``.

Copied from ``chip_smoke.py``'s ``ServerProc`` (which later PRs may change) and
cut to the single-process topology the cells run. The process that uses this
never imports jax: the chip belongs to the server.
"""

from __future__ import annotations

import base64
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

from . import corpus, prom
from .workload import JWT_SECRET

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class HarnessError(Exception):
    """The run cannot give a result; the harness exits non-zero with no last line."""


def write_policies(policy_dir: str, docs: list[str], mods: int) -> int:
    """One policy document per file plus the schemas."""
    os.makedirs(policy_dir, exist_ok=True)
    for i, doc in enumerate(docs):
        with open(os.path.join(policy_dir, f"policy_{i:05d}.yaml"), "w") as f:
            f.write(doc)
    schema_dir = os.path.join(policy_dir, "_schemas")
    os.makedirs(schema_dir, exist_ok=True)
    for name, data in corpus.schemas(mods).items():
        with open(os.path.join(schema_dir, name), "wb") as f:
            f.write(data)
    return len(docs)


def _set_dotted(tree: dict, dotted: str, value) -> None:
    *path, leaf = dotted.split(".")
    for part in path:
        tree = tree.setdefault(part, {})
    tree[leaf] = value


class ServerProc:
    def __init__(self, work_dir: str, policy_dir: str, settings: dict, log):
        """``settings``: dotted config key -> value, beyond addresses, storage
        and the key set of the requests' tokens (the configuration file's
        ``assumed.server`` entries, and the profiler in a traced run)."""
        import yaml

        self.log = log
        self.stderr_path = os.path.join(work_dir, "server.stderr")
        cfg = {
            "server": {"httpListenAddr": "127.0.0.1:0", "grpcListenAddr": "127.0.0.1:0"},
            "storage": {"driver": "disk", "disk": {"directory": policy_dir}},
            "engine": {"tpu": {"enabled": True}},
            "auxData": {
                "jwt": {
                    "keySets": [
                        {
                            "id": "default",
                            "algorithm": "HS256",
                            "local": {"data": base64.b64encode(JWT_SECRET).decode()},
                        }
                    ]
                }
            },
        }
        for key, value in settings.items():
            _set_dotted(cfg, key, value)
        cfg_path = os.path.join(work_dir, "cerbos.yaml")
        with open(cfg_path, "w") as f:
            yaml.safe_dump(cfg, f)
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        self._stderr = open(self.stderr_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "cerbos_tpu.cli", "server", "--config", cfg_path],
            stdout=subprocess.PIPE,
            stderr=self._stderr,
            text=True,
            env=env,
            cwd=REPO,
        )
        self.http_port = self.grpc_port = 0
        self.native = None
        self._serving_line = ""
        self._serving = threading.Event()
        self._pump = threading.Thread(target=self._pump_stdout, daemon=True)
        self._pump.start()

    def _pump_stdout(self) -> None:
        for line in self.proc.stdout:
            line = line.rstrip("\n")
            self.log(f"[server] {line}")
            if line.startswith("cerbos-tpu serving:"):
                self._serving_line = line
                self._serving.set()

    def wait_serving(self, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        while not self._serving.wait(0.1):
            if self.proc.poll() is not None:
                raise HarnessError(f"server exited {self.proc.returncode} before announcing ports")
            if time.monotonic() >= deadline:
                raise HarnessError(f"no 'cerbos-tpu serving:' line within {timeout:.0f} s")
        fields = dict(t.split("=", 1) for t in self._serving_line.split() if "=" in t)
        self.http_port = int(fields["http"])
        self.grpc_port = int(fields["grpc"])
        self.native = fields.get("native")
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(self.url("/_cerbos/ready"), timeout=2) as r:
                    if r.status == 200:
                        return
            except OSError:
                pass
            if self.proc.poll() is not None:
                raise HarnessError(f"server exited {self.proc.returncode} before becoming ready")
            time.sleep(0.1)
        raise HarnessError(f"server not ready within {timeout:.0f} s")

    def url(self, path: str) -> str:
        return f"http://127.0.0.1:{self.http_port}{path}"

    def get_json(self, path: str, timeout: float = 60):
        with urllib.request.urlopen(self.url(path), timeout=timeout) as r:
            return json.loads(r.read())

    def status(self) -> dict:
        """The device owner's boot status, from ``X-Cerbos-Jitcache``."""
        with urllib.request.urlopen(self.url("/_cerbos/debug/flight"), timeout=30) as r:
            r.read()
            return json.loads(r.headers.get("X-Cerbos-Jitcache") or "{}")

    def scrape(self) -> tuple[prom.Scrape, str]:
        # the hot-rule recorder folds decision_source_total every 256
        # decisions or on snapshot: ask for one so the counters are current
        with urllib.request.urlopen(self.url("/_cerbos/debug/hotrules?k=1"), timeout=30) as r:
            r.read()
        with urllib.request.urlopen(self.url("/_cerbos/metrics"), timeout=30) as r:
            text = r.read().decode()
        return prom.parse(text), text

    def stop(self) -> int | None:
        """SIGTERM, wait; the exit code (None = had to be killed)."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.kill()
                return None
        self._pump.join(timeout=5)
        self._stderr.close()
        return self.proc.returncode

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        self._pump.join(timeout=5)
        if not self._stderr.closed:
            self._stderr.close()

    def stderr_tail(self, n: int = 40) -> str:
        try:
            with open(self.stderr_path, errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""
