"""Configuration: single YAML file with env interpolation + overrides.

Behavioral reference: internal/config/config.go — one YAML document, env
var interpolation (``${VAR}`` / ``${VAR:default}``), per-section access, CLI
``--set key=value`` overrides merged on top, sensible defaults.
"""

from __future__ import annotations

import os
import re
from typing import Any, Optional

import yaml

_ENV_RX = re.compile(r"\$\{([A-Za-z_][A-Za-z0-9_]*)(?::([^}]*))?\}")

DEFAULTS: dict[str, Any] = {
    "server": {
        "httpListenAddr": "0.0.0.0:3592",
        "grpcListenAddr": "0.0.0.0:3593",
        "requestLimits": {"maxActionsPerResource": 50, "maxResourcesPerRequest": 50},
        "adminAPI": {"enabled": False},
    },
    "engine": {
        "defaultPolicyVersion": "default",
        "defaultScope": "",
        "lenientScopeSearch": False,
        "globals": {},
        "tpu": {
            "enabled": True,
            "batchThreshold": 5,
            "maxRoles": 8,
            "maxCandidates": 32,
            "maxDepth": 8,
            # a batch up to pipelineChunk is one device call (a larger, direct
            # one is cut into chunks of that size); inflightDepth is how many
            # flights the batcher and each lane keep in flight
            "pipelineChunk": 4096,
            "inflightDepth": 3,
            # device-path fault domain (docs/ROBUSTNESS.md): circuit breaker
            # routing check() to the CPU oracle while the device is unhealthy,
            # poison-input quarantine bound, and the fault-injection spec
            # (same grammar as the CERBOS_TPU_FAULTS env var, which wins)
            "breaker": {
                "enabled": True,
                "failureThreshold": 5,
                "timeoutRateThreshold": 0.5,
                "timeoutWindowSeconds": 30,
                "timeoutMinSamples": 10,
                "probeBackoffBaseMs": 500,
                "probeBackoffCapMs": 30000,
                "probeTimeoutMs": 5000,
            },
            "quarantineMax": 128,
            "faults": "",
            # sharded serving pool: drive the full device mesh from the
            # batcher. shards=0 keeps the single-evaluator path; shards=N
            # (or "auto" = one per visible device) builds N batcher lanes,
            # each with its own device-pinned evaluator clone, breaker,
            # quarantine set, and flight-recorder lane. perShardInflight=0
            # inherits inflightDepth; routing: least_loaded | round_robin
            "mesh": {
                "shards": 0,
                "perShardInflight": 0,
                "routing": "least_loaded",
            },
            # front-door ticket queue (server.frontends > 0): transport
            # "shm" runs native shared-memory frame rings per front end
            # (auto-falling back to uds when the native module is missing
            # on either side); "uds" forces marshal frames over the socket
            "sharedBatcher": {
                "socketPath": "",
                "transport": "shm",
                "ringKiB": 1024,
                "requestTimeoutMs": 30000,
                "maxOutstanding": 4096,
                "statusPollMs": 500,
            },
            # bounded ring of recent device-batch records + fault events,
            # served at /_cerbos/debug/flight and dumped on SIGQUIT
            "flightRecorder": {"enabled": True, "capacity": 256},
            # bootstrap warmup: pre-compile the dominant device layouts
            # before /_cerbos/ready opens the gates (docs/OBSERVABILITY.md,
            # "Compile economy"). synthetic: optional explicit corpus of
            # {kind, actions, roles} entries; empty derives one from the
            # loaded rule table
            "warmup": {
                "enabled": False,
                "batchSizes": [16, 64],
                "background": True,
                "timeoutSeconds": 120,
                "maxKinds": 8,
                "synthetic": [],
            },
            # operator-gated /_cerbos/debug/profile?seconds=N endpoint:
            # captures a jax.profiler.trace into a bounded directory
            "profiler": {
                "enabled": False,
                "dir": "",
                "maxArtifacts": 4,
                "maxSeconds": 30,
            },
            # per-request latency-budget waterfall + goodput accounting:
            # stage histograms, decisions_total{outcome}, and the bounded
            # slow-request ring at /_cerbos/debug/slow
            "latencyBudget": {
                "enabled": True,
                "slowRingCapacity": 64,
                "slowThresholdMs": 250,
            },
            # saturation pressure signals: rolling 0..1 components + the
            # cerbos_tpu_pressure_score gauge and /_cerbos/debug/pressure
            "pressure": {
                "enabled": True,
                "intervalMs": 500,
                "windowSec": 30,
            },
        },
    },
    # overload control (docs/ROBUSTNESS.md, "Overload & brownout"): front-door
    # admission (token bucket + concurrency caps per priority class, compiled
    # once at bootstrap like the rule table) and the staged brownout ladder
    # driven by the pressure score. classes=[] keeps a single "default" class;
    # each class entry: {name, priority, weight, match: {principals, roles,
    # kinds, apis}, rate, burst, maxConcurrent, queueBudget, sheddable}
    "overload": {
        "enabled": True,
        "default": {},
        "classes": [],
        "brownout": {
            "enabled": True,
            "hysteresis": 0.05,
            "holdSeconds": 2.0,
            "stages": [
                {"name": "shed_audit", "enterAbove": 0.85},
                {"name": "shed_parity", "enterAbove": 0.90},
                {"name": "shed_plan", "enterAbove": 0.95},
                {"name": "shed_low_priority", "enterAbove": 0.98},
            ],
        },
    },
    "storage": {"driver": "disk", "disk": {"directory": "policies", "watchForChanges": False}},
    "schema": {"enforcement": "none"},
    "audit": {"enabled": False, "backend": "local"},
    "auxData": {"jwt": {"keySets": []}},
    "telemetry": {"disabled": True},
}


def _interpolate(value: Any) -> Any:
    if isinstance(value, str):
        def sub(m: re.Match) -> str:
            return os.environ.get(m.group(1), m.group(2) if m.group(2) is not None else "")

        return _ENV_RX.sub(sub, value)
    if isinstance(value, dict):
        return {k: _interpolate(v) for k, v in value.items()}
    if isinstance(value, list):
        return [_interpolate(v) for v in value]
    return value


def _deep_merge(base: dict, overlay: dict) -> dict:
    out = dict(base)
    for k, v in overlay.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _deep_merge(out[k], v)
        else:
            out[k] = v
    return out


def _parse_set(expr: str) -> tuple[list[str], Any]:
    key, _, raw = expr.partition("=")
    try:
        value = yaml.safe_load(raw)
    except yaml.YAMLError:
        value = raw
    return key.strip().split("."), value


class Config:
    def __init__(self, data: dict[str, Any]):
        self.data = data

    @classmethod
    def load(cls, path: Optional[str] = None, overrides: Optional[list[str]] = None) -> "Config":
        import copy

        data: dict[str, Any] = {}
        if path:
            with open(path, encoding="utf-8") as f:
                data = yaml.safe_load(f) or {}
        # deep-copy the defaults: _deep_merge shares untouched subtrees with
        # its inputs, and --set overrides mutate nested dicts in place — a
        # shared DEFAULTS would leak overrides across Config.load calls
        data = _deep_merge(copy.deepcopy(DEFAULTS), _interpolate(data))
        for expr in overrides or []:
            keys, value = _parse_set(expr)
            cur = data
            for k in keys[:-1]:
                cur = cur.setdefault(k, {})
            cur[keys[-1]] = value
        return cls(data)

    def section(self, name: str) -> dict[str, Any]:
        v = self.data.get(name, {})
        return v if isinstance(v, dict) else {}

    def get(self, dotted: str, default: Any = None) -> Any:
        cur: Any = self.data
        for k in dotted.split("."):
            if not isinstance(cur, dict) or k not in cur:
                return default
            cur = cur[k]
        return cur
