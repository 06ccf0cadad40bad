"""``BENCHMARK.json`` and the data files it names: resolution and checks.

A cell is resolved by names alone, so a later PR adds a configuration, a
traffic mix, a rate, a per-layer metric or a cell by adding files and one
``workloads`` entry:

- ``config``  -> ``benchmarks/configs/<config>.json``
- ``traffic`` -> ``benchmarks/traffic/<traffic>.json``; its per-configuration
  parameters (``by_config``) are keyed by configuration name, and a pair the
  file does not have is read from
  ``benchmarks/traffic/rates/<config>.<traffic>.json``
- per-layer metrics -> by the rule of ``BENCHMARK.json`` itself: every
  ``per_layer`` entry whose ``moves`` is an end-to-end metric that the cell
  reports (and whose ``workloads``, where it has the key, name the cell). So a
  new cell that lists itself under ``page_p50_ms`` gets every metric that
  moves it. The entry's reader and its arguments are in
  ``benchmarks/metrics/<name>.json``; the reader is
  ``benchmarks/readers/<reader>.py``
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TRAFFIC_KINDS = ("open_poisson",)


class SpecError(Exception):
    pass


def _load(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise SpecError(f"cannot read {path}: {e}") from None
    except ValueError as e:
        raise SpecError(f"{path} is not JSON: {e}") from None


class Cell:
    def __init__(self, root: str, workload: str):
        """``root``: the checkout (holds BENCHMARK.json and ``benchmarks/``)."""
        self.root = root
        self.bench_dir = os.path.join(root, "benchmarks")
        self.manifest = _load(os.path.join(root, "BENCHMARK.json"))
        entries = [w for w in self.manifest["workloads"] if w["name"] == workload]
        if len(entries) != 1:
            raise SpecError(f"BENCHMARK.json has {len(entries)} workloads named {workload!r}")
        self.entry = entries[0]
        self.name = workload
        self.chips = int(self.entry["chips"])
        self.config_name = self.entry["config"]
        self.traffic_name = self.entry["traffic"]
        self.config = _load(os.path.join(self.bench_dir, "configs", f"{self.config_name}.json"))
        self.traffic = _load(os.path.join(self.bench_dir, "traffic", f"{self.traffic_name}.json"))
        if self.traffic.get("kind") not in TRAFFIC_KINDS:
            raise SpecError(f"traffic {self.traffic_name!r}: kind must be one of {TRAFFIC_KINDS}")
        self.pair = self._pair_parameters()
        self.end_to_end = [m for m in self.manifest["end_to_end"] if self._applies(m)]
        self.per_layer = self._metric_files()

    def _applies(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def _pair_parameters(self) -> dict:
        """What the traffic file fixes for this configuration: ``{"rate": r}``."""
        by_config = self.traffic.get("by_config", {})
        if self.config_name in by_config:
            return dict(by_config[self.config_name])
        path = os.path.join(self.bench_dir, "traffic", "rates", f"{self.config_name}.{self.traffic_name}.json")
        if os.path.exists(path):
            return _load(path)
        raise SpecError(
            f"traffic {self.traffic_name!r} has no parameters for configuration {self.config_name!r}: "
            f"add them to {path}"
        )

    def _metric_files(self) -> list[dict]:
        reported = {m["name"] for m in self.end_to_end}
        out = []
        for entry in self.manifest["per_layer"]:
            if entry["moves"] not in reported or not self._applies(entry):
                continue
            m = _load(os.path.join(self.bench_dir, "metrics", f"{entry['name']}.json"))
            if m.get("name") != entry["name"]:
                raise SpecError(f"metrics/{entry['name']}.json: its name is {m.get('name')!r}")
            out.append(m)
        return out

    def reader(self, metric: dict):
        return load_reader(self.bench_dir, metric["reader"])


def load_reader(bench_dir: str, reader: str):
    if not NAME.match(reader):
        raise SpecError(f"reader name {reader!r}")
    path = os.path.join(bench_dir, "readers", f"{reader}.py")
    if not os.path.exists(path):
        raise SpecError(f"no reader {path}")
    spec = importlib.util.spec_from_file_location(f"benchmarks_reader_{reader}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{path} defines no read(ctx, **args)")
    return mod.read


def peaks(bench_dir: str, device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    table = _load(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table["devices"]:
        raise SpecError(f"benchmarks/peaks.json has no device kind {device_kind!r}")
    return table["devices"][device_kind]
