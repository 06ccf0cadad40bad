"""``BENCHMARK.json`` against the contract, every name resolved to its files,
and a configuration, a mix, a rate, a metric and a cell added as files."""

import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchmark_rig as rig  # noqa: E402

from benchmarks.lib import spec  # noqa: E402

BENCH = os.path.join(rig.REPO, "benchmarks")
with open(os.path.join(rig.REPO, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
WORKLOADS = [w["name"] for w in MANIFEST["workloads"]]
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")


def test_top_level_keys_and_sizes():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(rig.REPO, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(MANIFEST["run_seconds"], int) and 1 <= MANIFEST["run_seconds"] <= 51
    assert 1 <= len(MANIFEST["paths"]) <= 16 and all(PATH.match(p) and ".." not in p for p in MANIFEST["paths"])
    assert 1 <= len(MANIFEST["command"]) <= 32 and all(LINE.match(w) for w in MANIFEST["command"])
    assert MANIFEST["command"][-1].startswith(tuple(MANIFEST["paths"]))
    assert 1 <= len(MANIFEST["configs"]) <= 24 and 1 <= len(MANIFEST["workloads"]) <= 24
    assert 1 <= len(MANIFEST["end_to_end"]) <= 16 and 1 <= len(MANIFEST["per_layer"]) <= 128


def test_names_are_unique_and_well_formed():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(set(names)) == len(names)
    metrics = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    for name in metrics + WORKLOADS + [c["name"] for c in MANIFEST["configs"]]:
        assert spec.NAME.match(name), name
    for w in MANIFEST["workloads"]:
        assert spec.NAME.match(w["config"]) and spec.NAME.match(w["traffic"])
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)


@pytest.mark.parametrize("config", MANIFEST["configs"], ids=lambda c: c["name"])
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert LINE.match(config["source"]) and LINE.match(config["why"])
    assert config["file"].startswith(tuple(p + "/" for p in MANIFEST["paths"])) and PATH.match(config["file"])
    with open(os.path.join(rig.REPO, config["file"])) as f:
        body = json.load(f)
    assert body["name"] == config["name"] and body["reduced"] == config["reduced"] == []
    assert body["guarantees"] and body["layout"]["chips"] == 1 and body["assumed"]["server"]
    assert any(w["config"] == config["name"] for w in MANIFEST["workloads"])
    files = [c["file"] for c in MANIFEST["configs"]]
    sources = [c["source"] for c in MANIFEST["configs"]]
    assert len(set(files)) == len(files) and len(set(sources)) == len(sources)


@pytest.mark.parametrize("workload", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_workload_entry_resolves(workload):
    assert set(workload) == {"name", "config", "traffic", "chips", "why"}
    assert workload["chips"] == 1 and LINE.match(workload["why"])
    cell = spec.Cell(rig.REPO, workload["name"])
    assert cell.traffic["kind"] in spec.TRAFFIC_KINDS and cell.pair["rate"] > 0 and cell.traffic["connections"] >= 1
    waiter_s = cell.config["assumed"]["server"]["engine.tpu.requestTimeoutMs"]["value"] / 1000.0
    assert 42 < float(cell.traffic["deadline_s"]) < waiter_s  # over the longest compile seen, under the server's waiter timeout
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(cell.reader(m)) and m["moves"] in names


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_entry(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    assert spec.UNIT.match(metric["unit"]) and metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.25
    assert set(metric.get("workloads", WORKLOADS)) <= set(WORKLOADS)


@pytest.mark.parametrize("metric", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_per_layer_entry_matches_its_file(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    assert spec.UNIT.match(metric["unit"]) and metric["better"] in ("lower", "higher")
    assert metric["source"] in spec.SOURCES and LINE.match(metric["layer"])
    with open(os.path.join(BENCH, "metrics", metric["name"] + ".json")) as f:
        body = json.load(f)
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert body[key] == metric[key], key
    # which cells read it is not the file's to say: every cell that reports the metric it moves
    assert set(body) == {"name", "unit", "better", "source", "layer", "moves", "reader", "args"}
    spec.load_reader(BENCH, body["reader"])
    moved = next(m for m in MANIFEST["end_to_end"] if m["name"] == metric["moves"])
    readers = [w for w in WORKLOADS if w in moved.get("workloads", WORKLOADS)]
    assert readers and set(metric.get("workloads", readers)) <= set(readers)
    for w in metric.get("workloads", readers):
        assert metric["name"] in [m["name"] for m in spec.Cell(rig.REPO, w).per_layer]


def test_every_metric_file_is_in_the_manifest():
    files = {fn[: -len(".json")] for fn in os.listdir(os.path.join(BENCH, "metrics")) if fn.endswith(".json")}
    assert files == {m["name"] for m in MANIFEST["per_layer"]}


def test_files_under_paths_are_named_from_allowed_characters():
    for path in MANIFEST["paths"]:
        for dirpath, dirnames, filenames in os.walk(os.path.join(rig.REPO, path)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in filenames:
                assert PATH.match(os.path.relpath(os.path.join(dirpath, fn), rig.REPO)), fn


def test_peaks_table_knows_the_v5e_and_refuses_an_unknown_kind():
    assert spec.peaks(BENCH, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.peaks(BENCH, "TPU v9 imaginary")


def test_a_config_a_mix_a_rate_a_metric_and_a_cell_are_added_as_files(tmp_path):
    root = rig.copy_benchmark(str(tmp_path))
    before = {}
    for dirpath, _, filenames in os.walk(os.path.join(root, "benchmarks")):
        for fn in filenames:
            with open(os.path.join(dirpath, fn), "rb") as f:
                before[os.path.join(dirpath, fn)] = f.read()
    bench = os.path.join(root, "benchmarks")
    # a configuration, a traffic mix, a rate for the pair, a per-layer metric: four new files
    with open(os.path.join(bench, "configs", "classic-800.json")) as f:
        cfg = json.load(f)
    cfg.update(name="classic-40k", corpus={"generator": "classic", "mods": 5000})
    with open(os.path.join(bench, "configs", "classic-40k.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "pages.json")) as f:
        mix = json.load(f)
    mix.update(name="halfpages", request={"resources": [8, 25]}, by_config={})
    with open(os.path.join(bench, "traffic", "halfpages.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bench, "traffic", "rates", "classic-40k.halfpages.json"), "w") as f:
        json.dump({"rate": 33.0}, f)
    with open(os.path.join(bench, "metrics", "admission_mean_ms.pages.json")) as f:
        metric = json.load(f)
    metric.update(name="settle_mean_ms.halfpages", moves="halfpage_p50_ms")
    metric["args"] = {"metric": "cerbos_tpu_batch_stage_seconds", "labels": {"stage": "settle"}, "scale": 1000.0}
    with open(os.path.join(bench, "metrics", "settle_mean_ms.halfpages.json"), "w") as f:
        json.dump(metric, f)
    # ... and one workloads entry (with the entries the contract wants beside it)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["workloads"].append(
        {"name": "classic-40k.halfpages", "config": "classic-40k", "traffic": "halfpages", "chips": 1, "why": "test"}
    )
    manifest["end_to_end"].append(
        {"name": "halfpage_p50_ms", "unit": "ms", "better": "lower", "bound": 0.13, "source": "host_clock",
         "workloads": ["classic-40k.halfpages"]}
    )
    manifest["per_layer"].append({k: metric[k] for k in ("name", "unit", "better", "source", "layer", "moves")})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)

    cell = spec.Cell(root, "classic-40k.halfpages")
    assert cell.config["corpus"]["mods"] == 5000 and cell.traffic["request"] == {"resources": [8, 25]}
    assert cell.pair == {"rate": 33.0}
    assert [m["name"] for m in cell.per_layer] == ["settle_mean_ms.halfpages"]
    assert callable(cell.reader(cell.per_layer[0]))
    assert {m["name"] for m in cell.end_to_end} == {"halfpage_p50_ms", "setup_s"}
    # no file that was there was edited
    for path, content in before.items():
        with open(path, "rb") as f:
            assert f.read() == content, path


def test_a_cell_on_a_mix_that_is_there_gets_the_metrics_of_that_mix(tmp_path):
    """``classic-8k.sidecar``, the first of PERF.md's further cells: a rate file,
    a ``workloads`` entry, and its name under ``check_p50_ms``; no metric file
    is edited or copied."""
    root = rig.copy_benchmark(str(tmp_path))
    with open(os.path.join(root, "benchmarks", "traffic", "rates", "classic-8k.sidecar.json"), "w") as f:
        json.dump({"rate": 300}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["workloads"].append({"name": "classic-8k.sidecar", "config": "classic-8k", "traffic": "sidecar", "chips": 1, "why": "t"})
    next(m for m in manifest["end_to_end"] if m["name"] == "check_p50_ms")["workloads"].append("classic-8k.sidecar")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    new, twin = spec.Cell(root, "classic-8k.sidecar"), spec.Cell(root, "classic-800.sidecar")
    assert new.pair == {"rate": 300} and new.config["corpus"]["mods"] == 1000
    assert [m["name"] for m in new.per_layer] == [m["name"] for m in twin.per_layer] and len(new.per_layer) >= 12
    assert all(m["moves"] == "check_p50_ms" for m in new.per_layer)
    assert not {m["name"] for m in new.per_layer} & {m["name"] for m in spec.Cell(root, "classic-8k.pages").per_layer}


def test_a_pair_without_parameters_is_refused(tmp_path):
    root = rig.copy_benchmark(str(tmp_path))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["workloads"].append({"name": "classic-8k.sidecar", "config": "classic-8k", "traffic": "sidecar", "chips": 1, "why": "t"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    with pytest.raises(spec.SpecError, match="rates"):
        spec.Cell(root, "classic-8k.sidecar")
