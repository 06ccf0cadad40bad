"""What the benchmark reads of a front-door pool (PR 32), on hand-written
scrapes: the reader ``label_share_max`` and the metric files of the two
``classic-800-pool4`` cells, as a pool's one scrape holds them (every front
end's series under its ``worker`` label, the owner's under ``batcher``), and
their entries in ``BENCHMARK.json``. No server: ``tests/benchmark/test_bench_pool.py``
boots the pool under the harness."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.lib import prom, spec  # noqa: E402

BENCH = os.path.join(REPO, "benchmarks")
HANDLED = "cerbos_tpu_request_handler_seconds_count"
STAGES = "cerbos_tpu_request_stage_seconds"
RTT = "cerbos_tpu_ipc_client_rtt_seconds"
CELLS = {"sidecar-fanin": ("classic-800-pool4.sidecar-fanin", "check_p50_ms"), "pages-fanin": ("classic-800-pool4.pages-fanin", "page_p50_ms")}
TWINS = ("flight_inputs_mean", "window_wait_mean_ms", "batcher_busy_share", "batcher_cpu_share")
IPC = ("ipc_encode_mean_ms", "ipc_transit_mean_ms", "ipc_return_mean_ms", "ipc_rtt_mean_ms", "frontend_share_max")
NEW = [f"{base}.sidecar-fanin" for base in TWINS] + [f"{base}.{mix}" for mix in CELLS for base in IPC]


def ctx(before: str, after: str) -> dict:
    return {"before": prom.parse(before), "after": prom.parse(after)}


def handled(fe1: float, fe2: float, fe3: float, bare: float = 0.0) -> str:
    return (
        f'{HANDLED}{{worker="fe1"}} {fe1}\n{HANDLED}{{worker="fe2"}} {fe2}\n{HANDLED}{{worker="fe3"}} {fe3}\n'
        f"{HANDLED} {bare}\n"
    )


@pytest.mark.parametrize(
    "after, want",
    [
        (handled(110, 120, 130), pytest.approx(100 / 3)),  # an even spread of three
        (handled(10, 320, 30), 100.0),  # one front end took every request
        (handled(160, 120, 80), 50.0),
        (handled(10, 20, 30), None),  # the counter did not move
        (handled(10, 20, 30, bare=500), None),  # a series without the label is not a front end's
    ],
    ids=["even", "one_takes_all", "half", "still", "unlabelled_only"],
)
def test_label_share_max_is_the_busiest_labels_share_of_the_growth(after, want):
    read = spec.load_reader(BENCH, "label_share_max")
    assert read(ctx(handled(10, 20, 30), after), metric=HANDLED, label="worker") == want


def test_label_share_max_adds_a_labels_series_and_needs_the_label():
    read = spec.load_reader(BENCH, "label_share_max")
    before = 'm{worker="fe1",kind="a"} 0\nm{worker="fe2",kind="a"} 0\nm{worker="fe2",kind="b"} 0\n'
    after = 'm{worker="fe1",kind="a"} 30\nm{worker="fe2",kind="a"} 10\nm{worker="fe2",kind="b"} 960\n'
    assert read(ctx(before, after), metric="m", label="worker") == 97.0
    assert read(ctx(before, after), metric="m", label="kind") == 96.0
    assert read(ctx(before, after), metric="absent", label="worker") is None
    assert read(ctx(before, after), metric="m", label="shard") is None  # no series carries the label


def read_metric(name: str, before: str, after: str):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        body = json.load(f)
    return spec.load_reader(BENCH, body["reader"])(ctx(before, after), **body["args"])


def pool_scrape(n: float) -> str:
    """``n`` requests since boot, a third on each front end: stage sums in
    seconds (0.5 ms encode, 0.2 transit, 0.1 return, 2.0 round trip each), and
    the owner's flights of 1.25 checks with a drain thread at work 40% of the
    wall time, on the CPU for three quarters of that."""
    lines = []
    for fe in ("fe1", "fe2", "fe3"):
        k = n / 3
        lines.append(f'{HANDLED}{{worker="{fe}"}} {k}')
        for stage, each in (("ipc_encode", 0.0005), ("transit", 0.0002), ("ipc_return", 0.0001), ("queue_wait", 0.0003)):
            lines.append(f'{STAGES}_sum{{stage="{stage}",worker="{fe}"}} {k * each}')
            lines.append(f'{STAGES}_count{{stage="{stage}",worker="{fe}"}} {k}')
        lines.append(f'{RTT}_sum{{transport="shm",worker="{fe}"}} {k * 0.002}')
        lines.append(f'{RTT}_count{{transport="shm",worker="{fe}"}} {k}')
    flights = n / 1.25
    lines += [
        f'cerbos_tpu_batcher_batch_size_sum{{worker="batcher"}} {n}',
        f'cerbos_tpu_batcher_batch_size_count{{worker="batcher"}} {flights}',
        'cerbos_tpu_batcher_window_wait_seconds_sum{worker="batcher"} 0',
        f'cerbos_tpu_batcher_window_wait_seconds_count{{worker="batcher"}} {flights}',
    ]
    for state, kind, wall, cpu in (("idle", "wait", 0.6, 0.0), ("oracle", "work", 0.3, 0.2), ("other", "work", 0.1, 0.1)):
        for clock, v in (("wall", wall), ("cpu", cpu)):
            lines.append(
                f'cerbos_tpu_batcher_thread_seconds_total{{state="{state}",kind="{kind}",clock="{clock}",worker="batcher"}} {v * n / 100}'
            )
    return "\n".join(lines) + "\n"


WANT = {
    "ipc_encode_mean_ms": 0.5, "ipc_transit_mean_ms": 0.2, "ipc_return_mean_ms": 0.1, "ipc_rtt_mean_ms": 2.0,
    "frontend_share_max": 100 / 3, "flight_inputs_mean": 1.25, "window_wait_mean_ms": 0.0,
    "batcher_busy_share": 40.0, "batcher_cpu_share": 75.0,
}
SINGLE = 'cerbos_tpu_request_stage_seconds_sum{stage="admission"} 4\ncerbos_tpu_request_stage_seconds_count{stage="admission"} 9\n'


@pytest.mark.parametrize("name", NEW)
def test_pool_metric_file_reads_a_pools_scrape(name):
    assert read_metric(name, pool_scrape(300), pool_scrape(28_300)) == pytest.approx(WANT[name.split(".")[0]])


@pytest.mark.parametrize("name", NEW)
def test_pool_metric_file_reads_nothing_where_there_is_no_pool(name):
    """A single process has no ``worker`` label and no ipc stage, and a window
    that moved nothing has no mean: the line leaves the metric out."""
    assert read_metric(name, SINGLE, SINGLE) is None
    assert read_metric(name, pool_scrape(300), pool_scrape(300)) is None


@pytest.mark.parametrize("name", NEW)
def test_pool_metric_is_in_the_manifest_for_its_one_cell(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    cell, moves = CELLS[name.split(".", 1)[1]]
    assert entry["workloads"] == [cell] and entry["moves"] == moves and entry["layer"] == "Front door"
    assert name in [m["name"] for m in spec.Cell(REPO, cell).per_layer]
    other = next(c for c, _ in CELLS.values() if c != cell)
    assert name not in [m["name"] for m in spec.Cell(REPO, other).per_layer]


def test_the_four_that_fell_silent_are_held_to_the_single_process_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for base in TWINS:
        assert per_layer[f"{base}.sidecar"]["workloads"] == ["classic-800.sidecar"]
        assert "workloads" not in per_layer[f"{base}.pages"]  # live in every pages cell, the pool's too
    fanin = {m["name"] for m in spec.Cell(REPO, "classic-800-pool4.sidecar-fanin").per_layer}
    assert not fanin & {f"{base}.sidecar" for base in TWINS} and {"inline_share.sidecar", "oracle_share.sidecar"} <= fanin
    pages = {m["name"] for m in spec.Cell(REPO, "classic-800-pool4.pages-fanin").per_layer}
    assert {f"{base}.pages" for base in TWINS} | {"inline_share.pages", "device_calls_mean.pages"} <= pages
