"""Percentiles, histogram-delta means and the readers, against hand-worked values."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchmark_rig as rig  # noqa: E402

from benchmarks.lib import prom, spec, stats  # noqa: E402

BENCH = os.path.join(rig.REPO, "benchmarks")


@pytest.mark.parametrize(
    "values, p, want",
    [
        ([5, 1, 4, 2, 3], 50, 3),  # rank ceil(2.5) = 3 of 1..5
        ([5, 1, 4, 2, 3], 95, 5),  # rank ceil(4.75) = 5
        ([5, 1, 4, 2, 3], 20, 1),  # rank 1 exactly
        (list(range(1, 101)), 95, 95),
        (list(range(1, 101)), 99, 99),
        ([7.5], 99, 7.5),
        ([2, 2, 9, 9], 50, 2),  # rank 2: no interpolation between 2 and 9
    ],
)
def test_percentile_is_nearest_rank(values, p, want):
    assert stats.percentile(values, p) == want


def test_percentile_and_share_refuse_an_empty_sample():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.share_within([], 1.0)


def test_share_within():
    assert stats.share_within([1.0, 11.62, 11.63, 40.0], 11.62) == 50.0


BEFORE = """
# TYPE cerbos_tpu_request_stage_seconds histogram
cerbos_tpu_request_stage_seconds_bucket{stage="admission",le="0.001"} 2
cerbos_tpu_request_stage_seconds_sum{stage="admission"} 0.010
cerbos_tpu_request_stage_seconds_count{stage="admission"} 4
cerbos_tpu_batch_stage_seconds_sum{stage="pack"} 1.0
cerbos_tpu_batch_stage_seconds_count{stage="pack"} 10
cerbos_tpu_batch_stage_seconds_sum{stage="submit"} 2.0
cerbos_tpu_batch_stage_seconds_count{stage="submit"} 10
cerbos_tpu_decision_source_total{source="device"} 100
cerbos_tpu_decision_source_total{source="oracle"} 50
cerbos_tpu_xla_compiles_total{source="fresh"} 3
cerbos_tpu_xla_compiles_total{source="persistent"} 4
cerbos_tpu_xla_compile_seconds_sum 12.5
cerbos_tpu_brownout_stage 0
cerbos_tpu_brownout_transitions_total{stage="shed_audit",direction="enter"} 1
"""
AFTER = """
cerbos_tpu_request_stage_seconds_bucket{stage="admission",le="0.001"} 3
cerbos_tpu_request_stage_seconds_sum{stage="admission"} 0.040
cerbos_tpu_request_stage_seconds_count{stage="admission"} 14
cerbos_tpu_batch_stage_seconds_sum{stage="pack"} 1.5
cerbos_tpu_batch_stage_seconds_count{stage="pack"} 15
cerbos_tpu_batch_stage_seconds_sum{stage="submit"} 3.5
cerbos_tpu_batch_stage_seconds_count{stage="submit"} 15
cerbos_tpu_decision_source_total{source="device"} 400
cerbos_tpu_decision_source_total{source="oracle"} 150
cerbos_tpu_xla_compiles_total{source="fresh"} 3
cerbos_tpu_xla_compiles_total{source="persistent"} 5
cerbos_tpu_xla_compile_seconds_sum 13.0
cerbos_tpu_brownout_stage 0
cerbos_tpu_brownout_transitions_total{stage="shed_audit",direction="enter"} 2
"""


@pytest.fixture()
def ctx():
    return {
        "before": prom.parse(BEFORE), "after": prom.parse(AFTER),
        "gen": {"gen_late_p99_ms": 0.7}, "trace": {"busy_s": 0.003}, "seconds": 10.0,
        "trace_before": prom.parse(BEFORE), "trace_after": prom.parse(AFTER),
    }


def test_histogram_delta_mean():
    b, a = prom.parse(BEFORE), prom.parse(AFTER)
    # (0.040 - 0.010) s over (14 - 4) observations
    assert prom.hist_mean(b, a, "cerbos_tpu_request_stage_seconds", stage="admission") == pytest.approx(0.003)
    assert prom.hist_mean(b, b, "cerbos_tpu_request_stage_seconds", stage="admission") is None


@pytest.mark.parametrize(
    "reader, args, want",
    [
        ("hist_mean", {"metric": "cerbos_tpu_request_stage_seconds", "labels": {"stage": "admission"}, "scale": 1000.0}, 3.0),
        # pack grew 0.5 s and submit 1.5 s over 5 flights: 0.4 s a flight
        ("hist_mean", {"metric": "cerbos_tpu_batch_stage_seconds", "stages": ["pack", "submit"], "scale": 1000.0}, 400.0),
        ("hist_mean", {"metric": "cerbos_tpu_request_stage_seconds", "labels": {"stage": "no_such_stage"}}, None),
        ("counter_delta", {"metric": "cerbos_tpu_xla_compiles_total"}, 1.0),
        ("counter_delta", {"metric": "cerbos_tpu_no_such_total"}, None),
        # oracle grew 100 of 400
        ("counter_share", {"metric": "cerbos_tpu_decision_source_total", "part": {"source": "oracle"}}, 25.0),
        ("counter_at_open", {"metric": "cerbos_tpu_xla_compile_seconds_sum"}, 12.5),
        # the gauge read 0 at both ends, but a stage was entered in between
        ("gauge_max", {"metric": "cerbos_tpu_brownout_stage", "entered": "cerbos_tpu_brownout_transitions_total",
                       "entered_labels": {"direction": "enter"}}, 1.0),
        ("gauge_max", {"metric": "cerbos_tpu_brownout_stage"}, 0.0),
        ("gen_stat", {"key": "gen_late_p99_ms"}, 0.7),
        ("gen_stat", {"key": "absent"}, None),
        # 3 ms busy over 300 device-served decisions: 10 us each
        ("trace_busy_per_count", {"metric": "cerbos_tpu_decision_source_total", "labels": {"source": "device"}, "scale": 1e6}, 10.0),
    ],
)
def test_reader(ctx, reader, args, want):
    got = spec.load_reader(BENCH, reader)(ctx, **args)
    assert got == (pytest.approx(want) if want is not None else None)


def test_trace_reader_without_a_trace_reads_nothing(ctx):
    ctx["trace"] = None
    read = spec.load_reader(BENCH, "trace_busy_per_count")
    assert read(ctx, metric="cerbos_tpu_decision_source_total", labels={"source": "device"}) is None
