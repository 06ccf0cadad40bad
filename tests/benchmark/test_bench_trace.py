"""``trace_reduce`` on a small recorded trace, against values worked out by hand.

``data/tpu_v5e_probe.xplane.pb`` was recorded on the chip (TPU v5 lite) by the
traced run of ``classic-800.sidecar`` (seed 502, PR 23) and cut by
``benchmarks/tools/trace_trim.py`` to the first 12 events of every line. Its
``XLA Ops`` line holds these 12 operations, in picoseconds from the first:

    fusion.133   0        .. 1337578      copy.914     1972500 .. 2097578
    copy.906     1338750  .. 1463906      copy.915     2098750 .. 2225000
    copy.907     1465000  .. 1591250      copy-start.2 2225078 .. 2227500
    copy.908     1592500  .. 1717578      copy.916     2227578 .. 2353828
    copy.909     1718750  .. 1843906      copy-done.2  2355000 .. 2548828
    copy.910     1845000  .. 1971250      fusion.135   2548906 .. 2698750

None overlaps another, so the device was busy for the sum of their lengths,
2,689,140 ps, and the eleven gaps between them are 78 to 1,250 ps long. The
first starts 8,285,022,741,250 ps after the start of the capture.
"""

import os

import pytest

from benchmarks.lib import trace_reduce
from benchmarks.tools import trace_trim

FIRST_PS = 8_285_022_741_250
SAMPLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "tpu_v5e_probe.xplane.pb")


def test_recorded_trace_reduces_to_the_hand_worked_busy_and_idle_share():
    # a window of 10 us that opens 3 us before the 2.69 us of work
    span = ((FIRST_PS - 3_000_000) / 1e12, (FIRST_PS + 7_000_000) / 1e12)
    red = trace_reduce.reduce_file(SAMPLE, span)
    assert red["devices"] == ["/device:TPU:0"] and red["events"] == 12 and red["events_outside"] == 0
    assert red["window_s"] == pytest.approx(10e-6, rel=1e-9)
    assert red["busy_s"] == pytest.approx(2_689_140e-12, rel=1e-12)
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(0.731086, abs=1e-9)
    ops = dict(red["device_ops"])
    assert len(red["device_ops"]) == 10 and red["device_ops"][0] == ["fusion.133", pytest.approx(1_337_578e-12)]
    assert ops["copy-done.2"] == pytest.approx(193_828e-12) and ops["fusion.135"] == pytest.approx(149_844e-12)
    assert "copy-start.2" not in ops  # the two shortest of the twelve fall off a list of ten
    # the longest gaps are the window's own ends, before the first operation and after the last, then the
    # two of 1,250 ps; the host's events lie seconds away, so nothing is named
    assert [g[1] for g in red["idle_gaps"][:4]] == [pytest.approx(x * 1e-12) for x in (4_301_250, 3_000_000, 1250, 1250)]
    assert {g[0] for g in red["idle_gaps"]} == {"host:unattributed"} and len(red["idle_gaps"]) == 10


def test_what_the_capture_holds_outside_the_traced_traffic_is_left_out():
    """The window is the span of the traced traffic, not the capture: an
    operation before or after it is not counted, one across its edge is cut."""
    planes = [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        (0, 100, "stray"), (1000, 1200, "a"), (1900, 2100, "b"), (9000, 9100, "at the capture's stop")]}]}]
    red = trace_reduce.reduce_planes(planes, (1000e-12, 2000e-12))
    assert red["events"] == 2 and red["events_outside"] == 2
    assert red["busy_s"] == pytest.approx(300e-12) and red["window_s"] == pytest.approx(1000e-12)
    assert red["device_ops"] == [["a", pytest.approx(200e-12)], ["b", pytest.approx(100e-12)]]
    assert red["idle_gaps"] == [["host:unattributed", pytest.approx(700e-12)]]
    assert trace_reduce.reduce_planes(planes, (3000e-12, 8000e-12)) is None


def test_lines_that_are_not_operations_are_not_counted_as_busy():
    with open(SAMPLE, "rb") as f:
        planes = trace_reduce.read_planes(f.read())
    tpu = next(p for p in planes if p["name"] == "/device:TPU:0")
    assert [ln["name"] for ln in tpu["lines"]] == ["Scalar Unit", "XLA Modules", "XLA Ops", "Async XLA Ops", "TC Overlay"]
    modules = next(ln for ln in tpu["lines"] if ln["name"] == "XLA Modules")
    assert modules["events"][0][2].startswith("jit_run(")  # a whole program encloses its operations
    assert len(trace_reduce._device_events(tpu)) == 12


def test_union_merges_overlapping_and_touching_intervals():
    assert trace_reduce.union([(5, 9), (0, 3), (2, 4), (9, 10), (20, 20)]) == [(0, 4), (5, 10)]


def test_a_gap_is_named_after_the_host_event_that_covers_most_of_it():
    planes = [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [(0, 100, "a"), (1100, 1200, "b"), (1300, 1400, "a")]}]},
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [
            (90, 1000, "pack"), (150, 300, "inner"), (0, 10_000_000, "thread main"), (1210, 1240, "short")]}]},
    ]
    red = trace_reduce.reduce_planes(planes, (0.0, 1400e-12))
    assert red["busy_s"] == pytest.approx(300e-12)
    # 100..1100: "pack" covers 900 of 1000 ps; the thread's whole life is too long to mean anything
    # 1200..1300: "short" covers 30 of 100 ps, under half: unattributed
    assert red["idle_gaps"] == [["host:pack", pytest.approx(1000e-12)], ["host:unattributed", pytest.approx(100e-12)]]
    assert red["device_ops"] == [["a", pytest.approx(200e-12)], ["b", pytest.approx(100e-12)]]


def test_a_trace_with_no_operation_on_a_device_reduces_to_nothing():
    planes = [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": []}]},
              {"name": "/host:CPU", "lines": [{"name": "t", "events": [(0, 5, "x")]}]}]
    assert trace_reduce.reduce_planes(planes, (0.0, 1.0)) is None


def test_trim_keeps_the_first_events_of_every_line_in_the_same_format():
    with open(SAMPLE, "rb") as f:
        raw = f.read()
    full, cut = trace_reduce.read_planes(raw), trace_reduce.read_planes(trace_trim.trim(raw, 5))
    for a, b in zip(full, cut, strict=True):
        assert a["name"] == b["name"]
        if a["name"].startswith(("/device:", "/host:")):
            for la, lb in zip(a["lines"], b["lines"]):
                assert la["name"] == lb["name"] and lb["events"] == la["events"][:5]
