"""When the window may open: the warm replay's one rule and its one ceiling,
driven with a server and a generator that are scripted."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchmark_rig  # noqa: E402, F401 - puts the repo on the path

from benchmarks.lib import session  # noqa: E402
from benchmarks.lib.server import HarnessError  # noqa: E402

SLICES = 4


class ScriptedServer:
    """Scrapes in order: the one before the replay, then one after each slice.
    Each is (compiles so far, seconds spent compiling so far, brownout stage);
    the last repeats."""

    def __init__(self, scrapes):
        self.scrapes, self.k = scrapes, 0

    def scrape(self):
        compiles, seconds, stage = self.scrapes[min(self.k, len(self.scrapes) - 1)]
        self.k += 1
        return {
            ("cerbos_tpu_xla_compiles_total", (("source", "persistent"),)): float(compiles),
            ("cerbos_tpu_xla_compile_seconds_sum", ()): float(seconds),
            ("cerbos_tpu_brownout_stage", ()): float(stage),
        }, ""


class ScriptedGenerator:
    def __init__(self, clock, fail_at=None, first_slice_s=session.ROUND_S):
        self.sent, self.clock, self.fail_at, self.first_slice_s = [], clock, fail_at, first_slice_s

    def run(self, cmd):
        self.sent.append(cmd["first"])
        self.clock[0] += session.ROUND_S if len(self.sent) > 1 else self.first_slice_s
        return {"index": [cmd["first"]], "status": ["UNAVAILABLE" if len(self.sent) == self.fail_at else "OK"]}


def _warm(monkeypatch, scrapes, **gen):
    clock = [0.0]
    monkeypatch.setattr(session.time, "monotonic", lambda: clock[0])
    ses = session.Session.__new__(session.Session)
    ses.srv, ses.gen = ScriptedServer(scrapes), ScriptedGenerator(clock, **gen)
    ses.t, ses.work, ses.log = {"start": 0.0}, "/nonexistent", lambda msg: None
    prepared = {"slices": [{"first": 10 * k, "due": [0.0]} for k in range(SLICES)]}
    return ses.warm(prepared), ses.gen.sent


@pytest.mark.parametrize(
    "scrapes, rounds, why",
    [
        ([(0, 0, 0)], session.IDLE_ROUNDS, "nothing has compiled since boot: a mix that bypasses the device"),
        ([(3, 2, 0)], SLICES, "layouts compiled at boot, none since: one whole rehearsal"),
        ([(0, 0, 0), (5, 4, 0), (6, 5, 0), (6, 5, 0)], 2 + SLICES, "the last compile in slice 2: a whole rehearsal after it"),
        ([(0, 0, 0), (9, 8, 4)] + [(9, 8, 4)] * 5 + [(9, 8, 0)], 7, "quiet since slice 1, but the brownout ladder is left only after slice 7"),
        ([(0, 0, 0), (1, 1, 0), (1, 1, 0), (1, 1, 0), (2, 2, 0), (2, 2, 0)], 4 + SLICES, "a late layout starts the count again"),
    ],
    ids=["bypasses-device", "compiled-at-boot", "compiles-early", "brownout", "late-layout"],
)
def test_the_window_opens_after_one_whole_rehearsal_without_a_compile(monkeypatch, scrapes, rounds, why):
    got, sent = _warm(monkeypatch, scrapes)
    assert got == rounds, why
    assert sent == [10 * (k % SLICES) for k in range(rounds)]  # the window's slices in order, again and again


def test_an_unsettled_server_at_the_ceiling_fails_the_run(monkeypatch):
    # a layout in every slice: never quiet
    scrapes = [(k, 0.5 * k, 0) for k in range(200)]
    with pytest.raises(HarnessError, match="has not settled after"):
        _warm(monkeypatch, scrapes)


def test_seconds_spent_compiling_do_not_count_against_the_ceiling(monkeypatch):
    # a cell's first run in a checkout: its first slice waits 500 s for the layouts it compiles, then quiet
    over = session.SETUP_CEILING_S + 280
    rounds, _ = _warm(monkeypatch, [(0, 0, 0), (40, over, 0)], first_slice_s=over + session.ROUND_S)
    assert rounds == 1 + SLICES
    # a first slice as long with nothing compiled in it is an unsettled server
    with pytest.raises(HarnessError, match="after 1 slices .* lasted 505 s, 0 s of it compiling"):
        _warm(monkeypatch, [(0, 0, 0), (1, 0, 0), (2, 0, 0)], first_slice_s=over + session.ROUND_S)
    # the same slices with no compile seconds to set against them run into the ceiling only when they outlast it
    slow = [(0, 0, 0)] + [(k, 0, 0) for k in range(1, 200)]
    with pytest.raises(HarnessError) as e:
        _warm(monkeypatch, slow)
    assert f"after {int(session.SETUP_CEILING_S // session.ROUND_S)} slices" in str(e.value)


def test_a_request_that_fails_in_the_warm_replay_fails_the_run(monkeypatch):
    with pytest.raises(HarnessError, match="warm replay round 2: 1 requests failed, first UNAVAILABLE"):
        _warm(monkeypatch, [(0, 0, 0)], fail_at=2)
