"""Several requests in ONE flight, the way production gets them: queued behind
a flight in progress.

The batcher drains a queue that holds a check at once (its coalescing window
is for plan queries alone, ``engine/batcher.py:_plans_alone``), so a long
window no longer forces a burst into one flight. ``FlightGate`` wraps an
evaluator and holds the drain thread inside its FIRST flight until released:
what is enqueued meanwhile queues behind that flight and drains together.
"""

import threading
import time
from concurrent.futures import Future


class EchoPlanner:
    """``plan_planner`` for a batcher under test: a plan query is what opens
    the coalescing window."""

    def __init__(self):
        self.flights: list[int] = []

    def plan_batch(self, inputs, params=None):
        self.flights.append(len(inputs))
        return [f"plan:{i}" for i in inputs]

    def plan(self, inp, params=None):
        return f"plan:{inp}"


class FlightGate:
    def __init__(self, evaluator):
        self._ev = evaluator
        self.entered = threading.Event()  # the first flight has reached the evaluator
        self._release = threading.Event()
        if hasattr(evaluator, "submit"):  # the batcher asks which surface there is
            self.submit = self._submit

    def __getattr__(self, name):
        return getattr(self._ev, name)

    def _hold(self) -> None:
        if not self.entered.is_set():
            self.entered.set()
            assert self._release.wait(timeout=30), "the gate was never released"

    def check(self, inputs, params=None):
        self._hold()
        return self._ev.check(inputs, params)

    def _submit(self, inputs, params=None):
        self._hold()
        return self._ev.submit(inputs, params)

    def hold(self, batcher, plug_inputs) -> Future:
        """Send a plug request and wait until its flight holds the drain thread."""
        plug = batcher.check_async(list(plug_inputs))
        assert self.entered.wait(timeout=10), "the plug never reached the evaluator"
        return plug

    def release(self, batcher=None, queued: int = 0) -> None:
        """Let the held flight go, once ``queued`` requests wait behind it."""
        end = time.monotonic() + 10
        while batcher is not None and len(batcher._queue) < queued:
            assert time.monotonic() < end, f"only {len(batcher._queue)} of {queued} requests queued"
            time.sleep(0.002)
        self._release.set()
