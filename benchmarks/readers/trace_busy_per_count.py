"""Device-busy time of the profiler's capture over the growth of a counter
across it.

The capture encloses the traced replay (a slice of the window's own traffic,
sent again after the window) and the two scrapes ``trace_before`` and
``trace_after`` enclose the capture, with no other load in between: busy time
and count cover the same work. args: ``metric``, ``labels`` (optional),
``scale`` (1e6 gives microseconds per count)."""

from benchmarks.lib.prom import delta, total


def read(ctx, metric, labels=None, scale=1.0):
    if ctx.get("trace") is None:
        return None
    count = total(delta(ctx["trace_before"], ctx["trace_after"]), metric, **(labels or {}))
    if count <= 0:
        return None
    return ctx["trace"]["busy_s"] / count * scale
