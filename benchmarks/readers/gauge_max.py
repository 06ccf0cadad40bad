"""The larger of a gauge's values at the window's open and after its close;
at least 1 when the counter ``entered`` (with ``entered_labels``) moved in
between, so that a stage entered and left inside the window still shows.

args: ``metric``; ``entered``, ``entered_labels`` (optional)."""

from benchmarks.lib.prom import has, moved, total


def read(ctx, metric, entered=None, entered_labels=None):
    if not has(ctx["after"], metric):
        return None
    value = max(total(ctx["before"], metric), total(ctx["after"], metric))
    if entered and moved(ctx, entered, entered_labels) > 0:
        value = max(value, 1.0)
    return value
