"""A counter's value when the window opened: what set-up spent. args: ``metric``, ``labels`` (optional)."""

from benchmarks.lib.prom import has, total


def read(ctx, metric, labels=None):
    if not has(ctx["before"], metric):
        return None
    return total(ctx["before"], metric, **(labels or {}))
