"""How far a counter moved over the window. args: ``metric``, ``labels`` (optional)."""

from benchmarks.lib.prom import has, moved


def read(ctx, metric, labels=None):
    if not has(ctx["after"], metric):
        return None
    return moved(ctx, metric, labels)
