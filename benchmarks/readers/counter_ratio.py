"""How far one counter moved over the window, over how far another did.

args: ``metric``, ``labels`` (optional): the numerator; ``over``,
``over_labels`` (optional): the denominator; ``scale`` (100 gives percent).
None where either counter is absent from the scrape after the window (a
program that has no such instrument) or the denominator did not move."""

from benchmarks.lib.prom import has, moved


def read(ctx, metric, over, labels=None, over_labels=None, scale=1.0):
    if not has(ctx["after"], metric) or not has(ctx["after"], over):
        return None
    den = moved(ctx, over, over_labels)
    if den <= 0:
        return None
    return moved(ctx, metric, labels) / den * scale
