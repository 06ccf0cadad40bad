"""The share of a counter's growth over the window that carries given labels.

args: ``metric``; ``part`` (labels of the numerator); ``whole`` (labels of the
denominator, default all series); ``scale`` (100 gives percent)."""

from benchmarks.lib.prom import moved


def read(ctx, metric, part, whole=None, scale=100.0):
    den = moved(ctx, metric, whole)
    if den <= 0:
        return None
    return moved(ctx, metric, part) / den * scale
