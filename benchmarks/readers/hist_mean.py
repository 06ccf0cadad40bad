"""Mean of a histogram's observations over the window, from the growth of its
``_sum`` and ``_count`` between the two scrapes.

args: ``metric``; ``labels`` (optional); ``stages`` (optional): the growth of
``_sum`` is added over these values of the ``stage`` label and divided by the
growth of ``_count`` of the first, which gives the mean per flight of several
stages together; ``scale`` (1000 turns seconds into ms)."""

from benchmarks.lib.prom import moved


def read(ctx, metric, labels=None, stages=None, scale=1.0):
    labels = dict(labels or {})
    if stages:
        count = moved(ctx, metric + "_count", {**labels, "stage": stages[0]})
        total = sum(moved(ctx, metric + "_sum", {**labels, "stage": s}) for s in stages)
    else:
        count = moved(ctx, metric + "_count", labels)
        total = moved(ctx, metric + "_sum", labels)
    if count <= 0:
        return None
    return total / count * scale
