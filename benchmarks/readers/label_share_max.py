"""The largest share, in percent, that one value of a label took of a counter's
growth over the window: with three front ends behind one port, 33 is an even
spread and 100 is one of them doing everything. Series without the label are
left out; a counter that did not move under the label gives nothing.

args: ``metric``; ``label`` (whose values are compared)."""

from benchmarks.lib.prom import delta


def read(ctx, metric, label):
    grew: dict[str, float] = {}
    for (name, series), value in delta(ctx["before"], ctx["after"]).items():
        key = dict(series).get(label) if name == metric else None
        if key is not None:
            grew[key] = grew.get(key, 0.0) + value
    whole = sum(grew.values())
    if whole <= 0:
        return None
    return max(grew.values()) / whole * 100.0
