"""Prometheus text, as ``/_cerbos/metrics`` serves it: parsing, deltas, sums.

Copied from ``chip_smoke.py`` (which later PRs may change) and cut to what the
readers use.
"""

from __future__ import annotations

import re

_SERIES = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{(.*)\})?\s+(\S+)\s*$")
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')

Scrape = dict  # {(name, ((label, value), ...)): value}


def parse(text: str) -> Scrape:
    out: Scrape = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SERIES.match(line)
        if not m:
            continue
        name, labels, raw = m.groups()
        try:
            val = float(raw)
        except ValueError:
            continue
        out[(name, tuple(sorted(_LABEL.findall(labels or ""))))] = val
    return out


def total(scrape: Scrape, name: str, **want: str) -> float:
    """Sum of every series of ``name`` whose labels include ``want``."""
    return sum(
        v for (n, labels), v in scrape.items() if n == name and all((k, w) in labels for k, w in want.items())
    )


def delta(before: Scrape, after: Scrape) -> Scrape:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def hist_mean(before: Scrape, after: Scrape, name: str, **want: str) -> float | None:
    """Mean of the observations a histogram took between two scrapes:
    the growth of ``_sum`` over the growth of ``_count``. None when it took none."""
    d = delta(before, after)
    count = total(d, name + "_count", **want)
    if count <= 0:
        return None
    return total(d, name + "_sum", **want) / count


def moved(ctx: dict, metric: str, labels: dict | None = None) -> float:
    """For the readers: how far a counter moved between the scrape at the
    window's open (``ctx["before"]``) and the one after its close."""
    return total(delta(ctx["before"], ctx["after"]), metric, **(labels or {}))


def has(scrape: Scrape, metric: str) -> bool:
    return any(name == metric for name, _ in scrape)
