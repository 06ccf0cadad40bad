"""Percentiles and histogram arithmetic, each checked against hand-worked values in the tests."""

from __future__ import annotations

import math


def percentile(values, p: float) -> float:
    """The ``p``-th percentile (0..100) by the nearest-rank rule: the smallest
    value with at least ``p`` percent of the sample at or below it. No
    interpolation, so a reported tail is a latency some request really had."""
    if not values:
        raise ValueError("percentile of an empty sample")
    ordered = sorted(values)
    rank = max(1, math.ceil(p / 100.0 * len(ordered)))
    return ordered[rank - 1]


def share_within(values, limit: float) -> float:
    """Percent of ``values`` at or under ``limit``."""
    if not values:
        raise ValueError("share of an empty sample")
    return 100.0 * sum(1 for v in values if v <= limit) / len(values)
