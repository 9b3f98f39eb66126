"""A statistic of a list the driver recorded over the window on the host's
clock (a statistic of pieces: per-layer only)."""

import statistics


def read(metric: dict, facts: dict):
    values = facts.get(metric["fact"])
    if not values:
        return None
    stat = metric.get("stat", "p50")
    if stat == "p50":
        return float(statistics.median(values))
    if stat == "mean":
        return float(statistics.fmean(values))
    raise ValueError(f"{metric['name']}: unknown stat {stat!r}")
