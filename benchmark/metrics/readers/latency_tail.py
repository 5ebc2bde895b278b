"""A percentile of op latency (submit to verified completion, host
clock) over the ops of the named kinds that were issued in the window
and completed: the nearest-rank rule on the exact samples. As a metric
it is read in the traced run, so the profiler is on; every untraced run
prints the same percentiles by class on an earlier line (``run.py``)."""

from __future__ import annotations


def nearest_rank(values: list[float], percentile: int) -> float:
    ordered = sorted(values)
    rank = -(-percentile * len(ordered) // 100)  # ceil
    return ordered[max(rank, 1) - 1]


def read(spec: dict, ctx) -> float | None:
    kinds = set(spec["kinds"])
    lat = [
        (s.t_done - s.t_submit) * 1e3
        for s in ctx.samples if s.ok and s.kind in kinds
    ]
    if len(lat) < spec.get("min_samples", 1):
        return None
    return nearest_rank(lat, spec["percentile"])
