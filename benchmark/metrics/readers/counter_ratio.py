"""A ratio of sums of perf-counter deltas over the window. Counters are
named ``<set>:<key>`` with ``fnmatch`` globs; ``*_minus`` lists are
taken off the sum; ``scale`` multiplies the ratio (100 for a share in
percent). A later PR adds such a metric as a file, with no code."""

from __future__ import annotations

from ... import counters


def _sum(spec: dict, moved: dict, key: str) -> float:
    return counters.total(moved, spec[key]) - counters.total(
        moved, spec.get(key + "_minus", [])
    )


def read(spec: dict, ctx) -> float | None:
    denominator = _sum(spec, ctx.moved, "denominator")
    if denominator <= 0:
        return None
    return spec.get("scale", 1.0) * _sum(spec, ctx.moved, "numerator") / denominator
