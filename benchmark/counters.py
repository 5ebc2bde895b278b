"""The program's perf counters, flattened: ``perf_collection.dump()``
as ``{"<set>:<key>": number}``. Histograms and averages (nested) are
left out; the per-layer metrics are ratios of sums of deltas."""

from __future__ import annotations

import fnmatch


def snapshot() -> dict[str, float]:
    from ceph_tpu.utils import perf_collection

    out: dict[str, float] = {}
    for set_name, values in perf_collection.dump().items():
        for key, val in values.items():
            if isinstance(val, (int, float)) and not isinstance(val, bool):
                out[f"{set_name}:{key}"] = val
    return out


def delta(before: dict, after: dict) -> dict[str, float]:
    return {k: v - before.get(k, 0) for k, v in after.items()}


def total(moved: dict, patterns: list[str]) -> float:
    """Sum of the deltas whose name matches any pattern
    (``fnmatch``, over ``<set>:<key>``); each name counts once."""
    return sum(
        v for k, v in moved.items()
        if any(fnmatch.fnmatchcase(k, p) for p in patterns)
    )
