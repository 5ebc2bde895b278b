"""``counter_ratio`` for a metric whose numerator is newer than its
denominator: the same ratio of sums of counter deltas, read only where
the program has every counter the numerator names. A program from
before those counters (a parent commit) then reports nothing, where
``counter_ratio`` would report 0 over a denominator it does have."""

from __future__ import annotations

import fnmatch

from . import counter_ratio


def read(spec: dict, ctx) -> float | None:
    for pattern in spec["numerator"]:
        if not any(fnmatch.fnmatchcase(name, pattern) for name in ctx.moved):
            return None
    return counter_ratio.read(spec, ctx)
