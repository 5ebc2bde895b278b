"""Bytes a Clay single-chunk repair needs, whatever implements it.

Only what the algorithm needs: every helper byte read once (d helpers,
each the repair planes of its chunk: 1/q of it) and every rebuilt byte
written once. The pair transforms are a few shift-and-xor steps a byte
on the vector unit and the inner MDS decode is 64 multiply-adds per
output row per byte on a matrix unit that is three orders of magnitude
from its peak here, so the bound is the memory's. No stack, gather,
padding or layout copy around the kernels is counted, so a share of
the roofline cannot read over 100 %.

The bytes come from the program's own counters over the window
(``osd.*.read:repair_helper_bytes`` and ``:repair_rebuilt_bytes``:
what ``_repair_fractional`` handed to the codec and got back), checked
against the pool's geometry: d/q helper bytes per rebuilt byte."""

from __future__ import annotations


def helper_ratio(k: int, m: int, d: int) -> float:
    """Helper bytes per rebuilt byte: d helpers, 1/q of a chunk each."""
    return d / (d - k + 1)


def repair_cost(helper_bytes: int, rebuilt_bytes: int) -> dict:
    return {
        "bytes": helper_bytes + rebuilt_bytes,
        # the inner decode alone: q rows out of the (t-1)q known, at the
        # bare bit-matrix, is under a thousandth of the int8 peak's
        # time here; least_seconds takes the larger of the two
        "ops": 0,
        "data_bytes": helper_bytes,
    }
