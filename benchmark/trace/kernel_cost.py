"""Operations and bytes a codec kernel's call needs, from its shapes.

Only what the algorithm needs: the k data chunks in, the parity (or
rebuilt) chunks and the checksum words out, and 64 multiply-adds per
output row per data byte at the bare bit-matrix (``mac_stats`` in
``ops/pallas_encode.py``: 64*R per data byte for R output rows when k
is a multiple of 4). Never padding, batch fill or the layout copies
around the kernel, so a share of the roofline cannot read over 100 %.

The shapes come from the device event's own name, which on a TPU is
the HLO text of the op:
``%_apply_tiled_csum.1 = (u8[128,4,4096]{...}, s32[128,1,12,32]{...})
custom-call(...)``."""

from __future__ import annotations

import re

_SHAPE = re.compile(r"=\s*\(?\s*u8\[(\d+),(\d+),(\d+)\]")


def output_shape(event_name: str) -> tuple[int, int, int] | None:
    """``(stripes, rows out, lane bytes)`` of a bit-matrix kernel's
    first output, or None where the name carries no such shape."""
    m = _SHAPE.search(event_name)
    return (int(m[1]), int(m[2]), int(m[3])) if m else None


def bitmatrix_cost(
    data_bytes: int, k: int, rows: int, csum_block: int = 0
) -> dict:
    """One ``[rows*8, k*8]`` bit-matrix application over ``data_bytes``
    of k data chunks: encode (rows = m), a decode of ``rows`` lost
    chunks, or a delta. ``csum_block`` > 0 adds the fused kernel's
    per-block checksum words of all k+rows chunks to the bytes out."""
    out_bytes = data_bytes * rows // k
    if csum_block:
        out_bytes += (data_bytes // k) * (k + rows) // csum_block * 4
    return {
        "bytes": data_bytes + out_bytes,
        "ops": 2 * 64 * rows * data_bytes,
        "data_bytes": data_bytes,
    }


def bitmatrix_apply(event_name: str, k: int, csum_block: int = 0) -> dict | None:
    """The cost of one kernel call, from the shape in its event name."""
    shape = output_shape(event_name)
    if shape is None:
        return None
    stripes, rows, lane = shape
    return bitmatrix_cost(stripes * k * lane, k, rows, csum_block)


def least_seconds(cost: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take for the call, and which of
    the two peaks sets it."""
    by_hbm = cost["bytes"] / peaks["hbm_bytes_per_s"]
    by_int8 = cost["ops"] / peaks["int8_ops_per_s"]
    return (by_hbm, "hbm") if by_hbm >= by_int8 else (by_int8, "int8")
