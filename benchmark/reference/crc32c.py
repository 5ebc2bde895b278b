"""crc32c (Castagnoli, reflected 0x82F63B78), table-driven. The value
is the raw register, as Ceph keeps it: seeded by the caller (a shard's
cumulative hash starts at 0xFFFFFFFF) and with no final inversion."""

from __future__ import annotations

import numpy as np

POLY_REFLECTED = 0x82F63B78


def _build_table() -> np.ndarray:
    table = np.zeros(256, np.uint32)
    for i in range(256):
        crc = i
        for _ in range(8):
            crc = (crc >> 1) ^ (POLY_REFLECTED if crc & 1 else 0)
        table[i] = crc
    return table


TABLE = _build_table()


def crc32c(seed: int, data: bytes) -> int:
    crc = seed & 0xFFFFFFFF
    table = TABLE.tolist()
    for byte in data:
        crc = table[(crc ^ byte) & 0xFF] ^ (crc >> 8)
    return crc


def crc32c_rows(seed: int, rows: np.ndarray) -> np.ndarray:
    """crc32c of every row of ``[n, length]`` uint8, byte-serial along
    the row and vectorised across rows."""
    crc = np.full(rows.shape[0], seed & 0xFFFFFFFF, np.uint32)
    for p in range(rows.shape[1]):
        crc = TABLE[(crc ^ rows[:, p]) & 0xFF] ^ (crc >> 8)
    return crc
