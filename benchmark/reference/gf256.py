"""GF(2^8) arithmetic over the polynomial x^8+x^4+x^3+x^2+1 (0x11d),
the field jerasure's w=8 codes use. Tables are built here, byte by
byte, from the polynomial alone."""

from __future__ import annotations

import numpy as np

POLY = 0x11D


def _build_tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, np.int32)
    log = np.zeros(256, np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:510] = exp[0:255]
    return exp, log


EXP, LOG = _build_tables()


def mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(EXP[LOG[a] + LOG[b]])


def inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(EXP[255 - LOG[a]])


def mul_table(g: int) -> np.ndarray:
    """The 256 products ``g * x`` as a uint8 lookup table."""
    out = np.zeros(256, np.uint8)
    if g:
        xs = np.arange(1, 256)
        out[1:] = EXP[LOG[g] + LOG[xs]]
    return out


def apply_matrix(mat: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """``out[r] = XOR_c mat[r, c] * rows[c]`` for ``rows`` of shape
    ``[cols, n]`` uint8: one table lookup per coefficient."""
    mat = np.asarray(mat)
    out = np.zeros((mat.shape[0], rows.shape[1]), np.uint8)
    for c in range(mat.shape[1]):
        col = rows[c]
        for r in range(mat.shape[0]):
            g = int(mat[r, c])
            if g == 1:
                out[r] ^= col
            elif g:
                out[r] ^= mul_table(g)[col]
    return out


def invert(mat: np.ndarray) -> np.ndarray:
    """Inverse of a square matrix over GF(2^8), by Gauss-Jordan."""
    n = mat.shape[0]
    a = [[int(v) for v in row] for row in mat]
    b = [[int(i == j) for j in range(n)] for i in range(n)]
    for col in range(n):
        pivot = next((r for r in range(col, n) if a[r][col]), None)
        if pivot is None:
            raise ValueError("matrix is singular over GF(2^8)")
        a[col], a[pivot] = a[pivot], a[col]
        b[col], b[pivot] = b[pivot], b[col]
        scale = inv(a[col][col])
        a[col] = [mul(scale, v) for v in a[col]]
        b[col] = [mul(scale, v) for v in b[col]]
        for r in range(n):
            f = a[r][col]
            if r != col and f:
                a[r] = [v ^ mul(f, p) for v, p in zip(a[r], a[col])]
                b[r] = [v ^ mul(f, p) for v, p in zip(b[r], b[col])]
    return np.array(b, np.uint8)
