"""The pool's ``reed_sol_van`` code (w=8): the systematic Vandermonde
coding matrix, and the stripe layout an EC pool gives an object (``chunk_size``-byte chunks,
k to a stripe, shard s holding chunk s of every stripe)."""

from __future__ import annotations

import numpy as np

from . import gf256


def coding_matrix(k: int, m: int) -> np.ndarray:
    """The m x k parity rows of the systematic Vandermonde code this
    pool's profile names: V[i, j] = i^j for the k+m evaluation points
    i = 0..k+m-1 (0^0 = 1), right-multiplied by the inverse of its top
    k x k block so the top becomes the identity. This is the
    construction the repo's golden corpus freezes. It is not
    ``reed_sol.c``'s normalised matrix, whose first parity row is all
    ones (PERF.md, Open questions)."""
    v = np.zeros((k + m, k), np.uint8)
    for i in range(k + m):
        acc = 1
        for j in range(k):
            v[i, j] = acc if (i or j == 0) else 0
            acc = gf256.mul(acc, i)
    top_inv = gf256.invert(v[:k])
    out = np.zeros((m, k), np.uint8)
    for r in range(m):
        for c in range(k):
            acc = 0
            for t in range(k):
                acc ^= gf256.mul(int(v[k + r, t]), int(top_inv[t, c]))
            out[r, c] = acc
    return out


def shards_of(obj: bytes, k: int, m: int, chunk_size: int) -> np.ndarray:
    """The k+m shards of ``obj`` as ``[k+m, shard_bytes]`` uint8. The
    object is zero-padded to whole stripes."""
    stripe = k * chunk_size
    n_stripes = -(-len(obj) // stripe)
    buf = np.zeros(n_stripes * stripe, np.uint8)
    buf[: len(obj)] = np.frombuffer(obj, np.uint8)
    data = (
        buf.reshape(n_stripes, k, chunk_size)
        .transpose(1, 0, 2)
        .reshape(k, n_stripes * chunk_size)
    )
    parity = gf256.apply_matrix(coding_matrix(k, m), data)
    return np.concatenate([data, parity], axis=0)


def decode_data(
    shards: dict[int, np.ndarray], k: int, m: int
) -> np.ndarray:
    """The k data shards from any k of the k+m (``shards`` maps shard
    index to its bytes): invert the rows of the generator that
    survive."""
    have = sorted(shards)[:k]
    if len(have) < k:
        raise ValueError(f"need {k} shards, have {len(have)}")
    gen = np.concatenate(
        [np.eye(k, dtype=np.uint8), coding_matrix(k, m)], axis=0
    )
    inverse = gf256.invert(gen[have])
    return gf256.apply_matrix(inverse, np.stack([shards[s] for s in have]))


def object_from_data_shards(
    data: np.ndarray, size: int, chunk_size: int
) -> bytes:
    k = data.shape[0]
    n_stripes = data.shape[1] // chunk_size
    flat = (
        data.reshape(k, n_stripes, chunk_size).transpose(1, 0, 2).reshape(-1)
    )
    return flat[:size].tobytes()
