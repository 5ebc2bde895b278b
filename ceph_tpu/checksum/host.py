"""Host checksum dispatch — the ``ceph_crc32c`` runtime-probe analog.

The reference probes CPU features once and routes every crc32c call to
the fastest implementation (src/common/crc32c.cc:19-32). Here: native
(SSE4.2 hardware or slicing-by-8, ceph_tpu.native) when the C++ tier
loads, the bitwise Python oracle otherwise. Both are bit-identical —
tests/test_native.py proves it on random vectors.

The device-batched Checksummer kernels (checksum/crc32c.py) remain the
bulk path; this is for host-side hot spots: wire frame CRCs, HashInfo
chaining (``fold_words``: every shard's block csums in one call),
deep-scrub verification.
"""

from __future__ import annotations

import numpy as np

from . import reference as _ref

_BIT = np.arange(32, dtype=np.uint32)


def apply_columns(cols: np.ndarray, words) -> np.ndarray:
    """``A @ w`` over GF(2) for every uint32 of ``words``, A given as
    its 32 column words (bit i of ``cols[j]`` is ``A[i, j]``): the XOR
    of the columns that the word's set bits choose. Same shape out."""
    words = np.asarray(words, dtype=np.uint32)
    pick = (words[..., None] >> _BIT) & np.uint32(1)
    return np.bitwise_xor.reduce(cols * pick, axis=-1)


def fold_words_numpy(
    cols: np.ndarray, seeds: np.ndarray, csums: np.ndarray
) -> np.ndarray:
    """``native.crc32c_fold`` in numpy: per row of ``csums`` [shards,
    blocks], ``reg = A reg ^ c`` from the row's seed through its words
    in order, as a pairwise tree. A level halves the words (``A^s left
    ^ right`` for neighbours) and squares the transition (the columns
    of ``A^2s`` are ``A^s`` applied to its own columns), so the Python
    here is a few array calls a level, log2(blocks) levels, whatever
    the number of shards."""
    words = np.concatenate([seeds[:, None], csums], axis=1)
    while words.shape[1] > 1:
        if words.shape[1] & 1:
            # a zero word in front changes nothing (A 0 ^ w = w)
            words = np.concatenate(
                [np.zeros_like(words[:, :1]), words], axis=1
            )
        words = apply_columns(cols, words[:, 0::2]) ^ words[:, 1::2]
        cols = apply_columns(cols, cols)
    return words[:, 0]


def _native_or(name: str, fallback):
    """``ceph_tpu.native.<name>`` where the C++ tier loads, else
    ``fallback``: decided once, by what is loaded."""
    try:
        from ceph_tpu import native

        if native.available():
            return getattr(native, name)
    except Exception:
        pass
    return fallback


crc32c = _native_or("crc32c", _ref.crc32c_ref)
# The wire-frame hot path: zero-copy bytes entry (no numpy round-trip
# per segment) when the native tier loads, the bitwise oracle
# otherwise. Bit-identical across backends — pinned by the
# cross-backend oracle in tests/test_wire_native.py.
crc32c_wire = _native_or("crc32c_bytes", _ref.crc32c_ref)
# HashInfo's fold of the fused kernel's block csums (crc32c.py
# ``crc32c_fold``): one native call, the numpy tree above otherwise.
# Bit-identical, tests/test_crc_fold.py.
fold_words = _native_or("crc32c_fold", fold_words_numpy)
