"""The one rule by which a buffer changes hands instead of being copied.

Whoever keeps bytes it was handed (``ShardExtentMap.insert``, a
``Transaction``'s WRITE payload) keeps the caller's own memory only
where nobody can write to it afterwards, and copies once otherwise.
Both ask the same question here, so the rule is written once.
"""

from __future__ import annotations

import numpy as np


def is_frozen(data) -> bool:
    """Whether ``data`` may be kept as it is: a ``bytes`` object, a
    read-only contiguous ``memoryview``, or a read-only C-contiguous
    uint8 array (immutable by its maker's word). A ``bytearray``, a
    writable view or array, or anything else: whoever holds the memory
    can still write to it."""
    if isinstance(data, bytes):
        return True
    if isinstance(data, memoryview):
        return data.readonly and data.c_contiguous
    if isinstance(data, np.ndarray):
        return (
            data.dtype == np.uint8
            and data.flags.c_contiguous
            and not data.flags.writeable
        )
    return False
