"""Block checksumming — the BlueStore/deep-scrub integrity family.

Implements the five ``Checksummer`` algorithms of the reference
(src/common/Checksummer.h:15-23: crc32c, crc32c_16, crc32c_8,
xxhash32, xxhash64) with the same block-granular calculate/verify
contract (Checksummer.h:196-271), plus the raw ``ceph_crc32c``-style
entry point (src/common/crc32c.h).

TPU lowering: CRC32C is GF(2)-linear in the message bits, so a whole
batch of blocks reduces to one int8 MXU matmul against precomputed
fold matrices (``crc32c.py``). xxhash is genuinely sequential per
block, so it runs as a ``lax.scan`` over stripes vmapped across blocks
(``xxhash.py``), with 64-bit lanes emulated as uint32 pairs
(``u64.py``) — JAX x64 stays off.
"""

from . import backends
from .checksummer import (
    CSUM_ALGORITHMS,
    Checksummer,
    crc32c_scalar,
    csum_value_size,
)
from .crc32c import crc32c as crc32c_host
from .host import crc32c_wire
from .crc32c import (
    crc32c_device,
    crc32c_fold,
    crc32c_seed_shift,
    crc32c_stream,
    crc32c_streams,
)
from .reference import crc32c_ref, xxh32_ref, xxh64_ref

__all__ = [
    "CSUM_ALGORITHMS",
    "Checksummer",
    "backends",
    "crc32c_host",
    "crc32c_device",
    "crc32c_fold",
    "crc32c_ref",
    "crc32c_scalar",
    "crc32c_seed_shift",
    "crc32c_stream",
    "crc32c_streams",
    "crc32c_wire",
    "csum_value_size",
    "xxh32_ref",
    "xxh64_ref",
]
