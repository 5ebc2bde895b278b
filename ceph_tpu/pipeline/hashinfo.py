"""Per-shard cumulative CRC32C — the ``ECUtil::HashInfo`` analog.

Mirrors osd/ECUtil.h:731-780: one cumulative crc32c per shard, seeded
at -1 (0xFFFFFFFF), updated append-only as shards grow; persisted next
to the object and checked by deep scrub (ECBackend.cc:1829-1869).

Two append paths, bit-identical by construction:

- ``append``: raw bytes, all shards together through
  ``checksum.crc32c_streams`` (host native below the device threshold;
  above it ONE device checksum call and one fetch an append, whatever
  k+m is) — the tier of every write whose csums do not come fused.
- ``append_block_csums``: seeds the cumulative hashes from the fused
  encode+checksum kernel's ZERO-INIT per-block csums
  (ops/pallas_encode.py) via crc range concatenation — the bytes are
  hashed exactly once, on device, while they were resident for the
  encode matmul; the host never touches them again, and folds every
  shard's words in one call (``checksum.crc32c_fold``).
"""

from __future__ import annotations

import json

import numpy as np

from ceph_tpu.checksum import crc32c_fold, crc32c_streams
from ceph_tpu.checksum.crc32c import as_stream

SEED = 0xFFFFFFFF


class HashInfo:
    def __init__(self, num_chunks: int) -> None:
        self.total_chunk_size = 0
        self.cumulative_shard_hashes = [SEED] * num_chunks

    def append(
        self,
        old_size: int,
        to_append: "dict[int, np.ndarray | bytes | bytearray | memoryview]",
    ) -> int:
        """Extend shard crcs with bytes written at ``old_size``.

        Values are raw shard bytes: bytes-like taken as-is, ndarrays
        must already be uint8 (no silent value casts — the crc is over
        stored bytes, so a lossy cast would hide corruption). They are
        read where they lie, never copied into ``bytes``.

        The reference asserts appends are contiguous and equal-length
        across shards (HashInfo::append, ECUtil.cc); same contract here.
        Returns the number of device checksum calls the append made
        (``crc32c_streams``: one for all shards, or none where the
        host served it)."""
        if old_size != self.total_chunk_size:
            raise ValueError(
                f"non-contiguous append: old_size={old_size}, "
                f"have={self.total_chunk_size}"
            )
        shards = list(to_append)
        rows = [as_stream(b) for b in to_append.values()]
        hashes = self.cumulative_shard_hashes
        regs, calls = crc32c_streams([hashes[s] for s in shards], rows)
        for shard, reg in zip(shards, regs):
            hashes[shard] = reg
        if rows:
            self.total_chunk_size += int(rows[0].size)
        return calls

    def append_block_csums(
        self,
        old_size: int,
        to_append: "dict[int, np.ndarray]",
        block_bytes: int,
    ) -> int:
        """Extend shard crcs from kernel-produced ZERO-INIT per-block
        crc32c values (the fused encode+csum output) instead of raw
        bytes: cum' = A_block @ cum ⊕ crc_0(block), repeated — bit-
        identical to ``append`` over the same bytes, with no second
        pass over them. Every shard's words go through ONE
        ``crc32c_fold`` call. Same contiguity/equal-length contract.
        Returns the number of csum words folded."""
        if old_size != self.total_chunk_size:
            raise ValueError(
                f"non-contiguous append: old_size={old_size}, "
                f"have={self.total_chunk_size}"
            )
        shards = list(to_append)
        rows = [np.asarray(to_append[s]).reshape(-1) for s in shards]
        sizes = {v.size for v in rows}
        if len(sizes) > 1:
            raise ValueError(f"unequal append sizes {sizes}")
        if not sizes:
            return 0
        hashes = self.cumulative_shard_hashes
        folded = crc32c_fold(
            [hashes[s] for s in shards], np.stack(rows), block_bytes
        )
        # plain ints: the hashes are persisted as JSON
        for shard, reg in zip(shards, folded.tolist()):
            hashes[shard] = reg
        blocks = sizes.pop()
        self.total_chunk_size += blocks * block_bytes
        return blocks * len(shards)

    def get_chunk_hash(self, shard: int) -> int:
        return self.cumulative_shard_hashes[shard]

    def get_total_chunk_size(self) -> int:
        return self.total_chunk_size

    def has_chunk_hash(self) -> bool:
        return bool(self.cumulative_shard_hashes)

    def clear(self) -> None:
        self.total_chunk_size = 0
        self.cumulative_shard_hashes = [
            SEED for _ in self.cumulative_shard_hashes
        ]

    # -- persistence (the encode/decode-to-attr analog) ----------------
    def to_bytes(self) -> bytes:
        return json.dumps(
            {
                "total_chunk_size": self.total_chunk_size,
                "hashes": self.cumulative_shard_hashes,
            }
        ).encode()

    @classmethod
    def from_bytes(cls, raw: bytes) -> "HashInfo":
        obj = json.loads(raw.decode())
        hi = cls(len(obj["hashes"]))
        hi.total_chunk_size = obj["total_chunk_size"]
        hi.cumulative_shard_hashes = list(obj["hashes"])
        return hi

    def __eq__(self, other: object) -> bool:
        return (
            isinstance(other, HashInfo)
            and self.total_chunk_size == other.total_chunk_size
            and self.cumulative_shard_hashes == other.cumulative_shard_hashes
        )

    def __repr__(self) -> str:
        return (
            f"HashInfo(size={self.total_chunk_size}, "
            f"crcs={[hex(h) for h in self.cumulative_shard_hashes]})"
        )
