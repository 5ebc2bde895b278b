"""Stripe geometry — the ``stripe_info_t`` analog.

Behavioral mirror of osd/ECUtil.h:346-729: rados-object offsets
("ro offsets") map onto k data shards round-robin by chunk; parity
shards trail; an optional ``chunk_mapping`` permutes logical ("raw")
shard positions to stored shard ids. All of this is host-side integer
shape math — on TPU the stripe axis becomes the batch dimension of one
kernel dispatch, so getting this arithmetic right IS the data layout.

Vocabulary (matches the reference):
- ``raw_shard``: logical position 0..k-1 data, k..k+m-1 parity.
- ``shard``: stored position, ``chunk_mapping[raw_shard]``.
- ``ro_offset``: byte offset in the rados object.
- ``shard_offset``: byte offset within one shard's store.
"""

from __future__ import annotations

from typing import NamedTuple

from .extents import ExtentSet

# BlueStore writes whole pages; the reference aligns shard IO to 4K
# (ECUtil.h align_page_next). Device tiling wants the same.
PAGE_SIZE = 4096


def align_page_next(x: int) -> int:
    return -(-x // PAGE_SIZE) * PAGE_SIZE


def align_page_prev(x: int) -> int:
    return (x // PAGE_SIZE) * PAGE_SIZE


def csum_block_range(
    offset: int,
    length: int,
    window_lo: int,
    nblocks: int,
    csum_block: int,
) -> "tuple[int, int] | None":
    """Block-index [first, last) of ``[offset, offset+length)`` within
    a csum window starting at ``window_lo`` that holds ``nblocks``
    blocks of ``csum_block`` bytes — or None unless the range is
    exactly block-aligned and fully covered. The shared shape math
    that lets fused-kernel csums travel with sub-writes: a store may
    only adopt kernel csums for ranges they describe bit-for-bit."""
    if length <= 0 or csum_block <= 0 or offset < window_lo:
        return None
    rel = offset - window_lo
    if rel % csum_block or length % csum_block:
        return None
    first = rel // csum_block
    last = first + length // csum_block
    if last > nblocks:
        return None
    return first, last


class ShardRun(NamedTuple):
    """One data shard's share of an ro byte range: the contiguous shard
    run ``[start, end)`` and where it sits in the range's buffer.

    ``pieces`` holds at most three ``(ro_at, run_at, rows, width)``:
    ``rows`` blocks of ``width`` bytes, one every ``stripe_width`` bytes
    from ``ro_at`` in the ro buffer and back to back from ``run_at`` in
    the run — a partial head chunk, the whole chunks, a partial tail."""

    raw_shard: int
    start: int
    end: int
    pieces: tuple[tuple[int, int, int, int], ...]


class StripeInfo:
    """Geometry of one EC pool: (k, m, stripe_width, chunk_mapping).

    ``stripe_width`` must be a multiple of k; ``chunk_size`` =
    stripe_width / k (ECUtil.h:418).
    """

    def __init__(
        self,
        k: int,
        m: int,
        stripe_width: int,
        chunk_mapping: list[int] | None = None,
    ) -> None:
        if stripe_width <= 0 or stripe_width % k != 0:
            raise ValueError(
                f"stripe_width {stripe_width} must be a positive multiple of k={k}"
            )
        self.k = k
        self.m = m
        self.stripe_width = stripe_width
        self.chunk_size = stripe_width // k
        mapping = list(chunk_mapping or [])
        # complete_chunk_mapping semantics (ECUtil.h:370-382): identity
        # beyond the provided prefix.
        for i in range(len(mapping), k + m):
            mapping.append(i)
        mapping = mapping[: k + m]
        rev: list[int] = [-1] * (k + m)
        for raw, shard in enumerate(mapping):
            if rev[shard] != -1:
                raise ValueError(f"chunk_mapping not a permutation: {mapping}")
            rev[shard] = raw
        self.chunk_mapping = mapping
        self.chunk_mapping_reverse = rev
        self.data_shards = frozenset(mapping[:k])
        self.parity_shards = frozenset(mapping[k:])

    # -- shard id translation -----------------------------------------
    def get_shard(self, raw_shard: int) -> int:
        return self.chunk_mapping[raw_shard]

    def get_raw_shard(self, shard: int) -> int:
        return self.chunk_mapping_reverse[shard]

    def is_data_shard(self, shard: int) -> bool:
        return shard in self.data_shards

    def is_parity_shard(self, shard: int) -> bool:
        return shard in self.parity_shards

    # -- offset arithmetic (ECUtil.h:499-663) -------------------------
    def ro_offset_to_shard_offset(self, ro_offset: int, raw_shard: int) -> int:
        """Shard-local offset of ``ro_offset`` as seen by ``raw_shard``
        (ECUtil.h:517-529): full stripes contribute chunk_size each;
        within the current stripe, shards before the offset's chunk are
        full, later ones empty."""
        full = (ro_offset // self.stripe_width) * self.chunk_size
        offset_shard = (ro_offset // self.chunk_size) % self.k
        if raw_shard == offset_shard:
            return full + ro_offset % self.chunk_size
        if raw_shard < offset_shard:
            return full + self.chunk_size
        return full

    def object_size_to_shard_size(self, size: int, shard: int) -> int:
        """Stored bytes on ``shard`` for an object of ``size`` bytes,
        page-aligned (ECUtil.h:499-515). Parity shards match data
        shard 0 (they exist for every written stripe)."""
        remainder = size % self.stripe_width
        shard_size = (size - remainder) // self.k
        raw = self.get_raw_shard(shard)
        if raw >= self.k:
            raw = 0
        skip = raw * self.chunk_size
        if remainder > skip:
            shard_size += min(remainder - skip, self.chunk_size)
        return align_page_next(shard_size)

    def ro_offset_to_prev_stripe_ro_offset(self, ro_offset: int) -> int:
        return (ro_offset // self.stripe_width) * self.stripe_width

    def ro_offset_to_next_stripe_ro_offset(self, ro_offset: int) -> int:
        return -(-ro_offset // self.stripe_width) * self.stripe_width

    def ro_offset_to_prev_chunk_offset(self, ro_offset: int) -> int:
        return (ro_offset // self.stripe_width) * self.chunk_size

    def ro_offset_to_next_chunk_offset(self, ro_offset: int) -> int:
        return -(-ro_offset // self.stripe_width) * self.chunk_size

    def chunk_aligned_ro_range_to_shard_ro_range(
        self, ro_offset: int, ro_length: int
    ) -> tuple[int, int]:
        """Stripe-align an ro range, then express it per shard: every
        shard sees [off/k, len/k) of the aligned range (ECUtil.h:644)."""
        start = self.ro_offset_to_prev_stripe_ro_offset(ro_offset)
        end = self.ro_offset_to_next_stripe_ro_offset(ro_offset + ro_length)
        return start // self.k, (end - start) // self.k

    # -- range fan-out -------------------------------------------------
    def ro_range_to_shard_runs(
        self, ro_offset: int, ro_length: int
    ) -> list[ShardRun]:
        """The ro byte range as one contiguous run per touched data
        shard, in the order the range first touches them (at most
        ``min(k, chunks touched)`` runs). A shard's bytes of any ro
        range are one run: a partial head chunk is the first on its
        shard, a partial tail chunk the last, and every chunk between
        is whole and follows the previous one in shard space."""
        if ro_length <= 0:
            return []
        cs, k, sw = self.chunk_size, self.k, self.stripe_width
        end = ro_offset + ro_length
        first_chunk = ro_offset // cs
        touched = min(k, (end - 1) // cs - first_chunk + 1)
        runs = []
        for raw in ((first_chunk + i) % k for i in range(touched)):
            start = self.ro_offset_to_shard_offset(ro_offset, raw)
            stop = self.ro_offset_to_shard_offset(end, raw)
            head = min(stop, -(-start // cs) * cs) - start
            rows, tail = divmod(stop - start - head, cs)
            # shard offset x sits at ro offset (x // cs) * sw + raw * cs
            # + x % cs; the head starts mid-chunk, block and tail on a
            # chunk boundary
            base = raw * cs - ro_offset
            body = start + head
            pieces = (
                ((start // cs) * sw + start % cs + base, 0, 1, head),
                ((body // cs) * sw + base, head, rows, cs),
                ((body // cs + rows) * sw + base, head + rows * cs, 1, tail),
            )
            runs.append(ShardRun(
                raw, start, stop, tuple(p for p in pieces if p[2] and p[3])
            ))
        return runs

    def ro_range_to_shard_extent_set(
        self, ro_offset: int, ro_length: int, parity: bool = False
    ) -> dict[int, ExtentSet]:
        """Per-shard extents touched by the ro byte range
        (ECUtil.h:665-695). With ``parity=True`` parity shards get the
        chunk-aligned hull (every touched stripe writes all parity)."""
        out: dict[int, ExtentSet] = {}
        if ro_length <= 0:
            return out
        for run in self.ro_range_to_shard_runs(ro_offset, ro_length):
            out[self.get_shard(run.raw_shard)] = ExtentSet(
                [(run.start, run.end)]
            )
        if parity:
            first = self.ro_offset_to_prev_chunk_offset(ro_offset)
            last = self.ro_offset_to_next_chunk_offset(ro_offset + ro_length)
            for raw in range(self.k, self.k + self.m):
                out.setdefault(self.get_shard(raw), ExtentSet()).insert(
                    first, last - first
                )
        return out

    def object_size_to_exact_shard_size(self, size: int, shard: int) -> int:
        """Bytes the write path actually stores on ``shard``: data
        shards keep the exact (unpadded) tail; parity shards are
        written for every touched page, so they stay page-aligned."""
        raw = self.get_raw_shard(shard)
        if raw >= self.k:
            return self.object_size_to_shard_size(size, shard)
        remainder = size % self.stripe_width
        shard_size = (size - remainder) // self.k
        skip = raw * self.chunk_size
        if remainder > skip:
            shard_size += min(remainder - skip, self.chunk_size)
        return shard_size

    def chunk_aligned_hull(self, extent_sets) -> tuple[int, int] | None:
        """Chunk-aligned [lo, hi) hull over shard-offset extent sets —
        the window every decode/encode dispatch covers. None if empty."""
        cs = self.chunk_size
        lo = hi = None
        for es in extent_sets:
            if not es:
                continue
            s0 = (es.range_start() // cs) * cs
            e0 = -(-es.range_end() // cs) * cs
            lo = s0 if lo is None else min(lo, s0)
            hi = e0 if hi is None else max(hi, e0)
        if lo is None:
            return None
        return lo, hi

    def __repr__(self) -> str:
        return (
            f"StripeInfo(k={self.k}, m={self.m}, "
            f"stripe_width={self.stripe_width}, "
            f"chunk_size={self.chunk_size})"
        )
