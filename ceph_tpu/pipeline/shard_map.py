"""Per-shard extent maps of buffers — the ``shard_extent_map_t`` analog.

Mirrors osd/ECUtil.h:782+ / ECUtil.cc:487-729 semantics: a map
shard -> {extent -> bytes} plus the drivers that feed the codec —
``encode`` (parity over page-aligned slices), ``encode_parity_delta``
(delta = old XOR new, applied onto parity via generator columns), and
``decode`` (decode-of-data + re-encode-of-parity split).

TPU-first delta from the reference: the slice iterator batches ALL
slices with the same shard-presence signature into one [S, B, L] device
dispatch instead of a per-4K-slice virtual call — the stripe/slice axis
is the MXU batch axis.

Buffers are host numpy here (this layer is the staging side of the
pipeline); codec calls move them through jax and back.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from ceph_tpu.codecs.matrix_codec import codec_stage
from ceph_tpu.utils.buffers import is_frozen

from .extents import ExtentSet
from .hashinfo import HashInfo
from .stripe import PAGE_SIZE, StripeInfo, align_page_next, align_page_prev


@dataclasses.dataclass
class DeltaWork:
    """One op's prepared parity delta (``ShardExtentMap.delta_prepare``)."""

    #: shard offset of the parity window
    lo: int
    #: unit form: raw column of each unit; its delta page; the page of
    #: the window it lands on; the m old parity windows [m, W]
    cols: "np.ndarray | None" = None
    pages: "np.ndarray | None" = None
    at: "np.ndarray | None" = None
    parity: "np.ndarray | None" = None
    #: per-op form: (deltas, old parity) as ``codec.apply_delta`` takes
    windows: "tuple | None" = None


class ShardExtentMap:
    """shard -> sorted disjoint (offset, buffer) runs, plus codec drivers.

    **Ownership.** A run's buffer is never written in place once it is
    placed: an overlapping or abutting ``insert`` builds a new merged
    buffer, and ``_bufs`` is touched by no other module. Placed buffers
    are marked read-only, so a violation raises instead of corrupting a
    map that the extent cache keeps across ops. That is what lets
    ``insert`` take a buffer without copying it and ``get`` hand out a
    view of one."""

    def __init__(self, sinfo: StripeInfo) -> None:
        self.sinfo = sinfo
        self._bufs: dict[int, list[tuple[int, np.ndarray]]] = {}
        #: fused encode+csum output, set by ``encode`` when the kernel
        #: served it: {"block": cb, "shards": {shard: (window_lo,
        #: uint32[nblocks] ZERO-INIT per-block crc32c)}} — the blocks
        #: cover each shard's encode window contiguously
        self.csums: "dict | None" = None
        #: ``(csum words, seconds)`` of the fold of those csums into
        #: the HashInfo ``encode`` was given, where it took that route
        #: (the pipeline's ``hinfo_fold*`` counters read it)
        self.hinfo_fold: "tuple[int, float] | None" = None
        #: ``(device checksum calls, bytes, seconds)`` of the raw-bytes
        #: HashInfo append, where ``encode`` took that route instead
        #: (the pipeline's ``hinfo_stream*`` counters read it)
        self.hinfo_stream: "tuple[int, int, float] | None" = None
        #: ``(rows, stripes)`` of the whole stripes ``insert_ro_range``
        #: last scattered from an immutable buffer: ``rows`` [k, n, chunk]
        #: holds the data shards' runs, ``stripes`` [n, k, chunk] is that
        #: buffer, which is the layout the kernels take. ``_stripe_major``
        #: uses it only while the map still holds exactly those runs
        self._whole: "tuple | None" = None

    # -- buffer management --------------------------------------------
    @staticmethod
    def _owned(data) -> np.ndarray:
        """``data`` as a flat, read-only uint8 array the map may keep:
        itself where nobody can write to it afterwards
        (``buffers.is_frozen``, the rule ``Transaction.write`` shares),
        else a copy."""
        if is_frozen(data):
            # immutable by its maker's word
            if isinstance(data, np.ndarray):
                return data.reshape(-1)
            return np.frombuffer(data, dtype=np.uint8)
        arr = np.asarray(data)
        if not (
            arr.dtype == np.uint8
            and arr.flags.c_contiguous
            and arr.base is None
        ):
            # a bytearray, a writable memoryview or view: whoever holds
            # the memory can still write to it
            arr = np.array(
                np.frombuffer(data, dtype=np.uint8)
                if isinstance(data, (bytearray, memoryview)) else arr,
                dtype=np.uint8,
            )
        # an array that owns its bytes changes hands here
        arr.flags.writeable = False
        return arr.reshape(-1)

    def insert(self, shard: int, offset: int, data) -> None:
        """Insert bytes at a shard offset, coalescing adjacent/overlapping
        runs (later inserts win on overlap, matching extent_map assign).

        The map takes ``data`` over without a copy where it can: a
        ``bytes`` object, a read-only array, or a contiguous uint8 array
        that owns its memory, which is the caller's no longer (it turns
        read-only; pass a copy to go on writing). Anything else is
        copied once. Only a run that overlaps or abuts another allocates
        a merged buffer."""
        arr = self._owned(data)
        if arr.size == 0:
            return
        runs = self._bufs.setdefault(shard, [])
        new_start, new_end = offset, offset + arr.size
        merged_start, merged_end = new_start, new_end
        keep: list[tuple[int, np.ndarray]] = []
        overlapping: list[tuple[int, np.ndarray]] = []
        for off, buf in runs:
            if off + buf.size < merged_start or off > merged_end:
                keep.append((off, buf))
            else:
                overlapping.append((off, buf))
                merged_start = min(merged_start, off)
                merged_end = max(merged_end, off + buf.size)
        if (merged_start, merged_end) != (new_start, new_end):
            # the touching runs and the new one cover the merged range
            # between them, so nothing needs zeroing
            out = np.empty(merged_end - merged_start, dtype=np.uint8)
            for off, buf in overlapping:
                out[off - merged_start : off - merged_start + buf.size] = buf
            out[new_start - merged_start : new_end - merged_start] = arr
            out.flags.writeable = False
            arr = out
        keep.append((merged_start, arr))
        keep.sort(key=lambda t: t[0])
        self._bufs[shard] = keep

    def shards(self) -> list[int]:
        return sorted(self._bufs)

    def get_extent_set(self, shard: int) -> ExtentSet:
        return ExtentSet(
            (off, off + buf.size) for off, buf in self._bufs.get(shard, [])
        )

    def get(self, shard: int, offset: int, length: int) -> np.ndarray:
        """Read a range; absent bytes read as zero (the shared
        zero-buffer convention). Read-only either way: a view of the
        run that covers the range, which a later ``insert`` leaves as it
        was, or a zero-filled array where the range has a hole. Copy it
        to write to it."""
        runs = self._bufs.get(shard, ())
        for off, buf in runs:
            if off <= offset and offset + length <= off + buf.size:
                return buf[offset - off : offset - off + length]
        out = np.zeros(length, dtype=np.uint8)
        for off, buf in runs:
            s = max(offset, off)
            e = min(offset + length, off + buf.size)
            if s < e:
                out[s - offset : e - offset] = buf[s - off : e - off]
        out.flags.writeable = False
        return out

    def _ro_pieces(self, ro_buf, run_buf: np.ndarray, run):
        """(ro view, run view) pairs of one shard run's pieces, equal
        in shape: assign one to the other to scatter or to gather."""
        for ro_at, run_at, rows, width in run.pieces:
            yield (
                np.ndarray(
                    (rows, width), np.uint8, ro_buf, ro_at,
                    (self.sinfo.stripe_width, 1),
                ),
                run_buf[run_at : run_at + rows * width].reshape(rows, width),
            )

    def insert_ro_range(self, ro_offset: int, data) -> None:
        """Scatter rados-object bytes at ``ro_offset`` onto the data
        shards. Whole stripes go in one strided copy into one
        [k, n_chunks, chunk] array whose rows become the shards' runs
        (``encode`` then finds them stacked already); any other range
        takes one strided copy and one ``insert`` per touched shard.
        ``data`` is copied either way and stays the caller's; an
        immutable one (``bytes``) is also kept as it is, being the
        stripes in the layout the kernels take."""
        fixed = isinstance(data, bytes)
        data = np.frombuffer(data, dtype=np.uint8)
        k, cs, sw = self.sinfo.k, self.sinfo.chunk_size, self.sinfo.stripe_width
        if data.size and ro_offset % sw == 0 and data.size % sw == 0:
            lo, n = ro_offset // k, data.size // sw
            stripes = data.reshape(n, k, cs)
            rows = np.empty((k, n, cs), dtype=np.uint8)
            rows[...] = stripes.transpose(1, 0, 2)
            rows.flags.writeable = False  # its rows are placed as views
            for raw in range(k):
                self.insert(
                    self.sinfo.get_shard(raw), lo, rows[raw].reshape(-1)
                )
            self._whole = (rows, stripes) if fixed else None
            return
        for run in self.sinfo.ro_range_to_shard_runs(ro_offset, data.size):
            buf = np.empty(run.end - run.start, dtype=np.uint8)
            for src, dst in self._ro_pieces(data, buf, run):
                dst[...] = src
            self.insert(self.sinfo.get_shard(run.raw_shard), run.start, buf)

    def get_ro_range(self, ro_offset: int, length: int) -> bytes:
        """Gather the rados byte range from the data shards (the
        inverse of ``insert_ro_range``; absent bytes read as zero):
        one ``get`` and one strided copy per touched shard."""
        out = np.empty(length, dtype=np.uint8)
        for run in self.sinfo.ro_range_to_shard_runs(ro_offset, length):
            buf = self.get(
                self.sinfo.get_shard(run.raw_shard),
                run.start, run.end - run.start,
            )
            for dst, src in self._ro_pieces(out, buf, run):
                dst[...] = src
        return out.tobytes()

    def contains(self, shard: int, offset: int, length: int) -> bool:
        return self.get_extent_set(shard).contains(offset, length)

    def erase_shard(self, shard: int) -> None:
        self._bufs.pop(shard, None)

    def erase(self, shard: int, offset: int, length: int) -> None:
        runs = self._bufs.get(shard)
        if not runs:
            return
        out = []
        for off, buf in runs:
            lo, hi = offset, offset + length
            if off + buf.size <= lo or off >= hi:
                out.append((off, buf))
                continue
            if off < lo:
                out.append((off, buf[: lo - off]))
            if off + buf.size > hi:
                out.append((hi, buf[hi - off :]))
        if out:
            self._bufs[shard] = out
        else:
            del self._bufs[shard]

    # -- geometry helpers ---------------------------------------------
    def ro_range(self) -> tuple[int, int]:
        """(ro_start, ro_end) hull across data shards, stripe-aligned —
        the ro_start/ro_end members of shard_extent_map_t."""
        lo, hi = None, None
        for shard in self._bufs:
            raw = self.sinfo.get_raw_shard(shard)
            if raw >= self.sinfo.k:
                continue
            es = self.get_extent_set(shard)
            if not es:
                continue
            lo = es.range_start() if lo is None else min(lo, es.range_start())
            hi = es.range_end() if hi is None else max(hi, es.range_end())
        if lo is None:
            return 0, 0
        return align_page_prev(lo), align_page_next(hi)

    def pad_and_rebuild_to_page_align(self) -> None:
        """Round every run outward to page boundaries, zero-filling —
        pad_and_rebuild_to_page_align (ECUtil.cc:731): device DMA and
        store writes both want whole pages."""
        for shard in list(self._bufs):
            runs = self._bufs.pop(shard)
            for off, buf in runs:
                start = align_page_prev(off)
                end = align_page_next(off + buf.size)
                padded = np.zeros(end - start, dtype=np.uint8)
                padded[off - start : off - start + buf.size] = buf
                self.insert(shard, start, padded)

    def csums_for(
        self, shard: int, offset: int, length: int
    ) -> "np.ndarray | None":
        """Kernel-produced ZERO-INIT per-block csums covering exactly
        ``[offset, offset+length)`` of ``shard``, or None when the
        fused encode didn't run / the range isn't block-aligned within
        the csum window. What the sub-write generator attaches to each
        store write so BlueStore-analog blob csums come from the
        kernel, not a host re-hash."""
        if self.csums is None:
            return None
        from .stripe import csum_block_range

        entry = self.csums["shards"].get(shard)
        if entry is None:
            return None
        wlo, vals = entry
        rng = csum_block_range(
            offset, length, wlo, int(vals.size), self.csums["block"]
        )
        if rng is None:
            return None
        return vals[rng[0] : rng[1]]

    # -- codec drivers -------------------------------------------------
    def _slice_window(self) -> tuple[int, int]:
        lo, hi = self.ro_range()
        return lo, hi

    def _stripe_major(self, lo: int, n_chunks: int) -> np.ndarray:
        """The k data shards over ``n_chunks`` chunks from ``lo`` as
        [n_chunks, k, chunk]: the stacked form the codecs' kernels take.
        Where the map holds exactly the whole stripes that
        ``insert_ro_range`` scattered from an immutable buffer, that
        buffer is this array already; otherwise one strided copy per
        shard (a hole reads zero)."""
        k, cs = self.sinfo.k, self.sinfo.chunk_size
        if self._whole is not None:
            rows, stripes = self._whole

            def is_row(raw: int) -> bool:
                runs = self._bufs.get(self.sinfo.get_shard(raw), ())
                return (
                    len(runs) == 1
                    and runs[0][0] == lo
                    and runs[0][1].size == n_chunks * cs
                    and runs[0][1].ctypes.data == rows[raw].ctypes.data
                )

            if rows.shape[1] == n_chunks and all(map(is_row, range(k))):
                return stripes
        out = np.empty((n_chunks, k, cs), dtype=np.uint8)
        for raw in range(k):
            out[:, raw, :] = self.get(
                self.sinfo.get_shard(raw), lo, n_chunks * cs
            ).reshape(n_chunks, cs)
        return out

    def encode(self, codec, hashinfo: HashInfo | None = None,
               old_size: int | None = None,
               csum_block: int | None = None) -> None:
        """Compute parity for every page-aligned slice covered by the
        data shards and insert it into this map (ECUtil.cc:487-511).

        One batched device dispatch per presence-signature, not one per
        slice. Updates ``hashinfo`` with the newly written shard tails
        when given (the encode-time HashInfo append, ECUtil.cc:521-534).

        With ``csum_block`` set and the codec's fused encode+csum
        kernel able to serve the geometry, the SAME dispatch also
        emits per-csum-block crc32c for all k+m shards (recorded in
        ``self.csums`` for the sub-write path to carry to the stores)
        and the HashInfo append is seeded from those kernel csums via
        crc chaining — the bytes are hashed exactly once, on device.

        The data reaches a codec that takes the stacked form
        (``encode_stacked``) once, as ``_stripe_major`` gives it: with
        no host copy at all for whole stripes that ``insert_ro_range``
        placed. Parity comes back as one host array per dispatch, is
        laid out [m, n_chunks, chunk] in one strided copy, and its rows
        become the parity runs by ownership: the data runs are read,
        never copied or changed."""
        k, m = self.sinfo.k, self.sinfo.m
        self.csums = self.hinfo_fold = self.hinfo_stream = None
        lo0, hi0 = self._slice_window()
        if hi0 <= lo0:
            return
        # Chunk-align the dispatch window and batch per chunk: codecs
        # with intra-chunk structure (CLAY sub-chunks) need real chunk
        # boundaries, and the chunk axis is a free MXU batch axis. The
        # HASH window below stays page-aligned (lo0/hi0): hashed size
        # must track what the client wrote so contiguous appends keep
        # extending the cumulative CRCs when chunk_size > PAGE_SIZE.
        cs = self.sinfo.chunk_size
        lo = (lo0 // cs) * cs
        hi = -(-hi0 // cs) * cs
        n_chunks = (hi - lo) // cs
        parity = stacked = csums = None
        cb = csum_block
        if not (
            cb
            and cs % cb == 0
            and lo % cb == 0
            and hasattr(codec, "encode_stacked_with_csums")
        ):
            cb = 0
        ring = self._ring_routable(codec, n_chunks)
        if ring or cb:
            with codec_stage("prep"):
                stripes = self._stripe_major(lo, n_chunks)
            if ring:
                # Coalesced/streaming route: the op stages in the ring
                # and shares ONE dispatch (fused encode+csum where
                # ``cb`` asks) with every other op of the tick window.
                # The parity comes back either way; csums None = no
                # fused pass serves the geometry, and the host
                # checksums stand in.
                from .dispatcher import dispatcher_for

                stacked, csums = dispatcher_for(codec).encode_csum_sync(
                    stripes, cb
                )
            else:
                stacked, csums = codec.encode_stacked_with_csums(
                    stripes, cb
                )
        if stacked is not None:
            # np.asarray waits for the kernel; the csum words come
            # back with the parity
            with codec_stage("fetch"):
                parity = self._shard_rows(np.asarray(stacked))
                if csums is not None:
                    csums = np.asarray(csums)
        else:
            parity = self._dispatch_encode(codec, lo, n_chunks)
        for j in range(m):
            self.insert(self.sinfo.get_shard(k + j), lo, parity[j])
        if csums is not None:
            # [n_chunks, k+m, cs/cb] -> per shard the window's linear
            # block sequence (chunk-major, matching the shard's byte
            # stream at offsets lo + i*cb)
            per_shard = self._shard_rows(np.asarray(csums))
            self.csums = {
                "block": cb,
                "shards": {
                    self.sinfo.get_shard(raw): (lo, per_shard[raw])
                    for raw in range(k + m)
                },
            }
        if hashinfo is not None:
            # Appends must be contiguous and equal-length across shards
            # (the HashInfo contract): hash every shard's zero-padded
            # tail up to the common PAGE window end (not the chunk-
            # aligned dispatch window — see comment above).
            base = lo0 if old_size is None else old_size
            if hi0 > base:
                if (
                    self.csums is not None
                    and base >= lo
                    and (base - lo) % cb == 0
                    and (hi0 - base) % cb == 0
                    and hi0 <= hi
                ):
                    # device-seeded: fold the kernel's zero-init
                    # block csums into the cumulative shard hashes,
                    # all shards in one call
                    first, last = (base - lo) // cb, (hi0 - lo) // cb
                    t0 = time.perf_counter()
                    words = hashinfo.append_block_csums(
                        base,
                        {
                            shard: vals[first:last]
                            for shard, (_wlo, vals) in
                            self.csums["shards"].items()
                        },
                        cb,
                    )
                    self.hinfo_fold = (words, time.perf_counter() - t0)
                else:
                    # no kernel csums (a mesh, a codec without the
                    # fused pass, a window off the csum grid): the
                    # shards' bytes, every shard in one hash
                    t0 = time.perf_counter()
                    calls = hashinfo.append(
                        base,
                        {
                            self.sinfo.get_shard(raw): self.get(
                                self.sinfo.get_shard(raw), base,
                                hi0 - base,
                            )
                            for raw in range(k + m)
                        },
                    )
                    self.hinfo_stream = (
                        calls, (k + m) * (hi0 - base),
                        time.perf_counter() - t0,
                    )

    @staticmethod
    def _shard_rows(stacked: np.ndarray) -> list[np.ndarray]:
        """[n_chunks, rows, width] as a codec's kernel returns it ->
        one flat array per row, the shard's own byte (or word) order:
        one strided copy into a fresh [rows, n_chunks, width], whose
        rows own nothing else and can be placed as they are."""
        n_chunks, rows, width = stacked.shape
        out = np.empty((rows, n_chunks, width), dtype=stacked.dtype)
        out[...] = stacked.transpose(1, 0, 2)
        out = out.reshape(rows, n_chunks * width)
        out.flags.writeable = False
        return list(out)

    def _ring_routable(self, codec, n_chunks: int) -> bool:
        """The gate of the ring route: this thread is inside a
        coalesced OSD tick (dispatcher.coalescing_scope), whose
        concurrent groups stage into the same ring window. Sub-chunk
        codecs (CLAY) give chunk geometry meaning beyond byte count,
        and an op beyond the ring's small-op bound is no small op —
        both keep the per-op path."""
        from ceph_tpu.codecs.matrix_codec import BATCH_MAX_STRIPES

        from .dispatcher import MAX_OP_BYTES, coalescing_active

        return (
            codec.get_sub_chunk_count() == 1
            and coalescing_active()
            and n_chunks <= BATCH_MAX_STRIPES
            and n_chunks * self.sinfo.stripe_width <= MAX_OP_BYTES
        )

    def _dispatch_encode(self, codec, lo: int, n_chunks: int):
        """Parity of the data shards over ``n_chunks`` chunks from
        ``lo``, one flat host array a parity shard, through the codec's
        own dispatch (the per-op path)."""
        k, cs = self.sinfo.k, self.sinfo.chunk_size
        if hasattr(codec, "encode_stacked"):
            with codec_stage("prep"):
                stripes = self._stripe_major(lo, n_chunks)
            stacked = codec.encode_stacked(stripes)
            with codec_stage("fetch"):
                return self._shard_rows(np.asarray(stacked))
        # a codec with no stacked entry takes the runs' own views
        parity = codec.encode_chunks({
            raw: self.get(
                self.sinfo.get_shard(raw), lo, n_chunks * cs
            ).reshape(n_chunks, cs)
            for raw in range(k)
        })
        with codec_stage("fetch"):
            return [
                np.asarray(parity[k + j]).reshape(-1)
                for j in range(len(parity))
            ]

    def encode_parity_delta(
        self, codec, old_map: "ShardExtentMap"
    ) -> None:
        """Parity-delta RMW (ECUtil.cc:542-588): for each data shard
        present here, delta = old XOR new; parity' = parity XOR
        sum_i G[:,i] * delta_i. ``old_map`` must hold the old data AND
        old parity over this map's window.

        Three steps: ``delta_prepare``, ``delta_apply``,
        ``delta_place``. The RMW pipeline calls them itself, timing
        each, so that a coalesced tick can send all its ops' deltas
        between prepare and place as one dispatch
        (dispatcher.DeltaTick); this is the same path for a caller
        with one op."""
        work = self.delta_prepare(codec, old_map)
        if work is not None:
            self.delta_place(work, self.delta_apply(codec, work))

    def delta_prepare(
        self, codec, old_map: "ShardExtentMap"
    ) -> "DeltaWork | None":
        """What one op's parity delta is made of, or None where this
        map wrote nothing.

        For a matrix code the unit is one page of one data column:
        ``pages[u] = old XOR new`` over page ``at[u]`` of the window,
        zero wherever this map did not write, with the raw column
        ``cols[u]``; ``parity`` holds the m old parity windows. That
        form batches across ops (``dispatcher.delta_batch``).
        Packet-layout codes (``PARITY_DELTA_CHUNK_GRANULARITY``: the
        packet decomposition is per chunk, so windows widen to chunk
        boundaries), CLAY and any codec while a mesh owns its
        dispatches keep the per-op form, whole windows for
        ``codec.apply_delta`` (``windows``), behind the same three
        steps."""
        from ceph_tpu.codecs.interface import Flag
        from ceph_tpu.codecs.matrix_codec import DELTA_UNIT

        k, m = self.sinfo.k, self.sinfo.m
        lo, hi = self._slice_window()
        if hi <= lo:
            return None
        chunk_gran = bool(
            codec.get_flags() & Flag.PARITY_DELTA_CHUNK_GRANULARITY
        )
        batchable = (
            not chunk_gran
            and codec.get_sub_chunk_count() == 1
            and getattr(codec, "delta_batchable", lambda: False)()
        )
        if not batchable:
            return self._delta_windows(codec, old_map, lo, hi, chunk_gran)
        # Only bytes this map actually wrote may differ: a page is zero
        # outside them, so the delta is zero there (a page filled from
        # this map's gaps would XOR the old data OUT of the parity —
        # silent corruption).
        runs = []  # (raw, written bytes, their shard offset, first page, first unit)
        n = 0
        for raw in range(k):
            for off, buf in self._bufs.get(self.sinfo.get_shard(raw), ()):
                s, e = max(off, lo), min(off + buf.size, hi)
                if s < e:
                    first = (s - lo) // DELTA_UNIT
                    count = -(-(e - lo) // DELTA_UNIT) - first
                    runs.append((raw, buf[s - off : e - off], s, first, n))
                    n += count
        if not n:
            return None
        pages = np.zeros(n * DELTA_UNIT, dtype=np.uint8)
        cols = np.empty(n, dtype=np.uint8)
        at = np.empty(n, dtype=np.intp)
        for i, (raw, new, s, first, u) in enumerate(runs):
            end = runs[i + 1][4] if i + 1 < len(runs) else n
            cols[u:end] = raw
            at[u:end] = np.arange(first, first + end - u)
            # delta is plain GF addition: XOR on the host
            rel = u * DELTA_UNIT + (s - lo) - first * DELTA_UNIT
            np.bitwise_xor(
                old_map.get(self.sinfo.get_shard(raw), s, new.size), new,
                out=pages[rel : rel + new.size],
            )
        parity = np.stack([
            old_map.get(self.sinfo.get_shard(k + j), lo, hi - lo)
            for j in range(m)
        ])
        return DeltaWork(lo, cols, pages.reshape(n, DELTA_UNIT), at, parity)

    def _delta_windows(
        self, codec, old_map: "ShardExtentMap", lo: int, hi: int,
        chunk_gran: bool,
    ) -> "DeltaWork | None":
        """The per-op form: whole delta and parity windows."""
        k, m = self.sinfo.k, self.sinfo.m
        shape = None
        if chunk_gran:
            # the planner chunk-aligned the parity reads/writes; delta
            # outside the written extents is zero by construction
            cs = self.sinfo.chunk_size
            lo = (lo // cs) * cs
            hi = -(-hi // cs) * cs
            shape = ((hi - lo) // cs, cs)
        deltas = {}
        for raw in range(k):
            shard = self.sinfo.get_shard(raw)
            if shard not in self._bufs:
                continue
            old = old_map.get(shard, lo, hi - lo)
            new = old.copy()
            for off, end in self.get_extent_set(shard):
                s = max(off, lo)
                e = min(end, hi)
                if s < e:
                    new[s - lo : e - lo] = self.get(shard, s, e - s)
            d = np.bitwise_xor(old, new)
            deltas[raw] = d.reshape(shape) if chunk_gran else d
        if not deltas:
            return None
        parity_in = {}
        for j in range(m):
            p = old_map.get(self.sinfo.get_shard(k + j), lo, hi - lo)
            parity_in[k + j] = p.reshape(shape) if chunk_gran else p
        return DeltaWork(lo, windows=(deltas, parity_in))

    def delta_apply(self, codec, work: "DeltaWork"):
        """The codec's part: ``contribs [n, m, unit]`` of the unit
        form (a batch of one through ``dispatcher.delta_batch``), or
        the new parity windows of the per-op form."""
        if work.windows is None:
            from .dispatcher import delta_batch

            return delta_batch(codec, [(work.cols, work.pages, 1)])[0]
        k, m = self.sinfo.k, self.sinfo.m
        parity_out = codec.apply_delta(*work.windows)
        with codec_stage("fetch"):
            return [np.asarray(parity_out[k + j]) for j in range(m)]

    def delta_place(self, work: "DeltaWork", contribs) -> None:
        """New parity into this map: the old windows with every unit's
        contribution XORed onto its page."""
        k, m = self.sinfo.k, self.sinfo.m
        if work.windows is None:
            unit = work.pages.shape[1]
            paged = work.parity.reshape(m, -1, unit)
            for u, page in enumerate(work.at):
                paged[:, page] ^= contribs[u]
            contribs = work.parity
            contribs.flags.writeable = False  # its rows are placed as views
        for j in range(m):
            self.insert(
                self.sinfo.get_shard(k + j), work.lo,
                contribs[j].reshape(-1),
            )

    def decode(self, codec, want: set[int], object_size: int) -> None:
        """Reconstruct the wanted shards from whatever this map holds
        (ECUtil.cc:648-729): wanted data shards decode from any k
        survivors; wanted parity shards re-encode from (possibly just-
        decoded) data. Buffers are zero-padded to the common window and
        trimmed back to each shard's size within ``object_size``."""
        sinfo = self.sinfo
        missing_raw = sorted(
            sinfo.get_raw_shard(s) for s in want if s not in self._bufs
        )
        if not missing_raw:
            return
        cs = sinfo.chunk_size
        hull = sinfo.chunk_aligned_hull(
            self.get_extent_set(shard) for shard in self._bufs
        )
        if hull is None or hull[1] <= hull[0]:
            return
        lo, hi = hull
        # a wanted shard that STORES nothing in the window (short
        # object / post-truncate tail) needs no reconstruction — its
        # bytes are zeros by convention; demanding k survivors for it
        # would fail exactly when the object is small. It must still
        # MATERIALIZE as zeros here: callers (the RMW extent cache)
        # check that requested extents became present, and an absent
        # shard would re-issue the backend read forever.
        zero_raw = [
            raw for raw in missing_raw
            if sinfo.object_size_to_exact_shard_size(
                object_size, sinfo.get_shard(raw)
            ) <= lo
        ]
        for raw in zero_raw:
            shard = sinfo.get_shard(raw)
            end = min(
                hi, sinfo.object_size_to_shard_size(object_size, shard)
            )
            if end > lo:
                self.insert(
                    shard, lo, np.zeros(end - lo, dtype=np.uint8)
                )
        missing_raw = [r for r in missing_raw if r not in zero_raw]
        if not missing_raw:
            return
        # Survivors must cover the stored part of the window: a shard
        # holding only a sub-range would decode zero-filled gaps into
        # the output (absent bytes are zero ONLY beyond shard size).
        # EXACT size, not the page-rounded one: codecs whose chunk is
        # not a page multiple (liberation family, chunk = w * align)
        # store data shards to the exact tail — the page-rounding gap
        # is zeros by convention, not missing bytes.
        present_raw = []
        for shard in self._bufs:
            ssize = sinfo.object_size_to_exact_shard_size(object_size, shard)
            end = min(hi, ssize)
            if end <= lo or self.get_extent_set(shard).contains(lo, end - lo):
                present_raw.append(sinfo.get_raw_shard(shard))
        # a shard NOT in the map whose stored size ends at/before the
        # window is a KNOWN-ZERO survivor (short object / truncated
        # tail): its window content is zeros by convention, and
        # counting it can be the difference between decodable and not
        # (e.g. two lost shards + one empty shard in a k=4 stripe)
        for raw in range(sinfo.k + sinfo.m):
            shard = sinfo.get_shard(raw)
            if shard in self._bufs or raw in missing_raw:
                continue
            if sinfo.object_size_to_exact_shard_size(
                object_size, shard
            ) <= lo:
                present_raw.append(raw)
        present_raw.sort()
        n_chunks = (hi - lo) // cs
        with codec_stage("prep"):
            chunks = {
                raw: self.get(sinfo.get_shard(raw), lo, hi - lo).reshape(
                    n_chunks, cs
                )
                for raw in present_raw
            }
        out = codec.decode_chunks(set(missing_raw), chunks)
        with codec_stage("fetch"):
            fetched = {
                raw: np.asarray(out[raw]).reshape(-1)
                for raw in missing_raw
            }
        for raw in missing_raw:
            shard = sinfo.get_shard(raw)
            buf = fetched[raw]
            shard_size = sinfo.object_size_to_shard_size(object_size, shard)
            end = min(hi, shard_size)
            if end > lo:
                self.insert(shard, lo, buf[: end - lo])

    # -- debug ---------------------------------------------------------
    def __repr__(self) -> str:
        parts = ", ".join(
            f"{s}:{self.get_extent_set(s)!r}" for s in self.shards()
        )
        return f"ShardExtentMap({parts})"
