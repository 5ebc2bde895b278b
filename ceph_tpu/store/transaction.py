"""Atomic store transactions — the ``ceph::os::Transaction`` analog.

Mirrors src/os/Transaction.h: an ordered op list applied atomically by
a store. The op vocabulary is the subset the EC pipeline emits from
``generate_transactions`` (osd/ECTransaction.cc:916): touch, write,
zero, truncate, remove, setattr, rmattr. Each op is a plain record;
the store interprets them (src/os/memstore/MemStore.cc
``_do_transaction`` pattern).

A WRITE op's payload changes hands, it is not copied (the rule of
``ShardExtentMap.insert``): ``Transaction.write`` keeps ``data`` itself
where nobody can write to it afterwards, a frame too large for the
receiver's scratch buffer sends it from where it lies, as a segment of
its own (``pack_segments``), the parser hands the store a view of the
received segment (``parse_segments``), and the store, which adopts
nothing, makes the one copy into memory of its own.
"""

from __future__ import annotations

import enum
import struct
from dataclasses import dataclass, field

import numpy as np

from ceph_tpu.utils.buffers import is_frozen
from ceph_tpu.utils.perf_counters import built_once

#: the native frame receiver's two sizes (``native._SEG_OWN_BYTES``,
#: ``native.FRAME_SCRATCH_BYTES``; a test holds them equal): a frame
#: whose payload fits its scratch buffer is read in one call and every
#: segment copied out of the scratch; of a larger frame, a segment of
#: ``PAYLOAD_SEGMENT_BYTES`` or more is read straight into a ``bytes``
#: of its own. The sender mirrors that: a WRITE payload is a wire
#: segment by itself exactly where the receiver would not copy it
PAYLOAD_SEGMENT_BYTES = 4096
FRAME_SCRATCH_BYTES = 64 * 1024


@built_once
def codec_perf():
    """The process's ``txn_codec`` set: what serialising and parsing
    transactions copied, counted once a call (never per op)."""
    from ceph_tpu.utils import PerfCountersBuilder, perf_collection

    return (
        PerfCountersBuilder(perf_collection, "txn_codec")
        .add_u64_counter(
            "copy_bytes",
            "WRITE payload bytes memcpy'd serialising or parsing "
            "transactions (inline payloads, the journal's one stream, "
            "a payload that lay across segments)",
        )
        .add_u64_counter(
            "payload_segments",
            "WRITE payloads sent as a wire segment of their own",
        )
        .add_u64_counter(
            "payload_inline",
            "WRITE payloads packed among the small fields (under "
            "PAYLOAD_SEGMENT_BYTES, in a stream that fits the "
            "receiver's scratch, or past the frame's segments)",
        )
        .create_perf_counters()
    )


class OpKind(enum.Enum):
    TOUCH = "touch"
    WRITE = "write"
    ZERO = "zero"
    TRUNCATE = "truncate"
    REMOVE = "remove"
    SETATTR = "setattr"
    RMATTR = "rmattr"
    #: rmattr that no-ops when the attr is absent — the xattr-
    #: tombstone replay path, where the target may never have had it
    RMATTR_TOLERANT = "rmattr_tolerant"


@dataclass
class Op:
    kind: OpKind
    oid: str
    offset: int = 0
    length: int = 0
    #: ``bytes``, or for a WRITE a read-only flat ``memoryview`` of
    #: memory nobody writes to any more; a reader takes any buffer
    data: "bytes | memoryview" = b""
    name: str = ""
    #: optional kernel-produced ZERO-INIT per-block crc32c values for
    #: WRITE ops (the fused encode+csum output riding the sub-write);
    #: stores that keep blob csums may adopt them instead of
    #: re-hashing, others ignore them. Advisory: they must describe
    #: ``data`` exactly (csum_block-aligned offset and length).
    csums: "tuple[int, ...] | None" = None
    csum_block: int = 0


@dataclass
class Transaction:
    """Ordered op list; built fluently, applied atomically.

    ``write`` takes ``data`` over without a copy where it can: a
    ``bytes`` object, a read-only ``memoryview`` or a read-only
    contiguous uint8 array, which is the caller's to write to no longer
    (it never was). Anything else is copied once."""

    ops: list[Op] = field(default_factory=list)

    def touch(self, oid: str) -> "Transaction":
        self.ops.append(Op(OpKind.TOUCH, oid))
        return self

    def write(
        self, oid: str, offset: int, data: bytes,
        csums=None, csum_block: int = 0,
    ) -> "Transaction":
        """``csums``/``csum_block``: optional zero-init per-block
        crc32c of ``data`` from the fused encode+csum kernel — see
        ``Op.csums``. An array, list or tuple of uint32 values, taken
        whole: one conversion, no Python per word. ``data`` is kept as
        it is where ``buffers.is_frozen`` says nobody can write to it
        afterwards (as a flat view), else copied once."""
        if not isinstance(data, bytes):
            # (an empty view has nothing to keep, and cannot be cast)
            keep = is_frozen(data) and memoryview(data).nbytes
            data = memoryview(data).cast("B") if keep else bytes(data)
        if csums is not None:
            csums = tuple(np.asarray(csums, dtype=np.uint32).tolist())
            csum_block = int(csum_block)
        else:
            csum_block = 0
        self.ops.append(
            Op(OpKind.WRITE, oid, offset=offset, length=len(data),
               data=data, csums=csums, csum_block=csum_block)
        )
        return self

    def zero(self, oid: str, offset: int, length: int) -> "Transaction":
        self.ops.append(Op(OpKind.ZERO, oid, offset=offset, length=length))
        return self

    def truncate(self, oid: str, size: int) -> "Transaction":
        self.ops.append(Op(OpKind.TRUNCATE, oid, offset=size))
        return self

    def remove(self, oid: str) -> "Transaction":
        self.ops.append(Op(OpKind.REMOVE, oid))
        return self

    def setattr(self, oid: str, name: str, value: bytes) -> "Transaction":
        self.ops.append(Op(OpKind.SETATTR, oid, name=name, data=bytes(value)))
        return self

    def rmattr(
        self, oid: str, name: str, ignore_missing: bool = False
    ) -> "Transaction":
        """Remove an attr; strict by default (KeyError when absent).
        ``ignore_missing`` emits RMATTR_TOLERANT: a no-op on absence."""
        kind = OpKind.RMATTR_TOLERANT if ignore_missing else OpKind.RMATTR
        self.ops.append(Op(kind, oid, name=name))
        return self

    def append(self, other: "Transaction") -> "Transaction":
        """Concatenate another transaction's ops (Transaction::append)."""
        self.ops.extend(other.ops)
        return self

    # -- wire serialization (Transaction::encode/decode analog) --------
    # Explicit stable codes, independent of OpKind declaration order:
    # these live in persisted FileStore journals and ECSubWrite
    # payloads, so renumbering silently corrupts replay. New kinds
    # append new codes; never reuse one.
    _KIND_CODE = {
        OpKind.TOUCH: 0,
        OpKind.WRITE: 1,
        OpKind.ZERO: 2,
        OpKind.TRUNCATE: 3,
        OpKind.REMOVE: 4,
        OpKind.SETATTR: 5,
        OpKind.RMATTR: 6,
        OpKind.RMATTR_TOLERANT: 7,
    }
    assert len(_KIND_CODE) == len(OpKind), "every OpKind needs a wire code"
    assert len(set(_KIND_CODE.values())) == len(_KIND_CODE), "codes must be unique"

    def to_bytes(self) -> bytes:
        """Compact binary encoding (the FileStore journal's record, and
        byte for byte what ``pack_segments`` puts on the wire): version
        byte, op count, then per op kind/oid/offset/length/name/data
        with u32 length prefixes (the versioned encode/decode pattern
        of src/os/Transaction.h). Transactions carrying kernel csums
        encode as v2 (each op appends csum_block + u32 csum list);
        csum-free transactions stay byte-identical v1, so the frozen
        golden payloads and mixed-version peers are both safe."""
        pieces, _large, _writes, write_bytes = _pack(self)
        if write_bytes:
            codec_perf().inc("copy_bytes", write_bytes)
        return b"".join(pieces)

    @classmethod
    def from_bytes(cls, raw) -> "Transaction":
        return parse_segments([raw])[0]

    def oids(self) -> list[str]:
        """Distinct objects touched, in first-touch order."""
        seen: list[str] = []
        for op in self.ops:
            if op.oid not in seen:
                seen.append(op.oid)
        return seen

    def empty(self) -> bool:
        return not self.ops

    def __len__(self) -> int:
        return len(self.ops)


# -- the stream as pieces ---------------------------------------------------
def _pack(txn: Transaction) -> "tuple[list, list[int], int, int]":
    """``txn``'s stream as pieces: packed small fields, payloads as
    they are. Returns (the pieces, the indices among them of the WRITE
    payloads of ``PAYLOAD_SEGMENT_BYTES`` or more, which a caller may
    cut a segment around, the number of WRITE payloads, their bytes)."""
    ops = txn.ops
    ver = 2 if any(op.csums is not None for op in ops) else 1
    out = [struct.pack("<BI", ver, len(ops))]
    large: list[int] = []
    writes = write_bytes = 0
    for op in ops:
        oid = op.oid.encode()
        name = op.name.encode()
        n = len(op.data)
        out.append(struct.pack(
            f"<BI{len(oid)}sQQI{len(name)}sI",
            Transaction._KIND_CODE[op.kind], len(oid), oid,
            op.offset, op.length, len(name), name, n,
        ))
        if op.kind is OpKind.WRITE:
            writes += 1
            write_bytes += n
            if n >= PAYLOAD_SEGMENT_BYTES:
                large.append(len(out))
        out.append(op.data)
        if ver >= 2:
            csums = () if op.csums is None else op.csums
            out.append(struct.pack("<II", op.csum_block, len(csums)))
            out.append(np.asarray(csums, dtype="<u4").tobytes())
    return out, large, writes, write_bytes


def pack_segments(txns, room: int) -> "tuple[list, list[int]]":
    """(wire segments, each transaction's stream length) of ``txns`` in
    at most ``room`` segments whose concatenation is the transactions'
    ``to_bytes()`` one after another. A stream that fits the receiver's
    scratch buffer is one segment (it is copied out of the scratch
    whatever its shape, and every segment costs both ends Python). Of
    a larger one, a WRITE payload of ``PAYLOAD_SEGMENT_BYTES`` or more
    is a segment by itself, the sender's own buffer, while the frame has
    room for it and for the small fields around it; the others ride
    inline (one copy)."""
    packed = [_pack(txn) for txn in txns]
    lens = [sum(map(len, pieces)) for pieces, *_ in packed]
    cut_any = sum(lens) > FRAME_SCRATCH_BYTES
    segs: list = []
    run: list = []
    n_alone = n_writes = inline_bytes = 0
    for pieces, large, writes, write_bytes in packed:
        n_writes += writes
        inline_bytes += write_bytes
        at = 0
        if cut_any:
            # a payload by itself costs its segment and the run before
            # it; one more is kept for the fields after the last
            for cut in large[: max((room - len(segs) - 1) // 2, 0)]:
                run += pieces[at:cut]
                segs.append(b"".join(run))
                segs.append(pieces[cut])
                run = []
                at = cut + 1
                n_alone += 1
                inline_bytes -= len(pieces[cut])
        run += pieces[at:]
    if run:
        segs.append(b"".join(run))
    perf = codec_perf()
    if n_alone:
        perf.inc("payload_segments", n_alone)
    if n_writes > n_alone:
        perf.inc("payload_inline", n_writes - n_alone)
        if inline_bytes:
            perf.inc("copy_bytes", inline_bytes)
    return segs, lens


# -- and back ---------------------------------------------------------------
class _Stream:
    """A cursor over segments read as one stream."""

    __slots__ = ("segs", "seg", "pos", "done", "copied")

    def __init__(self, segments) -> None:
        self.segs = iter(segments)
        self.seg = b""
        self.pos = 0
        #: bytes of the segments before ``seg``
        self.done = 0
        #: payload bytes :meth:`payload` had to copy
        self.copied = 0

    @property
    def at(self) -> int:
        return self.done + self.pos

    def take(self, n: int):
        """The next ``n`` bytes, copied (small fields)."""
        if self.pos == len(self.seg) and n:
            self._next()
        end = self.pos + n
        if end <= len(self.seg):
            out = self.seg[self.pos : end]
            self.pos = end
            return out
        return self._gather(n)

    def payload(self, n: int):
        """The next ``n`` bytes where they lie: the segment itself, or
        a read-only view of it; a copy only across segments."""
        if self.pos == len(self.seg) and n:
            self._next()
        seg, pos = self.seg, self.pos
        end = pos + n
        if end > len(seg):
            self.copied += n
            return self._gather(n)
        self.pos = end
        if not pos and end == len(seg) and type(seg) is bytes:
            return seg
        return memoryview(seg).toreadonly()[pos:end]

    def _next(self) -> bool:
        self.done += len(self.seg)
        self.pos = 0
        self.seg = next(self.segs, None)
        if self.seg is None:
            self.seg = b""
            return False
        return True

    def _gather(self, n: int) -> bytes:
        at, parts, want = self.at, [], n
        while want:
            if self.pos == len(self.seg) and not self._next():
                raise ValueError(
                    f"truncated transaction encoding at byte {at}+{n}"
                )
            part = self.seg[self.pos : self.pos + want]
            self.pos += len(part)
            want -= len(part)
            parts.append(part)
        return b"".join(parts)

    def rest(self) -> int:
        """Bytes left (consumes the segments)."""
        left = len(self.seg) - self.pos
        while self._next():
            left += len(self.seg)
        return left


def parse_segments(segments, lens=None) -> "list[Transaction]":
    """The transactions whose streams, one after another, are the
    concatenation of ``segments`` (what ``pack_segments`` made, or one
    ``to_bytes()`` blob); ``lens`` gives each one's stream length, None
    for a single transaction that takes everything. A WRITE payload of
    ``PAYLOAD_SEGMENT_BYTES`` or more is not copied: its ``Op`` holds
    the received segment, or a read-only view into it."""
    kinds = list(OpKind)
    src = _Stream(segments)
    take = src.take
    txns = []
    for want in [None] if lens is None else lens:
        start = src.at
        ver, count = struct.unpack("<BI", take(5))
        if ver not in (1, 2):
            raise ValueError(f"unsupported transaction encoding v{ver}")
        txn = Transaction()
        for _ in range(count):
            code, oid_len = struct.unpack("<BI", take(5))
            if code >= len(kinds):
                raise ValueError(f"unknown op kind code {code}")
            oid = str(take(oid_len), "utf-8")
            offset, length, name_len = struct.unpack("<QQI", take(20))
            name = str(take(name_len), "utf-8")
            (data_len,) = struct.unpack("<I", take(4))
            kind = kinds[code]
            if kind is OpKind.WRITE and data_len >= PAYLOAD_SEGMENT_BYTES:
                data = src.payload(data_len)
            else:
                data = bytes(take(data_len))
            csums, csum_block = None, 0
            if ver >= 2:
                csum_block, n_csums = struct.unpack("<II", take(8))
                if n_csums:
                    csums = struct.unpack(
                        f"<{n_csums}I", take(4 * n_csums)
                    )
                else:
                    csum_block = 0
            txn.ops.append(
                Op(kind, oid, offset=offset, length=length,
                   data=data, name=name, csums=csums,
                   csum_block=csum_block)
            )
        if want is not None and src.at - start != want:
            raise ValueError(
                f"transaction stream of {src.at - start} bytes, "
                f"{want} announced"
            )
        txns.append(txn)
    left = src.rest()
    if left:
        raise ValueError(
            f"{left} trailing bytes after transaction ops"
        )
    if src.copied:
        codec_perf().inc("copy_bytes", src.copied)
    return txns
