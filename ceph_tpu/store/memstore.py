"""In-memory object store — the ``MemStore`` analog (src/os/memstore/).

One ``MemStore`` instance plays the role of one OSD shard's local
store in pipeline tests (the reference boots MemStore-backed OSDs for
exactly this, src/test/objectstore/store_test.cc). Objects are dense
byte buffers plus an attr map; transactions apply atomically —
validated first, then applied, so a failing op leaves no partial
state (stricter than the reference's assert-on-error, deliberately:
a functional-style store suits a replayable TPU pipeline).

The store takes nothing over: a WRITE's payload is the sender's (a view
of an encode's rows, of a received frame's segment) and stays so; the
store copies it once into an object's own ``bytearray``, so what is
acknowledged lies in memory the store alone owns and an overwritten
object never changes what a sender still holds.
"""

from __future__ import annotations

import time

from .transaction import Op, OpKind, Transaction
from ceph_tpu.utils.lockdep import DebugLock


def make_store_perf(name: str):
    """A store's counter set (``perf dump`` section ``osd.<id>.store``):
    transactions applied and reads served, their bytes, and the
    seconds each took inside the store (lock wait included: it is
    what the caller waited)."""
    from ceph_tpu.utils import PerfCountersBuilder, perf_collection

    return (
        PerfCountersBuilder(perf_collection, name)
        .add_u64_counter("txns", "queue_transactions calls applied")
        .add_u64_counter("txn_bytes", "data bytes of their WRITE ops")
        .add_u64_counter(
            "apply_copy_bytes",
            "bytes their WRITE ops moved or zero-filled (= txn_bytes "
            "where every payload is copied once and no gap is filled)",
        )
        .add_time("apply_seconds", "seconds inside queue_transactions")
        .add_u64_counter("reads", "read calls served")
        .add_u64_counter("read_bytes", "bytes they returned")
        .add_time("read_seconds", "seconds inside read")
        .create_perf_counters()
    )


#: keys the touched-note holds before it gives up: past this a reader
#: re-reads about as much as a walk of the store would
NOTE_MAX_KEYS = 4096


class _Object:
    __slots__ = ("data", "attrs")

    def __init__(self) -> None:
        self.data = bytearray()
        self.attrs: dict[str, bytes] = {}

    def clone(self) -> "_Object":
        o = _Object()
        o.data = bytearray(self.data)
        o.attrs = dict(self.attrs)
        return o


class MemStore:
    """oid -> object map with atomic transaction application."""

    def __init__(self, name: str = "memstore") -> None:
        self.name = name
        self._objects: dict[str, _Object] = {}
        self._lock = DebugLock("store.mem", rank=60)
        self.committed_seq = 0  # count of applied transactions
        #: the owning daemon attaches ``make_store_perf(...)``; a bare
        #: store (tests, tools) counts nothing
        self.perf = None
        #: the touched-note: every key a transaction has staged since
        #: the note was last read (``touched_since``); None once it
        #: outgrew ``NOTE_MAX_KEYS``, so a store nobody reads stops
        #: noting
        self._touched: set[str] | None = set()
        self._touched_cursor = 0

    # -- write path ----------------------------------------------------
    def queue_transactions(self, txns: list[Transaction] | Transaction) -> int:
        """Apply transactions atomically, in order; returns the commit
        sequence (the on_commit callback's context in the reference)."""
        if isinstance(txns, Transaction):
            txns = [txns]
        t0 = time.perf_counter()
        seq, written, moved = self._apply_all(txns)
        if self.perf is not None:
            self.perf.inc("txns")
            self.perf.inc("txn_bytes", written)
            self.perf.inc("apply_copy_bytes", moved)
            self.perf.tinc("apply_seconds", time.perf_counter() - t0)
        return seq

    def _apply_all(self, txns: list[Transaction]) -> tuple[int, int, int]:
        """(commit sequence, data bytes of the WRITE ops, bytes they
        moved or zero-filled)."""
        with self._lock:
            staged: dict[str, _Object | None] = {}
            written = moved = 0

            def get(oid: str, create: bool) -> _Object | None:
                if oid not in staged:
                    cur = self._objects.get(oid)
                    staged[oid] = cur.clone() if cur is not None else None
                if staged[oid] is None and create:
                    staged[oid] = _Object()
                return staged[oid]

            for t in txns:
                for op in t.ops:
                    if op.kind is OpKind.WRITE:
                        written += len(op.data)
                        moved += self._write(get(op.oid, create=True), op)
                    else:
                        self._apply(op, get, staged)
            for oid, obj in staged.items():
                if obj is None:
                    self._objects.pop(oid, None)
                else:
                    self._objects[oid] = obj
            if self._touched is not None:
                self._touched.update(staged)
                if len(self._touched) > NOTE_MAX_KEYS:
                    self._touched = None
            self.committed_seq += 1
            return self.committed_seq, written, moved

    @staticmethod
    def _write(obj: _Object, op: Op) -> int:
        """Copy a WRITE's payload into ``obj``, once; returns the bytes
        moved or zero-filled. At or past the object's end (a new
        object, an append, a write across the end) the object grows by
        the gap's zeros and the payload itself, with no zero-fill for
        the payload to overwrite; inside, the bytes are assigned in
        place."""
        buf, data, off = obj.data, op.data, op.offset
        n, size = len(data), len(buf)
        if off + n <= size:
            with memoryview(buf) as inside:
                inside[off : off + n] = data
            return n
        if off < size:
            del buf[off:]  # the tail the payload covers
        elif off > size:
            buf += bytes(off - size)  # the gap reads as zeros
            n += off - size
        buf += data
        return n

    @staticmethod
    def _apply(op: Op, get, staged: dict) -> None:
        if op.kind is OpKind.TOUCH:
            get(op.oid, create=True)
            return
        if op.kind is OpKind.REMOVE:
            obj = get(op.oid, create=False)
            if obj is None:
                raise FileNotFoundError(op.oid)
            staged[op.oid] = None
            return
        if op.kind is OpKind.ZERO:
            obj = get(op.oid, create=True)
            end = op.offset + op.length
            if len(obj.data) < end:
                obj.data.extend(b"\0" * (end - len(obj.data)))
            obj.data[op.offset:end] = b"\0" * op.length
            return
        if op.kind is OpKind.TRUNCATE:
            obj = get(op.oid, create=True)
            size = op.offset
            if len(obj.data) > size:
                del obj.data[size:]
            else:
                obj.data.extend(b"\0" * (size - len(obj.data)))
            return
        if op.kind is OpKind.SETATTR:
            obj = get(op.oid, create=True)
            obj.attrs[op.name] = op.data
            return
        if op.kind in (OpKind.RMATTR, OpKind.RMATTR_TOLERANT):
            obj = get(op.oid, create=False)
            if obj is None or op.name not in obj.attrs:
                if op.kind is OpKind.RMATTR_TOLERANT:
                    get(op.oid, create=True)
                    return
                raise KeyError(f"{op.oid}:{op.name}")
            del obj.attrs[op.name]
            return

    # -- read path -----------------------------------------------------
    def exists(self, oid: str) -> bool:
        with self._lock:
            return oid in self._objects

    def stat(self, oid: str) -> int:
        """Object size in bytes; FileNotFoundError if absent."""
        with self._lock:
            obj = self._objects.get(oid)
            if obj is None:
                raise FileNotFoundError(oid)
            return len(obj.data)

    def read(self, oid: str, offset: int = 0, length: int | None = None) -> bytes:
        """Read a range; short if it extends past EOF (POSIX-style, as
        MemStore::read). FileNotFoundError if the object is absent."""
        t0 = time.perf_counter()
        with self._lock:
            obj = self._objects.get(oid)
            if obj is None:
                raise FileNotFoundError(oid)
            if length is None:
                length = len(obj.data) - offset
            out = bytes(obj.data[offset:offset + length])
        if self.perf is not None:
            self.perf.inc("reads")
            self.perf.inc("read_bytes", len(out))
            self.perf.tinc("read_seconds", time.perf_counter() - t0)
        return out

    def getattr(self, oid: str, name: str) -> bytes:
        with self._lock:
            obj = self._objects.get(oid)
            if obj is None:
                raise FileNotFoundError(oid)
            if name not in obj.attrs:
                raise KeyError(f"{oid}:{name}")
            return obj.attrs[name]

    def getattrs(self, oid: str) -> dict[str, bytes]:
        with self._lock:
            obj = self._objects.get(oid)
            if obj is None:
                raise FileNotFoundError(oid)
            return dict(obj.attrs)

    def list_objects(self) -> list[str]:
        with self._lock:
            return sorted(self._objects)

    def touched_since(self, cursor: "int | None") -> "tuple[set[str] | None, int]":
        """(keys that transactions wrote, removed or set an attr of
        since the call that returned ``cursor``, the next cursor). The
        keys are None where the note cannot say: ``cursor`` is not the
        newest one handed out (a first call, or another reader took
        the note meanwhile), or the note overflowed. A key in the set
        may be gone again; one that is not in it has its size and
        attrs unchanged."""
        with self._lock:
            keys = self._touched if cursor == self._touched_cursor else None
            self._touched = set()
            self._touched_cursor += 1
            return keys, self._touched_cursor

    def __repr__(self) -> str:
        return f"MemStore({self.name!r}, objects={len(self._objects)})"
