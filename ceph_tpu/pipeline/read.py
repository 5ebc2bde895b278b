"""Read pipeline — the ``ECCommon::ReadPipeline`` analog.

Behavioral mirror of the reference's degraded-read path
(osd/ECCommon.cc: ``get_min_avail_to_read_shards`` :198, ``do_read_op``
:387, ``get_remaining_shards`` retry :312, ``complete_read_op`` :90;
client entry osd/ECBackend.cc ``objects_read_and_reconstruct`` :1725):

1. Plan: if every wanted data shard is available, read exactly the
   wanted extents (fast path, no decode). Otherwise apply the codec's
   ``minimum_to_decode`` (with sub-chunk selectors — the CLAY fractional
   repair plan rides the same ``shard_read_t`` seam, ECCommon.h:83-133)
   over the chunk-aligned window and decode.
2. Dispatch per-shard sub-reads (the ECSubRead fan-out seam).
3. On a shard EIO, retry from the remaining survivors: re-plan with the
   failed shard excluded and issue only the still-missing reads
   (``get_remaining_shards``); if no plan exists, the client gets EIO.
4. Client reads complete strictly in submission order regardless of
   backend completion order (``in_progress_client_reads``,
   ECBackend.h:131-148).

TPU-first delta: reconstruction is one batched device decode over the
whole window (cached inverted generator rows), not a per-slice call.
"""

from __future__ import annotations

import time
from collections import OrderedDict
from collections.abc import Callable
from dataclasses import dataclass, field

import numpy as np

from ceph_tpu.utils.trace import tracer

from .extents import ExtentSet, SubchunkSelect
from .shard_map import ShardExtentMap
from .stripe import StripeInfo


class ShardReadError(Exception):
    """A shard store failed a sub-read. ``kind`` distinguishes an IO
    error ("eio") from an absent object ("missing", the ENOENT analog
    of ECInject read type 1) — both retry identically."""

    def __init__(self, shard: int, oid: str = "", kind: str = "eio") -> None:
        super().__init__(f"shard {shard} {kind} on {oid!r}")
        self.shard = shard
        self.kind = kind


@dataclass
class ShardRead:
    """One shard's sub-read: extents plus optional sub-chunk selectors
    (the ``shard_read_t`` analog, ECCommon.h:83-133).

    ``subchunks`` marks a helper of a fractional repair and holds the
    plan's runs. A helper that nobody else wants bytes from goes out
    with ``select`` set: its extents are the whole chunks of the
    window, the selector rides beside them as runs (never expanded to
    a byte range a chunk), and the reply comes back packed into
    ``packed`` (window start -> the selected bytes). A helper the
    client also wants is read in full like any wanted shard."""

    shard: int
    extents: ExtentSet
    subchunks: list[tuple[int, int]] | None = None  # (index, count) runs
    select: SubchunkSelect | None = None
    packed: dict[int, bytes] = field(default_factory=dict)

    def wire_runs(self) -> int:
        """Extents and selector runs the sub-read carries."""
        return len(self.extents) + len(
            self.select.runs if self.select else ()
        )


def get_min_avail_to_read_shards(
    sinfo: StripeInfo,
    codec,
    want: dict[int, ExtentSet],
    avail: set[int],
    costs: dict[int, int] | None = None,
) -> tuple[dict[int, ShardRead], bool]:
    """Choose the shard sub-reads satisfying ``want`` given ``avail``
    (ECCommon.cc:198). Returns (shard_reads, need_decode).

    Fast path: all wanted shards available — read them directly. Slow
    path: available wanted shards still read their own extents, and
    ``minimum_to_decode`` over the MISSING wanted shards picks the
    decode survivors (cost-aware when per-shard ``costs`` are
    supplied); every survivor reads the chunk-aligned window covering
    the wanted extents, narrowed to sub-chunk ranges when the plan
    selects them (the CLAY single-shard repair plan).
    """
    if set(want) <= avail:
        return (
            {s: ShardRead(s, es.copy()) for s, es in want.items() if es},
            False,
        )

    missing = {s for s in want if s not in avail}
    want_raw = {sinfo.get_raw_shard(s) for s in missing}
    avail_raw = {sinfo.get_raw_shard(s) for s in avail}
    if costs is not None:
        chosen = codec.minimum_to_decode_with_cost(
            want_raw, {sinfo.get_raw_shard(s): c for s, c in costs.items()}
        )
        # Re-plan over the cost-chosen survivors so sub-chunk
        # selectors survive cost awareness: a CLAY single-shard
        # repair restricted to the chosen helpers still reads only
        # its repair planes (the cost-aware branch used to flatten
        # every plan to full chunks, silently forfeiting the MSR
        # read savings whenever a caller supplied costs).
        try:
            plan = codec.minimum_to_decode(want_raw, set(chosen))
        except ValueError:
            plan = {
                raw: [(0, codec.get_sub_chunk_count())]
                for raw in chosen
            }
    else:
        plan = codec.minimum_to_decode(want_raw, avail_raw)

    # Chunk-aligned hull of everything wanted, in shard-offset space.
    cs = sinfo.chunk_size
    hull = sinfo.chunk_aligned_hull(want.values())
    if hull is None:
        return {}, False
    window = ExtentSet([hull])

    sub_count = codec.get_sub_chunk_count()
    reads: dict[int, ShardRead] = {}
    for raw, subchunks in plan.items():
        shard = sinfo.get_shard(raw)
        full = [(0, sub_count)]
        if sub_count > 1 and subchunks and list(subchunks) != full:
            reads[shard] = ShardRead(
                shard, window.copy(), list(subchunks),
                SubchunkSelect(cs, sub_count, tuple(subchunks)),
            )
        else:
            reads[shard] = ShardRead(shard, window.copy())
    # Available wanted shards read their own extents on top of any
    # helper role (the client still needs their bytes verbatim): such
    # a helper reads the window whole, its repair planes are a view
    # of what came back.
    for s, es in want.items():
        if s not in avail or not es:
            continue
        if s in reads:
            reads[s].select = None
            reads[s].extents.union(es)
        else:
            reads[s] = ShardRead(s, es.copy())
    return reads, True


def issue_shard_read(backend, oid: str, sr: ShardRead, cb) -> None:
    """Send one sub-read; ``cb(shard, result, packed)`` when it is
    back, ``packed`` saying whether ``result`` holds selected runs."""
    packed = sr.select is not None
    backend.read_shard_async(
        sr.shard, oid, sr.extents,
        lambda shard, result: cb(shard, result, packed),
        select=sr.select,
    )


def place_shard_read(
    result: ShardExtentMap, sr: ShardRead | None, shard: int,
    buffers: dict, packed: bool,
) -> None:
    """A sub-read's reply into the op's state: plain bytes into the
    shard map, packed repair runs onto the ShardRead that asked."""
    if packed:
        if sr is not None:
            sr.packed.update(buffers)
        return
    for start, buf in buffers.items():
        result.insert(shard, start, buf)


def reconstruct_shards(
    sinfo: StripeInfo,
    codec,
    result: ShardExtentMap,
    want: dict[int, ExtentSet],
    shard_reads: dict[int, ShardRead],
    object_size: int,
    error_shards: frozenset[int] | set[int] = frozenset(),
    perf=None,
) -> None:
    """Fill wanted-but-unread shards of ``result`` from its survivors.

    Shared by the client read path and shard recovery: CLAY fractional
    repair when the plan carried sub-chunk selectors and exactly one
    shard is lost, plain windowed decode otherwise. ``perf``: the
    caller's counter set, where it keeps the repair counters
    (``ReadPipeline``)."""
    lost = set()
    for s, es in want.items():
        got = result.get_extent_set(s)
        if any(not got.contains(a, b - a) for a, b in es):
            lost.add(s)
    if not lost:
        return
    fractional = any(sr.subchunks is not None for sr in shard_reads.values())
    if fractional and len(lost) == 1 and hasattr(codec, "repair_window"):
        _repair_fractional(
            sinfo, codec, result, want, shard_reads, object_size,
            error_shards, lost, perf,
        )
        return
    result.decode(codec, lost, object_size)


def _repair_fractional(
    sinfo: StripeInfo,
    codec,
    result: ShardExtentMap,
    want: dict[int, ExtentSet],
    shard_reads: dict[int, ShardRead],
    object_size: int,
    error_shards,
    lost: set[int],
    perf=None,
) -> None:
    """CLAY fractional repair of the one lost shard over the window's
    chunks at once: every helper's repair sub-chunks as one row of a
    ``[helpers, chunks, packed bytes]`` stack (a packed sub-read is
    that row already, a helper read in full gives it as one strided
    copy), then ``codec.repair_window``: one program on the device."""
    cs = sinfo.chunk_size
    (lost_shard,) = lost
    helpers = {
        sinfo.get_raw_shard(s): sr for s, sr in shard_reads.items()
        if s not in error_shards and s not in lost
        and sr.subchunks is not None
    }
    # Window = chunk hull of the wanted extents.
    lo, hi = sinfo.chunk_aligned_hull(want.values())
    n_chunks = (hi - lo) // cs
    ids = sorted(helpers)
    select = SubchunkSelect(
        cs, codec.get_sub_chunk_count(), tuple(helpers[ids[0]].subchunks)
    )
    with tracer.span(
        "clay_gather", perf=perf, key="repair_gather_seconds"
    ):
        stack = np.empty(
            (len(ids), n_chunks, select.packed_chunk), np.uint8
        )
        for row, raw in zip(stack, ids):
            sr = helpers[raw]
            packed = sr.packed.get(lo)
            if packed is not None and len(packed) == row.size:
                row[...] = np.frombuffer(packed, np.uint8).reshape(row.shape)
            else:
                select.select_into(result.get(sr.shard, lo, hi - lo), row)
    with tracer.span("clay_repair", perf=perf, key="repair_seconds"):
        out = codec.repair_window(
            sinfo.get_raw_shard(lost_shard), ids, stack
        )
    shard_size = sinfo.object_size_to_shard_size(object_size, lost_shard)
    end = min(hi, shard_size)
    if end > lo:
        result.insert(lost_shard, lo, out.reshape(-1)[: end - lo])
    if perf is not None:
        perf.inc("repair_ops")
        perf.inc("repair_helper_bytes", stack.size)
        perf.inc("repair_rebuilt_bytes", out.size)


class ClientReadOp:
    """One in-flight client read (ECCommon::ClientAsyncReadStatus +
    read_request_t rolled together)."""

    def __init__(
        self,
        rid: int,
        oid: str,
        ro_offset: int,
        length: int,
        on_complete: Callable[["ClientReadOp"], None] | None,
    ) -> None:
        self.rid = rid
        self.oid = oid
        self.ro_offset = ro_offset
        self.length = length
        self.on_complete = on_complete
        self.want: dict[int, ExtentSet] = {}
        self.shard_reads: dict[int, ShardRead] = {}
        self.need_decode = False
        self.result: ShardExtentMap | None = None
        self.error_shards: set[int] = set()
        # shard -> outstanding sub-read count (a retry can widen a
        # shard's window while its first sub-read is still in flight).
        self.pending: dict[int, int] = {}
        self.done = False
        self.data: bytes | None = None
        self.error: Exception | None = None
        #: (trace_id, span_id) open at submit (the daemon's osd_op):
        #: parent of the recorded ``sub_read_wait``
        self.trace_ctx: tuple = (None, None)
        #: perf_counter when the first round of sub-reads had gone out
        self.t_issued: float | None = None


class ReadPipeline:
    """plan → sub-reads → (decode) → in-order client completion."""

    def __init__(
        self,
        sinfo: StripeInfo,
        codec,
        backend,
        size_fn: Callable[[str], int],
        perf_name: str = "ec_read",
    ) -> None:
        self.sinfo = sinfo
        self.codec = codec
        self.backend = backend
        self.size_fn = size_fn
        self._next_rid = 1
        self._inflight: "OrderedDict[int, ClientReadOp]" = OrderedDict()
        from ceph_tpu.utils import PerfCountersBuilder, perf_collection

        # The io_counters read_cnt/read_bytes analog (ECBackend.cc:
        # 1797-1823) plus reconstruct/retry visibility.
        self.perf = (
            PerfCountersBuilder(perf_collection, perf_name)
            .add_u64_counter("read_ops", "client reads submitted")
            .add_u64_counter("read_bytes", "client bytes returned")
            .add_u64_counter("reconstruct_ops", "reads that decoded")
            .add_u64_counter("retries", "sub-read retries after errors")
            .add_u64_counter("errors", "reads failed after retry")
            # stage timers (utils/trace.py spans of the same names,
            # summed over the reads whose sub-reads came back)
            .add_u64_counter(
                "gather_ops", "reads whose sub-reads were gathered"
            )
            .add_time(
                "issue_seconds", "ec_read.issue: plan + sub-read fan-out"
            )
            .add_time(
                "gather_seconds",
                "sub_read_wait: sub-reads issued to the last reply needed",
            )
            .add_time("reconstruct_seconds", "ec_reconstruct: the decode")
            .add_time(
                "finish_seconds",
                "ec_read.finish: assemble the byte range, complete in order",
            )
            # the fractional (CLAY) repair inside ec_reconstruct
            .add_u64_counter(
                "repair_ops", "reads that rebuilt a shard by repair"
            )
            .add_time("repair_seconds", "clay_repair: codec.repair_window")
            .add_time(
                "repair_gather_seconds",
                "clay_gather: the helpers' repair sub-chunks stacked",
            )
            .add_u64_counter(
                "repair_helper_bytes", "helper bytes handed to repair"
            )
            .add_u64_counter("repair_rebuilt_bytes", "bytes repair rebuilt")
            .add_u64_counter(
                "subread_extents",
                "extents and sub-chunk runs sent in sub-reads",
            )
            .create_perf_counters()
        )

    # -- client entry (objects_read_and_reconstruct analog) ------------
    def submit(
        self,
        oid: str,
        ro_offset: int,
        length: int,
        on_complete: Callable[[ClientReadOp], None] | None = None,
    ) -> int:
        op = ClientReadOp(self._next_rid, oid, ro_offset, length, on_complete)
        self._next_rid += 1
        self._inflight[op.rid] = op
        self.perf.inc("read_ops")

        # Reads past EOF are trimmed (objects_read_sync semantics).
        size = self.size_fn(oid)
        if ro_offset >= size:
            op.length = 0
        else:
            op.length = min(length, size - ro_offset)
        if op.length <= 0:
            op.data = b""
            self._finish(op)
            return op.rid

        op.trace_ctx = tracer.current()
        with tracer.span(
            "ec_read.issue", perf=self.perf, key="issue_seconds",
            oid=oid, rid=op.rid,
        ):
            op.want = self.sinfo.ro_range_to_shard_extent_set(
                op.ro_offset, op.length
            )
            op.result = ShardExtentMap(self.sinfo)
            try:
                op.shard_reads, op.need_decode = (
                    get_min_avail_to_read_shards(
                        self.sinfo, self.codec, op.want, self._avail()
                    )
                )
            except ValueError as e:
                op.error = e
                self._finish(op)
                return op.rid
            self._issue(op, op.shard_reads)
        op.t_issued = time.perf_counter()
        return op.rid

    def read_sync(self, oid: str, ro_offset: int, length: int) -> bytes:
        """Synchronous wrapper (ECBackend::objects_read_sync analog).
        Backends with a ``drain_until`` event loop (the networked one)
        are drained on this thread until the read completes."""
        out: dict[str, ClientReadOp] = {}
        self.submit(oid, ro_offset, length, lambda op: out.update(op=op))
        drain = getattr(self.backend, "drain_until", None)
        if drain is not None and "op" not in out:
            drain(lambda: "op" in out)
        op = out["op"]
        if op.error is not None:
            raise op.error
        return op.data

    # -- internals ------------------------------------------------------
    def _avail(self) -> set[int]:
        return self.backend.avail_shards()

    def _issue(self, op: ClientReadOp, reads: dict[int, ShardRead]) -> None:
        for shard in reads:
            op.pending[shard] = op.pending.get(shard, 0) + 1
        self.perf.inc(
            "subread_extents", sum(sr.wire_runs() for sr in reads.values())
        )
        for sr in list(reads.values()):
            issue_shard_read(
                self.backend, op.oid, sr,
                lambda shard, result, packed, _op=op: self._sub_read_done(
                    _op, shard, result, packed
                ),
            )

    def _sub_read_done(
        self, op: ClientReadOp, shard: int, result, packed: bool = False
    ) -> None:
        left = op.pending.get(shard, 0) - 1
        if left > 0:
            op.pending[shard] = left
        else:
            op.pending.pop(shard, None)
        if isinstance(result, Exception):
            op.error_shards.add(shard)
            self._retry(op)
        else:
            place_shard_read(
                op.result, op.shard_reads.get(shard), shard, result, packed
            )
            if not op.pending:
                self._complete(op)

    def _retry(self, op: ClientReadOp) -> None:
        """Re-plan from the remaining survivors (get_remaining_shards,
        ECCommon.cc:312): issue only byte ranges not already read or
        requested. A still-pending shard can be widened — the extra
        sub-read just bumps its pending count."""
        self.perf.inc("retries")
        avail = self._avail() - op.error_shards
        try:
            reads, need_decode = get_min_avail_to_read_shards(
                self.sinfo, self.codec, op.want, avail
            )
        except ValueError as e:
            op.error = e
            if not op.pending:
                self._complete(op)
            return
        op.need_decode = op.need_decode or need_decode
        fresh: dict[int, ShardRead] = {}
        for shard, sr in reads.items():
            if shard in op.error_shards:
                continue
            already = op.result.get_extent_set(shard)
            prior = op.shard_reads.get(shard)
            # a packed sub-read asked for its selector's runs only: it
            # covers the new plan's extents only if that asks the same
            if prior is not None and prior.select in (None, sr.select):
                already = already.copy()
                already.union(prior.extents)
            missing = sr.extents.difference(already)
            if missing:
                fresh[shard] = ShardRead(
                    shard, missing, sr.subchunks, sr.select
                )
        # Refresh the sub-chunk selectors to the CURRENT plan: a retry
        # that fell back from fractional repair to full decode must not
        # leave stale selectors steering _reconstruct into codec.repair
        # with too few helpers.
        for shard, sr in op.shard_reads.items():
            new = reads.get(shard)
            sr.subchunks = new.subchunks if new is not None else None
            sr.select = new.select if new is not None else None
        for shard, sr in fresh.items():
            if shard in op.shard_reads:
                op.shard_reads[shard].extents.union(sr.extents)
            else:
                op.shard_reads[shard] = ShardRead(
                    shard, sr.extents.copy(), sr.subchunks, sr.select
                )
        if fresh:
            self._issue(op, fresh)
        elif not op.pending:
            self._complete(op)

    def _complete(self, op: ClientReadOp) -> None:
        # a backend that answers inside _issue (local stores) gets here
        # before t_issued is set: it waited for nothing
        now = time.perf_counter()
        self.perf.inc("gather_ops")
        tracer.record(
            "sub_read_wait", op.t_issued or now, now,
            trace_id=op.trace_ctx[0], parent_id=op.trace_ctx[1],
            perf=self.perf, key="gather_seconds", oid=op.oid, rid=op.rid,
        )
        if op.error is None and op.need_decode:
            self.perf.inc("reconstruct_ops")
            try:
                with tracer.span(
                    "ec_reconstruct", perf=self.perf,
                    key="reconstruct_seconds", oid=op.oid, rid=op.rid,
                ):
                    self._reconstruct(op)
            except ValueError as e:
                op.error = e
        with tracer.span(
            "ec_read.finish", perf=self.perf, key="finish_seconds"
        ):
            if op.error is None:
                op.data = op.result.get_ro_range(op.ro_offset, op.length)
                self.perf.inc("read_bytes", len(op.data))
            else:
                self.perf.inc("errors")
            self._finish(op)

    def _reconstruct(self, op: ClientReadOp) -> None:
        """Decode missing wanted shards from the survivors in
        ``op.result`` (complete_read_op → shard_extent_map_t::decode)."""
        reconstruct_shards(
            self.sinfo,
            self.codec,
            op.result,
            op.want,
            op.shard_reads,
            self.size_fn(op.oid),
            op.error_shards,
            self.perf,
        )

    def _finish(self, op: ClientReadOp) -> None:
        """In-order completion (in_progress_client_reads semantics)."""
        op.done = True
        while self._inflight:
            rid, front = next(iter(self._inflight.items()))
            if not front.done:
                return
            self._inflight.pop(rid)
            if front.on_complete is not None:
                front.on_complete(front)
