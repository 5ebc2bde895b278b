"""Streaming dispatcher: the native staging ring feeding batched
device dispatches — SURVEY.md §7 step 4 assembled (host ring ->
staging -> batched device dispatch -> completion callbacks).

The role it fills is the reference's sharded op queues
(osd/OSD.cc:9874-9933): many client ops across many PGs land on a
shared queue and drain in batches. Here the batching axis IS the TPU
win: one [B, k, L] device encode amortizes the per-dispatch launch
and transfer over every small op in the batch — the per-op path pays
it per 4-64 KiB write.

Shape of the machinery:

- producers (OSD daemons, RMW pipelines, any thread) ``submit()``
  ops into the native MPMC ring (native/src/ceph_tpu_native.cc,
  ``ctpu_ring_*``) as header+payload slots; the ring is the
  bounded staging tier — backpressure is a blocking push;
- ONE dispatcher thread drains the ring: it blocks for the first op,
  then keeps popping until the ring is momentarily empty past the
  batching window or ``max_batch`` is reached;
- ops group by (k, chunk_len) signature; each group stacks into one
  [B, k, L] batch, encodes through the codec's normal dispatch
  (device kernel / mesh / einsum — the codec router decides), and
  completion callbacks fire with each op's parity rows;
- ``encode_sync`` is the synchronous facade for pipeline callers:
  submit + wait, with concurrency across threads supplying the batch.

The round-10 serving tier adds three seams:

- ``coalescing_scope()`` — a thread-local scope the OSD daemon's
  coalesced tick batch enters around each PG group's execution:
  inside it, and only there, ``ShardExtentMap`` routes encodes
  through the ring, so concurrent groups of one tick share batched
  device dispatches;
- fused encode+csum ops stage through the SAME ring (``submit`` with
  ``csum_block``): a fused group stacks every member's chunks into
  one ``encode_stacked_with_csums`` dispatch — the whole coalesced
  tick pays one HBM pass for data, parity AND block csums;
- per-op error isolation: a failed MULTI-op batch no longer fails
  every member — each op retries SOLO through the codec, and only
  the op that actually faults surfaces its error (``solo_retries`` /
  ``batch_faults`` counters). One poisoned op cannot sink its
  batch-mates.

PR 26 adds the parity-delta seam, for small overwrites whose delta is
a page or two and can only reach the device in company:

- ``delta_batch`` is the one entry for parity deltas: any number of
  ops' delta pages, each with its raw column, leave as ONE codec call
  (``MatrixErasureCodec.delta_contribs``), which decides host or
  device once, on the whole batch;
- ``DeltaTick`` gathers a coalesced OSD tick: every op of the tick
  prepares its delta and parks; when the tick's last PG group has
  submitted, that group's thread sends them all as one
  ``delta_batch``, and each group goes on with its own ops (place,
  transactions, fan-out) in order. An op outside a tick is a batch of
  one through the same ``delta_batch``.

Deltas do not ride the ring: on the served 4 KiB overwrite traffic
the ring merged the ticks of two OSDs in 7 of 673 batches (PERF.md
§6, PR 26), which did not pay for a slot format and a thread handoff.

Counters (``perf dump`` section ``ec_stream``): ops, batches,
batched_ops (ops that shared a dispatch), plus a max-batch gauge,
batch_faults (multi-op dispatches that failed and split), and
solo_retries (ops that recovered via solo fallback); for deltas,
delta_batches (codec calls), delta_batch_ops and delta_batch_units
(ops and real delta pages they carried) and delta_pad_units (zero
pages added to reach a compiled size).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import struct
import threading
import time
from collections import defaultdict
from collections.abc import Callable

import numpy as np
from ceph_tpu.utils import lockdep
from ceph_tpu.utils.lockdep import DebugLock

#: slot header: op id, k, chunk count, chunk size, csum block
#: (csum block 0 = plain encode; then the payload is [k, n*cs] flat)
_HDR = struct.Struct("<QHHII")


@functools.lru_cache(maxsize=1)
def _stream_counters():
    from ceph_tpu.utils.perf_counters import (
        PerfCountersBuilder,
        perf_collection,
    )

    b = PerfCountersBuilder(perf_collection, "ec_stream")
    b.add_u64_counter("ops", "ops submitted to the streaming dispatcher")
    b.add_u64_counter("batches", "device dispatches issued")
    b.add_u64_counter(
        "batched_ops", "ops that shared a dispatch with at least one other"
    )
    b.add_u64_gauge("max_batch", "largest batch assembled (high-water)")
    b.add_u64_counter(
        "batch_faults", "multi-op dispatches that failed and split"
    )
    b.add_u64_counter(
        "solo_retries", "ops recovered via solo fallback after a "
        "batch fault"
    )
    b.add_u64_counter(
        "delta_batches", "parity-delta batches handed to the codec"
    )
    b.add_u64_counter("delta_batch_ops", "ops those batches carried")
    b.add_u64_counter(
        "delta_batch_units", "real delta pages those batches carried"
    )
    b.add_u64_counter(
        "delta_pad_units",
        "zero pages added to reach a compiled batch size (device route)",
    )
    return b.create_perf_counters()


# ------------------------------------------------------- coalescing scope
_coal_tls = threading.local()


@contextlib.contextmanager
def coalescing_scope(tick: "DeltaTick | None" = None):
    """Thread-local scope marking this thread's encodes as part of a
    coalesced tick batch (the OSD daemon enters it around each PG
    group of a wave). Inside it, the shard-map encode routes through
    the streaming ring: concurrent group threads of one tick land
    their ops in the same ring window and share batched device
    dispatches. With ``tick``,
    the wave's ``DeltaTick``, parity deltas of this thread's ops park
    there until the whole tick has submitted. Nesting-safe."""
    _coal_tls.depth = getattr(_coal_tls, "depth", 0) + 1
    outer = getattr(_coal_tls, "tick", None)
    if tick is not None:
        _coal_tls.tick = tick
    try:
        yield
    finally:
        _coal_tls.depth -= 1
        _coal_tls.tick = outer


def current_tick() -> "DeltaTick | None":
    """The ``DeltaTick`` this thread's ops may park in, if any."""
    return getattr(_coal_tls, "tick", None)


def coalescing_active() -> bool:
    """True on a thread currently inside ``coalescing_scope`` (with
    the native ring present to stage into)."""
    if getattr(_coal_tls, "depth", 0) <= 0:
        return False
    from ceph_tpu import native

    return native.available()


# ------------------------------------------------------------ parity delta
def delta_batch(codec, members: list) -> list:
    """The one entry for parity deltas. ``members`` are ``(cols [n]
    uint8, pages [n, L] uint8, ops)``: delta pages (old XOR new) with
    the raw data column of each, and how many client ops they belong
    to. All of them go to the codec as one ``delta_contribs`` call
    (more than the largest compiled batch: one call per slice), and
    each member gets back its ``contribs [n, m, L]``, to XOR onto its
    old parity."""
    from ceph_tpu.codecs.matrix_codec import delta_batch_sizes

    pc = _stream_counters()
    if len(members) == 1:
        cols, pages = members[0][0], members[0][1]
    else:
        cols = np.concatenate([m[0] for m in members])
        pages = np.concatenate([m[1] for m in members])
    cap = delta_batch_sizes()[-1]
    outs = []
    for at in range(0, len(cols), cap):
        contribs, sent = codec.delta_contribs(
            cols[at : at + cap], pages[at : at + cap]
        )
        pc.inc("delta_batches")
        pc.inc("delta_pad_units", sent - len(contribs))
        outs.append(contribs)
    pc.inc("delta_batch_units", len(cols))
    pc.inc("delta_batch_ops", sum(m[2] for m in members))
    out = outs[0] if len(outs) == 1 else np.concatenate(outs)
    counts = [len(m[0]) for m in members]
    return [out[e - n : e] for n, e in zip(counts, np.cumsum(counts))]


@dataclasses.dataclass
class _Parked:
    """One op's delta, held by a ``DeltaTick``."""

    thread: int
    codec: object
    cols: np.ndarray
    pages: np.ndarray
    resume: Callable
    #: ``contribs [n, m, L]``, or the exception that took its place
    result: object = None


class DeltaTick:
    """The parity deltas of one coalesced OSD tick (a wave of ops on
    distinct objects, run as one thread per PG group). An op that
    encodes by delta prepares its pages and ``park``s; a group that has
    submitted all its ops ``arrive``s. The last group in sends every
    parked delta as one ``delta_batch``; then each group resumes the
    ops it parked, in the order it parked them, on its own thread."""

    def __init__(self, parties: int, timeout: float = 30.0) -> None:
        self._parties = parties
        self._timeout = timeout
        self._cv = threading.Condition()
        #: threads that have arrived: nothing of theirs parks any more
        self._arrived: set[int] = set()
        self._fired = False
        self._parked: list[_Parked] = []

    def park(self, codec, cols, pages, resume: Callable) -> bool:
        """Hold one op's delta; ``resume(contribs | exception)`` runs
        on this thread, from ``arrive`` or ``flush_thread``. False
        once this thread has arrived (an op that became ready late):
        the caller applies its delta itself."""
        me = threading.get_ident()
        with self._cv:
            if me in self._arrived:
                return False
            self._parked.append(_Parked(me, codec, cols, pages, resume))
            return True

    def _take_mine(self) -> list[_Parked]:
        """This thread's entries, out of the tick; caller holds the
        lock."""
        me = threading.get_ident()
        mine = [e for e in self._parked if e.thread == me]
        self._parked = [e for e in self._parked if e.thread != me]
        return mine

    @staticmethod
    def _apply(entries: list[_Parked]) -> None:
        """One ``delta_batch`` per codec among ``entries`` (a tick is
        one pool's as a rule); the answer, or the exception, lands in
        each entry."""
        by_codec: dict[tuple, list[_Parked]] = defaultdict(list)
        for e in entries:
            by_codec[_codec_signature(e.codec)].append(e)
        for group in by_codec.values():
            try:
                shares = delta_batch(
                    group[0].codec, [(e.cols, e.pages, 1) for e in group]
                )
                for e, share in zip(group, shares):
                    e.result = share
            except Exception as err:
                for e in group:
                    e.result = err

    def flush_thread(self) -> None:
        """Send and resume what THIS thread has parked, now: an op
        that cannot park (a full-stripe write, a packet-layout code)
        must not overtake the parked ones of its pipeline."""
        with self._cv:
            mine = self._take_mine()
        if mine:
            self._apply(mine)
            for e in mine:
                e.resume(e.result)

    def arrive(self) -> None:
        """This group has submitted its ops. Blocks until the tick's
        deltas are back (the last group in sends them), then resumes
        this thread's parked ops; a group that parked nothing (full
        writes) waits for nobody. A group that has waited ``timeout``
        for one that is still submitting sends its own."""
        me = threading.get_ident()
        with self._cv:
            self._arrived.add(me)
            self._cv.notify_all()
            if len(self._arrived) >= self._parties:
                role = "last"
                batch, self._parked = self._parked, []
            elif not any(e.thread == me for e in self._parked):
                return
            elif self._cv.wait_for(
                lambda: len(self._arrived) >= self._parties,
                self._timeout,
            ):
                self._cv.wait_for(lambda: self._fired)
                role = "follower"  # its answers are in its entries
                batch = self._take_mine()
            else:
                role = "alone"
                batch = self._take_mine()
        if role != "follower":
            self._apply(batch)
        if role == "last":
            with self._cv:
                # the other groups find their answers here
                self._parked.extend(e for e in batch if e.thread != me)
                self._fired = True
                self._cv.notify_all()
        for e in batch:
            if e.thread == me:
                e.resume(e.result)


class StreamingDispatcher:
    """Aggregates concurrent small encodes into batched dispatches."""

    def __init__(
        self,
        codec,
        *,
        capacity: int = 128,
        slot_bytes: int = (256 << 10) + _HDR.size,
        max_batch: int = 128,
        window_s: float = 0.0005,
    ) -> None:
        # Defaults size the ring for its small-op mission (the native
        # ring allocates capacity*slot_bytes EAGERLY — 32 MiB here,
        # not the 512 MiB a 1 MiB slot would pin); oversized ops take
        # the per-op path (see max_op_bytes / shard_map routing).
        from ceph_tpu.native import RingBuffer

        self.codec = codec
        self.max_batch = max_batch
        self.window_s = window_s
        self._ring = RingBuffer(capacity, slot_bytes)
        self._slot_payload = slot_bytes - _HDR.size
        self._lock = DebugLock("dispatcher.ring")
        self._next_id = 0
        #: op id -> (callback, k, chunk_len)
        self._pending: dict[int, tuple[Callable, int, int]] = {}
        self._closed = False
        self._thread = threading.Thread(
            target=self._drain_loop, name="ec-stream", daemon=True
        )
        self._thread.start()

    @property
    def max_op_bytes(self) -> int:
        """Largest [k, L] payload one slot can stage."""
        return self._slot_payload

    # -- producer side --------------------------------------------------
    def submit(
        self,
        data: np.ndarray,
        callback: Callable[[np.ndarray], None],
        csum_block: int = 0,
        n_chunks: int = 1,
    ) -> int:
        """Queue one encode of ``data`` [k, L] uint8; ``callback``
        fires (dispatcher thread) with the parity [m, L].

        With ``csum_block`` > 0 the op is a FUSED encode+csum: ``L``
        is ``n_chunks * chunk_size`` (chunk-major per shard) and the
        callback receives ``(parity [m, L], csums [n_chunks, k+m,
        cs/cb])`` — or ``(None, None)`` when no fused kernel route
        serves the geometry (callers keep their per-op fallback)."""
        data = np.ascontiguousarray(data, dtype=np.uint8)
        if data.ndim != 2:
            raise ValueError(f"want [k, L], got {data.shape}")
        k, ln = data.shape
        if k * ln > self._slot_payload:
            raise ValueError(
                f"op {k}x{ln} exceeds slot payload {self._slot_payload}"
            )
        if ln % max(n_chunks, 1):
            raise ValueError(f"L={ln} not divisible into {n_chunks}")
        with self._lock:
            if self._closed:
                raise RuntimeError("dispatcher stopped")
            op_id = self._next_id
            self._next_id += 1
            self._pending[op_id] = (callback, k, ln)
        slot = (
            _HDR.pack(op_id, k, n_chunks, ln // max(n_chunks, 1),
                      csum_block)
            + data.tobytes()
        )
        if not self._ring.push(slot, blocking=True):
            # the ring refused the slot (closed by a concurrent
            # stop()): fail loudly — a silent drop would wedge the
            # encode_sync waiter forever
            with self._lock:
                self._pending.pop(op_id, None)
            raise RuntimeError("dispatcher stopped")
        _stream_counters().inc("ops")
        return op_id

    def encode_sync(self, data: np.ndarray) -> np.ndarray:
        """Submit + wait; the batch forms from OTHER threads' ops
        arriving inside the window. A codec failure for the batch
        re-raises here (the callback receives the exception)."""
        out = self._submit_wait(data, 0, 1)
        return out

    def encode_csum_sync(
        self, data: np.ndarray, csum_block: int, n_chunks: int
    ):
        """Fused submit + wait: ``data`` [k, n_chunks*cs] chunk-major;
        returns ``(parity [m, L], csums [n_chunks, k+m, cs/cb])`` or
        ``(None, None)`` when the fused kernel can't serve the
        geometry."""
        return self._submit_wait(data, csum_block, n_chunks)

    def _submit_wait(self, data, csum_block, n_chunks):
        ev = threading.Event()
        out: list = []

        def cb(result) -> None:
            out.append(result)
            ev.set()

        self.submit(data, cb, csum_block=csum_block, n_chunks=n_chunks)
        # lockdep checkpoint: waiting out a batched device dispatch is
        # a blocking call (the "dispatcher.submit_wait" waiver covers
        # the op path's own encode work)
        with lockdep.blocking_region("dispatcher.submit_wait"):
            ev.wait()
        if isinstance(out[0], BaseException):
            raise out[0]
        return out[0]

    # -- dispatcher thread ----------------------------------------------
    def _drain_loop(self) -> None:
        while True:
            first = self._ring.pop(blocking=True)
            if first is None:  # closed and drained
                return
            ops = [first]
            # Self-clocking batch assembly (deadline + occupancy
            # hybrid, round 4): drain whatever is ALREADY queued, then
            # fire the moment the ring runs empty — waiting out the
            # window only added latency, because the next batch forms
            # naturally from the backlog that accumulates while THIS
            # dispatch is on the device (arrival rate x service time).
            # The window now only bounds a torn burst: producers
            # observed mid-enqueue get one short grace period instead
            # of a full window.
            deadline = time.monotonic() + self.window_s
            grace_used = False
            while len(ops) < self.max_batch:
                nxt = self._ring.pop(blocking=False)
                if nxt is not None:
                    ops.append(nxt)
                    continue
                if grace_used or time.monotonic() >= deadline:
                    break
                grace_used = True
                time.sleep(0.00005)
            try:
                self._fire(ops)
            except Exception:
                # The drain thread must survive ANYTHING — a dead
                # drain wedges every producer on the full ring. _fire
                # already routes per-group failures to callbacks; this
                # catches bookkeeping bugs.
                from ceph_tpu.utils.log import get_logger

                get_logger("ec-stream").error(
                    "drain iteration failed; continuing"
                )

    def _fire(self, slots: list[bytes]) -> None:
        pc = _stream_counters()
        #: plain encodes group by flat shape; fused group by chunk
        #: geometry + csum block (members stack on the chunk axis)
        plain: dict[tuple[int, int], list[tuple[int, np.ndarray]]] = (
            defaultdict(list)
        )
        fused: dict[
            tuple[int, int, int], list[tuple[int, int, np.ndarray]]
        ] = defaultdict(list)
        for raw in slots:
            op_id, k, nc, cs, cb = _HDR.unpack_from(raw)
            ln = nc * cs
            payload = np.frombuffer(
                raw, np.uint8, count=k * ln, offset=_HDR.size
            ).reshape(k, ln)
            if cb:
                fused[(k, cs, cb)].append((op_id, nc, payload))
            else:
                plain[(k, ln)].append((op_id, payload))
        for (k, ln), members in plain.items():
            results = self._fire_plain(pc, k, members)
            self._deliver(members, results)
        for (k, cs, cb), fmembers in fused.items():
            results = self._fire_fused(pc, k, cs, cb, fmembers)
            self._deliver(fmembers, results)

    def _fire_plain(self, pc, k, members) -> list:
        try:
            stacked = np.stack([p for _, p in members])  # [B, k, L]
            parity = self.codec.encode_chunks(
                {i: stacked[:, i, :] for i in range(k)}
            )
            m = len(parity)
            out = np.stack(
                [np.asarray(parity[k + j]) for j in range(m)],
                axis=1,
            )  # [B, m, L]
            results: list = [out[i] for i in range(len(members))]
            pc.inc("batches")
            if len(members) > 1:
                pc.inc("batched_ops", len(members))
            if len(members) > pc.get("max_batch"):
                pc.set("max_batch", len(members))
            return results
        except Exception as e:
            return self._solo_fallback(
                pc, members, e,
                lambda payload: self._encode_one(k, payload),
            )

    def _encode_one(self, k: int, payload: np.ndarray) -> np.ndarray:
        parity = self.codec.encode_chunks(
            {i: payload[None, i, :] for i in range(k)}
        )
        return np.stack(
            [np.asarray(parity[k + j])[0] for j in range(len(parity))]
        )

    def _fire_fused(self, pc, k, cs, cb, members) -> list:
        """One fused encode+csum dispatch for the whole group: every
        member's chunks stack on the batch axis, so the coalesced
        tick's data, parity and block csums are one HBM pass. A
        ``(None, None)`` kernel answer (geometry unservable) is a
        clean per-member result — callers fall back per-op."""

        def one(payload: np.ndarray):
            nc = payload.shape[1] // cs
            parity, csums = self.codec.encode_stacked_with_csums(
                np.ascontiguousarray(
                    payload.reshape(k, nc, cs).transpose(1, 0, 2)
                ),
                cb,
            )
            if parity is None:
                return (None, None)
            out = np.asarray(parity)  # [nc, m, cs]
            return (
                out.transpose(1, 0, 2).reshape(-1, nc * cs),
                np.asarray(csums),
            )

        try:
            counts = [nc for _, nc, _ in members]
            total = sum(counts)
            # The fused kernel is jitted on shape: every new sum(nc)
            # would be a fresh Mosaic compile in the middle of a tick.
            # Pad the stripe batch to the next power of two — a zero
            # stripe encodes to zero parity (ZERO_INPUT_ZERO_OUTPUT)
            # and its rows are never delivered — so a tick's batch
            # sizes hit at most log2(max) compiled programs.
            padded = 1 << (total - 1).bit_length()
            stacked = np.zeros((padded, k, cs), np.uint8)
            pos = 0
            for _, nc, p in members:
                stacked[pos : pos + nc] = (
                    p.reshape(k, nc, cs).transpose(1, 0, 2)
                )
                pos += nc
            parity, csums = self.codec.encode_stacked_with_csums(
                stacked, cb
            )
            if parity is None:
                return [(None, None)] * len(members)
            out = np.asarray(parity)  # [padded, m, cs]
            m = out.shape[1]
            csums = np.asarray(csums)
            results: list = []
            pos = 0
            for nc in counts:
                sl = out[pos : pos + nc]  # [nc, m, cs]
                results.append((
                    sl.transpose(1, 0, 2).reshape(m, nc * cs),
                    csums[pos : pos + nc],
                ))
                pos += nc
            pc.inc("batches")
            if len(members) > 1:
                pc.inc("batched_ops", len(members))
            if len(members) > pc.get("max_batch"):
                pc.set("max_batch", len(members))
            return results
        except Exception as e:
            return self._solo_fallback(
                pc, members, e, lambda payload: one(payload)
            )

    def _solo_fallback(self, pc, members, batch_err, one) -> list:
        """Per-op error isolation: a failed MULTI-op dispatch retries
        each member solo so one poisoned op cannot fail its
        batch-mates; a solo failure delivers the error to that member
        alone (a waiting encode_sync re-raises it; nobody hangs)."""
        if len(members) == 1:
            return [batch_err]
        pc.inc("batch_faults")
        results: list = []
        for member in members:
            payload = member[-1]
            try:
                results.append(one(payload))
                pc.inc("solo_retries")
            except Exception as solo_err:
                results.append(solo_err)
        return results

    def _deliver(self, members, results) -> None:
        for idx, member in enumerate(members):
            op_id = member[0]
            with self._lock:
                cb, _, _ = self._pending.pop(op_id)
            try:
                cb(results[idx])
            except Exception:
                from ceph_tpu.utils.log import get_logger

                get_logger("ec-stream").error(
                    "completion callback raised for op", op_id
                )

    # -- lifecycle -------------------------------------------------------
    def stop(self) -> None:
        with self._lock:
            self._closed = True
        self._ring.close()
        self._thread.join(timeout=5)


# ---------------------------------------------------------------- routing
_global: dict[tuple, StreamingDispatcher] = {}
_global_lock = DebugLock("dispatcher.registry")


def _codec_signature(codec) -> tuple:
    """Batching identity: two codecs with the same signature produce
    identical parity, so their ops may share a dispatcher (and a
    batch). Keyed by class + geometry + the encode matrix bytes when
    available — NOT instance id: PG objects rebuild their codecs on
    every map change, and an id-keyed cache would leak one ring +
    thread per rebuild while never batching across PGs."""
    bmat = getattr(codec, "_encode_bmat_np", None)
    return (
        type(codec).__name__,
        getattr(codec, "k", 0),
        getattr(codec, "m", 0),
        bmat.tobytes() if bmat is not None else None,
    )


def dispatcher_for(codec) -> StreamingDispatcher:
    """Shared dispatcher per codec SIGNATURE (lazily created) — the
    seam ShardExtentMap uses inside a coalesced tick.
    Ops from every PG with the same EC profile share one ring and
    batch together."""
    key = _codec_signature(codec)
    with _global_lock:
        d = _global.get(key)
        if d is None:
            d = StreamingDispatcher(codec)
            _global[key] = d
        return d


def shutdown_all() -> None:
    with _global_lock:
        for d in _global.values():
            d.stop()
        _global.clear()
