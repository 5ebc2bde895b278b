"""Streaming dispatcher: the staging ring feeding batched device
dispatches — SURVEY.md §7 step 4 assembled (host ring -> staging ->
batched device dispatch -> completion callbacks).

The role it fills is the reference's sharded op queues
(osd/OSD.cc:9874-9933): many client ops across many PGs land on a
shared queue and drain in batches. Here the batching axis IS the TPU
win: one [B, k, L] device encode amortizes the per-dispatch launch
and transfer over every small op in the batch — the per-op path pays
it per 4-64 KiB write.

Shape of the machinery:

- producers (OSD daemons, RMW pipelines, any thread) ``submit()``
  ops into the ring, a bounded queue of ``_RingOp``: the stripes stay
  where the submitter has them ([n, k, chunk], an object's own
  layout; one process, and nobody writes a placed run), so the one
  copy an op pays is into its batch's stack. The ring is the bounded
  staging tier — backpressure is a blocking ``submit``;
- ONE dispatcher thread drains the ring: it blocks for the first op
  and takes every op queued with it (up to ``max_batch``) under one
  hold of the ring's lock;
- ops group by (k, chunk, csum block); each group is one
  ``codec.encode_batch``: ONE route decision from the batch's real
  bytes by the codec's planner (a device route stacks the members
  once, padded to the next of ``matrix_codec.batch_sizes()``, a
  bounded set of shapes that a geometry's first fused batch compiles
  on the chip), and completion callbacks fire with each op's parity
  and csum rows;
- ``encode_sync`` / ``encode_csum_sync`` are the synchronous facade
  for pipeline callers: submit + wait, with concurrency across
  threads supplying the batch.

The round-10 serving tier adds three seams:

- ``coalescing_scope()`` — a thread-local scope the OSD daemon's
  coalesced tick batch enters around each PG group's execution:
  inside it, and only there, ``ShardExtentMap`` routes encodes
  through the ring, so concurrent groups of one tick share batched
  device dispatches;
- fused encode+csum ops stage through the SAME ring (``submit`` with
  ``csum_block``): the whole coalesced tick pays one pass for data,
  parity AND block csums;
- per-op error isolation: a failed MULTI-op batch no longer fails
  every member — each op retries SOLO through the codec, and only
  the op that actually faults surfaces its error (``solo_retries`` /
  ``batch_faults`` counters). One poisoned op cannot sink its
  batch-mates.

Spans: ``ring_wait`` (recorded across threads: an op's submit to the
firing of the batch that carries it, a child of the stage that
submitted it), ``ring.fire`` on the ``ec-stream`` thread with the
codec's ``codec.*`` steps under it, ``ring.deliver`` (the callbacks).

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
solo_retries (ops that recovered via solo fallback);
ring_wait_seconds, fire_seconds, deliver_seconds (the spans' timers);
fused_batches, fused_batch_ops, fused_batch_stripes and
fused_pad_stripes (batches that asked for csums, the ops and real
stripes they carried, the zero stripes a device route added); for
deltas,
delta_batches (codec calls), delta_batch_ops and delta_batch_units
(ops and real delta pages they carried) and delta_pad_units (zero
pages added to reach a compiled size).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from collections import defaultdict, deque
from collections.abc import Callable

import numpy as np
from ceph_tpu.utils import lockdep
from ceph_tpu.utils.lockdep import DebugLock, DebugRLock
from ceph_tpu.utils.perf_counters import built_once, register_thread_roles
from ceph_tpu.utils.trace import tracer

register_thread_roles({"ec-stream": "ec_stream"})

#: an op of more bytes is no small op: it takes the per-op path
#: (``ShardExtentMap._ring_routable``) and ``submit`` refuses it
MAX_OP_BYTES = 256 << 10


@built_once
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
    b.add_time(
        "ring_wait_seconds",
        "ring_wait: an op's submit to the firing of the batch that "
        "carries it, summed over ops",
    )
    b.add_time("fire_seconds", "ring.fire: the batches of one drain")
    b.add_time("deliver_seconds", "ring.deliver: their callbacks")
    b.add_u64_counter(
        "fused_batches", "codec batches that asked for parity and csums"
    )
    b.add_u64_counter("fused_batch_ops", "ops those batches carried")
    b.add_u64_counter(
        "fused_batch_stripes", "real stripes those batches carried"
    )
    b.add_u64_counter(
        "fused_pad_stripes",
        "zero stripes added to reach a compiled batch size (device "
        "route)",
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
    """True on a thread currently inside ``coalescing_scope``."""
    return getattr(_coal_tls, "depth", 0) > 0


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


@dataclasses.dataclass
class _RingOp:
    """One encode staged in the ring. The stripes stay where the
    submitter has them (one process, and a placed run is never written
    in place: ``ShardExtentMap``), so the only copy is the one into
    the batch's stack."""

    callback: Callable
    #: [n, k, N] host array, stripe-major
    stripes: np.ndarray
    #: 0 = parity only
    csum_block: int
    t_submit: float
    #: (trace id, span id) of the stage that submitted it
    trace: tuple
    #: its callback has been called
    answered: bool = False

    def answer(self, result) -> None:
        """Call the callback, once; what it raises is logged."""
        if self.answered:
            return
        self.answered = True
        try:
            self.callback(result)
        except Exception:
            from ceph_tpu.utils.log import get_logger

            get_logger("ec-stream").error("completion callback raised")


def _slices(members: "list[_RingOp]", cap: int):
    """``members`` in order, cut wherever the next would take a batch
    over ``cap`` stripes."""
    batch, held = [], 0
    for op in members:
        n = op.stripes.shape[0]
        if batch and held + n > cap:
            yield batch
            batch, held = [], 0
        batch.append(op)
        held += n
    if batch:
        yield batch


class StreamingDispatcher:
    """Aggregates concurrent small encodes into batched dispatches."""

    def __init__(
        self, codec, *, capacity: int = 128, max_batch: int = 128
    ) -> None:
        # The ring is the bounded staging tier: at ``capacity`` ops
        # ``submit`` blocks (backpressure), and the drain thread takes
        # what is queued, up to ``max_batch``, at once.
        self.codec = codec
        self.capacity = capacity
        self.max_batch = max_batch
        self._ring: deque[_RingOp] = deque()
        self._cv = threading.Condition(DebugRLock("dispatcher.ring"))
        self._closed = False
        self._thread = threading.Thread(
            target=self._drain_loop, name="ec-stream", daemon=True
        )
        self._thread.start()

    # -- producer side --------------------------------------------------
    def submit(
        self,
        stripes: np.ndarray,
        callback: Callable,
        csum_block: int = 0,
    ) -> None:
        """Queue one encode of ``stripes`` [n, k, N] uint8 (stripe-
        major: a whole-stripe object's own layout, so a client's
        buffer goes in as it is); it is read, never written, and must
        not change until ``callback`` has fired. ``callback`` fires on
        the dispatcher thread with ``(parity [n, m, N], csums)``, or
        with the exception that took their place.

        With ``csum_block`` > 0 the op asks for a FUSED encode+csum:
        ``csums`` is [n, k+m, N / csum_block] zero-init crc32c words,
        or None where no fused pass serves the geometry (the parity is
        there either way; callers keep their host checksums)."""
        from ceph_tpu.codecs.matrix_codec import BATCH_MAX_STRIPES

        if stripes.ndim != 3 or stripes.dtype != np.uint8:
            raise ValueError(f"want uint8 [n, k, N], got {stripes.shape}")
        if (
            stripes.nbytes > MAX_OP_BYTES
            or stripes.shape[0] > BATCH_MAX_STRIPES
        ):
            raise ValueError(
                f"op {stripes.shape} exceeds the ring's "
                f"{MAX_OP_BYTES} bytes / {BATCH_MAX_STRIPES} stripes"
            )
        if csum_block and stripes.shape[2] % csum_block:
            raise ValueError(
                f"chunk {stripes.shape[2]} not whole blocks of {csum_block}"
            )
        op = _RingOp(
            callback, stripes, csum_block, time.perf_counter(),
            tracer.current(),
        )
        with self._cv:
            self._cv.wait_for(
                lambda: self._closed or len(self._ring) < self.capacity
            )
            if self._closed:
                # fail loudly: a silent drop would wedge the
                # encode_sync waiter forever
                raise RuntimeError("dispatcher stopped")
            self._ring.append(op)
            self._cv.notify_all()
        _stream_counters().inc("ops")

    def encode_sync(self, stripes: np.ndarray) -> np.ndarray:
        """Submit + wait: parity [n, m, N]. The batch forms from OTHER
        threads' ops queued with this one. A codec failure for the
        batch re-raises here (the callback receives the exception)."""
        return self.encode_csum_sync(stripes, 0)[0]

    def encode_csum_sync(self, stripes: np.ndarray, csum_block: int):
        """Fused submit + wait: ``(parity [n, m, N], csums [n, k+m,
        N / csum_block] | None)``."""
        ev = threading.Event()
        out: list = []

        def cb(result) -> None:
            out.append(result)
            ev.set()

        self.submit(stripes, cb, csum_block=csum_block)
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
            # Self-clocking batch assembly: block for the first op,
            # take whatever is ALREADY queued with it, and fire — the
            # next batch forms from the backlog that accumulates while
            # THIS one is being served (arrival rate x service time).
            with self._cv:
                self._cv.wait_for(lambda: self._ring or self._closed)
                if not self._ring:  # closed and drained
                    return
                ops = [
                    self._ring.popleft()
                    for _ in range(min(len(self._ring), self.max_batch))
                ]
                self._cv.notify_all()
            try:
                self._fire(ops)
            except Exception as e:
                # The drain thread must survive ANYTHING — a dead
                # drain wedges every producer on the full ring. _fire
                # already routes per-group failures to callbacks; this
                # catches bookkeeping bugs, and nobody waits for ever.
                from ceph_tpu.utils.log import get_logger

                get_logger("ec-stream").error(
                    "drain iteration failed; continuing"
                )
                for op in ops:
                    op.answer(e)

    def _fire(self, ops: "list[_RingOp]") -> None:
        """One drain of the ring: its ops grouped by geometry (k,
        chunk, csum block), each group one codec batch (more where it
        holds over ``BATCH_MAX_STRIPES`` stripes), then the callbacks."""
        from ceph_tpu.codecs.matrix_codec import BATCH_MAX_STRIPES

        pc = _stream_counters()
        groups: dict[tuple, list[_RingOp]] = defaultdict(list)
        for op in ops:
            groups[op.stripes.shape[1:] + (op.csum_block,)].append(op)
        done: list[tuple[_RingOp, object]] = []
        with tracer.span(
            "ring.fire", perf=pc, key="fire_seconds", ops=len(ops)
        ):
            now = time.perf_counter()
            for op in ops:
                tracer.record(
                    "ring_wait", op.t_submit, max(now, op.t_submit),
                    trace_id=op.trace[0], parent_id=op.trace[1],
                    perf=pc, key="ring_wait_seconds",
                )
            for members in groups.values():
                for batch in _slices(members, BATCH_MAX_STRIPES):
                    done.extend(zip(batch, self._encode(pc, batch)))
        with tracer.span(
            "ring.deliver", perf=pc, key="deliver_seconds", ops=len(ops)
        ):
            for op, result in done:
                op.answer(result)

    def _encode(self, pc, members: "list[_RingOp]") -> list:
        """One codec batch: each member's ``(parity, csums | None)``,
        views of the batch's arrays. A failed MULTI-op batch retries
        each member solo so one poisoned op cannot fail its
        batch-mates; a solo failure delivers the error to that member
        alone (a waiting encode_sync re-raises it; nobody hangs)."""
        counts = [op.stripes.shape[0] for op in members]
        total, cb = sum(counts), members[0].csum_block
        arrays = [op.stripes for op in members]
        try:
            if hasattr(self.codec, "encode_batch"):
                # one route decision, one copy a member, a bounded set
                # of padded shapes (MatrixErasureCodec.encode_batch)
                parity, csums, sent = self.codec.encode_batch(arrays, cb)
            else:
                stack = arrays[0] if len(arrays) == 1 else (
                    np.concatenate(arrays)
                )
                k = stack.shape[1]
                out = self.codec.encode_chunks(
                    {i: stack[:, i, :] for i in range(k)}
                )
                parity = np.stack(
                    [np.asarray(out[k + j]) for j in range(len(out))],
                    axis=1,
                )
                csums, sent = None, total
        except Exception as e:
            if len(members) == 1:
                return [e]
            pc.inc("batch_faults")
            results = []
            for op in members:
                results.extend(self._encode(pc, [op]))
                if not isinstance(results[-1], BaseException):
                    pc.inc("solo_retries")
            return results
        pc.inc("batches")
        if cb:
            pc.inc("fused_batches")
            pc.inc("fused_batch_ops", len(members))
            pc.inc("fused_batch_stripes", total)
            pc.inc("fused_pad_stripes", sent - total)
        if len(members) > 1:
            pc.inc("batched_ops", len(members))
        if len(members) > pc.get("max_batch"):
            pc.set("max_batch", len(members))
        ends = np.cumsum(counts)
        return [
            (parity[e - n : e], None if csums is None else csums[e - n : e])
            for n, e in zip(counts, ends)
        ]

    # -- lifecycle -------------------------------------------------------
    def stop(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()
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
