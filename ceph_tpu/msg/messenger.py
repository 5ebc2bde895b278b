"""Socket messenger — the AsyncMessenger analog (host-to-host tier).

Mirrors the roles of msg/async/AsyncMessenger.{h,cc}: a ``Messenger``
binds a listening address and dispatches inbound typed messages to its
dispatcher (the ``ms_fast_dispatch`` seam, osd/OSD.cc:7686);
``Connection`` objects carry framed messages (wire.py) over TCP with a
reader thread per connection. Event-loop sophistication (epoll worker
pools, lossy/lossless policies with replay) is intentionally replaced
by one thread per connection — connection counts here are k+m, not
thousands; the wire format, per-segment CRC, and dispatch contract are
the load-bearing parts.

Network-fault plane (the tc/netem analog, qa thrasher msgr-failures
role): :data:`net_faults` is a process-global, seeded registry of
per-link (src name → dst name) rules — drop probability, delay
distribution, duplication, reordering, and full/asymmetric partitions.
Faults apply to LOGICAL frames above TCP, at the connection-initiating
end, which knows both endpoint names (outbound requests in ``send``,
inbound replies after decode in the read loop) — each direction of a
link is therefore faulted exactly once, and a delayed outbound frame
is re-sent through the normal seal-under-lock path so secure-mode
counters stay consistent with socket order. Every decision comes from
a per-link ``random.Random`` seeded from (plane seed, src, dst): the
same seed replays the same per-link firing sequence, which is what
makes a chaos run a regression test instead of a dice roll. When
nothing is armed the cost is one attribute check per frame.
"""

from __future__ import annotations

import errno
import fnmatch
import heapq
import itertools
import socket
import threading
import time
import weakref
import zlib
from collections.abc import Callable

from . import secure as secure_mod
from . import shm_ring
from .messages import decode_message, message_type
from . import wire
from .wire import BadFrame, decode_frame, encode_frame
from ceph_tpu.utils import lockdep
from ceph_tpu.utils.lockdep import DebugLock
from ceph_tpu.utils.perf_counters import register_thread_roles

# a messenger's threads carry its name (``msgr-<name>-rd`` a link's
# reader, ``-acc`` the accepter, ``-hs`` an accept's handshake), so
# that whoever names a messenger can claim its threads for another role
register_thread_roles({"msgr-*": "msgr", "net-fault-timer": "msgr"})


#: listening addr -> messenger name, registered at bind() — how a
#: connecting end resolves the PEER's name so the fault plane can key
#: its link rules on (src, dst) daemon names (in-process clusters
#: only; a cross-host deployment would carry names in a hello frame)
_addr_names: dict[tuple[str, int], str] = {}
_addr_lock = DebugLock("msgr.addr_registry")


class LinkRule:
    """One link's injection profile. Probabilities are per logical
    frame per direction; ``delay_ms`` + uniform ``delay_jitter_ms``
    is the netem delay/jitter pair (p95 = delay + 0.95·jitter);
    ``reorder`` holds a frame until the next one on the link passes
    it; ``partition`` drops everything (compose two asymmetric rules
    for a full partition)."""

    __slots__ = (
        "drop", "dup", "delay_ms", "delay_jitter_ms", "reorder",
        "partition",
    )

    def __init__(
        self,
        drop: float = 0.0,
        dup: float = 0.0,
        delay_ms: float = 0.0,
        delay_jitter_ms: float = 0.0,
        reorder: float = 0.0,
        partition: bool = False,
    ) -> None:
        for name, p in (("drop", drop), ("dup", dup), ("reorder", reorder)):
            if not 0.0 <= p <= 1.0:
                raise ValueError(f"{name} must be a probability, got {p}")
        if delay_ms < 0 or delay_jitter_ms < 0:
            raise ValueError("delays must be >= 0")
        self.drop = drop
        self.dup = dup
        self.delay_ms = delay_ms
        self.delay_jitter_ms = delay_jitter_ms
        self.reorder = reorder
        self.partition = partition

    def __repr__(self) -> str:  # the `tc qdisc show` analog
        parts = []
        if self.partition:
            parts.append("partition")
        if self.drop:
            parts.append(f"drop={self.drop}")
        if self.dup:
            parts.append(f"dup={self.dup}")
        if self.delay_ms or self.delay_jitter_ms:
            parts.append(
                f"delay={self.delay_ms}ms+{self.delay_jitter_ms}ms"
            )
        if self.reorder:
            parts.append(f"reorder={self.reorder}")
        return f"LinkRule({' '.join(parts) or 'clean'})"


class _Lane:
    """Per-(src, dst) state: the resolved rule, a deterministic RNG,
    and the held-frame slot the reorder fault uses."""

    __slots__ = ("rule", "rng", "held", "lock")

    def __init__(self, rule: "LinkRule | None", seed: int) -> None:
        import random

        self.rule = rule
        self.rng = random.Random(seed)
        self.held: "Callable[[], None] | None" = None
        self.lock = DebugLock("msgr.net_lane")


#: counters the plane keeps (process totals; per-daemon slices ride
#: the owning messenger's ``net_pc`` perf set when one is attached)
FAULT_COUNTERS = (
    "frames_dropped", "frames_delayed", "frames_duped",
    "frames_reordered",
)


class NetFaultPlane:
    """Process-global seeded link-fault registry (see module doc).

    Arm with :meth:`add_rule` / :meth:`partition`; every armed plane
    change bumps a generation so lanes re-resolve their rule lazily.
    ``clear()`` disarms and FLUSHES in-flight delayed/held frames
    (delivered immediately — a cleared plane must not keep eating
    frames), so a fault window has a crisp settle edge."""

    #: failsafe: a reorder-held frame is force-flushed after this many
    #: seconds even if no follow-up frame ever crosses the lane
    REORDER_FLUSH_S = 0.1

    def __init__(self) -> None:
        self._lock = DebugLock("msgr.net_faults")
        self._rules: list[tuple[str, str, LinkRule]] = []
        self._lanes: dict[tuple[str, int], _Lane] = {}
        self._gen = 0
        self.seed = 0
        self.active = False
        self.counters = dict.fromkeys(FAULT_COUNTERS, 0)
        # delayed-delivery timer machinery (lazy daemon thread)
        self._timers: list[tuple[float, int, Callable[[], None]]] = []
        self._timer_seq = itertools.count(1)
        self._timer_cv = threading.Condition()
        self._timer_thread: threading.Thread | None = None

    # -- operator surface (the `tc qdisc add` analog) -------------------
    def configure(self, seed: int) -> "NetFaultPlane":
        """Set the plane seed and reset lane RNGs — call once per run
        BEFORE arming rules; same seed => same per-link firings."""
        with self._lock:
            self.seed = int(seed)
            self._lanes.clear()
            self._gen += 1
        return self

    def add_rule(self, src: str, dst: str, rule: LinkRule) -> None:
        """Arm ``rule`` for frames src→dst (fnmatch patterns, e.g.
        ``("osd.*", "osd.*")``). First matching rule wins. The
        ``msgr_fault_plane`` config gate (evaluated at arm time) is
        the operator escape hatch that keeps armed rules inert."""
        from ceph_tpu.utils import config
        from ceph_tpu.utils.cluster_log import cluster_log

        with self._lock:
            self._rules.append((src, dst, rule))
            self._gen += 1
            self.active = bool(config.get("msgr_fault_plane"))
        # the arm lands in the cluster log so a chaos run's fallout
        # (slow ops, down-marks) lines up against its cause
        cluster_log.log(
            "net", "net_fault_armed",
            f"link rule armed {src} -> {dst}: {rule!r}"
            + ("" if self.active else " (inert: msgr_fault_plane=false)"),
            severity="WRN", seed=self.seed,
        )

    def partition(
        self, names, peers: str = "*", asymmetric: bool = False
    ) -> None:
        """Partition every name in ``names`` from ``peers``:
        symmetric by default; ``asymmetric=True`` cuts only the
        INBOUND direction (peers → victim), the half-partition that
        makes a victim keep talking into a void — the peering
        re-election torture case."""
        if isinstance(names, str):
            names = [names]
        for name in names:
            self.add_rule(peers, name, LinkRule(partition=True))
            if not asymmetric:
                self.add_rule(name, peers, LinkRule(partition=True))

    def clear(self) -> None:
        """Disarm everything and flush held/delayed frames NOW."""
        with self._lock:
            had_rules = bool(self._rules)
            self._rules.clear()
            self._gen += 1
            self.active = False
            lanes = list(self._lanes.values())
        if had_rules:
            from ceph_tpu.utils.cluster_log import cluster_log

            cluster_log.log(
                "net", "net_fault_cleared",
                "fault plane cleared (held/delayed frames flushed)",
            )
        held = []
        for lane in lanes:
            with lane.lock:
                if lane.held is not None:
                    held.append(lane.held)
                    lane.held = None
        with self._timer_cv:
            pending = [fn for _w, _s, fn in self._timers]
            self._timers.clear()
            self._timer_cv.notify()
        for fn in held + pending:
            try:
                fn()
            except Exception:
                pass  # the link may have died while the frame was held
        with self._lock:
            self._lanes.clear()

    def reset_counters(self) -> None:
        with self._lock:
            self.counters = dict.fromkeys(FAULT_COUNTERS, 0)

    # -- plumbing -------------------------------------------------------
    def _resolve(self, src: str, dst: str) -> "LinkRule | None":
        for pat_s, pat_d, rule in self._rules:
            if fnmatch.fnmatchcase(src, pat_s) and fnmatch.fnmatchcase(
                dst, pat_d
            ):
                return rule
        return None

    def _lane(self, src: str, dst: str) -> _Lane:
        key = (f"{src}>{dst}", self._gen)
        with self._lock:
            lane = self._lanes.get(key)
            if lane is None:
                # prune lanes from superseded generations (their rule
                # resolution is stale; the RNG restarts per arming,
                # which keeps a configure+arm block deterministic)
                for old in [k for k in self._lanes if k[1] != self._gen]:
                    del self._lanes[old]
                lane = self._lanes[key] = _Lane(
                    self._resolve(src, dst),
                    zlib.crc32(f"{self.seed}|{src}>{dst}".encode()),
                )
            return lane

    def _count(self, kind: str, owner: "Messenger | None") -> None:
        with self._lock:
            self.counters[kind] += 1
        pc = getattr(owner, "net_pc", None)
        if pc is not None:
            pc.inc(kind)

    def _at(self, when: float, fn: Callable[[], None]) -> None:
        with self._timer_cv:
            heapq.heappush(
                self._timers, (when, next(self._timer_seq), fn)
            )
            if self._timer_thread is None or not self._timer_thread.is_alive():
                self._timer_thread = threading.Thread(
                    target=self._timer_loop, daemon=True,
                    name="net-fault-timer",
                )
                self._timer_thread.start()
            self._timer_cv.notify()

    def _timer_loop(self) -> None:
        while True:
            with self._timer_cv:
                if not self._timers:
                    if not self._timer_cv.wait(5.0) and not self._timers:
                        self._timer_thread = None
                        return
                    continue
                when = self._timers[0][0]
                now = time.monotonic()
                if when > now:
                    self._timer_cv.wait(min(when - now, 0.5))
                    continue
                _w, _s, fn = heapq.heappop(self._timers)
            try:
                fn()
            except Exception:
                pass  # a dead link eats the frame, like a real drop

    # -- the per-frame decision (the netem hook) ------------------------
    def process(
        self,
        src: str,
        dst: str,
        deliver: Callable[[], None],
        owner: "Messenger | None" = None,
    ) -> None:
        """Run one frame src→dst through the link's rule. ``deliver``
        performs the actual send/dispatch; it may run synchronously
        (clean frame — exceptions propagate to the caller exactly as
        without the plane), later on the timer thread (delay/reorder/
        dup copies; exceptions there are swallowed, the frame is
        simply lost like any fault), or never (drop/partition)."""
        lane = self._lane(src, dst)
        rule = lane.rule
        if rule is None:
            deliver()
            return
        with lane.lock:
            rng = lane.rng
            # one draw per fault class per frame, in a FIXED order, so
            # the per-link decision sequence is a pure function of
            # (seed, frame index on the link)
            p_drop = rng.random()
            p_dup = rng.random()
            p_delay = rng.random()
            p_reorder = rng.random()
            dropped = rule.partition or (
                rule.drop > 0.0 and p_drop < rule.drop
            )
            dup = rule.dup > 0.0 and p_dup < rule.dup
            delay = 0.0
            if rule.delay_ms or rule.delay_jitter_ms:
                delay = (
                    rule.delay_ms + rule.delay_jitter_ms * p_delay
                ) / 1000.0
            reorder = rule.reorder > 0.0 and p_reorder < rule.reorder
            released, lane.held = lane.held, None
        if dropped:
            self._count("frames_dropped", owner)
            if released is not None:
                self._guarded(released)
            return
        if dup:
            self._count("frames_duped", owner)

        def emit() -> None:
            deliver()
            if dup:
                self._guarded(deliver)

        if reorder and released is None:
            # hold THIS frame; the next frame on the lane (or the
            # failsafe timer) releases it behind itself
            self._count("frames_reordered", owner)
            hold = (
                emit if delay == 0.0
                else lambda: self._later(delay, emit, owner, count=False)
            )
            with lane.lock:
                if lane.held is None:
                    lane.held = hold
                    self._at(
                        time.monotonic() + delay + self.REORDER_FLUSH_S,
                        lambda: self._flush_lane(lane),
                    )
                    if delay:
                        self._count("frames_delayed", owner)
                    return
            # lost the slot to a racing frame: fall through, deliver
        if delay:
            self._count("frames_delayed", owner)
            self._later(delay, emit, owner, count=False)
        else:
            emit()
        if released is not None:
            self._guarded(released)

    def _later(self, delay, fn, owner, count=True) -> None:
        if count:
            self._count("frames_delayed", owner)
        self._at(time.monotonic() + delay, lambda: self._guarded(fn))

    def _flush_lane(self, lane: _Lane) -> None:
        with lane.lock:
            held, lane.held = lane.held, None
        if held is not None:
            self._guarded(held)

    @staticmethod
    def _guarded(fn: Callable[[], None]) -> None:
        try:
            fn()
        except Exception:
            pass  # faulted copy on a dead link: just lost


#: the process-global fault plane (tests and loadgen arm it)
net_faults = NetFaultPlane()

def make_net_perf(name: str):
    """A messenger's ``net`` counter set (``perf dump`` section
    ``osd.<id>.net`` / ``<client>.net``, Prometheus via the exporter).
    Traffic first: frames and bytes each way, with the seconds spent
    framing + writing (``send_seconds``: the message's encode, the
    frame's crc32c and the socket write, under the send lock) and
    reading + parsing (``recv_seconds``: from a frame's header in hand
    to its message decoded, so an idle link adds nothing), and
    ``io_calls``: how often the messenger left the interpreter for
    those frames. Then what the seeded fault plane did to this
    daemon's links, and what the dedup tiers absorbed — the
    observability half of the chaos contract (injected faults MUST
    show up here, absorbed duplicates MUST show up there, and the
    ledger still balances exactly-once). Last the interpreter lock:
    what the native frame calls on this set's messengers' links kept
    of their hand-overs (:meth:`Messenger.hand_overs`), read when the
    set is dumped; a link on the Python frame path keeps none and
    reads 0."""
    from ceph_tpu.utils import PerfCountersBuilder, perf_collection

    def lock_sums() -> dict:
        with _messengers_lock:
            mine = [m for m in _messengers if m.net_pc is pc]
        return dict(zip(
            ("lock_waits", "lock_waits_slow", "call_seconds",
             "lock_wait_seconds"),
            _sum4(m.hand_overs() for m in mine),
        ))

    pc = (
        PerfCountersBuilder(perf_collection, name)
        .add_u64_counter("frames_sent", "frames written to a socket")
        .add_u64_counter("bytes_sent", "framed bytes written")
        .add_time("send_seconds", "frame encode + sendall")
        .add_u64_counter("frames_recv", "frames read and decoded")
        .add_u64_counter("bytes_recv", "framed bytes read")
        .add_time(
            "recv_seconds",
            "frame header in hand to message decoded (body read, "
            "CRC check, decode)",
        )
        .add_u64_counter(
            "io_calls",
            "times the messenger left the interpreter for a frame: a "
            "recv, a sendall, a codec call, a native frame send/recv",
        )
        .add_u64_counter(
            "frames_dropped", "frames dropped by fault injection"
        )
        .add_u64_counter(
            "frames_delayed", "frames delayed by fault injection"
        )
        .add_u64_counter(
            "frames_duped", "frames duplicated by fault injection"
        )
        .add_u64_counter(
            "frames_reordered", "frames reordered by fault injection"
        )
        .add_u64_counter(
            "resends_absorbed",
            "duplicate/straggler sub-write acks with no pending op",
        )
        .add_u64_counter(
            "dedup_hits",
            "resent client mutations replayed from the reqid cache",
        )
        .add_sampled_group(lock_sums, {
            "lock_waits":
                "native frame calls that gave the interpreter lock up "
                "and took it back (io_calls of the native frame path)",
            "lock_waits_slow":
                "of them, waits of one switch interval "
                "(sys.getswitchinterval) or more",
            "call_seconds":
                "inside those calls with the lock given up: crc, copy, "
                "socket (a receive counts from its header in hand)",
            "lock_wait_seconds":
                "waiting to hold the interpreter lock again at the end "
                "of those calls (inside PyEval_RestoreThread)",
        })
        .create_perf_counters()
    )
    return pc


def _sum4(rows) -> list:
    """Column sums of ``(calls, slow, call seconds, wait seconds)``
    rows (``native.HandOvers.read``)."""
    return [sum(col) for col in zip((0, 0, 0.0, 0.0), *rows)]


# In-the-clear handshake frame type for secure-mode nonce exchange
# (outside the normal message-type space; auth_none + CephX roles).
HANDSHAKE_TYPE = 0x7FFF


class Connection:
    """One peer link; ``send(msg)`` frames and writes atomically.

    With a cluster secret configured, the connection runs the secure
    handshake (nonce exchange -> per-direction AES-GCM sessions)
    synchronously before the reader thread starts, so no payload
    message ever travels in the clear."""

    def __init__(
        self,
        sock: socket.socket,
        messenger: "Messenger",
        is_client: bool = False,
        peer_name: "str | None" = None,
    ) -> None:
        self.sock = sock
        self.messenger = messenger
        #: the remote messenger's name when known (client-initiated
        #: conns resolve it from the bind registry). The fault plane
        #: only acts where BOTH names are known — i.e. once per
        #: logical direction, at the connection-initiating end.
        self.peer_name = peer_name
        self._send_lock = DebugLock("msgr.send")
        self._seq = 0
        self.alive = True
        self._tx = self._rx = None
        # native frame I/O works on the bare descriptor, so the
        # descriptor must outlive every call that took it: calls are
        # counted in and out under this lock, and a close that finds
        # one in flight is left to the last of them
        self._fd_lock = DebugLock("msgr.fd")
        self._fd_users = 0
        self._fd_close_pending = False
        self._rx_frames = None  # native.FrameReceiver, made when first used
        # times the interpreter was left for the frame being written
        # (under the send lock) and for the one being read (the reader)
        self._tx_calls = self._rx_calls = 0
        if messenger.secret is not None:
            try:
                self._handshake(is_client)
            except Exception:
                self.alive = False
                try:
                    self.sock.close()
                except OSError:
                    pass
                raise
        #: what the link is decides whether the native codec may take
        #: the socket: clear (no AES-GCM session), uncompressed, and on
        #: a kernel descriptor (a shm-ring end is not one). Whether it
        #: does is asked per frame (``wire.frame_io``: native tier
        #: loaded, ``msgr_native_codec`` on).
        self._kernel_clear = (
            self._tx is None
            and not messenger.compress
            and isinstance(sock, socket.socket)
        )
        #: where the native frame calls keep their hand-overs of the
        #: interpreter lock (``native.HandOvers``: the send side's,
        #: written under the send lock, and the reader's); None on a
        #: link the codec never takes, which then reports 0
        self._tx_ho = self._rx_ho = None
        self._ho_folded = False  # into the messenger's total, at close
        if self._kernel_clear:
            self._tx_ho, self._rx_ho = wire.hand_overs(), wire.hand_overs()
        self._reader = threading.Thread(
            target=self._read_loop, daemon=True,
            name=f"msgr-{messenger.name}-rd",
        )
        self._reader.start()

    def hand_overs(self) -> list:
        """Both sides' hand-overs of the interpreter lock, summed."""
        return _sum4(
            ho.read() for ho in (self._tx_ho, self._rx_ho) if ho is not None
        )

    def _handshake(self, is_client: bool) -> None:
        # Bounded: a peer that connects and goes silent must not wedge
        # the accept loop (the reference bounds auth exchanges too).
        self.sock.settimeout(5)
        try:
            self._do_handshake(is_client)
        finally:
            self.sock.settimeout(None)

    def _do_handshake(self, is_client: bool) -> None:
        my_nonce = secure_mod.fresh_nonce()
        hello = encode_frame(HANDSHAKE_TYPE, 0, [my_nonce])
        try:
            if is_client:
                self.sock.sendall(hello)
                peer_nonce = self._read_handshake()
                nonce_c, nonce_s = my_nonce, peer_nonce
            else:
                peer_nonce = self._read_handshake()
                self.sock.sendall(hello)
                nonce_c, nonce_s = peer_nonce, my_nonce
        except (EOFError, BadFrame, socket.timeout) as e:
            # A clear-mode or garbage-speaking peer must look like any
            # other dead link (callers map ConnectionError to a down
            # shard), not raise EOFError/BadFrame out of the op path.
            raise ConnectionError(f"secure handshake failed: {e!r}") from e
        self._tx, self._rx = secure_mod.derive_session(
            self.messenger.secret, nonce_c, nonce_s, is_client
        )

    def _read_handshake(self) -> bytes:
        msg_type, _seq, segments = decode_frame(self._read_exact)
        if msg_type != HANDSHAKE_TYPE or len(segments) != 1:
            raise ConnectionError("peer did not offer secure handshake")
        return segments[0]

    def send(self, msg) -> None:
        # lockdep checkpoint: a socket write is a blocking call —
        # executing one while an op-serializing lock is held is only
        # legitimate on the op's own (bounded) commit path, which the
        # "messenger.send" waiver documents
        with lockdep.blocking_region("messenger.send"):
            self._send_faulted(msg)

    def _send_faulted(self, msg) -> None:
        if net_faults.active and self.peer_name is not None:
            # outbound half of the link: the plane may drop the frame
            # (caller sees success — exactly a lost frame), defer it
            # (re-enters _send_now on the timer thread; sealing order
            # still matches socket order because encode happens at
            # delivery time under the send lock), or duplicate it.
            net_faults.process(
                self.messenger.name,
                self.peer_name,
                lambda m=msg: self._send_now(m),
                owner=self.messenger,
            )
            return
        self._send_now(msg)

    def _frame_io(self):
        """The native module when this frame's I/O is the codec's."""
        return wire.frame_io() if self._kernel_clear else None

    def _fd_enter(self) -> int:
        """The socket's descriptor for one native call; it stays open
        until the matching :meth:`_fd_exit`."""
        with self._fd_lock:
            fd = -1 if self._fd_close_pending else self.sock.fileno()
            if fd < 0:
                raise OSError(errno.EBADF, "connection closed")
            self._fd_users += 1
            return fd

    def _fd_exit(self) -> None:
        with self._fd_lock:
            self._fd_users -= 1
            last = self._fd_close_pending and not self._fd_users
        if last:
            self._close_sock()

    def _close_sock(self) -> None:
        with self._fd_lock:
            self.alive = False
            if self._fd_users:
                self._fd_close_pending = True
                return
        try:
            self.sock.close()
        except OSError:
            pass

    def _send_now(self, msg) -> None:
        pc = self.messenger.net_pc
        with self._send_lock:
            t0 = time.perf_counter()
            self._seq += 1
            segments = msg.encode()
            io = self._frame_io()
            self._tx_calls = 1  # the write itself
            try:
                if io is not None:
                    # framed, checksummed and written in the one call
                    fd = self._fd_enter()
                    try:
                        nbytes = wire.send_frame(
                            io, fd, message_type(msg), self._seq, segments,
                            self._tx_ho,
                        )
                    finally:
                        self._fd_exit()
                else:
                    # Sealing must happen under the send lock: the AEAD
                    # tx counter and the socket write have to agree on
                    # order.
                    frame = encode_frame(
                        message_type(msg),
                        self._seq,
                        segments,
                        compress=self.messenger.compress,
                        secure=self._tx,
                        tally=self._tally_tx,
                    )
                    self.sock.sendall(frame)
                    nbytes = len(frame)
            except OSError as e:
                self.alive = False
                raise ConnectionError(str(e)) from e
            if pc is not None:
                pc.inc("frames_sent")
                pc.inc("bytes_sent", nbytes)
                pc.inc("io_calls", self._tx_calls)
                pc.tinc("send_seconds", time.perf_counter() - t0)

    def _tally_tx(self, n: int) -> None:
        self._tx_calls += n

    def _tally_rx(self, n: int) -> None:
        self._rx_calls += n

    def _read_exact(self, n: int) -> bytes:
        buf = b""
        while len(buf) < n:
            chunk = self.sock.recv(n - len(buf))
            self._rx_calls += 1
            if not chunk:
                raise EOFError
            buf += chunk
        return buf

    def _recv_py(self):
        """One frame through the Python path: (msg_type, segments,
        framed bytes, clock at the header's arrival, io calls)."""
        # bytes read, and the clock at the header's arrival (the wait
        # for it is an idle link, not receive work)
        got = [0, 0.0]
        self._rx_calls = 0

        def read_counted(n: int) -> bytes:
            buf = self._read_exact(n)
            if not got[0]:
                got[1] = time.perf_counter()
            got[0] += n
            return buf

        msg_type, _seq, segments = decode_frame(
            read_counted, secure=self._rx, tally=self._tally_rx
        )
        return msg_type, segments, got[0], got[1], self._rx_calls

    def _recv_native(self, io):
        """One frame read and verified by the codec itself; same
        result as :meth:`_recv_py`."""
        rx = self._rx_frames
        if rx is None:
            rx = self._rx_frames = io.FrameReceiver(self._rx_ho)
        fd = self._fd_enter()
        try:
            msg_type, _seq, segments = wire.recv_frame(io, fd, rx)
        finally:
            self._fd_exit()
        return msg_type, segments, rx.frame_bytes, rx.info.t_header, rx.calls

    def _read_loop(self) -> None:
        try:
            while True:
                io = self._frame_io()
                msg_type, segments, nbytes, t_hdr, calls = (
                    self._recv_py() if io is None else self._recv_native(io)
                )
                msg = decode_message(msg_type, segments)
                pc = self.messenger.net_pc
                if pc is not None:
                    pc.inc("frames_recv")
                    pc.inc("bytes_recv", nbytes)
                    pc.inc("io_calls", calls)
                    pc.tinc(
                        "recv_seconds", time.perf_counter() - t_hdr
                    )
                if net_faults.active and self.peer_name is not None:
                    # inbound half of the link (peer → me): replies on
                    # a client-initiated conn are faulted HERE, after
                    # decode — the server end never needs to know our
                    # name, and secure frames are already opened
                    net_faults.process(
                        self.peer_name,
                        self.messenger.name,
                        lambda m=msg: self.messenger.dispatch(self, m),
                        owner=self.messenger,
                    )
                else:
                    self.messenger.dispatch(self, msg)
        except (EOFError, OSError):
            pass
        except Exception:
            # Decode/dispatch failure (bad frame, unknown type, handler
            # bug): drop the connection loudly-at-the-socket so the
            # peer sees EOF and fails fast instead of waiting out RPC
            # timeouts on a wedged link.
            pass
        finally:
            self._close_sock()
            self.messenger._conn_closed(self)

    def close(self) -> None:
        self.alive = False
        # wakes a reader blocked in recv — Python's or the native
        # codec's — with EOF, and a writer with EPIPE
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self._close_sock()


#: every messenger of the process (a ``net`` set finds the ones it is
#: attached to when it is dumped)
_messengers: "weakref.WeakSet[Messenger]" = weakref.WeakSet()
_messengers_lock = DebugLock("msgr.registry")


class Messenger:
    """Bind/connect endpoint + dispatcher registry."""

    def __init__(
        self,
        name: str,
        compress: bool = False,
        secret: bytes | None = None,
    ) -> None:
        self.name = name
        # On-wire compression for frames WE send (receivers auto-detect
        # via the frame flags — compression_onwire.cc role).
        self.compress = compress
        # Cluster pre-shared secret (keyring role): non-None enables
        # AES-GCM secure mode on every connection of this messenger.
        # Both ends must agree — a secure peer rejects clear frames
        # and vice versa (mode is per-connection, negotiated up front).
        self.secret = secret
        #: per-daemon net-fault counter set (``osd.N.net``): the
        #: owning daemon attaches one; the fault plane increments it
        #: for frames it drops/delays/dupes/reorders on this
        #: messenger's links
        self.net_pc = None
        self.dispatcher: Callable[[Connection, object], None] | None = None
        self._listener: socket.socket | None = None
        self._accept_thread: threading.Thread | None = None
        self._stopping = False
        self._conns: set[Connection] = set()
        self._lock = DebugLock("msgr.conns")
        #: hand-overs of the interpreter lock on links that have closed
        self._ho_closed = [0, 0, 0.0, 0.0]
        self.addr: tuple[str, int] | None = None
        with _messengers_lock:
            _messengers.add(self)

    def set_dispatcher(self, fn: Callable[[Connection, object], None]) -> None:
        self.dispatcher = fn

    def dispatch(self, conn: Connection, msg) -> None:
        if self.dispatcher is not None:
            self.dispatcher(conn, msg)

    # -- server side ---------------------------------------------------
    def bind(self, host: str = "127.0.0.1", port: int = 0) -> tuple[str, int]:
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind((host, port))
        s.listen(64)
        # Poll with a timeout: closing a listener out from under a
        # thread blocked in accept() does NOT close the kernel-side
        # open file description — the old accept keeps serving the
        # port. The flag + timeout loop is the portable shutdown.
        s.settimeout(0.2)
        self._stopping = False
        self._listener = s
        self.addr = s.getsockname()
        with _addr_lock:
            _addr_names[self.addr] = self.name
        # shm-ring lane registration (always cheap; the msgr_transport
        # gate decides at connect() time whether anyone upgrades)
        shm_ring.register(self.addr, self)
        self._accept_thread = threading.Thread(
            target=self._accept_loop, daemon=True,
            name=f"msgr-{self.name}-acc",
        )
        self._accept_thread.start()
        return self.addr

    def _accept_loop(self) -> None:
        while not self._stopping:
            try:
                sock, _peer = self._listener.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            sock.settimeout(None)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            # Finish connection setup off the accept thread: the secure
            # handshake blocks up to its 5 s timeout, and one silent
            # connector must not starve other peers' accepts.
            threading.Thread(
                target=self._finish_accept, args=(sock,), daemon=True,
                name=f"msgr-{self.name}-hs",
            ).start()
        try:
            self._listener.close()
        except OSError:
            pass

    def _finish_accept(self, sock: socket.socket) -> None:
        try:
            conn = Connection(sock, self, is_client=False)
        except Exception:
            return  # failed handshake drops the socket, not us
        with self._lock:
            if self._stopping:
                conn.close()
                return
            self._conns.add(conn)

    # -- client side ---------------------------------------------------
    def connect(self, addr: tuple[str, int]) -> Connection:
        # Transport negotiation: when the shm-ring lane is configured
        # and the peer listens in-process, skip the kernel socket
        # entirely — the Connection (framing, CRC, secure handshake,
        # fault-plane hooks) runs unchanged over the ring pair.
        target = shm_ring.lookup(addr)
        if target is not None:
            client_sock, server_sock = shm_ring.socketpair()
            # the server end rides the normal accept path, off-thread
            # (the secure handshake blocks, exactly like TCP accepts)
            threading.Thread(
                target=target._finish_accept,
                args=(server_sock,),
                daemon=True,
                name=f"msgr-{target.name}-hs",
            ).start()
            conn = Connection(
                client_sock, self, is_client=True, peer_name=target.name
            )
            with self._lock:
                self._conns.add(conn)
            return conn
        sock = socket.create_connection(addr, timeout=10)
        if sock.getsockname() == sock.getpeername():
            # TCP self-connect: the kernel picked the (freed) target
            # port as our ephemeral source port — the peer is gone.
            sock.close()
            raise ConnectionError(f"self-connect to dead peer {addr}")
        sock.settimeout(None)  # connect timeout must not become a
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)  # recv timeout
        with _addr_lock:
            peer_name = _addr_names.get(tuple(addr))
        conn = Connection(sock, self, is_client=True, peer_name=peer_name)
        with self._lock:
            self._conns.add(conn)
        return conn

    def _conn_closed(self, conn: Connection) -> None:
        with self._lock:
            self._conns.discard(conn)
            self._fold(conn)

    def _fold(self, conn: Connection) -> None:
        """Keep a closing link's hand-overs (once; under ``_lock``)."""
        if conn._ho_folded:
            return
        conn._ho_folded = True
        self._ho_closed = _sum4([self._ho_closed, conn.hand_overs()])

    def hand_overs(self) -> list:
        """``[calls, slow, call seconds, wait seconds]``: what the
        native frame calls kept of the interpreter lock's hand-overs
        on this messenger's links, the live ones' structs plus the
        closed ones' totals."""
        with self._lock:
            return _sum4(
                [self._ho_closed, *(c.hand_overs() for c in self._conns)]
            )

    def shutdown(self) -> None:
        self._stopping = True
        if self.addr is not None:
            shm_ring.unregister(self.addr, self)
            with _addr_lock:
                if _addr_names.get(self.addr) == self.name:
                    del _addr_names[self.addr]
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=1.0)
        with self._lock:
            conns = list(self._conns)
            for conn in conns:
                self._fold(conn)
            self._conns.clear()
        for conn in conns:
            conn.close()
