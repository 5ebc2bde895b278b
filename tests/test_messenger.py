"""Wire protocol + messenger + networked shard backend.

Contracts mirrored from the reference: ProtocolV2-style framing with
per-segment crc32c catching any on-wire corruption (msg/async/
frames_v2), versioned typed sub-op messages (MOSDECSubOp*), and the
standalone-cluster tier: real shard daemons on localhost sockets
serving the unchanged RMW/read/recovery pipelines
(qa/standalone/erasure-code boots exactly this topology).
"""

import time

import numpy as np
import pytest

from ceph_tpu.codecs import registry
from ceph_tpu.msg import (
    BadFrame,
    ECSubRead,
    ECSubReadReply,
    ECSubWrite,
    ECSubWriteReply,
    NetShardBackend,
    ShardServer,
    decode_message,
    encode_frame,
)
from ceph_tpu.msg.messages import message_type
from ceph_tpu.msg.wire import frame_from_buffer
from ceph_tpu.pipeline.inject import ec_inject
from ceph_tpu.pipeline.read import ReadPipeline
from ceph_tpu.pipeline.recovery import RecoveryBackend
from ceph_tpu.pipeline.rmw import RMWPipeline
from ceph_tpu.pipeline.stripe import PAGE_SIZE, StripeInfo
from ceph_tpu.store import MemStore, Transaction

K, M = 4, 2
CHUNK = PAGE_SIZE


@pytest.fixture(autouse=True)
def clean_inject():
    ec_inject.clear_all()
    yield
    ec_inject.clear_all()


class TestWire:
    def test_round_trip(self):
        segs = [b"header-ish", b"x" * 10000, b""]
        buf = encode_frame(7, 42, segs)
        msg_type, seq, out = frame_from_buffer(buf)
        assert (msg_type, seq, out) == (7, 42, segs)

    def test_corruption_detected(self):
        buf = bytearray(encode_frame(7, 1, [b"payload-bytes" * 100]))
        buf[-5] ^= 0x01  # flip one payload bit
        with pytest.raises(BadFrame, match="crc"):
            frame_from_buffer(bytes(buf))

    def test_bad_magic(self):
        buf = bytearray(encode_frame(7, 1, [b"x"]))
        buf[0] ^= 0xFF
        with pytest.raises(BadFrame, match="magic"):
            frame_from_buffer(bytes(buf))


class TestTransactionCodec:
    def test_round_trip(self):
        txn = (
            Transaction()
            .touch("o")
            .write("o", 4096, b"\x00\x01\x02" * 100)
            .zero("o", 0, 512)
            .truncate("o", 9999)
            .setattr("o", "hinfo_key", b"{}")
            .rmattr("o", "junk")
            .remove("gone")
        )
        back = Transaction.from_bytes(txn.to_bytes())
        assert [
            (op.kind, op.oid, op.offset, op.length, op.data, op.name)
            for op in back.ops
        ] == [
            (op.kind, op.oid, op.offset, op.length, op.data, op.name)
            for op in txn.ops
        ]


class TestMessages:
    def test_all_types_round_trip(self):
        msgs = [
            ECSubWrite(5, 2, Transaction().write("o", 0, b"abc")),
            ECSubWriteReply(5, 2, committed=True),
            ECSubRead(6, 1, "o", [(0, 4096), (8192, 12288)], [(0, 4)]),
            ECSubReadReply(6, 1, [0, 8192], [b"a" * 10, b"b" * 20]),
            ECSubReadReply(7, 3, error="eio"),
        ]
        for msg in msgs:
            buf = encode_frame(message_type(msg), 1, msg.encode())
            msg_type, _seq, segs = frame_from_buffer(buf)
            back = decode_message(msg_type, segs)
            assert type(back) is type(msg)
            if isinstance(msg, ECSubWrite):
                assert back.txn.to_bytes() == msg.txn.to_bytes()
                assert (back.tid, back.shard) == (msg.tid, msg.shard)
            else:
                assert back == msg


def boot_cluster(n=K + M, timeout=3.0):
    servers = {s: ShardServer(s) for s in range(n)}
    addrs = {s: srv.start() for s, srv in servers.items()}
    backend = NetShardBackend(addrs, timeout=timeout)
    return servers, backend


class TestCompression:
    def test_compressed_round_trip(self):
        segs = [b"header", b"A" * 50_000]
        buf = encode_frame(7, 1, segs, compress=True)
        assert len(buf) < 1000  # deflate crushed the run
        assert frame_from_buffer(buf)[2] == segs

    def test_compressed_corruption_detected(self):
        buf = bytearray(encode_frame(7, 1, [b"B" * 10_000], compress=True))
        buf[-3] ^= 0x01
        with pytest.raises(BadFrame, match="crc"):
            frame_from_buffer(bytes(buf))

    def test_compressed_messenger_end_to_end(self, rng):
        """A compressing client against a plain server: receivers
        auto-detect per frame, so mixed peers interoperate."""
        server = ShardServer(0)
        addr = server.start()
        backend = NetShardBackend({0: addr}, timeout=3.0)
        backend.messenger.compress = True
        try:
            payload = bytes(1000) + rng.integers(0, 4, 5000, np.uint8).tobytes()
            acked = []
            backend.submit_shard_txn(
                0,
                Transaction().write("o", 0, payload),
                lambda: acked.append(True),
            )
            backend.drain_until(lambda: acked)
            from ceph_tpu.pipeline.extents import ExtentSet

            out = backend.read_shard(0, "o", ExtentSet([(0, len(payload))]))
            assert out[0] == payload
        finally:
            backend.shutdown()
            server.stop()


class TestHeartbeat:
    def test_detects_dead_daemon_without_io(self):
        servers, backend = boot_cluster(3, timeout=3.0)
        try:
            backend.start_heartbeat(period=0.05, grace=0.3)
            time.sleep(0.3)
            assert backend.down_shards == set()
            servers[1].stop()
            deadline = time.monotonic() + 5.0
            while 1 not in backend.down_shards:
                assert time.monotonic() < deadline, "heartbeat never fired"
                time.sleep(0.05)
            assert backend.avail_shards() == {0, 2}
        finally:
            backend.shutdown()
            for srv in servers.values():
                srv.stop()

    def test_set_addr_revives(self):
        servers, backend = boot_cluster(2, timeout=3.0)
        try:
            backend.start_heartbeat(period=0.05, grace=0.3)
            servers[0].stop()
            deadline = time.monotonic() + 5.0
            while 0 not in backend.down_shards:
                assert time.monotonic() < deadline
                time.sleep(0.05)
            replacement = ShardServer(0)
            backend.set_addr(0, replacement.start())
            time.sleep(0.4)  # heartbeats flow again; no re-down
            assert 0 not in backend.down_shards
            replacement.stop()
        finally:
            backend.shutdown()
            for srv in servers.values():
                srv.stop()


class TestShardServer:
    def test_write_then_read(self, rng):
        servers, backend = boot_cluster(1)
        try:
            payload = rng.integers(0, 256, 10000, np.uint8).tobytes()
            acked = []
            backend.submit_shard_txn(
                0,
                Transaction().write("o", 0, payload),
                lambda: acked.append(True),
            )
            backend.drain_until(lambda: acked)
            assert acked == [True]
            from ceph_tpu.pipeline.extents import ExtentSet

            out = backend.read_shard(0, "o", ExtentSet([(0, 10000)]))
            assert out[0] == payload
            # absent tail zero-pads, absent object reads as zeros
            out = backend.read_shard(0, "ghost", ExtentSet([(0, 16)]))
            assert out[0] == b"\0" * 16
        finally:
            backend.shutdown()
            for srv in servers.values():
                srv.stop()


class TestDistributedPipeline:
    def make(self, timeout=3.0):
        servers, backend = boot_cluster(timeout=timeout)
        sinfo = StripeInfo(K, M, K * CHUNK)
        codec = registry.factory(
            "jerasure",
            {"technique": "reed_sol_van", "k": str(K), "m": str(M)},
        )
        rmw = RMWPipeline(sinfo, codec, backend, perf_name="net_rmw")
        reads = ReadPipeline(
            sinfo, codec, backend, rmw.object_size, perf_name="net_read"
        )
        return servers, backend, sinfo, codec, rmw, reads

    def teardown_cluster(self, servers, backend):
        backend.shutdown()
        for srv in servers.values():
            srv.stop()

    @staticmethod
    def net_write(rmw, backend, oid, offset, data):
        """Submit + drain: sub-write acks arrive via the event loop."""
        done = []
        rmw.submit(oid, offset, data, lambda op: done.append(op.tid))
        backend.drain_until(lambda: done)
        return done

    def test_write_read_over_sockets(self, rng):
        servers, backend, sinfo, codec, rmw, reads = self.make()
        try:
            data = rng.integers(
                0, 256, 3 * K * CHUNK + 501, np.uint8
            ).tobytes()
            done = self.net_write(rmw, backend, "obj", 0, data)
            assert done == [1]  # all k+m sub-writes acked over the wire
            assert reads.read_sync("obj", 0, len(data)) == data
            # the shard stores really hold the data remotely
            assert servers[0].store.exists("obj")
        finally:
            self.teardown_cluster(servers, backend)

    def test_daemon_death_degraded_read_and_recovery(self, rng):
        servers, backend, sinfo, codec, rmw, reads = self.make()
        try:
            data = rng.integers(0, 256, 2 * K * CHUNK, np.uint8).tobytes()
            self.net_write(rmw, backend, "obj", 0, data)
            # Kill shard 1's daemon: first read discovers the failure,
            # marks it down, and reconstructs.
            old_store = servers[1].store
            servers[1].stop()
            assert reads.read_sync("obj", 0, len(data)) == data
            assert 1 in backend.down_shards

            # Replacement daemon on a new port; backfill over the wire.
            replacement = ShardServer(1, MemStore("osd.1.reborn"))
            backend.set_addr(1, replacement.start())
            rec = RecoveryBackend(
                sinfo, codec, backend, rmw.object_size, rmw.hinfo,
                perf_name="net_recovery",
            )
            rec.recover_object("obj", {1})
            assert replacement.store.read("obj") == old_store.read("obj")
            # And the recovered shard serves reads with another down.
            servers[0].stop()
            assert reads.read_sync("obj", 0, len(data)) == data
            replacement.stop()
        finally:
            self.teardown_cluster(servers, backend)

    def test_inject_eio_server_side(self, rng):
        servers, backend, sinfo, codec, rmw, reads = self.make()
        try:
            data = rng.integers(0, 256, K * CHUNK, np.uint8).tobytes()
            self.net_write(rmw, backend, "obj", 0, data)
            ec_inject.read_error("obj", 0, duration=1, shard=2)
            assert reads.read_sync("obj", 0, len(data)) == data
            assert reads.perf.get("retries") >= 1
        finally:
            self.teardown_cluster(servers, backend)


# ---------------------------------------------------------------------------
# the native frame codec takes the socket (PR 31): over real Messenger
# pairs — how often the interpreter is left for a frame (``io_calls``),
# which links take the path, and the hazards of a bare descriptor
# ---------------------------------------------------------------------------
import socket
import sys
import threading

from ceph_tpu import native
from ceph_tpu.msg import messages as msgs
from ceph_tpu.msg import shm_ring, wire
from ceph_tpu.msg.messenger import (
    LinkRule,
    Messenger,
    make_net_perf,
    net_faults,
)
from ceph_tpu.utils.config import config
from ceph_tpu.utils.perf_counters import perf_collection

needs_native = pytest.mark.skipif(
    not native.available(), reason="native tier unavailable"
)

PSK = b"cluster-keyring-secret"


class Link:
    """A client messenger dialled into a server messenger, each with
    its ``net`` counter set; the server keeps what it was sent."""

    def __init__(self, server="osd.31", client="cli.31", **msgr_kw):
        self.srv = Messenger(server, **msgr_kw)
        self.cli = Messenger(client, **msgr_kw)
        self.srv.net_pc = make_net_perf(f"{server}.net")
        self.cli.net_pc = make_net_perf(f"{client}.net")
        self.srv_got, self.cli_got = [], []
        self.srv.set_dispatcher(lambda c, m: self.srv_got.append(m))
        self.cli.set_dispatcher(lambda c, m: self.cli_got.append(m))
        self.addr = self.srv.bind()
        self.conn = self.cli.connect(self.addr)

    def wait(self, cond, timeout=10.0):
        deadline = time.monotonic() + timeout
        while not cond():
            assert time.monotonic() < deadline, "timed out"
            time.sleep(0.002)

    def calls(self):
        return (self.cli.net_pc.get("io_calls"),
                self.srv.net_pc.get("io_calls"))

    def close(self):
        self.cli.shutdown()
        self.srv.shutdown()
        for m in (self.cli, self.srv):
            perf_collection.deregister(m.net_pc.name)


@pytest.fixture
def link():
    made = []

    def make(**kw):
        made.append(Link(**kw))
        return made[-1]

    yield make
    for ln in made:
        ln.close()
    net_faults.clear()
    net_faults.reset_counters()


def _sub_write(nbytes):
    return msgs.ECSubWrite(5, 2, Transaction().write("o", 0, b"\x5a" * nbytes))


#: (message, calls to send it, calls to receive it) on the native path
COUNT_PINS = {
    "512k-sub-write": (lambda: _sub_write(512 * 1024), 1, 2),
    "sub-write-reply": (lambda: msgs.ECSubWriteReply(5, 2), 1, 1),
    "4m-osd-op": (
        lambda: msgs.OSDOp(1, 1, "p", "o", "writefull", 0, 4 << 20,
                        b"\xa5" * (4 << 20)), 1, 2),
    "8k-sub-write": (lambda: _sub_write(8 * 1024), 1, 1),
    "sub-read": (lambda: msgs.ECSubRead(6, 1, "o", [(0, 524288)]), 1, 1),
    "osd-op-reply": (lambda: msgs.OSDOpReply(1, 1), 1, 1),
    "ping": (lambda: msgs.Ping(1, 0), 1, 1),
}


@needs_native
class TestNativeFrameIO:
    @pytest.mark.parametrize("name", COUNT_PINS)
    def test_calls_a_frame_on_the_native_path(self, link, name):
        make, sends, recvs = COUNT_PINS[name]
        ln = link()
        msg = make()
        ln.conn.send(msg)
        ln.wait(lambda: ln.srv_got)
        ln.wait(lambda: ln.srv.net_pc.get("frames_recv") == 1)
        assert ln.srv_got[0].encode() == msg.encode()
        assert ln.calls() == (sends, recvs)
        frame = encode_frame(message_type(msg), 1, msg.encode())
        assert ln.cli.net_pc.get("bytes_sent") == len(frame)
        assert ln.srv.net_pc.get("bytes_recv") == len(frame)
        assert ln.cli.net_pc.get("frames_sent") == 1

    @pytest.mark.parametrize("name", COUNT_PINS)
    def test_calls_a_frame_with_the_codec_gate_off(self, link, name):
        """``msgr_native_codec`` off: the Python codec (a crc32c call a
        segment) and Python I/O (``sendall``; a ``recv`` each for
        header, table and every piece of the payload)."""
        make, _sends, _recvs = COUNT_PINS[name]
        with config.override(msgr_native_codec=False):
            ln = link()
            msg = make()
            nseg = len(msg.encode())
            ln.conn.send(msg)
            ln.wait(lambda: ln.srv.net_pc.get("frames_recv") == 1)
            sent, received = ln.calls()
        assert ln.srv_got[0].encode() == msg.encode()
        assert sent == nseg + 1
        assert received >= nseg + 3

    def test_the_gate_is_read_a_frame_so_one_link_can_change_path(self, link):
        ln = link()
        ln.conn.send(msgs.Ping(1, 0))
        ln.wait(lambda: ln.srv.net_pc.get("frames_recv") == 1)
        assert ln.calls() == (1, 1)
        with config.override(msgr_native_codec=False):
            # the reader asked before it blocked: this frame is still
            # read natively; the send is Python's (codec + sendall)
            ln.conn.send(msgs.Ping(2, 0))
            ln.wait(lambda: ln.srv.net_pc.get("frames_recv") == 2)
            ln.conn.send(msgs.Ping(3, 0))
            ln.wait(lambda: ln.srv.net_pc.get("frames_recv") == 3)
        assert ln.cli.net_pc.get("io_calls") == 1 + 2 + 2
        assert ln.srv.net_pc.get("io_calls") == 1 + 1 + 4
        ln.conn.send(msgs.Ping(4, 0))
        ln.wait(lambda: len(ln.srv_got) == 4)
        assert [m.tid for m in ln.srv_got] == [1, 2, 3, 4]

    @pytest.mark.parametrize("kind", ["secure", "compressed", "shm-ring"])
    def test_other_links_keep_the_python_path(self, link, kind, monkeypatch):
        if kind == "secure":
            pytest.importorskip(
                "cryptography.hazmat.primitives.ciphers.aead",
                reason="secure mode needs the cryptography package",
            )

        def never(*a, **kw):
            raise AssertionError("native frame I/O on a link it must skip")

        monkeypatch.setattr(wire, "send_frame", never)
        monkeypatch.setattr(wire, "recv_frame", never)
        kw = {"secure": {"secret": PSK}, "compressed": {"compress": True},
              "shm-ring": {}}[kind]
        with config.override(
            msgr_transport="shm_ring" if kind == "shm-ring" else "tcp"
        ):
            ln = link(**kw)
        assert isinstance(ln.conn.sock, shm_ring.RingSock) == (
            kind == "shm-ring"
        )
        ln.srv.set_dispatcher(
            lambda c, m: (ln.srv_got.append(m), c.send(msgs.Pong(m.tid, 9)))
        )
        ln.conn.send(_sub_write(100 * 1024))
        ln.wait(lambda: ln.cli_got)
        assert ln.srv_got[0].txn.to_bytes() == _sub_write(100 * 1024).txn.to_bytes()
        assert ln.cli_got[0] == msgs.Pong(5, 9)
        # a receive is three recv or more on every Python path
        for pc in (ln.cli.net_pc, ln.srv.net_pc):
            ln.wait(
                lambda: pc.get("frames_sent") + pc.get("frames_recv") == 2
            )
            assert pc.get("io_calls") >= 1 + 3

    @pytest.mark.parametrize("size", ["small", "large"])
    @pytest.mark.parametrize("where", ["payload", "table"])
    def test_a_bad_crc_drops_the_link_and_nothing_is_dispatched(
        self, link, size, where
    ):
        ln = link()
        msg = _sub_write(300 if size == "small" else 512 * 1024)
        good = encode_frame(message_type(msg), 1, msg.encode())
        bad = bytearray(good)
        bad[len(bad) - 7 if where == "payload" else 16 + 8 + 4] ^= 0x04
        raw = socket.create_connection(ln.addr, timeout=10)
        try:
            raw.sendall(good)
            ln.wait(lambda: len(ln.srv_got) == 1)
            try:
                raw.sendall(bytes(bad))
                raw.sendall(good)  # behind the bad frame: never read
                hung_up = raw.recv(1) == b""
            except (ConnectionResetError, BrokenPipeError):
                hung_up = True  # closed with the second frame unread
            assert hung_up
        finally:
            raw.close()
        time.sleep(0.05)
        assert len(ln.srv_got) == 1
        assert ln.srv.net_pc.get("frames_recv") == 1

    def test_close_from_another_thread_wakes_a_blocked_reader(self, link):
        ln = link()
        ln.conn.send(msgs.Ping(1, 0))
        ln.wait(lambda: ln.srv_got)
        conn = ln.conn
        time.sleep(0.05)  # the reader is back inside the native recv
        assert conn._reader.is_alive() and conn._fd_users == 1
        fd = conn.sock.fileno()
        assert fd >= 0
        closer = threading.Thread(target=conn.close)
        closer.start()
        closer.join(5)
        conn._reader.join(5)
        assert not conn._reader.is_alive() and not closer.is_alive()
        assert conn._fd_users == 0 and conn.sock.fileno() == -1
        assert not conn.alive
        with pytest.raises(ConnectionError):
            conn.send(msgs.Ping(2, 0))
        # the server end saw EOF and let go of its side too
        ln.wait(lambda: not ln.srv._conns)

    def test_the_descriptor_outlives_a_call_in_flight(self, link, monkeypatch):
        """``close()`` while a native call holds the descriptor leaves
        the close to that call's end, and lets no new one start: the
        number can not be handed to another socket under the call."""
        ln = link()
        conn = ln.conn
        entered, release = threading.Event(), threading.Event()
        real = wire.send_frame

        def held(io, fd, *a):
            entered.set()
            assert release.wait(10)
            # still this socket's: open, and the same number
            assert conn.sock.fileno() == fd
            return real(io, fd, *a)

        monkeypatch.setattr(wire, "send_frame", held)
        errors = []

        def send():
            try:
                conn.send(msgs.Ping(1, 0))
            except ConnectionError as e:
                errors.append(e)

        sender = threading.Thread(target=send)
        sender.start()
        assert entered.wait(5)
        fd = conn.sock.fileno()
        conn.close()
        assert conn.sock.fileno() == fd  # not closed under the call
        with pytest.raises(OSError):
            conn._fd_enter()  # and none may start
        # a socket opened now cannot be given that number
        other = socket.socket()
        assert other.fileno() != fd
        other.close()
        release.set()
        sender.join(5)
        conn._reader.join(5)
        assert conn.sock.fileno() == -1 and conn._fd_users == 0
        assert len(errors) == 1  # EPIPE on the shut-down socket

    def test_sixteen_senders_keep_frames_whole_and_seq_in_socket_order(
        self, link, monkeypatch
    ):
        seqs = []
        real = wire.recv_frame

        def spy(io, fd, rx):
            out = real(io, fd, rx)
            if out[0] == msgs.MSG_EC_SUB_WRITE:
                seqs.append(out[1])
            return out

        monkeypatch.setattr(wire, "recv_frame", spy)
        ln = link()
        # small send buffer: the large frames go out in many pieces
        ln.conn.sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 8192)
        per = 24

        def sender(t):
            for i in range(per):
                size = 96 * 1024 if i % 3 == 0 else 700
                ln.conn.send(
                    msgs.ECSubWrite(t * 1000 + i, t,
                                 Transaction().write("o", i, bytes([t]) * size))
                )

        threads = [threading.Thread(target=sender, args=(t,))
                   for t in range(16)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-4)  # more hand-overs, more interleavings
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert not any(t.is_alive() for t in threads)
            ln.wait(lambda: len(ln.srv_got) == 16 * per, timeout=60)
        finally:
            sys.setswitchinterval(interval)
        assert seqs == list(range(1, 16 * per + 1))
        assert sorted(m.tid for m in ln.srv_got) == sorted(
            t * 1000 + i for t in range(16) for i in range(per)
        )
        for m in ln.srv_got:  # whole: each frame's payload is its sender's
            op = m.txn.ops[0]
            assert op.data == bytes([m.shard]) * len(op.data)
            assert (m.tid // 1000, op.offset) == (m.shard, m.tid % 1000)
        # each sender's frames arrive in the order it sent them
        for t in range(16):
            mine = [m.tid for m in ln.srv_got if m.shard == t]
            assert mine == sorted(mine)
        assert ln.cli.net_pc.get("io_calls") == 16 * per

    @pytest.mark.parametrize("fault", ["drop", "dup", "delay"])
    def test_an_armed_fault_plane_still_acts_above_the_call(self, link, fault):
        ln = link()
        rule = {"drop": LinkRule(drop=1.0), "dup": LinkRule(dup=1.0),
                "delay": LinkRule(delay_ms=120)}[fault]
        net_faults.configure(7)
        net_faults.add_rule("cli.31", "osd.31", rule)
        t0 = time.monotonic()
        ln.conn.send(msgs.Ping(1, 0))
        if fault == "drop":
            time.sleep(0.2)
            assert ln.srv_got == []
            assert ln.cli.net_pc.get("frames_dropped") == 1
            assert ln.cli.net_pc.get("frames_sent") == 0
        elif fault == "dup":
            ln.wait(lambda: len(ln.srv_got) == 2)
            assert [m.tid for m in ln.srv_got] == [1, 1]
            assert ln.cli.net_pc.get("frames_duped") == 1
            assert ln.calls() == (2, 2)
        else:
            assert ln.srv_got == []
            ln.wait(lambda: ln.srv_got)
            assert time.monotonic() - t0 >= 0.1
            assert ln.cli.net_pc.get("frames_delayed") == 1
            assert ln.calls() == (1, 1)
        net_faults.clear()
        ln.conn.send(msgs.Ping(2, 0))
        ln.wait(lambda: ln.srv_got and ln.srv_got[-1].tid == 2)

    def test_an_inbound_fault_acts_after_the_native_receive(self, link):
        ln = link()
        ln.srv.set_dispatcher(lambda c, m: c.send(msgs.Pong(m.tid, 9)))
        net_faults.configure(1)
        net_faults.add_rule("osd.31", "cli.31", LinkRule(partition=True))
        ln.conn.send(msgs.Ping(1, 0))
        ln.wait(lambda: ln.cli.net_pc.get("frames_dropped") == 1)
        assert ln.cli_got == []
        # the reply was read (and counted) before the plane ate it
        assert ln.cli.net_pc.get("frames_recv") == 1
        assert ln.cli.net_pc.get("io_calls") == 2

    def test_an_idle_link_adds_no_receive_seconds(self, link):
        ln = link()
        time.sleep(0.4)
        ln.conn.send(msgs.Ping(1, 0))
        ln.wait(lambda: ln.srv.net_pc.get("frames_recv") == 1)
        assert 0 < ln.srv.net_pc.get("recv_seconds") < 0.2
        assert 0 < ln.cli.net_pc.get("send_seconds") < 0.2
