"""Native frame codec vs the pure-Python wire path (ISSUE 20
tentpole (a) + satellites 1/3): the C encoder/verifier must be a
bit-identical drop-in — same bytes out, same BadFrame taxonomy on
corruption, same CRCs as every other checksum backend — with the
Python path preserved as the oracle behind ``msgr_native_codec``.
"""

import struct
import zlib

import pytest

from ceph_tpu import native
from ceph_tpu.msg.wire import (
    BadFrame,
    CRC_SEED,
    MAX_SEGMENTS,
    decode_frame,
    encode_frame,
    frame_from_buffer,
)
from ceph_tpu.utils.config import config

needs_native = pytest.mark.skipif(
    not native.available(), reason="native tier unavailable"
)


def _py_frame(msg_type, seq, segments, **kw):
    with config.override(msgr_native_codec=False):
        return encode_frame(msg_type, seq, segments, **kw)


def _native_frame(msg_type, seq, segments, **kw):
    with config.override(msgr_native_codec=True):
        return encode_frame(msg_type, seq, segments, **kw)


def _py_decode(buf):
    with config.override(msgr_native_codec=False):
        return frame_from_buffer(buf)


def _native_decode(buf):
    with config.override(msgr_native_codec=True):
        return frame_from_buffer(buf)


CASES = [
    [b"x"],
    [b""],
    [b"payload" * 500],
    [b"a", b"", b"bb", b"ccc"],
    [bytes(range(256)) * 16] * MAX_SEGMENTS,
    [b"\x00" * 4096, b"\xff" * 333],
]


# ---------------------------------------------------------------------------
# encode parity: the native assembler is bit-identical to the oracle
# ---------------------------------------------------------------------------
@needs_native
class TestEncodeParity:
    @pytest.mark.parametrize("segs", CASES)
    def test_bit_identical_clear(self, segs):
        assert _native_frame(9, 77, segs) == _py_frame(9, 77, segs)

    @pytest.mark.parametrize("segs", CASES)
    def test_bit_identical_compressed(self, segs):
        a = _native_frame(9, 77, segs, compress=True)
        b = _py_frame(9, 77, segs, compress=True)
        assert a == b

    def test_header_fields_survive(self):
        for msg_type, seq in [(0, 0), (65535, 2**63), (112, 1)]:
            t, s, segs = _py_decode(_native_frame(msg_type, seq, [b"p"]))
            assert (t, s, segs) == (msg_type, seq, [b"p"])


# ---------------------------------------------------------------------------
# decode parity: either path decodes either path's frames ("legacy
# frames" = python-encoded bytes through the native verifier and
# vice versa), compression transparent, roundtrip closed
# ---------------------------------------------------------------------------
@needs_native
class TestDecodeParity:
    @pytest.mark.parametrize("segs", CASES)
    def test_cross_decode(self, segs):
        py = _py_frame(5, 3, segs)
        nat = _native_frame(5, 3, segs)
        assert _native_decode(py) == (5, 3, segs)
        assert _py_decode(nat) == (5, 3, segs)

    def test_compressed_roundtrip_both_paths(self):
        segs = [b"Z" * 20_000, b"tail"]
        buf = _native_frame(5, 3, segs, compress=True)
        assert _py_decode(buf) == (5, 3, segs)
        assert _native_decode(buf) == (5, 3, segs)

    def test_streaming_decode_native(self):
        """decode_frame's read_exact streaming entry, native armed:
        the single table read + single payload read reassemble."""
        segs = [b"a" * 100, b"b" * 17]
        buf = _native_frame(5, 9, segs)
        pos = [0]

        def read_exact(n):
            out = buf[pos[0] : pos[0] + n]
            if len(out) != n:
                raise EOFError
            pos[0] += n
            return out

        with config.override(msgr_native_codec=True):
            assert decode_frame(read_exact) == (5, 9, segs)
        assert pos[0] == len(buf)


# ---------------------------------------------------------------------------
# corruption taxonomy: truncation and bit flips raise the same
# BadFrame family through both verifiers
# ---------------------------------------------------------------------------
@needs_native
class TestCorruption:
    def test_payload_bitflip_both_paths(self):
        buf = bytearray(_py_frame(7, 1, [b"seg-one" * 50, b"seg-two" * 50]))
        buf[-3] ^= 0x40
        for dec in (_py_decode, _native_decode):
            with pytest.raises(BadFrame, match="crc"):
                dec(bytes(buf))

    def test_table_crc_bitflip(self):
        buf = bytearray(_py_frame(7, 1, [b"payload" * 100]))
        buf[16 + 4] ^= 0x01  # first table entry's crc field
        for dec in (_py_decode, _native_decode):
            with pytest.raises(BadFrame, match="crc"):
                dec(bytes(buf))

    def test_native_reports_bad_segment_index(self):
        segs = [b"a" * 64, b"b" * 64, b"c" * 64]
        buf = bytearray(_native_frame(7, 1, segs))
        buf[-1] ^= 0x80  # last byte = inside segment 2
        with pytest.raises(BadFrame, match="segment 2"):
            _native_decode(bytes(buf))

    def test_truncated_frame(self):
        buf = _native_frame(7, 1, [b"payload" * 100])
        for cut in (4, 15, 20, len(buf) - 1):
            for dec in (_py_decode, _native_decode):
                with pytest.raises((BadFrame, EOFError)):
                    dec(buf[:cut])

    def test_bad_magic_checked_before_codec(self):
        buf = bytearray(_native_frame(7, 1, [b"x"]))
        buf[0] ^= 0xFF
        for dec in (_py_decode, _native_decode):
            with pytest.raises(BadFrame, match="magic"):
                dec(bytes(buf))

    def test_compressed_corruption_caught_by_crc_first(self):
        """Corrupt compressed bytes die at the CRC gate, never inside
        the decompressor — on both paths."""
        buf = bytearray(_native_frame(7, 1, [b"Q" * 30_000], compress=True))
        buf[30] ^= 0x10
        for dec in (_py_decode, _native_decode):
            with pytest.raises(BadFrame, match="crc"):
                dec(bytes(buf))


# ---------------------------------------------------------------------------
# secure mode: the AEAD path bypasses the codec entirely (GCM tag
# replaces per-segment CRC) — the codec gate must not disturb it
# ---------------------------------------------------------------------------
class TestSecureMode:
    def test_secure_frames_identical_with_codec_armed(self):
        pytest.importorskip(
            "cryptography.hazmat.primitives.ciphers.aead",
            reason="secure mode needs the cryptography package",
        )
        from ceph_tpu.msg.secure import KEY_BYTES, SALT_BYTES, SecureSession

        key, salt = b"k" * KEY_BYTES, b"s" * SALT_BYTES
        segs = [b"sealed-payload" * 10]
        tx_a = SecureSession(key, salt)
        tx_b = SecureSession(key, salt)
        with config.override(msgr_native_codec=True):
            sealed_a = encode_frame(3, 8, segs, secure=tx_a)
        with config.override(msgr_native_codec=False):
            sealed_b = encode_frame(3, 8, segs, secure=tx_b)
        assert sealed_a == sealed_b
        rx = SecureSession(key, salt)
        with config.override(msgr_native_codec=True):
            assert frame_from_buffer(sealed_a, secure=rx) == (3, 8, segs)

    def test_clear_frame_on_secure_session_still_rejected(self):
        buf = _py_frame(3, 8, [b"x"])
        with pytest.raises(BadFrame, match="secure-mode mismatch"):
            frame_from_buffer(buf, secure=object())


# ---------------------------------------------------------------------------
# satellite 1: CRC oracle across every checksum backend — the wire
# CRC must be byte-identical no matter which implementation serves it
# ---------------------------------------------------------------------------
class TestCrcOracle:
    VECTORS = [
        b"",
        b"a",
        b"123456789",
        bytes(range(256)),
        b"\x00" * 4096,
        b"payload" * 1000,
    ]

    def _backends(self):
        from ceph_tpu.checksum import crc32c_scalar, crc32c_wire
        from ceph_tpu.checksum.reference import crc32c_ref

        backends = {
            "wire": crc32c_wire,
            "scalar": crc32c_scalar,
            "ref": crc32c_ref,
        }
        if native.available():
            backends["native"] = native.crc32c
            backends["native_bytes"] = native.crc32c_bytes
        return backends

    @pytest.mark.parametrize("data", VECTORS)
    def test_all_backends_agree(self, data):
        got = {
            name: fn(CRC_SEED, data) & 0xFFFFFFFF
            for name, fn in self._backends().items()
        }
        assert len(set(got.values())) == 1, got

    def test_wire_crc_matches_frame_table(self):
        """The CRC the frame table carries IS crc32c_wire(seed, seg) —
        pinned so a backend swap can never silently reframe."""
        from ceph_tpu.checksum import crc32c_wire

        seg = b"pinned-segment" * 9
        buf = _py_frame(7, 1, [seg])
        _len, crc = struct.unpack_from("<II", buf, 16)
        assert crc == crc32c_wire(CRC_SEED, seg) & 0xFFFFFFFF

    def test_seeded_not_plain_crc32(self):
        seg = b"123456789"
        from ceph_tpu.checksum import crc32c_wire

        assert crc32c_wire(CRC_SEED, seg) != zlib.crc32(seg)


# ---------------------------------------------------------------------------
# config gate: msgr_native_codec=false forces the oracle path even
# when the native tier is loaded
# ---------------------------------------------------------------------------
@needs_native
def test_codec_gate_respected(monkeypatch):
    from ceph_tpu.msg import wire

    calls = []
    real = native.frame_encode

    def spy(*a, **kw):
        calls.append(a)
        return real(*a, **kw)

    monkeypatch.setattr(wire._native(), "frame_encode", spy, raising=False)
    with config.override(msgr_native_codec=False):
        encode_frame(7, 1, [b"x"])
    assert not calls
    with config.override(msgr_native_codec=True):
        encode_frame(7, 1, [b"x"])
    assert calls


# ---------------------------------------------------------------------------
# the codec takes the socket (PR 31): wire.send_frame writes the bytes
# encode_frame builds, wire.recv_frame hands over what decode_frame
# does, and raises what it raises — on a real kernel descriptor
# ---------------------------------------------------------------------------
import socket
import threading
import time

from ceph_tpu.msg import messages as M
from ceph_tpu.msg import wire
from ceph_tpu.store import Transaction

SCRATCH = native.FRAME_SCRATCH_BYTES if native.available() else 64 * 1024


def _txn(nbytes=64):
    return Transaction().write("obj", 0, bytes(range(256)) * (nbytes // 256 + 1))


def _sample_messages():
    """One instance of every message class the messenger carries."""
    return [
        M.ECSubWrite(5, 2, _txn(), "t" * 8, "s" * 8, 7, 3),
        M.ECSubWriteReply(5, 2, committed=False),
        M.ECSubRead(6, 1, "o", [(0, 4096), (8192, 12288)], [(0, 4)]),
        M.ECSubReadReply(6, 1, [0, 8192], [b"a" * 10, b"b" * 20]),
        M.Ping(1, 0),
        M.Pong(1, 9),
        M.OSDOp(9, 4, "pool", "oid", "write", 0, 300, b"\x07" * 300,
                reqid="c.1:9", tenant="gold"),
        M.OSDOpReply(9, 4, "", 300, b"\x08" * 300),
        M.PGList(2, 1, 3, 8, 5),
        M.PGListReply(2, 1, [("a", 1, 2), ("b", 3, 4)]),
        M.GetAttrs(3, 1, "o", ["hinfo", "oi"]),
        M.GetAttrsReply(3, 1, {"hinfo": b"\x01\x02", "oi": None}),
        M.WatchNotify(4, "cookie", "pool", "o", b"payload"),
        M.NotifyAck(4, "cookie"),
        M.PGInfo(5, 1, 3, 8, 5, 11),
        M.PGInfoReply(5, 1, 10, 11, 12),
        M.PGActivate(6, 1, 3, 5, 11),
        M.PGActivateAck(6, 1),
        M.BackfillReserve(7, 1, "request", 3, 5, 2),
        M.BackfillReserveReply(7, 1, granted=False),
        M.ECSubWriteBatch(8, 1, [(81, 0, 11, 2, _txn()), (82, 1, 11, 2, _txn(512))]),
        M.ECSubWriteBatchReply(8, 1, [(81, True), (82, False)]),
    ]


def _pair():
    a, b = socket.socketpair()
    return a, b


def _tcp_pair(sndbuf=None, rcvbuf=None):
    lst = socket.socket()
    if rcvbuf:  # inherited by the accepted socket; set before listen
        lst.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
    lst.bind(("127.0.0.1", 0))
    lst.listen(1)
    a = socket.socket()
    if sndbuf:
        a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, sndbuf)
    a.connect(lst.getsockname())
    b, _ = lst.accept()
    lst.close()
    return a, b


def _drain(sock, n):
    out = bytearray()
    while len(out) < n:
        chunk = sock.recv(n - len(out))
        if not chunk:
            break
        out += chunk
    return bytes(out)


def _native_send_bytes(msg_type, seq, segments):
    """What wire.send_frame puts on a socket."""
    a, b = _pair()
    try:
        want = 16 + 8 * len(segments) + sum(len(s) for s in segments)
        got = []
        t = threading.Thread(target=lambda: got.append(_drain(b, want)))
        t.start()
        n = wire.send_frame(native, a.fileno(), msg_type, seq, segments)
        a.shutdown(socket.SHUT_WR)
        t.join(10)
        assert n == want
        return got[0]
    finally:
        a.close()
        b.close()


def _native_recv(buf, writer=None):
    """wire.recv_frame over a socket fed ``buf`` (all at once, then
    EOF, unless ``writer(sock, buf)`` feeds it its own way): what it
    returns, then the frame's length, the header's clock and the
    native calls made."""
    a, b = _pair()
    try:
        def feed():
            try:
                (writer or (lambda s, d: s.sendall(d)))(a, buf)
                a.shutdown(socket.SHUT_WR)
            except OSError:
                pass

        t = threading.Thread(target=feed, daemon=True)
        t.start()
        rx = native.FrameReceiver()
        try:
            out = wire.recv_frame(native, b.fileno(), rx)
            return (*out, rx.frame_bytes, rx.info.t_header, rx.calls)
        finally:
            b.close()
            t.join(10)
    finally:
        a.close()


def _sized(total):
    """Two segments, ``total`` payload bytes in all."""
    head = b'{"v": 1}'
    return [head, (bytes(range(251)) * (total // 251 + 1))[: total - len(head)]]


SIZES = {
    "small": 300,
    "one-under-scratch": SCRATCH - 1,
    "exactly-scratch": SCRATCH,
    "one-over-scratch": SCRATCH + 1,
    "512k": 512 * 1024 + 120,
    "4m": 4 * 1024 * 1024 + 200,
}


@needs_native
class TestNativeSend:
    @pytest.mark.parametrize(
        "msg", _sample_messages(), ids=lambda m: type(m).__name__
    )
    def test_bytes_on_the_wire_are_encode_frames(self, msg):
        assert len(_sample_messages()) == len(M._TYPE_OF)
        segs = msg.encode()
        got = _native_send_bytes(M.message_type(msg), 77, segs)
        assert got == _py_frame(M.message_type(msg), 77, segs)
        assert got == _native_frame(M.message_type(msg), 77, segs)

    @pytest.mark.parametrize("segs", CASES)
    def test_bytes_equal_for_the_codec_cases(self, segs):
        assert _native_send_bytes(9, 2**63, segs) == _py_frame(9, 2**63, segs)

    def test_the_frozen_golden_frame(self):
        golden = bytes.fromhex(
            "43547632070000022a000000000000000a0000008aef3e8d0d000000"
            "c623f6106865616465722d6973687061796c6f61642d6279746573"
        )
        assert _native_send_bytes(
            7, 42, [b"header-ish", b"payload-bytes"]
        ) == golden

    @pytest.mark.parametrize("nseg", [0, MAX_SEGMENTS + 1])
    def test_segment_count_is_checked_as_encode_frame_checks_it(self, nseg):
        a, b = _pair()
        try:
            with pytest.raises(ValueError, match="segments"):
                wire.send_frame(native, a.fileno(), 7, 1, [b"x"] * nseg)
        finally:
            a.close()
            b.close()

    def test_a_closed_peer_is_an_oserror_not_a_signal(self):
        a, b = _pair()
        b.close()
        try:
            with pytest.raises(OSError):
                for _ in range(4):
                    wire.send_frame(native, a.fileno(), 7, 1, [b"x" * 70000])
        finally:
            a.close()

    def test_a_full_send_buffer_and_a_slow_reader(self):
        """The gather write resumes where a short write stopped."""
        a, b = _tcp_pair(sndbuf=4096, rcvbuf=4096)
        segs = _sized(1 << 20) + [b"", b"tail" * 1000]
        want = _py_frame(7, 3, segs)
        got = []

        def slow():
            time.sleep(0.2)
            out = bytearray()
            while len(out) < len(want):
                chunk = b.recv(3001)
                if not chunk:
                    break
                out += chunk
            got.append(bytes(out))

        t = threading.Thread(target=slow)
        t.start()
        try:
            n = wire.send_frame(native, a.fileno(), 7, 3, segs)
            t.join(30)
            assert n == len(want) and got[0] == want
        finally:
            a.close()
            b.close()

    def test_a_socket_with_a_timeout_is_waited_on(self):
        """A Python socket with a timeout is non-blocking underneath."""
        a, b = _tcp_pair(sndbuf=4096, rcvbuf=4096)
        a.settimeout(5)
        b.settimeout(5)
        segs = _sized(256 * 1024)
        got = []
        rx = native.FrameReceiver()
        t = threading.Thread(
            target=lambda: got.append(wire.recv_frame(native, b.fileno(), rx))
        )
        t.start()
        try:
            time.sleep(0.05)
            wire.send_frame(native, a.fileno(), 7, 3, segs)
            t.join(30)
            assert got[0] == (7, 3, segs)
        finally:
            a.close()
            b.close()


@needs_native
class TestNativeRecv:
    @pytest.mark.parametrize("segs", CASES)
    def test_hands_over_what_decode_frame_does(self, segs):
        buf = _py_frame(5, 3, segs)
        msg_type, seq, got, nbytes, t_hdr, calls = _native_recv(buf)
        assert (msg_type, seq, got) == _py_decode(buf)
        assert all(type(s) is bytes for s in got)
        assert nbytes == len(buf) and calls == 1
        assert 0 <= time.perf_counter() - t_hdr < 5

    @pytest.mark.parametrize("size", SIZES)
    def test_small_large_and_at_the_scratch_size(self, size):
        segs = _sized(SIZES[size])
        assert sum(map(len, segs)) == SIZES[size]
        buf = _py_frame(114, 2**40, segs)
        msg_type, seq, got, nbytes, _t, calls = _native_recv(buf)
        assert (msg_type, seq, got) == (114, 2**40, segs)
        assert all(type(s) is bytes for s in got)
        assert nbytes == len(buf)
        assert calls == (1 if SIZES[size] <= SCRATCH else 2)

    @pytest.mark.parametrize(
        "msg", _sample_messages(), ids=lambda m: type(m).__name__
    )
    def test_every_message_class_decodes_from_it(self, msg):
        buf = _py_frame(M.message_type(msg), 4, msg.encode())
        msg_type, _seq, segs, *_ = _native_recv(buf)
        back = M.decode_message(msg_type, segs)
        assert type(back) is type(msg)
        assert back.encode() == msg.encode()

    def test_several_large_segments_each_get_their_own_bytes(self):
        segs = [b"h" * 10, b"a" * 70000, b"", b"b" * 5000, b"c" * 100]
        buf = _py_frame(5, 1, segs)
        assert _native_recv(buf)[:3] == (5, 1, segs)

    @pytest.mark.parametrize("size", ["small", "512k"])
    def test_a_writer_of_one_byte_at_a_time(self, size):
        segs = _sized(SIZES[size])
        buf = _py_frame(5, 3, segs)

        def drip(sock, data):
            # every byte of header and table alone, the payload's first
            # and last hundred too; the middle in uneven pieces
            edge = 16 + 8 * len(segs) + 100
            for i in range(edge):
                sock.sendall(data[i : i + 1])
            pos = edge
            while pos < len(data) - 100:
                step = min(997, len(data) - 100 - pos)
                sock.sendall(data[pos : pos + step])
                pos += step
            for i in range(pos, len(data)):
                sock.sendall(data[i : i + 1])

        assert _native_recv(buf, writer=drip)[:3] == (5, 3, segs)

    def test_tiny_socket_buffers(self):
        a, b = _tcp_pair(sndbuf=2048, rcvbuf=2048)
        segs = _sized(1 << 20)
        buf = _py_frame(5, 3, segs)
        t = threading.Thread(target=lambda: a.sendall(buf))
        t.start()
        try:
            rx = native.FrameReceiver()
            got = wire.recv_frame(native, b.fileno(), rx)
            assert got == (5, 3, segs) and rx.calls == 2
            t.join(10)
        finally:
            a.close()
            b.close()

    def test_two_frames_back_to_back_stay_apart(self):
        one = _py_frame(5, 1, _sized(SIZES["small"]))
        two = _py_frame(6, 2, _sized(SIZES["512k"]))
        three = _py_frame(7, 3, [b"ack"])
        a, b = _pair()
        t = threading.Thread(target=lambda: a.sendall(one + two + three))
        t.start()
        try:
            rx = native.FrameReceiver()
            got = [wire.recv_frame(native, b.fileno(), rx) for _ in range(3)]
            assert [g[:2] for g in got] == [(5, 1), (6, 2), (7, 3)]
            assert got[1][2] == _sized(SIZES["512k"])
            assert got[2][2] == [b"ack"]
            t.join(10)
        finally:
            a.close()
            b.close()

    @pytest.mark.parametrize("size", ["small", "512k"])
    @pytest.mark.parametrize("where", ["payload", "table-crc", "first-seg"])
    def test_a_flipped_bit_is_a_badframe(self, size, where):
        segs = _sized(SIZES[size])
        buf = bytearray(_py_frame(7, 1, segs))
        at = {"payload": len(buf) - 3, "table-crc": 16 + 8 + 5,
              "first-seg": 16 + 16 + 2}[where]
        buf[at] ^= 0x10
        with pytest.raises(BadFrame, match="crc") as native_err:
            _native_recv(bytes(buf))
        with pytest.raises(BadFrame, match="crc"):
            _native_decode(bytes(buf))
        bad = 0 if where == "first-seg" else 1
        assert f"segment {bad}" in str(native_err.value)

    def test_a_flipped_length_bit_never_decodes(self):
        buf = bytearray(_py_frame(7, 1, [b"a" * 100, b"b" * 100]))
        buf[16] ^= 0x01  # first table entry's length: 100 -> 101
        with pytest.raises((BadFrame, EOFError)):
            _native_recv(bytes(buf))

    @pytest.mark.parametrize("field,match", [
        ("magic", "bad magic"), ("flags", "unsupported flags 0x84"),
        ("nseg0", "bad segment count 0"), ("nseg9", "bad segment count 9"),
        ("secure", "secure-mode mismatch: frame sealed but session clear"),
        ("length", "segment too large"),
    ])
    def test_header_faults_read_as_the_python_path_reads_them(
        self, field, match
    ):
        buf = bytearray(_py_frame(7, 1, [b"x" * 10]))
        if field == "magic":
            buf[0] ^= 0xFF
        elif field == "flags":
            buf[6] = 0x84
        elif field == "nseg0":
            buf[7] = 0
        elif field == "nseg9":
            buf[7] = 9
        elif field == "secure":
            buf[6] = 0x02
        else:
            buf[16:20] = struct.pack("<I", (1 << 30) + 1)
        with pytest.raises(BadFrame, match=match) as native_err:
            _native_recv(bytes(buf))
        if field != "secure":  # the oracle words its own text the same
            with pytest.raises(BadFrame) as py_err:
                _py_decode(bytes(buf))
            assert str(py_err.value) == str(native_err.value)

    @pytest.mark.parametrize("size", ["small", "512k"])
    @pytest.mark.parametrize(
        "cut", ["nothing", "mid-header", "mid-table", "mid-payload",
                "last-byte"]
    )
    def test_eof_inside_a_frame_is_eoferror(self, size, cut):
        buf = _py_frame(7, 1, _sized(SIZES[size]))
        n = {"nothing": 0, "mid-header": 9, "mid-table": 16 + 11,
             "mid-payload": 16 + 16 + 150, "last-byte": len(buf) - 1}[cut]
        with pytest.raises(EOFError):
            _native_recv(buf[:n])

    def test_a_reset_link_is_oserror_or_eof(self):
        a, b = _tcp_pair()
        buf = _py_frame(7, 1, _sized(SIZES["512k"]))
        a.sendall(buf[:40])
        # linger 0: close sends RST
        a.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                     struct.pack("ii", 1, 0))
        a.close()
        try:
            with pytest.raises((OSError, EOFError)):
                wire.recv_frame(native, b.fileno(), native.FrameReceiver())
        finally:
            b.close()

    def test_a_compressed_frame_is_inflated_after_its_crc(self):
        segs = [b"Z" * 20_000, b"tail"]
        buf = _py_frame(5, 3, segs, compress=True)
        assert _native_recv(buf)[:3] == (5, 3, segs)
        bad = bytearray(buf)
        bad[40] ^= 0x10
        with pytest.raises(BadFrame, match="crc"):
            _native_recv(bytes(bad))

    def test_the_header_clock_is_perf_counters_and_starts_at_the_header(self):
        """An idle link is not receive work: the reading is taken when
        the header is complete, not when the call began."""
        a, b = _pair()
        got = []
        rx = native.FrameReceiver()
        t = threading.Thread(
            target=lambda: got.append(wire.recv_frame(native, b.fileno(), rx))
        )
        t0 = time.perf_counter()
        t.start()
        time.sleep(0.3)
        t1 = time.perf_counter()
        a.sendall(_py_frame(7, 1, [b"x"]))
        t.join(10)
        t2 = time.perf_counter()
        a.close()
        b.close()
        assert got == [(7, 1, [b"x"])]
        assert t0 < t1 <= rx.info.t_header <= t2
