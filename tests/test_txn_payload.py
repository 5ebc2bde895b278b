"""A sub-write's bytes change hands (PR 42): a WRITE op's payload is an
immutable buffer from the encode's rows to the store's object.

``Transaction.write`` keeps what nobody can write to afterwards and
copies anything else once; on the wire a transaction is segments whose
concatenation is ``to_bytes()``'s stream (v1 / v2 and the frozen golden
payload unchanged), a payload of 4 KiB or more a segment by itself; the
parser hands out views of the received segments; the store copies a
payload once, into memory of its own.
"""

import socket
import threading

import numpy as np
import pytest

from ceph_tpu import native
from ceph_tpu.checksum.host import crc32c
from ceph_tpu.msg import messages as M
from ceph_tpu.msg import wire
from ceph_tpu.pipeline.rmw import RMWPipeline, ShardBackend
from ceph_tpu.pipeline.shard_map import ShardExtentMap
from ceph_tpu.pipeline.stripe import StripeInfo
from ceph_tpu.codecs.registry import registry
from ceph_tpu.store import BlockStore, FileStore, MemStore, OpKind, Transaction
from ceph_tpu.store import transaction as T
from ceph_tpu.utils import config
from ceph_tpu.utils.buffers import is_frozen

from test_format_freeze import TestTransactionCodec as _Frozen

SEG = T.PAYLOAD_SEGMENT_BYTES
ROOM = wire.MAX_SEGMENTS - 1


def _bytes(n: int, seed: int = 0) -> bytes:
    return np.random.default_rng([0x42, seed]).bytes(n)


def _view(n: int, seed: int = 0) -> np.ndarray:
    """A read-only run, as ``ShardExtentMap.get`` hands one out."""
    arr = np.frombuffer(_bytes(n + 64, seed), np.uint8)[32 : 32 + n]
    assert not arr.flags.writeable
    return arr


def _addr(buf) -> int:
    return np.frombuffer(buf, np.uint8).ctypes.data


def _inside(inner, outer) -> bool:
    """``inner``'s memory lies within ``outer``'s (no copy was made)."""
    if not len(inner):
        return True
    a, b = _addr(inner), _addr(outer)
    return b <= a and a + len(inner) <= b + len(outer)


# -- the transactions under test -------------------------------------------
def _one_write(n, csums=False):
    def make():
        kw = {}
        if csums:
            kw = {"csums": list(range(max(n // SEG, 1))), "csum_block": SEG}
        return (
            Transaction().touch("o").write("o", 0, _view(n), **kw)
            .setattr("o", "hinfo", b"h" * 40).setattr("o", "oi", b"i" * 12)
        )
    return make


def _many_writes(sizes, csums=False):
    def make():
        t = Transaction().touch("o")
        off = 0
        for i, n in enumerate(sizes):
            kw = {}
            if csums and n % SEG == 0 and n:
                kw = {"csums": [i] * (n // SEG), "csum_block": SEG}
            t.write("o", off, _view(n, i), **kw)
            off += n + 100
        return t.setattr("o", "a", b"v").truncate("o", off).zero("o", 3, 9)
    return make


TXNS = {
    "v1_under_4k": _one_write(SEG - 1),
    "v1_at_4k": _one_write(SEG),
    "v1_512k": _one_write(512 * 1024),
    "v2_under_4k": _one_write(1000, csums=True),
    "v2_512k": _one_write(512 * 1024, csums=True),
    "v1_64k_less_its_fields": _one_write(T.FRAME_SCRATCH_BYTES - 200),
    "v1_64k": _one_write(T.FRAME_SCRATCH_BYTES),
    "v1_two_extents": _many_writes([8192, 100, 65536]),
    "v2_two_extents": _many_writes([8192, 100, 65536], csums=True),
    "v1_small_payloads_fit_the_scratch": _many_writes([SEG] * 6 + [10]),
    "v1_more_payloads_than_segments": _many_writes([4 * SEG] * 6 + [10] + [2 * SEG]),
    "v2_more_payloads_than_segments": _many_writes([2 * SEG] * 9, csums=True),
    "no_write": lambda: Transaction().touch("o").setattr("o", "a", b"v" * 5000),
    "empty_payload": lambda: Transaction().write("o", 7, b""),
    "empty": Transaction,
}


def _alone(txn, room=ROOM):
    """WRITE payloads that ride as a segment of their own: none of a
    stream that fits the receiver's scratch buffer."""
    if len(txn.to_bytes()) <= T.FRAME_SCRATCH_BYTES:
        return 0
    big = sum(
        1 for op in txn.ops
        if op.kind is OpKind.WRITE and len(op.data) >= SEG
    )
    return min(big, (room - 1) // 2)


# -- (a) the segments ARE the stream -----------------------------------------
@pytest.mark.parametrize("name", TXNS)
def test_segments_concatenate_to_the_stream(name):
    txn = TXNS[name]()
    blob = txn.to_bytes()
    assert blob[0] == (2 if any(op.csums for op in txn.ops) else 1)
    segs, lens = T.pack_segments([txn], ROOM)
    assert len(segs) <= ROOM
    assert b"".join(segs) == blob and lens == [len(blob)]
    # a payload by itself is the sender's buffer, not a copy of it
    mine = [s for s in segs if isinstance(s, memoryview)]
    assert len(mine) == _alone(txn)
    writes = [op.data for op in txn.ops if op.kind is OpKind.WRITE]
    for seg in mine:
        assert seg.readonly and any(seg is w for w in writes)
    # and one segment gives the same bytes
    one, _ = T.pack_segments([txn], 1)
    assert b"".join(one) == blob and len(one) <= 1


@pytest.mark.parametrize("room", [1, 2, 3, 4, 5, 7])
def test_a_frame_with_less_room_inlines_what_does_not_fit(room):
    txn = _many_writes([8 * SEG, 16 * SEG, SEG], csums=True)()
    segs, _ = T.pack_segments([txn], room)
    assert len(segs) <= room and b"".join(segs) == txn.to_bytes()
    assert sum(isinstance(s, memoryview) for s in segs) == _alone(txn, room)


def test_the_senders_sizes_are_the_receivers():
    assert T.PAYLOAD_SEGMENT_BYTES == native._SEG_OWN_BYTES
    assert T.FRAME_SCRATCH_BYTES == native.FRAME_SCRATCH_BYTES


def test_the_golden_payload_is_unchanged():
    txn = (
        Transaction().write("obj", 64, b"bytes").setattr("obj", "a", b"v")
        .truncate("obj", 100).remove("gone")
    )
    assert txn.to_bytes() == _Frozen.GOLDEN_TXN
    assert T.pack_segments([txn], ROOM) == (
        [_Frozen.GOLDEN_TXN], [len(_Frozen.GOLDEN_TXN)]
    )
    assert Transaction.from_bytes(_Frozen.GOLDEN_TXN) == txn
    # the same stream cut anywhere parses to the same transaction
    for cut in (1, 5, 9, 40, len(_Frozen.GOLDEN_TXN) - 1):
        parts = [_Frozen.GOLDEN_TXN[:cut], b"", _Frozen.GOLDEN_TXN[cut:]]
        assert T.parse_segments(parts) == [txn]


@pytest.mark.parametrize("name", TXNS)
def test_a_stream_cut_anywhere_parses_the_same(name):
    txn = TXNS[name]()
    blob = txn.to_bytes()
    rng = np.random.default_rng(len(blob))
    for _ in range(4):
        cuts = sorted(rng.integers(0, len(blob) + 1, 3).tolist())
        parts = [blob[a:b] for a, b in zip([0] + cuts, cuts + [len(blob)])]
        assert T.parse_segments(parts) == [txn]
    assert Transaction.from_bytes(blob) == txn
    assert Transaction.from_bytes(bytearray(blob)) == txn
    assert Transaction.from_bytes(memoryview(blob)) == txn


@pytest.mark.parametrize("bad", ["truncated", "trailing", "announced"])
def test_a_bad_stream_is_refused(bad):
    a, b = _one_write(32 * SEG)(), _one_write(100)()
    segs, lens = T.pack_segments([a, b], ROOM)
    if bad == "truncated":
        segs[-1] = segs[-1][:-1]
    elif bad == "trailing":
        segs.append(b"\0")
    else:
        lens = [lens[0] - 1, lens[1] + 1]
    with pytest.raises(ValueError, match=bad):
        T.parse_segments(segs, lens)


# -- (b) through the messages and both frame paths ---------------------------
def _batch(n_items, size):
    return M.ECSubWriteBatch(8, 1, [
        (80 + i, i % 12, 11, 2, _one_write(size, csums=size >= SEG)())
        for i in range(n_items)
    ])


MESSAGES = {
    **{
        f"sub_write.{name}":
            (lambda make=make: M.ECSubWrite(5, 2, make(), "t" * 8, "s" * 8, 7, 3))
        for name, make in TXNS.items()
    },
    "batch_of_none": lambda: M.ECSubWriteBatch(8, 1, []),
    "batch_of_two_8k": lambda: _batch(2, 8192),
    "batch_of_nine_8k": lambda: _batch(9, 8192),
    "batch_of_five_small": lambda: _batch(5, 700),
    "batch_of_three_512k": lambda: _batch(3, 512 * 1024),
}


def _python_path(msg):
    frame = wire.encode_frame(M.message_type(msg), 1, msg.encode())
    return wire.frame_from_buffer(frame)


def _native_path(msg):
    a, b = socket.socketpair()
    try:
        segs = msg.encode()
        sender = threading.Thread(
            target=wire.send_frame,
            args=(native, a.fileno(), M.message_type(msg), 1, segs),
        )
        sender.start()
        out = wire.recv_frame(native, b.fileno(), native.FrameReceiver())
        sender.join()
        return out
    finally:
        a.close()
        b.close()


PATHS = {"python": _python_path, "native": _native_path}


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("name", MESSAGES)
def test_parse_of_serialise_is_the_message(name, path):
    if path == "native" and not native.available():
        pytest.skip("no native tier")
    msg = MESSAGES[name]()
    segs = msg.encode()
    assert len(segs) <= wire.MAX_SEGMENTS
    txns = [msg.txn] if isinstance(msg, M.ECSubWrite) else [
        item[-1] for item in msg.items
    ]
    assert b"".join(segs[1:]) == b"".join(t.to_bytes() for t in txns)
    msg_type, _seq, received = PATHS[path](msg)
    assert [bytes(s) for s in received] == [bytes(s) for s in segs]
    back = M.decode_message(msg_type, received)
    assert back == msg
    # no copy on the way in: a payload of 4 KiB or more lies inside a
    # segment the frame reader handed over, and nobody can write to it
    got = [back.txn] if isinstance(msg, M.ECSubWrite) else [
        item[-1] for item in back.items
    ]
    for txn in got:
        for op in txn.ops:
            if op.kind is OpKind.WRITE and len(op.data) >= SEG:
                assert is_frozen(op.data)
                assert any(_inside(op.data, s) for s in received[1:])
            else:
                assert type(op.data) is bytes


def test_the_native_frame_bytes_are_the_python_ones():
    if not native.available():
        pytest.skip("no native tier")
    msg = MESSAGES["sub_write.v2_two_extents"]()
    segs = msg.encode()
    assert any(isinstance(s, memoryview) for s in segs)
    with config.override(msgr_native_codec=False):
        oracle = wire.encode_frame(M.message_type(msg), 9, segs)
    assert native.frame_encode(M.message_type(msg), 0, 9, segs) == oracle
    assert wire.encode_frame(
        M.message_type(msg), 9, [bytes(s) for s in segs]
    ) == oracle


def test_a_batch_to_one_peer_is_one_stream():
    msg = _batch(9, 8192)
    segs = msg.encode()
    head = M._parse(segs[0], "sub_write_batch")
    blobs = [item[-1].to_bytes() for item in msg.items]
    assert head["lens"] == [len(b) for b in blobs]
    assert b"".join(segs[1:]) == b"".join(blobs)
    # three payloads have a segment of their own, six ride inline
    assert sum(isinstance(s, memoryview) for s in segs) == 3
    # the parent's wire form (one blob after the header) still parses
    assert M.ECSubWriteBatch.decode([segs[0], b"".join(blobs)]) == msg


# -- (c) who owns the bytes ----------------------------------------------------
MUTABLE = {
    "bytearray": lambda b: bytearray(b),
    "writable_array": lambda b: np.frombuffer(b, np.uint8).copy(),
    "writable_memoryview": lambda b: memoryview(bytearray(b)),
    "strided_array": lambda b: np.frombuffer(b + b, np.uint8)[::2],
}
FROZEN = {
    "bytes": lambda b: b,
    "readonly_memoryview": lambda b: memoryview(b),
    "readonly_array": lambda b: np.frombuffer(b, np.uint8),
    "readonly_2d_array": lambda b: np.frombuffer(b, np.uint8).reshape(4, -1),
}


@pytest.mark.parametrize("kind", MUTABLE)
def test_a_mutable_input_is_copied_once(kind):
    src = MUTABLE[kind](_bytes(8192))
    want = bytes(src)
    txn = Transaction().write("o", 0, src)
    (op,) = txn.ops
    assert type(op.data) is bytes and op.length == 8192
    if kind != "strided_array":
        src[:100] = b"\xff" * 100 if kind != "writable_array" else 255
    assert op.data == want
    assert ShardExtentMap._owned(src) is not src


@pytest.mark.parametrize("shape", [(0,), (0, 4), (3, 0)])
def test_an_empty_frozen_input_is_an_empty_payload(shape):
    arr = np.zeros(shape, np.uint8)
    arr.flags.writeable = False
    (op,) = Transaction().write("o", 9, arr).ops
    assert op.data == b"" and op.length == 0
    assert Transaction.from_bytes(Transaction(ops=[op]).to_bytes()).ops == [op]


@pytest.mark.parametrize("kind", FROZEN)
def test_a_frozen_input_changes_hands(kind):
    raw = _bytes(8192)
    src = FROZEN[kind](raw)
    assert is_frozen(src)
    (op,) = Transaction().write("o", 4, src).ops
    assert op.length == len(op.data) == 8192 and op.data == raw
    assert _addr(op.data) == _addr(src)  # kept, not copied
    if kind != "bytes":
        assert isinstance(op.data, memoryview) and op.data.readonly
        assert op.data.ndim == 1 and op.data.format == "B"
        with pytest.raises(TypeError):
            op.data[0] = 1
    # the same rule, not a second copy of it
    kept = ShardExtentMap._owned(src)
    assert _addr(kept) == _addr(src) and not kept.flags.writeable


def test_a_built_payload_lies_inside_the_maps_run():
    """``_build_transactions`` hands the run's view over: the payload a
    shard's transaction carries, the run ``op.written`` keeps and the
    extent cache's line are one memory, and the store's object is not."""
    k, m, cs = 4, 2, 4096
    sinfo = StripeInfo(k, m, k * cs)
    stores = {s: MemStore(f"osd.{s}") for s in range(k + m)}

    class Recording(ShardBackend):
        txns = []

        def submit_shard_txn(self, shard, txn, ack):
            self.txns.append((shard, txn))
            return super().submit_shard_txn(shard, txn, ack)

    codec = registry.factory(
        "jerasure", {"technique": "reed_sol_van", "k": str(k), "m": str(m)}
    )
    pipe = RMWPipeline(sinfo, codec, Recording(stores))
    image = _bytes(8 * k * cs)
    done = []
    pipe.submit("obj", 0, image, on_commit=done.append)
    (op,) = done
    assert pipe.perf.get("txn_copy_bytes") == 0
    assert len(Recording.txns) == k + m
    for shard, txn in Recording.txns:
        (write,) = [o for o in txn.ops if o.kind is OpKind.WRITE]
        ((off, run),) = op.written._bufs[shard]
        assert off == 0 and write.data.readonly
        assert _addr(write.data) == _addr(run) and len(write.data) == run.size
        stored = stores[shard]._objects["obj"].data
        assert bytes(stored) == bytes(write.data)
        assert not _inside(stored, run) and not _inside(run, stored)
    # overwrite every stored object: the sender's bytes do not move
    before = {s: bytes(op.written.get(s, 0, 8 * cs)) for s in range(k + m)}
    for s, st in stores.items():
        st.queue_transactions(Transaction().write("obj", 0, b"\xee" * 8 * cs))
    for s in range(k + m):
        assert bytes(op.written.get(s, 0, 8 * cs)) == before[s]
        assert bytes(pipe.cache._data["obj"].get(s, 0, 8 * cs)) == before[s]
    for raw in range(k):
        shard = sinfo.get_shard(raw)
        rows = np.frombuffer(image, np.uint8).reshape(8, k, cs)
        assert before[shard] == rows[:, raw].tobytes()


# -- (d) the store writes a payload once, into memory of its own ---------------
def _apply_model(model: bytearray, off: int, data: bytes) -> None:
    end = off + len(data)
    if len(model) < end:
        model.extend(b"\0" * (end - len(model)))
    model[off:end] = data


WRITES = {
    "new_object": [(0, 9000)],
    "append": [(0, 5000), (5000, 7000)],
    "past_the_end_with_a_gap": [(0, 100), (5000, 300)],
    "new_object_with_a_gap": [(4096, 4096)],
    "inside": [(0, 9000), (1000, 4096)],
    "inside_to_the_last_byte": [(0, 9000), (4904, 4096)],
    "across_the_end": [(0, 9000), (8000, 5000)],
    "truncate_then_write": [(0, 9000), ("truncate", 2000), (3000, 4096)],
    "truncate_up_then_write_inside": [(0, 100), ("truncate", 8192), (4096, 100)],
}


@pytest.fixture(params=["memstore", "filestore", "blockstore"])
def st(request, tmp_path):
    if request.param == "memstore":
        return MemStore()
    if request.param == "filestore":
        return FileStore(str(tmp_path / "fs"))
    return BlockStore(str(tmp_path / "bs"), size=1 << 22)


@pytest.mark.parametrize("one_txn", [False, True], ids=["txn_each", "one_txn"])
@pytest.mark.parametrize("name", WRITES)
def test_writes_against_a_plain_bytearray(st, name, one_txn):
    model = bytearray()
    txns = []
    for i, (off, n) in enumerate(WRITES[name]):
        if off == "truncate":
            txns.append(Transaction().truncate("o", n))
            if len(model) > n:
                del model[n:]
            else:
                model.extend(b"\0" * (n - len(model)))
            continue
        view = _view(n, i)
        txns.append(Transaction().write("o", off, view))
        _apply_model(model, off, bytes(view))
    if one_txn:
        whole = Transaction()
        for t in txns:
            whole.append(t)
        txns = [whole]
    for t in txns:
        st.queue_transactions(t)
    assert st.stat("o") == len(model)
    assert st.read("o") == bytes(model)


def test_random_writes_against_a_plain_bytearray():
    rng = np.random.default_rng(42)
    store, model = MemStore(), bytearray()
    store.perf = None
    for i in range(300):
        off = int(rng.integers(0, len(model) + 3000))
        n = int(rng.integers(0, 6000))
        view = _view(n, i)
        if rng.integers(0, 10) == 0:
            size = int(rng.integers(0, len(model) + 100))
            store.queue_transactions(Transaction().truncate("o", size))
            if len(model) > size:
                del model[size:]
            else:
                model.extend(b"\0" * (size - len(model)))
        store.queue_transactions(Transaction().write("o", off, view))
        _apply_model(model, off, bytes(view))
        assert store.read("o") == bytes(model)


def test_apply_counts_what_it_moved():
    from ceph_tpu.store.memstore import make_store_perf

    store = MemStore()
    store.perf = make_store_perf("test.txn_payload.store")

    def moved():
        return (store.perf.get("txn_bytes"), store.perf.get("apply_copy_bytes"))

    store.queue_transactions(Transaction().write("o", 0, _view(8192)))
    assert moved() == (8192, 8192)  # a new object: the payload, once
    store.queue_transactions(Transaction().write("o", 8192, _view(100)))
    assert moved() == (8292, 8292)  # an append
    store.queue_transactions(Transaction().write("o", 10, _view(50)))
    assert moved() == (8342, 8342)  # inside
    store.queue_transactions(Transaction().write("o", 9000, _view(10)))
    assert moved() == (8352, 8342 + 708 + 10)  # the gap's zeros count


@pytest.mark.parametrize("kind", ["fresh", "received"])
def test_the_store_adopts_nothing(st, kind):
    """After apply the object shares no memory with the payload: the
    payload's owner may drop or reuse it, and an overwrite of the
    object changes nothing the sender still reads."""
    raw = _bytes(16384)
    txn = Transaction().touch("o").write("o", 0, _view(16384))
    want = bytes(txn.ops[1].data)
    if kind == "received":
        segs, _ = T.pack_segments([txn], ROOM)
        received = [bytearray(s) for s in segs]  # a frame reader's buffers
        (txn,) = T.parse_segments(received)
        assert any(_inside(txn.ops[1].data, s) for s in received)
    st.queue_transactions(txn)
    payload = txn.ops[1].data
    if isinstance(st, MemStore):
        stored = st._objects["o"].data
        assert not _inside(stored, payload) and not _inside(payload, stored)
    st.queue_transactions(Transaction().write("o", 0, raw))
    assert bytes(payload) == want and st.read("o") == raw
    if kind == "received":
        for buf in received:  # the reader reuses its buffers
            buf[:] = bytes(len(buf))
        assert st.read("o") == raw


def test_a_failing_op_leaves_the_store_as_it_was(st):
    st.queue_transactions(
        Transaction().write("o", 0, _view(5000)).setattr("o", "a", b"1")
    )
    before = (st.read("o"), st.getattrs("o"))
    bad = (
        Transaction().write("o", 4000, _view(8192, 1))  # across the end
        .write("o", 100, _view(50, 2)).write("new", 4096, _view(4096, 3))
        .setattr("o", "a", b"2").remove("no-such-object")
    )
    with pytest.raises(FileNotFoundError):
        st.queue_transactions(bad)
    if isinstance(st, FileStore):
        # FileStore journals intent first and converges on the next
        # commit (tests/test_store.py holds that contract)
        return
    assert (st.read("o"), st.getattrs("o")) == before
    assert not st.exists("new")


@pytest.mark.parametrize("backend", ["filestore", "blockstore"])
def test_a_view_payload_is_journaled_and_survives_reopen(tmp_path, backend):
    def boot():
        if backend == "filestore":
            return FileStore(str(tmp_path / "s"))
        return BlockStore(str(tmp_path / "s"), size=1 << 22)

    store = boot()
    txn = (
        Transaction().touch("o")
        .write("o", 0, _view(8192), csums=None)
        .write("o", 12288, _view(4096, 1),
               csums=[crc32c(0, bytes(_view(4096, 1)))], csum_block=4096)
        .setattr("o", "hinfo", b"h" * 40)
    )
    assert all(
        isinstance(op.data, memoryview)
        for op in txn.ops if op.kind is OpKind.WRITE
    )
    store.queue_transactions(txn)
    want = bytes(_view(8192)) + bytes(4096) + bytes(_view(4096, 1))
    assert store.read("o") == want
    if hasattr(store, "close"):
        store.close()
    again = boot()
    assert again.read("o") == want
    assert again.getattr("o", "hinfo") == b"h" * 40
    if backend == "filestore":
        # the journal's record is ``to_bytes()``: a crash before apply
        # replays the very transaction
        from test_store import journal_append

        journal_append(again.journal_path, Transaction().write(
            "o", 4096, _view(8192, 5)).to_bytes())
        third = boot()
        assert third.read("o", 4096, 8192) == bytes(_view(8192, 5))


# -- (e) the served path counts it ---------------------------------------------
def _counters():
    from ceph_tpu.utils import perf_collection

    dump = perf_collection.dump()
    out = {"txn_copy_bytes": 0, "txn_bytes": 0, "apply_copy_bytes": 0}
    for name, vals in dump.items():
        if name.endswith(".rmw") and name.startswith("osd."):
            out["txn_copy_bytes"] += vals.get("txn_copy_bytes", 0)
        if name.endswith(".store") and name.startswith("osd."):
            out["txn_bytes"] += vals["txn_bytes"]
            out["apply_copy_bytes"] += vals["apply_copy_bytes"]
    out.update(dump["txn_codec"])
    return out


def test_a_4mib_write_through_the_client_copies_each_shard_once():
    from ceph_tpu.loadgen import LoadCluster

    k, m, cs = 8, 4, 4096
    cluster = LoadCluster(n_osds=12, k=k, m=m, pg_num=8, chunk_size=cs)
    try:
        before = _counters()
        images = {f"big{i}": _bytes(4 << 20, i) for i in range(3)}
        for oid, image in images.items():
            cluster.io.write_full(oid, image)
        moved = {k_: v - before[k_] for k_, v in _counters().items()}
        for oid, image in images.items():
            assert bytes(cluster.io.read(oid)) == image
        assert moved["txn_copy_bytes"] == 0
        assert moved["copy_bytes"] == 0
        assert moved["txn_bytes"] == 3 * (k + m) * (4 << 20) // k
        assert moved["apply_copy_bytes"] == moved["txn_bytes"]
        assert moved["payload_segments"] == 3 * (k + m - 1)
        assert moved["payload_inline"] == 0

        # small objects and small overwrites: exact, and counted inline
        before = _counters()
        small = {f"small{i}": _bytes(65536, 100 + i) for i in range(4)}
        for oid, image in small.items():
            cluster.io.write_full(oid, image)
        patched = bytearray(images["big0"])
        for off, n in ((12345, 1000), (4096 * 9, 4096), (70000, 300)):
            patch = _bytes(n, off)
            cluster.io.write("big0", patch, offset=off)
            patched[off : off + n] = patch
        moved = {k_: v - before[k_] for k_, v in _counters().items()}
        for oid, image in small.items():
            assert bytes(cluster.io.read(oid)) == image
        assert bytes(cluster.io.read("big0")) == bytes(patched)
        # frames that fit the receiver's scratch carry their payloads
        # inline, one copy each: none a segment of its own
        assert moved["payload_segments"] == 0
        assert moved["payload_inline"] >= 4 * (k + m - 1) + 3
        assert 0 < moved["copy_bytes"] <= moved["txn_bytes"]
        assert moved["apply_copy_bytes"] == moved["txn_bytes"]
    finally:
        cluster.shutdown()
