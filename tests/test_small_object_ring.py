"""The small-object pool (PR 30): objects of a stripe or three, written
concurrently through ``LoadCluster`` so that OSD ticks coalesce and
their encodes meet in the staging ring, must leave the k+m stored
shards and the stored cumulative crc32c of a plain reference encode,
whether a tick's batch is one op, several, or padded to a compiled
size, and whether the fused question is answered "fused" (the kernel,
in the interpreter here: parity and csum words from one pass) or not
at all (``ec_fused_csum`` off: the host tables and the host's
checksums). The same cases at the codec's batch entry; the batch
programs are all compiled before the first op's result; and the ring
itself: bounded, cut at the largest program, loud when stopped.
"""

import contextlib
import json
import threading

import numpy as np
import pytest

from benchmark.reference import crc32c as ref_crc
from benchmark.reference import rs_vandermonde as ref_rs
from ceph_tpu.codecs import matrix_codec as mc
from ceph_tpu.codecs.registry import registry
from ceph_tpu.utils import config

K, M, CHUNK = 4, 2, 4096
STRIPE = K * CHUNK
#: the fused question's two answers (``ec_fused_csum``): the kernel,
#: or None, which leaves the batch to the host tables and the csums to
#: the host
ARMS = {"fused": True, "host": False}
#: stripes an object, objects a round
MODES = {"solo": (2, 1), "batched": (2, 24), "padded": (3, 24)}


@pytest.fixture(autouse=True)
def _small_batch_set(monkeypatch):
    """Two batch programs (2, 8 stripes), not four: each is a compile
    in the Pallas interpreter here."""
    monkeypatch.setattr(mc, "BATCH_MAX_STRIPES", 8)


def _object(i: int, stripes: int) -> bytes:
    return np.random.default_rng([0x64C, i]).bytes(stripes * STRIPE)


def _codec():
    return registry.factory(
        "jerasure",
        {"technique": "reed_sol_van", "k": str(K), "m": str(M)},
    )


def _ring():
    from ceph_tpu.pipeline.dispatcher import _stream_counters

    pc = _stream_counters()
    return {k: pc.get(k) for k in (
        "ops", "fused_batches", "fused_batch_ops", "fused_batch_stripes",
        "fused_pad_stripes",
    )}


def _stored(cluster, oid: str):
    """(shards [k+m, n], the hinfo of each) as the stores hold them."""
    shards, hinfos = [], []
    acting = cluster.mon.osdmap.object_to_acting(cluster.pool, oid)
    for shard, osd in enumerate(acting):
        store = cluster.stores[osd]
        (key,) = [
            key for key in store.list_objects()
            if key.partition(":")[2] == f"{oid}#s{shard}"
        ]
        shards.append(np.frombuffer(store.read(key), np.uint8))
        hinfos.append(json.loads(store.getattr(key, "hinfo_key").decode()))
    return np.stack(shards), hinfos


@pytest.mark.parametrize("arm", ARMS)
@pytest.mark.parametrize("mode", MODES)
def test_stored_shards_and_crcs_equal_the_reference(mode, arm):
    from ceph_tpu.loadgen import LoadCluster

    stripes, n_obj = MODES[mode]
    with config.override(
        ec_fused_csum_interpret=True, ec_fused_csum=ARMS[arm],
    ):
        cluster = LoadCluster(n_osds=6, k=K, m=M, pg_num=8, chunk_size=CHUNK)
        try:
            before = _ring()
            with contextlib.ExitStack() as held:
                # queue the round behind every worker, so that each
                # primary's next tick takes its share at once and the
                # ticks' PG groups meet in the ring
                for d in cluster.daemons.values():
                    held.enter_context(d._op_lock)
                comps = [
                    cluster.io.aio_write_full(
                        f"obj{i}", _object(i, stripes)
                    )
                    for i in range(n_obj)
                ]
            for c in comps:
                c.wait_for_complete(60)
            moved = {k: v - before[k] for k, v in _ring().items()}
            for i in range(n_obj):
                image = _object(i, stripes)
                assert bytes(cluster.io.read(f"obj{i}")) == image
                want = ref_rs.shards_of(image, K, M, CHUNK)
                got, hinfos = _stored(cluster, f"obj{i}")
                np.testing.assert_array_equal(got, want)
                crcs = [int(v) for v in ref_crc.crc32c_rows(0xFFFFFFFF, want)]
                for hinfo in hinfos:
                    assert hinfo["total_chunk_size"] == want.shape[1]
                    assert [int(v) for v in hinfo["hashes"]] == crcs
        finally:
            cluster.shutdown()
    if mode == "solo":
        # a tick of one is served on the per-op path: no ring
        assert moved["ops"] == 0
        return
    assert moved["ops"] > 0, "no tick coalesced"
    assert moved["fused_batch_ops"] == moved["ops"]
    assert moved["fused_batch_stripes"] == stripes * moved["ops"]
    if arm == "host":
        # the planner's answer was None: the host tables pad nothing
        assert moved["fused_pad_stripes"] == 0
    elif mode == "padded":
        assert moved["fused_pad_stripes"] > 0  # 3 stripes is no size


@pytest.mark.parametrize("counts", [(2,), (2, 2, 4), (2, 1)],
                         ids=["solo", "batched", "padded"])
def test_batch_entry_answers_agree_bit_for_bit(rng, counts):
    """``encode_batch`` on the fused kernel and on the host tables:
    the same parity, equal to the reference's, and the kernel's
    per-4-KiB csum words equal the reference's crc32c; the device
    route pads to a compiled size and counts the real bytes, the host
    tables take the members as they are."""
    codec = _codec()
    members = [
        rng.integers(0, 256, (n, K, CHUNK), np.uint8) for n in counts
    ]
    total = sum(counts)
    out = {}
    pc = mc._dispatch_counters()
    for arm, fused in ARMS.items():
        with config.override(
            ec_fused_csum_interpret=True, ec_fused_csum=fused,
        ):
            before = {k: pc.get(k) for k in (
                "host_encode_bytes", "fused_encode_bytes",
            )}
            out[arm] = codec.encode_batch(members, CHUNK)
            moved = {k: pc.get(k) - v for k, v in before.items()}
        assert moved[f"{arm}_encode_bytes"] == total * K * CHUNK
        assert sum(moved.values()) == total * K * CHUNK
    assert out["host"][1:] == (None, total)
    assert out["fused"][2] == mc.padded_size(total)
    np.testing.assert_array_equal(out["host"][0], out["fused"][0])
    parity, csums, _sent = out["fused"]
    image = np.concatenate(members).tobytes()
    want = ref_rs.shards_of(image, K, M, CHUNK).reshape(K + M, total, CHUNK)
    np.testing.assert_array_equal(parity, want[K:].transpose(1, 0, 2))
    words = ref_crc.crc32c_rows(0, want.reshape(-1, CHUNK)).reshape(
        K + M, total
    )
    np.testing.assert_array_equal(csums[..., 0], words.T)


def test_every_batch_shape_is_compiled_before_the_first_op(rng, monkeypatch):
    """The shapes a tick can produce are ``batch_sizes()``, and on the
    chip (the TPU predicate patched on; the kernels still run in the
    interpreter) the first op of a geometry finds them all compiled:
    the programs are run (zero stripes, no bytes counted) before its
    own batch, and no later batch of any size compiles another."""
    from ceph_tpu.ops import pallas_encode as pe
    from ceph_tpu.pipeline.dispatcher import StreamingDispatcher
    from ceph_tpu.utils import platform

    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    chunk = 2 * CHUNK  # a geometry no other test of this process warms
    calls = []
    real = mc.MatrixErasureCodec._batch_device

    def spy(self, members, padded, route, csum_block, nbytes):
        calls.append((padded, route, nbytes))
        return real(self, members, padded, route, csum_block, nbytes)

    monkeypatch.setattr(mc.MatrixErasureCodec, "_batch_device", spy)
    disp = StreamingDispatcher(_codec())
    try:
        first = rng.integers(0, 256, (3, K, chunk), np.uint8)
        disp.encode_csum_sync(first, CHUNK)
        sizes = mc.batch_sizes()
        assert sizes == (2, 8)
        assert sorted(calls[: len(sizes)]) == [
            (p, "fused", 0) for p in sizes
        ]
        assert calls[len(sizes):] == [(8, "fused", first.nbytes)]
        compiled = pe._apply_tiled_csum._cache_size()
        results = []
        later = [
            rng.integers(0, 256, (n, K, chunk), np.uint8)
            for n in (1, 2, 3, 1, 2, 3, 1, 2)
        ]
        threads = [
            threading.Thread(target=lambda a=a: results.append(
                disp.encode_csum_sync(a, CHUNK)
            ))
            for a in later
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(results) == 8
        assert pe._apply_tiled_csum._cache_size() == compiled
        assert {c[0] for c in calls} <= set(sizes)
    finally:
        disp.stop()


def test_the_ring_is_bounded_and_a_stopped_one_says_so(rng):
    """``submit`` blocks once ``capacity`` ops are queued
    (backpressure) and goes on when the drain makes room; after
    ``stop`` it raises."""
    from ceph_tpu.pipeline.dispatcher import StreamingDispatcher

    codec = _codec()
    gate, entered = threading.Event(), threading.Event()
    real = codec.encode_batch

    def held(members, csum_block=0):
        entered.set()
        gate.wait(30)
        return real(members, csum_block)

    codec.encode_batch = held
    disp = StreamingDispatcher(codec, capacity=2)
    stripes = rng.integers(0, 256, (1, K, CHUNK), np.uint8)
    done, refused = [], []
    try:
        disp.submit(stripes, done.append)  # the drain takes it and parks
        assert entered.wait(30)
        disp.submit(stripes, done.append)
        disp.submit(stripes, done.append)  # the ring is full now

        def late(sink):
            try:
                disp.submit(stripes, done.append)
            except RuntimeError as e:
                sink.append(e)

        waiter = threading.Thread(target=late, args=(refused,))
        waiter.start()
        waiter.join(0.3)
        assert waiter.is_alive(), "submit did not block on a full ring"
        gate.set()
        waiter.join(30)
        assert not waiter.is_alive() and not refused
    finally:
        disp.stop()
    assert len(done) == 4
    assert all(not isinstance(r, BaseException) for r in done)
    with pytest.raises(RuntimeError):
        disp.submit(stripes, done.append)


def test_a_drain_over_the_largest_program_is_cut(rng):
    """More stripes in one drain than ``BATCH_MAX_STRIPES``: more than
    one codec batch, each within it, every op answered with its own
    parity."""
    from ceph_tpu.pipeline.dispatcher import StreamingDispatcher, _RingOp

    codec = _codec()
    sent = []
    real = codec.encode_batch

    def spy(members, csum_block=0):
        sent.append(sum(a.shape[0] for a in members))
        return real(members, csum_block)

    codec.encode_batch = spy
    disp = StreamingDispatcher(codec)
    results = {}
    ops = [
        rng.integers(0, 256, (3, K, CHUNK), np.uint8) for _ in range(5)
    ]
    try:
        disp._fire([
            _RingOp(
                lambda r, i=i: results.__setitem__(i, r), a, 0, 0.0,
                (None, None),
            )
            for i, a in enumerate(ops)
        ])
    finally:
        disp.stop()
    assert sent == [6, 6, 3]  # 8 stripes is the largest program here
    for i, a in enumerate(ops):
        want = ref_rs.shards_of(a.tobytes(), K, M, CHUNK).reshape(
            K + M, 3, CHUNK
        )
        np.testing.assert_array_equal(
            results[i][0], want[K:].transpose(1, 0, 2)
        )
        assert results[i][1] is None
