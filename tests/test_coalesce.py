"""Round-10 serving-tier gate: async objecter, per-tick op
coalescing, batched sub-write fan-out, ring-level error isolation,
and the mesh tier serving LIVE cluster ops.

The load-bearing pins:

- coalesced-dispatch equivalence: N concurrent writes through the
  coalesced tick path leave byte-identical objects, shard bytes and
  HashInfo chains as the one-op-at-a-time path (config-gated both
  ways), with the coalesce counters proving which path ran;
- per-op error isolation: one poisoned op in a tick batch fails
  alone — batch-mates commit and verify; at the ring tier a failed
  multi-op device dispatch retries each member solo;
- the async objecter keeps a bounded per-OSD window, completes
  everything it accepted, and exports the op_coalesced/batch_size
  counter pair;
- ECSubWriteBatch framing round-trips;
- a live cluster serves ops over the mesh route.
"""

import threading
import time

import numpy as np
import pytest

from ceph_tpu.pipeline.inject import ec_inject
from ceph_tpu.utils import config


@pytest.fixture(autouse=True)
def _clean_inject():
    ec_inject.clear_all()
    yield
    ec_inject.clear_all()


def _payload(i: int, size: int = 8192) -> bytes:
    return np.random.default_rng(0xC0A1 + i).integers(
        0, 256, size, np.uint8
    ).tobytes()


def _boot(coalesce: bool):
    from ceph_tpu.loadgen import LoadCluster

    return LoadCluster(
        n_osds=5, k=3, m=2, pg_num=1, chunk_size=2048,
        pool="coalpool",
    )


def _snapshot_stores(cluster) -> dict:
    """(osd, oid) -> (bytes, identity attrs) for every stored shard.
    The ``oi`` attr is excluded (its eversion carries a submit-order-
    dependent tid) and the ``rq`` reqid window too (reqids embed the
    client's per-run uuid) — object bytes, shard identity and the
    HashInfo chain are the cross-run invariants."""
    out = {}
    for osd, store in cluster.stores.items():
        for oid in store.list_objects():
            attrs = {
                k: v for k, v in store.getattrs(oid).items()
                if k in ("hinfo_key", "si")
            }
            out[(osd, oid)] = (store.read(oid), attrs)
    return out


def _run_write_round(coalesce: bool):
    """One deterministic round: a warmup create, then 8 concurrent
    full-object writes submitted while the primary's op worker is
    blocked (so the run is QUEUED together and the coalescer sees
    it), then 4 concurrent sub-stripe overwrites the same way."""
    n_obj, size = 8, 8192
    with config.override(osd_op_coalescing=coalesce):
        cluster = _boot(coalesce)
        try:
            cluster.io.write_full("warm", _payload(99, 2048))
            primary = cluster.mon.osdmap.pg_primary("coalpool", 0)
            pd = cluster.daemons[primary]
            with pd._op_lock:  # queue the whole round behind one tick
                comps = [
                    cluster.io.aio_write_full(f"o{i}", _payload(i, size))
                    for i in range(n_obj)
                ]
            for c in comps:
                c.wait_for_complete(30)
            with pd._op_lock:
                comps = [
                    cluster.io.aio_write(
                        f"o{i}", _payload(100 + i, 500), offset=1000
                    )
                    for i in range(0, n_obj, 2)
                ]
            for c in comps:
                c.wait_for_complete(30)
            reads = {
                f"o{i}": cluster.io.read(f"o{i}") for i in range(n_obj)
            }
            stores = _snapshot_stores(cluster)
            coalesced = sum(
                d.coalesce_pc.get("op_coalesced")
                for d in cluster.daemons.values()
            )
            subwrite_batches = sum(
                d.coalesce_pc.get("subwrite_batches")
                for d in cluster.daemons.values()
            )
            scrub_ok = cluster.scrub_clean(repair=False)
        finally:
            cluster.shutdown()
    expected = {}
    for i in range(n_obj):
        img = bytearray(_payload(i, size))
        if i % 2 == 0:
            img[1000:1500] = _payload(100 + i, 500)
        expected[f"o{i}"] = bytes(img)
    return reads, expected, stores, coalesced, subwrite_batches, scrub_ok


def test_coalesced_equivalence_with_solo_path():
    """The tentpole pin: coalesced tick execution is byte-identical
    to one-op-at-a-time — objects, per-shard store bytes, shard
    identity attrs and HashInfo chains — and deep scrub agrees the
    csums are clean on both."""
    r_on = _run_write_round(coalesce=True)
    r_off = _run_write_round(coalesce=False)
    reads_on, exp_on, stores_on, coal_on, swb_on, scrub_on = r_on
    reads_off, exp_off, stores_off, coal_off, _swb, scrub_off = r_off
    assert reads_on == exp_on, "coalesced path returned wrong bytes"
    assert reads_off == exp_off, "solo path returned wrong bytes"
    assert coal_on > 0, "coalesced run never actually coalesced"
    assert swb_on > 0, "no sub-write frames were batch-packed"
    assert coal_off == 0, "coalesce=off still batched ops"
    assert scrub_on and scrub_off, "deep scrub found csum damage"
    assert set(stores_on) == set(stores_off), (
        "shard placement diverged between the two paths"
    )
    for key in stores_on:
        b_on, a_on = stores_on[key]
        b_off, a_off = stores_off[key]
        assert b_on == b_off, f"shard bytes diverged at {key}"
        assert a_on == a_off, (
            f"identity attrs (hinfo/si) diverged at {key}"
        )


def test_coalesced_batch_error_isolation():
    """One injected bad op inside a tick batch fails ALONE: its
    batch-mates commit, verify byte-for-byte, and the failed op
    surfaces a clean eio to its own caller (the reference's op-level
    error semantics survive coalescing)."""
    with config.override(osd_op_coalescing=True):
        cluster = _boot(True)
        try:
            cluster.io.write_full("warm", _payload(99, 2048))
            pool_id = cluster.mon.osdmap.pools["coalpool"].pool_id
            # client-write abort on the rmw tier's loc-form oid
            ec_inject.write_error(f"{pool_id}:bad", 0, duration=1)
            primary = cluster.mon.osdmap.pg_primary("coalpool", 0)
            pd = cluster.daemons[primary]
            with pd._op_lock:
                bad = cluster.io.aio_write_full("bad", _payload(7))
                goods = [
                    cluster.io.aio_write_full(f"g{i}", _payload(i))
                    for i in range(5)
                ]
            with pytest.raises(IOError):
                bad.wait_for_complete(30)
            for c in goods:
                c.wait_for_complete(30)
            for i in range(5):
                assert cluster.io.read(f"g{i}") == _payload(i), (
                    "a batch-mate of the failed op lost its write"
                )
            assert sum(
                d.coalesce_pc.get("op_coalesced")
                for d in cluster.daemons.values()
            ) > 0, "the round never rode the coalesced path"
        finally:
            cluster.shutdown()


# ---------------------------------------------------------------- ring tier
class _FlakyBatchCodec:
    """Delegates to a real codec but refuses multi-op batches — the
    dispatcher must fall back to solo dispatch per member."""

    def __init__(self, codec) -> None:
        self._codec = codec
        self.k = codec.k
        self.m = codec.m
        self._encode_bmat_np = codec._encode_bmat_np

    def get_sub_chunk_count(self) -> int:
        return 1

    def encode_chunks(self, data):
        if next(iter(data.values())).shape[0] > 1:
            raise RuntimeError("injected batch fault")
        return self._codec.encode_chunks(data)


def test_ring_solo_fallback_isolates_batch_fault():
    """A failed multi-op device dispatch retries each member SOLO:
    every op still gets correct parity, and the batch_faults /
    solo_retries counters tick. Driven through _fire directly so the
    batch composition is deterministic."""
    from ceph_tpu.codecs.registry import registry
    from ceph_tpu.pipeline.dispatcher import (
        StreamingDispatcher,
        _stream_counters,
    )

    codec = registry.factory("isa", {"k": "3", "m": "2"})
    disp = StreamingDispatcher(_FlakyBatchCodec(codec))
    try:
        pc = _stream_counters()
        before = (pc.get("batch_faults"), pc.get("solo_retries"))
        rng = np.random.default_rng(3)
        payloads = [
            rng.integers(0, 256, (1, 3, 4096), np.uint8) for _ in range(3)
        ]
        results: dict[int, object] = {}
        disp._fire(_stage(disp, payloads, results, 0))
        assert set(results) == {0, 1, 2}
        for idx, p in enumerate(payloads):
            parity = codec.encode_chunks(
                {i: p[:, i, :] for i in range(3)}
            )
            want = np.stack(
                [np.asarray(parity[3 + j]) for j in range(2)], axis=1
            )
            got = results[idx]
            assert not isinstance(got, Exception), got
            np.testing.assert_array_equal(got[0], want)
        after = (pc.get("batch_faults"), pc.get("solo_retries"))
        assert after[0] == before[0] + 1
        assert after[1] == before[1] + 3
    finally:
        disp.stop()


def _stage(disp, payloads, results, csum_block):
    """``payloads`` as ring ops that were never pushed, so that one
    ``_fire`` sees exactly this batch."""
    from ceph_tpu.pipeline.dispatcher import _RingOp

    return [
        _RingOp(
            lambda r, i=idx: results.__setitem__(i, r),
            p, csum_block, time.perf_counter(), (None, None),
        )
        for idx, p in enumerate(payloads)
    ]


def test_ring_fused_csum_batch_matches_per_op():
    """Fused encode+csum ops stacked into one ring dispatch produce
    the same parity AND per-block csums as the per-op fused call
    (interpret mode off-TPU)."""
    from ceph_tpu.codecs.registry import registry
    from ceph_tpu.pipeline.dispatcher import StreamingDispatcher

    with config.override(
        ec_fused_csum=True, ec_use_pallas=True,
        ec_fused_csum_interpret=True,
    ):
        codec = registry.factory("isa", {"k": "2", "m": "1"})
        disp = StreamingDispatcher(codec)
        try:
            rng = np.random.default_rng(4)
            cs, cb = 2048, 512
            ops = [
                rng.integers(0, 256, (nc, 2, cs), np.uint8)
                for nc in (1, 2)
            ]
            results: dict[int, object] = {}
            disp._fire(_stage(disp, ops, results, cb))
            for idx, stripes in enumerate(ops):
                got = results[idx]
                assert not isinstance(got, Exception), got
                parity, csums = got
                pm, want_csums = codec.encode_chunks_with_csums(
                    {i: stripes[:, i, :] for i in range(2)}, cb
                )
                assert pm is not None and csums is not None
                np.testing.assert_array_equal(
                    parity[:, 0, :], np.asarray(pm[2])
                )
                np.testing.assert_array_equal(
                    np.asarray(csums), np.asarray(want_csums)
                )
        finally:
            disp.stop()


# ------------------------------------------------------------ async objecter
def test_async_objecter_window_and_counters():
    """submit_async never blocks the caller, honors the per-OSD
    in-flight window, completes everything it accepted, and the
    op_coalesced/batch_size counter pair is live in perf dump."""
    cluster = _boot(True)
    try:
        obj = cluster.client.objecter
        obj.max_inflight_per_osd = 2  # force window parking
        comps = [
            cluster.io.aio_write_full(f"w{i}", _payload(i, 4096))
            for i in range(12)
        ]
        for c in comps:
            c.wait_for_complete(30)
        for i in range(12):
            assert cluster.io.read(f"w{i}") == _payload(i, 4096)
        dump = obj.perf.dump()
        assert "op_coalesced" in dump and "batch_size" in dump
        assert dump["op_completed"] >= 24
        assert dump["op_inflight"] == 0, "inflight gauge leaked"
        # parked ops released in multi-op window flushes
        assert dump["op_coalesced"] > 0
        assert dump["batch_size"]["sum"] >= dump["op_coalesced"]
    finally:
        cluster.shutdown()


def test_async_objecter_callback_and_error():
    """Completions flow through callbacks (before waiters wake), and
    terminal errors surface on the completion, not the caller."""
    cluster = _boot(True)
    try:
        fired = threading.Event()
        seen: list = []

        def cb(c) -> None:
            seen.append(c.error)
            fired.set()

        c = cluster.io.aio_write_full("cb-obj", b"x" * 512, on_complete=cb)
        c.wait_for_complete(30)
        assert fired.is_set() and seen == [None]
        bad = cluster.io.aio_read("never-written")
        with pytest.raises(FileNotFoundError):
            bad.wait_for_complete(30)
        assert isinstance(bad.error, FileNotFoundError)
    finally:
        cluster.shutdown()


# ------------------------------------------------------------- wire framing
def test_subwrite_batch_framing_roundtrip():
    from ceph_tpu.msg.messages import (
        ECSubWriteBatch,
        ECSubWriteBatchReply,
    )
    from ceph_tpu.store import Transaction

    t1 = Transaction().touch("1:a:0").write("1:a:0", 0, b"alpha")
    t2 = Transaction().touch("1:b:0").write("1:b:0", 4096, b"beta")
    msg = ECSubWriteBatch(
        7, 3, [(11, 3, 5, 2, t1), (12, 3, 6, 2, t2)]
    )
    back = ECSubWriteBatch.decode(msg.encode())
    assert back.tid == 7 and back.shard == 3
    assert [it[:4] for it in back.items] == [
        (11, 3, 5, 2), (12, 3, 6, 2)
    ]
    assert [it[4].to_bytes() for it in back.items] == [
        t1.to_bytes(), t2.to_bytes()
    ]
    rep = ECSubWriteBatchReply(7, 3, [(11, True), (12, False)])
    back_r = ECSubWriteBatchReply.decode(rep.encode())
    assert back_r.results == [(11, True), (12, False)]


# ----------------------------------------------------------- multi-chip live
def test_mesh_serves_live_cluster_ops():
    """The mesh tier as a SYSTEM component: a live socket cluster with
    the process mesh installed serves client writes through the
    collective fan-out (counters prove the route) and reads verify."""
    from ceph_tpu.codecs.matrix_codec import _dispatch_counters
    from ceph_tpu.loadgen import LoadCluster

    pc = _dispatch_counters()
    before = pc.get("mesh_encode")
    cluster = LoadCluster(
        n_osds=6, k=4, m=2, pg_num=2, chunk_size=2048,
        pool="meshpool", use_mesh=True,
    )
    try:
        comps = [
            cluster.io.aio_write_full(f"m{i}", _payload(i, 16384))
            for i in range(6)
        ]
        for c in comps:
            c.wait_for_complete(30)
        for i in range(6):
            assert cluster.io.read(f"m{i}") == _payload(i, 16384)
    finally:
        cluster.shutdown()
    assert pc.get("mesh_encode") > before, (
        "live writes never rode the mesh route"
    )
