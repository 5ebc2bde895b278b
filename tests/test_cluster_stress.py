"""Concurrency stress tier — the TestErasureCodeShec_thread role
(src/test/erasure-code/TestErasureCodeShec_thread.cc): hammer shared
codecs (table caches), a live cluster under membership thrash, and
the RMW pipeline's commit-order/no-double-fire invariants under
adversarial ack interleavings.
"""

import threading
import time
from itertools import combinations

import numpy as np
import pytest

from ceph_tpu.codecs.registry import registry


def _roundtrip(codec, rng, nbytes, lose):
    k = codec.get_data_chunk_count()
    data = {
        i: rng.integers(0, 256, (nbytes,), np.uint8) for i in range(k)
    }
    parity = codec.encode_chunks(data)
    originals = {**data, **{i: np.asarray(p) for i, p in parity.items()}}
    chunks = dict(originals)
    for i in lose:
        del chunks[i]
    out = codec.decode_chunks(set(lose), chunks)
    for i in lose:
        np.testing.assert_array_equal(
            np.asarray(out[i]), originals[i]
        )


def test_shec_codec_hammered_from_threads():
    """One shared SHEC codec (determinant-search decode tables) under
    8 threads x random erasures — the literal SHEC_thread scenario."""
    codec = registry.factory(
        "shec", {"k": "4", "m": "3", "c": "2"}
    )
    errors: list = []

    def worker(seed):
        rng = np.random.default_rng(seed)
        try:
            for it in range(25):
                nlose = int(rng.integers(1, 3))
                lose = list(
                    rng.choice(7, size=nlose, replace=False)
                )
                _roundtrip(codec, rng, 512, [int(x) for x in lose])
        except Exception as e:  # pragma: no cover
            errors.append((seed, e))

    threads = [
        threading.Thread(target=worker, args=(s,)) for s in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors


def test_isa_decode_table_cache_threads():
    """Shared ISA codec: 8 threads cycling DIFFERENT erasure patterns
    contend on the LRU decode-table cache; results stay bit-exact."""
    codec = registry.factory("isa", {"k": "6", "m": "3"})
    patterns = list(combinations(range(9), 2))
    errors: list = []

    def worker(seed):
        rng = np.random.default_rng(1000 + seed)
        try:
            for it in range(20):
                lose = list(patterns[(seed * 31 + it * 7) % len(patterns)])
                _roundtrip(codec, rng, 1024, lose)
        except Exception as e:  # pragma: no cover
            errors.append((seed, e))

    threads = [
        threading.Thread(target=worker, args=(s,)) for s in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors


def test_rmw_commit_order_no_double_fire_under_racing_acks():
    """In-order commit and exactly-once callbacks survive adversarial
    ack ORDER: a releaser thread fires deferred sub-write acks in a
    different shuffled order every round while ops are in flight
    (waiting_commit / completed_to contract, ECCommon.h:553-555).
    One releaser, not several — release_deferred is a caller-thread
    hook like the rest of the pipeline (the single-threaded-drain
    contract); racing it would test a harness race, not the
    pipeline."""
    from ceph_tpu.pipeline.rmw import RMWPipeline, ShardBackend
    from ceph_tpu.pipeline.stripe import PAGE_SIZE, StripeInfo
    from ceph_tpu.store import MemStore

    k, m, chunk = 4, 2, PAGE_SIZE
    sinfo = StripeInfo(k, m, k * chunk)
    codec = registry.factory(
        "jerasure",
        {"technique": "reed_sol_van", "k": str(k), "m": str(m)},
    )
    backend = ShardBackend(
        {s: MemStore(f"osd.{s}") for s in range(k + m)}
    )
    backend.defer_acks = True
    pipe = RMWPipeline(sinfo, codec, backend)

    committed: list[int] = []
    commit_lock = threading.Lock()

    def on_commit(op):
        with commit_lock:
            committed.append(op.tid)

    rng = np.random.default_rng(0)
    n_ops = 10
    for i in range(n_ops):
        pipe.submit(
            "obj",
            (i % 2) * chunk,
            rng.integers(0, 256, chunk, dtype=np.uint8).tobytes(),
            on_commit=on_commit,
        )

    stop = threading.Event()

    def releaser():
        import random

        shard_ids = list(range(k + m))
        while not stop.is_set():
            random.shuffle(shard_ids)
            backend.release_deferred(order=list(shard_ids))
            time.sleep(0.001)

    t_rel = threading.Thread(target=releaser)
    t_rel.start()
    deadline = time.time() + 20
    while time.time() < deadline:
        with commit_lock:
            if len(committed) >= n_ops:
                break
        time.sleep(0.01)
    stop.set()
    t_rel.join()
    # exactly once, in submission order — the two invariants
    assert committed == list(range(1, n_ops + 1)), committed


def test_cluster_hammer_under_membership_thrash():
    """6 writer threads hammer one pool through their own clients
    while a thrasher downs/revives an OSD; when the dust settles every
    object reads back as its last write and reconstruct still works
    under any m erasures (service continuity + no torn stripes)."""
    from ceph_tpu.cluster import Monitor, OSDDaemon, RadosClient

    mon = Monitor()
    for i in range(5):
        mon.osd_crush_add(i)
    daemons = []
    for i in range(5):
        d = OSDDaemon(i, mon, chunk_size=1024, tick_period=0)
        d.start()
        daemons.append(d)
    mon.osd_erasure_code_profile_set(
        "rs32",
        {"plugin": "jerasure", "technique": "reed_sol_van",
         "k": "3", "m": "2"},
    )
    mon.osd_pool_create("stress", 8, "rs32")

    finals: dict[str, bytes] = {}
    finals_lock = threading.Lock()
    errors: list = []
    stop_thrash = threading.Event()

    def writer(wid):
        client = RadosClient(mon, backoff=0.02)
        try:
            io = client.open_ioctx("stress")
            r = np.random.default_rng(wid)
            for it in range(8):
                oid = f"w{wid}-o{it % 3}"
                data = r.integers(
                    0, 256, 3 * 1024 + it * 517, dtype=np.uint8
                ).tobytes()
                io.write(oid, data)
                with finals_lock:
                    finals[oid] = data
                # oids are writer-private: full content must match
                assert io.read(oid) == data
        except Exception as e:  # pragma: no cover
            errors.append((wid, e))
        finally:
            client.shutdown()

    def thrasher():
        victim = 4  # never primary for every PG; thrash regardless
        for _ in range(2):
            if stop_thrash.wait(0.3):
                return
            daemons[victim].stop()
            mon.osd_down(victim)
            if stop_thrash.wait(0.4):
                return
            d = OSDDaemon(
                victim, mon, store=daemons[victim].store,
                chunk_size=1024, tick_period=0,
            )
            d.start()
            daemons[victim] = d

    th = threading.Thread(target=thrasher)
    th.start()
    writers = [
        threading.Thread(target=writer, args=(w,)) for w in range(6)
    ]
    for t in writers:
        t.start()
    for t in writers:
        t.join()
    stop_thrash.set()
    th.join()
    try:
        assert not errors, errors
        client = RadosClient(mon, backoff=0.02)
        try:
            io = client.open_ioctx("stress")
            for oid, data in finals.items():
                assert io.read(oid) == data, f"{oid} diverged"
        finally:
            client.shutdown()
    finally:
        for d in daemons:
            try:
                d.stop()
            except Exception:
                pass


class TestQuorumConcurrentCommands:
    def test_parallel_commands_serialize_without_forking(self):
        """Concurrent proxied commands must serialize through the
        leader without forking the epoch sequence or losing any
        command's effect."""
        import threading

        from ceph_tpu.cluster.mon_quorum import (
            MonQuorumService,
            QuorumMonitor,
        )

        svc = MonQuorumService(3)
        mon = QuorumMonitor(svc)
        errors: list[Exception] = []

        def worker(base):
            try:
                for i in range(5):
                    mon.osd_crush_add(base * 10 + i, zone=f"z{base}")
            except Exception as e:
                errors.append(e)

        threads = [
            threading.Thread(target=worker, args=(b,)) for b in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors[0]
        # every command landed exactly once, all ranks agree
        head = mon.osdmap
        assert head.epoch == 20, head.epoch
        for r in range(3):
            assert (
                svc.monitors[r].osdmap.to_bytes() == head.to_bytes()
            ), f"rank {r} diverged under concurrency"
