"""Streaming dispatcher: ring staging -> batched device
dispatch -> completion callbacks (SURVEY §7 step 4; the sharded op
queue role, osd/OSD.cc:9874-9933).
"""

import threading

import numpy as np
import pytest

from ceph_tpu.codecs.registry import registry

from ceph_tpu.pipeline.dispatcher import (
    StreamingDispatcher,
    _stream_counters,
)


@pytest.fixture
def codec():
    return registry.factory("isa", {"k": "4", "m": "2"})


def _host_parity(codec, stripes):
    """Parity [n, m, N] of stripe-major ``stripes`` [n, k, N]."""
    parity = codec.encode_chunks(
        {i: np.asarray(stripes[:, i, :]) for i in range(stripes.shape[1])}
    )
    return np.stack([np.asarray(parity[4 + j]) for j in range(2)], axis=1)


def test_single_op_roundtrip(rng, codec):
    d = StreamingDispatcher(codec)
    try:
        data = rng.integers(0, 256, (2, 4, 4096), np.uint8)
        out = d.encode_sync(data)
        np.testing.assert_array_equal(out, _host_parity(codec, data))
    finally:
        d.stop()


def test_concurrent_ops_batch_and_match(rng, codec):
    """Many threads submit concurrently; every result is bit-exact
    and at least some ops shared a dispatch (the whole point)."""
    d = StreamingDispatcher(codec)
    pc = _stream_counters()
    before = pc.get("batched_ops")
    try:
        datas = [
            rng.integers(0, 256, (1, 4, 4096), np.uint8) for _ in range(64)
        ]
        outs: list = [None] * 64
        errs: list = []

        def worker(i):
            try:
                outs[i] = d.encode_sync(datas[i])
            except Exception as e:  # pragma: no cover
                errs.append(e)

        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(64)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        for i in range(64):
            np.testing.assert_array_equal(
                outs[i], _host_parity(codec, datas[i])
            )
        assert pc.get("batched_ops") > before, "nothing batched"
    finally:
        d.stop()


def test_mixed_shapes_group_separately(rng, codec):
    d = StreamingDispatcher(codec)
    try:
        a = rng.integers(0, 256, (1, 4, 4096), np.uint8)
        b = rng.integers(0, 256, (1, 4, 8192), np.uint8)
        results = {}
        done = threading.Barrier(3)

        def run(name, data):
            results[name] = d.encode_sync(data)
            done.wait()

        threading.Thread(target=run, args=("a", a)).start()
        threading.Thread(target=run, args=("b", b)).start()
        done.wait()
        np.testing.assert_array_equal(results["a"], _host_parity(codec, a))
        np.testing.assert_array_equal(results["b"], _host_parity(codec, b))
    finally:
        d.stop()


def test_oversized_op_rejected(codec):
    from ceph_tpu.pipeline.dispatcher import MAX_OP_BYTES

    d = StreamingDispatcher(codec)
    try:
        with pytest.raises(ValueError):
            d.submit(
                np.zeros((MAX_OP_BYTES // 4096 // 4 + 1, 4, 4096), np.uint8),
                lambda p: None,
            )
    finally:
        d.stop()


def test_pipeline_routes_through_dispatcher(rng):
    """Inside a coalesced tick's scope ShardExtentMap.encode rides the
    ring (ops counter moves) and parity matches the per-op path."""
    from ceph_tpu.pipeline.dispatcher import coalescing_scope, shutdown_all
    from ceph_tpu.pipeline.shard_map import ShardExtentMap
    from ceph_tpu.pipeline.stripe import StripeInfo

    codec = registry.factory("isa", {"k": "4", "m": "2"})
    sinfo = StripeInfo(4, 2, 4 * 4096)

    def build():
        smap = ShardExtentMap(sinfo)
        r = np.random.default_rng(11)
        for raw in range(4):
            smap.insert(
                sinfo.get_shard(raw), 0,
                r.integers(0, 256, 8192, dtype=np.uint8),
            )
        smap.encode(codec)
        return smap

    ref = build()
    pc = _stream_counters()
    before = pc.get("ops")
    try:
        with coalescing_scope():
            got = build()
    finally:
        shutdown_all()
    assert pc.get("ops") > before
    for j in range(2):
        s = sinfo.get_shard(4 + j)
        np.testing.assert_array_equal(
            got.get(s, 0, 8192), ref.get(s, 0, 8192)
        )
