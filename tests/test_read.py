"""Read pipeline semantics (ECCommon ReadPipeline analog).

Contract under test, mirroring the reference: fast-path direct reads
when all wanted shards are available, minimum-shard reconstruct when
not (ECCommon.cc:198), retry from survivors on shard EIO
(get_remaining_shards, ECCommon.cc:312), strict in-order client
completion (ECBackend.h:131-148), EOF trimming, and the CLAY
fractional-repair read savings riding the sub-chunk selectors
(ECCommon.h:83-133).
"""

import numpy as np
import pytest

from ceph_tpu.codecs import registry
from ceph_tpu.pipeline.extents import ExtentSet, SubchunkSelect
from ceph_tpu.pipeline.read import (
    ReadPipeline,
    get_min_avail_to_read_shards,
)
from ceph_tpu.pipeline.rmw import RMWPipeline, ShardBackend
from ceph_tpu.pipeline.stripe import PAGE_SIZE, StripeInfo
from ceph_tpu.store import MemStore

K, M = 4, 2
CHUNK = PAGE_SIZE


def make_stack(k=K, m=M, chunk=CHUNK, chunk_mapping=None):
    sinfo = StripeInfo(k, m, k * chunk, chunk_mapping)
    codec = registry.factory(
        "jerasure", {"technique": "reed_sol_van", "k": str(k), "m": str(m)}
    )
    backend = ShardBackend({s: MemStore(f"osd.{s}") for s in range(k + m)})
    rmw = RMWPipeline(sinfo, codec, backend)
    reads = ReadPipeline(sinfo, codec, backend, rmw.object_size)
    return rmw, reads, sinfo, codec, backend


def write(rmw, oid, offset, data):
    rmw.submit(oid, offset, data)


class TestFastPath:
    def test_round_trip(self, rng):
        rmw, reads, *_ = make_stack()
        data = rng.integers(0, 256, 3 * K * CHUNK + 517, np.uint8).tobytes()
        write(rmw, "obj", 0, data)
        assert reads.read_sync("obj", 0, len(data)) == data

    def test_sub_range(self, rng):
        rmw, reads, *_ = make_stack()
        data = rng.integers(0, 256, 2 * K * CHUNK, np.uint8).tobytes()
        write(rmw, "obj", 0, data)
        lo, ln = CHUNK + 100, 2 * CHUNK + 57
        assert reads.read_sync("obj", lo, ln) == data[lo : lo + ln]

    def test_no_decode_when_available(self, rng):
        rmw, reads, _, _, backend = make_stack()
        data = rng.integers(0, 256, K * CHUNK, np.uint8).tobytes()
        write(rmw, "obj", 0, data)
        got = {}
        reads.submit("obj", 0, 100, lambda op: got.update(op=op))
        assert not got["op"].need_decode
        # Only the one shard holding the range was read.
        assert set(got["op"].shard_reads) == {0}

    def test_eof_trim(self, rng):
        rmw, reads, *_ = make_stack()
        data = rng.integers(0, 256, 1000, np.uint8).tobytes()
        write(rmw, "obj", 0, data)
        assert reads.read_sync("obj", 500, 10_000) == data[500:]
        assert reads.read_sync("obj", 5000, 100) == b""
        assert reads.read_sync("missing", 0, 100) == b""


class TestReconstruct:
    @pytest.mark.parametrize("down", [0, 1, 3])
    def test_one_data_shard_down(self, rng, down):
        rmw, reads, _, _, backend = make_stack()
        data = rng.integers(0, 256, 2 * K * CHUNK + 999, np.uint8).tobytes()
        write(rmw, "obj", 0, data)
        backend.down_shards.add(down)
        assert reads.read_sync("obj", 0, len(data)) == data

    def test_two_shards_down(self, rng):
        rmw, reads, _, _, backend = make_stack()
        data = rng.integers(0, 256, K * CHUNK, np.uint8).tobytes()
        write(rmw, "obj", 0, data)
        backend.down_shards.update({0, 2})
        assert reads.read_sync("obj", 0, len(data)) == data

    def test_too_many_down(self, rng):
        rmw, reads, _, _, backend = make_stack()
        data = rng.integers(0, 256, K * CHUNK, np.uint8).tobytes()
        write(rmw, "obj", 0, data)
        backend.down_shards.update({0, 1, 2})  # m+1 losses
        got = {}
        reads.submit("obj", 0, 100, lambda op: got.update(op=op))
        assert got["op"].error is not None

    def test_partial_range_decode(self, rng):
        """Degraded sub-range read only touches the covering chunks."""
        rmw, reads, _, _, backend = make_stack()
        data = rng.integers(0, 256, 4 * K * CHUNK, np.uint8).tobytes()
        write(rmw, "obj", 0, data)
        backend.down_shards.add(1)
        got = {}
        reads.submit("obj", CHUNK, CHUNK // 2, lambda op: got.update(op=op))
        op = got["op"]
        assert op.data == data[CHUNK : CHUNK + CHUNK // 2]
        # Window is one chunk (the wanted range sits inside chunk 1 of
        # stripe 0 -> shard offsets [0, CHUNK)).
        for sr in op.shard_reads.values():
            assert sr.extents.size() <= CHUNK


class TestUnalignedOverwrite:
    def test_subpage_boundary_overwrite_then_degraded(self, rng):
        """Regression: a full-stripe RMW whose write starts/ends inside
        a page must read the boundary bytes — planning with page-
        aligned written extents encoded zeros into parity while the
        store kept old data, corrupting every later degraded read."""
        k, m, chunk = 8, 4, PAGE_SIZE
        sinfo = StripeInfo(k, m, k * chunk)
        codec = registry.factory("isa", {"k": str(k), "m": str(m)})
        backend = ShardBackend(
            {s: MemStore(f"osd.{s}") for s in range(k + m)}
        )
        rmw = RMWPipeline(sinfo, codec, backend)
        reads = ReadPipeline(sinfo, codec, backend, rmw.object_size)
        data = rng.integers(0, 256, 5 * k * chunk + 12345, np.uint8).tobytes()
        rmw.submit("obj", 0, data)
        patch = rng.integers(0, 256, 3 * chunk, np.uint8).tobytes()
        rmw.submit("obj", 2 * chunk + 17, patch)
        expect = bytearray(data)
        expect[2 * chunk + 17 : 2 * chunk + 17 + len(patch)] = patch
        backend.down_shards.update({1, 6, 9, 11})  # m losses
        assert reads.read_sync("obj", 0, len(data)) == bytes(expect)


    #: (ro_offset, length) read from a three-stripe-and-a-bit object
    RANGES = {
        "crosses_chunk_boundary": (CHUNK - 50, 100),
        "crosses_stripe_boundary": (K * CHUNK - 50, 100),
        "head_and_tail_across_two_stripes": (
            CHUNK + 37, 2 * K * CHUNK - CHUNK - 37 + 211
        ),
        "whole_object": (0, 3 * K * CHUNK + 999),
    }

    @pytest.mark.parametrize(
        "mapping", [None, [5, 0, 1, 2, 3, 4]],
        ids=["identity", "chunk_mapping"],
    )
    @pytest.mark.parametrize("span", RANGES)
    def test_unaligned_ranges_healthy_and_degraded(self, rng, span, mapping):
        """The gather's head and tail pieces: a read that starts and
        ends mid-chunk, over a patched object, with every shard up and
        with a data and a parity shard down."""
        rmw, reads, sinfo, _, backend = make_stack(chunk_mapping=mapping)
        data = bytearray(
            rng.integers(0, 256, 3 * K * CHUNK + 999, np.uint8).tobytes()
        )
        rmw.submit("obj", 0, bytes(data))
        patch = rng.integers(0, 256, CHUNK + 77, np.uint8).tobytes()
        at = K * CHUNK - 33  # across the first stripe boundary
        rmw.submit("obj", at, patch)
        data[at : at + len(patch)] = patch
        off, length = self.RANGES[span]
        assert reads.read_sync("obj", off, length) == bytes(
            data[off : off + length]
        )
        # the stored shards of raw data shard 1 and of the first parity
        backend.down_shards.update({sinfo.get_shard(1), sinfo.get_shard(K)})
        assert reads.read_sync("obj", off, length) == bytes(
            data[off : off + length]
        )


class TestRetry:
    def test_eio_retry_recovers(self, rng):
        rmw, reads, _, _, backend = make_stack()
        data = rng.integers(0, 256, K * CHUNK, np.uint8).tobytes()
        write(rmw, "obj", 0, data)
        backend.fail_read_shards.add(2)  # planner can't see it; read EIOs
        got = {}
        reads.submit("obj", 0, len(data), lambda op: got.update(op=op))
        op = got["op"]
        assert op.error is None
        assert op.data == data
        assert op.error_shards == {2}
        assert op.need_decode

    def test_eio_then_too_few(self, rng):
        rmw, reads, _, _, backend = make_stack()
        data = rng.integers(0, 256, K * CHUNK, np.uint8).tobytes()
        write(rmw, "obj", 0, data)
        backend.down_shards.update({4, 5})
        backend.fail_read_shards.add(1)
        got = {}
        reads.submit("obj", 0, len(data), lambda op: got.update(op=op))
        assert got["op"].error is not None


    def test_retry_widens_pending_shard(self, rng):
        """Regression: a retry that needs a wider window from a shard
        whose first (narrow) sub-read is still in flight must issue the
        widening read — skipping pending shards left the survivor with
        partial coverage and failed a recoverable decode."""
        rmw, reads, _, _, backend = make_stack()
        data = rng.integers(0, 256, K * CHUNK, np.uint8).tobytes()
        write(rmw, "obj", 0, data)
        backend.fail_read_shards.add(0)
        backend.defer_reads = True
        got = {}
        # Wants shard 0 [100, CHUNK) and shard 1 [0, 200).
        reads.submit(
            "obj", 100, CHUNK + 100, lambda op: got.update(op=op)
        )
        # Fail shard 0 first while shard 1's narrow read is pending.
        pending = sorted(backend.deferred_reads, key=lambda t: t[0])
        backend.deferred_reads = []
        for _, run in pending:
            run()
        # The widening read for shard 1 (and decode survivors) landed
        # in deferred_reads during the retry — release everything.
        while backend.deferred_reads:
            backend.release_deferred_reads()
        op = got["op"]
        assert op.error is None
        assert op.data == data[100 : CHUNK + 200]
        assert op.error_shards == {0}


class TestOrdering:
    def test_in_order_completion(self, rng):
        rmw, reads, _, _, backend = make_stack()
        a = rng.integers(0, 256, CHUNK, np.uint8).tobytes()
        b = rng.integers(0, 256, CHUNK, np.uint8).tobytes()
        write(rmw, "a", 0, a)
        write(rmw, "b", 0, b)
        backend.defer_reads = True
        done = []
        r1 = reads.submit("a", 0, len(a), lambda op: done.append(op.rid))
        r2 = reads.submit("b", 0, len(b), lambda op: done.append(op.rid))
        assert done == []
        # Complete the SECOND read's sub-reads first; completion must
        # still fire r1 before r2.
        pending = backend.deferred_reads
        backend.deferred_reads = []
        for _, run in reversed(pending):
            run()
        assert done == [r1, r2]


class TestSubchunkExtents:
    def test_restrict(self):
        es = SubchunkSelect(4096, 8, ((0, 2), (4, 2))).byte_extents(
            ExtentSet([(0, 8192)])
        )
        # Per 4K chunk with 512B sub-chunks: [0,1024) and [2048,3072).
        assert list(es) == [
            (0, 1024), (2048, 3072), (4096, 5120), (6144, 7168),
        ]
        assert es.size() == 4096


class TestClayFractionalRepair:
    def test_repair_through_pipeline(self, rng):
        k, m, d = 4, 2, 5
        codec = registry.factory(
            "clay", {"k": str(k), "m": str(m), "d": str(d)}
        )
        chunk = codec.get_chunk_size(k * PAGE_SIZE)
        sinfo = StripeInfo(k, m, k * chunk)
        backend = ShardBackend(
            {s: MemStore(f"osd.{s}") for s in range(k + m)}
        )
        # Two stripes of content, encoded directly into the stores.
        import jax.numpy as jnp

        n_stripes = 2
        data = rng.integers(0, 256, (n_stripes, k, chunk), np.uint8)
        parity = codec.encode_chunks(
            {i: jnp.asarray(data[:, i, :]) for i in range(k)}
        )
        size = n_stripes * k * chunk
        for s in range(k + m):
            buf = (
                data[:, s, :].reshape(-1)
                if s < k
                else np.asarray(parity[s]).reshape(-1)
            )
            from ceph_tpu.store import Transaction

            backend.stores[s].queue_transactions(
                Transaction().write("obj", 0, buf.tobytes())
            )

        reads = ReadPipeline(sinfo, codec, backend, lambda oid: size)
        backend.down_shards.add(1)
        got = {}
        reads.submit("obj", 0, size, lambda op: got.update(op=op))
        op = got["op"]
        assert op.error is None
        expect = np.zeros(size, np.uint8)
        pos = 0
        for stripe in range(n_stripes):
            for raw in range(k):
                expect[pos : pos + chunk] = data[stripe, raw]
                pos += chunk
        assert op.data == expect.tobytes()
        # Fractional read: helpers carry sub-chunk selectors; the ones
        # NOT also wanted by the client (the parity shards here) read
        # only sub_chunk_no/q of each chunk — the MSR bandwidth saving
        # end-to-end. Wanted data shards read their full extents too.
        Z, q = codec.get_sub_chunk_count(), codec.q
        helper_reads = {
            s: sr for s, sr in op.shard_reads.items() if s != 1
        }
        assert len(helper_reads) == d
        assert all(sr.subchunks is not None for sr in helper_reads.values())
        for s in (4, 5):
            assert (
                helper_reads[s].select.byte_extents(
                    helper_reads[s].extents
                ).size()
                == n_stripes * chunk * (Z // q) // Z
            )

    def test_repair_falls_back_to_decode_on_helper_eio(self, rng):
        """Regression: when a fractional-repair helper EIOs and the
        retry re-plans as a full decode, stale sub-chunk selectors must
        not steer reconstruction into codec.repair with too few
        helpers — the read is recoverable via plain decode."""
        k, m, d = 4, 2, 5
        codec = registry.factory(
            "clay", {"k": str(k), "m": str(m), "d": str(d)}
        )
        chunk = codec.get_chunk_size(k * PAGE_SIZE)
        sinfo = StripeInfo(k, m, k * chunk)
        backend = ShardBackend(
            {s: MemStore(f"osd.{s}") for s in range(k + m)}
        )
        import jax.numpy as jnp

        from ceph_tpu.store import Transaction

        data = rng.integers(0, 256, (1, k, chunk), np.uint8)
        parity = codec.encode_chunks(
            {i: jnp.asarray(data[:, i, :]) for i in range(k)}
        )
        size = k * chunk
        for s in range(k + m):
            buf = data[0, s] if s < k else np.asarray(parity[s])[0]
            backend.stores[s].queue_transactions(
                Transaction().write("obj", 0, buf.tobytes())
            )
        reads = ReadPipeline(sinfo, codec, backend, lambda oid: size)
        backend.down_shards.add(1)       # triggers fractional repair
        backend.fail_read_shards.add(5)  # a repair helper EIOs
        got = {}
        reads.submit("obj", 0, size, lambda op: got.update(op=op))
        op = got["op"]
        assert op.error is None
        expect = data[0].reshape(-1).tobytes()
        assert op.data == expect
        assert op.error_shards == {5}

    def test_plan_fast_path_unaffected(self):
        codec = registry.factory("clay", {"k": "4", "m": "2", "d": "5"})
        chunk = codec.get_chunk_size(4 * PAGE_SIZE)
        sinfo = StripeInfo(4, 2, 4 * chunk)
        want = {0: ExtentSet([(0, chunk)])}
        reads, need_decode = get_min_avail_to_read_shards(
            sinfo, codec, want, {0, 1, 2, 3, 4, 5}
        )
        assert not need_decode
        assert set(reads) == {0}
