"""The ro-range <-> shard-run mapping against the per-chunk walks it
replaced.

``StripeInfo.ro_range_to_shard_runs`` computes, per touched data shard,
one contiguous shard run in closed form; ``ShardExtentMap.insert_ro_range``
/ ``get_ro_range`` move the bytes with one strided copy per shard. Until
PR 25 the write scatter, the read gather and the extent fan-out each
walked the range one chunk at a time. Those three loops live on here as
the oracle: same shard bytes, same extent sets, same bytes read back.
"""

import numpy as np
import pytest

from ceph_tpu.codecs import registry
from ceph_tpu.pipeline.extents import ExtentSet
from ceph_tpu.pipeline.read import ReadPipeline
from ceph_tpu.pipeline.rmw import RMWPipeline, ShardBackend
from ceph_tpu.pipeline.shard_map import ShardExtentMap
from ceph_tpu.pipeline.stripe import StripeInfo
from ceph_tpu.store import MemStore
from ceph_tpu.utils.trace import tracer

M = 2
MIB4 = 4 << 20


# -- the oracle: the loops as they were --------------------------------
def _walk(sinfo, ro_offset, length):
    """(shard, shard_off, taken, take) per chunk piece of the range."""
    pos, taken = ro_offset, 0
    while taken < length:
        chunk_index = pos // sinfo.chunk_size
        raw = chunk_index % sinfo.k
        in_chunk = pos % sinfo.chunk_size
        take = min(sinfo.chunk_size - in_chunk, length - taken)
        shard_off = (chunk_index // sinfo.k) * sinfo.chunk_size + in_chunk
        yield sinfo.get_shard(raw), shard_off, taken, take
        pos += take
        taken += take


def loop_scatter(smap, ro_offset, data):
    data = np.frombuffer(data, dtype=np.uint8)
    for shard, shard_off, taken, take in _walk(smap.sinfo, ro_offset, len(data)):
        smap.insert(shard, shard_off, data[taken : taken + take])


def loop_gather(smap, ro_offset, length):
    out = np.zeros(length, dtype=np.uint8)
    for shard, shard_off, taken, take in _walk(smap.sinfo, ro_offset, length):
        out[taken : taken + take] = smap.get(shard, shard_off, take)
    return out.tobytes()


def loop_extent_set(sinfo, ro_offset, ro_length, parity=False):
    out = {}
    if ro_length <= 0:
        return out
    for shard, shard_off, _, take in _walk(sinfo, ro_offset, ro_length):
        out.setdefault(shard, ExtentSet()).insert(shard_off, take)
    if parity:
        first = sinfo.ro_offset_to_prev_chunk_offset(ro_offset)
        last = sinfo.ro_offset_to_next_chunk_offset(ro_offset + ro_length)
        for raw in range(sinfo.k, sinfo.k + sinfo.m):
            out.setdefault(sinfo.get_shard(raw), ExtentSet()).insert(
                first, last - first
            )
    return out


# -- cases --------------------------------------------------------------
def _mapping(k):
    """Data shards reversed, parity first: no raw shard keeps its id."""
    return [M + k - 1 - r for r in range(k)] + list(range(M))


GEOMETRIES = [
    pytest.param(k, cs, None, id=f"k{k}-cs{cs}")
    for k in (2, 4, 8, 10)
    for cs in (1024, 4096, 6144)
] + [
    pytest.param(k, cs, _mapping(k), id=f"k{k}-cs{cs}-mapped")
    for k, cs in ((2, 4096), (4, 6144), (8, 4096), (10, 1024))
]

#: name -> (ro_offset, length) from (chunk_size, stripe_width)
RANGES = {
    "unaligned_head": lambda cs, sw: (cs + 37, 3 * sw - cs - 37),
    "unaligned_tail": lambda cs, sw: (sw, 2 * sw + cs + 123),
    "unaligned_both": lambda cs, sw: (2 * cs - 100, 2 * sw + 3 * cs + 57),
    "inside_one_chunk": lambda cs, sw: (sw + cs + 10, cs // 2),
    "crosses_chunk_boundary": lambda cs, sw: (cs - 50, 100),
    "crosses_stripe_boundary": lambda cs, sw: (sw - 50, 100),
    "exactly_one_stripe": lambda cs, sw: (sw, sw),
    "one_whole_chunk": lambda cs, sw: (sw + cs, cs),
    "head_and_tail_on_one_shard": lambda cs, sw: (cs // 2, sw),
    "fewer_chunks_than_shards": lambda cs, sw: (cs + 3, 2 * cs),
    "one_byte": lambda cs, sw: (3 * sw - 1, 1),
    "length_zero": lambda cs, sw: (cs + 5, 0),
}

CASES = pytest.mark.parametrize("k,cs,mapping", GEOMETRIES)
SPANS = pytest.mark.parametrize("span", RANGES)

#: the benchmark's object, whole and off every boundary; the oracle's
#: inserts copy a growing run each, so the large size runs on few shapes
BIG = pytest.mark.parametrize(
    "k,cs,mapping,ro_offset",
    [
        pytest.param(8, 4096, None, 0, id="k8-cs4096-4MiB"),
        pytest.param(8, 4096, _mapping(8), 4099, id="k8-cs4096-mapped-4MiB-at-4099"),
        pytest.param(4, 6144, None, 6000, id="k4-cs6144-4MiB-at-6000"),
        pytest.param(10, 4096, None, 0, id="k10-cs4096-4MiB"),
    ],
)


def _payload(length, seed=7):
    return np.random.default_rng(seed).integers(
        0, 256, length, dtype=np.uint8
    ).tobytes()


def _assert_same_map(got: ShardExtentMap, want: ShardExtentMap):
    assert got.shards() == want.shards()
    assert list(got._bufs) == list(want._bufs)  # first-touched order
    for shard in want.shards():
        assert got.get_extent_set(shard) == want.get_extent_set(shard)
        for start, end in want.get_extent_set(shard):
            assert np.array_equal(
                got.get(shard, start, end - start),
                want.get(shard, start, end - start),
            ), f"shard {shard} [{start},{end})"


def _check_scatter_and_extents(sinfo, ro_offset, data):
    got, want = ShardExtentMap(sinfo), ShardExtentMap(sinfo)
    got.insert_ro_range(ro_offset, data)
    loop_scatter(want, ro_offset, data)
    _assert_same_map(got, want)
    for parity in (False, True):
        new = sinfo.ro_range_to_shard_extent_set(ro_offset, len(data), parity)
        old = loop_extent_set(sinfo, ro_offset, len(data), parity)
        assert new == old
        assert list(new) == list(old)
    runs = sinfo.ro_range_to_shard_runs(ro_offset, len(data))
    chunks = {
        (ro_offset + taken) // sinfo.chunk_size
        for _, _, taken, _ in _walk(sinfo, ro_offset, len(data))
    }
    assert len(runs) == min(sinfo.k, len(chunks))
    assert all(1 <= len(run.pieces) <= 3 for run in runs)


def _check_gather(sinfo, ro_offset, data):
    smap = ShardExtentMap(sinfo)
    smap.insert_ro_range(ro_offset, data)
    assert smap.get_ro_range(ro_offset, len(data)) == data
    assert loop_gather(smap, ro_offset, len(data)) == data
    # holes: a shard gone, a bite out of another, and a read wider than
    # what was written — zeros wherever ``get`` gives zeros
    shards = smap.shards()
    if shards:
        start, end = next(iter(smap.get_extent_set(shards[0])))
        smap.erase(shards[0], start + (end - start) // 3, (end - start) // 2 + 1)
        if len(shards) > 2:
            smap.erase_shard(shards[1])
    lo = max(0, ro_offset - sinfo.chunk_size - 11)
    wide = ro_offset - lo + len(data) + sinfo.stripe_width + 13
    assert smap.get_ro_range(lo, wide) == loop_gather(smap, lo, wide)
    assert smap.get_ro_range(ro_offset, len(data)) == loop_gather(
        smap, ro_offset, len(data)
    )


@SPANS
@CASES
def test_scatter_and_extent_sets_match_the_chunk_loops(k, cs, mapping, span):
    sinfo = StripeInfo(k, M, k * cs, mapping)
    ro_offset, length = RANGES[span](cs, k * cs)
    _check_scatter_and_extents(sinfo, ro_offset, _payload(length))


@SPANS
@CASES
def test_gather_matches_the_chunk_loop(k, cs, mapping, span):
    sinfo = StripeInfo(k, M, k * cs, mapping)
    ro_offset, length = RANGES[span](cs, k * cs)
    _check_gather(sinfo, ro_offset, _payload(length))


@BIG
def test_4mib_scatter_and_extent_sets_match_the_chunk_loops(
    k, cs, mapping, ro_offset
):
    sinfo = StripeInfo(k, M, k * cs, mapping)
    _check_scatter_and_extents(sinfo, ro_offset, _payload(MIB4))


@BIG
def test_4mib_gather_matches_the_chunk_loop(k, cs, mapping, ro_offset):
    sinfo = StripeInfo(k, M, k * cs, mapping)
    _check_gather(sinfo, ro_offset, _payload(MIB4))


def test_scatter_overwrites_what_the_map_held():
    """A second scatter over a map that already holds bytes wins on the
    overlap and keeps the rest, as the chunk-by-chunk inserts did."""
    sinfo = StripeInfo(4, M, 4 * 4096)
    got, want = ShardExtentMap(sinfo), ShardExtentMap(sinfo)
    for seed, (ro_offset, length) in enumerate(
        [(0, 3 * 16384), (5000, 20000), (16384 - 7, 14), (40000, 30000)]
    ):
        data = _payload(length, seed)
        got.insert_ro_range(ro_offset, data)
        loop_scatter(want, ro_offset, data)
    _assert_same_map(got, want)
    assert got.get_ro_range(0, 70000) == loop_gather(want, 0, 70000)


@pytest.mark.parametrize(
    "make",
    [bytearray, memoryview, lambda b: np.frombuffer(b, np.uint8)],
    ids=["bytearray", "memoryview", "ndarray"],
)
def test_scatter_takes_any_byte_buffer(make):
    sinfo = StripeInfo(4, M, 4 * 1024)
    data = _payload(9000)
    smap = ShardExtentMap(sinfo)
    smap.insert_ro_range(700, make(data))
    assert smap.get_ro_range(700, 9000) == data


# -- the structural guard: the loops cannot come back unseen -----------
def _open_spans():
    return {sp.name for sp in tracer._stack()}


@pytest.fixture
def call_counts(monkeypatch):
    """Calls of ``ShardExtentMap.insert`` on data shards while an
    ``ec_write.assemble`` span is open on the thread, and of ``.get``
    while ``ec_read.finish`` is."""
    counts = {"assemble_inserts": 0, "finish_gets": 0}
    insert, get = ShardExtentMap.insert, ShardExtentMap.get

    def counting_insert(self, shard, offset, data):
        if "ec_write.assemble" in _open_spans() and self.sinfo.is_data_shard(shard):
            counts["assemble_inserts"] += 1
        return insert(self, shard, offset, data)

    def counting_get(self, shard, offset, length):
        if "ec_read.finish" in _open_spans():
            counts["finish_gets"] += 1
        return get(self, shard, offset, length)

    monkeypatch.setattr(ShardExtentMap, "insert", counting_insert)
    monkeypatch.setattr(ShardExtentMap, "get", counting_get)
    monkeypatch.setattr(tracer, "enabled", True)
    return counts


@pytest.mark.parametrize("size", [MIB4, 64 << 10, 2048],
                         ids=["4MiB", "64KiB", "2KiB"])
def test_one_insert_and_one_get_per_shard_not_per_chunk(call_counts, size):
    k, m, cs = 8, 4, 4096
    sinfo = StripeInfo(k, m, k * cs)
    codec = registry.factory(
        "jerasure", {"technique": "reed_sol_van", "k": str(k), "m": str(m)}
    )
    backend = ShardBackend({s: MemStore(f"osd.{s}") for s in range(k + m)})
    rmw = RMWPipeline(sinfo, codec, backend)
    reads = ReadPipeline(sinfo, codec, backend, rmw.object_size)
    data = _payload(size)
    shards = min(k, -(-size // cs))

    rmw.submit("obj", 0, data)
    assert 1 <= call_counts["assemble_inserts"] <= shards

    assert reads.read_sync("obj", 0, size) == data
    assert 1 <= call_counts["finish_gets"] <= shards
