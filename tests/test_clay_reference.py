"""The Clay pool on the served path against its plain reference
(``benchmark/reference/clay.py``), at toy sizes: what the program
stores is the reference's encode, the reference rebuilds from any
erasures up to m, the constants the two have to share are the same,
a read's plan is d helpers and the reference's repair planes, the
strided gather of ``_repair_fractional`` is the loop it replaced, and
one compiled program a repair gives the host path's bytes."""

import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import clay as ref
from ceph_tpu.codecs import clay as clay_mod
from ceph_tpu.codecs import registry
from ceph_tpu.msg.messages import ECSubRead
from ceph_tpu.pipeline.extents import ExtentSet, SubchunkSelect
from ceph_tpu.pipeline.read import (
    ReadPipeline,
    get_min_avail_to_read_shards,
)
from ceph_tpu.pipeline.rmw import ShardBackend
from ceph_tpu.pipeline.shard_map import ShardExtentMap
from ceph_tpu.pipeline.stripe import StripeInfo
from ceph_tpu.store import MemStore, Transaction

#: (k, m, d, chunk_size, stripes): the thrash suite's pool and the
#: document's, at a few stripes
GEOMETRIES = [(4, 2, 5, 1024, 3), (8, 4, 11, 16384, 2)]
IDS = ["clay-4-2-5", "clay-8-4-11"]


def make(k, m, d):
    codec = registry.factory("clay", {
        "k": str(k), "m": str(m), "d": str(d), "technique": "reed_sol_van",
    })
    return codec, {"k": k, "m": m, "d": d}


def seeded_shards(k, m, d, chunk, stripes, seed=11, ragged=0):
    rng = np.random.default_rng([seed, k, m, d])
    obj = bytes(rng.integers(0, 256, stripes * k * chunk - ragged, np.uint8))
    pool = {"k": k, "m": m, "d": d, "chunk_size": chunk}
    return obj, pool, ref.shards_of(obj, k, m, chunk, pool=pool)


# ------------------------------------------------ the shared constants
@pytest.mark.parametrize("k,m,d", [g[:3] for g in GEOMETRIES] + [(3, 2, 4)])
def test_geometry_and_node_order_are_the_programs(k, m, d):
    codec, _ = make(k, m, d)
    geo = ref.Geometry(k, m, d)
    assert (geo.q, geo.nu, geo.t, geo.planes) == (
        codec.q, codec.nu, codec.t, codec.sub_chunk_no
    )
    for chunk in range(k + m):
        assert geo.node_of(chunk) == codec._to_node(chunk)
    for z in range(geo.planes):
        assert geo.digits(z) == codec._plane_vector(z)


def test_pair_matrix_and_which_member_is_hi_are_the_programs():
    codec, _ = make(8, 4, 11)
    # rows (C_hi, C_lo, U_hi, U_lo) as functions of (C_hi, C_lo)
    assert codec._g4[:2].tolist() == [[1, 0], [0, 1]]
    assert tuple(map(tuple, codec._g4[2:].tolist())) == ref.PAIR
    # the larger x is "hi": index 0 of the coupled pair, 2 of the U
    assert codec._pair_idx(3, 1) == (0, 2)
    assert codec._pair_idx(1, 3) == (1, 3)
    # the reference calls PAIR its own inverse
    a, b = 0x53, 0xCA
    u = ref._pair_forward(np.array([a], np.uint8), np.array([b], np.uint8))
    back = ref._pair_forward(*u)
    assert (int(back[0][0]), int(back[1][0])) == (a, b)


# ------------------------------------------- stored shards == reference
@pytest.mark.parametrize("k,m,d,chunk,stripes", GEOMETRIES, ids=IDS)
def test_program_encode_is_the_references(k, m, d, chunk, stripes):
    codec, _ = make(k, m, d)
    _obj, _pool, want = seeded_shards(k, m, d, chunk, stripes)
    data = {i: want[i].reshape(stripes, chunk) for i in range(k)}
    parity = codec.encode_chunks(data)  # the host path
    for j in range(m):
        assert np.array_equal(
            np.asarray(parity[k + j]).reshape(-1), want[k + j]
        ), j


@pytest.mark.parametrize(
    "k,m,d,chunk,stripes", GEOMETRIES + [(3, 2, 4, 1024, 2)],
    ids=IDS + ["clay-3-2-4-shortened"],
)
def test_the_write_pipelines_encode_stores_the_references_shards(
    k, m, d, chunk, stripes
):
    """``ShardExtentMap.encode`` through ``encode_stacked``, the one
    compiled program an object, ragged last stripe included: whole
    rows of the node grid take the three whole-tensor steps, a
    shortened code (a virtual node) the plane-by-plane trace."""
    codec, _ = make(k, m, d)
    assert codec._whole_rows() == (codec.nu == 0)
    obj, _pool, want = seeded_shards(k, m, d, chunk, stripes, ragged=333)
    sinfo = StripeInfo(k, m, k * chunk)
    sem = ShardExtentMap(sinfo)
    sem.insert_ro_range(0, obj)
    sem.encode(codec)
    for shard in range(k, k + m):
        got = sem.get(shard, 0, stripes * chunk)
        assert np.array_equal(got, want[shard]), shard


def test_encode_stacked_pads_to_a_shared_batch_and_counts_a_dispatch():
    from ceph_tpu.codecs.matrix_codec import _dispatch_counters

    codec, _ = make(4, 2, 5)
    assert [clay_mod.batch_size(n) for n in (1, 8, 9, 32, 33)] == [
        8, 8, 16, 32, 64
    ]
    _obj, _pool, want = seeded_shards(4, 2, 5, 1024, 3)
    stripes = np.stack(
        [want[i].reshape(3, 1024) for i in range(4)], axis=1
    )
    codec.encode_stacked(stripes)  # traces: counts inside, once
    before = _dispatch_counters().dump()["dispatches"]
    out = codec.encode_stacked(stripes)
    assert out.shape == (3, 2, 1024)
    assert np.array_equal(out[:, 1].reshape(-1), want[5])
    assert _dispatch_counters().dump()["dispatches"] == before + 1
    # one program a padded size for every codec object of the pool
    again, _ = make(4, 2, 5)
    programs = len(clay_mod._PROGRAMS)
    again.encode_stacked(stripes[:2])
    assert len(clay_mod._PROGRAMS) == programs


# ------------------------------------------- the reference's any-k decode
def erasure_sample(k, m, count=10):
    combos = [
        c for r in range(1, m + 1)
        for c in itertools.combinations(range(k + m), r)
    ]
    if len(combos) <= 24:
        return combos
    rng = np.random.default_rng([k, m])
    picked = rng.choice(len(combos), count, replace=False)
    # always with every parity gone and with the first m data gone
    return [combos[i] for i in sorted(picked)] + [
        tuple(range(k, k + m)), tuple(range(m)),
    ]


@pytest.mark.parametrize("k,m,d,chunk,stripes", GEOMETRIES, ids=IDS)
def test_reference_rebuilds_from_every_choice_of_erasures(
    k, m, d, chunk, stripes
):
    obj, pool, want = seeded_shards(k, m, d, chunk, stripes, ragged=77)
    for erased in erasure_sample(k, m):
        have = {s: want[s] for s in range(k + m) if s not in erased}
        got = ref.decode_data(have, k, m, pool=pool)
        assert np.array_equal(got, want[:k]), erased
    assert ref.object_from_data_shards(want[:k], len(obj), chunk) == obj


@pytest.mark.parametrize("k,m,d,chunk,stripes", GEOMETRIES, ids=IDS)
def test_program_decodes_what_the_reference_encoded(k, m, d, chunk, stripes):
    codec, _ = make(k, m, d)
    _obj, _pool, want = seeded_shards(k, m, d, chunk, stripes)
    erased = (1, k)  # a data and a parity chunk
    have = {
        s: want[s].reshape(stripes, chunk)
        for s in range(k + m) if s not in erased
    }
    out = codec.decode_chunks(set(erased), have)
    for s in erased:
        assert np.array_equal(np.asarray(out[s]).reshape(-1), want[s]), s


# --------------------------------------------------- the plan of a read
def lost_chunks(k, m):
    return list(range(k + m))


@pytest.mark.parametrize("k,m,d,chunk,stripes", GEOMETRIES, ids=IDS)
def test_plan_is_d_helpers_and_the_references_repair_planes(
    k, m, d, chunk, stripes
):
    codec, _ = make(k, m, d)
    geo = ref.Geometry(k, m, d)
    sinfo = StripeInfo(k, m, k * chunk)
    window = ExtentSet([(0, stripes * chunk)])
    for lost in range(k):  # a client's read wants the data shards
        want = {s: window.copy() for s in range(k)}
        avail = set(range(k + m)) - {lost}
        reads, decode = get_min_avail_to_read_shards(
            sinfo, codec, want, avail
        )
        assert decode
        helpers = {s: sr for s, sr in reads.items() if sr.subchunks}
        assert len(helpers) == d and lost not in reads
        planes = geo.repair_planes(geo.node_of(lost))
        assert len(planes) == geo.planes // geo.q
        for shard, sr in helpers.items():
            assert [
                z for i, n in sr.subchunks for z in range(i, i + n)
            ] == planes
            if shard < k:
                # the client wants it whole: read in full, no selector
                assert sr.select is None
                assert sr.extents == window
            else:
                # one extent and the runs, never a byte range a chunk
                assert sr.select == SubchunkSelect(
                    chunk, geo.planes, tuple(sr.subchunks)
                )
                assert list(sr.extents) == list(window)
                assert sr.wire_runs() == 1 + len(sr.subchunks)
                assert sr.select.byte_extents(sr.extents).size() == (
                    stripes * chunk // geo.q
                )
        # runs by the lost node's row: q^y runs of q^(t-1-y) planes
        y = geo.node_of(lost) // geo.q
        runs = next(iter(helpers.values())).subchunks
        assert len(runs) == geo.q ** y
        assert {n for _i, n in runs} == {geo.q ** (geo.t - 1 - y)}


# --------------------------------- the gather against the loop it replaced
def gather_by_the_old_loop(result, shard, lo, n_chunks, select):
    """``_repair_fractional`` before PR 33: a byte-extent set a chunk,
    a ``result.get`` a run, a concatenate a chunk."""
    cs = select.chunk_size
    rows = []
    for c in range(n_chunks):
        base = lo + c * cs
        sel = select.byte_extents(ExtentSet([(base, base + cs)]))
        rows.append(np.concatenate(
            [result.get(shard, s, e - s) for s, e in sel]
        ))
    return np.stack(rows)


@pytest.mark.parametrize("k,m,d,chunk,stripes", GEOMETRIES, ids=IDS)
def test_strided_gather_is_the_old_loop_for_every_lost_node(
    k, m, d, chunk, stripes
):
    codec, _ = make(k, m, d)
    geo = ref.Geometry(k, m, d)
    sinfo = StripeInfo(k, m, k * chunk)
    # a ragged object: the last stripe's later shards are short, a hole
    # reads zero
    obj, _pool, _want = seeded_shards(
        k, m, d, chunk, stripes, ragged=k * chunk // 2 + 5
    )
    result = ShardExtentMap(sinfo)
    result.insert_ro_range(0, obj)
    for lost in lost_chunks(k, m):
        runs = codec.get_repair_subchunks(codec._to_node(lost))
        select = SubchunkSelect(chunk, geo.planes, tuple(runs))
        for shard in range(k):
            if shard == lost:
                continue
            want = gather_by_the_old_loop(result, shard, 0, stripes, select)
            got = select.select(result.get(shard, 0, stripes * chunk))
            assert np.array_equal(got, want), (lost, shard)


def test_select_takes_irregular_runs_and_a_single_run():
    rng = np.random.default_rng(4)
    buf = rng.integers(0, 256, 3 * 4096, np.uint8)
    for runs in [((0, 2), (5, 1)), ((3, 2),), ((0, 8),), ((1, 1), (3, 1), (5, 1), (7, 1))]:
        select = SubchunkSelect(4096, 8, runs)
        extents = select.byte_extents(ExtentSet([(0, buf.size)]))
        want = np.concatenate([buf[s:e] for s, e in extents])
        assert np.array_equal(select.select(buf).reshape(-1), want), runs
        assert select.packed_chunk == sum(n for _i, n in runs) * 512


# ------------------------------------------------------ the wire and store
def test_sub_read_carries_runs_and_a_store_answers_packed():
    select = SubchunkSelect(1024, 8, ((1, 1), (5, 1)))
    msg = ECSubRead(
        7, 2, "o", [(0, 2048)], list(select.runs),
        (select.chunk_size, select.sub_count),
    )
    back = ECSubRead.decode(msg.encode())
    assert back.select() == select
    assert ECSubRead.decode(
        ECSubRead(7, 2, "o", [(0, 2048)]).encode()
    ).select() is None
    store = MemStore("osd.0")
    rng = np.random.default_rng(9)
    shard = rng.integers(0, 256, 2048 - 100, np.uint8)  # short: EOF pads
    store.queue_transactions(Transaction().write("o", 0, shard.tobytes()))
    backend = ShardBackend({0: store})
    got = backend.read_shard(0, "o", ExtentSet([(0, 2048)]), select)
    padded = np.zeros(2048, np.uint8)
    padded[: shard.size] = shard
    assert list(got) == [0]
    assert got[0] == select.select(padded).tobytes()
    assert len(got[0]) == 2 * select.packed_chunk


# ---------------------------------------------- one program a repair
@pytest.mark.parametrize("k,m,d,chunk,stripes,losts", [
    (4, 2, 5, 1024, 3, (0, 3, 5)),
    (8, 4, 11, 16384, 2, (1, 6)),
], ids=IDS)
def test_repair_window_is_the_host_repair_and_the_references(
    k, m, d, chunk, stripes, losts
):
    codec, pool = make(k, m, d)
    geo = ref.Geometry(k, m, d)
    _obj, _p, want = seeded_shards(k, m, d, chunk, stripes)
    sub = chunk // geo.planes
    for lost in losts:
        planes = geo.repair_planes(geo.node_of(lost))
        ids = [c for c in range(k + m) if c != lost]
        stack = np.stack([
            want[c].reshape(stripes, geo.planes, sub)[:, planes]
            .reshape(stripes, -1)
            for c in ids
        ])
        out = codec.repair_window(lost, ids, stack)
        assert out.shape == (stripes, chunk)
        assert np.array_equal(out.reshape(-1), want[lost]), lost
        host = codec.repair({lost}, dict(zip(ids, stack)))[lost]
        assert np.array_equal(np.asarray(host), out), lost
        plain = ref.repair(
            {c: stack[i].reshape(stripes, len(planes), sub)
             for i, c in enumerate(ids)},
            lost, k, m, pool=pool,
        )
        assert np.array_equal(plain, out), lost


def test_repair_counts_one_dispatch_and_its_bytes_by_how_it_was_served():
    from ceph_tpu.codecs.matrix_codec import _dispatch_counters
    from ceph_tpu.utils import config

    codec, _ = make(4, 2, 5)
    geo = ref.Geometry(4, 2, 5)
    _obj, _p, want = seeded_shards(4, 2, 5, 1024, 3)
    planes = geo.repair_planes(2)
    ids = [0, 1, 3, 4, 5]
    stack = np.stack([
        want[c].reshape(3, 8, 128)[:, planes].reshape(3, -1) for c in ids
    ])
    pc = _dispatch_counters()
    # (the first call traces the program, and the inner decode's own
    # dispatch site counts once at trace time)
    codec.repair_window(2, ids, stack)
    before = pc.dump()
    codec.repair_window(2, ids, stack)
    after = pc.dump()
    assert after["dispatches"] == before["dispatches"] + 1
    moved = {
        key: after[key] - before[key]
        for key in ("clay_kernel_bytes", "clay_fallback_bytes")
    }
    # sub-chunks of 128 B are lane-aligned: the kernels take them
    assert moved == {"clay_kernel_bytes": stack.nbytes, "clay_fallback_bytes": 0}
    # with the kernels gated off it is another program, the same bytes
    with config.override(ec_clay_kernels=False):
        before = pc.dump()["clay_fallback_bytes"]
        out = codec.repair_window(2, ids, stack)
        assert pc.dump()["clay_fallback_bytes"] == before + stack.nbytes
    assert np.array_equal(out.reshape(-1), want[2])


def test_on_the_chip_the_first_repair_compiles_every_lost_chunks_program(
    monkeypatch,
):
    from ceph_tpu.utils import platform

    codec, _ = make(4, 2, 5)
    chunk, stripes = 2048, 8  # a shape no other test compiles
    _obj, _p, want = seeded_shards(4, 2, 5, chunk, stripes)
    geo = ref.Geometry(4, 2, 5)
    planes = geo.repair_planes(1)
    ids = [0, 2, 3, 4, 5]
    stack = np.stack([
        want[c].reshape(stripes, 8, 256)[:, planes].reshape(stripes, -1)
        for c in ids
    ])
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    monkeypatch.setattr(platform, "pallas_interpret", lambda: True)
    out = codec.repair_window(1, ids, stack)
    assert np.array_equal(out.reshape(-1), want[1])
    shape = (stripes, stack.shape[2])
    compiled = {
        key[2] for key in clay_mod._PROGRAMS
        if key[1] == "repair" and key[4:6] == shape
    }
    assert compiled == set(range(6))
    assert clay_mod._WARMED[(codec._signature(),) + shape].is_set()


def test_the_repair_programs_device_events_have_the_pinned_names():
    """The cell's file finds the program by ``jit_clay_repair`` (the
    modules line) and its inner decode by ``%_apply_tiled.``; the two
    pair-transform kernels carry names of their own."""
    from ceph_tpu.ops import clay_kernels
    from ceph_tpu.ops import pallas_encode as pe

    codec, _ = make(8, 4, 11)
    program = codec._repair_program(
        2, tuple(c for c in range(12) if c != 2), 32, 4096
    )
    text = program.fn.trace(
        jax.ShapeDtypeStruct((11, 32, 4096), jnp.uint8)
    ).lower(lowering_platforms=("cpu",)).as_text()
    (module,) = re.findall(r"^module @(\S+)", text, flags=re.M)
    assert module == "jit_clay_repair"
    assert clay_kernels.UNCOUPLED_KERNEL_NAME == "_clay_uncoupled"
    assert clay_kernels.COUPLE_KERNEL_NAME == "_clay_couple"
    assert pe.APPLY_KERNEL_NAME == "_apply_tiled"


# ------------------------------------------------- through the pipeline
@pytest.mark.parametrize("ragged", [0, 2048 + 17])
def test_a_degraded_read_repairs_through_packed_sub_reads(ragged):
    k, m, d, chunk, stripes = 4, 2, 5, 1024, 3
    codec, _ = make(k, m, d)
    obj, _pool, want = seeded_shards(k, m, d, chunk, stripes, ragged=ragged)
    stripes = want.shape[1] // chunk  # ragged: the last stripe is short
    sinfo = StripeInfo(k, m, k * chunk)
    backend = ShardBackend({s: MemStore(f"osd.{s}") for s in range(k + m)})
    for s in range(k + m):
        size = sinfo.object_size_to_shard_size(len(obj), s)
        backend.stores[s].queue_transactions(
            Transaction().write("obj", 0, want[s][:size].tobytes())
        )
    reads = ReadPipeline(sinfo, codec, backend, lambda oid: len(obj))
    asked = []
    read_shard = backend.read_shard

    def spy(shard, oid, extents, select=None):
        asked.append((shard, list(extents), select))
        return read_shard(shard, oid, extents, select)

    backend.read_shard = spy
    backend.down_shards.add(2)
    before = reads.perf.dump()
    got = {}
    reads.submit("obj", 0, len(obj), lambda op: got.update(op=op))
    op = got["op"]
    assert op.error is None and op.data == obj
    packed = [a for a in asked if a[2] is not None]
    assert sorted(a[0] for a in packed) == [4, 5]
    assert all(a[1] == [(0, stripes * chunk)] for a in packed)
    after = reads.perf.dump()
    moved = {key: after[key] - before[key] for key in (
        "repair_ops", "repair_helper_bytes", "repair_rebuilt_bytes",
        "subread_extents",
    )}
    assert moved["repair_ops"] == 1
    assert moved["repair_rebuilt_bytes"] == stripes * chunk
    assert moved["repair_helper_bytes"] == d * stripes * chunk // 2
    # three whole data shards, two packed parity helpers of 1-4 runs
    runs = len(codec.get_repair_subchunks(2))
    assert moved["subread_extents"] == 3 + 2 * (1 + runs)
    assert after["repair_seconds"] > before["repair_seconds"]
    assert after["repair_gather_seconds"] > before["repair_gather_seconds"]
