"""The raw-bytes HashInfo append (PR 48): all k+m shards hashed
together, ONE device checksum call and one fetch an append over the
size test and none under it, bit for bit the host's crc32c chained per
shard and ``append_block_csums`` over the same bytes. On the CPU the
device route is the einsum fold; ``pallas`` walks the Pallas fold in
the interpreter (``platform.on_tpu`` patched, as the verify notes say).
Then the counters that say it engaged, through ``RMWPipeline`` and
through a ``LoadCluster`` at (8,4) on a mesh of the CPU's virtual
devices, held against the benchmark's plain reference."""

from __future__ import annotations

import json

import numpy as np
import pytest

from benchmark import counters
from benchmark.reference import crc32c as ref_crc
from benchmark.reference import rs_vandermonde as ref
from ceph_tpu.checksum import backends, crc32c_stream, crc32c_streams
from ceph_tpu.checksum.host import crc32c as host_crc
from ceph_tpu.pipeline.hashinfo import SEED, HashInfo
from ceph_tpu.utils import config, platform

#: the size test, lowered so that the interpreter's folds stay small;
#: streams under 256 KiB hash in 4 KiB blocks
LIMIT = 16384
CB = 4096
SHARDS = [3, 6, 12, 14]
#: under the size test; at it (4 whole blocks: 12, 24, 48, 56 rows all
#: tile); over it with a ragged tail (5 blocks: 15, 30 and 70 rows need
#: the pad, 60 do not)
SIZES = {"under": 12288, "at": LIMIT, "over-ragged": 5 * CB + 123}
ROUTES = ["einsum", "pallas"]


@pytest.fixture
def route(request, monkeypatch):
    if request.param == "pallas":
        monkeypatch.setattr(platform, "on_tpu", lambda: True)
    with config.override(csum_device_min_bytes=LIMIT):
        yield request.param


def shard_bytes(n_shards: int, size: int, salt: int = 0) -> dict:
    rng = np.random.default_rng([48, n_shards, size, salt])
    return {
        s: rng.integers(0, 256, size, np.uint8) for s in range(n_shards)
    }


def oracle(hashes: list, bufs: dict) -> list:
    """The host's crc32c chained per shard."""
    out = list(hashes)
    for shard, buf in bufs.items():
        out[shard] = host_crc(out[shard], bytes(buf))
    return out


def device_calls() -> int:
    c = backends.counts()
    return c.get("einsum", 0) + c.get("pallas", 0)


@pytest.mark.parametrize("route", ROUTES, indirect=True)
@pytest.mark.parametrize("size", SIZES, ids=list(SIZES))
@pytest.mark.parametrize("n_shards", SHARDS)
def test_a_batched_append_is_the_host_oracle(route, n_shards, size):
    """Two appends in a row (the second on running hashes that are no
    seed), each one device call over the size test and none under."""
    n = SIZES[size]
    hi = HashInfo(n_shards)
    want = [SEED] * n_shards
    for step in range(2):
        bufs = shard_bytes(n_shards, n, step)
        want = oracle(want, bufs)
        calls0, counts0 = device_calls(), backends.counts()
        made = hi.append(step * n, bufs)
        assert hi.cumulative_shard_hashes == want
        assert all(type(h) is int for h in hi.cumulative_shard_hashes)
        assert hi.get_total_chunk_size() == (step + 1) * n
        if n < LIMIT:
            assert made == 0 and device_calls() == calls0
            assert backends.counts()["host"] == (
                counts0.get("host", 0) + n_shards
            )
        else:
            assert made == 1 and device_calls() == calls0 + 1
            assert backends.counts()[route] == counts0.get(route, 0) + 1
            assert backends.counts().get("pallas_fallback", 0) == (
                counts0.get("pallas_fallback", 0)
            )
    assert json.loads(hi.to_bytes())["hashes"] == want


@pytest.mark.parametrize("route", ROUTES, indirect=True)
@pytest.mark.parametrize("n_shards", SHARDS)
@pytest.mark.parametrize("blocks", [4, 5])
def test_it_equals_append_block_csums_over_the_same_bytes(
    route, n_shards, blocks
):
    first = shard_bytes(n_shards, LIMIT, 7)
    bufs = shard_bytes(n_shards, blocks * CB, 8)
    raw, folded = HashInfo(n_shards), HashInfo(n_shards)
    for hi in (raw, folded):
        hi.append(0, first)  # running hashes that are no seed
    assert raw.append(LIMIT, bufs) == 1
    words = folded.append_block_csums(
        LIMIT,
        {
            s: [host_crc(0, bytes(b[i * CB:(i + 1) * CB]))
                for i in range(blocks)]
            for s, b in bufs.items()
        },
        CB,
    )
    assert words == n_shards * blocks
    assert raw == folded


def test_the_default_size_test_and_64k_blocks():
    """Nothing lowered: a shard stream of 256 KiB (the default
    ``csum_device_min_bytes``) and a tail goes to the device in 64 KiB
    blocks, 3 shards x 4 blocks in one call; a byte less stays on the
    host."""
    assert int(config.get("csum_device_min_bytes")) == 1 << 18
    for n, calls in (((1 << 18) + 5, 1), ((1 << 18) - 1, 0)):
        bufs = shard_bytes(3, n)
        hi = HashInfo(3)
        before = device_calls()
        assert hi.append(0, bufs) == calls
        assert device_calls() == before + calls
        assert hi.cumulative_shard_hashes == oracle([SEED] * 3, bufs)


AS = {
    "bytes": bytes,
    "bytearray": bytearray,
    "memoryview": lambda a: memoryview(bytes(a)),
    "read-only array": lambda a: _frozen(a),
    "strided array": lambda a: np.repeat(a, 2)[::2],
}


def _frozen(a):
    a = a.copy()
    a.flags.writeable = False
    return a


@pytest.mark.parametrize("route", ROUTES, indirect=True)
@pytest.mark.parametrize("held", AS)
def test_any_byte_buffer_in(route, held):
    bufs = shard_bytes(6, SIZES["over-ragged"])
    given = {s: AS[held](b) for s, b in bufs.items()}
    if held == "strided array":
        assert not given[0].flags.c_contiguous
    hi = HashInfo(6)
    assert hi.append(0, given) == 1
    assert hi.cumulative_shard_hashes == oracle([SEED] * 6, bufs)
    assert hi.get_total_chunk_size() == SIZES["over-ragged"]


@pytest.mark.parametrize("size", SIZES, ids=list(SIZES))
def test_what_is_refused_stays_refused(size):
    n = SIZES[size]
    hi = HashInfo(3)
    with config.override(csum_device_min_bytes=LIMIT):
        hi.append(0, shard_bytes(3, n))
        kept = (hi.get_total_chunk_size(), list(hi.cumulative_shard_hashes))
        with pytest.raises(TypeError):
            hi.append(n, {0: np.zeros(n, np.int32)})
        with pytest.raises(TypeError):
            hi.append(n, {0: np.zeros(n, np.float32)})
        with pytest.raises(ValueError):
            hi.append(
                n, {0: np.zeros(n, np.uint8), 1: np.zeros(n + 1, np.uint8)}
            )
        with pytest.raises(ValueError):
            hi.append(n + 1, shard_bytes(3, n))
        assert hi.append(n, {}) == 0
    assert kept == (hi.get_total_chunk_size(), hi.cumulative_shard_hashes)


@pytest.mark.parametrize("route", ROUTES, indirect=True)
@pytest.mark.parametrize("n", [4 * CB + 123, 5 * CB, 8 * CB, 9 * CB + 1])
def test_one_stream_keeps_its_contract(route, n):
    """``crc32c_stream`` is ``crc32c_streams`` of one: chained across
    pieces as deep scrub's stride loop does, a count the fold cannot
    tile (5, 9 blocks) padded and served by the same route."""
    buf = shard_bytes(1, n)[0]
    want = host_crc(SEED, bytes(buf))
    before, counts0 = device_calls(), backends.counts()
    assert crc32c_stream(buf) == want
    assert device_calls() == before + 1
    assert backends.counts().get("pallas_fallback", 0) == (
        counts0.get("pallas_fallback", 0)
    )
    mid = crc32c_stream(bytes(buf[:CB]), SEED)  # under: the host's
    assert crc32c_stream(memoryview(bytes(buf[CB:])), mid) == want
    regs, calls = crc32c_streams([SEED, mid], [buf[:n - CB], buf[CB:]])
    assert calls == (1 if n - CB >= LIMIT else 0)
    assert regs == [host_crc(SEED, bytes(buf[:n - CB])), want]


# ------------------------------------------------- the pipeline's counters
STREAM_KEYS = (
    "hinfo_streams", "hinfo_stream_calls", "hinfo_stream_bytes",
    "hinfo_stream_seconds",
)


@pytest.mark.parametrize("limit,calls", [(1 << 18, 0), (8192, 1)],
                         ids=["host", "device"])
def test_the_pipeline_counts_its_raw_appends(limit, calls):
    """Two appends of one stripe each with no kernel csums are two raw
    appends of (k+m) x chunk bytes, each one device call where a
    shard's stream passes the size test; an overwrite clears the hashes
    and appends nothing; the fused route appends no raw bytes."""
    from ceph_tpu.codecs.registry import registry
    from ceph_tpu.pipeline.rmw import RMWPipeline, ShardBackend
    from ceph_tpu.pipeline.stripe import StripeInfo
    from ceph_tpu.store.memstore import MemStore

    k, m, chunk = 4, 2, 8192
    data = np.random.default_rng(48).integers(
        0, 256, k * chunk, np.uint8
    ).tobytes()

    def pipeline():
        return RMWPipeline(
            StripeInfo(k, m, k * chunk),
            registry.factory("jerasure", {
                "k": str(k), "m": str(m), "technique": "reed_sol_van",
            }),
            ShardBackend({i: MemStore() for i in range(k + m)}),
        )

    with config.override(
        ec_fused_csum_interpret=False, ec_host_dispatch_bytes=0,
        csum_device_min_bytes=limit,
    ):
        pipe = pipeline()
        before = device_calls()
        pipe.submit("obj", 0, data)
        pipe.submit("obj", len(data), data)
        appended = pipe.perf.dump()
        assert device_calls() == before + 2 * calls
        want = ref_crc.crc32c_rows(
            SEED, ref.shards_of(data + data, k, m, chunk)
        ).tolist()
        assert pipe.hinfo("obj").cumulative_shard_hashes == want
        pipe.submit("obj", 0, data)  # an overwrite
        after = pipe.perf.dump()
        hinfo = pipe.hinfo("obj")
    assert appended["hinfo_streams"] == 2
    assert appended["hinfo_stream_calls"] == 2 * calls
    assert appended["hinfo_stream_bytes"] == 2 * (k + m) * chunk
    assert 0 < appended["hinfo_stream_seconds"] < appended["encode_seconds"]
    assert appended["hinfo_folds"] == 0
    for key in STREAM_KEYS:
        assert after[key] == appended[key]
    assert not any(hinfo.cumulative_shard_hashes[s] != SEED
                   for s in range(k + m))  # the overwrite cleared them
    with config.override(
        ec_fused_csum_interpret=True, ec_host_dispatch_bytes=0
    ):
        fused = pipeline()
        fused.submit("obj", 0, data + data)
        dump = fused.perf.dump()
        assert [dump[key] for key in STREAM_KEYS] == [0, 0, 0, 0]
        assert dump["hinfo_folds"] == 1
        assert fused.hinfo("obj").cumulative_shard_hashes == want


# ------------------------------------------------- a pool on a mesh
K, M, CHUNK, OBJECT = 8, 4, 4096, 4 << 20
HINFO_ATTR = "hinfo_key"


def stored_hinfos(cluster, oid: str) -> dict[int, dict]:
    acting = cluster.mon.osdmap.object_to_acting(cluster.pool, oid)
    out = {}
    for position, osd in enumerate(acting):
        store = cluster.stores[osd]
        for key in store.list_objects():
            name, sep, shard = key.rpartition("#s")
            if sep and name.partition(":")[2] == oid and (
                int(shard) == position
            ):
                out[position] = json.loads(
                    store.getattr(key, HINFO_ATTR).decode()
                )
    return out


def test_a_mesh_pool_hashes_a_4m_write_in_one_device_call():
    """The benchmark's mesh cell in small: EC(8,4) over a 4-device
    mesh, 4 MiB objects. No kernel csums come back from the mesh
    program, so every write appends raw bytes: 12 shards of 512 KiB,
    one checksum call of [96, 65536] a write (the parent made 12), and
    what the stores hold is the reference's crc32c per shard."""
    from ceph_tpu.loadgen import LoadCluster

    objects = {
        f"m{i}": bytes(np.random.default_rng([48, i]).integers(
            0, 256, OBJECT, np.uint8
        ))
        for i in range(2)
    }
    cluster = LoadCluster(
        n_osds=K + M, k=K, m=M, pg_num=2, chunk_size=CHUNK,
        pool="meshpool", use_mesh=True, mesh_devices=4,
        client_op_timeout=60.0,
    )
    try:
        before, calls0 = counters.snapshot(), device_calls()
        for oid, data in objects.items():
            cluster.io.write(oid, data)
        moved = counters.delta(before, counters.snapshot())
        assert device_calls() == calls0 + len(objects)
        for oid, data in objects.items():
            assert bytes(cluster.io.read(oid)) == data
            word = ref.shards_of(data, K, M, CHUNK)
            want = ref_crc.crc32c_rows(SEED, word).tolist()
            got = stored_hinfos(cluster, oid)
            assert sorted(got) == list(range(K + M))
            for position, hinfo in got.items():
                assert hinfo["total_chunk_size"] == OBJECT // K
                assert [int(v) for v in hinfo["hashes"]] == want, position
    finally:
        cluster.shutdown()
    total = lambda key: counters.total(moved, [f"osd.*.rmw:{key}"])
    assert total("hinfo_streams") == len(objects)
    # csum_calls_per_append, as benchmark/metrics/ reads it
    assert total("hinfo_stream_calls") / total("hinfo_streams") == 1.0
    assert total("hinfo_stream_bytes") == len(objects) * (K + M) * (
        OBJECT // K
    )
    assert total("hinfo_folds") == 0
    assert counters.total(moved, ["ec_dispatch:mesh_encode"]) > 0
