"""The ISA Cauchy (10,4) pool on the served path against its plain
reference (``benchmark/reference/isa_cauchy.py``), at small sizes: a
pool booted from its whole profile is the code that was asked for,
what the OSDs store of an object that ends inside a stripe is the
reference's rows, each at its own length, the stored cumulative crc32c
is the reference's over the whole code word, and a read through a dead
OSD gives the object back whichever kind of shard the OSD held. Beside
it: every plugin of the registry boots from a whole profile, and the
counters that say what a geometry adds to a write. Every comparison is
of bytes and exact: GF(2^8) has no rounding."""

from __future__ import annotations

import json

import numpy as np
import pytest

from benchmark import counters
from benchmark.reference import crc32c, gf256
from benchmark.reference import isa_cauchy as ref
from ceph_tpu.codecs import registry
from ceph_tpu.loadgen import LoadCluster
from ceph_tpu.utils.config import config

K, M, CHUNK = 10, 4, 4096
PROFILE = {"plugin": "isa", "technique": "cauchy", "k": "10", "m": "4"}
#: one whole stripe; 2.6 stripes (the cell's rehearsal size); 3.12
#: stripes ending inside a chunk; a chunk and a byte; a byte
SIZES = [40_960, 106_496, 127_880, 4_097, 1]
FORMS = ["profile", "keywords"]
HINFO_ATTR = "hinfo_key"
CRC_SEED = 0xFFFFFFFF


def boot(form: str, **more) -> LoadCluster:
    """The pool through ``profile=`` or through the keyword form: the
    two have to be one pool."""
    if form == "profile":
        return LoadCluster(
            n_osds=14, chunk_size=CHUNK, profile=dict(PROFILE), **more
        )
    return LoadCluster(
        n_osds=14, chunk_size=CHUNK, k=K, m=M, plugin="isa",
        technique="cauchy", **more
    )


def seeded(size: int) -> bytes:
    return bytes(np.random.default_rng([45, size]).integers(
        0, 256, size, np.uint8
    ))


def write_all(cluster: LoadCluster) -> dict[int, bytes]:
    objects = {size: seeded(size) for size in SIZES}
    for size, data in objects.items():
        cluster.io.write(f"obj{size}", data)
    return objects


def stored(cluster: LoadCluster, oid: str) -> dict[int, tuple]:
    """position in the acting set -> (the store's bytes, its HashInfo),
    read from the OSDs' stores and not through the client."""
    acting = cluster.mon.osdmap.object_to_acting(cluster.pool, oid)
    out = {}
    for position, osd in enumerate(acting):
        store = cluster.stores[osd]
        for key in store.list_objects():
            name, sep, shard = key.rpartition("#s")
            if sep and name.partition(":")[2] == oid and int(shard) == position:
                out[position] = (
                    np.frombuffer(store.read(key), np.uint8),
                    json.loads(store.getattr(key, HINFO_ATTR).decode()),
                )
    return out


def moved(before: dict, patterns: list[str]) -> float:
    return counters.total(
        counters.delta(before, counters.snapshot()), patterns
    )


# ------------------------------------------------- the healthy pool
@pytest.fixture(scope="module", params=FORMS)
def healthy(request):
    cluster = boot(request.param, pg_num=4)
    try:
        yield cluster, write_all(cluster)
    finally:
        cluster.shutdown()


def test_the_pools_codec_returns_every_key_of_its_profile(healthy):
    cluster, _ = healthy
    codec = cluster.codec()
    assert codec.profile == PROFILE
    assert (cluster.k, cluster.m) == (K, M)
    assert (codec.get_data_chunk_count(), codec.get_chunk_count()) == (K, 14)


@pytest.mark.parametrize("size", SIZES)
def test_stored_shards_are_the_references_each_at_its_own_length(
    healthy, size
):
    cluster, objects = healthy
    word = ref.shards_of(objects[size], K, M, CHUNK)
    lengths = ref.stored_lengths(size, K, M, CHUNK)
    got = stored(cluster, f"obj{size}")
    assert sorted(got) == list(range(K + M))
    for position, (data, _hinfo) in got.items():
        assert data.shape == (lengths[position],), position
        assert np.array_equal(data, word[position][: lengths[position]]), (
            position
        )
    if size % (K * CHUNK):
        # the object ends inside a stripe: the data shards past its end
        # are a chunk shorter than the parity shards
        assert lengths[K - 1] < lengths[K]


@pytest.mark.parametrize("size", SIZES)
def test_stored_crc_is_the_references_over_the_whole_code_word(
    healthy, size
):
    cluster, objects = healthy
    word = ref.shards_of(objects[size], K, M, CHUNK)
    want = crc32c.crc32c_rows(CRC_SEED, word).tolist()
    for position, (_data, hinfo) in stored(cluster, f"obj{size}").items():
        # one total for all shards, the short ones too, as upstream's
        assert hinfo["total_chunk_size"] == word.shape[1], position
        assert [int(v) for v in hinfo["hashes"]] == want, position


@pytest.mark.parametrize("size", SIZES)
def test_the_object_reads_back(healthy, size):
    cluster, objects = healthy
    assert bytes(cluster.io.read(f"obj{size}")) == objects[size]


# ---------------------------------------------- one OSD down, by shard
#: the position in the acting set of the OSD that is killed: a data
#: shard that is long in the cell's 1 MiB object (and the PG's primary),
#: one that is short there, a parity shard
DOWN = {"long-data-shard": 0, "short-data-shard": 7, "parity-shard": 12}


@pytest.fixture(scope="module", params=sorted(DOWN))
def degraded(request):
    """One PG, so that one OSD holds the same position of every
    object; the objects are written healthy, then the OSD dies."""
    position = DOWN[request.param]
    cluster = boot("profile", pg_num=1)
    try:
        objects = write_all(cluster)
        acting = cluster.mon.osdmap.object_to_acting(cluster.pool, "obj1")
        cluster.kill(acting[position])
        yield cluster, objects, position
    finally:
        cluster.shutdown()


@pytest.mark.parametrize("size", SIZES)
def test_a_read_through_the_dead_osd_is_bit_exact(degraded, size):
    cluster, objects, position = degraded
    before = counters.snapshot()
    assert bytes(cluster.io.read(f"obj{size}")) == objects[size]
    decoded = moved(before, ["osd.*.read:reconstruct_ops"])
    lost = ref.stored_lengths(size, K, M, CHUNK)[position]
    if position < K and lost:
        # the read wanted bytes the dead OSD held (a read that the
        # interval change bounced is decoded again on the resend)
        assert decoded >= 1
    else:
        assert decoded == 0  # a parity shard, or a shard with no byte


# ------------------------------------------ the code that was asked for
def _stripe_parity(codec) -> tuple[np.ndarray, np.ndarray]:
    data = np.random.default_rng(104).integers(
        0, 256, (K, CHUNK), np.uint8
    )
    parity = codec.encode_chunks({i: data[i] for i in range(K)})
    return data, np.stack([np.asarray(parity[K + j]) for j in range(M)])


@pytest.mark.parametrize("form", FORMS)
def test_technique_cauchy_is_the_cauchy_matrix(form):
    """Fails on the parent's keyword form, which kept ``technique``
    for jerasure alone and served ISA's Vandermonde code here."""
    cluster = boot(form)
    try:
        codec = cluster.codec()
    finally:
        cluster.shutdown()
    data, parity = _stripe_parity(codec)
    assert np.array_equal(
        parity, gf256.apply_matrix(ref.coding_matrix(K, M), data)
    )
    van = registry.factory("isa", {**PROFILE, "technique": "reed_sol_van"})
    assert not np.array_equal(parity, _stripe_parity(van)[1])


def test_technique_not_given_is_the_plugins_own_default():
    cluster = LoadCluster(n_osds=14, k=K, m=M, plugin="isa", chunk_size=CHUNK)
    try:
        codec = cluster.codec()
    finally:
        cluster.shutdown()
    assert "technique" not in codec.profile
    van = registry.factory("isa", {**PROFILE, "technique": "reed_sol_van"})
    assert np.array_equal(_stripe_parity(codec)[1], _stripe_parity(van)[1])


def test_a_key_the_plugin_does_not_know_is_the_codecs_to_refuse():
    """Through the monitor's command, which builds the codec."""
    from ceph_tpu.cluster.monitor import CommandError

    with pytest.raises(CommandError, match="unknown isa technique"):
        LoadCluster(n_osds=14, k=K, m=M, plugin="isa", technique="cauchy_good")
    with pytest.raises(CommandError, match="unknown isa technique"):
        LoadCluster(n_osds=14, profile={**PROFILE, "technique": "liberation"})


# ------------------------------- every plugin, from a whole profile
#: name -> (profile, chunks): jerasure and isa by both techniques,
#: Clay, the SHEC pool ``test_cluster_plugins.py`` serves, LRC whose
#: k=4 m=2 l=3 is eight chunks (two local parities)
PLUGINS = {
    "jerasure-reed_sol_van": (
        {"plugin": "jerasure", "technique": "reed_sol_van",
         "k": "4", "m": "2"}, 6),
    "jerasure-cauchy_good": (
        {"plugin": "jerasure", "technique": "cauchy_good",
         "k": "4", "m": "2"}, 6),
    "isa-reed_sol_van": (
        {"plugin": "isa", "technique": "reed_sol_van", "k": "4", "m": "2"},
        6),
    "isa-cauchy": (
        {"plugin": "isa", "technique": "cauchy", "k": "4", "m": "2"}, 6),
    "clay": ({"plugin": "clay", "k": "4", "m": "2", "d": "5"}, 6),
    "shec": ({"plugin": "shec", "k": "3", "m": "2", "c": "1"}, 5),
    "lrc": ({"plugin": "lrc", "k": "4", "m": "2", "l": "3"}, 8),
}


@pytest.fixture(scope="module", params=sorted(PLUGINS))
def plugin_pool(request):
    profile, chunks = PLUGINS[request.param]
    cluster = LoadCluster(
        n_osds=chunks, chunk_size=1024, pg_num=1, profile=dict(profile)
    )
    try:
        yield cluster, profile, chunks
    finally:
        cluster.shutdown()


def test_a_plugin_boots_from_its_whole_profile(plugin_pool):
    cluster, profile, chunks = plugin_pool
    codec = cluster.codec()
    assert codec.profile == profile
    assert codec.get_chunk_count() == chunks == cluster.k + cluster.m
    assert cluster.k == codec.get_data_chunk_count() == int(profile["k"])


def test_a_plugins_pool_serves_healthy_and_with_one_osd_down(plugin_pool):
    cluster, _profile, _chunks = plugin_pool
    data = seeded(9_000)
    cluster.io.write("obj", data)
    assert bytes(cluster.io.read("obj")) == data
    acting = cluster.mon.osdmap.object_to_acting(cluster.pool, "obj")
    cluster.kill(acting[1])
    assert bytes(cluster.io.read("obj")) == data


def test_a_cluster_smaller_than_the_codes_chunk_count_is_refused():
    profile, chunks = PLUGINS["lrc"]
    assert chunks == 8 > int(profile["k"]) + int(profile["m"])
    with pytest.raises(ValueError, match="8 OSDs"):
        LoadCluster(n_osds=7, chunk_size=1024, profile=dict(profile))


def test_a_profile_without_plugin_is_the_default_plugins():
    """The monitor takes one (``erasure_code_default_plugin``); so does
    the cluster it boots."""
    cluster = LoadCluster(n_osds=3, pg_num=1, profile={"k": "2", "m": "1"})
    try:
        default = registry.factory(
            config.get("erasure_code_default_plugin"), {"k": "2", "m": "1"}
        )
        assert type(cluster.codec()) is type(default)
        assert (cluster.k, cluster.m) == (2, 1)
        data = seeded(5_000)
        cluster.io.write("obj", data)
        assert bytes(cluster.io.read("obj")) == data
    finally:
        cluster.shutdown()


@pytest.mark.parametrize(
    "beside",
    [{"k": 10}, {"m": 4}, {"plugin": "isa"}, {"technique": "cauchy"},
     {"d": 13}, {"k": 3}, {"m": 2}, {"plugin": "jerasure"}],
    ids=lambda kw: "-".join(f"{key}={value}" for key, value in kw.items()),
)
def test_a_whole_profile_is_given_alone(beside):
    with pytest.raises(ValueError, match="whole profile"):
        LoadCluster(n_osds=14, profile=dict(PROFILE), **beside)


@pytest.mark.parametrize(
    "argv,want",
    [
        (["--plugin", "isa", "-P", "technique=cauchy"],
         {"plugin": "isa", "technique": "cauchy", "k": "3", "m": "2"}),
        (["-P", "k=2", "-P", "m=1"],
         {"plugin": "jerasure", "k": "2", "m": "1"}),
        ([], {"plugin": "jerasure", "k": "3", "m": "2"}),
    ],
    ids=["plugin-and-a-key", "k-and-m", "nothing"],
)
def test_bench_cli_loadgen_lays_its_keys_over_k3_m2(monkeypatch, argv, want):
    from ceph_tpu import bench_cli, loadgen

    pools = []

    class Seen(LoadCluster):
        def __init__(self, **kw):
            super().__init__(**kw)
            pools.append(self.codec().profile)

    monkeypatch.setattr(loadgen, "LoadCluster", Seen)
    args = bench_cli.parse_args([
        "loadgen", "--osds", "5", "--chunk-size", "1024", "--objects", "4",
        "--object-size", "4096", "--queue-depth", "2", "--ops", "8", *argv,
    ])
    elapsed, kib = bench_cli.run(args)
    assert elapsed > 0 and kib > 0
    assert pools == [want]


def test_the_keyword_form_passes_clays_d_and_checks_its_sub_chunks():
    with pytest.raises(ValueError, match="sub-chunks"):
        LoadCluster(n_osds=6, k=4, m=2, plugin="clay", d=5, chunk_size=1028)
    cluster = LoadCluster(
        n_osds=6, k=4, m=2, plugin="clay", d=5, chunk_size=1024, pg_num=1
    )
    try:
        assert cluster.codec().profile == {
            "plugin": "clay", "k": "4", "m": "2", "d": "5",
        }
    finally:
        cluster.shutdown()


# ------------------------------- what the geometry adds, in counters
@pytest.mark.parametrize(
    "size,short_writes",
    [(106_496, 1), (4_097, 1), (2 * K * CHUNK, 0)],
    ids=["2.6-stripes", "a-chunk-and-a-byte", "whole-stripes"],
)
def test_a_write_that_ends_inside_a_stripe_is_counted(size, short_writes):
    cluster = boot("profile", pg_num=1)
    try:
        before = counters.snapshot()
        with config.override(ec_fused_csum_interpret=True):
            cluster.io.write("obj", seeded(size))
        assert moved(before, ["osd.*.rmw:encode_ops"]) == 1
        assert moved(before, ["osd.*.rmw:short_stripe_writes"]) == (
            short_writes
        )
    finally:
        cluster.shutdown()


@pytest.mark.parametrize(
    "k,tail_chunks",
    [(k, t) for k in (10, 8) for t in range(k + 1)],
    ids=lambda v: str(v),
)
def test_stored_lengths_at_every_remainder_of_a_stripe(k, tail_chunks):
    """``tail_chunks`` chunks of the last stripe hold a byte (0: the
    object is whole stripes): the other data shards are stored a chunk
    shorter than the parities. Checked a byte inside and at the end of
    the last chunk, against the reference's stored lengths."""
    from ceph_tpu.pipeline.stripe import StripeInfo

    sinfo = StripeInfo(k, M, k * CHUNK)
    whole = 3 * k * CHUNK
    ends = [whole] if not tail_chunks else [
        whole + (tail_chunks - 1) * CHUNK + 1, whole + tail_chunks * CHUNK,
    ]
    for size in ends:
        lengths = ref.stored_lengths(size, k, M, CHUNK)
        assert [
            sinfo.object_size_to_exact_shard_size(size, shard)
            for shard in range(k + M)
        ] == lengths, size
        short = 0 if size % (k * CHUNK) == 0 else k - tail_chunks
        assert sum(n <= lengths[k] - CHUNK for n in lengths[:k]) == short
