"""Every EC plugin family through the FULL cluster stack (monitor
profile validation → pool → client IO over sockets → degraded read) —
the test-erasure-code-plugins.sh tier (qa/standalone/erasure-code/
test-erasure-code-plugins.sh boots a real cluster per plugin)."""

import zlib

import numpy as np
import pytest

from ceph_tpu.cluster import Monitor, OSDDaemon, RadosClient

PROFILES = {
    "jerasure_rs": {"plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "3", "m": "2"},
    "jerasure_cauchy": {"plugin": "jerasure", "technique": "cauchy_good",
                        "k": "3", "m": "2"},
    "isa": {"plugin": "isa", "k": "3", "m": "2"},
    "lrc": {"plugin": "lrc", "k": "4", "m": "2", "l": "3"},
    "shec": {"plugin": "shec", "k": "3", "m": "2", "c": "1"},
    "clay": {"plugin": "clay", "k": "3", "m": "2"},
}


@pytest.fixture(scope="module")
def cluster():
    mon = Monitor()
    daemons = []
    n = 9  # lrc k=4,m=2,l=3 expands to more chunks
    for i in range(n):
        mon.osd_crush_add(i)
    for i in range(n):
        d = OSDDaemon(i, mon, chunk_size=1024, tick_period=0)
        d.start()
        daemons.append(d)
    client = RadosClient(mon, backoff=0.02)
    yield mon, daemons, client
    client.shutdown()
    for d in daemons:
        d.stop()


@pytest.mark.parametrize("name", sorted(PROFILES))
def test_plugin_through_cluster(cluster, name):
    mon, daemons, client = cluster
    profile = PROFILES[name]
    mon.osd_erasure_code_profile_set(name, profile)
    pool = f"pool_{name}"
    mon.osd_pool_create(pool, 4, name)
    spec = mon.osdmap.pools[pool]
    assert spec.plugin == profile["plugin"]
    io = client.open_ioctx(pool)
    data = np.random.default_rng(zlib.crc32(name.encode())).integers(
        0, 256, 9_000, dtype=np.uint8
    ).tobytes()
    io.write("obj", data)
    assert io.read("obj") == data
    # degraded: hole one non-primary member for THIS pool's object
    acting = mon.osdmap.object_to_acting(pool, "obj")
    victim = acting[-1]
    mon.osd_down(victim)
    try:
        assert io.read("obj") == data
    finally:
        mon.osd_boot(victim, daemons[victim].addr)


@pytest.mark.parametrize("name", sorted(PROFILES))
def test_plugin_profile_through_loadcluster(name):
    """The same profiles, whole, through ``LoadCluster(profile=...)``:
    every key reaches the pool's codec, the cluster is sized by the
    codec's chunk count, and a key left out stays left out (the
    plugin's own default)."""
    from ceph_tpu.codecs import registry
    from ceph_tpu.loadgen import LoadCluster

    profile = PROFILES[name]
    chunks = registry.factory(
        profile["plugin"], dict(profile)
    ).get_chunk_count()
    with pytest.raises(ValueError, match=f"{chunks} OSDs"):
        LoadCluster(n_osds=chunks - 1, profile=dict(profile))
    cluster = LoadCluster(n_osds=chunks, pg_num=2, profile=dict(profile))
    try:
        assert cluster.codec().profile == profile
        assert cluster.k + cluster.m == chunks
        data = bytes(range(256)) * 20
        cluster.io.write("obj", data)
        assert bytes(cluster.io.read("obj")) == data
    finally:
        cluster.shutdown()


@pytest.mark.parametrize(
    "plugin,technique,held",
    [
        ("jerasure", None, "reed_sol_van"),  # written out, as it always was
        ("jerasure", "cauchy_good", "cauchy_good"),
        ("isa", None, None),  # not given: the plugin's own default
        ("isa", "cauchy", "cauchy"),
        ("clay", None, None),
    ],
)
def test_the_keyword_forms_technique_reaches_every_plugin(
    plugin, technique, held
):
    """``LoadCluster(plugin=, technique=)`` kept ``technique`` for
    jerasure alone and dropped it in silence for the others."""
    from ceph_tpu.loadgen import LoadCluster

    cluster = LoadCluster(
        n_osds=5, k=3, m=2, pg_num=2, plugin=plugin, technique=technique
    )
    try:
        assert cluster.codec().profile.get("technique") == held
        data = bytes(range(256)) * 20
        cluster.io.write("obj", data)
        assert bytes(cluster.io.read("obj")) == data
    finally:
        cluster.shutdown()


@pytest.mark.parametrize(
    "plugin,technique",
    [("isa", "cauchy_good"), ("jerasure", "cauchy")],
)
def test_a_technique_the_plugin_does_not_know_is_refused(plugin, technique):
    from ceph_tpu.cluster.monitor import CommandError
    from ceph_tpu.loadgen import LoadCluster

    with pytest.raises(CommandError, match="technique"):
        LoadCluster(n_osds=5, k=3, m=2, plugin=plugin, technique=technique)


@pytest.mark.parametrize("technique", ["single", "multiple"])
def test_shecs_technique_comes_in_its_whole_profile(technique):
    """SHEC needs ``c`` beside k and m, which only a whole profile can
    say; its ``technique`` rides there."""
    from ceph_tpu.loadgen import LoadCluster

    profile = {**PROFILES["shec"], "technique": technique}
    cluster = LoadCluster(n_osds=5, pg_num=2, profile=dict(profile))
    try:
        codec = cluster.codec()
        assert codec.profile == profile
        assert codec.technique == technique
    finally:
        cluster.shutdown()
