"""``writefull`` is one fan-out unless it shrinks the object.

A ``writefull`` whose payload is at least as long as the object (a new
object, a same-size rewrite, a grow) has no tail to cut: it is served
as ONE rmw write that carries the reqid window, so k+m store
transactions and no truncate. Only a true shrink keeps the two halves
(write, then a truncate that carries the window). Both the serial
handler and the coalesced tick take the same decision; the guarantees
(exact payload from any k of k+m, the window on every shard at the
ack, exactly-once on a resend, a seeded window on a new primary) are
pinned here for the one-fan-out form.
"""

import fnmatch
import time

import numpy as np
import pytest

from ceph_tpu.cluster.osd_daemon import REQ_KEY, parse_reqs, shard_key
from ceph_tpu.msg.messages import OSDOp
from ceph_tpu.utils import config, perf_collection

K, M, CHUNK = 3, 2, 2048
STRIPE = K * CHUNK
POOL = "wfpool"
BASE = 2 * STRIPE

#: case -> (bytes the object holds before | None, payload bytes,
#:          truncates an op, store transactions an op)
CASES = {
    "new": (None, BASE, 0, K + M),
    "same": (BASE, BASE, 0, K + M),
    "grow": (BASE, 3 * STRIPE, 0, K + M),
    # stripe-aligned cut: the write, then the truncate
    "shrink": (BASE, STRIPE, 1, 2 * (K + M)),
    # ragged cut: the write, zeros over the boundary stripe's tail,
    # then the truncate
    "shrink-ragged": (BASE, STRIPE - 700, 1, 3 * (K + M)),
    # nothing to write: the truncate half does all the work
    "empty": (BASE, 0, 1, K + M),
}


def payload(n: int, seed: int) -> bytes:
    return np.random.default_rng(0xF0 + seed).integers(
        0, 256, n, np.uint8
    ).tobytes()


def counter(set_glob: str, key: str) -> float:
    return sum(
        values.get(key, 0)
        for name, values in perf_collection.dump().items()
        if fnmatch.fnmatchcase(name, set_glob)
    )


def settle() -> None:
    time.sleep(0.2)  # acks of the op before have all landed


@pytest.fixture(scope="module")
def cluster():
    from ceph_tpu.loadgen import LoadCluster

    c = LoadCluster(
        n_osds=K + M, k=K, m=M, pg_num=1, chunk_size=CHUNK, pool=POOL,
        client_op_timeout=30.0,
    )
    try:
        c.io.write_full("warm", payload(BASE, 99))
        yield c
    finally:
        c.shutdown()


def shard_stores(cluster, oid: str) -> list:
    """(store, key, position) of every stored shard of ``oid``."""
    loc = f"{cluster.mon.osdmap.pools[POOL].pool_id}:{oid}"
    found = []
    for store in cluster.stores.values():
        objects = set(store.list_objects())
        for pos in range(K + M):
            if shard_key(loc, pos) in objects:
                found.append((store, shard_key(loc, pos), pos))
    return found


def assert_stored_exactly(cluster, oid: str, data: bytes) -> None:
    assert cluster.io.stat(oid) == len(data)
    assert cluster.io.read(oid) == data
    shards = shard_stores(cluster, oid)
    assert sorted(pos for _, _, pos in shards) == list(range(K + M))
    # every shard is cut at its exact size: no stale tail anywhere
    sinfo = next(iter(cluster.daemons.values()))._get_pg(POOL, 0).rmw.sinfo
    for store, key, pos in shards:
        assert len(store.read(key)) == (
            sinfo.object_size_to_exact_shard_size(len(data), pos)
        ), (oid, pos)
    # the reqid window of the op is on all k+m shards, the same on each
    windows = {store.getattr(key, REQ_KEY) for store, key, _ in shards}
    assert len(windows) == 1
    assert parse_reqs(windows.pop())[-1][1] == len(data)


@pytest.mark.parametrize("path", ["serial", "coalesced"])
@pytest.mark.parametrize("case", list(CASES))
def test_writefull_fans_out_once_unless_it_shrinks(cluster, case, path):
    before, size, truncates, txns = CASES[case]
    n = 1 if path == "serial" else 3
    oids = [f"{case}-{path}-{i}" for i in range(n)]
    if before is not None:
        for i, oid in enumerate(oids):
            cluster.io.write_full(oid, payload(before, i))
    datas = [payload(size, 10 + i) for i in range(n)]
    settle()
    t0 = counter("osd.*.rmw", "truncate_ops")
    x0 = counter("osd.*.store", "txns")
    c0 = counter("osd.*.coalesce", "op_coalesced")
    if path == "serial":
        assert cluster.io.write_full(oids[0], datas[0]) == size
    else:
        primary = cluster.daemons[cluster.mon.osdmap.pg_primary(POOL, 0)]
        with primary._op_lock:  # queue the run behind one tick
            comps = [
                cluster.io.aio_write_full(oid, data)
                for oid, data in zip(oids, datas)
            ]
            time.sleep(0.2)
        for comp in comps:
            assert comp.wait_for_complete(30).size == size
    settle()
    # the worker was already waiting for the lock with the run's first
    # op in hand: that one is served alone, the rest as one tick
    coalesced = counter("osd.*.coalesce", "op_coalesced") - c0
    assert coalesced in ((0,) if path == "serial" else (n - 1, n))
    assert counter("osd.*.rmw", "truncate_ops") - t0 == n * truncates
    assert counter("osd.*.store", "txns") - x0 == n * txns
    for oid, data in zip(oids, datas):
        assert_stored_exactly(cluster, oid, data)


def test_a_write_queued_on_the_object_counts_as_its_size(cluster):
    """The decision reads the size once every op submitted so far has
    applied, not the size at the last dispatch."""
    primary = cluster.daemons[cluster.mon.osdmap.pg_primary(POOL, 0)]
    cluster.io.write_full("proj", payload(STRIPE, 1))
    pg = primary._get_pg(POOL, 0)
    loc = f"{cluster.mon.osdmap.pools[POOL].pool_id}:proj"
    assert pg.rmw.projected_size(loc) == pg.rmw.object_size(loc) == STRIPE
    op = OSDOp(1, cluster.mon.osdmap.epoch, POOL, loc, "writefull",
               data=payload(STRIPE, 2))
    assert not primary._writefull_cuts(pg, op)
    pg.rmw._projected_sizes[loc] = BASE  # a longer write still queued
    try:
        assert primary._writefull_cuts(pg, op)
    finally:
        pg.rmw._projected_sizes[loc] = STRIPE


# ---------------------------------------------------------- exactly once
@pytest.fixture
def small_cluster():
    from ceph_tpu.loadgen import LoadCluster

    with config.override(osd_op_coalescing=False):
        c = LoadCluster(
            n_osds=K + M + 1, k=K, m=M, pg_num=4, chunk_size=CHUNK,
            pool=POOL, client_op_timeout=30.0,
        )
        try:
            yield c
        finally:
            c.shutdown()


def execute_retry(d, make_op, tries=80, delay=0.05):
    """A daemon-direct op through the durability poll, as the
    objecter's backoff would drive it (a fresh OSDOp per attempt: the
    daemon rewrites ``msg.oid`` in place)."""
    for _ in range(tries):
        r = d._execute_client_op(make_op())
        if r.error != "eagain":
            return r
        time.sleep(delay)
    return r


def writefull(cluster, tid: int, oid: str, data: bytes, reqid: str) -> OSDOp:
    return OSDOp(tid, cluster.mon.osdmap.epoch, POOL, oid, "writefull",
                 data=data, reqid=reqid)


def test_resend_of_an_applied_writefull_replays_and_applies_nothing(
    small_cluster,
):
    c = small_cluster
    a, b = payload(BASE, 1), payload(BASE, 2)
    d = c.daemons[c.mon.osdmap.primary(POOL, "obj")]
    perf = d._get_pg(POOL, c.mon.osdmap.object_to_pg(POOL, "obj")).rmw.perf
    r1 = d._execute_client_op(writefull(c, 1, "obj", a, "cl.1"))
    assert (r1.error, r1.size) == ("", BASE)
    # the window rode the write: on all k+m shards at the reply
    shards = shard_stores(c, "obj")
    assert len(shards) == K + M
    for store, key, _ in shards:
        assert parse_reqs(store.getattr(key, REQ_KEY)) == [("cl.1", BASE)]
    r2 = d._execute_client_op(writefull(c, 2, "obj", b, "cl.2"))
    assert (r2.error, r2.size) == ("", BASE)
    settle()
    txns = counter("osd.*.store", "txns")
    again = d._execute_client_op(writefull(c, 3, "obj", a, "cl.1"))
    assert (again.error, again.size) == ("", r1.size)
    settle()
    assert (perf.get("write_ops"), perf.get("truncate_ops")) == (2, 0)
    assert counter("osd.*.store", "txns") == txns
    assert c.io.read("obj") == b, "a resent writefull must not re-apply"
    for store, key, _ in shard_stores(c, "obj"):
        assert parse_reqs(store.getattr(key, REQ_KEY)) == [
            ("cl.1", BASE), ("cl.2", BASE)
        ]


def test_new_primary_seeds_the_window_from_the_write_half(small_cluster):
    """Takeover: the attr that the one fan-out stamped is what the new
    primary replays a lost-reply resend from."""
    c = small_cluster
    a, b = payload(BASE, 3), payload(3 * STRIPE, 4)
    primary = c.mon.osdmap.primary(POOL, "obj")
    pgid = c.mon.osdmap.object_to_pg(POOL, "obj")
    d = c.daemons[primary]
    assert d._execute_client_op(writefull(c, 1, "obj", a, "cl.1")).error == ""
    r2 = d._execute_client_op(writefull(c, 2, "obj", b, "cl.2"))
    assert (r2.error, r2.size) == ("", len(b))
    assert d._get_pg(POOL, pgid).rmw.perf.get("truncate_ops") == 0
    c.kill(primary)  # its in-memory dedup state dies with it
    deadline = time.monotonic() + 30
    while c.mon.osdmap.is_up(primary):
        assert time.monotonic() < deadline
        time.sleep(0.05)
    new_primary = c.mon.osdmap.primary(POOL, "obj")
    assert new_primary != primary
    d2 = c.daemons[new_primary]
    r = execute_retry(d2, lambda: writefull(c, 3, "obj", a, "cl.1"))
    assert (r.error, r.size) == ("", len(a))
    loc = f"{c.mon.osdmap.pools[POOL].pool_id}:obj"
    assert d2._req_windows[loc] == [("cl.1", len(a)), ("cl.2", len(b))]
    perf = d2._get_pg(POOL, pgid).rmw.perf
    assert perf.get("write_ops") == perf.get("truncate_ops") == 0
    # the payload reads back from the k+m-1 shards that are left
    assert c.io.read("obj") == b
