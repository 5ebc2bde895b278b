"""Overwrites through a hole: one OSD down, the pool keeps taking
small overwrites, and every live shard still equals the plain
reference's encode of the object as it now is.

Held against ``benchmark/reference/rs_vandermonde.py`` (apply the
patch chain to a numpy image, encode it whole, drop the dead shard's
row), for the dead shard at EVERY position of the acting set (data,
parity, the primary's own: one killed OSD sits at a different position
in every PG), at (8,4) and (4,2). Then the OSD is revived and the
returned shard has to equal the reference's row too: the journal of a
hole is worth what recovery makes of it. Beside it, the write plan
against a plan-level reference written out plainly, and the counts
that say a write was degraded. Counts, never times."""

from __future__ import annotations

import time
import types

import numpy as np
import pytest

from benchmark.reference import rs_vandermonde as ref
from ceph_tpu.codecs import registry
from ceph_tpu.codecs.interface import Flag
from ceph_tpu.pipeline.extents import ExtentSet
from ceph_tpu.pipeline.pglog import PGLog
from ceph_tpu.pipeline.read import get_min_avail_to_read_shards
from ceph_tpu.pipeline.rmw import (
    RMWPipeline, ShardBackend, WritePlan, plan_write,
)
from ceph_tpu.pipeline.stripe import StripeInfo
from ceph_tpu.store import MemStore

PAGE = 4096
GEOMETRIES = [(8, 4), (4, 2)]
DELTA = Flag.PARITY_DELTA_OPTIMIZATION


# ------------------------------------------------------- the write plan
def parent_plan(sinfo, flags, ro_offset, length, object_size) -> WritePlan:
    """``plan_write`` as commit 7707ea2 had it, which knew no live set:
    the table a plan with every shard live is held to."""
    touched = sinfo.ro_range_to_shard_extent_set(ro_offset, length, parity=True)
    to_write = {s: es.align(4096) for s, es in touched.items()}
    if flags & Flag.PARITY_DELTA_CHUNK_GRANULARITY:
        to_write = {
            s: es.align(sinfo.chunk_size) if sinfo.is_parity_shard(s) else es
            for s, es in to_write.items()
        }

    def clip_to_stored(shard, es):
        stored = sinfo.object_size_to_shard_size(object_size, shard)
        out = ExtentSet()
        for s, e in es:
            if s < stored:
                out.insert(s, min(e, stored) - s)
        return out

    data_written = {
        s: es for s, es in touched.items() if sinfo.is_data_shard(s)
    }
    full_read = {}
    lo = min(es.range_start() for es in to_write.values())
    hi = max(es.range_end() for es in to_write.values())
    for raw in range(sinfo.k):
        shard = sinfo.get_shard(raw)
        need = ExtentSet([(lo, hi)]).difference(
            data_written.get(shard, ExtentSet())
        )
        need = clip_to_stored(shard, need)
        if need:
            full_read[shard] = need
    delta_read = {}
    for shard, es in to_write.items():
        need = clip_to_stored(shard, es)
        if need:
            delta_read[shard] = need
    full = WritePlan(False, full_read, to_write)
    if not (flags & Flag.PARITY_DELTA_OPTIMIZATION):
        return full
    delta = WritePlan(True, delta_read, to_write)
    if not delta_read:
        return full
    return delta if delta.read_bytes() <= full.read_bytes() else full


def _cases(seed: int, n: int):
    """Seeded (k, m, chunk, flags, offset, length, object size)."""
    rng = np.random.default_rng(seed)
    for _ in range(n):
        k, m = GEOMETRIES[int(rng.integers(0, 2))]
        chunk = int(rng.choice([1024, 4096, 8192]))
        flags = DELTA if rng.integers(0, 4) else Flag(0)
        stripes = int(rng.integers(1, 9))
        size = int(rng.integers(0, stripes * k * chunk + 1))
        # overwrites, appends and writes past the end
        offset = int(rng.integers(0, max(size, 1) + chunk))
        length = int(rng.integers(1, 2 * PAGE + 2))
        yield StripeInfo(k, m, k * chunk), flags, offset, length, size


def test_with_every_shard_live_the_plan_is_the_parents():
    for sinfo, flags, off, ln, size in _cases(0x7707EA2, 400):
        want = parent_plan(sinfo, flags, off, ln, size)
        for live in (None, set(range(sinfo.k + sinfo.m))):
            got = plan_write(sinfo, flags, off, ln, size, live=live)
            assert got.do_parity_delta == want.do_parity_delta
            assert got.to_read == want.to_read
            assert got.to_write == want.to_write
            assert got.read_bytes() == want.read_bytes()
            assert not got.holes and got.fetch is None


def _rows(plan_reads, sinfo):
    """{page row: {shard: the extents read inside it}}."""
    rows: dict[int, dict[int, ExtentSet]] = {}
    for shard, es in plan_reads.items():
        for start, end in es:
            for row in range(start // PAGE, (end - 1) // PAGE + 1):
                lo, hi = max(start, row * PAGE), min(end, (row + 1) * PAGE)
                rows.setdefault(row, {}).setdefault(
                    shard, ExtentSet()
                ).insert(lo, hi - lo)
    return rows


def reference_needs(sinfo, offset, length, live):
    """The plan-level reference, written out plainly, for an object
    that stores every page it touches (chunk = one page). For every
    page row the patch touches: which routes determine every live
    shard's new page, and the fewest bytes any of them reads.

    ``whole``: the old page read in full. ``rest``: at least the bytes
    of it that the patch does not overwrite. A row is settled by
    (R) k whole live pages (the code is MDS: the old row is known), or
    (F) every data page either wholly overwritten or live with its
    rest read (all new data known, so all new parity), or
    (D) every written data page live and whole, and every live parity
    page whole (new parity = old + G x delta).
    Returns {row: (written: {shard: ExtentSet}, fewest bytes read)}."""
    k, m = sinfo.k, sinfo.m
    touched = sinfo.ro_range_to_shard_extent_set(offset, length, parity=False)
    rows = _rows(touched, sinfo)
    out = {}
    for row, written in rows.items():
        costs = [k * PAGE]  # (R) always works with k live
        rests = {
            d: PAGE - written.get(d, ExtentSet()).size() for d in range(k)
        }
        if all(d in live for d, left in rests.items() if left):
            costs.append(sum(rests.values()))  # (F)
        if all(d in live for d in written):
            costs.append(PAGE * (  # (D)
                len(written) + sum(1 for p in range(k, k + m) if p in live)
            ))
        out[row] = (written, min(costs))
    return out


def settles(sinfo, row, written, reads, live) -> bool:
    """Whether ``reads`` (shard -> extents inside the row) determine
    every live shard's new page of the row, by the three routes."""
    k, m = sinfo.k, sinfo.m
    page = ExtentSet([(row * PAGE, (row + 1) * PAGE)])

    def whole(s):
        return s in live and reads.get(s, ExtentSet()) == page

    def rest(s):
        need = page.difference(written.get(s, ExtentSet()))
        return not need or (
            s in live and not need.difference(reads.get(s, ExtentSet()))
        )

    if sum(whole(s) for s in range(k + m)) >= k:
        return True
    if all(rest(d) for d in range(k)):
        return True
    return all(whole(d) for d in written) and all(
        whole(p) for p in range(k, k + m) if p in live
    )


@pytest.mark.parametrize("k,m", GEOMETRIES)
def test_the_plan_reads_around_a_hole_what_the_reference_needs(k, m):
    sinfo = StripeInfo(k, m, k * PAGE)
    size = 16 * k * PAGE  # a preloaded object: every page is stored
    codec = registry.factory(
        "jerasure", {"technique": "reed_sol_van", "k": str(k), "m": str(m)}
    )
    rng = np.random.default_rng(0xD0E + k)
    checked = {"data": 0, "parity": 0, "reconstruct": 0}
    for _ in range(300):
        dead = int(rng.integers(0, k + m))
        live = set(range(k + m)) - {dead}
        length = int(rng.integers(1, 2 * PAGE + 1))
        offset = int(rng.integers(0, size - length + 1))
        plan = plan_write(sinfo, DELTA, offset, length, size, live=live)
        reads = plan.to_read if plan.fetch is None else plan.fetch
        # nothing is read on the dead shard, and nothing sent to it
        assert dead not in reads
        assert dead not in plan.to_write or dead < k
        if dead >= k:
            checked["parity"] += 1
            assert plan.fetch is None and dead not in plan.to_read
            assert set(plan.holes) == {dead}
            healthy = plan_write(sinfo, DELTA, offset, length, size)
            assert plan.holes[dead] == healthy.to_write[dead]
        else:
            checked["data"] += 1
            assert not plan.holes
        # what _backend_read asks for is what the plan priced
        if plan.fetch is not None:
            checked["reconstruct"] += 1
            asked, decode = get_min_avail_to_read_shards(
                sinfo, codec, plan.to_read, live
            )
            assert decode
            assert {s: sr.extents for s, sr in asked.items()} == plan.fetch
        by_row = _rows(reads, sinfo)
        needs = reference_needs(sinfo, offset, length, live)
        for row, (written, fewest) in needs.items():
            got = by_row.get(row, {})
            assert settles(sinfo, row, written, got, live), (
                offset, length, dead, plan
            )
            read = sum(es.size() for es in got.values())
            assert read >= fewest
            if len(needs) == 1:
                # one row: the plan takes the cheapest route
                assert read == fewest, (offset, length, dead, plan)
        # a reconstruct only where old data of the dead shard is needed
        must = any(
            dead in written and written[dead].size() < PAGE
            for written, _ in needs.values()
        )
        if k == 8:
            assert (plan.fetch is not None) == must, (offset, length, dead)
        elif must:
            assert plan.fetch is not None
    assert min(checked.values()) > 20, checked


# ------------------------------------------- transactions and the journal
@pytest.mark.parametrize("dead", range(6))
def test_a_hole_gets_no_transaction_and_stays_in_the_journal(dead):
    k, m = 4, 2
    sinfo = StripeInfo(k, m, k * PAGE)
    codec = registry.factory(
        "jerasure", {"technique": "reed_sol_van", "k": str(k), "m": str(m)}
    )
    backend = ShardBackend({s: MemStore(f"s{s}") for s in range(k + m)})
    log = PGLog(k + m)
    rmw = RMWPipeline(
        sinfo, codec, backend, perf_name=f"hole_txn_{dead}", pglog=log
    )
    built = []
    inner = rmw._build_transactions

    def spy(op, result, new_size):
        live, txns = inner(op, result, new_size)
        built.append((op, live, txns))
        return live, txns

    rmw._build_transactions = spy
    rng = np.random.default_rng(dead)
    image = rng.integers(0, 256, 2 * k * PAGE, np.uint8)
    rmw.submit("o", 0, image.tobytes())
    backend.down_shards.add(dead)
    # one whole stripe, then a patch inside one chunk of the other
    image[: k * PAGE] = rng.integers(0, 256, k * PAGE, np.uint8)
    rmw.submit("o", 0, image[: k * PAGE].tobytes())
    at = k * PAGE + 100
    image[at : at + 50] = 7
    rmw.submit("o", at, image[at : at + 50].tobytes())
    healthy, whole, patch = built
    assert len(healthy[2]) == k + m
    for op, live, txns in (whole, patch):
        assert op.error is None and op.committed
        assert live == set(range(k + m)) - {dead}
        assert len(txns) == k + m - 1 and dead not in dict(txns)
        assert op.acked_shards == live
    # the journal still lists the hole's extents: all k+m for a whole
    # stripe, and the hole's page where the patch touches its shard
    entry = log.entries[-2]
    assert sorted(entry.shard_extents) == list(range(k + m))
    assert entry.shard_extents[dead] == ExtentSet([(0, PAGE)])
    touched = set(patch[0].plan.to_write) | set(patch[0].plan.holes)
    assert (dead in log.entries[-1].shard_extents) == (dead in touched)
    assert log.dirty_extents(dead)["o"].contains(0, PAGE)
    assert rmw.perf.dump()["hole_shard_writes"] == 2
    want = ref.shards_of(image.tobytes(), k, m, PAGE)
    for s in live:
        got = np.frombuffer(backend.stores[s].read("o"), np.uint8)
        assert np.array_equal(got, want[s]), s


# --------------------------------------------------- the served path
def _chain(rng, k: int, rows: int, dead_data: "int | None"):
    """A seeded chain of patches, each in page rows of its own (so no
    patch finds another's pages cached): sub-page, one whole page, two
    pages, and one that crosses a stripe; where the hole is a data
    shard, one patch of each kind is aimed at it."""
    stripe = k * PAGE
    free = list(rng.permutation(rows - 1))
    out = []

    def at(row, shard, inner):
        return int(row) * stripe + shard * PAGE + inner

    for kind in ("sub", "page", "two", "cross", "sub", "two"):
        row = free.pop()
        shard = int(rng.integers(0, k))
        if dead_data is not None and len(out) % 2 == 0:
            shard = dead_data
        if kind == "sub":
            ln = int(rng.integers(1, PAGE // 2))
            out.append((at(row, shard, int(rng.integers(0, PAGE - ln))), ln))
        elif kind == "page":
            out.append((at(row, shard, 0), PAGE))
        elif kind == "two":
            shard = min(shard, k - 2)
            out.append((at(row, shard, int(rng.integers(1, PAGE))), PAGE))
        else:  # from the last chunk of one stripe into the next
            if int(row) + 1 in free:
                free.remove(int(row) + 1)
            inner = int(rng.integers(PAGE // 2, PAGE))
            out.append((at(row, k - 1, inner), PAGE))
    return out


def _rmw_counters(pool: str, pgid: int) -> dict:
    from ceph_tpu.utils import perf_collection

    total: dict = {}
    for name, vals in perf_collection.dump().items():
        if name.endswith(f".{pool}.{pgid}.rmw"):
            for key, val in vals.items():
                if isinstance(val, (int, float)):
                    total[key] = total.get(key, 0) + val
    return total


def _daemon_counters(cluster, section: str) -> dict:
    from ceph_tpu.utils import perf_collection

    total: dict = {}
    for name, vals in perf_collection.dump().items():
        if name.startswith("osd.") and name.endswith("." + section):
            for key, val in vals.items():
                if isinstance(val, (int, float)):
                    total[key] = total.get(key, 0) + val
    return total


def _stored(cluster, oid: str, shard: int, osd: int) -> np.ndarray:
    store = cluster.stores[osd]
    key = next(
        key for key in store.list_objects()
        if key.partition(":")[2] == f"{oid}#s{shard}"
    )
    return np.frombuffer(store.read(key), np.uint8)


def _run_degraded(k: int, m: int) -> dict:
    """Boot, preload one object for every position of the victim, kill
    it, overwrite, compare; revive, recover, compare the returned
    shard. Returns what each position's test asserts."""
    from ceph_tpu.loadgen import LoadCluster

    rows = 16
    size = rows * k * PAGE
    pool = f"hole{k}{m}"
    cluster = LoadCluster(
        n_osds=k + m, k=k, m=m, pg_num=64, chunk_size=PAGE, pool=pool,
        client_op_timeout=15.0, client_max_attempts=2,
    )
    out: dict = {"positions": {}}
    try:
        osdmap = cluster.mon.osdmap
        # the victim whose positions, over the PGs, cover all k+m; one
        # object a position, each in a PG of its own
        pick = None
        for victim in range(k + m):
            oids: dict[int, str] = {}
            pgs: set[int] = set()
            for i in range(4096):
                oid = f"obj-{i}"
                pos = osdmap.object_to_acting(pool, oid).index(victim)
                pgid = osdmap.object_to_pg(pool, oid)
                if pos not in oids and pgid not in pgs:
                    oids[pos] = oid
                    pgs.add(pgid)
                if len(oids) == k + m:
                    break
            if len(oids) == k + m:
                pick = (victim, oids)
                break
        assert pick is not None, "no OSD sits at every position"
        victim, oids = pick
        rng = np.random.default_rng(0x40 + k)
        images = {
            pos: rng.integers(0, 256, size, np.uint8) for pos in oids
        }
        for pos, oid in oids.items():
            cluster.io.write_full(oid, images[pos].tobytes())
        cluster.kill(victim)
        # every op of the new interval, as the pipelines build it
        built: list = []
        inner = RMWPipeline._build_transactions

        def spy(self, op, result, new_size):
            live, txns = inner(self, op, result, new_size)
            built.append((op, set(live), len(txns)))
            return live, txns

        RMWPipeline._build_transactions = spy
        eagain0 = _daemon_counters(cluster, "eagain")
        opq0 = _daemon_counters(cluster, "opq")
        try:
            for pos, oid in sorted(oids.items()):
                pgid = osdmap.object_to_pg(pool, oid)
                before = _rmw_counters(pool, pgid)
                chain = _chain(rng, k, rows, pos if pos < k else None)
                touching = must = 0
                for offset, length in chain:
                    patch = rng.integers(0, 256, length, np.uint8)
                    images[pos][offset : offset + length] = patch
                    cluster.io.write(oid, patch.tobytes(), offset=offset)
                    if pos < k:
                        sinfo = StripeInfo(k, m, k * PAGE)
                        es = sinfo.ro_range_to_shard_extent_set(
                            offset, length, parity=False
                        ).get(pos)
                        touching += bool(es)
                        must += bool(es) and any(
                            (e - s) % PAGE or s % PAGE for s, e in es
                        )
                after = _rmw_counters(pool, pgid)
                moved = {
                    key: after[key] - before.get(key, 0) for key in after
                }
                image = images[pos].tobytes()
                want = ref.shards_of(image, k, m, PAGE)
                acting = cluster.mon.osdmap.object_to_acting(pool, oid)
                stored = {
                    shard: _stored(cluster, oid, shard, osd)
                    for shard, osd in enumerate(acting)
                    if osd >= 0 and osd not in cluster.dead
                }
                # a seeded k of the live shards, all live parity among
                # them, rebuild the image by the reference
                parity = [s for s in stored if s >= k]
                data = [s for s in stored if s < k]
                use = parity + list(
                    rng.permutation(data)[: k - len(parity)]
                )
                rebuilt = ref.object_from_data_shards(
                    ref.decode_data(
                        {int(s): stored[int(s)] for s in use}, k, m
                    ),
                    size, PAGE,
                )
                out["positions"][pos] = {
                    "oid": oid, "writes": len(chain),
                    "touching": touching, "must": must, "moved": moved,
                    "read_back": bytes(cluster.io.read(oid)) == image,
                    "rebuilt": rebuilt == image,
                    "shards": sorted(stored),
                    "mismatch": [
                        s for s, got in stored.items()
                        if not np.array_equal(got, want[s])
                    ],
                }
        finally:
            RMWPipeline._build_transactions = inner
        out["built"] = [
            (op.error, op.committed, set(op.acked_shards), live, n)
            for op, live, n in built
        ]
        eagain1 = _daemon_counters(cluster, "eagain")
        out["eagain"] = {
            key: eagain1[key] - eagain0.get(key, 0) for key in eagain1
        }
        opq1 = _daemon_counters(cluster, "opq")
        out["holds"] = opq1["req_poll_holds"] - opq0["req_poll_holds"]
        # -- the OSD returns: recovery rebuilds its shard from the journal
        cluster.revive(victim)
        cluster.wait_recovered(timeout=60.0)
        # a daemon makes a PG's state at the PG's first op: where the
        # returned OSD leads the PG again, this read is what starts its
        # election, and that reconciles its own shard
        out["read_after_revive"] = all(
            bytes(cluster.io.read(oid)) == images[pos].tobytes()
            for pos, oid in oids.items()
        )
        out["recovered"] = cluster.wait_recovered(timeout=60.0)
        for pos, oid in oids.items():
            want = ref.shards_of(images[pos].tobytes(), k, m, PAGE)
            deadline = time.monotonic() + 20.0
            while True:
                try:
                    got = _stored(cluster, oid, pos, victim)
                    same = np.array_equal(got, want[pos])
                except StopIteration:
                    same = False
                if same or time.monotonic() > deadline:
                    break
                time.sleep(0.1)
            out["positions"][pos]["returned_equal"] = same
    finally:
        cluster.shutdown()
    return out


@pytest.fixture(scope="module")
def degraded_runs():
    runs: dict = {}

    def get(k, m):
        if (k, m) not in runs:
            runs[(k, m)] = _run_degraded(k, m)
        return runs[(k, m)]

    return get


POSITIONS = [
    pytest.param(k, m, pos, id=f"rs{k}{m}-{'data' if pos < k else 'parity'}{pos}")
    for k, m in GEOMETRIES for pos in range(k + m)
]


@pytest.mark.parametrize("k,m,pos", POSITIONS)
def test_overwrites_through_a_hole_match_the_reference(
    degraded_runs, k, m, pos
):
    got = degraded_runs(k, m)["positions"][pos]
    # the 11 (5) live shards, byte for byte; the client through the hole
    assert got["shards"] == [s for s in range(k + m) if s != pos]
    assert got["mismatch"] == []
    assert got["read_back"] and got["rebuilt"]
    moved = got["moved"]
    assert moved["encode_ops"] == got["writes"]
    assert moved["aborts"] == 0
    # one transaction not built a write: the proof it was degraded
    assert moved["hole_shard_writes"] == got["writes"]
    if pos >= k:
        # a parity hole rebuilds nothing: its old page is not read,
        # its new page not made
        assert moved["rmw_reconstruct_ops"] == 0
    else:
        # a data hole: a reconstruct for the patches that need its old
        # bytes, and at (8,4) for no other
        assert moved["rmw_reconstruct_ops"] >= got["must"] > 0
        if k == 8:
            assert moved["rmw_reconstruct_ops"] == got["must"]
        assert moved["rmw_reconstruct_ops"] <= got["touching"] or k == 4
        assert moved["rmw_reconstruct_seconds"] > 0
    assert moved["rmw_subreads"] >= moved["rmw_read_ops"] > 0


@pytest.mark.parametrize("k,m,pos", POSITIONS)
def test_the_returned_shard_is_the_references_row(degraded_runs, k, m, pos):
    run = degraded_runs(k, m)
    assert run["recovered"] and run["read_after_revive"]
    assert run["positions"][pos]["returned_equal"]


@pytest.mark.parametrize("k,m", GEOMETRIES)
def test_an_overwrite_is_acknowledged_by_every_live_shard(
    degraded_runs, k, m
):
    built = degraded_runs(k, m)["built"]
    assert len(built) >= 6 * (k + m)
    for error, committed, acked, live, n_txns in built:
        assert error is None and committed
        assert len(live) == k + m - 1 and acked == live
        assert n_txns == k + m - 1


@pytest.mark.parametrize("k,m", GEOMETRIES)
def test_the_first_overwrite_of_an_interval_does_not_bounce(
    degraded_runs, k, m
):
    """Every object's first overwrite after the kill has to prove the
    reqid window it seeded from the stored attrs: the op is held at
    the primary for that poll, and the client (two attempts, as the
    benchmark's cells give it) hears no "try again" from the gate."""
    run = degraded_runs(k, m)
    assert run["holds"] >= k + m
    for reason in (
        "window_unsettled", "resend_unverified", "resend_unknown",
        "hold_expired",
    ):
        assert run["eagain"][reason] == 0, run["eagain"]


def test_forty_first_touches_at_once_do_not_spend_the_poll_budget():
    """Two pollers a daemon (``REQ_POLL_BUDGET``) and forty objects
    whose first overwrite of the interval arrives together: the polls
    past the budget wait their turn, every op is held for its own, and
    none bounces or is lost."""
    import sys
    import threading

    from ceph_tpu.loadgen import LoadCluster

    k, m, n = 4, 2, 40
    cluster = LoadCluster(
        n_osds=k + m, k=k, m=m, pg_num=8, chunk_size=PAGE, pool="burst",
        client_op_timeout=15.0, client_max_attempts=2,
    )
    interval = sys.getswitchinterval()
    try:
        rng = np.random.default_rng(40)
        images = [rng.integers(0, 256, k * PAGE, np.uint8) for _ in range(n)]
        for i, image in enumerate(images):
            cluster.io.write_full(f"b{i}", image.tobytes())
        cluster.kill(cluster.most_primary_osd())
        eagain0 = _daemon_counters(cluster, "eagain")
        opq0 = _daemon_counters(cluster, "opq")
        sys.setswitchinterval(1e-4)
        errors, done = [], threading.Semaphore(0)

        def landed(comp):
            if comp.error is not None:
                errors.append(repr(comp.error))
            done.release()

        for i, image in enumerate(images):
            image[100 + i : 200 + i] = i
            cluster.io.aio_write(
                f"b{i}", image[100 + i : 200 + i].tobytes(),
                offset=100 + i, on_complete=landed,
            )
        for _ in range(n):
            assert done.acquire(timeout=60.0), "an overwrite never answered"
        sys.setswitchinterval(interval)
        assert not errors, errors[:3]
        eagain = _daemon_counters(cluster, "eagain")
        for reason in (
            "window_unsettled", "resend_unverified", "resend_unknown",
            "hold_expired",
        ):
            assert eagain[reason] == eagain0.get(reason, 0), reason
        opq = _daemon_counters(cluster, "opq")
        assert opq["req_poll_holds"] - opq0["req_poll_holds"] >= n
        for d in cluster.daemons.values():
            assert not d._req_held and not d._req_poll_backlog
        for i, image in enumerate(images):
            assert bytes(cluster.io.read(f"b{i}")) == image.tobytes()
    finally:
        sys.setswitchinterval(interval)
        cluster.shutdown()


def test_the_objecter_says_why_it_was_told_to_try_again():
    from ceph_tpu.cluster.objecter import Objecter
    from ceph_tpu.msg.messages import OSDOpReply

    events, retried = [], []
    me = types.SimpleNamespace(_retry=retried.append)
    aop = types.SimpleNamespace(
        osd=3, last="", tracked=types.SimpleNamespace(
            mark_event=lambda name, **kw: events.append(name)
        ),
    )
    Objecter._handle_reply(
        me, aop, OSDOpReply(1, 27, error="eagain", data=b"window_unsettled")
    )
    assert aop.last == "osd.3 answered window_unsettled (its epoch 27)"
    assert retried == [aop] and events == ["eagain"]
    Objecter._handle_reply(me, aop, OSDOpReply(1, 28, error="eagain"))
    assert aop.last == "osd.3 answered eagain (its epoch 28)"


def test_every_eagain_site_has_a_counter():
    """One key a site that builds the reply, and nothing builds one
    without saying why."""
    import inspect

    from ceph_tpu.cluster import osd_daemon

    source = inspect.getsource(osd_daemon)
    assert source.count('error="eagain"') == 1  # ``_eagain`` itself
    used = {
        reason for reason in osd_daemon.EAGAIN_REASONS
        if f'"{reason}"' in source.split("def make_eagain_perf")[1]
    }
    assert used == set(osd_daemon.EAGAIN_REASONS)
