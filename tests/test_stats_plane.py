"""Cluster-wide stats plane (round 15): PG-stats reports folded into
the PGMap aggregate, stale-report rejection, windowed IO/recovery
rates, stats-fed mgr health checks (PG_DEGRADED with object counts,
PG_STUCK, OSD_NEARFULL, SLOW_OPS), the `status`/`pg dump`/`df`
surfaces, and the live deterministic-seed smoke pinning the ISSUE-12
acceptance: degraded object counts rise on a primary kill, recovery
rates go nonzero, everything returns to clean, and the stats-derived
``time_to_recovered_s`` agrees with the legacy direct-state poll
within about one report interval.
"""

import json
import time

import pytest

from ceph_tpu.cluster import Manager, Monitor
from ceph_tpu.cluster.pgmap import (
    OSDStat,
    PGMap,
    PGStats,
    format_df,
    format_pg_dump,
    format_status,
    status_dict,
    status_digest,
)
from ceph_tpu.utils import config
from ceph_tpu.utils.optracker import op_tracker


def mkstats(
    pool="p1",
    pool_id=1,
    pgid=0,
    state=("active", "clean"),
    epoch=5,
    seq=1,
    primary=0,
    **kw,
):
    return PGStats(
        pool=pool, pool_id=pool_id, pgid=pgid,
        state=tuple(sorted(state)), reported_epoch=epoch,
        reported_seq=seq, primary=primary, **kw,
    )


class _FakeClock:
    def __init__(self) -> None:
        self.t = 100.0

    def __call__(self) -> float:
        return self.t


class TestPGMapFold:
    def test_totals_histogram_and_pools(self):
        pm = PGMap()
        pm.apply_report(0, 5, [
            mkstats(pgid=0, num_objects=4, num_bytes=4096),
            mkstats(pgid=1, state=("active", "degraded"),
                    num_objects=2, num_bytes=1024, degraded=2),
        ])
        pm.apply_report(1, 5, [
            mkstats(pgid=2, primary=1, num_objects=1, num_bytes=512),
        ], OSDStat(osd=1, used_bytes=100, capacity_bytes=1000))
        t = pm.totals()
        assert t["pgs"] == 3
        assert t["objects"] == 7
        assert t["bytes"] == 4096 + 1024 + 512
        assert t["degraded_objects"] == 2
        assert t["pgs_degraded"] == 1
        assert t["pgs_clean"] == 2
        assert t["osd_used_bytes"] == 100
        hist = pm.state_histogram()
        assert hist["active+clean"] == 2
        assert hist["active+degraded"] == 1
        pools = pm.pool_totals()
        assert pools["p1"]["pgs"] == 3
        assert pools["p1"]["objects"] == 7

    def test_stale_report_rejected_by_epoch(self):
        """The acceptance scenario: a demoted primary's report (older
        reported epoch) is rejected once the takeover primary has
        reported at the newer epoch."""
        pm = PGMap()
        assert pm.apply_report(0, 5, [mkstats(epoch=5, primary=0)]) == 1
        # takeover: osd.3 reports at the post-failover epoch
        assert pm.apply_report(
            3, 7, [mkstats(epoch=7, primary=3, num_objects=9)]
        ) == 1
        # the demoted primary retries with its stale interval
        assert pm.apply_report(
            0, 5, [mkstats(epoch=5, seq=2, primary=0)]
        ) == 0
        s = pm.get(1, 0)
        assert s.primary == 3 and s.num_objects == 9
        from ceph_tpu.utils.perf_counters import perf_collection

        dump = perf_collection.dump()["pgmap"]
        assert dump["reports_rejected"] >= 1

    def test_same_epoch_second_claimant_rejected(self):
        pm = PGMap()
        pm.apply_report(0, 5, [mkstats(epoch=5, primary=0)])
        assert pm.apply_report(
            2, 5, [mkstats(epoch=5, primary=2)]
        ) == 0
        assert pm.get(1, 0).primary == 0

    def test_seq_regression_same_primary_rejected(self):
        pm = PGMap()
        pm.apply_report(0, 5, [mkstats(epoch=5, seq=8)])
        assert pm.apply_report(0, 5, [mkstats(epoch=5, seq=7)]) == 0
        assert pm.apply_report(0, 5, [mkstats(epoch=5, seq=9)]) == 1

    def test_rates_from_successive_deltas(self):
        clock = _FakeClock()
        pm = PGMap(clock=clock)
        pm.apply_report(0, 5, [mkstats(
            client_write_bytes=0, client_write_ops=0,
        )])
        clock.t += 2.0
        pm.apply_report(0, 5, [mkstats(
            seq=2, client_write_bytes=2000, client_write_ops=10,
            recovery_bytes=500, recovery_ops=2,
        )])
        r = pm.rates(window=10.0)
        assert r["client_write_bps"] == pytest.approx(1000.0)
        assert r["client_write_iops"] == pytest.approx(5.0)
        assert r["recovery_bps"] == pytest.approx(250.0)
        assert r["recovery_ops_per_s"] == pytest.approx(1.0)

    def test_negative_delta_clamps_to_zero(self):
        """A primary takeover resets cumulative counters; the rate
        window must clamp, not go negative."""
        clock = _FakeClock()
        pm = PGMap(clock=clock)
        pm.apply_report(0, 5, [mkstats(client_write_bytes=9000)])
        clock.t += 1.0
        pm.apply_report(3, 7, [mkstats(
            epoch=7, primary=3, client_write_bytes=100,
        )])
        r = pm.rates(window=10.0)
        assert r["client_write_bps"] == 0.0

    def test_stuck_pg_ages_from_last_clean(self):
        clock = _FakeClock()
        pm = PGMap(clock=clock)
        pm.apply_report(0, 5, [mkstats(
            state=("active", "degraded"), degraded=3,
        )])
        assert pm.stuck_pgs(30.0) == []
        clock.t += 40.0
        stuck = pm.stuck_pgs(30.0)
        assert len(stuck) == 1
        assert stuck[0]["pgid"] == "p1/0"
        assert stuck[0]["stuck_for_s"] == pytest.approx(40.0)
        # a clean report resets the age
        pm.apply_report(0, 6, [mkstats(epoch=6, seq=2)])
        assert pm.stuck_pgs(30.0) == []

    def test_nearfull_osds(self):
        pm = PGMap()
        pm.apply_report(
            0, 5, [], OSDStat(osd=0, used_bytes=90, capacity_bytes=100)
        )
        pm.apply_report(
            1, 5, [], OSDStat(osd=1, used_bytes=10, capacity_bytes=100)
        )
        near = pm.nearfull_osds(0.85)
        assert [o["osd"] for o in near] == [0]

    def test_prune_pools(self):
        pm = PGMap()
        pm.apply_report(0, 5, [mkstats(pool="a", pool_id=1),
                               mkstats(pool="b", pool_id=2)])
        pm.prune_pools({2})
        assert pm.get(1, 0) is None
        assert pm.get(2, 0) is not None

    def test_degraded_transitions_land_in_cluster_log(self):
        from ceph_tpu.utils.cluster_log import cluster_log

        cluster_log.clear()
        pm = PGMap()
        pm.apply_report(0, 5, [mkstats(
            pool="tlog", state=("active", "degraded"), degraded=4,
        )])
        pm.apply_report(0, 6, [mkstats(pool="tlog", epoch=6, seq=2)])
        events = [
            e for e in cluster_log.last(50, daemon="mgr")
            if "tlog/0" in e["message"]
        ]
        kinds = [e["type"] for e in events]
        assert "pg_degraded" in kinds and "pg_clean" in kinds
        deg = next(e for e in events if e["type"] == "pg_degraded")
        assert deg["severity"] == "WRN"
        assert "4 degraded object copies" in deg["message"]


def mkmon(n=6, pools=(("p1", 8, 2, 1),)):
    mon = Monitor()
    for i in range(n):
        mon.osd_crush_add(i, zone=f"z{i % 3}")
        mon.osd_boot(i, ("127.0.0.1", 7000 + i))
    for name, pgs, k, m in pools:
        prof = f"prof_{name}"
        mon.osd_erasure_code_profile_set(
            prof, {"plugin": "isa", "k": str(k), "m": str(m)}
        )
        mon.osd_pool_create(name, pgs, prof)
    return mon


class TestStatsFedHealth:
    def test_pg_degraded_gains_object_counts(self):
        mon = mkmon()
        spec = mon.osdmap.pools["p1"]
        mon.pgmap.apply_report(0, mon.osdmap.epoch, [mkstats(
            pool="p1", pool_id=spec.pool_id, pgid=0,
            state=("active", "degraded", "undersized"),
            num_objects=6, degraded=6, epoch=mon.osdmap.epoch,
        )])
        checks = Manager(mon).health()["checks"]
        assert "PG_DEGRADED" in checks
        assert "6 object copies" in checks["PG_DEGRADED"]["detail"]

    def test_pg_unavailable_from_down_state(self):
        mon = mkmon()
        spec = mon.osdmap.pools["p1"]
        mon.pgmap.apply_report(0, mon.osdmap.epoch, [mkstats(
            pool="p1", pool_id=spec.pool_id, pgid=3,
            state=("down", "undersized", "degraded"),
            epoch=mon.osdmap.epoch,
        )])
        report = Manager(mon).health()
        assert report["status"] == "HEALTH_ERR"
        assert "PG_UNAVAILABLE" in report["checks"]

    def test_pg_stuck_check(self):
        mon = mkmon()
        clock = _FakeClock()
        mon.pgmap = PGMap(clock=clock)  # swap in a steerable clock
        spec = mon.osdmap.pools["p1"]
        mon.pgmap.apply_report(0, mon.osdmap.epoch, [mkstats(
            pool="p1", pool_id=spec.pool_id, pgid=1,
            state=("active", "degraded"), epoch=mon.osdmap.epoch,
        )])
        clock.t += 100.0
        with config.override(mon_pg_stuck_threshold=60.0):
            checks = Manager(mon).health()["checks"]
        assert "PG_STUCK" in checks
        assert "p1/1" in checks["PG_STUCK"]["detail"]

    def test_osd_nearfull_check(self):
        mon = mkmon()
        spec = mon.osdmap.pools["p1"]
        mon.pgmap.apply_report(0, mon.osdmap.epoch, [mkstats(
            pool="p1", pool_id=spec.pool_id,
            epoch=mon.osdmap.epoch,
        )], OSDStat(osd=2, used_bytes=95, capacity_bytes=100))
        checks = Manager(mon).health()["checks"]
        assert "OSD_NEARFULL" in checks
        assert "osd.2" in checks["OSD_NEARFULL"]["detail"]

    def test_slow_ops_check(self):
        mon = mkmon()
        with config.override(osd_op_complaint_time=0.05):
            # osd.3 is in the map: the check scopes to the cluster's
            # own daemons (unrelated pipelines' ops don't count)
            top = op_tracker.register(
                "rmw_write", daemon="osd.3", oid="stuckobj"
            )
            try:
                deadline = time.monotonic() + 5.0
                while not top.slow and time.monotonic() < deadline:
                    op_tracker.poke()
                    time.sleep(0.02)
                assert top.slow
                checks = Manager(mon).health()["checks"]
                assert "SLOW_OPS" in checks
                assert "slow ops in flight" in (
                    checks["SLOW_OPS"]["detail"]
                )
            finally:
                top.finish()
        assert "SLOW_OPS" not in Manager(mon).health()["checks"]

    def test_slow_ops_scoped_to_cluster_daemons(self):
        """A slow op of an unrelated pipeline (not a map daemon) must
        not poison this cluster's health."""
        mon = mkmon()
        with config.override(osd_op_complaint_time=0.05):
            top = op_tracker.register(
                "rmw_write", daemon="some_pipeline", oid="elsewhere"
            )
            try:
                deadline = time.monotonic() + 5.0
                while not top.slow and time.monotonic() < deadline:
                    op_tracker.poke()
                    time.sleep(0.02)
                assert top.slow
                assert "SLOW_OPS" not in (
                    Manager(mon).health()["checks"]
                )
            finally:
                top.finish()

    def test_fallback_to_map_scan_without_reports(self):
        """A bare monitor (no daemons, no reports) keeps the legacy
        CRUSH-rescan checks."""
        mon = mkmon(n=3, pools=[("p1", 8, 2, 1)])
        mon.pgmap.pg.clear()
        mon.osd_down(0)
        checks = Manager(mon).health()["checks"]
        assert "OSD_DOWN" in checks
        assert "PG_DEGRADED" in checks or "PG_UNAVAILABLE" in checks


class TestSurfaces:
    def _reported_mon(self):
        mon = mkmon()
        spec = mon.osdmap.pools["p1"]
        for pgid in range(spec.pg_num):
            mon.pg_stats_report(0, mon.osdmap.epoch, [mkstats(
                pool="p1", pool_id=spec.pool_id, pgid=pgid,
                num_objects=2, num_bytes=2048,
                epoch=mon.osdmap.epoch,
            )], OSDStat(osd=0, used_bytes=4096,
                        capacity_bytes=1 << 20))
        return mon

    def test_status_dict_and_format(self):
        mon = self._reported_mon()
        st = status_dict(mon)
        assert st["pgs"]["total"] == 8
        assert st["pgs"]["histogram"]["active+clean"] == 8
        assert st["pgs"]["unreported"] == 0
        assert st["objects"] == 16
        text = format_status(st)
        assert "8 active+clean" in text
        assert "health:" in text and "osd: 6 total" in text
        digest = status_digest(st)
        assert "\n" not in digest
        assert "8 active+clean" in digest

    def test_pg_dump_and_df_render(self):
        mon = self._reported_mon()
        dump = mon.pgmap.pg_dump()
        assert len(dump["pg_stats"]) == 8
        text = format_pg_dump(dump)
        assert "p1/0" in text and "active+clean" in text
        df = mon.pgmap.df(mon.osdmap)
        assert df["pools"]["p1"]["objects"] == 16
        # EC 2+1 raw estimate = stored * 3/2
        assert df["pools"]["p1"]["raw_bytes_est"] == (
            df["pools"]["p1"]["stored_bytes"] * 3 // 2
        )
        assert "CLUSTER:" in format_df(df)
        json.dumps(df)  # CLI --json contract

    def test_admin_socket_pgmap_dump(self):
        from ceph_tpu.utils.admin_socket import admin_socket

        mon = self._reported_mon()
        dump = admin_socket.execute("pgmap")
        assert dump["totals"]["pgs"] == 8
        assert dump["version"] == mon.pgmap.version


class TestExporterPoolLabels:
    def test_pgmap_and_pool_sets_render(self):
        from ceph_tpu.utils.exporter import render_exposition
        from ceph_tpu.utils.perf_counters import perf_collection

        mon = mkmon()
        spec = mon.osdmap.pools["p1"]
        mon.pg_stats_report(0, mon.osdmap.epoch, [mkstats(
            pool="p1", pool_id=spec.pool_id, num_objects=3,
            num_bytes=300, epoch=mon.osdmap.epoch,
        )])
        text = render_exposition(perf_collection)
        assert 'ceph_tpu_pgs{set="pgmap"}' in text
        # per-pool gauges carry the pool label
        assert (
            'ceph_tpu_pool_objects{pool="p1",set="pgmap"} 3' in text
        )

    def test_objecter_per_pool_accounting(self):
        """The ROADMAP-#2 seed observable: client op/byte counters
        sliced by pool on the objecter perf set, pool-labelled on the
        exporter."""
        from ceph_tpu.loadgen import LoadCluster
        from ceph_tpu.utils.exporter import render_exposition
        from ceph_tpu.utils.perf_counters import perf_collection

        cluster = LoadCluster(
            n_osds=4, k=2, m=1, pg_num=4, chunk_size=1024,
        )
        try:
            cluster.io.write_full("acct-obj", b"x" * 4096)
            assert cluster.io.read("acct-obj") == b"x" * 4096
        finally:
            cluster.shutdown()
        dump = perf_collection.dump()
        key = "loadgen_client.pool.loadpool"
        assert key in dump
        assert dump[key]["pool_op_w"] >= 1
        assert dump[key]["pool_op_r"] >= 1
        assert dump[key]["pool_bytes_w"] >= 4096
        assert dump[key]["pool_bytes_r"] >= 4096
        text = render_exposition(perf_collection)
        assert (
            'ceph_tpu_pool_op_w{pool="loadpool",'
            'set="loadgen_client"}' in text
        )


class TestLiveStatsPlane:
    """The deterministic-seed acceptance smoke: the stats plane sees
    a primary kill as rising degraded counts + recovery rates, and
    convergence back to clean exactly when recovery completes."""

    def test_kill_degrades_revive_cleans(self):
        from ceph_tpu.loadgen import LoadCluster

        cluster = LoadCluster(
            n_osds=5, k=2, m=1, pg_num=4, chunk_size=1024,
        )
        try:
            rng_data = bytes(range(256)) * 16  # 4 KiB
            for i in range(8):
                cluster.io.write_full(f"sp-{i}", rng_data)
            for d in cluster.daemons.values():
                d.report_pg_stats(force=True)
            pm = cluster.pgmap
            st = status_dict(cluster.mon)
            assert st["objects"] == 8
            assert st["pgs"]["histogram"].get("active+clean", 0) >= 1
            # client IO rates go nonzero once the cumulative counters
            # move across two report samples — keep writing until the
            # window sees the delta
            deadline = time.monotonic() + 15.0
            i = 0
            while time.monotonic() < deadline:
                cluster.io.write_full(f"sp-{i % 8}", rng_data)
                i += 1
                io = pm.rates()
                if io["client_write_bps"] > 0:
                    break
                time.sleep(0.05)
            assert io["client_write_bps"] > 0
            victim = cluster.most_primary_osd()
            cluster.kill(victim)
            # the takeover primaries report degraded object copies
            deadline = time.monotonic() + 15.0
            while time.monotonic() < deadline:
                if pm.degraded_objects() > 0:
                    break
                time.sleep(0.1)
            assert pm.degraded_objects() > 0, (
                "stats plane never saw the kill"
            )
            hist = pm.state_histogram()
            assert any("degraded" in k for k in hist), hist
            checks = Manager(cluster.mon).health()["checks"]
            assert "PG_DEGRADED" in checks
            assert "object copies" in checks["PG_DEGRADED"]["detail"]
            # revive: counts return to zero exactly when the legacy
            # poll reports recovered (within one report interval)
            cluster.revive(victim)
            min_epoch = cluster.mon.osdmap.epoch
            assert cluster.wait_recovered(timeout=60.0)
            assert cluster.wait_recovered_stats(
                timeout=10.0, min_epoch=min_epoch
            ), "stats plane never converged after recovery"
            assert pm.degraded_objects() == 0
            hist = pm.state_histogram()
            assert set(hist) == {"active+clean"}, hist
        finally:
            cluster.shutdown()

    def test_time_to_recovered_agreement(self):
        """The stats-derived time_to_recovered_s agrees with the
        legacy direct-state poll within about one report interval
        (0.5 s default + tick scheduling slack)."""
        from ceph_tpu.loadgen import (
            FaultSchedule,
            LoadCluster,
            WorkloadSpec,
            run_spec,
        )

        cluster = LoadCluster(
            n_osds=5, k=2, m=1, pg_num=4, chunk_size=1024,
        )
        try:
            report = run_spec(cluster, WorkloadSpec(
                mix={"seq_write": 2, "read": 1, "rmw_overwrite": 1},
                object_size=4096, max_objects=8, queue_depth=4,
                total_ops=60, seed=0x57A7,
            ), FaultSchedule.primary_kill(60, recovery_timeout=60.0))
        finally:
            cluster.shutdown()
        assert report["verify_failures"] == 0
        assert report["errors"] == 0
        assert report["recovered"]
        fault = report["fault"]
        assert "time_to_recovered_s" in fault, fault
        assert "time_to_recovered_legacy_s" in fault, fault
        # stats convergence trails the direct poll by at most one
        # report interval (+ a tick of scheduling slack)
        lag = (
            fault["time_to_recovered_s"]
            - fault["time_to_recovered_legacy_s"]
        )
        assert -0.001 <= lag <= 1.0, fault
        # the run report carries the stats-plane snapshot
        assert report["pg_states"] == {"active+clean": 4}
        assert report["degraded_objects"] == 0
        assert "active+clean" in report["status_digest"]

    def test_interval_zero_disables_reporting(self):
        from ceph_tpu.loadgen import LoadCluster

        with config.override(osd_stats_report_interval=0.0):
            cluster = LoadCluster(
                n_osds=4, k=2, m=1, pg_num=4, chunk_size=1024,
            )
            try:
                cluster.io.write_full("quiet", b"q" * 2048)
                time.sleep(0.8)  # several ticks
                assert cluster.pgmap.version == 0
            finally:
                cluster.shutdown()

    def test_forensics_bundle_captures_stats(self, tmp_path):
        from ceph_tpu.loadgen import LoadCluster, write_bundle

        cluster = LoadCluster(
            n_osds=4, k=2, m=1, pg_num=4, chunk_size=1024,
        )
        try:
            cluster.io.write_full("fb-obj", b"f" * 2048)
            manifest = write_bundle(
                str(tmp_path), report={"verify_failures": 0},
                reason="stats-plane unit", cluster=cluster,
            )
        finally:
            cluster.shutdown()
        assert "status.json" in manifest["files"]
        assert "pg_dump.json" in manifest["files"]
        bundle = tmp_path / manifest["stamp"]
        st = json.loads((bundle / "status.json").read_text())
        assert st["objects"] >= 1
        dump = json.loads((bundle / "pg_dump.json").read_text())
        assert dump["pg_stats"]


# -- the kept census (PR 36): a report re-reads what changed ------------

def _walk_census(store, osdmap):
    """The census as every report walked it until PR 36, kept as the
    reference: one pass over the whole store, the first key (sorted)
    of a loc speaking for its logical size. ({(pool_id, pgid): {loc:
    size}} for every PG, used bytes, key count)."""
    from ceph_tpu.cluster.osd_daemon import (
        head_of_loc,
        split_loc,
        split_shard_key,
    )
    from ceph_tpu.pipeline.rmw import OI_KEY, parse_oi
    from ceph_tpu.placement import stable_hash

    pg_nums = {s.pool_id: s.pg_num for s in osdmap.pools.values()}
    census, used = {}, 0
    keys = store.list_objects()
    for key in keys:
        used += store.stat(key)
        try:
            loc, _si = split_shard_key(key)
            pool_id, oid = split_loc(loc)
        except ValueError:
            continue
        if pool_id not in pg_nums:
            continue
        pgid = stable_hash(
            str(pool_id), head_of_loc(oid)
        ) % pg_nums[pool_id]
        sized = census.setdefault((pool_id, pgid), {})
        if loc in sized:
            continue
        try:
            size, _ev = parse_oi(store.getattr(key, OI_KEY))
        except (FileNotFoundError, KeyError, ValueError):
            size = 0
        sized[loc] = size
    return census, used, len(keys)


def _stats_moved(daemon, before=None):
    now = dict(daemon.stats_pc.dump())
    if before is None:
        return now
    return {k: now[k] - before[k] for k in ("census_keys", "census_walks")}


def _assert_reports_equal_walk(cluster):
    """Every live daemon cuts a report; what the PGMap then holds for
    each PG and OSD equals the reference walk of that daemon's store,
    field for field."""
    osdmap = cluster.mon.osdmap
    live = [cluster.daemons[i] for i in cluster.live_osds()]
    for d in live:
        deadline = time.monotonic() + 10.0
        while d.osdmap.epoch < osdmap.epoch:
            assert time.monotonic() < deadline, "map never reached the OSD"
            time.sleep(0.02)
        d.report_pg_stats(force=True)
    pm = cluster.pgmap
    checked = 0
    for d in live:
        census, used, n_keys = _walk_census(d.store, osdmap)
        assert d._census._pgs == census
        stat = pm.osd[d.osd_id]
        assert (stat.used_bytes, stat.num_objects) == (used, n_keys)
        for pool, spec in osdmap.pools.items():
            for pgid in range(spec.pg_num):
                if osdmap.pg_primary(pool, pgid) != d.osd_id:
                    continue
                sized = census.get((spec.pool_id, pgid), {})
                got = pm.pg[(spec.pool_id, pgid)]
                assert got.primary == d.osd_id
                assert got.num_objects == len(sized), (pool, pgid)
                assert got.num_bytes == sum(sized.values()), (pool, pgid)
                checked += 1
    return checked


def _fake_map(**pg_nums):
    """What ``_StatsCensus.refresh`` reads of an OSDMap."""
    from types import SimpleNamespace

    return SimpleNamespace(pools={
        name: SimpleNamespace(pool_id=int(name[1:]), pg_num=n)
        for name, n in pg_nums.items()
    })


def _bare_census(n_keys, pool_id=1):
    """A MemStore holding ``n_keys`` shard keys with OI attrs, and a
    census over it with a counter set of its own."""
    from ceph_tpu.cluster.osd_daemon import (
        _StatsCensus,
        make_loc,
        make_stats_perf,
        shard_key,
    )
    from ceph_tpu.pipeline.rmw import OI_KEY, pack_oi
    from ceph_tpu.store import MemStore, Transaction

    store = MemStore()

    def put(i, size=4096):
        key = shard_key(make_loc(pool_id, f"obj-{i:06d}"), i % 3)
        store.queue_transactions(
            Transaction().write(key, 0, b"x" * (size // 4))
            .setattr(key, OI_KEY, pack_oi(size))
        )
        return key

    for i in range(n_keys):
        put(i)
    census = _StatsCensus(store, make_stats_perf("test.census.stats"))
    return store, census, put


class TestKeptCensus:
    @pytest.mark.parametrize("backend", ["mem", "file"])
    def test_report_equals_a_full_walk(self, backend, tmp_path):
        """Random writefull / append / overwrite / truncate / remove /
        snapshot clone / recovery push over two pools: after every
        batch each OSD's report equals the walk. MemStore keeps the
        note; FileStore keeps none and walks every time."""
        import random

        from ceph_tpu.cluster.osd_daemon import SNAP_SEP
        from ceph_tpu.loadgen import LoadCluster
        from ceph_tpu.store import FileStore

        factory = None
        if backend == "file":
            def factory(i):
                return FileStore(str(tmp_path / f"osd{i}"))
        rng = random.Random(0x36 if backend == "mem" else 0x37)
        with config.override(osd_stats_report_interval=0.0):
            cluster = LoadCluster(
                n_osds=5, k=2, m=1, pg_num=4, chunk_size=1024,
                store_factory=factory,
            )
            try:
                cluster.mon.osd_pool_create("second", 3, "loadprof")
                ios = [cluster.io, cluster.client.open_ioctx("second")]
                live: list[tuple[int, str]] = []

                def one_op():
                    kind = rng.choice([
                        "writefull", "writefull", "append", "overwrite",
                        "truncate", "remove",
                    ])
                    if kind == "writefull" or not live:
                        p = rng.randrange(2)
                        name = f"o{rng.randrange(24)}"
                        ios[p].write_full(
                            name, rng.randbytes(rng.randrange(1, 9000))
                        )
                        if (p, name) not in live:
                            live.append((p, name))
                        return
                    p, name = rng.choice(live)
                    if kind == "append":
                        ios[p].append(name, rng.randbytes(2048))
                    elif kind == "overwrite":
                        ios[p].write(name, rng.randbytes(700), offset=100)
                    elif kind == "truncate":
                        ios[p].truncate(name, rng.randrange(0, 3000))
                    else:
                        ios[p].remove(name)
                        live.remove((p, name))

                for batch in range(6):
                    if batch == 2:
                        ios[0].snap_create("s1")
                        ios[1].snap_create("s1")
                    victim = None
                    if batch == 4:
                        # writes a dead OSD misses come back to it as
                        # recovery pushes, through its store's apply
                        victim = cluster.least_primary_osd()
                        cluster.kill(victim)
                    for _ in range(12):
                        one_op()
                    if victim is not None:
                        cluster.revive(victim)
                        cluster.daemons[victim].report_pg_stats(force=True)
                        assert cluster.wait_recovered(timeout=60.0)
                    assert _assert_reports_equal_walk(cluster) == 7
                pgs_with_keys = {
                    pg for d in cluster.daemons.values()
                    for pg in d._census._pgs
                }
                assert len(pgs_with_keys) >= 3
                assert len({pool_id for pool_id, _ in pgs_with_keys}) == 2
                assert any(
                    SNAP_SEP in loc for d in cluster.daemons.values()
                    for sized in d._census._pgs.values() for loc in sized
                ), "no write after the snapshot made a clone"
                for i, d in cluster.daemons.items():
                    s = _stats_moved(d)
                    if backend == "file":
                        assert s["census_walks"] == s["reports"]
                    else:
                        assert s["census_walks"] == 1, (i, s)
            finally:
                cluster.shutdown()

    @pytest.mark.parametrize("touched", [0, 1, 37])
    def test_a_report_rereads_the_touched_keys_only(self, touched):
        store, census, put = _bare_census(2000)
        osdmap = _fake_map(p1=8)
        assert census.refresh(osdmap) == (2000 * 1024, 2000)
        assert census.perf.get("census_keys") == 2000
        assert census.perf.get("census_walks") == 1
        for i in range(touched):
            put(i * 50, size=8192)
        used, n_keys = census.refresh(osdmap)
        assert census.perf.get("census_keys") == 2000 + touched
        assert census.perf.get("census_walks") == 1
        assert (used, n_keys) == (2000 * 1024 + touched * 1024, 2000)
        assert (census._pgs, used, n_keys) == _walk_census(store, osdmap)

    def test_a_pg_num_change_is_one_walk(self):
        store, census, put = _bare_census(300)
        census.refresh(_fake_map(p1=8))
        put(5, size=100)
        wider = _fake_map(p1=16)
        used, n_keys = census.refresh(wider)
        assert census.perf.get("census_walks") == 2
        assert (census._pgs, used, n_keys) == _walk_census(store, wider)
        assert len(census._pgs) > 8
        put(6, size=100)
        census.refresh(wider)
        assert census.perf.get("census_walks") == 2
        assert census.perf.get("census_keys") == 300 + 300 + 1
        # a pool the census holds no key of changes nothing
        census.refresh(_fake_map(p1=16, p2=4))
        assert census.perf.get("census_walks") == 2

    def test_keys_of_a_pool_the_map_lacks_are_placed_when_it_comes(self):
        store, census, put = _bare_census(40, pool_id=2)
        census.refresh(_fake_map(p1=8))
        assert census._pgs == {} and len(census._keys) == 40
        both = _fake_map(p1=8, p2=4)
        census.refresh(both)
        assert census.perf.get("census_walks") == 2
        assert (census._pgs, 40 * 1024, 40) == _walk_census(store, both)

    def test_an_overflowed_note_is_one_walk(self, monkeypatch):
        from ceph_tpu.store import memstore

        monkeypatch.setattr(memstore, "NOTE_MAX_KEYS", 16)
        store, census, put = _bare_census(10)
        osdmap = _fake_map(p1=8)
        census.refresh(osdmap)
        for i in range(10, 40):
            put(i)
        assert store._touched is None
        census.refresh(osdmap)
        assert census.perf.get("census_walks") == 2
        assert (census._pgs, 40 * 1024, 40) == _walk_census(store, osdmap)
        put(41)
        census.refresh(osdmap)
        assert census.perf.get("census_walks") == 2
        assert census.perf.get("census_keys") == 10 + 40 + 1

    def test_a_second_reader_of_the_note_costs_each_a_walk(self):
        """The cursor: a reader whose cursor is not the newest handed
        out missed keys the other took, and is told so."""
        store, census, put = _bare_census(20)
        osdmap = _fake_map(p1=8)
        census.refresh(osdmap)
        put(3, size=64)
        keys, _cursor = store.touched_since(None)
        assert keys is None
        used, n_keys = census.refresh(osdmap)
        assert census.perf.get("census_walks") == 2
        assert (census._pgs, used, n_keys) == _walk_census(store, osdmap)

    def test_two_shards_of_one_object_and_removal(self):
        """An OSD holds shards 0 and 2 of one loc while backfill runs:
        the smallest key's OI speaks, as in the walk; the object goes
        when its last key does."""
        from ceph_tpu.cluster.osd_daemon import make_loc, shard_key
        from ceph_tpu.pipeline.rmw import OI_KEY, pack_oi
        from ceph_tpu.store import Transaction

        store, census, put = _bare_census(0)
        osdmap = _fake_map(p1=4)
        loc = make_loc(1, "twice")
        k0, k2 = shard_key(loc, 0), shard_key(loc, 2)
        census.refresh(osdmap)
        for key, size in ((k2, 500), (k0, 900)):
            store.queue_transactions(
                Transaction().write(key, 0, b"y" * 10)
                .setattr(key, OI_KEY, pack_oi(size))
            )
            census.refresh(osdmap)
            assert (census._pgs, *census.refresh(osdmap)) == (
                _walk_census(store, osdmap)
            )
        assert list(census._pgs.values()) == [{loc: 900}]
        store.queue_transactions(Transaction().remove(k0))
        census.refresh(osdmap)
        assert list(census._pgs.values()) == [{loc: 500}]
        store.queue_transactions(Transaction().remove(k2))
        assert census.refresh(osdmap) == (0, 0)
        assert census._pgs == {} and census._shards == {}
        assert census.perf.get("census_walks") == 1

    def test_a_new_leader_reports_right_with_no_walk(self):
        from ceph_tpu.loadgen import LoadCluster

        with config.override(osd_stats_report_interval=0.0):
            cluster = LoadCluster(
                n_osds=5, k=2, m=1, pg_num=4, chunk_size=1024,
            )
            try:
                for i in range(16):
                    cluster.io.write_full(f"lead-{i}", b"L" * (512 + 64 * i))
                assert _assert_reports_equal_walk(cluster) == 4
                before_map = cluster.mon.osdmap
                victim = cluster.most_primary_osd()
                before = {
                    i: _stats_moved(d) for i, d in cluster.daemons.items()
                }
                cluster.kill(victim)
                osdmap = cluster.mon.osdmap
                took_over = {
                    osdmap.pg_primary(cluster.pool, pgid)
                    for pgid in range(4)
                    if before_map.pg_primary(cluster.pool, pgid) == victim
                }
                assert took_over and victim not in took_over
                assert _assert_reports_equal_walk(cluster) == 4
                for i in cluster.live_osds():
                    moved = _stats_moved(cluster.daemons[i], before[i])
                    assert moved["census_walks"] == 0, (i, moved)
                for pgid in range(4):
                    got = cluster.pgmap.pg[(osdmap.pools[cluster.pool].pool_id, pgid)]
                    assert got.primary in cluster.live_osds()
            finally:
                cluster.shutdown()

    def test_a_revived_osd_walks_once(self):
        from ceph_tpu.loadgen import LoadCluster

        with config.override(osd_stats_report_interval=0.0):
            cluster = LoadCluster(
                n_osds=5, k=2, m=1, pg_num=4, chunk_size=1024,
            )
            try:
                for i in range(8):
                    cluster.io.write_full(f"rv-{i}", b"R" * 3000)
                victim = cluster.most_primary_osd()
                cluster.daemons[victim].report_pg_stats(force=True)
                cluster.kill(victim)
                cluster.io.write_full("rv-while-down", b"D" * 3000)
                cluster.revive(victim)
                d = cluster.daemons[victim]
                assert _stats_moved(d)["census_walks"] == 0
                d.report_pg_stats(force=True)
                assert _stats_moved(d)["census_walks"] == 1
                assert cluster.wait_recovered(timeout=60.0)
                cluster.io.write_full("rv-after", b"A" * 3000)
                assert _assert_reports_equal_walk(cluster) == 4
                d.report_pg_stats(force=True)
                s = _stats_moved(d)
                assert s["census_walks"] == 1 and s["reports"] >= 3, s
            finally:
                cluster.shutdown()

    def test_the_tick_counts_its_reports(self):
        """The five counters of ``osd.N.stats`` move on the tick's own
        reports, and an idle store's report re-reads nothing."""
        from ceph_tpu.loadgen import LoadCluster

        cluster = LoadCluster(n_osds=4, k=2, m=1, pg_num=4, chunk_size=1024)
        try:
            cluster.io.write_full("ticked", b"t" * 2048)
            d = cluster.daemons[0]
            deadline = time.monotonic() + 10.0
            while d.stats_pc.get("reports") < 3:
                assert time.monotonic() < deadline
                time.sleep(0.1)
            settled = _stats_moved(d)
            assert settled["census_walks"] == 1
            assert 0 < settled["report_cpu_seconds"]
            assert settled["report_cpu_seconds"] <= (
                settled["report_seconds"] * 1.5 + 0.01
            )
            while d.stats_pc.get("reports") < settled["reports"] + 2:
                assert time.monotonic() < deadline
                time.sleep(0.1)
            assert _stats_moved(d, settled) == {
                "census_keys": 0, "census_walks": 0,
            }
        finally:
            cluster.shutdown()
