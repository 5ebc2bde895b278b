"""loadgen/ — live-cluster load generation (the radosbench-analog
tier): spec/histogram/recorder units, the deterministic-seed tier-1
smoke (mixed workload + one OSD kill/revive over a REAL socket
cluster, zero verification failures, exactly-once accounting,
recovered at exit), the bench_cli surface, client-side perf-counter
observability, and the _op_lock poll-parking regression (ADVICE r5
osd_daemon:1912)."""

import threading
import time

import numpy as np
import pytest

from ceph_tpu.loadgen import (
    FaultEvent,
    FaultSchedule,
    LoadCluster,
    LoadGenerator,
    Log2Histogram,
    Popularity,
    RunRecorder,
    WorkloadSpec,
    expected_image,
    object_bytes,
    parse_mix,
    patch_bytes,
    preset,
    run_spec,
)


# -- histogram ----------------------------------------------------------
class TestLog2Histogram:
    def test_percentiles_uniform(self):
        h = Log2Histogram()
        for ms in range(1, 1001):  # 1..1000 ms uniform
            h.record(ms / 1e3)
        assert h.n == 1000
        assert abs(h.percentile(50) - 0.5) / 0.5 < 0.1
        assert abs(h.percentile(99) - 0.99) / 0.99 < 0.1
        assert h.percentile(100) == h.max == 1.0
        assert h.min == 1e-3

    def test_single_sample_exact(self):
        h = Log2Histogram()
        h.record(0.0423)
        for p in (1, 50, 99, 100):
            assert h.percentile(p) == 0.0423

    def test_extremes_clamp_but_count(self):
        h = Log2Histogram()
        h.record(1e-9)    # below range
        h.record(1e6)     # above range
        assert h.n == 2
        assert h.max == 1e6

    def test_merge(self):
        a, b = Log2Histogram(), Log2Histogram()
        for v in (0.001, 0.002, 0.004):
            a.record(v)
        for v in (0.008, 0.016):
            b.record(v)
        a.merge(b)
        assert a.n == 5
        assert a.max == 0.016
        assert a.min == 0.001
        assert sum(a.counts) == 5

    def test_perf_buckets_shape(self):
        h = Log2Histogram()
        h.record(0.01)
        bounds, counts = h.perf_buckets()
        assert len(counts) == len(bounds) + 1
        assert sorted(bounds) == bounds
        assert sum(counts) == 1


# -- spec ---------------------------------------------------------------
class TestSpec:
    def test_parse_mix(self):
        mix = parse_mix("seq_write=2, read=5,rmw_overwrite")
        assert mix == {
            "seq_write": 2.0, "read": 5.0, "rmw_overwrite": 1.0
        }

    def test_parse_mix_rejects_unknown(self):
        with pytest.raises(ValueError):
            parse_mix("seq_write=1,shred=9")

    def test_spec_validation(self):
        with pytest.raises(ValueError):
            WorkloadSpec(mix={"nope": 1.0})
        with pytest.raises(ValueError):
            WorkloadSpec(total_ops=10, warmup_ops=10)
        with pytest.raises(ValueError):
            WorkloadSpec(popularity="hot")

    def test_preset_overrides(self):
        s = preset("smoke", total_ops=33, seed=5)
        assert s.total_ops == 33 and s.seed == 5

    def test_zipfian_skew(self):
        """The zipfian law must actually concentrate mass (a uniform
        sampler in zipf clothing would fake hot-set behavior)."""
        spec = WorkloadSpec(popularity="zipfian", zipf_theta=1.2)
        pop = Popularity(spec)
        rng = np.random.default_rng(3)
        picks = [pop.pick(rng, 100) for _ in range(4000)]
        _, counts = np.unique(picks, return_counts=True)
        top = np.sort(counts)[::-1]
        assert top[0] > 4000 * 0.10      # hottest object >10% of ops
        uniform = Popularity(WorkloadSpec(popularity="uniform"))
        upicks = [uniform.pick(rng, 100) for _ in range(4000)]
        _, uc = np.unique(upicks, return_counts=True)
        assert np.sort(uc)[::-1][0] < 4000 * 0.05

    def test_content_determinism_and_patch_replay(self):
        base = object_bytes(7, 3, 1, 4096)
        assert base == object_bytes(7, 3, 1, 4096)
        assert base != object_bytes(7, 3, 2, 4096)
        off, payload = patch_bytes(7, 3, 1, 1, 4096, 512)
        img = bytearray(base)
        img[off:off + len(payload)] = payload
        assert expected_image(7, 3, 1, 1, 4096, 512) == bytes(img)
        assert expected_image(7, 3, 1, 0, 4096, 512) == base


# -- recorder -----------------------------------------------------------
class TestRecorder:
    def test_warmup_exclusion_and_exactly_once(self):
        r = RunRecorder(warmup_ops=3)
        for i in range(10):
            r.record("read", 0.01, 100)
        r.record("read", 0.01, 100, ok=False)
        r.finish()
        rep = r.report()
        assert rep["classes"]["read"]["warmup_ops"] == 3
        assert rep["classes"]["read"]["ops"] == 7
        assert rep["classes"]["read"]["errors"] == 1
        assert rep["ops_accounted"] == 11
        assert rep["bytes"] == 700  # warmup bytes excluded

    def test_window_cut(self):
        r = RunRecorder()
        t0 = time.monotonic()
        r.record("read", 0.0, 1000)
        mid = time.monotonic()
        time.sleep(0.02)
        r.record("read", 0.0, 5000)
        r.finish()
        assert r.window_gbps(mid, time.monotonic()) > 0
        total = r.window_gbps(t0 - 1, time.monotonic())
        assert total > 0

    def test_device_clock_replaces_host_floor(self):
        """p99_dev = host_p99 - host_min + dev_per_op: the constant
        host floor (dispatch overhead) drops out, the measured device
        time replaces it."""
        r = RunRecorder()
        # synthetic host floor: 100 ms + spread
        for lat in (0.100, 0.101, 0.102, 0.110):
            for _ in range(25):
                r.record("read", lat, 100)
        r.device_floor_s = 0.002
        r.finish()
        rep = r.report()
        dev = rep["lat_p99_ms_device"]
        host = rep["lat_p99_ms"]
        assert host >= 100.0  # host row carries the floor
        # device row = spread (~10ms) + dev floor (2ms), NOT ~110
        assert dev == pytest.approx(host - 100.0 + 2.0, abs=1.5)


# -- the tier-1 cluster smoke ------------------------------------------
@pytest.fixture(scope="module")
def smoke_run():
    """One deterministic-seed mixed run with a kill/revive cycle,
    shared by the assertion tests below (booting a socket cluster per
    assertion would triple the tier's wall time)."""
    cluster = LoadCluster(
        n_osds=5, k=2, m=1, pg_num=4, chunk_size=1024,
    )
    try:
        spec = WorkloadSpec(
            mix={"seq_write": 3, "rand_write": 1, "read": 3,
                 "reconstruct_read": 1, "rmw_overwrite": 1},
            object_size=8192, max_objects=16, queue_depth=4,
            total_ops=80, warmup_ops=8, popularity="zipfian",
            seed=7,
        )
        # the gate kills the MOST-primary member: every one of its
        # PGs runs a takeover election mid-run, and the revive forces
        # the returning ex-primary back through peering. The round-8
        # smoke deliberately killed a non-primary to dodge the
        # takeover race; the peering FSM closed it (ROADMAP #1), so
        # the racy path is now the default CI target.
        victim = cluster.most_primary_osd()
        faults = FaultSchedule(
            [FaultEvent(26, "kill", osd=victim),
             FaultEvent(53, "revive", osd=victim)],
            recovery_timeout=60,
        )
        gen = LoadGenerator(cluster, spec, faults)
        report = gen.run()
        yield cluster, spec, gen, report
    finally:
        cluster.shutdown()


class TestClusterSmoke:
    def test_zero_verification_failures(self, smoke_run):
        _c, _s, _g, report = smoke_run
        assert report["verify_failures"] == 0
        # op errors (a client giving up mid-kill-window) are rare but
        # legal under thrash; they must stay small and accounted —
        # verification integrity is the hard invariant
        assert report["errors"] <= 3, report.get("error_samples")

    def test_exactly_once_accounting(self, smoke_run):
        _c, spec, _g, report = smoke_run
        assert report["ops_in"] == spec.total_ops
        assert report["ops_accounted"] == report["ops_in"]
        assert report["exactly_once"] is True
        # histogram/counter consistency: measured + warmup == total
        per_class = sum(
            e["ops"] + e["warmup_ops"] + e["errors"]
            for e in report["classes"].values()
        )
        assert per_class == report["ops_in"]

    def test_fault_metrics_and_recovery(self, smoke_run):
        cluster, _s, _g, report = smoke_run
        assert report["fault"]["degraded_window_s"] > 0
        assert "time_to_recovered_s" in report["fault"]
        assert report["recovered"] is True
        assert cluster.is_recovered()
        assert cluster.scrub_clean()

    def test_degraded_reads_happened(self, smoke_run):
        """The kill window must produce true reconstruct reads (or at
        least have tried: requests outside the window reclassify)."""
        _c, _s, gen, report = smoke_run
        recon = report["classes"].get(
            "reconstruct_read", {}
        ).get("ops", 0)
        assert recon + report["reclassified_reads"] > 0

    def test_throughput_rows_present(self, smoke_run):
        _c, _s, _g, report = smoke_run
        assert report["bytes"] > 0
        assert report["gbps"] > 0
        assert report["iops"] > 0
        assert report["lat_p99_ms"] > 0
        for cls in ("seq_write", "read"):
            assert report["classes"][cls]["ops"] > 0

    def test_client_counters_observable(self, smoke_run):
        """The run is visible from the admin socket / exporter like
        daemon-side ops: objecter counters + per-class counters."""
        from ceph_tpu.utils.admin_socket import admin_socket
        from ceph_tpu.utils.exporter import render_exposition

        _c, _s, _g, report = smoke_run
        dump = admin_socket.execute("perf dump")
        client = dump["loadgen_client"]
        completed = report["ops_in"] - report["errors"]
        assert client["op_completed"] >= completed
        assert client["op_inflight"] == 0
        assert client["verify_failed"] == 0
        lg = dump["loadgen"]
        per_class = {
            cls: e["ops"] + e["warmup_ops"]
            for cls, e in report["classes"].items()
        }
        for cls, n in per_class.items():
            assert lg[f"ops_{cls}"] == n
        assert lg["op_latency"]["counts"]
        assert lg["op_latency"]["sum"] > 0
        text = render_exposition()
        assert "ceph_tpu_op_completed" in text
        assert 'ceph_tpu_ops_seq_write{set="loadgen"}' in text
        assert "ceph_tpu_op_latency_sum" in text


# -- bench_cli surface --------------------------------------------------
class TestCli:
    def test_smoke_two_column_contract(self, capsys):
        from ceph_tpu import bench_cli

        args = bench_cli.parse_args(["loadgen", "--smoke"])
        elapsed, kib = bench_cli.run(args)
        assert elapsed > 0
        assert kib > 0

    def test_loadgen_flags_parse(self):
        from ceph_tpu import bench_cli

        args = bench_cli.parse_args([
            "loadgen", "--mix", "seq_write=1,read=2",
            "--objects", "8", "--object-size", "4096",
            "--queue-depth", "2", "--ops", "20",
            "--popularity", "zipfian", "--fault-at", "5",
            "--revive-at", "10", "-P", "k=2", "-P", "m=1",
        ])
        assert args.workload == "loadgen"
        assert args.fault_at == 5

    def test_net_fault_flags_parse(self):
        from ceph_tpu import bench_cli

        args = bench_cli.parse_args([
            "loadgen", "--smoke", "--net-fault", "flaky",
            "--net-drop", "0.05", "--net-dup", "0.01",
            "--net-delay-ms", "2",
        ])
        assert args.net_fault == "flaky"
        assert args.net_drop == 0.05
        args = bench_cli.parse_args(
            ["loadgen", "--smoke", "--net-fault", "partition"]
        )
        assert args.net_fault == "partition"
        with pytest.raises(SystemExit):
            bench_cli.parse_args(
                ["loadgen", "--net-fault", "bogus"]
            )

    def test_bad_mix_rejected(self):
        with pytest.raises(ValueError):
            parse_mix("")

    def test_lockdep_flag_parses(self):
        from ceph_tpu import bench_cli

        args = bench_cli.parse_args(["loadgen", "--smoke", "--lockdep"])
        assert args.lockdep is True
        args = bench_cli.parse_args(["loadgen", "--smoke"])
        assert args.lockdep is False


# -- _op_lock poll parking (ADVICE r5 osd_daemon:1912) ------------------
class TestPollParking:
    def _two_oids_same_primary(self, mon, pool):
        """Two distinct objects served by the same primary daemon."""
        by_primary: dict[int, list[str]] = {}
        for i in range(64):
            oid = f"park-{i}"
            p = mon.osdmap.primary(pool, oid)
            by_primary.setdefault(p, []).append(oid)
            if len(by_primary[p]) == 2:
                return p, by_primary[p]
        raise AssertionError("no two objects share a primary?")

    def test_fanout_does_not_stall_other_objects(self):
        """A torn object's durability fan-out must NOT serialize the
        daemon: while object A's poll runs (on its own thread, A
        parked in the client's retry loop), a write to object B
        through the SAME primary completes. Before the fix the poll
        ran under _op_lock ON the op worker and B waited out A's
        full fan-out deadline."""
        from ceph_tpu.cluster.osd_daemon import make_loc

        cluster = LoadCluster(
            n_osds=4, k=2, m=1, pg_num=4, chunk_size=1024,
        )
        try:
            io = cluster.io
            primary, (oid_a, oid_b) = self._two_oids_same_primary(
                cluster.mon, cluster.pool
            )
            io.write(oid_a, b"a" * 512)
            io.write(oid_b, b"b" * 512)
            d = cluster.daemons[primary]
            pool_id = cluster.mon.osdmap.pools[cluster.pool].pool_id
            loc_a = make_loc(pool_id, oid_a)
            # seed a suspect storage-seeded window entry for A, and a
            # slow poll whose verdict proves it durable (k=2 support)
            poll_started = threading.Event()

            def slow_poll(pg, loc):
                poll_started.set()
                time.sleep(1.2)
                return [[("phantom.1", 123)]] * 3, []

            d._req_windows[loc_a] = [("phantom.1", 123)]
            d._req_unverified[loc_a] = {"phantom.1"}
            d._poll_req_state = slow_poll

            t_a: list[float] = []

            def write_a():
                t0 = time.monotonic()
                io.write(oid_a, b"A" * 512)
                t_a.append(time.monotonic() - t0)

            th = threading.Thread(target=write_a)
            th.start()
            assert poll_started.wait(5.0), "fan-out never started"
            t0 = time.monotonic()
            io.write(oid_b, b"B" * 512)  # other object, same primary
            dt_b = time.monotonic() - t0
            th.join(10.0)
            assert not th.is_alive()
            assert dt_b < 0.8, (
                f"write to another object stalled {dt_b:.2f}s behind "
                "a parked durability fan-out"
            )
            assert t_a and t_a[0] >= 1.0  # A really waited the poll
            # the phantom entry settled durable and A's write landed
            assert loc_a not in d._req_unverified
            assert io.read(oid_a) == b"A" * 512
        finally:
            cluster.shutdown()

    def test_poll_budget_bounds_poller_threads(self):
        """Budget exhausted -> _take_or_spawn_poll declines (eagain
        path) instead of spawning more poller threads."""
        cluster = LoadCluster(
            n_osds=4, k=2, m=1, pg_num=4, chunk_size=1024,
        )
        try:
            d = cluster.daemons[0]
            d._req_poll_sem = threading.Semaphore(0)
            with d._op_lock:
                assert d._take_or_spawn_poll(None, "0:x") is None
            assert "0:x" not in d._req_polls_inflight
        finally:
            cluster.shutdown()

    def test_cached_verdict_consumed_on_retry(self):
        """A finished poll's verdict is consumed by the next attempt
        even inside the cooldown window (the retry must not wait out
        a second cooldown for a result that is already there)."""
        cluster = LoadCluster(
            n_osds=4, k=2, m=1, pg_num=4, chunk_size=1024,
        )
        try:
            d = cluster.daemons[0]
            d._req_poll_at["0:y"] = time.monotonic()  # cooldown hot
            with d._req_poll_lock:
                d._req_poll_results["0:y"] = ([["w"]], [])
            with d._op_lock:
                assert d._take_or_spawn_poll(None, "0:y") == (
                    [["w"]], []
                )
                # consumed exactly once
                assert d._take_or_spawn_poll(None, "0:y") is None
        finally:
            cluster.shutdown()


# -- CLAY fractional repair at the cluster tier ------------------------
@pytest.fixture(scope="module")
def clay_smoke_run():
    """CLAY pool over real sockets: write -> kill -> rewrite while
    down -> revive, so the returning shard's catch-up recovery runs
    through ``get_repair_subchunks`` sub-chunk reads; then a short
    reconstruct-read generator run against the degraded pool."""
    from ceph_tpu.utils import perf_collection

    cluster = LoadCluster(
        n_osds=6, k=4, m=2, d=5, pg_num=2, chunk_size=1024,
        plugin="clay",
    )
    try:
        rng = np.random.default_rng(3)
        n_obj, size = 4, 8192
        data0 = bytes(rng.integers(0, 256, size, np.uint8))
        for i in range(n_obj):
            cluster.io.write_full(f"clayobj{i}", data0)
        victim = cluster.least_primary_osd()
        cluster.kill(victim)

        # generator phase against the degraded pool: every read is a
        # reconstruct (the victim's shards decode from survivors)
        spec = WorkloadSpec(
            mix={"seq_write": 1, "reconstruct_read": 3},
            object_size=size, max_objects=4, queue_depth=2,
            total_ops=24, warmup_ops=4, seed=13,
        )
        report = LoadGenerator(cluster, spec).run()

        # shard catch-up: overwrite while the victim is down, revive,
        # and measure what recovery READ to rebuild what it PUSHED.
        # Deltas against a pre-revive snapshot: perf_collection is
        # process-global, and earlier test modules leave their own
        # recovery counters behind.
        def _rec_totals():
            dump = perf_collection.dump()
            return {
                key: sum(
                    v.get(key, 0)
                    for name, v in dump.items()
                    if ".recovery" in name
                )
                for key in (
                    "recovery_ops", "recovery_read_bytes",
                    "recovered_bytes",
                )
            }

        data1 = bytes(rng.integers(0, 256, size, np.uint8))
        for i in range(n_obj):
            cluster.io.write_full(f"clayobj{i}", data1)
        before = _rec_totals()
        cluster.revive(victim)
        recovered = cluster.wait_recovered(60)
        after = _rec_totals()
        rec = {k: after[k] - before[k] for k in after}
        yield cluster, report, recovered, rec, data1, n_obj
    finally:
        cluster.shutdown()


class TestClayClusterSmoke:
    def test_reconstruct_reads_verified(self, clay_smoke_run):
        _c, report, _rec, _r, _d, _n = clay_smoke_run
        assert report["verify_failures"] == 0
        assert report["classes"]["reconstruct_read"]["ops"] > 0

    def test_recovery_reads_fractional(self, clay_smoke_run):
        """The MSR observable: rebuilding the returned shard read
        d/(q*k) of what a naive k-full-chunk decode reads — for
        (4,2,d=5) that is 5/8 of the naive bytes, strictly less."""
        _c, _rep, recovered, rec, _d, _n = clay_smoke_run
        assert recovered
        assert rec["recovery_ops"] > 0
        assert rec["recovered_bytes"] > 0
        naive = 4 * rec["recovered_bytes"]  # k full survivor chunks
        assert 0 < rec["recovery_read_bytes"] < naive
        frac = rec["recovery_read_bytes"] / naive
        assert frac == pytest.approx(5 / 8, rel=0.05), frac

    def test_recovered_content_intact(self, clay_smoke_run):
        cluster, _rep, _recov, _rec, data1, n_obj = clay_smoke_run
        for i in range(n_obj):
            assert cluster.io.read(
                f"clayobj{i}", 0, len(data1)
            ) == data1
        assert cluster.scrub_clean()


# -- full-size run (excluded from tier-1 by the slow marker) -----------
@pytest.mark.slow
def test_full_size_mixed_run():
    cluster = LoadCluster(n_osds=6, k=3, m=2, pg_num=8,
                          chunk_size=4096)
    try:
        spec = preset("mixed", total_ops=400, seed=11)
        faults = FaultSchedule(
            [FaultEvent(120, "kill"), FaultEvent(260, "revive")]
        )
        report = run_spec(cluster, spec, faults)
        assert report["verify_failures"] == 0
        assert report["exactly_once"]
        assert report["recovered"]
    finally:
        cluster.shutdown()
