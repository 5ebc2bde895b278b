"""The deterministic network-fault plane (msg/messenger.py net_faults
— the tc/netem analog) and the machinery it exists to exercise: the
objecter timeout/backoff resend ladder under sustained loss, duplicate
sub-write absorption, reqid dedup across lossy links, and the
loadgen chaos/partition legs (ISSUE 9 tentpole + satellite 4).
"""

import threading
import time

import pytest

from ceph_tpu.msg.messenger import (
    LinkRule,
    Messenger,
    NetFaultPlane,
    net_faults,
)


@pytest.fixture(autouse=True)
def clean_plane():
    net_faults.clear()
    net_faults.reset_counters()
    yield
    net_faults.clear()
    net_faults.reset_counters()


# ---------------------------------------------------------------------------
# plane units: decision determinism and per-fault semantics
# ---------------------------------------------------------------------------
class TestPlaneUnits:
    def _pattern(self, seed, n=300, rule=None):
        plane = NetFaultPlane().configure(seed)
        plane.add_rule("a", "b", rule or LinkRule(drop=0.3, dup=0.2))
        out = []
        for i in range(n):
            hits = []
            plane.process("a", "b", lambda i=i, h=hits: h.append(i))
            out.append(len(hits))  # 0 = dropped, 1 = clean, 2 = dup
        return out

    def test_same_seed_same_firings(self):
        """The acceptance determinism clause: same seed => the same
        per-link fault firing sequence, frame for frame."""
        assert self._pattern(1234) == self._pattern(1234)

    def test_different_seed_different_firings(self):
        assert self._pattern(1234) != self._pattern(4321)

    def test_link_lanes_are_independent(self):
        """osd.0->osd.1 and osd.0->osd.2 draw from different RNG
        streams (one link's traffic cannot perturb another's
        schedule — what makes multi-link runs composable)."""
        plane = NetFaultPlane().configure(7)
        plane.add_rule("osd.*", "osd.*", LinkRule(drop=0.5))
        seq = {}
        for dst in ("osd.1", "osd.2"):
            got = []
            for _ in range(64):
                hits = []
                plane.process("osd.0", dst, lambda h=hits: h.append(1))
                got.append(bool(hits))
            seq[dst] = got
        assert seq["osd.1"] != seq["osd.2"]

    def test_drop_rate_and_counters(self):
        pat = self._pattern(99, n=1000, rule=LinkRule(drop=0.5))
        dropped = pat.count(0)
        assert 400 < dropped < 600  # binomial(1000, .5) well inside

    def test_dup_delivers_twice(self):
        pat = self._pattern(5, n=50, rule=LinkRule(dup=1.0))
        assert pat == [2] * 50

    def test_partition_drops_everything(self):
        plane = NetFaultPlane().configure(1)
        plane.partition("b")
        hits = []
        for _ in range(20):
            plane.process("a", "b", lambda: hits.append("in"))
            plane.process("b", "a", lambda: hits.append("out"))
        assert hits == []
        assert plane.counters["frames_dropped"] == 40

    def test_asymmetric_partition_is_one_way(self):
        """asymmetric=True cuts only peers->victim: the victim keeps
        transmitting into the void (the half-dead re-election case)."""
        plane = NetFaultPlane().configure(1)
        plane.partition("b", asymmetric=True)
        hits = []
        plane.process("a", "b", lambda: hits.append("to_victim"))
        plane.process("b", "a", lambda: hits.append("from_victim"))
        assert hits == ["from_victim"]

    def test_delay_defers_delivery(self):
        plane = NetFaultPlane().configure(3)
        plane.add_rule("a", "b", LinkRule(delay_ms=80))
        done = threading.Event()
        t0 = time.monotonic()
        plane.process("a", "b", done.set)
        assert not done.is_set()  # not delivered synchronously
        assert done.wait(2.0)
        assert time.monotonic() - t0 >= 0.06
        assert plane.counters["frames_delayed"] == 1

    def test_reorder_swaps_with_next_frame(self):
        plane = NetFaultPlane().configure(3)
        plane.add_rule("a", "b", LinkRule(reorder=1.0))
        order = []
        ev = threading.Event()
        plane.process("a", "b", lambda: order.append("first"))
        plane.process("a", "b", lambda: (order.append("second"), ev.set()))
        # frame 1 was held; frame 2's passage released it behind...
        # frame 2 itself reorder-fires too but the held slot is taken
        assert ev.wait(2.0)
        assert order[0] == "second"
        time.sleep(plane.REORDER_FLUSH_S + 0.1)
        assert "first" in order
        assert plane.counters["frames_reordered"] >= 1

    def test_clear_flushes_held_frames(self):
        plane = NetFaultPlane().configure(3)
        plane.add_rule("a", "b", LinkRule(reorder=1.0))
        order = []
        plane.process("a", "b", lambda: order.append("held"))
        assert order == []
        plane.clear()
        assert order == ["held"]
        # and a cleared plane is transparent
        plane.process("a", "b", lambda: order.append("clean"))
        assert order == ["held", "clean"]


# ---------------------------------------------------------------------------
# messenger integration: name resolution + both fault directions
# ---------------------------------------------------------------------------
class TestMessengerIntegration:
    def _pair(self, server_name="osd.77", client_name="cli.t"):
        from ceph_tpu.msg.messages import Ping

        srv = Messenger(server_name)
        srv_got = []
        srv.set_dispatcher(lambda c, m: srv_got.append(m))
        addr = srv.bind()
        cli = Messenger(client_name)
        cli_got = []
        cli.set_dispatcher(lambda c, m: cli_got.append(m))
        conn = cli.connect(addr)
        return srv, srv_got, cli, cli_got, conn, Ping

    def test_peer_name_resolved_from_bind_registry(self):
        srv, _sg, cli, _cg, conn, _Ping = self._pair()
        try:
            assert conn.peer_name == "osd.77"
        finally:
            cli.shutdown()
            srv.shutdown()

    def test_outbound_drop_eats_request(self):
        srv, srv_got, cli, _cg, conn, Ping = self._pair()
        try:
            net_faults.configure(1)
            net_faults.add_rule("cli.t", "osd.77", LinkRule(partition=True))
            conn.send(Ping(1, 0))
            time.sleep(0.25)
            assert srv_got == []
            net_faults.clear()
            conn.send(Ping(2, 0))
            deadline = time.monotonic() + 2
            while not srv_got and time.monotonic() < deadline:
                time.sleep(0.01)
            assert [m.tid for m in srv_got] == [2]
        finally:
            cli.shutdown()
            srv.shutdown()

    def test_inbound_reply_faulted_at_client_end(self):
        """Server->client frames are faulted on the CLIENT's read loop
        (the server's accepted conn has no peer name): a dropped reply
        is exactly a lost ack."""
        from ceph_tpu.msg.messages import Pong

        srv, _sg, cli, cli_got, conn, Ping = self._pair()
        srv.set_dispatcher(lambda c, m: c.send(Pong(m.tid, 9)))
        try:
            net_faults.configure(1)
            net_faults.add_rule("osd.77", "cli.t", LinkRule(partition=True))
            conn.send(Ping(1, 0))
            time.sleep(0.25)
            assert cli_got == []
            net_faults.clear()
            conn.send(Ping(2, 0))
            deadline = time.monotonic() + 2
            while not cli_got and time.monotonic() < deadline:
                time.sleep(0.01)
            assert [m.tid for m in cli_got] == [2]
        finally:
            cli.shutdown()
            srv.shutdown()

    def test_duplicated_frames_arrive_twice(self):
        srv, srv_got, cli, _cg, conn, Ping = self._pair()
        try:
            net_faults.configure(1)
            net_faults.add_rule("cli.t", "osd.77", LinkRule(dup=1.0))
            conn.send(Ping(5, 0))
            deadline = time.monotonic() + 2
            while len(srv_got) < 2 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert [m.tid for m in srv_got] == [5, 5]
        finally:
            cli.shutdown()
            srv.shutdown()

    def test_escape_hatch_keeps_armed_rules_inert(self):
        from ceph_tpu.utils import config

        srv, srv_got, cli, _cg, conn, Ping = self._pair()
        try:
            with config.override(msgr_fault_plane=False):
                net_faults.configure(1)
                net_faults.add_rule(
                    "cli.t", "osd.77", LinkRule(partition=True)
                )
                assert not net_faults.active
                conn.send(Ping(3, 0))
                deadline = time.monotonic() + 2
                while not srv_got and time.monotonic() < deadline:
                    time.sleep(0.01)
                assert [m.tid for m in srv_got] == [3]
        finally:
            cli.shutdown()
            srv.shutdown()


# ---------------------------------------------------------------------------
# duplicate sub-write absorption (satellite 4's dedup proof)
# ---------------------------------------------------------------------------
class TestDuplicateSubWrites:
    def test_duplicated_batch_frame_commits_once(self):
        """A duplicated MSG_EC_SUB_WRITE_BATCH frame: the receiver
        re-applies idempotently, the sender's reqid window (pending
        entry) absorbs the second ack set — each sub-write acks its
        op EXACTLY once, and the absorbed duplicates are counted."""
        from ceph_tpu.msg.shard_server import NetShardBackend, ShardServer
        from ceph_tpu.store import Transaction

        server = ShardServer(0)
        addr = server.start()
        backend = NetShardBackend({0: addr}, timeout=5.0, name="cli.dup")

        class _PC:
            absorbed = 0

            def inc(self, key, n=1):
                if key == "resends_absorbed":
                    _PC.absorbed += n

            def tinc(self, key, seconds):
                pass  # the messenger's send/recv timers

        backend.messenger.net_pc = _PC()
        net_faults.configure(11)
        net_faults.add_rule("cli.dup", "osd.0", LinkRule(dup=1.0))
        acks = {"a": 0, "b": 0}
        try:
            with backend.subwrite_batching():
                backend.submit_shard_txn(
                    0, Transaction().write("a", 0, b"AAAA"),
                    lambda: acks.__setitem__("a", acks["a"] + 1),
                )
                backend.submit_shard_txn(
                    0, Transaction().write("b", 0, b"BBBB"),
                    lambda: acks.__setitem__("b", acks["b"] + 1),
                )
            backend.drain_until(
                lambda: acks["a"] and acks["b"], timeout=10
            )
            # the dup'd batch re-applied and re-acked; give the second
            # reply time to arrive and be absorbed
            deadline = time.monotonic() + 3
            while _PC.absorbed < 2 and time.monotonic() < deadline:
                backend.drain_until(lambda: True, timeout=0.2)
                time.sleep(0.02)
            assert acks == {"a": 1, "b": 1}, "an op must commit once"
            assert _PC.absorbed >= 2
            assert server.store.read("a") == b"AAAA"
            assert server.store.read("b") == b"BBBB"
        finally:
            backend.shutdown()
            server.stop()


# ---------------------------------------------------------------------------
# objecter backoff ladder under sustained loss (satellite 4)
# ---------------------------------------------------------------------------
class TestObjecterLadderUnderLoss:
    def test_exhaustion_is_a_clean_error_with_exponential_spacing(self):
        from ceph_tpu.cluster.objecter import NoPrimary
        from ceph_tpu.loadgen import LoadCluster

        cluster = LoadCluster(
            n_osds=3, k=2, m=1, pg_num=2, chunk_size=1024,
            client_op_timeout=0.25, client_backoff=0.1,
            client_max_attempts=4, tick_period=0.1,
        )
        obj = cluster.client.objecter
        try:
            cluster.io.write_full("pre", b"x" * 512)  # clean baseline
            attempt_times = []
            orig = obj._send_attempt

            def timed(aop):
                attempt_times.append(time.monotonic())
                return orig(aop)

            obj._send_attempt = timed
            base_resends = obj.resends
            net_faults.configure(2)
            # total loss client->everyone: every attempt's outcome is
            # ambiguous, the ladder must walk all rungs then SURFACE
            net_faults.add_rule("client", "osd.*", LinkRule(partition=True))
            t0 = time.monotonic()
            with pytest.raises(NoPrimary) as exc:
                cluster.io.write_full("lost", b"y" * 512)
            assert "gave up after 4 attempts" in str(exc.value)
            # never a hang: bounded by attempts * (timeout + backoff)
            assert time.monotonic() - t0 < 10.0
            # resend accounting: attempts - 1 re-attempts counted on
            # both the legacy counter and the perf set
            assert obj.resends - base_resends == 3
            assert obj.perf.get("op_resend") >= 3
            # the ladder's spacing grows (timeout + backoff * 2^n):
            gaps = [
                b - a
                for a, b in zip(attempt_times[-4:-1], attempt_times[-3:])
            ]
            assert gaps[-1] > gaps[0] + 0.15, (
                f"expected exponential spacing, got {gaps}"
            )
            # and the exhaustion left no wedge: heal, the client works
            net_faults.clear()
            assert cluster.io.write_full("post", b"z" * 512) == 512
            assert cluster.io.read("post") == b"z" * 512
        finally:
            obj._send_attempt = orig
            cluster.shutdown()


# ---------------------------------------------------------------------------
# the cluster chaos legs (tier-1 acceptance smokes)
# ---------------------------------------------------------------------------
def _chaos_cluster():
    from ceph_tpu.loadgen import LoadCluster
    from ceph_tpu.utils import config

    ctx = config.override(
        osd_peer_rpc_timeout=1.0, osd_subop_resend_interval=0.2,
    )
    ctx.__enter__()
    cluster = LoadCluster(
        n_osds=5, k=2, m=1, pg_num=4, chunk_size=1024,
        tick_period=0.2,
    )
    return cluster, ctx


@pytest.mark.net_chaos
class TestChaosSmoke:
    def test_flaky_links_zero_verify_failures_exactly_once(self):
        """THE acceptance smoke: a mixed loadgen run under the seeded
        >=2% drop + duplication + ~50 ms p95 delay profile on every
        inter-OSD link completes with zero verify failures,
        exactly-once accounting, recovered + scrub-clean at exit —
        and the injections/absorptions are observable on the
        osd.N.net counters and the Prometheus exporter."""
        from ceph_tpu.loadgen import FaultSchedule, preset, run_spec

        cluster, ctx = _chaos_cluster()
        try:
            spec = preset("smoke", seed=0xEC)
            sched = FaultSchedule.net_flaky(spec.total_ops, seed=0xEC)
            report = run_spec(cluster, spec, sched)
            assert report["verify_failures"] == 0
            assert report["errors"] == 0
            assert report["exactly_once"]
            assert report["ops_in"] == spec.total_ops
            assert report["recovered"]
            assert cluster.scrub_clean()
            # the plane actually fired (deterministic from the seed)
            assert net_faults.counters["frames_dropped"] > 0
            assert net_faults.counters["frames_delayed"] > 0
            assert net_faults.counters["frames_duped"] > 0
            # per-daemon observability: inter-OSD faults land on the
            # owning daemons' osd.N.net sets ...
            dropped = sum(
                d.net_pc.get("frames_dropped")
                for d in cluster.daemons.values()
            )
            assert dropped > 0
            # ... and ride the exporter exposition
            from ceph_tpu.utils import perf_collection
            from ceph_tpu.utils.exporter import render_exposition

            text = render_exposition(perf_collection)
            assert "frames_dropped" in text
            assert "resends_absorbed" in text
        finally:
            cluster.shutdown()
            ctx.__exit__(None, None, None)

    def test_asymmetric_partition_heals_scrub_clean(self):
        """The partition acceptance leg: asymmetrically cut the
        MOST-primary OSD mid-run, merge at 2/3 — the peering FSM
        re-elects (elections counted), the run stays verify-clean,
        and the merged cluster heals to scrub-clean."""
        from ceph_tpu.loadgen import FaultSchedule, preset, run_spec

        cluster, ctx = _chaos_cluster()
        try:
            elections0 = sum(
                d.peering_pc.get("elections_run")
                for d in cluster.daemons.values()
            )
            spec = preset("smoke", seed=0xEC)
            sched = FaultSchedule.net_partition(
                spec.total_ops, victim="most_primary",
                asymmetric=True, seed=7,
            )
            report = run_spec(cluster, spec, sched)
            assert report["verify_failures"] == 0
            assert report["exactly_once"]
            assert report["recovered"]
            assert cluster.scrub_clean()
            assert not cluster.partitioned  # healed at settle
            elections1 = sum(
                d.peering_pc.get("elections_run")
                for d in cluster.daemons.values()
            )
            assert elections1 > elections0, (
                "the partition must have forced re-elections"
            )
        finally:
            cluster.shutdown()
            ctx.__exit__(None, None, None)

    def test_reqid_dedup_absorbs_duplicated_client_ops(self):
        """Duplicate every client->primary frame: each mutation's
        resent/duplicated OSDOp must be absorbed by the reqid dedup
        gate (replay, never re-apply) — appends would otherwise
        double. Dedup hits are observable on osd.N.net."""
        cluster, ctx = _chaos_cluster()
        try:
            net_faults.configure(5)
            net_faults.add_rule("client", "osd.*", LinkRule(dup=1.0))
            oid = "dup-client"
            cluster.io.write_full(oid, b"base|")
            for i in range(4):
                cluster.io.append(oid, f"seg{i}|".encode())
            got = cluster.io.read(oid)
            assert got == b"base|seg0|seg1|seg2|seg3|"
            net_faults.clear()
            hits = sum(
                d.net_pc.get("dedup_hits")
                for d in cluster.daemons.values()
            )
            assert hits > 0, "duplicated mutations must hit the dedup gate"
        finally:
            cluster.shutdown()
            ctx.__exit__(None, None, None)
