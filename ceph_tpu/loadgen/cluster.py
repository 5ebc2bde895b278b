"""vstart-analog cluster harness for load generation.

Boots the REAL tier: monitor + N OSD daemons over sockets (``msg/``
framed messenger), an EC pool through the profile/pool machinery,
and a ``RadosClient`` — the same stack the e2e/chaos tests drive,
packaged with the kill/revive/wait-recovered controls the fault
schedule needs (qa/tasks/ceph_manager.py kill_osd/revive_osd role).
MemStore by default: loadgen measures the service path, not the
backing-store medium, unless a store factory says otherwise."""

from __future__ import annotations

import time

from ceph_tpu.cluster import Monitor, OSDDaemon, RadosClient
from ceph_tpu.cluster.osdmap import SHARD_NONE


#: the name the pool's erasure-code profile has at the monitor
PROFILE_NAME = "loadprof"


class LoadCluster:
    """mon + OSDs + EC pool + client, with thrasher controls."""

    def __init__(
        self,
        n_osds: int = 6,
        k: int | None = None,
        m: int | None = None,
        pg_num: int = 8,
        chunk_size: int = 1024,
        pool: str = "loadpool",
        plugin: str | None = None,
        technique: str | None = None,
        d: int | None = None,
        store_factory=None,
        tick_period: float = 0.2,
        client_backoff: float = 0.02,
        client_op_timeout: float = 3.0,
        client_max_attempts: int = 10,
        use_mesh: bool = False,
        mesh_devices: int | None = None,
        profile: dict[str, str] | None = None,
    ) -> None:
        """``profile`` is the pool's erasure-code profile, whole, as
        ``ceph osd erasure-code-profile set`` takes it (every key and
        value a string, ``plugin`` among them), and is handed to the
        monitor as it is; it is given alone, not beside the keywords
        it replaces. Without it ``k`` (3), ``m`` (2), ``plugin``
        (jerasure), ``technique`` and ``d`` make one; ``technique`` not
        given is the plugin's own default. A key the plugin does not
        know is the codec's to refuse, through the monitor's command.
        The counts (OSDs needed, CRUSH zones, the mesh's shard axis)
        are those of the code the monitor then holds: a code may have
        more chunks than k+m."""
        beside = (k, m, plugin, technique, d)
        if profile is not None:
            if any(given is not None for given in beside):
                raise ValueError(
                    "profile= is the pool's whole profile: give k, m, "
                    "plugin, technique and d inside it, not beside it"
                )
        else:
            plugin = plugin or "jerasure"
            profile = {
                "plugin": plugin,
                "k": str(3 if k is None else k),
                "m": str(2 if m is None else m),
            }
            if technique is None and plugin == "jerasure":
                technique = "reed_sol_van"  # what this form always sent
            if technique is not None:
                profile["technique"] = technique
            if d is not None:
                # CLAY's d steers the MSR repair bandwidth (default
                # k+m-1)
                profile["d"] = str(d)
        self.mon = Monitor()
        self.mon.osd_erasure_code_profile_set(PROFILE_NAME, dict(profile))
        codec = self.codec()
        k = codec.get_data_chunk_count()
        chunks = codec.get_chunk_count()
        m = chunks - k
        if n_osds < chunks:
            raise ValueError(
                f"need >= {chunks} OSDs (the code's chunk count), "
                f"got {n_osds}"
            )
        sub = codec.get_sub_chunk_count()
        if chunk_size % sub:
            # a code with sub-chunks (CLAY's q^t): the fractional
            # sub-reads take whole, lane-aligned sub-chunks of a chunk
            raise ValueError(
                f"chunk_size {chunk_size} must divide into the "
                f"code's {sub} sub-chunks"
            )
        self.pool = pool
        self.k, self.m = k, m
        self.chunk_size = chunk_size
        self._tick_period = tick_period
        # -- multi-chip tier wired into the LIVE path (round-10): the
        # daemons run in-process, so the process-wide dispatch mesh
        # (parallel/dispatch.py) IS the live data path — every RMW
        # encode, degraded decode and recovery rebuild the daemons
        # run from here on rides the collective fan-out, the way the
        # reference's sub-op fan-out is its distributed backend.
        # Installed BEFORE the daemons boot so even the first op
        # routes over it; shutdown() restores what was there.
        self.mesh = None
        self._prev_mesh = None
        if use_mesh:
            from ceph_tpu.parallel import dispatch as mesh_dispatch
            from ceph_tpu.parallel import make_ec_mesh

            self._prev_mesh = mesh_dispatch.get_mesh()
            self.mesh = make_ec_mesh(mesh_devices, k=k)
            mesh_dispatch.set_mesh(self.mesh)
        self.daemons: dict[int, OSDDaemon] = {}
        self.stores: dict[int, object] = {}
        for i in range(n_osds):
            self.mon.osd_crush_add(i, zone=f"z{i % max(m + 1, 3)}")
        for i in range(n_osds):
            store = store_factory(i) if store_factory else None
            d = OSDDaemon(
                i, self.mon, store=store, chunk_size=chunk_size,
                tick_period=tick_period,
            )
            d.start()
            self.daemons[i] = d
            self.stores[i] = d.store
        self.mon.osd_pool_create(pool, pg_num, PROFILE_NAME)
        # short op timeout: a kill can eat an in-flight op's reply
        # mid-run, and the default 30 s wait would freeze the whole
        # closed loop for the duration (the reqid dedup makes the
        # fast resend safe)
        # generous retry budget: a kill + peering + durability-poll
        # cooldowns can stack several seconds of eagain before an op
        # lands; the default 8-attempt ladder at this backoff gives
        # up mid-recovery and turns a healable wait into an op error
        self.client = RadosClient(
            self.mon, backoff=client_backoff,
            op_timeout=client_op_timeout,
            max_attempts=client_max_attempts,
            perf_name="loadgen_client",
        )
        self.io = self.client.open_ioctx(pool)
        self.dead: list[int] = []
        #: OSDs currently cut off by a net partition (alive but
        #: unreachable on the data plane; map-down once evidence lands)
        self.partitioned: list[int] = []

    # -- thrasher controls ---------------------------------------------
    def live_osds(self) -> list[int]:
        return [i for i in self.daemons if i not in self.dead]

    def _primary_counts(self) -> dict[int, int]:
        spec = self.mon.osdmap.pools[self.pool]
        counts = {o: 0 for o in self.live_osds()}
        for pgid in range(spec.pg_num):
            p = self.mon.osdmap.pg_primary(self.pool, pgid)
            if p in counts:
                counts[p] += 1
        return counts

    def least_primary_osd(self) -> int:
        """The live OSD leading the FEWEST PGs of the pool (ties ->
        lowest id). Killing this one exercises degraded/reconstruct
        reads, revive catch-up and the recovery clock while forcing
        the fewest primary failovers — the gentlest victim."""
        counts = self._primary_counts()
        return min(counts, key=lambda o: (counts[o], o))

    def most_primary_osd(self) -> int:
        """The live OSD leading the MOST PGs of the pool (ties ->
        lowest id). Killing this one forces the maximum number of
        primary takeovers at once — the peering-FSM torture victim,
        and the default soak target now that the takeover race
        (ROADMAP #1) is closed by construction."""
        counts = self._primary_counts()
        return min(counts, key=lambda o: (-counts[o], o))

    def kill(self, osd: int) -> None:
        """Hard-stop the daemon and mark it down (failure detection
        collapsed to a command, as the e2e tier does)."""
        if osd in self.dead:
            return
        self.daemons[osd].stop()
        self.mon.osd_down(osd)
        self.dead.append(osd)

    def revive(self, osd: int) -> None:
        """Fresh daemon over the corpse's store: boot + log catch-up
        brings the shard back (the revive_osd path)."""
        if osd not in self.dead:
            return
        d = OSDDaemon(
            osd, self.mon, store=self.stores[osd],
            chunk_size=self.chunk_size, tick_period=self._tick_period,
        )
        d.start()
        self.daemons[osd] = d
        self.dead.remove(osd)

    # -- network-fault controls (the tc/netem analog) ------------------
    def net_flaky(
        self,
        seed: int = 0xEC,
        drop: float = 0.02,
        dup: float = 0.02,
        delay_ms: float = 5.0,
        delay_jitter_ms: float = 47.0,
        reorder: float = 0.01,
        scope: str = "osd",
    ) -> None:
        """Arm a seeded flaky profile on every link: inter-OSD only
        (``scope="osd"``, the acceptance profile) or the client legs
        too (``scope="all"``). Deterministic per link from ``seed``."""
        from ceph_tpu.msg.messenger import LinkRule, net_faults

        rule = LinkRule(
            drop=drop, dup=dup, delay_ms=delay_ms,
            delay_jitter_ms=delay_jitter_ms, reorder=reorder,
        )
        net_faults.configure(seed)
        if scope == "all":
            net_faults.add_rule("*", "*", rule)
        else:
            net_faults.add_rule("osd.*", "osd.*", rule)

    def net_partition(
        self, osd: int, asymmetric: bool = False, seed: int = 0xEC,
    ) -> None:
        """Cut osd.<id> off the data plane (frames dropped; TCP stays
        up, exactly a switch eating packets). ``asymmetric`` cuts only
        the inbound half — the victim keeps sending into the void, the
        re-election torture case. Failure detection is collapsed to a
        command like ``kill()``'s: the mon marks the victim down (its
        peers' evidence), so peering re-elects deterministically."""
        from ceph_tpu.msg.messenger import net_faults

        if not net_faults.active:
            net_faults.configure(seed)
        net_faults.partition(f"osd.{osd}", asymmetric=asymmetric)
        if osd not in self.partitioned:
            self.partitioned.append(osd)
        self.mon.osd_down(osd)

    def net_heal(self) -> None:
        """Merge: clear every armed link rule (held/delayed frames
        flush) and re-announce surviving partitioned daemons to the
        mon (the MOSDBoot a real OSD sends when its links return).
        Peering then re-admits them; scrub_clean is the caller's
        convergence gate."""
        from ceph_tpu.msg.messenger import net_faults

        net_faults.clear()
        for osd in list(self.partitioned):
            self.partitioned.remove(osd)
            if osd in self.dead:
                continue  # killed while partitioned: revive's problem
            d = self.daemons[osd]
            if d.addr is not None:
                self.mon.osd_boot(osd, d.addr)

    # -- recovery observation ------------------------------------------
    def is_recovered(self) -> bool:
        """Every member up, and for every PG: a full up_acting set in
        the map, the PRIMARY's instance peered with no hole in acting
        and no shard catch-up in flight, and no backfill running
        anywhere. Non-primary instances may cache a stale acting view
        from an old interval — only the primary's view (which serves
        ops) counts."""
        if self.dead:
            return False
        osdmap = self.mon.osdmap
        spec = osdmap.pools[self.pool]
        for pgid in range(spec.pg_num):
            acting = osdmap.pg_to_up_acting(self.pool, pgid)
            if any(o == SHARD_NONE for o in acting):
                return False
            primary = next(o for o in acting if o != SHARD_NONE)
            pg = self.daemons[primary]._pgs.get((self.pool, pgid))
            if pg is None:
                continue  # never instantiated: no state to heal
            if not pg.peered.is_set():
                return False
            if any(o == SHARD_NONE for o in pg.acting):
                return False
            if pg.backend.recovering:
                return False
        for d in self.daemons.values():
            if any(t.is_alive() for t in d._backfills.values()):
                return False
        return True

    def wait_recovered(self, timeout: float = 60.0) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.is_recovered():
                return True
            time.sleep(0.05)
        return self.is_recovered()

    # -- stats-plane recovery observation (round 15) --------------------
    @property
    def pgmap(self):
        """The monitor-side PGMap aggregate the stats plane folds
        primaries' reports into (cluster/pgmap.py)."""
        return self.mon.pgmap

    def is_recovered_stats(self, min_epoch: int = 0) -> bool:
        """Recovery as the STATS PLANE sees it: every reported PG of
        the pool is clean with zero degraded object copies, reported
        at/after ``min_epoch`` (pass the post-revive map epoch so a
        dead primary's stale clean report cannot fake convergence).
        PGs with no report yet (never instantiated — no data) don't
        block; any degraded data forces a report via peering."""
        if self.dead:
            return False
        spec = self.mon.osdmap.pools[self.pool]
        pgmap = self.pgmap
        seen = 0
        for pgid in range(spec.pg_num):
            s = pgmap.get(spec.pool_id, pgid)
            if s is None:
                continue
            if s.reported_epoch < min_epoch:
                return False
            if s.degraded or "clean" not in s.state:
                return False
            seen += 1
        return seen > 0

    def wait_recovered_stats(
        self, timeout: float = 60.0, min_epoch: int = 0
    ) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.is_recovered_stats(min_epoch):
                return True
            time.sleep(0.05)
        return self.is_recovered_stats(min_epoch)

    def scrub_clean(self, repair: bool = True) -> bool:
        """Primary-driven scrub sweep; True iff no object reported
        errors (after optional repair — the post-thrash convergence
        check of the chaos tier)."""
        if repair:
            for d in self.daemons.values():
                if d.osd_id not in self.dead:
                    d.scrub_all(repair=True)
        ok = True
        for d in self.daemons.values():
            if d.osd_id in self.dead:
                continue
            for _pg, results in d.scrub_all().items():
                for r in results:
                    ok = ok and r.ok
        return ok

    def codec(self):
        """The pool's codec instance: the code of the profile the
        monitor holds for the pool (which its command has validated; a
        profile without ``plugin`` is the default plugin's)."""
        from ceph_tpu.codecs import registry
        from ceph_tpu.utils import config

        profile = dict(self.mon.osdmap.profiles[PROFILE_NAME])
        plugin = profile.get(
            "plugin", config.get("erasure_code_default_plugin")
        )
        return registry.factory(plugin, profile)

    def shutdown(self) -> None:
        from ceph_tpu.msg.messenger import net_faults

        if self.partitioned or net_faults.active:
            net_faults.clear()
            self.partitioned.clear()
        self.client.shutdown()
        for d in self.daemons.values():
            d.stop()
        if self.mesh is not None:
            from ceph_tpu.parallel import dispatch as mesh_dispatch

            mesh_dispatch.set_mesh(self._prev_mesh)
