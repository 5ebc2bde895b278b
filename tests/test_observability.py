"""Observability layer: typed perf counters + dump schema, layered
config resolution with observers and validation, span tracing with the
historic-op ring, and the admin command surface tying them together —
mirroring the reference's PerfCounters / options.yaml+config /
ZTracer+OpTracker / admin_socket contracts (SURVEY.md section 5).
"""

import json

import numpy as np
import pytest

from ceph_tpu.codecs import registry
from ceph_tpu.pipeline.read import ReadPipeline
from ceph_tpu.pipeline.rmw import RMWPipeline, ShardBackend
from ceph_tpu.pipeline.stripe import PAGE_SIZE, StripeInfo
from ceph_tpu.store import MemStore
from ceph_tpu.utils.admin_socket import admin_socket
from ceph_tpu.utils.config import ConfigProxy, Option
from ceph_tpu.utils.perf_counters import (
    PerfCountersBuilder,
    PerfCountersCollection,
)
from ceph_tpu.utils.trace import Tracer, tracer


class TestPerfCounters:
    def make(self):
        coll = PerfCountersCollection()
        pc = (
            PerfCountersBuilder(coll, "t")
            .add_u64_counter("ops")
            .add_u64_gauge("depth")
            .add_time("busy")
            .add_avg("lat")
            .add_histogram("sizes", [100, 1000, 10000])
            .create_perf_counters()
        )
        return coll, pc

    def test_types_and_dump(self):
        coll, pc = self.make()
        pc.inc("ops")
        pc.inc("ops", 4)
        pc.set("depth", 7)
        pc.tinc("busy", 0.5)
        pc.ainc("lat", 0.25)
        pc.ainc("lat", 0.75)
        for v in (50, 500, 5000, 50000):
            pc.hinc("sizes", v)
        d = coll.dump()["t"]
        assert d["ops"] == 5
        assert d["depth"] == 7
        assert d["busy"] == pytest.approx(0.5)
        assert d["lat"] == {"avgcount": 2, "sum": 1.0}
        assert d["sizes"]["counts"] == [1, 1, 1, 1]

    def test_type_misuse_raises(self):
        _, pc = self.make()
        with pytest.raises(TypeError):
            pc.inc("depth")
        with pytest.raises(KeyError):
            pc.inc("nope")

    def test_duplicate_key_rejected(self):
        coll = PerfCountersCollection()
        b = PerfCountersBuilder(coll, "x").add_u64_counter("a")
        with pytest.raises(ValueError):
            b.add_u64_counter("a")


class TestConfig:
    def make(self):
        opts = (
            Option("alpha", int, 5, min=1, max=100),
            Option("mode", str, "fast", enum_values=("fast", "safe")),
            Option("ratio", float, 0.5),
        )
        return ConfigProxy(opts)

    def test_layering(self, tmp_path, monkeypatch):
        monkeypatch.setenv("CEPH_TPU_ALPHA", "30")
        cfg = ConfigProxy(
            (Option("alpha", int, 5, min=1, max=100),)
        )
        assert cfg.get("alpha") == 30 and cfg.get_source("alpha") == "env"
        f = tmp_path / "conf.json"
        f.write_text(json.dumps({"alpha": 20}))
        cfg.load_file(str(f))
        # env beats file
        assert cfg.get("alpha") == 30
        cfg.set("alpha", 40)  # runtime beats all
        assert cfg.get("alpha") == 40
        assert cfg.get_source("alpha") == "runtime"
        cfg.rm("alpha")
        assert cfg.get("alpha") == 30

    def test_validation(self):
        cfg = self.make()
        with pytest.raises(ValueError):
            cfg.set("alpha", 1000)
        with pytest.raises(ValueError):
            cfg.set("mode", "warp")
        with pytest.raises(KeyError):
            cfg.set("ghost", 1)
        cfg.set("alpha", "42")  # string coercion
        assert cfg.get("alpha") == 42

    def test_observer(self):
        cfg = self.make()
        seen = []
        cfg.add_observer("alpha", lambda n, v: seen.append((n, v)))
        cfg.set("alpha", 9)
        cfg.set("mode", "safe")  # not observed
        cfg.set("alpha", 9)  # unchanged -> no event
        assert seen == [("alpha", 9)]

    def test_show(self):
        cfg = self.make()
        cfg.set("mode", "safe")
        show = cfg.show()
        assert show["mode"] == {"value": "safe", "source": "runtime"}
        assert show["alpha"] == {"value": 5, "source": "default"}


class TestTracer:
    def test_nesting_and_history(self):
        t = Tracer(history=10)
        with t.span("outer", oid="o") as outer:
            with t.span("inner") as inner:
                pass
        hist = t.dump_historic()
        assert [h["name"] for h in hist] == ["inner", "outer"]
        assert hist[0]["parent_id"] == outer.span_id
        assert hist[1]["parent_id"] is None
        assert all(h["duration"] is not None for h in hist)

    def test_ring_bound(self):
        t = Tracer(history=3)
        for i in range(10):
            with t.span(f"s{i}"):
                pass
        assert len(t.dump_historic()) == 3

    def test_disabled(self):
        t = Tracer(enabled=False)
        with t.span("x") as sp:
            assert sp is None
        assert t.dump_historic() == []


class TestAdminSocket:
    def test_help_and_builtins(self):
        cmds = admin_socket.help()
        for cmd in (
            "perf dump", "config show", "config set", "dump_historic_ops",
            "injectecreaderr", "injectecwriteerr",
        ):
            assert cmd in cmds

    def test_config_roundtrip(self):
        assert (
            admin_socket.execute("config set", name="osd_coalesce_max",
                                 value="16")
            == 16
        )
        assert admin_socket.execute("config get", name="osd_coalesce_max") == 16
        from ceph_tpu.utils.config import config

        config.rm("osd_coalesce_max")

    def test_unknown_command(self):
        with pytest.raises(KeyError):
            admin_socket.execute("launch missiles")

    def test_pyprof_round_trip_on_a_two_osd_cluster(self):
        """``pyprof start`` / ``dump`` / ``stop`` (PR 47): the process
        profiles itself while a two-OSD cluster serves a write, every
        thread lands in a registered role, and after ``stop`` no tool
        is left behind."""
        import sys

        from ceph_tpu.cluster import Monitor, OSDDaemon, RadosClient

        mon = Monitor()
        for i in range(2):
            mon.osd_crush_add(i, zone=f"z{i}")
        daemons = [OSDDaemon(i, mon, chunk_size=1024) for i in range(2)]
        for d in daemons:
            d.start()
        mon.osd_erasure_code_profile_set(
            "rs11", {"plugin": "jerasure", "technique": "reed_sol_van",
                     "k": "1", "m": "1"}
        )
        mon.osd_pool_create("pp", 2, "rs11")
        client = RadosClient(mon, backoff=0.01)
        try:
            io = client.open_ioctx("pp")
            io.write("warm", b"w" * 2048)
            started = admin_socket.execute("pyprof start", lock_lost_ms="2")
            assert started["active"] and started["lock_lost_ns"] == 2_000_000
            with pytest.raises(RuntimeError, match="already started"):
                admin_socket.execute("pyprof start")
            for i in range(4):
                io.write(f"obj{i}", bytes([i]) * 4096)
                assert io.read(f"obj{i}") == bytes([i]) * 4096
            live = admin_socket.execute("pyprof dump", top="5", ops="8")
            assert live["active"] and live["per"] == "op"
            stopped = admin_socket.execute("pyprof stop")
            assert not stopped["active"]
        finally:
            if sys.monitoring.get_tool(sys.monitoring.PROFILER_ID):
                admin_socket.execute("pyprof stop")
            client.shutdown()
            for d in daemons:
                d.stop()
        assert sys.monitoring.get_tool(sys.monitoring.PROFILER_ID) is None
        done = admin_socket.execute("pyprof dump", top="5", ops="8")
        assert not done["active"] and done["ops"] == 8
        assert {"op_worker", "msgr", "client"} <= set(done["roles"]), (
            done["thread_names"]
        )
        assert not [n for n in done["thread_names"] if n.startswith("Dummy")]
        worker = done["roles"]["op_worker"]
        assert worker["self_ms"] > 0 and len(worker["functions"]) == 5
        text = admin_socket.execute("pyprof dump", text="true")
        assert "role op_worker" in text and "role msgr" in text
        with pytest.raises(RuntimeError, match="not started"):
            admin_socket.execute("pyprof stop")

    def test_pyprof_dump_with_nothing_started_says_so(self):
        import subprocess
        import sys

        out = subprocess.run(
            [sys.executable, "-c",
             "from ceph_tpu.utils.admin_socket import admin_socket as a\n"
             "d = a.execute('pyprof dump')\n"
             "print(d['active'], 'never started' in d['note'])"],
            capture_output=True, text=True, timeout=120,
        )
        assert out.stdout.strip().split("\n")[-1] == "False True", out.stderr
        assert {"pyprof start", "pyprof stop", "pyprof dump"} <= set(
            admin_socket.help()
        )


class TestPipelineIntegration:
    def test_counters_and_spans_flow(self, rng):
        k, m, chunk = 4, 2, PAGE_SIZE
        sinfo = StripeInfo(k, m, k * chunk)
        codec = registry.factory(
            "jerasure",
            {"technique": "reed_sol_van", "k": str(k), "m": str(m)},
        )
        backend = ShardBackend(
            {s: MemStore(f"osd.{s}") for s in range(k + m)}
        )
        rmw = RMWPipeline(sinfo, codec, backend, perf_name="t_rmw")
        reads = ReadPipeline(
            sinfo, codec, backend, rmw.object_size, perf_name="t_read"
        )
        tracer.clear()
        data = rng.integers(0, 256, 2 * k * chunk, np.uint8).tobytes()
        rmw.submit("obj", 0, data)
        backend.down_shards.add(1)
        assert reads.read_sync("obj", 0, len(data)) == data

        dump = admin_socket.execute("perf dump")
        assert dump["t_rmw"]["write_ops"] == 1
        assert dump["t_rmw"]["write_bytes"] == len(data)
        assert dump["t_rmw"]["full_stripe_ops"] == 1
        assert dump["t_rmw"]["commit_lat"]["avgcount"] == 1
        assert dump["t_read"]["read_ops"] == 1
        assert dump["t_read"]["read_bytes"] == len(data)
        assert dump["t_read"]["reconstruct_ops"] == 1
        assert dump["t_read"]["errors"] == 0

        names = [
            s["name"]
            for s in admin_socket.execute("dump_historic_ops")
        ]
        assert "ec_write" in names and "ec_reconstruct" in names

    def test_inject_via_admin_socket(self, rng):
        from ceph_tpu.pipeline.inject import ec_inject

        ec_inject.clear_all()
        k, m, chunk = 4, 2, PAGE_SIZE
        sinfo = StripeInfo(k, m, k * chunk)
        codec = registry.factory(
            "jerasure",
            {"technique": "reed_sol_van", "k": str(k), "m": str(m)},
        )
        backend = ShardBackend(
            {s: MemStore(f"osd.{s}") for s in range(k + m)}
        )
        rmw = RMWPipeline(sinfo, codec, backend, perf_name="t2_rmw")
        reads = ReadPipeline(
            sinfo, codec, backend, rmw.object_size, perf_name="t2_read"
        )
        data = rng.integers(0, 256, k * chunk, np.uint8).tobytes()
        rmw.submit("obj", 0, data)
        out = admin_socket.execute(
            "injectecreaderr", oid="obj", type=0, shard=0
        )
        assert "ok" in out
        assert reads.read_sync("obj", 0, len(data)) == data
        assert reads.perf.get("retries") == 1
        ec_inject.clear_all()


class TestDebugModes:
    """debug_* options map onto jax debug flags (the sanitizer-toggle
    analog, SURVEY §5.2) and flip live via the admin socket."""

    def test_admin_config_set_flips_jax_flag(self):
        import jax

        from ceph_tpu.utils.admin_socket import admin_socket
        from ceph_tpu.utils.config import config

        assert not jax.config.jax_debug_nans
        try:
            admin_socket.execute(
                "config set", name="debug_nan_check", value="true"
            )
            assert jax.config.jax_debug_nans
        finally:
            admin_socket.execute(
                "config set", name="debug_nan_check", value="false"
            )
        assert not jax.config.jax_debug_nans
        assert not config.get("debug_nan_check")

    def test_apply_is_idempotent(self):
        from ceph_tpu.utils import apply_debug_modes

        apply_debug_modes()
        apply_debug_modes()


class TestDistributedTrace:
    """Trace context crosses the wire (the ZTracer/blkin hop the
    reference threads through op + sub-op messages,
    osd/ECBackend.h:70-94): one client op's spans on the client, the
    primary, and every replica share a trace id."""

    def test_client_op_trace_spans_daemons(self):
        import numpy as np

        from ceph_tpu.cluster import Monitor, OSDDaemon, RadosClient
        from ceph_tpu.utils import tracer

        tracer.clear()
        mon = Monitor()
        daemons = []
        for i in range(5):
            mon.osd_crush_add(i, zone=f"z{i % 3}")
        for i in range(5):
            d = OSDDaemon(i, mon, chunk_size=1024)
            d.start()
            daemons.append(d)
        mon.osd_erasure_code_profile_set(
            "rs32", {"plugin": "jerasure", "technique": "reed_sol_van",
                     "k": "3", "m": "2"}
        )
        mon.osd_pool_create("tp", 4, "rs32")
        client = RadosClient(mon, backoff=0.01)
        try:
            io = client.open_ioctx("tp")
            data = np.random.default_rng(1).integers(
                0, 256, 5000, np.uint8
            ).tobytes()
            io.write("tobj", data)
            assert io.read("tobj") == data
        finally:
            client.shutdown()
            for d in daemons:
                d.stop()
        spans = tracer.dump_historic()
        client_spans = [
            s for s in spans
            if s["name"] == "client_op" and s["tags"].get("oid") == "tobj"
        ]
        assert client_spans, "client span missing"
        tid = client_spans[0]["trace_id"]
        names = {
            s["name"] for s in spans if s["trace_id"] == tid
        }
        # the trace crossed the wire: the primary's op span and the
        # replica sub-op spans share the client op's trace id
        assert "osd_op" in names, names
        assert "sub_write" in names, names
        # and EVERY span of the op correlates by that one id
        osd_spans = [
            s for s in spans
            if s["trace_id"] == tid and s["name"] == "sub_write"
        ]
        assert len(osd_spans) >= 2, "sub-op fan-out not traced"
