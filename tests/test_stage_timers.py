"""Stage timers on the served path: one measurement, three outputs.

A stage is timed once, where the work happens (``Tracer.span(...,
perf=, key=)`` on one thread, ``Tracer.record`` across threads), and
the same seconds reach a ``Span`` in the op's trace tree and a ``TIME``
counter in the layer's perf set (what the benchmark's per-layer metrics
read). Here: one write and one degraded read through a small in-process
cluster, fused encode+csum in the interpreter and no host shortcut, so
every codec step (prep, h2d, launch, fetch) runs.
"""

import fnmatch
import time

import numpy as np
import pytest

from ceph_tpu.utils import config, perf_collection
from ceph_tpu.utils import trace as trace_mod
from ceph_tpu.utils.exporter import render_exposition
from ceph_tpu.utils.perf_counters import (
    PerfCountersBuilder,
    PerfCountersCollection,
    register_process_counters,
)
from ceph_tpu.utils.trace import Tracer, tracer
from ceph_tpu.utils.trace_assembly import assemble_traces

K, M, CHUNK = 4, 2, 4096
PAYLOAD = 16 * K * CHUNK  # 16 full stripes

WRITE_STAGES = [
    "opq_wait", "osd_op", "ec_write", "ec_write.plan",
    "ec_write.assemble", "ec_write.encode", "codec.prep", "codec.h2d",
    "codec.launch", "codec.fetch", "ec_write.txn_build",
    "ec_write.fanout", "sub_write", "subop_wait",
]
#: a ``writefull`` that shrinks its object keeps the second half
SHRINK_STAGES = ["ec_write", "sub_write", "subop_wait", "ec_truncate"]
READ_STAGES = [
    "opq_wait", "osd_op", "ec_read.issue", "sub_read", "sub_read_wait",
    "ec_reconstruct", "codec.prep", "codec.h2d", "codec.launch",
    "codec.fetch", "ec_read.finish",
]
#: (set glob, key) of every stage counter, by the op that moves it
WRITE_COUNTERS = [
    ("osd.*.opq", "wait_seconds"), ("osd.*.opq", "service_seconds"),
    ("osd.*.opq", "service_cpu_seconds"),
    ("osd.*.rmw", "write_seconds"), ("osd.*.rmw", "plan_seconds"),
    ("osd.*.rmw", "assemble_seconds"), ("osd.*.rmw", "encode_seconds"),
    ("osd.*.rmw", "txn_build_seconds"), ("osd.*.rmw", "fanout_seconds"),
    ("osd.*.rmw", "subop_wait_seconds"),
    ("ec_dispatch", "prep_seconds"), ("ec_dispatch", "h2d_seconds"),
    ("ec_dispatch", "launch_seconds"), ("ec_dispatch", "fetch_seconds"),
    ("*.net", "send_seconds"), ("*.net", "recv_seconds"),
    ("osd.*.store", "apply_seconds"),
    ("process", "cpu_seconds"), ("process", "wall_seconds"),
]
SHRINK_COUNTERS = [
    ("osd.*.rmw", "write_seconds"), ("osd.*.rmw", "subop_wait_seconds"),
    ("osd.*.rmw", "truncate_seconds"),
    ("osd.*.rmw", "truncate_wait_seconds"),
    ("osd.*.store", "apply_seconds"),
]
READ_COUNTERS = [
    ("osd.*.opq", "wait_seconds"), ("osd.*.opq", "service_seconds"),
    ("osd.*.read", "issue_seconds"), ("osd.*.read", "gather_seconds"),
    ("osd.*.read", "reconstruct_seconds"),
    ("osd.*.read", "finish_seconds"),
    ("ec_dispatch", "prep_seconds"), ("ec_dispatch", "h2d_seconds"),
    ("ec_dispatch", "launch_seconds"), ("ec_dispatch", "fetch_seconds"),
    ("*.net", "send_seconds"), ("*.net", "recv_seconds"),
    ("osd.*.store", "read_seconds"),
]


def flat_counters() -> dict:
    out = {}
    for set_name, values in perf_collection.dump().items():
        for key, val in values.items():
            if isinstance(val, (int, float)):
                out[(set_name, key)] = val
    return out


def moved(delta: dict, set_glob: str, key: str) -> float:
    return sum(
        v for (s, k), v in delta.items()
        if k == key and fnmatch.fnmatchcase(s, set_glob)
    )


class Leg:
    """What one client op left behind: its spans and counter deltas."""

    def __init__(self, spans: list, delta: dict) -> None:
        self.spans = spans
        self.delta = delta
        self.by_id = {s["span_id"]: s for s in spans}
        roots = [s for s in spans if s["name"] == "client_op"]
        assert len(roots) == 1, [s["name"] for s in spans]
        self.root = roots[0]

    def named(self, name: str) -> list:
        return [s for s in self.spans if s["name"] == name]

    def descends_from_root(self, span: dict) -> bool:
        seen = set()
        while span["parent_id"] is not None:
            if span["span_id"] in seen or span["parent_id"] not in self.by_id:
                return False
            seen.add(span["span_id"])
            span = self.by_id[span["parent_id"]]
        return span is self.root


@pytest.fixture(scope="module")
def all_legs():
    """(write leg, degraded-read leg) of one object, and the leg of a
    ``writefull`` that halves another."""
    from ceph_tpu.loadgen import LoadCluster

    with config.override(
        ec_fused_csum_interpret=True, ec_host_dispatch_bytes=0,
    ):
        cluster = LoadCluster(
            n_osds=K + M, k=K, m=M, pg_num=4, chunk_size=CHUNK,
            client_op_timeout=60.0,
        )
        try:
            rng = np.random.default_rng(24)
            data = rng.integers(0, 256, PAYLOAD, np.uint8).tobytes()

            def run(fn) -> Leg:
                time.sleep(0.3)  # stragglers of the op before
                tracer.clear()
                before = flat_counters()
                fn()
                time.sleep(0.3)  # replica-side spans land
                after = flat_counters()
                return Leg(
                    tracer.dump_historic(),
                    {k: v - before.get(k, 0) for k, v in after.items()},
                )

            cluster.io.write_full("warm", data)  # compiles
            write = run(lambda: cluster.io.write_full("obj", data))
            cluster.io.write_full("long", data)
            shrink = run(
                lambda: cluster.io.write_full("long", data[:PAYLOAD // 2])
            )
            assert cluster.io.read("long") == data[:PAYLOAD // 2]
            # lose a DATA shard of the object, not its primary
            primary = cluster.mon.osdmap.primary(cluster.pool, "obj")
            acting = cluster.mon.osdmap.object_to_acting(
                cluster.pool, "obj"
            )
            victim = next(o for o in acting[:K] if o != primary)
            cluster.kill(victim)
            deadline = time.monotonic() + 30
            while cluster.mon.osdmap.is_up(victim):
                assert time.monotonic() < deadline
                time.sleep(0.05)
            assert cluster.io.read("obj") == data  # compiles the decode
            got = []
            read = run(lambda: got.append(cluster.io.read("obj")))
            assert got[0] == data
        finally:
            cluster.shutdown()
    return write, read, shrink


@pytest.fixture(scope="module")
def legs(all_legs):
    return all_legs[:2]


@pytest.fixture(scope="module")
def shrink(all_legs):
    return all_legs[2]


# ------------------------------------------------------------ the op's tree
@pytest.mark.parametrize("name", WRITE_STAGES)
def test_write_stage_is_in_the_ops_tree(legs, name):
    write, _ = legs
    found = write.named(name)
    assert found, f"no {name!r} span among {sorted({s['name'] for s in write.spans})}"
    for span in found:
        assert span["trace_id"] == write.root["trace_id"]
        assert write.descends_from_root(span), name


@pytest.mark.parametrize("name", SHRINK_STAGES)
def test_shrink_stage_is_in_the_ops_tree(shrink, name):
    found = shrink.named(name)
    assert found, f"no {name!r} span among {sorted({s['name'] for s in shrink.spans})}"
    for span in found:
        assert span["trace_id"] == shrink.root["trace_id"]
        assert shrink.descends_from_root(span), name


@pytest.mark.parametrize("name", READ_STAGES)
def test_read_stage_is_in_the_ops_tree(legs, name):
    _, read = legs
    found = read.named(name)
    assert found, f"no {name!r} span among {sorted({s['name'] for s in read.spans})}"
    for span in found:
        assert span["trace_id"] == read.root["trace_id"]
        assert read.descends_from_root(span), name


@pytest.mark.parametrize("which", [0, 1], ids=["write", "read"])
def test_one_tree_one_root_no_orphan(legs, which):
    leg = legs[which]
    trees = [
        t for t in assemble_traces(leg.spans)
        if t["trace_id"] == leg.root["trace_id"]
    ]
    assert len(trees) == 1
    assert trees[0]["complete"] and trees[0]["orphans"] == 0
    assert trees[0]["roots"][0]["name"] == "client_op"


@pytest.mark.parametrize("which", [0, 1], ids=["write", "read"])
def test_stage_children_lie_inside_their_parent(legs, which):
    """Stages of one thread nest in time as they nest in the tree
    (sub-ops on other daemons and recorded waits start inside but may
    end after the span that sent them)."""
    leg = legs[which]
    checked = 0
    for span in leg.spans:
        if not span["name"].startswith(("ec_write.", "codec.", "ec_read.")):
            continue
        parent = leg.by_id[span["parent_id"]]
        lo, hi = parent["start_mono"], parent["start_mono"] + parent["duration"]
        assert lo <= span["start_mono"], (span["name"], parent["name"])
        assert span["start_mono"] + span["duration"] <= hi + 1e-6
        checked += 1
    assert checked >= 4


def test_write_stage_parents(legs):
    write, _ = legs
    (ec_write,) = write.named("ec_write")
    (osd_op,) = write.named("osd_op")
    assert ec_write["parent_id"] == osd_op["span_id"]
    for name in ("plan", "assemble", "encode", "txn_build", "fanout"):
        (stage,) = write.named("ec_write." + name)
        assert stage["parent_id"] == ec_write["span_id"], name
    (encode,) = write.named("ec_write.encode")
    for span in write.named("codec.launch") + write.named("codec.fetch"):
        assert span["parent_id"] == encode["span_id"]
    (wait,) = write.named("opq_wait")
    assert wait["parent_id"] == write.root["span_id"]
    assert osd_op["parent_id"] == write.root["span_id"]
    # one fan-out, and its wait hangs off the op, not ec_write
    (subop_wait,) = write.named("subop_wait")
    assert subop_wait["parent_id"] == osd_op["span_id"]
    assert not write.named("ec_truncate")


def test_shrink_stage_parents(shrink):
    """The second half of a shrinking ``writefull`` runs under the same
    ``osd_op`` as the first, and so do both waits."""
    (osd_op,) = shrink.named("osd_op")
    (ec_write,) = shrink.named("ec_write")
    (ec_truncate,) = shrink.named("ec_truncate")
    assert ec_write["parent_id"] == ec_truncate["parent_id"] == (
        osd_op["span_id"]
    )
    waits = shrink.named("subop_wait")
    assert len(waits) == 2
    assert {s["parent_id"] for s in waits} == {osd_op["span_id"]}


def test_stages_account_for_the_ec_write_span(legs):
    write, _ = legs
    parts = sum(
        moved(write.delta, "osd.*.rmw", key + "_seconds")
        for key in ("plan", "assemble", "encode", "txn_build", "fanout")
    )
    whole = moved(write.delta, "osd.*.rmw", "write_seconds")
    (ec_write,) = write.named("ec_write")
    assert whole == pytest.approx(ec_write["duration"])
    assert 0.9 * whole <= parts <= whole


# --------------------------------------------------------------- counters
@pytest.mark.parametrize("set_glob,key", WRITE_COUNTERS)
def test_write_moves_every_stage_counter(legs, set_glob, key):
    write, _ = legs
    assert moved(write.delta, set_glob, key) > 0


@pytest.mark.parametrize("set_glob,key", SHRINK_COUNTERS)
def test_shrink_moves_every_stage_counter(shrink, set_glob, key):
    assert moved(shrink.delta, set_glob, key) > 0


@pytest.mark.parametrize("set_glob,key", READ_COUNTERS)
def test_read_moves_every_stage_counter(legs, set_glob, key):
    _, read = legs
    assert moved(read.delta, set_glob, key) > 0


def test_counter_seconds_are_the_spans_seconds(legs):
    """The TIME counter and the span are one measurement."""
    write, read = legs
    for leg, set_glob, key, name in (
        (write, "osd.*.rmw", "encode_seconds", "ec_write.encode"),
        (write, "osd.*.rmw", "fanout_seconds", "ec_write.fanout"),
        (write, "osd.*.opq", "wait_seconds", "opq_wait"),
        (write, "osd.*.opq", "service_seconds", "osd_op"),
        (write, "ec_dispatch", "fetch_seconds", "codec.fetch"),
        (read, "osd.*.read", "gather_seconds", "sub_read_wait"),
        (read, "osd.*.read", "reconstruct_seconds", "ec_reconstruct"),
    ):
        spans = sum(s["duration"] for s in leg.named(name))
        assert moved(leg.delta, set_glob, key) == pytest.approx(spans), name


def test_op_counts_match_the_spans(legs):
    write, read = legs
    assert moved(write.delta, "osd.*.rmw", "write_ops") == len(
        write.named("ec_write.encode")
    ) == moved(write.delta, "osd.*.rmw", "encode_ops") == 1
    # a new object: nothing to cut, so no second half
    assert moved(write.delta, "osd.*.rmw", "truncate_ops") == len(
        write.named("ec_truncate")
    ) == 0
    assert moved(write.delta, "ec_dispatch", "dispatches") == len(
        write.named("codec.launch")
    ) == 1
    for leg in (write, read):
        assert moved(leg.delta, "osd.*.opq", "ops") == len(
            leg.named("opq_wait")
        ) == 1
    assert moved(read.delta, "osd.*.read", "read_ops") == len(
        read.named("ec_read.issue")
    ) == moved(read.delta, "osd.*.read", "gather_ops") == 1
    assert moved(read.delta, "osd.*.read", "reconstruct_ops") == len(
        read.named("ec_reconstruct")
    ) == 1
    # k+m stores took the write, once
    assert moved(write.delta, "osd.*.store", "txns") == K + M
    assert moved(write.delta, "osd.*.store", "txn_bytes") == (
        PAYLOAD * (K + M) // K
    )
    assert moved(read.delta, "osd.*.store", "reads") >= K
    assert moved(read.delta, "osd.*.store", "read_bytes") >= PAYLOAD


def test_a_shrinking_writefull_is_two_fanouts(shrink):
    assert moved(shrink.delta, "osd.*.rmw", "encode_ops") == len(
        shrink.named("ec_write")
    ) == 1
    assert moved(shrink.delta, "osd.*.rmw", "truncate_ops") == len(
        shrink.named("ec_truncate")
    ) == 1
    # k+m stores took the write and then the truncate
    assert moved(shrink.delta, "osd.*.store", "txns") == 2 * (K + M)
    assert len(shrink.named("sub_write")) == 2 * (K + M)


def test_wire_and_client_bytes(legs):
    write, read = legs
    assert moved(write.delta, "loadgen_client", "bytes_completed") == PAYLOAD
    assert moved(read.delta, "loadgen_client", "bytes_completed") == PAYLOAD
    # the payload to the primary, then k+m-1 shards to its peers
    assert moved(write.delta, "*.net", "bytes_sent") >= (
        PAYLOAD * (K + M) / K
    )
    assert moved(write.delta, "loadgen_client.net", "bytes_sent") >= PAYLOAD
    assert moved(read.delta, "loadgen_client.net", "bytes_recv") >= PAYLOAD
    # every frame sent in this process is received in it (heartbeats
    # in flight at a snapshot's edge may straddle it)
    for leg in (write, read):
        sent = moved(leg.delta, "*.net", "frames_sent")
        assert sent > 0
        assert abs(sent - moved(leg.delta, "*.net", "frames_recv")) <= 4
        assert moved(leg.delta, "*.net", "bytes_recv") == pytest.approx(
            moved(leg.delta, "*.net", "bytes_sent"), rel=0.01
        )


def test_worker_cpu_is_no_more_than_its_service_time(legs):
    write, _ = legs
    cpu = moved(write.delta, "osd.*.opq", "service_cpu_seconds")
    assert 0 < cpu <= moved(write.delta, "osd.*.opq", "service_seconds") * 1.05


# ------------------------------------------------- a stage after its parent
def test_late_cache_ready_adopts_the_ops_context():
    """An op queued behind another on its object runs ``_cache_ready``
    from the first op's ack, after its own ec_write span closed: its
    stage spans still hang off that ec_write."""
    from ceph_tpu.codecs.registry import create_codec
    from ceph_tpu.pipeline.rmw import RMWPipeline, ShardBackend
    from ceph_tpu.pipeline.stripe import StripeInfo
    from ceph_tpu.store import MemStore

    codec = create_codec(
        "jerasure", k=2, m=1, technique="reed_sol_van"
    )
    sinfo = StripeInfo(2, 1, 4096)
    backend = ShardBackend({i: MemStore(f"s{i}") for i in range(3)})
    backend.defer_acks = True
    pipe = RMWPipeline(sinfo, codec, backend, perf_name="late.rmw")
    tracer.clear()
    with tracer.span("client_op"):
        pipe.submit("o", 0, b"a" * 8192)
        pipe.submit("o", 0, b"b" * 8192)
    spans = tracer.dump_historic()
    assert len([s for s in spans if s["name"] == "ec_write"]) == 2
    assert len([s for s in spans if s["name"] == "ec_write.encode"]) == 1
    backend.release_deferred()  # op 1 commits; op 2 gets its cache
    spans = tracer.dump_historic()
    second = [s for s in spans if s["name"] == "ec_write"][1]
    late = [
        s for s in spans
        if s["name"] in ("ec_write.assemble", "ec_write.encode",
                         "ec_write.txn_build", "ec_write.fanout")
        and s["parent_id"] == second["span_id"]
    ]
    assert len(late) == 4
    assert {s["trace_id"] for s in late} == {second["trace_id"]}
    assert all(
        s["start_mono"] >= second["start_mono"] + second["duration"]
        for s in late
    )
    backend.release_deferred()
    perf_collection.deregister("late.rmw")


# ------------------------------------------------------------ the tracer
def _sink():
    coll = PerfCountersCollection()
    pc = (
        PerfCountersBuilder(coll, "stage")
        .add_time("work_seconds").add_time("wait_seconds")
        .create_perf_counters()
    )
    return coll, pc


def test_span_feeds_its_time_counter():
    _coll, pc = _sink()
    t = Tracer()
    with t.span("work", perf=pc, key="work_seconds", oid="x") as sp:
        time.sleep(0.01)
    assert sp.duration >= 0.01
    assert pc.get("work_seconds") == pytest.approx(sp.duration)
    assert sp.tags == {"oid": "x"}  # perf and key are no tags


def test_disabled_tracer_still_feeds_the_counter():
    _coll, pc = _sink()
    t = Tracer(enabled=False)
    with t.span("work", perf=pc, key="work_seconds") as sp:
        time.sleep(0.005)
    assert sp is None and t.dump_historic() == []
    assert pc.get("work_seconds") >= 0.005


def test_record_keeps_a_span_and_feeds_the_counter():
    _coll, pc = _sink()
    t = Tracer()
    with t.span("op") as op:
        pass
    now = time.perf_counter()
    sp = t.record(
        "wait", now - 0.25, now, trace_id=op.trace_id,
        parent_id=op.span_id, perf=pc, key="wait_seconds", osd=3,
    )
    assert sp.duration == pytest.approx(0.25)
    assert sp.start_mono == pytest.approx(now - 0.25)
    assert abs(sp.start - (time.time() - 0.25)) < 0.05
    assert (sp.trace_id, sp.parent_id) == (op.trace_id, op.span_id)
    assert pc.get("wait_seconds") == pytest.approx(0.25)
    assert t.dump_historic()[-1]["name"] == "wait"
    assert t.dump_historic()[-1]["tags"] == {"osd": 3}


def test_record_rejects_an_interval_that_ends_before_it_starts():
    _coll, pc = _sink()
    t = Tracer()
    with pytest.raises(ValueError, match="before it starts"):
        t.record("wait", 2.0, 1.0, trace_id="t", parent_id=None,
                 perf=pc, key="wait_seconds")
    assert pc.get("wait_seconds") == 0 and t.dump_historic() == []
    assert t.record("wait", 1.0, 1.0, trace_id="t", parent_id=None).duration == 0


class _FakeAnnotation:
    seen: list = []

    def __init__(self, name, **stats):
        self.name, self.stats = name, stats

    def __enter__(self):
        _FakeAnnotation.seen.append(self)
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def annotations(monkeypatch):
    _FakeAnnotation.seen = []
    monkeypatch.setattr(trace_mod, "_ANNOTATION_CLS", _FakeAnnotation)
    return _FakeAnnotation.seen


def test_clock_anchor_at_most_once_a_second(annotations, monkeypatch):
    t = Tracer()
    for _ in range(300):
        with t.span("s"):
            pass
    anchors = [a for a in annotations if a.name == trace_mod.ANCHOR_NAME]
    assert len(anchors) == 1  # the first span's exit, then silence
    assert len([a for a in annotations if a.name == "s"]) == 300
    near = time.perf_counter_ns()
    assert abs(anchors[0].stats["mono_ns"] - near) < 5e9
    # a second later the next span to close leaves another
    monkeypatch.setattr(t, "_last_anchor", time.perf_counter() - 1.01)
    for _ in range(50):
        with t.span("s"):
            pass
    anchors = [a for a in annotations if a.name == trace_mod.ANCHOR_NAME]
    assert len(anchors) == 2


def test_clear_brings_the_next_anchor_forward(annotations):
    t = Tracer()
    with t.span("s"):
        pass
    t.clear()
    with t.span("s"):
        pass
    anchors = [a for a in annotations if a.name == trace_mod.ANCHOR_NAME]
    assert len(anchors) == 2


def test_recorded_intervals_leave_no_annotation(annotations):
    t = Tracer()
    t.record("wait", 1.0, 2.0, trace_id="t", parent_id=None)
    assert annotations == []


# --------------------------------------------------- process set, exporter
def test_process_counters_are_monotone_across_dumps():
    coll = PerfCountersCollection()
    pc = register_process_counters(coll)
    first = coll.dump()["process"]
    sum(i * i for i in range(200000))  # burn some CPU
    second = coll.dump()["process"]
    assert second["cpu_seconds"] > first["cpu_seconds"] > 0
    assert second["wall_seconds"] > first["wall_seconds"]
    assert pc.get("cpu_seconds") >= second["cpu_seconds"]
    pc.reset()  # nothing stored, nothing to zero
    assert coll.dump()["process"]["cpu_seconds"] >= second["cpu_seconds"]
    assert "process" in perf_collection.dump()


def test_exporter_renders_time_counters_with_one_seconds_suffix():
    coll, pc = _sink()
    (
        PerfCountersBuilder(coll, "legacy")
        .add_time("busy").create_perf_counters()
    ).tinc("busy", 1.5)
    pc.tinc("work_seconds", 0.25)
    register_process_counters(coll)
    text = render_exposition(coll)
    assert 'ceph_tpu_work_seconds{set="stage"} 0.25' in text
    assert "work_seconds_seconds" not in text
    assert 'ceph_tpu_busy_seconds{set="legacy"} 1.5' in text
    assert "# TYPE ceph_tpu_work_seconds counter" in text
    assert "# TYPE ceph_tpu_cpu_seconds counter" in text
    assert 'ceph_tpu_cpu_seconds{set="process"}' in text


# --------------------------------------- spans on a profiler trace's clock
@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """A profiler trace (CPU: host plane only) taken while the tracer
    ran, and the spans it recorded."""
    import jax

    trace_dir = str(tmp_path_factory.mktemp("xplane"))
    t = Tracer()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with t.span("osd_op"):
            with t.span("ec_write.encode"):
                time.sleep(0.02)
            start = time.perf_counter()
            time.sleep(0.01)
            t.record("subop_wait", start, time.perf_counter(),
                     trace_id="t", parent_id=None)
    finally:
        jax.profiler.stop_trace()
    return trace_dir, t.dump_historic()


def test_anchors_put_spans_on_the_profilers_clock(profiled):
    from benchmark.trace import xplane
    from tools import trace_tool

    trace_dir, spans = profiled
    path = xplane.find_xplane(trace_dir)
    offset, n_anchors = trace_tool.clock_offset(path)
    assert n_anchors >= 1
    shifted = {s["name"]: s for s in trace_tool.shift_spans(spans, offset)}
    assert set(shifted) == {"osd_op", "ec_write.encode", "subop_wait"}
    # the annotation of the same span, as the profiler saw it
    seen = xplane.load(path, {"ec_write.encode", "osd_op"}).host
    for name, start, end in seen:
        assert shifted[name]["start"] == pytest.approx(start, abs=2e-3)
        assert shifted[name]["duration"] == pytest.approx(
            end - start, abs=2e-3
        )
    # a recorded interval has no annotation and lands on the clock too
    enc = shifted["ec_write.encode"]
    assert shifted["subop_wait"]["start"] >= enc["start"] + enc["duration"]


def test_a_trace_without_anchors_is_refused(tmp_path):
    import jax

    from benchmark.trace import xplane
    from tools import trace_tool

    jax.profiler.start_trace(str(tmp_path))
    jax.profiler.stop_trace()
    with pytest.raises(SystemExit, match="clock_anchor"):
        trace_tool.clock_offset(xplane.find_xplane(str(tmp_path)))


def test_device_report_gives_idle_time_to_the_innermost_stage():
    from benchmark.trace import xplane
    from tools import trace_tool

    def span(name, start, dur):
        return {"name": name, "start": start, "start_mono": start,
                "duration": dur}

    device = xplane.Trace(
        {"/device:TPU:0": [
            ("%_apply_tiled_csum.1 = custom-call(...)", 2.0, 2.5),
            ("%copy.1 = copy(...)", 2.5, 2.75),
        ]}, [],
    )
    spans = [
        span("osd_op", 0.0, 6.0), span("ec_write", 1.0, 3.0),
        span("ec_write.encode", 1.5, 2.0), span("codec.fetch", 2.25, 1.0),
        span("subop_wait", 4.0, 1.5),
    ]
    text = trace_tool.device_report(device, spans)
    by = {
        ln.split()[2]: float(ln.split()[0])
        for ln in text.splitlines() if ln.startswith("  ") and " s  " in ln
    }
    # busy 2.0-2.75; idle: encode 1.5-2.0, fetch 2.75-3.25, encode
    # 3.25-3.5, ec_write 1.0-1.5 and 3.5-4.0, subop_wait 4.0-5.5,
    # osd_op the rest
    assert by == pytest.approx({
        "ec_write.encode": 0.75, "codec.fetch": 0.5, "ec_write": 1.0,
        "subop_wait": 1.5, "osd_op": 1.5,
    })
    assert "%_apply_tiled_csum.1  500.000 ms: 1 in ec_write.encode" in text
    assert "%copy.1  250.000 ms: 1 in codec.fetch" in text
    lanes = trace_tool.device_lanes(device)
    assert [e["name"] for e in lanes if e["ph"] == "X"] == [
        "%_apply_tiled_csum.1", "%copy.1"
    ]
    assert {e["pid"] for e in lanes} == {2}


def test_trace_tool_main_with_xplane(profiled, tmp_path, capsys):
    import json

    from tools import trace_tool

    trace_dir, spans = profiled
    spans_file = tmp_path / "spans.json"
    spans_file.write_text(json.dumps(spans))
    chrome = tmp_path / "chrome.json"
    assert trace_tool.main([
        "--spans", str(spans_file), "--xplane", trace_dir,
        "--chrome", str(chrome), "--all",
    ]) == 0
    out = capsys.readouterr().out
    assert "clock: spans + " in out and "by innermost stage" in out
    events = json.loads(chrome.read_text())["traceEvents"]
    assert {e["name"] for e in events if e["ph"] == "X"} >= {
        "osd_op", "ec_write.encode"
    }
