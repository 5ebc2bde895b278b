"""Who waits for the interpreter lock, and who is on the CPU meanwhile
(PR 38). Two instruments, both read at dump time:

* the three native frame calls give the lock up and take it back
  themselves and keep, in a struct the connection owns, how many calls
  they made, the seconds inside them and the seconds it took to hold
  the lock again (``<name>.net:lock_waits`` / ``lock_waits_slow`` /
  ``call_seconds`` / ``lock_wait_seconds``);
* ``process.threads``: the process's CPU seconds by thread role, from
  one native pass over ``/proc/self/task``.

Neither adds a Python statement to a frame's path.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import threading
import time

import pytest

from ceph_tpu import native
from ceph_tpu.loadgen.cluster import LoadCluster  # every layer's roles
from ceph_tpu.msg import messages as msgs
from ceph_tpu.msg.messenger import Messenger, make_net_perf
from ceph_tpu.utils import perf_counters
from ceph_tpu.utils.config import config
from ceph_tpu.utils.perf_counters import (
    THREAD_ROLES,
    PerfCountersBuilder,
    PerfCountersCollection,
    built_once,
    perf_collection,
    register_thread_roles,
    thread_role,
)

needs_native = pytest.mark.skipif(
    not native.available(), reason="native tier unavailable"
)
needs_hooks = pytest.mark.skipif(
    not native.available() or native.hand_overs() is None,
    reason="the interpreter's lock functions cannot be handed over",
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INTERVAL = sys.getswitchinterval()
LOCK_KEYS = (
    "lock_waits", "lock_waits_slow", "call_seconds", "lock_wait_seconds",
)
SMALL, BIG = 300, 200 * 1024  # under / over native.FRAME_SCRATCH_BYTES


def wait(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.002)


# ---------------------------------------------------------------------------
# the native calls, over a socketpair
# ---------------------------------------------------------------------------
@pytest.fixture
def pair():
    a, b = socket.socketpair()
    yield a, b
    a.close()
    b.close()


@needs_hooks
class TestTheCallKeepsItsHandOver:
    @pytest.mark.parametrize("call,size,calls", [
        ("send", SMALL, 1), ("recv", SMALL, 1), ("recv_body", BIG, 2),
    ])
    def test_a_call_counts_once_inside_its_callers_wall(
        self, pair, call, size, calls
    ):
        a, b = pair
        tx, rx_ho = native.hand_overs(), native.hand_overs()
        rx = native.FrameReceiver(rx_ho)
        payload = [b"hdr", b"\x5a" * size]
        if call == "send":
            t0 = time.perf_counter()
            n = native.frame_send(a.fileno(), 7, 0, 1, payload, tx)
            wall = time.perf_counter() - t0
            assert n == 16 + 16 + 3 + size
            mine = tx
        else:
            # a frame past the socket buffer needs its reader running
            sender = threading.Thread(
                target=native.frame_send,
                args=(a.fileno(), 7, 0, 1, payload, tx),
            )
            sender.start()
            t0 = time.perf_counter()
            rc, segs = rx.recv(b.fileno())
            wall = time.perf_counter() - t0
            sender.join(5)
            assert rc == native.FRAME_DONE and segs == payload
            assert rx.calls == calls
            mine = rx_ho
        n_calls, slow, in_call, waited = mine.read()
        assert n_calls == calls
        assert slow in (0, 1)  # a sender thread may hold the lock once
        assert in_call > 0.0 and waited >= 0.0
        assert in_call + waited <= wall
        assert tx.calls == 1

    def test_alone_the_lock_comes_straight_back(self, pair):
        a, b = pair
        tx, rx_ho = native.hand_overs(), native.hand_overs()
        rx = native.FrameReceiver(rx_ho)
        for seq in range(500):
            native.frame_send(a.fileno(), 7, 0, seq, [b"x" * 100], tx)
            assert rx.recv(b.fileno())[0] == native.FRAME_DONE
        for ho in (tx, rx_ho):
            n_calls, _slow, _in_call, waited = ho.read()
            assert n_calls == 500
            assert waited / n_calls < 100e-6
        # no thread of this test wants the lock; another test's leaked
        # daemon thread may have taken it once
        assert tx.slow + rx_ho.slow <= 2

    def test_beside_a_spinning_thread_a_wait_is_a_switch_interval(self, pair):
        a, b = pair
        # the peer writes from another process (it never wants OUR
        # lock), a frame every 10 ms: each receive blocks long enough
        # for the spinner to take the lock, and has to ask it back
        frame = native.frame_encode(7, 0, 1, [b"x" * 100])
        peer = subprocess.Popen(
            [sys.executable, "-c",
             "import os, sys, time\n"
             "frame = bytes.fromhex(sys.argv[2])\n"
             "for _ in range(40):\n"
             "    time.sleep(0.01)\n"
             "    os.write(int(sys.argv[1]), frame)\n",
             str(a.fileno()), frame.hex()],
            pass_fds=[a.fileno()],
        )
        stop = threading.Event()

        def spin():
            x = 0
            while not stop.is_set():
                x += 1

        spinner = threading.Thread(target=spin, daemon=True)
        spinner.start()
        rx_ho = native.hand_overs()
        rx = native.FrameReceiver(rx_ho)
        try:
            for _ in range(40):
                assert rx.recv(b.fileno())[0] == native.FRAME_DONE
        finally:
            stop.set()
            spinner.join(5)
            peer.wait(10)
        n_calls, slow, in_call, waited = rx_ho.read()
        assert n_calls == 40
        assert waited / n_calls >= INTERVAL / 2
        assert slow >= 10
        assert in_call < waited  # from the header in hand: microseconds

    def test_an_idle_link_adds_nothing_while_its_reader_waits(self, pair):
        a, b = pair
        rx_ho = native.hand_overs()
        rx = native.FrameReceiver(rx_ho)
        out = []
        reader = threading.Thread(
            target=lambda: out.append(rx.recv(b.fileno()))
        )
        reader.start()
        time.sleep(0.3)  # blocked inside the call, no header yet
        assert rx_ho.read() == (0, 0, 0.0, 0.0)
        native.frame_send(a.fileno(), 7, 0, 1, [b"late"])
        reader.join(5)
        assert out[0][0] == native.FRAME_DONE
        n_calls, _slow, in_call, _waited = rx_ho.read()
        assert n_calls == 1
        assert 0.0 < in_call < 0.1  # from the header in hand, not 0.3 s

    def test_a_call_that_ends_at_eof_counts_and_times_nothing(self, pair):
        a, b = pair
        rx_ho = native.hand_overs()
        rx = native.FrameReceiver(rx_ho)
        a.close()
        assert rx.recv(b.fileno())[0] == native.FRAME_EOF
        n_calls, _slow, in_call, _waited = rx_ho.read()
        assert (n_calls, in_call) == (1, 0.0)


@needs_native
def test_without_the_two_pointers_frames_are_served_and_nothing_is_read():
    """An interpreter that does not export its lock functions: the
    three calls stay on ``CDLL`` as the parent's, no struct is made and
    a link reports ``lock_waits`` 0."""
    code = """
import ctypes, json, time
from ceph_tpu import native
native._lock_api = lambda: None
assert native.available()
from ceph_tpu.msg import messages as msgs
from ceph_tpu.msg.messenger import Messenger, make_net_perf
srv, cli = Messenger("osd.77"), Messenger("cli.77")
srv.net_pc, cli.net_pc = make_net_perf("osd.77.net"), make_net_perf("cli.77.net")
got = []
srv.set_dispatcher(lambda c, m: got.append(m))
conn = cli.connect(srv.bind())
for tid in range(20):
    conn.send(msgs.Ping(tid, 0))
deadline = time.monotonic() + 10
while len(got) < 20 and time.monotonic() < deadline:
    time.sleep(0.005)
out = {"pydll": isinstance(native._frame_lib, ctypes.PyDLL),
       "struct": native.hand_overs() is None, "got": len(got),
       "native_path": conn._rx_frames is not None or conn._tx_ho is None,
       "cli": cli.net_pc.dump(), "srv": srv.net_pc.dump()}
cli.shutdown(); srv.shutdown()
print(json.dumps(out))
"""
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["pydll"] is False and out["struct"] is True
    assert out["got"] == 20
    assert out["cli"]["io_calls"] == 20 and out["srv"]["io_calls"] == 20
    for side in ("cli", "srv"):
        assert [out[side][k] for k in LOCK_KEYS] == [0, 0, 0.0, 0.0]


# ---------------------------------------------------------------------------
# the messenger's four sampled counters
# ---------------------------------------------------------------------------
class Link:
    """A client messenger dialled into a server that answers each
    message with a Pong, each with its ``net`` set."""

    def __init__(self, tag, **msgr_kw):
        self.srv = Messenger(f"osd.{tag}", **msgr_kw)
        self.cli = Messenger(f"cli.{tag}", **msgr_kw)
        self.srv.net_pc = make_net_perf(f"osd.{tag}.net")
        self.cli.net_pc = make_net_perf(f"cli.{tag}.net")
        self.pongs = []
        self.srv.set_dispatcher(lambda c, m: c.send(msgs.Pong(m.tid, 0)))
        self.cli.set_dispatcher(lambda c, m: self.pongs.append(m))
        self.addr = self.srv.bind()
        self.conn = self.cli.connect(self.addr)

    def ping(self, n):
        want = len(self.pongs) + n
        for tid in range(n):
            self.conn.send(msgs.Ping(tid, 0))
        wait(lambda: len(self.pongs) >= want)
        # the servers' and the readers' counter updates trail the pong
        for m in (self.cli, self.srv):
            wait(lambda: m.net_pc.get("frames_sent")
                 == m.net_pc.get("frames_recv"))

    def close(self):
        self.cli.shutdown()
        self.srv.shutdown()
        for m in (self.cli, self.srv):
            perf_collection.deregister(m.net_pc.name)


@pytest.fixture
def link():
    made = []

    def make(tag, **kw):
        made.append(Link(tag, **kw))
        return made[-1]

    yield make
    for ln in made:
        ln.close()


PSK = b"cluster-keyring-secret"


@needs_hooks
class TestTheNetSetsLockCounters:
    def test_on_a_native_link_every_io_call_is_a_lock_wait(self, link):
        ln = link(41)
        ln.ping(50)
        for m in (ln.cli, ln.srv):
            d = m.net_pc.dump()
            assert d["io_calls"] == 100
            assert d["lock_waits"] == d["io_calls"]
            assert 0 <= d["lock_waits_slow"] <= d["lock_waits"]
            assert d["call_seconds"] > 0.0
            assert d["lock_wait_seconds"] >= 0.0
            # inside the call + waiting for the lock lie inside the
            # messenger's wall: what is left is its Python
            assert d["call_seconds"] + d["lock_wait_seconds"] <= (
                d["send_seconds"] + d["recv_seconds"]
            )
        assert perf_collection.dump()["osd.41.net"]["lock_waits"] == 100

    @pytest.mark.parametrize("kind", ["secure", "compressed", "codec-off"])
    def test_a_link_on_the_python_path_reads_zero(self, link, kind):
        if kind == "secure":
            pytest.importorskip(
                "cryptography.hazmat.primitives.ciphers.aead",
                reason="secure mode needs the cryptography package",
            )
        kw = {"secure": {"secret": PSK}, "compressed": {"compress": True},
              "codec-off": {}}[kind]
        with config.override(msgr_native_codec=kind != "codec-off"):
            ln = link({"secure": 42, "compressed": 43, "codec-off": 44}[kind],
                      **kw)
            ln.ping(10)
            for m in (ln.cli, ln.srv):
                d = m.net_pc.dump()
                assert d["io_calls"] >= 20
                assert [d[k] for k in LOCK_KEYS] == [0, 0, 0.0, 0.0]

    @pytest.mark.parametrize("how", ["close", "shutdown"])
    def test_a_closed_links_sums_stay_in_its_messengers_counters(
        self, link, how
    ):
        ln = link(45 if how == "close" else 46)
        ln.ping(30)
        before = {m: m.net_pc.dump() for m in (ln.cli, ln.srv)}
        assert before[ln.cli]["lock_waits"] == 60
        if how == "close":
            reader = ln.conn._reader
            ln.conn.close()
            reader.join(5)
            wait(lambda: not ln.cli._conns and not ln.srv._conns)
        else:
            ln.cli.shutdown()
            ln.srv.shutdown()
        for m in (ln.cli, ln.srv):
            after = m.net_pc.dump()
            for key in LOCK_KEYS:
                assert after[key] >= before[m][key], key
            # a reader's last call ended at EOF: a hand-over, no frame
            assert 60 <= after["lock_waits"] <= 61
            assert after["io_calls"] == 60

    def test_dumps_while_frames_fly_never_go_backwards(self, link):
        ln = link(47)
        stop = threading.Event()

        def pump():
            # links come and go under the dumps: each folds in once
            while not stop.is_set():
                conn = ln.cli.connect(ln.addr)
                for tid in range(20):
                    conn.send(msgs.Ping(tid, 0))
                conn.close()

        pumper = threading.Thread(target=pump, daemon=True)
        pumper.start()
        last = {m: dict.fromkeys(LOCK_KEYS, 0) for m in (ln.cli, ln.srv)}
        try:
            end = time.monotonic() + 0.6
            while time.monotonic() < end:
                for m in (ln.cli, ln.srv):
                    d = m.net_pc.dump()
                    for key in LOCK_KEYS:
                        assert d[key] >= last[m][key], key
                        last[m][key] = d[key]
        finally:
            stop.set()
            pumper.join(10)
        assert last[ln.cli]["lock_waits"] > 0

    def test_two_messengers_that_share_a_set_are_summed(self, link):
        ln = link(48)
        second = Messenger("osd.48")
        second.net_pc = ln.srv.net_pc  # an OSD's two messengers
        got = []
        second.set_dispatcher(lambda c, m: got.append(m))
        conn = ln.cli.connect(second.bind())
        try:
            ln.ping(5)
            for tid in range(7):
                conn.send(msgs.Ping(tid, 0))
            wait(lambda: len(got) == 7)
            wait(lambda: ln.srv.net_pc.get("frames_recv") == 12)
            d = ln.srv.net_pc.dump()
            assert d["lock_waits"] == d["io_calls"] == 5 + 5 + 7
        finally:
            second.shutdown()


# ---------------------------------------------------------------------------
# process.threads: the process's CPU by thread role
# ---------------------------------------------------------------------------
ROLE_KEYS = [f"{role}_cpu_seconds" for role in THREAD_ROLES]


def spin_for(seconds, name, halfway=None):
    """A thread of that name that burns ``seconds`` of CPU of its own
    (``time.thread_time``, not wall: a loaded runner makes it take
    longer, never burn less) and sets ``halfway`` once half of them are
    burned; returns it started."""

    def burn():
        start = time.thread_time()
        while (burned := time.thread_time() - start) < seconds:
            if halfway is not None and burned >= seconds / 2:
                halfway.set()

    t = threading.Thread(target=burn, name=name, daemon=True)
    t.start()
    return t


@needs_native
class TestProcessThreads:
    def test_the_set_has_one_key_a_role(self):
        d = perf_collection.dump()["process.threads"]
        assert list(d) == ROLE_KEYS and len(ROLE_KEYS) == 8

    @pytest.mark.parametrize("read", ["dump", "snapshot", "one-key"])
    def test_one_native_scan_a_dump_whatever_the_keys(self, monkeypatch, read):
        scans = []
        real = native.task_cpu
        monkeypatch.setattr(
            native, "task_cpu", lambda: scans.append(1) or real()
        )
        if read == "dump":
            perf_collection.dump()
        elif read == "snapshot":
            perf_collection.snapshot()
        else:
            perf_collection._sets["process.threads"].get(ROLE_KEYS[0])
        assert len(scans) == 1

    @pytest.mark.parametrize("name,role", [
        ("osd.91-worker", "op_worker"),
        ("osd.91-shard3", "op_worker"),
        ("msgr-osd.91-rd", "msgr"),
        ("osd.91-coal", "tick"),
        ("osd.91-hb", "tick"),
        ("ec-stream", "ec_stream"),
        ("loadgen-issue0", "client"),
        ("bench-issue", "client"),
        ("msgr-client-rd", "client"),
        ("a-name-nobody-listed", "other_python"),
    ])
    def test_a_busy_thread_moves_its_role_and_no_other(self, name, role):
        pc = perf_collection._sets["process.threads"]
        # a thread that has just ended is for a moment a task with no
        # Python thread (``runtime``): let an earlier case's be reaped
        time.sleep(0.05)
        before = pc.dump()
        halfway = threading.Event()
        t = spin_for(0.3, name, halfway)
        assert halfway.wait(60)
        mid = pc.dump()  # read while it runs: it is a task of the scan
        t.join(60)
        moved = {k: mid[k] - before[k] for k in ROLE_KEYS}
        mine = moved.pop(f"{role}_cpu_seconds")
        assert mine >= 0.1
        for key, other in moved.items():
            assert other < mine / 2, key

    def test_a_thread_that_ended_between_two_dumps_is_unlisted(self):
        pc = perf_collection._sets["process.threads"]
        time.sleep(0.05)
        before = pc.dump()
        spin_for(0.3, "osd.92-coal").join(60)
        time.sleep(0.05)
        after = pc.dump()
        assert after["unlisted_cpu_seconds"] - before[
            "unlisted_cpu_seconds"] >= 0.15
        assert after["tick_cpu_seconds"] - before["tick_cpu_seconds"] < 0.1

    @pytest.mark.parametrize("clock", ["pinned", "live"])
    def test_the_eight_sum_to_the_process_cpu(self, monkeypatch, clock):
        pc = perf_collection._sets["process.threads"]
        if clock == "pinned":
            monkeypatch.setattr(time, "process_time", lambda: 1234.5)
            assert sum(pc.dump().values()) == pytest.approx(1234.5, abs=1e-9)
            return
        lo = perf_collection.dump()["process"]["cpu_seconds"]
        total = sum(pc.dump().values())
        hi = perf_collection.dump()["process"]["cpu_seconds"]
        assert lo <= total <= hi

    def test_with_no_native_tier_all_of_it_is_unlisted(self, monkeypatch):
        monkeypatch.setattr(native, "available", lambda: False)
        d = perf_collection._sets["process.threads"].dump()
        assert d["unlisted_cpu_seconds"] > 0.0
        assert sum(d.values()) == pytest.approx(d["unlisted_cpu_seconds"])

    def test_the_descriptions_name_the_file_read(self):
        have = os.path.exists(f"/proc/self/task/{os.getpid()}/schedstat")
        for spec in perf_collection._sets["process.threads"]._schema.values():
            assert ("schedstat" in spec["desc"]) == have
            assert "/proc/self/task" in spec["desc"]


class TestThreadRoles:
    def test_the_longest_pattern_wins(self):
        assert thread_role("msgr-osd.3-rd") == "msgr"
        assert thread_role("msgr-client-rd") == "client"
        assert thread_role("msgr-client-hs") == "client"
        assert thread_role("Thread-7 (run)") is None

    @pytest.mark.parametrize("role", ["runtime", "unlisted", "bogus"])
    def test_only_a_python_threads_role_can_be_registered(self, role):
        with pytest.raises(ValueError):
            register_thread_roles({"x-*": role})

    def test_a_cluster_under_traffic_has_no_thread_nobody_listed(self):
        """Every thread the system starts carries a name some module
        registered: ``other_python`` holds what was put there on
        purpose (main, the log flusher, the op tracker's watchdog)."""
        mine_before = set(threading.enumerate())
        seen: dict[str, str | None] = {}

        def look():
            for t in threading.enumerate():
                if t not in mine_before:
                    seen[t.name] = thread_role(t.name)

        cluster = LoadCluster(n_osds=6, k=3, m=2, chunk_size=1024)
        try:
            io = cluster.client.open_ioctx(cluster.pool)
            done = []

            def write(i):
                io.write_full(f"obj-{i}", os.urandom(8192))
                done.append(i)

            writers = [
                threading.Thread(target=write, args=(i,),
                                 name=f"loadgen-w{i}")
                for i in range(24)
            ]
            for t in writers:
                t.start()
                look()
            for t in writers:
                t.join(30)
                look()
            assert len(done) == 24
            cluster.kill(cluster.most_primary_osd())
            look()
        finally:
            look()
            cluster.shutdown()
        unlisted = sorted(n for n, role in seen.items() if role is None)
        assert not unlisted
        roles = set(seen.values())
        assert {"op_worker", "msgr", "tick", "client"} <= roles
        on_purpose = {"log-flusher", "optracker-watchdog"}
        assert {
            n for n, role in seen.items() if role == "other_python"
        } <= on_purpose


# ---------------------------------------------------------------------------
# a counter set built on first use is built once
# ---------------------------------------------------------------------------
class TestBuiltOnce:
    def test_threads_that_miss_together_build_one_registered_set(self):
        collection = PerfCountersCollection()
        builds = []

        @built_once
        def counters():
            builds.append(1)
            time.sleep(0.02)  # every thread is inside its first use
            return (
                PerfCountersBuilder(collection, "lazy")
                .add_u64_counter("ops").create_perf_counters()
            )

        gate = threading.Barrier(10)
        got = []

        def first_use():
            gate.wait()
            pc = counters()
            pc.inc("ops")
            got.append(pc)

        threads = [threading.Thread(target=first_use) for _ in range(10)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(5)
        assert len(builds) == 1
        assert all(pc is got[0] for pc in got)
        assert collection.dump() == {"lazy": {"ops": 10}}

    @pytest.mark.parametrize("name", ["ec_stream", "ec_dispatch"])
    def test_the_two_lazily_built_sets_are_the_ones_a_dump_shows(self, name):
        if name == "ec_stream":
            from ceph_tpu.pipeline.dispatcher import _stream_counters as get
            key = "ops"
        else:
            from ceph_tpu.codecs.matrix_codec import _dispatch_counters as get
            key = "dispatches"
        gate = threading.Barrier(8)
        got = []

        def use():
            gate.wait()
            got.append(get())

        threads = [threading.Thread(target=use) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(5)
        assert all(pc is got[0] for pc in got)
        assert perf_collection._sets[name] is got[0]
        before = perf_collection.dump()[name][key]
        got[0].inc(key)
        assert perf_collection.dump()[name][key] == before + 1


class TestADumpHoldsNoLockWhileItSamples:
    def test_a_sampled_reading_may_register_a_set(self):
        collection = PerfCountersCollection()

        def reading():
            # a first use on another path, inside the reading
            PerfCountersBuilder(collection, "late").create_perf_counters()
            return 1

        PerfCountersBuilder(collection, "early").add_sampled(
            "n", reading
        ).create_perf_counters()
        out = []
        t = threading.Thread(target=lambda: out.append(collection.dump()))
        t.start()
        t.join(5)
        assert out and out[0]["early"] == {"n": 1}

    def test_a_sampled_reading_may_update_its_own_set(self):
        collection = PerfCountersCollection()
        holder = []

        def reading():
            holder[0].inc("reads")
            return 7

        holder.append(
            PerfCountersBuilder(collection, "self")
            .add_u64_counter("reads").add_sampled("n", reading)
            .create_perf_counters()
        )
        out = []
        t = threading.Thread(target=lambda: out.append(collection.dump()))
        t.start()
        t.join(5)
        assert out and out[0]["self"]["n"] == 7

    def test_a_group_is_read_once_and_keeps_the_schemas_order(self):
        collection = PerfCountersCollection()
        reads = []

        def group():
            reads.append(1)
            return {"a": len(reads), "b": 10 * len(reads)}

        pc = (
            PerfCountersBuilder(collection, "g")
            .add_u64_counter("first")
            .add_sampled_group(group, {"a": "one", "b": "ten"})
            .add_u64_counter("last")
            .create_perf_counters()
        )
        assert collection.dump() == {
            "g": {"first": 0, "a": 1, "b": 10, "last": 0}
        }
        assert len(reads) == 1
        assert pc.get("b") == 20 and len(reads) == 2


def test_perf_counters_module_keeps_no_other_layers_names():
    """``utils`` holds the registry; a role's names are declared beside
    the code that names the threads."""
    with open(perf_counters.__file__, encoding="utf-8") as f:
        src = f.read()
    for name in ("msgr-", "loadgen-", "ec-stream", "-coal", "objecter"):
        assert f'"{name}' not in src, name


def test_the_live_demos_host_report_reads_the_counters():
    from tools.trace_tool import host_report

    before = {
        "osd.0.net:lock_waits": 10, "osd.0.net:io_calls": 10,
        "process.threads:tick_cpu_seconds": 4.0,
    }
    after = {
        "osd.0.net:lock_waits": 110, "osd.0.net:io_calls": 110,
        "osd.0.net:lock_waits_slow": 25,
        "osd.0.net:lock_wait_seconds": 0.5, "osd.0.net:call_seconds": 0.1,
        "osd.0.net:send_seconds": 0.6, "osd.0.net:recv_seconds": 0.4,
        "client.net:lock_waits": 100, "client.net:io_calls": 100,
        "client.net:lock_wait_seconds": 0.3, "client.net:call_seconds": 0.1,
        "client.net:send_seconds": 0.5, "client.net:recv_seconds": 0.5,
        "process.threads:op_worker_cpu_seconds": 1.0,
        "process.threads:msgr_cpu_seconds": 0.5,
        "process.threads:tick_cpu_seconds": 3.75,  # a group thread ended
        "process.threads:unlisted_cpu_seconds": 0.75,
    }
    text = host_report(before, after)
    assert "lock_waits 200 of io_calls 200" in text
    assert "lock_waits_slow 25" in text
    assert "mean wait 4000.0 us, 12.5 %" in text
    assert ("in the call 10.0 %, waiting for the lock 40.0 %, "
            "Python 50.0 %") in text
    assert "op_worker 50.0 %  msgr 25.0 %  tick 25.0 %" in text
