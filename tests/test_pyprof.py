"""Python under the interpreter lock, by function and by thread role
(PR 47; ``ceph_tpu/utils/pyprof.py``, ``tools/pyprof_cell.py``).

What is held here: every function's calls and role are exact; a
generator and an exception that unwinds leave no frame behind; time
inside a C call is native and never self Python; a gap no event
brackets is lock lost; the profiler is one at a time and, off, is not
there at all.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from ceph_tpu.utils import pyprof
from ceph_tpu.utils.perf_counters import register_thread_roles

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = sys.monitoring.PROFILER_ID
HERE = __file__

register_thread_roles({"pyprof-test-msgr*": "msgr", "pyprof-test-tick*": "tick"})


@pytest.fixture
def profile():
    """Start, hand the profile out, and stop whatever the test did."""
    prof = pyprof.start()
    try:
        yield prof
    finally:
        if prof.active:
            pyprof.stop()


def functions(report: dict, role: str) -> dict[str, dict]:
    """The role's functions of this file, by qualified name."""
    return {
        f["name"].rsplit(".", 1)[-1]: f
        for f in report["roles"][role]["functions"] if f["file"] == HERE
    }


def in_threads(*named_targets) -> None:
    threads = [
        threading.Thread(target=target, name=name)
        for name, target in named_targets
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


# -- the code under the profiler ---------------------------------------
def leaf(x):
    return x + 1


def loop_of(n):
    x = 0
    for _ in range(n):
        x = leaf(x)
    return x


def msgr_work():
    loop_of(300)
    loop_of(200)


def tick_work():
    loop_of(70)


def counting():
    yield 1
    yield 2
    yield 3


def raises_at(depth):
    if depth == 0:
        raise KeyError("the bottom")
    raises_at(depth - 1)


def generator_and_unwind():
    assert sum(counting()) == 6
    half = counting()
    next(half)
    half.close()  # resumed by a throw, unwinds
    with pytest.raises(KeyError):
        raises_at(3)  # the raise, then three frames unwound


def waits():
    time.sleep(0.05)
    threading.Event().wait(0.05)


class TestBooking:
    def test_calls_and_roles_are_exact_in_two_threads(self, profile):
        in_threads(
            ("pyprof-test-msgr-0", msgr_work), ("pyprof-test-tick-0", tick_work)
        )
        pyprof.stop()
        report = profile.report(top=100_000)
        msgr, tick = functions(report, "msgr"), functions(report, "tick")
        assert msgr["msgr_work"]["calls"] == 1
        assert msgr["loop_of"]["calls"] == 2
        assert msgr["leaf"]["calls"] == 500
        assert "tick_work" not in msgr
        assert tick["tick_work"]["calls"] == 1
        assert tick["loop_of"]["calls"] == 1
        assert tick["leaf"]["calls"] == 70
        # an inclusive time holds the callee's (raw: what was booked)
        raw = {
            key[2]: f
            for key, f in profile.snapshot()["roles"]["msgr"]["funcs"].items()
            if key[0] == HERE
        }
        assert raw["leaf"]["cum_ns"] == raw["leaf"]["ns"]
        assert raw["loop_of"]["cum_ns"] == raw["loop_of"]["ns"] + raw["leaf"]["ns"]
        assert raw["loop_of"]["cum_call_intervals"] == 500

    def test_a_thread_started_under_the_profiler_gets_its_role(self, profile):
        before = {t.name for t in threading.enumerate()}
        in_threads(("pyprof-test-tick-late", tick_work))
        pyprof.stop()
        report = profile.report()
        assert report["thread_names"]["pyprof-test-tick-late"] == 1
        assert not [n for n in report["thread_names"] if n.startswith("Dummy")]
        assert "tick" in report["roles"]
        # and asking for the name registered no stand-in thread
        after = {t.name for t in threading.enumerate()}
        assert not [n for n in after - before if n.startswith("Dummy")]

    def test_a_generator_and_an_unwind_leave_every_stack_empty(self, profile):
        in_threads(
            ("pyprof-test-msgr-g", generator_and_unwind),
            ("pyprof-test-tick-g", generator_and_unwind),
        )
        pyprof.stop()
        stacks = {
            name: depth for name, depth in profile.stacks().items()
            if name.startswith("pyprof-test-")
        }
        assert len(stacks) == 2 and set(stacks.values()) == {0}, stacks
        report = profile.report(top=100_000)
        assert report["resyncs"] == 0
        fns = functions(report, "msgr")
        assert fns["counting"]["calls"] == 2      # started, not resumed
        assert fns["raises_at"]["calls"] == 4

    def test_a_sleep_and_a_wait_are_native_not_self_python(self, profile):
        in_threads(("pyprof-test-msgr-w", waits))
        pyprof.stop()
        report = profile.report(top=100_000)
        role = report["roles"]["msgr"]
        fn = functions(report, "msgr")["waits"]
        assert fn["native"]["time.sleep"] >= 45
        assert fn["self_ms"] < 5
        # Event.wait is Python down to the lock it parks on
        parked = {
            f["name"]: f for f in role["functions"]
        }["Condition.wait"]
        assert parked["native"]["lock.acquire"] >= 40
        assert role["native_ms"] >= 90
        assert role["self_ms"] < 10
        callees = {n["callee"]: n for n in role["native"]}
        assert callees["time.sleep"]["calls"] == 1

    def test_a_bound_method_of_a_c_callable_is_that_callables_call(self, profile):
        """A jitted function used as a method arrives as a bound method
        at the call and as itself at the return."""
        import types

        def naps():
            types.MethodType(time.sleep, 0.03)()

        in_threads(("pyprof-test-msgr-m", naps))
        pyprof.stop()
        report = profile.report(top=100_000)
        assert functions(report, "msgr")["naps"]["native"]["time.sleep"] >= 25
        assert report["resyncs"] == 0, report["resync_notes"]

    def test_a_wait_no_event_brackets_is_lock_lost(self, profile):
        """``with lock:`` enters C with no CALL event, as a forced
        switch enters nothing at all: the gap is neither self Python
        nor native."""
        held = threading.Lock()
        taken = threading.Event()

        def holder():
            with held:
                taken.set()
                time.sleep(0.03)

        def blocked():
            taken.wait(5)
            with held:
                pass

        in_threads(("pyprof-test-tick-h", holder), ("pyprof-test-msgr-b", blocked))
        pyprof.stop()
        report = profile.report(top=100_000)
        fn = functions(report, "msgr")["blocked"]
        assert fn["lost_n"] >= 1 and fn["lost_ms"] >= 15
        assert fn["self_ms"] < 5
        lost = report["roles"]["msgr"]["lock_lost"][0]
        assert lost["name"].endswith("blocked")
        assert lost["at"][0]["before"].startswith("line ")

    def test_a_lower_threshold_moves_python_into_lock_lost(self):
        prof = pyprof.start(lock_lost_ns=1)
        try:
            in_threads(("pyprof-test-msgr-t", msgr_work))
        finally:
            pyprof.stop()
        fns = functions(prof.report(top=100_000), "msgr")
        assert fns["leaf"]["lost_n"] == 500 and fns["leaf"]["self_ms"] == 0


class TestCalibration:
    def test_the_profilers_own_time_is_taken_off(self, profile):
        in_threads(("pyprof-test-msgr-c", lambda: loop_of(20_000)))
        pyprof.stop()
        assert profile.event_cost_ns > 0 and profile.call_cost_ns > 0
        assert profile.native_cost_ns > 0
        fn = functions(profile.report(top=100_000), "msgr")["leaf"]
        assert fn["self_ms"] < fn["raw_self_ms"]
        # a call of one addition is not microseconds
        assert fn["us_per_call"] < 1.0

    def test_a_quiet_builtin_is_self_python_of_its_caller(self, profile):
        def many_lens():
            return sum(len(()) for _ in range(2_000))

        in_threads(("pyprof-test-msgr-q", many_lens))
        pyprof.stop()
        role = profile.report(top=100_000)["roles"]["msgr"]
        assert "len" not in {n["callee"] for n in role["native"]}
        assert pyprof.is_quiet(len) and pyprof.is_quiet(dict.get)
        assert pyprof.is_quiet(b"".join) and pyprof.is_quiet(dict)
        assert not pyprof.is_quiet(time.sleep)
        assert not pyprof.is_quiet(threading.Lock().acquire)
        assert pyprof.callee_name(time.sleep) == "time.sleep"
        assert pyprof.callee_name(threading.Lock().acquire) == "lock.acquire"


class TestOneAtATimeAndOffIsOff:
    def test_start_twice_raises_and_stop_leaves_nothing(self):
        assert sys.monitoring.get_tool(TOOL) is None
        pyprof.start()
        try:
            assert sys.monitoring.get_tool(TOOL) == "ceph_tpu.pyprof"
            with pytest.raises(RuntimeError, match="already started"):
                pyprof.start()
        finally:
            pyprof.stop()
        assert sys.monitoring.get_tool(TOOL) is None
        assert sys.monitoring.get_events(TOOL) == 0
        with pytest.raises(RuntimeError, match="not started"):
            pyprof.stop()

    def test_another_profiler_in_the_way_is_an_error_and_is_left_alone(self):
        sys.monitoring.use_tool_id(TOOL, "someone else")
        try:
            with pytest.raises(RuntimeError, match="someone else"):
                pyprof.start()
            assert sys.monitoring.get_tool(TOOL) == "someone else"
        finally:
            sys.monitoring.free_tool_id(TOOL)

    def test_a_dump_goes_on_after_stop_and_divides_by_ops(self, profile):
        in_threads(("pyprof-test-msgr-d", msgr_work))
        pyprof.stop()
        whole = pyprof.dump(top=100_000)
        an_op = pyprof.dump(top=100_000, ops=10)
        assert whole["per"] == "window" and an_op["per"] == "op"
        assert (
            functions(an_op, "msgr")["leaf"]["calls"] * 10
            == functions(whole, "msgr")["leaf"]["calls"] == 500
        )
        text = pyprof.dump(text=True)
        assert "role msgr" in text and "never a measurement of speed" in text
        json.dumps(whole)

    def test_importing_the_package_registers_nothing(self):
        out = subprocess.run(
            [sys.executable, "-c",
             "import sys, ceph_tpu, ceph_tpu.utils.pyprof\n"
             "from ceph_tpu.utils.admin_socket import admin_socket\n"
             "from ceph_tpu.utils.trace import tracer\n"
             "admin_socket.execute('perf dump'); tracer.enabled = True\n"
             "m = sys.monitoring\n"
             "print([m.get_tool(i) for i in range(6)],"
             " [m.get_events(i) for i in range(6)])"],
            cwd=ROOT, capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.split("\n")[-2] == f"{[None] * 6} {[0] * 6}"


# -- the cell under the profiler, and the cell without it ----------------
def run_cell(module_args: list[str], tmp_path, prelude: str = ""):
    code = prelude + (
        "import runpy, sys\n"
        f"sys.argv = {module_args!r}\n"
        "runpy.run_module(sys.argv[0], run_name='__main__', alter_sys=True)\n"
    )
    return subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")},
    )


CELL = ["--workload", "rs84-64k.write", "--seed", "1", "--seconds", "4",
        "--rehearse"]


def test_the_tool_profiles_a_rehearsed_cell(tmp_path):
    out = run_cell(
        ["tools.pyprof_cell", *CELL, "--out", str(tmp_path)], tmp_path
    )
    result = json.loads(out.stdout.strip().split("\n")[-1])
    assert out.returncode == 0 and result["correct"] is True, (
        out.stdout[-3000:] + out.stderr[-3000:]
    )
    with open(tmp_path / "pyprof.rs84-64k.write.json") as f:
        report = json.load(f)
    assert {"op_worker", "msgr", "tick"} <= set(report["roles"])
    assert not [r for r in report["roles"] if r.startswith("Dummy")]
    assert not [n for n in report["thread_names"] if n.startswith("Dummy")]
    assert report["ops"] > 0 and report["per"] == "op"
    assert report["rehearsal"] is True and report["slow_down"] is None
    for role in ("op_worker", "msgr", "tick"):
        r = report["roles"][role]
        assert r["self_ms"] > 0 and r["functions"], role
    # native holds the waits: self Python is under the role's CPU (of
    # the roles whose threads outlive the window: a tick's one-tick
    # threads end, and their CPU goes to ``unlisted``)
    for role in ("op_worker", "msgr"):
        assert report["roles"][role]["self_ms"] < report["cpu_ms"][role], role
    table = (tmp_path / "pyprof.rs84-64k.write.txt").read_text()
    assert "role op_worker" in table and table in out.stdout


def test_an_unprofiled_cell_ends_with_no_tool(tmp_path):
    """``benchmark.run`` as the driver runs it never meets the
    profiler: at its last line no tool holds the profiler's id."""
    prelude = (
        "import os, sys\n"
        "leave = os._exit\n"
        "def told(code):\n"
        "    m = sys.monitoring\n"
        "    print('PYPROF', m.get_tool(m.PROFILER_ID),"
        " m.get_events(m.PROFILER_ID), 'ceph_tpu.utils.pyprof' in"
        " sys.modules, flush=True)\n"
        "    leave(code)\n"
        "os._exit = told\n"
    )
    short = [a if a != "4" else "2" for a in CELL]  # the window's seconds
    out = run_cell(["benchmark.run", *short, "--trace", "0"], tmp_path, prelude)
    lines = out.stdout.strip().split("\n")
    assert json.loads(lines[-2])["correct"] is True, out.stdout[-3000:]
    assert lines[-1] == "PYPROF None 0 False", lines[-1]
