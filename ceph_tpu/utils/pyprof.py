"""Python under the interpreter lock, by function and by thread role.

The chip's host has no ``perf`` and no eBPF, its thread clock ticks in
10 ms, cProfile is one call stack for the whole interpreter on the
wall clock, and a sampler over ``sys._current_frames()`` cannot tell
the thread that holds the lock from the twenty that want it. This
module is the profile the program takes of itself instead: CPython
3.12's ``sys.monitoring`` (tool id ``PROFILER_ID``), one call stack a
thread, the clock ``time.perf_counter_ns``.

Between two consecutive events of one thread the thread was running
Python with the lock held, unless it lost the lock in between. So:

* the interval is booked as **self Python** of the frame on top;
* the interval between a ``CALL`` of a callable the interpreter hands
  to C and its ``C_RETURN`` / ``C_RAISE`` is booked apart, as
  **native** under the callee's name (``ctpu_frame_recv``,
  ``lock.acquire``, ``ndarray.copy``, a class: ``type.__call__`` is C
  too). It holds the waits, the socket and everything done without
  the lock, and is never counted as Python. A C function that calls
  back into Python has the frames it runs booked as Python again;
* an interval between two events that are not a C call's two ends and
  that is longer than ``lock_lost_ns`` is a lock taken away in
  mid-Python: it goes to a third column, **lock lost**, with its
  count, under the function it happened in and the place where the
  thread's next event was. What no event brackets lands there too: a
  ``with lock:`` that waited (``BEFORE_WITH`` is no ``CALL``), a
  collection of the garbage collector, an operator or a slice copy
  that took that long in C. The place tells them apart.

C callables that cannot wait and cannot give the lock away (``len``,
``isinstance``, the methods of ``str`` / ``bytes`` / ``list`` / ``dict``,
``struct``, ``math``, a class being instantiated: ``is_quiet``) have
their call sites switched off at the first call seen, so their time is
self Python of the caller; a profile of every ``len`` would run the
program at a tenth of its speed and say nothing more.

What it cannot see: time inside a C call is not split into lock held
and lock given away; a lock lost for under ``lock_lost_ns`` is self
Python; a switch forced while the profiler's own callback runs is not
booked at all; and it watches a slowed run, in which work that comes
round by the clock (a tick) is more *an op* by the slow-down, so a role
prints its share of a core as well.

Every interval carries the profiler's own time between the end of one
callback and the start of the next. ``start`` measures that cost on a
loop of empty calls (as cProfile's ``calibrate``), a report takes
``intervals x cost`` off each function and prints both numbers.

**Off, it costs nothing**: nothing here runs at import, the tool id is
free and no event is set until ``start()``; nothing in ``ceph_tpu``,
no config option and no tracer switch calls ``start``. The two ways
in are the admin socket (``pyprof start`` / ``stop`` / ``dump``) and
``tools/pyprof_cell.py``. A profiled run is never a measurement of
speed.
"""

from __future__ import annotations

import builtins
import collections
import json
import struct
import sys
import threading
import time
from types import (
    BuiltinFunctionType, FunctionType, MethodDescriptorType, MethodType,
    ModuleType, WrapperDescriptorType,
)

from .perf_counters import thread_role

#: an interval between two non-C events longer than this is a lock lost
LOCK_LOST_NS = 1_000_000
_TOOL_NAME = "ceph_tpu.pyprof"
_CALIBRATE_CALLS = 3_000
_CALIBRATE_ROUNDS = 7
_LOST_ROWS = 5  # lock-lost functions a role in a table cut to its top rows

# A record is a list, so that a callback books with two index stores.
# Of a function: [ns, intervals, calls, code, intervals a call of a
# Python function ended (they hold the CALL event's short callback as
# well), lock-lost ns and count, then the inclusive columns: open
# activations, the thread's three sums when the outermost opened, what
# the thread booked between its opening and its closing]. Of a native
# call site: [ns, intervals, calls, None, callee, name].
_NS, _IV, _CALLS, _CODE, _IVC, _LOST_NS, _LOST_N = 0, 1, 2, 3, 4, 5, 6
_DEPTH, _PY0, _IV0, _IVC0, _CUM_NS, _CUM_IV, _CUM_IVC = 7, 8, 9, 10, 11, 12, 13
_CALLEE, _NAME = 4, 5


class _Thread:
    """What one thread booked. Only its own thread writes it."""

    __slots__ = (
        "ident", "name", "stack", "last", "py", "iv", "ivc", "funcs",
        "sites", "lost_at", "resyncs", "notes",
    )

    def __init__(self, ident: int) -> None:
        self.ident = ident
        self.name: "str | None" = None
        self.stack: list = []
        self.last = 0
        # self Python ns this thread booked, all functions, and in how
        # many intervals of the two kinds
        self.py = self.iv = self.ivc = 0
        self.funcs: dict = {}
        self.sites: dict = {}
        self.lost_at: dict = {}
        self.resyncs = 0
        self.notes: list = []  # the first few resyncs, for the report


# C callables that cannot wait and cannot give the lock away: their call
# sites are switched off at the first call seen (``DISABLE``), so their
# time is self Python of the caller, which is what it is, and a
# function of many ``len`` and ``isinstance`` does not run at a tenth
# of its speed. Everything else that is C stays a native interval.
_QUIET_TYPES = frozenset({
    str, bytes, bytearray, list, dict, set, frozenset, tuple, int, float,
    bool, complex, range, slice, object, type, memoryview, struct.Struct,
    collections.deque, collections.OrderedDict, collections.defaultdict,
    type(None), type({}.items()), type({}.keys()), type({}.values()),
})
_QUIET_MODULES = frozenset({
    "builtins", "math", "operator", "_operator", "itertools", "_struct",
    "binascii", "_bisect", "_heapq", "_functools", "_collections",
    "_weakref", "_abc", "sys",
})
_LOUD_BUILTINS = frozenset({
    "print", "input", "open", "exec", "eval", "compile", "__import__",
    "breakpoint",
})
_QUIET_FUNCS = frozenset({
    time.monotonic, time.monotonic_ns, time.perf_counter,
    time.perf_counter_ns, time.time, time.time_ns, threading.get_ident,
    threading.get_native_id,
})


def is_quiet(callee) -> bool:
    """Whether a C callable's call sites are switched off (above)."""
    kind = type(callee)
    if kind is type:
        # a class being instantiated: type.__call__ is glue, and an
        # __init__ written in Python is frames like any other
        return True
    if kind is BuiltinFunctionType:
        if callee in _QUIET_FUNCS:
            return True
        owner = callee.__self__
        if isinstance(owner, ModuleType):
            return owner.__name__ in _QUIET_MODULES and (
                owner is not builtins or callee.__name__ not in _LOUD_BUILTINS
            )
        return (owner if isinstance(owner, type) else type(owner)) in _QUIET_TYPES
    if kind is MethodDescriptorType or kind is WrapperDescriptorType:
        return callee.__objclass__ in _QUIET_TYPES
    return False


def callee_name(fn) -> str:
    """A C callable's name as a report prints it: ``time.sleep``,
    ``lock.acquire``, ``ndarray.copy``, ``ctpu_frame_recv``, a class's
    qualified name."""
    name = getattr(fn, "__qualname__", None) or getattr(fn, "__name__", None)
    if not isinstance(name, str) or not name:
        return type(fn).__qualname__
    module = getattr(fn, "__module__", None)
    if "." not in name and isinstance(module, str) and module != "builtins":
        return f"{module}.{name}"
    return name


class Profile:
    """One profiled window: the callbacks, what every thread booked,
    and the report. Made by ``start()``."""

    def __init__(self, lock_lost_ns: int = LOCK_LOST_NS) -> None:
        self.lock_lost_ns = int(lock_lost_ns)
        # the profiler's own time in an interval booked as Python, in
        # one that a call of a Python function ended, in a native one
        self.event_cost_ns = self.call_cost_ns = self.native_cost_ns = 0.0
        self.t0_ns = self.t1_ns = 0
        self.active = False
        self._threads: list[_Thread] = []
        self._callbacks: dict = {}

    # ------------------------------------------------------ the callbacks
    def _make_callbacks(self) -> dict:
        tls = threading.local()
        threads = self._threads
        clock = time.perf_counter_ns
        get_ident = threading.get_ident
        lost_after = self.lock_lost_ns
        registered = threading._active  # ident -> Thread, once it runs
        disable = sys.monitoring.DISABLE

        def enter() -> _Thread:
            st = tls.st = _Thread(get_ident())
            threads.append(st)
            return st

        def lost(st, top, dt, code, offset) -> None:
            top[_LOST_NS] += dt
            top[_LOST_N] += 1
            at = st.lost_at.get((top[_CODE], code, offset))
            if at is None:
                at = st.lost_at[(top[_CODE], code, offset)] = [0, 0]
            at[0] += dt
            at[1] += 1

        def resync(st, what, code, callee=None) -> None:
            st.resyncs += 1
            if len(st.notes) < 3:
                top = st.stack[-1] if st.stack else None
                st.notes.append((
                    what, code.co_qualname,
                    callee_name(callee) if callee is not None else None,
                    None if top is None else
                    top[_NAME] if top[_CODE] is None else top[_CODE].co_qualname,
                ))

        def new_func(st, code) -> list:
            rec = st.funcs[code] = [0, 0, 0, code, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0]
            return rec

        def close(st, rec) -> None:
            rec[_DEPTH] -= 1
            if not rec[_DEPTH]:
                rec[_CUM_NS] += st.py - rec[_PY0]
                rec[_CUM_IV] += st.iv - rec[_IV0]
                rec[_CUM_IVC] += st.ivc - rec[_IVC0]

        def sync(st, code) -> list:
            """Make the frame of ``code`` the top of the stack: it is
            the one running, whatever the stack says (a frame that was
            running before ``start``, an end the events did not show)."""
            stack = st.stack
            for i in range(len(stack) - 1, -1, -1):
                if stack[i][_CODE] is code:
                    for dropped in stack[i + 1:]:
                        if dropped[_CODE] is not None:
                            close(st, dropped)
                    resync(st, "frames above the running one", code)
                    del stack[i + 1:]
                    return stack[i]
            rec = st.funcs.get(code) or new_func(st, code)
            if stack:
                resync(st, "running frame not on the stack", code)
            if not rec[_DEPTH]:
                rec[_PY0], rec[_IV0], rec[_IVC0] = st.py, st.iv, st.ivc
            rec[_DEPTH] += 1
            stack.append(rec)
            return rec

        def entering(counts: int):
            # PY_START counts a call; PY_RESUME, and PY_THROW (a
            # generator resumed by throw() or close()), put the frame
            # on top again until it yields, returns or unwinds
            def py_enter(code, offset, exc=None):
                now = clock()
                try:
                    st = tls.st
                except AttributeError:
                    st = enter()
                stack = st.stack
                if stack:
                    top = stack[-1]
                    dt = now - st.last
                    if top[_CODE] is None:
                        top[_NS] += dt
                        top[_IV] += 1
                    elif dt > lost_after:
                        lost(st, top, dt, code, -1)
                    else:
                        top[_NS] += dt
                        top[_IVC] += 1
                        st.py += dt
                        st.ivc += 1
                if st.name is None:
                    # a thread's first events come before ``threading``
                    # knows it: asked here until it does, never through
                    # current_thread(), which would register a Dummy-N
                    th = registered.get(st.ident)
                    if th is not None:
                        st.name = th.name
                rec = st.funcs.get(code)
                if rec is None:
                    rec = new_func(st, code)
                rec[_CALLS] += counts
                if not rec[_DEPTH]:
                    rec[_PY0] = st.py
                    rec[_IV0] = st.iv
                    rec[_IVC0] = st.ivc
                rec[_DEPTH] += 1
                stack.append(rec)
                st.last = clock()

            return py_enter

        def py_leave(code, offset, value):
            now = clock()
            try:
                st = tls.st
            except AttributeError:
                st = enter()
            stack = st.stack
            if stack and stack[-1][_CODE] is code:
                top = stack.pop()
            else:
                top = sync(st, code)
                stack.pop()
            if st.last:
                dt = now - st.last
                if dt > lost_after:
                    lost(st, top, dt, code, offset)
                else:
                    top[_NS] += dt
                    top[_IV] += 1
                    st.py += dt
                    st.iv += 1
            top[_DEPTH] -= 1
            if not top[_DEPTH]:
                top[_CUM_NS] += st.py - top[_PY0]
                top[_CUM_IV] += st.iv - top[_IV0]
                top[_CUM_IVC] += st.ivc - top[_IVC0]
            st.last = clock()

        def call(code, offset, callee, arg0):
            kind = type(callee)
            if kind is FunctionType:
                # the interpreter runs it itself: PY_START is next and
                # there is no C_RETURN. Nothing is booked here: this
                # callback's few lines are in the caller's interval,
                # which is counted apart and priced apart
                return
            now = clock()
            try:
                st = tls.st
            except AttributeError:
                st = enter()
            stack = st.stack
            if stack and stack[-1][_CODE] is code:
                top = stack[-1]
            else:
                top = sync(st, code)
            if st.last:
                dt = now - st.last
                if dt > lost_after:
                    lost(st, top, dt, code, offset)
                else:
                    top[_NS] += dt
                    top[_IV] += 1
                    st.py += dt
                    st.iv += 1
            if kind is MethodType:
                # a bound method of a Python function may be unwrapped
                # and run by the interpreter, or go through C and end
                # in a C_RETURN: the call site decides. Its frame is
                # Python either way, so it gets no native record. One
                # of a C callable (a jitted function as a method) is
                # that callable's call, and ends under its name
                callee = callee.__func__
                kind = type(callee)
            if kind is not FunctionType:
                key = (code, offset)
                rec = st.sites.get(key)
                if rec is None and is_quiet(callee):
                    st.last = clock()
                    return disable
                if rec is None or rec[_CALLEE] is not callee:
                    name = callee_name(callee)
                    if rec is None or rec[_NAME] != name:
                        key = (code, offset, name)
                        rec = st.sites.get(key)
                        if rec is None:
                            rec = st.sites[key] = [0, 0, 0, None, callee, name]
                            st.sites.setdefault((code, offset), rec)
                rec[_CALLS] += 1
                stack.append(rec)
            st.last = clock()

        def c_end(code, offset, callee, arg0):
            now = clock()
            try:
                st = tls.st
            except AttributeError:
                st = enter()
            stack = st.stack
            if stack:
                top = stack[-1]
                if top[_CODE] is None:
                    stack.pop()
                    top[_NS] += now - st.last
                    top[_IV] += 1
                elif type(callee) is MethodType or is_quiet(callee):
                    # Python all the same: a bound method that went
                    # through C, or the one call of a quiet callee
                    # that was seen before its site was switched off
                    top[_NS] += now - st.last
                    top[_IV] += 1
                    st.py += now - st.last
                    st.iv += 1
                else:
                    resync(st, "C call's start not seen", code, callee)
            st.last = clock()

        E = sys.monitoring.events
        return {
            E.PY_START: entering(1), E.PY_RESUME: entering(0),
            E.PY_THROW: entering(0), E.PY_RETURN: py_leave,
            E.PY_YIELD: py_leave, E.PY_UNWIND: py_leave,
            E.CALL: call, E.C_RETURN: c_end, E.C_RAISE: c_end,
        }

    # C_RETURN and C_RAISE come with CALL and cannot be set themselves
    @staticmethod
    def _event_set() -> int:
        E = sys.monitoring.events
        return (
            E.PY_START | E.PY_RESUME | E.PY_THROW | E.PY_RETURN
            | E.PY_YIELD | E.PY_UNWIND | E.CALL
        )

    # -------------------------------------------------------- start, stop
    def _start(self) -> None:
        mon = sys.monitoring
        tool = mon.PROFILER_ID
        try:
            mon.use_tool_id(tool, _TOOL_NAME)
        except ValueError:
            raise RuntimeError(
                f"sys.monitoring's profiler id is held by "
                f"{mon.get_tool(tool)!r}"
            ) from None
        self._register()
        # the call sites an earlier profile switched off come back
        mon.restart_events()
        try:
            self._calibrate()
        except BaseException:
            self._release()
            raise
        self.active = True
        self.t0_ns = time.perf_counter_ns()
        mon.set_events(tool, self._event_set())

    def _release(self) -> None:
        mon = sys.monitoring
        tool = mon.PROFILER_ID
        mon.set_events(tool, 0)
        for event in self._callbacks:
            mon.register_callback(tool, event, None)
        mon.free_tool_id(tool)

    def _stop(self) -> None:
        self.t1_ns = time.perf_counter_ns()
        self._release()
        self.active = False

    def _calibrate(self) -> None:
        """What an interval holds of the profiler's own time: a loop of
        small Python calls and one of small C calls with the events on,
        as the window will have them, for every thread, against the same
        loops with no event. A loop's plain time is split evenly between
        its two intervals an iteration. What the other threads book
        meanwhile is thrown away with the calibration's own."""
        mon = sys.monitoring
        tool = mon.PROFILER_ID

        def one(x):
            return x

        def py_loop(n):
            x = 0
            for _ in range(n):
                x = one(x)

        def c_loop(n, fn=threading.Lock().locked):
            for _ in range(n):
                fn()

        def timed(loop) -> int:
            t = time.perf_counter_ns()
            loop(_CALIBRATE_CALLS)
            return time.perf_counter_ns() - t

        codes = [f.__code__ for f in (one, py_loop, c_loop)]
        rounds = []
        for _ in range(_CALIBRATE_ROUNDS):
            # short rounds, so that most run without the lock changing
            # hands, and the median of their costs, so that the ones
            # that did not do not count
            half = min(timed(py_loop), timed(py_loop)) / 2
            c_half = min(timed(c_loop), timed(c_loop)) / 2
            mon.set_events(tool, self._event_set())
            try:
                py_loop(_CALIBRATE_CALLS)
                c_loop(_CALIBRATE_CALLS)
            finally:
                mon.set_events(tool, 0)
            (st,) = [
                t for t in list(self._threads)
                if t.ident == threading.get_ident()
            ]
            callee, loop = st.funcs.pop(codes[0]), st.funcs.pop(codes[1])
            native = [r for k, r in st.sites.items() if len(k) == 2]
            rounds.append((
                max(callee[_NS] - half, 0) / max(callee[_IV], 1),
                max(loop[_NS] - half, 0) / max(loop[_IVC], 1),
                max(sum(r[_NS] for r in native) - c_half, 0)
                / max(sum(r[_IV] for r in native), 1),
            ))
            st.sites.clear()
            del st.stack[:]
        self.event_cost_ns, self.call_cost_ns, self.native_cost_ns = (
            sorted(column)[len(column) // 2] for column in zip(*rounds)
        )
        # the calibration's bookings are not the window's: every
        # callback made from here on sees a thread anew
        self._threads.clear()
        self._register()

    def _register(self) -> None:
        """Fresh callbacks: no thread has been seen by them."""
        mon = sys.monitoring
        self._callbacks = self._make_callbacks()
        for event, fn in self._callbacks.items():
            mon.register_callback(mon.PROFILER_ID, event, fn)

    # -------------------------------------------------------- the report
    def stacks(self) -> dict[str, int]:
        """Depth of every thread's stack, by thread name (tests)."""
        return {
            f"{st.name or 'unregistered'}-{st.ident}": len(st.stack)
            for st in list(self._threads)
        }

    def snapshot(self) -> dict:
        """What every thread booked so far, merged by (role, function):
        raw nanoseconds and counts. Safe while the profile runs: a
        thread's tables are copied in one C call each."""
        roles: dict[str, dict] = {}
        names: dict[str, int] = {}
        resyncs, notes = 0, []
        for st in list(self._threads):
            role = thread_role(st.name) if st.name else None
            role = role or "other_python"
            names[st.name or "unregistered"] = (
                names.get(st.name or "unregistered", 0) + 1
            )
            resyncs += st.resyncs
            notes.extend(st.notes)
            into = roles.setdefault(
                role, {"threads": 0, "funcs": {}, "lost_at": {}}
            )
            into["threads"] += 1
            for code, rec in list(st.funcs.items()):
                f = into["funcs"].get(_key(code))
                if f is None:
                    f = into["funcs"][_key(code)] = {
                        "ns": 0, "intervals": 0, "call_intervals": 0,
                        "calls": 0, "lost_ns": 0, "lost_n": 0, "cum_ns": 0,
                        "cum_intervals": 0, "cum_call_intervals": 0,
                        "native": {},
                    }
                f["ns"] += rec[_NS]
                f["intervals"] += rec[_IV]
                f["call_intervals"] += rec[_IVC]
                f["calls"] += rec[_CALLS]
                f["lost_ns"] += rec[_LOST_NS]
                f["lost_n"] += rec[_LOST_N]
                f["cum_ns"] += rec[_CUM_NS]
                f["cum_intervals"] += rec[_CUM_IV]
                f["cum_call_intervals"] += rec[_CUM_IVC]
            seen = set()
            for key, rec in list(st.sites.items()):
                if id(rec) in seen:
                    continue
                seen.add(id(rec))
                f = into["funcs"].get(_key(key[0]))
                if f is None:
                    continue
                n = f["native"].setdefault(rec[_NAME], [0, 0, 0])
                n[0] += rec[_NS]
                n[1] += rec[_IV]
                n[2] += rec[_CALLS]
            for (code, ev_code, offset), (ns, n) in list(st.lost_at.items()):
                at = into["lost_at"].setdefault(
                    (_key(code), _place(ev_code, offset)), [0, 0]
                )
                at[0] += ns
                at[1] += n
        end = self.t1_ns if not self.active else time.perf_counter_ns()
        return {
            "window_s": (end - self.t0_ns) / 1e9, "roles": roles,
            "thread_names": names, "resyncs": resyncs,
            "resync_notes": [
                f"{what}: in {where}" + (f", callee {callee}" if callee else "")
                + f", {on_top} on top"
                for what, where, callee, on_top in notes[:12]
            ],
        }

    def report(self, ops: "float | None" = None, top: int = 15,
               unprofiled_ops: "float | None" = None,
               unprofiled_window_s: "float | None" = None) -> dict:
        """The profile as a JSON-ready dict. With ``ops`` (the window's
        ``loadgen_client:op_completed`` delta) every time is ms an op
        and every count is an op's; without, they are the window's
        totals (``per`` says which). ``unprofiled_ops`` is what the
        same seed completed with the profiler off: the slow-down."""
        snap = self.snapshot()
        per = float(ops) if ops else 1.0
        cost, ccost = self.event_cost_ns, self.call_cost_ns
        ncost = self.native_cost_ns
        window_s = snap["window_s"]

        def ms(ns: float) -> float:
            return ns / 1e6 / per

        out_roles = {}
        for role, data in snap["roles"].items():
            funcs = []
            callees: dict[str, list] = {}
            for (file, line, name), f in data["funcs"].items():
                self_ns = max(
                    f["ns"] - f["intervals"] * cost
                    - f["call_intervals"] * ccost, 0.0,
                )
                cum_ns = max(
                    f["cum_ns"] - f["cum_intervals"] * cost
                    - f["cum_call_intervals"] * ccost, 0.0,
                )
                native = {}
                for callee, (ns, iv, calls) in f["native"].items():
                    ns = max(ns - iv * ncost, 0.0)
                    native[callee] = ns
                    c = callees.setdefault(callee, [0.0, 0])
                    c[0] += ns
                    c[1] += calls
                funcs.append({
                    "file": file, "line": line, "name": name,
                    "calls": f["calls"] / per,
                    "self_ms": ms(self_ns), "raw_self_ms": ms(f["ns"]),
                    "us_per_call": self_ns / 1e3 / max(f["calls"], 1),
                    "cum_ms": ms(cum_ns),
                    "cum_us_per_call": cum_ns / 1e3 / max(f["calls"], 1),
                    "native_ms": ms(sum(native.values())),
                    "lost_ms": ms(f["lost_ns"]), "lost_n": f["lost_n"] / per,
                    "native": {
                        k: ms(v) for k, v in sorted(
                            native.items(), key=lambda kv: -kv[1]
                        )[:5]
                    },
                })
            funcs.sort(key=lambda f: -f["self_ms"])
            lost = sorted(
                (f for f in funcs if f["lost_n"]), key=lambda f: -f["lost_ms"]
            )[:top]
            places: dict[tuple, list] = {}
            for (fkey, place), (ns, n) in data["lost_at"].items():
                places.setdefault(fkey, []).append((ns, n, place))
            intervals = sum(
                f["intervals"] + f["call_intervals"]
                + sum(n[1] for n in f["native"].values())
                for f in data["funcs"].values()
            )
            out_roles[role] = {
                "threads": data["threads"],
                # what the role held of one core: per-time work (a
                # tick) reads higher an op the slower the run
                "self_core_pct": sum(
                    f["self_ms"] for f in funcs
                ) * per / window_s / 10,
                "intervals": intervals / per,
                "self_ms": sum(f["self_ms"] for f in funcs),
                "raw_self_ms": sum(f["raw_self_ms"] for f in funcs),
                "native_ms": sum(f["native_ms"] for f in funcs),
                "lost_ms": sum(f["lost_ms"] for f in funcs),
                "lost_n": sum(f["lost_n"] for f in funcs),
                "functions_seen": len(funcs),
                "functions": funcs[:top],
                "native": [
                    {"callee": k, "ms": ms(ns), "calls": calls / per,
                     "us_per_call": ns / 1e3 / max(calls, 1)}
                    for k, (ns, calls) in sorted(
                        callees.items(), key=lambda kv: -kv[1][0]
                    )[:top]
                ],
                "lock_lost": [
                    {"file": f["file"], "line": f["line"], "name": f["name"],
                     "ms": f["lost_ms"], "n": f["lost_n"],
                     "at": [
                         {"before": place, "ms": ms(ns), "n": n / per}
                         for ns, n, place in sorted(
                             places.get((f["file"], f["line"], f["name"]), []),
                             reverse=True,
                         )[:3]
                     ]}
                    for f in lost
                ],
            }
        slow = None
        if unprofiled_ops and ops:
            rate = ops / window_s
            rate_off = unprofiled_ops / (unprofiled_window_s or window_s)
            slow = {
                "ops_profiled": ops, "ops_unprofiled": unprofiled_ops,
                "factor": rate_off / rate if rate else None,
            }
        return {
            "pyprof": 1, "per": "op" if ops else "window",
            "ops": ops, "window_s": window_s, "active": self.active,
            "lock_lost_ns": self.lock_lost_ns,
            "event_cost_ns": cost, "call_cost_ns": ccost,
            "native_cost_ns": ncost,
            "slow_down": slow, "resyncs": snap["resyncs"],
            "resync_notes": snap["resync_notes"],
            "thread_names": snap["thread_names"],
            "roles": dict(sorted(
                out_roles.items(), key=lambda kv: -kv[1]["self_ms"]
            )),
        }


def _key(code) -> tuple:
    """A function as a report names it: (file, first line, qualified name)."""
    return code.co_filename, code.co_firstlineno, code.co_qualname


def _place(code, offset: int) -> str:
    """Where the event was that ended a gap: a line of the function the
    gap was in, or the function it was about to enter."""
    if offset < 0:
        return f"entering {code.co_qualname}"
    line = None
    for start, end, ln in code.co_lines():
        if start <= offset < end:
            line = ln
            break
    return f"line {line}"


def _short(path: str) -> str:
    for mark in ("/ceph_tpu/", "/benchmark/", "/tools/", "/site-packages/",
                 "/lib/python"):
        at = path.rfind(mark)
        if at >= 0:
            return path[at + 1:]
    return path


def format_report(report: dict, top: "int | None" = None) -> str:
    """The report as the text table PERF.md quotes (the first ``top``
    rows of each list, all of them by default)."""
    unit = "ms/op" if report["per"] == "op" else "ms"
    each = "/op" if report["per"] == "op" else ""
    slow = report["slow_down"]
    lines = [
        f"pyprof: window {report['window_s']:.2f} s, "
        + (f"{report['ops']:g} ops" if report["ops"] else "no op count")
        + f", {sum(r['threads'] for r in report['roles'].values())} threads "
        f"seen; an interval's own cost taken off: "
        f"{report['event_cost_ns']:.0f} ns (Python), "
        f"{report['call_cost_ns']:.0f} ns (Python, ended by a call), "
        f"{report['native_cost_ns']:.0f} ns (native); lock lost = a gap "
        f"over {report['lock_lost_ns'] / 1e6:g} ms; stack resyncs "
        f"{report['resyncs']}",
        "slow-down: " + (
            f"{slow['factor']:.2f}x ({slow['ops_unprofiled']:g} ops "
            f"unprofiled on this seed, {slow['ops_profiled']:g} profiled)"
            if slow else
            "not measured (no unprofiled run of this seed beside it)"
        ) + "; a profiled run is never a measurement of speed",
    ]
    for role, r in report["roles"].items():
        lines += [
            "",
            f"role {role} ({r['threads']} threads, {r['functions_seen']} "
            f"functions, {r['intervals']:.0f} intervals{each}): self Python "
            f"{r['self_ms']:.3f} {unit} (raw {r['raw_self_ms']:.3f}; "
            f"{r['self_core_pct']:.1f} % of a core), native "
            f"{r['native_ms']:.3f}, lock lost {r['lost_ms']:.3f} in "
            f"{r['lost_n']:.3g} gaps{each}",
            f"  {'self ' + unit:>12} {'calls' + each:>10} {'us/call':>9} "
            f"{'incl ' + unit:>12} {'incl us/call':>12} {'native':>9} "
            f"{'lost':>8}  function",
        ]
        for f in r["functions"][:top]:
            lines.append(
                f"  {f['self_ms']:12.4f} {f['calls']:10.3f} "
                f"{f['us_per_call']:9.2f} {f['cum_ms']:12.4f} "
                f"{f['cum_us_per_call']:12.2f} {f['native_ms']:9.3f} "
                f"{f['lost_ms']:8.3f}  {f['name']} "
                f"({_short(f['file'])}:{f['line']})"
            )
        lines.append(f"  native, top callees: {'ms' + each:>10} "
                     f"{'calls' + each:>10} {'us/call':>10}  callee")
        for n in r["native"][:top]:
            lines.append(
                f"  {'':20} {n['ms']:10.3f} {n['calls']:10.3f} "
                f"{n['us_per_call']:10.2f}  {n['callee']}"
            )
        if r["lock_lost"]:
            lines.append(f"  lock lost: {'ms' + each:>10} {'gaps' + each:>10}"
                         "  function, and where its next event was")
        for f in r["lock_lost"][:top and _LOST_ROWS]:
            at = "; ".join(
                f"{a['before']} {a['ms']:.3f}" for a in f["at"]
            )
            lines.append(
                f"  {'':10} {f['ms']:10.3f} {f['n']:10.3f}  {f['name']} "
                f"({_short(f['file'])}:{f['line']}) [{at}]"
            )
    return "\n".join(lines)


# ------------------------------------------------------ the one profile
_lock = threading.Lock()
_current: "Profile | None" = None


def start(lock_lost_ns: int = LOCK_LOST_NS) -> Profile:
    """Start profiling every thread of the process. A second ``start``
    while one is active is an error, not a nest."""
    global _current
    with _lock:
        if _current is not None and _current.active:
            raise RuntimeError("pyprof is already started")
        prof = Profile(lock_lost_ns)
        prof._start()
        _current = prof
        return prof


def stop() -> Profile:
    """Stop: no event is set and the tool id is free again. The profile
    stays for ``dump``."""
    with _lock:
        if _current is None or not _current.active:
            raise RuntimeError("pyprof is not started")
        _current._stop()
        return _current


def dump(top: int = 15, ops: "float | None" = None, text: bool = False):
    """The running profile's report so far, or the last one's; with
    nothing started it says so."""
    prof = _current
    if prof is None:
        return {"pyprof": 1, "active": False, "note": "pyprof was never "
                "started in this process: `pyprof start` first"}
    report = prof.report(ops=ops, top=top)
    return format_report(report) if text else report


# the admin socket's three commands (arguments arrive as text)
def _state(prof: Profile) -> dict:
    return {
        "active": prof.active, "lock_lost_ns": prof.lock_lost_ns,
        "event_cost_ns": prof.event_cost_ns,
        "call_cost_ns": prof.call_cost_ns,
        "native_cost_ns": prof.native_cost_ns,
    }


def admin_start(lock_lost_ms=1.0) -> dict:
    return _state(start(int(float(lock_lost_ms) * 1e6)))


def admin_stop() -> dict:
    return _state(stop())


def admin_dump(top=15, ops=None, text=False):
    return dump(
        int(top), float(ops) if ops else None,
        str(text).lower() in ("1", "true", "yes"),
    )


def write(report: dict, json_path: str, text_path: "str | None" = None,
          top: "int | None" = None) -> None:
    with open(json_path, "w") as f:
        json.dump(report, f, indent=1)
    if text_path:
        with open(text_path, "w") as f:
            f.write(format_report(report, top) + "\n")


__all__ = [
    "LOCK_LOST_NS", "Profile", "callee_name", "dump", "format_report",
    "is_quiet", "start", "stop", "write",
]
