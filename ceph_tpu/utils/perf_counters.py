"""Typed performance counters — the ``PerfCounters`` analog.

Mirrors common/perf_counters.{h,cc}: a builder declares typed metrics
(u64 counters, gauges, time totals, averages with count+sum, histogram
buckets), instances update them cheaply at runtime, and a process
collection serves ``perf dump``-style JSON through the admin socket
(common/admin_socket.cc) — the same schema shape the reference's
``ceph daemon ... perf dump`` emits: averages as {avgcount, sum},
histograms as bucket arrays.

Thread-safe via one lock per counter set (the reference uses atomics;
Python increments are cheap enough under a lock here).
"""

from __future__ import annotations

import bisect
import enum
import fnmatch
import functools
import os
import re
import threading
import time


class CounterType(enum.Enum):
    U64 = "u64"            # monotonically increasing counter
    GAUGE = "gauge"        # settable value
    TIME = "time"          # accumulated seconds
    AVG = "avg"            # count + sum (time or value averages)
    HISTOGRAM = "histogram"
    #: a monotone reading taken when the set is dumped (a process
    #: clock): the value is the schema's ``fn()``, nothing is stored.
    #: Keys declared together (``add_sampled_group``) share one reading
    #: a dump: the schema's ``group()`` answers all of them
    SAMPLED = "sampled"


class PerfCounters:
    """One subsystem's counter set; create via PerfCountersBuilder."""

    def __init__(self, name: str, schema: dict[str, dict]) -> None:
        self.name = name
        self._schema = schema
        self._lock = threading.Lock()
        self._values: dict[str, object] = {}
        #: histogram value totals (Prometheus histograms carry a
        #: ``_sum`` so rate(sum)/rate(count) gives a live mean)
        self._hist_sums: dict[str, float] = {}
        for key, spec in schema.items():
            if spec["type"] is CounterType.AVG:
                self._values[key] = [0, 0.0]  # avgcount, sum
            elif spec["type"] is CounterType.HISTOGRAM:
                self._values[key] = [0] * (len(spec["buckets"]) + 1)
                self._hist_sums[key] = 0.0
            elif spec["type"] is not CounterType.SAMPLED:
                self._values[key] = 0 if spec["type"] in (
                    CounterType.U64, CounterType.GAUGE
                ) else 0.0

    def _check(self, key: str, *types: CounterType) -> dict:
        spec = self._schema.get(key)
        if spec is None:
            raise KeyError(f"{self.name}: no counter {key!r}")
        if types and spec["type"] not in types:
            raise TypeError(
                f"{self.name}.{key} is {spec['type'].value}, not "
                f"{'/'.join(t.value for t in types)}"
            )
        return spec

    def inc(self, key: str, by: int = 1) -> None:
        self._check(key, CounterType.U64)
        with self._lock:
            self._values[key] += by

    def set(self, key: str, value) -> None:
        self._check(key, CounterType.GAUGE)
        with self._lock:
            self._values[key] = value

    def tinc(self, key: str, seconds: float) -> None:
        self._check(key, CounterType.TIME)
        with self._lock:
            self._values[key] += seconds

    def ainc(self, key: str, value: float) -> None:
        """Add one sample to an average (count += 1, sum += value)."""
        self._check(key, CounterType.AVG)
        with self._lock:
            pair = self._values[key]
            pair[0] += 1
            pair[1] += value

    def hinc(self, key: str, value: float) -> None:
        spec = self._check(key, CounterType.HISTOGRAM)
        with self._lock:
            self._values[key][bisect.bisect_right(spec["buckets"], value)] += 1
            self._hist_sums[key] += value

    def get(self, key: str):
        spec = self._check(key)
        if spec["type"] is CounterType.SAMPLED:
            return spec["fn"]()
        with self._lock:
            v = self._values[key]
            return list(v) if isinstance(v, list) else v

    def reset(self) -> None:
        """Zero every counter, gauge, time accumulator, average pair
        and histogram bucket (the ``perf reset`` admin command): bench
        A/B legs and soak iterations start from clean counters instead
        of differencing against a snapshot."""
        with self._lock:
            for key, spec in self._schema.items():
                if spec["type"] is CounterType.AVG:
                    self._values[key] = [0, 0.0]
                elif spec["type"] is CounterType.HISTOGRAM:
                    self._values[key] = [0] * (len(spec["buckets"]) + 1)
                    self._hist_sums[key] = 0.0
                elif spec["type"] in (CounterType.U64, CounterType.GAUGE):
                    self._values[key] = 0
                elif spec["type"] is CounterType.TIME:
                    self._values[key] = 0.0

    def dump(self) -> dict:
        out: dict[str, object] = {}
        sampled = []
        with self._lock:
            for key, spec in self._schema.items():
                if spec["type"] is CounterType.SAMPLED:
                    out[key] = None  # keeps the schema's order
                    sampled.append((key, spec))
                    continue
                v = self._values[key]
                if spec["type"] is CounterType.AVG:
                    out[key] = {"avgcount": v[0], "sum": v[1]}
                elif spec["type"] is CounterType.HISTOGRAM:
                    out[key] = {
                        "buckets": list(spec["buckets"]),
                        "counts": list(v),
                        "sum": self._hist_sums[key],
                    }
                else:
                    out[key] = v
        # readings are taken with no lock of ours held (one may walk a
        # messenger's links or the process's tasks, and an ``inc`` on
        # the hot path must not wait for that); a group is read once
        groups: dict = {}
        for key, spec in sampled:
            group = spec.get("group")
            if group is None:
                out[key] = spec["fn"]()
                continue
            if group not in groups:
                groups[group] = group()
            out[key] = groups[group][key]
        return out


class PerfCountersBuilder:
    """Declare a counter set, then ``create_perf_counters()``
    (PerfCountersBuilder, common/perf_counters.h)."""

    def __init__(self, collection: "PerfCountersCollection", name: str) -> None:
        self._collection = collection
        self._name = name
        self._schema: dict[str, dict] = {}

    def _add(self, key: str, type: CounterType, desc: str, **extra):
        if key in self._schema:
            raise ValueError(f"duplicate counter {key!r}")
        self._schema[key] = {"type": type, "desc": desc, **extra}
        return self

    def add_u64_counter(self, key: str, desc: str = ""):
        return self._add(key, CounterType.U64, desc)

    def add_u64_gauge(self, key: str, desc: str = ""):
        return self._add(key, CounterType.GAUGE, desc)

    def add_time(self, key: str, desc: str = ""):
        return self._add(key, CounterType.TIME, desc)

    def add_sampled(self, key: str, fn, desc: str = ""):
        """``fn() -> number``, called at every dump."""
        return self._add(key, CounterType.SAMPLED, desc, fn=fn)

    def add_sampled_group(self, group, keys: dict[str, str]):
        """``group() -> {key: number}`` for every key of ``keys`` (key
        -> description), called ONCE a dump however many keys it
        serves: one walk, one consistent reading."""
        for key, desc in keys.items():
            self._add(
                key, CounterType.SAMPLED, desc,
                fn=lambda key=key: group()[key], group=group,
            )
        return self

    def add_avg(self, key: str, desc: str = ""):
        return self._add(key, CounterType.AVG, desc)

    def add_histogram(self, key: str, buckets: list[float], desc: str = ""):
        if sorted(buckets) != list(buckets):
            raise ValueError("histogram buckets must be sorted")
        return self._add(key, CounterType.HISTOGRAM, desc, buckets=buckets)

    def create_perf_counters(self) -> PerfCounters:
        pc = PerfCounters(self._name, dict(self._schema))
        self._collection.register(pc)
        return pc


class PerfCountersCollection:
    """All counter sets in the process (PerfCountersCollectionImpl)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._sets: dict[str, PerfCounters] = {}

    def register(self, pc: PerfCounters) -> None:
        with self._lock:
            # Same-name re-registration replaces (a rebuilt pipeline
            # supersedes its predecessor's counters).
            self._sets[pc.name] = pc

    def deregister(self, name: str) -> None:
        with self._lock:
            self._sets.pop(name, None)

    def reset(self, name: str | None = None) -> int:
        """Zero one named set, or every registered set (``perf
        reset`` over the admin socket). Returns how many sets were
        reset; an unknown name raises KeyError like the other admin
        lookups."""
        with self._lock:
            if name is None:
                targets = list(self._sets.values())
            else:
                pc = self._sets.get(name)
                if pc is None:
                    raise KeyError(f"no counter set {name!r}")
                targets = [pc]
        for pc in targets:
            pc.reset()
        return len(targets)

    def dump(self) -> dict:
        # the sets are dumped outside the collection's lock: a sampled
        # reading can take a while, and a set built meanwhile (a first
        # use on a hot path) must not wait for it to register
        with self._lock:
            sets = sorted(self._sets.items())
        return {name: pc.dump() for name, pc in sets}

    def snapshot(self) -> dict[str, tuple[dict, dict]]:
        """name -> (schema, dumped values), sorted — the exporter
        surface (schema carries each counter's type and histogram
        bucket bounds)."""
        with self._lock:
            sets = sorted(self._sets.items())
        return {name: (dict(pc._schema), pc.dump()) for name, pc in sets}


# Process-global collection, served by the admin socket's "perf dump".
perf_collection = PerfCountersCollection()


def built_once(build):
    """A counter set built on its first use: ``build()`` runs once,
    under a lock, however many threads make that first use together.
    (``functools.lru_cache`` lets each of them build and register a
    set, keeps the first and leaves the collection the last: counters
    then move where no dump sees them.)"""
    lock = threading.Lock()
    made: list = []

    @functools.wraps(build)
    def get():
        if not made:
            with lock:
                if not made:
                    made.append(build())
        return made[0]

    return get


# -- the process's CPU by thread role ------------------------------------
#: the roles a task's CPU seconds are booked under. ``other_python`` is
#: a Python thread whose name no pattern lists; ``runtime`` is a task no
#: Python thread owns (XLA, libtpu, the profiler: CPU that never holds
#: the interpreter lock); ``unlisted`` is what the process used beyond
#: the tasks a scan saw (threads that ended before it).
THREAD_ROLES = (
    "op_worker", "msgr", "tick", "ec_stream", "client", "other_python",
    "runtime", "unlisted",
)
_role_lock = threading.Lock()
_role_patterns: list[tuple[str, "re.Pattern[str]", str]] = []


def register_thread_roles(roles: dict[str, str]) -> None:
    """Declare which role threads named like ``pattern`` (``fnmatch``)
    work in: ``{pattern: role}``, one call a module, beside the code
    that names the threads. Where several patterns match a name the
    longest wins (``msgr-client-*`` over ``msgr-*``)."""
    for role in roles.values():
        if role not in THREAD_ROLES[:-2]:
            raise ValueError(f"no thread role {role!r}")
    with _role_lock:
        known = {p for p, _, _ in _role_patterns}
        _role_patterns.extend(
            (p, re.compile(fnmatch.translate(p)), role)
            for p, role in roles.items() if p not in known
        )
        _role_patterns.sort(key=lambda e: -len(e[0]))
    thread_role.cache_clear()


@functools.lru_cache(maxsize=4096)
def thread_role(name: str) -> "str | None":
    """The role the registered patterns give a thread's name, or None
    where none lists it (its CPU is then booked as ``other_python``)."""
    with _role_lock:
        patterns = list(_role_patterns)
    return next((r for _, rx, r in patterns if rx.match(name)), None)


register_thread_roles({"MainThread": "other_python"})


def thread_cpu_seconds() -> dict[str, float]:
    """``{role + "_cpu_seconds": seconds}`` for every role: ONE native
    pass over ``/proc/self/task`` (``native.task_cpu``), each task put
    to the role of the Python thread that owns it
    (``threading.enumerate()``'s ``native_id -> name``). ``unlisted``
    is ``time.process_time()`` less the tasks seen, so the eight sum to
    the process's CPU exactly and no record of ended threads is kept.
    With no native tier nothing is seen and all of it is ``unlisted``."""
    from ceph_tpu import native

    tasks = native.task_cpu() if native.available() else {}
    total = time.process_time()
    names = {t.native_id: t.name for t in threading.enumerate()}
    out = dict.fromkeys(THREAD_ROLES, 0.0)
    for tid, seconds in tasks.items():
        name = names.get(tid)
        if name is None:
            out["runtime"] += seconds
        else:
            out[thread_role(name) or "other_python"] += seconds
    out["unlisted"] = total - sum(tasks.values())
    return {f"{role}_cpu_seconds": v for role, v in out.items()}


def register_process_counters(
    collection: PerfCountersCollection = perf_collection,
) -> PerfCounters:
    """The ``process`` set: CPU seconds of the whole interpreter
    (every thread, user + system) beside wall seconds on the tracer's
    clock. Their ratio over a window is how many cores the host side
    kept busy: near 1.0 in a one-interpreter cluster means every layer
    is queueing for the GIL. Beside it ``process.threads``: the same
    CPU seconds by thread role, read at dump time and nowhere else."""
    clock = (
        "schedstat, ns on the CPU"
        if os.path.exists(f"/proc/self/task/{os.getpid()}/schedstat")
        else "stat, utime + stime in clock ticks"
    )
    what = {
        "op_worker": "OSD op workers and shards",
        "msgr": "OSD messengers' readers, accepters and handshakes",
        "tick": "OSD ticks, coalescer groups, heartbeat, scrub, gc, "
                "peering, backfill",
        "ec_stream": "the codec dispatcher's drain thread",
        "client": "the load generator, the objecter and the client "
                  "messenger's readers",
        "other_python": "main and every Python thread no pattern lists",
        "runtime": "tasks no Python thread owns (XLA, libtpu, the "
                   "profiler: never holds the interpreter lock; and, "
                   "until the kernel drops its task, a thread that "
                   "has just ended)",
        "unlisted": "process:cpu_seconds less the tasks this scan saw: "
                    "threads that ended before it",
    }
    (
        PerfCountersBuilder(collection, "process.threads")
        .add_sampled_group(thread_cpu_seconds, {
            f"{role}_cpu_seconds":
                f"CPU seconds of {what[role]} (/proc/self/task/*/{clock})"
            for role in THREAD_ROLES
        })
        .create_perf_counters()
    )
    return (
        PerfCountersBuilder(collection, "process")
        .add_sampled(
            "cpu_seconds", time.process_time,
            "CPU seconds of this process, all threads",
        )
        .add_sampled(
            "wall_seconds", time.perf_counter,
            "wall seconds on the perf_counter clock",
        )
        .create_perf_counters()
    )


register_process_counters()
