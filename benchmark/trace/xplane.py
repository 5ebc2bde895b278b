"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: per-device intervals in which an operation ran, the
device events by name, and the program's host spans on the same clock.

Read with ``jax.profiler.ProfileData`` and nothing else. On a TPU the
device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed HLO op (the name is the op's HLO text) and the
``XLA Modules`` line one per program run. The program's spans
(``jax.profiler.TraceAnnotation`` under ``utils/trace.py``) are events
on the thread lines of ``/host:CPU``."""

from __future__ import annotations

import dataclasses
import glob
import os

DEVICE_PREFIXES = ("/device:TPU:", "/device:GPU:")
OPS_LINES = ("XLA Ops", "XLA Modules")
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass
class Trace:
    #: device plane name -> [(name, start_s, end_s)] of executed ops
    devices: dict[str, list[tuple[str, float, float]]]
    #: [(name, start_s, end_s)] of every named host event
    host: list[tuple[str, float, float]]
    #: device plane name -> [(name, start_s, end_s)] of program runs
    #: (one per executed XLA module, named ``jit_<function>(<id>)``)
    modules: dict[str, list[tuple[str, float, float]]] = dataclasses.field(
        default_factory=dict
    )


def find_xplane(trace_dir: str) -> str:
    paths = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, host_names: set[str] | None = None) -> Trace:
    """``host_names``: keep only these host events (the program's span
    names); None keeps every host event."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list[tuple[str, float, float]]] = {}
    modules: dict[str, list[tuple[str, float, float]]] = {}
    host: list[tuple[str, float, float]] = []

    def events(line) -> list[tuple[str, float, float]]:
        return [] if line is None else [
            (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events
        ]

    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIXES):
            lines = {line.name: line for line in plane.lines}
            devices[plane.name] = events(
                next((lines[n] for n in OPS_LINES if n in lines), None)
            )
            modules[plane.name] = events(lines.get(MODULES_LINE))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if host_names is None or e.name in host_names:
                        host.append((
                            e.name, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9,
                        ))
    return Trace(devices, host, modules)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge overlapping ``(start, end)`` intervals."""
    merged: list[tuple[float, float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def busy_seconds(trace: Trace) -> dict[str, float]:
    """Seconds in which an operation ran, per device: the union of its
    op intervals, so ops that overlap count once."""
    return {
        name: sum(e - s for s, e in union([(s, e) for _n, s, e in events]))
        for name, events in trace.devices.items()
    }


def kernel_events(
    trace: Trace, match: str, line: str = "ops"
) -> list[tuple[str, float]]:
    """``(name, seconds)`` of every device event, on any device, whose
    name contains ``match``: executed ops, or with ``line="modules"``
    whole program runs."""
    planes = trace.modules if line == "modules" else trace.devices
    return [
        (name, end - start)
        for events in planes.values()
        for name, start, end in events
        if match in name
    ]


def top_ops(trace: Trace, limit: int = 10) -> list[list]:
    """The device operations that took most time, summed by the op's
    short name (the HLO text up to its first ``=``)."""
    totals: dict[str, float] = {}
    for events in trace.devices.values():
        for name, start, end in events:
            short = name.split(" = ", 1)[0].strip()
            totals[short] = totals.get(short, 0.0) + (end - start)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, seconds] for name, seconds in ranked]


def idle_gaps(
    trace: Trace, t0: float, t1: float
) -> list[tuple[float, float]]:
    """The intervals of ``[t0, t1]`` in which no device ran anything."""
    busy = union([
        (s, e) for events in trace.devices.values() for _n, s, e in events
    ])
    gaps, at = [], t0
    for start, end in busy:
        if start > at:
            gaps.append((at, min(start, t1)))
        at = max(at, end)
        if at >= t1:
            break
    if at < t1:
        gaps.append((at, t1))
    return [(s, e) for s, e in gaps if e > s]


def attribute_gaps(
    trace: Trace, gaps: list[tuple[float, float]], order: list[str],
    limit: int = 10,
) -> list[list]:
    """Idle seconds by what the host was doing: each gap goes, piece by
    piece, to the first span name of ``order`` (innermost first) that
    was open then; a stretch with no span open is ``unattributed``."""
    open_by_name = {
        name: union([(s, e) for n, s, e in trace.host if n == name])
        for name in order
    }
    totals: dict[str, float] = {}
    for gap in gaps:
        left = [gap]
        for name in order:
            taken = 0.0
            rest: list[tuple[float, float]] = []
            for s, e in left:
                at = s
                for span_s, span_e in open_by_name[name]:
                    if span_e <= at or span_s >= e:
                        continue
                    lo, hi = max(at, span_s), min(e, span_e)
                    if lo > at:
                        rest.append((at, lo))
                    taken += hi - lo
                    at = hi
                if at < e:
                    rest.append((at, e))
            if taken:
                totals[name] = totals.get(name, 0.0) + taken
            left = rest
        free = sum(e - s for s, e in left)
        if free:
            totals["unattributed"] = totals.get("unattributed", 0.0) + free
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, seconds] for name, seconds in ranked]


def span_bounds(trace: Trace) -> tuple[float, float]:
    """First start and last end over every event kept."""
    starts = [s for ev in trace.devices.values() for _n, s, _e in ev]
    ends = [e for ev in trace.devices.values() for _n, _s, e in ev]
    starts += [s for _n, s, _e in trace.host]
    ends += [e for _n, _s, e in trace.host]
    return (min(starts), max(ends)) if starts else (0.0, 0.0)
