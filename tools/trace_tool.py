#!/usr/bin/env python
"""Cross-daemon trace assembly CLI — merge ``dump_historic_ops`` +
``dump_ops_in_flight`` into per-trace span trees, critical paths, a
top-N-slowest report, and Chrome trace-event JSON (Perfetto /
chrome://tracing).

Inputs, merged together:

- ``--spans FILE`` (repeatable): a JSON file holding a list of span
  dicts — exactly what ``admin_socket execute("dump_historic_ops")``
  returns.  Other processes dump the same format through their own
  admin sockets; feed one file per process and the wire-carried
  trace/parent ids stitch the trees across processes.
- ``--ops FILE`` (repeatable): a ``dump_ops_in_flight`` dump (the
  ``{"num_ops": N, "ops": [...]}`` shape or a bare list); live ops
  join their traces as open-ended spans.
- ``--live-demo``: boot a small LoadCluster in-process, run a few
  client ops (client → primary → sub-write fan-out), and assemble the
  run's traces — the zero-to-trace smoke.

- ``--xplane DIR``: a ``jax.profiler`` trace taken while the spans
  were recorded.  The tracer leaves a ``clock_anchor`` annotation in
  it about once a second (``utils/trace.py``); from those the spans —
  recorded intervals and spans that were open when the trace started
  too — are shifted onto the profiler's clock, where the device's ops
  live.  With ``--live-demo`` the demo records the trace into DIR
  itself (``--demo-*`` size it; the defaults are tiny).

Outputs:

- the text report on stdout (``--top N`` slowest traces, default 10);
- ``--chrome OUT.json``: Chrome trace-event JSON for the selected
  traces; with ``--xplane`` on the profiler's clock, with one more lane
  per device holding its ops;
- with ``--xplane``: the device's idle seconds by the innermost stage
  span open at the time (``STAGE_ORDER``), and for the busiest device
  ops the stage each call started in;
- with ``--live-demo``: the host's side of the run from the perf
  counters (``host_report``): the interpreter lock's hand-overs as the
  native frame calls kept them, the messengers' wall split into call /
  lock / Python, and the process's CPU by thread role.

The assembly core lives in ``ceph_tpu/utils/trace_assembly.py`` —
loadgen's ``--trace-capture`` and the soak forensics bundle use the
same functions in-process.
"""

from __future__ import annotations

import argparse
import json
import sys


#: the program's spans, innermost first: an idle stretch of the device
#: goes to the first of these that was open (``PERF.md`` section 3
#: names each). Finer than ``benchmark/trace/spans.json``, which the
#: ledger's ``breakdown`` keeps to its six coarse names.
STAGE_ORDER = [
    "codec.fetch", "codec.launch", "codec.h2d", "codec.prep",
    "ring.deliver", "ring.fire",
    "ec_write.fanout", "ec_write.txn_build", "ec_write.delta_place",
    "ec_write.delta_prepare", "ring_wait", "ec_write.encode",
    "ec_write.delta_apply",
    "ec_write.assemble", "ec_write.plan", "sub_write", "sub_read",
    "ec_reconstruct", "ec_read.finish", "ec_read.issue", "ec_truncate",
    "ec_write", "subop_wait", "sub_read_wait", "rmw_read_wait", "osd_op",
    "opq_wait", "client_op",
]


def clock_offset(xplane_path: str) -> "tuple[float, int]":
    """Seconds to add to a ``Span.start_mono`` to land on the profiler
    trace's clock, and the number of anchors it is the median of."""
    import statistics

    from jax.profiler import ProfileData

    from ceph_tpu.utils.trace import ANCHOR_NAME

    offsets = []
    for plane in ProfileData.from_file(xplane_path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name != ANCHOR_NAME:
                    continue
                mono_ns = dict(e.stats).get("mono_ns")
                if mono_ns is not None:
                    offsets.append((e.start_ns - int(mono_ns)) * 1e-9)
    if not offsets:
        raise SystemExit(
            f"{xplane_path}: no {ANCHOR_NAME!r} annotation; was the "
            "program's tracer on while the profiler ran?"
        )
    return statistics.median(offsets), len(offsets)


def shift_spans(spans: list[dict], offset: float) -> list[dict]:
    """The spans that carry a monotonic start, moved onto the profiler
    trace's clock (``start`` and ``start_mono`` both)."""
    out = []
    for s in spans:
        if s.get("start_mono") is None:
            continue
        at = s["start_mono"] + offset
        out.append({**s, "start": at, "start_mono": at})
    return out


def device_report(trace, spans: list[dict]) -> str:
    """Idle seconds of the device by stage, and where the busiest
    device ops started. ``trace``: ``benchmark.trace.xplane.Trace``;
    ``spans``: shifted onto its clock."""
    import dataclasses

    from benchmark.trace import xplane

    host = [
        (s["name"], s["start"], s["start"] + (s.get("duration") or 0.0))
        for s in spans
    ]
    on_clock = dataclasses.replace(trace, host=host)
    lo, hi = xplane.span_bounds(on_clock)
    gaps = xplane.idle_gaps(on_clock, lo, hi)
    busy = sum(xplane.busy_seconds(on_clock).values()) / max(
        len(on_clock.devices), 1
    )
    out = [
        f"device: {hi - lo:.3f} s of trace, busy {busy:.6f} s a chip, "
        f"idle {sum(e - s for s, e in gaps):.3f} s, by innermost stage:"
    ]
    for name, seconds in xplane.attribute_gaps(
        on_clock, gaps, STAGE_ORDER, limit=len(STAGE_ORDER) + 1
    ):
        out.append(f"  {seconds:10.4f} s  {name}")
    open_by_name = {
        n: xplane.union([(s, e) for m, s, e in host if m == n])
        for n in STAGE_ORDER
    }

    def stage_at(t: float) -> str:
        for n in STAGE_ORDER:
            if any(s <= t < e for s, e in open_by_name[n]):
                return n
        return "no span"

    out.append("device ops, by the stage open when each call started:")
    for short, seconds in xplane.top_ops(on_clock, limit=5):
        where: dict[str, int] = {}
        for events in on_clock.devices.values():
            for name, start, _end in events:
                if name.split(" = ", 1)[0].strip() == short:
                    key = stage_at(start)
                    where[key] = where.get(key, 0) + 1
        out.append(
            f"  {short}  {seconds * 1e3:.3f} ms: " + ", ".join(
                f"{n} in {k}" for k, n in sorted(
                    where.items(), key=lambda kv: -kv[1]
                )
            )
        )
    return "\n".join(out)


def device_lanes(trace) -> list[dict]:
    """Chrome events of the device's ops, one lane (pid 2) a chip."""
    events = []
    for tid, (plane, ops) in enumerate(sorted(trace.devices.items()), 1):
        events.append({
            "name": "thread_name", "ph": "M", "pid": 2, "tid": tid,
            "args": {"name": plane},
        })
        for name, start, end in ops:
            events.append({
                "name": name.split(" = ", 1)[0].strip(), "cat": "device",
                "ph": "X", "ts": start * 1e6, "dur": (end - start) * 1e6,
                "pid": 2, "tid": tid,
            })
    return events


def host_report(before: dict, after: dict) -> str:
    """Who waited for the interpreter lock between two
    ``benchmark.counters.snapshot`` and who was on the CPU meanwhile: the four ``*.net`` lock counters
    over every messenger, ``send_seconds`` + ``recv_seconds`` split
    into the native call, the wait to hold the lock again and the rest
    (Python), and ``process.threads`` as shares of the CPU used."""
    from benchmark import counters

    moved = counters.delta(before, after)

    def net(key: str) -> float:
        return counters.total(moved, [f"*.net:{key}"])

    waits, slow = net("lock_waits"), net("lock_waits_slow")
    waited, in_call = net("lock_wait_seconds"), net("call_seconds")
    wall = net("send_seconds") + net("recv_seconds")
    lines = [
        "interpreter lock, as the native frame calls kept it "
        "(*.net, every messenger):",
        f"  lock_waits {waits:.0f} of io_calls {net('io_calls'):.0f}  "
        f"lock_waits_slow {slow:.0f}  "
        f"lock_wait_seconds {waited:.6f}  call_seconds {in_call:.6f}",
    ]
    if waits:
        lines.append(
            f"  mean wait {1e6 * waited / waits:.1f} us, "
            f"{100 * slow / waits:.1f} % of a switch interval or more"
        )
    if wall > 0:
        lines.append(
            f"messenger wall {wall:.4f} s (send_seconds + recv_seconds): "
            f"in the call {100 * in_call / wall:.1f} %, waiting for the "
            f"lock {100 * waited / wall:.1f} %, Python "
            f"{100 * (wall - in_call - waited) / wall:.1f} %"
        )
    prefix = "process.threads:"
    roles = {
        k[len(prefix):-len("_cpu_seconds")]: v
        for k, v in moved.items() if k.startswith(prefix)
    }
    # a thread that ended inside the window takes its earlier seconds
    # out of its role with it: nearly all of them are tick threads
    # (coalescer groups, peering), so the two are read as one
    roles["tick"] = roles.get("tick", 0.0) + roles.pop("unlisted", 0.0)
    cpu = sum(roles.values())
    if cpu > 0:
        lines.append(
            f"CPU by thread role (process.threads; tick with the "
            f"threads that ended), {cpu:.3f} s: "
            + "  ".join(
                f"{role} {100 * v / cpu:.1f} %" for role, v in roles.items()
            )
        )
    return "\n".join(lines)


def collect_process() -> tuple[list[dict], list[dict]]:
    """This process's spans + live ops (the in-process cluster case:
    every daemon of a LoadCluster shares the global tracer/tracker)."""
    from ceph_tpu.utils.optracker import op_tracker
    from ceph_tpu.utils.trace import tracer

    return tracer.dump_historic(), op_tracker.dump_ops_in_flight()["ops"]


def _load_spans(path: str) -> list[dict]:
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    if isinstance(data, dict):
        data = data.get("spans", data.get("traceEvents", []))
    return list(data)


def _load_ops(path: str) -> list[dict]:
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    if isinstance(data, dict):
        data = data.get("ops", [])
    return list(data)


def _live_demo(args) -> tuple[list[dict], list[dict], str]:
    """Boot a LoadCluster, drive a handful of ops, return the spans
    and the run's ``host_report``. With ``--xplane`` the ops run under
    a profiler trace written there (after one untraced op has compiled
    what they use)."""
    import numpy as np

    from benchmark import counters
    from ceph_tpu.loadgen import LoadCluster
    from ceph_tpu.utils.trace import tracer

    cluster = LoadCluster(
        n_osds=args.demo_osds, k=args.demo_k, m=args.demo_m, pg_num=4,
        chunk_size=args.demo_chunk,
        client_op_timeout=60.0,
    )
    try:
        rng = np.random.default_rng(7)

        def one(name: str) -> None:
            data = rng.integers(
                0, 256, args.demo_object_bytes, np.uint8
            ).tobytes()
            cluster.io.write_full(name, data)
            assert cluster.io.read(name) == data

        if args.xplane:
            import jax

            one("demo-warm")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(args.xplane, profiler_options=options)
        tracer.clear()
        before = counters.snapshot()
        try:
            for i in range(args.demo_ops):
                one(f"demo-{i}")
        finally:
            if args.xplane:
                jax.profiler.stop_trace()
        host = host_report(before, counters.snapshot())
        spans, ops = collect_process()
    finally:
        cluster.shutdown()
    return spans, ops, host


def main(argv: "list[str] | None" = None) -> int:
    from ceph_tpu.utils.trace_assembly import (
        assemble_traces,
        chrome_trace,
        format_report,
    )

    p = argparse.ArgumentParser(
        prog="trace_tool", description=__doc__.splitlines()[0],
    )
    p.add_argument("--spans", action="append", default=[],
                   help="dump_historic_ops JSON file (repeatable)")
    p.add_argument("--ops", action="append", default=[],
                   help="dump_ops_in_flight JSON file (repeatable)")
    p.add_argument("--live-demo", action="store_true",
                   help="boot a small LoadCluster and trace it")
    p.add_argument("--top", type=int, default=10,
                   help="slowest traces to report (default 10)")
    p.add_argument("--chrome", default=None, metavar="OUT.json",
                   help="write Chrome trace-event JSON here")
    p.add_argument("--xplane", default=None, metavar="DIR",
                   help="profiler trace taken with the spans: put both "
                        "on one clock, add the device's lanes and its "
                        "idle time by stage")
    p.add_argument("--demo-k", type=int, default=2)
    p.add_argument("--demo-m", type=int, default=1)
    p.add_argument("--demo-osds", type=int, default=5)
    p.add_argument("--demo-chunk", type=int, default=1024)
    p.add_argument("--demo-object-bytes", type=int, default=4096)
    p.add_argument("--demo-ops", type=int, default=4)
    p.add_argument("--all", action="store_true",
                   help="include incomplete (multi-root/orphaned) "
                        "traces in the report")
    args = p.parse_args(argv)

    from ceph_tpu.utils import enable_compile_cache

    enable_compile_cache()
    spans: list[dict] = []
    ops: list[dict] = []
    for path in args.spans:
        spans.extend(_load_spans(path))
    for path in args.ops:
        ops.extend(_load_ops(path))
    host = None
    if args.live_demo:
        s, o, host = _live_demo(args)
        spans.extend(s)
        ops.extend(o)
    if not spans and not ops:
        spans, ops = collect_process()
    device = None
    if args.xplane:
        from benchmark.trace import xplane

        path = xplane.find_xplane(args.xplane)
        offset, n_anchors = clock_offset(path)
        spans = shift_spans(spans, offset)
        ops = []  # live ops carry wall-clock starts only
        device = xplane.load(path, set())
        print(f"clock: spans + {offset:.6f} s = the trace's clock "
              f"(median of {n_anchors} anchors)")

    trees = assemble_traces(spans, ops)
    if not args.all:
        complete = [t for t in trees if t["complete"]]
        if complete:
            trees = complete
    print(format_report(trees, top=args.top))
    if device is not None:
        print(device_report(device, spans))
    if host is not None:
        print(host)
    if args.chrome:
        chrome = chrome_trace(trees[: args.top])
        if device is not None:
            chrome["traceEvents"].extend(device_lanes(device))
        with open(args.chrome, "w", encoding="utf-8") as f:
            json.dump(chrome, f)
        print(f"chrome trace: {args.chrome}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
