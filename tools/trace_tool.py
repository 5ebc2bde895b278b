#!/usr/bin/env python
"""Cross-daemon trace assembly CLI — merge ``dump_historic_ops`` +
``dump_ops_in_flight`` into per-trace span trees, critical paths, a
top-N-slowest report, and Chrome trace-event JSON (Perfetto /
chrome://tracing).

Inputs, merged together:

- ``--spans FILE`` (repeatable): a JSON file holding a list of span
  dicts — exactly what ``admin_socket execute("dump_historic_ops")``
  returns.  DCN host processes dump the same format through their own
  admin sockets; feed one file per process and the wire-carried
  trace/parent ids stitch the trees across processes.
- ``--ops FILE`` (repeatable): a ``dump_ops_in_flight`` dump (the
  ``{"num_ops": N, "ops": [...]}`` shape or a bare list); live ops
  join their traces as open-ended spans.
- ``--live-demo``: boot a small LoadCluster in-process, run a few
  client ops (client → primary → sub-write fan-out), and assemble the
  run's traces — the zero-to-trace smoke.

Outputs:

- the text report on stdout (``--top N`` slowest traces, default 10);
- ``--chrome OUT.json``: Chrome trace-event JSON for the selected
  traces.

The assembly core lives in ``ceph_tpu/utils/trace_assembly.py`` —
loadgen's ``--trace-capture`` and the soak forensics bundle use the
same functions in-process.
"""

from __future__ import annotations

import argparse
import json
import sys


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


def _live_demo() -> tuple[list[dict], list[dict]]:
    """Boot a LoadCluster, drive a handful of ops, return the spans."""
    import numpy as np

    from ceph_tpu.loadgen import LoadCluster
    from ceph_tpu.utils.trace import tracer

    cluster = LoadCluster(
        n_osds=5, k=2, m=1, pg_num=4, chunk_size=1024
    )
    try:
        tracer.clear()
        rng = np.random.default_rng(7)
        for i in range(4):
            data = rng.integers(0, 256, 4096, np.uint8).tobytes()
            cluster.io.write(f"demo-{i}", data)
            cluster.io.read(f"demo-{i}")
        spans, ops = collect_process()
    finally:
        cluster.shutdown()
    return spans, ops


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
    if args.live_demo:
        s, o = _live_demo()
        spans.extend(s)
        ops.extend(o)
    if not spans and not ops:
        spans, ops = collect_process()

    trees = assemble_traces(spans, ops)
    if not args.all:
        complete = [t for t in trees if t["complete"]]
        if complete:
            trees = complete
    print(format_report(trees, top=args.top))
    if args.chrome:
        with open(args.chrome, "w", encoding="utf-8") as f:
            json.dump(chrome_trace(trees[: args.top]), f)
        print(f"chrome trace: {args.chrome}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
