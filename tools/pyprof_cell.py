#!/usr/bin/env python
"""One cell of the benchmark under ``ceph_tpu/utils/pyprof.py``: ms of
Python an op with the interpreter lock held, by function and by thread
role.

    python3 -m tools.pyprof_cell --workload <cell> --seed S --seconds T \\
        [--rehearse] [--unprofiled] [--out DIR] [--top N]

It imports ``benchmark.run`` as it is and, in this process only, wraps
``benchmark.run.measure`` (the window): the profiler starts as the
window opens and stops as it closes, so preload, warm-up, drain and
the check are not profiled, and the ops are the window's
``loadgen_client:op_completed`` delta. No file under ``benchmark/`` is
edited or copied. It writes ``<out>/pyprof.<cell>.json`` and
``<out>/pyprof.<cell>.txt`` (the table, printed too) and ends with the
run's ordinary result line, so ``correct`` is seen.

**A profiled run is never a measurement of speed.** Every Python call
costs the profiled program a microsecond or two more, so the window
completes fewer ops; what the table gives is the Python an op runs,
with the profiler's own time taken off (``pyprof``'s calibration), not
how fast anything is. The slow-down is printed with the table: run the
same seed first with ``--unprofiled`` (the same wrapper, the profiler
never started; it writes ``<out>/pyprof.<cell>.unprofiled.json``) and
the profiled run beside it reads that file. On the chip, through the
chip tool, one call a cell:

    python3 -m tools.pyprof_cell --workload rs84-4m.write --seed 7 \\
        --seconds 30 --unprofiled --out chiprun_out/pr47 && \\
    python3 -m tools.pyprof_cell --workload rs84-4m.write --seed 7 \\
        --seconds 30 --out chiprun_out/pr47

Beside the profile the report carries the window's ``process.threads``
CPU an op by role (``cpu_ms``): a role's self Python has to come out
under it, since native time holds the waits.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

OPS_KEY = "loadgen_client:op_completed"
JSON_ROWS = 100


def parse(argv):
    ap = argparse.ArgumentParser(prog="tools.pyprof_cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the cell's tiny sizes")
    ap.add_argument("--unprofiled", action="store_true",
                    help="the same run with the profiler never started: "
                         "the ops the slow-down is taken against")
    ap.add_argument("--out", default=os.path.join("chiprun_out", "pyprof"))
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--lock-lost-ms", type=float, default=1.0)
    return ap.parse_args(argv)


def role_cpu_ms(moved: dict, ops: float) -> dict[str, float]:
    """The window's ``process.threads`` CPU by role, ms an op."""
    tail = "_cpu_seconds"
    return {
        key.split(":", 1)[1][:-len(tail)]: value * 1e3 / max(ops, 1)
        for key, value in moved.items()
        if key.startswith("process.threads:") and key.endswith(tail)
    }


def main(argv=None) -> int:
    args = parse(argv if argv is not None else sys.argv[1:])
    os.makedirs(args.out, exist_ok=True)
    base = os.path.join(args.out, f"pyprof.{args.workload}")
    twin = base + ".unprofiled.json"

    from benchmark import run
    from ceph_tpu.utils import pyprof

    window = run.measure

    def measure(gen, seconds, trace_dir):
        if not args.unprofiled:
            pyprof.start(int(args.lock_lost_ms * 1e6))
        try:
            t0, t1, moved = window(gen, seconds, trace_dir)
        finally:
            if not args.unprofiled:
                prof = pyprof.stop()
        ops = moved.get(OPS_KEY, 0)
        sides = {
            "workload": args.workload, "seed": args.seed,
            "seconds": args.seconds, "rehearsal": args.rehearse,
            "ops": ops, "window_s": t1 - t0,
            "cpu_ms": role_cpu_ms(moved, ops),
        }
        if args.unprofiled:
            with open(twin, "w") as f:
                json.dump(sides, f, indent=1)
            run.say(f"pyprof: unprofiled twin, {ops:g} ops in "
                    f"{t1 - t0:.2f} s -> {twin}")
            return t0, t1, moved
        off = {}
        if os.path.exists(twin):
            with open(twin) as f:
                off = json.load(f)
            if any(off.get(k) != sides[k]
                   for k in ("seed", "seconds", "rehearsal")):
                off = {}
        # the file keeps more rows than the table shows: whoever sizes a
        # function that is not among a role's first finds it there
        report = prof.report(
            ops=ops, top=max(args.top, JSON_ROWS), unprofiled_ops=off.get("ops"),
            unprofiled_window_s=off.get("window_s"),
        )
        # the report's window is the profiler's; the ops are counted
        # over the generator's, which lies inside it
        report.update(sides, unprofiled_cpu_ms=off.get("cpu_ms"))
        pyprof.write(report, base + ".json", base + ".txt", args.top)
        print(pyprof.format_report(report, args.top), flush=True)
        cpu = ", ".join(
            f"{role} {ms:.3f}" for role, ms in sorted(
                sides["cpu_ms"].items(), key=lambda kv: -kv[1]
            )
        )
        run.say(f"pyprof: process.threads CPU ms/op in this window: {cpu}")
        run.say(f"pyprof: wrote {base}.json and {base}.txt")
        return t0, t1, moved

    run.measure = measure
    forwarded = [
        "--workload", args.workload, "--seed", str(args.seed),
        "--seconds", str(args.seconds), "--trace", "0",
    ] + (["--rehearse"] if args.rehearse else [])
    return run.main(forwarded)  # ends the process itself


if __name__ == "__main__":
    main()
