"""One run of one cell of the benchmark.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that refuses anything but a TPU, boots the cell's
cluster, runs the cell's set-up, measures for ``--seconds``, checks the
guarantees against the plain reference and prints one JSON line last.
With ``--trace 0`` the metrics are the cell's end-to-end metrics, taken
on the host clock at the client's side from exact samples; with
``--trace 1`` they are its per-layer metrics, from a profiler trace and
the program's counters over a traced window of its own.

The run's clock is the first design rule (README.md): the generator
stops on a deadline, every wait has a deadline held in the cell's file,
and a whole-run alarm, started before JAX is imported, ends the run
with ``correct: false`` well before the driver's clock.

``--rehearse`` is for the CPU: tiny sizes from the cell's ``rehearse``
block, interpreted kernels, a last line that names the CPU and carries
no metric. It is never a measurement."""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python can tell

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

from . import clock, files  # noqa: E402
from .metrics.readers.latency_tail import nearest_rank  # noqa: E402

#: allowance for import, TPU initialisation and the native tier's
#: first build, which no cell deadline covers
STARTUP_ALLOWANCE_S = 45.0
#: until the cell's file is read
PROVISIONAL_ALARM_S = 300.0

UNKNOWN_DEVICE = {
    "platform": "unknown", "kind": "unknown", "count": 0,
    "memory_peak_bytes": 0,
}


def say(*parts) -> None:
    print(f"[{time.perf_counter() - _T0:7.2f}s]", *parts, flush=True)


def result_line(
    correct: bool, attempted: int, failed: int, metrics: dict,
    device: dict, **extra,
) -> str:
    return json.dumps({
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed), "metrics": metrics, "device": device,
        **extra,
    })


def alarm_seconds(cell: dict, seconds: float) -> float:
    """The whole-run alarm: the cell's deadlines, the window and the
    start-up allowance."""
    return (
        sum(cell["deadlines_s"].values()) + seconds + STARTUP_ALLOWANCE_S
    )


# ----------------------------------------------------------------- cluster
def boot(cell: dict, config: dict):
    from ceph_tpu.loadgen import LoadCluster

    pool, cluster = config["pool"], config["cluster"]
    mesh = int(cluster.get("mesh_devices") or 0)
    return LoadCluster(
        n_osds=cluster["n_osds"], k=pool["k"], m=pool["m"],
        pg_num=pool["pg_num"], chunk_size=pool["chunk_size"],
        plugin=pool["plugin"], technique=pool["technique"],
        d=pool.get("d"), use_mesh=bool(mesh), mesh_devices=mesh or None,
        client_op_timeout=cell["client"]["op_timeout_s"],
        client_max_attempts=cell["client"]["max_attempts"],
    )


def hold_scrubs(cluster) -> None:
    """``ceph osd set noscrub nodeep-scrub`` for a program that has no
    such flag: stamp every PG as scrubbed now, as a PG is at creation
    upstream. Left alone, a PG that was never scrubbed is due at once
    (``OSDDaemon._scrub_due``) and every window holds a deep scrub. No
    public knob does it (``osd_max_scrubs`` has a floor of 1, and a
    stamp of 0 is due whatever the intervals), so this writes the
    daemons' own table, and refuses to go on where that table is gone:
    a refactor of the program must not bring the scrubs back unseen."""
    now = time.monotonic()
    pg_num = cluster.mon.osdmap.pools[cluster.pool].pg_num
    for daemon in cluster.daemons.values():
        stamps = getattr(daemon, "_scrub_stamps", None)
        if not isinstance(stamps, dict) or not hasattr(daemon, "_scrub_due"):
            raise RuntimeError(
                "OSDDaemon keeps no _scrub_stamps table any more: the "
                "benchmark cannot hold scheduled scrubs (PERF.md, tracing list)"
            )
        for pgid in range(pg_num):
            stamps[(cluster.pool, pgid)] = [now, now]


def pgs_not_active(cluster, epoch: int) -> list[int]:
    """PGs that the stats plane (the monitor's PGMap, what ``ceph pg
    stat`` reads) does not yet show as serving in the map of the kill:
    no report at or after ``epoch``, still peering, or still bringing a
    live shard up to date after the interval change (log catch-up: it
    decodes and checksums in the background, which a window must not
    hold). ``LoadCluster.is_recovered_stats`` without its demand that
    nothing be down or degraded: the dead OSD's own shard has nowhere
    to go."""
    spec = cluster.mon.osdmap.pools[cluster.pool]
    waiting = []
    for pgid in range(spec.pg_num):
        stats = cluster.pgmap.get(spec.pool_id, pgid)
        if (
            stats is None or stats.reported_epoch < epoch
            or "active" not in stats.state or "recovering" in stats.state
        ):
            waiting.append(pgid)
    return waiting


def standing_fault(cluster, cell: dict) -> None:
    """The cell's failure, held for the whole run: kill during set-up,
    then wait, bounded, until every PG serves without the victim. No
    revive, no recovery wait."""
    fault = cell["standing_fault"]
    if not fault:
        return
    seconds = cell["deadlines_s"]["fault"]
    t0 = time.monotonic()
    victim = getattr(cluster, fault["kill"])()
    clock.call_with_deadline(
        "fault.kill", seconds, lambda: cluster.kill(victim),
        lambda: f"osd.{victim} did not stop",
    )
    epoch = cluster.mon.osdmap.epoch
    left = max(seconds - (time.monotonic() - t0), 0.1)
    took = clock.wait_until(
        "fault.pgs_active", left,
        lambda: not pgs_not_active(cluster, epoch),
        lambda: f"osd.{victim} down at epoch {epoch}, PGs not active: "
                f"{pgs_not_active(cluster, epoch)}",
    )
    say(f"fault: osd.{victim} down for the whole run; every PG active "
        f"{took:.2f} s after the kill")


# ------------------------------------------------------------------ phases
def preload(cluster, cell, config, seed, gen_cls):
    n = cell["preload_objects"]
    if not n:
        return None
    loader = gen_cls(
        cluster.io, files.mix("write"), config["object_size"],
        config["queue_depth"], seed, limit=n,
    )
    t0 = time.monotonic()
    loader.start()
    try:
        clock.wait_until(
            "preload", cell["deadlines_s"]["preload"],
            lambda: loader.completed() >= n,
            lambda: f"{loader.completed()} of {n} objects written, "
                    f"{loader.in_flight()} in flight",
        )
    finally:
        loader.close()
    bad = [s for s in loader.samples if not s.ok]
    if bad:
        raise RuntimeError(f"preload: {len(bad)} writes failed: {bad[0].why}")
    took = time.monotonic() - t0
    say(f"preload: {n} objects of {config['object_size']} B in {took:.2f} s")
    return loader


def warm_up(gen, log, cell) -> None:
    """Ops of the cell's own mix until ``min_ops`` are done, every
    counter the cell lists under ``warmup.moved`` has moved, and no
    compilation has ended for ``quiet_s``. ``moved`` is for a program
    that compiles on a batch the traffic sends only now and then (the
    overwrite cell's first device delta batch compiles every size of
    its set): without it a quiet stretch before that batch ends the
    warm-up and the compilations fall into the window."""
    from . import counters

    spec = cell["warmup"]
    wanted = spec.get("moved", [])
    before = counters.snapshot() if wanted else {}

    def still() -> list[str]:
        now = counters.snapshot() if wanted else {}
        return [c for c in wanted if now.get(c, 0) <= before.get(c, 0)]

    def warm() -> bool:
        quiet = time.perf_counter() - max(log.last(), gen_started)
        return (
            gen.completed() >= spec["min_ops"] and quiet >= spec["quiet_s"]
            and not still()
        )

    gen_started = time.perf_counter()
    took = clock.wait_until(
        "warmup", cell["deadlines_s"]["warmup"], warm,
        lambda: f"{gen.completed()} ops done (need {spec['min_ops']}), last "
                f"compilation {time.perf_counter() - log.last():.1f} s ago, "
                f"counters not moved: {still()}",
    )
    say(f"warm-up: {gen.completed()} ops in {took:.2f} s, "
        f"{len(log.events)} compilations so far "
        f"({log.total_seconds():.2f} s)")


def measure(gen, seconds: float, trace_dir: str | None):
    """The window: ``seconds`` of the generator's loop, or a traced
    window of the same loop. Returns (t0, t1, counters before/after)."""
    from . import counters

    if trace_dir:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1  # the program's spans, no runtime
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    before = counters.snapshot()
    t0 = gen.open_window()
    try:
        while True:
            left = t0 + seconds - time.perf_counter()
            if left <= 0:
                break
            time.sleep(min(left, 0.05))
    finally:
        t1 = gen.stop_issuing()
        after = counters.snapshot()
        if trace_dir:
            jax.profiler.stop_trace()
    return t0, t1, counters.delta(before, after)


def end_to_end(cell_name: str, gen, t0: float, t1: float) -> dict:
    """The cell's end-to-end metrics from the exact samples: ops whose
    verified completion landed inside the window, and their bytes, per
    second of it."""
    issued, completed = gen.window_samples()
    span = t1 - t0
    values = {
        "client_mbs": sum(s.nbytes for s in completed) / span / 1e6,
        "client_iops": len(completed) / span,
    }
    say(f"window: {span:.3f} s, issued {len(issued)}, completed in it "
        f"{len(completed)}")
    # the tails a user feels, from these untraced samples: printed, not
    # gated (PERF.md section 2 says why no bound holds them yet)
    by_class: dict[str, list[float]] = {}
    for s in issued:
        if s.ok:
            by_class.setdefault(s.cls, []).append(
                (s.t_done - s.t_submit) * 1e3
            )
    for cls, lat in sorted(by_class.items()):
        say(f"latency ms, {cls} (n={len(lat)}): " + " ".join(
            f"p{p} {nearest_rank(lat, p):.1f}" for p in (50, 90, 95)
        ))
    return {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in files.metrics_for(cell_name, "end_to_end")
        if m["name"] in values
    }


def per_layer(cell_name, cell, config, device, moved, compiles, trace,
              window_s, samples) -> dict:
    from . import metrics

    ctx = metrics.RunContext(
        cell=cell, config=config, device_kind=device["kind"], moved=moved,
        compiles=compiles, trace=trace, window_s=window_s, samples=samples,
    )
    out = {}
    for m in files.metrics_for(cell_name, "per_layer"):
        value = metrics.read(files.metric(m["name"]), ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    for note in ctx.notes:
        say("note:", note)
    return out


def memory_peak() -> int:
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


# -------------------------------------------------------------------- main
def parse(argv):
    ap = argparse.ArgumentParser(prog="benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the cell's tiny sizes; prints "
                         "no metric and is never a measurement")
    return ap.parse_args(argv)


def apply_rehearsal(cell: dict, config: dict, mix: dict) -> None:
    tiny = cell.get("rehearse", {})
    if "names" in mix:
        mix["names"] = tiny.get("names", mix["names"])
    config["object_size"] = tiny.get("object_size", config["object_size"])
    config["pool"]["pg_num"] = tiny.get("pg_num", config["pool"]["pg_num"])
    cell["preload_objects"] = min(
        cell["preload_objects"], tiny.get("preload_objects", 0)
    )
    cell["warmup"]["min_ops"] = tiny.get("warmup_min_ops", 8)
    # the CPU's routes are not the chip's: nothing to wait for
    cell["warmup"].pop("moved", None)
    cell["check_objects"] = tiny.get("check_objects", 4)


def run(args, alarm: clock.Alarm, state: dict) -> int:
    cell = files.cell(args.workload)
    config = files.config(cell["config"])
    mix = files.mix(cell["traffic"])
    files.reference(config)  # a pool with no reference ends here
    if args.rehearse:
        apply_rehearsal(cell, config, mix)
    budget = alarm_seconds(cell, args.seconds)
    alarm.arm(budget - (time.perf_counter() - _T0), state["alarm_line"])
    say(f"cell {args.workload} seed {args.seed} seconds {args.seconds:g} "
        f"trace {args.trace}; whole-run alarm at {budget:g} s")

    # -- the gate: a TPU with the chips the cell asks for, or nothing
    from ceph_tpu.utils import platform

    cache_dir = platform.enable_compile_cache()
    if args.rehearse:
        device = platform.device_identity()
        if device["platform"] == "tpu":
            raise RuntimeError("--rehearse is for the CPU, not for a chip")
        from ceph_tpu.utils.config import config as rehearsal_config

        # walk the fused route in the interpreter, as the tests do
        rehearsal_config.set("ec_fused_csum_interpret", True)
    else:
        device = platform.require_tpu()
    if device["count"] < cell["chips"]:
        raise RuntimeError(
            f"{args.workload} needs {cell['chips']} chips, JAX shows "
            f"{device['count']}"
        )
    device = {**device, "memory_peak_bytes": 0}
    state["device"] = device
    say(f"device {device['platform']} {device['kind']} x{device['count']}; "
        f"compile cache {cache_dir}")

    from . import check, compile_log
    from .traffic.generator import Generator

    log = compile_log.CompileLog()
    log.install()
    deadlines = cell["deadlines_s"]
    state["shutdown_s"] = deadlines["shutdown"]
    t = time.monotonic()
    cluster = clock.call_with_deadline(
        "boot", deadlines["boot"], lambda: boot(cell, config),
        lambda: "LoadCluster did not come up",
    )
    state["cluster"] = cluster
    if config["cluster"].get("scheduled_scrubs") == "held":
        hold_scrubs(cluster)
    say(f"boot: {config['cluster']['n_osds']} OSDs in "
        f"{time.monotonic() - t:.2f} s")

    loader = preload(cluster, cell, config, args.seed, Generator)
    standing_fault(cluster, cell)

    gen = Generator(
        cluster.io, mix, config["object_size"], config["queue_depth"],
        args.seed,
    )
    if loader is not None:
        gen.adopt(loader)
    state["gen"] = gen
    objecter_before = _objecter_ledger()
    gen.start()
    warm_up(gen, log, cell)

    trace_dir = None
    seconds = args.seconds
    if args.trace:
        trace_dir = os.path.join(files.HERE, ".trace", args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)  # the last run's
        seconds = min(seconds, cell["trace_window_s"])
    setup_s = time.perf_counter() - _T0
    t0, t1, moved = measure(gen, seconds, trace_dir)
    left = gen.drain(deadlines["drain"])
    gen.close()
    if left:
        say(f"drain: {left} ops still out after {deadlines['drain']} s; "
            "they count as failed")
    issued, _completed = gen.window_samples()
    attempted = len(issued)
    failed = sum(1 for s in issued if not s.ok)
    for s in [s for s in issued if not s.ok][:5]:
        say(f"failed op: {s.cls} object {s.idx}: {s.why or 'not completed'}")
    device["memory_peak_bytes"] = memory_peak()

    # -- correct: the guarantees, outside the window
    numbers = clock.call_with_deadline(
        "check", deadlines["check"],
        lambda: check.check(
            cluster, gen, config, args.seed, cell["check_objects"]
        ),
        lambda: "the comparison with the reference did not end",
    )
    ledger = _objecter_ledger() - objecter_before
    numbers["ledger_gap"] = abs(gen.issued - gen.accounted) + abs(
        # the check's own ops went through the same objecter
        ledger - gen.issued - numbers.pop("client_ops")
    )
    numbers["failed_ops"] = failed
    limits = {**check.LIMITS, "ledger_gap": 0, "failed_ops": 0}
    # what has to have been compared at all: a mix that deletes has to
    # have deleted
    compared = ["objects", "shards", "csum_objects", "deleted_objects"]
    deletes = any(c["op"] == "delete" for c in mix["classes"])
    needed = ["objects", "shards"] + ["deleted_objects"] * deletes
    correct = all(numbers[k] <= limits[k] for k in limits) and all(
        numbers[k] > 0 for k in needed
    )
    # every number compared beside its limit: the result line's last
    # key, and the run's last lines on standard error
    checked = {
        **{k: {"value": numbers[k], "limit": limits[k]} for k in limits},
        **{k: {"value": numbers[k], "at_least": int(k in needed)}
           for k in compared},
    }

    extra = {}
    if args.trace:
        from .trace import xplane

        spans = files.benchmark_spans()
        trace = xplane.load(xplane.find_xplane(trace_dir), set(spans))
        busy = xplane.busy_seconds(trace)
        device["busy_s"] = sum(busy.values()) / max(len(busy), 1)
        device["window_s"] = t1 - t0
        lo, hi = xplane.span_bounds(trace)
        extra["breakdown"] = {
            "device_ops": xplane.top_ops(trace),
            "idle_gaps": xplane.attribute_gaps(
                trace, xplane.idle_gaps(trace, lo, hi), spans
            ),
        }
        metrics = per_layer(
            args.workload, cell, config, device, moved,
            log.between(t0, t1), trace, t1 - t0, issued,
        )
    else:
        metrics = end_to_end(args.workload, gen, t0, t1)
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        in_window = log.between(t0, t1)
        if in_window:
            say(f"note: {len(in_window)} compilations inside the window")
    if args.rehearse:
        say("rehearsal readings (CPU, not a measurement): " + json.dumps(
            {"metrics": {k: v["value"] for k, v in metrics.items()}, **extra}
        ))
        metrics, extra = {}, {}
        device.pop("busy_s", None)
        device.pop("window_s", None)
    state["result"] = result_line(
        correct, attempted, failed, metrics, device, **extra, checked=checked
    )
    state["checked"] = checked
    return 0 if correct else 1


def _objecter_ledger() -> int:
    """Ops the client's objecter has resolved, either way."""
    from . import counters

    snap = counters.snapshot()
    return int(
        snap.get("loadgen_client:op_completed", 0)
        + snap.get("loadgen_client:op_error", 0)
    )


def main(argv=None) -> int:
    args = parse(argv if argv is not None else sys.argv[1:])
    state: dict = {"device": dict(UNKNOWN_DEVICE)}

    def alarm_line() -> str:
        gen = state.get("gen")
        attempted = failed = 0
        if gen is not None and gen.window_t0 is not None:
            gen.window_t1 = gen.window_t1 or time.perf_counter()
            issued, _ = gen.window_samples()
            attempted = len(issued)
            failed = sum(1 for s in issued if not s.ok)
        return result_line(False, attempted, failed, {}, state["device"])

    state["alarm_line"] = alarm_line
    alarm = clock.Alarm()
    alarm.arm(PROVISIONAL_ALARM_S, alarm_line)
    code = 1
    try:
        code = run(args, alarm, state)
    except clock.DeadlineMissed as e:
        say(f"{e}")
        state["result"] = alarm_line()
        code = 4
    except Exception:
        # before the gate has found the chips: no result line at all;
        # after it: the run is not correct, and says so
        traceback.print_exc()
        if state["device"]["platform"] != "unknown":
            state["result"] = alarm_line()
        code = 1
    finally:
        cluster = state.get("cluster")
        if cluster is not None:
            try:
                clock.call_with_deadline(
                    "shutdown", state["shutdown_s"], cluster.shutdown,
                )
            except Exception as e:  # the result still goes out
                say(f"shutdown: {type(e).__name__}: {e}")
        sys.stdout.flush()
        sys.stderr.flush()
    for name, row in state.get("checked", {}).items():
        print("check", name, json.dumps(row), file=sys.stderr)
    sys.stderr.flush()
    if "result" in state:
        print(state["result"], flush=True)
    alarm.disarm()
    # nothing outlives the run: daemon threads of a cluster that would
    # not stop must not hold the process
    os._exit(code)


if __name__ == "__main__":
    main()
