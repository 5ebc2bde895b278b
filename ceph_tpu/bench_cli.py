"""ceph_erasure_code_benchmark-compatible CLI.

Reproduces the reference tool's interface and output contract
(src/test/erasure-code/ceph_erasure_code_benchmark.cc): encode/decode
workloads over a sized buffer for N iterations, ``--parameter k=v``
profile injection, random or exhaustive erasure generation with decoded
content verified against the original, and the two-column
``<elapsed_seconds>\t<total_KiB>`` output the qa sweep harness parses
(qa/workunits/erasure-code/bench.sh).

Timing contract: like the reference tool, each iteration is a
host-driven dispatch and the clock covers the full per-call path,
launch and transfer included. A kernel's time on the device alone is
the benchmark's (``benchmark/``: ``codec_roofline``, from the trace).

Two further workloads cover BASELINE.md configs 4-5 (which the
reference drives through the same tool plus Checksummer):

``repair`` — CLAY MSR single-chunk repair decode: rotate the lost
chunk, read only the fractional sub-chunk helper ranges that
``minimum_to_decode`` plans, and time ``codec.repair``. The KiB
column counts HELPER BYTES READ (the repair-bandwidth story —
(d*chunk)/(d-k+1) instead of k*chunk).

``checksum`` — Checksummer calculate over vmapped blocks
(BlueStore's deep-scrub role): ``--csum-alg``/``--csum-block``
select algorithm and granularity; the KiB column counts bytes
hashed.

Usage:
    python -m ceph_tpu.bench_cli encode --plugin isa -P k=8 -P m=4 \
        --size $((80 * 1024 * 1024)) --iterations 100
    python -m ceph_tpu.bench_cli decode --plugin jerasure \
        -P technique=reed_sol_van -P k=4 -P m=2 --erasures 2 \
        --erasures-generation exhaustive
    python -m ceph_tpu.bench_cli repair --plugin clay \
        -P k=8 -P m=4 -P d=11 --iterations 20
    python -m ceph_tpu.bench_cli checksum --csum-alg crc32c \
        --csum-block 4096 --size $((64 * 1024 * 1024))
"""

from __future__ import annotations

import argparse
import sys
import time
from itertools import combinations

import numpy as np


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        prog="ecbench", description=__doc__.splitlines()[0]
    )
    p.add_argument(
        "workload",
        choices=["encode", "decode", "repair", "checksum", "loadgen"],
    )
    p.add_argument(
        "--plugin", "-p", default=None,
        help="codec plugin (default: isa; repair defaults to clay; "
             "loadgen's pool defaults to jerasure)",
    )
    p.add_argument(
        "--parameter",
        "-P",
        action="append",
        default=[],
        help="profile key=value (repeatable), e.g. -P k=8 -P m=4; "
             "loadgen hands --plugin and every -P key, over k=3 m=2, to "
             "the pool as its whole profile",
    )
    p.add_argument("--size", "-s", type=int, default=80 * 1024 * 1024,
                   help="total bytes per iteration (default 80 MiB)")
    p.add_argument("--iterations", "-i", type=int, default=100)
    p.add_argument("--erasures", "-e", type=int, default=1,
                   help="erasures per decode iteration")
    p.add_argument(
        "--erasures-generation",
        "-E",
        choices=["random", "exhaustive"],
        default="random",
    )
    p.add_argument("--batch", type=int, default=8,
                   help="stripes per device dispatch")
    p.add_argument("--csum-alg", default="crc32c",
                   help="checksum workload: algorithm "
                        "(crc32c/crc32c_16/crc32c_8/xxhash32/xxhash64)")
    p.add_argument("--csum-block", type=int, default=4096,
                   help="checksum workload: csum block size in bytes")
    p.add_argument("--verbose", "-v", action="store_true")
    lg = p.add_argument_group(
        "loadgen", "live-cluster workload (radosbench analog): the "
        "two-column contract reports wall seconds and client bytes "
        "moved; the full JSON report goes to stderr"
    )
    lg.add_argument("--preset", default=None,
                    help="canned spec (smoke/mixed/write-heavy/"
                         "read-heavy); flags below override")
    lg.add_argument("--mix", default=None,
                    help='op mix, e.g. "seq_write=2,read=5,'
                         'rmw_overwrite=1"')
    lg.add_argument("--objects", type=int, default=None,
                    help="working-set cap (max objects)")
    lg.add_argument("--object-size", type=int, default=None)
    lg.add_argument("--queue-depth", type=int, default=None,
                    help="closed-loop workers (radosbench -t)")
    lg.add_argument("--ops", type=int, default=None,
                    help="total ops to run")
    lg.add_argument("--warmup", type=int, default=None,
                    help="leading ops excluded from the measurement")
    lg.add_argument("--popularity", default=None,
                    choices=["uniform", "zipfian"])
    lg.add_argument("--zipf-theta", type=float, default=None)
    lg.add_argument("--osds", type=int, default=6)
    lg.add_argument("--pg-num", type=int, default=8)
    lg.add_argument("--chunk-size", type=int, default=4096,
                    help="per-shard chunk bytes on the OSDs")
    lg.add_argument("--fault-at", type=int, default=0,
                    help="kill an OSD once this many ops completed "
                         "(0 = no fault)")
    lg.add_argument("--revive-at", type=int, default=0,
                    help="revive it at this op count (0 = at run end)")
    lg.add_argument("--fault-osd", type=int, default=-1,
                    help="kill victim osd id (-1 = use --victim)")
    lg.add_argument("--victim", default="most_primary",
                    choices=["least_primary", "most_primary"],
                    help="named victim picker when --fault-osd is -1 "
                         "(default most_primary: maximum simultaneous "
                         "primary takeovers — the peering soak path)")
    lg.add_argument("--device-clock", action="store_true",
                    help="report small-op p99 from the device clock "
                         "(host floor replaced by device op time)")
    lg.add_argument("--net-fault", default="none",
                    choices=["none", "flaky", "partition"],
                    help="arm the seeded network-fault plane: 'flaky' "
                         "layers >=2%% drop + dup + ~50 ms p95 delay on "
                         "every inter-OSD link between the fire/settle "
                         "offsets; 'partition' asymmetrically cuts the "
                         "--victim OSD off the data plane and merges it "
                         "back (both deterministic from --seed)")
    lg.add_argument("--net-drop", type=float, default=0.02,
                    help="flaky profile drop probability per frame")
    lg.add_argument("--net-dup", type=float, default=0.02,
                    help="flaky profile duplication probability")
    lg.add_argument("--net-delay-ms", type=float, default=5.0,
                    help="flaky profile base delay (+ jitter to ~50 ms "
                         "p95)")
    lg.add_argument("--seed", type=int, default=0xEC)
    lg.add_argument("--coalesce", choices=["on", "off"], default="on",
                    help="per-OSD-tick op coalescing (A/B flag: run "
                         "the same spec both ways to measure what "
                         "batching buys the live path)")
    lg.add_argument("--trace-capture", type=int, default=0,
                    help="capture the N slowest assembled traces "
                         "(span trees + critical paths + Chrome "
                         "trace JSON) into the report")
    lg.add_argument("--forensics-dir", default=None,
                    help="write a forensics bundle (ops-in-flight + "
                         "assembled traces + cluster-log tail + perf "
                         "dump) into this directory when the run is "
                         "non-green or converges slowly")
    lg.add_argument("--slow-convergence-s", type=float, default=0.0,
                    help="with --forensics-dir: also dump when "
                         "post-kill time_to_recovered_s exceeds this "
                         "(0 = only on non-green)")
    lg.add_argument("--force-forensics", action="store_true",
                    help="treat the run as non-green regardless of "
                         "outcome (the forensics smoke-test hook)")
    lg.add_argument("--lockdep", action="store_true",
                    help="arm the runtime lock-order / blocking-"
                         "under-lock detector for the run "
                         "(utils/lockdep.py): findings land in the "
                         "report + forensics bundle (lockdep.json) "
                         "and fail the run like a verify failure")
    lg.add_argument("--smoke", action="store_true",
                    help="tiny deterministic end-to-end run (CI "
                         "surface): smoke preset, 4 OSDs, one "
                         "kill/revive cycle")
    lg.add_argument("--tenants", type=int, default=0,
                    help="multi-tenant mode: run N identically-shaped "
                         "tenants (t0..tN-1), each its own closed "
                         "loop through a tenant-tagged IoCtx onto the "
                         "OSDs' per-tenant mClock classes; the report "
                         "grows per-tenant sections")
    lg.add_argument("--qos-profile", default=None,
                    choices=["high_client", "balanced",
                             "high_recovery"],
                    help="osd_mclock_profile for the run (the "
                         "recovery-vs-client slosh knob)")
    lg.add_argument("--transport", default=None,
                    choices=["tcp", "shm_ring"],
                    help="messenger lane (msgr_transport): shm_ring "
                         "takes the shared-memory fast path for "
                         "co-located peers, falling back to TCP per "
                         "connection when the peer is out-of-process")
    lg.add_argument("--op-shards", type=int, default=None,
                    help="osd_op_num_shards: split each OSD's op "
                         "worker into N per-PG-hash shards (default "
                         "1 = the classic single worker)")
    return p.parse_args(argv)


def _force(out) -> None:
    """Force completion with a real readback of ONE element per
    output leaf (sliced on device first — a full-array readback would
    bill the transfer, not the compute)."""
    import jax

    for leaf in jax.tree_util.tree_leaves(out):
        if hasattr(leaf, "ndim"):
            np.asarray(leaf[(0,) * leaf.ndim])


def run(args: argparse.Namespace) -> tuple[float, float]:
    """Execute one workload; returns (elapsed_seconds, total_KiB).
    Raises RuntimeError if a decoded chunk differs from the original."""
    from ceph_tpu.utils import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    from ceph_tpu.codecs import registry

    if args.workload == "checksum":
        return _run_checksum(args)
    if args.workload == "loadgen":
        return _run_loadgen(args)

    profile = {}
    for kv in args.parameter:
        key, _, val = kv.partition("=")
        profile[key] = val
    if args.plugin is None:
        # Only substitute a default when the flag was omitted — an
        # explicit --plugin must never be silently rebound.
        args.plugin = "clay" if args.workload == "repair" else "isa"
    codec = registry.factory(args.plugin, profile)
    if args.workload == "repair":
        if not hasattr(codec, "repair"):
            raise RuntimeError(
                f"plugin {args.plugin!r} has no fractional repair path "
                "(the repair workload needs an MSR codec, e.g. clay)"
            )
        return _run_repair(args, codec)
    k = codec.get_data_chunk_count()
    m = codec.get_coding_chunk_count()

    # Size -> per-shard chunk bytes across the stripe batch.
    chunk = codec.get_chunk_size(max(args.size // args.batch, k))
    rng = np.random.default_rng(0)
    data_np = rng.integers(0, 256, (args.batch, k, chunk)).astype(np.uint8)
    data = {i: jnp.asarray(data_np[:, i, :]) for i in range(k)}

    if args.verbose:
        print(
            f"plugin={args.plugin} profile={profile} k={k} m={m} "
            f"chunk={chunk} batch={args.batch}",
            file=sys.stderr,
        )

    parity = codec.encode_chunks(data)  # compile + warm
    jax.block_until_ready(parity)

    if args.workload == "encode":
        t0 = time.perf_counter()
        for _ in range(args.iterations):
            parity = codec.encode_chunks(data)
        _force(parity)
        elapsed = time.perf_counter() - t0
        total_kib = args.iterations * args.batch * k * chunk / 1024
    else:
        chunks = {**data, **parity}
        originals = {i: np.asarray(c) for i, c in chunks.items()}
        if args.erasures_generation == "exhaustive":
            patterns = list(combinations(range(k + m), args.erasures))
        else:
            pool = list(range(k + m))
            patterns = [
                tuple(rng.choice(pool, args.erasures, replace=False))
                for _ in range(args.iterations)
            ]
        # Warm every pattern once outside the clock: host-side matrix
        # inversion, device upload of the decode table, and first-call
        # compilation all happen here, not in the timed loop (the
        # reference also excludes setup from the timed section).
        for erased in set(patterns):
            have = {i: c for i, c in chunks.items() if i not in erased}
            jax.block_until_ready(codec.decode_chunks(set(erased), have))
        elapsed = 0.0
        total_kib = 0.0
        for it in range(args.iterations):
            erased = patterns[it % len(patterns)]
            have = {i: c for i, c in chunks.items() if i not in erased}
            t0 = time.perf_counter()
            out = codec.decode_chunks(set(erased), have)
            _force(out)
            elapsed += time.perf_counter() - t0
            total_kib += args.batch * k * chunk / 1024
            for e in erased:
                if not (np.asarray(out[e]) == originals[e]).all():
                    raise RuntimeError(f"chunk {e} differs after decode")
    return elapsed, total_kib


def _run_repair(args, codec) -> tuple[float, float]:
    """CLAY (or any sub-chunk codec) single-chunk repair decode —
    BASELINE.md config 4. Reads only the helper sub-chunk ranges the
    repair plan asks for, mirroring what the read pipeline ships over
    the wire (ECCommon.h:85 subchunk selectors)."""
    import jax
    import jax.numpy as jnp

    k = codec.get_data_chunk_count()
    m = codec.get_coding_chunk_count()
    n = k + m
    sub = codec.get_sub_chunk_count()
    chunk = codec.get_chunk_size(max(args.size, k))
    sc = chunk // sub
    rng = np.random.default_rng(0)
    data = {
        i: jnp.asarray(rng.integers(0, 256, (chunk,), np.uint8))
        for i in range(k)
    }
    chunks = {**data, **codec.encode_chunks(data)}
    originals = {i: np.asarray(c) for i, c in chunks.items()}

    def helper_reads(lost: int):
        plan = codec.minimum_to_decode({lost}, set(range(n)) - {lost})
        helper = {}
        read_bytes = 0
        for node, ranges in plan.items():
            parts = [
                chunks[node][idx * sc : (idx + cnt) * sc]
                for idx, cnt in ranges
            ]
            read_bytes += sum(p.shape[0] for p in parts)
            helper[node] = jnp.asarray(np.concatenate(
                [np.asarray(p) for p in parts]
            ))
        return helper, read_bytes

    for lost in range(n):  # warm every rotation outside the clock
        helper, _ = helper_reads(lost)
        jax.block_until_ready(codec.repair({lost}, helper))

    elapsed = 0.0
    total_kib = 0.0
    for it in range(args.iterations):
        lost = it % n
        helper, read_bytes = helper_reads(lost)
        t0 = time.perf_counter()
        out = codec.repair({lost}, helper)
        _force(out)
        elapsed += time.perf_counter() - t0
        total_kib += read_bytes / 1024
        if not (np.asarray(out[lost]) == originals[lost]).all():
            raise RuntimeError(f"chunk {lost} differs after repair")
    return elapsed, total_kib


def _run_loadgen(args) -> tuple[float, float]:
    """Live-cluster load generation (the radosbench workload): boot a
    vstart-analog cluster, drive the spec, verify every op, print the
    JSON report on stderr, and honor the two-column contract with
    (wall seconds, client bytes moved / 1024)."""
    import json

    from ceph_tpu.loadgen import (
        FaultEvent,
        FaultSchedule,
        LoadCluster,
        WorkloadSpec,
        parse_mix,
        preset,
        run_spec,
    )

    if args.smoke:
        spec = preset(
            "smoke", seed=args.seed,
            device_clock=bool(args.device_clock),
            trace_capture=args.trace_capture,
        )
        osds, chunk, pool = 5, 1024, {"k": 2, "m": 1}
        fault_at = spec.total_ops // 3
        revive_at = (2 * spec.total_ops) // 3
        args.fault_osd = -1  # named victim, resolved below
    else:
        kw: dict = {}
        if args.mix is not None:
            kw["mix"] = parse_mix(args.mix)
        if args.objects is not None:
            kw["max_objects"] = args.objects
        if args.object_size is not None:
            kw["object_size"] = args.object_size
        if args.queue_depth is not None:
            kw["queue_depth"] = args.queue_depth
        if args.ops is not None:
            kw["total_ops"] = args.ops
        if args.warmup is not None:
            kw["warmup_ops"] = args.warmup
        if args.popularity is not None:
            kw["popularity"] = args.popularity
        if args.zipf_theta is not None:
            kw["zipf_theta"] = args.zipf_theta
        kw["seed"] = args.seed
        kw["device_clock"] = bool(args.device_clock)
        kw["trace_capture"] = args.trace_capture
        spec = (
            preset(args.preset, **kw)
            if args.preset else WorkloadSpec(**kw)
        )
        # the pool's profile goes to the cluster whole (--plugin and
        # every -P key, as ``ceph osd erasure-code-profile set`` takes
        # them) over this command's k=3 m=2 on jerasure; a key the
        # plugin does not know is the codec's to refuse
        profile = {"plugin": args.plugin or "jerasure", "k": "3", "m": "2"}
        for pkv in args.parameter:
            key, _, val = pkv.partition("=")
            profile[key] = val
        pool = {"profile": profile}
        osds, chunk = args.osds, args.chunk_size
        fault_at, revive_at = args.fault_at, args.revive_at
    from ceph_tpu.utils import config as _config

    if getattr(args, "tenants", 0):
        from ceph_tpu.loadgen.spec import default_tenants

        spec.tenants = default_tenants(args.tenants)
    net_fault = getattr(args, "net_fault", "none")
    overrides = dict(osd_op_coalescing=(args.coalesce == "on"))
    if getattr(args, "qos_profile", None):
        overrides["osd_mclock_profile"] = args.qos_profile
    if getattr(args, "transport", None):
        overrides["msgr_transport"] = args.transport
    if getattr(args, "op_shards", None):
        overrides["osd_op_num_shards"] = args.op_shards
    if args.lockdep:
        # arm the runtime lock-order / blocking-under-lock detector
        # for this cluster (locks read the flag at construction);
        # findings land in the report + forensics bundle and fail
        # the run like a verify failure
        from ceph_tpu.utils import lockdep as _lockdep

        _lockdep.reset()
        overrides["lockdep"] = True
    if net_fault != "none":
        # lost frames must resolve inside the client's resend
        # ladder, not a 10 s peer-RPC stall per drop (daemons read
        # these at boot — the override wraps cluster creation); the
        # sub-op retransmit ladder arms so a single lost sub-write
        # ack costs ~0.2 s, not an op park
        overrides["osd_peer_rpc_timeout"] = 1.0
        overrides["osd_subop_resend_interval"] = 0.2
    _override_ctx = _config.override(**overrides)
    _override_ctx.__enter__()
    cluster = LoadCluster(
        n_osds=osds,
        pg_num=(args.pg_num if not args.smoke else 4),
        chunk_size=chunk, **pool,
    )
    schedule = None
    if fault_at:
        # -1 = a NAMED picker resolved at fire time (the default
        # most_primary targets the takeover path the FSM soaks)
        victim = (
            args.fault_osd if args.fault_osd != -1 else args.victim
        )
        events = [
            FaultEvent(at_op=fault_at, action="kill", osd=victim)
        ]
        if revive_at:
            events.append(
                FaultEvent(at_op=revive_at, action="revive")
            )
        schedule = FaultSchedule(events)
    if net_fault == "flaky":
        net_sched = FaultSchedule.net_flaky(
            spec.total_ops, seed=args.seed, drop=args.net_drop,
            dup=args.net_dup, delay_ms=args.net_delay_ms,
        )
        if schedule is None:
            schedule = net_sched
        else:  # chaos composition: churn x lossy links, one schedule
            schedule = FaultSchedule(
                schedule.events + net_sched.events,
                recovery_timeout=schedule.recovery_timeout,
            )
    elif net_fault == "partition":
        part_victim = (
            args.fault_osd if args.fault_osd != -1 else args.victim
        )
        schedule = FaultSchedule.net_partition(
            spec.total_ops, victim=part_victim, seed=args.seed,
        )
    try:
        report = run_spec(cluster, spec, schedule)
        report["coalesce"] = args.coalesce
        if net_fault != "none":
            from ceph_tpu.msg.messenger import net_faults

            report["net_fault"] = net_fault
            report["net_fault_counters"] = dict(net_faults.counters)
            report["net_dedup_hits"] = sum(
                d.net_pc.get("dedup_hits")
                for d in cluster.daemons.values()
            )
            report["net_resends_absorbed"] = sum(
                d.net_pc.get("resends_absorbed")
                for d in cluster.daemons.values()
            )
        report["op_coalesced"] = sum(
            d.coalesce_pc.get("op_coalesced")
            for d in cluster.daemons.values()
        )
        report["subwrite_batches"] = sum(
            d.coalesce_pc.get("subwrite_batches")
            for d in cluster.daemons.values()
        )
        if args.lockdep:
            from ceph_tpu.utils import lockdep as _lockdep

            report["lockdep"] = _lockdep.findings()
        # forensics BEFORE teardown and before any raise: wedged ops
        # are still live, the cluster log still holds this run's tail
        from ceph_tpu.loadgen.forensics import run_is_green

        green, why = run_is_green(report, args.slow_convergence_s)
        if "status_digest" in report:
            # the one-line `cli status` digest (soak.sh echoes it
            # per lap)
            print(
                f"status digest: {report['status_digest']}",
                file=sys.stderr,
            )
        if not green and report.get("pg_states") is not None:
            # the final PG state histogram, for non-green triage
            hist = ", ".join(
                f"{n} {state}" for state, n in sorted(
                    report["pg_states"].items(),
                    key=lambda kv: (-kv[1], kv[0]),
                )
            ) or "(no reports)"
            print(
                f"final pg states ({why}): {hist}", file=sys.stderr
            )
        if args.forensics_dir:
            from ceph_tpu.loadgen.forensics import write_bundle

            if args.force_forensics:
                green, why = False, "forced (--force-forensics)"
            if not green:
                manifest = write_bundle(
                    args.forensics_dir, report, reason=why,
                    trace_capture=args.trace_capture or 8,
                    cluster=cluster,
                )
                report["forensics"] = manifest
                print(
                    f"forensics bundle: {manifest['dir']} ({why})",
                    file=sys.stderr,
                )
        if not report.get("exactly_once"):
            raise RuntimeError(
                f"op accounting mismatch: issued {report['ops_in']} "
                f"!= accounted {report['ops_accounted']}"
            )
        if report["verify_failures"]:
            raise RuntimeError(
                f"{report['verify_failures']} ops failed "
                "content/checksum verification"
            )
        if args.lockdep and any(report.get("lockdep", {}).values()):
            raise RuntimeError(
                f"lockdep findings: {report['lockdep']} (dump: "
                "admin-socket `lockdep`; bundle: lockdep.json)"
            )
    finally:
        cluster.shutdown()
        _override_ctx.__exit__(None, None, None)
    print(json.dumps(report, sort_keys=True), file=sys.stderr)
    return report["duration_s"], report["bytes"] / 1024


def _run_checksum(args) -> tuple[float, float]:
    """Checksummer calculate over vmapped blocks — BASELINE.md
    config 5 (the BlueStore deep-scrub role, Checksummer.h:196)."""
    from ceph_tpu.checksum import Checksummer

    import jax.numpy as jnp

    summer = Checksummer(args.csum_alg, args.csum_block)
    size = (args.size // args.csum_block) * args.csum_block
    if size == 0:
        raise RuntimeError("--size smaller than one csum block")
    rng = np.random.default_rng(0)
    # Device-resident buffer: the workload measures the checksum
    # kernels, not a host->device upload per iteration.
    buf = jnp.asarray(rng.integers(0, 256, (size,), np.uint8))
    np.asarray(summer.calculate(buf))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(args.iterations):
        csums = summer.calculate(buf)
    np.asarray(csums)
    elapsed = time.perf_counter() - t0
    return elapsed, args.iterations * size / 1024


def main(argv: list[str] | None = None) -> int:
    args = parse_args(argv)
    try:
        elapsed, total_kib = run(args)
    except RuntimeError as e:
        print(e, file=sys.stderr)
        return 1
    # The reference's two-column contract: elapsed seconds TAB total KiB.
    print(f"{elapsed:.6f}\t{int(total_kib)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
