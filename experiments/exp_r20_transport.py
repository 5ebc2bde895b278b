"""Round-20: Messenger v2 transport/codec grid — the prepared chip
run for ISSUE 20's acceptance numbers.

The messenger grew a native (C) clear-frame codec behind
``msgr_native_codec``, a shared-memory ring lane for co-located peers
behind ``msgr_transport=shm_ring``, and the OSD op worker split into
per-PG-hash shards behind ``osd_op_num_shards``. This script measures
what the tier buys, as within-run A/Bs (same seed, same process, so
run-to-run drift cancels):

- the transport x codec grid: the same mixed workload over
  {tcp, shm_ring} x {python, native} frame codecs — gbps / iops /
  p99 per leg plus ``vs_kernel_frac`` (cluster throughput as a
  fraction of the raw encode kernel rate: how much of the device's
  rate the cluster plumbing delivers end-to-end);
- trace-attributed critical paths on the two corner legs (tcp+python
  vs shm_ring+native): per-lane self-time from the span trees —
  the wire/queue share must shrink when the codec goes native and
  the frames stop crossing a socket;
- the head-of-line rows: flood x kill tenant-A latency spread at
  1 vs 4 op shards, plus the deterministic parked-shard sibling
  probe (the single-worker wedge, measured directly).

Run on the v5e chip:

    python experiments/exp_r20_transport.py                # full
    python experiments/exp_r20_transport.py --quick        # CI-sized
    python experiments/exp_r20_transport.py --enc-gbps 57  # reuse
        bench.py's kernel headline as the vs-kernel denominator

The CPU fallback runs the same legs at toy sizes (correctness smoke;
absolute rates mean nothing off-TPU)."""

import json
import sys
import time

sys.path.insert(0, ".")

QUICK = "--quick" in sys.argv


def _enc_gbps_arg():
    for i, a in enumerate(sys.argv):
        if a == "--enc-gbps" and i + 1 < len(sys.argv):
            return float(sys.argv[i + 1])
        if a.startswith("--enc-gbps="):
            return float(a.split("=", 1)[1])
    return None


def _kernel_gbps(k=4, m=2, chunk=16384, batch=8, iters=10):
    """Encode-kernel rate through the codec front door (includes
    host<->device staging — a conservative denominator; pass
    ``--enc-gbps`` with bench.py's pure device-loop headline for the
    strict one)."""
    import numpy as np

    from ceph_tpu.codecs import create_codec

    codec = create_codec(
        "jerasure", k=str(k), m=str(m), technique="reed_sol_van",
    )
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, batch * k * chunk, np.uint8).tobytes()
    codec.encode(data)  # warm + compile
    t0 = time.perf_counter()
    for _ in range(iters):
        codec.encode(data)
    dt = time.perf_counter() - t0
    return len(data) * iters / dt / 1e9


def _lane_self_ms(cap):
    """Sum critical-path self time by lane across the captured
    traces: the 'where does the wall time live' attribution."""
    lanes: dict = {}
    for cp in cap.get("critical_paths", []):
        for st in cp.get("stages", []):
            lanes[st["lane"]] = lanes.get(st["lane"], 0.0) + st["self_s"]
    return {k: round(v * 1e3, 3) for k, v in sorted(lanes.items())}


def _leg(tag, out, *, transport, native_codec, total_ops, qd, objects,
         object_size, enc_gbps=None, trace=False, seed=0xEC20):
    """One grid leg: the standard mixed workload with the messenger
    lane and frame codec pinned for the cluster's whole lifetime."""
    from ceph_tpu.loadgen import LoadCluster, WorkloadSpec, run_spec
    from ceph_tpu.msg import shm_ring
    from ceph_tpu.utils import config
    from ceph_tpu.utils.trace import tracer

    shm_ring.reset_stats()
    with config.override(msgr_transport=transport,
                         msgr_native_codec=native_codec):
        cluster = LoadCluster(
            n_osds=6, k=4, m=2, pg_num=8, chunk_size=16384,
        )
        try:
            if trace:
                tracer.clear()
            spec = WorkloadSpec(
                mix={"seq_write": 2, "rand_write": 1, "read": 3,
                     "rmw_overwrite": 1},
                object_size=object_size, max_objects=objects,
                queue_depth=qd, total_ops=total_ops,
                warmup_ops=max(total_ops // 10, 8),
                popularity="zipfian", seed=seed,
            )
            t0 = time.monotonic()
            report = run_spec(cluster, spec, None)
            row = {
                "gbps": report["gbps"],
                "iops": report["iops"],
                "p99_ms": report.get("lat_p99_ms"),
                "errors": report["errors"],
                "verify_failures": report["verify_failures"],
                "wall_s": round(time.monotonic() - t0, 2),
            }
            if transport == "shm_ring":
                snap = shm_ring.snapshot()
                row["shm_chunks"] = snap["chunks"]
                row["shm_bytes"] = snap["bytes"]
            if enc_gbps:
                row["vs_kernel_frac"] = round(
                    report["gbps"] / enc_gbps, 6
                )
            if trace:
                from ceph_tpu.utils.trace_assembly import capture_traces

                cap = capture_traces(limit=8)
                row["trace_lane_self_ms"] = _lane_self_ms(cap)
        finally:
            cluster.shutdown()
    out[tag] = row
    print(f"  {tag}: {row}", flush=True)
    return row


def main() -> None:
    import jax

    ops = 48 if QUICK else 640
    objects = 24 if QUICK else 256
    qd = 8 if QUICK else 32
    osize = 16 * 1024 if QUICK else 256 * 1024
    out: dict = {"platform": jax.devices()[0].platform,
                 "ops": ops, "objects": objects, "qd": qd}

    enc_gbps = _enc_gbps_arg()
    if enc_gbps is None:
        enc_gbps = round(_kernel_gbps(), 3)
        out["enc_gbps_source"] = "in-run codec.encode loop"
    else:
        out["enc_gbps_source"] = "--enc-gbps (bench.py headline)"
    out["enc_gbps"] = enc_gbps

    print("== transport x codec grid ==", flush=True)
    for tag, transport, native, trace in (
        ("tcp_py", "tcp", False, True),
        ("tcp_native", "tcp", True, False),
        ("shm_py", "shm_ring", False, False),
        ("shm_native", "shm_ring", True, True),
    ):
        _leg(tag, out, transport=transport, native_codec=native,
             total_ops=ops, qd=qd, objects=objects, object_size=osize,
             enc_gbps=enc_gbps, trace=trace, seed=0xEC20)
    if out["tcp_py"]["gbps"]:
        out["frame_codec_speedup"] = round(
            out["tcp_native"]["gbps"] / out["tcp_py"]["gbps"], 4
        )
    if out["tcp_native"]["gbps"]:
        out["shm_ring_speedup"] = round(
            out["shm_native"]["gbps"] / out["tcp_native"]["gbps"], 4
        )
    out["accept_shm_lane_used"] = bool(
        out["shm_native"].get("shm_chunks", 0) > 0
    )
    # wire/queue self-time across the corner legs: the gap stages on
    # the critical path (client close -> primary pickup, dispatch ->
    # sub-write) are where the codec + socket time lives
    wq0 = out["tcp_py"].get("trace_lane_self_ms", {}).get("wire/queue")
    wq1 = out["shm_native"].get(
        "trace_lane_self_ms", {}
    ).get("wire/queue")
    if wq0 and wq1:
        out["wire_queue_self_frac"] = round(wq1 / wq0, 4)

    print("== flood x kill shard ladder (1 vs 4 op shards) ==",
          flush=True)
    from ceph_tpu.loadgen.bench_phase import hol_probe_ms, qos_leg
    from ceph_tpu.utils import config

    for n in (1, 4):
        with config.override(osd_op_num_shards=n):
            rep = qos_leg(ops, qd, objects, flood=True, faults=True,
                          seed=0xEC20)
        a = rep.get("tenants", {}).get("tenantA", {})
        row = {pct: a.get(f"lat_{pct}_ms")
               for pct in ("p50", "p95", "p99")}
        row["verify_failures"] = rep.get("verify_failures")
        out[f"shards{n}_storm"] = row
        print(f"  shards{n}_storm: {row}", flush=True)

    print("== deterministic head-of-line probe ==", flush=True)
    h1 = hol_probe_ms(1)
    h4 = hol_probe_ms(4)
    out["hol_probe_shards1_ms"] = h1
    out["hol_probe_shards4_ms"] = h4
    if h1 > 0 and h4 > 0:
        out["hol_probe_frac"] = round(h4 / h1, 4)
        # the parked sibling must clear in a small fraction of the
        # park window once the worker is sharded
        out["accept_hol_removed"] = bool(h4 / h1 < 0.5)

    print(json.dumps(out, indent=2, sort_keys=True))


if __name__ == "__main__":
    main()
