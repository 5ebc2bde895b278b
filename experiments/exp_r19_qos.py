"""Round-19: multi-tenant QoS sweep — the prepared chip run for
ISSUE 19's acceptance numbers.

Client ops now carry a tenant identity end-to-end (client -> objecter
-> OSDOp wire -> per-tenant dmClock class on every OSD), pool QoS
specs ride the map, costs are byte-proportional, and the
``osd_mclock_profile`` slosh knob re-splits capacity between clients
and recovery. This script measures what the plane buys:

- the noisy-neighbor ladder: tenant A's p99 vs tenant-B flood
  intensity (queue-depth rungs), with and without concurrent
  recovery, QoS armed — the bound must hold flat-ish while the
  ``osd_op_qos=false`` escape hatch at the top rung blows past it;
- the slosh curve: time-to-recovered vs tenant-A p99 across
  high_client / balanced / high_recovery — the knob must trade them
  monotonically (>=3 settings, the acceptance shape);
- per-tenant p99 rows in BOTH clocks (host and device-clock mode) at
  the contended point — the chip row BASELINE.md wants.

Run on the v5e chip:

    python experiments/exp_r19_qos.py          # full sweep
    python experiments/exp_r19_qos.py --quick  # CI-sized

The CPU fallback runs the same legs at toy sizes (correctness smoke;
absolute latencies mean nothing off-TPU)."""

import json
import sys
import time

sys.path.insert(0, ".")

QUICK = "--quick" in sys.argv


def _leg(tag, out, *, total_ops, qd, objects, flood_qd=0,
         flood_mult=2, faults=False, qos_on=True, profile="balanced",
         device_clock=False, object_size=64 * 1024, seed=0xEC19):
    """One multi-tenant run: tenant A's modest read-heavy mix with a
    reservation+weight spec, optionally tenant B's write flood at
    ``flood_qd`` on top, optionally a mid-run most-primary
    kill/revive."""
    from ceph_tpu.loadgen import LoadCluster, WorkloadSpec, run_spec
    from ceph_tpu.loadgen.faults import FaultEvent, FaultSchedule
    from ceph_tpu.utils import config

    tenants: dict = {
        "tenantA": {
            "mix": {"seq_write": 1, "read": 3, "rmw_overwrite": 1},
            "object_size": object_size,
            "queue_depth": max(qd // 4, 2),
            "total_ops": total_ops,
            "qos": {"res_ops": 64.0, "res_bytes": 8 << 20,
                    "weight": 4.0},
        },
    }
    if flood_qd:
        tenants["tenantB"] = {
            "mix": {"seq_write": 3, "rand_write": 2},
            "object_size": object_size * 4,
            "queue_depth": flood_qd,
            "total_ops": total_ops * flood_mult,
            "qos": {"weight": 1.0},
        }
    with config.override(osd_op_qos=qos_on,
                         osd_mclock_profile=profile):
        cluster = LoadCluster(
            n_osds=6, k=4, m=2, pg_num=8, chunk_size=16384,
        )
        try:
            spec = WorkloadSpec(
                mix={"seq_write": 1, "read": 1},
                object_size=object_size, max_objects=objects,
                queue_depth=qd, total_ops=total_ops,
                warmup_ops=max(total_ops // 10, 8),
                popularity="zipfian", device_clock=device_clock,
                seed=seed, tenants=tenants,
            )
            schedule = None
            if faults:
                schedule = FaultSchedule(
                    [FaultEvent(at_op=total_ops // 3, action="kill"),
                     FaultEvent(at_op=(2 * total_ops) // 3,
                                action="revive")],
                )
            t0 = time.monotonic()
            report = run_spec(cluster, spec, schedule)
        finally:
            cluster.shutdown()
    a = report["tenants"]["tenantA"]
    row = {
        "tenantA_p99_ms": a.get("lat_p99_ms"),
        "tenantA_iops": round(a["ops"] / a["duration_s"], 2)
        if a.get("duration_s") else None,
        "errors": report["errors"],
        "verify_failures": report["verify_failures"],
        "wall_s": round(time.monotonic() - t0, 2),
    }
    if device_clock:
        row["tenantA_p99_ms_device"] = a.get("lat_p99_ms_device")
        b = report["tenants"].get("tenantB", {})
        row["tenantB_p99_ms_device"] = b.get("lat_p99_ms_device")
    if faults and "fault" in report:
        row["time_to_recovered_s"] = report["fault"].get(
            "time_to_recovered_s")
    out[tag] = row
    print(f"  {tag}: {row}", flush=True)
    return report


def main() -> None:
    import jax

    ops = 48 if QUICK else 720
    objects = 24 if QUICK else 512
    qd = 8 if QUICK else 32
    osize = 16 * 1024 if QUICK else 256 * 1024
    out: dict = {"platform": jax.devices()[0].platform,
                 "ops": ops, "objects": objects, "qd": qd}

    print("== noisy-neighbor ladder: flood qd x recovery ==",
          flush=True)
    rungs = (0, qd // 2, qd) if QUICK else (0, qd // 2, qd, qd * 2)
    for flood_qd in rungs:
        for faults in (False, True):
            tag = (f"flood{flood_qd}" + ("_recovery" if faults else ""))
            _leg(tag, out, total_ops=ops, qd=qd, objects=objects,
                 flood_qd=flood_qd, faults=faults,
                 object_size=osize, seed=0xEC19)
    # the escape hatch at the top rung: same storm, flat class
    _leg("hatch_noqos", out, total_ops=ops, qd=qd, objects=objects,
         flood_qd=rungs[-1], faults=True, qos_on=False,
         object_size=osize, seed=0xEC19)
    solo = out["flood0"]["tenantA_p99_ms"]
    top = out[f"flood{rungs[-1]}_recovery"]["tenantA_p99_ms"]
    hatch = out["hatch_noqos"]["tenantA_p99_ms"]
    if solo:
        out["noisy_neighbor_frac"] = round(top / solo, 3)
        out["escape_hatch_frac"] = round(hatch / solo, 3)
        out["accept_qos_beats_hatch"] = bool(top < hatch)

    print("== slosh curve: >=3 knob settings ==", flush=True)
    curve = {}
    for prof in ("high_client", "balanced", "high_recovery"):
        rep = _leg(f"slosh_{prof}", out, total_ops=ops, qd=qd,
                   objects=objects, flood_qd=qd // 2, faults=True,
                   profile=prof, object_size=osize, seed=0x5119)
        curve[prof] = out[f"slosh_{prof}"].get("time_to_recovered_s")
    if all(v is not None for v in curve.values()):
        out["accept_slosh_monotone"] = bool(
            curve["high_recovery"] <= curve["balanced"]
            <= curve["high_client"]
        )

    print("== per-tenant p99, device clock (the chip row) ==",
          flush=True)
    _leg("contended_device_clock", out, total_ops=ops, qd=qd,
         objects=objects, flood_qd=qd // 2, device_clock=True,
         object_size=osize, seed=0xEC19)

    print(json.dumps(out, indent=2, sort_keys=True))


if __name__ == "__main__":
    main()
