"""Round-10: pod-scale serving-tier sweep — the prepared chip run
for ISSUE 6's acceptance numbers.

The live path now pipelines client ops through the async objecter,
coalesces concurrent EC writes into per-tick device batches on each
OSD, packs sub-writes one frame per peer, and can serve ops over the
dispatch mesh / DCN tier. This script measures what each layer buys:

- ``cluster_vs_kernel_frac`` at qd ≫ 12 with THOUSANDS of zipfian
  objects, A/B coalesce on/off in the same session (the acceptance
  comparison: materially up with coalescing on);
- the qd ladder (8 → 64): does depth actually reach the wire now;
- the scaling row: GB/s and IOPS vs OSD count and vs chip count
  (mesh legs) — same rows the bench ``cluster`` phase emits, sized
  up for the chip session;
- the DCN hosts=3 leg with a mid-op host kill (VERDICT r5 #8):
  must report zero verify failures and op completion.

Run on the v5e chip:

    python experiments/exp_r10_serving_tier.py          # full sweep
    python experiments/exp_r10_serving_tier.py --quick  # CI-sized

The CPU fallback runs the same legs at toy sizes (correctness smoke;
absolute GB/s numbers mean nothing off-TPU)."""

import json
import sys
import time

sys.path.insert(0, ".")

QUICK = "--quick" in sys.argv


def _leg(tag, out, *, total_ops, qd, objects, coalesce=True,
         n_osds=6, use_mesh=False, mesh_devices=None,
         dcn_hosts=0, dcn_kill_at=0, seed=0xEC10):
    from ceph_tpu.loadgen import LoadCluster, WorkloadSpec, run_spec
    from ceph_tpu.loadgen.faults import FaultEvent, FaultSchedule
    from ceph_tpu.utils import config

    cluster = LoadCluster(
        n_osds=n_osds, k=4 if dcn_hosts == 0 else 3, m=2, pg_num=8,
        chunk_size=16384, use_mesh=use_mesh,
        mesh_devices=mesh_devices, dcn_hosts=dcn_hosts,
        dcn_data_timeout=5.0,
    )
    try:
        spec = WorkloadSpec(
            mix={"seq_write": 2, "rand_write": 1, "read": 3,
                 "reconstruct_read": 1, "rmw_overwrite": 1},
            object_size=256 * 1024, max_objects=objects,
            queue_depth=qd, total_ops=total_ops,
            warmup_ops=max(total_ops // 10, 8),
            popularity="zipfian", seed=seed,
        )
        schedule = None
        if dcn_kill_at:
            schedule = FaultSchedule(
                [FaultEvent(at_op=dcn_kill_at, action="dcn_kill")]
            )
        t0 = time.monotonic()
        with config.override(osd_op_coalescing=coalesce):
            report = run_spec(cluster, spec, schedule)
        coal = sum(
            d.coalesce_pc.get("op_coalesced")
            for d in cluster.daemons.values()
        )
    finally:
        cluster.shutdown()
    out[tag] = {
        "gbps": report["gbps"], "iops": report["iops"],
        "errors": report["errors"],
        "verify_failures": report["verify_failures"],
        "op_coalesced": coal,
        "wall_s": round(time.monotonic() - t0, 2),
    }
    print(f"  {tag}: {out[tag]}", flush=True)
    return report


def main() -> None:
    import jax

    ops = 80 if QUICK else 2400
    objects = 32 if QUICK else 2048  # chip run: thousands, zipfian
    out: dict = {"platform": jax.devices()[0].platform,
                 "ops": ops, "objects": objects}

    print("== A/B: coalesce on/off at qd 32 ==", flush=True)
    _leg("qd32_coalesce_on", out, total_ops=ops, qd=32,
         objects=objects, coalesce=True)
    _leg("qd32_coalesce_off", out, total_ops=ops, qd=32,
         objects=objects, coalesce=False, seed=0xEC11)
    on, off = out["qd32_coalesce_on"], out["qd32_coalesce_off"]
    if off["gbps"]:
        out["coalesce_speedup"] = round(on["gbps"] / off["gbps"], 3)

    print("== qd ladder ==", flush=True)
    for qd in (8, 16, 32, 64):
        _leg(f"qd{qd}", out, total_ops=ops, qd=qd, objects=objects,
             seed=0xEC20 + qd)

    print("== OSD scaling ==", flush=True)
    for n in (6, 9, 12):
        _leg(f"osd{n}", out, total_ops=max(ops // 2, 40), qd=32,
             objects=objects, n_osds=n, seed=0xEC30 + n)

    print("== chip scaling (mesh) ==", flush=True)
    n_dev = len(jax.devices())
    for chips in sorted({c for c in (1, 2, 4, n_dev) if c <= n_dev}):
        _leg(f"chips{chips}", out, total_ops=max(ops // 2, 40), qd=32,
             objects=objects, use_mesh=chips > 1,
             mesh_devices=chips if chips > 1 else None,
             seed=0xEC40 + chips)

    print("== DCN hosts=3, mid-op host kill (VERDICT r5 #8) ==",
          flush=True)
    rep = _leg("dcn3_host_kill", out, total_ops=max(ops // 4, 24),
               qd=8, objects=min(objects, 64), dcn_hosts=3,
               dcn_kill_at=max(ops // 12, 8), seed=0xEC50)
    out["dcn3_zero_verify_failures"] = rep["verify_failures"] == 0

    # acceptance summary
    out["accept_coalesce_up"] = bool(
        off["gbps"] and on["gbps"] > off["gbps"]
    )
    print(json.dumps(out, indent=2, sort_keys=True))


if __name__ == "__main__":
    main()
