"""Flagship benchmark: EC(8,4) Reed-Solomon batched stripe encode,
plus the full BASELINE.json scorecard.

Prints ONE JSON line. Headline fields {"metric", "value", "unit",
"vs_baseline"} report the encode throughput against the 25 GB/s/chip
target (BASELINE.json north star); extra fields cover the rest of the
BASELINE.md scorecard (see the keys in main()).

Methodology (round 5 — the measurement itself is a deliverable;
VERDICT r4 item 5):

1. **Feedback loops.** Each iteration's kernel OUTPUT patches the next
   iteration's INPUT (a 128-byte slice), so iterations are serially
   dependent *through the kernel*. Round-4's loop only perturbed the
   input from the induction variable — with nothing consuming the
   output inside the carry, the runtime overlapped/elided iterations:
   a pure-copy kernel measured flat wall time from 100 to 8100
   iterations. With feedback the same probe scales linearly and
   reproduces the known bf16 matmul rate (~0.7 ms per 4096^3 step).
2. **Working sets larger than VMEM.** v5e has 16 MiB of VMEM; any
   input under that can be served without touching HBM after the
   first pass, inflating "bandwidth" far beyond the roofline. All
   throughput configs here stream >= 64 MB.
3. **Diff-of-minima timing.** t(n1) and t(n2) are each timed `reps`
   times; host hiccups only ADD time, so min(t) is the clean
   estimate of each; per-iter = (min t2 - min t1)/(n2 - n1). The
   paired diffs additionally give a dispersion estimate reported as
   `<key>_iqr` (inter-quartile range of per-iter GB/s across rep
   pairs) for the headline metrics.
4. **Roofline, BOTH axes.** The HBM rate is measured each run with
   a pure-copy Pallas kernel over a 128 MB working set
   (`hbm_copy_gbps`, read+write); `hbm_roofline_frac` is achieved
   encode traffic over that *measured* rate. The flagship bit-plane
   kernel is COMPUTE-bound (256 MACs per data byte at (8,4)), so
   `mxu_util_frac` — achieved int8 TOPS over the chip's published
   peak, from the one table below (`PUBLISHED_PEAKS`, keyed by
   `device_kind`; a device not in it is an error) — is its governing
   roofline.
5. **A TPU or nothing.** `require_tpu()` runs first: on any other
   backend the Pallas kernels would run in the interpreter and their
   times would be written under device names. Every phase that raises
   lands in `failed_phases` and makes the exit code non-zero.

The reference tool's spirit is kept (big buffer, fixed iteration
count, throughput = bytes/elapsed —
src/test/erasure-code/ceph_erasure_code_benchmark.cc:185-192).

No number this file printed has been taken on a chip with the current
kernels; a `benchmark` PR replaces it with BENCHMARK.json cells.
"""

from __future__ import annotations

import contextlib
import json
import sys
import time
import traceback

import numpy as np

K, M = 8, 4
CHUNK = 1 << 20          # 1 MiB per shard
BATCH = 8                # stripes per dispatch -> 64 MiB input per iter
TARGET_GBPS = 25.0
LAT_CHUNK = 1 << 16      # 64 KiB single-chunk reconstruct latency probe

#: published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
#: A device that is not here is an error, never a default.
PUBLISHED_PEAKS = {
    "TPU v5 lite": {
        "hbm_gbps": 819.0,
        "int8_tops": 393.0,
        "bf16_tflops": 197.0,
        "source": "Google Cloud documentation, \"TPU v5e\" (system "
                  "architecture table: per-chip HBM bandwidth and peak "
                  "compute)",
    },
}


def published_peaks(device_kind: str) -> dict:
    peaks = PUBLISHED_PEAKS.get(device_kind)
    if peaks is None:
        raise RuntimeError(
            f"no published peaks for device_kind {device_kind!r} "
            f"(know {sorted(PUBLISHED_PEAKS)}); add its row with a source"
        )
    return peaks


@contextlib.contextmanager
def _phase(result: dict, name: str):
    """One named phase: wall time on stderr (stdout carries only the
    one JSON line), and a phase that raises is recorded under
    ``failed_phases`` — the run goes on to report every failure, and
    ``main`` exits non-zero if there is one."""
    t0 = time.perf_counter()
    try:
        yield
    except Exception as e:
        traceback.print_exc(file=sys.stderr)
        result.setdefault("failed_phases", {})[name] = (
            f"{type(e).__name__}: {e}"[:400]
        )
    finally:
        print(
            f"[bench] {name}: {time.perf_counter() - t0:.1f}s",
            file=sys.stderr, flush=True,
        )


def _timed(fn, *args) -> float:
    t0 = time.perf_counter()
    np.asarray(fn(*args))  # the readback waits for the device
    return time.perf_counter() - t0


#: target kernel-time span between the two iteration counts: the
#: differenced quantity must dwarf host-clock jitter, so spans
#: auto-scale to ~this much on-device time regardless of
#: per-iteration cost
SPAN_TARGET_S = 0.45
SPAN_MAX_ITERS = 40000


def _loop_stats(loop, data, n1=None, n2=None, reps=4):
    """(per_iter_seconds, iqr_seconds) via diff-of-minima + paired
    diffs. ``loop(data, iters)`` must be feedback-structured.

    Iteration counts auto-scale: a fixed n2=110 makes the differenced
    span ~20 ms for fast kernels — below the host clock's jitter
    floor, which round-4 bench entries (and an early r5 run that
    printed a 960 GB/s "decode") show produces pure noise. A rough
    warm-run estimate picks n2 so the span is ~SPAN_TARGET_S of real
    kernel time; explicit n1/n2 skip the estimate."""
    if n2 is None:
        # iterative doubling with a MEASURED stop condition: a span
        # estimate derived from two jitter-contaminated samples can be
        # off by orders of magnitude (an early r5 run picked 40000
        # iterations for a 200 us kernel and burned 80 s per metric);
        # doubling stops when the wall-time delta itself clears the
        # target, so the pick is right regardless of jitter. The
        # probe ladder doubles as the warm-up (iters is a traced
        # argument — one compile serves every count).
        base = min(_timed(loop, data, 1) for _ in range(2))
        n2 = 60
        while n2 < SPAN_MAX_ITERS:
            if _timed(loop, data, n2) - base >= SPAN_TARGET_S:
                break
            n2 *= 2
        n2 = min(n2, SPAN_MAX_ITERS)
        n1 = max(1, n2 // 10)
    else:
        for t in (n1, n2):
            _timed(loop, data, t)  # warm/compile
    t1s = [_timed(loop, data, n1) for _ in range(reps)]
    t2s = [_timed(loop, data, n2) for _ in range(reps)]
    per = (min(t2s) - min(t1s)) / (n2 - n1)
    if per <= 0:
        raise RuntimeError("non-positive differenced timing")
    pairs = [
        (b - a) / (n2 - n1) for a, b in zip(sorted(t1s), sorted(t2s))
    ]
    pairs = [p for p in pairs if p > 0]
    if len(pairs) >= 3:
        iqr = float(
            np.percentile(pairs, 75) - np.percentile(pairs, 25)
        )
    else:
        iqr = 0.0
    return per, iqr


def _feedback_loop(apply, opaque: bool):
    """Build the standard feedback loop over [B, C, N] uint8 data:
    out -> 128-byte fold -> patches next input. Opaque (Pallas)
    applies fold a slice (XLA cannot slice through the custom call);
    plain-XLA applies fold the full output via sum, or XLA dead-codes
    the unread majority of the work."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def loop(d0, iters):
        def body(i, carry):
            d, acc = carry
            out = apply(d)
            if opaque:
                fold = jax.lax.dynamic_slice(
                    out, (0,) * (out.ndim - 1) + (0,),
                    (1,) * (out.ndim - 1) + (128,),
                )
                patch = fold.reshape(1, 1, 128) ^ jnp.uint8(i + 1)
                scalar = fold.reshape(-1)[0]
            else:
                scalar = jnp.sum(out, dtype=jnp.uint8)
                patch = jnp.full((1, 1, 128), scalar, jnp.uint8) ^ jnp.uint8(
                    i + 1
                )
            d = jax.lax.dynamic_update_slice(d, patch, (0, 0, 0))
            return d, acc ^ scalar

        _, acc = jax.lax.fori_loop(0, iters, body, (d0, jnp.uint8(0)))
        return acc

    return loop


def _device_loop_gbps(apply, data, reps=4, opaque=True):
    """(GB/s data-in, iqr GB/s) for `apply` over [B, C, N] uint8."""
    batch, k, n = data.shape
    loop = _feedback_loop(apply, opaque)
    per, iqr = _loop_stats(loop, data, reps=reps)
    gbps = batch * k * n / per / 1e9
    return gbps, gbps - batch * k * n / (per + iqr) / 1e9


def _kernel_apply(bmat_np):
    """Device-path bitmatrix apply: the Pallas kernel, compiled
    (``main`` has already refused any backend but a TPU)."""
    from ceph_tpu.ops import pallas_encode as pe

    return lambda d: pe.gf_encode_bitplane_pallas(
        bmat_np, d, interpret=False
    )


def _device_rand(shape, seed: int):
    """Benchmark data generated ON DEVICE (jax PRNG + cast): the
    kernels' cost is data-independent, so device PRNG bytes are
    equivalent and skip a 64-340 MB host upload per working set."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed)
    return jax.random.randint(
        key, shape, 0, 256, dtype=jnp.int32
    ).astype(jnp.uint8)


def _measure_roofline(result: dict) -> float:
    """Pure-copy (xor-1) Pallas kernel over 128 MB: the achievable
    HBM read+write rate this run, the denominator for roofline
    fractions. 2D [rows, lanes] layout — the sublane dimension stays
    dense, so no tile padding confounds the number."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    # 117 MB in 3.7 MB blocks over few grid steps: big blocks keep
    # per-step overhead out of the denominator
    rows, lanes, sb = 512, 229376, 16

    def kernel(d_ref, o_ref):
        o_ref[:] = d_ref[:] ^ jnp.uint8(1)

    def copy(x):
        return pl.pallas_call(
            kernel,
            grid=(rows // sb,),
            in_specs=[pl.BlockSpec((sb, lanes), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((sb, lanes), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(x.shape, jnp.uint8),
        )(x)

    @jax.jit
    def loop(d0, iters):
        def body(i, carry):
            d, acc = carry
            out = copy(d)
            fold = jax.lax.dynamic_slice(out, (0, 0), (1, 128))
            d = jax.lax.dynamic_update_slice(
                d, fold ^ jnp.uint8(i + 1), (0, 0)
            )
            return d, acc ^ fold[0, 0]

        _, acc = jax.lax.fori_loop(
            0, iters, body, (d0, jnp.uint8(0))
        )
        return acc

    data = _device_rand((rows, lanes), 0)
    per, _ = _loop_stats(loop, data)
    gbps = 2 * rows * lanes / per / 1e9  # read + write
    result["hbm_copy_gbps"] = round(gbps, 1)
    return gbps


def _measure_device_path(
    result: dict, roofline: "float | None", peaks: dict
) -> float:
    import jax.numpy as jnp

    from ceph_tpu.gf import (
        decode_matrix,
        gf_matrix_to_bitmatrix,
        vandermonde_rs_matrix,
    )

    g = vandermonde_rs_matrix(K, M)
    enc_bmat_np = gf_matrix_to_bitmatrix(g[K:, :])

    # Decode config: lose data shards 4-7, survive on 0-3 + all parity
    # (a full-m erasure needing true matrix reconstruct).
    present = [0, 1, 2, 3, 8, 9, 10, 11]
    want = [4, 5, 6, 7]
    dmat = decode_matrix(g, K, present)
    dec_rows = np.stack([dmat[w, :] for w in want])
    dec_bmat_np = gf_matrix_to_bitmatrix(dec_rows)

    data = _device_rand((BATCH, K, CHUNK), 0)

    enc_gbps, enc_iqr = _device_loop_gbps(_kernel_apply(enc_bmat_np), data)
    dec_gbps, dec_iqr = _device_loop_gbps(_kernel_apply(dec_bmat_np), data)

    # single-row reconstruct: the honest "naive repair" comparator
    # for the CLAY metric — rebuilding ONE lost chunk needs a 1-row
    # decode, which is far cheaper per input byte than the full-m
    # reconstruct above (MACs scale with output rows)
    dec1_bmat_np = gf_matrix_to_bitmatrix(dmat[4:5, :])
    dec1_gbps, _ = _device_loop_gbps(
        _kernel_apply(dec1_bmat_np), data, reps=3
    )

    enc_s = BATCH * K * CHUNK / enc_gbps / 1e9
    hbm_gbps = (BATCH * (K + M) * CHUNK) / enc_s / 1e9

    result["value_iqr"] = round(enc_iqr, 2)
    result["decode_gbps"] = round(dec_gbps, 2)
    result["decode_iqr"] = round(dec_iqr, 2)
    result["decode1_gbps"] = round(dec1_gbps, 2)
    result["hbm_gbps"] = round(hbm_gbps, 1)
    if roofline:  # the roofline phase reports its own failure
        result["hbm_roofline_frac"] = round(hbm_gbps / roofline, 3)
    # The flagship kernel is COMPUTE-bound, not HBM-bound: the
    # bit-plane formulation streams [8R, 8F] int8 matmuls (F = K +
    # pad-to-4). MAC accounting comes from the kernel's own packing
    # rule (ops.pallas_encode.mac_stats): 256 MACs per data byte at
    # (8,4) — HALF the round-5 count, whose s=2 block-diagonal stripe
    # pair clocked 512 with every other MAC a structural zero.
    # mxu_util_frac is the achieved rate against the chip's published
    # int8 peak (PUBLISHED_PEAKS); mxu_useful_util_frac discounts the pad
    # columns — the only structural zeros the zero-waste layout has
    # left (identical to mxu_util_frac for the flagship, where
    # K % 4 == 0 means no pad at all).
    from ceph_tpu.ops.pallas_encode import mac_stats

    stats = mac_stats(K, M)
    mxu_tops = 2 * stats["macs_per_byte"] * enc_gbps / 1e3  # TOPS
    result["mxu_tops"] = round(mxu_tops, 1)
    result["mxu_util_frac"] = round(mxu_tops / peaks["int8_tops"], 3)
    result["mxu_useful_util_frac"] = round(
        mxu_tops * stats["useful_frac"] / peaks["int8_tops"], 3
    )
    return enc_gbps


def _measure_baseline_configs(result: dict) -> None:
    """BASELINE configs 1-3 + the ISA envelope max: per-plugin encode
    throughput with the config's exact geometry. Stripe counts sized
    so every working set streams >= 64 MB (methodology note 2)."""
    import jax.numpy as jnp

    from ceph_tpu.gf import (
        cauchy_good_matrix,
        gf_matrix_to_bitmatrix,
        isa_rs_matrix,
        vandermonde_rs_matrix,
    )

    configs = [
        # (result key, generator matrix, k, m, chunk bytes, stripes)
        ("jerasure_k4m2_4k_gbps", vandermonde_rs_matrix(4, 2), 4, 2,
         4096, 4096),
        ("isa_k8m3_64k_gbps", isa_rs_matrix(8, 3), 8, 3, 8192, 1024),
        # 100 KiB chunks as in BASELINE config 3, but 256 stripes
        # (262 MB/iter): honest per-iteration timing makes the old
        # 1 GiB set cost ~7 ms/iter of pure wall time for no extra
        # signal — still 16x VMEM
        ("cauchy_k10m4_1m_gbps", cauchy_good_matrix(10, 4), 10, 4,
         102400, 256),
        # the ISA-L documented envelope max (isa/README:23-24)
        ("isa_k21m4_gbps", isa_rs_matrix(21, 4), 21, 4, 65536, 256),
    ]
    for key, gmat, k, m, chunk, stripes in configs:
        with _phase(result, f"baseline_configs.{key}"):
            bmat = gf_matrix_to_bitmatrix(np.asarray(gmat)[k:, :])
            data = _device_rand((stripes, k, chunk), 7)
            gbps, iqr = _device_loop_gbps(
                _kernel_apply(bmat), data, reps=3
            )
            result[key] = round(gbps, 2)
            result[key + "_iqr"] = round(iqr, 2)


def _measure_code_families(result: dict) -> None:
    """Family-level device throughput for the packet bit-matrix codes
    and LRC/SHEC, through the REAL codec dispatch path — registry
    factory, route selection, schedule/MXU kernels — not a bare
    matmul. The packet families use the shards form: per-shard arrays
    in, per-shard parity out (stacking the output back into one
    tensor is a relayout copy the real pipeline never performs, so
    the fold XORs 128-byte slices of each parity shard instead).

    Budget trim (round 9, the checksums-trim discipline): ONE warmed
    device buffer is sliced+reshaped into every family's shard set,
    stripe counts are equalized so each working set streams 64-76 MB
    (r5 ran up to 300 MB/iter for no extra signal), and the
    iteration-count ladder runs once on the first family with its
    counts reused everywhere (near-identical bytes/iter).  The old
    per-family ladder + fresh buffers cost the phase 269.5 s in r5."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.codecs import registry

    families = [
        # (result key, plugin, profile, chunk bytes, stripes) —
        # stripes sized so k*stripes*chunk streams >= 64 MB (note 2)
        # while every family lands within ~12% of the same bytes/iter
        ("liberation_k4m2_gbps", "jerasure",
         {"technique": "liberation", "k": "4", "m": "2", "w": "7"},
         7 * 16384, 160),
        ("blaum_roth_k4m2_gbps", "jerasure",
         {"technique": "blaum_roth", "k": "4", "m": "2", "w": "6"},
         6 * 16384, 192),
        ("liber8tion_k4m2_gbps", "jerasure",
         {"technique": "liber8tion", "k": "4", "m": "2", "w": "8"},
         8 * 16384, 128),
        ("lrc_k4m2l3_gbps", "lrc",
         {"k": "4", "m": "2", "l": "3"}, 65536, 256),
        ("shec_k4m3c2_gbps", "shec",
         {"k": "4", "m": "3", "c": "2"}, 65536, 256),
    ]
    total = max(
        int(p["k"]) * stripes * chunk
        for _key, _pl, p, chunk, stripes in families
    )
    flat = _device_rand((total,), 11)
    counts = {"n1": None, "n2": None}
    for key, plugin, profile, chunk, stripes in families:
        with _phase(result, f"code_families.{key}"):
            codec = registry.factory(plugin, dict(profile))
            k = codec.k

            def apply_dict(shards, codec=codec, k=k):
                parity = codec.encode_chunks(
                    {i: shards[i] for i in range(k)}
                )
                return [parity[j] for j in sorted(parity)]

            sz = stripes * chunk
            shards0 = tuple(
                flat[i * sz : (i + 1) * sz].reshape(stripes, chunk)
                for i in range(k)
            )

            @jax.jit
            def loop(arrs, iters, apply_dict=apply_dict):
                def body(i, carry):
                    arrs, acc = carry
                    outs = apply_dict(arrs)
                    fold = jax.lax.dynamic_slice(
                        outs[0], (0, 0), (1, 128)
                    )
                    scalar = fold[0, 0]
                    for o in outs[1:]:
                        scalar = scalar ^ o[0, 0]
                    first = jax.lax.dynamic_update_slice(
                        arrs[0], fold ^ jnp.uint8(i + 1), (0, 0)
                    )
                    return (first,) + arrs[1:], acc ^ scalar

                _, acc = jax.lax.fori_loop(
                    0, iters, body, (arrs, jnp.uint8(0))
                )
                return acc

            nbytes = stripes * k * chunk
            if counts["n2"] is None:
                per, iqr = _loop_stats(loop, shards0, reps=3)
                counts["n2"] = max(
                    60, int(SPAN_TARGET_S / max(per, 1e-6))
                )
                counts["n1"] = max(1, counts["n2"] // 10)
            else:
                per, iqr = _loop_stats(
                    loop, shards0, n1=counts["n1"], n2=counts["n2"],
                    reps=3,
                )
            result[key] = round(nbytes / per / 1e9, 2)
            result[key + "_iqr"] = round(
                nbytes / per / 1e9 - nbytes / (per + iqr) / 1e9, 2
            )


def _measure_sched_superopt(result: dict) -> None:
    """Round-11 phase: the XOR-schedule superoptimizer scorecard.

    Host rows (device-free): per packet family at the bench geometry,
    the raw ones count, selection-form XOR count, post-CSE op count
    and saving fraction (``xor_schedule.cse_stats``) — the numbers the
    tier-1 golden pins assert, recorded next to the measured rates.

    Device rows:
    - ``sched_unopt_liberation_gbps``: the liberation encode
      re-measured with ``ec_sched_opt=false`` — the within-run A/B leg
      against ``liberation_k4m2_gbps`` (code-families phase, optimizer
      on). Same geometry, same session: the pair isolates the CSE'd
      multi-level schedule's effect on the dispatch ceiling.
    - ``lrc_local_repair_gbps``: single-lost-chunk repair on the
      xor-local-parity LRC profile (k=4 m=2 l=3, 64 KiB chunks),
      survivor-bytes-in basis — the locality story's on-device rate:
      3 survivor chunks read instead of k, through the schedule
      engine's w=1 route (BASELINE `lrc_*_gbps >= 200` row).
    """
    import jax
    import jax.numpy as jnp

    from ceph_tpu.codecs.registry import registry
    from ceph_tpu.ops import xor_schedule
    from ceph_tpu.utils import config

    fam_profiles = [
        ("liberation", {"technique": "liberation", "k": "4", "m": "2",
                        "w": "7"}),
        ("blaum_roth", {"technique": "blaum_roth", "k": "4", "m": "2",
                        "w": "6"}),
        ("liber8tion", {"technique": "liber8tion", "k": "4", "m": "2",
                        "w": "8"}),
    ]
    for fam, profile in fam_profiles:
        with _phase(result, f"sched_superopt.cse_stats.{fam}"):
            codec = registry.factory("jerasure", dict(profile))
            st = xor_schedule.cse_stats(codec.coding_bitmatrix)
            result[f"{fam}_sched_raw_xors"] = st["raw_xors"]
            result[f"{fam}_sched_opt_xors"] = st["opt_xors"]
            result[f"{fam}_sched_cse_saving"] = st["saving_frac"]

    def encode_loop_gbps(codec, k, chunk, stripes, seed):
        sz = stripes * chunk
        flat = _device_rand((k * sz,), seed)
        shards = tuple(
            flat[i * sz : (i + 1) * sz].reshape(stripes, chunk)
            for i in range(k)
        )

        @jax.jit
        def loop(arrs, iters):
            def body(i, carry):
                arrs, acc = carry
                parity = codec.encode_chunks(
                    {j: arrs[j] for j in range(k)}
                )
                outs = [parity[j] for j in sorted(parity)]
                fold = jax.lax.dynamic_slice(outs[0], (0, 0), (1, 128))
                scalar = fold[0, 0]
                for o in outs[1:]:
                    scalar = scalar ^ o[0, 0]
                first = jax.lax.dynamic_update_slice(
                    arrs[0], fold ^ jnp.uint8(i + 1), (0, 0)
                )
                return (first,) + arrs[1:], acc ^ scalar

            _, acc = jax.lax.fori_loop(
                0, iters, body, (arrs, jnp.uint8(0))
            )
            return acc

        per, iqr = _loop_stats(loop, shards, reps=3)
        g = stripes * k * chunk / per / 1e9
        return g, g - stripes * k * chunk / (per + iqr) / 1e9

    # A/B leg: liberation encode on the PINNED selection-form
    # schedule (the escape hatch) — trace under the override so the
    # route decision compiles with the optimizer off
    with _phase(result, "sched_superopt.unopt_liberation"):
        with config.override(ec_sched_opt=False):
            codec = registry.factory(
                "jerasure", dict(fam_profiles[0][1])
            )
            g, iqr = encode_loop_gbps(codec, 4, 7 * 16384, 160, 21)
        result["sched_unopt_liberation_gbps"] = round(g, 2)
        result["sched_unopt_liberation_iqr"] = round(iqr, 2)

    # LRC local repair: one lost data chunk, minimum survivors only
    # (3 chunks of the local group), xor local parity -> schedule
    # route on TPU
    with _phase(result, "sched_superopt.lrc_local_repair"):
        codec = registry.factory(
            "lrc",
            {"k": "4", "m": "2", "l": "3", "local_parity": "xor"},
        )
        chunk, stripes, lost = 65536, 256, 0
        plan = codec.minimum_to_decode(
            {lost}, set(range(codec.k + codec.m)) - {lost}
        )
        keys = sorted(plan)
        sz = stripes * chunk
        flat = _device_rand((len(keys) * sz,), 23)
        arrs0 = tuple(
            flat[i * sz : (i + 1) * sz].reshape(stripes, chunk)
            for i in range(len(keys))
        )

        @jax.jit
        def rloop(arrs, iters):
            def body(i, carry):
                arrs, acc = carry
                out = codec.decode_chunks(
                    {lost}, dict(zip(keys, arrs))
                )[lost]
                fold = jax.lax.dynamic_slice(out, (0, 0), (1, 128))
                first = jax.lax.dynamic_update_slice(
                    arrs[0], fold ^ jnp.uint8(i + 1), (0, 0)
                )
                return (first,) + arrs[1:], acc ^ fold[0, 0]

            _, acc = jax.lax.fori_loop(
                0, iters, body, (arrs, jnp.uint8(0))
            )
            return acc

        nbytes = len(keys) * sz  # survivor bytes read per repair
        per, iqr = _loop_stats(rloop, arrs0, reps=3)
        g = nbytes / per / 1e9
        result["lrc_local_repair_gbps"] = round(g, 2)
        result["lrc_local_repair_iqr"] = round(
            g - nbytes / (per + iqr) / 1e9, 2
        )
        result["lrc_local_repair_survivors"] = len(keys)


def _measure_clay_repair(result: dict) -> None:
    """BASELINE config 4 + the general-d envelope: CLAY single-chunk
    repair, helper bytes read per second, device loop with feedback —
    per geometry.  ``clay_repair_*`` is the aloof-free flagship
    (8,4,d=11); ``clay_repair_aloof_*`` the (8,4,d=10) profile whose
    one aloof node exercises the B1/B2 kernel split and per-score-
    group decodes (round 9 — previously that geometry fell back to
    the itemized XLA path at ~20 GB/s).  Each geometry reports
    ``*_time_vs_naive`` against the 1-row reconstruct comparator
    (decode1_gbps); target < 1.0 — MSR repair winning on-chip TIME,
    not just the 0.344x byte ratio."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.codecs.registry import registry

    geometries = [
        ("clay_repair", {"k": "8", "m": "4", "d": "11"}),
        ("clay_repair_aloof", {"k": "8", "m": "4", "d": "10"}),
    ]
    counts: dict = {"n1": None, "n2": None}
    for key, profile in geometries:
        with _phase(result, f"clay_repair.{key}"):
            codec = registry.factory("clay", profile)
            k, m, d = codec.k, codec.m, codec.d
            n = k + m
            sub = codec.get_sub_chunk_count()
            chunk = codec.get_chunk_size(k << 16)  # 64 KiB chunks
            sc = chunk // sub
            stripes = 256
            lost = k + 1  # a parity chunk: full helper-plane read path

            plan = codec.minimum_to_decode(
                {lost}, set(range(n)) - {lost}
            )
            # helper bytes generated ON DEVICE: repair cost is
            # data-independent, and correctness is covered by the
            # test suite + dryrun — the bench only times the plane
            # program, not a host-side encode of a 128 MB codeword
            helper, read = {}, 0
            for hseed, (node, ranges) in enumerate(sorted(plan.items())):
                nbytes = sum(cnt for _idx, cnt in ranges) * sc
                read += stripes * nbytes
                helper[node] = _device_rand(
                    (stripes, nbytes), 100 + hseed
                )
            keys = sorted(helper)

            @jax.jit
            def loop(arrs, iters, codec=codec, keys=keys, lost=lost):
                def body(i, carry):
                    arrs, acc = carry
                    out = codec.repair(
                        {lost}, dict(zip(keys, arrs))
                    )[lost]
                    fold = jax.lax.dynamic_slice(out, (0, 0), (1, 128))
                    first = jax.lax.dynamic_update_slice(
                        arrs[0], fold ^ jnp.uint8(i + 1), (0, 0)
                    )
                    return (first,) + arrs[1:], acc + jnp.sum(
                        fold, dtype=jnp.uint32
                    )

                _, acc = jax.lax.fori_loop(
                    0, iters, body, (arrs, jnp.uint32(0))
                )
                return acc

            arrs = tuple(helper[kk] for kk in keys)
            if counts["n2"] is None:
                per, iqr = _loop_stats(loop, arrs, reps=3)
                # reuse the flagship's auto-scaled span for the other
                # geometries (bytes/iter within ~10%, checksums-trim
                # discipline) — the doubling ladder runs once
                counts["n2"] = max(
                    60, int(SPAN_TARGET_S / max(per, 1e-6))
                )
                counts["n1"] = max(1, counts["n2"] // 10)
            else:
                per, iqr = _loop_stats(
                    loop, arrs, n1=counts["n1"], n2=counts["n2"],
                    reps=3,
                )
            gbps = read / per / 1e9
            result[f"{key}_gbps"] = round(gbps, 2)
            result[f"{key}_iqr"] = round(
                gbps - read / (per + iqr) / 1e9, 2
            )
            # The hardware-independent MSR story: helper bytes read
            # as a fraction of the k*chunk a naive decode would read.
            result[f"{key}_read_frac"] = round(
                read / (k * chunk * stripes), 3
            )
            dec1 = result.get("decode1_gbps")
            if dec1:
                naive_s = k * chunk * stripes / (dec1 * 1e9)
                result[f"{key}_time_vs_naive"] = round(
                    per / naive_s, 2
                )


def _measure_smallop_dispatch(result: dict) -> None:
    """Small-op (64 KiB = 8 x 8 KiB) encode throughput: the per-op
    device path vs the native-ring streaming dispatcher aggregating 16
    concurrent writers (pipeline/dispatcher.py). Latency-class metric
    on the host clock."""
    import threading

    import jax.numpy as jnp

    from ceph_tpu import native
    from ceph_tpu.codecs.registry import registry
    from ceph_tpu.pipeline.dispatcher import StreamingDispatcher

    if not native.available():
        raise RuntimeError("native tier unavailable: no staging ring")
    codec = registry.factory("isa", {"k": str(K), "m": str(M)})
    k, chunk = K, 8192
    rng = np.random.default_rng(5)

    ops = [
        jnp.asarray(rng.integers(0, 256, (k, chunk), np.uint8))
        for _ in range(16)
    ]
    for o in ops[:2]:  # warm/compile
        p = codec.encode_chunks({i: o[i] for i in range(k)})
        np.asarray(p[k])
    t0 = time.perf_counter()
    for o in ops:
        p = codec.encode_chunks({i: o[i] for i in range(k)})
        np.asarray(p[k])
    perop_s = (time.perf_counter() - t0) / len(ops)
    perop_gbps = k * chunk / perop_s / 1e9

    disp = StreamingDispatcher(codec, window_s=0.002)
    try:
        datas = rng.integers(
            0, 256, (16, k, chunk), np.uint8
        )
        lat: list[float] = []
        lat_lock = threading.Lock()

        def worker(i):
            for _ in range(24):
                t1 = time.perf_counter()
                disp.encode_sync(datas[i])
                dt = time.perf_counter() - t1
                with lat_lock:
                    lat.append(dt)

        disp.encode_sync(datas[0])  # warm the batched shape
        threads = [
            threading.Thread(target=worker, args=(i,))
            for i in range(16)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
    finally:
        disp.stop()
    total_bytes = 16 * 24 * k * chunk
    stream_gbps = total_bytes / wall / 1e9
    result["smallop_perop_gbps"] = round(perop_gbps, 4)
    result["smallop_stream_gbps"] = round(stream_gbps, 4)
    result["smallop_speedup"] = round(stream_gbps / perop_gbps, 1)
    lat_ms = np.array(lat) * 1e3
    result["smallop_p99_ms"] = round(
        float(np.percentile(lat_ms, 99)), 2
    )
    # device-clock row: host p99 with the constant floor (dispatch
    # overhead, pinned by the fastest op) replaced by the trip-count-
    # differenced device op time (see loadgen.recorder.DeviceClock)
    from ceph_tpu.loadgen.recorder import DeviceClock

    dev_s = DeviceClock.measure(codec, chunk)
    result["smallop_p99_device_ms"] = round(
        float(np.percentile(lat_ms, 99))
        - float(lat_ms.min()) + dev_s * 1e3, 3
    )


def _measure_single_core(result: dict, enc_gbps: float) -> None:
    """Native C single-core GF encode — the ISA-L-role CPU baseline
    (BASELINE.md target: >= 10x). Same k/m, 1 MiB chunks."""
    from ceph_tpu import native
    from ceph_tpu.gf import vandermonde_rs_matrix

    if not native.available():
        raise RuntimeError("native tier unavailable: no CPU baseline")
    g = vandermonde_rs_matrix(K, M)
    coding = np.ascontiguousarray(g[K:, :])
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, (K, CHUNK), np.uint8)
    native.gf_matrix_encode(coding, data)  # warm
    iters, t0 = 8, time.perf_counter()
    for _ in range(iters):
        native.gf_matrix_encode(coding, data)
    dt = (time.perf_counter() - t0) / iters
    cpu_gbps = K * CHUNK / dt / 1e9
    result["single_core_gbps"] = round(cpu_gbps, 3)
    result["vs_single_core"] = round(enc_gbps / cpu_gbps, 1)


def _measure_reconstruct_latency(result: dict) -> None:
    """p50/p99 single-chunk reconstruct on the host small-op path —
    true per-op wall time: numpy in, numpy out, no device round
    trip."""
    from ceph_tpu.codecs.registry import registry

    codec = registry.factory("isa", {"k": str(K), "m": str(M)})
    rng = np.random.default_rng(2)
    data = {i: rng.integers(0, 256, (LAT_CHUNK,), np.uint8) for i in range(K)}
    parity = codec.encode_chunks(data)
    chunks = {**data, **parity}
    del chunks[5]  # one lost data shard, the common repair case
    lat = []
    for _ in range(200):
        t0 = time.perf_counter()
        out = codec.decode_chunks({5}, chunks)
        np.asarray(out[5])
        lat.append(time.perf_counter() - t0)
    lat_ms = np.array(lat) * 1e3
    result["reconstruct_p50_ms"] = round(float(np.percentile(lat_ms, 50)), 3)
    result["reconstruct_p99_ms"] = round(float(np.percentile(lat_ms, 99)), 3)


def _measure_checksums(result: dict) -> None:
    """BASELINE config 5 (CRC32C over 4/16/64 KiB) + xxhash32/64.
    Feedback form: the per-block hash vector's first lanes patch the
    next input; the accumulator folds the full hash vector (the hash
    path is partly plain XLA — a sliced consumer would let XLA
    dead-code most blocks).

    Budget trim (round 7): ONE warmed 32 MB device buffer is reshaped
    for every block size (the kernels are data-independent, and 32 MB
    still streams 2x VMEM), the iteration-count ladder runs once on
    the first config and its counts are reused everywhere (identical
    bytes/iter => near-identical per-iter time), and reps drop to 3.
    The old per-key ladder + fresh 64 MB buffers cost the section
    ~225 s."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.checksum.crc32c import crc32c_device

    size = 32 << 20
    flat = _device_rand((size,), 3)
    counts = {"n1": None, "n2": None}

    def hash_loop_gbps(hash_fn, blocks, reps=3):
        nblocks, block = blocks.shape

        @jax.jit
        def loop(b0, iters):
            def body(i, carry):
                b, acc = carry
                h = hash_fn(b)  # [nblocks] uint32
                s = jnp.sum(h, dtype=jnp.uint32)
                patch = (
                    jax.lax.dynamic_slice(h, (0,), (32,))
                    .astype(jnp.uint8)
                    .reshape(1, 32)
                    ^ jnp.uint8(i + 1)
                )
                b = jax.lax.dynamic_update_slice(b, patch, (0, 0))
                return b, acc + s

            _, acc = jax.lax.fori_loop(
                0, iters, body, (b0, jnp.uint32(0))
            )
            return acc

        if counts["n2"] is None:
            per, iqr = _loop_stats(loop, blocks, reps=reps)
            # reuse this config's auto-scaled span for the rest of the
            # section: every config streams the same bytes per iter
            base = min(_timed(loop, blocks, 1) for _ in range(2))
            n2 = max(60, int(SPAN_TARGET_S / max(per, 1e-6)))
            counts["n1"], counts["n2"] = max(1, n2 // 10), n2
        else:
            per, iqr = _loop_stats(
                loop, blocks, n1=counts["n1"], n2=counts["n2"],
                reps=reps,
            )
        g = nblocks * block / per / 1e9
        return g, g - nblocks * block / (per + iqr) / 1e9

    for key, block in (
        ("crc32c_gbps", 4096),
        ("crc32c_16k_gbps", 16384),
        ("crc32c_64k_gbps", 65536),
    ):
        with _phase(result, f"checksums.{key}"):
            blocks = flat.reshape(size // block, block)
            g, iqr = hash_loop_gbps(
                lambda b: crc32c_device(b, 0xFFFFFFFF), blocks
            )
            result[key] = round(g, 1)
            result[key + "_iqr"] = round(iqr, 1)
    with _phase(result, "checksums.xxhash"):
        from ceph_tpu.checksum.xxhash import xxh32_device, xxh64_device

        blocks = flat.reshape(size // 4096, 4096)
        g, iqr = hash_loop_gbps(lambda b: xxh32_device(b), blocks)
        result["xxhash32_gbps"] = round(g, 1)
        result["xxhash32_iqr"] = round(iqr, 1)

        def xx64(b):
            h = xxh64_device(b)
            return (h[0] ^ h[1]).astype(jnp.uint32) if isinstance(
                h, tuple
            ) else h.astype(jnp.uint32)

        g, iqr = hash_loop_gbps(xx64, blocks)
        result["xxhash64_gbps"] = round(g, 1)
        result["xxhash64_iqr"] = round(iqr, 1)


def _measure_fused_write_path(result: dict, enc_gbps: float) -> None:
    """Tentpole metric (round 7): the whole write path's device cost —
    parity AND per-4K-block crc32c for all k+m shards — three ways:

    - ``fused_write_path_gbps``: the fused encode+csum kernel, ONE
      pass over the data while it is resident for the encode matmul;
    - ``write_path_sep_gbps``: the plain encode kernel followed by a
      separate ``crc32c_device`` pass over data + parity (re-reads
      every byte encode just wrote — the extra HBM pass fusion kills);
    - ``write_path_host_gbps``: device encode + HOST csum, composed
      analytically from a 4 MB host-hash sample (hashing 96 MB/iter
      on the host directly would burn minutes for a number whose
      magnitude is not in doubt).

    ``fused_vs_sep`` is the headline ratio (acceptance: >= 1.3x)."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.checksum.crc32c import crc32c_device
    from ceph_tpu.gf import (
        gf_matrix_to_bitmatrix,
        vandermonde_rs_matrix,
    )
    from ceph_tpu.ops import pallas_encode as pe

    cb = 4096
    g = vandermonde_rs_matrix(K, M)
    bmat = gf_matrix_to_bitmatrix(g[K:, :])
    data = _device_rand((BATCH, K, CHUNK), 9)
    nbytes = BATCH * K * CHUNK

    def csum_feedback(p, cs, d, i):
        # fold BOTH outputs into the next input: iterations are
        # serially dependent through parity AND csums, so neither
        # leg can be elided/overlapped (methodology note 1)
        fold = jax.lax.dynamic_slice(p, (0, 0, 0), (1, 1, 128))
        cfold = jnp.tile(
            jax.lax.dynamic_slice(
                cs, (0, 0, 0), (1, 1, 32)
            ).astype(jnp.uint8),
            (1, 1, 4),
        )
        patch = fold ^ cfold ^ jnp.uint8(i + 1)
        d = jax.lax.dynamic_update_slice(d, patch, (0, 0, 0))
        return d, fold.reshape(-1)[0] ^ cfold.reshape(-1)[0]

    @jax.jit
    def loop_fused(d0, iters):
        def body(i, carry):
            d, acc = carry
            p, cs = pe.gf_encode_csum_bitplane_pallas(bmat, d, cb)
            d, scalar = csum_feedback(p, cs, d, i)
            return d, acc ^ scalar

        _, acc = jax.lax.fori_loop(
            0, iters, body, (d0, jnp.uint8(0))
        )
        return acc

    @jax.jit
    def loop_sep(d0, iters):
        def body(i, carry):
            d, acc = carry
            p = pe.gf_encode_bitplane_pallas(bmat, d)
            cs_d = crc32c_device(
                d.reshape(BATCH, K, CHUNK // cb, cb), 0
            )
            cs_p = crc32c_device(
                p.reshape(BATCH, M, CHUNK // cb, cb), 0
            )
            cs = jnp.concatenate([cs_d, cs_p], axis=1)
            d, scalar = csum_feedback(p, cs, d, i)
            return d, acc ^ scalar

        _, acc = jax.lax.fori_loop(
            0, iters, body, (d0, jnp.uint8(0))
        )
        return acc

    per_f, iqr_f = _loop_stats(loop_fused, data, reps=3)
    per_s, _ = _loop_stats(loop_sep, data, reps=3)
    fused_gbps = nbytes / per_f / 1e9
    result["fused_write_path_gbps"] = round(fused_gbps, 2)
    result["fused_write_path_iqr"] = round(
        fused_gbps - nbytes / (per_f + iqr_f) / 1e9, 2
    )
    result["write_path_sep_gbps"] = round(nbytes / per_s / 1e9, 2)
    result["fused_vs_sep"] = round(per_s / per_f, 2)

    # host-csum comparator: sample the host scalar rate, compose
    from ceph_tpu.checksum import crc32c_scalar

    sample = np.random.default_rng(10).integers(
        0, 256, 4 << 20, np.uint8
    ).tobytes()
    crc32c_scalar(0xFFFFFFFF, sample[:cb])  # warm native load
    t0 = time.perf_counter()
    for off in range(0, len(sample), cb):
        crc32c_scalar(0xFFFFFFFF, sample[off : off + cb])
    host_gbps = len(sample) / (time.perf_counter() - t0) / 1e9
    result["host_csum_gbps"] = round(host_gbps, 3)
    csum_bytes = BATCH * (K + M) * CHUNK
    t_total = nbytes / (enc_gbps * 1e9) + csum_bytes / (
        host_gbps * 1e9
    )
    result["write_path_host_gbps"] = round(
        nbytes / t_total / 1e9, 2
    )


def _measure_cluster(result: dict, enc_gbps: float) -> None:
    """Live-tier phase (round 8): mixed workload + OSD kill/revive
    over the real mini-cluster — cluster_gbps / cluster_iops /
    cluster_p99_ms (device clock), the degraded-window cut, the
    kernel-vs-cluster efficiency ratio, the coalesce/degraded-link
    A/Bs, and the round-14 tracked-vs-untracked observability A/B
    (trace_overhead_frac, acceptance < 0.02). See
    loadgen/bench_phase.py for methodology; sized by
    CEPH_TPU_BENCH_CLUSTER_OPS."""
    from ceph_tpu.loadgen.bench_phase import measure_cluster

    measure_cluster(result, enc_gbps)


def _measure_qos(result: dict) -> None:
    """Multi-tenant QoS phase (round 19): the noisy-neighbor A/B —
    tenant A's p99 solo, under a tenant-B flood + concurrent recovery
    with dmClock QoS armed, and the same storm with osd_op_qos=false
    (the escape hatch) — plus the recovery-slosh curve
    (time_to_recovered_s vs client p99 across high_client / balanced /
    high_recovery). See loadgen/bench_phase.py:measure_qos; sized by
    CEPH_TPU_BENCH_QOS_OPS."""
    from ceph_tpu.loadgen.bench_phase import measure_qos

    measure_qos(result)


def _measure_transport(result: dict, enc_gbps: float) -> None:
    """Messenger-v2 transport phase (round 20): the within-run
    transport x codec A/B grid (tcp/shm_ring x python/native frame
    codec) with a per-leg cluster-vs-kernel fraction, the shm-ring
    lane headline (shm_ring_gbps + chunk/byte traffic proof), the
    native-codec speedup, and the op-shard head-of-line rows — the
    flood x kill latency-spread ladder at 1 vs 4 shards plus the
    deterministic parked-shard sibling probe. See
    loadgen/bench_phase.py:measure_transport; sized by
    CEPH_TPU_BENCH_TRANSPORT_OPS."""
    from ceph_tpu.loadgen.bench_phase import measure_transport

    measure_transport(result, enc_gbps)


def main() -> int:
    from ceph_tpu.utils import platform

    platform.enable_compile_cache()
    device = platform.require_tpu()  # raises, naming the backend found
    peaks = published_peaks(device["kind"])
    result: dict = {"device": device, "peaks_source": peaks["source"]}
    roofline = enc_gbps = None
    with _phase(result, "roofline"):
        roofline = _measure_roofline(result)
    with _phase(result, "device_path"):
        enc_gbps = _measure_device_path(result, roofline, peaks)
    with _phase(result, "baseline_configs"):
        _measure_baseline_configs(result)
    with _phase(result, "code_families"):
        _measure_code_families(result)
    with _phase(result, "sched_superopt"):
        _measure_sched_superopt(result)
        # the dispatch-path ceiling: best packet-family rate through
        # the (optimized) schedule engine this run — the > 537 GB/s
        # round-11 target row
        rates = [
            result.get(k)
            for k in (
                "liberation_k4m2_gbps",
                "blaum_roth_k4m2_gbps",
                "liber8tion_k4m2_gbps",
            )
        ]
        rates = [r for r in rates if isinstance(r, (int, float))]
        if rates:
            result["sched_dispatch_ceiling_gbps"] = round(
                max(rates), 2
            )
    with _phase(result, "clay_repair"):
        _measure_clay_repair(result)
    with _phase(result, "smallop"):
        _measure_smallop_dispatch(result)
    with _phase(result, "single_core"):
        _measure_single_core(result, enc_gbps)
    with _phase(result, "reconstruct_latency"):
        _measure_reconstruct_latency(result)
    with _phase(result, "checksums"):
        _measure_checksums(result)
    with _phase(result, "fused_write_path"):
        _measure_fused_write_path(result, enc_gbps)
    with _phase(result, "cluster"):
        _measure_cluster(result, enc_gbps)
    with _phase(result, "qos"):
        _measure_qos(result)
    with _phase(result, "transport"):
        _measure_transport(result, enc_gbps)
    measured = enc_gbps is not None
    print(
        json.dumps(
            {
                "metric": f"EC({K},{M}) reed_sol_van batched stripe encode",
                "value": round(enc_gbps, 2) if measured else None,
                "unit": "GB/s data-in per chip",
                "vs_baseline": (
                    round(enc_gbps / TARGET_GBPS, 3) if measured else None
                ),
                **result,
            }
        )
    )
    return 1 if result.get("failed_phases") else 0


if __name__ == "__main__":
    sys.exit(main())
