"""Round-9: CLAY general-d plane-blocked repair sweep — the prepared
chip run for ISSUE 5's acceptance numbers.

The production path (codecs/clay.py _repair_kernels +
ops/clay_kernels.py) now serves ANY ``k <= d <= k+m-1`` and any
``sub_chunk_no * sc`` through 2D lane-blocked Pallas refs.  This
script measures, per geometry x chunk size:

- helper-read GB/s through the kernel path (the bench
  ``clay_repair_gbps`` methodology: serially-dependent feedback loop,
  diff-of-minima timing);
- the same with ``ec_clay_kernels=false`` (the XLA fast/itemized
  comparators the kernels replace);
- ``time_vs_naive`` against a 1-row RS reconstruct over k full
  chunks (decode1) measured inline — the < 1.0 acceptance target
  (helper-read >= ~130 GB/s at the 0.344x byte ratio break-even);
- the aloof path's rate vs the aloof-free rate (target: within 20%).

Run on the v5e chip:

    python experiments/exp_r9_clay_general.py          # full sweep
    python experiments/exp_r9_clay_general.py --quick  # one config

Off-TPU the kernels run in interpreter mode on the smallest config
(correctness smoke only; the timings mean nothing there).
"""

import sys
import time

sys.path.insert(0, ".")

import numpy as np
import jax
import jax.numpy as jnp

from ceph_tpu.codecs.registry import registry
from ceph_tpu.gf import (
    decode_matrix,
    gf_matrix_to_bitmatrix,
    vandermonde_rs_matrix,
)
from ceph_tpu.ops import pallas_encode as pe
from ceph_tpu.utils import config, platform


def timed(fn, *args):
    t0 = time.perf_counter()
    np.asarray(fn(*args))
    return time.perf_counter() - t0


def loop_stats(loop, data, target=0.45, reps=3):
    base = min(timed(loop, data, 1) for _ in range(2))
    n2 = 60
    while n2 < 40000:
        if timed(loop, data, n2) - base >= target:
            break
        n2 *= 2
    n1 = max(1, n2 // 10)
    t1 = min(timed(loop, data, n1) for _ in range(reps))
    t2 = min(timed(loop, data, n2) for _ in range(reps))
    return (t2 - t1) / (n2 - n1)


def device_rand(shape, seed):
    key = jax.random.PRNGKey(seed)
    return jax.random.randint(
        key, shape, 0, 256, dtype=jnp.int32
    ).astype(jnp.uint8)


def repair_loop(codec, lost, keys):
    @jax.jit
    def loop(arrs, iters):
        def body(i, carry):
            arrs, acc = carry
            out = codec.repair({lost}, dict(zip(keys, arrs)))[lost]
            fold = jax.lax.dynamic_slice(out, (0, 0), (1, 128))
            first = jax.lax.dynamic_update_slice(
                arrs[0], fold ^ jnp.uint8(i + 1), (0, 0)
            )
            return (first,) + arrs[1:], acc + jnp.sum(
                fold, dtype=jnp.uint32
            )

        _, acc = jax.lax.fori_loop(0, iters, body, (arrs, jnp.uint32(0)))
        return acc

    return loop


def decode1_loop(k, m, chunk, stripes, seed=5):
    """1-row RS reconstruct over k full chunks — the naive repair
    comparator, measured inline so every sweep row is self-contained."""
    g = vandermonde_rs_matrix(k, m)
    present = [i for i in range(k + m) if i != 4][: k]
    dmat = decode_matrix(g, k, present)
    bmat = gf_matrix_to_bitmatrix(dmat[4:5, :])
    data = device_rand((stripes, k, chunk), seed)

    def apply(d):
        return pe.gf_encode_bitplane_pallas(bmat, d)

    @jax.jit
    def loop(d0, iters):
        def body(i, carry):
            d, acc = carry
            out = apply(d)
            fold = jax.lax.dynamic_slice(
                out, (0, 0, 0), (1, 1, 128)
            )
            d = jax.lax.dynamic_update_slice(
                d, fold ^ jnp.uint8(i + 1), (0, 0, 0)
            )
            return d, acc ^ fold.reshape(-1)[0]

        _, acc = jax.lax.fori_loop(0, iters, body, (d0, jnp.uint8(0)))
        return acc

    return loop, data, stripes * k * chunk


def sweep_row(kk, m, d, chunk_kib, stripes, naive_per_byte):
    codec = registry.factory(
        "clay", {"k": str(kk), "m": str(m), "d": str(d)}
    )
    n = kk + m
    sub = codec.get_sub_chunk_count()
    chunk = codec.get_chunk_size(kk * chunk_kib * 1024)
    sc = chunk // sub
    lost = kk + 1
    plan = codec.minimum_to_decode({lost}, set(range(n)) - {lost})
    helper, read = {}, 0
    for hseed, (node, ranges) in enumerate(sorted(plan.items())):
        nbytes = sum(c for _i, c in ranges) * sc
        read += stripes * nbytes
        helper[node] = device_rand((stripes, nbytes), 100 + hseed)
    keys = sorted(helper)
    arrs = tuple(helper[kk2] for kk2 in keys)
    loop = repair_loop(codec, lost, keys)
    per = loop_stats(loop, arrs)
    with config.override(ec_clay_kernels=False):
        loop_xla = repair_loop(codec, lost, keys)
        per_xla = loop_stats(loop_xla, arrs)
    naive_s = naive_per_byte * kk * chunk * stripes
    row = {
        "geom": f"({kk},{m},d={d})",
        "chunk_kib": chunk // 1024,
        "sub_chunk_no": sub,
        "read_frac": round(read / (kk * chunk * stripes), 3),
        "kernel_gbps": round(read / per / 1e9, 2),
        "xla_gbps": round(read / per_xla / 1e9, 2),
        "kernel_vs_xla": round(per_xla / per, 2),
        "time_vs_naive": round(per / naive_s, 2),
    }
    print(row, flush=True)
    return row


def main():
    quick = "--quick" in sys.argv
    on_tpu = platform.on_tpu()
    if not on_tpu:
        print("# off-TPU: interpreter-mode correctness smoke only")
        sweep_row(4, 2, 5, 1, 8, naive_per_byte=1e-9)
        return
    # naive comparator at the flagship shape (64 KiB and 1 MiB chunks)
    rows = []
    for chunk_kib, stripes in ((64, 256), (1024, 16)):
        loop, data, nbytes = decode1_loop(8, 4, chunk_kib * 1024, stripes)
        naive_per_byte = loop_stats(loop, data) / nbytes
        print(
            {"decode1_gbps": round(1 / naive_per_byte / 1e9, 2),
             "chunk_kib": chunk_kib},
            flush=True,
        )
        geoms = [(8, 4, 11)] if quick else [
            (8, 4, 11),   # aloof-free flagship
            (8, 4, 10),   # one aloof (q=3)
            (8, 4, 9),    # two aloof (q=2)
            (6, 3, 7),    # aloof + shortened (nu=1)
        ]
        for kk, m, d in geoms:
            try:
                rows.append(sweep_row(
                    kk, m, d, chunk_kib, stripes, naive_per_byte
                ))
            except Exception as e:
                print({"geom": f"({kk},{m},d={d})",
                       "error": f"{type(e).__name__}: {e}"[:200]},
                      flush=True)
        if quick:
            break
    # acceptance summary
    by_geom = {r["geom"]: r for r in rows if r["chunk_kib"] >= 512}
    flag = by_geom.get("(8,4,d=11)")
    alo = by_geom.get("(8,4,d=10)")
    if flag:
        print({
            "accept_time_vs_naive_lt_1": flag["time_vs_naive"] < 1.0,
            "accept_aloof_within_20pct": (
                alo is not None
                and alo["kernel_gbps"] >= 0.8 * flag["kernel_gbps"]
            ),
        }, flush=True)


if __name__ == "__main__":
    main()
