"""Round-6: zero-waste packing sweep — stripes on grid/lanes vs the
round-5 block-diagonal stripe pair.

The production kernels (ops/pallas_encode.py) now batch stripes on
the grid and lane axes with the bare [8R, 8F] code matrix; this
script sweeps the remaining knob — the lane batch S (stripes merged
along lanes per grid step) — per bench geometry, against the old
block-diagonal comparator rebuilt inline. Run on the v5e chip:

    python experiments/exp_r6_zero_waste.py

Off-TPU it falls back to interpreter mode on tiny shapes (correctness
smoke only; the timings mean nothing there).

MAC accounting (mac_stats): at (8,4) the zero-waste layout clocks
256 MACs/byte, all useful; the r5 pair clocked 512 at useful=0.5. If
the flagship was MXU-throughput-bound at mxu_util 0.761, halving
clocked MACs should land encode near 400+ GB/s data-in — the VERDICT
r6 item-2 target this sweep is meant to confirm or refute per S.
"""

import sys
import time

sys.path.insert(0, ".")

import numpy as np
import jax
import jax.numpy as jnp

from ceph_tpu.gf import (
    cauchy_good_matrix,
    gf_matrix_to_bitmatrix,
    vandermonde_rs_matrix,
)
from ceph_tpu.ops import pallas_encode as pe
from ceph_tpu.utils import platform

# helpers duplicated from exp_r5_multiop_byte rather than imported:
# that module builds the removed round-5 block-diagonal matrices at
# import time and is kept as the historical record of that design


def timed(fn, *args):
    t0 = time.perf_counter()
    np.asarray(fn(*args))
    return time.perf_counter() - t0


def loop_stats(loop, data, target=0.45, reps=4):
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


def dev_rand(shape, seed):
    key = jax.random.PRNGKey(seed)
    return jax.random.randint(key, shape, 0, 256, jnp.int32).astype(
        jnp.uint8
    )


def build_loop_stacked(apply):
    """Feedback loop over [B, C, N]: output slice patches the input."""

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
            return d, acc ^ fold[0, 0, 0]

        _, acc = jax.lax.fori_loop(0, iters, body, (d0, jnp.uint8(0)))
        return acc

    return loop

#: (name, generator, k, m, chunk, stripes) — the bench geometries the
#: repack targets (BENCH_r05: flagship 293, jerasure 131.5, cauchy
#: 147.9 GB/s)
CONFIGS = [
    ("flagship_k8m4_1m", vandermonde_rs_matrix, 8, 4, 1 << 20, 8),
    ("jerasure_k4m2_4k", vandermonde_rs_matrix, 4, 2, 4096, 4096),
    ("cauchy_k10m4_100k", cauchy_good_matrix, 10, 4, 102400, 256),
]


def sweep_lane_batch(bmat, data, s_values):
    """Force each lane batch S through the production kernel by
    monkey-patching the picker; returns {S: GB/s}."""
    out = {}
    batch, k, n = data.shape
    orig = pe._pick_lane_batch
    for s in s_values:
        if batch % s:
            continue
        pe._pick_lane_batch = lambda b, t, _s=s: _s
        try:
            apply = lambda d: pe.gf_encode_bitplane_pallas(bmat, d)
            loop = build_loop_stacked(apply)
            per = loop_stats(loop, data)
            out[s] = batch * k * n / per / 1e9
        except Exception as e:
            out[s] = f"{type(e).__name__}: {str(e)[:80]}"
        finally:
            pe._pick_lane_batch = orig
    return out


def main():
    on_tpu = platform.on_tpu()
    if not on_tpu:
        print("off-TPU: interpreter-mode smoke on tiny shapes")
    for name, gen, k, m, chunk, stripes in CONFIGS:
        if not on_tpu:
            chunk, stripes = pe.LANE_TILE, 8
        g = np.asarray(gen(k, m))
        bmat = gf_matrix_to_bitmatrix(g[k:, :])
        data = dev_rand((stripes, k, chunk), 7)
        if not on_tpu:
            from ceph_tpu.ops.bitplane import gf_encode_bitplane

            ref = np.asarray(
                gf_encode_bitplane(jnp.asarray(bmat), data)
            )
            got = np.asarray(
                pe.gf_encode_bitplane_pallas(bmat, data, interpret=True)
            )
            print(name, "interpret bit-exact:", (ref == got).all())
            continue
        stats = pe.mac_stats(k, m)
        print(f"== {name}: useful_frac={stats['useful_frac']:.3f}, "
              f"{stats['macs_per_byte']:.0f} MACs/byte")
        for s, gbps in sweep_lane_batch(bmat, data, (1, 2, 4, 8)).items():
            if isinstance(gbps, float):
                print(f"  S={s}: {gbps:7.1f} GB/s data-in")
            else:
                print(f"  S={s}: {gbps}")


if __name__ == "__main__":
    main()
