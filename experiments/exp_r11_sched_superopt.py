"""Round-11: XOR-schedule superoptimization sweep (arxiv 2108.02692).

The schedule builder became an optimizer: greedy pairwise CSE factors
shared XOR subexpressions across parity rows into VMEM-scratch
intermediates, the DAG is linearized for operand locality, and the
route gate moved to post-CSE op count — which admits inverted decode
matrices (~50% ones, raw ratio 7-8) and LRC xor-local-parity repair
to the schedule route the raw density gate locked out. This script is
the chip evidence run behind the round-11 BASELINE rows. Run on the
v5e chip:

    python experiments/exp_r11_sched_superopt.py

Legs (each printed as its own table):

1. op-count scorecard — ones / selection XORs / post-CSE XORs /
   intermediates / scratch-slot peak, per family encode matrix AND
   per 2-lost inverted decode matrix (host-side; matches the tier-1
   golden pins).
2. encode A/B — family encode GB/s with ec_sched_opt on vs off
   (same geometry as bench.py's code-families phase). Target: opt >=
   unopt everywhere, and a new dispatch ceiling > 537 GB/s.
3. inverted-decode A/B — 2-lost-chunk decode GB/s through the
   schedule route (optimizer on; the matrix CSE-compresses under the
   gate) vs the MXU engine (ec_use_sched off). The round-11 claim:
   decode/repair shapes now ride the fixed engine.
4. LRC local repair — single-lost-chunk repair GB/s,
   local_parity=xor (schedule route) vs the default rs layout (MXU
   route), survivor-bytes-in basis — the `lrc_*_gbps >= 200` check.

Off-TPU it degrades to an interpret-mode bit-equality smoke on tiny
shapes (timings mean nothing there).
"""

import sys
import time

sys.path.insert(0, ".")

import numpy as np
import jax
import jax.numpy as jnp

from ceph_tpu.codecs.registry import registry
from ceph_tpu.ops import xor_schedule as xs
from ceph_tpu.utils import config, platform

FAMILIES = [
    ("liberation", {"technique": "liberation", "k": "4", "m": "2",
                    "w": "7"}, 7 * 16384, 160),
    ("blaum_roth", {"technique": "blaum_roth", "k": "4", "m": "2",
                    "w": "6"}, 6 * 16384, 192),
    ("liber8tion", {"technique": "liber8tion", "k": "4", "m": "2",
                    "w": "8"}, 8 * 16384, 128),
]


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


def shard_loop(apply_shards, nshards, chunk, stripes, seed):
    """Feedback loop over a tuple of [stripes, chunk] shard arrays;
    apply_shards(dict) -> list of output arrays."""
    sz = stripes * chunk
    flat = dev_rand((nshards * sz,), seed)
    arrs = tuple(
        flat[i * sz : (i + 1) * sz].reshape(stripes, chunk)
        for i in range(nshards)
    )

    @jax.jit
    def loop(arrs, iters):
        def body(i, carry):
            arrs, acc = carry
            outs = apply_shards(arrs)
            fold = jax.lax.dynamic_slice(outs[0], (0, 0), (1, 128))
            scalar = fold[0, 0]
            for o in outs[1:]:
                scalar = scalar ^ o[0, 0]
            first = jax.lax.dynamic_update_slice(
                arrs[0], fold ^ jnp.uint8(i + 1), (0, 0)
            )
            return (first,) + arrs[1:], acc ^ scalar

        _, acc = jax.lax.fori_loop(0, iters, body, (arrs, jnp.uint8(0)))
        return acc

    return loop, arrs


def leg1_op_counts():
    print("== leg 1: op-count scorecard (host-side)")
    print(f"{'matrix':34s} {'ones':>5s} {'raw':>5s} {'cse':>5s} "
          f"{'tmps':>5s} {'slots':>5s} {'save':>6s}")
    for fam, profile, _c, _s in FAMILIES:
        codec = registry.factory("jerasure", dict(profile))
        st = xs.cse_stats(codec.coding_bitmatrix)
        print(f"{fam + ' encode':34s} {st['ones']:5d} "
              f"{st['raw_xors']:5d} {st['opt_xors']:5d} "
              f"{st['temps']:5d} {st['scratch_slots']:5d} "
              f"{st['saving_frac']:6.1%}")
        dec = codec._build_decode_bitmatrix([2, 3, 4, 5], [0, 1])
        st = xs.cse_stats(dec)
        ratio_raw = (st["ones"] + dec.shape[0]) / dec.shape[1]
        ratio_opt = (st["opt_xors"] + dec.shape[0]) / dec.shape[1]
        print(f"{fam + ' decode lose(0,1)':34s} {st['ones']:5d} "
              f"{st['raw_xors']:5d} {st['opt_xors']:5d} "
              f"{st['temps']:5d} {st['scratch_slots']:5d} "
              f"{st['saving_frac']:6.1%}  "
              f"(gate ratio {ratio_raw:.2f} -> {ratio_opt:.2f})")


def leg2_encode_ab():
    print("== leg 2: encode A/B (ec_sched_opt on vs off), GB/s data-in")
    ceiling = 0.0
    for fam, profile, chunk, stripes in FAMILIES:
        codec = registry.factory("jerasure", dict(profile))
        k = codec.k
        rates = {}
        for opt in (True, False):
            with config.override(ec_sched_opt=opt):
                def apply(arrs, codec=codec, k=k):
                    p = codec.encode_chunks(
                        {i: arrs[i] for i in range(k)}
                    )
                    return [p[j] for j in sorted(p)]

                loop, arrs = shard_loop(apply, k, chunk, stripes, 31)
                per = loop_stats(loop, arrs)
            rates[opt] = stripes * k * chunk / per / 1e9
        ceiling = max(ceiling, rates[True])
        print(f"  {fam}: opt {rates[True]:7.1f}  unopt "
              f"{rates[False]:7.1f}  ratio {rates[True]/rates[False]:.3f}")
    print(f"  dispatch ceiling (opt): {ceiling:.1f} GB/s "
          f"(round-11 target > 537)")


def leg3_decode_ab():
    print("== leg 3: 2-lost inverted decode, schedule route vs MXU")
    for fam, profile, chunk, stripes in FAMILIES:
        codec = registry.factory("jerasure", dict(profile))
        k = codec.k
        keys = [2, 3, 4, 5]  # survivors: 2 data + 2 parity

        def apply(arrs, codec=codec, keys=keys):
            out = codec.decode_chunks(
                {0, 1}, dict(zip(keys, arrs))
            )
            return [out[0], out[1]]

        rates = {}
        for sched_on in (True, False):
            with config.override(ec_use_sched=sched_on):
                loop, arrs = shard_loop(
                    apply, len(keys), chunk, stripes, 37
                )
                per = loop_stats(loop, arrs)
            rates[sched_on] = len(keys) * stripes * chunk / per / 1e9
        print(f"  {fam}: sched {rates[True]:7.1f}  mxu "
              f"{rates[False]:7.1f}  ratio "
              f"{rates[True]/rates[False]:.3f}")


def leg4_lrc_local():
    print("== leg 4: LRC local repair (survivor-bytes-in GB/s)")
    chunk, stripes = 65536, 256
    for name, extra in (("xor", {"local_parity": "xor"}), ("rs", {})):
        codec = registry.factory(
            "lrc", {"k": "4", "m": "2", "l": "3", **extra}
        )
        plan = codec.minimum_to_decode(
            {0}, set(range(codec.k + codec.m)) - {0}
        )
        keys = sorted(plan)

        def apply(arrs, codec=codec, keys=keys):
            return [
                codec.decode_chunks({0}, dict(zip(keys, arrs)))[0]
            ]

        loop, arrs = shard_loop(apply, len(keys), chunk, stripes, 41)
        per = loop_stats(loop, arrs)
        gbps = len(keys) * stripes * chunk / per / 1e9
        print(f"  local_parity={name}: {gbps:7.1f} GB/s "
              f"({len(keys)} survivors read; target >= 200)")


def smoke_off_tpu():
    print("off-TPU: interpret-mode bit-equality smoke")
    import functools

    platform.on_tpu = lambda: True
    orig = xs.xor_schedule_apply_shards
    xs.xor_schedule_apply_shards = functools.partial(
        orig, interpret=True
    )
    rng = np.random.default_rng(5)
    codec = registry.factory(
        "jerasure",
        {"technique": "liberation", "k": "4", "m": "2", "w": "7"},
    )
    n = 7 * 2048
    data = {
        i: jnp.asarray(rng.integers(0, 256, (8, n), np.uint8))
        for i in range(4)
    }
    parity = codec.encode_chunks(dict(data))
    with config.override(ec_sched_opt=False):
        ref = codec.encode_chunks(dict(data))
    ok = all(
        (np.asarray(parity[i]) == np.asarray(ref[i])).all()
        for i in parity
    )
    print("  liberation encode opt == unopt:", ok)
    chunks = {**data, **parity}
    del chunks[0], chunks[1]
    out = codec.decode_chunks({0, 1}, chunks)
    ok = (np.asarray(out[0]) == np.asarray(data[0])).all() and (
        np.asarray(out[1]) == np.asarray(data[1])
    ).all()
    print("  liberation 2-lost decode via schedule route:", ok)


def main():
    leg1_op_counts()
    if not platform.on_tpu():
        smoke_off_tpu()
        return
    leg2_encode_ab()
    leg3_decode_ab()
    leg4_lrc_local()


if __name__ == "__main__":
    main()
