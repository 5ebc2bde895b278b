#!/usr/bin/env python3
"""chip_smoke.py — does the EC(8,4) cluster write/read/recover path
still start on the chip?

One process, the only one that touches JAX. It fails unless JAX's
default backend is a TPU, compiles every Pallas kernel family at the
geometries default configuration routes to it and compares each bit
for bit with a host oracle, then drives the system's main path — the
in-process cluster ``python -m ceph_tpu.bench_cli loadgen`` drives
(``LoadCluster`` + ``LoadGenerator``) — at default configuration:

- leg A: pool ``jerasure reed_sol_van k=8 m=4`` (the flagship of
  BASELINE.json), 12 OSDs, pg_num 32, 4 KiB chunks, MemStore; traffic
  at ``rados bench``'s defaults (4 MiB objects, 16 in flight): load
  256 objects, then a few hundred reads / reconstruct-reads / RMW
  overwrites / rewrites with the most-primary OSD killed a third of
  the way in and revived at two thirds; recovery, then scrub.
- leg B: the same pool with 64 KiB objects at queue depth 32 — ops
  that fit a ring slot and share coalesced device dispatches.
- leg C (four or more devices only): leg A's traffic at a quarter of
  the ops over a 4-device dispatch mesh.

Every acknowledged write must read back bit-exact with one OSD dead;
the route counters must show the device served the work with no
fallback. Every time printed here is a smoke reading from one run,
never a metric. The figures go out as one ``summary {...}`` line; the
last line of stdout is the verdict alone, ``{"ok": true, "device":
{"platform": "tpu", "kind": ..., "count": N}}``. The exit code is 0
only if every phase passed.

    python3 chip_smoke.py [--seed N]
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import faulthandler
import functools
import json
import sys
import threading
import time

import numpy as np

K, M = 8, 4
CSUM_BLOCK = 4096


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that scales. ``FULL`` is what the chip runs; the
    tier-1 test runs ``TINY`` with the same code and assertions."""

    #: stacked / shards-form encode and 2-lost decode: (stripes, chunk)
    rs_shapes: tuple = ((8, 1 << 20), (128, 4096))
    #: fused encode+csum: (stripes, chunk) at cb=4096
    fused_shapes: tuple = (
        (128, 4096), (32, 16384), (8, 65536), (8, 1 << 20),
    )
    sched_stripes: int = 16
    sched_packet: int = 16384
    clay_stripes: int = 8
    clay_chunks: tuple = (65536, 1 << 20)
    #: pallas_crc: (blocks, block bytes)
    crc_shapes: tuple = ((512, 4096), (128, 16384), (32, 65536))
    n_osds: int = 12
    pg_num: int = 32
    chunk_size: int = 4096
    a_object: int = 4 << 20
    a_objects: int = 256
    a_ops: int = 300
    a_depth: int = 16
    b_object: int = 64 << 10
    b_objects: int = 256
    b_ops: int = 400
    b_depth: int = 32
    recovery_timeout: float = 240.0


FULL = Sizes()
TINY = Sizes(
    rs_shapes=((8, 4096),),
    fused_shapes=((8, 4096),),
    sched_stripes=8,
    sched_packet=2048,
    clay_stripes=8,
    clay_chunks=(8192,),
    crc_shapes=((8, 4096),),
    pg_num=4,  # few primaries: writes queue up and coalesce
    a_object=(1 << 20) + (256 << 10),
    a_objects=6,
    a_ops=36,
    a_depth=4,
    b_object=64 << 10,
    b_objects=24,
    b_ops=60,
    b_depth=16,
    recovery_timeout=120.0,
)


_T0 = time.perf_counter()


def say(*parts) -> None:
    print(f"[{time.perf_counter() - _T0:7.1f}s]", *parts, flush=True)


# ------------------------------------------------------------ compile log
class CompileLog:
    """Every XLA backend compilation of the process, from JAX's own
    monitoring events: name, seconds (a persistent-cache hit shows as
    a short one) and how far the current phase had got."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.events: list[tuple[str, float, int]] = []
        self.cache_hits = 0
        #: callable giving the ops completed so far in the running leg
        self.progress = None

    def install(self) -> None:
        import jax.monitoring as monitoring

        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def uninstall(self) -> None:
        import jax.monitoring as monitoring

        monitoring.unregister_event_duration_listener(self._duration)
        monitoring.unregister_event_listener(self._event)

    def _duration(self, name: str, secs: float, **kw) -> None:
        if name != self.COMPILE:
            return
        progress = self.progress
        done = progress() if progress is not None else 0
        with self._lock:
            self.events.append((str(kw.get("fun_name", "?")), secs, done))

    def _event(self, name: str, **_kw) -> None:
        if name == self.CACHE_HIT:
            with self._lock:
                self.cache_hits += 1

    def mark(self) -> int:
        with self._lock:
            return len(self.events)

    def since(self, mark: int) -> list[tuple[str, float, int]]:
        with self._lock:
            return list(self.events[mark:])


def counters() -> dict[str, int]:
    """The route counters the repo already keeps, flattened:
    ``ec.*`` (codec dispatch), ``ring.*`` (streaming dispatcher),
    ``csum.*`` (checksum backends, with byte totals)."""
    from ceph_tpu.checksum import backends
    from ceph_tpu.utils import perf_collection

    dump = perf_collection.dump()
    out: dict[str, int] = {}
    for prefix, name in (("ec", "ec_dispatch"), ("ring", "ec_stream")):
        for key, val in dump.get(name, {}).items():
            out[f"{prefix}.{key}"] = int(val)
    for key, val in backends.counts().items():
        out[f"csum.{key}"] = int(val)
    for key, val in backends.bytes_hashed().items():
        out[f"csum.{key}_bytes"] = int(val)
    return out


def delta(before: dict, after: dict) -> dict[str, int]:
    """Non-zero counter movement; the ring's high-water gauge is
    reported as it stands."""
    out = {}
    for key, val in after.items():
        d = val if key == "ring.max_batch" else val - before.get(key, 0)
        if d:
            out[key] = d
    return out


class Run:
    """Shared state of one smoke run: the compile log and the rows
    each phase leaves for the final JSON line."""

    def __init__(self, sizes: Sizes, seed: int, interpret: bool) -> None:
        self.sizes = sizes
        self.seed = seed
        #: Pallas interpret mode — False on the chip; the tier-1 test
        #: passes True
        self.interpret = interpret
        self.log = CompileLog()
        self.phases: list[dict] = []

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time one phase and attach counter deltas and compilations.
        An exception inside the phase propagates: nothing is carried
        past a failure."""
        row: dict = {"phase": name}
        before = counters()
        mark = self.log.mark()
        t0 = time.perf_counter()
        say(f"== {name}")
        try:
            yield row
        finally:  # the row prints either way; the exception goes on
            compiles = self.log.since(mark)
            row["wall_s"] = round(time.perf_counter() - t0, 3)
            row["compiles"] = len(compiles)
            row["compile_s"] = round(sum(s for _n, s, _d in compiles), 3)
            names: dict[str, int] = {}
            for fun, _secs, _done in compiles:
                names[fun] = names.get(fun, 0) + 1
            row["compiled"] = dict(
                sorted(names.items(), key=lambda kv: -kv[1])[:8]
            )
            row["counters"] = delta(before, counters())
            self.phases.append(row)
            say(f"-- {name}: " + json.dumps(
                {k: v for k, v in row.items() if k != "phase"},
                sort_keys=True,
            ))


# ---------------------------------------------------------------- oracles
@functools.lru_cache(maxsize=1)
def _crc_table() -> np.ndarray:
    from ceph_tpu.checksum.reference import CRC32C_POLY_REFLECTED

    t = np.arange(256, dtype=np.uint32)
    for _ in range(8):
        t = np.where(
            t & 1, (t >> 1) ^ np.uint32(CRC32C_POLY_REFLECTED), t >> 1
        ).astype(np.uint32)
    return t


def crc32c_blocks_np(blocks: np.ndarray, init: int) -> np.ndarray:
    """Per-row crc32c (raw register, no final xor — ``crc32c_ref``'s
    semantics) of ``[..., block]`` uint8, byte-serial over the block
    and vectorised across rows. Checked against ``crc32c_ref`` itself
    on one row per call, so the oracle is the repo's reference."""
    from ceph_tpu.checksum.reference import crc32c_ref

    table = _crc_table()
    lead = blocks.shape[:-1]
    flat = blocks.reshape(-1, blocks.shape[-1])
    crc = np.full(flat.shape[0], init & 0xFFFFFFFF, np.uint32)
    for p in range(flat.shape[1]):
        crc = table[(crc ^ flat[:, p]) & 0xFF] ^ (crc >> 8)
    if int(crc[0]) != crc32c_ref(init, flat[0].tobytes()):
        raise SmokeFailure("numpy crc32c oracle disagrees with crc32c_ref")
    return crc.reshape(lead)


def gf_apply_np(mat: np.ndarray, stacked: np.ndarray) -> np.ndarray:
    """``out[..., r, :] = XOR_c mat[r, c] * stacked[..., c, :]`` over
    GF(2^8) from the repo's log/exp tables (``gf.gf_mul_bytes``), one
    256-entry product table per coefficient — independent of the
    bit-plane device path and of the native tier."""
    from ceph_tpu.gf import gf_mul_bytes

    mat = np.asarray(mat, np.uint8)
    out = np.zeros(
        stacked.shape[:-2] + (mat.shape[0], stacked.shape[-1]), np.uint8
    )
    byte_values = np.arange(256, dtype=np.uint8)
    for c in range(mat.shape[1]):
        col = stacked[..., c, :]
        for r in range(mat.shape[0]):
            g = int(mat[r, c])
            if g:
                out[..., r, :] ^= gf_mul_bytes(g, byte_values)[col]
    return out


def xor_apply_np(mat01: np.ndarray, packets: np.ndarray) -> np.ndarray:
    """0/1 packet matrix over ``[..., cols, P]`` packets by plain XOR."""
    out = np.zeros(
        packets.shape[:-2] + (mat01.shape[0], packets.shape[-1]), np.uint8
    )
    for q in range(mat01.shape[0]):
        for j in np.flatnonzero(mat01[q]):
            out[..., q, :] ^= packets[..., j, :]
    return out


# ----------------------------------------------------------- kernel census
def _rs_cases(run: Run, rng):
    """reed_sol_van (8,4): encode and 2-lost decode, stacked and
    shards form, plus the fused encode+csum kernel in both forms."""
    import jax.numpy as jnp

    from ceph_tpu.gf import (
        decode_matrix,
        gf_matrix_to_bitmatrix,
        vandermonde_rs_matrix,
    )
    from ceph_tpu.ops import pallas_encode as pe
    from ceph_tpu.utils import config

    interp = run.interpret
    g = vandermonde_rs_matrix(K, M)
    enc_bmat = gf_matrix_to_bitmatrix(g[K:, :])
    lost = [1, 6]
    present = [0, 2, 3, 4, 5, 7, 8, 9]
    dmat = decode_matrix(g, K, present)
    dec_bmat = gf_matrix_to_bitmatrix(np.stack([dmat[w] for w in lost]))

    def codeword(b, n):
        data = rng.integers(0, 256, (b, K, n), np.uint8)
        return data, gf_apply_np(g[K:, :], data)

    for b, n in run.sizes.rs_shapes:
        data, parity = codeword(b, n)
        full = np.concatenate([data, parity], axis=1)
        survivors = np.ascontiguousarray(full[:, present, :])
        tile = pe._pick_lane_tile(n)
        stacked_tile = f"tile={tile} s={pe._pick_lane_batch(b, tile)}"
        stile = pe._shards_tile(n)
        shards_tile = f"tile={stile} s={pe._shards_lane_batch(stile)}"

        def stacked(bmat, x):
            return lambda: [pe.gf_encode_bitplane_pallas(
                bmat, jnp.asarray(x), interpret=interp
            )]

        def shards(bmat, x):
            return lambda: [jnp.stack(
                pe.gf_encode_bitplane_pallas_shards(
                    bmat,
                    [jnp.asarray(x[:, i, :]) for i in range(x.shape[1])],
                    interpret=interp,
                ),
                axis=1,
            )]

        shape = f"[{b},{K},{n}]"
        yield (f"rs84 encode stacked {shape}", stacked_tile,
               stacked(enc_bmat, data), [parity])
        yield (f"rs84 encode shards {shape}", shards_tile,
               shards(enc_bmat, data), [parity])
        yield (f"rs84 decode2 stacked {shape}", stacked_tile,
               stacked(dec_bmat, survivors), [data[:, lost, :]])
        yield (f"rs84 decode2 shards {shape}", shards_tile,
               shards(dec_bmat, survivors), [data[:, lost, :]])

    if not (config.get("ec_fused_csum") and config.get("ec_use_pallas")):
        yield ("rs84 fused encode+csum", "", None,
               "disabled: ec_fused_csum / ec_use_pallas is off")
        return
    cb = CSUM_BLOCK
    for b, n in run.sizes.fused_shapes:
        data, parity = codeword(b, n)
        full = np.concatenate([data, parity], axis=1)
        csums = crc32c_blocks_np(full.reshape(b, K + M, n // cb, cb), 0)
        tile = pe._pick_fused_tile(n, cb, pe.FUSED_MAX_TILE)
        stile = pe._pick_fused_tile(n, cb, pe.FUSED_SHARDS_MAX_TILE)

        def fused_stacked(x=data):
            return list(pe.gf_encode_csum_bitplane_pallas(
                enc_bmat, jnp.asarray(x), cb, interpret=interp
            ))

        def fused_shards(x=data):
            par, cs = pe.gf_encode_csum_bitplane_pallas_shards(
                enc_bmat, [jnp.asarray(x[:, i, :]) for i in range(K)],
                cb, interpret=interp,
            )
            return [jnp.stack(par, axis=1), cs]

        shape = f"[{b},{K},{n}] cb={cb}"
        yield (f"rs84 fused encode+csum stacked {shape}",
               f"tile={tile} s={pe._pick_lane_batch(b, tile)}",
               fused_stacked, [parity, csums])
        yield (f"rs84 fused encode+csum shards {shape}",
               f"tile={stile} s={pe._shards_lane_batch(stile)}",
               fused_shards, [parity, csums])


def _sched_cases(run: Run, rng):
    """liberation / blaum_roth / liber8tion k=4 m=2: encode and the
    2-lost inverted decode (a multi-level ``Schedule`` with VMEM
    scratch), packetized and shards form."""
    import jax.numpy as jnp

    from ceph_tpu.codecs.registry import registry
    from ceph_tpu.ops import xor_schedule as xs
    from ceph_tpu.utils import config

    if not config.get("ec_use_sched"):
        yield ("xor_schedule", "", None, "disabled: ec_use_sched is off")
        return
    interp = run.interpret
    opt = config.get("ec_sched_opt")
    b, p = run.sizes.sched_stripes, run.sizes.sched_packet
    k, m = 4, 2
    lost, present = [1, 2], [0, 3, 4, 5]
    for tech, w in (("liberation", 7), ("blaum_roth", 6), ("liber8tion", 8)):
        codec = registry.factory(
            "jerasure",
            {"technique": tech, "k": str(k), "m": str(m), "w": str(w)},
        )
        chunk = w * p
        data = rng.integers(0, 256, (b, k * w, p), np.uint8)
        parity = xor_apply_np(codec.coding_bitmatrix, data)
        full = np.concatenate([data, parity], axis=1)  # [b, (k+m)w, p]
        rows = [s * w + t for s in present for t in range(w)]
        survivors = np.ascontiguousarray(full[:, rows, :])
        want = np.ascontiguousarray(
            data[:, [s * w + t for s in lost for t in range(w)], :]
        )
        dec01 = codec._build_decode_bitmatrix(present, lost)
        for op, mat01, x, expect in (
            ("encode", codec.coding_bitmatrix, data, parity),
            ("decode2", dec01, survivors, want),
        ):
            sched = xs.routable_schedule(mat01, opt)
            name = f"sched {tech} {op} [{b},{x.shape[1]},{p}]"
            if sched is None:
                # over the op-count gate: default config sends this
                # matrix to the MXU engine, not to these kernels
                yield (name, "", None, "not routed: over the schedule gate")
                continue
            slots = (
                xs._linearize(sched)[1]
                if isinstance(sched, xs.Schedule) else 0
            )
            n_in = x.shape[1] // w
            if not xs.shards_supported(
                n_in, expect.shape[1] // w, w, (b, chunk), slots
            ):
                raise SmokeFailure(f"{name}: shards form refuses {chunk}")

            def packetized(sched=sched, x=x):
                return [xs.xor_schedule_apply(
                    sched, jnp.asarray(x), interpret=interp
                )]

            def shards(sched=sched, x=x, n_in=n_in):
                outs = xs.xor_schedule_apply_shards(
                    sched,
                    [
                        jnp.asarray(
                            x[:, i * w : (i + 1) * w, :].reshape(b, chunk)
                        )
                        for i in range(n_in)
                    ],
                    w, interpret=interp,
                )
                return [jnp.stack(outs, axis=1).reshape(b, -1, p)]

            desc = f"xors={xs.schedule_xors(sched)} slots={slots}"
            yield (name + " packetized",
                   f"tile={xs._pick_tile(p)} {desc}", packetized, [expect])
            yield (name + " shards", f"chunk={chunk} {desc}",
                   shards, [expect])


def _clay_cases(run: Run, rng):
    """CLAY(8,4,d=11) single-chunk repair through the plane-blocked
    kernels (the traced route) against the lost chunk itself."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.codecs.registry import registry
    from ceph_tpu.ops import clay_kernels
    from ceph_tpu.utils import config

    if not config.get("ec_clay_kernels"):
        yield ("clay repair", "", None, "disabled: ec_clay_kernels is off")
        return
    codec = registry.factory(
        "clay", {"k": str(K), "m": str(M), "d": str(K + M - 1)}
    )
    b = run.sizes.clay_stripes
    sub = codec.get_sub_chunk_count()
    lost = 1
    for chunk in run.sizes.clay_chunks:
        sc = chunk // sub
        if not clay_kernels.supported(b, sc, codec.q, codec.t):
            raise SmokeFailure(f"clay kernels refuse b={b} sc={sc}")
        data = {
            i: rng.integers(0, 256, (b, chunk), np.uint8) for i in range(K)
        }
        chunks = {**data, **codec.encode_chunks(data)}
        plan = codec.minimum_to_decode(
            {lost}, set(range(K + M)) - {lost}
        )
        keys = sorted(plan)
        helpers = [
            np.concatenate(
                [
                    np.asarray(chunks[node])[:, i * sc : (i + n) * sc]
                    for i, n in plan[node]
                ],
                axis=-1,
            )
            for node in keys
        ]
        repair = jax.jit(
            lambda *h: codec.repair({lost}, dict(zip(keys, h)))[lost]
        )

        def fn(helpers=helpers, repair=repair):
            # asked for, not built: another caller of this process may
            # have built the same kernels already (a cache hit)
            info = clay_kernels._uncoupled_fn.cache_info()
            out = repair(*[jnp.asarray(h) for h in helpers])
            now = clay_kernels._uncoupled_fn.cache_info()
            if now.hits + now.misses == info.hits + info.misses:
                raise SmokeFailure("clay repair did not take the kernels")
            return [out]

        yield (f"clay(8,4,11) repair [{b},{chunk}]",
               f"sb={clay_kernels._pick_sb(b)} sc={sc}", fn, [data[lost]])


def _crc_cases(run: Run, rng):
    import jax.numpy as jnp

    from ceph_tpu.checksum import pallas_crc
    from ceph_tpu.utils import config

    if not config.get("ec_use_pallas"):
        yield ("pallas_crc", "", None, "disabled: ec_use_pallas is off")
        return
    init = 0xFFFFFFFF
    for nb, block in run.sizes.crc_shapes:
        if not pallas_crc.supported(nb, block):
            raise SmokeFailure(f"pallas_crc refuses [{nb},{block}]")
        data = rng.integers(0, 256, (nb, block), np.uint8)

        def fn(data=data):
            return [pallas_crc.crc32c_fold_pallas(
                jnp.asarray(data), init, interpret=run.interpret
            )]

        yield (f"pallas_crc [{nb},{block}]",
               f"bt={min(pallas_crc.BLOCK_TILE, nb)} "
               f"sub={min(pallas_crc.SUB_BYTES, block)}",
               fn, [crc32c_blocks_np(data, init)])


def census(run: Run) -> list[dict]:
    """Compile and run every Pallas kernel family once, bit-compare
    with the host oracle, print one line per kernel. A kernel that
    fails is reported with the compiler's own words and the census
    goes on, so one run shows every failure — then the phase raises."""
    import jax

    rng = np.random.default_rng([run.seed, 0xCE])
    rows: list[dict] = []
    with run.phase("census") as phase:
        for make in (_rs_cases, _sched_cases, _clay_cases, _crc_cases):
            for name, tile, fn, expect in make(run, rng):
                row = {"kernel": name, "tile": tile}
                if fn is None:  # disabled / not routed: expect says why
                    row["status"] = expect
                    rows.append(row)
                    say(f"census {name}: {expect}")
                    continue
                mark = run.log.mark()
                t0 = time.perf_counter()
                try:
                    outs = [np.asarray(o) for o in
                            jax.block_until_ready(fn())]
                except SmokeFailure:
                    raise
                except Exception as e:  # the compiler's words, kept
                    row["status"] = "FAILED: " + " ".join(
                        f"{type(e).__name__}: {e}".split()
                    )[:600]
                else:
                    bad = [
                        i for i, (o, e) in enumerate(zip(outs, expect))
                        if o.shape != e.shape or not np.array_equal(o, e)
                    ]
                    row["status"] = (
                        "ok" if not bad and len(outs) == len(expect)
                        else f"FAILED: output {bad} differs from the oracle"
                    )
                row["first_call_s"] = round(time.perf_counter() - t0, 3)
                row["compile_s"] = round(
                    sum(s for _n, s, _d in run.log.since(mark)), 3
                )
                rows.append(row)
                say(f"census {name}: {tile} compile_s={row['compile_s']} "
                    f"first_call_s={row['first_call_s']} {row['status']}")
        phase["kernels"] = len(rows)
        phase["ok"] = sum(r["status"] == "ok" for r in rows)
    failed = [r["kernel"] for r in rows if r["status"].startswith("FAILED")]
    if failed:
        raise SmokeFailure(f"census: {len(failed)} kernels failed: {failed}")
    return rows


# ------------------------------------------------------------ cluster legs
#: route counters that must stay zero at default configuration: every
#: one is a quiet reroute away from the kernel the smoke is proving
ZERO_COUNTERS = (
    "ec.fused_fallback", "ec.pallas_fallback", "ec.sched_rejected_shape",
    "ec.einsum_encode", "ec.einsum_decode", "ec.einsum_delta",
    "ring.batch_faults", "ring.solo_retries", "csum.pallas_fallback",
)
MIXED = {
    "read": 3, "reconstruct_read": 3, "rmw_overwrite": 2, "rand_write": 2,
}


def _check_report(leg: str, what: str, report: dict) -> None:
    problems = []
    if report["errors"]:
        problems.append(f"errors={report['errors']} "
                        f"{report.get('error_samples')}")
    if report["verify_failures"]:
        problems.append(f"verify_failures={report['verify_failures']} "
                        f"{report.get('verify_detail')}")
    if not report["exactly_once"]:
        problems.append(f"ops_in={report['ops_in']} != "
                        f"accounted={report['ops_accounted']}")
    if "recovered" in report and not report["recovered"]:
        problems.append("not recovered after revive")
    if problems:
        raise SmokeFailure(f"leg {leg} {what}: " + "; ".join(problems))


def _summary(report: dict) -> dict:
    keep = ("ops", "bytes", "errors", "verify_failures", "exactly_once",
            "recovered", "reclassified_reads", "lat_p50_ms", "lat_p99_ms")
    out = {k: report[k] for k in keep if k in report}
    out["classes"] = {
        name: {"ops": c["ops"], "bytes": c["bytes"]}
        for name, c in report["classes"].items()
    }
    if "fault" in report:
        out["fault"] = report["fault"]
    return out


def run_leg(
    run: Run, leg: str, object_size: int, objects: int, ops: int,
    depth: int, mesh_devices: int = 0,
) -> dict:
    """One cluster leg: boot, load, mixed traffic with the primary
    kill, recovery, scrub; then the leg's guarantees and routes.

    The client's patience scales with the object: the harness default
    (3 s per attempt, tuned on 8 KiB objects) is under the median
    latency of a 4 MiB write at depth 16 on this Python cluster, so
    half the ops were resent while still in service and degraded reads
    behind a parked primary ran out of attempts. ``rados bench`` itself
    sets no client op timeout."""
    from ceph_tpu.loadgen import (
        FaultSchedule,
        LoadCluster,
        LoadGenerator,
        WorkloadSpec,
    )

    sz = run.sizes
    common = dict(
        object_size=object_size, max_objects=objects, queue_depth=depth,
        seed=run.seed, device_clock=False,
    )
    before = counters()
    result: dict = {"leg": leg}
    cluster = LoadCluster(
        n_osds=sz.n_osds, k=K, m=M, pg_num=sz.pg_num,
        chunk_size=sz.chunk_size, plugin="jerasure",
        technique="reed_sol_van", use_mesh=bool(mesh_devices),
        mesh_devices=mesh_devices or None,
        client_op_timeout=max(3.0, 15.0 * object_size / (4 << 20)),
        client_max_attempts=20,
    )
    try:
        with run.phase(f"{leg}.load") as phase:
            loader = LoadGenerator(cluster, WorkloadSpec(
                mix={"seq_write": 1}, total_ops=objects, **common
            ))
            run.log.progress = lambda: loader.recorder.ops_accounted
            report = loader.run()
            phase.update(_summary(report))
            _check_report(leg, "load", report)
        with run.phase(f"{leg}.mixed") as phase:
            mixed = LoadGenerator(
                cluster,
                WorkloadSpec(mix=dict(MIXED), total_ops=ops, **common),
                FaultSchedule.primary_kill(
                    ops, recovery_timeout=sz.recovery_timeout
                ),
            )
            mixed.adopt_objects(loader)
            run.log.progress = lambda: mixed.recorder.ops_accounted
            mark = run.log.mark()
            before_mixed = counters()
            report = mixed.run()
            run.log.progress = None
            halves = [0, 0]
            for _name, _secs, done in run.log.since(mark):
                halves[done >= ops // 2] += 1
            phase.update(_summary(report))
            phase["compiles_first_half"] = halves[0]
            phase["compiles_second_half"] = halves[1]
            _check_report(leg, "mixed", report)
            # reads issued while the OSD was down decode from the
            # remaining shards whatever class the generator drew for
            # them; recovery is the only other source of decodes
            decodes = sum(
                v for k, v in delta(before_mixed, counters()).items()
                if k.startswith("ec.") and k.endswith("_decode")
            )
            phase["decodes"] = decodes
            if not decodes:
                raise SmokeFailure(
                    f"leg {leg}: nothing was read back from the "
                    "remaining shards while the OSD was down"
                )
        with run.phase(f"{leg}.scrub") as phase:
            if not cluster.wait_recovered(sz.recovery_timeout):
                raise SmokeFailure(f"leg {leg}: cluster did not recover")
            # one deep-scrub pass WITHOUT repair: a repair pass first
            # would mend — and so hide — a shard the device path wrote
            # wrong. (Scrub is a QoS-paced background class: the pass
            # takes ~0.6 s per 4 MiB object whatever the device does.)
            if not cluster.scrub_clean(repair=False):
                raise SmokeFailure(f"leg {leg}: scrub found errors")
            phase["scrub_clean"] = True
        if mesh_devices:
            result["mesh_output_devices"] = _mesh_output_devices(cluster)
    finally:
        run.log.progress = None
        cluster.shutdown()
    moved = delta(before, counters())
    result["counters"] = moved
    result["compiles_first_half"] = halves[0]
    result["compiles_second_half"] = halves[1]
    nonzero = {k: moved[k] for k in ZERO_COUNTERS if moved.get(k)}
    if nonzero:
        raise SmokeFailure(f"leg {leg}: fallback routes taken: {nonzero}")
    host = sum(v for k, v in moved.items()
               if k.startswith("ec.host_") and k.endswith("_bytes"))
    total = sum(v for k, v in moved.items()
                if k.startswith("ec.") and k.endswith("_bytes"))
    result["codec_bytes_host"] = host
    result["codec_bytes_device"] = total - host
    say(f"leg {leg}: codec input bytes host={host} "
        f"device={total - host} counters={json.dumps(moved, sort_keys=True)}")
    return result


def _mesh_output_devices(cluster) -> int:
    """Devices holding the shards of one mesh-dispatched output. Code
    that has only ever met virtual CPU devices may put everything on
    the first."""
    import jax

    from ceph_tpu.gf import gf_matrix_to_bitmatrix, vandermonde_rs_matrix
    from ceph_tpu.parallel import dispatch as mesh_dispatch

    g = vandermonde_rs_matrix(K, M)
    bmat = jax.numpy.asarray(gf_matrix_to_bitmatrix(g[K:, :]))
    data = np.random.default_rng(0).integers(
        0, 256, (8, K, 4096), np.uint8
    )
    out = mesh_dispatch.mesh_apply_bitmatrix(cluster.mesh, bmat, data)
    jax.block_until_ready(out)
    if not np.array_equal(np.asarray(out), gf_apply_np(g[K:, :], data)):
        raise SmokeFailure("mesh dispatch disagrees with the GF oracle")
    return len({s.device for s in out.addressable_shards})


def _need(result: dict, *keys: str) -> None:
    missing = [k for k in keys if not result["counters"].get(k)]
    if missing:
        raise SmokeFailure(
            f"leg {result['leg']}: expected routes never taken: {missing}"
        )


def leg_a(run: Run) -> dict:
    sz = run.sizes
    result = run_leg(run, "A", sz.a_object, sz.a_objects, sz.a_ops,
                     sz.a_depth)
    # 4 MiB per op is four times ec_host_dispatch_bytes: encode and
    # the degraded-read / recovery decode all have to reach the chip
    _need(result, "ec.fused_encode", "ec.pallas_decode")
    return result


def leg_b(run: Run) -> dict:
    sz = run.sizes
    result = run_leg(run, "B", sz.b_object, sz.b_objects, sz.b_ops,
                     sz.b_depth)
    _need(result, "ec.fused_encode", "ring.batches")
    if result["compiles_second_half"] > result["compiles_first_half"]:
        raise SmokeFailure(
            "leg B: compilations keep rising with ops: "
            f"{result['compiles_first_half']} in the first half, "
            f"{result['compiles_second_half']} in the second"
        )
    return result


def leg_c(run: Run, n_devices: int) -> dict:
    """Leg A's pool and traffic at a quarter of the ops over a
    4-device dispatch mesh, one process driving all four."""
    if n_devices < 4:
        say(f"== C\n-- C: skipped: {n_devices} device")
        return {"leg": "C", "skipped": f"{n_devices} device"}
    sz = run.sizes
    result = run_leg(
        run, "C", sz.a_object, max(sz.a_objects // 4, 4),
        max(sz.a_ops // 4, 48), sz.a_depth, mesh_devices=4,
    )
    _need(result, "ec.mesh_encode", "ec.mesh_decode")
    if result["counters"].get("ec.mesh_fallback"):
        raise SmokeFailure("leg C: mesh_fallback taken")
    if result["mesh_output_devices"] != 4:
        raise SmokeFailure(
            "leg C: a mesh-dispatched output sits on "
            f"{result['mesh_output_devices']} devices, not 4"
        )
    return result


# ------------------------------------------------------------------- main
def gate() -> tuple[dict, str]:
    """Device gate: compile cache on, a TPU or an error naming what
    was found, and the installation printed."""
    from importlib import metadata

    from ceph_tpu import native
    from ceph_tpu.utils import platform

    cache_dir = platform.enable_compile_cache()
    device = platform.require_tpu()
    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            versions[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            versions[pkg] = "absent"
    say(f"device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    say("versions: " + " ".join(f"{k}={v}" for k, v in versions.items()))
    say(f"compile cache: {cache_dir}")
    say(f"native tier: available={native.available()}")
    if not native.available():
        # default config (msgr_native_codec, the dispatcher ring) runs
        # on the native tier; without it leg B has no ring to ride
        raise SmokeFailure("native tier failed to build or load")
    return device, cache_dir


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0xEC,
                    help="seeds all data the run generates")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    device, cache_dir = gate()
    run = Run(FULL, args.seed, interpret=False)
    run.log.install()
    ok = False
    try:
        kernels = census(run)
        legs = [leg_a(run), leg_b(run), leg_c(run, device["count"])]
        compiles = run.log.since(0)
        print("summary " + json.dumps({
            "claim": None,
            "seed": args.seed,
            "compile_cache": cache_dir,
            "wall_s": round(time.perf_counter() - t0, 1),
            "compiles": len(compiles),
            "compile_s": round(sum(s for _n, s, _d in compiles), 1),
            "compile_cache_hits": run.log.cache_hits,
            "census": {"kernels": len(kernels),
                       "ok": sum(r["status"] == "ok" for r in kernels)},
            "legs": legs,
            "phases": run.phases,
        }, sort_keys=True), flush=True)
        ok = True
    finally:
        run.log.uninstall()
        # the verdict, exactly these two keys, is the last line once
        # the gate has found a TPU; a failed phase's exception goes on
        # past it and ends the run non-zero
        print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    # the contract is 1200 s: on a hang, say where and exit non-zero
    faulthandler.dump_traceback_later(1150, exit=True)
    sys.exit(main())
