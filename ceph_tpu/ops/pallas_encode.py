"""Pallas TPU kernel: fused bit-plane GF(2^8) matrix apply.

One generic kernel serves encode, decode and delta application — any
[R*8, C*8] GF(2) bitmatrix over [B, C, N] uint8 shards (the
ErasureCodeInterface encode_chunks/decode_chunks contract,
erasure-code/ErasureCodeInterface.h:449,571; the hot loop under
osd/ECUtil.cc:487-511).

v5 design (round 6): ZERO-WASTE packing. Rounds 3-5 paired two
stripes block-diagonally in the contraction ([8·2R, 8·2C] with the
cross-stripe blocks zero), which doubled rows AND contraction so half
the clocked MACs were structural zeros — mxu_util_frac read 0.761
while useful utilization was ~0.38 (VERDICT r6 item #2/weak #3).
The v5 layout removes the tax:

- **The stationary matrix IS the code matrix.** [8R, 8F] with
  F = C + pad (pad only to the int32 sublane granularity the packed
  unpack needs, F % 4 == 0) — no stripe duplication, no block
  diagonal. Every MAC outside the pad columns touches real data:
  useful_frac = C/F (1.0 for the flagship C=8 and every C % 4 == 0
  family): 64*R*F/C MACs per data byte, of which 64*R touch real
  data.
- **Stripes batch on the grid and the LANE axis, not the
  contraction.** Each grid step carries S stripes; their bit planes
  are unpacked per stripe and concatenated along lanes into one
  [8F, S·T] operand, so one stationary matmul streams S·T columns.
  The MXU column stream per step is as long as the old stripe-pair
  layout's, but MACs per data byte drop 2x (512 -> 256 at (8,4)) —
  the compute-bound families get their ceiling back. S is a pure
  tuning knob (lane width), not a matrix-shape choice: wide chunks
  take S=1 (pure grid batching), narrow chunks merge up to 8 stripes
  to keep ~64 KiB of lanes per step.
- **Packed unpack / bitcast-nibble pack** carry over from v3: bytes
  are reinterpreted 4-rows-per-int32 with a sublane ``pltpu.bitcast``,
  all 8 planes extracted with one row-indexed variable shift, and the
  int32 popcounts merge to output bytes with 3 shifts+ors — no second
  matmul stream. (See git history for the v3 experiment ladder.)

Falls back to the einsum path off-TPU; unit tests run the kernel in
interpreter mode (the sublane bitcasts are emulated bit-exactly
there) so CPU CI covers it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ceph_tpu.utils import platform

LANE_TILE = 2048       # minimum chunk-axis granularity the kernel accepts
MAX_LANE_TILE = 65536  # sweep-best tile (grid-step overhead flat above)
#: target combined lane width (stripes-per-step x tile) of one matmul:
#: the v3/v4 sweeps measured grid-step overhead flat above ~64 KiB of
#: lanes, and VMEM pressure grows past it (bits + int32 accumulator
#: scale with the width)
LANE_WIDTH_TARGET = 65536
FOLD = 1               # retained for API compat; superseded since v3


def _pick_lane_tile(n: int) -> int:
    """Largest LANE_TILE-multiple <= MAX_LANE_TILE dividing the chunk.

    Not power-of-two halving: a 100 KiB chunk (the Cauchy baseline
    config) divides 51200 but no power of two above 4096 — the old
    halving search landed on a 4 KiB tile and paid 16x the grid-step
    overhead."""
    t = MAX_LANE_TILE
    while t > LANE_TILE and n % t:
        t -= LANE_TILE
    return t


def _pick_lane_batch(batch: int, tile: int) -> int:
    """Stripes merged along the lane axis per grid step.

    Powers of two dividing the stripe batch, until the combined lane
    width reaches LANE_WIDTH_TARGET: 1 MiB chunks run S=1 (the 64 KiB
    tile already fills the stream), the 4 KiB jerasure config merges
    8 stripes into a 32 KiB-wide matmul instead of paying 8 separate
    grid steps of starved columns."""
    s = 1
    while s < 8 and batch % (2 * s) == 0 and 2 * s * tile <= LANE_WIDTH_TARGET:
        s *= 2
    return s


# ---------------------------------------------------------------- legacy
# helpers kept for tests/benches that assert on the matrix layouts.
def _plane_major_bitmatrix(bitmatrix: np.ndarray, k: int, m: int) -> np.ndarray:
    """Permute [m*8, k*8] from shard-major (row j*8+b, col i*8+b) to
    plane-major (row b*m+j, col b*k+i) index order."""
    b = np.asarray(bitmatrix)
    rows = [j * 8 + bit for bit in range(8) for j in range(m)]
    cols = [i * 8 + bit for bit in range(8) for i in range(k)]
    return np.ascontiguousarray(b[np.ix_(rows, cols)])


def _folded_bitmatrix(bitmatrix: np.ndarray, fold: int) -> np.ndarray:
    """block_diag(fold copies) of the plane-major matrix — the
    round-2 layout (and the round-3..5 stripe pair at fold=2), kept
    as the structural-zero comparator for tests and MAC accounting."""
    m8, k8 = bitmatrix.shape
    pm = _plane_major_bitmatrix(bitmatrix, k8 // 8, m8 // 8)
    big = np.zeros((fold * m8, fold * k8), np.uint8)
    for f in range(fold):
        big[f * m8 : (f + 1) * m8, f * k8 : (f + 1) * k8] = pm
    return big


# ----------------------------------------------------- v5 stationary form
def _zw_matrix(bitmatrix: np.ndarray, c: int, r: int, pad: int) -> np.ndarray:
    """Zero-waste stationary matrix: the [R*8, C*8] code matrix
    reindexed for the packed unpack and nibble pack, nothing more.

    acc row  = h*(4*r) + j*4 + b2   (output bit b' = h*4 + b2)
    bits col = b*F + i, F = c + pad (pad columns stay zero)
    """
    from ceph_tpu.gf.bitmatrix import plane_major_cols

    rows = [
        j * 8 + h * 4 + b2
        for h in range(2)
        for j in range(r)
        for b2 in range(4)
    ]
    src = np.asarray(bitmatrix, dtype=np.uint8)[rows, :]
    return plane_major_cols(src, pad).astype(np.int8)


@functools.lru_cache(maxsize=128)
def _zw_matrix_cached(bitmatrix_bytes: bytes, r8: int, c8: int, pad: int):
    """NUMPY only in the cache: caching a device array built inside a
    jit trace would leak that trace's tracer into every later call
    with the same key (UnexpectedTracerError on the first eager
    encode after a traced one — the round-3 lru_cache lesson, hit
    again by exp_pack.py). pallas_call converts per call site. The
    key no longer carries the stripe count: the v5 matrix depends
    only on the code matrix and its pad, so every (batch, tile)
    combination shares one stationary upload."""
    mat = np.frombuffer(bitmatrix_bytes, np.uint8).reshape(r8, c8)
    return _zw_matrix(mat, c8 // 8, r8 // 8, pad)


#: second-level DEVICE cache for eager callers — populated ONLY with
#: concrete arrays (never under a trace), bounded like the np cache
_DEV_CACHE: "OrderedDict[tuple, jax.Array]" = None  # type: ignore


def _dev_cached(key: tuple, big_np: np.ndarray):
    global _DEV_CACHE
    from collections import OrderedDict

    if _DEV_CACHE is None:
        _DEV_CACHE = OrderedDict()
    dev = _DEV_CACHE.get(key)
    if dev is None:
        dev = jnp.asarray(big_np)
        _DEV_CACHE[key] = dev
        if len(_DEV_CACHE) > 128:
            _DEV_CACHE.popitem(last=False)
    else:
        _DEV_CACHE.move_to_end(key)
    return dev


# -------------------------------------------------------------- the kernel
def _emulate_rows_to_i32(x):
    """Interpret-mode stand-in for pltpu.bitcast(u8 -> i32): 4 sublane
    rows pack little-endian into one int32 row (measured hardware
    order — the nibble pack depends on it)."""
    rows, t = x.shape
    g = x.reshape(rows // 4, 4, t).astype(jnp.uint32)
    xi = g[:, 0] | (g[:, 1] << 8) | (g[:, 2] << 16) | (g[:, 3] << 24)
    return jax.lax.bitcast_convert_type(xi, jnp.int32)


def _emulate_i32_to_i8(p):
    """Inverse direction: int32 row r unpacks to int8 rows 4r+j."""
    rows, t = p.shape
    u = jax.lax.bitcast_convert_type(p, jnp.uint32)
    parts = [((u >> (8 * j)) & jnp.uint32(0xFF)) for j in range(4)]
    stacked = jnp.stack(parts, axis=1).reshape(4 * rows, t)
    return stacked.astype(jnp.int8)


def _emulate_i8_to_i32(x):
    rows, t = x.shape
    g = x.astype(jnp.uint8).reshape(rows // 4, 4, t).astype(jnp.uint32)
    xi = g[:, 0] | (g[:, 1] << 8) | (g[:, 2] << 16) | (g[:, 3] << 24)
    return jax.lax.bitcast_convert_type(xi, jnp.int32)


def bitcast_u8_to_i32(x, interpret: bool):
    """In-kernel sublane bitcast: [R, T] uint8 -> [R/4, T] int32 (4
    sublane rows pack little-endian per lane).  The shared seam for
    every kernel doing packed-byte GF arithmetic (clay_kernels, the
    plane unpack below): interpret mode emulates the measured
    hardware pack bit-exactly, so CPU CI covers the same math."""
    if interpret:
        return _emulate_i8_to_i32(x)
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.bitcast(x, jnp.int32)


def bitcast_i32_to_u8(p, interpret: bool):
    """Inverse direction: [R, T] int32 -> [4R, T] uint8."""
    if interpret:
        return _emulate_i32_to_i8(p).astype(jnp.uint8)
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.bitcast(p, jnp.int8).astype(jnp.uint8)


def unpack_bitplanes(flat, interpret: bool):
    """In-kernel bit-plane unpack shared by the EC and CRC kernels.

    ``flat`` is [F, T] uint8 with F % 4 == 0. Returns [8F, T] int8
    bit planes in (plane, row) order: a sublane bitcast packs 4 rows
    per int32 lane, ONE variable shift over 8 b-major replicas
    (row-indexed iota) extracts every plane, and the bitcast back
    scatters each byte's bit to the row it came from. Interpret mode
    emulates the measured little-endian sublane pack bit-exactly."""
    from jax.experimental.pallas import tpu as pltpu

    f, t = flat.shape
    if interpret:
        xi = _emulate_rows_to_i32(flat)
    else:
        xi = pltpu.bitcast(flat, jnp.int32)  # [F/4, T]
    X = jnp.concatenate([xi] * 8, axis=0)  # [2F, T]
    shifts = jax.lax.broadcasted_iota(
        jnp.int32, (2 * f, t), 0
    ) // jnp.int32(f // 4)  # row group F/4 rows per plane
    pb = (X >> shifts) & jnp.int32(0x01010101)
    if interpret:
        return _emulate_i32_to_i8(pb)
    return pltpu.bitcast(pb, jnp.int8)  # [8F, T]


def _unpack_stripe_lanes(stripes, pad, interpret: bool):
    """Unpack each [C, T] stripe to bit planes and merge along lanes.

    The heart of the zero-waste layout: stripes land side by side on
    the LANE axis ([8F, S*T]) instead of block-diagonally in the
    contraction, so the stationary matrix stays the [8R, 8F] code
    matrix and every contraction row feeds real data. The lane concat
    is free (tiles are lane-aligned); the per-stripe unpack costs the
    same total VPU work as one fused unpack did."""
    t = stripes[0].shape[1]
    planes = []
    for flat in stripes:
        if pad:
            flat = jnp.concatenate(
                [flat, jnp.zeros((pad, t), jnp.uint8)], axis=0
            )
        planes.append(unpack_bitplanes(flat, interpret))
    return planes[0] if len(planes) == 1 else jnp.concatenate(planes, axis=1)


def _matmul_pack(bmat, bits, r, interpret: bool):
    """[8R, 8F] @ [8F, W] -> packed [R, W] uint8 output bytes via the
    bitcast-nibble pack (no second matmul stream)."""
    from jax.experimental.pallas import tpu as pltpu

    acc = jax.lax.dot_general(
        bmat, bits,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # [8R, W] rows (h, j, b2)
    acc8 = acc.astype(jnp.int8)  # parity lives in bit 0; truncation safe
    if interpret:
        p32 = _emulate_i8_to_i32(acc8)
    else:
        p32 = pltpu.bitcast(acc8, jnp.int32)  # [2R, W]
    masked = p32 & jnp.int32(0x01010101)
    nib = (
        masked
        | (masked >> jnp.int32(7))
        | (masked >> jnp.int32(14))
        | (masked >> jnp.int32(21))
    ) & jnp.int32(0xF)
    out32 = nib[0:r] | (nib[r : 2 * r] << jnp.int32(4))
    return out32.astype(jnp.uint8)  # [R, W]


def _make_kernel(c: int, r: int, s: int, pad: int, interpret: bool):
    def kernel(bmat_ref, data_ref, out_ref):
        d = data_ref[:]  # [S, C, T] uint8
        t = d.shape[2]
        bits = _unpack_stripe_lanes(
            [d[si] for si in range(s)], pad, interpret
        )  # [8F, S*T]
        out8 = _matmul_pack(bmat_ref[:], bits, r, interpret)  # [R, S*T]
        if s == 1:
            out_ref[:] = out8.reshape(1, r, t)
        else:
            for si in range(s):
                out_ref[si] = out8[:, si * t : (si + 1) * t]

    return kernel


#: The kernels' names on the device: the TPU compiler calls a Pallas
#: custom call ``%<name>.<n>``, and the benchmark's cells find their
#: codec kernel in a profiler trace by that text
#: (``benchmark/workloads/*.json`` ``codec_kernel.match``:
#: ``%_apply_tiled.`` and ``%_apply_tiled_csum``). Pinned here so that
#: renaming a Python function cannot empty ``codec_roofline`` unseen;
#: tests/test_kernel_names.py lowers both and looks for the strings.
APPLY_KERNEL_NAME = "_apply_tiled"
FUSED_KERNEL_NAME = "_apply_tiled_csum"


@functools.partial(
    jax.jit,
    static_argnames=("c", "r", "s", "pad", "lane_tile", "interpret"),
)
def _apply_tiled(bmat_big, data, c, r, s, pad, lane_tile, interpret=False):
    batch, _, n = data.shape
    return pl.pallas_call(
        _make_kernel(c, r, s, pad, interpret),
        grid=(batch // s, n // lane_tile),
        in_specs=[
            pl.BlockSpec(bmat_big.shape, lambda b, ch: (0, 0)),
            pl.BlockSpec((s, c, lane_tile), lambda b, ch: (b, 0, ch)),
        ],
        out_specs=pl.BlockSpec((s, r, lane_tile), lambda b, ch: (b, 0, ch)),
        out_shape=jax.ShapeDtypeStruct((batch, r, n), jnp.uint8),
        interpret=interpret,
        name=APPLY_KERNEL_NAME,
    )(bmat_big, data)


def supported(data_shape: tuple[int, ...]) -> bool:
    """Kernel preconditions: [B, C, N] with the chunk axis tileable."""
    return len(data_shape) == 3 and data_shape[-1] % LANE_TILE == 0


# ----------------------------------------------------------- shards form
#: block rows per grid step (sublane granularity: a 2D block's
#: second-minor dim must be a multiple of 8 or the whole axis)
SHARDS_SB = 8
#: shards-form lane-tile cap, set in round 5 when 64 KiB tiles crashed
#: that libtpu's Mosaic compiler at c=8 and measured no better than
#: 32 KiB where they compiled (a round-5 A/B run).
#: libtpu 0.0.34 compiles 64 KiB (AOT, round 21); whether it is faster
#: is not measured, so the cap stays
SHARDS_MAX_TILE = 32768
#: widest contraction the shards form serves (F <= 16, one clean MXU
#: pass); wider codes take the stacked kernel, which tiles the
#: contraction itself
SHARDS_MAX_C = 16


def _shards_lane_batch(tile: int) -> int:
    """Stripes per matmul group, merged along lanes (power of two
    dividing SHARDS_SB) — same LANE_WIDTH_TARGET rule as the stacked
    kernel. With zero-waste packing the group size no longer bends
    the matrix shape, so every c <= SHARDS_MAX_C rides the shards
    form (the round-5 s*c <= 16 rule shut out c > 8 entirely and sent
    cauchy/shec decode through the stacked relayout copy)."""
    s = 1
    while s < SHARDS_SB and 2 * s * tile <= LANE_WIDTH_TARGET:
        s *= 2
    return s


def shards_supported(c: int, shape: tuple[int, ...]) -> bool:
    """Can the shards-form kernel serve c per-shard [..., N] arrays?"""
    if len(shape) < 1 or not 0 < c <= SHARDS_MAX_C:
        return False
    n = shape[-1]
    b = int(np.prod(shape[:-1], initial=1))
    return b % SHARDS_SB == 0 and n % LANE_TILE == 0


def _shards_tile(n: int) -> int:
    t = min(SHARDS_MAX_TILE, n)
    while t > LANE_TILE and n % t:
        t -= LANE_TILE
    return t


@functools.lru_cache(maxsize=128)
def _shards_fn(
    mat_bytes: bytes, r8: int, c8: int, s: int, tile: int,
    interpret: bool,
):
    """Jitted shards-form apply, cached per (bitmatrix, geometry).

    The kernel carries SB stripes of every shard per block and loops
    over SB/s groups; each group gathers one [C, T] slice per stripe,
    lane-concats the unpacked planes and runs ONE stationary matmul
    with the zero-waste [8R, 8F] matrix — no per-row sublane gathers,
    no block diagonal. Output bytes come back stripe-major along
    lanes and land in m separate parity refs: neither input nor
    output is ever stacked in HBM, which is the whole win (the
    [B, k, N] stack is a relayout copy measured at 3.5x the kernel's
    own cost on the SHEC/LRC bench geometry)."""
    bitmatrix = np.frombuffer(mat_bytes, np.uint8).reshape(r8, c8)
    c, r = c8 // 8, r8 // 8
    pad = (-c) % 4
    groups = SHARDS_SB // s
    big = _zw_matrix(bitmatrix, c, r, pad)

    def kernel(bmat_ref, *refs):
        ins, outs = refs[:c], refs[c:]
        t = ins[0].shape[1]
        for g in range(groups):
            stripes = []
            for si in range(s):
                q = g * s + si
                stripes.append(jnp.concatenate(
                    [ins[i][q : q + 1, :] for i in range(c)], axis=0
                ))  # [C, T]
            bits = _unpack_stripe_lanes(stripes, pad, interpret)
            out8 = _matmul_pack(bmat_ref[:], bits, r, interpret)
            for si in range(s):
                q = g * s + si
                for j in range(r):
                    outs[j][q : q + 1, :] = out8[
                        j : j + 1, si * t : (si + 1) * t
                    ]

    @jax.jit
    def apply(bmat, *shards):
        b, n = shards[0].shape
        return pl.pallas_call(
            kernel,
            grid=(b // SHARDS_SB, n // tile),
            in_specs=[pl.BlockSpec(big.shape, lambda i, ch: (0, 0))]
            + [
                pl.BlockSpec((SHARDS_SB, tile), lambda i, ch: (i, ch))
                for _ in range(c)
            ],
            out_specs=[
                pl.BlockSpec((SHARDS_SB, tile), lambda i, ch: (i, ch))
                for _ in range(r)
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, n), jnp.uint8)
                for _ in range(r)
            ],
            interpret=interpret,
        )(bmat, *shards)

    return apply, big


def gf_encode_bitplane_pallas_shards(
    bitmatrix,
    shards: list,
    interpret: bool | None = None,
) -> list:
    """Shards-form bitmatrix apply: c per-shard [..., N] arrays in,
    R = rows/8 per-shard parity arrays out — same math as
    ``gf_encode_bitplane_pallas`` with neither side ever stacked.
    Callers gate with ``shards_supported``."""
    if interpret is None:
        interpret = platform.pallas_interpret()
    mat = np.ascontiguousarray(np.asarray(bitmatrix, dtype=np.uint8))
    r8, c8 = mat.shape
    lead = shards[0].shape[:-1]
    n = shards[0].shape[-1]
    if c8 != len(shards) * 8:
        raise ValueError(
            f"bitmatrix cols {c8} != shards*8 {len(shards) * 8}"
        )
    tile = _shards_tile(n)
    s = _shards_lane_batch(tile)
    key = (mat.tobytes(), r8, c8, s, tile, interpret)
    fn, big = _shards_fn(*key)
    traced = any(isinstance(v, jax.core.Tracer) for v in shards)
    if not traced:
        big = _dev_cached(("zw-shards",) + key[:-1], big)
    b = int(np.prod(lead, initial=1))
    flat = [jnp.asarray(v).reshape(b, n) for v in shards]
    outs = fn(big, *flat)
    return [o.reshape(lead + (n,)) for o in outs]


# ------------------------------------------------- fused encode+checksum
# One device pass for the whole write path: while each stripe's data
# tiles are resident for the encode matmul, fold per-csum-block CRC32C
# for the k data shards from the SAME bit planes the matmul consumes,
# and fold the freshly-produced parity tiles before they leave VMEM —
# [shards, nblocks] u32 csums emitted alongside parity in one
# pallas_call. The separate checksum pass (which re-read every byte
# encode just wrote) disappears; at hbm_roofline_frac ~0.34 the write
# path is bandwidth-bound, so that second HBM pass was the bill.
#
# The fold reuses checksum/pallas_crc's table machinery
# (plane_fold_kb): per plane b a stationary [32, cb] matrix whose
# column p holds the crc-register contribution of bit b of byte p —
# the CRC of one block is 8 extra [rows, cb] x kb[b]^T MXU dots over
# bits the kernel already holds. Csums come out ZERO-INIT; any seed is a
# constant XOR on the host (checksum.crc32c.crc32c_seed_shift), so
# one kernel output serves BlueStore blob csums (seed -1), HashInfo
# chaining, and wire csums alike.


#: fused-kernel lane-tile caps. Stacked form: 32 KiB compiles at every
#: geometry the write path produces. Shards form: its 8-stripe blocks
#: and two-stripe [32, 64 Ki] int32 accumulator put a 32 KiB tile at
#: 18.05 MB of v5e's 16 MiB scoped VMEM ("exceeded scoped vmem limit
#: by 2.05M", libtpu 0.0.34); 16 KiB compiles with ~2 MB to spare
FUSED_MAX_TILE = 32768
FUSED_SHARDS_MAX_TILE = 16384


@functools.lru_cache(maxsize=8)
def _kb_cached(csum_block: int) -> np.ndarray:
    """NUMPY only (the _zw_matrix_cached trace-safety rule)."""
    from ceph_tpu.checksum.pallas_crc import plane_fold_kb

    return plane_fold_kb(csum_block)


def _crc_fold_tile(
    planes, parity8, kb_ref, c, f, r, rp, cb, interpret: bool
):
    """CRC32C fold epilogue for ONE stripe's resident tile.

    ``planes`` are the data bit planes the encode matmul just consumed
    ([8F, T], plane-major); ``parity8`` the packed parity bytes
    ([R, T]) — unpacked once more in registers (rows padded to the
    int32 sublane granularity), never via HBM. Returns [C+R, nb*32]
    int32 fold counts: per csum block q and plane b one
    [C+R, cb] x kb[b]^T dot, summed over the 8 planes — contraction cb,
    exactly the pallas_crc discipline, minus its unpack (already
    paid) and minus its HBM read (the data never left VMEM)."""
    t = parity8.shape[1]
    if rp > r:
        parity8 = jnp.concatenate(
            [parity8, jnp.zeros((rp - r, t), jnp.uint8)], axis=0
        )
    pplanes = unpack_bitplanes(parity8, interpret)  # [8*rp, T]
    nb = t // cb
    accs = []
    for q in range(nb):
        lo = q * cb
        acc = None
        for b in range(8):
            rows = jnp.concatenate(
                [
                    planes[b * f : b * f + c, lo : lo + cb],
                    pplanes[b * rp : b * rp + r, lo : lo + cb],
                ],
                axis=0,
            )  # [C+R, cb] bits of plane b
            part = jax.lax.dot_general(
                rows, kb_ref[b],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32,
            )  # [C+R, cb] x [32, cb]^T -> [C+R, 32]
            acc = part if acc is None else acc + part
        accs.append(acc)
    return accs[0] if nb == 1 else jnp.concatenate(accs, axis=1)


def _csum_pack(acc, c, r):
    """[B, N/tile, C+R, (tile/cb)*32] int32 fold counts ->
    [B, C+R, N/cb] uint32 zero-init csums (mod 2 + LSB-first bit pack,
    lane tiles back in shard order) — the tiny epilogue outside the
    kernel, same as pallas_crc's.

    The kernel emits one [C+R, nb*32] slab per (stripe, lane tile): a
    block whose last two dims ARE the array's last two dims is legal
    for any nb, where a [.., C+R, nb*32] block of a [.., C+R,
    (N/cb)*32] array is rejected by the TPU lowering unless nb*32 is
    a multiple of 128 or the whole axis (a 12 KiB tile at cb=4096 is
    96 lanes)."""
    batch, n_tiles = acc.shape[:2]
    bits = (
        acc.reshape(batch, n_tiles, c + r, -1, 32) & 1
    ).astype(jnp.uint32)
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    packed = jnp.sum(bits * weights, axis=-1, dtype=jnp.uint32)
    return packed.transpose(0, 2, 1, 3).reshape(batch, c + r, -1)


def _make_fused_kernel(c, r, s, pad, cb, interpret: bool):
    f = c + pad
    rp = -(-r // 4) * 4

    def kernel(bmat_ref, kb_ref, data_ref, out_ref, csum_ref):
        d = data_ref[:]  # [S, C, T] uint8
        t = d.shape[2]
        planes = []
        for si in range(s):
            flat = d[si]
            if pad:
                flat = jnp.concatenate(
                    [flat, jnp.zeros((pad, t), jnp.uint8)], axis=0
                )
            planes.append(unpack_bitplanes(flat, interpret))
        bits = planes[0] if s == 1 else jnp.concatenate(planes, axis=1)
        out8 = _matmul_pack(bmat_ref[:], bits, r, interpret)  # [R, S*T]
        nb = t // cb
        for si in range(s):
            tile = out8[:, si * t : (si + 1) * t]
            fold = _crc_fold_tile(
                planes[si], tile, kb_ref, c, f, r, rp, cb, interpret
            )
            if s == 1:
                out_ref[:] = tile.reshape(1, r, t)
                csum_ref[:] = fold.reshape(1, 1, c + r, nb * 32)
            else:
                out_ref[si] = tile
                csum_ref[si, 0] = fold

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("c", "r", "s", "pad", "lane_tile", "cb", "interpret"),
)
def _apply_tiled_csum(
    bmat_big, kb, data, c, r, s, pad, lane_tile, cb, interpret=False
):
    batch, _, n = data.shape
    nb = lane_tile // cb
    parity, acc = pl.pallas_call(
        _make_fused_kernel(c, r, s, pad, cb, interpret),
        grid=(batch // s, n // lane_tile),
        in_specs=[
            pl.BlockSpec(bmat_big.shape, lambda b, ch: (0, 0)),
            pl.BlockSpec(kb.shape, lambda b, ch: (0, 0, 0)),
            pl.BlockSpec((s, c, lane_tile), lambda b, ch: (b, 0, ch)),
        ],
        out_specs=[
            pl.BlockSpec((s, r, lane_tile), lambda b, ch: (b, 0, ch)),
            pl.BlockSpec(
                (s, 1, c + r, nb * 32), lambda b, ch: (b, ch, 0, 0)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, r, n), jnp.uint8),
            jax.ShapeDtypeStruct(
                (batch, n // lane_tile, c + r, nb * 32), jnp.int32
            ),
        ],
        interpret=interpret,
        name=FUSED_KERNEL_NAME,
    )(bmat_big, kb, data)
    return parity, _csum_pack(acc, c, r)


def fused_csum_supported(data_shape: tuple[int, ...], csum_block: int) -> bool:
    """Stacked-form gate: the encode kernel's own preconditions plus a
    csum block that the lane tiling can respect (power of two >= 256
    dividing the chunk axis)."""
    return (
        supported(data_shape)
        and csum_block >= 256
        and csum_block & (csum_block - 1) == 0
        and data_shape[-1] % csum_block == 0
    )


def _pick_fused_tile(n: int, cb: int, cap: int = MAX_LANE_TILE) -> int:
    """Largest tile <= cap that divides the chunk AND is a multiple of
    both the lane granularity and the csum block (so every csum block
    lives wholly inside one grid step — no cross-step accumulator)."""
    step = max(cb, LANE_TILE)
    t = max(step, (min(cap, n) // step) * step)
    while t > step and n % t:
        t -= step
    return t


def gf_encode_csum_bitplane_pallas(
    bitmatrix,
    data: jax.Array,
    csum_block: int,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused encode+checksum: same parity as
    ``gf_encode_bitplane_pallas`` PLUS ``[B, C+R, N//csum_block]``
    uint32 ZERO-INIT per-block CRC32C csums (rows 0..C-1 = the data
    shards in input order, C..C+R-1 = the parity rows), all from one
    pallas_call. Callers gate with ``fused_csum_supported``."""
    if interpret is None:
        interpret = platform.pallas_interpret()
    mat = np.ascontiguousarray(np.asarray(bitmatrix, dtype=np.uint8))
    r8, c8 = mat.shape
    batch, c, n = data.shape
    if c8 != c * 8:
        raise ValueError(f"bitmatrix cols {c8} != shards*8 {c * 8}")
    if not fused_csum_supported(data.shape, csum_block):
        raise ValueError(
            f"shape {data.shape} x csum_block {csum_block} untileable"
        )
    pad = (-c) % 4
    key = (mat.tobytes(), r8, c8, pad)
    big = _zw_matrix_cached(*key)
    kb = _kb_cached(csum_block)
    r = r8 // 8
    f = c + pad
    # the fused epilogue adds the kb fold table (8*32*cb int8) and the
    # parity bit planes to the plain kernel's VMEM budget — cap the
    # tile at 32 KiB, with the plain kernel's wide-contraction shrink
    # on top
    cap = FUSED_MAX_TILE if f <= 32 else max(
        max(csum_block, LANE_TILE), (65536 * 32) // f
    )
    tile = _pick_fused_tile(n, csum_block, cap)
    s = _pick_lane_batch(batch, tile)
    if not isinstance(data, jax.core.Tracer):
        big = _dev_cached(key, big)
        kb = _dev_cached(("kb", csum_block), kb)
    return _apply_tiled_csum(
        big, kb, data, c, r, s, pad, tile, csum_block,
        interpret=interpret,
    )


# -- shards form --------------------------------------------------------
def fused_csum_shards_supported(
    c: int, shape: tuple[int, ...], csum_block: int
) -> bool:
    return (
        shards_supported(c, shape)
        and 256 <= csum_block <= FUSED_SHARDS_MAX_TILE
        and csum_block & (csum_block - 1) == 0
        and shape[-1] % csum_block == 0
    )


@functools.lru_cache(maxsize=64)
def _shards_csum_fn(
    mat_bytes: bytes, r8: int, c8: int, s: int, tile: int, cb: int,
    interpret: bool,
):
    """Fused shards-form apply: the zero-waste shards kernel
    (_shards_fn) with the CRC fold epilogue per stripe — parity lands
    in R per-shard refs, csums in one [B, N/tile, C+R, nb*32]
    accumulator (see ``_csum_pack``), neither inputs nor outputs ever stacked in HBM."""
    bitmatrix = np.frombuffer(mat_bytes, np.uint8).reshape(r8, c8)
    c, r = c8 // 8, r8 // 8
    pad = (-c) % 4
    f = c + pad
    rp = -(-r // 4) * 4
    groups = SHARDS_SB // s
    big = _zw_matrix(bitmatrix, c, r, pad)
    kb_np = _kb_cached(cb)
    nb = tile // cb

    def kernel(bmat_ref, kb_ref, *refs):
        ins = refs[:c]
        outs = refs[c : c + r]
        csum_ref = refs[c + r]
        t = ins[0].shape[1]
        for g in range(groups):
            planes = []
            for si in range(s):
                q = g * s + si
                flat = jnp.concatenate(
                    [ins[i][q : q + 1, :] for i in range(c)], axis=0
                )
                if pad:
                    flat = jnp.concatenate(
                        [flat, jnp.zeros((pad, t), jnp.uint8)], axis=0
                    )
                planes.append(unpack_bitplanes(flat, interpret))
            bits = (
                planes[0] if s == 1
                else jnp.concatenate(planes, axis=1)
            )
            out8 = _matmul_pack(bmat_ref[:], bits, r, interpret)
            for si in range(s):
                q = g * s + si
                tile_o = out8[:, si * t : (si + 1) * t]
                for j in range(r):
                    outs[j][q : q + 1, :] = tile_o[j : j + 1, :]
                csum_ref[q, 0] = _crc_fold_tile(
                    planes[si], tile_o, kb_ref, c, f, r, rp, cb,
                    interpret,
                )

    @jax.jit
    def apply(bmat, kb, *shards):
        b, n = shards[0].shape
        outs = pl.pallas_call(
            kernel,
            grid=(b // SHARDS_SB, n // tile),
            in_specs=[
                pl.BlockSpec(big.shape, lambda i, ch: (0, 0)),
                pl.BlockSpec(kb_np.shape, lambda i, ch: (0, 0, 0)),
            ]
            + [
                pl.BlockSpec((SHARDS_SB, tile), lambda i, ch: (i, ch))
                for _ in range(c)
            ],
            out_specs=[
                pl.BlockSpec((SHARDS_SB, tile), lambda i, ch: (i, ch))
                for _ in range(r)
            ]
            + [
                pl.BlockSpec(
                    (SHARDS_SB, 1, c + r, nb * 32),
                    lambda i, ch: (i, ch, 0, 0),
                )
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, n), jnp.uint8)
                for _ in range(r)
            ]
            + [
                jax.ShapeDtypeStruct(
                    (b, n // tile, c + r, nb * 32), jnp.int32
                )
            ],
            interpret=interpret,
        )(bmat, kb, *shards)
        return list(outs[:r]) + [_csum_pack(outs[r], c, r)]

    return apply, big, kb_np


def gf_encode_csum_bitplane_pallas_shards(
    bitmatrix,
    shards: list,
    csum_block: int,
    interpret: bool | None = None,
) -> tuple[list, jax.Array]:
    """Shards-form fused encode+checksum: c per-shard [..., N] arrays
    in; (R per-shard parity arrays, [B, C+R, N//csum_block] uint32
    zero-init csums) out. Callers gate with
    ``fused_csum_shards_supported``."""
    if interpret is None:
        interpret = platform.pallas_interpret()
    mat = np.ascontiguousarray(np.asarray(bitmatrix, dtype=np.uint8))
    r8, c8 = mat.shape
    lead = shards[0].shape[:-1]
    n = shards[0].shape[-1]
    if c8 != len(shards) * 8:
        raise ValueError(
            f"bitmatrix cols {c8} != shards*8 {len(shards) * 8}"
        )
    tile = _pick_fused_tile(n, csum_block, FUSED_SHARDS_MAX_TILE)
    s = _shards_lane_batch(tile)
    key = (mat.tobytes(), r8, c8, s, tile, csum_block, interpret)
    fn, big, kb = _shards_csum_fn(*key)
    traced = any(isinstance(v, jax.core.Tracer) for v in shards)
    if not traced:
        big = _dev_cached(("zw-shards",) + key[:-1], big)
        kb = _dev_cached(("kb", csum_block), kb)
    b = int(np.prod(lead, initial=1))
    r = r8 // 8
    flat = [jnp.asarray(v).reshape(b, n) for v in shards]
    outs = fn(big, kb, *flat)
    parity = [o.reshape(lead + (n,)) for o in outs[:r]]
    csums = outs[r].reshape(lead + (c8 // 8 + r, n // csum_block))
    return parity, csums


def gf_encode_bitplane_pallas(
    bitmatrix,
    data: jax.Array,
    interpret: bool | None = None,
    fold: int = FOLD,
) -> jax.Array:
    """Fused-tile bitmatrix apply; same contract as
    ``ops.bitplane.gf_encode_bitplane`` for [B, C, N] inputs.
    ``bitmatrix`` must be a concrete [R*8, C*8] array (host-permuted
    once, LRU-cached). ``fold`` is accepted for API compatibility;
    the zero-waste lane batching supersedes it."""
    del fold
    if interpret is None:
        interpret = platform.pallas_interpret()
    mat = np.ascontiguousarray(np.asarray(bitmatrix, dtype=np.uint8))
    r8, c8 = mat.shape
    batch, c, n = data.shape
    if c8 != c * 8:
        raise ValueError(f"bitmatrix cols {c8} != shards*8 {c * 8}")
    pad = (-c) % 4
    key = (mat.tobytes(), r8, c8, pad)
    big = _zw_matrix_cached(*key)
    if not isinstance(data, jax.core.Tracer):
        # eager calls keep a CONCRETE device copy so the stationary
        # matrix uploads once, not per call; traced calls embed the
        # numpy constant in their own trace (caching a device array
        # built under a trace is the tracer-leak this split avoids)
        big = _dev_cached(key, big)
    r = r8 // 8
    tile = _pick_lane_tile(n)
    # VMEM pressure scales with the contraction width (8 * (C+pad)
    # int8 rows of bits plus the int32 accumulator); shrink the lane
    # tile for wide matrices up front. F <= 32 keeps the full 64K
    # width — measured FASTER there (k=32/F=32 at 64K ran 1.5x the
    # shrunken tile); only genuinely wide contractions shrink.
    f = c + pad
    if f > 32:
        while tile > LANE_TILE and tile > (65536 * 32) // f:
            tile //= 2
    s = _pick_lane_batch(batch, tile)
    return _apply_tiled(
        big, data, c, r, s, pad, tile, interpret=interpret
    )
