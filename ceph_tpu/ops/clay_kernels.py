"""Pallas kernels for CLAY fractional repair — general d, any chunk.

The XLA formulation of the repair stages (stack rows -> plane-permute
gather -> fused pair transform; pair-combine -> stack -> inverse
permute) pays every intermediate against HBM: ~500 MB of traffic to
repair 45 MB of helper bytes.  These kernels express the SAME algebra
as lane-sliced networks: every (node, plane-digit) class of a helper
row is one 2D ``(sb, lb)`` lane block whose companion block is another
ref of the same pallas_call, so each pair transform is a handful of
packed-int32 VPU ops and HBM sees each helper byte once in, each
recovered byte once out.

v2 design (round 9), replacing the aloof-free whole-chunk kernels:

- **General d.**  Repair with ``k <= d < k+m-1`` leaves ``k+m-1-d``
  helper nodes "aloof".  Aloof nodes contribute no helper bytes; their
  uncoupled values come out of the per-score-group inner-MDS decodes
  and re-enter the NEXT group's solve as known rows — the B1/B2
  helper split of ``repair_one_lost_chunk`` (ErasureCodeClay.cc:
  454-699).  The kernel computes every pair transform that does not
  depend on an aloof U (B1) and emits the helper's own coupled value
  as a placeholder for the few that do (B2); the codec patches those
  between group decodes (codecs/clay.py) — they are a 1/q fraction of
  one row per aloof node, far too small to earn a kernel.
- **Plane-blocked streaming.**  The round-7 kernels held the WHOLE
  output chunk per grid step, capping ``sub_chunk_no * sc`` at the
  1 Mi-lane VMEM scatter budget (a 1 MiB-chunk (8,4,d=11) repair —
  the flagship geometry — already overflowed it).  Now every ref is a
  2D ``(sb, lb)`` lane block with ``lb | sc``; the grid walks the
  repair-plane lane space and the per-class index maps do the digit
  arithmetic, so VMEM per step is ``refs * sb * lb`` bytes no matter
  how large ``sub_chunk_no * sc`` grows.  ``supported()`` therefore
  carries NO chunk-size cap any more — only lane alignment and a ref
  budget.
- **Any pair algebra.**  Coefficients are static Python ints baked
  into the kernel as shift/mask peasant ladders on packed int32 lanes
  (Mosaic cannot shift i8 vectors); the canonical RS(2,2) coupling
  reduces to the one-step ``U = C ^ 2*(C_hi^C_lo)`` /
  ``C = C_x ^ inv2*(C_x^U_x)`` fusions, anything else takes the
  general ladder.  The old ``_canonical_pair_algebra`` routing gate
  is gone.

Geometry conventions (see codecs/clay.py): nodes live on a q x t
grid; the lost node is (x_l, y_l); repair planes are the sub-chunks
whose digit y_l equals x_l, indexed 0..r-1 in ascending plane order
(r = sub_chunk_no / q).  Changing digit ``y`` of a repair plane by
``delta`` moves its repair index by ``delta * stride(y)`` where
``stride(y) = q ** #{y' > y, y' != y_l}`` — all static, which is what
lets DMA index maps do every gather and scatter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .pallas_encode import bitcast_i32_to_u8, bitcast_u8_to_i32

SB = 8   # minimum stripes per block (sublane granularity)
#: per-grid-step VMEM budget in bytes across all refs: blocks are
#: (sb, lb) u8 lanes; lb shrinks (halving, floor 128) until the step
#: fits.  4 MiB leaves headroom beside the double-buffered pipeline.
STEP_BYTES = 4 << 20
#: ref-count cap: (t-1)*q*(q+1) in+out refs for the uncoupled kernel
#: (each of the (t-1)*q helper rows is read once per companion digit
#: class).  Mosaic compiles ~64 refs comfortably; wider geometries
#: fall back to the XLA paths.
MAX_REFS = 64
#: the device events' names (``%<name>.<n>`` in a trace): pinned, as
#: ``pallas_encode``'s are, so that a trace tells the two pair-transform
#: stages of a repair from the inner MDS decode between them
UNCOUPLED_KERNEL_NAME = "_clay_uncoupled"
COUPLE_KERNEL_NAME = "_clay_couple"


def supported(b: int, sc: int, q: int, t: int) -> bool:
    """Kernel preconditions: batch blocks on sublanes, plane packets
    lane-align, and the ref fan-out stays within the Mosaic budget.
    Unlike the round-7 kernels there is NO ``sub_chunk_no * sc`` cap:
    blocks are fixed-size lane slices, so any chunk size streams."""
    return (
        b % SB == 0
        and sc % 128 == 0
        and q >= 2
        and t >= 2
        and (t - 1) * q * (q + 1) <= MAX_REFS
    )


def _pick_sb(b: int) -> int:
    """16 measured ~1 GB/s over 8 on the round-7 kernels (fewer DMA
    grid steps); fall back to the sublane minimum otherwise."""
    return 16 if b % 16 == 0 else SB


def _pick_lb(sc: int, n_refs: int, sb: int) -> int:
    """Largest lane-block dividing ``sc`` that keeps one grid step's
    resident refs within STEP_BYTES (halving preserves divisibility;
    128 always divides sc per ``supported``)."""
    lb = sc
    while lb >= 256 and lb % 2 == 0 and n_refs * sb * lb > STEP_BYTES:
        lb //= 2
    if lb % 128 or n_refs * sb * lb > STEP_BYTES:
        lb = 128
    return lb


# ------------------------------------------------------- packed GF ops
def _mul2_i32(xi):
    """Per-byte GF(2^8)/0x11D multiply-by-2 on packed int32 lanes."""
    return (
        ((xi & jnp.int32(0x7F7F7F7F)) << jnp.int32(1))
        ^ (((xi >> jnp.int32(7)) & jnp.int32(0x01010101))
           * jnp.int32(0x1D))
    )


def _div2_i32(xi):
    """Per-byte multiply by inv(2) = 142 on packed int32 lanes."""
    return (
        ((xi >> jnp.int32(1)) & jnp.int32(0x7F7F7F7F))
        ^ ((xi & jnp.int32(0x01010101)) * jnp.int32(0x8E))
    )


def _mulc_i32(xi, c: int):
    """Per-byte GF(2^8) multiply by the static constant ``c`` — the
    shift/mask peasant ladder, bit-length many _mul2 steps."""
    if c == 0:
        return jnp.zeros_like(xi)
    acc = None
    cur = xi
    cc = c
    while cc:
        if cc & 1:
            acc = cur if acc is None else acc ^ cur
        cc >>= 1
        if cc:
            cur = _mul2_i32(cur)
    return acc


def _pair_i32(a, b, c0: int, c1: int):
    """``c0*a ^ c1*b`` with the canonical coupling coefficients fused
    to single mul2/div2 steps.  ``a``/``b`` may be None (a statically
    zero operand — shortened virtual nodes)."""
    if a is None and b is None:
        return None  # two virtual (zero) nodes pair to zero
    if a is None:
        return _mulc_i32(b, c1)
    if b is None:
        return _mulc_i32(a, c0)
    if (c0, c1) == (1, 0):
        return a
    if (c0, c1) == (0, 1):
        return b
    if (c0, c1) == (3, 2):
        return a ^ _mul2_i32(a ^ b)
    if (c0, c1) == (2, 3):
        return b ^ _mul2_i32(a ^ b)
    if (c0, c1) == (143, 142):
        return a ^ _div2_i32(a ^ b)
    if (c0, c1) == (142, 143):
        return b ^ _div2_i32(a ^ b)
    return _mulc_i32(a, c0) ^ _mulc_i32(b, c1)


# -------------------------------------------------- uncoupled solve (a)
@functools.lru_cache(maxsize=64)
def _uncoupled_fn(
    q: int,
    strides: tuple[int, ...],
    kinds: tuple[tuple[str, ...], ...],
    pair_fwd: tuple[tuple[int, int], tuple[int, int]],
    r: int,
    sc: int,
    sb: int,
    interpret: bool,
):
    """Stage-a kernel builder.  One ref per (row, real member, digit
    class) — q index-mapped views of each helper array — and one
    ``[B, Mj, q, stride*sc]`` output per non-aloof member, so every
    pair transform finds both operands resident without a gather.

    ``strides[ri]`` is row ri's repair-index digit stride; ``kinds``
    marks members 'r'eal / 'v'irtual (shortened, statically zero) /
    'a'loof (no bytes; B2 classes emit the helper's C as the patch
    placeholder); ``pair_fwd`` the (self, partner) coefficients for
    the hi/lo pair member."""
    n_rows = len(kinds)
    in_plan: list[tuple[int, int, int]] = []   # (row, x, zv)
    in_idx: dict[tuple[int, int, int], int] = {}
    out_plan: list[tuple[int, int]] = []       # (row, x)
    for ri in range(n_rows):
        for x in range(q):
            if kinds[ri][x] == "r":
                for zv in range(q):
                    in_idx[(ri, x, zv)] = len(in_plan)
                    in_plan.append((ri, x, zv))
            if kinds[ri][x] != "a":
                out_plan.append((ri, x))
    n_in = len(in_plan)
    lb = _pick_lb(sc, n_in + len(out_plan) * q, sb)

    def kernel(*refs):
        ins, outs = refs[:n_in], refs[n_in:]
        cache: dict[tuple[int, int, int], jax.Array] = {}

        def block(ri, x, zv):
            key = (ri, x, zv)
            if key not in cache:
                cache[key] = bitcast_u8_to_i32(
                    ins[in_idx[key]][:], interpret
                )
            return cache[key]

        for oi, (ri, x) in enumerate(out_plan):
            for zv in range(q):
                if zv == x:
                    # dot plane: U = C (virtual: U = 0)
                    if kinds[ri][x] == "v":
                        outs[oi][:, 0, zv, :] = jnp.zeros(
                            (sb, lb), jnp.uint8
                        )
                        continue
                    u = block(ri, x, zv)
                elif kinds[ri][x] == "r" and kinds[ri][zv] == "a":
                    # B2 class: companion U is decoded later — emit C
                    # as the placeholder the codec's patch consumes.
                    u = block(ri, x, zv)
                else:
                    a = (
                        block(ri, x, zv)
                        if kinds[ri][x] == "r" else None
                    )
                    bb = (
                        block(ri, zv, x)
                        if kinds[ri][zv] == "r" else None
                    )
                    c0, c1 = pair_fwd[0] if x > zv else pair_fwd[1]
                    u = _pair_i32(a, bb, c0, c1)
                    if u is None:  # virtual pair: statically zero
                        outs[oi][:, 0, zv, :] = jnp.zeros(
                            (sb, lb), jnp.uint8
                        )
                        continue
                outs[oi][:, 0, zv, :] = bitcast_i32_to_u8(u, interpret)

    @jax.jit
    def apply(*helpers):
        b = helpers[0].shape[0]
        in_specs = []
        operands = []
        helpers_by_rx = {}
        hi = 0
        for ri in range(n_rows):
            for x in range(q):
                if kinds[ri][x] == "r":
                    helpers_by_rx[(ri, x)] = helpers[hi]
                    hi += 1
        for ri, x, zv in in_plan:
            s = strides[ri]
            spb = s * sc // lb
            operands.append(helpers_by_rx[(ri, x)])
            in_specs.append(pl.BlockSpec(
                (sb, lb),
                lambda bi, w, zv=zv, spb=spb: (
                    bi, (w // spb) * (q * spb) + zv * spb + w % spb
                ),
            ))
        out_specs = []
        out_shapes = []
        for ri, _x in out_plan:
            s = strides[ri]
            spb = s * sc // lb
            mj = r // (q * s)
            out_specs.append(pl.BlockSpec(
                (sb, 1, q, lb),
                lambda bi, w, spb=spb: (bi, w // spb, 0, w % spb),
            ))
            out_shapes.append(
                jax.ShapeDtypeStruct((b, mj, q, s * sc), jnp.uint8)
            )
        outs = pl.pallas_call(
            kernel,
            grid=(b // sb, r * sc // (q * lb)),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shapes,
            interpret=interpret,
            name=UNCOUPLED_KERNEL_NAME,
        )(*operands)
        return [o.reshape(b, r * sc) for o in outs]

    return apply


def uncoupled_rows(
    q: int,
    strides: tuple[int, ...],
    kinds: tuple[tuple[str, ...], ...],
    pair_fwd,
    helpers: list,
    r: int,
    sc: int,
    interpret: bool = False,
):
    """helpers: one [B, r*sc] array per REAL member, (row, x) order.
    Returns one [B, r*sc] uncoupled-U array per non-aloof member in
    the same order (virtual members included — the inner MDS counts
    them as known rows; B2 classes hold the C placeholder)."""
    fn = _uncoupled_fn(
        q, tuple(strides),
        tuple(tuple(row) for row in kinds),
        (tuple(pair_fwd[0]), tuple(pair_fwd[1])),
        r, sc, _pick_sb(helpers[0].shape[0]), interpret,
    )
    return fn(*helpers)


# ---------------------------------------------- couple + scatter (c)
@functools.lru_cache(maxsize=64)
def _couple_scatter_fn(
    q: int,
    x_l: int,
    kinds: tuple[str, ...],
    pair_inv: tuple[tuple[int, int], tuple[int, int]],
    seq: int,
    r: int,
    sc: int,
    sb: int,
    interpret: bool,
):
    """Stage-c kernel builder: the lost row's q decoded U arrays plus
    its q-1 helper arrays in, the recovered chunk out.  Repair run j
    (``seq`` consecutive repair planes) produces output planes
    ``[j*q*seq, (j+1)*q*seq)`` — member x owns the x-th ``seq`` planes
    of the run — so the output view ``[B, num_seq, q, seq*sc]`` makes
    the whole scatter a rectangular block walk at any chunk size.

    ``kinds[x]`` is 'r'/'v' for the helper members (x_l's slot is
    ignored); ``pair_inv`` the (C_helper, U) coefficients recovering
    the lost coupled value, hi/lo."""
    helper_x = [
        x for x in range(q) if x != x_l and kinds[x] == "r"
    ]
    n_in = q + len(helper_x)
    lb = _pick_lb(sc, n_in + q, sb)
    spb = seq * sc // lb
    num_seq = r // seq
    hidx = {x: q + i for i, x in enumerate(helper_x)}

    def kernel(*refs):
        ins, out = refs[:n_in], refs[n_in]
        for x in range(q):
            u = bitcast_u8_to_i32(ins[x][:], interpret)
            if x == x_l:
                o = u
            else:
                c0, c1 = pair_inv[0] if x > x_l else pair_inv[1]
                h = (
                    bitcast_u8_to_i32(ins[hidx[x]][:], interpret)
                    if kinds[x] == "r" else None
                )
                o = _pair_i32(h, u, c0, c1)
            out[:, 0, x, :] = bitcast_i32_to_u8(o, interpret)

    @jax.jit
    def apply(*arrs):
        b = arrs[0].shape[0]
        return pl.pallas_call(
            kernel,
            grid=(b // sb, r * sc // lb),
            in_specs=[
                pl.BlockSpec((sb, lb), lambda bi, w: (bi, w))
                for _ in range(n_in)
            ],
            out_specs=pl.BlockSpec(
                (sb, 1, q, lb),
                lambda bi, w: (bi, w // spb, 0, w % spb),
            ),
            out_shape=jax.ShapeDtypeStruct(
                (b, num_seq, q, seq * sc), jnp.uint8
            ),
            interpret=interpret,
            name=COUPLE_KERNEL_NAME,
        )(*arrs).reshape(b, q * r * sc)

    return apply


def couple_scatter(
    q: int,
    x_l: int,
    kinds,
    pair_inv,
    udec: list,
    helpers: list,
    seq: int,
    r: int,
    sc: int,
    interpret: bool = False,
):
    """udec: q decoded lost-row U arrays [B, r*sc], ascending x;
    helpers: the REAL lost-row helper arrays [B, r*sc], ascending x
    with x_l and virtual members absent.  Returns the recovered chunk
    [B, sub_chunk_no*sc]."""
    fn = _couple_scatter_fn(
        q, x_l, tuple(kinds),
        (tuple(pair_inv[0]), tuple(pair_inv[1])),
        seq, r, sc, _pick_sb(udec[0].shape[0]), interpret,
    )
    return fn(*udec, *helpers)
