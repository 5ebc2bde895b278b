"""Pallas TPU kernel: batched CRC32C as an in-VMEM GF(2) fold.

The einsum formulation (checksum/crc32c.py) is algebraically right but
lets XLA materialize the unpacked bit tensor — an 8x expansion of the
input round-tripping HBM (measured ~33 GB/s hashed on v5e). This
kernel applies the EC encode kernel's discipline (ops/pallas_encode):
unpack bits in registers, one int8 MXU matmul per tile, never write
bits to memory — HBM traffic is the data itself plus a [B, 32] int32
accumulator.

Shape: blocks ride the sublane axis; the shared packed-int32 unpack
(ops/pallas_encode.unpack_bitplanes) produces planes as ROWS
(plane b, block), so the fold is 8 per-plane dots:

    acc[bt, :] = Σ_sub Σ_b  bits_b[bt, SUB] @ K_T[sub][b*SUB:(b+1)*SUB, 32]

with the fold tensor K (checksum/crc32c.fold_tensor) transposed and
permuted host-side to plane-major row order (row b*SUB + j = bit b
of byte j within the sub-block). Contraction per dot is SUB, not
SUB*8 — a streamed MXU column carries 16 data bytes instead of 8.
Long blocks fold across a second grid axis that revisits the
accumulator (read-modify-write on out_ref); parity (&1), the
init-register contribution, and the 32-bit pack are a tiny [B, 32]
epilogue after the kernel, inside the same jit (``_fold_tiled``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

#: bytes of one block folded per grid step (contraction tile); with
#: the 8-plane int32 unpack intermediates, SUB x BLOCK_TILE is the
#: VMEM budget knob. 2048 x 512 measured best overall on v5e
#: (203/171/221 GB/s hashed at 4/16/64 KiB blocks vs ~33 for the
#: einsum path); larger SUB re-fetches more fold tensor per data byte
#: on multi-sub blocks, larger BT blows the 16M scoped-vmem limit.
SUB_BYTES = 2048
#: blocks per kernel instance (sublane tile)
BLOCK_TILE = 512


def plane_fold_kb(block_bytes: int) -> np.ndarray:
    """[8, 32, block_bytes] int8 per-plane fold matrices for ONE
    zero-init csum block: kb[b][:, p] = the 32 crc-register bits
    contributed by bit b of byte p of the block.

    This is the fold machinery the fused encode+checksum epilogue
    (ops/pallas_encode.gf_encode_csum_bitplane_pallas) keeps stationary
    in VMEM: the encode kernel already holds each tile's bit planes in
    registers, so per-block CRCs are 8 extra [rows, block] x kb[b]^T
    dots — no second unpack, no second HBM pass. The block axis is
    MINOR: a 32-wide minor dimension pads to 128 lanes in VMEM, which
    made the cb=4096 table 4 MiB per buffer (8 MiB double-buffered,
    half of v5e's 16 MiB scoped VMEM); this layout is the exact int8
    (32, 128) tile — 1 MiB per buffer."""
    from .crc32c import _pick_chunk, fold_tensor

    c = _pick_chunk(block_bytes)
    kf = fold_tensor(block_bytes, c)  # [S, 32, c*8]
    flat = np.transpose(kf, (1, 0, 2)).reshape(32, block_bytes * 8)
    out = np.empty((8, 32, block_bytes), dtype=np.int8)
    for b in range(8):
        out[b] = flat[:, b::8]
    return out


def _plane_major_kt(k_fold: np.ndarray, c: int) -> np.ndarray:
    """[S, 32, c*8] fold tensor -> [nsub, SUB*8, 32] transposed K with
    rows in plane-major order (row b*SUB + j = bit b of byte j within
    the sub-block)."""
    s, _, c8 = k_fold.shape
    assert c8 == c * 8
    block_bytes = s * c
    sub = min(SUB_BYTES, block_bytes)
    assert block_bytes % sub == 0
    nsub = block_bytes // sub
    # K columns are (byte j within chunk, bit b) at index j*8+b; build
    # a flat [32, block_bytes*8] byte-major matrix first.
    flat = np.transpose(k_fold, (1, 0, 2)).reshape(32, block_bytes * 8)
    out = np.empty((nsub, sub * 8, 32), dtype=np.int8)
    for n in range(nsub):
        seg = flat[:, n * sub * 8 : (n + 1) * sub * 8]  # [32, sub*8]
        rows = np.empty((sub * 8, 32), dtype=np.int8)
        for b in range(8):
            # plane b: rows b*sub + j  <-  seg column j*8+b
            rows[b * sub : (b + 1) * sub, :] = seg[:, b::8].T
        out[n] = rows
    return out


def _make_kernel(bt: int, sub: int, interpret: bool):
    """Round-3 kernel, sharing the encode kernel's unpack
    (ops/pallas_encode.unpack_bitplanes): blocks ride sublanes, so
    the sublane bitcast packs 4 BLOCKS per int32 lane — each block's
    bits stay inside its own byte lane. Planes land as rows
    (b, block), so the fold becomes 8 per-plane dots against aligned
    [SUB, 32] slices of the fold tensor — contraction SUB instead of
    SUB*8, which doubles the useful bytes per streamed MXU column
    (16 vs 8)."""

    def kernel(kt_ref, data_ref, out_ref):
        from ceph_tpu.ops.pallas_encode import unpack_bitplanes

        d = data_ref[...]  # [BT, SUB] uint8
        bits = unpack_bitplanes(d, interpret)  # [8BT, SUB] (b, block)
        kt = kt_ref[0]  # [SUB*8, 32] rows b*SUB + j
        partial = jax.lax.dot_general(
            bits[0:bt], kt[0:sub],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        for b in range(1, 8):
            partial += jax.lax.dot_general(
                bits[b * bt : (b + 1) * bt],
                kt[b * sub : (b + 1) * sub],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )  # [BT, 32]
        s = pl.program_id(1)

        @pl.when(s == 0)
        def _init():
            out_ref[...] = partial

        @pl.when(s != 0)
        def _acc():
            out_ref[...] += partial

    return kernel


@functools.partial(
    jax.jit, static_argnames=("block_bytes", "interpret")
)
def _fold_tiled(kt, a_total, data, init, block_bytes, interpret=False):
    """The whole checksum as one program a (blocks, block_bytes) shape:
    the Pallas fold, the init register's journey across the block
    (``a_total``), the mod 2 and the bit pack. ``init`` is an argument,
    so another init compiles nothing."""
    from .crc32c import acc_to_crc32, init_bits32

    nblocks = data.shape[0]
    nsub = kt.shape[0]
    sub = block_bytes // nsub
    bt = min(BLOCK_TILE, nblocks)
    acc = pl.pallas_call(
        _make_kernel(bt, sub, interpret),
        grid=(nblocks // bt, nsub),
        in_specs=[
            pl.BlockSpec((1,) + kt.shape[1:], lambda i, s: (s, 0, 0)),
            pl.BlockSpec((bt, sub), lambda i, s: (i, s)),
        ],
        out_specs=pl.BlockSpec((bt, 32), lambda i, s: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nblocks, 32), jnp.int32),
        interpret=interpret,
    )(kt, data)
    acc = acc + (
        a_total.astype(jnp.int32) @ init_bits32(init).astype(jnp.int32)
    )
    return acc_to_crc32(acc)


@functools.lru_cache(maxsize=16)
def _host_consts(block_bytes: int, c: int):
    from .crc32c import fold_tensor, mat32, zero_gap_matrix

    return (
        _plane_major_kt(fold_tensor(block_bytes, c), c),
        mat32(zero_gap_matrix(block_bytes)).astype(np.int8),
    )


_device_cache: dict = {}


def _device_consts(block_bytes: int, c: int):
    """(K^T plane-major, A_total) on the device, uploaded once a block
    size. Under an active trace (``crc32c_device`` inside a jit or a
    shard_map) an upload would be a tracer, which must NOT be cached:
    the host arrays go in as they are and become compile-time
    constants (``crc32c._device_fold``'s rule)."""
    from ceph_tpu.utils.platform import trace_state_clean

    if not trace_state_clean():
        return _host_consts(block_bytes, c)
    key = (block_bytes, c)
    if key not in _device_cache:
        kt, a_total = _host_consts(block_bytes, c)
        _device_cache[key] = (jnp.asarray(kt), jnp.asarray(a_total))
    return _device_cache[key]


def supported(nblocks: int, block_bytes: int) -> bool:
    """Tileable: enough blocks to fill a sublane tile evenly, a
    lane-aligned sub-fold, and a block count the sublane bitcast can
    pack (4 blocks per int32 lane)."""
    sub = min(SUB_BYTES, block_bytes)
    return (
        block_bytes % sub == 0
        and sub % 256 == 0
        and nblocks % min(BLOCK_TILE, nblocks) == 0
        and nblocks % 4 == 0
        and nblocks >= 8
    )


def tile_blocks(nblocks: int) -> int:
    """The least block count >= ``nblocks`` that ``supported`` takes:
    what a caller that owns its stack pads to (zero blocks, their
    words dropped) instead of leaving the kernel for a count."""
    if nblocks > BLOCK_TILE:
        return -(-nblocks // BLOCK_TILE) * BLOCK_TILE
    return max(8, -(-nblocks // 4) * 4)


def crc32c_fold_pallas(
    data: jax.Array,  # [B, block_bytes] uint8
    init,
    interpret: bool | None = None,
) -> jax.Array:
    """Per-block CRC32C accumulator path on the MXU; same contract as
    the einsum kernel in checksum/crc32c: ONE compiled program a call
    (``_fold_tiled``), no eager op before or after it."""
    from .crc32c import _pick_chunk

    if interpret is None:
        from ceph_tpu.utils import platform

        interpret = platform.pallas_interpret()
    block_bytes = data.shape[1]
    kt, a_total = _device_consts(block_bytes, _pick_chunk(block_bytes))
    if isinstance(init, (int, np.integer)):
        # a host scalar rides the call's own argument upload; a weak
        # Python int would overflow int32 and key a second program
        init = np.uint32(int(init) & 0xFFFFFFFF)
    return _fold_tiled(
        kt, a_total, data, init, block_bytes, interpret=interpret
    )
