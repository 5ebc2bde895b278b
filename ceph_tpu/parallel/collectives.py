"""Alternative collective schedules for the EC fan-out: ring parity
accumulation and sequence-parallel CRC.

Two distributed patterns beyond mesh.py's all-reduce encode, mirroring
the scaling-book playbook (pick a mesh, annotate shardings, let XLA
place collectives on ICI):

**Ring parity** (`ring_parity`): the XOR-reduction across the shard
axis as the canonical bandwidth-optimal ring all-reduce — a
reduce-scatter phase (each of sp-1 hops moves ONE 1/sp slice of the
packed parity; after them device d owns the fully-reduced slice) then
an all-gather phase (sp-1 more one-slice hops) — ~2(sp-1)/sp times
the parity bytes per link, the schedule large-model training uses
over ICI. The accumulator travels PACKED (XOR commutes with bit
packing). Bit-exact with ``sharded_encode``'s psum; falls back to
psum when the lane axis doesn't split into sp slices.

**Sequence-parallel CRC32C** (`sharded_crc32c`): the long-object axis
(SURVEY.md §5.7 — object size is this framework's sequence length)
sharded across devices. CRC is position-dependent, so naive sharding
breaks; linearity saves it: with per-device fold tensors pre-composed
with the zero-gap transition for the device's suffix length
(crc32c.zero_gap_matrix), each device folds its local bytes and the
combine is a single 32-bit-per-block XOR-allreduce:

    crc(block) = mod2( Σ_d  A_{suffix(d)} @ fold(bytes_d) )

One object of any length (left-padded with zero bytes to the mesh
granularity — a no-op for the fold, since zeros from the zero register
stay zero, while the init contribution uses the true length) hashes
with one psum of [B, 32] ints — the deep-scrub integrity pass for
objects too large for one chip's HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ceph_tpu.ops.bitplane import pack_bits

from .mesh import mesh_program, partial_parity_counts

#: fixed fold granularity for the sequence-parallel CRC scan: keeps
#: the fold-tensor constant bounded (<= 16 MiB) no matter how long
#: the object is — a monolithic per-segment tensor would be 256x the
#: segment size and OOM exactly on the large objects this op exists for
FOLD_BLOCK_MAX = 65536


def ring_parity(
    mesh: Mesh, bitmatrix: jax.Array, data: jax.Array
) -> jax.Array:
    """[B, k, N] uint8 -> [B, m, N] parity; XOR-reduction over the
    ``sp`` axis as ring reduce-scatter + all-gather."""
    sp = mesh.shape["sp"]
    n = data.shape[-1]
    if sp == 1 or n % sp:
        # no ring to run / lane axis unsliceable: psum is the schedule
        from .mesh import sharded_encode

        return sharded_encode(mesh, bitmatrix, data)
    return _ring_parity_fn(mesh, n)(bitmatrix, data)


#: the ring encode's program name on the device is ``jit_local``: the
#: benchmark's mesh cell finds its codec program in a trace by that
#: text (``benchmark/workloads/rs84-4m-mesh4.write.json``); pinned so a
#: rename of the inner function cannot empty ``codec_roofline``
RING_PROGRAM_NAME = "local"


@functools.lru_cache(maxsize=64)
def _ring_parity_fn(mesh: Mesh, n: int):
    """The ring program for lane width ``n`` (the slice width is
    static in it), built once per (mesh, n)."""
    sp = mesh.shape["sp"]
    w = n // sp
    fwd = [(d, (d + 1) % sp) for d in range(sp)]

    def local(bmat_cols: jax.Array, shards: jax.Array) -> jax.Array:
        acc = partial_parity_counts(bmat_cols, shards)
        # pack BEFORE the ring: hop traffic is parity bytes, not the
        # 8x bit expansion
        partial = pack_bits((acc & 1).astype(jnp.uint8))  # [b, m, n]
        d = jax.lax.axis_index("sp")

        def slice_at(x, j):
            return jax.lax.dynamic_slice_in_dim(x, j * w, w, axis=-1)

        # -- reduce-scatter: at step t device d sends its accumulated
        # slice (d - t) mod sp and folds its own contribution into the
        # slice arriving from d-1. After sp-1 steps it owns the FULLY
        # reduced slice (d + 1) mod sp.
        def rs_step(t, carry):
            recv = jax.lax.ppermute(carry, "sp", fwd)
            return jnp.bitwise_xor(
                recv, slice_at(partial, (d - t - 1) % sp)
            )

        # carry starts as this device's own slice d: at step t the
        # carry IS the partially-reduced slice (d - t) mod sp
        mine = jax.lax.fori_loop(
            0, sp - 1, rs_step, slice_at(partial, d)
        )
        my_slice = (d + 1) % sp

        # -- all-gather: circulate the reduced slices; each device
        # scatters every arriving slice into its output at the slice
        # index it belongs to ((d + 1 - t) mod sp at step t).
        out = jax.lax.dynamic_update_slice_in_dim(
            jnp.zeros_like(partial), mine, my_slice * w, axis=-1
        )

        def ag_step(t, carry):
            out, moving = carry
            moving = jax.lax.ppermute(moving, "sp", fwd)
            src = (d - t) % sp  # slice index the arrival carries
            out = jax.lax.dynamic_update_slice_in_dim(
                out, moving, src * w, axis=-1
            )
            return out, moving

        out, _ = jax.lax.fori_loop(0, sp - 1, ag_step, (out, mine))
        return out

    return mesh_program(
        local,
        mesh,
        in_specs=(P(None, "sp"), P("dp", "sp", None)),
        out_specs=P("dp", None, None),
        name=RING_PROGRAM_NAME,
    )


def _suffix_transforms(n_shards: int, local_bytes: int) -> np.ndarray:
    """[D, 32, 32] with row d = A_{(D-1-d)*local}: the zero-gap
    transition carrying device d's local remainder across everything
    to its right."""
    from ceph_tpu.checksum.crc32c import mat32, zero_gap_matrix

    out = np.empty((n_shards, 32, 32), dtype=np.int8)
    for d in range(n_shards):
        out[d] = mat32(zero_gap_matrix((n_shards - 1 - d) * local_bytes))
    return out


_fold_cache: dict = {}
_suffix_cache: dict = {}


def _pick_geometry(total: int, n_dev: int) -> tuple[int, int, int]:
    """(fb, npieces, padded): fold-block chosen FIRST (padding with
    zeros is free), so awkward lengths never degenerate into tiny
    folds — the object pads up to n_dev * npieces * fb."""
    local = -(-total // n_dev)
    fb = min(FOLD_BLOCK_MAX, max(64, ((local + 63) // 64) * 64))
    npieces = -(-local // fb)
    return fb, npieces, n_dev * npieces * fb


def _fold_consts(fb: int):
    """(K_fb, A_fb), cached per fold-block size ONLY — the big tensor
    (fb*256 bytes) has a handful of distinct sizes, never one per
    object length. Trace guard per the _device_fold discipline."""
    from ceph_tpu.checksum.crc32c import (
        _pick_chunk,
        fold_tensor,
        mat32,
        zero_gap_matrix,
    )
    from ceph_tpu.utils.platform import trace_state_clean

    def build():
        return (
            jnp.asarray(fold_tensor(fb, _pick_chunk(fb)), jnp.int8),
            jnp.asarray(mat32(zero_gap_matrix(fb)), jnp.int32),
        )

    if not trace_state_clean():
        return build()
    if fb not in _fold_cache:
        _fold_cache[fb] = build()
    return _fold_cache[fb]


def _suffix_consts(n_dev: int, local_bytes: int):
    """Suffix transform stack — [D, 32, 32] int8, tiny; cached per
    geometry."""
    from ceph_tpu.utils.platform import trace_state_clean

    if not trace_state_clean():
        return jnp.asarray(_suffix_transforms(n_dev, local_bytes))
    key = (n_dev, local_bytes)
    if key not in _suffix_cache:
        _suffix_cache[key] = jnp.asarray(
            _suffix_transforms(n_dev, local_bytes)
        )
    return _suffix_cache[key]


def sharded_crc32c(
    mesh: Mesh,
    data: jax.Array,  # [B, L] uint8, L sharded over ``axes``
    init: int = 0xFFFFFFFF,
    axes: tuple[str, ...] = ("dp", "sp"),
) -> jax.Array:
    """Per-block CRC32C with the BLOCK axis sharded across the WHOLE
    mesh (both axes by default — this op has no stripe axis to give
    ``dp``, so anything less duplicates data and FLOPs). Each device
    scans its segment in FOLD_BLOCK-bounded pieces

        r <- (r @ A_fb^T) xor fold(piece)      (remainder chaining)

    so the fold-tensor constant stays <= 16 MiB for any object length.
    Returns [B] uint32."""
    from ceph_tpu.checksum.crc32c import (
        acc_to_crc32,
        init_bits32,
        zero_gap_matrix,
    )

    nblocks, total = data.shape
    n_dev = 1
    for a in axes:
        n_dev *= mesh.shape[a]
    fb, npieces, padded = _pick_geometry(total, n_dev)
    # Left-pad with zero bytes to the fold geometry: a no-op for the
    # zero-init fold; the init contribution below uses TRUE length.
    if padded != total:
        data = jnp.pad(data, ((0, 0), (padded - total, 0)))
    k_fb, a_fb = _fold_consts(fb)
    local_bytes = padded // n_dev
    suffix = _suffix_consts(n_dev, local_bytes)
    acc = _sharded_crc_fn(mesh, axes, npieces, fb)(
        k_fb, a_fb, suffix, data
    )
    a_true = jnp.asarray(
        np.frombuffer(
            zero_gap_matrix(total), dtype=np.uint8
        ).reshape(32, 32),
        jnp.int32,
    )
    acc = acc + (a_true @ init_bits32(init).astype(jnp.int32))
    return acc_to_crc32(acc)


@functools.lru_cache(maxsize=64)
def _sharded_crc_fn(mesh: Mesh, axes: tuple, npieces: int, fb: int):
    from ceph_tpu.checksum.crc32c import fold_blocks_bits

    def local(kf, afb, sfx, blocks):
        pieces = blocks.reshape(blocks.shape[0], npieces, fb)

        def step(r, piece):
            folded = fold_blocks_bits(kf, piece) & 1
            r = ((r @ afb.T) + folded) & 1
            return r, None

        r0 = jnp.zeros((blocks.shape[0], 32), jnp.int32)
        local_bits, _ = jax.lax.scan(
            step, r0, jnp.swapaxes(pieces, 0, 1)
        )
        d = jax.lax.axis_index(axes)
        a_sfx = jax.lax.dynamic_index_in_dim(
            sfx, d, axis=0, keepdims=False
        ).astype(jnp.int32)
        carried = local_bits @ a_sfx.T  # [B, 32] suffix-shifted
        return jax.lax.psum(carried, axes)  # one 32-int all-reduce

    return mesh_program(
        local,
        mesh,
        in_specs=(P(), P(), P(), P(None, axes)),
        out_specs=P(),
    )
