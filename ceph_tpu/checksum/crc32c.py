"""CRC32C as a batched GF(2) matrix fold on the MXU.

The reference dispatches to per-arch carryless-multiply kernels
(src/common/crc32c.cc:19-32, src/arch/intel.c). TPUs have no clmul, so
we use linearity instead (SURVEY.md §7 "Hard parts"): with the
reflected Castagnoli polynomial, the CRC register after a message is

    crc(init, msg) = A_L @ init  ⊕  Σ_i  K_i @ bits(chunk_i)

over GF(2), where A_L is the 32x32 zero-message transition for L bytes
and K_i folds chunk i's bits directly to its final-position remainder
contribution. All K_i stack into one [S, 32, c*8] tensor, so a whole
batch of blocks is ONE int8 einsum with int32 accumulation (exact:
fan-in ≤ S*c*8 < 2^31) followed by ``& 1`` — the same mod-2 MXU
discipline as the EC engine (ceph_tpu.ops.bitplane).

Bit convention is LSB-first everywhere (bit b of byte j sits at index
j*8+b), matching the reflected-CRC register order so no bit reversal
is ever materialised.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .host import apply_columns, fold_words
from .reference import CRC32C_POLY_REFLECTED, crc32c_ref

CHUNK_BYTES = 64  # fold granularity; 512-bit MXU contraction per chunk


def _bits32(v: int) -> np.ndarray:
    return np.array([(v >> i) & 1 for i in range(32)], dtype=np.uint8)


@functools.lru_cache(maxsize=None)
def byte_step_matrix() -> bytes:
    """32x32 GF(2) matrix M: register transition for one ZERO byte.

    Column j = register after feeding one zero byte starting from the
    unit register e_j (the transition is linear, so unit responses
    define it).
    """
    m = np.zeros((32, 32), dtype=np.uint8)
    for j in range(32):
        m[:, j] = _bits32(crc32c_ref(1 << j, b"\x00"))
    return m.tobytes()


def _mat(b: bytes) -> np.ndarray:
    return np.frombuffer(b, dtype=np.uint8).reshape(32, 32)


#: public alias: consumers decoding zero_gap_matrix/byte_step_matrix
#: payloads must share ONE layout definition
mat32 = _mat


@functools.lru_cache(maxsize=None)
def zero_gap_matrix(nbytes: int) -> bytes:
    """A_n = M^n: transition across n zero bytes (square-and-multiply)."""
    result = np.eye(32, dtype=np.uint8)
    base = _mat(byte_step_matrix())
    n = nbytes
    while n:
        if n & 1:
            result = (result @ base) & 1
        base = (base @ base) & 1
        n >>= 1
    return result.astype(np.uint8).tobytes()


@functools.lru_cache(maxsize=None)
def zero_gap_columns(nbytes: int) -> np.ndarray:
    """``zero_gap_matrix(nbytes)`` as its 32 column words (bit i of
    word j is ``A[i, j]``), read-only: the form the host applies
    (``host.apply_columns``) and the native fold takes."""
    a = _mat(zero_gap_matrix(nbytes)).astype(np.uint32)
    cols = np.bitwise_or.reduce(
        a << np.arange(32, dtype=np.uint32)[:, None], axis=0
    )
    cols.flags.writeable = False
    return cols


def _across_zeros(nbytes: int, reg: int) -> int:
    """The register ``reg`` after ``nbytes`` zero bytes."""
    return int(
        apply_columns(zero_gap_columns(nbytes), np.uint32(reg & 0xFFFFFFFF))
    )


@functools.lru_cache(maxsize=None)
def chunk_fold_matrix(c: int = CHUNK_BYTES) -> bytes:
    """B_c [32, c*8]: remainder of a c-byte chunk from zero init.

    Column j*8+b = crc register after the chunk whose only set bit is
    bit b of byte j. Built from unit responses once per chunk size.
    """
    out = np.zeros((32, c * 8), dtype=np.uint8)
    for j in range(c):
        for b in range(8):
            msg = bytearray(c)
            msg[j] = 1 << b
            out[:, j * 8 + b] = _bits32(crc32c_ref(0, bytes(msg)))
    return out.tobytes()


@functools.lru_cache(maxsize=None)
def fold_tensor(block_bytes: int, c: int = CHUNK_BYTES) -> np.ndarray:
    """K [S, 32, c*8] with K_i = A_{(S-1-i)*c} @ B_c. One-time per
    (block size, chunk size); the TableCache discipline again."""
    assert block_bytes % c == 0, (block_bytes, c)
    s = block_bytes // c
    bc = np.frombuffer(chunk_fold_matrix(c), dtype=np.uint8).reshape(32, c * 8)
    k = np.empty((s, 32, c * 8), dtype=np.uint8)
    for i in range(s):
        a = _mat(zero_gap_matrix((s - 1 - i) * c))
        k[i] = (a @ bc) & 1
    return k


def _pick_chunk(block_bytes: int) -> int:
    c = CHUNK_BYTES
    while block_bytes % c:
        c >>= 1
    return c


@functools.lru_cache(maxsize=None)
def _host_fold(block_bytes: int, c: int):
    return (
        fold_tensor(block_bytes, c),
        _mat(zero_gap_matrix(block_bytes)),
    )


_device_cache: dict = {}


def _device_fold(block_bytes: int, c: int):
    """Device-resident (K, A_total) — uploaded once per block size, not
    per call. Under an active trace (crc32c_device inside a jit or
    shard_map) the arrays become tracers, which must NOT be cached —
    they are embedded as compile-time constants instead."""
    kf, at = _host_fold(block_bytes, c)
    from ceph_tpu.utils.platform import trace_state_clean

    if not trace_state_clean():
        return jnp.asarray(kf, jnp.int8), jnp.asarray(at, jnp.int8)
    key = (block_bytes, c)
    if key not in _device_cache:
        _device_cache[key] = (
            jnp.asarray(kf, jnp.int8),
            jnp.asarray(at, jnp.int8),
        )
    return _device_cache[key]


def fold_blocks_bits(k_fold: jax.Array, data: jax.Array) -> jax.Array:
    """[B, L] uint8 x [S, 32, c*8] fold tensor -> [B, 32] int32
    remainder counts (mod 2 pending) — the shared einsum fold body."""
    c8 = k_fold.shape[-1]
    s = k_fold.shape[0]
    chunks = data.reshape(data.shape[0], s, c8 // 8)
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = ((chunks[..., None] >> shifts) & jnp.uint8(1)).reshape(
        data.shape[0], s, c8
    )
    return jnp.einsum(
        "src,bsc->br",
        k_fold,
        bits.astype(jnp.int8),
        preferred_element_type=jnp.int32,
    )


def init_bits32(init) -> jax.Array:
    return (
        (jnp.asarray(init, jnp.uint32) >> jnp.arange(32, dtype=jnp.uint32))
        & 1
    ).astype(jnp.int8)


def acc_to_crc32(acc: jax.Array) -> jax.Array:
    """[..., 32] int32 counts -> [...] uint32 (mod 2 + bit pack)."""
    crc_bits = (acc & 1).astype(jnp.uint32)
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(crc_bits * weights, axis=-1, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("block_bytes",))
def _crc32c_kernel(
    data: jax.Array,  # [B, L] uint8
    init: jax.Array,  # scalar uint32
    k_fold: jax.Array,  # [S, 32, c*8] int8
    a_total: jax.Array,  # [32, 32] int8
    *,
    block_bytes: int,
) -> jax.Array:
    acc = fold_blocks_bits(k_fold, data)
    acc = acc + (
        a_total.astype(jnp.int32) @ init_bits32(init).astype(jnp.int32)
    )
    return acc_to_crc32(acc)


def crc32c_device(
    data: jax.Array, init: int | jax.Array = 0xFFFFFFFF
) -> jax.Array:
    """Per-block CRC32C of ``data`` [..., block_bytes] -> [...] uint32.

    Device analog of ``ceph_crc32c(init, block, len)`` vmapped over
    blocks; used by deep scrub and the ProtocolV2-analog segment
    checksums.
    """
    block_bytes = int(data.shape[-1])
    lead = data.shape[:-1]
    flat = data.reshape(-1, block_bytes)
    from ceph_tpu.utils import config

    from . import pallas_crc

    from . import backends

    if config.get("ec_use_pallas"):
        from ceph_tpu.utils import platform

        if platform.on_tpu():
            if pallas_crc.supported(int(flat.shape[0]), block_bytes):
                backends.record("pallas", int(flat.size))
                return pallas_crc.crc32c_fold_pallas(flat, init).reshape(
                    lead
                )
            # the round-6 silent fallback, now visible: Pallas was
            # enabled on TPU but the shape could not tile
            backends.record("pallas_fallback")
            backends.warn_once(
                f"crc-untileable-{flat.shape[0]}x{block_bytes}",
                f"crc32c [{flat.shape[0]}, {block_bytes}] untileable "
                "for the Pallas fold; serving via einsum",
            )
    backends.record("einsum", int(flat.size))
    c = _pick_chunk(block_bytes)
    k_fold, a_total = _device_fold(block_bytes, c)
    out = _crc32c_kernel(
        flat,
        jnp.asarray(init, dtype=jnp.uint32),
        k_fold,
        a_total,
        block_bytes=block_bytes,
    )
    return out.reshape(lead)


def crc32c(init: int, data: bytes) -> int:
    """Host scalar API mirroring ``ceph_crc32c`` exactly — including the
    crc-of-zeros fast path the reference gets from crc32c_null
    (common/crc32c.h): runs the matrix transition, no byte loop."""
    if not data:
        return init & 0xFFFFFFFF
    if not any(data):
        return _across_zeros(len(data), init)
    return crc32c_ref(init, data)


def crc32c_concat(crc_a: int, crc_b_zero_init: int, len_b: int) -> int:
    """crc(A||B) from crc(A) and crc(B with zero init) — the bufferlist
    cached-crc "range concatenation" trick (common/crc32c.h,
    buffer.cc): crc(A||B) = A_{len_b} @ crc(A) ⊕ crc_0(B)."""
    return _across_zeros(len_b, crc_a) ^ crc_b_zero_init


# -- fused-kernel csum plumbing ----------------------------------------
def crc32c_seed_shift(block_bytes: int, init: int) -> int:
    """The constant with crc(init, B) = crc(0, B) ^ shift for EVERY
    block of ``block_bytes`` (linearity: the init register's journey
    through the message is independent of the message bits). The
    fused encode+csum kernel emits ZERO-INIT per-block csums so one
    device pass serves every consumer seed — BlueStore blob csums
    (seed -1), HashInfo chains, wire csums — via this one XOR."""
    return _across_zeros(block_bytes, init)


def crc32c_fold(seeds, block_csums, block_bytes: int) -> np.ndarray:
    """Fold ZERO-INIT per-block crc32c values into running registers,
    every shard in one call: ``block_csums`` is [shards, blocks] (each
    row the crcs of consecutive ``block_bytes`` blocks of one stream),
    ``seeds`` [shards] the registers before them; returns [shards]
    uint32, ``reg' = A_block @ reg ^ crc_0(B_i)`` block after block
    (repeated range concatenation). How HashInfo seeds the cumulative
    shard hashes from fused-kernel csums without touching the bytes
    again. One algorithm whatever the sizes: the native fold where the
    C++ tier loads, the numpy tree otherwise (``host.fold_words``), no
    Python per word either way.

    What a fold of 12 shards x 128 words (a 4 MiB write on k=8 m=4)
    costs on the chip machine's host, one thread (builder's, PR 39,
    wall, median of 200): 18 us this call, 42 us the whole of
    ``HashInfo.append_block_csums``; 302 us through the numpy tree;
    12,400 us as the per-block Python loop this replaced
    (CAPABILITIES.md, "HashInfo fold")."""
    seeds = np.ascontiguousarray(seeds, dtype=np.uint32).reshape(-1)
    csums = np.ascontiguousarray(block_csums, dtype=np.uint32)
    csums = csums.reshape(seeds.size, -1)
    return fold_words(zero_gap_columns(block_bytes), seeds, csums)


def as_stream(data) -> np.ndarray:
    """One byte stream as a flat uint8 array, a view wherever the
    buffer allows (a strided array stays strided: the one copy is the
    caller's)."""
    if isinstance(data, (bytes, bytearray, memoryview)):
        return np.frombuffer(data, dtype=np.uint8)
    arr = np.asarray(data)
    if arr.dtype != np.uint8:
        # no silent value casts: the crc is over stored bytes
        raise TypeError(f"stream bytes must be uint8, got {arr.dtype}")
    return arr.reshape(-1)


def crc32c_streams(inits, streams) -> "tuple[list[int], int]":
    """Cumulative crc32c of equal-length byte streams hashed TOGETHER:
    ``inits[i]`` is stream i's register before, the answer its
    register after, and the number of device checksum calls made
    (0 or 1). Backend-routed by ONE stream's length: host scalar
    (native C when loaded) below ``csum_device_min_bytes``; above it
    the whole blocks of every stream ride ONE zero-init
    ``crc32c_device`` call ([streams * blocks, block] rows
    stream-major, padded with zero blocks to a count the Pallas fold
    tiles, their words dropped), come back in one fetch and chain
    into the registers through one ``crc32c_fold``; a ragged tail
    finishes on the host. The streams' bytes are copied on the host
    at most once (the stack the call uploads); one contiguous stream
    whose block count tiles is uploaded as the view it is."""
    rows = [as_stream(s) for s in streams]
    sizes = {int(r.size) for r in rows}
    if len(sizes) > 1:
        raise ValueError(f"unequal stream sizes {sizes}")
    regs = [int(i) & 0xFFFFFFFF for i in inits]
    if len(regs) != len(rows):
        raise ValueError(f"{len(regs)} inits for {len(rows)} streams")
    if not rows:
        return regs, 0
    from ceph_tpu.utils import config

    from . import backends
    from .host import crc32c as _host_crc

    n = sizes.pop()
    limit = int(config.get("csum_device_min_bytes"))
    cb = 65536 if n >= 4 * 65536 else 4096
    nb = n // cb
    if limit <= 0 or n < limit or not nb:
        for i, row in enumerate(rows):
            backends.record("host", n)
            regs[i] = _host_crc(regs[i], row.tobytes())
        return regs, 0
    from .pallas_crc import tile_blocks

    count = len(rows) * nb
    padded = tile_blocks(count)
    if padded == count and len(rows) == 1 and rows[0].flags.c_contiguous:
        blocks = rows[0][: nb * cb].reshape(nb, cb)
    else:
        blocks = np.empty((padded, cb), dtype=np.uint8)
        flat = blocks.reshape(-1)
        for i, row in enumerate(rows):
            flat[i * nb * cb : (i + 1) * nb * cb] = row[: nb * cb]
        blocks[count:] = 0
    words = np.asarray(crc32c_device(blocks, 0))[:count]
    regs = crc32c_fold(regs, words.reshape(len(rows), nb), cb).tolist()
    if n > nb * cb:
        for i, row in enumerate(rows):
            regs[i] = _host_crc(regs[i], row[nb * cb :].tobytes())
    return regs, 1


def crc32c_stream(data, init: int = 0xFFFFFFFF) -> int:
    """Cumulative crc32c of one byte stream: ``crc32c_streams`` of one.
    Callers chain across pieces by passing the previous return as
    ``init`` (the deep-scrub stride loop)."""
    return crc32c_streams([init], [data])[0][0]
