"""Bit-matrix (XOR-schedule) erasure codecs — the liberation family.

The reference's jerasure plugin runs liberation / blaum_roth /
liber8tion as w-bit bit-matrix codes executed as XOR schedules over
"packets" (ErasureCodeJerasure.h:188-324). Here a chunk is w packets,
the coding matrix is [m*w, k*w] over GF(2), and encode/decode is the
same mod-2 MXU matmul as the byte codes — XOR networks are *natively*
this formulation on TPU (SURVEY.md section 7 "Design stance").

Construction note: the vendored jerasure/gf-complete sources are
absent from the reference snapshot (empty submodules), so the
matrices are built from the PUBLISHED definitions rather than the C
files: ``liberation_bitmatrix`` ports Plank's FAST'08 construction
(cyclic shifts plus the one correction bit per column, w prime),
``blaum_roth_bitmatrix`` the Blaum-Roth ring form over
GF(2)[x]/(1 + x + ... + x^w), and liber8tion's envelope is served by
``gf2w_power_bitmatrix`` (generator powers, guaranteed MDS at w=8).
Every construction re-verifies MDS exhaustively at build time, and
bit-compatibility IS tested: corpus v1 freezes encoded chunks for
each technique (tests/corpus/v1, tests/test_corpus.py), so the
matrices — and the kernels applying them — can never drift across
versions. The earlier searched minimal-density RAID-6 matrices
(``raid6_bitmatrix``) remain available as ``construction=v0``, pinned
by the corpus v0 entries.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.gf import gf_matrix_to_bitmatrix
from ceph_tpu.gf.bitmatrix import bitmatrix_invert, bitmatrix_matmul
from ceph_tpu.ops import xor_schedule
from ceph_tpu.ops.bitplane import xor_bytes

from .base import ErasureCodeBase
from .interface import Flag
from .matrix_codec import (
    BitplaneDispatchMixin,
    DecodeTableCache,
    count_route,
    dev_bmat,
)


def _shift(w: int, d: int) -> np.ndarray:
    """Cyclic shift matrix S^d: ones at (i, (i+d) mod w)."""
    m = np.zeros((w, w), dtype=np.uint8)
    for i in range(w):
        m[i, (i + d) % w] = 1
    return m


def _invertible(m: np.ndarray) -> bool:
    try:
        bitmatrix_invert(m)
        return True
    except ValueError:
        return False


@functools.lru_cache(maxsize=None)
def raid6_bitmatrix(k: int, w: int) -> bytes:
    """Search a minimal-density RAID-6 bit-matrix code.

    P row: identity blocks. Q row: X_j = S^j plus at most one correction
    bit, chosen (deterministic scan order) so that every X_j and every
    pairwise X_i ^ X_j is invertible — the exact MDS condition for
    two-parity bit-matrix codes. Returns [2*w, k*w] packed bytes.
    """
    if k > w:
        raise ValueError(f"k={k} must be <= w={w}")
    blocks: list[np.ndarray] = []
    cells = [(r, c) for r in range(w) for c in range(w)]
    for j in range(k):
        base = _shift(w, j)
        placed = None
        # Iterative deepening over correction-bit count: the bare
        # shift, then 1 bit, then 2 (prime w always succeeds at <= 1,
        # so those matrices — corpus-frozen since v0 — are unchanged;
        # even w, where S^d ^ S^e is never invertible, needs 2).
        def candidates():
            yield ()
            for cell in cells:
                yield (cell,)
            for a in range(len(cells)):
                for b in range(a + 1, len(cells)):
                    yield (cells[a], cells[b])

        for cand in candidates():
            x = base.copy()
            for r, c in cand:
                x[r, c] ^= 1
            if not _invertible(x):
                continue
            if all(_invertible(x ^ b) for b in blocks):
                placed = x
                break
        if placed is None:
            raise ValueError(
                f"no minimal-density RAID-6 construction found for k={k}, w={w}"
            )
        blocks.append(placed)
    coding = np.zeros((2 * w, k * w), dtype=np.uint8)
    for j in range(k):
        coding[:w, j * w : (j + 1) * w] = np.eye(w, dtype=np.uint8)
        coding[w:, j * w : (j + 1) * w] = blocks[j]
    return coding.tobytes()


def _is_prime(n: int) -> bool:
    if n < 2:
        return False
    return all(n % i for i in range(2, int(n**0.5) + 1))


@functools.lru_cache(maxsize=None)
def liberation_bitmatrix(k: int, w: int) -> bytes:
    """The Liberation code construction (Plank, FAST'08) — the matrix
    ``liberation_coding_bitmatrix`` builds for the reference's
    liberation technique (ErasureCodeJerasure.cc:676; the vendored
    jerasure sources are absent from the snapshot, so this is ported
    from the paper's published definition, not the C file).

    w prime, k <= w. P row: identity blocks. Q block X_i: ones at
    (r, (r+i) mod w) for every r — the cyclic shift S^i — plus, for
    i > 0, one extra bit at (y, (y+i-1) mod w) with y = i(w-1)/2 mod w.
    Total Q density k*w + k - 1 ones: the minimal-density bound the
    family is named for. MDS (every X_i and X_i ^ X_j invertible) is
    re-verified exhaustively at construction time rather than trusted.
    """
    if not _is_prime(w):
        raise ValueError(f"liberation requires prime w, got {w}")
    if k > w:
        raise ValueError(f"k={k} must be <= w={w}")
    coding = np.zeros((2 * w, k * w), dtype=np.uint8)
    blocks: list[np.ndarray] = []
    for i in range(k):
        coding[:w, i * w : (i + 1) * w] = np.eye(w, dtype=np.uint8)
        x = np.zeros((w, w), dtype=np.uint8)
        for r in range(w):
            x[r, (r + i) % w] = 1
        if i > 0:
            y = (i * ((w - 1) // 2)) % w
            x[y, (y + i - 1) % w] ^= 1
        if not _invertible(x) or any(
            not _invertible(x ^ b) for b in blocks
        ):
            raise ValueError(
                f"liberation construction not MDS for k={k}, w={w}"
            )
        blocks.append(x)
        coding[w:, i * w : (i + 1) * w] = x
    return coding.tobytes()


@functools.lru_cache(maxsize=None)
def blaum_roth_bitmatrix(k: int, w: int) -> bytes:
    """Blaum-Roth RAID-6 code over the ring GF(2)[x]/(1 + x + ... + x^w).

    Requires w+1 prime. Q block for data column j is multiplication by
    x^j (C^j with C the companion matrix of M_p(x) = (x^p - 1)/(x - 1),
    p = w+1). MDS because C^i ^ C^j = C^j (C^(i-j) ^ I) and x^d + 1 is
    coprime to M_p(x) for 0 < d < p when p is prime (their only common
    candidate root, 1, is not a root of M_p since p is odd).
    """
    if not _is_prime(w + 1):
        raise ValueError(f"blaum_roth requires w+1 prime, got w={w}")
    if k > w:
        raise ValueError(f"k={k} must be <= w={w}")
    # Companion matrix: column j of C holds x^(j+1) mod M_p.
    c = np.zeros((w, w), dtype=np.uint8)
    for j in range(w - 1):
        c[j + 1, j] = 1
    c[:, w - 1] = 1  # x^w = 1 + x + ... + x^(w-1)
    coding = np.zeros((2 * w, k * w), dtype=np.uint8)
    block = np.eye(w, dtype=np.uint8)
    for j in range(k):
        coding[:w, j * w : (j + 1) * w] = np.eye(w, dtype=np.uint8)
        coding[w:, j * w : (j + 1) * w] = block
        block = bitmatrix_matmul(block, c)
    return coding.tobytes()


@functools.lru_cache(maxsize=None)
def sparse_power_bitmatrix(k: int, w: int = 8) -> bytes:
    """RAID-6 Q blocks = the k *sparsest* multiplication-by-g^e
    bitmatrices over GF(2^8). Any distinct powers are pairwise MDS
    (C^a ^ C^b = C^b (C^(a-b) ^ I), multiplication by g^(a-b) + 1
    != 0), so density is a free choice — picking the sparsest k of
    the 255 powers (ones counts 8, 11, 11, 14, 14, 17, 18, 18 for
    k=8 -> 111 total vs ~128 for random powers) keeps the XOR
    schedule short. Exponents are frozen by the deterministic
    (ones, exponent) sort; the layout is corpus-pinned."""
    from ceph_tpu.gf.tables import gf_pow, mul_bitmatrix

    if w != 8:
        raise ValueError("sparse_power_bitmatrix implemented for w=8")
    if k > 2**w - 1:
        raise ValueError(f"k={k} too large for w={w}")
    dens = sorted(
        (int(np.asarray(mul_bitmatrix(gf_pow(2, e))).sum()), e)
        for e in range(2**w - 1)
    )
    chosen = sorted(e for _, e in dens[:k])
    coding = np.zeros((2 * w, k * w), dtype=np.uint8)
    for j, e in enumerate(chosen):
        coding[:w, j * w : (j + 1) * w] = np.eye(w, dtype=np.uint8)
        coding[w:, j * w : (j + 1) * w] = mul_bitmatrix(gf_pow(2, e))
    return coding.tobytes()


@functools.lru_cache(maxsize=None)
def gf2w_power_bitmatrix(k: int, w: int = 8) -> bytes:
    """RAID-6 bit-matrix with Q blocks = powers of the GF(2^w) generator.

    X_j = C^j with C the companion matrix of the field polynomial (0x11D
    for w=8), i.e. multiplication by g^j. MDS for k <= 2^w - 1: every C^j
    is invertible and C^i ^ C^j = C^j(C^(i-j) ^ I) is multiplication by
    g^(i-j) + 1 != 0. Used for the liber8tion envelope (w=8): the
    reference's liber8tion matrices minimize XOR-schedule density, which
    is irrelevant on the MXU — this construction keeps the same envelope
    and packet layout with guaranteed MDS.
    """
    from ceph_tpu.gf.tables import mul_bitmatrix, gf_pow

    if w != 8:
        raise ValueError("gf2w_power_bitmatrix implemented for w=8")
    if k > 2**w - 1:
        raise ValueError(f"k={k} too large for w={w}")
    coding = np.zeros((2 * w, k * w), dtype=np.uint8)
    for j in range(k):
        coding[:w, j * w : (j + 1) * w] = np.eye(w, dtype=np.uint8)
        coding[w:, j * w : (j + 1) * w] = mul_bitmatrix(gf_pow(2, j))
    return coding.tobytes()


class BitMatrixCodec(BitplaneDispatchMixin, ErasureCodeBase):
    """Erasure codec driven by a [m*w, k*w] GF(2) coding matrix.

    Chunk layout: chunk = w consecutive packets of chunk_size/w bytes
    (the jerasure packet convention, with packetsize implied by chunk
    size rather than a separate profile knob — TPU tiling makes the
    packet the natural unit).

    Engine note (round 4): a packet-selection XOR network IS a GF(2^8)
    matrix apply whose matrix entries happen to be 0/1 — GF(2) is the
    subfield {0,1} of GF(2^8), so the packet matrix routes through the
    SAME dispatch engine as the byte codes (host GF tables / mesh /
    Pallas MXU kernel / einsum, with ec_dispatch counters), the way
    the reference funnels both jerasure_matrix_encode and
    jerasure_schedule_encode into one plugin hot path.
    """

    def __init__(self) -> None:
        super().__init__()
        self.w = 0
        self.coding_bitmatrix: np.ndarray | None = None  # [m*w, k*w]
        self._tables = DecodeTableCache()       # device matrices
        self._host_tables = DecodeTableCache()  # packet 0/1 matrices

    def _set_bitmatrix(self, coding: np.ndarray) -> None:
        assert coding.shape == (self.m * self.w, self.k * self.w)
        self.coding_bitmatrix = coding.astype(np.uint8)
        # the packet matrix as a GF(2^8) 0/1 byte matrix, expanded to
        # bit-plane form for the device engine (kron with I8)
        self._encode_bmat_np = gf_matrix_to_bitmatrix(self.coding_bitmatrix)
        self._encode_bmat = jnp.asarray(self._encode_bmat_np)

    def get_flags(self) -> Flag:
        return (
            Flag.OPTIMIZED_SUPPORTED
            | Flag.ZERO_INPUT_ZERO_OUTPUT
            | Flag.ZERO_PADDING_EXPECTED
            | Flag.PARITY_DELTA_OPTIMIZATION
            | Flag.PARITY_DELTA_CHUNK_GRANULARITY
        )

    def get_chunk_size(self, stripe_width: int) -> int:
        """Chunks must split into w lane-aligned packets."""
        from .base import CHUNK_ALIGN

        per = -(-stripe_width // self.k)
        unit = self.w * CHUNK_ALIGN
        return -(-per // unit) * unit

    # [..., S, N] chunks -> [..., S*w, N/w] packets
    def _to_packets(self, chunks: jax.Array) -> jax.Array:
        *lead, s, n = chunks.shape
        assert n % self.w == 0, (n, self.w)
        return chunks.reshape(*lead, s * self.w, n // self.w)

    def _to_chunks(self, packets: jax.Array) -> jax.Array:
        *lead, sw, p = packets.shape
        return packets.reshape(*lead, sw // self.w, p * self.w)

    def _apply_packet_matrix(
        self,
        mat01: np.ndarray,
        shards: list,
        op: str,
        tables: "tuple[np.ndarray, jax.Array] | None" = None,
    ) -> list:
        """Apply a packet-level 0/1 matrix to per-shard [..., N]
        chunks via the shared engine, on the route ``_plan_route``
        names; returns the output shards. The multi-operand schedule
        kernel takes the shard arrays as they are: no [.., n, chunk]
        stack, no packetize reshape. Every other route (mesh / host /
        packetized XOR-schedule / Pallas / einsum) stacks, packetizes
        and de-packetizes. ``tables`` passes precomputed bit-expanded
        forms (the encode path keeps them resident)."""
        from ceph_tpu.utils import config

        route = self._route_shards(
            shards, self.w, host_tables=True, mat01=mat01
        )
        if route == "sched_shards":
            return self._run_sched_shards(mat01, shards, self.w, op)
        packets = self._to_packets(self._stack(shards))
        if route == "host":
            from ceph_tpu.gf import gf_apply_bytes_host

            count_route(f"host_{op}", packets)
            out = gf_apply_bytes_host(mat01, np.asarray(packets))
        elif route == "sched":
            # schedule-native route: sparse packet matrices ARE XOR
            # networks (jerasure_schedule_encode's insight), and the
            # round-11 optimizer CSE-compresses denser shapes —
            # inverted decode tables, parity-delta columns — under
            # the op-count gate. Matrices still over the gate, or
            # shapes no schedule kernel can tile, ride the MXU engine
            count_route(f"sched_{op}", packets)
            out = xor_schedule.xor_schedule_apply(
                xor_schedule.routable_schedule(
                    mat01, config.get("ec_sched_opt")
                ),
                packets,
            )
        else:
            if tables:
                bm_np, bm_dev = tables
            else:
                bm_np, key = self._host_bits(mat01)
                bm_dev = dev_bmat(
                    self._tables, key, bm_np,
                    isinstance(packets, jax.core.Tracer),
                )
            out = self._dispatch_bitmatrix(
                bm_np, bm_dev, packets, op, route=route
            )
        chunks = self._to_chunks(out)
        return [chunks[..., j, :] for j in range(chunks.shape[-2])]

    def _host_bits(self, mat01: np.ndarray):
        """(bit-expanded HOST matrix, cache key) for a packet 0/1
        matrix — the one source of truth for the ("bits", ...)
        cache."""
        key = ("bits", mat01.tobytes())
        return self._tables.get(
            key, lambda: gf_matrix_to_bitmatrix(mat01)
        ), key

    def encode_chunks(
        self, data: dict[int, jax.Array]
    ) -> dict[int, jax.Array]:
        parity = self._apply_packet_matrix(
            self.coding_bitmatrix,
            self._shard_list(data),
            "encode",
            tables=(self._encode_bmat_np, self._encode_bmat),
        )
        return {self.k + i: parity[i] for i in range(self.m)}

    def decode_chunks(
        self,
        want_to_read: set[int],
        chunks: dict[int, jax.Array],
    ) -> dict[int, jax.Array]:
        present = sorted(chunks)
        want = sorted(w for w in want_to_read if w not in chunks)
        if not want:
            return {w: chunks[w] for w in want_to_read}
        key = (tuple(present), tuple(want))
        dec01 = self._host_tables.get(
            key, lambda: self._build_decode_bitmatrix(present, want)
        )
        outs = self._apply_packet_matrix(
            dec01, [chunks[i] for i in present], "decode"
        )
        result = {w: chunks[w] for w in want_to_read if w in chunks}
        for idx, wshard in enumerate(want):
            result[wshard] = outs[idx]
        return result

    # -- parity delta (RMW) -------------------------------------------
    def encode_delta(
        self, old_data: jax.Array, new_data: jax.Array
    ) -> jax.Array:
        return xor_bytes(old_data, new_data)

    def apply_delta(
        self,
        delta: dict[int, jax.Array],
        parity: dict[int, jax.Array],
    ) -> dict[int, jax.Array]:
        """parity'_j = parity_j XOR (packet-matrix columns of the
        changed chunks applied to the delta packets) — the
        schedule_apply_delta analog (ErasureCodeJerasure.h:110-119).

        Delta buffers must be whole chunks (the codec sets
        PARITY_DELTA_CHUNK_GRANULARITY): a sub-chunk write's parity
        update scatters across the entire chunk through the packet
        structure, so the pipeline hands in chunk-aligned windows.
        """
        cols = sorted(delta)
        w = self.w
        pcols = [c * w + t for c in cols for t in range(w)]
        mat01 = np.ascontiguousarray(self.coding_bitmatrix[:, pcols])
        contrib = self._apply_packet_matrix(
            mat01, [delta[c] for c in cols], "delta"
        )
        out = {}
        for pid, p in parity.items():
            c = contrib[pid - self.k]
            if isinstance(p, np.ndarray) and isinstance(c, np.ndarray):
                out[pid] = np.bitwise_xor(p, c)
            else:
                out[pid] = xor_bytes(p, c)
        return out

    def _build_decode_bitmatrix(
        self, present: list[int], want: list[int]
    ) -> jax.Array:
        """Invert the surviving (k*w)-row sub-bitmatrix, then compose
        wanted rows (jerasure_invert_bitmatrix's role)."""
        kw = self.k * self.w
        full = np.zeros(((self.k + self.m) * self.w, kw), dtype=np.uint8)
        for i in range(self.k):
            full[i * self.w : (i + 1) * self.w, i * self.w : (i + 1) * self.w] = (
                np.eye(self.w, dtype=np.uint8)
            )
        full[kw:, :] = self.coding_bitmatrix
        # Greedy rank extension over survivor row-blocks.
        rows = []
        for s in present:
            rows.extend(range(s * self.w, (s + 1) * self.w))
        # Select kw independent rows (first k blocks usually suffice).
        sel = full[rows[:kw], :]
        try:
            inv = bitmatrix_invert(sel)
            chosen = rows[:kw]
        except ValueError:
            # Rank-extend row by row over GF(2).
            chosen = []
            basis: list[np.ndarray] = []
            for ridx, r in enumerate(rows):
                if len(chosen) == kw:
                    break
                v = full[r].copy()
                for e in basis:
                    lead = int(np.argmax(e != 0))
                    if v[lead]:
                        v ^= e
                if v.any():
                    chosen.append(r)
                    basis.append(v)
            if len(chosen) < kw:
                raise ValueError("erasure pattern not decodable")
            inv = bitmatrix_invert(full[chosen, :])
        # data packet rows in terms of chosen survivor rows:
        # data = inv @ chosen_rows; wanted shard rows = full_rows @ data.
        dec = np.zeros((len(want) * self.w, len(present) * self.w), dtype=np.uint8)
        # Map chosen row -> column position among present packet rows.
        col_of = {r: i for i, r in enumerate(rows)}
        for wi, wshard in enumerate(want):
            wrows = full[wshard * self.w : (wshard + 1) * self.w, :]
            # [w, kw] coefficients over the chosen survivor rows.
            comp = bitmatrix_matmul(wrows, inv)
            for a in range(self.w):
                for b, r in enumerate(chosen):
                    dec[wi * self.w + a, col_of[r]] = comp[a, b]
        # host 0/1 matrix — cached in _host_tables and consumed by
        # both routes (the device route bit-expands via _host_bits +
        # dev_bmat)
        return dec
