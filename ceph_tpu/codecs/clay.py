"""Coupled-Layer (CLAY) MSR regenerating code — the clay plugin.

Behavioral mirror of src/erasure-code/clay/ErasureCodeClay.{h,cc}
(IISc): parameters (k, m, d) with k+1 <= d <= k+m-1. Derived geometry
(ErasureCodeClay.cc:316-348): q = d-k+1, nu pads k+m to a multiple of q
(shortened zero chunks), t = (k+m+nu)/q, and every chunk consists of
``sub_chunk_no = q^t`` sub-chunks ("planes"). Nodes live on a q x t
grid; plane z has a base-q digit vector z_vec[t]; node (x, y) is a
"dot" in plane z when x == z_vec[y], else it pairs with node
(z_vec[y], y) in the companion plane z_sw (digit y swapped to x).

Stored ("coupled") values C and intermediate ("uncoupled") values U are
linked pairwise by an invertible 2x2 GF(2^8) transform — the reference
realizes it as an RS(2,2) pairwise-forward-transform codec (pft); here
it is explicit algebra: (U_hi, U_lo) = P @ (C_hi, C_lo) where "hi" is
the pair member with the larger x. Across nodes, each plane of U is a
codeword of an inner scalar MDS code (k+nu data, m parity — the mds
member, default jerasure reed_sol_van).

Encode = decode with all parity erased (ErasureCodeClay.cc:141-169).
Single-chunk repair reads only sub_chunk_no/q sub-chunks from each of d
helpers — the MSR property (repair*, ErasureCodeClay.cc:454-699).

TPU-first deltas from the reference:

- Planes of equal "intersection score" are independent; the per-plane
  inner-MDS decodes are batched into ONE device dispatch per score
  group (the plane axis becomes a batch dim of the bit-plane MXU
  kernel) instead of q^t sequential 4KB calls.
- Pair transforms are closed-form 2-coefficient GF combinations
  (host-cached), not recursive codec calls.
- ``is_repair`` is genuinely enabled (the reference currently disables
  it pending its new-EC refactor, ErasureCodeClay.cc:356-368; we
  implement the documented pre-refactor semantics). On the served
  path that shows in ``pipeline/read.py``:
  ``get_min_avail_to_read_shards`` asks ``minimum_to_decode`` for the
  MISSING wanted shards only, so a client's read of an object whose
  dead OSD held a data shard takes the d-helper sub-chunk plan
  (recovery of any one shard likewise); the helpers nobody else wants
  bytes from go out as one extent plus the plan's runs
  (``SubchunkSelect``) and come back packed, and
  ``_repair_fractional`` hands all the window's chunks to
  ``repair_window`` at once.
- The served path runs compiled programs, one a repair
  (``repair_window``) and one an encode (``encode_stacked``), cached
  for the process by the code's parameters and the padded batch: the
  host path below stays as the eager entry and the oracle.
"""

from __future__ import annotations

import functools
import threading

import jax
import numpy as np

from ceph_tpu import PLUGIN_ABI_VERSION
from ceph_tpu.gf import vandermonde_rs_matrix
from ceph_tpu.gf.matrices import gf_invert_matrix, gf_matmul_np
from ceph_tpu.gf.tables import gf_mul_bytes

from .base import ErasureCodeBase, to_int
from .interface import ErasureCodeProfile, Flag, SubChunkPlan
from .registry import registry


def _pow_int(a: int, x: int) -> int:
    return a**x


#: compiled repair and encode programs, by the code's parameters and
#: the batch's shape: codec objects are rebuilt for every PG and on
#: every map change, the programs are the process's
_PROGRAMS: dict[tuple, object] = {}
_PROGRAMS_LOCK = threading.Lock()
#: (parameters, shape) -> set once every lost chunk's repair program
#: of that shape exists (``ClayCodec._repair_warm``)
_WARMED: dict[tuple, threading.Event] = {}
#: a batch is zero-padded to one of these many chunks, so that objects
#: of every size share a handful of programs and the pair-transform
#: kernels (eight stripes a block) stay on the path
MIN_BATCH = 8


class _Program:
    """A jitted function whose first call, the one that compiles, is
    made by one thread alone: the OSDs' op workers meet a new shape
    together, and a dozen compilations of the one program side by side
    cost the set-up what one costs."""

    def __init__(self, fn, counters: tuple[str, str]) -> None:
        self.fn = jax.jit(fn)
        #: what a run counts in ``ec_dispatch``: the route of its
        #: bit-matrix kernel, and the counter that takes its bytes
        self.route, self.bytes_counter = counters
        self._first = threading.Lock()
        self._compiled = False

    def __call__(self, arg):
        if self._compiled:
            return self.fn(arg)
        with self._first:
            out = self.fn(arg)
            self._compiled = True
        return out

    def run(self, arr: np.ndarray, axis: int, batch: int) -> np.ndarray:
        """One dispatch of ``arr``, zero-padded to ``batch`` along its
        batch ``axis`` (both codes are linear: zero chunks in, zero
        out): counted once, its bytes under ``bytes_counter``, then
        one upload, one launch, one fetch, each a ``codec.*`` stage.
        The result's leading axis is the batch, cut back."""
        import jax.numpy as jnp

        from .matrix_codec import _dispatch_counters, codec_stage, count_route

        n = arr.shape[axis]
        count_route(self.route, nbytes=0)
        _dispatch_counters().inc(self.bytes_counter, arr.nbytes)
        with codec_stage("prep"):
            if n != batch:
                shape = list(arr.shape)
                shape[axis] = batch
                padded = np.zeros(shape, np.uint8)
                padded[(slice(None),) * axis + (slice(0, n),)] = arr
                arr = padded
        with codec_stage("h2d"):
            dev = jnp.asarray(arr)
        with codec_stage("launch"):
            out = self(dev)
        with codec_stage("fetch"):
            return np.asarray(out)[:n]


def batch_size(n: int) -> int:
    """The padded batch that carries ``n`` chunks: the next power of
    two, at least ``MIN_BATCH``."""
    return max(MIN_BATCH, 1 << (n - 1).bit_length())


@functools.lru_cache(maxsize=256)
def _mul_table_np(c: int) -> np.ndarray:
    """[256] uint8 host table for GF mul-by-constant ``c``. The cache
    holds NUMPY only — caching a device array built inside a jit
    trace would leak that trace's tracer into every later call
    (UnexpectedTracerError); jnp.asarray at the call site turns it
    into a per-trace constant instead."""
    return np.array(
        [gf_mul_bytes(c, np.array([v], np.uint8))[0] for v in range(256)],
        np.uint8,
    )


def _gf_mul_traced(c: int, x):
    """GF(2^8) multiply-by-constant as a shift/mask/xor chain (the
    carry-less "peasant" ladder): ~8 fused VPU ops. Replaces the
    256-entry ``jnp.take`` gather, which serializes on TPU — the
    gather formulation measured 0.08 GB/s through the whole CLAY
    repair; this chain is what makes the traced repair stream."""
    import jax.numpy as jnp

    if c == 0:
        return jnp.zeros_like(x)
    if c == 1:
        return x
    acc = None
    xt = x
    cc = c
    while cc:
        if cc & 1:
            acc = xt if acc is None else acc ^ xt
        cc >>= 1
        if cc:
            hi = (xt >> jnp.uint8(7)).astype(jnp.uint8)
            xt = ((xt << jnp.uint8(1)) ^ (hi * jnp.uint8(0x1D))).astype(
                jnp.uint8
            )
    return acc


def _gf_mul2(x):
    """x * 2 in GF(2^8)/0x11D: one shift step (3 VPU ops)."""
    import jax.numpy as jnp

    return (
        (x << jnp.uint8(1))
        ^ ((x >> jnp.uint8(7)) * jnp.uint8(0x1D))
    ).astype(jnp.uint8)


def _gf_div2(x):
    """x * inv(2) = x * 142: the inverse shift step."""
    import jax.numpy as jnp

    return (
        (x >> jnp.uint8(1))
        ^ ((x & jnp.uint8(1)) * jnp.uint8(0x8E))
    ).astype(jnp.uint8)


def _gf_mul_planes(cs: np.ndarray, x):
    """GF constant multiply with a PER-PLANE constant: ``x`` is
    [..., P, sc], ``cs`` [P] uint8 broadcast over the plane axis.
    The shift/xor ladder of _gf_mul_vec_traced, shaped for whole-
    helper-tensor transforms and truncated to the constants' actual
    bit length (the pair-transform coefficients are tiny)."""
    import jax.numpy as jnp

    cs = np.asarray(cs, np.uint8)
    nbits = max(int(v).bit_length() for v in cs) or 1
    c = jnp.asarray(cs).reshape(-1, 1)
    acc = jnp.zeros_like(x)
    xt = x
    for j in range(nbits):
        bit = ((c >> jnp.uint8(j)) & jnp.uint8(1)).astype(jnp.uint8)
        acc = acc ^ (xt * bit)
        if j < nbits - 1:
            xt = _gf_mul2(xt)
    return acc


def _gf_mul_vec_traced(cs: np.ndarray, x):
    """Per-row GF constant multiply: ``x`` [P, ...], ``cs`` [P] uint8.
    One 8-step shift/xor ladder over the WHOLE stack — this is the op
    that lets a plane-group's pair transforms run as a single fused
    dispatch instead of one kernel per (plane, node)."""
    import jax.numpy as jnp

    c = jnp.asarray(np.asarray(cs, np.uint8)).reshape(
        (-1,) + (1,) * (x.ndim - 1)
    )
    acc = jnp.zeros_like(x)
    xt = x
    for j in range(8):
        bit = ((c >> jnp.uint8(j)) & jnp.uint8(1)).astype(jnp.uint8)
        acc = acc ^ (xt * bit)
        if j < 7:
            hi = (xt >> jnp.uint8(7)).astype(jnp.uint8)
            xt = ((xt << jnp.uint8(1)) ^ (hi * jnp.uint8(0x1D))).astype(
                jnp.uint8
            )
    return acc


class ClayCodec(ErasureCodeBase):
    SCALAR_MDS = ("jerasure", "isa", "shec")

    def init(self, profile: ErasureCodeProfile) -> None:
        self.profile = dict(profile)
        self.k = to_int("k", profile, 4)
        self.m = to_int("m", profile, 2)
        self.d = to_int("d", profile, self.k + self.m - 1)
        self.w = to_int("w", profile, 8)
        if self.k < 2 or self.m < 1:
            raise ValueError(f"k={self.k} must be >= 2 and m={self.m} >= 1")
        if not (self.k + 1 <= self.d <= self.k + self.m - 1):
            raise ValueError(
                f"value of d {self.d} must be within "
                f"[{self.k + 1},{self.k + self.m - 1}]"
            )
        scalar_mds = profile.get("scalar_mds") or "jerasure"
        self.scalar_mds = scalar_mds
        if scalar_mds not in self.SCALAR_MDS:
            raise ValueError(
                f"scalar_mds {scalar_mds!r} is not supported, use one of "
                f"{self.SCALAR_MDS}"
            )
        technique = profile.get("technique") or (
            "reed_sol_van" if scalar_mds in ("jerasure", "isa") else "single"
        )
        self.q = self.d - self.k + 1
        self.nu = (
            0
            if (self.k + self.m) % self.q == 0
            else self.q - (self.k + self.m) % self.q
        )
        if self.k + self.m + self.nu > 254:
            raise ValueError("k + m + nu must be <= 254")
        self.t = (self.k + self.m + self.nu) // self.q
        self.sub_chunk_no = _pow_int(self.q, self.t)
        mds_profile = {
            "k": str(self.k + self.nu),
            "m": str(self.m),
            "technique": technique,
            "w": "8",
        }
        if scalar_mds == "shec":
            mds_profile["c"] = "2"
        self.mds = registry.factory(scalar_mds, mds_profile)
        # Pairwise transform: G4 maps (C_hi, C_lo) -> (C_hi, C_lo,
        # U_hi, U_lo); any 2 of the 4 determine the rest (RS(2,2) MDS).
        self._g4 = vandermonde_rs_matrix(2, 2)  # [4, 2]
        self._pair_cache: dict[tuple, tuple[int, int]] = {}
        #: static kernel-repair plans keyed by (lost_node, aloof set):
        #: digit strides, member kinds, pair coefficients, score
        #: groups and B2 patch items — all host-side planning shared
        #: by every traced repair of the same erasure pattern (the
        #: device decode matrices ride mds._tables / dev_bmat).
        self._kernel_plans: dict[tuple, dict] = {}

    # -- geometry ------------------------------------------------------
    def get_sub_chunk_count(self) -> int:
        return self.sub_chunk_no

    def get_chunk_size(self, stripe_width: int) -> int:
        # Chunks must split into q^t sub-chunks, each lane-aligned
        # (the sub_chunk_no * k * scalar-alignment rule of
        # ErasureCodeClay.cc:95-101).
        from .base import CHUNK_ALIGN

        align = self.sub_chunk_no * CHUNK_ALIGN
        per = -(-stripe_width // self.k)
        return -(-per // align) * align

    def get_flags(self) -> Flag:
        flags = Flag.PARTIAL_READ_OPTIMIZATION | Flag.REQUIRE_SUB_CHUNKS
        if self.m == 1:
            flags |= Flag.PARTIAL_WRITE_OPTIMIZATION
        return flags

    # -- plane arithmetic ---------------------------------------------
    def _plane_vector(self, z: int) -> list[int]:
        vec = [0] * self.t
        for i in range(self.t):
            vec[self.t - 1 - i] = z % self.q
            z //= self.q
        return vec

    def _z_sw(self, z: int, x: int, y: int, z_vec: list[int]) -> int:
        return z + (x - z_vec[y]) * _pow_int(self.q, self.t - 1 - y)

    # -- pair algebra --------------------------------------------------
    def _pair_coeffs(self, known: tuple[int, int], want: int) -> tuple[int, int]:
        """v[want] = c0*v[known[0]] + c1*v[known[1]] in the 4-tuple
        (C_hi, C_lo, U_hi, U_lo)."""
        key = (known, want)
        if key not in self._pair_cache:
            msub = self._g4[list(known), :]  # [2, 2]
            inv = gf_invert_matrix(msub)
            row = gf_matmul_np(self._g4[want : want + 1, :], inv)[0]
            self._pair_cache[key] = (int(row[0]), int(row[1]))
        return self._pair_cache[key]

    def _pair_solve(
        self,
        known: tuple[int, int],
        a,
        b,
        want: int,
    ):
        c0, c1 = self._pair_coeffs(known, want)
        if isinstance(a, np.ndarray):
            return gf_mul_bytes(c0, a) ^ gf_mul_bytes(c1, b)
        return _gf_mul_traced(c0, a) ^ _gf_mul_traced(c1, b)

    def _pair_idx(self, x: int, x_other: int) -> tuple[int, int]:
        """(C index, U index) of the member with coordinate ``x`` in the
        canonical tuple: larger-x member is (0, 2), smaller is (1, 3)."""
        return (0, 2) if x > x_other else (1, 3)

    # -- repair planning (the MSR read-savings surface) ----------------
    def is_repair(self, want_to_read: set[int], available: set[int]) -> bool:
        """True when the fractional-read repair path applies: a single
        lost chunk, all other members of its x-group available, and at
        least d helpers (the documented semantics of
        ErasureCodeClay.cc:356-382 before the upstream disable)."""
        if set(want_to_read) <= set(available):
            return False
        if len(want_to_read) != 1:
            return False
        lost = next(iter(want_to_read))
        lost_node = self._to_node(lost)
        for x in range(self.q):
            node = (lost_node // self.q) * self.q + x
            if self.k <= node < self.k + self.nu:
                continue  # shortened (virtual) node — always "available"
            chunk = self._from_node(node)
            if chunk != lost and chunk not in available:
                return False
        return len(available) >= self.d

    def get_repair_subchunks(self, lost_node: int) -> list[tuple[int, int]]:
        """(index, count) runs of the planes where the lost node is a
        dot: digit y_lost == x_lost (ErasureCodeClay.cc:422-436)."""
        y_lost, x_lost = lost_node // self.q, lost_node % self.q
        seq = _pow_int(self.q, self.t - 1 - y_lost)
        num_seq = _pow_int(self.q, y_lost)
        out = []
        index = x_lost * seq
        for _ in range(num_seq):
            out.append((index, seq))
            index += self.q * seq
        return out

    def get_repair_sub_chunk_count(self, want_to_read: set[int]) -> int:
        weights = [0] * self.t
        for node in want_to_read:
            weights[node // self.q] += 1
        remaining = 1
        for y in range(self.t):
            remaining *= self.q - weights[y]
        return self.sub_chunk_no - remaining

    def minimum_to_decode(
        self, want_to_read: set[int], available: set[int]
    ) -> SubChunkPlan:
        if self.is_repair(want_to_read, available):
            return self._minimum_to_repair(want_to_read, available)
        return super().minimum_to_decode(want_to_read, available)

    def _minimum_to_repair(
        self, want_to_read: set[int], available: set[int]
    ) -> SubChunkPlan:
        lost = next(iter(want_to_read))
        lost_node = lost if lost < self.k else lost + self.nu
        sub_ind = self.get_repair_subchunks(lost_node)
        minimum: SubChunkPlan = {}
        # Same x-group members first (they are mandatory helpers).
        for j in range(self.q):
            node = (lost_node // self.q) * self.q + j
            if j != lost_node % self.q:
                if node < self.k:
                    minimum[node] = list(sub_ind)
                elif node >= self.k + self.nu:
                    minimum[node - self.nu] = list(sub_ind)
        for chunk in sorted(available):
            if len(minimum) >= self.d:
                break
            if chunk not in minimum and chunk != lost:
                minimum[chunk] = list(sub_ind)
        if len(minimum) != self.d:
            raise ValueError(
                f"cannot repair {lost}: need {self.d} helpers from "
                f"{sorted(available)}"
            )
        return minimum

    # -- node-id mapping (shortening) ---------------------------------
    def _to_node(self, chunk: int) -> int:
        return chunk if chunk < self.k else chunk + self.nu

    def _from_node(self, node: int) -> int:
        return node if node < self.k else node - self.nu

    # -- encode --------------------------------------------------------
    def encode_chunks(
        self, data: dict[int, jax.Array]
    ) -> dict[int, jax.Array]:
        # encode = decode with all parity erased; see _is_traced for
        # the traced/host split rationale
        traced = self._is_traced(data.values())
        xp = jax.numpy if traced else np
        sample = xp.asarray(next(iter(data.values())))
        nbytes = sample.shape[-1]
        if nbytes % self.sub_chunk_no:
            raise ValueError(
                f"chunk bytes {nbytes} not divisible by sub_chunk_no "
                f"{self.sub_chunk_no}"
            )
        sc = nbytes // self.sub_chunk_no
        n = self.q * self.t
        shape = sample.shape[:-1] + (self.sub_chunk_no, sc)
        C = {}
        for i in range(self.k):
            arr = xp.asarray(data[i]) if i in data else None
            C[i] = (
                xp.zeros(shape, np.uint8)
                if arr is None
                else self._reshaped(arr, shape, xp)
            )
        for i in range(self.k, n):
            C[i] = xp.zeros(shape, np.uint8)
        erased = set(range(self.k + self.nu, n))
        self._decode_layered(erased, C, traced)
        return {
            self.k + j: jax.numpy.asarray(
                C[self.k + self.nu + j].reshape(sample.shape[:-1] + (nbytes,))
            )
            for j in range(self.m)
        }

    @staticmethod
    def _reshaped(arr, shape, xp):
        # astype always copies (even same-dtype), so the host path's
        # in-place mutation never aliases caller data
        return arr.reshape(shape).astype(np.uint8)

    # -- full decode ---------------------------------------------------
    def decode_chunks(
        self,
        want_to_read: set[int],
        chunks: dict[int, jax.Array],
    ) -> dict[int, jax.Array]:
        missing = [s for s in want_to_read if s not in chunks]
        if not missing:
            return {s: chunks[s] for s in want_to_read}
        if len(chunks) < self.k:
            raise ValueError(
                f"cannot decode: {len(chunks)} < k={self.k} chunks"
            )
        traced = self._is_traced(chunks.values())
        xp = jax.numpy if traced else np
        sample = xp.asarray(next(iter(chunks.values())))
        nbytes = sample.shape[-1]
        if nbytes % self.sub_chunk_no:
            raise ValueError(
                f"chunk bytes {nbytes} not divisible by sub_chunk_no "
                f"{self.sub_chunk_no}"
            )
        sc = nbytes // self.sub_chunk_no
        n = self.q * self.t
        shape = sample.shape[:-1] + (self.sub_chunk_no, sc)
        C = {}
        erased = set()
        for chunk_id in range(self.k + self.m):
            node = self._to_node(chunk_id)
            if chunk_id in chunks:
                C[node] = self._reshaped(
                    xp.asarray(chunks[chunk_id]), shape, xp
                )
            else:
                C[node] = xp.zeros(shape, np.uint8)
                erased.add(node)
        for i in range(self.k, self.k + self.nu):
            C[i] = xp.zeros(shape, np.uint8)
        self._decode_layered(erased, C, traced)
        out = {s: chunks[s] for s in want_to_read if s in chunks}
        for s in missing:
            out[s] = jax.numpy.asarray(
                C[self._to_node(s)].reshape(sample.shape[:-1] + (nbytes,))
            )
        return out

    # -- the layered engine -------------------------------------------
    @staticmethod
    def _is_traced(values) -> bool:
        """True when any input is a jax tracer: the engines then
        build ONE functional device program (jit over a fixed erasure
        pattern). Eager callers keep the host path — an un-jitted run
        of the traced body would be hundreds of per-op device round
        trips."""
        return any(isinstance(v, jax.core.Tracer) for v in values)

    @staticmethod
    def _setz(arr, z: int, val, traced: bool):
        """arr[..., z, :] = val — in place (host) or functional."""
        if traced:
            return arr.at[..., z, :].set(val)
        arr[..., z, :] = val
        return arr

    def _decode_layered(
        self,
        erased_chunks: set[int],
        C: dict[int, np.ndarray],
        traced: bool = False,
    ) -> None:
        """Recover coupled values of ``erased_chunks`` (node ids) in
        ``C`` (decode_layered, ErasureCodeClay.cc:702-767). TRACE-
        GENERIC like repair: host numpy mutates in place; tracer
        inputs build one functional device program (jit over a fixed
        erasure pattern), which is what makes CLAY encode AND full
        decode usable on device — encode is decode with all parity
        erased."""
        q, t, n = self.q, self.t, self.q * self.t
        erased = set(erased_chunks)
        for i in range(self.k + self.nu, n):
            if len(erased) >= self.m:
                break
            erased.add(i)
        if len(erased) > self.m:
            raise ValueError(
                f"too many erasures {sorted(erased_chunks)} for m={self.m}"
            )
        shape = next(iter(C.values())).shape
        if traced:
            import jax.numpy as jnp

            U = {i: jnp.zeros(shape, np.uint8) for i in range(n)}
        else:
            U = {i: np.zeros(shape, np.uint8) for i in range(n)}

        # order[z] = number of erased nodes that are dots in plane z.
        order: dict[int, list[int]] = {}
        for z in range(self.sub_chunk_no):
            z_vec = self._plane_vector(z)
            sc_order = sum(1 for i in erased if i % q == z_vec[i // q])
            order.setdefault(sc_order, []).append(z)

        for iscore in sorted(order):
            planes = order[iscore]
            # Step a: uncoupled values of non-erased nodes, plane by
            # plane (pair reads touch companion planes of other groups,
            # already final).
            for z in planes:
                self._compute_uncoupled(erased, z, C, U, traced)
            # Step b: ONE batched inner-MDS decode across this score
            # group (TPU delta: the reference dispatches per plane).
            self._decode_uncoupled_batch(erased, planes, U, traced)
            # Step c: uncoupled -> coupled for erased nodes.
            for z in planes:
                z_vec = self._plane_vector(z)
                for node in sorted(erased):
                    x, y = node % q, node // q
                    node_sw = y * q + z_vec[y]
                    z_sw = self._z_sw(z, x, y, z_vec)
                    if z_vec[y] == x:  # dot: C = U
                        C[node] = self._setz(
                            C[node], z, U[node][..., z, :], traced
                        )
                    elif node_sw not in erased:
                        # recover_type1: C_xy from (C_sw, U_xy).
                        ci, ui = self._pair_idx(x, z_vec[y])
                        cj, _ = self._pair_idx(z_vec[y], x)
                        C[node] = self._setz(
                            C[node], z,
                            self._pair_solve(
                                (cj, ui),
                                C[node_sw][..., z_sw, :],
                                U[node][..., z, :],
                                ci,
                            ),
                            traced,
                        )
                    elif z_vec[y] < x:
                        # Both pair members erased: invert the full
                        # pair transform from (U_xy, U_sw).
                        u_xy = U[node][..., z, :]
                        u_sw = U[node_sw][..., z_sw, :]
                        C[node] = self._setz(
                            C[node], z,
                            self._pair_solve((2, 3), u_xy, u_sw, 0),
                            traced,
                        )
                        C[node_sw] = self._setz(
                            C[node_sw], z_sw,
                            self._pair_solve((2, 3), u_xy, u_sw, 1),
                            traced,
                        )

    def _compute_uncoupled(
        self,
        erased: set[int],
        z: int,
        C: dict[int, np.ndarray],
        U: dict[int, np.ndarray],
        traced: bool = False,
    ) -> None:
        """U values of non-erased nodes in plane z (decode_erasures,
        ErasureCodeClay.cc:769-796)."""
        q, t = self.q, self.t
        z_vec = self._plane_vector(z)
        for x in range(q):
            for y in range(t):
                node = q * y + x
                if node in erased:
                    continue
                node_sw = q * y + z_vec[y]
                z_sw = self._z_sw(z, x, y, z_vec)
                if z_vec[y] == x:
                    U[node] = self._setz(
                        U[node], z, C[node][..., z, :], traced
                    )
                elif z_vec[y] < x or node_sw in erased:
                    # Forward transform of the coupled pair fills the
                    # U of both members.
                    node_c, node_u = self._pair_idx(x, z_vec[y])
                    sw_c, sw_u = self._pair_idx(z_vec[y], x)
                    a = C[node][..., z, :]
                    b = C[node_sw][..., z_sw, :]
                    U[node] = self._setz(
                        U[node], z,
                        self._pair_solve((node_c, sw_c), a, b, node_u),
                        traced,
                    )
                    U[node_sw] = self._setz(
                        U[node_sw], z_sw,
                        self._pair_solve((node_c, sw_c), a, b, sw_u),
                        traced,
                    )

    def _decode_uncoupled_batch(
        self,
        erased: set[int],
        planes: list[int],
        U: dict[int, np.ndarray],
        traced: bool = False,
    ) -> None:
        """Inner-MDS decode of erased nodes' U over a batch of planes
        in one device dispatch (decode_uncoupled,
        ErasureCodeClay.cc:798-816)."""
        import jax.numpy as jnp

        n = self.q * self.t
        zsel = np.asarray(planes)
        # the group's planes folded onto the lane axis: at 256 B
        # sub-chunks a plane alone is under the kernels' lane tile and
        # the decode fell to einsum; a group is planes x sub-chunk wide
        shape = next(iter(U.values())).shape
        folded = shape[:-2] + (len(planes) * shape[-1],)
        known = {
            node: jnp.asarray(U[node][..., zsel, :]).reshape(folded)
            for node in range(n)
            if node not in erased
        }
        out = self.mds.decode_chunks(set(erased), known)
        unfolded = shape[:-2] + (len(planes), shape[-1])
        for node in erased:
            if traced:
                U[node] = U[node].at[..., zsel, :].set(
                    out[node].reshape(unfolded)
                )
            else:
                U[node][..., zsel, :] = np.asarray(out[node]).reshape(
                    unfolded
                )

    # -- fractional repair ---------------------------------------------
    def repair(
        self,
        want_to_read: set[int],
        chunks: dict[int, jax.Array],
    ) -> dict[int, jax.Array]:
        """Single-chunk repair from d helpers' repair sub-chunks
        (repair + repair_one_lost_chunk, ErasureCodeClay.cc:454-699).

        ``chunks`` maps helper chunk id -> the CONCATENATED repair
        sub-chunks selected by minimum_to_decode (in plane order).
        Returns the full lost chunk.

        The whole body is TRACE-GENERIC: numpy inputs run the host
        path with in-place updates; jax inputs (or tracers) build a
        single functional device program — ``jax.jit`` over a fixed
        erasure pattern turns repair into ONE dispatch instead of
        hundreds of per-op launches (round-3; the plane planning is
        all static Python either way).
        """
        if len(want_to_read) != 1 or len(chunks) != self.d:
            raise ValueError(
                f"repair wants 1 chunk from exactly d={self.d} helpers"
            )
        lost = next(iter(want_to_read))
        lost_node = self._to_node(lost)
        q, t, n = self.q, self.t, self.q * self.t

        # Traced ONLY under an enclosing jit (tracer inputs): the
        # functional device program then compiles to one dispatch.
        # Eager callers — including the read pipeline handing over
        # concrete jax arrays — keep the host path (coerce to numpy):
        # an UN-jitted run of the traced body would be hundreds of
        # per-op device round trips, the exact cost this split exists
        # to avoid. Mixed input dicts are normalized either way.
        traced = any(
            isinstance(v, jax.core.Tracer) for v in chunks.values()
        )
        if traced:
            import jax.numpy as jnp

            zeros = jnp.zeros
            chunks = {i: jnp.asarray(v) for i, v in chunks.items()}
        else:
            zeros = np.zeros
            chunks = {i: np.asarray(v) for i, v in chunks.items()}

        def setz(arr, z, val):
            return self._setz(arr, z, val, traced)

        repair_planes: list[int] = []
        for index, count in self.get_repair_subchunks(lost_node):
            repair_planes.extend(range(index, index + count))
        plane_ind = {z: i for i, z in enumerate(repair_planes)}
        r = len(repair_planes)

        sample = next(iter(chunks.values()))
        if sample.shape[-1] % r:
            raise ValueError(
                f"helper bytes {sample.shape[-1]} not divisible by "
                f"{r} repair planes"
            )
        sc = sample.shape[-1] // r
        lead = tuple(sample.shape[:-1])
        helper = {}
        aloof = set()
        for chunk_id in range(self.k + self.m):
            node = self._to_node(chunk_id)
            if chunk_id in chunks:
                helper[node] = (
                    chunks[chunk_id]
                    .reshape(lead + (r, sc))
                    .astype(np.uint8)
                )
            elif chunk_id != lost:
                aloof.add(node)
        for i in range(self.k, self.k + self.nu):
            helper[i] = zeros(lead + (r, sc), np.uint8)

        if traced:
            # Plane-blocked Pallas kernels: general d (aloof nodes
            # enter the per-group uncoupled solves as decoded known
            # rows) at any sub_chunk_no — HBM sees each helper byte
            # once in, each recovered byte once out.
            kout = self._repair_kernels(
                lost_node, helper, aloof, sc
            )
            if kout is not None:
                out = kout.reshape(lead + (self.sub_chunk_no * sc,))
                return {lost: out}
        if traced and not aloof:
            # d = k+m-1 (no aloof nodes): every repair plane has
            # intersection score 1 and the whole repair collapses to
            # three whole-tensor stages — the XLA fast path when the
            # kernels are gated off or the geometry does not fit (the
            # itemized stacked path below gathers hundreds of
            # per-plane slices and measured 20 GB/s against this
            # path's device rate).
            recovered = self._repair_fast(
                lost_node, helper, repair_planes, plane_ind
            )
            out = recovered.reshape(lead + (self.sub_chunk_no * sc,))
            return {lost: out}

        recovered = zeros(lead + (self.sub_chunk_no, sc), np.uint8)
        U = {i: zeros(lead + (self.sub_chunk_no, sc), np.uint8)
             for i in range(n)}

        # Erasures for the uncoupled decode: the lost node's whole
        # x-row plus the aloof nodes.
        erasures = {lost_node - lost_node % q + i for i in range(q)}
        erasures |= aloof
        if len(erasures) > self.m:
            raise ValueError(
                f"repair infeasible: {len(erasures)} uncoupled erasures "
                f"> m={self.m}"
            )

        # Order repair planes by intersection score w.r.t. the lost
        # node and aloof nodes.
        ordered: dict[int, list[int]] = {}
        for z in repair_planes:
            z_vec = self._plane_vector(z)
            o = sum(
                1
                for nd in ({lost_node} | aloof)
                if nd % q == z_vec[nd // q]
            )
            if o <= 0:
                raise AssertionError("repair plane with zero order")
            ordered.setdefault(o, []).append(z)

        for o in sorted(ordered):
            planes = ordered[o]
            uitems, citems = self._plan_repair_group(
                planes, erasures, aloof, lost_node
            )
            if traced:
                self._exec_uitems_stacked(uitems, helper, U, plane_ind)
            else:
                for (node, z, c0, c1, asrc, bsrc) in uitems:
                    a = self._item_slice(asrc, helper, U, plane_ind)
                    if c1 == 0 and c0 == 1:
                        U[node] = setz(U[node], z, a)
                        continue
                    b = self._item_slice(bsrc, helper, U, plane_ind)
                    U[node] = setz(
                        U[node], z,
                        gf_mul_bytes(c0, a) ^ gf_mul_bytes(c1, b),
                    )
            # Batched uncoupled decode over this order group.
            self._repair_decode_batch(erasures, planes, U, sc, lead, traced)
            # Convert: recover coupled values of the lost chunk.
            if traced:
                recovered = self._exec_citems_stacked(
                    citems, helper, U, plane_ind, recovered
                )
            else:
                for (zdst, c0, c1, asrc, bsrc) in citems:
                    a = self._item_slice(asrc, helper, U, plane_ind)
                    if c1 == 0 and c0 == 1:
                        recovered = setz(recovered, zdst, a)
                        continue
                    b = self._item_slice(bsrc, helper, U, plane_ind)
                    recovered = setz(
                        recovered, zdst,
                        gf_mul_bytes(c0, a) ^ gf_mul_bytes(c1, b),
                    )
        out = recovered.reshape(lead + (self.sub_chunk_no * sc,))
        return {
            lost: out if traced else jax.numpy.asarray(out)
        }

    # -- the served path: one program a repair, one an encode ----------
    def _signature(self) -> tuple:
        return (
            self.k, self.m, self.d, self.scalar_mds,
            self.profile.get("technique") or "",
        )

    def _kernels_fit(self, b: int, sc: int) -> bool:
        """Do the pair transforms of a repair of ``b`` chunks at ``sc``
        bytes a sub-chunk run on ops/clay_kernels.py?"""
        from ceph_tpu.ops import clay_kernels
        from ceph_tpu.utils import config

        return bool(
            config.get("ec_clay_kernels")
            and self.scalar_mds in ("jerasure", "isa")
            and clay_kernels.supported(b, sc, self.q, self.t)
        )

    def _program(self, key: tuple, build):
        key = (self._signature(),) + key
        with _PROGRAMS_LOCK:
            program = _PROGRAMS.get(key)
            if program is None:
                program = _PROGRAMS[key] = build()
        return program

    def _repair_program(self, lost: int, ids: tuple, b: int, w: int):
        # what the trace branches on is part of the program's name
        kernels = self._kernels_fit(b, w // (self.sub_chunk_no // self.q))

        def build():
            codec = self

            def clay_repair(helpers):
                return codec.repair(
                    {lost}, {cid: helpers[i] for i, cid in enumerate(ids)}
                )[lost]

            # one dispatch a repair: counted under the inner decode's
            # route, its bytes once, under how the pair transforms run
            known = self.q * (self.t - 1) - (self.k + self.m - 1 - len(ids))
            route, _ = self.mds._plan_route((b, known, w), False, 0)
            return _Program(clay_repair, (
                f"{route}_decode",
                "clay_kernel_bytes" if kernels else "clay_fallback_bytes",
            ))

        return self._program(("repair", lost, ids, b, w, kernels), build)

    def repair_window(
        self, lost: int, helper_ids, helpers: np.ndarray
    ) -> np.ndarray:
        """Repair chunk ``lost`` of a window of chunks at once.

        ``helpers`` is ``[d, n, r*sc]``: row i holds helper
        ``helper_ids[i]``'s repair sub-chunks (the plan's runs, packed
        in plane order) of each of the window's n chunks. Returns the
        lost chunks, ``[n, chunk]``, on the host.

        One upload, one launch of one compiled program, one fetch:
        the pair transforms, the inner MDS decode (planes and stripes
        on its lane axis) and the scatter all inside ``repair``'s
        traced path, jitted per (lost chunk, helpers, shape) once a
        process. The first repair of a shape compiles every lost
        chunk's program (``_repair_warm``)."""
        ids = tuple(int(i) for i in helper_ids)
        n, w = helpers.shape[1:]
        b = batch_size(n)
        self._repair_warm(b, w)
        return self._repair_program(lost, ids, b, w).run(helpers, 1, b)

    def _repair_warm(self, b: int, w: int) -> None:
        """On the chip, compile the repair program of every chunk for
        this shape, side by side, while the shape's first repair (and
        any that arrives meanwhile) waits for all of them: a dead OSD
        holds another shard in every PG, so traffic would otherwise
        meet the k+m programs one by one, some inside a measured
        window. Once a process. Off the chip nothing is measured and
        the interpreter's compilations are dear: a program compiles
        when it is first met."""
        from ceph_tpu.utils import platform

        if not platform.on_tpu():
            return
        key = (self._signature(), b, w)
        with _PROGRAMS_LOCK:
            done = _WARMED.get(key)
            first = done is None
            if first:
                done = _WARMED[key] = threading.Event()
        if not first:
            done.wait()
            return
        from concurrent.futures import ThreadPoolExecutor

        chunks = set(range(self.k + self.m))

        def compile_one(lost: int) -> None:
            ids = tuple(sorted(
                self.minimum_to_decode({lost}, chunks - {lost})
            ))
            jax.block_until_ready(
                self._repair_program(lost, ids, b, w)(
                    np.zeros((len(ids), b, w), np.uint8)
                )
            )

        try:
            with ThreadPoolExecutor(len(chunks), "ec-warm") as pool:
                list(pool.map(compile_one, sorted(chunks)))
        finally:
            done.set()

    def _whole_rows(self) -> bool:
        """Data and parity fill whole rows of the node grid and the
        pair matrix is the canonical one, [[3, 2], [2, 3]], its own
        inverse: the geometry ``_encode_whole`` is written for."""
        return (
            self.nu == 0
            and self.k % self.q == 0
            and self.scalar_mds in ("jerasure", "isa")
            and self._pair_coeffs((0, 1), 2) == (3, 2)
            and self._pair_coeffs((1, 0), 3) == (3, 2)
            and self._pair_coeffs((2, 3), 0) == (3, 2)
            and self._pair_coeffs((3, 2), 1) == (3, 2)
        )

    def _encode_whole(self, stripes):
        """The layered encode as three whole-tensor steps, for tracers
        (``_decode_layered`` with whole parity rows erased has one
        score group: every plane holds exactly one parity dot).

        With the planes of a chunk viewed as [q^y, q, q^(t-1-y)]
        around digit y, the partner of node x of row y in the plane
        with digit d is node d in the plane with digit x: the
        transpose of the two axes, and a dot (x == d) is its own
        partner. The canonical pair transform is then
        ``f(H) = H ^ 2*(H ^ H^T)`` for every member, dots included,
        and it is its own inverse. So: U of each data row = f(row);
        U of the parity nodes = the scalar code's encode of all data
        U, planes and stripes on the lane axis; parity row = f(its
        U). A dozen fused ops and one kernel, where the plane-by-plane
        trace is some three thousand and half a second on the chip."""
        import jax.numpy as jnp

        q, t = self.q, self.t
        b, _k, cs = stripes.shape

        def f(row, y):  # [b, q, chunk] of row y
            h = row.reshape(b, q, q**y, q, cs // q ** (y + 1))
            return (h ^ _gf_mul2(h ^ jnp.swapaxes(h, 1, 3))).reshape(
                b, q, cs
            )

        rows = self.k // q
        u = jnp.concatenate(
            [f(stripes[:, y * q : (y + 1) * q], y) for y in range(rows)],
            axis=1,
        )
        pu = self.mds._dispatch_bitmatrix(
            self.mds._encode_bmat_np, self.mds._encode_bmat, u, "encode"
        )
        return jnp.concatenate(
            [
                f(pu[:, j * q : (j + 1) * q], rows + j)
                for j in range(t - rows)
            ],
            axis=1,
        )

    def encode_stacked(self, stacked):
        """Parity of ``[n, k, chunk]`` stripes as ``[n, m, chunk]``:
        the layered encode (``encode_chunks`` on tracers) as one
        compiled program a padded batch size, one upload and one
        launch where the host path ran 64 planes of numpy and a
        dispatch a score group. The write pipeline's entry
        (``ShardExtentMap._dispatch_encode``)."""
        import jax.numpy as jnp

        stacked = np.asarray(stacked)
        n, k, cs = stacked.shape
        b = batch_size(n)

        def build():
            codec = self

            def clay_encode(stripes):
                if codec._whole_rows():
                    return codec._encode_whole(stripes)
                parity = codec.encode_chunks(
                    {i: stripes[:, i, :] for i in range(k)}
                )
                return jnp.stack(
                    [parity[k + j] for j in range(codec.m)], axis=1
                )

            route, _ = self.mds._plan_route((b, k + self.nu, cs), False, 0)
            return _Program(
                clay_encode, (f"{route}_encode", f"{route}_encode_bytes")
            )

        return self._program(("encode", b, cs), build).run(stacked, 0, b)

    # -- fast repair (aloof-free: d = k+m-1) ---------------------------
    def _repair_fast(
        self, lost_node: int, helper: dict,
        repair_planes: list, plane_ind: dict,
    ):
        """Whole-tensor repair for the aloof-free case. With d =
        k+m-1 every helper node is present, every repair plane has
        intersection score 1, and the pair algebra reduces to
        PER-PLANE-CONSTANT GF ladders:

        a. For each row y != y_lost, the q helpers' uncoupled values
           are c0(z)*h[x][z] ^ c1(z)*h[x'][z'] where (x', z') is a
           static permutation of the same row's (helper, plane) grid
           and the coefficients depend only on the plane's digit —
           one stack + one gather + two ladders per row, instead of
           one stacked dispatch per (node, plane) work item.
        b. The lost ROW's uncoupled values come from ONE inner-MDS
           decode with the plane axis folded into the lane axis (so
           the shards-form MXU kernel serves it at full tile width).
        c. The lost chunk's q^t coupled planes are a static
           permutation of q per-row-member ladder combinations.

        Matches repair_one_lost_chunk (ErasureCodeClay.cc:454-699)
        restricted to aloof == {}; the itemized path keeps the
        general case."""
        import jax.numpy as jnp

        q, t, n = self.q, self.t, self.q * self.t
        y_l, x_l = lost_node // q, lost_node % q
        P = len(repair_planes)
        pvecs = [self._plane_vector(z) for z in repair_planes]
        sc = helper[next(iter(helper))].shape[-1]

        # -- a: uncoupled values of every non-lost row ---------------
        U: dict[int, jax.Array] = {}
        row_u: list = []  # Uy per non-lost row, ascending y
        for y in range(t):
            if y == y_l:
                continue
            Hy = jnp.stack(
                [helper[y * q + x] for x in range(q)], axis=-3
            )  # [..., q, P, sc]
            lead = Hy.shape[:-3]
            flat = Hy.reshape(lead + (q * P, sc))
            c0s = np.zeros(q * P, np.uint8)
            c1s = np.zeros(q * P, np.uint8)
            bidx = np.zeros(q * P, np.int32)
            for x in range(q):
                for p in range(P):
                    zv = pvecs[p][y]
                    i = x * P + p
                    if zv == x:  # dot: U = C
                        c0s[i], c1s[i], bidx[i] = 1, 0, i
                        continue
                    node_c, node_u = self._pair_idx(x, zv)
                    sw_c, _ = self._pair_idx(zv, x)
                    c0s[i], c1s[i] = self._pair_coeffs(
                        (node_c, sw_c), node_u
                    )
                    z_sw = repair_planes[p] + (x - zv) * _pow_int(
                        q, t - 1 - y
                    )
                    bidx[i] = zv * P + plane_ind[z_sw]
            B = jnp.take(flat, jnp.asarray(bidx), axis=-2)
            # The canonical pair transform is U = C ^ 2*(C_hi^C_lo)
            # for BOTH members ((c0,c1) = (3,2) on (self, partner)),
            # so the whole row reduces to one masked mul-by-2 — a
            # 5-op fusion instead of two 8-step ladders. The ladder
            # form stays as the fallback for any other _g4.
            if all(
                (int(c0s[i]), int(c1s[i])) in ((1, 0), (3, 2))
                for i in range(q * P)
            ):
                mask = jnp.asarray(
                    (c1s != 0).astype(np.uint8)
                ).reshape(-1, 1)
                Uy = flat ^ _gf_mul2((flat ^ B) * mask)
            else:
                Uy = _gf_mul_planes(c0s, flat) ^ _gf_mul_planes(c1s, B)
            row_u.append(Uy.reshape(Hy.shape))

        # -- b: one batched inner-MDS decode of the lost row ---------
        # The known nodes are exactly the non-lost rows, already
        # stacked per row — concat them into the [.., C, N] form and
        # hit the STACKED MXU kernel directly (the shards-form route
        # measured 102 GB/s at c=8 vs 267 stacked; the stack here is
        # one cheap concat of row tensors, not a per-shard relayout).
        from .matrix_codec import dev_bmat

        erased_row = {y_l * q + x for x in range(q)}
        present = [nd for nd in range(n) if nd not in erased_row]
        want = sorted(erased_row)
        stack = jnp.concatenate(row_u, axis=-3)  # [.., (t-1)q, P, sc]
        lead = stack.shape[:-3]
        if self.scalar_mds in ("jerasure", "isa"):
            ks = stack.reshape(lead + (len(present), P * sc))
            key = (tuple(present), tuple(want))
            bmat_np = self.mds._tables.get(
                key, lambda: self.mds._build_decode_bmat(present, want)
            )
            dec = self.mds._dispatch_bitmatrix(
                bmat_np,
                dev_bmat(self.mds._tables, key, bmat_np, True),
                ks, "decode",
            )  # [.., q, P*sc]
            for idx, node in enumerate(want):
                U[node] = dec[..., idx, :].reshape(lead + (P, sc))
        else:
            # shec inner codec: its decode runs a non-MDS subset
            # search — go through its own decode_chunks
            known = {
                node: stack[..., i, :, :].reshape(lead + (P * sc,))
                for i, node in enumerate(present)
            }
            dec = self.mds.decode_chunks(erased_row, known)
            for node in want:
                U[node] = dec[node].reshape(lead + (P, sc))

        # -- c: coupled planes of the lost chunk ---------------------
        srcs = []
        for x in range(q):
            node = y_l * q + x
            if x == x_l:
                srcs.append(U[lost_node])
                continue
            node_c, node_u = self._pair_idx(x, x_l)
            lost_c, _ = self._pair_idx(x_l, x)
            c0, c1 = self._pair_coeffs((node_c, node_u), lost_c)
            if (c0, c1) == (143, 142):
                # C_lost = C_x ^ inv2*(C_x ^ U_x): the inverse of the
                # canonical pair transform, one div-by-2 fusion
                srcs.append(
                    helper[node]
                    ^ _gf_div2(helper[node] ^ U[node])
                )
            else:
                srcs.append(
                    _gf_mul_traced(c0, helper[node])
                    ^ _gf_mul_traced(c1, U[node])
                )
        stack4 = jnp.stack(srcs, axis=-3)  # [..., q, P, sc]
        flat = stack4.reshape(stack4.shape[:-3] + (q * P, sc))
        inv = np.zeros(self.sub_chunk_no, np.int32)
        for x in range(q):
            for p in range(P):
                z_dst = repair_planes[p] + (x - x_l) * _pow_int(
                    q, t - 1 - y_l
                )
                inv[z_dst] = x * P + p
        return jnp.take(flat, jnp.asarray(inv), axis=-2)

    # -- Pallas kernel repair (general d, plane-blocked) ---------------
    def _kernel_plan(self, lost_node: int, aloof: frozenset) -> dict:
        """Static planning for the kernel repair path, cached per
        (lost node, aloof set) — digit strides, member kinds, pair
        coefficients, intersection-score groups and the B2 patch
        items.  Pure host arithmetic: one dict serves every traced
        repair of the same erasure pattern."""
        key = (lost_node, aloof)
        plan = self._kernel_plans.get(key)
        if plan is None:
            plan = self._build_kernel_plan(lost_node, aloof)
            self._kernel_plans[key] = plan
        return plan

    def _build_kernel_plan(self, lost_node: int, aloof: frozenset) -> dict:
        q, t = self.q, self.t
        y_l, x_l = lost_node // q, lost_node % q
        r = self.sub_chunk_no // q
        rows = [y for y in range(t) if y != y_l]

        def stride(y: int) -> int:
            # repair-index stride of digit y: q per free digit minor
            # to it (free = every row but y_l; y=0 most significant)
            return _pow_int(q, sum(1 for y2 in rows if y2 > y))

        def kind(node: int) -> str:
            if node in aloof:
                return "a"
            if self.k <= node < self.k + self.nu:
                return "v"
            return "r"

        strides = tuple(stride(y) for y in rows)
        kinds = tuple(
            tuple(kind(y * q + x) for x in range(q)) for y in rows
        )
        lost_kinds = tuple(kind(y_l * q + x) for x in range(q))
        # (self, partner) coefficients: forward transform U_self from
        # (C_self, C_partner), hi/lo member; inverse C_lost from
        # (C_helper, U_helper) of a lost-row member.
        pair_fwd = (
            self._pair_coeffs((0, 1), 2),
            self._pair_coeffs((1, 0), 3),
        )
        pair_inv = (
            self._pair_coeffs((0, 2), 1),
            self._pair_coeffs((1, 3), 0),
        )
        present = [
            y * q + x
            for y in rows
            for x in range(q)
            if (y * q + x) not in aloof
        ]
        want = sorted({y_l * q + x for x in range(q)} | aloof)

        def digit(p: int, y: int) -> int:
            return (p // stride(y)) % q

        score = [
            1 + sum(
                1 for nd in aloof if digit(p, nd // q) == nd % q
            )
            for p in range(r)
        ]
        groups: dict[int, np.ndarray] = {}
        for s in sorted(set(score)):
            groups[s] = np.array(
                [p for p in range(r) if score[p] == s], np.int64
            )
        # B2 patch items: helpers sharing a row with an aloof node, at
        # the planes where that aloof node is a dot.  Their uncoupled
        # value needs the aloof node's U from the companion plane (one
        # score lower) — patched between group decodes.
        patches: dict[int, list] = {}
        for nd_a in sorted(aloof):
            x_a, y_a = nd_a % q, nd_a // q
            s_a = stride(y_a)
            dots = [p for p in range(r) if digit(p, y_a) == x_a]
            for x in range(q):
                nd = y_a * q + x
                if x == x_a or nd in aloof:
                    continue
                node_c, node_u = self._pair_idx(x, x_a)
                _sw_c, sw_u = self._pair_idx(x_a, x)
                c0, c1 = self._pair_coeffs((node_c, sw_u), node_u)
                by_score: dict[int, list[int]] = {}
                for p in dots:
                    by_score.setdefault(score[p], []).append(p)
                for s, ps in by_score.items():
                    psw = [p + (x - x_a) * s_a for p in ps]
                    patches.setdefault(s, []).append((
                        nd, nd_a,
                        np.array(ps, np.int64),
                        np.array(psw, np.int64),
                        c0, c1,
                    ))
        return {
            "rows": rows,
            "strides": strides,
            "kinds": kinds,
            "lost_kinds": lost_kinds,
            "pair_fwd": pair_fwd,
            "pair_inv": pair_inv,
            "present": present,
            "want": want,
            "groups": groups,
            "patches": patches,
            "seq": _pow_int(q, sum(1 for y2 in rows if y2 > y_l)),
        }

    def _repair_kernels(self, lost_node, helper, aloof, sc):
        """All repair stages on the plane-blocked Pallas kernels
        (ops/clay_kernels.py) + per-score-group MXU decodes: HBM sees
        each helper byte once in, each recovered byte once out — the
        XLA formulation's stack/gather/permute intermediates cost
        ~10x the payload in HBM traffic.  General d: aloof nodes are
        decoded alongside the lost row and their U feeds the next
        score group's B2 patches (repair_one_lost_chunk's helper
        split, ErasureCodeClay.cc:454-699).  Returns None when the
        kernels are gated off or the geometry does not fit (the XLA
        paths take over)."""
        import numpy as _np

        from ceph_tpu.ops import clay_kernels
        from ceph_tpu.utils import platform

        q, t = self.q, self.t
        r = self.sub_chunk_no // q
        sample = helper[next(iter(helper))]
        lead = sample.shape[:-2]
        b = int(_np.prod(lead, initial=1))
        if not self._kernels_fit(b, sc):
            return None
        import jax.numpy as jnp

        from .matrix_codec import dev_bmat

        plan = self._kernel_plan(lost_node, frozenset(aloof))
        interp = platform.pallas_interpret()
        flat = {
            node: helper[node].reshape((b, r * sc)) for node in helper
        }
        real_in = [
            flat[y * q + x]
            for ri, y in enumerate(plan["rows"])
            for x in range(q)
            if plan["kinds"][ri][x] == "r"
        ]
        # stage a: every B1 pair transform in one plane-blocked pass
        U = dict(zip(plan["present"], clay_kernels.uncoupled_rows(
            q, plan["strides"], plan["kinds"], plan["pair_fwd"],
            real_in, r, sc, interp,
        )))
        # stage b: inner-MDS decode of lost row + aloof, one dispatch
        # per intersection-score group (aloof-free: exactly one).
        present, want = plan["present"], plan["want"]
        key = (tuple(present), tuple(want))
        bmat_np = self.mds._tables.get(
            key, lambda: self.mds._build_decode_bmat(present, want)
        )
        bdev = dev_bmat(self.mds._tables, key, bmat_np, True)
        groups = plan["groups"]
        if len(groups) == 1:
            # planes and stripes are on the lane axis already ([b,
            # r*sc] a node): the stacked kernel, as in _repair_fast
            # (102 GB/s shards form against 267 stacked at c=8)
            dec = self.mds._dispatch_bitmatrix(
                bmat_np, bdev,
                jnp.stack([U[nd] for nd in present], axis=-2), "decode",
            )
            Uw = {nd: dec[..., i, :] for i, nd in enumerate(want)}
        else:
            Uv = {nd: U[nd].reshape(b, r, sc) for nd in present}
            Uwb = {
                nd: jnp.zeros((b, r, sc), _np.uint8) for nd in want
            }
            for s in sorted(groups):
                for (nd, nd_a, ps, psw, c0, c1) in plan[
                    "patches"
                ].get(s, ()):
                    cx = jnp.take(
                        flat[nd].reshape(b, r, sc),
                        jnp.asarray(ps), axis=1,
                    )
                    ua = jnp.take(Uwb[nd_a], jnp.asarray(psw), axis=1)
                    val = (
                        _gf_mul_traced(c0, cx)
                        ^ _gf_mul_traced(c1, ua)
                    )
                    Uv[nd] = Uv[nd].at[:, ps, :].set(val)
                zsel = jnp.asarray(groups[s])
                known = [
                    jnp.take(Uv[nd], zsel, axis=1).reshape(b, -1)
                    for nd in present
                ]
                dec = self.mds._dispatch_bitmatrix_shards(
                    bmat_np, bdev, known, "decode"
                )
                for i, nd in enumerate(want):
                    Uwb[nd] = Uwb[nd].at[:, groups[s], :].set(
                        dec[i].reshape(b, len(groups[s]), sc)
                    )
            Uw = {nd: v.reshape(b, r * sc) for nd, v in Uwb.items()}
        # stage c: couple + blocked scatter of the lost chunk
        y_l, x_l = lost_node // q, lost_node % q
        udec = [Uw[y_l * q + x] for x in range(q)]
        lost_help = [
            flat[y_l * q + x]
            for x in range(q)
            if x != x_l and plan["lost_kinds"][x] == "r"
        ]
        rec = clay_kernels.couple_scatter(
            q, x_l, plan["lost_kinds"], plan["pair_inv"],
            udec, lost_help, plan["seq"], r, sc, interp,
        )
        return rec.reshape(lead + (self.sub_chunk_no, sc))

    # -- repair work-item planning + stacked execution -----------------
    def _plan_repair_group(
        self,
        planes: list[int],
        erasures: set[int],
        aloof: set[int],
        lost_node: int,
    ):
        """Static work items for one intersection-score group — ONE
        source of truth for the pair algebra, executed either stacked
        (traced device path) or element-at-a-time (host path).

        U item:  (node, z, c0, c1, a_src, b_src): U[node][z] =
                 c0*a ^ c1*b.
        C item:  (z_dst, c0, c1, a_src, b_src): recovered[z_dst] = ...
        src: ("h", node, z) helper packet at repair-plane z, or
             ("u", node, z) U packet at absolute plane z.
        """
        q, t = self.q, self.t
        uitems, citems = [], []
        for z in planes:
            z_vec = self._plane_vector(z)
            for y in range(t):
                for x in range(q):
                    node = y * q + x
                    if node in erasures:
                        continue
                    node_sw = y * q + z_vec[y]
                    z_sw = self._z_sw(z, x, y, z_vec)
                    # Tuple indices of this node and its companion in
                    # the canonical (C_hi, C_lo, U_hi, U_lo).
                    node_c, node_u = self._pair_idx(x, z_vec[y])
                    sw_c, sw_u = self._pair_idx(z_vec[y], x)
                    if node_sw in aloof:
                        # U_xy from (C_xy, U_sw) — U_sw was decoded in
                        # an earlier (lower-order) plane group.
                        c0, c1 = self._pair_coeffs((node_c, sw_u), node_u)
                        uitems.append((
                            node, z, c0, c1,
                            ("h", node, z), ("u", node_sw, z_sw),
                        ))
                    elif z_vec[y] != x:
                        # Both coupled values are helper data.
                        c0, c1 = self._pair_coeffs((node_c, sw_c), node_u)
                        uitems.append((
                            node, z, c0, c1,
                            ("h", node, z), ("h", node_sw, z_sw),
                        ))
                    else:
                        uitems.append((
                            node, z, 1, 0,
                            ("h", node, z), ("h", node, z),
                        ))
            for node in sorted(erasures):
                if node in aloof:
                    continue
                x, y = node % q, node // q
                node_sw = y * q + z_vec[y]
                z_sw = self._z_sw(z, x, y, z_vec)
                if x == z_vec[y]:
                    if node == lost_node:
                        citems.append((
                            z, 1, 0, ("u", node, z), ("u", node, z)
                        ))
                else:
                    # Helper member of the lost row: its coupled
                    # (helper) value plus its U give the LOST node's
                    # coupled value at the companion plane.
                    if y != lost_node // q or node_sw != lost_node:
                        raise AssertionError("unexpected repair pair")
                    node_c, node_u = self._pair_idx(x, z_vec[y])
                    lost_c, _ = self._pair_idx(z_vec[y], x)
                    c0, c1 = self._pair_coeffs((node_c, node_u), lost_c)
                    citems.append((
                        z_sw, c0, c1, ("h", node, z), ("u", node, z)
                    ))
        return uitems, citems

    @staticmethod
    def _item_slice(src, helper, U, plane_ind):
        kind, node, z = src
        if kind == "h":
            return helper[node][..., plane_ind[z], :]
        return U[node][..., z, :]

    def _exec_uitems_stacked(self, uitems, helper, U, plane_ind) -> None:
        """All pair transforms of a plane group as ONE stacked
        dispatch: [P, lead, sc] operand stacks, per-row constant GF
        ladder, then grouped scatter back into U."""
        import jax.numpy as jnp

        if not uitems:
            return
        A = jnp.stack([
            self._item_slice(a, helper, U, plane_ind)
            for (_, _, _, _, a, _) in uitems
        ])
        B = jnp.stack([
            self._item_slice(b, helper, U, plane_ind)
            for (_, _, _, _, _, b) in uitems
        ])
        c0s = np.array([it[2] for it in uitems], np.uint8)
        c1s = np.array([it[3] for it in uitems], np.uint8)
        out = _gf_mul_vec_traced(c0s, A) ^ _gf_mul_vec_traced(c1s, B)
        by_node: dict[int, list[int]] = {}
        for idx, (node, *_rest) in enumerate(uitems):
            by_node.setdefault(node, []).append(idx)
        for node, idxs in by_node.items():
            zs = np.array([uitems[i][1] for i in idxs])
            sel = jnp.moveaxis(out[np.array(idxs)], 0, -2)
            U[node] = U[node].at[..., zs, :].set(sel)

    def _exec_citems_stacked(
        self, citems, helper, U, plane_ind, recovered
    ):
        import jax.numpy as jnp

        if not citems:
            return recovered
        A = jnp.stack([
            self._item_slice(a, helper, U, plane_ind)
            for (_, _, _, a, _) in citems
        ])
        B = jnp.stack([
            self._item_slice(b, helper, U, plane_ind)
            for (_, _, _, _, b) in citems
        ])
        c0s = np.array([it[1] for it in citems], np.uint8)
        c1s = np.array([it[2] for it in citems], np.uint8)
        out = _gf_mul_vec_traced(c0s, A) ^ _gf_mul_vec_traced(c1s, B)
        zs = np.array([it[0] for it in citems])
        sel = jnp.moveaxis(out, 0, -2)
        return recovered.at[..., zs, :].set(sel)

    def _repair_decode_batch(
        self,
        erasures: set[int],
        planes: list[int],
        U: dict,
        sc: int,
        lead: tuple,
        traced: bool = False,
    ) -> None:
        import jax.numpy as jnp

        n = self.q * self.t
        zsel = np.asarray(planes)
        # host path keeps numpy: the inner decode's dispatch then
        # serves small ops from host GF tables and ROUTES large ones
        # (the mesh takes host-staged inputs too); converting to
        # device arrays here barred both and forced einsum
        conv = jnp.asarray if traced else np.ascontiguousarray
        known = {
            node: conv(U[node][..., zsel, :])
            for node in range(n)
            if node not in erasures
        }
        out = self.mds.decode_chunks(set(erasures), known)
        for node in erasures:
            if traced:
                U[node] = U[node].at[..., zsel, :].set(out[node])
            else:
                U[node][..., zsel, :] = np.asarray(out[node])


registry.register("clay", ClayCodec, PLUGIN_ABI_VERSION)
