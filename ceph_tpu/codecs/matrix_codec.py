"""Generic GF(2^8) matrix erasure codec on the bit-plane MXU engine.

The shared engine under every matrix-style family (jerasure
reed_sol_van/reed_sol_r6_op/cauchy_*, ISA-L RS) — the role
``jerasure_matrix_encode`` / ``ec_encode_data`` play in the reference,
re-designed so one jitted dispatch encodes an arbitrary stripe batch.

Decode matrices are computed host-side (tiny <=32x32 inversions) and
cached in an LRU keyed by the erasure signature — the TableCache
precedent (isa/ErasureCodeIsaTableCache.cc; SURVEY.md section 7
"Hard parts").
"""

from __future__ import annotations

from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.gf import (
    decode_matrix,
    gf_matrix_to_bitmatrix,
)
from ceph_tpu.ops import xor_schedule
from ceph_tpu.ops.bitplane import gf_encode_bitplane, xor_bytes
from ceph_tpu.utils import platform
from ceph_tpu.utils.perf_counters import built_once, register_thread_roles

from .base import ErasureCodeBase
from .interface import Flag

# the pools that compile a geometry's programs side by side, once
register_thread_roles({"ec-warm*": "other_python"})


@jax.jit
def _apply_bitmatrix(bmat: jax.Array, shards: jax.Array) -> jax.Array:
    return gf_encode_bitplane(bmat, shards)


@built_once
def _dispatch_counters():
    """Kernel-path visibility: which engine served each bit-matrix
    application (Pallas MXU kernel / XLA einsum / host GF tables) and
    how often an enabled Pallas path had to fall back on an
    untileable shape. Served by ``perf dump`` as ``ec_dispatch``."""
    from ceph_tpu.utils.perf_counters import (
        PerfCountersBuilder,
        perf_collection,
    )

    b = PerfCountersBuilder(perf_collection, "ec_dispatch")
    routes = {
        "mesh": "sharded over the mesh",
        "pallas": "served by the Pallas kernel",
        "einsum": "served by the einsum engine",
        "host": "served by host GF tables",
        "sched": "served by the schedule-native XOR kernel (sparse "
                 "packet bit-matrices)",
    }
    for op in ("encode", "decode", "delta"):
        for route, how in routes.items():
            b.add_u64_counter(f"{route}_{op}", f"{op}s {how}")
            b.add_u64_counter(
                f"{route}_{op}_bytes", f"input bytes of the {op}s {how}"
            )
    b.add_u64_counter(
        "dispatches", "dispatches served, over every route and op"
    )
    # the host's side of a dispatch, step by step: each is the wall of
    # the ``codec.<step>`` span (codec_stage), summed over dispatches
    b.add_time("prep_seconds", "codec.prep: stack / reshape on the host")
    b.add_time("h2d_seconds", "codec.h2d: jnp.asarray of host data")
    b.add_time(
        "launch_seconds",
        "codec.launch: the jitted / Pallas call returning (on the mesh "
        "route the sharded upload rides inside it)",
    )
    b.add_time(
        "fetch_seconds",
        "codec.fetch: np.asarray of the results (waits for the kernel "
        "and copies back)",
    )
    b.add_u64_counter(
        "fused_encode",
        "encodes served by the fused encode+checksum kernel (parity "
        "AND per-block crc32c in one device pass)",
    )
    b.add_u64_counter(
        "fused_encode_bytes", "input bytes of the fused encodes"
    )
    b.add_u64_counter(
        "clay_kernel_bytes",
        "helper bytes of the CLAY repairs whose pair transforms ran on "
        "the plane-blocked Pallas kernels (ops/clay_kernels.py)",
    )
    b.add_u64_counter(
        "clay_fallback_bytes",
        "helper bytes of the CLAY repairs whose pair transforms ran as "
        "XLA ops (kernels gated off, or a geometry they do not take)",
    )
    b.add_u64_counter(
        "fused_fallback",
        "fused encode+csum requests the kernel could not serve "
        "(untileable shape / non-TPU without interpret) — parity "
        "encoded normally, csums fell back to the host tier",
    )
    b.add_u64_counter(
        "sched_rejected_density",
        "sched-eligible dispatches that fell back to the MXU engine "
        "because even the post-CSE schedule stayed over the op-count "
        "gate (dense matrix); counted once per dispatch at the "
        "terminal schedule probe",
    )
    b.add_u64_counter(
        "sched_rejected_shape",
        "sched-eligible dispatches that fell back because no "
        "schedule kernel form could tile the shape (packet axis not "
        "lane-tileable / VMEM-oversized shard blocks)",
    )
    b.add_u64_counter(
        "pallas_fallback",
        "dispatches where Pallas was enabled on TPU but the shape "
        "could not tile (chunk axis % LANE_TILE != 0)",
    )
    b.add_u64_counter(
        "mesh_fallback",
        "dispatches where a mesh was installed but neither the stripe "
        "batch nor the lane axis divided dp (the shard axis always "
        "zero-pads to sp) and a single-chip route served the op",
    )
    return b.create_perf_counters()


def count_route(name: str, *arrays, nbytes: int | None = None) -> None:
    """Count one served dispatch under ``name`` and the input bytes it
    carried under ``name_bytes`` — the host/device byte split is what
    says how much of the traffic reached the chip. ``nbytes`` overrides
    the arrays' size where they carry zero columns or padding (a
    batched parity delta counts its real delta pages only)."""
    pc = _dispatch_counters()
    pc.inc("dispatches")
    pc.inc(name)
    if nbytes is None:
        nbytes = sum(int(a.size) * a.dtype.itemsize for a in arrays)
    pc.inc(name + "_bytes", nbytes)


def codec_stage(step: str):
    """Time one host-side step of a dispatch (``prep``, ``h2d``,
    ``launch``, ``fetch``) once: a ``codec.<step>`` span under the
    caller's encode / reconstruct stage, and the same seconds into
    ``ec_dispatch:<step>_seconds``. It reads the host's clock around
    code that runs anyway: no sync, no copy of its own."""
    from ceph_tpu.utils.trace import tracer

    return tracer.span(
        "codec." + step, perf=_dispatch_counters(), key=step + "_seconds"
    )


#: the unit of a batched parity delta: one page of one data column
#: (the RMW planner page-aligns every parity window)
DELTA_UNIT = 4096
#: a delta batch of at most this many units stays on the host GF
#: tables: the host-or-device decision, taken once on the batch. Read
#: off the served path (PERF.md section 5, PR 26: both routes in one
#: run of ``rs84-rbd.randwrite``, a coin a batch): a page on the host
#: tables costs ~2.2 ms there, a device dispatch 10-15 ms whatever it
#: carries, so the device wins from six pages on
DELTA_HOST_UNITS = 5


def delta_batch_sizes() -> tuple[int, ...]:
    """Every unit count a batched delta dispatch can have on the
    device: batches are zero-padded up to the next power of two, the
    largest being two pages for each op of a full coalesced tick
    (``osd_coalesce_max`` x 2). A fixed, small set, so that the device
    route compiles a known list of programs and traffic cannot meet a
    new shape mid-window."""
    from ceph_tpu.utils import config

    top = 2 * int(config.get("osd_coalesce_max"))
    sizes = [1]
    while sizes[-1] < top:
        sizes.append(sizes[-1] * 2)
    return tuple(sizes)


#: (encode bit-matrix, unit length, device route) of the delta programs
#: already compiled in this process
_delta_warmed: set[tuple] = set()

#: stripes in the largest batched dispatch (``encode_batch``: the
#: staging ring's): a 4 MiB object's worth at k=8, chunk 4096, so the
#: top program is the one a whole 4 MiB write compiles anyway
BATCH_MAX_STRIPES = 128
#: the smallest compiled batch, and the step from one size to the next
_BATCH_MIN_STRIPES, _BATCH_STEP = 2, 4


def batch_sizes() -> tuple[int, ...]:
    """Every stripe count a batched dispatch can have on the device: a
    batch is zero-padded up to the next of 2, 8, 32, 128 (a zero stripe
    encodes to zero parity, and its rows are dropped), the largest
    being ``BATCH_MAX_STRIPES``; more than that is more than one
    dispatch. Steps of four, not two: the device's share of a dispatch
    is microseconds at any of these sizes and a zero stripe costs the
    host a memset, while each size is a Mosaic compilation that the
    pool's first batch waits for (``_batch_warm``: 11 s cold for the
    four, PERF.md section 5, against a client's 15 s op timeout).
    A fixed, small set, so that traffic cannot meet a new shape in the
    middle of a window."""
    sizes = [min(_BATCH_MIN_STRIPES, BATCH_MAX_STRIPES)]
    while sizes[-1] < BATCH_MAX_STRIPES:
        sizes.append(min(sizes[-1] * _BATCH_STEP, BATCH_MAX_STRIPES))
    return tuple(sizes)


def padded_size(stripes: int) -> int:
    """The size of ``batch_sizes()`` that ``stripes`` ride as."""
    return next(p for p in batch_sizes() if p >= stripes)


#: (encode bit-matrix, chunk length, csum block) of the fused batch
#: programs already compiled in this process
_batch_warmed: set[tuple] = set()


def _upload(x):
    """``codec.h2d``: host data onto the device, where the jitted call
    would have put it anyway; device arrays and tracers pass through."""
    if not isinstance(x, np.ndarray):
        return x
    with codec_stage("h2d"):
        return jnp.asarray(x)


def dev_bmat(
    cache: "DecodeTableCache", key: tuple, np_mat: np.ndarray,
    traced: bool,
) -> jax.Array:
    """Device copy of a host matrix. Under a trace the copy is a
    TRACE-LOCAL constant — caching an array created while tracing
    stores that trace's tracer and poisons every later call with the
    same key (UnexpectedTracerError; the round-3 lru_cache lesson,
    re-hit by the traced CLAY repair's inner decode). Eager callers
    get an LRU-cached concrete upload."""
    if traced:
        return jnp.asarray(np_mat)
    return cache.get(("dev",) + key, lambda: jnp.asarray(np_mat))


class DecodeTableCache:
    """LRU of device bit-matrices keyed by (present-shards, wanted-shards).

    The ISA plugin caches inverted decode tables because inversion is the
    sequential hot-path cost under churny erasure patterns
    (ErasureCodeIsaTableCache.cc, 327 LoC). Same idea; the cached value
    here is the expanded GF(2) matrix, host-side and on device (both
    forms: the Pallas kernel folds the host copy, einsum uses the
    device copy).
    """

    def __init__(self, maxsize: int = 256) -> None:
        self.maxsize = maxsize
        # Values are whatever the builder returns — (np bitmatrix,
        # device bitmatrix) pairs here; codecs may cache richer tuples.
        self._cache: OrderedDict[tuple, object] = OrderedDict()
        self.hits = 0
        self.misses = 0

    def get(self, key: tuple, build):
        if key in self._cache:
            self._cache.move_to_end(key)
            self.hits += 1
            return self._cache[key]
        self.misses += 1
        val = build()
        self._cache[key] = val
        if len(self._cache) > self.maxsize:
            self._cache.popitem(last=False)
        return val


def as_01_matrix(mat) -> np.ndarray | None:
    """``mat`` as a contiguous uint8 0/1 matrix, or None when any
    entry is a generic GF(2^8) coefficient. Over the subfield {0,1}
    each output chunk is a pure XOR of input chunks, so the schedule
    kernels apply with packet == chunk (w=1): how LRC xor-local-parity
    repair (a single all-ones decode row) and the xor plugin's parity
    ride the schedule engine. Generic rows are not schedule-eligible,
    which is not a rejection: no counter."""
    mat = np.asarray(mat)
    if mat.size == 0 or int(mat.max()) > 1:
        return None
    return np.ascontiguousarray(mat, dtype=np.uint8)


class BitplaneDispatchMixin:
    """The device-dispatch engine shared by every bit-plane codec
    family: route one bitmatrix application to the mesh (when
    installed), host GF tables (small numpy inputs), the schedule
    kernels (sparse 0/1 matrices), the Pallas MXU kernel (on TPU,
    tileable shapes), or the XLA einsum engine — with every route
    visible in the ``ec_dispatch`` counters. The byte matrix families
    (jerasure RS/Cauchy, ISA) and the packet bit-matrix families
    (liberation/blaum_roth/liber8tion) both dispatch here; the
    reference splits these across jerasure_matrix_encode vs
    jerasure_schedule_encode, but on TPU they are one engine.

    ``_plan_route`` is the one place that orders the routes; every
    dispatch site asks it (``_route`` / ``_route_shards``) and
    executes the route it names."""

    @staticmethod
    def _active_mesh():
        """The configured dispatch mesh, or None. Mesh routing wins
        over every single-chip path (including the host small-op
        shortcut) — when the operator installs a mesh, shard fan-out
        IS the system's dispatch, the way the reference's sub-op
        fan-out is its distributed backend (SURVEY.md §5.8)."""
        from ceph_tpu.utils import config

        if not config.get("ec_use_mesh"):
            return None
        from ceph_tpu.parallel import dispatch as mesh_dispatch

        return mesh_dispatch.get_mesh()

    def _mesh_routable_shape(self, shape) -> bool:
        """True when a mesh is active AND this dispatch shape will
        actually ride it — the host small-op shortcut stays available
        for shapes that would only hit mesh_fallback (device launch
        latency dwarfs the GF math there, same as without a mesh).
        ``shape`` is the stacked [..., n_shards, chunk] form."""
        mesh = self._active_mesh()
        if mesh is None:
            return False
        from ceph_tpu.parallel import dispatch as mesh_dispatch

        c = shape[-2]
        flat_shape = (
            int(np.prod(shape[:-2], initial=1)),
            c,
            shape[-1],
        )
        return mesh_dispatch.mesh_supported(mesh, (0, c * 8), flat_shape)

    @staticmethod
    def _stack(vals: list):
        """Stack shard buffers along the shard axis, KEEPING host
        arrays host-side (np): the host GF shortcut reads them in
        place, and the device routes time their upload as
        ``codec.h2d``. One policy for every family."""
        if all(isinstance(v, np.ndarray) for v in vals):
            return np.stack(vals, axis=-2)
        return jnp.stack(vals, axis=-2)

    def _plan_route(
        self,
        shape,
        host_staged: bool,
        nbytes: int,
        *,
        host_tables: bool = False,
        shard_shape=None,
        mat01: np.ndarray | None = None,
        w: int = 1,
        csum_block: int | None = None,
    ) -> tuple[str | None, tuple[str, ...]]:
        """Which route serves one bit-matrix application: the ONE
        place the precedence is written. No side effects: it returns
        ``(route, missed)``, the route's name and the ``ec_dispatch``
        counters of the tiers that were enabled, outranked the route
        and could not take the shape; the site that asked counts them
        and executes the route.

        What the caller holds decides which tiers are on offer:
        ``shape`` is the stacked [..., C, N] form (packetized for a
        packet code: C = shards x w, N = chunk / w); ``host_staged``
        and ``nbytes`` say whether the inputs are numpy and how large;
        ``host_tables``: the caller has the byte matrix the host GF
        tables take; ``shard_shape``: the inputs are per-shard
        operands of this one shape, so the shards-form kernels (no
        stack relayout) can take them; ``mat01`` / ``w``: a 0/1
        packet matrix, which makes the schedule kernels eligible;
        ``csum_block``: the caller wants parity AND block checksums
        from one pass.

        The order: an installed mesh that supports the shape (it
        owns that shape whatever else could serve it) > [the fused
        encode+csum kernel, shards form before stacked, where
        ``csum_block`` asks for it: that question ends there, and
        None sends the caller to a plain encode, which asks again] >
        host tables for host-staged inputs within
        ``ec_host_dispatch_bytes`` > schedule kernel, shards form (a
        TPU kernel) before the stacked form (packet layouts only; XLA
        serves it off the chip) > Pallas shards form (whole-chunk
        device-resident operands) > Pallas stacked > XLA einsum."""
        from ceph_tpu.ops import pallas_encode as pe
        from ceph_tpu.utils import config

        if self._mesh_routable_shape(shape):
            return "mesh", ()
        c, n = shape[-2:]
        tpu = platform.on_tpu()
        pallas = bool(config.get("ec_use_pallas"))
        if csum_block is not None:
            if not (
                pallas
                and config.get("ec_fused_csum")
                and (tpu or config.get("ec_fused_csum_interpret"))
            ):
                return None, ()
            if (
                shard_shape is not None
                and not host_staged
                and pe.fused_csum_shards_supported(
                    c, shard_shape, csum_block
                )
            ):
                return "fused_shards", ()
            flat_shape = (int(np.prod(shape[:-2], initial=1)), c, n)
            if pe.fused_csum_supported(flat_shape, csum_block):
                return "fused", ()
            return None, ("fused_fallback",)
        if (
            host_tables
            and host_staged
            and 0 < nbytes <= config.get("ec_host_dispatch_bytes")
        ):
            return "host", ()
        missed = []
        if (
            mat01 is not None
            and config.get("ec_use_sched")
            and (tpu or w > 1)
        ):
            sched = xor_schedule.routable_schedule(
                mat01, config.get("ec_sched_opt")
            )
            if sched is None:
                missed.append("sched_rejected_density")
            elif (
                tpu
                and shard_shape is not None
                and xor_schedule.shards_supported(
                    c // w,
                    xor_schedule._n_rows(sched) // w,
                    w,
                    shard_shape,
                    xor_schedule._linearize(sched)[1]
                    if isinstance(sched, xor_schedule.Schedule)
                    else 0,
                )
            ):
                return "sched_shards", ()
            elif w > 1 and xor_schedule.supported((1, c, n)):
                return "sched", ()
            else:
                missed.append("sched_rejected_shape")
        if (
            pallas
            and tpu
            and w == 1
            and shard_shape is not None
            and not host_staged
            and pe.shards_supported(c, shard_shape)
        ):
            return "pallas_shards", tuple(missed)
        # the stacked MXU tiers: the only ones a mesh that is
        # installed but cannot split the shape counts itself out of
        if self._active_mesh() is not None:
            missed.append("mesh_fallback")
        if pallas and tpu:
            if pe.supported((1, c, n)):
                return "pallas", tuple(missed)
            missed.append("pallas_fallback")
        return "einsum", tuple(missed)

    def _route(self, shape, host_staged: bool, nbytes: int, **held):
        """Ask ``_plan_route`` and count what it reports missed: the
        ``*_fallback`` and ``sched_rejected_*`` counters move once a
        dispatch, at the site that asked."""
        route, missed = self._plan_route(
            shape, host_staged, nbytes, **held
        )
        for name in missed:
            _dispatch_counters().inc(name)
        return route

    def _route_stacked(self, stacked, **held):
        """``_route`` for one stacked [..., C, N] operand."""
        return self._route(
            stacked.shape,
            isinstance(stacked, np.ndarray),
            int(stacked.size) * stacked.dtype.itemsize,
            **held,
        )

    def _route_shards(self, shards: list, w: int = 1, **held):
        """``_route`` for a list of per-shard operands (``w`` packets
        a chunk): the stacked shape they would have, and their own
        shape for the shards-form kernels."""
        first = shards[0].shape
        uniform = all(s.shape == first for s in shards[1:])
        return self._route(
            first[:-1] + (len(shards) * w, first[-1] // w),
            all(isinstance(v, np.ndarray) for v in shards),
            sum(int(v.size) * v.dtype.itemsize for v in shards),
            shard_shape=first if uniform else None,
            w=w,
            **held,
        )

    def _dispatch_bitmatrix(
        self,
        bmat_np: np.ndarray,
        bmat_dev: jax.Array,
        stacked: jax.Array,
        op: str,
        nbytes: int | None = None,
        route: str | None = None,
    ) -> jax.Array:
        """Run one bit-matrix application on the stacked [..., C, N]
        form over the mesh, the Pallas kernel or the einsum engine,
        whichever ``route`` names (asked here when the caller has not
        asked already). Decode and delta ride the same fused kernel
        as encode — the kernel is generic over [R*8, C*8]
        bitmatrices, so reconstruct is a first-class on-chip path
        (the reference treats decode as equally hot:
        osd/ECUtil.cc:648-729, isa/ErasureCodeIsa.cc:504-516).
        ``nbytes``: what the route counts as input bytes where
        ``stacked`` carries zero columns or padding."""
        if route is None:
            route = self._route_stacked(stacked)
        if route == "mesh":
            from ceph_tpu.parallel import dispatch as mesh_dispatch

            flat = stacked.reshape((-1,) + stacked.shape[-2:])
            count_route(f"mesh_{op}", flat, nbytes=nbytes)
            with codec_stage("launch"):
                out = mesh_dispatch.mesh_apply_bitmatrix(
                    self._active_mesh(), bmat_dev, flat
                )
                return out.reshape(
                    stacked.shape[:-2] + out.shape[-2:]
                )
        if route == "pallas":
            from ceph_tpu.ops import pallas_encode as pe

            count_route(f"pallas_{op}", stacked, nbytes=nbytes)
            flat = _upload(
                stacked.reshape((-1,) + stacked.shape[-2:])
            )
            with codec_stage("launch"):
                out = pe.gf_encode_bitplane_pallas(bmat_np, flat)
                return out.reshape(
                    stacked.shape[:-2] + out.shape[-2:]
                )
        count_route(f"einsum_{op}", stacked, nbytes=nbytes)
        stacked = _upload(stacked)
        with codec_stage("launch"):
            return _apply_bitmatrix(bmat_dev, stacked)

    def _run_host_tables(
        self, mat: np.ndarray, shards: list, op: str
    ) -> list:
        """The ``host`` route: the byte matrix over the stacked numpy
        shards on the host GF tables, numpy in and out."""
        from ceph_tpu.gf import gf_apply_bytes_host

        count_route(f"host_{op}", *shards)
        out = gf_apply_bytes_host(mat, np.stack(shards, axis=-2))
        return [out[..., j, :] for j in range(out.shape[-2])]

    def _run_sched_shards(
        self, mat01: np.ndarray, shards: list, w: int, op: str
    ) -> list:
        """The ``sched_shards`` route: the multi-operand schedule
        kernel, shard arrays in, shard arrays out, no [.., n, chunk]
        stack and no packetize reshape (both are real relayout copies
        on TPU; see ops/xor_schedule.py). The schedule is the one the
        planner gated: the CSE-optimized multi-level program under
        ``ec_sched_opt`` (default), the pinned selection form
        otherwise; cached process-wide by the matrix's bytes."""
        from ceph_tpu.utils import config

        count_route(f"sched_{op}", *shards)
        sched = xor_schedule.routable_schedule(
            mat01, config.get("ec_sched_opt")
        )
        return xor_schedule.xor_schedule_apply_shards(sched, shards, w)

    def _dispatch_bitmatrix_shards(
        self,
        bmat_np: np.ndarray,
        bmat_dev: jax.Array,
        shards: list,
        op: str,
        route: str | None = None,
    ) -> list:
        """Per-shard-operand route: device inputs that fit the
        shards-form Pallas kernel skip the [.., C, N] stack entirely
        (the stack is a relayout copy that measured 3.5x the kernel's
        own cost on the LRC/SHEC bench geometry — the same finding
        that shaped the XOR-schedule engine's shards form,
        ops/xor_schedule.py). The zero-waste packing widened this
        route to any c <= pallas_encode.SHARDS_MAX_C: cauchy k=10
        encode and wide SHEC survivor sets now ride it, where the
        round-5 block-diagonal rule (s*c <= 16) forced them through
        the stacked path. The mesh route and the einsum fallback
        still take the stacked tensor. Returns one array per output
        row-group (R = bitmatrix rows / 8). ``route``: the planner's
        answer where the caller has asked already."""
        if route is None:
            route = self._route_shards(shards)
        if route == "pallas_shards":
            from ceph_tpu.ops import pallas_encode as pe

            count_route(f"pallas_{op}", *shards)
            with codec_stage("launch"):
                return pe.gf_encode_bitplane_pallas_shards(bmat_np, shards)
        with codec_stage("prep"):
            stacked = self._stack(list(shards))
        out = self._dispatch_bitmatrix(
            bmat_np, bmat_dev, stacked, op, route=route
        )
        return [out[..., j, :] for j in range(out.shape[-2])]


class MatrixErasureCodec(BitplaneDispatchMixin, ErasureCodeBase):
    """Codec defined by a systematic (k+m) x k GF(2^8) generator matrix."""

    def __init__(self) -> None:
        super().__init__()
        self.generator: np.ndarray | None = None  # [(k+m), k] uint8
        self._encode_bmat: jax.Array | None = None
        self._tables = DecodeTableCache()
        self._host_tables = DecodeTableCache()  # byte matrices

    # Subclasses set self.k/self.m then call this from init().
    def _set_generator(self, generator: np.ndarray) -> None:
        self.generator = np.asarray(generator, dtype=np.uint8)
        assert self.generator.shape == (self.k + self.m, self.k)
        self._encode_bmat_np = gf_matrix_to_bitmatrix(
            self.generator[self.k :, :]
        )
        self._encode_bmat = jnp.asarray(self._encode_bmat_np)

    def get_flags(self) -> Flag:
        return (
            Flag.OPTIMIZED_SUPPORTED
            | Flag.PARITY_DELTA_OPTIMIZATION
            | Flag.ZERO_INPUT_ZERO_OUTPUT
            | Flag.ZERO_PADDING_EXPECTED
            | Flag.PARTIAL_READ_OPTIMIZATION
            | Flag.PARTIAL_WRITE_OPTIMIZATION
        )

    # -- encode -------------------------------------------------------
    def encode_chunks(
        self, data: dict[int, jax.Array]
    ) -> dict[int, jax.Array]:
        parity = self._apply_byte_matrix(
            self.generator[self.k :, :], self._shard_list(data),
            "encode", None,
        )
        return {self.k + i: parity[i] for i in range(self.m)}

    def encode_stacked(self, stacked):
        """``encode_chunks`` for data that is stacked already,
        [..., k, N] on the host or the device: the same route and
        counters, parity stacked [..., m, N] (numpy off the host
        tables, a device array otherwise; one fetch for the caller).
        The stacked tiers take the array as it is, with no unstack and
        no second stack."""
        return self._encode_routed(stacked, self._route_plain(
            stacked.shape, isinstance(stacked, np.ndarray),
            int(stacked.size) * stacked.dtype.itemsize,
        ))

    def _route_plain(self, shape, host_staged: bool, nbytes: int):
        """The planner's answer for a plain encode of the stacked
        [..., k, N] form."""
        return self._route(
            shape, host_staged, nbytes, host_tables=True,
            shard_shape=tuple(shape[:-2]) + tuple(shape[-1:]),
            mat01=as_01_matrix(self.generator[self.k :, :]),
        )

    def _encode_routed(self, stacked, route, nbytes: int | None = None):
        """A plain encode of ``stacked`` on the route the planner
        named."""
        if route in ("mesh", "pallas", "einsum"):
            return self._dispatch_bitmatrix(
                self._encode_bmat_np, self._encode_bmat, stacked,
                "encode", nbytes=nbytes, route=route,
            )
        # the host tables and the shards-form kernels take shard operands
        return self._stack(self._apply_byte_matrix(
            self.generator[self.k :, :],
            [stacked[..., i, :] for i in range(self.k)],
            "encode", None, route,
        ))

    def encode_chunks_with_csums(
        self, data: dict[int, jax.Array], csum_block: int
    ):
        """Fused encode+checksum dispatch: (parity dict, csums) where
        ``csums`` is ``[..., k+m, nblocks]`` uint32 ZERO-INIT per-block
        crc32c (row i = shard i; seed conversion is a constant XOR,
        checksum.crc32c.crc32c_seed_shift). Returns ``(None, None)``
        when no fused kernel route can serve the shape — callers then
        encode normally and keep their host csum fallback. The fused
        route runs on TPU, or off-TPU in Pallas interpreter mode when
        ``ec_fused_csum_interpret`` is set (tests/CI)."""
        from ceph_tpu.ops import pallas_encode as pe

        shards, _xp = self._shard_list_xp(data)
        route = self._route_shards(shards, csum_block=csum_block)
        if route == "fused_shards":
            # device-resident per-shard inputs skip the stack relayout
            count_route("fused_encode", *shards)
            with codec_stage("launch"):
                parity, csums = pe.gf_encode_csum_bitplane_pallas_shards(
                    self._encode_bmat_np, shards, csum_block,
                    interpret=None if platform.on_tpu() else True,
                )
            return (
                {self.k + j: parity[j] for j in range(self.m)},
                csums,
            )
        if route != "fused":
            # the mesh owns the shape, or no fused kernel serves it
            return None, None
        with codec_stage("prep"):
            stacked = self._stack(list(shards))
        parity, csums = self._run_fused(stacked, csum_block)
        return (
            {self.k + j: parity[..., j, :] for j in range(self.m)},
            csums,
        )

    def encode_stacked_with_csums(self, stacked, csum_block: int):
        """``encode_chunks_with_csums`` for data that is stacked
        already, [..., k, N]: ``(parity [..., m, N], csums)``, both
        device arrays, or ``(None, None)``. The kernel's own layout in
        and out: nothing is stacked, and the caller fetches the parity
        once."""
        if self._route_stacked(stacked, csum_block=csum_block) != "fused":
            return None, None
        return self._run_fused(stacked, csum_block)

    def encode_batch(
        self, members: list, csum_block: int = 0
    ) -> tuple[np.ndarray, "np.ndarray | None", int]:
        """A coalesced tick's encodes as ONE dispatch (the staging
        ring's entry, ``pipeline/dispatcher.py``): ``members`` are
        host arrays [n_i, k, N], stripe-major (an object's own
        layout), at most ``BATCH_MAX_STRIPES`` stripes together.
        Returns ``(parity [sum n_i, m, N], csums [sum n_i, k+m, N //
        csum_block] | None, stripes sent)``, numpy: ``csums`` where
        ``csum_block`` asks and the fused kernel serves it (None sends
        the caller to its host checksums, the parity is there either
        way).

        One decision for the whole batch, from its real bytes, by the
        one planner. The host tables (a plain encode within
        ``ec_host_dispatch_bytes``) take the members run together,
        stripes sent = the real ones. A device route: the members are
        copied ONCE into a stack of the next of ``batch_sizes()``,
        only the pad rows zeroed (a lone member of such a size is the
        stack); the route counts the real bytes, not the padding. On
        the chip the first fused batch of a geometry compiles every
        size of the set (``_batch_warm``), so traffic never meets a
        new shape in the middle of a run."""
        total = sum(a.shape[0] for a in members)
        k, n = members[0].shape[-2:]
        shape, nbytes = (total, k, n), total * k * n
        route = None
        if csum_block:
            route = self._route(shape, True, nbytes, csum_block=csum_block)
        if route == "fused":
            self._batch_warm(n, csum_block)
        else:
            # no checksums from this pass (or a mesh owns the shape):
            # a plain encode, asked as one
            route = self._route_plain(shape, True, nbytes)
            if route == "host":
                out = self._encode_routed(
                    members[0] if len(members) == 1
                    else np.concatenate(members),
                    route,
                )
                return out, None, total
        padded = padded_size(total)
        parity, csums = self._batch_device(
            members, padded, route, csum_block, nbytes
        )
        return parity[:total], (
            None if csums is None else csums[:total]
        ), padded

    def _batch_device(
        self, members: list, padded: int, route: str, csum_block: int,
        nbytes: int,
    ):
        """One device dispatch of ``members`` stacked to ``padded``
        stripes on ``route``, fetched: numpy ``(parity, csums |
        None)`` of the padded stack."""
        with codec_stage("prep"):
            if len(members) == 1 and members[0].shape[0] == padded:
                stack = members[0]
            else:
                stack = np.empty(
                    (padded,) + members[0].shape[1:], np.uint8
                )
                at = 0
                for a in members:
                    stack[at : at + a.shape[0]] = a
                    at += a.shape[0]
                stack[at:] = 0
        csums = None
        if route == "fused":
            parity, csums = self._run_fused(stack, csum_block, nbytes)
        else:
            parity = self._encode_routed(stack, route, nbytes)
        with codec_stage("fetch"):
            return np.asarray(parity), (
                None if csums is None else np.asarray(csums)
            )

    def _batch_warm(self, n: int, csum_block: int) -> None:
        """On the chip, compile the fused batch program of every size
        for this geometry, side by side, while the geometry's first
        batch waits for all of them (so no op of the pool completes,
        and no warm-up can end, before they exist): once a process for
        a given matrix (codec objects are rebuilt on every map change;
        the compiled programs are not). Off the chip nothing is
        measured, a window is a test's few seconds and the
        interpreter's compilations are dear: a size compiles when it
        is first met."""
        if not platform.on_tpu():
            return
        key = (self._encode_bmat_np.tobytes(), n, csum_block)
        if key in _batch_warmed:
            return
        _batch_warmed.add(key)
        from concurrent.futures import ThreadPoolExecutor

        def compile_one(padded: int) -> None:
            self._batch_device(
                [np.zeros((padded, self.k, n), np.uint8)], padded,
                "fused", csum_block, 0,
            )

        with ThreadPoolExecutor(len(batch_sizes()), "ec-warm") as pool:
            list(pool.map(compile_one, batch_sizes()))

    def _run_fused(
        self, stacked, csum_block: int, nbytes: int | None = None
    ):
        """The ``fused`` route on the stacked [..., k, N] form;
        ``nbytes``: what it counts as input bytes where ``stacked``
        carries padding."""
        from ceph_tpu.ops import pallas_encode as pe

        count_route("fused_encode", stacked, nbytes=nbytes)
        lead, (c, n) = stacked.shape[:-2], stacked.shape[-2:]
        flat = _upload(stacked.reshape((-1, c, n)))
        with codec_stage("launch"):
            parity, csums = pe.gf_encode_csum_bitplane_pallas(
                self._encode_bmat_np, flat, csum_block,
                interpret=None if platform.on_tpu() else True,
            )
            return (
                parity.reshape(lead + (self.m, n)),
                csums.reshape(lead + (c + self.m, n // csum_block)),
            )

    def _apply_byte_matrix(
        self, mat: np.ndarray, shards: list, op: str, key: tuple | None,
        route: str | None = None,
    ) -> list:
        """Apply the GF(2^8) byte matrix ``mat`` to per-shard operands
        on the route ``_plan_route`` names: host GF tables for small
        numpy inputs (numpy out), the schedule engine where every
        entry is 0/1 (the xor plugin / LRC xor-local layers, LRC local
        repair rows), otherwise the MXU routes on ``mat``'s bit-matrix:
        the encode matrix's resident copy for ``key`` None, else the
        LRU's under ``key`` (host copy cached, device copy through
        ``dev_bmat`` so a trace never caches its own tracer). ``route``:
        the planner's answer where the caller has asked already."""
        mat01 = as_01_matrix(mat)
        if route is None:
            route = self._route_shards(
                shards, host_tables=True, mat01=mat01
            )
        if route == "host":
            return self._run_host_tables(mat, shards, op)
        if route == "sched_shards":
            return self._run_sched_shards(mat01, shards, 1, op)
        if key is None:
            bmat_np, bmat_dev = self._encode_bmat_np, self._encode_bmat
        else:
            bmat_np = self._tables.get(
                key, lambda: gf_matrix_to_bitmatrix(mat)
            )
            traced = any(isinstance(v, jax.core.Tracer) for v in shards)
            bmat_dev = dev_bmat(self._tables, key, bmat_np, traced)
        return self._dispatch_bitmatrix_shards(
            bmat_np, bmat_dev, shards, op, route
        )

    # -- decode -------------------------------------------------------
    def decode_chunks(
        self,
        want_to_read: set[int],
        chunks: dict[int, jax.Array],
    ) -> dict[int, jax.Array]:
        present = sorted(chunks)
        # Only reconstruct what is actually missing: wanted-but-present
        # shards pass through, keeping decode tables (and the LRU keys)
        # erasure-pattern-minimal.
        want = sorted(w for w in want_to_read if w not in chunks)
        if not want:
            return {w: chunks[w] for w in want_to_read}
        key = (tuple(present), tuple(want))
        # one host byte matrix for the host tables, the schedule
        # probe (0/1 decode rows, the common LRC local repair) and the
        # bit-matrix of the MXU routes
        mat = self._host_tables.get(
            key, lambda: self._build_decode_bytes(present, want)
        )
        outs = self._apply_byte_matrix(
            mat, [chunks[i] for i in present], "decode", key
        )
        result = {w: chunks[w] for w in want_to_read if w in chunks}
        for idx, w in enumerate(want):
            result[w] = outs[idx]
        return result

    def _build_decode_bytes(
        self, present: list[int], want: list[int]
    ) -> np.ndarray:
        """Byte-matrix rows producing each wanted shard from the
        present shards. Data shards come from the inverted-submatrix
        rows; wanted parity shards are re-encoded as G_parity_row @
        (decode rows) — the decode-of-data + re-encode-of-parity split
        of shard_extent_map_t::decode (osd/ECUtil.cc:648-729)."""
        from ceph_tpu.gf import gf_matmul_np

        d = decode_matrix(self.generator, self.k, present)  # [k, len(present)]
        rows = []
        for w in want:
            if w < self.k:
                rows.append(d[w, :])
            else:
                rows.append(gf_matmul_np(self.generator[w : w + 1, :], d)[0])
        return np.stack(rows)

    def _build_decode_bmat(
        self, present: list[int], want: list[int]
    ) -> np.ndarray:
        """HOST bitmatrix only — the device copy goes through
        dev_bmat so a trace never caches its own tracer."""
        return gf_matrix_to_bitmatrix(
            self._build_decode_bytes(present, want)
        )

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
        """parity'_j = parity_j XOR sum_i G[j, i] * delta_i.

        The matrix_apply_delta analog (ErasureCodeJerasure.h:110-119):
        one small matmul over just the changed columns.
        """
        cols = sorted(delta)
        contribs = self._apply_byte_matrix(
            self.generator[self.k :, cols],
            [delta[c] for c in cols],
            "delta",
            ("delta", tuple(cols)),
        )
        out = {}
        for pid, p in parity.items():
            c = contribs[pid - self.k]
            # the host tables answer in numpy, and the parity stays there
            out[pid] = (
                np.bitwise_xor(np.asarray(p), c)
                if isinstance(c, np.ndarray)
                else xor_bytes(p, c)
            )
        return out

    def delta_batchable(self) -> bool:
        """Whether ``delta_contribs`` serves this codec now: an
        installed mesh owns its dispatch shapes and keeps the per-op
        ``apply_delta``. (Asked before any batch has a shape, so this
        is ``_plan_route``'s first question without its shape.)"""
        return self._active_mesh() is None

    def delta_contribs(
        self, cols: np.ndarray, units: np.ndarray
    ) -> tuple[np.ndarray, int]:
        """The batched parity delta: ``units`` [U, L] are delta pages
        (old XOR new) of any number of ops, ``cols`` [U] the raw data
        column of each. Returns ``(contribs [U, m, L], units sent)``
        with ``contribs[u, j] = G[k+j, cols[u]] * units[u]``; the caller
        XORs them onto its old parity windows.

        One decision for the whole batch, in the byte threshold's
        place: up to ``DELTA_HOST_UNITS`` units the host GF tables
        serve it (units sent = U). Otherwise ONE device dispatch: by
        linearity a delta on column c is the encode of a stripe that
        is zero everywhere but c, so the batch stacks as [P, k, L]
        with the other columns zero, P the next of
        ``delta_batch_sizes()`` (units sent = P), through the encode
        bit-matrix and the kernel every encode and decode rides. The
        route counts the real delta pages as its bytes, not the zero
        columns or the padding. The first device batch of a process
        compiles every size of the set, so traffic never meets a new
        shape in the middle of a run. (Not the first delta: a batch
        for the host tables would wait seconds for programs it does
        not use; on the served overwrite traffic one batch in eleven
        is the device's, so the compile falls in the first seconds.)"""
        n, ln = units.shape
        if n <= DELTA_HOST_UNITS:
            from ceph_tpu.gf import gf_apply_bytes_host

            count_route("host_delta", units)
            parity_rows = self.generator[self.k :, :]
            return np.stack([
                gf_apply_bytes_host(
                    parity_rows[:, c : c + 1], units[u : u + 1]
                )
                for u, c in enumerate(cols)
            ]), n
        self._delta_warm(ln)
        padded = next(p for p in delta_batch_sizes() if p >= n)
        return self._delta_device(cols, units, padded, units.nbytes), padded

    def _delta_device(
        self, cols: np.ndarray, units: np.ndarray, padded: int,
        nbytes: int,
    ) -> np.ndarray:
        n, ln = units.shape
        with codec_stage("prep"):
            stacked = np.zeros((padded, self.k, ln), np.uint8)
            stacked[np.arange(n), cols] = units
        out = self._dispatch_bitmatrix(
            self._encode_bmat_np, self._encode_bmat, stacked, "delta",
            nbytes=nbytes,
        )
        with codec_stage("fetch"):
            return np.asarray(out)[:n]

    def _delta_warm(self, unit_len: int) -> None:
        """Compile the delta program of every batch size, once a
        process for a given matrix and route (codec objects are rebuilt
        on every map change; the compiled programs are not)."""
        from ceph_tpu.utils import config

        key = (
            self._encode_bmat_np.tobytes(), unit_len,
            bool(config.get("ec_use_pallas")) and platform.on_tpu(),
        )
        if key in _delta_warmed:
            return
        _delta_warmed.add(key)
        none = np.zeros((0, unit_len), np.uint8)
        for padded in delta_batch_sizes():
            self._delta_device(none[:, 0], none, padded, 0)
