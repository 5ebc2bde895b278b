"""Schedule-native XOR engine for sparse packet bit-matrix codes.

The reference executes liberation / blaum_roth / liber8tion (and the
cauchy techniques) as XOR *schedules*: ``jerasure_smart_bitmatrix_to_
schedule`` walks the 0/1 coding matrix and emits one XOR per set bit,
so encode cost tracks matrix density, not dimension
(jerasure/ErasureCodeJerasure.h:255-324, ``jerasure_schedule_encode``).
Routing those codes through the generic bit-plane MXU engine pays the
full [m*w*8, k*w*8] matrix stream with none of that sparsity — the r4
bench measured 35-83 GB/s vs 296 for the flagship byte code.

This module is the TPU form of the schedule — and, since round 11, a
schedule *optimizer* in the sense of "Accelerating XOR-based Erasure
Coding using Program Optimization Techniques" (arxiv 2108.02692):

- ``schedule_rows`` still emits the single-level selection form (row q
  = XOR of the packets its matrix row selects), the
  ``jerasure_smart_bitmatrix_to_schedule`` analog and the pinned
  bit-equal escape hatch (``ec_sched_opt=false``).
- ``optimize_schedule`` applies the paper's core move on top: greedy
  pairwise common-subexpression elimination over the 0/1 matrix
  (Paar's algorithm) factors XOR pairs shared across parity rows into
  intermediate packets, recursively (intermediates pair with
  intermediates, so schedules are multi-level), then ``_linearize``
  reorders the resulting DAG for VMEM/operand locality: outputs chain
  by operand affinity, intermediates materialize just before first
  use into scratch slots that are recycled at last use (register-
  allocation over VMEM), bounding live intermediates to the DAG's
  peak width instead of its size.

Both Pallas kernels (the packetized form and the multi-operand shards
form) execute the linearized program with intermediates staged in a
VMEM scratch ref; XOR is exact on uint8, so any operand order is
bit-equal to the un-optimized schedule and to the host GF engine.

Execution model: parity packet q is the XOR of the data packets (and
intermediates) its program selects, executed as one Pallas VPU kernel
blocked over (stripe, lane-tile). No MXU, no bit-plane unpack — the
blocks stream (cols + rows) packets per stripe against HBM, which a
round-5 A/B run read at 553-621 GB/s data-in (before the benchmark:
no cell runs the schedule route yet, so not re-measured), while the
VPU work per block tracks the schedule's op count.

Gate math (round 11): the un-optimized route keeps the original
traffic-ratio gate — (ones + rows) <= MAX_TRAFFIC_RATIO * cols, the
r4/r5 model where every set bit is one operand read. The optimized
route gates on the *post-CSE op count* instead: (XORs + output
writes) <= MAX_OP_RATIO * cols. Minimal-density encode matrices pass
both (ratio 2.0-2.2 post-CSE); inverted decode matrices (~50% ones,
raw ratio 7-8, rejected by the old gate) compress under CSE to ratio
~2.5 and now ride the schedule route, as do LRC xor-local-parity
repair rows — the r11 superopt targets.
"""

from __future__ import annotations

import functools
import heapq
from collections import Counter
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ceph_tpu.utils import platform

#: lane-tile granularity; multiples of 2048 keep uint8 blocks on the
#: native (32, 128) tiling, and 8192 measured at/above every larger
#: tile on v5e (grid-step overhead is already amortized there)
LANE_TILE = 2048
BEST_TILE = 8192
#: the divisor-search fallback in ``_pick_tile`` stays lane-aligned
#: (multiples of 128) and above a floor so awkward packet sizes never
#: degrade to sliver tiles whose grid-step overhead dominates
TILE_ALIGN = 128
MIN_TILE = 512

#: density gate for the UN-optimized (selection-form) schedule — the
#: ``ec_sched_opt=false`` escape hatch: HBM traffic is (ones + rows)
#: packets per ``cols`` packets of data in, so its rate is
#: ~roofline/ratio. The minimal-density families encode at ratio
#: 2.1-3.0; the single-chunk parity delta — the common small-write
#: RMW shape — runs 4 + 1/w (the fixed m*w output rows charge
#: against one chunk's w columns), so the gate sits above that;
#: inverted decode matrices (~50% ones) run 7-10 raw and only pass
#: through the optimized gate below.
MAX_TRAFFIC_RATIO = 5.0

#: op-count gate for OPTIMIZED schedules: (post-CSE XORs + output
#: writes) per data column. Same constant as the traffic gate — the
#: MXU-stream comparator is unchanged — but measured after CSE, which
#: is what converts the ~50%-ones inverted decode matrices (raw 7-8)
#: into ratio ~2.5 programs that beat the matrix stream.
MAX_OP_RATIO = 5.0


class Schedule(NamedTuple):
    """A multi-level XOR program over packet node ids.

    Nodes 0..n_in-1 are the input packets; node n_in + t is
    intermediate ``temps[t]``, defined as the XOR of two earlier
    nodes (inputs or intermediates — CSE pairs recursively). Output
    row q is the XOR of ``outputs[q]``'s nodes; an empty tuple means
    a zero packet. Hashable, so it keys the jitted-kernel caches the
    same way the plain selection rows do.
    """

    n_in: int
    temps: tuple[tuple[int, int], ...]
    outputs: tuple[tuple[int, ...], ...]


def schedule_rows(mat01: np.ndarray) -> tuple[tuple[int, ...], ...]:
    """Single-level XOR schedule: row q -> indices of the packets to
    XOR — the ``jerasure_smart_bitmatrix_to_schedule`` analog, pure
    selection with no factoring. This form is kept verbatim as the
    ``ec_sched_opt=false`` escape hatch (pinned bit-equal, and pinned
    *structurally*: the kernels run it through the original
    single-level code path); ``optimize_schedule`` builds the CSE'd
    multi-level program the optimizer route dispatches.
    """
    m = np.asarray(mat01)
    return tuple(
        tuple(int(j) for j in np.flatnonzero(m[q])) for q in range(m.shape[0])
    )


def optimize_schedule(mat01: np.ndarray) -> Schedule:
    """Greedy pairwise CSE over a 0/1 matrix (Paar's algorithm).

    Repeatedly factor the operand pair co-occurring in the most rows
    into a fresh intermediate (each factoring saves >= 1 XOR: one
    intermediate XOR buys >= 2 pair eliminations), substituting the
    intermediate everywhere — including into pairs with other
    intermediates, so the result is multi-level. Deterministic:
    ties break to the lexicographically smallest pair, so golden
    op-count pins (tests/test_sched_superopt.py) hold across runs.

    Pair counts update incrementally with a lazy max-heap — O(E log E)
    in the number of count updates — so dense inverted decode
    matrices optimize in milliseconds, not the seconds a recount-
    per-iteration scan costs.
    """
    m = np.asarray(mat01, dtype=np.uint8)
    n_out, n_in = m.shape
    rows = [set(int(j) for j in np.flatnonzero(m[q])) for q in range(n_out)]
    cnt: Counter = Counter()
    for r in rows:
        s = sorted(r)
        for i in range(len(s)):
            for j in range(i + 1, len(s)):
                cnt[(s[i], s[j])] += 1
    heap = [(-c, p) for p, c in cnt.items()]
    heapq.heapify(heap)
    temps: list[tuple[int, int]] = []
    next_id = n_in

    def bump(pair: tuple[int, int], d: int) -> None:
        c = cnt[pair] + d
        if c <= 0:
            cnt.pop(pair, None)
        else:
            cnt[pair] = c
            heapq.heappush(heap, (-c, pair))

    while heap:
        negc, pair = heapq.heappop(heap)
        if cnt.get(pair, 0) != -negc:
            continue  # stale heap entry (lazy deletion)
        if -negc < 2:
            break
        a, b = pair
        tid = next_id
        next_id += 1
        temps.append((a, b))
        hits = 0
        for r in rows:
            if a in r and b in r:
                hits += 1
                r.discard(a)
                r.discard(b)
                for x in r:
                    bump((x, a) if x < a else (a, x), -1)
                    bump((x, b) if x < b else (b, x), -1)
                    bump((x, tid), +1)  # tid > every existing node
                r.add(tid)
        bump(pair, -hits)
    return Schedule(
        n_in,
        tuple(temps),
        tuple(tuple(sorted(r)) for r in rows),
    )


def schedule_xors(sel) -> int:
    """XOR ops a schedule executes (either form): intermediate XORs
    plus per-row chain XORs. The quantity the optimized gate and the
    bench/CI op-count pins measure."""
    if isinstance(sel, Schedule):
        return len(sel.temps) + sum(
            max(len(o) - 1, 0) for o in sel.outputs
        )
    return sum(max(len(s) - 1, 0) for s in sel)


def cse_stats(mat01: np.ndarray) -> dict:
    """Optimizer scorecard for one matrix: raw ones / selection-form
    XORs / post-CSE XORs / intermediate count / scratch-slot peak.
    Consumed by the golden op-count regression pins."""
    m = np.asarray(mat01, dtype=np.uint8)
    rows = schedule_rows(m)
    sched = optimize_schedule(m)
    raw = schedule_xors(rows)
    opt = schedule_xors(sched)
    return {
        "ones": int(m.sum()),
        "raw_xors": raw,
        "opt_xors": opt,
        "temps": len(sched.temps),
        "saving_frac": round(1.0 - opt / max(raw, 1), 3),
        "scratch_slots": _linearize(sched)[1],
    }


def profitable(
    sel_rows: tuple[tuple[int, ...], ...], cols: int
) -> bool:
    """Selection-form gate (the escape-hatch route): True when the
    matrix is sparse enough that raw XOR traffic beats the MXU stream
    (minimal-density families: ~k+1 ones/row). See MAX_TRAFFIC_RATIO
    for the model; optimized schedules gate via ``profitable_opt``."""
    if not sel_rows or cols <= 0:
        return False
    ones = sum(len(s) for s in sel_rows)
    return (ones + len(sel_rows)) <= MAX_TRAFFIC_RATIO * cols


def profitable_opt(sched: Schedule, cols: int) -> bool:
    """Optimizer-route gate: post-CSE op count (XORs + output writes)
    per data column against the same MXU-stream comparator. This is
    what lets CSE-compressible dense shapes — inverted decode
    matrices, LRC local-repair rows — ride the schedule route the
    raw-density gate locked out."""
    if not sched.outputs or cols <= 0:
        return False
    return (schedule_xors(sched) + len(sched.outputs)) <= (
        MAX_OP_RATIO * cols
    )


@functools.lru_cache(maxsize=1024)
def _routable_cached(mat_bytes: bytes, shape: tuple, opt: bool):
    m = np.frombuffer(mat_bytes, dtype=np.uint8).reshape(shape)
    if opt:
        sched = optimize_schedule(m)
        return sched if profitable_opt(sched, shape[1]) else None
    rows = schedule_rows(m)
    return rows if profitable(rows, shape[1]) else None


def routable_schedule(mat01: np.ndarray, opt: bool = True):
    """The schedule the route should execute for a 0/1 matrix, or
    None when even the post-CSE program stays over the gate (the
    matrix is served better by the MXU stream). ``opt=False`` is the
    ``ec_sched_opt`` escape hatch: the raw selection form under the
    original traffic-ratio gate. Cached process-wide — schedules
    depend only on the matrix, so every codec shares one table."""
    m = np.ascontiguousarray(np.asarray(mat01, dtype=np.uint8))
    return _routable_cached(m.tobytes(), m.shape, bool(opt))


def supported(shape: tuple[int, ...]) -> bool:
    """[B, KW, P] with the packet axis lane-tileable."""
    return len(shape) == 3 and shape[-1] % LANE_TILE == 0


def _pick_tile(p: int) -> int:
    """Largest grid-remainder-free lane tile for a packet axis of
    ``p`` lanes: BEST_TILE when it divides exactly, else the largest
    divisor of p that is lane-aligned (multiple of TILE_ALIGN) at or
    under BEST_TILE with a MIN_TILE floor. The old search only walked
    LANE_TILE multiples, so awkward packet sizes (p with no large
    2048-multiple divisor, e.g. 10240 or 14336) degraded to a 2048
    sliver and paid 4-7x the grid steps; the divisor search keeps
    them at 5120/7168."""
    if p % BEST_TILE == 0:
        return BEST_TILE
    best = 0
    t = TILE_ALIGN
    while t <= BEST_TILE and t <= p:
        if t >= MIN_TILE and p % t == 0:
            best = t
        t += TILE_ALIGN
    if best:
        return best
    # no aligned divisor at/above the floor — legacy LANE_TILE-step
    # fallback (unreachable while supported() demands p % 2048 == 0,
    # kept for forward safety if the alignment contract relaxes)
    t = BEST_TILE - LANE_TILE
    while t > LANE_TILE and p % t:
        t -= LANE_TILE
    return t


# ------------------------------------------------------ linearization
@functools.lru_cache(maxsize=512)
def _linearize(sched: Schedule):
    """Compile a Schedule into ``(ops, n_slots)`` — the VMEM-local
    execution order both kernels run.

    - Output rows chain greedily by operand affinity (next row shares
      the most operands with the previous one), so consecutive rows
      re-read hot operands.
    - Intermediates materialize lazily, immediately before their
      first use (dependencies first — creation order is already
      topological), and their scratch slot is recycled at last use:
      ``n_slots`` is the DAG's peak liveness, not its size, which is
      what the shards form charges against the VMEM budget.
    - Within a row, intermediate operands lead (most recent first —
      the hottest VMEM lines) and input packets follow in index
      order. XOR on uint8 is exact, so every ordering is bit-equal.

    ``ops`` entries: ``("t", slot, (src, src))`` materializes an
    intermediate, ``("o", q, (src, ...))`` emits output row q; each
    ``src`` is ``(0, input_index)`` or ``(1, slot)``.
    """
    n_in, temps, outputs = sched.n_in, sched.temps, sched.outputs
    remaining = list(range(len(outputs)))
    order: list[int] = []
    prev: set[int] = set()
    while remaining:
        q = max(
            remaining,
            key=lambda r: (len(prev & set(outputs[r])), -r),
        )
        order.append(q)
        remaining.remove(q)
        prev = set(outputs[q])

    seq: list[tuple[str, int]] = []
    emitted: set[int] = set()

    def emit(t: int) -> None:
        if t in emitted:
            return
        emitted.add(t)
        for d in temps[t]:
            if d >= n_in:
                emit(d - n_in)
        seq.append(("t", t))

    for q in order:
        for x in outputs[q]:
            if x >= n_in:
                emit(x - n_in)
        seq.append(("o", q))

    last_use: dict[int, int] = {}
    for i, (kind, x) in enumerate(seq):
        for r in temps[x] if kind == "t" else outputs[x]:
            if r >= n_in:
                last_use[r - n_in] = i

    slot_of: dict[int, int] = {}
    free: list[int] = []
    n_slots = 0
    ops: list[tuple] = []

    def src(v: int) -> tuple[int, int]:
        return (0, v) if v < n_in else (1, slot_of[v - n_in])

    for i, (kind, x) in enumerate(seq):
        if kind == "t":
            a, b = temps[x]
            srcs = (src(a), src(b))
            # destination allocated BEFORE operand slots release, so
            # a temp never aliases its own operands' storage
            s = free.pop() if free else n_slots
            n_slots = max(n_slots, s + 1)
            slot_of[x] = s
            ops.append(("t", s, srcs))
        else:
            ids = outputs[x]
            ts = sorted((v for v in ids if v >= n_in), reverse=True)
            ins_ = sorted(v for v in ids if v < n_in)
            ops.append(("o", x, tuple(src(v) for v in ts + ins_)))
        for r in temps[x] if kind == "t" else outputs[x]:
            if r >= n_in and last_use.get(r - n_in) == i:
                free.append(slot_of[r - n_in])
    return tuple(ops), n_slots


# ------------------------------------------------------------- kernels
@functools.lru_cache(maxsize=256)
def _sched_fn(
    sel_rows,
    kw: int,
    lane_tile: int,
    interpret: bool,
):
    """Jitted (cached per static schedule) pallas apply. Functions only
    in this cache — never device arrays (the round-3/4 tracer-leak
    lesson applies to arrays, not callables). Plain selection rows run
    the original single-level kernel unchanged; ``Schedule`` programs
    run their linearized op list with intermediates staged in a VMEM
    scratch ref (one lane-tile row per live slot)."""
    scratch_shapes: list = []
    if isinstance(sel_rows, Schedule):
        ops, n_slots = _linearize(sel_rows)
        mw = len(sel_rows.outputs)
        if n_slots:
            scratch_shapes = [
                pltpu.VMEM((n_slots, lane_tile), jnp.uint8)
            ]

        def kernel(d_ref, o_ref, *scratch):
            d = d_ref[:]  # [1, KW, T] uint8
            scr = scratch[0] if scratch else None

            def val(s):
                kind, i = s
                if kind == 0:
                    return d[:, i, :]
                return scr[i : i + 1, :]

            for entry in ops:
                if entry[0] == "t":
                    _, slot, (a, b) = entry
                    scr[slot : slot + 1, :] = val(a) ^ val(b)
                else:
                    _, q, srcs = entry
                    if srcs:
                        acc = val(srcs[0])
                        for s in srcs[1:]:
                            acc = acc ^ val(s)
                    else:
                        acc = jnp.zeros_like(d[:, 0, :])
                    o_ref[:, q, :] = acc

    else:
        mw = len(sel_rows)

        def kernel(d_ref, o_ref):
            d = d_ref[:]  # [1, KW, T] uint8
            for q, sel in enumerate(sel_rows):
                if sel:
                    acc = d[:, sel[0], :]
                    for j in sel[1:]:
                        acc = acc ^ d[:, j, :]
                else:
                    acc = jnp.zeros_like(d[:, 0, :])
                o_ref[:, q, :] = acc

    @jax.jit
    def apply(packets):
        b, _, p = packets.shape
        return pl.pallas_call(
            kernel,
            grid=(b, p // lane_tile),
            in_specs=[
                pl.BlockSpec((1, kw, lane_tile), lambda i, c: (i, 0, c))
            ],
            out_specs=pl.BlockSpec(
                (1, mw, lane_tile), lambda i, c: (i, 0, c)
            ),
            out_shape=jax.ShapeDtypeStruct((b, mw, p), jnp.uint8),
            scratch_shapes=scratch_shapes,
            interpret=interpret,
        )(packets)

    return apply


def _xla_apply(sel_rows, packets: jax.Array) -> jax.Array:
    """Off-TPU form: unrolled jnp XOR chains (XLA fuses the row
    gathers and chains into one elementwise pass). Multi-level
    schedules compute their intermediates as ordinary fused values."""
    if isinstance(sel_rows, Schedule):
        n_in = sel_rows.n_in
        vals: dict[int, jax.Array] = {}

        def node(i):
            return packets[..., i, :] if i < n_in else vals[i]

        for t, (a, b) in enumerate(sel_rows.temps):
            vals[n_in + t] = node(a) ^ node(b)
        rows = sel_rows.outputs
        fetch = node
    else:
        rows = sel_rows
        fetch = lambda j: packets[..., j, :]  # noqa: E731
    outs = []
    zero = None
    for sel in rows:
        if sel:
            acc = fetch(sel[0])
            for j in sel[1:]:
                acc = acc ^ fetch(j)
        else:
            if zero is None:
                zero = jnp.zeros_like(packets[..., 0, :])
            acc = zero
        outs.append(acc)
    return jnp.stack(outs, axis=-2)


def _n_rows(sel) -> int:
    """Output-row count of either schedule form."""
    return len(sel.outputs) if isinstance(sel, Schedule) else len(sel)


# ---------------------------------------------------------- shards form
#: scoped VMEM is 16 MiB on v5e. The pipeline double-buffers every
#: operand and result block and the optimizer's scratch sits beside
#: them; the compiler's own figure for a rejected shape is exactly
#: 2 * block bytes + scratch bytes (libtpu 0.0.34: liberation 2-lost
#: decode at 187264-byte chunks, "Scoped allocation with size 20.00M
#: and limit 16.00M"). Gate that sum at 15 MiB.
VMEM_BUDGET = 15 << 20
SUBLANE = 8


def shards_supported(
    n_in: int,
    n_out: int,
    w: int,
    shape: tuple[int, ...],
    n_slots: int = 0,
) -> bool:
    """Can the shards-form kernel serve [B, chunk] shard arrays?

    Requirements: 2D after lead-flatten, packet size lane-aligned,
    batch a sublane multiple (or small enough to be one block), and
    the double-buffered blocks 2 * (n_in + n_out) * sb * chunk — plus
    the optimizer's scratch, ``n_slots`` live intermediate packets of
    sb * (chunk/w) bytes — within the VMEM budget.
    """
    if len(shape) < 1:
        return False
    chunk = shape[-1]
    b = int(np.prod(shape[:-1], initial=1))
    if chunk % w or (chunk // w) % 128:
        return False
    sb = SUBLANE if b % SUBLANE == 0 else b
    rows = -(-sb // SUBLANE) * SUBLANE  # blocks pad to whole sublane tiles
    blocks = 2 * (n_in + n_out) * rows * chunk
    scratch = n_slots * rows * (chunk // w)
    return blocks + scratch <= VMEM_BUDGET


@functools.lru_cache(maxsize=256)
def _sched_shards_fn(
    sel_rows,
    n_in: int,
    w: int,
    chunk: int,
    sb: int,
    interpret: bool,
):
    """Multi-operand whole-chunk kernel: k separate [B, chunk] shard
    operands, m separate [B, chunk] parity results, packets addressed
    as in-kernel lane slices. The single-operand form pays a real
    relayout copy for the [B, k, chunk] stack and the packetize
    reshape (TPU tiles the minor-most two dims, so those reshapes
    move every byte); this form never materializes either (a
    round-5 A/B run read 407 vs ~100 GB/s data-in; before the
    benchmark, not re-measured). ``Schedule`` programs execute
    their linearized op list with intermediates in a VMEM scratch ref
    (sb rows per live slot, recycled at last use)."""
    p = chunk // w
    scratch_shapes: list = []
    if isinstance(sel_rows, Schedule):
        ops, n_slots = _linearize(sel_rows)
        n_out = len(sel_rows.outputs) // w
        if n_slots:
            scratch_shapes = [
                pltpu.VMEM((n_slots * sb, p), jnp.uint8)
            ]

        def kernel(*refs):
            ins = refs[:n_in]
            outs = refs[n_in : n_in + n_out]
            scr = refs[n_in + n_out] if n_slots else None

            def val(s):
                kind, i = s
                if kind == 0:
                    ci, pi = divmod(i, w)
                    return ins[ci][:, pi * p : (pi + 1) * p]
                return scr[i * sb : (i + 1) * sb, :]

            for entry in ops:
                if entry[0] == "t":
                    _, slot, (a, b) = entry
                    scr[slot * sb : (slot + 1) * sb, :] = (
                        val(a) ^ val(b)
                    )
                else:
                    _, q, srcs = entry
                    if srcs:
                        acc = val(srcs[0])
                        for s in srcs[1:]:
                            acc = acc ^ val(s)
                    else:
                        acc = jnp.zeros(
                            (refs[0].shape[0], p), jnp.uint8
                        )
                    qc, qp = divmod(q, w)
                    outs[qc][:, qp * p : (qp + 1) * p] = acc

    else:
        n_out = len(sel_rows) // w

        def kernel(*refs):
            ins, outs = refs[:n_in], refs[n_in:]

            def packet(j):
                ci, pi = divmod(j, w)
                return ins[ci][:, pi * p : (pi + 1) * p]

            for q, sel in enumerate(sel_rows):
                if sel:
                    acc = packet(sel[0])
                    for j in sel[1:]:
                        acc = acc ^ packet(j)
                else:
                    acc = jnp.zeros((refs[0].shape[0], p), jnp.uint8)
                qc, qp = divmod(q, w)
                outs[qc][:, qp * p : (qp + 1) * p] = acc

    @jax.jit
    def apply(*shards):
        b = shards[0].shape[0]
        return pl.pallas_call(
            kernel,
            grid=(b // sb,),
            in_specs=[
                pl.BlockSpec((sb, chunk), lambda i: (i, 0))
                for _ in range(n_in)
            ],
            out_specs=[
                pl.BlockSpec((sb, chunk), lambda i: (i, 0))
                for _ in range(n_out)
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, chunk), jnp.uint8)
                for _ in range(n_out)
            ],
            scratch_shapes=scratch_shapes,
            interpret=interpret,
        )(*shards)

    return apply


def xor_schedule_apply_shards(
    sel_rows,
    shards: list,
    w: int,
    interpret: bool | None = None,
) -> list:
    """Shards-form schedule apply: ``shards`` are n_in arrays of
    [..., chunk] (common shape); returns n_out = rows/w arrays of the
    same shape, one per output shard. Row q of the schedule indexes
    input packet (q//w, q%w) across the shard list; ``sel_rows`` is
    either the selection form or an optimized ``Schedule``. ``w=1``
    serves whole-chunk 0/1 byte matrices (LRC xor-local-parity
    repair), where packet == chunk.

    On TPU this is the no-copy hot path; off-TPU it falls back to the
    fused-XLA packetized form (CPU tests can force interpret=True for
    bit-exact kernel coverage).
    """
    n_in = len(shards)
    lead = shards[0].shape[:-1]
    chunk = shards[0].shape[-1]
    n_out = _n_rows(sel_rows) // w
    if interpret is None:
        if not platform.on_tpu():
            stacked = jnp.stack(
                [jnp.asarray(s) for s in shards], axis=-2
            )
            pk = stacked.reshape(lead + (n_in * w, chunk // w))
            out = _xla_apply(sel_rows, pk)
            ch = out.reshape(lead + (n_out, chunk))
            return [ch[..., j, :] for j in range(n_out)]
        interpret = platform.pallas_interpret()
    b = int(np.prod(lead, initial=1))
    sb = SUBLANE if b % SUBLANE == 0 else b
    fn = _sched_shards_fn(sel_rows, n_in, w, chunk, sb, interpret)
    flat = [jnp.asarray(s).reshape(b, chunk) for s in shards]
    outs = fn(*flat)
    return [o.reshape(lead + (chunk,)) for o in outs]


def xor_schedule_apply(
    sel_rows,
    packets: jax.Array,
    interpret: bool | None = None,
) -> jax.Array:
    """Apply a static XOR schedule (either form) to [..., KW, P]
    packets.

    Pallas kernel on TPU (or interpret=True for bit-exact CPU tests);
    plain fused XLA off-TPU. numpy input is accepted and returns a
    device array (callers on the host path use their own GF engine).
    """
    if interpret is None:
        if not platform.on_tpu():
            return _xla_apply(sel_rows, jnp.asarray(packets))
        interpret = platform.pallas_interpret()
    lead = packets.shape[:-2]
    kw, p = packets.shape[-2:]
    if p % LANE_TILE:
        # a non-tileable packet axis would silently drop lanes (the
        # grid floors to zero/partial blocks); callers gate with
        # supported(), so reaching here is a contract violation
        raise ValueError(
            f"packet axis {p} not a multiple of {LANE_TILE}; "
            "check supported() before calling"
        )
    flat = jnp.asarray(packets).reshape((-1, kw, p))
    out = _sched_fn(sel_rows, kw, _pick_tile(p), interpret)(flat)
    return out.reshape(lead + out.shape[-2:])
