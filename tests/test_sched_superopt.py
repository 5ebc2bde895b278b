"""Round-11 XOR-schedule superoptimizer (arxiv 2108.02692).

Covers: greedy pairwise CSE correctness (optimized multi-level
schedule vs the pinned selection form vs a matrix oracle, bit-exact,
all packet families + inverted decode matrices + LRC local groups),
interpret-mode kernel coverage for multi-level schedules with VMEM
scratch intermediates (both the packetized and shards forms), the
post-CSE profitability gate, golden op-count regression pins for the
bench geometries, the sched_rejected_* observability counters, and
the _pick_tile divisor-search fix.
"""

import functools

import numpy as np
import pytest

from ceph_tpu.ops import xor_schedule as xs
from ceph_tpu.utils import platform


@pytest.fixture
def rng():
    return np.random.default_rng(1107)


def matrix_oracle(mat01, packets):
    """Ground truth: one XOR per set bit, straight off the matrix."""
    m = np.asarray(mat01)
    out = np.zeros(
        packets.shape[:-2] + (m.shape[0], packets.shape[-1]), np.uint8
    )
    for q in range(m.shape[0]):
        for j in np.flatnonzero(m[q]):
            out[..., q, :] ^= packets[..., j, :]
    return out


@pytest.fixture
def sched_interpret(monkeypatch):
    """Force the schedule route on (TPU predicate true, kernels in
    interpret mode) so CPU tests exercise the real Pallas programs."""
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    orig = xs.xor_schedule_apply_shards
    monkeypatch.setattr(
        xs,
        "xor_schedule_apply_shards",
        functools.partial(orig, interpret=True),
    )


# ------------------------------------------------------ optimizer core
def test_optimize_schedule_factors_shared_pairs():
    # rows 0 and 1 share {0,1}; CSE must factor it exactly once
    mat = np.array(
        [[1, 1, 1, 0], [1, 1, 0, 1], [0, 0, 1, 1]], np.uint8
    )
    sched = xs.optimize_schedule(mat)
    assert sched.n_in == 4
    assert (0, 1) in sched.temps
    # raw: 2+2+1 = 5 XORs; factored: 1 temp + 1+1+1 = 4
    assert xs.schedule_xors(sched) == 4
    assert xs.schedule_xors(xs.schedule_rows(mat)) == 5


def test_optimize_never_worse_and_deterministic(rng):
    for _ in range(25):
        m = (
            rng.random((rng.integers(1, 10), rng.integers(2, 16)))
            < rng.uniform(0.1, 0.9)
        ).astype(np.uint8)
        a = xs.optimize_schedule(m)
        b = xs.optimize_schedule(m)
        assert a == b, "optimizer must be deterministic (golden pins)"
        assert xs.schedule_xors(a) <= xs.schedule_xors(
            xs.schedule_rows(m)
        )


def test_profitable_opt_gate():
    # dense random-ish matrix the raw gate rejects but whose
    # perfectly-shared rows CSE to almost nothing
    shared = np.ones((8, 16), np.uint8)
    rows = xs.schedule_rows(shared)
    assert not xs.profitable(rows, 16)  # (128 + 8)/16 = 8.5
    sched = xs.optimize_schedule(shared)
    # 8 identical rows collapse to one chain of temps
    assert xs.profitable_opt(sched, 16)
    # empty program never profits
    assert not xs.profitable_opt(xs.Schedule(4, (), ()), 4)


def test_routable_schedule_forms():
    mat = np.array([[1, 1, 0], [0, 1, 1]], np.uint8)
    opt = xs.routable_schedule(mat, True)
    raw = xs.routable_schedule(mat, False)
    assert isinstance(opt, xs.Schedule)
    assert raw == xs.schedule_rows(mat), (
        "escape hatch must be the pinned selection form"
    )


def test_linearize_recycles_scratch_slots():
    from ceph_tpu.codecs.registry import registry

    codec = registry.factory(
        "jerasure",
        {"technique": "liberation", "k": "4", "m": "2", "w": "7"},
    )
    dec = codec._build_decode_bitmatrix([2, 3, 4, 5], [0, 1])
    sched = xs.optimize_schedule(dec)
    ops, n_slots = xs._linearize(sched)
    assert len(sched.temps) > 0
    assert 0 < n_slots < len(sched.temps), (
        "slot allocation must recycle at last use (peak liveness, "
        "not DAG size)"
    )
    # every slot read must follow its latest write (program order)
    written: dict[int, int] = {}
    for i, entry in enumerate(ops):
        srcs = entry[2]
        for kind, idx in srcs:
            if kind == 1:
                assert idx in written and written[idx] < i
        if entry[0] == "t":
            written[entry[1]] = i


# --------------------------------------------- kernel-level equivalence
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_fuzz_multilevel_kernels_match_oracle(seed):
    rng = np.random.default_rng(seed)
    n_out = int(rng.integers(1, 10))
    n_in = int(rng.integers(2, 14))
    m = (rng.random((n_out, n_in)) < rng.uniform(0.2, 0.8)).astype(
        np.uint8
    )
    sched = xs.optimize_schedule(m)
    pk = rng.integers(0, 256, (2, n_in, 2048), np.uint8)
    want = matrix_oracle(m, pk)
    got_xla = np.asarray(xs.xor_schedule_apply(sched, pk))
    got_kernel = np.asarray(
        xs.xor_schedule_apply(sched, pk, interpret=True)
    )
    got_raw = np.asarray(
        xs.xor_schedule_apply(
            xs.schedule_rows(m), pk, interpret=True
        )
    )
    np.testing.assert_array_equal(got_xla, want)
    np.testing.assert_array_equal(got_kernel, want)
    np.testing.assert_array_equal(got_raw, want)


@pytest.mark.parametrize("w,k,mo", [(3, 4, 2), (1, 5, 2), (7, 4, 2)])
def test_shards_kernel_multilevel(rng, w, k, mo):
    """Multi-operand kernel executes multi-level schedules (scratch
    intermediates) bit-exactly — including w=1, the whole-chunk byte
    0/1 route LRC local repair rides."""
    chunk = w * 1024 if (w * 1024) % 128 == 0 else w * 128
    m = (rng.random((mo * w, k * w)) < 0.5).astype(np.uint8)
    m[:2, :2] = 1  # guarantee at least one shared pair -> a temp
    sched = xs.optimize_schedule(m)
    assert sched.temps, "want a schedule with intermediates here"
    shards = [
        rng.integers(0, 256, (8, chunk), np.uint8) for _ in range(k)
    ]
    pk = np.stack(shards, axis=-2).reshape(8, k * w, chunk // w)
    want = matrix_oracle(m, pk).reshape(8, mo, chunk)
    outs = xs.xor_schedule_apply_shards(
        sched, shards, w, interpret=True
    )
    assert len(outs) == mo
    for j, o in enumerate(outs):
        np.testing.assert_array_equal(np.asarray(o), want[:, j])
    # XLA fallback agrees
    outs2 = xs.xor_schedule_apply_shards(sched, shards, w)
    for j, o in enumerate(outs2):
        np.testing.assert_array_equal(np.asarray(o), want[:, j])


def test_empty_and_single_rows_multilevel(rng):
    m = np.array(
        [[0, 0, 0, 0], [1, 0, 0, 0], [1, 1, 0, 1], [1, 1, 1, 1]],
        np.uint8,
    )
    sched = xs.optimize_schedule(m)
    pk = rng.integers(0, 256, (1, 4, 2048), np.uint8)
    want = matrix_oracle(m, pk)
    got = np.asarray(xs.xor_schedule_apply(sched, pk, interpret=True))
    np.testing.assert_array_equal(got, want)


# ----------------------------------------------------- family bit-equal
FAMILY_PROFILES = [
    {"technique": "liberation", "k": "4", "m": "2", "w": "7"},
    {"technique": "blaum_roth", "k": "4", "m": "2", "w": "6"},
    {"technique": "liber8tion", "k": "4", "m": "2", "w": "8"},
]


@pytest.mark.parametrize(
    "profile", FAMILY_PROFILES, ids=lambda p: p["technique"]
)
def test_family_opt_vs_unopt_vs_oracle(
    rng, sched_interpret, profile
):
    """Optimized route == pinned selection route == matrix oracle,
    through the real codec dispatch (encode, inverted 2-lost decode,
    parity delta)."""
    import jax.numpy as jnp

    from ceph_tpu.codecs.registry import registry
    from ceph_tpu.utils import config

    codec = registry.factory("jerasure", dict(profile))
    w, k = codec.w, codec.k
    n = w * 2048
    data = {
        i: jnp.asarray(rng.integers(0, 256, (8, n), np.uint8))
        for i in range(k)
    }
    parity = codec.encode_chunks(dict(data))
    with config.override(ec_sched_opt=False):
        ref = codec.encode_chunks(dict(data))
    # oracle straight off the coding bitmatrix
    pk = np.stack(
        [np.asarray(data[i]) for i in range(k)], axis=-2
    ).reshape(8, k * w, n // w)
    want = matrix_oracle(codec.coding_bitmatrix, pk).reshape(
        8, codec.m, n
    )
    for i in range(codec.m):
        np.testing.assert_array_equal(
            np.asarray(parity[k + i]), want[:, i]
        )
        np.testing.assert_array_equal(
            np.asarray(ref[k + i]), want[:, i]
        )

    # inverted 2-lost decode through both routes
    chunks = {**data, **parity}
    del chunks[0], chunks[1]
    out = codec.decode_chunks({0, 1}, chunks)
    with config.override(ec_sched_opt=False):
        out_unopt = codec.decode_chunks({0, 1}, chunks)
    for i in (0, 1):
        np.testing.assert_array_equal(
            np.asarray(out[i]), np.asarray(data[i])
        )
        np.testing.assert_array_equal(
            np.asarray(out_unopt[i]), np.asarray(data[i])
        )

    # parity delta (single changed chunk) through both routes
    delta = {
        1: jnp.asarray(rng.integers(0, 256, (8, n), np.uint8))
    }
    pd = {k: parity[k], k + 1: parity[k + 1]}
    got_d = codec.apply_delta(dict(delta), dict(pd))
    with config.override(ec_sched_opt=False):
        ref_d = codec.apply_delta(dict(delta), dict(pd))
    for pid in got_d:
        np.testing.assert_array_equal(
            np.asarray(got_d[pid]), np.asarray(ref_d[pid])
        )


def test_inverted_decode_dispatches_schedule_route(
    rng, sched_interpret
):
    """The round-11 gate change, counter-verified: a 2-lost inverted
    decode matrix (raw density ratio ~8, rejected by the old gate)
    rides the schedule route once CSE compresses it; with the
    optimizer off it falls back and the rejection is counted."""
    import jax.numpy as jnp

    from ceph_tpu.codecs.matrix_codec import _dispatch_counters
    from ceph_tpu.codecs.registry import registry
    from ceph_tpu.utils import config

    codec = registry.factory(
        "jerasure",
        {"technique": "liberation", "k": "4", "m": "2", "w": "7"},
    )
    n = 7 * 2048
    data = {
        i: jnp.asarray(rng.integers(0, 256, (8, n), np.uint8))
        for i in range(4)
    }
    parity = codec.encode_chunks(dict(data))
    chunks = {**data, **parity}
    del chunks[0], chunks[1]
    pc = _dispatch_counters()

    before = pc.get("sched_decode")
    out = codec.decode_chunks({0, 1}, chunks)
    assert pc.get("sched_decode") > before
    np.testing.assert_array_equal(
        np.asarray(out[0]), np.asarray(data[0])
    )

    with config.override(ec_sched_opt=False):
        before = pc.get("sched_rejected_density")
        out2 = codec.decode_chunks({0, 1}, chunks)
        assert pc.get("sched_rejected_density") > before
    np.testing.assert_array_equal(
        np.asarray(out2[1]), np.asarray(data[1])
    )


def test_shape_rejection_counter(rng, sched_interpret):
    """A sched-eligible matrix over an untileable packet axis counts
    sched_rejected_shape at the terminal (packetized) probe."""
    import jax.numpy as jnp

    from ceph_tpu.codecs.matrix_codec import _dispatch_counters
    from ceph_tpu.codecs.registry import registry

    codec = registry.factory(
        "jerasure",
        {"technique": "liberation", "k": "4", "m": "2", "w": "7"},
    )
    pc = _dispatch_counters()
    # packet axis 7*136/7 = 136: lane-aligned for the shards form
    # probe (136 % 128 != 0 rejects there too) and not LANE_TILE-
    # tileable for the packetized form
    n = 7 * 1000  # 1000 % 128 != 0 and 1000 % 2048 != 0
    stacked = jnp.asarray(
        rng.integers(0, 256, (8, 4, n), np.uint8)
    )
    before = pc.get("sched_rejected_shape")
    codec._apply_packet_matrix(
        codec.coding_bitmatrix,
        [stacked[:, i, :] for i in range(4)],
        "encode",
    )
    assert pc.get("sched_rejected_shape") > before


# ------------------------------------------------------ LRC local repair
def test_lrc_xor_local_repair_via_schedule(rng, sched_interpret):
    """LRC local repair through the schedule engine, counter-verified:
    the xor-local-parity kml layout repairs a lost chunk from its
    3-survivor local group as one w=1 XOR program."""
    import jax.numpy as jnp

    from ceph_tpu.codecs.matrix_codec import _dispatch_counters
    from ceph_tpu.codecs.registry import registry
    from ceph_tpu.utils import config

    codec = registry.factory(
        "lrc", {"k": "4", "m": "2", "l": "3", "local_parity": "xor"}
    )
    n = 4096
    data = {
        i: jnp.asarray(rng.integers(0, 256, (8, n), np.uint8))
        for i in range(codec.k)
    }
    parity = codec.encode_chunks(dict(data))
    # the local parity IS the XOR of its group (Azure-LRC layout)
    pos = {
        codec.chunk_mapping[i]: np.asarray(v)
        for i, v in {**data, **parity}.items()
    }
    np.testing.assert_array_equal(
        pos[3], pos[0] ^ pos[1] ^ pos[2]
    )

    # local repair uses only the 3-chunk local group...
    plan = codec.minimum_to_decode(
        {0}, set(range(codec.k + codec.m)) - {0}
    )
    assert len(plan) == 3
    # ...and dispatches through the schedule route
    chunks = {s: ({**data, **parity})[s] for s in plan}
    pc = _dispatch_counters()
    before = pc.get("sched_decode")
    out = codec.decode_chunks({0}, chunks)
    assert pc.get("sched_decode") > before
    np.testing.assert_array_equal(
        np.asarray(out[0]), np.asarray(data[0])
    )
    # bit-equal with the schedule engine disabled
    with config.override(ec_use_sched=False):
        ref = codec.decode_chunks({0}, chunks)
    np.testing.assert_array_equal(
        np.asarray(out[0]), np.asarray(ref[0])
    )


def test_lrc_default_layout_unchanged(rng):
    """local_parity defaults to rs: the generated layers and the
    encoded bits must match the pre-round-11 (corpus-pinned) layout;
    the xor layout only changes the LOCAL parities."""
    import jax.numpy as jnp

    from ceph_tpu.codecs.registry import registry

    rs = registry.factory("lrc", {"k": "4", "m": "2", "l": "3"})
    xor = registry.factory(
        "lrc", {"k": "4", "m": "2", "l": "3", "local_parity": "xor"}
    )
    assert [l.profile.get("plugin") for l in rs.layers] == [
        None, None, None
    ]
    assert [l.profile.get("plugin") for l in xor.layers] == [
        None, "xor", "xor"
    ]
    data = {
        i: jnp.asarray(rng.integers(0, 256, (2, 4096), np.uint8))
        for i in range(4)
    }
    p_rs = rs.encode_chunks(dict(data))
    p_xor = xor.encode_chunks(dict(data))
    for lg in (4, 5):  # global parities identical across layouts
        np.testing.assert_array_equal(
            np.asarray(p_rs[lg]), np.asarray(p_xor[lg])
        )
    with pytest.raises(ValueError):
        registry.factory(
            "lrc", {"k": "4", "m": "2", "l": "3", "local_parity": "no"}
        )


def test_xor_plugin_standalone(rng, sched_interpret):
    """The xor plugin: encode/decode/delta correctness, with encode
    and delta riding the schedule engine's w=1 route."""
    import jax.numpy as jnp

    from ceph_tpu.codecs.matrix_codec import _dispatch_counters
    from ceph_tpu.codecs.registry import registry
    from ceph_tpu.ops.bitplane import xor_bytes

    codec = registry.factory("xor", {"k": "3"})
    data = {
        i: jnp.asarray(rng.integers(0, 256, (8, 1024), np.uint8))
        for i in range(3)
    }
    pc = _dispatch_counters()
    before = pc.get("sched_encode")
    parity = codec.encode_chunks(dict(data))
    assert pc.get("sched_encode") > before
    want = (
        np.asarray(data[0]) ^ np.asarray(data[1]) ^ np.asarray(data[2])
    )
    np.testing.assert_array_equal(np.asarray(parity[3]), want)

    chunks = {**data, **parity}
    del chunks[1]
    out = codec.decode_chunks({1}, chunks)
    np.testing.assert_array_equal(
        np.asarray(out[1]), np.asarray(data[1])
    )

    delta = {0: jnp.asarray(rng.integers(0, 256, (8, 1024), np.uint8))}
    before = pc.get("sched_delta")
    newp = codec.apply_delta(dict(delta), {3: parity[3]})
    assert pc.get("sched_delta") > before
    np.testing.assert_array_equal(
        np.asarray(newp[3]),
        np.asarray(xor_bytes(parity[3], delta[0])),
    )
    with pytest.raises(ValueError):
        registry.factory("xor", {"k": "3", "m": "2"})


# ------------------------------------------------- golden op-count pins
#: CI regression pins for the bench-geometry encode matrices: if an
#: optimizer change pushes post-CSE op counts UP, tier-1 fails fast
#: instead of the regression only surfacing in a chip run. The
#: optimizer is deterministic (lexicographic tie-breaks), so these are
#: exact. ones counts are construction-frozen by the corpus.
GOLDEN_OPS = {
    "liberation": {"ones": 59, "raw_xors": 45, "opt_xors": 42},
    "blaum_roth": {"ones": 63, "raw_xors": 51, "opt_xors": 41},
    "liber8tion": {"ones": 68, "raw_xors": 52, "opt_xors": 48},
}


@pytest.mark.parametrize(
    "profile", FAMILY_PROFILES, ids=lambda p: p["technique"]
)
def test_golden_post_cse_op_counts(profile):
    from ceph_tpu.codecs.registry import registry

    codec = registry.factory("jerasure", dict(profile))
    st = xs.cse_stats(codec.coding_bitmatrix)
    want = GOLDEN_OPS[profile["technique"]]
    assert st["ones"] == want["ones"]
    assert st["raw_xors"] == want["raw_xors"]
    assert st["opt_xors"] == want["opt_xors"], (
        "post-CSE op count regressed vs the golden pin — the "
        "optimizer got worse on a bench matrix"
    )
    # the acceptance shape: post-CSE ops measurably below raw ones
    assert st["opt_xors"] < st["ones"]


def test_inverted_decode_matrices_pass_post_cse_gate():
    """The matrices the round-11 gate change admits: every family's
    2-lost inverted decode compresses under MAX_OP_RATIO while its
    raw form stays over MAX_TRAFFIC_RATIO."""
    from ceph_tpu.codecs.registry import registry

    for profile in FAMILY_PROFILES:
        codec = registry.factory("jerasure", dict(profile))
        dec = codec._build_decode_bitmatrix([2, 3, 4, 5], [0, 1])
        rows = xs.schedule_rows(dec)
        assert not xs.profitable(rows, dec.shape[1])
        sched = xs.optimize_schedule(dec)
        assert xs.profitable_opt(sched, dec.shape[1]), (
            profile["technique"]
        )


# ------------------------------------------------------- tile-pick fix
def test_pick_tile_divisor_search():
    """Awkward packet sizes no longer degrade to a 2048 sliver: the
    grid-remainder-free largest-divisor search (lane-aligned, floored)
    picks the biggest tile that divides p. Pinned for the corpus
    chunk-size packet axes (w * 2048-lane chunks and the odd cases)."""
    cases = {
        8192: 8192,      # exact BEST_TILE
        32768: 8192,
        2048: 2048,
        4096: 4096,
        6144: 6144,      # w=3 layouts
        10240: 5120,     # 2048*5: was 2048, divisor search finds 5120
        14336: 7168,     # 2048*7 (liberation w=7 chunks): was 2048
        22528: 5632,     # 2048*11: largest 128-aligned divisor <= 8192
        12288: 6144,     # 2048*6
        57344: 8192,     # liberation bench geometry (7*16384)/w... 8192 | 57344
    }
    for p, want in cases.items():
        got = xs._pick_tile(p)
        assert got == want, (p, got, want)
        assert p % got == 0, "tile must divide the packet axis"
        assert got % xs.TILE_ALIGN == 0
        assert got >= xs.MIN_TILE
