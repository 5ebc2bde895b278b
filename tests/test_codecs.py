"""Codec round trips and contract tests.

Models the reference's per-plugin unit tests
(src/test/erasure-code/TestErasureCodeIsa.cc compare_chunks,
TestErasureCodeJerasure.cc) plus exhaustive-erasure decode — the
pattern of ceph_erasure_code_benchmark.cc:210-257 with
--erasures-generation=exhaustive.
"""

from itertools import combinations

import jax.numpy as jnp
import numpy as np
import pytest

from ceph_tpu.codecs import Flag, create_codec, registry
from ceph_tpu.codecs.registry import PluginLoadError

MATRIX_CONFIGS = [
    ("jerasure", {"technique": "reed_sol_van", "k": "4", "m": "2"}),
    ("jerasure", {"technique": "reed_sol_van", "k": "8", "m": "4"}),
    ("jerasure", {"technique": "reed_sol_r6_op", "k": "6", "m": "2"}),
    ("jerasure", {"technique": "cauchy_orig", "k": "5", "m": "3"}),
    ("jerasure", {"technique": "cauchy_good", "k": "8", "m": "4"}),
    ("isa", {"technique": "reed_sol_van", "k": "8", "m": "3"}),
    ("isa", {"technique": "cauchy", "k": "8", "m": "4"}),
]

BITMATRIX_CONFIGS = [
    ("jerasure", {"technique": "liberation", "k": "4", "m": "2", "w": "7"}),
    ("jerasure", {"technique": "blaum_roth", "k": "4", "m": "2", "w": "6"}),
    ("jerasure", {"technique": "liber8tion", "k": "5", "m": "2"}),
]

ALL_CONFIGS = MATRIX_CONFIGS + BITMATRIX_CONFIGS


def make(plugin, profile):
    return registry.factory(plugin, profile)


def encode_all(codec, rng, nbytes=None):
    k = codec.get_data_chunk_count()
    cs = nbytes or codec.get_chunk_size(k * 4096)
    data = {
        i: jnp.asarray(rng.integers(0, 256, cs).astype(np.uint8))
        for i in range(k)
    }
    parity = codec.encode_chunks(data)
    return {**data, **parity}


@pytest.mark.parametrize("plugin,profile", ALL_CONFIGS)
def test_roundtrip_exhaustive_erasures(plugin, profile, rng):
    codec = make(plugin, profile)
    k, m = codec.get_data_chunk_count(), codec.get_coding_chunk_count()
    chunks = encode_all(codec, rng)
    originals = {i: np.asarray(c) for i, c in chunks.items()}
    # Exhaustive over all 1- and 2-erasure combinations (the corpus
    # tool's guarantee, ceph_erasure_code_non_regression.cc), plus all
    # m-erasure patterns when affordable.
    patterns = list(combinations(range(k + m), 1)) + list(
        combinations(range(k + m), 2)
    )
    if m > 2:
        patterns += list(combinations(range(k + m), m))[:50]
    for erased in patterns:
        have = {i: c for i, c in chunks.items() if i not in erased}
        out = codec.decode_chunks(set(erased), have)
        for e in erased:
            assert (np.asarray(out[e]) == originals[e]).all(), (
                plugin,
                profile,
                erased,
            )


@pytest.mark.parametrize("plugin,profile", MATRIX_CONFIGS[:3])
def test_batched_encode_matches_single(plugin, profile, rng):
    codec = make(plugin, profile)
    k = codec.get_data_chunk_count()
    cs = 512
    batch = 5
    data_np = rng.integers(0, 256, (batch, k, cs)).astype(np.uint8)
    batched = codec.encode_chunks(
        {i: jnp.asarray(data_np[:, i, :]) for i in range(k)}
    )
    for b in range(batch):
        single = codec.encode_chunks(
            {i: jnp.asarray(data_np[b, i, :]) for i in range(k)}
        )
        for pid, p in single.items():
            assert (np.asarray(batched[pid])[b] == np.asarray(p)).all()


def test_encode_missing_shards_are_zero(rng):
    """Absent shards encode as zeros (shared zero-buffer convention)."""
    codec = create_codec("isa", k=4, m=2)
    cs = 256
    full = {
        i: jnp.asarray(rng.integers(0, 256, cs).astype(np.uint8))
        for i in range(4)
    }
    explicit_zero = {**full, 2: jnp.zeros(cs, jnp.uint8)}
    absent = {i: c for i, c in full.items() if i != 2}
    p_zero = codec.encode_chunks(explicit_zero)
    p_absent = codec.encode_chunks(absent)
    for pid in p_zero:
        assert (np.asarray(p_zero[pid]) == np.asarray(p_absent[pid])).all()


@pytest.mark.parametrize("plugin,profile", MATRIX_CONFIGS[:4])
def test_parity_delta_rmw(plugin, profile, rng):
    """encode_delta/apply_delta == full re-encode
    (ErasureCodeInterface.h:471-537 contract)."""
    codec = make(plugin, profile)
    k = codec.get_data_chunk_count()
    cs = 256
    old = {
        i: jnp.asarray(rng.integers(0, 256, cs).astype(np.uint8))
        for i in range(k)
    }
    new = dict(old)
    new[1] = jnp.asarray(rng.integers(0, 256, cs).astype(np.uint8))
    p_old = codec.encode_chunks(old)
    p_full = codec.encode_chunks(new)
    delta = codec.encode_delta(old[1], new[1])
    p_delta = codec.apply_delta({1: delta}, p_old)
    for pid in p_full:
        assert (np.asarray(p_delta[pid]) == np.asarray(p_full[pid])).all()


def test_bytes_level_encode_decode(rng):
    codec = create_codec("jerasure", technique="reed_sol_van", k=3, m=2)
    payload = bytes(rng.integers(0, 256, 1000).astype(np.uint8))
    chunks = codec.encode(payload)
    assert len(chunks) == 5
    # Drop two, decode, reassemble.
    have = {i: c for i, c in chunks.items() if i not in (0, 3)}
    out = codec.decode({0, 3}, have)
    reassembled = b"".join(
        (out | have)[i] for i in range(3)
    )[: len(payload)]
    assert reassembled == payload


def test_minimum_to_decode(rng):
    codec = create_codec("isa", k=4, m=2)
    # All wanted present: plan is exactly the wanted shards.
    plan = codec.minimum_to_decode({0, 1}, {0, 1, 2, 3, 4, 5})
    assert set(plan) == {0, 1}
    # Shard 0 missing: need k shards.
    plan = codec.minimum_to_decode({0}, {1, 2, 3, 4})
    assert len(plan) == 4
    with pytest.raises(ValueError):
        codec.minimum_to_decode({0}, {1, 2, 3})


def test_minimum_to_decode_with_cost():
    codec = create_codec("isa", k=2, m=2)
    cost = {0: 1, 1: 100, 2: 1, 3: 1}
    chosen = codec.minimum_to_decode_with_cost({0}, cost)
    assert 1 not in chosen


def test_registry_contract():
    assert set(registry.names()) >= {"jerasure", "isa"}
    with pytest.raises(PluginLoadError):
        registry.load("no_such_plugin")
    with pytest.raises(PluginLoadError):
        registry.register("bad_version", object, "wrong-abi-1.0")
    with pytest.raises(ValueError):
        create_codec("jerasure", technique="not_a_technique")
    with pytest.raises(ValueError):
        create_codec("isa", k=33, m=3)  # beyond MAX_K
    with pytest.raises(ValueError):
        create_codec("isa", k=22, m=4)  # outside vandermonde envelope
    with pytest.raises(ValueError):
        create_codec("jerasure", technique="liberation", k=4, m=2, w=6)


def test_flags():
    van = create_codec("jerasure", technique="reed_sol_van", k=4, m=2)
    assert Flag.OPTIMIZED_SUPPORTED in van.get_flags()
    assert Flag.PARITY_DELTA_OPTIMIZATION in van.get_flags()
    lib = create_codec("jerasure", technique="liberation", k=4, m=2, w=7)
    assert Flag.ZERO_INPUT_ZERO_OUTPUT in lib.get_flags()


def test_chunk_size_alignment():
    codec = create_codec("isa", k=8, m=4)
    assert codec.get_chunk_size(8 * 4096) == 4096
    assert codec.get_chunk_size(100) == 128  # padded to lane width
    lib = create_codec("jerasure", technique="liberation", k=4, m=2, w=7)
    cs = lib.get_chunk_size(4 * 1000)
    assert cs % (7 * 128) == 0


def test_clay_repair_traced_matches_numpy(rng):
    """The trace-generic repair body: jax-array helpers under jit
    produce the numpy path's bytes exactly (one device program — the
    round-3 per-op-launch fix)."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.codecs.registry import registry

    codec = registry.factory("clay", {"k": "4", "m": "2", "d": "5"})
    k, n = 4, 6
    chunk = codec.get_chunk_size(k * 2048)
    sub = codec.get_sub_chunk_count()
    sc = chunk // sub
    data = {
        i: rng.integers(0, 256, (2, chunk), np.uint8) for i in range(k)
    }
    chunks = {
        **data,
        **{i: np.asarray(v) for i, v in codec.encode_chunks(data).items()},
    }
    for lost in (1, k + 1):
        plan = codec.minimum_to_decode({lost}, set(range(n)) - {lost})
        helper = {}
        for node, ranges in plan.items():
            parts = [
                chunks[node][..., idx * sc : (idx + cnt) * sc]
                for idx, cnt in ranges
            ]
            helper[node] = np.concatenate(parts, axis=-1)
        ref = np.asarray(codec.repair({lost}, helper)[lost])
        np.testing.assert_array_equal(ref, chunks[lost])
        keys = sorted(helper)
        fn = jax.jit(
            lambda *arrs: codec.repair(
                {lost}, dict(zip(keys, arrs))
            )[lost]
        )
        got = np.asarray(fn(*[jnp.asarray(helper[kk]) for kk in keys]))
        np.testing.assert_array_equal(got, ref)


def test_jerasure_packetsize_accepted_for_interop():
    """The reference plugin writes packetsize=2048 into every profile
    it normalizes (ErasureCodeJerasure.h DEFAULT_PACKETSIZE), so
    reference-originated profiles must initialize here (round-4
    advisor finding). The value is advisory — geometry stays
    chunk-derived — but negatives are still rejected."""
    from ceph_tpu.codecs import registry

    base = {"technique": "liberation", "k": "4", "m": "2", "w": "7"}
    registry.factory("jerasure", dict(base))                      # ok
    registry.factory("jerasure", dict(base, packetsize="0"))      # auto
    c = registry.factory("jerasure", dict(base, packetsize="2048"))
    assert c.packetsize == 2048
    # the accepted key does not change the bits
    rng = np.random.default_rng(5)
    data = {i: rng.integers(0, 256, (7 * 4096,), np.uint8) for i in range(4)}
    plain = registry.factory("jerasure", dict(base))
    a = plain.encode_chunks(dict(data))
    b = c.encode_chunks(dict(data))
    for i in a:
        np.testing.assert_array_equal(np.asarray(a[i]), np.asarray(b[i]))
    with pytest.raises(ValueError, match="packetsize"):
        registry.factory("jerasure", dict(base, packetsize="-1"))


def test_packetsize_accepted_across_techniques():
    from ceph_tpu.codecs import registry

    for tech in ("reed_sol_van", "cauchy_good", "cauchy_orig"):
        c = registry.factory("jerasure", {
            "technique": tech, "k": "4", "m": "2",
            "packetsize": "2048",
        })
        assert c.packetsize == 2048


def test_liberation_construction_is_plank():
    """Default liberation matrices follow the published Liberation
    definition (Plank FAST'08; jerasure liberation_coding_bitmatrix,
    ErasureCodeJerasure.cc:676): Q block X_i = cyclic shift S^i plus,
    for i>0, one extra bit at (y, (y+i-1) mod w), y = i(w-1)/2 mod w.
    Verified structurally here; MDS is checked at construction."""
    from ceph_tpu.codecs import registry

    for k, w in ((4, 7), (3, 5), (7, 7), (5, 11)):
        codec = registry.factory("jerasure", {
            "technique": "liberation", "k": str(k), "m": "2", "w": str(w),
        })
        mat = np.asarray(codec.coding_bitmatrix)
        assert mat.shape == (2 * w, k * w)
        # P rows: plain identities
        for i in range(k):
            np.testing.assert_array_equal(
                mat[:w, i * w : (i + 1) * w], np.eye(w, dtype=np.uint8)
            )
        # Q rows: S^i (+ the single liberation bit for i > 0)
        for i in range(k):
            x = mat[w:, i * w : (i + 1) * w].copy()
            if i > 0:
                y = (i * ((w - 1) // 2)) % w
                assert x[y, (y + i - 1) % w] == 1
                x[y, (y + i - 1) % w] = 0
            expect = np.zeros((w, w), np.uint8)
            for r in range(w):
                expect[r, (r + i) % w] = 1
            np.testing.assert_array_equal(x, expect)
        # minimal density: k*w + k - 1 ones in Q
        assert int(mat[w:].sum()) == k * w + k - 1


def test_bitmatrix_construction_v0_pin():
    """construction=v0 reproduces the round-1 matrices (corpus-v0
    reproducibility); the default differs for liberation/liber8tion."""
    from ceph_tpu.codecs import registry
    from ceph_tpu.codecs.bitmatrix_codec import (
        gf2w_power_bitmatrix,
        raid6_bitmatrix,
    )

    v0 = registry.factory("jerasure", {
        "technique": "liberation", "k": "4", "m": "2", "w": "7",
        "construction": "v0",
    })
    assert v0.coding_bitmatrix.tobytes() == raid6_bitmatrix(4, 7)
    new = registry.factory("jerasure", {
        "technique": "liberation", "k": "4", "m": "2", "w": "7",
    })
    assert new.coding_bitmatrix.tobytes() != raid6_bitmatrix(4, 7)

    v0 = registry.factory("jerasure", {
        "technique": "liber8tion", "k": "4", "m": "2",
        "construction": "v0",
    })
    assert v0.coding_bitmatrix.tobytes() == gf2w_power_bitmatrix(4, 8)
    with pytest.raises(ValueError, match="construction"):
        registry.factory("jerasure", {
            "technique": "liberation", "k": "4", "m": "2",
            "construction": "nope",
        })


def test_liber8tion_defaults_are_sparse_mds():
    """The default liber8tion matrices (minimal-density search for
    k<=4, sparsest generator powers for k>=5) must stay sparse enough
    for the XOR-schedule route AND decode every 1-2 erasure pattern
    (exhaustive MDS, the liber8tion property)."""
    from ceph_tpu.codecs import registry
    from ceph_tpu.ops import xor_schedule

    rng = np.random.default_rng(9)
    for k in (2, 4, 5, 8):
        codec = registry.factory("jerasure", {
            "technique": "liber8tion", "k": str(k), "m": "2",
        })
        rows = xor_schedule.schedule_rows(codec.coding_bitmatrix)
        assert xor_schedule.profitable(rows, k * 8), (
            f"k={k} liber8tion matrix too dense for the schedule route"
        )
        cs = codec.get_chunk_size(k * 1024)
        data = {i: rng.integers(0, 256, (cs,), np.uint8) for i in range(k)}
        chunks = {**data, **codec.encode_chunks(dict(data))}
        chunks = {i: np.asarray(c) for i, c in chunks.items()}
        for count in (1, 2):
            for erased in combinations(range(k + 2), count):
                have = {i: c for i, c in chunks.items() if i not in erased}
                out = codec.decode_chunks(set(erased), have)
                for e in erased:
                    np.testing.assert_array_equal(
                        np.asarray(out[e]), chunks[e]
                    )
