"""Schedule-native XOR engine (ops/xor_schedule.py) — the
jerasure_schedule_encode analog. Bit-exactness of the Pallas kernel
(interpret mode on CPU) vs the plain-XLA form vs a numpy oracle, the
density gate, and tiling preconditions."""

import numpy as np
import pytest

from ceph_tpu.ops import xor_schedule
from ceph_tpu.utils import platform


@pytest.fixture
def rng():
    return np.random.default_rng(17)


def numpy_oracle(sel_rows, packets):
    out = np.zeros(
        packets.shape[:-2] + (len(sel_rows), packets.shape[-1]), np.uint8
    )
    for q, sel in enumerate(sel_rows):
        for j in sel:
            out[..., q, :] ^= packets[..., j, :]
    return out


def test_schedule_rows_and_density():
    mat = np.array(
        [[1, 0, 1, 0], [0, 0, 0, 0], [1, 1, 1, 1]], np.uint8
    )
    rows = xor_schedule.schedule_rows(mat)
    assert rows == ((0, 2), (), (0, 1, 2, 3))
    # ones=6, rows=3 -> ratio (6+3)/4 = 2.25
    assert xor_schedule.profitable(rows, 4)
    dense = tuple(tuple(range(16)) for _ in range(8))
    assert not xor_schedule.profitable(dense, 16)  # (128+8)/16 = 8.5
    assert not xor_schedule.profitable((), 4)


def test_supported_predicate():
    assert xor_schedule.supported((1, 28, 2048))
    assert xor_schedule.supported((4, 12, 8192))
    assert not xor_schedule.supported((1, 28, 1000))
    assert not xor_schedule.supported((28, 2048))


@pytest.mark.parametrize("p", [2048, 8192, 10240])
def test_pallas_interpret_matches_oracle(rng, p):
    sel_rows = ((0, 3, 5), (1, 2), (), (0, 1, 2, 3, 4, 5, 6))
    packets = rng.integers(0, 256, (3, 7, p), np.uint8)
    want = numpy_oracle(sel_rows, packets)
    got = np.asarray(
        xor_schedule.xor_schedule_apply(sel_rows, packets, interpret=True)
    )
    np.testing.assert_array_equal(got, want)


def test_xla_fallback_matches_oracle(rng):
    sel_rows = ((0, 2), (1,), (0, 1, 2))
    packets = rng.integers(0, 256, (2, 2, 3, 4096), np.uint8)
    want = numpy_oracle(sel_rows, packets)
    got = np.asarray(xor_schedule.xor_schedule_apply(sel_rows, packets))
    np.testing.assert_array_equal(got, want)


def test_liberation_schedule_end_to_end(rng):
    """The real liberation matrix through both kernel forms."""
    from ceph_tpu.codecs import registry

    codec = registry.factory(
        "jerasure",
        {"technique": "liberation", "k": "4", "m": "2", "w": "7"},
    )
    rows = xor_schedule.schedule_rows(codec.coding_bitmatrix)
    assert xor_schedule.profitable(rows, 28)
    packets = rng.integers(0, 256, (2, 28, 2048), np.uint8)
    want = numpy_oracle(rows, packets)
    got_interp = np.asarray(
        xor_schedule.xor_schedule_apply(rows, packets, interpret=True)
    )
    got_xla = np.asarray(xor_schedule.xor_schedule_apply(rows, packets))
    np.testing.assert_array_equal(got_interp, want)
    np.testing.assert_array_equal(got_xla, want)


@pytest.mark.parametrize("lead", [(2,), (8,), (), (2, 3)])
def test_shards_form_matches_oracle(rng, lead):
    """Multi-operand whole-chunk kernel (interpret mode) vs oracle,
    across leading-dim shapes including sublane-multiple batches."""
    w, k = 3, 4
    chunk = 3 * 1024
    sel_rows = (
        (0, 3, 6, 9), (1, 4, 7, 10), (2, 5, 8, 11),
        (0, 4, 8), (1, 5, 9, 2), (11,),
    )
    shards = [
        rng.integers(0, 256, lead + (chunk,), np.uint8) for _ in range(k)
    ]
    packets = np.stack(shards, axis=-2).reshape(
        lead + (k * w, chunk // w)
    )
    want = numpy_oracle(sel_rows, packets).reshape(lead + (2, chunk))
    outs = xor_schedule.xor_schedule_apply_shards(
        sel_rows, shards, w, interpret=True
    )
    assert len(outs) == 2
    for j, o in enumerate(outs):
        np.testing.assert_array_equal(np.asarray(o), want[..., j, :])


def test_shards_form_xla_fallback_matches(rng):
    """Off-TPU the shards form routes through the fused-XLA path and
    must agree with interpret-mode pallas."""
    w, k = 3, 2
    chunk = 3 * 512
    sel_rows = ((0, 3), (1, 4, 2), (5,), (0, 1, 2, 3, 4, 5), (2, 5), ())
    shards = [
        rng.integers(0, 256, (4, chunk), np.uint8) for _ in range(k)
    ]
    a = xor_schedule.xor_schedule_apply_shards(sel_rows, shards, w)
    b = xor_schedule.xor_schedule_apply_shards(
        sel_rows, shards, w, interpret=True
    )
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_shards_supported():
    f = xor_schedule.shards_supported
    assert f(4, 2, 7, (8, 7 * 2048))
    assert f(4, 2, 7, (7 * 2048,))          # single stripe, one block
    assert not f(4, 2, 7, (8, 7 * 100))     # packet not lane-aligned
    assert not f(4, 2, 7, (8, 7 * 524288))  # VMEM blowout
    assert f(4, 2, 7, (3, 7 * 2048))        # odd batch -> one block


def test_codec_shards_route(rng, monkeypatch):
    """With the TPU predicate forced on (kernel in interpret mode),
    the codec serves encode/decode/delta through the shards form and
    the results match the engine bit-for-bit."""
    import functools

    from ceph_tpu.codecs import registry
    from ceph_tpu.codecs.matrix_codec import _dispatch_counters

    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    orig = xor_schedule.xor_schedule_apply_shards
    monkeypatch.setattr(
        xor_schedule,
        "xor_schedule_apply_shards",
        functools.partial(orig, interpret=True),
    )
    codec = registry.factory(
        "jerasure",
        {"technique": "liberation", "k": "4", "m": "2", "w": "7"},
    )
    import jax.numpy as jnp

    n = 7 * 2048
    data = {
        i: jnp.asarray(rng.integers(0, 256, (8, n), np.uint8))
        for i in range(4)
    }
    pc = _dispatch_counters()
    before = pc.get("sched_encode")
    parity = codec.encode_chunks(dict(data))
    assert pc.get("sched_encode") > before

    # reference: engine path (schedule off)
    from ceph_tpu.utils import config

    with config.override(ec_use_sched=False):
        ref = codec.encode_chunks(dict(data))
    for i in parity:
        np.testing.assert_array_equal(
            np.asarray(parity[i]), np.asarray(ref[i])
        )

    # decode via shards route (sparse 1-data+1-parity pattern)
    chunks = {**data, **parity}
    del chunks[0], chunks[4]
    out = codec.decode_chunks({0, 4}, chunks)
    np.testing.assert_array_equal(
        np.asarray(out[0]), np.asarray(data[0])
    )
    np.testing.assert_array_equal(
        np.asarray(out[4]), np.asarray(parity[4])
    )

    # delta via shards route
    deltas = {
        i: jnp.asarray(rng.integers(0, 256, (8, n), np.uint8))
        for i in (1, 2)
    }
    got = codec.apply_delta(
        dict(deltas), {4: parity[4], 5: parity[5]}
    )
    with config.override(ec_use_sched=False):
        ref = codec.apply_delta(
            dict(deltas), {4: parity[4], 5: parity[5]}
        )
    for pid in got:
        np.testing.assert_array_equal(
            np.asarray(got[pid]), np.asarray(ref[pid])
        )


def test_pick_tile():
    assert xor_schedule._pick_tile(32768) == 8192
    assert xor_schedule._pick_tile(8192) == 8192
    # round-11 divisor search: 2048*5 no longer degrades to a 2048
    # sliver (tests/test_sched_superopt.py pins the full corpus set)
    assert xor_schedule._pick_tile(10240) == 5120
    assert xor_schedule._pick_tile(6144) == 6144
