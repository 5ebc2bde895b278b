"""Pallas CRC32C fold kernel: bit-exact vs the host reference across
block sizes (interpreter mode — CPU CI runs the kernel itself), the
supported-shape predicate, and the crc32c_device dispatch gate.
"""

import numpy as np
import pytest

from ceph_tpu.checksum.pallas_crc import (
    BLOCK_TILE,
    SUB_BYTES,
    crc32c_fold_pallas,
    supported,
)
from ceph_tpu.checksum.reference import crc32c_ref


@pytest.mark.parametrize("nblocks,block_bytes", [
    (8, 4096),
    (16, 8192),
    (8, 16384),
    (32, 512),
    (8, 2048),
])
def test_bit_exact_vs_reference(rng, nblocks, block_bytes):
    import jax.numpy as jnp

    assert supported(nblocks, block_bytes)
    data = rng.integers(0, 256, (nblocks, block_bytes), np.uint8)
    out = np.asarray(
        crc32c_fold_pallas(jnp.asarray(data), 0xFFFFFFFF, interpret=True)
    )
    ref = np.array(
        [crc32c_ref(0xFFFFFFFF, data[i].tobytes()) for i in range(nblocks)],
        np.uint32,
    )
    np.testing.assert_array_equal(out, ref)


def test_nonstandard_init(rng):
    import jax.numpy as jnp

    data = rng.integers(0, 256, (8, 4096), np.uint8)
    init = 0x12345678
    out = np.asarray(
        crc32c_fold_pallas(jnp.asarray(data), init, interpret=True)
    )
    ref = np.array(
        [crc32c_ref(init, data[i].tobytes()) for i in range(8)], np.uint32
    )
    np.testing.assert_array_equal(out, ref)


def test_supported_predicate():
    assert supported(8, 4096)
    assert supported(BLOCK_TILE * 2, SUB_BYTES * 4)
    assert not supported(4, 4096)        # too few blocks
    assert not supported(8, 1000)        # lane-unaligned sub-fold
    assert supported(12, SUB_BYTES * 8)  # small counts tile as-is
    assert not supported(9, SUB_BYTES * 8)  # bitcast needs 4-packs
    assert not supported(BLOCK_TILE + 1, 4096)  # uneven sublane tile


def test_tile_blocks_is_the_least_supported_count():
    from ceph_tpu.checksum.pallas_crc import tile_blocks

    for n in range(1, 2 * BLOCK_TILE + 2):
        padded = tile_blocks(n)
        assert padded >= n and supported(padded, 4096), n
        assert not any(supported(c, 4096) for c in range(n, padded)), n
    assert [tile_blocks(n) for n in (1, 8, 9, 96, 513)] == [
        8, 8, 12, 96, 2 * BLOCK_TILE
    ]


def test_device_dispatch_gates_on_tpu(rng, monkeypatch):
    """crc32c_device routes through the pallas fold when on TPU and
    the shape tiles (kernel forced to interpreter mode for CPU CI)."""
    import functools

    import jax.numpy as jnp

    from ceph_tpu.checksum import crc32c as crc_mod
    from ceph_tpu.checksum import pallas_crc
    from ceph_tpu.utils import platform

    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    called = []
    orig = pallas_crc.crc32c_fold_pallas

    def spy(data, init, interpret=None):
        called.append(data.shape)
        return orig(data, init, interpret=True)

    monkeypatch.setattr(pallas_crc, "crc32c_fold_pallas", spy)
    data = rng.integers(0, 256, (8, 4096), np.uint8)
    out = np.asarray(crc_mod.crc32c_device(jnp.asarray(data), 0xFFFFFFFF))
    assert called == [(8, 4096)]
    ref = np.array(
        [crc32c_ref(0xFFFFFFFF, data[i].tobytes()) for i in range(8)],
        np.uint32,
    )
    np.testing.assert_array_equal(out, ref)


# ------------------------------------------- one compiled program a call
INITS = [0, 0xFFFFFFFF, 0x9E3779B9]


class _Compiles:
    """Backend compilations of the process while ``on`` (JAX's own
    monitoring event, as benchmark/compile_log.py reads it)."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.on = False
        self.names = []
        monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **kw):
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.names.append(str(kw.get("fun_name", "?")))


@pytest.fixture(scope="module")
def compiles():
    return _Compiles()


@pytest.mark.parametrize("init", INITS)
@pytest.mark.parametrize("nblocks,block_bytes", [(8, 4096), (96, 512)])
def test_bit_exact_vs_einsum_and_host(rng, nblocks, block_bytes, init):
    import jax.numpy as jnp

    from ceph_tpu.checksum.crc32c import crc32c_device

    data = rng.integers(0, 256, (nblocks, block_bytes), np.uint8)
    out = np.asarray(
        crc32c_fold_pallas(jnp.asarray(data), init, interpret=True)
    )
    # off the chip crc32c_device is the einsum route
    np.testing.assert_array_equal(
        out, np.asarray(crc32c_device(jnp.asarray(data), init))
    )
    ref = [crc32c_ref(init, data[i].tobytes()) for i in range(nblocks)]
    np.testing.assert_array_equal(out, np.array(ref, np.uint32))


def test_second_init_compiles_nothing(rng, compiles):
    """The fold, the init term, the mod 2 and the pack are ONE program
    a shape: another init, as a host int or a device scalar, compiles
    nothing and no second program runs beside it."""
    import jax.numpy as jnp

    # a shape no other test folds: a program compiled earlier in this
    # process would read as no cold compilation at all
    data = jnp.asarray(rng.integers(0, 256, (20, 768), np.uint8))
    dev_init = jnp.uint32(0xCAFEF00D)
    compiles.names.clear()
    compiles.on = True
    try:
        first = np.asarray(crc32c_fold_pallas(data, 0, interpret=True))
        cold = list(compiles.names)
        again = [
            np.asarray(crc32c_fold_pallas(data, init, interpret=True))
            for init in (0xFFFFFFFF, 0x12345678, dev_init)
        ]
    finally:
        compiles.on = False
    assert len(cold) <= 1 and all("_fold_tiled" in c for c in cold), cold
    assert compiles.names == cold
    host = np.asarray(data)
    for init, out in zip((0xFFFFFFFF, 0x12345678, 0xCAFEF00D), again):
        assert out[3] == crc32c_ref(init, host[3].tobytes())
    assert first[3] == crc32c_ref(0, host[3].tobytes())


def test_no_eager_op_between_entry_and_result(rng):
    """With its inputs on the device the call moves nothing between
    host and device and runs no primitive of its own: under a transfer
    guard an eager ``jnp`` op with a host operand (the old tail built
    ``a_total``, ``arange`` and the weights from numpy every call)
    would raise."""
    import jax
    import jax.numpy as jnp

    data = jnp.asarray(rng.integers(0, 256, (8, 2048), np.uint8))
    init = jnp.uint32(0xFFFFFFFF)
    want = np.asarray(crc32c_fold_pallas(data, init, interpret=True))
    with jax.transfer_guard("disallow"):
        out = crc32c_fold_pallas(data, init, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), want)


def test_traces_inside_an_outer_jit(rng):
    """Under an active trace the constants are compile-time ones and
    nothing traced is cached: the eager call after it still works."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.checksum import pallas_crc

    pallas_crc._device_cache.pop((1024, 64), None)
    data = rng.integers(0, 256, (8, 1024), np.uint8)
    ref = np.array(
        [crc32c_ref(7, data[i].tobytes()) for i in range(8)], np.uint32
    )
    outer = jax.jit(lambda d, i: crc32c_fold_pallas(d, i, interpret=True))
    np.testing.assert_array_equal(
        np.asarray(outer(jnp.asarray(data), jnp.uint32(7))), ref
    )
    assert (1024, 64) not in pallas_crc._device_cache
    np.testing.assert_array_equal(
        np.asarray(crc32c_fold_pallas(jnp.asarray(data), 7, interpret=True)),
        ref,
    )


def test_traces_inside_sharded_pipeline_step(rng, monkeypatch):
    """The driver's multichip dry run on a TPU: ``crc32c_device`` inside
    the jitted mesh step takes the Pallas fold (interpreted here)."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.checksum import backends
    from ceph_tpu.gf import gf_matrix_to_bitmatrix, vandermonde_rs_matrix
    from ceph_tpu.parallel import make_ec_mesh, sharded_pipeline_step
    from ceph_tpu.utils import platform

    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    k, m = 8, 4
    g = vandermonde_rs_matrix(k, m)
    bmat = jnp.asarray(gf_matrix_to_bitmatrix(g[k:, :]))
    mesh = make_ec_mesh(4, k=k)
    batch = 2 * mesh.shape["dp"]
    data = rng.integers(0, 256, (batch, k, 256), np.uint8)
    before = backends.counts().get("pallas", 0)
    out = jax.jit(lambda b, d: sharded_pipeline_step(mesh, b, d))(
        bmat, jnp.asarray(data)
    )
    assert backends.counts().get("pallas", 0) == before + 1
    parity, csum = np.asarray(out["parity"]), np.asarray(out["csum"])
    for b in range(batch):
        for j in range(m):
            assert csum[b, j] == crc32c_ref(
                0xFFFFFFFF, parity[b, j].tobytes()
            )
