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
