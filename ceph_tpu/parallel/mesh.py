"""Distributed EC: shard fan-out as XLA collectives over a device mesh.

The reference fans per-shard sub-ops to k+m-1 remote OSDs over
AsyncMessenger/ProtocolV2 (MOSDECSubOpWrite — SURVEY.md section 5.8).
The TPU-native design replaces that with SPMD over a Mesh:

- axis ``dp`` — stripe batch (data parallel): independent stripes on
  different devices, no communication.
- axis ``sp`` — shard axis (the tensor-parallel analog): each device
  holds a subset of data shards; parity is an XOR-reduction across
  devices, expressed as an integer ``psum`` over bit-plane counts
  followed by mod 2. XLA lowers the psum onto ICI; on multi-host
  meshes the same program spans the hosts' network with no code change — that IS the
  framework's distributed communication backend.

GF(2) trick making the collective cheap: parity bits are (sum of
per-device partial bit-counts) mod 2, and psum-of-int32 is exact, so
the cross-device combine is a single standard all-reduce.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ceph_tpu.ops.bitplane import pack_bits, unpack_bits


def mesh_program(f, mesh: Mesh, in_specs, out_specs, name=None):
    """``f`` as ONE jitted SPMD program over ``mesh``. Builders cache
    the result per geometry (``functools.lru_cache``): an un-jitted
    ``shard_map`` executes primitive by primitive, and a closure
    rebuilt per call never hits jit's cache — the live path paid ~56
    backend compilations for every dispatch that way.

    ``name`` pins the program's name on the device (``jit_<name>``,
    what a profiler trace's module line shows) whatever ``f`` is
    called in the source."""
    mapped = jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    if name is not None:
        mapped.__name__ = mapped.__qualname__ = name
    return jax.jit(mapped)


def make_ec_mesh(n_devices: int | None = None, k: int = 8) -> Mesh:
    """Mesh over (dp, sp): sp divides both n_devices and k so the shard
    axis splits evenly; prefer using both axes when possible."""
    avail = jax.devices()
    n = n_devices or len(avail)
    if n > len(avail):
        raise ValueError(
            f"requested {n} devices but only {len(avail)} available; "
            "a degenerate mesh would silently skip the collective path"
        )
    devs = avail[:n]
    # sp must divide BOTH n (for the reshape) and k (for even shard
    # split); prefer the largest such sp that still leaves dp > 1 so
    # both axes are exercised, else fall back to sp = gcd(n, k).
    divisors = [d for d in range(1, n + 1) if n % d == 0 and k % d == 0]
    proper = [d for d in divisors if d < n]
    sp = max(proper) if proper else max(divisors)
    dp = n // sp
    return Mesh(np.array(devs).reshape(dp, sp), ("dp", "sp"))


def partial_parity_counts(
    bmat_cols: jax.Array, shards: jax.Array
) -> jax.Array:
    """One device's contribution to the parity bit counts:
    [m*8, k_local*8] x [b, k_local, N] -> [b, m*8, N] int32 (mod 2
    pending). The shared local body of every parity collective."""
    bits = unpack_bits(shards)
    return jnp.einsum(
        "rc,bcn->brn",
        bmat_cols.astype(jnp.int8),
        bits.astype(jnp.int8),
        preferred_element_type=jnp.int32,
    )


def sharded_encode(
    mesh: Mesh, bitmatrix: jax.Array, data: jax.Array
) -> jax.Array:
    """Encode [B, k, N] uint8 -> [B, m, N] parity, stripes sharded over
    ``dp`` and shards over ``sp`` (XOR-allreduce for the parity combine).

    ``bitmatrix`` is the [m*8, k*8] GF(2) coding matrix; its column
    blocks are sharded over ``sp`` alongside the data shards.
    """
    return _sharded_encode_fn(mesh)(bitmatrix, data)


@functools.lru_cache(maxsize=16)
def _sharded_encode_fn(mesh: Mesh):
    def local(bmat_cols: jax.Array, shards: jax.Array) -> jax.Array:
        acc = partial_parity_counts(bmat_cols, shards)
        acc = jax.lax.psum(acc, "sp")  # XOR-allreduce (mod 2 below)
        return pack_bits((acc & 1).astype(jnp.uint8))

    # bitmatrix columns follow the shard axis: [m*8, k*8] -> sp-sharded.
    return mesh_program(
        local,
        mesh,
        in_specs=(P(None, "sp"), P("dp", "sp", None)),
        out_specs=P("dp", None, None),
    )


def sharded_decode(
    mesh: Mesh, dec_bitmatrix: jax.Array, survivors: jax.Array
) -> jax.Array:
    """Distributed reconstruct: decode is the same mod-2 matmul as
    encode with the inverted-submatrix rows, so the survivor axis
    shards over ``sp`` and the partial products combine with the same
    XOR-allreduce. ``survivors`` is [B, k, N] (any k survivors, rows
    matching the decode matrix columns); returns the missing shards.
    """
    return sharded_encode(mesh, dec_bitmatrix, survivors)


def sharded_pipeline_step(
    mesh: Mesh, bitmatrix: jax.Array, data: jax.Array
) -> dict[str, jax.Array]:
    """One full distributed EC step — the framework's "training step":

    encode (sp-XOR-allreduce across the shard axis) followed by the
    real per-chunk Checksummer CRC32C fold (the HashInfo/deep-scrub
    integrity word, computed on device). Jit-able under the mesh; the
    driver dry-runs this over N virtual devices and separately
    verifies a degraded-read reconstruct
    (see __graft_entry__.dryrun_multichip).
    """
    from ceph_tpu.checksum.crc32c import crc32c_device

    parity = sharded_encode(mesh, bitmatrix, data)
    csum = crc32c_device(parity)  # [B, m] uint32, one per parity chunk
    return {"parity": parity, "csum": csum}
