"""Mesh dispatch context: the seam that makes the multi-chip tier a
SYSTEM component instead of a standalone demo.

The reference's distributed backend is the per-shard sub-op fan-out
over AsyncMessenger (MOSDECSubOpWrite,
msg/async/AsyncMessenger.h:95 — SURVEY.md §5.8 maps it to an ICI
all-to-all of shard slices). Here the equivalent seam is a process-
wide active ``jax.sharding.Mesh``: when one is configured (and the
``ec_use_mesh`` option is on), every bitmatrix dispatch in the codec
tier — encode, decode, parity delta — shards the stripe batch over
``dp`` and the shard axis over ``sp`` and combines parity with the
ring XOR collective (parallel/collectives.ring_parity), with the
same dispatch-counter visibility the single-chip routes have
(``mesh_encode`` / ``mesh_decode`` / ``mesh_delta`` /
``mesh_fallback`` in ``perf dump``).

The RMW and read pipelines need no code of their own for this: their
device work flows through ``codec.encode_chunks`` /
``decode_chunks`` / ``apply_delta``, all of which ask
``BitplaneDispatchMixin._plan_route``, the one router this module
feeds, and run the mesh route in ``_dispatch_bitmatrix``. ``__graft_entry__.dryrun_multichip`` drives a full
RMW write and a reconstruct read through this route on the virtual
8-device mesh; ``tests/test_mesh_pipeline.py`` forces it on for a
cluster round trip.
"""

from __future__ import annotations

import contextlib

from jax.sharding import Mesh

# Process-wide, NOT thread-local: OSD daemons dispatch codec work from
# their connection-reader threads, and those must see the mesh the
# operator installed.
_mesh: Mesh | None = None


def set_mesh(mesh: Mesh | None) -> None:
    """Install (or clear) the process-wide EC dispatch mesh."""
    global _mesh
    _mesh = mesh


def get_mesh() -> Mesh | None:
    return _mesh


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None):
    """Scoped mesh activation (tests, dryruns)."""
    prev = get_mesh()
    set_mesh(mesh)
    try:
        yield mesh
    finally:
        set_mesh(prev)


def mesh_supported(
    mesh: Mesh, bitmatrix_shape, data_shape
) -> bool:
    """Divisibility contract for the sharded route: stripes split
    over ``dp`` (directly, or by folding the lane axis into the
    batch — the bitmatrix apply is lane-independent, so any exact
    lane split is free parallelism; parity-delta dispatches always
    arrive with batch 1), and bitmatrix columns (= input shards)
    over ``sp``. The residual lane axis need not split — ring_parity
    falls back to the psum schedule internally when it doesn't."""
    if len(data_shape) != 3:
        return False
    batch, c, n = data_shape
    if bitmatrix_shape[1] != c * 8:
        return False
    dp = mesh.shape.get("dp", 1)
    # The shard axis pads with zero shards up to sp (exact in GF(2)),
    # so only the stripe/lane split can disqualify a dispatch.
    return batch % dp == 0 or n % dp == 0


def mesh_apply_bitmatrix(mesh: Mesh, bitmatrix, data):
    """[R*8, C*8] GF(2) bitmatrix over [B, C, N] uint8 shards, stripe
    batch over ``dp``, shard/survivor axis over ``sp``, ring-XOR
    parity combine. Same contract as the single-chip kernel routes.

    When the batch does not divide ``dp``, the lane axis is folded
    into the batch (transpose + reshape) before the shard_map and
    unfolded after — exact, because the GF(2) apply is independent
    per lane. When the shard count does not divide ``sp`` (a
    parity-delta touching few columns, or an odd survivor set), zero
    shards pad it out — zeros contribute nothing in GF(2)."""
    import jax.numpy as jnp

    from .collectives import ring_parity

    b, c, n = data.shape
    sp = mesh.shape.get("sp", 1)
    pad = (-c) % sp
    if pad:
        data = jnp.concatenate(
            [data, jnp.zeros((b, pad, n), data.dtype)], axis=1
        )
        bitmatrix = jnp.concatenate(
            [
                bitmatrix,
                jnp.zeros(
                    (bitmatrix.shape[0], pad * 8), bitmatrix.dtype
                ),
            ],
            axis=1,
        )
        c += pad
    dp = mesh.shape.get("dp", 1)
    if b % dp == 0:
        return ring_parity(mesh, bitmatrix, data)
    folded = (
        data.reshape(b, c, dp, n // dp)
        .transpose(0, 2, 1, 3)
        .reshape(b * dp, c, n // dp)
    )
    out = ring_parity(mesh, bitmatrix, folded)
    r = out.shape[1]
    return (
        out.reshape(b, dp, r, n // dp)
        .transpose(0, 2, 1, 3)
        .reshape(b, r, n)
    )
