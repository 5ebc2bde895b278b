"""Fused encode+checksum: one HBM pass for the whole write path.

Pins the round-7 tentpole against independent oracles:

- the fused kernels' per-block csums vs ``checksum.reference``
  crc32c_ref for every dense family and geometry (non-pow2 k with pad
  columns, c > 8 through the shards form, partial/zero tail blocks),
  with the parity simultaneously checked against the host GF tables;
- seed conversion (zero-init kernel csums -> any seed via one XOR);
- HashInfo cumulative-hash equivalence: device-seeded
  (append_block_csums from kernel csums) vs host-seeded (append over
  raw bytes) must match bit-for-bit, through the unit API AND through
  a full RMW pipeline run;
- BlockStore genuinely ADOPTS sub-write csums (a wrong provided csum
  surfaces as CsumError on read — proving no host re-hash happened);
- Checksummer backend exposure + the crc32c_stream host/device policy;
- recovery's pre-push HashInfo verification of reconstructed shards.
"""

import numpy as np
import pytest

from ceph_tpu.checksum.reference import crc32c_ref
from ceph_tpu.gf import (
    cauchy_good_matrix,
    cauchy_original_matrix,
    gf_matrix_to_bitmatrix,
    isa_rs_matrix,
    vandermonde_rs_matrix,
)
from ceph_tpu.gf.tables import gf_apply_bytes_host
from ceph_tpu.ops import pallas_encode as pe

B, N = 8, pe.LANE_TILE
SEED32 = 0xFFFFFFFF

FAMILIES = [
    # two geometries per family: k=5 exercises the pad columns, k=10
    # the c > 8 shards form; csum blocks span nb=1 (cb == tile) to
    # nb=8 within a grid step
    ("reed_sol_van", vandermonde_rs_matrix, (8, 4), 512),
    ("reed_sol_van", vandermonde_rs_matrix, (5, 3), 2048),
    ("cauchy_orig", cauchy_original_matrix, (4, 2), 256),
    ("cauchy_orig", cauchy_original_matrix, (5, 3), 1024),
    ("cauchy_good", cauchy_good_matrix, (4, 2), 512),
    ("cauchy_good", cauchy_good_matrix, (10, 4), 512),
    ("isa_rs", isa_rs_matrix, (8, 3), 1024),
    ("isa_rs", isa_rs_matrix, (6, 3), 256),
]
IDS = [f"{n}-k{k}m{m}-cb{cb}" for n, _, (k, m), cb in FAMILIES]


def _ref_csums(full: np.ndarray, cb: int) -> np.ndarray:
    """[B, S, N] bytes -> [B, S, N//cb] zero-init crc32c via the
    bitwise oracle."""
    b, s, n = full.shape
    return np.array(
        [
            [
                [
                    crc32c_ref(
                        0, full[i, j, q * cb : (q + 1) * cb].tobytes()
                    )
                    for q in range(n // cb)
                ]
                for j in range(s)
            ]
            for i in range(b)
        ],
        np.uint32,
    )


@pytest.mark.parametrize("name,build,km,cb", FAMILIES, ids=IDS)
def test_fused_kernel_csums_match_reference(rng, name, build, km, cb):
    """Stacked AND shards fused kernels: parity == host GF tables,
    csums == the bitwise crc32c oracle, zero-init."""
    import jax.numpy as jnp

    k, m = km
    g = np.asarray(build(k, m))
    bmat = gf_matrix_to_bitmatrix(g[k:, :])
    data = rng.integers(0, 256, (B, k, N), np.uint8)
    want = gf_apply_bytes_host(g[k:, :], data)
    ref = _ref_csums(np.concatenate([data, want], axis=1), cb)

    assert pe.fused_csum_supported(data.shape, cb)
    par, cs = pe.gf_encode_csum_bitplane_pallas(
        bmat, jnp.asarray(data), cb, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(par), want)
    np.testing.assert_array_equal(np.asarray(cs), ref)

    assert pe.fused_csum_shards_supported(k, (B, N), cb)
    outs, cs2 = pe.gf_encode_csum_bitplane_pallas_shards(
        bmat, [jnp.asarray(data[:, i, :]) for i in range(k)], cb,
        interpret=True,
    )
    for j in range(m):
        np.testing.assert_array_equal(np.asarray(outs[j]), want[:, j, :])
    np.testing.assert_array_equal(np.asarray(cs2), ref)


def test_fused_kernel_partial_tail_blocks(rng):
    """A ragged shard tail (zero-padded by the write-path convention)
    csums as its zero-padded blocks — the consumer contract for
    partial tail blocks: stores must NOT adopt kernel csums for a
    shorter-than-block write (crc(partial) != crc(padded)), and the
    gate in BlockStore._write_range enforces exactly that."""
    import jax.numpy as jnp

    k, m, cb = 4, 2, 512
    g = np.asarray(vandermonde_rs_matrix(k, m))
    bmat = gf_matrix_to_bitmatrix(g[k:, :])
    data = rng.integers(0, 256, (B, k, N), np.uint8)
    data[:, :, -700:] = 0  # ragged tail, zero-padded mid-block
    want = gf_apply_bytes_host(g[k:, :], data)
    _par, cs = pe.gf_encode_csum_bitplane_pallas(
        bmat, jnp.asarray(data), cb, interpret=True
    )
    ref = _ref_csums(np.concatenate([data, want], axis=1), cb)
    np.testing.assert_array_equal(np.asarray(cs), ref)
    # block 2 straddles the ragged boundary (zeros from 1348): its
    # kernel csum is the crc of the PADDED block — distinct from the
    # crc of just the surviving partial bytes, which is why stores
    # must never adopt kernel csums for sub-block writes
    assert int(np.asarray(cs)[0, 0, 2]) == crc32c_ref(
        0, data[0, 0, 1024:1536].tobytes()
    )
    assert int(np.asarray(cs)[0, 0, 2]) != crc32c_ref(
        0, data[0, 0, 1024:1348].tobytes()
    )


def test_seed_conversion_matches_seeded_reference(rng):
    """crc(seed, B) == kernel_zero_init ^ crc32c_seed_shift — one XOR
    turns the kernel output into BlueStore blob csums (seed -1)."""
    import jax.numpy as jnp

    from ceph_tpu.checksum import crc32c_seed_shift

    k, m, cb = 4, 2, 512
    g = np.asarray(vandermonde_rs_matrix(k, m))
    bmat = gf_matrix_to_bitmatrix(g[k:, :])
    data = rng.integers(0, 256, (B, k, N), np.uint8)
    _par, cs = pe.gf_encode_csum_bitplane_pallas(
        bmat, jnp.asarray(data), cb, interpret=True
    )
    shift = crc32c_seed_shift(cb, SEED32)
    got = int(np.asarray(cs)[2, 1, 0]) ^ shift
    assert got == crc32c_ref(SEED32, data[2, 1, :cb].tobytes())


# -------------------------------------------------- hashinfo equivalence
def test_hashinfo_device_seeded_equals_host_seeded(rng):
    """append_block_csums (kernel csums + crc chaining) must land on
    bit-identical cumulative hashes as append (raw bytes), across
    multiple contiguous appends and mixed paths."""
    from ceph_tpu.pipeline.hashinfo import HashInfo

    cb = 512
    host = HashInfo(3)
    dev = HashInfo(3)
    off = 0
    for step, nblk in enumerate((4, 1, 8)):
        bufs = {
            s: rng.integers(0, 256, nblk * cb, np.uint8)
            for s in range(3)
        }
        host.append(off, bufs)
        csums = {
            s: np.array(
                [
                    crc32c_ref(0, b[q * cb : (q + 1) * cb].tobytes())
                    for q in range(nblk)
                ],
                np.uint32,
            )
            for s, b in bufs.items()
        }
        dev.append_block_csums(off, csums, cb)
        off += nblk * cb
    assert host == dev
    # mixed: bytes append onto a device-seeded chain still matches
    tail = {s: rng.integers(0, 256, cb, np.uint8) for s in range(3)}
    host.append(off, tail)
    dev.append(off, tail)
    assert host == dev


def test_hashinfo_block_append_contract():
    from ceph_tpu.pipeline.hashinfo import HashInfo

    hi = HashInfo(2)
    with pytest.raises(ValueError):
        hi.append_block_csums(512, {0: [1], 1: [2]}, 512)
    with pytest.raises(ValueError):
        hi.append_block_csums(0, {0: [1, 2], 1: [3]}, 512)


# ------------------------------------------------ end-to-end write path
def _run_pipeline(tmp_path, fused: bool, store_cls, tag: str):
    from ceph_tpu.codecs.registry import registry
    from ceph_tpu.pipeline.rmw import RMWPipeline, ShardBackend
    from ceph_tpu.pipeline.stripe import StripeInfo
    from ceph_tpu.store.memstore import MemStore
    from ceph_tpu.utils import config

    k, m = 4, 2
    with config.override(
        ec_fused_csum_interpret=fused, ec_host_dispatch_bytes=0
    ):
        sinfo = StripeInfo(k, m, k * 8192)
        codec = registry.factory("isa", {"k": str(k), "m": str(m)})
        if store_cls is MemStore:
            stores = {i: MemStore() for i in range(k + m)}
        else:
            stores = {
                i: store_cls(
                    str(tmp_path / f"{tag}-s{i}"), size=1 << 24
                )
                for i in range(k + m)
            }
        backend = ShardBackend(stores)
        pipe = RMWPipeline(sinfo, codec, backend)
        rng = np.random.default_rng(7)
        data = rng.integers(0, 256, k * 8192, np.uint8).tobytes()
        pipe.submit("obj", 0, data)
        pipe.submit("obj", len(data), data)  # contiguous append
    return pipe, stores


def test_rmw_fused_equals_host_path(tmp_path):
    """Full pipeline, fused vs host csum paths: identical stored
    bytes, bit-identical HashInfo — the device-seeded cumulative
    hashes are indistinguishable from the host-seeded ones."""
    from ceph_tpu.store.memstore import MemStore

    p_host, s_host = _run_pipeline(tmp_path, False, MemStore, "h")
    p_dev, s_dev = _run_pipeline(tmp_path, True, MemStore, "d")
    assert p_host.hinfo("obj") == p_dev.hinfo("obj")
    for i in s_host:
        assert s_host[i].read("obj") == s_dev[i].read("obj")


def test_rmw_sub_writes_carry_kernel_csums(tmp_path):
    """The sub-write transactions of a fused-path write carry Op.csums
    for every aligned extent, and a BlockStore-backed cluster stores
    csums that verify against the host oracle."""
    from ceph_tpu.store.blockstore import BlockStore

    pipe, stores = _run_pipeline(tmp_path, True, BlockStore, "b")
    st = stores[0]
    onode = st._objects["obj"]
    assert onode.blobs, "write landed"
    for blob in onode.blobs.values():
        raw = st._blob_bytes(blob)
        for i, c in enumerate(blob.csums):
            assert c == crc32c_ref(
                SEED32, raw[i * 4096 : (i + 1) * 4096]
            )


def test_blockstore_adopts_provided_csums(tmp_path):
    """Adoption is real: a deliberately WRONG provided csum stored
    without complaint surfaces as CsumError on read — the store did
    not re-hash the bytes. Correct zero-init csums verify clean."""
    from ceph_tpu.store import Transaction
    from ceph_tpu.store.blockstore import BlockStore, CsumError

    st = BlockStore(str(tmp_path / "adopt"), size=1 << 22)
    blk = b"\xcd" * 4096
    st.queue_transactions(
        Transaction().touch("good").write(
            "good", 0, blk, csums=[crc32c_ref(0, blk)], csum_block=4096
        )
    )
    assert st.read("good") == blk
    st.queue_transactions(
        Transaction().touch("bad").write(
            "bad", 0, blk, csums=[0xDEADBEEF], csum_block=4096
        )
    )
    with pytest.raises(CsumError):
        st.read("bad")
    # unaligned/partial writes must NOT adopt (fall back to re-hash)
    st.queue_transactions(
        Transaction().touch("part").write(
            "part", 0, b"\xab" * 1000, csums=[0xDEADBEEF],
            csum_block=4096,
        )
    )
    assert st.read("part") == b"\xab" * 1000


def test_transaction_wire_roundtrips_csums():
    """v2 encoding carries csums; csum-free transactions stay v1
    byte-identical (the frozen golden payload depends on it)."""
    from ceph_tpu.store import Transaction

    plain = Transaction().touch("o").write("o", 0, b"x" * 8)
    assert plain.to_bytes()[0] == 1
    rt = Transaction.from_bytes(plain.to_bytes())
    assert rt.ops[1].csums is None

    txn = Transaction().write(
        "o", 4096, b"y" * 8192, csums=[1, 0xFFFFFFFF], csum_block=4096
    ).setattr("o", "a", b"v")
    raw = txn.to_bytes()
    assert raw[0] == 2
    rt = Transaction.from_bytes(raw)
    assert rt.ops[0].csums == (1, 0xFFFFFFFF)
    assert rt.ops[0].csum_block == 4096
    assert rt.ops[1].csums is None and rt.ops[1].csum_block == 0


# ---------------------------------------------- backend observability
def test_checksummer_exposes_backend(rng):
    from ceph_tpu.checksum import Checksummer, backends
    from ceph_tpu.utils import config

    cs = Checksummer("crc32c", 4096)
    data = rng.integers(0, 256, 8 * 4096, np.uint8).tobytes()
    with config.override(csum_device_min_bytes=1 << 20):
        cs.calculate(data)
        assert cs.last_backend == "host"
    with config.override(csum_device_min_bytes=1):
        before = backends.counts().get("einsum", 0)
        out_dev = cs.calculate(data)
        assert cs.last_backend in ("einsum", "pallas")
        assert backends.counts().get("einsum", 0) + backends.counts().get(
            "pallas", 0
        ) > before
    # both backends produce identical csums
    with config.override(csum_device_min_bytes=1 << 20):
        np.testing.assert_array_equal(cs.calculate(data), out_dev)


def test_crc32c_stream_policy_and_equivalence(rng):
    from ceph_tpu.checksum import crc32c_stream
    from ceph_tpu.utils import config

    buf = rng.integers(0, 256, 3 * 4096 + 123, np.uint8)
    want = crc32c_ref(SEED32, buf.tobytes())
    assert crc32c_stream(buf) == want  # host route (small)
    with config.override(csum_device_min_bytes=1):
        assert crc32c_stream(buf) == want  # device blocks + host tail
    # chaining across pieces
    with config.override(csum_device_min_bytes=1):
        mid = crc32c_stream(buf[:8192])
        assert crc32c_stream(buf[8192:], mid) == want


def test_pallas_fallback_is_visible(monkeypatch, rng):
    """supported() falling back no longer hides: the einsum route and
    the pallas_fallback counter both record."""
    import jax.numpy as jnp

    from ceph_tpu.checksum import backends
    from ceph_tpu.checksum.crc32c import crc32c_device
    from ceph_tpu.utils import platform

    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    before = backends.counts().get("pallas_fallback", 0)
    data = rng.integers(0, 256, (3, 1000), np.uint8)  # untileable
    out = np.asarray(crc32c_device(jnp.asarray(data), SEED32))
    ref = np.array(
        [crc32c_ref(SEED32, data[i].tobytes()) for i in range(3)],
        np.uint32,
    )
    np.testing.assert_array_equal(out, ref)
    assert backends.counts().get("pallas_fallback", 0) == before + 1


# -------------------------------------------------- recovery + scrub
def test_recovery_verifies_reconstruction_against_hinfo(tmp_path):
    """A full rebuild whose bytes do not match the persisted HashInfo
    is rejected BEFORE the push; a clean rebuild passes and the scrub
    tier (crc32c_stream-routed) agrees."""
    from ceph_tpu.codecs.registry import registry
    from ceph_tpu.pipeline.recovery import RecoveryBackend, be_deep_scrub
    from ceph_tpu.pipeline.rmw import HINFO_KEY, RMWPipeline, ShardBackend
    from ceph_tpu.pipeline.hashinfo import HashInfo
    from ceph_tpu.pipeline.stripe import StripeInfo
    from ceph_tpu.store.memstore import MemStore

    k, m = 4, 2
    sinfo = StripeInfo(k, m, k * 8192)
    codec = registry.factory("isa", {"k": str(k), "m": str(m)})
    stores = {i: MemStore() for i in range(k + m)}
    backend = ShardBackend(stores)
    pipe = RMWPipeline(sinfo, codec, backend)
    rng = np.random.default_rng(9)
    data = rng.integers(0, 256, k * 8192, np.uint8).tobytes()
    pipe.submit("obj", 0, data)
    hinfo = pipe.hinfo("obj")
    assert be_deep_scrub(sinfo, backend, "obj", hinfo).ok

    rb = RecoveryBackend(
        sinfo, codec, backend,
        size_fn=lambda oid: pipe.object_size(oid),
        hinfo_fn=lambda oid: pipe.hinfo(oid),
    )
    # clean rebuild of a lost shard passes the verify and the scrub
    stores[1] = MemStore()
    backend.stores[1] = stores[1]
    rb.recover_object("obj", {1})
    assert be_deep_scrub(sinfo, backend, "obj").ok

    # poisoned hinfo: the rebuild no longer matches -> rejected
    bad = HashInfo(k + m)
    bad.total_chunk_size = hinfo.total_chunk_size
    bad.cumulative_shard_hashes = [0x1234] * (k + m)
    rb_bad = RecoveryBackend(
        sinfo, codec, backend,
        size_fn=lambda oid: pipe.object_size(oid),
        hinfo_fn=lambda oid: bad,
        perf_name="ec_recovery_bad",
    )
    stores[2] = MemStore()
    backend.stores[2] = stores[2]
    with pytest.raises(IOError, match="fails HashInfo verify"):
        rb_bad.recover_object("obj", {2})
