"""The fold of zero-init block crc32c words into running registers
(``checksum.crc32c_fold``): what HashInfo's cumulative shard hashes are
made of on the fused encode+csum route. Both forms of it (the native
call, the numpy tree) equal the per-block loop the program had before
the one-call fold, kept here as the reference, and equal crc32c over
the bytes themselves; and the csum words ride a transaction to the
same wire bytes whatever sequence holds them."""

import numpy as np
import pytest

from ceph_tpu import native
from ceph_tpu.checksum import crc32c_fold
from ceph_tpu.checksum import host
from ceph_tpu.checksum.crc32c import mat32, zero_gap_columns, zero_gap_matrix
from ceph_tpu.pipeline.hashinfo import SEED, HashInfo
from ceph_tpu.store import Transaction

FOLDS = {
    "selected": host.fold_words,
    "numpy": host.fold_words_numpy,
}
SEEDS = {"zero": 0, "ones": 0xFFFFFFFF, "random": None}


def bits32(v: int) -> np.ndarray:
    return np.array([(v >> i) & 1 for i in range(32)], dtype=np.uint8)


def chain_block_by_block(init: int, csums, block_bytes: int) -> int:
    """The chain as it was before PR 39: one GF(2) matvec a block."""
    a = mat32(zero_gap_matrix(block_bytes))
    reg = bits32(init)
    for c0 in csums:
        reg = ((a @ reg) & 1) ^ bits32(int(c0))
    return int(sum(int(b) << i for i, b in enumerate(reg)))


def block_crcs(data: np.ndarray, block_bytes: int) -> list[int]:
    return [
        host.crc32c(0, data[i:i + block_bytes].tobytes())
        for i in range(0, data.size, block_bytes)
    ]


def test_the_selected_fold_is_the_native_one_where_it_loads():
    want = native.crc32c_fold if native.available() else host.fold_words_numpy
    assert host.fold_words is want


@pytest.mark.parametrize("fold", FOLDS)
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("blocks", [0, 1, 2, 127, 128, 1024])
@pytest.mark.parametrize("block_bytes", [4096, 65536])
def test_one_call_equals_the_loop_and_the_bytes(
    block_bytes, blocks, seed, fold
):
    rng = np.random.default_rng([block_bytes, blocks, len(seed)])
    init = SEEDS[seed]
    if init is None:
        init = int(rng.integers(0, 1 << 32))
    # bytes for one shard (crc32c over 64 MiB wants the native crc);
    # two more shards of words alone, so the call is over [3, blocks]
    with_bytes = native.available() or blocks * block_bytes <= 1 << 20
    words = rng.integers(0, 1 << 32, (3, blocks), dtype=np.uint32)
    if with_bytes:
        data = rng.integers(0, 256, blocks * block_bytes, dtype=np.uint8)
        words[0] = block_crcs(data, block_bytes)
    seeds = np.array([init, init ^ 1, 0x12345678], dtype=np.uint32)
    got = FOLDS[fold](zero_gap_columns(block_bytes), seeds, words)
    assert got.dtype == np.uint32 and got.shape == (3,)
    want = [
        chain_block_by_block(int(s), row, block_bytes)
        for s, row in zip(seeds, words)
    ]
    assert got.tolist() == want
    if with_bytes:
        assert want[0] == host.crc32c(init, data.tobytes())
    if fold == "selected":
        assert crc32c_fold(seeds, words, block_bytes).tolist() == want
        one = crc32c_fold([init], [words[0].tolist()], block_bytes)
        assert one.tolist() == want[:1]  # one stream, a list of words
    assert seeds.tolist() == [init, init ^ 1, 0x12345678]  # not written


@pytest.mark.parametrize("block_bytes", [4096, 65536])
@pytest.mark.parametrize("blocks", [0, 1, 2, 127, 128, 1024])
def test_twelve_shards_in_one_append_equal_twelve_appends(blocks, block_bytes):
    rng = np.random.default_rng([blocks, block_bytes])
    words = rng.integers(0, 1 << 32, (12, blocks), dtype=np.uint32)
    # an earlier append, so the seeds differ by shard
    first = rng.integers(0, 1 << 32, (12, 3), dtype=np.uint32)
    together, apart = HashInfo(12), HashInfo(12)
    for hi in (together, apart):
        rows = dict(enumerate(first))
        assert hi.append_block_csums(0, rows, block_bytes) == 36
    base = 3 * block_bytes
    folded = together.append_block_csums(
        base, {s: words[s] for s in range(12)}, block_bytes
    )
    assert folded == 12 * blocks
    for s in range(12):
        one = HashInfo(12)
        one.cumulative_shard_hashes[s] = apart.get_chunk_hash(s)
        one.total_chunk_size = base
        one.append_block_csums(base, {s: words[s]}, block_bytes)
        assert one.cumulative_shard_hashes[:s] == [SEED] * s
        assert together.get_chunk_hash(s) == one.get_chunk_hash(s)
        assert together.get_chunk_hash(s) == chain_block_by_block(
            apart.get_chunk_hash(s), words[s], block_bytes
        )
    assert together.get_total_chunk_size() == base + blocks * block_bytes
    # what is persisted is plain JSON, as before
    assert all(type(h) is int for h in together.cumulative_shard_hashes)
    assert HashInfo.from_bytes(together.to_bytes()) == together


@pytest.mark.parametrize("to_append", [
    {0: [1, 2], 1: [3]},
    {0: np.zeros(4, np.uint32), 5: np.zeros(3, np.uint32)},
    {0: [], 1: [7]},
])
def test_unequal_lengths_still_raise_and_change_nothing(to_append):
    hi = HashInfo(6)
    with pytest.raises(ValueError, match="unequal append sizes"):
        hi.append_block_csums(0, to_append, 4096)
    assert hi == HashInfo(6)


def test_an_append_of_nothing_folds_nothing():
    hi = HashInfo(3)
    assert hi.append_block_csums(0, {}, 4096) == 0
    assert hi.append_block_csums(0, {0: [], 2: []}, 4096) == 0
    assert hi == HashInfo(3)


# ------------------------------------------------------------- the wire
#: ``to_bytes`` of the transaction below as the per-word packing wrote
#: it (commit 01ef28a): v2, the csum words ``<I`` after csum_block and
#: their count
GOLDEN_V2 = bytes.fromhex(
    "020400000000030000006f626a00000000000000000000000000000000"
    "0000000000000000000000000000000001030000006f626a0010000000"
    "0000001000000000000000000000001000000079797979797979797979"
    "797979797979001000000300000001000000ffffffff783bf682010300"
    "00006f626a400000000000000005000000000000000000000005000000"
    "6279746573000000000000000005030000006f626a0000000000000000"
    "0000000000000000010000006101000000760000000000000000"
)
CSUMS = [1, 0xFFFFFFFF, 0x82F63B78]
AS = {
    "uint32 array": lambda: np.array(CSUMS, dtype=np.uint32),
    "array slice": lambda: np.array([9] + CSUMS + [9], dtype=np.uint32)[1:4],
    "int64 array": lambda: np.array(CSUMS, dtype=np.int64),
    "list": lambda: list(CSUMS),
    "tuple": lambda: tuple(CSUMS),
}


def golden_txn(csums) -> Transaction:
    return (
        Transaction().touch("obj")
        .write("obj", 4096, b"y" * 16, csums=csums, csum_block=4096)
        .write("obj", 64, b"bytes")
        .setattr("obj", "a", b"v")
    )


@pytest.mark.parametrize("held", AS)
def test_csum_words_reach_the_wire_as_the_golden_v2_payload(held):
    txn = golden_txn(AS[held]())
    assert txn.ops[1].csums == tuple(CSUMS)
    assert all(type(v) is int for v in txn.ops[1].csums)
    assert txn.to_bytes() == GOLDEN_V2
    back = Transaction.from_bytes(GOLDEN_V2)
    assert back.ops == txn.ops
    assert back.to_bytes() == GOLDEN_V2


#: tests/test_format_freeze.py's frozen v1 payload
GOLDEN_V1 = bytes.fromhex(
    "010400000001030000006f626a40000000000000000500000000000000"
    "0000000005000000627974657305030000006f626a0000000000000000"
    "00000000000000000100000061010000007603030000006f626a640000"
    "0000000000000000000000000000000000000000000404000000676f6e"
    "65000000000000000000000000000000000000000000000000"
)


def test_a_csum_free_transaction_stays_v1_byte_for_byte():
    txn = (
        Transaction()
        .write("obj", 64, b"bytes")
        .setattr("obj", "a", b"v")
        .truncate("obj", 100)
        .remove("gone")
    )
    assert txn.to_bytes() == GOLDEN_V1
    assert txn.to_bytes()[0] == 1


def test_a_word_that_is_no_uint32_is_refused():
    with pytest.raises((OverflowError, ValueError)):
        Transaction().write(
            "o", 0, b"x" * 4096, csums=[1 << 32], csum_block=4096
        )


# ------------------------------------------------- the pipeline's counters
@pytest.mark.parametrize("fused", [True, False], ids=["fused", "host-csums"])
def test_the_pipeline_counts_its_folds(fused):
    """Two appends of one stripe each through the fused route are two
    folds of (k+m) x chunk/4 KiB words; an overwrite clears the hashes
    and folds nothing, as do csums from the host's pass."""
    from ceph_tpu.codecs.registry import registry
    from ceph_tpu.pipeline.rmw import RMWPipeline, ShardBackend
    from ceph_tpu.pipeline.stripe import StripeInfo
    from ceph_tpu.store.memstore import MemStore
    from ceph_tpu.utils import config

    k, m, chunk = 4, 2, 8192
    with config.override(
        ec_fused_csum_interpret=fused, ec_host_dispatch_bytes=0
    ):
        pipe = RMWPipeline(
            StripeInfo(k, m, k * chunk),
            registry.factory("isa", {"k": str(k), "m": str(m)}),
            ShardBackend({i: MemStore() for i in range(k + m)}),
        )
        data = np.random.default_rng(39).integers(
            0, 256, k * chunk, np.uint8
        ).tobytes()
        pipe.submit("obj", 0, data)
        pipe.submit("obj", len(data), data)
        appended = pipe.perf.dump()
        assert pipe.hinfo("obj").get_total_chunk_size() == 2 * chunk
        pipe.submit("obj", 0, data)  # an overwrite
        after = pipe.perf.dump()
    if fused:
        assert appended["hinfo_folds"] == 2
        assert appended["hinfo_fold_blocks"] == 2 * (k + m) * (chunk // 4096)
        assert 0 < appended["hinfo_fold_seconds"] < appended["encode_seconds"]
    else:
        assert appended["hinfo_folds"] == 0
        assert appended["hinfo_fold_blocks"] == 0
        assert appended["hinfo_fold_seconds"] == 0
    for key in ("hinfo_folds", "hinfo_fold_blocks", "hinfo_fold_seconds"):
        assert after[key] == appended[key]
    assert after["encode_ops"] == 3
