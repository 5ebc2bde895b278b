"""``ShardExtentMap`` owns the buffers it is given and hands out views.

Until PR 29 every ``insert`` copied its argument, zero-filled a fresh
buffer and copied again; every ``get`` zero-filled and copied; ``encode``
stacked k ``get``s, the codec stacked them a second time, and
``_build_transactions`` copied each shard's bytes seven times. Those
forms live on here as the oracle (``OldMap``): the maps, the parity, the
kernel's checksums, HashInfo, the transactions and the stored shards of
the forms that replaced them are the same bytes.

The other half is what a map that shares buffers must never allow:
somebody writing to a buffer the map holds, or to a view it handed out.
"""

import contextlib
from unittest import mock

import jax
import numpy as np
import pytest

from ceph_tpu.codecs import registry
from ceph_tpu.pipeline import extent_cache as extent_cache_mod
from ceph_tpu.pipeline import rmw as rmw_mod
from ceph_tpu.pipeline.hashinfo import HashInfo
from ceph_tpu.pipeline.rmw import RMWPipeline, ShardBackend
from ceph_tpu.pipeline.shard_map import ShardExtentMap
from ceph_tpu.pipeline.stripe import StripeInfo
from ceph_tpu.store import MemStore
from ceph_tpu.store.transaction import OpKind
from ceph_tpu.utils import config
from ceph_tpu.utils.trace import tracer

MIB4 = 4 << 20


# -- the oracle: the copying forms as they were -------------------------
class OldMap(ShardExtentMap):
    """``insert`` / ``get`` / ``insert_ro_range`` / ``encode`` as PR 28
    left them: a copy in, a zero-fill and a copy on every placement, a
    zero-fill and a copy on every read, two stacks before the kernel."""

    def insert(self, shard, offset, data):
        arr = np.frombuffer(bytes(data), dtype=np.uint8).copy() \
            if isinstance(data, (bytes, bytearray, memoryview)) \
            else np.asarray(data, dtype=np.uint8).reshape(-1).copy()
        if arr.size == 0:
            return
        runs = self._bufs.setdefault(shard, [])
        new_start, new_end = offset, offset + arr.size
        merged_start, merged_end = new_start, new_end
        keep, overlapping = [], []
        for off, buf in runs:
            if off + buf.size < merged_start or off > merged_end:
                keep.append((off, buf))
            else:
                overlapping.append((off, buf))
                merged_start = min(merged_start, off)
                merged_end = max(merged_end, off + buf.size)
        out = np.zeros(merged_end - merged_start, dtype=np.uint8)
        for off, buf in overlapping:
            out[off - merged_start : off - merged_start + buf.size] = buf
        out[new_start - merged_start : new_end - merged_start] = arr
        keep.append((merged_start, out))
        keep.sort(key=lambda t: t[0])
        self._bufs[shard] = keep

    def get(self, shard, offset, length):
        out = np.zeros(length, dtype=np.uint8)
        for off, buf in self._bufs.get(shard, []):
            s = max(offset, off)
            e = min(offset + length, off + buf.size)
            if s < e:
                out[s - offset : e - offset] = buf[s - off : e - off]
        return out

    def insert_ro_range(self, ro_offset, data):
        data = np.frombuffer(data, dtype=np.uint8)
        for run in self.sinfo.ro_range_to_shard_runs(ro_offset, data.size):
            buf = np.empty(run.end - run.start, dtype=np.uint8)
            for src, dst in self._ro_pieces(data, buf, run):
                dst[...] = src
            self.insert(self.sinfo.get_shard(run.raw_shard), run.start, buf)

    def encode(self, codec, hashinfo=None, old_size=None, csum_block=None):
        k, m = self.sinfo.k, self.sinfo.m
        self.csums = None
        lo0, hi0 = self._slice_window()
        if hi0 <= lo0:
            return
        cs = self.sinfo.chunk_size
        lo = (lo0 // cs) * cs
        hi = -(-hi0 // cs) * cs
        n_chunks = (hi - lo) // cs
        data = np.stack([
            self.get(self.sinfo.get_shard(r), lo, hi - lo).reshape(
                n_chunks, cs
            )
            for r in range(k)
        ])
        parity = csums = None
        cb = csum_block
        if (
            cb and cs % cb == 0 and lo % cb == 0
            and hasattr(codec, "encode_chunks_with_csums")
        ):
            parity_map, csums = codec.encode_chunks_with_csums(
                {i: data[i] for i in range(k)}, cb
            )
            if parity_map is not None:
                parity = np.stack(
                    [np.asarray(parity_map[k + j]) for j in range(m)]
                )
                csums = np.asarray(csums)
        if parity is None:
            out = codec.encode_chunks(
                {i: np.asarray(data[i]) for i in range(k)}
            )
            parity = np.stack(
                [np.asarray(out[k + j]) for j in range(len(out))]
            )
        for j in range(m):
            self.insert(
                self.sinfo.get_shard(k + j), lo, parity[j].reshape(-1)
            )
        if csums is not None:
            arr = np.asarray(csums)
            self.csums = {
                "block": cb,
                "shards": {
                    self.sinfo.get_shard(raw): (
                        lo, np.ascontiguousarray(arr[:, raw, :]).reshape(-1)
                    )
                    for raw in range(k + m)
                },
            }
        if hashinfo is not None:
            base = lo0 if old_size is None else old_size
            if hi0 > base:
                if (
                    self.csums is not None
                    and base >= lo
                    and (base - lo) % cb == 0
                    and (hi0 - base) % cb == 0
                    and hi0 <= hi
                ):
                    first, last = (base - lo) // cb, (hi0 - lo) // cb
                    hashinfo.append_block_csums(
                        base,
                        {
                            shard: vals[first:last]
                            for shard, (_wlo, vals) in
                            self.csums["shards"].items()
                        },
                        cb,
                    )
                else:
                    hashinfo.append(
                        base,
                        {
                            self.sinfo.get_shard(raw): self.get(
                                self.sinfo.get_shard(raw), base, hi0 - base
                            )
                            for raw in range(k + m)
                        },
                    )


@contextlib.contextmanager
def the_old_map_everywhere():
    """The pipeline and its extent cache build ``OldMap``s."""
    with mock.patch.object(rmw_mod, "ShardExtentMap", OldMap), \
            mock.patch.object(extent_cache_mod, "ShardExtentMap", OldMap):
        yield


# -- helpers -------------------------------------------------------------
def _payload(length, seed=7):
    return np.random.default_rng(seed).integers(
        0, 256, length, dtype=np.uint8
    ).tobytes()


def _runs(smap):
    """{shard: [(offset, bytes)]}: the map's runs, structure and all."""
    return {
        shard: [(off, buf.tobytes()) for off, buf in runs]
        for shard, runs in smap._bufs.items()
    }


def _csums(smap):
    if smap.csums is None:
        return None
    return smap.csums["block"], {
        shard: (lo, vals.tolist())
        for shard, (lo, vals) in smap.csums["shards"].items()
    }


def _mapping(k, m):
    """Data shards reversed, parity first: no raw shard keeps its id."""
    return [m + k - 1 - r for r in range(k)] + list(range(m))


def _codec(k, m):
    return registry.factory(
        "jerasure", {"technique": "reed_sol_van", "k": str(k), "m": str(m)}
    )


# -- (a) the same bytes as the copying forms ----------------------------
#: name -> [(op, shard, offset, length)] on one shard space of 64 KiB
MAP_SCRIPTS = {
    "disjoint": [("ins", 0, 0, 100), ("ins", 0, 500, 100), ("ins", 1, 7, 9)],
    "abutting_after": [("ins", 0, 0, 4096), ("ins", 0, 4096, 4096)],
    "abutting_before": [("ins", 0, 4096, 4096), ("ins", 0, 0, 4096)],
    "overlap_tail": [("ins", 0, 0, 5000), ("ins", 0, 3000, 5000)],
    "overlap_head": [("ins", 0, 3000, 5000), ("ins", 0, 0, 5000)],
    "inside": [("ins", 0, 0, 9000), ("ins", 0, 1000, 10)],
    "covers_all": [("ins", 0, 100, 50), ("ins", 0, 300, 50),
                   ("ins", 0, 0, 1000)],
    "bridges_a_hole": [("ins", 0, 0, 100), ("ins", 0, 200, 100),
                       ("ins", 0, 100, 100)],
    "bridges_and_overlaps": [("ins", 0, 0, 150), ("ins", 0, 180, 100),
                             ("ins", 0, 400, 10), ("ins", 0, 90, 120)],
    "erase_middle_then_fill": [("ins", 0, 0, 8192), ("era", 0, 1000, 3000),
                               ("ins", 0, 2000, 500)],
    "erase_shard_then_insert": [("ins", 2, 0, 100), ("ers", 2, 0, 0),
                                ("ins", 2, 50, 100)],
    "empty_insert": [("ins", 0, 10, 0), ("ins", 0, 10, 5)],
}

#: how the caller hands the bytes over
MAKERS = {
    "bytes": lambda b: b,
    "bytearray": bytearray,
    "memoryview": memoryview,
    "owning_array": lambda b: np.frombuffer(b, np.uint8).copy(),
    "readonly_view": lambda b: np.frombuffer(b, np.uint8),
    "writable_view": lambda b: np.frombuffer(bytearray(b), np.uint8)[:],
    "list": list,
}

READS = [(0, 0, 65536), (0, 50, 100), (0, 2500, 1000), (0, 95, 10),
         (0, 4090, 12), (1, 0, 64), (2, 0, 200), (3, 0, 16)]


@pytest.mark.parametrize("make", MAKERS)
@pytest.mark.parametrize("script", MAP_SCRIPTS)
def test_insert_get_erase_match_the_copying_forms(script, make):
    sinfo = StripeInfo(4, 2, 4 * 4096)
    got, want = ShardExtentMap(sinfo), OldMap(sinfo)
    for step, (op, shard, offset, length) in enumerate(MAP_SCRIPTS[script]):
        for smap in (got, want):
            if op == "ins":
                smap.insert(
                    shard, offset, MAKERS[make](_payload(length, step))
                )
            elif op == "era":
                smap.erase(shard, offset, length)
            else:
                smap.erase_shard(shard)
        assert _runs(got) == _runs(want), f"after step {step}"
        for rshard, roff, rlen in READS:
            assert np.array_equal(
                got.get(rshard, roff, rlen), want.get(rshard, roff, rlen)
            )
    assert all(
        not buf.flags.writeable for runs in got._bufs.values()
        for _off, buf in runs
    )


#: name -> (k, m, chunk, mapped, [(ro_offset, length)] from (cs, sw))
ENCODES = {
    "full_stripe_4mib": (8, 4, 4096, False, lambda cs, sw: [(0, MIB4)]),
    "full_stripe_4mib_mapped": (
        8, 4, 4096, True, lambda cs, sw: [(0, MIB4)]),
    "one_stripe": (4, 2, 4096, False, lambda cs, sw: [(sw, sw)]),
    "whole_stripes_at_an_offset": (
        4, 2, 8192, False, lambda cs, sw: [(3 * sw, 5 * sw)]),
    "two_whole_ranges_abutting": (
        4, 2, 4096, False, lambda cs, sw: [(0, 2 * sw), (2 * sw, sw)]),
    "whole_then_overlapping_patch": (
        4, 2, 4096, False, lambda cs, sw: [(0, 3 * sw), (5000, 20000)]),
    "unaligned_head_and_tail": (
        4, 2, 4096, False, lambda cs, sw: [(cs + 37, 3 * sw - cs - 100)]),
    "a_hole_between": (
        4, 2, 4096, False, lambda cs, sw: [(0, cs), (2 * cs, cs + 9)]),
    "fewer_chunks_than_shards": (
        8, 4, 4096, False, lambda cs, sw: [(cs + 3, 2 * cs)]),
    "chunk_wider_than_a_page": (
        4, 2, 8192, False, lambda cs, sw: [(100, sw + 5000)]),
}


def _plain_and_fused(cases):
    """Every case on the plain route, and all but the 4 MiB ones (the
    interpreted kernel takes minutes there) through the fused kernel."""
    return [
        pytest.param(case, fused, id=f"{case}-{'fused' if fused else 'plain'}")
        for case in cases for fused in (False, True)
        if not (fused and "4mib" in case)
    ]


@pytest.mark.parametrize("case,fused", _plain_and_fused(ENCODES))
def test_scatter_and_encode_match_the_copying_forms(case, fused):
    k, m, cs, mapped, ranges = ENCODES[case]
    sinfo = StripeInfo(k, m, k * cs, _mapping(k, m) if mapped else None)
    codec = _codec(k, m)
    got, want = ShardExtentMap(sinfo), OldMap(sinfo)
    hi_got, hi_want = HashInfo(k + m), HashInfo(k + m)
    with config.override(
        ec_fused_csum_interpret=fused, ec_host_dispatch_bytes=0
    ):
        for seed, (ro_offset, length) in enumerate(ranges(cs, k * cs)):
            data = _payload(length, seed)
            got.insert_ro_range(ro_offset, data)
            want.insert_ro_range(ro_offset, data)
        assert _runs(got) == _runs(want)
        assert list(got._bufs) == list(want._bufs)  # first-touched order
        # a fresh HashInfo takes an append at shard offset 0 alone
        hashed = got.ro_range()[0] == 0
        got.encode(codec, hi_got if hashed else None, csum_block=4096)
        want.encode(codec, hi_want if hashed else None, csum_block=4096)
    assert _runs(got) == _runs(want)
    assert _csums(got) == _csums(want)
    if case == "two_whole_ranges_abutting":
        assert (_csums(got) is not None) == fused
    assert hi_got.to_bytes() == hi_want.to_bytes()
    lo, hi = got.ro_range()
    assert got.get_ro_range(lo * k, (hi - lo) * k) == want.get_ro_range(
        lo * k, (hi - lo) * k
    )


def test_whole_stripes_from_bytes_reach_the_codec_uncopied():
    """The kernel's [n, k, chunk] form of whole stripes is the caller's
    own ``bytes``; a buffer somebody may still write, or a map whose
    runs are no longer those rows, takes the strided copy."""
    sinfo = StripeInfo(4, 2, 4 * 4096)
    data = _payload(3 * sinfo.stripe_width)
    smap = ShardExtentMap(sinfo)
    smap.insert_ro_range(0, data)
    stripes = smap._stripe_major(0, 3)
    assert np.shares_memory(stripes, np.frombuffer(data, np.uint8))
    for buffer in (bytearray(data), np.frombuffer(data, np.uint8).copy()):
        other = ShardExtentMap(sinfo)
        other.insert_ro_range(0, buffer)
        assert not np.shares_memory(
            other._stripe_major(0, 3), np.frombuffer(buffer, np.uint8)
        )
        assert np.array_equal(other._stripe_major(0, 3), stripes)
    # the map moves on: a patch, an erase, a shard swapped for another
    patched = ShardExtentMap(sinfo)
    patched.insert_ro_range(0, data)
    patched.insert(sinfo.get_shard(1), 100, b"\xff" * 10)
    assert patched._stripe_major(0, 3)[0, 1, 100] == 0xFF
    erased = ShardExtentMap(sinfo)
    erased.insert_ro_range(0, data)
    erased.erase(sinfo.get_shard(2), 0, 4096)
    assert not erased._stripe_major(0, 3)[0, 2].any()
    swapped = ShardExtentMap(sinfo)
    swapped.insert_ro_range(0, data)
    row = swapped.get(sinfo.get_shard(0), 0, 3 * 4096)
    swapped.erase_shard(sinfo.get_shard(3))
    swapped.insert(sinfo.get_shard(3), 0, row)
    assert np.array_equal(
        swapped._stripe_major(0, 3)[:, 3], stripes[:, 0]
    )


# -- the pipeline: transactions, HashInfo, stored shards -----------------
class RecordingBackend(ShardBackend):
    def __init__(self, stores):
        super().__init__(stores)
        self.txns = []

    def submit_shard_txn(self, shard, txn, ack):
        self.txns.append((shard, list(txn.ops)))
        return super().submit_shard_txn(shard, txn, ack)


#: name -> (k, m, chunk, [(ro_offset, length)] from (cs, sw))
WRITES = {
    "writefull_4mib": (8, 4, 4096, lambda cs, sw: [(0, MIB4)]),
    "appends_of_whole_stripes": (
        4, 2, 8192, lambda cs, sw: [(0, 2 * sw), (2 * sw, sw), (3 * sw, sw)]),
    "overwrite_the_whole_object": (
        4, 2, 4096, lambda cs, sw: [(0, 2 * sw), (0, 2 * sw)]),
    "rmw_merges": (
        4, 2, 4096,
        lambda cs, sw: [(0, 3 * sw), (5000, 20000), (3 * sw, sw),
                        (sw - 7, 14), (100, 1), (2 * cs, cs)]),
    "holes_and_a_sparse_tail": (
        4, 2, 4096,
        lambda cs, sw: [(cs + 3, 100), (6 * sw + 100, 3000), (3 * sw, 10)]),
    "small_overwrites_of_one_page": (
        8, 4, 4096,
        lambda cs, sw: [(0, 2 * sw), (4096 * 3 + 17, 2000), (4096 * 3, 4096),
                        (sw + 4000, 200)]),
}


def _serve(case, fused, old):
    k, m, cs, writes = WRITES[case]
    sinfo = StripeInfo(k, m, k * cs)
    stores = {s: MemStore(f"osd.{s}") for s in range(k + m)}
    backend = RecordingBackend(stores)
    seen = []
    with contextlib.ExitStack() as stack:
        stack.enter_context(config.override(
            ec_fused_csum_interpret=fused, ec_host_dispatch_bytes=0
        ))
        if old:
            stack.enter_context(the_old_map_everywhere())
        pipe = RMWPipeline(sinfo, _codec(k, m), backend)
        for seed, (ro_offset, length) in enumerate(writes(cs, k * cs)):
            done = []
            pipe.submit(
                "obj", ro_offset, _payload(length, seed),
                on_commit=done.append,
            )
            (op,) = done
            assert op.error is None
            hinfo = pipe.hinfo("obj")
            seen.append({
                "txns": backend.txns[:],
                "written": _runs(op.written),
                "hinfo": None if hinfo is None else hinfo.to_bytes(),
                "delta": op.plan.do_parity_delta,
                "stored": {
                    s: st.read("obj") for s, st in stores.items()
                    if st.exists("obj")
                },
            })
            del backend.txns[:]
    return seen


@pytest.mark.parametrize("case,fused", _plain_and_fused(WRITES))
def test_served_writes_match_the_copying_forms(case, fused):
    """Each write of a sequence on one object: the sub-write
    transactions (bytes, offsets, kernel csums, attributes), what the
    op publishes to the extent cache, HashInfo and every store's shard,
    new forms against old."""
    got = _serve(case, fused, old=False)
    want = _serve(case, fused, old=True)
    assert len(got) == len(want)
    for step, (g, w) in enumerate(zip(got, want)):
        for key in w:
            assert g[key] == w[key], f"write {step}: {key}"
    if fused:
        assert any(
            op.csums is not None
            for g in got for _shard, ops in g["txns"] for op in ops
        )
    if case in ("rmw_merges", "small_overwrites_of_one_page"):
        assert any(g["delta"] for g in got)


# -- (b) nobody writes to what the map holds -----------------------------
@pytest.fixture
def smap():
    return ShardExtentMap(StripeInfo(4, 2, 4 * 4096))


def test_an_array_handed_over_turns_read_only(smap):
    mine = np.arange(256, dtype=np.uint8)
    smap.insert(0, 0, mine)
    assert np.shares_memory(smap.get(0, 0, 256), mine)  # taken, not copied
    with pytest.raises(ValueError):
        mine[0] = 99
    assert smap.get(0, 0, 256).tolist() == list(range(256))


@pytest.mark.parametrize(
    "make",
    [
        lambda b: (b, b[16:48]),
        lambda b: (b, memoryview(b)[16:48]),
        lambda b: (lambda a: (a, a[16:48]))(np.frombuffer(b, np.uint8)),
        lambda b: (lambda a: (a, a.reshape(8, 8)[2:6]))(
            np.frombuffer(b, np.uint8)),
    ],
    ids=["bytearray", "memoryview", "view_of_an_array", "2d_view"],
)
def test_a_buffer_the_caller_can_still_write_is_copied(smap, make):
    held, given = make(bytearray(range(64)))
    smap.insert(0, 1000, given)
    held[20] = 0xEE
    held[40] = 0xEE
    assert smap.get(0, 1000, 32).tolist() == list(range(16, 48))


@pytest.mark.parametrize("hole", [False, True], ids=["one_run", "a_hole"])
def test_what_get_returns_cannot_be_written(smap, hole):
    smap.insert(0, 0, _payload(100))
    smap.insert(0, 200, _payload(100, 1))
    offset, length = (50, 200) if hole else (10, 80)
    view = smap.get(0, offset, length)
    assert (view.base is None) == hole
    before = view.copy()
    with pytest.raises(ValueError):
        view[0] ^= 0xFF
    assert np.array_equal(smap.get(0, offset, length), before)


@pytest.mark.parametrize(
    "offset,length",
    [(50, 20), (0, 100), (90, 50), (100, 10), (0, 400)],
    ids=["inside", "covering", "overlap_tail", "abutting", "swallowing"],
)
def test_a_later_insert_leaves_an_earlier_view_as_it_was(
    smap, offset, length
):
    first = _payload(100)
    smap.insert(0, 0, first)
    view = smap.get(0, 0, 100)
    smap.insert(0, offset, b"\xaa" * length)
    assert view.tobytes() == first
    expect = bytearray(first.ljust(max(100, offset + length), b"\0"))
    expect[offset : offset + length] = b"\xaa" * length
    assert smap.get(0, 0, len(expect)).tobytes() == bytes(expect)
    smap.erase(0, 10, 50)
    assert view.tobytes() == first


def test_maps_that_share_a_buffer_do_not_see_each_others_inserts(smap):
    """The extent cache publishes an op's ``written`` map and snapshots
    its own for the next op: views of one buffer in three maps."""
    written = ShardExtentMap(smap.sinfo)
    written.insert(0, 0, _payload(8192))
    cache, snapshot = smap, ShardExtentMap(smap.sinfo)
    cache.insert(0, 0, written.get(0, 0, 8192))
    snapshot.insert(0, 4096, cache.get(0, 4096, 4096))
    assert np.shares_memory(snapshot.get(0, 4096, 1), written.get(0, 4096, 1))
    cache.insert(0, 4096, b"\x11" * 4096)  # the next op's pages land
    cache.erase(0, 0, 4096)  # and a line is evicted
    assert written.get(0, 0, 8192).tobytes() == _payload(8192)
    assert snapshot.get(0, 4096, 4096).tobytes() == _payload(8192)[4096:]
    assert cache.get(0, 4096, 4096).tobytes() == b"\x11" * 4096
    assert not cache.get(0, 0, 4096).any()


def test_a_caller_writing_to_its_buffer_after_the_scatter_changes_nothing():
    sinfo = StripeInfo(4, 2, 4 * 4096)
    data = _payload(2 * sinfo.stripe_width)
    codec = _codec(4, 2)
    want = ShardExtentMap(sinfo)
    want.insert_ro_range(0, data)
    want.encode(codec)
    for make in (bytearray, lambda b: np.frombuffer(b, np.uint8).copy()):
        buffer = make(data)
        got = ShardExtentMap(sinfo)
        got.insert_ro_range(0, buffer)
        buffer[:1000] = b"\0" * 1000 if isinstance(buffer, bytearray) else 0
        got.encode(codec)
        assert _runs(got) == _runs(want)


def test_encode_leaves_the_data_runs_alone_and_its_parity_read_only():
    sinfo = StripeInfo(4, 2, 4 * 4096)
    smap = ShardExtentMap(sinfo)
    smap.insert_ro_range(0, _payload(2 * sinfo.stripe_width))
    before = {s: smap._bufs[s][0][1] for s in smap.shards()}
    smap.encode(_codec(4, 2))
    for shard, buf in before.items():
        assert smap._bufs[shard][0][1] is buf
    for shard in smap.shards():
        with pytest.raises(ValueError):
            smap._bufs[shard][0][1][0] = 1


def test_the_delta_path_xors_into_its_own_copy_of_the_old_parity():
    """``delta_place`` writes in place: into the stack that
    ``delta_prepare`` made, never into the old map's runs (which the
    extent cache still holds)."""
    sinfo = StripeInfo(4, 2, 4 * 4096)
    codec = _codec(4, 2)
    old = ShardExtentMap(sinfo)
    old.insert_ro_range(0, _payload(sinfo.stripe_width))
    old.encode(codec)
    kept = _runs(old)
    new = ShardExtentMap(sinfo)
    new.insert_ro_range(4096 + 100, _payload(900, 3))
    new.encode_parity_delta(codec, old)
    assert _runs(old) == kept
    full = ShardExtentMap(sinfo)
    full.insert_ro_range(0, _payload(sinfo.stripe_width))
    full.insert_ro_range(4096 + 100, _payload(900, 3))
    full.encode(codec)
    for j in range(2):
        shard = sinfo.get_shard(4 + j)
        assert np.array_equal(
            new.get(shard, 0, 4096), full.get(shard, 0, 4096)
        )


# -- (c) the copies of one full-stripe write, counted --------------------
def _open_spans():
    return {sp.name for sp in tracer._stack()}


@pytest.fixture
def allocations(monkeypatch):
    """(bytes, open spans) of every array numpy is asked for through
    ``np.empty`` / ``np.zeros`` / ``np.stack`` while the test runs."""
    made = []

    def counting(fn):
        def wrapper(*args, **kwargs):
            out = fn(*args, **kwargs)
            made.append((out.nbytes, _open_spans()))
            return out
        return wrapper

    for name in ("empty", "zeros", "stack"):
        monkeypatch.setattr(np, name, counting(getattr(np, name)))
    monkeypatch.setattr(tracer, "enabled", True)
    return made


def test_a_full_stripe_write_copies_its_bytes_in_once(allocations):
    """4 MiB through the served write: one array of object size in
    assemble (the scatter), none in the codec's prep, nothing of a
    shard's size in txn_build, whose payloads are read-only views of
    the very runs that ``written`` keeps."""
    k, m, cs = 8, 4, 4096
    sinfo = StripeInfo(k, m, k * cs)
    backend = RecordingBackend(
        {s: MemStore(f"osd.{s}") for s in range(k + m)}
    )
    pipe = RMWPipeline(sinfo, _codec(k, m), backend)
    done = []
    with config.override(ec_host_dispatch_bytes=0):
        pipe.submit("obj", 0, _payload(MIB4), on_commit=done.append)
    (op,) = done
    shard_bytes = MIB4 // k

    def count(span, at_least):
        return sum(
            1 for nbytes, spans in allocations
            if span in spans and nbytes >= at_least
        )

    assert count("ec_write.assemble", shard_bytes) == 1
    assert count("ec_write.assemble", MIB4) == 1
    assert count("codec.prep", shard_bytes) == 0
    assert count("codec.fetch", shard_bytes) == 1  # [m, n, chunk], once
    assert count("ec_write.txn_build", shard_bytes) == 0
    assert len(backend.txns) == k + m
    for shard, ops in backend.txns:
        (write,) = [o for o in ops if o.kind is OpKind.WRITE]
        ((off, run),) = op.written._bufs[shard]
        assert off == 0 and write.data.readonly
        payload = np.frombuffer(write.data, np.uint8)
        assert payload.size == run.size and (
            payload.ctypes.data == run.ctypes.data  # no copy a shard
        )


def test_the_codec_is_handed_the_callers_bytes(monkeypatch):
    """What ``encode`` passes to the stacked entry for a full-stripe
    write is the client's buffer itself, and the parity comes back in
    one fetch."""
    k, m, cs = 8, 4, 4096
    sinfo = StripeInfo(k, m, k * cs)
    codec = _codec(k, m)
    data = _payload(16 * k * cs)
    handed, fetched = [], []
    entry = type(codec).encode_stacked

    def spy(self, stacked):
        handed.append(stacked)
        return entry(self, stacked)

    monkeypatch.setattr(type(codec), "encode_stacked", spy)
    monkeypatch.setattr(tracer, "enabled", True)
    asarray = np.asarray

    def counting_asarray(a, *args, **kwargs):
        if isinstance(a, jax.Array):
            fetched.append(_open_spans())
        return asarray(a, *args, **kwargs)

    monkeypatch.setattr(np, "asarray", counting_asarray)
    smap = ShardExtentMap(sinfo)
    smap.insert_ro_range(0, data)
    with config.override(ec_host_dispatch_bytes=0):
        smap.encode(codec)
    (stacked,) = handed
    assert stacked.shape == (16, k, cs)
    assert np.shares_memory(stacked, np.frombuffer(data, np.uint8))
    assert len(fetched) == 1 and "codec.fetch" in fetched[0]
