"""The batched parity delta (PR 26): any number of ops' delta pages, each
with its raw column, as one codec call, against two oracles.

- (a) the codec's public per-op ``apply_delta`` on the host GF tables;
- (b) a plain reference that RE-ENCODES the whole patched stripe with
  GF(2^8) tables of its own (shift-and-reduce by 0x11d, none of the
  program's), which is what a chain of parity deltas has to equal.

Every comparison is bit-exact (limit 0), and a control shows the
comparison can fail: a delta applied to the wrong column, or one
coefficient of the matrix changed, is caught.

The ops follow the benchmark generator's law for ``rs84-rbd.randwrite``
(1-4,096 B at any byte offset), walk the program's own prepare / place
steps (``ShardExtentMap.delta_prepare`` / ``delta_place``) and, on the
device route, the Pallas kernel in the interpreter."""

import numpy as np
import pytest

from ceph_tpu.codecs import matrix_codec, registry
from ceph_tpu.pipeline import dispatcher
from ceph_tpu.pipeline.rmw import plan_write
from ceph_tpu.pipeline.shard_map import ShardExtentMap
from ceph_tpu.pipeline.stripe import StripeInfo
from ceph_tpu.utils import config, platform

PAGE = 4096
STRIPES = 4


# -- the plain reference: GF(2^8) by shift and reduce --------------------
def _gf_mul_table() -> np.ndarray:
    table = np.zeros((256, 256), dtype=np.uint8)
    for a in range(256):
        for b in range(256):
            x, y, acc = a, b, 0
            while y:
                if y & 1:
                    acc ^= x
                x <<= 1
                if x & 0x100:
                    x ^= 0x11D
                y >>= 1
            table[a, b] = acc
    return table


GF_MUL = _gf_mul_table()


def ref_parity(generator: np.ndarray, data: np.ndarray) -> np.ndarray:
    """[k, n] data shards -> [m, n] parity, by the plain tables."""
    k = data.shape[0]
    out = np.zeros((generator.shape[0] - k, data.shape[1]), np.uint8)
    for j in range(out.shape[0]):
        for c in range(k):
            out[j] ^= GF_MUL[generator[k + j, c]][data[c]]
    return out


def ref_shards(generator, image: bytes, k: int, chunk: int) -> np.ndarray:
    """The k+m shards of an object: striped data, re-encoded parity."""
    flat = np.frombuffer(image, np.uint8)
    flat = np.concatenate(
        [flat, np.zeros((-flat.size) % (k * chunk), np.uint8)]
    )
    data = flat.reshape(-1, k, chunk).transpose(1, 0, 2).reshape(k, -1)
    return np.concatenate([data, ref_parity(generator, data)])


# -- one op, the way the RMW pipeline walks it ---------------------------
def make(k, m, mapping=None, technique="reed_sol_van"):
    sinfo = StripeInfo(k, m, k * PAGE, mapping)
    codec = registry.factory(
        "jerasure", {"technique": technique, "k": str(k), "m": str(m)}
    )
    return sinfo, codec


def prepared_op(sinfo, codec, rng, offset, length):
    """An object, a patch of it, and the prepared delta: (new map,
    work, the patched object's reference shards, the plan)."""
    k = sinfo.k
    size = STRIPES * k * PAGE
    image = bytearray(rng.integers(0, 256, size, np.uint8).tobytes())
    old = ref_shards(codec.generator, bytes(image), k, PAGE)
    patch = rng.integers(0, 256, length, np.uint8).tobytes()
    image[offset : offset + length] = patch
    plan = plan_write(sinfo, codec.get_flags(), offset, length, size)
    old_map = ShardExtentMap(sinfo)
    # what a delta reads: the pages written and the parity over them
    # (with k = 2 or 3 the planner itself may prefer a full stripe)
    for shard, es in plan.to_write.items():
        raw = sinfo.get_raw_shard(shard)
        for s, e in es:
            old_map.insert(shard, s, old[raw, s:e])
    new_map = ShardExtentMap(sinfo)
    new_map.insert_ro_range(offset, patch)
    work = new_map.delta_prepare(codec, old_map)
    want = ref_shards(codec.generator, bytes(image), k, PAGE)
    return new_map, work, want, plan


def law(rng, k, col):
    """A patch by the generator's law that starts in raw column
    ``col``: 1-4,096 B at any byte of the chunk, so it may run on into
    the next column or the next stripe."""
    length = int(rng.integers(1, PAGE + 1))
    stripe = int(rng.integers(0, STRIPES))
    offset = (stripe * k + col) * PAGE + int(rng.integers(0, PAGE))
    return offset, min(length, STRIPES * k * PAGE - offset)


def parity_of(sinfo, new_map, want, work):
    """(parity in the map, the reference's) over the op's window."""
    k, m = sinfo.k, sinfo.m
    width = work.parity.shape[1]
    got = np.stack([
        new_map.get(sinfo.get_shard(k + j), work.lo, width)
        for j in range(m)
    ])
    return got, want[k:, work.lo : work.lo + width]


@pytest.fixture
def route(request, monkeypatch):
    """``device``: every batch through the Pallas kernel, interpreted
    (the TPU route, walked on the CPU); ``host``: the GF tables."""
    if request.param == "device":
        monkeypatch.setattr(platform, "on_tpu", lambda: True)
        monkeypatch.setattr(matrix_codec, "DELTA_HOST_UNITS", 0)
    else:
        monkeypatch.setattr(matrix_codec, "DELTA_HOST_UNITS", 1 << 30)
    return request.param


def _mapping(k, m):
    """Data shards reversed, parity first: no raw shard keeps its id."""
    return [m + k - 1 - r for r in range(k)] + list(range(m))


CODES = [
    pytest.param(2, 1, None, id="k2m1"),
    pytest.param(3, 2, None, id="k3m2"),
    pytest.param(4, 2, "mapped", id="k4m2-mapped"),
    pytest.param(6, 3, None, id="k6m3"),
    pytest.param(8, 4, None, id="k8m4"),
    pytest.param(8, 4, "mapped", id="k8m4-mapped"),
    pytest.param(10, 4, None, id="k10m4"),
]


@pytest.mark.parametrize("route", ["device", "host"], indirect=True)
@pytest.mark.parametrize("n_ops", [1, 5, 16])
@pytest.mark.parametrize("k,m,mapping", CODES)
def test_batch_equals_per_op_delta_and_whole_stripe_reencode(
    k, m, mapping, n_ops, route
):
    sinfo, codec = make(k, m, _mapping(k, m) if mapping else None)
    rng = np.random.default_rng([k, m, n_ops, 0xDE17A])
    # a small set of compiled sizes keeps the interpreter affordable;
    # 16 ops of two pages still overflow it, so the slicing runs too
    with config.override(osd_coalesce_max=8):
        ops = [
            prepared_op(sinfo, codec, rng, *law(rng, k, i % k))
            for i in range(max(n_ops, k if n_ops > 1 else 1))
        ]
        pc = dispatcher._stream_counters()
        before = pc.get("delta_batches"), pc.get("delta_batch_ops")
        contribs = dispatcher.delta_batch(
            codec, [(w.cols, w.pages, 1) for _, w, *_ in ops]
        )
    assert pc.get("delta_batch_ops") - before[1] == len(ops)
    units = sum(len(w.cols) for _, w, *_ in ops)
    assert pc.get("delta_batches") - before[0] == -(-units // 16)
    assert {int(c) for _, w, *_ in ops for c in w.cols} >= set(
        range(k) if n_ops > 1 else ()
    )
    for (new_map, work, want, plan), contrib in zip(ops, contribs):
        assert plan.do_parity_delta or k < 8
        assert 1 <= len(work.cols) <= 2 and work.windows is None
        # (a) the codec's own per-op apply_delta, host tables
        width = work.parity.shape[1]
        deltas = {}
        for u, col in enumerate(work.cols):
            d = deltas.setdefault(int(col), np.zeros(width, np.uint8))
            d[work.at[u] * PAGE : (work.at[u] + 1) * PAGE] = work.pages[u]
        per_op = codec.apply_delta(
            deltas, {k + j: work.parity[j].copy() for j in range(m)}
        )
        new_map.delta_place(work, contrib)
        got, ref = parity_of(sinfo, new_map, want, work)
        assert np.array_equal(
            got, np.stack([np.asarray(per_op[k + j]) for j in range(m)])
        )
        # (b) the plain reference's re-encode of the patched stripe
        assert np.array_equal(got, ref)


@pytest.mark.parametrize("route", ["device", "host"], indirect=True)
@pytest.mark.parametrize("broken", ["wrong_column", "one_coefficient"])
def test_control_a_wrong_delta_is_caught(broken, route):
    """The comparison above can fail: the same batch with one delta on
    the next column, or through a matrix with one coefficient
    changed, leaves parity that differs from the reference's."""
    k, m = 8, 4
    sinfo, codec = make(k, m)
    rng = np.random.default_rng(0xC0271)
    ops = [
        prepared_op(sinfo, codec, rng, *law(rng, k, i % k)) for i in range(6)
    ]
    members = [(w.cols.copy(), w.pages, 1) for _, w, *_ in ops]
    sound = dispatcher.delta_batch(codec, members)
    if broken == "wrong_column":
        members[3][0][0] = (members[3][0][0] + 1) % k
        bad = dispatcher.delta_batch(codec, members)
    else:
        generator = codec.generator.copy()
        generator[k + 1, 2] ^= 0x01
        other = registry.factory(
            "jerasure",
            {"technique": "reed_sol_van", "k": str(k), "m": str(m)},
        )
        other._set_generator(generator)
        bad = dispatcher.delta_batch(other, members)
    mismatched = 0
    for (new_map, work, want, *_), good, wrong in zip(ops, sound, bad):
        new_map.delta_place(work, wrong)
        got, ref = parity_of(sinfo, new_map, want, work)
        mismatched += not np.array_equal(got, ref)
    assert mismatched >= 1


# -- the shapes the device route can see ---------------------------------
def test_batch_sizes_are_the_fixed_set():
    assert matrix_codec.delta_batch_sizes() == (1, 2, 4, 8, 16, 32)
    with config.override(osd_coalesce_max=24):
        assert matrix_codec.delta_batch_sizes() == (1, 2, 4, 8, 16, 32, 64)


def test_every_batch_pads_to_the_set_and_warm_up_compiles_each(monkeypatch):
    """Whatever number of units a batch has, the device sees one of
    ``delta_batch_sizes()``; the first device batch compiles them all,
    and no later batch compiles anything."""
    import jax.monitoring

    monkeypatch.setattr(matrix_codec, "DELTA_HOST_UNITS", 0)
    compiled: list[str] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiled.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None
    )
    # a geometry no other test of this process has compiled
    sinfo, codec = make(5, 3)
    rng = np.random.default_rng(5)
    sizes = matrix_codec.delta_batch_sizes()
    shapes: list[int] = []
    real = matrix_codec.MatrixErasureCodec._dispatch_bitmatrix

    def spy(self, bmat_np, bmat_dev, stacked, op, nbytes=None):
        shapes.append(stacked.shape[0])
        return real(self, bmat_np, bmat_dev, stacked, op, nbytes=nbytes)

    monkeypatch.setattr(
        matrix_codec.MatrixErasureCodec, "_dispatch_bitmatrix", spy
    )
    pages = rng.integers(0, 256, (1, PAGE), np.uint8)
    codec.delta_contribs(np.zeros(1, np.uint8), pages)
    assert sorted(shapes[:-1]) == list(sizes)  # the warm-up, then the op
    assert len(compiled) >= len(sizes)
    del compiled[:], shapes[:]
    for n in range(1, sizes[-1] + 1):
        cols = rng.integers(0, 5, n).astype(np.uint8)
        out, sent = codec.delta_contribs(
            cols, rng.integers(0, 256, (n, PAGE), np.uint8)
        )
        assert out.shape == (n, 3, PAGE)
        assert sent == next(s for s in sizes if s >= n)
    assert set(shapes) == set(sizes)
    assert compiled == []
    # more units than the largest size: one dispatch per slice
    pc = dispatcher._stream_counters()
    before = pc.get("delta_batches"), pc.get("delta_pad_units")
    n = 2 * sizes[-1] + 3
    dispatcher.delta_batch(codec, [(
        rng.integers(0, 5, n).astype(np.uint8),
        rng.integers(0, 256, (n, PAGE), np.uint8), 1,
    )])
    assert pc.get("delta_batches") - before[0] == 3
    assert pc.get("delta_pad_units") - before[1] == 1
    assert compiled == []


def test_route_counts_real_delta_bytes_only(monkeypatch):
    monkeypatch.setattr(matrix_codec, "DELTA_HOST_UNITS", 2)
    _, codec = make(8, 4)
    pc = matrix_codec._dispatch_counters()
    rng = np.random.default_rng(9)

    def moved(n):
        keys = ("host_delta_bytes", "einsum_delta_bytes", "host_delta",
                "einsum_delta")
        before = {key: pc.get(key) for key in keys}
        codec.delta_contribs(
            rng.integers(0, 8, n).astype(np.uint8),
            rng.integers(0, 256, (n, PAGE), np.uint8),
        )
        return {key: pc.get(key) - before[key] for key in keys}

    codec.delta_contribs(  # the warm-up's dispatches, out of the way
        np.zeros(3, np.uint8), np.zeros((3, PAGE), np.uint8)
    )
    assert moved(2) == {
        "host_delta_bytes": 2 * PAGE, "einsum_delta_bytes": 0,
        "host_delta": 1, "einsum_delta": 0,
    }
    # three units pad to four of eight columns: 128 KiB cross to the
    # device, 12 KiB of them are delta
    assert moved(3) == {
        "host_delta_bytes": 0, "einsum_delta_bytes": 3 * PAGE,
        "host_delta": 0, "einsum_delta": 1,
    }


# -- the codecs that keep the per-op form --------------------------------
@pytest.mark.parametrize("technique", ["liberation", "cauchy_good"])
def test_other_codecs_go_through_the_same_three_steps(technique):
    """A packet-layout code (``PARITY_DELTA_CHUNK_GRANULARITY``) keeps
    whole windows and the per-op ``apply_delta``; a byte-matrix
    technique other than the benchmark's takes the unit form. Both end
    at the parity a full re-encode gives."""
    k, m = 4, 2
    profile = {"technique": technique, "k": str(k), "m": str(m)}
    if technique == "liberation":
        profile["w"] = "7"
    codec = registry.factory("jerasure", profile)
    chunk = codec.get_chunk_size(k * PAGE)
    sinfo = StripeInfo(k, m, k * chunk)
    rng = np.random.default_rng(0x7EC)
    size = 2 * k * chunk
    image = bytearray(rng.integers(0, 256, size, np.uint8).tobytes())

    def shards_of(img):
        full = ShardExtentMap(sinfo)
        full.insert_ro_range(0, bytes(img))
        full.encode(codec)
        return full

    old = shards_of(image)
    offset, length = chunk + 100, 700
    patch = rng.integers(0, 256, length, np.uint8).tobytes()
    image[offset : offset + length] = patch
    plan = plan_write(sinfo, codec.get_flags(), offset, length, size)
    assert plan.do_parity_delta
    old_map = ShardExtentMap(sinfo)
    for shard, es in plan.to_read.items():
        for s, e in es:
            old_map.insert(shard, s, old.get(shard, s, e - s))
    new_map = ShardExtentMap(sinfo)
    new_map.insert_ro_range(offset, patch)
    work = new_map.delta_prepare(codec, old_map)
    assert (work.windows is not None) == (technique == "liberation")
    new_map.encode_parity_delta(codec, old_map)
    want = shards_of(image)
    for j in range(m):
        shard = sinfo.get_shard(k + j)
        for s, e in new_map.get_extent_set(shard):
            assert np.array_equal(
                new_map.get(shard, s, e - s), want.get(shard, s, e - s)
            )
        assert new_map.get_extent_set(shard)


# -- plan_write on this traffic ------------------------------------------
def _law_cases():
    k = 8
    stripe = k * PAGE
    size = 4 << 20
    cases = {
        "one-byte": (12345, 1),
        "page-aligned-4k": (5 * PAGE, PAGE),
        "inside-a-page": (7 * PAGE + 100, 2000),
        "crosses-a-chunk": (3 * PAGE - 10, 300),
        "crosses-a-chunk-4k": (2 * PAGE + 1, PAGE),
        "crosses-a-stripe": (stripe - 1, 2),
        "crosses-a-stripe-4k": (9 * stripe - 2000, PAGE),
        "first-byte": (0, PAGE),
        "object-tail": (size - 1, 1),
        "object-tail-4k": (size - PAGE, PAGE),
        "object-tail-unaligned": (size - 3000, 3000),
    }
    rng = np.random.default_rng(0x1A3)
    for i in range(24):  # the law itself: benchmark patch_bytes
        ln = int(rng.integers(1, PAGE + 1))
        cases[f"drawn-{i}"] = (int(rng.integers(0, size - ln + 1)), ln)
    return [pytest.param(o, n, id=name) for name, (o, n) in cases.items()]


@pytest.mark.parametrize("offset,length", _law_cases())
def test_plan_write_on_the_randwrite_law(offset, length):
    """1-4,096 B at any byte of a 4 MiB EC(8,4) object: parity delta
    every time; the read set is the written pages and the parity
    windows over them, and never past the stored size."""
    k, m = 8, 4
    sinfo, codec = make(k, m)
    size = 4 << 20
    plan = plan_write(sinfo, codec.get_flags(), offset, length, size)
    assert plan.do_parity_delta
    written = sinfo.ro_range_to_shard_extent_set(offset, length)
    pages = {s: es.align(PAGE) for s, es in written.items()}
    assert 1 <= len(pages) <= 2
    lo = min(es.range_start() for es in pages.values())
    hi = max(es.range_end() for es in pages.values())
    assert hi - lo <= 2 * PAGE
    for shard, es in pages.items():
        assert list(plan.to_read[shard]) == list(es)
        assert list(plan.to_write[shard]) == list(es)
    parity_pages = set()
    for es in pages.values():
        parity_pages |= {p for s, e in es for p in range(s, e, PAGE)}
    for j in range(m):
        shard = sinfo.get_shard(k + j)
        got = {p for s, e in plan.to_read[shard] for p in range(s, e, PAGE)}
        assert got == parity_pages
        assert list(plan.to_write[shard]) == list(plan.to_read[shard])
    assert set(plan.to_read) == set(pages) | {
        sinfo.get_shard(k + j) for j in range(m)
    }
    for shard, es in plan.to_read.items():
        assert es.range_end() <= sinfo.object_size_to_shard_size(size, shard)
    assert plan.read_bytes() == len(parity_pages) * PAGE * m + sum(
        es.size() for es in pages.values()
    )


# -- a live cluster -------------------------------------------------------
def test_live_cluster_overwrites_batch_and_match_the_reference():
    """EC(4,2), 64 KiB objects, 200 seeded overwrites by the
    generator's law at depth 8: every stored shard equals the plain
    reference's encode of the final image, writes went by parity
    delta, and ticks really batched (more ops than dispatches)."""
    import threading

    from ceph_tpu.loadgen import LoadCluster
    from ceph_tpu.utils import perf_collection

    k, m, size, n_obj, depth, total = 4, 2, 65536, 16, 8, 200
    rng = np.random.default_rng(0x11FE)
    images = [
        bytearray(rng.integers(0, 256, size, np.uint8).tobytes())
        for _ in range(n_obj)
    ]

    def counters():
        out = {"parity_delta_ops": 0}
        for name, vals in perf_collection.dump().items():
            if name.endswith(".rmw"):
                out["parity_delta_ops"] += vals["parity_delta_ops"]
            if name == "ec_stream":
                out.update({
                    key: vals[key]
                    for key in ("delta_batches", "delta_batch_ops")
                })
        return out

    cluster = LoadCluster(
        n_osds=6, k=k, m=m, pg_num=4, chunk_size=PAGE, pool="deltapool",
    )
    try:
        for i, img in enumerate(images):
            cluster.io.write_full(f"d{i}", bytes(img))
        before = counters()
        lock = threading.Lock()
        free = list(range(n_obj))
        state = {"issued": 0, "done": 0, "errors": []}
        finished = threading.Event()

        def issue():
            with lock:
                if state["issued"] >= total or not free:
                    return
                state["issued"] += 1
                idx = free.pop(int(rng.integers(0, len(free))))
                ln = int(rng.integers(1, PAGE + 1))
                off = int(rng.integers(0, size - ln + 1))
                patch = rng.integers(0, 256, ln, np.uint8).tobytes()
                images[idx][off : off + ln] = patch
            cluster.io.aio_write(
                f"d{idx}", patch, offset=off,
                on_complete=lambda comp, i=idx: landed(comp, i),
            )

        def landed(comp, idx):
            with lock:
                if comp.error is not None:
                    state["errors"].append(repr(comp.error))
                free.append(idx)
                state["done"] += 1
                if state["done"] >= total:
                    finished.set()
            issue()

        for _ in range(depth):
            issue()
        assert finished.wait(240), state
        assert not state["errors"], state["errors"][:3]
        after = counters()
        generator = registry.factory(
            "jerasure",
            {"technique": "reed_sol_van", "k": str(k), "m": str(m)},
        ).generator
        compared = 0
        for i, img in enumerate(images):
            oid = f"d{i}"
            assert bytes(cluster.io.read(oid)) == bytes(img)
            want = ref_shards(generator, bytes(img), k, PAGE)
            acting = cluster.mon.osdmap.object_to_acting("deltapool", oid)
            for shard, osd in enumerate(acting):
                store = cluster.stores[osd]
                key = next(
                    key for key in store.list_objects()
                    if key.partition(":")[2] == f"{oid}#s{shard}"
                )
                got = np.frombuffer(store.read(key), np.uint8)
                assert np.array_equal(got, want[shard]), (oid, shard)
                compared += 1
        assert compared == n_obj * (k + m)
    finally:
        cluster.shutdown()
    # with k = 4 a patch that crosses a chunk is planned as a full
    # stripe; the rest, about half, go by delta
    by_delta = after["parity_delta_ops"] - before["parity_delta_ops"]
    batches = after["delta_batches"] - before["delta_batches"]
    ops = after["delta_batch_ops"] - before["delta_batch_ops"]
    assert by_delta >= total // 4
    assert ops == by_delta and 0 < batches < ops, (by_delta, batches, ops)
