"""The one generator against a store that cannot fail: the op kinds of
a mix without ``names`` (``write_new``, ``write_full``, ``write_patch``,
``read``; no cell sends ``write_full`` today, this keeps the code a later
cell needs, which may add only data) verify every read against the
seed's bytes, and two seeds do the same work in another order. Then the
laws of a mix with ``names`` (``read``, ``append``, ``delete``; PR 32)
against a dict as the store: lengths, existence and bytes after 5,000
ops are what the seed says, the class shares are exact in every block,
an append never targets a busy name, and a failed op retires its
name."""

import collections
import time
import types

import pytest

from benchmark import files
from benchmark.traffic import generator as G

from . import golden

SIZE = 8192
#: PR 23's ``mixed`` data file, which no cell ever sent (PR 32 rewrote
#: that file for the cell ``rs84-64k.mixed``)
REWRITE_MIX = {
    "rmw_max_len": 2048,
    "classes": [
        {"name": "read", "op": "read", "weight": 6},
        {"name": "rand_write", "op": "write_full", "weight": 2},
        {"name": "rmw_overwrite", "op": "write_patch", "weight": 2},
    ],
}
NAMED_MIX = {
    "names": 64,
    "append_len": 512,
    "classes": [
        {"name": "read", "op": "read", "weight": 4},
        {"name": "append", "op": "append", "weight": 4},
        {"name": "delete", "op": "delete", "weight": 2},
    ],
}


class DictIo:
    """The client's async surface over a dict."""

    def __init__(self) -> None:
        self.objects: dict[str, bytearray] = {}

    @staticmethod
    def _done(on_complete, **reply) -> None:
        on_complete(types.SimpleNamespace(
            error=None, reply=types.SimpleNamespace(**reply)
        ))

    def aio_write_full(self, oid, data, on_complete) -> None:
        self.objects[oid] = bytearray(data)
        self._done(on_complete, size=len(data))

    def aio_write(self, oid, data, offset, on_complete) -> None:
        obj = self.objects.setdefault(oid, bytearray())
        assert offset <= len(obj), "a write past the end would leave a hole"
        obj[offset : offset + len(data)] = data
        self._done(on_complete, size=len(obj))

    def aio_read(self, oid, on_complete) -> None:
        self._done(on_complete, data=bytes(self.objects[oid]))

    def aio_remove(self, oid, on_complete) -> None:
        del self.objects[oid]
        self._done(on_complete)


def drive(seed: int, ops: int):
    io = DictIo()
    loader = G.Generator(io, files.mix("write"), SIZE, 4, seed, limit=16)
    gen = G.Generator(io, REWRITE_MIX, SIZE, 4, seed, limit=ops)
    for g in (loader, gen):
        if g is gen:
            g.adopt(loader)
        g.start()
        deadline = time.monotonic() + 30
        while g.completed() < g.limit and time.monotonic() < deadline:
            time.sleep(0.01)
        g.close()
        assert g.completed() == g.limit
    return io, gen


@pytest.mark.parametrize("seed", [7, 3000000019])
def test_rewrite_mix_verifies_and_accounts_exactly_once(seed):
    io, gen = drive(seed, 200)
    assert gen.issued == gen.accounted == 200
    assert all(s.ok for s in gen.samples), [
        s.why for s in gen.samples if not s.ok
    ][:3]
    assert {s.kind for s in gen.samples} == {
        "read", "write_full", "write_patch"
    }
    # what the store holds is what the seed says, patch chain included
    for idx, st in gen.objects.items():
        want = G.expected_image(
            seed, idx, st.version, st.n_patches, SIZE, gen.max_patch
        )
        assert bytes(io.objects[gen.oid(idx)]) == want
    assert any(st.n_patches for st in gen.objects.values())
    assert any(st.version > 1 for st in gen.objects.values())


def test_every_seed_gives_the_same_work_in_another_order():
    kinds = []
    for seed in (11, 12):
        _io, gen = drive(seed, 200)  # 20 whole blocks of 6/2/2
        kinds.append([s.cls for s in gen.samples])
    a, b = (collections.Counter(k) for k in kinds)
    assert a == b == {"read": 120, "rand_write": 40, "rmw_overwrite": 40}
    assert kinds[0] != kinds[1]


def test_a_read_that_differs_from_the_seed_fails_the_op():
    io = DictIo()
    gen = G.Generator(io, files.mix("write"), SIZE, 1, 5, limit=1)
    gen.start()
    while gen.completed() < 1:
        time.sleep(0.01)
    gen.close()
    io.objects[gen.oid(0)][3] ^= 1
    reader = G.Generator(io, files.mix("degraded-read"), SIZE, 1, 5, limit=1)
    reader.adopt(gen)
    reader.start()
    while reader.completed() < 1:
        time.sleep(0.01)
    reader.close()
    assert not reader.samples[0].ok
    assert "differs" in reader.samples[0].why


# ------------------------------------------------- a mix with ``names``
class BusyWatchingIo(DictIo):
    """Fails the test's claim if an op reaches an object that already
    has one in flight (completions are held until ``golden.drive``
    reaps them, so ``busy`` is what the generator thinks)."""

    def __init__(self) -> None:
        super().__init__()
        self.in_flight: set[str] = set()
        self.collisions = 0

    def _enter(self, oid: str) -> None:
        self.collisions += oid in self.in_flight
        self.in_flight.add(oid)

    def aio_write(self, oid, data, offset, on_complete) -> None:
        self._enter(oid)
        super().aio_write(oid, data, offset, on_complete)

    def aio_read(self, oid, on_complete) -> None:
        self._enter(oid)
        super().aio_read(oid, on_complete)

    def aio_remove(self, oid, on_complete) -> None:
        self._enter(oid)
        super().aio_remove(oid, on_complete)


def drive_named(seed: int, ops: int, preloaded: int = 16, depth: int = 8):
    io = BusyWatchingIo()
    loader = G.Generator(io, files.mix("write"), SIZE, depth, seed)
    golden.drive(loader, preloaded)
    gen = G.Generator(io, NAMED_MIX, SIZE, depth, seed)
    gen.adopt(loader)
    io.in_flight.clear()
    reap = gen._reap_one

    def reaping(ctx):
        io.in_flight.discard(gen.oid(ctx["idx"]))
        reap(ctx)

    gen._reap_one = reaping
    golden.drive(gen, ops)
    return io, gen


@pytest.mark.parametrize("seed", [7, 3000000019])
def test_named_mix_after_5000_ops_the_store_is_what_the_seed_says(seed):
    io, gen = drive_named(seed, 5000)
    assert gen.issued == gen.accounted == 5000
    assert all(s.ok for s in gen.samples), [
        s.why for s in gen.samples if not s.ok
    ][:3]
    assert io.collisions == 0  # no op on a name that had one in flight
    assert set(gen.objects) == set(range(NAMED_MIX["names"]))
    live = {gen.oid(i) for i, st in gen.objects.items() if st.exists}
    assert set(io.objects) == live and 0 < len(live) < NAMED_MIX["names"]
    assert sorted(gen._live) == sorted(
        i for i, st in gen.objects.items() if st.exists
    )
    lengths = set()
    for idx, st in gen.objects.items():
        if not st.exists:
            continue
        stored = bytes(io.objects[gen.oid(idx)])
        assert len(stored) == st.length
        assert stored == gen.image(idx)
        lengths.add(st.length)
    assert len(lengths) > 3  # objects of different lengths side by side
    # a name that came back is another life with other bytes, and an
    # object that a preload wrote whole grew on end of that
    assert any(st.version > 1 for st in gen.objects.values())
    by_kind = collections.Counter(s.kind for s in gen.samples)
    assert set(by_kind) == {"read", "append", "delete"}
    assert all(s.nbytes == 0 for s in gen.samples if s.kind == "delete")
    assert all(
        s.nbytes == NAMED_MIX["append_len"]
        for s in gen.samples if s.kind == "append"
    )


def test_named_mix_class_shares_are_exact_in_every_block():
    _io, gen = drive_named(11, 1000)
    classes = [s.cls for s in gen.samples]
    for at in range(0, 1000, 10):
        assert collections.Counter(classes[at : at + 10]) == {
            "read": 4, "append": 4, "delete": 2
        }
    _io, other = drive_named(12, 1000)
    assert classes != [s.cls for s in other.samples]


def test_an_append_makes_an_absent_name_and_extends_one_that_exists():
    io = BusyWatchingIo()
    mix = dict(NAMED_MIX, names=1, classes=[NAMED_MIX["classes"][1]])
    gen = G.Generator(io, mix, SIZE, 1, 5)
    golden.drive(gen, 3)
    st = gen.objects[0]
    assert (st.version, st.n_appends, st.length) == (1, 3, 3 * 512)
    assert bytes(io.objects["bench-0"]) == b"".join(
        G.append_bytes(5, 0, 1, j, 512) for j in (1, 2, 3)
    )
    # deleted and made again: version 2, from offset 0, other bytes
    mix = dict(NAMED_MIX, names=1, classes=[
        {"name": "delete", "op": "delete", "weight": 1},
        {"name": "append", "op": "append", "weight": 1},
    ])
    again = G.Generator(io, mix, SIZE, 1, 5)
    again.objects, again._live = gen.objects, gen._live
    golden.drive(again, 40)
    kinds = [s.kind for s in again.samples]
    assert kinds.count("delete") >= 5 and all(s.ok for s in again.samples)
    assert st.version > 2
    if st.exists:
        assert bytes(io.objects["bench-0"]) == again.image(0)
        assert bytes(io.objects["bench-0"])[:512] == G.append_bytes(
            5, 0, st.version, 1, 512
        )
    else:
        assert "bench-0" not in io.objects


def test_a_failed_append_retires_the_name():
    class FailingIo(BusyWatchingIo):
        def aio_write(self, oid, data, offset, on_complete) -> None:
            if oid == "bench-1" and not self.failed:
                self.failed = True
                on_complete(types.SimpleNamespace(
                    error=IOError("injected"), reply=None
                ))
                return
            super().aio_write(oid, data, offset, on_complete)

    io = FailingIo()
    io.failed = False
    mix = dict(NAMED_MIX, names=4)
    gen = G.Generator(io, mix, SIZE, 2, 9)
    golden.drive(gen, 400)
    bad = [s for s in gen.samples if not s.ok]
    assert len(bad) == 1 and bad[0].kind == "append" and bad[0].idx == 1
    st = gen.objects[1]
    assert st.retired and not st.exists and not st.busy
    at = gen.samples.index(bad[0])
    assert all(s.idx != 1 for s in gen.samples[at + 1 :])
    assert gen.issued == gen.accounted == 400


@pytest.mark.parametrize("mix,word", [
    (dict(NAMED_MIX, classes=[
        {"name": "w", "op": "write_patch", "weight": 1}]), "names"),
    ({"classes": [{"name": "a", "op": "append", "weight": 1}]}, "names"),
    (dict(NAMED_MIX, append_len=0), "append_len"),
    (dict(NAMED_MIX, names=3), "in flight"),
    ({"classes": [{"name": "x", "op": "truncate", "weight": 1}]}, "unknown"),
])
def test_a_mix_the_generator_cannot_send_is_refused(mix, word):
    with pytest.raises(ValueError, match=word):
        G.Generator(DictIo(), mix, SIZE, 4, 1)


def test_the_threaded_loop_sends_the_named_mix_too():
    io = DictIo()
    gen = G.Generator(io, NAMED_MIX, SIZE, 4, 21, limit=600)
    gen.start()
    deadline = time.monotonic() + 30
    while gen.completed() < 600 and time.monotonic() < deadline:
        time.sleep(0.01)
    gen.close()
    assert gen.issued == gen.accounted == 600
    assert all(s.ok for s in gen.samples), [
        s.why for s in gen.samples if not s.ok
    ][:3]
    for idx, st in gen.objects.items():
        if st.exists:
            assert bytes(io.objects[gen.oid(idx)]) == gen.image(idx)
