"""The one generator against a store that cannot fail: every op kind a
mix may name (``write_new``, ``write_full``, ``write_patch``, ``read``)
is driven from the ``mixed`` data file, every read verifies against the
seed's bytes, and two seeds do the same work in another order. No cell
of ``BENCHMARK.json`` sends this mix today (PERF.md, Open questions);
this keeps the code a later cell needs, which may add only data."""

import collections
import time
import types

import pytest

from benchmark import files
from benchmark.traffic import generator as G

SIZE = 8192


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
        self.objects[oid][offset : offset + len(data)] = data
        self._done(on_complete, size=len(data))

    def aio_read(self, oid, on_complete) -> None:
        self._done(on_complete, data=bytes(self.objects[oid]))


def drive(seed: int, ops: int):
    io = DictIo()
    loader = G.Generator(io, files.mix("write"), SIZE, 4, seed, limit=16)
    gen = G.Generator(io, files.mix("mixed"), SIZE, 4, seed, limit=ops)
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
def test_mixed_mix_verifies_and_accounts_exactly_once(seed):
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
