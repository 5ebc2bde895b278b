"""What the accepted cells' generators send, op for op: the recorder
behind ``golden_traffic.json`` and the test that compares with it.

The generator's two threads make the order of a run depend on timing
(an object is busy until the reaper has seen its op), so the recorder
drives the same methods from one thread: issue while the window has
room, else reap the oldest completion. The io completes at once and
keeps, of every op, what went on the wire.

    PYTHONPATH=<a checkout> python3 benchmark/tests/golden.py golden_traffic.json

records from the generator of that checkout (PR 32 recorded from its
parent, 6a6c90d, before it touched the generator)."""

from __future__ import annotations

import hashlib
import json
import sys
import types

OPS = 200
SEEDS = (7, 3000000019)
#: mix, object size, depth, preloaded objects: one line for each pair
#: of mix and size that an accepted cell sends (the mesh cell sends
#: ``rs84-4m.write``'s)
CASES = {
    "rs84-4m.write": ("write", 4194304, 16, 0),
    "rs84-64k.write": ("write", 65536, 64, 0),
    "rs84-4m.degraded-read": ("degraded-read", 4194304, 16, 48),
    "rs84-rbd.randwrite": ("randwrite", 4194304, 32, 128),
}


class RecordingIo:
    """The client's async surface: every op completes at once, and its
    offset and the sha1 of its payload are kept. ``keep`` holds whole
    objects for the reads of a mix that has them."""

    def __init__(self, keep: bool) -> None:
        self.keep = keep
        self.objects: dict[str, bytes] = {}
        self.wire: list[tuple[str, int, str]] = []

    def _done(self, on_complete, op, offset, payload, **reply) -> None:
        sha = hashlib.sha1(payload).hexdigest()[:16] if payload else ""
        self.wire.append((op, offset, sha))
        on_complete(types.SimpleNamespace(
            error=None, reply=types.SimpleNamespace(**reply)
        ))

    def aio_write_full(self, oid, data, on_complete) -> None:
        if self.keep:
            self.objects[oid] = bytes(data)
        self._done(on_complete, "writefull", 0, data, size=len(data))

    def aio_write(self, oid, data, offset, on_complete) -> None:
        self._done(on_complete, "write", offset, data, size=0)

    def aio_read(self, oid, on_complete) -> None:
        self._done(on_complete, "read", 0, b"", data=self.objects[oid])


def drive(gen, ops: int) -> None:
    """``ops`` ops of ``gen`` from this thread, then everything reaped."""
    issued = 0
    while issued < ops:
        if gen._window.acquire(blocking=False):
            gen._issue_one()
            issued += 1
        else:
            gen._reap_one(gen._done_q.get_nowait())
    while gen.in_flight():
        gen._reap_one(gen._done_q.get_nowait())


def rows_of(gen, io: RecordingIo, start: int = 0) -> list[list]:
    return [
        [s.cls, s.kind, s.idx, s.nbytes, offset, sha, op]
        for s, (op, offset, sha) in zip(gen.samples, io.wire[start:])
    ]


def record_case(case: str, seed: int) -> dict:
    from benchmark import files
    from benchmark.traffic.generator import Generator

    mix, size, depth, preloaded = CASES[case]
    io = RecordingIo(keep=mix == "degraded-read")
    loader = None
    if preloaded:
        loader = Generator(io, files.mix("write"), size, depth, seed)
        drive(loader, preloaded)
    gen = Generator(io, files.mix(mix), size, depth, seed)
    if loader is not None:
        gen.adopt(loader)
    start = len(io.wire)
    drive(gen, OPS)
    assert all(s.ok for s in gen.samples)
    out = {"ops": rows_of(gen, io, start)}
    if loader is not None:
        # the preload's ops as one digest: 128 rows of 4 MiB writes
        # say no more than their hash
        out["preload_sha1"] = hashlib.sha1(
            json.dumps(rows_of(loader, io)).encode()
        ).hexdigest()
    return out


def record() -> dict:
    return {
        case: {str(seed): record_case(case, seed) for seed in SEEDS}
        for case in CASES
    }


if __name__ == "__main__":
    with open(sys.argv[1], "w", encoding="utf-8") as f:
        json.dump(record(), f, separators=(",", ":"))
        f.write("\n")
