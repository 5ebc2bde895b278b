"""One general closed-loop traffic generator, bounded by time.

A mix is a data file (``mixes/<name>.json``): op classes with weights,
the key law and the patch cap. The generator keeps ``depth`` ops in
flight through the client's async surface (``rados bench -t``) from one
issuer thread, verifies every read against the bytes the seed gives,
keeps an exact latency sample per op, and stops issuing on a deadline.
It never counts ops to decide when to stop.

Every seed gives the same work in another order: the class sequence is
the mix's weights laid out as a block (6 reads, 2 rewrites, 2 patches
for weights 6/2/2) and shuffled block by block, so two seeds differ in
order and in bytes, not in the share of each class.

Object contents are pure functions of (seed, object, version, patch
chain), so verification regenerates and remembers nothing. The
functions follow ``ceph_tpu/loadgen/spec.py`` (PERF.md, Open
questions, lists that original for a later PR to reconcile)."""

from __future__ import annotations

import dataclasses
import queue
import threading
import time

import numpy as np

#: op kinds a class may name; the class name is free (a degraded cell
#: calls its reads ``reconstruct_read``)
OP_KINDS = ("write_new", "write_full", "write_patch", "read")
WRITE_KINDS = frozenset({"write_new", "write_full", "write_patch"})


def _seed_words(seed: int) -> list[int]:
    """Any whole number as 32-bit words for ``SeedSequence``."""
    seed = abs(int(seed))
    return [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF]


def object_bytes(seed: int, idx: int, version: int, size: int) -> bytes:
    return np.random.default_rng(
        _seed_words(seed) + [idx, version]
    ).bytes(size)


def patch_bytes(
    seed: int, idx: int, version: int, patch_no: int, size: int,
    max_len: int,
) -> tuple[int, bytes]:
    rng = np.random.default_rng(
        _seed_words(seed) + [idx, version, patch_no, 0x9A7C]
    )
    ln = int(rng.integers(1, min(max_len, size) + 1))
    off = int(rng.integers(0, size - ln + 1))
    return off, rng.bytes(ln)


def expected_image(
    seed: int, idx: int, version: int, n_patches: int, size: int,
    max_len: int,
) -> bytes:
    img = bytearray(object_bytes(seed, idx, version, size))
    for p in range(1, n_patches + 1):
        off, payload = patch_bytes(seed, idx, version, p, size, max_len)
        img[off : off + len(payload)] = payload
    return bytes(img)


@dataclasses.dataclass
class ObjState:
    version: int = 1
    n_patches: int = 0
    exists: bool = False
    busy: bool = False


@dataclasses.dataclass
class Sample:
    """One op, exactly as it went."""

    cls: str
    kind: str
    idx: int
    nbytes: int
    t_submit: float
    t_done: float = 0.0
    ok: bool = False
    why: str = ""


class Generator:
    """Drive one mix against ``io`` (the client's IoCtx).

    ``start()`` begins issuing; ``open_window()`` marks the start of the
    measured window; ``stop_issuing()`` is the deadline; ``drain()``
    waits, bounded, for what is in flight. All clocks are
    ``time.perf_counter``."""

    def __init__(
        self, io, mix: dict, object_size: int, depth: int, seed: int,
        oid_prefix: str = "bench", limit: int | None = None,
    ) -> None:
        self.io = io
        self.object_size = int(object_size)
        self.depth = int(depth)
        self.seed = int(seed)
        self.oid_prefix = oid_prefix
        #: stop issuing after this many ops (a preload); the measured
        #: window never sets it
        self.limit = limit
        self.max_patch = int(mix.get("rmw_max_len", 2048))
        self.classes = []
        block: list[int] = []
        for i, c in enumerate(mix["classes"]):
            if c["op"] not in OP_KINDS:
                raise ValueError(
                    f"unknown op kind {c['op']!r} (know {OP_KINDS})"
                )
            weight = int(c["weight"])
            if weight < 1 or weight != c["weight"]:
                raise ValueError("class weights are whole numbers >= 1")
            self.classes.append((c["name"], c["op"]))
            block += [i] * weight
        self._block = np.array(block)
        self._rng = np.random.default_rng(_seed_words(seed) + [0x40B])
        self._order: list[int] = []
        self.objects: dict[int, ObjState] = {}
        self._live: list[int] = []
        self._next_idx = 0
        self._lock = threading.Lock()
        self._window = threading.Semaphore(self.depth)
        self._done_q: queue.Queue = queue.Queue()
        self._halt = threading.Event()
        self.samples: list[Sample] = []
        self.issued = 0
        self.accounted = 0
        self.window_t0: float | None = None
        self.window_t1: float | None = None
        self._threads: list[threading.Thread] = []

    # -- targets ---------------------------------------------------------
    def oid(self, idx: int) -> str:
        # no seed in the name: placement hashes the name, and every
        # seed has to load the PGs alike
        return f"{self.oid_prefix}-{idx}"

    def _next_class(self) -> int:
        if not self._order:
            self._order = list(self._rng.permutation(self._block))
        return int(self._order.pop())

    def _pick_existing(self) -> int | None:
        """A uniform draw over the objects that exist, stepping on from
        one that has an op in flight (its bytes are not known until
        that op lands)."""
        with self._lock:
            n = len(self._live)
            if not n:
                return None
            start = int(self._rng.integers(0, n))
            for off in range(n):
                idx = self._live[(start + off) % n]
                st = self.objects[idx]
                if st.exists and not st.busy:
                    st.busy = True
                    return idx
        return None

    def _new_object(self) -> int:
        with self._lock:
            idx = self._next_idx
            self._next_idx += 1
            self.objects[idx] = ObjState(busy=True)
            return idx

    # -- issue -----------------------------------------------------------
    def _issue_one(self) -> None:
        name, kind = self.classes[self._next_class()]
        idx = None if kind == "write_new" else self._pick_existing()
        if idx is None:
            # nothing to read or patch yet (or every object is busy):
            # a create keeps the loop closed at its depth
            kind, idx = "write_new", self._new_object()
        st = self.objects[idx]
        ctx: dict = {"idx": idx, "st": st}
        oid = self.oid(idx)
        if kind in ("write_new", "write_full"):
            if kind == "write_full":
                ctx["version"] = st.version + 1
            else:
                ctx["version"] = st.version
            data = object_bytes(
                self.seed, idx, ctx["version"], self.object_size
            )
            nbytes = len(data)
        elif kind == "write_patch":
            ctx["patch_no"] = st.n_patches + 1
            off, data = patch_bytes(
                self.seed, idx, st.version, ctx["patch_no"],
                self.object_size, self.max_patch,
            )
            nbytes = len(data)
        else:
            ctx["version"], ctx["n_patches"] = st.version, st.n_patches
            nbytes = self.object_size
        sample = Sample(name, kind, idx, nbytes, time.perf_counter())
        ctx["sample"] = sample
        with self._lock:
            self.issued += 1
            self.samples.append(sample)

        def done(comp, _ctx=ctx) -> None:
            _ctx["comp"] = comp
            self._done_q.put(_ctx)

        try:
            if kind in ("write_new", "write_full"):
                self.io.aio_write_full(oid, data, on_complete=done)
            elif kind == "write_patch":
                self.io.aio_write(oid, data, offset=off, on_complete=done)
            else:
                self.io.aio_read(oid, on_complete=done)
        except Exception as e:  # submission failed: the op is accounted
            self._finish(ctx, False, f"submit: {type(e).__name__}: {e}")

    def _issuer(self) -> None:
        while not self._halt.is_set():
            if not self._window.acquire(timeout=0.05):
                continue
            if self._halt.is_set() or (
                self.limit is not None and self.issued >= self.limit
            ):
                self._window.release()
                return
            self._issue_one()

    # -- reap ------------------------------------------------------------
    def _finish(self, ctx: dict, ok: bool, why: str = "") -> None:
        sample, st = ctx["sample"], ctx["st"]
        sample.t_done = time.perf_counter()
        sample.ok, sample.why = ok, why
        with self._lock:
            if not ok and sample.kind in WRITE_KINDS:
                # outcome unknown: no later op may verify against it
                st.exists = False
            st.busy = False
            self.accounted += 1
        self._window.release()

    def _reap_one(self, ctx: dict) -> None:
        comp, st, sample = ctx["comp"], ctx["st"], ctx["sample"]
        if comp.error is not None:
            self._finish(
                ctx, False, f"{type(comp.error).__name__}: {comp.error}"
            )
            return
        if sample.kind in ("write_new", "write_full"):
            if comp.reply.size != sample.nbytes:
                self._finish(ctx, False, f"short write {comp.reply.size}")
                return
            with self._lock:
                if sample.kind == "write_new":
                    self._live.append(sample.idx)
                st.version, st.n_patches = ctx["version"], 0
                st.exists = True
        elif sample.kind == "write_patch":
            with self._lock:
                st.n_patches = ctx["patch_no"]
        else:
            want = expected_image(
                self.seed, sample.idx, ctx["version"], ctx["n_patches"],
                self.object_size, self.max_patch,
            )
            if bytes(comp.reply.data) != want:
                self._finish(ctx, False, "read differs from the seed's bytes")
                return
        self._finish(ctx, True)

    def _reaper(self) -> None:
        while True:
            ctx = self._done_q.get()
            if ctx is None:
                return
            try:
                self._reap_one(ctx)
            except Exception as e:  # a dead reaper would wedge the loop
                self._finish(ctx, False, f"reap: {type(e).__name__}: {e}")

    # -- control ---------------------------------------------------------
    def adopt(self, loader: "Generator") -> None:
        """Go on with the objects a finished preload made (same seed,
        size and prefix, or verification would compare with bytes that
        were never written)."""
        if (loader.seed, loader.object_size, loader.oid_prefix) != (
            self.seed, self.object_size, self.oid_prefix
        ):
            raise ValueError("adopt needs equal seed, size and prefix")
        self.objects = loader.objects
        self._live = loader._live
        self._next_idx = loader._next_idx

    def start(self) -> None:
        self._threads = [
            threading.Thread(
                target=self._reaper, daemon=True, name="bench-reap"
            ),
            threading.Thread(
                target=self._issuer, daemon=True, name="bench-issue"
            ),
        ]
        for t in self._threads:
            t.start()

    def open_window(self) -> float:
        self.window_t0 = time.perf_counter()
        return self.window_t0

    def stop_issuing(self) -> float:
        self._halt.set()
        self.window_t1 = time.perf_counter()
        return self.window_t1

    def in_flight(self) -> int:
        with self._lock:
            return self.issued - self.accounted

    def completed(self) -> int:
        with self._lock:
            return self.accounted

    def drain(self, seconds: float) -> int:
        """Wait up to ``seconds`` for the ops in flight; returns how
        many are still out. The reaper stays up for stragglers."""
        deadline = time.monotonic() + seconds
        while self.in_flight() and time.monotonic() < deadline:
            time.sleep(0.02)
        return self.in_flight()

    def close(self) -> None:
        self._halt.set()
        self._done_q.put(None)

    # -- reduction -------------------------------------------------------
    def window_samples(self) -> tuple[list[Sample], list[Sample]]:
        """(issued in the window, completed in the window)."""
        t0, t1 = self.window_t0, self.window_t1
        with self._lock:
            samples = list(self.samples)
        issued = [s for s in samples if t0 <= s.t_submit < t1]
        completed = [s for s in samples if s.ok and t0 <= s.t_done < t1]
        return issued, completed
