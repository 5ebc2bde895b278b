"""One general closed-loop traffic generator, bounded by time.

A mix is a data file (``mixes/<name>.json``): op classes with weights,
the key law, the patch cap and, where objects have a life, the set of
names and the length of an append. The generator keeps ``depth`` ops in
flight through the client's async surface (``rados bench -t``) from one
issuer thread, verifies every read against the bytes the seed gives,
keeps an exact latency sample per op, and stops issuing on a deadline.
It never counts ops to decide when to stop.

Every seed gives the same work in another order: the class sequence is
the mix's weights laid out as a block (6 reads, 2 rewrites, 2 patches
for weights 6/2/2) and shuffled block by block, so two seeds differ in
order and in bytes, not in the share of each class.

Object contents are pure functions of (seed, object, version, patch
chain, appends), so verification regenerates and remembers nothing.

A mix with ``names`` (a whole number n) sends ``read``, ``append`` and
``delete`` over the names ``bench-0`` ... ``bench-(n-1)``: an append
draws from the whole set and creates a name that is absent, a delete
draws from the names that exist, and a name that was deleted comes back
as a new ``version`` with other bytes. A mix without it is the first
benchmark's: objects are made by ``write_new`` and never go away. The
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
OP_KINDS = (
    "write_new", "write_full", "write_patch", "read", "append", "delete",
)
#: a failed op of these leaves an object no later op verifies against
WRITE_KINDS = frozenset(OP_KINDS) - {"read"}
#: what a mix with ``names`` may send, and what a mix without: a
#: patch's law is over one fixed size, and ``write_new`` and
#: ``write_full`` write an object of the configuration's size
NAMED_KINDS = frozenset({"read", "append", "delete"})
UNNAMED_KINDS = frozenset({"write_new", "write_full", "write_patch", "read"})


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


def append_bytes(
    seed: int, idx: int, version: int, append_no: int, size: int
) -> bytes:
    """Append number ``append_no`` (from 1) of life ``version``."""
    return np.random.default_rng(
        _seed_words(seed) + [idx, version, append_no, 0xA99E]
    ).bytes(size)


def expected_image(
    seed: int, idx: int, version: int, n_patches: int, size: int,
    max_len: int, n_appends: int = 0, append_len: int = 0,
) -> bytes:
    """The object as first written at ``size`` bytes (0 for a name that
    an append made), its patches, then its appends on end."""
    img = bytearray(object_bytes(seed, idx, version, size))
    for p in range(1, n_patches + 1):
        off, payload = patch_bytes(seed, idx, version, p, size, max_len)
        img[off : off + len(payload)] = payload
    for a in range(1, n_appends + 1):
        img += append_bytes(seed, idx, version, a, append_len)
    return bytes(img)


@dataclasses.dataclass
class ObjState:
    version: int = 1
    n_patches: int = 0
    exists: bool = False
    busy: bool = False
    #: bytes the object holds now; ``n_appends`` of ``append_len`` each
    #: on end of what it was first written with
    length: int = 0
    n_appends: int = 0
    #: an op on it failed: what the store holds is not known, so no
    #: later op reads it, and no append makes the name again
    retired: bool = False


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
        #: names 0 ... names-1 are all the objects there will ever be
        #: (0: objects are made by ``write_new`` and counted up)
        self.names = int(mix.get("names", 0))
        self.append_len = int(mix.get("append_len", 0))
        self.classes = []
        block: list[int] = []
        for i, c in enumerate(mix["classes"]):
            if c["op"] not in OP_KINDS:
                raise ValueError(
                    f"unknown op kind {c['op']!r} (know {OP_KINDS})"
                )
            if c["op"] not in (NAMED_KINDS if self.names else UNNAMED_KINDS):
                raise ValueError(
                    f"op kind {c['op']!r}: a mix with `names` sends "
                    f"{sorted(NAMED_KINDS)}, one without "
                    f"{sorted(UNNAMED_KINDS)}"
                )
            weight = int(c["weight"])
            if weight < 1 or weight != c["weight"]:
                raise ValueError("class weights are whole numbers >= 1")
            self.classes.append((c["name"], c["op"]))
            block += [i] * weight
        if self.names and self.append_len < 1:
            raise ValueError("a mix with `names` gives `append_len` >= 1")
        if self.names and self.names < self.depth:
            # an op holds its name busy, and an append needs an idle one
            raise ValueError(
                f"{self.names} names cannot keep {self.depth} ops in flight"
            )
        self._block = np.array(block)
        self._rng = np.random.default_rng(_seed_words(seed) + [0x40B])
        self._order: list[int] = []
        self.objects: dict[int, ObjState] = {
            idx: ObjState(version=0) for idx in range(self.names)
        }
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

    def image(self, idx: int) -> bytes:
        """What object ``idx`` holds now, by the seed."""
        st = self.objects[idx]
        return expected_image(
            self.seed, idx, st.version, st.n_patches,
            st.length - st.n_appends * self.append_len, self.max_patch,
            st.n_appends, self.append_len,
        )

    def append_only(self, idx: int) -> bool:
        """Whether object ``idx`` is as created but for appends: never
        patched, and never rewritten (in a mix with ``names`` a new
        ``version`` is a new life, in one without a ``write_full``)."""
        st = self.objects[idx]
        return st.n_patches == 0 and (bool(self.names) or st.version == 1)

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

    def _pick_name(self) -> int:
        """A uniform draw over the whole set of names, absent ones
        too, stepping on from a busy one as ``_pick_existing`` does."""
        with self._lock:
            start = int(self._rng.integers(0, self.names))
            for off in range(self.names):
                idx = (start + off) % self.names
                st = self.objects[idx]
                if not st.busy and not st.retired:
                    st.busy = True
                    return idx
        raise RuntimeError(
            f"all {self.names} names are busy or retired at depth "
            f"{self.depth}: the mix needs more names"
        )

    def _new_object(self) -> int:
        with self._lock:
            idx = self._next_idx
            self._next_idx += 1
            self.objects[idx] = ObjState(busy=True)
            return idx

    # -- issue -----------------------------------------------------------
    def _issue_one(self) -> None:
        name, kind = self.classes[self._next_class()]
        if kind == "append":
            idx = self._pick_name()
        else:
            idx = None if kind == "write_new" else self._pick_existing()
        if idx is None:
            # nothing to read, patch or delete yet (or every object is
            # busy): a create keeps the loop closed at its depth
            if self.names:
                kind, idx = "append", self._pick_name()
            else:
                kind, idx = "write_new", self._new_object()
        st = self.objects[idx]
        ctx: dict = {"idx": idx, "st": st}
        oid = self.oid(idx)
        if kind == "append":
            # an absent name starts a new life at offset 0; the object
            # is held busy, so its end is where this generator left it
            if st.exists:
                ctx["version"], off = st.version, st.length
                ctx["n_appends"] = st.n_appends + 1
            else:
                ctx["version"], off, ctx["n_appends"] = st.version + 1, 0, 1
            data = append_bytes(
                self.seed, idx, ctx["version"], ctx["n_appends"],
                self.append_len,
            )
            ctx["length"] = off + len(data)
            nbytes = len(data)
        elif kind == "delete":
            nbytes = 0
        elif kind in ("write_new", "write_full"):
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
            nbytes = st.length
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
            elif kind in ("write_patch", "append"):
                self.io.aio_write(oid, data, offset=off, on_complete=done)
            elif kind == "delete":
                self.io.aio_remove(oid, on_complete=done)
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
                st.retired = True
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
                st.length, st.n_appends = sample.nbytes, 0
                st.exists = True
        elif sample.kind == "write_patch":
            with self._lock:
                st.n_patches = ctx["patch_no"]
        elif sample.kind == "append":
            if comp.reply.size != ctx["length"]:
                self._finish(
                    ctx, False, f"object is {comp.reply.size} B after the "
                    f"append, not {ctx['length']}"
                )
                return
            with self._lock:
                if not st.exists:
                    self._live.append(sample.idx)
                    st.version, st.n_patches = ctx["version"], 0
                    st.exists = True
                st.length, st.n_appends = ctx["length"], ctx["n_appends"]
        elif sample.kind == "delete":
            with self._lock:
                self._live.remove(sample.idx)
                st.exists = False
                st.length = st.n_patches = st.n_appends = 0
        else:
            # the object is held busy, so it is as the read found it
            if bytes(comp.reply.data) != self.image(sample.idx):
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
        if self.names and loader._next_idx > self.names:
            raise ValueError(
                f"{loader._next_idx} objects preloaded, {self.names} names"
            )
        self.objects = loader.objects
        self._live = loader._live
        self._next_idx = loader._next_idx
        for idx in range(self.names):  # the names no preload made
            self.objects.setdefault(idx, ObjState(version=0))

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
