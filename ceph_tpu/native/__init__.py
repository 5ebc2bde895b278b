"""Native host runtime loader — builds and binds the C++ tier.

The reference keeps its hot host paths native (vendored SIMD GF
libraries, common/crc32c.cc dispatch, the OSD runtime); this package
is the analog: ``src/ceph_tpu_native.cc`` compiled on first use into a
shared library and bound via ctypes (no pybind11 in the image — plain
C ABI instead).

``available()`` gates every consumer: with no compiler the pure-Python
paths keep working, bit-identically (the native kernels are verified
against the Python oracles in tests/test_native.py).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import sys
import threading

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src", "ceph_tpu_native.cc")
_BUILD_DIR = os.path.join(_HERE, "_build")
_CXXFLAGS = (
    "-O3", "-march=native", "-std=c++17", "-shared", "-fPIC", "-pthread",
)

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False
#: the handle the three frame socket calls are bound through: a
#: ``ctypes.PyDLL`` of the same library where the interpreter lock's
#: two functions could be handed over (the calls then give the lock up
#: and take it back themselves, and time the take-back), else ``_lib``
_frame_lib: ctypes.CDLL | None = None
#: the same library through ``ctypes.PyDLL``: what ``_bind_held`` binds
_held_lib: ctypes.PyDLL | None = None


def _host_cpu_flags() -> str:
    """This host's CPU feature list — what ``-march=native`` compiles
    against. A copied checkout (the chip tool ships the tree as it
    stands, ``_build/`` included) must not load a library built for
    another machine's instruction set."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return " ".join(sorted(line.split(":", 1)[1].split()))
    except OSError:
        pass
    return platform.machine() + " " + platform.processor()


def _lib_path() -> str:
    """The artefact is named by a hash of source, compiler flags and
    the host's CPU flags: a stale or foreign build has another name,
    so it is rebuilt here, never loaded."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_CXXFLAGS).encode())
    h.update(_host_cpu_flags().encode())
    return os.path.join(
        _BUILD_DIR, f"libceph_tpu_native-{h.hexdigest()[:16]}.so"
    )


def _build(lib_path: str) -> bool:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # build beside the target and rename: a concurrent process never
    # dlopens a half-written file
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = ["g++", *_CXXFLAGS, _SRC, "-o", tmp]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=120
        )
        if proc.returncode != 0:
            # -march=native can fail in exotic environments; retry plain.
            cmd.remove("-march=native")
            proc = subprocess.run(
                cmd, capture_output=True, text=True, timeout=120
            )
        if proc.returncode != 0:
            return False
        os.replace(tmp, lib_path)
        return True
    except (OSError, subprocess.TimeoutExpired):
        return False
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _bind(lib: ctypes.CDLL) -> None:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.ctpu_crc32c.restype = ctypes.c_uint32
    lib.ctpu_crc32c.argtypes = [ctypes.c_uint32, u8p, ctypes.c_size_t]
    lib.ctpu_xor_region.restype = None
    lib.ctpu_xor_region.argtypes = [u8p, u8p, ctypes.c_size_t]
    lib.ctpu_gf_mul_region.restype = None
    lib.ctpu_gf_mul_region.argtypes = [
        u8p, u8p, ctypes.c_size_t, ctypes.c_uint8, ctypes.c_int,
    ]
    lib.ctpu_gf_matrix_encode.restype = None
    lib.ctpu_gf_matrix_encode.argtypes = [
        ctypes.c_int, ctypes.c_int, u8p,
        ctypes.POINTER(u8p), ctypes.POINTER(u8p), ctypes.c_size_t,
    ]
    lib.ctpu_ring_create.restype = ctypes.c_void_p
    lib.ctpu_ring_create.argtypes = [ctypes.c_uint32, ctypes.c_uint32]
    lib.ctpu_ring_destroy.restype = None
    lib.ctpu_ring_destroy.argtypes = [ctypes.c_void_p]
    lib.ctpu_ring_close.restype = None
    lib.ctpu_ring_close.argtypes = [ctypes.c_void_p]
    lib.ctpu_ring_push.restype = ctypes.c_int
    lib.ctpu_ring_push.argtypes = [
        ctypes.c_void_p, u8p, ctypes.c_uint32, ctypes.c_int,
    ]
    lib.ctpu_ring_pop.restype = ctypes.c_int
    lib.ctpu_ring_pop.argtypes = [
        ctypes.c_void_p, u8p, ctypes.POINTER(ctypes.c_uint32), ctypes.c_int,
    ]
    lib.ctpu_ring_push_timed.restype = ctypes.c_int
    lib.ctpu_ring_push_timed.argtypes = [
        ctypes.c_void_p, u8p, ctypes.c_uint32, ctypes.c_int32,
    ]
    lib.ctpu_ring_pop_timed.restype = ctypes.c_int
    lib.ctpu_ring_pop_timed.argtypes = [
        ctypes.c_void_p, u8p, ctypes.POINTER(ctypes.c_uint32),
        ctypes.c_int32,
    ]
    lib.ctpu_ring_count.restype = ctypes.c_uint32
    lib.ctpu_ring_count.argtypes = [ctypes.c_void_p]
    lib.ctpu_ring_total_pushed.restype = ctypes.c_uint64
    lib.ctpu_ring_total_pushed.argtypes = [ctypes.c_void_p]
    # frame codec (msg/wire.py clear-mode hot path). c_char_p args are
    # zero-copy for Python bytes — no numpy round-trip per frame.
    lib.ctpu_crc32c_buf.restype = ctypes.c_uint32
    lib.ctpu_crc32c_buf.argtypes = [
        ctypes.c_uint32, ctypes.c_char_p, ctypes.c_size_t,
    ]
    lib.ctpu_frame_encode.restype = ctypes.c_size_t
    lib.ctpu_frame_encode.argtypes = [
        ctypes.c_uint32, ctypes.c_uint32, ctypes.c_uint64, ctypes.c_uint32,
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_uint64),
        u8p,
    ]
    lib.ctpu_frame_verify.restype = ctypes.c_int
    lib.ctpu_frame_verify.argtypes = [
        ctypes.c_char_p, ctypes.c_uint32, ctypes.c_char_p, ctypes.c_uint64,
    ]
    lib.ctpu_task_cpu.restype = ctypes.c_int
    lib.ctpu_task_cpu.argtypes = [
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_double),
        ctypes.c_int,
    ]
    lib.ctpu_lock_hooks.restype = ctypes.c_int
    lib.ctpu_lock_hooks.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_double,
    ]


def _bind_held(lib: ctypes.PyDLL) -> None:
    """Calls that keep the interpreter lock: microseconds of work on a
    few KiB, where giving the lock up would cost a wait to have it
    back (up to a switch interval under load) many times the call."""
    u32p = ctypes.POINTER(ctypes.c_uint32)
    lib.ctpu_crc32c_fold.restype = None
    lib.ctpu_crc32c_fold.argtypes = [
        u32p, u32p, u32p, ctypes.c_size_t, ctypes.c_size_t,
    ]


def _bind_frame_io(lib: ctypes.CDLL) -> None:
    """Frame socket I/O: the codec takes the descriptor (one call a
    frame). The last argument of each is the caller's
    :class:`HandOvers`, or None."""
    ho = ctypes.POINTER(HandOvers)
    lib.ctpu_frame_send.restype = ctypes.c_int64
    lib.ctpu_frame_send.argtypes = [
        ctypes.c_int, ctypes.c_uint32, ctypes.c_uint32, ctypes.c_uint64,
        ctypes.c_uint32,
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_uint64),
        ho,
    ]
    lib.ctpu_frame_recv.restype = ctypes.c_int
    lib.ctpu_frame_recv.argtypes = [
        ctypes.c_int, ctypes.c_void_p, ctypes.c_uint64,
        ctypes.POINTER(FrameInfo), ho,
    ]
    lib.ctpu_frame_recv_body.restype = ctypes.c_int
    lib.ctpu_frame_recv_body.argtypes = [
        ctypes.c_int, ctypes.POINTER(FrameInfo),
        ctypes.POINTER(ctypes.c_void_p), ho,
    ]


def _lock_api() -> "tuple[int, int] | None":
    """Addresses of ``PyEval_SaveThread`` / ``PyEval_RestoreThread``,
    or None where this interpreter does not export them."""
    try:
        api = ctypes.pythonapi
        return tuple(
            ctypes.cast(getattr(api, name), ctypes.c_void_p).value
            for name in ("PyEval_SaveThread", "PyEval_RestoreThread")
        )
    except (AttributeError, OSError):
        return None


def _frame_handle(lib: ctypes.CDLL, lib_path: str) -> ctypes.CDLL:
    """The handle of the frame socket calls. With the interpreter
    lock's two functions in hand they go through ``ctypes.PyDLL`` (the
    call is entered holding the lock and hands it over itself), else
    through ``lib`` as every other function, and no :class:`HandOvers`
    is ever passed."""
    api = _lock_api()
    if api is None or not lib.ctpu_lock_hooks(*api, sys.getswitchinterval()):
        lib.ctpu_lock_hooks(None, None, 0.0)
        return lib
    return ctypes.PyDLL(lib_path)


def _load() -> ctypes.CDLL | None:
    global _lib, _frame_lib, _held_lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("CEPH_TPU_NO_NATIVE"):
            return None
        lib_path = _lib_path()
        if not os.path.exists(lib_path) and not _build(lib_path):
            return None
        try:
            lib = ctypes.CDLL(lib_path)
            _bind(lib)
            frame_lib = _frame_handle(lib, lib_path)
            _bind_frame_io(frame_lib)
            held_lib = ctypes.PyDLL(lib_path)
            _bind_held(held_lib)
        except OSError:
            return None
        _lib, _frame_lib, _held_lib = lib, frame_lib, held_lib
        return _lib


def available() -> bool:
    return _load() is not None


def _as_u8p(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


# -- crc32c --------------------------------------------------------------
def crc32c(init: int, data) -> int:
    """Native crc32c (ceph_crc32c semantics); raises RuntimeError when
    the native library is unavailable — callers gate on available()."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native runtime unavailable")
    buf = np.frombuffer(bytes(data), dtype=np.uint8) \
        if not isinstance(data, np.ndarray) else np.ascontiguousarray(data)
    return lib.ctpu_crc32c(init & 0xFFFFFFFF, _as_u8p(buf), buf.size)


def crc32c_bytes(init: int, data) -> int:
    """Native crc32c over a bytes-like object, zero-copy for ``bytes``
    (no numpy round-trip — the wire hot-path entry). Semantics match
    :func:`crc32c` exactly: raw register in/out, no final xor."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native runtime unavailable")
    if not isinstance(data, bytes):
        data = bytes(data)
    return lib.ctpu_crc32c_buf(init & 0xFFFFFFFF, data, len(data))


def crc32c_fold(
    cols: np.ndarray, seeds: np.ndarray, csums: np.ndarray
) -> np.ndarray:
    """Fold ``csums`` [shards, blocks] (zero-init crc32c of consecutive
    blocks) into ``seeds`` [shards], one native call for all of them;
    returns the new registers. ``cols`` is the zero-gap transition of
    one block as 32 column words. All three are C-contiguous uint32
    (``checksum.crc32c.crc32c_fold`` makes them so). The call keeps
    the interpreter lock (``_bind_held``)."""
    if _load() is None:
        raise RuntimeError("native runtime unavailable")
    u32p = ctypes.POINTER(ctypes.c_uint32)
    out = seeds.copy()
    shards, blocks = csums.shape
    _held_lib.ctpu_crc32c_fold(
        cols.ctypes.data_as(u32p), out.ctypes.data_as(u32p),
        csums.ctypes.data_as(u32p), shards, blocks,
    )
    return out


# -- frame codec ---------------------------------------------------------
def _seg_arrays(segments):
    """(count, char* array, length array) of a frame's segments: a
    ``bytes`` goes in as it is, any other buffer (a read-only view of a
    payload) as the address it lies at: no copy either way. The caller
    keeps ``segments`` alive over the call."""
    ptrs, lens = [], []
    for s in segments:
        if isinstance(s, bytes):
            ptrs.append(s)
            lens.append(len(s))
        else:
            flat = np.frombuffer(s, np.uint8)
            ptrs.append(flat.ctypes.data if flat.size else None)
            lens.append(flat.size)
    nseg = len(ptrs)
    return (
        nseg,
        (ctypes.c_char_p * nseg)(*ptrs),
        (ctypes.c_uint64 * nseg)(*lens),
    )


def frame_encode(msg_type: int, flags: int, seq: int, segments) -> bytes:
    """Assemble a clear-mode wire frame (header + segment table with
    per-segment crc32c + payloads) in one native call. ``segments`` is
    a sequence of bytes-like objects; compressed segments arrive
    pre-deflated. Bit-identical to the pure-Python wire.encode_frame
    clear path (pinned by tests/test_wire_native.py)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native runtime unavailable")
    nseg, ptrs, lens = _seg_arrays(segments)
    total = 16 + nseg * 8 + sum(lens)
    out = bytearray(total)
    written = lib.ctpu_frame_encode(
        msg_type, flags, seq, nseg, ptrs, lens,
        (ctypes.c_uint8 * total).from_buffer(out),
    )
    if written != total:
        raise RuntimeError(
            f"frame encode size mismatch: {written} != {total}"
        )
    return bytes(out)


def frame_verify(table, payload) -> int:
    """Batch-verify per-segment CRCs of a received clear frame. Returns
    -1 when all segments match, -2 on a length/table mismatch, else the
    index of the first bad segment."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native runtime unavailable")
    if not isinstance(table, bytes):
        table = bytes(table)
    if not isinstance(payload, bytes):
        payload = bytes(payload)
    return lib.ctpu_frame_verify(table, len(table) // 8, payload, len(payload))


# -- frame socket I/O ----------------------------------------------------
class FrameInfo(ctypes.Structure):
    """``struct ctpu_frame_info``: what ``ctpu_frame_recv`` learned of
    a frame (same layout as the C side)."""

    _fields_ = [
        ("seq", ctypes.c_uint64),
        ("total", ctypes.c_uint64),
        ("t_header", ctypes.c_double),
        ("msg_type", ctypes.c_uint32),
        ("flags", ctypes.c_uint32),
        ("nseg", ctypes.c_uint32),
        ("bad", ctypes.c_uint32),
        ("got", ctypes.c_uint32),
        ("lens", ctypes.c_uint32 * 8),
        ("crcs", ctypes.c_uint32 * 8),
        ("magic", ctypes.c_uint8 * 4),
    ]


class HandOvers(ctypes.Structure):
    """``struct ctpu_hand_overs``: what the frame socket calls that
    were handed this struct kept of their hand-overs of the interpreter
    lock. One owner (a connection's send side, under its send lock; its
    reader thread); the call writes it holding the interpreter lock, so
    any thread reads whole values."""

    _fields_ = [
        ("calls", ctypes.c_uint64),
        ("slow", ctypes.c_uint64),
        ("call_seconds", ctypes.c_double),
        ("wait_seconds", ctypes.c_double),
    ]

    def read(self) -> tuple[int, int, float, float]:
        """(calls, waits of a switch interval or more, seconds inside
        the calls, seconds waiting to hold the lock again)."""
        return self.calls, self.slow, self.call_seconds, self.wait_seconds


def hand_overs() -> "HandOvers | None":
    """A zeroed :class:`HandOvers` for one side of a connection, or
    None where the frame calls do not hand the lock over themselves
    (no native tier, or an interpreter that does not export the two
    functions): such a link reports 0, not a guess."""
    _load()
    return HandOvers() if isinstance(_frame_lib, ctypes.PyDLL) else None


#: ctpu_frame_recv / ctpu_frame_recv_body results (negative values
#: above -1000 are -errno)
FRAME_EOF, FRAME_DONE, FRAME_BODY = 0, 1, 2
BAD_MAGIC, BAD_FLAGS, BAD_NSEG, BAD_SECURE, BAD_LENGTH, BAD_CRC = (
    -1001, -1002, -1003, -1004, -1005, -1006,
)

#: a payload up to this size is read by the call that read its header
#: (every ack, reply without data, sub-read request, 8 KiB sub-write)
FRAME_SCRATCH_BYTES = 64 * 1024
#: of a larger payload, segments below this size still land in the
#: scratch buffer (the json headers); the others get a buffer each
_SEG_OWN_BYTES = 4096
assert 8 * _SEG_OWN_BYTES <= FRAME_SCRATCH_BYTES

# a bytes object of n bytes that nobody has filled (or zeroed) yet: the
# C API's own way to build one, written through its pointer before any
# other reference to it exists
_new_bytes = ctypes.pythonapi.PyBytes_FromStringAndSize
_new_bytes.restype = ctypes.py_object
_new_bytes.argtypes = [ctypes.c_char_p, ctypes.c_ssize_t]


def frame_send(
    fd: int, msg_type: int, flags: int, seq: int, segments, ho=None
) -> int:
    """Frame ``segments`` and write the frame to descriptor ``fd`` in
    one native call: crc32c a segment, header and table on the stack,
    a gather write of the segments from where they lie. The bytes on
    the wire are :func:`frame_encode`'s. Returns the frame's length or
    ``-errno``. ``ho`` (from :func:`hand_overs`) is where the call
    keeps its hand-over of the interpreter lock."""
    if _load() is None:
        raise RuntimeError("native runtime unavailable")
    nseg, ptrs, lens = _seg_arrays(segments)
    return _frame_lib.ctpu_frame_send(
        fd, msg_type, flags, seq, nseg, ptrs, lens, ho
    )


class FrameReceiver:
    """One connection's receive state for the native read: the scratch
    buffer small payloads land in and the :class:`FrameInfo` the call
    fills. Used by one thread (the connection's reader). ``ho`` (from
    :func:`hand_overs`) is where its calls keep their hand-overs of the
    interpreter lock."""

    def __init__(self, ho=None) -> None:
        if _load() is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = _frame_lib
        self._ho = ho
        self._scratch = bytearray(FRAME_SCRATCH_BYTES)
        self._view = memoryview(self._scratch)
        self._addr = ctypes.addressof(
            ctypes.c_uint8.from_buffer(self._scratch)
        )
        self.info = FrameInfo()
        #: native calls the last :meth:`recv` made
        self.calls = 0

    @property
    def frame_bytes(self) -> int:
        """Framed length of the frame last read."""
        return 16 + 8 * self.info.nseg + self.info.total

    def recv(self, fd: int) -> tuple[int, "list[bytes] | None"]:
        """Read one frame from ``fd``: ``(FRAME_DONE, its verified
        segments)``, or ``(rc, None)`` with ``rc`` ``FRAME_EOF``, a
        ``BAD_*`` code (``info`` says which segment) or ``-errno``. One
        native call where the payload fits the scratch buffer, else
        two: the second reads every segment of ``_SEG_OWN_BYTES`` or
        more straight into a ``bytes`` of its own."""
        info = self.info
        self.calls = 1
        rc = self._lib.ctpu_frame_recv(
            fd, self._addr, FRAME_SCRATCH_BYTES, info, self._ho
        )
        if rc not in (FRAME_DONE, FRAME_BODY):
            return rc, None
        lens = info.lens[: info.nseg]
        segs: list = [None] * len(lens)
        if rc == FRAME_BODY:
            self.calls = 2
            bufs = (ctypes.c_void_p * len(lens))()
            pos = 0
            for i, n in enumerate(lens):
                if n < _SEG_OWN_BYTES:
                    bufs[i] = self._addr + pos
                    pos += n
                else:
                    segs[i] = _new_bytes(None, n)
                    bufs[i] = ctypes.cast(segs[i], ctypes.c_void_p)
            rc = self._lib.ctpu_frame_recv_body(fd, info, bufs, self._ho)
            if rc != FRAME_DONE:
                return rc, None
        pos = 0
        for i, n in enumerate(lens):
            if segs[i] is None:
                segs[i] = bytes(self._view[pos : pos + n])
                pos += n
        return rc, segs


# -- CPU seconds by task -------------------------------------------------
def task_cpu() -> dict[int, float]:
    """``{task id: CPU seconds}`` of every task of this process, read
    in one native pass over ``/proc/self/task`` (``schedstat`` where
    the kernel has it, else ``stat``'s utime + stime)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native runtime unavailable")
    cap = 1024
    while True:
        tids = (ctypes.c_uint64 * cap)()
        seconds = (ctypes.c_double * cap)()
        n = lib.ctpu_task_cpu(tids, seconds, cap)
        if n < 0:
            raise OSError(-n, os.strerror(-n))
        if n <= cap:
            return dict(zip(tids[:n], seconds[:n]))
        cap = 2 * n


# -- GF region ops -------------------------------------------------------
def xor_region(dst: np.ndarray, src: np.ndarray) -> None:
    lib = _load()
    if lib is None:
        raise RuntimeError("native runtime unavailable")
    assert dst.size == src.size and dst.dtype == np.uint8
    lib.ctpu_xor_region(_as_u8p(dst), _as_u8p(src), dst.size)


def gf_mul_region(
    dst: np.ndarray, src: np.ndarray, c: int, accumulate: bool = False
) -> None:
    lib = _load()
    if lib is None:
        raise RuntimeError("native runtime unavailable")
    assert dst.size == src.size and dst.dtype == np.uint8
    lib.ctpu_gf_mul_region(
        _as_u8p(dst), _as_u8p(src), dst.size, c, int(accumulate)
    )


def gf_matrix_encode(matrix: np.ndarray, data: np.ndarray) -> np.ndarray:
    """parity[m, n] = matrix[m, k] x data[k, n] over GF(2^8) — the host
    encode path (jerasure_matrix_encode / ec_encode_data analog)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native runtime unavailable")
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    m, k = matrix.shape
    assert data.shape[0] == k, (data.shape, k)
    n = data.shape[1]
    parity = np.zeros((m, n), dtype=np.uint8)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    data_ptrs = (u8p * k)(*[_as_u8p(data[i]) for i in range(k)])
    parity_ptrs = (u8p * m)(*[_as_u8p(parity[j]) for j in range(m)])
    lib.ctpu_gf_matrix_encode(
        k, m, _as_u8p(matrix), data_ptrs, parity_ptrs, n
    )
    return parity


# -- ring buffer ---------------------------------------------------------
class RingBuffer:
    """Blocking MPMC ring of fixed-size slots (native storage) — the
    host staging queue feeding device batches."""

    def __init__(self, capacity: int, slot_bytes: int) -> None:
        lib = _load()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = lib
        self._ring = lib.ctpu_ring_create(capacity, slot_bytes)
        if not self._ring:
            raise MemoryError("ring allocation failed")
        self.capacity = capacity
        self.slot_bytes = slot_bytes

    def push(self, data, blocking: bool = True) -> bool:
        buf = np.frombuffer(bytes(data), dtype=np.uint8) \
            if not isinstance(data, np.ndarray) else np.ascontiguousarray(data)
        rc = self._lib.ctpu_ring_push(
            self._ring, _as_u8p(buf), buf.size, int(blocking)
        )
        if rc < 0:
            raise ValueError(
                f"slot overflow: {buf.size} > {self.slot_bytes}"
            )
        return rc == 1

    def pop(self, blocking: bool = True) -> bytes | None:
        out = np.empty(self.slot_bytes, dtype=np.uint8)
        ln = ctypes.c_uint32()
        rc = self._lib.ctpu_ring_pop(
            self._ring, _as_u8p(out), ctypes.byref(ln), int(blocking)
        )
        if rc != 1:
            return None
        return out[: ln.value].tobytes()

    def push_timed(self, data, timeout: "float | None" = None) -> int:
        """Push with a bounded wait: 1 = pushed, 0 = ring closed,
        -2 = timed out (timeout is seconds; None waits forever)."""
        if not isinstance(data, bytes):
            data = bytes(data)
        ms = -1 if timeout is None else max(0, int(timeout * 1000))
        # zero-copy view of the bytes object (c_char_p cast, no staging
        # copy — the C side memcpys straight into the slot)
        ptr = ctypes.cast(
            ctypes.c_char_p(data), ctypes.POINTER(ctypes.c_uint8)
        )
        rc = self._lib.ctpu_ring_push_timed(self._ring, ptr, len(data), ms)
        if rc == -1:
            raise ValueError(
                f"slot overflow: {len(data)} > {self.slot_bytes}"
            )
        return rc

    def pop_timed(self, timeout: "float | None" = None):
        """Pop with a bounded wait: (1, chunk) on success, (0, None)
        when the ring is closed and drained, (-2, None) on timeout."""
        ms = -1 if timeout is None else max(0, int(timeout * 1000))
        out = bytearray(self.slot_bytes)
        ln = ctypes.c_uint32()
        rc = self._lib.ctpu_ring_pop_timed(
            self._ring,
            (ctypes.c_uint8 * self.slot_bytes).from_buffer(out),
            ctypes.byref(ln),
            ms,
        )
        if rc != 1:
            return rc, None
        return 1, bytes(out[: ln.value])

    def close(self) -> None:
        self._lib.ctpu_ring_close(self._ring)

    def __len__(self) -> int:
        return self._lib.ctpu_ring_count(self._ring)

    @property
    def total_pushed(self) -> int:
        return self._lib.ctpu_ring_total_pushed(self._ring)

    def __del__(self) -> None:
        ring = getattr(self, "_ring", None)
        if ring:
            self._lib.ctpu_ring_destroy(ring)
            self._ring = None
