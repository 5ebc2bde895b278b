// Native host runtime for ceph_tpu — the C++ tier the reference keeps
// in vendored SIMD libraries and the OSD runtime (SURVEY.md §2.4):
//
//  * crc32c: slicing-by-8 software kernel with an SSE4.2 hardware path
//    (the ceph_crc32c dispatch analog, src/common/crc32c.cc) — raw
//    register in/out, reflected Castagnoli, no final xor, bit-exact
//    with the Python oracle (checksum/reference.crc32c_ref); and the
//    fold of zero-init block crcs into running registers (HashInfo's
//    cumulative shard hashes), all shards and blocks in one call.
//  * GF(2^8) region ops over the 0x11D field: constant-multiply /
//    xor-accumulate regions and a full matrix encode — the
//    jerasure/ISA-L region-op analog used for host-side staging,
//    verification, and small low-latency fallback paths.
//  * A blocking MPMC ring buffer of fixed slots — the host staging
//    queue of the dispatch pipeline (host ring -> pinned staging ->
//    device batches; SURVEY.md §7 step 4).
//  * The wire frame codec (msg/wire.py): header + segment table +
//    per-segment crc32c in one call, and, given the socket's
//    descriptor, the frame's write or read in that same call.
//
// Plain C ABI so ctypes loads it with no binding generator.

#include <cerrno>
#include <chrono>
#include <cstdint>
#include <cstring>
#include <condition_variable>
#include <cstdio>
#include <cstdlib>
#include <ctime>
#include <mutex>
#include <new>

#include <dirent.h>
#include <fcntl.h>
#include <poll.h>
#include <sys/socket.h>
#include <sys/types.h>
#include <sys/uio.h>
#include <unistd.h>

#if defined(__SSE4_2__)
#include <nmmintrin.h>
#endif

extern "C" {

// ---------------------------------------------------------------- crc32c
static uint32_t crc_table[8][256];
static bool crc_init_done = false;

static void crc_init() {
    if (crc_init_done) return;
    const uint32_t poly = 0x82F63B78u;  // reflected Castagnoli
    for (uint32_t i = 0; i < 256; i++) {
        uint32_t c = i;
        for (int k = 0; k < 8; k++)
            c = (c & 1) ? (c >> 1) ^ poly : c >> 1;
        crc_table[0][i] = c;
    }
    for (uint32_t i = 0; i < 256; i++) {
        uint32_t c = crc_table[0][i];
        for (int t = 1; t < 8; t++) {
            c = crc_table[0][c & 0xFF] ^ (c >> 8);
            crc_table[t][i] = c;
        }
    }
    crc_init_done = true;
}

uint32_t ctpu_crc32c(uint32_t crc, const uint8_t* data, size_t len) {
#if defined(__SSE4_2__)
    // Hardware CRC32C (the ceph_crc32c_intel_fast analog).
    while (len >= 8 && (reinterpret_cast<uintptr_t>(data) & 7)) {
        crc = _mm_crc32_u8(crc, *data++);
        len--;
    }
    uint64_t c64 = crc;
    while (len >= 8) {
        c64 = _mm_crc32_u64(c64, *reinterpret_cast<const uint64_t*>(data));
        data += 8;
        len -= 8;
    }
    crc = static_cast<uint32_t>(c64);
    while (len--) crc = _mm_crc32_u8(crc, *data++);
    return crc;
#else
    crc_init();
    // slicing-by-8
    while (len >= 8) {
        uint32_t lo;
        std::memcpy(&lo, data, 4);
        lo ^= crc;
        uint32_t hi;
        std::memcpy(&hi, data + 4, 4);
        crc = crc_table[7][lo & 0xFF] ^ crc_table[6][(lo >> 8) & 0xFF] ^
              crc_table[5][(lo >> 16) & 0xFF] ^ crc_table[4][lo >> 24] ^
              crc_table[3][hi & 0xFF] ^ crc_table[2][(hi >> 8) & 0xFF] ^
              crc_table[1][(hi >> 16) & 0xFF] ^ crc_table[0][hi >> 24];
        data += 8;
        len -= 8;
    }
    while (len--) crc = crc_table[0][(crc ^ *data++) & 0xFF] ^ (crc >> 8);
    return crc;
#endif
}

// Fold ZERO-INIT per-block crc32c values into running registers: for
// each of ``shards`` rows, seed' = A seed ^ c over the row's ``blocks``
// words in order (crc32c range concatenation, block after block).
// ``cols`` is the 32x32 GF(2) transition A across one block of zero
// bytes, as its 32 column words (bit i of cols[j] = A[i][j]); the
// caller owns how it is made (checksum/crc32c.zero_gap_columns). A is
// applied a byte of the register at a time from four 256-entry tables
// built here, once a call.
void ctpu_crc32c_fold(const uint32_t* cols, uint32_t* seeds,
                      const uint32_t* csums, size_t shards,
                      size_t blocks) {
    uint32_t t[4][256];
    for (int b = 0; b < 4; b++) {
        t[b][0] = 0;
        for (uint32_t v = 1; v < 256; v++)
            t[b][v] = t[b][v & (v - 1)] ^ cols[8 * b + __builtin_ctz(v)];
    }
    for (size_t s = 0; s < shards; s++) {
        uint32_t reg = seeds[s];
        const uint32_t* row = csums + s * blocks;
        for (size_t i = 0; i < blocks; i++)
            reg = t[0][reg & 0xFF] ^ t[1][(reg >> 8) & 0xFF] ^
                  t[2][(reg >> 16) & 0xFF] ^ t[3][reg >> 24] ^ row[i];
        seeds[s] = reg;
    }
}

// ------------------------------------------------------------- GF(2^8)
// 0x11D field, matching ceph_tpu.gf.tables (the jerasure/ISA-L field).
static uint8_t gf_mul_table[256][256];
static bool gf_init_done = false;

static void gf_init() {
    if (gf_init_done) return;
    uint8_t exp_t[512];
    int log_t[256];
    int x = 1;
    for (int i = 0; i < 255; i++) {
        exp_t[i] = static_cast<uint8_t>(x);
        log_t[x] = i;
        x <<= 1;
        if (x & 0x100) x ^= 0x11D;
    }
    for (int i = 255; i < 512; i++) exp_t[i] = exp_t[i - 255];
    for (int a = 0; a < 256; a++) {
        gf_mul_table[0][a] = 0;
        gf_mul_table[a][0] = 0;
    }
    for (int a = 1; a < 256; a++)
        for (int b = 1; b < 256; b++)
            gf_mul_table[a][b] = exp_t[log_t[a] + log_t[b]];
    gf_init_done = true;
}

void ctpu_xor_region(uint8_t* dst, const uint8_t* src, size_t n) {
    // 64-bit wide XOR; compilers vectorize this loop.
    size_t i = 0;
    for (; i + 8 <= n; i += 8) {
        uint64_t a, b;
        std::memcpy(&a, dst + i, 8);
        std::memcpy(&b, src + i, 8);
        a ^= b;
        std::memcpy(dst + i, &a, 8);
    }
    for (; i < n; i++) dst[i] ^= src[i];
}

void ctpu_gf_mul_region(uint8_t* dst, const uint8_t* src, size_t n,
                        uint8_t c, int accumulate) {
    gf_init();
    const uint8_t* row = gf_mul_table[c];
    if (accumulate)
        for (size_t i = 0; i < n; i++) dst[i] ^= row[src[i]];
    else
        for (size_t i = 0; i < n; i++) dst[i] = row[src[i]];
}

// matrix: [m][k] row-major GF coefficients; data/parity: arrays of
// pointers to len-byte regions. parity[j] = sum_i matrix[j][i]*data[i].
void ctpu_gf_matrix_encode(int k, int m, const uint8_t* matrix,
                           const uint8_t* const* data,
                           uint8_t* const* parity, size_t len) {
    gf_init();
    for (int j = 0; j < m; j++) {
        std::memset(parity[j], 0, len);
        for (int i = 0; i < k; i++) {
            uint8_t c = matrix[j * k + i];
            if (c == 0) continue;
            if (c == 1)
                ctpu_xor_region(parity[j], data[i], len);
            else
                ctpu_gf_mul_region(parity[j], data[i], len, c, 1);
        }
    }
}

// ---------------------------------------------------------- ring buffer
struct Ring {
    uint32_t capacity;
    uint32_t slot_bytes;
    uint32_t head = 0;   // next pop
    uint32_t tail = 0;   // next push
    uint32_t count = 0;
    uint64_t total_pushed = 0;
    bool closed = false;
    uint8_t* slots;
    uint32_t* lens;
    std::mutex mu;
    std::condition_variable not_full, not_empty;
};

void* ctpu_ring_create(uint32_t capacity, uint32_t slot_bytes) {
    if (capacity == 0 || slot_bytes == 0) return nullptr;
    Ring* r = new (std::nothrow) Ring();
    if (!r) return nullptr;
    r->capacity = capacity;
    r->slot_bytes = slot_bytes;
    r->slots = new (std::nothrow) uint8_t[size_t(capacity) * slot_bytes];
    r->lens = new (std::nothrow) uint32_t[capacity];
    if (!r->slots || !r->lens) {
        delete[] r->slots;
        delete[] r->lens;
        delete r;
        return nullptr;
    }
    return r;
}

void ctpu_ring_destroy(void* h) {
    Ring* r = static_cast<Ring*>(h);
    if (!r) return;
    delete[] r->slots;
    delete[] r->lens;
    delete r;
}

void ctpu_ring_close(void* h) {
    Ring* r = static_cast<Ring*>(h);
    std::lock_guard<std::mutex> lk(r->mu);
    r->closed = true;
    r->not_empty.notify_all();
    r->not_full.notify_all();
}

// returns 1 on success, 0 if full (non-blocking) or closed, -1 bad args
int ctpu_ring_push(void* h, const uint8_t* data, uint32_t len,
                   int blocking) {
    Ring* r = static_cast<Ring*>(h);
    if (!r || len > r->slot_bytes) return -1;
    std::unique_lock<std::mutex> lk(r->mu);
    if (blocking)
        r->not_full.wait(lk, [r] { return r->count < r->capacity || r->closed; });
    if (r->closed || r->count == r->capacity) return 0;
    std::memcpy(r->slots + size_t(r->tail) * r->slot_bytes, data, len);
    r->lens[r->tail] = len;
    r->tail = (r->tail + 1) % r->capacity;
    r->count++;
    r->total_pushed++;
    r->not_empty.notify_one();
    return 1;
}

// returns 1 on success (len written), 0 if empty/closed, -1 bad args
int ctpu_ring_pop(void* h, uint8_t* out, uint32_t* len, int blocking) {
    Ring* r = static_cast<Ring*>(h);
    if (!r || !out || !len) return -1;
    std::unique_lock<std::mutex> lk(r->mu);
    if (blocking)
        r->not_empty.wait(lk, [r] { return r->count > 0 || r->closed; });
    if (r->count == 0) return 0;
    std::memcpy(out, r->slots + size_t(r->head) * r->slot_bytes,
                r->lens[r->head]);
    *len = r->lens[r->head];
    r->head = (r->head + 1) % r->capacity;
    r->count--;
    r->not_full.notify_one();
    return 1;
}

// Timed variants for transport use (msg/shm_ring.py): wait up to
// timeout_ms (negative = forever). Returns 1 on success, 0 when the
// ring is closed (push) / closed and drained (pop), -2 on timeout,
// -1 on bad args. A closed ring still drains buffered slots — the
// byte-stream EOF contract a half-closed TCP socket provides.
int ctpu_ring_push_timed(void* h, const uint8_t* data, uint32_t len,
                         int32_t timeout_ms) {
    Ring* r = static_cast<Ring*>(h);
    if (!r || len > r->slot_bytes) return -1;
    std::unique_lock<std::mutex> lk(r->mu);
    auto ready = [r] { return r->count < r->capacity || r->closed; };
    if (timeout_ms < 0) {
        r->not_full.wait(lk, ready);
    } else if (!r->not_full.wait_for(
                   lk, std::chrono::milliseconds(timeout_ms), ready)) {
        return -2;
    }
    if (r->closed) return 0;
    std::memcpy(r->slots + size_t(r->tail) * r->slot_bytes, data, len);
    r->lens[r->tail] = len;
    r->tail = (r->tail + 1) % r->capacity;
    r->count++;
    r->total_pushed++;
    r->not_empty.notify_one();
    return 1;
}

int ctpu_ring_pop_timed(void* h, uint8_t* out, uint32_t* len,
                        int32_t timeout_ms) {
    Ring* r = static_cast<Ring*>(h);
    if (!r || !out || !len) return -1;
    std::unique_lock<std::mutex> lk(r->mu);
    auto ready = [r] { return r->count > 0 || r->closed; };
    if (timeout_ms < 0) {
        r->not_empty.wait(lk, ready);
    } else if (!r->not_empty.wait_for(
                   lk, std::chrono::milliseconds(timeout_ms), ready)) {
        return -2;
    }
    if (r->count == 0) return 0;
    std::memcpy(out, r->slots + size_t(r->head) * r->slot_bytes,
                r->lens[r->head]);
    *len = r->lens[r->head];
    r->head = (r->head + 1) % r->capacity;
    r->count--;
    r->not_full.notify_one();
    return 1;
}

uint32_t ctpu_ring_count(void* h) {
    Ring* r = static_cast<Ring*>(h);
    std::lock_guard<std::mutex> lk(r->mu);
    return r->count;
}

uint64_t ctpu_ring_total_pushed(void* h) {
    Ring* r = static_cast<Ring*>(h);
    std::lock_guard<std::mutex> lk(r->mu);
    return r->total_pushed;
}

// ----------------------------------------------------------- frame codec
// msg/wire.py hot-path analog (the reference's msgr2 frame assembly,
// src/msg/async/frames_v2.cc): clear-mode frames only — a 16-byte
// little-endian header (magic "CTv2", u16 msg_type, u8 flags, u8 nseg,
// u64 seq), an nseg x (u32 len, u32 crc32c) segment table, then the
// concatenated payloads. CRCs are seeded 0xFFFFFFFF per segment
// (wire.CRC_SEED), matching the Python path bit-for-bit. Compressed
// segments arrive pre-deflated (the zlib step stays in Python); secure
// frames never reach this path.

// zero-copy crc32c entry for Python bytes (no numpy round-trip).
uint32_t ctpu_crc32c_buf(uint32_t crc, const char* data, size_t len) {
    return ctpu_crc32c(crc, reinterpret_cast<const uint8_t*>(data), len);
}

static void put_header(uint8_t* p, uint32_t msg_type, uint32_t flags,
                       uint32_t nseg, uint64_t seq) {
    p[0] = 'C'; p[1] = 'T'; p[2] = 'v'; p[3] = '2';
    p[4] = msg_type & 0xFF; p[5] = (msg_type >> 8) & 0xFF;
    p[6] = flags & 0xFF;
    p[7] = nseg & 0xFF;
    for (int b = 0; b < 8; b++) p[8 + b] = (seq >> (8 * b)) & 0xFF;
}

// One table entry: the segment's length and its crc32c.
static void put_entry(uint8_t* e, const uint8_t* seg, uint64_t len) {
    uint32_t crc = ctpu_crc32c(0xFFFFFFFFu, seg, len);
    for (int b = 0; b < 4; b++) e[b] = (len >> (8 * b)) & 0xFF;
    for (int b = 0; b < 4; b++) e[4 + b] = (crc >> (8 * b)) & 0xFF;
}

// Assemble header + table + payloads into `out` (caller sizes it as
// 16 + nseg*8 + sum(lens)). Returns total bytes written.
size_t ctpu_frame_encode(uint32_t msg_type, uint32_t flags, uint64_t seq,
                         uint32_t nseg, const char* const* segs,
                         const uint64_t* lens, uint8_t* out) {
    uint8_t* p = out;
    put_header(p, msg_type, flags, nseg, seq);
    p += 16;
    uint8_t* table = p;
    p += size_t(nseg) * 8;
    for (uint32_t i = 0; i < nseg; i++) {
        const uint8_t* seg = reinterpret_cast<const uint8_t*>(segs[i]);
        uint64_t len = lens[i];
        put_entry(table + i * 8, seg, len);
        std::memcpy(p, seg, len);
        p += len;
    }
    return static_cast<size_t>(p - out);
}

// Batch-verify the per-segment CRCs of a received clear frame:
// `table` is the raw nseg*8-byte little-endian (len, crc) entries,
// `payload` the concatenated segment bytes. Returns -1 when every
// segment matches, -2 when the table lengths disagree with
// payload_len, else the index of the first mismatching segment.
int ctpu_frame_verify(const char* table_c, uint32_t nseg,
                      const char* payload_c, uint64_t payload_len) {
    const uint8_t* table = reinterpret_cast<const uint8_t*>(table_c);
    const uint8_t* payload = reinterpret_cast<const uint8_t*>(payload_c);
    uint64_t off = 0;
    for (uint32_t i = 0; i < nseg; i++) {
        uint32_t len = 0, want = 0;
        for (int b = 0; b < 4; b++)
            len |= static_cast<uint32_t>(table[i * 8 + b]) << (8 * b);
        for (int b = 0; b < 4; b++)
            want |= static_cast<uint32_t>(table[i * 8 + 4 + b]) << (8 * b);
        if (off + len > payload_len) return -2;
        uint32_t got = ctpu_crc32c(0xFFFFFFFFu, payload + off, len);
        if (got != want) return static_cast<int>(i);
        off += len;
    }
    if (off != payload_len) return -2;
    return -1;
}

// ------------------------------------------------------ frame socket I/O
// The codec takes the socket (msg/messenger.py, clear uncompressed
// links on a kernel descriptor): a frame is framed, checksummed and
// written — or read, verified and handed over — inside ONE call, so
// the caller leaves the interpreter once a frame instead of once a
// codec call and once a socket call. Same bytes on the wire as
// ctpu_frame_encode; same checks, in the same order, as
// wire.decode_frame. A descriptor in non-blocking mode (a Python
// socket with a timeout) is waited on with poll().

enum : int {
    CTPU_FRAME_EOF = 0,        // peer closed (at or inside a frame)
    CTPU_FRAME_DONE = 1,       // whole frame read and verified
    CTPU_FRAME_BODY = 2,       // header + table read, payload pending
    CTPU_BAD_MAGIC = -1001,
    CTPU_BAD_FLAGS = -1002,
    CTPU_BAD_NSEG = -1003,
    CTPU_BAD_SECURE = -1004,   // sealed frame on a clear session
    CTPU_BAD_LENGTH = -1005,   // segment over MAX_SEGMENT_BYTES (info.bad)
    CTPU_BAD_CRC = -1006,      // segment info.bad: info.got != crcs[bad]
};

static const uint32_t CTPU_MAX_SEGMENTS = 8;           // wire.MAX_SEGMENTS
static const uint32_t CTPU_MAX_SEGMENT_BYTES = 1u << 30;

// What ctpu_frame_recv learned of a frame; the caller keeps one a
// connection (native.FrameReceiver mirrors the layout).
struct ctpu_frame_info {
    uint64_t seq;
    uint64_t total;      // payload bytes (sum of lens)
    double t_header;     // CLOCK_MONOTONIC (time.perf_counter's clock
                         // on Linux) when the header was complete
    uint32_t msg_type;
    uint32_t flags;
    uint32_t nseg;
    uint32_t bad;        // offending segment (BAD_LENGTH / BAD_CRC)
    uint32_t got;        // its computed crc (BAD_CRC)
    uint32_t lens[8];
    uint32_t crcs[8];
    uint8_t magic[4];    // as received (BAD_MAGIC names it)
};

// ---- the interpreter lock, given and taken back by the call itself
// Once ctpu_lock_hooks has been handed the interpreter's two functions
// (PyEval_SaveThread / PyEval_RestoreThread, as plain pointers: no
// Python header is needed to build this file) the three frame calls
// below are entered WITH the interpreter lock held (the binding goes
// through ctypes.PyDLL then): each gives the lock up at entry and
// takes it back as its last instruction, and where the caller passed
// a ctpu_hand_overs it stamps both sides of the take-back. Without
// the hooks the caller (ctypes.CDLL) dropped the lock itself, and a
// call touches neither the lock nor a clock nor the struct.
struct ctpu_hand_overs {
    uint64_t calls;        // frame calls made
    uint64_t slow;         // of them, waits of a switch interval or more
    double call_seconds;   // entry (recv: header in hand) to the last
                           // instruction before the lock is asked back
    double wait_seconds;   // inside PyEval_RestoreThread
};

static void* (*g_lock_give)(void) = nullptr;
static void (*g_lock_take)(void*) = nullptr;
static double g_switch_interval = 0.005;

// The binding's one call, before any frame call: both pointers or
// neither. Returns 1 where the frame calls are to be entered with the
// lock held.
int ctpu_lock_hooks(void* give, void* take, double switch_interval) {
    if (give == nullptr || take == nullptr) {
        g_lock_give = nullptr;
        g_lock_take = nullptr;
        return 0;
    }
    g_lock_give = reinterpret_cast<void* (*)(void)>(give);
    g_lock_take = reinterpret_cast<void (*)(void*)>(take);
    g_switch_interval = switch_interval;
    return 1;
}

static inline double mono_now() {
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return double(ts.tv_sec) + double(ts.tv_nsec) * 1e-9;
}

// One frame call's hold on "the lock is not mine": made at entry,
// closed by done() as the call's last instruction.
struct lock_given {
    void (*take)(void*);
    ctpu_hand_overs* ho;
    void* state;
    double t_in;

    explicit lock_given(ctpu_hand_overs* h)
        : take(g_lock_take), ho(h), state(nullptr), t_in(0.0) {
        if (g_lock_give == nullptr) {
            take = nullptr;
            return;
        }
        if (ho != nullptr) t_in = mono_now();
        state = g_lock_give();
    }

    // `since`: where call_seconds starts (0: at entry; a receive
    // passes the header's stamp, negative where no header came, so
    // that an idle link adds nothing).
    void done(double since = 0.0) {
        if (take == nullptr) return;
        if (ho == nullptr) {
            take(state);
            return;
        }
        double t_out = mono_now();
        take(state);
        double t_back = mono_now();
        // written with the lock held: a dump reads whole values
        ho->calls += 1;
        if (since >= 0.0)
            ho->call_seconds += t_out - (since > 0.0 ? since : t_in);
        double waited = t_back - t_out;
        ho->wait_seconds += waited;
        if (waited >= g_switch_interval) ho->slow += 1;
    }
};

static int wait_fd(int fd, short events) {
    struct pollfd p = {fd, events, 0};
    for (;;) {
        if (poll(&p, 1, -1) >= 0) return 0;
        if (errno != EINTR) return -errno;
    }
}

// Exactly n bytes into buf: 1, 0 at EOF, -errno.
static int recv_all(int fd, uint8_t* buf, size_t n) {
    while (n > 0) {
        ssize_t r = recv(fd, buf, n, MSG_WAITALL);
        if (r > 0) {
            buf += r;
            n -= static_cast<size_t>(r);
        } else if (r == 0) {
            return CTPU_FRAME_EOF;
        } else if (errno == EAGAIN || errno == EWOULDBLOCK) {
            int rc = wait_fd(fd, POLLIN);
            if (rc < 0) return rc;
        } else if (errno != EINTR) {
            return -errno;
        }
    }
    return 1;
}

// Frame `segs` and write header, table and the segments from where
// they lie (gather write; a short write resumes where it stopped).
// Returns the frame's length, or -errno.
static int64_t frame_send(int fd, uint32_t msg_type, uint32_t flags,
                          uint64_t seq, uint32_t nseg,
                          const char* const* segs, const uint64_t* lens) {
    if (nseg == 0 || nseg > CTPU_MAX_SEGMENTS) return -EINVAL;
    for (uint32_t i = 0; i < nseg; i++)
        if (lens[i] > 0xFFFFFFFFull) return -EMSGSIZE;
    uint8_t head[16 + CTPU_MAX_SEGMENTS * 8];
    put_header(head, msg_type, flags, nseg, seq);
    struct iovec iov[1 + CTPU_MAX_SEGMENTS];
    int cnt = 1;
    int64_t total = 16 + int64_t(nseg) * 8;
    iov[0].iov_base = head;
    iov[0].iov_len = static_cast<size_t>(total);
    for (uint32_t i = 0; i < nseg; i++) {
        const uint8_t* seg = reinterpret_cast<const uint8_t*>(segs[i]);
        put_entry(head + 16 + i * 8, seg, lens[i]);
        if (lens[i] == 0) continue;
        iov[cnt].iov_base = const_cast<uint8_t*>(seg);
        iov[cnt].iov_len = static_cast<size_t>(lens[i]);
        cnt++;
        total += static_cast<int64_t>(lens[i]);
    }
    struct iovec* v = iov;
    while (cnt > 0) {
        struct msghdr mh;
        std::memset(&mh, 0, sizeof mh);
        mh.msg_iov = v;
        mh.msg_iovlen = static_cast<size_t>(cnt);
        ssize_t w = sendmsg(fd, &mh, MSG_NOSIGNAL);
        if (w < 0) {
            if (errno == EINTR) continue;
            if (errno == EAGAIN || errno == EWOULDBLOCK) {
                int rc = wait_fd(fd, POLLOUT);
                if (rc < 0) return rc;
                continue;
            }
            return -errno;
        }
        size_t left = static_cast<size_t>(w);
        while (cnt > 0 && left >= v->iov_len) {
            left -= v->iov_len;
            v++;
            cnt--;
        }
        if (cnt > 0) {
            v->iov_base = static_cast<uint8_t*>(v->iov_base) + left;
            v->iov_len -= left;
        }
    }
    return total;
}

int64_t ctpu_frame_send(int fd, uint32_t msg_type, uint32_t flags,
                        uint64_t seq, uint32_t nseg,
                        const char* const* segs, const uint64_t* lens,
                        ctpu_hand_overs* ho) {
    lock_given lock(ho);
    int64_t rc = frame_send(fd, msg_type, flags, seq, nseg, segs, lens);
    lock.done();
    return rc;
}

static uint32_t le32(const uint8_t* p) {
    return uint32_t(p[0]) | uint32_t(p[1]) << 8 | uint32_t(p[2]) << 16
        | uint32_t(p[3]) << 24;
}

// Read one frame's header and table, check them as wire.decode_frame
// does, and — where the payload fits `scratch` — read and verify the
// payload too (CTPU_FRAME_DONE: the segments lie back to back from
// scratch[0]). A larger payload is left on the socket
// (CTPU_FRAME_BODY) for ctpu_frame_recv_body, into buffers the caller
// sizes from info->lens.
static int frame_recv(int fd, uint8_t* scratch, uint64_t cap,
                      ctpu_frame_info* info) {
    uint8_t hdr[16];
    info->t_header = -1.0;
    int rc = recv_all(fd, hdr, sizeof hdr);
    if (rc <= 0) return rc;
    info->t_header = mono_now();
    std::memcpy(info->magic, hdr, 4);
    if (std::memcmp(hdr, "CTv2", 4) != 0) return CTPU_BAD_MAGIC;
    info->msg_type = uint32_t(hdr[4]) | uint32_t(hdr[5]) << 8;
    info->flags = hdr[6];
    info->nseg = hdr[7];
    info->seq = 0;
    for (int b = 0; b < 8; b++) info->seq |= uint64_t(hdr[8 + b]) << (8 * b);
    if (info->flags & ~0x03u) return CTPU_BAD_FLAGS;
    if (info->nseg == 0 || info->nseg > CTPU_MAX_SEGMENTS)
        return CTPU_BAD_NSEG;
    if (info->flags & 0x02u) return CTPU_BAD_SECURE;
    uint8_t table[CTPU_MAX_SEGMENTS * 8];
    rc = recv_all(fd, table, size_t(info->nseg) * 8);
    if (rc <= 0) return rc;
    info->total = 0;
    for (uint32_t i = 0; i < info->nseg; i++) {
        info->lens[i] = le32(table + i * 8);
        info->crcs[i] = le32(table + i * 8 + 4);
        if (info->lens[i] > CTPU_MAX_SEGMENT_BYTES) {
            info->bad = i;
            return CTPU_BAD_LENGTH;
        }
        info->total += info->lens[i];
    }
    if (info->total > cap) return CTPU_FRAME_BODY;
    rc = recv_all(fd, scratch, static_cast<size_t>(info->total));
    if (rc <= 0) return rc;
    const uint8_t* p = scratch;
    for (uint32_t i = 0; i < info->nseg; i++) {
        info->got = ctpu_crc32c(0xFFFFFFFFu, p, info->lens[i]);
        if (info->got != info->crcs[i]) {
            info->bad = i;
            return CTPU_BAD_CRC;
        }
        p += info->lens[i];
    }
    return CTPU_FRAME_DONE;
}

int ctpu_frame_recv(int fd, uint8_t* scratch, uint64_t cap,
                    ctpu_frame_info* info, ctpu_hand_overs* ho) {
    lock_given lock(ho);
    int rc = frame_recv(fd, scratch, cap, info);
    // from the header's stamp: the wait for a header is an idle link
    lock.done(info->t_header);
    return rc;
}

// The payload ctpu_frame_recv left on the socket: segment i straight
// into bufs[i] (info->lens[i] bytes), every crc32c checked before the
// call returns CTPU_FRAME_DONE.
static int frame_recv_body(int fd, ctpu_frame_info* info,
                           uint8_t* const* bufs) {
    for (uint32_t i = 0; i < info->nseg; i++) {
        int rc = recv_all(fd, bufs[i], info->lens[i]);
        if (rc <= 0) return rc;
    }
    for (uint32_t i = 0; i < info->nseg; i++) {
        info->got = ctpu_crc32c(0xFFFFFFFFu, bufs[i], info->lens[i]);
        if (info->got != info->crcs[i]) {
            info->bad = i;
            return CTPU_BAD_CRC;
        }
    }
    return CTPU_FRAME_DONE;
}

int ctpu_frame_recv_body(int fd, ctpu_frame_info* info,
                         uint8_t* const* bufs, ctpu_hand_overs* ho) {
    lock_given lock(ho);
    int rc = frame_recv_body(fd, info, bufs);
    lock.done();
    return rc;
}

// ------------------------------------------------- CPU seconds by task
// One pass over /proc/self/task: each task's id and the CPU seconds it
// has used, from `schedstat` (nanoseconds on the CPU) where the kernel
// has it, else from `stat` (utime + stime, in clock ticks). Returns
// the number of tasks found (at most `cap` are written) or -errno.
static int read_small(const char* path, char* buf, size_t cap) {
    int fd = open(path, O_RDONLY | O_CLOEXEC);
    if (fd < 0) return -1;
    ssize_t n = read(fd, buf, cap - 1);
    close(fd);
    if (n <= 0) return -1;
    buf[n] = 0;
    return static_cast<int>(n);
}

int ctpu_task_cpu(uint64_t* tids, double* seconds, int cap) {
    DIR* d = opendir("/proc/self/task");
    if (d == nullptr) return -errno;
    static const double tick = 1.0 / double(sysconf(_SC_CLK_TCK));
    int n = 0;
    int from_sched = -1;  // decided by the first task that answers
    char path[64], buf[1024];
    while (struct dirent* e = readdir(d)) {
        if (e->d_name[0] < '0' || e->d_name[0] > '9') continue;
        double cpu = -1.0;
        if (from_sched != 0) {
            std::snprintf(path, sizeof path, "/proc/self/task/%s/schedstat",
                          e->d_name);
            if (read_small(path, buf, sizeof buf) > 0) {
                cpu = double(std::strtoull(buf, nullptr, 10)) * 1e-9;
                from_sched = 1;
            } else if (from_sched < 0) {
                from_sched = 0;
            }
        }
        if (from_sched == 0) {
            std::snprintf(path, sizeof path, "/proc/self/task/%s/stat",
                          e->d_name);
            if (read_small(path, buf, sizeof buf) > 0) {
                // the name may hold spaces and brackets: fields are
                // counted from the LAST ')'; utime and stime are the
                // 12th and 13th after it
                const char* p = std::strrchr(buf, ')');
                unsigned long long ut = 0, st = 0;
                if (p != nullptr && std::sscanf(
                        p + 1,
                        " %*c %*d %*d %*d %*d %*d %*u %*u %*u %*u %*u"
                        " %llu %llu", &ut, &st) == 2)
                    cpu = double(ut + st) * tick;
            }
        }
        if (cpu < 0.0) continue;  // the task ended under the scan
        if (n < cap) {
            tids[n] = std::strtoull(e->d_name, nullptr, 10);
            seconds[n] = cpu;
        }
        n++;
    }
    closedir(d);
    return n;
}

}  // extern "C"
