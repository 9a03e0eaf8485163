/* One-pass scan of a Commit's repeated `signatures` field into columns.
 *
 * Commit.from_proto (types/commit.py) spent ~12 us a vote in the
 * generic decoder (a generator hop, two FieldReader dicts, a dozen
 * Python-level varint calls) on entries whose layout never varies:
 *
 *   22 len ( 08 flag  12 len addr  1a len ( 08 secs  10 nanos )  22 len sig )
 *
 * This file walks that layout once and reports, per entry, the flag,
 * the address and signature as byte ranges of the input (Python slices
 * them, so any length the generic decoder accepts is carried
 * unchanged) and the timestamp as seconds * 10**9 + nanos.
 *
 * The accept set is what CommitSig.to_proto / gogoproto can emit, and
 * nothing else: fields in ascending order, each at most once, with the
 * schema's wire types; every varint minimal and, for a scalar, nonzero
 * (proto3 omits zeros); the flag one byte; a bytes field nonempty;
 * the timestamp message present or absent, possibly empty, its nanos
 * below 10**9 and its nanosecond total inside int64. Before the
 * entries the outer message may carry fields 1, 2 (varints) and 3
 * (bytes) in that order, each at most once: they are only skipped
 * here (`head_end` is where the entries start) and decoded in Python.
 * Anything else returns -1 and the whole commit goes through the
 * generic decoder, which thereby keeps defining every edge and every
 * error.
 *
 * Bounded by the input: every read is checked against the end of its
 * enclosing message, a length is compared with the bytes that remain
 * before it is used, and at most `cap` entries are written (the caller
 * sizes the columns from len(data) / 2: an entry is at least two
 * bytes). No allocation, no global state.
 *
 * Compiled on demand by tendermint_tpu.native (cc -O3 -shared), called
 * through ctypes; differential-tested against the generic decoder in
 * tests/test_types.py and swept under ASAN by scripts/asan_check.py.
 */
#include <stdint.h>

#define NOT_CANONICAL (-1L)

/* columns, in the order the caller unpacks them */
enum { C_FLAG, C_ADDR0, C_ADDR1, C_TS, C_SIG0, C_SIG1, N_COLS };

/* A minimal varint of at most 64 bits at d[*p], *p < end checked by
 * the reader. Returns 0 and advances *p, or -1 on truncation, a
 * redundant trailing zero group, or a value past 64 bits. */
static int read_varint(const uint8_t *d, long end, long *p, uint64_t *out)
{
    uint64_t v = 0;
    long at = *p;
    for (int shift = 0; shift < 70; shift += 7) {
        if (at >= end) return -1;
        uint8_t b = d[at++];
        if (shift == 63 && b > 1) return -1; /* past 64 bits */
        v |= (uint64_t)(b & 0x7F) << shift;
        if (!(b & 0x80)) {
            if (b == 0 && shift) return -1; /* not minimal */
            *p = at;
            *out = v;
            return 0;
        }
    }
    return -1; /* more than ten bytes */
}

/* A length prefix at d[*p] and the range it announces, which must lie
 * inside [*p, end). Sets [*lo, *hi) and moves *p to hi. */
static int read_range(const uint8_t *d, long end, long *p, long *lo, long *hi)
{
    uint64_t len;
    if (read_varint(d, end, p, &len)) return -1;
    if (len > (uint64_t)(end - *p)) return -1;
    *lo = *p;
    *hi = *p + (long)len;
    *p = *hi;
    return 0;
}

/* A present scalar: minimal varint, nonzero. */
static int read_scalar(const uint8_t *d, long end, long *p, uint64_t *out)
{
    return (read_varint(d, end, p, out) || *out == 0) ? -1 : 0;
}

/* google.protobuf.Timestamp body in [p, end) -> nanoseconds. */
static int read_timestamp(const uint8_t *d, long p, long end, int64_t *ns)
{
    uint64_t secs = 0, nanos = 0;
    __int128 total;
    if (p < end && d[p] == 0x08) {
        p++;
        if (read_scalar(d, end, &p, &secs)) return -1;
    }
    if (p < end && d[p] == 0x10) {
        p++;
        if (read_scalar(d, end, &p, &nanos)) return -1;
    }
    if (p != end || nanos >= 1000000000u) return -1;
    /* seconds is an int64 field: two's complement of the varint. The
     * product alone may leave int64 where the total does not (-2**63
     * is seconds -9223372037 and nanos 145224192), so sum in 128 bits */
    total = (__int128)(int64_t)secs * 1000000000 + (int64_t)nanos;
    if (total < INT64_MIN || total > INT64_MAX) return -1;
    *ns = (int64_t)total;
    return 0;
}

/* One CommitSig body in [p, end) -> row `i` of the columns. */
static int read_entry(const uint8_t *d, long p, long end,
                      int64_t *cols, long cap, long i)
{
    long lo, hi;
    int64_t flag = 0, ts = 0;
    long addr0 = p, addr1 = p, sig0 = p, sig1 = p;
    if (p < end && d[p] == 0x08) {
        if (p + 1 >= end) return -1;
        flag = d[p + 1];
        if (flag == 0 || flag > 0x7F) return -1;
        p += 2;
    }
    if (p < end && d[p] == 0x12) {
        p++;
        if (read_range(d, end, &p, &addr0, &addr1) || addr0 == addr1)
            return -1;
    }
    if (p < end && d[p] == 0x1a) {
        p++;
        if (read_range(d, end, &p, &lo, &hi)) return -1;
        if (read_timestamp(d, lo, hi, &ts)) return -1;
    }
    if (p < end && d[p] == 0x22) {
        p++;
        if (read_range(d, end, &p, &sig0, &sig1) || sig0 == sig1)
            return -1;
    }
    if (p != end) return -1;
    cols[C_FLAG * cap + i] = flag;
    cols[C_ADDR0 * cap + i] = addr0;
    cols[C_ADDR1 * cap + i] = addr1;
    cols[C_TS * cap + i] = ts;
    cols[C_SIG0 * cap + i] = sig0;
    cols[C_SIG1 * cap + i] = sig1;
    return 0;
}

/* Scans the `n` bytes of an encoded Commit. `cols` is N_COLS rows of
 * `cap` int64 each (row-major). Returns the number of entries written
 * (column c of entry i at cols[c * cap + i]) and sets *head_end to the
 * offset of the first entry, or returns -1: not canonical, decode it
 * generically. */
long tm_commit_scan(const uint8_t *d, long n, int64_t *cols, long cap,
                    long *head_end)
{
    long p = 0, lo, hi, count = 0;
    uint64_t skipped;
    if (n < 0 || cap < 0) return NOT_CANONICAL;
    if (p < n && d[p] == 0x08) {
        p++;
        if (read_varint(d, n, &p, &skipped)) return NOT_CANONICAL;
    }
    if (p < n && d[p] == 0x10) {
        p++;
        if (read_varint(d, n, &p, &skipped)) return NOT_CANONICAL;
    }
    if (p < n && d[p] == 0x1a) {
        p++;
        if (read_range(d, n, &p, &lo, &hi)) return NOT_CANONICAL;
    }
    *head_end = p;
    while (p < n) {
        if (d[p] != 0x22 || count >= cap) return NOT_CANONICAL;
        p++;
        if (read_range(d, n, &p, &lo, &hi)) return NOT_CANONICAL;
        if (read_entry(d, lo, hi, cols, cap, count)) return NOT_CANONICAL;
        count++;
    }
    return count;
}
