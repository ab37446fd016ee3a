// Datadog series encode: the per-series work of
// DatadogColumnarEncoder.encode_bodies (core/egress.py) for a slice of one
// FlushSection. Called through ctypes.CDLL, so the GIL is released for
// the call and the POST worker's gzip and HTTP run beside it.
//
// Build: g++ -O3 -std=c++20 -shared -fPIC -o libvntddseries.so ddseries.cc
// (std::to_chars(double) needs libstdc++ >= 11).

#include <charconv>
#include <cmath>
#include <cstdint>
#include <cstring>

namespace {

// the most repr(float) writes: sign, 17 digits, '.', 'e', sign, 3 digits
constexpr int64_t kValueRoom = 32;

inline char* put(char* p, const char* s, int64_t n) {
    std::memcpy(p, s, static_cast<size_t>(n));
    return p + n;
}

// CPython's repr(float) (float_repr_style 'short'), and json.dumps'
// spellings of the non-finite values. The shortest round-trip digits come
// from std::to_chars in scientific form; the layout is repr's own rule
// (format_float_short, mode 'r'): fixed notation for -4 < decpt <= 16 with
// a trailing ".0" on integers, else d[.ddd]e+XX with at least two exponent
// digits.
char* fmt_repr(double v, char* p) {
    if (!std::isfinite(v)) {
        if (std::isnan(v)) return put(p, "NaN", 3);
        return v > 0 ? put(p, "Infinity", 8) : put(p, "-Infinity", 9);
    }
    char buf[kValueRoom];
    const char* end = std::to_chars(buf, buf + sizeof buf, v,
                                    std::chars_format::scientific).ptr;
    const char* s = buf;
    if (*s == '-') *p++ = *s++;
    char digits[20];
    int nd = 0;
    digits[nd++] = *s++;
    if (*s == '.')
        for (++s; *s != 'e'; ++s) digits[nd++] = *s;
    ++s;  // 'e'
    const bool exp_neg = *s++ == '-';
    int exp10 = 0;
    for (; s < end; ++s) exp10 = exp10 * 10 + (*s - '0');
    if (exp_neg) exp10 = -exp10;
    const int decpt = exp10 + 1;  // value = 0.d1d2... * 10^decpt

    if (decpt <= -4 || decpt > 16) {
        *p++ = digits[0];
        if (nd > 1) {
            *p++ = '.';
            p = put(p, digits + 1, nd - 1);
        }
        *p++ = 'e';
        *p++ = exp10 < 0 ? '-' : '+';
        int mag = exp10 < 0 ? -exp10 : exp10;
        if (mag >= 100) {
            *p++ = static_cast<char>('0' + mag / 100);
            mag %= 100;
        }
        *p++ = static_cast<char>('0' + mag / 10);
        *p++ = static_cast<char>('0' + mag % 10);
        return p;
    }
    if (decpt <= 0) {
        *p++ = '0';
        *p++ = '.';
        for (int i = decpt; i < 0; ++i) *p++ = '0';
        return put(p, digits, nd);
    }
    if (decpt >= nd) {
        p = put(p, digits, nd);
        for (int i = nd; i < decpt; ++i) *p++ = '0';
        *p++ = '.';
        *p++ = '0';
        return p;
    }
    p = put(p, digits, decpt);
    *p++ = '.';
    return put(p, digits + decpt, nd - decpt);
}

}  // namespace

extern "C" {

// Appends `n` series, comma-joined, to `out`: per row
//   base[beg[i]:end[i]] + mid + repr(vals[i]) + "]]}"
// where `mid` is the flush's `],"points":[[<ts>,` fragment. Prefixes
// that lie back to back pass their n + 1 offsets as `beg` and, one entry
// on, as `end`. Returns the bytes written, or -1 if `cap` could not hold
// them.
int64_t vnt_dd_series(const char* base, const int64_t* beg,
                      const int64_t* end, const double* vals, int64_t n,
                      const char* mid, int64_t mid_len,
                      char* out, int64_t cap) {
    if (n <= 0) return 0;
    int64_t need = n * (mid_len + kValueRoom + 4);
    for (int64_t i = 0; i < n; ++i) need += end[i] - beg[i];
    if (need > cap) return -1;
    char* p = out;
    for (int64_t i = 0; i < n; ++i) {
        if (i) *p++ = ',';
        p = put(p, base + beg[i], end[i] - beg[i]);
        p = put(p, mid, mid_len);
        p = fmt_repr(vals[i], p);
        p = put(p, "]]}", 3);
    }
    return p - out;
}

// The bytes vnt_dd_series needs room for beside the prefixes, per series.
int64_t vnt_dd_series_room(int64_t mid_len) {
    return mid_len + kValueRoom + 4;
}

// Which of `n` rows changed identity since the last flush: compares the
// element pointers of two object arrays (names, tags) with the kept ones.
// Writes the differing row indices to `out`, returns how many.
int64_t vnt_dd_changed_rows(const void* const* names,
                            const void* const* kept_names,
                            const void* const* tags,
                            const void* const* kept_tags,
                            int64_t n, int64_t* out) {
    int64_t d = 0;
    for (int64_t i = 0; i < n; ++i)
        if (names[i] != kept_names[i] || tags[i] != kept_tags[i])
            out[d++] = i;
    return d;
}

// The same against a row shelf, which keeps its objects by table row:
// names[i] against kept_names[rows[i]], tags[i] against kept_tags[rows[i]].
int64_t vnt_dd_changed_at(const void* const* names,
                          const void* const* tags,
                          const void* const* kept_names,
                          const void* const* kept_tags,
                          const int64_t* rows, int64_t n, int64_t* out) {
    int64_t d = 0;
    for (int64_t i = 0; i < n; ++i)
        if (names[i] != kept_names[rows[i]] || tags[i] != kept_tags[rows[i]])
            out[d++] = i;
    return d;
}

}  // extern "C"
