// Native aggregation/optimizer kernels for the host-side PS data plane.
//
// The reference runs server aggregation and optimizer math through MXNet's
// engine-scheduled C++ kernels (reference: kvstore_dist_server.h:1296
// merged += recved via elemwise ops, src/operator/tensor/
// elemwise_binary_op-inl.h; optimizer steps in C++ for the built-ins).
// Our server's hot loop is numpy, which holds the GIL for these sizes —
// flattening multi-key throughput no matter how the locking is arranged.
// ctypes calls release the GIL, so these plain-C loops restore true
// thread scaling for concurrent per-key handling.
//
// Build: g++ -O3 -ffp-contract=off -std=c++17 -fPIC -shared
// (geomx_tpu/kernels_native.py,
// same on-demand pattern as the transport core).

#include <cstdint>
#include <cstring>
#include <cmath>

extern "C" {

// dst += src
void gxk_acc(float* dst, const float* src, int64_t n) {
    for (int64_t i = 0; i < n; ++i) dst[i] += src[i];
}

// dst = src (with cast-free fp32 copy)
void gxk_copy(float* dst, const float* src, int64_t n) {
    for (int64_t i = 0; i < n; ++i) dst[i] = src[i];
}

// dst = a * dst + src
void gxk_scale_acc(float* dst, float a, const float* src, int64_t n) {
    for (int64_t i = 0; i < n; ++i) dst[i] = a * dst[i] + src[i];
}

// SGD with optional momentum buffer and weight decay:
//   g' = g + wd * w;  m = mom * m + g';  w -= lr * m      (mom != 0)
//   w -= lr * g'                                           (mom == 0)
void gxk_sgd(float* w, const float* g, float* mom_buf, float lr,
             float momentum, float wd, int64_t n) {
    if (mom_buf && momentum != 0.0f) {
        for (int64_t i = 0; i < n; ++i) {
            float gi = g[i] + wd * w[i];
            mom_buf[i] = momentum * mom_buf[i] + gi;
            w[i] -= lr * mom_buf[i];
        }
    } else {
        for (int64_t i = 0; i < n; ++i) {
            float gi = g[i] + wd * w[i];
            w[i] -= lr * gi;
        }
    }
}

// Adam step (bias-corrected), t is the POST-increment step count.
void gxk_adam(float* w, const float* g, float* m, float* v, float lr,
              float b1, float b2, float eps, float wd, int64_t t,
              int64_t n) {
    float bc1 = 1.0f - std::pow(b1, (float)t);
    float bc2 = 1.0f - std::pow(b2, (float)t);
    for (int64_t i = 0; i < n; ++i) {
        float gi = g[i] + wd * w[i];
        m[i] = b1 * m[i] + (1.0f - b1) * gi;
        v[i] = b2 * v[i] + (1.0f - b2) * gi * gi;
        float mh = m[i] / bc1;
        float vh = v[i] / bc2;
        w[i] -= lr * mh / (std::sqrt(vh) + eps);
    }
}

// The party server's Bi-Sparse pass over one key
// (compression.bsc_compress runs it as numpy passes: u *= m, the pairs
// added into u, v += u, a boundary from |v| at sampled positions, the
// positions with |v| >= boundary, their values, v and u cleared there)
// in two calls: the sample read ahead, then one sweep in which a block
// of u and v is decayed, gets its pairs, is accumulated, compared and
// cleared where it was selected while it is in cache, so u and v are
// read and written once. Every element sees the float32 operations of
// the numpy passes in their order: one rounding a step, no fused
// multiply-add (-ffp-contract=off, and no clone is built with FMA).
//
// The pairs: pidx[np] ascending in [0, n), pvals beside it, added into u
// one by one in their order (a repeated position gets its values one
// after the other).
#if defined(__x86_64__) && defined(__GNUC__) && !defined(__clang__)
#define GXK_CLONES __attribute__((target_clones("avx2", "default")))
#else
#define GXK_CLONES
#endif

// out[ns] = |v + (m * u + g)| at the positions pos[ns] (ascending,
// distinct, in [0, n)): what the sweep below leaves in v there.
void gxk_bsc_sample(const float* u, const float* v, float m,
                    const int64_t* pidx, const float* pvals, int64_t np,
                    const int64_t* pos, int64_t ns, float* out) {
    int64_t p = 0;
    for (int64_t s = 0; s < ns; ++s) {
        const int64_t at = pos[s];
        float x = u[at] * m;
        while (p < np && pidx[p] < at) ++p;
        for (; p < np && pidx[p] == at; ++p) x += pvals[p];
        out[s] = std::fabs(v[at] + x);
    }
}

// The sweep. The first `cap` positions, ascending, with |v| >= boundary
// (a NaN never is) go to out_idx[cap], their values to out_val[cap], and
// v and u are cleared there; returns how many.
GXK_CLONES
int64_t gxk_bsc_sweep(float* __restrict u, float* __restrict v, int64_t n,
                      float m, const int64_t* pidx, const float* pvals,
                      int64_t np, float boundary, int32_t* out_idx,
                      float* out_val, int64_t cap) {
    const int64_t B = 2048;             // 16 KB of u and v a block
    uint8_t flag[B + 8];
    int64_t p = 0, cnt = 0;
    for (int64_t lo = 0; lo < n; lo += B) {
        const int64_t len = n - lo < B ? n - lo : B;
        float* __restrict ub = u + lo;
        float* __restrict vb = v + lo;
        for (int64_t i = 0; i < len; ++i) ub[i] *= m;
        for (; p < np && pidx[p] < lo + len; ++p) u[pidx[p]] += pvals[p];
        if (cnt >= cap) {
            for (int64_t i = 0; i < len; ++i) vb[i] += ub[i];
            continue;
        }
        for (int64_t i = 0; i < len; ++i) {
            const float x = vb[i] + ub[i];
            vb[i] = x;
            flag[i] = std::fabs(x) >= boundary;
        }
        std::memset(flag + len, 0, 8);
        for (int64_t i = 0; i < len; i += 8) {
            uint64_t any;
            std::memcpy(&any, flag + i, 8);
            if (!any) continue;
            for (int64_t j = i; j < i + 8 && cnt < cap; ++j) {
                if (!flag[j]) continue;
                out_idx[cnt] = (int32_t)(lo + j);
                out_val[cnt++] = vb[j];
                vb[j] = 0.0f;
                ub[j] = 0.0f;
            }
        }
    }
    return cnt;
}

}  // extern "C"

// A sparse payload's positions as the gaps between them (the party-
// global link's positions part; compression/entries.py has the numpy
// form, which is the reference): the first position, then each
// position's difference to the one before it, every one an unsigned
// LEB128 varint (7 bits a byte, low bits first, the high bit says
// "more"). The positions ascend strictly, so a gap after the first is
// at least 1 and a zero byte there can only be padding.
//
// idx[n] (int32, or int64 where `wide`) -> out[cap]. A varint of g
// takes at most 1 + g / 128 bytes, so ascending positions fit
// n + idx[n-1] / 128 + 10 (and 10 n + 10 in any case). Returns the
// bytes written, or -1, whatever was written, where a position is
// negative or not above the one before it (which is also how `out` can
// come to be too short).
template <typename T>
static int64_t idx_encode(const T* idx, int64_t n, uint8_t* out,
                          int64_t cap) {
    uint8_t* p = out;
    const uint8_t* const full = out + cap - 10;
    int64_t prev = -1;
    for (int64_t i = 0; i < n; ++i) {
        const int64_t at = (int64_t)idx[i];
        if (at <= prev || p > full) return -1;
        uint64_t g = (uint64_t)(i ? at - prev : at);
        prev = at;
        if (g < 0x4000) {
            // one byte or two, which at 1% is a coin the processor
            // cannot call: no branch on it (the second byte is written
            // either way and the next gap overwrites it where it was
            // not needed)
            const uint64_t two = g >= 0x80;
            p[0] = (uint8_t)(g | (two << 7));
            p[1] = (uint8_t)(g >> 7);
            p += 1 + two;
            continue;
        }
        while (g >= 0x80) {
            *p++ = (uint8_t)(g | 0x80);
            g >>= 7;
        }
        *p++ = (uint8_t)g;
    }
    return p - out;
}

// buf[len] -> exactly n positions in [0, size), out[n]. 0, or what was
// wrong: 1 the buffer ends inside the list, 2 a varint of more than 64
// bits, 3 a gap of 0 after the first position, 4 a position >= size,
// 5 more than padding (up to three zero bytes) behind the n-th.
template <typename T>
static int64_t idx_decode(const uint8_t* buf, int64_t len, int64_t n,
                          int64_t size, T* out) {
    const uint8_t* p = buf;
    const uint8_t* const end = buf + len;
    if (n > 0 && size <= 0) return 4;
    uint64_t room = (uint64_t)size - 1;     // how far the next may reach
    uint64_t pos = 0;
    int64_t i = 0;
    while (i < n) {
        uint64_t g;
        if (i && n - i >= 4 && end - p >= 8) {
            // four gaps out of one 8-byte load where none of them is
            // longer than two bytes (no two neighbours with the high
            // bit set): the next gap's bytes come out of the register,
            // not out of a load that waits for this gap's length
            uint64_t w;
            std::memcpy(&w, p, 8);
            const uint64_t more = w & 0x8080808080808080ull;
            if (!(more & (more >> 8))) {
                int64_t used = 0;
                for (int k = 0; k < 4; ++k) {
                    const uint64_t two = (w >> 7) & 1;
                    g = (w & 0x7f) | ((w >> 8) & (0x7f * two)) << 7;
                    w >>= 8 << two;
                    used += 1 + two;
                    if (!g) return 3;
                    if (g > room) return 4;
                    room -= g;
                    pos += g;
                    out[i + k] = (T)pos;
                }
                p += used;
                i += 4;
                continue;
            }
        }
        // the first gap, the last few, and any gap among longer ones
        if (p >= end) return 1;
        uint64_t b = *p++;
        g = b & 0x7f;
        for (int shift = 7; b & 0x80; shift += 7) {
            if (p >= end) return 1;
            b = *p++;
            if (shift > 63 || (shift == 63 && (b & 0x7e))) return 2;
            g |= (b & 0x7f) << shift;
        }
        if (i && !g) return 3;
        if (g > room) return 4;
        room -= g;
        pos += g;
        out[i++] = (T)pos;
    }
    if (end - p > 3 || (!n && end != p)) return 5;
    for (; p < end; ++p)
        if (*p) return 5;
    return 0;
}

// Whether p[n] ascends strictly: one pass the compiler vectorises.
template <typename T>
static bool ascends(const T* p, int64_t n) {
    int bad = 0;
    for (int64_t i = 1; i < n; ++i) bad |= p[i] <= p[i - 1];
    return !bad;
}

// The sum of two sparse aggregates of one range (compression/entries.py
// ``Entries.merge``, whose numpy chain is the reference: concatenate, a
// stable argsort, two gathers, a run-sum): a[na] and b[nb] are positions
// that ascend strictly, va and vb the float32 values beside them; the
// merged list goes to oi / ov (room for na + nb). A position both hold
// gets va + vb in float32, a's term first (a is the earlier arriver, as
// the stable sort kept it); every other value is copied bit for bit, so
// it moves as the integer it is and the choice of side is a conditional
// move, not a branch the processor cannot call: only "both hold it" is a
// branch, and that is rare. Returns the entries written, or -1, nothing
// of use written, where a list does not ascend strictly (one vector pass
// over each before the merge).
template <typename T>
static int64_t entries_merge(const T* a, const float* va, int64_t na,
                             const T* b, const float* vb, int64_t nb,
                             T* oi, float* ov) {
    if (!ascends(a, na) || !ascends(b, nb)) return -1;
    int64_t i = 0, j = 0, k = 0;
    while (i < na && j < nb) {
        const T x = a[i], y = b[j];
        if (x == y) {
            oi[k] = x;
            ov[k++] = va[i++] + vb[j++];
            continue;
        }
        const bool first = x < y;
        uint32_t ua, ub;
        std::memcpy(&ua, va + i, 4);
        std::memcpy(&ub, vb + j, 4);
        const uint32_t bits = first ? ua : ub;
        oi[k] = first ? x : y;
        std::memcpy(ov + k, &bits, 4);
        i += first;
        j += !first;
        ++k;
    }
    const int64_t left = i < na ? na - i : nb - j;
    std::memcpy(oi + k, i < na ? a + i : b + j, left * sizeof(T));
    std::memcpy(ov + k, i < na ? va + i : vb + j, left * sizeof(float));
    return k + left;
}

extern "C" {

int64_t gxk_idx_encode(const void* idx, int64_t n, int wide, uint8_t* out,
                       int64_t cap) {
    return wide ? idx_encode((const int64_t*)idx, n, out, cap)
                : idx_encode((const int32_t*)idx, n, out, cap);
}

int64_t gxk_idx_decode(const uint8_t* buf, int64_t len, int64_t n,
                       int64_t size, int wide, void* out) {
    return wide ? idx_decode(buf, len, n, size, (int64_t*)out)
                : idx_decode(buf, len, n, size, (int32_t*)out);
}

int64_t gxk_entries_merge(const void* a, const float* va, int64_t na,
                          const void* b, const float* vb, int64_t nb,
                          int wide, void* oi, float* ov) {
    return wide ? entries_merge((const int64_t*)a, va, na,
                                (const int64_t*)b, vb, nb, (int64_t*)oi, ov)
                : entries_merge((const int32_t*)a, va, na,
                                (const int32_t*)b, vb, nb, (int32_t*)oi, ov);
}

}  // extern "C"
