// GF(2^8) Reed-Solomon host codec with AVX2 PSHUFB nibble tables.
//
// This is the host-side equivalent of the reference's SIMD dependency
// (klauspost/reedsolomon, used from /root/reference/cmd/erasure-coding.go:63):
// multiplication by a constant c is two 16-entry table lookups (low/high
// nibble) XORed together; PSHUFB does 32 byte-lookups per instruction.
// Serves as the host codec: the CPU fallback when no TPU is attached, and
// under a device backend the tail blocks and inline objects.
//
// Field: polynomial 0x11D, generator 2 — identical to minio_tpu.ops.gf256.

#include <cstdint>
#include <cstring>

#if defined(__AVX2__)
#include <immintrin.h>
#endif

namespace {

uint8_t MUL[256][256];
uint8_t LOW_TBL[256][16];   // LOW_TBL[c][n]  = c * n
uint8_t HIGH_TBL[256][16];  // HIGH_TBL[c][n] = c * (n << 4)

struct TableInit {
  TableInit() {
    uint8_t exp[512];
    int log[256] = {0};
    int x = 1;
    for (int i = 0; i < 255; i++) {
      exp[i] = (uint8_t)x;
      log[x] = i;
      x <<= 1;
      if (x & 0x100) x ^= 0x11D;
    }
    for (int i = 255; i < 512; i++) exp[i] = exp[i - 255];
    for (int a = 0; a < 256; a++)
      for (int b = 0; b < 256; b++)
        MUL[a][b] = (a && b) ? exp[log[a] + log[b]] : 0;
    for (int c = 0; c < 256; c++)
      for (int n = 0; n < 16; n++) {
        LOW_TBL[c][n] = MUL[c][n];
        HIGH_TBL[c][n] = MUL[c][n << 4];
      }
  }
} table_init;

inline void mul_acc_scalar(uint8_t c, const uint8_t* src, uint8_t* dst,
                           size_t n, bool first) {
  const uint8_t* row = MUL[c];
  if (first) {
    for (size_t i = 0; i < n; i++) dst[i] = row[src[i]];
  } else {
    for (size_t i = 0; i < n; i++) dst[i] ^= row[src[i]];
  }
}

#if defined(__AVX2__)
// dst ^= c * src (or dst = c * src when first), 32 bytes per step.
inline void mul_acc_avx2(uint8_t c, const uint8_t* src, uint8_t* dst, size_t n,
                         bool first) {
  const __m256i lo_tbl = _mm256_broadcastsi128_si256(
      _mm_loadu_si128((const __m128i*)LOW_TBL[c]));
  const __m256i hi_tbl = _mm256_broadcastsi128_si256(
      _mm_loadu_si128((const __m128i*)HIGH_TBL[c]));
  const __m256i mask = _mm256_set1_epi8(0x0f);
  size_t i = 0;
  for (; i + 32 <= n; i += 32) {
    __m256i v = _mm256_loadu_si256((const __m256i*)(src + i));
    __m256i lo = _mm256_and_si256(v, mask);
    __m256i hi = _mm256_and_si256(_mm256_srli_epi64(v, 4), mask);
    __m256i prod = _mm256_xor_si256(_mm256_shuffle_epi8(lo_tbl, lo),
                                    _mm256_shuffle_epi8(hi_tbl, hi));
    if (!first)
      prod = _mm256_xor_si256(prod, _mm256_loadu_si256((const __m256i*)(dst + i)));
    _mm256_storeu_si256((__m256i*)(dst + i), prod);
  }
  if (i < n) mul_acc_scalar(c, src + i, dst + i, n - i, first);
}
#endif

}  // namespace

extern "C" {

// out[r] = XOR_k mat[r*k + j] * src[j]   (all rows length `n`)
// mat: rows x k coefficients; src: k contiguous shards of n bytes;
// out: rows contiguous shards of n bytes.
//
// Column-tiled so each 16 KiB source/destination tile stays cache-hot
// across the whole coefficient matrix: every source byte is pulled from
// RAM once per call instead of `rows` times (the row-major loop's RAM
// traffic limited large batches to ~0.7 GiB/s on a ~2 GB/s-bandwidth
// host; klauspost/reedsolomon tiles the same way for the same reason).
void gf256_matmul(const uint8_t* mat, int rows, int k, const uint8_t* src,
                  uint8_t* out, size_t n) {
  const size_t TILE = 16384;
  bool started[256];
  for (size_t off = 0; off < n; off += TILE) {
    const size_t len = (n - off < TILE) ? (n - off) : TILE;
    for (int r = 0; r < rows; r++) started[r] = false;
    for (int j = 0; j < k; j++) {
      const uint8_t* s = src + (size_t)j * n + off;
      for (int r = 0; r < rows; r++) {
        uint8_t c = mat[r * k + j];
        if (c == 0) continue;
        uint8_t* dst = out + (size_t)r * n + off;
#if defined(__AVX2__)
        mul_acc_avx2(c, s, dst, len, !started[r]);
#else
        mul_acc_scalar(c, s, dst, len, !started[r]);
#endif
        started[r] = true;
      }
    }
    for (int r = 0; r < rows; r++)
      if (!started[r]) memset(out + (size_t)r * n + off, 0, len);
  }
}

// Batched (B, K, S) -> (B, rows, S) codec call: src is B contiguous
// blocks of k shards, out is B contiguous blocks of `rows` outputs.
// Looping blocks INSIDE one call matters beyond convenience: the Python
// caller marshals arguments and releases the GIL once per chunk instead
// of once per block — 128 ctypes round trips per 32-block batch convoyed
// the GIL against the etag-hasher and shard-writer threads and tripled
// the apparent encode time under load (ISSUE 5 pipeline).
void gf256_matmul_batch(const uint8_t* mat, int rows, int k,
                        const uint8_t* src, uint8_t* out, size_t n,
                        size_t nblocks) {
  for (size_t b = 0; b < nblocks; b++) {
    gf256_matmul(mat, rows, k, src + b * (size_t)k * n,
                 out + b * (size_t)rows * n, n);
  }
}

// Convenience single multiply: dst = c * src.
void gf256_mul(uint8_t c, const uint8_t* src, uint8_t* dst, size_t n) {
#if defined(__AVX2__)
  mul_acc_avx2(c, src, dst, n, true);
#else
  mul_acc_scalar(c, src, dst, n, true);
#endif
}

int gf256_has_avx2(void) {
#if defined(__AVX2__)
  return 1;
#else
  return 0;
#endif
}

}  // extern "C"
