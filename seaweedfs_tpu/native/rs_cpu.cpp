// C++ GF(2^8) linear-map kernel — CPU baseline for the TPU RS pipeline.
//
// Same role as the reference's native RS dependency (klauspost/reedsolomon,
// /root/reference go.mod:46): nibble-table GF(2^8) multiply-accumulate,
// vectorized with AVX2 byte shuffles when available. Field: poly 0x11D.
//
// Exposed C ABI (used from Python via ctypes, see rs_native.py):
//   gf_linear(matrix[o*k], o, k, shards[k*n], out[o*n], n)
//     out[oi] = XOR_i matrix[oi,i] (x)gf shards[i]   (row-major, contiguous)

#include <cstdint>
#include <cstring>

#if defined(__AVX2__)
#include <immintrin.h>
#endif

namespace {

constexpr int kPoly = 0x11D;

struct Tables {
  uint8_t mul[256][256];
  // nibble tables: mul_lo[c][x&15] ^ mul_hi[c][x>>4] == mul[c][x]
  uint8_t mul_lo[256][16];
  uint8_t mul_hi[256][16];
  Tables() {
    uint8_t exp[512];
    int log[256] = {0};
    int x = 1;
    for (int i = 0; i < 255; i++) {
      exp[i] = static_cast<uint8_t>(x);
      log[x] = i;
      x <<= 1;
      if (x & 0x100) x ^= kPoly;
    }
    for (int i = 255; i < 512; i++) exp[i] = exp[i - 255];
    for (int a = 0; a < 256; a++) {
      for (int b = 0; b < 256; b++) {
        mul[a][b] = (a && b) ? exp[(log[a] + log[b]) % 255] : 0;
      }
      for (int n = 0; n < 16; n++) {
        mul_lo[a][n] = mul[a][n];
        mul_hi[a][n] = mul[a][n << 4];
      }
    }
  }
};

const Tables kT;

void mul_acc_scalar(uint8_t c, const uint8_t* src, uint8_t* dst, long long n,
                    bool first) {
  const uint8_t* lo = kT.mul_lo[c];
  const uint8_t* hi = kT.mul_hi[c];
  if (first) {
    for (long long i = 0; i < n; i++)
      dst[i] = static_cast<uint8_t>(lo[src[i] & 15] ^ hi[src[i] >> 4]);
  } else {
    for (long long i = 0; i < n; i++)
      dst[i] ^= static_cast<uint8_t>(lo[src[i] & 15] ^ hi[src[i] >> 4]);
  }
}

#if defined(__AVX2__)
void mul_acc_avx2(uint8_t c, const uint8_t* src, uint8_t* dst, long long n,
                  bool first) {
  const __m256i lo = _mm256_broadcastsi128_si256(
      _mm_loadu_si128(reinterpret_cast<const __m128i*>(kT.mul_lo[c])));
  const __m256i hi = _mm256_broadcastsi128_si256(
      _mm_loadu_si128(reinterpret_cast<const __m128i*>(kT.mul_hi[c])));
  const __m256i mask = _mm256_set1_epi8(0x0F);
  long long i = 0;
  for (; i + 32 <= n; i += 32) {
    __m256i v = _mm256_loadu_si256(reinterpret_cast<const __m256i*>(src + i));
    __m256i vlo = _mm256_and_si256(v, mask);
    __m256i vhi = _mm256_and_si256(_mm256_srli_epi64(v, 4), mask);
    __m256i p = _mm256_xor_si256(_mm256_shuffle_epi8(lo, vlo),
                                 _mm256_shuffle_epi8(hi, vhi));
    if (!first) {
      p = _mm256_xor_si256(
          p, _mm256_loadu_si256(reinterpret_cast<const __m256i*>(dst + i)));
    }
    _mm256_storeu_si256(reinterpret_cast<__m256i*>(dst + i), p);
  }
  if (i < n) mul_acc_scalar(c, src + i, dst + i, n - i, first);
}
#endif

void mul_acc(uint8_t c, const uint8_t* src, uint8_t* dst, long long n,
             bool first) {
#if defined(__AVX2__)
  mul_acc_avx2(c, src, dst, n, first);
#else
  mul_acc_scalar(c, src, dst, n, first);
#endif
}

}  // namespace

extern "C" {

void gf_linear(const uint8_t* matrix, int out_rows, int k,
               const uint8_t* shards, uint8_t* out, long long n) {
  for (int o = 0; o < out_rows; o++) {
    uint8_t* dst = out + static_cast<long long>(o) * n;
    bool first = true;
    for (int i = 0; i < k; i++) {
      uint8_t c = matrix[o * k + i];
      if (c == 0) continue;
      if (c == 1) {
        const uint8_t* src = shards + static_cast<long long>(i) * n;
        if (first) {
          std::memcpy(dst, src, static_cast<size_t>(n));
        } else {
          long long j = 0;
#if defined(__AVX2__)
          for (; j + 32 <= n; j += 32) {
            __m256i a = _mm256_loadu_si256(
                reinterpret_cast<const __m256i*>(dst + j));
            __m256i b = _mm256_loadu_si256(
                reinterpret_cast<const __m256i*>(src + j));
            _mm256_storeu_si256(reinterpret_cast<__m256i*>(dst + j),
                                _mm256_xor_si256(a, b));
          }
#endif
          for (; j < n; j++) dst[j] ^= src[j];
        }
        first = false;
        continue;
      }
      mul_acc(c, shards + static_cast<long long>(i) * n, dst, n, first);
      first = false;
    }
    if (first) std::memset(dst, 0, static_cast<size_t>(n));
  }
}

// crc32 (IEEE, zlib-compatible) — needle checksum hot path.
// Slice-by-8 table driven; table built at load time (thread-safe static init).
struct CrcTables {
  uint32_t tab[8][256];
  CrcTables() {
    for (uint32_t i = 0; i < 256; i++) {
      uint32_t c = i;
      for (int j = 0; j < 8; j++) c = (c & 1) ? (0xEDB88320u ^ (c >> 1)) : (c >> 1);
      tab[0][i] = c;
    }
    for (uint32_t i = 0; i < 256; i++)
      for (int s = 1; s < 8; s++)
        tab[s][i] = (tab[s - 1][i] >> 8) ^ tab[0][tab[s - 1][i] & 0xFF];
  }
};
static const CrcTables kCrc;
#define crc_tab kCrc.tab

uint32_t crc32_ieee(uint32_t crc, const uint8_t* buf, long long n) {
  crc = ~crc;
  long long i = 0;
  for (; i + 8 <= n; i += 8) {
    crc ^= static_cast<uint32_t>(buf[i]) | (static_cast<uint32_t>(buf[i + 1]) << 8) |
           (static_cast<uint32_t>(buf[i + 2]) << 16) |
           (static_cast<uint32_t>(buf[i + 3]) << 24);
    crc = crc_tab[7][crc & 0xFF] ^ crc_tab[6][(crc >> 8) & 0xFF] ^
          crc_tab[5][(crc >> 16) & 0xFF] ^ crc_tab[4][crc >> 24] ^
          crc_tab[3][buf[i + 4]] ^ crc_tab[2][buf[i + 5]] ^
          crc_tab[1][buf[i + 6]] ^ crc_tab[0][buf[i + 7]];
  }
  for (; i < n; i++) crc = crc_tab[0][(crc ^ buf[i]) & 0xFF] ^ (crc >> 8);
  return ~crc;
}

// crc32c (Castagnoli) — the needle checksum flavor. Hardware SSE4.2 when
// available, slice-by-8 table fallback.
struct Crc32cTables {
  uint32_t tab[8][256];
  Crc32cTables() {
    for (uint32_t i = 0; i < 256; i++) {
      uint32_t c = i;
      for (int j = 0; j < 8; j++) c = (c & 1) ? (0x82F63B78u ^ (c >> 1)) : (c >> 1);
      tab[0][i] = c;
    }
    for (uint32_t i = 0; i < 256; i++)
      for (int s = 1; s < 8; s++)
        tab[s][i] = (tab[s - 1][i] >> 8) ^ tab[0][tab[s - 1][i] & 0xFF];
  }
};
static const Crc32cTables kCrcC;

#if defined(__SSE4_2__)
// The crc32 instruction takes three cycles and can start one every
// cycle, so one chain of 8-byte steps uses a third of it. Three blocks
// of kStride bytes are taken side by side instead, each from register
// 0 but the first, and joined: a register after a block D from r is
// zeros(r, |D|) ^ (the register after D from 0), zeros linear in r
// (the register run over |D| zero bytes). kShift tabulates
// zeros(., kStride) a byte of the register at a time.
constexpr long long kStride = 8192;

inline uint32_t crc32c_step(uint32_t crc, const uint8_t* p) {
  uint64_t v;
  std::memcpy(&v, p, 8);
  return static_cast<uint32_t>(_mm_crc32_u64(crc, v));
}

struct Crc32cShift {
  uint32_t tab[4][256];
  Crc32cShift() {
    static const uint8_t zeros[8] = {0};
    for (int k = 0; k < 4; k++)
      for (uint32_t b = 0; b < 256; b++) {
        uint32_t r = b << (8 * k);
        for (long long i = 0; i < kStride; i += 8) r = crc32c_step(r, zeros);
        tab[k][b] = r;
      }
  }
  uint32_t operator()(uint32_t r) const {
    return tab[0][r & 0xFF] ^ tab[1][(r >> 8) & 0xFF] ^
           tab[2][(r >> 16) & 0xFF] ^ tab[3][r >> 24];
  }
};
static const Crc32cShift kCrcShift;
#endif

uint32_t crc32c(uint32_t crc, const uint8_t* buf, long long n) {
  crc = ~crc;
  long long i = 0;
#if defined(__SSE4_2__)
  for (; i + 3 * kStride <= n; i += 3 * kStride) {
    const uint8_t* p = buf + i;
    uint32_t c0 = crc, c1 = 0, c2 = 0;
    for (long long j = 0; j < kStride; j += 8) {
      c0 = crc32c_step(c0, p + j);
      c1 = crc32c_step(c1, p + kStride + j);
      c2 = crc32c_step(c2, p + 2 * kStride + j);
    }
    crc = kCrcShift(kCrcShift(c0) ^ c1) ^ c2;
  }
  for (; i + 8 <= n; i += 8) crc = crc32c_step(crc, buf + i);
  for (; i < n; i++) crc = _mm_crc32_u8(crc, buf[i]);
#else
  for (; i + 8 <= n; i += 8) {
    crc ^= static_cast<uint32_t>(buf[i]) | (static_cast<uint32_t>(buf[i + 1]) << 8) |
           (static_cast<uint32_t>(buf[i + 2]) << 16) |
           (static_cast<uint32_t>(buf[i + 3]) << 24);
    crc = kCrcC.tab[7][crc & 0xFF] ^ kCrcC.tab[6][(crc >> 8) & 0xFF] ^
          kCrcC.tab[5][(crc >> 16) & 0xFF] ^ kCrcC.tab[4][crc >> 24] ^
          kCrcC.tab[3][buf[i + 4]] ^ kCrcC.tab[2][buf[i + 5]] ^
          kCrcC.tab[1][buf[i + 6]] ^ kCrcC.tab[0][buf[i + 7]];
  }
  for (; i < n; i++) crc = kCrcC.tab[0][(crc ^ buf[i]) & 0xFF] ^ (crc >> 8);
#endif
  return ~crc;
}

}  // extern "C"
