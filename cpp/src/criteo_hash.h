// The feature id of one cell of a Criteo click-log line: the one rule the
// `criteo` text format hashes by.
//
// A line of the Criteo Terabyte click logs is `label \t I1 .. I13 \t C1 ..
// C26`: 39 feature cells, numbered 0..38 in line order, an empty cell a
// missing value. Like dmlc/wormhole's criteo_parser.h the format hashes
// every present cell, integer cells too, AS BYTES, together with its
// column, and gives every feature the value 1. The hash is this repo's own
// (wormhole's is CityHash64 with the column in the top bits; no library is
// vendored for it): defined on bytes, unseeded, one multiply a group of
// eight bytes.
//
//   hash64(c, s[0..n)):
//     h = (c + 1) * 0x9E3779B97F4A7C15                       (mod 2^64)
//     for each group of 8 bytes of s, read as a little-endian word w
//     (the last group filled up with zero bytes):
//       h = (h ^ w) * 0xFF51AFD7ED558CCD;   h ^= h >> 32
//     h ^= n
//     h ^= h >> 33;  h *= 0xFF51AFD7ED558CCD
//     h ^= h >> 33;  h *= 0xC4CEB9FE1A85EC53
//     h ^= h >> 33                          (MurmurHash3's 64-bit finalizer)
//   id = fold(h, bits) = (h ^ (h >> 32)) & (2^bits - 1)
//
// Every step is a bijection of h, so two cells of one column that fit one
// word (all of the logs' 8-hex-digit categoricals, and every integer of up
// to 8 characters) differ before the fold; the column enters first, so
// equal strings in different columns differ; n enters last, so a cell and
// the same cell with zero bytes after it differ.
//
// Stated twice, here and in dmlc_core_tpu/data/criteo.py (the numpy oracle;
// doc/parsing.md has a worked id); tests/test_criteo_parser.py holds the
// two and benchmarks/reference/criteo.py equal, id for id.
#ifndef DCT_CRITEO_HASH_H_
#define DCT_CRITEO_HASH_H_

#include <cstddef>
#include <cstdint>
#include <cstring>

namespace dct {

constexpr int kCriteoColumns = 39;  // 13 integer + 26 categorical
constexpr int kCriteoCells = 40;    // the label and the columns

inline uint64_t CriteoWordLE(const char* s, size_t n) {
  uint64_t w = 0;
  std::memcpy(&w, s, n);
#if defined(__BYTE_ORDER__) && __BYTE_ORDER__ == __ORDER_BIG_ENDIAN__
  w = __builtin_bswap64(w);
#endif
  return w;
}

inline uint64_t CriteoHash64(uint32_t column, const char* s, size_t n) {
  constexpr uint64_t kMul = 0xFF51AFD7ED558CCDULL;
  uint64_t h = (static_cast<uint64_t>(column) + 1) * 0x9E3779B97F4A7C15ULL;
  for (size_t i = 0; i < n; i += 8) {
    h = (h ^ CriteoWordLE(s + i, n - i < 8 ? n - i : 8)) * kMul;
    h ^= h >> 32;
  }
  h ^= n;
  h ^= h >> 33;
  h *= kMul;
  h ^= h >> 33;
  h *= 0xC4CEB9FE1A85EC53ULL;
  h ^= h >> 33;
  return h;
}

inline uint64_t CriteoFold(uint64_t h, int bits) {
  return (h ^ (h >> 32)) & ((uint64_t(1) << bits) - 1);
}

}  // namespace dct

#endif  // DCT_CRITEO_HASH_H_
