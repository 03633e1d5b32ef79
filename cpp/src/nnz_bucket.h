// The nnz capacity of a CSR batch: the one rule that chooses it.
//
// XLA compiles one program per batch shape, so a batch's per-shard nnz
// capacity (its "bucket") comes from a ladder, not from the batch's own
// count; the step's gathers and scatters cost per entry SENT, padding
// included (PERF.md section 5), so the ladder is fine: eight rungs an
// octave. For a fullest-shard count n above the floor, with p the smallest
// power of two >= n, the bucket is n rounded up to a multiple of p/16:
// padding stays under 12.5% of n, a power of two maps to itself, and the
// count of distinct shapes stays O(log max_nnz). The granule never falls
// under min(floor, 128) entries, so small shapes and 128-entry lane rows
// stay whole.
//
// Stated twice, here and in dmlc_core_tpu/tpu/device_iter.py (nnz_bucket,
// whose docstring has the trade); tests/test_nnz_bucket.py holds the two
// equal.
#ifndef DCT_NNZ_BUCKET_H_
#define DCT_NNZ_BUCKET_H_

#include <algorithm>
#include <cstdint>

namespace dct {

inline uint64_t NnzBucket(uint64_t n, uint64_t floor) {
  floor = std::max<uint64_t>(floor, 1);
  if (n <= floor) return floor;
  uint64_t p = 1;
  while (p < n) p <<= 1;
  const uint64_t g =
      std::max<uint64_t>(p >> 4, std::min<uint64_t>(floor, 128));
  return (n + g - 1) / g * g;
}

}  // namespace dct

#endif  // DCT_NNZ_BUCKET_H_
