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
// A part's short last batch. A byte-range part of a data set never holds a
// whole number of batches, so the last batch of every epoch has fewer real
// rows than batch_rows. Its rows are padded, but its own counts would land
// on lower rungs: a second compiled shape for one batch an epoch. So a
// batch with fewer real rows than batch_rows takes no rung below that of
// the batch before it in the same epoch (TailRung, for the nnz capacity and
// for the distinct-column list alike); the fill is the padding the ladder
// already uses. A full batch keeps its own rung, and so does a short batch
// with none before it.
//
// Both stated twice, here and in dmlc_core_tpu/tpu/device_iter.py
// (nnz_bucket, whose docstring has the trade, and tail_rung);
// tests/test_nnz_bucket.py holds the two equal.
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

// `own` is the batch's own rung, `before` the rung the batch before it in
// the epoch was sent at (0: there was none), `take` its count of real rows.
inline uint64_t TailRung(uint64_t own, uint64_t before, uint64_t take,
                         uint64_t batch_rows) {
  return take < batch_rows && before > own ? before : own;
}

}  // namespace dct

#endif  // DCT_NNZ_BUCKET_H_
