// Native padded-batch assembly for the TPU device bridge.
//
// The reference pipeline ends at host CSR views (RowBlockIter, reference
// include/dmlc/data.h:267); the TPU-native pipeline must emit *static-shape*
// batches (fixed rows per batch, nnz buckets from the eighth-of-an-octave
// ladder of nnz_bucket.h) so XLA compiles a bounded set of programs (SURVEY
// §7 hard part 1, "ragged → device").
//
// This module does that reshaping in C++ on the parser side of the ctypes
// boundary: Python asks for the next batch's metadata (row count, nnz
// bucket), allocates numpy arrays of exactly that shape, and the Fill* call
// writes them in one pass — no per-block numpy concatenation, padding, or
// fancy indexing on the (GIL-holding) Python thread.
//
// Zero-copy discipline (reference src/data/parser.h:95-109): parsed blocks
// are MOVED from the parser (Parser::NextBlockMove swap hand-off) into a
// deque and consumed through a (block, row) cursor — the only host copy of
// the parsed data is the final write into the caller's batch buffers.
// Normalization (implicit 1.0 values, default weights, typed csv values,
// qid/field sentinels) happens during that single write.
//
// Layouts match dmlc_core_tpu/tpu/device_iter.py:
//   CSR:   row/col/val [D, bucket]; per-nonzero local row segment ids with a
//          sacrificial padding segment id == R; label/weight [D*R] with
//          weight 0 marking padding rows; nrows [D].
//   Dense: x [D*R, F] zero-filled then scattered (the MXU on-ramp for
//          low-dimensional data, e.g. HIGGS's 28 columns), float32 or bf16.
#ifndef DCT_BATCHER_H_
#define DCT_BATCHER_H_

#include <cstdint>
#include <deque>
#include <memory>
#include <vector>

#include "col_slots.h"
#include "parser.h"

namespace dct {

class PaddedBatcher {
 public:
  // Takes ownership of parser. batch_rows must divide by num_shards.
  PaddedBatcher(Parser<uint32_t>* parser, uint64_t batch_rows,
                uint32_t num_shards, uint64_t min_nnz_bucket);

  // Stage the next batch. Returns false at end of data. On success:
  //   *take      true (unpadded) row count, <= batch_rows
  //   *bucket    per-shard nnz capacity (NnzBucket of the max shard nnz;
  //              for a short last batch no lower than the batch before it)
  //   *max_index running max feature id (drives the dense/csr auto choice)
  //   *has_qid   1 when any parsed block carried query/group ids
  //   *has_field 1 when any parsed block carried per-nonzero field ids
  bool NextMeta(uint64_t* take, uint64_t* bucket, uint64_t* max_index,
                int* has_qid, int* has_field);

  // Consume the staged batch into caller buffers (shapes per header
  // comment). qid is [batch_rows] int32 group ids (-1 on padding rows and
  // rows from qid-less blocks — the sentinel can't collide with a real
  // qid:0) and field is [D, bucket] int32 per-nonzero field ids (0 on
  // padding nonzeros); either may be null to skip (reference data.h:174-236
  // carries both on RowBlock — this is their device-layout continuation).
  void FillCSR(int32_t* row, int32_t* col, float* val, float* label,
               float* weight, int32_t* nrows, int32_t* qid = nullptr,
               int32_t* field = nullptr);
  // x is [batch_rows, num_features], zeroed here before scatter. x_dtype
  // selects the element store: 0 = float32, 1 = bfloat16 (uint16 storage,
  // round-to-nearest-even) — the MXU-native dtype; emitting bf16 here halves
  // both the host fill bytes and the host->HBM transfer bytes and removes
  // the numpy astype copy from the Python side. Field ids have no dense
  // representation; use the CSR layout for field-aware models.
  void FillDense(void* x, int x_dtype, uint64_t num_features, float* label,
                 float* weight, int32_t* nrows, int32_t* qid = nullptr);

  // Fused packed-batch fill: ONE pass writes the shard-major transfer
  // packs the device lane ships as-is, so Python never touches a plane.
  //   big [D, kb, bucket] int32  per shard: row, slot, [val f32 bits when
  //                              val_dtype==0], [field]; an entry's column
  //                              is cols[slot] of the shard's distinct
  //                              list, which FillCols writes (col_slots.h)
  //   val [D, bucket] uint16     bf16 values, only when val_dtype==1 (the
  //                              separate leaf keeps the pack int32-pure)
  //   aux [D, ka, R] int32       per shard: label bits, weight bits,
  //                              [qid], nrows plane ([d, last, 0] = shard
  //                              d's true row count)
  // kb/ka pin the caller's plane layout (kb = 2 + (val_dtype==0)
  // + has_field, ka = 3 + has_qid — validated here); nrows [D] is the
  // host-side copy of the per-shard counts. Writing straight into the
  // caller's recyclable 64-byte-aligned staging buffers is what makes the
  // downstream device_put zero-copy (device_iter.py `_device_put`).
  void FillPacked(int32_t* big, int32_t kb, void* val, int32_t val_dtype,
                  int32_t* aux, int32_t ka, int32_t* nrows);
  // The distinct-column lists of the batch FillPacked last wrote: their
  // capacity (the ladder rung of the fullest shard's count, same floor as
  // the nnz bucket; a short last batch's no lower than the batch before
  // it, nnz_bucket.h TailRung), the batch's count of distinct columns, and
  // the [D, capacity] lists themselves, padded as col_slots.h says.
  uint64_t ColsCapacity() const { return cols_cap_; }
  // Whether that batch was a short one sent at the rungs of the batch
  // before it, its own being lower (either capacity).
  bool TailLifted() const { return lifted_; }
  uint64_t ColsDistinct() const { return slots_.Distinct(); }
  // Key-range owners of the columns (col_slots.h "Owners"): set before
  // the first batch; the lists are then owner-major, ColsCapacity() all the
  // owners' stretches together, and ColsOwnerMax() the fullest owner's
  // count of the batch's distinct columns.
  void SetColOwners(uint32_t owners, uint64_t range) {
    slots_.SetOwners(owners, range);
  }
  uint64_t ColsOwnerMax() const { return slots_.OwnerMax(); }
  void FillCols(int32_t* cols, uint64_t cap) const {
    slots_.Write(cols, cap);
  }
  // Dense twin: x as FillDense, label/weight/qid/nrows fused into the
  // shard-major aux pack.
  void FillDensePacked(void* x, int x_dtype, uint64_t num_features,
                       int32_t* aux, int32_t ka, int32_t* nrows);

  void BeforeFirst();
  size_t BytesRead() const { return parser_->BytesRead(); }
  // Real nonzeros of the batch NextMeta last staged, all shards (the sum
  // it takes to find the fullest shard): with num_shards * bucket, the
  // device lane's fill share.
  uint64_t BatchNnz() const { return batch_nnz_; }
  // Pin the shuffle permutation the next BeforeFirst samples (mid-epoch
  // resume; Parser::SetShuffleEpoch). False when nothing shuffles.
  bool SetShuffleEpoch(unsigned epoch) {
    return parser_->SetShuffleEpoch(epoch);
  }

 private:
  // pending parsed blocks in arrival order; the front is partially
  // consumed up to row_in_front_
  using Block = RowBlockContainer<uint32_t>;

  void Accumulate();           // move parser blocks in until a batch pends
  // Visit the staged batch's rows as (block, row range) segments:
  // fn(block, r0, r1, out_row) covers block-local rows [r0, r1) landing at
  // batch rows [out_row, out_row + (r1-r0)).
  template <typename Fn>
  void ForEachRowRange(uint64_t skip, uint64_t count, Fn&& fn) const;
  template <typename T>
  void FillDenseT(T* x, uint64_t num_features);  // zero + scatter, typed
  void FillQid(int32_t* qid);  // staged qid column (or the -1 sentinel)
  void FillRowArrays(float* label, float* weight, int32_t* nrows);
  // One shard's nonzero planes (row segment ids, cols, fields) with the
  // value store abstracted out: copy_vals(block, p0, written, n) writes n
  // normalized values, pad_vals(written) zeroes [written, bucket_). Shared
  // by FillCSR (f32 planes) and FillPacked (f32-in-big or separate bf16).
  // Returns the shard's count of real entries.
  template <typename CopyVals, typename PadVals>
  uint64_t FillShardNnz(uint32_t d, int32_t* rowd, int32_t* cold,
                    int32_t* fieldd, CopyVals&& copy_vals,
                    PadVals&& pad_vals);
  // Shard-major row-wise planes of the packed layout: label/weight bits,
  // optional qid, and the nrows plane, plus the host-side nrows[D] copy.
  void FillRowWisePacked(int32_t* aux, int32_t ka, int32_t* nrows);
  void Consume();              // pop the staged rows off the deque
  // nnz of block-local rows [r0, r1)
  static uint64_t RowRangeNnz(const Block& b, uint64_t r0, uint64_t r1) {
    return b.offset[r1] - b.offset[r0];
  }
  // value of nonzero k of `b` under dtype/implicit-1.0 normalization
  static float ValueAt(const Block& b, uint64_t k) {
    if (b.value_dtype == 1) return static_cast<float>(b.value_i32[k]);
    if (b.value_dtype == 2) return static_cast<float>(b.value_i64[k]);
    return b.value.empty() ? 1.0f : b.value[k];
  }

  std::unique_ptr<Parser<uint32_t>> parser_;
  const uint64_t batch_rows_;
  const uint32_t num_shards_;
  const uint64_t min_bucket_;

  std::deque<Block> blocks_;
  // consumed blocks parked here (cleared, capacity kept) and fed back as
  // NextBlockMove out-arguments, so the swap hand-off really does recycle
  // buffer capacity end-to-end instead of reallocating per chunk
  std::vector<Block> spares_;
  uint64_t row_in_front_ = 0;  // consumed rows of blocks_.front()
  uint64_t avail_rows_ = 0;    // unconsumed rows across the deque
  bool done_ = false;
  bool have_qid_ = false;
  bool have_field_ = false;
  uint64_t max_index_ = 0;

  // staged by NextMeta for the following Fill* call
  uint64_t take_ = 0;
  uint64_t bucket_ = 0;
  uint64_t batch_nnz_ = 0;
  bool staged_ = false;
  ColSlots slots_;  // of the batch FillPacked last wrote
  uint64_t cols_cap_ = 0;     // its lists' capacity
  bool lifted_ = false;       // the staged batch took the rungs before it
  // rungs of the batch before, this epoch (0: none), for TailRung
  uint64_t prev_bucket_ = 0;
  uint64_t prev_cols_ = 0;
};

}  // namespace dct

#endif  // DCT_BATCHER_H_
