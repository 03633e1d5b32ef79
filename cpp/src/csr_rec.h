// Zero-rearrangement CSR ingest: RecordIO records that store col/val/
// row-length planes in (near-)final device batch layout.
//
// The "rec" lane (parser.cc RecParser) deserializes RowBlockContainers and
// re-batches them through PaddedBatcher — two full passes over the bytes
// (LoadAppend memcpy, then FillCSR copy + segment expansion). This lane is
// the CSR continuation of the dense_rec idea (dense_rec.h): the converter
// (dmlc_core_tpu/io/convert.py rows_to_csr_recordio) lays the data out so
// ingest is ONE pass — bulk memcpy of col/val spans straight into the
// packed batch planes plus a run-length expansion of row ids. Reference
// analog: RecordIOChunkReader zero-copy sub-partitioning
// (/root/reference/include/dmlc/recordio.h:166) — taken one step further
// by also fixing the layout on disk.
//
// Record payload (all little-endian):
//   u32 magic 'DRC1'   u32 flags (bit0 weight, bit1 qid, bit2 field)
//   u32 rows           u32 nwin
//   u64 nnz            u32 max_col   u32 reserved
//   u64 win_max[nwin]  // GLOBAL: max nnz over any 2^i consecutive rows
//   u32 row_len[rows]
//   f32 label[rows]    [f32 weight[rows]]  [i32 qid[rows]]
//   u32 col[nnz]       f32 val[nnz]        [u32 field[nnz]]
//
// The win_max table (stamped into every record, so any byte-range
// partition sees it) bounds the nnz of any R consecutive rows — the
// per-shard bucket becomes a STATIC property of (file, batch_rows,
// num_shards), computed once at Meta(): one compiled XLA shape per epoch
// and no per-batch meta round-trip.
#ifndef DCT_CSR_REC_H_
#define DCT_CSR_REC_H_

#include <cstdint>
#include <memory>
#include <string>
#include <vector>

#include "col_slots.h"
#include "input_split.h"

namespace dct {

constexpr uint32_t kCsrRecMagic = 0x44524331;  // 'DRC1'

class CsrRecBatcher {
 public:
  // batch_rows must divide by num_shards (device-axis reshape contract).
  CsrRecBatcher(const std::string& uri, unsigned part, unsigned npart,
                uint64_t batch_rows, uint32_t num_shards,
                uint64_t min_nnz_bucket);

  // Static batch shape, valid before any Fill: bucket is the per-shard
  // nnz capacity (NnzBucket of the window bound, floored at
  // min_nnz_bucket: nnz_bucket.h).
  void Meta(uint64_t* bucket, int* has_weight, int* has_qid, int* has_field);

  // Fill one batch into caller planes (PaddedBatcher::FillCSR layout):
  // row/col/val[/field] are [num_shards, bucket], label/weight[/qid] are
  // [batch_rows], nrows is [num_shards]. Padding: segment id R, col/val/
  // field 0, weight 0, qid -1. Returns the true row count; 0 at end.
  uint64_t Fill(int32_t* row, int32_t* col, float* val, int32_t* field,
                float* label, float* weight, int32_t* qid, int32_t* nrows);

  // Fused shard-major fill (PaddedBatcher::FillPacked layout, f32 values
  // in-pack since the record stores f32): big is [num_shards, kb, bucket]
  // int32 (row, slot, val bits, [field]; an entry's column is cols[slot]
  // of the shard's distinct list: col_slots.h), aux is [num_shards, ka, R]
  // int32 (label bits, weight bits, [qid], nrows plane). kb must be
  // 3 + has_field, ka must be 3 + has_qid. Returns the true row count;
  // 0 at end.
  uint64_t FillPacked(int32_t* big, int32_t kb, int32_t* aux, int32_t ka,
                      int32_t* nrows);
  // The distinct-column lists of the batch FillPacked last wrote, as
  // PaddedBatcher has them: capacity, count, the [num_shards, capacity]
  // lists.
  uint64_t ColsCapacity() const { return cols_cap_; }
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

  void BeforeFirst();
  size_t BytesRead() const { return bytes_read_; }
  // Real nonzeros of the batch the last Fill wrote, all shards (what the
  // fill counts span by span): with num_shards * bucket, the device
  // lane's fill share.
  uint64_t BatchNnz() const { return batch_nnz_; }
  bool SetShuffleEpoch(unsigned epoch) {
    return split_->SetShuffleEpoch(epoch);
  }

 private:
  // Shard-0 plane bases + per-shard element strides; Fill (stride = one
  // plane) and FillPacked (stride = all of a shard's planes) are the same
  // walk over different addressing. Spans never cross shard boundaries
  // (the fill loop clamps to R*(d+1)), so `base + d*stride + local` is
  // safe for both.
  struct Targets {
    int32_t* row;
    int32_t* col;
    float* val;
    int32_t* field;        // null to skip
    uint64_t nnz_stride;   // per-shard stride of the nnz planes (elements)
    float* label;
    float* weight;
    int32_t* qid;          // null to skip
    int32_t* nrows_plane;  // null for the legacy split-plane layout
    uint64_t row_stride;   // per-shard stride of the row-wise planes
  };
  uint64_t FillImpl(const Targets& t, int32_t* nrows);
  bool AdvanceRecord();  // load + validate the next record; false at end
  void Peek();           // ensure the first record's header is parsed

  std::unique_ptr<InputSplit> split_;
  const uint64_t batch_rows_;
  const uint32_t num_shards_;
  const uint64_t min_bucket_;

  // current record view (valid until the next NextRecord on split_)
  const char* row_len_ = nullptr;
  const char* labels_ = nullptr;
  const char* weights_ = nullptr;
  const char* qids_ = nullptr;
  const char* cols_ = nullptr;
  const char* vals_ = nullptr;
  const char* fields_ = nullptr;
  uint64_t rec_rows_ = 0;
  uint64_t rec_nnz_ = 0;
  uint64_t row_in_rec_ = 0;
  uint64_t nnz_in_rec_ = 0;  // nnz consumed from this record

  // pinned static shape (first record wins; later mismatches throw)
  int has_weight_ = -1;
  int has_qid_ = -1;
  int has_field_ = -1;
  uint64_t bucket_ = 0;
  uint64_t batch_nnz_ = 0;
  std::vector<uint64_t> shard_nnz_;  // real entries of each shard, last fill
  ColSlots slots_;                   // of the batch FillPacked last wrote
  uint64_t cols_cap_ = 0;            // its lists' capacity (TailRung)
  uint64_t prev_cols_ = 0;           // of the batch before, this epoch
  bool lifted_ = false;

  bool have_record_ = false;
  bool eof_ = false;
  size_t bytes_read_ = 0;
};

}  // namespace dct

#endif  // DCT_CSR_REC_H_
