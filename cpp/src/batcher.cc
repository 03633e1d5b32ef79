#include "batcher.h"

#include <algorithm>
#include <cstring>

#include "base.h"
#include "bf16.h"
#include "nnz_bucket.h"
#include "telemetry.h"

namespace dct {

PaddedBatcher::PaddedBatcher(Parser<uint32_t>* parser, uint64_t batch_rows,
                             uint32_t num_shards, uint64_t min_nnz_bucket)
    : parser_(parser),
      batch_rows_(batch_rows),
      num_shards_(num_shards),
      min_bucket_(std::max<uint64_t>(min_nnz_bucket, 1)) {
  DCT_CHECK(num_shards_ > 0) << "num_shards must be positive";
  DCT_CHECK(batch_rows_ > 0 && batch_rows_ % num_shards_ == 0)
      << "batch_rows=" << batch_rows_ << " must divide by shards="
      << num_shards_;
}

void PaddedBatcher::Accumulate() {
  while (avail_rows_ < batch_rows_ && !done_) {
    Block b;
    if (!spares_.empty()) {  // recycled capacity rides back to the parser
      b = std::move(spares_.back());
      spares_.pop_back();
      b.Clear();
    }
    if (!parser_->NextBlockMove(&b)) {
      done_ = true;
      break;
    }
    const size_t n = b.Size();
    const size_t nnz = b.offset.back();
    // Validation happens ON ARRIVAL, before the block joins the deque, so
    // a caught error leaves the pending state consistent.
    // The device layout is int32: a feature id >= 2^31 would wrap negative
    // and scatter to a wrong column — refuse loudly instead of corrupting
    // silently (reference data.h:26-32 makes index width a first-class
    // contract; the Python HostBatcher mirrors this).
    DCT_CHECK(b.max_index <= 0x7fffffffULL)
        << "feature index " << b.max_index
        << " exceeds the int32 device layout (max 2147483647); remap "
           "feature ids below 2^31 for the TPU batch layout";
    if (!b.qid.empty()) {
      DCT_CHECK(b.qid.size() == n) << "ragged qid column in block";
      for (uint64_t q : b.qid) {
        DCT_CHECK(q <= 0x7fffffffULL)
            << "qid " << q << " exceeds the int32 device layout";
      }
      have_qid_ = true;
    }
    if (!b.field.empty()) {
      DCT_CHECK(b.field.size() == nnz) << "ragged field column in block";
      have_field_ = true;
    }
    DCT_CHECK(b.weight.empty() || b.weight.size() == n)
        << "ragged weight column in block";
    max_index_ = std::max(max_index_, b.max_index);
    avail_rows_ += n;
    blocks_.push_back(std::move(b));
  }
}

template <typename Fn>
void PaddedBatcher::ForEachRowRange(uint64_t skip, uint64_t count,
                                    Fn&& fn) const {
  // visit `count` staged rows starting `skip` rows past the cursor
  uint64_t pos = row_in_front_ + skip;  // block-local start in walk order
  uint64_t out_row = 0;
  for (const Block& b : blocks_) {
    if (count == 0) return;
    const uint64_t n = b.Size();
    if (pos >= n) {
      pos -= n;
      continue;
    }
    const uint64_t r1 = std::min<uint64_t>(n, pos + count);
    fn(b, pos, r1, out_row);
    out_row += r1 - pos;
    count -= r1 - pos;
    pos = 0;
  }
  DCT_CHECK(count == 0) << "row walk ran past the staged data";
}

bool PaddedBatcher::NextMeta(uint64_t* take, uint64_t* bucket,
                             uint64_t* max_index, int* has_qid,
                             int* has_field) {
  DCT_CHECK(!staged_) << "NextMeta called with an unconsumed staged batch";
  telemetry::TraceSpan trace("batch.stage");
  Accumulate();
  trace.set_arg(avail_rows_);
  if (avail_rows_ == 0) return false;
  take_ = std::min<uint64_t>(batch_rows_, avail_rows_);

  // per-shard nnz -> bucket = the ladder rung at or above the fullest
  // shard's count, floored at min_bucket_ (nnz_bucket.h)
  const uint64_t R = batch_rows_ / num_shards_;
  uint64_t max_shard = 0;
  batch_nnz_ = 0;
  for (uint32_t d = 0; d < num_shards_; ++d) {
    const uint64_t lo = d * R;
    const uint64_t hi = std::min<uint64_t>((d + 1) * R, take_);
    if (lo >= hi) break;
    uint64_t shard_nnz = 0;
    ForEachRowRange(lo, hi - lo, [&](const Block& b, uint64_t r0,
                                     uint64_t r1, uint64_t) {
      shard_nnz += RowRangeNnz(b, r0, r1);
    });
    max_shard = std::max(max_shard, shard_nnz);
    batch_nnz_ += shard_nnz;
  }
  const uint64_t own = NnzBucket(max_shard, min_bucket_);
  // a short last batch takes no rung below the batch before it
  bucket_ = TailRung(own, prev_bucket_, take_, batch_rows_);
  lifted_ = bucket_ != own;
  staged_ = true;
  *take = take_;
  *bucket = bucket_;
  *max_index = max_index_;
  if (has_qid != nullptr) *has_qid = have_qid_ ? 1 : 0;
  if (has_field != nullptr) *has_field = have_field_ ? 1 : 0;
  return true;
}

void PaddedBatcher::FillRowArrays(float* label, float* weight,
                                  int32_t* nrows) {
  ForEachRowRange(0, take_, [&](const Block& b, uint64_t r0, uint64_t r1,
                                uint64_t out) {
    std::memcpy(label + out, b.label.data() + r0, (r1 - r0) * sizeof(float));
    if (b.weight.empty()) {
      std::fill(weight + out, weight + out + (r1 - r0), 1.0f);
    } else {
      std::memcpy(weight + out, b.weight.data() + r0,
                  (r1 - r0) * sizeof(float));
    }
  });
  if (take_ < batch_rows_) {  // weight 0 ⇒ padding rows drop out of the loss
    std::memset(label + take_, 0, (batch_rows_ - take_) * sizeof(float));
    std::memset(weight + take_, 0, (batch_rows_ - take_) * sizeof(float));
  }
  const uint64_t R = batch_rows_ / num_shards_;
  for (uint32_t d = 0; d < num_shards_; ++d) {
    const int64_t left = static_cast<int64_t>(take_) - d * R;
    nrows[d] = static_cast<int32_t>(
        std::max<int64_t>(0, std::min<int64_t>(left, R)));
  }
}

template <typename CopyVals, typename PadVals>
uint64_t PaddedBatcher::FillShardNnz(uint32_t d, int32_t* rowd, int32_t* cold,
                                 int32_t* fieldd, CopyVals&& copy_vals,
                                 PadVals&& pad_vals) {
  const uint64_t R = batch_rows_ / num_shards_;
  uint64_t written = 0;
  const uint64_t lo = d * R;
  const uint64_t hi = std::min<uint64_t>((d + 1) * R, take_);
  if (lo < hi) {
    ForEachRowRange(lo, hi - lo, [&](const Block& b, uint64_t r0,
                                     uint64_t r1, uint64_t out) {
      const uint64_t p0 = b.offset[r0];
      const uint64_t range_nnz = b.offset[r1] - p0;
      if (range_nnz == 0) return;  // feature-less rows; data() may be
      // null for empty vectors and memcpy is nonnull-UB
      // per-nonzero local row segment ids; `out` already walks the
      // shard-local row space (the walk starts at shard row lo == d*R)
      for (uint64_t r = r0; r < r1; ++r) {
        const int32_t local = static_cast<int32_t>(out + (r - r0));
        const uint64_t l = b.offset[r + 1] - b.offset[r];
        for (uint64_t k = 0; k < l; ++k) rowd[written + k] = local;
        written += l;
      }
      written -= range_nnz;  // rewind; bulk copies advance it once below
      // uint32 -> int32 is bit-identical for ids < 2^31 (guarded on
      // arrival in Accumulate): bulk copy straight from the block
      std::memcpy(cold + written, b.index.data() + p0,
                  range_nnz * sizeof(int32_t));
      copy_vals(b, p0, written, range_nnz);
      if (fieldd != nullptr) {
        if (b.field.empty()) {
          std::memset(fieldd + written, 0, range_nnz * sizeof(int32_t));
        } else {
          std::memcpy(fieldd + written, b.field.data() + p0,
                      range_nnz * sizeof(int32_t));
        }
      }
      written += range_nnz;
    });
  }
  // padding nonzeros land in the sacrificial segment id R, sliced off by
  // the segment ops (dmlc_core_tpu/ops/sparse.py)
  for (uint64_t k = written; k < bucket_; ++k) rowd[k] = R;
  std::memset(cold + written, 0, (bucket_ - written) * sizeof(int32_t));
  pad_vals(written);
  if (fieldd != nullptr) {
    std::memset(fieldd + written, 0, (bucket_ - written) * sizeof(int32_t));
  }
  return written;
}

void PaddedBatcher::FillCSR(int32_t* row, int32_t* col, float* val,
                            float* label, float* weight, int32_t* nrows,
                            int32_t* qid, int32_t* field) {
  DCT_CHECK(staged_) << "FillCSR without a staged batch (call NextMeta)";
  telemetry::TraceSpan trace("batch.fill");
  trace.set_arg(take_);
  for (uint32_t d = 0; d < num_shards_; ++d) {
    int32_t* rowd = row + d * bucket_;
    int32_t* cold = col + d * bucket_;
    float* vald = val + d * bucket_;
    int32_t* fieldd = field == nullptr ? nullptr : field + d * bucket_;
    FillShardNnz(
        d, rowd, cold, fieldd,
        [&](const Block& b, uint64_t p0, uint64_t w, uint64_t n) {
          if (b.value_dtype == 0 && !b.value.empty()) {
            std::memcpy(vald + w, b.value.data() + p0, n * sizeof(float));
          } else {
            for (uint64_t k = 0; k < n; ++k) vald[w + k] = ValueAt(b, p0 + k);
          }
        },
        [&](uint64_t w) {
          std::memset(vald + w, 0, (bucket_ - w) * sizeof(float));
        });
  }
  if (qid != nullptr) {
    FillQid(qid);
  }
  FillRowArrays(label, weight, nrows);
  Consume();
}

void PaddedBatcher::FillRowWisePacked(int32_t* aux, int32_t ka,
                                      int32_t* nrows) {
  const uint64_t R = batch_rows_ / num_shards_;
  for (uint32_t d = 0; d < num_shards_; ++d) {
    int32_t* auxd = aux + static_cast<uint64_t>(d) * ka * R;
    float* labeld = reinterpret_cast<float*>(auxd);
    float* weightd = reinterpret_cast<float*>(auxd + R);
    int32_t* qidd = ka == 4 ? auxd + 2 * R : nullptr;
    int32_t* nplane = auxd + static_cast<uint64_t>(ka - 1) * R;
    const uint64_t lo = d * R;
    const uint64_t hi =
        std::max<uint64_t>(lo, std::min<uint64_t>((d + 1) * R, take_));
    const uint64_t count = hi - lo;
    if (count > 0) {
      ForEachRowRange(lo, count, [&](const Block& b, uint64_t r0,
                                     uint64_t r1, uint64_t out) {
        std::memcpy(labeld + out, b.label.data() + r0,
                    (r1 - r0) * sizeof(float));
        if (b.weight.empty()) {
          std::fill(weightd + out, weightd + out + (r1 - r0), 1.0f);
        } else {
          std::memcpy(weightd + out, b.weight.data() + r0,
                      (r1 - r0) * sizeof(float));
        }
        if (qidd != nullptr) {
          if (b.qid.empty()) {
            std::fill(qidd + out, qidd + out + (r1 - r0), -1);
          } else {
            for (uint64_t r = r0; r < r1; ++r) {
              qidd[out + (r - r0)] = static_cast<int32_t>(b.qid[r]);
            }
          }
        }
      });
    }
    // padding rows: weight 0 drops them from the loss, qid -1 keeps them
    // out of any real group
    std::memset(labeld + count, 0, (R - count) * sizeof(float));
    std::memset(weightd + count, 0, (R - count) * sizeof(float));
    if (qidd != nullptr) std::fill(qidd + count, qidd + R, -1);
    std::memset(nplane, 0, R * sizeof(int32_t));
    nplane[0] = static_cast<int32_t>(count);
    nrows[d] = static_cast<int32_t>(count);
  }
}

void PaddedBatcher::FillPacked(int32_t* big, int32_t kb, void* val,
                               int32_t val_dtype, int32_t* aux, int32_t ka,
                               int32_t* nrows) {
  DCT_CHECK(staged_) << "FillPacked without a staged batch (call NextMeta)";
  DCT_CHECK(val_dtype == 0 || val_dtype == 1)
      << "packed val dtype must be 0 (float32) or 1 (bfloat16), got "
      << val_dtype;
  const int32_t want_kb =
      2 + (val_dtype == 0 ? 1 : 0) + (have_field_ ? 1 : 0);
  DCT_CHECK(kb == want_kb)
      << "packed big has " << kb << " planes but the batch needs " << want_kb;
  const int32_t want_ka = 3 + (have_qid_ ? 1 : 0);
  DCT_CHECK(ka == want_ka)
      << "packed aux has " << ka << " planes but the batch needs " << want_ka;
  DCT_CHECK(val_dtype == 0 || val != nullptr)
      << "bf16 packed fill needs a separate val buffer";
  telemetry::TraceSpan trace("batch.fill");
  trace.set_arg(take_);
  std::vector<uint64_t> written(num_shards_);
  for (uint32_t d = 0; d < num_shards_; ++d) {
    int32_t* based = big + static_cast<uint64_t>(d) * kb * bucket_;
    int32_t* rowd = based;
    int32_t* cold = based + bucket_;
    int32_t* fieldd =
        have_field_ ? based + static_cast<uint64_t>(kb - 1) * bucket_
                    : nullptr;
    if (val_dtype == 0) {
      float* vald = reinterpret_cast<float*>(based + 2 * bucket_);
      written[d] = FillShardNnz(
          d, rowd, cold, fieldd,
          [&](const Block& b, uint64_t p0, uint64_t w, uint64_t n) {
            if (b.value_dtype == 0 && !b.value.empty()) {
              std::memcpy(vald + w, b.value.data() + p0, n * sizeof(float));
            } else {
              for (uint64_t k = 0; k < n; ++k) {
                vald[w + k] = ValueAt(b, p0 + k);
              }
            }
          },
          [&](uint64_t w) {
            std::memset(vald + w, 0, (bucket_ - w) * sizeof(float));
          });
    } else {
      uint16_t* vald =
          static_cast<uint16_t*>(val) + static_cast<uint64_t>(d) * bucket_;
      written[d] = FillShardNnz(
          d, rowd, cold, fieldd,
          [&](const Block& b, uint64_t p0, uint64_t w, uint64_t n) {
            if (b.value_dtype == 0 && !b.value.empty()) {
              const float* src = b.value.data() + p0;
              for (uint64_t k = 0; k < n; ++k) {
                vald[w + k] = Bf16FromFloat(src[k]);
              }
            } else {
              for (uint64_t k = 0; k < n; ++k) {
                vald[w + k] = Bf16FromFloat(ValueAt(b, p0 + k));
              }
            }
          },
          [&](uint64_t w) {
            // bf16 0x0000 is +0.0f, so the zero pad stays byte-identical
            // with the f32 plane's zero pad after upcast
            std::memset(vald + w, 0, (bucket_ - w) * sizeof(uint16_t));
          });
    }
  }
  // the col planes become the slot planes (col_slots.h); the padded
  // entries' zeros already read slot 0
  slots_.Run(big + bucket_, static_cast<uint64_t>(kb) * bucket_,
             written.data(), num_shards_);
  const uint64_t own = slots_.Capacity(min_bucket_);
  cols_cap_ = TailRung(own, prev_cols_, take_, batch_rows_);
  lifted_ = lifted_ || cols_cap_ != own;
  prev_cols_ = cols_cap_;
  slots_.Lay(big + bucket_, static_cast<uint64_t>(kb) * bucket_,
             written.data(), cols_cap_);
  FillRowWisePacked(aux, ka, nrows);
  Consume();
}

void PaddedBatcher::FillQid(int32_t* qid) {
  // Rows from qid-less blocks get -1 (a value the uint64 parse can never
  // produce) so they can't merge with a legitimate qid:0 group; padding
  // rows get -1 too (weight 0 already excludes them from the loss).
  ForEachRowRange(0, take_, [&](const Block& b, uint64_t r0, uint64_t r1,
                                uint64_t out) {
    if (b.qid.empty()) {
      std::fill(qid + out, qid + out + (r1 - r0), -1);
    } else {
      for (uint64_t r = r0; r < r1; ++r) {
        qid[out + (r - r0)] = static_cast<int32_t>(b.qid[r]);
      }
    }
  });
  std::fill(qid + take_, qid + batch_rows_, -1);
}

namespace {

inline void StoreDense(float* xr, int32_t c, float v) { xr[c] = v; }
inline void StoreDense(uint16_t* xr, int32_t c, float v) {
  xr[c] = Bf16FromFloat(v);
}

}  // namespace

template <typename T>
void PaddedBatcher::FillDenseT(T* x, uint64_t num_features) {
  std::memset(x, 0, batch_rows_ * num_features * sizeof(T));
  ForEachRowRange(0, take_, [&](const Block& b, uint64_t r0, uint64_t r1,
                                uint64_t out) {
    for (uint64_t r = r0; r < r1; ++r) {
      T* xr = x + (out + (r - r0)) * num_features;
      for (uint64_t k = b.offset[r]; k < b.offset[r + 1]; ++k) {
        const uint32_t c = b.index[k];
        DCT_CHECK(static_cast<uint64_t>(c) < num_features)
            << "dense layout fixed at " << num_features
            << " features but saw index " << c
            << "; pass layout='csr' or a larger dense_max_features";
        StoreDense(xr, static_cast<int32_t>(c), ValueAt(b, k));
      }
    }
  });
}

void PaddedBatcher::FillDense(void* x, int x_dtype, uint64_t num_features,
                              float* label, float* weight, int32_t* nrows,
                              int32_t* qid) {
  DCT_CHECK(staged_) << "FillDense without a staged batch (call NextMeta)";
  DCT_CHECK(x_dtype == 0 || x_dtype == 1)
      << "dense x dtype must be 0 (float32) or 1 (bfloat16), got " << x_dtype;
  if (qid != nullptr) {
    FillQid(qid);
  }
  if (x_dtype == 1) {
    FillDenseT(static_cast<uint16_t*>(x), num_features);
  } else {
    FillDenseT(static_cast<float*>(x), num_features);
  }
  FillRowArrays(label, weight, nrows);
  Consume();
}

void PaddedBatcher::FillDensePacked(void* x, int x_dtype,
                                    uint64_t num_features, int32_t* aux,
                                    int32_t ka, int32_t* nrows) {
  DCT_CHECK(staged_)
      << "FillDensePacked without a staged batch (call NextMeta)";
  DCT_CHECK(x_dtype == 0 || x_dtype == 1)
      << "dense x dtype must be 0 (float32) or 1 (bfloat16), got " << x_dtype;
  const int32_t want_ka = 3 + (have_qid_ ? 1 : 0);
  DCT_CHECK(ka == want_ka)
      << "packed aux has " << ka << " planes but the batch needs " << want_ka;
  telemetry::TraceSpan trace("batch.fill");
  trace.set_arg(take_);
  if (x_dtype == 1) {
    FillDenseT(static_cast<uint16_t*>(x), num_features);
  } else {
    FillDenseT(static_cast<float*>(x), num_features);
  }
  FillRowWisePacked(aux, ka, nrows);
  Consume();
}

void PaddedBatcher::Consume() {
  uint64_t left = take_;
  while (left > 0) {
    Block& front = blocks_.front();
    const uint64_t remaining = front.Size() - row_in_front_;
    if (remaining <= left) {
      left -= remaining;
      if (spares_.size() < 16) {  // park capacity for the next Accumulate
        spares_.push_back(std::move(front));
      }
      blocks_.pop_front();
      row_in_front_ = 0;
    } else {
      row_in_front_ += left;
      left = 0;
    }
  }
  avail_rows_ -= take_;
  prev_bucket_ = bucket_;
  staged_ = false;
}

void PaddedBatcher::BeforeFirst() {
  parser_->BeforeFirst();
  blocks_.clear();
  row_in_front_ = 0;
  avail_rows_ = 0;
  done_ = false;
  staged_ = false;
  prev_bucket_ = prev_cols_ = 0;  // the tail rule looks back within an epoch
  // max_index_ deliberately survives reset: the dense/csr layout choice must
  // stay sticky across epochs so device shapes remain static
}

}  // namespace dct
