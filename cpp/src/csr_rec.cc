#include "csr_rec.h"

#include <algorithm>
#include <cstring>

#include "base.h"
#include "nnz_bucket.h"
#include "recordio.h"
#include "serializer.h"
#include "stream.h"

namespace dct {

namespace {

using recordio::CopyWords32LE;
using recordio::LoadU64LE;

uint32_t LoadRowLen(const char* row_len, uint64_t i) {
  return recordio::LoadWordLE(row_len + i * 4);
}

}  // namespace

CsrRecBatcher::CsrRecBatcher(const std::string& uri, unsigned part,
                             unsigned npart, uint64_t batch_rows,
                             uint32_t num_shards, uint64_t min_nnz_bucket)
    : batch_rows_(batch_rows),
      num_shards_(num_shards),
      min_bucket_(std::max<uint64_t>(min_nnz_bucket, 1)) {
  DCT_CHECK(num_shards_ > 0) << "num_shards must be positive";
  DCT_CHECK(batch_rows_ > 0 && batch_rows_ % num_shards_ == 0)
      << "batch_rows=" << batch_rows_ << " must divide by shards="
      << num_shards_;
  URISpec spec(uri, part, npart);
  // shuffling is additionally unsound here: the window-table bucket
  // bounds CONSECUTIVE rows, and a coarse shuffle would compose batches
  // from two windows' tails
  spec.RejectUnknownArgs("csr rec lane", {"format"});
  // already-binary lanes keep the legacy `#<path>` chunk cache; the
  // `#cachefile=<dir>` shard cache re-encodes parsed row blocks and
  // would be a silent no-op here (URI sugar must error, not no-op)
  DCT_CHECK(spec.cache_dir.empty())
      << "the csr rec lane takes the legacy `#<path>` chunk cache, not a "
         "`#cachefile=<dir>` shard-cache directory (the data is already "
         "binary)";
  split_.reset(InputSplit::Create(spec.uri, part, npart, "recordio", "",
                                  false, 0, 256, false, /*threaded=*/true,
                                  spec.cache_file));
}

bool CsrRecBatcher::AdvanceRecord() {
  InputSplit::Blob b;
  if (!split_->NextRecord(&b)) {
    eof_ = true;
    have_record_ = false;
    return false;
  }
  bytes_read_ += b.size;
  DCT_CHECK(b.size >= 32) << "csr rec record too short for its header";
  const char* p = static_cast<const char*>(b.dptr);
  DCT_CHECK(recordio::LoadWordLE(p) == kCsrRecMagic)
      << "not a csr-plane record (bad payload magic); .crec files are "
         "written by rows_to_csr_recordio (dmlc_core_tpu/io/convert.py)";
  const uint32_t flags = recordio::LoadWordLE(p + 4);
  const uint64_t rows = recordio::LoadWordLE(p + 8);
  const uint32_t nwin = recordio::LoadWordLE(p + 12);
  const uint64_t nnz = LoadU64LE(p + 16);
  const uint32_t max_col = recordio::LoadWordLE(p + 24);
  // RecordIO records are < 2^29 bytes; bounding the dims keeps the `need`
  // arithmetic overflow-free under fuzzed headers (dense_rec.cc rule)
  DCT_CHECK(rows <= (1u << 30) && nnz <= (1ull << 34) && nwin <= 64)
      << "corrupt csr rec header: rows=" << rows << " nnz=" << nnz
      << " nwin=" << nwin;
  DCT_CHECK(max_col <= 0x7fffffffu)
      << "csr rec feature index " << max_col
      << " exceeds the int32 device layout";
  const int hw = static_cast<int>(flags & 1u);
  const int hq = static_cast<int>((flags >> 1) & 1u);
  const int hf = static_cast<int>((flags >> 2) & 1u);
  // the window table must fit the blob BEFORE any table read: a truncated
  // record with a large claimed nwin would otherwise read past the end
  DCT_CHECK(nwin >= 1 && b.size >= 32 + 8ull * nwin)
      << "csr rec record truncated inside its window table";
  if (has_weight_ < 0) {
    has_weight_ = hw;
    has_qid_ = hq;
    has_field_ = hf;
    // the per-shard nnz capacity: any R consecutive rows carry at most
    // win_max[ceil_log2(R)] nonzeros (the converter's GLOBAL sliding
    // bound), so one ladder bucket (nnz_bucket.h) serves every batch of
    // the epoch
    const uint64_t R = batch_rows_ / num_shards_;
    uint32_t wi = 0;
    while ((1ull << wi) < R && wi + 1 < nwin) ++wi;
    const uint64_t bound = LoadU64LE(p + 32 + 8 * wi);
    // same sanity bound as nnz: a flipped high bit in the table must die
    // here, not drive the bucket rule into overflow or a multi-GB alloc
    DCT_CHECK(bound <= (1ull << 34))
        << "corrupt csr rec window table: bound " << bound;
    bucket_ = NnzBucket(bound, min_bucket_);
  } else {
    DCT_CHECK(hw == has_weight_ && hq == has_qid_ && hf == has_field_)
        << "csr rec record flag drift: got w/q/f=" << hw << hq << hf
        << ", pinned " << has_weight_ << has_qid_ << has_field_;
  }
  const char* tab_end = p + 32 + 8 * static_cast<uint64_t>(nwin);
  const uint64_t need = 32 + 8ull * nwin + rows * 4 /*row_len*/ +
                        rows * 4 /*label*/ + (hw ? rows * 4 : 0) +
                        (hq ? rows * 4 : 0) + nnz * 4 /*col*/ +
                        nnz * 4 /*val*/ + (hf ? nnz * 4 : 0);
  DCT_CHECK(b.size >= need)
      << "truncated csr rec record: " << b.size << " bytes, need " << need;
  row_len_ = tab_end;
  labels_ = row_len_ + rows * 4;
  weights_ = hw ? labels_ + rows * 4 : nullptr;
  qids_ = hq ? (hw ? weights_ : labels_) + rows * 4 : nullptr;
  const char* after_rowwise =
      (hq ? qids_ : (hw ? weights_ : labels_)) + rows * 4;
  cols_ = after_rowwise;
  vals_ = cols_ + nnz * 4;
  fields_ = hf ? vals_ + nnz * 4 : nullptr;
  rec_rows_ = rows;
  rec_nnz_ = nnz;
  row_in_rec_ = 0;
  nnz_in_rec_ = 0;
  have_record_ = true;
  return true;
}

void CsrRecBatcher::Peek() {
  if (has_weight_ < 0 && !eof_) {
    AdvanceRecord();
  }
}

void CsrRecBatcher::Meta(uint64_t* bucket, int* has_weight, int* has_qid,
                         int* has_field) {
  Peek();
  DCT_CHECK(has_weight_ >= 0)
      << "csr rec source is empty; cannot determine the batch shape";
  *bucket = bucket_;
  *has_weight = has_weight_;
  *has_qid = has_qid_;
  *has_field = has_field_;
}

uint64_t CsrRecBatcher::Fill(int32_t* row, int32_t* col, float* val,
                             int32_t* field, float* label, float* weight,
                             int32_t* qid, int32_t* nrows) {
  Peek();
  DCT_CHECK(has_field_ <= 0 || field != nullptr)
      << "csr rec file carries field ids but no field plane was passed";
  DCT_CHECK(has_qid_ <= 0 || qid != nullptr)
      << "csr rec file carries qid but no qid plane was passed";
  const uint64_t R = batch_rows_ / num_shards_;
  Targets t;
  t.row = row;
  t.col = col;
  t.val = val;
  t.field = field;
  t.nnz_stride = bucket_;
  t.label = label;
  t.weight = weight;
  t.qid = qid;
  t.nrows_plane = nullptr;
  t.row_stride = R;
  return FillImpl(t, nrows);
}

uint64_t CsrRecBatcher::FillPacked(int32_t* big, int32_t kb, int32_t* aux,
                                   int32_t ka, int32_t* nrows) {
  Peek();
  DCT_CHECK(has_weight_ >= 0)
      << "csr rec source is empty; cannot determine the batch shape";
  const int32_t want_kb = 3 + (has_field_ == 1 ? 1 : 0);
  DCT_CHECK(kb == want_kb)
      << "packed big has " << kb << " planes but the file needs " << want_kb;
  const int32_t want_ka = 3 + (has_qid_ == 1 ? 1 : 0);
  DCT_CHECK(ka == want_ka)
      << "packed aux has " << ka << " planes but the file needs " << want_ka;
  const uint64_t R = batch_rows_ / num_shards_;
  const uint64_t B = bucket_;
  Targets t;
  t.row = big;
  t.col = big + B;
  t.val = reinterpret_cast<float*>(big + 2 * B);
  t.field = has_field_ == 1 ? big + 3 * B : nullptr;
  t.nnz_stride = static_cast<uint64_t>(kb) * B;
  t.label = reinterpret_cast<float*>(aux);
  t.weight = reinterpret_cast<float*>(aux + R);
  t.qid = has_qid_ == 1 ? aux + 2 * R : nullptr;
  t.nrows_plane = aux + static_cast<uint64_t>(ka - 1) * R;
  t.row_stride = static_cast<uint64_t>(ka) * R;
  const uint64_t filled = FillImpl(t, nrows);
  if (filled == 0) return 0;
  // the col planes become the slot planes (col_slots.h); the padded
  // entries' zeros already read slot 0
  slots_.Run(t.col, t.nnz_stride, shard_nnz_.data(), num_shards_);
  // the nnz bucket is the file's; a short last batch's list takes no rung
  // below the batch before it (nnz_bucket.h TailRung)
  const uint64_t own = slots_.Capacity(min_bucket_);
  cols_cap_ = TailRung(own, prev_cols_, filled, batch_rows_);
  lifted_ = cols_cap_ != own;
  prev_cols_ = cols_cap_;
  slots_.Lay(t.col, t.nnz_stride, shard_nnz_.data(), cols_cap_);
  return filled;
}

uint64_t CsrRecBatcher::FillImpl(const Targets& t, int32_t* nrows) {
  const uint64_t R = batch_rows_ / num_shards_;
  const uint64_t B = bucket_;
  uint64_t filled = 0;                   // rows placed into this batch
  uint64_t shard_written = 0;            // nnz in the current shard's plane
  batch_nnz_ = 0;
  shard_nnz_.assign(num_shards_, 0);
  while (filled < batch_rows_) {
    if (!have_record_ || row_in_rec_ >= rec_rows_) {
      if (eof_ || !AdvanceRecord()) break;
      if (rec_rows_ == 0) continue;  // empty record: skip
    }
    const uint32_t d = static_cast<uint32_t>(filled / R);
    if (filled % R == 0) shard_written = 0;
    // rows until the shard boundary, batch end, or record end
    const uint64_t n = std::min({R * (d + 1) - filled,
                                 batch_rows_ - filled,
                                 rec_rows_ - row_in_rec_});
    // single pass over the span's row lengths: expand local segment ids
    // and count the span's nnz
    int32_t* rowd = t.row + static_cast<uint64_t>(d) * t.nnz_stride;
    uint64_t span_nnz = 0;
    const uint64_t local0 = filled - static_cast<uint64_t>(d) * R;
    for (uint64_t i = 0; i < n; ++i) {
      const uint32_t l = LoadRowLen(row_len_, row_in_rec_ + i);
      DCT_CHECK(shard_written + span_nnz + l <= B)
          << "csr rec shard nnz exceeds the file's window bound (corrupt "
             "row_len or window table)";
      const int32_t local = static_cast<int32_t>(local0 + i);
      for (uint32_t k = 0; k < l; ++k) {
        rowd[shard_written + span_nnz + k] = local;
      }
      span_nnz += l;
    }
    DCT_CHECK(nnz_in_rec_ + span_nnz <= rec_nnz_)
        << "csr rec row lengths overrun the record's nnz";
    // bulk copies: the span's col/val[/field] are contiguous on disk
    CopyWords32LE(t.col + static_cast<uint64_t>(d) * t.nnz_stride +
                      shard_written,
                  cols_ + nnz_in_rec_ * 4, span_nnz);
    CopyWords32LE(t.val + static_cast<uint64_t>(d) * t.nnz_stride +
                      shard_written,
                  vals_ + nnz_in_rec_ * 4, span_nnz);
    if (t.field != nullptr) {
      int32_t* fieldw = t.field + static_cast<uint64_t>(d) * t.nnz_stride +
                        shard_written;
      if (fields_ != nullptr) {
        CopyWords32LE(fieldw, fields_ + nnz_in_rec_ * 4, span_nnz);
      } else {
        std::memset(fieldw, 0, span_nnz * 4);
      }
    }
    const uint64_t roff = static_cast<uint64_t>(d) * t.row_stride + local0;
    CopyWords32LE(t.label + roff, labels_ + row_in_rec_ * 4, n);
    if (weights_ != nullptr) {
      CopyWords32LE(t.weight + roff, weights_ + row_in_rec_ * 4, n);
    } else {
      for (uint64_t i = 0; i < n; ++i) t.weight[roff + i] = 1.0f;
    }
    if (t.qid != nullptr) {
      if (qids_ != nullptr) {
        CopyWords32LE(t.qid + roff, qids_ + row_in_rec_ * 4, n);
      } else {
        for (uint64_t i = 0; i < n; ++i) t.qid[roff + i] = -1;
      }
    }
    shard_written += span_nnz;
    shard_nnz_[d] = shard_written;
    batch_nnz_ += span_nnz;
    nnz_in_rec_ += span_nnz;
    row_in_rec_ += n;
    filled += n;
    // pad the shard's plane tail when the shard completes (or data ends)
    if (filled % R == 0 || filled == batch_rows_) {
      for (uint64_t k = shard_written; k < B; ++k) {
        rowd[k] = static_cast<int32_t>(R);  // sacrificial segment
      }
      const uint64_t off = static_cast<uint64_t>(d) * t.nnz_stride +
                           shard_written;
      std::memset(t.col + off, 0, (B - shard_written) * 4);
      std::memset(t.val + off, 0, (B - shard_written) * 4);
      if (t.field != nullptr) {
        std::memset(t.field + off, 0, (B - shard_written) * 4);
      }
    }
  }
  if (filled == 0) return 0;
  // data ended mid-shard: the loop's pad-on-complete never ran for it
  if (filled % R != 0) {
    const uint32_t d = static_cast<uint32_t>(filled / R);
    int32_t* rowd = t.row + static_cast<uint64_t>(d) * t.nnz_stride;
    for (uint64_t k = shard_written; k < B; ++k) {
      rowd[k] = static_cast<int32_t>(R);
    }
    const uint64_t off = static_cast<uint64_t>(d) * t.nnz_stride +
                         shard_written;
    std::memset(t.col + off, 0, (B - shard_written) * 4);
    std::memset(t.val + off, 0, (B - shard_written) * 4);
    if (t.field != nullptr) {
      std::memset(t.field + off, 0, (B - shard_written) * 4);
    }
  }
  // pad wholly-empty shards and the row-wise tails
  const uint32_t first_empty =
      static_cast<uint32_t>((filled + R - 1) / R);
  for (uint32_t d = first_empty; d < num_shards_; ++d) {
    int32_t* rowd = t.row + static_cast<uint64_t>(d) * t.nnz_stride;
    for (uint64_t k = 0; k < B; ++k) rowd[k] = static_cast<int32_t>(R);
    const uint64_t off = static_cast<uint64_t>(d) * t.nnz_stride;
    std::memset(t.col + off, 0, B * 4);
    std::memset(t.val + off, 0, B * 4);
    if (t.field != nullptr) {
      std::memset(t.field + off, 0, B * 4);
    }
  }
  for (uint32_t d = 0; d < num_shards_; ++d) {
    const int64_t left = static_cast<int64_t>(filled) - d * R;
    const uint64_t count = static_cast<uint64_t>(
        std::max<int64_t>(0, std::min<int64_t>(left, R)));
    const uint64_t roff = static_cast<uint64_t>(d) * t.row_stride;
    if (count < R) {  // padding rows: weight 0 drops them from the loss
      std::memset(t.label + roff + count, 0, (R - count) * 4);
      std::memset(t.weight + roff + count, 0, (R - count) * 4);
      if (t.qid != nullptr) {
        for (uint64_t i = count; i < R; ++i) t.qid[roff + i] = -1;
      }
    }
    if (t.nrows_plane != nullptr) {
      int32_t* nplane = t.nrows_plane + roff;
      std::memset(nplane, 0, R * 4);
      nplane[0] = static_cast<int32_t>(count);
    }
    nrows[d] = static_cast<int32_t>(count);
  }
  return filled;
}

void CsrRecBatcher::BeforeFirst() {
  split_->BeforeFirst();
  eof_ = false;
  have_record_ = false;
  row_in_rec_ = 0;
  nnz_in_rec_ = 0;
  rec_rows_ = 0;
  rec_nnz_ = 0;
  prev_cols_ = 0;  // the tail rule looks back within an epoch
  // flags/bucket deliberately survive: device shapes stay static across
  // epochs (dense_rec.cc rule)
}

}  // namespace dct
