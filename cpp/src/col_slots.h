// The distinct columns of a CSR shard, and each entry's slot among them:
// the one native statement of the dedupe both assemblers run.
//
// The step's gathers from the parameter tables and its scatter-adds into
// them cost per entry SENT (PERF.md section 5), and a batch of one-hot rows
// names the same column many times. So every CSR shard travels as
//   cols [U]    its distinct columns, ascending, and
//   slot [NNZ]  for each entry the position of its column in cols (the slot
//               plane stands where the col plane stood: col == cols[slot]),
// and a consumer reads and updates a column's row once a batch. U is the
// ladder rung (nnz_bucket.h, the same floor) at or above the fullest shard's
// distinct count, so it is static the way the nnz capacity is.
//
// Padding. A padded ENTRY keeps slot 0 (its value is 0 and its row the
// sacrificial segment). The list's tail past the distinct count repeats
// INT32_MAX: beyond any table, so the list stays sorted to its end, a
// gather that fills reads zeros there, a scatter drops them, and no slot
// names them. Every int32 id stays a valid column. A shard with no entry
// at all lists column 0 once, so that slot 0 names a real row for every
// consumer; the scatter then adds a zero to it, as the padded entries'
// column 0 always did.
//
// Owners. Where the tables are sharded by key range (models/_dp.py, the
// range-sharded form), the id space is cut into `owners` contiguous ranges
// of `range` ids and a column's row lives with the owner of its range. The
// list is ascending, so an owner's columns are one contiguous stretch of
// it; only the stretch's length is dynamic. The list is then laid out
// owner-major, [owners, C] flattened: stretch o holds the shard's columns
// in [o * range, (o + 1) * range), padded to C as the list's tail is, C the
// ladder rung of the fullest (shard, owner) stretch of the batch, and slot
// names positions in the flattened list. With one owner that is the list
// above to the byte.
//
// Stated twice, here and in dmlc_core_tpu/tpu/device_iter.py (col_slots:
// np.unique, the oracle); tests/test_col_slots.py holds the two equal.
#ifndef DCT_COL_SLOTS_H_
#define DCT_COL_SLOTS_H_

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>

#include "base.h"
#include "nnz_bucket.h"

namespace dct {

class ColSlots {
 public:
  // The key ranges the lists are laid out by (the header's "Owners"): ids
  // at or beyond owners * range have no owner and are refused.
  void SetOwners(uint32_t owners, uint64_t range) {
    DCT_CHECK(owners >= 1 && (owners == 1 || range >= 1))
        << owners << " owners of " << range << " ids";
    owners_ = owners;
    range_ = range;
  }

  // A batch of num_shards shards, shard d's col plane at col + d * stride
  // holding n[d] real entries (non-negative ids): replace each entry by
  // its slot and keep every shard's distinct list. The shards share
  // nothing, so all but the first are sorted on threads of their own: the
  // dedupe then costs a batch what it costs a shard.
  void Run(int32_t* col, uint64_t stride, const uint64_t* n,
           uint32_t num_shards) {
    shards_.resize(num_shards);
    for (uint32_t d = 0; d < num_shards; ++d) {
      // an entry's place rides in the key's low half
      DCT_CHECK(n[d] <= 0xffffffffULL) << "a shard of " << n[d] << " entries";
    }
    std::vector<std::thread> others;
    for (uint32_t d = 1; d < num_shards; ++d) {
      others.emplace_back([this, col, stride, n, d] {
        shards_[d].Sort(col + d * stride, n[d]);
      });
    }
    if (num_shards != 0) shards_[0].Sort(col, n[0]);
    for (std::thread& t : others) t.join();
    real_ = 0;
    for (uint32_t d = 0; d < num_shards; ++d) {
      if (n[d] != 0) real_ += shards_[d].list.size();
    }
    owner_max_ = real_;
    if (owners_ == 1) return;
    // where each owner's stretch of a shard's list starts
    std::vector<uint64_t> got(owners_, 0);
    for (uint32_t d = 0; d < num_shards; ++d) {
      Shard& s = shards_[d];
      DCT_CHECK(static_cast<uint64_t>(s.list.back()) < owners_ * range_)
          << "column " << s.list.back() << " lies beyond the " << owners_
          << " owners' ranges of " << range_ << " ids";
      s.first.assign(owners_ + 1, s.list.size());
      for (uint32_t o = 0; o < owners_; ++o) {
        s.first[o] = std::lower_bound(s.list.begin(), s.list.end(),
                                      static_cast<int64_t>(o * range_)) -
                     s.list.begin();
      }
      for (uint32_t o = 0; o < owners_ && n[d] != 0; ++o) {
        got[o] += s.first[o + 1] - s.first[o];
      }
    }
    owner_max_ = *std::max_element(got.begin(), got.end());
  }

  // Distinct columns of the batch, summed over its shards (a shard's
  // stand-in column 0 not counted).
  uint64_t Distinct() const { return real_; }

  // Of those, the columns of the owner that got the most, all shards'
  // stretches together (one owner: all of them).
  uint64_t OwnerMax() const { return owner_max_; }

  // The list's capacity: owners times the ladder rung of the fullest
  // (shard, owner) stretch's count.
  uint64_t Capacity(uint64_t floor) const {
    uint64_t fullest = 1;
    for (const Shard& s : shards_) {
      for (uint32_t o = 0; o < owners_; ++o) {
        fullest = std::max<uint64_t>(fullest, s.Stretch(o, owners_));
      }
    }
    return owners_ * NnzBucket(fullest, floor);
  }

  // Move every slot of the planes Run wrote to its place in lists of
  // capacity `cap` (Capacity's, or a rung above it: TailRung). Nothing to
  // do for one owner, where a slot is the column's place in the list.
  void Lay(int32_t* col, uint64_t stride, const uint64_t* n, uint64_t cap) {
    if (owners_ == 1) return;
    const uint64_t c = cap / owners_;
    auto lay = [this, col, stride, n, c](size_t d) {
      const Shard& s = shards_[d];
      int32_t* plane = col + d * stride;
      for (uint64_t i = 0; i < n[d]; ++i) {
        const uint64_t at = static_cast<uint64_t>(plane[i]);
        uint32_t o = 0;
        while (at >= s.first[o + 1]) ++o;
        plane[i] = static_cast<int32_t>(o * c + (at - s.first[o]));
      }
    };
    std::vector<std::thread> others;
    for (size_t d = 1; d < shards_.size(); ++d) others.emplace_back(lay, d);
    if (!shards_.empty()) lay(0);
    for (std::thread& t : others) t.join();
  }

  // Write the [D, cap] lists, each stretch padded to its end as the header
  // says.
  void Write(int32_t* cols, uint64_t cap) const {
    const uint64_t c = cap / owners_;
    for (size_t d = 0; d < shards_.size(); ++d) {
      const Shard& s = shards_[d];
      for (uint32_t o = 0; o < owners_; ++o) {
        const uint64_t n = s.Stretch(o, owners_);
        DCT_CHECK((n >= 1 || owners_ > 1) && n <= c)
            << "distinct-column list of " << n << " does not fit " << c;
        int32_t* out = cols + d * cap + o * c;
        std::memcpy(out, s.list.data() + (owners_ == 1 ? 0 : s.first[o]),
                    n * sizeof(int32_t));
        std::fill(out + n, out + c, INT32_MAX);
      }
    }
  }

 private:
  static constexpr int kMaxDigit = 13;

  struct Shard {
    std::vector<int32_t> list;          // distinct columns, ascending
    std::vector<uint64_t> first;        // several owners: where each
                                        // one's stretch of the list starts

    uint64_t Stretch(uint32_t o, uint32_t owners) const {
      return owners == 1 ? list.size() : first[o + 1] - first[o];
    }
    std::vector<uint64_t> keys, tmp;    // sort scratch, kept
    std::vector<uint32_t> count;        // digit counts, every pass

    // An LSD radix sort of (col, entry) pairs by col, then one walk that
    // numbers the runs. The sort takes as few passes as 13-bit digits
    // allow over the bits the largest column has (two for a space of
    // 2^26, as the benchmark's), and counts every pass's digits in one
    // read.
    void Sort(int32_t* col, uint64_t n) {
      list.clear();
      if (n == 0) {
        list.push_back(0);
        return;
      }
      keys.resize(n);
      tmp.resize(n);
      uint32_t top = 0;
      for (uint64_t i = 0; i < n; ++i) {
        const uint32_t c = static_cast<uint32_t>(col[i]);
        top |= c;
        keys[i] = (static_cast<uint64_t>(c) << 32) | i;
      }
      int bits = 0;
      while (bits < 32 && (top >> bits) != 0) ++bits;
      const int passes = (bits + kMaxDigit - 1) / kMaxDigit;
      const int digit = passes == 0 ? 0 : (bits + passes - 1) / passes;
      const uint32_t radix = 1u << digit;
      count.assign(static_cast<size_t>(passes) * radix, 0);
      for (uint64_t i = 0; i < n; ++i) {
        const uint32_t c = static_cast<uint32_t>(keys[i] >> 32);
        for (int p = 0; p < passes; ++p) {
          ++count[p * radix + ((c >> (p * digit)) & (radix - 1))];
        }
      }
      uint64_t* src = keys.data();
      uint64_t* dst = tmp.data();
      for (int p = 0; p < passes; ++p) {
        uint32_t* at = count.data() + static_cast<size_t>(p) * radix;
        uint32_t sum = 0;
        for (uint32_t b = 0; b < radix; ++b) {  // counts -> first places
          const uint32_t c = at[b];
          at[b] = sum;
          sum += c;
        }
        const int shift = 32 + p * digit;
        for (uint64_t i = 0; i < n; ++i) {
          dst[at[(src[i] >> shift) & (radix - 1)]++] = src[i];
        }
        std::swap(src, dst);
      }
      int64_t prev = -1;
      for (uint64_t i = 0; i < n; ++i) {
        const int64_t c = static_cast<int64_t>(src[i] >> 32);
        if (c != prev) {
          list.push_back(static_cast<int32_t>(c));
          prev = c;
        }
        col[src[i] & 0xffffffffu] = static_cast<int32_t>(list.size() - 1);
      }
    }
  };

  std::vector<Shard> shards_;
  uint64_t real_ = 0;
  uint64_t owner_max_ = 0;
  uint32_t owners_ = 1;
  uint64_t range_ = 0;
};

}  // namespace dct

#endif  // DCT_COL_SLOTS_H_
