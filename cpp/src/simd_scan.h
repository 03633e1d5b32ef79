// SIMD text-ingest engine (doc/parsing.md).
//
// Two stages, after simdjson's design (Langdale & Lemire, "Parsing
// Gigabytes of JSON per Second") adapted to what actually measures faster
// on ML text formats:
//
// STAGE 1 — structural scan. Kernels classify 64-byte blocks — two
// 32-byte AVX2 compares, four SSE2 compares, or eight 64-bit SWAR loads —
// into bitmask planes:
//   eol    '\n' | '\r'            (row boundaries)
//   sep    ':' or the csv delimiter (token/cell boundaries)
//   blank  ' ' | '\t'             (token separators; disabled for csv)
//   digit  '0'..'9'               (digit-run extents)
// The production parsers run the count-only form (CountSepEol) per chunk:
// popcount(sep) bounds nnz and popcount(eol)+1 bounds rows, so every
// RowBlockContainer vector reserves once instead of realloc-churning. The
// full tape (ScanTape + StructCursor + DigitRunAt) is the same kernels
// with the masks materialized — the structural index a tape-walking
// stage 2 would consume, kept as the engine's API and cross-checked
// against scalar classification by test_core --parse on every tier.
//
// STAGE 2 — fused field decode (the primitives further down). Measured on
// the bench host, walking the bit tape per TOKEN loses: the scalar
// parsers' byte loops are branch-predictable and already fuse
// tokenization into decoding, so a separate positional walk pays twice.
// What wins is fusing the DECODE — classifying and folding whole fields
// from one or two 8-byte loads (DigitRunLen8/DigitRunValue8) instead of
// per-character loops. parser.cc instantiates ONE tokenizer per format
// twice: kFused=false IS the scalar lane, kFused=true swaps in these
// primitives, which only accept shapes whose value AND consumption
// provably equal the scalar ops' — byte-identical lanes by construction
// (tests/test_parse_simd.py and test_core --parse pin it).
//
// Tier selection is runtime: CPUID picks AVX2 > SSE2 on x86, the 64-bit
// SWAR kernels cover everything little-endian, and big-endian hosts (or
// DMLC_PARSE_SIMD=0, the kill switch) keep the scalar parsers.
#ifndef DCT_SIMD_SCAN_H_
#define DCT_SIMD_SCAN_H_

#include <cstdint>
#include <cstring>
#include <vector>

#include "numparse.h"

namespace dct {

// Dispatch tiers, ordered by preference. The numeric values are stable:
// they ride the C ABI (dct_parse_pipeline_stats_t.simd_tier) and the
// DMLC_PARSE_SIMD override env understood by the CI lanes.
enum SimdTier {
  kSimdScalar = 0,  // byte-at-a-time parsers, no tape
  kSimdSWAR = 1,    // 64-bit SWAR blocks (any little-endian CPU)
  kSimdSSE2 = 2,    // 16-byte blocks (x86-64 baseline)
  kSimdAVX2 = 3,    // 32-byte blocks (runtime CPUID)
};

// Best tier this CPU supports (CPUID probed once, cached).
SimdTier BestSupportedSimdTier();

// Tier for a parser constructed NOW: DMLC_PARSE_SIMD env, clamped to
// hardware support. "0"/"off"/"scalar" force the scalar lane; "swar",
// "sse2", "avx2" pin a tier (clamped down if unsupported); unset/""/"1"/
// "auto" pick BestSupportedSimdTier(). Read per call (not cached) so a
// process can flip lanes between parser constructions — the differential
// tests rely on that.
SimdTier ResolveSimdTier();

const char* SimdTierName(int tier);

// --------------------------------------------------------------------------
// The structural index tape: four bitmask planes, bit i of word w
// classifying byte base[w*64 + i]. Planes:
//   all_    any structural (blank | sep | eol) — the token-end scan plane
//   sep_    ':' (libsvm/libfm) or the csv delimiter
//   eol_    '\n' | '\r'
//   digit_  '0'..'9'
// blank is implied: all_ & ~sep_ & ~eol_.
class ScanTape {
 public:
  // Classify [begin, end). blank0/blank1 are the blank-class chars (pass
  // '\0' for both to disable the class — csv), sep is the single separator
  // char. tier must be > kSimdScalar.
  void Build(const char* begin, const char* end, char blank0, char blank1,
             char sep, SimdTier tier);

  size_t size() const { return size_; }
  // reserve hints
  size_t sep_count() const { return n_sep_; }
  size_t eol_count() const { return n_eol_; }

  // kinds returned by the structural scans below
  enum Kind : uint32_t { kBlank = 0, kSep = 1, kEol = 2, kNone = 3 };

  // First structural position >= pos, or size() when none. *kind receives
  // the class of the found byte (kNone at end).
  size_t NextStructural(size_t pos, Kind* kind) const {
    size_t w = pos >> 6;
    const size_t nw = words_;
    if (w >= nw) {
      *kind = kNone;
      return size_;
    }
    uint64_t m = all_[w] & (~0ull << (pos & 63));
    while (m == 0) {
      if (++w >= nw) {
        *kind = kNone;
        return size_;
      }
      m = all_[w];
    }
    const size_t hit = (w << 6) + static_cast<size_t>(__builtin_ctzll(m));
    *kind = KindAt(hit, w);
    return hit;
  }

  // Class of the structural byte at pos (caller guarantees the all_ bit).
  Kind KindAt(size_t pos, size_t w) const {
    const uint64_t bit = 1ull << (pos & 63);
    if (eol_[w] & bit) return kEol;
    if (sep_[w] & bit) return kSep;
    return kBlank;
  }
  Kind KindOf(size_t pos) const { return KindAt(pos, pos >> 6); }
  size_t words() const { return words_; }
  const uint64_t* all_words() const { return all_.data(); }
  const uint64_t* sep_words() const { return sep_.data(); }
  const uint64_t* eol_words() const { return eol_.data(); }
  bool IsStructural(size_t pos) const {
    return (all_[pos >> 6] >> (pos & 63)) & 1;
  }
  bool IsEol(size_t pos) const { return (eol_[pos >> 6] >> (pos & 63)) & 1; }
  bool IsSep(size_t pos) const { return (sep_[pos >> 6] >> (pos & 63)) & 1; }
  bool IsBlankKind(size_t pos) const {
    const size_t w = pos >> 6;
    const uint64_t bit = 1ull << (pos & 63);
    return (all_[w] & bit) && !((sep_[w] | eol_[w]) & bit);
  }

  // First EOL position >= pos, or size() (comment-line skipping).
  size_t NextEol(size_t pos) const {
    size_t w = pos >> 6;
    if (w >= words_) return size_;
    uint64_t m = eol_[w] & (~0ull << (pos & 63));
    while (m == 0) {
      if (++w >= words_) return size_;
      m = eol_[w];
    }
    return (w << 6) + static_cast<size_t>(__builtin_ctzll(m));
  }

  // Length of the digit run starting at pos, capped at `cap` (<= 64 - the
  // window the two-word load covers; token decoders need <= 20).
  int DigitRunAt(size_t pos, int cap) const {
    if (pos >= size_) return 0;
    const size_t w = pos >> 6;
    const unsigned o = pos & 63;
    uint64_t run = digit_[w] >> o;
    if (o != 0 && w + 1 < words_) run |= digit_[w + 1] << (64 - o);
    // trailing-ones count: first zero bit bounds the run
    const int len = run == ~0ull ? 64
                                 : static_cast<int>(__builtin_ctzll(~run));
    return len < cap ? len : cap;
  }

  // one block's classification lands here from whichever kernel ran
  // (public for the kernel functions in simd_scan.cc only)
  void PushBlock(uint64_t blank, uint64_t sep, uint64_t eol, uint64_t digit,
                 size_t w) {
    all_[w] = blank | sep | eol;
    sep_[w] = sep;
    eol_[w] = eol;
    digit_[w] = digit;
    n_sep_ += static_cast<size_t>(__builtin_popcountll(sep));
    n_eol_ += static_cast<size_t>(__builtin_popcountll(eol));
  }

 private:
  std::vector<uint64_t> all_, sep_, eol_, digit_;
  size_t size_ = 0;
  size_t words_ = 0;
  size_t n_sep_ = 0, n_eol_ = 0;
};

// --------------------------------------------------------------------------
// Streaming cursor over the structural bit stream: the current word's
// masks stay in registers, so advancing to the next structural is one
// ctz + clear-lowest-bit (plus a word refill every 64 bytes) instead of
// re-deriving word/bit state from a byte position per probe. The stage-2
// walkers are written against this: every structural byte is visited
// exactly once, in order, with its class.
class StructCursor {
 public:
  explicit StructCursor(const ScanTape& t)
      : all_(t.all_words()),
        sep_(t.sep_words()),
        eol_(t.eol_words()),
        nwords_(t.words()),
        size_(t.size()) {
    SeekTo(0);
  }

  size_t pos;          // position of the current structural; size() at end
  ScanTape::Kind kind; // its class; kNone at end

  // step past the current structural
  void Advance() {
    bits_ &= bits_ - 1;
    Settle();
  }

  // resync to the first structural >= p (fallback-row re-entry)
  void SeekTo(size_t p) {
    w_ = p >> 6;
    bits_ = w_ < nwords_ ? all_[w_] & (~0ull << (p & 63)) : 0;
    Settle();
  }

 private:
  void Settle() {
    while (bits_ == 0) {
      if (++w_ >= nwords_) {
        pos = size_;
        kind = ScanTape::kNone;
        return;
      }
      bits_ = all_[w_];
    }
    pos = (w_ << 6) + static_cast<size_t>(__builtin_ctzll(bits_));
    const uint64_t bit = bits_ & (~bits_ + 1);
    kind = (eol_[w_] & bit) ? ScanTape::kEol
           : (sep_[w_] & bit) ? ScanTape::kSep
                              : ScanTape::kBlank;
  }

  const uint64_t* all_;
  const uint64_t* sep_;
  const uint64_t* eol_;
  size_t nwords_, size_;
  size_t w_ = 0;
  uint64_t bits_ = 0;
};

// --------------------------------------------------------------------------
// Stage 2: fused SWAR field decoders.
//
// Measured on the bench host, walking the bit tape per TOKEN (a cursor
// advance per structural plus mask probes) costs more than it saves: the
// scalar parsers' byte loops are branch-predictable and fuse tokenization
// into decoding, so a separate walk pays twice. What does win is fusing
// the DECODE itself: one or two 8-byte loads classify and fold a whole
// field ([-]d+[.d+] or a feature id) with DigitRunLen8/DigitRunValue8
// instead of per-character loops. These primitives are drop-in
// replacements for the exact scalar ops (ParseNum / the inline digit
// loop) AT THE SAME CURSOR POSITION: whenever a fused primitive accepts,
// its value and consumption provably equal the scalar op's; whenever a
// shape is outside its envelope it declines and the caller runs the
// scalar op — so the fused and scalar parse lanes are byte-identical by
// construction, with no row re-parsing or rollback needed. The tape
// (ScanTape/StructCursor above) remains the structural engine: the
// production lane uses its counting kernels for reserve hints
// (CountSepEol), and the differential suites walk the full tape to
// cross-check every kernel tier.

// Count separator and newline/CR bytes in [begin, end) — the reserve-hint
// scan. Same classification kernels as ScanTape::Build, but pure popcount
// accumulation (no mask stores): sep bounds nnz, eol+1 bounds rows.
void CountSepEol(const char* begin, const char* end, char sep,
                 SimdTier tier, size_t* n_sep, size_t* n_eol);

// Scan a digit run starting at p: up to 15 digits via two guarded 8-byte
// loads, verified and folded in one pass. Returns the run length with the
// value in *v, 0 when p is not a digit (*v untouched), or kFusedOverflow
// when the run may extend past 15 digits or sits too close to load_end to
// load — the caller then delegates to its exact path (ParseNum /
// from_chars), which re-derives everything from p.
inline constexpr int kFusedOverflow = 99;

inline int FusedDigitScan(const char* p, const char* load_end, uint64_t* v) {
  // 1-2 digit ids dominate sparse ML data: settle them from byte probes
  // before any SWAR setup (two compares beat a load+classify there)
  const ptrdiff_t avail = load_end - p;
  if (avail <= 0 || !IsDigitChar(p[0])) return avail <= 0 ? kFusedOverflow
                                                          : 0;
  if (avail == 1 || !IsDigitChar(p[1])) {
    *v = static_cast<uint64_t>(p[0] - '0');
    return 1;
  }
  if (avail == 2 || !IsDigitChar(p[2])) {
    *v = static_cast<uint64_t>(p[0] - '0') * 10u +
         static_cast<uint64_t>(p[1] - '0');
    return 2;
  }
  if (!detail::kSwarLE || avail < 8) return kFusedOverflow;
  uint64_t c0;
  std::memcpy(&c0, p, 8);
  const int il = detail::DigitRunLen8(c0);
  if (il < 8) {
    *v = detail::DigitRunValue8(c0, il);  // il >= 3 here
    return il;
  }
  if (avail < 16) return kFusedOverflow;
  uint64_t c1;
  std::memcpy(&c1, p + 8, 8);
  const int fl = detail::DigitRunLen8(c1);
  if (fl >= 8) return kFusedOverflow;  // 16+ digits: exact path decides
  *v = fl != 0 ? detail::DigitRunValue8(c0, 8) * detail::kPow10U64[fl] +
                     detail::DigitRunValue8(c1, fl)
               : detail::DigitRunValue8(c0, 8);
  return 8 + fl;
}

// Fused float decode starting at p: finds its own end from the loaded
// words (like the scalar ParseFloatFast does from bytes) and covers the
// dominant ML shapes [-+]?D{1,7}(.D{1,7})? — sign, integer run, '.',
// fraction run, all measured by DigitRunLen8 on two 8-byte loads. Returns
// the first unconsumed byte, or nullptr for every other shape (exponents,
// 8+ digit runs, inf/nan/garbage, tokens too close to load_end): the
// caller then runs ParseNum from the SAME position. Acceptance is
// envelope-safe by construction (<= 14 digits, exponent >= -7, all inside
// ParseFloatFast's exact range) and the arithmetic below IS
// ParseFloatFast's — same mant, same exp10, same double ops — so fused
// and scalar decodes agree bit-for-bit (the differential suites pin it).
template <typename T>
inline const char* DecodeFloatAuto(const char* p, const char* load_end,
                                   T* v) {
  // caller guarantees p != load_end
  const bool neg = *p == '-';
  const char* s = p + (neg || *p == '+' ? 1 : 0);
  // room for the 2-digit byte probes plus the fraction's 8-byte load;
  // tokens closer to the chunk end than this take the exact path
  if (!detail::kSwarLE || load_end - s < 11) return nullptr;
  // integer part: byte probes for the dominant 0-2 digit case, one SWAR
  // gulp for longer runs
  uint64_t ipart;
  int il;
  if (!IsDigitChar(s[0])) {
    if (s[0] != '.') return nullptr;  // inf/nan/garbage: exact path
    il = 0;
    ipart = 0;
  } else if (!IsDigitChar(s[1])) {
    il = 1;
    ipart = static_cast<uint64_t>(s[0] - '0');
  } else if (!IsDigitChar(s[2])) {
    il = 2;
    ipart = static_cast<uint64_t>(s[0] - '0') * 10u +
            static_cast<uint64_t>(s[1] - '0');
  } else {
    uint64_t c0;
    std::memcpy(&c0, s, 8);
    il = detail::DigitRunLen8(c0);  // >= 3 here
    if (il >= 8) return nullptr;    // long integer part: exact path
    ipart = detail::DigitRunValue8(c0, il);
  }
  uint64_t mant;
  int fl = 0;
  const char* after;
  const char ci = s[il];
  if (ci == '.') {
    const char* f = s + il + 1;
    if (load_end - f < 8) return nullptr;
    uint64_t c1;
    std::memcpy(&c1, f, 8);
    fl = detail::DigitRunLen8(c1);
    if (fl == 0 || fl >= 8) return nullptr;  // "5." / long fraction
    const char ce = f[fl];
    if (ce == 'e' || ce == 'E') return nullptr;
    mant = ipart * detail::kPow10U64[fl] + detail::DigitRunValue8(c1, fl);
    after = f + fl;
  } else if (il == 0) {
    return nullptr;  // bare '.' — exact path decides consumption
  } else {
    if (ci == 'e' || ci == 'E') return nullptr;  // exponent: exact path
    mant = ipart;
    after = s + il;
  }
  double d = static_cast<double>(mant);
  if (fl != 0) d = d / detail::kPow10[fl];
  *v = static_cast<T>(neg ? -d : d);
  return after;
}

// ParseNum with the fused fast lane in front (compile-time selected):
// the scalar parse lanes instantiate kFused=false and get exactly the old
// ParseNum; the SIMD lanes instantiate kFused=true.
template <bool kFused, typename T>
inline bool ParseNumF(const char* p, const char* end, const char** out,
                      T* v) {
  if constexpr (kFused) {
    if (p != end) {
      if constexpr (std::is_floating_point_v<T>) {
        const char* after = DecodeFloatAuto(p, end, v);
        if (after != nullptr) {
          *out = after;
          return true;
        }
      } else {
        // integral ids/cells (qid, libfm fields, csv int dtypes): digit
        // budgets that can never overflow T (9 digits < 2^31, 15 < 2^50);
        // longer runs, '+' signs, and chunk-end tails take the exact path
        const bool sneg = std::is_signed_v<T> && *p == '-';
        const char* q = p + (sneg ? 1 : 0);
        if (q != end && IsDigitChar(*q)) {
          constexpr int kSafe = sizeof(T) == 8 ? 15 : 9;
          uint64_t val;
          const int il = FusedDigitScan(q, end, &val);
          if (il >= 1 && il <= kSafe) {
            const int64_t sv =
                sneg ? -static_cast<int64_t>(val) : static_cast<int64_t>(val);
            *v = static_cast<T>(sv);
            *out = q + il;
            return true;
          }
        }
      }
    }
  }
  return ParseNum<T>(p, end, out, v);
}

// ParsePair / ParseTriple over ParseNumF — same contracts as the
// numparse.h originals (which the kFused=false instantiation reproduces
// op for op).
template <bool kFused, typename TA, typename TB>
inline int ParsePairF(const char* p, const char* end, const char** out,
                      TA* a, TB* b) {
  while (p != end && IsBlankChar(*p)) ++p;
  if (p == end) {
    *out = end;
    return 0;
  }
  const char* q;
  if (!ParseNumF<kFused>(p, end, &q, a)) {
    *out = end;
    return 0;
  }
  if (q == end || *q != ':') {
    *out = q;
    return 1;
  }
  const char* r;
  if (!ParseNumF<kFused>(q + 1, end, &r, b)) {
    *out = q;
    return 1;
  }
  *out = r;
  return 2;
}

template <bool kFused, typename TA, typename TB, typename TC>
inline int ParseTripleF(const char* p, const char* end, const char** out,
                        TA* a, TB* b, TC* c) {
  TA ta;
  TB tb;
  int n = ParsePairF<kFused, TA, TB>(p, end, out, &ta, &tb);
  if (n >= 1) *a = ta;
  if (n >= 2) *b = tb;
  if (n < 2) return n;
  const char* q = *out;
  if (q == end || *q != ':') return 2;
  const char* r;
  if (!ParseNumF<kFused>(q + 1, end, &r, c)) return 2;
  *out = r;
  return 3;
}

}  // namespace dct

#endif  // DCT_SIMD_SCAN_H_
