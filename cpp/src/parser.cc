// Parser implementations. Parse-rule provenance is cited per function; the
// threading/fan-out structure is original (see parser.h).
#include "parser.h"

#include <algorithm>
#include <cmath>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <exception>
#include <limits>
#include <thread>

#include "criteo_hash.h"
#include "fs_fault.h"
#include "numparse.h"
#include "parameter.h"
#include "recordio.h"
#include "registry.h"
#include "shard_cache.h"
#include "telemetry.h"

namespace dct {

namespace {

// Process-wide pipeline telemetry (telemetry.h): totals across every
// PipelinedParser instance plus per-stage latency histograms. The
// per-handle ParsePipelineStats struct stays the per-parser view; these
// are what dct_telemetry_snapshot / /metrics serve. Pointers resolved
// once (registry lookup), then every touch is one relaxed atomic op.
struct PipeTelemetry {
  telemetry::Counter* chunks_read;
  telemetry::Counter* blocks_delivered;
  telemetry::Counter* reader_waits;
  telemetry::Counter* worker_waits;
  telemetry::Counter* consumer_waits;
  telemetry::Hist* fill_us;             // ReadChunk (source -> owned bytes)
  telemetry::Hist* scan_us;             // TileCuts slice pre-tiling
  telemetry::Hist* parse_us;            // one worker slice decode
  telemetry::Hist* reassemble_wait_us;  // consumer head-of-line wait
};

const PipeTelemetry& PipeTel() {
  static const PipeTelemetry t = {
      telemetry::GetCounter("parse_chunks_read_total"),
      telemetry::GetCounter("parse_blocks_delivered_total"),
      telemetry::GetCounter("parse_reader_waits_total"),
      telemetry::GetCounter("parse_worker_waits_total"),
      telemetry::GetCounter("parse_consumer_waits_total"),
      telemetry::GetHist("parse_stage_fill_us"),
      telemetry::GetHist("parse_stage_scan_us"),
      telemetry::GetHist("parse_stage_parse_us"),
      telemetry::GetHist("parse_stage_reassemble_wait_us"),
  };
  return t;
}

// Skip blanks; a '#' means the rest of the line is a comment
// (reference libsvm_parser.h IgnoreCommentAndBlank).
inline const char* SkipBlankOrComment(const char* p, const char* end) {
  while (p != end && IsBlankChar(*p)) ++p;
  if (p != end && *p == '#') return end;
  return p;
}

// Advance past one line; *line_end receives the end of the current line
// (excluding the terminator); returns the start of the next line. Both
// '\n' and bare '\r' terminate a line (reference text_parser.h semantics);
// memchr keeps the scans vectorized. "\r\n" and blank lines yield empty
// lines which every parser skips.
inline const char* LineSpan(const char* p, const char* end,
                            const char** line_end) {
  const char* nl =
      static_cast<const char*>(memchr(p, '\n', static_cast<size_t>(end - p)));
  const char* limit = nl == nullptr ? end : nl;
  const char* cr =
      static_cast<const char*>(memchr(p, '\r', static_cast<size_t>(limit - p)));
  const char* term = cr == nullptr ? limit : cr;
  *line_end = term;
  return term == end ? end : term + 1;
}

inline const char* SkipUTF8BOM(const char* p, const char* end) {
  if (end - p >= 3 && static_cast<unsigned char>(p[0]) == 0xEF &&
      static_cast<unsigned char>(p[1]) == 0xBB &&
      static_cast<unsigned char>(p[2]) == 0xBF) {
    return p + 3;
  }
  return p;
}

int DefaultThreads(int requested) {
  // The reference caps workers at max(nprocs/2 - 4, 1)
  // (text_parser.h:28) — a fudge tuned for 2010s many-core Xeons that
  // throttles to 1 thread on the small hosts fronting TPU slices. Here the
  // default uses every available core (the parse workers are the ingest
  // bottleneck and XLA compute runs on the TPU, not these cores), and an
  // explicit request is honored up to a 4x oversubscription bound so
  // I/O-stalled workers can still overlap.
  int hw = std::max(static_cast<int>(std::thread::hardware_concurrency()), 1);
  if (requested <= 0) return hw;
  return std::min(requested, std::max(4 * hw, 8));
}

std::string GetArg(const std::map<std::string, std::string>& args,
                   const std::string& key, const std::string& dflt) {
  auto it = args.find(key);
  return it == args.end() ? dflt : it->second;
}

}  // namespace

// -- parser parameters (reflection structs, reference LibSVMParserParam
//    libsvm_parser.h:24-39 / CSVParserParam csv_parser.h:24-55 /
//    LibFMParserParam libfm_parser.h:24-40) --------------------------------
struct LibSVMParserParam : public Parameter<LibSVMParserParam> {
  std::string format;
  int indexing_mode;
  DCT_DECLARE_PARAMETER(LibSVMParserParam) {
    DCT_DECLARE_FIELD(format).set_default("libsvm");
    DCT_DECLARE_FIELD(indexing_mode)
        .set_default(0)
        .add_enum("auto", -1)
        .add_enum("zero_based", 0)
        .add_enum("one_based", 1)
        .describe("0: indices start at 0; 1: start at 1 (converted); "
                  "-1: heuristic (sklearn-compatible, reference "
                  "libsvm_parser.h:24-39)");
  }
};

struct CSVParserParam : public Parameter<CSVParserParam> {
  std::string format;
  int label_column;
  int weight_column;
  std::string delimiter;
  int dtype;
  DCT_DECLARE_PARAMETER(CSVParserParam) {
    DCT_DECLARE_FIELD(format).set_default("csv");
    DCT_DECLARE_FIELD(label_column)
        .set_default(-1)
        .set_lower_bound(-1)
        .describe("column holding the label; -1: no label column");
    DCT_DECLARE_FIELD(weight_column)
        .set_default(-1)
        .set_lower_bound(-1)
        .describe("column holding the row weight; -1: none");
    DCT_DECLARE_FIELD(delimiter)
        .set_default(",")
        .describe("single-character field delimiter");
    DCT_DECLARE_FIELD(dtype)
        .set_default(0)
        .set_range(0, 2)
        .add_enum("float32", 0)
        .add_enum("int32", 1)
        .add_enum("int64", 2)
        .describe("value dtype (reference csv_parser.h DType)");
  }
};

struct LibFMParserParam : public Parameter<LibFMParserParam> {
  std::string format;
  int indexing_mode;
  DCT_DECLARE_PARAMETER(LibFMParserParam) {
    DCT_DECLARE_FIELD(format).set_default("libfm");
    DCT_DECLARE_FIELD(indexing_mode)
        .set_default(0)
        .add_enum("auto", -1)
        .add_enum("zero_based", 0)
        .add_enum("one_based", 1)
        .describe("indexing heuristic over field and feature ids "
                  "(reference libfm_parser.h:24-40)");
  }
};

struct CriteoParserParam : public Parameter<CriteoParserParam> {
  std::string format;
  int hash_bits;
  DCT_DECLARE_PARAMETER(CriteoParserParam) {
    DCT_DECLARE_FIELD(format).set_default("criteo");
    DCT_DECLARE_FIELD(hash_bits)
        .set_range(1, 63)
        .describe("feature ids are the cells' 64-bit hashes folded to this "
                  "many bits (criteo_hash.h), so the feature space is "
                  "2^hash_bits; at most 31 with 32-bit indices (the device "
                  "layout); no default: the model's table is sized by it");
  }
};

// --------------------------------------------------------------------------
template <typename IndexType>
TextParserBase<IndexType>::TextParserBase(InputSplit* source, int nthread)
    : source_(source),
      nthread_(DefaultThreads(nthread)),
      // per-construction resolve (not a process-global): the differential
      // lanes flip DMLC_PARSE_SIMD between parser constructions to compare
      // SIMD and scalar output in one process
      simd_tier_(ResolveSimdTier()) {}

template <typename IndexType>
TextParserBase<IndexType>::~TextParserBase() {
  {
    std::lock_guard<std::mutex> lk(pool_mu_);
    pool_stop_ = true;
  }
  pool_cv_.notify_all();
  for (auto& t : pool_) t.join();
}

template <typename IndexType>
void TextParserBase<IndexType>::EnsurePool(int workers) {
  while (static_cast<int>(pool_.size()) < workers) {
    int i = static_cast<int>(pool_.size());
    pool_.emplace_back([this, i] { WorkerLoop(i); });
  }
}

template <typename IndexType>
void TextParserBase<IndexType>::BeforeFirst() {
  source_->BeforeFirst();
  blocks_.clear();
  block_idx_ = block_count_ = 0;
}

namespace {
// Optional per-row arrays must be absent or full-length: the C ABI exposes
// them as dense parallel arrays, so ragged input (e.g. libsvm rows mixing
// `idx:val` and bare `idx` features) must fail loudly, not misalign.
// The offset checks guard the binary rec lane: LoadAppend validates vector
// LENGTHS against the stream, but a bit-flipped record can carry
// non-monotone or inflated offset VALUES that would underflow
// offset[r+1]-offset[r] in the batcher fills and index out of bounds —
// they must die here, not in a memcpy.
template <typename IndexType>
void ValidateBlock(const RowBlockContainer<IndexType>& b) {
  DCT_CHECK(b.offset.size() == b.label.size() + 1 && b.offset.front() == 0)
      << "corrupt row block: " << b.offset.size() << " offsets for "
      << b.label.size() << " rows";
  DCT_CHECK(b.offset.back() == b.index.size())
      << "corrupt row block: final offset " << b.offset.back()
      << " does not match " << b.index.size() << " features";
  for (size_t i = 1; i < b.offset.size(); ++i) {
    DCT_CHECK(b.offset[i - 1] <= b.offset[i])
        << "corrupt row block: offsets decrease at row " << (i - 1);
  }
  DCT_CHECK(b.ValueCount() == 0 || b.ValueCount() == b.index.size())
      << "inconsistent input: some features have explicit values and some "
         "do not (" << b.ValueCount() << " values for " << b.index.size()
      << " features)";
  DCT_CHECK(b.weight.empty() || b.weight.size() == b.label.size())
      << "inconsistent input: only " << b.weight.size() << " of "
      << b.label.size() << " rows carry a label:weight";
  DCT_CHECK(b.qid.empty() || b.qid.size() == b.label.size())
      << "inconsistent input: only " << b.qid.size() << " of "
      << b.label.size() << " rows carry qid:";
  DCT_CHECK(b.field.empty() || b.field.size() == b.index.size())
      << "inconsistent libfm input: field count mismatch";
}
}  // namespace

template <typename IndexType>
void TextParserBase<IndexType>::WorkerLoop(int i) {
  uint64_t seen = 0;
  for (;;) {
    std::unique_lock<std::mutex> lk(pool_mu_);
    pool_cv_.wait(lk, [&] {
      return pool_stop_ ||
             (pool_generation_ != seen && i < pool_active_);
    });
    if (pool_stop_) return;
    seen = pool_generation_;
    // worker i owns slice i+1 (slice 0 runs on the calling thread)
    const char* b = (*round_cuts_)[i + 1];
    const char* e = (*round_cuts_)[i + 2];
    auto* out = &(*round_blocks_)[i + 1];
    auto* err = &(*round_errors_)[i + 1];
    lk.unlock();
    try {
      this->ParseBlock(b, e, out);
      ValidateBlock(*out);
    } catch (...) {
      *err = std::current_exception();
    }
    lk.lock();
    if (++pool_done_ == pool_active_) done_cv_.notify_one();
  }
}

template <typename IndexType>
bool TextParserBase<IndexType>::ReadChunk(std::vector<char>* buf) {
  // Fast lane: when the split chain's top exposes the chunk-producer
  // interface (ByteSplit / IndexedRecordIOSplit — the pipelined Create
  // skips the PrefetchSplit wrapper precisely so it does), fill the task
  // buffer straight from the stream: zero extra copies.
  if (!chunk_source_probed_) {
    chunk_source_ = dynamic_cast<RecordChunkSource*>(source_.get());
    chunk_source_probed_ = true;
  }
  if (chunk_source_ != nullptr) {
    if (!chunk_source_->FillChunkBuffer(buf)) return false;
    bytes_read_.fetch_add(buf->size(), std::memory_order_relaxed);
    return true;
  }
  // wrapped chains (ShuffleSplit, PrefetchSplit): the Blob aliases the
  // split's internal buffer (invalid after the next NextChunk), so an
  // in-flight chunk needs its own copy — a memcpy at memory bandwidth
  // against parsing at ~1% of it
  InputSplit::Blob chunk;
  if (!source_->NextChunk(&chunk)) return false;
  bytes_read_.fetch_add(chunk.size, std::memory_order_relaxed);
  buf->assign(static_cast<const char*>(chunk.dptr),
              static_cast<const char*>(chunk.dptr) + chunk.size);
  return true;
}

template <typename IndexType>
void TextParserBase<IndexType>::TileCuts(const char* begin, const char* end,
                                         int nslice,
                                         std::vector<const char*>* cuts) {
  // Tile the chunk into unit-aligned slices: cut i starts at the first
  // parse-unit head at/after i*size/n — line heads for text formats,
  // RecordIO magics for binary (FindUnitBoundary; the reference tiles text
  // backward via BackFindEndLine — forward tiling yields the same cover).
  const size_t size = static_cast<size_t>(end - begin);
  cuts->resize(nslice + 1);
  (*cuts)[0] = begin;
  (*cuts)[nslice] = end;
  for (int i = 1; i < nslice; ++i) {
    (*cuts)[i] = FindUnitBoundary(begin, begin + size * i / nslice, end);
  }
  for (int i = 1; i < nslice; ++i) {
    if ((*cuts)[i] < (*cuts)[i - 1]) (*cuts)[i] = (*cuts)[i - 1];
  }
}

template <typename IndexType>
bool TextParserBase<IndexType>::FillBlocks(
    std::vector<RowBlockContainer<IndexType>>* blocks) {
  InputSplit::Blob chunk;
  if (!source_->NextChunk(&chunk)) return false;
  bytes_read_.fetch_add(chunk.size, std::memory_order_relaxed);
  const char* begin = static_cast<const char*>(chunk.dptr);
  const char* end = begin + chunk.size;
  const int nworker = SlicesFor(chunk.size);
  blocks->resize(nworker);
  if (nworker == 1) {
    ParseBlock(begin, end, &(*blocks)[0]);
    ValidateBlock((*blocks)[0]);
    return true;
  }
  std::vector<const char*> cuts;
  TileCuts(begin, end, nworker, &cuts);
  // fan out slices 1..n-1 to the persistent pool; slice 0 parses on this
  // thread (spawning fresh threads per chunk would tax every chunk ~100 us
  // per worker — the pool signals instead)
  std::vector<std::exception_ptr> errors(nworker);
  EnsurePool(nworker - 1);
  {
    std::lock_guard<std::mutex> lk(pool_mu_);
    round_cuts_ = &cuts;
    round_blocks_ = blocks;
    round_errors_ = &errors;
    pool_done_ = 0;
    pool_active_ = nworker - 1;
    ++pool_generation_;
  }
  pool_cv_.notify_all();
  std::exception_ptr my_error;
  try {
    ParseBlock(cuts[0], cuts[1], &(*blocks)[0]);
    ValidateBlock((*blocks)[0]);
  } catch (...) {
    my_error = std::current_exception();
  }
  {
    std::unique_lock<std::mutex> lk(pool_mu_);
    done_cv_.wait(lk, [&] { return pool_done_ == pool_active_; });
  }
  if (my_error != nullptr) std::rethrow_exception(my_error);
  for (auto& e : errors) {
    if (e != nullptr) std::rethrow_exception(e);  // reference OMPException
  }
  return true;
}

template <typename IndexType>
const char* TextParserBase<IndexType>::FindUnitBoundary(const char* base,
                                                        const char* hint,
                                                        const char* end) {
  (void)base;
  const char* nl = static_cast<const char*>(
      memchr(hint, '\n', static_cast<size_t>(end - hint)));
  return nl == nullptr ? end : nl + 1;
}

template <typename IndexType>
const RowBlockContainer<IndexType>* TextParserBase<IndexType>::NextBlock() {
  while (true) {
    while (block_idx_ < block_count_) {
      const RowBlockContainer<IndexType>* b = &blocks_[block_idx_++];
      if (b->Size() != 0) return b;
    }
    if (!FillBlocks(&blocks_)) return nullptr;
    block_count_ = blocks_.size();
    block_idx_ = 0;
  }
}

template <typename IndexType>
bool TextParserBase<IndexType>::NextBlockMove(
    RowBlockContainer<IndexType>* out) {
  // swap hand-off: the consumer gets the parsed buffers, the worker slot
  // keeps out's old capacity for the next chunk
  const RowBlockContainer<IndexType>* b = NextBlock();
  if (b == nullptr) return false;
  std::swap(*out, blocks_[block_idx_ - 1]);
  return true;
}

// --------------------------------------------------------------------------
template <typename IndexType>
LibSVMParser<IndexType>::LibSVMParser(
    InputSplit* source, const std::map<std::string, std::string>& args,
    int nthread)
    : TextParserBase<IndexType>(source, nthread) {
  LibSVMParserParam param;
  param.Init(args, ParamInitOption::kAllowUnknown);
  DCT_CHECK_EQ(param.format, std::string("libsvm")) << "format mismatch";
  indexing_mode_ = param.indexing_mode;
}

namespace {
// Advance past the current line: to just after the next '\n'/'\r', or end.
inline const char* SkipToEol(const char* p, const char* end) {
  const char* nl =
      static_cast<const char*>(memchr(p, '\n', static_cast<size_t>(end - p)));
  const char* limit = nl == nullptr ? end : nl;
  const char* cr =
      static_cast<const char*>(memchr(p, '\r', static_cast<size_t>(limit - p)));
  const char* term = cr == nullptr ? limit : cr;
  return term == end ? end : term + 1;
}

inline bool IsEolChar(char c) { return c == '\n' || c == '\r'; }
}  // namespace

namespace {
// One libsvm row starting at p (a non-blank, non-EOL char); returns the
// cursor past the row's line terminator (or end). This IS the scalar
// tokenizer (reference src/data/libsvm_parser.h:87-169 semantics:
// comment/garbage lines discard, label[:weight], qid:, bare-index
// features, ':'-garbage discards the line tail). kFused=false compiles
// to exactly the scalar byte loops; kFused=true swaps the numeric
// primitives for the fused SWAR field decoders (simd_scan.h), which
// accept only shapes whose value AND consumption provably equal the
// scalar ops' — so both instantiations are byte-identical by
// construction.
//
// `dec` (0 or 1) is subtracted from every feature id as it is written:
// the decode-path hoist of the old O(nnz) 1-based post-pass for forced
// indexing_mode=1. *min_feat tracks the RAW (pre-decrement) ids for the
// indexing_mode=auto heuristic, which still needs one deferred pass (the
// minimum over the block is only known once the block ends).
template <typename IndexType, bool kFused>
const char* ParseLibSVMRow(const char* p, const char* end,
                           RowBlockContainer<IndexType>* out,
                           IndexType* min_feat, IndexType dec) {
  // feature ids below 10 digits accumulate in a u64 without overflow; wider
  // tokens delegate to ParseNum for exact from_chars overflow semantics
  constexpr int kFastIdxDigits = sizeof(IndexType) == 8 ? 19 : 9;
  if (*p == '#') return SkipToEol(p, end);  // comment-only line
  // label[:weight] — the parse stops at any non-numeric char, so the
  // chunk end doubles as the line bound here
  float label;
  if (!ParseNumF<kFused, float>(p, end, &p, &label)) {
    return SkipToEol(p, end);  // garbage line: discard (ParsePair contract)
  }
  if (p != end && *p == ':') {
    float weight;
    const char* wp;
    if (ParseNumF<kFused, float>(p + 1, end, &wp, &weight)) {
      out->weight.push_back(weight);
      p = wp;
    }
    // ":garbage" leaves p at ':' — the token loop below then discards
    // the rest of the line, matching the line-oriented behavior
  }
  out->label.push_back(label);
  // optional qid:n (space-separated, reference libsvm_parser.h:116-126)
  while (p != end && *p == ' ') ++p;
  if (end - p > 4 && std::memcmp(p, "qid:", 4) == 0) {
    uint64_t qid = 0;
    const char* qp;
    if (ParseNumF<kFused, uint64_t>(p + 4, end, &qp, &qid)) {
      out->qid.push_back(qid);
      p = qp;
    }
  }
  // index[:value] tokens until end of line
  while (true) {
    while (p != end && IsBlankChar(*p)) ++p;
    if (p == end) break;
    const char c = *p;
    if (IsEolChar(c)) {
      ++p;
      break;
    }
    if (c == '#') {
      p = SkipToEol(p, end);
      break;
    }
    // feature id: fused digit-run scan (one or two 8-byte loads) or the
    // inline digit loop — identical consumption and value either way
    uint64_t idx = 0;
    int nd = 0;
    const char* tok = p;
    if constexpr (kFused) {
      const int il = FusedDigitScan(p, end, &idx);
      if (il >= 1 && il <= kFastIdxDigits) {
        nd = il;
        p += il;
      } else if (il != 0) {
        // overflow-length run or too close to the chunk end: force the
        // exact ParseNum delegate below (same as the scalar lane's
        // kFastIdxDigits+1 bail-out)
        nd = kFastIdxDigits + 1;
      }
    } else {
      while (p != end && IsDigitChar(*p)) {
        idx = idx * 10 + static_cast<uint64_t>(*p - '0');
        ++p;
        if (++nd > kFastIdxDigits) break;
      }
    }
    IndexType idx_t;
    if (nd == 0 || nd > kFastIdxDigits) {
      // '+'-prefixed, overflowing, or non-numeric token: exact fallback
      if (!ParseNum<IndexType>(tok, end, &p, &idx_t)) {
        p = SkipToEol(tok, end);  // discard rest of line
        break;
      }
    } else {
      idx_t = static_cast<IndexType>(idx);
    }
    const IndexType written = static_cast<IndexType>(idx_t - dec);
    out->index.push_back(written);
    // inline max tracking replaces the old post-parse UpdateMax pass (an
    // O(nnz) re-walk of the index array per block)
    out->max_index = std::max<uint64_t>(out->max_index, written);
    *min_feat = std::min(*min_feat, idx_t);
    if (p != end && *p == ':') {
      float value;
      const char* vp;
      if (ParseNumF<kFused, float>(p + 1, end, &vp, &value)) {
        out->value.push_back(value);
        p = vp;
      }
      // ":garbage": p stays at ':' and the next iteration discards the
      // line, matching ParsePair's r==1-then-fail sequence
    }
  }
  out->offset.push_back(out->index.size());
  return p;
}

// reference src/data/libsvm_parser.h:87-169. Single-pass tokenizer: rows
// and tokens are recognized in the same scan (newlines terminate the token
// loop directly), instead of pre-scanning each line for its end and then
// re-walking it. Semantics (comment/blank lines, label[:weight], qid:,
// bare-index features, discard-line-on-garbage, CRLF/CR/NOEOL) match the
// line-oriented form; tests/test_native_parser.py pins them and
// tests/test_parse_simd.py pins kFused=true == kFused=false.
template <bool kFused, typename IndexType>
void ParseLibSVMBlockImpl(const char* begin, const char* end,
                          int indexing_mode,
                          RowBlockContainer<IndexType>* out) {
  IndexType min_feat = std::numeric_limits<IndexType>::max();
  const IndexType dec = indexing_mode > 0 ? 1 : 0;
  const char* p = SkipUTF8BOM(begin, end);
  while (p != end) {
    // between rows: swallow blanks and empty lines in one skip
    while (p != end && (IsBlankChar(*p) || IsEolChar(*p))) ++p;
    if (p == end) break;
    p = ParseLibSVMRow<IndexType, kFused>(p, end, out, &min_feat, dec);
  }
  DCT_CHECK_EQ(out->label.size() + 1, out->offset.size());
  // 0/1-based auto heuristic (sklearn-compatible, reference
  // libsvm_parser.h:155-168); the forced >0 mode decrements at decode time
  // (dec above), so only auto-detect still re-walks the index array
  if (indexing_mode < 0 && !out->index.empty() && min_feat > 0) {
    for (IndexType& e : out->index) --e;
    --out->max_index;  // min_feat > 0 keeps the decrement wrap-free
  }
}
}  // namespace

template <typename IndexType>
void LibSVMParser<IndexType>::ParseBlock(const char* begin, const char* end,
                                         RowBlockContainer<IndexType>* out) {
  if (this->simd_tier_ != kSimdScalar) {
    ParseBlockSimd(begin, end, out);
  } else {
    ParseBlockScalar(begin, end, out);
  }
}

template <typename IndexType>
void LibSVMParser<IndexType>::ParseBlockScalar(
    const char* begin, const char* end, RowBlockContainer<IndexType>* out) {
  out->Clear();
  ParseLibSVMBlockImpl<false>(begin, end, indexing_mode_, out);
}

// The SIMD lane: stage 1 runs the tier kernels over the chunk for the
// reserve hints (every valued feature owns one ':', every row one EOL),
// stage 2 is the SAME tokenizer instantiated with the fused SWAR field
// decoders (see simd_scan.h for why fused decode beats per-token tape
// walking on real corpora).
template <typename IndexType>
void LibSVMParser<IndexType>::ParseBlockSimd(
    const char* begin, const char* end, RowBlockContainer<IndexType>* out) {
  out->Clear();
  size_t n_sep = 0, n_eol = 0;
  CountSepEol(begin, end, ':',
              static_cast<SimdTier>(this->simd_tier_), &n_sep, &n_eol);
  out->index.reserve(n_sep);
  out->value.reserve(n_sep);
  out->label.reserve(n_eol + 1);
  out->offset.reserve(n_eol + 2);
  ParseLibSVMBlockImpl<true>(begin, end, indexing_mode_, out);
}

// --------------------------------------------------------------------------
template <typename IndexType>
CSVParser<IndexType>::CSVParser(InputSplit* source,
                                const std::map<std::string, std::string>& args,
                                int nthread)
    : TextParserBase<IndexType>(source, nthread) {
  CSVParserParam param;
  param.Init(args, ParamInitOption::kAllowUnknown);
  DCT_CHECK_EQ(param.format, std::string("csv")) << "format mismatch";
  label_column_ = param.label_column;
  weight_column_ = param.weight_column;
  DCT_CHECK_EQ(param.delimiter.size(), size_t(1))
      << "delimiter must be a single char";
  delimiter_ = param.delimiter[0];
  // the single-pass cell parse relies on the delimiter terminating a
  // number scan; a numeric-looking delimiter would let values run across
  // cells (reference csv_parser.h has the same implicit assumption via
  // strtof stopping at it)
  DCT_CHECK(!IsDigitChar(delimiter_) && delimiter_ != '.' &&
            delimiter_ != '-' && delimiter_ != '+' && delimiter_ != 'e' &&
            delimiter_ != 'E')
      << "csv delimiter '" << delimiter_
      << "' is a numeric character; values could not be delimited";
  DCT_CHECK(label_column_ != weight_column_ || label_column_ < 0)
      << "label and weight columns must differ";
  // typed values (reference csv_parser.h:24-147 DType float32/int32/int64);
  // the enum mapping (string -> code) happens in CSVParserParam::Init
  value_dtype_ = param.dtype;
}

namespace {
// value-cell sink per csv dtype: parses a number at vp into `values` and
// advances *out past it (the caller then skips any cell residue).
// kFused selects the fused numeric primitives (simd_scan.h) — identical
// values and consumption, fewer per-character loops.
template <bool kFused, typename VT>
bool ParseCellF(const char* vp, const char* end, const char** out,
                std::vector<VT>* values) {
  VT v;
  const char* after;
  if (!ParseNumF<kFused, VT>(vp, end, &after, &v)) return false;
  *out = after;
  values->push_back(v);
  return true;
}

// reference src/data/csv_parser.h:76-147. Single-pass tokenizer: cells
// are parsed where the cursor stands and EOL characters double as cell
// terminators. Semantics (missing values keep their column index,
// label/weight columns, blank-only lines emit empty rows, delimiter
// presence check) match the line-oriented form; tests pin them, and
// tests/test_parse_simd.py pins kFused=true == kFused=false.
template <bool kFused, typename IndexType>
void ParseCSVBlockImpl(const char* begin, const char* end, int label_column,
                       int weight_column, char delimiter, int value_dtype,
                       RowBlockContainer<IndexType>* out) {
  out->value_dtype = value_dtype;
  const char* p = SkipUTF8BOM(begin, end);
  while (p != end) {
    if (IsEolChar(*p)) {  // empty line (also the LF of a CRLF pair)
      ++p;
      continue;
    }
    p = SkipUTF8BOM(p, end);
    int column = 0;
    IndexType idx = 0;
    float label = 0.0f;
    float weight = std::numeric_limits<float>::quiet_NaN();
    bool any_delim = false;
    bool line_done = false;
    while (!line_done) {
      // leading blanks of the cell — but never across a blank DELIMITER
      // (tab-separated files: '\t' both blank and delimiter)
      while (p != end && IsBlankChar(*p) && *p != delimiter) ++p;
      if (column == label_column || column == weight_column) {
        float v;
        const char* after;
        if (ParseNumF<kFused, float>(p, end, &after, &v)) {
          (column == label_column ? label : weight) = v;
          p = after;
        }
      } else {
        bool parsed =
            value_dtype == 0
                ? ParseCellF<kFused>(p, end, &p, &out->value)
            : value_dtype == 1
                ? ParseCellF<kFused>(p, end, &p, &out->value_i32)
                : ParseCellF<kFused>(p, end, &p, &out->value_i64);
        if (parsed) {
          out->index.push_back(idx);
          // inline max tracking replaces the old UpdateMax pass
          out->max_index = std::max<uint64_t>(out->max_index, idx);
          ++idx;
        } else {
          ++idx;  // missing value: skip but keep the column index
        }
      }
      // cell residue (trailing garbage/blanks) up to the next delimiter
      // or end of line
      while (p != end && *p != delimiter && !IsEolChar(*p)) ++p;
      ++column;
      if (p == end) {
        line_done = true;  // NOEOL final line
      } else if (*p == delimiter) {
        any_delim = true;
        ++p;
      } else {
        ++p;  // consume the EOL character
        line_done = true;
      }
    }
    DCT_CHECK(any_delim || column <= 1 || idx > 0)
        << "delimiter '" << delimiter << "' not found in csv line";
    out->label.push_back(label);
    if (!std::isnan(weight)) out->weight.push_back(weight);
    out->offset.push_back(out->index.size());
  }
  DCT_CHECK_EQ(out->label.size() + 1, out->offset.size());
  DCT_CHECK(out->weight.empty() || out->weight.size() == out->label.size())
      << "weight_column missing on some csv rows";
}
}  // namespace

template <typename IndexType>
void CSVParser<IndexType>::ParseBlock(const char* begin, const char* end,
                                      RowBlockContainer<IndexType>* out) {
  if (this->simd_tier_ != kSimdScalar) {
    ParseBlockSimd(begin, end, out);
  } else {
    ParseBlockScalar(begin, end, out);
  }
}

template <typename IndexType>
void CSVParser<IndexType>::ParseBlockScalar(
    const char* begin, const char* end, RowBlockContainer<IndexType>* out) {
  out->Clear();
  ParseCSVBlockImpl<false>(begin, end, label_column_, weight_column_,
                           delimiter_, value_dtype_, out);
}

template <typename IndexType>
void CSVParser<IndexType>::ParseBlockSimd(
    const char* begin, const char* end, RowBlockContainer<IndexType>* out) {
  out->Clear();
  size_t n_sep = 0, n_eol = 0;
  CountSepEol(begin, end, delimiter_,
              static_cast<SimdTier>(this->simd_tier_), &n_sep, &n_eol);
  // cells <= delimiters + rows; every row owns one EOL (+1 NOEOL tail)
  const size_t cells_hint = n_sep + n_eol + 1;
  out->index.reserve(cells_hint);
  if (value_dtype_ == 1) {
    out->value_i32.reserve(cells_hint);
  } else if (value_dtype_ == 2) {
    out->value_i64.reserve(cells_hint);
  } else {
    out->value.reserve(cells_hint);
  }
  out->label.reserve(n_eol + 1);
  out->offset.reserve(n_eol + 2);
  ParseCSVBlockImpl<true>(begin, end, label_column_, weight_column_,
                          delimiter_, value_dtype_, out);
}

// --------------------------------------------------------------------------
template <typename IndexType>
LibFMParser<IndexType>::LibFMParser(
    InputSplit* source, const std::map<std::string, std::string>& args,
    int nthread)
    : TextParserBase<IndexType>(source, nthread) {
  LibFMParserParam param;
  param.Init(args, ParamInitOption::kAllowUnknown);
  DCT_CHECK_EQ(param.format, std::string("libfm")) << "format mismatch";
  indexing_mode_ = param.indexing_mode;
}

namespace {
// One libfm row starting at p (a non-blank, non-EOL char); same
// fused/scalar contract as ParseLibSVMRow above. `dec`/`dec_field` hoist
// the forced 1-based decrement into the decode path; mins track RAW ids
// for the auto heuristic.
template <typename IndexType, bool kFused>
const char* ParseLibFMRow(const char* p, const char* end,
                          RowBlockContainer<IndexType>* out,
                          uint32_t* min_field, IndexType* min_feat,
                          IndexType dec) {
  const uint32_t dec_field = static_cast<uint32_t>(dec);
  if (*p == '#') return SkipToEol(p, end);  // comment-only line
  float label;
  if (!ParseNumF<kFused, float>(p, end, &p, &label)) {
    return SkipToEol(p, end);  // garbage line: discard (ParsePair contract)
  }
  if (p != end && *p == ':') {
    float weight;
    const char* wp;
    if (ParseNumF<kFused, float>(p + 1, end, &wp, &weight)) {
      out->weight.push_back(weight);
      p = wp;
    }
  }
  out->label.push_back(label);
  // field:feature[:value] triples until end of line
  while (true) {
    while (p != end && IsBlankChar(*p)) ++p;
    if (p == end) break;
    const char c = *p;
    if (IsEolChar(c)) {
      ++p;
      break;
    }
    if (c == '#') {
      p = SkipToEol(p, end);
      break;
    }
    uint32_t field;
    IndexType feat;
    float value;
    const char* after;
    // a triple shares the pair grammar; ParseTriple's rr<=1 cases
    // (bare number, no second ':') keep the line-oriented semantics
    int rr = ParseTripleF<kFused, uint32_t, IndexType, float>(
        p, end, &after, &field, &feat, &value);
    if (rr == 0) {
      p = SkipToEol(p, end);  // non-numeric token: discard the line
      break;
    }
    p = after;
    if (rr == 1) continue;  // bare number token: skipped (reference)
    const uint32_t wfield = field - dec_field;
    const IndexType wfeat = static_cast<IndexType>(feat - dec);
    out->field.push_back(wfield);
    out->index.push_back(wfeat);
    // inline max tracking replaces the old post-parse UpdateMax pass
    out->max_field = std::max(out->max_field, wfield);
    out->max_index = std::max<uint64_t>(out->max_index, wfeat);
    *min_field = std::min(*min_field, field);
    *min_feat = std::min(*min_feat, feat);
    if (rr == 3) out->value.push_back(value);
  }
  out->offset.push_back(out->index.size());
  return p;
}

// reference src/data/libfm_parser.h:67-144. Single-pass tokenizer (same
// structure as the libsvm impl: rows and `field:feature[:value]` triples
// recognized in one scan, newlines terminate the token loop).
template <bool kFused, typename IndexType>
void ParseLibFMBlockImpl(const char* begin, const char* end,
                         int indexing_mode,
                         RowBlockContainer<IndexType>* out) {
  uint32_t min_field = std::numeric_limits<uint32_t>::max();
  IndexType min_feat = std::numeric_limits<IndexType>::max();
  const IndexType dec = indexing_mode > 0 ? 1 : 0;
  const char* p = SkipUTF8BOM(begin, end);
  while (p != end) {
    while (p != end && (IsBlankChar(*p) || IsEolChar(*p))) ++p;
    if (p == end) break;
    p = ParseLibFMRow<IndexType, kFused>(p, end, out, &min_field,
                                         &min_feat, dec);
  }
  DCT_CHECK_EQ(out->field.size(), out->index.size());
  DCT_CHECK_EQ(out->label.size() + 1, out->offset.size());
  // 1-based auto detection requires BOTH field and feature ids to exceed 0
  // (reference libfm_parser.h:130-143); forced >0 mode decrements at
  // decode time (dec above)
  if (indexing_mode < 0 && !out->index.empty() && min_feat > 0 &&
      !out->field.empty() && min_field > 0) {
    for (IndexType& e : out->index) --e;
    for (uint32_t& e : out->field) --e;
    --out->max_index;  // both mins > 0 keep the decrements wrap-free
    --out->max_field;
  }
}
}  // namespace

template <typename IndexType>
void LibFMParser<IndexType>::ParseBlock(const char* begin, const char* end,
                                        RowBlockContainer<IndexType>* out) {
  if (this->simd_tier_ != kSimdScalar) {
    ParseBlockSimd(begin, end, out);
  } else {
    ParseBlockScalar(begin, end, out);
  }
}

template <typename IndexType>
void LibFMParser<IndexType>::ParseBlockScalar(
    const char* begin, const char* end, RowBlockContainer<IndexType>* out) {
  out->Clear();
  ParseLibFMBlockImpl<false>(begin, end, indexing_mode_, out);
}

template <typename IndexType>
void LibFMParser<IndexType>::ParseBlockSimd(
    const char* begin, const char* end, RowBlockContainer<IndexType>* out) {
  out->Clear();
  size_t n_sep = 0, n_eol = 0;
  CountSepEol(begin, end, ':',
              static_cast<SimdTier>(this->simd_tier_), &n_sep, &n_eol);
  // every full triple owns two ':'
  const size_t nnz_hint = n_sep / 2 + 1;
  out->index.reserve(nnz_hint);
  out->field.reserve(nnz_hint);
  out->value.reserve(nnz_hint);
  out->label.reserve(n_eol + 1);
  out->offset.reserve(n_eol + 2);
  ParseLibFMBlockImpl<true>(begin, end, indexing_mode_, out);
}

// --------------------------------------------------------------------------
template <typename IndexType>
CriteoParser<IndexType>::CriteoParser(
    InputSplit* source, const std::map<std::string, std::string>& args,
    int nthread)
    : TextParserBase<IndexType>(source, nthread) {
  CriteoParserParam param;
  param.Init(args, ParamInitOption::kAllowUnknown);
  DCT_CHECK_EQ(param.format, std::string("criteo")) << "format mismatch";
  DCT_CHECK(sizeof(IndexType) == 8 || param.hash_bits <= 31)
      << "criteo: hash_bits=" << param.hash_bits
      << " does not fit 32-bit indices (1..31; ids are int32 on the device)";
  hash_bits_ = param.hash_bits;
}

namespace {
struct CriteoTelemetry {
  telemetry::Counter* cells;
  telemetry::Counter* missing;
};

const CriteoTelemetry& CriteoTel() {
  static const CriteoTelemetry t = {
      telemetry::GetCounter("parse_cells_total", {{"format", "criteo"}}),
      telemetry::GetCounter("parse_cells_missing_total",
                            {{"format", "criteo"}}),
  };
  return t;
}

// A line the format refuses: never a short or a skipped row.
[[noreturn]] void CriteoRefuse(const char* begin, const char* line,
                               const char* end, const std::string& why) {
  const char* eol = line;
  while (eol != end && !IsEolChar(*eol)) ++eol;
  const size_t shown = std::min<size_t>(static_cast<size_t>(eol - line), 60);
  throw Error("criteo: the line at byte offset " +
              std::to_string(line - begin) + " of its block " + why +
              " (a line is the label, 13 integer and 26 categorical cells, "
              "tab-separated): '" + std::string(line, shown) +
              (shown < static_cast<size_t>(eol - line) ? "...'" : "'"));
}

// Single-pass tokenizer: the label where the cursor stands, then 39 cells
// each led by its tab; a cell's bytes are hashed as they stand (no trim, no
// number parse). Blank lines are skipped, as by every text parser here
// (the LF of a CRLF pair is one).
template <bool kFused, typename IndexType>
void ParseCriteoBlockImpl(const char* begin, const char* end, int hash_bits,
                          RowBlockContainer<IndexType>* out) {
  const char* p = SkipUTF8BOM(begin, end);
  while (p != end) {
    if (IsEolChar(*p)) {
      ++p;
      continue;
    }
    const char* line = p;
    float label;
    const char* after;
    if (*p == '\t' || !ParseNumF<kFused, float>(p, end, &after, &label) ||
        (after != end && *after != '\t' && !IsEolChar(*after))) {
      CriteoRefuse(begin, line, end, "has a label that is not a number");
    }
    p = after;
    int column = 0;
    for (; column < kCriteoColumns && p != end && *p == '\t'; ++column) {
      const char* cell = ++p;
      while (p != end && *p != '\t' && !IsEolChar(*p)) ++p;
      if (p != cell) {
        const IndexType id = static_cast<IndexType>(CriteoFold(
            CriteoHash64(static_cast<uint32_t>(column), cell,
                         static_cast<size_t>(p - cell)),
            hash_bits));
        out->index.push_back(id);
        out->max_index = std::max<uint64_t>(out->max_index, id);
      }
    }
    if (column != kCriteoColumns || (p != end && !IsEolChar(*p))) {
      size_t cells = 1;
      for (const char* q = line; q != end && !IsEolChar(*q); ++q) {
        cells += *q == '\t';
      }
      CriteoRefuse(begin, line, end,
                   "has " + std::to_string(cells) + " cells, not " +
                       std::to_string(kCriteoCells));
    }
    if (p != end) ++p;  // the EOL character; a last line may lack it
    out->label.push_back(label);
    out->offset.push_back(out->index.size());
  }
  DCT_CHECK_EQ(out->label.size() + 1, out->offset.size());
}
}  // namespace

template <typename IndexType>
void CriteoParser<IndexType>::ParseBlock(const char* begin, const char* end,
                                         RowBlockContainer<IndexType>* out) {
  out->Clear();
  if (this->simd_tier_ != kSimdScalar) {
    size_t n_tab = 0, n_eol = 0;
    CountSepEol(begin, end, '\t', static_cast<SimdTier>(this->simd_tier_),
                &n_tab, &n_eol);
    out->index.reserve(n_tab);  // every feature cell owns the tab before it
    out->label.reserve(n_eol + 1);
    out->offset.reserve(n_eol + 2);
    ParseCriteoBlockImpl<true>(begin, end, hash_bits_, out);
  } else {
    ParseCriteoBlockImpl<false>(begin, end, hash_bits_, out);
  }
  // per block, from the counts the block already holds
  const uint64_t cells = uint64_t(out->label.size()) * kCriteoColumns;
  CriteoTel().cells->Add(cells);
  CriteoTel().missing->Add(cells - out->index.size());
}

// --------------------------------------------------------------------------
// rec: binary RecordIO-framed row blocks (parser.h RecParser). Each record
// is [magic 'DRB1' u32le][flags u32le: bit0 = uint64 indices] followed by
// the rowblock.h wire format; deserialization is bulk memcpy.
namespace {
constexpr uint32_t kRecRowBlockMagic = 0x44524231;  // 'DRB1' (LE word '1BRD')
}  // namespace

template <typename IndexType>
RecParser<IndexType>::RecParser(InputSplit* source,
                                const std::map<std::string, std::string>& args,
                                int nthread)
    : TextParserBase<IndexType>(source, nthread) {
  (void)args;
}

template <typename IndexType>
const char* RecParser<IndexType>::FindUnitBoundary(const char* base,
                                                   const char* hint,
                                                   const char* end) {
  return FindRecordHead(base, hint, end);
}

template <typename IndexType>
void RecParser<IndexType>::ParseBlock(const char* begin, const char* end,
                                      RowBlockContainer<IndexType>* out) {
  out->Clear();
  RecordIOChunkReader reader(begin, end, 0, 1);
  RecordIOChunkReader::Blob rec;
  while (reader.NextRecord(&rec)) {
    DCT_CHECK(rec.size >= 8) << "rec record too short for a row-block header";
    const char* p = static_cast<const char*>(rec.dptr);
    DCT_CHECK(recordio::LoadWordLE(p) == kRecRowBlockMagic)
        << "not a row-block record (bad payload magic); rec files are "
           "written by rows_to_recordio (dmlc_core_tpu/io/convert.py)";
    const bool is64 = (recordio::LoadWordLE(p + 4) & 1u) != 0;
    DCT_CHECK(is64 == (sizeof(IndexType) == 8))
        << "rec index width mismatch: payload has "
        << (is64 ? "uint64" : "uint32") << " feature ids but the parser "
        << "was created with index64=" << (sizeof(IndexType) == 8);
    MemoryFixedSizeStream ms(const_cast<char*>(p) + 8, rec.size - 8);
    // append-deserialize straight into the output container: one memcpy
    // per array from the mapped chunk, no intermediate container
    DCT_CHECK(out->LoadAppend(&ms)) << "truncated row-block record";
  }
}

// --------------------------------------------------------------------------
namespace {
// "DCTRBL2" — bumped when the RowBlockContainer wire format changes (v2
// added typed csv value arrays); a stale v1 cache fails the magic check and
// is rebuilt transparently
constexpr uint64_t kRowCacheMagic = 0x44435452424c32;

uint64_t FingerprintHash64(const std::string& s) {
  uint64_t h = 1469598103934665603ull;  // FNV-1a
  for (unsigned char c : s) {
    h ^= c;
    h *= 1099511628211ull;
  }
  return h;
}
}  // namespace

template <typename IndexType>
DiskCacheParser<IndexType>::DiskCacheParser(Parser<IndexType>* base,
                                            const std::string& cache_file,
                                            const std::string& fingerprint)
    : base_(base),
      cache_file_(cache_file),
      fingerprint_(FingerprintHash64(fingerprint)) {
  replaying_ = TryOpenCache();
}

template <typename IndexType>
DiskCacheParser<IndexType>::~DiskCacheParser() {
  if (replay_cell_ != nullptr) replay_pipe_.Recycle(&replay_cell_);
  replay_pipe_.Shutdown();
}

template <typename IndexType>
bool DiskCacheParser<IndexType>::TryOpenCache() {
  std::unique_ptr<SeekStream> probe(
      SeekStream::CreateForRead(cache_file_, /*allow_null=*/true));
  if (probe == nullptr) return false;
  uint64_t magic = 0, fp = 0;
  if (probe->Read(&magic, 8) != 8 || probe->Read(&fp, 8) != 8) {
    return false;
  }
  if (!serial::NativeIsLE()) {
    magic = serial::ByteSwap(magic);
    fp = serial::ByteSwap(fp);
  }
  if (magic != kRowCacheMagic || fp != fingerprint_) {
    std::remove(cache_file_.c_str());  // stale/foreign cache: rebuild
    return false;
  }
  reader_ = std::move(probe);
  return true;
}

template <typename IndexType>
void DiskCacheParser<IndexType>::StartReplayPipeline() {
  if (replay_started_) return;
  replay_pipe_.Init(
      [this](RowBlockContainer<IndexType>** cell) {
        if (*cell == nullptr) *cell = new RowBlockContainer<IndexType>();
        return (*cell)->Load(reader_.get());
      },
      [this] {
        // rewind past the header
        reader_->Seek(16);
      });
  replay_started_ = true;
}

template <typename IndexType>
void DiskCacheParser<IndexType>::FinalizeCache() {
  // publish ONLY a complete pass (a partial .tmp would silently truncate
  // the dataset forever)
  if (writer_ == nullptr) return;
  writer_.reset();
  std::string tmp = cache_file_ + ".tmp";
  if (!write_complete_) {
    std::remove(tmp.c_str());
    return;
  }
  // injectable publish (fs_fault.h): a failed/torn rename surfaces as a
  // structured error with errno instead of a bare check string. The
  // DESTINATION is removed first: a torn half-copy keeps the magic+
  // fingerprint probe valid, so leaving it would wedge every later
  // epoch/process mid-replay — deleting it makes the failure a clean
  // first-pass re-parse instead (the shard cache gets this from
  // manifest-last publishing; this single-file format has no manifest).
  if (fsio::Rename(tmp.c_str(), cache_file_.c_str()) != 0) {
    const int err = errno != 0 ? errno : EIO;
    std::remove(cache_file_.c_str());
    std::remove(tmp.c_str());
    throw fsio::FsError(fsio::FsOp::kRename, cache_file_, err);
  }
}

template <typename IndexType>
void DiskCacheParser<IndexType>::EnsureWriter() {
  if (writer_ != nullptr) return;
  writer_.reset(Stream::Create(cache_file_ + ".tmp", "w"));
  uint64_t magic = kRowCacheMagic, fp = fingerprint_;
  if (!serial::NativeIsLE()) {
    magic = serial::ByteSwap(magic);
    fp = serial::ByteSwap(fp);
  }
  writer_->Write(&magic, 8);
  writer_->Write(&fp, 8);
}

template <typename IndexType>
const RowBlockContainer<IndexType>* DiskCacheParser<IndexType>::NextBlock() {
  if (replaying_) {
    StartReplayPipeline();
    if (replay_cell_ != nullptr) replay_pipe_.Recycle(&replay_cell_);
    if (!replay_pipe_.Next(&replay_cell_)) return nullptr;
    return replay_cell_;
  }
  const RowBlockContainer<IndexType>* b = base_->NextBlock();
  if (b == nullptr) {
    write_complete_ = true;
    FinalizeCache();
    return nullptr;
  }
  EnsureWriter();
  b->Save(writer_.get());
  return b;
}

template <typename IndexType>
bool DiskCacheParser<IndexType>::NextBlockMove(
    RowBlockContainer<IndexType>* out) {
  if (replaying_) {
    StartReplayPipeline();
    if (replay_cell_ != nullptr) replay_pipe_.Recycle(&replay_cell_);
    if (!replay_pipe_.Next(&replay_cell_)) return false;
    // swap hand-off: the recycled replay cell keeps out's old capacity
    std::swap(*out, *replay_cell_);
    replay_cell_->Clear();
    return true;
  }
  // write-through epoch: move from base, then append to the cache
  if (!base_->NextBlockMove(out)) {
    write_complete_ = true;
    FinalizeCache();
    return false;
  }
  EnsureWriter();
  out->Save(writer_.get());
  return true;
}

template <typename IndexType>
void DiskCacheParser<IndexType>::BeforeFirst() {
  FinalizeCache();  // publishes only when the pass completed
  write_complete_ = false;
  if (replay_started_) {
    if (replay_cell_ != nullptr) replay_pipe_.Recycle(&replay_cell_);
    replay_pipe_.Shutdown();
    replay_started_ = false;
  }
  if (TryOpenCache()) {
    replaying_ = true;
  } else {
    replaying_ = false;
    base_->BeforeFirst();
  }
}

// --------------------------------------------------------------------------
// PipelinedParser: reader -> (chunk, slice) work queue -> worker pool ->
// ordered head-of-line reassembly. See parser.h for the stage diagram.
namespace {
// Default in-flight chunk bound: enough outstanding slices to ride over a
// straggler slice plus one chunk being read and one being consumed, capped
// so host RSS stays bounded (each task holds ~chunk bytes raw + ~chunk
// bytes parsed).
size_t DefaultChunksInFlight(int workers) {
  return static_cast<size_t>(
      std::max(3, std::min(workers + 2, 10)));
}
}  // namespace

template <typename IndexType>
PipelinedParser<IndexType>::PipelinedParser(TextParserBase<IndexType>* base,
                                            int chunks_in_flight)
    : base_(base),
      capacity_(chunks_in_flight > 0
                    ? static_cast<size_t>(chunks_in_flight)
                    : DefaultChunksInFlight(base->num_threads())),
      nworker_(base->num_threads()) {
  if (capacity_ < 2) capacity_ = 2;  // 1 would re-serialize read vs parse
}

template <typename IndexType>
PipelinedParser<IndexType>::~PipelinedParser() {
  StopThreads();
  if (current_ != nullptr) delete current_;
  // lock-ok: StopThreads joined every stage thread; dtor is sole owner
  for (ChunkTask* t : free_) delete t;
}

template <typename IndexType>
void PipelinedParser<IndexType>::Start() {
  if (started_) return;
  // lock-ok: no stage thread exists yet (started_ false, all joined)
  stop_ = false;
  eof_ = false;  // lock-ok: pre-spawn init, single-threaded
  reader_ = std::thread([this] { ReaderLoop(); });
  workers_.reserve(nworker_);
  for (int i = 0; i < nworker_; ++i) {
    workers_.emplace_back([this] { WorkerLoop(); });
  }
  started_ = true;
}

template <typename IndexType>
void PipelinedParser<IndexType>::StopThreads() {
  if (!started_) return;
  {
    std::lock_guard<std::mutex> lk(mu_);
    stop_ = true;
  }
  space_cv_.notify_all();
  work_cv_.notify_all();
  done_cv_.notify_all();
  reader_.join();
  for (auto& w : workers_) w.join();
  workers_.clear();
  started_ = false;
  stop_ = false;  // lock-ok: every stage thread joined above
  // reclaim in-flight tasks (buffers kept for the next epoch); claim_ holds
  // aliases of inflight_ entries, never owned tasks.
  // lock-ok: single-threaded after the joins above
  for (ChunkTask* t : inflight_) free_.push_back(t);
  inflight_.clear();  // lock-ok: single-threaded after the joins above
  claim_.clear();  // lock-ok: single-threaded after the joins above
  // an unconsumed reader error dies with the round it belongs to: the
  // consumer either already rethrew it (failed_ set, restart forbidden) or
  // abandoned the epoch — a stale pointer here would poison the NEXT
  // epoch's first NextBlock
  reader_error_ = nullptr;  // lock-ok: single-threaded after the joins
}

template <typename IndexType>
void PipelinedParser<IndexType>::ReaderLoop() {
  try {
    for (;;) {
      ChunkTask* t = nullptr;
      {
        std::unique_lock<std::mutex> lk(mu_);
        if (inflight_.size() >= capacity_) {
          reader_waits_.fetch_add(1, std::memory_order_relaxed);
          PipeTel().reader_waits->Add(1);
          space_cv_.wait(lk, [&] {
            return stop_ || inflight_.size() < capacity_;
          });
        }
        if (stop_) return;
        if (!free_.empty()) {
          t = free_.back();
          free_.pop_back();
        }
      }
      if (t == nullptr) t = new ChunkTask();
      bool more;
      try {
        {
          telemetry::ScopedTimerUs fill_span(PipeTel().fill_us);
          telemetry::TraceSpan trace("parse.fill");
          more = base_->ReadChunk(&t->data);
          trace.set_arg(t->data.size());
        }
        if (more) {
          const int nslice = base_->SlicesFor(t->data.size());
          t->nslice = nslice;
          // lock-ok: task not yet published to inflight_/claim_ — the
          // reader is its sole owner until the push under mu_ below
          t->next_slice = 0;
          t->remaining = nslice;  // lock-ok: reader-owned until publish
          t->next_serve = 0;
          // keep blocks at their high-water count so a small final chunk
          // does not free the recycled capacity of unused slices
          if (static_cast<int>(t->blocks.size()) < nslice) {
            t->blocks.resize(nslice);
          }
          t->errors.assign(nslice, nullptr);
          telemetry::ScopedTimerUs scan_span(PipeTel().scan_us);
          telemetry::TraceSpan trace("parse.scan");
          base_->TileCuts(t->data.data(), t->data.data() + t->data.size(),
                          nslice, &t->cuts);
        }
      } catch (...) {
        // reclaim the in-flight task (read OR slice-prep may have thrown)
        // so the destructor's free-list sweep still owns it
        std::lock_guard<std::mutex> lk(mu_);
        free_.push_back(t);
        throw;
      }
      if (!more) {
        std::lock_guard<std::mutex> lk(mu_);
        free_.push_back(t);
        eof_ = true;
        done_cv_.notify_all();
        return;
      }
      {
        std::lock_guard<std::mutex> lk(mu_);
        if (stop_) {
          free_.push_back(t);
          return;
        }
        inflight_.push_back(t);
        claim_.push_back(t);
        chunks_read_.fetch_add(1, std::memory_order_relaxed);
        PipeTel().chunks_read->Add(1);
        inflight_sum_.fetch_add(inflight_.size(),
                                std::memory_order_relaxed);
        // single writer (this thread, under mu_); atomic only for the
        // lock-free stats read
        if (inflight_.size() >
            inflight_peak_.load(std::memory_order_relaxed)) {
          inflight_peak_.store(inflight_.size(), std::memory_order_relaxed);
        }
      }
      work_cv_.notify_all();
    }
  } catch (...) {
    std::lock_guard<std::mutex> lk(mu_);
    reader_error_ = std::current_exception();
    done_cv_.notify_all();
  }
}

template <typename IndexType>
void PipelinedParser<IndexType>::WorkerLoop() {
  for (;;) {
    ChunkTask* t;
    int slice;
    {
      std::unique_lock<std::mutex> lk(mu_);
      if (claim_.empty() && !stop_) {
        worker_waits_.fetch_add(1, std::memory_order_relaxed);
        PipeTel().worker_waits->Add(1);
        work_cv_.wait(lk, [&] { return stop_ || !claim_.empty(); });
      }
      if (stop_) return;
      // oldest chunk first: finishing the head chunk unblocks the ordered
      // consumer soonest, and chunks complete roughly in input order
      t = claim_.front();
      slice = t->next_slice++;
      if (t->next_slice == t->nslice) claim_.pop_front();
    }
    try {
      telemetry::ScopedTimerUs parse_span(PipeTel().parse_us);
      telemetry::TraceSpan trace("parse.slice");
      RowBlockContainer<IndexType>* out = &t->blocks[slice];
      base_->ParseBlock(t->cuts[slice], t->cuts[slice + 1], out);
      ValidateBlock(*out);
      trace.set_arg(out->Size());
    } catch (...) {
      t->errors[slice] = std::current_exception();
    }
    {
      std::lock_guard<std::mutex> lk(mu_);
      if (--t->remaining == 0 && !inflight_.empty() &&
          inflight_.front() == t) {
        done_cv_.notify_all();
      }
    }
  }
}

template <typename IndexType>
void PipelinedParser<IndexType>::RecycleCurrent() {
  {
    std::lock_guard<std::mutex> lk(mu_);
    free_.push_back(current_);
    current_ = nullptr;
  }
  space_cv_.notify_one();
}

template <typename IndexType>
RowBlockContainer<IndexType>* PipelinedParser<IndexType>::NextMutable() {
  if (failed_) {
    throw Error("parse pipeline is in a failed state after an earlier error");
  }
  Start();
  while (true) {
    if (current_ != nullptr) {
      while (current_->next_serve < static_cast<size_t>(current_->nslice)) {
        const size_t i = current_->next_serve++;
        if (current_->errors[i] != nullptr) {
          // input-order rethrow: everything before this slice was already
          // served, matching where a serial parse would have died
          std::exception_ptr e = current_->errors[i];
          failed_ = true;
          StopThreads();
          std::rethrow_exception(e);
        }
        RowBlockContainer<IndexType>* b = &current_->blocks[i];
        if (b->Size() != 0) {
          blocks_delivered_.fetch_add(1, std::memory_order_relaxed);
          PipeTel().blocks_delivered->Add(1);
          return b;
        }
      }
      RecycleCurrent();
    }
    {
      std::unique_lock<std::mutex> lk(mu_);
      bool waited = false;
      const uint64_t wait_from =
          telemetry::Enabled() ? telemetry::NowUs() : 0;
      done_cv_.wait(lk, [&] {
        if (stop_) return true;
        if (!inflight_.empty()) {
          if (inflight_.front()->remaining == 0) return true;
          waited = true;
          return false;
        }
        if (eof_ || reader_error_ != nullptr) return true;
        waited = true;
        return false;
      });
      if (waited) {
        consumer_waits_.fetch_add(1, std::memory_order_relaxed);
        PipeTel().consumer_waits->Add(1);
        if (wait_from != 0) {
          const uint64_t waited_us = telemetry::NowUs() - wait_from;
          PipeTel().reassemble_wait_us->Observe(waited_us);
          telemetry::EmitSpan("parse.wait", wait_from, waited_us);
        }
      }
      if (!inflight_.empty() && inflight_.front()->remaining == 0) {
        current_ = inflight_.front();
        inflight_.pop_front();
      } else if (reader_error_ != nullptr) {
        // all chunks admitted before the failure were drained above — the
        // error surfaces exactly where the serial read would have died
        std::exception_ptr e = reader_error_;
        lk.unlock();
        failed_ = true;
        StopThreads();
        std::rethrow_exception(e);
      } else {
        return nullptr;  // eof (or stop)
      }
    }
    space_cv_.notify_one();  // popping the head freed an in-flight slot
  }
}

template <typename IndexType>
const RowBlockContainer<IndexType>* PipelinedParser<IndexType>::NextBlock() {
  return NextMutable();
}

template <typename IndexType>
bool PipelinedParser<IndexType>::NextBlockMove(
    RowBlockContainer<IndexType>* out) {
  RowBlockContainer<IndexType>* b = NextMutable();
  if (b == nullptr) return false;
  // swap hand-off: the recycled task slot keeps out's old buffer capacity
  std::swap(*out, *b);
  b->Clear();
  return true;
}

template <typename IndexType>
void PipelinedParser<IndexType>::BeforeFirst() {
  DCT_CHECK(!failed_)
      << "cannot restart a parse pipeline after a parse error";
  StopThreads();
  if (current_ != nullptr) {
    std::lock_guard<std::mutex> lk(mu_);
    free_.push_back(current_);
    current_ = nullptr;
  }
  eof_ = false;  // lock-ok: StopThreads joined every stage thread
  // the rewind reaches the split chain synchronously (shuffled splits
  // resample their permutation in BeforeFirst — see
  // PrefetchSplit::BeforeFirst for the same rule); threads respawn lazily
  // on the next NextBlock
  base_->BeforeFirst();
}

template <typename IndexType>
bool PipelinedParser<IndexType>::GetPipelineStats(
    ParsePipelineStats* out) const {
  out->chunks_read = chunks_read_.load(std::memory_order_relaxed);
  out->blocks_delivered = blocks_delivered_.load(std::memory_order_relaxed);
  out->reader_waits = reader_waits_.load(std::memory_order_relaxed);
  out->worker_waits = worker_waits_.load(std::memory_order_relaxed);
  out->consumer_waits = consumer_waits_.load(std::memory_order_relaxed);
  {
    std::lock_guard<std::mutex> lk(mu_);
    out->inflight_now = inflight_.size();
  }
  out->inflight_peak = inflight_peak_.load(std::memory_order_relaxed);
  out->inflight_sum = inflight_sum_.load(std::memory_order_relaxed);
  out->capacity = capacity_;
  out->workers = static_cast<uint64_t>(nworker_);
  out->simd_tier = static_cast<uint64_t>(base_->simd_tier());
  return true;
}

// --------------------------------------------------------------------------
template <typename IndexType>
Parser<IndexType>* Parser<IndexType>::Create(const std::string& uri,
                                             unsigned part, unsigned npart,
                                             const std::string& format,
                                             int nthread, bool threaded,
                                             int chunks_in_flight,
                                             const std::string& cache_dir,
                                             const std::string& cache_mode) {
  URISpec spec(uri, part, npart);
  std::string fmt = format;
  if (fmt == "auto" || fmt.empty()) {
    auto it = spec.args.find("format");
    if (it != spec.args.end()) {
      fmt = it->second;
    } else if (spec.uri.size() >= 4 &&
               spec.uri.compare(spec.uri.size() - 4, 4, ".rec") == 0) {
      fmt = "rec";  // binary row-block files are self-identifying by suffix
    } else {
      fmt = "libsvm";
    }
  }
  std::map<std::string, std::string> args = spec.args;
  args["format"] = fmt;
  // `?io_*=` resilience overrides (retry.h) apply to DIRECT filesystem
  // opens (streams, OpenForRead); the parser lane strips the query into
  // parser args before the filesystem ever sees it, so the knobs would be
  // silent no-ops here — and URI sugar a lane does not implement must
  // error, not no-op (stream.h RejectUnknownArgs rationale). Configure
  // parser-lane resilience through the DMLC_IO_* / per-backend env.
  for (const auto& kv : args) {
    if (kv.first.compare(0, 3, "io_") == 0) {
      throw Error("the parser lane does not support per-open `?" + kv.first +
                  "=` resilience overrides (they reach only direct stream "
                  "opens); set DMLC_IO_* / per-backend env knobs instead");
    }
  }
  // NOTE: the chunk-level CachedSplit is NOT layered here — the row-block
  // DiskCacheParser below caches the *parsed* data, and double-caching
  // would write the dataset to disk twice (reference disk_row_iter caches
  // only row blocks too)
  ParserFactoryReg<IndexType>* entry =
      Registry<ParserFactoryReg<IndexType>>::Get()->Find(fmt);
  if (entry == nullptr) {
    throw Error("unknown data format: " + fmt);
  }
  // binary row-block files partition on RecordIO magics, text on newlines
  const char* split_type = fmt == "rec" ? "recordio" : "text";
  // epoch shuffling rides URI sugar like #cachefile does
  // (reference input_split_shuffle.h exposes the same knob through
  // InputSplit::Create): `?shuffle_parts=K[&shuffle_seed=S]` subdivides
  // this part into K byte ranges visited in a freshly shuffled order each
  // epoch — the coarse-grained training shuffle
  // strict numeric parse: garbage must error, not silently disable the
  // shuffle; negative/huge values must not wrap into multi-GB state
  auto parse_uarg = [&](const char* key, long lo, long hi,
                        long dflt) -> long {
    auto it = spec.args.find(key);
    if (it == spec.args.end()) return dflt;
    const char* s = it->second.c_str();
    char* end = nullptr;
    const long v = std::strtol(s, &end, 10);
    DCT_CHECK(end != s && *end == '\0' && v >= lo && v <= hi)
        << "bad URI arg " << key << "=" << it->second << " (expected an "
        << "integer in [" << lo << ", " << hi << "])";
    return v;
  };
  const unsigned shuffle_parts = static_cast<unsigned>(
      parse_uarg("shuffle_parts", 0, 65536, 0));
  const int shuffle_seed = static_cast<int>(
      parse_uarg("shuffle_seed", 0, 1 << 30, 0));
  // a row-block cache replays the first epoch's PARSED order, which
  // would freeze (and fingerprint-ignore) the shuffle — same rule as
  // the split layer's own guard
  DCT_CHECK(shuffle_parts == 0 || spec.cache_file.empty())
      << "shuffle_parts cannot combine with #cachefile: the cache "
         "replays epoch 1's order and would silently disable the "
         "per-epoch reshuffle";
  // shard cache (shard_cache.h, doc/caching.md): explicit args > URI
  // sugar (#cachefile=<dir>, ?cache=) > env (DMLC_DATA_CACHE_DIR,
  // DMLC_DATA_CACHE)
  ShardCacheConfig ccfg = ShardCacheConfig::Resolve(
      spec.cache_dir, GetArg(spec.args, "cache", ""), cache_dir, cache_mode);
  if (ccfg.enabled() && !spec.cache_file.empty()) {
    // same env-vs-explicit rule as the shuffle_parts guard below: an
    // explicit double opt-in is a contradiction and must error, but a
    // process-wide DMLC_DATA_CACHE_DIR must not break a job already
    // using the legacy cache — the legacy cache wins for this parser
    DCT_CHECK(!ccfg.explicit_opt_in)
        << "pass either the legacy `#<path>` row-block cache or the "
           "`#cachefile=<dir>` shard cache, not both";
    ccfg.dir.clear();
  }
  if (ccfg.enabled() && shuffle_parts != 0) {
    // the shard cache replays epoch 1's parsed order, like the legacy
    // cache above. An explicit opt-in conflicting with shuffling must
    // error (URI sugar never silently no-ops); a process-wide
    // DMLC_DATA_CACHE_DIR, though, must not break unrelated shuffled
    // lanes — shuffling wins and the cache stands down for this parser.
    DCT_CHECK(!ccfg.explicit_opt_in)
        << "?shuffle_parts= cannot combine with the shard cache: the "
           "cache replays epoch 1's order and would silently disable "
           "the per-epoch reshuffle";
    ccfg.dir.clear();
  }

  // `?index=1` (the conventional <uri>.idx) or `?index=<path>` switches a
  // rec stream onto the indexed_recordio splitter: record-count
  // partitioning plus EXACT per-epoch record shuffling with `?shuffle=1`
  // (reference indexed_recordio_split.h; index written by
  // build_recordio_index, dmlc_core_tpu/io/convert.py)
  std::string index_uri;
  {
    auto it = spec.args.find("index");
    if (it != spec.args.end()) {
      DCT_CHECK(fmt == "rec")
          << "?index= applies to the rec binary format only";
      DCT_CHECK(shuffle_parts == 0)
          << "?index= (exact record shuffle) and ?shuffle_parts= (coarse "
             "byte-range shuffle) are alternatives; pass one";
      DCT_CHECK(spec.cache_file.empty())
          << "?index= cannot combine with #cachefile (the cache replays "
             "epoch 1's order)";
      if (ccfg.enabled()) {
        // same env-vs-explicit rule as the shuffle_parts guard above
        DCT_CHECK(!ccfg.explicit_opt_in)
            << "?index= cannot combine with the shard cache (the cache "
               "replays epoch 1's order)";
        ccfg.dir.clear();
      }
      index_uri = it->second == "1" ? spec.uri + ".idx" : it->second;
    }
  }
  // pipeline depth knob rides the same URI sugar so batcher/device lanes
  // (which reach Create through their own C-ABI entry points) can tune it
  // without a signature change
  const int uri_cif = static_cast<int>(
      parse_uarg("chunks_in_flight", 0, 1024, 0));
  if (uri_cif > 0) chunks_in_flight = uri_cif;
  const bool rec_shuffle = parse_uarg("shuffle", 0, 1, 0) != 0;
  DCT_CHECK(!rec_shuffle || !index_uri.empty())
      << "?shuffle=1 needs ?index= (exact shuffling walks the record "
         "index); for index-less streams use ?shuffle_parts=";
  DCT_CHECK(spec.args.count("shuffle_batch") == 0 || !index_uri.empty())
      << "?shuffle_batch= applies to indexed streams only (pass ?index=); "
         "it would otherwise be silently ignored";
  const size_t shuffle_batch = static_cast<size_t>(
      parse_uarg("shuffle_batch", 1, 1 << 20, 256));

  // The pipelined parser's reader thread IS the prefetch stage, so layering
  // PrefetchSplit under it would only add a second copy of every chunk and
  // a thread hop (ReadChunk then fills task buffers directly through the
  // RecordChunkSource fast lane). The synchronous parser keeps the
  // prefetch wrapper — it is its only read/parse overlap.
  //
  // The base chain is a FACTORY so the shard-cache wrapper can defer it:
  // on a cache hit the whole epoch is an mmap replay and the source —
  // including any remote filesystem open — is never touched.
  const bool split_threaded = !threaded;
  const std::string base_uri = spec.uri;
  auto build_base = [base_uri, part, npart, split_type, index_uri,
                     rec_shuffle, shuffle_seed, shuffle_batch,
                     split_threaded, shuffle_parts, entry, args, nthread,
                     threaded, chunks_in_flight]() -> Parser<IndexType>* {
    InputSplit* split =
        index_uri.empty()
            ? InputSplit::Create(base_uri, part, npart, split_type, "",
                                 false, shuffle_seed, 256, false,
                                 split_threaded, "", shuffle_parts)
            : InputSplit::Create(base_uri, part, npart, "indexed_recordio",
                                 index_uri, rec_shuffle, shuffle_seed,
                                 shuffle_batch, false, split_threaded, "");
    // ownership of split passes into the parser's base immediately; a
    // throwing constructor body unwinds through the already-built base,
    // which frees it
    TextParserBase<IndexType>* parser = entry->body(split, args, nthread);
    return threaded ? static_cast<Parser<IndexType>*>(
                          new PipelinedParser<IndexType>(parser,
                                                         chunks_in_flight))
                    : parser;
  };
  if (ccfg.enabled()) {
    const std::string key = ShardCacheKeyText(
        spec.uri, part, npart, fmt, sizeof(IndexType) == 8, spec.args);
    return new ShardCacheParser<IndexType>(
        build_base, ccfg, ShardCacheStem(ccfg.dir, key, part, npart), key);
  }
  Parser<IndexType>* out = build_base();
  if (!spec.cache_file.empty()) {
    std::string fingerprint = spec.uri + "|" + std::to_string(part) + "|" +
                              std::to_string(npart) + "|" + fmt + "|dtype=" +
                              GetArg(spec.args, "dtype", "float32");
    out = new DiskCacheParser<IndexType>(out, spec.cache_file + ".rowblock",
                                         fingerprint);
  }
  return out;
}

// explicit instantiations (reference data.cc:224-256 registers
// {uint32, uint64} index types)
template class TextParserBase<uint32_t>;
template class TextParserBase<uint64_t>;
template class LibSVMParser<uint32_t>;
template class LibSVMParser<uint64_t>;
template class CSVParser<uint32_t>;
template class CSVParser<uint64_t>;
template class LibFMParser<uint32_t>;
template class LibFMParser<uint64_t>;
template class CriteoParser<uint32_t>;
template class CriteoParser<uint64_t>;
template class RecParser<uint32_t>;
template class RecParser<uint64_t>;
template class PipelinedParser<uint32_t>;
template class PipelinedParser<uint64_t>;
template class DiskCacheParser<uint32_t>;
template class DiskCacheParser<uint64_t>;
template class Parser<uint32_t>;
template class Parser<uint64_t>;

// -- format registrations (reference DMLC_REGISTER_DATA_PARSER instantiated
//    for both index widths, data.cc:224-256) ------------------------------
namespace {

template <typename IndexType>
void RegisterBuiltinParsers() {
  using Map = std::map<std::string, std::string>;
  auto* reg = Registry<ParserFactoryReg<IndexType>>::Get();
  reg->__REGISTER__("libsvm")
      .describe("sparse `label[:weight] [qid:n] index[:value]...` text rows")
      .add_arguments(LibSVMParserParam::__FIELDS__())
      .set_body([](InputSplit* s, const Map& args, int nthread) {
        return new LibSVMParser<IndexType>(s, args, nthread);
      });
  reg->__REGISTER__("csv")
      .describe("dense delimited rows; label/weight columns, typed values")
      .add_arguments(CSVParserParam::__FIELDS__())
      .set_body([](InputSplit* s, const Map& args, int nthread) {
        return new CSVParser<IndexType>(s, args, nthread);
      });
  reg->__REGISTER__("libfm")
      .describe("`label[:weight] field:feature:value...` factorization rows")
      .add_arguments(LibFMParserParam::__FIELDS__())
      .set_body([](InputSplit* s, const Map& args, int nthread) {
        return new LibFMParser<IndexType>(s, args, nthread);
      });
  reg->__REGISTER__("criteo")
      .describe("Criteo click logs: `label \\t 13 integer \\t 26 categorical` "
                "cells a line; every present cell hashed with its column "
                "to an id below 2^hash_bits, value 1, empty cells skipped")
      .add_arguments(CriteoParserParam::__FIELDS__())
      .set_body([](InputSplit* s, const Map& args, int nthread) {
        return new CriteoParser<IndexType>(s, args, nthread);
      });
  reg->__REGISTER__("rec")
      .describe("binary RecordIO-framed row blocks (rows_to_recordio)")
      .set_body([](InputSplit* s, const Map& args, int nthread) {
        return new RecParser<IndexType>(s, args, nthread);
      });
}

struct BuiltinParserRegistrar {
  BuiltinParserRegistrar() {
    RegisterBuiltinParsers<uint32_t>();
    RegisterBuiltinParsers<uint64_t>();
  }
} builtin_parser_registrar;

}  // namespace

}  // namespace dct
