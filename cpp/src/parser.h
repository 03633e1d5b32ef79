// Multithreaded text parsers producing CSR row blocks.
//
// Counterpart of reference src/data/parser.h (ParserImpl + ThreadedParser),
// src/data/text_parser.h (chunk → N worker threads, each parsing a
// line-aligned slice), and the format parsers libsvm_parser.h /
// csv_parser.h / libfm_parser.h. Parse semantics (comment/blank skipping,
// label[:weight], qid:, 0/1-based indexing heuristic, CSV missing values,
// NOEOL/BOM/CRLF handling) match the reference; the worker fan-out is
// restructured: slices are tiled forward to line heads and each worker fills
// its own RowBlockContainer which is exposed zero-copy through the C ABI.
#ifndef DCT_PARSER_H_
#define DCT_PARSER_H_

#include <atomic>
#include <condition_variable>
#include <deque>
#include <map>
#include <memory>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

#include "input_split.h"
#include "registry.h"
#include "rowblock.h"
#include "simd_scan.h"

namespace dct {

template <typename IndexType>
class TextParserBase;

// Occupancy/stall counters for the multi-chunk parse pipeline
// (PipelinedParser below), exposed through the C ABI
// (dct_parser_pipeline_stats) so a caller can see which stage
// binds: a starved reader (reader_waits low, consumer_waits high) means
// parse-bound; a full queue (reader_waits high) means consume-bound.
struct ParsePipelineStats {
  uint64_t chunks_read = 0;      // chunks admitted by the reader stage
  uint64_t blocks_delivered = 0; // row blocks handed to the consumer
  uint64_t reader_waits = 0;     // reader blocked on the in-flight bound
  uint64_t worker_waits = 0;     // worker slept with no claimable slice
  uint64_t consumer_waits = 0;   // consumer slept on the head-of-line chunk
  uint64_t inflight_now = 0;     // chunks currently outstanding
  uint64_t inflight_peak = 0;
  uint64_t inflight_sum = 0;     // summed at each admit; avg = sum/chunks
  uint64_t capacity = 0;         // configured chunks-in-flight bound
  uint64_t workers = 0;          // parse worker thread count
  uint64_t simd_tier = 0;        // structural-scan lane (simd_scan.h
                                 // SimdTier: 0 scalar, 1 swar, 2 sse2,
                                 // 3 avx2)
};

// Parser factory registry entry (reference ParserFactoryReg +
// DMLC_REGISTER_DATA_PARSER, data.h:330-358): formats resolve by name
// through Registry<ParserFactoryReg<I>> so downstream code can register
// additional native formats.
template <typename IndexType>
struct ParserFactoryReg
    : public FunctionRegEntryBase<
          ParserFactoryReg<IndexType>,
          std::function<TextParserBase<IndexType>*(
              InputSplit*, const std::map<std::string, std::string>&, int)>> {
};

template <typename IndexType>
class Parser {
 public:
  virtual ~Parser() = default;
  virtual void BeforeFirst() = 0;
  // Produce the next block of rows; nullptr at end of data. The returned
  // container stays valid until the next call.
  virtual const RowBlockContainer<IndexType>* NextBlock() = 0;
  // Move the next block into *out; false at end of data. Swap semantics
  // where the implementation allows it (out's old buffers return to the
  // producer's recycled cells, so capacity is never lost) — the zero-copy
  // hand-off the padded batcher rides (reference parser.h:95-109 keeps
  // the same discipline with its shared data_ vector). Base: copy.
  virtual bool NextBlockMove(RowBlockContainer<IndexType>* out) {
    const RowBlockContainer<IndexType>* b = NextBlock();
    if (b == nullptr) return false;
    *out = *b;
    return true;
  }
  // Borrowed view of the next block; false at end of data. The default
  // aliases NextBlock()'s container (valid until the next call, like the
  // C-ABI contract). The shard cache's mmap replay (shard_cache.h)
  // overrides this to serve pointers straight into the mapping — the
  // zero-copy lane dct_parser_next_block rides.
  virtual bool NextBlockView(RowBlockView<IndexType>* out) {
    const RowBlockContainer<IndexType>* b = NextBlock();
    if (b == nullptr) return false;
    out->FromContainer(*b);
    return true;
  }
  virtual size_t BytesRead() const = 0;
  // Pin the shuffle permutation the next BeforeFirst samples (mid-epoch
  // resume across restarts; InputSplit::SetShuffleEpoch). False when the
  // underlying split chain does not shuffle.
  virtual bool SetShuffleEpoch(unsigned epoch) {
    (void)epoch;
    return false;
  }
  // Pipeline occupancy counters; false when this parser chain carries no
  // multi-chunk pipeline (threaded=false). Wrappers forward to their base.
  virtual bool GetPipelineStats(ParsePipelineStats* out) const {
    (void)out;
    return false;
  }

  // Factory (reference src/data.cc:62-85 CreateParser_): format is
  // "libsvm" | "csv" | "libfm" | "criteo" | "rec" | "auto" (resolved from
  // the ?format= URI arg).
  // `threaded` pipelines parsing against consumption (PipelinedParser).
  // `chunks_in_flight` bounds the pipeline's outstanding chunks (0 = auto;
  // also settable per-URI via `?chunks_in_flight=K`). Caching sugar
  // (reference uri_spec.h:42-57, src/data.cc:97-103): a legacy `#<path>`
  // fragment enables the DiskCacheParser single-file row-block cache;
  // `#cachefile=<dir>` (or `cache_dir` here / DMLC_DATA_CACHE_DIR) enables
  // the manifest-keyed transcoding shard cache with mmap zero-copy replay
  // (shard_cache.h, doc/caching.md). `cache_mode` / `?cache=` /
  // DMLC_DATA_CACHE is never|auto|refresh.
  static Parser* Create(const std::string& uri, unsigned part, unsigned npart,
                        const std::string& format, int nthread = 0,
                        bool threaded = true, int chunks_in_flight = 0,
                        const std::string& cache_dir = "",
                        const std::string& cache_mode = "");
};

// --------------------------------------------------------------------------
// Chunk-parallel text parser base.
template <typename IndexType>
class TextParserBase : public Parser<IndexType> {
 public:
  TextParserBase(InputSplit* source, int nthread);
  ~TextParserBase() override;

  void BeforeFirst() override;
  const RowBlockContainer<IndexType>* NextBlock() override;
  bool NextBlockMove(RowBlockContainer<IndexType>* out) override;
  size_t BytesRead() const override {
    return bytes_read_.load(std::memory_order_relaxed);
  }
  bool SetShuffleEpoch(unsigned epoch) override {
    return source_->SetShuffleEpoch(epoch);
  }

  // Parse [begin, end) — whole lines — into *out. Public for testing.
  virtual void ParseBlock(const char* begin, const char* end,
                          RowBlockContainer<IndexType>* out) = 0;

  // Fill `blocks` (resized to the worker count) from the next chunk;
  // returns false at end of data. The synchronous (threaded=false) path:
  // barrier fan-out over the persistent pool, one chunk per round.
  bool FillBlocks(std::vector<RowBlockContainer<IndexType>>* blocks);

  // -- multi-chunk pipeline hooks (PipelinedParser stages) -----------------
  // Copy the next chunk into *buf (the source Blob is only valid until the
  // following NextChunk, so in-flight chunks need owned bytes); false at
  // end of data. Counts toward BytesRead.
  bool ReadChunk(std::vector<char>* buf);
  // Tile [begin, end) into `nslice` unit-aligned slices: cuts has
  // nslice + 1 monotone entries, cut i at the first parse-unit head at or
  // after i*size/nslice (the same tiling FillBlocks uses, so pipelined
  // output block boundaries match the barrier path exactly).
  void TileCuts(const char* begin, const char* end, int nslice,
                std::vector<const char*>* cuts);
  // Slice count for a chunk of `size` bytes: nthread, or 1 for chunks too
  // small to amortize the fan-out.
  int SlicesFor(size_t size) const {
    return size < (size_t(1) << 16) ? 1 : nthread_;
  }
  int num_threads() const { return nthread_; }
  // Structural-scan lane this parser decodes with (simd_scan.h SimdTier;
  // resolved from DMLC_PARSE_SIMD + CPUID at construction, reported
  // through ParsePipelineStats). The rec binary lane never consults it.
  int simd_tier() const { return simd_tier_; }

 protected:
  // Worker-tiling resync: the first parse-unit head at/after `hint` in
  // [base, end). Text formats resync at line heads (default); binary
  // formats override (RecParser resyncs at RecordIO magics — the reference
  // splits text by BackFindEndLine and recordio by magic scan,
  // src/recordio.cc FindNextRecordIOHead).
  virtual const char* FindUnitBoundary(const char* base, const char* hint,
                                       const char* end);

  std::unique_ptr<InputSplit> source_;
  int nthread_;
  SimdTier simd_tier_ = kSimdScalar;
  // read from the consumer thread while the pipeline reader fills
  std::atomic<size_t> bytes_read_{0};
  // direct chunk-producer view of source_ when its top layer exposes one
  // (ReadChunk fast lane); probed once, lazily
  RecordChunkSource* chunk_source_ = nullptr;
  bool chunk_source_probed_ = false;

 private:
  // Persistent worker pool for the chunk fan-out: spawning fresh
  // std::threads per chunk costs ~100 us each, which 2 MB chunks turn
  // into a measurable tax (the reference fans out via OpenMP's persistent
  // team, text_parser.h:60-84 — this is the same economics without omp).
  // Workers parse slices 1..n-1 of the current round; slice 0 runs on the
  // calling thread. Round state is handed over under pool_mu_.
  void EnsurePool(int workers);
  void WorkerLoop(int i);

  std::vector<std::thread> pool_;
  std::mutex pool_mu_;
  std::condition_variable pool_cv_, done_cv_;
  uint64_t pool_generation_ DMLC_GUARDED_BY(pool_mu_) = 0;
  int pool_done_ DMLC_GUARDED_BY(pool_mu_) = 0;
  int pool_active_ DMLC_GUARDED_BY(pool_mu_) = 0;
  bool pool_stop_ DMLC_GUARDED_BY(pool_mu_) = false;
  const std::vector<const char*>* round_cuts_
      DMLC_GUARDED_BY(pool_mu_) = nullptr;
  std::vector<RowBlockContainer<IndexType>>* round_blocks_
      DMLC_GUARDED_BY(pool_mu_) = nullptr;
  std::vector<std::exception_ptr>* round_errors_
      DMLC_GUARDED_BY(pool_mu_) = nullptr;

  std::vector<RowBlockContainer<IndexType>> blocks_;
  size_t block_idx_ = 0;
  size_t block_count_ = 0;
};

// libsvm: `label[:weight] [qid:n] index[:value]...`, '#' comments
// (reference src/data/libsvm_parser.h:87-169). Two decode lanes sharing
// ONE tokenizer template: ParseBlockScalar instantiates it with the
// byte-loop numeric primitives, ParseBlockSimd with the fused SWAR field
// decoders plus the stage-1 reserve-hint scan (simd_scan.h); outputs are
// byte-identical by construction (tests/test_parse_simd.py pins it over
// adversarial corpora, DMLC_PARSE_SIMD=0 forces the scalar lane).
template <typename IndexType>
class LibSVMParser : public TextParserBase<IndexType> {
 public:
  LibSVMParser(InputSplit* source,
               const std::map<std::string, std::string>& args, int nthread);
  void ParseBlock(const char* begin, const char* end,
                  RowBlockContainer<IndexType>* out) override;

 private:
  void ParseBlockScalar(const char* begin, const char* end,
                        RowBlockContainer<IndexType>* out);
  void ParseBlockSimd(const char* begin, const char* end,
                      RowBlockContainer<IndexType>* out);
  int indexing_mode_;  // >0: 1-based, 0: 0-based, <0: heuristic
};

// csv: dense rows, explicit column index per value, label/weight columns,
// single-char delimiter, missing values skipped
// (reference src/data/csv_parser.h:24-147).
template <typename IndexType>
class CSVParser : public TextParserBase<IndexType> {
 public:
  CSVParser(InputSplit* source, const std::map<std::string, std::string>& args,
            int nthread);
  void ParseBlock(const char* begin, const char* end,
                  RowBlockContainer<IndexType>* out) override;

 private:
  void ParseBlockScalar(const char* begin, const char* end,
                        RowBlockContainer<IndexType>* out);
  void ParseBlockSimd(const char* begin, const char* end,
                      RowBlockContainer<IndexType>* out);
  int label_column_ = -1;
  int weight_column_ = -1;
  char delimiter_ = ',';
  int value_dtype_ = 0;  // 0=float32, 1=int32, 2=int64
};

// libfm: `label[:weight] field:feature:value...`
// (reference src/data/libfm_parser.h:24-144).
template <typename IndexType>
class LibFMParser : public TextParserBase<IndexType> {
 public:
  LibFMParser(InputSplit* source,
              const std::map<std::string, std::string>& args, int nthread);
  void ParseBlock(const char* begin, const char* end,
                  RowBlockContainer<IndexType>* out) override;

 private:
  void ParseBlockScalar(const char* begin, const char* end,
                        RowBlockContainer<IndexType>* out);
  void ParseBlockSimd(const char* begin, const char* end,
                      RowBlockContainer<IndexType>* out);
  int indexing_mode_;
};

// criteo: the Criteo click logs as published — `label \t I1..I13 \t
// C1..C26`, 40 tab-separated cells a line, an empty cell a missing value
// (the format dmlc/wormhole reads with learn/base/criteo_parser.h). Every
// present feature cell, integer cells too, becomes the id
// fold(hash64(column, cell bytes), hash_bits) of criteo_hash.h; rows carry
// label, offset and index and no value (every value is 1). A line with
// another count of cells than 40, or a label that is not a number, throws,
// naming the format and where the line starts in its block. Both lanes run
// one tokenizer and the one hash: the SIMD tier only sizes the reserves
// and decodes the label, so ids cannot differ by tier.
template <typename IndexType>
class CriteoParser : public TextParserBase<IndexType> {
 public:
  CriteoParser(InputSplit* source,
               const std::map<std::string, std::string>& args, int nthread);
  void ParseBlock(const char* begin, const char* end,
                  RowBlockContainer<IndexType>* out) override;

 private:
  int hash_bits_;
};

// rec: binary ingest — RecordIO records whose payloads are serialized
// RowBlockContainers (8-byte header: 'DRB1' magic + flags, then the
// rowblock.h wire format). Deserialization is bulk memcpy, so this lane
// can feed the host->HBM path at rates text parsing cannot (the binary
// counterpart of the reference's pre-parsed .rec datasets; chunk-parallel
// via RecordIOChunkReader, reference recordio.h:166). Written by
// dmlc_core_tpu/io/convert.py rows_to_recordio.
template <typename IndexType>
class RecParser : public TextParserBase<IndexType> {
 public:
  RecParser(InputSplit* source, const std::map<std::string, std::string>& args,
            int nthread);
  void ParseBlock(const char* begin, const char* end,
                  RowBlockContainer<IndexType>* out) override;

 protected:
  const char* FindUnitBoundary(const char* base, const char* hint,
                               const char* end) override;
};

// --------------------------------------------------------------------------
// Disk row-block cache (reference src/data/disk_row_iter.h): the first
// epoch serves parsed blocks while appending their binary serialization to
// a cache file; later epochs replay the cache (skipping text parsing and
// the original filesystem entirely), prefetched on a pipeline thread.
template <typename IndexType>
class DiskCacheParser : public Parser<IndexType> {
 public:
  // takes ownership of base; fingerprint identifies (uri, part, npart)
  DiskCacheParser(Parser<IndexType>* base, const std::string& cache_file,
                  const std::string& fingerprint);
  ~DiskCacheParser() override;

  void BeforeFirst() override;
  const RowBlockContainer<IndexType>* NextBlock() override;
  bool NextBlockMove(RowBlockContainer<IndexType>* out) override;
  size_t BytesRead() const override { return base_->BytesRead(); }
  bool SetShuffleEpoch(unsigned epoch) override {
    // unreachable in practice: Create forbids shuffle + #cachefile
    return base_->SetShuffleEpoch(epoch);
  }
  bool GetPipelineStats(ParsePipelineStats* out) const override {
    // meaningful during the write-through epoch; replay bypasses the parse
    // pipeline (counters then freeze at their epoch-1 values)
    return base_->GetPipelineStats(out);
  }

 private:
  void FinalizeCache();
  bool TryOpenCache();
  void StartReplayPipeline();
  void EnsureWriter();  // open the .tmp cache + header on first write

  std::unique_ptr<Parser<IndexType>> base_;
  std::string cache_file_;
  uint64_t fingerprint_ = 0;
  std::unique_ptr<Stream> writer_;
  std::unique_ptr<SeekStream> reader_;
  bool replaying_ = false;
  bool write_complete_ = false;
  // replay is prefetched on a pipeline thread (reference DiskRowIter's
  // ThreadedIter, disk_row_iter.h:96-108)
  PipelineIter<RowBlockContainer<IndexType>> replay_pipe_{4};
  RowBlockContainer<IndexType>* replay_cell_ = nullptr;
  bool replay_started_ = false;
};

// --------------------------------------------------------------------------
// Multi-chunk in-flight parse pipeline — the threaded=true wrapper.
//
// The predecessor (ThreadedParser, reference src/data/parser.h:70-126)
// pipelined exactly ONE chunk against consumption and fanned each chunk out
// behind a barrier (FillBlocks), so the producer thread serialized the
// InputSplit read against the straggler slice of every round and added
// workers mostly waited (+2% at 4 threads when it was last measured).
// Here the stages are decoupled:
//
//   reader thread ──> bounded in-flight chunk queue ──> worker pool
//                        (≤ chunks_in_flight)        (claim (chunk, slice))
//                                  │
//                        ordered head-of-line reassembly ──> consumer
//
// - The reader keeps up to `chunks_in_flight` chunks outstanding, copying
//   each InputSplit::NextChunk blob into an owned, recycled buffer and
//   pre-tiling it into nthread unit-aligned slices (TileCuts — identical
//   tiling to the barrier path, so output blocks are byte-identical to
//   nthread=1 concatenation).
// - Workers claim (chunk, slice) work items oldest-chunk-first; slices of
//   chunk N+1 parse while a straggler of chunk N is still running — no
//   barrier anywhere.
// - The consumer drains chunks strictly in input order (head-of-line wait
//   on the oldest chunk), preserving deterministic output; consumed chunk
//   tasks recycle their buffers through a free list so the zero-copy C-ABI
//   hand-off and NextBlockMove swap semantics keep their capacity-reuse
//   discipline.
// Exceptions from any stage surface at the consumer in input order
// (reference OMPException rethrow semantics).
template <typename IndexType>
class PipelinedParser : public Parser<IndexType> {
 public:
  // takes ownership of base; chunks_in_flight <= 0 picks a default sized
  // to the worker count
  explicit PipelinedParser(TextParserBase<IndexType>* base,
                           int chunks_in_flight = 0);
  ~PipelinedParser() override;

  void BeforeFirst() override;
  const RowBlockContainer<IndexType>* NextBlock() override;
  bool NextBlockMove(RowBlockContainer<IndexType>* out) override;
  size_t BytesRead() const override { return base_->BytesRead(); }
  bool SetShuffleEpoch(unsigned epoch) override {
    return base_->SetShuffleEpoch(epoch);
  }
  bool GetPipelineStats(ParsePipelineStats* out) const override;

 private:
  // One chunk in flight: owned bytes, slice cuts, per-slice output blocks
  // and errors. Buffers (data + blocks) survive recycling, so steady state
  // allocates nothing.
  struct ChunkTask {
    std::vector<char> data;
    std::vector<const char*> cuts;  // nslice + 1 monotone boundaries
    std::vector<RowBlockContainer<IndexType>> blocks;
    std::vector<std::exception_ptr> errors;
    int nslice = 0;
    // next_slice/remaining are guarded by the owning parser's mu_ —
    // documented, not DMLC_GUARDED_BY: clang's thread-safety analysis
    // cannot name another object's member from a nested struct
    int next_slice = 0;  // next unclaimed slice
    int remaining = 0;   // unparsed slices; 0 = complete
    size_t next_serve = 0;  // consumer cursor over blocks[0..nslice)
  };

  void Start();        // spawn reader + workers (lazy, on first NextBlock)
  void StopThreads();  // join all stages, reclaim in-flight tasks
  void ReaderLoop();
  void WorkerLoop();
  RowBlockContainer<IndexType>* NextMutable();  // shared walk for both Next*
  void RecycleCurrent();

  std::unique_ptr<TextParserBase<IndexType>> base_;
  size_t capacity_;
  int nworker_;

  mutable std::mutex mu_;             // mutable: locked by const stats reads
  std::condition_variable space_cv_;  // reader waits for in-flight room
  std::condition_variable work_cv_;   // workers wait for claimable slices
  std::condition_variable done_cv_;   // consumer waits on head-of-line
  // admitted chunks, input order
  std::deque<ChunkTask*> inflight_ DMLC_GUARDED_BY(mu_);
  // prefix of inflight_ with free slices
  std::deque<ChunkTask*> claim_ DMLC_GUARDED_BY(mu_);
  std::vector<ChunkTask*> free_ DMLC_GUARDED_BY(mu_);  // recycled tasks
  bool stop_ DMLC_GUARDED_BY(mu_) = false;
  bool eof_ DMLC_GUARDED_BY(mu_) = false;
  std::exception_ptr reader_error_ DMLC_GUARDED_BY(mu_);
  bool failed_ = false;  // consumer saw an error; restart is forbidden
  bool started_ = false;
  std::thread reader_;
  std::vector<std::thread> workers_;

  ChunkTask* current_ = nullptr;  // chunk being served to the consumer

  // stats: relaxed atomics — written by stage threads, read via the C ABI
  std::atomic<uint64_t> chunks_read_{0}, blocks_delivered_{0},
      reader_waits_{0}, worker_waits_{0}, consumer_waits_{0},
      inflight_peak_{0}, inflight_sum_{0};
};

}  // namespace dct

#endif  // DCT_PARSER_H_
