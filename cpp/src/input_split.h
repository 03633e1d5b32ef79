// Record-aligned distributed input splitting.
//
// Counterpart of reference include/dmlc/io.h:155-302 (InputSplit) and
// src/io/input_split_base.{h,cc} / line_split / recordio_split /
// indexed_recordio_split / threaded_input_split / cached_input_split /
// single_file_split. The distributed-read contract (SURVEY §3.2, reference
// input_split_base.cc:30-64): the byte space of the expanded file list is
// tiled into num_parts aligned ranges, and both edges of each range are moved
// forward to the next record head with the *same* rule — so every record
// belongs to exactly one part and the union of parts covers the dataset.
//
// Architecture here differs from the reference: one ByteSplit base owns a
// (file cursor, chunk buffer, overflow carry) state machine, and format
// policy objects supply three hooks: SeekRecordHead (stream resync),
// FindLastRecordHead (chunk-tail truncation), and record extraction.
#ifndef DCT_INPUT_SPLIT_H_
#define DCT_INPUT_SPLIT_H_

#include <atomic>
#include <memory>
#include <string>
#include <vector>

#include "filesys.h"
#include "pipeline.h"
#include "stream.h"
#include "telemetry.h"

namespace dct {

class InputSplit {
 public:
  struct Blob {
    void* dptr = nullptr;
    size_t size = 0;
  };

  virtual ~InputSplit() = default;
  // restart this part from its beginning (re-shuffles shuffled variants)
  virtual void BeforeFirst() = 0;
  // Pin the permutation the NEXT BeforeFirst() samples: shuffled variants
  // derive their per-epoch order from (seed, epoch), so a checkpoint that
  // records the epoch can replay the exact visit order after a restart —
  // without this, a resumed skip-prefix walks a different permutation and
  // silently duplicates/drops rows (mid-epoch resume, device_iter.py).
  // Returns false when nothing in the split chain shuffles (ordering is
  // epoch-independent and resume is safe anyway).
  virtual bool SetShuffleEpoch(unsigned epoch) {
    (void)epoch;
    return false;
  }
  // next single record; false at end of part
  virtual bool NextRecord(Blob* out) = 0;
  // next raw chunk of whole records; false at end of part
  virtual bool NextChunk(Blob* out) = 0;
  virtual void HintChunkSize(size_t bytes) {}
  virtual size_t GetTotalSize() = 0;
  // re-point this object at another (rank, nsplit) partition
  virtual void ResetPartition(unsigned rank, unsigned nsplit) = 0;

  // Factory (reference src/io.cc:81-130). type is "text" | "recordio" |
  // "indexed_recordio" (requires index_uri; honors shuffle/seed/batch_size).
  // uri may be ';'-separated and may name directories or trailing-'*'
  // globs. Composition order: base split -> CachedSplit (when cache_file)
  // -> PrefetchSplit (threaded) -> ShuffleSplit (when shuffle_parts > 1).
  static InputSplit* Create(const std::string& uri, unsigned part,
                            unsigned nsplit, const std::string& type,
                            const std::string& index_uri = "",
                            bool shuffle = false, int seed = 0,
                            size_t batch_size = 256,
                            bool recurse_directories = false,
                            bool threaded = true,
                            const std::string& cache_file = "",
                            unsigned shuffle_parts = 0);
};

// ---------------------------------------------------------------------------
// Chunk-producer interface consumed by the prefetch/cache wrappers: fills a
// caller buffer with whole records and extracts records from such buffers.
class RecordChunkSource {
 public:
  virtual ~RecordChunkSource() = default;
  virtual bool FillChunkBuffer(std::vector<char>* buf) = 0;
  // Extraction must only touch extraction state (concurrent with filling).
  virtual bool ExtractRecordAt(char* data, size_t valid, size_t* cursor,
                               InputSplit::Blob* out) = 0;
  virtual void SourceBeforeFirst() = 0;
};

// Expand a ';'-separated uri list (directories, trailing-'*' globs) into an
// ordered file list (reference input_split_base.cc:96-147).
std::vector<FileInfo> ExpandFileList(const std::string& uri,
                                     bool recurse_directories);

// ---------------------------------------------------------------------------
// Base byte-range splitter over an expanded file list.
class ByteSplit : public InputSplit, public RecordChunkSource {
 public:
  ByteSplit(const std::string& uri, unsigned align_bytes, bool is_text,
            bool recurse_directories);

  void BeforeFirst() override;
  bool NextRecord(Blob* out) override;
  bool NextChunk(Blob* out) override;
  void HintChunkSize(size_t bytes) override {
    chunk_size_ = std::max(bytes, size_t(64));
  }
  size_t GetTotalSize() override { return total_size_; }
  void ResetPartition(unsigned rank, unsigned nsplit) override;

 public:
  // --- format hooks ---
  // Advance `s` (positioned inside a record) to the next record head; return
  // bytes consumed. `file_size` is the size of the current file.
  virtual size_t SeekRecordHead(SeekStream* s, size_t local_pos,
                                size_t file_size) = 0;
  // Last record-head offset in [begin, end) strictly after `begin`, given
  // that `begin` is a record head; bytes from there on are carried to the
  // next chunk. Return 0 when no boundary found (chunk must grow).
  virtual size_t FindLastRecordHead(const char* begin, const char* end) = 0;

  // RecordChunkSource: fill `*buf` with whole records (overflow carry
  // preserved across calls); false at end of partition.
  bool FillChunkBuffer(std::vector<char>* buf) override;
  void SourceBeforeFirst() override { BeforeFirst(); }

 protected:
  // chunk data for unwrapped record iteration
  std::vector<char> chunk_;
  size_t cursor_ = 0;  // record-extraction position in chunk_

 private:
  size_t GlobalBoundaryFixup(size_t ofs);
  void SeekToGlobal(size_t ofs);
  // Read up to `want` bytes from the partition byte range, crossing file
  // boundaries, injecting '\n' between text files lacking trailing newlines
  // (the NOEOL rule, reference input_split_base.cc:195-199). Returns bytes
  // written into buf.
  size_t ReadSpan(char* buf, size_t want);

  std::vector<FileInfo> files_;
  std::vector<size_t> file_start_;  // cumulative start offset of each file
  size_t total_size_ = 0;

  size_t begin_ = 0, end_ = 0;  // adjusted partition range (global bytes)
  unsigned rank_ = 0, nsplit_ = 1;

  // read cursor
  size_t file_idx_ = 0;
  size_t local_pos_ = 0;  // position within current file
  std::unique_ptr<SeekStream> cur_stream_;
  // split_bytes_read_total{scheme=} of the open stream's scheme
  telemetry::Counter* bytes_read_ = nullptr;
  char prev_byte_ = '\n';  // last byte read from current file
  bool pending_newline_ = false;

  std::vector<char> overflow_;  // partial trailing record from last chunk
  size_t chunk_size_;
  bool exhausted_ = false;

 protected:
  unsigned align_bytes_;
  bool is_text_;
};

// Sequential line split over one non-seekable stream — the stdin / single
// local FILE fallback (reference src/io/single_file_split.h:32-179, selected
// at src/io.cc:94-96 when uri=="stdin"). Partitioning is not possible on a
// pipe, so part must be 0 of 1.
class SingleFileSplit : public InputSplit {
 public:
  explicit SingleFileSplit(const std::string& uri);

  void BeforeFirst() override;
  bool NextRecord(Blob* out) override;
  bool NextChunk(Blob* out) override;
  void HintChunkSize(size_t bytes) override {
    chunk_size_ = std::max(bytes, size_t(64));
  }
  size_t GetTotalSize() override;
  void ResetPartition(unsigned rank, unsigned nsplit) override;

 private:
  // read chunk_size_ bytes + extend to the next '\n' (or EOF)
  bool FillChunk();

  std::string uri_;
  std::unique_ptr<Stream> stream_;
  std::vector<char> chunk_;
  size_t valid_ = 0;   // bytes of chunk_ holding whole records
  size_t cursor_ = 0;  // record-extraction position
  size_t chunk_size_ = 16 << 20;
  bool exhausted_ = false;
};

// Text records delimited by '\n' (reference src/io/line_split.cc).
class LineSplit : public ByteSplit {
 public:
  LineSplit(const std::string& uri, unsigned part, unsigned nsplit,
            bool recurse_directories = false);

 public:
  size_t SeekRecordHead(SeekStream* s, size_t local_pos,
                        size_t file_size) override;
  size_t FindLastRecordHead(const char* begin, const char* end) override;
  bool ExtractRecordAt(char* data, size_t valid, size_t* cursor,
                       Blob* out) override;
};

// Binary recordio records (reference src/io/recordio_split.cc): resync by
// scanning for an aligned magic word whose following header has cflag 0|1.
class RecordIOSplit : public ByteSplit {
 public:
  RecordIOSplit(const std::string& uri, unsigned part, unsigned nsplit,
                bool recurse_directories = false);

 public:
  size_t SeekRecordHead(SeekStream* s, size_t local_pos,
                        size_t file_size) override;
  size_t FindLastRecordHead(const char* begin, const char* end) override;
  bool ExtractRecordAt(char* data, size_t valid, size_t* cursor,
                       Blob* out) override;

 private:
  std::string assembled_;
};

// ---------------------------------------------------------------------------
// Record-exact partitioned split over an external index file of
// `record_index byte_offset` text pairs (reference src/io/
// indexed_recordio_split.{h,cc}): partitions BY RECORD COUNT, batches
// batch_size records per chunk, optionally visiting records in a freshly
// shuffled order each epoch (kRandMagic + seed mt19937, reshuffled in
// BeforeFirst — reference :221-233).
class IndexedRecordIOSplit : public InputSplit, public RecordChunkSource {
 public:
  IndexedRecordIOSplit(const std::string& uri, const std::string& index_uri,
                       unsigned part, unsigned nsplit, size_t batch_size,
                       bool shuffle, int seed, bool recurse_directories);

  void BeforeFirst() override;
  bool NextRecord(Blob* out) override;
  bool NextChunk(Blob* out) override;
  size_t GetTotalSize() override { return total_size_; }
  void ResetPartition(unsigned rank, unsigned nsplit) override;
  bool SetShuffleEpoch(unsigned epoch) override {
    epoch_.store(epoch, std::memory_order_relaxed);
    return shuffle_;
  }

  bool FillChunkBuffer(std::vector<char>* buf) override;
  bool ExtractRecordAt(char* data, size_t valid, size_t* cursor,
                       Blob* out) override;
  void SourceBeforeFirst() override { BeforeFirst(); }

 private:
  void ReadSpanAt(size_t global_ofs, char* dst, size_t size);

  std::vector<FileInfo> files_;
  std::vector<size_t> file_start_;
  size_t total_size_ = 0;
  // (global byte offset, byte size) of every record, in file order
  std::vector<std::pair<size_t, size_t>> index_;
  size_t lo_ = 0, hi_ = 0;     // record range of this partition
  std::vector<size_t> order_;  // visit order within [lo_, hi_)
  size_t next_rec_ = 0;
  size_t batch_size_;
  bool shuffle_;
  int seed_;
  // written by SetShuffleEpoch on the control thread, read/bumped inside
  // BeforeFirst on the prefetch producer thread (the pipe's mutex orders
  // the two; atomic removes the formal data race)
  std::atomic<unsigned> epoch_{0};
  std::vector<char> chunk_;
  size_t cursor_ = 0;
  std::string assembled_;
  std::unique_ptr<SeekStream> open_stream_;  // reused across records
  size_t open_file_ = size_t(-1);
};

// ---------------------------------------------------------------------------
// Write-through chunk cache (reference src/io/cached_input_split.h): the
// first epoch streams [u64 size][bytes] frames of every chunk to a local
// cache file while serving them; later epochs replay from the cache,
// skipping the original (possibly remote) filesystem entirely.
class CachedSplit : public InputSplit, public RecordChunkSource {
 public:
  // takes ownership of base (which must also be the extraction source).
  // `fingerprint` identifies (uri, part, nsplit, type); a pre-existing cache
  // written under a different fingerprint is ignored and rebuilt.
  CachedSplit(InputSplit* base, RecordChunkSource* base_src,
              const std::string& cache_file, const std::string& fingerprint);
  ~CachedSplit() override;

  void BeforeFirst() override;
  bool NextRecord(Blob* out) override;
  bool NextChunk(Blob* out) override;
  void HintChunkSize(size_t bytes) override { base_->HintChunkSize(bytes); }
  size_t GetTotalSize() override { return base_->GetTotalSize(); }
  void ResetPartition(unsigned rank, unsigned nsplit) override;
  bool SetShuffleEpoch(unsigned epoch) override {
    return base_->SetShuffleEpoch(epoch);
  }

  bool FillChunkBuffer(std::vector<char>* buf) override;
  bool ExtractRecordAt(char* data, size_t valid, size_t* cursor,
                       Blob* out) override;
  void SourceBeforeFirst() override { BeforeFirst(); }

 private:
  void FinalizeCache();

  std::unique_ptr<InputSplit> base_;
  RecordChunkSource* base_src_;  // borrowed view of base_
  std::string cache_file_;
  uint64_t fingerprint_ = 0;
  std::unique_ptr<Stream> cache_writer_;
  std::unique_ptr<SeekStream> cache_reader_;
  bool replaying_ = false;
  bool write_complete_ = false;
  std::vector<char> chunk_;
  size_t cursor_ = 0;
};

// ---------------------------------------------------------------------------
// Coarse-grained global shuffle (reference include/dmlc/
// input_split_shuffle.h): multiplies the partition count by
// num_shuffle_parts and visits this part's sub-parts in a freshly shuffled
// order each epoch.
class ShuffleSplit : public InputSplit {
 public:
  ShuffleSplit(InputSplit* base, unsigned part, unsigned nsplit,
               unsigned num_shuffle_parts, int seed);

  void BeforeFirst() override;
  bool NextRecord(Blob* out) override;
  bool NextChunk(Blob* out) override;
  void HintChunkSize(size_t bytes) override { base_->HintChunkSize(bytes); }
  size_t GetTotalSize() override { return base_->GetTotalSize(); }
  void ResetPartition(unsigned rank, unsigned nsplit) override;
  bool SetShuffleEpoch(unsigned epoch) override {
    epoch_.store(epoch, std::memory_order_relaxed);
    return true;
  }

 private:
  bool AdvanceSubPart();

  std::unique_ptr<InputSplit> base_;
  unsigned part_, nsplit_, num_shuffle_parts_;
  int seed_;
  std::atomic<unsigned> epoch_{0};  // see IndexedRecordIOSplit::epoch_
  std::vector<unsigned> order_;
  size_t cur_ = 0;
};

// ---------------------------------------------------------------------------
// Background prefetch wrapper (reference src/io/threaded_input_split.h):
// a PipelineIter of chunk cells produced by the wrapped source.
class PrefetchSplit : public InputSplit {
 public:
  // takes ownership of base; src must be the same object's chunk interface
  PrefetchSplit(InputSplit* base, RecordChunkSource* src,
                size_t capacity = 2);
  ~PrefetchSplit() override;

  void BeforeFirst() override;
  bool NextRecord(Blob* out) override;
  bool NextChunk(Blob* out) override;
  void HintChunkSize(size_t bytes) override { base_->HintChunkSize(bytes); }
  size_t GetTotalSize() override { return base_->GetTotalSize(); }
  void ResetPartition(unsigned rank, unsigned nsplit) override;
  bool SetShuffleEpoch(unsigned epoch) override {
    return base_->SetShuffleEpoch(epoch);
  }

 private:
  struct Cell {
    std::vector<char> data;
    size_t cursor = 0;
  };
  std::unique_ptr<InputSplit> base_;
  RecordChunkSource* src_;  // borrowed view of base_
  PipelineIter<Cell> pipe_;
  Cell* current_ = nullptr;
  bool started_ = false;
  void EnsureStarted();
};

}  // namespace dct

#endif  // DCT_INPUT_SPLIT_H_
