// InputSplit implementation. See input_split.h for the contract; the
// partition-edge rules mirror reference src/io/input_split_base.cc:30-64
// (aligned tiling + same-rule record-head fixup at both edges) and the
// chunking mirrors :221-258 (overflow carry of the partial trailing record).
#include "input_split.h"

#include "fs_fault.h"

#include <algorithm>
#include <cerrno>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <random>

#include "numparse.h"
#include "recordio.h"
#include "telemetry.h"

namespace dct {

namespace {

// Match a trailing-'*' glob or exact name.
bool GlobMatch(const std::string& pattern, const std::string& name) {
  size_t star = pattern.find('*');
  if (star == std::string::npos) return pattern == name;
  // prefix*suffix
  std::string prefix = pattern.substr(0, star);
  std::string suffix = pattern.substr(star + 1);
  if (name.size() < prefix.size() + suffix.size()) return false;
  return name.compare(0, prefix.size(), prefix) == 0 &&
         name.compare(name.size() - suffix.size(), suffix.size(), suffix) == 0;
}

std::string BaseName(const std::string& path) {
  size_t slash = path.find_last_of('/');
  return slash == std::string::npos ? path : path.substr(slash + 1);
}

}  // namespace

// --------------------------------------------------------------------------
// Expand ';'-separated URIs; directories list their contents; a '*' in the
// last path component globs within its directory
// (reference input_split_base.cc:96-147 InitInputFileInfo).
std::vector<FileInfo> ExpandFileList(const std::string& uri,
                                     bool recurse_directories) {
  std::vector<FileInfo> files_;
  for (const std::string& piece : StrSplit(uri, ';')) {
    if (piece.empty()) continue;
    URI u(piece);
    FileSystem* fs = FileSystem::GetInstance(u);
    std::string base = BaseName(u.path);
    if (base.find('*') != std::string::npos) {
      URI dir = u;
      size_t slash = u.path.find_last_of('/');
      dir.path = slash == std::string::npos ? "." : u.path.substr(0, slash);
      std::vector<FileInfo> listing;
      fs->ListDirectory(dir, &listing);
      std::sort(listing.begin(), listing.end(),
                [](const FileInfo& a, const FileInfo& b) {
                  return a.path.path < b.path.path;
                });
      for (const FileInfo& info : listing) {
        if (info.type == FileType::kFile && info.size != 0 &&
            GlobMatch(base, BaseName(info.path.path))) {
          files_.push_back(info);
        }
      }
      continue;
    }
    FileInfo info = fs->GetPathInfo(u);
    if (info.type == FileType::kDirectory) {
      std::vector<FileInfo> listing;
      if (recurse_directories) {
        fs->ListDirectoryRecursive(info.path, &listing);
      } else {
        fs->ListDirectory(info.path, &listing);
      }
      std::sort(listing.begin(), listing.end(),
                [](const FileInfo& a, const FileInfo& b) {
                  return a.path.path < b.path.path;
                });
      for (const FileInfo& f : listing) {
        std::string name = BaseName(f.path.path);
        if (f.type == FileType::kFile && f.size != 0 && !name.empty() &&
            name[0] != '.' && name[0] != '_') {
          files_.push_back(f);
        }
      }
    } else if (info.size != 0) {
      files_.push_back(info);
    }
  }
  DCT_CHECK(!files_.empty()) << "no non-empty input files match uri: " << uri;
  return files_;
}

namespace {
// Default read-chunk size, env-tunable (DCT_CHUNK_SIZE_KB). Chunk size
// trades per-chunk overhead against how finely prefetch/parse/consume
// overlap and how quickly the recycled-buffer pools warm up. 2 MB beats
// the earlier 8 MB by ~11% e2e on the 1-core bench host (A/B-interleaved,
// cpp/test/bench_pipeline.cc): a chunk plus its parsed CSR output stays
// cache-resident and short files see the recycle pools warm after the
// first few chunks instead of never.
size_t DefaultChunkSize() {
  const char* v = std::getenv("DCT_CHUNK_SIZE_KB");
  if (v != nullptr && *v != '\0') {
    char* end = nullptr;
    errno = 0;
    long kb = std::strtol(v, &end, 10);
    // bounded like parse_uarg: [64 KB, 1 GB]; anything else (junk,
    // overflow, tiny) falls back to the default instead of wrapping
    // through the shift into an absurd resize
    if (errno == 0 && end != v && *end == '\0' && kb >= 64 &&
        kb <= (1L << 20)) {
      return static_cast<size_t>(kb) << 10;
    }
  }
  return size_t(2) << 20;
}
}  // namespace

ByteSplit::ByteSplit(const std::string& uri, unsigned align_bytes,
                     bool is_text, bool recurse_directories)
    : chunk_size_(DefaultChunkSize()),
      align_bytes_(align_bytes),
      is_text_(is_text) {
  files_ = ExpandFileList(uri, recurse_directories);
  file_start_.resize(files_.size());
  size_t acc = 0;
  for (size_t i = 0; i < files_.size(); ++i) {
    file_start_[i] = acc;
    acc += files_[i].size;
  }
  total_size_ = acc;
}

void ByteSplit::ResetPartition(unsigned rank, unsigned nsplit) {
  DCT_CHECK_LT(rank, nsplit) << "part index out of range";
  rank_ = rank;
  nsplit_ = nsplit;
  size_t nstep = (total_size_ + nsplit - 1) / nsplit;
  nstep = (nstep + align_bytes_ - 1) / align_bytes_ * align_bytes_;
  size_t raw_begin = std::min(total_size_, nstep * rank);
  size_t raw_end = std::min(total_size_, nstep * (rank + 1));
  begin_ = GlobalBoundaryFixup(raw_begin);
  end_ = GlobalBoundaryFixup(raw_end);
  BeforeFirst();
}

size_t ByteSplit::GlobalBoundaryFixup(size_t ofs) {
  if (ofs == 0 || ofs >= total_size_) return std::min(ofs, total_size_);
  // file containing ofs
  size_t k =
      std::upper_bound(file_start_.begin(), file_start_.end(), ofs) -
      file_start_.begin() - 1;
  if (ofs == file_start_[k]) return ofs;  // a file start is a record head
  size_t local = ofs - file_start_[k];
  std::unique_ptr<SeekStream> s(
      FileSystem::GetInstance(files_[k].path)->OpenForRead(files_[k].path));
  s->Seek(local);
  // boundary probe: usually scans at most one record — no point letting a
  // readahead stream prefetch a whole window for it (the hint re-extends
  // automatically in the rare longer scan)
  s->HintReadBound(std::min(local + (64 << 10), files_[k].size));
  size_t consumed = SeekRecordHead(s.get(), local, files_[k].size);
  return std::min(file_start_[k] + local + consumed,
                  file_start_[k] + files_[k].size);
}

void ByteSplit::BeforeFirst() {
  // position the read cursor at begin_
  size_t k = files_.empty()
                 ? 0
                 : static_cast<size_t>(
                       std::upper_bound(file_start_.begin(), file_start_.end(),
                                        begin_) -
                       file_start_.begin()) -
                       1;
  if (begin_ >= total_size_ && !files_.empty()) k = files_.size() - 1;
  file_idx_ = k;
  local_pos_ = begin_ - file_start_[k];
  cur_stream_.reset();
  prev_byte_ = '\n';
  pending_newline_ = false;
  overflow_.clear();
  chunk_.clear();
  cursor_ = 0;
  exhausted_ = false;
}

size_t ByteSplit::ReadSpan(char* buf, size_t want) {
  size_t got = 0;
  while (got < want) {
    if (pending_newline_) {
      buf[got++] = '\n';
      pending_newline_ = false;
      continue;
    }
    size_t global = file_start_[file_idx_] + local_pos_;
    if (global >= end_) break;
    if (local_pos_ >= files_[file_idx_].size) {
      // advance to next file; inject newline between text files when the
      // previous file did not end with one (NOEOL rule,
      // reference input_split_base.cc:195-199, dmlc PRs 385/452)
      cur_stream_.reset();
      bool more = file_idx_ + 1 < files_.size() &&
                  file_start_[file_idx_ + 1] < end_;
      if (is_text_ && prev_byte_ != '\n' && more) pending_newline_ = true;
      if (!more) break;
      ++file_idx_;
      local_pos_ = 0;
      prev_byte_ = '\n';
      continue;
    }
    // The split over objects (doc/observability.md): an open that the
    // reader waits out. Nothing is in flight when the part's first object
    // is opened after BeforeFirst, nor when the next one is after the
    // current one is drained; split_open_us is that wait, from here to the
    // first byte read from the new stream.
    uint64_t open_start = 0;
    const bool opening = cur_stream_ == nullptr;
    if (opening) {
      if (telemetry::Enabled()) open_start = telemetry::NowUs();
      const URI& path = files_[file_idx_].path;
      cur_stream_.reset(FileSystem::GetInstance(path)->OpenForRead(path));
      cur_stream_->Seek(local_pos_);
      // this partition never reads past end_ in this file: a readahead
      // stream must not prefetch a window past the partition edge
      cur_stream_->HintReadBound(std::min(
          files_[file_idx_].size, end_ - file_start_[file_idx_]));
      bytes_read_ = telemetry::GetCounter(
          "split_bytes_read_total",
          {{"scheme", path.scheme.empty() ? "file" : path.scheme}});
    }
    size_t to_read = std::min(
        {want - got, files_[file_idx_].size - local_pos_, end_ - global});
    size_t n = cur_stream_->Read(buf + got, to_read);
    DCT_CHECK_GT(n, size_t(0))
        << "file " << files_[file_idx_].path.Str()
        << " shorter than listed size";
    if (opening) {
      static telemetry::Counter* opened =
          telemetry::GetCounter("split_objects_opened_total");
      opened->Add(1);
      if (open_start != 0) {
        static telemetry::Hist* open_us = telemetry::GetHist("split_open_us");
        const uint64_t dur = telemetry::NowUs() - open_start;
        open_us->Observe(dur);
        telemetry::EmitSpan("split.open", open_start, dur, file_idx_);
      }
    }
    bytes_read_->Add(n);
    local_pos_ += n;
    got += n;
    prev_byte_ = buf[got - 1];
  }
  return got;
}

bool ByteSplit::FillChunkBuffer(std::vector<char>* buf) {
  if (exhausted_ && overflow_.empty()) return false;
  buf->clear();
  buf->swap(overflow_);  // carried partial record heads the new chunk
  size_t target = buf->size() + chunk_size_;
  while (true) {
    size_t old = buf->size();
    buf->resize(target);
    size_t n = ReadSpan(buf->data() + old, target - old);
    buf->resize(old + n);
    if (n < target - old) exhausted_ = true;
    if (buf->empty()) return false;
    if (exhausted_) {
      // partition end is a record head: everything left is whole records
      break;
    }
    size_t boundary = FindLastRecordHead(buf->data(),
                                         buf->data() + buf->size());
    if (boundary == 0) {
      // no record boundary in sight: grow the chunk
      // (reference input_split_base.cc Chunk::Append)
      target = buf->size() + chunk_size_;
      continue;
    }
    overflow_.assign(buf->begin() + boundary, buf->end());
    buf->resize(boundary);
    break;
  }
  return true;
}

bool ByteSplit::NextChunk(Blob* out) {
  if (!FillChunkBuffer(&chunk_)) return false;
  out->dptr = chunk_.data();
  out->size = chunk_.size();
  cursor_ = chunk_.size();  // chunk handed out wholesale
  return true;
}

bool ByteSplit::NextRecord(Blob* out) {
  while (true) {
    if (cursor_ < chunk_.size() &&
        ExtractRecordAt(chunk_.data(), chunk_.size(), &cursor_, out)) {
      return true;
    }
    if (!FillChunkBuffer(&chunk_)) return false;
    cursor_ = 0;
  }
}

// --------------------------------------------------------------------------
LineSplit::LineSplit(const std::string& uri, unsigned part, unsigned nsplit,
                     bool recurse_directories)
    : ByteSplit(uri, /*align_bytes=*/1, /*is_text=*/true,
                recurse_directories) {
  ResetPartition(part, nsplit);
}

size_t LineSplit::SeekRecordHead(SeekStream* s, size_t local_pos,
                                 size_t file_size) {
  // consume bytes until just past the next '\n'; EOF counts as a head
  char buf[1024];
  size_t consumed = 0;
  while (true) {
    size_t n = s->Read(buf, sizeof(buf));
    if (n == 0) return consumed;  // NOEOL tail: boundary at file end
    const char* nl = static_cast<const char*>(std::memchr(buf, '\n', n));
    if (nl != nullptr) {
      return consumed + static_cast<size_t>(nl - buf) + 1;
    }
    consumed += n;
  }
}

size_t LineSplit::FindLastRecordHead(const char* begin, const char* end) {
  for (const char* p = end; p != begin;) {
    --p;
    if (*p == '\n') return static_cast<size_t>(p - begin) + 1;
  }
  return 0;
}

bool LineSplit::ExtractRecordAt(char* data, size_t valid, size_t* cursor,
                                Blob* out) {
  if (*cursor >= valid) return false;
  char* line = data + *cursor;
  size_t remain = valid - *cursor;
  char* nl = static_cast<char*>(std::memchr(line, '\n', remain));
  size_t len = (nl == nullptr) ? remain : static_cast<size_t>(nl - line);
  *cursor += len + (nl == nullptr ? 0 : 1);
  if (len > 0 && line[len - 1] == '\r') --len;  // CRLF
  out->dptr = line;
  out->size = len;
  return true;
}

// --------------------------------------------------------------------------
SingleFileSplit::SingleFileSplit(const std::string& uri) : uri_(uri) {
  stream_.reset(Stream::Create(uri, "r"));
}

void SingleFileSplit::BeforeFirst() {
  DCT_CHECK(uri_ != "stdin" || (valid_ == 0 && exhausted_ == false))
      << "stdin cannot be rewound";
  if (uri_ != "stdin") stream_.reset(Stream::Create(uri_, "r"));
  chunk_.clear();
  valid_ = cursor_ = 0;
  exhausted_ = false;
}

void SingleFileSplit::ResetPartition(unsigned rank, unsigned nsplit) {
  DCT_CHECK(rank == 0 && nsplit == 1)
      << "SingleFileSplit (stdin / single pipe) cannot be partitioned";
  BeforeFirst();
}

size_t SingleFileSplit::GetTotalSize() {
  if (uri_ == "stdin") return 0;  // unknowable on a pipe
  URI u(uri_);
  return FileSystem::GetInstance(u)->GetPathInfo(u).size;
}

bool SingleFileSplit::FillChunk() {
  if (exhausted_) return false;
  // carry bytes past `valid_` (a partial trailing line) to the front
  chunk_.erase(chunk_.begin(), chunk_.begin() + valid_);
  cursor_ = 0;
  size_t have = chunk_.size();
  chunk_.resize(have + chunk_size_);
  size_t n = stream_->Read(chunk_.data() + have, chunk_size_);
  chunk_.resize(have + n);
  if (n < chunk_size_) {
    exhausted_ = true;
    if (!chunk_.empty() && chunk_.back() != '\n') chunk_.push_back('\n');
    valid_ = chunk_.size();
    return valid_ != 0;
  }
  // grow byte-by-byte until the chunk ends on a line boundary
  while (!chunk_.empty() && chunk_.back() != '\n') {
    char c;
    if (stream_->Read(&c, 1) != 1) {
      exhausted_ = true;
      chunk_.push_back('\n');
      break;
    }
    chunk_.push_back(c);
  }
  valid_ = chunk_.size();
  return valid_ != 0;
}

bool SingleFileSplit::NextRecord(Blob* out) {
  while (true) {
    if (cursor_ < valid_) {
      char* line = chunk_.data() + cursor_;
      char* nl = static_cast<char*>(
          std::memchr(line, '\n', valid_ - cursor_));
      size_t len = (nl == nullptr) ? valid_ - cursor_
                                   : static_cast<size_t>(nl - line);
      cursor_ += len + (nl == nullptr ? 0 : 1);
      if (len > 0 && line[len - 1] == '\r') --len;  // CRLF
      out->dptr = line;
      out->size = len;
      return true;
    }
    if (!FillChunk()) return false;
  }
}

bool SingleFileSplit::NextChunk(Blob* out) {
  if (cursor_ >= valid_ && !FillChunk()) return false;
  out->dptr = chunk_.data() + cursor_;
  out->size = valid_ - cursor_;
  cursor_ = valid_;
  return true;
}

// --------------------------------------------------------------------------
RecordIOSplit::RecordIOSplit(const std::string& uri, unsigned part,
                             unsigned nsplit, bool recurse_directories)
    : ByteSplit(uri, /*align_bytes=*/4, /*is_text=*/false,
                recurse_directories) {
  ResetPartition(part, nsplit);
}

size_t RecordIOSplit::SeekRecordHead(SeekStream* s, size_t local_pos,
                                     size_t file_size) {
  // scan forward from the next 4-aligned offset for magic + cflag in {0,1}
  size_t aligned = recordio::AlignUp4(local_pos);
  if (aligned + 8 > file_size) return file_size - local_pos;
  s->Seek(aligned);
  std::vector<char> buf(size_t(1) << 16);
  size_t have = 0;       // valid bytes in buf
  size_t base = aligned;  // absolute file offset of buf[0] (4-aligned)
  while (true) {
    size_t n = s->Read(buf.data() + have, buf.size() - have);
    have += n;
    for (size_t i = 0; i + 8 <= have; i += 4) {
      if (recordio::IsRecordHead(buf.data() + i)) {
        return base + i - local_pos;
      }
    }
    if (n == 0) return file_size - local_pos;  // no head: file end
    // retain the unverified tail (first aligned i with i + 8 > have)
    size_t first_unchecked = have >= 8 ? recordio::AlignUp4(have - 7) : 0;
    size_t keep = have - first_unchecked;
    std::memmove(buf.data(), buf.data() + first_unchecked, keep);
    base += first_unchecked;
    have = keep;
  }
}

size_t RecordIOSplit::FindLastRecordHead(const char* begin, const char* end) {
  size_t size = static_cast<size_t>(end - begin) & ~size_t(3);
  for (size_t ofs = size >= 8 ? size - 8 : 0;; ofs -= 4) {
    if (ofs == 0) return 0;
    if (recordio::IsRecordHead(begin + ofs)) return ofs;
    if (ofs < 4) return 0;
  }
}

// Shared recordio frame extraction (multi-part reassembly into *assembled).
bool ExtractRecordIOFrame(char* data, size_t valid, size_t* cursor,
                          InputSplit::Blob* out, std::string* assembled) {
  if (*cursor + 8 > valid) {
    *cursor = valid;
    return false;
  }
  std::string& assembled_ = *assembled;
  assembled_.clear();
  bool multipart = false;
  while (true) {
    DCT_CHECK_LE(*cursor + 8, valid) << "truncated recordio chunk";
    uint32_t magic = recordio::LoadWordLE(data + *cursor);
    DCT_CHECK_EQ(magic, recordio::kMagic) << "bad recordio magic in chunk";
    uint32_t lrec = recordio::LoadWordLE(data + *cursor + 4);
    uint32_t cflag = recordio::HeaderFlag(lrec);
    uint32_t len = recordio::HeaderLen(lrec);
    size_t payload = *cursor + 8;
    DCT_CHECK_LE(payload + recordio::AlignUp4(len), valid)
        << "recordio record overruns chunk";
    *cursor = payload + recordio::AlignUp4(len);
    if (cflag == 0) {
      DCT_CHECK(!multipart) << "unexpected cflag=0 inside multi-part record";
      out->dptr = data + payload;
      out->size = len;
      return true;
    }
    if (cflag == 1) {
      DCT_CHECK(!multipart) << "unexpected cflag=1 inside multi-part record";
      multipart = true;
      assembled_.assign(data + payload, len);
    } else {
      DCT_CHECK(multipart) << "continuation part without a head";
      char magic_bytes[4];
      uint32_t m = recordio::kMagic;
      if (!serial::NativeIsLE()) m = serial::ByteSwap(m);
      std::memcpy(magic_bytes, &m, 4);
      assembled_.append(magic_bytes, 4);
      assembled_.append(data + payload, len);
      if (cflag == 3) {
        out->dptr = assembled_.data();
        out->size = assembled_.size();
        return true;
      }
      DCT_CHECK_EQ(cflag, 2u) << "invalid recordio cflag";
    }
  }
}

bool RecordIOSplit::ExtractRecordAt(char* data, size_t valid, size_t* cursor,
                                    Blob* out) {
  return ExtractRecordIOFrame(data, valid, cursor, out, &assembled_);
}

// --------------------------------------------------------------------------
// IndexedRecordIOSplit
IndexedRecordIOSplit::IndexedRecordIOSplit(
    const std::string& uri, const std::string& index_uri, unsigned part,
    unsigned nsplit, size_t batch_size, bool shuffle, int seed,
    bool recurse_directories)
    : batch_size_(std::max<size_t>(batch_size, 1)),
      shuffle_(shuffle),
      seed_(seed) {
  files_ = ExpandFileList(uri, recurse_directories);
  file_start_.resize(files_.size());
  size_t acc = 0;
  for (size_t i = 0; i < files_.size(); ++i) {
    file_start_[i] = acc;
    acc += files_[i].size;
  }
  total_size_ = acc;
  // index file: text `record_index byte_offset` pairs; offsets sorted and
  // differenced into (offset, size) records
  // (reference indexed_recordio_split.cc:43-62)
  std::vector<FileInfo> idx_files = ExpandFileList(index_uri, false);
  DCT_CHECK_EQ(idx_files.size(), size_t(1))
      << "indexed_recordio supports exactly one index file";
  std::unique_ptr<SeekStream> fi(
      FileSystem::GetInstance(idx_files[0].path)
          ->OpenForRead(idx_files[0].path));
  std::string text(idx_files[0].size, '\0');
  fi->ReadExact(&text[0], text.size());
  std::vector<size_t> offsets;
  const char* p = text.data();
  const char* end = p + text.size();
  while (p < end) {
    uint64_t idx_v, ofs_v;
    while (p < end && (*p == ' ' || *p == '\n' || *p == '\r' || *p == '\t'))
      ++p;
    if (p >= end) break;
    const char* q;
    DCT_CHECK(ParseNum<uint64_t>(p, end, &q, &idx_v)) << "bad index file";
    p = q;
    while (p < end && (*p == ' ' || *p == '\t')) ++p;
    DCT_CHECK(ParseNum<uint64_t>(p, end, &q, &ofs_v)) << "bad index file";
    p = q;
    offsets.push_back(ofs_v);
  }
  DCT_CHECK(!offsets.empty()) << "empty index file " << index_uri;
  std::sort(offsets.begin(), offsets.end());
  for (size_t j = 0; j + 1 < offsets.size(); ++j) {
    index_.emplace_back(offsets[j], offsets[j + 1] - offsets[j]);
  }
  index_.emplace_back(offsets.back(), total_size_ - offsets.back());
  ResetPartition(part, nsplit);
}

void IndexedRecordIOSplit::ResetPartition(unsigned rank, unsigned nsplit) {
  DCT_CHECK_LT(rank, nsplit) << "part index out of range";
  // partition BY RECORD COUNT, not bytes
  // (reference indexed_recordio_split.cc:12-41)
  size_t n = index_.size();
  size_t step = (n + nsplit - 1) / nsplit;
  lo_ = std::min(n, step * rank);
  hi_ = std::min(n, step * (rank + 1));
  epoch_ = 0;
  BeforeFirst();
}

void IndexedRecordIOSplit::BeforeFirst() {
  order_.resize(hi_ - lo_);
  for (size_t i = 0; i < order_.size(); ++i) order_[i] = lo_ + i;
  if (shuffle_) {
    // fresh permutation every epoch (reference BeforeFirst reshuffle,
    // kRandMagic = 111)
    std::mt19937 rng(111 + seed_ + static_cast<int>(epoch_));
    std::shuffle(order_.begin(), order_.end(), rng);
    ++epoch_;
  }
  next_rec_ = 0;
  chunk_.clear();
  cursor_ = 0;
}

void IndexedRecordIOSplit::ReadSpanAt(size_t global_ofs, char* dst,
                                      size_t size) {
  size_t k =
      std::upper_bound(file_start_.begin(), file_start_.end(), global_ofs) -
      file_start_.begin() - 1;
  size_t local = global_ofs - file_start_[k];
  while (size != 0) {
    DCT_CHECK_LT(k, files_.size()) << "record extends past data";
    if (open_file_ != k || open_stream_ == nullptr) {
      open_stream_.reset(FileSystem::GetInstance(files_[k].path)
                             ->OpenForRead(files_[k].path));
      open_file_ = k;
    }
    open_stream_->Seek(local);
    size_t take = std::min(size, files_[k].size - local);
    // record-exact span: prefetching past it would be discarded by the
    // next (possibly shuffled) seek anyway
    open_stream_->HintReadBound(local + take);
    open_stream_->ReadExact(dst, take);
    dst += take;
    size -= take;
    ++k;
    local = 0;
  }
}

bool IndexedRecordIOSplit::FillChunkBuffer(std::vector<char>* buf) {
  if (next_rec_ >= order_.size()) return false;
  buf->clear();
  size_t end_rec = std::min(order_.size(), next_rec_ + batch_size_);
  for (; next_rec_ < end_rec; ++next_rec_) {
    const auto& rec = index_[order_[next_rec_]];
    size_t old = buf->size();
    buf->resize(old + rec.second);
    ReadSpanAt(rec.first, buf->data() + old, rec.second);
  }
  return true;
}

bool IndexedRecordIOSplit::ExtractRecordAt(char* data, size_t valid,
                                           size_t* cursor, Blob* out) {
  return ExtractRecordIOFrame(data, valid, cursor, out, &assembled_);
}

bool IndexedRecordIOSplit::NextChunk(Blob* out) {
  if (!FillChunkBuffer(&chunk_)) return false;
  out->dptr = chunk_.data();
  out->size = chunk_.size();
  cursor_ = chunk_.size();
  return true;
}

bool IndexedRecordIOSplit::NextRecord(Blob* out) {
  while (true) {
    if (cursor_ < chunk_.size() &&
        ExtractRecordAt(chunk_.data(), chunk_.size(), &cursor_, out)) {
      return true;
    }
    if (!FillChunkBuffer(&chunk_)) return false;
    cursor_ = 0;
  }
}

// --------------------------------------------------------------------------
// CachedSplit
namespace {
constexpr uint64_t kCacheMagic = 0x44435443414348; // "DCTCACH"

uint64_t FingerprintHash(const std::string& s) {
  uint64_t h = 1469598103934665603ull;  // FNV-1a
  for (unsigned char c : s) {
    h ^= c;
    h *= 1099511628211ull;
  }
  return h;
}

void WriteU64(Stream* s, uint64_t v) {
  if (!serial::NativeIsLE()) v = serial::ByteSwap(v);
  s->Write(&v, 8);
}

bool ReadU64(Stream* s, uint64_t* v) {
  if (s->Read(v, 8) != 8) return false;
  if (!serial::NativeIsLE()) *v = serial::ByteSwap(*v);
  return true;
}
}  // namespace

CachedSplit::CachedSplit(InputSplit* base, RecordChunkSource* base_src,
                         const std::string& cache_file,
                         const std::string& fingerprint)
    : base_(base),
      base_src_(base_src),
      cache_file_(cache_file),
      fingerprint_(FingerprintHash(fingerprint)) {
  // a completed cache from an earlier run is replayed only when its header
  // matches this (uri, part, nsplit) — a stale cache for another partition
  // must not silently serve the wrong shard
  std::unique_ptr<SeekStream> probe(
      SeekStream::CreateForRead(cache_file_, /*allow_null=*/true));
  if (probe != nullptr) {
    uint64_t magic = 0, fp = 0;
    if (ReadU64(probe.get(), &magic) && magic == kCacheMagic &&
        ReadU64(probe.get(), &fp) && fp == fingerprint_) {
      cache_reader_ = std::move(probe);
      replaying_ = true;
    } else {
      std::remove(cache_file_.c_str());  // stale or foreign cache
    }
  }
}

CachedSplit::~CachedSplit() = default;

void CachedSplit::FinalizeCache() {
  // publish ONLY a complete first pass; a partial .tmp would silently
  // truncate the dataset for every later epoch and process
  if (cache_writer_ == nullptr) return;
  cache_writer_.reset();
  std::string tmp = cache_file_ + ".tmp";
  if (!write_complete_) {
    std::remove(tmp.c_str());
    return;
  }
  // injectable publish (fs_fault.h): a failed/torn rename surfaces as a
  // structured error with errno instead of a bare check string. The
  // DESTINATION is removed first: a torn half-copy keeps the 16-byte
  // magic+fingerprint probe valid, so leaving it would wedge every later
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

bool CachedSplit::FillChunkBuffer(std::vector<char>* buf) {
  if (replaying_) {
    uint64_t size;
    size_t n = cache_reader_->Read(&size, 8);
    if (n == 0) return false;
    DCT_CHECK_EQ(n, size_t(8))
        << "corrupt chunk cache (truncated header): " << cache_file_;
    if (!serial::NativeIsLE()) size = serial::ByteSwap(size);
    buf->resize(size);
    cache_reader_->ReadExact(buf->data(), size);
    return true;
  }
  if (!base_src_->FillChunkBuffer(buf)) {
    write_complete_ = true;
    FinalizeCache();
    return false;
  }
  if (cache_writer_ == nullptr) {
    cache_writer_.reset(Stream::Create(cache_file_ + ".tmp", "w"));
    WriteU64(cache_writer_.get(), kCacheMagic);
    WriteU64(cache_writer_.get(), fingerprint_);
  }
  uint64_t size = buf->size();
  if (!serial::NativeIsLE()) size = serial::ByteSwap(size);
  cache_writer_->Write(&size, 8);
  cache_writer_->Write(buf->data(), buf->size());
  return true;
}

bool CachedSplit::ExtractRecordAt(char* data, size_t valid, size_t* cursor,
                                  Blob* out) {
  return base_src_->ExtractRecordAt(data, valid, cursor, out);
}

void CachedSplit::BeforeFirst() {
  FinalizeCache();  // publishes only when the first pass completed
  write_complete_ = false;
  std::unique_ptr<SeekStream> probe(
      SeekStream::CreateForRead(cache_file_, /*allow_null=*/true));
  uint64_t magic = 0, fp = 0;
  if (probe != nullptr && ReadU64(probe.get(), &magic) &&
      magic == kCacheMagic && ReadU64(probe.get(), &fp) &&
      fp == fingerprint_) {
    cache_reader_ = std::move(probe);
    replaying_ = true;
  } else {
    replaying_ = false;
    cache_reader_.reset();
    base_->BeforeFirst();
  }
  chunk_.clear();
  cursor_ = 0;
}

bool CachedSplit::NextChunk(Blob* out) {
  if (!FillChunkBuffer(&chunk_)) return false;
  out->dptr = chunk_.data();
  out->size = chunk_.size();
  cursor_ = chunk_.size();
  return true;
}

bool CachedSplit::NextRecord(Blob* out) {
  while (true) {
    if (cursor_ < chunk_.size() &&
        ExtractRecordAt(chunk_.data(), chunk_.size(), &cursor_, out)) {
      return true;
    }
    if (!FillChunkBuffer(&chunk_)) return false;
    cursor_ = 0;
  }
}

void CachedSplit::ResetPartition(unsigned rank, unsigned nsplit) {
  // the cache is partition-specific; drop it and start over
  cache_writer_.reset();
  cache_reader_.reset();
  std::remove((cache_file_ + ".tmp").c_str());
  std::remove(cache_file_.c_str());
  replaying_ = false;
  write_complete_ = false;
  base_->ResetPartition(rank, nsplit);
  chunk_.clear();
  cursor_ = 0;
}

// --------------------------------------------------------------------------
// ShuffleSplit
ShuffleSplit::ShuffleSplit(InputSplit* base, unsigned part, unsigned nsplit,
                           unsigned num_shuffle_parts, int seed)
    : base_(base),
      part_(part),
      nsplit_(nsplit),
      num_shuffle_parts_(std::max(num_shuffle_parts, 1u)),
      seed_(seed) {
  BeforeFirst();
}

void ShuffleSplit::BeforeFirst() {
  order_.resize(num_shuffle_parts_);
  for (unsigned i = 0; i < num_shuffle_parts_; ++i) order_[i] = i;
  if (num_shuffle_parts_ > 1) {
    std::mt19937 rng(111 + seed_ + static_cast<int>(part_) * 997 +
                     static_cast<int>(epoch_));
    std::shuffle(order_.begin(), order_.end(), rng);
    ++epoch_;
    cur_ = 0;
    base_->ResetPartition(part_ * num_shuffle_parts_ + order_[0],
                          nsplit_ * num_shuffle_parts_);
  } else {
    base_->BeforeFirst();
  }
}

bool ShuffleSplit::AdvanceSubPart() {
  if (num_shuffle_parts_ <= 1 || cur_ + 1 >= num_shuffle_parts_) return false;
  ++cur_;
  base_->ResetPartition(part_ * num_shuffle_parts_ + order_[cur_],
                        nsplit_ * num_shuffle_parts_);
  return true;
}

bool ShuffleSplit::NextRecord(Blob* out) {
  while (!base_->NextRecord(out)) {
    if (!AdvanceSubPart()) return false;
  }
  return true;
}

bool ShuffleSplit::NextChunk(Blob* out) {
  while (!base_->NextChunk(out)) {
    if (!AdvanceSubPart()) return false;
  }
  return true;
}

void ShuffleSplit::ResetPartition(unsigned rank, unsigned nsplit) {
  part_ = rank;
  nsplit_ = nsplit;
  epoch_ = 0;
  BeforeFirst();
}

// --------------------------------------------------------------------------
PrefetchSplit::PrefetchSplit(InputSplit* base, RecordChunkSource* src,
                             size_t capacity)
    : base_(base), src_(src), pipe_(capacity) {}

PrefetchSplit::~PrefetchSplit() {
  if (current_ != nullptr) pipe_.Recycle(&current_);
  pipe_.Shutdown();
}

void PrefetchSplit::EnsureStarted() {
  if (started_) return;
  pipe_.Init(
      [this](Cell** cell) {
        if (*cell == nullptr) *cell = new Cell();
        (*cell)->cursor = 0;
        return src_->FillChunkBuffer(&(*cell)->data);
      },
      [this] { src_->SourceBeforeFirst(); });
  started_ = true;
}

void PrefetchSplit::BeforeFirst() {
  if (current_ != nullptr) pipe_.Recycle(&current_);
  if (started_) {
    pipe_.BeforeFirst();
  } else {
    // the pipeline starts producing from the source's CURRENT state
    // (PipelineIter::Init does not rewind), so an unstarted BeforeFirst
    // must walk the source chain synchronously — shuffled splits resample
    // their permutation here, which a pinned SetShuffleEpoch relies on
    src_->SourceBeforeFirst();
  }
}

bool PrefetchSplit::NextChunk(Blob* out) {
  EnsureStarted();
  if (current_ != nullptr) pipe_.Recycle(&current_);
  if (!pipe_.Next(&current_)) return false;
  out->dptr = current_->data.data();
  out->size = current_->data.size();
  current_->cursor = current_->data.size();
  return true;
}

bool PrefetchSplit::NextRecord(Blob* out) {
  EnsureStarted();
  while (true) {
    if (current_ != nullptr &&
        src_->ExtractRecordAt(current_->data.data(), current_->data.size(),
                              &current_->cursor, out)) {
      return true;
    }
    if (current_ != nullptr) pipe_.Recycle(&current_);
    if (!pipe_.Next(&current_)) return false;
  }
}

void PrefetchSplit::ResetPartition(unsigned rank, unsigned nsplit) {
  if (current_ != nullptr) pipe_.Recycle(&current_);
  pipe_.Shutdown();
  started_ = false;
  base_->ResetPartition(rank, nsplit);
}

InputSplit* InputSplit::Create(const std::string& uri, unsigned part,
                               unsigned nsplit, const std::string& type,
                               const std::string& index_uri, bool shuffle,
                               int seed, size_t batch_size,
                               bool recurse_directories, bool threaded,
                               const std::string& cache_file,
                               unsigned shuffle_parts) {
  DCT_CHECK(shuffle == false || type == "indexed_recordio")
      << "record shuffle requires type=indexed_recordio "
         "(use shuffle_parts for coarse shuffling)";
  DCT_CHECK(cache_file.empty() || shuffle_parts <= 1)
      << "cache_file cannot be combined with shuffle_parts: sub-part resets "
         "would invalidate the cache every epoch";
  if (uri == "stdin") {
    // single-pipe fallback (reference src/io.cc:94-96): no partitioning,
    // no cache, no prefetch wrapper
    DCT_CHECK(type == "text") << "stdin input must be type=text";
    DCT_CHECK(part == 0 && nsplit == 1) << "stdin cannot be partitioned";
    DCT_CHECK(cache_file.empty() && shuffle_parts <= 1)
        << "stdin cannot be cached or shuffled (it cannot be rewound)";
    return new SingleFileSplit(uri);
  }
  InputSplit* split;
  RecordChunkSource* src;
  if (type == "text") {
    auto* b = new LineSplit(uri, part, nsplit, recurse_directories);
    split = b;
    src = b;
  } else if (type == "recordio") {
    auto* b = new RecordIOSplit(uri, part, nsplit, recurse_directories);
    split = b;
    src = b;
  } else if (type == "indexed_recordio") {
    DCT_CHECK(!index_uri.empty())
        << "indexed_recordio requires an index uri";
    auto* b = new IndexedRecordIOSplit(uri, index_uri, part, nsplit,
                                       batch_size, shuffle, seed,
                                       recurse_directories);
    split = b;
    src = b;
  } else {
    throw Error("unknown input split type: " + type);
  }
  if (!cache_file.empty()) {
    // per-part cache naming for raw (non-URISpec) callers, matching the
    // URISpec `.splitN.partK` convention (reference uri_spec.h:42-57)
    std::string cf = cache_file;
    if (nsplit != 1 && cf.find(".split") == std::string::npos) {
      cf += ".split" + std::to_string(nsplit) + ".part" +
            std::to_string(part);
    }
    std::string fingerprint = uri + "|" + std::to_string(part) + "|" +
                              std::to_string(nsplit) + "|" + type;
    auto* c = new CachedSplit(split, src, cf, fingerprint);
    split = c;
    src = c;
  }
  if (threaded) {
    split = new PrefetchSplit(split, src, 2);
  }
  if (shuffle_parts > 1) {
    split = new ShuffleSplit(split, part, nsplit, shuffle_parts, seed);
  }
  return split;
}

}  // namespace dct
