// C ABI for ctypes binding (dmlc_core_tpu/io/native.py).
//
// The reference exposes C++ headers directly; a TPU-native rebuild needs a
// stable C surface instead because the Python/JAX layer binds via ctypes
// (pybind11 is not part of the toolchain — see repo README). Conventions:
//   - every call returns 0 on success, -1 on error; dct_last_error() returns
//     the thread-local message
//   - handles are opaque pointers; *_free releases
//   - blob/rowblock pointers remain valid until the next call on the same
//     handle (matching reference DataIter Value() semantics, data.h:55-66)
//
// MACHINE-CHECKED PARITY (scripts/analyze.py Pass 4, doc/analysis.md):
// every extern-"C" function below is diffed against the ctypes table in
// dmlc_core_tpu/io/native.py (explicit restype, arity, pointer-ness,
// scalar widths), and every `typedef struct` is diffed field-by-field
// against its ctypes Structure mirror AND proven byte-identical by a
// compile-time sizeof/offsetof probe. Adding a function or struct field
// here without updating the binding fails `make analyze` — keep
// declarations in the plain shapes the extractor parses (one `dct_*`
// definition per `extern "C"` symbol, `typedef struct { ... } name;`).
#include <cstring>
#include <string>

#include "batcher.h"
#include "bf16.h"
#include "criteo_hash.h"
#include "csr_rec.h"
#include "dense_rec.h"
#include "filesys.h"
#include "fs_fault.h"
#include "hdfs_filesys.h"
#include "http.h"
#include "input_split.h"
#include "nnz_bucket.h"
#include "parser.h"
#include "recordio.h"
#include "retry.h"
#include "rowblock.h"
#include "stream.h"
#include "telemetry.h"

namespace {
thread_local std::string g_last_error;

template <typename F>
int Guard(F&& fn) {
  try {
    fn();
    return 0;
  } catch (const std::exception& e) {
    g_last_error = e.what();
    return -1;
  } catch (...) {
    g_last_error = "unknown C++ exception";
    return -1;
  }
}
}  // namespace



typedef struct {
  uint64_t num_rows;
  uint64_t nnz;
  const uint64_t* offset;  // num_rows + 1
  const float* label;      // num_rows
  const float* weight;     // num_rows or NULL
  const uint64_t* qid;     // num_rows or NULL
  const uint32_t* field;   // nnz or NULL
  const void* index;       // nnz entries, dtype per index_is_64
  const float* value;      // nnz or NULL (implicit 1.0)
  uint64_t max_index;
  uint32_t max_field;
  int32_t index_is_64;
  // typed csv values (value_dtype 0=f32/1=i32/2=i64); for non-zero dtypes
  // `value` is NULL and the matching typed pointer holds nnz entries
  const int32_t* value_i32;
  const int64_t* value_i64;
  int32_t value_dtype;
} dct_rowblock_t;

namespace {
struct ParserHandle {
  dct::Parser<uint32_t>* p32 = nullptr;
  dct::Parser<uint64_t>* p64 = nullptr;

  ~ParserHandle() {
    delete p32;
    delete p64;
  }

  // dct_rowblock_t is exactly the RowBlockView shape: the parser's view
  // lane (Parser::NextBlockView) fills it with NO intermediate container —
  // on a shard-cache replay the pointers go straight into the mmap.
  template <typename T>
  static void FillView(const dct::RowBlockView<T>& v, dct_rowblock_t* out) {
    out->num_rows = v.num_rows;
    out->nnz = v.nnz;
    out->offset = v.offset;
    out->label = v.label;
    out->weight = v.weight;
    out->qid = v.qid;
    out->field = v.field;
    out->index = v.index;
    out->value = v.value;
    out->max_index = v.max_index;
    out->max_field = v.max_field;
    out->index_is_64 = sizeof(T) == 8 ? 1 : 0;
    out->value_i32 = v.value_i32;
    out->value_i64 = v.value_i64;
    out->value_dtype = v.value_dtype;
  }
};
}  // namespace

extern "C" {

const char* dct_last_error() { return g_last_error.c_str(); }

// Rotate the WebHDFS delegation token at runtime (long-running jobs renew
// Hadoop tokens mid-flight); empty string reverts to user.name auth.
int dct_webhdfs_set_delegation_token(const char* token) {
  return Guard([&] {
    dct::WebHdfsFileSystem::GetInstance()->set_delegation_token(
        token == nullptr ? "" : token);
  });
}

// Inject/rotate the verbatim Authorization header for WebHDFS (the SPNEGO
// hook: an external kinit-based helper supplies "Negotiate <token>");
// empty string reverts to user.name / delegation auth.
int dct_webhdfs_set_auth_header(const char* header) {
  return Guard([&] {
    dct::WebHdfsFileSystem::GetInstance()->set_auth_header(
        header == nullptr ? "" : header);
  });
}

// Publish the TLS-terminating helper's "host:port" address to the native
// https router (http.h SetTlsProxyOverride). The binding calls this instead
// of mutating DCT_TLS_PROXY: setenv after native request threads exist
// races their getenv (glibc UB). Empty/NULL clears back to the env fallback.
int dct_set_tls_proxy(const char* addr) {
  return Guard(
      [&] { dct::SetTlsProxyOverride(addr == nullptr ? "" : addr); });
}

// --------------------------------------------------------------- telemetry --
// The unified telemetry plane (cpp/src/telemetry.h). dct_telemetry_snapshot
// returns the versioned JSON document (schema doc/observability.md; caller
// frees with dct_str_free) that dmlc_core_tpu.telemetry.snapshot() merges
// and the tracker's /metrics scrape serves — one snapshot, three surfaces.
int dct_telemetry_snapshot(char** out) {
  return Guard([&] {
    // touch the io-stats singleton so its counters are registered even in
    // processes that have not issued a remote request yet: the snapshot's
    // metric SET must be stable, not dependent on call order
    dct::io::GlobalIoStats();
    const std::string s = dct::telemetry::SnapshotJson();
    char* buf = new char[s.size() + 1];
    std::memcpy(buf, s.c_str(), s.size() + 1);
    *out = buf;
  });
}

// Zero every registered metric (owned and adopted-external alike) and
// drop the buffered span ring — one reset restores the whole plane.
int dct_telemetry_reset() {
  return Guard([&] {
    dct::telemetry::Reset();
    dct::telemetry::TraceReset();
  });
}

// Runtime override of the DMLC_TELEMETRY gate for timed spans (counters
// keep counting either way — they are cheaper than the branch).
int dct_telemetry_enable(int on) {
  return Guard([&] { dct::telemetry::SetEnabled(on != 0); });
}

// The native span-ring trace document (telemetry.h TraceJson; schema
// doc/observability.md "Distributed tracing"). Steady-clock timestamps
// plus the per-process (wall, steady) anchor pair — the Python half
// (telemetry.trace_json / the tracker's /trace) merges it onto the
// wall clock. Caller frees with dct_str_free.
int dct_trace_snapshot(char** out) {
  return Guard([&] {
    const std::string s = dct::telemetry::TraceJson();
    char* buf = new char[s.size() + 1];
    std::memcpy(buf, s.c_str(), s.size() + 1);
    *out = buf;
  });
}

// Drop every buffered span and restart the trace sequence.
int dct_trace_reset() {
  return Guard([&] { dct::telemetry::TraceReset(); });
}

// Best-effort native flight-recorder dump (telemetry.h FlightDump):
// writes trace + metrics to $DMLC_TRACE_DUMP when set. Returns 0 with
// *written = 1 only when a dump file actually landed.
int dct_flight_dump(const char* reason, int* written) {
  return Guard([&] {
    *written = dct::telemetry::FlightDump(reason) ? 1 : 0;
  });
}

// The native pulse (telemetry.h "pulse"): a thread that naps 20 ms at a
// time and records how late it woke, with no interpreter lock to wait for.
// Start and stop are idempotent; the Python half starts it beside its own
// pulse (dmlc_core_tpu.telemetry.pulse_start).
int dct_pulse_start() {
  return Guard([&] { dct::telemetry::PulseStart(); });
}

int dct_pulse_stop() {
  return Guard([&] { dct::telemetry::PulseStop(); });
}

// The largest lateness of the native pulse between `since_us_ago` and
// `until_us_ago` microseconds before now, and how many ticks lay there.
int dct_pulse_max_late_us(uint64_t since_us_ago, uint64_t until_us_ago,
                          uint64_t* max_late_us, uint64_t* ticks) {
  return Guard([&] {
    *max_late_us =
        dct::telemetry::PulseMaxLateUs(since_us_ago, until_us_ago, ticks);
  });
}

// ----------------------------------------------------------- io resilience --
// Mirror of dct::io::IoStats (retry.h) — process-global remote-I/O
// resilience counters, surfaced in Python as io_stats() (alongside the
// PR-1 dct_parser_pipeline_stats).
typedef struct {
  uint64_t requests;          // HTTP requests sent
  uint64_t retries;           // backoff sleeps taken
  uint64_t backoff_ms_total;  // total milliseconds slept in backoff
  uint64_t timeouts;          // per-attempt timeout expiries
  uint64_t faults_injected;   // DMLC_IO_FAULT_PLAN firings
  uint64_t giveups;           // retry loops that exhausted their budget
  uint64_t deadline_exhausted;  // giveups caused by the deadline
} dct_io_retry_stats_t;

int dct_io_retry_stats(dct_io_retry_stats_t* out) {
  return Guard([&] {
    const dct::io::IoStats& st = dct::io::GlobalIoStats();
    out->requests = st.requests.load(std::memory_order_relaxed);
    out->retries = st.retries.load(std::memory_order_relaxed);
    out->backoff_ms_total =
        st.backoff_ms_total.load(std::memory_order_relaxed);
    out->timeouts = st.timeouts.load(std::memory_order_relaxed);
    out->faults_injected =
        st.faults_injected.load(std::memory_order_relaxed);
    out->giveups = st.giveups.load(std::memory_order_relaxed);
    out->deadline_exhausted =
        st.deadline_exhausted.load(std::memory_order_relaxed);
  });
}

int dct_io_stats_reset() {
  return Guard([&] { dct::io::ResetIoStats(); });
}

// Install/replace the deterministic fault-injection plan evaluated inside
// the native HTTP client (retry.h grammar, e.g.
// "reset:every=3;stall:every=5,ms=80;5xx:every=7,status=503"); empty/NULL
// clears. The explicit setter is the race-free alternative to mutating
// DMLC_IO_FAULT_PLAN after native request threads exist (same rule as
// dct_set_tls_proxy).
int dct_io_set_fault_plan(const char* plan) {
  return Guard(
      [&] { dct::io::SetFaultPlan(plan == nullptr ? "" : plan); });
}

// Override the per-attempt socket timeout (connect/recv/send bound,
// milliseconds); <=0 reverts to DMLC_IO_TIMEOUT_MS / the 60 s default.
int dct_io_set_timeout_ms(int ms) {
  return Guard([&] { dct::io::SetIoTimeoutMs(ms); });
}

// Install/replace the LOCAL-filesystem fault plan (fs_fault.h grammar,
// e.g. "write:fault=enospc,every=3;rename:fault=torn_rename,p=0.5") —
// evaluated inside the local stream/shard-cache syscall wrappers, below
// every mock. Empty/NULL clears; an explicit clear beats
// DMLC_FS_FAULT_PLAN (same race-free-setter rule as the io plan).
int dct_fs_set_fault_plan(const char* plan) {
  return Guard(
      [&] { dct::fsio::SetFsFaultPlan(plan == nullptr ? "" : plan); });
}

// ---------------------------------------------------------------- streams --
typedef void* dct_stream_t;

int dct_stream_create(const char* uri, const char* mode, dct_stream_t* out) {
  return Guard([&] { *out = dct::Stream::Create(uri, mode); });
}

int dct_stream_read(dct_stream_t h, void* buf, size_t size, size_t* nread) {
  return Guard(
      [&] { *nread = static_cast<dct::Stream*>(h)->Read(buf, size); });
}

int dct_stream_write(dct_stream_t h, const void* buf, size_t size) {
  return Guard([&] { static_cast<dct::Stream*>(h)->Write(buf, size); });
}

int dct_stream_free(dct_stream_t h) {
  // Finish() first so buffered-write failures reach the caller; the
  // destructor's own Finish is a no-op afterwards (finished_ latch), so the
  // handle is freed even on error.
  auto* s = static_cast<dct::Stream*>(h);
  if (s == nullptr) return 0;
  int rc = Guard([&] { s->Finish(); });
  delete s;
  return rc;
}

// ------------------------------------------------------------- filesystem --
// Lists to a newline-separated "path\tsize\ttype" string (caller frees with
// dct_str_free).
int dct_fs_list(const char* uri, int recursive, char** out) {
  return Guard([&] {
    dct::URI u(uri);
    dct::FileSystem* fs = dct::FileSystem::GetInstance(u);
    std::vector<dct::FileInfo> infos;
    if (recursive) {
      fs->ListDirectoryRecursive(u, &infos);
    } else {
      fs->ListDirectory(u, &infos);
    }
    std::string s;
    for (const auto& info : infos) {
      s += info.path.Str();
      s += '\t';
      s += std::to_string(info.size);
      s += '\t';
      s += info.type == dct::FileType::kDirectory ? 'd' : 'f';
      s += '\n';
    }
    char* buf = new char[s.size() + 1];
    std::memcpy(buf, s.c_str(), s.size() + 1);
    *out = buf;
  });
}

int dct_fs_path_info(const char* uri, size_t* size, int* is_dir) {
  return Guard([&] {
    dct::URI u(uri);
    dct::FileInfo info = dct::FileSystem::GetInstance(u)->GetPathInfo(u);
    *size = info.size;
    *is_dir = info.type == dct::FileType::kDirectory ? 1 : 0;
  });
}

int dct_str_free(char* s) {
  delete[] s;
  return 0;
}

// ------------------------------------------------------------ input split --
typedef void* dct_split_t;

int dct_split_create(const char* uri, unsigned part, unsigned nsplit,
                     const char* type, int threaded, dct_split_t* out) {
  return Guard([&] {
    *out = dct::InputSplit::Create(uri, part, nsplit, type, "", false, 0, 256,
                                   false, threaded != 0);
  });
}

// full-option factory: indexed recordio, shuffle, caching, coarse shuffle
int dct_split_create_ex(const char* uri, const char* index_uri, unsigned part,
                        unsigned nsplit, const char* type, int threaded,
                        int shuffle, int seed, size_t batch_size,
                        const char* cache_file, unsigned shuffle_parts,
                        int recurse, dct_split_t* out) {
  return Guard([&] {
    *out = dct::InputSplit::Create(
        uri, part, nsplit, type, index_uri == nullptr ? "" : index_uri,
        shuffle != 0, seed, batch_size, recurse != 0, threaded != 0,
        cache_file == nullptr ? "" : cache_file, shuffle_parts);
  });
}

int dct_split_next_record(dct_split_t h, const void** data, size_t* size,
                          int* has) {
  return Guard([&] {
    dct::InputSplit::Blob blob;
    *has = static_cast<dct::InputSplit*>(h)->NextRecord(&blob) ? 1 : 0;
    *data = blob.dptr;
    *size = blob.size;
  });
}

int dct_split_next_chunk(dct_split_t h, const void** data, size_t* size,
                         int* has) {
  return Guard([&] {
    dct::InputSplit::Blob blob;
    *has = static_cast<dct::InputSplit*>(h)->NextChunk(&blob) ? 1 : 0;
    *data = blob.dptr;
    *size = blob.size;
  });
}

int dct_split_before_first(dct_split_t h) {
  return Guard([&] { static_cast<dct::InputSplit*>(h)->BeforeFirst(); });
}

int dct_split_reset_partition(dct_split_t h, unsigned part, unsigned nsplit) {
  return Guard(
      [&] { static_cast<dct::InputSplit*>(h)->ResetPartition(part, nsplit); });
}

int dct_split_total_size(dct_split_t h, size_t* out) {
  return Guard(
      [&] { *out = static_cast<dct::InputSplit*>(h)->GetTotalSize(); });
}

int dct_split_hint_chunk_size(dct_split_t h, size_t bytes) {
  return Guard(
      [&] { static_cast<dct::InputSplit*>(h)->HintChunkSize(bytes); });
}

int dct_split_free(dct_split_t h) {
  return Guard([&] { delete static_cast<dct::InputSplit*>(h); });
}

// --------------------------------------------------------------- recordio --
typedef void* dct_recordio_writer_t;
typedef void* dct_recordio_reader_t;

namespace {
struct WriterHandle {
  dct::Stream* stream;
  dct::RecordIOWriter* writer;
};
struct ReaderHandle {
  dct::Stream* stream;
  dct::RecordIOReader* reader;
  std::string buf;
};
}  // namespace

int dct_recordio_writer_create(const char* uri, dct_recordio_writer_t* out) {
  return Guard([&] {
    auto* h = new WriterHandle();
    h->stream = dct::Stream::Create(uri, "w");
    h->writer = new dct::RecordIOWriter(h->stream);
    *out = h;
  });
}

int dct_recordio_write(dct_recordio_writer_t h, const void* data,
                       size_t size) {
  return Guard([&] {
    static_cast<WriterHandle*>(h)->writer->WriteRecord(data, size);
  });
}

int dct_recordio_writer_free(dct_recordio_writer_t h) {
  return Guard([&] {
    auto* wh = static_cast<WriterHandle*>(h);
    delete wh->writer;
    delete wh->stream;
    delete wh;
  });
}

int dct_recordio_reader_create(const char* uri, dct_recordio_reader_t* out) {
  return Guard([&] {
    auto* h = new ReaderHandle();
    h->stream = dct::Stream::Create(uri, "r");
    h->reader = new dct::RecordIOReader(h->stream);
    *out = h;
  });
}

int dct_recordio_read(dct_recordio_reader_t h, const void** data, size_t* size,
                      int* has) {
  return Guard([&] {
    auto* rh = static_cast<ReaderHandle*>(h);
    *has = rh->reader->NextRecord(&rh->buf) ? 1 : 0;
    *data = rh->buf.data();
    *size = rh->buf.size();
  });
}

int dct_recordio_reader_free(dct_recordio_reader_t h) {
  return Guard([&] {
    auto* rh = static_cast<ReaderHandle*>(h);
    delete rh->reader;
    delete rh->stream;
    delete rh;
  });
}

// ----------------------------------------------------------------- parser --
typedef void* dct_parser_t;




// chunks_in_flight bounds the threaded pipeline's outstanding chunks
// (0 = auto-size to the worker count; parser.cc DefaultChunksInFlight).
// cache_dir/cache_mode (NULL/"" = URI sugar + env only) opt into the
// transcoding shard cache (cpp/src/shard_cache.h, doc/caching.md):
// cache_dir names the shard directory, cache_mode is never|auto|refresh.
int dct_parser_create_ex(const char* uri, unsigned part, unsigned npart,
                         const char* format, int nthread, int threaded,
                         int index64, int chunks_in_flight,
                         const char* cache_dir, const char* cache_mode,
                         dct_parser_t* out) {
  return Guard([&] {
    const std::string cdir = cache_dir == nullptr ? "" : cache_dir;
    const std::string cmode = cache_mode == nullptr ? "" : cache_mode;
    auto* h = new ParserHandle();
    if (index64 != 0) {
      h->p64 = dct::Parser<uint64_t>::Create(uri, part, npart, format, nthread,
                                             threaded != 0, chunks_in_flight,
                                             cdir, cmode);
    } else {
      h->p32 = dct::Parser<uint32_t>::Create(uri, part, npart, format, nthread,
                                             threaded != 0, chunks_in_flight,
                                             cdir, cmode);
    }
    *out = h;
  });
}

int dct_parser_create(const char* uri, unsigned part, unsigned npart,
                      const char* format, int nthread, int threaded,
                      int index64, dct_parser_t* out) {
  return dct_parser_create_ex(uri, part, npart, format, nthread, threaded,
                              index64, 0, nullptr, nullptr, out);
}

int dct_parser_next_block(dct_parser_t h, dct_rowblock_t* out, int* has) {
  return Guard([&] {
    auto* ph = static_cast<ParserHandle*>(h);
    // the view lane: pointers into the producer's storage (a container's
    // vectors, or the shard cache's mmap — zero copies either way),
    // valid until the next call on this handle
    if (ph->p64 != nullptr) {
      dct::RowBlockView<uint64_t> v;
      *has = ph->p64->NextBlockView(&v) ? 1 : 0;
      if (*has) ParserHandle::FillView(v, out);
    } else {
      dct::RowBlockView<uint32_t> v;
      *has = ph->p32->NextBlockView(&v) ? 1 : 0;
      if (*has) ParserHandle::FillView(v, out);
    }
  });
}

int dct_parser_before_first(dct_parser_t h) {
  return Guard([&] {
    auto* ph = static_cast<ParserHandle*>(h);
    if (ph->p64 != nullptr) {
      ph->p64->BeforeFirst();
    } else {
      ph->p32->BeforeFirst();
    }
  });
}

int dct_parser_bytes_read(dct_parser_t h, size_t* out) {
  return Guard([&] {
    auto* ph = static_cast<ParserHandle*>(h);
    *out = ph->p64 != nullptr ? ph->p64->BytesRead() : ph->p32->BytesRead();
  });
}

// Mirror of dct::ParsePipelineStats (parser.h) — occupancy/stall counters
// of the multi-chunk parse pipeline, for an operator's introspection.
// APPEND-ONLY contract: the struct is caller-allocated and versionless
// (the in-tree ctypes mirror in dmlc_core_tpu/io/native.py ships in
// lockstep with this .so); new fields go at the END only, and out-of-tree
// consumers must rebuild against the matching header.
typedef struct {
  uint64_t chunks_read;
  uint64_t blocks_delivered;
  uint64_t reader_waits;
  uint64_t worker_waits;
  uint64_t consumer_waits;
  uint64_t inflight_now;
  uint64_t inflight_peak;
  uint64_t inflight_sum;
  uint64_t capacity;
  uint64_t workers;
  uint64_t simd_tier;  // structural-scan lane: 0 scalar, 1 swar, 2 sse2,
                       // 3 avx2 (simd_scan.h SimdTier)
} dct_parse_pipeline_stats_t;

// *has = 0 when the handle carries no pipeline (threaded=0 parsers).
int dct_parser_pipeline_stats(dct_parser_t h, dct_parse_pipeline_stats_t* out,
                              int* has) {
  return Guard([&] {
    auto* ph = static_cast<ParserHandle*>(h);
    dct::ParsePipelineStats s;
    const bool ok = ph->p64 != nullptr ? ph->p64->GetPipelineStats(&s)
                                       : ph->p32->GetPipelineStats(&s);
    *has = ok ? 1 : 0;
    if (ok) {
      out->chunks_read = s.chunks_read;
      out->blocks_delivered = s.blocks_delivered;
      out->reader_waits = s.reader_waits;
      out->worker_waits = s.worker_waits;
      out->consumer_waits = s.consumer_waits;
      out->inflight_now = s.inflight_now;
      out->inflight_peak = s.inflight_peak;
      out->inflight_sum = s.inflight_sum;
      out->capacity = s.capacity;
      out->workers = s.workers;
      out->simd_tier = s.simd_tier;
    }
  });
}

// Pin the shuffle permutation the next before_first samples; *supported = 0
// when nothing in the chain shuffles (resume is order-safe regardless).
int dct_parser_set_epoch(dct_parser_t h, unsigned epoch, int32_t* supported) {
  return Guard([&] {
    auto* ph = static_cast<ParserHandle*>(h);
    const bool ok = ph->p64 != nullptr ? ph->p64->SetShuffleEpoch(epoch)
                                       : ph->p32->SetShuffleEpoch(epoch);
    *supported = ok ? 1 : 0;
  });
}

int dct_parser_free(dct_parser_t h) {
  return Guard([&] { delete static_cast<ParserHandle*>(h); });
}

// The names of the native parser-format registry, comma-joined: the one
// list of formats (Python's data.Parser.create reads it, so a format is
// registered once, in RegisterBuiltinParsers).
int dct_parser_format_names(char** out) {
  return Guard([&] {
    std::string s;
    for (const std::string& name :
         dct::Registry<dct::ParserFactoryReg<uint32_t>>::Get()
             ->ListAllNames()) {
      s += (s.empty() ? "" : ",") + name;
    }
    char* buf = new char[s.size() + 1];
    std::memcpy(buf, s.c_str(), s.size() + 1);
    *out = buf;
  });
}

// Render the native parser-format registry as markdown (name, description,
// argument tables from each format's reflection params) — the doc lane's
// source of truth (scripts/gendoc.py; reference doc/parameter.md documents
// the same surface by hand).
int dct_parser_formats_doc(char** out) {
  return Guard([&] {
    auto* reg = dct::Registry<dct::ParserFactoryReg<uint32_t>>::Get();
    std::string s;
    for (const std::string& name : reg->ListAllNames()) {
      const auto* e = reg->Find(name);
      s += "## format `" + e->name + "`\n\n" + e->description + "\n\n";
      if (!e->arguments.empty()) {
        s += "| argument | type | description |\n|---|---|---|\n";
        for (const auto& a : e->arguments) {
          s += "| `" + a.name + "` | " + a.type_info_str + " | " +
               a.description + " |\n";
        }
        s += "\n";
      }
    }
    char* buf = new char[s.size() + 1];
    std::memcpy(buf, s.c_str(), s.size() + 1);
    *out = buf;
  });
}

// ---------------------------------------------------------------- batcher --
// Native static-shape batch assembly (batcher.h): Python asks for the next
// batch's shape via next_meta, allocates numpy arrays, and fill_* writes
// them in one GIL-free pass.
typedef void* dct_batcher_t;

int dct_batcher_create(const char* uri, unsigned part, unsigned npart,
                       const char* format, int nthread, int threaded,
                       uint64_t batch_rows, uint32_t num_shards,
                       uint64_t min_nnz_bucket, dct_batcher_t* out) {
  return Guard([&] {
    auto* p = dct::Parser<uint32_t>::Create(uri, part, npart, format, nthread,
                                            threaded != 0);
    *out = new dct::PaddedBatcher(p, batch_rows, num_shards, min_nnz_bucket);
  });
}

// The rule both batchers size a CSR batch's nnz capacity by (nnz_bucket.h),
// exported so a test can hold the Python statement of it equal.
int dct_nnz_bucket(uint64_t n, uint64_t floor, uint64_t* out) {
  return Guard([&] { *out = dct::NnzBucket(n, floor); });
}

// The rule for a part's short last batch (nnz_bucket.h TailRung), exported
// for the same test.
int dct_tail_rung(uint64_t own, uint64_t before, uint64_t take,
                  uint64_t batch_rows, uint64_t* out) {
  return Guard([&] { *out = dct::TailRung(own, before, take, batch_rows); });
}

// The rule the `criteo` format hashes a cell by (criteo_hash.h), exported so
// a test can hold the Python statements of it equal.
int dct_criteo_id(uint32_t column, const char* cell, uint64_t len,
                  int hash_bits, uint64_t* out) {
  return Guard([&] {
    DCT_CHECK(hash_bits >= 1 && hash_bits <= 63) << "hash_bits out of range";
    *out = dct::CriteoFold(dct::CriteoHash64(column, cell, len), hash_bits);
  });
}

int dct_batcher_next_meta(dct_batcher_t h, uint64_t* take, uint64_t* bucket,
                          uint64_t* max_index, int* has_qid, int* has_field,
                          int* has) {
  return Guard([&] {
    *has = static_cast<dct::PaddedBatcher*>(h)->NextMeta(
               take, bucket, max_index, has_qid, has_field)
               ? 1
               : 0;
  });
}

// qid/field may be NULL to skip (reference RowBlock carries both,
// data.h:174-236; here they continue into the device layout)
int dct_batcher_fill_csr(dct_batcher_t h, int32_t* row, int32_t* col,
                         float* val, float* label, float* weight,
                         int32_t* nrows, int32_t* qid, int32_t* field) {
  return Guard([&] {
    static_cast<dct::PaddedBatcher*>(h)->FillCSR(row, col, val, label, weight,
                                                 nrows, qid, field);
  });
}

// x_dtype: 0 = float32, 1 = bfloat16 (uint16 storage) — bf16 emission halves
// host fill and host->HBM transfer bytes for the dense (MXU) layout
int dct_batcher_fill_dense(dct_batcher_t h, void* x, int32_t x_dtype,
                           uint64_t num_features, float* label, float* weight,
                           int32_t* nrows, int32_t* qid) {
  return Guard([&] {
    static_cast<dct::PaddedBatcher*>(h)->FillDense(x, x_dtype, num_features,
                                                   label, weight, nrows, qid);
  });
}

// Fused shard-major fill (batcher.h FillPacked): big [D, kb, bucket] int32,
// aux [D, ka, R] int32, optional separate bf16 val plane [D, bucket] when
// val_dtype == 1 (val may be NULL for val_dtype == 0). One pass writes the
// transfer packs the device lane ships as-is.
int dct_batcher_fill_packed(dct_batcher_t h, int32_t* big, int32_t kb,
                            void* val, int32_t val_dtype, int32_t* aux,
                            int32_t ka, int32_t* nrows) {
  return Guard([&] {
    static_cast<dct::PaddedBatcher*>(h)->FillPacked(big, kb, val, val_dtype,
                                                    aux, ka, nrows);
  });
}

int dct_batcher_fill_dense_packed(dct_batcher_t h, void* x, int32_t x_dtype,
                                  uint64_t num_features, int32_t* aux,
                                  int32_t ka, int32_t* nrows) {
  return Guard([&] {
    static_cast<dct::PaddedBatcher*>(h)->FillDensePacked(
        x, x_dtype, num_features, aux, ka, nrows);
  });
}

int dct_batcher_before_first(dct_batcher_t h) {
  return Guard([&] { static_cast<dct::PaddedBatcher*>(h)->BeforeFirst(); });
}

int dct_batcher_set_epoch(dct_batcher_t h, unsigned epoch,
                          int32_t* supported) {
  return Guard([&] {
    *supported =
        static_cast<dct::PaddedBatcher*>(h)->SetShuffleEpoch(epoch) ? 1 : 0;
  });
}

int dct_batcher_bytes_read(dct_batcher_t h, size_t* out) {
  return Guard(
      [&] { *out = static_cast<dct::PaddedBatcher*>(h)->BytesRead(); });
}

int dct_batcher_batch_nnz(dct_batcher_t h, uint64_t* out) {
  return Guard(
      [&] { *out = static_cast<dct::PaddedBatcher*>(h)->BatchNnz(); });
}

// The distinct-column lists of the batch fill_packed last wrote
// (col_slots.h): capacity and count first, then the [D, cap] lists.
int dct_batcher_cols_meta(dct_batcher_t h, uint64_t* cap,
                          uint64_t* distinct, int* tail_lifted) {
  return Guard([&] {
    auto* b = static_cast<dct::PaddedBatcher*>(h);
    *cap = b->ColsCapacity();
    *distinct = b->ColsDistinct();
    *tail_lifted = b->TailLifted() ? 1 : 0;
  });
}

// Key-range owners of the columns (col_slots.h "Owners"), before the first
// batch; and the fullest owner's count of the last batch's distinct columns.
int dct_batcher_set_col_owners(dct_batcher_t h, uint32_t owners,
                               uint64_t range) {
  return Guard([&] {
    static_cast<dct::PaddedBatcher*>(h)->SetColOwners(owners, range);
  });
}

int dct_batcher_cols_owner_max(dct_batcher_t h, uint64_t* out) {
  return Guard(
      [&] { *out = static_cast<dct::PaddedBatcher*>(h)->ColsOwnerMax(); });
}

int dct_batcher_fill_cols(dct_batcher_t h, int32_t* cols, uint64_t cap) {
  return Guard(
      [&] { static_cast<dct::PaddedBatcher*>(h)->FillCols(cols, cap); });
}

// The dedupe both batchers run (col_slots.h), exported so a test can hold
// the Python statement of it equal: col is [D, stride] with n[d] real
// entries in shard d and becomes the slot plane; cols takes the [D, *cap]
// lists and must hold D * owners * NnzBucket(max n, floor) entries.
int dct_col_slots(int32_t* col, const uint64_t* n, uint32_t num_shards,
                        uint64_t stride, uint64_t floor, uint32_t owners,
                        uint64_t range, int32_t* cols, uint64_t* cap,
                        uint64_t* distinct) {
  return Guard([&] {
    dct::ColSlots slots;
    slots.SetOwners(owners, range);
    slots.Run(col, stride, n, num_shards);
    *cap = slots.Capacity(floor);
    *distinct = slots.Distinct();
    slots.Lay(col, stride, n, *cap);
    slots.Write(cols, *cap);
  });
}

int dct_batcher_free(dct_batcher_t h) {
  return Guard([&] { delete static_cast<dct::PaddedBatcher*>(h); });
}

// -------------------------------------------------------------- dense rec --
// Zero-parse dense ingest (dense_rec.h): records carry [rows, F] matrices
// in device layout, so fill is record framing + bulk memcpy.
typedef void* dct_denserec_t;

int dct_denserec_create(const char* uri, unsigned part, unsigned npart,
                        uint64_t batch_rows, uint32_t num_shards,
                        dct_denserec_t* out) {
  return Guard([&] {
    *out = new dct::DenseRecBatcher(uri, part, npart, batch_rows, num_shards);
  });
}

int dct_denserec_meta(dct_denserec_t h, uint64_t* num_features,
                      int32_t* x_dtype, int32_t* has_weight) {
  return Guard([&] {
    int dt = 0, hw = 0;
    static_cast<dct::DenseRecBatcher*>(h)->Meta(num_features, &dt, &hw);
    *x_dtype = dt;
    *has_weight = hw;
  });
}

int dct_denserec_fill(dct_denserec_t h, void* x, int32_t out_dtype,
                      uint64_t x_features, float* label, float* weight,
                      int32_t* nrows, uint64_t* take) {
  return Guard([&] {
    *take = static_cast<dct::DenseRecBatcher*>(h)->Fill(
        x, out_dtype, x_features, label, weight, nrows);
  });
}

int dct_denserec_fill_packed(dct_denserec_t h, void* x, int32_t out_dtype,
                             uint64_t x_features, int32_t* aux, int32_t ka,
                             int32_t* nrows, uint64_t* take) {
  return Guard([&] {
    *take = static_cast<dct::DenseRecBatcher*>(h)->FillPacked(
        x, out_dtype, x_features, aux, ka, nrows);
  });
}

int dct_denserec_before_first(dct_denserec_t h) {
  return Guard([&] { static_cast<dct::DenseRecBatcher*>(h)->BeforeFirst(); });
}

int dct_denserec_set_epoch(dct_denserec_t h, unsigned epoch,
                           int32_t* supported) {
  return Guard([&] {
    *supported =
        static_cast<dct::DenseRecBatcher*>(h)->SetShuffleEpoch(epoch) ? 1 : 0;
  });
}

int dct_denserec_bytes_read(dct_denserec_t h, size_t* out) {
  return Guard(
      [&] { *out = static_cast<dct::DenseRecBatcher*>(h)->BytesRead(); });
}

int dct_denserec_free(dct_denserec_t h) {
  return Guard([&] { delete static_cast<dct::DenseRecBatcher*>(h); });
}

// ---------------------------------------------------------------- csr rec --
// Zero-rearrangement CSR ingest (csr_rec.h): records carry col/val/row-len
// planes in device batch layout; fill is bulk memcpy + run-length row ids.
typedef void* dct_csrrec_t;

int dct_csrrec_create(const char* uri, unsigned part, unsigned npart,
                      uint64_t batch_rows, uint32_t num_shards,
                      uint64_t min_nnz_bucket, dct_csrrec_t* out) {
  return Guard([&] {
    *out = new dct::CsrRecBatcher(uri, part, npart, batch_rows, num_shards,
                                  min_nnz_bucket);
  });
}

int dct_csrrec_meta(dct_csrrec_t h, uint64_t* bucket, int32_t* has_weight,
                    int32_t* has_qid, int32_t* has_field) {
  return Guard([&] {
    int hw = 0, hq = 0, hf = 0;
    static_cast<dct::CsrRecBatcher*>(h)->Meta(bucket, &hw, &hq, &hf);
    *has_weight = hw;
    *has_qid = hq;
    *has_field = hf;
  });
}

int dct_csrrec_fill(dct_csrrec_t h, int32_t* row, int32_t* col, float* val,
                    int32_t* field, float* label, float* weight,
                    int32_t* qid, int32_t* nrows, uint64_t* take) {
  return Guard([&] {
    *take = static_cast<dct::CsrRecBatcher*>(h)->Fill(
        row, col, val, field, label, weight, qid, nrows);
  });
}

int dct_csrrec_fill_packed(dct_csrrec_t h, int32_t* big, int32_t kb,
                           int32_t* aux, int32_t ka, int32_t* nrows,
                           uint64_t* take) {
  return Guard([&] {
    *take = static_cast<dct::CsrRecBatcher*>(h)->FillPacked(big, kb, aux, ka,
                                                            nrows);
  });
}

int dct_csrrec_before_first(dct_csrrec_t h) {
  return Guard([&] { static_cast<dct::CsrRecBatcher*>(h)->BeforeFirst(); });
}

int dct_csrrec_set_epoch(dct_csrrec_t h, unsigned epoch,
                         int32_t* supported) {
  return Guard([&] {
    *supported =
        static_cast<dct::CsrRecBatcher*>(h)->SetShuffleEpoch(epoch) ? 1 : 0;
  });
}

int dct_csrrec_bytes_read(dct_csrrec_t h, size_t* out) {
  return Guard(
      [&] { *out = static_cast<dct::CsrRecBatcher*>(h)->BytesRead(); });
}

int dct_csrrec_batch_nnz(dct_csrrec_t h, uint64_t* out) {
  return Guard(
      [&] { *out = static_cast<dct::CsrRecBatcher*>(h)->BatchNnz(); });
}

int dct_csrrec_cols_meta(dct_csrrec_t h, uint64_t* cap, uint64_t* distinct,
                         int* tail_lifted) {
  return Guard([&] {
    auto* b = static_cast<dct::CsrRecBatcher*>(h);
    *cap = b->ColsCapacity();
    *distinct = b->ColsDistinct();
    *tail_lifted = b->TailLifted() ? 1 : 0;
  });
}

int dct_csrrec_set_col_owners(dct_csrrec_t h, uint32_t owners,
                              uint64_t range) {
  return Guard([&] {
    static_cast<dct::CsrRecBatcher*>(h)->SetColOwners(owners, range);
  });
}

int dct_csrrec_cols_owner_max(dct_csrrec_t h, uint64_t* out) {
  return Guard(
      [&] { *out = static_cast<dct::CsrRecBatcher*>(h)->ColsOwnerMax(); });
}

int dct_csrrec_fill_cols(dct_csrrec_t h, int32_t* cols, uint64_t cap) {
  return Guard(
      [&] { static_cast<dct::CsrRecBatcher*>(h)->FillCols(cols, cap); });
}

int dct_csrrec_free(dct_csrrec_t h) {
  return Guard([&] { delete static_cast<dct::CsrRecBatcher*>(h); });
}

// ------------------------------------------------------------------- bf16 --
// Bulk bf16 conversion hooks (bf16.h): the parity surface the Python tests
// fuzz against ml_dtypes.bfloat16 — the SAME inlines the batch fills use,
// so a rounding drift there fails the parity test here.

int dct_bf16_convert(const float* src, uint16_t* dst, uint64_t n) {
  return Guard([&] {
    for (uint64_t i = 0; i < n; ++i) dst[i] = dct::Bf16FromFloat(src[i]);
  });
}

int dct_bf16_upcast(const uint16_t* src, float* dst, uint64_t n) {
  return Guard([&] {
    for (uint64_t i = 0; i < n; ++i) dst[i] = dct::Bf16ToFloat(src[i]);
  });
}

}  // extern "C"
