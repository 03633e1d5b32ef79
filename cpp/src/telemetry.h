// Process-wide telemetry registry: one metrics plane for the native core.
//
// Before this layer the repo grew three disjoint observability side-channels
// (IoStats counters in retry.h, per-parser ParsePipelineStats, the Python
// tracker's ad-hoc event list) that shared no naming, no units, and no reset
// semantics. This registry is the single source the C ABI
// (dct_telemetry_snapshot), dmlc_core_tpu.telemetry.snapshot(), and the
// tracker's HTTP /metrics scrape all read from.
//
// Design rules:
//   - NO locks on the hot path. Counters/gauges/histogram buckets are plain
//     relaxed atomics; the registry mutex guards only metric REGISTRATION
//     (first lookup of a name) and the snapshot's walk of the entry list.
//     Metric objects are pointer-stable forever (never unregistered), so a
//     site resolves its pointer once and then only does atomic adds.
//   - Histograms are fixed-bucket log2: bucket i counts observations
//     v <= 2^i (i = 0..kHistBuckets-1), plus one overflow (+Inf) bucket.
//     Units are microseconds for every *_us histogram. Non-cumulative
//     counts are stored; exposition layers cumulate for Prometheus.
//   - DMLC_TELEMETRY=0 (or dct_telemetry_enable(0)) disables timed spans:
//     Enabled() is one relaxed atomic load, checked before any clock read.
//     Pure counters keep counting — they are cheaper than the branch.
//   - The snapshot is a stable, versioned JSON document (kSnapshotVersion);
//     fields are append-only across releases.
//
// Existing stats surfaces migrate in rather than duplicate: retry.cc
// registers the IoStats atomics as external counters (same storage, new
// canonical names), and parser.cc feeds process-wide pipeline counters and
// per-stage latency histograms alongside its per-handle struct.
//
// MACHINE-CHECKED CATALOG (scripts/analyze.py Pass 4, doc/analysis.md):
// every GetCounter/GetGauge/GetHist/RegisterExternalCounter call site is
// extracted and diffed against doc/observability.md's metric tables,
// telemetry.METRIC_HELP, and the Python half's registrations (label-key
// parity for shared names). Register with the metric NAME as a string
// literal at the call site (a name built at run time is invisible to the
// extractor and will surface as a documented-but-gone finding); new
// metrics need a catalog row and a METRIC_HELP entry before
// `make analyze` passes.
#ifndef DCT_TELEMETRY_H_
#define DCT_TELEMETRY_H_

#include <atomic>
#include <chrono>
#include <cstdint>
#include <map>
#include <string>

namespace dct {
namespace telemetry {

constexpr int kSnapshotVersion = 1;

// ---------------------------------------------------------------- enable --
// Span (clock-reading) instrumentation gate: DMLC_TELEMETRY env at first
// use (default on), overridable at runtime through the C ABI
// (dct_telemetry_enable). One relaxed load.
bool Enabled();
void SetEnabled(bool on);

// ---------------------------------------------------------------- metrics --
class Counter {
 public:
  void Add(uint64_t n = 1) { v_.fetch_add(n, std::memory_order_relaxed); }
  uint64_t value() const { return v_.load(std::memory_order_relaxed); }
  void Zero() { v_.store(0, std::memory_order_relaxed); }

 private:
  std::atomic<uint64_t> v_{0};
};

class Gauge {
 public:
  void Set(int64_t v) { v_.store(v, std::memory_order_relaxed); }
  int64_t value() const { return v_.load(std::memory_order_relaxed); }
  void Zero() { v_.store(0, std::memory_order_relaxed); }

 private:
  std::atomic<int64_t> v_{0};
};

// log2 latency histogram; all writers relaxed-atomic, safe from any thread
constexpr int kHistBuckets = 28;  // le 1,2,4,...,2^27 us (~134 s), then +Inf

class Hist {
 public:
  void Observe(uint64_t v) {
    count_.fetch_add(1, std::memory_order_relaxed);
    sum_.fetch_add(v, std::memory_order_relaxed);
    buckets_[BucketOf(v)].fetch_add(1, std::memory_order_relaxed);
  }
  // first bucket whose upper bound 2^i holds v; kHistBuckets = overflow
  static int BucketOf(uint64_t v) {
    if (v <= 1) return 0;
    int w = 64 - __builtin_clzll(v - 1);  // ceil(log2(v))
    return w < kHistBuckets ? w : kHistBuckets;
  }
  uint64_t count() const { return count_.load(std::memory_order_relaxed); }
  uint64_t sum() const { return sum_.load(std::memory_order_relaxed); }
  uint64_t bucket(int i) const {
    return buckets_[i].load(std::memory_order_relaxed);
  }
  void Zero() {
    count_.store(0, std::memory_order_relaxed);
    sum_.store(0, std::memory_order_relaxed);
    for (auto& b : buckets_) b.store(0, std::memory_order_relaxed);
  }

 private:
  std::atomic<uint64_t> count_{0};
  std::atomic<uint64_t> sum_{0};
  std::atomic<uint64_t> buckets_[kHistBuckets + 1] = {};
};

// --------------------------------------------------------------- registry --
// Resolve-or-register by (name, labels). Returned pointers are stable for
// the process lifetime; resolve once, keep the pointer. Names follow the
// Prometheus convention (snake_case, *_total counters, unit suffix).
Counter* GetCounter(const std::string& name);
// Labeled counter variant (fs_fault_injected_total{op=} et al.) — same
// stability contract; the unlabeled overload is (name, {}).
Counter* GetCounter(const std::string& name,
                    const std::map<std::string, std::string>& labels);
Gauge* GetGauge(const std::string& name);
Hist* GetHist(const std::string& name,
              const std::map<std::string, std::string>& labels = {});

// Adopt an atomic that lives elsewhere (the IoStats migration path: the
// storage stays where its writers already are, the registry snapshots and
// resets it). The atomic must outlive the process' last snapshot.
void RegisterExternalCounter(const std::string& name,
                             std::atomic<uint64_t>* v);

// The versioned JSON document every surface serves (schema documented in
// doc/observability.md): {"version","enabled","anchor":{wall_us,steady_us},
// "counters":[{name,labels,value}],"gauges":[...],"histograms":[{name,
// labels,count,sum,buckets}]}. The anchor is one (wall, steady) clock pair
// sampled back to back at snapshot time, so timelines recorded on the
// steady clock can be merged across processes without drift.
std::string SnapshotJson();

// Zero every registered metric (owned and external).
void Reset();

// ------------------------------------------------------------- span ring --
// Job-wide distributed tracing (doc/observability.md "Distributed
// tracing"): a lock-free bounded ring of COMPLETED spans covering the
// batch path (range fetch, chunk fill, scan, slice parse, cache tee/
// replay, batch assembly). Each record carries span-id/parent-id (a
// thread-local chain gives nesting), the steady-clock start, duration,
// and a small thread lane id. The ring is fixed-size; overwriting old
// spans is the design (a flight recorder keeps the RECENT past), and the
// dropped count makes the truncation visible. Writers are wait-free: one
// fetch_add to claim a slot, relaxed field stores, one release store of
// the slot's sequence number to publish; a concurrent snapshot detects a
// torn slot by its sequence and skips it. Disabled
// (DMLC_TELEMETRY=0 / SetEnabled(false)) cost: ONE relaxed load in the
// TraceSpan constructor — no clock read, no slot claim.
constexpr int kSpanRingBits = 13;                 // 8192 slots
constexpr size_t kSpanRingSize = 1u << kSpanRingBits;

// Emit one completed span (steady-clock start, microseconds). `arg` is a
// free u64 the site can use for the dominant dimension (bytes fetched,
// shard id); 0 when unused. No-op when telemetry is disabled.
void EmitSpan(const char* name, uint64_t start_us, uint64_t dur_us,
              uint64_t arg = 0);

// RAII span: claims a span id, parents under the thread's currently open
// span, and emits the completed record at scope exit. `name` must have
// static storage duration (string literals at the instrumentation sites).
class TraceSpan {
 public:
  explicit TraceSpan(const char* name);
  ~TraceSpan();
  void set_arg(uint64_t v) { arg_ = v; }
  TraceSpan(const TraceSpan&) = delete;
  TraceSpan& operator=(const TraceSpan&) = delete;

 private:
  const char* name_;
  uint64_t start_ = 0;
  uint64_t span_id_ = 0;
  uint64_t parent_ = 0;
  uint64_t arg_ = 0;
  bool active_;
};

// The trace document (schema doc/observability.md "Distributed tracing"):
// {"version","pid","anchor":{"wall_us","steady_us"},"emitted","dropped",
// "spans":[{"name","id","parent","tid","ts","dur","arg"}]} — spans oldest
// to newest, `ts` on the steady clock (convert via the anchor pair).
std::string TraceJson();

// Drop every buffered span and restart the sequence (tests / epoch cuts).
void TraceReset();

// Flight recorder (doc/observability.md): when DMLC_TRACE_DUMP names a
// directory, write flight_native_<pid>_<n>.json there — {"reason",
// "anchor", "trace": <TraceJson doc>, "metrics": <SnapshotJson doc>} —
// and return true. Failures are swallowed (a postmortem writer must never
// mask the failure it is recording). Called on fault-plane quarantines;
// the Python half mirrors it for abort paths.
bool FlightDump(const char* reason);

// ------------------------------------------------------------------ pulse --
// The native side of the pulse (doc/observability.md "The hold and the
// pulse"): one thread that asks for a nap of kPulsePeriodUs and records how
// late it woke into pulse_native_late_us, keeping the last kPulseTicks
// (wake time, lateness) pairs, about twenty seconds of them. It needs no
// interpreter lock to wake, so it stops only when the whole process does:
// beside the Python pulse, which needs the lock, it tells a frozen host from
// a held lock. One thread a process; the first tick comes at once.
constexpr uint64_t kPulsePeriodUs = 20000;
constexpr size_t kPulseTicks = 1024;

// Start the thread unless it runs (or telemetry is disabled); stop and join
// it. Both idempotent; SetEnabled(false) stops it too.
void PulseStart();
void PulseStop();
bool PulseRunning();

// The largest lateness among the ticks that woke between `since_us_ago` and
// `until_us_ago` microseconds before now, the running nap counted by how far
// it is overdue; `*ticks` (may be null) gets the number of ticks found.
uint64_t PulseMaxLateUs(uint64_t since_us_ago, uint64_t until_us_ago,
                        uint64_t* ticks);

// -------------------------------------------------------------- io spans --
// Per-backend remote-I/O latency histograms (connect / time-to-first-
// header-byte / per-ReadBody recv), labeled {backend="s3"|...}. Resolved
// once per HttpConnection (one connection per request), cached per backend.
struct IoHists {
  Hist* connect_us;
  Hist* ttfb_us;
  Hist* recv_us;
};
const IoHists* IoHistsFor(const std::string& backend);

// Ranged-read scheduler histograms (range_reader.h), labeled {backend=}:
// completed range sizes in bytes and the consumer's head-of-line wait.
// Resolved once per RangeReader, cached per backend like IoHistsFor.
struct RangeHists {
  Hist* bytes;
  Hist* wait_us;
};
const RangeHists* RangeHistsFor(const std::string& backend);

// ----------------------------------------------------------------- timing --
inline uint64_t NowUs() {
  return static_cast<uint64_t>(
      std::chrono::duration_cast<std::chrono::microseconds>(
          std::chrono::steady_clock::now().time_since_epoch())
          .count());
}

// Observe the scope's wall time into `h` (microseconds); both the clock
// reads and the observe vanish when telemetry is disabled or h is null.
class ScopedTimerUs {
 public:
  explicit ScopedTimerUs(Hist* h) : h_(Enabled() ? h : nullptr) {
    if (h_ != nullptr) start_ = NowUs();
  }
  ~ScopedTimerUs() {
    if (h_ != nullptr) h_->Observe(NowUs() - start_);
  }
  ScopedTimerUs(const ScopedTimerUs&) = delete;
  ScopedTimerUs& operator=(const ScopedTimerUs&) = delete;

 private:
  Hist* h_;
  uint64_t start_ = 0;
};

}  // namespace telemetry
}  // namespace dct

#endif  // DCT_TELEMETRY_H_
