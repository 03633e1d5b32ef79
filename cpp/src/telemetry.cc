// Telemetry registry implementation (see telemetry.h).
#include "telemetry.h"

#include "base.h"

#include <unistd.h>

#include <condition_variable>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <mutex>
#include <sstream>
#include <thread>
#include <vector>

namespace dct {
namespace telemetry {

namespace {

std::atomic<int> g_enabled{-1};  // -1: unresolved (read env on first use)

struct CounterEntry {
  std::string name;
  std::map<std::string, std::string> labels;
  Counter owned;
  std::atomic<uint64_t>* external = nullptr;  // wins over `owned` when set
  uint64_t value() const {
    return external != nullptr
               ? external->load(std::memory_order_relaxed)
               : owned.value();
  }
  void Zero() {
    if (external != nullptr) {
      external->store(0, std::memory_order_relaxed);
    } else {
      owned.Zero();
    }
  }
};

struct GaugeEntry {
  std::string name;
  Gauge gauge;
};

struct HistEntry {
  std::string name;
  std::map<std::string, std::string> labels;
  Hist hist;
};

// Entries live in deques for pointer stability and are never removed; the
// mutex guards registration and the snapshot/reset walks only.
struct Registry {
  std::mutex mu;
  std::deque<CounterEntry> counters DMLC_GUARDED_BY(mu);
  std::deque<GaugeEntry> gauges DMLC_GUARDED_BY(mu);
  std::deque<HistEntry> hists DMLC_GUARDED_BY(mu);
};

Registry& Reg() {
  static Registry* r = new Registry();  // leaked: outlive every static dtor
  return *r;
}

void EscapeJson(const std::string& s, std::string* out) {
  for (char c : s) {
    switch (c) {
      case '"': *out += "\\\""; break;
      case '\\': *out += "\\\\"; break;
      case '\n': *out += "\\n"; break;
      case '\r': *out += "\\r"; break;
      case '\t': *out += "\\t"; break;
      default:
        if (static_cast<unsigned char>(c) < 0x20) {
          char buf[8];
          std::snprintf(buf, sizeof(buf), "\\u%04x", c);
          *out += buf;
        } else {
          *out += c;
        }
    }
  }
}

void AppendNameLabels(const std::string& name,
                      const std::map<std::string, std::string>& labels,
                      std::string* out) {
  *out += "\"name\":\"";
  EscapeJson(name, out);
  *out += "\",\"labels\":{";
  bool first = true;
  for (const auto& kv : labels) {
    if (!first) *out += ',';
    first = false;
    *out += '"';
    EscapeJson(kv.first, out);
    *out += "\":\"";
    EscapeJson(kv.second, out);
    *out += '"';
  }
  *out += '}';
}

}  // namespace

bool Enabled() {
  int v = g_enabled.load(std::memory_order_relaxed);
  if (v < 0) {
    const char* env = std::getenv("DMLC_TELEMETRY");
    v = (env != nullptr &&
         (std::strcmp(env, "0") == 0 || std::strcmp(env, "off") == 0))
            ? 0
            : 1;
    g_enabled.store(v, std::memory_order_relaxed);
  }
  return v != 0;
}

void SetEnabled(bool on) {
  g_enabled.store(on ? 1 : 0, std::memory_order_relaxed);
  if (!on) PulseStop();
}

Counter* GetCounter(const std::string& name) {
  return GetCounter(name, {});
}

Counter* GetCounter(const std::string& name,
                    const std::map<std::string, std::string>& labels) {
  Registry& r = Reg();
  std::lock_guard<std::mutex> lk(r.mu);
  for (auto& e : r.counters) {
    // an externally-backed entry still hands out its owned counter: adds
    // to it are shadowed in the snapshot (external wins), never a crash
    if (e.name == name && e.labels == labels) return &e.owned;
  }
  r.counters.emplace_back();
  r.counters.back().name = name;
  r.counters.back().labels = labels;
  return &r.counters.back().owned;
}

void RegisterExternalCounter(const std::string& name,
                             std::atomic<uint64_t>* v) {
  Registry& r = Reg();
  std::lock_guard<std::mutex> lk(r.mu);
  for (auto& e : r.counters) {
    if (e.name == name && e.labels.empty()) {
      e.external = v;
      return;
    }
  }
  r.counters.emplace_back();
  r.counters.back().name = name;
  r.counters.back().external = v;
}

Gauge* GetGauge(const std::string& name) {
  Registry& r = Reg();
  std::lock_guard<std::mutex> lk(r.mu);
  for (auto& e : r.gauges) {
    if (e.name == name) return &e.gauge;
  }
  r.gauges.emplace_back();
  r.gauges.back().name = name;
  return &r.gauges.back().gauge;
}

Hist* GetHist(const std::string& name,
              const std::map<std::string, std::string>& labels) {
  Registry& r = Reg();
  std::lock_guard<std::mutex> lk(r.mu);
  for (auto& e : r.hists) {
    if (e.name == name && e.labels == labels) return &e.hist;
  }
  r.hists.emplace_back();
  r.hists.back().name = name;
  r.hists.back().labels = labels;
  return &r.hists.back().hist;
}

const IoHists* IoHistsFor(const std::string& backend) {
  // small leaked cache: one IoHists per backend, resolved under its own
  // mutex (called once per HttpConnection, never per byte)
  static std::mutex* mu = new std::mutex();
  static std::map<std::string, IoHists>* cache =
      new std::map<std::string, IoHists>();
  std::lock_guard<std::mutex> lk(*mu);
  auto it = cache->find(backend);
  if (it != cache->end()) return &it->second;
  std::map<std::string, std::string> labels{{"backend", backend}};
  IoHists h;
  h.connect_us = GetHist("io_connect_us", labels);
  h.ttfb_us = GetHist("io_ttfb_us", labels);
  h.recv_us = GetHist("io_recv_us", labels);
  return &((*cache)[backend] = h);
}

const RangeHists* RangeHistsFor(const std::string& backend) {
  // same shape as IoHistsFor: one leaked per-backend cache, resolved once
  // per RangeReader construction (never per range)
  static std::mutex* mu = new std::mutex();
  static std::map<std::string, RangeHists>* cache =
      new std::map<std::string, RangeHists>();
  std::lock_guard<std::mutex> lk(*mu);
  auto it = cache->find(backend);
  if (it != cache->end()) return &it->second;
  std::map<std::string, std::string> labels{{"backend", backend}};
  RangeHists h;
  h.bytes = GetHist("io_range_bytes", labels);
  h.wait_us = GetHist("io_range_wait_us", labels);
  return &((*cache)[backend] = h);
}

namespace {

// One (wall, steady) clock pair sampled back to back: the per-process
// anchor every snapshot/trace/dump carries so steady-clock timelines can
// be merged across processes (ranks) without drift.
void AppendAnchor(std::string* out) {
  const uint64_t wall_us = static_cast<uint64_t>(
      std::chrono::duration_cast<std::chrono::microseconds>(
          std::chrono::system_clock::now().time_since_epoch())
          .count());
  const uint64_t steady_us = NowUs();
  *out += "{\"wall_us\":";
  *out += std::to_string(wall_us);
  *out += ",\"steady_us\":";
  *out += std::to_string(steady_us);
  *out += '}';
}

}  // namespace

std::string SnapshotJson() {
  Registry& r = Reg();
  std::string out;
  out.reserve(4096);
  out += "{\"version\":";
  out += std::to_string(kSnapshotVersion);
  out += ",\"enabled\":";
  out += Enabled() ? "true" : "false";
  out += ",\"anchor\":";
  AppendAnchor(&out);
  out += ",\"counters\":[";
  {
    std::lock_guard<std::mutex> lk(r.mu);
    bool first = true;
    for (const auto& e : r.counters) {
      if (!first) out += ',';
      first = false;
      out += '{';
      AppendNameLabels(e.name, e.labels, &out);
      out += ",\"value\":";
      out += std::to_string(e.value());
      out += '}';
    }
    out += "],\"gauges\":[";
    first = true;
    for (const auto& e : r.gauges) {
      if (!first) out += ',';
      first = false;
      out += '{';
      AppendNameLabels(e.name, {}, &out);
      out += ",\"value\":";
      out += std::to_string(e.gauge.value());
      out += '}';
    }
    out += "],\"histograms\":[";
    first = true;
    for (const auto& e : r.hists) {
      if (!first) out += ',';
      first = false;
      out += '{';
      AppendNameLabels(e.name, e.labels, &out);
      out += ",\"count\":";
      out += std::to_string(e.hist.count());
      out += ",\"sum\":";
      out += std::to_string(e.hist.sum());
      out += ",\"buckets\":[";
      for (int i = 0; i <= kHistBuckets; ++i) {
        if (i) out += ',';
        out += std::to_string(e.hist.bucket(i));
      }
      out += "]}";
    }
  }
  out += "]}";
  return out;
}

void Reset() {
  Registry& r = Reg();
  std::lock_guard<std::mutex> lk(r.mu);
  for (auto& e : r.counters) e.Zero();
  for (auto& e : r.gauges) e.gauge.Zero();
  for (auto& e : r.hists) e.hist.Zero();
}

// ------------------------------------------------------------- span ring --
namespace {

// Every field is an atomic so a snapshot racing a writer reads a torn
// RECORD at worst, never undefined behavior; the per-slot seq (published
// last with release, checked before and after the field reads) rejects
// torn records. Slots are overwritten in claim order — the ring holds the
// most recent kSpanRingSize spans.
struct SpanSlot {
  std::atomic<uint64_t> seq{0};  // claim index + 1; 0 = never written
  std::atomic<const char*> name{nullptr};
  std::atomic<uint64_t> span_id{0};
  std::atomic<uint64_t> parent{0};
  std::atomic<uint64_t> start_us{0};
  std::atomic<uint64_t> dur_us{0};
  std::atomic<uint64_t> arg{0};
  std::atomic<uint32_t> tid{0};
};

struct SpanRing {
  std::atomic<uint64_t> cursor{0};     // total spans ever claimed
  std::atomic<uint64_t> next_span{1};  // span-id allocator (0 = no parent)
  std::atomic<uint32_t> next_tid{0};   // small per-thread lane ids
  SpanSlot slots[kSpanRingSize];
};

SpanRing& Ring() {
  static SpanRing* r = new SpanRing();  // leaked: outlive static dtors
  return *r;
}

uint32_t ThreadLane() {
  thread_local uint32_t lane =
      Ring().next_tid.fetch_add(1, std::memory_order_relaxed) + 1;
  return lane;
}

// the thread's currently open TraceSpan (parent of the next nested one)
thread_local uint64_t tls_open_span = 0;

void EmitSpanRecord(const char* name, uint64_t start_us, uint64_t dur_us,
                    uint64_t span_id, uint64_t parent, uint64_t arg) {
  SpanRing& r = Ring();
  const uint64_t idx = r.cursor.fetch_add(1, std::memory_order_relaxed);
  if (idx >= kSpanRingSize) {
    // this claim overwrites the record kSpanRingSize behind it — a wrap
    // must be countable, not silent (labeled per half: the Python ring
    // publishes its own spans_dropped_total{half="python"})
    static Counter* dropped =
        GetCounter("spans_dropped_total", {{"half", "native"}});
    dropped->Add(1);
  }
  SpanSlot& s = r.slots[idx & (kSpanRingSize - 1)];
  // Seqlock write protocol (Boehm, "Can seqlocks get along with
  // programming language memory models"): invalidate, RELEASE FENCE,
  // field stores, release publish. The fence — not a release store of
  // seq, which only orders PRIOR writes — is what guarantees a reader
  // that observed any NEW field value will also observe seq==0 (or the
  // final publish) at its re-check, so a torn old/new record can never
  // pass both seq checks even on weakly-ordered hardware.
  s.seq.store(0, std::memory_order_relaxed);
  std::atomic_thread_fence(std::memory_order_release);
  s.name.store(name, std::memory_order_relaxed);
  s.span_id.store(span_id, std::memory_order_relaxed);
  s.parent.store(parent, std::memory_order_relaxed);
  s.start_us.store(start_us, std::memory_order_relaxed);
  s.dur_us.store(dur_us, std::memory_order_relaxed);
  s.arg.store(arg, std::memory_order_relaxed);
  s.tid.store(ThreadLane(), std::memory_order_relaxed);
  s.seq.store(idx + 1, std::memory_order_release);
}

}  // namespace

void EmitSpan(const char* name, uint64_t start_us, uint64_t dur_us,
              uint64_t arg) {
  if (!Enabled()) return;
  SpanRing& r = Ring();
  EmitSpanRecord(name, start_us, dur_us,
                 r.next_span.fetch_add(1, std::memory_order_relaxed),
                 tls_open_span, arg);
}

TraceSpan::TraceSpan(const char* name)
    : name_(name), active_(Enabled()) {
  if (!active_) return;
  span_id_ = Ring().next_span.fetch_add(1, std::memory_order_relaxed);
  parent_ = tls_open_span;
  tls_open_span = span_id_;
  start_ = NowUs();
}

TraceSpan::~TraceSpan() {
  if (!active_) return;
  tls_open_span = parent_;
  EmitSpanRecord(name_, start_, NowUs() - start_, span_id_, parent_, arg_);
}

std::string TraceJson() {
  SpanRing& r = Ring();
  const uint64_t cur = r.cursor.load(std::memory_order_acquire);
  const uint64_t window = cur < kSpanRingSize ? cur : kSpanRingSize;
  std::string out;
  out.reserve(256 + window * 96);
  out += "{\"version\":1,\"pid\":";
  out += std::to_string(static_cast<uint64_t>(::getpid()));
  out += ",\"anchor\":";
  AppendAnchor(&out);
  out += ",\"emitted\":";
  out += std::to_string(cur);
  out += ",\"dropped\":";
  out += std::to_string(cur - window);
  out += ",\"spans\":[";
  bool first = true;
  for (uint64_t idx = cur - window; idx < cur; ++idx) {
    SpanSlot& s = r.slots[idx & (kSpanRingSize - 1)];
    const uint64_t seq = s.seq.load(std::memory_order_acquire);
    if (seq != idx + 1) continue;  // torn or already overwritten: skip
    const char* name = s.name.load(std::memory_order_relaxed);
    const uint64_t span_id = s.span_id.load(std::memory_order_relaxed);
    const uint64_t parent = s.parent.load(std::memory_order_relaxed);
    const uint64_t start_us = s.start_us.load(std::memory_order_relaxed);
    const uint64_t dur_us = s.dur_us.load(std::memory_order_relaxed);
    const uint64_t arg = s.arg.load(std::memory_order_relaxed);
    const uint32_t tid = s.tid.load(std::memory_order_relaxed);
    // Seqlock read re-check: the acquire FENCE pairs with the writer's
    // release fence — if any field load above saw a new-record value,
    // the re-check is guaranteed to see seq==0 or the new publish and
    // reject; an unchanged seq proves every field read was consistent.
    std::atomic_thread_fence(std::memory_order_acquire);
    if (s.seq.load(std::memory_order_relaxed) != idx + 1 ||
        name == nullptr) {
      continue;
    }
    if (!first) out += ',';
    first = false;
    out += "{\"name\":\"";
    EscapeJson(name, &out);
    out += "\",\"id\":";
    out += std::to_string(span_id);
    out += ",\"parent\":";
    out += std::to_string(parent);
    out += ",\"tid\":";
    out += std::to_string(tid);
    out += ",\"ts\":";
    out += std::to_string(start_us);
    out += ",\"dur\":";
    out += std::to_string(dur_us);
    out += ",\"arg\":";
    out += std::to_string(arg);
    out += '}';
  }
  out += "]}";
  return out;
}

void TraceReset() {
  SpanRing& r = Ring();
  // clear the slot seqs FIRST: a stale seq matching a post-reset claim
  // index would let TraceJson stitch an old record into the new window
  for (auto& s : r.slots) s.seq.store(0, std::memory_order_relaxed);
  r.cursor.store(0, std::memory_order_release);
}

bool FlightDump(const char* reason) {
  const char* dir = std::getenv("DMLC_TRACE_DUMP");
  if (dir == nullptr || dir[0] == '\0') return false;
  static std::atomic<uint32_t> n{0};
  const uint32_t id = n.fetch_add(1, std::memory_order_relaxed);
  std::string path = std::string(dir) + "/flight_native_" +
                     std::to_string(static_cast<uint64_t>(::getpid())) +
                     "_" + std::to_string(id) + ".json";
  std::string doc;
  doc += "{\"reason\":\"";
  EscapeJson(reason == nullptr ? "" : reason, &doc);
  doc += "\",\"anchor\":";
  AppendAnchor(&doc);
  doc += ",\"trace\":";
  doc += TraceJson();
  doc += ",\"metrics\":";
  doc += SnapshotJson();
  doc += "}\n";
  // plain stdio, errors swallowed: the dump is a best-effort postmortem
  // and must never mask (or re-enter, via the fault plane) the failure
  // being recorded
  std::FILE* f = std::fopen(path.c_str(), "w");
  if (f == nullptr) return false;
  const bool ok = std::fwrite(doc.data(), 1, doc.size(), f) == doc.size();
  std::fclose(f);
  return ok;
}

// ------------------------------------------------------------------ pulse --
namespace {

// The ticks change fifty times a second and are read once a long hold:
// one mutex, held by the thread whenever it is not napping.
struct Pulse {
  std::mutex mu;
  std::condition_variable cv;
  std::thread napper DMLC_GUARDED_BY(mu);  // joinable while the pulse runs
  bool stop DMLC_GUARDED_BY(mu) = false;
  uint64_t due_us DMLC_GUARDED_BY(mu) = 0;  // when the running nap ends
  uint64_t nticks DMLC_GUARDED_BY(mu) = 0;  // ticks ever recorded
  uint64_t wake_us[kPulseTicks] DMLC_GUARDED_BY(mu) = {};
  uint64_t late_us[kPulseTicks] DMLC_GUARDED_BY(mu) = {};
};

Pulse& ThePulse() {
  static Pulse* p = new Pulse();  // leaked: a running thread outlives exit
  return *p;
}

void PulseLoop(Pulse* p) {
  Hist* late_hist = GetHist("pulse_native_late_us");
  std::unique_lock<std::mutex> lk(p->mu);
  uint64_t due = NowUs();  // the first tick is due at once
  while (!p->stop) {
    p->due_us = due;
    const auto until = std::chrono::steady_clock::time_point(
        std::chrono::microseconds(due));
    if (p->cv.wait_until(lk, until, [p] { return p->stop; })) break;
    const uint64_t woke = NowUs();
    const uint64_t late = woke > due ? woke - due : 0;
    late_hist->Observe(late);
    const size_t slot = p->nticks++ % kPulseTicks;
    p->wake_us[slot] = woke;
    p->late_us[slot] = late;
    due = woke + kPulsePeriodUs;
  }
}

}  // namespace

void PulseStart() {
  if (!Enabled()) return;
  Pulse& p = ThePulse();
  std::lock_guard<std::mutex> lk(p.mu);
  if (p.napper.joinable()) return;
  p.stop = false;
  p.napper = std::thread(PulseLoop, &p);
}

void PulseStop() {
  Pulse& p = ThePulse();
  std::thread stopped;
  {
    std::lock_guard<std::mutex> lk(p.mu);
    if (!p.napper.joinable()) return;
    p.stop = true;
    stopped = std::move(p.napper);
  }
  p.cv.notify_all();
  stopped.join();
}

bool PulseRunning() {
  Pulse& p = ThePulse();
  std::lock_guard<std::mutex> lk(p.mu);
  return p.napper.joinable();
}

uint64_t PulseMaxLateUs(uint64_t since_us_ago, uint64_t until_us_ago,
                        uint64_t* ticks) {
  Pulse& p = ThePulse();
  const uint64_t now = NowUs();
  const uint64_t from = now > since_us_ago ? now - since_us_ago : 0;
  const uint64_t to = now > until_us_ago ? now - until_us_ago : 0;
  uint64_t worst = 0, found = 0;
  std::lock_guard<std::mutex> lk(p.mu);
  const uint64_t kept = p.nticks < kPulseTicks ? p.nticks : kPulseTicks;
  for (uint64_t i = 0; i < kept; ++i) {
    if (p.wake_us[i] < from || p.wake_us[i] > to) continue;
    ++found;
    if (p.late_us[i] > worst) worst = p.late_us[i];
  }
  // a nap that should have ended inside the window and has not: the thread
  // that asks may have got the processor back before this one
  if (p.napper.joinable() && p.due_us >= from && p.due_us <= to &&
      now > p.due_us && now - p.due_us > worst) {
    worst = now - p.due_us;
  }
  if (ticks != nullptr) *ticks = found;
  return worst;
}

}  // namespace telemetry
}  // namespace dct
