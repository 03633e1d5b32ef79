// Full native-pipeline benchmark: file -> InputSplit(prefetch) ->
// ThreadedParser -> consumed blocks, all in C++ — the stage between the
// ParseBlock microbench (bench_parse.cc) and the Python iterators. The
// spread between the two locates the pipeline overhead: IO+split+threading
// here, ctypes/Python above.
// Build: make -C cpp benchpipeline
// Run:   ./dmlc_core_tpu/_native/bench_pipeline FILE [nthread] [reps]
#include <chrono>
#include <cstdio>
#include <cstdlib>
#include <string>

#include "../src/parser.h"
#include "../src/recordio.h"
#include "../src/retry.h"

namespace {

// `bench_pipeline rt N PAYLOAD PATH`: native RecordIO write+read
// round-trip — the BASELINE.md parity row measured engine-to-engine
// (through the Python facade a record pays one ctypes call, which
// measures the binding, not the format).
int RoundTrip(int n, int payload, const char* path) {
  using Clock = std::chrono::steady_clock;
  std::string blob(payload, 'x');
  for (int i = 0; i < payload; ++i) blob[i] = static_cast<char>(i & 0xff);
  auto t0 = Clock::now();
  {
    std::unique_ptr<dct::Stream> fo(dct::Stream::Create(path, "w"));
    dct::RecordIOWriter w(fo.get());
    for (int i = 0; i < n; ++i) w.WriteRecord(blob.data(), blob.size());
  }
  double t_write = std::chrono::duration<double>(Clock::now() - t0).count();
  t0 = Clock::now();
  size_t got = 0;
  {
    std::unique_ptr<dct::Stream> fi(dct::Stream::Create(path, "r"));
    dct::RecordIOReader r(fi.get());
    std::string rec;
    while (r.NextRecord(&rec)) ++got;
  }
  double t_read = std::chrono::duration<double>(Clock::now() - t0).count();
  printf("recordio_rt %9.0f rec/s  (write %.0f, read %.0f, %zu recs, "
         "payload %d)\n", got / (t_write + t_read), n / t_write,
         got / t_read, got, payload);
  return got == static_cast<size_t>(n) ? 0 : 1;
}

}  // namespace

int main(int argc, char** argv) {
  if (argc < 2) {
    fprintf(stderr, "usage: %s FILE [nthread] [reps] | %s rt N PAYLOAD "
            "PATH\n", argv[0], argv[0]);
    return 2;
  }
  if (std::string(argv[1]) == "rt") {
    if (argc < 5) {
      fprintf(stderr, "usage: %s rt N PAYLOAD PATH\n", argv[0]);
      return 2;
    }
    return RoundTrip(
        static_cast<int>(dct::io::CheckedInt("N", argv[2], 1, 1 << 28)),
        static_cast<int>(dct::io::CheckedInt("PAYLOAD", argv[3], 1,
                                             1 << 28)),
        argv[4]);
  }
  const char* path = argv[1];
  // checked CLI parses (analyze.py env rule): garbage args error loudly
  int nthread = argc > 2 ? static_cast<int>(
      dct::io::CheckedInt("nthread", argv[2], 1, 1024)) : 1;
  int reps = argc > 3 ? static_cast<int>(
      dct::io::CheckedInt("reps", argv[3], 1, 1 << 20)) : 5;
  using Clock = std::chrono::steady_clock;
  double best = 1e30;
  size_t rows = 0, bytes = 0;
  for (int i = 0; i < reps; ++i) {
    auto t0 = Clock::now();
    auto parser = std::unique_ptr<dct::Parser<uint32_t>>(
        dct::Parser<uint32_t>::Create(path, 0, 1, "libsvm", nthread,
                                      /*threaded=*/true));
    rows = 0;
    while (const auto* b = parser->NextBlock()) {
      rows += b->Size();
    }
    bytes = parser->BytesRead();
    double dt = std::chrono::duration<double>(Clock::now() - t0).count();
    if (dt < best) best = dt;
  }
  printf("pipeline  %7.1f MB/s  %9.0f rows/s  (%zu rows, %.1f MB, "
         "nthread=%d, best of %d)\n",
         bytes / best / 1e6, rows / best, rows, bytes / 1e6, nthread, reps);
  return 0;
}
