// Host-parse microbenchmark: times ParseBlock on synthetic corpora shaped
// like the reference's data sets (HIGGS-ish libsvm, dense csv, libfm triples).
// Build:  make -C cpp benchparse   Run: ./dmlc_core_tpu/_native/bench_parse
// This is the fast inner loop for parser optimization work — it isolates
// the single-core ParseBlock cost from the split/pipeline/device stages
// (reference keeps equivalent manual probes in test/, e.g.
// test/split_read_test.cc:27-33 printing MB/s).
#include <chrono>
#include <cstdio>
#include <cstring>
#include <random>
#include <string>
#include <vector>

#include "../src/parser.h"
#include "../src/retry.h"

namespace {

using Clock = std::chrono::steady_clock;

double Secs(Clock::time_point a, Clock::time_point b) {
  return std::chrono::duration<double>(b - a).count();
}

std::string MakeLibsvm(int rows, int feats, uint32_t seed) {
  std::mt19937 rng(seed);
  std::uniform_real_distribution<double> val(-3.0, 3.0);
  std::string out;
  out.reserve(static_cast<size_t>(rows) * (feats * 11 + 3));
  char buf[64];
  for (int r = 0; r < rows; ++r) {
    out += (rng() & 1) ? '1' : '0';
    for (int f = 0; f < feats; ++f) {
      snprintf(buf, sizeof(buf), " %d:%.6f", f, val(rng));
      out += buf;
    }
    out += '\n';
  }
  return out;
}

std::string MakeCSV(int rows, int cols, uint32_t seed) {
  std::mt19937 rng(seed);
  std::uniform_real_distribution<double> val(-3.0, 3.0);
  std::string out;
  out.reserve(static_cast<size_t>(rows) * (cols * 10 + 3));
  char buf[64];
  for (int r = 0; r < rows; ++r) {
    out += (rng() & 1) ? '1' : '0';
    for (int c = 0; c < cols; ++c) {
      snprintf(buf, sizeof(buf), ",%.6f", val(rng));
      out += buf;
    }
    out += '\n';
  }
  return out;
}

std::string MakeLibfm(int rows, int feats, uint32_t seed) {
  std::mt19937 rng(seed);
  std::uniform_real_distribution<double> val(-3.0, 3.0);
  std::string out;
  out.reserve(static_cast<size_t>(rows) * (feats * 14 + 3));
  char buf[64];
  for (int r = 0; r < rows; ++r) {
    out += (rng() & 1) ? '1' : '0';
    for (int f = 0; f < feats; ++f) {
      snprintf(buf, sizeof(buf), " %d:%d:%.6f", f % 7, f, val(rng));
      out += buf;
    }
    out += '\n';
  }
  return out;
}

template <typename ParserT>
void BenchFormat(const char* name, const std::string& corpus,
                 const std::map<std::string, std::string>& args, int reps) {
  ParserT parser(nullptr, args, 1);
  dct::RowBlockContainer<uint32_t> out;
  // warm
  parser.ParseBlock(corpus.data(), corpus.data() + corpus.size(), &out);
  const size_t rows = out.Size();
  double best = 1e30;
  for (int i = 0; i < reps; ++i) {
    auto t0 = Clock::now();
    parser.ParseBlock(corpus.data(), corpus.data() + corpus.size(), &out);
    auto t1 = Clock::now();
    double dt = Secs(t0, t1);
    if (dt < best) best = dt;
  }
  printf("%-8s %7.1f MB/s  %9.0f rows/s  (%zu rows, %.1f MB, best of %d, "
         "%s lane)\n",
         name, corpus.size() / best / 1e6, rows / best, rows,
         corpus.size() / 1e6, reps, dct::SimdTierName(parser.simd_tier()));
}

// --check: correctness-mode smoke (make -C cpp ci): the SIMD decode lane
// must reproduce the scalar lane's containers on every format corpus, for
// every supported dispatch tier. No timing asserts — the throughput floor
// lives in tests/test_parse_scaling.py where noise is budgeted for.
template <typename ParserT>
int CheckFormat(const char* name, const std::string& corpus,
                const std::map<std::string, std::string>& args) {
  // save/restore any ambient tier pin instead of erasing it
  const char* ambient = ::getenv("DMLC_PARSE_SIMD");
  const std::string saved = ambient != nullptr ? ambient : "";
  const bool had = ambient != nullptr;
  auto restore = [&] {
    if (had) {
      ::setenv("DMLC_PARSE_SIMD", saved.c_str(), 1);
    } else {
      ::unsetenv("DMLC_PARSE_SIMD");
    }
  };
  ::setenv("DMLC_PARSE_SIMD", "0", 1);
  ParserT scalar(nullptr, args, 1);
  restore();
  dct::RowBlockContainer<uint32_t> want;
  scalar.ParseBlock(corpus.data(), corpus.data() + corpus.size(), &want);
  int failures = 0;
  for (int t = dct::kSimdSWAR; t <= dct::BestSupportedSimdTier(); ++t) {
    ::setenv("DMLC_PARSE_SIMD", dct::SimdTierName(t), 1);
    ParserT simd(nullptr, args, 1);
    restore();
    dct::RowBlockContainer<uint32_t> got;
    simd.ParseBlock(corpus.data(), corpus.data() + corpus.size(), &got);
    const bool same =
        want.offset == got.offset && want.label == got.label &&
        want.weight == got.weight && want.qid == got.qid &&
        want.field == got.field && want.index == got.index &&
        want.value == got.value && want.max_index == got.max_index &&
        want.max_field == got.max_field;
    if (!same) {
      fprintf(stderr, "MISMATCH: %s lane %s != scalar\n", name,
              dct::SimdTierName(t));
      ++failures;
    }
  }
  printf("%-8s ok (%zu rows, scalar == swar..%s)\n", name, want.Size(),
         dct::SimdTierName(dct::BestSupportedSimdTier()));
  return failures;
}

}  // namespace

int main(int argc, char** argv) {
  if (argc > 1 && std::string(argv[1]) == "--check") {
    const int rows = argc > 2
        ? static_cast<int>(dct::io::CheckedInt("rows", argv[2], 1,
                                               1 << 28))
        : 20000;
    int failures = 0;
    {
      std::string c = MakeLibsvm(rows, 28, 7);
      failures += CheckFormat<dct::LibSVMParser<uint32_t>>("libsvm", c, {});
    }
    {
      std::string c = MakeCSV(rows, 28, 7);
      failures += CheckFormat<dct::CSVParser<uint32_t>>("csv", c, {});
    }
    {
      std::string c = MakeLibfm(rows, 28, 7);
      failures += CheckFormat<dct::LibFMParser<uint32_t>>("libfm", c, {});
    }
    if (failures != 0) {
      fprintf(stderr, "%d lane mismatch(es)\n", failures);
      return 1;
    }
    printf("OK\n");
    return 0;
  }
  // checked CLI parses (analyze.py env rule): garbage args error loudly
  int rows = argc > 1 ? static_cast<int>(
      dct::io::CheckedInt("rows", argv[1], 1, 1 << 28)) : 100000;
  int reps = argc > 2 ? static_cast<int>(
      dct::io::CheckedInt("reps", argv[2], 1, 1 << 20)) : 7;
  {
    std::string c = MakeLibsvm(rows, 28, 7);
    BenchFormat<dct::LibSVMParser<uint32_t>>("libsvm", c, {}, reps);
  }
  {
    std::string c = MakeCSV(rows, 28, 7);
    BenchFormat<dct::CSVParser<uint32_t>>("csv", c, {}, reps);
  }
  {
    std::string c = MakeLibfm(rows, 28, 7);
    BenchFormat<dct::LibFMParser<uint32_t>>("libfm", c, {}, reps);
  }
  return 0;
}
