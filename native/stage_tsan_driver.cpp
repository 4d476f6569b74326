// Sanitizer exercise of the ingest engine: the concurrency arm
// (stage-counter accounting under TSan) plus single-threaded
// memory/UB arms (protobuf wire fuzz, dense-build boundary abuse) that
// give ASan and UBSan builds something to bite on.
//
// Built and run by tests/test_native_sanitizers.py (slow-marked) and
// scripts/native_sanitize.sh with each of -fsanitize=thread /
// address / undefined:
//   g++ -fsanitize=<mode> -O1 -g -std=c++17 -pthread
//       native/stage_tsan_driver.cpp native/ingest_engine.cpp -o <bin>
//
// Phase 1 hammers the counters from every direction at once — ingest
// threads (vn_ingest), a drain thread (vn_drain / vn_drain_clear),
// and a stats reader (vn_stage_stats / vn_stage_drain / vn_totals /
// vn_intern_count) — so a data race anywhere on the accounting path
// is a TSan report (nonzero exit), and finishes with a conservation
// check: after a final drain, parse-stage packets must equal the
// engine's packet total and stage-stage values its processed total.
// Phase 2 (wire fuzz) hand-encodes a forwardrpc.MetricList, routes
// and import-scans it intact, truncated at every stride, bit-flipped,
// and with degenerate ring/chunk arguments — corrupt wire bytes must
// yield a null fallback, never an out-of-bounds read; then a list of
// forwarded t-digests (centroids with samples and unknown fields)
// through vn_import_scan's digest descent: decoded values checked
// intact, then truncated at every cut and mutated at every byte, each
// surviving scan's columns read back whole.  Phase 3 feeds
// vn_build_tiers — as one operand and as two tiers — adversarial COO
// rows (negative ids, ids past the arena capacity, ids outside
// `touched`), corrupt touched ids and deep positions (out of order, out
// of range), operands too shallow or absent and a weighted tier without
// weights (nothing may be written), and, on sound input, operands left
// by a deeper interval that must come out as operands filled from zeros
// do, the same for every thread count.  Phase 4
// (SPSC stress)
// shrinks the staging rings to 2 slots so every handoff wraps and
// backpressures, runs TWO concurrent drainers against the producers,
// and checks exact packet conservation — a torn handoff (double-pop,
// lost steal) shows up as a count mismatch, a racy one as a TSan
// report.  Phase 5 (SIMD parity) asserts the scalar and SSE2/AVX2
// tokenizers and intern-key hashes compute identical results: direct
// vn_key_hash / vn_scan_tokens comparison over random bytes, then a
// seeded fuzz corpus (valid lines, truncations, bit-flips, degenerate
// tags) pushed through a scalar engine and a SIMD engine whose drains
// must serialize byte-for-byte.
//
// VN_SAN_ITERS / VN_SAN_THREADS shrink phases 1 and 4 for smoke runs
// (scripts/check.py uses VN_SAN_ITERS=2000).

#include <atomic>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <thread>
#include <vector>

extern "C" {
void* vn_engine_new(int max_packet_len, const char* implicit_tags_nl);
void vn_engine_free(void* ep);
int vn_thread_new(void* ep);
void vn_ingest(void* ep, int tid, const char* data, long len);
void* vn_drain(void* ep);
void* vn_drain_clear(void* ep);
void vn_drain_free(void* dp);
void vn_totals(void* ep, unsigned long long* out4);
unsigned long long vn_intern_count(void* ep);
long long vn_stage_thread_count(void* ep);
long long vn_stage_stats(void* ep, unsigned long long* out,
                         long long cap_threads);
void vn_stage_drain(void* ep, unsigned long long* out3);
unsigned long long vn_metro64(const char* data, long n);
void* vn_route(const uint8_t* data, long long len,
               const uint32_t* ring_hashes, const int32_t* ring_dests,
               long long ring_len, int n_dests, int chunk_max);
void vn_route_dest(void* handle, int d, const uint8_t** ptr,
                   long long* nbytes, long long* count);
void vn_route_free(void* handle);
void* vn_import_scan(const uint8_t* data, long long len);
long long vn_import_scan_n(void* handle);
void vn_import_scan_digests(void* handle, long long* n_cent,
                            const double** cent_mean,
                            const double** cent_weight,
                            const long long** cent_off,
                            const long long** cent_n,
                            const double** dmin, const double** dmax,
                            const double** drsum,
                            const double** compression);
void vn_import_scan_free(void* handle);
long long vn_build_tiers(const long long* rows, const double* vals,
                         const double* wts, long long n,
                         const long long* touched, long long nd,
                         const long long* deep, long long n_deep,
                         long long capacity, int* map, int* cursors,
                         float* const* dv, float* const* dw,
                         short* const* depths, const long long* u_pad,
                         const long long* d_pad, int n_threads,
                         long long* depth_out);
int vn_engine_opt(void* ep, const char* key, long long val);
long long vn_drain_section(void* dp, int which, const void** a,
                           const void** b, const void** c);
void vn_drain_stats(void* dp, unsigned long long* out4);
int vn_simd_supported(int mode);
unsigned long long vn_key_hash(const char* data, long n, int mode);
long long vn_scan_tokens(const char* data, long n, int mode,
                         long long* out_pos, unsigned char* out_cls,
                         long long cap);
}

namespace {

void put_varint(std::vector<uint8_t>& v, uint64_t x) {
  while (x >= 0x80) {
    v.push_back((uint8_t)(x | 0x80));
    x >>= 7;
  }
  v.push_back((uint8_t)x);
}

// Hand-encoded `repeated Metric metrics = 1` list: name (1), one tag
// (2), type enum (3) per record — the three fields vn_route keys on.
std::vector<uint8_t> make_metric_list(int n) {
  std::vector<uint8_t> ml;
  char buf[48];
  for (int i = 0; i < n; i++) {
    std::vector<uint8_t> m;
    int nl = snprintf(buf, sizeof buf, "svc.wire.metric.%d", i);
    m.push_back(0x0A);
    put_varint(m, (uint64_t)nl);
    m.insert(m.end(), buf, buf + nl);
    int tl = snprintf(buf, sizeof buf, "shard:%d", i % 7);
    m.push_back(0x12);
    put_varint(m, (uint64_t)tl);
    m.insert(m.end(), buf, buf + tl);
    m.push_back(0x18);
    put_varint(m, (uint64_t)(i % 5));
    ml.push_back(0x0A);
    put_varint(ml, m.size());
    ml.insert(ml.end(), m.begin(), m.end());
  }
  return ml;
}

void put_double(std::vector<uint8_t>& v, uint8_t tag, double x) {
  v.push_back(tag);
  uint8_t b[8];
  memcpy(b, &x, 8);
  v.insert(v.end(), b, b + 8);
}

void put_sub(std::vector<uint8_t>& v, uint8_t tag,
             const std::vector<uint8_t>& body) {
  v.push_back(tag);
  put_varint(v, body.size());
  v.insert(v.end(), body.begin(), body.end());
}

// Hand-encoded histogram records: name (1), tag (2), type (3),
// HistogramValue (7) { t_digest (1) { kCents x main_centroids (1)
// { mean (1), weight (2), packed samples (3), an unknown varint (15) },
// compression (2), min (3), max (4), reciprocalSum (5), an unknown
// length-delimited field (9) } }, scope (9) — every level of the
// descent vn_import_scan makes into a forwarded t-digest.
const int kDigests = 16, kCents = 6;

std::vector<uint8_t> make_digest_list() {
  std::vector<uint8_t> ml;
  char buf[48];
  for (int i = 0; i < kDigests; i++) {
    std::vector<uint8_t> td;
    for (int c = 0; c < kCents; c++) {
      std::vector<uint8_t> cent;
      put_double(cent, 0x09, 1.0 + i + 0.25 * c);
      put_double(cent, 0x11, 1.0 + (c % 3));
      std::vector<uint8_t> samples(16, 0);
      put_sub(cent, 0x1A, samples);
      cent.push_back(0x78);
      put_varint(cent, 300);
      put_sub(td, 0x0A, cent);
    }
    put_double(td, 0x11, 100.0);
    put_double(td, 0x19, 1.0 + i);
    put_double(td, 0x21, 3.0 + i);
    put_double(td, 0x29, 0.5);
    put_sub(td, 0x4A, std::vector<uint8_t>{1, 2, 3});
    std::vector<uint8_t> hv;
    put_sub(hv, 0x0A, td);
    std::vector<uint8_t> m;
    int nl = snprintf(buf, sizeof buf, "svc.wire.digest.%d", i % 5);
    m.push_back(0x0A);
    put_varint(m, (uint64_t)nl);
    m.insert(m.end(), buf, buf + nl);
    int tl = snprintf(buf, sizeof buf, "shard:%d", i % 3);
    m.push_back(0x12);
    put_varint(m, (uint64_t)tl);
    m.insert(m.end(), buf, buf + tl);
    m.push_back(0x18);
    put_varint(m, (uint64_t)(i % 2 ? 4 : 2));   // Timer / Histogram
    put_sub(m, 0x3A, hv);
    m.push_back(0x48);
    put_varint(m, (uint64_t)(i % 3));           // scope
    put_sub(ml, 0x0A, m);
  }
  return ml;
}

// The digest descent of vn_import_scan: the intact list decodes to the
// values encoded above, and every truncation and every single-byte
// mutation parses or falls back without reading out of bounds.
int digest_wire_fuzz() {
  std::vector<uint8_t> ml = make_digest_list();
  void* s = vn_import_scan(ml.data(), (long long)ml.size());
  if (s == nullptr || vn_import_scan_n(s) != kDigests) {
    fprintf(stderr, "digest fuzz: intact list failed to scan\n");
    if (s) vn_import_scan_free(s);
    return 1;
  }
  long long n_cent = 0;
  const double *mean, *weight, *dmin, *dmax, *drsum, *comp;
  const long long *off, *cnt;
  vn_import_scan_digests(s, &n_cent, &mean, &weight, &off, &cnt, &dmin,
                         &dmax, &drsum, &comp);
  int rc = 0;
  if (n_cent != (long long)kDigests * kCents) rc = 1;
  for (int i = 0; i < kDigests && rc == 0; i++) {
    if (off[i] != (long long)i * kCents || cnt[i] != kCents ||
        comp[i] != 100.0 || dmin[i] != 1.0 + i || dmax[i] != 3.0 + i ||
        drsum[i] != 0.5)
      rc = 1;
    for (int c = 0; c < kCents && rc == 0; c++)
      if (mean[off[i] + c] != 1.0 + i + 0.25 * c ||
          weight[off[i] + c] != 1.0 + (c % 3))
        rc = 1;
  }
  vn_import_scan_free(s);
  if (rc) {
    fprintf(stderr, "digest fuzz: intact list decoded wrongly\n");
    return 1;
  }
  // a scan that survives is read back whole, so a range the scan got
  // wrong is an out-of-bounds read here and not only in python
  auto read_back = [](void* h) {
    long long nc = 0;
    const double *a, *b, *c, *d, *e, *f;
    const long long *o, *k;
    vn_import_scan_digests(h, &nc, &a, &b, &o, &k, &c, &d, &e, &f);
    long long n = vn_import_scan_n(h);
    double sum = 0;
    for (long long i = 0; i < n; i++) {
      if (o[i] < 0 || k[i] < 0 || o[i] + k[i] > nc) return false;
      for (long long j = o[i]; j < o[i] + k[i]; j++) sum += a[j] + b[j];
      sum += c[i] + d[i] + e[i] + f[i];
    }
    volatile double sink = sum;   // the reads must happen
    (void)sink;
    return true;
  };
  for (size_t cut = 0; cut <= ml.size(); cut++) {
    void* ss = vn_import_scan(ml.data(), (long long)cut);
    if (ss) {
      bool fine = read_back(ss);
      vn_import_scan_free(ss);
      if (!fine) {
        fprintf(stderr, "digest fuzz: bad ranges at cut %zu\n", cut);
        return 1;
      }
    }
  }
  std::vector<uint8_t> mut(ml);
  const uint8_t flips[] = {0xFF, 0x80, 0x01, 0x7F};
  for (size_t i = 0; i < mut.size(); i++) {
    for (uint8_t flip : flips) {
      mut[i] ^= flip;
      void* ss = vn_import_scan(mut.data(), (long long)mut.size());
      if (ss) {
        bool fine = read_back(ss);
        vn_import_scan_free(ss);
        if (!fine) {
          fprintf(stderr, "digest fuzz: bad ranges, byte %zu\n", i);
          return 1;
        }
      }
      mut[i] ^= flip;
    }
  }
  return 0;
}

int wire_fuzz() {
  const int kMetrics = 64;
  std::vector<uint8_t> ml = make_metric_list(kMetrics);
  uint32_t ring_hashes[8];
  int32_t ring_dests[8];
  for (int i = 0; i < 8; i++) {
    ring_hashes[i] = (uint32_t)i * 0x20000000u;
    ring_dests[i] = i % 2;
  }
  // intact: every record routes to exactly one of two destinations
  void* r = vn_route(ml.data(), (long long)ml.size(), ring_hashes,
                     ring_dests, 8, 2, 3);
  if (r == nullptr) {
    fprintf(stderr, "wire fuzz: intact list failed to route\n");
    return 1;
  }
  long long total = 0;
  for (int d = 0; d < 2; d++) {
    const uint8_t* p;
    long long nb, cnt;
    vn_route_dest(r, d, &p, &nb, &cnt);
    total += cnt;
  }
  vn_route_free(r);
  if (total != kMetrics) {
    fprintf(stderr, "wire fuzz: routed %lld of %d metrics\n", total,
            kMetrics);
    return 1;
  }
  void* s = vn_import_scan(ml.data(), (long long)ml.size());
  if (s == nullptr || vn_import_scan_n(s) != kMetrics) {
    fprintf(stderr, "wire fuzz: intact list failed to scan\n");
    if (s) vn_import_scan_free(s);
    return 1;
  }
  vn_import_scan_free(s);
  // truncation sweep: every prefix must parse or fall back, never
  // read past the buffer (the ASan payoff)
  for (size_t cut = 0; cut <= ml.size(); cut += 3) {
    void* rr = vn_route(ml.data(), (long long)cut, ring_hashes,
                        ring_dests, 8, 2, 3);
    if (rr) vn_route_free(rr);
    void* ss = vn_import_scan(ml.data(), (long long)cut);
    if (ss) vn_import_scan_free(ss);
  }
  // bit-flip sweep: corrupt tags/lengths/varints in place
  std::vector<uint8_t> mut(ml);
  for (size_t i = 0; i < mut.size(); i += 5) {
    mut[i] ^= 0xFF;
    void* rr = vn_route(mut.data(), (long long)mut.size(), ring_hashes,
                        ring_dests, 8, 2, 3);
    if (rr) vn_route_free(rr);
    void* ss = vn_import_scan(mut.data(), (long long)mut.size());
    if (ss) vn_import_scan_free(ss);
    mut[i] ^= 0xFF;
  }
  // degenerate arguments: empty ring, zero destinations, chunk_max=0
  // (was a division by zero before the guard) — all must refuse
  if (vn_route(ml.data(), (long long)ml.size(), ring_hashes,
               ring_dests, 0, 2, 3) != nullptr ||
      vn_route(ml.data(), (long long)ml.size(), ring_hashes,
               ring_dests, 8, 0, 3) != nullptr ||
      vn_route(ml.data(), (long long)ml.size(), ring_hashes,
               ring_dests, 8, 2, 0) != nullptr) {
    fprintf(stderr, "wire fuzz: degenerate args were not refused\n");
    return 1;
  }
  vn_metro64((const char*)ml.data(), (long)ml.size());
  vn_metro64("", 0);
  return digest_wire_fuzz();
}

int env_int(const char* name, int dflt) {
  const char* v = getenv(name);
  if (v == nullptr || *v == '\0') return dflt;
  int out = atoi(v);
  return out > 0 ? out : dflt;
}

// -- phase 4: SPSC staging-ring stress --------------------------------------

int spsc_stress() {
  void* e = vn_engine_new(4096, "env:spsc");
  // 2-slot rings + 4-packet batches: every publish wraps the ring and
  // most of them find it full, so the producer-side accumulate path and
  // the drainer-side cur steal both run constantly
  if (vn_engine_opt(e, "ring_slots", 2) != 0 ||
      vn_engine_opt(e, "batch", 4) != 0) {
    fprintf(stderr, "spsc stress: engine options rejected\n");
    vn_engine_free(e);
    return 1;
  }
  const int kThreads = env_int("VN_SAN_THREADS", 4);
  const int kIters = env_int("VN_SAN_ITERS", 20000) / 2 + 1;
  std::atomic<bool> stop{false};
  std::atomic<unsigned long long> drained_pkts{0};

  std::vector<std::thread> workers;
  for (int t = 0; t < kThreads; t++) {
    int tid = vn_thread_new(e);
    workers.emplace_back([e, tid, t, kIters] {
      char buf[96];
      for (int i = 0; i < kIters; i++) {
        int n = snprintf(buf, sizeof buf, "spsc.m%d:%d|c|#thr:%d",
                         i % 29, i, t);
        vn_ingest(e, tid, buf, n);
      }
    });
  }
  // TWO concurrent drainers: drain_mu must keep each ring
  // single-consumer; a torn pop double-counts or drops a batch, which
  // the conservation check below catches even without TSan
  std::vector<std::thread> drainers;
  for (int di = 0; di < 2; di++) {
    drainers.emplace_back([e, di, &stop, &drained_pkts] {
      int i = 0;
      while (!stop.load(std::memory_order_relaxed)) {
        void* d = (di == 0 && ++i % 32 == 0) ? vn_drain_clear(e)
                                             : vn_drain(e);
        unsigned long long s4[4];
        vn_drain_stats(d, s4);
        drained_pkts.fetch_add(s4[2], std::memory_order_relaxed);
        vn_drain_free(d);
      }
    });
  }
  for (auto& w : workers) w.join();
  stop.store(true);
  for (auto& d : drainers) d.join();
  {
    void* d = vn_drain(e);  // consolidate ring tails + stolen curs
    unsigned long long s4[4];
    vn_drain_stats(d, s4);
    drained_pkts.fetch_add(s4[2], std::memory_order_relaxed);
    vn_drain_free(d);
  }
  int rc = 0;
  unsigned long long want =
      (unsigned long long)kThreads * (unsigned long long)kIters;
  unsigned long long t4[4];
  vn_totals(e, t4);
  if (drained_pkts.load() != want || t4[2] != want) {
    fprintf(stderr, "spsc stress: conservation failed: drained=%llu "
                    "totals=%llu want=%llu\n",
            drained_pkts.load(), t4[2], want);
    rc = 1;
  }
  vn_engine_free(e);
  return rc;
}

// -- phase 5: scalar/SIMD parity --------------------------------------------

uint64_t lcg_next(uint64_t* s) {
  *s = *s * 6364136223846793005ULL + 1442695040888963407ULL;
  return *s >> 33;
}

// Seeded fuzz corpus: well-formed lines across every metric family,
// truncations at random byte offsets, single bit-flips, and degenerate
// tag sections.  Deterministic, so both engines see identical bytes.
std::vector<std::vector<uint8_t>> parity_corpus() {
  std::vector<std::vector<uint8_t>> out;
  const char* degenerate[] = {
      "par.d1:1|c|#",       "par.d2:2|c|#,,",     "par.d3:3|g|#:,x:",
      "par.d4:4|ms|@0.5|#a:b,a:b", "par.d5:1:2:3|h|#t:u",
      "par.d6:nan|g",       "par.d7:+1e3|c",      "par.d8:1_0|c",
      ":|",                 "a:|c",               "par.d9:1|q",
      "",                   "\n\n",               "#only:tags",
      "par.d10:1|c|@",      "par.d11:1|",
  };
  for (const char* s : degenerate)
    out.emplace_back((const uint8_t*)s, (const uint8_t*)s + strlen(s));
  uint64_t seed = 0xC0FFEE5EEDULL;
  char buf[256];
  for (int i = 0; i < 200; i++) {
    int n = snprintf(
        buf, sizeof buf,
        "par.m%llu:%llu|%s|#k%llu:v%llu,env:prod\npar.x:%llu|ms|@0.25",
        (unsigned long long)(lcg_next(&seed) % 37),
        (unsigned long long)(lcg_next(&seed) % 100000),
        (lcg_next(&seed) & 1) ? "c" : "h",
        (unsigned long long)(lcg_next(&seed) % 11),
        (unsigned long long)(lcg_next(&seed) % 13),
        (unsigned long long)(lcg_next(&seed) % 997));
    std::vector<uint8_t> v(buf, buf + n);
    out.push_back(v);
    out.emplace_back(v.begin(),
                     v.begin() + (long)(lcg_next(&seed) % (n + 1)));
    std::vector<uint8_t> f(v);
    f[lcg_next(&seed) % f.size()] ^=
        (uint8_t)(1u << (lcg_next(&seed) % 8));
    out.push_back(f);
  }
  return out;
}

void blob_append(std::vector<uint8_t>& blob, const void* p, size_t n) {
  if (n == 0) return;
  const uint8_t* q = (const uint8_t*)p;
  blob.insert(blob.end(), q, q + n);
}

// Drain an engine and serialize every section — ids, values, weights,
// set hashes, interned keys blob, other-lines blob — into one byte
// string, so parity is a single memcmp.
std::vector<uint8_t> drain_blob(void* e, unsigned long long out4[4]) {
  void* d = vn_drain(e);
  vn_drain_stats(d, out4);
  std::vector<uint8_t> blob;
  for (int w = 0; w <= 5; w++) {
    const void *a = nullptr, *b = nullptr, *c = nullptr;
    long long n = vn_drain_section(d, w, &a, &b, &c);
    blob_append(blob, &n, sizeof n);
    switch (w) {
      case 0:  // counters: u32 ids + f64 values
      case 1:  // gauges
        blob_append(blob, a, (size_t)n * 4);
        blob_append(blob, b, (size_t)n * 8);
        break;
      case 2:  // histograms: ids + values + weights
        blob_append(blob, a, (size_t)n * 4);
        blob_append(blob, b, (size_t)n * 8);
        blob_append(blob, c, (size_t)n * 8);
        break;
      case 3:  // sets: u32 ids + u64 element hashes
        blob_append(blob, a, (size_t)n * 4);
        blob_append(blob, b, (size_t)n * 8);
        break;
      case 4: {  // interned keys blob (b carries the byte length)
        unsigned long long nb = (unsigned long long)(uintptr_t)b;
        blob_append(blob, &nb, sizeof nb);
        blob_append(blob, a, (size_t)nb);
        break;
      }
      case 5:  // events / service checks blob
        blob_append(blob, a, (size_t)n);
        break;
    }
  }
  vn_drain_free(d);
  return blob;
}

int simd_parity() {
  int rc = 0;
  // direct kernel parity: intern-key hash and token scan over random
  // bytes (which naturally contain '\n' ':' '|') at every length that
  // straddles the 16B/32B vector tails
  uint64_t seed = 0x5EEDF00DULL;
  uint8_t rnd[160];
  for (int len = 0; len <= (int)sizeof rnd; len++) {
    for (int i = 0; i < len; i++) rnd[i] = (uint8_t)lcg_next(&seed);
    unsigned long long ref = vn_key_hash((const char*)rnd, len, 1);
    long long pos1[176];
    unsigned char cls1[176];
    long long n1 =
        vn_scan_tokens((const char*)rnd, len, 1, pos1, cls1, 176);
    for (int m = 2; m <= 3; m++) {
      if (!vn_simd_supported(m)) continue;
      if (vn_key_hash((const char*)rnd, len, m) != ref) {
        fprintf(stderr, "simd parity: key_hash mode=%d len=%d\n", m,
                len);
        rc = 1;
      }
      long long pos2[176];
      unsigned char cls2[176];
      long long n2 =
          vn_scan_tokens((const char*)rnd, len, m, pos2, cls2, 176);
      if (n1 != n2 ||
          memcmp(pos1, pos2, (size_t)n1 * sizeof pos1[0]) != 0 ||
          memcmp(cls1, cls2, (size_t)n1) != 0) {
        fprintf(stderr, "simd parity: scan_tokens mode=%d len=%d "
                        "(%lld vs %lld tokens)\n", m, len, n1, n2);
        rc = 1;
      }
    }
  }
  // end-to-end parity: identical fuzz bytes through a scalar engine
  // and a SIMD engine must drain byte-for-byte the same — same intern
  // ids in the same order, same staged values, same rejects
  std::vector<std::vector<uint8_t>> corpus = parity_corpus();
  for (int m = 2; m <= 3; m++) {
    if (!vn_simd_supported(m)) continue;
    void* es = vn_engine_new(4096, "env:par");
    void* ev = vn_engine_new(4096, "env:par");
    if (vn_engine_opt(es, "simd", 1) != 0 ||
        vn_engine_opt(ev, "simd", m) != 0) {
      fprintf(stderr, "simd parity: simd option rejected (mode=%d)\n",
              m);
      vn_engine_free(es);
      vn_engine_free(ev);
      return 1;
    }
    int ts = vn_thread_new(es), tv = vn_thread_new(ev);
    for (const auto& dgram : corpus) {
      vn_ingest(es, ts, (const char*)dgram.data(), (long)dgram.size());
      vn_ingest(ev, tv, (const char*)dgram.data(), (long)dgram.size());
    }
    unsigned long long a4[4], b4[4];
    std::vector<uint8_t> ba = drain_blob(es, a4);
    std::vector<uint8_t> bb = drain_blob(ev, b4);
    if (memcmp(a4, b4, sizeof a4) != 0) {
      fprintf(stderr, "simd parity: drain stats diverge (mode=%d): "
                      "%llu/%llu/%llu/%llu vs %llu/%llu/%llu/%llu\n",
              m, a4[0], a4[1], a4[2], a4[3], b4[0], b4[1], b4[2],
              b4[3]);
      rc = 1;
    }
    if (ba != bb) {
      fprintf(stderr, "simd parity: drained sections diverge "
                      "(mode=%d, %zu vs %zu bytes)\n",
              m, ba.size(), bb.size());
      rc = 1;
    }
    if (vn_intern_count(es) != vn_intern_count(ev)) {
      fprintf(stderr, "simd parity: intern counts diverge (mode=%d)\n",
              m);
      rc = 1;
    }
    vn_engine_free(es);
    vn_engine_free(ev);
  }
  return rc;
}

}  // namespace

// vn_build_tiers: one operand (n_deep 0, an absent tier 1) or two from
// one COO.  Corrupt ids (in rows, in touched, in the deep positions), a
// row past its tier's d_pad, no operands, a weighted tier without
// weights: nothing written, not a cell and not a record.  Sound input
// over operands that a deeper, wider interval left (the call zeroes only
// what its record says was filled past a row's new count): the same
// cells as a fill of zeroed operands, for every thread count, each row's
// points in arrival order.
struct TierSet {
  std::vector<float> v[2], w[2];
  std::vector<short> rec[2];
  float* dv[2];
  float* dw[2];
  short* depths[2];
  TierSet(const long long* u_pad, const long long* d_pad, bool uniform) {
    for (int k = 0; k < 2; k++) {
      v[k].assign((size_t)(u_pad[k] * d_pad[k]), 0.f);
      w[k].assign((size_t)(u_pad[k] * d_pad[k]), 0.f);
      rec[k].assign((size_t)u_pad[k], 0);
      dv[k] = u_pad[k] ? v[k].data() : nullptr;
      dw[k] = ((k == 0 && uniform) || !u_pad[k]) ? nullptr : w[k].data();
      depths[k] = u_pad[k] ? rec[k].data() : nullptr;
    }
  }
  bool untouched() const {
    for (int k = 0; k < 2; k++) {
      for (float x : v[k]) if (x != 0.f) return false;
      for (float x : w[k]) if (x != 0.f) return false;
      for (short x : rec[k]) if (x != 0) return false;
    }
    return true;
  }
};

int build_tiers_fuzz(const long long n_deep) {
  const long long n = 4099, cap = 64, nd = 13;
  const long long has_deep = n_deep ? 1 : 0;
  const long long u_pad[2] = {16, 4 * has_deep};
  const long long d_pad[2] = {512, 512 * has_deep};
  const long long shallow[2] = {8, 512 * has_deep}, none[2] = {0, 0};
  std::vector<long long> touched(nd), rows(n), deep = {2, 7, 11};
  deep.resize((size_t)n_deep);
  std::vector<double> vals(n), wts(n);
  for (long long i = 0; i < nd; i++) touched[i] = i * 4 + 1;
  // dense slot of touched position k: tail rows in order, then the deep
  std::vector<long long> slot(nd), count(nd, 0);
  {
    long long t = 0, d = 0;
    for (long long k = 0; k < nd; k++)
      slot[k] = (d < n_deep && deep[d] == k) ? u_pad[0] + d++ : t++;
  }
  for (long long i = 0; i < n; i++) {
    long long k = (i * 7 + i / 5) % nd;
    rows[i] = touched[k];
    vals[i] = (double)(i + 1);
    wts[i] = (double)(i % 9 + 1) / 3.0;
    count[k]++;
  }
  // the interval under test: every third point, and none of row 5's
  std::vector<long long> rows2;
  std::vector<double> vals2, wts2;
  std::vector<long long> count2(nd, 0);
  for (long long i = 0; i < n; i += 3) {
    long long k = (i * 7 + i / 5) % nd;
    if (k == 5) continue;
    rows2.push_back(rows[i]);
    vals2.push_back(vals[i]);
    wts2.push_back(wts[i]);
    count2[k]++;
  }
  const long long n2 = (long long)rows2.size();
  float* no_ops[2] = {nullptr, nullptr};
  short* no_rec[2] = {nullptr, nullptr};
  for (bool uniform : {false, true}) {
    // a build with no weighted tier takes no weights at all
    const bool weightless = uniform && !n_deep;
    const double* w1 = weightless ? nullptr : wts.data();
    const double* w2 = weightless ? nullptr : wts2.data();
    std::vector<float> want_v[2], want_w[2];
    for (int threads : {1, 2, 3, 4}) {
      std::vector<int> map((size_t)cap, 12345);
      std::vector<int> cursors(
          (size_t)((threads + 1) * (u_pad[0] + u_pad[1])), -7);
      long long depth[2] = {-1, -1};
      TierSet ops(u_pad, d_pad, uniform);
      long long st = vn_build_tiers(
          rows.data(), vals.data(), w1, n, touched.data(), nd,
          deep.data(), n_deep, cap, map.data(), cursors.data(), no_ops,
          no_ops, no_rec, u_pad, none, threads, depth);
      long long deepest[2] = {0, 0};
      for (long long k = 0; k < nd; k++) {
        long long* d = &deepest[slot[k] >= u_pad[0]];
        if (count[k] > *d) *d = count[k];
      }
      // a row past its tier's d_pad: the depth comes back, no cell written
      TierSet thin(u_pad, shallow, uniform);
      long long st2 = vn_build_tiers(
          rows.data(), vals.data(), w1, n, touched.data(), nd,
          deep.data(), n_deep, cap, map.data(), cursors.data(), thin.dv,
          thin.dw, thin.depths, u_pad, shallow, threads, depth);
      if (st != -1 || st2 != -1 || depth[0] != deepest[0] ||
          depth[1] != deepest[1] || !thin.untouched()) {
        fprintf(stderr, "tiers fuzz: count-only / shallow call wrong "
                        "(deep=%lld threads=%d, %lld %lld)\n", n_deep,
                threads, st, st2);
        return 1;
      }
      // corrupt ids: refused, nothing written
      for (long long bad : {-5LL, cap + 3, 2LL /* not touched */}) {
        long long keep = rows[n / 2];
        rows[n / 2] = bad;
        st = vn_build_tiers(
            rows.data(), vals.data(), w1, n, touched.data(), nd,
            deep.data(), n_deep, cap, map.data(), cursors.data(), ops.dv,
            ops.dw, ops.depths, u_pad, d_pad, threads, depth);
        rows[n / 2] = keep;
        if (st <= 0 || !ops.untouched()) {
          fprintf(stderr, "tiers fuzz: corrupt row id %lld not refused "
                          "(deep=%lld threads=%d)\n", bad, n_deep, threads);
          return 1;
        }
      }
      for (int which = 0; which < 6; which++) {
        std::vector<long long> t2 = touched, d2 = deep;
        long long nd2 = nd, n_deep2 = n_deep;
        const double* w = w1;
        if (which == 0) t2[3] = cap;
        if (which == 1) t2[3] = -2;
        if (which == 2) {
          // a weighted tier and no weights
          if (weightless) continue;
          w = nullptr;
        }
        if (which >= 3 && !n_deep) {
          // (no deep positions to spoil: more deep rows than tier 1
          // holds, more touched rows than tier 0 does)
          if (which == 3) {
            d2 = {4};
            n_deep2 = 1;
          } else if (which == 4) {
            t2.resize(17, 3);
            nd2 = 17;
          } else {
            continue;
          }
        } else {
          if (which == 3) std::swap(d2[0], d2[1]);
          if (which == 4) d2[2] = nd;
          if (which == 5) d2[0] = -1;
        }
        st = vn_build_tiers(
            rows.data(), vals.data(), w, n, t2.data(), nd2, d2.data(),
            n_deep2, cap, map.data(), cursors.data(), ops.dv, ops.dw,
            ops.depths, u_pad, d_pad, threads, depth);
        if (st <= 0 || !ops.untouched()) {
          fprintf(stderr, "tiers fuzz: corrupt touched / deep / weights "
                          "(%d) not refused (deep=%lld)\n", which, n_deep);
          return 1;
        }
      }
      // the deeper, wider interval first; then the one under test into
      // what it left, against the same from zeros
      st = vn_build_tiers(
          rows.data(), vals.data(), w1, n, touched.data(), nd,
          deep.data(), n_deep, cap, map.data(), cursors.data(), ops.dv,
          ops.dw, ops.depths, u_pad, d_pad, threads, depth);
      st2 = vn_build_tiers(
          rows2.data(), vals2.data(), w2, n2, touched.data(), nd,
          deep.data(), n_deep, cap, map.data(), cursors.data(), ops.dv,
          ops.dw, ops.depths, u_pad, d_pad, threads, depth);
      TierSet clean(u_pad, d_pad, uniform);
      long long st3 = vn_build_tiers(
          rows2.data(), vals2.data(), w2, n2, touched.data(), nd,
          deep.data(), n_deep, cap, map.data(), cursors.data(), clean.dv,
          clean.dw, clean.depths, u_pad, d_pad, threads, depth);
      if (st != 0 || st2 != 0 || st3 != 0) {
        fprintf(stderr, "tiers fuzz: sound input not filled "
                        "(deep=%lld threads=%d)\n", n_deep, threads);
        return 1;
      }
      for (int k = 0; k < 2; k++) {
        if (ops.v[k] != clean.v[k] || ops.rec[k] != clean.rec[k] ||
            (ops.dw[k] && ops.w[k] != clean.w[k])) {
          fprintf(stderr, "tiers fuzz: tier %d keeps a stale cell "
                          "(deep=%lld threads=%d)\n", k, n_deep, threads);
          return 1;
        }
      }
      for (long long k = 0; k < nd; k++) {
        int tier = slot[k] >= u_pad[0];
        long long r = tier ? slot[k] - u_pad[0] : slot[k];
        if (ops.rec[tier][(size_t)r] != count2[k]) {
          fprintf(stderr, "tiers fuzz: record of row %lld\n", k);
          return 1;
        }
        for (long long c = 0; c < d_pad[tier]; c++) {
          float v = ops.v[tier][(size_t)(r * d_pad[tier] + c)];
          // arrival order: a row's values ascend; its tail is zero
          if (c >= count2[k]
                  ? v != 0.f
                  : (c > 0 &&
                     v <= ops.v[tier][(size_t)(r * d_pad[tier] + c - 1)])) {
            fprintf(stderr, "tiers fuzz: cell [%lld, %lld] of tier %d "
                            "(deep=%lld threads=%d)\n", r, c, tier, n_deep,
                    threads);
            return 1;
          }
        }
      }
      for (int k = 0; k < 2; k++) {
        if (want_v[k].empty()) {
          want_v[k] = ops.v[k];
          want_w[k] = ops.w[k];
        } else if (ops.v[k] != want_v[k] || ops.w[k] != want_w[k]) {
          fprintf(stderr, "tiers fuzz: %d threads built another "
                          "operand (deep=%lld)\n", threads, n_deep);
          return 1;
        }
      }
    }
  }
  return 0;
}

int main() {
  void* e = vn_engine_new(4096, "env:tsan");
  const int kIngestThreads = env_int("VN_SAN_THREADS", 4);
  const int kIters = env_int("VN_SAN_ITERS", 20000);
  std::atomic<bool> stop{false};

  std::vector<std::thread> workers;
  for (int t = 0; t < kIngestThreads; t++) {
    int tid = vn_thread_new(e);
    workers.emplace_back([e, tid, t, kIters] {
      char buf[224];
      for (int i = 0; i < kIters; i++) {
        // every metric family the parser speaks, plus a sampled
        // timer and a malformed tail line
        int n = snprintf(buf, sizeof(buf),
                         "tsan.m%d:%d|c|#thr:%d\ntsan.h:%d|h|@0.5\n"
                         "tsan.s:u%d|s\ntsan.g:%d|g\n"
                         "tsan.t:%d|ms|@0.25\nbad line",
                         i % 37, i, t, i % 101, i % 17, i % 23,
                         i % 19);
        vn_ingest(e, tid, buf, n);
      }
    });
  }
  std::thread drainer([e, &stop] {
    int i = 0;
    while (!stop.load(std::memory_order_relaxed)) {
      void* d = (++i % 16 == 0) ? vn_drain_clear(e) : vn_drain(e);
      vn_drain_free(d);
    }
  });
  std::thread reader([e, &stop] {
    unsigned long long rows[64 * 8], d3[3], t4[4];
    while (!stop.load(std::memory_order_relaxed)) {
      vn_stage_stats(e, rows, 64);
      vn_stage_drain(e, d3);
      vn_totals(e, t4);
      vn_intern_count(e);
    }
  });

  for (auto& w : workers) w.join();
  stop.store(true);
  drainer.join();
  reader.join();
  vn_drain_free(vn_drain(e));  // consolidate the tail

  // conservation: per-stage counters must reconcile with engine totals
  unsigned long long t4[4];
  vn_totals(e, t4);  // processed, malformed, packets, too_long
  long long n = vn_stage_thread_count(e);
  std::vector<unsigned long long> rows((size_t)n * 8);
  n = vn_stage_stats(e, rows.data(), n);
  unsigned long long parse_pkts = 0, stage_vals = 0;
  for (long long i = 0; i < n; i++) {
    parse_pkts += rows[i * 8 + 2];
    stage_vals += rows[i * 8 + 6];
  }
  unsigned long long d3[3];
  vn_stage_drain(e, d3);
  int rc = 0;
  unsigned long long want_pkts =
      (unsigned long long)kIngestThreads * kIters;
  if (parse_pkts != want_pkts || t4[2] != want_pkts) {
    fprintf(stderr, "packet conservation failed: parse=%llu totals=%llu "
                    "want=%llu\n", parse_pkts, t4[2], want_pkts);
    rc = 1;
  }
  if (stage_vals != t4[0]) {
    fprintf(stderr, "value conservation failed: stage=%llu "
                    "processed=%llu\n", stage_vals, t4[0]);
    rc = 1;
  }
  if (d3[1] != t4[2]) {
    fprintf(stderr, "drain conservation failed: drained=%llu "
                    "packets=%llu\n", d3[1], t4[2]);
    rc = 1;
  }
  vn_engine_free(e);
  rc |= wire_fuzz();
  rc |= build_tiers_fuzz(3);
  rc |= build_tiers_fuzz(0);
  rc |= spsc_stress();
  rc |= simd_parity();
  if (rc == 0)
    fprintf(stderr,
            "sanitize driver ok: %llu pkts, %llu values, wire fuzz + "
            "dense build + spsc stress + simd parity clean\n",
            parse_pkts, stage_vals);
  return rc;
}
