// Native text ingest: mmap + OpenMP delimited parse and bin encode.
//
// Role parity with the reference's native DatasetLoader/Parser pipeline
// (src/io/dataset_loader.cpp LoadFromFile + parser.cpp CSV/TSV parsers +
// bin.h ValueToBin:452-488): the reference parses training text and pushes
// binned values with native code; these entry points give the Python
// loader the same native fast path (ctypes, see lightgbm_tpu/io/parser.py
// and io/binning.py), with the tolerant Python parsers as the fallback.
//
// Scope: plain numeric CSV/TSV (no quoting — same contract as the pandas
// fast path it replaces); LibSVM stays in Python.

#include <algorithm>
#include <cctype>
#include <charconv>
#include <cmath>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <string>
#include <vector>

#include <fcntl.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <unistd.h>

#ifdef _OPENMP
#include <omp.h>
#endif

namespace {

struct Mapped {
  const char* data = nullptr;
  size_t size = 0;
  int fd = -1;
  bool ok() const { return data != nullptr; }
};

Mapped map_file(const char* path) {
  Mapped m;
  m.fd = ::open(path, O_RDONLY);
  if (m.fd < 0) return m;
  struct stat st;
  if (::fstat(m.fd, &st) != 0 || st.st_size == 0) {
    ::close(m.fd);
    m.fd = -1;
    return m;
  }
  void* p = ::mmap(nullptr, st.st_size, PROT_READ, MAP_PRIVATE, m.fd, 0);
  if (p == MAP_FAILED) {
    ::close(m.fd);
    m.fd = -1;
    return m;
  }
  m.data = static_cast<const char*>(p);
  m.size = st.st_size;
  return m;
}

void unmap_file(Mapped& m) {
  if (m.data) ::munmap(const_cast<char*>(m.data), m.size);
  if (m.fd >= 0) ::close(m.fd);
  m.data = nullptr;
  m.fd = -1;
}

bool line_blank(const char* b, const char* e, char sep) {
  for (const char* p = b; p < e; ++p) {
    if (*p == sep) return false;  // separators make it a data row of
                                  // empty fields, not a blank line
    if (!std::isspace(static_cast<unsigned char>(*p))) return false;
  }
  return true;
}

// skip the header (the first NON-BLANK line — the Python sniffer ignores
// leading blank lines) if present; returns body start
const char* body_start(const Mapped& m, int has_header, char sep) {
  const char* p = m.data;
  const char* end = m.data + m.size;
  if (!has_header) return p;
  while (p < end) {
    const char* nl = static_cast<const char*>(memchr(p, '\n', end - p));
    const char* le = nl ? nl : end;
    bool blank = line_blank(p, le, sep);
    p = nl ? nl + 1 : end;
    if (!blank) break;  // consumed the header line
  }
  return p;
}

// missing markers of the Python parsers: '', na, nan, null, n/a, none, ?
bool is_missing_token(const char* b, const char* e) {
  while (b < e && std::isspace(static_cast<unsigned char>(*b))) ++b;
  while (e > b && std::isspace(static_cast<unsigned char>(e[-1]))) --e;
  size_t len = e - b;
  if (len == 0) return true;
  char buf[8];
  if (len >= sizeof(buf)) return false;
  for (size_t i = 0; i < len; ++i)
    buf[i] = std::tolower(static_cast<unsigned char>(b[i]));
  buf[len] = 0;
  return !strcmp(buf, "na") || !strcmp(buf, "nan") || !strcmp(buf, "null") ||
         !strcmp(buf, "n/a") || !strcmp(buf, "none") || !strcmp(buf, "?");
}

double strtod_token(const char* b, const char* e) {
  // terminated copy for strtod (overflow/underflow parity with python
  // float(): 1e400 -> inf, 1e-400 -> 0.0); stack buffer for the common
  // case, heap for pathological token lengths (never truncate — a
  // truncated '1e400...' would parse to a wrong FINITE value)
  size_t len = e - b;
  char buf[64];
  std::string heap;
  const char* src;
  if (len < sizeof(buf)) {
    memcpy(buf, b, len);
    buf[len] = 0;
    src = buf;
  } else {
    heap.assign(b, e);
    src = heap.c_str();
  }
  char* endp = nullptr;
  double v = std::strtod(src, &endp);
  if (endp != src + len) return NAN;
  return v;
}

double parse_token(const char* b, const char* e, bool* bad) {
  // trim; empty/marker tokens -> NaN
  while (b < e && std::isspace(static_cast<unsigned char>(*b))) ++b;
  while (e > b && std::isspace(static_cast<unsigned char>(e[-1]))) --e;
  if (b == e) return NAN;
  const char* p = b;
  if (*p == '+') ++p;  // from_chars rejects a leading '+'; python allows it
#if defined(__cpp_lib_to_chars)
  // std::from_chars: correctly rounded like strtod/python float() (exact
  // bin parity with the Python parsers) at several times the speed, and
  // it takes an explicit [b, e) range — no NUL needed on the mmap.
  double v = 0.0;
  auto r = std::from_chars(p, e, v);
  if (r.ec == std::errc() && r.ptr == e) return v;
  if (r.ec == std::errc::result_out_of_range && r.ptr == e)
    return strtod_token(p, e);  // python parity: inf / 0.0, not NaN
#else
  double v = strtod_token(p, e);
  if (!std::isnan(v) || is_missing_token(b, e)) return v;
#endif
  if (is_missing_token(b, e)) return NAN;
  // a real text token (not a missing marker): the Python parser would
  // RAISE here — flag it so the wrapper falls back and the user sees
  // the loud error instead of silently training on NaNs
  *bad = true;
  return NAN;
}

// Split the body into per-thread ranges aligned to line starts, then count
// non-blank lines per range; prefix sums give each range's first row id.
struct Ranges {
  std::vector<const char*> begin;
  std::vector<const char*> end;
  std::vector<long long> first_row;
  long long total_rows = 0;
};

Ranges make_ranges(const char* body, const char* eof, int n_threads,
                   char sep) {
  Ranges r;
  size_t len = eof - body;
  std::vector<const char*> starts(n_threads + 1);
  starts[0] = body;
  for (int t = 1; t < n_threads; ++t) {
    const char* p = body + (len * t) / n_threads;
    const char* nl = static_cast<const char*>(memchr(p, '\n', eof - p));
    starts[t] = nl ? nl + 1 : eof;
  }
  starts[n_threads] = eof;
  std::vector<long long> counts(n_threads, 0);
#pragma omp parallel for schedule(static)
  for (int t = 0; t < n_threads; ++t) {
    const char* p = starts[t];
    const char* e = starts[t + 1];
    long long c = 0;
    while (p < e) {
      const char* nl = static_cast<const char*>(memchr(p, '\n', e - p));
      const char* le = nl ? nl : e;
      if (!line_blank(p, le, sep)) ++c;
      p = nl ? nl + 1 : e;
    }
    counts[t] = c;
  }
  r.begin.resize(n_threads);
  r.end.resize(n_threads);
  r.first_row.resize(n_threads);
  long long acc = 0;
  for (int t = 0; t < n_threads; ++t) {
    r.begin[t] = starts[t];
    r.end[t] = starts[t + 1];
    r.first_row[t] = acc;
    acc += counts[t];
  }
  r.total_rows = acc;
  return r;
}

int num_threads() {
#ifdef _OPENMP
  return std::max(1, omp_get_max_threads());
#else
  return 1;
#endif
}


// ---- find-bin (io/binning.py greedy_find_bin, find_bin_with_zero_as_one_bin
// and the numerical branch of BinMapper.find_bin, statement for statement:
// the Python routines are the oracle and the bounds are byte-equal) --------

constexpr double kZeroThreshold = 1e-35;

inline void push_bound(std::vector<double>* bounds, double mid) {
  double val = std::nextafter(mid, INFINITY);
  if (bounds->empty() || val > bounds->back()) bounds->push_back(val);
}

// Equal-frequency boundaries over n (distinct value, count) pairs.
// false: a case the Python routine raises on (left to it).
bool greedy_find_bin(const double* d, const long long* c, long long n,
                     long long max_bin, long long total_cnt,
                     long long min_data_in_bin, std::vector<char>* big_buf,
                     std::vector<double>* bounds) {
  bounds->clear();
  if (n == 0) {
    bounds->push_back(INFINITY);
    return true;
  }
  if (n <= max_bin) {
    long long cur = 0;
    for (long long i = 0; i + 1 < n; ++i) {
      cur += c[i];
      if (cur >= min_data_in_bin) {
        size_t before = bounds->size();
        push_bound(bounds, (d[i] + d[i + 1]) / 2.0);
        if (bounds->size() != before) cur = 0;
      }
    }
    bounds->push_back(INFINITY);
    return true;
  }
  if (min_data_in_bin > 0)
    max_bin = std::max(1LL, std::min(max_bin, total_cnt / min_data_in_bin));
  if (max_bin <= 0) return false;
  double mean_bin_size = static_cast<double>(total_cnt) / max_bin;
  std::vector<char>& is_big = *big_buf;
  is_big.resize(n);
  long long n_big = 0, big_cnt = 0;
  for (long long i = 0; i < n; ++i) {
    is_big[i] = static_cast<double>(c[i]) >= mean_bin_size;
    if (is_big[i]) {
      ++n_big;
      big_cnt += c[i];
    }
  }
  long long rest_bin_cnt = max_bin - n_big;
  long long rest_sample_cnt = total_cnt - big_cnt;
  mean_bin_size = static_cast<double>(rest_sample_cnt)
      / std::max(rest_bin_cnt, 1LL);
  std::vector<double> uppers, lowers;
  lowers.push_back(d[0]);
  long long cur = 0;
  for (long long i = 0; i + 1 < n; ++i) {
    if (!is_big[i]) rest_sample_cnt -= c[i];
    cur += c[i];
    if (is_big[i] || static_cast<double>(cur) >= mean_bin_size ||
        (is_big[i + 1] && static_cast<double>(cur) >=
                              std::max(1.0, mean_bin_size * 0.5))) {
      uppers.push_back(d[i]);
      lowers.push_back(d[i + 1]);
      if (static_cast<long long>(uppers.size()) >= max_bin - 1) break;
      cur = 0;
      if (!is_big[i]) {
        --rest_bin_cnt;
        mean_bin_size = static_cast<double>(rest_sample_cnt)
            / std::max(rest_bin_cnt, 1LL);
      }
    }
  }
  for (size_t i = 0; i < uppers.size(); ++i)
    push_bound(bounds, (uppers[i] + lowers[i + 1]) / 2.0);
  bounds->push_back(INFINITY);
  return true;
}

// The value range split at zero, so that bin(0.0) is exact.
bool find_bin_zero_as_one(const double* d, const long long* c, long long n,
                          long long max_bin, long long total_cnt,
                          long long min_data_in_bin,
                          std::vector<char>* big_buf,
                          std::vector<double>* bounds) {
  long long left_cnt = 0, left_cnt_data = 0, cnt_zero = 0;
  long long right_cnt_data = 0, right_start = -1;
  for (long long i = 0; i < n; ++i) {
    if (d[i] <= -kZeroThreshold) {
      ++left_cnt;
      left_cnt_data += c[i];
    } else if (d[i] > kZeroThreshold) {
      if (right_start < 0) right_start = i;
      right_cnt_data += c[i];
    } else {
      cnt_zero += c[i];
    }
  }
  bounds->clear();
  if (left_cnt > 0) {
    long long denom = std::max(total_cnt - cnt_zero, 1LL);
    long long left_max_bin = std::max(1LL, static_cast<long long>(
        static_cast<double>(left_cnt_data) / static_cast<double>(denom)
        * static_cast<double>(max_bin - 1)));
    if (!greedy_find_bin(d, c, left_cnt, left_max_bin, left_cnt_data,
                         min_data_in_bin, big_buf, bounds))
      return false;
    bounds->back() = -kZeroThreshold;
  }
  if (right_start >= 0) {
    long long right_max_bin =
        max_bin - 1 - static_cast<long long>(bounds->size());
    std::vector<double> right;
    if (!greedy_find_bin(d + right_start, c + right_start, n - right_start,
                         right_max_bin, right_cnt_data, min_data_in_bin,
                         big_buf, &right))
      return false;
    bounds->push_back(kZeroThreshold);
    bounds->insert(bounds->end(), right.begin(), right.end());
  } else {
    bounds->push_back(INFINITY);
  }
  return true;
}

// A thread's scratch for one column at a time, kept across columns: a
// fresh allocation of a sample's size a column is an mmap and a munmap,
// and those serialise the threads (150 ms a column against 20).
struct FindBinScratch {
  std::vector<double> distinct;
  std::vector<long long> counts;
  std::vector<char> is_big;
  explicit FindBinScratch(size_t n) {
    distinct.reserve(n);
    counts.reserve(n);
    is_big.reserve(n);
  }
};

struct ColumnBins {
  std::vector<double> bounds;
  int missing_type = 0;
  int default_bin = 0;
  double min_val = 0.0, max_val = 0.0, sparse_rate = 0.0;
};

// BinMapper.find_bin for one numerical column whose every sampled row is
// present in values[0, total) (no implicit zeros).  Reorders `values`.
// false: a column the Python routine must take (a negative zero, whose
// place among the zeros numpy's sort decides; a case it raises on).
bool find_bin_numerical(double* values, long long total, long long max_bin,
                        long long min_data_in_bin, bool use_missing,
                        bool zero_as_missing, FindBinScratch* scratch,
                        ColumnBins* out) {
  double* first_nan = std::partition(
      values, values + total, [](double v) { return !std::isnan(v); });
  const long long na_cnt = values + total - first_nan;
  for (double* v = values; v < first_nan; ++v)
    if (*v == 0.0 && std::signbit(*v)) return false;
  std::sort(values, first_nan);
  std::vector<double>& d = scratch->distinct;
  std::vector<long long>& c = scratch->counts;
  d.clear();
  c.clear();
  for (double* v = values; v < first_nan; ++v) {
    if (d.empty() || *v != d.back()) {
      d.push_back(*v);
      c.push_back(1);
    } else {
      ++c.back();
    }
  }
  if (d.empty()) {
    d.push_back(0.0);
    c.push_back(1);
  }
  const long long n = static_cast<long long>(d.size());
  out->min_val = d.front();
  out->max_val = d.back();
  out->missing_type = !use_missing ? 0 : zero_as_missing ? 1
      : (na_cnt > 0 ? 2 : 0);
  std::vector<double>& b = out->bounds;
  if (out->missing_type == 2) {
    if (!find_bin_zero_as_one(d.data(), c.data(), n, max_bin - 1,
                              total - na_cnt, min_data_in_bin,
                              &scratch->is_big, &b))
      return false;
    b.push_back(NAN);
  } else {
    if (!find_bin_zero_as_one(d.data(), c.data(), n, max_bin, total,
                              min_data_in_bin, &scratch->is_big, &b))
      return false;
    if (out->missing_type == 1 && b.size() == 2) out->missing_type = 0;
  }
  const long long nb = static_cast<long long>(b.size());
  // value_to_bin(0.0): the encode's search ranges
  long long hi = out->missing_type == 2 ? (nb >= 2 ? nb - 2 : 0) : nb - 1;
  out->default_bin = static_cast<int>(
      std::lower_bound(b.begin(), b.begin() + hi, 0.0) - b.begin());
  // _cnt_in_bin: rows of the sample in the default bin
  long long in_default = 0;
  for (long long i = 0; i < n; ++i) {
    long long idx = std::lower_bound(b.begin(), b.begin() + (nb - 1), d[i])
        - b.begin();
    if (std::min(idx, nb - 1) == out->default_bin) in_default += c[i];
  }
  if (out->missing_type == 2 && out->default_bin == nb - 1)
    in_default = na_cnt;
  out->sparse_rate = static_cast<double>(in_default)
      / static_cast<double>(std::max(total, 1LL));
  return true;
}

}  // namespace

// The rows' loop of LGBMT_EncodeBins (below, where its contract is), for
// a float64 or a float32 table.
template <typename T>
static void encode_bins_rows(const T* X, long long n, int F,
                             const double* bounds, const long long* offs,
                             const int* cnts, const int* missing_type,
                             const int* num_bin, const int* trivial,
                             const int* cat_len, const long long* cat_offs,
                             const int* cat_table,
                             unsigned char* out, long long n_stride) {
#pragma omp parallel for schedule(static)
  for (long long i = 0; i < n; ++i) {
    const T* xrow = X + i * F;
    for (int f = 0; f < F; ++f) {
      if (trivial[f]) continue;
      const bool nan_mode = missing_type[f] == 2;
      double v = static_cast<double>(xrow[f]);
      int idx;
      if (cat_len[f] >= 0) {
        const int last = num_bin[f] > 0 ? num_bin[f] - 1 : 0;
        const int* table = cat_table + cat_offs[f];
        if (std::isnan(v)) v = nan_mode ? -1.0 : 0.0;
        idx = -1;
        if (v > -1.0 && v < static_cast<double>(cat_len[f]))
          idx = table[static_cast<long long>(v)];
        if (idx < 0) idx = last;
      } else {
        const double* b = bounds + offs[f];
        const int cnt = cnts[f];
        int hi = nan_mode ? (num_bin[f] >= 2 ? cnt - 2 : 0) : cnt - 1;
        if (hi < 0) hi = 0;
        if (std::isnan(v)) {
          idx = nan_mode ? num_bin[f] - 1
                         : static_cast<int>(std::lower_bound(b, b + hi, 0.0) - b);
        } else {
          idx = static_cast<int>(std::lower_bound(b, b + hi, v) - b);
        }
      }
      out[static_cast<long long>(f) * n_stride + i] =
          static_cast<unsigned char>(idx);
    }
  }
}

extern "C" {

// Number of non-blank data rows (excluding the header), or -1 on error.
long long LGBMT_CountRows(const char* path, int has_header, char sep) {
  Mapped m = map_file(path);
  if (!m.ok()) return -1;
  const char* body = body_start(m, has_header, sep);
  Ranges r = make_ranges(body, m.data + m.size, num_threads(), sep);
  long long n = r.total_rows;
  unmap_file(m);
  return n;
}

// Parse a delimited numeric file into X [n_rows, n_cols-1] row-major f64
// (label column removed) and y [n_rows].  Short lines are tolerated
// (missing fields stay NaN); lines with MORE than n_cols fields abort
// with rc -4 so the Python fallback's widest-row semantics apply.
// rc 0 ok, -1 I/O error, -2 row-count mismatch (file changed between
// calls).
int LGBMT_ParseDense(const char* path, char sep, int has_header,
                     long long n_rows, int n_cols, int label_col,
                     double* X, double* y) {
  // NOTE: the file is memchr-scanned once in CountRows and once more by
  // this make_ranges — redundant but cheap next to the field parse
  // (SIMD memchr runs at several GB/s vs ~0.2 GB/s for number parsing)
  Mapped m = map_file(path);
  if (!m.ok()) return -1;
  const char* body = body_start(m, has_header, sep);
  Ranges r = make_ranges(body, m.data + m.size, num_threads(), sep);
  if (r.total_rows != n_rows) {
    unmap_file(m);
    return -2;
  }
  const int n_feat = n_cols - 1;
  const long long xbytes_row = n_feat;
  int n_ranges = static_cast<int>(r.begin.size());
  int ragged = 0;
  int bad_token = 0;
#pragma omp parallel for schedule(static) reduction(|| : ragged) \
    reduction(|| : bad_token)
  for (int t = 0; t < n_ranges; ++t) {
    const char* p = r.begin[t];
    const char* e = r.end[t];
    long long row = r.first_row[t];
    while (p < e) {
      const char* nl = static_cast<const char*>(memchr(p, '\n', e - p));
      const char* le = nl ? nl : e;
      if (!line_blank(p, le, sep)) {
        double* xrow = X + row * xbytes_row;
        for (int j = 0; j < n_feat; ++j) xrow[j] = NAN;
        int col = 0;
        bool consumed_all = false;
        const char* fb = p;
        while (fb <= le && col < n_cols) {
          const char* fe = static_cast<const char*>(
              memchr(fb, sep, le - fb));
          if (fe == nullptr) fe = le;
          bool bad = false;
          double v = parse_token(fb, fe, &bad);
          if (bad) bad_token = 1;
          if (col == label_col) {
            y[row] = v;
          } else {
            int j = col < label_col ? col : col - 1;
            xrow[j] = v;
          }
          ++col;
          if (fe == le) {
            consumed_all = true;
            break;
          }
          fb = fe + 1;
        }
        // fields beyond n_cols (even empty trailing ones): bail out so
        // the Python fallback's widest-row semantics decide the schema
        if (!consumed_all && col >= n_cols) ragged = 1;
        ++row;
      }
      p = nl ? nl + 1 : e;
    }
  }
  unmap_file(m);
  if (ragged) return -4;
  return bad_token ? -5 : 0;
}

// ValueToBin (bin.h:452-488 semantics, matching
// BinMapper.values_to_bins).  A numerical feature f (cat_len[f] < 0) has
// upper bounds bounds[offs[f] : offs[f]+cnts[f]]:
//   missing_type == 2 (NaN): NaN -> num_bin-1; values searchsorted-left
//     over bounds[:cnt-2] (when num_bin >= 2)
//   else: NaN treated as 0.0; searchsorted-left over bounds[:cnt-1]
// A categorical feature f (cat_len[f] >= 0) has a dense table
// cat_table[cat_offs[f] : cat_offs[f]+cat_len[f]] from category value to
// bin, -1 where the value has none:
//   the value truncated toward zero; negative, past the table or with no
//     bin -> num_bin-1 (the last bin)
//   NaN -> num_bin-1 when missing_type == 2, else the bin of category 0
// X is row-major [n, F]; out is FEATURE-major uint8 [F, n_stride] (the
// dataset's storage layout).  Features with trivial[f] != 0 are skipped.
// rc 0 ok, -3 if any num_bin > 256 (caller must use the Python path).
// X is f64, or f32 when is_f32: a float32 value is widened as it is read
// (exactly), so a float32 table is coded where it lies, with no float64
// copy of it (7.7 GB for a million rows of 968 columns).
int LGBMT_EncodeBins(const void* X, int is_f32, long long n, int F,
                     const double* bounds, const long long* offs,
                     const int* cnts, const int* missing_type,
                     const int* num_bin, const int* trivial,
                     const int* cat_len, const long long* cat_offs,
                     const int* cat_table,
                     unsigned char* out, long long n_stride) {
  for (int f = 0; f < F; ++f)
    if (!trivial[f] && num_bin[f] > 256) return -3;
  if (is_f32)
    encode_bins_rows(static_cast<const float*>(X), n, F, bounds, offs, cnts,
                     missing_type, num_bin, trivial, cat_len, cat_offs,
                     cat_table, out, n_stride);
  else
    encode_bins_rows(static_cast<const double*>(X), n, F, bounds, offs, cnts,
                     missing_type, num_bin, trivial, cat_len, cat_offs,
                     cat_table, out, n_stride);
  return 0;
}

// Numerical find-bin over the columns of a matrix, in threads: for each
// column j with skip[j] == 0, the sampled rows' values go through
// BinMapper.find_bin's numerical branch (byte-equal upper bounds to
// io/binning.py, the oracle and the fallback).  X is [*, F] of f64 (or
// f32 when is_f32) with byte strides; sample_idx holds n_sample row
// numbers, every sampled row present (no implicit zeros).  Column j's
// bounds land in bounds_out[j * bounds_cap ...] and status[j] is 0, or 1
// where the column is left to the Python routine (skipped, a negative
// zero in the sample, more bounds than bounds_cap).  rc 0.
int LGBMT_FindBinsNumerical(const void* X, int is_f32,
                            long long row_stride, long long col_stride,
                            const long long* sample_idx, long long n_sample,
                            int F, const unsigned char* skip,
                            int max_bin, int min_data_in_bin,
                            int use_missing, int zero_as_missing,
                            int bounds_cap, double* bounds_out,
                            int* num_bin, int* missing_type,
                            int* default_bin, double* min_val,
                            double* max_val, double* sparse_rate,
                            int* status) {
  const char* base = static_cast<const char*>(X);
  // rows in ascending order: the order of a sample does not reach its
  // sorted values, and the gather then walks the matrix forwards
  std::vector<long long> rows(sample_idx, sample_idx + n_sample);
  std::sort(rows.begin(), rows.end());
  // a block of adjacent columns a task: one pass over the sampled rows
  // reads a block's values from each (adjacent in a row-major matrix);
  // narrower blocks where the columns are few, so every thread has some
  const int kBlock = std::max(1, std::min(16, F / (4 * num_threads())));
  const int n_blocks = (F + kBlock - 1) / kBlock;
#pragma omp parallel
  {
    // a thread's buffers, allocated once: the block's sampled values
    // and one column's scratch
    std::vector<double> cols(static_cast<size_t>(kBlock) * n_sample);
    FindBinScratch scratch(n_sample);
#pragma omp for schedule(dynamic, 1)
    for (int blk = 0; blk < n_blocks; ++blk) {
      const int j0 = blk * kBlock;
      const int j1 = std::min(F, j0 + kBlock);
      for (long long i = 0; i < n_sample; ++i) {
        const char* row = base + rows[i] * row_stride;
        for (int j = j0; j < j1; ++j) {
          if (skip[j]) continue;
          const char* p = row + j * col_stride;
          cols[static_cast<size_t>(j - j0) * n_sample + i] = is_f32
              ? static_cast<double>(*reinterpret_cast<const float*>(p))
              : *reinterpret_cast<const double*>(p);
        }
      }
      for (int j = j0; j < j1; ++j) {
        status[j] = 1;
        if (skip[j]) continue;
        ColumnBins out;
        if (!find_bin_numerical(
                cols.data() + static_cast<size_t>(j - j0) * n_sample,
                n_sample, max_bin, min_data_in_bin, use_missing != 0,
                zero_as_missing != 0, &scratch, &out))
          continue;
        if (static_cast<int>(out.bounds.size()) > bounds_cap) continue;
        std::copy(out.bounds.begin(), out.bounds.end(),
                  bounds_out + static_cast<long long>(j) * bounds_cap);
        num_bin[j] = static_cast<int>(out.bounds.size());
        missing_type[j] = out.missing_type;
        default_bin[j] = out.default_bin;
        min_val[j] = out.min_val;
        max_val[j] = out.max_val;
        sparse_rate[j] = out.sparse_rate;
        status[j] = 0;
      }
    }
  }
  return 0;
}

}  // extern "C"
