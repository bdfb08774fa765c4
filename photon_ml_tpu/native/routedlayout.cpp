// The routed layout's linear passes over a plan's slots (ops/routing.py,
// ops/sparse_perm.py), each once, in one loop.
//
// fill_slot_perm: the slot permutation of a layout. perm[csc_pos[e]] =
// ell_pos[e] for every entry e, and the pads (the slots no entry holds, on
// either side) matched to each other in ascending order. numpy takes five
// passes over the S slots for this (two masks, a list of the free sources,
// a masked assignment, the scatter); here it is one walk that writes runs
// of pads from runs of free sources, both sides' slots in ascending order.
//
// invert_lanes / invert_sublanes: the inverse of a routed plan's shuffle
// stages. A plan is a chain of lane shuffles (a permutation of the 128
// lanes within every row) and sublane shuffles (a permutation of the rows
// within every block of `rows` rows, lane by lane). Its inverse runs the
// stages backwards, each inverted:
//   lane:    inv[r, idx[r, c]] = c
//   sublane: inv[g*rows + idx[g*rows + i, c], c] = i
// numpy's fancy-index scatter does this at ~10 ns an entry; here it is a
// cache-local loop over each row or block, threaded over rows as
// sortperm.cpp threads its passes.
//
// Contract (see PermPlan.invert in ops/routing.py, build_slot_perm in
// ops/sparse_perm.py):
//   fill_slot_perm(S, nnz, ell_asc, csc_asc, ell_of_csc, perm) -> 0 on
//     success. ell_asc: the entries' ELL slots, ascending; csc_asc: their
//     CSC slots, ascending; ell_of_csc: the ELL slot of each in that order.
//     2 when a slot is outside [0, S) or a side does not strictly ascend.
//   invert_lanes_<t>(total_rows, idx, inv, n_threads) -> 0 on success
//   invert_sublanes_<t>(total_rows, rows, idx, inv, n_threads) -> 0
//     <t> is the index dtype (i8, i32); idx and inv are row-major
//     [total_rows, 128]. An index outside its row (lanes) or block
//     (sublanes) returns 2 and leaves inv incomplete.
//   Bad sizes return 1.

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <thread>
#include <vector>

namespace {

constexpr int64_t LANES = 128;

// Run body(lo, hi) over [0, n) in n_threads contiguous chunks.
template <typename F>
void parallel_for(int64_t n, int n_threads, F body) {
  int64_t chunk = (n + n_threads - 1) / n_threads;
  if (n_threads <= 1 || n <= chunk) {
    body((int64_t)0, n);
    return;
  }
  std::vector<std::thread> ts;
  for (int t = 0; t < n_threads; ++t) {
    int64_t lo = t * chunk, hi = std::min(n, lo + chunk);
    if (lo >= hi) break;
    ts.emplace_back([=]() { body(lo, hi); });
  }
  for (auto& th : ts) th.join();
}

template <typename T>
int invert_lanes(int64_t total_rows, const T* idx, T* inv, int n_threads) {
  if (total_rows < 0 || n_threads < 1) return 1;
  std::atomic<int> bad{0};
  parallel_for(total_rows, n_threads, [&](int64_t lo, int64_t hi) {
    for (int64_t r = lo; r < hi; ++r) {
      const T* src = idx + r * LANES;
      T* dst = inv + r * LANES;
      for (int64_t c = 0; c < LANES; ++c) {
        int64_t v = (int64_t)src[c];
        if (v < 0 || v >= LANES) {
          bad.store(1);
          return;
        }
        dst[v] = (T)c;
      }
    }
  });
  return bad.load() ? 2 : 0;
}

template <typename T>
int invert_sublanes(int64_t total_rows, int64_t rows, const T* idx, T* inv,
                    int n_threads) {
  if (total_rows < 0 || rows < 1 || total_rows % rows || n_threads < 1)
    return 1;
  std::atomic<int> bad{0};
  parallel_for(total_rows / rows, n_threads, [&](int64_t lo, int64_t hi) {
    for (int64_t g = lo; g < hi; ++g) {
      const T* src = idx + g * rows * LANES;
      T* dst = inv + g * rows * LANES;
      for (int64_t i = 0; i < rows; ++i) {
        for (int64_t c = 0; c < LANES; ++c) {
          int64_t v = (int64_t)src[i * LANES + c];
          if (v < 0 || v >= rows) {
            bad.store(1);
            return;
          }
          dst[v * LANES + c] = (T)i;
        }
      }
    }
  });
  return bad.load() ? 2 : 0;
}

}  // namespace

extern "C" {

#define LAYOUT_EXPORT(SUFFIX, T)                                        \
  int invert_lanes_##SUFFIX(int64_t total_rows, const T* idx, T* inv,   \
                            int n_threads) {                            \
    return invert_lanes<T>(total_rows, idx, inv, n_threads);            \
  }                                                                     \
  int invert_sublanes_##SUFFIX(int64_t total_rows, int64_t rows,        \
                               const T* idx, T* inv, int n_threads) {   \
    return invert_sublanes<T>(total_rows, rows, idx, inv, n_threads);   \
  }

LAYOUT_EXPORT(i8, int8_t)
LAYOUT_EXPORT(i32, int32_t)

#undef LAYOUT_EXPORT

int fill_slot_perm(int64_t S, int64_t nnz, const int64_t* ell_asc,
                   const int64_t* csc_asc, const int64_t* ell_of_csc,
                   int64_t* perm) {
  if (S < 0 || nnz < 0 || nnz > S) return 1;
  if (nnz && (ell_asc[0] < 0 || ell_asc[nnz - 1] >= S)) return 2;
  // q walks the destination slots, e the entries in csc order, j the
  // sources and k the used ones among them in ascending order: a run of
  // pads takes a run of free sources, an entry's slot its own source
  int64_t q = 0, e = 0, j = 0, k = 0;
  while (q < S) {
    int64_t next = e < nnz ? csc_asc[e] : S;
    if (e < nnz && (next < q || next >= S)) return 2;  // not ascending
    while (q < next) {
      while (k < nnz && ell_asc[k] == j) {
        ++j;
        ++k;
      }
      if (k < nnz && ell_asc[k] < j) return 2;  // sources not ascending
      int64_t free_end = k < nnz ? ell_asc[k] : S;
      int64_t m = std::min(next - q, free_end - j);
      if (m <= 0) return 2;  // more pads than free sources
      for (int64_t i = 0; i < m; ++i) perm[q + i] = j + i;
      q += m;
      j += m;
    }
    if (e < nnz) {
      perm[q++] = ell_of_csc[e++];
    }
  }
  return 0;
}
}
