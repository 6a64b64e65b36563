// The tcoo pack in one sort and one call
// (ops/coo_kernels.pack_tile_coo; the reference's Localizer,
// learn/base/localizer.h:98-221, with the tile-aligned slot assignment
// and the COO layout the compacted kernels read folded in).
//
// The numpy body sorts the ids (localize), assigns slots in key order
// (assign_tile_slots) and then sorts the slots again (pack_sorted_coo).
// Slot order is key order, so one stable sort serves both: the sorted
// (key, position) pairs are swept once for the unique keys, their
// BLK_U-aligned slots and the update-block maps, and once more for the
// BLK-padded COO stream. Every array comes out bit-equal to the numpy
// body's, which stays as the fallback and as the tests' oracle. No
// OpenMP here: each loader thread packs its own batch, and the call
// holds no interpreter lock.

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <vector>

namespace {

// per-thread work space, kept between calls: a loader packs batch after
// batch of one shape, and fresh pages for ~50 MB a call cost more than
// the sweeps that fill them
struct Scratch {
  std::vector<uint64_t> a, b;  // (key or slot) << 32 | position
};
thread_local Scratch scratch;

constexpr int kMaxDigit = 11;
constexpr int64_t kAhead = 16;  // entries the gathers prefetch ahead

inline uint64_t pair(int64_t hi, uint32_t pos) {
  return (static_cast<uint64_t>(hi) << 32) | pos;
}

// Stable LSD radix sort of the ids into (id << 32 | position) pairs, at
// the ids' own width: `bits` of key in the fewest digits of at most 11
// bits, all histograms from one read, a digit that is constant skipped.
// Returns the buffer (a or b) that holds the sorted pairs.
uint64_t* sort_pairs(const int32_t* idx, int64_t n, int bits, uint64_t* a,
                     uint64_t* b) {
  const int passes = (bits + kMaxDigit - 1) / kMaxDigit;
  const int digit = passes ? (bits + passes - 1) / passes : 0;
  const int64_t width = int64_t{1} << digit;
  const uint32_t mask = static_cast<uint32_t>(width - 1);
  std::vector<int64_t> hist(static_cast<size_t>(passes) * width, 0);
  for (int64_t i = 0; i < n; ++i) {
    const uint32_t k = static_cast<uint32_t>(idx[i]);
    for (int p = 0; p < passes; ++p)
      ++hist[p * width + ((k >> (p * digit)) & mask)];
  }
  const uint64_t* src = nullptr;  // nullptr: the ids themselves
  uint64_t* dst = a;
  for (int p = 0; p < passes; ++p) {
    int64_t* h = hist.data() + p * width;
    if (std::find(h, h + width, n) != h + width) continue;
    int64_t pos = 0;
    for (int64_t d = 0; d < width; ++d) {
      const int64_t c = h[d];
      h[d] = pos;
      pos += c;
    }
    const int shift = p * digit;
    if (src == nullptr) {
      for (int64_t i = 0; i < n; ++i) {
        const uint32_t k = static_cast<uint32_t>(idx[i]);
        dst[h[(k >> shift) & mask]++] = pair(k, static_cast<uint32_t>(i));
      }
    } else {
      for (int64_t i = 0; i < n; ++i) {
        const uint64_t x = src[i];
        dst[h[(x >> (32 + shift)) & mask]++] = x;
      }
    }
    src = dst;
    dst = dst == a ? b : a;
  }
  if (src == nullptr) {  // every id the same (or none): input order
    for (int64_t i = 0; i < n; ++i)
      a[i] = pair(static_cast<uint32_t>(idx[i]), static_cast<uint32_t>(i));
    return a;
  }
  return const_cast<uint64_t*>(src);
}

}  // namespace

extern "C" {

// Returns 0, or a reason the batch is outside this pass's domain (the
// caller then runs the numpy body, which decides what such a batch
// means): 1 an id outside [0, num_buckets), 3 more entries than
// `capacity` has blocks for. capacity < 0: that of the entries kept (the
// COO arrays then have room for all n, and the length used comes back).
// counts: num_uniq, dropped_uniq, dropped_nnz, length of the COO stream.
int32_t wh_pack_tile_coo(
    const int32_t* idx, const int32_t* seg, const float* val, int64_t n,
    int64_t num_buckets, int64_t u_cap, int64_t capacity, int64_t tile,
    int64_t blk, int64_t blk_u, int32_t* uniq, int32_t* tmap_u,
    int32_t* first_u, int32_t* last_u, int32_t* coo_idx, int32_t* coo_seg,
    float* coo_val, int32_t* tmap, int32_t* first, int64_t* counts) {
  for (int64_t i = 0; i < n; ++i)
    if (idx[i] < 0 || idx[i] >= num_buckets) return 1;

  Scratch& w = scratch;
  if (static_cast<int64_t>(w.a.size()) < n) {
    w.a.resize(n);
    w.b.resize(n);
  }
  int bits = 0;
  while (bits < 32 && (int64_t{1} << bits) < num_buckets) ++bits;
  uint64_t* s = sort_pairs(idx, n, bits, w.a.data(), w.b.data());

  // --- the sorted keys, once: unique keys, their slots (a tile's run
  // starts on a BLK_U boundary), the update-block maps, and each
  // entry's slot. Keys are kept while their slot is under u_cap: whole
  // tiles, then the boundary tile's first blocks.
  const int32_t sentinel = static_cast<int32_t>(num_buckets);
  const int64_t nb = u_cap / blk_u;
  std::memset(first_u, 0, nb * sizeof(int32_t));
  std::memset(last_u, 0, nb * sizeof(int32_t));
  int64_t next = 0, cur_tile = -1, prev = -1, total_uniq = 0;
  bool open = false;
  auto close_tile = [&] {
    const int64_t end = (next + blk_u - 1) / blk_u * blk_u;
    std::fill(uniq + next, uniq + end, sentinel);
    last_u[end / blk_u - 1] = 1;
    next = end;
    open = false;
  };
  int32_t slot = 0;
  int64_t j = 0;
  for (; j < n; ++j) {
    const uint64_t x = s[j];
    const int64_t key = static_cast<int64_t>(x >> 32);
    if (key != prev) {
      const int64_t t = key / tile;
      if (t != cur_tile) {
        if (open) close_tile();
        if (next >= u_cap) break;
        cur_tile = t;
        open = true;
        first_u[next / blk_u] = 1;
      } else if (next >= u_cap) {
        break;
      }
      if (next % blk_u == 0) tmap_u[next / blk_u] = static_cast<int32_t>(t);
      prev = key;
      ++total_uniq;
      uniq[next] = static_cast<int32_t>(key);
      slot = static_cast<int32_t>(next++);
    }
    s[j] = pair(slot, static_cast<uint32_t>(x));
  }
  if (open) close_tile();
  const int64_t kept_n = j, kept_uniq = total_uniq;
  int64_t dropped_nnz = 0;
  for (; j < n; ++j) {  // past the cut: dropped with their keys
    const int64_t key = static_cast<int64_t>(s[j] >> 32);
    const uint32_t i = static_cast<uint32_t>(s[j]);
    if (key != prev) {
      prev = key;
      ++total_uniq;
    }
    dropped_nnz += val[i] != 0.0f;
  }
  const int64_t used = next / blk_u;
  std::fill(uniq + next, uniq + u_cap, sentinel);
  // trailing spare blocks: inert revisits of the last tile kept
  std::fill(tmap_u + used, tmap_u + nb,
            static_cast<int32_t>(cur_tile < 0 ? 0 : cur_tile));
  if (used == 0) first_u[0] = last_u[0] = 1;

  // --- the sorted slots, once: the COO stream over the compact domain,
  // each compact tile's run padded to whole BLK blocks (one at least),
  // the last tile taking the spare blocks
  const int64_t num_tiles = u_cap / tile;
  if (capacity < 0) capacity = kept_n;
  const int64_t P = (capacity / blk + num_tiles) * blk;
  std::memset(first, 0, P / blk * sizeof(int32_t));
  int64_t p = 0;
  j = 0;
  for (int64_t t = 0; t < num_tiles; ++t) {
    const int64_t start = p, hi = (t + 1) * tile;
    if (start >= P) return 3;
    first[start / blk] = 1;
    for (; j < kept_n && static_cast<int64_t>(s[j] >> 32) < hi; ++j, ++p) {
      if (p >= P) return 3;
      if (j + kAhead < kept_n) {
        const uint32_t ahead = static_cast<uint32_t>(s[j + kAhead]);
        __builtin_prefetch(seg + ahead);
        __builtin_prefetch(val + ahead);
      }
      const uint32_t i = static_cast<uint32_t>(s[j]);
      coo_idx[p] = static_cast<int32_t>(s[j] >> 32);
      coo_seg[p] = seg[i];
      coo_val[p] = val[i];
    }
    int64_t end = p == start ? start + blk : (p + blk - 1) / blk * blk;
    if (end > P) return 3;
    if (t == num_tiles - 1) end = P;
    std::fill(coo_idx + p, coo_idx + end, static_cast<int32_t>(t * tile));
    std::memset(coo_seg + p, 0, (end - p) * sizeof(int32_t));
    std::memset(coo_val + p, 0, (end - p) * sizeof(float));
    std::fill(tmap + start / blk, tmap + end / blk, static_cast<int32_t>(t));
    p = end;
  }

  counts[0] = kept_uniq;
  counts[1] = total_uniq - kept_uniq;
  counts[2] = dropped_nnz;
  counts[3] = P;
  return 0;
}

}  // extern "C"
