// Native key index: open-addressing uint64 -> int64 hash map with batch ops.
//
// This is the hot host-side structure of the embedding engine — the role of
// the key agent / dedup index inside the reference's BoxPS
// (MergeInsKeys feeds keys to the PS agent, reference data_set.cc:1786;
// DedupKeysAndFillIdx, box_wrapper_impl.h:103). The Python fallback is a
// dict with a per-key loop; this replaces it with linear-probing batch
// lookups (~30ns/key) so million-key passes don't spend seconds in the
// interpreter.
//
// Not thread-safe by itself: HostEmbeddingStore serializes access under its
// own lock, matching how it already guarded the dict.

#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <vector>

namespace {

constexpr uint64_t kEmpty = ~0ULL;  // sentinel slot (key 2^64-1 unusable)

inline uint64_t splitmix64(uint64_t x) {
  x += 0x9E3779B97F4A7C15ULL;
  x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9ULL;
  x = (x ^ (x >> 27)) * 0x94D049BB133111EBULL;
  return x ^ (x >> 31);
}

struct KeyIndex {
  uint64_t* keys = nullptr;   // slot -> key (kEmpty = free)
  int64_t* vals = nullptr;    // slot -> assigned id
  uint64_t cap = 0;           // power of two
  uint64_t mask = 0;
  int64_t size = 0;
  // key 2^64-1 collides with the free-slot sentinel; give it dedicated
  // storage so every uint64 key is representable (the dict fallback has no
  // such restriction and the two backends must agree)
  int64_t sentinel_val = -1;

  // Returns false (state unchanged) if the OS refuses the allocation —
  // multi-GB tables must surface OOM, not dereference nullptr.
  bool alloc(uint64_t c) {
    auto* nk = static_cast<uint64_t*>(std::malloc(c * sizeof(uint64_t)));
    auto* nv = static_cast<int64_t*>(std::malloc(c * sizeof(int64_t)));
    if (nk == nullptr || nv == nullptr) {
      std::free(nk);
      std::free(nv);
      return false;
    }
    cap = c;
    mask = c - 1;
    keys = nk;
    vals = nv;
    std::memset(keys, 0xFF, c * sizeof(uint64_t));  // all kEmpty
    return true;
  }

  void grow() {
    uint64_t old_cap = cap;
    uint64_t* old_keys = keys;
    int64_t* old_vals = vals;
    if (!alloc(cap * 2)) {
      // mid-insert there is no error channel back through the batch API;
      // fail loudly rather than corrupt the table
      std::fprintf(stderr,
                   "keyindex: out of memory growing to %llu slots\n",
                   static_cast<unsigned long long>(cap * 2));
      std::abort();
    }
    for (uint64_t i = 0; i < old_cap; ++i) {
      if (old_keys[i] != kEmpty) {
        uint64_t s = splitmix64(old_keys[i]) & mask;
        while (keys[s] != kEmpty) s = (s + 1) & mask;
        keys[s] = old_keys[i];
        vals[s] = old_vals[i];
      }
    }
    std::free(old_keys);
    std::free(old_vals);
  }

  // slot of key, or slot of first free probe position
  inline uint64_t probe(uint64_t k) const {
    uint64_t s = splitmix64(k) & mask;
    while (keys[s] != kEmpty && keys[s] != k) s = (s + 1) & mask;
    return s;
  }
};

}  // namespace

extern "C" {

void* ki_create(int64_t capacity_hint) {
  auto* ki = new KeyIndex();
  uint64_t c = 1024;
  while (static_cast<int64_t>(c) < capacity_hint * 2) c <<= 1;
  if (!ki->alloc(c)) {
    delete ki;
    return nullptr;  // ctypes layer falls back to the dict backend
  }
  return ki;
}

void ki_free(void* h) {
  auto* ki = static_cast<KeyIndex*>(h);
  std::free(ki->keys);
  std::free(ki->vals);
  delete ki;
}

int64_t ki_size(void* h) { return static_cast<KeyIndex*>(h)->size; }

// out[i] = id of keys[i], or -1 if absent.
void ki_lookup(void* h, const uint64_t* ks, int64_t n, int64_t* out) {
  auto* ki = static_cast<KeyIndex*>(h);
  for (int64_t i = 0; i < n; ++i) {
    if (ks[i] == kEmpty) {
      out[i] = ki->sentinel_val;
      continue;
    }
    uint64_t s = ki->probe(ks[i]);
    out[i] = (ki->keys[s] == ks[i]) ? ki->vals[s] : -1;
  }
}

// Insert missing keys with sequential ids (first-occurrence order) starting
// at the current size. out[i] = id; returns the number of NEW keys.
int64_t ki_lookup_or_insert(void* h, const uint64_t* ks, int64_t n,
                            int64_t* out) {
  auto* ki = static_cast<KeyIndex*>(h);
  int64_t added = 0;
  for (int64_t i = 0; i < n; ++i) {
    if (ks[i] == kEmpty) {
      if (ki->sentinel_val < 0) {
        ki->sentinel_val = ki->size;
        ++ki->size;
        ++added;
      }
      out[i] = ki->sentinel_val;
      continue;
    }
    if (10 * static_cast<uint64_t>(ki->size + 1) > 7 * ki->cap) ki->grow();
    uint64_t s = ki->probe(ks[i]);
    if (ki->keys[s] == ks[i]) {
      out[i] = ki->vals[s];
    } else {
      ki->keys[s] = ks[i];
      ki->vals[s] = ki->size;
      out[i] = ki->size;
      ++ki->size;
      ++added;
    }
  }
  return added;
}

// Clear and bulk-load `ks` with ids 0..n-1 (shrink/remove rebuilds).
void ki_rebuild(void* h, const uint64_t* ks, int64_t n) {
  auto* ki = static_cast<KeyIndex*>(h);
  uint64_t c = 1024;
  while (static_cast<int64_t>(c) < n * 2) c <<= 1;
  std::free(ki->keys);
  std::free(ki->vals);
  ki->keys = nullptr;
  ki->vals = nullptr;
  if (!ki->alloc(c)) {
    std::fprintf(stderr,
                 "keyindex: out of memory rebuilding with %llu slots\n",
                 static_cast<unsigned long long>(c));
    std::abort();
  }
  ki->size = 0;
  ki->sentinel_val = -1;
  for (int64_t i = 0; i < n; ++i) {
    if (ks[i] == kEmpty) {
      if (ki->sentinel_val < 0) ++ki->size;
      ki->sentinel_val = i;  // last occurrence wins (dict-fallback parity)
      continue;
    }
    uint64_t s = ki->probe(ks[i]);
    if (ki->keys[s] != ks[i]) {
      ki->keys[s] = ks[i];
      ++ki->size;
    }
    ki->vals[s] = i;  // last occurrence wins (dict-fallback parity)
  }
}

// ---------------------------------------------------------------------
// Binned-push plan: stable counting sort of token row-ids by table
// super-block. The device kernel (ops/pallas_kernels.binned_push) only
// needs tokens GROUPED per super-block — order within a block is
// irrelevant (the one-hot matmul merges) — so a two-pass counting sort
// does in ~1ms of host time what a device argsort spends ~2.2ms of
// chip time on. Runs in the host pack pipeline, overlapped with device
// compute.
//   idx      : (n,) int32 row ids in [0, n_blocks*super_block)
//              (out-of-range ids land in the last block, clamped — the
//              kernel's local-range mask drops them, matching the XLA
//              path's mode="drop")
//   order    : (n,) int32 out — token positions grouped by block
//   rstart   : (n_blocks,) int32 out — DMA-aligned (8) tile starts
//   end      : (n_blocks,) int32 out — exclusive token ends
void pbtpu_block_plan(const int32_t* idx, int64_t n, int32_t super_block,
                      int64_t n_blocks, int32_t* order, int32_t* rstart,
                      int32_t* end) {
  std::vector<int64_t> counts(static_cast<size_t>(n_blocks) + 1, 0);
  const int64_t last = n_blocks - 1;
  for (int64_t i = 0; i < n; ++i) {
    int64_t b = static_cast<int64_t>(idx[i]) / super_block;
    if (b < 0) b = 0;
    if (b > last) b = last;
    ++counts[b];
  }
  int64_t run = 0;
  std::vector<int64_t> cursor(static_cast<size_t>(n_blocks), 0);
  for (int64_t b = 0; b < n_blocks; ++b) {
    rstart[b] = static_cast<int32_t>((run / 8) * 8);
    cursor[b] = run;
    run += counts[b];
    end[b] = static_cast<int32_t>(run);
  }
  for (int64_t i = 0; i < n; ++i) {
    int64_t b = static_cast<int64_t>(idx[i]) / super_block;
    if (b < 0) b = 0;
    if (b > last) b = last;
    order[cursor[b]++] = static_cast<int32_t>(i);
  }
}

// ---------------------------------------------------------------------
// Dedup plan: counting sort by FULL row id + unique-row segment bounds —
// the host half of the reference's DedupKeysAndFillIdx + PushMergeCopy
// pairing (box_wrapper_impl.h:103, box_wrapper.cu:630-830). The device
// pre-merge then segment-sums each unique row's payloads over the
// already-grouped token order (no argsort, no per-duplicate scatter) and
// both merge engines see ONE lane per unique row.
//   idx      : (n,) int32 row ids; anything outside [0, n_rows) sorts
//              into a sentinel bucket at the end (device drops it)
//   order    : (n,) out — token positions sorted ascending by row id
//   uniq     : (n,) out — ascending unique row ids; tail padded with
//              n_rows + i (distinct AND ascending, so the scatter's
//              unique/sorted promises hold; all >= n_rows -> dropped)
//   segend   : (n,) out — exclusive end of unique i's token run in the
//              sorted order; pads repeat n_valid (zero-width segments)
//   rstart   : (n_blocks,) out — 8-aligned unique-LANE window starts
//              per table super-block (binned kernel DMA windows)
//   end      : (n_blocks,) out — exclusive unique-lane window ends
// Returns u, the number of unique valid rows. uniq and segend are filled
// to n lanes (one a token, the most a batch can need), and every prefix
// of L >= u lanes is a whole plan under the same contract: the caller
// that carries no kernel windows ships only such a prefix
// (Trainer._host_plan), so the device's lane-shaped work follows the
// batch's distinct rows and not its tokens.
int64_t pbtpu_dedup_plan(const int32_t* idx, int64_t n, int64_t n_rows,
                         int32_t super_block, int64_t n_blocks,
                         int32_t* order, int32_t* uniq, int32_t* segend,
                         int32_t* rstart, int32_t* end) {
  // counts over rows + one sentinel bucket for out-of-range ids
  std::vector<int32_t> counts(static_cast<size_t>(n_rows) + 1, 0);
  for (int64_t i = 0; i < n; ++i) {
    int64_t r = idx[i];
    if (r < 0 || r >= n_rows) r = n_rows;
    ++counts[r];
  }
  // prefix over rows: token start offsets (reused as insert cursors),
  // unique list, segment ends, and per-block unique-lane windows
  if (n_blocks <= 0 || super_block <= 0) return -1;  // wrapper contract
  std::vector<int64_t> cursor(static_cast<size_t>(n_rows) + 1, 0);
  int64_t run = 0, u = 0, blk = -1;
  for (int64_t r = 0; r < n_rows; ++r) {
    cursor[r] = run;
    if (counts[r] > 0) {
      int64_t b = r / super_block;
      if (b >= n_blocks) b = n_blocks - 1;
      while (blk < b) {  // open blocks [blk+1, b]: start at lane u
        ++blk;
        rstart[blk] = static_cast<int32_t>((u / 8) * 8);
        end[blk] = static_cast<int32_t>(u);
      }
      run += counts[r];
      uniq[u] = static_cast<int32_t>(r);
      segend[u] = static_cast<int32_t>(run);
      end[blk] = static_cast<int32_t>(u + 1);
      ++u;
    }
  }
  while (blk + 1 < n_blocks) {  // trailing empty blocks
    ++blk;
    rstart[blk] = static_cast<int32_t>((u / 8) * 8);
    end[blk] = static_cast<int32_t>(u);
  }
  const int64_t n_valid = run;
  cursor[n_rows] = run;  // sentinel tokens go after every valid row
  for (int64_t j = u; j < n; ++j) {  // pad lanes: distinct, ascending,
    uniq[j] = static_cast<int32_t>(n_rows + (j - u));  // out of range
    segend[j] = static_cast<int32_t>(n_valid);
  }
  for (int64_t i = 0; i < n; ++i) {
    int64_t r = idx[i];
    if (r < 0 || r >= n_rows) r = n_rows;
    order[cursor[r]++] = static_cast<int32_t>(i);
  }
  return u;
}

// ---------------------------------------------------------------------
// Key-set merge: two ascending, duplicate-free int64 runs -> their
// ascending, duplicate-free union (signed order, as np.unique gives
// int64). The step of the pairwise tree that makes a pass's key set out
// of one run a file (key_index.py merge_sorted_runs; MergeInsKeys,
// reference data_set.cc:1786): a key both runs hold advances both, so
// every round drops what its pairs share, and the loop's body has no
// data-dependent branch.
//   a, b : strictly ascending over na, nb
//   out  : (na + nb,) the union lands in out[0:n]
// Returns n.
int64_t pbtpu_merge2(const int64_t* a, int64_t na, const int64_t* b,
                     int64_t nb, int64_t* out) {
  int64_t i = 0, j = 0, n = 0;
  while (i < na && j < nb) {
    const int64_t x = a[i], y = b[j];
    out[n++] = x < y ? x : y;
    i += (x <= y);
    j += (y <= x);
  }
  if (i < na) {
    std::memcpy(out + n, a + i,
                static_cast<size_t>(na - i) * sizeof(int64_t));
    n += na - i;
  }
  if (j < nb) {
    std::memcpy(out + n, b + j,
                static_cast<size_t>(nb - j) * sizeof(int64_t));
    n += nb - j;
  }
  return n;
}

}  // extern "C"
