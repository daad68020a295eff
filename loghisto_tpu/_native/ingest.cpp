// Native ingest runtime: lock-striped sample staging + vectorized codec.
//
// This is the C++ analog of the reference's hot path machinery (the Go
// library's RWMutex + atomic lock-promotion ingest, metrics.go:251-295),
// rebuilt for the batch/device design: writers append (metric_id, value)
// pairs into per-shard ring buffers under a per-shard mutex with the GIL
// released, and the reaper drains whole shards for vectorized compression
// and device upload.  Also provides the log-bucket codec and a dense
// accumulate as portable C for host-side verification and CPU fallback.
//
// Plain C ABI on purpose: loaded via ctypes (no pybind11 in the image).

#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <cmath>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <functional>
#include <memory>
#include <mutex>
#include <new>
#include <thread>
#include <vector>

namespace {

constexpr int16_t kBucketLimit = 32767;

struct Shard {
  std::mutex mu;
  std::vector<int32_t> ids;
  std::vector<double> values;
  // lifetime counters of dropped samples (buffer full)
  std::atomic<uint64_t> dropped{0};
};

struct Buffer {
  std::vector<Shard> shards;
  int64_t capacity_per_shard;
  explicit Buffer(int num_shards, int64_t cap)
      : shards(num_shards), capacity_per_shard(cap) {
    for (auto& s : shards) {
      s.ids.reserve(static_cast<size_t>(std::min<int64_t>(cap, 1 << 20)));
      s.values.reserve(static_cast<size_t>(std::min<int64_t>(cap, 1 << 20)));
    }
  }
};

inline int16_t compress_one(double value, int precision) {
  double mag = std::floor(precision * std::log1p(std::fabs(value)) + 0.5);
  if (std::isnan(mag)) mag = 0.0;  // NaN -> bucket 0 (matches device tier)
  if (mag > kBucketLimit) mag = kBucketLimit;
  int16_t i = static_cast<int16_t>(mag);
  return value < 0 ? static_cast<int16_t>(-i) : i;
}

// A float sample's codec bucket, clamped to +-bucket_limit.
inline int32_t clamped_bucket(float value, int precision, int bucket_limit) {
  int32_t b = compress_one(static_cast<double>(value), precision);
  if (b < -bucket_limit) b = -bucket_limit;
  if (b > bucket_limit) b = bucket_limit;
  return b;
}

// The (id, bucket) cell as one table key; 0 never occurs for id >= 0.
inline uint64_t cell_key(int32_t id, int32_t b) {
  return (static_cast<uint64_t>(static_cast<uint32_t>(id)) << 16) |
         static_cast<uint16_t>(b + 32768);
}

}  // namespace

extern "C" {

void* lh_create(int num_shards, int64_t capacity_per_shard) {
  if (num_shards < 1 || capacity_per_shard < 1) return nullptr;
  return new (std::nothrow) Buffer(num_shards, capacity_per_shard);
}

void lh_destroy(void* handle) { delete static_cast<Buffer*>(handle); }

int lh_num_shards(void* handle) {
  return static_cast<int>(static_cast<Buffer*>(handle)->shards.size());
}

// Append a batch into one shard. Returns the number of samples accepted
// (the rest were dropped: shed-don't-block, like the reference's
// slow-subscriber policy).
int64_t lh_record_batch(void* handle, int shard_idx, const int32_t* ids,
                        const double* values, int64_t n) {
  Buffer* buf = static_cast<Buffer*>(handle);
  Shard& shard = buf->shards[shard_idx % buf->shards.size()];
  std::lock_guard<std::mutex> lock(shard.mu);
  int64_t room = buf->capacity_per_shard -
                 static_cast<int64_t>(shard.ids.size());
  int64_t take = std::max<int64_t>(0, std::min(room, n));
  if (take > 0) {
    shard.ids.insert(shard.ids.end(), ids, ids + take);
    shard.values.insert(shard.values.end(), values, values + take);
  }
  if (take < n) shard.dropped.fetch_add(static_cast<uint64_t>(n - take));
  return take;
}

int64_t lh_record(void* handle, int shard_idx, int32_t id, double value) {
  return lh_record_batch(handle, shard_idx, &id, &value, 1);
}

// Swap one shard's buffers and copy them out. Returns the sample count
// (<= max_n; anything beyond max_n is discarded and counted as dropped).
int64_t lh_drain(void* handle, int shard_idx, int32_t* ids_out,
                 double* values_out, int64_t max_n) {
  Buffer* buf = static_cast<Buffer*>(handle);
  Shard& shard = buf->shards[shard_idx % buf->shards.size()];
  std::vector<int32_t> ids;
  std::vector<double> values;
  {
    std::lock_guard<std::mutex> lock(shard.mu);
    ids.swap(shard.ids);
    values.swap(shard.values);
    // keep the warm reserve: without this, every post-drain interval
    // re-grows through the realloc ladder while holding the shard mutex
    size_t warm = std::min<size_t>(
        ids.capacity(), static_cast<size_t>(buf->capacity_per_shard));
    shard.ids.reserve(warm);
    shard.values.reserve(warm);
  }
  int64_t n = static_cast<int64_t>(ids.size());
  int64_t take = std::min(n, max_n);
  if (take > 0) {
    std::memcpy(ids_out, ids.data(), take * sizeof(int32_t));
    std::memcpy(values_out, values.data(), take * sizeof(double));
  }
  if (take < n) shard.dropped.fetch_add(static_cast<uint64_t>(n - take));
  return take;
}

uint64_t lh_dropped(void* handle) {
  Buffer* buf = static_cast<Buffer*>(handle);
  uint64_t total = 0;
  for (auto& s : buf->shards) total += s.dropped.load();
  return total;
}

// Vectorized codec: values -> int16 buckets (reference metrics.go:316-322
// semantics, saturating instead of wrapping).
void lh_compress(const double* values, int64_t n, int precision,
                 int16_t* out) {
  for (int64_t i = 0; i < n; ++i) out[i] = compress_one(values[i], precision);
}

void lh_decompress(const int16_t* buckets, int64_t n, int precision,
                   double* out) {
  for (int64_t i = 0; i < n; ++i) {
    double f = std::exp(std::fabs(static_cast<double>(buckets[i])) /
                        precision) - 1.0;
    out[i] = buckets[i] < 0 ? -f : f;
  }
}

}  // extern "C"

// Persistent host cell store: an open-addressing (id, codec_bucket) ->
// int64 count table that ACCUMULATES across flushes, so one device ship
// per interval carries the dedup of the whole interval, not one batch.
// This is the host-tier half of interval-granularity transport: sample
// rate is decoupled from wire bandwidth (wire cost = unique cells per
// interval), which is what lets a thin host->device link keep up with
// a firehose of samples.

namespace {

struct CellSlot {
  uint64_t key;  // (id << 16) | (bucket + 32768); 0 = empty
  int64_t count;
};

struct CellStore {
  std::vector<CellSlot> table;
  uint64_t mask;
  int64_t used = 0;
  int64_t grows = 0;  // successful grow() calls over the table's life

  explicit CellStore(uint64_t cap) : table(cap, CellSlot{0, 0}), mask(cap - 1) {}

  bool grow() {
    uint64_t new_cap = table.size() * 2;
    std::vector<CellSlot> fresh;
    try {
      fresh.assign(new_cap, CellSlot{0, 0});
    } catch (...) {
      return false;
    }
    uint64_t new_mask = new_cap - 1;
    for (const CellSlot& s : table) {
      if (s.key == 0) continue;
      uint64_t h = s.key * 0x9E3779B97F4A7C15ull;
      uint64_t j = (h ^ (h >> 32)) & new_mask;
      while (fresh[j].key != 0) j = (j + 1) & new_mask;
      fresh[j] = s;
    }
    table.swap(fresh);
    mask = new_mask;
    ++grows;
    return true;
  }

  bool add_one(uint64_t key, int64_t weight) {
    uint64_t h = key * 0x9E3779B97F4A7C15ull;
    uint64_t j = (h ^ (h >> 32)) & mask;
    while (true) {
      if (table[j].key == key) {
        table[j].count += weight;
        return true;
      }
      if (table[j].key == 0) {
        // keep load factor under ~0.7 so probe chains stay short
        if ((used + 1) * 10 >= static_cast<int64_t>(table.size()) * 7) {
          if (!grow()) return false;
          return add_one(key, weight);
        }
        table[j].key = key;
        table[j].count = weight;
        ++used;
        return true;
      }
      j = (j + 1) & mask;
    }
  }
};

}  // namespace

extern "C" {

void* lh_cells_create(int64_t initial_capacity) {
  uint64_t cap = 1024;
  while (cap < static_cast<uint64_t>(initial_capacity)) cap <<= 1;
  try {
    // nothrow covers only the object shell; the constructor's vector
    // fill can itself throw, and an exception must never cross the C ABI
    return new (std::nothrow) CellStore(cap);
  } catch (...) {
    return nullptr;
  }
}

void lh_cells_destroy(void* store) { delete static_cast<CellStore*>(store); }

int64_t lh_cells_size(void* store) {
  return static_cast<CellStore*>(store)->used;
}

// Fold one batch into the store. Returns the number of samples CONSUMED
// from the input (including skipped negative ids): n on full success,
// or i < n if a table growth allocation failed before sample i — the
// prefix [0, i) is already folded, so the caller retries only ids[i:]
// (typically after draining).  This exactness contract is what lets the
// Python layer recover from allocation failure without double counting.
int64_t lh_cells_add(void* store, const int32_t* ids, const float* values,
                     int64_t n, int precision, int bucket_limit) {
  CellStore* cs = static_cast<CellStore*>(store);
  for (int64_t i = 0; i < n; ++i) {
    int32_t id = ids[i];
    if (id < 0) continue;
    uint64_t key = cell_key(id, clamped_bucket(values[i], precision,
                                               bucket_limit));
    if (!cs->add_one(key, 1)) return i;
  }
  return n;
}

// Copy out every cell and clear the table (capacity retained). Output
// arrays must hold lh_cells_size entries. Returns the cell count.
int64_t lh_cells_drain(void* store, int32_t* ids_out, int32_t* buckets_out,
                       int64_t* counts_out) {
  CellStore* cs = static_cast<CellStore*>(store);
  int64_t m = 0;
  for (CellSlot& s : cs->table) {
    if (s.key == 0) continue;
    ids_out[m] = static_cast<int32_t>(s.key >> 16);
    buckets_out[m] = static_cast<int32_t>(s.key & 0xFFFF) - 32768;
    counts_out[m] = s.count;
    s.key = 0;
    s.count = 0;
    ++m;
  }
  cs->used = 0;
  return m;
}

// Copy out every cell as interleaved [id, codec_bucket, count] int32
// triples and clear the table (capacity retained).  int32 END TO END on
// purpose: the device merge never enables jax_enable_x64, so an int64
// wire array would be silently canonicalized to int32 — with the earlier
// (id << 16) key format that truncation corrupted every id >= 2^15.
// One packed array still means ONE host->device transfer per merge
// chunk instead of three — per-transfer latency is the dominant wire
// cost on a thin host-to-device link.  out must hold 3 * lh_cells_size(store)
// entries.  A cell whose int64 count exceeds LH_PACKED_COUNT_CAP is
// emitted capped and LEFT IN THE TABLE with the remainder — the caller
// loops until lh_cells_size reaches 0 (one pass in any realistic run;
// the cap keeps every emitted row < 2^30, below the aggregator's int32
// accumulator spill threshold).
static const int64_t LH_PACKED_COUNT_CAP = (1 << 30) - 1;

int64_t lh_cells_drain_packed(void* store, int32_t* out) {
  CellStore* cs = static_cast<CellStore*>(store);
  int64_t m = 0;
  int64_t remaining = 0;
  for (CellSlot& s : cs->table) {
    if (s.key == 0) continue;
    int64_t c = s.count;
    int64_t emit = c > LH_PACKED_COUNT_CAP ? LH_PACKED_COUNT_CAP : c;
    out[3 * m] = static_cast<int32_t>(s.key >> 16);
    out[3 * m + 1] = static_cast<int32_t>(s.key & 0xFFFF) - 32768;
    out[3 * m + 2] = static_cast<int32_t>(emit);
    ++m;
    if (c > emit) {
      s.count = c - emit;
      ++remaining;
    } else {
      s.key = 0;
      s.count = 0;
    }
  }
  cs->used = remaining;
  return m;
}

}  // extern "C"

// -- the host fold of transport="sparse" ------------------------------------
//
// One GIL-released call turns a raw (ids, values) batch into packed int32
// [n, 3] (id, codec_bucket, count) triples, the wire format of the packed
// merge.  With T > 1 threads the fold partitions cells by key hash: each
// thread codes one contiguous slice of the batch and scatters its keys by
// partition, then each thread folds one partition's keys, from every
// slice, into its own table.  So each distinct (id, bucket) cell is
// counted by one thread and emitted as one row (more only past the 2^30-1
// count cap).  The tables, the key buffers and the threads live in a
// context of the calling thread that outlasts the call, so tables grown
// for one item are warm for the next.

namespace {

// Rows needed to emit one table under the 2^30-1 per-row count cap
// (split rule shared with lh_cells_drain_packed).
int64_t packed_rows_needed(const CellStore& cs, int64_t cap) {
  int64_t rows = 0;
  for (const CellSlot& s : cs.table) {
    if (s.key == 0) continue;
    rows += s.count <= cap ? 1 : (s.count + cap - 1) / cap;
  }
  return rows;
}

// Emit every cell as split [id, bucket, count<=cap] triples at out;
// clears the table (capacity retained).  Returns rows written.
int64_t emit_packed_split(CellStore& cs, int64_t cap, int32_t* out) {
  int64_t m = 0;
  for (CellSlot& s : cs.table) {
    if (s.key == 0) continue;
    int64_t c = s.count;
    while (c > 0) {
      int64_t emit = c > cap ? cap : c;
      out[3 * m] = static_cast<int32_t>(s.key >> 16);
      out[3 * m + 1] = static_cast<int32_t>(s.key & 0xFFFF) - 32768;
      out[3 * m + 2] = static_cast<int32_t>(emit);
      c -= emit;
      ++m;
    }
    s.key = 0;
    s.count = 0;
  }
  cs.used = 0;
  return m;
}

// The partition of a key among t: the high half of a second
// multiplicative hash, scaled to [0, t).  Its bits are independent of
// the slot hash in CellStore::add_one, so one partition's keys still
// spread over all of its table's slots.
inline int partition_of(uint64_t key, int t) {
  uint64_t h = (key * 0xD6E8FEB86659FD93ull) >> 32;
  return static_cast<int>((h * static_cast<uint64_t>(t)) >> 32);
}

// Each slice's per-partition counters live on its thread's stack (shared
// counters would bounce one cache line between the threads).
constexpr int kMaxFoldThreads = 64;

// The threads of one calling thread's folds.  run(t, fn) calls fn(0) on
// the caller and fn(1) .. fn(t-1) on kept threads, started the first time
// a width asks for them, and returns once every call has returned.  Only
// the owning thread calls run, so two runs never overlap.
class FoldPool {
 public:
  FoldPool() = default;
  FoldPool(const FoldPool&) = delete;
  FoldPool& operator=(const FoldPool&) = delete;

  ~FoldPool() {
    {
      std::lock_guard<std::mutex> lock(mu_);
      stop_ = true;
    }
    start_.notify_all();
    for (std::thread& th : threads_) th.join();
  }

  // False, before anything ran, when a thread could not be started.
  bool run(int t, const std::function<void(int)>& fn) {
    while (static_cast<int>(threads_.size()) < t - 1) {
      try {
        threads_.emplace_back(&FoldPool::serve, this,
                              static_cast<int>(threads_.size()) + 1, gen_);
      } catch (...) {
        return false;
      }
    }
    if (t > 1) {
      {
        std::lock_guard<std::mutex> lock(mu_);
        job_ = &fn;
        width_ = t;
        pending_ = t - 1;
        ++gen_;
      }
      start_.notify_all();
    }
    fn(0);
    std::unique_lock<std::mutex> lock(mu_);
    done_.wait(lock, [this] { return pending_ == 0; });
    return true;
  }

 private:
  void serve(int index, uint64_t seen) {
    std::unique_lock<std::mutex> lock(mu_);
    while (true) {
      start_.wait(lock, [&] { return stop_ || gen_ != seen; });
      if (stop_) return;
      seen = gen_;
      if (index >= width_) continue;
      const std::function<void(int)>* fn = job_;
      lock.unlock();
      (*fn)(index);
      lock.lock();
      if (--pending_ == 0) done_.notify_one();
    }
  }

  std::mutex mu_;
  std::condition_variable start_, done_;
  std::vector<std::thread> threads_;
  const std::function<void(int)>* job_ = nullptr;
  uint64_t gen_ = 0;  // written by the owner under mu_
  int width_ = 0;
  int pending_ = 0;
  bool stop_ = false;
};

// What one calling thread's folds keep between calls.  Its memory is
// bounded by the largest item that thread has folded: 10 bytes a sample
// (a codec bucket and a key), and one table a partition that never
// shrinks, of 16-byte slots, at most the power of two above 10/7 of the
// most distinct cells that partition has held.
struct FoldContext {
  pid_t pid = getpid();
  FoldPool pool;
  std::vector<std::unique_ptr<CellStore>> tables;  // one a partition
  std::unique_ptr<int16_t[]> buckets;  // each sample's clamped bucket
  std::unique_ptr<uint64_t[]> keys;    // the keys, grouped by partition
  int64_t capacity = 0;                // samples the two buffers hold
  std::vector<int64_t> starts;  // [slice * t + partition] scatter offsets
  std::vector<int64_t> bounds;  // partition p's keys: [bounds[p], bounds[p+1])
  std::vector<int64_t> rows;    // partition p's rows, then its emit offset

  // Sizes everything for n samples over t partitions; false when a
  // buffer could not be allocated (a table or vector may throw instead).
  bool reserve(int64_t n, int t) {
    if (n > capacity) {
      // free the old buffers first: old and new never coexist
      buckets.reset();
      keys.reset();
      capacity = 0;
      buckets.reset(new (std::nothrow) int16_t[n]);
      keys.reset(new (std::nothrow) uint64_t[n]);
      if (!buckets || !keys) {
        buckets.reset();
        keys.reset();
        return false;
      }
      capacity = n;
    }
    while (static_cast<int>(tables.size()) < t)
      tables.emplace_back(new CellStore(1 << 14));
    starts.assign(static_cast<size_t>(t) * t, 0);
    bounds.assign(t + 1, 0);
    rows.assign(t, 0);
    return true;
  }

  int64_t grows() const {
    int64_t g = 0;
    for (const auto& cs : tables) g += cs->grows;
    return g;
  }

  // Empties every table after a fold that failed partway.
  void clear_tables() {
    for (auto& cs : tables) {
      std::fill(cs->table.begin(), cs->table.end(), CellSlot{0, 0});
      cs->used = 0;
    }
  }
};

// One context per calling thread: the transfer worker, its fold-ahead
// helper and a federation emitter each fold with their own, so no two
// concurrent folds share one.  It is freed when its thread exits.  A
// forked child inherits the pointer but not the threads, so it leaves
// its parent's context alone and builds its own.
struct FoldContextSlot {
  FoldContext* ctx = nullptr;

  ~FoldContextSlot() {
    if (ctx != nullptr && ctx->pid == getpid()) delete ctx;
  }

  FoldContext& get() {
    if (ctx != nullptr && ctx->pid != getpid()) ctx = nullptr;
    if (ctx == nullptr) ctx = new FoldContext();
    return *ctx;
  }
};

thread_local FoldContextSlot t_fold_context;
// Table grows during the calling thread's newest lh_fold_packed.
thread_local int64_t t_fold_grows = 0;

// The one-thread fold: one fresh table for the whole batch.
int64_t fold_one_table(const int32_t* ids, const float* values, int64_t n,
                       int precision, int bucket_limit, int32_t** out) {
  const int64_t cap = LH_PACKED_COUNT_CAP;
  std::unique_ptr<CellStore> cs(new CellStore(1 << 14));
  for (int64_t i = 0; i < n; ++i) {
    int32_t id = ids[i];
    if (id < 0) continue;
    uint64_t key = cell_key(id, clamped_bucket(values[i], precision,
                                               bucket_limit));
    if (!cs->add_one(key, 1)) {
      t_fold_grows = cs->grows;
      return -1;
    }
  }
  t_fold_grows = cs->grows;
  int64_t total = packed_rows_needed(*cs, cap);
  int32_t* buf = new (std::nothrow) int32_t[3 * std::max<int64_t>(total, 1)];
  if (!buf) return -1;
  emit_packed_split(*cs, cap, buf);
  *out = buf;
  return total;
}

// The key-partitioned fold over t threads, in the calling thread's
// context.  Leaves its tables empty, whether it succeeds or fails.
int64_t fold_partitioned(const int32_t* ids, const float* values, int64_t n,
                         int precision, int bucket_limit, int t,
                         int32_t** out) {
  const int64_t cap = LH_PACKED_COUNT_CAP;
  FoldContext& c = t_fold_context.get();
  if (!c.reserve(n, t)) return -1;
  const int64_t grows_before = c.grows();
  // 1. each slice: code its samples, count its keys by partition
  auto code = [&](int s) {
    int64_t counts[kMaxFoldThreads] = {};
    for (int64_t i = n * s / t, hi = n * (s + 1) / t; i < hi; ++i) {
      int32_t id = ids[i];
      if (id < 0) continue;
      int32_t b = clamped_bucket(values[i], precision, bucket_limit);
      c.buckets[i] = static_cast<int16_t>(b);
      ++counts[partition_of(cell_key(id, b), t)];
    }
    std::copy(counts, counts + t, &c.starts[static_cast<size_t>(s) * t]);
  };
  if (!c.pool.run(t, code)) return -1;
  // partition p holds slice 0's keys of p, then slice 1's, ...
  int64_t at = 0;
  for (int p = 0; p < t; ++p) {
    c.bounds[p] = at;
    for (int s = 0; s < t; ++s) {
      int64_t& start = c.starts[static_cast<size_t>(s) * t + p];
      int64_t count = start;
      start = at;
      at += count;
    }
  }
  c.bounds[t] = at;
  // 2. each slice: scatter its keys to their partitions
  auto scatter = [&](int s) {
    int64_t pos[kMaxFoldThreads];
    std::copy(&c.starts[static_cast<size_t>(s) * t],
              &c.starts[static_cast<size_t>(s) * t] + t, pos);
    for (int64_t i = n * s / t, hi = n * (s + 1) / t; i < hi; ++i) {
      int32_t id = ids[i];
      if (id < 0) continue;
      uint64_t key = cell_key(id, c.buckets[i]);
      c.keys[pos[partition_of(key, t)]++] = key;
    }
  };
  // 3. each partition: fold its keys into its table, count its rows
  std::atomic<bool> failed{false};
  auto fold = [&](int p) {
    CellStore& cs = *c.tables[p];
    for (int64_t k = c.bounds[p]; k < c.bounds[p + 1]; ++k) {
      if (!cs.add_one(c.keys[k], 1)) {
        failed.store(true);
        return;
      }
    }
    c.rows[p] = packed_rows_needed(cs, cap);
  };
  bool ran = c.pool.run(t, scatter) && c.pool.run(t, fold);
  t_fold_grows = c.grows() - grows_before;
  int32_t* buf = nullptr;
  if (ran && !failed.load()) {
    int64_t total = 0;
    for (int p = 0; p < t; ++p) {
      int64_t r = c.rows[p];
      c.rows[p] = total;
      total += r;
    }
    buf = new (std::nothrow) int32_t[3 * std::max<int64_t>(total, 1)];
    // 4. each partition: emit its rows at its offset, emptying its table
    auto emit = [&](int p) {
      emit_packed_split(*c.tables[p], cap, buf + 3 * c.rows[p]);
    };
    if (buf && c.pool.run(t, emit)) {
      *out = buf;
      return total;
    }
  }
  delete[] buf;
  c.clear_tables();
  return -1;
}

}  // namespace

extern "C" {

void lh_packed_free(int32_t* p) { delete[] p; }

// Fold a raw batch into packed triples with up to `num_threads` threads
// (one below ~64k samples a thread, where the partition pass costs more
// than it saves).  *out receives a buffer allocated here (release with
// lh_packed_free).  Returns the row count, or -1 when an allocation
// failed (nothing is leaked and the kept tables are left empty; the
// caller falls back to the NumPy tier or raw transport).
int64_t lh_fold_packed(const int32_t* ids, const float* values, int64_t n,
                       int precision, int bucket_limit, int num_threads,
                       int32_t** out) {
  if (num_threads < 1) num_threads = 1;
  if (num_threads > kMaxFoldThreads) num_threads = kMaxFoldThreads;
  int64_t max_t = n / 65536 + 1;
  if (num_threads > max_t) num_threads = static_cast<int>(max_t);
  t_fold_grows = 0;
  try {
    if (num_threads == 1)
      return fold_one_table(ids, values, n, precision, bucket_limit, out);
    return fold_partitioned(ids, values, n, precision, bucket_limit,
                            num_threads, out);
  } catch (...) {
    // an allocation threw partway: no exception crosses the C ABI, and
    // the next call must not find this one's cells in the kept tables
    if (t_fold_context.ctx != nullptr) t_fold_context.ctx->clear_tables();
    return -1;
  }
}

// Table grows during the calling thread's newest lh_fold_packed: near 0
// once that thread's kept tables have grown to its items' cells.
int64_t lh_fold_last_grows() { return t_fold_grows; }

// Parallel drain of `num_stores` detached CellStore handles into one
// packed buffer (allocated here; release with lh_packed_free) — the
// ShardedCellStore's whole-store drain in one GIL-released call, shards
// scanned concurrently.  Returns total rows or -1 on allocation failure
// (the stores are left untouched in that case: sizing happens before
// any table is cleared).
int64_t lh_cells_drain_packed_multi(void** stores, int num_stores,
                                    int num_threads, int32_t** out) {
  const int64_t cap = LH_PACKED_COUNT_CAP;
  if (num_stores < 1) return 0;
  if (num_threads < 1) num_threads = 1;
  if (num_threads > num_stores) num_threads = num_stores;
  std::vector<int64_t> offsets(num_stores);
  int64_t total = 0;
  for (int i = 0; i < num_stores; ++i) {
    offsets[i] = total;
    total += packed_rows_needed(*static_cast<CellStore*>(stores[i]), cap);
  }
  int32_t* buf = new (std::nothrow) int32_t[3 * std::max<int64_t>(total, 1)];
  if (!buf) return -1;
  auto drain_range = [&](int t) {
    for (int i = t; i < num_stores; i += num_threads)
      emit_packed_split(*static_cast<CellStore*>(stores[i]), cap,
                        buf + 3 * offsets[i]);
  };
  if (num_threads == 1) {
    drain_range(0);
  } else {
    std::vector<std::thread> threads;
    threads.reserve(num_threads);
    for (int t = 0; t < num_threads; ++t)
      threads.emplace_back(drain_range, t);
    for (auto& th : threads) th.join();
  }
  *out = buf;
  return total;
}

// Dense accumulate on host: the CPU fallback / verification twin of the
// device scatter-add kernel. acc is uint32[num_metrics][2*bucket_limit+1].
void lh_accumulate_dense(const int32_t* ids, const double* values, int64_t n,
                         int precision, int bucket_limit, uint32_t* acc,
                         int32_t num_metrics) {
  const int64_t row = 2 * static_cast<int64_t>(bucket_limit) + 1;
  for (int64_t i = 0; i < n; ++i) {
    int32_t id = ids[i];
    if (id < 0 || id >= num_metrics) continue;
    int32_t b = compress_one(values[i], precision);
    if (b < -bucket_limit) b = -bucket_limit;
    if (b > bucket_limit) b = bucket_limit;
    ++acc[id * row + b + bucket_limit];
  }
}

}  // extern "C"
