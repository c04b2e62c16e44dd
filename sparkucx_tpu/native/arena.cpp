// Native memory arena + batch copy for the TPU shuffle framework.
//
// This is the in-repo replacement for the two JNI libraries the reference
// delegates all native work to (SURVEY.md §2 "Native / non-JVM components"):
//
//  * jucx's registered-memory role (ucxContext.memoryMap behind
//    MemoryPool.scala:55-110): ts_alloc_aligned/ts_mlock provide page-aligned,
//    optionally pinned host slabs that XLA's host->HBM DMA path can stream from
//    without bouncing.
//  * nvkv's shared block-device role (NvkvHandler.scala): ts_shm_* exposes a
//    named shared-memory arena so executor processes on one host stage and
//    serve shuffle blocks zero-copy — the single-host analogue of the
//    DPU-attached NVMe store every executor's daemon can read.
//  * the server-side parallel block gather (ForkJoin ioThreadPool,
//    UcxWorkerWrapper.scala:416-426): ts_batch_copy moves N scattered segments
//    with a thread team sized to the total byte count.
//
// Plain C ABI; bound from Python with ctypes (no pybind11 in the image).

#include <atomic>
#include <cerrno>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <fcntl.h>
#include <mutex>
#include <new>
#include <sys/mman.h>
#include <sys/stat.h>
#include <thread>
#include <unistd.h>
#include <vector>

extern "C" {

// ---------------------------------------------------------------------------
// Aligned (optionally pinned) private allocations
// ---------------------------------------------------------------------------

void* ts_alloc_aligned(uint64_t size, uint64_t alignment) {
  void* ptr = nullptr;
  if (posix_memalign(&ptr, alignment, size) != 0) return nullptr;
  return ptr;
}

void ts_free_aligned(void* ptr) { free(ptr); }

// Pin pages (registered-memory analogue). Returns 0 on success, errno on failure
// (callers treat failure as advisory: unpinned staging still works, like the
// reference running UCX without ODP).
int ts_mlock(void* ptr, uint64_t size) {
  return mlock(ptr, size) == 0 ? 0 : errno;
}

int ts_munlock(void* ptr, uint64_t size) {
  return munlock(ptr, size) == 0 ? 0 : errno;
}

// ---------------------------------------------------------------------------
// Named shared-memory arenas (cross-process staging)
// ---------------------------------------------------------------------------

struct TsShm {
  void* addr;
  uint64_t size;
  int fd;
};

// create=1: O_CREAT|O_EXCL + ftruncate (the owner); create=0: attach existing.
TsShm* ts_shm_open(const char* name, uint64_t size, int create) {
  int flags = create ? (O_RDWR | O_CREAT | O_EXCL) : O_RDWR;
  int fd = shm_open(name, flags, 0600);
  if (fd < 0) return nullptr;
  if (create && ftruncate(fd, (off_t)size) != 0) {
    close(fd);
    shm_unlink(name);
    return nullptr;
  }
  if (!create) {
    struct stat st;
    if (fstat(fd, &st) != 0 || (uint64_t)st.st_size < size) {
      close(fd);
      return nullptr;
    }
  }
  void* addr = mmap(nullptr, size, PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
  if (addr == MAP_FAILED) {
    close(fd);
    if (create) shm_unlink(name);
    return nullptr;
  }
  TsShm* handle = new TsShm{addr, size, fd};
  return handle;
}

void* ts_shm_addr(TsShm* handle) { return handle ? handle->addr : nullptr; }
uint64_t ts_shm_size(TsShm* handle) { return handle ? handle->size : 0; }

void ts_shm_close(TsShm* handle) {
  if (!handle) return;
  munmap(handle->addr, handle->size);
  close(handle->fd);
  delete handle;
}

int ts_shm_unlink(const char* name) {
  return shm_unlink(name) == 0 ? 0 : errno;
}

// ---------------------------------------------------------------------------
// Batched scattered copy (server-side gather / client-side scatter)
// ---------------------------------------------------------------------------

struct TsSegment {
  uint64_t dst_off;
  uint64_t src_off;
  uint64_t len;
};

// Copy n segments from src to dst. Splits the segment list across a thread team
// when total bytes exceed ~4 MiB (below that, spawn cost dominates).
void ts_batch_copy(uint8_t* dst, const uint8_t* src, const TsSegment* segs,
                   uint64_t n, int max_threads) {
  uint64_t total = 0;
  for (uint64_t i = 0; i < n; ++i) total += segs[i].len;
  int hw = (int)std::thread::hardware_concurrency();
  int threads = max_threads > 0 ? max_threads : (hw > 0 ? hw : 1);
  if (total < (4u << 20) || threads <= 1 || n <= 1) {
    for (uint64_t i = 0; i < n; ++i)
      memcpy(dst + segs[i].dst_off, src + segs[i].src_off, segs[i].len);
    return;
  }
  std::atomic<uint64_t> next{0};
  auto worker = [&]() {
    for (;;) {
      uint64_t i = next.fetch_add(1);
      if (i >= n) return;
      memcpy(dst + segs[i].dst_off, src + segs[i].src_off, segs[i].len);
    }
  };
  std::vector<std::thread> team;
  int spawn = threads - 1;
  for (int t = 0; t < spawn; ++t) team.emplace_back(worker);
  worker();
  for (auto& th : team) th.join();
}

// ---------------------------------------------------------------------------
// Landing pool: a NumPy data allocator (NEP 49) that keeps its large blocks
// ---------------------------------------------------------------------------
//
// The runtime lands a device array on the host in a NumPy array it allocates
// for that one array; at 64 MiB that is a fresh mapping every time, first
// touched by the runtime's copy.  NumPy lets a thread name the allocator of
// the arrays it creates (PyDataMem_SetHandler) and remembers it on the array,
// so the array's last release comes back here: a block of `min_bytes` or
// more is then kept — its pages stay the process's — under `budget` bytes,
// and handed to the next allocation of that size; when the budget is full the
// blocks that came back longest ago make room.  Every block is plain
// malloc memory, kept or not, so realloc and a pool that has been emptied
// need nothing special.  The struct is never freed: arrays may outlive the
// Python object that made it, and they hold the handler.

struct TsDataMemAllocator {  // numpy/ndarraytypes.h PyDataMemAllocator
  void* ctx;
  void* (*malloc)(void* ctx, size_t size);
  void* (*calloc)(void* ctx, size_t nelem, size_t elsize);
  void* (*realloc)(void* ctx, void* ptr, size_t new_size);
  void (*free)(void* ctx, void* ptr, size_t size);
};

struct TsDataMemHandler {  // PyDataMem_Handler, version 1
  char name[127];
  uint8_t version;
  TsDataMemAllocator allocator;
};

struct TsLandingBlock {
  size_t size;
  void* ptr;
};

struct TsLandingPool {
  TsDataMemHandler handler;  // first member: what the capsule points at
  std::mutex mu;
  std::vector<TsLandingBlock> kept;  // in the order they came back
  uint64_t budget = 0, min_bytes = 0, held_bytes = 0;
  uint64_t hits = 0, misses = 0, kept_blocks = 0, dropped_blocks = 0;
};

static void* landing_take(TsLandingPool* pool, size_t size) {
  if (size < pool->min_bytes) return nullptr;
  std::lock_guard<std::mutex> g(pool->mu);
  // the block of this size that came back last: the likeliest still cached
  for (size_t i = pool->kept.size(); i-- > 0;) {
    if (pool->kept[i].size != size) continue;
    void* ptr = pool->kept[i].ptr;
    pool->kept.erase(pool->kept.begin() + i);
    pool->held_bytes -= size;
    ++pool->hits;
    return ptr;
  }
  ++pool->misses;
  return nullptr;
}

static void* landing_malloc(void* ctx, size_t size) {
  void* ptr = landing_take(static_cast<TsLandingPool*>(ctx), size);
  return ptr ? ptr : malloc(size ? size : 1);
}

static void* landing_calloc(void* ctx, size_t nelem, size_t elsize) {
  size_t size = nelem * elsize;
  if (elsize && size / elsize != nelem) return nullptr;
  void* ptr = landing_take(static_cast<TsLandingPool*>(ctx), size);
  if (ptr) return memset(ptr, 0, size);
  return calloc(nelem ? nelem : 1, elsize ? elsize : 1);
}

static void* landing_realloc(void*, void* ptr, size_t new_size) {
  return realloc(ptr, new_size ? new_size : 1);
}

static void landing_free(void* ctx, void* ptr, size_t size) {
  auto* pool = static_cast<TsLandingPool*>(ctx);
  std::vector<void*> gone;  // freed outside the lock
  if (ptr && size >= pool->min_bytes) {
    std::lock_guard<std::mutex> g(pool->mu);
    if (size <= pool->budget) {
      // the newest block stays and the oldest go: sizes nobody asks for any
      // more must not hold the budget against the sizes in use
      size_t n = 0;
      while (pool->held_bytes + size > pool->budget && n < pool->kept.size()) {
        pool->held_bytes -= pool->kept[n].size;
        gone.push_back(pool->kept[n++].ptr);
      }
      pool->kept.erase(pool->kept.begin(), pool->kept.begin() + n);
      pool->kept.push_back({size, ptr});
      pool->held_bytes += size;
      ++pool->kept_blocks;
    } else {
      gone.push_back(ptr);
    }
    pool->dropped_blocks += gone.size();
  } else {
    gone.push_back(ptr);
  }
  for (void* block : gone) free(block);
}

// A pool keeping blocks of `min_bytes` or more under `budget` bytes; what it
// returns is also the address of its PyDataMem_Handler.
void* ts_landing_pool_new(uint64_t budget, uint64_t min_bytes) {
  auto* pool = new (std::nothrow) TsLandingPool();
  if (!pool) return nullptr;
  snprintf(pool->handler.name, sizeof(pool->handler.name), "sparkucx_tpu_landing");
  pool->handler.version = 1;
  pool->handler.allocator = {pool, landing_malloc, landing_calloc, landing_realloc, landing_free};
  pool->budget = budget;
  pool->min_bytes = min_bytes ? min_bytes : 1;
  return pool;
}

// Give the kept blocks back and keep none from now on (the pool's owner is
// gone); blocks still out are freed as they come back.
void ts_landing_pool_retire(void* handle) {
  auto* pool = static_cast<TsLandingPool*>(handle);
  std::lock_guard<std::mutex> g(pool->mu);
  pool->budget = 0;
  for (auto& block : pool->kept) free(block.ptr);
  pool->kept.clear();
  pool->held_bytes = 0;
}

// hits, misses, kept_blocks, dropped_blocks, held_bytes, budget
void ts_landing_pool_stats(void* handle, uint64_t* out) {
  auto* pool = static_cast<TsLandingPool*>(handle);
  std::lock_guard<std::mutex> g(pool->mu);
  out[0] = pool->hits;
  out[1] = pool->misses;
  out[2] = pool->kept_blocks;
  out[3] = pool->dropped_blocks;
  out[4] = pool->held_bytes;
  out[5] = pool->budget;
}

uint64_t ts_version() { return 1; }

}  // extern "C"
