// Native zero-Python PS read path (SURVEY §3.1: the reference serves ALL
// traffic from native handlers).  A CPsShard holds generation-versioned
// row snapshots; the Python tier keeps ownership of the write path
// (ApplyGrad mutates its numpy table, then publishes a new generation via
// brt_ps_shard_install) while Lookup is served entirely inside the C++
// fiber handler — no GIL, no ctypes trampoline, no Python framing.
//
// Concurrency is the PR-4 handle-generation scheme moved down a layer:
// readers pin the current generation (a snapshot is immutable once
// installed), gather outside the lock, unpin; install swaps the current
// pointer under the mutex and retires the old snapshot, which is freed by
// the last reader to unpin it.  Torn rows are impossible by construction;
// no reader ever blocks a writer beyond the pointer swap.

#include <atomic>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <string>
#include <vector>

#include "base/time.h"
#include "capi/c_api.h"
#include "capi/capi_internal.h"
#include "fiber/sync.h"
#include "rpc/errors.h"

namespace {

using namespace brt;
using brt_capi::CServer;
using brt_capi::CSession;

// One immutable snapshot of the shard's rows.  `pins` counts in-flight
// readers; a retired snapshot is freed by whoever drops the last pin.
struct ShardGen {
  std::vector<float> rows;   // [rows_per, dim], row-major
  uint64_t gen = 0;
  int pins = 0;
  bool retired = false;
};

struct CPsShard {
  int64_t vocab = 0;
  int64_t dim = 0;
  int shard_index = 0;
  int n_shards = 1;
  int64_t rows_per = 0;
  int64_t base = 0;

  FiberMutex mu;                       // guards current/retired only
  ShardGen* current = nullptr;         // owned; swapped by install
  std::atomic<uint64_t> generation{0};
  std::atomic<uint64_t> native_lookups{0};
  // Service-time accounting for the zero-Python read path: the bound
  // language's per-server latency recorder never sees native Lookups,
  // so the sum/count pair is exported (brt_ps_shard_lookup_stats) and
  // folded into its tail stats there.
  std::atomic<uint64_t> lookup_us_sum{0};

  ~CPsShard() {
    // By contract the server (and with it every in-flight handler) is
    // destroyed before the shard, so no pins remain.
    delete current;
  }
};

// Serves `Lookup` natively; every other method (ApplyGrad, lifecycle,
// fault injection) goes through the bound-language fallback handler with
// the exact CService session contract.
class CPsService : public Service {
 public:
  CPsService(CPsShard* shard, brt_service_handler fallback, void* user)
      : shard_(shard), fallback_(fallback), user_(user) {}

  void CallMethod(const std::string& method, Controller* cntl,
                  const IOBuf& request, IOBuf* response,
                  Closure done) override {
    if (method == "Lookup") {
      ServeLookup(cntl, request, response);
      done();
      return;
    }
    auto* sess = new CSession{cntl, response, std::move(done)};
    const std::string req = request.to_string();
    cntl->stamps.handler_ns = monotonic_ns();
    fallback_(user_, method.c_str(), req.data(), req.size(), sess);
  }

 private:
  void ServeLookup(Controller* cntl, const IOBuf& request,
                   IOBuf* response) {
    // Wire format (ps_remote.py): int32 count ++ int32 ids (absolute);
    // response float32 rows [count, dim].  An optional deadline header
    // (wire schema deadline_hdr: magic int32 0x7EAD11E5 ++ absolute
    // wall-clock deadline in us) may prefix the frame — the magic is
    // above any legitimate count, so the two framings cannot collide.
    // Expired work is shed HERE, before ids are even copied out: the
    // overload-control contract for the zero-Python read path.
    const int64_t t0 = monotonic_us();
    size_t off = 0;
    int32_t count = 0;
    if (request.size() < 4) {
      cntl->SetFailed(EREQUEST, "Lookup request shorter than its header");
      return;
    }
    request.copy_to(&count, 4);
    if (count == 0x7EAD11E5 /* wire.DEADLINE_MAGIC */ ||
        count == 0x7EAD11E6 /* wire.DEADLINE_MAGIC2 (relative) */) {
      if (request.size() < 12) {
        cntl->SetFailed(EREQUEST, "Lookup deadline header truncated");
        return;
      }
      int64_t deadline_us = 0;
      request.copy_to(&deadline_us, 8, 4);
      if (count == 0x7EAD11E6) {
        // v2: the field is the REMAINING budget; expiry is the local
        // arrival stamp plus that budget — no cross-host wall-clock
        // agreement is assumed (wire schema deadline_hdr_v2).
        if (deadline_us <= 0) {
          cntl->SetFailed(EDEADLINE,
                          "deadline budget exhausted before Lookup started");
          return;
        }
        deadline_us += realtime_us();
      }
      off = 12;
      if (deadline_us > 0 && realtime_us() > deadline_us) {
        cntl->SetFailed(EDEADLINE,
                        "deadline budget exhausted before Lookup started");
        return;
      }
      if (request.size() < off + 4) {
        cntl->SetFailed(EREQUEST, "Lookup request shorter than its header");
        return;
      }
      request.copy_to(&count, 4, off);
    }
    if (count < 0 ||
        request.size() != off + 4 + size_t(count) * 4) {
      cntl->SetFailed(EREQUEST, "Lookup request length mismatch "
                                "(count=%d, %zu bytes)",
                      int(count), request.size() - off);
      return;
    }
    std::vector<int32_t> ids(static_cast<size_t>(count));
    if (count > 0) request.copy_to(ids.data(), size_t(count) * 4, off + 4);
    for (int32_t& id : ids) {
      const int64_t local = int64_t(id) - shard_->base;
      if (local < 0 || local >= shard_->rows_per) {
        // Same failure the Python _serve path raises (EINTERNAL via the
        // trampoline): out-of-range ids would gather the wrong rows.
        cntl->SetFailed(
            EINTERNAL, "ids outside shard [%lld, %lld) for shard base %lld",
            (long long)shard_->base,
            (long long)(shard_->base + shard_->rows_per),
            (long long)shard_->base);
        return;
      }
      id = int32_t(local);
    }
    // Pin the live snapshot; gather happens outside the lock.
    shard_->mu.lock();
    ShardGen* g = shard_->current;
    if (g == nullptr) {
      shard_->mu.unlock();
      cntl->SetFailed(EINTERNAL, "no table generation installed");
      return;
    }
    ++g->pins;
    shard_->mu.unlock();

    const size_t dim = size_t(shard_->dim);
    const size_t nbytes = size_t(count) * dim * 4;
    if (nbytes > 0) {
      // Gather straight into a malloc'd region adopted by the response
      // IOBuf (one copy total; free() runs when the socket releases it).
      float* out = static_cast<float*>(malloc(nbytes));
      if (out == nullptr) {
        Unpin(g);
        cntl->SetFailed(EINTERNAL, "oom gathering %zu bytes", nbytes);
        return;
      }
      const float* rows = g->rows.data();
      for (size_t i = 0; i < size_t(count); ++i) {
        memcpy(out + i * dim, rows + size_t(ids[i]) * dim, dim * 4);
      }
      response->append_user_data(
          out, nbytes, [](void* data, void*) { free(data); }, nullptr);
    }
    Unpin(g);
    shard_->lookup_us_sum.fetch_add(uint64_t(monotonic_us() - t0),
                                    std::memory_order_relaxed);
    shard_->native_lookups.fetch_add(1, std::memory_order_relaxed);
  }

  void Unpin(ShardGen* g) {
    shard_->mu.lock();
    const bool free_it = (--g->pins == 0) && g->retired;
    shard_->mu.unlock();
    if (free_it) delete g;
  }

  CPsShard* shard_;
  brt_service_handler fallback_;
  void* user_;
};

}  // namespace

extern "C" {

void* brt_ps_shard_new(int64_t vocab, int64_t dim, int shard_index,
                       int n_shards) {
  if (vocab <= 0 || dim <= 0 || n_shards <= 0 || shard_index < 0 ||
      shard_index >= n_shards || vocab % n_shards != 0) {
    return nullptr;
  }
  auto* s = new CPsShard;
  s->vocab = vocab;
  s->dim = dim;
  s->shard_index = shard_index;
  s->n_shards = n_shards;
  s->rows_per = vocab / n_shards;
  s->base = int64_t(shard_index) * s->rows_per;
  brt_capi::handle_inc(brt_capi::HandleKind::kPsShard);
  return s;
}

int brt_ps_shard_install(void* shard, const void* table, int64_t rows,
                         uint64_t gen) {
  auto* s = static_cast<CPsShard*>(shard);
  if (table == nullptr || rows != s->rows_per) return EINVAL;
  // Snapshot the caller's buffer NOW: the Python tier goes on mutating
  // its numpy table the moment this returns, while pinned readers keep
  // gathering from retired snapshots.
  auto* next = new ShardGen;
  next->gen = gen;
  next->rows.resize(size_t(rows) * size_t(s->dim));
  memcpy(next->rows.data(), table, next->rows.size() * 4);

  s->mu.lock();
  ShardGen* old = s->current;
  s->current = next;
  bool free_old = false;
  if (old != nullptr) {
    old->retired = true;
    free_old = (old->pins == 0);
  }
  s->generation.store(gen, std::memory_order_release);
  s->mu.unlock();
  if (free_old) delete old;
  return 0;
}

uint64_t brt_ps_shard_generation(void* shard) {
  return static_cast<CPsShard*>(shard)->generation.load(
      std::memory_order_acquire);
}

uint64_t brt_ps_shard_native_lookups(void* shard) {
  return static_cast<CPsShard*>(shard)->native_lookups.load(
      std::memory_order_relaxed);
}

void brt_ps_shard_lookup_stats(void* shard, int64_t* sum_us,
                               int64_t* count) {
  auto* s = static_cast<CPsShard*>(shard);
  // count is read after sum so a racing Lookup can only make the pair
  // conservative (sum missing its newest sample), never inflate the mean.
  if (sum_us != nullptr) {
    *sum_us = int64_t(s->lookup_us_sum.load(std::memory_order_relaxed));
  }
  if (count != nullptr) {
    *count = int64_t(s->native_lookups.load(std::memory_order_relaxed));
  }
}

int brt_server_add_ps_service(void* server, const char* name, void* shard,
                              brt_service_handler fallback, void* user) {
  auto* s = static_cast<CServer*>(server);
  auto svc = std::make_unique<CPsService>(static_cast<CPsShard*>(shard),
                                          fallback, user);
  const int rc = s->server.AddService(svc.get(), name);
  if (rc == 0) s->services.push_back(std::move(svc));
  return rc;
}

void brt_ps_shard_destroy(void* shard) {
  if (shard == nullptr) return;
  delete static_cast<CPsShard*>(shard);
  brt_capi::handle_dec(brt_capi::HandleKind::kPsShard);
}

}  // extern "C"
