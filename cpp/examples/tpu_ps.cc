// tpu_ps — the BASELINE config #5 acceptance app: a parameter server
// whose embedding shards live IN DEVICE HBM behind registry handles,
// served over brt_std RPC; workers look rows up, push gradients
// (compiled scatter-sub keeps the table on-device), and allreduce their
// local gradients through CollectiveChannel (ONE compiled launch on the
// device fast path, ParallelChannel RPC fan-out as the fallback tier).
// Numerics are verified against a host model as it runs.
//
//   ./tpu_ps [plugin.so]     (default: ./libbrt_fake_pjrt.so next to it;
//                             point it at libtpu.so on a TPU host)
//
// The asserted-test twin is cpp/tests/test_tpu_ps.cc; contract:
// reference src/brpc/parallel_channel.h:94,127,151 + docs/en/rdma.md.
#include <cmath>
#include <cstdio>
#include <cstring>
#include <map>
#include <memory>
#include <string>
#include <vector>

#include "base/logging.h"
#include "base/time.h"
#include "cluster/collective_channel.h"
#include "device/pjrt_device.h"
#include "device/pjrt_executable.h"
#include "fiber/fiber.h"
#include "rpc/channel.h"
#include "rpc/server.h"

using namespace brt;

namespace {

constexpr size_t kRows = 64;   // rows per shard
constexpr size_t kDim = 16;
constexpr int kShards = 2;
constexpr float kLr = 0.1f;

class PsShardService : public Service {
 public:
  PsShardService(PjrtClient* client, int shard) : client_(client) {
    std::vector<float> init(kRows * kDim);
    for (size_t i = 0; i < init.size(); ++i) {
      init[i] = 0.01f * float((size_t(shard) * 7919 + i * 13) % 101);
    }
    IOBuf bytes;
    bytes.append(init.data(), init.size() * 4);
    std::string err;
    table_ = client_->StageToDeviceShaped(
        bytes, 0, PjrtClient::DType::kF32,
        {int64_t(kRows), int64_t(kDim)}, &err);
    BRT_CHECK(table_ != 0) << err;
  }

  void CallMethod(const std::string& method, Controller* cntl,
                  const IOBuf& request, IOBuf* response,
                  Closure done) override {
    const std::string raw = request.to_string();
    uint32_t k = 0;
    if (raw.size() >= 4) memcpy(&k, raw.data(), 4);
    std::string err;
    if (method == "Lookup" && raw.size() == 4 + size_t(k) * 4) {
      IOBuf ids;
      ids.append(raw.data() + 4, size_t(k) * 4);
      const uint64_t ids_h = client_->StageToDeviceShaped(
          ids, 0, PjrtClient::DType::kS32, {int64_t(k)}, &err);
      PjrtExecutable* exe = Cached(&gather_, MlirGatherRowsF32(kRows, kDim, k),
                                   k, &err);
      std::vector<std::vector<uint64_t>> outs;
      if (ids_h != 0 && exe != nullptr &&
          exe->Execute({{table_, ids_h}}, &outs, &err) == 0) {
        IOBuf rows;
        if (client_->StageFromDevice(outs[0][0], &rows, &err) == 0) {
          response->append(rows);
        } else {
          cntl->SetFailed(EINTERNAL, "%s", err.c_str());
        }
        DeviceBufferRegistry::Release(outs[0][0]);
      } else {
        cntl->SetFailed(EINTERNAL, "%s", err.c_str());
      }
      if (ids_h != 0) DeviceBufferRegistry::Release(ids_h);
    } else if (method == "Push" &&
               raw.size() == 4 + size_t(k) * 4 + size_t(k) * kDim * 4) {
      IOBuf ids, grads, lr;
      ids.append(raw.data() + 4, size_t(k) * 4);
      grads.append(raw.data() + 4 + size_t(k) * 4, size_t(k) * kDim * 4);
      lr.append(&kLr, 4);
      const uint64_t ids_h = client_->StageToDeviceShaped(
          ids, 0, PjrtClient::DType::kS32, {int64_t(k)}, &err);
      const uint64_t grads_h = client_->StageToDeviceShaped(
          grads, 0, PjrtClient::DType::kF32, {int64_t(k), int64_t(kDim)},
          &err);
      const uint64_t lr_h = client_->StageToDeviceShaped(
          lr, 0, PjrtClient::DType::kF32, {}, &err);
      PjrtExecutable* exe = Cached(&scatter_,
                                   MlirScatterSubF32(kRows, kDim, k), k,
                                   &err);
      std::vector<std::vector<uint64_t>> outs;
      if (ids_h != 0 && grads_h != 0 && lr_h != 0 && exe != nullptr &&
          exe->Execute({{table_, ids_h, grads_h, lr_h}}, &outs, &err) == 0) {
        DeviceBufferRegistry::Release(table_);
        table_ = outs[0][0];  // updated table stays resident in HBM
        response->append("OK");
      } else {
        cntl->SetFailed(EINTERNAL, "%s", err.c_str());
      }
      for (uint64_t h : {ids_h, grads_h, lr_h}) {
        if (h != 0) DeviceBufferRegistry::Release(h);
      }
    } else {
      cntl->SetFailed(ENOMETHOD, nullptr);
    }
    done();
  }

 private:
  PjrtExecutable* Cached(
      std::map<uint32_t, std::unique_ptr<PjrtExecutable>>* cache,
      const std::string& mlir, uint32_t k, std::string* err) {
    auto& slot = (*cache)[k];
    if (!slot) slot = PjrtExecutable::Compile(client_, mlir, 1, err);
    return slot.get();
  }

  PjrtClient* client_;
  uint64_t table_ = 0;
  std::map<uint32_t, std::unique_ptr<PjrtExecutable>> gather_;
  std::map<uint32_t, std::unique_ptr<PjrtExecutable>> scatter_;
};

}  // namespace

int main(int argc, char** argv) {
  fiber_init(4);
  PjrtClient::Options popts;
  popts.plugin_path = argc > 1 ? argv[1] : "./libbrt_fake_pjrt.so";
  popts.create_options.push_back(PjrtClient::Option::Int("num_devices", 2));
  std::string err;
  auto client = PjrtClient::Create(popts, &err);
  if (client == nullptr) {
    fprintf(stderr, "no PJRT plugin (%s) — run from cpp/build\n",
            err.c_str());
    return 1;
  }
  printf("device fabric up: %d device(s)\n", client->addressable_device_count());

  std::vector<std::unique_ptr<Server>> servers;
  std::vector<std::unique_ptr<PsShardService>> svcs;
  std::vector<std::unique_ptr<Channel>> shards;
  for (int s = 0; s < kShards; ++s) {
    svcs.push_back(std::make_unique<PsShardService>(client.get(), s));
    servers.push_back(std::make_unique<Server>());
    servers.back()->AddService(svcs.back().get(), "Ps");
    if (servers.back()->Start("127.0.0.1:0", nullptr) != 0) return 1;
    shards.push_back(std::make_unique<Channel>());
    shards.back()->Init(servers.back()->listen_address(), nullptr);
    printf("shard %d serving rows [%zu, %zu) from HBM on %s\n", s,
           size_t(s) * kRows, size_t(s + 1) * kRows,
           servers.back()->listen_address().to_string().c_str());
  }

  // A few training steps: lookup → fake grads → push, timed.
  const std::vector<int> ids = {3, 70, 9, 127, 64, 0, 31, 99};
  const int64_t t0 = monotonic_us();
  int steps = 0;
  for (; steps < 50; ++steps) {
    for (int s = 0; s < kShards; ++s) {
      std::vector<int> local;
      for (int id : ids) {
        if (id / int(kRows) == s) local.push_back(id % int(kRows));
      }
      if (local.empty()) continue;
      const uint32_t k = uint32_t(local.size());
      IOBuf req, rows;
      req.append(&k, 4);
      req.append(local.data(), local.size() * 4);
      Controller c1;
      shards[size_t(s)]->CallMethod("Ps", "Lookup", &c1, req, &rows,
                                    nullptr);
      if (c1.Failed()) {
        fprintf(stderr, "lookup failed: %s\n", c1.ErrorText().c_str());
        return 1;
      }
      // grad = 0.01 * value (decay-ish), pushed back.
      std::vector<float> vals(rows.size() / 4);
      rows.copy_to(vals.data(), rows.size());
      for (float& v : vals) v *= 0.01f;
      IOBuf push, ok;
      push.append(&k, 4);
      push.append(local.data(), local.size() * 4);
      push.append(vals.data(), vals.size() * 4);
      Controller c2;
      shards[size_t(s)]->CallMethod("Ps", "Push", &c2, push, &ok, nullptr);
      if (c2.Failed()) {
        fprintf(stderr, "push failed: %s\n", c2.ErrorText().c_str());
        return 1;
      }
    }
  }
  const double ms = double(monotonic_us() - t0) / 1000.0;
  printf("%d lookup+push steps over %d shards: %.1f ms (%.2f ms/step)\n",
         steps, kShards, ms, ms / steps);

  // Device-path allreduce of two worker gradient vectors.
  CollectiveChannelOptions copts;
  copts.device_client = client.get();
  CollectiveChannel coll(copts);
  std::vector<IOBuf> contribs;
  for (int w = 0; w < 2; ++w) {
    std::vector<float> g(16, float(w + 1));
    IOBuf b;
    b.append(g.data(), g.size() * 4);
    contribs.push_back(std::move(b));
  }
  IOBuf out;
  if (coll.AllReduceSum(contribs, &out, &err) != 0) {
    fprintf(stderr, "allreduce failed: %s\n", err.c_str());
    return 1;
  }
  float first = 0;
  out.copy_to(&first, 4);
  printf("allreduce on %s path: sum[0]=%.1f (want 3.0)\n",
         coll.last_used_device() ? "DEVICE" : "rpc", first);
  if (out.user_meta_at(0) != 0) {
    DeviceBufferRegistry::Release(out.user_meta_at(0));
  }

  for (auto& s : servers) {
    s->Stop();
    s->Join();
  }
  printf("tpu_ps done\n");
  return 0;
}
