// PJRT device-layer tests: IOBuf staged through a PJRT device buffer,
// fibers parking on PJRT events, and an RPC echo whose payload rides device
// memory. Mirrors the reference's rdma_endpoint zero-copy contract
// (src/brpc/rdma/rdma_endpoint.cpp:774,1011) with PJRT as the fabric.
//
// Runs against $BRT_PJRT_PLUGIN (point it at libtpu on a TPU host), else
// the in-repo fake ./libbrt_fake_pjrt.so (run from the build directory).
// A plugin that does not come up is a failure, never a skip.
#include <unistd.h>

#include <atomic>
#include <cassert>
#include <cstdio>
#include <cstring>
#include <string>
#include <thread>

#include "base/iobuf.h"
#include "device/block_pool.h"
#include "device/pjrt_device.h"
#include "device/pjrt_executable.h"
#include "fiber/fiber.h"
#include "fiber/sync.h"
#include "rpc/channel.h"
#include "rpc/server.h"

using namespace brt;

namespace {

PjrtClient* g_client = nullptr;

// Echo service that bounces the attachment through device memory: request
// bytes DMA to HBM, DMA back, and the response attachment references the
// D2H landing block directly (no memcpy on the host path).
class DeviceEchoService : public Service {
 public:
  void CallMethod(const std::string& method, Controller* cntl,
                  const IOBuf& request, IOBuf* response, Closure done) override {
    std::string err;
    uint64_t h = g_client->StageToDevice(cntl->request_attachment(), 0, &err);
    if (h == 0) {
      cntl->SetFailed(5001, "stage to device failed: %s", err.c_str());
      done();
      return;
    }
    IOBuf from_dev;
    int rc = g_client->StageFromDevice(h, &from_dev, &err);
    if (rc != 0) {
      DeviceBufferRegistry::Release(h);
      cntl->SetFailed(5002, "stage from device failed: %s", err.c_str());
      done();
      return;
    }
    // The attachment's block meta carries the device-buffer handle — the
    // lkey analog a smarter peer could use to keep the tensor in HBM.
    assert(from_dev.user_meta_at(0) == h);
    cntl->response_attachment() = from_dev;
    response->append(request);
    done();
    DeviceBufferRegistry::Release(h);
  }
};

void test_roundtrip(PjrtClient* client) {
  // Single-block payload: staged zero-copy from the block.
  IOBuf small;
  small.append(std::string(1000, 'x'));
  IOBuf back;
  std::string err;
  assert(client->Roundtrip(small, &back, 0, &err) == 0);
  assert(back.size() == 1000);
  assert(back.equals(std::string(1000, 'x')));

  // Multi-block payload (coalesced once, then DMA'd).
  IOBuf big;
  std::string blob(100000, 'y');
  for (int i = 0; i < 3; ++i) big.append(blob);
  IOBuf back2;
  assert(client->Roundtrip(big, &back2, 0, &err) == 0);
  assert(back2.size() == 300000);
  std::string s = back2.to_string();
  for (char c : s) assert(c == 'y');
  printf("  roundtrip ok\n");
}

void test_block_pool_unit() {
  auto& pool = DeviceBlockPool::singleton();
  size_t cap = 0;
  void* p = pool.Acquire(1000, &cap);
  assert(p != nullptr && cap == 4096);
  pool.Release(p, cap);
  // Same-class acquire reuses the parked block.
  size_t cap2 = 0;
  void* q = pool.Acquire(4096, &cap2);
  assert(q == p && cap2 == 4096);
  pool.Release(q, cap2);
  // Oversize requests bypass the pool but are still accounted.
  const uint64_t over0 = pool.oversize_allocs.load();
  size_t cap3 = 0;
  void* r = pool.Acquire((16u << 20) + 1, &cap3);
  assert(r != nullptr && cap3 == (16u << 20) + 1);
  pool.Release(r, cap3);
  assert(pool.oversize_allocs.load() == over0 + 1);
  printf("  block pool unit ok\n");
}

// The staging hot path must not allocate: after warmup, repeated stagings
// are pure pool hits and every block comes back (the zero-malloc assertion
// VERDICT asked for, backed by the pool-stats vars).
void test_block_pool_staging(PjrtClient* client) {
  auto& pool = DeviceBlockPool::singleton();
  std::string err;
  {
    IOBuf in, out;
    in.append(std::string(1000, 'w'));
    assert(client->Roundtrip(in, &out, 0, &err) == 0);  // warm the class
  }
  const uint64_t misses0 = pool.misses.load();
  const uint64_t over0 = pool.oversize_allocs.load();
  const int64_t out0 = pool.outstanding.load();
  for (int i = 0; i < 8; ++i) {
    IOBuf in, out;
    in.append(std::string(1000, 'z'));
    assert(client->Roundtrip(in, &out, 0, &err) == 0);
    // `out` drops here → its landing block returns to the pool.
  }
  assert(pool.misses.load() == misses0);          // zero fresh allocations
  assert(pool.oversize_allocs.load() == over0);   // nothing bypassed
  assert(pool.hits.load() >= 8);
  assert(pool.outstanding.load() == out0);        // all blocks came back
  printf("  block pool staging reuse ok (zero malloc on hot path)\n");
}

void test_handle_registry(PjrtClient* client) {
  IOBuf payload;
  payload.append("registry");
  std::string err;
  uint64_t h = client->StageToDevice(payload, 0, &err);
  assert(h != 0);
  assert(DeviceBufferRegistry::Lookup(h) != nullptr);
  // Two independent D2H stages from the same resident buffer.
  IOBuf a, b;
  assert(client->StageFromDevice(h, &a, &err) == 0);
  assert(client->StageFromDevice(h, &b, &err) == 0);
  assert(a.equals("registry") && b.equals("registry"));
  assert(a.user_meta_at(0) == h);
  // Pin keeps the buffer alive across a Release (ship-the-handle race):
  // Release marks the handle dead immediately but destroys the PJRT buffer
  // only when the last pin drops.
  assert(DeviceBufferRegistry::Pin(h) != nullptr);
  assert(DeviceBufferRegistry::Release(h));
  assert(!DeviceBufferRegistry::Release(h));  // stale now
  assert(DeviceBufferRegistry::Lookup(h) == nullptr);
  assert(DeviceBufferRegistry::Pin(h) == nullptr);  // dead: no new pins
  DeviceBufferRegistry::Unpin(h);  // last ref → buffer destroyed here
  assert(DeviceBufferRegistry::Lookup(h) == nullptr);
  printf("  handle registry ok\n");
}

struct FiberArg {
  PjrtClient* client;
  CountdownEvent* ev;
  bool ok = false;
};

void* FiberStage(void* argp) {
  auto* arg = static_cast<FiberArg*>(argp);
  IOBuf in, out;
  in.append(std::string(5000, 'f'));
  std::string err;
  // The D2H wait inside parks THIS fiber on the PJRT event.
  arg->ok = arg->client->Roundtrip(in, &out, 0, &err) == 0 &&
            out.equals(std::string(5000, 'f'));
  arg->ev->signal();
  return nullptr;
}

void test_fiber_event_wait(PjrtClient* client) {
  // Many concurrent fibers, each parking on its own device event.
  constexpr int kN = 8;
  CountdownEvent ev(kN);
  FiberArg args[kN];
  for (auto& a : args) {
    a.client = client;
    a.ev = &ev;
    fiber_t tid;
    assert(fiber_start(&tid, FiberStage, &a) == 0);
  }
  ev.wait(-1);
  for (auto& a : args) assert(a.ok);
  printf("  fiber event wait ok (%d concurrent)\n", kN);
}

void test_device_echo_rpc(PjrtClient* client) {
  g_client = client;
  Server server;
  DeviceEchoService svc;
  assert(server.AddService(&svc, "DevEcho") == 0);
  assert(server.Start("127.0.0.1:0") == 0);
  Channel ch;
  assert(ch.Init(server.listen_address()) == 0);

  Controller cntl;
  cntl.timeout_ms = 30000;
  std::string payload(64 * 1024, 'd');
  cntl.request_attachment().append(payload);
  IOBuf req, rsp;
  req.append("via-device");
  ch.CallMethod("DevEcho", "Echo", &cntl, req, &rsp, nullptr);
  assert(!cntl.Failed());
  assert(rsp.equals("via-device"));
  assert(cntl.response_attachment().size() == payload.size());
  assert(cntl.response_attachment().equals(payload));
  server.Stop();
  server.Join();
  printf("  device echo rpc ok\n");
}

// Native compile + launch on the real device: the executable tier
// (device/pjrt_executable.cc) without JAX anywhere in the process.
void test_compile_execute(PjrtClient* client) {
  std::string err;
  auto add = PjrtExecutable::Compile(client, MlirAddF32(16), 1, &err);
  assert(add != nullptr && add->num_outputs() == 1);
  float a[16], b[16];
  for (int i = 0; i < 16; ++i) {
    a[i] = float(i);
    b[i] = float(100 - i);
  }
  IOBuf ba, bb;
  ba.append(a, sizeof(a));
  bb.append(b, sizeof(b));
  uint64_t ha = client->StageToDeviceShaped(ba, 0, PjrtClient::DType::kF32,
                                            {16}, &err);
  uint64_t hb = client->StageToDeviceShaped(bb, 0, PjrtClient::DType::kF32,
                                            {16}, &err);
  assert(ha != 0 && hb != 0);
  std::vector<std::vector<uint64_t>> outs;
  assert(add->Execute({{ha, hb}}, &outs, &err) == 0);
  IOBuf res;
  assert(client->StageFromDevice(outs[0][0], &res, &err) == 0);
  float r[16];
  res.copy_to(r, sizeof(r));
  for (int i = 0; i < 16; ++i) assert(r[i] == 100.0f);
  DeviceBufferRegistry::Release(outs[0][0]);

  // reduce-sum to scalar, and a 1-replica cross-replica all-reduce (the
  // collective op itself compiled and launched on the chip).
  auto rs = PjrtExecutable::Compile(client, MlirReduceSumF32(16), 1, &err);
  assert(rs != nullptr);
  auto ar =
      PjrtExecutable::Compile(client, MlirAllReduceSumF32(16, 1), 1, &err);
  assert(ar != nullptr);
  std::vector<std::vector<uint64_t>> o2, o3;
  assert(rs->Execute({{ha}}, &o2, &err) == 0);
  assert(ar->Execute({{ha}}, &o3, &err) == 0);
  IOBuf r2, r3;
  assert(client->StageFromDevice(o2[0][0], &r2, &err) == 0);
  assert(client->StageFromDevice(o3[0][0], &r3, &err) == 0);
  float sum;
  r2.copy_to(&sum, 4);
  assert(sum == 120.0f);  // 0+1+...+15
  float v[16];
  r3.copy_to(v, sizeof(v));
  for (int i = 0; i < 16; ++i) assert(v[i] == a[i]);
  for (auto& l : {o2, o3}) {
    for (uint64_t h : l[0]) DeviceBufferRegistry::Release(h);
  }
  DeviceBufferRegistry::Release(ha);
  DeviceBufferRegistry::Release(hb);
  printf("  native compile/execute ok (add, reduce, all_reduce)\n");
}

// The PS embedding fast path compiled on-device: gather rows by ids, then
// scatter-subtract a scaled gradient update (SGD step) — the executables
// brt_device_* serves to the Python PS tier.
void test_gather_scatter(PjrtClient* client) {
  std::string err;
  const size_t rows = 8, dim = 4, k = 3;
  auto gather = PjrtExecutable::Compile(
      client, MlirGatherRowsF32(rows, dim, k), 1, &err);
  assert(gather != nullptr);
  auto scatter = PjrtExecutable::Compile(
      client, MlirScatterSubF32(rows, dim, k), 1, &err);
  assert(scatter != nullptr);

  float table[rows][dim];
  for (size_t r = 0; r < rows; ++r) {
    for (size_t d = 0; d < dim; ++d) table[r][d] = float(r * 10 + d);
  }
  int32_t ids[k] = {6, 0, 3};
  float grads[k][dim];
  for (size_t i = 0; i < k; ++i) {
    for (size_t d = 0; d < dim; ++d) grads[i][d] = 1.0f;
  }
  float lr = 0.5f;

  IOBuf tb, ib, gb, lb;
  tb.append(table, sizeof(table));
  ib.append(ids, sizeof(ids));
  gb.append(grads, sizeof(grads));
  lb.append(&lr, sizeof(lr));
  uint64_t ht = client->StageToDeviceShaped(
      tb, 0, PjrtClient::DType::kF32, {int64_t(rows), int64_t(dim)}, &err);
  uint64_t hi = client->StageToDeviceShaped(ib, 0, PjrtClient::DType::kS32,
                                            {int64_t(k)}, &err);
  uint64_t hg = client->StageToDeviceShaped(
      gb, 0, PjrtClient::DType::kF32, {int64_t(k), int64_t(dim)}, &err);
  uint64_t hl = client->StageToDeviceShaped(lb, 0, PjrtClient::DType::kF32,
                                            {}, &err);
  assert(ht && hi && hg && hl);

  std::vector<std::vector<uint64_t>> outs;
  assert(gather->Execute({{ht, hi}}, &outs, &err) == 0);
  IOBuf rowsbuf;
  assert(client->StageFromDevice(outs[0][0], &rowsbuf, &err) == 0);
  float got[k][dim];
  rowsbuf.copy_to(got, sizeof(got));
  for (size_t i = 0; i < k; ++i) {
    for (size_t d = 0; d < dim; ++d) {
      assert(got[i][d] == table[size_t(ids[i])][d]);
    }
  }
  DeviceBufferRegistry::Release(outs[0][0]);

  // SGD step: updated table stays resident; gather again to verify.
  std::vector<std::vector<uint64_t>> upd;
  assert(scatter->Execute({{ht, hi, hg, hl}}, &upd, &err) == 0);
  std::vector<std::vector<uint64_t>> outs2;
  assert(gather->Execute({{upd[0][0], hi}}, &outs2, &err) == 0);
  IOBuf after;
  assert(client->StageFromDevice(outs2[0][0], &after, &err) == 0);
  float got2[k][dim];
  after.copy_to(got2, sizeof(got2));
  for (size_t i = 0; i < k; ++i) {
    for (size_t d = 0; d < dim; ++d) {
      assert(got2[i][d] == table[size_t(ids[i])][d] - 0.5f);
    }
  }
  for (uint64_t h : {ht, hi, hg, hl, upd[0][0], outs2[0][0]}) {
    DeviceBufferRegistry::Release(h);
  }
  printf("  gather/scatter (PS embedding ops) ok\n");
}

// A one-replica executable is bound to the device it was compiled for:
// arguments staged elsewhere are refused, and results land (and are
// registered) on the bound device.
void test_device_binding(PjrtClient* client) {
  std::string err;
  const size_t rows = 8, dim = 4, k = 2;
  auto gather = PjrtExecutable::Compile(
      client, MlirGatherRowsF32(rows, dim, k), 1, &err, /*first_device=*/1);
  assert(gather != nullptr);
  std::vector<float> table(rows * dim);
  for (size_t i = 0; i < table.size(); ++i) table[i] = float(i);
  int32_t ids[k] = {5, 2};
  IOBuf tb, ib;
  tb.append(table.data(), table.size() * 4);
  ib.append(ids, sizeof(ids));
  uint64_t ht[2], hi[2];
  for (int d = 0; d < 2; ++d) {
    ht[d] = client->StageToDeviceShaped(
        tb, d, PjrtClient::DType::kF32, {int64_t(rows), int64_t(dim)}, &err);
    hi[d] = client->StageToDeviceShaped(ib, d, PjrtClient::DType::kS32,
                                        {int64_t(k)}, &err);
    assert(ht[d] && hi[d]);
    assert(client->DeviceIndexOf(DeviceBufferRegistry::Lookup(ht[d])) == d);
  }
  std::vector<std::vector<uint64_t>> outs;
  assert(gather->Execute({{ht[0], hi[0]}}, &outs, &err) != 0);
  assert(gather->Execute({{ht[1], hi[1]}}, &outs, &err) == 0);
  int out_dev = -1;
  assert(DeviceBufferRegistry::Info(outs[0][0], &out_dev, nullptr));
  assert(out_dev == 1);
  IOBuf got;
  assert(client->StageFromDevice(outs[0][0], &got, &err) == 0);
  float r[k][dim];
  got.copy_to(r, sizeof(r));
  for (size_t i = 0; i < k; ++i) {
    for (size_t d = 0; d < dim; ++d) {
      assert(r[i][d] == table[size_t(ids[i]) * dim + d]);
    }
  }
  for (uint64_t h : {ht[0], ht[1], hi[0], hi[1], outs[0][0]}) {
    DeviceBufferRegistry::Release(h);
  }
  printf("  executable bound to device 1 ok\n");
}

// 0 = client init, 1 = tests running, 2 = done.
std::atomic<int> g_watchdog_phase{0};

// Without a chip libtpu retries inside PJRT_Client_Create for minutes
// instead of failing; the watchdog turns a hang in either phase into a
// loud timeout (exit 124), so a plain `for t in test_*; do ./$t; done`
// always completes unattended.
void StartWatchdog() {
  std::thread([] {
    for (int i = 0; i < 60 && g_watchdog_phase.load() == 0; ++i) sleep(1);
    if (g_watchdog_phase.load() == 0) {
      fprintf(stderr, "TIMEOUT: PJRT client init exceeded 60s\n");
      fflush(nullptr);
      _exit(124);
    }
    for (int i = 0; i < 300 && g_watchdog_phase.load() == 1; ++i) sleep(1);
    if (g_watchdog_phase.load() == 1) {
      fprintf(stderr, "TIMEOUT: device tests exceeded 300s deadline\n");
      fflush(nullptr);
      _exit(124);
    }
  }).detach();
}

}  // namespace

int main() {
  fiber_init(4);
  StartWatchdog();
  std::string err;
  PjrtClient::Options opts;
  const char* named = getenv("BRT_PJRT_PLUGIN");
  opts.plugin_path = named ? named : "./libbrt_fake_pjrt.so";
  auto client = PjrtClient::Create(opts, &err);
  if (client == nullptr) {
    fprintf(stderr, "FAIL: PJRT plugin %s did not come up: %s\n",
            opts.plugin_path.c_str(), err.c_str());
    return 1;
  }
  g_watchdog_phase.store(1);
  printf("platform=%s kind=%s devices=%d api_minor=%d\n",
         client->platform_name().c_str(), client->device_kind(0).c_str(),
         client->addressable_device_count(),
         client->api()->api_minor_version());
  assert(client->addressable_device_count() >= 1);

  test_block_pool_unit();
  test_roundtrip(client.get());
  test_block_pool_staging(client.get());
  test_handle_registry(client.get());
  test_fiber_event_wait(client.get());
  test_device_echo_rpc(client.get());
  test_compile_execute(client.get());
  test_gather_scatter(client.get());
  if (client->addressable_device_count() >= 2) {
    test_device_binding(client.get());
  }
  g_watchdog_phase.store(2);
  printf("ALL device tests OK\n");
  return 0;
}
