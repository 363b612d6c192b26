// Native CollectiveChannel tests: the ParallelChannel contract over the
// compiled device fabric (fast path) and the RPC fallback tier.
//
// Multi-replica launches use the in-process fake PJRT plugin
// (device/fake_pjrt_plugin.cc — N virtual host devices), the native
// sibling of the Python tier's virtual 8-device CPU mesh. test_device.cc
// covers the same executable tier against the real chip.
#include <cassert>
#include <cstdio>
#include <cstring>
#include <string>
#include <vector>

#include "cluster/collective_channel.h"
#include "rpc/channel.h"
#include "rpc/server.h"

using namespace brt;

namespace {

std::string FakePluginPath() {
  // Next to the test binary (cpp/build).
  return "./libbrt_fake_pjrt.so";
}

std::unique_ptr<PjrtClient> FakeClient(int num_devices) {
  PjrtClient::Options o;
  o.plugin_path = FakePluginPath();
  o.create_options.push_back(
      PjrtClient::Option::Int("num_devices", num_devices));
  std::string err;
  auto c = PjrtClient::Create(o, &err);
  if (c == nullptr) {
    fprintf(stderr, "fake plugin unavailable: %s\n", err.c_str());
  }
  return c;
}

IOBuf F32Buf(const std::vector<float>& v) {
  IOBuf b;
  b.append(v.data(), v.size() * 4);
  return b;
}

std::vector<float> ToF32(const IOBuf& b) {
  std::vector<float> v(b.size() / 4);
  b.copy_to(v.data(), b.size());
  return v;
}

// A collective member: replies to AllReduce/AllGather with its own local
// contribution (what a remote host would contribute at the DCN tier).
class MemberService : public Service {
 public:
  explicit MemberService(std::vector<float> local)
      : local_(std::move(local)) {}
  void CallMethod(const std::string& method, Controller* cntl,
                  const IOBuf& request, IOBuf* response,
                  Closure done) override {
    if (method == "AllReduce" || method == "AllGather") {
      // The fan-out delivers this member's input slice; a real member
      // would combine it with local state — here contribution = slice
      // (empty slice → local state), keeping the data flow visible.
      if (!request.empty()) {
        *response = request;
      } else {
        response->append(local_.data(), local_.size() * 4);
      }
    } else {
      cntl->SetFailed(ENOMETHOD, "no such method");
    }
    done();
  }

 private:
  std::vector<float> local_;
};

void test_device_allreduce() {
  auto client = FakeClient(8);
  assert(client != nullptr);
  CollectiveChannelOptions opts;
  opts.device_client = client.get();
  CollectiveChannel cc(opts);
  // 8 members, member d contributes vector of (d+1)s → sum 36 everywhere.
  std::vector<IOBuf> inputs;
  for (int d = 0; d < 8; ++d) {
    inputs.push_back(F32Buf(std::vector<float>(64, float(d + 1))));
  }
  IOBuf out;
  std::string err;
  assert(cc.AllReduceSum(inputs, &out, &err) == 0);
  assert(cc.last_used_device());
  auto v = ToF32(out);
  assert(v.size() == 64);
  for (float x : v) assert(x == 36.0f);
  // The device-path result hands its HBM handle to the caller.
  assert(out.user_meta_at(0) != 0);
  assert(DeviceBufferRegistry::Release(out.user_meta_at(0)));
  printf("device allreduce (8 fake replicas) OK\n");
}

void test_device_allgather() {
  auto client = FakeClient(4);
  assert(client != nullptr);
  CollectiveChannelOptions opts;
  opts.device_client = client.get();
  CollectiveChannel cc(opts);
  std::vector<IOBuf> inputs;
  for (int d = 0; d < 4; ++d) {
    inputs.push_back(F32Buf({float(d), float(d) + 0.5f}));
  }
  IOBuf out;
  std::string err;
  assert(cc.AllGather(inputs, &out, &err) == 0);
  assert(cc.last_used_device());
  auto v = ToF32(out);
  assert(v.size() == 8);
  for (int d = 0; d < 4; ++d) {
    assert(v[size_t(d) * 2] == float(d));
    assert(v[size_t(d) * 2 + 1] == float(d) + 0.5f);
  }
  DeviceBufferRegistry::Release(out.user_meta_at(0));
  printf("device allgather OK\n");
}

void test_ship_the_handle_input() {
  // A member input that is already device-resident (user-data block whose
  // meta is a live handle) is consumed in place — no restaging.
  auto client = FakeClient(2);
  assert(client != nullptr);
  std::string err;
  // Stage member 0's contribution up front and fetch it back: the fetched
  // IOBuf is a single user-data block with meta = the resident handle.
  uint64_t h = client->StageToDeviceShaped(
      F32Buf({10.f, 20.f}), 0, PjrtClient::DType::kF32, {2}, &err);
  assert(h != 0);
  IOBuf resident;
  assert(client->StageFromDevice(h, &resident, &err) == 0);
  assert(resident.user_meta_at(0) == h);

  CollectiveChannelOptions opts;
  opts.device_client = client.get();
  CollectiveChannel cc(opts);
  std::vector<IOBuf> inputs;
  inputs.push_back(resident);          // rides the handle
  inputs.push_back(F32Buf({1.f, 2.f}));  // staged fresh
  IOBuf out;
  assert(cc.AllReduceSum(inputs, &out, &err) == 0);
  auto v = ToF32(out);
  assert(v.size() == 2 && v[0] == 11.f && v[1] == 22.f);
  // The shipped handle must still be alive (the channel must not release
  // buffers it does not own).
  assert(DeviceBufferRegistry::Lookup(h) != nullptr);
  assert(DeviceBufferRegistry::Release(h));
  // The result itself is resident (handle in meta, on device 0) — feed it
  // straight back as member 0 of the next collective, zero-copy.
  uint64_t result_h = out.user_meta_at(0);
  assert(result_h != 0 && DeviceBufferRegistry::Lookup(result_h) != nullptr);
  std::vector<IOBuf> round2;
  round2.push_back(out);
  round2.push_back(F32Buf({1.f, 2.f}));
  IOBuf out2;
  assert(cc.AllReduceSum(round2, &out2, &err) == 0);
  auto v2 = ToF32(out2);
  assert(v2.size() == 2 && v2[0] == 12.f && v2[1] == 24.f);
  assert(DeviceBufferRegistry::Release(result_h));
  DeviceBufferRegistry::Release(out2.user_meta_at(0));
  printf("ship-the-handle input OK\n");
}

struct RpcFixture {
  std::vector<std::unique_ptr<Server>> servers;
  std::vector<std::unique_ptr<MemberService>> services;
  std::vector<std::unique_ptr<Channel>> channels;

  explicit RpcFixture(int n) {
    for (int i = 0; i < n; ++i) {
      services.push_back(std::make_unique<MemberService>(
          std::vector<float>{float(i), float(i)}));
      servers.push_back(std::make_unique<Server>());
      assert(servers.back()->AddService(services.back().get(),
                                        "Collective") == 0);
      assert(servers.back()->Start("127.0.0.1:0") == 0);
      char addr[64];
      snprintf(addr, sizeof(addr), "127.0.0.1:%d",
               servers.back()->listen_address().port);
      channels.push_back(std::make_unique<Channel>());
      assert(channels.back()->Init(addr) == 0);
    }
  }
  ~RpcFixture() {
    for (auto& s : servers) {
      s->Stop();
      s->Join();
    }
  }
};

void test_rpc_fallback() {
  // No device client at all: the same call rides the RPC ParallelChannel.
  RpcFixture fx(3);
  CollectiveChannel cc;  // no device fabric
  for (auto& ch : fx.channels) cc.AddChannel(ch.get());
  std::vector<IOBuf> inputs;
  for (int i = 0; i < 3; ++i) {
    inputs.push_back(F32Buf({float(i + 1), float(i + 1)}));
  }
  IOBuf out;
  std::string err;
  assert(cc.AllReduceSum(inputs, &out, &err) == 0);
  assert(!cc.last_used_device());
  auto v = ToF32(out);
  assert(v.size() == 2 && v[0] == 6.f && v[1] == 6.f);

  IOBuf cat;
  assert(cc.AllGather(inputs, &cat, &err) == 0);
  auto g = ToF32(cat);
  assert(g.size() == 6 && g[0] == 1.f && g[2] == 2.f && g[4] == 3.f);
  printf("rpc fallback (allreduce+allgather) OK\n");
}

void test_device_failure_falls_back() {
  // Device tier armed but too small (2 devices, 3 members) → RPC tier.
  auto client = FakeClient(2);
  assert(client != nullptr);
  RpcFixture fx(3);
  CollectiveChannelOptions opts;
  opts.device_client = client.get();
  CollectiveChannel cc(opts);
  for (auto& ch : fx.channels) cc.AddChannel(ch.get());
  std::vector<IOBuf> inputs;
  for (int i = 0; i < 3; ++i) inputs.push_back(F32Buf({1.f}));
  IOBuf out;
  std::string err;
  assert(cc.AllReduceSum(inputs, &out, &err) == 0);
  assert(!cc.last_used_device());
  assert(ToF32(out)[0] == 3.f);
  printf("undersized device tier falls back to RPC OK\n");
}

void test_fail_limit_on_rpc_tier() {
  // Partial failure only exists on the RPC tier: one member down.
  RpcFixture fx(3);
  fx.servers[1]->Stop();
  fx.servers[1]->Join();

  std::vector<IOBuf> inputs;
  for (int i = 0; i < 3; ++i) inputs.push_back(F32Buf({2.f}));

  {
    CollectiveChannelOptions opts;
    opts.fail_limit = 1;  // tolerate one dead member
    opts.timeout_ms = 500;
    CollectiveChannel cc(opts);
    for (auto& ch : fx.channels) cc.AddChannel(ch.get());
    IOBuf out;
    std::string err;
    assert(cc.AllReduceSum(inputs, &out, &err) == 0);
    assert(ToF32(out)[0] == 4.f);  // survivors' sum
  }
  {
    CollectiveChannelOptions opts;  // fail_limit -1: any failure fatal
    opts.timeout_ms = 500;
    CollectiveChannel cc(opts);
    for (auto& ch : fx.channels) cc.AddChannel(ch.get());
    IOBuf out;
    std::string err;
    assert(cc.AllReduceSum(inputs, &out, &err) != 0);
  }
  printf("fail_limit semantics on RPC tier OK\n");
}

}  // namespace

void test_nonrowmajor_landing_repacked() {
  // $BRT_FAKE_COLMAJOR makes the fake store rank-2 buffers column-major
  // and report minor_to_major={0,1} — how libtpu lands narrow rank-2
  // arrays such as (16,8) f32.
  // StageFromDevice must hand back dense ROW-major bytes regardless
  // (pjrt_device.cc RepackDeviceLayout).
  setenv("BRT_FAKE_COLMAJOR", "1", 1);
  auto client = FakeClient(1);
  assert(client != nullptr);
  std::string err;
  std::vector<float> rowmajor(4 * 6);
  for (size_t i = 0; i < rowmajor.size(); ++i) rowmajor[i] = float(i);
  uint64_t h = client->StageToDeviceShaped(
      F32Buf(rowmajor), 0, PjrtClient::DType::kF32, {4, 6}, &err);
  assert(h != 0);
  IOBuf back;
  assert(client->StageFromDevice(h, &back, &err) == 0);
  auto v = ToF32(back);
  assert(v.size() == rowmajor.size());
  for (size_t i = 0; i < v.size(); ++i) assert(v[i] == rowmajor[i]);
  DeviceBufferRegistry::Release(h);
  unsetenv("BRT_FAKE_COLMAJOR");
  printf("non-row-major landing repack OK\n");
}

int main() {
  test_device_allreduce();
  test_device_allgather();
  test_ship_the_handle_input();
  test_nonrowmajor_landing_repacked();
  test_rpc_fallback();
  test_device_failure_falls_back();
  test_fail_limit_on_rpc_tier();
  printf("ALL collective tests OK\n");
  return 0;
}
