#include "device/pjrt_device.h"

#include "device/block_pool.h"

#include <dlfcn.h>
#include <glob.h>
#include <unistd.h>

#include <atomic>
#include <condition_variable>
#include <cstring>
#include <mutex>
#include <unordered_map>

#include "base/logging.h"
#include "base/time.h"
#include "device/pjrt_args.h"
#include "fiber/butex.h"
#include "third_party/pjrt/pjrt_c_api.h"

namespace brt {

// ---------------------------------------------------------------------------
// PjrtApi
// ---------------------------------------------------------------------------

std::string DefaultPjrtPluginPath() {
  if (const char* env = getenv("BRT_PJRT_PLUGIN")) return env;
  // libtpu ships as a Python wheel: look in the site-packages of the first
  // python3 on $PATH (the interpreter JAX runs under), so both halves of
  // the fabric load the same runtime.
  const char* path_env = getenv("PATH");
  const std::string path = path_env ? path_env : "";
  for (size_t pos = 0; pos <= path.size();) {
    size_t end = path.find(':', pos);
    if (end == std::string::npos) end = path.size();
    const std::string dir = path.substr(pos, end - pos);
    pos = end + 1;
    if (dir.empty() || access((dir + "/python3").c_str(), X_OK) != 0) continue;
    const std::string pattern =
        dir + "/../lib/python3*/site-packages/libtpu/libtpu.so";
    glob_t g;
    std::string found;
    if (glob(pattern.c_str(), 0, nullptr, &g) == 0 && g.gl_pathc > 0) {
      found = g.gl_pathv[0];
    }
    globfree(&g);
    return found;
  }
  return "";
}

const PjrtApi* PjrtApi::Load(const std::string& plugin_path,
                             std::string* error) {
  static std::mutex mu;
  static auto* cache = new std::unordered_map<std::string, PjrtApi*>();
  std::lock_guard<std::mutex> g(mu);
  auto it = cache->find(plugin_path);
  if (it != cache->end()) return it->second;

  void* handle = dlopen(plugin_path.c_str(), RTLD_NOW | RTLD_LOCAL);
  if (handle == nullptr) {
    if (error) *error = std::string("dlopen failed: ") + dlerror();
    return nullptr;
  }
  using GetPjrtApiFn = const PJRT_Api* (*)();
  auto get_api =
      reinterpret_cast<GetPjrtApiFn>(dlsym(handle, "GetPjrtApi"));
  if (get_api == nullptr) {
    if (error) *error = "plugin has no GetPjrtApi symbol";
    return nullptr;
  }
  const PJRT_Api* raw = get_api();
  if (raw == nullptr) {
    if (error) *error = "GetPjrtApi returned null";
    return nullptr;
  }
  auto* api = new PjrtApi();
  api->api_ = raw;
  // One-time plugin init (idempotent per plugin).
  auto args = BRT_PJRT_ARGS(PJRT_Plugin_Initialize_Args);
  if (PJRT_Error* err = raw->PJRT_Plugin_Initialize(&args)) {
    if (error) *error = "PJRT_Plugin_Initialize: " + api->ConsumeError(err);
    delete api;
    return nullptr;
  }
  (*cache)[plugin_path] = api;
  return api;
}

int PjrtApi::api_minor_version() const {
  return api_->pjrt_api_version.minor_version;
}

std::string PjrtApi::ConsumeError(void* pjrt_error) const {
  auto* err = static_cast<PJRT_Error*>(pjrt_error);
  if (err == nullptr) return "";
  auto margs = BRT_PJRT_ARGS(PJRT_Error_Message_Args);
  margs.error = err;
  api_->PJRT_Error_Message(&margs);
  std::string msg(margs.message, margs.message_size);
  auto dargs = BRT_PJRT_ARGS(PJRT_Error_Destroy_Args);
  dargs.error = err;
  api_->PJRT_Error_Destroy(&dargs);
  return msg;
}

// ---------------------------------------------------------------------------
// PjrtEvent: fiber parks on a device event (the bthread_fd_wait analog).
// ---------------------------------------------------------------------------

namespace {

// Shared between the waiting fiber and the plugin's completion callback;
// refcounted so neither side frees the butex while the other still touches
// it (the callback may be inside butex_wake_all when the waiter resumes).
struct EventWaitCtx {
  Butex* butex = butex_create();
  std::atomic<int> rc{0};
  std::atomic<int> refs{2};
  const PjrtApi* api = nullptr;

  void Unref() {
    if (refs.fetch_sub(1, std::memory_order_acq_rel) == 1) {
      butex_destroy(butex);
      delete this;
    }
  }
};

}  // namespace

PjrtEvent::~PjrtEvent() {
  if (ev_ != nullptr) {
    auto args = BRT_PJRT_ARGS(PJRT_Event_Destroy_Args);
    args.event = ev_;
    api_->raw()->PJRT_Event_Destroy(&args);
  }
}

int PjrtEvent::FiberWait() {
  if (ev_ == nullptr) return EINVAL;
  const PJRT_Api* raw = api_->raw();
  auto* ctx = new EventWaitCtx;
  ctx->api = api_;
  const int expected =
      butex_value(ctx->butex).load(std::memory_order_acquire);

  auto args = BRT_PJRT_ARGS(PJRT_Event_OnReady_Args);
  args.event = ev_;
  args.user_arg = ctx;
  args.callback = [](PJRT_Error* err, void* user_arg) {
    auto* c = static_cast<EventWaitCtx*>(user_arg);
    if (err != nullptr) {
      // The callback owns `err`; ConsumeError destroys it.
      BRT_LOG(ERROR) << "PJRT event error: " << c->api->ConsumeError(err);
      c->rc.store(EIO, std::memory_order_release);
    }
    butex_value(c->butex).fetch_add(1, std::memory_order_release);
    butex_wake_all(c->butex);
    c->Unref();
  };
  if (PJRT_Error* err = raw->PJRT_Event_OnReady(&args)) {
    std::string msg = api_->ConsumeError(err);
    BRT_LOG(ERROR) << "PJRT_Event_OnReady failed: " << msg;
    ctx->Unref();  // callback will never run
    ctx->Unref();
    return EIO;
  }
  // Park THIS FIBER until the plugin's completion thread bumps the butex.
  // If the event completed before registration, the value already moved and
  // butex_wait returns immediately.
  while (butex_value(ctx->butex).load(std::memory_order_acquire) ==
         expected) {
    butex_wait(ctx->butex, expected, -1);
  }
  const int rc = ctx->rc.load(std::memory_order_acquire);
  ctx->Unref();
  return rc;
}

namespace {

// Shared by ThreadWait and the plugin callback; same two-ref protocol as
// EventWaitCtx but on a plain mutex/condvar (no fiber runtime involved).
struct ThreadWaitCtx {
  std::mutex mu;
  std::condition_variable cv;
  bool done = false;
  int rc = 0;
  const PjrtApi* api = nullptr;
  std::atomic<int> refs{2};

  void Unref() {
    if (refs.fetch_sub(1, std::memory_order_acq_rel) == 1) delete this;
  }
};

}  // namespace

int PjrtEvent::ThreadWait() {
  if (ev_ == nullptr) return EINVAL;
  auto* ctx = new ThreadWaitCtx;
  ctx->api = api_;
  auto args = BRT_PJRT_ARGS(PJRT_Event_OnReady_Args);
  args.event = ev_;
  args.user_arg = ctx;
  args.callback = [](PJRT_Error* err, void* user_arg) {
    auto* c = static_cast<ThreadWaitCtx*>(user_arg);
    {
      std::lock_guard<std::mutex> g(c->mu);
      if (err != nullptr) {
        BRT_LOG(ERROR) << "PJRT event error: " << c->api->ConsumeError(err);
        c->rc = EIO;
      }
      c->done = true;
    }
    c->cv.notify_all();
    c->Unref();
  };
  if (PJRT_Error* err = api_->raw()->PJRT_Event_OnReady(&args)) {
    BRT_LOG(ERROR) << "PJRT_Event_OnReady failed: "
                   << api_->ConsumeError(err);
    ctx->Unref();  // callback will never run
    ctx->Unref();
    return EIO;
  }
  int rc;
  {
    std::unique_lock<std::mutex> lk(ctx->mu);
    ctx->cv.wait(lk, [&] { return ctx->done; });
    rc = ctx->rc;
  }
  ctx->Unref();
  return rc;
}

// ---------------------------------------------------------------------------
// DeviceBufferRegistry: 64-bit handles for live HBM buffers (lkey analog).
// ---------------------------------------------------------------------------

namespace {

struct RegisteredBuffer {
  const PjrtApi* api;
  PJRT_Buffer* buf;
  int refs;   // 1 registry ref (until Release) + one per outstanding Pin
  bool dead;  // Release() called; Lookup/Pin fail from then on
  int device = -1;  // placement metadata (see Register)
  int dtype = -1;
};

std::mutex g_reg_mu;
std::unordered_map<uint64_t, RegisteredBuffer>& registry() {
  static auto* m = new std::unordered_map<uint64_t, RegisteredBuffer>();
  return *m;
}
std::atomic<uint64_t> g_next_handle{1};

void DestroyPjrtBuffer(const PjrtApi* api, PJRT_Buffer* buf) {
  auto args = BRT_PJRT_ARGS(PJRT_Buffer_Destroy_Args);
  args.buffer = buf;
  if (PJRT_Error* err = api->raw()->PJRT_Buffer_Destroy(&args)) {
    BRT_LOG(ERROR) << "PJRT_Buffer_Destroy: " << api->ConsumeError(err);
  }
}

}  // namespace

uint64_t DeviceBufferRegistry::Register(const PjrtApi* api,
                                        PJRT_Buffer* buf, int device_index,
                                        int dtype) {
  const uint64_t h = g_next_handle.fetch_add(1, std::memory_order_relaxed);
  std::lock_guard<std::mutex> g(g_reg_mu);
  registry()[h] = RegisteredBuffer{api,   buf,          /*refs=*/1,
                                   false, device_index, dtype};
  return h;
}

bool DeviceBufferRegistry::Info(uint64_t handle, int* device_index,
                                int* dtype) {
  std::lock_guard<std::mutex> g(g_reg_mu);
  auto it = registry().find(handle);
  if (it == registry().end() || it->second.dead) return false;
  if (device_index != nullptr) *device_index = it->second.device;
  if (dtype != nullptr) *dtype = it->second.dtype;
  return true;
}

PJRT_Buffer* DeviceBufferRegistry::Lookup(uint64_t handle) {
  std::lock_guard<std::mutex> g(g_reg_mu);
  auto it = registry().find(handle);
  if (it == registry().end() || it->second.dead) return nullptr;
  return it->second.buf;
}

PJRT_Buffer* DeviceBufferRegistry::Pin(uint64_t handle) {
  std::lock_guard<std::mutex> g(g_reg_mu);
  auto it = registry().find(handle);
  if (it == registry().end() || it->second.dead) return nullptr;
  ++it->second.refs;
  return it->second.buf;
}

void DeviceBufferRegistry::Unpin(uint64_t handle) {
  RegisteredBuffer rb;
  {
    std::lock_guard<std::mutex> g(g_reg_mu);
    auto it = registry().find(handle);
    if (it == registry().end()) return;
    if (--it->second.refs > 0) return;
    if (!it->second.dead) {
      // Unbalanced Unpin on a live handle: the registry's own ref was never
      // dropped by Release, so destroying here would be a use-after-free for
      // other holders. Restore the ref and flag the bug.
      it->second.refs = 1;
      BRT_LOG(ERROR) << "unbalanced Unpin on live device handle " << handle;
      return;
    }
    rb = it->second;
    registry().erase(it);
  }
  DestroyPjrtBuffer(rb.api, rb.buf);
}

bool DeviceBufferRegistry::Release(uint64_t handle) {
  RegisteredBuffer rb;
  {
    std::lock_guard<std::mutex> g(g_reg_mu);
    auto it = registry().find(handle);
    if (it == registry().end() || it->second.dead) return false;
    it->second.dead = true;
    if (--it->second.refs > 0) return true;  // a pinned DMA finishes it
    rb = it->second;
    registry().erase(it);
  }
  DestroyPjrtBuffer(rb.api, rb.buf);
  return true;
}

// ---------------------------------------------------------------------------
// PjrtClient
// ---------------------------------------------------------------------------

std::unique_ptr<PjrtClient> PjrtClient::Create(const Options& opts,
                                               std::string* error) {
  DeviceBlockPool::ExposeVars();
  std::string path = opts.plugin_path.empty() ? DefaultPjrtPluginPath()
                                              : opts.plugin_path;
  if (path.empty()) {
    if (error) *error =
        "no PJRT plugin found: set BRT_PJRT_PLUGIN, or install libtpu "
        "beside the python3 on PATH";
    return nullptr;
  }
  const PjrtApi* api = PjrtApi::Load(path, error);
  if (api == nullptr) return nullptr;

  std::vector<PJRT_NamedValue> nvs;
  nvs.reserve(opts.create_options.size());
  for (const Option& o : opts.create_options) {
    auto nv = BRT_PJRT_ARGS(PJRT_NamedValue);
    nv.name = o.name.c_str();
    nv.name_size = o.name.size();
    if (o.is_string) {
      nv.type = PJRT_NamedValue_kString;
      nv.string_value = o.str.c_str();
      nv.value_size = o.str.size();
    } else {
      nv.type = PJRT_NamedValue_kInt64;
      nv.int64_value = o.i64;
      nv.value_size = 1;
    }
    nvs.push_back(nv);
  }

  auto cargs = BRT_PJRT_ARGS(PJRT_Client_Create_Args);
  cargs.create_options = nvs.data();
  cargs.num_options = nvs.size();
  if (PJRT_Error* err = api->raw()->PJRT_Client_Create(&cargs)) {
    if (error) *error = "PJRT_Client_Create: " + api->ConsumeError(err);
    return nullptr;
  }
  std::unique_ptr<PjrtClient> c(new PjrtClient());
  c->api_ = api;
  c->client_ = cargs.client;

  auto dargs = BRT_PJRT_ARGS(PJRT_Client_AddressableDevices_Args);
  dargs.client = c->client_;
  if (PJRT_Error* err = api->raw()->PJRT_Client_AddressableDevices(&dargs)) {
    if (error) *error =
        "PJRT_Client_AddressableDevices: " + api->ConsumeError(err);
    return nullptr;
  }
  c->addressable_.assign(dargs.addressable_devices,
                         dargs.addressable_devices +
                             dargs.num_addressable_devices);
  return c;
}

PjrtClient::~PjrtClient() {
  if (client_ != nullptr) {
    auto args = BRT_PJRT_ARGS(PJRT_Client_Destroy_Args);
    args.client = client_;
    if (PJRT_Error* err = api_->raw()->PJRT_Client_Destroy(&args)) {
      BRT_LOG(ERROR) << "PJRT_Client_Destroy: " << api_->ConsumeError(err);
    }
  }
}

std::string PjrtClient::platform_name() const {
  auto args = BRT_PJRT_ARGS(PJRT_Client_PlatformName_Args);
  args.client = client_;
  if (PJRT_Error* err = api_->raw()->PJRT_Client_PlatformName(&args)) {
    api_->ConsumeError(err);
    return "";
  }
  return std::string(args.platform_name, args.platform_name_size);
}

int PjrtClient::addressable_device_count() const {
  return int(addressable_.size());
}

PJRT_Device* PjrtClient::addressable_device(int i) const {
  return addressable_[size_t(i)];
}

namespace {

PJRT_DeviceDescription* DescriptionOf(const PjrtApi* api, PJRT_Device* dev) {
  auto args = BRT_PJRT_ARGS(PJRT_Device_GetDescription_Args);
  args.device = dev;
  if (PJRT_Error* err = api->raw()->PJRT_Device_GetDescription(&args)) {
    BRT_LOG(ERROR) << "PJRT_Device_GetDescription: " << api->ConsumeError(err);
    return nullptr;
  }
  return args.device_description;
}

}  // namespace

std::string PjrtClient::device_kind(int i) const {
  PJRT_DeviceDescription* desc = DescriptionOf(api_, addressable_device(i));
  if (desc == nullptr) return "";
  auto args = BRT_PJRT_ARGS(PJRT_DeviceDescription_Kind_Args);
  args.device_description = desc;
  if (PJRT_Error* err = api_->raw()->PJRT_DeviceDescription_Kind(&args)) {
    BRT_LOG(ERROR) << "PJRT_DeviceDescription_Kind: "
                   << api_->ConsumeError(err);
    return "";
  }
  return std::string(args.device_kind, args.device_kind_size);
}

int PjrtClient::device_id(int i) const {
  PJRT_DeviceDescription* desc = DescriptionOf(api_, addressable_device(i));
  if (desc == nullptr) return -1;
  auto args = BRT_PJRT_ARGS(PJRT_DeviceDescription_Id_Args);
  args.device_description = desc;
  if (PJRT_Error* err = api_->raw()->PJRT_DeviceDescription_Id(&args)) {
    BRT_LOG(ERROR) << "PJRT_DeviceDescription_Id: " << api_->ConsumeError(err);
    return -1;
  }
  return args.id;
}

int PjrtClient::DeviceIndexOf(PJRT_Buffer* buf) const {
  auto args = BRT_PJRT_ARGS(PJRT_Buffer_Device_Args);
  args.buffer = buf;
  if (PJRT_Error* err = api_->raw()->PJRT_Buffer_Device(&args)) {
    BRT_LOG(ERROR) << "PJRT_Buffer_Device: " << api_->ConsumeError(err);
    return -1;
  }
  for (size_t i = 0; i < addressable_.size(); ++i) {
    if (addressable_[i] == args.device) return int(i);
  }
  return -1;
}

// ---------------------------------------------------------------------------
// Staging: zero-copy DMA between IOBuf blocks and HBM.
// ---------------------------------------------------------------------------

namespace {

// Holds a host-side pin (an IOBuf sharing the source blocks) until the
// plugin reports the H2D DMA no longer needs the host memory — the analog
// of keeping sbuf refs until the RDMA send completes
// (reference rdma_endpoint.cpp:774 _sbuf).
struct HostPin {
  IOBuf pinned;
  const PjrtApi* api;
  PJRT_Event* done;
  uint32_t done_slot;  // late stamp (base/time.h) for this moment, or 0
};

void ReleaseHostPin(PJRT_Error* err, void* user_arg) {
  auto* pin = static_cast<HostPin*>(user_arg);
  stamp_late(pin->done_slot);
  if (err != nullptr) {
    BRT_LOG(ERROR) << "H2D done-with-host-buffer event failed: "
                   << pin->api->ConsumeError(err);
  }
  auto dargs = BRT_PJRT_ARGS(PJRT_Event_Destroy_Args);
  dargs.event = pin->done;
  pin->api->raw()->PJRT_Event_Destroy(&dargs);
  delete pin;  // drops the block refs
}

}  // namespace

uint64_t PjrtClient::StageToDevice(const IOBuf& data, int device_index,
                                   std::string* error) {
  return StageToDeviceShaped(data, device_index, DType::kU8,
                             {int64_t(data.size())}, error);
}

uint64_t PjrtClient::StageToDeviceShaped(const IOBuf& data, int device_index,
                                         DType dtype,
                                         const std::vector<int64_t>& dims,
                                         std::string* error,
                                         uint32_t done_slot) {
  if (device_index < 0 || device_index >= addressable_device_count()) {
    if (error) *error = "bad device index";
    return 0;
  }
  size_t elem = dtype == DType::kU8 ? 1 : 4;
  int64_t nelem = 1;
  for (int64_t d : dims) nelem *= d;
  if (size_t(nelem) * elem != data.size()) {
    if (error) *error = "dims do not match payload size";
    return 0;
  }
  // The DMA source must be one contiguous region. Single-block payloads
  // (the common case: a cut attachment) transfer in place; multi-block
  // ones coalesce once into a fresh region.
  IOBuf src = data;  // shares blocks
  const size_t len = src.size();
  const void* base;
  if (src.block_count() == 1) {
    base = src.ref_data(0);
  } else {
    // PJRT's host-buffer API takes one contiguous region (no scatter list
    // like ibverbs sge), so multi-block payloads coalesce once — into a
    // pooled registered block, not a malloc (block_pool.cpp:39 analog).
    size_t cap = 0;
    char* flat = static_cast<char*>(
        DeviceBlockPool::singleton().Acquire(len ? len : 1, &cap));
    if (flat == nullptr) {
      if (error) *error = "out of memory coalescing H2D payload";
      return 0;
    }
    src.copy_to(flat, len);
    IOBuf owned;
    owned.append_user_data(flat, len, DeviceBlockPool::IOBufDeleter,
                           reinterpret_cast<void*>(uintptr_t(cap)));
    src = std::move(owned);
    base = flat;
  }

  auto args = BRT_PJRT_ARGS(PJRT_Client_BufferFromHostBuffer_Args);
  args.client = client_;
  args.data = base;
  switch (dtype) {
    case DType::kU8: args.type = PJRT_Buffer_Type_U8; break;
    case DType::kF32: args.type = PJRT_Buffer_Type_F32; break;
    case DType::kS32: args.type = PJRT_Buffer_Type_S32; break;
  }
  args.dims = dims.data();
  args.num_dims = dims.size();
  args.host_buffer_semantics =
      PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
  args.device = addressable_[size_t(device_index)];
  if (PJRT_Error* err = api_->raw()->PJRT_Client_BufferFromHostBuffer(&args)) {
    if (error) *error = "BufferFromHostBuffer: " + api_->ConsumeError(err);
    return 0;
  }
  // Pin the host blocks until the plugin is done DMA-ing from them.
  if (args.done_with_host_buffer != nullptr) {
    auto* pin = new HostPin{std::move(src), api_,
                            args.done_with_host_buffer, done_slot};
    auto rargs = BRT_PJRT_ARGS(PJRT_Event_OnReady_Args);
    rargs.event = args.done_with_host_buffer;
    rargs.callback = &ReleaseHostPin;
    rargs.user_arg = pin;
    if (PJRT_Error* err = api_->raw()->PJRT_Event_OnReady(&rargs)) {
      BRT_LOG(ERROR) << "OnReady(done_with_host_buffer): "
                     << api_->ConsumeError(err);
      // Conservatively keep the pin (leak) rather than risk a
      // use-after-free DMA; this path indicates a broken plugin.
    }
  } else {
    stamp_late(done_slot);  // the plugin copied before it returned
  }
  return DeviceBufferRegistry::Register(api_, args.buffer, device_index,
                                        int(dtype));
}

char* PjrtClient::RepackDeviceLayout(PJRT_Buffer* buf, char* src, size_t n,
                                     size_t* cap) {
  const PJRT_Api* raw = api_->raw();
  if (raw->PJRT_Buffer_Dimensions == nullptr ||
      raw->PJRT_Buffer_GetMemoryLayout == nullptr) {
    return nullptr;
  }
  auto dargs = BRT_PJRT_ARGS(PJRT_Buffer_Dimensions_Args);
  dargs.buffer = buf;
  if (PJRT_Error* err = raw->PJRT_Buffer_Dimensions(&dargs)) {
    api_->ConsumeError(err);
    return nullptr;
  }
  const size_t rank = dargs.num_dims;
  if (rank < 2 || rank > 16) return nullptr;  // rank<2: layout is trivial
  auto largs = BRT_PJRT_ARGS(PJRT_Buffer_GetMemoryLayout_Args);
  largs.buffer = buf;
  if (PJRT_Error* err = raw->PJRT_Buffer_GetMemoryLayout(&largs)) {
    api_->ConsumeError(err);
    return nullptr;
  }
  if (largs.layout.type != PJRT_Buffer_MemoryLayout_Type_Tiled ||
      largs.layout.tiled.minor_to_major_size != rank) {
    return nullptr;  // strided landings not seen in practice
  }
  const int64_t* mtm = largs.layout.tiled.minor_to_major;
  // Plugin-supplied input: must be a permutation of [0, rank) before it
  // can index the stride array below.
  bool seen[16] = {false};
  bool row_major = true;
  for (size_t i = 0; i < rank; ++i) {
    if (mtm[i] < 0 || mtm[i] >= int64_t(rank) || seen[mtm[i]]) {
      return nullptr;  // malformed layout: leave bytes untouched
    }
    seen[mtm[i]] = true;
    if (mtm[i] != int64_t(rank) - 1 - int64_t(i)) row_major = false;
  }
  if (row_major) return nullptr;
  size_t total = 1;
  for (size_t d = 0; d < rank; ++d) total *= size_t(dargs.dims[d]);
  // The landed byte count must be exactly the dense footprint: libtpu
  // untiles on the way out but keeps the permutation (the layout carries
  // tiles, yet total*elem bytes come back — seen on a v5e for (16,8) f32
  // and (5,3) s32, which land column-major, next to (16,256), (32,128)
  // and (7,13), which land row-major). A
  // truly tile-padded landing (n > dense) cannot be fixed by permutation
  // alone. Known limitation: a plugin that lands genuinely
  // tile-INTERLEAVED bytes whose tiles divide the shape exactly would be
  // indistinguishable from a permuted-dense landing; no observed plugin
  // does that (they all materialize the logical array).
  size_t elem = 0;
  if (raw->PJRT_Buffer_ElementType != nullptr) {
    auto eargs = BRT_PJRT_ARGS(PJRT_Buffer_ElementType_Args);
    eargs.buffer = buf;
    if (PJRT_Error* err = raw->PJRT_Buffer_ElementType(&eargs)) {
      api_->ConsumeError(err);
    } else {
      switch (eargs.type) {
        case PJRT_Buffer_Type_PRED:
        case PJRT_Buffer_Type_S8:
        case PJRT_Buffer_Type_U8:
        case PJRT_Buffer_Type_F8E5M2:
        case PJRT_Buffer_Type_F8E4M3FN:
        case PJRT_Buffer_Type_F8E4M3B11FNUZ:
        case PJRT_Buffer_Type_F8E5M2FNUZ:
        case PJRT_Buffer_Type_F8E4M3FNUZ:
        case PJRT_Buffer_Type_F8E4M3:
        case PJRT_Buffer_Type_F8E3M4: elem = 1; break;
        case PJRT_Buffer_Type_S16:
        case PJRT_Buffer_Type_U16:
        case PJRT_Buffer_Type_F16:
        case PJRT_Buffer_Type_BF16: elem = 2; break;
        case PJRT_Buffer_Type_S32:
        case PJRT_Buffer_Type_U32:
        case PJRT_Buffer_Type_F32: elem = 4; break;
        case PJRT_Buffer_Type_S64:
        case PJRT_Buffer_Type_U64:
        case PJRT_Buffer_Type_F64:
        case PJRT_Buffer_Type_C64: elem = 8; break;
        case PJRT_Buffer_Type_C128: elem = 16; break;
        default: elem = 0; break;  // sub-byte (S4/U4) and unknown types
      }
    }
  }
  if (total == 0 || elem == 0 || n != total * elem) {
    // We KNOW the landing is permuted (non-row-major layout above) but
    // cannot repack it — surface that loudly instead of handing the
    // caller silently transposed bytes.
    BRT_LOG(ERROR) << "D2H landing is non-row-major but cannot be "
                      "repacked (elem=" << elem << " total=" << total
                   << " n=" << n << "); returning device-layout bytes";
    return nullptr;
  }
  // Element strides of the landed (device-layout) bytes per logical dim.
  int64_t stride[16];
  int64_t acc = 1;
  for (size_t i = 0; i < rank; ++i) {
    stride[mtm[i]] = acc;
    acc *= dargs.dims[mtm[i]];
  }
  size_t dcap = 0;
  char* dense = static_cast<char*>(
      DeviceBlockPool::singleton().Acquire(n, &dcap));
  if (dense == nullptr) return nullptr;  // keep device-layout bytes
  // Walk logical indices in row-major order, maintaining the source
  // element offset incrementally (+stride on the dim that increments,
  // -(dim-1)*stride on each wrap) — no per-element dot product. When the
  // logical innermost dim is contiguous in the device layout, whole rows
  // copy with one memcpy; otherwise fixed-size stores (constant-size
  // memcpy inlines to a load/store pair).
  int64_t idx[16] = {0};
  const int64_t run = (stride[rank - 1] == 1) ? dargs.dims[rank - 1] : 1;
  int64_t off = 0;
  char* out_p = dense;
  for (size_t i = 0; i < total; i += size_t(run)) {
    const char* in_p = src + size_t(off) * elem;
    if (run > 1) {
      memcpy(out_p, in_p, size_t(run) * elem);
    } else {
      switch (elem) {
        case 1: *out_p = *in_p; break;
        case 2: memcpy(out_p, in_p, 2); break;
        case 4: memcpy(out_p, in_p, 4); break;
        default: memcpy(out_p, in_p, 8); break;
      }
    }
    out_p += size_t(run) * elem;
    for (int d = int(rank) - 1 - (run > 1 ? 1 : 0); d >= 0; --d) {
      if (++idx[d] < dargs.dims[d]) {
        off += stride[d];
        break;
      }
      idx[d] = 0;
      off -= stride[d] * (dargs.dims[d] - 1);
    }
  }
  DeviceBlockPool::singleton().Release(src, *cap);
  *cap = dcap;
  return dense;
}

int PjrtClient::StageFromDevice(uint64_t handle, IOBuf* out,
                                std::string* error, int64_t* stamps_ns) {
  // Pin across the blocking DMA: a concurrent Release of the same handle
  // (the "ship the handle" pattern) must not destroy the buffer mid-read.
  PJRT_Buffer* buf = DeviceBufferRegistry::Pin(handle);
  if (buf == nullptr) {
    if (error) *error = "stale device buffer handle";
    return EINVAL;
  }
  auto unpin = [handle] { DeviceBufferRegistry::Unpin(handle); };
  auto szargs = BRT_PJRT_ARGS(PJRT_Buffer_ToHostBuffer_Args);
  szargs.src = buf;
  if (PJRT_Error* err = api_->raw()->PJRT_Buffer_ToHostBuffer(&szargs)) {
    if (error) *error = "ToHostBuffer(size query): " + api_->ConsumeError(err);
    unpin();
    return EIO;
  }
  const size_t n = szargs.dst_size;
  // D2H lands directly in a pooled registered block that the caller's
  // IOBuf will reference — no bounce buffer, no malloc (reference
  // recv-side zero copy, docs/en/rdma.md:38 + block_pool.cpp:39).
  size_t cap = 0;
  char* dst = static_cast<char*>(
      DeviceBlockPool::singleton().Acquire(n ? n : 1, &cap));
  if (dst == nullptr) {
    if (error) *error = "out of memory for D2H landing buffer";
    unpin();
    return ENOMEM;
  }
  auto args = BRT_PJRT_ARGS(PJRT_Buffer_ToHostBuffer_Args);
  args.src = buf;
  args.dst = dst;
  args.dst_size = n;
  if (PJRT_Error* err = api_->raw()->PJRT_Buffer_ToHostBuffer(&args)) {
    if (error) *error = "ToHostBuffer: " + api_->ConsumeError(err);
    DeviceBlockPool::singleton().Release(dst, cap);
    unpin();
    return EIO;
  }
  int rc = 0;
  if (args.event != nullptr) {  // no event => plugin copied synchronously
    PjrtEvent ev(api_, args.event);
    rc = ev.Wait(thread_wait_);  // parks fiber (or blocks thread)
  }
  if (rc != 0) {
    unpin();
    if (error) *error = "D2H event failed";
    DeviceBlockPool::singleton().Release(dst, cap);
    return rc;
  }
  // With host_layout unset the plugin copies in the buffer's ON-DEVICE
  // layout (PJRT_Buffer_ToHostBuffer_Args contract), and libtpu does not
  // keep every array row-major: on a v5e narrow rank-2 arrays such as
  // (16,8) f32 or (5,3) s32 land column-major, and (3,5,7) f32 in yet
  // another permutation, while the PS tier's wide tables land row-major.
  // Un-permute host-side into dense row-major so callers always see
  // numpy-compatible bytes.
  if (stamps_ns != nullptr) stamps_ns[0] = monotonic_ns();
  char* repacked = RepackDeviceLayout(buf, dst, n, &cap);
  if (stamps_ns != nullptr) {
    stamps_ns[1] = monotonic_ns();
    stamps_ns[2] = repacked != nullptr ? int64_t(n) : 0;
  }
  unpin();
  if (repacked != nullptr) dst = repacked;
  out->append_user_data(dst, n, DeviceBlockPool::IOBufDeleter,
                        reinterpret_cast<void*>(uintptr_t(cap)),
                        /*meta=*/handle);
  return 0;
}

int PjrtClient::Roundtrip(const IOBuf& in, IOBuf* out, int device_index,
                          std::string* error) {
  uint64_t h = StageToDevice(in, device_index, error);
  if (h == 0) return EIO;
  int rc = StageFromDevice(h, out, error);
  DeviceBufferRegistry::Release(h);
  return rc;
}

}  // namespace brt
