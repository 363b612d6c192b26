// A minimal in-process PJRT plugin with N virtual host devices — TEST
// INFRASTRUCTURE ONLY.
//
// The real fabric runs against libtpu via the same C API; this .so exists
// so the device tier (pjrt_executable.cc, cluster/collective_channel.cc,
// the Python DevicePsShardServer) can be exercised natively on a host with
// no chip, the same way the Python tier tests sharding on a virtual
// 8-device CPU mesh (tests/conftest.py). It implements exactly the slice
// of the PJRT C API the brt device layer calls, and it "executes" only the
// StableHLO modules the Mlir* builders in pjrt_executable.cc generate
// (recognized by module name — this is a test double, not a compiler).
// Like a real PJRT it binds each replica to the device its compile options
// assign and refuses arguments that live elsewhere.
//
// Reference analog: loopback integration tests that fake the wire peer
// (e.g. test/brpc_channel_unittest.cpp:215-298 builds a half-fake server
// to exercise the real client stack).
#include <cstdint>
#include <cstring>
#include <memory>
#include <string>
#include <vector>

#include "third_party/pjrt/pjrt_c_api.h"

namespace {

// ---- concrete definitions of the opaque C API types ----

struct Error {
  std::string msg;
};

struct Event {
  // Host execution is synchronous: every event is born ready.
  Error* error = nullptr;  // owned until handed to a callback
};

struct Device {
  int id = 0;
};

struct Client {
  std::vector<Device> devices;
  std::vector<PJRT_Device*> device_ptrs;
};

struct Buffer {
  std::vector<char> data;
  std::vector<int64_t> dims;
  PJRT_Buffer_Type type = PJRT_Buffer_Type_U8;
  Device* device = nullptr;
  // $BRT_FAKE_COLMAJOR mode: rank-2 buffers store column-major bytes and
  // report minor_to_major={0,1}, the way libtpu lands narrow rank-2 arrays
  // such as (16,8) f32, so RepackDeviceLayout gets native coverage (it is
  // a no-op on the default row-major fake layout).
  bool colmajor = false;
  // Layout storage handed out by GetMemoryLayout (buffer-owned). Built
  // eagerly at creation: concurrent StageFromDevice on one pinned handle
  // is a supported pattern, so no lazy mutation after publication.
  std::vector<int64_t> mtm;

  void InitLayout() {
    const size_t rank = dims.size();
    for (size_t i = 0; i < rank; ++i) {
      mtm.push_back(colmajor ? int64_t(i) : int64_t(rank) - 1 - int64_t(i));
    }
  }
};

enum class Kind {
  kAdd,
  kReduceSum,
  kAllReduce,
  kAllGather,
  kGatherRows,
  kScatterSub,
};

struct Executable {
  Kind kind;
  int replicas = 1;
  std::vector<int> device_ids;  // replica r runs on device device_ids[r]
  size_t n = 0;     // vector length / rows
  size_t dim = 0;   // gather/scatter row width
  size_t k = 0;     // gather/scatter id count
};
struct LoadedExecutable {
  Executable exe;
  Client* client;
};

PJRT_Error* Err(const std::string& m) {
  return reinterpret_cast<PJRT_Error*>(new Error{m});
}

// ---- error / event / plugin ----

void ErrorDestroy(PJRT_Error_Destroy_Args* a) {
  delete reinterpret_cast<Error*>(a->error);
}
void ErrorMessage(PJRT_Error_Message_Args* a) {
  auto* e = reinterpret_cast<const Error*>(a->error);
  a->message = e->msg.c_str();
  a->message_size = e->msg.size();
}
PJRT_Error* ErrorGetCode(PJRT_Error_GetCode_Args* a) {
  a->code = PJRT_Error_Code_INTERNAL;
  return nullptr;
}
PJRT_Error* PluginInitialize(PJRT_Plugin_Initialize_Args*) { return nullptr; }
PJRT_Error* PluginAttributes(PJRT_Plugin_Attributes_Args* a) {
  a->num_attributes = 0;
  a->attributes = nullptr;
  return nullptr;
}

PJRT_Error* EventDestroy(PJRT_Event_Destroy_Args* a) {
  auto* ev = reinterpret_cast<Event*>(a->event);
  delete ev->error;
  delete ev;
  return nullptr;
}
PJRT_Error* EventIsReady(PJRT_Event_IsReady_Args* a) {
  a->is_ready = true;
  return nullptr;
}
PJRT_Error* EventError(PJRT_Event_Error_Args* a) {
  auto* ev = reinterpret_cast<Event*>(a->event);
  if (ev->error == nullptr) return nullptr;
  return Err(ev->error->msg);
}
PJRT_Error* EventAwait(PJRT_Event_Await_Args* a) {
  return EventError(reinterpret_cast<PJRT_Event_Error_Args*>(a));
}
PJRT_Error* EventOnReady(PJRT_Event_OnReady_Args* a) {
  auto* ev = reinterpret_cast<Event*>(a->event);
  // Ready at birth: fire the callback inline. The callback owns any error.
  PJRT_Error* cb_err = nullptr;
  if (ev->error != nullptr) {
    cb_err = Err(ev->error->msg);
  }
  a->callback(cb_err, a->user_arg);
  return nullptr;
}

// ---- client ----

PJRT_Error* ClientCreate(PJRT_Client_Create_Args* a) {
  int n = 4;
  if (const char* env = getenv("BRT_FAKE_PJRT_DEVICES")) n = atoi(env);
  for (size_t i = 0; i < a->num_options; ++i) {
    const PJRT_NamedValue& nv = a->create_options[i];
    if (std::string(nv.name, nv.name_size) == "num_devices" &&
        nv.type == PJRT_NamedValue_kInt64) {
      n = int(nv.int64_value);
    }
  }
  if (n <= 0) n = 1;
  auto* c = new Client();
  c->devices.resize(size_t(n));
  for (int i = 0; i < n; ++i) {
    c->devices[size_t(i)].id = i;
    c->device_ptrs.push_back(
        reinterpret_cast<PJRT_Device*>(&c->devices[size_t(i)]));
  }
  a->client = reinterpret_cast<PJRT_Client*>(c);
  return nullptr;
}
PJRT_Error* ClientDestroy(PJRT_Client_Destroy_Args* a) {
  delete reinterpret_cast<Client*>(a->client);
  return nullptr;
}
PJRT_Error* ClientPlatformName(PJRT_Client_PlatformName_Args* a) {
  static const char kName[] = "brt_fake";
  a->platform_name = kName;
  a->platform_name_size = sizeof(kName) - 1;
  return nullptr;
}
PJRT_Error* ClientAddressableDevices(
    PJRT_Client_AddressableDevices_Args* a) {
  auto* c = reinterpret_cast<Client*>(a->client);
  a->addressable_devices = c->device_ptrs.data();
  a->num_addressable_devices = c->device_ptrs.size();
  return nullptr;
}

// The fake's PJRT_DeviceDescription is the Device itself.
PJRT_Error* DeviceGetDescription(PJRT_Device_GetDescription_Args* a) {
  a->device_description = reinterpret_cast<PJRT_DeviceDescription*>(a->device);
  return nullptr;
}
PJRT_Error* DeviceDescriptionId(PJRT_DeviceDescription_Id_Args* a) {
  a->id = reinterpret_cast<Device*>(a->device_description)->id;
  return nullptr;
}
PJRT_Error* DeviceDescriptionKind(PJRT_DeviceDescription_Kind_Args* a) {
  static const char kKind[] = "brt_fake_device";
  a->device_kind = kKind;
  a->device_kind_size = sizeof(kKind) - 1;
  return nullptr;
}

size_t ElemSize(PJRT_Buffer_Type t) {
  switch (t) {
    case PJRT_Buffer_Type_U8:
      return 1;
    case PJRT_Buffer_Type_F32:
    case PJRT_Buffer_Type_S32:
      return 4;
    default:
      return 1;
  }
}

PJRT_Error* BufferFromHostBuffer(PJRT_Client_BufferFromHostBuffer_Args* a) {
  auto* b = new Buffer();
  b->type = a->type;
  b->dims.assign(a->dims, a->dims + a->num_dims);
  int64_t n = 1;
  for (int64_t d : b->dims) n *= d;
  const size_t bytes = size_t(n) * ElemSize(a->type);
  const char* src = static_cast<const char*>(a->data);
  b->device = reinterpret_cast<Device*>(a->device);
  if (getenv("BRT_FAKE_COLMAJOR") != nullptr && b->dims.size() == 2) {
    // Host input is dense row-major (byte_strides unset); store it
    // transposed, as a column-major device would.
    const size_t e = ElemSize(a->type);
    const size_t rows = size_t(b->dims[0]), cols = size_t(b->dims[1]);
    b->colmajor = true;
    b->data.resize(bytes);
    for (size_t r = 0; r < rows; ++r) {
      for (size_t c = 0; c < cols; ++c) {
        memcpy(&b->data[(c * rows + r) * e], src + (r * cols + c) * e, e);
      }
    }
  } else {
    b->data.assign(src, src + bytes);
  }
  b->InitLayout();
  a->buffer = reinterpret_cast<PJRT_Buffer*>(b);
  a->done_with_host_buffer = reinterpret_cast<PJRT_Event*>(new Event());
  return nullptr;
}
PJRT_Error* BufferDestroy(PJRT_Buffer_Destroy_Args* a) {
  delete reinterpret_cast<Buffer*>(a->buffer);
  return nullptr;
}
PJRT_Error* BufferOnDeviceSize(PJRT_Buffer_OnDeviceSizeInBytes_Args* a) {
  a->on_device_size_in_bytes =
      reinterpret_cast<Buffer*>(a->buffer)->data.size();
  return nullptr;
}
PJRT_Error* BufferDimensions(PJRT_Buffer_Dimensions_Args* a) {
  auto* b = reinterpret_cast<Buffer*>(a->buffer);
  a->dims = b->dims.data();
  a->num_dims = b->dims.size();
  return nullptr;
}
PJRT_Error* BufferElementType(PJRT_Buffer_ElementType_Args* a) {
  a->type = reinterpret_cast<Buffer*>(a->buffer)->type;
  return nullptr;
}
PJRT_Error* BufferGetMemoryLayout(PJRT_Buffer_GetMemoryLayout_Args* a) {
  auto* b = reinterpret_cast<Buffer*>(a->buffer);
  const size_t rank = b->dims.size();
  memset(&a->layout, 0, sizeof(a->layout));
  a->layout.struct_size = PJRT_Buffer_MemoryLayout_STRUCT_SIZE;
  a->layout.type = PJRT_Buffer_MemoryLayout_Type_Tiled;
  a->layout.tiled.struct_size = PJRT_Buffer_MemoryLayout_Tiled_STRUCT_SIZE;
  a->layout.tiled.minor_to_major = b->mtm.data();
  a->layout.tiled.minor_to_major_size = rank;
  return nullptr;
}
PJRT_Error* BufferDevice(PJRT_Buffer_Device_Args* a) {
  a->device = reinterpret_cast<PJRT_Device*>(
      reinterpret_cast<Buffer*>(a->buffer)->device);
  return nullptr;
}
PJRT_Error* BufferToHostBuffer(PJRT_Buffer_ToHostBuffer_Args* a) {
  auto* b = reinterpret_cast<Buffer*>(a->src);
  if (a->dst == nullptr) {
    a->dst_size = b->data.size();
    return nullptr;
  }
  if (a->dst_size < b->data.size()) return Err("dst too small");
  memcpy(a->dst, b->data.data(), b->data.size());
  a->event = reinterpret_cast<PJRT_Event*>(new Event());
  return nullptr;
}

// ---- compile: recognize the brt Mlir* builder modules by name ----

bool FindNum(const std::string& text, const std::string& anchor,
             size_t* out) {
  size_t p = text.find(anchor);
  if (p == std::string::npos) return false;
  *out = size_t(atoll(text.c_str() + p + anchor.size()));
  return true;
}

// Minimal protobuf walk, enough for the CompileOptionsProto that
// EncodeCompileOptions (pjrt_executable.cc) writes: varints and
// length-delimited fields only.
bool ReadVarint(const char** p, const char* end, uint64_t* v) {
  *v = 0;
  for (int shift = 0; *p < end && shift < 64; shift += 7) {
    const uint8_t b = uint8_t(*(*p)++);
    *v |= uint64_t(b & 0x7f) << shift;
    if (!(b & 0x80)) return true;
  }
  return false;
}

// Narrows [*p, *end) to the payload of its first length-delimited field
// numbered `field`.
bool EnterField(const char** p, const char** end, uint64_t field) {
  while (*p < *end) {
    uint64_t tag, v;
    if (!ReadVarint(p, *end, &tag) || !ReadVarint(p, *end, &v)) return false;
    if ((tag & 7) == 0) continue;  // a varint field: v was its value
    if ((tag & 7) != 2 || v > uint64_t(*end - *p)) return false;
    if (tag >> 3 == field) {
      *end = *p + v;
      return true;
    }
    *p += v;
  }
  return false;
}

// CompileOptionsProto.executable_build_options(3).device_assignment(9)
// .computation_devices(3).replica_device_ids(1, repeated varint).
bool ParseDeviceAssignment(const char* p, size_t n, std::vector<int>* ids) {
  const char* end = p + n;
  for (uint64_t field : {3, 9, 3}) {
    if (!EnterField(&p, &end, field)) return false;
  }
  while (p < end) {
    uint64_t tag, id;
    if (!ReadVarint(&p, end, &tag) || tag != (1 << 3 | 0) ||
        !ReadVarint(&p, end, &id)) {
      return false;
    }
    ids->push_back(int(id));
  }
  return true;
}

PJRT_Error* ClientCompile(PJRT_Client_Compile_Args* a) {
  const std::string text(a->program->code, a->program->code_size);
  auto* client = reinterpret_cast<Client*>(a->client);
  Executable exe;
  size_t replicas = 1;
  FindNum(text, "mhlo.num_replicas = ", &replicas);
  exe.replicas = int(replicas);
  if (!ParseDeviceAssignment(a->compile_options, a->compile_options_size,
                             &exe.device_ids) ||
      exe.device_ids.size() != replicas) {
    return Err("fake plugin: compile options carry no device assignment "
               "for every replica");
  }
  for (int id : exe.device_ids) {
    if (id < 0 || size_t(id) >= client->devices.size()) {
      return Err("fake plugin: device assignment names an unknown device");
    }
  }
  if (text.find("module @brt_add ") != std::string::npos) {
    exe.kind = Kind::kAdd;
  } else if (text.find("module @brt_reduce_sum ") != std::string::npos) {
    exe.kind = Kind::kReduceSum;
  } else if (text.find("module @brt_all_reduce ") != std::string::npos) {
    exe.kind = Kind::kAllReduce;
  } else if (text.find("module @brt_all_gather ") != std::string::npos) {
    exe.kind = Kind::kAllGather;
  } else if (text.find("module @brt_gather_rows ") != std::string::npos) {
    exe.kind = Kind::kGatherRows;
  } else if (text.find("module @brt_scatter_sub ") != std::string::npos) {
    exe.kind = Kind::kScatterSub;
  } else {
    return Err("fake plugin: unrecognized module (only brt_* builders)");
  }
  if (exe.kind == Kind::kGatherRows || exe.kind == Kind::kScatterSub) {
    // main(%arg0: tensor<RxDxf32>, %arg1: tensor<Kxi32> ...
    size_t p = text.find("%arg0: tensor<");
    if (p == std::string::npos) return Err("fake plugin: bad module");
    exe.n = size_t(atoll(text.c_str() + p + 14));
    size_t x = text.find('x', p + 14);
    exe.dim = size_t(atoll(text.c_str() + x + 1));
    size_t q = text.find("%arg1: tensor<");
    exe.k = size_t(atoll(text.c_str() + q + 14));
  } else {
    size_t p = text.find("%arg0: tensor<");
    if (p == std::string::npos) return Err("fake plugin: bad module");
    exe.n = size_t(atoll(text.c_str() + p + 14));
  }
  auto* le = new LoadedExecutable{exe, client};
  a->executable = reinterpret_cast<PJRT_LoadedExecutable*>(le);
  return nullptr;
}

PJRT_Error* LoadedGetExecutable(
    PJRT_LoadedExecutable_GetExecutable_Args* a) {
  auto* le = reinterpret_cast<LoadedExecutable*>(a->loaded_executable);
  a->executable = reinterpret_cast<PJRT_Executable*>(new Executable(le->exe));
  return nullptr;
}
PJRT_Error* ExecutableDestroy(PJRT_Executable_Destroy_Args* a) {
  delete reinterpret_cast<Executable*>(a->executable);
  return nullptr;
}
PJRT_Error* LoadedDestroy(PJRT_LoadedExecutable_Destroy_Args* a) {
  delete reinterpret_cast<LoadedExecutable*>(a->executable);
  return nullptr;
}
PJRT_Error* ExecutableNumOutputs(PJRT_Executable_NumOutputs_Args* a) {
  a->num_outputs = 1;
  return nullptr;
}

Buffer* NewF32(const std::vector<int64_t>& dims, Device* device) {
  auto* b = new Buffer();
  b->type = PJRT_Buffer_Type_F32;
  b->dims = dims;
  b->device = device;
  int64_t n = 1;
  for (int64_t d : dims) n *= d;
  b->data.assign(size_t(n) * 4, 0);
  b->InitLayout();
  return b;
}
float* F(Buffer* b) { return reinterpret_cast<float*>(b->data.data()); }
const float* F(PJRT_Buffer* b) {
  return reinterpret_cast<const float*>(
      reinterpret_cast<Buffer*>(b)->data.data());
}
const int32_t* I(PJRT_Buffer* b) {
  return reinterpret_cast<const int32_t*>(
      reinterpret_cast<Buffer*>(b)->data.data());
}

PJRT_Error* LoadedExecute(PJRT_LoadedExecutable_Execute_Args* a) {
  auto* le = reinterpret_cast<LoadedExecutable*>(a->executable);
  const Executable& e = le->exe;
  const size_t ndev = a->num_devices;
  if (int(ndev) != e.replicas) return Err("fake plugin: ndev != replicas");
  // Replica d runs on its assigned device: every argument must already
  // live there, and the result is created there.
  std::vector<Device*> dev(ndev);
  for (size_t d = 0; d < ndev; ++d) {
    dev[d] = &le->client->devices[size_t(e.device_ids[d])];
    for (size_t i = 0; i < a->num_args; ++i) {
      auto* arg = reinterpret_cast<Buffer*>(a->argument_lists[d][i]);
      if (arg->device != dev[d]) {
        return Err("fake plugin: argument " + std::to_string(i) +
                   " lives on device " + std::to_string(arg->device->id) +
                   " but replica " + std::to_string(d) +
                   " is bound to device " + std::to_string(dev[d]->id));
      }
    }
  }
  const size_t n = e.n;
  switch (e.kind) {
    case Kind::kAdd:
      for (size_t d = 0; d < ndev; ++d) {
        Buffer* out = NewF32({int64_t(n)}, dev[d]);
        const float* x = F(a->argument_lists[d][0]);
        const float* y = F(a->argument_lists[d][1]);
        for (size_t i = 0; i < n; ++i) F(out)[i] = x[i] + y[i];
        a->output_lists[d][0] = reinterpret_cast<PJRT_Buffer*>(out);
      }
      break;
    case Kind::kReduceSum:
      for (size_t d = 0; d < ndev; ++d) {
        Buffer* out = NewF32({}, dev[d]);
        const float* x = F(a->argument_lists[d][0]);
        float s = 0;
        for (size_t i = 0; i < n; ++i) s += x[i];
        F(out)[0] = s;
        a->output_lists[d][0] = reinterpret_cast<PJRT_Buffer*>(out);
      }
      break;
    case Kind::kAllReduce: {
      std::vector<float> sum(n, 0.f);
      for (size_t d = 0; d < ndev; ++d) {
        const float* x = F(a->argument_lists[d][0]);
        for (size_t i = 0; i < n; ++i) sum[i] += x[i];
      }
      for (size_t d = 0; d < ndev; ++d) {
        Buffer* out = NewF32({int64_t(n)}, dev[d]);
        memcpy(F(out), sum.data(), n * 4);
        a->output_lists[d][0] = reinterpret_cast<PJRT_Buffer*>(out);
      }
      break;
    }
    case Kind::kAllGather:
      for (size_t d = 0; d < ndev; ++d) {
        Buffer* out = NewF32({int64_t(n * ndev)}, dev[d]);
        for (size_t r = 0; r < ndev; ++r) {
          memcpy(F(out) + r * n, F(a->argument_lists[r][0]), n * 4);
        }
        a->output_lists[d][0] = reinterpret_cast<PJRT_Buffer*>(out);
      }
      break;
    case Kind::kGatherRows:
      for (size_t d = 0; d < ndev; ++d) {
        Buffer* out = NewF32({int64_t(e.k), int64_t(e.dim)}, dev[d]);
        const float* tbl = F(a->argument_lists[d][0]);
        const int32_t* ids = I(a->argument_lists[d][1]);
        for (size_t i = 0; i < e.k; ++i) {
          memcpy(F(out) + i * e.dim, tbl + size_t(ids[i]) * e.dim,
                 e.dim * 4);
        }
        a->output_lists[d][0] = reinterpret_cast<PJRT_Buffer*>(out);
      }
      break;
    case Kind::kScatterSub:
      for (size_t d = 0; d < ndev; ++d) {
        Buffer* out = NewF32({int64_t(e.n), int64_t(e.dim)}, dev[d]);
        const float* tbl = F(a->argument_lists[d][0]);
        const int32_t* ids = I(a->argument_lists[d][1]);
        const float* g = F(a->argument_lists[d][2]);
        const float lr = F(a->argument_lists[d][3])[0];
        memcpy(F(out), tbl, e.n * e.dim * 4);
        for (size_t i = 0; i < e.k; ++i) {
          for (size_t j = 0; j < e.dim; ++j) {
            F(out)[size_t(ids[i]) * e.dim + j] -= lr * g[i * e.dim + j];
          }
        }
        a->output_lists[d][0] = reinterpret_cast<PJRT_Buffer*>(out);
      }
      break;
  }
  if (a->device_complete_events != nullptr) {
    for (size_t d = 0; d < ndev; ++d) {
      a->device_complete_events[d] =
          reinterpret_cast<PJRT_Event*>(new Event());
    }
  }
  return nullptr;
}

PJRT_Api MakeApi() {
  PJRT_Api api;
  memset(&api, 0, sizeof(api));
  api.struct_size = PJRT_Api_STRUCT_SIZE;
  api.pjrt_api_version.struct_size = PJRT_Api_Version_STRUCT_SIZE;
  api.pjrt_api_version.major_version = PJRT_API_MAJOR;
  api.pjrt_api_version.minor_version = PJRT_API_MINOR;
  api.PJRT_Error_Destroy = ErrorDestroy;
  api.PJRT_Error_Message = ErrorMessage;
  api.PJRT_Error_GetCode = ErrorGetCode;
  api.PJRT_Plugin_Initialize = PluginInitialize;
  api.PJRT_Plugin_Attributes = PluginAttributes;
  api.PJRT_Event_Destroy = EventDestroy;
  api.PJRT_Event_IsReady = EventIsReady;
  api.PJRT_Event_Error = EventError;
  api.PJRT_Event_Await = EventAwait;
  api.PJRT_Event_OnReady = EventOnReady;
  api.PJRT_Client_Create = ClientCreate;
  api.PJRT_Client_Destroy = ClientDestroy;
  api.PJRT_Client_PlatformName = ClientPlatformName;
  api.PJRT_Client_AddressableDevices = ClientAddressableDevices;
  api.PJRT_Device_GetDescription = DeviceGetDescription;
  api.PJRT_DeviceDescription_Id = DeviceDescriptionId;
  api.PJRT_DeviceDescription_Kind = DeviceDescriptionKind;
  api.PJRT_Client_BufferFromHostBuffer = BufferFromHostBuffer;
  api.PJRT_Client_Compile = ClientCompile;
  api.PJRT_Buffer_Destroy = BufferDestroy;
  api.PJRT_Buffer_OnDeviceSizeInBytes = BufferOnDeviceSize;
  api.PJRT_Buffer_Dimensions = BufferDimensions;
  api.PJRT_Buffer_ElementType = BufferElementType;
  api.PJRT_Buffer_GetMemoryLayout = BufferGetMemoryLayout;
  api.PJRT_Buffer_Device = BufferDevice;
  api.PJRT_Buffer_ToHostBuffer = BufferToHostBuffer;
  api.PJRT_LoadedExecutable_Destroy = LoadedDestroy;
  api.PJRT_LoadedExecutable_GetExecutable = LoadedGetExecutable;
  api.PJRT_LoadedExecutable_Execute = LoadedExecute;
  api.PJRT_Executable_Destroy = ExecutableDestroy;
  api.PJRT_Executable_NumOutputs = ExecutableNumOutputs;
  return api;
}

}  // namespace

extern "C" const PJRT_Api* GetPjrtApi() {
  static PJRT_Api api = MakeApi();
  return &api;
}
