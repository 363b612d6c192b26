#include "device/pjrt_executable.h"

#include <cstdio>
#include <cstring>

#include "base/logging.h"
#include "device/pjrt_args.h"
#include "third_party/pjrt/pjrt_c_api.h"

namespace brt {

namespace {

void AppendVarint(std::string* out, uint64_t v) {
  while (v >= 0x80) {
    out->push_back(char(v | 0x80));
    v >>= 7;
  }
  out->push_back(char(v));
}

void AppendTag(std::string* out, int field, int wire) {
  AppendVarint(out, uint64_t(field) << 3 | uint64_t(wire));
}

std::string ModuleHeader(const char* name, int replicas) {
  char buf[160];
  snprintf(buf, sizeof(buf),
           "module @%s attributes {mhlo.num_partitions = 1 : i32, "
           "mhlo.num_replicas = %d : i32} {\n",
           name, replicas);
  return buf;
}

// replica_groups = dense<[[0, 1, ..., n-1]]> : tensor<1xNxi64>
std::string ReplicaGroups(int replicas) {
  std::string s = "dense<[[";
  for (int i = 0; i < replicas; ++i) {
    if (i) s += ", ";
    s += std::to_string(i);
  }
  s += "]]> : tensor<1x" + std::to_string(replicas) + "xi64>";
  return s;
}

// The add-reduction region shared by all_reduce / reduce.
constexpr const char* kAddRegion =
    "    ^bb0(%a: tensor<f32>, %b: tensor<f32>):\n"
    "      %s = stablehlo.add %a, %b : tensor<f32>\n"
    "      stablehlo.return %s : tensor<f32>\n";

}  // namespace

std::string MlirAddF32(size_t n) {
  const std::string t = "tensor<" + std::to_string(n) + "xf32>";
  return ModuleHeader("brt_add", 1) +
         "  func.func public @main(%arg0: " + t + ", %arg1: " + t +
         ") -> " + t + " {\n"
         "    %0 = stablehlo.add %arg0, %arg1 : " + t + "\n"
         "    return %0 : " + t + "\n"
         "  }\n}\n";
}

std::string MlirReduceSumF32(size_t n) {
  const std::string t = "tensor<" + std::to_string(n) + "xf32>";
  return ModuleHeader("brt_reduce_sum", 1) +
         "  func.func public @main(%arg0: " + t + ") -> tensor<f32> {\n"
         "    %cst = stablehlo.constant dense<0.000000e+00> : tensor<f32>\n"
         "    %0 = stablehlo.reduce(%arg0 init: %cst) applies "
         "stablehlo.add across dimensions = [0] : (" + t +
         ", tensor<f32>) -> tensor<f32>\n"
         "    return %0 : tensor<f32>\n"
         "  }\n}\n";
}

std::string MlirAllReduceSumF32(size_t n, int replicas) {
  const std::string t = "tensor<" + std::to_string(n) + "xf32>";
  return ModuleHeader("brt_all_reduce", replicas) +
         "  func.func public @main(%arg0: " + t + ") -> " + t + " {\n"
         "    %0 = \"stablehlo.all_reduce\"(%arg0) <{replica_groups = " +
         ReplicaGroups(replicas) + "}> ({\n" + kAddRegion +
         "    }) : (" + t + ") -> " + t + "\n"
         "    return %0 : " + t + "\n"
         "  }\n}\n";
}

std::string MlirAllGatherF32(size_t n, int replicas) {
  const std::string t = "tensor<" + std::to_string(n) + "xf32>";
  const std::string to =
      "tensor<" + std::to_string(n * size_t(replicas)) + "xf32>";
  return ModuleHeader("brt_all_gather", replicas) +
         "  func.func public @main(%arg0: " + t + ") -> " + to + " {\n"
         "    %0 = \"stablehlo.all_gather\"(%arg0) <{all_gather_dim = 0 : "
         "i64, replica_groups = " + ReplicaGroups(replicas) +
         "}> : (" + t + ") -> " + to + "\n"
         "    return %0 : " + to + "\n"
         "  }\n}\n";
}

std::string MlirGatherRowsF32(size_t rows, size_t dim, size_t k) {
  const std::string tbl =
      "tensor<" + std::to_string(rows) + "x" + std::to_string(dim) + "xf32>";
  const std::string ids = "tensor<" + std::to_string(k) + "xi32>";
  const std::string ids2 = "tensor<" + std::to_string(k) + "x1xi32>";
  const std::string out =
      "tensor<" + std::to_string(k) + "x" + std::to_string(dim) + "xf32>";
  return ModuleHeader("brt_gather_rows", 1) +
         "  func.func public @main(%arg0: " + tbl + ", %arg1: " + ids +
         ") -> " + out + " {\n"
         "    %0 = stablehlo.broadcast_in_dim %arg1, dims = [0] : (" + ids +
         ") -> " + ids2 + "\n"
         "    %1 = \"stablehlo.gather\"(%arg0, %0) <{dimension_numbers = "
         "#stablehlo.gather<offset_dims = [1], collapsed_slice_dims = [0], "
         "start_index_map = [0], index_vector_dim = 1>, indices_are_sorted "
         "= false, slice_sizes = array<i64: 1, " + std::to_string(dim) +
         ">}> : (" + tbl + ", " + ids2 + ") -> " + out + "\n"
         "    return %1 : " + out + "\n"
         "  }\n}\n";
}

std::string MlirScatterSubF32(size_t rows, size_t dim, size_t k) {
  const std::string tbl =
      "tensor<" + std::to_string(rows) + "x" + std::to_string(dim) + "xf32>";
  const std::string ids = "tensor<" + std::to_string(k) + "xi32>";
  const std::string ids2 = "tensor<" + std::to_string(k) + "x1xi32>";
  const std::string upd =
      "tensor<" + std::to_string(k) + "x" + std::to_string(dim) + "xf32>";
  return ModuleHeader("brt_scatter_sub", 1) +
         "  func.func public @main(%arg0: " + tbl + ", %arg1: " + ids +
         ", %arg2: " + upd + ", %arg3: tensor<f32>) -> " + tbl + " {\n"
         "    %0 = stablehlo.negate %arg3 : tensor<f32>\n"
         "    %1 = stablehlo.broadcast_in_dim %0, dims = [] : "
         "(tensor<f32>) -> " + upd + "\n"
         "    %2 = stablehlo.multiply %1, %arg2 : " + upd + "\n"
         "    %3 = stablehlo.broadcast_in_dim %arg1, dims = [0] : (" + ids +
         ") -> " + ids2 + "\n"
         "    %4 = \"stablehlo.scatter\"(%arg0, %3, %2) "
         "<{indices_are_sorted = false, scatter_dimension_numbers = "
         "#stablehlo.scatter<update_window_dims = [1], inserted_window_dims "
         "= [0], scatter_dims_to_operand_dims = [0], index_vector_dim = 1>, "
         "unique_indices = false}> ({\n" + kAddRegion +
         "    }) : (" + tbl + ", " + ids2 + ", " + upd + ") -> " + tbl +
         "\n"
         "    return %4 : " + tbl + "\n"
         "  }\n}\n";
}

std::string EncodeCompileOptions(const std::vector<int>& replica_device_ids) {
  // Field numbers from xla/pjrt/proto/compile_options.proto and
  // xla/xla_data.proto (cited by the PJRT C API header at
  // PJRT_Client_Compile_Args). Everything absent takes plugin defaults.
  //
  // xla.DeviceAssignmentProto: replica_count=1, computation_count=2,
  // computation_devices=3 { replica_device_ids=1 }. One computation
  // (partition); replica r runs on replica_device_ids[r].
  std::string comp;
  for (int id : replica_device_ids) {
    AppendTag(&comp, 1, 0);
    AppendVarint(&comp, uint64_t(id));
  }
  std::string assign;
  AppendTag(&assign, 1, 0);
  AppendVarint(&assign, replica_device_ids.size());
  AppendTag(&assign, 2, 0);
  AppendVarint(&assign, 1);
  AppendTag(&assign, 3, 2);
  AppendVarint(&assign, comp.size());
  assign += comp;
  // xla.ExecutableBuildOptionsProto: device_ordinal=1, num_replicas=4,
  // num_partitions=5, device_assignment=9.
  std::string build;
  AppendTag(&build, 1, 0);                    // device_ordinal = -1
  AppendVarint(&build, uint64_t(int64_t(-1)));  // ("unset": the assignment
  AppendTag(&build, 4, 0);                    //  below places the replicas)
  AppendVarint(&build, replica_device_ids.size());
  AppendTag(&build, 5, 0);
  AppendVarint(&build, 1);
  AppendTag(&build, 9, 2);
  AppendVarint(&build, assign.size());
  build += assign;
  // xla.CompileOptionsProto: executable_build_options = field 3.
  std::string opts;
  AppendTag(&opts, 3, 2);
  AppendVarint(&opts, build.size());
  opts += build;
  return opts;
}

std::unique_ptr<PjrtExecutable> PjrtExecutable::Compile(
    PjrtClient* client, const std::string& mlir_text, int num_replicas,
    std::string* error, int first_device) {
  const PjrtApi* api = client->api();
  if (num_replicas < 1 || first_device < 0 ||
      first_device + num_replicas > client->addressable_device_count()) {
    if (error) *error = "replicas do not fit the addressable devices";
    return nullptr;
  }
  std::vector<int> device_ids;
  for (int r = 0; r < num_replicas; ++r) {
    const int id = client->device_id(first_device + r);
    if (id < 0) {
      if (error) *error = "device has no PJRT id";
      return nullptr;
    }
    device_ids.push_back(id);
  }
  const std::string copts = EncodeCompileOptions(device_ids);

  auto prog = BRT_PJRT_ARGS(PJRT_Program);
  prog.code = const_cast<char*>(mlir_text.data());
  prog.code_size = mlir_text.size();
  prog.format = "mlir";
  prog.format_size = 4;

  auto args = BRT_PJRT_ARGS(PJRT_Client_Compile_Args);
  args.client = client->raw_client();
  args.program = &prog;
  args.compile_options = copts.data();
  args.compile_options_size = copts.size();
  if (PJRT_Error* err = api->raw()->PJRT_Client_Compile(&args)) {
    if (error) *error = "PJRT_Client_Compile: " + api->ConsumeError(err);
    return nullptr;
  }

  std::unique_ptr<PjrtExecutable> exe(new PjrtExecutable());
  exe->client_ = client;
  exe->exe_ = args.executable;
  exe->num_replicas_ = num_replicas;

  // Output arity, via the unloaded view of the executable.
  auto gargs = BRT_PJRT_ARGS(PJRT_LoadedExecutable_GetExecutable_Args);
  gargs.loaded_executable = args.executable;
  if (PJRT_Error* err =
          api->raw()->PJRT_LoadedExecutable_GetExecutable(&gargs)) {
    if (error) *error =
        "LoadedExecutable_GetExecutable: " + api->ConsumeError(err);
    return nullptr;
  }
  auto nargs = BRT_PJRT_ARGS(PJRT_Executable_NumOutputs_Args);
  nargs.executable = gargs.executable;
  PJRT_Error* nerr = api->raw()->PJRT_Executable_NumOutputs(&nargs);
  auto dargs = BRT_PJRT_ARGS(PJRT_Executable_Destroy_Args);
  dargs.executable = gargs.executable;
  if (PJRT_Error* derr = api->raw()->PJRT_Executable_Destroy(&dargs)) {
    BRT_LOG(ERROR) << "Executable_Destroy: " << api->ConsumeError(derr);
  }
  if (nerr != nullptr) {
    if (error) *error = "Executable_NumOutputs: " + api->ConsumeError(nerr);
    return nullptr;
  }
  exe->num_outputs_ = int(nargs.num_outputs);
  return exe;
}

PjrtExecutable::~PjrtExecutable() {
  if (exe_ != nullptr) {
    const PjrtApi* api = client_->api();
    auto args = BRT_PJRT_ARGS(PJRT_LoadedExecutable_Destroy_Args);
    args.executable = exe_;
    if (PJRT_Error* err = api->raw()->PJRT_LoadedExecutable_Destroy(&args)) {
      BRT_LOG(ERROR) << "LoadedExecutable_Destroy: "
                     << api->ConsumeError(err);
    }
  }
}

int PjrtExecutable::Execute(const std::vector<std::vector<uint64_t>>& args,
                            std::vector<std::vector<uint64_t>>* outs,
                            std::string* error) {
  const PjrtApi* api = client_->api();
  const size_t ndev = size_t(num_replicas_);
  if (args.size() != ndev) {
    if (error) *error = "argument lists != num_replicas";
    return EINVAL;
  }
  const size_t nargs = args.empty() ? 0 : args[0].size();

  // Pin every argument for the duration of the launch.
  std::vector<uint64_t> pinned;
  pinned.reserve(ndev * nargs);
  auto unpin_all = [&pinned] {
    for (uint64_t h : pinned) DeviceBufferRegistry::Unpin(h);
  };
  std::vector<std::vector<PJRT_Buffer*>> arg_bufs(ndev);
  std::vector<PJRT_Buffer* const*> arg_lists(ndev);
  for (size_t d = 0; d < ndev; ++d) {
    if (args[d].size() != nargs) {
      unpin_all();
      if (error) *error = "ragged argument lists";
      return EINVAL;
    }
    arg_bufs[d].resize(nargs);
    for (size_t i = 0; i < nargs; ++i) {
      PJRT_Buffer* b = DeviceBufferRegistry::Pin(args[d][i]);
      if (b == nullptr) {
        unpin_all();
        if (error) *error = "stale argument handle";
        return EINVAL;
      }
      pinned.push_back(args[d][i]);
      arg_bufs[d][i] = b;
    }
    arg_lists[d] = arg_bufs[d].data();
  }

  const size_t nouts = size_t(num_outputs_);
  std::vector<std::vector<PJRT_Buffer*>> out_bufs(
      ndev, std::vector<PJRT_Buffer*>(nouts, nullptr));
  std::vector<PJRT_Buffer**> out_lists(ndev);
  for (size_t d = 0; d < ndev; ++d) out_lists[d] = out_bufs[d].data();
  std::vector<PJRT_Event*> done(ndev, nullptr);

  auto opts = BRT_PJRT_ARGS(PJRT_ExecuteOptions);
  auto eargs = BRT_PJRT_ARGS(PJRT_LoadedExecutable_Execute_Args);
  eargs.executable = exe_;
  eargs.options = &opts;
  eargs.argument_lists = arg_lists.data();
  eargs.num_devices = ndev;
  eargs.num_args = nargs;
  eargs.output_lists = out_lists.data();
  eargs.device_complete_events = done.data();
  if (PJRT_Error* err = api->raw()->PJRT_LoadedExecutable_Execute(&eargs)) {
    unpin_all();
    if (error) *error =
        "LoadedExecutable_Execute: " + api->ConsumeError(err);
    return EIO;
  }
  // Park the calling fiber until every replica's execution completes; the
  // inputs stay pinned until then.
  int rc = 0;
  for (size_t d = 0; d < ndev; ++d) {
    PjrtEvent ev(api, done[d]);
    int erc = ev.Wait(client_->thread_wait());
    if (erc != 0 && rc == 0) rc = erc;
  }
  unpin_all();
  if (rc != 0) {
    for (auto& per_dev : out_bufs) {
      for (PJRT_Buffer* b : per_dev) {
        if (b == nullptr) continue;
        auto bd = BRT_PJRT_ARGS(PJRT_Buffer_Destroy_Args);
        bd.buffer = b;
        api->raw()->PJRT_Buffer_Destroy(&bd);
      }
    }
    if (error) *error = "device execution failed";
    return rc;
  }
  outs->assign(ndev, std::vector<uint64_t>(nouts, 0));
  for (size_t d = 0; d < ndev; ++d) {
    for (size_t o = 0; o < nouts; ++o) {
      // All Mlir* builder programs produce f32 results; the device is
      // where PJRT says the result lives — recorded so shipped handles
      // can be placement-checked.
      (*outs)[d][o] = DeviceBufferRegistry::Register(
          api, out_bufs[d][o], client_->DeviceIndexOf(out_bufs[d][o]),
          int(PjrtClient::DType::kF32));
    }
  }
  return 0;
}

}  // namespace brt
