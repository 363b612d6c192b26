// Native compiled-execution tier over PJRT — compile + launch without JAX.
//
// This is the piece that turns the device layer from a staging demo into a
// fabric: the CollectiveChannel (cluster/collective_channel.h) maps the
// ParallelChannel fan-out/merge contract (reference
// src/brpc/parallel_channel.h:94,127,185) onto ONE compiled cross-replica
// collective launched here, the way the reference maps a Socket write onto
// RDMA QPs (src/brpc/rdma/rdma_endpoint.cpp:774,1153).
//
// Programs are textual StableHLO built by the Mlir* helpers below; replica
// d of the launch is the analog of sub-channel d of a ParallelChannel.
// Arguments and results are DeviceBufferRegistry handles, so executables
// compose with the staging tier: stage → execute → ship the result handle.
#pragma once

#include <cstdint>
#include <memory>
#include <string>
#include <vector>

#include "device/pjrt_device.h"

typedef struct PJRT_LoadedExecutable PJRT_LoadedExecutable;

namespace brt {

// Textual StableHLO builders for the collective fast paths. `n` is the
// element count of the f32 operand vectors; shapes are static (XLA traces
// once — cache executables per shape).
std::string MlirAddF32(size_t n);            // main(a, b) = a + b
std::string MlirReduceSumF32(size_t n);      // main(a) = sum(a) : scalar
// Cross-replica elementwise sum: every replica contributes its vector and
// receives the merged result (the ParallelChannel broadcast + additive
// ResponseMerger shape).
std::string MlirAllReduceSumF32(size_t n, int replicas);
// Cross-replica concat: replica r contributes its n-vector, every replica
// receives the n*replicas concatenation (the default "append responses in
// channel order" merger).
std::string MlirAllGatherF32(size_t n, int replicas);
// main(table[rows,dim], ids[k]) = table rows gathered by ids — the PS
// embedding-lookup fast path, run where the table lives.
std::string MlirGatherRowsF32(size_t rows, size_t dim, size_t k);
// main(table[rows,dim], ids[k], grads[k,dim], lr[]) = table with
// lr-scaled grads scattered-subtracted at ids (SGD embedding update).
std::string MlirScatterSubF32(size_t rows, size_t dim, size_t k);

// Hand-rolled serialized xla.CompileOptionsProto: one partition,
// replica_device_ids.size() replicas, replica r assigned to the device with
// PJRT id replica_device_ids[r] (the only fields the fabric needs;
// everything else takes plugin defaults).
std::string EncodeCompileOptions(const std::vector<int>& replica_device_ids);

class PjrtExecutable {
 public:
  // Compiles textual StableHLO for `num_replicas` replicas; replica r is
  // bound to client->addressable_device(first_device + r), so its
  // arguments must live there and its results land there.
  static std::unique_ptr<PjrtExecutable> Compile(PjrtClient* client,
                                                 const std::string& mlir_text,
                                                 int num_replicas,
                                                 std::string* error,
                                                 int first_device = 0);
  ~PjrtExecutable();
  PjrtExecutable(const PjrtExecutable&) = delete;
  PjrtExecutable& operator=(const PjrtExecutable&) = delete;

  int num_replicas() const { return num_replicas_; }
  int num_outputs() const { return num_outputs_; }

  // Launches once across all replicas. args[d][i] is the
  // DeviceBufferRegistry handle of argument i on replica d; args.size()
  // must equal num_replicas(). Argument buffers are pinned for the
  // duration (a concurrent Release cannot free them mid-launch). On
  // success (*outs)[d][o] holds freshly registered handles of the outputs,
  // resident in HBM until released. The calling fiber parks on the
  // per-device completion events; worker pthreads keep running.
  int Execute(const std::vector<std::vector<uint64_t>>& args,
              std::vector<std::vector<uint64_t>>* outs, std::string* error);

 private:
  PjrtExecutable() = default;
  PjrtClient* client_ = nullptr;
  PJRT_LoadedExecutable* exe_ = nullptr;
  int num_replicas_ = 1;
  int num_outputs_ = 1;
};

}  // namespace brt
