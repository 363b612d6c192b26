// Native TPU device layer — the RDMA-transport analog.
//
// Parity target: reference src/brpc/rdma/ —
//   * RdmaEndpoint handshake/zero-copy send/recv (rdma_endpoint.cpp:412,
//     555, 774, 1011, 1153),
//   * the registered block pool replacing IOBuf's allocator
//     (block_pool.cpp:39), and
//   * user memory carried as IOBuf user-data blocks with an lkey meta
//     (iobuf.h:250-254 in the reference).
//
// TPU redesign: instead of ibverbs QPs, the device fabric is PJRT.
//   * `PjrtApi` dlopens a PJRT plugin (libtpu, or the in-repo fake for
//     tests) and speaks the stable PJRT C API — no JAX, no Python.
//   * `PjrtClient` owns a PJRT_Client and its addressable devices.
//   * `PjrtEvent::FiberWait` parks the calling *fiber* on a PJRT event the
//     way bthread_fd_wait parks on epoll (reference src/bthread/fd.cpp):
//     the plugin's OnReady callback bumps a butex; the worker thread is
//     never blocked.
//   * `StageToDevice` DMAs an IOBuf's blocks into an HBM buffer without an
//     intermediate host copy (single-block payloads transfer straight from
//     the pooled socket block; the block is pinned by a ref until the
//     plugin's done-with-host-buffer event fires).
//   * `StageFromDevice` lands D2H output directly in a block that is
//     appended to an IOBuf as user data whose 64-bit meta is a
//     DeviceBufferRegistry handle — the lkey analog: upper layers can ship
//     the handle instead of bytes and keep the tensor resident in HBM.
#pragma once

#include <cstdint>
#include <memory>
#include <string>
#include <vector>

#include "base/iobuf.h"

// Opaque PJRT types (full definitions in third_party/pjrt/pjrt_c_api.h,
// included only by pjrt_device.cc).
typedef struct PJRT_Api PJRT_Api;
typedef struct PJRT_Client PJRT_Client;
typedef struct PJRT_Device PJRT_Device;
typedef struct PJRT_Event PJRT_Event;
typedef struct PJRT_Buffer PJRT_Buffer;

namespace brt {

// Loads a PJRT plugin shared object and resolves its API table.
// Thread-safe after construction; one per plugin path.
class PjrtApi {
 public:
  // nullptr on failure (missing file / no GetPjrtApi symbol); *error holds
  // the reason. The handle stays loaded for process lifetime.
  static const PjrtApi* Load(const std::string& plugin_path,
                             std::string* error);

  const PJRT_Api* raw() const { return api_; }
  int api_minor_version() const;

  // Human-readable message for a PJRT_Error, which is then destroyed.
  std::string ConsumeError(void* pjrt_error) const;

 private:
  PjrtApi() = default;
  const PJRT_Api* api_ = nullptr;
};

// A PJRT event bound to the fiber runtime.
class PjrtEvent {
 public:
  PjrtEvent(const PjrtApi* api, PJRT_Event* ev) : api_(api), ev_(ev) {}
  ~PjrtEvent();
  PjrtEvent(const PjrtEvent&) = delete;
  PjrtEvent& operator=(const PjrtEvent&) = delete;

  // Parks the calling fiber until the event fires (worker pthread keeps
  // running other fibers). Returns 0 or an errno-style code if the event
  // carries an error. Safe to call from non-fiber threads too (butex_wait
  // degrades to a futex wait).
  int FiberWait();

  // Blocks the calling OS THREAD (mutex/condvar; never touches the fiber
  // runtime). Required by callers holding per-thread state across the wait
  // — a parked fiber may resume on a different worker, which breaks e.g.
  // Python's ctypes GIL bookkeeping (PyGILState is per-OS-thread).
  int ThreadWait();

  // Dispatches on mode: thread_blocking ? ThreadWait() : FiberWait().
  int Wait(bool thread_blocking) {
    return thread_blocking ? ThreadWait() : FiberWait();
  }

  bool valid() const { return ev_ != nullptr; }

 private:
  const PjrtApi* api_;
  PJRT_Event* ev_;
};

// Registry of live device buffers addressable by 64-bit handles — the meta
// value carried in IOBuf user-data blocks (reference: lkey in
// append_user_data_with_meta, docs/en/rdma.md:44-46).
// Entries are refcounted: Pin() takes a reference for the duration of a DMA
// (or any other use across a blocking wait) so a concurrent Release() of the
// same handle — the advertised "ship the handle" pattern — cannot destroy
// the PJRT buffer out from under the user. Release() marks the handle dead
// (subsequent Lookup/Pin fail) and destroys the buffer once the last pin
// drops.
class DeviceBufferRegistry {
 public:
  // device_index / dtype record where the buffer lives and what it holds
  // (dtype = int(PjrtClient::DType), -1 unknown) so consumers that accept
  // shipped handles can validate placement before a launch.
  static uint64_t Register(const PjrtApi* api, PJRT_Buffer* buf,
                           int device_index = -1, int dtype = -1);
  // Placement metadata recorded at Register time. False if stale/dead.
  static bool Info(uint64_t handle, int* device_index, int* dtype);
  // Live buffer for the handle, or nullptr. Non-owning peek: the result is
  // only safe to use while the caller otherwise guarantees no concurrent
  // Release (use Pin/Unpin across blocking operations).
  static PJRT_Buffer* Lookup(uint64_t handle);
  // Takes a reference and returns the buffer (nullptr if stale/dead). Every
  // successful Pin must be paired with an Unpin.
  static PJRT_Buffer* Pin(uint64_t handle);
  // Drops a Pin reference; destroys the PJRT buffer if the handle was
  // Released and this was the last reference.
  static void Unpin(uint64_t handle);
  // Marks the handle dead and destroys the PJRT buffer once no pins remain.
  // False if stale.
  static bool Release(uint64_t handle);
};

class PjrtClient {
 public:
  // Plugin create option (becomes a PJRT_NamedValue).
  struct Option {
    std::string name;
    bool is_string = false;
    std::string str;
    int64_t i64 = 0;
    static Option String(std::string n, std::string v) {
      Option o;
      o.name = std::move(n);
      o.is_string = true;
      o.str = std::move(v);
      return o;
    }
    static Option Int(std::string n, int64_t v) {
      Option o;
      o.name = std::move(n);
      o.i64 = v;
      return o;
    }
  };

  struct Options {
    std::string plugin_path;  // empty: DefaultPjrtPluginPath()
    // Passed to PJRT_Client_Create as given; libtpu needs none.
    std::vector<Option> create_options;
  };

  // Creates a client over the plugin. nullptr on failure with *error set.
  static std::unique_ptr<PjrtClient> Create(const Options& opts,
                                            std::string* error);
  ~PjrtClient();

  const PjrtApi* api() const { return api_; }
  PJRT_Client* raw_client() const { return client_; }
  std::string platform_name() const;
  int addressable_device_count() const;
  PJRT_Device* addressable_device(int i) const;
  // What PJRT reports for addressable device i: its kind string (e.g.
  // "TPU v5 lite"; "" on error) and its global id (the value device
  // assignments are written in; -1 on error).
  std::string device_kind(int i) const;
  int device_id(int i) const;
  // Addressable index of the device PJRT says holds `buf` (-1 if unknown).
  int DeviceIndexOf(PJRT_Buffer* buf) const;

  // Element type for shaped staging (subset the fabric needs; mapped to
  // PJRT_Buffer_Type internally).
  enum class DType { kU8, kF32, kS32 };

  // DMAs `data` (treated as a 1-D u8 array — the RPC payload level) into
  // device memory on addressable device `device_index`. Zero host copies
  // for single-block IOBufs: the transfer reads straight from the block,
  // which stays pinned (ref held) until the plugin signals it is done with
  // the host memory. Multi-block IOBufs are coalesced into one staging
  // block first. Returns a DeviceBufferRegistry handle (0 on failure).
  uint64_t StageToDevice(const IOBuf& data, int device_index,
                         std::string* error);

  // Shaped variant for executable arguments: stages `data` as an array of
  // `dtype` with the given dims (byte size must match). Same zero-copy /
  // host-pin behavior as StageToDevice. `done_slot` (0: none) is the late
  // stamp (base/time.h) taken when the plugin is done with the host
  // memory: the transfer's end, usually after this call has returned.
  uint64_t StageToDeviceShaped(const IOBuf& data, int device_index,
                               DType dtype,
                               const std::vector<int64_t>& dims,
                               std::string* error, uint32_t done_slot = 0);

  // DMAs the device buffer behind `handle` back to host, landing the bytes
  // directly in a fresh block appended to `out` as user data with
  // meta=handle — no intermediate host copy, and the device buffer stays
  // alive (resident in HBM) until the handle is released. The calling
  // fiber parks while the DMA runs. Returns 0 or errno-style code.
  // stamps_ns (may be null; CLOCK_MONOTONIC ns): [0] the D2H landed in
  // the host block, [1] the layout repack is done, [2] bytes it moved (0:
  // the landing was row-major already).
  int StageFromDevice(uint64_t handle, IOBuf* out, std::string* error,
                      int64_t* stamps_ns = nullptr);

  // Synchronous convenience: device round trip (H2D then D2H), releasing
  // the device buffer afterwards. The fiber parks during both DMAs.
  int Roundtrip(const IOBuf& in, IOBuf* out, int device_index,
                std::string* error);

  // When true, DMA/execute completion waits block the calling OS thread
  // (PjrtEvent::ThreadWait) instead of parking the fiber. The C API sets
  // this for clients driven from Python: ctypes GIL state is
  // per-OS-thread, so a fiber that resumes on another worker would crash
  // the interpreter.
  void set_thread_wait(bool v) { thread_wait_ = v; }
  bool thread_wait() const { return thread_wait_; }

 private:
  PjrtClient() = default;
  // If `buf`'s on-device layout is an untiled non-row-major permutation
  // (what ToHostBuffer landed in `src`), returns a fresh pooled block
  // holding the dense row-major repack, releasing `src` and updating
  // *cap. Returns nullptr when the bytes are already row-major (or the
  // layout is unknown/tiled — left as-is).
  char* RepackDeviceLayout(PJRT_Buffer* buf, char* src, size_t n,
                           size_t* cap);
  const PjrtApi* api_ = nullptr;
  PJRT_Client* client_ = nullptr;
  std::vector<PJRT_Device*> addressable_;
  bool thread_wait_ = false;
};

// Default plugin path resolution: $BRT_PJRT_PLUGIN, else the libtpu wheel
// installed beside the first python3 on $PATH, else empty. Without a chip
// libtpu spends minutes retrying before client creation fails, so tests
// always name the fake plugin instead of coming here.
std::string DefaultPjrtPluginPath();

}  // namespace brt
