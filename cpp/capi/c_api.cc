#include "capi/c_api.h"

#include <cstddef>
#include <cstdlib>
#include <cstring>
#include <memory>
#include <string>
#include <vector>

#include "base/time.h"
#include "capi/capi_internal.h"
#include "cluster/cluster_channel.h"
#include "cluster/remote_naming.h"
#include "fiber/fiber.h"
#include "fiber/sync.h"
#include "rpc/channel.h"
#include "rpc/server.h"
#include "rpc/span.h"
#include "transport/socket.h"

namespace {

using namespace brt;
using brt_capi::CChannel;
using brt_capi::CServer;
using brt_capi::CSession;
using brt_capi::HandleKind;

class CService : public Service {
 public:
  CService(brt_service_handler h, void* user) : handler_(h), user_(user) {}

  void CallMethod(const std::string& method, Controller* cntl,
                  const IOBuf& request, IOBuf* response,
                  Closure done) override {
    auto* sess = new CSession{cntl, response, std::move(done)};
    const std::string req = request.to_string();
    cntl->stamps.handler_ns = monotonic_ns();
    handler_(user_, method.c_str(), req.data(), req.size(), sess);
  }

 private:
  brt_service_handler handler_;
  void* user_;
};

// Exact multi-call fan-in (the ParallelChannel CountdownEvent shape,
// cluster/parallel_channel.*): N done-closures signal one waiter, which
// wakes exactly — never on a polling slice.  Refcounted so a group is
// safe to destroy while registered calls are still in flight (each
// incomplete registration holds a ref until its done-closure fires).
struct CCallGroup {
  FiberMutex mu;
  FiberCond cond;
  int total = 0;      // calls registered
  int completed = 0;  // calls finished
  int consumed = 0;   // completions handed out by wait_any
  std::atomic<int> refs{1};
};

void group_unref(CCallGroup* g) {
  if (g->refs.fetch_sub(1, std::memory_order_acq_rel) == 1) delete g;
}

void group_notify(CCallGroup* g) {
  g->mu.lock();
  ++g->completed;
  g->cond.notify_all();
  g->mu.unlock();
  group_unref(g);
}

// One in-flight async call (brt_channel_call_start).  The done closure
// marks completion (releasing any registered call groups), then signals
// the CountdownEvent; join/destroy wait on it before reading
// cntl/response or freeing, so completion never races the caller.
// brt_call_trace_next's ids, until this thread's next call takes them.
thread_local uint64_t tls_next_trace_id = 0;
thread_local uint64_t tls_next_span_id = 0;

void take_call_trace(Controller* cntl) {
  if (tls_next_trace_id == 0) return;
  cntl->trace_id = tls_next_trace_id;
  cntl->span_id = tls_next_span_id;
  cntl->caller_owns_span = true;
  tls_next_trace_id = tls_next_span_id = 0;
}

struct CCall {
  Controller cntl;
  IOBuf response;
  CountdownEvent done{1};
  FiberMutex group_mu;               // guards completed/groups
  bool completed = false;
  std::vector<CCallGroup*> groups;   // registered, not yet notified
};

}  // namespace

extern "C" {

void brt_init(int fiber_workers) { brt::fiber_init(fiber_workers); }

void* brt_server_new(void) {
  brt_capi::handle_inc(HandleKind::kServer);
  return new CServer;
}

int brt_server_add_service(void* server, const char* name,
                           brt_service_handler handler, void* user) {
  auto* s = static_cast<CServer*>(server);
  auto svc = std::make_unique<CService>(handler, user);
  int rc = s->server.AddService(svc.get(), name);
  if (rc == 0) s->services.push_back(std::move(svc));
  return rc;
}

int brt_server_start(void* server, const char* addr) {
  auto* s = static_cast<CServer*>(server);
  // Always pass the staged options: defaults are identical to a bare
  // Start, and brt_server_set_concurrency_limiter writes into them.
  return s->server.Start(std::string(addr), &s->opts);
}

int brt_server_set_concurrency_limiter(void* server, const char* name,
                                       int max_concurrency) {
  auto* s = static_cast<CServer*>(server);
  if (s->server.IsRunning()) return EPERM;
  s->opts.concurrency_limiter = name ? name : "";
  s->opts.max_concurrency = max_concurrency;
  return 0;
}

int brt_server_max_concurrency(void* server) {
  auto* l = static_cast<CServer*>(server)->server.limiter();
  return l ? l->max_concurrency() : 0;
}

int brt_server_add_naming_registry(void* server) {
  // Hosts the in-framework service registry (cluster/remote_naming.h) on
  // this server under "Naming", JSON-mapped so HTTP+JSON clients (the
  // Python tier) can Register/Watch with no binary codec.
  auto* s = static_cast<CServer*>(server);
  if (s->naming != nullptr) return EEXIST;
  s->naming = std::make_unique<NamingRegistryService>();
  const int rc = s->server.AddService(s->naming.get(), "Naming");
  if (rc != 0) {
    s->naming.reset();
    return rc;
  }
  NamingRegistryService::MapJsonMethods(&s->server);
  return 0;
}

int brt_server_port(void* server) {
  return static_cast<CServer*>(server)->server.listen_address().port;
}

void brt_server_stop(void* server) {
  auto* s = static_cast<CServer*>(server);
  s->server.Stop();
  s->server.Join();
}

void brt_server_destroy(void* server) {
  auto* s = static_cast<CServer*>(server);
  s->server.Stop();
  s->server.Join();
  delete s;
  brt_capi::handle_dec(HandleKind::kServer);
}

uint64_t brt_session_trace(void* session, uint64_t* parent_span_id,
                           int64_t* stamps) {
  Controller* cntl = static_cast<CSession*>(session)->cntl;
  if (parent_span_id != nullptr) *parent_span_id = cntl->parent_span_id;
  if (stamps != nullptr) {
    stamps[0] = cntl->stamps.first_byte_ns;
    stamps[1] = cntl->stamps.complete_ns;
    stamps[2] = cntl->stamps.dispatch_ns;
    stamps[3] = cntl->stamps.handler_ns;
  }
  return cntl->trace_id;
}

int64_t* brt_late_stamps(size_t* nslots) {
  static_assert(sizeof(std::atomic<int64_t>) == sizeof(int64_t),
                "a late stamp is read as a plain int64");
  *nslots = brt::kLateStampSlots;
  return reinterpret_cast<int64_t*>(brt::g_late_stamps);
}

void brt_call_trace_next(uint64_t trace_id, uint64_t span_id) {
  tls_next_trace_id = trace_id;
  tls_next_span_id = span_id;
}

namespace {

// The response is in its buffer (`copied` bytes of it by memcpy): what a
// tracing binding asked to know, before `done` sends it.
void note_responded(CSession* sess, size_t copied, int64_t* stamps_ns,
                    uint32_t written_slot) {
  RequestStamps& st = sess->cntl->stamps;
  st.written_slot = written_slot;
  if (stamps_ns != nullptr) {
    stamps_ns[0] = st.respond_ns;
    stamps_ns[1] = monotonic_ns();
    stamps_ns[2] = int64_t(copied);
  }
}

}  // namespace

void brt_session_respond(void* session, const void* data, size_t len,
                         int error_code, const char* error_text,
                         int64_t* stamps_ns, uint32_t written_slot) {
  auto* sess = static_cast<CSession*>(session);
  sess->cntl->stamps.respond_ns = monotonic_ns();
  size_t copied = 0;
  if (error_code != 0) {
    sess->cntl->SetFailed(error_code, "%s",
                          error_text ? error_text : "handler error");
  } else if (data != nullptr && len > 0) {
    sess->response->append(data, len);
    copied = len;
  }
  note_responded(sess, copied, stamps_ns, written_slot);
  Closure done = std::move(sess->done);
  delete sess;
  done();
}

void brt_session_respond_iobuf(void* session, const void* iobuf,
                               int error_code, const char* error_text,
                               int64_t* stamps_ns, uint32_t written_slot) {
  auto* sess = static_cast<CSession*>(session);
  auto* io = static_cast<const brt_capi::CIobuf*>(iobuf);
  sess->cntl->stamps.respond_ns = monotonic_ns();
  if (error_code != 0) {
    sess->cntl->SetFailed(error_code, "%s",
                          error_text ? error_text : "handler error");
  } else if (io != nullptr && !io->buf.empty()) {
    // Shares the iobuf's blocks into the response — no payload copy; a
    // borrowed (user-data) block stays pinned until the socket write
    // drops the last ref.
    sess->response->append(io->buf);
  }
  note_responded(sess, 0, stamps_ns, written_slot);  // blocks shared
  Closure done = std::move(sess->done);
  delete sess;
  done();
}

void* brt_channel_new(const char* addr, const char* lb, int64_t timeout_ms,
                      int max_retry) {
  brt::fiber_init(0);
  auto* c = new CChannel;
  ChannelOptions opts;
  opts.timeout_ms = timeout_ms;
  opts.max_retry = max_retry;
  const std::string a = addr;
  if (a.find("://") != std::string::npos) {
    auto cc = std::make_unique<ClusterChannel>();
    if (cc->Init(a, lb ? lb : "rr", &opts) != 0) {
      delete c;
      return nullptr;
    }
    c->channel = std::move(cc);
  } else {
    auto ch = std::make_unique<Channel>();
    if (ch->Init(a, &opts) != 0) {
      delete c;
      return nullptr;
    }
    c->channel = std::move(ch);
  }
  brt_capi::handle_inc(HandleKind::kChannel);
  return c;
}

int brt_channel_call(void* channel, const char* service, const char* method,
                     const void* req, size_t req_len, void** rsp,
                     size_t* rsp_len, char* errbuf, size_t errbuf_len) {
  auto* c = static_cast<CChannel*>(channel);
  Controller cntl;
  take_call_trace(&cntl);
  IOBuf request, response;
  if (req && req_len) request.append(req, req_len);
  c->channel->CallMethod(service, method, &cntl, request, &response,
                         nullptr);
  if (cntl.Failed()) {
    if (errbuf && errbuf_len) {
      snprintf(errbuf, errbuf_len, "%s", cntl.ErrorText().c_str());
    }
    return cntl.ErrorCode() ? cntl.ErrorCode() : -1;
  }
  const size_t n = response.size();
  void* buf = malloc(n ? n : 1);
  response.copy_to(buf, n);
  *rsp = buf;
  *rsp_len = n;
  return 0;
}

void brt_channel_destroy(void* channel) {
  if (channel == nullptr) return;
  delete static_cast<CChannel*>(channel);
  brt_capi::handle_dec(HandleKind::kChannel);
}

void* brt_channel_call_iobuf(void* channel, const char* service,
                             const char* method, const void* req_iobuf,
                             int* error_code, char* errbuf,
                             size_t errbuf_len) {
  auto* c = static_cast<CChannel*>(channel);
  Controller cntl;
  take_call_trace(&cntl);
  IOBuf request, response;
  if (req_iobuf != nullptr) {
    // Shares the request blocks (refcount bump): borrowed numpy-backed
    // blocks go to the socket without a copy and stay pinned until the
    // write drains.
    request.append(static_cast<const brt_capi::CIobuf*>(req_iobuf)->buf);
  }
  c->channel->CallMethod(service, method, &cntl, request, &response,
                         nullptr);
  if (cntl.Failed()) {
    if (errbuf && errbuf_len) {
      snprintf(errbuf, errbuf_len, "%s", cntl.ErrorText().c_str());
    }
    if (error_code != nullptr) {
      *error_code = cntl.ErrorCode() ? cntl.ErrorCode() : -1;
    }
    return nullptr;
  }
  if (error_code != nullptr) *error_code = 0;
  auto* out = new brt_capi::CIobuf;
  out->buf.swap(response);  // steal the wire blocks, no copy
  brt_capi::handle_inc(HandleKind::kIobuf);
  return out;
}

void* brt_channel_call_start(void* channel, const char* service,
                             const char* method, const void* req,
                             size_t req_len) {
  return brt_channel_call_start_opts(channel, service, method, req,
                                     req_len, INT64_MIN);
}

void* brt_channel_call_start_opts(void* channel, const char* service,
                                  const char* method, const void* req,
                                  size_t req_len, int64_t timeout_ms) {
  auto* c = static_cast<CChannel*>(channel);
  auto* call = new CCall;
  brt_capi::handle_inc(HandleKind::kCall);
  call->cntl.timeout_ms = timeout_ms;  // INT64_MIN inherits the channel
  take_call_trace(&call->cntl);
  IOBuf request;
  if (req && req_len) request.append(req, req_len);
  // The done closure runs exactly once, in a fiber, after cntl/response
  // are filled (including synchronous local failures, which invoke done
  // before CallMethod returns).  Group notification happens AFTER the
  // completion latch is signaled, so a waiter woken by the group always
  // observes brt_call_wait(call, 0) == 0 for the finished call.
  CCall* raw = call;
  c->channel->CallMethod(service, method, &call->cntl, request,
                         &call->response, [raw] {
                           raw->group_mu.lock();
                           raw->completed = true;
                           std::vector<CCallGroup*> gs;
                           gs.swap(raw->groups);
                           raw->group_mu.unlock();
                           raw->done.signal();  // last touch of raw
                           for (CCallGroup* g : gs) group_notify(g);
                         });
  return call;
}

void* brt_channel_call_start_iobuf(void* channel, const char* service,
                                   const char* method,
                                   const void* req_iobuf,
                                   int64_t timeout_ms) {
  auto* c = static_cast<CChannel*>(channel);
  auto* call = new CCall;
  brt_capi::handle_inc(HandleKind::kCall);
  call->cntl.timeout_ms = timeout_ms;  // INT64_MIN inherits the channel
  take_call_trace(&call->cntl);
  IOBuf request;
  if (req_iobuf != nullptr) {
    request.append(static_cast<const brt_capi::CIobuf*>(req_iobuf)->buf);
  }
  CCall* raw = call;
  c->channel->CallMethod(service, method, &call->cntl, request,
                         &call->response, [raw] {
                           raw->group_mu.lock();
                           raw->completed = true;
                           std::vector<CCallGroup*> gs;
                           gs.swap(raw->groups);
                           raw->group_mu.unlock();
                           raw->done.signal();  // last touch of raw
                           for (CCallGroup* g : gs) group_notify(g);
                         });
  return call;
}

void* brt_call_group_new(void) {
  brt_capi::handle_inc(HandleKind::kCallGroup);
  return new CCallGroup;
}

int brt_call_group_add(void* group, void* call) {
  auto* g = static_cast<CCallGroup*>(group);
  auto* c = static_cast<CCall*>(call);
  c->group_mu.lock();
  const bool already_done = c->completed;
  if (!already_done) {
    c->groups.push_back(g);
    g->refs.fetch_add(1, std::memory_order_relaxed);
  }
  c->group_mu.unlock();
  g->mu.lock();
  ++g->total;
  if (already_done) {
    ++g->completed;
    g->cond.notify_all();
  }
  g->mu.unlock();
  return 0;
}

int brt_call_group_wait(void* group, int64_t timeout_us) {
  auto* g = static_cast<CCallGroup*>(group);
  const int64_t deadline =
      timeout_us < 0 ? -1 : monotonic_us() + timeout_us;
  g->mu.lock();
  while (g->completed < g->total) {
    int64_t left = -1;
    if (deadline >= 0) {
      left = deadline - monotonic_us();
      if (left <= 0) {
        g->mu.unlock();
        return ETIMEDOUT;
      }
    }
    g->cond.wait(g->mu, left);
  }
  g->mu.unlock();
  return 0;
}

int brt_call_group_wait_any(void* group, int64_t timeout_us) {
  auto* g = static_cast<CCallGroup*>(group);
  const int64_t deadline =
      timeout_us < 0 ? -1 : monotonic_us() + timeout_us;
  g->mu.lock();
  while (g->completed <= g->consumed) {
    int64_t left = -1;
    if (deadline >= 0) {
      left = deadline - monotonic_us();
      if (left <= 0) {
        g->mu.unlock();
        return ETIMEDOUT;
      }
    }
    g->cond.wait(g->mu, left);
  }
  ++g->consumed;
  g->mu.unlock();
  return 0;
}

int brt_call_group_completed(void* group) {
  auto* g = static_cast<CCallGroup*>(group);
  g->mu.lock();
  const int n = g->completed;
  g->mu.unlock();
  return n;
}

void brt_call_group_destroy(void* group) {
  // The ABI handle is released here; the refcounted object itself may
  // outlive this until in-flight done-closures drop their refs.
  group_unref(static_cast<CCallGroup*>(group));
  brt_capi::handle_dec(HandleKind::kCallGroup);
}

int brt_call_wait(void* call, int64_t timeout_us) {
  return static_cast<CCall*>(call)->done.wait(timeout_us);
}

void brt_call_cancel(void* call) {
  // StartCancel feeds ECANCELEDRPC into the correlation-id error funnel;
  // the versioned fid makes a post-completion cancel a harmless no-op,
  // so this needs no coordination with join/destroy.
  static_cast<CCall*>(call)->cntl.StartCancel();
}

int brt_call_join(void* call, void** rsp, size_t* rsp_len, char* errbuf,
                  size_t errbuf_len) {
  auto* c = static_cast<CCall*>(call);
  c->done.wait();
  if (c->cntl.Failed()) {
    if (errbuf && errbuf_len) {
      snprintf(errbuf, errbuf_len, "%s", c->cntl.ErrorText().c_str());
    }
    return c->cntl.ErrorCode() ? c->cntl.ErrorCode() : -1;
  }
  const size_t n = c->response.size();
  void* buf = malloc(n ? n : 1);
  c->response.copy_to(buf, n);
  *rsp = buf;
  *rsp_len = n;
  return 0;
}

void* brt_call_join_iobuf(void* call, int* error_code, char* errbuf,
                          size_t errbuf_len) {
  auto* c = static_cast<CCall*>(call);
  c->done.wait();
  if (c->cntl.Failed()) {
    if (errbuf && errbuf_len) {
      snprintf(errbuf, errbuf_len, "%s", c->cntl.ErrorText().c_str());
    }
    if (error_code != nullptr) {
      *error_code = c->cntl.ErrorCode() ? c->cntl.ErrorCode() : -1;
    }
    return nullptr;
  }
  if (error_code != nullptr) *error_code = 0;
  auto* out = new brt_capi::CIobuf;
  out->buf.swap(c->response);  // steal the wire blocks, no copy
  brt_capi::handle_inc(HandleKind::kIobuf);
  return out;
}

void brt_call_destroy(void* call) {
  auto* c = static_cast<CCall*>(call);
  c->done.wait();
  delete c;
  brt_capi::handle_dec(HandleKind::kCall);
}

void brt_free(void* p) { free(p); }

int brt_debug_fail_connections(const char* addr) {
  EndPoint target;
  if (addr == nullptr || !EndPoint::parse(addr, &target)) return -1;
  std::vector<SocketId> all;
  Socket::ListSockets(&all);
  int failed = 0;
  for (SocketId sid : all) {
    SocketUniquePtr p;
    // Skip LISTEN sockets: a listener records its own listen address
    // as `remote`, and failing it would kill an in-process server's
    // accept path forever — the lever severs CONNECTIONS to the
    // address, it does not decommission the address.
    if (Socket::Address(sid, &p) == 0 && p->remote() == target &&
        !p->is_listener()) {
      p->SetFailed(ECONNRESET, "brt_debug_fail_connections(%s)", addr);
      ++failed;
    }
  }
  return failed;
}

}  // extern "C"

extern "C" {

void* brt_event_new(void) {
  brt_capi::handle_inc(HandleKind::kEvent);
  return new brt::CountdownEvent(1);
}

void brt_event_set(void* event) {
  static_cast<brt::CountdownEvent*>(event)->signal();
}

int brt_event_wait(void* event, int64_t timeout_us) {
  return static_cast<brt::CountdownEvent*>(event)->wait(timeout_us);
}

void brt_event_destroy(void* event) {
  delete static_cast<brt::CountdownEvent*>(event);
  brt_capi::handle_dec(HandleKind::kEvent);
}

}  // extern "C"

// ---- device staging (cpp/device/pjrt_device.h) ----

#include "device/block_pool.h"
#include "device/pjrt_device.h"
#include "device/pjrt_executable.h"

extern "C" {

void* brt_device_client_new(const char* plugin_path, char* errbuf,
                            size_t errbuf_len) {
  brt::PjrtClient::Options opts;
  if (plugin_path != nullptr) opts.plugin_path = plugin_path;
  std::string err;
  auto client = brt::PjrtClient::Create(opts, &err);
  if (client == nullptr) {
    if (errbuf && errbuf_len) snprintf(errbuf, errbuf_len, "%s", err.c_str());
    return nullptr;
  }
  // C-API clients are driven from Python: completion waits must block the
  // calling OS thread, never fiber-park — ctypes' GIL state is bound to
  // the OS thread, and a fiber resuming on another worker would corrupt it.
  client->set_thread_wait(true);
  brt_capi::handle_inc(brt_capi::HandleKind::kDeviceClient);
  return client.release();
}

int brt_device_count(void* client) {
  return static_cast<brt::PjrtClient*>(client)->addressable_device_count();
}

void brt_device_platform_name(void* client, char* buf, size_t buf_len) {
  if (buf == nullptr || buf_len == 0) return;
  snprintf(buf, buf_len, "%s",
           static_cast<brt::PjrtClient*>(client)->platform_name().c_str());
}

int brt_device_kind(void* client, int device_index, char* buf,
                    size_t buf_len) {
  auto* c = static_cast<brt::PjrtClient*>(client);
  if (device_index < 0 || device_index >= c->addressable_device_count()) {
    return EINVAL;
  }
  if (buf && buf_len) {
    snprintf(buf, buf_len, "%s", c->device_kind(device_index).c_str());
  }
  return 0;
}

int brt_device_buffer_device(void* client, uint64_t handle) {
  PJRT_Buffer* buf = brt::DeviceBufferRegistry::Pin(handle);
  if (buf == nullptr) return -1;
  const int index = static_cast<brt::PjrtClient*>(client)->DeviceIndexOf(buf);
  brt::DeviceBufferRegistry::Unpin(handle);
  return index;
}

uint64_t brt_device_stage(void* client, const void* data, size_t len,
                          int device_index, char* errbuf, size_t errbuf_len) {
  // Same single-contiguous-region discipline as brt_device_stage_shaped
  // below (one copy, one DMA source, caller's pointer never pinned).
  brt::IOBuf buf;
  size_t cap = 0;
  char* flat = static_cast<char*>(
      brt::DeviceBlockPool::singleton().Acquire(len ? len : 1, &cap));
  if (flat == nullptr) {
    if (errbuf && errbuf_len) snprintf(errbuf, errbuf_len, "oom staging");
    return 0;
  }
  memcpy(flat, data, len);
  buf.append_user_data(flat, len, brt::DeviceBlockPool::IOBufDeleter,
                       reinterpret_cast<void*>(uintptr_t(cap)));
  std::string err;
  uint64_t h = static_cast<brt::PjrtClient*>(client)->StageToDevice(
      buf, device_index, &err);
  if (h == 0 && errbuf && errbuf_len) {
    snprintf(errbuf, errbuf_len, "%s", err.c_str());
  }
  return h;
}

int brt_device_fetch(void* client, uint64_t handle, void** out,
                     size_t* out_len, char* errbuf, size_t errbuf_len,
                     int64_t* stamps_ns) {
  brt::IOBuf buf;
  std::string err;
  if (stamps_ns != nullptr) stamps_ns[0] = brt::monotonic_ns();
  int rc = static_cast<brt::PjrtClient*>(client)->StageFromDevice(
      handle, &buf, &err, stamps_ns != nullptr ? stamps_ns + 1 : nullptr);
  if (rc != 0) {
    if (errbuf && errbuf_len) snprintf(errbuf, errbuf_len, "%s", err.c_str());
    return rc;
  }
  const size_t n = buf.size();
  void* mem = malloc(n ? n : 1);
  if (mem == nullptr) {
    if (errbuf && errbuf_len) snprintf(errbuf, errbuf_len, "out of memory");
    return ENOMEM;
  }
  buf.copy_to(mem, n);
  if (stamps_ns != nullptr) {
    // StageFromDevice left [1] landed, [2] repacked, [3] repacked bytes
    stamps_ns[4] = stamps_ns[3];
    stamps_ns[3] = brt::monotonic_ns();
  }
  *out = mem;
  *out_len = n;
  return 0;
}

int brt_device_release(uint64_t handle) {
  return brt::DeviceBufferRegistry::Release(handle) ? 0 : EINVAL;
}

uint64_t brt_device_stage_shaped(void* client, const void* data, size_t len,
                                 int device_index, int dtype,
                                 const int64_t* dims, size_t ndims,
                                 char* errbuf, size_t errbuf_len,
                                 int64_t* stamps_ns, uint32_t done_slot) {
  if (dtype < 0 || dtype > 2) {
    if (errbuf && errbuf_len) snprintf(errbuf, errbuf_len, "bad dtype");
    return 0;
  }
  // One copy into a single registered region (NOT buf.append, which
  // splinters a 64MB stage into 8K pooled blocks — per-block overhead ×
  // thousands, then a second coalescing copy inside StageToDeviceShaped
  // because PJRT wants one contiguous host region). The caller's pointer
  // cannot be wrapped zero-copy: the DMA is async and the Python bytes
  // object may be freed the moment this call returns, while the pooled
  // region below is pinned by the transfer until its done event.
  brt::IOBuf buf;
  size_t cap = 0;
  if (stamps_ns != nullptr) stamps_ns[0] = brt::monotonic_ns();
  char* flat = static_cast<char*>(
      brt::DeviceBlockPool::singleton().Acquire(len ? len : 1, &cap));
  if (flat == nullptr) {
    if (errbuf && errbuf_len) snprintf(errbuf, errbuf_len, "oom staging");
    return 0;
  }
  memcpy(flat, data, len);
  if (stamps_ns != nullptr) stamps_ns[1] = brt::monotonic_ns();
  buf.append_user_data(flat, len, brt::DeviceBlockPool::IOBufDeleter,
                       reinterpret_cast<void*>(uintptr_t(cap)));
  std::string err;
  uint64_t h = static_cast<brt::PjrtClient*>(client)->StageToDeviceShaped(
      buf, device_index, brt::PjrtClient::DType(dtype),
      std::vector<int64_t>(dims, dims + ndims), &err, done_slot);
  if (h == 0 && errbuf && errbuf_len) {
    snprintf(errbuf, errbuf_len, "%s", err.c_str());
  }
  return h;
}

char* brt_mlir_module(const char* kind, int64_t p0, int64_t p1, int64_t p2) {
  std::string k(kind ? kind : ""), text;
  if (k == "add") {
    text = brt::MlirAddF32(size_t(p0));
  } else if (k == "reduce_sum") {
    text = brt::MlirReduceSumF32(size_t(p0));
  } else if (k == "all_reduce_sum") {
    text = brt::MlirAllReduceSumF32(size_t(p0), int(p1));
  } else if (k == "all_gather") {
    text = brt::MlirAllGatherF32(size_t(p0), int(p1));
  } else if (k == "gather_rows") {
    text = brt::MlirGatherRowsF32(size_t(p0), size_t(p1), size_t(p2));
  } else if (k == "scatter_sub") {
    text = brt::MlirScatterSubF32(size_t(p0), size_t(p1), size_t(p2));
  } else {
    return nullptr;
  }
  char* out = static_cast<char*>(malloc(text.size() + 1));
  if (out == nullptr) return nullptr;
  memcpy(out, text.c_str(), text.size() + 1);
  return out;
}

void* brt_device_compile(void* client, const char* mlir, int num_replicas,
                         int first_device, char* errbuf, size_t errbuf_len) {
  std::string err;
  auto exe = brt::PjrtExecutable::Compile(
      static_cast<brt::PjrtClient*>(client), mlir, num_replicas, &err,
      first_device);
  if (exe == nullptr) {
    if (errbuf && errbuf_len) snprintf(errbuf, errbuf_len, "%s", err.c_str());
    return nullptr;
  }
  brt_capi::handle_inc(brt_capi::HandleKind::kDeviceExecutable);
  return exe.release();
}

int brt_device_executable_num_outputs(void* exe) {
  return static_cast<brt::PjrtExecutable*>(exe)->num_outputs();
}

int brt_device_execute(void* exe, const uint64_t* args, size_t nargs,
                       size_t nreplicas, uint64_t* outs, size_t outs_cap,
                       char* errbuf, size_t errbuf_len) {
  auto* e = static_cast<brt::PjrtExecutable*>(exe);
  if (size_t(e->num_replicas()) != nreplicas) {
    if (errbuf && errbuf_len) {
      snprintf(errbuf, errbuf_len, "nreplicas != %d", e->num_replicas());
    }
    return EINVAL;
  }
  const size_t nouts = size_t(e->num_outputs());
  if (outs_cap < nreplicas * nouts) {
    if (errbuf && errbuf_len) snprintf(errbuf, errbuf_len, "outs too small");
    return EINVAL;
  }
  std::vector<std::vector<uint64_t>> arg_lists(nreplicas);
  for (size_t d = 0; d < nreplicas; ++d) {
    arg_lists[d].assign(args + d * nargs, args + (d + 1) * nargs);
  }
  std::vector<std::vector<uint64_t>> out_lists;
  std::string err;
  int rc = e->Execute(arg_lists, &out_lists, &err);
  if (rc != 0) {
    if (errbuf && errbuf_len) snprintf(errbuf, errbuf_len, "%s", err.c_str());
    return rc;
  }
  for (size_t d = 0; d < nreplicas; ++d) {
    for (size_t o = 0; o < nouts; ++o) {
      outs[d * nouts + o] = out_lists[d][o];
    }
  }
  return 0;
}

void brt_device_executable_destroy(void* exe) {
  delete static_cast<brt::PjrtExecutable*>(exe);
  brt_capi::handle_dec(brt_capi::HandleKind::kDeviceExecutable);
}

void brt_device_client_destroy(void* client) {
  delete static_cast<brt::PjrtClient*>(client);
  brt_capi::handle_dec(brt_capi::HandleKind::kDeviceClient);
}

}  // extern "C"
