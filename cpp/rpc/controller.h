// Per-RPC state machine for both client and server side.
// Parity target: reference src/brpc/controller.h:113 — deadline, retries,
// backup request, attachments, error code/text, cancellation; client-side
// completion funnel serialized by the correlation id (bthread_id /
// OnVersionedRPCReturned, controller.cpp:581), timeout via the timer thread
// (controller.cpp:576).
#pragma once

#include <atomic>
#include <functional>
#include <memory>
#include <string>
#include <vector>

#include "base/endpoint.h"
#include "base/iobuf.h"
#include "fiber/fiber_id.h"
#include "fiber/timer.h"
#include "rpc/brt_meta.h"
#include "rpc/errors.h"
#include "rpc/http_message.h"
#include "rpc/span.h"
#include "transport/socket.h"

namespace brt {

class Controller;
struct ClientReply;   // rpc/client_protocol.h
using Closure = std::function<void()>;

// Set by stream.cc: invoked (with the correlation id locked) when a
// response binds a client-created stream to its connection.
extern void (*g_stream_connect_hook)(Controller*);

// Implemented by Channel and the combo channels: (re-)issues the packed
// request for one attempt. Called with the correlation id LOCKED.
class CallIssuer {
 public:
  virtual ~CallIssuer() = default;
  virtual int IssueRPC(Controller* cntl) = 0;
};

class Controller {
 public:
  Controller() = default;
  ~Controller();
  Controller(const Controller&) = delete;
  Controller& operator=(const Controller&) = delete;

  // ---- options (effective for the next call through this controller) ----
  // <0 means "inherit channel option"; timeout -1 after inherit = no deadline.
  int64_t timeout_ms = INT64_MIN;
  int max_retry = -1;
  int64_t backup_request_ms = INT64_MIN;
  // Per-call connection-type override (reference
  // Controller::set_connection_type): -1 inherits the channel's;
  // ConnectionType::ADAPTIVE resolves per protocol. Protocols without a
  // pipelining guarantee still upgrade SINGLE to POOLED.
  int connection_type = -1;

  // ---- error state ----
  void SetFailed(int code, const char* fmt = nullptr, ...);
  bool Failed() const { return error_code_ != 0; }
  int ErrorCode() const { return error_code_; }
  const std::string& ErrorText() const { return error_text_; }

  // ---- payload extras ----
  IOBuf& request_attachment() { return request_attachment_; }
  IOBuf& response_attachment() { return response_attachment_; }

  // ---- introspection ----
  EndPoint remote_side() const { return remote_side_; }
  EndPoint local_side() const { return local_side_; }
  int64_t latency_us() const { return latency_us_; }
  fid_t call_id() const { return cid_.load(std::memory_order_acquire); }
  int retried_count() const { return retried_; }
  bool has_backup_request() const { return backup_fired_; }

  // Requests cancellation of the in-flight call; completion (done / sync
  // wakeup) still happens exactly once. Safe from any thread.
  void StartCancel() {
    // cid_ is atomic: cancel may race the issuing thread's set_cid
    // (cancel-before-issue reads 0 and is a no-op; the versioned fid makes
    // a stale id harmless).
    const fid_t id = cid_.load(std::memory_order_acquire);
    if (id) fid_error(id, ECANCELEDRPC);
  }

  // Resets error/latency state so the controller can be reused for another
  // call (reference Controller::Reset).
  void Reset();

  // Consistent-hashing key for "c_murmurhash" load balancers (reference
  // Controller::set_request_code).
  uint64_t request_code = 0;

  // Compression (rpc/compress.h): client sets request_compress_type before
  // the call; servers answer with response_compress_type (defaults to the
  // request's — reference Controller::set_request_compress_type).
  uint8_t request_compress_type = 0;
  uint8_t response_compress_type = 0;

  // ---- streaming (rpc/stream.h; reference stream.cpp rides stream
  // settings on the RPC meta) ----
  uint64_t pending_stream_id = 0;   // client: set by StreamCreate
  uint64_t accepted_stream_id = 0;  // server: set by StreamAccept
  uint64_t peer_stream_id = 0;      // learned from the peer's meta
  SocketId stream_socket = 0;       // connection the stream binds to

  // ---- tracing (rpcz span propagation, reference span.h:47) ----
  uint64_t trace_id = 0;
  uint64_t span_id = 0;
  uint64_t parent_span_id = 0;
  // Client: the caller records this call's span itself under `span_id`
  // (a binding's tree); the ids go on the wire as they are and the
  // channel opens no native span of its own.
  bool caller_owns_span = false;
  // Server: where this request was, and when (span.h).
  RequestStamps stamps;

  // ---- http-protocol calls (ChannelOptions.protocol = "http") ----
  // Request line + headers out, status + headers back (reference
  // Controller::http_request()/http_response(), controller.h:113).
  // Lazily created; both survive Reset-less reuse of the controller.
  HttpMessage* http_request();
  HttpMessage* http_response();

  // ---- redis-protocol calls (ChannelOptions.protocol = "redis") ----
  // The reply parsed once by the wire cutter (finding a RESP frame
  // boundary IS a parse); veneers consume this instead of re-parsing the
  // raw bytes in the response IOBuf.
  std::shared_ptr<struct RedisReply> redis_reply;

  // ================= internal (Channel / protocol / Server) =================
  struct Call {
    fid_t cid = 0;
    CallIssuer* issuer = nullptr;
    IOBuf request_body;            // retained for retries/backup
    RpcMeta request_meta;          // cid/service/method prefilled
    IOBuf* response = nullptr;     // user output
    Closure done;                  // empty = synchronous call
    int64_t abs_deadline_us = -1;  // monotonic
    int64_t start_us = 0;
    int remaining_retries = 0;
    TimerId timeout_timer = kInvalidTimerId;
    TimerId backup_timer = kInvalidTimerId;
    SocketId last_socket = INVALID_SOCKET_ID;
    int conn_type = 0;   // ConnectionType; POOLED sockets return on success
    // True once a COMPLETE reply was cut off last_socket for this attempt
    // — the connection is aligned even if the reply carried an error
    // (EHTTP 404, server-reported failure), so a POOLED socket can go
    // back to the freelist instead of being torn down. Reset per attempt.
    bool reply_consumed = false;
    int conn_group = 0;  // SocketMap group the socket came from
    class TlsContext* conn_tls = nullptr;  // SocketMap TLS key part
    // SocketMap protocol key part (null = brt_std/InputMessenger conns).
    const struct ClientProtocol* conn_proto = nullptr;
    // Exclusive (POOLED/SHORT) sockets of earlier attempts this call
    // superseded (retry / backup request). Disposed of at EndRPC: pooled
    // back when healthy (their FIFO queue entry keeps reply alignment for
    // the next borrower), failed otherwise. Without this they would leak
    // — they are not in any pool and nothing else references them.
    std::vector<SocketId> superseded;
    // Cluster layer: endpoints already tried this call (reference
    // excluded_servers.h), and an end-of-call hook for LB feedback /
    // circuit breaker (reference LoadBalancer::Feedback +
    // CircuitBreaker::OnCallEnd).
    std::vector<EndPoint> excluded;
    void (*on_end)(Controller*, void*) = nullptr;
    void* on_end_arg = nullptr;
    bool attempt_pending = false;  // a selected attempt awaits feedback
    Span* span = nullptr;          // rpcz client span (sampled)
    // Sub-call bookkeeping for combo channels (parallel_channel.cpp:46).
    void* parent_done = nullptr;
    int sub_index = -1;
  };
  Call call;

  // fid on_error handler: serializes timeout / cancel / socket-failure /
  // backup-request events (reference OnVersionedRPCReturned).
  static int HandleError(fid_t id, void* data, int error_code);

  // Response arrival (id already locked by the caller).
  void OnResponse(RpcMeta&& meta, IOBuf&& body);

  // Foreign-protocol reply arrival (FIFO matcher, client_protocol.cc;
  // id already locked by the caller).
  void OnForeignReply(ClientReply&& reply);

  // Finalizes: destroys the id, records latency, runs done / wakes joiner.
  // Id must be locked; consumed by this call.
  void EndRPC();

  void set_remote_side(const EndPoint& ep) { remote_side_ = ep; }

  // Pooled per-request user data (server-side; nullptr without a
  // DataFactory — reference Controller::session_local_data()).
  void* session_local_data() const { return session_local_data_; }
  void set_session_local_data(void* d) { session_local_data_ = d; }

  // Set by CreateProgressiveAttachment (rpc/progressive_attachment.h);
  // consumed by the HTTP/1.1 front-end to switch the response to chunked
  // streaming. shared_ptr<ProgressiveAttachment> under the hood.
  std::shared_ptr<void> progressive_attachment;
  void set_local_side(const EndPoint& ep) { local_side_ = ep; }
  void set_latency(int64_t us) { latency_us_ = us; }
  void set_cid(fid_t id) { cid_.store(id, std::memory_order_release); }

  // Server side: accounting cookie (MethodStatus*), response meta basis.
  void* server_cookie = nullptr;
  uint64_t server_cid = 0;

 private:
  int error_code_ = 0;
  std::string error_text_;
  IOBuf request_attachment_;
  IOBuf response_attachment_;
  std::unique_ptr<HttpMessage> http_request_;
  std::unique_ptr<HttpMessage> http_response_;
  void* session_local_data_ = nullptr;
  EndPoint remote_side_;
  EndPoint local_side_;
  int64_t latency_us_ = 0;
  int retried_ = 0;
  bool backup_fired_ = false;
  std::atomic<fid_t> cid_{0};

  friend class Channel;
};

}  // namespace brt
