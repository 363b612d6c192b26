// The brt_std wire protocol plugged into the InputMessenger.
// Server path mirrors reference ProcessRpcRequest
// (policy/baidu_rpc_protocol.cpp:327): concurrency check → find service →
// user CallMethod in this fiber → done sends the response via the wait-free
// Socket::Write. Client path mirrors ProcessRpcResponse (:584): lock the
// correlation id, hand the frame to the Controller (which owns the
// retry/timeout/backup race resolution).
#include "rpc/progressive_attachment.h"
#include "rpc/protocol_brt.h"

#include <mutex>

#include "base/flags.h"
#include "base/logging.h"
#include "base/time.h"
#include "rpc/compress.h"
#include "rpc/controller.h"
#include "rpc/rpc_dump.h"
#include "fiber/usercode_pool.h"
#include "rpc/server.h"
#include "rpc/span.h"
#include "transport/input_messenger.h"

namespace brt {

uint32_t FLAGS_max_body_size = 64u * 1024 * 1024;

namespace {

std::atomic<StreamFrameHandler> g_stream_handler{nullptr};
std::atomic<RequestDropHook> g_drop_hook{nullptr};

constexpr size_t kHeaderLen = 12;

ParseResult BrtParse(IOBuf* source, IOBuf* msg, Socket*) {
  if (source->size() < kHeaderLen) return ParseResult::NOT_ENOUGH_DATA;
  char hdr[kHeaderLen];
  source->copy_to(hdr, kHeaderLen);
  if (memcmp(hdr, "BRT1", 4) != 0) return ParseResult::TRY_OTHER;
  uint32_t mlen = (uint8_t(hdr[5]) << 16) |
                  (uint8_t(hdr[6]) << 8) | uint8_t(hdr[7]);
  uint32_t blen = (uint8_t(hdr[8]) << 24) | (uint8_t(hdr[9]) << 16) |
                  (uint8_t(hdr[10]) << 8) | uint8_t(hdr[11]);
  if (mlen > 64 * 1024) return ParseResult::ERROR;
  if (blen > FLAGS_max_body_size) return ParseResult::ERROR;
  const size_t total = kHeaderLen + size_t(mlen) + blen;
  if (source->size() < total) return ParseResult::NOT_ENOUGH_DATA;
  source->cutn(msg, total);
  return ParseResult::OK;
}

// One in-flight server-side request (freed by the done closure).
struct RpcSession {
  Controller cntl;
  IOBuf request;
  IOBuf response;
  SocketId sock = INVALID_SOCKET_ID;
  uint64_t cid = 0;
  Server* server = nullptr;
  MethodStatus* mstatus = nullptr;
  int64_t start_us = 0;
  Span* span = nullptr;  // rpcz (sampled or trace-propagated)
};

// What rides a traced response's write request until its last byte is
// handed to the socket: the native rpcz span (or null) and the late-stamp
// slot of a binding that traces the request (or 0).
struct SendTrace {
  Span* span;
  uint32_t written_slot;
};

void OnResponseWritten(void* arg, int /*error*/) {
  auto* t = static_cast<SendTrace*>(arg);
  stamp_late(t->written_slot);
  if (t->span != nullptr) {
    const int64_t now_us = monotonic_us();
    t->span->annotations.emplace_back(now_us, "written");
    t->span->end_us = now_us;
    SpanSubmit(std::move(*t->span));
    delete t->span;
  }
  delete t;
}

void SendResponse(RpcSession* sess) {
  // brt_std cannot stream a response: a progressive attachment the
  // handler created must fail loudly for its writer, not buffer forever.
  AbortProgressiveIfAny(&sess->cntl);
  const int64_t now_ns = monotonic_ns();
  const int64_t lat = now_ns / 1000 - sess->start_us;
  RequestStamps& st = sess->cntl.stamps;
  if (st.respond_ns == 0) st.respond_ns = now_ns;
  SendTrace* trace = nullptr;
  if (sess->span != nullptr || st.written_slot != 0) {
    trace = new SendTrace{sess->span, st.written_slot};
  }
  if (sess->span != nullptr) {
    // The request's phase boundaries, as stamped where they happened.
    Span* sp = sess->span;
    sp->annotations.emplace_back(st.complete_ns / 1000, "frame complete");
    sp->annotations.emplace_back(st.dispatch_ns / 1000, "dispatched");
    sp->annotations.emplace_back(st.respond_ns / 1000, "respond");
    sp->error_code = sess->cntl.ErrorCode();
    sess->span = nullptr;  // the write request owns it now
  }
  RpcMeta meta;
  meta.type = MetaType::RESPONSE;
  meta.correlation_id = sess->cid;
  meta.error_code = sess->cntl.ErrorCode();
  if (meta.error_code) meta.error_text = sess->cntl.ErrorText();
  meta.attachment_size = sess->cntl.response_attachment().size();
  meta.stream_id = sess->cntl.accepted_stream_id;
  IOBuf body;
  body.append(std::move(sess->response));
  body.append(std::move(sess->cntl.response_attachment()));
  if (sess->cntl.response_compress_type != 0 && meta.error_code == 0) {
    const CompressHandler* h =
        GetCompressHandler(sess->cntl.response_compress_type);
    IOBuf packed;
    if (h != nullptr && h->compress(body, &packed)) {
      body = std::move(packed);
      meta.compress_type = sess->cntl.response_compress_type;
    }
  }
  IOBuf frame;
  PackFrame(&frame, meta, std::move(body));
  SocketUniquePtr ptr;
  if (Socket::Address(sess->sock, &ptr) == 0) {
    ptr->Write(&frame, 0, trace != nullptr ? OnResponseWritten : nullptr,
               trace);
  } else if (trace != nullptr) {
    OnResponseWritten(trace, ECONNRESET);
  }
  if (sess->mstatus) sess->mstatus->OnResponded(meta.error_code, lat);
  if (sess->server) {
    sess->server->ReturnSessionData(sess->cntl.session_local_data());
    sess->server->OnResponseSent(meta.error_code, lat);
    sess->server->requests_processed.fetch_add(1, std::memory_order_relaxed);
    // Last touch: after this decrement Join() may return and the Server
    // may be destroyed.
    sess->server->OnRequestDone();
  }
  delete sess;
}

// Failure answer without a session (bad request / no server / limits).
void SendErrorResponse(SocketId sock, uint64_t cid, int code,
                       const char* text) {
  RpcMeta meta;
  meta.type = MetaType::RESPONSE;
  meta.correlation_id = cid;
  meta.error_code = code;
  meta.error_text = text ? text : RpcErrorText(code);
  IOBuf frame;
  PackFrame(&frame, meta, IOBuf());
  SocketUniquePtr ptr;
  if (Socket::Address(sock, &ptr) == 0) ptr->Write(&frame);
}

void ProcessRequest(RpcMeta&& meta, IOBuf&& body, SocketId sock,
                    Socket* s, const RecvStamps& recv) {
  auto* server = static_cast<Server*>(s->user());
  if (!server || !server->IsRunning()) {
    SendErrorResponse(sock, meta.correlation_id, ELOGOFF, nullptr);
    return;
  }
  // Fault-injection drop: parsed, then silently discarded — no response,
  // no accounting (OnRequestArrived has not run), the client sees only
  // its own deadline expire.
  RequestDropHook drop = g_drop_hook.load(std::memory_order_acquire);
  if (drop != nullptr &&
      drop(meta.service.c_str(), meta.method.c_str(),
           server->listen_address().port) != 0) {
    return;
  }
  // Credential gate (reference authenticator.h:58): verified before any
  // resource is committed to the request.
  if (server->options().auth != nullptr &&
      server->options().auth->VerifyCredential(meta.auth, s->remote()) !=
          0) {
    SendErrorResponse(sock, meta.correlation_id, EAUTH, nullptr);
    return;
  }
  if (!server->OnRequestArrived()) {
    SendErrorResponse(sock, meta.correlation_id, ELIMIT, nullptr);
    return;
  }
  Service* svc = server->FindService(meta.service);
  if (!svc) {
    server->OnRequestDone();
    SendErrorResponse(sock, meta.correlation_id, ENOSERVICE, nullptr);
    return;
  }
  MethodStatus* ms = server->GetMethodStatus(meta.service, meta.method);
  if (!ms->OnRequested()) {
    server->OnRequestDone();
    SendErrorResponse(sock, meta.correlation_id, ELIMIT, nullptr);
    return;
  }
  auto* sess = new RpcSession;
  // Interceptor hook (reference interceptor.h:26): may veto the call.
  if (server->options().interceptor) {
    int ec = EREJECT;
    sess->cntl.set_remote_side(s->remote());
    if (!server->options().interceptor(&sess->cntl, meta.service,
                                       meta.method, &ec)) {
      ms->OnResponded(ec, 0);
      server->OnRequestDone();
      delete sess;
      SendErrorResponse(sock, meta.correlation_id, ec, nullptr);
      return;
    }
  }
  sess->cntl.set_session_local_data(server->BorrowSessionData());
  sess->sock = sock;
  sess->cid = meta.correlation_id;
  sess->server = server;
  sess->mstatus = ms;
  sess->start_us = monotonic_us();
  sess->cntl.stamps.first_byte_ns = recv.first_byte_ns;
  sess->cntl.stamps.complete_ns = recv.complete_ns;
  sess->cntl.set_remote_side(s->remote());
  sess->cntl.trace_id = meta.trace_id;
  sess->cntl.parent_span_id = meta.span_id;
  sess->cntl.peer_stream_id = meta.stream_id;  // client wants a stream
  sess->cntl.stream_socket = sock;
  if (meta.trace_id != 0 || SpanShouldSample()) {
    // reference span.cpp: the server span is a child of the client's span;
    // ids ride the protocol meta (SURVEY §5.1)
    auto* sp = new Span;
    sp->trace_id = meta.trace_id ? meta.trace_id : SpanRandomId();
    sp->span_id = SpanRandomId();
    sp->parent_span_id = meta.span_id;
    sp->server_side = true;
    sp->service = meta.service;
    sp->method = meta.method;
    sp->remote = s->remote();
    // from the frame's first byte, not from where the parse ended
    sp->start_us = recv.first_byte_ns / 1000;
    sp->start_real_us = realtime_us() - (sess->start_us - sp->start_us);
    sess->span = sp;
    sess->cntl.trace_id = sp->trace_id;
    sess->cntl.span_id = sp->span_id;
  }
  if (meta.compress_type != 0) {
    const CompressHandler* h = GetCompressHandler(meta.compress_type);
    IOBuf plain;
    if (h == nullptr || !h->decompress(body, &plain)) {
      server->ReturnSessionData(sess->cntl.session_local_data());
      ms->OnResponded(EREQUEST, 0);
      server->OnRequestDone();  // last touch (Join may return after this)
      delete sess;
      SendErrorResponse(sock, meta.correlation_id, EREQUEST,
                        "cannot decompress request");
      return;
    }
    body = std::move(plain);
    sess->cntl.request_compress_type = meta.compress_type;
    sess->cntl.response_compress_type = meta.compress_type;
  }
  if (RpcDumpWanted()) {
    RpcDumpRecord(meta, body);  // decompressed body, pre-split
  }
  // Split payload / attachment.
  const size_t att = meta.attachment_size;
  const size_t payload = body.size() - att;
  body.cutn(&sess->request, payload);
  body.cutn(&sess->cntl.request_attachment(), att);
  const std::string method = std::move(meta.method);
  if (server->options().usercode_in_pthread) {
    // Blocking user code runs on the backup pthread pool so it cannot
    // starve the fiber workers driving IO
    // (reference details/usercode_backup_pool.cpp:37).
    UsercodePool::singleton().Run([svc, method, sess] {
      sess->cntl.stamps.dispatch_ns = monotonic_ns();
      svc->CallMethod(method, &sess->cntl, sess->request, &sess->response,
                      [sess] { SendResponse(sess); });
    });
    return;
  }
  sess->cntl.stamps.dispatch_ns = monotonic_ns();
  svc->CallMethod(method, &sess->cntl, sess->request, &sess->response,
                  [sess] { SendResponse(sess); });
}

void ProcessResponse(RpcMeta&& meta, IOBuf&& body) {
  const fid_t cid = meta.correlation_id;
  void* data = nullptr;
  if (fid_lock(cid, &data) != 0) {
    // Late response after timeout/cancel, or the loser of a backup-request
    // race: silently dropped (reference controller.cpp:581 EINVAL path).
    return;
  }
  static_cast<Controller*>(data)->OnResponse(std::move(meta), std::move(body));
}

void BrtProcess(IOBuf&& msg, SocketId sock) {
  const RecvStamps recv = CurrentRecvStamps();  // first: see the header
  RpcMeta meta;
  IOBuf body;
  const int rc = ParseFrame(&msg, &meta, &body);
  SocketUniquePtr ptr;
  if (Socket::Address(sock, &ptr) != 0) return;
  if (rc != 0) {
    ptr->SetFailed(EBADMSG, "malformed brt frame");
    return;
  }
  switch (meta.type) {
    case MetaType::REQUEST:
      ProcessRequest(std::move(meta), std::move(body), sock, ptr.get(),
                     recv);
      break;
    case MetaType::RESPONSE:
      ProcessResponse(std::move(meta), std::move(body));
      break;
    case MetaType::STREAM: {
      StreamFrameHandler h = g_stream_handler.load(std::memory_order_acquire);
      if (h) h(std::move(meta), std::move(body), sock);
      break;
    }
  }
}

// Stream frames (header kind byte == 1) must be handed over in arrival
// order; requests/responses fan out to fibers.
bool BrtIsOrdered(const IOBuf& msg) {
  char hdr[5];
  if (msg.copy_to(hdr, 5) < 5) return false;
  return hdr[4] == 1;
}

int g_proto_index = -1;

}  // namespace

void SetStreamFrameHandler(StreamFrameHandler h) {
  g_stream_handler.store(h, std::memory_order_release);
}

void SetRequestDropHook(RequestDropHook h) {
  g_drop_hook.store(h, std::memory_order_release);
}

int RegisterBrtProtocol() {
  static std::once_flag once;
  std::call_once(once, [] {
    RegisterFlag("max_body_size", &FLAGS_max_body_size,
                 "largest accepted rpc frame body in bytes");
    Protocol p;
    p.name = "brt_std";
    p.parse = BrtParse;
    p.process = BrtProcess;
    p.is_ordered = BrtIsOrdered;
    g_proto_index = RegisterProtocol(p);
  });
  return g_proto_index;
}

}  // namespace brt
