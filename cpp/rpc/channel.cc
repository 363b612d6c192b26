#include "rpc/channel.h"

#include "base/logging.h"
#include "base/time.h"
#include "rpc/client_protocol.h"
#include "rpc/compress.h"
#include "rpc/protocol_brt.h"
#include "rpc/span.h"
#include "transport/tls.h"

namespace brt {

namespace {

// Timer callbacks carry the fid by value: a late firing after the call ended
// hits a destroyed versioned id and is a no-op (never a dangling pointer).
void TimeoutFn(void* arg) {
  fid_error(fid_t(uintptr_t(arg)), ERPCTIMEDOUT);
}
void BackupFn(void* arg) {
  fid_error(fid_t(uintptr_t(arg)), EBACKUPREQUEST);
}

}  // namespace

int Channel::Init(const std::string& server_addr, const ChannelOptions* opts) {
  EndPoint ep;
  if (!EndPoint::parse(server_addr, &ep)) return EINVAL;
  return Init(ep, opts);
}

int Channel::InitTls() {
  if (!options_.use_ssl) return 0;
  TlsOptions to;
  to.verify_peer = options_.ssl_verify_peer;
  to.ca_file = options_.ssl_ca_file;
  to.alpn = options_.ssl_alpn;
  std::string err;
  tls_ctx_ = TlsContext::NewClient(to, &err);
  if (tls_ctx_ == nullptr) {
    BRT_LOG(ERROR) << "channel tls init failed: " << err;
    return EINVAL;
  }
  return 0;
}

int Channel::ResolveProtocol() {
  RegisterBuiltinClientProtocols();
  if (options_.protocol.empty() || options_.protocol == "brt_std") {
    proto_ = nullptr;
  } else {
    proto_ = FindClientProtocol(options_.protocol);
    if (proto_ == nullptr) {
      BRT_LOG(ERROR) << "unknown client protocol '" << options_.protocol
                     << "'";
      return EINVAL;
    }
  }
  return 0;
}

ConnectionType Channel::EffConnType(const Controller* cntl) const {
  // Out-of-range per-call values fall back to the channel default: a
  // bogus cast would be interpreted inconsistently across layers (the
  // socket map would hand back the SHARED multiplexed socket while
  // EndRPC's exclusive-socket disposal would SetFailed it, erroring
  // every unrelated in-flight call on the connection).
  ConnectionType t =
      cntl != nullptr && cntl->connection_type >= 0 &&
              cntl->connection_type <= int(ConnectionType::ADAPTIVE)
          ? ConnectionType(cntl->connection_type)
          : options_.connection_type;
  // ADAPTIVE (reference adaptive_connection_type.h): multiplexed or
  // pipelined protocols share one connection; the rest go exclusive.
  if (t == ConnectionType::ADAPTIVE) {
    t = (proto_ == nullptr || proto_->pipelined_safe)
            ? ConnectionType::SINGLE
            : ConnectionType::POOLED;
  }
  // Without a pipelining guarantee a shared multiplexed connection would
  // interleave concurrent callers' requests; exclusive POOLED connections
  // keep the one-in-flight-per-connection invariant.
  if (proto_ != nullptr && !proto_->pipelined_safe &&
      t == ConnectionType::SINGLE) {
    t = ConnectionType::POOLED;
  }
  return t;
}

int Channel::Init(const EndPoint& server, const ChannelOptions* opts) {
  if (opts) options_ = *opts;
  server_ = server;
  RegisterBrtProtocol();
  if (ResolveProtocol() != 0) return EINVAL;
  if (InitTls() != 0) return EINVAL;
  inited_ = true;
  return 0;
}

void Channel::CallMethod(const std::string& service, const std::string& method,
                         Controller* cntl, const IOBuf& request,
                         IOBuf* response, Closure done) {
  const int64_t timeout_ms =
      cntl->timeout_ms != INT64_MIN ? cntl->timeout_ms : options_.timeout_ms;
  const int max_retry =
      cntl->max_retry >= 0 ? cntl->max_retry : options_.max_retry;
  const int64_t backup_ms = cntl->backup_request_ms != INT64_MIN
                                ? cntl->backup_request_ms
                                : options_.backup_request_ms;
  const bool sync = !done;

  fid_t cid = 0;
  fid_create(&cid, cntl, Controller::HandleError);
  cntl->set_cid(cid);
  Controller::Call& c = cntl->call;
  c.cid = cid;
  c.issuer = this;
  c.response = response;
  c.done = std::move(done);
  c.start_us = monotonic_us();
  c.remaining_retries = max_retry;
  c.abs_deadline_us = timeout_ms < 0 ? -1 : c.start_us + timeout_ms * 1000;

  if (!cntl->caller_owns_span &&
      (cntl->trace_id != 0 || SpanShouldSample())) {
    auto* sp = new Span;
    sp->trace_id = cntl->trace_id ? cntl->trace_id : SpanRandomId();
    sp->span_id = SpanRandomId();
    sp->parent_span_id = cntl->span_id;  // the caller's span, if any
    sp->service = service;
    sp->method = method;
    sp->start_us = c.start_us;
    sp->start_real_us = realtime_us();
    sp->annotate("call started");
    cntl->trace_id = sp->trace_id;
    cntl->span_id = sp->span_id;
    c.span = sp;
  }
  c.request_meta.type = MetaType::REQUEST;
  c.request_meta.correlation_id = cid;
  c.request_meta.service = service;
  c.request_meta.method = method;
  c.request_meta.timeout_ms = timeout_ms < 0 ? 0 : uint32_t(timeout_ms);
  c.request_meta.attachment_size = cntl->request_attachment().size();
  c.request_meta.trace_id = cntl->trace_id;
  c.request_meta.span_id = cntl->span_id;
  c.request_meta.stream_id = cntl->pending_stream_id;
  const bool auth_failed =
      options_.auth != nullptr &&
      options_.auth->GenerateCredential(&c.request_meta.auth) != 0;
  c.request_body = request;  // shares blocks — no copy
  c.request_body.append(cntl->request_attachment());
  // Channel-default request compression when the call didn't choose —
  // an EFFECTIVE value like timeout/retry above, not a write-back (the
  // controller may be Reset and reused on a channel with no default).
  // Meta-signaled compression is a brt_std feature; foreign protocols
  // carry their own content encodings (http veneers set headers).
  const uint8_t compress = cntl->request_compress_type != 0
                               ? cntl->request_compress_type
                               : options_.request_compress_type;
  if (compress != 0 && proto_ == nullptr) {
    const CompressHandler* h = GetCompressHandler(compress);
    IOBuf packed;
    if (h != nullptr && h->compress(c.request_body, &packed)) {
      c.request_body = std::move(packed);
      c.request_meta.compress_type = compress;
    }
  }

  void* data = nullptr;
  if (fid_lock(cid, &data) != 0) {
    // Impossible for a fresh id; defend anyway.
    cntl->SetFailed(EINVAL, "fresh correlation id unusable");
    if (c.done) c.done();
    return;
  }
  if (!inited_) {
    cntl->SetFailed(EINVAL, "channel not initialized");
    cntl->EndRPC();
    return;
  }
  if (auth_failed) {
    // Fail locally: shipping a broken credential would burn a round trip
    // and retries just to learn EAUTH from the server.
    cntl->SetFailed(EAUTH, "GenerateCredential failed");
    cntl->EndRPC();
    return;
  }
  // Arm timers BEFORE the first attempt: a first attempt that fails
  // synchronously but retries successfully must still be covered by the
  // deadline (EndRPC cancels both timers on any termination).
  if (c.abs_deadline_us >= 0) {
    c.timeout_timer = timer_add(c.abs_deadline_us, TimeoutFn,
                                reinterpret_cast<void*>(uintptr_t(cid)));
  }
  if (backup_ms >= 0 && (timeout_ms < 0 || backup_ms < timeout_ms)) {
    c.backup_timer = timer_add(c.start_us + backup_ms * 1000, BackupFn,
                               reinterpret_cast<void*>(uintptr_t(cid)));
  }
  const int rc = IssueRPC(cntl);
  if (rc != 0) {
    // Route through the same serialized error funnel as async failures so
    // the retry policy applies uniformly (reference HandleSendFailed,
    // controller.cpp:998). The queued error fires on unlock.
    fid_error(cid, rc);
  }
  fid_unlock(cid);
  if (sync) fid_join(cid);
}

int Channel::SendAttempt(Controller* cntl, SocketUniquePtr& sock,
                         const EndPoint& ep, ConnectionType conn_type) {
  Controller::Call& c = cntl->call;
  // A retry attempt abandons the previous socket's response wait. On
  // exclusive (POOLED/SHORT) connections the superseded socket must also
  // be disposed of at EndRPC — it is not in the pool and nothing else
  // references it — but NOT yet: a backup request's primary may still
  // answer on it and win the hedge race.
  if (c.last_socket != INVALID_SOCKET_ID && c.last_socket != sock->id()) {
    SocketUniquePtr prev;
    if (Socket::Address(c.last_socket, &prev) == 0) {
      prev->RemoveWaiter(c.cid);
    }
    if (conn_type != ConnectionType::SINGLE) {
      c.superseded.push_back(c.last_socket);
    }
  }
  cntl->set_remote_side(ep);
  c.last_socket = sock->id();
  c.reply_consumed = false;  // refers to THIS attempt's socket
  c.conn_type = int(conn_type);
  c.conn_group = options_.connection_group;
  c.conn_tls = tls_ctx_.get();
  c.conn_proto = proto_;
  // Register for failure notification BEFORE the bytes leave: a socket that
  // dies after a successful Write must still error this call.
  sock->AddWaiter(c.cid);
  IOBuf frame;
  if (proto_ != nullptr) {
    uint64_t cut_hint = 0;
    const int prc =
        proto_->pack(&frame, cntl, c.request_meta, c.request_body,
                     &cut_hint);
    if (prc != 0) {
      cntl->SetFailed(prc, "cannot pack %s request", proto_->name);
      return prc;
    }
    // Queue position and wire position must match atomically (FIFO reply
    // matching); a write failure surfaces through fid_error(cid).
    return FifoCallEnqueue(sock.get(), c.cid, &frame, cut_hint);
  }
  IOBuf body = c.request_body;  // keep the original for retries
  PackFrame(&frame, c.request_meta, std::move(body));
  // A write failure surfaces through fid_error(cid) (Socket::Write
  // contract) and re-enters Controller::HandleError — report success here
  // so the funnel stays single-entry.
  sock->Write(&frame, c.cid);
  return 0;
}

int Channel::IssueRPC(Controller* cntl) {
  SocketUniquePtr sock;
  const ConnectionType ct = EffConnType(cntl);
  const int rc = GetOrNewSocket(server_, ct, &sock,
                                options_.connect_timeout_us,
                                options_.connection_group, tls_ctx_.get(),
                                options_.ssl_sni, proto_);
  if (rc != 0) {
    cntl->SetFailed(rc == ETIMEDOUT ? ECONNREFUSED : rc,
                    "fail to connect %s", server_.to_string().c_str());
    return rc ? rc : ECONNREFUSED;
  }
  return SendAttempt(cntl, sock, server_, ct);
}

}  // namespace brt
