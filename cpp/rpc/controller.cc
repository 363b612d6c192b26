#include "rpc/controller.h"

#include <cstdarg>
#include <cstdio>
#include <cstring>

#include "base/time.h"
#include "rpc/client_protocol.h"
#include "rpc/compress.h"
#include "rpc/http_message.h"
#include "rpc/socket_map.h"

namespace brt {

const char* RpcErrorText(int code) {
  switch (code) {
    case ENOSERVICE: return "service not found";
    case ENOMETHOD: return "method not found";
    case EREQUEST: return "malformed request";
    case ETOOMANYFAILS: return "too many sub-call failures";
    case EBACKUPREQUEST: return "backup request";
    case ERPCTIMEDOUT: return "rpc timed out";
    case EFAILEDSOCKET: return "connection broken";
    case EOVERCROWDED: return "too many buffered writes";
    case EINTERNAL: return "server internal error";
    case ERESPONSE: return "malformed response";
    case ELOGOFF: return "server is stopping";
    case ELIMIT: return "concurrency limit reached";
    case ECANCELEDRPC: return "rpc canceled";
    case EAUTH: return "authentication failed";
    case EREJECT: return "rejected by interceptor";
    case EHTTP: return "non-2xx http response";
    default: return strerror(code);
  }
}

void (*g_stream_connect_hook)(Controller*) = nullptr;

Controller::~Controller() = default;

HttpMessage* Controller::http_request() {
  if (!http_request_) http_request_ = std::make_unique<HttpMessage>();
  return http_request_.get();
}

HttpMessage* Controller::http_response() {
  if (!http_response_) http_response_ = std::make_unique<HttpMessage>();
  return http_response_.get();
}

void Controller::SetFailed(int code, const char* fmt, ...) {
  error_code_ = code ? code : EINTERNAL;
  if (fmt) {
    char buf[256];
    va_list ap;
    va_start(ap, fmt);
    vsnprintf(buf, sizeof(buf), fmt, ap);
    va_end(ap);
    error_text_ = buf;
  } else {
    error_text_ = RpcErrorText(error_code_);
  }
}

void Controller::Reset() {
  progressive_attachment.reset();
  http_request_.reset();
  http_response_.reset();
  redis_reply.reset();
  error_code_ = 0;
  error_text_.clear();
  request_attachment_.clear();
  response_attachment_.clear();
  latency_us_ = 0;
  retried_ = 0;
  backup_fired_ = false;
  cid_.store(0, std::memory_order_release);
  // Per-call option overrides revert to "inherit the channel's" as a
  // group — resetting some but not others would surprise reuse-heavy
  // clients.
  timeout_ms = INT64_MIN;
  max_retry = -1;
  backup_request_ms = INT64_MIN;
  request_compress_type = 0;
  response_compress_type = 0;
  request_code = 0;
  connection_type = -1;
  call = Call();
  trace_id = span_id = parent_span_id = 0;
  caller_owns_span = false;
  stamps = RequestStamps();
}

namespace {

// Errors that justify another attempt (reference DefaultRetryPolicy,
// retry_policy.cpp: EFAILEDSOCKET/EHOSTDOWN/ELOGOFF and connect errnos).
bool Retryable(int err) {
  switch (err) {
    case EFAILEDSOCKET:
    case ELOGOFF:
    case EOVERCROWDED:
    case ECONNREFUSED:
    case ECONNRESET:
    case EPIPE:
    case EHOSTDOWN:
    case EHOSTUNREACH:
    case ENETUNREACH:
      return true;
    default:
      return false;
  }
}

}  // namespace

int Controller::HandleError(fid_t id, void* data, int error_code) {
  auto* cntl = static_cast<Controller*>(data);
  Controller::Call& c = cntl->call;
  const int64_t now = monotonic_us();

  if (error_code == EBACKUPREQUEST) {
    // Hedge: fire a second attempt, keep waiting for whichever response
    // arrives first (reference controller.cpp:337, docs/en/backup_request.md).
    // A failed backup issue must not poison the still-pending primary call:
    // clear any error the issuer recorded.
    cntl->backup_fired_ = true;
    if (c.issuer && c.issuer->IssueRPC(cntl) != 0) {
      cntl->error_code_ = 0;
      cntl->error_text_.clear();
    }
    fid_unlock(id);
    return 0;
  }

  const bool before_deadline = c.abs_deadline_us < 0 || now < c.abs_deadline_us;
  if (Retryable(error_code) && before_deadline && c.issuer) {
    // Synchronous issue failures (connect refused) loop here; asynchronous
    // ones (write failed later) come back through another fid_error.
    while (c.remaining_retries > 0) {
      --c.remaining_retries;
      ++cntl->retried_;
      if (c.span) {
        c.span->annotate(std::string("retrying: ") +
                         RpcErrorText(error_code));
      }
      if (c.issuer->IssueRPC(cntl) == 0) {
        fid_unlock(id);
        return 0;
      }
    }
    if (!cntl->Failed()) cntl->SetFailed(error_code);
  } else if (!cntl->Failed() || cntl->ErrorCode() != error_code) {
    // Keep a more descriptive message recorded by the issuer for the same
    // error; otherwise record this one.
    cntl->SetFailed(error_code);
  }
  cntl->EndRPC();
  return 0;
}

void Controller::OnResponse(RpcMeta&& meta, IOBuf&& body) {
  Call& c = call;
  c.reply_consumed = true;  // a whole frame arrived: connection aligned
  if (meta.error_code != 0) {
    // Server-reported failure: retryable codes re-issue like socket errors.
    const int64_t now = monotonic_us();
    const bool before_deadline =
        c.abs_deadline_us < 0 || now < c.abs_deadline_us;
    if (Retryable(meta.error_code) && c.remaining_retries > 0 &&
        before_deadline && c.issuer) {
      --c.remaining_retries;
      ++retried_;
      if (c.issuer->IssueRPC(this) == 0) {
        fid_unlock(cid_.load(std::memory_order_acquire));
        return;
      }
    }
    error_code_ = meta.error_code;
    error_text_ = !meta.error_text.empty() ? meta.error_text
                                           : RpcErrorText(meta.error_code);
    EndRPC();
    return;
  }
  // Success: any error recorded by a failed earlier attempt (retry/backup
  // issue failure) is superseded by this response.
  error_code_ = 0;
  error_text_.clear();
  // Bind a pending stream to the connection that answered (stream.cc hook;
  // kept as a function pointer so the core has no stream dependency).
  if (pending_stream_id != 0) {
    peer_stream_id = meta.stream_id;
    stream_socket = c.last_socket;
    if (g_stream_connect_hook) g_stream_connect_hook(this);
  }
  if (meta.compress_type != 0) {
    const CompressHandler* h = GetCompressHandler(meta.compress_type);
    IOBuf plain;
    if (h == nullptr || !h->decompress(body, &plain)) {
      error_code_ = ERESPONSE;
      error_text_ = "cannot decompress response";
      EndRPC();
      return;
    }
    body = std::move(plain);
  }
  const size_t att = meta.attachment_size;
  const size_t payload = body.size() - att;
  if (c.response) body.cutn(c.response, payload);
  else body.pop_front(payload);
  body.cutn(&response_attachment_, att);
  EndRPC();
}

void Controller::OnForeignReply(ClientReply&& reply) {
  Call& c = call;
  c.reply_consumed = true;  // a whole reply was cut: connection aligned
  // Any error recorded by a failed earlier attempt is superseded.
  error_code_ = 0;
  error_text_.clear();
  if (reply.has_http) *http_response() = std::move(reply.http);
  redis_reply = std::move(reply.redis);
  // Body is delivered even on EHTTP: a 404's payload is still the answer
  // (reference http client keeps the body on failed status).
  if (c.response) *c.response = std::move(reply.body);
  if (reply.error_code != 0) {
    error_code_ = reply.error_code;
    error_text_ = !reply.error_text.empty() ? reply.error_text
                                            : RpcErrorText(reply.error_code);
  }
  EndRPC();
}

void Controller::EndRPC() {
  Call& c = call;
  set_latency(monotonic_us() - c.start_us);
  if (c.on_end) c.on_end(this, c.on_end_arg);
  if (c.span != nullptr) {
    c.span->remote = remote_side_;
    c.span->end_us = monotonic_us();
    c.span->error_code = error_code_;
    SpanSubmit(std::move(*c.span));
    delete c.span;
    c.span = nullptr;
  }
  const fid_t id = cid_.load(std::memory_order_acquire);
  Closure done;
  done.swap(c.done);
  // Deregister from the socket's failure wait-list (no response coming /
  // already consumed).
  if (c.last_socket != INVALID_SOCKET_ID) {
    SocketUniquePtr p;
    if (Socket::Address(c.last_socket, &p) == 0) p->RemoveWaiter(id);
  }
  // Exclusive sockets superseded by a later attempt (retry/backup): pool
  // the healthy ones — a possibly in-flight late reply is safe because
  // its FIFO queue entry (or brt correlation id) still consumes it for
  // the next borrower — and close the rest.
  for (SocketId sid : c.superseded) {
    if (sid == c.last_socket) continue;
    SocketUniquePtr p;
    if (Socket::Address(sid, &p) != 0) continue;
    p->RemoveWaiter(id);
    if (ConnectionType(c.conn_type) == ConnectionType::POOLED &&
        !p->Failed()) {
      ReturnPooledSocket(p->remote(), sid, c.conn_group, c.conn_tls,
                         c.conn_proto);
    } else {
      p->SetFailed(ECANCELED, "superseded attempt");
    }
  }
  c.superseded.clear();
  // Exclusive connections: POOLED sockets go back to their group's freelist
  // when the connection is known aligned — success, OR a complete reply
  // that merely carried an error (EHTTP 404, server-reported failure);
  // closing those would defeat keep-alive on routine non-2xx statuses.
  // POOLED sockets whose reply never arrived are closed (a late response
  // may still be in flight) and SHORT sockets always close (reference
  // socket_map.h:147 / adaptive_connection_type.h:30-36).
  if (c.last_socket != INVALID_SOCKET_ID) {
    const ConnectionType ct = ConnectionType(c.conn_type);
    const bool poolable = error_code_ == 0 || c.reply_consumed;
    if (ct == ConnectionType::POOLED && poolable) {
      ReturnPooledSocket(remote_side_, c.last_socket, c.conn_group,
                         c.conn_tls, c.conn_proto);
    } else if (ct == ConnectionType::SHORT ||
               (ct == ConnectionType::POOLED && !poolable)) {
      SocketUniquePtr p;
      if (Socket::Address(c.last_socket, &p) == 0) {
        p->SetFailed(ECANCELED, "exclusive connection done");
      }
    }
  }
  // Timers: do not block on cancel — a concurrently running timeout callback
  // only does fid_error, which is a no-op after the destroy below.
  if (c.timeout_timer) timer_cancel_nonblocking(c.timeout_timer);
  if (c.backup_timer) timer_cancel_nonblocking(c.backup_timer);
  c.timeout_timer = c.backup_timer = kInvalidTimerId;
  // Destroy wakes synchronous joiners and invalidates future fid_error
  // (timeout/cancel racing in are dropped) — the reference's
  // unlock_and_destroy contract (id.h:35).
  fid_unlock_and_destroy(id);
  if (done) done();
}

}  // namespace brt
