// The central connection object.
// Parity target: reference src/brpc/socket.h:229 — versioned SocketId
// (use-after-free-safe handles), wait-free write path (lock-free MPSC
// request chain; the first writer flushes inline, overflow continues in a
// dedicated KeepWrite fiber, socket.cpp:1583-1863), SetFailed + recycle on
// last dereference, per-socket stats.
// Redesigned: the version and the reference count share one atomic word
// ([version:32|nref:32]); slots live in a never-freed ResourcePool-style
// arena so stale-id dereferences are memory-safe.
#pragma once

#include <atomic>
#include <cstdarg>
#include <cstdint>
#include <functional>
#include <mutex>
#include <string>
#include <vector>

#include "base/endpoint.h"
#include "base/iobuf.h"
#include "fiber/butex.h"
#include "fiber/fiber_id.h"

namespace brt {

class Socket;
class EventDispatcher;
class TlsContext;
class TlsSession;
using SocketId = uint64_t;
constexpr SocketId INVALID_SOCKET_ID = 0;

// Scoped, refcounted reference to a live Socket.
class SocketUniquePtr {
 public:
  SocketUniquePtr() = default;
  ~SocketUniquePtr() { reset(); }
  SocketUniquePtr(const SocketUniquePtr&) = delete;
  SocketUniquePtr& operator=(const SocketUniquePtr&) = delete;
  SocketUniquePtr(SocketUniquePtr&& o) noexcept : s_(o.s_) { o.s_ = nullptr; }
  SocketUniquePtr& operator=(SocketUniquePtr&& o) noexcept {
    if (this != &o) {
      reset();
      s_ = o.s_;
      o.s_ = nullptr;
    }
    return *this;
  }
  Socket* get() const { return s_; }
  Socket* operator->() const { return s_; }
  Socket& operator*() const { return *s_; }
  explicit operator bool() const { return s_ != nullptr; }
  void reset();
  Socket* release() {
    Socket* s = s_;
    s_ = nullptr;
    return s;
  }

 private:
  friend class Socket;
  Socket* s_ = nullptr;
};

class Socket {
 public:
  struct Options {
    int fd = -1;
    EndPoint remote;
    // True for an acceptor's LISTEN socket: it records its own listen
    // address as `remote`, so remote-matching sweeps (the
    // debug_fail_connections test lever) must be able to tell it from
    // a client connection TO that address — failing the listener kills
    // the server's accept path, not a connection.
    bool is_listener = false;
    void* user = nullptr;  // owner cookie (Server*, Channel state, ...)
    // Called in a fiber when the fd becomes readable (edge-triggered:
    // implementations must read until EAGAIN). Null for connect-only
    // sockets whose reads are driven elsewhere. May return one DEFERRED
    // work item: it runs only after the read gate is released (or in its
    // own fiber when more input is pending), so a handler that blocks —
    // e.g. a naming Watch long-poll — can never stall reads on a shared
    // connection (see ReadEventEntry).
    void* (*on_edge_triggered)(Socket*) = nullptr;
    // Runs a deferred item (fiber-entry signature). Required when
    // on_edge_triggered can return non-null.
    void* (*run_deferred)(void*) = nullptr;
    // Called once when the socket transitions to failed.
    void (*on_failed)(Socket*) = nullptr;
    // Installed as the socket's parsing_context BEFORE the fd is armed
    // with the dispatcher — per-connection state that on_edge_triggered /
    // on_failed need from their very first invocation (a post-Create
    // reset_parsing_context would race the read fiber). Freed by the
    // destroyer when the socket recycles.
    void* initial_parsing_context = nullptr;
    void (*parsing_context_destroyer)(void*) = nullptr;
    int dispatcher_index = -1;  // -1: shard by fd
    // Server-side TLS: when set, the connection's first bytes are sniffed
    // (0x16 handshake record => TLS session; anything else => plaintext on
    // the same port — the reference's ssl-vs-plaintext sniffing). Ownership
    // stays with the server; must outlive the socket.
    TlsContext* tls_server_ctx = nullptr;
    // TCP keepalive (reference SocketKeepaliveOptions, socket.h:178):
    // enable with keepalive=true; <=0 leaves a knob at the kernel default.
    bool keepalive = false;
    int keepalive_idle_s = 0;      // TCP_KEEPIDLE
    int keepalive_interval_s = 0;  // TCP_KEEPINTVL
    int keepalive_count = 0;       // TCP_KEEPCNT
  };

  // Wraps an existing connected/listening fd, registers it with the event
  // dispatcher, returns a versioned id.
  static int Create(const Options& opts, SocketId* id);

  // Non-blocking connect + dispatcher registration; parks the calling fiber
  // until connected or timeout. Returns 0 on success. `on_created` (may be
  // null) fires with the socket id right after Create, BEFORE the connect
  // wait — a canceller can SetFailed the id to abort the park (SetFailed
  // wakes the epollout butex the waiter parks on).
  static int Connect(const EndPoint& remote, const Options& opts,
                     SocketId* id, int64_t timeout_us = 1000000,
                     const std::function<void(SocketId)>& on_created =
                         nullptr);

  // Live reference for id (nullptr-safe failure): EINVAL on stale id.
  static int Address(SocketId id, SocketUniquePtr* out);

  // Wait-free write: steals *data. Thread/fiber-safe. On socket failure the
  // data is dropped and cid (if non-zero) receives fid_error(err).
  // Returns 0 if accepted (delivery still asynchronous).
  // `on_written(written_arg, error)`, if given, runs exactly once: when
  // the last byte of *data has been handed to the fd (error 0), or when
  // the data is dropped because the socket failed (the errno) — from
  // whichever thread flushes, so it must not block.
  using WrittenFn = void (*)(void* arg, int error);
  int Write(IOBuf* data, fid_t cid = 0, WrittenFn on_written = nullptr,
            void* written_arg = nullptr);

  // Hints that ~n more Write calls are imminent on this socket (the
  // messenger just dispatched a batch of n messages, each of which will
  // produce a response — or, client-side, a batch of n responses whose
  // waiters will issue follow-up requests). While the hint is positive,
  // a Write that would flush inline defers to a fiber scheduled AFTER
  // the expected writers, so k pipelined small messages leave in ONE
  // writev instead of k sendmsg calls (reference KeepWrite batching,
  // socket.cpp:1758, made proactive). Self-correcting: each Write
  // consumes one unit and a stale hint only costs one deferred flush.
  void SetWriteBatchHint(int n) {
    write_batch_hint_.store(n, std::memory_order_relaxed);
  }

  // Marks failed; pending & future writes error out; on_failed runs once;
  // fd is closed when the last reference drops.
  void SetFailed(int err, const char* fmt = nullptr, ...);

  // Process-global failure notification, fired exactly once per socket
  // inside SetFailed (after the failure is latched, before the ownership
  // ref drops).  Layers that key per-connection state by SocketId — the
  // stream registry, which must tear down receivers whose peer died
  // WITHOUT a graceful CLOSE — register here at init.  One hook; the
  // installer owns composition.  Must not block: it runs on whatever
  // thread/fiber noticed the failure.
  using FailureHook = void (*)(SocketId);
  static void set_failure_hook(FailureHook hook);

  // Graceful close: fails the socket once the write chain has fully
  // drained (HTTP "Connection: close" — the final response must reach the
  // kernel before the fd dies). If nothing is in flight, fails now.
  void CloseAfterFlush();
  bool Failed() const {
    return failed_.load(std::memory_order_acquire) != 0;
  }
  int error_code() const { return failed_.load(std::memory_order_acquire); }
  const std::string& error_text() const { return error_text_; }

  SocketId id() const { return id_; }
  int fd() const { return fd_; }
  const EndPoint& remote() const { return remote_; }
  bool is_listener() const { return is_listener_; }
  void* user() const { return user_; }

  // Last-matched protocol index for InputMessenger (reference keeps this on
  // the socket too, input_messenger.cpp:77).
  int preferred_protocol = -1;
  // CLOCK_MONOTONIC ns of the read event that brought the first byte of
  // the frame now at the head of read_buf (0: read_buf is empty). Only
  // the connection's one read fiber touches it (input_messenger.cc).
  int64_t frame_first_byte_ns = 0;

  // Per-protocol connection state (HTTP parser, h2 session, ...). Owned by
  // the socket: the destroyer runs at recycle (reference keeps
  // parsing_context on Socket the same way, socket.h:229 region). Only the
  // read fiber installs it; completion paths reach it under a live ref.
  void* parsing_context() const { return parsing_context_; }
  void reset_parsing_context(void* ctx, void (*destroyer)(void*)) {
    if (parsing_context_ != nullptr && parsing_context_destroyer_) {
      parsing_context_destroyer_(parsing_context_);
    }
    parsing_context_ = ctx;
    parsing_context_destroyer_ = destroyer;
  }
  // Correlation-id of the in-flight RPC for single-connection client sockets
  // is tracked by the Controller, not here.

  // --- stats (reference socket.h:124-156) ---
  std::atomic<uint64_t> bytes_read{0};
  std::atomic<uint64_t> bytes_written{0};
  std::atomic<uint64_t> messages_read{0};

  // Read-side reentrancy guard for edge-triggered events; used by the
  // dispatcher. 0 idle / 1 reading / 2 reading+pending.
  std::atomic<int> read_state{0};

  // Ingestion buffer (only touched by the single active read fiber).
  IOPortal read_buf;
  // Wire-side staging for TLS sockets (ciphertext before decryption);
  // persistent so IOPortal's partial-block reuse works per connection.
  IOPortal tls_wire_buf;

  // The ONE read seam: reads the fd into *out. Plaintext sockets readv
  // straight into the portal; TLS sockets (or server-side TLS candidates
  // still sniffing) decrypt first, so every caller parses plaintext
  // unchanged. Same contract as IOPortal::append_from_fd: >0 bytes
  // appended, 0 EOF, -1 with errno (EAGAIN = nothing yet).
  ssize_t AppendFromFd(IOPortal* out);

  // Client-side TLS: starts the handshake and parks the calling fiber
  // until it completes (the read path must be live — handshake replies
  // arrive through AppendFromFd). Call before the first Write. Returns 0,
  // ETIMEDOUT or EPROTO (socket failed on error).
  int StartTlsClient(TlsContext* ctx, const std::string& sni,
                     int64_t timeout_us);

  // Live TLS session (null for plaintext connections). alpn() etc.
  TlsSession* tls() const { return tls_.load(std::memory_order_acquire); }

  // Parking spot for fibers waiting for EPOLLOUT (value bumped + woken by
  // the dispatcher on writable events).
  Butex* epollout_butex() { return epollout_butex_; }
  // Blocks the calling fiber until the fd reports writable (or timeout).
  int WaitEpollOut(int64_t timeout_us);

  // In-process registry walk (builtin /connections service).
  static void ListSockets(std::vector<SocketId>* out);

  // In-flight RPC registration: a correlation id registered here receives
  // fid_error(EFAILEDSOCKET-mapped errno) when the socket fails — the
  // reference's id-wait-list (socket.h:229 region, wakes RPCs whose
  // response can no longer arrive). Register BEFORE writing the request;
  // deregister on response arrival / call end.
  void AddWaiter(fid_t cid);
  void RemoveWaiter(fid_t cid);

  // One node of the wait-free MPSC write chain (pooled via ObjectPool — the
  // per-call hot path must not malloc).
  struct WriteReq {
    IOBuf data;
    fid_t cid = 0;
    // Bytes are already wire-format (TLS handshake records / encrypted):
    // the flusher must not run them through the session again.
    bool raw = false;
    WrittenFn on_written = nullptr;
    void* written_arg = nullptr;
    std::atomic<WriteReq*> next{nullptr};
  };

 private:
  friend class SocketUniquePtr;

  Socket() = default;
  ~Socket() = default;

  void Dereference();
  void OnRecycle();

  // Flusher internals.
  int FlushWriteChain(WriteReq* head, bool in_keepwrite_fiber);
  static void* KeepWriteEntry(void* arg);
  WriteReq* AdvanceWriteChain(WriteReq* cur);
  void ReleaseChainOnError(WriteReq* head, int err);

  static void* ReadEventEntry(void* arg);

  SocketId id_ = INVALID_SOCKET_ID;
  int fd_ = -1;
  EndPoint remote_;
  bool is_listener_ = false;
  void* user_ = nullptr;
  void* (*on_edge_triggered_)(Socket*) = nullptr;
  void* (*run_deferred_)(void*) = nullptr;
  void (*on_failed_)(Socket*) = nullptr;
  std::atomic<int> failed_{0};
  std::string error_text_;
  void* parsing_context_ = nullptr;
  void (*parsing_context_destroyer_)(void*) = nullptr;
  std::atomic<bool> close_after_flush_{false};
  std::atomic<int> write_batch_hint_{0};  // see SetWriteBatchHint
  std::atomic<WriteReq*> write_head_{nullptr};  // MPSC chain, Vyukov-style
  // Wire-format write that bypasses TLS encryption (handshake replies).
  int WriteWire(IOBuf* data);
  int TakeBatchHint();
  int QueueOrFlush(WriteReq* req);
  std::atomic<TlsSession*> tls_{nullptr};  // owned; freed at recycle
  TlsContext* tls_server_ctx_ = nullptr;   // sniffing candidate (server)
  std::mutex waiters_mu_;
  std::vector<fid_t> waiters_;  // in-flight RPC ids awaiting responses
  Butex* epollout_butex_ = nullptr;
  EventDispatcher* dispatcher_ = nullptr;
  std::atomic<uint64_t> vref_{0};  // [version:32|nref:32]

  friend struct SocketSlab;
  friend struct KeepWriteArg;
  friend void dispatcher_handle_event(SocketId, uint32_t);
};

}  // namespace brt
