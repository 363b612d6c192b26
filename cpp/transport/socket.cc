#include "transport/socket.h"

#include <fcntl.h>
#include <netinet/tcp.h>
#include <sys/epoll.h>
#include <sys/socket.h>
#include <unistd.h>

#include <cstdio>
#include <mutex>
#include <unordered_set>
#include <vector>

#include "base/logging.h"
#include "base/object_pool.h"
#include "base/time.h"
#include "fiber/fiber.h"
#include "transport/event_dispatcher.h"
#include "transport/tls.h"

namespace brt {

namespace {

// WriteReq allocation is on the per-call hot path (reference pools its
// WriteRequest through butil::ObjectPool for the same reason).
using WriteReqPool = ObjectPool<Socket::WriteReq>;

Socket::WriteReq* GetWriteReq() {
  Socket::WriteReq* r = WriteReqPool::Get();
  r->next.store(nullptr, std::memory_order_relaxed);
  r->cid = 0;
  r->raw = false;
  r->on_written = nullptr;
  r->written_arg = nullptr;
  return r;
}

// The request's bytes have left (or were dropped with `err`).
void FireWritten(Socket::WriteReq* r, int err) {
  if (r->on_written == nullptr) return;
  Socket::WrittenFn fn = r->on_written;
  r->on_written = nullptr;
  fn(r->written_arg, err);
}

void PutWriteReq(Socket::WriteReq* r) {
  r->data.clear();
  WriteReqPool::Put(r);
}

}  // namespace

// ---------------------------------------------------------------------------
// Slab of Socket slots. Slots are constructed once and never destroyed
// (reference contract: stale SocketId dereferences must be memory-safe,
// socket.h:229 + socket_id.h).
// ---------------------------------------------------------------------------
struct SocketSlab {
  static constexpr uint32_t kBlockSlots = 256;
  static constexpr uint32_t kMaxBlocks = 4096;  // 1M sockets

  static SocketSlab& singleton() {
    static SocketSlab* s = new SocketSlab;
    return *s;
  }

  SocketSlab() : blocks(new std::atomic<Socket*>[kMaxBlocks]) {
    for (uint32_t i = 0; i < kMaxBlocks; ++i) blocks[i].store(nullptr);
  }

  Socket* slot(uint32_t index) {
    Socket* b = blocks[index / kBlockSlots].load(std::memory_order_acquire);
    return &b[index % kBlockSlots];
  }

  uint32_t alloc_index() {
    std::lock_guard<std::mutex> g(mu);
    if (!free_list.empty()) {
      uint32_t i = free_list.back();
      free_list.pop_back();
      return i;
    }
    uint32_t i = next_index.load(std::memory_order_relaxed);
    uint32_t b = i / kBlockSlots;
    BRT_CHECK_LT(b, kMaxBlocks) << "socket slab exhausted";
    if (blocks[b].load(std::memory_order_acquire) == nullptr) {
      blocks[b].store(new Socket[kBlockSlots], std::memory_order_release);
    }
    // Publish AFTER the block exists so lock-free readers of next_index
    // always find slot memory.
    next_index.store(i + 1, std::memory_order_release);
    return i;
  }

  void free_index(uint32_t i) {
    std::lock_guard<std::mutex> g(mu);
    free_list.push_back(i);
  }

  std::mutex mu;
  std::vector<uint32_t> free_list;
  std::atomic<uint32_t> next_index{0};
  std::atomic<Socket*>* blocks;

  // Live-id registry for /connections.
  std::mutex live_mu;
  std::unordered_set<SocketId> live;
};

static uint32_t id_index(SocketId id) { return uint32_t(id); }
static uint32_t id_version(SocketId id) { return uint32_t(id >> 32); }

void SocketUniquePtr::reset() {
  if (s_) {
    s_->Dereference();
    s_ = nullptr;
  }
}

static int set_nonblocking(int fd) {
  int flags = fcntl(fd, F_GETFL, 0);
  return fcntl(fd, F_SETFL, flags | O_NONBLOCK);
}

int Socket::Create(const Options& opts, SocketId* id_out) {
  BRT_CHECK_GE(opts.fd, 0);
  set_nonblocking(opts.fd);
  int one = 1;
  setsockopt(opts.fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
  if (opts.keepalive) {
    setsockopt(opts.fd, SOL_SOCKET, SO_KEEPALIVE, &one, sizeof(one));
    if (opts.keepalive_idle_s > 0) {
      setsockopt(opts.fd, IPPROTO_TCP, TCP_KEEPIDLE, &opts.keepalive_idle_s,
                 sizeof(int));
    }
    if (opts.keepalive_interval_s > 0) {
      setsockopt(opts.fd, IPPROTO_TCP, TCP_KEEPINTVL,
                 &opts.keepalive_interval_s, sizeof(int));
    }
    if (opts.keepalive_count > 0) {
      setsockopt(opts.fd, IPPROTO_TCP, TCP_KEEPCNT, &opts.keepalive_count,
                 sizeof(int));
    }
  }

  SocketSlab& slab = SocketSlab::singleton();
  uint32_t index = slab.alloc_index();
  Socket* s = slab.slot(index);

  uint32_t v = uint32_t(s->vref_.load(std::memory_order_relaxed) >> 32) + 1;
  BRT_CHECK(v & 1);
  s->fd_ = opts.fd;
  s->remote_ = opts.remote;
  s->is_listener_ = opts.is_listener;
  s->user_ = opts.user;
  s->on_edge_triggered_ = opts.on_edge_triggered;
  s->run_deferred_ = opts.run_deferred;
  s->parsing_context_ = opts.initial_parsing_context;
  s->parsing_context_destroyer_ = opts.parsing_context_destroyer;
  s->on_failed_ = opts.on_failed;
  s->failed_.store(0, std::memory_order_relaxed);
  s->error_text_.clear();
  s->preferred_protocol = -1;
  s->bytes_read.store(0, std::memory_order_relaxed);
  s->bytes_written.store(0, std::memory_order_relaxed);
  s->messages_read.store(0, std::memory_order_relaxed);
  s->read_state.store(0, std::memory_order_relaxed);
  // Recycled slot: a stale close-after-flush from the previous connection
  // would kill this one at its first write-chain drain.
  s->close_after_flush_.store(false, std::memory_order_relaxed);
  s->read_buf.clear();
  s->tls_wire_buf.clear();
  s->waiters_.clear();
  s->tls_.store(nullptr, std::memory_order_relaxed);
  s->tls_server_ctx_ = opts.tls_server_ctx;
  if (s->epollout_butex_ == nullptr) s->epollout_butex_ = butex_create();
  s->write_head_.store(nullptr, std::memory_order_relaxed);
  s->id_ = (uint64_t(v) << 32) | index;
  // One "ownership" reference representing the live fd; dropped by
  // SetFailed so the socket recycles once all users release.
  s->vref_.store((uint64_t(v) << 32) | 1, std::memory_order_release);

  {
    std::lock_guard<std::mutex> g(slab.live_mu);
    slab.live.insert(s->id_);
  }

  EventDispatcher& d = opts.dispatcher_index >= 0
                           ? EventDispatcher::at(opts.dispatcher_index)
                           : EventDispatcher::global(opts.fd);
  s->dispatcher_ = &d;
  if (d.AddConsumer(opts.fd, s->id_) != 0) {
    int err = errno;
    *id_out = s->id_;
    s->SetFailed(err, "epoll_ctl add failed");
    return -1;
  }
  *id_out = s->id_;
  return 0;
}

int Socket::Address(SocketId id, SocketUniquePtr* out) {
  // Lock-free: this runs on every epoll event and every RPC lookup.
  SocketSlab& slab = SocketSlab::singleton();
  uint32_t index = id_index(id);
  if (index >= slab.next_index.load(std::memory_order_acquire)) return EINVAL;
  Socket* s = slab.slot(index);
  uint64_t vref = s->vref_.load(std::memory_order_acquire);
  for (;;) {
    if (uint32_t(vref >> 32) != id_version(id)) return EINVAL;
    // nref==0 with a matching version is the window between the last
    // Dereference and OnRecycle's version bump: resurrecting here would
    // recycle the slot TWICE (double close + double free_index).
    if (uint32_t(vref) == 0) return EINVAL;
    if (s->vref_.compare_exchange_weak(vref, vref + 1,
                                       std::memory_order_acq_rel)) {
      out->reset();
      out->s_ = s;
      return 0;
    }
  }
}

void Socket::Dereference() {
  uint64_t prev = vref_.fetch_sub(1, std::memory_order_acq_rel);
  if (uint32_t(prev) == 1) OnRecycle();
}

void Socket::OnRecycle() {
  // Reference Socket::OnRecycle (socket.cpp:1084): close fd, release
  // pending write chain, bump version, return the slot.
  SocketSlab& slab = SocketSlab::singleton();
  {
    std::lock_guard<std::mutex> g(slab.live_mu);
    slab.live.erase(id_);
  }
  if (fd_ >= 0) {
    if (dispatcher_) dispatcher_->RemoveConsumer(fd_);
    ::close(fd_);
    fd_ = -1;
  }
  // Every Write() happens under a live reference and its chain is always
  // drained by a flusher that also holds one, so the chain must be empty by
  // the time the last reference drops.
  WriteReq* head = write_head_.exchange(nullptr, std::memory_order_acq_rel);
  if (head != nullptr) {
    BRT_LOG(ERROR) << "write chain not empty at recycle, leaking it";
  }
  read_buf.clear();
  tls_wire_buf.clear();
  TlsSession* tls = tls_.exchange(nullptr, std::memory_order_acq_rel);
  delete tls;
  tls_server_ctx_ = nullptr;
  if (parsing_context_ != nullptr) {
    if (parsing_context_destroyer_) parsing_context_destroyer_(parsing_context_);
    parsing_context_ = nullptr;
    parsing_context_destroyer_ = nullptr;
  }
  uint32_t v = id_version(id_);
  vref_.store(uint64_t(v + 1) << 32, std::memory_order_release);
  slab.free_index(id_index(id_));
}

// Global failure hook (stream-layer teardown). Installed once at stream
// init; relaxed is enough — installation happens-before any socket the
// installer cares about exists.
static std::atomic<Socket::FailureHook> g_failure_hook{nullptr};

void Socket::set_failure_hook(FailureHook hook) {
  g_failure_hook.store(hook, std::memory_order_release);
}

void Socket::SetFailed(int err, const char* fmt, ...) {
  int expected = 0;
  if (!failed_.compare_exchange_strong(expected, err ? err : ECONNRESET,
                                       std::memory_order_acq_rel)) {
    return;  // already failed
  }
  if (fmt != nullptr) {
    char buf[256];
    va_list ap;
    va_start(ap, fmt);
    vsnprintf(buf, sizeof(buf), fmt, ap);
    va_end(ap);
    error_text_ = buf;
  }
  // Wake EPOLLOUT waiters so KeepWrite notices the failure.
  butex_value(epollout_butex_).fetch_add(1, std::memory_order_release);
  butex_wake_all(epollout_butex_);
  // A handshake waiter must not sleep to its timeout on a dead socket.
  if (TlsSession* tls = tls_.load(std::memory_order_acquire)) {
    tls->FailHandshake();
  }
  // Error every in-flight RPC whose response can no longer arrive
  // (reference id-wait-list semantics).
  std::vector<fid_t> waiters;
  {
    std::lock_guard<std::mutex> g(waiters_mu_);
    waiters.swap(waiters_);
  }
  const int werr = failed_.load(std::memory_order_acquire);
  for (fid_t cid : waiters) fid_error(cid, werr);
  if (on_failed_) on_failed_(this);
  // Global notification (stream teardown) AFTER per-socket cleanup, while
  // the ownership ref still pins the id: hooks may Address() this socket.
  if (FailureHook hook = g_failure_hook.load(std::memory_order_acquire)) {
    hook(id_);
  }
  Dereference();  // drop the ownership ref
}

void Socket::AddWaiter(fid_t cid) {
  {
    std::lock_guard<std::mutex> g(waiters_mu_);
    if (failed_.load(std::memory_order_acquire) == 0) {
      waiters_.push_back(cid);
      return;
    }
  }
  // Raced with SetFailed's drain: deliver directly.
  fid_error(cid, failed_.load(std::memory_order_acquire));
}

void Socket::RemoveWaiter(fid_t cid) {
  std::lock_guard<std::mutex> g(waiters_mu_);
  for (size_t i = 0; i < waiters_.size(); ++i) {
    if (waiters_[i] == cid) {
      waiters_[i] = waiters_.back();
      waiters_.pop_back();
      return;
    }
  }
}

// ---------------------------------------------------------------------------
// Wait-free write path (reference socket.cpp:1583,1657,1758,1863).
// Producers push onto a lock-free MPSC chain; whoever finds the chain empty
// becomes the flusher: writes inline once, and on EAGAIN hands off to a
// KeepWrite fiber that parks on EPOLLOUT.
// ---------------------------------------------------------------------------
struct KeepWriteArg {
  SocketId sid;
  Socket::WriteReq* cur;
};

// Consumes one batch-hint unit; returns the pre-decrement value (0 when
// no batch is expected).
int Socket::TakeBatchHint() {
  int hint = write_batch_hint_.load(std::memory_order_relaxed);
  while (hint > 0 && !write_batch_hint_.compare_exchange_weak(
                         hint, hint - 1, std::memory_order_relaxed)) {
  }
  return hint;
}

// Links req into the MPSC chain; the writer that becomes head flushes —
// inline normally, or (when the batch hint says more writers are
// imminent) from a lazily-scheduled fiber that runs AFTER them, so their
// frames coalesce into this chain and leave in one writev. On
// flusher-spawn failure falls back to inline.
int Socket::QueueOrFlush(WriteReq* req) {
  const int hint = TakeBatchHint();
  WriteReq* prev = write_head_.exchange(req, std::memory_order_acq_rel);
  if (prev != nullptr) {
    // Another writer is (or will become) the flusher; just link in.
    prev->next.store(req, std::memory_order_release);
    return 0;
  }
  if (hint > 1) {
    auto* arg = new KeepWriteArg{id_, req};
    fiber_t tid;
    if (fiber_start_lazy(&tid, &Socket::KeepWriteEntry, arg) == 0) return 0;
    delete arg;
  }
  return FlushWriteChain(req, /*in_keepwrite_fiber=*/false);
}

int Socket::Write(IOBuf* data, fid_t cid, WrittenFn on_written,
                  void* written_arg) {
  int err = failed_.load(std::memory_order_acquire);
  if (err != 0) {
    data->clear();
    if (cid != 0) fid_error(cid, err);
    if (on_written != nullptr) on_written(written_arg, err);
    return err;
  }
  WriteReq* req = GetWriteReq();
  req->data.swap(*data);
  req->cid = cid;
  req->on_written = on_written;
  req->written_arg = written_arg;
  return QueueOrFlush(req);
}

int Socket::WriteWire(IOBuf* data) {
  int err = failed_.load(std::memory_order_acquire);
  if (err != 0) {
    data->clear();
    return err;
  }
  WriteReq* req = GetWriteReq();
  req->data.swap(*data);
  req->raw = true;
  return QueueOrFlush(req);
}

void* Socket::KeepWriteEntry(void* argp) {
  auto* arg = static_cast<KeepWriteArg*>(argp);
  SocketUniquePtr ptr;
  if (Socket::Address(arg->sid, &ptr) == 0) {
    ptr->FlushWriteChain(arg->cur, /*in_keepwrite_fiber=*/true);
  } else {
    // Socket recycled under us: free the chain outright.
    Socket::WriteReq* c = arg->cur;
    while (c) {
      Socket::WriteReq* n = c->next.load(std::memory_order_acquire);
      if (c->cid) fid_error(c->cid, ECONNRESET);
      FireWritten(c, ECONNRESET);
      PutWriteReq(c);
      c = n;
    }
  }
  delete arg;
  return nullptr;
}

int Socket::FlushWriteChain(WriteReq* cur, bool in_keepwrite_fiber) {
  for (;;) {
    // Coalesce already-queued successors (same raw state) into cur before
    // the syscall: k pipelined small frames leave in one writev — and,
    // under TLS, in one record batch — instead of k. The flusher owns
    // every linked node (producers only touch a node before publishing
    // it), so moving their data is race-free; drained nodes stay in the
    // chain empty so error accounting still walks them.
    {
      size_t merged = cur->data.size();
      for (WriteReq* n = cur->next.load(std::memory_order_acquire);
           n != nullptr && n->raw == cur->raw && merged < (1u << 20);
           n = n->next.load(std::memory_order_acquire)) {
        merged += n->data.size();
        cur->data.append(std::move(n->data));
      }
    }
    // TLS: encrypt the request's plaintext into wire records. Exactly one
    // flusher runs at a time, so the session sees writes in chain order;
    // raw is flipped so a KeepWrite handoff can't double-encrypt.
    TlsSession* tls = tls_.load(std::memory_order_acquire);
    if (tls != nullptr && !cur->raw && !cur->data.empty()) {
      IOBuf wire;
      if (tls->Encrypt(&cur->data, &wire) != 0) {
        SetFailed(EPROTO, "tls encrypt failed");
        ReleaseChainOnError(cur, EPROTO);
        return EPROTO;
      }
      cur->data.swap(wire);
      cur->raw = true;
    }
    // Drain cur->data into the fd.
    while (!cur->data.empty()) {
      ssize_t nw = cur->data.cut_into_writev(fd_);
      if (nw > 0) {
        bytes_written.fetch_add(uint64_t(nw), std::memory_order_relaxed);
        continue;
      }
      if (nw < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) {
        if (!in_keepwrite_fiber) {
          auto* arg = new KeepWriteArg{id_, cur};
          fiber_t tid;
          if (fiber_start(&tid, &Socket::KeepWriteEntry, arg) != 0) {
            delete arg;
            SetFailed(ENOMEM, "fiber_start failed in Write");
            ReleaseChainOnError(cur, ENOMEM);
            return ENOMEM;
          }
          return 0;
        }
        int rc = WaitEpollOut(/*timeout_us=*/-1);
        int err = failed_.load(std::memory_order_acquire);
        if (err != 0) {
          ReleaseChainOnError(cur, err);
          return err;
        }
        (void)rc;
        continue;
      }
      if (nw < 0 && errno == EINTR) continue;
      int err = errno != 0 ? errno : EPIPE;
      SetFailed(err, "write failed: %s", strerror(err));
      ReleaseChainOnError(cur, err);
      return err;
    }
    // cur fully written: advance or terminate.
    FireWritten(cur, 0);
    WriteReq* next = AdvanceWriteChain(cur);
    if (next == nullptr) {
      // Chain drained: honor a pending graceful close. This is a Dekker
      // handshake with CloseAfterFlush (flag-store vs head-CAS on one
      // side, head-load vs flag-load on the other): both sides' accesses
      // are seq_cst so at least one of them observes the other — plain
      // acquire/release would allow both to miss (store-load reordering)
      // and the close to be lost.
      if (close_after_flush_.load(std::memory_order_seq_cst)) {
        SetFailed(EPIPE, "closed after final response");
      }
      return 0;
    }
    cur = next;
  }
}

void Socket::CloseAfterFlush() {
  close_after_flush_.store(true, std::memory_order_seq_cst);
  if (write_head_.load(std::memory_order_seq_cst) == nullptr) {
    SetFailed(EPIPE, "closed after final response");
  }
}

// Frees cur and returns its successor, or nullptr after successfully
// detaching the chain (CAS head cur→null; spins for a racing producer's
// not-yet-visible link otherwise). The single subtle piece of the MPSC
// protocol — shared by the success and error drains.
Socket::WriteReq* Socket::AdvanceWriteChain(WriteReq* cur) {
  WriteReq* next = cur->next.load(std::memory_order_acquire);
  if (next == nullptr) {
    WriteReq* expected = cur;
    // seq_cst: one side of the CloseAfterFlush Dekker handshake (the
    // flag check after a drained chain must not miss a racing closer).
    if (write_head_.compare_exchange_strong(expected, nullptr,
                                            std::memory_order_seq_cst)) {
      PutWriteReq(cur);
      return nullptr;
    }
    do {
      next = cur->next.load(std::memory_order_acquire);
    } while (next == nullptr);
  }
  PutWriteReq(cur);
  return next;
}

void Socket::ReleaseChainOnError(WriteReq* cur, int err) {
  // We are the flusher: drain everything (including racing pushes) and
  // propagate err to each request's correlation id.
  while (cur != nullptr) {
    if (cur->cid != 0) fid_error(cur->cid, err);
    FireWritten(cur, err);
    cur = AdvanceWriteChain(cur);
  }
}

int Socket::WaitEpollOut(int64_t timeout_us) {
  int expected = butex_value(epollout_butex_).load(std::memory_order_acquire);
  // Missed-wakeup guard: SetFailed CASes failed_, THEN bumps the butex and
  // wakes. A failure landing between our expected-load and butex_wait would
  // otherwise bump a butex nobody watches and leave this fiber parked to
  // its full timeout (forever for the -1 KeepWrite wait). failed_'s CAS
  // precedes the bump in SetFailed's program order, so seeing failed_==0
  // here means any concurrent bump lands after `expected` was read —
  // butex_wait then returns immediately on the value mismatch.
  if (failed_.load(std::memory_order_acquire) != 0) return 0;
  dispatcher_->RegisterEpollOut(fd_, id_);
  int rc = butex_wait(epollout_butex_, expected, timeout_us);
  dispatcher_->UnregisterEpollOut(fd_, id_);
  return rc == EWOULDBLOCK ? 0 : rc;
}

int Socket::Connect(const EndPoint& remote, const Options& opts,
                    SocketId* id_out, int64_t timeout_us,
                    const std::function<void(SocketId)>& on_created) {
  const int family = remote.is_unix() ? AF_UNIX : AF_INET;
  int fd = ::socket(family, SOCK_STREAM | SOCK_NONBLOCK, 0);
  if (fd < 0) return errno;
  sockaddr_storage ss;
  socklen_t slen = remote.to_sockaddr_storage(&ss);
  int rc = ::connect(fd, reinterpret_cast<sockaddr*>(&ss), slen);
  // AF_UNIX returns EAGAIN (not EINPROGRESS) when the listener backlog is
  // full, and the connect will NOT complete later via EPOLLOUT — retry with
  // a backoff for up to the connect timeout before giving up.
  if (rc != 0 && errno == EAGAIN && remote.is_unix()) {
    // timeout_us <= 0 means "no timeout": retry without a deadline
    // (matching WaitEpollOut, where <=0 waits indefinitely).
    const int64_t give_up =
        timeout_us > 0 ? monotonic_us() + timeout_us : INT64_MAX;
    int64_t delay_us = 1000;
    while (rc != 0 && errno == EAGAIN && monotonic_us() < give_up) {
      fiber_usleep(delay_us);
      if (delay_us < 32000) delay_us *= 2;
      rc = ::connect(fd, reinterpret_cast<sockaddr*>(&ss), slen);
    }
  }
  if (rc != 0 && errno != EINPROGRESS) {
    int err = errno;
    ::close(fd);
    return err;
  }
  Options o = opts;
  o.fd = fd;
  o.remote = remote;
  if (Socket::Create(o, id_out) != 0) return ECONNREFUSED;
  if (on_created) on_created(*id_out);
  if (rc != 0) {
    // Wait for writability, then check SO_ERROR.
    SocketUniquePtr ptr;
    if (Socket::Address(*id_out, &ptr) != 0) return ECONNREFUSED;
    int wrc = ptr->WaitEpollOut(timeout_us);
    // The fd is already registered for reads: on a refused connect the
    // read path may consume the error (read() → ECONNREFUSED → SetFailed)
    // before we get here, leaving SO_ERROR clean — trust the socket state
    // first.
    if (ptr->Failed()) return ptr->error_code();
    if (wrc == ETIMEDOUT) {
      ptr->SetFailed(ETIMEDOUT, "connect timeout");
      return ETIMEDOUT;
    }
    int soerr = 0;
    socklen_t len = sizeof(soerr);
    if (getsockopt(fd, SOL_SOCKET, SO_ERROR, &soerr, &len) != 0) {
      soerr = ptr->Failed() ? ptr->error_code() : ECONNREFUSED;
    }
    if (soerr != 0) {
      ptr->SetFailed(soerr, "connect failed: %s", strerror(soerr));
      return soerr;
    }
  }
  return 0;
}

// ---------------------------------------------------------------------------
// TLS read seam + client handshake.
// ---------------------------------------------------------------------------
ssize_t Socket::AppendFromFd(IOPortal* out) {
  TlsSession* tls = tls_.load(std::memory_order_acquire);
  if (tls == nullptr && tls_server_ctx_ == nullptr) {
    return out->append_from_fd(fd_);  // plaintext fast path
  }
  const size_t before = out->size();
  IOBuf wire_out;
  int rc = 0;
  if (tls == nullptr) {
    // Server-side sniff (only the single active read fiber gets here,
    // before any plaintext has ever been delivered): the first byte
    // decides — 0x16 is a TLS handshake record, nothing any supported
    // plaintext protocol starts with.
    ssize_t nr = out->append_from_fd(fd_);
    if (nr <= 0) return nr;
    char b0 = 0;
    out->copy_to(&b0, 1, before);
    if (uint8_t(b0) != 0x16) {
      tls_server_ctx_ = nullptr;  // plaintext connection: stop sniffing
      return nr;
    }
    std::string err;
    TlsSession* sess = TlsSession::New(tls_server_ctx_, "", &err);
    if (sess == nullptr) {
      BRT_LOG(WARNING) << "tls session create failed: " << err;
      errno = EPROTO;
      return -1;
    }
    tls_.store(sess, std::memory_order_release);
    tls = sess;
    // The sniffed bytes are wire data for the session, not app plaintext.
    IOBuf wire;
    out->cutn(&wire, out->size() - before);
    rc = tls->OnWireData(&wire, out, &wire_out);
  }
  // Drain the fd (edge-triggered contract — returning EAGAIN with wire
  // bytes still readable would lose the edge), decrypt, hand plaintext to
  // the caller.
  bool saw_eof = false;
  if (rc == 0) {
    for (;;) {
      ssize_t nr = tls_wire_buf.append_from_fd(fd_);
      if (nr > 0) {
        if (tls_wire_buf.size() >= 512 * 1024) break;  // fairness bound
        continue;
      }
      if (nr == 0) {
        saw_eof = true;
        break;
      }
      if (errno == EINTR) continue;
      if (errno == EAGAIN || errno == EWOULDBLOCK) break;
      return -1;  // real IO error, errno set
    }
    IOBuf wire_in;
    tls_wire_buf.cutn(&wire_in, tls_wire_buf.size());
    rc = tls->OnWireData(&wire_in, out, &wire_out);
  }
  if (!wire_out.empty()) WriteWire(&wire_out);
  // Publish handshake completion only now — after the final handshake
  // record is on the write chain — so a woken writer's first encrypted
  // app record cannot overtake it.
  tls->PublishHandshakeState();
  if (rc == EPROTO) {
    errno = EPROTO;
    return -1;
  }
  if (out->size() > before) return ssize_t(out->size() - before);
  if (saw_eof || rc == ESHUTDOWN) return 0;
  errno = EAGAIN;
  return -1;
}

int Socket::StartTlsClient(TlsContext* ctx, const std::string& sni,
                           int64_t timeout_us) {
  std::string err;
  TlsSession* sess = TlsSession::New(ctx, sni, &err);
  if (sess == nullptr) {
    SetFailed(EPROTO, "tls session create failed: %s", err.c_str());
    return EPROTO;
  }
  IOBuf first;
  if (sess->Pump(&first) != 0) {
    delete sess;
    SetFailed(EPROTO, "tls client hello failed");
    return EPROTO;
  }
  // Publish BEFORE the first flight hits the wire: the server's reply may
  // arrive (and must decrypt) on the read fiber immediately after.
  tls_.store(sess, std::memory_order_release);
  // A failure that landed before the publish (instant RST consumed by the
  // plaintext read path) skipped FailHandshake — re-check so the waiter
  // below cannot sleep to its timeout on a dead socket.
  if (Failed()) {
    sess->FailHandshake();
    return error_code();
  }
  int wrc = first.empty() ? 0 : WriteWire(&first);
  if (wrc != 0) {
    sess->FailHandshake();
    return wrc;
  }
  int rc = sess->WaitHandshake(timeout_us);
  if (rc != 0) {
    SetFailed(rc, "tls handshake %s",
              rc == ETIMEDOUT ? "timeout" : "failed");
  }
  return rc;
}

void Socket::ListSockets(std::vector<SocketId>* out) {
  SocketSlab& slab = SocketSlab::singleton();
  std::lock_guard<std::mutex> g(slab.live_mu);
  out->assign(slab.live.begin(), slab.live.end());
}

// ---------------------------------------------------------------------------
// Event entry points (called from dispatcher threads).
// ---------------------------------------------------------------------------
void* Socket::ReadEventEntry(void* arg) {
  SocketId sid = reinterpret_cast<uintptr_t>(arg);
  SocketUniquePtr ptr;
  if (Socket::Address(sid, &ptr) != 0) return nullptr;
  Socket* s = ptr.get();
  for (;;) {
    void* deferred = s->on_edge_triggered_(s);
    int st = 1;
    if (s->read_state.compare_exchange_strong(st, 0,
                                              std::memory_order_acq_rel)) {
      // Gate released FIRST: new input now spawns a fresh read fiber, so
      // running the deferred handler inline here (the "thread jump"
      // optimization) cannot stall the connection even if it blocks for
      // seconds (e.g. a registry Watch long-poll on a shared connection).
      if (deferred != nullptr) s->run_deferred_(deferred);
      return nullptr;
    }
    // st was 2: more events arrived while reading; we must read again NOW,
    // so the deferred item gets its own fiber instead of running inline.
    s->read_state.store(1, std::memory_order_release);
    if (deferred != nullptr) {
      fiber_t tid;
      if (fiber_start(&tid, s->run_deferred_, deferred) != 0) {
        s->run_deferred_(deferred);
      }
    }
  }
}

void dispatcher_handle_event(SocketId sid, uint32_t events) {
  SocketUniquePtr ptr;
  if (Socket::Address(sid, &ptr) != 0) return;
  Socket* s = ptr.get();
  if (events & EPOLLOUT) {
    butex_value(s->epollout_butex_).fetch_add(1, std::memory_order_release);
    butex_wake_all(s->epollout_butex_);
  }
  if ((events & (EPOLLIN | EPOLLHUP | EPOLLRDHUP | EPOLLERR)) &&
      s->on_edge_triggered_ != nullptr) {
    int st = s->read_state.load(std::memory_order_acquire);
    for (;;) {
      if (st == 0) {
        if (s->read_state.compare_exchange_weak(st, 1,
                                                std::memory_order_acq_rel)) {
          fiber_t tid;
          fiber_start(&tid, &Socket::ReadEventEntry,
                      reinterpret_cast<void*>(uintptr_t(sid)));
          return;
        }
      } else {
        if (s->read_state.compare_exchange_weak(st, 2,
                                                std::memory_order_acq_rel)) {
          return;  // the active reader will loop again
        }
      }
    }
  }
}

}  // namespace brt
