#include "transport/input_messenger.h"

#include <atomic>
#include <mutex>

#include <vector>

#include "base/logging.h"
#include "base/object_pool.h"
#include "base/time.h"
#include "fiber/fiber.h"

namespace brt {

// Diagnostic: how many complete messages each read event yields (the
// denominator of response-write aggregation).
std::atomic<long> g_msg_batches{0};
std::atomic<long> g_msg_batched{0};

namespace {
constexpr int kMaxProtocols = 32;
Protocol g_protocols[kMaxProtocols];
// release-stored after the slot is fully written; acquire loads on the
// read side so GetProtocol/protocol_count never observe a half-written
// Protocol during a concurrent lazy registration.
std::atomic<int> g_nprotocols{0};
}  // namespace

// Scan order published as an immutable snapshot: RegisterProtocol may run
// while OTHER servers' IO fibers are mid-scan (the lazy call_once
// registrations in ServeMongoOn etc.), so the order array is rebuilt into
// a fresh buffer and swapped in with one release store — readers never
// see a half-rebuilt array.
struct ScanOrder {
  int n = 0;
  int order[kMaxProtocols];
};
std::atomic<const ScanOrder*> g_scan_order{nullptr};

int RegisterProtocol(const Protocol& p) {
  // Registration is reachable lazily (ServeRedisOn/ServeMongoOn/... each
  // behind their own call_once), so two protocols may register
  // concurrently; the snapshot swap protects readers, not writers.
  static std::mutex g_register_mu;
  std::lock_guard<std::mutex> lock(g_register_mu);
  const int index = g_nprotocols.load(std::memory_order_relaxed);
  BRT_CHECK_LT(index, kMaxProtocols);
  g_protocols[index] = p;
  // Clamp: the rebuild below buckets by priority value.
  if (g_protocols[index].scan_priority < 0) {
    g_protocols[index].scan_priority = 0;
  }
  if (g_protocols[index].scan_priority > 100) {
    g_protocols[index].scan_priority = 100;
  }
  // Publish the slot before the count: readers that see the bumped count
  // are guaranteed a fully-written Protocol.
  g_nprotocols.store(index + 1, std::memory_order_release);
  auto* next = new ScanOrder();  // leaked: readers may hold old snapshots
  for (int pri = 0; pri <= 100; ++pri) {
    for (int i = 0; i <= index; ++i) {
      if (g_protocols[i].scan_priority == pri) next->order[next->n++] = i;
    }
  }
  g_scan_order.store(next, std::memory_order_release);
  return index;
}

const Protocol* GetProtocol(int index) {
  const int n = g_nprotocols.load(std::memory_order_acquire);
  return (index >= 0 && index < n) ? &g_protocols[index] : nullptr;
}

int protocol_count() {
  return g_nprotocols.load(std::memory_order_acquire);
}

namespace {

struct ProcessArg {
  const Protocol* proto;
  IOBuf msg;
  SocketId sid;
  RecvStamps stamps;
};

thread_local RecvStamps tls_recv_stamps;

// One ProcessArg per dispatched message: pooled, not malloc'd (reference
// runs these through butil::ObjectPool for the same reason).
ProcessArg* GetProcessArg(const Protocol* proto, IOBuf&& msg, SocketId sid,
                          const RecvStamps& stamps) {
  ProcessArg* a = ObjectPool<ProcessArg>::Get();
  a->proto = proto;
  a->msg = std::move(msg);
  a->sid = sid;
  a->stamps = stamps;
  return a;
}

void PutProcessArg(ProcessArg* a) {
  a->msg.clear();
  ObjectPool<ProcessArg>::Put(a);
}

void* process_entry(void* argp) {
  auto* arg = static_cast<ProcessArg*>(argp);
  tls_recv_stamps = arg->stamps;
  arg->proto->process(std::move(arg->msg), arg->sid);
  PutProcessArg(arg);
  return nullptr;
}

// Cut one message using the socket's remembered protocol first, else scan
// all registered ones (reference CutInputMessage, input_messenger.cpp:77).
// Returns the protocol index, -1 for need-more-data, -2 for fatal.
int cut_message(Socket* s, IOBuf* source, IOBuf* msg) {
  int pref = s->preferred_protocol;
  if (pref >= 0) {
    ParseResult r = g_protocols[pref].parse(source, msg, s);
    if (r == ParseResult::OK) return pref;
    if (r == ParseResult::NOT_ENOUGH_DATA) return -1;
    if (r == ParseResult::ERROR) return -2;
    // TRY_OTHER: fall through to the full scan.
  }
  const ScanOrder* scan = g_scan_order.load(std::memory_order_acquire);
  for (int k = 0; scan != nullptr && k < scan->n; ++k) {
    const int i = scan->order[k];
    if (i == pref) continue;
    ParseResult r = g_protocols[i].parse(source, msg, s);
    if (r == ParseResult::OK) {
      s->preferred_protocol = i;
      return i;
    }
    if (r == ParseResult::NOT_ENOUGH_DATA) {
      s->preferred_protocol = i;
      return -1;
    }
    if (r == ParseResult::ERROR) return -2;
  }
  // No protocol claimed it: if the buffer is still small it may be a
  // not-yet-complete magic; over a small threshold it's garbage.
  return source->size() < 16 ? -1 : -2;
}

}  // namespace

const RecvStamps& CurrentRecvStamps() { return tls_recv_stamps; }

void* InputMessengerOnEdgeTriggered(Socket* s) {
  IOPortal& portal = s->read_buf;
  // The bytes this event reads arrived no later than now: the first byte
  // of a frame that starts in this event is stamped with it.
  const int64_t event_ns = monotonic_ns();
  if (portal.empty()) s->frame_first_byte_ns = event_ns;
  // Read to EAGAIN first; EOF/errors are acted on only AFTER dispatching any
  // complete messages already buffered (a peer may write a full request and
  // immediately close — the reference processes those too).
  int pending_err = 0;
  const char* pending_msg = nullptr;
  for (;;) {
    ssize_t nr = s->AppendFromFd(&portal);
    if (nr == 0) {
      pending_err = ECONNRESET;
      pending_msg = "peer closed connection";
      break;
    }
    if (nr < 0) {
      if (errno == EAGAIN || errno == EWOULDBLOCK) break;
      if (errno == EINTR) continue;
      pending_err = errno;
      pending_msg = "read failed";
      break;
    }
    s->bytes_read.fetch_add(uint64_t(nr), std::memory_order_relaxed);
  }
  // Cut and dispatch all complete messages now buffered.
  std::vector<ProcessArg*> batch;
  for (;;) {
    IOBuf msg;
    int pi = cut_message(s, &portal, &msg);
    if (pi == -1) break;
    if (pi == -2) {
      s->SetFailed(EPROTO, "unparsable input (%zu bytes)", portal.size());
      for (auto* a : batch) PutProcessArg(a);
      return nullptr;
    }
    s->messages_read.fetch_add(1, std::memory_order_relaxed);
    const RecvStamps stamps{s->frame_first_byte_ns, monotonic_ns()};
    // What is left in the buffer begins the next frame.
    s->frame_first_byte_ns = event_ns;
    const Protocol& proto = g_protocols[pi];
    if (proto.is_ordered != nullptr && proto.is_ordered(msg)) {
      // Ordered frames (streams) are handed over NOW, in arrival order —
      // fanning them out to fibers would scramble the stream.
      proto.process(std::move(msg), s->id());
      continue;
    }
    batch.push_back(GetProcessArg(&proto, std::move(msg), s->id(), stamps));
  }
  if (pending_err != 0) {
    s->SetFailed(pending_err, "%s", pending_msg);
  }
  if (batch.empty()) return nullptr;
  g_msg_batches.fetch_add(1, std::memory_order_relaxed);
  g_msg_batched.fetch_add(long(batch.size()), std::memory_order_relaxed);
  // Response write aggregation: each of these messages will produce one
  // write on this socket (server: a response; client: the woken waiter's
  // follow-up request). Hint the socket so those writes coalesce into one
  // writev instead of one sendmsg each — the dominant small-RPC cost
  // (reference thread-jump + KeepWrite batching, input_messenger.cpp:286
  // + socket.cpp:1758).
  if (batch.size() > 1) s->SetWriteBatchHint(int(batch.size()));
  // All but the last message get their own fibers; the last is DEFERRED to
  // the caller ("thread jump": the read fiber becomes the processing fiber
  // — but only after it releases the socket's read gate, so a blocking
  // handler cannot stall this connection's reads).
  for (size_t i = 0; i + 1 < batch.size(); ++i) {
    fiber_t tid;
    if (fiber_start(&tid, process_entry, batch[i]) != 0) {
      process_entry(batch[i]);
    }
  }
  return batch.back();
}

void* InputMessengerProcessDeferred(void* arg) { return process_entry(arg); }

}  // namespace brt
