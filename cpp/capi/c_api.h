// C ABI over the native RPC core for language bindings (Python ctypes —
// brpc_tpu/rpc.py). The reference exposes C++ directly; a flat C surface is
// the TPU build's equivalent of its "thin binding layer" (SURVEY.md intro).
#pragma once

#include <stddef.h>
#include <stdint.h>

#ifdef __cplusplus
extern "C" {
#endif

// ---- server ----

// Handler runs in a fiber. Respond exactly once per session via
// brt_session_respond (may happen after the handler returns — async
// services are first-class, mirroring rpc/server.h Closure semantics).
typedef void (*brt_service_handler)(void* user, const char* method,
                                    const void* req, size_t req_len,
                                    void* session);

void* brt_server_new(void);
// Hosts the in-framework naming registry on this server ("Naming"
// service, JSON-mapped). 0 on success.
int brt_server_add_naming_registry(void* server);
int brt_server_add_service(void* server, const char* name,
                           brt_service_handler handler, void* user);
// addr: "ip:port" (port 0 = ephemeral). Returns 0 on success.
int brt_server_start(void* server, const char* addr);
int brt_server_port(void* server);
void brt_server_stop(void* server);
void brt_server_destroy(void* server);
// Server-wide overload control (rpc/concurrency_limiter.h), enforced in
// the native dispatch path BEFORE any bound-language code runs — shed
// requests answer ELIMIT (2004).  name: "auto" (adaptive
// gradient/Vegas), "constant" (bounded by max_concurrency),
// "timeout[:us]", "" = off.  Must precede brt_server_start; returns 0
// on success, EPERM once the server is running.
int brt_server_set_concurrency_limiter(void* server, const char* name,
                                       int max_concurrency);
// The installed limiter's current ceiling (0 = off/unlimited) — the
// adaptive gauge for the native path.
int brt_server_max_concurrency(void* server);

// stamps_ns (may be NULL; CLOCK_MONOTONIC ns) receives [0] respond
// entered, [1] the response is in its buffer, [2] the bytes that took a
// memcpy. written_slot (0: none): the late stamp (brt_late_stamps) taken
// when the response's last byte has been handed to the socket.
void brt_session_respond(void* session, const void* data, size_t len,
                         int error_code, const char* error_text,
                         int64_t* stamps_ns, uint32_t written_slot);

// ---- request tracing (rpc/span.h; all times CLOCK_MONOTONIC ns) ----

// Where the in-flight request behind `session` has been (call inside the
// handler). Returns the trace id that came over the wire (0: none);
// *parent_span_id (may be NULL): the client's span id that came with it.
// stamps[0..3] (may be NULL): read event that brought the frame's first
// byte; frame whole; the service entered (the request is then flattened
// into the handler's contiguous buffer, one copy); the handler called. A
// binding asks with both NULL first and for the rest only where it traces.
uint64_t brt_session_trace(void* session, uint64_t* parent_span_id,
                           int64_t* stamps);
// Late stamps: the end of work that outlives the call which started it
// (brt_session_respond's written_slot, brt_device_stage_shaped's
// done_slot). The table of *nslots CLOCK_MONOTONIC ns readings lives as
// long as the library. A tracing binding picks a slot in [1, *nslots),
// zeroes it, passes it with the call and reads it later (0: not finished
// yet); slot 0 asks for nothing. The core stamps with one store from
// whichever thread finishes the work.
int64_t* brt_late_stamps(size_t* nslots);
// The next brt_channel_call* made by THIS thread carries trace_id /
// span_id on the wire as the caller's own span: the server's spans join
// it, and the channel records no native span for the call.
void brt_call_trace_next(uint64_t trace_id, uint64_t span_id);

// ---- client ----

// Single-server channel: addr "ip:port". Cluster channel: addr
// "list://...|file://...|dns://..." with lb ("rr","la",...). lb may be
// NULL for single-server.
void* brt_channel_new(const char* addr, const char* lb, int64_t timeout_ms,
                      int max_retry);
// Synchronous call. On success returns 0 and *rsp/*rsp_len hold a
// malloc'd buffer (free with brt_free). On failure returns the error code
// and fills errbuf.
int brt_channel_call(void* channel, const char* service, const char* method,
                     const void* req, size_t req_len, void** rsp,
                     size_t* rsp_len, char* errbuf, size_t errbuf_len);
void brt_channel_destroy(void* channel);

// ---- async client calls (the ParallelChannel fan-out primitive) ----
// Starts `service`.`method` and returns a completion handle immediately;
// the call proceeds on the fiber scheduler (the reference's done-closure
// CallMethod, channel.h:89).  The request bytes are copied before return,
// so the caller's buffer may be freed as soon as this returns.  Never
// NULL for a live channel.
void* brt_channel_call_start(void* channel, const char* service,
                             const char* method, const void* req,
                             size_t req_len);
// Parks the calling fiber (or blocks a non-worker thread) until the call
// behind the handle completes.  Same result contract as brt_channel_call:
// returns 0 with *rsp/*rsp_len a malloc'd buffer (free with brt_free), or
// the error code with errbuf filled.  Join at most once per handle, then
// brt_call_destroy it.
int brt_call_join(void* call, void** rsp, size_t* rsp_len, char* errbuf,
                  size_t errbuf_len);
// Frees the handle.  An un-joined in-flight call is waited for first, so
// destroy-without-join never races the completion closure.
void brt_call_destroy(void* call);

// Like brt_channel_call_start, with per-call controller options
// (reference Controller::set_timeout_ms — per-call values override the
// channel defaults for this one RPC).  timeout_ms: INT64_MIN inherits
// the channel option, -1 means no deadline, >=0 is the per-call
// deadline.  The fault-tolerance tier uses this to shrink the attempt
// timeout as a retry loop's deadline budget drains.
void* brt_channel_call_start_opts(void* channel, const char* service,
                                  const char* method, const void* req,
                                  size_t req_len, int64_t timeout_ms);
// Peek-waits for completion of the call behind the handle WITHOUT
// consuming the result: returns 0 once complete (join still collects),
// ETIMEDOUT if timeout_us elapses first (timeout_us < 0 = forever).
// Callable any number of times — the completion latch is level-
// triggered.  The Python hedge uses one bounded wait here as its arming
// window ("did the primary answer within backup_ms?"); multi-call
// waiting goes through brt_call_group_* below, never a wait loop.
int brt_call_wait(void* call, int64_t timeout_us);
// Requests cancellation of the in-flight call (reference
// Controller::StartCancel): completion still happens exactly once, with
// ECANCELEDRPC (2005) if the cancel won the race.  Safe from any thread,
// any time between start and destroy; idempotent; a no-op on a call
// that already completed.  join/destroy remain mandatory.
void brt_call_cancel(void* call);

// ---- call groups (exact multi-call fan-in) ----
// One CountdownEvent-shaped latch signaled by N done-closures (the
// ParallelChannel fan-in, SURVEY §3.4): hedges and fan-out joins wake
// EXACTLY on completion instead of polling brt_call_wait in time slices.
// Register in-flight calls with brt_call_group_add (a call that already
// completed counts immediately); a group may outlive or predate its
// calls — registration is refcounted, so destroy is safe with members
// still in flight.  Groups observe completion only; join/destroy of each
// call remain the caller's responsibility.
void* brt_call_group_new(void);
// Registers the call (started via brt_channel_call_start*) with the
// group.  Returns 0.  Add each call at most once per group.
int brt_call_group_add(void* group, void* call);
// Parks until EVERY registered call has completed (0), or ETIMEDOUT.
// timeout_us < 0 = forever.  Level-triggered: callable repeatedly.
int brt_call_group_wait(void* group, int64_t timeout_us);
// Wait-any mode: parks until at least one completion has not yet been
// consumed by a previous wait_any, consumes it, returns 0 (or
// ETIMEDOUT).  N calls → N successful wait_any returns, one per
// completion — the hedge loop's exact-wakeup primitive.
int brt_call_group_wait_any(void* group, int64_t timeout_us);
// Completions observed so far (diagnostics/tests).
int brt_call_group_completed(void* group);
void brt_call_group_destroy(void* group);

void brt_free(void* p);

// ---- streaming RPC (ordered, flow-controlled; rpc/stream.h) ----
// A stream is an ordered byte-frame pipe bound to an RPC's connection
// (reference src/brpc/stream.{h,cpp}): the client creates it together
// with a normal RPC, the server accepts it inside the handler, then the
// client writes framed messages at wire rate under credit-based flow
// control — the receiver acknowledges consumed bytes and a writer whose
// unconsumed window (max_buf_size, default 2MB) is full parks until
// credit returns.  This is the gradient-push substrate: per-frame cost
// is one framed socket write, no per-call dispatch/response.
//
// Receive callback: runs SERIALIZED per stream (an ExecutionQueue
// consumer — a slow callback back-pressures the writer through the
// consumed-bytes feedback).  Data frames arrive with closed == 0; the
// final callback is (NULL, 0, closed=1) exactly once, after every data
// frame, when the peer closes gracefully.  NOT invoked on
// brt_stream_abort or peer death without CLOSE.
typedef void (*brt_stream_handler)(void* user, uint64_t stream_id,
                                   const void* data, size_t len,
                                   int closed);

// Client side: creates a stream and binds it by running
// `service`.`method` synchronously on `channel` (the stream settings
// ride the request meta; the stream becomes writable when the RPC
// succeeds).  max_buf_size <= 0 takes the 2MB default.  On success
// returns 0, fills *stream_id and the RPC's response (*rsp malloc'd,
// free with brt_free).  On failure returns the RPC error code, fills
// errbuf, and the half-created stream is aborted — nothing to clean up.
int brt_stream_create(void* channel, const char* service,
                      const char* method, const void* req, size_t req_len,
                      int64_t max_buf_size, uint64_t* stream_id,
                      void** rsp, size_t* rsp_len, char* errbuf,
                      size_t errbuf_len);
// Like brt_stream_create, but the CLIENT side carries a receive handler
// too: the native stream layer is symmetric (both ends StreamWrite
// freely once bound) and `handler` gets the frames the SERVER writes on
// its accepted half — the server->client direction (replica acks,
// progress reports, catch-up data).  Same handler contract as
// brt_stream_accept: serialized delivery, final (NULL, 0, closed=1)
// exactly once after the peer's graceful close or the socket-failure
// teardown.  Tear an rx stream down with brt_stream_close (abort
// suppresses the closed callback and would strand the relay).
int brt_stream_create_rx(void* channel, const char* service,
                         const char* method, const void* req,
                         size_t req_len, int64_t max_buf_size,
                         brt_stream_handler handler, void* user,
                         uint64_t* stream_id, void** rsp, size_t* rsp_len,
                         char* errbuf, size_t errbuf_len);
// Server side: accepts the stream riding the in-flight request behind
// `session` (call INSIDE the handler, BEFORE brt_session_respond).
// `handler` receives the frames; it must stay valid until its
// closed == 1 callback runs (after which the native side forgets it).
// Returns 0 and fills *stream_id, or EINVAL when the request carries no
// stream.
int brt_stream_accept(void* session, int64_t max_buf_size,
                      brt_stream_handler handler, void* user,
                      uint64_t* stream_id);
// Ordered framed write.  Parks the calling fiber/thread while the
// flow-control window is full; *stall_us (may be NULL) receives the
// time spent inside the native write — parked time plus the wait-free
// socket write, i.e. the backpressure stall for any write that did not
// return immediately.  Returns 0, EINVAL (unknown/locally-closed id),
// EPIPE (peer closed), or a socket error.  Writes on one stream must
// come from one caller at a time — concurrent writers interleave frame
// order.
int brt_stream_write(uint64_t stream_id, const void* data, size_t len,
                     int64_t* stall_us);
// Graceful close: in-flight frames drain to the peer IN ORDER before
// its closed callback fires.  Idempotent; 0 always.
int brt_stream_close(uint64_t stream_id);
// Waits until BOTH sides have closed (the peer consumed everything and
// answered CLOSE).  0, or ETIMEDOUT (timeout_us < 0 = forever).
int brt_stream_join(uint64_t stream_id, int64_t timeout_us);
// Abrupt teardown for error paths (failed setup RPC, dead connection):
// wakes writers/joiners, frees the local state, sends nothing.  Only
// for streams without a receive handler still consuming (write-only
// client streams are always safe).  Idempotent; 0 always.
int brt_stream_abort(uint64_t stream_id);

// ---- zero-copy buffer currency (brt_iobuf; capi/iobuf_capi.cc) ----
// An ABI handle over the native IOBuf (cpp/base/iobuf.h): a refcounted
// chain of block references.  Appends either COPY into pooled 8KB blocks
// (brt_iobuf_append/appendv — small headers) or BORROW caller memory
// zero-copy (brt_iobuf_append_user_data — the numpy-grads path); borrowed
// blocks hold the caller's buffer via `release(data, arg)`, which fires
// on the LAST block-ref drop, possibly after the handle itself was
// destroyed (the payload may still sit in a socket write queue or a
// response the peer side borrowed).  Handles are tracked in the handle
// ledger under kind "iobuf"; every constructor below pairs with
// brt_iobuf_destroy.
typedef void (*brt_iobuf_release)(void* data, void* arg);

void* brt_iobuf_new(void);
void brt_iobuf_destroy(void* iobuf);
// Copying append (one pooled-block copy).  Returns 0, EINVAL on NULL.
int brt_iobuf_append(void* iobuf, const void* data, size_t len);
// Copying append of n buffers in order — one ABI crossing for a
// header+payload pair.  Returns 0, EINVAL on NULL input.
int brt_iobuf_appendv(void* iobuf, const void* const* datas,
                      const size_t* lens, int n);
// Zero-copy append of caller-owned memory: the block borrows `data`
// until the last ref drops, then calls `release(data, arg)` exactly
// once.  The caller must keep `data` valid and UNCHANGED until release
// (a mutated borrowed block would change bytes already "sent").
int brt_iobuf_append_user_data(void* iobuf, void* data, size_t len,
                               brt_iobuf_release release, void* arg);
// Shares src's blocks into dst (refcount bump, no payload copy) — the
// prepend-a-header composition: build a small header iobuf, then share
// the big body in behind it.
int brt_iobuf_append_iobuf(void* iobuf, const void* src);
int64_t brt_iobuf_size(const void* iobuf);
// Copies up to `max` bytes starting at `from` into `out`; returns the
// byte count copied (the ONE copy the borrow path still pays when a
// multi-block response must be materialized contiguously).
int64_t brt_iobuf_copy_out(const void* iobuf, void* out, size_t max,
                           size_t from);
// Borrowed block list: count, then per-block data pointer/length.  The
// pointers are valid while the handle lives — the Python side wraps a
// single-block response in a memoryview without copying and pins the
// handle for the view's lifetime.
int brt_iobuf_block_count(const void* iobuf);
const void* brt_iobuf_block_data(const void* iobuf, int i);
int64_t brt_iobuf_block_len(const void* iobuf, int i);

// Synchronous call whose request rides an iobuf (borrowed request blocks
// are NOT copied before the socket write) and whose response comes back
// as a NEW iobuf handle holding the wire blocks (no malloc+copy_to).
// Returns the handle on success; on failure returns NULL with
// *error_code/errbuf filled.  Destroy the returned handle with
// brt_iobuf_destroy.
void* brt_channel_call_iobuf(void* channel, const char* service,
                             const char* method, const void* req_iobuf,
                             int* error_code, char* errbuf,
                             size_t errbuf_len);
// Async variant: like brt_channel_call_start_opts but the request rides
// an iobuf (blocks shared, not copied — keep borrowed request memory
// alive until the call completes).  Join with brt_call_join_iobuf (or
// the copying brt_call_join); destroy with brt_call_destroy as usual.
void* brt_channel_call_start_iobuf(void* channel, const char* service,
                                   const char* method,
                                   const void* req_iobuf,
                                   int64_t timeout_ms);
// Joins the call and MOVES its response into a new iobuf handle (block
// steal, no copy).  Join at most once per call handle (a second join of
// either flavor sees an empty response); brt_call_destroy remains the
// caller's responsibility.  Returns the handle, or NULL with
// *error_code/errbuf filled on RPC failure.
void* brt_call_join_iobuf(void* call, int* error_code, char* errbuf,
                          size_t errbuf_len);
// Responds with the iobuf's blocks shared into the RPC response (no
// payload copy; borrowed blocks stay pinned until the socket write
// drains).  The iobuf handle is NOT consumed — destroy it after.
// stamps_ns / written_slot: as brt_session_respond ([2] is 0 here).
void brt_session_respond_iobuf(void* session, const void* iobuf,
                               int error_code, const char* error_text,
                               int64_t* stamps_ns, uint32_t written_slot);
// Batched ordered writes: each iobuf is ONE framed stream message,
// written in order with a single ABI crossing for the batch.  Stops at
// the first failing write: returns its error code with *nwritten the
// count of fully written frames (0 on success ⇒ *nwritten == n).
// *stall_us (may be NULL) accumulates backpressure time across the
// batch.  Same single-writer rule as brt_stream_write.
int brt_stream_writev(uint64_t stream_id, const void* const* iobufs,
                      int n, int* nwritten, int64_t* stall_us);

// ---- pre-dispatch request drop (fault-injection tier) ----
// Process-global hook consulted for EVERY parsed request before
// dispatch/accounting; returning nonzero silently discards the request
// (no response — the client times out for real, unlike a client-side
// simulated drop).  `port` is the receiving server's listen port, so a
// plan can target one shard of a fleet.  NULL uninstalls; the uninstalled
// cost is one atomic load per request.
typedef int (*brt_drop_hook)(void* user, const char* service,
                             const char* method, int port);
void brt_set_drop_hook(brt_drop_hook hook, void* user);

// ---- native PS shard (zero-Python read path) ----
// A generation-versioned row table serving `Lookup` straight from the
// C++ fiber handler (SURVEY §3.1 — the reference serves all traffic
// natively).  The bound language keeps the WRITE path: it owns the
// mutable table, applies gradients, then publishes an immutable snapshot
// with brt_ps_shard_install.  Readers pin a generation, gather outside
// any lock, unpin; install swaps atomically and the last reader frees a
// retired snapshot (the PR-4 handle-generation scheme, one layer down).
//
// vocab must divide by n_shards; the shard owns rows
// [shard_index*vocab/n_shards, (shard_index+1)*vocab/n_shards).
// Returns NULL on bad arguments.
void* brt_ps_shard_new(int64_t vocab, int64_t dim, int shard_index,
                       int n_shards);
// Publishes a snapshot: copies rows*dim float32 values from `table`
// (the caller may mutate its buffer again the moment this returns).
// rows must equal the shard's rows-per-shard.  0 on success.
int brt_ps_shard_install(void* shard, const void* table, int64_t rows,
                         uint64_t gen);
// Generation of the currently-served snapshot (0 before any install).
uint64_t brt_ps_shard_generation(void* shard);
// Lookups served natively since creation (proves zero-Python serving).
uint64_t brt_ps_shard_native_lookups(void* shard);
// Native Lookup service-time accounting (debug/observability surface,
// brt_debug-style): writes the sum of per-request service times in us
// and the number of requests it covers.  Lets the bound language fold
// the zero-Python read path into its per-server tail-latency stats.
void brt_ps_shard_lookup_stats(void* shard, int64_t* sum_us,
                               int64_t* count);
// Registers a service on `server` whose `Lookup` is served natively from
// `shard`; every other method is dispatched to `fallback` with the
// standard brt_service_handler session contract.  The shard must outlive
// the server.  0 on success.
int brt_server_add_ps_service(void* server, const char* name, void* shard,
                              brt_service_handler fallback, void* user);
// The server using the shard must be destroyed first.
void brt_ps_shard_destroy(void* shard);

// ---- native handle ledger (leak diagnostics) ----
// Ground-truth live-object counts per ABI handle family, bumped by the
// objects themselves at construction/destruction.  The bound language's
// dynamic handle ledger (BRPC_TPU_HANDLECHECK=1) cross-checks its own
// bookkeeping against these — Python knows creation stacks, C++ knows
// the truth.  brt_debug_handle_counts returns a malloc'd "kind count\n"
// table (free with brt_free) covering server/channel/call/call_group/
// ps_shard/event/stream_relay/device_client/device_executable plus
// "stream" (live entries in the stream registry, BOTH directions);
// brt_debug_handle_count returns one kind's count, or -1 for an unknown
// kind name.
char* brt_debug_handle_counts(void);
long brt_debug_handle_count(const char* kind);

// Fault-injection lever for abrupt-death testing: SetFailed()s every live
// client connection whose REMOTE endpoint is `addr` ("ip:port"), exactly
// what happens when the process holding those sockets dies — the peer
// sees EOF and fails its half, which (among other teardown) tears down
// any streams riding the connection.  Returns the number of sockets
// failed, or -1 on a malformed address.  Debug/test surface only.
int brt_debug_fail_connections(const char* addr);

// ---- runtime ----
void brt_init(int fiber_workers);

// ---- device (native PJRT staging — the RDMA-analog tier) ----
// Creates a PJRT client over the given plugin (NULL/"" = $BRT_PJRT_PLUGIN
// or the installed libtpu). NULL on failure; errbuf holds the reason.
void* brt_device_client_new(const char* plugin_path, char* errbuf,
                            size_t errbuf_len);
int brt_device_count(void* client);
// What PJRT reports the client runs on: the platform name ("tpu" for
// libtpu) and the kind of addressable device device_index (e.g. "TPU v5
// lite"), NUL-terminated into buf. brt_device_kind returns 0, or EINVAL
// for a bad index.
void brt_device_platform_name(void* client, char* buf, size_t buf_len);
int brt_device_kind(void* client, int device_index, char* buf,
                    size_t buf_len);
// Addressable index of the device PJRT says holds the buffer behind
// handle; -1 if the handle is stale or the device unknown.
int brt_device_buffer_device(void* client, uint64_t handle);
// DMAs bytes to device memory on device_index; returns a nonzero 64-bit
// buffer handle (the lkey analog carried in IOBuf meta), 0 on failure.
uint64_t brt_device_stage(void* client, const void* data, size_t len,
                          int device_index, char* errbuf, size_t errbuf_len);
// DMAs the buffer behind handle back to host. *out is malloc'd (free with
// brt_free); the calling fiber (or thread) parks while the DMA runs.
// Returns 0 on success. stamps_ns (may be NULL; CLOCK_MONOTONIC ns)
// receives [0] start, [1] D2H landed, [2] layout repacked, [3] copied
// into *out, and [4] the bytes the repack moved (0: landed row-major).
int brt_device_fetch(void* client, uint64_t handle, void** out,
                     size_t* out_len, char* errbuf, size_t errbuf_len,
                     int64_t* stamps_ns);
// Frees the device buffer behind handle. Returns 0, or EINVAL if stale.
int brt_device_release(uint64_t handle);
void brt_device_client_destroy(void* client);

// ---- compiled execution (device/pjrt_executable.h) ----
// Shaped staging for executable arguments. dtype: 0=u8, 1=f32, 2=i32.
// len must equal product(dims)*elemsize. Returns a handle (0 on failure).
// stamps_ns (may be NULL; CLOCK_MONOTONIC ns) receives [0] start, [1]
// data copied into a registered pool block and BufferFromHostBuffer
// about to be called. That call only queues the transfer: done_slot (0:
// none) is the late stamp (brt_late_stamps) taken when the plug-in
// reports it is done with the host block — the transfer's end.
uint64_t brt_device_stage_shaped(void* client, const void* data, size_t len,
                                 int device_index, int dtype,
                                 const int64_t* dims, size_t ndims,
                                 char* errbuf, size_t errbuf_len,
                                 int64_t* stamps_ns, uint32_t done_slot);
// Textual StableHLO from the builtin builders (device/pjrt_executable.h).
// kind: "add"|"reduce_sum"|"all_reduce_sum"|"all_gather" (p0=n,
// p1=replicas) or "gather_rows"|"scatter_sub" (p0=rows, p1=dim, p2=k).
// malloc'd string (free with brt_free); NULL on unknown kind.
char* brt_mlir_module(const char* kind, int64_t p0, int64_t p1, int64_t p2);
// Compiles textual StableHLO for num_replicas; replica r is bound to
// addressable device first_device + r (its arguments must live there).
// NULL on failure.
void* brt_device_compile(void* client, const char* mlir, int num_replicas,
                         int first_device, char* errbuf, size_t errbuf_len);
int brt_device_executable_num_outputs(void* exe);
// Launches across all replicas. args is row-major [nreplicas][nargs]
// buffer handles; outs receives [nreplicas][num_outputs] fresh handles
// (caller must brt_device_release each). The calling fiber/thread parks
// until every replica completes. Returns 0 on success.
int brt_device_execute(void* exe, const uint64_t* args, size_t nargs,
                       size_t nreplicas, uint64_t* outs, size_t outs_cap,
                       char* errbuf, size_t errbuf_len);
void brt_device_executable_destroy(void* exe);

// ---- fiber events (the "yield on TPU stream events" bridge) ----
// A native fiber can wait without blocking its worker pthread while any
// thread (e.g. a JAX async-dispatch completion callback in Python) sets
// the event. This is the bthread↔TPU-stream analog of the BASELINE north
// star ("async RPC handlers enqueue JAX/XLA computations without blocking
// workers").
void* brt_event_new(void);
void brt_event_set(void* event);
// Returns 0 (set) or ETIMEDOUT. timeout_us < 0 = forever.
int brt_event_wait(void* event, int64_t timeout_us);
void brt_event_destroy(void* event);

#ifdef __cplusplus
}
#endif
