// rpcz spans: per-RPC trace records with timestamped annotations.
// Parity target: reference src/brpc/span.h:47 + span.cpp —
//   * sampling speed-limited through the shared collector budget
//     (bvar/collector.h:40; here var::RateLimiter),
//   * spans persisted to an on-disk store keyed by time+id with retention
//     (reference SpanDB/LevelDB, span.cpp:354, flags rpcz_database_dir /
//     rpcz_keep_span_seconds, span.cpp:43,56),
//   * trace/span/parent ids propagated through protocol meta so client and
//     server spans of one RPC join under one trace (docs/cn/rpcz.md).
// Redesigned storage: instead of LevelDB, time-bucketed recordio segment
// files (base/recordio.h — CRC-framed, torn-tail-safe) with retention by
// segment age; queries scan newest-first. An in-memory ring fronts the
// disk for the hot list view.
#pragma once

#include <cstdint>
#include <ostream>
#include <string>
#include <vector>

#include "base/endpoint.h"

namespace brt {

class IOBuf;

struct Span {
  uint64_t trace_id = 0;
  uint64_t span_id = 0;
  uint64_t parent_span_id = 0;
  bool server_side = false;
  std::string service, method;
  EndPoint remote;
  int64_t start_us = 0;   // monotonic
  int64_t end_us = 0;
  int64_t start_real_us = 0;  // wall clock at start (display + disk key)
  int error_code = 0;
  std::vector<std::pair<int64_t, std::string>> annotations;

  void annotate(const std::string& text);
  int64_t latency_us() const { return end_us - start_us; }
};

// Where one server-side request was, on CLOCK_MONOTONIC ns (base/time.h):
// stamped unconditionally where each thing happens, no allocation. 0 =
// not reached. These are the boundaries of the request's phases — receive,
// scheduling, the handler, the send — for the native span's annotations
// and for a bound language's span tree (capi brt_session_trace).
struct RequestStamps {
  int64_t first_byte_ns = 0;  // read event that brought the frame's 1st byte
  int64_t complete_ns = 0;    // frame whole and cut from the read buffer
  int64_t dispatch_ns = 0;    // the service's CallMethod entered
  int64_t handler_ns = 0;     // a bound handler called (request flattened)
  int64_t respond_ns = 0;     // respond entered
  // Late-stamp slot (base/time.h) for "written": the response's last
  // byte handed to the socket. Set by a binding that traces this request
  // (capi brt_session_respond); 0 = not asked for.
  uint32_t written_slot = 0;
};

// 0 disables tracing; N → ~N per million unsampled requests start traces.
// A request arriving WITH a trace id is always recorded (propagation).
extern uint32_t FLAGS_rpcz_sample_ppm;
extern uint32_t FLAGS_rpcz_max_spans;       // in-memory ring size
extern uint32_t FLAGS_rpcz_max_per_second;  // collector-style speed limit
extern uint32_t FLAGS_rpcz_keep_span_seconds;  // disk retention

bool SpanShouldSample();
uint64_t SpanRandomId();

// Takes ownership. Speed-limited (FLAGS_rpcz_max_per_second); appended to
// the in-memory ring and, when a database dir is configured, to the
// current disk segment.
void SpanSubmit(Span&& span);

// Text dump of the most recent `max` spans (newest first) — /rpcz list
// view. Each line carries the trace id for drill-down.
void SpanDump(std::ostream& os, size_t max = 100,
              const std::string& filter = "");

// Drill-down: every stored span of `trace_id` (memory + disk), client and
// server sides joined, oldest first. Returns the number of spans shown.
size_t SpanDumpTrace(std::ostream& os, uint64_t trace_id);

// Points the disk store at `dir` (empty = memory only). Creates the
// directory, reopens the active segment, applies retention. Also
// reachable at runtime via /flags/rpcz_database_dir?setvalue=...
void SpanSetDatabaseDir(const std::string& dir);
std::string SpanGetDatabaseDir();

// Serialization (exposed for tests / tools).
void SpanEncode(const Span& s, IOBuf* out);
bool SpanDecode(const IOBuf& in, Span* out);

// Blocks until queued spans have reached disk (the background flusher
// drained). Pthread-blocking: call from a non-worker thread (tests).
void SpanStoreFlush();

// Test hook: drops the in-memory ring and closes the active segment —
// the moral equivalent of a process restart (disk remains).
void SpanStoreReset();

// Registers rpcz flags (idempotent).
void RegisterSpanFlags();

}  // namespace brt
