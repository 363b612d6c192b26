// Monotonic/realtime clock helpers (reference: src/butil/time.h).
#pragma once

#include <atomic>
#include <cstdint>
#include <ctime>

namespace brt {

inline int64_t monotonic_ns() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return int64_t(ts.tv_sec) * 1000000000 + ts.tv_nsec;
}

inline int64_t monotonic_us() { return monotonic_ns() / 1000; }

// Late stamps: the end of work that outlives the call which started it (a
// response's last byte handed to the socket, an H2D transfer done with its
// host buffer). Whoever traces such work picks a slot, zeroes it, hands it
// down with the call and reads it when it looks at the span; whoever
// finishes the work stamps it, from any thread, with one store. Slot 0
// means "not asked for". The span itself lives in the asker's store: this
// table holds clock readings and nothing else, so a slot reused before a
// very late finish (65,535 asks later) reads that finish's time.
constexpr uint32_t kLateStampSlots = 1u << 16;
inline std::atomic<int64_t> g_late_stamps[kLateStampSlots];

inline void stamp_late(uint32_t slot) {
  if (slot != 0 && slot < kLateStampSlots) {
    g_late_stamps[slot].store(monotonic_ns(), std::memory_order_release);
  }
}

inline int64_t realtime_us() {
  timespec ts;
  clock_gettime(CLOCK_REALTIME, &ts);
  return int64_t(ts.tv_sec) * 1000000 + ts.tv_nsec / 1000;
}

inline timespec us_to_abstime_monotonic(int64_t us_from_now) {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  ts.tv_sec += us_from_now / 1000000;
  ts.tv_nsec += (us_from_now % 1000000) * 1000;
  if (ts.tv_nsec >= 1000000000) {
    ts.tv_sec += 1;
    ts.tv_nsec -= 1000000000;
  }
  return ts;
}

}  // namespace brt
