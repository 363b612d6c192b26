// Protocol-agnostic message ingestion: reads the fd into an IOPortal,
// tries registered protocols in order to cut whole messages (remembering the
// last match per socket), then runs each message's process fn in a fiber —
// the LAST message of a batch runs inline in the reading fiber (the
// reference's "thread jump", input_messenger.cpp:183,286).
// Parity target: reference src/brpc/input_messenger.{h,cpp} +
// protocol.h:77-160 (Protocol as a table of function pointers).
#pragma once

#include "base/iobuf.h"
#include "transport/socket.h"

namespace brt {

enum class ParseResult {
  OK,               // one message cut into *msg
  NOT_ENOUGH_DATA,  // header matches, need more bytes
  TRY_OTHER,        // magic mismatch: not this protocol
  ERROR,            // malformed: fail the socket
};

struct Protocol {
  const char* name;
  // Cut ONE complete message from *source into *msg.
  ParseResult (*parse)(IOBuf* source, IOBuf* msg, Socket* s);
  // Handle a cut message; runs in a fiber. May use s->user() to reach the
  // owning Server/Channel.
  void (*process)(IOBuf&& msg, SocketId sid);
  // Optional: messages answering true are processed INLINE in the read
  // fiber, preserving arrival order (stream frames — the reference routes
  // those through the socket-ordered path into the stream's
  // ExecutionQueue, stream.cpp:447; requests/responses stay parallel).
  bool (*is_ordered)(const IOBuf& msg) = nullptr;
  // Unknown-protocol scan order (lower scans first). Protocols that
  // discriminate on a magic at offset 0 (brt/h2/http) keep 0; ones whose
  // magic sits deeper (nshead @24, mongo opcode @12) or that have no
  // magic at all (esp) must scan AFTER them — their NOT_ENOUGH_DATA on a
  // short prefix would otherwise hold a stream that belongs to a
  // zero-offset protocol (reference orders its protocol array the same
  // way, global.cpp registration order).
  int scan_priority = 0;
};

// Registers at startup (not thread-safe vs traffic; mirror of the
// reference's GlobalInitializeOrDie, global.cpp:409-589). Returns index.
int RegisterProtocol(const Protocol& p);

// When the message now being handed to Protocol::process arrived, on
// CLOCK_MONOTONIC ns: the read event that brought its first byte, and the
// moment it was cut whole from the read buffer. Valid on entry to
// `process` (read it first: the values are the thread's, and the next
// message dispatched on this thread replaces them).
struct RecvStamps {
  int64_t first_byte_ns = 0;
  int64_t complete_ns = 0;
};
const RecvStamps& CurrentRecvStamps();
const Protocol* GetProtocol(int index);
int protocol_count();

// The standard on_edge_triggered callback for RPC sockets. Returns the
// last cut message as a DEFERRED item (Socket::Options.run_deferred must
// be InputMessengerProcessDeferred): the socket runs it after releasing
// its read gate, keeping the thread-jump optimization without letting a
// blocking handler stall the connection's reads.
void* InputMessengerOnEdgeTriggered(Socket* s);
void* InputMessengerProcessDeferred(void* arg);

}  // namespace brt
