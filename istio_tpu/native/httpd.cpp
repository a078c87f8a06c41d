// Native sidecar-facing Mixer front-end: a from-scratch HTTP/2 (h2c
// prior-knowledge) + HPACK + gRPC-framing server speaking the REAL
// unary istio.mixer.v1.Mixer/Check|Report protocol at the wire.
//
// Role (SURVEY §2.9 implication (a), VERDICT r4 item 1): the reference
// terminates sidecar gRPC in Go (mixer/pkg/api/grpcServer.go:118) and
// its per-request cost is goroutine-cheap; this repo's python-grpc
// front caps the box at ~2.4k RPC/s of pure transport. Here the wire
// lives in C++: connections, HTTP/2 framing, HPACK state, request
// envelope splitting and BATCH formation all happen off the GIL;
// python only runs the per-batch engine step (decode → tensorize →
// device → verdicts) through the existing fused path and returns
// serialized CheckResponse bytes that this layer frames back onto the
// wire. Done deliberately WITHOUT a grpc dependency: the image has no
// C++ gRPC/nghttp2 headers, and the subset HTTP/2 a unary gRPC server
// needs (SETTINGS/HEADERS/CONTINUATION/DATA/WINDOW_UPDATE/PING/
// RST_STREAM/GOAWAY + full HPACK decode incl. Huffman and the dynamic
// table) is small enough to own — and owning it is what makes the
// front-end auditable as the data-plane component the survey owes.
//
// Threading model: ONE IO thread owns every socket (poll loop; writes
// and protocol state never race). Decoded requests are queued; python
// "pump" threads block in h2srv_take() (ctypes releases the GIL) and
// receive whole batches under an adaptive policy — a batch dispatches
// when it reaches `min_fill`, when `window_us` has passed since its
// first request, or instantly when a pump is idle and anything is
// queued. Completions enter via h2srv_complete() from pump threads,
// are handed to the IO thread over an eventfd-signalled queue, and are
// framed + written there.
//
// C ABI only (ctypes; no pybind11 in this image).
#include <arpa/inet.h>
#include <errno.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <sys/eventfd.h>
#include <sys/socket.h>
#include <unistd.h>

#include <atomic>
#include <chrono>
#include <cmath>
#include <condition_variable>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <map>
#include <mutex>
#include <string>
#include <thread>
#include <unordered_map>
#include <unordered_set>
#include <vector>

#include "h2_frame.h"
#include "hpack_tables.h"

namespace {

// ------------------------------ HPACK ------------------------------

struct HuffNode {
  int16_t next[2];   // child node index, -1 none
  int16_t sym;       // decoded symbol (0..256), -1 internal
};

struct HuffTrie {
  std::vector<HuffNode> nodes;
  HuffTrie() {
    nodes.push_back({{-1, -1}, -1});
    for (int s = 0; s < 257; s++) {
      uint32_t code = kHuffCodes[s];
      int len = kHuffLens[s];
      int at = 0;
      for (int b = len - 1; b >= 0; b--) {
        int bit = (code >> b) & 1;
        if (nodes[at].next[bit] < 0) {
          nodes[at].next[bit] = static_cast<int16_t>(nodes.size());
          nodes.push_back({{-1, -1}, -1});
        }
        at = nodes[at].next[bit];
      }
      nodes[at].sym = static_cast<int16_t>(s);
    }
  }
};

const HuffTrie& huff_trie() {
  static HuffTrie t;
  return t;
}

bool huff_decode(const uint8_t* p, size_t n, std::string* out) {
  const HuffTrie& t = huff_trie();
  int at = 0;
  int bits_since_sym = 0;
  for (size_t i = 0; i < n; i++) {
    for (int b = 7; b >= 0; b--) {
      int bit = (p[i] >> b) & 1;
      at = t.nodes[at].next[bit];
      if (at < 0) return false;
      bits_since_sym++;
      int sym = t.nodes[at].sym;
      if (sym >= 0) {
        if (sym == 256) return false;  // EOS in data is an error
        out->push_back(static_cast<char>(sym));
        at = 0;
        bits_since_sym = 0;
      }
    }
  }
  // padding: ≤7 bits, all 1s (a prefix of EOS) — lenient on content,
  // strict on length
  return bits_since_sym <= 7;
}

struct HpackDecoder {
  // dynamic table, newest first (RFC 7541 §2.3.2 addressing)
  std::deque<std::pair<std::string, std::string>> dyn;
  size_t dyn_size = 0;
  size_t max_dyn = 4096;   // our advertised SETTINGS_HEADER_TABLE_SIZE

  void evict() {
    while (dyn_size > max_dyn && !dyn.empty()) {
      dyn_size -= dyn.back().first.size() + dyn.back().second.size() + 32;
      dyn.pop_back();
    }
  }
  void add(const std::string& n, const std::string& v) {
    dyn_size += n.size() + v.size() + 32;
    dyn.emplace_front(n, v);
    evict();
  }
  bool lookup(uint64_t idx, std::string* n, std::string* v) {
    if (idx == 0) return false;
    if (idx <= 61) {
      *n = kHpackStatic[idx - 1].name;
      *v = kHpackStatic[idx - 1].value;
      return true;
    }
    size_t di = idx - 62;
    if (di >= dyn.size()) return false;
    *n = dyn[di].first;
    *v = dyn[di].second;
    return true;
  }
};

bool hpack_int(const uint8_t*& p, const uint8_t* end, int prefix,
               uint64_t* out) {
  if (p >= end) return false;
  uint64_t max = (1u << prefix) - 1;
  uint64_t v = *p++ & max;
  if (v < max) { *out = v; return true; }
  int shift = 0;
  while (p < end) {
    uint8_t b = *p++;
    v += static_cast<uint64_t>(b & 0x7f) << shift;
    if (v > (1ull << 32)) return false;   // sanity bound
    if (!(b & 0x80)) { *out = v; return true; }
    shift += 7;
    if (shift > 35) return false;
  }
  return false;
}

bool hpack_str(const uint8_t*& p, const uint8_t* end, std::string* out) {
  if (p >= end) return false;
  bool huff = (*p & 0x80) != 0;
  uint64_t len;
  if (!hpack_int(p, end, 7, &len)) return false;
  if (p + len > end) return false;
  out->clear();
  if (huff) {
    if (!huff_decode(p, len, out)) return false;
  } else {
    out->assign(reinterpret_cast<const char*>(p), len);
  }
  p += len;
  return true;
}

// Decode a complete header block; collects every header (table state
// depends on all of them) and reports the few the server routes on —
// plus the W3C traceparent, which rides the take blob so the python
// engine's rpc.check root span joins the client's trace.
bool hpack_block(HpackDecoder* dec, const uint8_t* p, size_t n,
                 std::string* path, std::string* content_type,
                 std::string* te, std::string* traceparent) {
  const uint8_t* end = p + n;
  while (p < end) {
    uint8_t b = *p;
    std::string name, value;
    if (b & 0x80) {                       // indexed field
      uint64_t idx;
      if (!hpack_int(p, end, 7, &idx)) return false;
      if (!dec->lookup(idx, &name, &value)) return false;
    } else if ((b & 0xe0) == 0x20) {      // dynamic table size update
      uint64_t sz;
      if (!hpack_int(p, end, 5, &sz)) return false;
      if (sz > 4096) return false;        // above our advertised max
      dec->max_dyn = sz;
      dec->evict();
      continue;
    } else {
      bool incremental = (b & 0xc0) == 0x40;
      int prefix = incremental ? 6 : 4;
      uint64_t idx;
      if (!hpack_int(p, end, prefix, &idx)) return false;
      if (idx) {
        std::string ignored;
        if (!dec->lookup(idx, &name, &ignored)) return false;
      } else if (!hpack_str(p, end, &name)) {
        return false;
      }
      if (!hpack_str(p, end, &value)) return false;
      if (incremental) dec->add(name, value);
    }
    if (name == ":path") *path = value;
    else if (name == "content-type") *content_type = value;
    else if (name == "te") *te = value;
    else if (name == "traceparent" && traceparent) *traceparent = value;
  }
  return true;
}

// --------------------------- HTTP/2 bits ---------------------------
// frame constants + put_frame_header live in h2_frame.h (shared with
// the h2load client)

const char kPreface[] = "PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n";
constexpr size_t kPrefaceLen = 24;
constexpr uint32_t kOurWindow = 1u << 30;

// response header blocks are STATELESS hpack (no dynamic-table adds):
// indexed :status 200 + literal-without-indexing content-type
std::string resp_headers_block() {
  std::string b;
  b.push_back(static_cast<char>(0x88));        // :status 200 (static 8)
  b.push_back(static_cast<char>(0x0f));        // literal w/o idx, name
  b.push_back(static_cast<char>(31 - 15));     //   = static 31
  const char ct[] = "application/grpc";
  b.push_back(static_cast<char>(sizeof(ct) - 1));
  b.append(ct, sizeof(ct) - 1);
  return b;
}

void lit_header(std::string* b, const char* name, const std::string& v) {
  b->push_back(0x00);                          // literal w/o idx, new name
  b->push_back(static_cast<char>(strlen(name)));
  b->append(name);
  // values here are short (status ints / messages ≤ 126 bytes after
  // truncation below); keep 7-bit length encoding valid
  std::string vv = v.size() > 120 ? v.substr(0, 120) : v;
  b->push_back(static_cast<char>(vv.size()));
  b->append(vv);
}

// ------------------------- protobuf walking ------------------------
// The request ENVELOPE (CheckRequest / ReportRequest top level) is
// split with a hand varint walker — the payload `attributes` bytes
// pass through to the python/engine side untouched (the shim's
// protobuf decode happens once, there).

struct PbReader {
  const uint8_t* p;
  const uint8_t* end;
  bool ok = true;
  uint64_t varint() {
    uint64_t v = 0;
    int shift = 0;
    while (p < end && shift < 64) {
      uint8_t b = *p++;
      v |= static_cast<uint64_t>(b & 0x7f) << shift;
      if (!(b & 0x80)) return v;
      shift += 7;
    }
    ok = false;
    return 0;
  }
  bool skip(uint32_t wt) {
    switch (wt) {
      case 0: varint(); return ok;
      case 1: if (end - p < 8) return ok = false; p += 8; return true;
      case 2: {
        uint64_t n = varint();
        if (!ok || static_cast<uint64_t>(end - p) < n) return ok = false;
        p += n;
        return true;
      }
      case 5: if (end - p < 4) return ok = false; p += 4; return true;
      default: return ok = false;
    }
  }
  bool bytes_field(std::string* out) {
    uint64_t n = varint();
    if (!ok || static_cast<uint64_t>(end - p) < n) return ok = false;
    out->assign(reinterpret_cast<const char*>(p), n);
    p += n;
    return true;
  }
};

struct QuotaParam {
  std::string name;
  int64_t amount = 0;
  uint8_t best_effort = 0;
};

struct CheckEnvelope {
  std::string attributes;   // raw CompressedAttributes bytes
  uint32_t global_word_count = 0;
  std::string dedup;
  std::vector<QuotaParam> quotas;
};

bool parse_check_envelope(const uint8_t* p, size_t n, CheckEnvelope* out) {
  PbReader r{p, p + n};
  while (r.ok && r.p < r.end) {
    uint64_t tag = r.varint();
    if (!r.ok) return false;
    uint32_t field = static_cast<uint32_t>(tag >> 3);
    uint32_t wt = tag & 7;
    if (field == 1 && wt == 2) {
      if (!r.bytes_field(&out->attributes)) return false;
    } else if (field == 2 && wt == 0) {
      out->global_word_count = static_cast<uint32_t>(r.varint());
    } else if (field == 3 && wt == 2) {
      if (!r.bytes_field(&out->dedup)) return false;
    } else if (field == 4 && wt == 2) {
      std::string entry;
      if (!r.bytes_field(&entry)) return false;
      QuotaParam q;
      PbReader er{reinterpret_cast<const uint8_t*>(entry.data()),
                  reinterpret_cast<const uint8_t*>(entry.data()) +
                      entry.size()};
      while (er.ok && er.p < er.end) {
        uint64_t etag = er.varint();
        if (!er.ok) return false;
        if ((etag >> 3) == 1 && (etag & 7) == 2) {
          if (!er.bytes_field(&q.name)) return false;
        } else if ((etag >> 3) == 2 && (etag & 7) == 2) {
          std::string params;
          if (!er.bytes_field(&params)) return false;
          PbReader pr{reinterpret_cast<const uint8_t*>(params.data()),
                      reinterpret_cast<const uint8_t*>(params.data()) +
                          params.size()};
          while (pr.ok && pr.p < pr.end) {
            uint64_t ptag = pr.varint();
            if (!pr.ok) return false;
            if ((ptag >> 3) == 1 && (ptag & 7) == 0) {
              q.amount = static_cast<int64_t>(pr.varint());
            } else if ((ptag >> 3) == 2 && (ptag & 7) == 0) {
              q.best_effort = pr.varint() ? 1 : 0;
            } else if (!pr.skip(ptag & 7)) {
              return false;
            }
          }
        } else if (!er.skip(etag & 7)) {
          return false;
        }
      }
      out->quotas.push_back(std::move(q));
    } else if (!r.skip(wt)) {
      return false;
    }
  }
  return r.ok;
}

// ------------------------------ server -----------------------------

struct Stream {
  std::string path;
  std::string traceparent;   // W3C trace context request header
  std::string body;          // gRPC-framed request bytes
  bool headers_done = false;
  bool dispatched = false;   // handed to the pump queue
  bool closed = false;       // RST/error — completion is discarded
  int64_t send_window = 65535;
  // wire-to-verdict timestamp: set the instant the request's gRPC
  // frame is fully decoded (enqueue_request), read when the response
  // frames are queued for write — the latency histogram measures
  // EVERYTHING between (queue wait, batch formation, python pump,
  // tensorize, device step, response build), which python-side timers
  // structurally cannot (they never see the C++ queue or framing)
  int64_t t_decode_ns = 0;
  std::string pending_out;   // DATA bytes parked on flow control
  bool trailers_after_data = false;
  std::string trailer_buf;   // trailers to emit once pending_out drains
};

struct PendingItem {
  uint64_t tag;
  uint8_t kind;   // 0 Check, 1 Report
  CheckEnvelope env;
  std::string report_raw;   // kind 1: full ReportRequest bytes
  std::string traceparent;  // request's W3C trace context (may be "")
  int64_t t_enq_ns;
};

// One row of the index take_impl writes in front of a take's heap;
// api/take.py TAKE_ROW mirrors it field for field. Offsets count from
// the blob's first byte; a length of 0 is an absent field.
struct TakeRow {
  uint64_t tag;
  uint32_t payload_off, payload_len;
  uint32_t global_word_count;
  uint32_t dedup_off, dedup_len;
  uint32_t traceparent_off, traceparent_len;
  uint32_t quota_off;
  uint16_t quota_count;
  uint8_t kind;   // 0 Check, 1 Report
  uint8_t reserved[5];
};
static_assert(sizeof(TakeRow) == 48, "api/take.py mirrors this layout");

struct Completion {
  uint64_t tag;
  int32_t grpc_status;
  std::string msg;   // resp proto (status 0) | grpc-message text
};

struct Conn {
  int fd = -1;
  uint32_t gen = 0;
  std::string in;            // unparsed inbound bytes
  std::string out;           // outbound bytes awaiting write
  bool preface_done = false;
  bool goaway_sent = false;
  bool broken = false;       // protocol error seen; drain out + close
  HpackDecoder hpack;
  std::unordered_map<uint32_t, Stream> streams;
  // CONTINUATION state
  uint32_t cont_stream = 0;
  uint8_t cont_flags = 0;
  std::string cont_block;
  bool in_cont = false;
  int64_t send_window = 65535;           // connection-level, theirs
  int64_t remote_initial_window = 65535;
  uint32_t remote_max_frame = 16384;
  uint64_t recv_since_update = 0;
};

struct Server {
  int listen_fd = -1;
  int port = 0;
  int wake_fd = -1;
  std::thread io;
  std::atomic<bool> stopping{false};
  // intake stopped (h2srv_quiesce): new wire requests answer
  // UNAVAILABLE immediately, already-queued rows dispatch to pumps
  // without holding for min_fill/window — the graceful-drain phase
  std::atomic<bool> draining{false};
  // threads currently inside an ABI call on this handle (take/
  // complete/counters/port): stop waits for this to reach zero before
  // freeing the server, so a straggling pump can never use-after-free
  std::atomic<int> abi_calls{0};

  int32_t max_batch = 1024;
  int32_t min_fill = 256;
  int64_t window_us = 2000;
  int32_t n_pumps = 1;
  // continuous batching (the latency lane): an idle pump takes
  // whatever is queued IMMEDIATELY — no min_fill / window_us hold —
  // so a request never waits for a batch to fill; in-flight step
  // pipelining is bounded by n_pumps (each pump runs one step)
  bool continuous = false;
  bool echo = false;
  std::string echo_resp;

  std::mutex mu;                      // guards queue + hist
  std::condition_variable cv;
  std::deque<PendingItem> queue;
  int64_t first_enq_ns = 0;
  int idle_pumps = 0;

  std::mutex cmu;                     // completion queue (pump → IO)
  std::deque<Completion> completions;

  // counters: [0] requests_decoded [1] responses_sent [2] batches
  // [3] batch_rows [4] in_flight [5] conns_opened [6] conns_closed
  // [7] protocol_errors [8] bytes_in [9] bytes_out
  std::atomic<int64_t> counters[10] = {};
  int64_t hist[16] = {0};
  // C++ queue wait: at the moment take_impl hands rows to a pump, the
  // time each row sat in `queue` since enqueue_request stamped it
  // (t_enq_ns, microseconds after the frame-decode stamp). [0] sum of
  // ns, [1] rows. Written under `mu` by the taking pump; read by
  // h2srv_queue_wait.
  std::atomic<int64_t> queue_wait[2] = {};
  // Gaps of kGapNs or more that the front sees with no python running
  // (h2srv_gaps): three kinds x {count, sum of ns}, cumulative.
  //  [0..1] starved: at a handover in take_impl, the time since
  //         first_enq_ns (the first row's arrival, or the previous
  //         handover that left rows queued): rows waited and no pump
  //         came. Written under `mu` by the taking pump.
  //  [2..3] silent: at an enqueue with nothing in flight, the time
  //         since the last response was handed to a connection
  //         (last_resp_ns): the server had answered everything and
  //         the client sent nothing. IO thread only.
  //  [4..5] io: the time between two returns of io_loop's poll less
  //         its time-out (kPollMs): the IO thread itself did not run,
  //         and it holds no python lock. IO thread only.
  static constexpr int64_t kGapNs = 200 * 1000000LL;
  static constexpr int kPollMs = 100;   // io_loop's poll time-out
  std::atomic<int64_t> gaps[6] = {};
  int64_t last_resp_ns = 0;   // IO thread; 0: no response written yet
  // wire-to-verdict latency histogram: 192 log-spaced buckets, bucket
  // i covers latencies up to 1µs·2^(i/8) (ratio 2^(1/8) ≈ 1.09, so a
  // quantile read interpolates within ±4.5%); covers 1µs .. ~16s.
  // Relaxed atomics, same pattern as counters[]: written only by the
  // IO thread per response — a mutex here would put lock traffic on
  // the exact hot path this histogram exists to measure. Read (rare)
  // by h2srv_latency without locking; single-writer makes the
  // min/max read-modify-write races a non-issue.
  static constexpr int kLatBuckets = 192;
  std::atomic<int64_t> lat_hist[kLatBuckets] = {};
  std::atomic<int64_t> lat_min_ns{0};   // 0 = no observation yet
  std::atomic<int64_t> lat_max_ns{0};

  std::unordered_map<uint32_t, Conn*> conns;   // by gen
  uint32_t next_gen = 1;
};

// ------------------------- lifecycle registry -----------------------
// Live-handle set: h2srv_stop erases first (double-stop on the same
// handle becomes a no-op instead of a use-after-free), ABI entry
// points check membership before touching the pointer, and an atexit
// sweep quiesces anything python never stopped so process teardown is
// orderly (no IO thread mid-poll while the runtime unloads). Leaky
// singletons: static-destruction order must never free these while a
// straggler thread is still checking in.

std::mutex& reg_mu() {
  static std::mutex* m = new std::mutex();
  return *m;
}

std::unordered_set<Server*>& live_servers() {
  static std::unordered_set<Server*>* s = new std::unordered_set<Server*>();
  return *s;
}

// RAII abi-call token; acquire() under reg_mu so a stop that already
// erased the handle is seen (the caller then backs off, never touching
// freed memory)
bool abi_enter(Server* srv) {
  std::lock_guard<std::mutex> lk(reg_mu());
  if (!live_servers().count(srv)) return false;
  srv->abi_calls.fetch_add(1, std::memory_order_acq_rel);
  return true;
}

void abi_exit(Server* srv) {
  srv->abi_calls.fetch_sub(1, std::memory_order_acq_rel);
}

void stop_server(Server* srv, bool at_exit);
int64_t take_impl(Server* srv, int32_t timeout_ms, uint8_t* buf,
                  int64_t cap);

void stop_all_at_exit() {
  std::vector<Server*> all;
  {
    std::lock_guard<std::mutex> lk(reg_mu());
    for (Server* s : live_servers()) all.push_back(s);
    live_servers().clear();
  }
  for (Server* s : all) stop_server(s, /*at_exit=*/true);
}

void conn_error(Server* srv, Conn* c, uint32_t code) {
  if (!c->goaway_sent) {
    std::string f;
    put_frame_header(&f, 8, F_GOAWAY, 0, 0);
    uint32_t last = htonl(0), ec = htonl(code);
    f.append(reinterpret_cast<char*>(&last), 4);
    f.append(reinterpret_cast<char*>(&ec), 4);
    c->out += f;
    c->goaway_sent = true;
  }
  srv->counters[7]++;
}

// emit DATA in frames capped at the client's SETTINGS_MAX_FRAME_SIZE
void put_data_frames(Conn* c, uint32_t stream_id,
                     const std::string& data) {
  size_t off = 0;
  do {
    size_t chunk = std::min(data.size() - off,
                            static_cast<size_t>(c->remote_max_frame));
    put_frame_header(&c->out, chunk, F_DATA, 0, stream_id);
    c->out.append(data, off, chunk);
    off += chunk;
  } while (off < data.size());
}

// wire-to-verdict latency observation (IO thread only; lock-free —
// see the lat_hist declaration). Bucket i holds latencies in
// (1µs·2^((i-1)/8), 1µs·2^(i/8)]. Only DISPATCHED streams record:
// pre-dispatch error fast paths (malformed frame, unknown method,
// draining UNAVAILABLE) answer in microseconds and would drag the
// served-verdict quantiles toward zero — the histogram's one job is
// the wire-to-VERDICT number.
void note_gap(Server* srv, int kind, int64_t ns) {
  if (ns < Server::kGapNs) return;
  srv->gaps[2 * kind].fetch_add(1, std::memory_order_relaxed);
  srv->gaps[2 * kind + 1].fetch_add(ns, std::memory_order_relaxed);
}

void record_latency(Server* srv, Stream* st) {
  if (!st->t_decode_ns || !st->dispatched) return;
  int64_t ns = mono_ns() - st->t_decode_ns;
  st->t_decode_ns = 0;
  if (ns < 1) ns = 1;
  double us = static_cast<double>(ns) / 1000.0;
  int idx = us <= 1.0 ? 0
                      : static_cast<int>(std::ceil(std::log2(us) * 8));
  if (idx < 0) idx = 0;
  if (idx >= Server::kLatBuckets) idx = Server::kLatBuckets - 1;
  srv->lat_hist[idx].fetch_add(1, std::memory_order_relaxed);
  int64_t mn = srv->lat_min_ns.load(std::memory_order_relaxed);
  if (!mn || ns < mn)
    srv->lat_min_ns.store(ns, std::memory_order_relaxed);
  if (ns > srv->lat_max_ns.load(std::memory_order_relaxed))
    srv->lat_max_ns.store(ns, std::memory_order_relaxed);
}

// frame up one gRPC response onto the stream (headers + DATA +
// trailers), honoring send windows; parks DATA when blocked
void write_response(Server* srv, Conn* c, uint32_t stream_id,
                    int32_t grpc_status, const std::string& msg) {
  auto it = c->streams.find(stream_id);
  if (it == c->streams.end()) return;
  if (it->second.closed) {   // RST'd while dispatched: drop, reclaim
    c->streams.erase(it);
    return;
  }
  Stream& st = it->second;
  record_latency(srv, &st);

  static const std::string hdr_block = resp_headers_block();
  put_frame_header(&c->out, hdr_block.size(), F_HEADERS, FL_END_HEADERS,
                   stream_id);
  c->out += hdr_block;

  std::string trailers;
  {
    std::string tb;
    lit_header(&tb, "grpc-status", std::to_string(grpc_status));
    if (grpc_status != 0 && !msg.empty())
      lit_header(&tb, "grpc-message", msg);
    put_frame_header(&trailers, tb.size(), F_HEADERS,
                     FL_END_HEADERS | FL_END_STREAM, stream_id);
    trailers += tb;
  }

  if (grpc_status == 0) {
    std::string data;
    data.push_back('\0');
    uint32_t n = htonl(static_cast<uint32_t>(msg.size()));
    data.append(reinterpret_cast<char*>(&n), 4);
    data += msg;
    int64_t len = static_cast<int64_t>(data.size());
    if (st.send_window >= len && c->send_window >= len) {
      st.send_window -= len;
      c->send_window -= len;
      put_data_frames(c, stream_id, data);
      c->out += trailers;
      c->streams.erase(it);
      srv->counters[1]++;
      return;
    }
    // parked: tiny responses only hit this when the client starves
    // its windows; drained on WINDOW_UPDATE/SETTINGS
    st.pending_out = std::move(data);
    st.trailers_after_data = true;
    st.trailer_buf = std::move(trailers);
    return;
  }
  c->out += trailers;
  c->streams.erase(it);
  srv->counters[1]++;
}

void flush_parked(Server* srv, Conn* c) {
  for (auto it = c->streams.begin(); it != c->streams.end();) {
    Stream& st = it->second;
    if (!st.trailers_after_data || st.pending_out.empty()) {
      ++it;
      continue;
    }
    int64_t len = static_cast<int64_t>(st.pending_out.size());
    if (st.send_window >= len && c->send_window >= len) {
      st.send_window -= len;
      c->send_window -= len;
      put_data_frames(c, it->first, st.pending_out);
      c->out += st.trailer_buf;
      srv->counters[1]++;
      it = c->streams.erase(it);
    } else {
      ++it;
    }
  }
}

void enqueue_request(Server* srv, Conn* c, uint32_t stream_id,
                     Stream* st) {
  // frame-decode timestamp: the wire-to-verdict clock starts here —
  // the complete gRPC frame just arrived, nothing downstream has
  // touched it yet (write_response stops the clock)
  st->t_decode_ns = mono_ns();
  // unary gRPC: exactly one length-prefixed message in the body
  if (st->body.size() < 5 || st->body[0] != 0) {
    write_response(srv, c, stream_id, 12,
                   st->body.empty() ? "empty body"
                                    : "compressed requests unsupported");
    return;
  }
  uint32_t mlen;
  memcpy(&mlen, st->body.data() + 1, 4);
  mlen = ntohl(mlen);
  if (st->body.size() < 5 + static_cast<size_t>(mlen)) {
    write_response(srv, c, stream_id, 13, "truncated grpc frame");
    return;
  }
  const uint8_t* msg =
      reinterpret_cast<const uint8_t*>(st->body.data()) + 5;

  uint8_t kind;
  PendingItem item;
  if (st->path == "/istio.mixer.v1.Mixer/Check") {
    kind = 0;
    if (!parse_check_envelope(msg, mlen, &item.env)) {
      write_response(srv, c, stream_id, 13, "bad CheckRequest");
      return;
    }
  } else if (st->path == "/istio.mixer.v1.Mixer/Report") {
    kind = 1;
    item.report_raw.assign(reinterpret_cast<const char*>(msg), mlen);
  } else {
    write_response(srv, c, stream_id, 12, "unknown method " + st->path);
    return;
  }
  if (srv->draining.load(std::memory_order_relaxed)) {
    // intake stopped (graceful drain): a TYPED rejection, never a
    // silent connection drop — the client sees UNAVAILABLE and can
    // retry against a peer. Not dispatched → not latency-recorded
    // (a drain's instant rejections must not drag the verdict
    // quantiles).
    write_response(srv, c, stream_id, 14, "server draining");
    return;
  }

  if (srv->echo) {   // wire-ceiling mode: respond in C++, no engine
    srv->counters[0]++;
    write_response(srv, c, stream_id, 0, srv->echo_resp);
    return;
  }

  // dispatched = handed to the pump queue — set only now, past the
  // error/draining/echo fast paths, so record_latency's dispatched
  // gate admits exactly the wire-to-VERDICT population
  st->dispatched = true;
  st->body.clear();
  st->body.shrink_to_fit();

  item.tag = (static_cast<uint64_t>(c->gen) << 32) | stream_id;
  item.kind = kind;
  item.traceparent = st->traceparent;
  item.t_enq_ns = mono_ns();
  if (srv->last_resp_ns &&
      srv->counters[4].load(std::memory_order_relaxed) == 0)
    note_gap(srv, 1, item.t_enq_ns - srv->last_resp_ns);
  {
    std::lock_guard<std::mutex> lk(srv->mu);
    if (srv->queue.empty()) srv->first_enq_ns = item.t_enq_ns;
    srv->queue.push_back(std::move(item));
  }
  srv->counters[0]++;
  srv->counters[4]++;
  srv->cv.notify_one();
}

// a complete header block arrived (HEADERS or final CONTINUATION):
// initial headers open the stream; a second block on the same stream
// is client trailers — decoded for HPACK table state, content dropped
bool finish_header_block(Server* srv, Conn* c, uint32_t stream_id,
                         uint8_t flags) {
  Stream& st = c->streams[stream_id];
  if (st.headers_done) {
    std::string a, b2, d;
    if (!hpack_block(&c->hpack,
                     reinterpret_cast<const uint8_t*>(
                         c->cont_block.data()),
                     c->cont_block.size(), &a, &b2, &d, nullptr))
      return false;
    if ((flags & FL_END_STREAM) && !st.dispatched)
      enqueue_request(srv, c, stream_id, &st);
    return true;
  }
  std::string ct, te;
  if (!hpack_block(&c->hpack,
                   reinterpret_cast<const uint8_t*>(
                       c->cont_block.data()),
                   c->cont_block.size(), &st.path, &ct, &te,
                   &st.traceparent))
    return false;
  st.headers_done = true;
  st.send_window = c->remote_initial_window;
  if (flags & FL_END_STREAM)
    enqueue_request(srv, c, stream_id, &st);
  return true;
}

// parse as many complete frames as the inbound buffer holds
bool process_in(Server* srv, Conn* c) {
  if (!c->preface_done) {
    if (c->in.size() < kPrefaceLen) return true;
    if (memcmp(c->in.data(), kPreface, kPrefaceLen) != 0) return false;
    c->in.erase(0, kPrefaceLen);
    c->preface_done = true;
  }
  size_t pos = 0;   // cursor: one erase per call, not per frame
  while (c->in.size() - pos >= 9) {
    const uint8_t* hp =
        reinterpret_cast<const uint8_t*>(c->in.data()) + pos;
    uint32_t len = (hp[0] << 16) | (hp[1] << 8) | hp[2];
    if (len > (1u << 24)) return false;
    if (c->in.size() - pos < 9 + len) break;
    uint8_t type = hp[3], flags = hp[4];
    uint32_t stream_id;
    memcpy(&stream_id, hp + 5, 4);
    stream_id = ntohl(stream_id) & 0x7fffffffu;
    const uint8_t* payload = hp + 9;

    if (c->in_cont && type != F_CONT) return false;

    switch (type) {
      case F_SETTINGS: {
        if (flags & FL_ACK) break;
        if (len % 6) return false;
        for (uint32_t off = 0; off + 6 <= len; off += 6) {
          uint16_t id = (payload[off] << 8) | payload[off + 1];
          uint32_t val;
          memcpy(&val, payload + off + 2, 4);
          val = ntohl(val);
          if (id == 4) {   // INITIAL_WINDOW_SIZE
            int64_t delta = static_cast<int64_t>(val) -
                            c->remote_initial_window;
            c->remote_initial_window = val;
            for (auto& kv : c->streams) kv.second.send_window += delta;
          } else if (id == 5 && val >= 16384) {   // MAX_FRAME_SIZE
            c->remote_max_frame = val;
          }
        }
        put_frame_header(&c->out, 0, F_SETTINGS, FL_ACK, 0);
        flush_parked(srv, c);
        break;
      }
      case F_PING: {
        if (len != 8) return false;
        if (!(flags & FL_ACK)) {
          put_frame_header(&c->out, 8, F_PING, FL_ACK, 0);
          c->out.append(reinterpret_cast<const char*>(payload), 8);
        }
        break;
      }
      case F_WINUPD: {
        if (len != 4) return false;
        uint32_t inc;
        memcpy(&inc, payload, 4);
        inc = ntohl(inc) & 0x7fffffffu;
        if (stream_id == 0) {
          c->send_window += inc;
        } else {
          auto it = c->streams.find(stream_id);
          if (it != c->streams.end()) it->second.send_window += inc;
        }
        flush_parked(srv, c);
        break;
      }
      case F_HEADERS: {
        if (stream_id == 0) return false;
        const uint8_t* p = payload;
        uint32_t n = len;
        if (flags & FL_PADDED) {
          if (!n) return false;
          uint8_t pad = *p++;
          n--;
          if (pad > n) return false;
          n -= pad;
        }
        if (flags & FL_PRIORITY_FLAG) {
          if (n < 5) return false;
          p += 5;
          n -= 5;
        }
        c->cont_stream = stream_id;
        c->cont_flags = flags;
        c->cont_block.assign(reinterpret_cast<const char*>(p), n);
        if (flags & FL_END_HEADERS) {
          c->in_cont = false;
          if (!finish_header_block(srv, c, stream_id, flags))
            return false;
        } else {
          c->in_cont = true;
        }
        break;
      }
      case F_CONT: {
        if (!c->in_cont || stream_id != c->cont_stream) return false;
        c->cont_block.append(reinterpret_cast<const char*>(payload),
                             len);
        if (flags & FL_END_HEADERS) {
          c->in_cont = false;
          if (!finish_header_block(srv, c, stream_id, c->cont_flags))
            return false;
        }
        break;
      }
      case F_DATA: {
        if (stream_id == 0) return false;
        const uint8_t* p = payload;
        uint32_t n = len;
        if (flags & FL_PADDED) {
          if (!n) return false;
          uint8_t pad = *p++;
          n--;
          if (pad > n) return false;
          n -= pad;
        }
        auto it = c->streams.find(stream_id);
        if (it != c->streams.end() && !it->second.dispatched) {
          it->second.body.append(reinterpret_cast<const char*>(p), n);
          if (it->second.body.size() > (1u << 24)) return false;
          if (flags & FL_END_STREAM)
            enqueue_request(srv, c, stream_id, &it->second);
        }
        // connection window top-up (we granted 1GB upfront)
        c->recv_since_update += len;
        if (c->recv_since_update >= (1u << 20)) {
          put_frame_header(&c->out, 4, F_WINUPD, 0, 0);
          uint32_t inc = htonl(
              static_cast<uint32_t>(c->recv_since_update));
          c->out.append(reinterpret_cast<char*>(&inc), 4);
          c->recv_since_update = 0;
        }
        break;
      }
      case F_RST: {
        if (len != 4 || stream_id == 0) return false;
        auto it = c->streams.find(stream_id);
        if (it != c->streams.end()) {
          it->second.closed = true;
          if (!it->second.dispatched) c->streams.erase(it);
        }
        break;
      }
      case F_GOAWAY:
        break;   // client is draining; keep serving open streams
      case F_PRIORITY:
      case F_PUSH:
      default:
        break;   // ignore (PUSH from a client is protocol-noise)
    }
    srv->counters[8] += 9 + len;
    pos += 9 + len;
  }
  if (pos) c->in.erase(0, pos);
  return true;
}

void close_conn(Server* srv, Conn* c) {
  srv->conns.erase(c->gen);
  if (c->fd >= 0) close(c->fd);
  srv->counters[6]++;
  delete c;
}

void io_loop(Server* srv) {
  std::vector<pollfd> pfds;
  std::vector<Conn*> order;
  int64_t polled_ns = mono_ns();
  while (!srv->stopping.load(std::memory_order_relaxed)) {
    pfds.clear();
    order.clear();
    pfds.push_back({srv->listen_fd, POLLIN, 0});
    pfds.push_back({srv->wake_fd, POLLIN, 0});
    for (auto& kv : srv->conns) {
      short ev = POLLIN;
      if (!kv.second->out.empty()) ev |= POLLOUT;
      pfds.push_back({kv.second->fd, ev, 0});
      order.push_back(kv.second);
    }
    int rc = poll(pfds.data(), pfds.size(), Server::kPollMs);
    if (rc < 0 && errno != EINTR) break;
    const int64_t now_ns = mono_ns();
    note_gap(srv, 2, now_ns - polled_ns - Server::kPollMs * 1000000LL);
    polled_ns = now_ns;

    // batch-window wakeups: a pump waiting out a window needs a
    // notify when the window expires even with no IO
    srv->cv.notify_all();

    if (pfds[1].revents & POLLIN) {
      uint64_t x;
      while (read(srv->wake_fd, &x, 8) > 0) {}
    }
    // drain completions (frame + queue bytes on the owning conn)
    {
      std::deque<Completion> done;
      {
        std::lock_guard<std::mutex> lk(srv->cmu);
        done.swap(srv->completions);
      }
      for (auto& comp : done) {
        uint32_t gen = static_cast<uint32_t>(comp.tag >> 32);
        uint32_t sid = static_cast<uint32_t>(comp.tag & 0xffffffffu);
        auto it = srv->conns.find(gen);
        srv->counters[4]--;
        if (it != srv->conns.end())
          write_response(srv, it->second, sid, comp.grpc_status,
                         comp.msg);
      }
      if (!done.empty()) srv->last_resp_ns = mono_ns();
    }
    if (pfds[0].revents & POLLIN) {
      while (true) {
        int fd = accept4(srv->listen_fd, nullptr, nullptr,
                         SOCK_NONBLOCK);
        if (fd < 0) break;
        int one = 1;
        setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
        Conn* c = new Conn();
        c->fd = fd;
        c->gen = srv->next_gen++;
        srv->conns[c->gen] = c;
        srv->counters[5]++;
        // server preface: SETTINGS + big connection window
        std::string f;
        put_frame_header(&f, 12, F_SETTINGS, 0, 0);
        const uint16_t ids[2] = {4, 3};      // INITIAL_WINDOW, MAX_STREAMS
        const uint32_t vals[2] = {kOurWindow, 65535};
        for (int i = 0; i < 2; i++) {
          char s[6];
          s[0] = static_cast<char>(ids[i] >> 8);
          s[1] = static_cast<char>(ids[i] & 0xff);
          uint32_t v = htonl(vals[i]);
          memcpy(s + 2, &v, 4);
          f.append(s, 6);
        }
        put_frame_header(&f, 4, F_WINUPD, 0, 0);
        uint32_t inc = htonl(kOurWindow - 65535);
        f.append(reinterpret_cast<char*>(&inc), 4);
        c->out += f;
      }
    }
    // per-conn IO
    for (size_t i = 2; i < pfds.size(); i++) {
      Conn* c = order[i - 2];
      if (pfds[i].revents & (POLLERR | POLLHUP | POLLNVAL)) {
        close_conn(srv, c);
        continue;
      }
      if (pfds[i].revents & POLLIN) {
        char buf[65536];
        bool dead = false;
        while (true) {
          ssize_t n = read(c->fd, buf, sizeof(buf));
          if (n > 0) {
            if (!c->broken) c->in.append(buf, n);
            if (c->in.size() > (1u << 26)) { dead = true; break; }
          } else if (n == 0) {
            dead = true;
            break;
          } else {
            if (errno != EAGAIN && errno != EWOULDBLOCK) dead = true;
            break;
          }
        }
        if (!dead && !c->broken && !process_in(srv, c)) {
          conn_error(srv, c, 1);   // PROTOCOL_ERROR
          c->broken = true;
          dead = c->out.empty();
        }
        if (dead) {
          close_conn(srv, c);
          continue;
        }
      }
      if (!c->out.empty()) {
        ssize_t n = write(c->fd, c->out.data(), c->out.size());
        if (n > 0) {
          srv->counters[9] += n;
          c->out.erase(0, n);
        } else if (n < 0 && errno != EAGAIN && errno != EWOULDBLOCK) {
          close_conn(srv, c);
          continue;
        }
        if ((c->goaway_sent || c->broken) && c->out.empty())
          close_conn(srv, c);
      }
    }
  }
  // shutdown: answer everything already completed (including the
  // typed rejections stop_server queued for rows no pump will take),
  // then best-effort flush outbound bytes so clients SEE their
  // responses before the close — a silently dropped in-flight request
  // is the failure mode this drain exists to prevent
  {
    std::deque<Completion> done;
    {
      std::lock_guard<std::mutex> lk(srv->cmu);
      done.swap(srv->completions);
    }
    for (auto& comp : done) {
      uint32_t gen = static_cast<uint32_t>(comp.tag >> 32);
      uint32_t sid = static_cast<uint32_t>(comp.tag & 0xffffffffu);
      auto it = srv->conns.find(gen);
      srv->counters[4]--;
      if (it != srv->conns.end())
        write_response(srv, it->second, sid, comp.grpc_status,
                       comp.msg);
    }
  }
  // bounded flush (~200ms): a client that starves its flow-control
  // windows must not hold the stop hostage
  int64_t flush_deadline = mono_ns() + 200 * 1000000LL;
  bool pending = true;
  while (pending && mono_ns() < flush_deadline) {
    pending = false;
    for (auto& kv : srv->conns) {
      Conn* c = kv.second;
      if (c->out.empty()) continue;
      ssize_t n = write(c->fd, c->out.data(), c->out.size());
      if (n > 0) {
        srv->counters[9] += n;
        c->out.erase(0, static_cast<size_t>(n));
      } else if (n < 0 && errno != EAGAIN && errno != EWOULDBLOCK) {
        c->out.clear();
        continue;
      }
      if (!c->out.empty()) pending = true;
    }
    if (pending)
      std::this_thread::sleep_for(std::chrono::milliseconds(2));
  }
  std::vector<Conn*> all;
  for (auto& kv : srv->conns) all.push_back(kv.second);
  for (Conn* c : all) close_conn(srv, c);
}

// Ordered teardown (the graceful-lifecycle plane's native leg):
//   1. stop intake + mark stopping (pumps in take return -1, the IO
//      loop exits its poll cycle);
//   2. convert rows no pump will ever take into typed UNAVAILABLE
//      completions (drained + flushed by the IO thread's shutdown
//      path — zero silently dropped in-flight requests);
//   3. join the IO thread;
//   4. wait for every in-flight ABI caller to leave before freeing —
//      a pump wedged inside take gets the handle LEAKED, never freed
//      under it (a stall must stay a stall, not become a segfault).
// Callers must have erased the handle from live_servers() first (the
// double-stop guard), so no NEW abi_enter can succeed while we wait.
void stop_server(Server* srv, bool at_exit) {
  srv->draining.store(true);
  srv->stopping.store(true);
  srv->cv.notify_all();
  {
    std::lock_guard<std::mutex> lk(srv->mu);
    std::lock_guard<std::mutex> lk2(srv->cmu);
    while (!srv->queue.empty()) {
      Completion comp;
      comp.tag = srv->queue.front().tag;
      comp.grpc_status = 14;
      comp.msg = "server shutting down";
      srv->completions.push_back(std::move(comp));
      srv->queue.pop_front();
    }
  }
  uint64_t one = 1;
  ssize_t ignored = write(srv->wake_fd, &one, 8);
  (void)ignored;
  if (srv->io.joinable()) srv->io.join();
  for (int i = 0; i < 5000; i++) {   // ~5s bound
    if (srv->abi_calls.load(std::memory_order_acquire) == 0) break;
    std::this_thread::sleep_for(std::chrono::milliseconds(1));
  }
  if (srv->abi_calls.load(std::memory_order_acquire) > 0) {
    // a straggler is still inside take/complete: leak the server (fds
    // included — closing them could hand recycled fd numbers to its
    // next syscall) rather than free memory under a live thread
    return;
  }
  close(srv->listen_fd);
  close(srv->wake_fd);
  if (!at_exit) delete srv;
  // at exit: frozen interpreter threads may still hold the pointer —
  // the process is dying, the leak is free, the UAF would not be
}

}  // namespace

extern "C" {

void* h2srv_start(int32_t port, int32_t max_batch, int32_t min_fill,
                  int64_t window_us, int32_t n_pumps,
                  int32_t echo_mode, int32_t continuous) {
  Server* srv = new Server();
  srv->max_batch = max_batch > 0 ? max_batch : 1024;
  srv->min_fill = min_fill > 0 ? min_fill : 256;
  srv->window_us = window_us > 0 ? window_us : 2000;
  srv->n_pumps = n_pumps > 0 ? n_pumps : 1;
  srv->continuous = continuous != 0;
  srv->echo = echo_mode != 0;
  if (srv->echo) {
    // fixed OK CheckResponse: precondition{status{} dur{5s} uses 10000}
    // (field 2 msg: {1:{},2:{1:5},3:10000})
    const uint8_t resp[] = {0x12, 0x09, 0x0a, 0x00, 0x12, 0x02, 0x08,
                            0x05, 0x18, 0x90, 0x4e};
    srv->echo_resp.assign(reinterpret_cast<const char*>(resp),
                          sizeof(resp));
  }

  srv->listen_fd = socket(AF_INET, SOCK_STREAM | SOCK_NONBLOCK, 0);
  int one = 1;
  setsockopt(srv->listen_fd, SOL_SOCKET, SO_REUSEADDR, &one,
             sizeof(one));
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
  addr.sin_port = htons(static_cast<uint16_t>(port));
  if (bind(srv->listen_fd, reinterpret_cast<sockaddr*>(&addr),
           sizeof(addr)) != 0 ||
      listen(srv->listen_fd, 512) != 0) {
    close(srv->listen_fd);
    delete srv;
    return nullptr;
  }
  socklen_t alen = sizeof(addr);
  getsockname(srv->listen_fd, reinterpret_cast<sockaddr*>(&addr),
              &alen);
  srv->port = ntohs(addr.sin_port);
  srv->wake_fd = eventfd(0, EFD_NONBLOCK);
  srv->io = std::thread(io_loop, srv);
  {
    std::lock_guard<std::mutex> lk(reg_mu());
    live_servers().insert(srv);
    static bool atexit_registered = false;
    if (!atexit_registered) {
      atexit_registered = true;
      std::atexit(stop_all_at_exit);
    }
  }
  return srv;
}

int32_t h2srv_port(void* h) {
  Server* srv = static_cast<Server*>(h);
  if (!abi_enter(srv)) return 0;
  int32_t p = srv->port;
  abi_exit(srv);
  return p;
}

// Graceful-drain entry (ordered shutdown step 1, callable long before
// h2srv_stop): stop intake — new wire requests answer UNAVAILABLE
// immediately, queued rows dispatch to pumps without holding for
// min_fill/window. Connections stay open and in-flight rows complete
// normally; the caller polls counters()[in_flight] down to zero, THEN
// stops pumps and calls h2srv_stop.
void h2srv_quiesce(void* h) {
  Server* srv = static_cast<Server*>(h);
  if (!abi_enter(srv)) return;
  srv->draining.store(true);
  srv->cv.notify_all();
  uint64_t one = 1;
  ssize_t ignored = write(srv->wake_fd, &one, 8);
  (void)ignored;
  abi_exit(srv);
}

// Blocking batch take (pump side). Adaptive policy (the saturation-
// batcher fix the python batcher's fixed window lacked): dispatch when
// the queue reaches min_fill; dispatch IMMEDIATELY when every pump is
// idle (nothing in flight → a waiting request buys nothing by
// waiting — light-load latency is one trip); otherwise a trip is in
// flight, and this pump holds out for min_fill or window_us — tiny
// trips never ride a busy transport. Returns bytes written, 0 on
// timeout, -needed if the buffer is too small, -1 on shutdown.
int64_t h2srv_take(void* h, int32_t timeout_ms, uint8_t* buf,
                   int64_t cap) {
  Server* srv = static_cast<Server*>(h);
  if (!abi_enter(srv)) return -1;   // already stopped: shutdown signal
  int64_t rc = take_impl(srv, timeout_ms, buf, cap);
  abi_exit(srv);
  return rc;
}

}  // extern "C"

namespace {

int64_t take_impl(Server* srv, int32_t timeout_ms, uint8_t* buf,
                  int64_t cap) {
  std::unique_lock<std::mutex> lk(srv->mu);
  auto deadline = std::chrono::steady_clock::now() +
                  std::chrono::milliseconds(timeout_ms);
  srv->idle_pumps++;
  while (true) {
    if (srv->stopping.load(std::memory_order_relaxed)) {
      srv->idle_pumps--;
      return -1;
    }
    if (!srv->queue.empty()) {
      int64_t waited_us = (mono_ns() - srv->first_enq_ns) / 1000;
      if (srv->continuous ||
          static_cast<int32_t>(srv->queue.size()) >= srv->min_fill ||
          srv->idle_pumps == srv->n_pumps ||
          waited_us >= srv->window_us ||
          srv->draining.load(std::memory_order_relaxed)) {
        // continuous: the latency lane — an idle pump launches the
        // next step the moment anything is queued (the previous step
        // is already dispatched on another pump; in-flight depth is
        // bounded by n_pumps). A request NEVER waits for a batch to
        // fill. draining: already-queued rows dispatch IMMEDIATELY —
        // a shutdown must never hold submitted work for min_fill.
        break;   // this pump takes the batch
      }
      // wait out the window (bounded; re-checked on every enqueue)
      srv->cv.wait_for(lk, std::chrono::microseconds(
                               srv->window_us - waited_us + 100));
      continue;
    }
    if (srv->cv.wait_until(lk, deadline) == std::cv_status::timeout &&
        srv->queue.empty()) {
      srv->idle_pumps--;
      return 0;
    }
  }
  srv->idle_pumps--;

  int32_t n = static_cast<int32_t>(srv->queue.size());
  if (n > srv->max_batch) n = srv->max_batch;
  // size pass: the index, then each row's bytes on the heap. Offsets
  // are 32 bits, so a take stops at the row that would pass them (a
  // message is 16 MiB at most: the first row always fits)
  int64_t need = 8 + static_cast<int64_t>(n) * sizeof(TakeRow);
  for (int32_t i = 0; i < n; i++) {
    const PendingItem& it = srv->queue[i];
    int64_t row =
        (it.kind ? it.report_raw.size() : it.env.attributes.size()) +
        it.env.dedup.size() + it.traceparent.size();
    for (const auto& q : it.env.quotas) row += 4 + q.name.size() + 9;
    if (need + row > UINT32_MAX) {
      need -= static_cast<int64_t>(n - i) * sizeof(TakeRow);
      n = i;
      break;
    }
    need += row;
  }
  if (need > cap) return -need;

  // Blob: u32 batch number, u32 n, n TakeRow, then the heap the rows
  // point into, written straight into the pump's buffer.
  auto put = [buf](int64_t at, const void* p, size_t len) {
    memcpy(buf + at, p, len);
    return static_cast<uint32_t>(at);
  };
  const uint32_t head[2] = {static_cast<uint32_t>(srv->counters[2]),
                            static_cast<uint32_t>(n)};
  put(0, head, 8);
  int64_t heap = 8 + static_cast<int64_t>(n) * sizeof(TakeRow);
  const int64_t t_take_ns = mono_ns();
  note_gap(srv, 0, t_take_ns - srv->first_enq_ns);
  int64_t waited_ns = 0;
  for (int32_t i = 0; i < n; i++) {
    PendingItem& it = srv->queue.front();
    waited_ns += t_take_ns - it.t_enq_ns;
    const std::string& payload =
        it.kind ? it.report_raw : it.env.attributes;
    TakeRow row = {};
    row.tag = it.tag;
    row.kind = it.kind;
    row.global_word_count = it.env.global_word_count;
    row.payload_off = put(heap, payload.data(), payload.size());
    row.payload_len = static_cast<uint32_t>(payload.size());
    heap += payload.size();
    row.dedup_off = put(heap, it.env.dedup.data(), it.env.dedup.size());
    row.dedup_len = static_cast<uint32_t>(it.env.dedup.size());
    heap += it.env.dedup.size();
    row.traceparent_off =
        put(heap, it.traceparent.data(), it.traceparent.size());
    row.traceparent_len = static_cast<uint32_t>(it.traceparent.size());
    heap += it.traceparent.size();
    // quota section: per quota u32 name length, name, i64 amount,
    // u8 best_effort
    row.quota_off = static_cast<uint32_t>(heap);
    row.quota_count = static_cast<uint16_t>(it.env.quotas.size());
    for (const auto& q : it.env.quotas) {
      const uint32_t nlen = static_cast<uint32_t>(q.name.size());
      put(heap, &nlen, 4);
      put(heap + 4, q.name.data(), nlen);
      put(heap + 4 + nlen, &q.amount, 8);
      put(heap + 12 + nlen, &q.best_effort, 1);
      heap += 4 + nlen + 9;
    }
    put(8 + static_cast<int64_t>(i) * sizeof(TakeRow), &row,
        sizeof(TakeRow));
    srv->queue.pop_front();
  }
  if (!srv->queue.empty()) srv->first_enq_ns = mono_ns();
  srv->counters[2]++;
  srv->counters[3] += n;
  srv->queue_wait[0].fetch_add(waited_ns, std::memory_order_relaxed);
  srv->queue_wait[1].fetch_add(n, std::memory_order_relaxed);
  int b = 0;
  while ((1 << b) < n && b < 15) b++;
  srv->hist[b]++;
  return heap;
}

}  // namespace

extern "C" {

// Completion blob: u32 n, then per item u64 tag, i32 grpc_status,
// u32 len, bytes (resp proto when status 0, else grpc-message text).
void h2srv_complete(void* h, const uint8_t* blob, int64_t len) {
  Server* srv = static_cast<Server*>(h);
  if (!abi_enter(srv)) return;   // stopped under a deferred completion
  const uint8_t* p = blob;
  const uint8_t* end = blob + len;
  if (end - p < 4) {
    abi_exit(srv);
    return;
  }
  uint32_t n;
  memcpy(&n, p, 4);
  p += 4;
  std::deque<Completion> out;
  for (uint32_t i = 0; i < n && p + 16 <= end; i++) {
    Completion comp;
    memcpy(&comp.tag, p, 8);
    p += 8;
    memcpy(&comp.grpc_status, p, 4);
    p += 4;
    uint32_t mlen;
    memcpy(&mlen, p, 4);
    p += 4;
    if (p + mlen > end) break;
    comp.msg.assign(reinterpret_cast<const char*>(p), mlen);
    p += mlen;
    out.push_back(std::move(comp));
  }
  {
    std::lock_guard<std::mutex> lk(srv->cmu);
    for (auto& comp : out) srv->completions.push_back(std::move(comp));
  }
  uint64_t one = 1;
  ssize_t ignored = write(srv->wake_fd, &one, 8);
  (void)ignored;
  abi_exit(srv);
}

void h2srv_counters(void* h, int64_t* out, int64_t* hist) {
  Server* srv = static_cast<Server*>(h);
  if (!abi_enter(srv)) {
    memset(out, 0, 10 * sizeof(int64_t));
    memset(hist, 0, 16 * sizeof(int64_t));
    return;
  }
  for (int i = 0; i < 10; i++)
    out[i] = srv->counters[i].load(std::memory_order_relaxed);
  {
    std::lock_guard<std::mutex> lk(srv->mu);
    memcpy(hist, srv->hist, sizeof(srv->hist));
  }
  abi_exit(srv);
}

// Queue wait of the rows handed to pumps so far: out[0] = sum over
// rows of (take time - enqueue time) in ns, out[1] = rows. Cumulative;
// the python side reads mean wait per row from snapshot deltas.
void h2srv_queue_wait(void* h, int64_t* out) {
  Server* srv = static_cast<Server*>(h);
  out[0] = out[1] = 0;
  if (!abi_enter(srv)) return;
  out[0] = srv->queue_wait[0].load(std::memory_order_relaxed);
  out[1] = srv->queue_wait[1].load(std::memory_order_relaxed);
  abi_exit(srv);
}

// The front's gaps (Server::gaps): out[6] = starved, silent, io, each
// {count, sum of ns}; cumulative.
void h2srv_gaps(void* h, int64_t* out) {
  Server* srv = static_cast<Server*>(h);
  memset(out, 0, 6 * sizeof(int64_t));
  if (!abi_enter(srv)) return;
  for (int i = 0; i < 6; i++)
    out[i] = srv->gaps[i].load(std::memory_order_relaxed);
  abi_exit(srv);
}

// Wire-to-verdict latency histogram snapshot: 192 log-spaced bucket
// counts (bucket i ≤ 1µs·2^(i/8)) into `out`, observed [min_ns,
// max_ns] into `minmax[2]`. Counts are CUMULATIVE since start — the
// python side computes per-window quantiles from snapshot deltas.
void h2srv_latency(void* h, int64_t* out, int64_t* minmax) {
  Server* srv = static_cast<Server*>(h);
  if (!abi_enter(srv)) {
    memset(out, 0, Server::kLatBuckets * sizeof(int64_t));
    minmax[0] = minmax[1] = 0;
    return;
  }
  for (int i = 0; i < Server::kLatBuckets; i++)
    out[i] = srv->lat_hist[i].load(std::memory_order_relaxed);
  minmax[0] = srv->lat_min_ns.load(std::memory_order_relaxed);
  minmax[1] = srv->lat_max_ns.load(std::memory_order_relaxed);
  abi_exit(srv);
}

void h2srv_stop(void* h) {
  Server* srv = static_cast<Server*>(h);
  {
    // double-stop guard: only the caller that actually erases the
    // live entry tears the server down; any later stop (or a stop
    // racing the atexit sweep) is a no-op instead of a use-after-free
    std::lock_guard<std::mutex> lk(reg_mu());
    if (!live_servers().erase(srv)) return;
  }
  stop_server(srv, /*at_exit=*/false);
}

}  // extern "C"
