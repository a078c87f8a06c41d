// Closed-loop load client of the benchmark (copied from
// istio_tpu/native/h2load.cpp, which records a COUNT of requests; this
// copy records a WINDOW of seconds and is the yardstick's own).
//
// One HTTP/2 h2c connection, `depth` unary gRPC streams in flight,
// payloads cycled from a file of u32-length-prefixed serialized
// request messages. Header blocks are literal-without-indexing
// (stateless HPACK, RFC 7541), so the request block is a constant.
//
// stdout, two JSON lines:
//   {"recording": true}     flushed the instant the window opens, so
//                           the parent takes its baselines then
//   {"check_rate", "p50_ms", ..., "attempted", "failed", ...}
// `attempted` counts what completed or errored inside the window (the
// `depth` requests still in flight when it closes are neither);
// `failed` is grpc-status != 0. No reply for 5 s is exit code 2.
// Latencies are the exact per-request vector on this process's clock.
//
// With a quota table (run.py writes one for a mix whose `quota_every`
// is not 0; without one nothing below runs): a text file, first line
//   quota <name> <id_width> <read-back keys>
// then a line for each payload that asks for the quota,
//   <payload index> <offset of its deduplication_id> <amount>
//   <the precondition status its reply must carry>
//   <the read-back key its grant counts against, or -1>
// Such a payload's id is overwritten at every send with the hex of a
// counter (a sidecar stamps each Check; an id that came round again
// inside the server's dedup window would replay and allocate nothing).
// The reply's DATA frame is kept and the CheckResponse walked by hand
// (field numbers of istio_tpu/api/proto/mixer.proto):
// precondition.status.code, and quotas[name].granted_amount. Inside
// the window, and in `failed`: another status than the table's, an OK
// precondition with no entry for <name> (`quota_missing`), an entry
// beside a precondition that is not OK, a reply that does not parse.
// Counted beside them: `quota_asked`, `quota_granted` (the sum),
// `short_grants` (granted < amount), `quota_denied` (precondition not
// OK: no grant is due), `quota_ids_sent` (ids stamped, one a send).
// And from the connection's first send to its last reply, warm-up
// included, for each read-back key: `readback_sent` (the amounts asked
// of it) and `readback_granted` (the amounts its replies granted), so
// that run.py can read the server's counter back after the window and
// hold it between the two.
//
// Usage: h2load <port> <payload_file> <seconds> <depth> <warmup_s>
//               [:path [quota_table]]
#include <arpa/inet.h>
#include <errno.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <sys/socket.h>
#include <unistd.h>

#include <algorithm>
#include <chrono>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <string>
#include <unordered_map>
#include <vector>

#include "h2_frame.h"

namespace {

void lit_header(std::string* b, const std::string& name,
                const std::string& v) {
  b->push_back(0x00);
  b->push_back(static_cast<char>(name.size()));
  *b += name;
  b->push_back(static_cast<char>(v.size()));
  *b += v;
}

double now_s() { return mono_s(); }

// -- the quota table and the reply of a quota row ----------------------

struct QuotaRow {
  uint32_t id_off;
  int64_t amount;
  int32_t expect;
  int32_t key;   // read-back key, or -1
};

struct QuotaFlight {
  int32_t row;
  std::string reply;   // the stream's DATA frames, gRPC prefix included
};

struct Reply {
  int32_t status = 0;
  bool entry = false;
  int64_t granted = 0;
};

bool varint(const uint8_t*& p, const uint8_t* e, uint64_t* v) {
  *v = 0;
  for (int shift = 0; p < e && shift < 64; shift += 7) {
    uint8_t b = *p++;
    *v |= static_cast<uint64_t>(b & 0x7f) << shift;
    if (!(b & 0x80)) return true;
  }
  return false;
}

// every field of one message: f(number, wire type, varint or length,
// start of a length-delimited value); false where the bytes end early
template <class F>
bool fields(const uint8_t* p, const uint8_t* e, F f) {
  while (p < e) {
    uint64_t key, v = 0;
    if (!varint(p, e, &key)) return false;
    const uint8_t* at = nullptr;
    switch (key & 7) {
      case 0: if (!varint(p, e, &v)) return false; break;
      case 1: if (e - p < 8) return false; p += 8; break;
      case 2:
        if (!varint(p, e, &v) || static_cast<uint64_t>(e - p) < v)
          return false;
        at = p;
        p += v;
        break;
      case 5: if (e - p < 4) return false; p += 4; break;
      default: return false;
    }
    f(static_cast<uint32_t>(key >> 3), static_cast<uint32_t>(key & 7), v,
      at);
  }
  return true;
}

// CheckResponse{precondition = 2 {status = 1 {code = 1}},
//               quotas = 3 {key = 1, value = 2 {granted_amount = 2}}}
bool read_reply(const std::string& data, const std::string& name,
                Reply* r) {
  if (data.size() < 5 || data[0] != 0) return false;
  const uint8_t* p = reinterpret_cast<const uint8_t*>(data.data());
  uint32_t n = (p[1] << 24) | (p[2] << 16) | (p[3] << 8) | p[4];
  if (data.size() != 5 + static_cast<size_t>(n)) return false;
  bool ok = true;
  ok &= fields(p + 5, p + 5 + n, [&](uint32_t f, uint32_t wt, uint64_t v,
                                     const uint8_t* at) {
    if (f == 2 && wt == 2) {
      ok &= fields(at, at + v, [&](uint32_t f, uint32_t wt, uint64_t v,
                                   const uint8_t* at) {
        if (f == 1 && wt == 2)
          ok &= fields(at, at + v, [&](uint32_t f, uint32_t wt, uint64_t v,
                                       const uint8_t*) {
            if (f == 1 && wt == 0) r->status = static_cast<int32_t>(v);
          });
      });
    } else if (f == 3 && wt == 2) {
      std::string key;
      const uint8_t* value = nullptr;
      uint64_t value_len = 0;
      ok &= fields(at, at + v, [&](uint32_t f, uint32_t wt, uint64_t v,
                                   const uint8_t* at) {
        if (f == 1 && wt == 2)
          key.assign(reinterpret_cast<const char*>(at), v);
        if (f == 2 && wt == 2) { value = at; value_len = v; }
      });
      if (key != name) return;
      r->entry = true;
      if (value)
        ok &= fields(value, value + value_len,
                     [&](uint32_t f, uint32_t wt, uint64_t v,
                         const uint8_t*) {
                       if (f == 2 && wt == 0)
                         r->granted = static_cast<int64_t>(v);
                     });
    }
  });
  return ok;
}

}  // namespace

int main(int argc, char** argv) {
  if (argc < 6) {
    fprintf(stderr,
            "usage: h2load <port> <payload_file> <seconds> <depth> "
            "<warmup_s> [:path [quota_table]]\n");
    return 2;
  }
  int port = atoi(argv[1]);
  const char* payload_path = argv[2];
  double seconds = atof(argv[3]);
  int depth = atoi(argv[4]);
  double warmup_s = atof(argv[5]);
  // optional gRPC method path (default Check)
  std::string method_path = argc > 6 ? argv[6]
                                     : "/istio.mixer.v1.Mixer/Check";

  // load payloads (u32 len prefix each)
  std::vector<std::string> payloads;
  {
    FILE* f = fopen(payload_path, "rb");
    if (!f) { perror("payload file"); return 2; }
    while (true) {
      uint32_t n;
      if (fread(&n, 4, 1, f) != 1) break;
      std::string p(n, '\0');
      if (fread(p.data(), 1, n, f) != n) break;
      payloads.push_back(std::move(p));
    }
    fclose(f);
  }
  if (payloads.empty()) { fprintf(stderr, "no payloads\n"); return 2; }

  // the quota table: which payloads ask, where their id sits
  std::string quota_name;
  std::vector<QuotaRow> qrows;
  std::vector<int32_t> quota_of;   // payload index -> its row, or -1
  unsigned id_width = 0, readback_keys = 0;
  if (argc > 7) {
    FILE* f = fopen(argv[7], "r");
    if (!f) { perror("quota table"); return 2; }
    char name[256];
    if (fscanf(f, "quota %255s %u %u", name, &id_width,
               &readback_keys) != 3 || id_width != 16) {
      fprintf(stderr, "quota table: bad first line\n");
      return 2;
    }
    quota_name = name;
    quota_of.assign(payloads.size(), -1);
    unsigned long idx, off;
    long long amount;
    int expect, key;
    while (fscanf(f, "%lu %lu %lld %d %d", &idx, &off, &amount, &expect,
                  &key) == 5) {
      if (idx >= payloads.size() ||
          off + id_width > payloads[idx].size() ||
          key >= static_cast<int>(readback_keys)) {
        fprintf(stderr, "quota table: row %lu out of range\n", idx);
        return 2;
      }
      quota_of[idx] = static_cast<int32_t>(qrows.size());
      qrows.push_back({static_cast<uint32_t>(off), amount, expect, key});
    }
    fclose(f);
    if (qrows.empty()) { fprintf(stderr, "quota table: no row\n"); return 2; }
  }
  std::unordered_map<uint32_t, QuotaFlight> qflight;
  uint64_t next_id = 0;
  long quota_asked = 0, quota_missing = 0, short_grants = 0,
       quota_denied = 0, status_mismatches = 0, quota_unexpected = 0,
       replies_malformed = 0;
  long long quota_granted = 0;
  std::vector<long long> readback_sent(readback_keys, 0),
      readback_granted(readback_keys, 0);

  int fd = socket(AF_INET, SOCK_STREAM, 0);
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
  addr.sin_port = htons(static_cast<uint16_t>(port));
  if (connect(fd, reinterpret_cast<sockaddr*>(&addr), sizeof(addr))) {
    perror("connect");
    return 2;
  }
  int one = 1;
  setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));

  std::string out;
  out.append("PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n");
  // SETTINGS: INITIAL_WINDOW_SIZE 1GB; then 1GB connection window
  put_frame_header(&out, 6, F_SETTINGS, 0, 0);
  out.push_back(0);
  out.push_back(4);
  uint32_t w = htonl(1u << 30);
  out.append(reinterpret_cast<char*>(&w), 4);
  put_frame_header(&out, 4, F_WINUPD, 0, 0);
  uint32_t inc = htonl((1u << 30) - 65535);
  out.append(reinterpret_cast<char*>(&inc), 4);

  // constant request header block (stateless hpack)
  std::string hdr;
  lit_header(&hdr, ":method", "POST");
  lit_header(&hdr, ":scheme", "http");
  lit_header(&hdr, ":path", method_path);
  lit_header(&hdr, ":authority", "localhost");
  lit_header(&hdr, "content-type", "application/grpc");
  lit_header(&hdr, "te", "trailers");

  uint32_t next_stream = 1;
  size_t next_payload = 0;
  std::unordered_map<uint32_t, double> inflight;
  std::vector<double> lat;
  lat.reserve(1 << 20);
  long completions = 0, errors = 0, warmup_completions = 0;
  bool recording = false;
  double t_start = now_s(), t_rec_start = 0, t_rec_end = 0;

  auto send_one = [&]() {
    uint32_t sid = next_stream;
    next_stream += 2;
    const std::string& body = payloads[next_payload];
    int32_t qrow = qrows.empty() ? -1 : quota_of[next_payload];
    next_payload = (next_payload + 1) % payloads.size();
    put_frame_header(&out, hdr.size(), F_HEADERS, FL_END_HEADERS, sid);
    out += hdr;
    put_frame_header(&out, 5 + body.size(), F_DATA, FL_END_STREAM, sid);
    out.push_back('\0');
    uint32_t n = htonl(static_cast<uint32_t>(body.size()));
    out.append(reinterpret_cast<char*>(&n), 4);
    out += body;
    if (qrow >= 0) {
      const QuotaRow& q = qrows[qrow];
      char hex[17];
      snprintf(hex, sizeof(hex), "%016llx",
               static_cast<unsigned long long>(++next_id));
      memcpy(&out[out.size() - body.size() + q.id_off], hex, 16);
      if (q.key >= 0) readback_sent[q.key] += q.amount;
      qflight[sid] = {qrow, std::string()};
    }
    inflight[sid] = now_s();
  };
  for (int i = 0; i < depth; i++) send_one();

  std::string in;
  char buf[65536];
  while (!recording || now_s() - t_rec_start < seconds) {
    // write what we can, then read
    if (!out.empty()) {
      ssize_t n = write(fd, out.data(), out.size());
      if (n > 0) out.erase(0, n);
      else if (n < 0 && errno != EAGAIN) { perror("write"); return 2; }
    }
    pollfd p{fd, static_cast<short>(POLLIN | (out.empty() ? 0 : POLLOUT)),
             0};
    // never sleep past the window's end: the window closes on time
    // even when the next burst of replies is late
    int wait_ms = 5000;
    if (recording) {
      double left = seconds - (now_s() - t_rec_start);
      wait_ms = std::min(5000, static_cast<int>(left * 1e3) + 1);
    }
    int ready = poll(&p, 1, std::max(wait_ms, 0));
    if (ready == 0 && wait_ms < 5000) continue;  // window's end: re-test
    if (ready <= 0) {
      fprintf(stderr, "poll timeout/err with %zu inflight\n",
              inflight.size());
      return 2;
    }
    if (p.revents & POLLIN) {
      ssize_t n = read(fd, buf, sizeof(buf));
      if (n <= 0) { fprintf(stderr, "server closed\n"); return 2; }
      in.append(buf, n);
    }
    size_t pos = 0;
    while (in.size() - pos >= 9) {
      const uint8_t* hp = reinterpret_cast<const uint8_t*>(in.data()) +
                          pos;
      uint32_t len = (hp[0] << 16) | (hp[1] << 8) | hp[2];
      if (in.size() - pos < 9 + len) break;
      uint8_t type = hp[3], flags = hp[4];
      uint32_t sid;
      memcpy(&sid, hp + 5, 4);
      sid = ntohl(sid) & 0x7fffffffu;
      if (type == F_SETTINGS && !(flags & FL_ACK)) {
        put_frame_header(&out, 0, F_SETTINGS, FL_ACK, 0);
      } else if (type == F_PING && !(flags & FL_ACK)) {
        put_frame_header(&out, 8, F_PING, FL_ACK, 0);
        out.append(reinterpret_cast<const char*>(hp) + 9, 8);
      } else if (type == F_GOAWAY) {
        fprintf(stderr, "server goaway\n");
        return 2;
      } else if (type == F_DATA && !qflight.empty()) {
        auto q = qflight.find(sid);
        if (q != qflight.end())
          q->second.reply.append(reinterpret_cast<const char*>(hp) + 9, len);
      } else if (type == F_HEADERS && (flags & FL_END_STREAM)) {
        // trailers: scan the (literal-encoded) block for grpc-status
        const char* blk = reinterpret_cast<const char*>(hp) + 9;
        std::string block(blk, len);
        size_t at = block.find("grpc-status");
        bool ok = false;
        if (at != std::string::npos &&
            at + 11 + 2 <= block.size()) {
          uint8_t vlen = block[at + 11];
          ok = vlen == 1 && block[at + 12] == '0';
        }
        auto it = inflight.find(sid);
        auto q = qflight.empty() ? qflight.end() : qflight.find(sid);
        if (q != qflight.end()) {
          const QuotaRow& row = qrows[q->second.row];
          Reply r;
          bool parsed = ok && it != inflight.end() &&
                        read_reply(q->second.reply, quota_name, &r);
          if (parsed && r.entry && row.key >= 0)
            readback_granted[row.key] += r.granted;
          if (recording && ok && it != inflight.end()) {
            quota_asked++;
            if (!parsed) {
              replies_malformed++;
              ok = false;
            } else if (r.status != row.expect) {
              status_mismatches++;
              ok = false;
            } else if (r.status != 0) {
              quota_denied++;
              if (r.entry) { quota_unexpected++; ok = false; }
            } else if (!r.entry) {
              quota_missing++;
              ok = false;
            } else {
              quota_granted += r.granted;
              if (r.granted < row.amount) short_grants++;
            }
          }
          qflight.erase(q);
        }
        if (it != inflight.end()) {
          double dt = now_s() - it->second;
          inflight.erase(it);
          completions++;
          // errors cover the SAME window as n/checks_per_sec — a
          // warmup-phase blip must not taint the recorded figures
          if (!ok && recording) errors++;
          if (recording) {
            lat.push_back(dt);
          } else if (now_s() - t_start >= warmup_s) {
            recording = true;
            warmup_completions = completions - 1;
            t_rec_start = now_s();
            printf("{\"recording\": true}\n");
            fflush(stdout);
          }
          send_one();
        }
      }
      pos += 9 + len;
    }
    if (pos) in.erase(0, pos);
  }
  t_rec_end = now_s();
  close(fd);

  if (lat.empty()) {
    fprintf(stderr, "no recorded completions\n");
    return 2;
  }
  std::sort(lat.begin(), lat.end());
  double dur = t_rec_end - t_rec_start;
  auto q = [&](double frac) {
    return lat[std::min(lat.size() - 1,
                        static_cast<size_t>(lat.size() * frac))] * 1e3;
  };
  double mean = 0;
  for (double v : lat) mean += v;
  mean = mean / lat.size() * 1e3;
  printf(
      "{\"check_rate\": %.3f, \"p50_ms\": %.6f, \"p90_ms\": %.6f, "
      "\"p95_ms\": %.6f, \"p99_ms\": %.6f, \"mean_ms\": %.6f, "
      "\"max_ms\": %.6f, \"attempted\": %zu, \"failed\": %ld, "
      "\"duration_s\": %.6f, \"warmup_completions\": %ld, "
      "\"depth\": %d, \"quota_asked\": %ld, \"quota_granted\": %lld, "
      "\"quota_missing\": %ld, \"short_grants\": %ld, "
      "\"quota_denied\": %ld, \"status_mismatches\": %ld, "
      "\"quota_unexpected\": %ld, \"replies_malformed\": %ld, "
      "\"quota_ids_sent\": %llu",
      (lat.size() - errors) / dur, q(0.50), q(0.90), q(0.95), q(0.99),
      mean, lat.back() * 1e3, lat.size(), errors, dur,
      warmup_completions, depth, quota_asked, quota_granted,
      quota_missing, short_grants, quota_denied, status_mismatches,
      quota_unexpected, replies_malformed,
      static_cast<unsigned long long>(next_id));
  auto list = [](const char* name, const std::vector<long long>& v) {
    printf(", \"%s\": [", name);
    for (size_t i = 0; i < v.size(); i++)
      printf("%s%lld", i ? ", " : "", v[i]);
    printf("]");
  };
  list("readback_sent", readback_sent);
  list("readback_granted", readback_granted);
  printf("}\n");
  return 0;
}
