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
// Usage: h2load <port> <payload_file> <seconds> <depth> <warmup_s> [:path]
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

}  // namespace

int main(int argc, char** argv) {
  if (argc < 6) {
    fprintf(stderr,
            "usage: h2load <port> <payload_file> <seconds> <depth> "
            "<warmup_s> [:path]\n");
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
    next_payload = (next_payload + 1) % payloads.size();
    put_frame_header(&out, hdr.size(), F_HEADERS, FL_END_HEADERS, sid);
    out += hdr;
    put_frame_header(&out, 5 + body.size(), F_DATA, FL_END_STREAM, sid);
    out.push_back('\0');
    uint32_t n = htonl(static_cast<uint32_t>(body.size()));
    out.append(reinterpret_cast<char*>(&n), 4);
    out += body;
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
      "\"depth\": %d}\n",
      (lat.size() - errors) / dur, q(0.50), q(0.90), q(0.95), q(0.99),
      mean, lat.back() * 1e3, lat.size(), errors, dur,
      warmup_completions, depth);
  return 0;
}
