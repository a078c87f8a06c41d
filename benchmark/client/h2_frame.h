// Shared HTTP/2 framing primitives for the native front-end server
// (httpd.cpp) and its load client (h2load.cpp) — one home for the
// frame header layout, type/flag constants and the monotonic clock,
// so the bench client can never desynchronize from the server wire.
#pragma once
#include <arpa/inet.h>
#include <time.h>

#include <cstdint>
#include <cstring>
#include <string>

constexpr uint8_t F_DATA = 0x0, F_HEADERS = 0x1, F_PRIORITY = 0x2,
                  F_RST = 0x3, F_SETTINGS = 0x4, F_PUSH = 0x5,
                  F_PING = 0x6, F_GOAWAY = 0x7, F_WINUPD = 0x8,
                  F_CONT = 0x9;
constexpr uint8_t FL_END_STREAM = 0x1, FL_END_HEADERS = 0x4,
                  FL_PADDED = 0x8, FL_PRIORITY_FLAG = 0x20,
                  FL_ACK = 0x1;

inline void put_frame_header(std::string* out, uint32_t len,
                             uint8_t type, uint8_t flags,
                             uint32_t stream) {
  char h[9];
  h[0] = static_cast<char>((len >> 16) & 0xff);
  h[1] = static_cast<char>((len >> 8) & 0xff);
  h[2] = static_cast<char>(len & 0xff);
  h[3] = static_cast<char>(type);
  h[4] = static_cast<char>(flags);
  uint32_t s = htonl(stream & 0x7fffffffu);
  memcpy(h + 5, &s, 4);
  out->append(h, 9);
}

inline int64_t mono_ns() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return ts.tv_sec * 1000000000ll + ts.tv_nsec;
}

inline double mono_s() { return mono_ns() * 1e-9; }
