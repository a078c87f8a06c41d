// Native wire→tensor shim.
//
// Parses serialized istio.mixer.v1.CompressedAttributes records and
// fills the AttributeBatch buffers (ids / present / map_present /
// str_bytes / str_lens) exactly like the Python Tensorizer
// (istio_tpu/compiler/layout.py), which is the conformance oracle.
// The intern table is authoritative HERE once the shim is in use:
// Python seeds it with compile-time constants and imports any new
// entries after each batch (export API below).
//
// C ABI only — loaded via ctypes (no pybind11 in this image).
#include <cstdint>
#include <cstring>
#include <cmath>
#include <map>
#include <string>
#include <vector>

#include "mixer.pb.h"

using istio::mixer::v1::CompressedAttributes;

namespace {

constexpr int32_t ID_INVALID = 0;
constexpr int32_t ID_FALSE = 1;
constexpr int32_t ID_TRUE = 2;

// canonical intern key: 1 type-tag byte + canonical payload
// (mirrors layout.py _normalize)
using Key = std::string;

Key key_bool(bool v) { return std::string("b") + (v ? '\1' : '\0'); }
Key key_i64(int64_t v) {
  std::string k("i");
  k.append(reinterpret_cast<const char*>(&v), 8);
  return k;
}
Key key_f64(double v) {
  std::string k("d");
  k.append(reinterpret_cast<const char*>(&v), 8);
  return k;
}
Key key_str(const std::string& v) { return "s" + v; }
Key key_bytes(const std::string& raw) {
  // v4 → v4-in-v6 canonical form (net.IP.Equal semantics)
  std::string v = raw;
  if (v.size() == 4) {
    std::string mapped(10, '\0');
    mapped += "\xff\xff";
    mapped += v;
    v = mapped;
  }
  return "p" + v;
}
Key key_dur_ns(int64_t ns) {
  std::string k("D");
  k.append(reinterpret_cast<const char*>(&ns), 8);
  return k;
}
Key key_ts_ns(int64_t ns) {
  std::string k("t");
  k.append(reinterpret_cast<const char*>(&ns), 8);
  return k;
}

// Python normalizes datetimes/timedeltas through float seconds
// (round(value.timestamp() * 1e9)); replicate the same IEEE ops so ids
// agree bit-for-bit. Proto → datetime truncates to microseconds.
int64_t ts_ns_like_python(int64_t seconds, int32_t nanos) {
  double ts = static_cast<double>(seconds) +
              static_cast<double>(nanos / 1000) / 1e6;
  return llround(ts * 1e9);
}
int64_t dur_ns_like_python(int64_t seconds, int32_t nanos) {
  double total = static_cast<double>(seconds) +
                 static_cast<double>(nanos / 1000) / 1e6;
  return llround(total * 1e9);
}

struct Layout {
  uint32_t max_str_len = 128;
  std::vector<std::string> global_words;
  std::map<std::string, int32_t> scalar_slots;          // attr → col
  std::map<std::string, int32_t> map_slots;             // map attr → mcol
  std::map<std::pair<std::string, std::string>, int32_t> derived;  // (map,key)→col
  std::map<std::string, int32_t> byte_attr;             // attr → bcol
  // encoding per attr byte slot: 0 utf-8, 2 int64 / 3 double /
  // 4 duration-ns / 5 timestamp-ns ORDER KEYS (the 8-byte
  // order-preserving encodings of layout.order_key_bytes — ordered
  // comparisons on device read these planes)
  std::map<std::string, uint8_t> byte_kind;
  std::map<std::pair<std::string, std::string>, int32_t> byte_pair;
  uint32_t n_columns = 0, n_maps = 0, n_byte = 0;
};

struct Shim {
  Layout layout;
  std::map<Key, int32_t> interns;
  std::vector<Key> intern_order;   // ids 3.. in assignment order
  std::string error;

  // ids: 0 invalid, 1 false, 2 true, then sequential
  int32_t intern(const Key& k) {
    auto it = interns.find(k);
    if (it != interns.end()) return it->second;
    int32_t id = next_id_++;
    interns.emplace(k, id);
    intern_order.push_back(k);
    return id;
  }
  int32_t next_id_ = 3;
};

// ---- little binary reader for the layout blob Python packs ----
struct Reader {
  const uint8_t* p;
  const uint8_t* end;
  bool ok = true;
  uint32_t u32() {
    if (p + 4 > end) { ok = false; return 0; }
    uint32_t v;
    memcpy(&v, p, 4);
    p += 4;
    return v;
  }
  uint8_t u8() {
    if (p >= end) { ok = false; return 0; }
    return *p++;
  }
  std::string str() {
    uint32_t n = u32();
    if (!ok || p + n > end) { ok = false; return ""; }
    std::string s(reinterpret_cast<const char*>(p), n);
    p += n;
    return s;
  }
};

const std::string* resolve_word(const Shim& sh,
                                const CompressedAttributes& msg,
                                int32_t index) {
  if (index < 0) {
    size_t gi = static_cast<size_t>(-index - 1);
    if (gi >= sh.layout.global_words.size()) return nullptr;
    return &sh.layout.global_words[gi];
  }
  if (index >= msg.words_size()) return nullptr;
  return &msg.words(index);
}

}  // namespace

extern "C" {

void* shim_create(const uint8_t* blob, size_t len) {
  auto* sh = new Shim();
  Reader r{blob, blob + len};
  uint32_t magic = r.u32();
  if (magic != 0x49545032) {  // "ITP2": byte slots carry a kind
    delete sh;
    return nullptr;
  }
  Layout& L = sh->layout;
  L.max_str_len = r.u32();
  uint32_t n = r.u32();
  for (uint32_t i = 0; i < n; i++) L.global_words.push_back(r.str());
  n = r.u32();
  for (uint32_t i = 0; i < n; i++) {
    int32_t col = static_cast<int32_t>(r.u32());
    L.scalar_slots[r.str()] = col;
  }
  n = r.u32();
  for (uint32_t i = 0; i < n; i++) {
    int32_t col = static_cast<int32_t>(r.u32());
    L.map_slots[r.str()] = col;
  }
  n = r.u32();
  for (uint32_t i = 0; i < n; i++) {
    int32_t col = static_cast<int32_t>(r.u32());
    std::string m = r.str(), k = r.str();
    L.derived[{m, k}] = col;
  }
  n = r.u32();
  for (uint32_t i = 0; i < n; i++) {
    int32_t bcol = static_cast<int32_t>(r.u32());
    uint8_t kind = r.u8();
    std::string a = r.str();
    if (kind == 1) {
      std::string k = r.str();
      L.byte_pair[{a, k}] = bcol;
    } else {
      L.byte_attr[a] = bcol;
      L.byte_kind[a] = kind;
    }
  }
  L.n_columns = r.u32();
  L.n_maps = r.u32();
  L.n_byte = r.u32();
  // seed interns (tag + canonical payload, pre-keyed by Python)
  n = r.u32();
  sh->interns[key_bool(false)] = ID_FALSE;
  sh->interns[key_bool(true)] = ID_TRUE;
  for (uint32_t i = 0; i < n; i++) {
    std::string key = r.str();
    if (sh->interns.find(key) == sh->interns.end()) {
      sh->interns[key] = sh->next_id_++;
      sh->intern_order.push_back(key);   // keeps export indexable
    }
  }
  if (!r.ok) {
    delete sh;
    return nullptr;
  }
  return sh;
}

void shim_destroy(void* h) { delete static_cast<Shim*>(h); }

const char* shim_error(void* h) {
  return static_cast<Shim*>(h)->error.c_str();
}

int32_t shim_intern_count(void* h) {
  return static_cast<Shim*>(h)->next_id_;
}

// Drop interned entries with id >= keep_count (runtime-observed
// values); compile-time seeds stay. Bounds a long-running server's
// intern memory — Python flushes in lockstep with its remap table.
void shim_flush_interns(void* h, int32_t keep_count) {
  auto* sh = static_cast<Shim*>(h);
  if (keep_count < 3 || keep_count >= sh->next_id_) return;
  for (int32_t id = keep_count; id < sh->next_id_; id++) {
    sh->interns.erase(sh->intern_order[id - 3]);
  }
  sh->intern_order.resize(keep_count - 3);
  sh->next_id_ = keep_count;
}

// Export canonical keys for ids in [from_id, next_id): packed as
// u32 len + bytes per key. Returns bytes written or -needed.
int64_t shim_export_interns(void* h, int32_t from_id, uint8_t* buf,
                            size_t cap) {
  auto* sh = static_cast<Shim*>(h);
  size_t need = 0;
  std::vector<const Key*> keys;
  for (int32_t id = from_id; id < sh->next_id_; id++) {
    const Key& k = sh->intern_order[id - 3];
    keys.push_back(&k);
    need += 4 + k.size();
  }
  if (need > cap) return -static_cast<int64_t>(need);
  uint8_t* p = buf;
  for (auto* k : keys) {
    uint32_t n = static_cast<uint32_t>(k->size());
    memcpy(p, &n, 4);
    p += 4;
    memcpy(p, k->data(), n);
    p += n;
  }
  return static_cast<int64_t>(need);
}

// Stable 31-bit content hash of a canonical key (FNV-1a); must match
// stable_hash31 in compiler/layout.py — quota buckets key on it.
static int32_t fnv1a31(const Key& k) {
  uint32_t h = 0x811C9DC5u;
  for (unsigned char c : k) {
    h = (h ^ c) * 0x01000193u;
  }
  return static_cast<int32_t>(h & 0x7FFFFFFFu);
}

// Tensorize a batch of serialized CompressedAttributes.
// Buffers (caller-allocated, zeroed):
//   ids        int32 [n, n_columns]
//   hash_ids   int32 [n, n_columns]   stable content hash per slot
//   present    uint8 [n, n_columns]
//   map_present uint8 [n, max(n_maps,1)]
//   str_bytes  uint8 [n, max(n_byte,1), max_str_len]
//   str_lens   int32 [n, max(n_byte,1)]
// The wide rows (layout.WideRows; wide_bytes null: none kept). A row
// with a string of max_str_len bytes or more claims the next row of
//   wide_bytes uint8 [n, max(n_byte,1), wide_len]   NOT zeroed by the
//   wide_lens  int32 [n, max(n_byte,1)]             caller: a claim
//   wide_row   int32 [n]   the claimed row, -1      zeroes its row
// and holds every byte slot of the request there, the long ones whole
// up to wide_len; *n_wide counts the claims.
// Returns 0 on success, <0 on parse error (row index encoded).
int32_t shim_tensorize(void* h, const uint8_t* const* msgs,
                       const int64_t* msg_lens, int32_t n,
                       int32_t* ids, int32_t* hash_ids,
                       uint8_t* present,
                       uint8_t* map_present, uint8_t* str_bytes,
                       int32_t* str_lens, uint8_t* wide_bytes,
                       int32_t* wide_lens, int32_t* wide_row,
                       int32_t wide_len, int32_t* n_wide) {
  auto* sh = static_cast<Shim*>(h);
  const Layout& L = sh->layout;
  const size_t ncol = L.n_columns;
  const size_t nmap = L.n_maps ? L.n_maps : 1;
  const size_t nbyte = L.n_byte ? L.n_byte : 1;
  const size_t slen = L.max_str_len;
  const size_t wlen = static_cast<size_t>(wide_len);
  int32_t claimed = 0;
  if (n_wide) *n_wide = 0;

  CompressedAttributes msg;
  for (int32_t i = 0; i < n; i++) {
    msg.Clear();
    if (wide_bytes) wide_row[i] = -1;
    if (!msg.ParseFromArray(msgs[i], static_cast<int>(msg_lens[i]))) {
      sh->error = "parse failure at record " + std::to_string(i);
      return -(i + 1);
    }
    int32_t* row_ids = ids + i * ncol;
    int32_t* row_h = hash_ids + i * ncol;
    uint8_t* row_p = present + i * ncol;
    uint8_t* row_mp = map_present + i * nmap;
    uint8_t* row_sb = str_bytes + i * nbyte * slen;
    int32_t* row_sl = str_lens + i * nbyte;

    auto set_scalar = [&](const std::string& name, const Key& key) {
      auto it = L.scalar_slots.find(name);
      if (it == L.scalar_slots.end()) return;
      row_ids[it->second] = sh->intern(key);
      row_h[it->second] = fnv1a31(key);
      row_p[it->second] = 1;
    };
    auto set_bytes_slot = [&](int32_t bcol, const std::string& value) {
      size_t m = value.size() < slen ? value.size() : slen;
      memcpy(row_sb + bcol * slen, value.data(), m);
      row_sl[bcol] = static_cast<int32_t>(m);
      if (!wide_bytes || value.size() < slen) return;
      if (wide_row[i] < 0) {
        wide_row[i] = claimed++;
        memset(wide_bytes + wide_row[i] * nbyte * wlen, 0, nbyte * wlen);
        memset(wide_lens + wide_row[i] * nbyte, 0,
               nbyte * sizeof(int32_t));
      }
      uint8_t* w = wide_bytes + (wide_row[i] * nbyte + bcol) * wlen;
      int32_t* wl = wide_lens + wide_row[i] * nbyte + bcol;
      if (*wl) memset(w, 0, wlen);          // the slot set twice
      size_t mw = value.size() < wlen ? value.size() : wlen;
      memcpy(w, value.data(), mw);
      *wl = static_cast<int32_t>(mw);
    };
    // 8-byte big-endian order key (layout.order_key_bytes parity)
    auto set_key8 = [&](int32_t bcol, uint64_t bits) {
      uint8_t* p = row_sb + bcol * slen;
      for (int b = 0; b < 8; b++)
        p[b] = static_cast<uint8_t>(bits >> (56 - 8 * b));
      row_sl[bcol] = 8;
    };
    // len-1 marker: value not encodable for this slot's kind (the
    // python tensorizer's ORDER_KEY_ERROR; device reads it as err)
    auto set_key_error = [&](int32_t bcol) {
      row_sb[bcol * slen] = 0;
      row_sl[bcol] = 1;
    };
    auto i64_bits = [](int64_t v) {
      return static_cast<uint64_t>(v) ^ 0x8000000000000000ull;
    };
    // numeric value → key by SLOT kind; returns false for NaN (slot
    // stays len-0: the "compares False" marker)
    auto set_numeric_key = [&](int32_t bcol, uint8_t kind, double dv,
                               int64_t iv, bool from_double) {
      if (kind == 3) {                       // double order key
        double d = from_double ? dv : static_cast<double>(iv);
        if (d != d) { row_sl[bcol] = 0; return; }   // NaN
        if (d == 0.0) d = 0.0;               // -0.0 == +0.0
        uint64_t bits;
        memcpy(&bits, &d, 8);
        bits = (bits >> 63) ? ~bits : (bits | 0x8000000000000000ull);
        set_key8(bcol, bits);
        return;
      }
      // int64 / duration-ns / timestamp-ns all key the integer value
      int64_t v = from_double ? static_cast<int64_t>(dv) : iv;
      if (from_double && dv != dv) { row_sl[bcol] = 0; return; }
      set_key8(bcol, i64_bits(v));
    };

    for (const auto& kv : msg.strings()) {
      const std::string* name = resolve_word(*sh, msg, kv.first);
      const std::string* value = resolve_word(*sh, msg, kv.second);
      if (!name || !value) continue;
      set_scalar(*name, key_str(*value));
      auto bit = L.byte_attr.find(*name);
      if (bit != L.byte_attr.end()) {
        uint8_t kind = L.byte_kind.at(*name);
        if (kind == 0) set_bytes_slot(bit->second, *value);
        else set_key_error(bit->second);   // string under numeric slot
      }
    }
    for (const auto& kv : msg.int64s()) {
      const std::string* name = resolve_word(*sh, msg, kv.first);
      if (!name) continue;
      set_scalar(*name, key_i64(kv.second));
      auto bit = L.byte_attr.find(*name);
      if (bit != L.byte_attr.end()) {
        uint8_t kind = L.byte_kind.at(*name);
        if (kind == 0) continue;           // int under string slot
        set_numeric_key(bit->second, kind, 0.0, kv.second, false);
      }
    }
    for (const auto& kv : msg.doubles()) {
      const std::string* name = resolve_word(*sh, msg, kv.first);
      if (!name) continue;
      set_scalar(*name, key_f64(kv.second));
      auto bit = L.byte_attr.find(*name);
      if (bit != L.byte_attr.end()) {
        uint8_t kind = L.byte_kind.at(*name);
        if (kind == 0) continue;
        set_numeric_key(bit->second, kind, kv.second, 0, true);
      }
    }
    for (const auto& kv : msg.bools()) {
      const std::string* name = resolve_word(*sh, msg, kv.first);
      if (!name) continue;
      auto it = L.scalar_slots.find(*name);
      if (it == L.scalar_slots.end()) continue;
      row_ids[it->second] = kv.second ? ID_TRUE : ID_FALSE;
      row_h[it->second] = fnv1a31(key_bool(kv.second));
      row_p[it->second] = 1;
    }
    for (const auto& kv : msg.bytes()) {
      const std::string* name = resolve_word(*sh, msg, kv.first);
      if (!name) continue;
      set_scalar(*name, key_bytes(kv.second));
      auto bit = L.byte_attr.find(*name);
      if (bit != L.byte_attr.end()) {
        uint8_t kind = L.byte_kind.at(*name);
        // raw bytes ride the byte plane (CIDR list lowering compares
        // IP bytes in v6-mapped space — layout._byte_source_value
        // parity); bytes under a numeric order-key slot are
        // unencodable
        if (kind == 0) set_bytes_slot(bit->second, kv.second);
        else set_key_error(bit->second);
      }
    }
    for (const auto& kv : msg.timestamps()) {
      const std::string* name = resolve_word(*sh, msg, kv.first);
      if (!name) continue;
      int64_t ns = ts_ns_like_python(kv.second.seconds(),
                                     kv.second.nanos());
      set_scalar(*name, key_ts_ns(ns));
      auto bit = L.byte_attr.find(*name);
      if (bit != L.byte_attr.end() && L.byte_kind.at(*name) != 0)
        set_numeric_key(bit->second, L.byte_kind.at(*name), 0.0, ns,
                        false);
    }
    for (const auto& kv : msg.durations()) {
      const std::string* name = resolve_word(*sh, msg, kv.first);
      if (!name) continue;
      int64_t ns = dur_ns_like_python(kv.second.seconds(),
                                      kv.second.nanos());
      set_scalar(*name, key_dur_ns(ns));
      auto bit = L.byte_attr.find(*name);
      if (bit != L.byte_attr.end() && L.byte_kind.at(*name) != 0)
        set_numeric_key(bit->second, L.byte_kind.at(*name), 0.0, ns,
                        false);
    }
    for (const auto& kv : msg.string_maps()) {
      const std::string* mname = resolve_word(*sh, msg, kv.first);
      if (!mname) continue;
      auto mit = L.map_slots.find(*mname);
      if (mit != L.map_slots.end()) row_mp[mit->second] = 1;
      for (const auto& ekv : kv.second.entries()) {
        const std::string* key = resolve_word(*sh, msg, ekv.first);
        const std::string* value = resolve_word(*sh, msg, ekv.second);
        if (!key || !value) continue;
        auto dit = L.derived.find({*mname, *key});
        if (dit != L.derived.end()) {
          row_ids[dit->second] = sh->intern(key_str(*value));
          row_h[dit->second] = fnv1a31(key_str(*value));
          row_p[dit->second] = 1;
        }
        auto bit = L.byte_pair.find({*mname, *key});
        if (bit != L.byte_pair.end()) set_bytes_slot(bit->second, *value);
      }
    }
    if (wide_bytes && wide_row[i] >= 0) {
      // the row's other slots, as the narrow plane holds them
      for (size_t bcol = 0; bcol < nbyte; bcol++) {
        if (static_cast<size_t>(row_sl[bcol]) >= slen) continue;
        uint8_t* w = wide_bytes + (wide_row[i] * nbyte + bcol) * wlen;
        int32_t* wl = wide_lens + wide_row[i] * nbyte + bcol;
        if (*wl) memset(w, 0, wlen);        // set long, then short
        memcpy(w, row_sb + bcol * slen, row_sl[bcol]);
        *wl = row_sl[bcol];
      }
    }
  }
  if (n_wide) *n_wide = claimed;
  return 0;
}

}  // extern "C"
