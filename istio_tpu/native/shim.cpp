// Native wire→tensor shim.
//
// Reads serialized istio.mixer.v1.CompressedAttributes records and
// fills the AttributeBatch buffers (ids / present / map_present /
// str_bytes / str_lens) exactly like the Python Tensorizer
// (istio_tpu/compiler/layout.py), which is the conformance oracle.
//
// A record is read in place, in one pass over its wire bytes: no
// protobuf message is built and libprotobuf is not linked. Words and
// bytes values are string_views into the caller's buffer, which stays
// alive and unwritten for the call. The pass accepts and rejects what
// protobuf's ParseFromArray does (its limits on a tag's, a length's and
// a value's varint, its nesting budget, groups skipped when they close,
// UTF-8 checked in `words`), keeps of a key repeated in one map the
// last entry and merges a value field repeated in one entry, as a
// protobuf map does. A record is applied only after all of it was
// read, typed map by typed map in the order of apply_row: a record
// that fails writes nothing.
//
// Names are resolved when the handle is made: one Name a distinct
// attribute or map of the layout, found from a global word by index
// and from a message-local word by one hashed look-up of its bytes.
//
// The intern table is authoritative HERE once the shim is in use:
// Python seeds it with compile-time constants and imports any new
// entries after each batch (export API below). It is an
// open-addressing index over canonical keys kept back to back; a key is
// copied only when its value is new. Handle state (the intern table,
// the scratch vectors of a record) is not synchronised: one call at a
// time a handle (NativeTensorizer._call_lock).
//
// C ABI only — loaded via ctypes (no pybind11 in this image).
#include <algorithm>
#include <climits>
#include <cmath>
#include <cstdint>
#include <cstring>
#include <string>
#include <string_view>
#include <vector>

namespace {

using SV = std::string_view;

constexpr int32_t ID_FALSE = 1;
constexpr int32_t ID_TRUE = 2;

// FNV-1a: the low 31 bits over a canonical key are the stable content
// hash (stable_hash31 in compiler/layout.py — quota buckets key on
// it); all 32 place the key in the intern index, from the same pass
constexpr uint32_t FNV_BASIS = 0x811C9DC5u;

inline uint32_t fnv1a(uint32_t h, SV s) {
  for (unsigned char c : s) h = (h ^ c) * 0x01000193u;
  return h;
}
inline int32_t hash31(uint32_t h) {
  return static_cast<int32_t>(h & 0x7FFFFFFFu);
}

// Open-addressing index hash → id, linear probing; the caller holds
// the keys and says whether an id's key is the one it looks for.
struct FlatIndex {
  struct Cell { uint32_t hash; int32_t id; };   // id < 0: empty
  std::vector<Cell> cells{16, Cell{0, -1}};
  uint32_t shift = 28;                          // 32 - log2(cells)
  size_t used = 0;

  size_t home(uint32_t h) const { return (h * 0x9E3779B1u) >> shift; }

  template <class Eq>
  int32_t find(uint32_t h, Eq eq) const {
    const size_t mask = cells.size() - 1;
    for (size_t i = home(h);; i = (i + 1) & mask) {
      const Cell& c = cells[i];
      if (c.id < 0) return -1;
      if (c.hash == h && eq(c.id)) return c.id;
    }
  }
  void place(uint32_t h, int32_t id) {
    const size_t mask = cells.size() - 1;
    size_t i = home(h);
    while (cells[i].id >= 0) i = (i + 1) & mask;
    cells[i] = Cell{h, id};
  }
  // a key the caller knows is absent
  void insert(uint32_t h, int32_t id) {
    if ((used + 1) * 2 > cells.size()) {
      std::vector<Cell> old(cells.size() * 2, Cell{0, -1});
      old.swap(cells);
      shift--;
      for (const Cell& c : old)
        if (c.id >= 0) place(c.hash, c.id);
    }
    place(h, id);
    used++;
  }
  void clear() {   // keeps its capacity
    std::fill(cells.begin(), cells.end(), Cell{0, -1});
    used = 0;
  }
};

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

// What the layout holds under one attribute or map name; -1: nothing.
struct Name {
  std::string text;
  int32_t col = -1;    // scalar column
  int32_t bcol = -1;   // byte slot of the attribute itself
  // encoding of that byte slot: 0 utf-8, 2 int64 / 3 double /
  // 4 duration-ns / 5 timestamp-ns ORDER KEYS (the 8-byte
  // order-preserving encodings of layout.order_key_bytes — ordered
  // comparisons on device read these planes)
  uint8_t kind = 0;
  int32_t mcol = -1;   // map-presence column
  bool pairs = false;  // some (this map, key) has a Pair
};

// A (map, key) the layout derives a column or a byte slot from.
struct Pair {
  int32_t name;
  std::string key;
  int32_t col = -1, bcol = -1;
};

struct Layout {
  uint32_t max_str_len = 128;
  std::vector<std::string> global_words;
  std::vector<int32_t> global_name;   // global word → names, -1
  std::vector<Name> names;
  FlatIndex name_index;               // a name's bytes → names
  std::vector<Pair> pairs;
  FlatIndex pair_index;               // (names index, key bytes) → pairs
  uint32_t n_columns = 0, n_maps = 0, n_byte = 0;

  int32_t find_name(SV text) const {
    return name_index.find(fnv1a(FNV_BASIS, text), [&](int32_t id) {
      return names[id].text == text;
    });
  }
  int32_t add_name(const std::string& text) {
    int32_t id = find_name(text);
    if (id >= 0) return id;
    id = static_cast<int32_t>(names.size());
    names.emplace_back();
    names.back().text = text;
    name_index.insert(fnv1a(FNV_BASIS, text), id);
    return id;
  }
  static uint32_t pair_hash(int32_t name, SV key) {
    return fnv1a(FNV_BASIS ^ (static_cast<uint32_t>(name) * 0x9E3779B1u),
                 key);
  }
  int32_t find_pair(int32_t name, SV key) const {
    return pair_index.find(pair_hash(name, key), [&](int32_t id) {
      return pairs[id].name == name && pairs[id].key == key;
    });
  }
  Pair& add_pair(const std::string& map, const std::string& key) {
    int32_t name = add_name(map);
    names[name].pairs = true;
    int32_t id = find_pair(name, key);
    if (id < 0) {
      id = static_cast<int32_t>(pairs.size());
      pairs.push_back(Pair{name, key});
      pair_index.insert(pair_hash(name, key), id);
    }
    return pairs[id];
  }
};

// ---- the wire readers, with protobuf's limits ----

// a varint of at most `Bytes` bytes, bits past T's width dropped: a
// value is <uint64_t, 10>, a tag <uint32_t, 5>
template <class T, int Bytes>
inline bool read_varint(const uint8_t*& p, const uint8_t* end, T* out) {
  T v = 0;
  for (int i = 0; i < Bytes && p < end; i++) {
    uint8_t b = *p++;
    v |= static_cast<T>(b & 0x7F) << (7 * i);
    if (b < 0x80) { *out = v; return true; }
  }
  return false;
}
inline bool read_value(const uint8_t*& p, const uint8_t* end,
                       uint64_t* out) {
  return read_varint<uint64_t, 10>(p, end, out);
}
inline bool read_tag(const uint8_t*& p, const uint8_t* end,
                     uint32_t* out) {
  return read_varint<uint32_t, 5>(p, end, out);
}
// a length-delimited field's payload: the length at most five bytes
// and INT_MAX - 16, the payload inside the enclosing field
inline bool read_span(const uint8_t*& p, const uint8_t* end, SV* out) {
  uint64_t n;
  if (!read_varint<uint64_t, 5>(p, end, &n) || n > INT_MAX - 16 ||
      n > static_cast<size_t>(end - p))
    return false;
  *out = SV(reinterpret_cast<const char*>(p), n);
  p += n;
  return true;
}
inline const uint8_t* begin_of(SV s) {
  return reinterpret_cast<const uint8_t*>(s.data());
}

inline int32_t zigzag32(uint64_t v) {
  uint32_t n = static_cast<uint32_t>(v);
  return static_cast<int32_t>((n >> 1) ^ (0u - (n & 1)));
}

// Skips the field whose tag was just read and is none the message
// knows. `depth`: the nesting budget left at this message (protobuf's
// recursion limit, 100 at the record); a group spends one a level and
// has to close with its own field number inside the enclosing field.
bool skip_unknown(uint32_t tag, const uint8_t*& p, const uint8_t* end,
                  int depth) {
  uint32_t open[100];
  int n_open = 0;
  for (;;) {
    uint32_t field = tag >> 3;
    uint64_t v;
    SV s;
    switch (tag & 7) {
      case 0:
        if (!field || !read_value(p, end, &v)) return false;
        break;
      case 1:
        if (!field || end - p < 8) return false;
        p += 8;
        break;
      case 2:
        if (!field || !read_span(p, end, &s)) return false;
        break;
      case 3:
        if (!field || --depth < 0) return false;
        open[n_open++] = field;
        break;
      case 4:
        if (!n_open || open[--n_open] != field) return false;
        depth++;
        break;
      case 5:
        if (!field || end - p < 4) return false;
        p += 4;
        break;
      default:
        return false;
    }
    if (!n_open) return true;
    if (!read_tag(p, end, &tag)) return false;
  }
}

// structurally valid UTF-8, as protobuf checks a proto3 string: no
// overlong form, no surrogate, nothing past U+10FFFF
bool valid_utf8(SV s) {
  const uint8_t* p = begin_of(s);
  const uint8_t* end = p + s.size();
  while (p < end) {
    uint64_t w;
    if (end - p >= 8 && (memcpy(&w, p, 8), !(w & 0x8080808080808080ull))) {
      p += 8;
      continue;
    }
    uint8_t c = *p;
    if (c < 0x80) { p++; continue; }
    size_t left = end - p;
    auto cont = [&](size_t i) { return (p[i] & 0xC0) == 0x80; };
    if (c >= 0xC2 && c <= 0xDF) {
      if (left < 2 || !cont(1)) return false;
      p += 2;
    } else if (c >= 0xE0 && c <= 0xEF) {
      if (left < 3 || !cont(1) || !cont(2)) return false;
      if (c == 0xE0 && p[1] < 0xA0) return false;    // overlong
      if (c == 0xED && p[1] >= 0xA0) return false;   // surrogate
      p += 3;
    } else if (c >= 0xF0 && c <= 0xF4) {
      if (left < 4 || !cont(1) || !cont(2) || !cont(3)) return false;
      if (c == 0xF0 && p[1] < 0x90) return false;    // overlong
      if (c == 0xF4 && p[1] >= 0x90) return false;   // > U+10FFFF
      p += 4;
    } else {
      return false;
    }
  }
  return true;
}

// ---- one record, as read ----

// the typed maps, by field number less 2
enum { STRINGS, INT64S, DOUBLES, BOOLS, TIMESTAMPS, DURATIONS, BYTES,
       STRING_MAPS, N_MAPS };
// the wire type of each map's value field
constexpr uint32_t VALUE_WIRE[N_MAPS] = {0, 0, 1, 0, 2, 2, 2, 2};

// One map entry: its key, and of the rest what its map's type uses.
struct Entry {
  int32_t key = 0;
  // strings and a StringMap's entries: the value's word index; int64s;
  // doubles: the bits; bools; timestamps and durations: seconds
  uint64_t bits = 0;
  int32_t nanos = 0;
  SV bytes;
  uint32_t first = 0, last = 0;   // string_maps: in Record::map_entries
};

struct Record {
  std::vector<SV> words;
  std::vector<Entry> maps[N_MAPS];
  std::vector<Entry> map_entries;   // every StringMap's, back to back

  void clear() {
    words.clear();
    map_entries.clear();
    for (auto& m : maps) m.clear();
  }
};

bool read_map_value(int map, const uint8_t*& p, const uint8_t* end,
                    int depth, Entry* e, Record* rec);

// One entry of map field `map` (its own nesting budget `depth`): the
// key (field 1, sint32) and the value (field 2) each time they occur,
// the last winning; any other field skipped.
bool read_entry(int map, SV entry, int depth, Entry* e, Record* rec) {
  const uint8_t* p = begin_of(entry);
  const uint8_t* end = p + entry.size();
  while (p < end) {
    uint32_t tag;
    uint64_t v;
    if (!read_tag(p, end, &tag)) return false;
    if (tag == 8) {
      if (!read_value(p, end, &v)) return false;
      e->key = zigzag32(v);
    } else if (tag == (16 | VALUE_WIRE[map])) {
      if (!read_map_value(map, p, end, depth, e, rec)) return false;
    } else if (!skip_unknown(tag, p, end, depth)) {
      return false;
    }
  }
  return true;
}

// The value field of an entry of `map`. A message (Timestamp,
// Duration, StringMap) that occurs again in one entry adds to what the
// earlier occurrence left: protobuf merges.
bool read_map_value(int map, const uint8_t*& p, const uint8_t* end,
                    int depth, Entry* e, Record* rec) {
  if (VALUE_WIRE[map] == 0) {
    if (!read_value(p, end, &e->bits)) return false;
    if (map == STRINGS)
      e->bits = static_cast<uint32_t>(zigzag32(e->bits));
    else if (map == BOOLS)
      e->bits = e->bits != 0;
    return true;
  }
  if (map == DOUBLES) {
    if (end - p < 8) return false;
    memcpy(&e->bits, p, 8);
    p += 8;
    return true;
  }
  SV msg;
  if (!read_span(p, end, &msg)) return false;
  if (map == BYTES) {
    e->bytes = msg;
    return true;
  }
  // Timestamp / Duration: seconds 1, nanos 2. StringMap: entries 1
  const uint8_t* m = begin_of(msg);
  const uint8_t* m_end = m + msg.size();
  while (m < m_end) {
    uint32_t tag;
    uint64_t v;
    if (!read_tag(m, m_end, &tag)) return false;
    if (map == STRING_MAPS && tag == 10) {
      SV inner;
      Entry pair;
      if (!read_span(m, m_end, &inner) ||
          !read_entry(STRINGS, inner, depth - 2, &pair, rec))
        return false;
      rec->map_entries.push_back(pair);
    } else if (map != STRING_MAPS && (tag == 8 || tag == 16)) {
      if (!read_value(m, m_end, &v)) return false;
      if (tag == 8)
        e->bits = v;
      else
        e->nanos = static_cast<int32_t>(static_cast<uint32_t>(v));
    } else if (!skip_unknown(tag, m, m_end, depth - 1)) {
      return false;
    }
  }
  return true;
}

// Reads the whole record into `rec`; false where ParseFromArray would
// return false. The nesting budgets: the record 100, a map entry 99, a
// message in it 98, an entry of a StringMap 97.
bool read_record(const uint8_t* p, size_t len, Record* rec) {
  const uint8_t* end = p + len;
  while (p < end) {
    uint32_t tag;
    if (!read_tag(p, end, &tag)) return false;
    uint32_t field = tag >> 3;
    if ((tag & 7) != 2 || field < 1 || field > 1 + N_MAPS) {
      if (!skip_unknown(tag, p, end, 100)) return false;
      continue;
    }
    SV body;
    if (!read_span(p, end, &body)) return false;
    if (field == 1) {
      if (!valid_utf8(body)) return false;
      rec->words.push_back(body);
      continue;
    }
    Entry e;
    e.first = static_cast<uint32_t>(rec->map_entries.size());
    if (!read_entry(field - 2, body, 99, &e, rec)) return false;
    e.last = static_cast<uint32_t>(rec->map_entries.size());
    rec->maps[field - 2].push_back(e);
  }
  return true;
}

// Of the entries in [first, last) with one key keeps the last, as a
// protobuf map holds it; → the new end. Entries of different keys may
// change places (a protobuf map has no order either).
Entry* keep_last(Entry* first, Entry* last) {
  const size_t n = last - first;
  if (n < 2) return last;
  Entry* out = first;
  if (n <= 16) {
    for (Entry* it = first; it != last; ++it) {
      bool later = false;
      for (Entry* j = it + 1; j != last && !later; ++j)
        later = j->key == it->key;
      if (!later) *out++ = *it;
    }
    return out;
  }
  std::stable_sort(first, last, [](const Entry& a, const Entry& b) {
    return a.key < b.key;
  });
  for (Entry* it = first; it != last; ++it)
    if (it + 1 == last || it[1].key != it->key) *out++ = *it;
  return out;
}
// → the map's entries, deduplicated
const std::vector<Entry>& kept(std::vector<Entry>& v) {
  v.resize(keep_last(v.data(), v.data() + v.size()) - v.data());
  return v;
}

struct Shim {
  Layout layout;
  // canonical intern keys (1 type-tag byte + canonical payload,
  // mirrors layout.py _normalize) of ids 3.., back to back in
  // assignment order: id's key is key_bytes[key_ends[id - 3] ..
  // key_ends[id - 2]). ids: 0 invalid, 1 false, 2 true, then sequential
  std::vector<uint8_t> key_bytes;
  std::vector<size_t> key_ends{0};
  FlatIndex intern_index;
  Record rec;
  std::string error;

  int32_t next_id() const {
    return static_cast<int32_t>(key_ends.size()) + 2;
  }
  SV key_of(int32_t id) const {
    return SV(reinterpret_cast<const char*>(key_bytes.data()) +
                  key_ends[id - 3],
              key_ends[id - 2] - key_ends[id - 3]);
  }
  // the id of the key head ++ tail, a new one where it has none; *h31
  // its stable content hash
  int32_t intern(SV head, SV tail, int32_t* h31) {
    uint32_t h = fnv1a(fnv1a(FNV_BASIS, head), tail);
    *h31 = hash31(h);
    int32_t id = intern_index.find(h, [&](int32_t cand) {
      SV k = key_of(cand);
      return k.size() == head.size() + tail.size() &&
             k.substr(0, head.size()) == head &&
             k.substr(head.size()) == tail;
    });
    if (id >= 0) return id;
    id = next_id();
    key_bytes.insert(key_bytes.end(), head.begin(), head.end());
    key_bytes.insert(key_bytes.end(), tail.begin(), tail.end());
    key_ends.push_back(key_bytes.size());
    intern_index.insert(h, id);
    return id;
  }
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

// One row's planes, and the batch's wide rows it may claim one of.
struct Row {
  int32_t* ids;
  int32_t* hash_ids;
  uint8_t* present;
  uint8_t* map_present;
  uint8_t* str_bytes;
  int32_t* str_lens;
  size_t slen, nbyte;
  uint8_t* wide_bytes;   // null: no wide rows kept
  int32_t* wide_lens;
  int32_t* wide_row;     // this row's entry
  size_t wlen;
  int32_t* claimed;

  void set_id(int32_t col, int32_t id, int32_t h31) {
    ids[col] = id;
    hash_ids[col] = h31;
    present[col] = 1;
  }
  void set_bytes(int32_t bcol, SV value) {
    size_t m = value.size() < slen ? value.size() : slen;
    if (m) memcpy(str_bytes + bcol * slen, value.data(), m);
    str_lens[bcol] = static_cast<int32_t>(m);
    if (!wide_bytes || value.size() < slen) return;
    if (*wide_row < 0) {
      *wide_row = (*claimed)++;
      memset(wide_bytes + *wide_row * nbyte * wlen, 0, nbyte * wlen);
      memset(wide_lens + *wide_row * nbyte, 0, nbyte * sizeof(int32_t));
    }
    uint8_t* w = wide_bytes + (*wide_row * nbyte + bcol) * wlen;
    int32_t* wl = wide_lens + *wide_row * nbyte + bcol;
    if (*wl) memset(w, 0, wlen);          // the slot set twice
    size_t mw = value.size() < wlen ? value.size() : wlen;
    memcpy(w, value.data(), mw);
    *wl = static_cast<int32_t>(mw);
  }
  // 8-byte big-endian order key (layout.order_key_bytes parity)
  void set_key8(int32_t bcol, uint64_t bits) {
    uint8_t* p = str_bytes + bcol * slen;
    for (int b = 0; b < 8; b++)
      p[b] = static_cast<uint8_t>(bits >> (56 - 8 * b));
    str_lens[bcol] = 8;
  }
  // len-1 marker: value not encodable for this slot's kind (the
  // python tensorizer's ORDER_KEY_ERROR; device reads it as err)
  void set_key_error(int32_t bcol) {
    str_bytes[bcol * slen] = 0;
    str_lens[bcol] = 1;
  }
  // numeric value → key by SLOT kind; NaN leaves the slot len-0 (the
  // "compares False" marker)
  void set_numeric_key(int32_t bcol, uint8_t kind, double dv, int64_t iv,
                       bool from_double) {
    if (kind == 3) {                       // double order key
      double d = from_double ? dv : static_cast<double>(iv);
      if (d != d) { str_lens[bcol] = 0; return; }   // NaN
      if (d == 0.0) d = 0.0;               // -0.0 == +0.0
      uint64_t bits;
      memcpy(&bits, &d, 8);
      bits = (bits >> 63) ? ~bits : (bits | 0x8000000000000000ull);
      set_key8(bcol, bits);
      return;
    }
    // int64 / duration-ns / timestamp-ns all key the integer value
    if (from_double && dv != dv) { str_lens[bcol] = 0; return; }
    int64_t v = from_double ? static_cast<int64_t>(dv) : iv;
    set_key8(bcol, static_cast<uint64_t>(v) ^ 0x8000000000000000ull);
  }
  // the row's other slots onto its wide row, as the narrow plane
  // holds them
  void finish_wide() {
    if (!wide_bytes || *wide_row < 0) return;
    for (size_t bcol = 0; bcol < nbyte; bcol++) {
      if (static_cast<size_t>(str_lens[bcol]) >= slen) continue;
      uint8_t* w = wide_bytes + (*wide_row * nbyte + bcol) * wlen;
      int32_t* wl = wide_lens + *wide_row * nbyte + bcol;
      if (*wl) memset(w, 0, wlen);        // set long, then short
      memcpy(w, str_bytes + bcol * slen, str_lens[bcol]);
      *wl = str_lens[bcol];
    }
  }
};

// Writes the record just read into `row`. The typed maps in this
// order — strings, int64s, doubles, bools, bytes, timestamps,
// durations, string_maps — so a name under two of them ends as the
// later one set it.
void apply_row(Shim* sh, Row row) {
  const Layout& L = sh->layout;
  Record& rec = sh->rec;

  auto word_of = [&](int32_t index, SV* out) {
    if (index < 0) {
      size_t gi = static_cast<size_t>(-static_cast<int64_t>(index) - 1);
      if (gi >= L.global_words.size()) return false;
      *out = L.global_words[gi];
      return true;
    }
    if (static_cast<size_t>(index) >= rec.words.size()) return false;
    *out = rec.words[index];
    return true;
  };
  // the layout's record of the attribute the word names; null where
  // the word does not resolve or the layout holds nothing under it
  auto name_of = [&](int32_t index) -> const Name* {
    int32_t id;
    if (index < 0) {
      size_t gi = static_cast<size_t>(-static_cast<int64_t>(index) - 1);
      if (gi >= L.global_name.size()) return nullptr;
      id = L.global_name[gi];
    } else {
      if (static_cast<size_t>(index) >= rec.words.size()) return nullptr;
      id = L.find_name(rec.words[index]);
    }
    return id < 0 ? nullptr : &L.names[id];
  };
  auto set_scalar = [&](int32_t col, SV head, SV tail) {
    if (col < 0) return;
    int32_t h31;
    int32_t id = sh->intern(head, tail, &h31);
    row.set_id(col, id, h31);
  };
  // 'tag' + the value's eight bytes
  auto set_scalar8 = [&](int32_t col, char tag, uint64_t value) {
    char key[9];
    key[0] = tag;
    memcpy(key + 1, &value, 8);
    set_scalar(col, SV(key, 9), SV());
  };

  for (const Entry& e : kept(rec.maps[STRINGS])) {
    const Name* name = name_of(e.key);
    SV value;
    if (!name || !word_of(static_cast<int32_t>(e.bits), &value)) continue;
    set_scalar(name->col, "s", value);
    if (name->bcol < 0) continue;
    if (name->kind == 0) row.set_bytes(name->bcol, value);
    else row.set_key_error(name->bcol);   // string under numeric slot
  }
  for (const Entry& e : kept(rec.maps[INT64S])) {
    const Name* name = name_of(e.key);
    if (!name) continue;
    set_scalar8(name->col, 'i', e.bits);
    if (name->bcol >= 0 && name->kind != 0)   // else int under string slot
      row.set_numeric_key(name->bcol, name->kind, 0.0,
                          static_cast<int64_t>(e.bits), false);
  }
  for (const Entry& e : kept(rec.maps[DOUBLES])) {
    const Name* name = name_of(e.key);
    if (!name) continue;
    set_scalar8(name->col, 'd', e.bits);
    if (name->bcol >= 0 && name->kind != 0) {
      double d;
      memcpy(&d, &e.bits, 8);
      row.set_numeric_key(name->bcol, name->kind, d, 0, true);
    }
  }
  for (const Entry& e : kept(rec.maps[BOOLS])) {
    const Name* name = name_of(e.key);
    if (!name || name->col < 0) continue;
    const char key[2] = {'b', e.bits ? '\1' : '\0'};
    row.set_id(name->col, e.bits ? ID_TRUE : ID_FALSE,
               hash31(fnv1a(FNV_BASIS, SV(key, 2))));
  }
  for (const Entry& e : kept(rec.maps[BYTES])) {
    const Name* name = name_of(e.key);
    if (!name) continue;
    if (e.bytes.size() == 4) {
      // v4 → v4-in-v6 canonical form (net.IP.Equal semantics)
      char key[17] = {'p', 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, '\xff', '\xff'};
      memcpy(key + 13, e.bytes.data(), 4);
      set_scalar(name->col, SV(key, 17), SV());
    } else {
      set_scalar(name->col, "p", e.bytes);
    }
    if (name->bcol < 0) continue;
    // raw bytes ride the byte plane (CIDR list lowering compares IP
    // bytes in v6-mapped space — layout._byte_source_value parity);
    // bytes under a numeric order-key slot are unencodable
    if (name->kind == 0) row.set_bytes(name->bcol, e.bytes);
    else row.set_key_error(name->bcol);
  }
  for (int map : {TIMESTAMPS, DURATIONS}) {
    for (const Entry& e : kept(rec.maps[map])) {
      const Name* name = name_of(e.key);
      if (!name) continue;
      int64_t seconds = static_cast<int64_t>(e.bits);
      int64_t ns = map == TIMESTAMPS ? ts_ns_like_python(seconds, e.nanos)
                                     : dur_ns_like_python(seconds, e.nanos);
      set_scalar8(name->col, map == TIMESTAMPS ? 't' : 'D',
                  static_cast<uint64_t>(ns));
      if (name->bcol >= 0 && name->kind != 0)
        row.set_numeric_key(name->bcol, name->kind, 0.0, ns, false);
    }
  }
  for (const Entry& m : kept(rec.maps[STRING_MAPS])) {
    const Name* mname = name_of(m.key);
    if (!mname) continue;
    if (mname->mcol >= 0) row.map_present[mname->mcol] = 1;
    if (!mname->pairs) continue;
    int32_t name_id = static_cast<int32_t>(mname - L.names.data());
    Entry* first = rec.map_entries.data() + m.first;
    Entry* last = keep_last(first, rec.map_entries.data() + m.last);
    for (const Entry* e = first; e != last; ++e) {
      SV key, value;
      if (!word_of(e->key, &key) ||
          !word_of(static_cast<int32_t>(e->bits), &value))
        continue;
      int32_t pid = L.find_pair(name_id, key);
      if (pid < 0) continue;
      set_scalar(L.pairs[pid].col, "s", value);
      if (L.pairs[pid].bcol >= 0) row.set_bytes(L.pairs[pid].bcol, value);
    }
  }
  row.finish_wide();
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
  for (uint32_t i = 0; i < n && r.ok; i++)
    L.global_words.push_back(r.str());
  n = r.u32();
  for (uint32_t i = 0; i < n && r.ok; i++) {
    int32_t col = static_cast<int32_t>(r.u32());
    L.names[L.add_name(r.str())].col = col;
  }
  n = r.u32();
  for (uint32_t i = 0; i < n && r.ok; i++) {
    int32_t col = static_cast<int32_t>(r.u32());
    L.names[L.add_name(r.str())].mcol = col;
  }
  n = r.u32();
  for (uint32_t i = 0; i < n && r.ok; i++) {
    int32_t col = static_cast<int32_t>(r.u32());
    std::string m = r.str(), k = r.str();
    L.add_pair(m, k).col = col;
  }
  n = r.u32();
  for (uint32_t i = 0; i < n && r.ok; i++) {
    int32_t bcol = static_cast<int32_t>(r.u32());
    uint8_t kind = r.u8();
    std::string a = r.str();
    if (kind == 1) {
      std::string k = r.str();
      L.add_pair(a, k).bcol = bcol;
    } else {
      Name& name = L.names[L.add_name(a)];
      name.bcol = bcol;
      name.kind = kind;
    }
  }
  L.n_columns = r.u32();
  L.n_maps = r.u32();
  L.n_byte = r.u32();
  for (const std::string& w : L.global_words)
    L.global_name.push_back(L.find_name(w));
  // seed interns (tag + canonical payload, pre-keyed by Python); the
  // two bools have their ids already
  n = r.u32();
  for (uint32_t i = 0; i < n && r.ok; i++) {
    std::string key = r.str();
    int32_t h31;
    if (key != SV("b\0", 2) && key != SV("b\1", 2))
      sh->intern(SV(), key, &h31);
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
  return static_cast<Shim*>(h)->next_id();
}

// Drop interned entries with id >= keep_count (runtime-observed
// values); compile-time seeds stay. Bounds a long-running server's
// intern memory — Python flushes in lockstep with its remap table.
void shim_flush_interns(void* h, int32_t keep_count) {
  auto* sh = static_cast<Shim*>(h);
  if (keep_count < 3 || keep_count >= sh->next_id()) return;
  sh->key_ends.resize(keep_count - 2);
  sh->key_bytes.resize(sh->key_ends.back());
  sh->intern_index.clear();
  for (int32_t id = 3; id < keep_count; id++)
    sh->intern_index.insert(fnv1a(FNV_BASIS, sh->key_of(id)), id);
}

// Export canonical keys for ids in [from_id, next_id): packed as
// u32 len + bytes per key. Returns bytes written or -needed.
int64_t shim_export_interns(void* h, int32_t from_id, uint8_t* buf,
                            size_t cap) {
  auto* sh = static_cast<Shim*>(h);
  if (from_id < 3) from_id = 3;
  const int32_t next = sh->next_id();
  if (from_id >= next) return 0;
  size_t need = 4 * static_cast<size_t>(next - from_id) +
                sh->key_ends.back() - sh->key_ends[from_id - 3];
  if (need > cap) return -static_cast<int64_t>(need);
  uint8_t* p = buf;
  for (int32_t id = from_id; id < next; id++) {
    SV k = sh->key_of(id);
    uint32_t n = static_cast<uint32_t>(k.size());
    memcpy(p, &n, 4);
    p += 4;
    memcpy(p, k.data(), n);
    p += n;
  }
  return static_cast<int64_t>(need);
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
// msgs[i] is read in place and not kept; a record of no bytes is a
// padding row. Returns 0 on success, <0 on parse error (row index
// encoded): the rows before it are written, that row is not.
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
  int32_t claimed = 0;
  if (n_wide) *n_wide = 0;

  for (int32_t i = 0; i < n; i++) {
    if (wide_bytes) wide_row[i] = -1;
    if (msg_lens[i] <= 0) continue;
    sh->rec.clear();
    if (!read_record(msgs[i], static_cast<size_t>(msg_lens[i]),
                     &sh->rec)) {
      sh->error = "parse failure at record " + std::to_string(i);
      return -(i + 1);
    }
    apply_row(sh, Row{ids + i * ncol, hash_ids + i * ncol,
                      present + i * ncol, map_present + i * nmap,
                      str_bytes + i * nbyte * slen, str_lens + i * nbyte,
                      slen, nbyte, wide_bytes, wide_lens,
                      wide_bytes ? wide_row + i : nullptr,
                      static_cast<size_t>(wide_len), &claimed});
  }
  if (n_wide) *n_wide = claimed;
  return 0;
}

}  // extern "C"
