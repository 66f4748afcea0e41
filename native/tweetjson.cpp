// Native tweet-JSON block ingest — the framework's data-loader hot loop.
//
// The reference delegates ingestion to Twitter4j/Spark receivers (external
// JVM dependencies, SURVEY.md §2.4); our replay/stream sources parse
// newline-delimited tweet JSON. CPython json.loads + object assembly is
// the object path's per-tweet host cost (rates on this machine: not
// measured, PERF.md), so this parser extracts exactly the fields the
// featurizer reads (MllibHelper.scala:42-95: the retweeted status' text,
// retweet_count, user counts, timestamp) straight into columnar buffers,
// applying the isRetweet + retweet-count-interval filter in-line
// (MllibHelper.scala:89-95). Text is emitted as UTF-16-LE code units with
// JSON escapes resolved (\uXXXX surrogate halves pass through exactly like
// the JVM sees them), ready for the UnitBatch wire format (the device
// hashes bigrams over these units — ops/text_hash.py).
//
// Only well-formed JSON is expected; a malformed line is skipped and
// counted (callers surface the count). Semantic ground truth remains the
// Python path (features/featurizer.py Status.from_json + filtrate +
// featurize) — differential tests assert unit-for-unit equality.
//
// Build: compiled into libfasthash.so together with fasthash.cpp.

#include <cstdint>
#include <cstring>

#if defined(__AVX2__)
#include <immintrin.h>
#endif

namespace {

struct Cursor {
  const char* p;
  const char* end;
  bool ok = true;

  bool at_end() const { return p >= end; }
  char peek() const { return at_end() ? '\0' : *p; }
  void skip_ws() {
    while (!at_end() && (*p == ' ' || *p == '\t' || *p == '\n' || *p == '\r'))
      ++p;
  }
  bool eat(char c) {
    skip_ws();
    if (at_end() || *p != c) return false;
    ++p;
    return true;
  }
};

// ---- string scanning ------------------------------------------------------

inline int hex_val(char c) {
  if (c >= '0' && c <= '9') return c - '0';
  if (c >= 'a' && c <= 'f') return c - 'a' + 10;
  if (c >= 'A' && c <= 'F') return c - 'A' + 10;
  return -1;
}

// Scan a JSON string (cursor at opening quote). If out != nullptr, write
// UTF-16 code units (escapes resolved, UTF-8 decoded) and return the unit
// count via *n_units (buffer has cap units; overflow sets cur.ok = false).
bool scan_string(Cursor& cur, uint16_t* out, int64_t cap, int64_t* n_units) {
  if (!cur.eat('"')) return false;
  int64_t n = 0;
  auto emit = [&](uint32_t cp) {
    if (out == nullptr) {
      n += cp >= 0x10000 ? 2 : 1;
      return;
    }
    if (cp >= 0x10000) {
      if (n + 2 > cap) { cur.ok = false; return; }
      cp -= 0x10000;
      out[n++] = static_cast<uint16_t>(0xD800 + (cp >> 10));
      out[n++] = static_cast<uint16_t>(0xDC00 + (cp & 0x3FF));
    } else {
      if (n + 1 > cap) { cur.ok = false; return; }
      out[n++] = static_cast<uint16_t>(cp);
    }
  };
  while (!cur.at_end() && cur.ok) {
    // bulk fast path: plain-ASCII runs (the overwhelming majority of tweet
    // bytes) copy/count without per-byte dispatch — SWAR scans 8 bytes per
    // iteration for the next special byte (quote/escape/UTF-8 lead); the
    // scalar loop below handles only that byte
    {
      const char* q = cur.p;
      while (cur.end - q >= 8) {
        uint64_t v;
        std::memcpy(&v, q, 8);
        uint64_t hi = v & 0x8080808080808080ULL;           // >= 0x80
        uint64_t xq = v ^ 0x2222222222222222ULL;           // '"'
        uint64_t xb = v ^ 0x5C5C5C5C5C5C5C5CULL;           // '\\'
        uint64_t sq = (xq - 0x0101010101010101ULL) & ~xq;
        uint64_t sb = (xb - 0x0101010101010101ULL) & ~xb;
        uint64_t special = (hi | sq | sb) & 0x8080808080808080ULL;
        if (special) {
          q += __builtin_ctzll(special) >> 3;
          break;
        }
        q += 8;
      }
      while (q < cur.end) {
        unsigned char cc = static_cast<unsigned char>(*q);
        if (cc == '"' || cc == '\\' || cc >= 0x80) break;
        ++q;
      }
      int64_t run = q - cur.p;
      if (run > 0) {
        if (out != nullptr) {
          if (n + run > cap) { cur.ok = false; return false; }
          for (int64_t i = 0; i < run; ++i)
            out[n + i] = static_cast<uint16_t>(
                static_cast<unsigned char>(cur.p[i]));
        }
        n += run;
        cur.p = q;
        if (cur.at_end()) break;
      }
    }
    unsigned char c = static_cast<unsigned char>(*cur.p);
    if (c == '"') {
      ++cur.p;
      if (n_units) *n_units = n;
      return true;
    }
    if (c == '\\') {
      ++cur.p;
      if (cur.at_end()) break;
      char e = *cur.p++;
      switch (e) {
        case '"': emit('"'); break;
        case '\\': emit('\\'); break;
        case '/': emit('/'); break;
        case 'b': emit('\b'); break;
        case 'f': emit('\f'); break;
        case 'n': emit('\n'); break;
        case 'r': emit('\r'); break;
        case 't': emit('\t'); break;
        case 'u': {
          if (cur.end - cur.p < 4) return false;
          int v = 0;
          for (int i = 0; i < 4; ++i) {
            int h = hex_val(cur.p[i]);
            if (h < 0) return false;
            v = (v << 4) | h;
          }
          cur.p += 4;
          // emit the unit as-is: surrogate halves stay halves, exactly the
          // JVM's view of the string (features/hashing.py utf16_units)
          if (out != nullptr) {
            if (n + 1 > cap) { cur.ok = false; break; }
            out[n++] = static_cast<uint16_t>(v);
          } else {
            n += 1;
          }
          break;
        }
        default: return false;
      }
      continue;
    }
    // UTF-8 decode (1-4 bytes) -> code point, matching what the Python
    // fallback's json.loads(bytes) accepts: overlong encodings and values
    // past U+10FFFF are malformed (CPython utf-8 is strict about those),
    // but UTF-8-encoded SURROGATE code points are kept as lone UTF-16
    // units — json decodes bytes with errors='surrogatepass', and the
    // hashing ground truth handles lone surrogates by design
    // (features/hashing.py utf16_units)
    uint32_t cp;
    int extra;
    if (c < 0x80) { cp = c; extra = 0; }
    else if ((c >> 5) == 0x6) { cp = c & 0x1F; extra = 1; }
    else if ((c >> 4) == 0xE) { cp = c & 0x0F; extra = 2; }
    else if ((c >> 3) == 0x1E) { cp = c & 0x07; extra = 3; }
    else return false;
    if (cur.end - cur.p < extra + 1) return false;
    for (int i = 1; i <= extra; ++i) {
      unsigned char cc = static_cast<unsigned char>(cur.p[i]);
      if ((cc >> 6) != 0x2) return false;
      cp = (cp << 6) | (cc & 0x3F);
    }
    if (extra == 1 && cp < 0x80) return false;          // overlong
    if (extra == 2 && cp < 0x800) return false;         // overlong
    if (extra == 3 && (cp < 0x10000 || cp > 0x10FFFF)) return false;
    cur.p += extra + 1;
    emit(cp);
  }
  return false;
}

// ---- generic value skipping ----------------------------------------------

// Depth cap: a well-formed line with ~100k nested brackets would otherwise
// recurse once per level and smash the C stack; past the cap the line is a
// counted bad line, like the Python fallback's caught RecursionError.
constexpr int kMaxSkipDepth = 256;

bool skip_value(Cursor& cur, int depth = 0);

bool skip_container(Cursor& cur, char open, char close, int depth) {
  if (depth >= kMaxSkipDepth) return false;
  if (!cur.eat(open)) return false;
  cur.skip_ws();
  if (cur.peek() == close) { ++cur.p; return true; }
  while (true) {
    if (open == '{') {
      if (!scan_string(cur, nullptr, 0, nullptr)) return false;
      if (!cur.eat(':')) return false;
    }
    if (!skip_value(cur, depth + 1)) return false;
    cur.skip_ws();
    if (cur.peek() == ',') { ++cur.p; cur.skip_ws(); continue; }
    if (cur.peek() == close) { ++cur.p; return true; }
    return false;
  }
}

bool skip_value(Cursor& cur, int depth) {
  cur.skip_ws();
  char c = cur.peek();
  if (c == '"') return scan_string(cur, nullptr, 0, nullptr);
  if (c == '{') return skip_container(cur, '{', '}', depth);
  if (c == '[') return skip_container(cur, '[', ']', depth);
  // number / true / false / null: scan to a structural delimiter
  const char* start = cur.p;
  while (!cur.at_end() && *cur.p != ',' && *cur.p != '}' && *cur.p != ']' &&
         *cur.p != ' ' && *cur.p != '\t' && *cur.p != '\n' && *cur.p != '\r')
    ++cur.p;
  return cur.p > start;
}

// Parse an integer-valued JSON number (or a string wrapping one, Twitter's
// "timestamp_ms"); fractional digits are truncated. Returns false on
// non-numeric values with the cursor UNTOUCHED (parsing happens on a probe
// copy), so the caller's skip_value fallback starts from a clean position —
// e.g. a non-numeric quoted value is then skipped as a string, matching the
// Python path's keep-the-row-with-default behavior.
bool parse_int(Cursor& cur, int64_t* out) {
  Cursor probe = cur;
  probe.skip_ws();
  bool quoted = probe.peek() == '"';
  if (quoted) ++probe.p;
  bool neg = false;
  if (probe.peek() == '-') { neg = true; ++probe.p; }
  if (probe.at_end() || *probe.p < '0' || *probe.p > '9') return false;
  int64_t v = 0;
  while (!probe.at_end() && *probe.p >= '0' && *probe.p <= '9')
    v = v * 10 + (*probe.p++ - '0');
  if (!probe.at_end() && *probe.p == '.') {  // truncate fraction
    ++probe.p;
    while (!probe.at_end() && *probe.p >= '0' && *probe.p <= '9') ++probe.p;
  }
  if (quoted && !probe.eat('"')) return false;
  *out = neg ? -v : v;
  cur = probe;
  return true;
}

// "Wed Aug 27 13:08:45 +0000 2008" -> epoch millis (0 on mismatch).
int64_t parse_created_at(const uint16_t* u, int64_t n) {
  if (n != 30) return 0;
  char s[31];
  for (int i = 0; i < 30; ++i) {
    if (u[i] > 127) return 0;
    s[i] = static_cast<char>(u[i]);
  }
  s[30] = '\0';
  static const char* months = "JanFebMarAprMayJunJulAugSepOctNovDec";
  int mon = -1;
  for (int m = 0; m < 12; ++m)
    if (std::memcmp(s + 4, months + m * 3, 3) == 0) { mon = m; break; }
  if (mon < 0) return 0;
  auto num = [&](int off, int len) {
    int v = 0;
    for (int i = 0; i < len; ++i) {
      if (s[off + i] < '0' || s[off + i] > '9') return -1;
      v = v * 10 + (s[off + i] - '0');
    }
    return v;
  };
  int day = num(8, 2), hh = num(11, 2), mm = num(14, 2), ss = num(17, 2);
  int tz_h = num(21, 2), tz_m = num(23, 2), year = num(26, 4);
  if (day < 0 || hh < 0 || mm < 0 || ss < 0 || tz_h < 0 || tz_m < 0 ||
      year < 0 || (s[20] != '+' && s[20] != '-'))
    return 0;
  // days since epoch (civil calendar, Howard Hinnant's algorithm)
  int y = year - (mon < 2 ? 1 : 0);
  int era = (y >= 0 ? y : y - 399) / 400;
  unsigned yoe = static_cast<unsigned>(y - era * 400);
  unsigned m2 = static_cast<unsigned>(mon >= 2 ? mon - 2 : mon + 10);
  unsigned doy = (153 * m2 + 2) / 5 + static_cast<unsigned>(day) - 1;
  unsigned doe = yoe * 365 + yoe / 4 - yoe / 100 + doy;
  int64_t days = static_cast<int64_t>(era) * 146097 +
                 static_cast<int64_t>(doe) - 719468;
  int64_t secs = days * 86400 + hh * 3600 + mm * 60 + ss;
  int64_t tz = (tz_h * 3600 + tz_m * 60);
  secs -= (s[20] == '+') ? tz : -tz;
  return secs * 1000;
}

struct RtFields {
  // absent numeric fields default to 0, exactly like Status.from_json
  int64_t retweet_count = 0;
  int64_t followers = 0, favourites = 0, friends = 0, created_ms = 0;
  int64_t text_units = 0;       // units written to the text buffer
  int64_t full_text_units = 0;  // units written to the full_text buffer
  bool present = false;
};

// Documented bound of the columnar wire format: a retweeted status whose
// "text"/"full_text" exceeds this many UTF-16 units makes the LINE a counted
// bad line (cur.ok = false on buffer overflow below). Real tweets cap well
// below this; the Python block fallback (_py_parse) pins the identical drop,
// and the object-ingest Status path (the semantic ground truth) has no such
// bound — a flagged, tested divergence on adversarial input only
// (tests/test_block_ingest.py::test_oversized_text_drops_line_both_paths).
constexpr int64_t kMaxTextUnits = 4096;

// Parse the retweeted_status object, extracting our fields. ``text_buf``
// and ``full_buf`` each hold kMaxTextUnits; the caller picks text-or-
// full_text afterwards (Status.from_json semantics: "text" wins unless
// empty — extended-tweet archives store the body in "full_text").
bool parse_rt_object(Cursor& cur, RtFields* rt, uint16_t* text_buf,
                     uint16_t* full_buf) {
  if (!cur.eat('{')) return false;
  rt->present = true;
  cur.skip_ws();
  if (cur.peek() == '}') { ++cur.p; return true; }
  uint16_t key[32];
  while (true) {
    int64_t klen = 0;
    {
      Cursor probe = cur;
      if (!scan_string(probe, key, 32, &klen)) {
        // long/unsupported key: skip it generically
        if (!scan_string(cur, nullptr, 0, nullptr)) return false;
        klen = -1;
      } else {
        cur = probe;
      }
    }
    if (!cur.eat(':')) return false;
    auto is_key = [&](const char* name) {
      int64_t len = static_cast<int64_t>(std::strlen(name));
      if (klen != len) return false;
      for (int64_t i = 0; i < len; ++i)
        if (key[i] != static_cast<uint16_t>(name[i])) return false;
      return true;
    };
    if (klen > 0 && is_key("text")) {
      cur.skip_ws();
      if (cur.peek() == '"') {
        if (!scan_string(cur, text_buf, kMaxTextUnits, &rt->text_units))
          return false;
      } else if (!skip_value(cur)) {
        return false;
      }
    } else if (klen > 0 && is_key("full_text")) {
      cur.skip_ws();
      if (cur.peek() == '"') {
        if (!scan_string(cur, full_buf, kMaxTextUnits, &rt->full_text_units))
          return false;
      } else if (!skip_value(cur)) {
        return false;
      }
    } else if (klen > 0 && is_key("retweet_count")) {
      if (!parse_int(cur, &rt->retweet_count)) {
        if (!skip_value(cur)) return false;
      }
    } else if (klen > 0 && is_key("timestamp_ms")) {
      int64_t v;
      if (parse_int(cur, &v)) rt->created_ms = v;
      else if (!skip_value(cur)) return false;
    } else if (klen > 0 && is_key("created_at")) {
      cur.skip_ws();
      if (cur.peek() == '"') {
        uint16_t date[40];
        int64_t dn = 0;
        if (!scan_string(cur, date, 40, &dn)) return false;
        if (rt->created_ms == 0) rt->created_ms = parse_created_at(date, dn);
      } else if (!skip_value(cur)) {
        return false;
      }
    } else if (klen > 0 && is_key("user")) {
      cur.skip_ws();
      if (cur.peek() != '{') {
        if (!skip_value(cur)) return false;
      } else {
        ++cur.p;
        cur.skip_ws();
        if (cur.peek() == '}') { ++cur.p; }
        else while (true) {
          int64_t uklen = 0;
          uint16_t ukey[32];
          Cursor probe = cur;
          if (!scan_string(probe, ukey, 32, &uklen)) {
            if (!scan_string(cur, nullptr, 0, nullptr)) return false;
            uklen = -1;
          } else {
            cur = probe;
          }
          if (!cur.eat(':')) return false;
          auto is_ukey = [&](const char* name) {
            int64_t len = static_cast<int64_t>(std::strlen(name));
            if (uklen != len) return false;
            for (int64_t i = 0; i < len; ++i)
              if (ukey[i] != static_cast<uint16_t>(name[i])) return false;
            return true;
          };
          int64_t* dst = nullptr;
          if (uklen > 0 && is_ukey("followers_count")) dst = &rt->followers;
          else if (uklen > 0 && is_ukey("favourites_count")) dst = &rt->favourites;
          else if (uklen > 0 && is_ukey("friends_count")) dst = &rt->friends;
          if (dst != nullptr) {
            if (!parse_int(cur, dst)) {
              if (!skip_value(cur)) return false;
            }
          } else if (!skip_value(cur)) {
            return false;
          }
          cur.skip_ws();
          if (cur.peek() == ',') { ++cur.p; continue; }
          if (cur.peek() == '}') { ++cur.p; break; }
          return false;
        }
      }
    } else if (!skip_value(cur)) {
      return false;
    }
    cur.skip_ws();
    if (cur.peek() == ',') { ++cur.p; cur.skip_ws(); continue; }
    if (cur.peek() == '}') { ++cur.p; return true; }
    return false;
  }
}

}  // namespace

extern "C" {

// Parse a block of newline-delimited tweet JSON, keeping only rows that
// pass the reference filter (isRetweet && begin <= rt.retweet_count <= end,
// MllibHelper.scala:89-95). Outputs, per kept row i:
//   out_numeric[i*5 .. i*5+4] = {retweet_count (label), followers,
//                                favourites, friends, created_ms}
//   out_units[out_offsets[i] .. out_offsets[i+1]) = the original tweet's
//     text as UTF-16 code units (escapes resolved; NOT lowercased — callers
//     use the pad-time ASCII fold + Python lower for non-ASCII rows)
//   out_ascii[i] = 1 when every unit < 128 (row skips Python lower())
//
// buf/len: UTF-8 bytes; rows split on '\n'. cap_rows/cap_units bound the
// outputs; parsing stops early (cleanly) when either would overflow, and
// *consumed reports how many input bytes were processed so the caller can
// continue from there. Malformed lines are skipped and counted in
// *bad_lines. Returns the number of kept rows.
int64_t parse_tweet_block(const char* buf, int64_t len,
                          int64_t begin, int64_t end,
                          int64_t cap_rows, int64_t cap_units,
                          int64_t* out_numeric, uint16_t* out_units,
                          int64_t* out_offsets, uint8_t* out_ascii,
                          int64_t* consumed, int64_t* bad_lines) {
  int64_t rows = 0, unit_pos = 0, bad = 0;
  const char* p = buf;
  const char* block_end = buf + len;
  out_offsets[0] = 0;
  uint16_t text[kMaxTextUnits];
  uint16_t full_text[kMaxTextUnits];
  while (p < block_end) {
    const char* nl = static_cast<const char*>(
        std::memchr(p, '\n', block_end - p));
    if (nl == nullptr) break;  // incomplete trailing line: leave for carry
    const char* line_end = nl;
    if (rows >= cap_rows || unit_pos + kMaxTextUnits > cap_units) break;
    Cursor cur{p, line_end};
    cur.skip_ws();
    if (!cur.at_end()) {
      RtFields rt;
      bool parsed = false;
      if (cur.eat('{')) {
        cur.skip_ws();
        parsed = true;
        if (cur.peek() == '}') { ++cur.p; }
        else while (true) {
          uint16_t key[32];
          int64_t klen = 0;
          Cursor probe = cur;
          if (!scan_string(probe, key, 32, &klen)) {
            if (!scan_string(cur, nullptr, 0, nullptr)) { parsed = false; break; }
            klen = -1;
          } else {
            cur = probe;
          }
          if (!cur.eat(':')) { parsed = false; break; }
          bool is_rt_key = false;
          if (klen == 16) {
            static const char* name = "retweeted_status";
            is_rt_key = true;
            for (int i = 0; i < 16; ++i)
              if (key[i] != static_cast<uint16_t>(name[i])) {
                is_rt_key = false;
                break;
              }
          }
          if (is_rt_key) {
            cur.skip_ws();
            if (cur.peek() == '{') {
              if (!parse_rt_object(cur, &rt, text, full_text)) {
                parsed = false;
                break;
              }
            } else if (!skip_value(cur)) {  // null and friends
              parsed = false;
              break;
            }
          } else if (!skip_value(cur)) {
            parsed = false;
            break;
          }
          cur.skip_ws();
          if (cur.peek() == ',') { ++cur.p; cur.skip_ws(); continue; }
          if (cur.peek() == '}') { ++cur.p; break; }
          parsed = false;
          break;
        }
      }
      if (!parsed || !cur.ok) {
        ++bad;
      } else if (rt.present && rt.retweet_count >= begin &&
                 rt.retweet_count <= end) {
        int64_t* num = out_numeric + rows * 5;
        num[0] = rt.retweet_count;
        num[1] = rt.followers;
        num[2] = rt.favourites;
        num[3] = rt.friends;
        num[4] = rt.created_ms;
        // "text" wins unless empty, else "full_text" (Status.from_json)
        const uint16_t* body = rt.text_units > 0 ? text : full_text;
        const int64_t body_units =
            rt.text_units > 0 ? rt.text_units : rt.full_text_units;
        bool ascii = true;
        for (int64_t i = 0; i < body_units; ++i) {
          out_units[unit_pos + i] = body[i];
          if (body[i] >= 128) ascii = false;
        }
        out_ascii[rows] = ascii ? 1 : 0;
        unit_pos += body_units;
        ++rows;
        out_offsets[rows] = unit_pos;
      }
    }
    p = nl + 1;
  }
  *consumed = p - buf;
  *bad_lines = bad;
  return rows;
}

}  // extern "C"

// ===== zero-copy wire emitter ==============================================
//
// parse_tweet_block_wire: the same tweet semantics as parse_tweet_block
// (same kept rows, units, numeric columns, ascii flags — differential-tested
// line for line), emitted straight in the RAGGED WIRE's representation:
//
//  - units land in the caller's uint8 buffer while every kept row is ASCII
//    (the narrow wire the featurizer would otherwise downcast to in a
//    separate pass) and widen ONCE into the uint16 buffer when the first
//    non-ASCII row commits — the committed prefix is converted in place,
//    never re-parsed;
//  - scanning classifies 32-byte chunks ONCE into special-byte masks
//    (quote/backslash/non-ASCII; AVX2 movemask, SWAR fallback) cached in a
//    monotonic stream cursor, so the per-token cost is a shift + tzcnt
//    instead of re-scanning bytes — short tokens (keys, ": " gaps) are
//    where the old per-call scanner burned its cycles;
//  - keys classify as raw bytes (length switch + one memcmp) in the
//    overwhelmingly common unescaped-ASCII case; escaped keys still decode
//    through scan_string, so "text" keeps matching "text";
//  - a rolling memmem prescreen skips lines that contain neither the
//    literal "retweeted_status" key nor any backslash (which could spell
//    the key via \u escapes): such a line can never produce a row, so it
//    skips at memchr speed. A prescreen-skipped line counts as a bad line
//    only when it does not even start with '{' — torn/garbled buffers stay
//    visible to the skip-and-count contract, while well-formed non-retweet
//    objects skip silently. (Full-parsed lines keep parse_tweet_block's
//    exact bad-line rules; whole-line JSON+UTF-8 validation is exactly what
//    the prescreen saves, so bad-line COUNTS — never kept rows — may
//    undercount the Python fallback's on keyless malformed lines.)

namespace {

// Monotonic special-byte stream over the block: aligned chunks (64 bytes
// with AVX-512BW, else 32) classify once into a bitmask of bytes that are
// '"', '\\' or >= 0x80; the cursor caches the current chunk's mask, so
// repeated next() calls inside one chunk cost a shift + tzcnt. Aligned
// loads never cross a page boundary, so reading the partial chunks at the
// block's edges is safe; bits outside [block start, hard_end) are masked
// off.
#if defined(__AVX512BW__)
constexpr int kStreamChunk = 64;
#else
constexpr int kStreamChunk = 32;
#endif

struct SpecialStream {
  const char* cur_base = nullptr;
  uint64_t cur_mask = 0;
  const char* hard_end = nullptr;

  inline uint64_t compute(const char* base) const {
    uint64_t m;
#if defined(__AVX512BW__)
    __m512i v = _mm512_load_si512(reinterpret_cast<const void*>(base));
    m = _mm512_cmpeq_epi8_mask(v, _mm512_set1_epi8('"')) |
        _mm512_cmpeq_epi8_mask(v, _mm512_set1_epi8('\\')) |
        _mm512_movepi8_mask(v);
#elif defined(__AVX2__)
    __m256i v = _mm256_load_si256(reinterpret_cast<const __m256i*>(base));
    m = static_cast<uint32_t>(_mm256_movemask_epi8(_mm256_or_si256(
            _mm256_cmpeq_epi8(v, _mm256_set1_epi8('"')),
            _mm256_cmpeq_epi8(v, _mm256_set1_epi8('\\'))))) |
        static_cast<uint32_t>(_mm256_movemask_epi8(v));
#else
    m = 0;
    for (int i = 0; i < 32; i += 8) {
      uint64_t v;
      std::memcpy(&v, base + i, 8);
      uint64_t hi = v & 0x8080808080808080ULL;
      uint64_t xq = v ^ 0x2222222222222222ULL;
      uint64_t xb = v ^ 0x5C5C5C5C5C5C5C5CULL;
      uint64_t sq = (xq - 0x0101010101010101ULL) & ~xq;
      uint64_t sb = (xb - 0x0101010101010101ULL) & ~xb;
      uint64_t special = (hi | sq | sb) & 0x8080808080808080ULL;
      // pack the per-byte high bits into 8 mask bits (movemask emulation)
      m |= ((special * 0x0002040810204081ULL) >> 56) << i;
    }
#endif
    if (base + kStreamChunk > hard_end) {
      int64_t valid = hard_end - base;
      m &= valid >= 64 ? ~0ull : ((1ull << valid) - 1);
    }
    return m;
  }

  // first special byte in [p, end); end when none.
  inline const char* next(const char* p, const char* end) {
    const char* base = reinterpret_cast<const char*>(
        reinterpret_cast<uintptr_t>(p) &
        ~static_cast<uintptr_t>(kStreamChunk - 1));
    uint64_t mask = base == cur_base ? cur_mask : compute(base);
    cur_base = base;
    cur_mask = mask;
    uint64_t live = mask & (~0ull << (p - base));
    while (live == 0) {
      base += kStreamChunk;
      if (base >= end) return end;
      mask = compute(base);
      cur_base = base;
      cur_mask = mask;
      live = mask;
    }
    const char* r = base + __builtin_ctzll(live);
    return r < end ? r : end;
  }
};

// validate/decode one UTF-8 sequence at p (first byte >= 0x80): writes the
// code point and returns the byte length, 0 on malformed. Identical accept
// set to scan_string: overlong and > U+10FFFF malformed, encoded SURROGATE
// code points pass (json.loads' errors='surrogatepass' view of the bytes).
inline int utf8_decode(const char* p, const char* end, uint32_t* cp_out) {
  unsigned char c = static_cast<unsigned char>(*p);
  uint32_t cp;
  int extra;
  if ((c >> 5) == 0x6) { cp = c & 0x1F; extra = 1; }
  else if ((c >> 4) == 0xE) { cp = c & 0x0F; extra = 2; }
  else if ((c >> 3) == 0x1E) { cp = c & 0x07; extra = 3; }
  else return 0;
  if (end - p < extra + 1) return 0;
  for (int i = 1; i <= extra; ++i) {
    unsigned char cc = static_cast<unsigned char>(p[i]);
    if ((cc >> 6) != 0x2) return 0;
    cp = (cp << 6) | (cc & 0x3F);
  }
  if (extra == 1 && cp < 0x80) return 0;
  if (extra == 2 && cp < 0x800) return 0;
  if (extra == 3 && (cp < 0x10000 || cp > 0x10FFFF)) return 0;
  *cp_out = cp;
  return extra + 1;
}

// ASCII run widen-copy: input bytes -> UTF-16 units.
inline void widen_copy(uint16_t* dst, const char* src, int64_t n) {
  int64_t i = 0;
#if defined(__AVX2__)
  for (; i + 16 <= n; i += 16) {
    __m128i b = _mm_loadu_si128(reinterpret_cast<const __m128i*>(src + i));
    _mm256_storeu_si256(reinterpret_cast<__m256i*>(dst + i),
                        _mm256_cvtepu8_epi16(b));
  }
#endif
  for (; i < n; ++i)
    dst[i] = static_cast<uint16_t>(static_cast<unsigned char>(src[i]));
}

// ASCII unit narrow-copy (every unit < 128 by the caller's row_ascii gate).
inline void narrow_copy(uint8_t* dst, const uint16_t* src, int64_t n) {
  int64_t i = 0;
#if defined(__AVX2__)
  for (; i + 16 <= n; i += 16) {
    __m128i lo = _mm_loadu_si128(reinterpret_cast<const __m128i*>(src + i));
    __m128i hi =
        _mm_loadu_si128(reinterpret_cast<const __m128i*>(src + i + 8));
    _mm_storeu_si128(reinterpret_cast<__m128i*>(dst + i),
                     _mm_packus_epi16(lo, hi));
  }
#endif
  for (; i < n; ++i) dst[i] = static_cast<uint8_t>(src[i]);
}

inline const char* wire_ws(const char* p, const char* end) {
  while (p < end &&
         (*p == ' ' || *p == '\t' || *p == '\n' || *p == '\r'))
    ++p;
  return p;
}

// number / true / false / null (and, as in skip_value, any garbage token):
// scan to a structural delimiter, non-empty.
inline const char* skip_token_fast(const char* p, const char* end) {
  const char* start = p;
  while (p < end && *p != ',' && *p != '}' && *p != ']' && *p != ' ' &&
         *p != '\t' && *p != '\n' && *p != '\r')
    ++p;
  return p > start ? p : nullptr;
}

// skip a string (p at the opening quote) validating escapes and UTF-8 —
// the accept set of scan_string(out=nullptr). Returns past the closing
// quote, nullptr on malformed/unterminated.
const char* skip_string_fast(SpecialStream& ss, const char* p,
                             const char* end) {
  ++p;
  for (;;) {
    p = ss.next(p, end);
    if (p >= end) return nullptr;
    unsigned char c = static_cast<unsigned char>(*p);
    if (c == '"') return p + 1;
    if (c == '\\') {
      if (end - p < 2) return nullptr;
      char e = p[1];
      if (e == 'u') {
        if (end - p < 6) return nullptr;
        if (hex_val(p[2]) < 0 || hex_val(p[3]) < 0 || hex_val(p[4]) < 0 ||
            hex_val(p[5]) < 0)
          return nullptr;
        p += 6;
      } else if (e == '"' || e == '\\' || e == '/' || e == 'b' ||
                 e == 'f' || e == 'n' || e == 'r' || e == 't') {
        p += 2;
      } else {
        return nullptr;
      }
      continue;
    }
    uint32_t cp;
    int adv = utf8_decode(p, end, &cp);
    if (adv == 0) return nullptr;
    p += adv;
  }
}

// grammar-following iterative value skip — the accept set of skip_value
// (including its kMaxSkipDepth container cap and its tolerance for garbage
// primitive tokens), with the per-byte recursion replaced by the masked
// string scanner and an explicit container stack.
const char* skip_value_fast(SpecialStream& ss, const char* p,
                            const char* end) {
  p = wire_ws(p, end);
  if (p >= end) return nullptr;
  char c = *p;
  if (c == '"') return skip_string_fast(ss, p, end);
  if (c != '{' && c != '[') return skip_token_fast(p, end);
  bool isobj[kMaxSkipDepth];
  int depth = 0;
  for (;;) {
    // p at '{' or '[' — push
    if (depth >= kMaxSkipDepth) return nullptr;
    isobj[depth++] = (*p == '{');
    ++p;
    p = wire_ws(p, end);
    if (p >= end) return nullptr;
    if ((*p == '}' && isobj[depth - 1]) ||
        (*p == ']' && !isobj[depth - 1]))
      goto close_one;
  element:
    if (isobj[depth - 1]) {
      if (*p != '"') return nullptr;
      p = skip_string_fast(ss, p, end);
      if (p == nullptr) return nullptr;
      p = wire_ws(p, end);
      if (p >= end || *p != ':') return nullptr;
      ++p;
      p = wire_ws(p, end);
      if (p >= end) return nullptr;
    }
    if (*p == '{' || *p == '[') continue;  // push the nested container
    if (*p == '"') {
      p = skip_string_fast(ss, p, end);
    } else {
      p = skip_token_fast(p, end);
    }
    if (p == nullptr) return nullptr;
  after_value:
    p = wire_ws(p, end);
    if (p >= end) return nullptr;
    if (*p == ',') {
      ++p;
      p = wire_ws(p, end);
      if (p >= end) return nullptr;
      goto element;
    }
    if ((*p == '}' && isobj[depth - 1]) ||
        (*p == ']' && !isobj[depth - 1])) {
    close_one:
      ++p;
      --depth;
      if (depth == 0) return p;
      goto after_value;
    }
    return nullptr;
  }
}

// parse_int's accept set without the probe-Cursor copies: optional quotes
// (Twitter's "timestamp_ms"), optional '-', >= 1 digit, truncated fraction;
// nullptr (out untouched) on non-numeric so the caller can skip generically.
inline const char* parse_int_fast(const char* p, const char* end,
                                  int64_t* out) {
  p = wire_ws(p, end);
  bool quoted = p < end && *p == '"';
  if (quoted) ++p;
  bool neg = false;
  if (p < end && *p == '-') { neg = true; ++p; }
  if (p >= end || *p < '0' || *p > '9') return nullptr;
  int64_t v = 0;
  while (p < end && *p >= '0' && *p <= '9') v = v * 10 + (*p++ - '0');
  if (p < end && *p == '.') {  // truncate fraction
    ++p;
    while (p < end && *p >= '0' && *p <= '9') ++p;
  }
  if (quoted) {
    p = wire_ws(p, end);
    if (p >= end || *p != '"') return nullptr;
    ++p;
  }
  *out = neg ? -v : v;
  return p;
}

// decode a string VALUE into UTF-16 units (p at the opening quote) with
// scan_string's exact emit rules (escapes resolved, \uXXXX kept as-is so
// surrogate halves pass through, UTF-8 decoded to units/pairs), tracking
// the max unit for the narrow-wire/ascii decisions. nullptr on malformed
// OR on overflowing cap — the line becomes a counted bad line, exactly the
// kMaxTextUnits wire bound of parse_tweet_block.
const char* scan_units_fast(SpecialStream& ss, const char* p,
                            const char* end, uint16_t* out, int64_t cap,
                            int64_t* n_out, uint32_t* max_unit) {
  ++p;
  int64_t n = 0;
  uint32_t mx = 0;
  for (;;) {
    const char* q = ss.next(p, end);
    int64_t run = q - p;
    if (run > 0) {
      if (n + run > cap) return nullptr;
      widen_copy(out + n, p, run);
      n += run;
      p = q;
    }
    if (p >= end) return nullptr;  // unterminated
    unsigned char c = static_cast<unsigned char>(*p);
    if (c == '"') {
      *n_out = n;
      *max_unit = mx;
      return p + 1;
    }
    if (c == '\\') {
      if (end - p < 2) return nullptr;
      char e = p[1];
      uint32_t cp;
      switch (e) {
        case '"': cp = '"'; p += 2; break;
        case '\\': cp = '\\'; p += 2; break;
        case '/': cp = '/'; p += 2; break;
        case 'b': cp = '\b'; p += 2; break;
        case 'f': cp = '\f'; p += 2; break;
        case 'n': cp = '\n'; p += 2; break;
        case 'r': cp = '\r'; p += 2; break;
        case 't': cp = '\t'; p += 2; break;
        case 'u': {
          if (end - p < 6) return nullptr;
          int v = 0;
          for (int i = 2; i < 6; ++i) {
            int h = hex_val(p[i]);
            if (h < 0) return nullptr;
            v = (v << 4) | h;
          }
          p += 6;
          cp = static_cast<uint32_t>(v);  // the unit as-is (JVM view)
          break;
        }
        default:
          return nullptr;
      }
      if (n + 1 > cap) return nullptr;
      out[n++] = static_cast<uint16_t>(cp);
      if (cp > mx) mx = cp;
      continue;
    }
    uint32_t cp;
    int adv = utf8_decode(p, end, &cp);
    if (adv == 0) return nullptr;
    p += adv;
    if (cp >= 0x10000) {
      if (n + 2 > cap) return nullptr;
      cp -= 0x10000;
      out[n++] = static_cast<uint16_t>(0xD800 + (cp >> 10));
      out[n++] = static_cast<uint16_t>(0xDC00 + (cp & 0x3FF));
      if (0xDC00u > mx) mx = 0xDC00u;
    } else {
      if (n + 1 > cap) return nullptr;
      out[n++] = static_cast<uint16_t>(cp);
      if (cp > mx) mx = cp;
    }
  }
}

// key ids for the fused scan+classify (context decides which ids it acts
// on; an id the context ignores behaves exactly like K_UNKNOWN)
enum KeyId : int {
  K_UNKNOWN = 0,
  K_RT,
  K_TEXT,
  K_FULL_TEXT,
  K_RETWEET_COUNT,
  K_TIMESTAMP_MS,
  K_CREATED_AT,
  K_USER,
  K_FOLLOWERS,
  K_FAVOURITES,
  K_FRIENDS,
};

inline int classify_key(const char* k, int64_t len) {
  switch (len) {
    case 4:
      if (std::memcmp(k, "text", 4) == 0) return K_TEXT;
      if (std::memcmp(k, "user", 4) == 0) return K_USER;
      return K_UNKNOWN;
    case 9:
      return std::memcmp(k, "full_text", 9) == 0 ? K_FULL_TEXT : K_UNKNOWN;
    case 10:
      return std::memcmp(k, "created_at", 10) == 0 ? K_CREATED_AT
                                                   : K_UNKNOWN;
    case 12:
      return std::memcmp(k, "timestamp_ms", 12) == 0 ? K_TIMESTAMP_MS
                                                     : K_UNKNOWN;
    case 13:
      if (std::memcmp(k, "retweet_count", 13) == 0) return K_RETWEET_COUNT;
      if (std::memcmp(k, "friends_count", 13) == 0) return K_FRIENDS;
      return K_UNKNOWN;
    case 15:
      return std::memcmp(k, "followers_count", 15) == 0 ? K_FOLLOWERS
                                                        : K_UNKNOWN;
    case 16:
      if (std::memcmp(k, "retweeted_status", 16) == 0) return K_RT;
      if (std::memcmp(k, "favourites_count", 16) == 0) return K_FAVOURITES;
      return K_UNKNOWN;
    default:
      return K_UNKNOWN;
  }
}

// scan a KEY string at p (opening quote) and classify it. Fast path: raw
// unescaped-ASCII bytes classify in place. Keys containing escapes or
// non-ASCII decode through scan_string (32-unit cap, as in
// parse_tweet_block — "text" still matches "text"); longer or
// unsupported keys skip generically and come back K_UNKNOWN. nullptr on
// malformed.
const char* scan_key_id(SpecialStream& ss, const char* p, const char* end,
                        int* id) {
  const char* q = ss.next(p + 1, end);
  if (q >= end) return nullptr;
  if (*q == '"') {
    *id = classify_key(p + 1, q - (p + 1));
    return q + 1;
  }
  Cursor probe{p, end};
  uint16_t k16[32];
  int64_t n = 0;
  if (scan_string(probe, k16, 32, &n) && probe.ok) {
    char kb[32];
    bool ascii = true;
    for (int64_t i = 0; i < n; ++i) {
      if (k16[i] > 127) { ascii = false; break; }
      kb[i] = static_cast<char>(k16[i]);
    }
    *id = ascii ? classify_key(kb, n) : K_UNKNOWN;
    return probe.p;
  }
  Cursor c{p, end};
  if (!scan_string(c, nullptr, 0, nullptr)) return nullptr;
  *id = K_UNKNOWN;
  return c.p;
}

struct RtWire {
  int64_t retweet_count = 0;
  int64_t followers = 0, favourites = 0, friends = 0, created_ms = 0;
  int64_t text_units = 0, full_units = 0;
  uint32_t text_max = 0, full_max = 0;
  bool present = false;
};

// parse_rt_object's semantics on the fast primitives: field staging, the
// duplicate-key/occurrence rules, and the text/full_text wire bound all
// mirror the reference implementation above.
const char* parse_rt_wire(SpecialStream& ss, const char* p, const char* end,
                          RtWire* rt, uint16_t* text, uint16_t* full) {
  rt->present = true;
  ++p;  // '{'
  p = wire_ws(p, end);
  if (p < end && *p == '}') return p + 1;
  for (;;) {
    if (p >= end || *p != '"') return nullptr;
    int key;
    p = scan_key_id(ss, p, end, &key);
    if (p == nullptr) return nullptr;
    p = wire_ws(p, end);
    if (p >= end || *p != ':') return nullptr;
    ++p;
    switch (key) {
      case K_TEXT:
      case K_FULL_TEXT: {
        p = wire_ws(p, end);
        if (p < end && *p == '"') {
          p = key == K_TEXT
                  ? scan_units_fast(ss, p, end, text, kMaxTextUnits,
                                    &rt->text_units, &rt->text_max)
                  : scan_units_fast(ss, p, end, full, kMaxTextUnits,
                                    &rt->full_units, &rt->full_max);
        } else {
          p = skip_value_fast(ss, p, end);
        }
        break;
      }
      case K_RETWEET_COUNT: {
        const char* r = parse_int_fast(p, end, &rt->retweet_count);
        p = r != nullptr ? r : skip_value_fast(ss, p, end);
        break;
      }
      case K_TIMESTAMP_MS: {
        int64_t v;
        const char* r = parse_int_fast(p, end, &v);
        if (r != nullptr) {
          rt->created_ms = v;
          p = r;
        } else {
          p = skip_value_fast(ss, p, end);
        }
        break;
      }
      case K_CREATED_AT: {
        p = wire_ws(p, end);
        if (p < end && *p == '"') {
          uint16_t date[40];
          int64_t dn = 0;
          uint32_t dmax = 0;
          p = scan_units_fast(ss, p, end, date, 40, &dn, &dmax);
          if (p != nullptr && rt->created_ms == 0)
            rt->created_ms = parse_created_at(date, dn);
        } else {
          p = skip_value_fast(ss, p, end);
        }
        break;
      }
      case K_USER: {
        p = wire_ws(p, end);
        if (p >= end || *p != '{') {
          p = skip_value_fast(ss, p, end);
          break;
        }
        ++p;
        p = wire_ws(p, end);
        if (p < end && *p == '}') {
          ++p;
          break;
        }
        for (;;) {
          if (p >= end || *p != '"') return nullptr;
          int ukey;
          p = scan_key_id(ss, p, end, &ukey);
          if (p == nullptr) return nullptr;
          p = wire_ws(p, end);
          if (p >= end || *p != ':') return nullptr;
          ++p;
          int64_t* dst = nullptr;
          if (ukey == K_FOLLOWERS) dst = &rt->followers;
          else if (ukey == K_FAVOURITES) dst = &rt->favourites;
          else if (ukey == K_FRIENDS) dst = &rt->friends;
          if (dst != nullptr) {
            const char* r = parse_int_fast(p, end, dst);
            p = r != nullptr ? r : skip_value_fast(ss, p, end);
          } else {
            p = skip_value_fast(ss, p, end);
          }
          if (p == nullptr) return nullptr;
          p = wire_ws(p, end);
          if (p < end && *p == ',') {
            ++p;
            p = wire_ws(p, end);
            continue;
          }
          if (p < end && *p == '}') {
            ++p;
            break;
          }
          return nullptr;
        }
        break;
      }
      default:
        p = skip_value_fast(ss, p, end);
        break;
    }
    if (p == nullptr) return nullptr;
    p = wire_ws(p, end);
    if (p < end && *p == ',') {
      ++p;
      p = wire_ws(p, end);
      continue;
    }
    if (p < end && *p == '}') return p + 1;
    return nullptr;
  }
}

}  // namespace

extern "C" {

// Parse newline-delimited tweet JSON straight into the ragged-wire unit
// representation (see the banner comment above). Outputs per kept row i:
//   out_numeric[i*5 .. i*5+4], out_offsets[i]/[i+1], out_ascii[i] — as in
//   parse_tweet_block;
//   units: out_units_u8[...] while *narrow_out (every kept row ASCII so
//   far), else out_units_u16[...] — on the first non-ASCII commit the
//   already-written u8 prefix widens into out_units_u16 and the parse
//   continues wide. out_units_u16 may be NULL: a parse that then needs to
//   widen stops cleanly BEFORE the offending line (*needs_wide = 1,
//   *consumed excludes it) so the caller can retry the remainder with a
//   wide buffer.
// cap_rows/cap_units/consumed/bad_lines behave as in parse_tweet_block.
int64_t parse_tweet_block_wire(const char* buf, int64_t len,
                               int64_t begin, int64_t end_count,
                               int64_t cap_rows, int64_t cap_units,
                               int64_t* out_numeric, uint8_t* out_units_u8,
                               uint16_t* out_units_u16, int64_t* out_offsets,
                               uint8_t* out_ascii, int64_t* consumed,
                               int64_t* bad_lines, int64_t* narrow_out,
                               int64_t* needs_wide_out) {
  int64_t rows = 0, unit_pos = 0, bad = 0;
  bool narrow = true;
  *needs_wide_out = 0;
  const char* p = buf;
  const char* hard_end = buf + len;
  out_offsets[0] = 0;
  uint16_t text[kMaxTextUnits];
  uint16_t full[kMaxTextUnits];
  SpecialStream ss;
  ss.hard_end = hard_end;
  static const char kNeedle[] = "\"retweeted_status\"";
  const size_t kNeedleLen = 18;
  const char* next_key = nullptr;
  bool key_stale = true;
  // adaptive prescreen: while the previous full-parsed line carried the rt
  // key (retweet-dense corpora — the replay/bench regime), the memmem is
  // pure overhead, so it stands down until a keyless line reappears. Purely
  // an optimization: which lines full-parse is a deterministic function of
  // the input bytes either way.
  bool assume_key = false;
  while (p < hard_end) {
    const char* nl =
        static_cast<const char*>(std::memchr(p, '\n', hard_end - p));
    if (nl == nullptr) break;  // incomplete trailing line: leave for carry
    if (rows >= cap_rows || unit_pos + kMaxTextUnits > cap_units) break;
    const char* line_end = nl;
    // ---- prescreen ------------------------------------------------------
    if (!assume_key) {
      if (key_stale || (next_key != nullptr && next_key < p)) {
        next_key = static_cast<const char*>(
            memmem(p, hard_end - p, kNeedle, kNeedleLen));
        key_stale = false;
      }
      bool has_key = next_key != nullptr && next_key < line_end;
      if (!has_key && std::memchr(p, '\\', line_end - p) == nullptr) {
        const char* q = wire_ws(p, line_end);
        if (q != line_end && *q != '{') ++bad;  // garbage stays visible
        p = nl + 1;
        continue;
      }
    } else {
      key_stale = true;  // the rolling memmem restarts when it re-engages
    }
    // ---- full parse (parse_tweet_block's line semantics) ----------------
    const char* q = wire_ws(p, line_end);
    if (q == line_end) {  // blank line
      p = nl + 1;
      continue;
    }
    bool parsed = false;
    bool saw_rt = false;
    RtWire rt;
    if (*q == '{') {
      parsed = true;
      ++q;
      q = wire_ws(q, line_end);
      if (q < line_end && *q == '}') {
        ++q;
      } else {
        for (;;) {
          if (q >= line_end || *q != '"') { parsed = false; break; }
          int key;
          q = scan_key_id(ss, q, line_end, &key);
          if (q == nullptr) { parsed = false; break; }
          q = wire_ws(q, line_end);
          if (q >= line_end || *q != ':') { parsed = false; break; }
          ++q;
          if (key == K_RT) {
            saw_rt = true;
            q = wire_ws(q, line_end);
            if (q < line_end && *q == '{') {
              q = parse_rt_wire(ss, q, line_end, &rt, text, full);
            } else {  // null and friends
              q = skip_value_fast(ss, q, line_end);
            }
          } else {
            q = skip_value_fast(ss, q, line_end);
          }
          if (q == nullptr) { parsed = false; break; }
          q = wire_ws(q, line_end);
          if (q < line_end && *q == ',') {
            ++q;
            q = wire_ws(q, line_end);
            continue;
          }
          if (q < line_end && *q == '}') { ++q; break; }
          parsed = false;
          break;
        }
      }
    }
    assume_key = saw_rt;
    if (!parsed) {
      ++bad;
    } else if (rt.present && rt.retweet_count >= begin &&
               rt.retweet_count <= end_count) {
      // "text" wins unless empty, else "full_text" (Status.from_json)
      const uint16_t* body = rt.text_units > 0 ? text : full;
      const int64_t body_units =
          rt.text_units > 0 ? rt.text_units : rt.full_units;
      const uint32_t body_max =
          rt.text_units > 0 ? rt.text_max : rt.full_max;
      bool row_ascii = body_max < 128;
      if (!row_ascii && narrow) {
        if (out_units_u16 == nullptr) {
          // no wide buffer: stop cleanly before this line (caller retries)
          *needs_wide_out = 1;
          break;
        }
        widen_copy(out_units_u16,
                   reinterpret_cast<const char*>(out_units_u8), unit_pos);
        narrow = false;
      }
      if (narrow) {
        narrow_copy(out_units_u8 + unit_pos, body, body_units);
      } else {
        std::memcpy(out_units_u16 + unit_pos, body,
                    static_cast<size_t>(body_units) * 2);
      }
      int64_t* num = out_numeric + rows * 5;
      num[0] = rt.retweet_count;
      num[1] = rt.followers;
      num[2] = rt.favourites;
      num[3] = rt.friends;
      num[4] = rt.created_ms;
      out_ascii[rows] = row_ascii ? 1 : 0;
      unit_pos += body_units;
      ++rows;
      out_offsets[rows] = unit_pos;
    }
    p = nl + 1;
  }
  *consumed = p - buf;
  *bad_lines = bad;
  *narrow_out = narrow ? 1 : 0;
  return rows;
}

}  // extern "C"
