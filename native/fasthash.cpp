// Fast char-bigram HashingTF featurizer — the host-side hot loop in C++.
//
// Semantics are identical to twtml_tpu/features/hashing.py (the ground
// truth): Java String.hashCode over UTF-16 code units per bigram
// (h = 31*cu0 + cu1 in int32 arithmetic), nonNegativeMod into num_features,
// term-frequency counts deduplicated per tweet. The Python caller lowercases
// and encodes to UTF-16-LE (locale-correct, cheap CPython fast paths); this
// code consumes raw code units — surrogate pairs therefore contribute their
// two units exactly like the JVM, matching MllibHelper.scala:42-56 /
// MLlib HashingTF.
//
// Build: g++ -O3 -shared -fPIC -pthread -o libfasthash.so fasthash.cpp
// Loaded via ctypes (twtml_tpu/features/native.py); pure-Python fallback
// remains authoritative for parity tests.

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>

namespace {

// Open-addressing scratch table for per-tweet term-frequency dedup.
// Tweets cap at 280 chars -> <=279 bigrams; 1024 slots keep load < 0.28.
constexpr int kTableSize = 1024;  // power of two
constexpr int kTableMask = kTableSize - 1;

struct Slot {
  int32_t idx;   // hashed feature index, -1 = empty
  float count;
};

inline int32_t non_negative_mod(int32_t x, int32_t m) {
  int32_t r = x % m;           // C++ % truncates toward zero, like Java
  return r < 0 ? r + m : r;
}

}  // namespace

extern "C" {

// Featurize one micro-batch of lowercased UTF-16-LE texts.
//
//   units:        concatenated code units of all texts
//   offsets:      B+1 prefix offsets into `units` (in code units)
//   batch:        number of texts B
//   num_features: HashingTF dimensionality
//   l_max:        token capacity per row in the padded output
//   out_idx:      [B, l_max] int32, caller-zeroed
//   out_val:      [B, l_max] float32, caller-zeroed
//   out_ntok:     [B] int32 — distinct hashed terms per tweet (may exceed
//                 l_max; caller re-buckets and retries in that case)
//
// Returns the maximum distinct-term count seen (for bucket sizing).
static int32_t fasthash_rows(const uint16_t* units, const int64_t* offsets,
                             int32_t row_begin, int32_t row_end,
                             int32_t num_features, int32_t l_max,
                             int32_t* out_idx, float* out_val,
                             int32_t* out_ntok) {
  Slot table[kTableSize];
  for (int32_t i = 0; i < kTableSize; ++i) table[i].idx = -1;
  int32_t max_terms = 0;

  for (int32_t b = row_begin; b < row_end; ++b) {
    const int64_t start = offsets[b];
    const int64_t end = offsets[b + 1];
    const int64_t len = end - start;

    // collect this tweet's distinct (index, count) pairs
    int32_t used[kTableSize];
    int32_t n_used = 0;

    bool overflowed = false;
    auto add_term = [&](int32_t h) {
      // A full table has no empty slot to terminate the probe loop, and a
      // new distinct term couldn't be inserted anyway — bail to the exact
      // Python path before probing.
      if (n_used == kTableSize) {
        overflowed = true;
        return;
      }
      const int32_t idx = non_negative_mod(h, num_features);
      uint32_t probe = static_cast<uint32_t>(idx) & kTableMask;
      while (true) {
        Slot& s = table[probe];
        if (s.idx == idx) {
          s.count += 1.0f;
          return;
        }
        if (s.idx < 0) {
          s.idx = idx;
          s.count = 1.0f;
          used[n_used++] = static_cast<int32_t>(probe);
          return;
        }
        probe = (probe + 1) & kTableMask;
      }
    };

    if (len == 1) {
      // sliding(2) on a 1-unit string yields the string itself
      add_term(static_cast<int32_t>(units[start]));
    } else {
      for (int64_t i = start; i + 1 < end && !overflowed; ++i) {
        // Java hashCode of the 2-unit string: 31*cu0 + cu1 (int32 wrap)
        const int32_t h = static_cast<int32_t>(
            31u * static_cast<uint32_t>(units[i]) +
            static_cast<uint32_t>(units[i + 1]));
        add_term(h);
      }
    }

    if (overflowed) {
      // >kTableSize distinct terms in one tweet: unambiguous sentinel so the
      // Python caller falls back to the exact path
      out_ntok[b] = -1;
      for (int32_t j = 0; j < n_used; ++j) table[used[j]].idx = -1;
      continue;
    }
    out_ntok[b] = n_used;
    if (n_used > max_terms) max_terms = n_used;
    const int32_t n_emit = n_used < l_max ? n_used : l_max;
    int32_t* row_idx = out_idx + static_cast<int64_t>(b) * l_max;
    float* row_val = out_val + static_cast<int64_t>(b) * l_max;
    for (int32_t j = 0; j < n_emit; ++j) {
      const Slot& s = table[used[j]];
      row_idx[j] = s.idx;
      row_val[j] = s.count;
    }
    // reset only the touched slots for the next row (the full table is
    // cleared once per thread above)
    for (int32_t j = 0; j < n_used; ++j) table[used[j]].idx = -1;
  }
  return max_terms;
}

// Featurize one micro-batch, row-parallel across up to n_threads OS threads
// (rows are independent; each thread owns a contiguous row range and its own
// scratch table). n_threads <= 0 means auto (hardware concurrency, capped).
// The ctypes caller releases the GIL for the duration of this call.
int32_t fasthash_batch(const uint16_t* units, const int64_t* offsets,
                       int32_t batch, int32_t num_features, int32_t l_max,
                       int32_t* out_idx, float* out_val, int32_t* out_ntok,
                       int32_t n_threads) {
  constexpr int32_t kMinRowsPerThread = 256;
  if (n_threads <= 0) {
    const unsigned hw = std::thread::hardware_concurrency();
    n_threads = static_cast<int32_t>(hw ? std::min(hw, 8u) : 1u);
  }
  n_threads = std::max(
      1, std::min(n_threads, batch / kMinRowsPerThread));

  if (n_threads == 1) {
    return fasthash_rows(units, offsets, 0, batch, num_features, l_max,
                         out_idx, out_val, out_ntok);
  }

  std::vector<int32_t> maxes(n_threads, 0);
  std::vector<std::thread> workers;
  workers.reserve(n_threads);
  const int32_t rows_per = (batch + n_threads - 1) / n_threads;
  for (int32_t t = 0; t < n_threads; ++t) {
    const int32_t b0 = t * rows_per;
    const int32_t b1 = std::min(batch, b0 + rows_per);
    workers.emplace_back([=, &maxes] {
      maxes[t] = fasthash_rows(units, offsets, b0, b1, num_features, l_max,
                               out_idx, out_val, out_ntok);
    });
  }
  int32_t max_terms = 0;
  for (int32_t t = 0; t < n_threads; ++t) {
    workers[t].join();
    max_terms = std::max(max_terms, maxes[t]);
  }
  return max_terms;
}

// Ragged→padded copy for the on-device featurization wire format
// (UnitBatch): concatenated code units → [padded_rows, l_max] uint16 with
// zero padding, plus per-row unit counts (row-sliced memcpys instead of
// numpy's vectorized gather). Rows in [batch, padded_rows) are
// zeroed here too, so the caller can hand in uninitialized buffers.
// ascii_lower != 0 folds 'A'-'Z' to lowercase during the copy: the Python
// caller then only pays str.lower() for texts containing non-ASCII chars
// (those are pre-lowered, and re-folding their ASCII range is idempotent).
// Returns the maximum row length seen; the caller sized l_max from the same
// offsets, so a return value > l_max means caller error (nothing truncated
// silently — the rows are copied clamped but flagged by the return).
int32_t pad_units_batch(const uint16_t* units, const int64_t* offsets,
                        int32_t batch, int32_t padded_rows, int32_t l_max,
                        int32_t ascii_lower, uint16_t* out_units,
                        int32_t* out_len) {
  int32_t max_len = 0;
  for (int32_t b = 0; b < batch; ++b) {
    const int64_t start = offsets[b];
    const int64_t len = offsets[b + 1] - start;
    max_len = std::max(max_len, static_cast<int32_t>(len));
    const int64_t n = std::min<int64_t>(len, l_max);
    uint16_t* row = out_units + static_cast<int64_t>(b) * l_max;
    if (ascii_lower) {
      for (int64_t i = 0; i < n; ++i) {
        const uint16_t u = units[start + i];
        row[i] = (u >= 'A' && u <= 'Z') ? u + 32 : u;
      }
    } else {
      std::memcpy(row, units + start, n * sizeof(uint16_t));
    }
    std::memset(row + n, 0, (l_max - n) * sizeof(uint16_t));
    out_len[b] = static_cast<int32_t>(n);
  }
  if (padded_rows > batch) {
    std::memset(out_units + static_cast<int64_t>(batch) * l_max, 0,
                static_cast<int64_t>(padded_rows - batch) * l_max *
                    sizeof(uint16_t));
    std::memset(out_len + batch, 0,
                (padded_rows - batch) * sizeof(int32_t));
  }
  return max_len;
}

// uint8 variant of pad_units_batch: the narrow wire format for batches the
// caller KNOWS are byte-ranged (every row ASCII-flagged by the parser /
// isascii() on the host path) — the units buffer is the largest tensor on
// the host→device wire, so the narrow pad halves it with zero extra scans. Units >= 256 must not reach
// this function (the caller's ascii gate guarantees < 128).
int32_t pad_units_batch_u8(const uint16_t* units, const int64_t* offsets,
                           int32_t batch, int32_t padded_rows, int32_t l_max,
                           int32_t ascii_lower, uint8_t* out_units,
                           int32_t* out_len) {
  int32_t max_len = 0;
  for (int32_t b = 0; b < batch; ++b) {
    const int64_t start = offsets[b];
    const int64_t len = offsets[b + 1] - start;
    max_len = std::max(max_len, static_cast<int32_t>(len));
    const int64_t n = std::min<int64_t>(len, l_max);
    uint8_t* row = out_units + static_cast<int64_t>(b) * l_max;
    if (ascii_lower) {
      for (int64_t i = 0; i < n; ++i) {
        const uint16_t u = units[start + i];
        row[i] = static_cast<uint8_t>((u >= 'A' && u <= 'Z') ? u + 32 : u);
      }
    } else {
      for (int64_t i = 0; i < n; ++i)
        row[i] = static_cast<uint8_t>(units[start + i]);
    }
    std::memset(row + n, 0, l_max - n);
    out_len[b] = static_cast<int32_t>(n);
  }
  if (padded_rows > batch) {
    std::memset(out_units + static_cast<int64_t>(batch) * l_max, 0,
                static_cast<int64_t>(padded_rows - batch) * l_max);
    std::memset(out_len + batch, 0,
                (padded_rows - batch) * sizeof(int32_t));
  }
  return max_len;
}

// Lexicon sentiment scorer over raw UTF-16 units (features/sentiment.py's
// C hot path), exact for EVERY row: it tokenizes as Python's `[a-z']+`
// regex does over `text.lower()`. A-Z fold inline. Over all of Unicode only
// two code points >= 128 lower-case into `[a-z']`: U+0130 (to `i` + U+0307:
// the token takes an `i` and ends, U+0307 being a separator) and U+212A
// (to `k`); both are handled here, and every other unit >= 128 — the halves
// of a surrogate pair included, since no astral code point lower-cases into
// ASCII — is a separator (tests/test_sentiment_labeler.py checks every code
// point). Units arrive as uint16, or as the narrow wire's uint8
// (`unit_bytes` 1: no widened copy of the block).
// Lexicon words arrive as concatenated units + offsets with precomputed
// Java-hashCode values; both lists go into one small open-addressed table
// on that hash (built per call: 63 inserts), and a hash hit verifies the
// actual units, so a colliding non-lexicon token can never flip a label vs
// the Python set.
}  // extern "C" (templates cannot have C linkage)

namespace {
constexpr int32_t kLexSlots = 256;  // power of two, >= 2x the words held
constexpr int32_t kLexMaxWord = 31;  // a longer listed word is refused

// `[a-z']` after lower-casing, else 0 (a separator)
struct LexFold {
  uint8_t ascii[128];
  constexpr LexFold() : ascii() {
    for (int u = 0; u < 128; ++u)
      ascii[u] = (u >= 'a' && u <= 'z')   ? u
                 : (u >= 'A' && u <= 'Z') ? u + 32
                 : u == '\''              ? u
                                          : 0;
  }
};
constexpr LexFold kLexFold;

inline uint16_t lex_fold(uint16_t u) {
  if (u < 128) return kLexFold.ascii[u];
  if (u == 0x212A) return 'k';
  return u == 0x0130 ? 'i' : 0;
}

struct LexTable {
  int32_t hash[kLexSlots];
  const uint16_t* word[kLexSlots];
  int8_t len[kLexSlots];  // 0 = empty slot
  int8_t sign[kLexSlots];
  // bit L of lens_of[c & 31]: some word of L units starts with letter c (a
  // token that fails it is no lexicon word: no fold, no hash, no probe)
  uint32_t lens_of[32];
  int32_t used = 0;

  LexTable() {
    std::memset(len, 0, sizeof(len));
    std::memset(lens_of, 0, sizeof(lens_of));
  }

  static int32_t slot_of(int32_t h) {
    return static_cast<int32_t>((static_cast<uint32_t>(h) * 0x9E3779B1u) >> 24);
  }

  void add(const uint16_t* words, const int64_t* off, const int32_t* hashes,
           int32_t n, int8_t s) {
    for (int32_t w = 0; w < n; ++w) {
      const int32_t len_w = static_cast<int32_t>(off[w + 1] - off[w]);
      // sentiment.py's _pack_lexicon refuses a lexicon that would trip these
      if (len_w <= 0 || len_w > kLexMaxWord || ++used > kLexSlots / 2) return;
      int32_t i = slot_of(hashes[w]);
      while (len[i] != 0) i = (i + 1) & (kLexSlots - 1);
      hash[i] = hashes[w];
      word[i] = words + off[w];
      len[i] = static_cast<int8_t>(len_w);
      sign[i] = s;
      lens_of[words[off[w]] & 31] |= 1u << len_w;
    }
  }

  int32_t find(const uint16_t* tok, int32_t tok_len, int32_t tok_hash) const {
    for (int32_t i = slot_of(tok_hash); len[i] != 0;
         i = (i + 1) & (kLexSlots - 1)) {
      if (hash[i] == tok_hash && len[i] == tok_len &&
          std::memcmp(word[i], tok, tok_len * sizeof(uint16_t)) == 0)
        return sign[i];
    }
    return 0;
  }
};

template <typename Unit>
void lexicon_score_rows(const Unit* units, const int64_t* offsets,
                        int32_t batch, const LexTable& table,
                        int32_t* out_score) {
  for (int32_t b = 0; b < batch; ++b) {
    const int64_t end = offsets[b + 1];
    int32_t score = 0;
    int64_t tok_start = -1;  // the open token's first unit, -1 = none
    auto flush = [&](int64_t stop) {
      const int64_t len = stop - tok_start;
      const uint16_t first = lex_fold(units[tok_start]);
      if (len <= kLexMaxWord && ((table.lens_of[first & 31] >> len) & 1u)) {
        uint16_t tok[kLexMaxWord];
        int32_t h = 0;
        for (int64_t k = 0; k < len; ++k) {
          tok[k] = lex_fold(units[tok_start + k]);
          h = static_cast<int32_t>(31u * static_cast<uint32_t>(h) +
                                   static_cast<uint32_t>(tok[k]));
        }
        score += table.find(tok, static_cast<int32_t>(len), h);
      }
      tok_start = -1;
    };
    for (int64_t i = offsets[b]; i < end; ++i) {
      const uint16_t u = units[i];
      if (lex_fold(u)) {
        if (tok_start < 0) tok_start = i;
        if (u == 0x0130) flush(i + 1);  // `i` + U+0307: the token ends here
      } else if (tok_start >= 0) {
        flush(i);
      }
    }
    if (tok_start >= 0) flush(end);
    out_score[b] = score;
  }
}
}  // namespace

extern "C" {

void lexicon_score_batch(const void* units, int32_t unit_bytes,
                         const int64_t* offsets, int32_t batch,
                         const uint16_t* pos_words, const int64_t* pos_off,
                         const int32_t* pos_hash, int32_t n_pos,
                         const uint16_t* neg_words, const int64_t* neg_off,
                         const int32_t* neg_hash, int32_t n_neg,
                         int32_t* out_score) {
  LexTable table;
  table.add(pos_words, pos_off, pos_hash, n_pos, 1);
  table.add(neg_words, neg_off, neg_hash, n_neg, -1);
  if (unit_bytes == 1)
    lexicon_score_rows(static_cast<const uint8_t*>(units), offsets, batch,
                       table, out_score);
  else
    lexicon_score_rows(static_cast<const uint16_t*>(units), offsets, batch,
                       table, out_score);
}

}  // extern "C"
