// One-pass wire assembly (r17) — the fused pack+delta+codec emitter.
//
// The numpy pack pipeline (twtml_tpu/features/batch.py pack_batch /
// pack_ragged_sharded / pack_ragged_group — the byte-identical ground
// truth) touches the wire bytes 3-5 times on the ONE usable host core:
// per-field np.stack + np.ascontiguousarray copies, the offsets→deltas
// pass, the digram-encode pass into a fresh buffer, and the final
// np.concatenate into yet another fresh buffer. This emitter lays the
// FINAL PackedBatch buffer down in one sweep: for every (shard, k)
// segment it memcpys the units (digram-encoding them via the shared LUT
// when the codec applies — reusing wirecodec.cpp's digram_encode, so the
// dictionary has exactly one definition), emits the offsets as uint16
// length deltas under the caller's static row_len gate, and lays the
// numeric/label/mask sideband behind them. k=1 degenerates to the flat
// and per-shard wires, so all three Python packers ride this one entry.
//
// Destination and scratch are CALLER-OWNED (the pooled buffer arena,
// twtml_tpu/features/arena.py): this pass allocates nothing — per-tick
// fresh wire buffers are both CPU churn and fuel for host RSS that grows
// with uploaded bytes.
//
// Layout contract (must stay byte-identical to features/batch.py —
// tests/test_wireassemble.py is the differential):
//   out = [S, K, per-segment], segment (si, ki) at (si*K + ki)*per_seg:
//     units   enc_bucket bytes (codes, zero-padded) | n_sb*unit_size raw
//     offsets bl uint16 deltas | (bl+1) int32 raw
//     numeric bl*4 float32, label bl float32, mask bl float32
//
// Codec decision (mirrors _encode_units_segments/_encode_units_codec):
// all segments encode into scratch; auto mode picks the shared bucket
// max(1024, ceil(max_len/1024)*1024) and falls back to the raw wire when
// the bucket is not strictly smaller than the raw segment; a forced
// bucket (the multi-host cross-agreed value) that under-covers a segment
// is an error, never silent truncation.

#include <cstdint>
#include <cstring>

extern "C" {

// native/wirecodec.cpp — the one greedy digram encoder both wire forms use
int64_t digram_encode(const uint8_t* in, int64_t n, const uint8_t* lut,
                      uint8_t* out, int64_t cap);

// Mirrors features/wirecodec.encoded_bucket: max(1024, round up to 1024).
static int64_t enc_bucket_of(int64_t m) {
  const int64_t kMultiple = 1024;  // wirecodec.CODEC_UNIT_MULTIPLE
  int64_t b = ((m + kMultiple - 1) / kMultiple) * kMultiple;
  return b < kMultiple ? kMultiple : b;
}

// Returns total bytes written, or:
//   -1  destination capacity exceeded (caller sized it wrong)
//   -2  offsets not uint16-delta encodable (negative or > 65535 length)
//   -3  forced codec bucket under-covers a segment encoding
// out_enc_bucket receives the chosen per-segment codec bucket (0 = the
// raw units wire — codec off, or the incompressible fallback).
int64_t wire_assemble(
    const void* const* units_ptrs,   // [k] per-batch units, s*n_sb units
    const int32_t* const* offs_ptrs, // [k] per-batch offsets, s*(bl+1)
    const float* const* num_ptrs,    // [k] numeric, s*bl*4
    const float* const* lab_ptrs,    // [k] label, s*bl
    const float* const* mask_ptrs,   // [k] mask, s*bl
    int64_t k, int64_t s, int64_t n_sb, int64_t bl,
    int64_t unit_size,               // 1 (uint8) or 2 (uint16)
    int64_t narrow_offsets,          // 1 = uint16 deltas, 0 = raw int32
    const uint8_t* lut,              // pair LUT, NULL = codec off
    int64_t forced_bucket,           // > 0: cross-host agreed bucket
    uint8_t* scratch,                // s*k*n_sb bytes iff lut != NULL
    int64_t* enc_lens,               // [s*k] iff lut != NULL
    uint8_t* out, int64_t cap,
    int64_t* out_enc_bucket) {
  int64_t enc_bucket = 0;
  if (lut != nullptr && unit_size == 1) {
    int64_t max_len = 0;
    for (int64_t si = 0; si < s; ++si) {
      for (int64_t ki = 0; ki < k; ++ki) {
        const int64_t seg = si * k + ki;
        const uint8_t* src =
            (const uint8_t*)units_ptrs[ki] + si * n_sb;
        // encode can never exceed its input length (a pair shrinks, a
        // literal copies), so cap = n_sb always fits
        const int64_t m =
            digram_encode(src, n_sb, lut, scratch + seg * n_sb, n_sb);
        enc_lens[seg] = m;
        if (m > max_len) max_len = m;
      }
    }
    if (forced_bucket > 0) {
      if (max_len > forced_bucket) return -3;
      enc_bucket = forced_bucket;
    } else {
      const int64_t b = enc_bucket_of(max_len);
      // not strictly smaller than raw: the raw wire is the smaller wire
      enc_bucket = (b >= n_sb) ? 0 : b;
    }
  }
  const int64_t per_units =
      enc_bucket ? enc_bucket : n_sb * unit_size;
  const int64_t per_offs =
      narrow_offsets ? bl * 2 : (bl + 1) * 4;
  const int64_t per_side = bl * 4 * 4 + bl * 4 + bl * 4;
  const int64_t per_seg = per_units + per_offs + per_side;
  const int64_t total = s * k * per_seg;
  if (total > cap) return -1;
  for (int64_t si = 0; si < s; ++si) {
    for (int64_t ki = 0; ki < k; ++ki) {
      const int64_t seg = si * k + ki;
      uint8_t* p = out + seg * per_seg;
      if (enc_bucket) {
        const int64_t m = enc_lens[seg];
        std::memcpy(p, scratch + seg * n_sb, (size_t)m);
        std::memset(p + m, 0, (size_t)(enc_bucket - m));
      } else {
        std::memcpy(p, (const uint8_t*)units_ptrs[ki] +
                           si * n_sb * unit_size,
                    (size_t)(n_sb * unit_size));
      }
      p += per_units;
      const int32_t* offs = offs_ptrs[ki] + si * (bl + 1);
      if (narrow_offsets) {
        for (int64_t r = 0; r < bl; ++r) {
          const int64_t d = (int64_t)offs[r + 1] - (int64_t)offs[r];
          if (d < 0 || d > 0xFFFF) return -2;
          const uint16_t d16 = (uint16_t)d;
          std::memcpy(p + r * 2, &d16, 2);
        }
      } else {
        std::memcpy(p, offs, (size_t)((bl + 1) * 4));
      }
      p += per_offs;
      std::memcpy(p, num_ptrs[ki] + si * bl * 4, (size_t)(bl * 4 * 4));
      p += bl * 4 * 4;
      std::memcpy(p, lab_ptrs[ki] + si * bl, (size_t)(bl * 4));
      p += bl * 4;
      std::memcpy(p, mask_ptrs[ki] + si * bl, (size_t)(bl * 4));
    }
  }
  *out_enc_bucket = enc_bucket;
  return total;
}

}  // extern "C"
