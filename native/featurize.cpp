// One-pass host featurize (r18) — the fused numeric+label+mask+wire
// emitter behind --featurizeNative.
//
// Between the native parse (PR 6) and the native pack (PR 14), the
// featurize stage still ran several separate numpy passes (float64 scale + f32 cast,
// label/mask fills, the ragged-wire zero+copy) plus — on object ingest —
// four per-tweet Python traversals. This entry collapses the array half
// of that stage into ONE C sweep: given the batch's encoded units +
// offsets and its numeric columns (float64 straight from the Python
// Status traversal, or int64 straight from the block parser), it emits
// the final ragged-wire arrays — flat units buffer (narrow uint8 when
// every row is ASCII), padded int32 offsets, scaled float32
// numeric/label/mask — into CALLER-OWNED destinations (one pooled arena
// lease, twtml_tpu/features/arena.py; this pass allocates nothing).
//
// Parity law (twtml_tpu/features/featurizer.py is the ground truth;
// tests/test_featurize_native.py is the differential):
//   numeric[:, 0..2] = (float)((double)col * 1e-12)
//   numeric[:, 3]    = (float)(((double)now_ms - (double)created) * 1e-14)
//   label            = (float)(double)label_col   (Python may overwrite
//                      label[:n] afterwards for label_fn variants)
//   units/offsets    = features/batch.ragged_wire_arrays, byte for byte
// float64-multiply-then-f32-cast matches numpy's astype(float64) * scale
// stored into a float32 array exactly (same IEEE ops, same order).
// int64→double conversion is the same correctly-rounded conversion
// numpy's astype performs. col_order maps the two callers' column
// layouts onto one loop, so the scaling code exists exactly once.

#include <cstdint>
#include <cstring>

namespace {

// hand-scaling constants of the reference (MllibHelper.scala:64-67),
// duplicated from featurizer.py COUNT_SCALE/AGE_SCALE — a differential
// test pins the two definitions together.
constexpr double kCountScale = 1e-12;
constexpr double kAgeScale = 1e-14;

}  // namespace

extern "C" {

// Returns the maximum row length seen (>= 0) for the caller's row_len
// bucket policy, or -1 when offsets overrun n_bucket (caller sized the
// destination from these offsets; never expected — the caller falls back
// to the numpy ground truth, which cannot hit it).
//
//   units:       source code units (unit_size bytes each; uint16 from the
//                object path's UTF-16 encode, uint8|uint16 from blocks)
//   offsets:     [n+1] int64 row offsets into units
//   cols_f64 /   exactly one non-NULL: [n, 5] numeric columns (float64
//   cols_i64     from the Status traversal / int64 from the block parser)
//   col_order:   [5] source-column indices of followers, favourites,
//                friends, created_ms, label
//   n:           kept rows;  b: padded rows;  n_bucket: flat units
//                capacity (RAGGED_UNIT_MULTIPLE-rounded)
//   narrow:      1 = emit uint8 units (every row ASCII — metadata-gated
//                by the caller, never sniffed), 0 = emit uint16
//   out_units:   [n_bucket] uint8|uint16 — zero-padded past the total
//   out_offsets: [b+1] int32 — rows past n hold the total (length 0)
//   out_numeric: [b, 4] float32;  out_label/out_mask: [b] float32 —
//                all fully written (the lease buffer arrives dirty)
int64_t featurize_wire(
    const void* units, int64_t unit_size,
    const int64_t* offsets,
    const double* cols_f64, const int64_t* cols_i64,
    const int64_t* col_order,
    int64_t n, int64_t b, int64_t n_bucket,
    int64_t now_ms, int64_t narrow,
    void* out_units, int32_t* out_offsets,
    float* out_numeric, float* out_label, float* out_mask) {
  const int64_t total = n ? offsets[n] : 0;
  if (total > n_bucket || total < 0) return -1;

  // -- units: one copy (narrowing or widening folded in), zeroed tail ---
  if (narrow) {
    uint8_t* out8 = static_cast<uint8_t*>(out_units);
    if (unit_size == 1) {
      std::memcpy(out8, units, static_cast<size_t>(total));
    } else {
      const uint16_t* in16 = static_cast<const uint16_t*>(units);
      for (int64_t i = 0; i < total; ++i)
        out8[i] = static_cast<uint8_t>(in16[i]);  // values < 128 by gate
    }
    std::memset(out8 + total, 0, static_cast<size_t>(n_bucket - total));
  } else {
    uint16_t* out16 = static_cast<uint16_t*>(out_units);
    if (unit_size == 2) {
      std::memcpy(out16, units, static_cast<size_t>(total) * 2);
    } else {
      const uint8_t* in8 = static_cast<const uint8_t*>(units);
      for (int64_t i = 0; i < total; ++i) out16[i] = in8[i];
    }
    std::memset(out16 + total, 0,
                static_cast<size_t>(n_bucket - total) * 2);
  }

  // -- offsets: [b+1] int32, pad rows pinned at total (length 0) --------
  int64_t max_len = 0;
  for (int64_t i = 0; i < n; ++i) {
    out_offsets[i] = static_cast<int32_t>(offsets[i]);
    const int64_t len = offsets[i + 1] - offsets[i];
    if (len > max_len) max_len = len;
  }
  const int32_t total32 = static_cast<int32_t>(total);
  for (int64_t i = n; i <= b; ++i) out_offsets[i] = total32;

  // -- scaled numeric + label + mask, one pass over the columns ---------
  const int64_t cf = col_order[0], cv = col_order[1], cr = col_order[2],
                cc = col_order[3], cl = col_order[4];
  const double now = static_cast<double>(now_ms);
  for (int64_t i = 0; i < n; ++i) {
    double followers, favourites, friends, created, labelv;
    if (cols_f64 != nullptr) {
      const double* row = cols_f64 + i * 5;
      followers = row[cf]; favourites = row[cv]; friends = row[cr];
      created = row[cc]; labelv = row[cl];
    } else {
      const int64_t* row = cols_i64 + i * 5;
      followers = static_cast<double>(row[cf]);
      favourites = static_cast<double>(row[cv]);
      friends = static_cast<double>(row[cr]);
      created = static_cast<double>(row[cc]);
      labelv = static_cast<double>(row[cl]);
    }
    float* num = out_numeric + i * 4;
    num[0] = static_cast<float>(followers * kCountScale);
    num[1] = static_cast<float>(favourites * kCountScale);
    num[2] = static_cast<float>(friends * kCountScale);
    num[3] = static_cast<float>((now - created) * kAgeScale);
    out_label[i] = static_cast<float>(labelv);
    out_mask[i] = 1.0f;
  }
  if (b > n) {
    std::memset(out_numeric + n * 4, 0,
                static_cast<size_t>(b - n) * 4 * sizeof(float));
    std::memset(out_label + n, 0,
                static_cast<size_t>(b - n) * sizeof(float));
    std::memset(out_mask + n, 0,
                static_cast<size_t>(b - n) * sizeof(float));
  }
  return max_len;
}

}  // extern "C"
