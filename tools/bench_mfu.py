"""Config #4 int8-plane MFU accounting.

Measures COMPLETION-VERIFIED device time for the shipped 2^18 Gram step and
decomposes it, then states achieved FLOP/s against the chip's published
peaks. Method: the batch is made device-RESIDENT first (one upload), then K
chained dispatches end with ONE scalar fetch; per-step time is the
(K2 − K1) delta so the fixed dispatch/fetch overhead cancels.

Arms (each its own jit program over the same resident operands):
  full_step   — the shipped train step (ragged re-pad + hash + int8 Gram
                + 50-iteration dual loop + write-back)
  counts_i8   — the two-level one-hot densify alone ([B, L]→[B, F] int8)
  gram_i8     — the G = C·Cᵀ s8×s8→s32 matmul alone (resident counts)
  dual_50     — the 50-iteration dual loop alone (resident G)

FLOP model (B rows, L token slots, F = 2^18 — k_hi·k_lo = F exactly):
  counts: 2·B·L·F    gram: 2·B²·F    dual: 50·2·B²    (rest negligible)

Peaks come from one table keyed by jax's ``device_kind`` (TPU v5e: 393
TOP/s int8, 197 TFLOP/s bf16 — Google Cloud documentation, "TPU v5e"); a
device that is not in the table is refused, never divided by someone
else's peak.

Usage: python tools/bench_mfu.py [--batch 2048] [--k 64]
Prints one JSON line.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

F_TEXT = 2**18
# (int8 OP/s, bf16 FLOP/s) per chip, keyed by jax's device_kind — Google
# Cloud documentation, "TPU v5e"
PEAKS = {
    "TPU v5 lite": (393e12, 197e12),
    "TPU v5e": (393e12, 197e12),
}


def _chained_step_time(dispatch, fetch, k1: int = 8, k2: int = 72,
                       budget_s: float = 75.0, min_reps: int = 3,
                       settle: int = 6):
    """Per-iteration seconds via the (k2−k1) chained-dispatch delta, timed
    under the repo's shared stall-riding policy (benchloop.measure_passes:
    reps spread over a time budget, settled when the best stops improving
    — best-of-3 back-to-back reps can land entirely inside one stall
    burst and report a stalled delta as the truth). Returns ``(best_dt, reps, median_over_best)`` — the last is
    the burst-visibility diagnostic (a large ratio = the window was mostly
    stalled)."""
    from twtml_tpu.utils.benchloop import measure_passes

    def run_pass():
        ts = {}
        for k in (k1, k2):
            t0 = time.perf_counter()
            for _ in range(k):
                out = dispatch()
            fetch(out)
            ts[k] = time.perf_counter() - t0
        dt = (ts[k2] - ts[k1]) / (k2 - k1)
        if dt <= 0:
            # a stall burst inside the k1 window makes the delta
            # meaningless (even negative). Substitute the k2 pass's
            # per-step mean — a strict UPPER bound on the true per-step
            # time (it still carries the fixed dispatch/RTT overhead), so
            # a stalled rep can never fake a best.
            dt = ts[k2] / k2
        return dt, None

    best, _, times = measure_passes(
        run_pass, repeats=min_reps, time_budget_s=budget_s,
        settled_after=settle,
    )
    # statistics.median, not sorted(times)[len//2]: the upper-middle pick
    # is biased high on even-length samples
    import statistics

    med = statistics.median(times)
    return best, len(times), round(med / best, 3)


def main(argv=None) -> None:
    args = list(sys.argv[1:] if argv is None else argv)
    batch, k_hi = 2048, 72
    i = 0
    while i < len(args):
        if args[i] == "--batch":
            batch = int(args[i + 1]); i += 2
        elif args[i] == "--k":
            k_hi = int(args[i + 1]); i += 2
        else:
            raise SystemExit(f"unknown flag {args[i]!r}")
    if k_hi <= 8:
        raise SystemExit(
            "--k must exceed the fixed k1=8 (the per-step time is the "
            "(k2-k1) chained delta)"
        )

    import jax
    import jax.numpy as jnp

    from twtml_tpu.utils.backend import device_identity

    device = device_identity()
    if device["kind"] not in PEAKS:
        raise SystemExit(
            f"device_kind {device['kind']!r} (platform "
            f"{device['platform']!r}) is not in the peak table "
            f"{sorted(PEAKS)}: an MFU needs that device's published peaks "
            "— add them with their source"
        )
    int8_peak, bf16_peak = PEAKS[device["kind"]]

    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.models import StreamingLinearRegressionWithSGD
    from twtml_tpu.ops.gram import onehot_counts_int8
    from twtml_tpu.streaming.sources import SyntheticSource

    feat = Featurizer(num_text_features=F_TEXT, now_ms=1785320000000)
    statuses = list(SyntheticSource(total=batch, seed=3).produce())
    unit = feat.featurize_batch_units(
        statuses, row_bucket=batch, pre_filtered=True
    )
    dev_batch = jax.device_put(unit)
    # the hashed token width the one-hot build actually sees (bigrams)
    l_tok = unit.units.shape[1] - 1

    model = StreamingLinearRegressionWithSGD(
        num_text_features=F_TEXT, l2_reg=0.1, gram_int8=True
    )
    num_iter = 50

    # resident token arrays for the sub-programs
    from twtml_tpu.ops.text_hash import hash_bigrams_device

    @jax.jit
    def tokens(b):
        return hash_bigrams_device(b.units, b.length, F_TEXT, jnp.float32)

    tok_idx, tok_val = jax.tree_util.tree_map(
        lambda x: jax.device_put(x), jax.device_get(tokens(dev_batch))
    )
    tok_idx = jnp.asarray(tok_idx, jnp.int32)
    tok_val = jnp.asarray(tok_val, jnp.float32)

    @jax.jit
    def counts_only(idx, val, salt):
        # salt keeps repeated dispatches distinct (no constant folding of
        # identical result reuse); MUST stay int32 — a float salt would
        # silently promote the operands off the integer MXU path
        c = onehot_counts_int8(idx + 0 * salt, val, F_TEXT)
        # abs defeats XLA's sum-of-matmul factorization (sum(C) would
        # reduce the one-hot matmul to a cheap vector rewrite)
        return jnp.sum(jnp.abs(c.astype(jnp.int32)))

    counts = jax.jit(
        # [B, k_hi, k_lo] as built; 2^18 splits exactly, so [B, F] below
        lambda idx, val: onehot_counts_int8(idx, val, F_TEXT).reshape(
            batch, F_TEXT)
    )(tok_idx, tok_val)
    counts = jax.device_put(jax.device_get(counts))

    @jax.jit
    def gram_only(c, salt):
        g = jnp.matmul(
            c + (0 * salt).astype(jnp.int8), c.T,
            preferred_element_type=jnp.int32,
        )
        # abs is load-bearing: plain sum(C·Cᵀ) factorizes to Σ_f colsum²
        # and XLA takes that rewrite (measured "484 TFLOP/s" — above
        # peak — before this guard)
        return jnp.sum(jnp.abs(g))

    g_f32 = jax.jit(
        lambda c: jnp.matmul(
            c, c.T, preferred_element_type=jnp.int32
        ).astype(jnp.float32)
    )(counts)
    g_f32 = jax.device_put(jax.device_get(g_f32))
    u0 = jnp.zeros((batch,), jnp.float32)
    lab = jnp.asarray(unit.label)
    msk = jnp.asarray(unit.mask)

    from twtml_tpu.models.sgd import run_dual_loop

    @jax.jit
    def dual_only(g, salt):
        dual = run_dual_loop(
            u=u0 + salt * 0.0, g=g, labels=lab, mask=msk,
            dtype=jnp.float32,
            residual_fn=lambda raw, label: raw - label,
            num_iterations=num_iter, step_size=0.005,
            mini_batch_fraction=1.0, l2_reg=0.1, convergence_tol=0.001,
            p_prev=jnp.zeros((), jnp.float32),
        )
        return dual["alpha"].sum()

    # ---- warmups (full completion fetch each) -----------------------------
    float(model.step(dev_batch).mse)
    float(counts_only(tok_idx, tok_val, jnp.int32(0)))
    float(gram_only(counts, jnp.int32(0)))
    float(dual_only(g_f32, jnp.float32(0.0)))

    # ---- chained timings --------------------------------------------------
    t_step, n_step, sp_step = _chained_step_time(
        lambda: model.step(dev_batch), lambda o: float(o.mse), k2=k_hi
    )
    salt_box = [0]

    def salted(fn, *operands, flt: bool = False):
        def dispatch():
            salt_box[0] += 1
            salt = (
                jnp.float32(salt_box[0]) if flt else jnp.int32(salt_box[0])
            )
            return fn(*operands, salt)
        return dispatch

    t_counts, n_counts, sp_counts = _chained_step_time(
        salted(counts_only, tok_idx, tok_val), lambda o: float(o), k2=k_hi
    )
    t_gram, n_gram, sp_gram = _chained_step_time(
        salted(gram_only, counts), lambda o: float(o), k2=k_hi
    )
    t_dual, n_dual, sp_dual = _chained_step_time(
        salted(dual_only, g_f32, flt=True), lambda o: float(o), k2=k_hi
    )

    f_counts = 2.0 * batch * l_tok * F_TEXT
    f_gram = 2.0 * batch * batch * F_TEXT
    f_dual = 2.0 * batch * batch * num_iter
    f_total = f_counts + f_gram + f_dual

    def tflops(f, t):
        return round(f / t / 1e12, 2)

    out = {
        "config": "hashing_2e18_l2_mfu",
        "device": device,
        "batch": batch,
        "l_tok": l_tok,
        "flops_per_step_T": round(f_total / 1e12, 3),
        "step_ms": round(t_step * 1e3, 3),
        "counts_ms": round(t_counts * 1e3, 3),
        "gram_ms": round(t_gram * 1e3, 3),
        "dual_ms": round(t_dual * 1e3, 3),
        "achieved_tflops_full_step": tflops(f_total, t_step),
        "mfu_vs_int8_peak": round(f_total / t_step / int8_peak, 3),
        "mfu_vs_bf16_peak": round(f_total / t_step / bf16_peak, 3),
        "gram_tflops": tflops(f_gram, t_gram),
        "gram_mfu_int8": round(f_gram / t_gram / int8_peak, 3),
        "counts_tflops": tflops(f_counts, t_counts),
        "dual_tflops": tflops(f_dual, t_dual),
        # burst visibility: reps taken and median/best per arm — a large
        # ratio means the budget sat mostly in a stalled phase
        "reps": [n_step, n_counts, n_gram, n_dual],
        "median_over_best": [sp_step, sp_counts, sp_gram, sp_dual],
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
