"""Headline benchmark: streaming tweets/sec ingested+trained.

Measures the full pipeline (host featurization → ragged units wire → fused
re-pad+hash+predict+stats+train device step) on the TPU, against the
BASELINE.md metric "tweets/sec ingested+trained". The reference publishes no
numbers (BASELINE.json ``published: {}``), so the baseline is measured in the
same process family: the identical pipeline forced onto the CPU backend in a
subprocess (the moral equivalent of the reference's ``local[8]`` operating
point on this host).

Prints ONE JSON line:
  {"metric": "tweets_per_sec_e2e", "value": N, "unit": "tweets/s",
   "device": {"platform": "tpu", "kind": "...", "count": N},
   "vs_baseline": N / cpu_tweets_per_sec,
   "passes": P, "best": N, "median": M}

No chip, no number: the device child asks for ``--backend tpu`` and requires
the native fast path to be live; when it fails, this script exits non-zero
and prints no metric line — a CPU rate (or a zero) is never written under
``tweets_per_sec_e2e``.

Measurement policy: every timed pass ends with a real host fetch of the
last step's mse — the weights chain through every step, so that one
data-dependent scalar closes the window over actual completion of the whole
pass (utils/benchloop.py). The shape of the measurement (600 s budget,
``vs_baseline``, the modeled-latency children) is ROADMAP S1's to replace.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

N_TWEETS = 524288  # 32 batches/pass: the ONE completion fetch closing each
# pass is measurement cost, not pipeline cost (production streaming never
# syncs); a longer pass amortizes it toward steady-state streaming
# operating point from the last batch-size sweep on record
# (tools/bench_batchsize.py): per-batch fixed costs amortize up to b16384.
# ROADMAP S3 re-settles it on the chip the ledger runs on.
BATCH = 16384
WARMUP_BATCHES = 2
# best-of over a FIXED time budget, no early settle: a settle check
# "converges" on whatever fetch-latency phase it lands in, so the headline
# keeps adding passes for the whole budget and the median in the output
# exposes a run that sat in a slow phase. Watchdog margin: 600 s + compile
# stays well under the 1200 s per-child TWTML_BENCH_TIMEOUT.
REPEATS = 6
TIME_BUDGET_S = 600.0
SETTLED_AFTER = 0


def measure(
    n_tweets: int = N_TWEETS,
    batch_size: int = BATCH,
    repeats: int = REPEATS,
    time_budget_s: float | None = TIME_BUDGET_S,
    settled_after: int = SETTLED_AFTER,
    tenants: int | None = None,
    backend: str = "tpu",
) -> dict:
    from twtml_tpu.apps.common import select_backend
    from twtml_tpu.config import ConfArguments
    from twtml_tpu.features import native
    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.models import StreamingLinearRegressionWithSGD
    from twtml_tpu.streaming.sources import SyntheticSource

    # the same gate every entry point passes (and the shared compile
    # cache): ``tpu`` fails unless jax's first device is a TPU
    device = select_backend(ConfArguments().parse(["--backend", backend]))
    # a host-bound rate taken on the Python fallback is a tenth of the real
    # one with nothing said: fail instead
    native.require_live()
    statuses = list(SyntheticSource(total=n_tweets, seed=3).produce())
    feat = Featurizer(now_ms=1785320000000)
    # TWTML_BENCH_TENANTS > 1 runs the headline pipeline through the
    # multi-tenant model plane (M models, one program, one fetch —
    # parallel/tenants.py); the tenant count rides the JSON record so a
    # multi-tenant headline number is never mistaken for the M=1 one
    tenants = (
        int(os.environ.get("TWTML_BENCH_TENANTS", "1") or 1)
        if tenants is None else tenants
    )
    if tenants > 1:
        from twtml_tpu.parallel import TenantStackModel

        model = TenantStackModel(tenants)
    else:
        model = StreamingLinearRegressionWithSGD()

    from twtml_tpu.utils.benchloop import measure_pipeline

    chunks = [statuses[i : i + batch_size] for i in range(0, n_tweets, batch_size)]

    def featurize(chunk):
        # ragged device wire: the host encodes raw code units and ships
        # them CONCATENATED (no per-row pad bytes), PACKED into one buffer;
        # the fused device step re-pads by lane rows and hashes bigrams
        # in-program. Bit-identical features (tests/test_ragged_wire.py,
        # test_device_hash.py). The tenant plane builds its own routed wire
        # at the model boundary (TenantStackModel.prepare_wire); the
        # single-model path keeps the k=1 packed wire
        return feat.featurize_batch_ragged(
            chunk, row_bucket=batch_size, pre_filtered=True,
            pack=(tenants == 1),
        )

    out = measure_pipeline(
        model, featurize, chunks, warmup_steps=WARMUP_BATCHES, repeats=repeats,
        time_budget_s=time_budget_s, settled_after=settled_after,
    )
    del out["batches"]
    out["tenants"] = tenants
    out["device"] = device
    return out


def _run_child(kind: str, timeout: float) -> tuple[dict | None, str]:
    """Run one measurement in a subprocess (clean backend state; a hung
    device can be timed out instead of hanging the bench). Returns (record,
    failure detail) — record None on any failure, with the detail
    distinguishing a timeout from a crash (stderr tail included).

    One process per chip: this parent never imports jax (checked:
    ``twtml_tpu.utils.runid`` leaves ``jax`` out of ``sys.modules``), and
    ``subprocess.run`` returns before the next child starts, so the children
    hold the chip strictly one after another. Keep both properties — a
    parent that touches jax holds the chip, and a child that needs it then
    fails or hangs."""
    proc = None
    try:
        env = dict(os.environ, TWTML_BENCH_CHILD=kind)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env, capture_output=True, text=True, timeout=timeout,
        )
        if proc.returncode != 0:
            return None, (
                f"exit {proc.returncode}: "
                + (proc.stderr or proc.stdout).strip()[-400:]
            )
        return json.loads(proc.stdout.strip().splitlines()[-1]), ""
    except subprocess.TimeoutExpired:
        return None, f"timeout after {timeout:.0f}s (device unreachable?)"
    except (ValueError, IndexError) as exc:
        detail = (proc.stderr or proc.stdout).strip()[-400:] if proc else ""
        return None, detail or repr(exc)


def main() -> None:
    child = os.environ.get("TWTML_BENCH_CHILD")
    if child == "cpu":
        # two plain passes suffice on the host backend. The CPU sample keeps
        # its own b2048 operating point: padding a 4096-tweet sample to a
        # 16384-row bucket would 4x the CPU work and artificially inflate
        # vs_baseline.
        print(json.dumps(
            measure(
                n_tweets=4096, batch_size=2048, repeats=2, time_budget_s=None,
                backend="cpu",
            )
        ))
        return
    if child == "device":
        print(json.dumps(measure()))
        return
    if child == "wire":
        # compact compressed-wire record (ISSUE 12): digram codec off/on,
        # paired, object ingest, with the modeled upload-bound transport
        # control — tools/bench_wirecodec.py is the full harness (both
        # ingest regimes + the coalesced group-wire arms)
        from tools.bench_wirecodec import measure as wire_measure

        rec = wire_measure(
            regime="object", n_tweets=32768, batch=4096, k=4, budget_s=25.0
        )
        modeled = rec["modeled_upload"]
        print(json.dumps({
            "wire_ratio": modeled["wire_ratio_single"],
            "units_ratio": modeled["units_ratio"],
            "paired_codec_cpu_control": (
                rec["control"]["paired_single_codec_vs_raw"]
            ),
            "paired_codec_upload_bound": {
                mbs: arms["single_codec_vs_raw"]
                for mbs, arms in modeled["paired_upload_bound"].items()
            },
            "backend": rec["backend"],
        }))
        return
    if child == "serving":
        # compact serving-plane record (ISSUE 9): coalesced + depth-8
        # pipelined vs naive per-request under the 70 ms modeled-fetch-
        # latency control — the mechanism number; tools/bench_serving.py is
        # the full paired harness (--modelRttMs 0 drops the modeled latency)
        from tools.bench_serving import measure as serving_measure

        rec = serving_measure(
            requests=64, rows_per_request=16, batch_rows=256, depth=8,
            budget=25.0, model_rtt_ms=70.0,
        )
        print(json.dumps({
            "qps_pipelined_rtt70": rec["pipelined_rtt"]["qps_median"],
            "qps_naive_rtt70": rec["naive_rtt"]["qps_median"],
            "p99_ms_rtt70": rec["pipelined_rtt"]["p99_ms"],
            "paired_speedup_rtt70": (
                rec["pipelined_rtt"]["paired_speedup_vs_naive"]
            ),
            "paired_speedup_cpu_control": (
                rec["pipelined"]["paired_speedup_vs_naive"]
            ),
            "backend": rec["backend"],
        }))
        return

    # device measurement with a watchdog (TWTML_BENCH_TIMEOUT seconds): a
    # healthy run ≈ compile + a pass loop that spends TIME_BUDGET_S (600 s).
    # No chip (or no native fast path) is a FAILURE, not a CPU number under
    # a device metric's name (on-chip-measurement guide §2).
    timeout = float(os.environ.get("TWTML_BENCH_TIMEOUT", "1200"))
    device_result, device_err = _run_child("device", timeout)
    if device_result is None:
        print(f"device measurement failed: {device_err}", file=sys.stderr)
        raise SystemExit(1)
    cpu_result, _ = _run_child("cpu", timeout)
    cpu_rate = cpu_result["tweets_per_sec"] if cpu_result else None
    # serving-plane record (ISSUE 9; TWTML_BENCH_SERVING=0 skips): a short
    # paired child — ~1 minute against the headline's 600 s budget — so the
    # one JSON line also answers "what does the read path sustain?"
    serving_result = None
    if os.environ.get("TWTML_BENCH_SERVING", "1") != "0":
        serving_result, serving_err = _run_child("serving", 300.0)
        if serving_result is None:
            serving_result = {"error": serving_err}
    # compressed-wire record (ISSUE 12; TWTML_BENCH_WIRE=0 skips): a short
    # paired child — codec off/on in the object-ingest regime under the
    # modeled upload-bound control (tools/bench_wirecodec.py)
    wire_result = None
    if os.environ.get("TWTML_BENCH_WIRE", "1") != "0":
        wire_result, wire_err = _run_child("wire", 300.0)
        if wire_result is None:
            wire_result = {"error": wire_err}

    value = device_result["tweets_per_sec"]
    record = {
        "metric": "tweets_per_sec_e2e",
        "value": round(value, 1),
        "unit": "tweets/s",
        # the device the number was taken on, as jax reports it
        "device": device_result["device"],
        "vs_baseline": round(value / cpu_rate, 2) if cpu_rate else None,
        # vs_baseline compares OPERATING POINTS, not just backends: the
        # device arm runs its b16384 point, the CPU arm its own b2048 point
        # (padding the CPU sample 8x would understate it). The multiplier
        # is end-to-end pipeline vs pipeline; it is not a same-batch
        # backend ratio.
        "vs_baseline_basis": "device b16384 vs cpu b2048 (per-backend operating points)",
        # self-explaining round-over-round numbers: how many passes ran
        # and where the distribution sits (best == value's basis)
        "passes": device_result.get("passes"),
        "best": round(value, 1),
        "median": round(
            device_result.get("median_tweets_per_sec", value), 1
        ),
        # fetch-latency phase counts over the pass loop (the rolling
        # completion-fetch classifier, telemetry/metrics.py): how many
        # passes sat in a healthy vs degraded window, and how often the
        # phase flipped — so a degraded-budget run explains its own median
        "health": device_result.get("health"),
        # active tenant count of the measured pipeline (the multi-
        # tenant model plane, TWTML_BENCH_TENANTS; 1 = the headline
        # single-model configuration)
        "tenants": device_result.get("tenants", 1),
    }
    if serving_result is not None:
        # the serving plane's sustained read-path record (see the "serving"
        # child above; full paired harness: tools/bench_serving.py)
        record["serving"] = serving_result
    if wire_result is not None:
        # the compressed-wire record (see the "wire" child above; full
        # paired harness: tools/bench_wirecodec.py)
        record["wire"] = wire_result
    # run provenance (ISSUE 20): the monotonic per-host run id and the
    # operating-point fingerprint join this line to the telemetry
    # historian's segments
    from twtml_tpu.utils.runid import config_fingerprint, next_run_id

    record["run_id"] = next_run_id()
    record["config_fingerprint"] = config_fingerprint({
        "bench": "headline", "n_tweets": N_TWEETS, "batch": BATCH,
        "time_budget_s": TIME_BUDGET_S,
        "tenants": os.environ.get("TWTML_BENCH_TENANTS", "1"),
    })
    print(json.dumps(record))


if __name__ == "__main__":
    main()
