"""Endurance soak: alternate the flagship configs back-to-back on the chip
and assert numeric bit-stability.

Each round runs, on the SAME process/models: the dense ragged-wire pipeline
at the r4 headline operating point (batch 16384) and the 2^18 int8-Gram
config at its r4 operating point (batch 3072, ragged). Every pass resets
weights and streams the identical corpus, so the final-batch mse must be
BIT-IDENTICAL on every pass — any drift, leak-induced slowdown, or
transport wedge fails loudly.

r17 additions (ISSUE 14):

- the RSS **slope** (least-squares MB/min over per-pass samples of the
  live VmRSS) joins the JSON line, and ``--maxRssSlopeMbPerMin X`` turns
  the soak into a CI/ops GATE: exit 1 when the slope breaches X — RSS
  flatness becomes assertable instead of eyeballed.
- ``--arena <on|off>`` toggles the pooled wire-buffer arena
  (features/arena.py): the soak retires each pass's pack leases at the
  pass's completion fetch (every dispatch has provably executed by then),
  so arena-on reuses the same destination buffers pass over pass while
  arena-off is the pre-r17 fresh-allocation control arm. The two slopes,
  recorded side by side, are the arena's RSS evidence.

r22 addition (ISSUE 20): the soak feeds the telemetry historian — one
``historian.sample()`` per pass into ``--historyDir`` (default
``soak_history/`` in the repo root; ``--historyDir off`` disables). The
segments are the soak's durable black box: a SIGKILLed soak leaves CRC-
valid frames behind, and ``tools/history_report.py soak_history/``
reconstructs the RSS slope and health-phase intervals from the leftovers
alone. The JSON line reports the segment-derived slope next to the
in-process one — the two estimators must agree, which is the historian's
own correctness check.

Usage: python tools/soak.py [--minutes M] [--tweets N]
       [--arena on|off] [--wireAssemble auto|on|off]
       [--maxRssSlopeMbPerMin X] [--configs both|dense|hash2e18]
       [--historyDir DIR|off]
Prints one JSON line at the end (exit 1 on a slope breach).

``--configs dense`` keeps only the dense ragged arm — the wire-heavy
config whose uploaded bytes would drive a transfer-buffer retention, and
the one a cpu-only control window can actually cycle (the 2^18 Gram step
is minutes per pass on a CPU host).
"""

from __future__ import annotations

import json
import os
import resource
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _slope_mb_per_min(samples: "list[tuple[float, float]]") -> float:
    """Least-squares RSS slope over (seconds, MB) samples — robust to the
    sawtooth a GC'd process shows, unlike endpoint deltas. Shared with the
    live ``host.rss_slope_mb_per_min`` gauge (utils/rss.py) so the soak
    report and the dashboard agree on the math."""
    from twtml_tpu.utils.rss import slope_mb_per_min

    return slope_mb_per_min(samples)


def _run_once(model, featurize, chunks) -> float:
    """One pass: featurize chunk k+1 on a host thread while the device runs
    chunk k, dispatch freely, and close with ONE real fetch — the weights
    chain through every step, so the last step's mse cannot arrive before
    the whole pass has run. Returns that mse."""
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=1) as pool:
        pending = pool.submit(featurize, chunks[0])
        for nxt in chunks[1:]:
            batch = pending.result()
            pending = pool.submit(featurize, nxt)
            model.step(batch)
        last = model.step(pending.result())
    return float(last.mse)


def main(argv=None) -> None:
    args = list(sys.argv[1:] if argv is None else argv)
    minutes, n_tweets = 15.0, 65536
    arena_on, assemble_mode = True, "auto"
    max_slope = None
    configs = "both"
    history_dir = os.path.join(REPO, "soak_history")
    i = 0
    while i < len(args):
        if args[i] == "--minutes":
            minutes = float(args[i + 1]); i += 2
        elif args[i] == "--tweets":
            n_tweets = int(args[i + 1]); i += 2
        elif args[i] == "--arena":
            arena_on = args[i + 1] == "on"; i += 2
        elif args[i] == "--wireAssemble":
            assemble_mode = args[i + 1]; i += 2
        elif args[i] == "--maxRssSlopeMbPerMin":
            max_slope = float(args[i + 1]); i += 2
        elif args[i] == "--configs":
            configs = args[i + 1]; i += 2
        elif args[i] == "--historyDir":
            history_dir = None if args[i + 1] == "off" else args[i + 1]
            i += 2
        else:
            raise SystemExit(f"unknown flag {args[i]!r}")

    import jax

    from twtml_tpu.features import arena as _arena, assemble as _assemble
    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.models import StreamingLinearRegressionWithSGD
    from twtml_tpu.streaming.sources import SyntheticSource
    from twtml_tpu.utils.rss import rss_mb

    _assemble.configure(assemble_mode)
    _arena.set_enabled(arena_on)

    # durable long-horizon record (ISSUE 20): one historian sample per
    # pass; the segments survive a SIGKILL and history_report reconstructs
    # phase intervals + RSS slope from the leftovers alone
    from twtml_tpu.telemetry import historian as _historian
    from twtml_tpu.utils.runid import config_fingerprint, next_run_id

    if history_dir:
        _historian.configure(
            history_dir, max_mb=64,
            run_id=next_run_id(),
            fingerprint=config_fingerprint({
                "tool": "soak", "tweets": n_tweets, "configs": configs,
                "arena": arena_on, "wire_assemble": assemble_mode,
            }),
        )

    statuses = list(SyntheticSource(total=n_tweets, seed=3).produce())
    # per-pass pack leases, retired at the pass's completion fetch (every
    # dispatch has executed by then — the arena's retire-on-delivery rule)
    pass_leases: list = []

    def arm(f_text, batch, l2):
        feat = Featurizer(num_text_features=f_text, now_ms=1785320000000)
        chunks = [
            statuses[i : i + batch] for i in range(0, len(statuses), batch)
        ]

        def fz(c):
            pb = feat.featurize_batch_ragged(
                c, row_bucket=batch, pre_filtered=True, pack=True
            )
            lease = getattr(pb, "_lease", None)
            if lease is not None:
                pass_leases.append(lease)
            return pb

        model = StreamingLinearRegressionWithSGD(
            num_text_features=f_text, l2_reg=l2
        )
        float(model.step(fz(chunks[0])).mse)  # warm
        return model, fz, chunks

    arms = {}
    if configs in ("both", "dense"):
        arms["dense_ragged_b16384"] = arm(1000, 16384, 0.0)
    if configs in ("both", "hash2e18"):
        arms["hash2e18_ragged_b3072"] = arm(2**18, 3072, 0.1)
    if not arms:
        raise SystemExit(f"unknown --configs {configs!r}")
    from twtml_tpu.utils.rss import RssWatchdog

    reference_mse: dict[str, float] = {}
    passes = {k: 0 for k in arms}
    rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    # the same guard the app loops run (utils/rss.py): sample every pass,
    # warn with the diagnosis + checkpoint-restart workaround
    # as growth crosses each threshold — the soak records whether it fired
    watchdog = RssWatchdog(sample_every=1)
    t_start = time.perf_counter()
    t_end = t_start + minutes * 60
    rss_samples: "list[tuple[float, float]]" = [(0.0, rss_mb())]
    while time.perf_counter() < t_end:
        for name, (model, fz, chunks) in arms.items():
            model.reset()
            pass_leases.clear()
            mse = _run_once(model, fz, chunks)
            # completion fetch done ⇒ every dispatch consumed its wire:
            # the pass's leases retire to the pool (arena-on) or no-op
            for lease in pass_leases:
                lease.retire()
            pass_leases.clear()
            if name not in reference_mse:
                reference_mse[name] = mse
            elif mse != reference_mse[name]:
                raise SystemExit(
                    f"NUMERIC DRIFT in {name} pass {passes[name]}: "
                    f"{mse} != {reference_mse[name]}"
                )
            passes[name] += 1
            watchdog.tick()
            rss_samples.append(
                (time.perf_counter() - t_start, rss_mb())
            )
            _historian.sample()  # no-op when --historyDir off
    rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    slope = round(_slope_mb_per_min(rss_samples), 3)
    breach = max_slope is not None and slope > max_slope
    # segment-derived slope: re-read what actually hit disk and run the
    # same estimator over it — the historian's own durability check (a
    # disagreement means samples were lost or mis-framed)
    history_slope = None
    if history_dir:
        _historian.stamp_baseline()  # clean soak end → next run gets deltas
        _historian.uninstall()
        history_slope = round(
            _historian.rss_slope(_historian.read_series(history_dir)), 3
        )
    from twtml_tpu.features.arena import get_arena

    print(json.dumps({
        "soak_minutes": minutes,
        "tweets_per_pass": n_tweets,
        "passes": passes,
        "tweets_total": sum(passes.values()) * n_tweets,
        "final_mse": reference_mse,
        "bit_identical": True,
        "rss_growth_mb": round((rss1 - rss0) / 1024, 1),
        "rss_slope_mb_per_min": slope,
        "rss_slope_gate_mb_per_min": max_slope,
        "rss_slope_breach": breach,
        "rss_samples": len(rss_samples),
        "arena": "on" if arena_on else "off",
        "wire_assemble": assemble_mode,
        "arena_stats": get_arena().stats(),
        "rss_watchdog_warnings": watchdog.warn_count,
        "history_dir": history_dir,
        "history_rss_slope_mb_per_min": history_slope,
        "backend": jax.default_backend(),
    }))
    if breach:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
