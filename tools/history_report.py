"""Reconstruct a run's long-horizon story from the telemetry historian's
leftovers (telemetry/historian.py) — no live process needed: point it at a
``--history`` segment directory (a SIGKILLed run's included; torn tails are
skipped by the CRC scan, never an error) or at a crash flight-recorder
bundle whose ``history`` tail the blackbox folded in.

Renders the questions fourteen cpu-only windows carried: per-metric
sparkline table (RSS / fetch RTT / per-tick stage cost), healthy/degraded
phase intervals from the persisted classifier transitions, the hours-scale
least-squares RSS slope (the soak gate's estimator over any run's
leftovers), per-phase trend medians, and run-over-run per-stage deltas
against the ``--perfGuard`` baseline stamped at the previous clean
shutdown.

Everything rendered was already ON DISK — this tool adds zero
instrumentation (the ISSUE 20 law: observability at zero added fetches).

Exit status is a CHECK, the sibling contract to tools/postmortem_report.py
and tools/freshness_report.py: 0 = a readable history (segments with at
least one valid record, or a well-formed bundle); 2 = malformed/empty.
``--json`` emits the summary as one machine-readable line.

Usage: python tools/history_report.py HISTORY_DIR_OR_BUNDLE.json [--json]
"""

from __future__ import annotations

import json
import os
import sys

try:  # runnable both as a module and as a script
    from tools.postmortem_report import MalformedBundle, load_bundle
    from twtml_tpu.telemetry import historian as _historian
except ImportError:  # pragma: no cover - script mode from repo root
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    from tools.postmortem_report import MalformedBundle, load_bundle
    from twtml_tpu.telemetry import historian as _historian

SPARK_CHARS = "▁▂▃▄▅▆▇█"
SPARK_WIDTH = 48


def sparkline(values) -> str:
    vals = [float(v) for v in values][-SPARK_WIDTH:]
    if not vals:
        return ""
    lo, hi = min(vals), max(vals)
    span = (hi - lo) or 1.0
    return "".join(
        SPARK_CHARS[int((v - lo) / span * (len(SPARK_CHARS) - 1))]
        for v in vals
    )


def _bundle_records(doc: dict) -> "list[dict]":
    """Synthesize a record stream from a bundle's historian tail (the same
    shape read_series yields, so every derivation below is shared)."""
    hist = doc.get("history") or {}
    records: "list[dict]" = []
    for t_ms, phase in hist.get("transitions", []):
        records.append({"k": "p", "t_ms": int(t_ms), "phase": phase})
    for s in hist.get("samples", []):
        rec = dict(s)
        rec["k"] = "s"
        records.append(rec)
    records.sort(key=lambda r: r.get("t_ms", 0))
    if hist.get("run_id") is not None and records:
        records.insert(0, {
            "k": "r", "t_ms": records[0].get("t_ms", 0),
            "run_id": hist["run_id"],
            "fingerprint": hist.get("fingerprint", ""),
        })
    return records


def _load_baseline(path: "str | None") -> "dict | None":
    if not path:
        return None
    try:
        with open(path, encoding="utf-8") as fh:
            doc = json.load(fh)
        if isinstance(doc, dict) and isinstance(doc.get("stages_ms"), dict):
            return doc
    except Exception:
        pass
    return None


def summarize(records: "list[dict]",
              baseline: "dict | None" = None) -> dict:
    samples = [r for r in records if r.get("k") == "s"]
    runs = [
        {"run_id": r.get("run_id"), "fingerprint": r.get("fingerprint", ""),
         "t_ms": r.get("t_ms")}
        for r in records if r.get("k") == "r"
    ]
    trends = _historian.phase_trends(records)
    healthy = trends.get("healthy", {}).get("stages_ms", {})
    deltas = {}
    if baseline:
        for stage, base_ms in sorted(baseline.get("stages_ms", {}).items()):
            cur = healthy.get(stage)
            if cur is None or base_ms <= 0:
                continue
            deltas[stage] = {
                "baseline_ms": base_ms,
                "current_ms": cur,
                "ratio": round(cur / base_ms, 3),
            }
    span_ms = (
        samples[-1]["t_ms"] - samples[0]["t_ms"] if len(samples) > 1 else 0
    )
    return {
        "records": len(records),
        "samples": len(samples),
        "span_minutes": round(span_ms / 60000.0, 2),
        "runs": runs,
        "phase_intervals": _historian.phase_intervals(records),
        "rss_slope_mb_per_min": round(_historian.rss_slope(records), 4),
        "trends": trends,
        "series": {
            "rss_mb": [s.get("rss_mb", 0.0) for s in samples],
            "rtt_ms": [s.get("rtt_ms", 0.0) for s in samples],
            "stage_ms": [
                round(sum(s.get("stages_ms", {}).values()), 2)
                for s in samples
            ],
        },
        "baseline": baseline,
        "baseline_deltas": deltas,
    }


def render(s: dict) -> str:
    out = [
        f"telemetry history — {s['samples']} sample(s) over "
        f"{s['span_minutes']:.1f} min ({s['records']} records)"
    ]
    for run in s["runs"]:
        out.append(
            f"  run {run['run_id']}  config {run['fingerprint'] or '?'}"
        )
    out.append("  series (oldest → newest):")
    for name, unit in (
        ("rss_mb", "MB"), ("rtt_ms", "ms"), ("stage_ms", "ms/tick")
    ):
        vals = s["series"][name]
        last = f"{vals[-1]:.1f} {unit}" if vals else "—"
        out.append(f"    {name:<10} {sparkline(vals):<{SPARK_WIDTH}} {last}")
    out.append(
        f"  host RSS slope (least squares): "
        f"{s['rss_slope_mb_per_min']:.3f} MB/min"
    )
    if s["phase_intervals"]:
        out.append("  fetch health phases:")
        for iv in s["phase_intervals"]:
            mins = (iv["end_ms"] - iv["start_ms"]) / 60000.0
            out.append(
                f"    {iv['phase']:<9} {mins:7.1f} min  "
                f"{iv['samples']:>5} sample(s)"
            )
    for phase, t in sorted(s["trends"].items()):
        out.append(
            f"  {phase} medians: rtt {t['rtt_ms']:.1f} ms  "
            f"rss {t['rss_mb']:.0f} MB  rows/s {t['rows_per_s']:.0f}"
        )
        for stage, ms in t["stages_ms"].items():
            out.append(f"    {stage:<14} {ms:>9.3f} ms/tick")
    if s["baseline"]:
        out.append(
            f"  perfGuard baseline: run {s['baseline'].get('run_id', '?')} "
            f"({s['baseline'].get('samples', 0)} healthy samples)"
        )
        for stage, d in s["baseline_deltas"].items():
            flag = "  <-- regressed" if d["ratio"] > 1.5 else ""
            out.append(
                f"    {stage:<14} {d['baseline_ms']:>9.3f} -> "
                f"{d['current_ms']:>9.3f} ms/tick  "
                f"({d['ratio']:.2f}x){flag}"
            )
    return "\n".join(out)


def main(argv=None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    as_json = "--json" in args
    args = [a for a in args if a != "--json"]
    if len(args) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    target = args[0]
    baseline = None
    if os.path.isdir(target):
        records = _historian.read_series(target)
        if not records:
            print(
                f"history_report: no CRC-valid historian records in "
                f"{target}", file=sys.stderr,
            )
            return 2
        baseline = _load_baseline(
            os.path.join(target, _historian.BASELINE_NAME)
        )
    else:
        try:
            doc = load_bundle(target)
        except (OSError, MalformedBundle) as exc:
            print(f"history_report: malformed bundle: {exc}",
                  file=sys.stderr)
            return 2
        records = _bundle_records(doc)
        if not records:
            print(
                "history_report: bundle has no historian tail (the run "
                "predates the historian or ran with --history off)",
                file=sys.stderr,
            )
            return 2
        hist = doc.get("history") or {}
        baseline = hist.get("baseline")
    summary = summarize(records, baseline)
    if as_json:
        print(json.dumps(summary))
    else:
        print(render(summary))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
