"""Render a crash flight-recorder bundle (telemetry/blackbox.py) into the
post-mortem summary an on-call engineer wants first: why the run died, what
the guards saw on the way down, which host was gating, and where the last
verified checkpoint is.

Exit status is a CHECK, exactly like tools/trace_report.py: 0 = a
well-formed bundle; 2 = malformed (missing required keys, unparseable JSON,
wrong kind). CI's post-mortem smoke step and tools/chaos_soak.py gate on
it. ``--json`` re-emits the validated summary as one machine-readable line.

Usage: python tools/postmortem_report.py BUNDLE.json [--json] [--events N]
"""

from __future__ import annotations

import json
import sys
from collections import Counter

try:  # runnable both as a module and as a script
    from twtml_tpu.telemetry.blackbox import BUNDLE_KIND, REQUIRED_KEYS
except ImportError:  # pragma: no cover - script mode from repo root
    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from twtml_tpu.telemetry.blackbox import BUNDLE_KIND, REQUIRED_KEYS


class MalformedBundle(ValueError):
    pass


def load_bundle(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        text = fh.read()
    if not text.strip():
        raise MalformedBundle("empty bundle file")
    try:
        doc = json.loads(text)
    except json.JSONDecodeError as exc:
        raise MalformedBundle(f"not JSON: {exc}") from exc
    if not isinstance(doc, dict):
        raise MalformedBundle("bundle is not a JSON object")
    missing = [k for k in REQUIRED_KEYS if k not in doc]
    if missing:
        raise MalformedBundle(f"missing required keys: {missing}")
    if doc.get("kind") != BUNDLE_KIND:
        raise MalformedBundle(f"not a {BUNDLE_KIND} bundle: {doc.get('kind')!r}")
    if not isinstance(doc["events"], list):
        raise MalformedBundle("events is not a list")
    return doc


def summarize(doc: dict, tail_events: int = 12) -> dict:
    events = doc["events"]
    kinds = Counter(e.get("kind", "?") for e in events if isinstance(e, dict))
    counters = (doc.get("metrics") or {}).get("counters", {})
    guard_counters = {
        k: v for k, v in counters.items()
        if k.startswith((
            "fetch.retries", "fetch.aborts", "model.rollbacks",
            "model.sentinel_aborts", "lockstep.", "chaos.injected",
            "ingest.rows_shed", "trace.dropped_events",
        ))
    }
    hosts = doc.get("hosts") or {}
    history = doc.get("history") or {}
    hist_samples = history.get("samples") or []
    return {
        "reason": doc["reason"],
        "time_unix": doc["time_unix"],
        "process_index": doc.get("process_index", 0),
        "app": (doc.get("config") or {}).get("_appName")
        or (doc.get("config") or {}).get("appName", ""),
        "checkpoint": (doc.get("notes") or {}).get("last_checkpoint"),
        "events": len(events),
        "events_dropped": doc.get("events_dropped", 0),
        "event_kinds": dict(kinds),
        "guard_counters": guard_counters,
        "health": doc.get("health") or {},
        "straggler": {
            "host": hosts.get("straggler", -1),
            "stage": hosts.get("stage", ""),
            "skew_ms": hosts.get("skew_ms", 0.0),
        } if hosts else None,
        # the minutes BEFORE death (ISSUE 20): the historian tail the
        # blackbox folded in — RSS/RTT trajectory and phase flips leading
        # up to the crash, not just the event ring
        "history": {
            "run_id": history.get("run_id"),
            "samples": len(hist_samples),
            "transitions": len(history.get("transitions") or []),
            "rss_mb": [s.get("rss_mb", 0.0) for s in hist_samples],
            "rtt_ms": [s.get("rtt_ms", 0.0) for s in hist_samples],
            "last_phase": (
                hist_samples[-1].get("phase", "") if hist_samples else ""
            ),
        } if hist_samples else None,
        "tail": events[-tail_events:],
    }


def render(s: dict) -> str:
    out = [
        f"post-mortem: {s['reason']}",
        f"  process {s['process_index']}"
        + (f" · app {s['app']}" if s["app"] else "")
        + f" · t={s['time_unix']}",
        f"  last checkpoint: {s['checkpoint'] or '(none recorded)'}",
        f"  events in ring: {s['events']} (+{s['events_dropped']} dropped)",
    ]
    if s["event_kinds"]:
        kinds = ", ".join(
            f"{k}={v}" for k, v in sorted(s["event_kinds"].items())
        )
        out.append(f"  event kinds: {kinds}")
    if s["guard_counters"]:
        out.append("  guard counters:")
        for k, v in sorted(s["guard_counters"].items()):
            out.append(f"    {k} = {v}")
    health = s["health"]
    if health:
        out.append(
            f"  fetch health: {health.get('phase', '?')} "
            f"(rtt {health.get('rtt_ms', 0)} ms, "
            f"{health.get('transitions', 0)} transitions)"
        )
    if s["straggler"] and s["straggler"]["host"] >= 0:
        st = s["straggler"]
        out.append(
            f"  lockstep straggler: host {st['host']} · {st['stage']} "
            f"(tick skew {st['skew_ms']} ms)"
        )
    if s.get("history"):
        h = s["history"]
        rss = h["rss_mb"]
        rss_arc = (
            f"{rss[0]:.0f} -> {rss[-1]:.0f} MB" if rss else "?"
        )
        out.append(
            f"  history tail (run {h['run_id']}): {h['samples']} sample(s)"
            f" before death · rss {rss_arc} · last phase "
            f"{h['last_phase'] or '?'} · {h['transitions']} phase flip(s)"
            " — full timeline: tools/history_report.py on the bundle or"
            " the run's history directory"
        )
    out.append("  last events:")
    for ev in s["tail"]:
        kind = ev.get("kind", "?") if isinstance(ev, dict) else "?"
        rest = {
            k: v for k, v in ev.items() if k not in ("kind", "t")
        } if isinstance(ev, dict) else {}
        out.append(f"    [{ev.get('t', '?')}] {kind} {json.dumps(rest)[:120]}")
    return "\n".join(out)


def main(argv=None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    as_json = "--json" in args
    args = [a for a in args if a != "--json"]
    tail = 12
    if "--events" in args:
        i = args.index("--events")
        tail = int(args[i + 1])
        del args[i : i + 2]
    if len(args) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    try:
        summary = summarize(load_bundle(args[0]), tail_events=tail)
    except (OSError, MalformedBundle) as exc:
        print(f"postmortem_report: malformed bundle: {exc}", file=sys.stderr)
        return 2
    if as_json:
        print(json.dumps(summary))
    else:
        print(render(summary))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
