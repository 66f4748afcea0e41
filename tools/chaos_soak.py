"""Chaos soak: drive the flagship replay app back-to-back under the
transport fault injector (streaming/faults.ChaosInjector) and assert the
runtime guards hold up over time — the app-level companion of the unit
chaos tests (tests/test_chaos.py) and the endurance soaks (tools/soak.py).

Each round replays the same synthetic corpus through the full linear app
(FetchPipeline, checkpoints, telemetry) with chaos active on all three
injection points: fetch latency spikes + occasional fetch errors (the
watchdog's re-issue path), dispatch delays, and a flaky dashboard (the
publish circuit breaker's open/half-open cycle — the twtweb endpoint is a
closed port, so un-dropped publishes also fail fast). The run must
SURVIVE: every round trains the full corpus, counters prove the guards
fired (retries > 0, breaker failures > 0), and zero fetch aborts occur.

r7 adds a SOURCE-chaos phase (--sourcePhase, on by default: the budget
splits between the two phases): block-ingest rounds under source.garbage
(corrupt wire bytes the parser must skip-and-count), source.burst (rate
spikes into the bounded intake queue), and source.nan (poisoned labels →
the divergence sentinel's rollback-to-verified-checkpoint path). The
contract is survive-and-recover: every round completes, rollbacks fire
and RECOVER (no sentinel abort, no fetch abort), all three rules fire,
and row losses show up in counters (rows_lost / rows_dropped_parse /
rows_shed) — never silently.

r21 adds a JOURNAL phase (--journalPhase, on by default): one
poisoned-batch storm with the durable intake journal ON against a clean
no-chaos control over the same corpus, pinned clock. The sentinel's
rollback must land as a journal REPLAY — replayed rows > 0, zero rows
lost, zero torn tails — and the storm's final weights must be BIT-EQUAL
to the unfailed control's (crash-equals-clean, ISSUE 19). The source
phase inherits the same contract: its rollbacks must replay, not count
losses.

r20 adds a FLEET phase (--fleetPhase, on by default): one lead-kill
election storm through tools/chaos_fleet.py — ``--fleetHosts`` real
lockstep worker processes, the launch lead hard-killed mid-run, the
survivors expected to elect the deterministic successor, re-form, and
finish clean with fleet-agreeing resync CRCs and counted losses. The
storm's violated invariants fold into this soak's ``failures``.

On ANY invariant failure the soak collects the crash flight recorder's
post-mortem bundle (telemetry/blackbox.py — the apps install it per round)
into ``--artifactDir`` and prints its path, so a CI chaos failure is
diagnosable after the fact instead of being a dead stdout log.

Usage: python tools/chaos_soak.py [--minutes M] [--tweets N] [--chaos SPEC]
          [--sourceChaos SPEC] [--sourcePhase on|off]
          [--fleetPhase on|off] [--fleetHosts N] [--journalPhase on|off]
          [--artifactDir DIR]
Prints one JSON line at the end; exits non-zero on any violated invariant.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# survivable defaults: delays well under the fetch deadline, errors rare
# enough that the retry budget (3) never exhausts on one batch, a mostly
# dead dashboard to cycle the breaker through open/half-open/probe.
# Triggers sized to the default round (16384 tweets / 2048 = 8 batches —
# each round re-installs the injector, resetting its call counters).
DEFAULT_CHAOS = (
    "fetch:delay=0.5@5,fetch:error@7,step:delay=0.1@3,"
    "web:error@p0.8,seed=3"
)

# source-phase defaults: one poisoned batch per round (16384/2048 = 8
# batches; @6 lands mid-round after several verified checkpoint saves), a
# corrupted parse chunk, and a block-duplication burst into the bounded
# queue. All three are survivable by design: the sentinel rolls back and
# continues, the parser skips and counts, the queue blocks the producer.
DEFAULT_SOURCE_CHAOS = (
    "source.nan@6,source.garbage@4,source.burst:rows=1@5,seed=3"
)


def main(argv=None) -> None:
    args = list(sys.argv[1:] if argv is None else argv)
    minutes, n_tweets, chaos = 10.0, 16384, DEFAULT_CHAOS
    source_chaos, source_phase = DEFAULT_SOURCE_CHAOS, True
    fleet_phase, fleet_hosts = True, 2
    journal_phase = True
    artifact_dir = ""
    i = 0
    while i < len(args):
        if args[i] == "--minutes":
            minutes = float(args[i + 1]); i += 2
        elif args[i] == "--tweets":
            n_tweets = int(args[i + 1]); i += 2
        elif args[i] == "--chaos":
            chaos = args[i + 1]; i += 2
        elif args[i] == "--sourceChaos":
            source_chaos = args[i + 1]; i += 2
        elif args[i] == "--sourcePhase":
            source_phase = args[i + 1] == "on"; i += 2
        elif args[i] == "--fleetPhase":
            fleet_phase = args[i + 1] == "on"; i += 2
        elif args[i] == "--journalPhase":
            journal_phase = args[i + 1] == "on"; i += 2
        elif args[i] == "--fleetHosts":
            fleet_hosts = int(args[i + 1]); i += 2
        elif args[i] == "--artifactDir":
            artifact_dir = args[i + 1]; i += 2
        else:
            raise SystemExit(f"unknown flag {args[i]!r}")

    from twtml_tpu.apps import linear_regression as app
    from twtml_tpu.config import ConfArguments
    from twtml_tpu.streaming.sources import SyntheticSource
    from twtml_tpu.telemetry import metrics as _metrics

    tmp = tempfile.mkdtemp(prefix="chaos-soak-")
    replay = os.path.join(tmp, "tweets.jsonl")
    with open(replay, "w") as fh:
        for s in SyntheticSource(
            total=n_tweets, seed=5, base_ms=1785320000000
        ).produce():
            fh.write(json.dumps(s.to_json()) + "\n")

    closed = "http://127.0.0.1:9"  # closed port: fails fast when attempted
    conf_args = [
        "--source", "replay", "--replayFile", replay,
        "--seconds", "0", "--batchBucket", "2048", "--tokenBucket", "512",
        "--checkpointDir", os.path.join(tmp, "ck"), "--checkpointEvery", "4",
        "--lightning", closed, "--twtweb", closed,
        "--webTimeout", "0.5",
        # the transport phase REUSES its checkpoint dir: each round
        # restores the last round's counters and re-reads the whole file
        # on top (the endurance ledger below checks per-round deltas).
        # With the journal on, boot replay would correctly fast-forward
        # past the fully-journaled corpus and train 0 rows — so this
        # phase pins --journal off, which doubles as soak coverage for
        # the off path under transport chaos (the journal's own contract
        # has its dedicated phase)
        "--journal", "off",
        "--chaos", chaos,
    ]

    transport_s = minutes * 60.0 * (0.5 if source_phase else 1.0)
    deadline = time.time() + transport_s
    rounds, tweets, failures = 0, 0, []
    t0 = time.time()
    while time.time() < deadline:
        totals = app.run(ConfArguments().parse(list(conf_args)))
        rounds += 1
        # counters resume from the checkpoint each round, so check deltas
        if totals["count"] - tweets != n_tweets:
            failures.append(
                f"round {rounds} trained {totals['count'] - tweets} "
                f"of {n_tweets} tweets"
            )
            break
        tweets = totals["count"]

    # -- source-chaos phase (r7): block ingest + garbage/burst/nan -------
    from twtml_tpu.streaming import faults as _faults

    src_rounds, src_rollbacks = 0, 0
    if source_phase and not failures:
        _faults.uninstall_chaos()
        src_args = [
            "--source", "replay", "--replayFile", replay,
            "--ingest", "block",
            "--seconds", "0", "--batchBucket", "2048",
            "--tokenBucket", "512",
            "--maxQueueRows", str(4 * 2048),
            "--checkpointEvery", "2",
            "--lightning", closed, "--twtweb", closed,
            "--webTimeout", "0.5",
            "--chaos", source_chaos,
        ]
        deadline = time.time() + minutes * 60.0 * 0.5
        reg0 = _metrics.get_registry()
        while time.time() < deadline:
            # a FRESH checkpoint dir per round: the journal (on — the
            # sentinel's replay conversion is this phase's invariant now)
            # makes a reused dir an exact resume, which would correctly
            # train 0 new rows on round 2 — each round stands alone
            ck_src = os.path.join(tmp, f"ck-src-{src_rounds}")
            try:
                totals = app.run(ConfArguments().parse(
                    src_args + ["--checkpointDir", ck_src]
                ))
            except RuntimeError as exc:
                failures.append(
                    f"source-chaos round {src_rounds + 1} aborted: {exc}"
                )
                break
            src_rounds += 1
            if totals["count"] <= 0:
                failures.append(
                    f"source-chaos round {src_rounds} made no progress"
                )
                break
        snap = reg0.snapshot()["counters"]
        src_rollbacks = snap.get("model.rollbacks", 0)
        if src_rounds:
            if not src_rollbacks:
                failures.append("source.nan never drove a sentinel rollback")
            if snap.get("model.sentinel_aborts", 0):
                failures.append("sentinel aborted under survivable chaos")
            for rule in ("source.nan", "source.garbage", "source.burst"):
                if not snap.get(f"chaos.{rule}.injected", 0):
                    failures.append(f"{rule} never fired")
            # the sentinel's rollback is a REPLAY site now (ISSUE 19: the
            # intake journal is on — --checkpointDir implies --journal
            # auto), so a fired rollback must show replayed rows and ZERO
            # lost rows; garbled lines stay counted in rows_dropped_parse
            if not snap.get("journal.replayed_rows", 0):
                failures.append(
                    "rollbacks fired but journal.replayed_rows is 0 — "
                    "the rollback loss site stayed counted, not replayed"
                )
            if snap.get("model.rows_lost", 0):
                failures.append(
                    f"{snap['model.rows_lost']} row(s) lost to rollbacks "
                    "with the journal ON — recovery is not replay-exact"
                )
            if not snap.get("ingest.rows_dropped_parse", 0):
                failures.append(
                    "garbage fired but ingest.rows_dropped_parse is 0"
                )

    # -- journal phase (r21, ISSUE 19): crash-equals-clean ---------------
    # one poisoned-batch storm with the intake journal ON, against a
    # clean no-chaos control over the same corpus: the sentinel rollback
    # must convert into a journal replay (replayed rows > 0, ZERO rows
    # lost), and the storm's final weights must be BIT-EQUAL to the
    # control's — the whole crash-equals-clean contract in one
    # differential. The clock seam is pinned for the phase (featurize
    # freshness terms must match across the two runs).
    jr = {}
    if journal_phase and not failures:
        import numpy as np

        from twtml_tpu.checkpoint import Checkpointer

        _faults.uninstall_chaos()
        prior_now = os.environ.get("TWTML_NOW_MS")
        os.environ["TWTML_NOW_MS"] = "1785320000000"
        try:
            def jr_args(ck, spec):
                a = [
                    "--source", "replay", "--replayFile", replay,
                    "--seconds", "0", "--batchBucket", "2048",
                    "--tokenBucket", "512",
                    "--checkpointDir", os.path.join(tmp, ck),
                    "--checkpointEvery", "2",
                    "--lightning", closed, "--twtweb", closed,
                    "--webTimeout", "0.5",
                ]
                return a + (["--chaos", spec] if spec else [])

            before = _metrics.get_registry().snapshot()["counters"]
            storm = app.run(ConfArguments().parse(
                jr_args("ck-journal", "source.nan@6,seed=3")
            ))
            _faults.uninstall_chaos()
            after = _metrics.get_registry().snapshot()["counters"]
            clean = app.run(ConfArguments().parse(
                jr_args("ck-journal-clean", "")
            ))
            jr = {
                "replayed_rows": after.get("journal.replayed_rows", 0)
                - before.get("journal.replayed_rows", 0),
                "rows_lost": after.get("model.rows_lost", 0)
                - before.get("model.rows_lost", 0),
                "torn_tails": after.get("journal.torn_tails", 0)
                - before.get("journal.torn_tails", 0),
            }
            if storm["count"] != n_tweets or clean["count"] != n_tweets:
                failures.append(
                    f"journal phase trained {storm['count']} (storm) / "
                    f"{clean['count']} (control) of {n_tweets} tweets"
                )
            if not jr["replayed_rows"]:
                failures.append(
                    "journal phase: the poisoned batch never replayed"
                )
            if jr["rows_lost"]:
                failures.append(
                    f"journal phase: {jr['rows_lost']} row(s) lost — "
                    "recovery is not replay-exact"
                )
            if jr["torn_tails"]:
                failures.append(
                    f"journal phase: {jr['torn_tails']} torn tail(s) on "
                    "clean shutdown/reopen"
                )
            w_storm, m_storm = Checkpointer(
                os.path.join(tmp, "ck-journal")
            ).restore()
            w_clean, m_clean = Checkpointer(
                os.path.join(tmp, "ck-journal-clean")
            ).restore()
            jr["bit_equal"] = bool(
                m_storm["count"] == m_clean["count"]
                and np.array_equal(np.asarray(w_storm), np.asarray(w_clean))
            )
            if not jr["bit_equal"]:
                failures.append(
                    "journal phase: storm weights are not bit-equal to "
                    "the unfailed control — crash-equals-clean violated"
                )
        finally:
            if prior_now is None:
                os.environ.pop("TWTML_NOW_MS", None)
            else:
                os.environ["TWTML_NOW_MS"] = prior_now

    # -- fleet phase (r20): lead-kill election storm, real processes -----
    # one storm, not time-budgeted (~90 s at 2 hosts): the launch lead is
    # hard-killed mid-run and the survivors must elect the deterministic
    # successor, re-form, and finish clean — the whole membership
    # contract is verified from the OUTSIDE by tools/chaos_fleet.py
    # (exit codes, epoch ladder, one winner, fleet-agreeing resync CRCs,
    # counted losses), so its failures fold straight into this soak's
    fleet_res = None
    if fleet_phase and not failures:
        from tools.chaos_fleet import run_storm
        fleet_res = run_storm(
            hosts=fleet_hosts, tweets=128 * fleet_hosts,
            workdir=os.path.join(tmp, "fleet"),
        )
        failures.extend(f"fleet: {f}" for f in fleet_res["failures"])

    reg = _metrics.get_registry().snapshot()
    counters = reg["counters"]
    aborts = counters.get("fetch.aborts", 0)
    retries = counters.get("fetch.retries", 0)
    injected = counters.get("chaos.injected", 0)
    fetch_errors = counters.get("chaos.fetch.errors", 0)
    breaker_failures = counters.get("publish.web.failures", 0)
    if aborts:
        failures.append(f"{aborts} fetch abort(s) under survivable chaos")
    if not injected:
        failures.append("chaos injector never fired")
    if fetch_errors and retries < fetch_errors:
        # every injected fetch error must have been absorbed by a re-issue
        failures.append(
            f"{fetch_errors} injected fetch error(s) but only "
            f"{retries} watchdog retries"
        )

    # on any violated invariant, collect the flight recorder's post-mortem
    # bundle into the artifact dir — aborted rounds already dumped at the
    # abort funnel; force=True captures the terminal state either way
    postmortem = ""
    if failures:
        from twtml_tpu.telemetry import blackbox as _blackbox

        path = _blackbox.dump(
            f"chaos-soak invariant failure: {failures[0]}",
            out_dir=artifact_dir or tmp, force=True,
        )
        if path:
            postmortem = path
            print(f"chaos-soak post-mortem bundle: {path}", file=sys.stderr)

    print(json.dumps({
        "mode": "chaos-soak",
        "postmortem": postmortem,
        "minutes": round((time.time() - t0) / 60.0, 2),
        "rounds": rounds,
        "tweets": tweets,
        "source_rounds": src_rounds,
        "source_chaos": source_chaos if source_phase else "",
        "fleet_hosts": fleet_hosts if fleet_phase else 0,
        "fleet_elections": fleet_res["elections"] if fleet_res else 0,
        "fleet_epochs": [m for _e, m in fleet_res["epochs"]]
        if fleet_res else [],
        "sentinel_rollbacks": src_rollbacks,
        "journal": jr,
        "journal_replayed_rows": counters.get("journal.replayed_rows", 0),
        "rows_lost": counters.get("model.rows_lost", 0),
        "rows_dropped_parse": counters.get("ingest.rows_dropped_parse", 0),
        "rows_shed": counters.get("ingest.rows_shed", 0),
        "chaos": chaos,
        "chaos_injected": injected,
        "fetch_retries": retries,
        "fetch_aborts": aborts,
        "publish_failures": breaker_failures,
        "publish_dropped": counters.get("publish.web.dropped", 0),
        "series_shed": counters.get("publish.series_shed", 0),
        "health": _metrics.get_health_monitor().summary(),
        "failures": failures,
        "ok": not failures,
    }))
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
