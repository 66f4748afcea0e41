"""Churn-proof elastic fleet storm driver (r20, ISSUE 17): launch an
N-host virtual lockstep fleet (CPU/gloo subprocesses of the real linear
app) under ONE fleet-wide ``--chaos`` spec — follower kills, LEAD kills,
sub-threshold pauses — and verify the elastic membership plane's whole
contract from the outside:

- exit codes: every ``peer.kill`` victim leaves with the chaos exit code
  (77), every survivor finishes clean — no aborts under survivable churn;
- epoch ladder: every reform's ``elastic epoch E formed`` line agrees
  across every member that logged it (one committed view per epoch);
- elections: each dead LEAD produces exactly one ``WON the election``
  winner fleet-wide (the deterministic successor — lowest live uid of the
  committed view — see streaming/membership.py);
- bit-matching continuations: every reform's resync CRC
  (``elastic resync: ... state crc``) is IDENTICAL on every member that
  joined that reform — the fleet restored the same verified bytes;
- counted losses: a killed replay-shard host's undeliverable rows show up
  in ``rows_lost_estimate`` on a survivor — never silent.

The driver is self-contained: it re-execs itself as the per-host worker
(``--worker``), so it needs nothing from tests/. The 8-host churn test
(tests/test_elastic_multiprocess.py, ``slow``) and the chaos-soak fleet
phase (tools/chaos_soak.py --fleetPhase) both drive ``run_storm``; CI's
election smoke runs the CLI's 2-host lead-kill default.

Usage: python tools/chaos_fleet.py [--hosts N] [--tweets T] [--chaos SPEC]
          [--workdir DIR] [--timeout S]
Prints one JSON line; exits non-zero on any violated invariant.
"""

from __future__ import annotations

import json
import os
import re
import socket
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

NOW_MS = 1785320000000
CLOSED = "http://127.0.0.1:9"  # closed port: telemetry Try paths, no DNS
PEER_KILL_EXIT_CODE = 77  # streaming/faults.py, asserted not imported:
# the driver must not import jax-adjacent modules before its workers fork

# the 2-host lead-kill smoke the CLI runs by default (CI election smoke):
# the launch lead dies at tick 4, the sole survivor must elect itself
DEFAULT_CHAOS = "peer.kill:uid=0:tick=4"


def _worker(argv: "list[str]") -> None:
    """Per-host entry (re-exec target): configure a CPU/gloo jax runtime
    sized by the driver, then run the REAL linear app with its own CLI —
    the same launch shape as tests/app_worker.py, owned by the tool."""
    pid, nprocs, port, ndev = (
        int(argv[0]), int(argv[1]), int(argv[2]), int(argv[3])
    )
    app_args = list(argv[5:])  # argv[4] is the app name ("linear")
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    from twtml_tpu.utils.backend import set_cpu_device_count_hint

    set_cpu_device_count_hint(ndev)
    app_args += [
        "--master", f"twtml://127.0.0.1:{port}",
        "--numProcesses", str(nprocs), "--processId", str(pid),
    ]
    from twtml_tpu.apps import linear_regression

    linear_regression.main(app_args)


def _free_port_range(span: int = 10) -> int:
    """A base port with ``span`` consecutive free ports: elastic reserves
    base (epoch-0 compat), base+1 (beacon), base+2+e (epoch e)."""
    for cand in range(29500, 61000, span + 3):
        socks, ok = [], True
        for off in range(span):
            s = socket.socket()
            try:
                s.bind(("127.0.0.1", cand + off))
                socks.append(s)
            except OSError:
                ok = False
                break
        for s in socks:
            s.close()
        if ok:
            return cand
    raise RuntimeError("no contiguous free port range found")


def _killed_uids(chaos: str, hosts: int) -> "list[int]":
    """The uids a fleet-wide ``--chaos`` spec hard-kills (peer.kill
    clauses; a selector-free kill takes the whole fleet)."""
    killed: "set[int]" = set()
    for clause in chaos.split(","):
        if not clause.strip().startswith("peer.kill"):
            continue
        m = re.search(r":uid=(\d+)", clause)
        killed.update([int(m.group(1))] if m else range(hosts))
    return sorted(killed)


def run_storm(
    hosts: int = 8,
    tweets: int = 1024,
    chaos: str = DEFAULT_CHAOS,
    workdir: "str | None" = None,
    batch_bucket: int = 16,
    token_bucket: int = 64,
    checkpoint_every: int = 2,
    ndev: int = 1,
    timeout_s: float = 600.0,
    seed: int = 5,
) -> dict:
    """Launch the fleet, apply the storm, collect and verify. Returns a
    result dict with ``ok``/``failures`` plus the parsed evidence (epoch
    ladder, election winners, per-reform CRC rounds, counted pauses)."""
    from twtml_tpu.streaming.sources import SyntheticSource

    workdir = workdir or tempfile.mkdtemp(prefix="chaos-fleet-")
    os.makedirs(workdir, exist_ok=True)
    replay = os.path.join(workdir, "tweets.jsonl")
    with open(replay, "w") as fh:
        for s in SyntheticSource(
            total=tweets, seed=seed, base_ms=NOW_MS
        ).produce():
            fh.write(json.dumps(s.to_json()) + "\n")

    base = _free_port_range()
    env = dict(
        os.environ, PYTHONPATH=REPO, TWTML_NOW_MS=str(NOW_MS),
        TWTML_LOCKSTEP_TIMEOUT_S="5", TWTML_ELASTIC_RESCUE_GRACE_S="2",
        # a loaded box can delay the rank-0 candidate's bind past the
        # default 0.3s stagger and hand the election to a higher rank —
        # widen the per-rank window so the storm's winner is deterministic
        TWTML_ELASTIC_ELECT_STAGGER_S="1.0",
    )
    args = [
        "linear", "--source", "replay", "--replayFile", replay,
        "--seconds", "0", "--backend", "cpu",
        "--batchBucket", str(batch_bucket),
        "--tokenBucket", str(token_bucket),
        "--checkpointDir", os.path.join(workdir, "ck"),
        "--checkpointEvery", str(checkpoint_every),
        "--elastic", "on", "--lightning", CLOSED, "--twtweb", CLOSED,
        "--chaos", chaos,
    ]
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker",
             str(i), str(hosts), str(base), str(ndev)] + args,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
        )
        for i in range(hosts)
    ]
    outs, errs, rcs = [], [], []
    try:
        for p in procs:
            try:
                o, e = p.communicate(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                p.kill()
                o, e = p.communicate()
                e += "\n[chaos_fleet] HOST TIMED OUT and was killed"
            outs.append(o)
            errs.append(e)
            rcs.append(p.returncode)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for i, e in enumerate(errs):
        with open(os.path.join(workdir, f"host-{i}.stderr"), "w") as fh:
            fh.write(e)

    killed = _killed_uids(chaos, hosts)
    failures: "list[str]" = []
    for uid, rc in enumerate(rcs):
        want = PEER_KILL_EXIT_CODE if uid in killed else 0
        if rc != want:
            failures.append(
                f"host {uid} exited {rc} (wanted {want}); tail: "
                f"{errs[uid][-500:]!r}"
            )

    # -- epoch ladder: one committed view per epoch, fleet-wide ----------
    per_epoch: "dict[int, set[str]]" = {}
    for e in errs:
        for num, members in re.findall(
            r"elastic epoch (\d+) formed: \d+ host\(s\) \[([^\]]*)\]", e
        ):
            per_epoch.setdefault(int(num), set()).add(members)
    epochs = []
    for num in sorted(per_epoch):
        views = per_epoch[num]
        if len(views) != 1:
            failures.append(f"epoch {num} formed with DIVERGENT views {views}")
        epochs.append(
            (num, [int(u) for u in next(iter(views)).split(",") if u.strip()])
        )

    # -- elections: one winner per dead lead, deterministic successor ----
    winners = [
        int(u) for e in errs for u in re.findall(r"uid (\d+) WON the election", e)
    ]
    expect_elections = 1 if 0 in killed else 0
    if len(winners) != expect_elections:
        failures.append(
            f"{len(winners)} election win(s) {winners} for "
            f"{expect_elections} dead lead(s)"
        )

    # -- bit-matching continuations: per-reform CRCs agree fleet-wide ----
    crc_per_host = [
        re.findall(r"elastic resync: .* state crc ([0-9a-f]+)", e)
        for e in errs
    ]
    rounds = max((len(c) for c in crc_per_host), default=0)
    crc_rounds = [
        [c[k] for c in crc_per_host if len(c) > k] for k in range(rounds)
    ]
    for k, crcs in enumerate(crc_rounds):
        if len(set(crcs)) != 1:
            failures.append(f"reform {k + 1} resync CRCs diverged: {crcs}")
    reforms = sum(1 for num, _m in epochs if num >= 1)  # epoch 0 is the
    # initial formation: it synchronizes state but logs no resync line
    if len(crc_rounds) < reforms:
        failures.append(
            f"{reforms} reform(s) but only {len(crc_rounds)} "
            f"resync round(s) logged"
        )

    # -- counted losses: a dead replay shard is never silently dropped --
    if killed and not any("rows_lost_estimate" in e for e in errs):
        failures.append(
            "hosts were killed but no survivor counted rows_lost_estimate"
        )

    # -- replay-exact reforms (ISSUE 19): on every reform, each survivor's
    # journal replay re-covers EXACTLY what the rescue threw away — its
    # own discarded in-flight rows plus its share of the rolled-back
    # post-checkpoint progress (global rows, evenly sharded across the
    # pre-reform members). The journal is on in every storm
    # (--checkpointDir implies --journal auto), so a missing replay line
    # means a loss site stayed counted instead of converted.
    replayed_rows = 0
    for uid, e in enumerate(errs):
        resyncs = re.findall(
            r"elastic resync: state from the lead's [a-z ]+ "
            r"\(count=\d+, batches=\d+, state crc [0-9a-f]+\)"
            r"(?: — (\d+) row\(s\) of post-checkpoint progress "
            r"rolled back)?", e,
        )
        replays = [
            int(r) for r in re.findall(
                r"journal: replayed (\d+) row\(s\) from cursor \d+ "
                r"after elastic", e,
            )
        ]
        resets = e.count("journal: reset on rejoin")
        if len(replays) + resets != len(resyncs):
            failures.append(
                f"host {uid}: {len(resyncs)} reform resync(s) but "
                f"{len(replays)} journal replay(s) + {resets} rejoin "
                f"reset(s) — a loss site stayed counted"
            )
            continue
        discarded = sum(
            int(r) for r in re.findall(
                r"elastic rescue: discarded \d+ in-flight.*?"
                r"\(~(\d+) row\(s\)\)", e,
            )
        )
        # each resync's rolled-back rows are global; this host's share is
        # 1/len(pre-reform members) (even synthetic shards, all-padding
        # ticks excluded from counts). epochs[k] is the view REFORM k+1
        # left — resync k's old view.
        rolled_share = sum(
            int(rolled or 0) // len(epochs[k][1])
            for k, rolled in enumerate(resyncs)
            if k < len(epochs)
        )
        if replays and sum(replays) != rolled_share + discarded:
            failures.append(
                f"host {uid}: replayed {sum(replays)} row(s) but the "
                f"rescue threw away {rolled_share + discarded} "
                f"(rolled share {rolled_share} + discarded {discarded}) "
                f"— recovery is not replay-exact"
            )
        replayed_rows += sum(replays)

    pauses = sum(e.count("chaos: peer.pause stalling") for e in errs)
    return {
        "mode": "chaos-fleet",
        "hosts": hosts,
        "tweets": tweets,
        "chaos": chaos,
        "workdir": workdir,
        "rcs": rcs,
        "killed": killed,
        "epochs": epochs,
        "elections": len(winners),
        "winners": winners,
        "crc_rounds": crc_rounds,
        "replayed_rows": replayed_rows,
        "pauses": pauses,
        "failures": failures,
        "ok": not failures,
    }


def main(argv=None) -> None:
    args = list(sys.argv[1:] if argv is None else argv)
    if args and args[0] == "--worker":
        _worker(args[1:])
        return
    hosts, tweets, chaos = 2, 256, DEFAULT_CHAOS
    workdir, timeout_s = None, 600.0
    i = 0
    while i < len(args):
        if args[i] == "--hosts":
            hosts = int(args[i + 1]); i += 2
        elif args[i] == "--tweets":
            tweets = int(args[i + 1]); i += 2
        elif args[i] == "--chaos":
            chaos = args[i + 1]; i += 2
        elif args[i] == "--workdir":
            workdir = args[i + 1]; i += 2
        elif args[i] == "--timeout":
            timeout_s = float(args[i + 1]); i += 2
        else:
            raise SystemExit(f"unknown flag {args[i]!r}")
    res = run_storm(
        hosts=hosts, tweets=tweets, chaos=chaos, workdir=workdir,
        timeout_s=timeout_s,
    )
    print(json.dumps(res))
    if not res["ok"]:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
