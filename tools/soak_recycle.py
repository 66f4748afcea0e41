"""Recycle soak: demonstrate ``--recycleAfterMb`` against a REAL host-memory
growth (the unit test ``tests/test_recycler.py`` forces a 1 MB ceiling on
CPU; this soak runs the shipped linear-regression app on the attached
device, lets whatever the device client retains grow host RSS at its natural
rate, and proves the mechanism end-to-end: ceiling crossed -> checkpoint at a weights-current boundary ->
in-place re-exec -> bit-identical resume -> bounded per-life RSS).

Two phases over the same replay corpus (identical flags except the ceiling):

1. CALIBRATE: run the app with recycling off, sampling its RSS from the
   OUTSIDE (/proc/<pid>/statm, ~4 Hz) — yields the post-compile baseline
   and the corpus' natural retention growth.
2. DEMONSTRATE: ceiling = baseline + 60% of the measured growth (guaranteed
   to cross mid-file), TWTML_RECYCLE_MAX=1. The harness keeps sampling the
   SAME pid across the os.execv and asserts, from the run's own logs:
   exactly one recycle; save/restore state CRCs match (bit-identical
   weights); the final count equals count-at-recycle + corpus size (exact
   counter resume + full second replay, the documented replay-recycle
   semantics); and the re-exec actually reclaimed the retention (RSS cliff
   at the exec, every life bounded).

Usage: python tools/soak_recycle.py [--tweets N] [--batch B]
Prints one JSON line (machine-checkable; "ok": true is the soak passing).
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CLOSED = "http://127.0.0.1:9"  # closed port: telemetry stays best-effort-off


def _write_corpus(path: str, total: int) -> None:
    from twtml_tpu.streaming.sources import SyntheticSource

    with open(path, "w") as fh:
        for s in SyntheticSource(
            total=total, seed=11, base_ms=1785320000000
        ).produce():
            fh.write(json.dumps(s.to_json()) + "\n")


def _statm_mb(pid: int) -> float | None:
    try:
        with open(f"/proc/{pid}/statm") as fh:
            return int(fh.read().split()[1]) * (os.sysconf("SC_PAGE_SIZE") / 1e6)
    except (OSError, IndexError, ValueError):
        return None


class _AppRun:
    """Launch the app, drain stdout/stderr on threads, sample RSS at ~4 Hz
    until exit. The recycler re-execs IN PLACE (same pid), so one sample
    series spans every life; the exec shows up as an RSS cliff."""

    def __init__(self, argv, env):
        self.proc = subprocess.Popen(
            argv, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=env, cwd=REPO,
        )
        self.out_lines: list[str] = []
        self.err_lines: list[str] = []
        self.samples: list[tuple[float, float]] = []  # (t, rss_mb)
        self.first_stat_t: float | None = None
        self._threads = [
            threading.Thread(target=self._drain, args=(self.proc.stdout, True)),
            threading.Thread(target=self._drain, args=(self.proc.stderr, False)),
        ]
        for t in self._threads:
            t.daemon = True
            t.start()

    def _drain(self, pipe, is_out):
        for line in pipe:
            (self.out_lines if is_out else self.err_lines).append(line)
            if is_out and self.first_stat_t is None and line.startswith("count:"):
                self.first_stat_t = time.monotonic()

    def wait(self, timeout: float) -> int:
        deadline = time.monotonic() + timeout
        while self.proc.poll() is None:
            if time.monotonic() > deadline:
                self.proc.kill()
                self.proc.wait()
                raise TimeoutError("app run exceeded its budget")
            mb = _statm_mb(self.proc.pid)
            if mb is not None:
                self.samples.append((time.monotonic(), mb))
            time.sleep(0.25)
        for t in self._threads:
            t.join(timeout=10)
        return self.proc.returncode

    @property
    def stdout(self) -> str:
        return "".join(self.out_lines)

    @property
    def stderr(self) -> str:
        return "".join(self.err_lines)


def _app_argv(replay: str, ckdir: str, batch: int, ceiling_mb: int) -> list:
    argv = [
        sys.executable, "-m", "twtml_tpu.apps.linear_regression",
        "--source", "replay", "--replayFile", replay,
        "--seconds", "0", "--batchBucket", str(batch),
        # cadence 16: boundary drains (the recycler's only actuation
        # points) land ~8x per corpus at the default batch, so a ceiling
        # crossed mid-file recycles well before the file ends
        "--checkpointDir", ckdir, "--checkpointEvery", "16",
        "--lightning", CLOSED, "--twtweb", CLOSED,
    ]
    if ceiling_mb > 0:
        argv += ["--recycleAfterMb", str(ceiling_mb)]
    return argv


def main(argv=None) -> None:
    args = list(sys.argv[1:] if argv is None else argv)
    total, batch = 2_000_000, 16384
    i = 0
    while i < len(args):
        if args[i] == "--tweets":
            total = int(args[i + 1]); i += 2
        elif args[i] == "--batch":
            batch = int(args[i + 1]); i += 2
        else:
            raise SystemExit(f"unknown flag {args[i]!r}")

    import tempfile

    work = tempfile.mkdtemp(prefix="twtml-recycle-soak-")
    replay = os.path.join(work, "tweets.jsonl")
    t0 = time.monotonic()
    _write_corpus(replay, total)
    gen_s = time.monotonic() - t0
    # APPEND the repo to PYTHONPATH — operator modules on the existing
    # path keep precedence over same-named repo files
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        env["PYTHONPATH"] + os.pathsep + REPO
        if env.get("PYTHONPATH") else REPO
    )

    # ---- phase 1: calibrate the natural retention ----
    run_a = _AppRun(
        _app_argv(replay, os.path.join(work, "ck_a"), batch, 0), env
    )
    rc_a = run_a.wait(timeout=900)
    if rc_a != 0:
        print(json.dumps({"ok": False, "phase": "calibrate", "rc": rc_a,
                          "stderr_tail": run_a.stderr[-2000:]}))
        raise SystemExit(1)
    # post-compile baseline: first sample at/after the first stats line
    # (compile + device init are done once streaming starts)
    base = next(
        (mb for (t, mb) in run_a.samples
         if run_a.first_stat_t and t >= run_a.first_stat_t),
        run_a.samples[-1][1] if run_a.samples else 0.0,
    )
    # default=0.0: a sub-250ms crash leaves no samples, and the empty-max
    # ValueError would mask the {"ok": false} line below
    peak_a = max((mb for (_, mb) in run_a.samples), default=0.0)
    growth = peak_a - base
    if growth < 50.0:
        print(json.dumps({
            "ok": False, "phase": "calibrate", "rc": 0,
            "error": "retention growth below the 50 MB demo floor; "
                     "raise --tweets (or the transport stopped leaking)",
            "rss_base_mb": round(base, 1), "rss_peak_mb": round(peak_a, 1),
        }))
        raise SystemExit(1)

    # ---- phase 2: demonstrate the automatic recycle ----
    ceiling = int(base + 0.6 * growth)
    env_b = dict(env, TWTML_RECYCLE_MAX="1")
    run_b = _AppRun(
        _app_argv(replay, os.path.join(work, "ck_b"), batch, ceiling), env_b
    )
    rc_b = run_b.wait(timeout=1200)
    err = run_b.stderr
    ok = rc_b == 0
    recycles = re.findall(
        r"checkpointed at batch (\d+) \(count=(\d+), state crc ([0-9a-f]+)\)"
        r" and re-exec'ing", err,
    )
    resumes = re.findall(
        r"resumed from checkpoint step \d+ \(count=(\d+), state crc "
        r"([0-9a-f]+)\)", err,
    )
    ok &= len(recycles) == 1 and len(resumes) == 1
    crc_match = count_match = False
    count_r = 0
    if recycles and resumes:
        count_r = int(recycles[0][1])
        crc_match = resumes[0][1] == recycles[0][2]
        count_match = int(resumes[0][0]) == count_r
    stats = [l for l in run_b.out_lines if l.startswith("count:")]
    final_count = int(re.findall(r"count: (\d+)", stats[-1])[0]) if stats else -1
    full_resume = final_count == count_r + total

    # RSS cliff at the exec: largest single-step drop in the series
    drops = [
        (run_b.samples[j - 1][1] - run_b.samples[j][1], j)
        for j in range(1, len(run_b.samples))
    ]
    cliff_mb, j_cliff = max(drops) if drops else (0.0, 0)
    pre_exec_peak = max(
        (mb for (_, mb) in run_b.samples[:j_cliff]), default=0.0
    )
    post_exec_floor = run_b.samples[j_cliff][1] if drops else 0.0
    life2_peak = max(
        (mb for (_, mb) in run_b.samples[j_cliff:]), default=0.0
    )
    reclaimed = cliff_mb > 0.3 * max(pre_exec_peak, 1.0)
    # bounded: no life strays above ceiling + one full corpus' retention
    # (the recycler acts at the NEXT boundary, so one cadence of overshoot
    # is by design; life 2 replays the whole file under MAX=1)
    bound_mb = ceiling + growth + 256
    bounded = max(
        (mb for (_, mb) in run_b.samples), default=0.0
    ) <= bound_mb

    import shutil

    shutil.rmtree(work, ignore_errors=True)  # the corpus is ~350 MB/1M tweets
    ok &= crc_match and count_match and full_resume and reclaimed and bounded
    print(json.dumps({
        "ok": bool(ok), "metric": "recycle_soak", "tweets": total,
        "batch": batch, "corpus_gen_s": round(gen_s, 1),
        "calibrate": {
            "rss_base_mb": round(base, 1), "rss_peak_mb": round(peak_a, 1),
            "growth_mb": round(growth, 1),
            "retention_bytes_per_tweet": round(growth * 1e6 / total, 1),
        },
        "ceiling_mb": ceiling, "recycles": len(recycles),
        "crc_match": crc_match, "count_at_recycle": count_r,
        "final_count": final_count, "full_resume": full_resume,
        "exec_cliff_mb": round(cliff_mb, 1),
        "pre_exec_peak_mb": round(pre_exec_peak, 1),
        "post_exec_floor_mb": round(post_exec_floor, 1),
        "life2_peak_mb": round(life2_peak, 1),
        "bounded_under_mb": bound_mb, "bounded": bounded, "rc": rc_b,
    }))
    if not ok:
        sys.stderr.write(err[-3000:] + "\n")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
