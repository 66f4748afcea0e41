"""--recycleAfterMb (r5, VERDICT r4 #7): the RSS watchdog's diagnosis made
actionable — crossing the ceiling checkpoints at the next weights-current
boundary and re-execs the process in place. The test forces a recycle with a
1 MB ceiling (always exceeded) and proves, from the run's own logs, that the
post-restart state is BIT-identical to the pre-exec save (matching state
CRCs), counters resume exactly, and the run completes."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_replay(path, total=96):
    from twtml_tpu.streaming.sources import SyntheticSource

    with open(path, "w") as fh:
        for s in SyntheticSource(
            total=total, seed=11, base_ms=1785320000000
        ).produce():
            fh.write(json.dumps(s.to_json()) + "\n")


def test_auto_recycle_resumes_bit_identically(tmp_path):
    replay = tmp_path / "tweets.jsonl"
    _write_replay(replay)
    ckdir = tmp_path / "ck"
    closed = "http://127.0.0.1:9"
    env = dict(
        os.environ,
        PYTHONPATH=REPO,
        JAX_PLATFORMS="cpu",
        TWTML_RECYCLE_MAX="1",  # one recycle, then run to completion
        TWTML_RECYCLE_SAMPLE_EVERY="1",
    )
    proc = subprocess.run(
        [
            sys.executable, "-m", "twtml_tpu.apps.linear_regression",
            "--source", "replay", "--replayFile", str(replay),
            "--seconds", "0", "--backend", "cpu",
            "--batchBucket", "16", "--tokenBucket", "64",
            "--checkpointDir", str(ckdir),
            "--recycleAfterMb", "1",  # any real process exceeds 1 MB
            "--lightning", closed, "--twtweb", closed,
        ],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]

    recycles = re.findall(
        r"checkpointed at batch (\d+) \(count=(\d+), state crc ([0-9a-f]+)\)"
        r" and re-exec'ing", proc.stderr,
    )
    assert len(recycles) == 1, proc.stderr[-3000:]
    batch_r, count_r, crc_saved = (
        int(recycles[0][0]), int(recycles[0][1]), recycles[0][2],
    )

    resumes = re.findall(
        r"resumed from checkpoint step \d+ \(count=(\d+), state crc "
        r"([0-9a-f]+)\)", proc.stderr,
    )
    assert len(resumes) == 1, proc.stderr[-3000:]
    count_resumed, crc_restored = int(resumes[0][0]), resumes[0][1]

    # bit-identical post-restart state, exact counter resume
    assert crc_restored == crc_saved
    assert count_resumed == count_r

    # exact resume (ISSUE 19): the intake journal's boot replay
    # fast-forwards the re-exec'd process past every row the first life
    # journaled (SkipRowsSource) and re-ingests the post-cursor tail, so
    # the second life trains each row EXACTLY ONCE — the pre-journal
    # behavior re-read the whole file on top of the restored count
    boots = re.findall(
        r"journal: boot resume — (\d+) journaled row\(s\), (\d+) "
        r"fast-forwarded", proc.stderr,
    )
    assert len(boots) == 1, proc.stderr[-3000:]
    assert int(boots[0][0]) == int(boots[0][1])  # deterministic source
    stats = [
        ln for ln in proc.stdout.splitlines() if ln.startswith("count:")
    ]
    assert stats, proc.stdout[-2000:]
    final_count = int(re.findall(r"count: (\d+)", stats[-1])[0])
    assert final_count == 96

    from twtml_tpu.checkpoint import Checkpointer

    weights, meta = Checkpointer(str(ckdir)).restore()
    assert meta["count"] == final_count
    assert np.abs(np.asarray(weights)).sum() > 0


def test_recycle_refused_multihost(tmp_path, monkeypatch):
    """One host exec'ing away would desert the lockstep group — the flag
    must refuse loudly at startup in multi-host mode (apps/common)."""
    import jax
    import pytest

    from twtml_tpu.apps.common import ProcessRecycler
    from twtml_tpu.config import ConfArguments

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    conf = ConfArguments().parse([
        "--recycleAfterMb", "1024", "--checkpointDir", str(tmp_path),
    ])
    with pytest.raises(SystemExit, match="single-host"):
        ProcessRecycler(conf, ckpt=None, totals={"count": 0, "batches": 0})
