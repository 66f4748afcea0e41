"""Durable intake journal (ISSUE 19): crash-equals-clean replay recovery.

Three layers, mirroring the journal's own contract:

- **Framing/disk units**: CRC-framed records round-trip bit-exactly for
  both seam item kinds (Status objects, ParsedBlocks in both units
  dtypes); a torn tail (kill -9 mid-append) is truncated LOUDLY; mid-
  history corruption RAISES instead of silently under-replaying; segments
  rotate, retire under checkpoint coverage, and the --journalMaxMb
  ceiling drops oldest-first, counted.
- **Cursor semantics**: the committed cursor advances on DELIVERY (the
  fetch pipeline dispatches ahead of delivery, so the tail is not safe to
  stamp), replay arms suppression + re-bases the cursor, and saves are
  deferred while a replay drains.
- **End-to-end**: a SIGKILL'd run restarted from its checkpoint + journal
  ends with weights BIT-EQUAL to an unfailed control over the same file
  (the acceptance differential), `--journal off` is bit-exact pre-journal
  behavior, and the healthy path adds zero host fetches.
"""

from __future__ import annotations

import json
import os
import re
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from twtml_tpu.config import ConfArguments
from twtml_tpu.features.featurizer import Status
from twtml_tpu.streaming import journal as journal_mod
from twtml_tpu.streaming.journal import IntakeJournal
from twtml_tpu.telemetry import metrics as _metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLOSED = "http://127.0.0.1:9"


@pytest.fixture(autouse=True)
def _clean_metrics():
    _metrics.reset_for_tests()
    yield
    journal_mod.uninstall()
    _metrics.reset_for_tests()


def _statuses(n, tag="t", rt_every=3):
    out = []
    for i in range(n):
        rs = None
        if rt_every and i % rt_every == 0:
            rs = Status(
                text=f"original {tag} {i} é", retweet_count=i * 2,
                followers_count=100 + i, created_at_ms=1785310000000 + i,
                lang="fr", id=900000 + i,
            )
        out.append(Status(
            text=f"tweet {tag} {i} ünïcode", retweet_count=i,
            followers_count=10 + i, favourites_count=i % 7,
            friends_count=i % 5, created_at_ms=1785320000000 + i,
            retweeted_status=rs, lang="en", id=1000000 + i,
        ))
    return out


def _block(rows, dtype=np.uint8, seed=0):
    from twtml_tpu.features.blocks import ParsedBlock

    rng = np.random.RandomState(seed)
    numeric = rng.randint(0, 1000, size=(rows, 5)).astype(np.int64)
    lens = rng.randint(1, 9, size=rows)
    units = rng.randint(
        0, 255 if dtype == np.uint8 else 60000, size=int(lens.sum())
    ).astype(dtype)
    offsets = np.zeros(rows + 1, np.int64)
    offsets[1:] = np.cumsum(lens)
    ascii_col = (dtype == np.uint8) * np.ones(rows, np.uint8)
    return ParsedBlock(numeric, units, offsets, ascii_col)


# -- framing / disk units ----------------------------------------------------


def test_object_records_roundtrip_bit_parity(tmp_path):
    j = IntakeJournal(str(tmp_path / "j"))
    batches = [_statuses(16, "a"), _statuses(7, "b", rt_every=2)]
    for b in batches:
        j.append(b)
    j.close()
    j2 = IntakeJournal(str(tmp_path / "j"))
    assert j2.next_id == 2
    assert j2.rows_total == 23
    replayed = [items for _id, items in j2.records_from(0)]
    # dataclass equality over every field, recursively through
    # retweeted_status — what the featurizer reads is byte-identical
    assert replayed == batches


def test_block_records_roundtrip_both_dtypes(tmp_path):
    j = IntakeJournal(str(tmp_path / "j"))
    b8, b16 = _block(12, np.uint8, seed=1), _block(9, np.uint16, seed=2)
    j.append([b8])
    j.append([b16])
    assert j.rows_total == 21
    out = [items[0] for _id, items in j.records_from(0)]
    for orig, back in zip((b8, b16), out):
        assert back.units.dtype == orig.units.dtype
        np.testing.assert_array_equal(back.numeric, orig.numeric)
        np.testing.assert_array_equal(back.units, orig.units)
        np.testing.assert_array_equal(back.offsets, orig.offsets)
        np.testing.assert_array_equal(back.ascii, orig.ascii)


def test_torn_tail_truncated_loudly(tmp_path):
    d = str(tmp_path / "j")
    j = IntakeJournal(d)
    for i in range(3):
        j.append(_statuses(4, f"k{i}"))
    j.close()
    seg = [f for f in os.listdir(d) if f.endswith(".twj")]
    assert len(seg) == 1
    path = os.path.join(d, seg[0])
    size_before = os.path.getsize(path)
    # what a kill -9 mid-append leaves: a frame header + partial payload
    with open(path, "ab") as fh:
        fh.write(b"TWJL" + (9999).to_bytes(4, "little") + b"\x00" * 40)
    j2 = IntakeJournal(d)
    # every complete record survives, the torn bytes are gone, counted
    assert j2.next_id == 3
    assert j2.rows_total == 12
    assert os.path.getsize(path) == size_before
    assert _metrics.get_registry().counter(
        "journal.torn_tails").snapshot() == 1
    assert sum(len(it) for _i, it in j2.records_from(0)) == 12


def test_mid_history_corruption_raises(tmp_path):
    d = str(tmp_path / "j")
    # max_mb=4 -> segment_bytes floored to 1 MB; force rotation w/ big rows
    j = IntakeJournal(d, max_mb=4)
    big = [Status(text="x" * 300000, id=i) for i in range(8)]
    for s in big:
        j.append([s])  # ~300 KB/record -> rotates after ~4
    segs = sorted(f for f in os.listdir(d) if f.endswith(".twj"))
    assert len(segs) >= 2, "need a non-tail segment to corrupt"
    # flip a payload byte mid-way through the FIRST (non-tail) segment
    first = os.path.join(d, segs[0])
    with open(first, "r+b") as fh:
        fh.seek(os.path.getsize(first) // 2)
        b = fh.read(1)
        fh.seek(-1, os.SEEK_CUR)
        fh.write(bytes([b[0] ^ 0xFF]))
    with pytest.raises(RuntimeError, match="corrupt mid-history"):
        list(j.records_from(0))
    j.close()


def test_rotation_retirement_and_disk_ceiling(tmp_path):
    d = str(tmp_path / "j")
    j = IntakeJournal(d, max_mb=4)  # segment_bytes floored to 1 MB
    big = [Status(text="y" * 200000, id=i) for i in range(30)]
    for s in big:
        j.append([s])
    reg = _metrics.get_registry()
    # ~6 MB appended against a 4 MB hard ceiling: oldest segments
    # dropped loudly, disk stays bounded
    assert reg.counter("journal.segments_dropped").snapshot() >= 1
    assert j.disk_bytes() <= 4 * 1024 * 1024 + 1024 * 1024  # +active slack
    segs = sorted(f for f in os.listdir(d) if f.endswith(".twj"))
    assert len(segs) >= 2
    # retirement: a verified-checkpoint cursor past a whole segment
    # unlinks it (never the active tail segment)
    first_alive = int(re.match(r"seg-(\d+)\.twj", segs[0]).group(1))
    cursor_past_first = int(re.match(r"seg-(\d+)\.twj", segs[1]).group(1))
    retired = j.retire_covered(cursor_past_first)
    assert retired == 1
    assert first_alive not in [
        int(re.match(r"seg-(\d+)\.twj", f).group(1))
        for f in os.listdir(d) if f.endswith(".twj")
    ]
    # the active segment never retires, even with a cursor at the tail
    j.retire_covered(j.next_id)
    assert any(f.endswith(".twj") for f in os.listdir(d))
    j.close()


def test_replay_suppression_and_mixed_batch(tmp_path):
    j = IntakeJournal(str(tmp_path / "j"))
    a, b = _statuses(16, "a"), _statuses(16, "b")
    j.append(a)
    j.append(b)
    items, rows = j.replay_from(1)
    assert rows == 16 and [s.id for s in items] == [s.id for s in b]
    # the replayed rows re-cross the seam: the first 16 rows are NOT
    # re-appended, and a mixed batch (replayed head + fresh tail in one
    # drain) appends only the fresh tail
    fresh = _statuses(4, "c")
    j.append(b[:10])          # fully suppressed
    assert j.rows_total == 32
    j.append(b[10:] + fresh)  # 6 suppressed + 4 fresh appended
    assert j.rows_total == 36
    assert j.next_id == 3
    tail = list(j.records_from(2))
    assert [s.id for s in tail[0][1]] == [s.id for s in fresh]
    j.close()


# -- dispatch-token committed cursor -----------------------------------------


def test_committed_cursor_advances_on_delivery_not_dispatch(tmp_path):
    j = IntakeJournal(str(tmp_path / "j"))
    # two batches cross the seam (append + token push), none delivered:
    # the checkpoint stamp must NOT cover them
    j.append(_statuses(16, "a")); j.push_dispatch()
    j.append(_statuses(16, "b")); j.push_dispatch()
    assert j.snapshot_for_checkpoint() == {"cursor": 0, "rows": 0}
    # first delivery commits its own token only
    j.pop_dispatch(); j.note_delivered()
    assert j.snapshot_for_checkpoint() == {"cursor": 1, "rows": 16}
    # a delivery an admission filter skipped pops WITHOUT committing
    j.pop_dispatch()
    assert j.snapshot_for_checkpoint() == {"cursor": 1, "rows": 16}
    j.close()


def test_replay_rebases_cursor_and_defers_saves(tmp_path):
    j = IntakeJournal(str(tmp_path / "j"))
    for tag in "abc":
        j.append(_statuses(8, tag)); j.push_dispatch()
        j.pop_dispatch(); j.note_delivered()
    assert j.snapshot_for_checkpoint() == {"cursor": 3, "rows": 24}
    items, rows = j.replay_from(1)
    assert rows == 16
    # the restored weights cover [0, 1): saves hold until the replay drains
    assert j.snapshot_for_checkpoint() == {"cursor": 1, "rows": 8}
    assert not j.save_allowed
    # mid-replay batch: suppressed append, token is None -> no commit
    j.append(items[:8]); j.push_dispatch()
    j.pop_dispatch(); j.note_delivered()
    assert not j.save_allowed
    assert j.snapshot_for_checkpoint() == {"cursor": 1, "rows": 8}
    # the batch that drains suppression to zero pushes the REAL tail;
    # its delivery re-opens saves with every journaled row covered
    j.append(items[8:]); j.push_dispatch()
    j.pop_dispatch(); j.note_delivered()
    assert j.save_allowed
    assert j.snapshot_for_checkpoint() == {"cursor": 3, "rows": 24}
    j.close()


def test_shed_and_reform_token_hygiene(tmp_path):
    j = IntakeJournal(str(tmp_path / "j"))
    j.append(_statuses(8, "a")); j.push_dispatch()
    # single-host shed: the batch never dispatches — un-push, then the
    # next real delivery pairs with its own token
    j.drop_newest()
    j.append(_statuses(8, "b")); j.push_dispatch()
    j.pop_dispatch(); j.note_delivered()
    assert j.snapshot_for_checkpoint()["cursor"] == 2
    # elastic reform: in-flight deliveries discarded wholesale
    j.append(_statuses(8, "c")); j.push_dispatch()
    j.clear_inflight()
    j.pop_dispatch()  # a stray late pop finds an empty FIFO: no commit
    j.note_delivered()
    assert j.snapshot_for_checkpoint()["cursor"] == 2
    j.close()


# -- end-to-end --------------------------------------------------------------


def _write_corpus(path, total, seed):
    from twtml_tpu.streaming.sources import SyntheticSource

    with open(path, "w") as fh:
        for s in SyntheticSource(
            total=total, seed=seed, base_ms=1785320000000
        ).produce():
            fh.write(json.dumps(s.to_json()) + "\n")


BASE = [
    "--source", "replay", "--seconds", "0", "--backend", "cpu",
    "--batchBucket", "16", "--tokenBucket", "64", "--master", "local[1]",
    "--lightning", CLOSED, "--twtweb", CLOSED, "--webTimeout", "0.2",
]


def test_checkpoint_stamp_roundtrip_and_journal_off_bit_exact(tmp_path,
                                                              monkeypatch):
    """Healthy path: the save stamps the journal cursor into verified
    checkpoint meta (cursor == batches delivered, rows == rows trained),
    and --journal off produces BIT-identical weights and the same fetch
    count — the journal's healthy-path cost is host-disk only."""
    import jax

    from twtml_tpu.apps import linear_regression as app
    from twtml_tpu.checkpoint import Checkpointer

    jax.devices()
    monkeypatch.setenv("TWTML_NOW_MS", "1785320000000")
    path = tmp_path / "tweets.jsonl"
    _write_corpus(path, 6 * 16, seed=71)

    def run(ckdir, *extra):
        calls = {"n": 0}
        real = jax.device_get

        def counting(x):
            calls["n"] += 1
            return real(x)

        jax.device_get = counting
        try:
            totals = app.run(ConfArguments().parse(
                BASE + ["--replayFile", str(path), "--checkpointDir",
                        ckdir, "--checkpointEvery", "2", *extra]
            ))
        finally:
            jax.device_get = real
        return totals, calls["n"]

    d_on, d_off = str(tmp_path / "on"), str(tmp_path / "off")
    totals_on, fetches_on = run(d_on)
    stamp = Checkpointer(d_on).latest_meta()["journal"]
    assert stamp == {"cursor": 6, "rows": 6 * 16}
    assert journal_mod.get() is None  # run() uninstalls on the way out

    _metrics.reset_for_tests()
    totals_off, fetches_off = run(d_off, "--journal", "off")
    assert "journal" not in Checkpointer(d_off).latest_meta()
    assert (totals_on["count"], totals_on["batches"]) == (
        totals_off["count"], totals_off["batches"]) == (6 * 16, 6)
    # zero added host fetches on the healthy path (counted, the
    # measurement-integrity idiom)
    assert fetches_on == fetches_off
    w_on, _ = Checkpointer(d_on).restore()
    w_off, _ = Checkpointer(d_off).restore()
    np.testing.assert_array_equal(w_on, w_off)


_KILL_DRIVER = """
import os, signal, sys
sys.path.insert(0, {repo!r})
from twtml_tpu.checkpoint.checkpointer import Checkpointer
orig = Checkpointer.save
state = {{"n": 0}}
def save(self, step, weights, metadata=None):
    out = orig(self, step, weights, metadata)
    state["n"] += 1
    if state["n"] == 3:
        os.kill(os.getpid(), signal.SIGKILL)  # hard death mid-stream
    return out
Checkpointer.save = save
from twtml_tpu.apps import linear_regression as app
app.main(sys.argv[1:])
"""


def test_sigkill_restart_weights_equal_unfailed_control(tmp_path):
    """THE acceptance differential: a run SIGKILL'd mid-stream (right
    after its 3rd cadence save, queue and fetch pipeline full of
    in-flight rows) and restarted ends with weights np.array_equal to a
    control run that never failed — zero rows lost, zero double-trained,
    proven on the final checkpoint of each."""
    from twtml_tpu.checkpoint import Checkpointer

    corpus = tmp_path / "tweets.jsonl"
    _write_corpus(corpus, 12 * 16, seed=72)
    env = dict(
        os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
        TWTML_NOW_MS="1785320000000",
    )
    driver = tmp_path / "kill_driver.py"
    driver.write_text(_KILL_DRIVER.format(repo=REPO))
    ck_kill = str(tmp_path / "ck_kill")
    args = BASE + ["--replayFile", str(corpus), "--checkpointDir", ck_kill,
                   "--checkpointEvery", "1"]
    proc = subprocess.run(
        [sys.executable, str(driver), *args],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO,
    )
    assert proc.returncode == -signal.SIGKILL, proc.stderr[-3000:]
    saved = Checkpointer(ck_kill).latest_meta()
    assert saved is not None and saved["batches"] < 12  # died mid-stream

    # second life: plain restart, same flags — checkpoint restore +
    # journal boot replay + source fast-forward must reconstruct exactly
    proc2 = subprocess.run(
        [sys.executable, "-m", "twtml_tpu.apps.linear_regression", *args],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO,
    )
    assert proc2.returncode == 0, proc2.stderr[-3000:]
    assert "journal: boot resume" in proc2.stderr

    ck_ctrl = str(tmp_path / "ck_ctrl")
    proc3 = subprocess.run(
        [sys.executable, "-m", "twtml_tpu.apps.linear_regression",
         *(BASE + ["--replayFile", str(corpus), "--checkpointDir", ck_ctrl,
                   "--checkpointEvery", "1"])],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO,
    )
    assert proc3.returncode == 0, proc3.stderr[-3000:]

    w_kill, meta_kill = Checkpointer(ck_kill).restore()
    w_ctrl, meta_ctrl = Checkpointer(ck_ctrl).restore()
    assert meta_kill["count"] == meta_ctrl["count"] == 12 * 16
    assert meta_kill["batches"] == meta_ctrl["batches"] == 12
    np.testing.assert_array_equal(np.asarray(w_kill), np.asarray(w_ctrl))
